"""Everything the harness runs is found by name, from ``BENCHMARK.json``
and the data files it names:

* a cell is an entry of its ``workloads``;
* its configuration is ``bench/configs/<config>.json``;
* its traffic mix is ``bench/traffic/<traffic>.json``, data read by the
  generator its ``kind`` names, ``bench/kinds/<kind>.py``;
* the keys a configuration or mix asks for come from the key source each
  names, ``bench/keys/<source>.py``;
* a served mix's arrival times come from ``bench/arrivals/<process>.py``,
  and the records its requests ask for from ``bench/choosers/<name>.py``;
* a per-layer metric is read by ``bench/layers/<metric>.py``, whose
  ``read(ctx)`` returns a number, or None where it finds nothing to read.

Adding a cell, configuration, mix, key source, arrival process, chooser,
generator kind or per-layer metric is adding files and entries; no file
the harness already has changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")


def load_benchmark(path: str = BENCHMARK_JSON) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _load_json("configs", name)


def load_traffic(name: str) -> dict:
    return _load_json("traffic", name)


_MODULES: Dict[str, ModuleType] = {}


def plugin(kind: str, name: str) -> ModuleType:
    """The module ``bench/<kind>/<name>.py``, loaded once."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if path not in _MODULES:
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {kind} module named {name!r} "
                                    f"({path})")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"),
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _MODULES[path] = module
    return _MODULES[path]


def names(kind: str) -> List[str]:
    """The names of the modules under ``bench/<kind>/``."""
    return sorted(f[:-3] for f in os.listdir(os.path.join(BENCH_DIR, kind))
                  if f.endswith(".py") and not f.startswith("_"))


def reader(metric: str) -> Callable:
    """The ``read`` function of ``bench/layers/<metric>.py``."""
    return plugin("layers", metric).read


def kind(name: str) -> ModuleType:
    """The generator of a traffic mix's ``kind``: ``bench/kinds/<name>.py``,
    with ``setup(run)``, ``window(run, state)`` and
    ``check(run, state, window)``."""
    return plugin("kinds", name)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_names


def cell(name: str, bench: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic and metrics."""
    bench = bench or load_benchmark()
    by_name: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(by_name)}")
    w = by_name[name]
    return make_cell(name, w["config"], w["traffic"], int(w["chips"]), bench)


def make_cell(name: str, config: str, traffic: str, chips: int,
              bench: dict) -> Cell:
    """A cell of ``config`` under ``traffic``, with the metrics that
    ``bench`` gives a cell of that name."""
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=chips, config_name=config,
                traffic_name=traffic, config=load_config(config),
                traffic=load_traffic(traffic), end_to_end=e2e,
                per_layer=per_layer)
