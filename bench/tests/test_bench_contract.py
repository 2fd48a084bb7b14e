"""BENCHMARK.json: its shape, and every name in it resolving to a file."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench.lib import catalog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return catalog.load_benchmark()


def _one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][0] == "python3"
    assert bench["paths"] == ["bench"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) <= 64 * 1024


def test_command_stays_inside_paths(bench):
    for word in bench["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in bench["paths"])
            assert os.path.isfile(os.path.join(catalog.ROOT, word))


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(names) == len(set(names))
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _one_line(c["why"])
        assert _one_line(c["source"])
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["file"] not in files
        files.add(c["file"])
        cfg = catalog.load_config(c["name"])
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert cfg["guarantees"]


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metric_entries(bench, kind):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench[kind]:
        keys = {"name", "unit", "better", "source"}
        keys |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
        assert set(m) - {"workloads"} == keys, m["name"]
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert _one_line(m["layer"]) and m["moves"] in e2e
            assert m["workloads"]


def test_metric_names_unique(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


def test_roofline_names(bench):
    for m in bench["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_every_cell_resolves(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(names) // 2)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _one_line(w["why"])
        cell = catalog.cell(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert cell.traffic["kind"] in catalog_kinds()
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in e2e


def catalog_kinds():
    return set(catalog.names("kinds"))


def test_every_per_layer_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        assert callable(catalog.reader(m["name"]))


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        catalog.cell("no_such.cell")
    with pytest.raises(FileNotFoundError):
        catalog.reader("no_such_metric")


def _named(kind, entry, key):
    return {(kind, entry[key])} if isinstance(entry, dict) and key in \
        entry else set()


def test_every_plugin_named_by_the_data_resolves(bench):
    """Each key source, arrival process and chooser that a configuration
    or mix names is a module of its own, with the function the kinds
    call."""
    wanted = set()
    for w in bench["workloads"]:
        cell = catalog.cell(w["name"], bench)
        for entry in list(cell.config.get("keys", {}).values()) + list(
                cell.traffic.get("keys", {}).values()):
            wanted |= _named("keys", entry, "source")
        wanted |= _named("arrivals", cell.traffic.get("arrivals"),
                         "process")
        wanted |= _named("choosers", cell.traffic.get("records"),
                         "chooser")
    assert wanted
    function = {"keys": "batches", "arrivals": "due", "choosers": "draw"}
    for kind, name in wanted:
        assert callable(getattr(catalog.plugin(kind, name), function[kind]))


@pytest.mark.parametrize("kind,function", [
    ("kinds", "check"), ("keys", "batches"), ("arrivals", "due"),
    ("choosers", "draw"), ("layers", "read")])
def test_every_module_of_a_plugin_directory_loads(kind, function):
    names = catalog.names(kind)
    assert names
    for name in names:
        module = catalog.plugin(kind, name)
        assert callable(getattr(module, function)), (kind, name)
        if kind == "kinds":
            assert callable(module.setup) and callable(module.window)
    with pytest.raises(FileNotFoundError):
        catalog.plugin(kind, "no_such_module")
