"""What every generator kind (``bench/kinds/<kind>.py``) shares: the run it
is given, the window it returns, the program's filters, and the key
sources (``bench/keys/<source>.py``) it draws its keys from.

A kind has three steps:

* ``setup(run)`` builds the system under test from the configuration,
  makes the inputs from the seed, and warms every shape the window uses;
* ``window(run, state)`` drives the timed path for ``run.seconds`` and
  returns a :class:`Window`;
* ``check(run, state, window)`` compares what the timed path produced
  with the plain reference (``bench/lib/reference.py``), after the
  window, and returns each number compared with its limit.

The system under test is the ``repro`` package; only the kinds and this
module call it.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.lib import catalog
from bench.lib import reference as R


@dataclasses.dataclass
class Run:
    cell: object                 # catalog.Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    # settings of the program that differ from the configuration: empty
    # in the benchmark's runs, a lower guarantee in the control's
    program: dict = dataclasses.field(default_factory=dict)
    info: List[Tuple[str, object]] = dataclasses.field(default_factory=list)

    def note(self, label: str, value) -> None:
        self.info.append((label, value))


@dataclasses.dataclass
class Window:
    t0: float                    # perf_counter at the first timed call
    t1: float                    # ... at the end of the window's last work
    kind: str                    # "lookup", "build" or "service"
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    keys: int = 0                # keys the window's calls carried
    bytes_needed: int = 0        # by bench/lib/work.py, 0 where not counted
    host: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def spec(cfg: dict) -> Tuple[int, int, int]:
    """(m_bits, k, block_bits) of a configuration."""
    return int(cfg["m_bits"]), int(cfg["k"]), int(cfg["block_bits"])


@jax.jit
def count_true(x):
    return jnp.sum(x.astype(jnp.int32))


@jax.jit
def count_differing(a, b):
    return jnp.sum((a != b).astype(jnp.int32))


@partial(jax.jit, static_argnums=(1, 2, 3))
def _touched_blocks(keys, m_bits: int, k: int, block_bits: int):
    blk = R.key_bits(keys, m_bits, k, block_bits)[0]
    return count_true(jnp.zeros((m_bits // block_bits,), bool).at[blk].set(
        True))


def distinct_blocks(keys, cfg: dict) -> int:
    """Distinct blocks a batch of keys touches (for the bytes it needs)."""
    return int(_touched_blocks(keys, *spec(cfg)))


def filter_kwargs(run: Run) -> dict:
    """The program's filter settings: the configuration's, with the
    run's overrides."""
    cfg = dict(run.cell.config, **run.program)
    return dict(variant=cfg["variant"], m_bits=int(cfg["m_bits"]),
                k=int(cfg["k"]), block_bits=int(cfg["block_bits"]),
                backend=cfg.get("backend", "auto"))


def make_filter(run: Run):
    """An empty filter of the configuration, through the program's API."""
    from repro import api
    return api.make_filter(**filter_kwargs(run))


def dispatched() -> dict:
    from repro.kernels import ops
    return {f"{op}:{impl}": n for (op, impl), n in sorted(
        ops.dispatched().items())}


def key_spec(run: Run, role: str) -> dict:
    """The key source of ``role`` ("build", "probe", "records", ...): the
    traffic mix's ``keys`` entry, else the configuration's."""
    cfg, tr = run.cell.config, run.cell.traffic
    merged = dict(cfg.get("keys", {}), **tr.get("keys", {}))
    if role not in merged:
        raise KeyError(f"neither {run.cell.config_name!r} nor "
                       f"{run.cell.traffic_name!r} names the {role!r} keys")
    return merged[role]


def key_batches(run: Run, role: str, batch: int, count: int) -> list:
    """``count`` batches of ``batch`` keys ((batch, 2) uint32 on the run's
    first device) from the key source that ``role`` names."""
    ks = key_spec(run, role)
    source = catalog.plugin("keys", ks["source"])
    out = source.batches(run, role, ks, batch, count)
    return [jax.device_put(b, run.devices[0]) for b in out]
