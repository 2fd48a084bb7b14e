"""Measured machine calibration for the filter performance model.

The model (:mod:`repro.perfmodel.model`) produces machine-independent
resource counts; this module supplies the five machine constants that turn
counts into wall time:

* ``bw_hbm_gbs``   — streaming main-memory bandwidth (GB/s), measured by
  summing an array much larger than the last-level cache;
* ``bw_res_gbs``   — cache/VMEM-resident gather bandwidth (GB/s), measured
  by a dependent gather loop over a table that fits the fast tier;
* ``gops``         — elementwise u32 ALU rate (Gop/s), measured by a
  dependent multiply-add chain (nothing for the compiler to hoist);
* ``launch_us``    — per dispatched program overhead, measured by timing a
  trivially small jitted op;
* ``step_us``      — per schedule vector-op overhead (interpret mode: the
  Python dispatch cost per kernel-body op, the dominant term off-TPU;
  on TPU: the per-grid-step issue cost).

``get_calibration()`` is cheap by default: it returns the disk-cached
measurement for this device kind if one exists, else the conservative
per-device-kind defaults — it never measures unless asked
(``measure=True`` or ``REPRO_CALIB_MEASURE=1``), so library code (the
drift monitor, the cuckoo and quotient ``coop`` choice) can call it at
trace time without timing anything. ``get_calibration(measure=True)``
measures once and persists the result (``REPRO_CALIB_CACHE`` env var,
default ``~/.cache/repro/calibration.json``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

_SCHEMA = 1


@dataclasses.dataclass(frozen=True)
class Calibration:
    """One host's practical speed-of-light constants (see module doc)."""

    backend: str                 # device kind the constants describe
    bw_hbm_gbs: float
    bw_res_gbs: float
    gops: float
    launch_us: float
    step_us: float
    measured: bool = False

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["schema"] = _SCHEMA
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Calibration":
        if int(d.get("schema", 0)) != _SCHEMA:
            raise ValueError(f"calibration schema {d.get('schema')!r}")
        return cls(backend=str(d["backend"]),
                   bw_hbm_gbs=float(d["bw_hbm_gbs"]),
                   bw_res_gbs=float(d["bw_res_gbs"]),
                   gops=float(d["gops"]),
                   launch_us=float(d["launch_us"]),
                   step_us=float(d["step_us"]),
                   measured=bool(d.get("measured", False)))


# Uncalibrated constants, keyed by ``jax.devices()[0].device_kind``. A
# device kind not listed here is an error, never a borrowed default.
# "TPU v5 lite" (TPU v5e): HBM 819 GB/s from Google Cloud's "TPU v5e"
# documentation; the VMEM gather bandwidth, VPU u32 rate and overheads are
# order-of-magnitude placeholders until measured on the chip. "cpu": a
# mid-range server core running jnp ops (and the large interpret-mode step
# cost), not measured. A measured calibration supersedes either.
_DEFAULTS = {
    "TPU v5 lite": dict(bw_hbm_gbs=819.0, bw_res_gbs=8000.0, gops=4000.0,
                        launch_us=3.0, step_us=0.5),
    "cpu": dict(bw_hbm_gbs=12.0, bw_res_gbs=40.0, gops=8.0,
                launch_us=50.0, step_us=150.0),
}


def device_kind() -> str:
    return jax.devices()[0].device_kind


def _defaults(kind: str) -> dict:
    if kind not in _DEFAULTS:
        raise ValueError(f"no calibration defaults for device kind "
                         f"{kind!r}; known: {sorted(_DEFAULTS)}")
    return dict(_DEFAULTS[kind])


def default_calibration(kind: str | None = None) -> Calibration:
    """Unmeasured constants for ``kind`` (default: this process's device)."""
    kind = kind or device_kind()
    return Calibration(backend=kind, measured=False, **_defaults(kind))


def cache_path() -> str:
    return os.environ.get(
        "REPRO_CALIB_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "calibration.json"))


def _load_disk() -> dict:
    try:
        with open(cache_path()) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def _store_disk(key: str, value: dict) -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        data = _load_disk()
        data[key] = value
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass                       # cache is an optimization, never an error


def _best_of(fn, reps: int = 3) -> float:
    """Minimum post-warmup wall time — the standard noise-floor estimator."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def measure_bw_hbm(n_bytes: int = 1 << 25) -> float:
    """Streaming GB/s: one pass (read) over an array >> LLC."""
    x = jnp.arange(n_bytes // 4, dtype=jnp.uint32)
    f = jax.jit(lambda a: a.sum())
    t = _best_of(lambda: f(x))
    return n_bytes / t / 1e9


def measure_bw_res(table_bytes: int = 1 << 16, n_gather: int = 1 << 20
                   ) -> float:
    """Cache-resident gather GB/s: random gathers over a fast-tier table."""
    table = jnp.arange(table_bytes // 4, dtype=jnp.uint32)
    idx = jnp.asarray(
        np.random.default_rng(0).integers(0, table_bytes // 4, n_gather),
        jnp.int32)
    f = jax.jit(lambda t, i: jnp.take(t, i, axis=0).sum())
    t = _best_of(lambda: f(table, idx))
    return 4.0 * n_gather / t / 1e9


def measure_gops(width: int = 1 << 13, iters: int = 512) -> float:
    """Dependent u32 multiply-add chain, Gop/s (2 ops per lane-iter)."""
    x = jnp.arange(width, dtype=jnp.uint32)

    def chain(v):
        def body(_, a):
            return a * jnp.uint32(2654435761) + jnp.uint32(0x9E3779B9)
        return jax.lax.fori_loop(0, iters, body, v)

    f = jax.jit(chain)
    t = _best_of(lambda: f(x))
    return 2.0 * width * iters / t / 1e9


def measure_launch_us(calls: int = 50) -> float:
    """Per-dispatch overhead: a trivially small jitted op, amortized."""
    x = jnp.zeros((8,), jnp.uint32)
    f = jax.jit(lambda a: a + jnp.uint32(1))
    jax.block_until_ready(f(x))
    t0 = time.perf_counter()
    for _ in range(calls):
        jax.block_until_ready(f(x))
    return (time.perf_counter() - t0) / calls * 1e6


def measure_step_us(grid: int = 16) -> float:
    """Per schedule vector-op cost from a trivial Pallas kernel: the time
    difference between a ``grid``-step and a 1-step launch, divided by the
    extra body executions (each body issues ~one vector op)."""
    from jax.experimental import pallas as pl
    from repro.kernels import ops

    interpret = ops._interpret()

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] + jnp.uint32(1)

    def make(g):
        # jitted, like every real kernel call the model predicts — eager
        # pallas re-traces per call and would overstate the step cost by
        # orders of magnitude.
        call = pl.pallas_call(
            kern, grid=(g,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((8 * g, 128), jnp.uint32),
            interpret=interpret)
        return jax.jit(call), jnp.zeros((8 * g, 128), jnp.uint32)

    f_many, x_many = make(grid)
    f_one, x_one = make(1)
    t_many = _best_of(lambda: f_many(x_many), reps=5)
    t_one = _best_of(lambda: f_one(x_one), reps=5)
    return max(t_many - t_one, 0.0) / (grid - 1) * 1e6


def measure_calibration() -> Calibration:
    """Run the full microbench (~a second on CPU). On the CPU a probe that
    fails keeps the default for that constant; on any accelerator a failed
    probe raises, because predicting on unmeasured constants there
    would hide the failure."""
    from repro.kernels import ops
    kind = device_kind()
    base = _defaults(kind)
    probes = {
        "bw_hbm_gbs": measure_bw_hbm,
        "bw_res_gbs": measure_bw_res,
        "gops": measure_gops,
        "launch_us": measure_launch_us,
        "step_us": measure_step_us,
    }
    for name, fn in probes.items():
        try:
            v = float(fn())
        except Exception:
            if not ops._interpret():
                raise
            continue               # CPU: keep the default for this constant
        if np.isfinite(v) and v > 0:
            base[name] = v
    return Calibration(backend=kind, measured=True, **base)


def get_calibration(measure: bool | None = None) -> Calibration:
    """The calibration for this device kind: disk-cached measurement if
    one exists, else (``measure`` falsy) the conservative defaults, else a
    fresh measurement persisted to the disk cache."""
    kind = device_kind()
    key = f"calib|{_SCHEMA}|{kind}"
    cached = _load_disk().get(key)
    if cached is not None:
        try:
            return Calibration.from_dict(cached)
        except (KeyError, ValueError, TypeError):
            pass                   # stale/corrupt entry: fall through
    if measure is None:
        measure = os.environ.get("REPRO_CALIB_MEASURE", "") == "1"
    if not measure:
        return default_calibration(kind)
    calib = measure_calibration()
    _store_disk(key, calib.to_dict())
    return calib
