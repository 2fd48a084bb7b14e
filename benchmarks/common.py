"""Shared benchmark utilities: timing, CSV rows, key generation.

Methodology (paper §5.1 analog, adapted to CPU): jit + warmup (compile
excluded), repeat until the median stabilizes, report median; keys are
unique random uint64 (key distribution does not affect throughput). The
roles of the paper's nvbench/Nsight are played by block_until_ready timing
and the dry-run HLO inspection respectively.
"""
from __future__ import annotations

import time
from typing import Callable, List

import numpy as np
import jax

from repro.core import hashing as H


def time_fn(fn: Callable, *args, warmup: int = 2, reps: int = 5,
            min_reps: int = 3) -> float:
    """Median wall seconds of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(max(reps, min_reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def keys_u64x2(n: int, seed: int = 0):
    import jax.numpy as jnp
    return jnp.asarray(H.random_u64x2(n, seed=seed))


def percentile(samples, q: float) -> float:
    """Tail-latency percentile with the nearest-rank (inverted-CDF)
    definition: the smallest sample s.t. at least q% of samples are <= it.
    Interpolating estimators (numpy's default) invent values between the
    two largest samples — exactly where p999 lives — so latency reporting
    uses rank statistics on actual observations. Thin wrapper over the
    single shared implementation in ``repro.telemetry.nearest_rank``
    (bench and service report tails from one definition)."""
    from repro.telemetry import nearest_rank
    return nearest_rank(samples, q)


def latency_summary(samples, unit: float = 1e6) -> dict:
    """{p50, p99, p999, mean, max, n} of a latency sample set, scaled by
    ``unit`` (seconds -> µs by default) — the replay harness's report row.
    Built on the telemetry :class:`~repro.telemetry.Histogram` so the
    bench report and the service's ``service.latency`` summaries share
    one implementation (empty input raises, as ``percentile`` always
    did)."""
    from repro.telemetry import Histogram
    h = Histogram("bench.latency", ())
    h.observe_many(samples)
    if h.n == 0:
        raise ValueError("percentile of an empty sample set")
    return h.summary(unit=unit)


class Csv:
    def __init__(self):
        self.rows: List[str] = []
        self.records: List[dict] = []

    def add(self, name: str, us_per_call: float, derived: str = "",
            n_ops: int = None, predicted_us: float = None):
        """One bench row. ``n_ops`` (ops per timed call) derives Mops for
        the machine-readable record so future PRs can diff throughput;
        ``predicted_us`` is the perfmodel's full prediction for the same
        call (records carrying it feed the warn-only model-sanity gate in
        benchmarks/run.py)."""
        row = f"{name},{us_per_call:.3f},{derived}"
        self.rows.append(row)
        rec = {"name": name, "us_per_call": round(float(us_per_call), 3),
               "derived": derived}
        if n_ops and us_per_call > 0:
            rec["mops"] = round(n_ops / us_per_call, 3)
        if predicted_us is not None:
            rec["predicted_us"] = round(float(predicted_us), 3)
        self.records.append(rec)
        print(row, flush=True)

    def header(self):
        print("name,us_per_call,derived", flush=True)

    def write_json(self, path: str):
        """Persist the perf trajectory: {meta, benches:[{name, us_per_call,
        mops?, derived}]} — the diffable artifact committed as BENCH_PR*.json
        and uploaded by the CI bench-json step."""
        import json
        import platform
        payload = {
            "meta": {
                "backend": jax.default_backend(),
                "interpret_mode": jax.default_backend() == "cpu",
                "python": platform.python_version(),
                "jax": jax.__version__,
            },
            "benches": self.records,
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {len(self.records)} bench records -> {path}",
              flush=True)
