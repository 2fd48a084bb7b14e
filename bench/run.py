"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. The run
builds the filter system (``src/repro``) at the cell's configuration,
warms every shape its traffic uses, measures for ``--seconds``, checks
what the measured path produced against the plain reference, and prints
one JSON object as the last line of standard output. ``--trace 1``
profiles the window and reports the cell's per-layer metrics instead of
its end-to-end ones.

It runs only on a TPU: with no TPU, or fewer chips than the cell asks
for, it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")


def _use_checkout_caches() -> None:
    """Every compile and tuning cache in a fixed place inside the
    checkout: the path is part of a compile cache entry's key, and two
    checkouts never share one."""
    os.makedirs(os.path.join(CACHE, "jax"), exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ["REPRO_TUNING_CACHE"] = os.path.join(CACHE, "tuning.json")
    os.environ["REPRO_CALIB_CACHE"] = os.path.join(CACHE, "calibration.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    _use_checkout_caches()
    from bench.lib import catalog
    from bench.lib import device as D

    cell = catalog.cell(args.workload)
    try:
        devices = D.require_tpu(cell.chips)
    except D.NoAcceleratorError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench.lib.harness import run_cell
    run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
             T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
