"""Readings of the numbers a cell compares, on many seeds in one process.

    python3 bench/readings.py --workload <cell> --seeds 11,12,13 --seconds 3
    python3 bench/readings.py --workload <cell> --seeds 21,22,23 --seconds 3 \\
        --control

Without ``--control`` it runs the program as the configuration states and
gives the lower readings; with it, the program runs the control's lower
guarantee (half the configuration's bits per key, ``k/2``, while the
reference keeps ``k``), and gives the upper readings. Each seed prints the
harness's lines; the last line sums up every seed's compared numbers. The
benchmark's own runs (``bench/run.py``) never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def control_program(config: dict) -> dict:
    """The control's settings of the program: half the bits per key."""
    return {"k": max(int(config["k"]) // 2, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run as bench_run
    bench_run._use_checkout_caches()
    from bench.lib import catalog
    from bench.lib import device as D

    cell = catalog.cell(args.workload)
    try:
        devices = D.require_tpu(cell.chips)
    except D.NoAcceleratorError as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench.lib.harness import run_cell
    program = control_program(cell.config) if args.control else None
    summary = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        result = run_cell(cell, seed, args.seconds, False, devices,
                          time.perf_counter(), program=program)
        summary[seed] = {"correct": result["correct"],
                         **{k: v["value"] for k, v in
                            result["checks"].items()}}
        gc.collect()                 # the last seed's device buffers
    print(json.dumps({"workload": cell.name, "control": args.control,
                      "program": program, "readings": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
