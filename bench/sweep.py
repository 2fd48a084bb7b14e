"""Sweep the offered rate of a served cell, once, to find the highest rate
it sustains; the cell's traffic file then fixes a rate below it.

    python3 bench/sweep.py --workload ycsb_c.service --seeds 5,6,7 \\
        --rates 500,1000,2000,4000 --seconds 10

One process, one set-up per rate and seed (the compile cache makes the
later ones cheap). For each it prints the median and tail, how late the
generator ran, the host stalls, and whether the backlog grew: the median
latency of the window's last tenth of gets over that of its first tenth.
The last line gives, per rate, the median of each over the seeds and the
largest backlog growth.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run as bench_run
    bench_run._use_checkout_caches()
    from bench.lib import catalog
    from bench.lib import device as D
    from bench.lib import drive
    from bench.lib.stats import nearest_rank

    base = catalog.cell(args.workload)
    try:
        devices = D.require_tpu(base.chips)
    except D.NoAcceleratorError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    drv = catalog.kind(base.traffic["kind"])
    by_rate = {}
    for rate in [float(r) for r in args.rates.split(",")]:
        for seed in [int(x) for x in args.seeds.split(",")]:
            cell = copy.deepcopy(base)
            cell.traffic["arrivals"]["rate_per_s"] = rate
            run = drive.Run(cell=cell, seed=seed, seconds=args.seconds,
                            trace=False, devices=devices)
            t0 = time.perf_counter()
            state = drv.setup(run)
            setup_s = time.perf_counter() - t0
            gc.collect()          # as bench/lib/harness.py runs a window
            gc.freeze()
            win = drv.window(run, state)
            gc.unfreeze()
            lat = win.host["latency_s"]
            tenth = max(len(lat) // 10, 1)
            row = {
                "rate_per_s": rate, "seed": seed,
                "lookups_per_s": rate * cell.traffic["lookups_per_get"],
                "gets": int(win.attempted), "failed": int(win.failed),
                "get_p50_ms": nearest_rank(lat, 50.0) * 1e3,
                "get_p99_ms": win.host["get_p99_ms"],
                "lag_p99_ms": nearest_rank(win.host["lag_s"], 99.0) * 1e3,
                "backlog_growth": float(np.median(lat[-tenth:])
                                        / np.median(lat[:tenth])),
                "occupancy": win.host["counters"]["flushed_ops"]
                / max(win.host["counters"]["flushes"], 1)
                / win.host["max_batch"],
                "stalls": win.host["stalls"],
                "window_s": win.seconds, "setup_s": setup_s}
            print(json.dumps(row), flush=True)
            by_rate.setdefault(rate, []).append(row)
            del state
    summary = {}
    for rate, rows in by_rate.items():
        summary[rate] = {
            k: float(np.median([r[k] for r in rows]))
            for k in ("get_p50_ms", "get_p99_ms", "lag_p99_ms")}
        summary[rate]["backlog_growth_max"] = max(r["backlog_growth"]
                                                  for r in rows)
        summary[rate]["failed"] = sum(r["failed"] for r in rows)
    print(json.dumps({"workload": base.name, "per_rate": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
