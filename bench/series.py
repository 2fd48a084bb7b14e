"""A set of runs of one cell, each a process of its own, one after another,
and the spread of each metric over the set.

    python3 bench/series.py --workload <cell> --seeds 1,2,3,4,5,6 \\
        --seconds 10 [--trace 1] [--out out/set1.jsonl]

Each run is ``bench/run.py`` exactly as the benchmark's command runs it.
The result lines go to ``--out`` (one JSON object per run, with its seed,
exit code and the end of its standard error); the last line printed sums
up the set: per metric the values, median and quartile spread.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from bench.lib.stats import spread  # noqa: E402


def run_once(workload: str, seed: int, seconds: float, trace: int,
             timeout: float) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=timeout)
        rc, stdout, stderr = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, stdout, stderr = 124, e.stdout or "", e.stderr or ""
        stdout = stdout if isinstance(stdout, str) else stdout.decode()
        stderr = stderr if isinstance(stderr, str) else stderr.decode()
    lines = stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if rc == 0 and lines else None
    except json.JSONDecodeError:
        result = None
    return {"seed": seed, "rc": rc, "result": result,
            "info": [ln for ln in lines[:-1] if ln.startswith("info ")],
            "stderr_tail": stderr[-3000:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=1200.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    runs = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = run_once(args.workload, seed, args.seconds, args.trace,
                     args.timeout)
        runs.append(r)
        res = r["result"] or {}
        print(json.dumps({"seed": seed, "rc": r["rc"],
                          "correct": res.get("correct"),
                          "metrics": {k: v["value"] for k, v in
                                      res.get("metrics", {}).items()},
                          "checks": res.get("checks")}), flush=True)
        if r["rc"] != 0 or not res.get("correct"):
            print(r["stderr_tail"], file=sys.stderr, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(r) + "\n")
    values = {}
    for r in runs:
        for k, v in ((r["result"] or {}).get("metrics") or {}).items():
            values.setdefault(k, []).append(v["value"])
    out = {}
    for k, vs in values.items():
        out[k] = {"values": vs, "median": statistics.median(vs),
                  "spread": spread(vs) if len(vs) >= 2 else None}
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "trace": args.trace,
                      "all_correct": all((r["result"] or {}).get("correct")
                                         for r in runs),
                      "metrics": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
