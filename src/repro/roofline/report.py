"""Aggregate TRANSFORMER dry-run JSON reports into the EXPERIMENTS.md
tables (compile stats, collective counts, macro-model rooflines). The
filter kernels have their own performance model in ``repro.perfmodel``;
the generic helpers live in :mod:`repro.roofline.report_utils`.

    PYTHONPATH=src python -m repro.roofline.report [--dir experiments/dryrun]
"""
from __future__ import annotations

import argparse
from typing import Dict, List

from repro.roofline.report_utils import fmt_bytes, fmt_float, load_reports

# Back-compat aliases (test_dryrun and older callers import these names).
_fmt_bytes = fmt_bytes
_s = fmt_float


def dryrun_table(reports: List[Dict], mesh: str) -> str:
    rows = ["| arch | shape | status | compile_s | bytes/dev (args+tmp) | "
            "collective ops (AR/AG/RS/A2A/CP) | coll bytes/dev |",
            "|---|---|---|---|---|---|---|"]
    for r in reports:
        if r.get("mesh") != mesh or r.get("tag", "baseline") != "baseline":
            continue
        if r["status"] != "ok":
            rows.append(f"| {r['arch']} | {r['shape']} | {r['status']}: "
                        f"{r.get('reason', r.get('error', ''))[:60]} | - | - | - | - |")
            continue
        mem = r["memory_analysis"]
        byts = _fmt_bytes(mem.get("argument_size_in_bytes", 0)
                          + mem.get("temp_size_in_bytes", 0))
        cc = r["collectives"]["counts"]
        ops = (f"{cc['all-reduce']}/{cc['all-gather']}/"
               f"{cc['reduce-scatter']}/{cc['all-to-all']}/"
               f"{cc['collective-permute']}")
        rows.append(
            f"| {r['arch']} | {r['shape']} | ok | {r['compile_s']} | {byts} "
            f"| {ops} | {_fmt_bytes(r['collectives']['total_bytes'])} |")
    return "\n".join(rows)


def roofline_table(reports: List[Dict], mesh: str = "single") -> str:
    rows = ["| arch | shape | compute_s | memory_s | collective_s | "
            "bottleneck | MODEL_FLOPS/HLO_FLOPS | roofline frac |",
            "|---|---|---|---|---|---|---|---|"]
    for r in reports:
        if (r.get("mesh") != mesh or r["status"] != "ok"
                or r.get("tag", "baseline") != "baseline"):
            continue
        ro = r["roofline"]
        dom = max(ro["compute_s"], ro["memory_s"], ro["collective_s"])
        useful_s = ro["model_flops_per_chip"] / 197e12
        frac = ro.get("roofline_fraction",
                      useful_s / dom if dom > 0 else 0)
        rows.append(
            f"| {r['arch']} | {r['shape']} | {_s(ro['compute_s'])} | "
            f"{_s(ro['memory_s'])} | {_s(ro['collective_s'])} | "
            f"{ro['bottleneck']} | {_s(ro['useful_ratio'], 3)} | "
            f"{_s(frac, 3)} |")
    return "\n".join(rows)


def worst_cells(reports: List[Dict], n: int = 5):
    scored = []
    for r in reports:
        if (r.get("mesh") != "single" or r["status"] != "ok"
                or r.get("tag", "baseline") != "baseline"):
            continue
        ro = r["roofline"]
        dom = max(ro["compute_s"], ro["memory_s"], ro["collective_s"])
        useful_s = ro["model_flops_per_chip"] / 197e12
        frac = ro.get("roofline_fraction",
                      useful_s / dom if dom > 0 else 0)
        scored.append((frac, ro["collective_s"] / max(dom, 1e-12),
                       r["arch"], r["shape"], ro["bottleneck"]))
    scored.sort()
    return scored[:n]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    args = ap.parse_args()
    reports = load_reports(args.dir)
    print("## Dry-run (single pod, 16x16 = 256 chips)\n")
    print(dryrun_table(reports, "single"))
    print("\n## Dry-run (multi-pod, 2x16x16 = 512 chips)\n")
    print(dryrun_table(reports, "multi"))
    print("\n## Roofline (single pod)\n")
    print(roofline_table(reports))
    print("\n## Worst roofline fractions\n")
    for frac, coll, arch, shape, bn in worst_cells(reports):
        print(f"- {arch} x {shape}: frac={frac:.3f} bottleneck={bn} "
              f"collective_share={coll:.2f}")


if __name__ == "__main__":
    main()
