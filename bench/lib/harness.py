"""One run of one cell: set-up, the measured window, the check against the
reference, and the result line."""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from bench.lib import catalog
from bench.lib import device as D
from bench.lib import trace as T
from bench.lib.drive import Run, Window, dispatched

BREAKDOWN_ENTRIES = 10


class GcPauses:
    """Pauses of Python's cyclic collector while it is on, by generation:
    a pause of the process that hosts the service is latency its gets
    see."""

    def __init__(self):
        self.pauses = {0: [], 1: [], 2: []}
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses[info["generation"]].append(
                time.perf_counter() - self._t0)
            self._t0 = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def summary(self) -> dict:
        return {f"gen{g}": {"count": len(p),
                            "max_ms": 1e3 * max(p) if p else 0.0,
                            "total_ms": 1e3 * sum(p)}
                for g, p in self.pauses.items()}


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader (``bench/layers/<metric>.py``) reads."""
    cell: catalog.Cell
    window: Window
    trace: Optional[T.Summary]
    peaks: dict

    @property
    def spans(self) -> List[dict]:
        return self.window.host.get("spans", [])

    @property
    def counters(self) -> Dict[str, int]:
        return self.window.host.get("counters", {})


def _jsonable(x):
    try:
        json.dumps(x)
        return x
    except TypeError:
        return str(x)


def run_cell(cell: catalog.Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float, out=None, err=None,
             program: Optional[dict] = None) -> dict:
    """Run ``cell`` once on ``devices`` and print its lines: information
    first, then the compared numbers on ``err``, then the result object as
    the last line of ``out``. Returns the result object. ``program``
    overrides settings of the system under test (the control's lower
    guarantee); the reference keeps the configuration's."""
    out = out or sys.stdout
    err = err or sys.stderr
    clock = D.CompileClock()
    run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
              devices=list(devices), program=dict(program or {}))
    drv = catalog.kind(cell.traffic["kind"])
    state = drv.setup(run)
    # what set-up made lives for the whole run: a server freezes it out of
    # the cyclic collector's reach after warm-up, so that a full
    # collection in the window scans only what the window makes
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    compile_setup_s = clock.total
    events_before = dict(clock.events)
    summary = None
    with GcPauses() as gc_pauses:
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            try:
                with T.capture(trace_dir):
                    win = drv.window(run, state)
                events = T.load(trace_dir)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            summary = T.summarize(
                events, sorted(d.id for d in devices)
                if devices[0].platform == "tpu" else None)
        else:
            win = drv.window(run, state)
    gc.unfreeze()
    compile_events_in_window = {
        name: n - events_before.get(name, 0)
        for name, n in clock.events.items()
        if n != events_before.get(name, 0)}
    peak = D.memory_peak_bytes(devices)
    run.note("dispatched", dispatched())
    checks = drv.check(run, state, win)
    del state
    correct = all(v <= lim for v, lim in checks.values())

    if trace:
        ctx = LayerContext(cell=cell, window=win, trace=summary,
                           peaks=D.peaks(devices[0].device_kind)
                           if devices[0].platform == "tpu" else {})
        metrics = {}
        for m in cell.per_layer:
            value = catalog.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = dict(win.end_to_end, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}

    dev = D.describe(devices)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(win.attempted),
              "failed": int(win.failed), "metrics": metrics,
              "device": device}
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in
                           summary.ops[:BREAKDOWN_ENTRIES]],
            "idle_gaps": [[n, s] for n, s in
                          summary.idle_gaps[:BREAKDOWN_ENTRIES]]}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}

    info = [("cell", cell.name), ("seed", seed), ("device", dev),
            ("setup_s", setup_s), ("compile_s.setup", compile_setup_s),
            ("compile_events_in_window", compile_events_in_window),
            ("window_s", win.seconds), ("memory_peak_bytes", peak),
            ("gc_pauses_in_window", gc_pauses.summary())]
    if "lag_s" in win.host and len(win.host["lag_s"]):
        from bench.lib.stats import nearest_rank
        info.append(("loadgen.lag_p99_ms",
                     nearest_rank(win.host["lag_s"], 99.0) * 1e3))
    if win.host.get("call_s"):
        calls = sorted(win.host["call_s"])
        info.append(("call_s", {"n": len(calls), "min": calls[0],
                                "median": calls[len(calls) // 2],
                                "max": calls[-1]}))
    for k in ("calls", "builds_completed", "refused", "unanswered",
              "get_p99_ms", "stalls", "counters"):
        if k in win.host:
            info.append((k, win.host[k]))
    if summary is not None:
        info.append(("trace.kind_s", summary.kind_s))
    for label, value in info + run.info:
        print(f"info {label}: {json.dumps(_jsonable(value))}", file=out,
              flush=True)
    for name, (v, lim) in checks.items():
        print(f"check {name}: {v} (limit {lim})", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result
