"""The profiler's trace of a window, reduced to device numbers.

``capture`` records one window with JAX's profiler (Python tracing off,
host annotations on). ``load`` flattens the newest ``.xplane.pb`` into
:class:`Ev` records. ``summarize`` reduces them:

* busy time of a device: the union of the intervals in which one of its
  operations ran (the device plane's "XLA Ops" line), clipped to the
  window (the host annotation ``bench.window``);
* each operation's kind: a Pallas kernel (a TPU custom call), a
  collective (all-to-all, all-reduce, all-gather, reduce-scatter,
  collective-permute, send/recv, in their async start/done halves), or
  any other XLA operation;
* idle gaps: the holes in device 0's busy union inside the window, each
  put down to the innermost ``bench.*`` host annotation that covers its
  middle, so that idle time is attributed to what the host was doing.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
_COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "send", "recv")


def hlo_parts(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of a TPU op event, whose name is the HLO
    instruction's text (``%name = shape opcode(operands), ...``); a name
    that is not such text is its own name, with no opcode."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text, ""
    m = _OPCODE.search(" " + rest)
    return head.lstrip("%"), (m.group(1) if m else "")


@dataclasses.dataclass(frozen=True)
class Ev:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def stat(self, key: str) -> str:
        for k, v in self.stats:
            if k == key:
                return v
        return ""


@contextlib.contextmanager
def capture(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def newest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def events_of(profile) -> List[Ev]:
    """Flatten a ``jax.profiler.ProfileData`` into :class:`Ev` records.
    Host planes keep only the benchmark's own annotations."""
    out = []
    for plane in profile.planes:
        device = bool(_DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if not device and not e.name.startswith("bench."):
                    continue
                stats = tuple((str(k), str(v)) for k, v in e.stats) \
                    if device else ()
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns),
                              stats))
    return out


def load(log_dir: str) -> List[Ev]:
    from jax.profiler import ProfileData
    return events_of(ProfileData.from_file(newest_xplane(log_dir)))


def kind(ev: Ev) -> str:
    """"pallas" (a TPU custom call, what a Pallas kernel lowers to),
    "collective", or "xla" (every other operation)."""
    name, opcode = hlo_parts(ev.name)
    if any(opcode.startswith(c) or name.startswith(c) for c in _COLLECTIVES):
        return "collective"
    if "collective" in ev.stat("hlo_category").lower():
        return "collective"
    if opcode == "custom-call" and "tpu_custom_call" in ev.name:
        return "pallas"
    if opcode == "" and ev.stat("hlo_category") == "custom-call":
        return "pallas"
    return "xla"


def short_name(ev: Ev) -> str:
    """The instruction name and its kind, for the breakdown."""
    return f"{hlo_parts(ev.name)[0]} [{kind(ev)}]"


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                    # mean over the devices used
    kind_s: Dict[str, float]         # device seconds by kind, mean
    ops: List[Tuple[str, float]]     # seconds per op name, mean, desc
    idle_gaps: List[Tuple[str, float]]   # device 0's gaps by host activity
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_bounds(events: List[Ev]) -> Tuple[float, float]:
    wins = [e for e in events if e.name == WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    w = max(wins, key=lambda e: e.dur_ns)
    return w.start_ns, w.end_ns


def summarize(events: List[Ev], devices: Optional[List[int]] = None
              ) -> Optional[Summary]:
    """Reduce one traced window. None where no operation ran on a device
    in the window."""
    lo, hi = window_bounds(events)
    by_dev: Dict[int, List[Ev]] = defaultdict(list)
    for e in events:
        m = _DEVICE_PLANE.match(e.plane)
        if m and e.end_ns > lo and e.start_ns < hi:
            by_dev[int(m.group(1))].append(e)
    ids = sorted(by_dev) if devices is None else list(devices)
    if not ids or not any(by_dev.get(d) for d in ids):
        return None
    n = len(ids)
    busy, kinds, ops = {}, defaultdict(float), defaultdict(float)
    for d in ids:
        evs = by_dev.get(d, [])
        busy[d] = total(merge(clip([(e.start_ns, e.end_ns) for e in evs],
                                   lo, hi)))
        by_kind = defaultdict(list)
        for e in evs:
            by_kind[kind(e)].append((e.start_ns, e.end_ns))
            part = total(clip([(e.start_ns, e.end_ns)], lo, hi))
            ops[short_name(e)] += part / n / 1e9
        for k, iv in by_kind.items():
            kinds[k] += total(merge(clip(iv, lo, hi))) / n / 1e9
    anns = sorted((e for e in events if e.name.startswith("bench.")
                   and e.name != WINDOW and not _DEVICE_PLANE.match(e.plane)),
                  key=lambda e: e.start_ns)
    starts = [a.start_ns for a in anns]
    d0 = merge(clip([(e.start_ns, e.end_ns) for e in by_dev.get(ids[0], [])],
                    lo, hi))
    gaps, cursor = defaultdict(float), lo
    for a, b in d0 + [(hi, hi)]:
        if a > cursor:
            gaps[_cover(anns, starts, (cursor + a) / 2)] += (a - cursor) / 1e9
        cursor = max(cursor, b)
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy.values()) / n / 1e9,
        kind_s=dict(kinds),
        ops=sorted(ops.items(), key=lambda x: -x[1]),
        idle_gaps=sorted(gaps.items(), key=lambda x: -x[1]),
        n_devices=n)


def _cover(anns: List[Ev], starts: List[float], t: float,
           depth: int = 64) -> str:
    """The innermost (latest-starting) annotation covering time t, looking
    back over at most ``depth`` annotations that started before t."""
    i = bisect.bisect_right(starts, t) - 1
    for a in anns[max(i - depth + 1, 0):i + 1][::-1]:
        if a.end_ns >= t:
            return a.name
    return "host.other"
