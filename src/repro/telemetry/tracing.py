"""Span tracing for the service hot path, clock-parameterized.

A span is one timed, attributed, nested region of the serving loop
(``service.flush`` wrapping ``pad -> h2d -> launch -> sync -> results``).
The tracer mirrors the service's clock contract (DESIGN.md §14): the
replay harness drives it with the real clock for honest latency traces, while
the recovery driver drives it with the virtual step clock — span
timestamps are then pure step arithmetic and a replayed stream emits an
identical trace. The clock is read through a callable indirection so the
driver's post-construction ``service.clock`` rebind is picked up.

An enabled tracer also enters a ``jax.profiler.TraceAnnotation`` of the
span's name for its lifetime, so that a profiler capture holds the
service's spans on the same clock as the device's operations and an idle
gap of the device can be put down to the step of the program that ran in
it. The annotation carries the name only, never the attributes; with no
capture running it costs under a microsecond.

Events are appended on span *exit* (children complete before parents —
the standard trace-log ordering) into a bounded ring; ``export_jsonl``
writes one sorted-key JSON object per line, the artifact the bench-smoke
CI job uploads. Span/parent ids are a deterministic sequence, so golden
tests can pin whole trace files.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Callable, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer", "NULL_SPAN"]


class Span:
    """One span, its own context manager: entering it assigns its id,
    reads the clock and enters its profiler annotation; leaving it reads
    the clock again and appends its event. ``set(**attrs)`` attaches
    attributes any time before exit (the flush span's OpCost annotation
    lands this way)."""

    __slots__ = ("name", "span_id", "parent_id", "t0", "t1", "attrs",
                 "_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = self.parent_id = self.t0 = self.t1 = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        tr = self._tracer
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.span_id = tr._next_id
        self.parent_id = tr._stack[-1].span_id if tr._stack else None
        self.t0 = tr.clock()
        tr._next_id += 1
        tr.n_started += 1
        tr._stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        tr = self._tracer
        tr._stack.pop()
        self.t1 = tr.clock()
        ev = {"name": self.name, "span": self.span_id,
              "parent": self.parent_id, "t0": self.t0, "t1": self.t1,
              "dur": self.t1 - self.t0}
        ev.update(self.attrs)
        tr.events.append(ev)
        self._annotation.__exit__(None, None, None)


class _NullSpan:
    """No-op stand-in when tracing is disabled: ``set`` swallows attrs."""

    __slots__ = ()

    def set(self, **attrs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


NULL_SPAN = _NullSpan()


class Tracer:
    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 enabled: bool = True, max_spans: int = 4096):
        self.clock = clock
        self.enabled = bool(enabled)
        self.events = deque(maxlen=int(max_spans))
        self.n_started = 0          # total spans ever opened (ring may drop)
        self._stack: List[Span] = []
        self._next_id = 0

    def span(self, name: str, **attrs):
        """One nested span, to be entered with ``with``; it is also a
        profiler annotation of the same name. Disabled tracers return a
        shared null span and record and annotate nothing (the
        overhead-gate path)."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, attrs)

    # -- views / export --------------------------------------------------------
    def spans(self, name: Optional[str] = None) -> List[dict]:
        return [e for e in self.events if name is None or e["name"] == name]

    def export_jsonl(self, path_or_file) -> int:
        """Write one JSON object per completed span (sorted keys, append
        order = completion order); returns the number of lines written."""
        own = isinstance(path_or_file, str)
        f = open(path_or_file, "w") if own else path_or_file
        try:
            for ev in self.events:
                f.write(json.dumps(ev, sort_keys=True,
                                   default=_jsonable) + "\n")
        finally:
            if own:
                f.close()
        return len(self.events)


def _jsonable(x):
    """Last-resort JSON coercion for numpy scalars riding in span attrs."""
    for attr in ("item",):
        fn = getattr(x, attr, None)
        if callable(fn):
            return fn()
    return str(x)
