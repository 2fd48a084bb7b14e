"""The readers of the service's program spans: queue wait, admission
refresh and launch time per flush, on hand-made spans, on the spans of a
program that lacks them, and in a tiny traced run that lists them."""
from __future__ import annotations

import copy

import pytest

from bench.lib import catalog
from bench.lib.drive import Window
from bench.lib.harness import LayerContext
from bench.tests.tiny import run_tiny

# How the three readers are listed for the served cell.
SPAN_METRICS = [
    {"name": "service.queue_wait_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "service front end",
     "moves": "get_p50_ms", "workloads": ["ycsb_c.service"]},
    {"name": "service.refresh_ms_per_flush", "unit": "ms",
     "better": "lower", "source": "program_span",
     "layer": "service front end", "moves": "get_p50_ms",
     "workloads": ["ycsb_c.service"]},
    {"name": "service.launch_ms_per_flush", "unit": "ms",
     "better": "lower", "source": "program_span",
     "layer": "filter API and dispatch", "moves": "get_p50_ms",
     "workloads": ["ycsb_c.service"]},
]


def _span(name, span, parent, dur, **attrs):
    return dict(name=name, span=span, parent=parent, t0=0.0, t1=dur,
                dur=dur, **attrs)


def _ctx(spans):
    win = Window(t0=0.0, t1=1.0, kind="service", attempted=0, failed=0,
                 end_to_end={}, host={"spans": spans})
    return LayerContext(cell=None, window=win, trace=None, peaks={})


def _spans(with_this_programs_spans=True):
    """Three flushes of 4, 2 and 2 requests; the third refreshes
    admission."""
    waits = [dict(wait_s=0.012, wait_max_s=0.004),
             dict(wait_s=0.004, wait_max_s=0.002),
             dict(wait_s=0.002, wait_max_s=0.001)]
    out = []
    for i, (take, launch) in enumerate(((4, 0.001), (2, 0.002),
                                        (2, 0.003))):
        f = 10 * i
        out.append(_span("service.flush.launch", f + 1, f, launch))
        if with_this_programs_spans and i == 2:
            out.append(_span("admission.refresh", f + 2, f, 0.009))
        attrs = waits[i] if with_this_programs_spans else {}
        out.append(_span("service.flush", f, None, 0.02, take=take,
                         **attrs))
    return out


@pytest.mark.parametrize("metric,want", [
    ("service.queue_wait_ms", 1e3 * 0.018 / 8),
    ("service.refresh_ms_per_flush", 1e3 * 0.009 / 3),
    ("service.launch_ms_per_flush", 1e3 * 0.006 / 3)])
def test_reads_the_window_spans(metric, want):
    assert catalog.reader(metric)(_ctx(_spans())) == pytest.approx(want)


@pytest.mark.parametrize("metric", [m["name"] for m in SPAN_METRICS])
def test_silent_where_the_program_has_no_such_span(metric):
    """A program whose flushes carry no wait and which spans no refresh
    reads nothing there; launch spans it has."""
    value = catalog.reader(metric)(_ctx(_spans(False)))
    if metric == "service.launch_ms_per_flush":
        assert value == pytest.approx(2.0)
    else:
        assert value is None
    assert catalog.reader(metric)(_ctx([])) is None


def test_tiny_traced_service_reports_them(monkeypatch):
    bench = copy.deepcopy(catalog.load_benchmark())
    bench["per_layer"] += copy.deepcopy(SPAN_METRICS)
    monkeypatch.setattr(catalog, "load_benchmark", lambda *a: bench)
    result, _, _ = run_tiny("ycsb_c.service", seed=(1 << 33) + 3,
                            seconds=0.3, trace=True)
    assert result["correct"]
    for m in SPAN_METRICS:
        got = result["metrics"][m["name"]]
        assert got["unit"] == "ms" and got["value"] > 0, m["name"]
