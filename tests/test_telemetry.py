"""Telemetry subsystem: metrics determinism, tracing, export, drift.

The §17 invariants:
* histogram bucket edges are a fixed log-spaced grid (pinned here), so
  the same sample stream always produces bit-identical snapshots;
* registry snapshot/restore is bit-exact, labels included;
* span traces are deterministic under a virtual clock (ids and
  timestamps are pure step arithmetic) and nest children-before-parents;
* Prometheus / JSONL exports are byte-stable (golden-tested);
* the drift monitor's alert gauge fires when the perfmodel calibration
  is deliberately wrong by more than the tolerance factor.
"""
import io
import json

import numpy as np
import pytest

from repro.telemetry import (DEFAULT_LATENCY_EDGES, Counter, DriftConfig,
                             DriftMonitor, Histogram, MetricsRegistry,
                             Tracer, log_edges, nearest_rank,
                             prometheus_text)


# -- metrics ------------------------------------------------------------------

def test_log_edges_pinned_grid():
    edges = log_edges(1e-7, 10.0, per_decade=5)
    assert edges == DEFAULT_LATENCY_EDGES
    assert len(edges) == 41
    # the grid is 10**(i/5) for integer i — a pure function, never data
    assert edges == tuple(10.0 ** (i / 5) for i in range(-35, 6))
    assert list(edges) == sorted(edges)
    with pytest.raises(ValueError):
        log_edges(0.0, 1.0)


def test_nearest_rank_matches_bench_percentile():
    from benchmarks.common import percentile
    samples = [5.0, 1.0, 3.0, 2.0, 4.0]
    for q in (0.0, 50.0, 99.0, 100.0):
        assert nearest_rank(samples, q) == percentile(samples, q)
    with pytest.raises(ValueError):
        nearest_rank([], 50.0)
    with pytest.raises(ValueError):
        nearest_rank([1.0], 101.0)


def test_histogram_deterministic_and_exact_tails():
    rng = np.random.RandomState(0)
    xs = rng.exponential(1e-3, 500)
    h1 = Histogram("lat", (), edges=DEFAULT_LATENCY_EDGES)
    h2 = Histogram("lat", (), edges=DEFAULT_LATENCY_EDGES)
    h1.observe_many(xs)
    h2.observe_many(xs)
    assert h1.counts == h2.counts and h1.sum == h2.sum
    # exact nearest-rank over retained samples: p999 is an observation
    assert h1.percentile(99.9) in xs
    assert h1.summary(unit=1e6)["n"] == 500
    # without samples, percentiles degrade to the bucket upper bound
    h3 = Histogram("lat", (), edges=(1.0, 10.0), keep_samples=False)
    h3.observe_many([0.5, 5.0, 5.0])
    assert h3.percentile(50.0) == 10.0


def test_registry_snapshot_restore_bit_exact_with_labels():
    reg = MetricsRegistry()
    reg.counter("service.flushes").inc(7)
    reg.counter("admission.shed", reason="quota", tenant=3).inc(2)
    reg.gauge("filter.fill", deterministic=False).set(0.123456789)
    h = reg.histogram("service.latency", op="add")
    h.observe_many([1e-4, 2e-3, 0.5])
    state = reg.snapshot_state()
    # JSON round-trip is part of the contract (checkpoints store JSON)
    state = json.loads(json.dumps(state))
    reg2 = MetricsRegistry()
    reg2.restore_state(state)
    assert reg2.snapshot_state() == reg.snapshot_state()
    c = reg2.counter("admission.shed", reason="quota", tenant=3)
    assert c.value == 2 and c.key == "admission.shed{reason=quota,tenant=3}"
    # the non-deterministic gauge is excluded from the recovery surface
    det = reg.snapshot_state(deterministic_only=True)
    assert all(m["name"] != "filter.fill" for m in det["metrics"])


def test_registry_kind_and_monotonicity_guards():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.counter("x").inc(-1)
    with pytest.raises(ValueError):
        reg.counter("x").set_total(0)
    with pytest.raises(ValueError):
        Histogram("bad", (), edges=(2.0, 1.0))


# -- tracing ------------------------------------------------------------------

def _step_clock():
    t = {"now": 0.0}

    def clock():
        t["now"] += 1.0
        return t["now"]

    return clock


def test_span_nesting_virtual_clock_deterministic():
    def trace():
        tr = Tracer(clock=_step_clock())
        with tr.span("outer", op="add") as sp:
            with tr.span("inner"):
                pass
            sp.set(extra=1)
        return tr

    tr1, tr2 = trace(), trace()
    assert tr1.spans() == tr2.spans()            # bit-identical replays
    inner, outer = tr1.spans()                   # children exit first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["parent"] == outer["span"]
    assert outer["parent"] is None
    assert (outer["t0"], inner["t0"], inner["t1"], outer["t1"]) == (
        1.0, 2.0, 3.0, 4.0)
    assert outer["extra"] == 1 and outer["op"] == "add"


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("a") as sp:
        sp.set(x=1)                              # null span swallows attrs
    assert tr.spans() == [] and tr.n_started == 0


def _profiled_annotations(log_dir, trace: bool):
    """The service's spans that a profiler capture of two size flushes
    (the second one refreshing admission) holds on its host planes, as
    (name, start ns, end ns)."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    from repro import api
    from repro.service import AdmissionPolicy, FilterService, ServiceConfig
    from repro.telemetry import TelemetryConfig

    svc = FilterService(
        api.make_filter_bank(2, m_bits=1 << 10),
        ServiceConfig(max_batch=8, flush_deadline=None,
                      admission=AdmissionPolicy(health_every=2),
                      telemetry=TelemetryConfig(trace=trace)))
    rng = np.random.RandomState(20)
    keys = rng.randint(0, 2 ** 32, (16, 2)).astype(np.uint32)
    tenants = rng.randint(0, 2, 16)
    svc.submit_many("add", keys, tenants)        # compile outside the capture
    jax.profiler.start_trace(str(log_dir))
    try:
        svc.submit_many("add", keys, tenants)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("service.", "admission."))]


def test_enabled_tracer_annotates_the_profiler_trace(tmp_path):
    """Every span is also a profiler annotation of its name alone, nested
    as the spans are: the device trace's clock sees the program's
    steps."""
    anns = _profiled_annotations(tmp_path, trace=True)
    names = [a[0] for a in anns]
    for name, n in (("service.submit", 1), ("service.flush", 2),
                    ("service.flush.h2d", 2), ("admission.refresh", 1)):
        assert names.count(name) == n, names
    flushes = [a for a in anns if a[0] == "service.flush"]
    (submit,) = [a for a in anns if a[0] == "service.submit"]

    def inside(a, b):
        return b[1] <= a[1] and a[2] <= b[2]

    assert all(inside(f, submit) for f in flushes)
    for a in anns:
        if a[0] in ("service.flush.h2d", "admission.refresh"):
            assert sum(inside(a, f) for f in flushes) == 1
    (refresh,) = [a for a in anns if a[0] == "admission.refresh"]
    assert inside(refresh, max(flushes, key=lambda f: f[1]))


def test_disabled_tracer_adds_no_annotation(tmp_path):
    assert _profiled_annotations(tmp_path, trace=False) == []


def test_trace_jsonl_golden():
    tr = Tracer(clock=_step_clock())
    with tr.span("flush", op="add"):
        pass
    buf = io.StringIO()
    assert tr.export_jsonl(buf) == 1
    assert buf.getvalue() == (
        '{"dur": 1.0, "name": "flush", "op": "add", "parent": null, '
        '"span": 0, "t0": 1.0, "t1": 2.0}\n')


# -- prometheus export --------------------------------------------------------

def test_prometheus_text_golden():
    reg = MetricsRegistry()
    reg.histogram("lat", edges=(1.0, 10.0)).observe_many([0.5, 5.0, 50.0])
    reg.counter("service.requests", tenant=0).inc(3)
    reg.gauge("temp").set(1.5)
    assert prometheus_text(reg) == (
        '# TYPE lat histogram\n'
        'lat_bucket{le="1.0"} 1\n'
        'lat_bucket{le="10.0"} 2\n'
        'lat_bucket{le="+Inf"} 3\n'
        'lat_sum 55.5\n'
        'lat_count 3\n'
        '# TYPE service_requests counter\n'
        'service_requests{tenant="0"} 3\n'
        '# TYPE temp gauge\n'
        'temp 1.5\n')


def test_prometheus_text_is_deterministic():
    def build():
        reg = MetricsRegistry()
        reg.counter("b").inc(2)
        reg.counter("a", z="1").inc(1)
        reg.histogram("h", edges=(1.0,)).observe(0.5)
        return prometheus_text(reg)

    assert build() == build()


# -- drift monitor ------------------------------------------------------------

def _filt():
    from repro import api
    return api.make_filter_bank(2, m_bits=1 << 10, k=4)


def test_drift_alert_fires_on_miscalibrated_model():
    from repro.perfmodel import Calibration
    # a calibration claiming an absurdly fast machine makes every
    # prediction ~0 -> measured/predicted >> tolerance -> alert
    fast = Calibration(backend="cpu", bw_hbm_gbs=1e9, bw_res_gbs=1e9,
                       gops=1e9, launch_us=1e-6, step_us=1e-6,
                       measured=True)
    reg = MetricsRegistry()
    mon = DriftMonitor(reg, DriftConfig(window=8, min_samples=3,
                                        tolerance=16.0), calib=fast)
    filt = _filt()
    for _ in range(3):
        ann = mon.observe(filt, "add", 64, measured_s=1e-2)
    assert ann["drift_ratio"] > 16.0
    assert reg.gauge("perfmodel.drift.alert", deterministic=False,
                     op="add").value == 1.0
    assert reg.counter("perfmodel.drift.alerts", deterministic=False,
                       op="add").value >= 1


def test_drift_quiet_on_sane_calibration():
    from repro.perfmodel import Calibration, get_calibration, op_cost, \
        predict_us
    calib = get_calibration()
    reg = MetricsRegistry()
    mon = DriftMonitor(reg, DriftConfig(window=8, min_samples=3,
                                        tolerance=16.0), calib=calib)
    filt = _filt()
    pred = mon.predict(filt, "add", 64)
    assert pred is not None
    predicted_us = pred[0]
    for _ in range(4):
        mon.observe(filt, "add", 64, measured_s=predicted_us * 1e-6)
    assert reg.gauge("perfmodel.drift.alert", deterministic=False,
                     op="add").value == 0.0


def test_drift_annotation_plan_fields():
    from repro.telemetry import resolve_flush_plan
    plan = resolve_flush_plan(_filt(), "contains")
    assert plan["regime"] in ("vmem", "hbm")
    assert plan["coop"] in ("none", "subtile")
    assert plan["mix"] in ("full", "cheap")
    assert plan["bank"] == 2
