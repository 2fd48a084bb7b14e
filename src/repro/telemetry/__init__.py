"""``repro.telemetry`` — the unified observability subsystem.

One contract for the serving stack's counters, latencies and spans:

* :class:`MetricsRegistry` — deterministic, namespaced, labeled
  :class:`Counter`/:class:`Gauge`/:class:`Histogram` metrics with
  fixed log-spaced bucket edges, bit-exact ``snapshot_state`` /
  ``restore_state`` through the service's flush-barrier checkpoints;
* :class:`Tracer` — clock-parameterized span tracing of the service hot
  path (``submit -> admit``; ``flush -> pad -> h2d -> launch -> sync ->
  results``, with ``admission.refresh`` every ``health_every`` flushes
  and each flush's queue wait in ``wait_s``/``wait_max_s``), JSONL
  event export, each span also a ``jax.profiler`` annotation so that a
  profiler capture holds it on the device trace's clock;
* :func:`prometheus_text` — deterministic Prometheus text snapshots;
* :class:`DriftMonitor` — every flush annotated with the perfmodel's
  :class:`~repro.perfmodel.OpCost` prediction and rolling
  measured/predicted drift gauges that flag stale calibration or
  schedule regressions at runtime;
* :class:`Telemetry` — the per-service bundle of all three.

See DESIGN.md §17 for the determinism rules, the namespacing scheme and
the drift-gauge definition.
"""
from repro.telemetry.drift import (DriftConfig, DriftMonitor,
                                   resolve_flush_plan)
from repro.telemetry.export import prometheus_text, write_prometheus
from repro.telemetry.hub import Telemetry, TelemetryConfig
from repro.telemetry.metrics import (DEFAULT_LATENCY_EDGES, Counter, Gauge,
                                     Histogram, MetricsRegistry, log_edges,
                                     nearest_rank)
from repro.telemetry.tracing import Span, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "log_edges",
    "nearest_rank", "DEFAULT_LATENCY_EDGES", "Span", "Tracer",
    "prometheus_text", "write_prometheus", "DriftConfig", "DriftMonitor",
    "resolve_flush_plan", "Telemetry", "TelemetryConfig",
]
