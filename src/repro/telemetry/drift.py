"""Perfmodel drift detection: measured-vs-predicted flush cost at runtime.

The performance model (``repro.perfmodel``) predicts each flush's cost;
the service checks that prediction *continuously* — a schedule
regression (an extra pass, a lost fusion) or stale machine calibration
shows up as a drifting measured/predicted ratio long before anyone reruns
a benchmark.

Every service flush is annotated with the model's :class:`OpCost`
prediction for its exact configuration — (spec, op, regime, tile, padded
batch size, bank) — and the monitor maintains, per op, a rolling window
of ``measured_us / predicted_us`` ratios:

* ``perfmodel.predicted_us{op=}`` / ``perfmodel.ceiling_us{op=}`` — the
  model's full prediction and its speed-of-light floor for one flush;
* ``perfmodel.drift.ratio{op=}`` — rolling **median** ratio (median, not
  mean: one GC pause or checkpoint stall must not trip the gauge);
* ``perfmodel.drift.alert{op=}`` — 1.0 when the window holds at least
  ``min_samples`` ratios and the median sits outside
  ``[1/tolerance, tolerance]``. The tolerance is loose (the model's
  constants are not calibrated on the chip), so an alert means a model
  term or the calibration is *structurally* wrong for this host, not
  mistuned.

Flush wall time is measured with the real clock even when the service
runs on the virtual clock — drift is a report metric, not service state
(the same split the driver uses for recovery time), so every drift
metric is registered ``deterministic=False`` and excluded from the
recovery drill's bit-exactness comparison.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, Optional, Tuple

from repro.telemetry.metrics import MetricsRegistry

__all__ = ["DriftConfig", "DriftMonitor", "resolve_flush_plan"]


@dataclasses.dataclass(frozen=True)
class DriftConfig:
    window: int = 32         # rolling ratios kept per op
    min_samples: int = 8     # gauge arms only with this much evidence
    tolerance: float = 16.0  # alert outside [1/tol, tol] median ratio


def resolve_flush_plan(filt, op: str) -> dict:
    """The (regime, tile, bank) a service flush of ``op`` executes under:
    the regime read off the engine (engines without one are modeled as
    vmem), the tile the options pin (else the default), the bank size.
    The Bloom and counting kernels run one schedule per regime, so
    nothing else is left to resolve."""
    regime = getattr(filt.engine, "regime", None)
    if regime not in ("vmem", "hbm"):
        regime = "vmem"
    return {"regime": regime,
            "tile": int(filt.options.tile) if filt.options.tile else 256,
            "bank": max(int(filt.bank_size), 1)}


class DriftMonitor:
    """Per-op rolling measured/predicted gauges over one registry."""

    def __init__(self, registry: MetricsRegistry,
                 cfg: DriftConfig = DriftConfig(), calib=None):
        self.registry = registry
        self.cfg = cfg
        self._calib = calib            # None -> get_calibration() lazily
        self._windows: Dict[str, deque] = {}
        self._cost_cache: Dict[Tuple, tuple] = {}

    def _calibration(self):
        if self._calib is None:
            from repro.perfmodel import get_calibration
            self._calib = get_calibration()
        return self._calib

    def predict(self, filt, op: str, n_keys: int) -> Optional[tuple]:
        """(predicted_us, ceiling_us, plan) for one padded flush; cached
        per (spec, backend, options, op, n_keys, bank) — static between
        reshard/resize events. None when the spec falls outside the
        model (the flush is then traced without an annotation)."""
        key = (filt.spec, filt.backend, filt.options, op, int(n_keys))
        hit = self._cost_cache.get(key)
        if hit is not None:
            return hit if hit != () else None
        try:
            from repro import perfmodel as PM
            plan = resolve_flush_plan(filt, op)
            cost = PM.op_cost(filt.spec, op, plan["regime"],
                              tile=plan["tile"], n_keys=int(n_keys),
                              bank=plan["bank"])
            calib = self._calibration()
            out = (PM.predict_us(cost, calib), PM.ceiling_us(cost, calib),
                   plan)
        except Exception:
            self._cost_cache[key] = ()
            self.registry.counter("perfmodel.predict_errors",
                                  deterministic=False).inc()
            return None
        self._cost_cache[key] = out
        return out

    def observe(self, filt, op: str, n_keys: int,
                measured_s: float) -> dict:
        """Record one flush measurement; updates the gauges and returns
        the span annotation (empty when the spec is unmodeled)."""
        pred = self.predict(filt, op, n_keys)
        if pred is None:
            return {}
        predicted_us, ceil_us, plan = pred
        measured_us = float(measured_s) * 1e6
        ratio = measured_us / max(predicted_us, 1e-9)
        win = self._windows.get(op)
        if win is None:
            win = self._windows[op] = deque(maxlen=self.cfg.window)
        win.append(ratio)
        med = sorted(win)[len(win) // 2]
        alert = (len(win) >= self.cfg.min_samples
                 and not (1.0 / self.cfg.tolerance <= med
                          <= self.cfg.tolerance))
        reg = self.registry
        reg.gauge("perfmodel.predicted_us", deterministic=False,
                  op=op).set(predicted_us)
        reg.gauge("perfmodel.ceiling_us", deterministic=False,
                  op=op).set(ceil_us)
        reg.gauge("perfmodel.drift.ratio", deterministic=False,
                  op=op).set(med)
        reg.gauge("perfmodel.drift.alert", deterministic=False,
                  op=op).set(1.0 if alert else 0.0)
        if alert:
            reg.counter("perfmodel.drift.alerts", deterministic=False,
                        op=op).inc()
        return {"predicted_us": round(predicted_us, 3),
                "ceiling_us": round(ceil_us, 3),
                "measured_us": round(measured_us, 3),
                "drift_ratio": round(ratio, 4),
                "regime": plan["regime"]}
