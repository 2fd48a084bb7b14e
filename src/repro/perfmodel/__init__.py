"""Filter-native performance model and its machine calibration.

Two layers:

* :mod:`repro.perfmodel.model` — first-principles per-bulk-op resource
  counts (:class:`OpCost`: HBM bytes, resident bytes, flops, launches,
  schedule vector-ops) for every ``FilterSpec`` x op x regime x layout
  configuration (the Bloom and counting families' one schedule per
  regime; cuckoo and quotient also x their ``coop`` schedule), plus the
  time predictors (:func:`predict_us`, :func:`ceiling_us`,
  :func:`ceiling_mops`) that convert counts to wall time through a
  :class:`Calibration`;
* :mod:`repro.perfmodel.calibrate` — the tiny measured microbench
  (streaming bandwidth, cache-resident gather bandwidth, u32 ALU rate,
  launch and schedule-step overhead) that turns the machine-independent
  counts into a *practical* speed-of-light for THIS host, disk-cached per
  backend so a fleet pays the measurement once.

Two consumers: the service's drift monitor (``repro.telemetry.drift``)
compares each flush with :func:`predict_us`, and :func:`choose_coop`
resolves the cuckoo and quotient engines' ``coop="auto"``. The constants
for the TPU are placeholders, not measured on the chip.
"""
from repro.perfmodel.calibrate import (Calibration, default_calibration,
                                       get_calibration)
from repro.perfmodel.model import (OpCost, ceiling_mops, ceiling_us,
                                   choose_coop, op_cost, predict_us)

__all__ = [
    "Calibration", "OpCost", "ceiling_mops", "ceiling_us", "choose_coop",
    "default_calibration", "get_calibration", "op_cost", "predict_us",
]
