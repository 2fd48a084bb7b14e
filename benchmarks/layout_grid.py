"""Paper Table 1/2 Θ layout grid on the Pallas kernels.

Off the TPU the kernels execute in interpret mode; the grid then measures
the SCHEDULE STRUCTURE (loop trip counts) rather than TPU wall-clock. The
paper's second axis, Φ words per load, has no counterpart here: every probe
loads the whole row holding its block. Two artifacts:

  * structural metrics per layout: the tuner's structural score and the
    loop/gather schedule-step counts;
  * interpret-mode relative times (same engine overhead for all layouts, so
    ratios indicate schedule cost on the traced graph).

The paper's empirically-optimal picks (Θ̂_c = max(1, B/256), Θ̂_a = s) are
encoded in kernels.sbf.default_layout; this bench verifies the defaults lie
on the structural-cost frontier.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import Csv, keys_u64x2, time_fn
from repro.core import tuning
from repro.core import variants as V
from repro.kernels import ops
from repro.kernels.sbf import default_layout

M_BITS = 1 << 20
N_KEYS = 2048
K = 16


def run(csv: Csv, measure: bool = True):
    for B in (128, 256, 512):
        spec = V.FilterSpec("sbf", M_BITS, K, block_bits=B)
        keys = keys_u64x2(N_KEYS, seed=3)
        filt = V.add_scatter(spec, V.init(spec), keys)
        base_t = None
        for lay in tuning.valid_layouts(spec, tile=256):
            steps_loop = tuning.probe_schedule_steps(spec, lay, "contains",
                                                     256, "loop")
            steps_gather = tuning.probe_schedule_steps(spec, lay, "contains",
                                                       256, "gather")
            probe_win = "gather" if steps_gather <= steps_loop else "loop"
            derived = (f"score={tuning.structural_score(spec, lay, 'contains'):.3f} "
                       f"probe_steps(loop/gather)={steps_loop:.0f}/"
                       f"{steps_gather:.0f} probe_best={probe_win}")
            if measure:
                t = time_fn(
                    lambda f, k, lay=lay, spec=spec:
                        ops.bloom_contains(spec, f, k, layout=lay, tile=256,
                                           probe="loop"),
                    filt, keys, warmup=1, reps=3)
                base_t = base_t or t
                derived += f" rel_time={t/base_t:.2f}"
            csv.add(f"layout/B{B}/{lay}", (t * 1e6) if measure else 0,
                    derived, n_ops=N_KEYS)
        # the whole-tile gather engine is layout-free: one row per (B, op)
        for op in ("contains", "add"):
            steps_loop = tuning.probe_schedule_steps(
                spec, default_layout(spec, op), op, 256, "loop")
            steps_gather = tuning.probe_schedule_steps(
                spec, default_layout(spec, op), op, 256, "gather")
            if measure:
                if op == "contains":
                    fn = lambda f, k, spec=spec: ops.bloom_contains(
                        spec, f, k, tile=256, probe="gather")
                    t = time_fn(fn, filt, keys, warmup=1, reps=3)
                else:
                    fn = lambda f, k, spec=spec: ops.bloom_add(
                        spec, f, k, tile=256, probe="gather")
                    t = time_fn(fn, V.init(spec), keys, warmup=1, reps=3)
            csv.add(f"layout/B{B}/gather/{op}", (t * 1e6) if measure else 0,
                    f"probe_steps(loop/gather)={steps_loop:.0f}/"
                    f"{steps_gather:.0f} "
                    f"speedup_structural={steps_loop/max(steps_gather,1e-9):.1f}x",
                    n_ops=N_KEYS)
        d = default_layout(spec, "contains")
        plan = tuning.tune_plan(spec, "contains", regime="vmem", tile=256)
        csv.add(f"layout/B{B}/default", 0,
                f"picked={d} (paper rule Θ̂=max(1,B/256)) "
                f"plan_probe={plan.probe} plan_depth={plan.depth}")


if __name__ == "__main__":
    c = Csv()
    c.header()
    run(c)
