"""(Θ, probe, depth, segments) autotuner — the paper's Table 1/2 grid
search as a library, extended to every axis the kernels expose.

The paper's headline empirical result is that the optimal vectorization
layout depends on (operation, block size, residency). ``tune_layout`` sweeps
the valid Θ grid for a (spec, tile) and returns the fastest layout;
``tune_plan`` additionally picks the probe strategy (per-key loop vs
whole-tile gather), the HBM DMA pipeline depth and the partitioned-add
segment count, returning a :class:`Plan` that `api.backends` threads into
the kernels:

* ``mode="measure"`` times the Pallas kernels, best-of-``repeats`` after a
  warmup run to de-noise the grid (meaningful on real TPU; in interpret
  mode the ratios reflect schedule structure);
* ``mode="structural"`` (default) ranks the candidate grid — now including
  the cooperation axes (``coop``: lane-group subtile probing, ``mix``:
  fused cheap double-hash) — by the calibrated performance model's
  predicted cost (``repro.perfmodel``: bytes moved / flops / launch and
  schedule overhead per bulk op, converted to time through the measured
  machine calibration). The original §4.1 structural scorers remain for
  ``tune_layout`` (the paper's empirical Θ̂ tie-breaks) and diagnostics.

Results are cached per (spec, op, mode, tile[, regime]) in-process AND in a
disk-persisted JSON cache (``REPRO_TUNING_CACHE`` env var, default
``~/.cache/repro/tuning.json``) so a fleet of processes pays the grid
search once. The cache key includes every axis that changes the valid
candidate set — in particular ``tile``: a layout tuned for tile=256 is NOT
valid for tile=8 (Θ must divide the tile), which is why tile lives in the
key and every candidate is re-validated against it.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import hashing as H
from repro.core.variants import FilterSpec
from repro.kernels.sbf import (DEFAULT_TILE, DMA_DEPTHS, Layout,
                               VMEM_FILTER_BYTES, default_layout)

TUNABLE_DEPTHS = (2, 4, 8)        # the sweep; depth=1 (serial) is debug-only
TUNABLE_SEGMENTS = (4, 8, 16, 32)


@dataclasses.dataclass(frozen=True)
class Plan:
    """One tuned kernel configuration (static, hashable — carried through
    `api.BackendOptions` and closed over by the cached-jit dispatch)."""

    layout: Layout
    probe: str = "gather"          # "loop" | "gather" (vmem-regime phase 2)
    depth: int = 2                 # HBM contains DMA pipeline depth
    n_segments: int = 8            # partitioned bulk-add grid width
    coop: str = "none"             # "none" | "subtile" lane-group probing
    mix: str = "full"              # "full" | "cheap" fused double-hash

    def to_dict(self) -> dict:
        return {"theta": self.layout.theta, "probe": self.probe, "depth": self.depth,
                "n_segments": self.n_segments, "coop": self.coop,
                "mix": self.mix}

    @classmethod
    def from_dict(cls, d: dict) -> "Plan":
        return cls(Layout(int(d["theta"])), str(d["probe"]),
                   int(d["depth"]), int(d["n_segments"]),
                   str(d.get("coop", "none")), str(d.get("mix", "full")))


# ---------------------------------------------------------------------------
# Disk-persisted cache
# ---------------------------------------------------------------------------

def cache_path() -> str:
    return os.environ.get(
        "REPRO_TUNING_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "tuning.json"))


def _load_disk() -> dict:
    try:
        with open(cache_path()) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def _store_disk(key: str, value: dict) -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        data = _load_disk()
        data[key] = value
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass                       # cache is an optimization, never an error


def _plan_key(spec: FilterSpec, op: str, regime: str, mode: str,
              tile: int, bank: int = 1, coop: str = "auto",
              mix: str = "auto") -> str:
    # The backend is part of the key: measure-mode timings taken in CPU
    # interpret mode must never pin a plan for a real TPU run (the same
    # stale-key class of bug as omitting tile). ``bank`` joins the key for
    # the same reason — a B-member bank shifts the loop/gather crossover
    # (B× the gather index space, B× the RMW working set) and must never
    # silently reuse a plan tuned for the scalar filter.
    # ``coop``/``mix`` join the key because a PINNED cooperation or mix
    # axis restricts the candidate grid: a plan tuned under coop="none"
    # must never answer a coop="subtile" query (and vice versa) — the same
    # stale-key bug class again. The "plan2" version prefix retires every
    # pre-cooperation cache entry wholesale: old entries lack the
    # coop/mix fields and were ranked by the structural scorer, not the
    # perfmodel predictor.
    # ``str(spec)`` carries the variant name AND every variant-specific
    # geometry field (FilterSpec.__str__ spells cuckoo slot geometry out),
    # so same-m specs of different variants never share an entry.
    base = (f"plan2|{jax.default_backend()}|{spec}|{op}|{regime}|{mode}"
            f"|tile{tile}|coop:{coop}|mix:{mix}")
    return base if bank == 1 else f"{base}|bank{bank}"


# ---------------------------------------------------------------------------
# Θ layout grid
# ---------------------------------------------------------------------------

def valid_layouts(spec: FilterSpec, tile: int = DEFAULT_TILE) -> List[Layout]:
    return [Layout(theta) for theta in (1, 2, 4, 8, 16)
            if tile % theta == 0 and theta <= max(spec.s, 8)]


def structural_score(spec: FilterSpec, lay: Layout, op: str) -> float:
    """Lower is better. Mirrors §4.1: unrolling Θ keys amortizes the loop
    trip's scalar overhead; too much Θ under-utilizes lanes for lookups but
    tightens RMW windows for adds (the paper's Θ̂ rules, encoded as a soft
    preference). Every probe loads one whole row, so no load count enters."""
    s = spec.s
    score = 1.0 / lay.theta                   # trip overhead per key
    if op == "contains":
        target = max(1, spec.block_bits // 256)
        score += 0.25 * abs(lay.theta - target)
    else:                                     # add: fully horizontal wins
        score += 0.25 * (s - min(lay.theta, s)) / max(s, 1)
    return score


def probe_schedule_steps(spec: FilterSpec, lay: Layout, op: str, tile: int,
                         probe: str, bank: int = 1) -> float:
    """Interpret-mode schedule-step count of one key tile's phase 2.

    loop:   (tile/Θ) trips, each issuing one row load + one fused compare
            (or one row store for add) — the per-key scalar walk.
    gather: a constant number of whole-tile vector ops — index build,
            ONE gather, ONE fused compare for contains; sort (log²-depth
            bitonic analogue), segmented scan, gather, scatter for add.

    ``bank``: a B-member bank widens the resident word array B×. The loop
    probe's dynamic-slice loads then stride across the whole bank (one
    address stream per key, locality decaying with bank depth); the gather
    probe only grows its index space (one extra vector op worth per
    doubling). Both are soft log2 terms — the fixed per-trip structure is
    unchanged.
    """
    import math
    lg_b = math.log2(max(bank, 1))
    if probe == "loop":
        per_trip = 2
        return (tile // lay.theta) * per_trip * (1.0 + 0.05 * lg_b)
    if op == "contains":
        return 3.0 + 0.25 * lg_b
    lg = max(math.log2(max(tile, 2)), 1.0)
    # sort + segmented scan + gather + scatter (+ bank index widening)
    return 2.0 * lg + 4.0 + 0.25 * lg_b


def depth_structural_score(spec: FilterSpec, depth: int) -> float:
    """Stall model for the HBM contains pipeline: a row DMA costs a fixed
    issue latency plus the row transfer; each in-flight slot hides one
    row's compute. Deeper pipelines win for small rows (latency-bound) and
    waste scratch for large rows (bandwidth-bound)."""
    s = spec.s
    latency = 32.0 + s             # fixed DMA latency + transfer (words)
    compute = float(s)             # per-row test cost
    stall = max(latency - (depth - 1) * compute, 0.0)
    return stall + compute + 0.1 * depth * s   # + scratch pressure tiebreak


def segments_structural_score(spec: FilterSpec, n_segments: int) -> float:
    """Prefer the smallest grid whose exclusive segment fits the VMEM
    budget (each partitioned-grid step pins one segment)."""
    if spec.n_blocks % n_segments or spec.storage_words % n_segments:
        return float("inf")
    seg_bytes = spec.storage_words * 4 / n_segments
    penalty = 0.0 if seg_bytes <= VMEM_FILTER_BYTES else seg_bytes
    return penalty + n_segments    # grid-launch overhead tiebreak


def _measure(spec: FilterSpec, op: str, n_keys: int, repeats: int,
             **kw) -> float:
    """Best-of-``repeats`` post-warmup wall time.

    A single timed run is dominated by scheduler/allocator noise at the
    microsecond scales the grid search discriminates on; the *minimum* over
    k runs is the standard noise-floor estimator (any positive perturbation
    only raises a sample, never lowers it)."""
    from repro.kernels import ops
    keys = jnp.asarray(H.random_u64x2(n_keys, seed=7))
    filt = jnp.zeros((spec.n_words,), jnp.uint32)
    if op == "contains":
        fn = lambda: ops.bloom_contains(spec, filt, keys, **kw)
    else:
        fn = lambda: ops.bloom_add(spec, filt, keys, **kw)
    jax.block_until_ready(fn())                       # warmup (compile)
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


@functools.lru_cache(maxsize=256)
def tune_layout(spec: FilterSpec, op: str = "contains",
                mode: str = "structural", n_keys: int = 1024,
                repeats: int = 3, tile: int = DEFAULT_TILE
                ) -> Tuple[Layout, List[Tuple[str, float]]]:
    """Returns (best layout, [(layout-name, score/time) ...]).

    ``tile`` is part of the cache key AND the validation constraint: Θ must
    divide the tile, so the candidate grid differs per tile and a layout
    tuned for one tile must never be silently reused for another.
    ``repeats`` (measure mode) de-noises the grid search: each candidate is
    timed ``repeats`` times post-warmup and scored by its best run."""
    assert op in ("contains", "add")
    cands = []
    for lay in valid_layouts(spec, tile):
        try:
            cands.append(lay.validate(spec, tile))
        except AssertionError:
            continue
    cands = sorted(set(cands), key=lambda l: l.theta)
    if not cands:
        return default_layout(spec, op), []
    if mode == "structural":
        scored = [(str(l), structural_score(spec, l, op)) for l in cands]
    else:
        scored = [(str(l), _measure(spec, op, n_keys, repeats,
                                    layout=l, tile=tile, probe="loop"))
                  for l in cands]
    best_name, _ = min(scored, key=lambda kv: kv[1])
    best = next(l for l in cands if str(l) == best_name)
    return best, sorted(scored, key=lambda kv: kv[1])


# ---------------------------------------------------------------------------
# Full-plan sweep: probe strategy x depth x segments (+ the layout grid)
# ---------------------------------------------------------------------------

def _model_candidates(coop: str, mix: str, regime: str = "vmem"):
    """The (probe, coop, mix) candidate grid under optional pinning.
    coop="subtile" supersedes the probe strategy in the kernels, so
    cooperative candidates are canonicalized to probe="gather" — one
    spelling per distinct schedule, no duplicate cache entries. Order
    breaks predicted-cost ties toward the non-coop baseline and the full
    mix's cheap sibling is ranked by its strictly-lower flop count.
    Off the CPU only schedules the chip's compiler accepts are offered
    (``sbf.lowers``); a pin that leaves none raises."""
    from repro.kernels import ops
    from repro.kernels.sbf import lowers
    coops = ("none", "subtile") if coop == "auto" else (coop,)
    mixes = ("cheap", "full") if mix == "auto" else (mix,)
    out = []
    for c in coops:
        probes = ("gather", "loop") if c == "none" else ("gather",)
        for p in probes:
            if not (ops._interpret() or lowers(regime, p, c)):
                continue
            for m in mixes:
                out.append((p, c, m))
    if not out:
        raise ValueError(f"coop={coop!r} has no schedule that lowers for "
                         f"{jax.default_backend()} in the {regime} regime")
    return out


@functools.lru_cache(maxsize=256)
def tune_plan(spec: FilterSpec, op: str = "contains", regime: str = "vmem",
              mode: str = "structural", n_keys: int = 1024, repeats: int = 3,
              tile: int = DEFAULT_TILE, bank: int = 1, coop: str = "auto",
              mix: str = "auto") -> Plan:
    """Pick (layout, probe, coop, mix, depth, n_segments) for a
    (spec, op, regime).

    Checks the disk cache first; a miss runs the sweep and persists the
    winner, so every process on a host converges to one tuned plan per
    configuration. The default (non-measure) mode ranks the full
    (layout x probe x coop x mix x depth) candidate grid by the
    calibrated performance model's predicted cost
    (``perfmodel.predict_config_us``) — the structural scorers survive as
    the legacy ``tune_layout`` path and for diagnostics, but plan
    selection is model-driven.

    ``coop``/``mix``: ``"auto"`` sweeps both axes; a pinned value
    restricts the grid (and keys the cache entry — see ``_plan_key``).
    ``mode="measure"`` still times the actual kernels for the probe/depth
    axes and keeps the pinned-or-baseline coop/mix (measuring the
    cooperative kernels adds nothing off-TPU where every path is
    interpret-mode).

    ``bank`` keys the plan to a B-member :class:`FilterBank` workload: the
    model scales the loop probe's per-trip cost by the bank's deeper
    working set while the gather probe stays whole-tile constant.
    """
    assert op in ("contains", "add") and bank >= 1
    from repro.kernels import ops
    from repro.kernels.sbf import COOPS, DMA_DEPTHS, MIXES, PROBES, lowers
    assert coop == "auto" or coop in COOPS, coop
    assert mix == "auto" or mix in MIXES, mix
    key = _plan_key(spec, op, regime, mode, tile, bank, coop, mix)
    cached = _load_disk().get(key)
    if cached is not None:
        try:
            plan = Plan.from_dict(cached)
            # Re-validate against the CURRENT constraint sets — a stale
            # entry from an older library version (depth no longer in the
            # sweep, renamed probe/coop/mix, Θ that stopped dividing the
            # tile) must re-tune, not crash every probe="auto" call until
            # the user deletes the cache file by hand.
            if (plan.probe in PROBES and plan.depth in DMA_DEPTHS
                    and plan.n_segments in TUNABLE_SEGMENTS
                    and plan.coop in COOPS and plan.mix in MIXES
                    and (ops._interpret()
                         or lowers(regime, plan.probe, plan.coop))):
                plan.layout.validate(spec, tile)
                return plan
        except (KeyError, ValueError, TypeError, AssertionError):
            pass                   # stale/corrupt entry: re-tune
    layout, _ = tune_layout(spec, op, mode=mode, n_keys=n_keys,
                            repeats=repeats, tile=tile)
    if mode == "measure":
        if regime == "vmem" and ops._interpret():
            t_loop = _measure(spec, op, n_keys, repeats, layout=layout,
                              tile=tile, probe="loop", regime="vmem")
            t_gather = _measure(spec, op, n_keys, repeats, tile=tile,
                                probe="gather", regime="vmem")
            probe = "gather" if t_gather <= t_loop else "loop"
        else:
            probe = "gather" if regime == "hbm" else "loop"
        if regime == "hbm" and op == "contains":
            timed = {d: _measure(spec, op, n_keys, repeats, regime="hbm",
                                 tile=tile, depth=d) for d in TUNABLE_DEPTHS}
            depth = min(timed, key=timed.get)
        else:
            depth = min(TUNABLE_DEPTHS,
                        key=lambda d: depth_structural_score(spec, d))
        best_coop = coop if coop != "auto" else "none"
        best_mix = mix if mix != "auto" else "full"
    else:
        from repro import perfmodel as PM
        calib = PM.get_calibration()

        def score(p, c, m, d):
            t = PM.predict_config_us(spec, op, regime, layout=layout,
                                     probe=p, coop=c, mix=m, depth=d,
                                     tile=tile, bank=bank, calib=calib)
            flops = PM.op_cost(spec, op, regime, layout=layout, probe=p,
                               coop=c, mix=m, depth=d, tile=tile,
                               n_keys=tile, bank=bank).flops
            return (t, flops)      # flop tie-break: cheap mix wins ties

        cands = _model_candidates(coop, mix, regime)
        probe, best_coop, best_mix = min(
            cands, key=lambda pcm: score(*pcm, 2))
        depth = min(TUNABLE_DEPTHS,
                    key=lambda d: score(probe, "none", best_mix, d))
    n_segments = min(TUNABLE_SEGMENTS,
                     key=lambda ns: segments_structural_score(spec, ns))
    plan = Plan(layout=layout, probe=probe, depth=depth,
                n_segments=n_segments, coop=best_coop, mix=best_mix)
    _store_disk(key, plan.to_dict())
    return plan
