"""Arithmetic shared by the per-layer readers in ``bench/layers``."""
from __future__ import annotations


def _trace_of(ctx, kind: str):
    if ctx.trace is None or ctx.window.kind != kind:
        return None
    return ctx.trace


def idle_share(ctx, kind: str):
    t = _trace_of(ctx, kind)
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share


def ns_per_key(ctx, kind: str, op_kind: str):
    """Device seconds of one kind of operation, summed over the chips
    used, per key of the window (ns)."""
    t = _trace_of(ctx, kind)
    if t is None or not ctx.window.keys:
        return None
    seconds = t.kind_s.get(op_kind, 0.0) * t.n_devices
    if seconds <= 0:
        return None
    return 1e9 * seconds / ctx.window.keys


def roofline(ctx, kind: str):
    t = _trace_of(ctx, kind)
    if t is None or t.busy_s <= 0 or not ctx.window.bytes_needed:
        return None
    least_s = ctx.window.bytes_needed / (ctx.peaks["hbm_bytes_per_s"]
                                         * t.n_devices)
    return 100.0 * least_s / t.busy_s
