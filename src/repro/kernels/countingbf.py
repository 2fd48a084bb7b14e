"""Pallas TPU kernels for the counting Bloom filter (countingbf).

Same Θ layout machinery and residency regimes as ``kernels.sbf``, but
every logical bit is a packed 4-bit saturating counter, so three things
change:

* rows are **4s counter words** per block instead of s bit words;
* the per-key op is a **read-modify-write with carry-free nibble
  arithmetic** (``sat_inc_word`` / ``guard_dec_word`` from core.variants —
  plain vector ops, so the identical helpers run in the jnp reference);
* **padding is valid-masked, never repeat-key**: counting updates are not
  OR-idempotent, so a repeated padding key would double-count. Invalid
  slots carry an all-zero increment row (RMW no-op).

Ownership replaces atomics exactly as for the bit kernels: sequential-grid
RMW in the vmem/hbm paths, and a PARALLEL grid over exclusively-owned
filter segments in the partitioned path (``update_partitioned``) — the
TPU answer to the GPU's per-counter ``atomicAdd``/``atomicSub``
(DESIGN.md §10). Decrements ride the same partitioned path as increments.

All kernels validate bit-exactly against ``core.variants.counting_*`` /
``counting_update_loop`` in interpret mode (tests/test_counting.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing as H
from repro.core import variants as V
from repro.core.variants import FilterSpec
from repro.kernels import sbf
from repro.kernels.sbf import (COOPS, DEFAULT_DMA_DEPTH, DEFAULT_TILE,
                               DMA_DEPTHS, Layout, MIXES, PROBES,
                               _check_lowers, _hash_streams)


def _cfingerprints(spec: FilterSpec, keys: jnp.ndarray,
                   valid: jnp.ndarray = None, mix: str = "full"):
    """Lockstep phase 1 for counting kernels.

    Returns (cstarts[int32], cmasks[uint32 (n, 4s)]): counter-row starts and
    nibble-increment words, already valid-masked (padded slots -> all-zero
    rows, an RMW no-op)."""
    h1, h2 = _hash_streams(keys, mix)
    blk = H.block_index(h2, spec.n_blocks)
    masks = V.block_patterns(spec, h1, batched=False)
    cmasks = V.expand_mask_words(masks)                       # (n, 4s)
    if valid is not None:
        cmasks = cmasks * valid.astype(jnp.uint32)[:, None]
    cstarts = (blk * jnp.uint32(spec.counter_row_words)).astype(jnp.int32)
    return cstarts, cmasks


def counting_fingerprints(spec: FilterSpec, keys: jnp.ndarray,
                          mix: str = "full", valid: jnp.ndarray = None):
    """Phase 1 in XLA for the compiled kernels: (counter-row starts (n,)
    int32, nibble-increment words (n, 4s)), valid-masked when ``valid`` is
    given. For a probe the increment words double as the occupancy masks:
    a key is present where ``nib_nonzero(row)`` covers them."""
    starts, masks = sbf.fingerprints(spec, keys, mix)
    cmasks = V.expand_mask_words(masks)
    if valid is not None:
        cmasks = cmasks * valid.astype(jnp.uint32)[:, None]
    return starts * jnp.int32(V.COUNTER_WORDS_PER_WORD), cmasks


def _update(op: str):
    return V.sat_inc_word if op == "add" else V.guard_dec_word


# ---------------------------------------------------------------------------
# Whole-tile gather-probe kernels (probe="gather") — counting analogue
# ---------------------------------------------------------------------------
# Counting updates cannot use the bit filters' segment OR: increments are
# not idempotent. The conflict-free construction is instead a segmented
# SATURATING NIBBLE ADD (`nib_sat_add_words` — associative because
# min(Σ, 15) is grouping-independent for nonnegative nibbles): all
# same-block increment rows collapse to one total row, then ONE row gather
# + ONE row scatter applies min(old + total, 15) (add) or the guarded
# where(old == 15, 15, max(old - total, 0)) (remove) — bit-exact against
# the sequential per-key kernels because counts clip at 15 either way.

def _accumulate(op: str):
    return V.nib_sat_add_words if op == "add" else V.nib_guard_sub_words


def _update_vmem_gather_kernel(keys_ref, valid_ref, filt_ref, out_ref, *,
                               spec: FilterSpec, tile: int, op: str,
                               mix: str):
    cs = spec.counter_row_words
    apply = _accumulate(op)

    @pl.when(pl.program_id(0) == 0)
    def _seed():
        out_ref[...] = filt_ref[...]

    cstarts, cmasks = _cfingerprints(spec, keys_ref[...], valid_ref[...],
                                     mix=mix)
    blk = jax.lax.div(cstarts, jnp.int32(cs))
    order = jnp.argsort(blk)
    sb = blk[order]
    totals = V.segment_totals(sb, cmasks[order], V.nib_sat_add_words)
    f2d = out_ref[...].reshape(-1, cs)
    rows = jnp.take(f2d, sb, axis=0)
    out_ref[...] = f2d.at[sb].set(apply(rows, totals)).reshape(-1)


def _contains_vmem_gather_kernel(keys_ref, filt_ref, out_ref, *,
                                 spec: FilterSpec, tile: int, mix: str):
    cs = spec.counter_row_words
    h1, h2 = _hash_streams(keys_ref[...], mix)
    blk = H.block_index(h2, spec.n_blocks).astype(jnp.int32)
    masks = V.block_patterns(spec, h1, batched=False)          # logical (n, s)
    rows = jnp.take(filt_ref[...].reshape(-1, cs), blk, axis=0)  # (tile, 4s)
    occ = V.collapse_counter_words(rows)                       # (tile, s)
    out_ref[...] = jnp.all((occ & masks) == masks, axis=-1)


# ---------------------------------------------------------------------------
# Cooperative sub-tile kernels (coop="subtile") — counting analogue
# ---------------------------------------------------------------------------
# Same cooperative tiling as sbf, at COUNTER-WORD granularity:
#
# * update: every (key, counter word) pair becomes one lane of a
#   (tile*4s,) flat stream, sorted by absolute counter-word index and
#   collapsed with the segmented saturating nibble add — one flat gather +
#   one conflict-free flat scatter per tile, each unique counter WORD
#   touched once (the "none" gather engine collapses at row granularity).
#   Bit-exact because min(Σ, 15) is grouping-independent for nonnegative
#   nibbles, per word exactly as per row.
# * contains: column-major early-exit over LOGICAL word columns — column c
#   gathers its 4 counter words, collapses them to the occupancy word, and
#   folds the test into the per-key alive mask under a lax.cond ballot.

def _update_vmem_coop_kernel(keys_ref, valid_ref, filt_ref, out_ref, *,
                             spec: FilterSpec, tile: int, op: str, mix: str):
    cs = spec.counter_row_words
    apply = _accumulate(op)

    @pl.when(pl.program_id(0) == 0)
    def _seed():
        out_ref[...] = filt_ref[...]

    cstarts, cmasks = _cfingerprints(spec, keys_ref[...], valid_ref[...],
                                     mix=mix)
    idx = (cstarts[:, None]
           + jax.lax.broadcasted_iota(jnp.int32, (tile, cs), 1)
           ).reshape(tile * cs)
    vals = cmasks.reshape(tile * cs)
    order = jnp.argsort(idx)
    si = idx[order]
    tot = V.segment_totals(si, vals[order][:, None], V.nib_sat_add_words)[:, 0]
    f = out_ref[...]
    words = jnp.take(f, si, axis=0)
    # duplicate indices carry identical segment totals -> deterministic set
    out_ref[...] = f.at[si].set(apply(words, tot))


def _contains_vmem_coop_kernel(keys_ref, filt_ref, out_ref, *,
                               spec: FilterSpec, tile: int, mix: str):
    cs = spec.counter_row_words
    h1, h2 = _hash_streams(keys_ref[...], mix)
    blk = H.block_index(h2, spec.n_blocks)
    masks = V.block_patterns(spec, h1, batched=False)          # logical (n, s)
    cstarts = (blk * jnp.uint32(cs)).astype(jnp.int32)
    filt = filt_ref[...]
    alive = jnp.ones((tile,), jnp.bool_)
    for c in range(spec.s):                     # static unroll over columns
        m = masks[:, c]

        def probe_col(al, m=m, c=c):
            cw = jnp.stack([jnp.take(filt, cstarts + 4 * c + j, axis=0)
                            for j in range(4)], axis=-1)       # (tile, 4)
            occ = V.collapse_counter_words(cw)[:, 0]           # (tile,)
            return al & ((occ & m) == m)

        alive = jax.lax.cond(jnp.any(alive), probe_col, lambda al: al, alive)
    out_ref[...] = alive


def update_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                valid: jnp.ndarray, op: str, *, interpret: bool,
                layout: Layout = None, tile: int = DEFAULT_TILE,
                probe: str = "loop", coop: str = "none",
                mix: str = "full") -> jnp.ndarray:
    """Bulk increment/decrement, whole counter array pinned in VMEM."""
    n = keys.shape[0]
    assert n % tile == 0
    assert probe in PROBES, probe
    assert coop in COOPS, coop
    assert mix in MIXES, mix
    # An explicitly-passed layout is validated regardless of probe — the
    # gather engine ignores it, but never silently accepts an invalid one.
    _check_lowers("vmem", probe, coop, interpret)
    layout = (layout or sbf.default_layout(spec, op)).validate(spec, tile)
    if coop == "none" and probe == "loop":
        starts, cmasks = counting_fingerprints(spec, keys, mix, valid)
        return sbf.update_rows_vmem(filt, starts, cmasks, theta=layout.theta,
                                    tile=tile, interpret=interpret,
                                    apply=_update(op))
    if coop == "subtile":      # cooperative schedule supersedes the probe
        kern = functools.partial(_update_vmem_coop_kernel, spec=spec,
                                 tile=tile, op=op, mix=mix)
    else:
        kern = functools.partial(_update_vmem_gather_kernel, spec=spec,
                                 tile=tile, op=op, mix=mix)
    return pl.pallas_call(
        kern,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),
            pl.BlockSpec((tile,), lambda i: (i,)),              # valid mask
            pl.BlockSpec((spec.storage_words,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((spec.storage_words,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((spec.storage_words,), jnp.uint32),
        interpret=interpret,
    )(keys, valid, filt)


def contains_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray, *,
                  interpret: bool, layout: Layout = None,
                  tile: int = DEFAULT_TILE, probe: str = "loop",
                  coop: str = "none", mix: str = "full") -> jnp.ndarray:
    n = keys.shape[0]
    assert n % tile == 0
    assert probe in PROBES, probe
    assert coop in COOPS, coop
    assert mix in MIXES, mix
    _check_lowers("vmem", probe, coop, interpret)
    layout = (layout or sbf.default_layout(spec, "contains")).validate(
        spec, tile)
    if coop == "none" and probe == "loop":
        starts, cmasks = counting_fingerprints(spec, keys, mix)
        return sbf.probe_rows_vmem(filt, starts, cmasks, theta=layout.theta,
                                   tile=tile, interpret=interpret,
                                   held=V.nib_nonzero)
    if coop == "subtile":      # cooperative schedule supersedes the probe
        kern = functools.partial(_contains_vmem_coop_kernel, spec=spec,
                                 tile=tile, mix=mix)
    else:
        kern = functools.partial(_contains_vmem_gather_kernel, spec=spec,
                                 tile=tile, mix=mix)
    return pl.pallas_call(
        kern,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),
            pl.BlockSpec((spec.storage_words,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.bool_),
        interpret=interpret,
    )(keys, filt)


# ---------------------------------------------------------------------------
# Bank kernels — B VMEM-resident counter filters, ONE launch
# ---------------------------------------------------------------------------
# Counting analogue of sbf's bank kernels: the (B, 4*n_words) counter bank
# is flattened and every counter-row start is offset by
# member * storage_words, so a whole multi-tenant bank updates/queries in a
# single pallas_call. Updates are valid-masked as always (counting is not
# idempotent) and same-row increments collapse through the segmented
# saturating nibble add before the one row scatter (gather probe).

def _bank_cstarts(spec: FilterSpec, keys, member, valid=None,
                  mix: str = "full"):
    cstarts, cmasks = _cfingerprints(spec, keys, valid, mix=mix)
    return cstarts + member * jnp.int32(spec.storage_words), cmasks


def _bank_update_vmem_gather_kernel(keys_ref, member_ref, valid_ref, filt_ref,
                                    out_ref, *, spec: FilterSpec, tile: int,
                                    bank: int, op: str, mix: str):
    cs = spec.counter_row_words
    apply = _accumulate(op)

    @pl.when(pl.program_id(0) == 0)
    def _seed():
        out_ref[...] = filt_ref[...]

    cstarts, cmasks = _bank_cstarts(spec, keys_ref[...], member_ref[...],
                                    valid_ref[...], mix=mix)
    blk = jax.lax.div(cstarts, jnp.int32(cs))       # member-offset row ids
    order = jnp.argsort(blk)
    sb = blk[order]
    totals = V.segment_totals(sb, cmasks[order], V.nib_sat_add_words)
    f2d = out_ref[...].reshape(bank * spec.n_blocks, cs)
    rows = jnp.take(f2d, sb, axis=0)
    out_ref[...] = f2d.at[sb].set(apply(rows, totals)).reshape(-1)


def bank_update_vmem(spec: FilterSpec, bank: jnp.ndarray, keys: jnp.ndarray,
                     member: jnp.ndarray, valid: jnp.ndarray, op: str, *,
                     interpret: bool, layout: Layout = None,
                     tile: int = DEFAULT_TILE, probe: str = "gather",
                     mix: str = "full") -> jnp.ndarray:
    """Flat routed counter update of a (B, storage_words) bank — one launch."""
    n = keys.shape[0]
    assert n % tile == 0 and member.shape == (n,) and valid.shape == (n,)
    assert probe in PROBES, probe
    assert mix in MIXES, mix
    _check_lowers("vmem", probe, "none", interpret)
    B, flat = bank.shape[0], bank.reshape(-1)
    layout = (layout or sbf.default_layout(spec, op)).validate(spec, tile)
    member = member.astype(jnp.int32)
    if probe == "loop":
        starts, cmasks = counting_fingerprints(spec, keys, mix, valid)
        out = sbf.update_rows_vmem(
            flat, starts + member * jnp.int32(spec.storage_words), cmasks,
            theta=layout.theta, tile=tile, interpret=interpret,
            apply=_update(op))
        return out.reshape(B, spec.storage_words)
    out = pl.pallas_call(
        functools.partial(_bank_update_vmem_gather_kernel, spec=spec,
                          tile=tile, bank=B, op=op, mix=mix),
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),
            pl.BlockSpec((tile,), lambda i: (i,)),               # member ids
            pl.BlockSpec((tile,), lambda i: (i,)),               # valid mask
            pl.BlockSpec((B * spec.storage_words,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((B * spec.storage_words,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((B * spec.storage_words,), jnp.uint32),
        interpret=interpret,
    )(keys, member, valid, flat)
    return out.reshape(B, spec.storage_words)


def bank_contains_vmem(spec: FilterSpec, bank: jnp.ndarray, keys: jnp.ndarray,
                       member: jnp.ndarray, *, interpret: bool,
                       tile: int = DEFAULT_TILE,
                       mix: str = "full") -> jnp.ndarray:
    """Flat routed occupancy membership against a counter bank — one
    launch of the per-key row probe."""
    n = keys.shape[0]
    assert n % tile == 0 and member.shape == (n,)
    assert mix in MIXES, mix
    starts, cmasks = counting_fingerprints(spec, keys, mix)
    starts = starts + member.astype(jnp.int32) * jnp.int32(spec.storage_words)
    return sbf.probe_rows_vmem(bank.reshape(-1), starts, cmasks,
                               theta=1, tile=tile, interpret=interpret,
                               held=V.nib_nonzero)


# ---------------------------------------------------------------------------
# HBM-resident and partitioned paths — the sbf row engine on counter rows
# ---------------------------------------------------------------------------

def update_hbm(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
               valid: jnp.ndarray, op: str, *, interpret: bool,
               tile: int = DEFAULT_TILE, coop: str = "none",
               mix: str = "full") -> jnp.ndarray:
    """Row-sorted RMW of DMA-streamed counter rows. Like sbf.add_hbm the
    schedule is already cooperative: one read and one write per unique row
    per tile, the tile's reads in flight together and its writes drained
    at the tile's end. A run's keys still apply in order to the one copy
    of its row, so the saturating counts stay exact; coop is validated
    and both values run the same kernel."""
    n = keys.shape[0]
    assert n % tile == 0
    assert coop in COOPS, coop
    assert mix in MIXES, mix
    starts, cmasks = counting_fingerprints(spec, keys, mix, valid)
    return sbf.update_rows_hbm(filt, starts, cmasks, tile=tile,
                               interpret=interpret, apply=_update(op))


def contains_hbm(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                 *, interpret: bool,
                 tile: int = DEFAULT_TILE,
                 depth: int = DEFAULT_DMA_DEPTH, coop: str = "none",
                 mix: str = "full") -> jnp.ndarray:
    n = keys.shape[0]
    assert n % tile == 0
    assert depth in DMA_DEPTHS, f"depth={depth} not in {DMA_DEPTHS}"
    assert coop in COOPS, coop
    assert mix in MIXES, mix
    starts, cmasks = counting_fingerprints(spec, keys, mix)
    return sbf.probe_rows_hbm(filt, starts, cmasks, tile=tile, depth=depth,
                              coop=coop, interpret=interpret,
                              held=V.nib_nonzero)


def update_partitioned(spec: FilterSpec, filt: jnp.ndarray,
                       keys_by_seg: jnp.ndarray, valid: jnp.ndarray,
                       n_segments: int, op: str, *, interpret: bool,
                       mix: str = "full") -> jnp.ndarray:
    """keys_by_seg: (n_segments, capacity, 2); valid: (n_segments, capacity).

    One PARALLEL grid step owns one counter segment exclusively: the path
    that replaces the GPU's atomicAdd/atomicSub for counter updates."""
    assert spec.storage_words % n_segments == 0
    assert mix in MIXES, mix
    seg_cwords = spec.storage_words // n_segments
    starts, cmasks = counting_fingerprints(spec, keys_by_seg.reshape(-1, 2),
                                           mix, valid.reshape(-1))
    return sbf.update_rows_partitioned(
        filt, jax.lax.rem(starts, jnp.int32(seg_cwords)), cmasks, n_segments,
        interpret=interpret, apply=_update(op))


# ---------------------------------------------------------------------------
# Decay — embarrassingly parallel elementwise aging pass
# ---------------------------------------------------------------------------

def _decay_kernel(filt_ref, out_ref):
    out_ref[...] = V.decay_word(filt_ref[...])


def decay(spec: FilterSpec, filt: jnp.ndarray, *, interpret: bool,
          tile_words: int = 4096) -> jnp.ndarray:
    """One aging step over the whole counter array (PARALLEL word tiles)."""
    nw = spec.storage_words
    tile_words = min(tile_words, nw)
    assert nw % tile_words == 0
    return pl.pallas_call(
        _decay_kernel,
        grid=(nw // tile_words,),
        in_specs=[pl.BlockSpec((tile_words,), lambda i: (i,))],
        out_specs=pl.BlockSpec((tile_words,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((nw,), jnp.uint32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(filt)
