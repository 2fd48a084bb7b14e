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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import variants as V
from repro.core.variants import FilterSpec
from repro.kernels import sbf
from repro.kernels.sbf import DEFAULT_TILE, Layout


def counting_fingerprints(spec: FilterSpec, keys: jnp.ndarray,
                          valid: jnp.ndarray = None):
    """Phase 1 in XLA for the compiled kernels: (counter-row starts (n,)
    int32, nibble-increment words (n, 4s)), valid-masked when ``valid`` is
    given (padded slots get an all-zero row, an RMW no-op). For a probe
    the increment words double as the occupancy masks: a key is present
    where ``nib_nonzero(row)`` covers them."""
    starts, masks = sbf.fingerprints(spec, keys)
    cmasks = V.expand_mask_words(masks)
    if valid is not None:
        cmasks = cmasks * valid.astype(jnp.uint32)[:, None]
    return starts * jnp.int32(V.COUNTER_WORDS_PER_WORD), cmasks


def _update(op: str):
    return V.sat_inc_word if op == "add" else V.guard_dec_word


def update_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                valid: jnp.ndarray, op: str, *, interpret: bool,
                layout: Layout = None,
                tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Bulk increment/decrement, whole counter array pinned in VMEM."""
    assert keys.shape[0] % tile == 0
    layout = (layout or sbf.default_layout(spec, op)).validate(spec, tile)
    starts, cmasks = counting_fingerprints(spec, keys, valid)
    return sbf.update_rows_vmem(filt, starts, cmasks, theta=layout.theta,
                                tile=tile, interpret=interpret,
                                apply=_update(op))


def contains_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray, *,
                  interpret: bool, layout: Layout = None,
                  tile: int = DEFAULT_TILE) -> jnp.ndarray:
    assert keys.shape[0] % tile == 0
    layout = (layout or sbf.default_layout(spec, "contains")).validate(
        spec, tile)
    starts, cmasks = counting_fingerprints(spec, keys)
    return sbf.probe_rows_vmem(filt, starts, cmasks, theta=layout.theta,
                               tile=tile, interpret=interpret,
                               held=V.nib_nonzero)


# ---------------------------------------------------------------------------
# Bank kernels — B VMEM-resident counter filters, ONE launch
# ---------------------------------------------------------------------------
# Counting analogue of sbf's bank kernels: the (B, 4*n_words) counter bank
# is flattened and every counter-row start is offset by
# member * storage_words, so a whole multi-tenant bank updates/queries in a
# single pallas_call. Updates are valid-masked as always (counting is not
# idempotent).

def bank_update_vmem(spec: FilterSpec, bank: jnp.ndarray, keys: jnp.ndarray,
                     member: jnp.ndarray, valid: jnp.ndarray, op: str, *,
                     interpret: bool, layout: Layout = None,
                     tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Flat routed counter update of a (B, storage_words) bank — one launch."""
    n = keys.shape[0]
    assert n % tile == 0 and member.shape == (n,) and valid.shape == (n,)
    layout = (layout or sbf.default_layout(spec, op)).validate(spec, tile)
    starts, cmasks = counting_fingerprints(spec, keys, valid)
    out = sbf.update_rows_vmem(
        bank.reshape(-1),
        starts + member.astype(jnp.int32) * jnp.int32(spec.storage_words),
        cmasks, theta=layout.theta, tile=tile, interpret=interpret,
        apply=_update(op))
    return out.reshape(bank.shape[0], spec.storage_words)


def bank_contains_vmem(spec: FilterSpec, bank: jnp.ndarray, keys: jnp.ndarray,
                       member: jnp.ndarray, *, interpret: bool,
                       tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Flat routed occupancy membership against a counter bank — one
    launch of the per-key row probe."""
    n = keys.shape[0]
    assert n % tile == 0 and member.shape == (n,)
    starts, cmasks = counting_fingerprints(spec, keys)
    starts = starts + member.astype(jnp.int32) * jnp.int32(spec.storage_words)
    return sbf.probe_rows_vmem(bank.reshape(-1), starts, cmasks,
                               theta=1, tile=tile, interpret=interpret,
                               held=V.nib_nonzero)


# ---------------------------------------------------------------------------
# HBM-resident and partitioned paths — the sbf row engine on counter rows
# ---------------------------------------------------------------------------

def update_hbm(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
               valid: jnp.ndarray, op: str, *, interpret: bool,
               tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Row-sorted RMW of DMA-streamed counter rows, as sbf.add_hbm: one
    read and one write per unique row per tile, the tile's reads in
    flight together and its writes drained at the tile's end. A run's keys
    apply in order to the one copy of its row, so the saturating counts
    stay exact."""
    assert keys.shape[0] % tile == 0
    starts, cmasks = counting_fingerprints(spec, keys, valid)
    return sbf.update_rows_hbm(filt, starts, cmasks, tile=tile,
                               interpret=interpret, apply=_update(op))


def contains_hbm(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                 *, interpret: bool,
                 tile: int = DEFAULT_TILE) -> jnp.ndarray:
    assert keys.shape[0] % tile == 0
    starts, cmasks = counting_fingerprints(spec, keys)
    return sbf.probe_rows_hbm(filt, starts, cmasks, tile=tile,
                              interpret=interpret, held=V.nib_nonzero)


def update_partitioned(spec: FilterSpec, filt: jnp.ndarray,
                       keys_by_seg: jnp.ndarray, valid: jnp.ndarray,
                       n_segments: int, op: str, *,
                       interpret: bool) -> jnp.ndarray:
    """keys_by_seg: (n_segments, capacity, 2); valid: (n_segments, capacity).

    One PARALLEL grid step owns one counter segment exclusively: the path
    that replaces the GPU's atomicAdd/atomicSub for counter updates."""
    assert spec.storage_words % n_segments == 0
    seg_cwords = spec.storage_words // n_segments
    starts, cmasks = counting_fingerprints(spec, keys_by_seg.reshape(-1, 2),
                                           valid.reshape(-1))
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
