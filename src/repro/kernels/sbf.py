"""Pallas TPU kernels for blocked Bloom filter variants (BBF/RBBF/SBF/CSBF).

This is the TPU-native realization of the paper's §4 design space:

* **Two phases.** Phase 1 hashes the whole key batch in XLA, before the
  kernel: every key gets its block start and its (s,) word masks
  (:func:`fingerprints`). Phase 2 is the kernel: a per-key walk that
  reads the key's block start from SMEM as a scalar and tests or ORs its
  masks against the filter. Keeping block starts scalar is what the
  chip's compiler needs: a vector value cannot be indexed dynamically
  inside a kernel, a ref can.
* **Deferred test.** A probe does not reduce each key's test to a scalar
  inside the walk: each key stores the bits it misses into its row of a
  ``(tile, lanes)`` scratch, and the tile's answers come from one
  transpose and one reduction after the walk (:func:`_test_keys`). The
  keys of a loop trip are then independent chains, with no vector-to-scalar
  move and no loop-carried value between them, and a trip places all its
  keys' masks before it waits for the first row.
* **Row layout.** The filter words are viewed as ``(n_words / L, L)`` rows
  of ``L = min(128, n_words)`` lanes (:func:`row_view`, a free reshape).
  A block of s words never straddles a row, so a probe is one aligned
  row load at a dynamic sublane offset plus a lane rotate of the key's
  masks into place. Masks arrive lane-dense (``128 / s`` keys per row)
  so the mask stream costs s words per key, not a whole row.
* **Θ layout** (`Layout`): Θ keys are unrolled per loop trip. The paper's
  second knob, Φ words per load, has no counterpart: a probe always loads
  the whole row holding the block.
* **Residency regimes** (§5.2/§5.3): ``*_vmem`` kernels pin the whole filter
  in VMEM via its BlockSpec (the L2-cache-resident analogue); ``*_hbm``
  kernels leave the filter in HBM (``pl.ANY``) and stream rows through a
  DMA scratch (the DRAM-resident analogue). Sorting keys by block for the
  HBM schedules runs in XLA before the kernel.
* **Ownership instead of atomics**: TPU Pallas grids execute sequentially on
  a core, and the partitioned bulk path gives each grid step an exclusive
  filter segment, so read-modify-write needs no atomics (DESIGN.md §2).
  The HBM update keeps a tile's row DMAs in flight together: the runs of
  a row-sorted tile are distinct rows, and the tile waits for its writes
  before the next tile reads, which orders a row shared across the edge.

The whole-tile gather probe and the VMEM cooperative kernels use an
in-kernel gather or sort, which the chip's compiler refuses; they run only
in interpret mode (:func:`lowers`). All kernels are validated bit-exactly
against ``repro.core.variants`` in interpret mode.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing as H
from repro.core import variants as V
from repro.core.variants import FilterSpec

DEFAULT_TILE = 256
# VMEM-regime budget for the filter words (bytes). The add kernels hold the
# filter twice (input and output blocks); both compile for v5e at this edge
# (tests/test_tpu_compile.py).
VMEM_FILTER_BYTES = 4 * 1024 * 1024
LANES = 128


def _is_pow2(x: int) -> bool:
    return x > 0 and (x & (x - 1)) == 0


@dataclasses.dataclass(frozen=True)
class Layout:
    """Θ, the keys processed per inner step (horizontal; Θ probes are
    unrolled into one loop trip). The paper's vertical Φ (words per load)
    does not exist here: every probe loads the whole row holding its block.
    """
    theta: int = 1

    def validate(self, spec: FilterSpec, tile: int) -> "Layout":
        assert _is_pow2(self.theta), self.theta
        assert tile % self.theta == 0, f"theta={self.theta} must divide tile={tile}"
        return self

    def __str__(self):
        return f"Θ{self.theta}"


def default_layout(spec: FilterSpec, op: str) -> Layout:
    """The paper's empirically-optimal layouts (§5.2), re-expressed for S=32.

    contains: Θ̂ = max(1, B/256) — one "thread" per 256-bit sector;
    add:      Θ̂ = s — fully horizontal maximizes temporal locality of the
              word updates (our analogue: tightest RMW grouping per block).
    """
    if op == "contains":
        return Layout(min(max(1, spec.block_bits // 256), 8))
    return Layout(min(spec.s, 8))


# ---------------------------------------------------------------------------
# Phase 1 — lockstep fingerprint generation (shared by all kernels)
# ---------------------------------------------------------------------------

def _hash_streams(keys: jnp.ndarray, mix: str):
    """(pattern, block) hash pair under the chosen mixing schedule.

    ``mix="full"`` evaluates the two seeded xxh32 streams independently;
    ``mix="cheap"`` shares the seed-independent lane products between them
    (one wide mix feeding all k indices) — bit-identical outputs either
    way (see ``hashing.xxh32_u64x2_pair``)."""
    assert mix in MIXES, mix
    if mix == "cheap":
        return H.xxh32_u64x2_pair(keys)
    return (H.xxh32_u64x2(keys, H.SEED_PATTERN),
            H.block_hash(keys))


def _fingerprints(spec: FilterSpec, keys: jnp.ndarray, mix: str = "full"):
    """In-kernel hash + pattern phase: (starts[int32], masks[uint32 (n,s)]).

    batched=False: inside a pallas_call the salts must stay scalar literals
    (kernel bodies may not capture array constants). Used by the
    interpret-only kernels; the row-layout kernels get both from
    :func:`fingerprints` in XLA."""
    h1, h2 = _hash_streams(keys, mix)
    blk = H.block_index(h2, spec.n_blocks)
    masks = V.block_patterns(spec, h1, batched=False)
    starts = (blk * jnp.uint32(spec.s)).astype(jnp.int32)
    return starts, masks


def fingerprints(spec: FilterSpec, keys: jnp.ndarray, mix: str = "full"):
    """Phase 1 in XLA: (starts (n,) int32, masks (n, s) uint32)."""
    h1, h2 = _hash_streams(keys, mix)
    blk = H.block_index(h2, spec.n_blocks)
    starts = (blk * jnp.uint32(spec.s)).astype(jnp.int32)
    return starts, V.block_patterns(spec, h1)


def _take_scalar(vec: jnp.ndarray, i) -> jnp.ndarray:
    return jax.lax.dynamic_index_in_dim(vec, i, keepdims=False)


def _mask_row(masks: jnp.ndarray, i, s: int) -> jnp.ndarray:
    return jax.lax.dynamic_slice(masks, (i, 0), (1, s))[0]


PROBES = ("loop", "gather")
# Cooperation axis (paper §4.3 / McCoy et al.): "none" keeps the per-key
# probe schedules; "subtile" shares one key's probe row across a lane
# sub-tile — column-major early-exit contains, word-granular flat-lane
# segmented adds, sorted row sharing in HBM. Every coop path is bit-exact
# with its "none" baseline.
COOPS = ("none", "subtile")
# Hash mixing schedule: "full" = two independent seeded xxh32 streams;
# "cheap" = one fused wide mix feeding both streams (bit-identical).
MIXES = ("full", "cheap")
DMA_DEPTHS = (1, 2, 4, 8)
DEFAULT_DMA_DEPTH = 2
# Keys a trip of the HBM probe loop takes: one sublane tile of its
# missing-bits scratch. Fixed, not tuned.
HBM_PROBE_UNROLL = 8


def lowers(regime: str, probe: str, coop: str) -> bool:
    """Whether the chip's compiler accepts this schedule. It has no
    in-kernel sort and no general gather, so in the VMEM regime only the
    per-key loop without cooperation lowers. Every HBM schedule does:
    their sort runs in XLA before the kernel."""
    return regime == "hbm" or (probe == "loop" and coop == "none")


def _check_lowers(regime: str, probe: str, coop: str, interpret: bool):
    if not (interpret or lowers(regime, probe, coop)):
        raise ValueError(
            f"probe={probe!r} coop={coop!r} does not lower for the TPU in "
            f"the {regime} regime (in-kernel gather/sort); use "
            f"probe='loop', coop='none'")


# ---------------------------------------------------------------------------
# Row layout shared by the compiled kernels
# ---------------------------------------------------------------------------

def row_lanes(n_words: int) -> int:
    return min(LANES, n_words)


def row_view(words: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """(n_words,) -> (n_words / lanes, lanes); a bitcast on TPU."""
    return words.reshape(-1, lanes)


def _tiles(x: jnp.ndarray, tile: int) -> jnp.ndarray:
    """(n,) -> (n / tile, 1, tile): one SMEM block per grid step."""
    return x.reshape(-1, 1, tile)


def _mask_tiles(masks: jnp.ndarray, tile: int, lanes: int) -> jnp.ndarray:
    """(n, s) masks -> (n / tile, rows, ml) lane-dense VMEM blocks: key i of
    a tile holds flat words [i*s, i*s + s) of its block, ``ml >= lanes``
    so one rotate can place them anywhere in a filter row."""
    n, s = masks.shape
    w = tile * s
    ml = max(min(LANES, w), lanes)
    m = masks.reshape(n // tile, w)
    pad = (-w) % ml
    if pad:
        m = jnp.pad(m, ((0, 0), (0, pad)))
    return m.reshape(n // tile, -1, ml)


def _key_mask(m_ref, i, col, s: int, lanes: int) -> jnp.ndarray:
    """(1, lanes) row holding key ``i``'s s mask words at lanes
    [col, col + s) and zeros elsewhere."""
    ml = m_ref.shape[-1]
    flat = i * s
    row = m_ref[0, pl.ds(flat // ml, 1), :]                      # (1, ml)
    shift = jax.lax.rem(col + ml - jax.lax.rem(flat, ml), ml)
    row = pltpu.roll(row, shift, 1)[:, :lanes]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    return jnp.where((lane >= col) & (lane < col + s), row, jnp.uint32(0))


def _locate(st_ref, i, lanes: int):
    """(row, lane) of the block starting at word ``st_ref[0, 0, i]``."""
    st = st_ref[0, 0, i]
    return st // lanes, jax.lax.rem(st, lanes)


def _held(row):
    """The bits a probe tests: the words themselves for bit filters
    (counting filters pass their nibble-occupancy view instead)."""
    return row


def _test_keys(out_ref, miss_ref, mask_of, row_of, *, n_keys: int,
               unroll: int, held):
    """The deferred membership test every probe kernel shares.

    ``mask_of(i)`` gives key ``i``'s mask placed in its lanes and
    ``row_of(i)`` the filter row holding its block, both ``(1, lanes)``.
    Inside the loop a key only stores the bits it misses,
    ``mask & ~held(row)``, into row ``i`` of ``miss_ref``, a
    ``(tile, lanes)`` scratch: no per-key reduction, no vector-to-scalar
    move and no loop-carried value, so the ``unroll`` keys of one trip are
    independent chains. A trip places all its keys' masks before it asks
    for the first row, so the lane rotates overlap the row waits. After the
    loop one transpose and one sublane reduction give the tile's
    ``(1, tile)`` answers: a key is present iff its row of missing bits is
    all zero.
    """
    def body(g, carry):
        keys = [g * unroll + t for t in range(unroll)]   # static unroll
        masks = [mask_of(i) for i in keys]
        for i, m in zip(keys, masks):
            miss_ref[pl.ds(i, 1), :] = m & ~held(row_of(i))
        return carry

    jax.lax.fori_loop(0, n_keys // unroll, body, jnp.int32(0))
    missing = (miss_ref[...] != 0).astype(jnp.int32)           # (tile, lanes)
    out_ref[0] = 1 - jnp.max(missing.T, axis=0, keepdims=True)


def _mask_at(st_ref, m_ref, s: int, lanes: int):
    """``mask_of`` for :func:`_test_keys`: key ``i``'s mask in the lanes
    of its block."""
    def mask_of(i):
        _, c = _locate(st_ref, i, lanes)
        return _key_mask(m_ref, i, c, s, lanes)
    return mask_of


def _probe_loop_kernel(st_ref, m_ref, filt_ref, out_ref, miss_ref, *, s: int,
                       theta: int, tile: int, held):
    """Membership against a VMEM-resident row view, Θ keys a loop trip,
    tested once per tile (:func:`_test_keys`)."""
    lanes = filt_ref.shape[-1]

    def row_of(i):
        r, _ = _locate(st_ref, i, lanes)
        return filt_ref[pl.ds(r, 1), :]

    _test_keys(out_ref, miss_ref, _mask_at(st_ref, m_ref, s, lanes), row_of,
               n_keys=tile, unroll=theta, held=held)


def _update_loop(st_ref, m_ref, out_ref, *, s: int, theta: int, n_keys: int,
                 apply):
    """Per-key read-modify-write ``row = apply(row, mask)`` of ``out_ref``
    rows. ``apply(row, 0)`` must leave the row unchanged: that is how the
    lanes outside a key's block, and padded or invalid keys, are masked."""
    lanes = out_ref.shape[-1]

    def group_body(g, carry):
        for t in range(theta):                      # static unroll over Θ
            i = g * theta + t
            r, c = _locate(st_ref, i, lanes)
            out_ref[pl.ds(r, 1), :] = apply(out_ref[pl.ds(r, 1), :],
                                            _key_mask(m_ref, i, c, s, lanes))
        return carry

    jax.lax.fori_loop(0, n_keys // theta, group_body, jnp.int32(0))


def _update_loop_kernel(st_ref, m_ref, filt_ref, out_ref, *, s: int,
                        theta: int, tile: int, apply):
    # Grid steps execute sequentially on a TPU core; the first step seeds the
    # output with the input filter, later steps accumulate into it (RMW —
    # ownership replaces the GPU's atomicOr, see DESIGN.md §2).
    @pl.when(pl.program_id(0) == 0)
    def _seed():
        out_ref[...] = filt_ref[...]

    _update_loop(st_ref, m_ref, out_ref, s=s, theta=theta, n_keys=tile,
                 apply=apply)


def _key_specs(tile: int, mask_tiles: jnp.ndarray, carried: bool = False):
    """Block specs of a tile's starts (SMEM) and masks. ``carried``: the
    grid's first operand is the scalar count of tiles that carry a key,
    and a step past it maps to the last such tile, which the pipeline
    then does not fetch again."""
    if carried:
        def index(i, n_carried):
            return jnp.minimum(i, jnp.maximum(n_carried[0] - 1, 0)), 0, 0
    else:
        def index(i):
            return i, 0, 0
    return [pl.BlockSpec((1, 1, tile), index,
                         memory_space=pltpu.SMEM),            # block starts
            pl.BlockSpec((1,) + mask_tiles.shape[1:], index)]  # lane-dense masks


def _carried_first(valid: jnp.ndarray, starts: jnp.ndarray, n_words: int,
                   s: int, tile: int):
    """Sort keys so that padded slots (``valid`` false) come after every
    carried key and carried keys are in row order. Returns (order, sorted
    starts, count of tiles holding a carried key); padded slots take the
    last block, so a tile's starts stay sorted."""
    key = jnp.where(valid, starts, n_words)
    order = jnp.argsort(key)
    n_carried = jnp.sum(valid.astype(jnp.int32))
    return (order, jnp.minimum(key[order], n_words - s),
            ((n_carried + tile - 1) // tile).reshape(1))


# The row engine. Every compiled kernel of this module and of countingbf.py
# is one of these five calls: ``words`` is the flat word array (a filter,
# a counter array, or a bank flattened to one), ``starts`` (n,) the first
# word of each key's row, ``masks`` (n, w) the w words to test or apply.

def probe_rows_vmem(words: jnp.ndarray, starts: jnp.ndarray,
                    masks: jnp.ndarray, *, theta: int, tile: int,
                    interpret: bool, held=_held) -> jnp.ndarray:
    """(n,) bool: probes of a VMEM-pinned row view of ``words``; each grid
    step stores its keys' missing bits in a ``(tile, lanes)`` scratch and
    tests the whole tile after its key loop."""
    n = starts.shape[0]
    lanes = row_lanes(words.shape[0])
    rows = row_view(words, lanes)
    mt = _mask_tiles(masks, tile, lanes)
    out = pl.pallas_call(
        functools.partial(_probe_loop_kernel, s=masks.shape[1], theta=theta,
                          tile=tile, held=held),
        grid=(n // tile,),
        in_specs=_key_specs(tile, mt) + [
            pl.BlockSpec(rows.shape, lambda i: (0, 0))],      # whole filter
        out_specs=pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n // tile, 1, tile), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tile, lanes), jnp.uint32)],  # missing bits
        interpret=interpret,
    )(_tiles(starts, tile), mt, rows)
    return out.reshape(n) != 0


def update_rows_vmem(words: jnp.ndarray, starts: jnp.ndarray,
                     masks: jnp.ndarray, *, theta: int, tile: int,
                     interpret: bool, apply=jnp.bitwise_or) -> jnp.ndarray:
    """Sequential-grid RMW of a VMEM-pinned row view; returns the updated
    flat words."""
    n = starts.shape[0]
    lanes = row_lanes(words.shape[0])
    rows = row_view(words, lanes)
    mt = _mask_tiles(masks, tile, lanes)
    out = pl.pallas_call(
        functools.partial(_update_loop_kernel, s=masks.shape[1], theta=theta,
                          tile=tile, apply=apply),
        grid=(n // tile,),
        in_specs=_key_specs(tile, mt) + [
            pl.BlockSpec(rows.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec(rows.shape, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.uint32),
        interpret=interpret,
    )(_tiles(starts, tile), mt, rows)
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# VMEM-resident kernels (cache-resident regime analogue)
# ---------------------------------------------------------------------------

def contains_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                  layout: Layout, *, interpret: bool, tile: int = DEFAULT_TILE,
                  probe: str = "loop", coop: str = "none",
                  mix: str = "full") -> jnp.ndarray:
    """Bulk membership test, whole filter pinned in VMEM via BlockSpec."""
    n = keys.shape[0]
    assert n % tile == 0
    assert probe in PROBES, probe
    assert coop in COOPS, coop
    assert mix in MIXES, mix
    _check_lowers("vmem", probe, coop, interpret)
    # An explicit layout is ALWAYS validated, even though the gather engine
    # ignores it — probe is a schedule choice and must never change which
    # (layout, tile) combinations are accepted.
    layout = layout.validate(spec, tile)
    if coop == "none" and probe == "loop":
        starts, masks = fingerprints(spec, keys, mix)
        return probe_rows_vmem(filt, starts, masks, theta=layout.theta,
                               tile=tile, interpret=interpret)
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
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),          # key tile
            pl.BlockSpec((spec.n_words,), lambda i: (0,)),      # whole filter
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.bool_),
        interpret=interpret,
    )(keys, filt)


def add_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
             layout: Layout, *, interpret: bool, tile: int = DEFAULT_TILE,
             probe: str = "loop", coop: str = "none",
             mix: str = "full") -> jnp.ndarray:
    """Bulk insert, whole filter pinned in VMEM; sequential-grid RMW."""
    n = keys.shape[0]
    assert n % tile == 0
    assert probe in PROBES, probe
    assert coop in COOPS, coop
    assert mix in MIXES, mix
    _check_lowers("vmem", probe, coop, interpret)
    layout = layout.validate(spec, tile)     # validated even on gather
    if coop == "none" and probe == "loop":
        starts, masks = fingerprints(spec, keys, mix)
        return update_rows_vmem(filt, starts, masks, theta=layout.theta,
                                tile=tile, interpret=interpret)
    if coop == "subtile":      # cooperative schedule supersedes the probe
        kern = functools.partial(_add_vmem_coop_kernel, spec=spec, tile=tile,
                                 mix=mix)
    else:
        kern = functools.partial(_add_vmem_gather_kernel, spec=spec,
                                 tile=tile, mix=mix)
    return pl.pallas_call(
        kern,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),
            pl.BlockSpec((spec.n_words,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((spec.n_words,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((spec.n_words,), jnp.uint32),
        interpret=interpret,
    )(keys, filt)


# ---------------------------------------------------------------------------
# Whole-tile gather-probe kernels (probe="gather")
# ---------------------------------------------------------------------------
# Interpret-only (see ``lowers``): these kernels hash in-kernel and keep
# phase 2 on the vector unit too. contains: build the full (tile, s)
# word-index matrix, ONE gather, ONE fused compare — no per-key loop at
# all. add: sort the tile by block, segment-OR the masks of same-block
# keys, then one row gather + one conflict-free row scatter (duplicate
# indices carry identical rows). The Θ layout is irrelevant here —
# the whole tile IS the vector.

def _contains_vmem_gather_kernel(keys_ref, filt_ref, out_ref, *,
                                 spec: FilterSpec, tile: int, mix: str):
    s = spec.s
    starts, masks = _fingerprints(spec, keys_ref[...], mix=mix)
    idx = starts[:, None] + jax.lax.broadcasted_iota(jnp.int32, (tile, s), 1)
    words = jnp.take(filt_ref[...], idx, axis=0)         # (tile, s) gather
    out_ref[...] = jnp.all((words & masks) == masks, axis=-1)


def _add_vmem_gather_kernel(keys_ref, filt_ref, out_ref, *, spec: FilterSpec,
                            tile: int, mix: str):
    s = spec.s

    @pl.when(pl.program_id(0) == 0)
    def _seed():
        out_ref[...] = filt_ref[...]

    starts, masks = _fingerprints(spec, keys_ref[...], mix=mix)
    blk = jax.lax.div(starts, jnp.int32(s))
    out_ref[...] = V.or_rows(spec, out_ref[...], blk, masks)


# ---------------------------------------------------------------------------
# Cooperative sub-tile kernels (coop="subtile"; VMEM forms interpret-only)
# ---------------------------------------------------------------------------
# The cooperation axis re-slices phase 2 at WORD granularity instead of KEY
# granularity — the TPU analogue of a lane group sharing one key's k probes:
#
# * contains: column-major early-exit. The whole tile probes word column c
#   together (ONE flat gather of tile words), folds the column test into a
#   per-key `alive` mask, and the next column only runs while any key is
#   still alive (`lax.cond` — the cooperative ballot). Bit-exact because
#   the result is the same AND over the s per-column tests, and a dead key
#   stays dead regardless of skipped columns.
# * add: word-granular flat-lane scatter. Every (key, word) pair becomes
#   one lane of a (tile*s,) flat stream, sorted by absolute word index and
#   OR-collapsed with the segmented scan — one flat gather + one
#   conflict-free flat scatter touches each unique WORD once (the "none"
#   gather engine collapses at block granularity; this is the finer
#   cooperative tiling of the same associative reduction).

def _contains_vmem_coop_kernel(keys_ref, filt_ref, out_ref, *,
                               spec: FilterSpec, tile: int, mix: str):
    s = spec.s
    starts, masks = _fingerprints(spec, keys_ref[...], mix=mix)
    filt = filt_ref[...]
    alive = jnp.ones((tile,), jnp.bool_)
    for c in range(s):                          # static unroll over columns
        m = masks[:, c]

        def probe_col(al, m=m, c=c):
            w = jnp.take(filt, starts + c, axis=0)        # (tile,) flat gather
            return al & ((w & m) == m)

        alive = jax.lax.cond(jnp.any(alive), probe_col, lambda al: al, alive)
    out_ref[...] = alive


def _add_vmem_coop_kernel(keys_ref, filt_ref, out_ref, *, spec: FilterSpec,
                          tile: int, mix: str):
    s = spec.s

    @pl.when(pl.program_id(0) == 0)
    def _seed():
        out_ref[...] = filt_ref[...]

    starts, masks = _fingerprints(spec, keys_ref[...], mix=mix)
    idx = (starts[:, None]
           + jax.lax.broadcasted_iota(jnp.int32, (tile, s), 1)
           ).reshape(tile * s)
    vals = masks.reshape(tile * s)
    order = jnp.argsort(idx)
    si = idx[order]
    or_w = V.segment_totals(si, vals[order][:, None], jnp.bitwise_or)[:, 0]
    f = out_ref[...]
    words = jnp.take(f, si, axis=0)
    # duplicate indices carry identical segment totals -> deterministic set
    out_ref[...] = f.at[si].set(words | or_w)




# ---------------------------------------------------------------------------
# HBM-resident kernels (DRAM-resident regime analogue) — explicit DMA
# ---------------------------------------------------------------------------

def _contains_hbm_kernel(st_ref, m_ref, filt_hbm, out_ref, scratch, sem,
                         miss_ref, *, s: int, tile: int, depth: int, held):
    """Depth-``depth`` row-streaming pipeline: keep up to ``depth - 1``
    row DMAs in flight ahead of the one being tested — the TPU-explicit
    version of the paper's load pipelining, with the pipeline depth a
    tunable instead of hardcoded double-buffering (depth=2). Each loop trip
    takes ``HBM_PROBE_UNROLL`` keys in one basic block: their masks are
    placed first, then each key waits for its row and stores its missing
    bits; the tile is tested after the loop (:func:`_test_keys`)."""
    lanes = filt_hbm.shape[-1]

    def dma(i, slot):
        r, _ = _locate(st_ref, i, lanes)
        return pltpu.make_async_copy(filt_hbm.at[pl.ds(r, 1)],
                                     scratch.at[pl.ds(slot, 1)],
                                     sem.at[slot])

    for d in range(depth - 1):             # static prologue: fill the pipe
        dma(d, d).start()

    def row_of(i):
        slot = jax.lax.rem(i, depth)

        # At depth=1 the offset is 0: the "prefetch" starts the current DMA
        # (fully serial); at depth>=2 it keeps depth-1 copies in flight.
        @pl.when(i + depth - 1 < tile)
        def _prefetch():
            dma(i + depth - 1, jax.lax.rem(i + depth - 1, depth)).start()

        dma(i, slot).wait()
        return scratch[pl.ds(slot, 1), :]

    _test_keys(out_ref, miss_ref, _mask_at(st_ref, m_ref, s, lanes), row_of,
               n_keys=tile, unroll=min(HBM_PROBE_UNROLL, tile), held=held)


def _run_bounds(st_ref, i, tile: int, lanes: int):
    """(first, last): is key ``i`` of a row-sorted tile the first / last of
    its row's run? Tile edges always open and close a run."""
    r = st_ref[0, 0, i] // lanes
    prev = st_ref[0, 0, jnp.maximum(i - 1, 0)] // lanes
    nxt = st_ref[0, 0, jnp.minimum(i + 1, tile - 1)] // lanes
    return (i == 0) | (r != prev), (i == tile - 1) | (r != nxt)


def _contains_hbm_coop_kernel(st_ref, m_ref, filt_hbm, out_ref, scratch, sem,
                              miss_ref, *, s: int, tile: int, held):
    """Cooperative HBM contains over a tile sorted by row start (in XLA):
    the first key of each row run fetches the row, followers test against
    the scratch row already resident — each unique row moves over the HBM
    bus once per tile. The tile is tested after the loop
    (:func:`_test_keys`); results come out in sorted order."""
    lanes = filt_hbm.shape[-1]

    def row_of(i):
        r, _ = _locate(st_ref, i, lanes)
        first, _ = _run_bounds(st_ref, i, tile, lanes)

        @pl.when(first)
        def _fetch():                      # one DMA per unique row
            cp = pltpu.make_async_copy(filt_hbm.at[pl.ds(r, 1)], scratch,
                                       sem.at[0])
            cp.start()
            cp.wait()

        return scratch[...]

    _test_keys(out_ref, miss_ref, _mask_at(st_ref, m_ref, s, lanes), row_of,
               n_keys=tile, unroll=1, held=held)


def _seed_output(filt_hbm, out_hbm, sem):
    """Seed the output filter once (full-array DMA HBM->HBM)."""
    @pl.when(pl.program_id(0) == 0)
    def _seed():
        cp = pltpu.make_async_copy(filt_hbm, out_hbm, sem.at[0])
        cp.start()
        cp.wait()


def _update_hbm_kernel(st_ref, m_ref, filt_hbm, out_hbm, rows_ref, run_rows,
                       sem, *, s: int, tile: int, apply):
    """HBM update over a tile sorted by row start (in XLA): one
    read-modify-write per unique row, with the tile's row DMAs in flight
    together (:func:`_update_hbm_tile`). The first grid step seeds the
    output with the input filter before its first row read."""
    _seed_output(filt_hbm, out_hbm, sem)
    _update_hbm_tile(st_ref, m_ref, out_hbm, rows_ref, run_rows, sem, s=s,
                     tile=tile, apply=apply)


def _update_hbm_carried_kernel(n_carried, st_ref, m_ref, filt_hbm, out_hbm,
                               rows_ref, run_rows, sem, *, s: int, tile: int,
                               apply):
    """:func:`_update_hbm_kernel` on the tiles that carry a key; the
    tiles of padding after them make no row DMA. The output aliases the
    input filter, so there is no seed copy: one DMA of a segment of 4 GiB
    or more is past what a DMA can carry."""
    del filt_hbm

    @pl.when(pl.program_id(0) < n_carried[0])
    def _update():
        _update_hbm_tile(st_ref, m_ref, out_hbm, rows_ref, run_rows, sem,
                         s=s, tile=tile, apply=apply)


def _update_hbm_tile(st_ref, m_ref, out_hbm, rows_ref, run_rows, sem, *,
                     s: int, tile: int, apply):
    """One tile of row-sorted keys in three phases. Reads: the first key of
    each row run j notes its row in ``run_rows[j]`` and starts the row's
    DMA into slot j of ``rows_ref`` (``(tile, lanes)`` VMEM), with no wait.
    Apply: the keys in order; run j's first key waits for its read, every
    key applies its mask to slot j, and the last starts the row's write,
    again with no wait. Drain: the tile waits for every write it started.

    The runs of a sorted tile are distinct rows, so their DMAs never
    overlap; a row can end one tile and begin the next, and the drain
    lands this tile's write before the next tile reads it. Run j's read
    and write share semaphore j, since the read is waited for before the
    write starts."""
    lanes = out_hbm.shape[-1]

    def copy(j, r, read: bool):
        row, slot = out_hbm.at[pl.ds(r, 1)], rows_ref.at[pl.ds(j, 1)]
        src, dst = (row, slot) if read else (slot, row)
        return pltpu.make_async_copy(src, dst, sem.at[j])

    def read(i, carry):
        j, prev = carry
        r, _ = _locate(st_ref, i, lanes)
        first = r != prev

        @pl.when(first)
        def _start():
            run_rows[j] = r
            copy(j, r, True).start()

        return j + first.astype(jnp.int32), r

    n_runs, _ = jax.lax.fori_loop(0, tile, read, (jnp.int32(0), jnp.int32(-1)))

    def update(i, j):
        r, c = _locate(st_ref, i, lanes)
        first, last = _run_bounds(st_ref, i, tile, lanes)

        @pl.when(first)
        def _arrive():
            copy(j, r, True).wait()

        rows_ref[pl.ds(j, 1), :] = apply(rows_ref[pl.ds(j, 1), :],
                                         _key_mask(m_ref, i, c, s, lanes))

        @pl.when(last)
        def _write():
            copy(j, r, False).start()

        return j + last.astype(jnp.int32)

    jax.lax.fori_loop(0, tile, update, jnp.int32(0))

    def drain(j, carry):
        copy(j, run_rows[j], False).wait()
        return carry

    jax.lax.fori_loop(0, n_runs, drain, jnp.int32(0))


def _probe_carried_kernel(n_carried, *refs, probe):
    """``probe`` on the tiles that carry a key; a tile of padding makes no
    row DMA and answers 0."""
    carried = pl.program_id(0) < n_carried[0]
    out_ref = refs[3]                  # after starts, masks and the filter

    @pl.when(carried)
    def _probe():
        probe(*refs)

    @pl.when(jnp.logical_not(carried))
    def _padding():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


def probe_rows_hbm(words: jnp.ndarray, starts: jnp.ndarray,
                   masks: jnp.ndarray, *, tile: int, depth: int, coop: str,
                   interpret: bool, held=_held, valid=None) -> jnp.ndarray:
    """(n,) bool: probes streaming rows of HBM-resident ``words``.
    ``coop="subtile"`` sorts the keys by row in XLA so each row is fetched
    once per tile; otherwise a depth-``depth`` DMA pipeline. Either way a
    grid step stores its keys' missing bits in a ``(tile, lanes)`` scratch
    and tests the whole tile after its key loop.

    ``valid`` (n,) marks the slots that carry a key (an exchange's padded
    slots are false): the keys are then sorted by row with the padding
    last, and tiles that hold only padding make no row DMA. A padded
    slot's answer is meaningless."""
    n = starts.shape[0]
    lanes = row_lanes(words.shape[0])
    s = masks.shape[1]
    n_carried = None
    if valid is not None:
        order, starts, n_carried = _carried_first(valid, starts,
                                                  words.shape[0], s, tile)
        masks = masks[order]
    elif coop == "subtile":
        order = jnp.argsort(starts)
        starts, masks = starts[order], masks[order]
    if coop == "subtile":
        kern = functools.partial(_contains_hbm_coop_kernel, s=masks.shape[1],
                                 tile=tile, held=held)
        scratch = [pltpu.VMEM((1, lanes), jnp.uint32),      # shared row
                   pltpu.SemaphoreType.DMA((1,))]
    else:
        depth = min(depth, tile)
        kern = functools.partial(_contains_hbm_kernel, s=masks.shape[1],
                                 tile=tile, depth=depth, held=held)
        scratch = [pltpu.VMEM((depth, lanes), jnp.uint32),  # depth-slot ring
                   pltpu.SemaphoreType.DMA((depth,))]
    scratch.append(pltpu.VMEM((tile, lanes), jnp.uint32))   # missing bits
    mt = _mask_tiles(masks, tile, lanes)
    operands = (_tiles(starts, tile), mt, row_view(words, lanes))
    out_shape = jax.ShapeDtypeStruct((n // tile, 1, tile), jnp.int32)
    if n_carried is None:
        out = pl.pallas_call(
            kern,
            grid=(n // tile,),
            in_specs=_key_specs(tile, mt) + [
                pl.BlockSpec(memory_space=pl.ANY)],           # filter in HBM
            out_specs=pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0)),
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(*operands)
    else:
        out = pl.pallas_call(
            functools.partial(_probe_carried_kernel, probe=kern),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n // tile,),
                in_specs=_key_specs(tile, mt, carried=True) + [
                    pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, 1, tile),
                                       lambda i, n_carried: (i, 0, 0)),
                scratch_shapes=scratch),
            out_shape=out_shape,
            interpret=interpret,
        )(n_carried, *operands)
    ok = out.reshape(n) != 0
    if coop == "subtile" or valid is not None:   # back to the caller's order
        ok = jnp.zeros((n,), jnp.bool_).at[order].set(ok)
    return ok


def update_rows_hbm(words: jnp.ndarray, starts: jnp.ndarray,
                    masks: jnp.ndarray, *, tile: int, interpret: bool,
                    apply=jnp.bitwise_or, valid=None) -> jnp.ndarray:
    """Row-sorted RMW of HBM-resident ``words`` (the sort runs in XLA);
    returns the updated flat words. Each grid step reads every row its
    tile touches at once, applies the keys, and writes each row back as
    its run ends, waiting for the writes once at the tile's end
    (:func:`_update_hbm_tile`).

    ``valid`` (n,) marks the slots that carry a key (an exchange's padded
    slots are false): padded slots then apply a zero mask, sort after
    every carried key, and the tiles that hold only padding make no row
    DMA, so the row work follows the keys carried, not the slots."""
    n = starts.shape[0]
    lanes = row_lanes(words.shape[0])
    rows = row_view(words, lanes)
    s = masks.shape[1]
    scratch = [pltpu.VMEM((tile, lanes), jnp.uint32),    # a slot a run
               pltpu.SMEM((tile,), jnp.int32),           # each run's row
               pltpu.SemaphoreType.DMA((tile,))]
    out_shape = jax.ShapeDtypeStruct(rows.shape, jnp.uint32)
    if valid is None:
        order = jnp.argsort(starts)
        mt = _mask_tiles(masks[order], tile, lanes)
        out = pl.pallas_call(
            functools.partial(_update_hbm_kernel, s=s, tile=tile,
                              apply=apply),
            grid=(n // tile,),
            in_specs=_key_specs(tile, mt) + [
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(_tiles(starts[order], tile), mt, rows)
        return out.reshape(-1)
    order, starts, n_carried = _carried_first(valid, starts, words.shape[0],
                                              s, tile)
    masks = jnp.where(valid[:, None], masks, jnp.uint32(0))
    mt = _mask_tiles(masks[order], tile, lanes)
    out = pl.pallas_call(
        functools.partial(_update_hbm_carried_kernel, s=s, tile=tile,
                          apply=apply),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tile,),
            in_specs=_key_specs(tile, mt, carried=True) + [
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=scratch),
        out_shape=out_shape,
        input_output_aliases={3: 0},
        interpret=interpret,
    )(n_carried, _tiles(starts, tile), mt, rows)
    return out.reshape(-1)


def contains_hbm(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray, *,
                 interpret: bool, tile: int = DEFAULT_TILE,
                 depth: int = DEFAULT_DMA_DEPTH, coop: str = "none",
                 mix: str = "full") -> jnp.ndarray:
    n = keys.shape[0]
    assert n % tile == 0
    assert depth in DMA_DEPTHS, f"depth={depth} not in {DMA_DEPTHS}"
    assert coop in COOPS, coop
    assert mix in MIXES, mix
    starts, masks = fingerprints(spec, keys, mix)
    return probe_rows_hbm(filt, starts, masks, tile=tile, depth=depth,
                          coop=coop, interpret=interpret)


def add_hbm(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray, *,
            interpret: bool, tile: int = DEFAULT_TILE, coop: str = "none",
            mix: str = "full") -> jnp.ndarray:
    # The HBM add is already fully cooperative: the row-sorted schedule
    # reads and writes each unique row once per tile, with the tile's row
    # DMAs in flight together, which is exactly the coop="subtile" memory
    # schedule. The axis is accepted (and validated) so dispatch can thread
    # a uniform plan; both values run the same kernel.
    n = keys.shape[0]
    assert n % tile == 0
    assert coop in COOPS, coop
    assert mix in MIXES, mix
    starts, masks = fingerprints(spec, keys, mix)
    return update_rows_hbm(filt, starts, masks, tile=tile,
                           interpret=interpret)


# ---------------------------------------------------------------------------
# Bank kernels — B VMEM-resident filters, ONE launch (FilterBank backend)
# ---------------------------------------------------------------------------
# A (B, n_words) bank is pinned in VMEM *whole* (flattened to B*n_words
# words) and keys arrive flat with a per-key member index: the kernels are
# the single-filter kernels with every block start offset by
# member * n_words. B small filters therefore cost one pallas_call instead
# of B — the launch-amortization win WarpSpeed-style batched GPU filters
# get from fusing many small structures into one kernel. Adds are
# valid-masked (zero mask = OR no-op) so routed/padded batches stay exact.

def _bank_starts(spec: FilterSpec, keys, member, mix: str = "full"):
    starts, masks = _fingerprints(spec, keys, mix=mix)
    return starts + member * jnp.int32(spec.n_words), masks


def _bank_contains_vmem_gather_kernel(keys_ref, member_ref, filt_ref, out_ref,
                                      *, spec: FilterSpec, tile: int,
                                      mix: str):
    s = spec.s
    starts, masks = _bank_starts(spec, keys_ref[...], member_ref[...], mix)
    idx = starts[:, None] + jax.lax.broadcasted_iota(jnp.int32, (tile, s), 1)
    words = jnp.take(filt_ref[...], idx, axis=0)         # (tile, s) gather
    out_ref[...] = jnp.all((words & masks) == masks, axis=-1)


def _bank_add_vmem_gather_kernel(keys_ref, member_ref, valid_ref, filt_ref,
                                 out_ref, *, spec: FilterSpec, tile: int,
                                 bank: int, mix: str):
    @pl.when(pl.program_id(0) == 0)
    def _seed():
        out_ref[...] = filt_ref[...]

    starts, masks = _bank_starts(spec, keys_ref[...], member_ref[...], mix)
    masks = masks * valid_ref[...][:, None].astype(jnp.uint32)
    blk = jax.lax.div(starts, jnp.int32(spec.s))    # member-offset block ids
    out_ref[...] = V.or_rows(spec, out_ref[...], blk, masks,
                             n_rows=bank * spec.n_blocks)


def bank_contains_vmem(spec: FilterSpec, bank: jnp.ndarray, keys: jnp.ndarray,
                       member: jnp.ndarray, layout: Layout, *,
                       interpret: bool, tile: int = DEFAULT_TILE,
                       probe: str = "loop", mix: str = "full") -> jnp.ndarray:
    """Flat routed membership against a (B, n_words) bank — one launch."""
    n = keys.shape[0]
    assert n % tile == 0 and member.shape == (n,)
    assert probe in PROBES, probe
    assert mix in MIXES, mix
    _check_lowers("vmem", probe, "none", interpret)
    B, flat = bank.shape[0], bank.reshape(-1)
    layout = layout.validate(spec, tile)
    member = member.astype(jnp.int32)
    if probe == "loop":
        starts, masks = fingerprints(spec, keys, mix)
        return probe_rows_vmem(
            flat, starts + member * jnp.int32(spec.n_words), masks,
            theta=layout.theta, tile=tile, interpret=interpret)
    return pl.pallas_call(
        functools.partial(_bank_contains_vmem_gather_kernel, spec=spec,
                          tile=tile, mix=mix),
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),
            pl.BlockSpec((tile,), lambda i: (i,)),               # member ids
            pl.BlockSpec((B * spec.n_words,), lambda i: (0,)),   # whole bank
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.bool_),
        interpret=interpret,
    )(keys, member, flat)


def bank_add_vmem(spec: FilterSpec, bank: jnp.ndarray, keys: jnp.ndarray,
                  member: jnp.ndarray, valid: jnp.ndarray, layout: Layout, *,
                  interpret: bool, tile: int = DEFAULT_TILE,
                  probe: str = "loop", mix: str = "full") -> jnp.ndarray:
    """Flat routed valid-masked insert into a (B, n_words) bank — one
    launch, sequential-grid RMW over the whole VMEM-resident bank."""
    n = keys.shape[0]
    assert n % tile == 0 and member.shape == (n,) and valid.shape == (n,)
    assert probe in PROBES, probe
    assert mix in MIXES, mix
    _check_lowers("vmem", probe, "none", interpret)
    B, flat = bank.shape[0], bank.reshape(-1)
    layout = layout.validate(spec, tile)
    member = member.astype(jnp.int32)
    if probe == "loop":
        starts, masks = fingerprints(spec, keys, mix)
        masks = masks * valid[:, None].astype(jnp.uint32)
        out = update_rows_vmem(
            flat, starts + member * jnp.int32(spec.n_words), masks,
            theta=layout.theta, tile=tile, interpret=interpret)
        return out.reshape(B, spec.n_words)
    out = pl.pallas_call(
        functools.partial(_bank_add_vmem_gather_kernel, spec=spec,
                          tile=tile, bank=B, mix=mix),
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile,), lambda i: (i,)),               # valid mask
            pl.BlockSpec((B * spec.n_words,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((B * spec.n_words,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((B * spec.n_words,), jnp.uint32),
        interpret=interpret,
    )(keys, member, valid, flat)
    return out.reshape(B, spec.n_words)


# ---------------------------------------------------------------------------
# Partitioned bulk add — the beyond-paper TPU-native path
# ---------------------------------------------------------------------------

def _update_partitioned_kernel(st_ref, m_ref, filt_ref, out_ref, *, s: int,
                               capacity: int, apply):
    """One grid step owns one filter segment exclusively (PARALLEL-safe).

    Keys were pre-partitioned so every key in this step's tile lands in this
    segment; invalid (padding) slots carry zero masks (no-op updates).
    """
    out_ref[...] = filt_ref[...]
    _update_loop(st_ref, m_ref, out_ref, s=s, theta=1, n_keys=capacity,
                 apply=apply)


def update_rows_partitioned(words: jnp.ndarray, starts: jnp.ndarray,
                            masks: jnp.ndarray, n_segments: int, *,
                            interpret: bool,
                            apply=jnp.bitwise_or) -> jnp.ndarray:
    """``starts``/``masks`` hold ``n_segments`` equal groups of keys, group
    g owning segment g of ``words``; starts are segment-local. One PARALLEL
    grid step per segment."""
    seg_words = words.shape[0] // n_segments
    capacity = starts.shape[0] // n_segments
    lanes = row_lanes(seg_words)
    mt = _mask_tiles(masks, capacity, lanes)
    seg = (seg_words // lanes, lanes)
    out = pl.pallas_call(
        functools.partial(_update_partitioned_kernel, s=masks.shape[1],
                          capacity=capacity, apply=apply),
        grid=(n_segments,),
        in_specs=_key_specs(capacity, mt) + [
            pl.BlockSpec(seg, lambda i: (i, 0))],             # own segment only
        out_specs=pl.BlockSpec(seg, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((words.shape[0] // lanes, lanes),
                                       jnp.uint32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),                # segments are independent
    )(_tiles(starts, capacity), mt, row_view(words, lanes))
    return out.reshape(-1)


def add_partitioned(spec: FilterSpec, filt: jnp.ndarray,
                    keys_by_seg: jnp.ndarray, valid: jnp.ndarray,
                    n_segments: int, *, interpret: bool,
                    mix: str = "full") -> jnp.ndarray:
    """keys_by_seg: (n_segments, capacity, 2); valid: (n_segments, capacity)."""
    assert spec.n_words % n_segments == 0
    assert mix in MIXES, mix
    seg_words = spec.n_words // n_segments
    starts, masks = fingerprints(spec, keys_by_seg.reshape(-1, 2), mix)
    masks = masks * valid.reshape(-1, 1).astype(jnp.uint32)
    return update_rows_partitioned(
        filt, jax.lax.rem(starts, jnp.int32(seg_words)), masks, n_segments,
        interpret=interpret)
