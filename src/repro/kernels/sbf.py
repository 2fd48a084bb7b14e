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

One schedule per regime: the VMEM kernels walk the keys Θ at a time, the
HBM probe streams rows through a fixed ring of ``HBM_PROBE_DEPTH`` DMA
slots, and the HBM update works a row-sorted tile's runs together. The
CPU runs these same kernels in interpret mode, where they are validated
bit-exactly against ``repro.core.variants``.
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

def fingerprints(spec: FilterSpec, keys: jnp.ndarray):
    """Phase 1 in XLA: (starts (n,) int32, masks (n, s) uint32). Both hash
    streams come from one fused mix (``hashing.xxh32_u64x2_pair``)."""
    h1, h2 = H.xxh32_u64x2_pair(keys)
    blk = H.block_index(h2, spec.n_blocks)
    starts = (blk * jnp.uint32(spec.s)).astype(jnp.int32)
    return starts, V.block_patterns(spec, h1)


def _take_scalar(vec: jnp.ndarray, i) -> jnp.ndarray:
    return jax.lax.dynamic_index_in_dim(vec, i, keepdims=False)


def _mask_row(masks: jnp.ndarray, i, s: int) -> jnp.ndarray:
    return jax.lax.dynamic_slice(masks, (i, 0), (1, s))[0]


# Keys a trip of the HBM probe loop takes: one sublane tile of its
# missing-bits scratch.
HBM_PROBE_UNROLL = 8
# Row DMAs the HBM probe keeps in its ring: ``HBM_PROBE_DEPTH - 1`` in
# flight ahead of the row being tested.
HBM_PROBE_DEPTH = 8


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
                  layout: Layout, *, interpret: bool,
                  tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Bulk membership test, whole filter pinned in VMEM via BlockSpec."""
    assert keys.shape[0] % tile == 0
    layout = layout.validate(spec, tile)
    starts, masks = fingerprints(spec, keys)
    return probe_rows_vmem(filt, starts, masks, theta=layout.theta,
                           tile=tile, interpret=interpret)


def add_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
             layout: Layout, *, interpret: bool,
             tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Bulk insert, whole filter pinned in VMEM; sequential-grid RMW."""
    assert keys.shape[0] % tile == 0
    layout = layout.validate(spec, tile)
    starts, masks = fingerprints(spec, keys)
    return update_rows_vmem(filt, starts, masks, theta=layout.theta,
                            tile=tile, interpret=interpret)


# ---------------------------------------------------------------------------
# HBM-resident kernels (DRAM-resident regime analogue) — explicit DMA
# ---------------------------------------------------------------------------

def _contains_hbm_kernel(st_ref, m_ref, filt_hbm, out_ref, scratch, sem,
                         miss_ref, *, s: int, tile: int, depth: int, held):
    """Depth-``depth`` row-streaming pipeline: keep up to ``depth - 1``
    row DMAs in flight ahead of the one being tested — the TPU-explicit
    version of the paper's load pipelining. Each loop trip
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
                   masks: jnp.ndarray, *, tile: int, interpret: bool,
                   held=_held, valid=None) -> jnp.ndarray:
    """(n,) bool: probes streaming rows of HBM-resident ``words`` through a
    ring of ``HBM_PROBE_DEPTH`` DMA slots (fewer for a smaller tile). A
    grid step stores its keys' missing bits in a ``(tile, lanes)`` scratch
    and tests the whole tile after its key loop.

    ``valid`` (n,) marks the slots that carry a key (an exchange's padded
    slots are false): the keys are then sorted by row with the padding
    last, and tiles that hold only padding make no row DMA. A padded
    slot's answer is meaningless."""
    n = starts.shape[0]
    lanes = row_lanes(words.shape[0])
    if valid is not None:
        order, starts, n_carried = _carried_first(valid, starts,
                                                  words.shape[0],
                                                  masks.shape[1], tile)
        masks = masks[order]
    depth = min(HBM_PROBE_DEPTH, tile)
    kern = functools.partial(_contains_hbm_kernel, s=masks.shape[1],
                             tile=tile, depth=depth, held=held)
    scratch = [pltpu.VMEM((depth, lanes), jnp.uint32),      # the row ring
               pltpu.SemaphoreType.DMA((depth,)),
               pltpu.VMEM((tile, lanes), jnp.uint32)]       # missing bits
    mt = _mask_tiles(masks, tile, lanes)
    operands = (_tiles(starts, tile), mt, row_view(words, lanes))
    out_shape = jax.ShapeDtypeStruct((n // tile, 1, tile), jnp.int32)
    if valid is None:
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
        return out.reshape(n) != 0
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
    # back to the caller's order
    return jnp.zeros((n,), jnp.bool_).at[order].set(out.reshape(n) != 0)


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
                 interpret: bool, tile: int = DEFAULT_TILE) -> jnp.ndarray:
    assert keys.shape[0] % tile == 0
    starts, masks = fingerprints(spec, keys)
    return probe_rows_hbm(filt, starts, masks, tile=tile,
                          interpret=interpret)


def add_hbm(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray, *,
            interpret: bool, tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Row-sorted bulk insert: each unique row of a tile is read and
    written once, with the tile's row DMAs in flight together."""
    assert keys.shape[0] % tile == 0
    starts, masks = fingerprints(spec, keys)
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

def bank_contains_vmem(spec: FilterSpec, bank: jnp.ndarray, keys: jnp.ndarray,
                       member: jnp.ndarray, layout: Layout, *,
                       interpret: bool,
                       tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Flat routed membership against a (B, n_words) bank — one launch."""
    n = keys.shape[0]
    assert n % tile == 0 and member.shape == (n,)
    layout = layout.validate(spec, tile)
    starts, masks = fingerprints(spec, keys)
    return probe_rows_vmem(
        bank.reshape(-1),
        starts + member.astype(jnp.int32) * jnp.int32(spec.n_words), masks,
        theta=layout.theta, tile=tile, interpret=interpret)


def bank_add_vmem(spec: FilterSpec, bank: jnp.ndarray, keys: jnp.ndarray,
                  member: jnp.ndarray, valid: jnp.ndarray, layout: Layout, *,
                  interpret: bool, tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Flat routed valid-masked insert into a (B, n_words) bank — one
    launch, sequential-grid RMW over the whole VMEM-resident bank."""
    n = keys.shape[0]
    assert n % tile == 0 and member.shape == (n,) and valid.shape == (n,)
    layout = layout.validate(spec, tile)
    starts, masks = fingerprints(spec, keys)
    masks = masks * valid[:, None].astype(jnp.uint32)
    out = update_rows_vmem(
        bank.reshape(-1),
        starts + member.astype(jnp.int32) * jnp.int32(spec.n_words), masks,
        theta=layout.theta, tile=tile, interpret=interpret)
    return out.reshape(bank.shape[0], spec.n_words)


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
                    n_segments: int, *, interpret: bool) -> jnp.ndarray:
    """keys_by_seg: (n_segments, capacity, 2); valid: (n_segments, capacity)."""
    assert spec.n_words % n_segments == 0
    seg_words = spec.n_words // n_segments
    starts, masks = fingerprints(spec, keys_by_seg.reshape(-1, 2))
    masks = masks * valid.reshape(-1, 1).astype(jnp.uint32)
    return update_rows_partitioned(
        filt, jax.lax.rem(starts, jnp.int32(seg_words)), masks, n_segments,
        interpret=interpret)
