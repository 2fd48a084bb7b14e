"""Bucketed cuckoo fingerprint filter — pure-jnp reference semantics.

The fingerprint AMQ family (Fan et al.'s cuckoo filter) the GPU filter
literature benchmarks Bloom designs against ("High-Performance Filters for
GPUs", "Cuckoo-GPU"), adapted to the repo's conventions:

* the table is a flat ``(n_words,)`` uint32 array — ``n_buckets`` buckets of
  ``slots_per_bucket`` fingerprints, ``slot_bits`` (8 or 16) each, packed
  little-endian into ``s = bucket_bits/32`` words per bucket. A bucket is
  the "block" of the shared :class:`FilterSpec` geometry, so VMEM budgets,
  bank offsets and row gathers reuse the Bloom machinery unchanged;
* **partial-key hashing**: the block hash stream picks the primary bucket,
  the pattern stream yields the fingerprint (forced nonzero — 0 means
  empty slot); the alternate bucket is ``b XOR h(fp)``, an involution, so
  relocation during kicks never needs the original key;
* **bounded-kick eviction** under ``lax.while_loop``: an insert that finds
  both candidate buckets full evicts a deterministic pseudo-random victim
  and relocates it, up to :data:`CUCKOO_MAX_KICKS` hops. The loop bound
  makes the op jit/scan-compilable; exceeding it returns an EXPLICIT
  failure flag per key (``ok=False``) — never a silent drop. Failed
  inserts leave a relocated-but-consistent table (the standard cuckoo
  behavior: the displaced fingerprint chain remains findable);
* inserts and removes are **not idempotent** (a duplicate key occupies a
  second slot; a remove clears exactly one matching slot), so bulk ops
  take a ``valid`` mask for padding — never repeat-key padding;
* bulk-add order is DETERMINISTIC and tile-stable: keys are processed in
  :data:`CUCKOO_ADD_TILE` chunks, each chunk stably sorted by primary
  bucket ("block-sorted", coalescing same-bucket RMWs) — exactly the
  schedule of the Pallas kernel (`kernels.cuckoofilter`), which is what
  makes jnp-vs-Pallas builds bit-identical.

Every function here is plain jnp/lax vector code, so the same helpers run
inside Pallas kernel bodies (interpret or compiled) and under
vmap/jit/scan — the single source of truth the kernels validate against.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hashing as H
from repro.core.variants import CUCKOO_SLOT_BITS, FilterSpec, _log2i

CUCKOO_MAX_KICKS = 64          # bounded eviction chain per insert
CUCKOO_ADD_TILE = 2048         # bulk-add chunk (sort + insert unit)
CUCKOO_MAX_LOAD = 0.95         # standard achievable load, 4-slot buckets

# fingerprint-stream salt (index 0) and alternate-bucket salt (index 1):
# distinct fixed members of the global salt table, inlined at trace time
_FP_SALT = H.SALTS[0]
_ALT_SALT = H.SALTS[1]

_LCG_MUL = np.uint32(747796405)       # PCG-style victim-slot stream
_LCG_ADD = np.uint32(2891336453)


def init(spec: FilterSpec) -> jnp.ndarray:
    assert spec.is_fingerprint
    return jnp.zeros((spec.n_words,), jnp.uint32)


# ---------------------------------------------------------------------------
# Hashing: partial-key scheme
# ---------------------------------------------------------------------------

def cuckoo_hashes(spec: FilterSpec, keys: jnp.ndarray):
    """(primary bucket (n,) int32, fingerprint (n,) uint32 in [1, 2^f),
    rng seed (n,) uint32 for the kick-path victim stream).

    The fingerprint comes from the pattern hash stream, the bucket from the
    block stream — same split as the Bloom kernels' phase 1. ``fp == 0``
    is remapped to 1 (0 encodes an empty slot)."""
    h1 = H.xxh32_u64x2(keys, H.SEED_PATTERN)
    h2 = H.block_hash(keys)
    fp = H.mulshift(h1, _FP_SALT, spec.slot_bits)
    fp = jnp.where(fp == 0, jnp.uint32(1), fp)
    b1 = H.block_index(h2, spec.n_buckets).astype(jnp.int32)
    rng = h1 ^ H.SEED_AUX
    return b1, fp, rng


def alt_bucket(spec: FilterSpec, b: jnp.ndarray, fp: jnp.ndarray):
    """The XOR-derived alternate bucket: ``alt(alt(b, fp), fp) == b``.

    Works on scalars (kernel kick loop) and vectors (bulk contains)."""
    lg = _log2i(spec.n_buckets)
    if lg == 0:
        return b
    h = H.mulshift(fp, _ALT_SALT, lg).astype(jnp.int32)
    return b ^ h


# ---------------------------------------------------------------------------
# Slot packing: u8/u16 fingerprints in u32 words
# ---------------------------------------------------------------------------

def unpack_slots(spec: FilterSpec, words: jnp.ndarray) -> jnp.ndarray:
    """(..., s) bucket words -> (..., slots_per_bucket) fingerprints.
    Slot j lives in word ``j // slots_per_word``, lane ``j % slots_per_word``
    (little-endian). The loop unrolls at trace time."""
    sb, spw = spec.slot_bits, spec.slots_per_word
    mask = jnp.uint32((1 << sb) - 1)
    lanes = [(words[..., j // spw] >> jnp.uint32(sb * (j % spw))) & mask
             for j in range(spec.slots_per_bucket)]
    return jnp.stack(lanes, axis=-1)


def pack_slots(spec: FilterSpec, slots: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`unpack_slots`: (..., spb) -> (..., s) words."""
    sb, spw = spec.slot_bits, spec.slots_per_word
    words = []
    for w in range(spec.s):
        acc = jnp.zeros(slots.shape[:-1], jnp.uint32)
        for lane in range(spw):
            acc = acc | (slots[..., w * spw + lane] << jnp.uint32(sb * lane))
        words.append(acc)
    return jnp.stack(words, axis=-1)


# ---------------------------------------------------------------------------
# contains — whole-batch gather + fused two-bucket compare
# ---------------------------------------------------------------------------

def cuckoo_contains(spec: FilterSpec, table: jnp.ndarray, keys: jnp.ndarray
                    ) -> jnp.ndarray:
    """(n,) bool membership: fingerprint present in either candidate bucket.

    One flat-index gather per candidate bucket over the whole batch and a
    single fused compare — written in the kernel-safe idiom
    (broadcasted_iota + take on the flat word array), so this exact
    function IS the Pallas contains kernel body."""
    n, s = keys.shape[0], spec.s
    b1, fp, _ = cuckoo_hashes(spec, keys)
    b2 = alt_bucket(spec, b1, fp)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, s), 1)
    w1 = jnp.take(table, b1[:, None] * s + col, axis=0)       # (n, s)
    w2 = jnp.take(table, b2[:, None] * s + col, axis=0)
    s1 = unpack_slots(spec, w1)                               # (n, spb)
    s2 = unpack_slots(spec, w2)
    return (jnp.any(s1 == fp[:, None], axis=-1)
            | jnp.any(s2 == fp[:, None], axis=-1))


def cuckoo_contains_coop(spec: FilterSpec, table: jnp.ndarray,
                         keys: jnp.ndarray) -> jnp.ndarray:
    """Cooperative early-exit contains: the tile probes all PRIMARY buckets
    together first, and only gathers the alternate buckets when some key is
    still unresolved (the cooperative ballot, ``lax.cond`` on the whole
    tile). At realistic loads most present keys sit in their primary
    bucket, so the second gather — half the memory traffic — is frequently
    skipped for the whole tile. Bit-exact with :func:`cuckoo_contains`: the
    result is the same OR of the two bucket tests, and a key already hit in
    its primary bucket stays a hit whether or not phase 2 runs."""
    n, s = keys.shape[0], spec.s
    b1, fp, _ = cuckoo_hashes(spec, keys)
    b2 = alt_bucket(spec, b1, fp)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, s), 1)
    w1 = jnp.take(table, b1[:, None] * s + col, axis=0)       # (n, s)
    hit1 = jnp.any(unpack_slots(spec, w1) == fp[:, None], axis=-1)

    def probe_alt(h):
        w2 = jnp.take(table, b2[:, None] * s + col, axis=0)
        return h | jnp.any(unpack_slots(spec, w2) == fp[:, None], axis=-1)

    return jax.lax.cond(jnp.all(hit1), lambda h: h, probe_alt, hit1)


# ---------------------------------------------------------------------------
# add — block-sorted tiles, bounded-kick eviction, explicit failure signal
# ---------------------------------------------------------------------------

def _bucket_words(spec: FilterSpec, table: jnp.ndarray, b) -> jnp.ndarray:
    return jax.lax.dynamic_slice(table, (b * spec.s,), (spec.s,))


def _store_bucket(spec: FilterSpec, table: jnp.ndarray, b,
                  slots: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(table, pack_slots(spec, slots),
                                        (b * spec.s,))


def _try_place(spec: FilterSpec, table: jnp.ndarray, b, fp):
    """Place ``fp`` in the first free slot of bucket ``b`` if any.
    Returns (table, placed: bool). Branch-free: a full bucket writes its
    own contents back (no-op)."""
    slots = unpack_slots(spec, _bucket_words(spec, table, b))   # (spb,)
    free = slots == 0
    placed = jnp.any(free)
    idx = jnp.argmax(free)
    lane = jnp.arange(spec.slots_per_bucket)
    new = jnp.where((lane == idx) & placed, fp, slots)
    return _store_bucket(spec, table, b, new), placed


def _insert_one(spec: FilterSpec, table: jnp.ndarray, b1, fp, rng, valid):
    """One key's insert: try both candidate buckets, then the bounded kick
    chain. Returns (table, ok). Invalid (padding) slots are exact no-ops
    reported as ok=True (nothing was dropped — nothing was asked)."""
    spb = spec.slots_per_bucket
    lg_spb = _log2i(spb)
    lane = jnp.arange(spb)

    def run(tbl):
        t, placed = _try_place(spec, tbl, b1, fp)
        b2 = alt_bucket(spec, b1, fp)
        t, placed = jax.lax.cond(
            placed, lambda a: (a, jnp.bool_(True)),
            lambda a: _try_place(spec, a, b2, fp), t)

        def kick_cond(st):
            _, _, _, _, kicks, placed = st
            return (~placed) & (kicks < CUCKOO_MAX_KICKS)

        def kick_body(st):
            t, b, f, r, kicks, _ = st
            # evict a pseudo-random victim from the full bucket b ...
            slots = unpack_slots(spec, _bucket_words(spec, t, b))
            if lg_spb == 0:
                v = jnp.int32(0)
            else:
                v = (r >> jnp.uint32(32 - lg_spb)).astype(jnp.int32)
            victim = jax.lax.dynamic_index_in_dim(slots, v, keepdims=False)
            t = _store_bucket(spec, t, b, jnp.where(lane == v, f, slots))
            # ... and relocate it to ITS alternate bucket (XOR involution:
            # derived from the victim fingerprint alone, no key needed)
            f = victim
            b = alt_bucket(spec, b, f)
            t, placed = _try_place(spec, t, b, f)
            return (t, b, f, r * _LCG_MUL + _LCG_ADD, kicks + 1, placed)

        t, _, _, _, _, placed = jax.lax.while_loop(
            kick_cond, kick_body,
            (t, b2, fp, rng, jnp.int32(0), placed))
        return t, placed

    return jax.lax.cond(valid, run, lambda tbl: (tbl, jnp.bool_(True)),
                        table)


def _tile_loop(spec: FilterSpec, table: jnp.ndarray, b1, fp, rng, valid,
               one_fn):
    """Stable-sort one tile by primary bucket, then apply ``one_fn``
    sequentially in sorted order; flags are returned in ORIGINAL order.

    The sort is the "block-sorted partition" of the bulk build: same-bucket
    keys become adjacent runs whose RMWs coalesce, and — because the whole
    tile is applied by one sequential owner — kicks crossing partition
    boundaries need no atomics (DESIGN.md §13)."""
    n = b1.shape[0]
    order = jnp.argsort(b1)                      # stable
    inv = jnp.argsort(order)
    sb1, sfp = b1[order], fp[order]
    srng, sval = rng[order], valid[order]

    def body(i, carry):
        tbl, ok = carry
        tbl, oki = one_fn(spec, tbl,
                          jax.lax.dynamic_index_in_dim(sb1, i, keepdims=False),
                          jax.lax.dynamic_index_in_dim(sfp, i, keepdims=False),
                          jax.lax.dynamic_index_in_dim(srng, i, keepdims=False),
                          jax.lax.dynamic_index_in_dim(sval, i, keepdims=False))
        return tbl, jax.lax.dynamic_update_slice(ok, oki[None], (i,))

    table, ok_sorted = jax.lax.fori_loop(
        0, n, body, (table, jnp.zeros((n,), jnp.bool_)))
    return table, ok_sorted[inv]


def cuckoo_insert_tile(spec: FilterSpec, table: jnp.ndarray, b1, fp, rng,
                       valid) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One tile's bulk insert (shared verbatim by the Pallas add kernel)."""
    return _tile_loop(spec, table, b1, fp, rng, valid, _insert_one)


def _as_valid(n: int, valid: Optional[jnp.ndarray]) -> jnp.ndarray:
    if valid is None:
        return jnp.ones((n,), jnp.bool_)
    return jnp.asarray(valid).astype(jnp.bool_)


def cuckoo_add(spec: FilterSpec, table: jnp.ndarray, keys: jnp.ndarray,
               valid: Optional[jnp.ndarray] = None,
               tile: Optional[int] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Bulk insert. Returns ``(table, ok)`` with ``ok[i]=False`` iff key i's
    kick chain exceeded :data:`CUCKOO_MAX_KICKS` — the EXPLICIT
    insert-failure signal (surface it; the table is over capacity).

    Failure accounting is exact — each failure leaves exactly one
    fingerprint homeless, so ``occupied_slots == sum(ok)`` always — but,
    as in every cuckoo filter, the homeless fingerprint is the LAST
    victim of the kick chain, which may belong to an earlier key rather
    than the failing one. A nonzero failure count therefore means
    "resize/rebuild": the no-false-negative guarantee holds only for
    tables that never reported a failure.

    ``valid`` masks padding slots (inserts are not idempotent).
    ``tile`` pins the chunk size (default :data:`CUCKOO_ADD_TILE`) — the
    chunk boundaries and in-chunk bucket sort define the deterministic
    insertion order the Pallas kernel reproduces bit-for-bit."""
    assert spec.is_fingerprint
    n = keys.shape[0]
    if n == 0:
        return table, jnp.zeros((0,), jnp.bool_)
    b1, fp, rng = cuckoo_hashes(spec, keys)
    v = _as_valid(n, valid)
    T = tile or CUCKOO_ADD_TILE
    oks = []
    for c in range(0, n, T):                     # trace-time chunking
        sl = slice(c, min(c + T, n))
        table, ok = cuckoo_insert_tile(spec, table, b1[sl], fp[sl],
                                       rng[sl], v[sl])
        oks.append(ok)
    return table, (oks[0] if len(oks) == 1 else jnp.concatenate(oks))


# ---------------------------------------------------------------------------
# remove — clear exactly one matching slot per key
# ---------------------------------------------------------------------------

def _remove_one(spec: FilterSpec, table: jnp.ndarray, b1, fp, rng, valid):
    """Clear the first slot matching ``fp`` in the primary bucket, else in
    the alternate. Returns (table, found). Removing an absent key is a
    guarded no-op with found=False (never corrupts other keys)."""
    lane = jnp.arange(spec.slots_per_bucket)

    def clear(tbl, b):
        slots = unpack_slots(spec, _bucket_words(spec, tbl, b))
        hit = slots == fp
        found = jnp.any(hit)
        idx = jnp.argmax(hit)
        new = jnp.where((lane == idx) & found, jnp.uint32(0), slots)
        return _store_bucket(spec, tbl, b, new), found

    def run(tbl):
        t, found = clear(tbl, b1)
        return jax.lax.cond(
            found, lambda a: (a, jnp.bool_(True)),
            lambda a: clear(a, alt_bucket(spec, b1, fp)), t)

    return jax.lax.cond(valid, run, lambda tbl: (tbl, jnp.bool_(True)),
                        table)


def cuckoo_remove_tile(spec: FilterSpec, table: jnp.ndarray, b1, fp, rng,
                       valid) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One tile's bulk remove (shared verbatim by the Pallas kernel)."""
    return _tile_loop(spec, table, b1, fp, rng, valid, _remove_one)


def cuckoo_remove(spec: FilterSpec, table: jnp.ndarray, keys: jnp.ndarray,
                  valid: Optional[jnp.ndarray] = None,
                  tile: Optional[int] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Bulk delete: each key clears ONE slot holding its fingerprint
    (duplicates in the batch clear one slot each — same-bucket order is
    the sorted sequential order, identical in jnp and Pallas). Returns
    ``(table, found)``; ``found[i]=False`` means key i was not present
    (or its fingerprint was already cleared by an earlier duplicate).

    Only remove keys that were actually inserted — the cuckoo contract
    (shared with every fingerprint filter): deleting a never-inserted key
    can clear a colliding key's fingerprint and induce false negatives."""
    assert spec.is_fingerprint
    n = keys.shape[0]
    if n == 0:
        return table, jnp.zeros((0,), jnp.bool_)
    b1, fp, rng = cuckoo_hashes(spec, keys)
    v = _as_valid(n, valid)
    T = tile or CUCKOO_ADD_TILE
    outs = []
    for c in range(0, n, T):
        sl = slice(c, min(c + T, n))
        table, found = cuckoo_remove_tile(spec, table, b1[sl], fp[sl],
                                          rng[sl], v[sl])
        outs.append(found)
    return table, (outs[0] if len(outs) == 1 else jnp.concatenate(outs))


# ---------------------------------------------------------------------------
# Introspection + theory + sizing
# ---------------------------------------------------------------------------

def occupied_slots(spec: FilterSpec, table: jnp.ndarray) -> jnp.ndarray:
    """Scalar uint32: number of nonzero fingerprint slots (bank-shaped
    tables report per-member counts over the last axis)."""
    slots = unpack_slots(spec, table.reshape(*table.shape[:-1],
                                             spec.n_buckets, spec.s))
    return jnp.sum((slots != 0).astype(jnp.uint32), axis=(-1, -2))


def cuckoo_load_factor(spec: FilterSpec, table: jnp.ndarray) -> jnp.ndarray:
    """Occupied fraction of all slots — the fingerprint filter's fill
    metric (bit-density ``fill_fraction`` is meaningless for slot values)."""
    return occupied_slots(spec, table).astype(jnp.float32) / spec.n_slots


def fpr_cuckoo(slot_bits: int, slots_per_bucket: int, alpha: float) -> float:
    """Analytic FPR at load factor ``alpha``: a negative probe scans
    ``2*slots_per_bucket`` slots, each occupied w.p. alpha, each occupied
    slot matching w.p. ``(2^f + 2) / 4^f`` (the exact collision mass of
    the nonzero-forced fingerprint map, ~= 2^-f)."""
    two_f = 2.0 ** slot_bits
    p_match = (two_f + 2.0) / (two_f * two_f)
    return 1.0 - (1.0 - p_match) ** (2.0 * slots_per_bucket * alpha)


def bits_per_key(spec: FilterSpec, n: Optional[int] = None) -> float:
    """Storage bits per stored key (at load n; default: max load)."""
    n = n or int(spec.n_slots * CUCKOO_MAX_LOAD)
    return spec.m_bits / max(n, 1)


def slot_bits_for_fpr(target_fpr: float, slots_per_bucket: int = 4,
                      max_load: float = CUCKOO_MAX_LOAD) -> Optional[int]:
    """Smallest supported slot width meeting ``target_fpr`` at max load
    (None if even u16 fingerprints cannot)."""
    for f in CUCKOO_SLOT_BITS:
        if fpr_cuckoo(f, slots_per_bucket, max_load) <= target_fpr:
            return f
    return None


def spec_for_n(n: int, target_fpr: Optional[float] = None,
               slot_bits: Optional[int] = None, slots_per_bucket: int = 4,
               max_load: float = CUCKOO_MAX_LOAD) -> FilterSpec:
    """Size a cuckoo spec for ~n keys at load factor <= ``max_load``.

    ``slot_bits`` defaults to the smallest width meeting ``target_fpr``
    (or u8 when no target is given). Bucket count rounds up to a power of
    two, so the realized load is at most ``max_load``."""
    if slot_bits is None:
        if target_fpr is None:
            slot_bits = 8
        else:
            slot_bits = slot_bits_for_fpr(target_fpr, slots_per_bucket,
                                          max_load)
            if slot_bits is None:
                raise ValueError(
                    f"no supported cuckoo slot width reaches fpr "
                    f"{target_fpr:g} at load {max_load}; use a Bloom "
                    f"variant or lower the load")
    need = max(int(math.ceil(n / (max_load * slots_per_bucket))), 1)
    n_buckets = 1 << max(int(math.ceil(math.log2(need))), 0)
    m_bits = n_buckets * slots_per_bucket * slot_bits
    return FilterSpec(variant="cuckoo", m_bits=m_bits, k=2,
                      slot_bits=slot_bits, slots_per_bucket=slots_per_bucket)
