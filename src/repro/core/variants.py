"""Bloom filter variants (paper §2.1): CBF, BBF, RBBF, SBF, CSBF.

Pure-jnp reference semantics, vectorized over keys. These definitions are the
single source of truth: the Pallas kernels in ``repro.kernels`` are verified
against the functions here, and the distributed filters in
``repro.core.distributed`` compose them.

Layout conventions (TPU adaptation, see DESIGN.md §2):

* word size S = 32 bits (the TPU VPU's native word);
* the filter is a flat ``(n_words,)`` uint32 array;
* blocked variants view it as ``n_blocks`` blocks of ``s = B/32`` words;
* all sizes (m, B) are powers of two so index extraction is mask/shift —
  mirroring the paper's practice of power-of-two block counts.

Variant semantics
-----------------
CBF    k bit positions anywhere in the m-bit array (double hashing +
       multiplicative salts; Kirsch–Mitzenmacher index derivation).
BBF    k bit positions anywhere within one B-bit block (word chosen per bit
       by multiplicative hash — the WarpCore-style layout).
RBBF   BBF with B = 32 (one machine word).
SBF    bit i lives in word ``i mod s`` of the block — even spread, whole-word
       test, vectorizable (the paper's main subject).
CSBF   the s words are split into z groups of g = s/z; one word per group is
       selected by hash and receives k/z bits (Lang et al. layout).
COUNTINGBF
       SBF bit placement, but every logical bit is a packed 4-bit saturating
       counter (8 per uint32), enabling ``remove`` and ``decay`` — the
       deletable-filter capability GPU counting filters buy with atomicAdd
       and we buy with ownership partitioning (DESIGN.md §10). Storage is
       4x the bit filter: logical word w expands to counter words
       [4w, 4w+4); bit i of w lives in counter word 4w + i//8, nibble i%8.
CUCKOO
       Not a Bloom variant at all: a bucketed cuckoo *fingerprint* filter
       (Fan et al.), the AMQ family GPU filter papers benchmark Bloom
       designs against. ``slots_per_bucket`` fingerprints of ``slot_bits``
       bits each, packed into u32 words; partial-key hashing derives the
       alternate bucket from the fingerprint alone (XOR involution), so
       relocation never re-reads the key. Deletable at ~1x storage (vs the
       counting filter's 4x), at the cost of a bounded-kick insert loop
       with an explicit failure signal. Reference semantics live in
       ``core.fingerprint``; kernels in ``kernels.cuckoofilter``
       (DESIGN.md §13).
QUOTIENT
       The second fingerprint family (Bender et al.'s quotient filter, the
       design "High-Performance Filters for GPUs" builds its two-level GQF
       on). A p-bit fingerprint splits into ``q = log2(n_slots)`` quotient
       bits (the home slot) and ``r_bits`` remainder bits stored in the
       slot; three metadata bits per slot (is_occupied / is_continuation /
       is_shifted) encode the run/cluster structure of linear-probe
       displacement. Every stored fingerprint is exactly recoverable from
       the table, which is what buys the two capabilities no other engine
       here has: **lossless merge** (decode both, union, rebuild) and
       **lossless resize** (doubling the table moves one bit from
       remainder to quotient — re-slot fingerprints, no raw keys).
       Reference semantics live in ``core.quotient``; kernels in
       ``kernels.quotientfilter`` (DESIGN.md §15).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hashing as H

WORD_BITS = 32
_LOG2_WORD = 5

VARIANTS = ("cbf", "bbf", "rbbf", "sbf", "csbf", "countingbf", "cuckoo",
            "quotient")

CUCKOO_SLOT_BITS = (8, 16)           # u8 / u16 fingerprint slot widths

QUOTIENT_SLOT_BITS = (8, 16, 32)     # quotient slot lane widths
QF_META_BITS = 3                     # occupied / continuation / shifted

# Packed 4-bit counters (countingbf): expansion factor and nibble geometry.
COUNTER_BITS = 4
NIBBLES_PER_WORD = WORD_BITS // COUNTER_BITS          # 8
COUNTER_WORDS_PER_WORD = WORD_BITS // NIBBLES_PER_WORD  # 4
COUNTER_MAX = (1 << COUNTER_BITS) - 1                 # 15 (saturation value)
_NIB_LSB = np.uint32(0x11111111)                      # LSB of every nibble


def _log2i(x: int) -> int:
    assert x > 0 and (x & (x - 1)) == 0, f"{x} must be a power of two"
    return x.bit_length() - 1


@dataclasses.dataclass(frozen=True)
class FilterSpec:
    """Static description of a Bloom filter instance."""

    variant: str                 # one of VARIANTS
    m_bits: int                  # total size in bits (power of two)
    k: int                       # fingerprint bits per key
    block_bits: int = 256        # B — block size in bits (blocked variants)
    z: int = 1                   # CSBF: number of sector groups
    slot_bits: int = 8           # CUCKOO/QUOTIENT: slot lane width
    slots_per_bucket: int = 4    # CUCKOO: slots per bucket (pow2)
    r_bits: int = 0              # QUOTIENT: remainder bits stored per slot

    def __post_init__(self):
        assert self.variant in VARIANTS, self.variant
        _log2i(self.m_bits)
        assert 1 <= self.k <= H.MAX_SALTS
        if self.variant == "cbf":
            object.__setattr__(self, "block_bits", self.m_bits)
        if self.variant == "rbbf":
            object.__setattr__(self, "block_bits", WORD_BITS)
        if self.variant == "quotient":
            assert self.slot_bits in QUOTIENT_SLOT_BITS, self.slot_bits
            assert 1 <= self.r_bits <= self.slot_bits - QF_META_BITS, \
                (f"r_bits={self.r_bits} must leave {QF_META_BITS} metadata "
                 f"bits in a u{self.slot_bits} slot")
            n_slots = self.m_bits // self.slot_bits
            q = _log2i(n_slots)
            assert q + self.r_bits <= 31, \
                "fingerprint q+r must fit a uint32 below the empty sentinel"
            # one hash stream yields the whole p-bit fingerprint; a u32
            # word is the "block" of the shared geometry (s == 1), so VMEM
            # budgets and bank offsets reuse the Bloom machinery unchanged
            object.__setattr__(self, "k", 1)
            object.__setattr__(self, "block_bits", WORD_BITS)
        if self.variant == "cuckoo":
            assert self.slot_bits in CUCKOO_SLOT_BITS, self.slot_bits
            _log2i(self.slots_per_bucket)
            bucket_bits = self.slots_per_bucket * self.slot_bits
            assert bucket_bits >= WORD_BITS, \
                "a bucket must fill at least one u32 word"
            # a bucket IS the "block" of the shared geometry: s words per
            # bucket, n_blocks == n_buckets — so layout/regime machinery
            # (VMEM budgets, row gathers, bank offsets) applies unchanged
            object.__setattr__(self, "block_bits", bucket_bits)
        _log2i(self.block_bits)
        assert WORD_BITS <= self.block_bits <= self.m_bits
        if self.variant == "csbf":
            assert self.z >= 1 and self.s % self.z == 0, "z must divide s"
            assert self.k % self.z == 0, "k must be a multiple of z"

    # -- derived geometry ---------------------------------------------------
    @property
    def n_words(self) -> int:
        return self.m_bits // WORD_BITS

    @property
    def is_counting(self) -> bool:
        return self.variant == "countingbf"

    @property
    def is_fingerprint(self) -> bool:
        """Fingerprint (cuckoo/quotient) specs store hashed slot values,
        not bit patterns — the Bloom engines and pattern helpers don't
        apply, and fill is measured as slot load factor."""
        return self.variant in ("cuckoo", "quotient")

    @property
    def is_quotient(self) -> bool:
        return self.variant == "quotient"

    # -- fingerprint geometry (is_fingerprint specs only) --------------------
    @property
    def slots_per_word(self) -> int:
        return WORD_BITS // self.slot_bits

    @property
    def n_buckets(self) -> int:
        return self.n_blocks

    @property
    def n_slots(self) -> int:
        """Total fingerprint slots — the capacity at load factor 1.0."""
        if self.is_quotient:
            return self.m_bits // self.slot_bits
        return self.n_buckets * self.slots_per_bucket

    @property
    def q_bits(self) -> int:
        """QUOTIENT: quotient bits — log2 of the slot count."""
        assert self.is_quotient
        return _log2i(self.n_slots)

    @property
    def fingerprint_bits(self) -> int:
        """QUOTIENT: full fingerprint width p = q + r. Conserved across
        lossless resize (a doubling moves one bit from r to q)."""
        return self.q_bits + self.r_bits

    @property
    def storage_words(self) -> int:
        """uint32 words of backing storage: 4x the logical words for the
        counting variant (4-bit counter per logical bit), 1x otherwise."""
        return self.n_words * (COUNTER_WORDS_PER_WORD if self.is_counting
                               else 1)

    @property
    def counter_row_words(self) -> int:
        """Counter words per block (countingbf): 4 per logical word."""
        return self.s * COUNTER_WORDS_PER_WORD

    @property
    def s(self) -> int:
        """Words per block."""
        return self.block_bits // WORD_BITS

    @property
    def n_blocks(self) -> int:
        return self.m_bits // self.block_bits

    @property
    def g(self) -> int:
        """CSBF: words per group."""
        return self.s // self.z

    def bits_per_element(self, n: int) -> float:
        """c = m/n — filter bits per inserted element at load ``n``."""
        return self.m_bits / max(n, 1)

    def __str__(self):
        if self.variant == "quotient":
            # the q/r split and metadata layout ARE the spec: a quotient
            # table at the same m as an sbf or cuckoo spec (or the same
            # quotient table pre/post resize, same p different split) must
            # never print — or cache-key (core.tuning._plan_key) —
            # identically
            return (f"quotient(m=2^{_log2i(self.m_bits)}b, "
                    f"q{self.q_bits}+r{self.r_bits}, "
                    f"u{self.slot_bits}[occ|cont|shift])")
        if self.variant == "cuckoo":
            # slot geometry IS the spec for fingerprint filters: two cuckoo
            # specs with equal m but different slot widths must never print
            # (or cache-key, see core.tuning._plan_key) identically
            return (f"cuckoo(m=2^{_log2i(self.m_bits)}b, "
                    f"{self.slots_per_bucket}xu{self.slot_bits})")
        return (f"{self.variant}(m=2^{_log2i(self.m_bits)}b, B={self.block_bits}, "
                f"k={self.k}" + (f", z={self.z}" if self.variant == "csbf" else "") + ")")


def init(spec: FilterSpec) -> jnp.ndarray:
    return jnp.zeros((spec.storage_words,), dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# Pattern generation (paper §4.2) — trace-time-unrolled multiplicative hashing
# ---------------------------------------------------------------------------

def block_patterns(spec: FilterSpec, h_pattern: jnp.ndarray,
                   batched: bool = True) -> jnp.ndarray:
    """Per-key word masks for blocked variants.

    ``h_pattern``: (n,) uint32 base hashes. Returns (n, s) uint32 masks; the
    bitwise OR of mask[j] into word j of the key's block realizes an add, and
    ``(word & mask) == mask`` for all j realizes a membership test.

    The loops below run at *trace time* (Python), so every salt index is a
    compile-time constant and XLA sees inlined literals — the analogue of the
    paper's template-metaprogramming salt inlining.
    """
    n = h_pattern.shape[0]
    s = spec.s
    masks = jnp.zeros((n, s), dtype=jnp.uint32)

    if spec.variant in ("sbf", "countingbf"):   # identical bit placement
        # `batched=False` keeps every salt a scalar literal — required inside
        # Pallas kernel bodies, which may not capture array constants.
        if spec.k % s == 0 and batched:
            # §Perf B4 — the paper-recommended k ≡ 0 (mod s) configuration
            # admits a fully-batched pattern build: ONE broadcast multiply
            # against the salt vector, one shift, one OR-fold over the k/s
            # rounds. Replaces 2k sequential vector ops with ~4.
            salts = jnp.asarray(H.SALTS[: spec.k], dtype=jnp.uint32)
            bits = (h_pattern[:, None] * salts[None, :]) >> jnp.uint32(
                32 - _LOG2_WORD)                              # (n, k)
            layers = (jnp.uint32(1) << bits).reshape(n, spec.k // s, s)
            masks = layers[:, 0]
            for j in range(1, spec.k // s):   # k/s <= 2 in practice
                masks = masks | layers[:, j]
            return masks
        cols = [jnp.zeros((n,), jnp.uint32) for _ in range(s)]
        for i in range(spec.k):
            bit = H.mulshift(h_pattern, H.SALTS[i], _LOG2_WORD)
            cols[i % s] = cols[i % s] | (jnp.uint32(1) << bit)
        return jnp.stack(cols, axis=1)

    if spec.variant in ("bbf", "rbbf"):
        log2s = _log2i(s)
        cols = jnp.arange(s, dtype=jnp.uint32)[None, :]
        for i in range(spec.k):
            bit = H.mulshift(h_pattern, H.SALTS[i], _LOG2_WORD)
            bitval = (jnp.uint32(1) << bit)[:, None]
            if log2s == 0:
                masks = masks | bitval
            else:
                w = H.mulshift(h_pattern, H.WORD_SALTS[i], log2s)[:, None]
                masks = masks | jnp.where(cols == w, bitval, jnp.uint32(0))
        return masks

    if spec.variant == "csbf":
        g, z, kz = spec.g, spec.z, spec.k // spec.z
        log2g = _log2i(g)
        cols = jnp.arange(s, dtype=jnp.uint32)[None, :]
        for j in range(z):
            # select the word within group j that receives this key's bits
            if log2g == 0:
                w = jnp.full_like(h_pattern, j * g)
            else:
                w = jnp.uint32(j * g) + H.mulshift(h_pattern, H.GROUP_SALTS[j], log2g)
            gmask = jnp.zeros_like(h_pattern)
            for t in range(kz):
                bit = H.mulshift(h_pattern, H.SALTS[j * kz + t], _LOG2_WORD)
                gmask = gmask | (jnp.uint32(1) << bit)
            masks = masks | jnp.where(cols == w[:, None], gmask[:, None], jnp.uint32(0))
        return masks

    raise ValueError(f"block_patterns undefined for variant {spec.variant}")


def cbf_positions(spec: FilterSpec, h_pattern: jnp.ndarray,
                  h_block: jnp.ndarray) -> jnp.ndarray:
    """(n, k) global bit positions for the classical filter.

    Kirsch–Mitzenmacher double hashing (h1 + i*h2) re-mixed per index with a
    multiplicative salt, masked to the power-of-two filter size.
    """
    log2m = _log2i(spec.m_bits)
    pos = []
    for i in range(spec.k):
        hi = h_pattern + jnp.uint32(i) * h_block
        pos.append(H.mulshift(hi, H.SALTS[i], min(log2m, 32)) & jnp.uint32(spec.m_bits - 1))
    return jnp.stack(pos, axis=-1)


# ---------------------------------------------------------------------------
# contains / add — vectorized reference implementations
# ---------------------------------------------------------------------------

def _hashes(keys: jnp.ndarray):
    return H.hash_keys(keys)


def contains(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray) -> jnp.ndarray:
    """Vectorized bulk membership test. Returns (n,) bool."""
    assert not spec.is_fingerprint, "use core.fingerprint.cuckoo_contains"
    if spec.is_counting:
        return counting_contains(spec, filt, keys)
    h1, h2 = _hashes(keys)
    if spec.variant == "cbf":
        pos = cbf_positions(spec, h1, h2)                       # (n, k)
        words = filt[(pos >> np.uint32(_LOG2_WORD)).astype(jnp.int32)]
        bits = jnp.uint32(1) << (pos & jnp.uint32(WORD_BITS - 1))
        return jnp.all((words & bits) != 0, axis=-1)
    blk = H.block_index(h2, spec.n_blocks)                      # (n,)
    masks = block_patterns(spec, h1)                            # (n, s)
    word_idx = (blk[:, None] * jnp.uint32(spec.s)
                + jnp.arange(spec.s, dtype=jnp.uint32)[None, :]).astype(jnp.int32)
    words = filt[word_idx]                                      # (n, s) gather
    return jnp.all((words & masks) == masks, axis=-1)


def add_loop(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray) -> jnp.ndarray:
    """Sequential (fori_loop) bulk insert — the exact-ownership reference.

    One dynamic-slice read-modify-write per key; no scatter collisions by
    construction. This is the semantics the Pallas add kernel reproduces.
    """
    h1, h2 = _hashes(keys)
    if spec.variant == "cbf":
        pos = cbf_positions(spec, h1, h2)                       # (n, k)
        widx = (pos >> np.uint32(_LOG2_WORD)).astype(jnp.int32)
        bits = jnp.uint32(1) << (pos & jnp.uint32(WORD_BITS - 1))

        def body(i, f):
            for j in range(spec.k):   # static unroll over k
                f = f.at[widx[i, j]].set(f[widx[i, j]] | bits[i, j])
            return f

        return jax.lax.fori_loop(0, h1.shape[0], body, filt)

    blk = H.block_index(h2, spec.n_blocks)
    masks = block_patterns(spec, h1)                            # (n, s)
    s = spec.s

    def body(i, f):
        start = (blk[i] * jnp.uint32(s)).astype(jnp.int32)
        words = jax.lax.dynamic_slice(f, (start,), (s,))
        return jax.lax.dynamic_update_slice(f, words | masks[i], (start,))

    return jax.lax.fori_loop(0, h1.shape[0], body, filt)


def add_scatter(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray) -> jnp.ndarray:
    """Vectorized bulk insert via bit-plane scatter-add.

    Bitwise-OR is not a JAX scatter combiner, so each of the 32 bit planes is
    scattered with ``add`` and re-thresholded — duplicate-index safe because
    OR is idempotent. Memory stays O(n_words) per plane.
    """
    h1, h2 = _hashes(keys)
    if spec.variant == "cbf":
        pos = cbf_positions(spec, h1, h2).reshape(-1)
        widx = (pos >> np.uint32(_LOG2_WORD)).astype(jnp.int32)
        vals = jnp.uint32(1) << (pos & jnp.uint32(WORD_BITS - 1))
    else:
        blk = H.block_index(h2, spec.n_blocks)
        masks = block_patterns(spec, h1)
        widx = ((blk[:, None] * jnp.uint32(spec.s)
                 + jnp.arange(spec.s, dtype=jnp.uint32)[None, :])
                .astype(jnp.int32).reshape(-1))
        vals = masks.reshape(-1)
    acc = filt
    for b in range(WORD_BITS):
        plane = ((vals >> np.uint32(b)) & jnp.uint32(1))
        cnt = jnp.zeros((spec.n_words,), jnp.uint32).at[widx].add(plane)
        acc = acc | ((cnt > 0).astype(jnp.uint32) << np.uint32(b))
    return acc


def segment_totals(sorted_ids: jnp.ndarray, vals: jnp.ndarray,
                   combine) -> jnp.ndarray:
    """Per-row full-segment reduction of ``vals`` grouped by ``sorted_ids``.

    ``sorted_ids``: (n,) nondecreasing segment ids; ``vals``: (n, w) rows;
    ``combine``: associative elementwise op (e.g. ``jnp.bitwise_or``,
    :func:`nib_sat_add_words`). Returns (n, w) where every row holds the
    reduction of its *whole* segment (broadcast back from the segment end),
    via one segmented associative scan — no data-dependent loop, so it runs
    identically in the jnp reference and inside Pallas kernel bodies.
    """
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])

    def seg_combine(a, b):
        m1, f1 = a
        m2, f2 = b
        return jnp.where(f2[:, None], m2, combine(m1, m2)), f1 | f2

    scanned, _ = jax.lax.associative_scan(seg_combine, (vals, seg_start),
                                          axis=0)
    end_idx = jnp.searchsorted(sorted_ids, sorted_ids, side="right") - 1
    return scanned[end_idx]


def contains_rows(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray
                  ) -> jnp.ndarray:
    """Row-gather membership test (§Perf iteration B1).

    Hypothesis: ``filt[word_idx]`` with (n, s) scattered indices issues s
    independent random accesses per key; viewing the filter as
    (n_blocks, s) and gathering ONE row per key touches each block once —
    the paper's one-cache-line-per-query property, restored at the XLA
    gather level. Semantics identical to ``contains``.
    """
    if spec.variant == "cbf" or spec.is_counting:
        return contains(spec, filt, keys)
    h1, h2 = _hashes(keys)
    blk = H.block_index(h2, spec.n_blocks).astype(jnp.int32)
    masks = block_patterns(spec, h1)
    rows = filt[_row_words(spec, blk)]                       # one row/key
    return jnp.all((rows & masks) == masks, axis=-1)


def _row_words(spec: FilterSpec, blk: jnp.ndarray) -> jnp.ndarray:
    """(n, s) flat word indices of each block's row. Indexing the 1-D
    words with these keeps every array lane-dense; a (n_blocks, s) view
    would pad s words to 128 lanes on TPU (16x the filter at s=8)."""
    return blk[:, None] * spec.s + jnp.arange(spec.s, dtype=jnp.int32)


def add_rows(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray
             ) -> jnp.ndarray:
    """Whole-batch bulk insert by row gather + row scatter (§Perf B2,
    see :func:`or_rows`).

    Per-key RMW loops pay XLA while-loop overhead (~10 us/key) and
    bit-plane scatters pay 32 full-filter passes; this touches each key's
    row a few times at most.
    """
    if spec.variant == "cbf":
        return add_scatter(spec, filt, keys)
    h1, h2 = _hashes(keys)
    blk = H.block_index(h2, spec.n_blocks).astype(jnp.int32)
    masks = block_patterns(spec, h1)
    return or_rows(spec, filt, blk, masks)


def or_rows(spec: FilterSpec, filt: jnp.ndarray, blk: jnp.ndarray,
            masks: jnp.ndarray, n_rows: Optional[int] = None) -> jnp.ndarray:
    """Exact whole-batch OR of per-key ``masks`` into their blocks.

    Each round gathers every key's row and scatters ``row | mask`` back,
    but only for the words still missing some of the key's bits. Where
    several keys share a word, one write lands per round and it adds that
    key's missing bits, so every round makes progress and the loop ends
    after as many rounds as the most keys sharing one word (a handful at
    realistic loads). The result is ``filt | masks`` per word whatever the
    scatter's order among duplicates. No sort and no segmented scan:
    both compile for minutes on TPU at millions of keys. Rows with
    all-zero masks are OR no-ops, which is what makes this the
    overflow-residual backstop of the jit partition path (`kernels.ops`)
    as well as the `add_rows` engine.

    ``n_rows`` overrides the row count (default ``spec.n_blocks``) so a
    *bank* of B filters can be treated as one super-filter of B*n_blocks
    rows — ``blk`` then carries member-offset block ids (see ``bank_*``).
    """
    assert filt.shape == ((n_rows or spec.n_blocks) * spec.s,), filt.shape
    idx = _row_words(spec, blk)
    drop = jnp.int32(filt.shape[0])                 # out of range: no write

    def missing(f):
        return (f[idx] & masks) != masks

    def body(carry):
        f, todo = carry
        f = f.at[jnp.where(todo, idx, drop)].set(f[idx] | masks, mode="drop")
        return f, missing(f)

    out, _ = jax.lax.while_loop(lambda c: jnp.any(c[1]), body,
                                (filt, missing(filt)))
    return out


def add(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
        method: str = "rows") -> jnp.ndarray:
    assert not spec.is_fingerprint, "use core.fingerprint.cuckoo_add"
    if spec.is_counting:
        return counting_add(spec, filt, keys)
    if method == "loop":
        return add_loop(spec, filt, keys)
    if method == "scatter":
        return add_scatter(spec, filt, keys)
    if method == "rows":
        return add_rows(spec, filt, keys)
    raise ValueError(method)


def fill_fraction(filt: jnp.ndarray) -> jnp.ndarray:
    """Fraction of set bits (useful health metric for dedup filters).
    Shape-agnostic: a ``(B, n_words)`` bank reports its aggregate fill."""
    pop = jax.lax.population_count(filt.view(jnp.int32) if filt.dtype != jnp.uint32 else filt)
    return jnp.sum(pop.astype(jnp.float32)) / (filt.size * WORD_BITS)


# ---------------------------------------------------------------------------
# Counting filter (countingbf): packed 4-bit saturating counters
# ---------------------------------------------------------------------------
# Nibble-parallel bit tricks operate on all 8 counters of a uint32 at once;
# they are plain vector ops, so the same helpers run inside Pallas kernel
# bodies (kernels/countingbf.py) and in the jnp reference below.
#
# Update semantics (order-independent within one bulk op, which is what
# makes the sequential kernels bit-exact against the vectorized reference):
#   increment: saturate at 15; a saturated counter sticks forever (it can no
#              longer prove its true count, so decrements must skip it too —
#              the standard counting-Bloom rule that preserves
#              no-false-negatives under remove).
#   remove:    decrement counters in (0, 15); 0 is an underflow guard, 15 is
#              sticky.
#   decay:     decrement EVERY nonzero counter, including saturated ones —
#              aging deliberately forgets; stale keys gaining false
#              negatives is the point.


def nib_saturated(w: jnp.ndarray) -> jnp.ndarray:
    """1 at the LSB of each nibble that equals 15 (saturated)."""
    return w & (w >> jnp.uint32(1)) & (w >> jnp.uint32(2)) \
        & (w >> jnp.uint32(3)) & _NIB_LSB


def nib_nonzero(w: jnp.ndarray) -> jnp.ndarray:
    """1 at the LSB of each nibble that is nonzero."""
    return (w | (w >> jnp.uint32(1)) | (w >> jnp.uint32(2))
            | (w >> jnp.uint32(3))) & _NIB_LSB


def sat_inc_word(w: jnp.ndarray, inc: jnp.ndarray) -> jnp.ndarray:
    """Saturating +1 on the nibbles flagged (value 1) in ``inc``."""
    return w + (inc & ~nib_saturated(w))


def guard_dec_word(w: jnp.ndarray, dec: jnp.ndarray) -> jnp.ndarray:
    """Guarded -1 on flagged nibbles: skips 0 (underflow) and 15 (sticky)."""
    return w - (dec & nib_nonzero(w) & ~nib_saturated(w))


def decay_word(w: jnp.ndarray) -> jnp.ndarray:
    """-1 on every nonzero nibble (aging step; saturated counters decay too)."""
    return w - nib_nonzero(w)


# Multi-count nibble arithmetic (whole-tile gather/scatter probe engine).
# The per-key kernels apply 0/1 increments one key at a time; the gather
# engine instead segment-reduces all same-block increments first and applies
# the TOTAL in one RMW. Saturation makes that exact: counts clip at 15
# during the reduction, and min(old + c, 15) / max(old - c, 0) for c >= 15
# equal the c = 15 results, so the batched formulas below reproduce the
# sequential per-key semantics bit-for-bit.
_NIB_EVEN = np.uint32(0x0F0F0F0F)     # even-nibble byte lanes
_BYTE_BIT4 = np.uint32(0x10101010)    # bit 4 of every byte (carry/borrow flag)


def _halves(w: jnp.ndarray):
    """Split packed nibbles into even/odd byte lanes (each value fits a byte
    with headroom, so per-byte +/- is carry-free SWAR)."""
    return w & _NIB_EVEN, (w >> jnp.uint32(4)) & _NIB_EVEN


def nib_sat_add_words(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Nibble-wise saturating add of two packed counter words: min(a+b, 15).

    Associative and commutative, so it is a valid segmented-scan combiner
    (the counting analogue of the bit filters' segment OR)."""
    def half(x, y):
        s = x + y                               # per-byte sums <= 30
        ov = s & _BYTE_BIT4                     # set iff the byte is >= 16
        return (s | (ov - (ov >> jnp.uint32(4)))) & _NIB_EVEN
    ae, ao = _halves(a)
    be, bo = _halves(b)
    return half(ae, be) | (half(ao, bo) << jnp.uint32(4))


def nib_guard_sub_words(w: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Nibble-wise guarded multi-decrement: where(w == 15, 15, max(w - c, 0)).

    The batched form of ``c`` applications of :func:`guard_dec_word` —
    sticky saturation and the 0 floor are preserved per nibble."""
    def half(x, y):
        d = (x | _BYTE_BIT4) - y                # bias: per-byte in [1, 31]
        ok = d & _BYTE_BIT4                     # set iff x >= y (no borrow)
        return d & (ok - (ok >> jnp.uint32(4))) & _NIB_EVEN
    we, wo = _halves(w)
    ce, co = _halves(c)
    sub = half(we, ce) | (half(wo, co) << jnp.uint32(4))
    return sub | (nib_saturated(w) * jnp.uint32(COUNTER_MAX))   # 15 sticks


def expand_mask_words(masks: jnp.ndarray) -> jnp.ndarray:
    """Logical bit masks -> nibble-increment words, (..., s) -> (..., 4s).

    Byte c of logical word j maps to counter word 4j+c; bit b of that byte
    becomes nibble b (value 1). All loops unroll at trace time."""
    cols = []
    for c in range(COUNTER_WORDS_PER_WORD):
        byte = (masks >> jnp.uint32(8 * c)) & jnp.uint32(0xFF)
        inc = jnp.zeros_like(masks)
        for b in range(NIBBLES_PER_WORD):
            inc = inc | (((byte >> jnp.uint32(b)) & jnp.uint32(1))
                         << jnp.uint32(COUNTER_BITS * b))
        cols.append(inc)
    out = jnp.stack(cols, axis=-1)
    return out.reshape(*masks.shape[:-1],
                       masks.shape[-1] * COUNTER_WORDS_PER_WORD)


def collapse_counter_words(cwords: jnp.ndarray) -> jnp.ndarray:
    """Occupancy view: counter words -> logical bit words, (..., 4s) -> (..., s).

    Bit i of the result is set iff the counter for logical bit i is nonzero.
    Exact inverse direction of :func:`expand_mask_words`."""
    nzb = nib_nonzero(cwords)                 # bit 4b <-> nibble b nonzero
    byte = jnp.zeros_like(cwords)
    for b in range(NIBBLES_PER_WORD):
        byte = byte | (((nzb >> jnp.uint32(COUNTER_BITS * b))
                        & jnp.uint32(1)) << jnp.uint32(b))
    b4 = byte.reshape(*cwords.shape[:-1],
                      cwords.shape[-1] // COUNTER_WORDS_PER_WORD,
                      COUNTER_WORDS_PER_WORD)
    return (b4[..., 0] | (b4[..., 1] << jnp.uint32(8))
            | (b4[..., 2] << jnp.uint32(16)) | (b4[..., 3] << jnp.uint32(24)))


def counting_to_bloom(spec: FilterSpec, counters: jnp.ndarray) -> jnp.ndarray:
    """Collapse a counting filter to the equivalent (n_words,) bit filter."""
    assert spec.is_counting
    return collapse_counter_words(counters[None])[0]


def counting_from_bloom(spec: FilterSpec, bits: jnp.ndarray) -> jnp.ndarray:
    """Bit filter -> counting filter with every set bit's counter at 1.

    Membership-preserving but count-lossy — the inverse of
    :func:`counting_to_bloom` only up to occupancy."""
    assert spec.is_counting
    return expand_mask_words(bits[None])[0]


def _counting_layout(spec: FilterSpec, keys: jnp.ndarray):
    h1, h2 = _hashes(keys)
    blk = H.block_index(h2, spec.n_blocks)
    masks = block_patterns(spec, h1)                   # (n, s) logical masks
    return blk, masks


def _nibble_counts(spec: FilterSpec, keys: jnp.ndarray,
                   valid: Optional[jnp.ndarray],
                   member: Optional[jnp.ndarray] = None,
                   total_words: Optional[int] = None):
    """Yield, for nibble b = 0..7, (b, (4*T,) int32 number of (valid) keys
    whose pattern sets nibble b of each counter word) (T = logical words;
    ``spec.n_words`` for one filter, ``B * n_words`` for a bank, where
    ``member`` offsets each key's words).

    One scatter-add per nibble of the k bit positions per key, duplicates
    within a key dropped (a pattern sets such a bit once, as
    ``block_patterns`` ORs it once). Every array keeps keys or counter
    words on the last axis: an (n, s) or (words, 32) layout pads to 128
    lanes on a TPU (48 GiB for 128 MiB of counters), and a scatter holds
    a few copies of its operand, so each holds one nibble plane."""
    assert spec.variant == "countingbf"
    h1, h2 = _hashes(keys)
    word0 = H.block_index(h2, spec.n_blocks).astype(jnp.int32) * spec.s
    if member is not None:
        word0 = word0 + member.astype(jnp.int32) * jnp.int32(spec.n_words)
    n_cw = (total_words or spec.n_words) * COUNTER_WORDS_PER_WORD
    keep = (jnp.ones(keys.shape[:1], jnp.bool_) if valid is None
            else valid.astype(jnp.bool_))
    # bit i of the pattern lands in word i % s (see ``block_patterns``)
    pos = [H.mulshift(h1, H.SALTS[i], _LOG2_WORD).astype(jnp.int32)
           for i in range(spec.k)]
    nib, cw = [], []
    for i in range(spec.k):
        first = keep
        for j in range(i % spec.s, i, spec.s):
            first = first & (pos[j] != pos[i])
        word = word0 + i % spec.s
        nib.append(jnp.where(first, pos[i] % NIBBLES_PER_WORD, -1))
        cw.append(word * COUNTER_WORDS_PER_WORD + pos[i] // NIBBLES_PER_WORD)
    nib, cw = jnp.stack(nib), jnp.stack(cw)                   # (k, n)
    for b in range(NIBBLES_PER_WORD):
        plane = jnp.zeros((n_cw,), jnp.int32)
        yield b, plane.at[jnp.where(nib == b, cw, n_cw)].add(1, mode="drop")


def _apply_counts(counters: jnp.ndarray, counts, op: str) -> jnp.ndarray:
    """Saturating add (``op="add"``) or guarded subtract of per-counter
    ``counts`` (the planes of :func:`_nibble_counts`) into packed (W,)
    counter words."""
    out = jnp.zeros_like(counters)
    for b, count in counts:
        shift = jnp.uint32(COUNTER_BITS * b)
        nib = ((counters >> shift) & jnp.uint32(COUNTER_MAX)).astype(jnp.int32)
        if op == "add":
            new = jnp.minimum(nib + count, COUNTER_MAX)
        else:
            new = jnp.where(nib == COUNTER_MAX, COUNTER_MAX,
                            jnp.maximum(nib - count, 0))
        out = out | (new.astype(jnp.uint32) << shift)
    return out


def counting_add(spec: FilterSpec, counters: jnp.ndarray, keys: jnp.ndarray,
                 valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Vectorized bulk increment (saturating at 15).

    Saturating increments commute, so the batch result equals any sequential
    order: new = min(old + per-bit-count, 15). ``valid`` masks padded slots —
    counting updates are NOT idempotent, so repeat-key padding is forbidden
    here (see kernels/ops.py)."""
    assert spec.is_counting
    return _apply_counts(counters, _nibble_counts(spec, keys, valid), "add")


def counting_remove(spec: FilterSpec, counters: jnp.ndarray, keys: jnp.ndarray,
                    valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Vectorized bulk decrement (guarded: 0 floors, 15 is sticky)."""
    assert spec.is_counting
    return _apply_counts(counters, _nibble_counts(spec, keys, valid),
                         "remove")


def counting_contains(spec: FilterSpec, counters: jnp.ndarray,
                      keys: jnp.ndarray) -> jnp.ndarray:
    """(n,) bool: all k counters of the key nonzero (one row gather/key)."""
    assert spec.is_counting
    blk, masks = _counting_layout(spec, keys)
    rows = counters.reshape(spec.n_blocks, spec.counter_row_words
                            )[blk.astype(jnp.int32)]             # (n, 4s)
    logical = collapse_counter_words(rows)                       # (n, s)
    return jnp.all((logical & masks) == masks, axis=-1)


def counting_count(spec: FilterSpec, counters: jnp.ndarray,
                   keys: jnp.ndarray) -> jnp.ndarray:
    """(n,) uint32 min-counter estimate of each key's multiplicity
    (count-min style upper bound; 15 means 'at least 15')."""
    assert spec.is_counting
    blk, masks = _counting_layout(spec, keys)
    rows = counters.reshape(spec.n_blocks, spec.counter_row_words
                            )[blk.astype(jnp.int32)]             # (n, 4s)
    nib = jnp.stack([(rows >> jnp.uint32(COUNTER_BITS * b))
                     & jnp.uint32(COUNTER_MAX)
                     for b in range(NIBBLES_PER_WORD)], axis=-1)
    nib = nib.reshape(rows.shape[0], spec.s, WORD_BITS)          # (n, s, 32)
    bit = (masks[:, :, None] >> jnp.arange(WORD_BITS, dtype=jnp.uint32)
           [None, None, :]) & jnp.uint32(1)
    sel = jnp.where(bit == 1, nib, jnp.uint32(COUNTER_MAX + 1))
    return jnp.min(sel.reshape(rows.shape[0], -1), axis=-1)


def counting_decay(spec: FilterSpec, counters: jnp.ndarray) -> jnp.ndarray:
    """One aging step: every nonzero counter loses 1 (pure elementwise)."""
    assert spec.is_counting
    return decay_word(counters)


def counting_update_loop(spec: FilterSpec, counters: jnp.ndarray,
                         keys: jnp.ndarray, valid: Optional[jnp.ndarray],
                         op: str) -> jnp.ndarray:
    """Sequential (fori_loop) oracle mirroring the Pallas kernels exactly:
    one dynamic-slice RMW of the key's 4s-word counter row per key."""
    assert spec.is_counting and op in ("add", "remove")
    blk, masks = _counting_layout(spec, keys)
    cmasks = expand_mask_words(masks)                            # (n, 4s)
    if valid is not None:
        cmasks = cmasks * valid.astype(jnp.uint32)[:, None]
    cs = spec.counter_row_words
    starts = (blk * jnp.uint32(cs)).astype(jnp.int32)
    update = sat_inc_word if op == "add" else guard_dec_word

    def body(i, f):
        start = starts[i]
        row = jax.lax.dynamic_slice(f, (start,), (cs,))
        return jax.lax.dynamic_update_slice(f, update(row, cmasks[i]),
                                            (start,))

    return jax.lax.fori_loop(0, keys.shape[0], body, counters)


# ---------------------------------------------------------------------------
# Bank references: B same-spec filters as ONE super-filter
# ---------------------------------------------------------------------------
# The bank trick: a (B, n_words) stack of blocked filters is bit-identical
# to a single filter of B * n_blocks blocks in which key i's block id is
# offset by member[i] * n_blocks. Every single-filter bulk op therefore
# lifts to the whole bank as ONE fused op over flat routed keys
# ``(keys (N, 2), member (N,))`` — no per-member loop, no scatter into
# per-member batches. These are the jnp reference semantics the Pallas
# bank kernels (kernels/sbf.py, kernels/countingbf.py) validate against.


def bank_block_ids(spec: FilterSpec, keys: jnp.ndarray, member: jnp.ndarray):
    """(member-offset block ids (N,) int32, logical masks (N, s)) for flat
    routed keys. ``member`` indexes the bank's leading axis."""
    h1, h2 = _hashes(keys)
    blk = H.block_index(h2, spec.n_blocks).astype(jnp.int32)
    masks = block_patterns(spec, h1)
    return member.astype(jnp.int32) * jnp.int32(spec.n_blocks) + blk, masks


def bank_contains_rows(spec: FilterSpec, words: jnp.ndarray,
                       keys: jnp.ndarray, member: jnp.ndarray) -> jnp.ndarray:
    """(N,) bool membership of flat routed keys against a (B, n_words)
    bank — one row gather over the B*n_blocks super-filter."""
    assert spec.variant != "cbf" and not spec.is_counting
    blk, masks = bank_block_ids(spec, keys, member)
    rows = words.reshape(-1)[_row_words(spec, blk)]
    return jnp.all((rows & masks) == masks, axis=-1)


def bank_add_rows(spec: FilterSpec, words: jnp.ndarray, keys: jnp.ndarray,
                  member: jnp.ndarray,
                  valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Bulk OR of flat routed keys into a (B, n_words) bank: ``or_rows``
    over the super-filter. ``valid`` zeroes
    the masks of padding slots (an OR no-op), so routed batches pad safely."""
    assert spec.variant != "cbf" and not spec.is_counting
    B = words.shape[0]
    blk, masks = bank_block_ids(spec, keys, member)
    if valid is not None:
        masks = masks * valid.astype(jnp.uint32)[:, None]
    flat = or_rows(spec, words.reshape(-1), blk, masks,
                   n_rows=B * spec.n_blocks)
    return flat.reshape(B, spec.n_words)


def bank_counting_update(spec: FilterSpec, counters: jnp.ndarray,
                         keys: jnp.ndarray, member: jnp.ndarray,
                         valid: Optional[jnp.ndarray], op: str) -> jnp.ndarray:
    """Bulk saturating increment / guarded decrement of flat routed keys
    into a (B, 4*n_words) counter bank (counting super-filter)."""
    assert spec.is_counting and op in ("add", "remove")
    B = counters.shape[0]
    counts = _nibble_counts(spec, keys, valid, member=member,
                            total_words=B * spec.n_words)
    return _apply_counts(counters.reshape(-1), counts, op).reshape(B, -1)


def bank_counting_contains(spec: FilterSpec, counters: jnp.ndarray,
                           keys: jnp.ndarray, member: jnp.ndarray
                           ) -> jnp.ndarray:
    """(N,) bool occupancy membership against a (B, 4*n_words) counter bank."""
    assert spec.is_counting
    B = counters.shape[0]
    h1, h2 = _hashes(keys)
    blk = H.block_index(h2, spec.n_blocks).astype(jnp.int32)
    masks = block_patterns(spec, h1)
    row_idx = member.astype(jnp.int32) * jnp.int32(spec.n_blocks) + blk
    rows = counters.reshape(B * spec.n_blocks, spec.counter_row_words)[row_idx]
    logical = collapse_counter_words(rows)                        # (N, s)
    return jnp.all((logical & masks) == masks, axis=-1)


# ---------------------------------------------------------------------------
# FPR theory (paper Eq. 1–3 + blocked/sectorized extensions)
# ---------------------------------------------------------------------------

def fpr_cbf(m: int, n: int, k: int) -> float:
    """Paper Eq. (1)."""
    return float((1.0 - math.exp(-k * n / m)) ** k)


def optimal_k(c: float) -> float:
    """Paper Eq. (2): k* = c ln 2."""
    return c * math.log(2.0)


def fpr_min(c: float) -> float:
    """Paper Eq. (3)."""
    return 0.5 ** (c * math.log(2.0))


def _poisson_pmf(lam: float, i: np.ndarray) -> np.ndarray:
    # exp(i log lam - lam - lgamma(i+1)) — stable for the ranges we use
    from math import lgamma
    logp = i * math.log(max(lam, 1e-300)) - lam - np.array([lgamma(x + 1) for x in i])
    return np.exp(logp)


def _poisson_support(lam: float):
    hi = int(lam + 10 * math.sqrt(lam) + 16)
    return np.arange(0, hi + 1)


def fpr_bbf(B: int, c: float, k: int) -> float:
    """Blocked filter FPR: Poisson mixture over per-block load (Putze et al.)."""
    lam = B / c
    i = _poisson_support(lam)
    p = _poisson_pmf(lam, i)
    f = np.array([fpr_cbf(B, int(x), k) if x > 0 else 0.0 for x in i])
    return float(np.sum(p * f))


def fpr_sbf(B: int, S: int, c: float, k: int) -> float:
    """Sectorized filter FPR: each word receives k/s of the key's bits."""
    s = B // S
    kw = max(k // s, 1)
    lam = B / c  # keys per block
    i = _poisson_support(lam)
    p = _poisson_pmf(lam, i)
    # P(all kw bits of one word set | i keys in block), word fill from i*kw draws
    f_word = (1.0 - (1.0 - 1.0 / S) ** (i * kw)) ** kw
    return float(np.sum(p * f_word ** s))


def fpr_csbf(B: int, S: int, c: float, k: int, z: int) -> float:
    """Cache-sectorized FPR: z groups, one word of g=s/z selected per group."""
    s = B // S
    g = s // z
    kz = k // z
    lam = (B / c) / g  # keys landing in a given *word* of a group (uniform choice)
    i = _poisson_support(lam)
    p = _poisson_pmf(lam, i)
    f_word = (1.0 - (1.0 - 1.0 / S) ** (i * kz)) ** kz
    return float(np.sum(p * f_word) ** z)


def fpr_theory(spec: FilterSpec, n: int) -> float:
    if spec.is_quotient:
        from repro.core import quotient as Q        # avoid import cycle
        return Q.fpr_quotient(spec.q_bits, spec.r_bits,
                              min(n / spec.n_slots, 1.0))
    if spec.is_fingerprint:
        from repro.core import fingerprint as F     # avoid import cycle
        return F.fpr_cuckoo(spec.slot_bits, spec.slots_per_bucket,
                            min(n / spec.n_slots, 1.0))
    c = spec.bits_per_element(n)
    if spec.variant == "cbf":
        return fpr_cbf(spec.m_bits, n, spec.k)
    if spec.variant in ("bbf", "rbbf"):
        return fpr_bbf(spec.block_bits, c, spec.k)
    if spec.variant in ("sbf", "countingbf"):   # identical bit placement
        return fpr_sbf(spec.block_bits, WORD_BITS, c, spec.k)
    if spec.variant == "csbf":
        return fpr_csbf(spec.block_bits, WORD_BITS, c, spec.k, spec.z)
    raise ValueError(spec.variant)


def snap_k(variant: str, c: float, block_bits: int = 256, z: int = 1) -> int:
    """k near the space-optimal k* = c ln 2 (Eq. 2), snapped to the
    variant's structural constraints (k ≡ 0 mod s for SBF-placement
    variants, mod z for CSBF), capped at 32."""
    k = max(int(round(optimal_k(c))), 1)
    if variant == "csbf":
        k = max(z, (k // z) * z)
    if variant in ("sbf", "countingbf"):
        s = block_bits // WORD_BITS
        k = max(s, (k // s) * s) if k >= s else k
    return min(k, 32)


def space_optimal_c(variant: str, block_bits: int, z: int, n: int,
                    target_fpr: float, max_log2_m: int = 40) -> float:
    """Iso-error sizing: smallest bits/key c = m/n (m a power of two, k
    snapped per :func:`snap_k`) whose variant-aware analytic FPR meets
    ``target_fpr`` at load n — the inverse of :func:`fpr_theory` the AMQ
    comparison harness sizes Bloom families with."""
    assert 0.0 < target_fpr < 1.0
    start = max(_log2i(1 << 10), int(math.ceil(math.log2(max(n, 2)))))
    for log2m in range(start, max_log2_m):
        m = 1 << log2m
        k = snap_k(variant, m / n, block_bits, z)
        spec = FilterSpec(variant=variant, m_bits=m, k=k,
                          block_bits=block_bits, z=z)
        if fpr_theory(spec, n) <= target_fpr:
            return m / n
    raise ValueError(f"no m <= 2^{max_log2_m} reaches fpr {target_fpr:g} "
                     f"for {variant} at n={n}")


def space_optimal_n(spec: FilterSpec, target_fpr: float = None) -> int:
    """Load n for the spec (paper §5.1).

    Without ``target_fpr``: solve Eq. (3) — the load at which the spec's k
    equals the space-error-rate-optimal k* = c ln 2.

    With ``target_fpr``: the largest n whose analytic FPR (``fpr_theory``,
    variant-aware) stays at or below the target; 0 if even n = 1 exceeds it.
    """
    if target_fpr is None:
        if spec.is_quotient:
            # quotient capacity is structural too: linear probing stays
            # practical to ~0.9 load (cluster lengths blow up past it),
            # and one slot is reserved as the cluster-scan anchor
            return max(min(int(spec.n_slots * 0.90), spec.n_slots - 1), 1)
        if spec.is_fingerprint:
            # cuckoo capacity is structural, not space-error-optimal: the
            # standard achievable load for 4-slot buckets is ~0.95
            return max(int(spec.n_slots * 0.95), 1)
        # k = c ln2  =>  c = k / ln2  =>  n = m / c
        c = spec.k / math.log(2.0)
        return max(int(spec.m_bits / c), 1)
    if fpr_theory(spec, 1) > target_fpr:
        return 0
    # fpr_theory is monotone nondecreasing in n; quotient load is capped
    # by its structural capacity (n_slots - 1 stored fingerprints)
    lo = 1
    hi = max(spec.n_slots - 1, 1) if spec.is_quotient else spec.m_bits
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if fpr_theory(spec, mid) <= target_fpr:
            lo = mid
        else:
            hi = mid - 1
    return lo
