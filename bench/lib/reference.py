"""Plain reference of the sectorized Bloom filter (``sbf``) the benchmark
holds the program to.

Written from the layout's definition, in straightforward ``jax.numpy`` on
uint32 words, and independent of the program: it imports nothing of
``repro`` and takes no table the program made.

The layout (a filter of ``m_bits`` bits, blocks of ``block_bits`` bits, s =
block_bits / 32 words per block, ``k`` bits per key):

* a key is 64 bits, held as uint32 ``[hi, lo]``;
* pattern hash h1 = xxHash32 of the key's 8 little-endian bytes (lo word,
  then hi word) under seed 0xCAFEBABE;
* block hash h2 = xxHash32 of the same two words in swapped order (hi,
  then lo) under seed 0xDEADBEEF; the block is h2 mod n_blocks;
* bit i < k of the key lives in word i mod s of its block, at bit
  (h1 * SALT[i]) >> 27 of that word, with SALT the odd multipliers drawn
  by ``numpy.random.RandomState(0xB100F)`` as :func:`salts` does;
* a bank of B members is one filter of B * n_blocks blocks, member b
  owning blocks [b * n_blocks, (b + 1) * n_blocks).

A key is present when all of its k bits are set. Adding keys sets their
bits and nothing else, so the filter is the OR of its keys' bits whatever
the order they were added in.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

WORD_BITS = 32
_P2, _P3, _P4, _P5 = 2246822519, 3266489917, 668265263, 374761393
SEED_PATTERN = 0xCAFEBABE
SEED_BLOCK = 0xDEADBEEF
SALT_SEED = 0xB100F
MAX_K = 96


def salts(n: int = MAX_K) -> np.ndarray:
    """The layout's odd 32-bit multipliers: 2r+1 for r uniform below 2^31,
    xor-ed with a second draw shifted up by one, then forced odd."""
    rng = np.random.RandomState(SALT_SEED)
    first = rng.randint(0, 2**31, size=n, dtype=np.int64).astype(np.uint64)
    out = (first * 2 + 1).astype(np.uint32)
    out ^= rng.randint(0, 2**31, size=n, dtype=np.int64).astype(
        np.uint32) << np.uint32(1)
    return out | np.uint32(1)


_SALTS = salts()


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def xxh32_two_words(first, second, seed: int):
    """xxHash32 of an 8-byte input given as its two little-endian 4-byte
    lanes, ``first`` at the lower address."""
    acc = jnp.uint32((seed + _P5 + 8) & 0xFFFFFFFF)
    for lane in (first, second):
        acc = _rotl(acc + lane * jnp.uint32(_P3), 17) * jnp.uint32(_P4)
    acc = acc ^ (acc >> jnp.uint32(15))
    acc = acc * jnp.uint32(_P2)
    acc = acc ^ (acc >> jnp.uint32(13))
    acc = acc * jnp.uint32(_P3)
    return acc ^ (acc >> jnp.uint32(16))


def key_bits(keys, m_bits: int, k: int, block_bits: int):
    """For (n, 2) uint32 keys: the block of each key ((n,) int32), and for
    each of its k bits the word within the block (an int) and the bit
    within that word ((n,) uint32). Every array is one value per key, so
    none pads a short minor dimension on the chip."""
    keys = jnp.asarray(keys, jnp.uint32)
    hi, lo = keys[:, 0], keys[:, 1]
    h1 = xxh32_two_words(lo, hi, SEED_PATTERN)
    h2 = xxh32_two_words(hi, lo, SEED_BLOCK)
    n_blocks = m_bits // block_bits
    blk = (h2 & jnp.uint32(n_blocks - 1)).astype(jnp.int32)
    s = block_bits // WORD_BITS
    bits = [(i % s, (h1 * jnp.uint32(int(_SALTS[i]))) >> jnp.uint32(27))
            for i in range(k)]
    return blk, bits


def _blocks(keys, m_bits, k, block_bits, member):
    blk, bits = key_bits(keys, m_bits, k, block_bits)
    if member is not None:
        blk = blk + jnp.asarray(member, jnp.int32) * (m_bits // block_bits)
    return blk, bits


def _present(words, blk, bits, s: int):
    ok = None
    for word, bit in bits:
        set_ = ((words[blk * s + word] >> bit) & 1) == 1
        ok = set_ if ok is None else ok & set_
    return ok


def contains(words, keys, m_bits: int, k: int, block_bits: int,
             member=None):
    """(n,) bool: is every bit of each key set in ``words``. ``words`` is
    the flat filter, or a bank flattened member after member, with
    ``member`` (n,) the member each key asks."""
    blk, bits = _blocks(keys, m_bits, k, block_bits, member)
    return _present(words.reshape(-1), blk, bits, block_bits // WORD_BITS)


def count_missing(words, keys, m_bits: int, k: int, block_bits: int,
                  first_block, n_blocks: int, valid=None):
    """How many of ``keys`` whose block lies in [first_block, first_block +
    n_blocks) lack one of their bits in ``words``, the words of that range.
    Keys with ``valid`` false are not counted."""
    blk, bits = key_bits(keys, m_bits, k, block_bits)
    local = blk - jnp.asarray(first_block, jnp.int32)
    inside = (local >= 0) & (local < n_blocks)
    if valid is not None:
        inside = inside & jnp.asarray(valid, bool)
    present = _present(words.reshape(-1), jnp.clip(local, 0, n_blocks - 1),
                       bits, block_bits // WORD_BITS)
    return jnp.sum((inside & ~present).astype(jnp.int32))


def build_range(keys, m_bits: int, k: int, block_bits: int, first_block,
                n_blocks: int, member=None, valid=None):
    """The words of blocks [first_block, first_block + n_blocks) of the
    filter that holds exactly ``keys``: ((n_blocks * s,) uint32). Keys
    outside the range, or with ``valid`` false, set nothing here.

    Each set bit is written into its own boolean plane (one per bit of a
    word, laid along the words), so duplicates only write True again and
    no OR needs a scatter combiner."""
    blk, bits = _blocks(keys, m_bits, k, block_bits, member)
    s = block_bits // WORD_BITS
    n_words = n_blocks * s
    if WORD_BITS * n_words >= 2**31:
        raise ValueError(f"a range of {n_words} words is too large for "
                         f"int32 plane indices; compare it in pieces")
    local = blk - jnp.asarray(first_block, jnp.int32)
    inside = (local >= 0) & (local < n_blocks)
    if valid is not None:
        inside = inside & jnp.asarray(valid, bool)
    planes = jnp.zeros((WORD_BITS * n_words,), bool)
    for word, bit in bits:
        flat = bit.astype(jnp.int32) * n_words + local * s + word
        flat = jnp.where(inside, flat, WORD_BITS * n_words)
        planes = planes.at[flat].set(True, mode="drop")
    planes = planes.reshape(WORD_BITS, n_words)
    out = jnp.zeros((n_words,), jnp.uint32)
    for b in range(WORD_BITS):
        out = out | (planes[b].astype(jnp.uint32) << jnp.uint32(b))
    return out


def build(keys, m_bits: int, k: int, block_bits: int, member=None,
          bank: int = 1):
    """The whole filter (or bank, flattened) holding exactly ``keys``."""
    return build_range(keys, m_bits, k, block_bits, 0,
                       bank * (m_bits // block_bits), member=member)


jit_contains = jax.jit(contains, static_argnums=(2, 3, 4))
jit_build_range = jax.jit(build_range, static_argnums=(1, 2, 3, 5))
jit_count_missing = jax.jit(count_missing, static_argnums=(2, 3, 4, 6))
