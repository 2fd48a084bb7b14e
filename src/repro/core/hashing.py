"""Key hashing + branchless multiplicative pattern generation (paper §4.2).

TPU adaptation notes
--------------------
The paper hashes 64-bit keys with xxHash64 and derives fingerprint bits by
multiplying the base hash with compile-time-inlined odd constants ("salts").
The TPU VPU is a 32-bit machine (no native u64), so:

* keys are carried in ``u64x2`` format — an array of shape ``(..., 2)`` of
  ``uint32`` holding ``[hi, lo]`` words of the conceptual 64-bit key;
* the base hash is an *exact* xxHash32 of the 8-byte little-endian key
  (the specialization of xxHash32 for inputs < 16 bytes), evaluated twice
  with independent seeds to recover 64 bits of fingerprint entropy
  (one stream selects the block, the other generates bit patterns);
* fingerprint bits use multiplicative (mul-shift) hashing
  [Dietzfelbinger et al. 1997], i.e. ``bit = (h * salt) >> (32 - log2(S))``.

Salts live in a module-level table and are indexed with *Python* integers at
trace time, so XLA sees them as literal constants folded into the kernel —
the exact analogue of the paper's C++ template-metaprogramming trick that
inlines multipliers into the generated SASS.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# xxHash32 constants
# ---------------------------------------------------------------------------
_P1 = np.uint32(2654435761)
_P2 = np.uint32(2246822519)
_P3 = np.uint32(3266489917)
_P4 = np.uint32(668265263)
_P5 = np.uint32(374761393)

# Independent hash streams (seeds) for block selection vs. pattern generation.
SEED_PATTERN = np.uint32(0xCAFEBABE)
SEED_BLOCK = np.uint32(0xDEADBEEF)
SEED_AUX = np.uint32(0x9E3779B9)

# Version of the key -> bit layout that every engine shares. A filter's words
# answer only for keys hashed under the layout that wrote them, so saved
# states carry it and a state from another layout is refused on restore.
#   1: block stream xxh32(keys, SEED_BLOCK)
#   2: block stream ``block_hash`` (lanes swapped, independent of the
#      pattern stream)
HASH_LAYOUT = 2

# ---------------------------------------------------------------------------
# Salt table — odd 32-bit multiplicative constants, fixed at import time.
# ---------------------------------------------------------------------------
MAX_SALTS = 96


def _make_salts(n: int, seed: int = 0xB100F) -> np.ndarray:
    rng = np.random.RandomState(seed)
    salts = rng.randint(0, 2**31, size=n, dtype=np.int64).astype(np.uint64)
    salts = (salts * 2 + 1).astype(np.uint32)  # force odd
    # make sure high bits are well mixed: xor-fold a second stream
    salts ^= rng.randint(0, 2**31, size=n, dtype=np.int64).astype(np.uint32) << np.uint32(1)
    return salts | np.uint32(1)


SALTS = _make_salts(MAX_SALTS)                      # fingerprint bit salts
WORD_SALTS = _make_salts(MAX_SALTS, seed=0x5EC70)   # BBF word-selection salts
GROUP_SALTS = _make_salts(MAX_SALTS, seed=0x6709)   # CSBF group->word salts


def _u32(x) -> jnp.ndarray:
    return jnp.asarray(x, dtype=jnp.uint32)


def rotl32(x: jnp.ndarray, r: int) -> jnp.ndarray:
    """Rotate-left on uint32 (r is a Python int — static)."""
    r = int(r) % 32
    if r == 0:
        return x
    x = _u32(x)
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def xxh32_u64x2(keys: jnp.ndarray, seed=SEED_PATTERN) -> jnp.ndarray:
    """Exact xxHash32 of an 8-byte (u64) key held as uint32 ``[hi, lo]`` pairs.

    ``keys``: (..., 2) uint32. Returns (...,) uint32.

    This is the xxHash32 algorithm specialized for len==8: the accumulator
    starts at ``seed + PRIME5 + len`` and consumes the two 4-byte lanes of
    the little-endian u64 (lo word first), followed by the final avalanche.
    """
    keys = _u32(keys)
    hi = keys[..., 0]
    lo = keys[..., 1]
    acc = _u32(seed) + _P5 + np.uint32(8)
    for lane in (lo, hi):  # little-endian order: low word first
        acc = acc + lane * _P3
        acc = rotl32(acc, 17) * _P4
    # avalanche
    acc = acc ^ (acc >> np.uint32(15))
    acc = acc * _P2
    acc = acc ^ (acc >> np.uint32(13))
    acc = acc * _P3
    acc = acc ^ (acc >> np.uint32(16))
    return acc


def block_hash(keys: jnp.ndarray) -> jnp.ndarray:
    """The block stream of (..., 2) u64x2 keys: xxHash32 under SEED_BLOCK
    of the key's lanes in swapped order (high word first).

    The swap is what makes the stream independent of the pattern stream.
    xxHash32 of an 8-byte input is close to linear in its seed, so two keys
    whose pattern hashes collide would, under a second seed alone, mostly
    collide again: they land in the same block with the same pattern, and
    one is a false positive for the other. That adds about n / 2^32 to the
    false-positive rate of a filter holding n keys (+0.05% at 2^21 keys,
    +1.5% at 2^26), which the analytic FPR never counts."""
    return xxh32_u64x2(_u32(keys)[..., ::-1], SEED_BLOCK)


def xxh32_u64x2_pair(keys: jnp.ndarray):
    """Fused dual-seed xxHash32 — both hash streams from ONE wide mix.

    Returns ``(xxh32_u64x2(keys, SEED_PATTERN), block_hash(keys))``
    bit-for-bit, but computes the seed-independent lane products
    ``lane * PRIME3`` once and feeds them to both accumulators. The seed
    only enters xxHash32 through the accumulator initial value, so the
    per-lane multiplies (the expensive u32 ops on a 32-bit VPU) are shared:
    2 of the 8 multiplies drop out relative to two independent evaluations.
    This is the ``mix="cheap"`` engine option (paper §4.2's fused
    multi-hash): identical uint32 arithmetic, merely restructured, which is
    what keeps every kernel built on it bit-exact with ``mix="full"``.
    """
    keys = _u32(keys)
    hi = keys[..., 0]
    lo = keys[..., 1]
    plo = lo * _P3                       # seed-independent lane products,
    phi = hi * _P3                       # computed once for both streams
    outs = []
    # the pattern stream reads the low word first, the block stream the high
    for seed, lanes in ((SEED_PATTERN, (plo, phi)), (SEED_BLOCK, (phi, plo))):
        acc = _u32(seed) + _P5 + np.uint32(8)
        for lanep in lanes:
            acc = rotl32(acc + lanep, 17) * _P4
        acc = acc ^ (acc >> np.uint32(15))
        acc = acc * _P2
        acc = acc ^ (acc >> np.uint32(13))
        acc = acc * _P3
        acc = acc ^ (acc >> np.uint32(16))
        outs.append(acc)
    return outs[0], outs[1]


def xxh32_u32(keys: jnp.ndarray, seed=SEED_PATTERN) -> jnp.ndarray:
    """Exact xxHash32 of a 4-byte key (single uint32 lane)."""
    keys = _u32(keys)
    acc = _u32(seed) + _P5 + np.uint32(4)
    acc = acc + keys * _P3
    acc = rotl32(acc, 17) * _P4
    acc = acc ^ (acc >> np.uint32(15))
    acc = acc * _P2
    acc = acc ^ (acc >> np.uint32(13))
    acc = acc * _P3
    acc = acc ^ (acc >> np.uint32(16))
    return acc


def mulshift(h: jnp.ndarray, salt: np.uint32, bits: int) -> jnp.ndarray:
    """Multiplicative hash: top ``bits`` bits of ``h * salt`` (universal family).

    ``salt`` and ``bits`` are Python-level constants — folded into the
    generated code at trace time (the paper's salt-inlining analogue).
    """
    if bits == 0:
        return jnp.zeros_like(_u32(h))
    return (_u32(h) * np.uint32(salt)) >> np.uint32(32 - bits)


def block_index(h_block: jnp.ndarray, n_blocks: int) -> jnp.ndarray:
    """Map the block-stream hash to ``[0, n_blocks)``; n_blocks must be pow2."""
    assert n_blocks & (n_blocks - 1) == 0, "n_blocks must be a power of two"
    return _u32(h_block) & np.uint32(n_blocks - 1)


def hash_keys(keys: jnp.ndarray):
    """Return the (pattern, block) hash-stream pair for u64x2 or u32 keys."""
    if keys.ndim >= 1 and keys.shape[-1] == 2 and keys.dtype == jnp.uint32:
        return xxh32_u64x2(keys, SEED_PATTERN), block_hash(keys)
    return (xxh32_u32(keys, SEED_PATTERN), xxh32_u32(keys, SEED_BLOCK))


def mix_rows(mat: jnp.ndarray) -> jnp.ndarray:
    """Hash rows of uint32 tokens to u64x2 keys, fully on device.

    ``mat``: (..., w) uint32. Returns (..., 2) uint32. The column loop is
    a *trace-time* Python loop over the (small, static) row width — FNV/
    Fibonacci-style mixing fuses into a handful of whole-batch vector ops,
    so callers like the n-gram guard hash an entire decode batch per step
    with zero host-side per-row work."""
    mat = jnp.asarray(mat, jnp.uint32)
    h1 = jnp.full(mat.shape[:-1], 0x811C9DC5, jnp.uint32)
    h2 = jnp.full(mat.shape[:-1], 0x9E3779B9, jnp.uint32)
    for j in range(mat.shape[-1]):        # static unroll over columns
        c = mat[..., j]
        h1 = (h1 ^ c) * jnp.uint32(16777619)
        h2 = (h2 + c) * jnp.uint32(2246822519)
        h2 = h2 ^ (h2 >> jnp.uint32(13))
    h1 = h1 ^ (h1 >> jnp.uint32(16))
    return jnp.stack([h1, h2], axis=-1)


# ---------------------------------------------------------------------------
# Host-side reference (numpy, used by tests to cross-check the jnp path)
# ---------------------------------------------------------------------------

def xxh32_u64_numpy(keys_u64: np.ndarray, seed: int = int(SEED_PATTERN)) -> np.ndarray:
    keys_u64 = keys_u64.astype(np.uint64)
    lo = (keys_u64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (keys_u64 >> np.uint64(32)).astype(np.uint32)
    with np.errstate(over="ignore"):
        acc = np.uint32(seed) + _P5 + np.uint32(8)
        for lane in (lo, hi):
            acc = acc + lane * _P3
            acc = ((acc << np.uint32(17)) | (acc >> np.uint32(15))) * _P4
        acc = acc ^ (acc >> np.uint32(15))
        acc = acc * _P2
        acc = acc ^ (acc >> np.uint32(13))
        acc = acc * _P3
        acc = acc ^ (acc >> np.uint32(16))
    return acc


def u64x2_from_u64(keys_u64: np.ndarray) -> np.ndarray:
    """Host helper: pack np.uint64 keys into (n, 2) uint32 [hi, lo]."""
    keys_u64 = np.asarray(keys_u64, dtype=np.uint64)
    hi = (keys_u64 >> np.uint64(32)).astype(np.uint32)
    lo = (keys_u64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return np.stack([hi, lo], axis=-1)


def random_u64x2(n: int, seed: int = 0) -> np.ndarray:
    """Host helper: n distinct-ish random u64 keys in u64x2 format.

    Keys are drawn from the *insert* keyspace — the top bit of the u64 is
    always clear. The complementary range (top bit set) is reserved for
    ``probe_u64x2``, so FPR probes are structurally disjoint from any key
    set generated here (see ``Filter.measure_fpr``).
    """
    rng = np.random.RandomState(seed)
    lo = rng.randint(0, 2**32, size=n, dtype=np.uint64)
    hi = rng.randint(0, 2**31, size=n, dtype=np.uint64)  # top bit reserved
    return u64x2_from_u64((hi << np.uint64(32)) | lo)


def probe_u64x2(n: int, seed: int = 0) -> np.ndarray:
    """n random u64 probe keys from the reserved range (top bit set).

    Disjoint by construction from every ``random_u64x2`` draw — the
    right source for empirical FPR measurements, where a probe that
    collides with an inserted key would misreport a true positive as a
    false one."""
    rng = np.random.RandomState(seed ^ 0x5EED)
    lo = rng.randint(0, 2**32, size=n, dtype=np.uint64)
    hi = rng.randint(0, 2**31, size=n, dtype=np.uint64) | np.uint64(1 << 31)
    return u64x2_from_u64((hi << np.uint64(32)) | lo)
