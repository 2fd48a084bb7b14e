"""Per-kernel validation sweeps: Pallas (interpret mode) vs pure-jnp oracle.

Sweeps shapes (key counts incl. non-tile-multiples), block sizes, variants,
Θ layouts, residency regimes and tile sizes; asserts exact integer /
boolean equality against repro.kernels.ref.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import variants as V
from repro.core import hashing as H
from repro.core import partition as P
from repro.kernels import ops, ref
from repro.kernels.sbf import Layout, default_layout

M = 1 << 16


def _keys(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


BLOCKED_SPECS = [
    V.FilterSpec("sbf", M, 8, block_bits=256),
    V.FilterSpec("sbf", M, 16, block_bits=512),
    V.FilterSpec("sbf", M, 4, block_bits=128),
    V.FilterSpec("sbf", M, 2, block_bits=64),
    V.FilterSpec("rbbf", M, 4),
    V.FilterSpec("bbf", M, 8, block_bits=256),
    V.FilterSpec("csbf", M, 8, block_bits=512, z=2),
    V.FilterSpec("csbf", M, 16, block_bits=1024, z=4),
]


@pytest.mark.parametrize("spec", BLOCKED_SPECS, ids=str)
@pytest.mark.parametrize("n", [64, 1000, 2048])
def test_kernel_add_contains_matches_ref(spec, n):
    keys = _keys(n, seed=n)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_ker = ops.bloom_add(spec, V.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))
    c_ref = np.asarray(ref.bloom_contains_ref(spec, f_ref, keys))
    c_ker = np.asarray(ops.bloom_contains(spec, f_ref, keys))
    np.testing.assert_array_equal(c_ker, c_ref)
    assert c_ker.all()  # no false negatives through the kernel path


@pytest.mark.parametrize("theta,block_bits", [(1, 128), (1, 256), (1, 512),
                                              (2, 256), (2, 512), (4, 256),
                                              (8, 128), (8, 256), (16, 512)])
def test_layout_grid_exactness(theta, block_bits):
    """Every Θ point computes identical results — layout only affects the
    schedule, never the semantics (paper §4.1 invariant)."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=block_bits)
    keys = _keys(777, seed=3)
    lay = Layout(theta)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_ker = ops.bloom_add(spec, V.init(spec), keys, layout=lay)
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))
    c_ker = np.asarray(ops.bloom_contains(spec, f_ref, keys, layout=lay))
    c_ref = np.asarray(ref.bloom_contains_ref(spec, f_ref, keys))
    np.testing.assert_array_equal(c_ker, c_ref)


@pytest.mark.parametrize("spec", BLOCKED_SPECS[:4], ids=str)
def test_hbm_regime_matches_ref(spec):
    """DMA-streaming kernels (filter in HBM) == oracle."""
    keys = _keys(512, seed=11)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_hbm = ops.bloom_add(spec, V.init(spec), keys, regime="hbm")
    np.testing.assert_array_equal(np.asarray(f_hbm), np.asarray(f_ref))
    c_hbm = np.asarray(ops.bloom_contains(spec, f_ref, keys, regime="hbm"))
    np.testing.assert_array_equal(
        c_hbm, np.asarray(ref.bloom_contains_ref(spec, f_ref, keys)))


@pytest.mark.parametrize("tile", [8, 64, 512])
def test_tile_size_invariance(tile):
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(600, seed=5)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_ker = ops.bloom_add(spec, V.init(spec), keys, tile=tile)
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))


def test_cbf_kernels_match_ref():
    spec = V.FilterSpec("cbf", M, 8)
    keys = _keys(1024, seed=2)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_ker = ops.bloom_add(spec, V.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))
    c = np.asarray(ops.bloom_contains(spec, f_ref, keys))
    np.testing.assert_array_equal(
        c, np.asarray(ref.bloom_contains_ref(spec, f_ref, keys)))


@pytest.mark.parametrize("n_segments", [2, 8, 16])
def test_partitioned_add_matches_ref(n_segments):
    """Ownership-partitioned PARALLEL-grid add == sequential oracle."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(1500, seed=7)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_par = ops.bloom_add_partitioned(spec, V.init(spec), np.asarray(keys),
                                      n_segments=n_segments)
    np.testing.assert_array_equal(np.asarray(f_par), np.asarray(f_ref))


def test_partition_host_covers_all_keys():
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = np.asarray(_keys(999, seed=13))
    by_seg, valid, counts = P.partition_host(spec, keys, 8)
    assert counts.sum() == 999
    assert valid.sum() == 999
    # every valid key belongs to its segment
    for sidx in range(8):
        ks = by_seg[sidx][valid[sidx].astype(bool)]
        if len(ks):
            seg = np.asarray(P.segment_ids(spec, jnp.asarray(ks), 8))
            assert (seg == sidx).all()


def test_partition_jit_matches_host():
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(512, seed=17)
    part = P.partition_jit(spec, keys, 8, capacity=256)
    by_seg_j, valid_j = part.keys_by_seg, part.valid
    assert int(part.overflow) == 0 and bool(np.asarray(part.keep).all())
    by_seg_h, valid_h, _ = P.partition_host(spec, np.asarray(keys), 8)
    # same multiset of keys per segment (order may differ)
    for sidx in range(8):
        a = {tuple(x) for x in np.asarray(by_seg_j[sidx])[np.asarray(valid_j[sidx], bool)]}
        b = {tuple(x) for x in by_seg_h[sidx][valid_h[sidx].astype(bool)]}
        assert a == b


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=1, max_value=300), st.integers(min_value=0, max_value=99))
def test_property_kernel_equals_ref_random_sizes(n, seed):
    """Hypothesis sweep over key counts (padding edge cases) and seeds."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(n, seed=seed)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_ker = ops.bloom_add(spec, V.init(spec), keys, tile=64)
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))
    c = np.asarray(ops.bloom_contains(spec, f_ref, keys, tile=64))
    np.testing.assert_array_equal(
        c, np.asarray(ref.bloom_contains_ref(spec, f_ref, keys)))


def test_empty_keys_noop():
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    f = V.init(spec)
    out = ops.bloom_add(spec, f, jnp.zeros((0, 2), jnp.uint32))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(f))
    c = ops.bloom_contains(spec, f, jnp.zeros((0, 2), jnp.uint32))
    assert c.shape == (0,)


def test_api_pallas_backend_roundtrip():
    from repro import api
    f = api.make_filter("sbf", m_bits=1 << 16, k=8, block_bits=256,
                        backend="pallas")   # legacy alias -> a pallas engine
    keys = H.random_u64x2(500, seed=21)
    f = f.add(keys)
    assert bool(np.asarray(f.contains(keys)).all())
    # pallas path == jnp path
    f2 = api.make_filter("sbf", m_bits=1 << 16, k=8, block_bits=256,
                         backend="jnp").add(keys)
    np.testing.assert_array_equal(np.asarray(f.words), np.asarray(f2.words))
