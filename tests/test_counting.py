"""Counting-filter validation: Pallas kernels (interpret mode) vs jnp oracle.

Acceptance sweep for the deletable-filter subsystem: bit-exact equality of
the counting kernels against ``core.variants.counting_*`` across both
residency regimes, a Θ layout grid, the partitioned-ownership path,
and the semantic invariants (exact add/remove inverse, sticky saturation,
decay aging, no false negatives while present).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import variants as V
from repro.core import hashing as H
from repro.kernels import ops
from repro.kernels.sbf import Layout

M = 1 << 14

CSPECS = [
    V.FilterSpec("countingbf", M, 8, block_bits=256),
    V.FilterSpec("countingbf", M, 16, block_bits=512),
    V.FilterSpec("countingbf", M, 4, block_bits=128),
    V.FilterSpec("countingbf", M, 2, block_bits=64),
]


def _keys(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


# ---------------------------------------------------------------------------
# Kernel == reference, both regimes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", CSPECS, ids=str)
@pytest.mark.parametrize("regime", ["vmem", "hbm"])
def test_counting_kernels_match_ref(spec, regime):
    keys = _keys(300, seed=spec.k)
    c0 = V.init(spec)
    ref_add = V.counting_add(spec, c0, keys)
    k_add = ops.counting_add(spec, c0, keys, regime=regime, tile=64)
    np.testing.assert_array_equal(np.asarray(k_add), np.asarray(ref_add))

    ref_q = V.counting_contains(spec, ref_add, keys)
    k_q = ops.counting_contains(spec, ref_add, keys, regime=regime, tile=64)
    np.testing.assert_array_equal(np.asarray(k_q), np.asarray(ref_q))
    assert np.asarray(k_q).all()          # no false negatives while present

    ref_rm = V.counting_remove(spec, ref_add, keys)
    k_rm = ops.counting_remove(spec, ref_add, keys, regime=regime, tile=64)
    np.testing.assert_array_equal(np.asarray(k_rm), np.asarray(ref_rm))
    np.testing.assert_array_equal(np.asarray(k_rm), np.asarray(c0))


@pytest.mark.parametrize("theta,spec_i", [(1, 0), (1, 1), (1, 2), (1, 3),
                                          (2, 0), (2, 2), (4, 1), (8, 0),
                                          (16, 3)])
def test_counting_layout_grid_exactness(theta, spec_i):
    """Every Θ point over the expanded 4s counter row computes identical
    results — layout only schedules, never changes semantics."""
    spec = CSPECS[spec_i]
    keys = _keys(257, seed=5)
    lay = Layout(theta)
    c0 = V.init(spec)
    ref_add = V.counting_add(spec, c0, keys)
    k_add = ops.counting_add(spec, c0, keys, layout=lay, tile=64)
    np.testing.assert_array_equal(np.asarray(k_add), np.asarray(ref_add))
    k_q = ops.counting_contains(spec, ref_add, keys, layout=lay, tile=64)
    np.testing.assert_array_equal(
        np.asarray(k_q), np.asarray(V.counting_contains(spec, ref_add, keys)))
    k_rm = ops.counting_remove(spec, ref_add, keys, layout=lay, tile=64)
    np.testing.assert_array_equal(np.asarray(k_rm), np.asarray(c0))


@pytest.mark.parametrize("n_segments", [2, 8, 16])
@pytest.mark.parametrize("op", ["add", "remove"])
def test_counting_partitioned_matches_ref(n_segments, op):
    """Ownership-partitioned PARALLEL updates == vectorized oracle, for
    increments AND decrements (the atomicAdd/atomicSub replacement)."""
    spec = CSPECS[0]
    keys = _keys(500, seed=7)
    base = V.counting_add(spec, V.init(spec), keys) if op == "remove" \
        else V.init(spec)
    ref_fn = V.counting_remove if op == "remove" else V.counting_add
    ref = ref_fn(spec, base, keys)
    got = ops.counting_update_partitioned(spec, base, np.asarray(keys),
                                          op=op, n_segments=n_segments)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_counting_decay_kernel_matches_ref():
    spec = CSPECS[1]
    keys = _keys(400, seed=9)
    c = V.counting_add(spec, V.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(ops.counting_decay(spec, c)),
                                  np.asarray(V.counting_decay(spec, c)))


def test_counting_loop_oracle_matches_vectorized():
    """The sequential per-key oracle (which mirrors kernel execution order)
    equals the order-independent vectorized formula — the property that
    makes the kernels verifiable against either."""
    spec = CSPECS[0]
    keys = _keys(200, seed=11)
    dup = jnp.concatenate([keys, keys[:50]])         # duplicates in-batch
    c0 = V.init(spec)
    np.testing.assert_array_equal(
        np.asarray(V.counting_update_loop(spec, c0, dup, None, "add")),
        np.asarray(V.counting_add(spec, c0, dup)))
    c = V.counting_add(spec, c0, dup)
    np.testing.assert_array_equal(
        np.asarray(V.counting_update_loop(spec, c, keys, None, "remove")),
        np.asarray(V.counting_remove(spec, c, keys)))


# ---------------------------------------------------------------------------
# Semantic invariants
# ---------------------------------------------------------------------------

def test_remove_is_exact_inverse_under_multiplicity():
    """add x2, remove x1 -> present; remove x2 -> exact empty state."""
    spec = CSPECS[0]
    keys = _keys(300, seed=13)
    c = ops.counting_add(spec, V.init(spec), keys, tile=64)
    c = ops.counting_add(spec, c, keys, tile=64)
    c = ops.counting_remove(spec, c, keys, tile=64)
    assert bool(np.asarray(ops.counting_contains(spec, c, keys)).all())
    c = ops.counting_remove(spec, c, keys, tile=64)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(V.init(spec)))


def test_saturation_is_sticky_and_safe():
    """A counter driven past 15 sticks there: later removes cannot create a
    false negative for other keys sharing it."""
    spec = CSPECS[0]
    k1 = _keys(1, seed=17)
    c = V.init(spec)
    for _ in range(20):
        c = ops.counting_add(spec, c, k1, tile=8)
    assert int(np.asarray(V.counting_count(spec, c, k1))[0]) == 15
    for _ in range(20):
        c = ops.counting_remove(spec, c, k1, tile=8)
    assert bool(np.asarray(ops.counting_contains(spec, c, k1)).all())


def test_decay_ages_out_single_inserts_but_not_refreshed():
    """One decay clears keys seen once; keys re-inserted after each decay
    survive — the time-decayed-membership contract."""
    spec = CSPECS[0]
    stale = _keys(100, seed=19)
    fresh = _keys(100, seed=23)
    c = V.init(spec)
    c = ops.counting_add(spec, c, stale, tile=64)
    c = ops.counting_add(spec, c, fresh, tile=64)
    for _ in range(3):
        c = ops.counting_decay(spec, c)
        c = ops.counting_add(spec, c, fresh, tile=64)    # refresh
    assert bool(np.asarray(ops.counting_contains(spec, c, fresh)).all())
    stale_hits = float(np.asarray(
        ops.counting_contains(spec, c, stale)).mean())
    assert stale_hits < 0.05, stale_hits                 # aged out (FPR-level)


def test_counting_fpr_tracks_bit_filter_theory():
    """Occupancy FPR of the counting filter == the SBF analytic model (the
    counters only add depth, not placement)."""
    spec = V.FilterSpec("countingbf", 1 << 17, 8, block_bits=256)
    n = spec.m_bits // 12
    c = V.counting_add(spec, V.init(spec), _keys(n, seed=29))
    probes = jnp.asarray(H.probe_u64x2(1 << 15, seed=31))
    fpr = float(np.asarray(V.counting_contains(spec, c, probes)).mean())
    th = V.fpr_theory(spec, n)
    assert 0.5 * th <= fpr <= 2.0 * th, (fpr, th)
