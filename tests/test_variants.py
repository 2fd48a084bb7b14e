"""Behavioural + property tests for the five Bloom filter variants.

These validate the paper's accuracy-side claims exactly (CPU-measurable):
no false negatives ever, measured FPR tracks Eq.(1)/blocked extensions,
variant FPR ordering (CBF best ... RBBF worst), Eq.(2)/(3) optima.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import variants as V
from repro.core import hashing as H

SPECS = [
    V.FilterSpec("cbf", 1 << 16, 8),
    V.FilterSpec("bbf", 1 << 16, 8, block_bits=256),
    V.FilterSpec("rbbf", 1 << 16, 4),
    V.FilterSpec("sbf", 1 << 16, 8, block_bits=256),
    V.FilterSpec("sbf", 1 << 16, 16, block_bits=512),
    V.FilterSpec("csbf", 1 << 16, 8, block_bits=512, z=2),
    V.FilterSpec("csbf", 1 << 16, 16, block_bits=1024, z=4),
]


@pytest.mark.parametrize("spec", SPECS, ids=str)
def test_no_false_negatives(spec):
    keys = jnp.asarray(H.random_u64x2(1500, seed=42))
    filt = V.add(spec, V.init(spec), keys)
    assert bool(V.contains(spec, filt, keys).all()), "Bloom filters must never miss"


@pytest.mark.parametrize("spec", SPECS, ids=str)
def test_add_loop_equals_add_scatter(spec):
    keys = jnp.asarray(H.random_u64x2(700, seed=9))
    f_loop = V.add_loop(spec, V.init(spec), keys)
    f_scat = V.add_scatter(spec, V.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(f_loop), np.asarray(f_scat))


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=2**64 - 1), min_size=1,
                max_size=200),
       st.sampled_from(range(len(SPECS))))
def test_property_inserted_keys_always_found(keys, spec_idx):
    """Hypothesis: arbitrary key multisets (incl. duplicates) are found."""
    spec = SPECS[spec_idx]
    packed = jnp.asarray(H.u64x2_from_u64(np.array(keys, dtype=np.uint64)))
    filt = V.add_scatter(spec, V.init(spec), packed)
    assert bool(V.contains(spec, filt, packed).all())


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=2**64 - 1), min_size=2,
                max_size=100))
def test_property_add_is_idempotent_and_commutative(keys):
    """OR-semantics: re-adding keys or permuting order gives identical words."""
    spec = V.FilterSpec("sbf", 1 << 14, 8, block_bits=256)
    packed = H.u64x2_from_u64(np.array(keys, dtype=np.uint64))
    f1 = V.add_scatter(spec, V.init(spec), jnp.asarray(packed))
    f2 = V.add_scatter(spec, f1, jnp.asarray(packed))          # idempotent
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    perm = np.random.RandomState(0).permutation(len(packed))
    f3 = V.add_scatter(spec, V.init(spec), jnp.asarray(packed[perm]))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f3))


def test_empty_filter_contains_nothing():
    for spec in SPECS:
        keys = jnp.asarray(H.random_u64x2(512, seed=3))
        assert not bool(V.contains(spec, V.init(spec), keys).any())


def test_monotonicity_superset_of_bits():
    """Adding more keys never turns a positive into a negative."""
    spec = V.FilterSpec("sbf", 1 << 14, 8, block_bits=256)
    k1 = jnp.asarray(H.random_u64x2(300, seed=1))
    k2 = jnp.asarray(H.random_u64x2(300, seed=2))
    f1 = V.add_scatter(spec, V.init(spec), k1)
    f2 = V.add_scatter(spec, f1, k2)
    before = np.asarray(V.contains(spec, f1, k1))
    after = np.asarray(V.contains(spec, f2, k1))
    assert (after >= before).all()


# ---------------------------------------------------------------------------
# Accuracy claims from the paper
# ---------------------------------------------------------------------------

def _measured_fpr(spec, n, probe=1 << 16):
    ins = jnp.asarray(H.random_u64x2(n, seed=5))
    filt = V.add_scatter(spec, V.init(spec), ins)
    probes = jnp.asarray(H.random_u64x2(probe, seed=1234))
    return float(np.asarray(V.contains(spec, filt, probes)).mean())


@pytest.mark.parametrize("variant,kw", [
    ("cbf", {}),
    ("bbf", {"block_bits": 256}),
    ("sbf", {"block_bits": 256}),
    ("csbf", {"block_bits": 512, "z": 2}),
])
def test_fpr_tracks_theory(variant, kw):
    """Measured FPR within [0.5x, 2x] of the analytic model at c=12."""
    m = 1 << 19
    spec = V.FilterSpec(variant, m, 8, **kw)
    n = m // 12
    fpr = _measured_fpr(spec, n)
    th = V.fpr_theory(spec, n)
    assert 0.5 * th <= fpr <= 2.0 * th, (fpr, th)


def test_sbf_fpr_matches_theory_at_two_million_keys():
    """At 2^21 keys the measured FPR of a 4 MiB sbf (16 bits/key) sits
    within 1.15x of the analytic model, as on the chip (chip_smoke.py)."""
    spec = V.FilterSpec("sbf", 1 << 25, 8)
    n = 1 << 21
    filt = V.add_rows(spec, V.init(spec), jnp.asarray(H.random_u64x2(n, 5)))
    probes = jnp.asarray(H.probe_u64x2(n, seed=5))
    fpr = float(np.asarray(V.contains_rows(spec, filt, probes)).mean())
    th = V.fpr_sbf(spec.block_bits, 32, spec.m_bits / n, spec.k)
    assert th / 1.15 <= fpr <= 1.15 * th, (fpr, th)


def test_fpr_ordering_cbf_best_rbbf_worst():
    """Paper Fig. 4 x-axis ordering at iso space & k."""
    m, k, n = 1 << 19, 8, (1 << 19) // 12
    f_cbf = _measured_fpr(V.FilterSpec("cbf", m, k), n)
    f_sbf = _measured_fpr(V.FilterSpec("sbf", m, k, block_bits=256), n)
    f_rbbf = _measured_fpr(V.FilterSpec("rbbf", m, k), n)
    assert f_cbf < f_sbf < f_rbbf


def test_fpr_improves_with_block_size():
    """Larger B -> lower FPR (the accuracy side of the paper's trade-off).

    Respects the paper's SBF constraint k >= s: with k=16 the largest valid
    block is 512 bits (s=16 words) at our S=32 word size.
    """
    m, k, n = 1 << 19, 16, (1 << 19) // 12
    fprs = [_measured_fpr(V.FilterSpec("sbf", m, k, block_bits=b), n)
            for b in (64, 256, 512)]
    assert fprs[0] > fprs[-1]


def test_sbf_k_below_s_is_degenerate():
    """Documents the paper's k >= s constraint: k < s wastes words -> FPR blows up.

    This is exactly the motivation the paper gives for the CSBF (§2.1.5)."""
    m, n = 1 << 19, (1 << 19) // 12
    f_bad = _measured_fpr(V.FilterSpec("sbf", m, 8, block_bits=1024), n)   # s=32 > k
    f_csbf = _measured_fpr(V.FilterSpec("csbf", m, 8, block_bits=1024, z=2), n)
    assert f_csbf < f_bad  # CSBF fixes the degenerate regime


def test_csbf_z_tradeoff():
    """Smaller z -> fewer words touched but higher FPR (paper §5.2)."""
    m, k, n = 1 << 19, 8, (1 << 19) // 12
    f_z2 = _measured_fpr(V.FilterSpec("csbf", m, k, block_bits=1024, z=2), n)
    f_z8 = _measured_fpr(V.FilterSpec("csbf", m, k, block_bits=1024, z=8), n)
    assert f_z8 < f_z2


def test_eq2_eq3_formulas():
    assert V.optimal_k(10) == pytest.approx(10 * np.log(2))
    assert V.fpr_min(10) == pytest.approx(0.5 ** (10 * np.log(2)))
    # k* minimizes Eq.(1) over integer k
    m, n = 1 << 16, (1 << 16) // 10
    ks = range(1, 20)
    best = min(ks, key=lambda k: V.fpr_cbf(m, n, k))
    assert abs(best - V.optimal_k(10)) <= 1.0


def test_fill_fraction_matches_expectation():
    spec = V.FilterSpec("cbf", 1 << 16, 8)
    n = 1000
    filt = V.add_scatter(spec, V.init(spec), jnp.asarray(H.random_u64x2(n, seed=0)))
    expected = 1 - np.exp(-spec.k * n / spec.m_bits)
    assert abs(V.fill_fraction(filt) - expected) < 0.02


# ---------------------------------------------------------------------------
# Spec validation + API sizing
# ---------------------------------------------------------------------------

def test_spec_validation():
    with pytest.raises(AssertionError):
        V.FilterSpec("sbf", (1 << 16) + 1, 8)       # m not pow2
    with pytest.raises(AssertionError):
        V.FilterSpec("csbf", 1 << 16, 7, block_bits=512, z=2)  # k % z != 0
    with pytest.raises(AssertionError):
        V.FilterSpec("csbf", 1 << 16, 8, block_bits=512, z=5)  # z !| s
    with pytest.raises(AssertionError):
        V.FilterSpec("nope", 1 << 16, 8)            # unknown variant


def test_for_n_items_sizing():
    from repro import api
    f = api.filter_for_n_items(10_000, bits_per_key=16, variant="sbf",
                               backend="jnp")
    assert f.spec.m_bits >= 10_000 * 16
    f = f.add(H.random_u64x2(10_000, seed=8))
    assert f.measure_fpr() < 0.01  # c=16 should be well under 1%


def test_filter_accepts_uint64_numpy():
    from repro import api
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp")
    keys = np.array([1, 2, 3], dtype=np.uint64)
    f = f.add(keys)
    assert bool(np.asarray(f.contains(keys)).all())
