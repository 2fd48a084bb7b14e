"""PR-3 probe-engine contracts: whole-tile gather kernels, depth-tunable
HBM pipeline, device-resident partitioned add, cached-jit donation layer,
and the tile-aware tuning cache.

The parity sweeps pin the acceptance criterion "gather-probe kernels are
bit-identical to kernels/ref across variants x regimes x Θ x probe
strategy"; the jit/scan tests prove the partitioned bulk add never leaves
the device (no host numpy partition, no callbacks in the jaxpr).
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import hashing as H
from repro.core import partition as P
from repro.core import tuning
from repro.core import variants as V
from repro.kernels import ops, ref
from repro.kernels.sbf import Layout, default_layout

M = 1 << 16


def _keys(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


SWEEP_SPECS = [
    V.FilterSpec("sbf", M, 8, block_bits=256),
    V.FilterSpec("sbf", M, 16, block_bits=512),
    V.FilterSpec("bbf", M, 8, block_bits=256),
    V.FilterSpec("rbbf", M, 4),
    V.FilterSpec("csbf", M, 8, block_bits=512, z=2),
]


# ---------------------------------------------------------------------------
# Whole-tile gather parity (vmem regime)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", SWEEP_SPECS, ids=str)
@pytest.mark.parametrize("probe", ["loop", "gather"])
def test_gather_probe_matches_ref(spec, probe):
    keys = _keys(900, seed=5)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_ker = ops.bloom_add(spec, V.init(spec), keys, probe=probe)
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))
    c_ker = ops.bloom_contains(spec, f_ref, keys, probe=probe)
    np.testing.assert_array_equal(
        np.asarray(c_ker), np.asarray(ref.bloom_contains_ref(spec, f_ref, keys)))


@pytest.mark.parametrize("theta,block_bits", [(1, 256), (1, 512), (2, 256),
                                              (8, 128)])
@pytest.mark.parametrize("probe", ["loop", "gather"])
def test_gather_probe_layout_invariance(theta, block_bits, probe):
    """The gather engine ignores Θ — results must match the loop path
    under every layout (layouts affect schedule, never semantics)."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=block_bits)
    keys = _keys(513, seed=9)           # non-tile-multiple: padding on
    lay = Layout(theta)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_ker = ops.bloom_add(spec, V.init(spec), keys, layout=lay, probe=probe)
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))
    c = ops.bloom_contains(spec, f_ref, keys, layout=lay, probe=probe)
    np.testing.assert_array_equal(
        np.asarray(c), np.asarray(ref.bloom_contains_ref(spec, f_ref, keys)))


@pytest.mark.parametrize("probe", ["loop", "gather"])
def test_counting_gather_matches_reference(probe):
    spec = V.FilterSpec("countingbf", M, 8, block_bits=256)
    keys = _keys(700, seed=21)
    dups = jnp.concatenate([keys, keys[:350]])      # non-idempotent updates
    f_ref = V.counting_add(spec, V.init(spec), dups)
    f_ker = ops.counting_add(spec, V.init(spec), dups, probe=probe)
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))
    r_ref = V.counting_remove(spec, f_ref, keys[:200])
    r_ker = ops.counting_remove(spec, f_ker, keys[:200], probe=probe)
    np.testing.assert_array_equal(np.asarray(r_ker), np.asarray(r_ref))
    c_ker = ops.counting_contains(spec, f_ref, keys, probe=probe)
    np.testing.assert_array_equal(
        np.asarray(c_ker), np.asarray(V.counting_contains(spec, f_ref, keys)))


# ---------------------------------------------------------------------------
# HBM regime: depth-tunable contains, coalesced add
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 2, 4, 8])
def test_hbm_contains_depth_sweep(depth):
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(512, seed=31)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    c = ops.bloom_contains(spec, f_ref, keys, regime="hbm", depth=depth)
    np.testing.assert_array_equal(
        np.asarray(c), np.asarray(ref.bloom_contains_ref(spec, f_ref, keys)))


def test_hbm_coalesced_add_duplicate_blocks():
    """The block-sorted HBM add must OR same-block keys into ONE RMW —
    adversarial input: every key hashes into a tiny block range."""
    spec = V.FilterSpec("sbf", 1 << 12, 8, block_bits=256)   # 16 blocks
    keys = _keys(256, seed=3)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_ker = ops.bloom_add(spec, V.init(spec), keys, regime="hbm")
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))


@pytest.mark.parametrize("depth", [2, 8])
def test_counting_hbm_depth(depth):
    spec = V.FilterSpec("countingbf", M, 8, block_bits=256)
    keys = _keys(300, seed=13)
    f_ref = V.counting_add(spec, V.init(spec), keys)
    f_ker = ops.counting_add(spec, V.init(spec), keys, regime="hbm")
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))
    c = ops.counting_contains(spec, f_ref, keys, regime="hbm", depth=depth)
    np.testing.assert_array_equal(
        np.asarray(c), np.asarray(V.counting_contains(spec, f_ref, keys)))


# ---------------------------------------------------------------------------
# Device-resident partitioned add: jit / scan, overflow, no host sync
# ---------------------------------------------------------------------------

def test_partition_jit_reports_overflow():
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(512, seed=41)
    part = P.partition_jit(spec, keys, 8, capacity=8)   # far too small
    n_kept = int(np.asarray(part.keep).sum())
    assert int(part.overflow) == 512 - n_kept > 0
    assert int(np.asarray(part.valid).sum()) == n_kept


def test_partitioned_add_escalates_capacity_concrete():
    """Concrete keys + undersized capacity: dispatch doubles capacity until
    nothing overflows — bit-exact, no silent key loss."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(1000, seed=43)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_par = ops.bloom_add_partitioned(spec, V.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(f_par), np.asarray(f_ref))


def test_partitioned_add_traced_residual_exact():
    """Under jit the capacity is static; overflowed keys must flow through
    the vectorized residual pass — still bit-exact."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(800, seed=47)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_par = jax.jit(
        lambda f, k: ops.bloom_add_partitioned(spec, f, k, capacity=8)
    )(V.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(f_par), np.asarray(f_ref))


def test_partitioned_add_jit_scan_no_host_partition(monkeypatch):
    """The acceptance criterion: Filter.add-style partitioned bulk add runs
    under jit + lax.scan with ZERO host transfers. partition_host is
    booby-trapped; any host sync raises."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)

    def boom(*a, **k):                                  # pragma: no cover
        raise AssertionError("host partition called on the jit path")

    monkeypatch.setattr(P, "partition_host", boom)

    keys = _keys(1024, seed=53)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)

    @jax.jit
    def bulk(f, chunks):
        def step(f, k):
            return ops.bloom_add_partitioned(spec, f, k, capacity=256), None
        f, _ = jax.lax.scan(step, f, chunks)
        return f

    f_out = bulk(V.init(spec), keys.reshape(4, 256, 2))
    np.testing.assert_array_equal(np.asarray(f_out), np.asarray(f_ref))


def test_partitioned_add_jaxpr_has_no_callbacks():
    """No pure_callback / io_callback / debug_callback primitives anywhere
    in the traced computation — it is device-resident by construction."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(256, seed=59)
    jaxpr = jax.make_jaxpr(
        lambda f, k: ops.bloom_add_partitioned(spec, f, k, capacity=128)
    )(V.init(spec), keys)
    assert "callback" not in str(jaxpr)


# ---------------------------------------------------------------------------
# Cached-jit dispatch layer (donation)
# ---------------------------------------------------------------------------

def test_bloom_add_jit_correct_and_cached():
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    ops.jit_cache_clear()
    keys1, keys2 = _keys(512, seed=61), _keys(512, seed=67)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys1)
    f_ref = ref.bloom_add_ref(spec, f_ref, keys2)
    f = ops.bloom_add_jit(spec, V.init(spec), keys1, donate=True)
    (n_exec,) = ops.jit_cache_info()
    f = ops.bloom_add_jit(spec, f, keys2, donate=True)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
    # the second same-shape call reused the compiled executable
    assert ops.jit_cache_info() == (n_exec,)
    hits = ops.bloom_contains_jit(spec, f, keys1)
    assert bool(np.asarray(hits).all())


def test_bloom_add_jit_donation_consumes_buffer():
    """donate=True aliases the output onto the input filter — no second
    filter-sized allocation. XLA only honors donation on TPU/GPU; on CPU it
    ignores the hint, so the deletion assert is platform-gated (the
    correctness + cache contract above runs everywhere)."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    filt = V.init(spec) | jnp.uint32(0)        # fresh, owned buffer
    keep = ops.bloom_add_jit(spec, filt, _keys(256, seed=71), donate=False)
    assert not filt.is_deleted()
    del keep
    if jax.default_backend() in ("tpu", "gpu"):
        ops.bloom_add_jit(spec, filt, _keys(256, seed=71), donate=True)
        assert filt.is_deleted()


def test_counting_update_jit_donation_path():
    spec = V.FilterSpec("countingbf", M, 8, block_bits=256)
    keys = _keys(300, seed=73)
    f_ref = V.counting_add(spec, V.init(spec), keys)
    f = ops.counting_update_jit(spec, V.init(spec), keys, "add", donate=True)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
    f2 = ops.counting_update_jit(spec, f, keys[:100], "remove", donate=True)
    np.testing.assert_array_equal(
        np.asarray(f2), np.asarray(V.counting_remove(spec, f_ref, keys[:100])))


# ---------------------------------------------------------------------------
# Tuning: tile-aware cache key, plan sweep, disk persistence
# ---------------------------------------------------------------------------

def test_tune_layout_tile_in_cache_key():
    """A layout tuned for tile=256 must not leak into tile=8 (where Θ > 8
    candidates are invalid): each tile re-runs validation."""
    spec = V.FilterSpec("sbf", M, 16, block_bits=512)
    lay256, _ = tuning.tune_layout(spec, "contains", tile=256)
    lay8, _ = tuning.tune_layout(spec, "contains", tile=8)
    assert 256 % lay256.theta == 0
    assert 8 % lay8.theta == 0          # would fail if the 256 entry leaked
    lay8.validate(spec, 8)


def test_tune_plan_axes_and_disk_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "tuning.json"))
    tuning.tune_plan.cache_clear()
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    plan = tuning.tune_plan(spec, "contains", regime="vmem", tile=256)
    assert plan.probe in ("loop", "gather")
    assert plan.depth in tuning.TUNABLE_DEPTHS
    assert plan.n_segments in tuning.TUNABLE_SEGMENTS
    plan.layout.validate(spec, 256)
    assert os.path.exists(str(tmp_path / "tuning.json"))
    # a fresh in-process cache must round-trip through the disk entry
    tuning.tune_plan.cache_clear()
    again = tuning.tune_plan(spec, "contains", regime="vmem", tile=256)
    assert again == plan


def test_auto_probe_dispatch_runs():
    """probe="auto" resolves through tune_plan inside dispatch (trace-time
    static) and still matches the reference."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(400, seed=83)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f = ops.bloom_add(spec, V.init(spec), keys, probe="auto")
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
    c = ops.bloom_contains(spec, f, keys, probe="auto")
    assert bool(np.asarray(c).all())


def test_api_options_thread_probe_and_depth():
    """BackendOptions.probe/depth reach the kernels through the Filter API."""
    from repro import api
    f = api.make_filter("sbf", m_bits=M, k=8, backend="pallas-vmem",
                        probe="gather")
    keys = _keys(300, seed=89)
    f = f.add(keys)
    assert bool(np.asarray(f.contains(keys)).all())
    g = api.make_filter("sbf", m_bits=M, k=8, backend="pallas-hbm", depth=4)
    g = g.add(keys)
    assert bool(np.asarray(g.contains(keys)).all())
