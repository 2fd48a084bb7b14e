"""PR-9 cooperative probe engine contracts.

The cooperation axes (``coop="subtile"`` lane-group probing, ``mix="cheap"``
fused double-hash) are SCHEDULE options: every cooperative/fused path must
be bit-exact with the baseline kernels across filter families x regimes,
stay single-launch, thread from ``make_filter`` through ``BackendOptions``
to the kernels, and be selected by the autotuner exactly when the
calibrated performance model predicts a win.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.core import fingerprint as F
from repro.core import hashing as H
from repro.core import quotient as Q
from repro.core import tuning
from repro.core import variants as V
from repro.kernels import ops, ref
from repro.kernels import sbf as sbf_k
from repro.kernels.sbf import default_layout
from repro.perfmodel.calibrate import Calibration

M = 1 << 16


def _keys(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


def _n_pallas(jaxpr):
    return sum(1 for e in jaxpr.jaxpr.eqns if "pallas" in e.primitive.name)


COOP_SPECS = [
    V.FilterSpec("sbf", M, 8, block_bits=256),
    V.FilterSpec("sbf", M, 16, block_bits=512),
    V.FilterSpec("bbf", M, 8, block_bits=256),
    V.FilterSpec("csbf", M, 8, block_bits=512, z=2),
]


# ---------------------------------------------------------------------------
# Bit-exact parity: Bloom families x regimes x coop x mix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", COOP_SPECS, ids=str)
@pytest.mark.parametrize("regime", ["vmem", "hbm"])
@pytest.mark.parametrize("mix", ["full", "cheap"])
def test_bloom_coop_parity(spec, regime, mix):
    keys = _keys(700, seed=3)
    absent = _keys(300, seed=4)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_coop = ops.bloom_add(spec, V.init(spec), keys, regime=regime,
                           coop="subtile", mix=mix)
    np.testing.assert_array_equal(np.asarray(f_coop), np.asarray(f_ref))
    for probe_keys in (keys, jnp.concatenate([keys[:100], absent])):
        want = ref.bloom_contains_ref(spec, f_ref, probe_keys)
        got = ops.bloom_contains(spec, f_ref, probe_keys, regime=regime,
                                 coop="subtile", mix=mix)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("regime", ["vmem", "hbm"])
@pytest.mark.parametrize("mix", ["full", "cheap"])
def test_counting_coop_parity(regime, mix):
    spec = V.FilterSpec("countingbf", M, 8, block_bits=256)
    keys = _keys(500, seed=7)
    dups = jnp.concatenate([keys, keys[:250]])     # non-idempotent updates
    f_ref = V.counting_add(spec, V.init(spec), dups)
    f_coop = ops.counting_add(spec, V.init(spec), dups, regime=regime,
                              coop="subtile", mix=mix)
    np.testing.assert_array_equal(np.asarray(f_coop), np.asarray(f_ref))
    r_ref = V.counting_remove(spec, f_ref, keys[:150])
    r_coop = ops.counting_remove(spec, f_ref, keys[:150], regime=regime,
                                 coop="subtile", mix=mix)
    np.testing.assert_array_equal(np.asarray(r_coop), np.asarray(r_ref))
    probe = jnp.concatenate([keys, _keys(200, seed=8)])
    want = V.counting_contains(spec, r_ref, probe)
    got = ops.counting_contains(spec, r_ref, probe, regime=regime,
                                coop="subtile", mix=mix)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cheap_mix_alone_is_bit_exact():
    """mix="cheap" without cooperation: the fused hash must reproduce the
    two-stream hashes exactly on both probe strategies."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(513, seed=11)                     # padding in play
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    for probe in ("loop", "gather"):
        f = ops.bloom_add(spec, V.init(spec), keys, probe=probe,
                          coop="none", mix="cheap")
        np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
        got = ops.bloom_contains(spec, f_ref, keys, probe=probe,
                                 coop="none", mix="cheap")
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(ref.bloom_contains_ref(spec, f_ref, keys)))


def test_cuckoo_coop_parity():
    spec = V.FilterSpec("cuckoo", 1 << 14, 1, slot_bits=16)
    keys = _keys(400, seed=13)
    table, _ = F.cuckoo_add(spec, F.init(spec), keys)
    probe = jnp.concatenate([keys, _keys(400, seed=14)])
    want = F.cuckoo_contains(spec, table, probe)
    for coop in ("none", "subtile"):
        got = ops.cuckoo_contains(spec, table, probe, coop=coop)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quotient_coop_parity():
    spec = V.FilterSpec("quotient", 1 << 13, 1, slot_bits=16, r_bits=9)
    keys = _keys(300, seed=15)
    table, _ = Q.quotient_add(spec, Q.init(spec), keys)
    probe = jnp.concatenate([keys, _keys(300, seed=16)])
    want = Q.quotient_contains(spec, table, probe)
    for coop in ("none", "subtile"):
        got = ops.quotient_contains(spec, table, probe, coop=coop)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Single-launch: cooperation never adds a second pallas_call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("regime", ["vmem", "hbm"])
def test_coop_contains_single_pallas_call(regime):
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    filt = V.init(spec)
    keys = _keys(512, seed=1)
    jaxpr = jax.make_jaxpr(
        lambda f, k: ops.bloom_contains(spec, f, k, regime=regime,
                                        coop="subtile", mix="cheap"))(
        filt, keys)
    assert _n_pallas(jaxpr) == 1, jaxpr


def test_coop_counting_update_single_pallas_call():
    spec = V.FilterSpec("countingbf", M, 8, block_bits=256)
    filt = V.init(spec)
    keys = _keys(512, seed=1)
    jaxpr = jax.make_jaxpr(
        lambda f, k: ops.counting_add(spec, f, k, coop="subtile",
                                      mix="cheap"))(filt, keys)
    assert _n_pallas(jaxpr) == 1, jaxpr


def test_coop_fingerprint_single_pallas_call():
    ck = V.FilterSpec("cuckoo", 1 << 14, 1, slot_bits=16)
    qt = V.FilterSpec("quotient", 1 << 13, 1, slot_bits=16, r_bits=9)
    keys = _keys(512, seed=1)
    for spec, op, init in ((ck, ops.cuckoo_contains, F.init),
                           (qt, ops.quotient_contains, Q.init)):
        jaxpr = jax.make_jaxpr(
            lambda f, k, o=op, s=spec: o(s, f, k, coop="subtile"))(
            init(spec), keys)
        assert _n_pallas(jaxpr) == 1, jaxpr


# ---------------------------------------------------------------------------
# Model-driven plan selection
# ---------------------------------------------------------------------------

def _calib(**kw):
    base = dict(backend="cpu", bw_hbm_gbs=1e6, bw_res_gbs=1e6, gops=1e6,
                launch_us=0.0, step_us=0.0, measured=True)
    base.update(kw)
    return Calibration(**base)


@pytest.fixture
def fresh_tuner(tmp_path, monkeypatch):
    """Isolated plan + calibration caches; cleared lru state both sides."""
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "plans.json"))
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(tmp_path / "calib.json"))
    tuning.tune_plan.cache_clear()
    yield monkeypatch
    tuning.tune_plan.cache_clear()


def test_tune_plan_picks_coop_when_model_says_so(fresh_tuner):
    """Resident-bandwidth-starved machine with free schedule steps: the
    early-exit fraction makes coop strictly cheaper -> the tuner must
    select (coop="subtile", mix="cheap")."""
    import repro.perfmodel as PM
    fresh_tuner.setattr(PM, "get_calibration",
                        lambda measure=None: _calib(bw_res_gbs=1e-3))
    spec = V.FilterSpec("sbf", 1 << 18, 16, block_bits=512)
    plan = tuning.tune_plan(spec, "contains", "vmem")
    assert plan.coop == "subtile"
    assert plan.mix == "cheap"                     # fewer flops, tie-broken
    assert plan.probe == "gather"                  # coop canonical spelling


def test_tune_plan_off_cpu_offers_only_what_lowers(fresh_tuner):
    """Off the CPU the tuner ranks only schedules the TPU compiler accepts:
    the same machine that picks coop above gets the per-key loop in VMEM,
    the HBM regime keeps its cooperative form, and a VMEM coop pin that
    cannot lower raises instead of failing deep in the compiler."""
    import repro.perfmodel as PM
    fresh_tuner.setattr(PM, "get_calibration",
                        lambda measure=None: _calib(bw_res_gbs=1e-3))
    fresh_tuner.setattr(ops, "_interpret", lambda: False)
    spec = V.FilterSpec("sbf", 1 << 18, 16, block_bits=512)
    plan = tuning.tune_plan(spec, "contains", "vmem")
    assert (plan.probe, plan.coop) == ("loop", "none")
    assert tuning.tune_plan(spec, "contains", "hbm").coop in ("none",
                                                              "subtile")
    with pytest.raises(ValueError, match="lowers"):
        tuning.tune_plan(spec, "contains", "vmem", coop="subtile")
    keys = jnp.asarray(H.random_u64x2(256, seed=0))
    with pytest.raises(ValueError, match="does not lower"):
        sbf_k.contains_vmem(spec, V.init(spec), keys,
                            default_layout(spec, "contains"),
                            interpret=False, probe="gather")


def test_tune_plan_keeps_baseline_when_steps_dominate(fresh_tuner):
    """Schedule-step-dominated machine (interpret mode): coop's extra
    vector ops lose -> the tuner stays on the non-coop baseline."""
    import repro.perfmodel as PM
    fresh_tuner.setattr(PM, "get_calibration",
                        lambda measure=None: _calib(step_us=1e3))
    spec = V.FilterSpec("sbf", 1 << 18, 16, block_bits=512)
    plan = tuning.tune_plan(spec, "contains", "vmem")
    assert plan.coop == "none"
    assert plan.mix == "cheap"                     # bit-exact + fewer flops


def test_tune_plan_pinned_axes_obeyed(fresh_tuner):
    spec = V.FilterSpec("sbf", 1 << 16, 8, block_bits=256)
    plan = tuning.tune_plan(spec, "contains", "vmem", coop="subtile",
                            mix="full")
    assert plan.coop == "subtile" and plan.mix == "full"
    with pytest.raises(AssertionError):
        tuning.tune_plan(spec, "contains", "vmem", coop="warp")


# ---------------------------------------------------------------------------
# Plan-cache key disambiguation
# ---------------------------------------------------------------------------

def test_plan_key_includes_coop_and_mix_axes():
    spec = V.FilterSpec("sbf", 1 << 16, 8, block_bits=256)
    keys = {tuning._plan_key(spec, "contains", "vmem", "structural", 256,
                             1, coop, mix)
            for coop in ("auto", "none", "subtile")
            for mix in ("auto", "full", "cheap")}
    assert len(keys) == 9                          # every axis combination
    for k in keys:
        assert k.startswith("plan2|")              # versioned: retires pre-
        assert "|coop:" in k and "|mix:" in k      # coop cache entries


def test_plan_key_positional_back_compat():
    spec = V.FilterSpec("sbf", 1 << 16, 8, block_bits=256)
    old_style = tuning._plan_key(spec, "contains", "vmem", "structural", 256)
    assert old_style == tuning._plan_key(spec, "contains", "vmem",
                                         "structural", 256, 1, "auto",
                                         "auto")


def test_plan_roundtrips_coop_mix_through_disk(fresh_tuner):
    from repro.core.tuning import Plan
    plan = tuning.tune_plan(
        V.FilterSpec("sbf", 1 << 15, 8, block_bits=256), "add", "vmem")
    again = Plan.from_dict(plan.to_dict())
    assert again == plan and again.coop in ("none", "subtile")


# ---------------------------------------------------------------------------
# API threading: make_filter -> BackendOptions -> kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant,kw", [
    ("sbf", dict(k=8, block_bits=256)),
    ("countingbf", dict(k=4, block_bits=256)),
    ("cuckoo", dict(slot_bits=16)),
    ("quotient", dict(slot_bits=16, r_bits=9)),
])
def test_make_filter_coop_options_bit_exact(variant, kw):
    keys = _keys(300, seed=21)
    probe = jnp.concatenate([keys, _keys(200, seed=22)])
    base = api.make_filter(variant=variant, m_bits=1 << 14, **kw)
    coop = api.make_filter(variant=variant, m_bits=1 << 14, coop="subtile",
                           mix="cheap", **kw)
    assert coop.options.coop == "subtile" and coop.options.mix == "cheap"
    b, c = base.add(keys), coop.add(keys)
    np.testing.assert_array_equal(np.asarray(b.words), np.asarray(c.words))
    np.testing.assert_array_equal(np.asarray(b.contains(probe)),
                                  np.asarray(c.contains(probe)))


def test_tuned_options_carries_coop_mix():
    spec = V.FilterSpec("sbf", 1 << 16, 8, block_bits=256)
    opts = api.tuned_options(spec, "contains")
    assert opts.coop in ("none", "subtile")
    assert opts.mix in ("full", "cheap")


def test_backend_options_defaults_are_auto():
    from repro.api.filter import BackendOptions
    o = BackendOptions()
    assert o.coop == "auto" and o.mix == "auto"
