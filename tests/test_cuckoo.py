"""Cuckoo fingerprint filter subsystem (PR 5).

Pins the contracts DESIGN.md §13 documents:

* jnp-reference vs Pallas-kernel **bit-exact parity** for add / remove /
  contains across slot widths (u8/u16), bucket arities and batch shapes
  (including the multi-tile chunked build and valid-masked padding);
* **measured FPR within theory** at load factor 0.95 (the acceptance bound:
  <= 1.15x the fingerprint-theory value);
* the **insert-failure signal** is surfaced — never silently dropped —
  including under jit and lax.scan (traced state leaf);
* bulk contains compiles to a **single pallas_call**;
* API integration: registry claims, capability flags + memory-cost
  reporting, sizing helpers, checkpoint round-trip, banks (batched and
  routed) and dedup consumers.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import api
from repro.core import fingerprint as F
from repro.core import hashing as H
from repro.core import variants as V
from repro.core.variants import FilterSpec
from repro.kernels import ops


def keys_of(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


def spec_of(m_bits=1 << 14, slot_bits=8, spb=4):
    return FilterSpec(variant="cuckoo", m_bits=m_bits, k=2,
                      slot_bits=slot_bits, slots_per_bucket=spb)


# ---------------------------------------------------------------------------
# Geometry + hashing invariants
# ---------------------------------------------------------------------------

def test_spec_geometry():
    s = spec_of(1 << 14, slot_bits=8, spb=4)
    assert s.block_bits == 32 and s.s == 1
    assert s.n_buckets == (1 << 14) // 32
    assert s.n_slots == s.n_buckets * 4
    assert s.storage_words == s.n_words          # 1x storage
    s16 = spec_of(1 << 14, slot_bits=16, spb=4)
    assert s16.s == 2 and s16.n_buckets == (1 << 14) // 64
    assert "u8" in str(s) and "u16" in str(s16) and str(s) != str(s16)


def test_alt_bucket_is_involution_and_fp_nonzero():
    spec = spec_of()
    b1, fp, _ = F.cuckoo_hashes(spec, keys_of(4096, seed=3))
    assert int(jnp.min(fp)) >= 1
    assert int(jnp.max(fp)) < (1 << spec.slot_bits)
    b2 = F.alt_bucket(spec, b1, fp)
    np.testing.assert_array_equal(np.asarray(F.alt_bucket(spec, b2, fp)),
                                  np.asarray(b1))


def test_pack_unpack_roundtrip():
    for sb, spb in ((8, 4), (16, 4), (16, 2), (8, 8)):
        spec = spec_of(1 << 13, slot_bits=sb, spb=spb)
        rng = np.random.RandomState(7)
        slots = jnp.asarray(rng.randint(0, 1 << sb, size=(32, spb)),
                            dtype=jnp.uint32)
        words = F.pack_slots(spec, slots)
        assert words.shape == (32, spec.s)
        np.testing.assert_array_equal(np.asarray(F.unpack_slots(spec, words)),
                                      np.asarray(slots))


# ---------------------------------------------------------------------------
# jnp vs Pallas parity (the kernels share the tile functions — the parity
# tests pin the dispatch plumbing: padding, tiling, valid masks, ordering)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("slot_bits,spb", [(8, 4), (16, 4), (16, 2)])
def test_kernel_parity_add_contains_remove(slot_bits, spb):
    spec = spec_of(1 << 14, slot_bits=slot_bits, spb=spb)
    keys = keys_of(1000, seed=5)
    t_ref, ok_ref = F.cuckoo_add(spec, F.init(spec), keys)
    t_pal, ok_pal = ops.cuckoo_add(spec, F.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(t_ref), np.asarray(t_pal))
    np.testing.assert_array_equal(np.asarray(ok_ref), np.asarray(ok_pal))
    np.testing.assert_array_equal(
        np.asarray(F.cuckoo_contains(spec, t_ref, keys)),
        np.asarray(ops.cuckoo_contains(spec, t_pal, keys)))
    r_ref, f_ref = F.cuckoo_remove(spec, t_ref, keys[:500])
    r_pal, f_pal = ops.cuckoo_remove(spec, t_pal, keys[:500])
    np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_pal))
    np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_pal))


def test_kernel_parity_multi_tile_and_valid_mask():
    spec = F.spec_for_n(4000)
    keys = keys_of(2 * F.CUCKOO_ADD_TILE + 321, seed=9)   # 3 chunks
    a, _ = F.cuckoo_add(spec, F.init(spec), keys)
    b, _ = ops.cuckoo_add(spec, F.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # zero-padded + valid-masked build equals the unpadded build (inserts
    # are not idempotent, so this is the padding contract that matters)
    pad = jnp.concatenate([keys, jnp.zeros((37, 2), jnp.uint32)])
    v = jnp.concatenate([jnp.ones(keys.shape[0], bool), jnp.zeros(37, bool)])
    c, _ = F.cuckoo_add(spec, F.init(spec), pad, valid=v)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_api_impl_parity():
    """make_filter(variant='cuckoo') is bit-exact between its jnp and
    pallas execution paths for add/remove/contains (acceptance criterion)."""
    keys = keys_of(900, seed=2)
    outs = []
    for impl in ("jnp", "pallas"):
        f = api.make_filter(variant="cuckoo", m_bits=1 << 14, impl=impl)
        f = f.add(keys)
        f = f.remove(keys[:300])
        outs.append((np.asarray(f.words), np.asarray(f.contains(keys)),
                     int(f.insert_failures)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    assert outs[0][2] == outs[1][2]


# ---------------------------------------------------------------------------
# Semantics: no false negatives, deletion, FPR vs theory
# ---------------------------------------------------------------------------

def test_no_false_negatives_and_remove_preserves_others():
    spec = F.spec_for_n(2000)
    keys = keys_of(2000, seed=1)
    t, ok = F.cuckoo_add(spec, F.init(spec), keys)
    assert bool(ok.all())
    assert bool(F.cuckoo_contains(spec, t, keys).all())
    t2, found = F.cuckoo_remove(spec, t, keys[:1000])
    assert bool(found.all())
    # the no-false-negative guarantee survives deletion of OTHER keys
    assert bool(F.cuckoo_contains(spec, t2, keys[1000:]).all())
    # removed keys revert to FPR-level hits
    assert float(F.cuckoo_contains(spec, t2, keys[:1000]).mean()) < 0.1


def test_duplicate_keys_occupy_and_release_per_instance():
    spec = spec_of(1 << 12)
    k1 = keys_of(1, seed=4)
    dup = jnp.concatenate([k1, k1, k1])
    t, ok = F.cuckoo_add(spec, F.init(spec), dup)
    assert bool(ok.all())
    assert int(F.occupied_slots(spec, t)) == 3       # three slots taken
    t, found = F.cuckoo_remove(spec, t, dup[:2])
    assert bool(found.all())
    assert int(F.occupied_slots(spec, t)) == 1       # one instance left
    assert bool(F.cuckoo_contains(spec, t, k1).all())


@pytest.mark.parametrize("slot_bits", [8, 16])
def test_measured_fpr_within_theory_at_095(slot_bits):
    """Acceptance: measured FPR <= 1.15x fingerprint theory at load 0.95."""
    spec = spec_of(1 << 15, slot_bits=slot_bits)
    n = int(spec.n_slots * 0.95)
    t, ok = F.cuckoo_add(spec, F.init(spec), keys_of(n, seed=12))
    n_stored = n - int(jnp.sum(~ok))
    assert n_stored >= 0.99 * n                      # 0.95 load is feasible
    # u16's ~1e-4 FPR needs ~1M probes for the 1.15x bound to be a ~2-sigma
    # statement rather than Poisson noise on a handful of hits
    n_probe = 1 << (16 if slot_bits == 8 else 21)
    probes = jnp.asarray(H.probe_u64x2(n_probe, seed=77))
    measured = float(F.cuckoo_contains(spec, t, probes).mean())
    theory = F.fpr_cuckoo(spec.slot_bits, spec.slots_per_bucket,
                          n_stored / spec.n_slots)
    assert measured <= 1.15 * theory, (measured, theory)
    if slot_bits == 8:                               # u16 FPR is ~1e-4: noisy
        assert measured >= 0.5 * theory, (measured, theory)


def test_load_factor_and_theory_monotonicity():
    spec = spec_of(1 << 13)
    t, _ = F.cuckoo_add(spec, F.init(spec), keys_of(512, seed=3))
    assert abs(float(F.cuckoo_load_factor(spec, t)) - 512 / spec.n_slots) \
        < 1e-6
    assert V.fpr_theory(spec, 100) < V.fpr_theory(spec, 500)
    assert V.space_optimal_n(spec) == int(spec.n_slots * 0.95)


# ---------------------------------------------------------------------------
# Insert-failure signal: explicit, cumulative, jit/scan-safe
# ---------------------------------------------------------------------------

def test_insert_failure_signal_surfaced():
    spec = spec_of(32 * 16)                          # 128 slots
    t, ok = F.cuckoo_add(spec, F.init(spec), keys_of(200, seed=6))
    n_fail = int(jnp.sum(~ok))
    assert n_fail > 0                                # way past capacity
    # exact accounting: each failure = exactly one homeless fingerprint,
    # so stored slots == successful inserts (nothing vanishes untallied)
    assert int(F.occupied_slots(spec, t)) == int(jnp.sum(ok))
    # the API accumulates the same count into the traced state leaf
    f = api.make_filter(variant="cuckoo", m_bits=32 * 16).add(
        keys_of(200, seed=6))
    assert int(f.insert_failures) == n_fail


def test_insert_failures_under_jit_and_scan():
    f0 = api.make_filter(variant="cuckoo", m_bits=32 * 16)
    batches = keys_of(256, seed=8).reshape(4, 64, 2)

    @jax.jit
    def fill(f, kbs):
        def step(flt, kb):
            return flt.add(kb), flt.insert_failures
        return jax.lax.scan(step, f, kbs)

    out, trace = fill(f0, batches)
    assert int(out.insert_failures) > 0              # signal not dropped
    tr = np.asarray(trace)
    assert tr[0] == 0 and np.all(np.diff(tr) >= 0)   # cumulative carry
    # eager path agrees with the jitted scan
    g = f0
    for i in range(4):
        g = g.add(batches[i])
    assert int(g.insert_failures) == int(out.insert_failures)
    np.testing.assert_array_equal(np.asarray(g.words), np.asarray(out.words))


def test_failure_counter_not_reset_by_other_ops():
    f = api.make_filter(variant="cuckoo", m_bits=32 * 16)
    f = f.add(keys_of(200, seed=6))
    before = int(f.insert_failures)
    f = f.remove(keys_of(10, seed=6))
    f.contains(keys_of(10, seed=6))
    assert int(f.insert_failures) == before


# ---------------------------------------------------------------------------
# Single-launch jaxpr + engine/registry integration
# ---------------------------------------------------------------------------

def test_bulk_contains_single_pallas_call():
    spec = spec_of(1 << 14)
    t = F.init(spec)
    keys = keys_of(1024, seed=2)
    jaxpr = jax.make_jaxpr(
        lambda f, k: ops.cuckoo_contains(spec, f, k))(t, keys)
    n_calls = sum(1 for e in jaxpr.jaxpr.eqns
                  if "pallas" in e.primitive.name)
    assert n_calls == 1, jaxpr


def test_registry_claims_and_flags():
    f = api.make_filter(variant="cuckoo", m_bits=1 << 13)
    assert f.backend == "cuckoo"
    descs = {d["name"]: d for d in api.describe_backends()}
    d = descs["cuckoo"]
    assert d["supports_remove"] and not d["supports_decay"]
    assert not d["supports_count"]                   # no counters
    # memory cost reported alongside the flags (satellite): cuckoo beats
    # counting at the reference FPR, both are priced, bloom is cheapest
    assert d["bits_per_key_at_ref_fpr"] < descs["counting"][
        "bits_per_key_at_ref_fpr"]
    assert descs["jnp"]["bits_per_key_at_ref_fpr"] < d[
        "bits_per_key_at_ref_fpr"]
    # bloom/dist engines must decline fingerprint specs
    ctx = api.BackendOptions().ctx()
    for name in ("jnp", "pallas-vmem", "pallas-hbm"):
        assert not api.get_backend(name).supports(f.spec, ctx)
    with pytest.raises(NotImplementedError):
        api.make_filter(variant="sbf", m_bits=1 << 13).remove(keys_of(4))
    with pytest.raises(NotImplementedError):
        f.decay()
    # the supports_merge flag is checked up front: a uniform ValueError
    # naming the engine and the nearest alternative, not an engine-deep
    # NotImplementedError
    assert not d["supports_merge"] and not d["supports_resize"]
    with pytest.raises(ValueError, match="quotient"):
        f.merge(api.make_filter(variant="cuckoo", m_bits=1 << 13))
    with pytest.raises(ValueError, match="quotient"):
        f.resize(1 << 14)


def test_filter_for_workload_prefers_cuckoo_for_remove_only():
    f = api.filter_for_workload(1 << 10, needs_remove=True)
    assert f.backend == "cuckoo"
    g = api.filter_for_workload(1 << 10, needs_remove=True, needs_decay=True)
    assert g.backend == "counting"
    h = api.filter_for_workload(1 << 10, needs_remove=True, needs_count=True)
    assert h.backend == "counting"
    p = api.filter_for_workload(1 << 10)
    assert not p.spec.is_counting and not p.spec.is_fingerprint


def test_sizing_helpers():
    f = api.filter_for_n_items(10_000, variant="cuckoo", target_fpr=1e-3)
    assert f.spec.slot_bits == 16                    # u8 can't reach 1e-3
    assert 10_000 / f.spec.n_slots <= F.CUCKOO_MAX_LOAD
    g = api.filter_for_n_items(10_000, variant="cuckoo", target_fpr=3e-2)
    assert g.spec.slot_bits == 8
    with pytest.raises(ValueError):
        F.spec_for_n(100, target_fpr=1e-9)           # u16 can't reach 1e-9
    # bloom iso-error sizing (the harness's inverse): theory meets target
    s = api.filter_for_n_items(10_000, variant="sbf", target_fpr=1e-3)
    assert s.fpr_theory(10_000) <= 1e-3


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import checkpoint as C
    f = api.make_filter(variant="cuckoo", m_bits=32 * 16)
    f = f.add(keys_of(200, seed=6))                  # forces failures > 0
    C.save_filter(str(tmp_path), 3, f)
    step, g = C.restore_filter(str(tmp_path))
    assert step == 3 and g.spec == f.spec and g.backend == "cuckoo"
    np.testing.assert_array_equal(np.asarray(g.words), np.asarray(f.words))
    assert int(g.insert_failures) == int(f.insert_failures)
    # to_state/from_state path round-trips the same way
    h = api.Filter.from_state(f.to_state())
    np.testing.assert_array_equal(np.asarray(h.words), np.asarray(f.words))
    assert int(h.insert_failures) == int(f.insert_failures)


# ---------------------------------------------------------------------------
# Banks (generic vmap fallback with real valid masks)
# ---------------------------------------------------------------------------

def test_bank_matches_per_member_loop():
    B, n = 4, 64
    kb = keys_of(B * n, seed=13).reshape(B, n, 2)
    bank = api.make_filter_bank(B, variant="cuckoo", m_bits=1 << 12)
    bank = bank.add(kb)
    singles = []
    for b in range(B):
        s = api.make_filter(variant="cuckoo", m_bits=1 << 12).add(kb[b])
        singles.append(np.asarray(s.words))
    np.testing.assert_array_equal(np.asarray(bank.words),
                                  np.stack(singles))
    assert np.asarray(bank.contains(kb)).all()
    assert np.asarray(bank.insert_failures).shape == (B,)


def test_bank_routed_with_valid_and_remove():
    B = 4
    bank = api.make_filter_bank(B, variant="cuckoo", m_bits=1 << 12)
    keys = keys_of(80, seed=14)
    tenants = np.tile(np.arange(B), 20)
    valid = np.ones(80, np.uint8)
    valid[60:] = 0                                   # padding tail
    bank = bank.add(keys, tenants=tenants, valid=valid)
    hits = np.asarray(bank.contains(keys, tenants=tenants))
    assert hits[:60].all()
    # tenant isolation: other members don't see these keys
    other = np.asarray(bank.contains(keys[:60],
                                     tenants=(tenants[:60] + 1) % B))
    assert other.mean() < 0.1
    bank2 = bank.remove(keys[:20], tenants=tenants[:20])
    gone = np.asarray(bank2.contains(keys[:20], tenants=tenants[:20]))
    assert gone.mean() < 0.2
    still = np.asarray(bank2.contains(keys[20:60], tenants=tenants[20:60]))
    assert still.all()


def test_bank_select_scatter_state():
    bank = api.make_filter_bank(3, variant="cuckoo", m_bits=32 * 16)
    kb = keys_of(3 * 150, seed=15).reshape(3, 150, 2)
    bank = bank.add(kb)
    fails = np.asarray(bank.insert_failures)
    assert fails.sum() > 0
    m1 = bank.select(1)
    assert int(m1.insert_failures) == fails[1]
    fresh = api.make_filter(variant="cuckoo", m_bits=32 * 16)
    bank2 = bank.scatter_update(1, fresh)
    assert int(np.asarray(bank2.insert_failures)[1]) == 0
    assert float(bank2.select(1).load_factor()) == 0.0


# ---------------------------------------------------------------------------
# Consumers
# ---------------------------------------------------------------------------

def test_streaming_dedup_cuckoo_readmits_after_eviction():
    import itertools
    from repro.data import dedup as D
    from repro.data import pipeline as DP
    sd = D.StreamingDedupFilter(window_docs=256, generations=4,
                                batch_docs=32, engine="cuckoo",
                                bits_per_key=8)
    cfg = DP.CorpusConfig(n_docs=400, dup_fraction=0.2, seed=2)
    stream = itertools.chain(*(DP.synthetic_corpus(cfg) for _ in range(3)))
    kept = sum(1 for _ in sd.filter_stream(stream))
    assert sd.stats.advances > 0
    assert kept > 400                # eviction re-admitted expired docs
    assert int(sd.filt.insert_failures) == 0
    assert 0.0 < sd.filt.load_factor() <= 1.0


def test_tenant_dedup_cuckoo_engine():
    from repro.data import dedup as D
    td = D.TenantDedupFilter(n_tenants=4, expected_docs_per_tenant=256,
                             batch_docs=16, engine="cuckoo")
    assert td.filt.spec.is_fingerprint
    docs = [np.arange(i % 7 + 3, dtype=np.uint32) + 13 * i
            for i in range(48)]
    tenants = [i % 4 for i in range(48)]
    keep = td.dedupe_batch(docs, tenants)
    assert len(keep) == 48                           # all unique per tenant
    keep2 = td.dedupe_batch(docs, tenants)           # exact duplicates
    assert len(keep2) == 0
    # per-tenant deletion (the capability the satellite wires in)
    sigs = D.doc_signatures_batch(docs)
    td.filt = td.filt.remove(sigs[:12], tenants=np.asarray(tenants[:12]))
    keep3 = td.dedupe_batch(docs[:12], tenants[:12])
    assert len(keep3) == 12                          # forgotten -> fresh


def test_empty_batches_and_repr():
    f = api.make_filter(variant="cuckoo", m_bits=1 << 12)
    empty = jnp.zeros((0, 2), jnp.uint32)
    assert f.add(empty) is f
    assert f.remove(empty) is f
    assert f.contains(empty).shape == (0,)
    assert "cuckoo" in repr(f)
    assert f.nbytes == f.spec.n_words * 4
