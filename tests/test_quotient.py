"""Counting quotient filter subsystem (PR 7).

Pins the contracts DESIGN.md §15 documents:

* jnp-reference vs Pallas-kernel **bit-exact parity** for add / remove /
  contains across sizes, tile schedules and valid masks (the canonical
  decode+rebuild layout is a pure function of the stored multiset, so
  EVERY schedule must produce the same words);
* **measured FPR within theory** at load factor 0.9 (<= 1.15x the
  fingerprint-collision value);
* **merge is lossless**: the merged table is bit-identical to a table
  built from the concatenated key streams;
* **resize is lossless**: membership preserved exactly, words
  bit-identical to a from-scratch build at the new size, FPR unchanged
  (p = q + r is conserved — only the split moves);
* bulk contains compiles to a **single pallas_call**;
* API integration: registry claims + capability flags
  (supports_merge/supports_resize), workload selection, banks (batched,
  routed, valid-masked), checkpoint round-trip and the insert-failure
  signal.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import api
from repro.core import hashing as H
from repro.core import quotient as Q
from repro.core import variants as V
from repro.core.variants import FilterSpec
from repro.kernels import ops


def keys_of(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


def spec_of(m_bits=1 << 13, slot_bits=16, r_bits=10):
    return FilterSpec(variant="quotient", m_bits=m_bits, k=1,
                      slot_bits=slot_bits, r_bits=r_bits)


# ---------------------------------------------------------------------------
# Geometry + spec invariants
# ---------------------------------------------------------------------------

def test_spec_geometry():
    s = spec_of(1 << 13, slot_bits=16, r_bits=10)
    assert s.is_quotient and s.is_fingerprint and not s.is_counting
    assert s.n_slots == (1 << 13) // 16 and s.q_bits == 9
    assert s.fingerprint_bits == 19 and s.k == 1
    s8 = spec_of(1 << 10, slot_bits=8, r_bits=5)
    assert s8.slots_per_word == 4 and s8.n_words == s8.n_slots // 4


def test_str_spells_quotient_geometry():
    """Satellite: the tune-plan/disk-cache key must encode the q/r split
    and lane so quotient specs never collide with each other or with
    sbf/cuckoo specs of equal m."""
    s = spec_of(1 << 13, slot_bits=16, r_bits=10)
    out = str(s)
    assert "quotient" in out and "q9" in out and "r10" in out
    assert "u16" in out and "occ" in out
    assert str(spec_of(r_bits=9)) != str(spec_of(r_bits=10))
    assert str(spec_of(slot_bits=16, r_bits=10)) != \
        str(FilterSpec(variant="cuckoo", m_bits=1 << 13, k=2, slot_bits=16))


def test_pack_unpack_roundtrip():
    for sb in (8, 16, 32):
        q = V._log2i((1 << 10) // sb)
        spec = spec_of(1 << 10, slot_bits=sb, r_bits=min(sb - 3, 31 - q))
        rng = np.random.RandomState(7)
        lanes = jnp.asarray(rng.randint(0, 1 << sb, size=(spec.n_slots,)),
                            dtype=jnp.uint32)
        np.testing.assert_array_equal(
            np.asarray(Q.unpack_slots(spec, Q.pack_slots(spec, lanes))),
            np.asarray(lanes))


def test_decode_inverts_layout():
    """decode(build(S)) == S as a multiset, for a random multiset with
    duplicates — the identity every structural op (merge/resize) rests
    on."""
    spec = spec_of(1 << 12, slot_bits=16, r_bits=8)
    rng = np.random.RandomState(3)
    fps = rng.randint(0, 1 << spec.fingerprint_bits, size=120)
    fps[40:60] = fps[:20]                       # force duplicates
    fp = jnp.asarray(fps, jnp.uint32)
    lanes = Q._layout(spec, fp, jnp.ones((120,), bool))
    got, count = Q.decode_fingerprints(spec, Q.pack_slots(spec, lanes))
    assert int(count) == 120
    np.testing.assert_array_equal(np.sort(np.asarray(got[:120])),
                                  np.sort(fps.astype(np.uint32)))


# ---------------------------------------------------------------------------
# jnp vs Pallas parity — the kernel body IS the reference tile function,
# so these pin the dispatch plumbing: padding, tiling, valid masks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 100, 400])
@pytest.mark.parametrize("slot_bits,r_bits", [(8, 5), (16, 10)])
def test_kernel_parity_add_contains_remove(n, slot_bits, r_bits):
    spec = spec_of(1 << 13, slot_bits=slot_bits, r_bits=r_bits)
    keys = keys_of(n, seed=5)
    t_ref, ok_ref = Q.quotient_add(spec, Q.init(spec), keys)
    t_pal, ok_pal = ops.quotient_add(spec, Q.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(t_ref), np.asarray(t_pal))
    np.testing.assert_array_equal(np.asarray(ok_ref), np.asarray(ok_pal))
    np.testing.assert_array_equal(
        np.asarray(Q.quotient_contains(spec, t_ref, keys)),
        np.asarray(ops.quotient_contains(spec, t_pal, keys)))
    nrm = max(n // 2, 1)
    r_ref, f_ref = Q.quotient_remove(spec, t_ref, keys[:nrm])
    r_pal, f_pal = ops.quotient_remove(spec, t_pal, keys[:nrm])
    np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_pal))
    np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_pal))


def test_build_is_tile_size_independent():
    """The words are a pure function of the stored multiset: any tile
    schedule (including the kernel's padded one) produces identical
    words — stronger than cuckoo's schedule-parity guarantee."""
    spec = Q.spec_for_n(900, target_fpr=1e-2)
    keys = keys_of(800, seed=9)
    ref, _ = Q.quotient_add(spec, Q.init(spec), keys)
    for tile in (64, 128, 1024):
        t, _ = Q.quotient_add(spec, Q.init(spec), keys, tile=tile)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(t))
        t, _ = ops.quotient_add(spec, Q.init(spec), keys, tile=tile)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(t))


def test_kernel_parity_valid_mask():
    """Zero-padded + valid-masked build equals the unpadded build —
    the padding contract for non-idempotent inserts."""
    spec = Q.spec_for_n(600, target_fpr=1e-2)
    keys = keys_of(500, seed=11)
    ref, _ = Q.quotient_add(spec, Q.init(spec), keys)
    pad = jnp.concatenate([keys, jnp.zeros((37, 2), jnp.uint32)])
    v = jnp.concatenate([jnp.ones(500, bool), jnp.zeros(37, bool)])
    for fn in (Q.quotient_add, ops.quotient_add):
        t, ok = fn(spec, Q.init(spec), pad, valid=v)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(t))
        assert bool(ok.all())                    # padding reported as no-op


def test_api_impl_parity():
    """make_filter(variant='quotient') is bit-exact between its jnp and
    pallas execution paths for add/remove/contains."""
    keys = keys_of(300, seed=2)
    outs = []
    for impl in ("jnp", "pallas"):
        f = api.make_filter(variant="quotient", m_bits=1 << 13,
                            slot_bits=16, r_bits=10, impl=impl)
        f = f.add(keys).remove(keys[:100])
        outs.append((np.asarray(f.words), np.asarray(f.contains(keys)),
                     int(f.insert_failures)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    assert outs[0][2] == outs[1][2]


# ---------------------------------------------------------------------------
# Semantics: no false negatives, counting deletes, FPR vs theory
# ---------------------------------------------------------------------------

def test_no_false_negatives_and_remove_preserves_others():
    spec = Q.spec_for_n(2000, target_fpr=1e-3)
    keys = keys_of(2000, seed=1)
    t, ok = Q.quotient_add(spec, Q.init(spec), keys)
    assert bool(ok.all())
    assert bool(Q.quotient_contains(spec, t, keys).all())
    t2, found = Q.quotient_remove(spec, t, keys[:1000])
    assert bool(found.all())
    assert bool(Q.quotient_contains(spec, t2, keys[1000:]).all())
    assert float(Q.quotient_contains(spec, t2, keys[:1000]).mean()) < 0.1


def test_duplicates_count_per_instance():
    spec = spec_of(1 << 12, slot_bits=16, r_bits=8)
    k1 = keys_of(1, seed=4)
    dup = jnp.concatenate([k1, k1, k1])
    t, ok = Q.quotient_add(spec, Q.init(spec), dup)
    assert bool(ok.all())
    assert int(Q.occupied_slots(spec, t)) == 3   # counting: one slot each
    t, found = Q.quotient_remove(spec, t, dup[:2])
    assert bool(found.all())
    assert int(Q.occupied_slots(spec, t)) == 1
    assert bool(Q.quotient_contains(spec, t, k1).all())
    t, found = Q.quotient_remove(spec, t, dup)   # 3 requests, 1 copy left
    assert int(jnp.sum(found)) == 1


def test_measured_fpr_within_theory_at_09():
    """Acceptance: measured FPR <= 1.15x quotient theory at load 0.9.
    A short remainder (r=5) keeps the FPR high enough that 2^16 probes
    make 1.15x a many-sigma statement, not Poisson noise."""
    spec = spec_of((1 << 10) * 8, slot_bits=8, r_bits=5)   # q=10, r=5
    n = int(spec.n_slots * 0.9)
    t, ok = Q.quotient_add(spec, Q.init(spec), keys_of(n, seed=12))
    assert bool(ok.all())
    probes = jnp.asarray(H.probe_u64x2(1 << 16, seed=77))
    measured = float(Q.quotient_contains(spec, t, probes).mean())
    theory = Q.fpr_quotient(spec.q_bits, spec.r_bits, n / spec.n_slots)
    assert measured <= 1.15 * theory, (measured, theory)
    assert measured >= 0.5 * theory, (measured, theory)


def test_load_factor_and_theory():
    spec = spec_of(1 << 13, slot_bits=16, r_bits=10)
    t, _ = Q.quotient_add(spec, Q.init(spec), keys_of(256, seed=3))
    assert abs(float(Q.quotient_load_factor(spec, t))
               - 256 / spec.n_slots) < 1e-6
    assert V.fpr_theory(spec, 100) < V.fpr_theory(spec, 400)
    assert V.space_optimal_n(spec) == min(int(spec.n_slots * 0.9),
                                          spec.n_slots - 1)


def test_insert_failure_signal_exact():
    spec = spec_of(1 << 7, slot_bits=8, r_bits=5)     # 16 slots, cap 15
    t, ok = Q.quotient_add(spec, Q.init(spec), keys_of(40, seed=6))
    n_fail = int(jnp.sum(~ok))
    assert n_fail == 40 - (spec.n_slots - 1)          # FCFS to exactly cap
    assert int(Q.occupied_slots(spec, t)) == spec.n_slots - 1
    f = api.make_filter(variant="quotient", m_bits=1 << 7, slot_bits=8,
                        r_bits=5).add(keys_of(40, seed=6))
    assert int(f.insert_failures) == n_fail


# ---------------------------------------------------------------------------
# merge / resize — the lossless structural ops (the tentpole's point)
# ---------------------------------------------------------------------------

def test_merge_bit_identical_to_concatenated_build():
    spec = Q.spec_for_n(1000, target_fpr=1e-3)
    ka, kb = keys_of(400, seed=21), keys_of(300, seed=22)
    ta, _ = Q.quotient_add(spec, Q.init(spec), ka)
    tb, _ = Q.quotient_add(spec, Q.init(spec), kb)
    merged = Q.quotient_merge(spec, ta, tb)
    ref, _ = Q.quotient_add(spec, Q.init(spec),
                            jnp.concatenate([ka, kb]))
    np.testing.assert_array_equal(np.asarray(merged), np.asarray(ref))


def test_api_merge_and_union():
    spec = Q.spec_for_n(600, target_fpr=1e-2)
    ka, kb = keys_of(200, seed=31), keys_of(150, seed=32)
    a = api.make_filter(variant="quotient", m_bits=spec.m_bits,
                        slot_bits=spec.slot_bits, r_bits=spec.r_bits)
    b = a.replace(words=a.words)
    a, b = a.add(ka), b.add(kb)
    m = a | b
    assert bool(m.contains(ka).all()) and bool(m.contains(kb).all())
    ref = api.make_filter(variant="quotient", m_bits=spec.m_bits,
                          slot_bits=spec.slot_bits, r_bits=spec.r_bits
                          ).add(jnp.concatenate([ka, kb]))
    np.testing.assert_array_equal(np.asarray(m.words), np.asarray(ref.words))
    # overflow is refused eagerly, never silently lossy
    tiny = api.make_filter(variant="quotient", m_bits=1 << 8, slot_bits=8,
                           r_bits=5)
    x = tiny.add(keys_of(16, seed=1))
    y = tiny.add(keys_of(16, seed=2))
    with pytest.raises(ValueError, match="overflow"):
        x.merge(y)


def test_resize_grow_preserves_membership_and_words():
    spec = Q.spec_for_n(800, target_fpr=1e-3)
    keys = keys_of(700, seed=41)
    f = api.make_filter(variant="quotient", m_bits=spec.m_bits,
                        slot_bits=spec.slot_bits, r_bits=spec.r_bits
                        ).add(keys)
    g = f.resize(spec.m_bits * 2)
    assert g.spec.m_bits == spec.m_bits * 2
    assert g.spec.fingerprint_bits == spec.fingerprint_bits  # p conserved
    assert g.spec.r_bits == spec.r_bits - 1
    assert bool(g.contains(keys).all())
    # bit-identical to a from-scratch build at the new size (losslessness
    # is structural, not just membership-level)
    ref = api.make_filter(variant="quotient", m_bits=g.spec.m_bits,
                          slot_bits=g.spec.slot_bits, r_bits=g.spec.r_bits
                          ).add(keys)
    np.testing.assert_array_equal(np.asarray(g.words), np.asarray(ref.words))
    # shrink back: still lossless while the count fits
    h = g.resize(spec.m_bits)
    np.testing.assert_array_equal(np.asarray(h.words), np.asarray(f.words))


def test_resize_fpr_tracks_theory_at_new_size():
    """p = q + r is conserved, so the analytic FPR (1 - (1-2^-p)^n) is
    IDENTICAL across resizes — measured FPR must stay within the bound
    at the new geometry."""
    spec = spec_of((1 << 10) * 8, slot_bits=8, r_bits=5)
    n = int(spec.n_slots * 0.9)
    keys = keys_of(n, seed=51)
    f = api.make_filter(variant="quotient", m_bits=spec.m_bits, slot_bits=8,
                        r_bits=5).add(keys)
    g = f.resize(spec.m_bits * 2)
    probes = jnp.asarray(H.probe_u64x2(1 << 16, seed=78))
    measured = float(np.asarray(g.contains(probes)).mean())
    theory = Q.fpr_quotient(g.spec.q_bits, g.spec.r_bits,
                            n / g.spec.n_slots)
    assert abs(theory - Q.fpr_quotient(spec.q_bits, spec.r_bits,
                                       n / spec.n_slots)) < 1e-12
    assert measured <= 1.15 * theory, (measured, theory)


def test_resize_shrink_overflow_refused():
    f = api.make_filter(variant="quotient", m_bits=1 << 11, slot_bits=16,
                        r_bits=5).add(keys_of(100, seed=61))
    with pytest.raises(ValueError, match="shrink"):
        f.resize(1 << 10)                     # 64 slots < 100 stored
    with pytest.raises(ValueError, match="conserved fingerprint"):
        f.resize(1 << 30)                     # r would leave [1, lane-3]


# ---------------------------------------------------------------------------
# Single-launch jaxpr + registry/workload integration
# ---------------------------------------------------------------------------

def test_bulk_contains_single_pallas_call():
    spec = spec_of(1 << 13)
    t = Q.init(spec)
    keys = keys_of(1024, seed=2)
    jaxpr = jax.make_jaxpr(
        lambda f, k: ops.quotient_contains(spec, f, k))(t, keys)
    n_calls = sum(1 for e in jaxpr.jaxpr.eqns
                  if "pallas" in e.primitive.name)
    assert n_calls == 1, jaxpr


def test_registry_flags_and_workload_selection():
    f = api.make_filter(variant="quotient", m_bits=1 << 12, slot_bits=16,
                        r_bits=10)
    assert f.backend == "quotient"
    descs = {d["name"]: d for d in api.describe_backends()}
    d = descs["quotient"]
    assert d["supports_remove"] and d["supports_merge"]
    assert d["supports_resize"] and not d["supports_decay"]
    # cuckoo stays cheaper for remove-only; merge/resize flip to quotient
    assert descs["cuckoo"]["bits_per_key_at_ref_fpr"] < \
        d["bits_per_key_at_ref_fpr"]
    assert api.filter_for_workload(
        1 << 10, needs_remove=True).backend == "cuckoo"
    assert api.filter_for_workload(
        1 << 10, needs_remove=True, needs_merge=True).backend == "quotient"
    assert api.filter_for_workload(
        1 << 10, needs_resize=True).backend == "quotient"
    # bloom/dist engines must decline quotient specs
    ctx = api.BackendOptions().ctx()
    for name in ("jnp", "pallas-vmem", "pallas-hbm", "cuckoo"):
        assert not api.get_backend(name).supports(f.spec, ctx)


def test_sizing_helper():
    f = api.filter_for_n_items(10_000, variant="quotient", target_fpr=1e-3)
    assert f.spec.is_quotient
    assert 10_000 / f.spec.n_slots <= Q.QUOTIENT_MAX_LOAD
    assert V.fpr_theory(f.spec, 10_000) <= 1e-3 * 1.05
    keys = keys_of(10_000, seed=8)
    f = f.add(keys)
    assert int(f.insert_failures) == 0
    assert bool(f.contains(keys).all())


# ---------------------------------------------------------------------------
# Banks: batched, routed, valid-masked; checkpoint round-trip
# ---------------------------------------------------------------------------

def test_bank_batched_and_routed():
    B = 4
    fb = api.filter_for_n_items(300, variant="quotient", target_fpr=1e-2,
                                bank=B)
    keys = jnp.stack([keys_of(64, seed=i) for i in range(B)])
    fb = fb.add(keys)
    assert bool(fb.contains(keys).all())
    assert not bool(fb.select(0).contains(keys[1]).any())  # isolation
    flat = keys_of(128, seed=99)
    ten = jnp.arange(128, dtype=jnp.int32) % B
    fb = fb.add(flat, tenants=ten)
    assert bool(fb.contains(flat, tenants=ten).all())
    fb = fb.remove(flat, tenants=ten)
    assert bool(fb.contains(keys).all())              # originals intact


def test_bank_valid_mask_and_state():
    B = 3
    keys = jnp.stack([keys_of(32, seed=i) for i in range(B)])
    v = jnp.ones((B, 32), bool).at[:, 16:].set(False)
    fb = api.filter_for_n_items(200, variant="quotient", target_fpr=1e-2,
                                bank=B).add(keys, valid=v)
    counts = np.asarray(Q.occupied_slots(fb.spec, fb.words))
    np.testing.assert_array_equal(counts, [16, 16, 16])
    assert bool(fb.contains(keys[:, :16]).all())
    assert fb.state.shape == (B,)                     # per-member failures


def test_bank_merge_and_resize():
    B = 4
    fb = api.filter_for_n_items(300, variant="quotient", target_fpr=1e-2,
                                bank=B)
    ka = jnp.stack([keys_of(40, seed=i) for i in range(B)])
    kb = jnp.stack([keys_of(40, seed=100 + i) for i in range(B)])
    a, b = fb.add(ka), fb.add(kb)
    m = a.bank_merge(b)
    ref = fb.add(jnp.concatenate([ka, kb], axis=1))
    np.testing.assert_array_equal(np.asarray(m.words), np.asarray(ref.words))
    g = a.resize(a.spec.m_bits * 2)
    assert g.bank_shape == (B,) and bool(g.contains(ka).all())


def test_checkpoint_roundtrip():
    from repro.api.filter import Filter
    f = api.filter_for_n_items(200, variant="quotient", target_fpr=1e-2,
                               bank=2)
    keys = jnp.stack([keys_of(50, seed=i) for i in range(2)])
    f = f.add(keys)
    back = Filter.from_state(f.to_state())
    assert back.backend == "quotient" and back.spec == f.spec
    np.testing.assert_array_equal(np.asarray(back.words),
                                  np.asarray(f.words))
    np.testing.assert_array_equal(np.asarray(back.state),
                                  np.asarray(f.state))
    assert bool(back.contains(keys).all())


def test_empty_batches_and_repr():
    f = api.make_filter(variant="quotient", m_bits=1 << 12, slot_bits=16,
                        r_bits=10)
    empty = jnp.zeros((0, 2), jnp.uint32)
    assert f.add(empty) is f
    assert f.remove(empty) is f
    assert f.contains(empty).shape == (0,)
    assert "quotient" in repr(f)
    assert f.nbytes == f.spec.n_words * 4
