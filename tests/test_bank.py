"""Tests for the FilterBank axis (`repro.api` v2).

Acceptance contract of the bank redesign:
* a bank is bit-identical to B independent scalar filters on every engine
  (jnp / pallas-vmem / pallas-hbm / counting), for per-member batches AND
  routed ``(keys, tenant_ids)`` flat keys;
* a B-member VMEM-resident bank executes add/contains as a SINGLE Pallas
  launch (jaxpr-verified);
* ``jax.vmap`` over the Filter pytree's bank axis sees valid scalar
  filters (the words leaf carries the bank as leading dims);
* windowed heads are traced state: ``advance()`` never retraces jitted
  code and survives ``lax.scan``;
* banks checkpoint round-trip (state dict and on-disk save_filter);
* ``registry.describe()`` surfaces capability flags and ``repro.api``
  exports every documented name.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro import api
from repro.core import hashing as H

B, N = 4, 320


def _bank_keys(n=N, seed0=0):
    return jnp.asarray(np.stack([H.random_u64x2(n, seed=seed0 + b)
                                 for b in range(B)]))


def _scalar_ref_words(keys, variant="sbf", **kw):
    """B independent scalar jnp filters — the banked ops' oracle."""
    return jnp.stack([
        api.make_filter(variant, m_bits=1 << 14, k=8, backend="jnp", **kw)
        .add(keys[b]).dense_words() if variant != "countingbf"
        else api.make_filter(variant, m_bits=1 << 14, k=8).add(keys[b])
        .dense_words()
        for b in range(keys.shape[0])])


# ---------------------------------------------------------------------------
# Scalar-vs-banked bit-exactness across engines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas-vmem", "pallas-hbm"])
def test_bank_matches_scalar_filters(backend):
    keys = _bank_keys()
    ref = _scalar_ref_words(keys)
    fb = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8, backend=backend)
    assert fb.bank_shape == (B,) and fb.bank_size == B
    fb = fb.add(keys)
    np.testing.assert_array_equal(np.asarray(fb.dense_words()),
                                  np.asarray(ref), err_msg=backend)
    hits = fb.contains(keys)
    assert hits.shape == (B, N) and bool(np.asarray(hits).all())
    # a key inserted into member 0 only is found ONLY in member 0
    probe = keys[0][:1]
    per_member = np.asarray(
        fb.contains(jnp.broadcast_to(probe, (B, 1, 2))))[:, 0]
    assert per_member[0]
    # (other members may rarely FP; with these sizes they must not all hit)
    assert not per_member[1:].all()


def test_counting_bank_matches_scalar_filters():
    keys = _bank_keys(seed0=10)
    fb = api.make_filter_bank(B, "countingbf", m_bits=1 << 14, k=8)
    assert fb.backend == "counting"
    fb = fb.add(keys)
    ref = jnp.stack([api.make_filter("countingbf", m_bits=1 << 14, k=8)
                     .add(keys[b]).words for b in range(B)])
    np.testing.assert_array_equal(np.asarray(fb.words), np.asarray(ref))
    assert bool(np.asarray(fb.contains(keys)).all())
    # remove and decay apply member-wise
    gone = fb.remove(keys)
    assert not bool(np.asarray(gone.contains(keys)).any())
    assert not bool(np.asarray(fb.decay(1).contains(keys)).any())


# ---------------------------------------------------------------------------
# Routed (keys, tenant_ids) vs per-tenant loop parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas-vmem", "counting"])
def test_routed_matches_per_tenant_loop(backend):
    rng = np.random.RandomState(3)
    n = 500
    variant = "countingbf" if backend == "counting" else "sbf"
    keys = jnp.asarray(H.random_u64x2(n, seed=7))
    tenants = rng.randint(0, B, n)
    valid = (rng.rand(n) < 0.85).astype(np.uint8)
    kw = {} if backend == "counting" else {"backend": backend}
    fb = api.make_filter_bank(B, variant, m_bits=1 << 14, k=8, **kw)
    fr = fb.add(keys, tenants=tenants, valid=valid)
    # oracle: per-tenant python loop over scalar filters
    for b in range(B):
        sel = np.nonzero((tenants == b) & (valid == 1))[0]
        ref = fb.select(b).add(keys[sel])
        np.testing.assert_array_equal(
            np.asarray(fr.select(b).dense_words()),
            np.asarray(ref.dense_words()), err_msg=f"{backend} member {b}")
    # routed contains: each key consults only its tenant's member
    hits = np.asarray(fr.contains(keys, tenants=tenants))
    assert hits.shape == (n,)
    assert hits[valid == 1].all()


def test_route_scatter_utility():
    n = 100
    keys = H.random_u64x2(n, seed=9)
    tenants = np.random.RandomState(0).randint(0, B, n)
    kb, valid = api.route(keys, tenants, B)
    assert kb.shape == (B, n, 2) and valid.shape == (B, n)
    assert int(np.asarray(valid).sum()) == n           # nothing overflows
    counts = np.bincount(tenants, minlength=B)
    np.testing.assert_array_equal(np.asarray(valid).sum(axis=1), counts)


# ---------------------------------------------------------------------------
# Single-launch lowering (jaxpr) + vmap over the bank axis
# ---------------------------------------------------------------------------

def test_vmem_bank_is_single_pallas_launch():
    keys = _bank_keys()
    fb = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8,
                              backend="pallas-vmem")
    jc = str(jax.make_jaxpr(lambda f, k: f.contains(k))(fb, keys))
    assert jc.count("pallas_call") == 1, jc.count("pallas_call")
    ja = str(jax.make_jaxpr(lambda f, k: f.add(k))(fb, keys))
    assert ja.count("pallas_call") == 1
    # routed form too
    flat = keys.reshape(-1, 2)
    t = jnp.asarray(np.repeat(np.arange(B), N), jnp.int32)
    jr = str(jax.make_jaxpr(lambda f, k, tt: f.contains(k, tenants=tt)
                            )(fb, flat, t))
    assert jr.count("pallas_call") == 1


def test_bank_past_vmem_reports_the_xla_reference():
    """A bank too large for VMEM runs the jnp super-filter; the dispatch
    says so instead of swapping silently."""
    from repro.kernels import ops
    spec = api.make_filter("sbf", m_bits=1 << 20).spec        # 128 KiB each
    big, small = 64, 4                                        # 8 MiB, 512 KiB
    keys = jnp.asarray(H.random_u64x2(64, seed=1))
    member = jnp.arange(64, dtype=jnp.int32) % small
    before = ops.dispatched()
    ops.bloom_bank_add(spec, jnp.zeros((big, spec.n_words), jnp.uint32),
                       keys, member)
    ops.bloom_bank_add(spec, jnp.zeros((small, spec.n_words), jnp.uint32),
                       keys, member, probe="loop", mix="full")
    after = ops.dispatched()
    for impl in ("xla", "pallas-vmem"):
        key = ("bank_add", impl)
        assert after.get(key, 0) == before.get(key, 0) + 1, impl


def test_vmap_over_bank_axis():
    """vmap over the leading words dim sees scalar filters — no protocol."""
    keys = _bank_keys(seed0=20)
    fb = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8,
                              backend="jnp").add(keys)
    out = jax.vmap(lambda f, k: f.contains(k))(fb, keys)
    assert out.shape == (B, N) and bool(np.asarray(out).all())
    # vmapped add == banked add
    fb2 = jax.vmap(lambda f, k: f.add(k))(
        api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8, backend="jnp"),
        keys)
    np.testing.assert_array_equal(np.asarray(fb2.words),
                                  np.asarray(fb.words))


def test_bank_through_jit_and_scan():
    keys = _bank_keys(seed0=30)
    chunks = keys.reshape(B, 4, N // 4, 2).transpose(1, 0, 2, 3)  # (4,B,n,2)
    fb = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8, backend="jnp")

    def step(f, kchunk):
        return f.add(kchunk), kchunk.sum()

    f_scan, _ = jax.lax.scan(step, fb, chunks)
    f_bulk = fb.add(keys)
    np.testing.assert_array_equal(np.asarray(f_scan.words),
                                  np.asarray(f_bulk.words))


# ---------------------------------------------------------------------------
# Windowed: traced head, no retrace, banks
# ---------------------------------------------------------------------------

def test_advance_does_not_retrace_under_jit():
    """Satellite bugfix pin: the ring head is traced state, so jitted
    advance+add compiles ONCE across window slides (it used to retrace
    every advance when the head was static aux data)."""
    keys = _bank_keys(seed0=40)
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, generations=3)
    traces = []

    @jax.jit
    def step(filt, k):
        traces.append(1)
        return filt.advance().add(k)

    for i in range(5):
        f = step(f, keys[i % B])
    assert len(traces) == 1, f"advance retraced {len(traces)} times"
    # and the carry survives lax.scan (structure-invariant)
    def body(filt, k):
        return filt.advance().add(k), k.sum()
    f2, _ = jax.lax.scan(body, f, keys)
    assert int(f2.head) == (int(f.head) + B) % 3


def test_windowed_bank_advances_in_lockstep():
    gens = [_bank_keys(200, seed0=50 + 10 * g) for g in range(3)]
    fb = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8, generations=3)
    assert fb.backend == "windowed" and fb.head.shape == (B,)
    fb = fb.add(gens[0]).advance().add(gens[1]).advance().add(gens[2])
    for g in gens:
        assert bool(np.asarray(fb.contains(g)).all())
    fb = fb.advance()                               # retires gens[0]
    assert float(np.asarray(fb.contains(gens[0])).mean()) < 0.05
    assert bool(np.asarray(fb.contains(gens[1])).all())


# ---------------------------------------------------------------------------
# Bank structure ops: select / scatter_update / bank_merge
# ---------------------------------------------------------------------------

def test_select_scatter_update_bank_merge():
    keys = _bank_keys(seed0=60)
    fb = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8,
                              backend="jnp").add(keys)
    m0 = fb.select(0)
    assert m0.bank_shape == ()
    assert bool(np.asarray(m0.contains(keys[0])).all())
    empty = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp")
    wiped = fb.scatter_update(0, empty)
    assert not bool(np.asarray(wiped.select(0).contains(keys[0])).any())
    assert bool(np.asarray(wiped.select(1).contains(keys[1])).all())
    merged = wiped.bank_merge(fb)                   # member-wise union
    assert bool(np.asarray(merged.contains(keys)).all())
    with pytest.raises(ValueError):
        m0.select(0)                                # scalar has no bank
    with pytest.raises(ValueError):
        fb.bank_merge(m0)


def test_windowed_merge_keeps_no_false_negatives():
    """Regression pin: rings cannot be ORed slot-by-slot when heads
    differ (slot g is a different age class per ring). The merge lands
    the other window's union in MY head, so the merged-in keys survive
    at least G-1 further advances — never a false negative in-window."""
    ka, kb = _bank_keys(150, seed0=200), _bank_keys(150, seed0=210)
    a = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8, generations=3)
    a = a.add(ka)                                   # a's keys in gen 0
    b = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8, generations=3)
    b = b.advance().advance().add(kb)               # b's keys in gen 2
    m = a.bank_merge(b)
    m = m.advance().add(_bank_keys(10, seed0=220)) \
         .advance().add(_bank_keys(10, seed0=230))  # 2 slides, still in-window
    assert bool(np.asarray(m.contains(kb)).all())   # no early retirement
    # scalar windowed merge takes the same path
    sa = api.make_filter("sbf", m_bits=1 << 14, k=8, generations=3).add(ka[0])
    sb = api.make_filter("sbf", m_bits=1 << 14, k=8, generations=3) \
        .advance().advance().add(kb[0])
    sm = (sa | sb).advance().advance()
    assert bool(np.asarray(sm.contains(kb[0])).all())
    # CROSS-ENGINE merge into a windowed filter with a rotated head must
    # also land in the head (not generation 0, which the next advance
    # after a full rotation would retire)
    w = api.make_filter("sbf", m_bits=1 << 14, k=8, generations=3) \
        .advance().advance()                         # head = 2
    j = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp").add(kb[0])
    wm = w.merge(j).advance()                        # retires gen 0 only
    assert bool(np.asarray(wm.contains(kb[0])).all())


def test_scalar_valid_mask_is_rejected():
    """valid= is a bank-op contract; silently ignoring it on a scalar
    filter would insert (or worse, counting-remove) masked-off keys."""
    keys = _bank_keys(20, seed0=240)[0]
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp")
    with pytest.raises(ValueError):
        f.add(keys, valid=np.zeros(20, np.uint8))
    c = api.make_filter("countingbf", m_bits=1 << 14, k=8).add(keys)
    with pytest.raises(ValueError):
        c.remove(keys, valid=np.zeros(20, np.uint8))


def test_counting_bank_merge_is_counter_true():
    keys = _bank_keys(150, seed0=70)
    a = api.make_filter_bank(B, "countingbf", m_bits=1 << 14, k=8).add(keys)
    u = a.bank_merge(a)                             # counts double
    u = u.remove(keys)
    assert bool(np.asarray(u.contains(keys)).all())
    u = u.remove(keys)
    assert not bool(np.asarray(u.contains(keys)).any())


# ---------------------------------------------------------------------------
# Distributed banks
# ---------------------------------------------------------------------------

def test_sharded_bank_axis():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    keys = _bank_keys(seed0=80)
    ref = _scalar_ref_words(keys)
    fb = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8,
                              backend="sharded", mesh=mesh)
    fb = fb.add(keys)
    np.testing.assert_array_equal(np.asarray(fb.dense_words()),
                                  np.asarray(ref))
    flat = keys.reshape(-1, 2)
    t = np.repeat(np.arange(B), N)
    assert bool(np.asarray(fb.contains(flat, tenants=t)).all())


def test_replicated_declines_banks():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    with pytest.raises(ValueError):
        api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8,
                             backend="replicated", mesh=mesh)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_bank_state_roundtrip_cross_engine():
    keys = _bank_keys(seed0=90)
    fb = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8,
                              backend="pallas-vmem").add(keys)
    st = fb.to_state()
    assert st["bank_shape"] == [B]
    g = api.Filter.from_state(st, backend="jnp")
    assert g.backend == "jnp" and g.bank_shape == (B,)
    np.testing.assert_array_equal(np.asarray(g.dense_words()),
                                  np.asarray(fb.dense_words()))
    assert bool(np.asarray(g.contains(keys)).all())


def test_bank_save_restore_filter(tmp_path):
    from repro.checkpoint import checkpoint as ckpt
    keys = _bank_keys(seed0=95)
    fb = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8,
                              backend="jnp").add(keys)
    ckpt.save_filter(str(tmp_path), 7, fb)
    step, g = ckpt.restore_filter(str(tmp_path))
    assert step == 7 and g.bank_shape == (B,)
    np.testing.assert_array_equal(np.asarray(g.dense_words()),
                                  np.asarray(fb.dense_words()))
    assert bool(np.asarray(g.contains(keys)).all())


def test_bank_checkpoints_inline_as_pytree(tmp_path):
    from repro.checkpoint import checkpoint as ckpt
    keys = _bank_keys(seed0=97)
    fb = api.make_filter_bank(B, "sbf", m_bits=1 << 14, k=8,
                              backend="jnp").add(keys)
    state = {"step_count": jnp.int32(3), "guard_bank": fb}
    ckpt.save(str(tmp_path), 3, state)
    _, restored = ckpt.restore(str(tmp_path), state)
    rb = restored["guard_bank"]
    assert isinstance(rb, api.Filter) and rb.bank_shape == (B,)
    assert bool(np.asarray(rb.contains(keys)).all())


# ---------------------------------------------------------------------------
# Registry + export surface (satellite)
# ---------------------------------------------------------------------------

def test_describe_surfaces_capability_flags():
    descs = {d["name"]: d for d in api.describe_backends()}
    for name, d in descs.items():
        for flag in ("supports_remove", "supports_decay", "supports_advance",
                     "supports_bank"):
            assert flag in d, (name, flag)
    assert descs["counting"]["supports_remove"]
    assert descs["counting"]["supports_decay"]
    assert descs["counting"]["supports_bank"]
    assert descs["windowed"]["supports_advance"]
    assert descs["jnp"]["supports_bank"]
    assert descs["pallas-vmem"]["supports_bank"]
    assert descs["sharded"]["supports_bank"]
    assert not descs["replicated"]["supports_bank"]


def test_api_exports_are_importable():
    """Every name in __all__ resolves, and the documented bank symbols are
    reachable from repro.api."""
    for name in api.__all__:
        assert getattr(api, name, None) is not None, name
    for required in ("make_filter_bank", "route", "make_filter",
                     "filter_for_n_items", "union", "Filter", "FilterSpec",
                     "BackendOptions", "as_keys", "backends",
                     "describe_backends", "get_backend"):
        assert required in api.__all__, required


# ---------------------------------------------------------------------------
# Consumers: the guard has no host-side per-row loops
# ---------------------------------------------------------------------------

def test_ngram_guard_is_bank_native_and_loopless():
    from repro.serving import ngram_guard
    assert not hasattr(ngram_guard, "_mix_rows")   # host numpy row loop gone
    g = ngram_guard.NGramGuard(batch=B, n=3, m_bits=1 << 16, top_k=8)
    assert g.filt.bank_shape == (B,)       # one member per sequence
    rng = np.random.RandomState(1)
    for step in range(12):
        toks = rng.randint(0, 50, B)
        toks[0] = step % 3                 # sequence 0 loops
        g.penalize(jnp.asarray(rng.randn(B, 50).astype(np.float32)))
        g.observe(toks)
    out = np.asarray(g.penalize(jnp.zeros((B, 50), jnp.float32)))
    assert out[0].min() < -1e8             # the loop continuation is caught


def test_tenant_dedup_isolates_tenants():
    from repro.data.dedup import TenantDedupFilter
    rng = np.random.RandomState(2)
    docs = [rng.randint(0, 1000, 16) for _ in range(10)]
    td = TenantDedupFilter(n_tenants=B, expected_docs_per_tenant=1 << 10,
                           batch_docs=8)
    kept_t0 = td.dedupe_batch(docs, [0] * len(docs))
    assert len(kept_t0) == len(docs)
    # same docs under another tenant are NOT duplicates
    kept_t1 = td.dedupe_batch(docs, [1] * len(docs))
    assert len(kept_t1) == len(docs)
    # replay within a tenant is fully dropped
    assert td.dedupe_batch(docs, [0] * len(docs)) == []
