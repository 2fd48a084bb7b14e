"""Tests for the pytree-native ``repro.api`` surface.

Covers the acceptance contract of the API redesign:
* ``Filter`` is a registered pytree carried through ``jit`` and ``scan``
  without host round-trips;
* every registered engine is bit-identical to the ``"jnp"`` reference on a
  spec sweep (cross-backend parity);
* the ``"pallas"`` legacy alias still resolves (the class shims from PR 1
  are gone);
* the forgetting engines: ``counting`` (remove/decay) and ``windowed``
  (advance) honor their capability flags, and other engines refuse those
  ops with a clear error;
* engine-independent checkpointing via to_state/from_state and
  checkpoint.save_filter/restore_filter;
* FPR probes are structurally disjoint from insert keys.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro import api
from repro.core import hashing as H
from repro.core import variants as V

SPECS = [
    dict(variant="cbf", m_bits=1 << 16, k=8),
    dict(variant="bbf", m_bits=1 << 16, k=8, block_bits=256),
    dict(variant="rbbf", m_bits=1 << 16, k=4),
    dict(variant="sbf", m_bits=1 << 16, k=8, block_bits=256),
    dict(variant="sbf", m_bits=1 << 16, k=16, block_bits=512),
    dict(variant="csbf", m_bits=1 << 16, k=8, block_bits=512, z=2),
]


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))


def _keys(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


# ---------------------------------------------------------------------------
# Pytree contract
# ---------------------------------------------------------------------------

def test_filter_is_registered_pytree():
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp")
    leaves, treedef = jax.tree_util.tree_flatten(f)
    assert len(leaves) == 1 and leaves[0] is f.words
    f2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert f2.spec == f.spec and f2.backend == f.backend


def test_filter_through_jit():
    keys = _keys(500, seed=1)
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp")

    @jax.jit
    def insert_and_check(filt, ks):
        filt = filt.add(ks)
        return filt, filt.contains(ks)

    f2, hits = insert_and_check(f, keys)
    assert isinstance(f2, api.Filter)
    assert bool(np.asarray(hits).all())
    # immutability: the original filter is untouched
    assert f.fill_fraction() == 0.0 and f2.fill_fraction() > 0.0


def test_filter_through_scan():
    keys = _keys(1000, seed=2)
    chunks = keys.reshape(10, 100, 2)
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp")

    def step(filt, kchunk):
        return filt.add(kchunk), jnp.sum(kchunk)

    f_scan, _ = jax.lax.scan(step, f, chunks)
    f_bulk = f.add(keys)
    np.testing.assert_array_equal(np.asarray(f_scan.words),
                                  np.asarray(f_bulk.words))


def test_add_is_functional_not_in_place():
    keys = _keys(200, seed=3)
    f0 = api.make_filter("sbf", m_bits=1 << 14, k=8)
    f1 = f0.add(keys)
    assert not bool(np.asarray(f0.contains(keys)).any())
    assert bool(np.asarray(f1.contains(keys)).all())


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_lists_required_engines():
    names = api.backends()
    assert len(names) >= 4
    for required in ("jnp", "pallas-vmem", "pallas-hbm", "replicated",
                     "sharded", "counting", "windowed"):
        assert required in names
    descs = api.describe_backends()
    assert all(d["name"] for d in descs)


def test_auto_selection_prefers_jnp_off_tpu():
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="auto")
    if jax.default_backend() != "tpu":
        assert f.backend == "jnp"


def test_explicit_unsupported_backend_raises():
    # sharded without a mesh is unsupported
    with pytest.raises(ValueError):
        api.make_filter("sbf", m_bits=1 << 14, k=8, backend="sharded")
    with pytest.raises(KeyError):
        api.make_filter("sbf", m_bits=1 << 14, k=8, backend="no-such-engine")


@pytest.mark.parametrize("spec_kw", SPECS,
                         ids=lambda d: f"{d['variant']}-k{d['k']}")
def test_backend_parity_sweep(spec_kw):
    """Every registered engine == the jnp reference, bit for bit."""
    keys = _keys(800, seed=spec_kw["k"])
    probes = jnp.asarray(H.probe_u64x2(512, seed=5))
    ref = api.make_filter(backend="jnp", **spec_kw).add(keys)
    ref_words = np.asarray(ref.dense_words())
    ref_hits = np.asarray(ref.contains(probes))
    mesh = _mesh1()
    ctx_kw = {"mesh": mesh}
    for name in api.backends():
        if name == "jnp":
            continue
        eng = api.get_backend(name)
        kw = dict(spec_kw)
        if name in ("replicated", "sharded"):
            kw["mesh"] = mesh
        spec = V.FilterSpec(
            variant=kw["variant"], m_bits=kw["m_bits"], k=kw["k"],
            block_bits=kw.get("block_bits", 256), z=kw.get("z", 1))
        opts = api.BackendOptions(mesh=kw.get("mesh"))
        if not eng.supports(spec, opts.ctx()):
            continue   # e.g. sharded has no cbf locality
        f = api.make_filter(backend=name, **kw).add(keys)
        np.testing.assert_array_equal(np.asarray(f.dense_words()), ref_words,
                                      err_msg=f"words diverge on {name}")
        assert bool(np.asarray(f.contains(keys)).all()), name
        np.testing.assert_array_equal(np.asarray(f.contains(probes)),
                                      ref_hits,
                                      err_msg=f"probe hits diverge on {name}")


def test_union_cross_engine():
    k1, k2 = _keys(300, seed=7), _keys(300, seed=8)
    a = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp").add(k1)
    b = api.make_filter("sbf", m_bits=1 << 14, k=8,
                        backend="pallas-vmem").add(k2)
    u = api.union(a, b)
    assert bool(np.asarray(u.contains(k1)).all())
    assert bool(np.asarray(u.contains(k2)).all())
    both = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp"
                           ).add(k1).add(k2)
    np.testing.assert_array_equal(np.asarray(u.dense_words()),
                                  np.asarray(both.dense_words()))


def test_union_spec_mismatch_raises():
    a = api.make_filter("sbf", m_bits=1 << 14, k=8)
    b = api.make_filter("sbf", m_bits=1 << 15, k=8)
    with pytest.raises(ValueError):
        api.union(a, b)


def test_merge_operator():
    k1, k2 = _keys(100, seed=11), _keys(100, seed=12)
    a = api.make_filter("sbf", m_bits=1 << 14, k=8).add(k1)
    b = api.make_filter("sbf", m_bits=1 << 14, k=8).add(k2)
    u = a | b
    assert bool(np.asarray(u.contains(k1)).all())
    assert bool(np.asarray(u.contains(k2)).all())


# ---------------------------------------------------------------------------
# Introspection + sizing
# ---------------------------------------------------------------------------

def test_approx_count_tracks_inserts():
    n = 5000
    f = api.filter_for_n_items(1 << 14, bits_per_key=16).add(
        _keys(n, seed=13))
    assert 0.9 * n <= f.approx_count() <= 1.1 * n


def test_filter_for_n_items_sizing():
    f = api.filter_for_n_items(10_000, bits_per_key=16, variant="sbf")
    assert f.spec.m_bits >= 10_000 * 16
    f = f.add(H.random_u64x2(10_000, seed=8))
    assert f.measure_fpr() < 0.01


def test_bits_per_element():
    spec = V.FilterSpec("sbf", 1 << 16, 8, block_bits=256)
    assert spec.bits_per_element(1 << 12) == 16.0
    assert spec.bits_per_element(0) == float(spec.m_bits)  # guarded n=0


def test_space_optimal_n_target_fpr():
    spec = V.FilterSpec("cbf", 1 << 16, 8)
    n_opt = V.space_optimal_n(spec)
    assert n_opt == int(spec.m_bits * np.log(2) / spec.k)
    n_at = V.space_optimal_n(spec, target_fpr=1e-3)
    assert n_at > 0
    assert V.fpr_theory(spec, n_at) <= 1e-3 < V.fpr_theory(spec, n_at + 1)
    # an impossible target yields 0, not a bogus load
    assert V.space_optimal_n(spec, target_fpr=1e-40) == 0


def test_probe_keys_structurally_disjoint_from_inserts():
    ins = H.random_u64x2(1 << 14, seed=0)
    probes = H.probe_u64x2(1 << 14, seed=0)
    # reserved top bit: set on every probe, clear on every insert key
    assert (probes[:, 0] >> 31 == 1).all()
    assert (ins[:, 0] >> 31 == 0).all()
    ins_set = {bytes(r) for r in ins}
    assert not any(bytes(r) in ins_set for r in probes)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_state_roundtrip_cross_engine():
    keys = _keys(400, seed=21)
    f = api.make_filter("sbf", m_bits=1 << 14, k=8,
                        backend="pallas-vmem").add(keys)
    st = f.to_state()
    g = api.Filter.from_state(st, backend="jnp")
    assert g.backend == "jnp"
    np.testing.assert_array_equal(np.asarray(g.words),
                                  np.asarray(f.dense_words()))
    assert bool(np.asarray(g.contains(keys)).all())


def test_filter_checkpoints_inline_as_pytree(tmp_path):
    from repro.checkpoint import checkpoint as ckpt
    keys = _keys(300, seed=22)
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp").add(keys)
    state = {"step_count": jnp.int32(3), "dedup_filter": f}
    ckpt.save(str(tmp_path), 3, state)
    _, restored = ckpt.restore(str(tmp_path), state)
    rf = restored["dedup_filter"]
    assert isinstance(rf, api.Filter) and rf.spec == f.spec
    assert bool(np.asarray(rf.contains(keys)).all())


def test_save_filter_restore_filter(tmp_path):
    from repro.checkpoint import checkpoint as ckpt
    keys = _keys(300, seed=23)
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp").add(keys)
    ckpt.save_filter(str(tmp_path), 5, f)
    step, g = ckpt.restore_filter(str(tmp_path))
    assert step == 5 and g.spec == f.spec
    np.testing.assert_array_equal(np.asarray(g.dense_words()),
                                  np.asarray(f.dense_words()))
    # re-homing onto an explicit engine at restore
    _, h = ckpt.restore_filter(str(tmp_path), backend="pallas-vmem")
    assert h.backend == "pallas-vmem"
    assert bool(np.asarray(h.contains(keys)).all())


def _drop_layout(tmp_path):
    """Rewrite the newest manifest as a build without the layout field
    (hash layout 1) would have written it."""
    import json
    import os
    from repro.checkpoint import checkpoint as ckpt
    path = os.path.join(str(tmp_path),
                        f"step_{ckpt.latest_step(str(tmp_path)):08d}",
                        "manifest.json")
    with open(path) as fh:
        manifest = json.load(fh)
    del manifest["extra"]["filter_hash_layout"]
    with open(path, "w") as fh:
        json.dump(manifest, fh)


@pytest.mark.parametrize("path", ["restore_filter", "inline", "from_state"])
def test_state_from_another_hash_layout_is_refused(tmp_path, path):
    """Words written under the old block stream would answer 'absent' for
    keys they hold; every restore path refuses them instead."""
    from repro.checkpoint import checkpoint as ckpt
    from repro.core.hashing import HASH_LAYOUT
    f = api.make_filter("sbf", m_bits=1 << 14, k=8,
                        backend="jnp").add(_keys(100, seed=24))
    if path == "restore_filter":
        ckpt.save_filter(str(tmp_path), 1, f)
        assert ckpt.manifest_extra(str(tmp_path))["filter_hash_layout"] \
            == HASH_LAYOUT
        _drop_layout(tmp_path)
        restore = lambda: ckpt.restore_filter(str(tmp_path))
    elif path == "inline":
        state = {"dedup_filter": f}
        ckpt.save(str(tmp_path), 1, state)
        _drop_layout(tmp_path)
        restore = lambda: ckpt.restore(str(tmp_path), state)
    else:
        st = f.to_state()
        assert st["hash_layout"] == HASH_LAYOUT
        del st["hash_layout"]
        restore = lambda: api.Filter.from_state(st)
    with pytest.raises(ValueError, match="hash layout 1"):
        restore()


# ---------------------------------------------------------------------------
# Legacy spellings + shim removal
# ---------------------------------------------------------------------------

def test_pallas_alias_still_resolves():
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="pallas")
    assert f.backend in ("pallas-vmem", "pallas-hbm")


def test_class_shims_are_gone():
    """The one-release shims promised in PR 1 have been removed."""
    import repro.core as core
    import repro.core.distributed as dist
    assert not hasattr(core, "BloomFilter")
    assert not hasattr(dist, "ReplicatedFilter")
    assert not hasattr(dist, "ShardedFilter")
    with pytest.raises(ImportError):
        from repro.core.filter import BloomFilter  # noqa: F401


def test_dedupfilter_uses_api_filter():
    from repro.data.dedup import DedupFilter
    dd = DedupFilter(expected_docs=1 << 12, backend="jnp", batch_docs=32)
    assert isinstance(dd.filt, api.Filter)


# ---------------------------------------------------------------------------
# Forgetting engines: counting (remove/decay) + windowed (advance)
# ---------------------------------------------------------------------------

def test_counting_engine_remove_decay():
    keys = _keys(400, seed=41)
    f = api.make_filter("countingbf", m_bits=1 << 14, k=8)
    assert f.backend == "counting"
    assert f.words.shape == (4 * f.spec.n_words,)    # 4-bit counters
    f = f.add(keys)
    assert bool(np.asarray(f.contains(keys)).all())
    g = f.remove(keys)
    assert not bool(np.asarray(g.contains(keys)).any())
    # decay of a twice-added set needs two steps
    f2 = f.add(keys)
    assert bool(np.asarray(f2.decay(1).contains(keys)).all())
    assert not bool(np.asarray(f2.decay(2).contains(keys)).any())


def test_counting_merge_preserves_counts():
    keys = _keys(200, seed=42)
    a = api.make_filter("countingbf", m_bits=1 << 14, k=8).add(keys)
    b = api.make_filter("countingbf", m_bits=1 << 14, k=8).add(keys)
    u = api.union(a, b)                       # counter-true union: counts add
    u = u.remove(keys)
    assert bool(np.asarray(u.contains(keys)).all())
    u = u.remove(keys)
    assert not bool(np.asarray(u.contains(keys)).any())


def test_windowed_engine_advance():
    gens = [_keys(200, seed=50 + g) for g in range(3)]
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, generations=3)
    assert f.backend == "windowed"
    f = f.add(gens[0]).advance().add(gens[1]).advance().add(gens[2])
    for g in gens:
        assert bool(np.asarray(f.contains(g)).all())   # whole window live
    f = f.advance()                                    # retires gens[0]
    assert float(np.asarray(f.contains(gens[0])).mean()) < 0.05
    assert bool(np.asarray(f.contains(gens[1])).all())
    assert bool(np.asarray(f.contains(gens[2])).all())


def test_capability_flags_enforced():
    plain = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp")
    keys = _keys(10, seed=60)
    with pytest.raises(NotImplementedError):
        plain.remove(keys)
    with pytest.raises(NotImplementedError):
        plain.decay()
    with pytest.raises(NotImplementedError):
        plain.advance()
    counting = api.make_filter("countingbf", m_bits=1 << 14, k=8)
    with pytest.raises(NotImplementedError):
        counting.advance()
    descs = {d["name"]: d for d in api.describe_backends()}
    assert descs["counting"]["supports_remove"]
    assert descs["counting"]["supports_decay"]
    assert descs["windowed"]["supports_advance"]
    assert not descs["jnp"]["supports_remove"]


def test_windowed_state_roundtrip():
    """to_state records the ring geometry; the default from_state re-selects
    the windowed engine, and an explicit backend re-homes the dense union."""
    gens = [_keys(150, seed=70 + g) for g in range(2)]
    f = api.make_filter("sbf", m_bits=1 << 14, k=8, generations=3)
    f = f.add(gens[0]).advance().add(gens[1])
    assert int(f.head) == 1                      # head is traced state now
    st = f.to_state()
    g = api.Filter.from_state(st)
    assert g.backend == "windowed"
    assert g.options.generations == 3 and g.head is not None
    for k in gens:
        assert bool(np.asarray(g.contains(k)).all())
    g.advance()                                  # still a working window
    h = api.Filter.from_state(st, backend="jnp")  # re-home the union
    assert h.backend == "jnp"
    for k in gens:
        assert bool(np.asarray(h.contains(k)).all())


def test_nbytes_reflects_actual_storage():
    plain = api.make_filter("sbf", m_bits=1 << 14, k=8, backend="jnp")
    assert plain.nbytes == (1 << 14) // 8
    counting = api.make_filter("countingbf", m_bits=1 << 14, k=8)
    assert counting.nbytes == 4 * (1 << 14) // 8          # 4-bit counters
    windowed = api.make_filter("sbf", m_bits=1 << 14, k=8, generations=3)
    assert windowed.nbytes == 3 * (1 << 14) // 8          # G generations


def test_counting_state_roundtrip_membership():
    keys = _keys(300, seed=43)
    f = api.make_filter("countingbf", m_bits=1 << 14, k=8).add(keys)
    st = f.to_state()
    # canonical state is the occupancy bit view; restoring re-homes it into
    # the counting engine (counters at 1 — membership kept, counts lossy)
    g = api.Filter.from_state(st)
    assert g.backend == "counting"
    assert bool(np.asarray(g.contains(keys)).all())
    assert not bool(np.asarray(g.remove(keys).contains(keys)).any())
