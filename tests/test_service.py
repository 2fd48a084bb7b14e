"""Filter service: batching, admission, maintenance, resharding, recovery.

The tentpole invariants:
* fixed-shape flushes are *transparent* — a streamed workload produces the
  same filter words as one direct routed bulk add (OR idempotence makes
  the sbf comparison exact);
* admission shedding is deterministic and counted by reason;
* checkpoint/restore/replay around an injected failure is **bit-exact**
  with an uninterrupted twin run, for both a Bloom-family engine and the
  stateful cuckoo engine (DESIGN.md §14).
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro import api
from repro.runtime.fault_tolerance import SimulatedFailure
from repro.service import (AdmissionPolicy, FilterService, MaintenanceConfig,
                           MaintenanceLoop, ServiceConfig, ServiceDriver,
                           ServiceDriverConfig, grow_bank, grow_capacity,
                           reshard_service, restore_service)

T = 4


def _bank(variant="sbf", bank=T, **kw):
    kw.setdefault("m_bits", 1 << 13)
    if variant != "sbf":
        kw["variant"] = variant
    return api.make_filter_bank(bank, **kw)


def _requests(n, seed=0, n_tenants=T):
    rng = np.random.RandomState(seed)
    keys = rng.randint(0, 2 ** 32, (n, 2)).astype(np.uint32)
    tenants = rng.randint(0, n_tenants, n).astype(np.int64)
    return keys, tenants


def _count(svc, name):
    return svc.telemetry.registry.counter(f"service.{name}").value


# -- batching front end -------------------------------------------------------

def test_size_trigger_flushes_inline():
    svc = FilterService(_bank(), ServiceConfig(max_batch=16,
                                               flush_deadline=None))
    keys, tenants = _requests(40)
    svc.submit_many("add", keys, tenants)
    # 40 submitted at max_batch=16 -> two size flushes fired inline
    assert _count(svc, "size_flushes") == 2
    assert svc.pending_total == 8
    svc.drain()
    assert svc.pending_total == 0
    assert _count(svc, "flushed_ops") == 40


def test_deadline_trigger_via_pump():
    clock = {"t": 0.0}
    svc = FilterService(_bank(), ServiceConfig(max_batch=64,
                                               flush_deadline=1.0),
                        clock=lambda: clock["t"])
    keys, tenants = _requests(10)
    svc.submit_many("add", keys, tenants)
    assert svc.pump() == 0            # deadline not reached
    clock["t"] = 2.0
    assert svc.pump() == 1            # aged past deadline -> flushed
    assert _count(svc, "deadline_flushes") == 1
    assert svc.pending_total == 0


def test_streamed_equals_bulk_sbf():
    """Pad-to-tile + valid masks + FIFO chunking must be invisible: the
    streamed filter's words equal one direct routed bulk add."""
    keys, tenants = _requests(150, seed=3)
    svc = FilterService(_bank(), ServiceConfig(max_batch=32))
    for i in range(0, 150, 7):       # ragged bursts
        svc.submit_many("add", keys[i:i + 7], tenants[i:i + 7])
    svc.drain()
    direct = _bank().add(jnp.asarray(keys), tenants=jnp.asarray(tenants))
    assert jnp.array_equal(svc.filt.words, direct.words)


def test_contains_tickets_and_padding():
    svc = FilterService(_bank(), ServiceConfig(max_batch=32))
    keys, tenants = _requests(20, seed=4)
    svc.submit_many("add", keys, tenants)
    svc.drain()
    seqs = svc.submit_many("contains", keys, tenants)
    other_k, other_t = _requests(20, seed=99)
    neg = svc.submit_many("contains", other_k, other_t)
    svc.drain()
    res = svc.take_results()
    assert all(res[s] for s in seqs)             # no false negatives
    assert svc.take_results() == {}              # tickets are consumed
    assert len(res) == len(seqs) + len(neg)      # padding produced none


def test_remove_requires_capable_engine():
    svc = FilterService(_bank())
    with pytest.raises(NotImplementedError):
        svc.submit("remove", np.ones((1, 2), np.uint32))


def test_counting_remove_roundtrip():
    svc = FilterService(_bank("countingbf"), ServiceConfig(max_batch=16))
    keys, tenants = _requests(12, seed=5)
    svc.submit_many("add", keys, tenants)
    svc.submit_many("remove", keys[:6], tenants[:6])
    svc.drain()
    seqs = svc.submit_many("contains", keys, tenants)
    svc.drain()
    res = svc.take_results()
    hits = [res[s] for s in seqs]
    assert not any(hits[:6]) and all(hits[6:])


def test_tenant_validation():
    svc = FilterService(_bank())
    with pytest.raises(ValueError):
        svc.submit("add", np.ones((1, 2), np.uint32), tenant=T)


# -- admission ----------------------------------------------------------------

def test_queue_bound_sheds_excess():
    svc = FilterService(_bank(), ServiceConfig(
        max_batch=1 << 10, flush_deadline=None,
        admission=AdmissionPolicy(queue_limit=50)))
    keys, tenants = _requests(80, seed=6)
    seqs = svc.submit_many("add", keys, tenants)
    assert (seqs >= 0).sum() == 50
    assert (seqs < 0).sum() == 30
    assert svc.admission.shed_counts["queue"] == 30
    assert svc.health()["admission.shed_rate"] == pytest.approx(30 / 80)


def test_tenant_quota_sheds_hot_tenant():
    svc = FilterService(_bank(), ServiceConfig(
        max_batch=1 << 10, flush_deadline=None,
        admission=AdmissionPolicy(tenant_quota=5)))
    keys = np.ones((20, 2), np.uint32)
    seqs = svc.submit_many("add", keys, np.zeros(20, np.int64))
    assert (seqs >= 0).sum() == 5    # hot tenant capped at quota
    cold = svc.submit_many("add", keys[:3], np.full(3, 1))
    assert (cold >= 0).all()         # other tenants unaffected


def test_health_sheds_adds_not_reads_bloom():
    svc = FilterService(_bank(), ServiceConfig(
        max_batch=16, admission=AdmissionPolicy(shed_fill=0.0,
                                                health_every=1)))
    keys, tenants = _requests(16, seed=7)
    svc.submit_many("add", keys, tenants)   # flush -> refresh: all unhealthy
    assert svc.admission.unhealthy.all()
    s_add = svc.submit_many("add", keys, tenants)
    s_read = svc.submit_many("contains", keys, tenants)
    assert (s_add < 0).all()                 # adds shed...
    assert (s_read >= 0).all()               # ...reads never
    assert svc.admission.shed_counts["health"] == 16


def test_health_sheds_on_cuckoo_insert_failures():
    # a tiny cuckoo bank driven far past capacity records insert_failures;
    # the next health refresh must flag those members
    svc = FilterService(_bank("cuckoo", m_bits=1 << 8), ServiceConfig(
        max_batch=64, admission=AdmissionPolicy(health_every=1)))
    keys, tenants = _requests(512, seed=8)
    svc.submit_many("add", keys, tenants)
    svc.drain()
    assert int(np.asarray(svc.filt.state).sum()) > 0   # overload happened
    assert svc.admission.unhealthy.any()
    blocked = svc.submit_many("add", keys[:8], tenants[:8])
    assert (blocked < 0).any()


# -- maintenance --------------------------------------------------------------

def test_maintenance_advance_and_decay_cadence():
    svc = FilterService(_bank(generations=4), ServiceConfig(max_batch=16))
    maint = MaintenanceLoop(MaintenanceConfig(advance_every=2))
    for step in range(6):
        maint.tick(svc, step + 1)
    assert sum(1 for e in maint.events if e["kind"] == "advance") == 3

    svc = FilterService(_bank("countingbf"), ServiceConfig(max_batch=16))
    maint = MaintenanceLoop(MaintenanceConfig(decay_every=3))
    keys, tenants = _requests(8, seed=9)
    svc.submit_many("add", keys, tenants)
    for step in range(3):
        maint.tick(svc, step + 1)
    svc.drain()
    seqs = svc.submit_many("contains", keys, tenants)
    svc.drain()
    res = svc.take_results()
    assert not any(res[s] for s in seqs)   # one decay aged out single adds


def test_checkpoint_is_flush_barrier(tmp_path):
    svc = FilterService(_bank(), ServiceConfig(max_batch=1 << 10,
                                               flush_deadline=None))
    maint = MaintenanceLoop(MaintenanceConfig(
        checkpoint_every=1, ckpt_dir=str(tmp_path), async_checkpoint=False))
    keys, tenants = _requests(10, seed=10)
    svc.submit_many("add", keys, tenants)
    assert svc.pending_total == 10
    maint.tick(svc, 1)                      # checkpoint -> drains first
    assert svc.pending_total == 0
    # restore round-trips words + cursor
    svc2 = FilterService(_bank(), ServiceConfig(max_batch=1 << 10,
                                                flush_deadline=None))
    step = restore_service(svc2, None, str(tmp_path))
    assert step == 1
    assert jnp.array_equal(svc2.filt.words, svc.filt.words)
    assert svc2._seq == svc._seq


def test_snapshot_refuses_non_barrier():
    svc = FilterService(_bank(), ServiceConfig(max_batch=1 << 10,
                                               flush_deadline=None))
    svc.submit("add", np.ones((1, 2), np.uint32))
    with pytest.raises(RuntimeError):
        svc.snapshot_state()


# -- resharding ---------------------------------------------------------------

def test_grow_bank_preserves_members():
    filt = _bank()
    keys, tenants = _requests(40, seed=11)
    filt = filt.add(jnp.asarray(keys), tenants=jnp.asarray(tenants))
    grown = grow_bank(filt, 7)
    assert grown.bank_shape == (7,)
    assert jnp.array_equal(grown.words[:T], filt.words)
    assert not grown.words[T:].any()
    hits = grown.contains(jnp.asarray(keys), tenants=jnp.asarray(tenants))
    assert bool(np.asarray(hits).all())
    with pytest.raises(ValueError):
        grow_bank(filt, 2)              # shrink refused


def test_grow_bank_carries_cuckoo_state():
    filt = _bank("cuckoo", m_bits=1 << 8)
    keys, tenants = _requests(300, seed=12)
    filt = filt.add(jnp.asarray(keys), tenants=jnp.asarray(tenants))
    grown = grow_bank(filt, 6)
    assert jnp.array_equal(grown.state[:T], filt.state)
    assert not grown.state[T:].any()


def test_reshard_service_live():
    svc = FilterService(_bank(), ServiceConfig(max_batch=1 << 10,
                                               flush_deadline=None))
    keys, tenants = _requests(30, seed=13)
    svc.submit_many("add", keys, tenants)       # left pending on purpose
    svc.admission.unhealthy[1] = True
    reshard_service(svc, bank=8)
    assert svc.pending_total == 0               # drained at the barrier
    assert svc.n_tenants == 8
    assert svc.admission.unhealthy[1] and not svc.admission.unhealthy[7]
    # new tenants are servable immediately
    s = svc.submit_many("add", keys[:4], np.full(4, 7))
    assert (s >= 0).all()
    svc.drain()
    hits = svc.filt.contains(jnp.asarray(keys), tenants=jnp.asarray(tenants))
    assert bool(np.asarray(hits).all())


def test_grow_capacity_resizes_saturating_quotient_in_place():
    """Acceptance: a quotient bank streamed past its load ceiling grows in
    place via the maintenance resize tick — zero shed adds, zero insert
    failures, and the grown bank is bit-identical to a from-scratch build
    at the final geometry (the resize re-homed every fingerprint)."""
    fb = api.filter_for_n_items(100, variant="quotient", target_fpr=1e-3,
                                bank=T)
    svc = FilterService(fb, ServiceConfig(
        max_batch=16, flush_deadline=None,
        admission=AdmissionPolicy(health_every=1)))
    maint = MaintenanceLoop(MaintenanceConfig(resize_every=1))
    m0 = svc.filt.spec.m_bits
    keys, tenants = _requests(600, seed=14)    # ~150/tenant >> 0.8 ceiling
    shed = 0
    for step, i in enumerate(range(0, 600, 16)):
        seqs = svc.submit_many("add", keys[i:i + 16], tenants[i:i + 16])
        shed += int((np.asarray(seqs) < 0).sum())
        svc.drain()
        maint.tick(svc, step + 1)
    assert shed == 0                                    # nothing health-shed
    assert int(np.asarray(svc.filt.state).sum()) == 0   # nothing dropped
    assert svc.filt.spec.m_bits > m0
    resizes = [e for e in maint.events if e["kind"] == "resize"]
    assert resizes and all(e["load"] >= 0.80 for e in resizes)
    assert not svc.admission.unhealthy.any()     # refreshed post-resize
    # every pre- and post-resize add is present at the new geometry
    hits = svc.filt.contains(jnp.asarray(keys), tenants=jnp.asarray(tenants))
    assert bool(np.asarray(hits).all())
    # bit-exact losslessness: identical words to a from-scratch build
    spec = svc.filt.spec
    ref = api.make_filter_bank(
        T, variant="quotient", m_bits=spec.m_bits, slot_bits=spec.slot_bits,
        r_bits=spec.r_bits).add(jnp.asarray(keys),
                                tenants=jnp.asarray(tenants))
    assert jnp.array_equal(ref.words, svc.filt.words)


def test_grow_capacity_requires_resizable_engine():
    svc = FilterService(_bank(), ServiceConfig(max_batch=16))
    with pytest.raises(ValueError, match="resize"):
        grow_capacity(svc)
    maint = MaintenanceLoop(MaintenanceConfig(resize_every=1))
    with pytest.raises(ValueError, match="resize"):
        maint.tick(svc, 1)


# -- recovery (the acceptance invariant) --------------------------------------

def _stream(seed):
    def stream_fn(step):
        rng = np.random.RandomState(seed * 7919 + step)
        out = []
        for i in range(2):
            keys = rng.randint(0, 2 ** 32, (18, 2)).astype(np.uint32)
            tenants = rng.randint(0, T, 18)
            out.append((("add", "contains")[i % 2], keys, tenants))
        return out
    return stream_fn


def _driver_run(variant, tmpdir, fail_at=None, steps=9):
    kw = {"m_bits": 1 << 9} if variant == "cuckoo" else {}
    svc = FilterService(_bank(variant, **kw),
                        ServiceConfig(max_batch=32, flush_deadline=2.5))
    maint = MaintenanceLoop(MaintenanceConfig(checkpoint_every=3,
                                              ckpt_dir=str(tmpdir)))
    fired = []

    def hook(step):
        if fail_at is not None and step == fail_at and not fired:
            fired.append(step)
            raise SimulatedFailure("injected")

    drv = ServiceDriver(svc, _stream(42), maint,
                        ServiceDriverConfig(virtual_dt=1.0),
                        failure_hook=hook)
    return drv.run(steps), drv


@pytest.mark.parametrize("variant", ["sbf", "cuckoo"])
def test_recovery_bit_exact(variant, tmp_path):
    clean, drv_clean = _driver_run(variant, tmp_path / "clean")
    failed, drv = _driver_run(variant, tmp_path / "failed", fail_at=7)
    kinds = [e["kind"] for e in drv.events]
    assert kinds.count("failure") == 1 and "restore" in kinds
    assert jnp.array_equal(clean.words, failed.words)
    if clean.state is not None:
        assert jnp.array_equal(clean.state, failed.state)
    assert len(drv.recovery_times) == 1 and drv.recovery_times[0] > 0
    # §17: deterministic telemetry (counters, virtual-clock latency
    # histograms) replays bit-exactly alongside the filter words; the
    # wall-clock report metrics (drift gauges, service.restores) are
    # excluded by the deterministic_only view
    reg_c = drv_clean.service.telemetry.registry
    reg_f = drv.service.telemetry.registry
    assert (reg_c.snapshot_state(deterministic_only=True)
            == reg_f.snapshot_state(deterministic_only=True))
    assert reg_f.counter("service.restores",
                         deterministic=False).value == 1


def test_driver_max_restarts(tmp_path):
    def hook(step):
        raise SimulatedFailure("always")

    svc = FilterService(_bank(), ServiceConfig(max_batch=32,
                                               flush_deadline=2.5))
    maint = MaintenanceLoop(MaintenanceConfig(checkpoint_every=2,
                                              ckpt_dir=str(tmp_path)))
    drv = ServiceDriver(svc, _stream(1), maint,
                        ServiceDriverConfig(max_restarts=2),
                        failure_hook=hook)
    with pytest.raises(SimulatedFailure):
        drv.run(5)
    assert sum(1 for e in drv.events if e["kind"] == "failure") == 3


# -- health surface (satellite) -----------------------------------------------

def test_filter_health_keys():
    h = _bank().health()
    assert h["variant"] == "sbf" and "fill_fraction" in h
    assert h["bank_shape"] == [T]

    h = _bank("cuckoo", m_bits=1 << 8).health()
    assert "load_factor" in h and h["insert_failures"] == 0
    assert "fill_fraction" not in h

    h = _bank(generations=3).health()
    assert h["generations"] == 3 and h["head"] == [0] * T


def test_service_health_is_namespaced():
    """The §17 fix for the key-collision hazard: filter health and service
    counters live in disjoint namespaces of one flat dict."""
    svc = FilterService(_bank(), ServiceConfig(max_batch=16))
    keys, tenants = _requests(16, seed=14)
    svc.submit_many("add", keys, tenants)
    h = svc.health()
    for k in ("filter.fill_fraction", "service.flushes",
              "admission.shed_rate", "service.pending",
              "admission.admitted"):
        assert k in h
    # no raw (un-namespaced) keys survive — the collision class is gone
    assert "fill_fraction" not in h and "flushes" not in h
    # every key carries exactly one namespace prefix
    assert all("." in k for k in h)


def test_flush_spans_carry_perfmodel_prediction():
    svc = FilterService(_bank(), ServiceConfig(max_batch=16))
    keys, tenants = _requests(32, seed=15)
    svc.submit_many("add", keys, tenants)
    svc.drain()
    flushes = svc.telemetry.tracer.spans("service.flush")
    assert flushes
    for sp in flushes:
        assert sp["predicted_us"] > 0 and sp["ceiling_us"] > 0
        assert sp["regime"] in ("vmem", "hbm")
    # children nest under the flush span (ids are deterministic)
    kids = [s for s in svc.telemetry.tracer.spans()
            if s["name"].startswith("service.flush.")]
    flush_ids = {s["span"] for s in flushes}
    assert kids and all(k["parent"] in flush_ids for k in kids)


def test_flush_span_carries_queue_wait():
    """``wait_s`` sums, over the requests a flush takes, the flush's start
    minus their enqueue on the service clock; ``wait_max_s`` is the
    head's. Under a virtual clock both are exact."""
    clock = {"t": 0.0}
    svc = FilterService(_bank(), ServiceConfig(max_batch=16,
                                               flush_deadline=None),
                        clock=lambda: clock["t"])
    keys, tenants = _requests(20, seed=17)
    svc.submit_many("add", keys[:10], tenants[:10])
    clock["t"] = 2.0
    svc.submit_many("add", keys[10:], tenants[10:])   # size flush of 16
    clock["t"] = 5.0
    svc.drain()                                       # the 4 left
    first, second = svc.telemetry.tracer.spans("service.flush")
    assert (first["take"], first["wait_s"], first["wait_max_s"]) == (
        16, 10 * 2.0, 2.0)
    assert (second["take"], second["wait_s"], second["wait_max_s"]) == (
        4, 4 * 3.0, 3.0)


def test_flush_spans_the_transfer_and_the_admission_refresh():
    """Each flush has one ``service.flush.h2d`` child; ``admission.refresh``
    runs under every ``health_every``-th flush and no other."""
    svc = FilterService(_bank(), ServiceConfig(
        max_batch=4, flush_deadline=None,
        admission=AdmissionPolicy(health_every=3)))
    keys, tenants = _requests(4 * 7, seed=18)
    svc.submit_many("add", keys, tenants)             # 7 size flushes
    tr = svc.telemetry.tracer
    nth = {f["span"]: i + 1 for i, f in enumerate(
        sorted(tr.spans("service.flush"), key=lambda f: f["span"]))}
    assert len(nth) == 7
    assert sorted(nth[s["parent"]] for s in tr.spans("service.flush.h2d")
                  ) == list(range(1, 8))
    assert sorted(nth[s["parent"]] for s in tr.spans("admission.refresh")
                  ) == [3, 6]


def test_per_tenant_shed_counters():
    svc = FilterService(_bank(), ServiceConfig(
        max_batch=1 << 10, flush_deadline=None,
        admission=AdmissionPolicy(tenant_quota=5)))
    keys = np.ones((20, 2), np.uint32)
    svc.submit_many("add", keys, np.zeros(20, np.int64))    # tenant 0 hot
    svc.submit_many("add", keys[:3], np.full(3, 1))         # tenant 1 cold
    assert svc.admission.shed_by_tenant[0].sum() == 15
    assert svc.admission.shed_by_tenant[1].sum() == 0
    c = svc.telemetry.registry.counter("admission.shed",
                                       reason="quota", tenant=0)
    assert c.value == 15
    assert c.key == "admission.shed{reason=quota,tenant=0}"


def test_counter_continuity_across_snapshot_restore():
    svc = FilterService(_bank(), ServiceConfig(max_batch=16,
                                               flush_deadline=None))
    keys, tenants = _requests(48, seed=16)
    svc.submit_many("add", keys, tenants)
    svc.drain()
    state = svc.snapshot_state()
    assert "counters" not in state                # the registry carries them
    svc2 = FilterService(_bank(), ServiceConfig(max_batch=16,
                                                flush_deadline=None))
    svc2.restore_state(svc.filt, state)
    assert (svc2.telemetry.registry.snapshot_state()
            == svc.telemetry.registry.snapshot_state())
    # restored counters keep counting from the restored totals
    svc2.submit_many("add", keys[:16], tenants[:16])
    svc2.drain()
    assert _count(svc2, "flushed_ops") == _count(svc, "flushed_ops") + 16


def test_pre_telemetry_checkpoint_restores():
    """A checkpoint written before the telemetry registry holds only the
    flat ``counters``; they restore into the registry's counters."""
    svc = FilterService(_bank(), ServiceConfig(max_batch=16,
                                               flush_deadline=None))
    keys, tenants = _requests(32, seed=19)
    svc.submit_many("add", keys, tenants)
    state = svc.snapshot_state()
    old = {"seq": state["seq"], "admission": state["admission"],
           "counters": {name: _count(svc, name) for name in (
               "submitted", "flushes", "size_flushes", "deadline_flushes",
               "flushed_ops", "padded_slots")}}
    svc2 = FilterService(_bank(), ServiceConfig(max_batch=16,
                                                flush_deadline=None))
    svc2.restore_state(svc.filt, old)
    assert {n: _count(svc2, n) for n in old["counters"]} == old["counters"]
    assert _count(svc2, "flushes") == 2
    seqs = svc2.submit_many("add", keys[:16], tenants[:16])
    assert seqs[0] == 32                          # the restored cursor
    assert _count(svc2, "flushes") == 3


def test_counter_continuity_across_reshard_and_grow():
    svc = FilterService(_bank(), ServiceConfig(
        max_batch=1 << 10, flush_deadline=None,
        admission=AdmissionPolicy(tenant_quota=5)))
    keys = np.ones((20, 2), np.uint32)
    svc.submit_many("add", keys, np.zeros(20, np.int64))
    shed_before = svc.admission.shed_by_tenant.copy()
    flushes_before = _count(svc, "flushes")
    reshard_service(svc, bank=8)
    assert svc.admission.shed_by_tenant.shape == (8, 3)
    assert (svc.admission.shed_by_tenant[:T] == shed_before).all()
    # the registry is shared across the reshard: counters are continuous
    assert svc.telemetry.registry.counter(
        "admission.shed", reason="quota", tenant=0).value == 15
    assert _count(svc, "flushes") >= flushes_before
    assert svc.telemetry.registry.counter(
        "resharding.reshards").value == 1
