"""Bring-up smoke run of the filter library and service on a TPU.

    python chip_smoke.py [--seed N]           # one chip: phases a-e
    python chip_smoke.py --four-chips         # four chips: the sharded engine

Phases (one process, keys made on the device from ``--seed``):

  a. device: the first device must be a TPU; there is no CPU fallback.
  b. HBM regime: a 1 GiB ``sbf`` filter through ``make_filter`` (``auto``
     must pick ``pallas-hbm``), 16 adds of 2^22 keys, every key present,
     words equal to the ``jnp`` engine's filter built from the same keys.
  c. VMEM regime: a 4 MiB filter, 2^21 keys (16 bits/key), the same
     exactness checks, and the false-positive rate on 2^21 never-inserted
     probe keys within 1.15x of ``variants.fpr_sbf``.
  d. service: ``FilterService`` over a 16-tenant bank of 1 Mbit filters,
     routed adds then routed lookups, answers and words equal to a
     ``jnp``-engine bank fed the same stream.
  e. counting: the ``counting`` engine's kernels at the VMEM edge (4 MiB
     of counters) and in HBM (128 MiB), adds with repeats, then removes;
     counters and answers equal to the jnp reference (``core.variants``)
     run on the chip over the same keys.
  --four-chips: a 16 GiB ``sharded`` filter over four chips (4 GiB each),
     2^24 keys added and looked up, each device's shard equal to a jnp
     reference built on that device from the keys its block range owns;
     the adds and lookups must run the segments' Pallas row kernels.

Each phase prints one line: engine, implementation, compile seconds, one
warm wall time (informational only) and the device's peak bytes in use.
The last line is the JSON verdict; any failed phase exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


class _CompileClock:
    """Seconds spent in backend compiles (persistent-cache hits included,
    which is what makes a warm second run cheaper)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0

        def listen(name, secs, **_):
            if name == self.EVENT:
                self.total += secs

        jax.monitoring.register_event_duration_secs_listener(listen)

    def lap(self, since: float) -> float:
        return round(self.total - since, 3)


def _peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def _report(phase: str, **fields) -> None:
    print(f"phase {phase}: " + json.dumps(fields, sort_keys=True), flush=True)


def _check(cond, what: str) -> None:
    if not bool(cond):
        raise AssertionError(what)


def _key_batch(seed: int, i: int, n: int):
    """(n, 2) uint32 keys made on the device. The top bit of the high word
    is cleared: that half of the key space is ``probe_u64x2``'s, so probe
    keys are never inserted ones."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(k):
        bits = jax.random.bits(k, (n, 2), jnp.uint32)
        return bits.at[:, 0].set(bits[:, 0] & jnp.uint32(0x7FFFFFFF))

    return make(jax.random.fold_in(jax.random.key(seed), i))


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def phase_hbm(seed: int, clock: _CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.kernels import ops

    c0, d0 = clock.total, ops.dispatched()
    f = api.make_filter("sbf", m_bits=1 << 33, k=8)
    _check(f.backend == "pallas-hbm",
           f"auto chose {f.backend!r} for a 1 GiB filter, not pallas-hbm")
    ref = api.make_filter("sbf", m_bits=1 << 33, k=8, backend="jnp")
    n, batches = 1 << 22, 16
    warm = None
    for i in range(batches):
        keys = _key_batch(seed, i, n)
        f, dt = _timed(lambda: f.add(keys))
        if i == 1:
            warm = dt
        ref = ref.add(keys)
    for i in range(batches):
        _check(jnp.all(f.contains(_key_batch(seed, i, n))),
               f"batch {i}: an inserted key tests absent")
    _check(jnp.array_equal(f.words, ref.words),
           "pallas-hbm words differ from the jnp engine's")
    probes = _key_batch(seed + 1, 0, n)
    probes = probes.at[:, 0].set(probes[:, 0] | jnp.uint32(0x80000000))
    _check(jnp.array_equal(f.contains(probes), ref.contains(probes)),
           "pallas-hbm lookups of never-inserted keys differ from jnp's")
    _report("b", engine=f.backend, impl=f.engine.tpu_impl,
            dispatched=_delta(d0, ops.dispatched()),
            filter_bytes=f.nbytes, keys_added=n * batches,
            compile_s=clock.lap(c0), warm_add_s=warm,
            peak_bytes_in_use=_peak_bytes(jax.devices()[:1]))


def phase_vmem(seed: int, clock: _CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.core import hashing as H
    from repro.core import variants as V
    from repro.kernels import ops

    c0, d0 = clock.total, ops.dispatched()
    m_bits, n = 1 << 25, 1 << 21
    f = api.make_filter("sbf", m_bits=m_bits, k=8)
    _check(f.backend == "pallas-vmem",
           f"auto chose {f.backend!r} for a 4 MiB filter, not pallas-vmem")
    ref = api.make_filter("sbf", m_bits=m_bits, k=8, backend="jnp")
    keys = _key_batch(seed, 100, n)
    f = f.add(keys)
    ref = ref.add(keys)
    _, warm = _timed(lambda: f.add(keys))
    _check(jnp.all(f.contains(keys)), "an inserted key tests absent")
    _check(jnp.array_equal(f.words, ref.words),
           "pallas-vmem words differ from the jnp engine's")
    probes = jnp.asarray(H.probe_u64x2(n, seed=seed))
    hits = f.contains(probes)
    _check(jnp.array_equal(hits, ref.contains(probes)),
           "pallas-vmem lookups of never-inserted keys differ from jnp's")
    fpr = float(jnp.mean(hits.astype(jnp.float32)))
    theory = V.fpr_sbf(256, 32, m_bits / n, 8)
    _check(theory / 1.15 <= fpr <= theory * 1.15,
           f"FPR {fpr:.6g} not within 1.15x of theory {theory:.6g}")
    _report("c", engine=f.backend, impl=f.engine.tpu_impl,
            dispatched=_delta(d0, ops.dispatched()),
            filter_bytes=f.nbytes, keys_added=n, fpr=fpr, fpr_theory=theory,
            compile_s=clock.lap(c0), warm_add_s=warm,
            peak_bytes_in_use=_peak_bytes(jax.devices()[:1]))


def phase_service(seed: int, clock: _CompileClock) -> None:
    import jax
    import numpy as np
    from repro import api
    from repro.kernels import ops
    from repro.service import FilterService, ServiceConfig

    c0, d0 = clock.total, ops.dispatched()
    tenants_n, rng = 16, np.random.default_rng(seed)
    bank = api.make_filter_bank(tenants_n, "sbf", m_bits=1 << 20, k=8)
    _check(bank.backend == "pallas-vmem",
           f"auto chose {bank.backend!r} for the bank, not pallas-vmem")
    ref = api.make_filter_bank(tenants_n, "sbf", m_bits=1 << 20, k=8,
                               backend="jnp")
    svc = FilterService(bank, ServiceConfig(max_batch=4096))
    added = []
    for burst in range(3):                       # routed adds, then drain
        keys = np.asarray(_key_batch(seed, 200 + burst, 3000))
        tenants = rng.integers(0, tenants_n, 3000)
        seqs = svc.submit_many("add", keys, tenants)
        ok = seqs >= 0
        added.append((keys[ok], tenants[ok]))
        ref = ref.add(keys[ok], tenants=tenants[ok])
    svc.drain()
    asked = []
    t0 = time.perf_counter()
    for burst in range(2):                       # half present, half new
        old_k, old_t = added[burst]
        new_k = np.asarray(_key_batch(seed, 300 + burst, 1500))
        keys = np.concatenate([old_k[:1500], new_k])
        tenants = np.concatenate([old_t[:1500],
                                  rng.integers(0, tenants_n, 1500)])
        seqs = svc.submit_many("contains", keys, tenants)
        asked.append((seqs, keys, tenants))
    svc.drain()
    warm = time.perf_counter() - t0
    answers = svc.take_results()
    n_checked = 0
    for seqs, keys, tenants in asked:
        ok = seqs >= 0
        want = np.asarray(ref.contains(keys[ok], tenants=tenants[ok]))
        got = np.array([answers[int(s)] for s in seqs[ok]])
        _check(np.array_equal(got, want),
               "service answers differ from the jnp bank's")
        _check(got[: int(ok[:1500].sum())].all(),
               "an added key tests absent through the service")
        n_checked += int(ok.sum())
    _check(np.array_equal(np.asarray(svc.filt.words), np.asarray(ref.words)),
           "service bank words differ from the jnp bank's")
    flushes = svc.telemetry.registry.counter("service.flushes").value
    _report("d", engine=svc.filt.backend, impl=svc.filt.engine.tpu_impl,
            dispatched=_delta(d0, ops.dispatched()),
            adds=int(sum(k.shape[0] for k, _ in added)),
            lookups_checked=n_checked, flushes=flushes,
            compile_s=clock.lap(c0), warm_lookups_s=warm,
            peak_bytes_in_use=_peak_bytes(jax.devices()[:1]))


def phase_counting(seed: int, clock: _CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.core import hashing as H
    from repro.core import variants as V
    from repro.kernels import ops

    c0, d0 = clock.total, ops.dispatched()
    ref_add = jax.jit(V.counting_add, static_argnums=0)
    ref_remove = jax.jit(V.counting_remove, static_argnums=0)
    ref_contains = jax.jit(V.counting_contains, static_argnums=0)
    regimes = {}
    for regime, m_bits, n in (("vmem", 1 << 23, 1 << 19),
                              ("hbm", 1 << 28, 1 << 20)):
        f = api.make_filter("countingbf", m_bits=m_bits, k=8)
        _check(f.backend == "counting", f"engine {f.backend!r}, not counting")
        spec, keys = f.spec, _key_batch(seed, 500, n)
        again, gone = keys[: n // 2], keys[: n // 4]
        f = f.add(keys).add(again)                 # counts of 2 and of 1
        removed = f.remove(gone)                   # compiles the remove
        _, warm = _timed(lambda: f.remove(gone))
        f = removed
        ref = ref_remove(spec, ref_add(spec, ref_add(spec, V.init(spec),
                                                     keys), again), gone)
        _check(jnp.array_equal(f.words, ref),
               f"counting {regime}: counters differ from the jnp reference")
        _check(jnp.all(f.contains(keys)),
               f"counting {regime}: a key with a count left tests absent")
        probes = jnp.asarray(H.probe_u64x2(n, seed=seed))
        _check(jnp.array_equal(f.contains(probes),
                               ref_contains(spec, ref, probes)),
               f"counting {regime}: lookups differ from the jnp reference")
        regimes[regime] = {"filter_bytes": f.nbytes, "keys": n,
                           "warm_remove_s": warm}
    ran = _delta(d0, ops.dispatched())
    missing = [f"counting_{op}:pallas-{r}" for r in regimes
               for op in ("add", "remove", "contains")
               if f"counting_{op}:pallas-{r}" not in ran]
    _check(not missing, f"counting kernels never ran: {missing}")
    _report("e", engine="counting", impl=api.get_backend("counting").tpu_impl,
            dispatched=ran, regimes=regimes,
            compile_s=clock.lap(c0),
            peak_bytes_in_use=_peak_bytes(jax.devices()[:1]))


def phase_sharded(seed: int, clock: _CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, SingleDeviceSharding
    from repro import api
    from repro.core import hashing as H
    from repro.core import variants as V
    from repro.kernels import ops

    devices = jax.devices()[:4]
    _check(len(devices) == 4, f"--four-chips needs 4 devices, got "
                              f"{len(jax.devices())}")
    c0, d0 = clock.total, ops.dispatched()
    mesh = Mesh(np.array(devices), ("data",))
    f = api.make_filter("sbf", m_bits=1 << 37, k=8, backend="sharded",
                        mesh=mesh)
    spec = f.spec
    shards = f.words.addressable_shards
    _check(sorted(s.device.id for s in shards)
           == sorted(d.id for d in devices)
           and all(s.data.nbytes == f.nbytes // 4 for s in shards),
           "the sharded filter is not split 4 ways across the devices")
    bps = spec.n_blocks // 4
    seg_words = spec.n_words // 4

    def owned(keys, d):
        """Segment-local block ids and masks, zeroed for keys another
        device's block range owns."""
        h1, h2 = H.hash_keys(keys)
        blk = H.block_index(h2, spec.n_blocks).astype(jnp.int32)
        mine = (blk // bps) == d
        masks = V.block_patterns(spec, h1) * mine[:, None].astype(jnp.uint32)
        return blk % bps, masks

    n, batches = 1 << 20, 16
    warm = None
    for i in range(batches):
        keys = _key_batch(seed, 400 + i, n)
        f, dt = _timed(lambda: f.add(keys))
        if i == 1:
            warm = dt
    for i in range(batches):
        _check(jnp.all(f.contains(_key_batch(seed, 400 + i, n))),
               f"batch {i}: an inserted key tests absent")
    ran = _delta(d0, ops.dispatched())
    _check(set(ran) == {"sharded_add:pallas-hbm",
                        "sharded_contains:pallas-hbm"},
           f"the segments did not run the Pallas row kernels: {ran}")
    # The references are built only now, once the adds (4 GiB shard + its
    # 4 GiB update per device) are done, and donated in place: a 4 GiB
    # reference segment beside both would leave too little of 16 GiB.
    ref_add = jax.jit(lambda seg, keys, d: V.or_rows(
        spec, seg, *owned(keys, d), n_rows=bps), static_argnums=2,
        donate_argnums=0)
    refs = [jax.jit(lambda: jnp.zeros((seg_words,), jnp.uint32),
                    out_shardings=SingleDeviceSharding(d))()
            for d in devices]
    for i in range(batches):
        keys = _key_batch(seed, 400 + i, n)
        for d, dev in enumerate(devices):
            refs[d] = ref_add(refs[d], jax.device_put(keys, dev), d)
        jax.block_until_ready(refs)
    by_device = {s.device.id: s.data for s in f.words.addressable_shards}
    for d, dev in enumerate(devices):
        _check(jnp.array_equal(by_device[dev.id], refs[d]),
               f"device {d}: shard differs from its jnp reference")
    _report("4-chip", engine=f.backend, impl=f.engine.tpu_impl,
            filter_bytes=f.nbytes, keys_added=n * batches,
            compile_s=clock.lap(c0), warm_add_s=warm, dispatched=ran,
            counters=f.engine.registry.snapshot(),
            peak_bytes_in_use=_peak_bytes(devices))


def _delta(before: dict, after: dict) -> dict:
    return {f"{op}:{impl}": after[(op, impl)] - before.get((op, impl), 0)
            for op, impl in sorted(after)
            if after[(op, impl)] != before.get((op, impl), 0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 16 GiB sharded-engine phase")
    args = ap.parse_args(argv)

    import jax
    from repro import api
    from repro.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":                              # phase a
        print(f"chip_smoke: no TPU (first device is {dev.platform!r})",
              file=sys.stderr)
        return 1
    _report("a", platform=dev.platform, kind=dev.device_kind,
            count=len(jax.devices()), compile_cache=enable_compile_cache(),
            tpu_impl={e["name"]: e["tpu_impl"]
                      for e in api.registry.describe()})
    clock = _CompileClock()
    phases = ([phase_sharded] if args.four_chips
              else [phase_hbm, phase_vmem, phase_service, phase_counting])
    for phase in phases:
        phase(args.seed, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
