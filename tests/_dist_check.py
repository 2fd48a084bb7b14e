"""Subprocess worker: distributed-filter semantics on 8 emulated devices.

Run by tests/test_distributed.py with XLA_FLAGS=--xla_force_host_platform_device_count=8.
Prints OK on success; any assertion failure exits nonzero.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import variants as V
from repro.core import hashing as H
from repro.core import distributed as D
from repro.core.distributed import or_allreduce


def main():
    devs = jax.devices()
    assert len(devs) == 8, devs
    mesh = Mesh(np.array(devs).reshape(8), ("data",))
    spec = V.FilterSpec("sbf", 1 << 18, 8, block_bits=256)

    n_local = 256
    keys_np = H.random_u64x2(8 * n_local, seed=3)
    keys = jax.device_put(jnp.asarray(keys_np).reshape(8, n_local, 2),
                          NamedSharding(mesh, P("data")))
    ref = V.add_scatter(spec, V.init(spec), jnp.asarray(keys_np))

    # --- butterfly OR == gather OR == local reduce ---------------------------
    x = jax.device_put(
        jnp.arange(8 * 16, dtype=jnp.uint32).reshape(8, 16) * np.uint32(2654435761),
        NamedSharding(mesh, P("data")))
    for method in ("butterfly", "gather"):
        out = jax.shard_map(lambda v: or_allreduce(v, "data", method=method),
                        mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)
        expect = np.bitwise_or.reduce(np.asarray(x), axis=0)
        for d in range(8):
            np.testing.assert_array_equal(np.asarray(out)[d], expect)
    print("or_allreduce ok")

    # --- replicated transforms: local adds + sync == global reference --------
    rw = D.replicated_init(spec, mesh)
    rw = D.replicated_add_local(spec, mesh, "data", rw, keys)
    # pre-sync: each replica only knows its shard -> some misses across shards
    pre = np.asarray(D.replicated_contains_local(spec, mesh, "data", rw, keys))
    assert pre.all()  # own shard always found
    cross = np.asarray(D.replicated_contains_local(
        spec, mesh, "data", rw, jnp.roll(keys, 1, axis=0)))  # other device's keys
    assert not cross.all(), "pre-sync replicas should not know remote keys"
    rw = D.replicated_sync(spec, mesh, "data", rw)
    for d in range(8):
        np.testing.assert_array_equal(np.asarray(rw)[d], np.asarray(ref))
    post = np.asarray(D.replicated_contains_local(
        spec, mesh, "data", rw, jnp.roll(keys, 3, axis=0)))
    assert post.all()
    print("replicated ok")

    # --- sharded transforms: all_to_all routing == global reference ----------
    sw = D.sharded_init(spec, mesh)
    sw = D.sharded_add(spec, mesh, "data", n_local, sw, keys)
    np.testing.assert_array_equal(np.asarray(sw), np.asarray(ref))
    res = np.asarray(D.sharded_contains(spec, mesh, "data", n_local, sw, keys))
    assert res.all()
    # negatives: unseen keys should mostly be absent (FPR-bounded)
    probe = jax.device_put(
        jnp.asarray(H.random_u64x2(8 * n_local, seed=99)).reshape(8, n_local, 2),
        NamedSharding(mesh, P("data")))
    neg = np.asarray(D.sharded_contains(spec, mesh, "data", n_local, sw, probe))
    assert neg.mean() < 0.05, neg.mean()
    print("sharded ok")

    # --- capacity overflow degrades conservatively ---------------------------
    sw2 = D.sharded_init(spec, mesh)
    sw2 = D.sharded_add(spec, mesh, "data", 8, sw2, keys)   # force overflow
    res2 = np.asarray(D.sharded_contains(spec, mesh, "data", 8, sw2, keys))
    assert res2.all(), "overflow must never produce a false negative"
    print("overflow ok")

    print("OK")


if __name__ == "__main__":
    main()
