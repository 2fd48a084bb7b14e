"""Subprocess worker: the sharded engine on four emulated devices, one case
per run, through the normal path (``make_filter(backend="sharded")``).

Run by tests/test_distributed.py with
XLA_FLAGS=--xla_force_host_platform_device_count=4. Prints OK on success;
any assertion failure exits nonzero.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from bench.lib import reference as R  # noqa: E402
from repro import api  # noqa: E402
from repro.core import hashing as H  # noqa: E402
from repro.core import variants as V  # noqa: E402
from repro.kernels import ops  # noqa: E402

M_BITS, K, BLOCK_BITS = 1 << 16, 8, 256


def _owned_by(keys: np.ndarray, shard: int) -> np.ndarray:
    n_blocks = M_BITS // BLOCK_BITS
    blk = np.asarray(H.block_index(H.block_hash(jnp.asarray(keys)),
                                   n_blocks))
    return keys[blk // (n_blocks // 4) == shard]


def _keys(case: str) -> np.ndarray:
    if case == "uniform":
        return H.random_u64x2(1024, seed=31)
    if case == "one_owner":
        return _owned_by(H.random_u64x2(4096, seed=32), 2)[:600]
    if case == "ragged":
        return H.random_u64x2(1001, seed=33)
    if case == "repeated":
        base = H.random_u64x2(300, seed=34)
        idx = np.random.default_rng(34).permutation(np.repeat(np.arange(300),
                                                              3))
        return base[idx]
    raise ValueError(case)


def check_words(case: str) -> None:
    mesh = Mesh(np.array(jax.devices()), ("data",))
    f = api.make_filter("sbf", m_bits=M_BITS, k=K, block_bits=BLOCK_BITS,
                        backend="sharded", mesh=mesh)
    keys = jnp.asarray(_keys(case))
    g = f.add(keys)
    words = np.asarray(g.words)
    np.testing.assert_array_equal(
        words, np.asarray(V.add_scatter(f.spec, V.init(f.spec), keys)))
    np.testing.assert_array_equal(
        words, np.asarray(R.build(keys, M_BITS, K, BLOCK_BITS)))
    assert bool(np.asarray(g.contains(keys)).all()), "a key added tests absent"
    probes = jnp.asarray(H.probe_u64x2(777, seed=35))
    np.testing.assert_array_equal(
        np.asarray(g.contains(probes)),
        np.asarray(R.contains(words, probes, M_BITS, K, BLOCK_BITS)))
    # each shard holds its own block range, on its own device
    shards = sorted(g.words.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    assert [s.device for s in shards] == list(mesh.devices)
    seg = words.size // 4
    for i, s in enumerate(shards):
        np.testing.assert_array_equal(np.asarray(s.data),
                                      words[i * seg:(i + 1) * seg])


def check_counters() -> None:
    """Calls, keys offered and exchange slots, once per call, per op."""
    mesh = Mesh(np.array(jax.devices()), ("data",))
    f = api.make_filter("sbf", m_bits=M_BITS, k=K, block_bits=BLOCK_BITS,
                        backend="sharded", mesh=mesh)
    reg = f.engine.registry
    before = reg.snapshot(prefix="sharded.")
    keys = jnp.asarray(H.random_u64x2(1000, seed=36))
    for _ in range(3):                   # one trace, three calls
        f = f.add(keys)
    f.contains(keys[:10])
    got = {k: v - before.get(k, 0)
           for k, v in reg.snapshot(prefix="sharded.").items()}
    # 1000 keys: 250 a device, capacity 250, 4 x 4 x 250 slots a call;
    # 10 keys: 3 a device after padding to 12
    assert got == {"sharded.calls{op=add}": 3,
                   "sharded.keys{op=add}": 3000,
                   "sharded.exchange_slots{op=add}": 3 * 16 * 250,
                   "sharded.calls{op=contains}": 1,
                   "sharded.keys{op=contains}": 10,
                   "sharded.exchange_slots{op=contains}": 16 * 3}, got


def check_row_engine() -> None:
    """add and contains run the row engine on each segment: no XLA
    reference, no single-filter dispatch."""
    before = ops.dispatched()
    check_words("uniform")
    ran = {k: v - before.get(k, 0) for k, v in ops.dispatched().items()
           if v != before.get(k, 0)}
    assert set(ran) == {("sharded_add", "pallas-hbm"),
                        ("sharded_contains", "pallas-hbm")}, ran
    desc = {d["name"]: d for d in api.registry.describe()}
    assert desc["sharded"]["tpu_impl"] == "pallas"


def main(case: str) -> None:
    assert len(jax.devices()) == 4, jax.devices()
    if case == "counters":
        check_counters()
    elif case == "row_engine":
        check_row_engine()
    else:
        check_words(case)
    print("OK")


if __name__ == "__main__":
    main(sys.argv[1])
