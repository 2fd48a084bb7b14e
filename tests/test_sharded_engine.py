"""The sharded engine's segments on the row engine: the segment spec, the
padded exchange slots, and the engine through ``make_filter`` on four
emulated devices (a subprocess per case, so the test process keeps its
single-device view)."""
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import distributed as D
from repro.core import hashing as H
from repro.core import variants as V
from repro.kernels import ops
from repro.kernels import sbf

SPEC = V.FilterSpec("sbf", 1 << 16, 8, block_bits=256)


def test_segment_block_is_the_filters_masked_to_the_segment():
    """A segment's spec gives each key the block it has in the whole
    filter, masked to the segment (block indices mask the hash's low
    bits); the high bits name its owner."""
    keys = jnp.asarray(H.random_u64x2(4096, seed=4))
    h2 = H.block_hash(keys)
    blk = np.asarray(H.block_index(h2, SPEC.n_blocks))
    for n_dev in (1, 2, 4, 8):
        seg = D.segment_spec(SPEC, n_dev)
        bps = SPEC.n_blocks // n_dev
        assert seg.n_blocks == bps and seg.k == SPEC.k
        np.testing.assert_array_equal(
            np.asarray(H.block_index(h2, seg.n_blocks)), blk & (bps - 1))
        starts, _ = sbf.fingerprints(seg, keys)
        np.testing.assert_array_equal(np.asarray(starts),
                                      (blk & (bps - 1)) * seg.s)


@pytest.mark.parametrize("carried", [0, 1, 300, 511, 512])
def test_padded_slots_set_no_bit(carried):
    """A segment update of 512 exchange slots of which ``carried`` hold a
    key: the padded slots (random keys, valid 0) set nothing, and a probe
    answers as the reference for every carried key."""
    seg = D.segment_spec(SPEC, 4)
    keys = jnp.asarray(H.random_u64x2(512, seed=5))
    valid = np.zeros(512, np.uint8)
    valid[:carried] = 1
    valid = np.random.default_rng(carried).permutation(valid)
    out = ops.segment_add(seg, V.init(seg), keys, jnp.asarray(valid))
    want = V.add_scatter(seg, V.init(seg), keys[valid > 0])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    hits = np.asarray(ops.segment_contains(seg, out, keys,
                                           jnp.asarray(valid)))
    assert hits[valid > 0].all()
    probes = jnp.asarray(H.probe_u64x2(512, seed=6))
    hits = np.asarray(ops.segment_contains(seg, out, probes,
                                           jnp.ones((512,), jnp.uint8)))
    np.testing.assert_array_equal(hits,
                                  np.asarray(V.contains(seg, out, probes)))


FOUR_DEVICE_CASES = ["uniform", "one_owner", "ragged", "repeated",
                     "counters", "row_engine"]


@pytest.mark.multidevice
@pytest.mark.parametrize("case", FOUR_DEVICE_CASES)
def test_sharded_engine_on_four_devices(case):
    """``make_filter(backend="sharded")`` over four emulated devices equals
    the jnp engine and the benchmark's plain reference word for word
    (uniform keys, every key owned by one shard, a batch that is not a
    multiple of 4, repeated keys), counts its calls, keys and exchange
    slots, and runs the row engine on each segment
    (``tests/_sharded_check.py``)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(os.path.dirname(__file__), "_sharded_check.py")
    proc = subprocess.run([sys.executable, script, case], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(os.path.dirname(script)))
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "OK" in proc.stdout
