"""The row engine's one schedule per regime, against the plain reference.

The Bloom and counting kernels run exactly one schedule in each regime
(the filter's or bank's bytes against ``VMEM_FILTER_BYTES`` pick it), and
the CPU runs that schedule in interpret mode. These cases pin it to
``repro.core.variants`` word for word and answer for answer, for every
blocked family and the counting filter, in both regimes, at batch sizes
that exercise a tile clamped to 8 keys, a ragged tile, an exact tile and
several padded tiles. Banks, segments and every dispatch entry point are
in ``tests/test_row_engine_paths.py``.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import hashing as H
from repro.core import variants as V
from repro.kernels import ops, ref

M = 1 << 16

ROW_SPECS = [
    V.FilterSpec("sbf", M, 8, block_bits=256),
    V.FilterSpec("sbf", M, 16, block_bits=512),
    V.FilterSpec("bbf", M, 8, block_bits=256),
    V.FilterSpec("csbf", M, 8, block_bits=512, z=2),
    V.FilterSpec("countingbf", M, 8, block_bits=256),
]


def _keys(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


def _absent(n, seed=0):
    """Keys from the reserved keyspace: never added by ``_keys``."""
    return jnp.asarray(H.probe_u64x2(n, seed=seed))


def _eq(got, want):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Reference parity: families x regimes x batch sizes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 255, 256, 1000])
@pytest.mark.parametrize("regime", ["vmem", "hbm"])
@pytest.mark.parametrize("spec", ROW_SPECS, ids=str)
def test_regime_schedule_matches_reference(spec, regime, n):
    """A batch of ``n`` keys added to an empty filter gives the
    reference's words; a batch of ``n`` probes, every other one absent,
    gives its answers. For counters, a remove of half the batch as well.
    The tile is clamped to 8 keys at n=1, left ragged at 255, exact at 256
    and padded over 4 tiles at 1000."""
    keys = _keys(n, seed=n)
    odd = (jnp.arange(n) % 2 == 1)[:, None]
    probe = jnp.where(odd, _absent(n, seed=n), keys)
    f0 = V.init(spec)
    if spec.is_counting:
        f = ops.counting_add(spec, f0, keys, regime=regime)
        want = V.counting_add(spec, f0, keys)
        _eq(f, want)
        half = keys[: (n + 1) // 2]
        _eq(ops.counting_remove(spec, f, half, regime=regime),
            V.counting_remove(spec, want, half))
        got = ops.counting_contains(spec, want, probe, regime=regime)
        _eq(got, V.counting_contains(spec, want, probe))
    else:
        f = ops.bloom_add(spec, f0, keys, regime=regime)
        want = ref.bloom_add_ref(spec, f0, keys)
        _eq(f, want)
        got = ops.bloom_contains(spec, want, probe, regime=regime)
        _eq(got, ref.bloom_contains_ref(spec, want, probe))
    assert np.asarray(got)[::2].all()          # no false negative
