"""The plain reference agrees with the program at a tiny size, the
program's Pallas kernels running in interpret mode."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import keys as K
from bench.lib import reference as R

M_BITS, N = 1 << 16, 2048


def _keys(seed, n=N):
    return K.uniform_keys(seed, "ref", 0, n)


def test_salts_are_the_layouts():
    from repro.core import hashing as H
    assert np.array_equal(R.salts(), H.SALTS)


def test_xxh32_known_values():
    # XXH32 of 8 zero bytes under seed 0, and of the bytes 0..7 under the
    # pattern seed, as the xxhash library gives them
    zero = jnp.zeros((1,), jnp.uint32)
    assert int(R.xxh32_two_words(zero, zero, 0)[0]) == 3736311059
    first = jnp.array([0x03020100], jnp.uint32)
    second = jnp.array([0x07060504], jnp.uint32)
    assert int(R.xxh32_two_words(first, second, R.SEED_PATTERN)[0]) == \
        2269232397


@pytest.mark.parametrize("backend", ["jnp", "pallas-vmem", "pallas-hbm"])
@pytest.mark.parametrize("k", [8, 4])
def test_single_filter(backend, k):
    from repro import api
    keys, probes = _keys(1), _keys(2, 4096)
    f = api.make_filter("sbf", m_bits=M_BITS, k=k, backend=backend)
    f = f.add(keys[: N // 2]).add(keys[N // 2:])
    ref = R.build(keys, M_BITS, k, 256)
    assert np.array_equal(np.asarray(f.words), np.asarray(ref))
    want = R.contains(ref, probes, M_BITS, k, 256)
    assert np.array_equal(np.asarray(f.contains(probes)), np.asarray(want))
    assert bool(R.contains(ref, keys, M_BITS, k, 256).all())
    assert int(R.count_missing(ref, keys, M_BITS, k, 256, 0,
                               M_BITS // 256)) == 0


def test_bank_routed():
    from repro import api
    members = 4
    keys, probes = _keys(3), _keys(4, 1024)
    owner = jnp.arange(N, dtype=jnp.int32) % members
    asked = jnp.arange(1024, dtype=jnp.int32) % members
    bank = api.make_filter_bank(members, "sbf", m_bits=1 << 14, k=8,
                                backend="pallas-vmem")
    bank = bank.add(keys, tenants=owner)
    ref = R.build(keys, 1 << 14, 8, 256, member=owner, bank=members)
    assert np.array_equal(np.asarray(bank.words).reshape(-1),
                          np.asarray(ref))
    got = bank.contains(probes, tenants=asked)
    want = R.contains(ref, probes, 1 << 14, 8, 256, member=asked)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_range_equals_slice_of_whole():
    keys = _keys(5)
    whole = R.build(keys, M_BITS, 8, 256)
    part = R.build_range(keys, M_BITS, 8, 256, 40, 64)
    assert np.array_equal(np.asarray(part), np.asarray(whole[40 * 8:104 * 8]))
    # a missing bit is found in its range and only there
    holed = whole.at[int(jnp.argmax(whole != 0))].set(0)
    assert int(R.count_missing(holed, keys, M_BITS, 8, 256, 0,
                               M_BITS // 256)) >= 1


def test_valid_mask_and_order_do_not_matter():
    keys = _keys(6)
    a = R.build(keys, M_BITS, 8, 256)
    b = R.build(jnp.concatenate([keys[::-1], keys[:7]]), M_BITS, 8, 256)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    none = R.build_range(keys, M_BITS, 8, 256, 0, M_BITS // 256,
                         valid=jnp.zeros((N,), bool))
    assert not np.asarray(none).any()
