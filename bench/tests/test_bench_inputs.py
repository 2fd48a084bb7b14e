"""Inputs made from the seed, the bytes an operation needs, the peak
table and the order statistics."""
from __future__ import annotations

import numpy as np
import pytest

from bench.lib import device, keys as K, stats, work


def _np(x):
    return np.asarray(x)


def test_device_keys_are_a_function_of_the_seed():
    a = _np(K.uniform_keys(7, "keys", 3, 256))
    assert np.array_equal(a, _np(K.uniform_keys(7, "keys", 3, 256)))
    assert not np.array_equal(a, _np(K.uniform_keys(8, "keys", 3, 256)))
    assert not np.array_equal(a, _np(K.uniform_keys(7, "keys", 4, 256)))


def test_large_seeds_keep_every_bit():
    low = _np(K.uniform_keys(5, "keys", 0, 64))
    high = _np(K.uniform_keys(5 + (1 << 33), "keys", 0, 64))
    assert not np.array_equal(low, high)


def test_tpch_build_side():
    a = K.tpch_build(3, 1024, 12000, 0.097)
    b = K.tpch_build(3, 1024, 12000, 0.097)
    assert np.array_equal(_np(a), _np(b))
    a = _np(a)
    assert a.shape == (1024, 2) and (a[:, 0] == 0).all()
    # dbgen's sparse keys: only the first 8 of every 32 values
    assert ((a[:, 1] % 32) < 8).all()
    assert len(np.unique(a[:, 1])) == 1024
    # about 0.097 of the orders qualify, in table order
    last_order = (a[-1, 1] >> 5) * 8 + (a[-1, 1] & 7)
    assert 1024 / 0.097 * 0.8 < last_order < 1024 / 0.097 * 1.2
    with pytest.raises(ValueError):
        K.tpch_build(3, 1024, 5000, 0.097)


def test_tpch_lineitem_in_table_order():
    li = _np(K.tpch_lineitem(3, 0, 4096, 50_000))
    assert np.array_equal(li, _np(K.tpch_lineitem(3, 0, 4096, 50_000)))
    order_keys = li[:, 1]
    assert (np.diff(order_keys.astype(np.int64)) >= 0).all()
    _, runs = np.unique(order_keys, return_counts=True)
    assert runs[:-1].min() >= 1 and runs.max() <= 7
    assert 3.0 < runs[:-1].mean() < 5.0


def test_lineitem_probes_hit_the_build_side_at_its_share():
    n_orders = 40000
    build = K.tpch_build(5, 3600, n_orders, 0.097)
    li = _np(K.tpch_lineitem(5, 0, 8192, n_orders))
    hit = np.isin(li[:, 1], _np(build)[:, 1]).mean()
    assert 0.07 < hit < 0.12


def test_fnvhash64_matches_ycsb():
    # Utils.fnvhash64 over the 8 bytes of 0: FNV-1a of eight zero bytes
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = (h * 1099511628211) % (1 << 64)
    want = h - (1 << 64) if h >= 1 << 63 else h
    assert K.fnvhash64(np.array([0]))[0] == abs(want)


def test_scrambled_zipfian_is_skewed_and_pure():
    a = K.scrambled_zipfian(K.host_rng(1, "z"), 100_000, 1 << 20)
    b = K.scrambled_zipfian(K.host_rng(1, "z"), 100_000, 1 << 20)
    assert np.array_equal(a, b)
    assert a.min() >= 0 and a.max() < 1 << 20
    _, counts = np.unique(a, return_counts=True)
    # rank 0 of a theta=0.99 zipfian over 10^10 items takes 1/zeta ~ 3.8%
    assert 0.03 < counts.max() / a.size < 0.05


def test_poisson_arrivals():
    a = K.poisson_arrivals(K.host_rng(2, "a"), 1000.0, 5.0)
    assert np.array_equal(a, K.poisson_arrivals(K.host_rng(2, "a"), 1000.0,
                                                5.0))
    assert (np.diff(a) > 0).all() and a[-1] < 5.0
    assert 4700 < a.size < 5300


def test_bytes_needed():
    assert work.lookup_bytes(10, 4, 256) == 10 * 9 + 4 * 32
    assert work.add_bytes(10, 4, 256) == 10 * 8 + 2 * 4 * 32


def test_peak_table():
    row = device.peaks("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in row["source"]
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_nearest_rank_and_spread():
    xs = list(range(1, 101))
    assert stats.nearest_rank(xs, 99) == 99
    assert stats.nearest_rank(xs, 100) == 100
    assert stats.nearest_rank([5.0], 50) == 5.0
    with pytest.raises(ValueError):
        stats.nearest_rank([], 50)
    assert stats.spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    assert stats.spread([9, 10, 10, 11]) > 0


def test_on_off_arrivals_keep_the_mean_rate():
    from bench.lib import catalog
    on_off = catalog.plugin("arrivals", "on_off")
    spec = {"rate_per_s": 1000.0, "on_s": 0.25, "off_s": 0.75}
    a = on_off.due(K.host_rng(2, "a"), spec, 8.0)
    assert np.array_equal(a, on_off.due(K.host_rng(2, "a"), spec, 8.0))
    assert (np.diff(a) > 0).all() and a[-1] < 8.0
    assert 7500 < a.size < 8500
    # nothing arrives in the off part of a period
    assert (np.mod(a, 1.0) < 0.25).all()


@pytest.mark.parametrize("name,spec", [
    ("scrambled_zipfian", {"theta": 0.99}), ("uniform", {})])
def test_choosers_are_pure_and_in_range(name, spec):
    from bench.lib import catalog
    chooser = catalog.plugin("choosers", name)
    a = chooser.draw(K.host_rng(4, "c"), 5000, 1000, spec)
    b = chooser.draw(K.host_rng(4, "c"), 5000, 1000, spec)
    assert np.array_equal(a, b) and a.min() >= 0 and a.max() < 1000


def test_holders_are_ranges_of_the_high_word():
    from bench.lib import catalog
    holders = catalog.kind("service_gets").holders
    hi = np.array([0, 0x0FFFFFFF, 0x10000000, 0x7FFFFFFF, 0xFFFFFFFF],
                  np.uint32)
    keys = np.stack([hi, np.zeros_like(hi)], axis=1)
    assert _np(holders(keys, 16)).tolist() == [0, 0, 1, 7, 15]
    # any member count, not only a power of two
    got = _np(holders(keys, 3))
    assert got.tolist() == [0, 0, 0, 1, 2]
