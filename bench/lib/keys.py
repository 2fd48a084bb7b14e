"""Inputs made from the run's seed: keys on the device, request streams on
the host. The same seed gives the same inputs; different seeds give the
same sizes in another draw.

Keys are 64-bit, held as (n, 2) uint32 ``[hi, lo]``, the form the filter
takes.
"""
from __future__ import annotations

import math
import zlib
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A PRNG key from all 64 bits of ``seed`` (``jax.random.key`` keeps
    only the low 32 bits of a large seed)."""
    seed = int(seed) & (2**64 - 1)
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32),
        impl="threefry2x32")


def stream_key(seed: int, name: str, index: int = 0):
    """The key of one named stream of draws (and one batch of it)."""
    key = jax.random.fold_in(root_key(seed), zlib.crc32(name.encode()))
    return jax.random.fold_in(key, index)


def host_rng(seed: int, name: str) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1),
                                  zlib.crc32(name.encode())])


@partial(jax.jit, static_argnums=1)
def _bits(key, n: int):
    return jax.random.bits(key, (n, 2), jnp.uint32)


def uniform_keys(seed: int, name: str, index: int, n: int):
    """n uniform 64-bit keys (hashes of paragraphs, records)."""
    return _bits(stream_key(seed, name, index), n)


# -- TPC-H --------------------------------------------------------------------

def tpch_orderkey(index):
    """dbgen's sparse order key of 1-based order ``index``: of every 32
    key values the first 8 are used (``mk_sparse``: SPARSE_BITS=2,
    SPARSE_KEEP=3)."""
    index = jnp.asarray(index, jnp.uint32)
    return ((index >> 3) << 5) | (index & 7)


def _as_keys(lo):
    return jnp.stack([jnp.zeros_like(lo), lo], axis=1)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _qualifying_orders(key, n_build: int, n_orders: int, qualify_p: float):
    keep = jax.random.uniform(key, (n_orders,)) < qualify_p
    idx = jnp.nonzero(keep, size=n_build)[0].astype(jnp.uint32) + 1
    return _as_keys(tpch_orderkey(idx)), jnp.sum(keep.astype(jnp.int32))


def tpch_build(seed: int, n_build: int, n_orders: int, qualify_p: float):
    """The join's build side: the order keys of the orders that pass the
    query's filters, each of the table's ``n_orders`` orders qualifying by
    one draw at ``qualify_p``. The first ``n_build`` qualifying orders are
    kept, so every seed builds the same number of keys; a table in which
    fewer qualify is an error. Returns (n_build, 2) keys."""
    keys, qualified = _qualifying_orders(stream_key(seed, "tpch.orders"),
                                         n_build, n_orders, float(qualify_p))
    if int(qualified) < n_build:
        raise ValueError(f"only {int(qualified)} of {n_orders} orders "
                         f"qualify; the build side needs {n_build}")
    return keys


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _lineitem(key, first_order, n_rows: int, span: int, lo: int, hi: int):
    per_order = jax.random.randint(key, (span,), lo, hi + 1)
    orders = first_order + jnp.arange(span, dtype=jnp.uint32)
    rows = jnp.repeat(orders, per_order, total_repeat_length=n_rows)
    return _as_keys(tpch_orderkey(rows))


def tpch_lineitem(seed: int, index: int, n_rows: int, n_orders: int,
                  rows_per_order=(1, 7)):
    """One probe batch: ``n_rows`` lineitem rows in table order, their
    ``l_orderkey``. Each order has a uniform number of rows in
    ``rows_per_order``; the batch starts at an order drawn from the seed,
    inside the orders table."""
    lo, hi = rows_per_order
    span = int(math.ceil(n_rows / lo))
    key = stream_key(seed, "tpch.lineitem", index)
    k_start, k_rows = jax.random.split(key)
    room = max(n_orders - span, 1)
    first = 1 + int(jax.random.randint(k_start, (), 0, room))
    return _lineitem(k_rows, jnp.uint32(first), n_rows, span, lo, hi)


# -- YCSB ---------------------------------------------------------------------

YCSB_ITEM_COUNT = 10_000_000_000     # ScrambledZipfianGenerator.ITEM_COUNT
YCSB_ZETAN = 26.46902820178302       # its precomputed zeta(ITEM_COUNT, 0.99)
FNV_OFFSET_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 bytes of each value,
    low byte first, then the absolute value as a signed 64-bit number."""
    val = np.asarray(values).astype(np.uint64)
    h = np.full(val.shape, FNV_OFFSET_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= val & np.uint64(0xFF)
            val = val >> np.uint64(8)
            h *= np.uint64(FNV_PRIME_64)
    return np.abs(h.view(np.int64))


def zipfian_ranks(rng: np.random.Generator, n: int, items: int,
                  theta: float, zetan: float) -> np.ndarray:
    """YCSB's ``ZipfianGenerator`` (Gray et al.'s closed form): ranks in
    [0, items), rank 0 the most popular."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(n)
    uz = u * zetan
    tail = np.floor(items * np.power(eta * u - eta + 1.0, alpha))
    ranks = np.where(uz < 1.0, 0.0, np.where(uz < zeta2, 1.0, tail))
    return np.minimum(ranks, items - 1).astype(np.int64)


def scrambled_zipfian(rng: np.random.Generator, n: int, record_count: int,
                      theta: float = 0.99) -> np.ndarray:
    """YCSB's ``ScrambledZipfianGenerator``: a zipfian rank over 10^10
    items, hashed (FNV-64) onto the records, so the popular records are
    spread over the key space."""
    ranks = zipfian_ranks(rng, n, YCSB_ITEM_COUNT, theta, YCSB_ZETAN)
    return (fnvhash64(ranks) % record_count).astype(np.int64)


def poisson_arrivals(rng: np.random.Generator, rate: float,
                     seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop Poisson
    stream at ``rate`` per second, every arrival before ``seconds``."""
    n = int(rate * seconds + 10 * math.sqrt(rate * seconds) + 10)
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    while due[-1] < seconds:
        more = due[-1] + np.cumsum(rng.exponential(1.0 / rate, n))
        due = np.concatenate([due, more])
    return due[due < seconds]
