"""Pallas kernels for the classical Bloom filter (CBF) — the GPU baseline.

The CBF touches k scattered single words per key (no block locality), which
is exactly why the paper moves to blocked designs; we implement it anyway as
the faithful baseline for the Fig. 9 optimization-breakdown benchmark.
VMEM-resident only: a DRAM CBF on TPU would need k independent DMAs per key,
which the roofline in benchmarks/gups quantifies instead of executing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import hashing as H
from repro.core import variants as V
from repro.core.variants import FilterSpec

from repro.kernels.sbf import DEFAULT_TILE, _take_scalar


def _positions(spec: FilterSpec, keys: jnp.ndarray):
    h1 = H.xxh32_u64x2(keys, H.SEED_PATTERN)
    h2 = H.block_hash(keys)
    pos = V.cbf_positions(spec, h1, h2)                          # (n, k)
    widx = (pos >> jnp.uint32(5)).astype(jnp.int32)
    bits = jnp.uint32(1) << (pos & jnp.uint32(31))
    return widx, bits


def _contains_kernel(keys_ref, filt_ref, out_ref, *, spec: FilterSpec, tile: int):
    widx, bits = _positions(spec, keys_ref[...])

    def body(i, acc):
        ok = jnp.bool_(True)
        for j in range(spec.k):                                  # static unroll
            w = filt_ref[pl.ds(_take_scalar(widx[:, j], i), 1)][0]
            ok = jnp.logical_and(ok, (w & _take_scalar(bits[:, j], i)) != 0)
        return jax.lax.dynamic_update_slice(acc, ok[None], (i,))

    out = jax.lax.fori_loop(0, tile, body, jnp.zeros((tile,), jnp.bool_))
    out_ref[...] = out


def _add_kernel(keys_ref, filt_ref, out_ref, *, spec: FilterSpec, tile: int):
    @pl.when(pl.program_id(0) == 0)
    def _seed():
        out_ref[...] = filt_ref[...]

    widx, bits = _positions(spec, keys_ref[...])

    def body(i, carry):
        for j in range(spec.k):                                  # k scattered RMWs
            idx = (pl.ds(_take_scalar(widx[:, j], i), 1),)
            w = out_ref[idx]
            out_ref[idx] = w | _take_scalar(bits[:, j], i)[None]
        return carry

    jax.lax.fori_loop(0, tile, body, jnp.int32(0))


def contains_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                  *, interpret: bool,
                  tile: int = DEFAULT_TILE) -> jnp.ndarray:
    n = keys.shape[0]
    assert n % tile == 0
    kern = functools.partial(_contains_kernel, spec=spec, tile=tile)
    return pl.pallas_call(
        kern,
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((tile, 2), lambda i: (i, 0)),
                  pl.BlockSpec((spec.n_words,), lambda i: (0,))],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.bool_),
        interpret=interpret,
    )(keys, filt)


def add_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
             *, interpret: bool,
             tile: int = DEFAULT_TILE) -> jnp.ndarray:
    n = keys.shape[0]
    assert n % tile == 0
    kern = functools.partial(_add_kernel, spec=spec, tile=tile)
    return pl.pallas_call(
        kern,
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((tile, 2), lambda i: (i, 0)),
                  pl.BlockSpec((spec.n_words,), lambda i: (0,))],
        out_specs=pl.BlockSpec((spec.n_words,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((spec.n_words,), jnp.uint32),
        interpret=interpret,
    )(keys, filt)
