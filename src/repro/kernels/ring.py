"""Fused generation-ring membership kernel (the window subsystem's hot op).

A :class:`repro.window.WindowedFilter` holds G same-spec generation
sub-filters stacked as ``(G, n_words)``. The naive query runs G separate
contains passes and ORs the G boolean vectors — G full key-hash phases and
G gathers per key. The fused kernel hashes each key ONCE and ORs the G
block rows *before* the mask test, so the per-key cost is one hash phase +
G row loads + one vector compare:

    hit(key) = all(((row_0 | row_1 | ... | row_{G-1}) & mask) == mask)

which is exactly ``contains(OR of generations)`` — the ring OR is folded
into the probe instead of materializing an O(m) union filter.

Regimes mirror kernels/sbf.py: ``ring_contains_vmem`` pins the whole
(G, n_words) stack in VMEM; ``ring_contains_hbm`` leaves it in HBM and
streams the G rows of each key through a double-buffered DMA scratch
(prefetching generation g+1 while OR-ing generation g).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing as H
from repro.core import variants as V
from repro.core.variants import FilterSpec
from repro.kernels.sbf import DEFAULT_TILE, _mask_row, _take_scalar


def ring_contains_ref(spec: FilterSpec, rings: jnp.ndarray,
                      keys: jnp.ndarray) -> jnp.ndarray:
    """jnp oracle: contains against the OR-fold of all generations."""
    dense = rings[0]
    for g in range(1, rings.shape[0]):          # static fold (G is small)
        dense = dense | rings[g]
    return V.contains_rows(spec, dense, keys)


def _fingerprints(spec: FilterSpec, keys: jnp.ndarray):
    h1 = H.xxh32_u64x2(keys, H.SEED_PATTERN)
    h2 = H.block_hash(keys)
    blk = H.block_index(h2, spec.n_blocks)
    masks = V.block_patterns(spec, h1, batched=False)
    starts = (blk * jnp.uint32(spec.s)).astype(jnp.int32)
    return starts, masks


def _ring_vmem_kernel(keys_ref, rings_ref, out_ref, *, spec: FilterSpec,
                      n_gen: int, tile: int):
    s = spec.s
    starts, masks = _fingerprints(spec, keys_ref[...])

    def body(i, acc):
        st = _take_scalar(starts, i)
        row = rings_ref[0, pl.ds(st, s)]
        for g in range(1, n_gen):               # static unroll over the ring
            row = row | rings_ref[g, pl.ds(st, s)]
        m = _mask_row(masks, i, s)
        ok = jnp.all((row & m) == m)
        return jax.lax.dynamic_update_slice(acc, ok[None], (i,))

    out = jax.lax.fori_loop(0, tile, body, jnp.zeros((tile,), jnp.bool_))
    out_ref[...] = out


def _ring_hbm_kernel(keys_ref, rings_hbm, out_ref, scratch, sem, *,
                     spec: FilterSpec, n_gen: int, tile: int):
    """Stream the G generation rows per key, double-buffered across g."""
    s = spec.s
    starts, masks = _fingerprints(spec, keys_ref[...])

    def dma(i, g, slot):
        st = _take_scalar(starts, i)
        return pltpu.make_async_copy(
            rings_hbm.at[g, pl.ds(st, s)], scratch.at[slot], sem.at[slot])

    def body(i, acc):
        dma(i, 0, 0).start()
        row = jnp.zeros((s,), jnp.uint32)
        for g in range(n_gen):                  # static unroll over the ring
            slot = g % 2
            if g + 1 < n_gen:
                dma(i, g + 1, (g + 1) % 2).start()   # prefetch next gen
            dma(i, g, slot).wait()
            row = row | scratch[slot]
        m = _mask_row(masks, i, s)
        ok = jnp.all((row & m) == m)
        return jax.lax.dynamic_update_slice(acc, ok[None], (i,))

    out = jax.lax.fori_loop(0, tile, body, jnp.zeros((tile,), jnp.bool_))
    out_ref[...] = out


def ring_contains_vmem(spec: FilterSpec, rings: jnp.ndarray, keys: jnp.ndarray,
                       *, interpret: bool,
                       tile: int = DEFAULT_TILE) -> jnp.ndarray:
    n = keys.shape[0]
    n_gen = rings.shape[0]
    assert n % tile == 0
    kern = functools.partial(_ring_vmem_kernel, spec=spec, n_gen=n_gen,
                             tile=tile)
    return pl.pallas_call(
        kern,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),
            pl.BlockSpec((n_gen, spec.n_words), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.bool_),
        interpret=interpret,
    )(keys, rings)


def ring_contains_hbm(spec: FilterSpec, rings: jnp.ndarray, keys: jnp.ndarray,
                      *, interpret: bool,
                      tile: int = DEFAULT_TILE) -> jnp.ndarray:
    n = keys.shape[0]
    n_gen = rings.shape[0]
    assert n % tile == 0
    kern = functools.partial(_ring_hbm_kernel, spec=spec, n_gen=n_gen,
                             tile=tile)
    return pl.pallas_call(
        kern,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),        # ring stays in HBM
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.bool_),
        scratch_shapes=[
            pltpu.VMEM((2, spec.s), jnp.uint32),      # double buffer
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(keys, rings)
