"""Pallas TPU kernels for the counting quotient filter.

Reuses the PR-3 probe-engine machinery with the table pinned in VMEM:

* **contains** is the whole-tile gather engine: phase 1 hashes the key
  tile in lockstep, then ONE metadata run-scan over the resident table
  (cumulative run-start / occupied counts, shared by every probe in the
  tile), two gathers per probe and a single fused remainder compare — no
  per-key cluster walk, one ``pallas_call`` for the whole batch
  (jaxpr-verified in tests/test_quotient.py);
* **add / remove** are block-sorted sequential-ownership passes: each grid
  step decodes the resident fingerprint multiset, sorts it together with
  its key tile (the same sort-then-place schedule `core.partition` gives
  the Bloom bulk adds) and rebuilds the canonical layout via the SHARED
  tile functions from ``core.quotient`` — the kernel body and the jnp
  reference are literally the same code, which is what makes builds
  bit-identical across engines. TPU grids execute sequentially on a core,
  so the decode+rebuild needs no atomics: one exclusive owner per table,
  the role atomic CAS plays in the GPU quotient filters (DESIGN.md §15);
* inserts/removes are NOT idempotent (duplicates store one fingerprint
  copy each), so padding is **valid-masked** (``ops._pad_keys_valid``),
  never repeat-key; both ops emit their per-key flag array (capacity
  failure / not-found) as a second kernel output — the explicit signal
  the API surfaces instead of silently dropping keys.

The HBM regime is intentionally absent: the run scan reads the whole
table per tile, exactly the access pattern that wants VMEM residency.
Tables beyond the VMEM budget dispatch to the jnp reference (one fused
XLA program) in ``kernels.ops`` — bit-identical by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import quotient as Q
from repro.core.variants import FilterSpec
from repro.kernels.sbf import COOPS, DEFAULT_TILE


def _contains_kernel(keys_ref, filt_ref, out_ref, *, spec: FilterSpec,
                     coop: str = "none"):
    fn = (Q.quotient_contains_coop if coop == "subtile"
          else Q.quotient_contains)
    out_ref[...] = fn(spec, filt_ref[...], keys_ref[...])


def contains_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                  *, interpret: bool,
                  tile: int = DEFAULT_TILE,
                  coop: str = "none") -> jnp.ndarray:
    """Bulk membership, table pinned in VMEM — one launch, fused run scan.
    ``coop="subtile"`` predicates the run scan on the tile-wide home-slot
    ballot (``quotient_contains_coop``) — bit-exact early exit."""
    n = keys.shape[0]
    assert n % tile == 0
    assert coop in COOPS, coop
    return pl.pallas_call(
        functools.partial(_contains_kernel, spec=spec, coop=coop),
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),          # key tile
            pl.BlockSpec((spec.n_words,), lambda i: (0,)),      # whole table
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.bool_),
        interpret=interpret,
    )(keys, filt)


def _update_kernel(keys_ref, valid_ref, filt_ref, out_ref, flag_ref, *,
                   spec: FilterSpec, op: str):
    # Sequential grid: step 0 seeds the output table, later steps RMW it —
    # ownership instead of atomics, as for every mutating kernel here.
    @pl.when(pl.program_id(0) == 0)
    def _seed():
        out_ref[...] = filt_ref[...]

    fp = Q.quotient_hashes(spec, keys_ref[...])
    valid = valid_ref[...].astype(jnp.bool_)
    tile_fn = (Q.quotient_insert_tile if op == "add"
               else Q.quotient_remove_tile)
    table, flags = tile_fn(spec, out_ref[...], fp, valid)
    out_ref[...] = table
    flag_ref[...] = flags


def _update_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                 valid: jnp.ndarray, op: str, tile: int, interpret: bool):
    n = keys.shape[0]
    assert n % tile == 0 and valid.shape == (n,)
    return pl.pallas_call(
        functools.partial(_update_kernel, spec=spec, op=op),
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),
            pl.BlockSpec((tile,), lambda i: (i,)),              # valid mask
            pl.BlockSpec((spec.n_words,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((spec.n_words,), lambda i: (0,)),
            pl.BlockSpec((tile,), lambda i: (i,)),              # per-key flag
        ],
        out_shape=[
            jax.ShapeDtypeStruct((spec.n_words,), jnp.uint32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
        ],
        interpret=interpret,
    )(keys, valid, filt)


def add_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
             valid: jnp.ndarray, *, interpret: bool,
             tile: int = Q.QUOTIENT_ADD_TILE):
    """Bulk decode+rebuild insert. Returns (table, ok) — ``ok[i]=False``
    is the explicit table-full failure signal for key i."""
    return _update_vmem(spec, filt, keys, valid, "add", tile, interpret)


def remove_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                valid: jnp.ndarray, *, interpret: bool,
                tile: int = Q.QUOTIENT_ADD_TILE):
    """Bulk delete. Returns (table, found) — found=False means no stored
    copy of the key's fingerprint was left to clear."""
    return _update_vmem(spec, filt, keys, valid, "remove", tile, interpret)
