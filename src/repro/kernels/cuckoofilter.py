"""Pallas TPU kernels for the bucketed cuckoo fingerprint filter.

Reuses the PR-3 probe-engine machinery with the table pinned in VMEM:

* **contains** is the whole-tile gather engine: phase 1 hashes the key tile
  in lockstep, then ONE flat gather per candidate bucket and one fused
  slot compare — no per-key loop, one ``pallas_call`` for the whole batch
  (jaxpr-verified in tests/test_cuckoo.py);
* **add / remove** are block-sorted sequential-ownership passes: each grid
  step stably sorts its key tile by primary bucket (same-bucket RMWs
  coalesce into runs) and applies the bounded-kick insert / guarded clear
  chain via the SHARED tile functions from ``core.fingerprint`` — the
  kernel body and the jnp reference are literally the same code, which is
  what makes builds bit-identical across engines. TPU grids execute
  sequentially on a core, so a kick chain that crosses bucket-partition
  boundaries still has an exclusive owner — the role atomic CAS plays in
  the GPU cuckoo implementations (DESIGN.md §13);
* inserts/removes are NOT idempotent, so padding is **valid-masked**
  (``ops._pad_keys_valid``), never repeat-key; both ops emit their per-key
  flag array (insert failure / not-found) as a second kernel output —
  the explicit signal the API surfaces instead of silently dropping keys.

The HBM regime is intentionally absent: a kick chain is a data-dependent
pointer chase, the one access pattern DMA block streaming cannot pipeline.
Tables beyond the VMEM budget dispatch to the jnp reference (one fused XLA
program) in ``kernels.ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import fingerprint as F
from repro.core.variants import FilterSpec
from repro.kernels.sbf import DEFAULT_TILE

# The contains schedules: "none" the plain bucket probe, "subtile" its
# bit-exact tile-wide early exit.
COOPS = ("none", "subtile")


def _contains_kernel(keys_ref, filt_ref, out_ref, *, spec: FilterSpec,
                     coop: str = "none"):
    fn = F.cuckoo_contains_coop if coop == "subtile" else F.cuckoo_contains
    out_ref[...] = fn(spec, filt_ref[...], keys_ref[...])


def contains_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                  *, interpret: bool,
                  tile: int = DEFAULT_TILE,
                  coop: str = "none") -> jnp.ndarray:
    """Bulk membership, table pinned in VMEM — one launch, gather probe.
    ``coop="subtile"`` swaps in the early-exit two-phase bucket probe
    (``cuckoo_contains_coop``) — bit-exact, alternate-bucket gather skipped
    when the whole tile already hit in its primary buckets."""
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

    b1, fp, rng = F.cuckoo_hashes(spec, keys_ref[...])
    valid = valid_ref[...].astype(jnp.bool_)
    tile_fn = (F.cuckoo_insert_tile if op == "add"
               else F.cuckoo_remove_tile)
    table, flags = tile_fn(spec, out_ref[...], b1, fp, rng, valid)
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
             tile: int = F.CUCKOO_ADD_TILE):
    """Bulk block-sorted insert. Returns (table, ok) — ``ok[i]=False`` is
    the explicit kick-overflow failure signal for key i."""
    return _update_vmem(spec, filt, keys, valid, "add", tile, interpret)


def remove_vmem(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                valid: jnp.ndarray, *, interpret: bool,
                tile: int = F.CUCKOO_ADD_TILE):
    """Bulk delete. Returns (table, found) — found=False means the key's
    fingerprint was absent (nothing cleared)."""
    return _update_vmem(spec, filt, keys, valid, "remove", tile, interpret)
