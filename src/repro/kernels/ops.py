"""jit'd dispatch wrappers around the Pallas filter kernels.

``bloom_contains`` / ``bloom_add`` pick the kernel from what the call
shows, never from a tuner or a file:

* variant: blocked variants -> ``kernels.sbf``; classical ->
  ``kernels.cbf``;
* regime: filter words <= ``VMEM_FILTER_BYTES`` -> ``*_vmem`` (the whole
  filter pinned in VMEM, keys walked Θ at a time; the cache-resident
  analogue), else ``*_hbm`` (rows streamed by DMA: the probe through a
  fixed ring of ``sbf.HBM_PROBE_DEPTH`` slots, the update over row-sorted
  tiles) — the paper's §5.3/§5.2 split. Each regime has one schedule, and
  it is the one the chip compiles, so the CPU's interpret-mode tests run
  the chip's kernels. Banks follow the same rule on the bank's bytes;
* ``bloom_add_partitioned`` offers the partitioned ownership path — our
  beyond-paper TPU-native optimization. The partition step is
  **device-resident by default** (``core.partition.partition_jit``):
  jit/scan-compatible with no host sync, overflow-checked with automatic
  capacity escalation (concrete callers) or a vectorized residual pass
  (traced callers). The host numpy partition survives as the
  ``partition="host"`` fallback;
* the ``*_jit`` entry points are a **cached-jit dispatch layer**: one
  compiled executable per static configuration (spec/layout/regime/tile/
  batch shape), with ``donate_argnums`` on the filter buffer so
  streaming bulk adds update the filter in place — no O(m) copy and no
  re-trace per call. Donation invalidates the caller's input array
  (`x.is_deleted()`); pass ``donate=False`` to keep it;
* ``counting_*`` dispatch the counting-filter kernels. Counting updates are
  NOT OR-idempotent, so their padding switches from repeat-last-key to
  **valid-masking** (``_pad_keys_valid``): padded slots carry valid=0 and
  contribute an all-zero increment row.

On the CPU the kernels run in interpret mode (kernel body executed with
jnp semantics) — bit-exact, which is what the test sweeps rely on.
Everywhere else they are compiled. Families whose kernels the chip's
compiler refuses (cuckoo, quotient, ring, classical) run XLA on TPU
through their engines; calling their kernels here on a TPU raises
instead (:func:`_interpret_only`); their ``coop="auto"`` resolves through
the perfmodel (:func:`_resolve_coop_fp`). Dispatches that choose between
a kernel and the XLA reference by size record which one ran
(:func:`dispatched`).
"""
from __future__ import annotations

import collections
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hashing as H
from repro.core import partition as P
from repro.core import variants as V
from repro.core.variants import FilterSpec
from repro.core import fingerprint as F
from repro.core import quotient as Q
from repro.kernels import cbf as cbf_k
from repro.kernels import countingbf as cnt_k
from repro.kernels import cuckoofilter as ckoo_k
from repro.kernels import quotientfilter as qf_k
from repro.kernels import ring as ring_k
from repro.kernels import sbf as sbf_k
from repro.kernels.sbf import (DEFAULT_TILE, Layout, VMEM_FILTER_BYTES,
                               default_layout)


def _interpret() -> bool:
    """Interpret mode on the CPU only; every other backend compiles."""
    return jax.default_backend() == "cpu"


def _interpret_only(family: str) -> bool:
    """``interpret`` for a family whose kernels do not lower for the TPU:
    its engine runs the XLA implementation there, and a direct call on a
    TPU is refused here rather than deep in the compiler."""
    if not _interpret():
        raise NotImplementedError(
            f"the {family} Pallas kernels do not lower for "
            f"{jax.default_backend()}; use the engine's XLA implementation")
    return True


# (op, impl) -> traces that dispatched it. ``impl`` is the kernel regime
# ("pallas-vmem", "pallas-hbm") or "xla" where a size check swapped the
# kernel for the jnp reference.
_DISPATCHED: "collections.Counter[Tuple[str, str]]" = collections.Counter()


def dispatched() -> Dict[Tuple[str, str], int]:
    """Snapshot of which implementation each dispatch traced to."""
    return dict(_DISPATCHED)


def _ran(op: str, impl: str) -> None:
    _DISPATCHED[(op, impl)] += 1


def kernel_supported(spec: FilterSpec) -> bool:
    return spec.variant in ("cbf", "bbf", "rbbf", "sbf", "csbf",
                            "countingbf")


def _regime(spec: FilterSpec, regime: str) -> str:
    if regime != "auto":
        return regime
    return "vmem" if spec.storage_words * 4 <= VMEM_FILTER_BYTES else "hbm"


def _clamp_tile(n: int, tile: int) -> int:
    """Shrink the key tile for small batches: next pow2 >= n, floor 8 (the
    sublane width) — so a 10-key call doesn't pad to a 256-wide tile."""
    return min(tile, max(8, 1 << int(np.ceil(np.log2(n)))))


def _pad_keys(keys: jnp.ndarray, tile: int) -> jnp.ndarray:
    """Pad to a tile multiple by repeating the last key — valid ONLY for the
    OR-idempotent bit-filter ops: a repeated add ORs the same mask twice
    (no-op) and a repeated *contains* result is simply discarded. Counting
    updates must use :func:`_pad_keys_valid` instead."""
    n = keys.shape[0]
    pad = (-n) % tile
    if pad == 0:
        return keys
    return jnp.concatenate([keys, jnp.broadcast_to(keys[-1:], (pad, 2))])


def _pad_keys_valid(keys: jnp.ndarray, tile: int,
                    valid: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pad to a tile multiple with an explicit validity mask.

    Counting increments/decrements are not idempotent, so repeat-key padding
    would double-count; padded slots instead carry valid=0 (the kernels zero
    their increment rows). Returns (padded keys, (n_padded,) uint8 valid)."""
    n = keys.shape[0]
    if valid is None:
        valid = jnp.ones((n,), jnp.uint8)
    pad = (-n) % tile
    if pad == 0:
        return keys, valid
    return (jnp.concatenate([keys, jnp.zeros((pad, 2), keys.dtype)]),
            jnp.concatenate([valid, jnp.zeros((pad,), jnp.uint8)]))


def bloom_contains(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                   layout: Optional[Layout] = None, regime: str = "auto",
                   tile: int = DEFAULT_TILE) -> jnp.ndarray:
    assert not spec.is_counting, "use counting_contains for countingbf"
    n = keys.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    tile = _clamp_tile(n, tile)
    padded = _pad_keys(keys, tile)
    interp = _interpret()
    if spec.variant == "cbf":
        out = cbf_k.contains_vmem(spec, filt, padded, tile=tile,
                                  interpret=_interpret_only("cbf"))
    elif _regime(spec, regime) == "vmem":
        _ran("bloom_contains", "pallas-vmem")
        out = sbf_k.contains_vmem(
            spec, filt, padded, layout or default_layout(spec, "contains"),
            tile=tile, interpret=interp)
    else:
        _ran("bloom_contains", "pallas-hbm")
        out = sbf_k.contains_hbm(spec, filt, padded, tile=tile,
                                 interpret=interp)
    return out[:n]


def bloom_add(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
              layout: Optional[Layout] = None, regime: str = "auto",
              tile: int = DEFAULT_TILE) -> jnp.ndarray:
    assert not spec.is_counting, "use counting_add/counting_remove"
    n = keys.shape[0]
    if n == 0:
        return filt
    tile = _clamp_tile(n, tile)
    padded = _pad_keys(keys, tile)
    interp = _interpret()
    if spec.variant == "cbf":
        return cbf_k.add_vmem(spec, filt, padded, tile=tile,
                              interpret=_interpret_only("cbf"))
    if _regime(spec, regime) == "vmem":
        _ran("bloom_add", "pallas-vmem")
        return sbf_k.add_vmem(
            spec, filt, padded, layout or default_layout(spec, "add"),
            tile=tile, interpret=interp)
    _ran("bloom_add", "pallas-hbm")
    return sbf_k.add_hbm(spec, filt, padded, tile=tile, interpret=interp)


# ---------------------------------------------------------------------------
# Sharded segments — one device's block range of a block-sharded filter
# ---------------------------------------------------------------------------
# Inside the sharded engine's shard_map body, the owner of a segment runs
# the same two phases as one chip's filter in the HBM regime: phase 1 on
# the segment's own spec (its block index is the filter's, masked to the
# segment), phase 2 the HBM row kernels. ``valid`` marks the exchange's
# slots that carry a key: padded slots set nothing and make no row DMA.

def segment_add(seg_spec: FilterSpec, seg_words: jnp.ndarray,
                keys: jnp.ndarray, valid: jnp.ndarray,
                tile: int = DEFAULT_TILE) -> jnp.ndarray:
    n = keys.shape[0]
    tile = _clamp_tile(n, tile)
    keys, valid = _pad_keys_valid(keys, tile, valid)
    _ran("sharded_add", "pallas-hbm")
    starts, masks = sbf_k.fingerprints(seg_spec, keys)
    return sbf_k.update_rows_hbm(seg_words, starts, masks, tile=tile,
                                 interpret=_interpret(), valid=valid > 0)


def segment_contains(seg_spec: FilterSpec, seg_words: jnp.ndarray,
                     keys: jnp.ndarray, valid: jnp.ndarray,
                     tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """(n,) bool; a padded slot's answer is meaningless."""
    n = keys.shape[0]
    tile = _clamp_tile(n, tile)
    keys, valid = _pad_keys_valid(keys, tile, valid)
    _ran("sharded_contains", "pallas-hbm")
    starts, masks = sbf_k.fingerprints(seg_spec, keys)
    return sbf_k.probe_rows_hbm(seg_words, starts, masks, tile=tile,
                                interpret=_interpret(), valid=valid > 0)[:n]


# ---------------------------------------------------------------------------
# Bank dispatch — B small filters, one fused device op (FilterBank)
# ---------------------------------------------------------------------------
# Native form: flat routed keys (keys (N, 2), member (N,)). A VMEM-resident
# bank goes through the single-launch bank kernels; a bank too large for
# VMEM falls back to the jnp super-filter reference (still ONE fused XLA
# op, no per-member loop). Padding follows the usual contract: repeat-last
# for reads, valid-masking for writes (mandatory for counting, and used for
# bit adds too since routed batches already carry a mask).

def bank_vmem_resident(spec: FilterSpec, bank: int) -> bool:
    """Does a B-member bank fit the VMEM filter budget whole?"""
    return bank * spec.storage_words * 4 <= VMEM_FILTER_BYTES


def _pad_flat(keys: jnp.ndarray, member: jnp.ndarray, tile: int):
    """Repeat-last padding of (keys, member) — reads only."""
    n = keys.shape[0]
    pad = (-n) % tile
    if pad == 0:
        return keys, member
    return (jnp.concatenate([keys, jnp.broadcast_to(keys[-1:], (pad, 2))]),
            jnp.concatenate([member, jnp.broadcast_to(member[-1:], (pad,))]))


def _pad_flat_valid(keys: jnp.ndarray, member: jnp.ndarray,
                    valid: Optional[jnp.ndarray], tile: int):
    """Zero-pad (keys, member) with an explicit validity mask — writes."""
    n = keys.shape[0]
    if valid is None:
        valid = jnp.ones((n,), jnp.uint8)
    valid = valid.astype(jnp.uint8)
    pad = (-n) % tile
    if pad == 0:
        return keys, member, valid
    return (jnp.concatenate([keys, jnp.zeros((pad, 2), keys.dtype)]),
            jnp.concatenate([member, jnp.zeros((pad,), member.dtype)]),
            jnp.concatenate([valid, jnp.zeros((pad,), jnp.uint8)]))


def bloom_bank_contains(spec: FilterSpec, bank: jnp.ndarray,
                        keys: jnp.ndarray, member: jnp.ndarray,
                        layout: Optional[Layout] = None,
                        tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """(N,) bool membership of flat routed keys against a (B, n_words) bank."""
    assert not spec.is_counting
    n = keys.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    B = bank.shape[0]
    if spec.variant == "cbf" or not bank_vmem_resident(spec, B):
        _ran("bank_contains", "xla")
        return V.bank_contains_rows(spec, bank, keys,
                                    jnp.asarray(member, jnp.int32))
    _ran("bank_contains", "pallas-vmem")
    tile = _clamp_tile(n, tile)
    pk, pm = _pad_flat(keys, jnp.asarray(member, jnp.int32), tile)
    out = sbf_k.bank_contains_vmem(
        spec, bank, pk, pm, layout or default_layout(spec, "contains"),
        tile=tile, interpret=_interpret())
    return out[:n]


def bloom_bank_add(spec: FilterSpec, bank: jnp.ndarray, keys: jnp.ndarray,
                   member: jnp.ndarray, valid: Optional[jnp.ndarray] = None,
                   layout: Optional[Layout] = None,
                   tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Valid-masked bulk OR of flat routed keys into a (B, n_words) bank."""
    assert not spec.is_counting
    n = keys.shape[0]
    if n == 0:
        return bank
    B = bank.shape[0]
    member = jnp.asarray(member, jnp.int32)
    if spec.variant == "cbf" or not bank_vmem_resident(spec, B):
        _ran("bank_add", "xla")
        return V.bank_add_rows(spec, bank, keys, member, valid=valid)
    _ran("bank_add", "pallas-vmem")
    tile = _clamp_tile(n, tile)
    pk, pm, pv = _pad_flat_valid(keys, member, valid, tile)
    return sbf_k.bank_add_vmem(
        spec, bank, pk, pm, pv, layout or default_layout(spec, "add"),
        tile=tile, interpret=_interpret())


def counting_bank_update(spec: FilterSpec, bank: jnp.ndarray,
                         keys: jnp.ndarray, member: jnp.ndarray,
                         op: str = "add",
                         valid: Optional[jnp.ndarray] = None,
                         layout: Optional[Layout] = None,
                         tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Flat routed counter increment/decrement of a (B, 4*n_words) bank."""
    assert spec.is_counting
    n = keys.shape[0]
    if n == 0:
        return bank
    B = bank.shape[0]
    member = jnp.asarray(member, jnp.int32)
    if not bank_vmem_resident(spec, B):
        _ran("counting_bank_update", "xla")
        return V.bank_counting_update(spec, bank, keys, member, valid, op)
    _ran("counting_bank_update", "pallas-vmem")
    tile = _clamp_tile(n, tile)
    pk, pm, pv = _pad_flat_valid(keys, member, valid, tile)
    return cnt_k.bank_update_vmem(
        spec, bank, pk, pm, pv, op, layout=layout, tile=tile,
        interpret=_interpret())


def counting_bank_contains(spec: FilterSpec, bank: jnp.ndarray,
                           keys: jnp.ndarray, member: jnp.ndarray,
                           tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """(N,) bool occupancy membership against a counter bank."""
    assert spec.is_counting
    n = keys.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    B = bank.shape[0]
    member = jnp.asarray(member, jnp.int32)
    if not bank_vmem_resident(spec, B):
        _ran("counting_bank_contains", "xla")
        return V.bank_counting_contains(spec, bank, keys, member)
    _ran("counting_bank_contains", "pallas-vmem")
    tile = _clamp_tile(n, tile)
    pk, pm = _pad_flat(keys, member, tile)
    out = cnt_k.bank_contains_vmem(spec, bank, pk, pm, tile=tile,
                                   interpret=_interpret())
    return out[:n]


# ---------------------------------------------------------------------------
# Partitioned ownership path — device-resident by default
# ---------------------------------------------------------------------------

def _default_capacity(n: int, n_segments: int) -> int:
    """mean * 4 headroom (~overflow-free for uniform hashes), 8-aligned."""
    cap = max(4 * n // n_segments, 8)
    return (cap + 7) & ~7


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _partition_device(spec: FilterSpec, keys: jnp.ndarray, n_segments: int,
                      capacity: Optional[int]) -> P.JitPartition:
    """partition_jit with overflow handling.

    Concrete keys: inspect the overflow count and escalate capacity
    (doubling) until every key fits — bounded because capacity >= n can
    never overflow. Traced keys (under jit/scan): capacity must stay
    static, so return the partition as-is; the caller applies the
    residual pass over the dropped keys.
    """
    n = keys.shape[0]
    cap = capacity or _default_capacity(n, n_segments)
    part = P.partition_jit(spec, keys, n_segments, cap)
    if _is_traced(part.overflow) or capacity is not None:
        return part
    while int(part.overflow) > 0:
        cap = min(2 * cap, (n + 7) & ~7)     # cap >= n cannot overflow
        part = P.partition_jit(spec, keys, n_segments, cap)
    return part


def _residual_or(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                 keep: jnp.ndarray) -> jnp.ndarray:
    """Vectorized OR of the *dropped* keys' masks (kept keys contribute
    all-zero rows — OR no-ops), so the partitioned result stays exact even
    when a traced caller cannot escalate capacity. Device-resident."""
    h1, h2 = H.hash_keys(keys)
    blk = H.block_index(h2, spec.n_blocks).astype(jnp.int32)
    masks = V.block_patterns(spec, h1) * (~keep)[:, None].astype(jnp.uint32)
    return V.or_rows(spec, filt, blk, masks)


def bloom_add_partitioned(spec: FilterSpec, filt: jnp.ndarray, keys,
                          n_segments: int = 8, capacity: Optional[int] = None,
                          partition: str = "jit") -> jnp.ndarray:
    """Beyond-paper path: radix-partition keys by filter segment, then run a
    PARALLEL-grid kernel where each step owns its segment exclusively.

    ``partition="jit"`` (default) keeps the partition on device —
    jit/scan-compatible, no host sync; overflow beyond the static capacity
    escalates (concrete callers) or falls through to a vectorized residual
    OR of the dropped keys (traced callers), so keys are NEVER silently
    lost. ``partition="host"`` is the numpy fallback (exact capacity, host
    round-trip).
    """
    assert spec.variant != "cbf", "classical filter has no block locality"
    assert not spec.is_counting, "use counting_update_partitioned"
    if partition == "host":
        keys_np = np.asarray(keys, dtype=np.uint32)
        by_seg, valid, _ = P.partition_host(spec, keys_np, n_segments)
        return sbf_k.add_partitioned(spec, filt, jnp.asarray(by_seg),
                                     jnp.asarray(valid), n_segments,
                                     interpret=_interpret())
    keys = jnp.asarray(keys)
    part = _partition_device(spec, keys, n_segments, capacity)
    out = sbf_k.add_partitioned(spec, filt, part.keys_by_seg, part.valid,
                                n_segments, interpret=_interpret())
    if not _is_traced(part.overflow):
        # Concrete: the escalation loop guarantees overflow == 0 unless the
        # caller pinned capacity — either way, don't trace the residual
        # graph (lax.cond traces BOTH branches) for a branch that cannot
        # fire on this call.
        if int(part.overflow) == 0:
            return out
        return _residual_or(spec, out, keys, part.keep)
    return jax.lax.cond(part.overflow > 0,
                        lambda f: _residual_or(spec, f, keys, part.keep),
                        lambda f: f, out)


# ---------------------------------------------------------------------------
# Cached-jit dispatch layer (donated filter buffer)
# ---------------------------------------------------------------------------

from collections import OrderedDict

_JIT_CACHE: "OrderedDict" = OrderedDict()
_JIT_CACHE_MAX = 256     # LRU bound: streaming callers with ragged batch
                         # shapes must not grow executables without limit


def jit_cache_info() -> Tuple[int, ...]:
    """(#cached executables,) — exposed for tests/diagnostics."""
    return (len(_JIT_CACHE),)


def jit_cache_clear() -> None:
    _JIT_CACHE.clear()


def _cached_jit(key, make):
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = _JIT_CACHE[key] = make()
        if len(_JIT_CACHE) > _JIT_CACHE_MAX:
            _JIT_CACHE.popitem(last=False)
    else:
        _JIT_CACHE.move_to_end(key)
    return fn


def bloom_add_jit(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                  *, layout: Optional[Layout] = None, regime: str = "auto",
                  tile: int = DEFAULT_TILE,
                  donate: bool = True) -> jnp.ndarray:
    """Cached-jit bulk add with the filter buffer DONATED to the update:
    repeated streaming adds reuse one compiled executable per static
    configuration and alias the output onto the input filter — no O(m)
    copy, no per-call retrace. The caller's ``filt`` array is consumed
    (``filt.is_deleted()`` afterwards); pass ``donate=False`` to keep it.
    """
    keys = jnp.asarray(keys)
    key = ("bloom_add", spec, layout, regime, tile, keys.shape,
           str(keys.dtype), bool(donate))

    def make():
        def run(f, k):
            return bloom_add(spec, f, k, layout=layout, regime=regime,
                             tile=tile)
        return jax.jit(run, donate_argnums=(0,) if donate else ())

    return _cached_jit(key, make)(filt, keys)


def bloom_contains_jit(spec: FilterSpec, filt: jnp.ndarray,
                       keys: jnp.ndarray, *, layout: Optional[Layout] = None,
                       regime: str = "auto",
                       tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Cached-jit bulk membership (read-only — nothing to donate)."""
    keys = jnp.asarray(keys)
    key = ("bloom_contains", spec, layout, regime, tile, keys.shape,
           str(keys.dtype))

    def make():
        def run(f, k):
            return bloom_contains(spec, f, k, layout=layout, regime=regime,
                                  tile=tile)
        return jax.jit(run)

    return _cached_jit(key, make)(filt, keys)


def counting_update_jit(spec: FilterSpec, filt: jnp.ndarray,
                        keys: jnp.ndarray, op: str = "add", *,
                        layout: Optional[Layout] = None, regime: str = "auto",
                        tile: int = DEFAULT_TILE,
                        donate: bool = True) -> jnp.ndarray:
    """Cached-jit counting increment/decrement with a donated counter
    buffer — the counting analogue of :func:`bloom_add_jit`."""
    keys = jnp.asarray(keys)
    key = ("counting_update", spec, op, layout, regime, tile, keys.shape,
           str(keys.dtype), bool(donate))

    def make():
        fn = counting_add if op == "add" else counting_remove
        def run(f, k):
            return fn(spec, f, k, layout=layout, regime=regime, tile=tile)
        return jax.jit(run, donate_argnums=(0,) if donate else ())

    return _cached_jit(key, make)(filt, keys)


# ---------------------------------------------------------------------------
# Counting-filter dispatch (valid-masked padding; see module docstring)
# ---------------------------------------------------------------------------

def _counting_update(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                     op: str, layout: Optional[Layout], regime: str,
                     tile: int, valid: Optional[jnp.ndarray]) -> jnp.ndarray:
    assert spec.is_counting
    n = keys.shape[0]
    if n == 0:
        return filt
    tile = _clamp_tile(n, tile)
    padded, pvalid = _pad_keys_valid(keys, tile, valid)
    interp = _interpret()
    if _regime(spec, regime) == "vmem":
        _ran(f"counting_{op}", "pallas-vmem")
        return cnt_k.update_vmem(spec, filt, padded, pvalid, op,
                                 layout=layout, tile=tile, interpret=interp)
    _ran(f"counting_{op}", "pallas-hbm")
    return cnt_k.update_hbm(spec, filt, padded, pvalid, op, tile=tile,
                            interpret=interp)


def counting_add(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                 layout: Optional[Layout] = None, regime: str = "auto",
                 tile: int = DEFAULT_TILE,
                 valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Bulk saturating increment of each key's k counters."""
    return _counting_update(spec, filt, keys, "add", layout, regime, tile,
                            valid)


def counting_remove(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                    layout: Optional[Layout] = None, regime: str = "auto",
                    tile: int = DEFAULT_TILE,
                    valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Bulk guarded decrement (0 floors, saturated counters stick)."""
    return _counting_update(spec, filt, keys, "remove", layout, regime, tile,
                            valid)


def counting_contains(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                      layout: Optional[Layout] = None, regime: str = "auto",
                      tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Bulk membership against the counter occupancy (read-only, so
    repeat-key padding is safe here — results are sliced off)."""
    assert spec.is_counting
    n = keys.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    tile = _clamp_tile(n, tile)
    padded = _pad_keys(keys, tile)
    interp = _interpret()
    if _regime(spec, regime) == "vmem":
        _ran("counting_contains", "pallas-vmem")
        out = cnt_k.contains_vmem(spec, filt, padded, layout=layout,
                                  tile=tile, interpret=interp)
    else:
        _ran("counting_contains", "pallas-hbm")
        out = cnt_k.contains_hbm(spec, filt, padded, tile=tile,
                                 interpret=interp)
    return out[:n]


def counting_decay(spec: FilterSpec, filt: jnp.ndarray) -> jnp.ndarray:
    """One aging step (every nonzero counter -1) as a PARALLEL Pallas pass."""
    assert spec.is_counting
    return cnt_k.decay(spec, filt, interpret=_interpret())


def _residual_counting(spec: FilterSpec, filt: jnp.ndarray,
                       keys: jnp.ndarray, keep: jnp.ndarray,
                       op: str) -> jnp.ndarray:
    """Valid-masked vectorized update of the dropped keys (kept keys carry
    valid=0 — counting updates are not idempotent, so the residual must
    touch ONLY the overflow set)."""
    dropped = (~keep).astype(jnp.uint8)
    if op == "add":
        return V.counting_add(spec, filt, keys, valid=dropped)
    return V.counting_remove(spec, filt, keys, valid=dropped)


def counting_update_partitioned(spec: FilterSpec, filt: jnp.ndarray, keys,
                                op: str = "add", n_segments: int = 8,
                                capacity: Optional[int] = None,
                                partition: str = "jit") -> jnp.ndarray:
    """Ownership path for counter updates: radix-partition keys by segment,
    then a PARALLEL grid where each step owns its counter segment — the
    atomics-free route for increments AND decrements. Device-resident
    partition by default, same overflow contract as
    :func:`bloom_add_partitioned`."""
    assert spec.is_counting
    if partition == "host":
        keys_np = np.asarray(keys, dtype=np.uint32)
        by_seg, valid, _ = P.partition_host(spec, keys_np, n_segments)
        return cnt_k.update_partitioned(spec, filt, jnp.asarray(by_seg),
                                        jnp.asarray(valid), n_segments, op,
                                        interpret=_interpret())
    keys = jnp.asarray(keys)
    part = _partition_device(spec, keys, n_segments, capacity)
    out = cnt_k.update_partitioned(spec, filt, part.keys_by_seg, part.valid,
                                   n_segments, op,
                                   interpret=_interpret())
    if not _is_traced(part.overflow):
        if int(part.overflow) == 0:
            return out
        return _residual_counting(spec, out, keys, part.keep, op)
    return jax.lax.cond(
        part.overflow > 0,
        lambda f: _residual_counting(spec, f, keys, part.keep, op),
        lambda f: f, out)


# ---------------------------------------------------------------------------
# Cuckoo fingerprint dispatch (valid-masked padding; inserts/removes are
# not idempotent). No HBM regime: a kick chain is a data-dependent pointer
# chase DMA streaming can't pipeline — tables beyond the VMEM budget run
# the jnp reference (same tile schedule, so results stay bit-identical).
# ---------------------------------------------------------------------------

def cuckoo_vmem_resident(spec: FilterSpec) -> bool:
    return spec.n_words * 4 <= VMEM_FILTER_BYTES


def _resolve_coop_fp(spec: FilterSpec, coop: str, tile: int) -> str:
    """``"auto"`` cooperation for the fingerprint/quotient engines: the
    lru-cached perfmodel helper (all-static, trace-time safe)."""
    if coop != "auto":
        assert coop in ckoo_k.COOPS, coop
        return coop
    from repro import perfmodel as PM
    return PM.choose_coop(spec, "contains", "vmem", tile)


def cuckoo_contains(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                    tile: int = DEFAULT_TILE,
                    coop: str = "auto") -> jnp.ndarray:
    """(n,) bool two-bucket membership; ONE pallas_call for the batch.
    ``coop="subtile"`` gates the alternate-bucket gather on the tile-wide
    primary-hit ballot (bit-exact early exit)."""
    assert spec.is_fingerprint
    n = keys.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    if not cuckoo_vmem_resident(spec):
        _ran("cuckoo_contains", "xla")
        return F.cuckoo_contains(spec, filt, keys)
    _ran("cuckoo_contains", "pallas-vmem")
    tile = _clamp_tile(n, tile or DEFAULT_TILE)
    padded = _pad_keys(keys, tile)              # reads: repeat-last is safe
    out = ckoo_k.contains_vmem(spec, filt, padded, tile=tile,
                               interpret=_interpret_only("cuckoo"),
                               coop=_resolve_coop_fp(spec, coop, tile))
    return out[:n]


def _cuckoo_tile(n: int, tile: Optional[int]) -> int:
    """The bulk-update chunk size. MUST mirror ``fingerprint.cuckoo_add``'s
    trace-time chunking (chunks of T over the unpadded batch): a batch at
    or under T runs as one tile (padded up to the 8-key floor), a larger
    one pads to a multiple of T — so the (sort, insert) order, and hence
    the resulting table, is bit-identical between jnp and Pallas."""
    T = tile or F.CUCKOO_ADD_TILE
    if n <= T:
        return max(8, 1 << int(np.ceil(np.log2(max(n, 1)))))
    return T


def _cuckoo_update(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                   op: str, valid: Optional[jnp.ndarray],
                   tile: Optional[int]):
    assert spec.is_fingerprint
    n = keys.shape[0]
    if n == 0:
        return filt, jnp.zeros((0,), jnp.bool_)
    T = tile or F.CUCKOO_ADD_TILE
    if not cuckoo_vmem_resident(spec):
        _ran(f"cuckoo_{op}", "xla")
        fn = F.cuckoo_add if op == "add" else F.cuckoo_remove
        return fn(spec, filt, keys, valid=valid, tile=T)
    _ran(f"cuckoo_{op}", "pallas-vmem")
    eff = _cuckoo_tile(n, tile)
    pk, pv = _pad_keys_valid(keys, eff, valid)
    fn = ckoo_k.add_vmem if op == "add" else ckoo_k.remove_vmem
    out, flags = fn(spec, filt, pk, pv, tile=eff,
                    interpret=_interpret_only("cuckoo"))
    return out, flags[:n]


def cuckoo_add(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
               valid: Optional[jnp.ndarray] = None,
               tile: Optional[int] = None):
    """Bulk block-sorted insert. Returns ``(table, ok)``; ``ok[i]=False``
    is the explicit bounded-kick failure signal (never silently dropped —
    the API accumulates it into ``Filter.insert_failures``)."""
    return _cuckoo_update(spec, filt, keys, "add", valid, tile)


def cuckoo_remove(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                  valid: Optional[jnp.ndarray] = None,
                  tile: Optional[int] = None):
    """Bulk delete (one slot cleared per key). Returns (table, found)."""
    return _cuckoo_update(spec, filt, keys, "remove", valid, tile)


# ---------------------------------------------------------------------------
# Quotient filter dispatch (valid-masked padding; inserts/removes are not
# idempotent). No HBM regime: the run scan reads the whole table per tile —
# tables beyond the VMEM budget run the jnp reference (the decode+rebuild
# layout is a pure function of the stored multiset, so results stay
# bit-identical for every tile schedule).
# ---------------------------------------------------------------------------

def quotient_vmem_resident(spec: FilterSpec) -> bool:
    return spec.n_words * 4 <= VMEM_FILTER_BYTES


def quotient_contains(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                      tile: int = DEFAULT_TILE,
                      coop: str = "auto") -> jnp.ndarray:
    """(n,) bool run-scan membership; ONE pallas_call for the batch.
    ``coop="subtile"`` predicates the run scan on the tile-wide home-slot
    ballot (bit-exact early exit)."""
    assert spec.is_quotient
    n = keys.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    if not quotient_vmem_resident(spec):
        _ran("quotient_contains", "xla")
        return Q.quotient_contains(spec, filt, keys)
    _ran("quotient_contains", "pallas-vmem")
    tile = _clamp_tile(n, tile or DEFAULT_TILE)
    padded = _pad_keys(keys, tile)              # reads: repeat-last is safe
    out = qf_k.contains_vmem(spec, filt, padded, tile=tile,
                             interpret=_interpret_only("quotient"),
                             coop=_resolve_coop_fp(spec, coop, tile))
    return out[:n]


def _quotient_tile(n: int, tile: Optional[int]) -> int:
    """The bulk-update chunk size. Mirrors ``quotient.quotient_add``'s
    trace-time chunking (chunks of T over the unpadded batch) for schedule
    parity with the jnp reference — and unlike cuckoo, the quotient build
    is tile-size independent anyway (pure function of the multiset)."""
    T = tile or Q.QUOTIENT_ADD_TILE
    if n <= T:
        return max(8, 1 << int(np.ceil(np.log2(max(n, 1)))))
    return T


def _quotient_update(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                     op: str, valid: Optional[jnp.ndarray],
                     tile: Optional[int]):
    assert spec.is_quotient
    n = keys.shape[0]
    if n == 0:
        return filt, jnp.zeros((0,), jnp.bool_)
    T = tile or Q.QUOTIENT_ADD_TILE
    if not quotient_vmem_resident(spec):
        _ran(f"quotient_{op}", "xla")
        fn = Q.quotient_add if op == "add" else Q.quotient_remove
        return fn(spec, filt, keys, valid=valid, tile=T)
    _ran(f"quotient_{op}", "pallas-vmem")
    eff = _quotient_tile(n, tile)
    pk, pv = _pad_keys_valid(keys, eff, valid)
    fn = qf_k.add_vmem if op == "add" else qf_k.remove_vmem
    out, flags = fn(spec, filt, pk, pv, tile=eff,
                    interpret=_interpret_only("quotient"))
    return out, flags[:n]


def quotient_add(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                 valid: Optional[jnp.ndarray] = None,
                 tile: Optional[int] = None):
    """Bulk decode+rebuild insert. Returns ``(table, ok)``; ``ok[i]=False``
    is the explicit table-full signal (never silently dropped — the API
    accumulates it into ``Filter.insert_failures``)."""
    return _quotient_update(spec, filt, keys, "add", valid, tile)


def quotient_remove(spec: FilterSpec, filt: jnp.ndarray, keys: jnp.ndarray,
                    valid: Optional[jnp.ndarray] = None,
                    tile: Optional[int] = None):
    """Bulk delete (one fingerprint copy per key). Returns (table, found)."""
    return _quotient_update(spec, filt, keys, "remove", valid, tile)


# ---------------------------------------------------------------------------
# Generation-ring dispatch (window subsystem)
# ---------------------------------------------------------------------------

def ring_contains(spec: FilterSpec, rings: jnp.ndarray, keys: jnp.ndarray,
                  regime: str = "auto", tile: int = DEFAULT_TILE
                  ) -> jnp.ndarray:
    """Fused membership across a (G, n_words) generation ring: one hash
    phase per key, G row loads ORed before a single mask test."""
    n = keys.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    tile = _clamp_tile(n, tile)
    padded = _pad_keys(keys, tile)
    interp = _interpret_only("ring")
    n_gen = rings.shape[0]
    if regime == "auto":
        regime = ("vmem" if n_gen * spec.n_words * 4 <= VMEM_FILTER_BYTES
                  else "hbm")
    if regime == "vmem":
        out = ring_k.ring_contains_vmem(spec, rings, padded, tile=tile,
                                        interpret=interp)
    else:
        out = ring_k.ring_contains_hbm(spec, rings, padded, tile=tile,
                                       interpret=interp)
    return out[:n]
