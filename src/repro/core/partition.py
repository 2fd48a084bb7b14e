"""Radix partitioning of keys by filter segment — the TPU ownership model.

On the GPU, concurrent inserts from many SMs into shared blocks are made safe
by ``atomicOr`` and made *fast* by the L1 temporal coalescer (paper §2.2/§5.2).
TPUs have neither; instead we adopt the strategy of the paper's own CPU
baseline (Schmidt et al. [30], radix partitioning): bucket the keys by the
filter segment their block falls in, so that

* each Pallas grid step (or each device of a sharded filter) owns one
  segment exclusively -> plain read-modify-write, no atomics;
* every access within a step hits one VMEM-resident segment -> the
  cache-resident fast path applies even to HBM-sized filters.

Both a host-side (numpy, exact capacity) and a jit-compatible (fixed
capacity, validity-masked) implementation are provided.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hashing as H
from repro.core.variants import FilterSpec


class JitPartition(NamedTuple):
    """Result of :func:`partition_jit` (all device arrays, fixed shapes).

    ``keep`` marks the keys that landed inside their segment's capacity;
    ``overflow`` counts the ones that did NOT (they are absent from
    ``keys_by_seg`` and the caller MUST handle them — retry with a larger
    capacity, or apply a residual pass over ``~keep``). Silent key loss
    through this path is a bug, not a policy.
    """

    keys_by_seg: jnp.ndarray   # (n_segments, capacity, 2) uint32
    valid: jnp.ndarray         # (n_segments, capacity) uint8
    keep: jnp.ndarray          # (n,) bool — key survived into its segment
    overflow: jnp.ndarray      # () int32 — number of dropped keys
    rank: jnp.ndarray          # (n,) int32 — key's slot within its bucket


def segment_ids(spec: FilterSpec, keys: jnp.ndarray, n_segments: int) -> jnp.ndarray:
    """Segment owning each key's block. Segments are contiguous block ranges."""
    assert spec.n_blocks % n_segments == 0
    blocks_per_seg = spec.n_blocks // n_segments
    h2 = H.block_hash(keys) if keys.shape[-1] == 2 else H.xxh32_u32(keys, H.SEED_BLOCK)
    blk = H.block_index(h2, spec.n_blocks)
    return (blk // jnp.uint32(blocks_per_seg)).astype(jnp.int32)


def partition_host(spec: FilterSpec, keys: np.ndarray, n_segments: int
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side exact partition.

    Returns (keys_by_seg [S, cap, 2] uint32, valid [S, cap] uint8,
    counts [S] int64). cap = max per-segment count, rounded up to 8 for
    sublane alignment.
    """
    keys = np.asarray(keys, dtype=np.uint32)
    seg = np.asarray(segment_ids(spec, jnp.asarray(keys), n_segments))
    counts = np.bincount(seg, minlength=n_segments)
    cap = max(int(counts.max()), 1)
    cap = (cap + 7) & ~7
    out = np.zeros((n_segments, cap, 2), dtype=np.uint32)
    valid = np.zeros((n_segments, cap), dtype=np.uint8)
    order = np.argsort(seg, kind="stable")
    sorted_keys = keys[order]
    offsets = np.concatenate([[0], np.cumsum(counts)])
    for sidx in range(n_segments):
        lo, hi = offsets[sidx], offsets[sidx + 1]
        out[sidx, : hi - lo] = sorted_keys[lo:hi]
        valid[sidx, : hi - lo] = 1
    return out, valid, counts


def partition_jit(spec: FilterSpec, keys: jnp.ndarray, n_segments: int,
                  capacity: int) -> JitPartition:
    """jit-compatible partition with static per-segment capacity.

    Keys beyond ``capacity`` in a segment do not fit the fixed-shape output;
    instead of silently dropping them this reports ``keep``/``overflow`` so
    dispatch (`kernels.ops.bloom_add_partitioned`) can escalate capacity
    (concrete callers) or run a vectorized residual pass over the dropped
    keys (traced callers). Capacity of mean * 4 is ~overflow-free for
    uniform hashes. Returns a :class:`JitPartition`.
    """
    seg = segment_ids(spec, keys, n_segments)                    # (n,)
    return route_by_id(keys, seg, n_segments, capacity)


def route_by_id(keys: jnp.ndarray, ids: jnp.ndarray, n_buckets: int,
                capacity: int) -> JitPartition:
    """jit-compatible scatter of flat keys into per-bucket batches.

    The generic form of :func:`partition_jit` with *caller-supplied* bucket
    ids — used by tenant routing (``repro.api.route``: ids are bank member
    indices) and by the hash-segment partition above (ids are segment
    owners). Fixed-shape output: (n_buckets, capacity, 2) keys plus a
    validity mask; same keep/overflow contract as :class:`JitPartition`
    (no silent key loss).
    """
    n = keys.shape[0]
    ids = jnp.asarray(ids, jnp.int32)
    # rank of each key within its bucket (stable): count predecessors
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    idx_in_run = jnp.arange(n) - jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    rank = jnp.zeros((n,), jnp.int32).at[order].set(idx_in_run.astype(jnp.int32))
    keep = rank < capacity
    slot = jnp.where(keep, ids * capacity + rank, n_buckets * capacity)  # overflow bin
    flat_keys = jnp.zeros((n_buckets * capacity + 1, 2), jnp.uint32
                          ).at[slot].set(keys, mode="drop")
    flat_valid = jnp.zeros((n_buckets * capacity + 1,), jnp.uint8
                           ).at[slot].set(1, mode="drop")
    return JitPartition(
        flat_keys[:-1].reshape(n_buckets, capacity, 2),
        flat_valid[:-1].reshape(n_buckets, capacity),
        keep,
        jnp.int32(n) - jnp.sum(keep).astype(jnp.int32),
        rank)
