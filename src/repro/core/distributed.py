"""Distributed Bloom filters over a JAX device mesh.

Two deployment shapes, both built on ``shard_map``:

**replicated**
    Every device holds the full word array; adds are applied locally to the
    device's replica against its own key shard, and a **butterfly OR
    all-reduce** built from ``lax.ppermute`` merges the replicas (bitwise OR
    is not a native JAX collective — log2(n) rounds, each moving m bits,
    same volume schedule as a bidirectional-ring all-reduce for small device
    counts). Between syncs the filter is eventually-consistent: a duplicate
    may slip through, the FPR is unaffected — the right trade for
    data-pipeline dedup where a missed duplicate costs one wasted sample,
    not correctness.

**sharded**
    The word array is split into per-device **segments** (contiguous block
    ranges — the distributed extension of the ownership model in
    core.partition). Bulk ops route each key to its segment owner with a
    fixed-capacity ``all_to_all`` (GShard-style: static capacity + validity
    mask), the owner runs one chip's HBM-regime row engine on its segment
    (``kernels.ops.segment_add`` / ``segment_contains``: phase 1 on the
    segment's spec, phase 2 the Pallas HBM row kernels, padded slots making
    no row DMA), and lookup results ride the inverse all_to_all home. A
    segment lives in HBM: at 2^37 bits over four chips it is 4 GiB a chip.

    The default capacity, one device's whole batch, is exact: no slot can
    overflow. An explicit smaller capacity bounds the exchange's memory
    and is lossy: an overflowed lookup reports "present" (an allowed false
    positive), but an overflowed add is dropped, so that key later tests
    absent — a false negative.

This module holds the **pure collective transforms** (``replicated_*`` /
``sharded_*`` functions), consumed by the ``"replicated"`` / ``"sharded"``
engines in ``repro.api.registry`` — the supported surface, conforming to
the uniform ``Filter`` protocol. (The one-release ``ReplicatedFilter`` /
``ShardedFilter`` shims have been removed; use
``repro.api.make_filter(..., backend=..., mesh=...)``.)

Scale note: the sharded shape keeps per-device memory at m/n, so a filter
too large for one chip's HBM is held by several; each owner's work is the
single-chip DRAM-resident regime on its segment, with the keys it owns.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hashing as H
from repro.core import variants as V
from repro.core.variants import FilterSpec


def _zeros(shape, sharding) -> jnp.ndarray:
    """Zeroed uint32 words created in place on every device's shard; a
    host-side or single-device array would have to fit one device first."""
    return _zeros_fn(shape, sharding)()


@functools.lru_cache(maxsize=None)
def _zeros_fn(shape, sharding):
    # one jitted function per shape and sharding, so a new filter of a
    # shape already made compiles nothing
    return jax.jit(lambda: jnp.zeros(shape, jnp.uint32),
                   out_shardings=sharding)


# ---------------------------------------------------------------------------
# Butterfly OR all-reduce (custom collective)
# ---------------------------------------------------------------------------

def or_allreduce(x: jnp.ndarray, axis_name: str, method: str = "butterfly"
                 ) -> jnp.ndarray:
    """Bitwise-OR all-reduce along a mesh axis (inside shard_map).

    butterfly: log2(n) ppermute rounds (n must be a power of two).
    gather:    all_gather + local OR fold (any n; more memory).
    """
    n = jax.lax.axis_size(axis_name)
    if method == "gather" or (n & (n - 1)) != 0:
        g = jax.lax.all_gather(x, axis_name, axis=0)         # (n, ...)
        acc = g[0]
        for i in range(1, n):                                 # static fold
            acc = acc | g[i]
        return acc
    step = 1
    while step < n:
        perm = [(i, i ^ step) for i in range(n)]
        x = x | jax.lax.ppermute(x, axis_name, perm)
        step <<= 1
    return x


# ---------------------------------------------------------------------------
# One device's segment of a block-sharded filter
# ---------------------------------------------------------------------------

def segment_spec(spec: FilterSpec, n_dev: int) -> FilterSpec:
    """The spec of one of ``n_dev`` block-range segments. Block indices
    mask the hash's low bits (``hashing.block_index``), so a key's block
    in its segment is its block in the filter masked to the segment:
    ``blk & (blocks_per_segment - 1)``, and its owner is the high bits."""
    return dataclasses.replace(spec, m_bits=spec.m_bits // n_dev)


def _segment_add(seg: FilterSpec, seg_words: jnp.ndarray, keys: jnp.ndarray,
                 valid: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels import ops
    with jax.named_scope("sharded.segment_update"):
        return ops.segment_add(seg, seg_words, keys, valid)


def _segment_contains(seg: FilterSpec, seg_words: jnp.ndarray,
                      keys: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels import ops
    with jax.named_scope("sharded.segment_probe"):
        return ops.segment_contains(seg, seg_words, keys, valid)


# ---------------------------------------------------------------------------
# Replicated deployment — pure transforms
# ---------------------------------------------------------------------------

def replicated_init(spec: FilterSpec, mesh: Mesh, axis: str = "data"
                    ) -> jnp.ndarray:
    """(n_dev, n_words) zeroed replicas, one per device along ``axis``."""
    return _zeros((mesh.shape[axis], spec.n_words),
                  NamedSharding(mesh, P(axis)))


def replicated_add_local(spec: FilterSpec, mesh: Mesh, axis: str,
                         words: jnp.ndarray, keys_sharded: jnp.ndarray
                         ) -> jnp.ndarray:
    """Each device ORs its (n_dev, n_local, 2) key shard into its replica —
    no collectives; replicas diverge until the next OR-merge."""
    def body(w, keys):
        return V.add_scatter(spec, w[0], keys[0])[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=P(axis), check_vma=True)
    return fn(words, keys_sharded)


def replicated_sync(spec: FilterSpec, mesh: Mesh, axis: str,
                    words: jnp.ndarray, method: str = "butterfly"
                    ) -> jnp.ndarray:
    """Merge replicas: afterwards every device's replica is the global OR."""
    def body(w):
        return or_allreduce(w, axis, method=method)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
                       check_vma=True)
    return fn(words)


def replicated_contains_local(spec: FilterSpec, mesh: Mesh, axis: str,
                              words: jnp.ndarray, keys_sharded: jnp.ndarray
                              ) -> jnp.ndarray:
    """Test each device's key shard against its *own* replica (pre-sync view:
    remote adds since the last sync are invisible)."""
    def body(w, keys):
        return V.contains(spec, w[0], keys[0])[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=P(axis), check_vma=True)
    return fn(words, keys_sharded)


def replicated_contains_merged(spec: FilterSpec, mesh: Mesh, axis: str,
                               words: jnp.ndarray, keys_sharded: jnp.ndarray
                               ) -> jnp.ndarray:
    """Test against the OR of all replicas (one butterfly per call) — the
    no-false-negative view the uniform Filter protocol promises, without
    mutating the replicas themselves."""
    def body(w, keys):
        merged = or_allreduce(w[0], axis)
        return V.contains(spec, merged, keys[0])[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=P(axis), check_vma=True)
    return fn(words, keys_sharded)


# ---------------------------------------------------------------------------
# Sharded deployment — pure transforms
# ---------------------------------------------------------------------------

def sharded_init(spec: FilterSpec, mesh: Mesh, axis: str = "data"
                 ) -> jnp.ndarray:
    """(n_words,) zeroed filter, block-range sharded along ``axis``."""
    n_dev = mesh.shape[axis]
    assert spec.n_blocks % n_dev == 0
    assert (n_dev & (n_dev - 1)) == 0, "device count must be pow2 (segments)"
    return _zeros((spec.n_words,), NamedSharding(mesh, P(axis)))


def _route(spec: FilterSpec, keys: jnp.ndarray, n_dev: int, capacity: int):
    """Per-device: bucket local keys by owner segment, fixed capacity.

    Returns (send [n_dev, cap, 2], valid [n_dev, cap], seg, rank, keep).
    """
    blocks_per_seg = spec.n_blocks // n_dev
    n = keys.shape[0]
    h2 = H.block_hash(keys)
    blk = H.block_index(h2, spec.n_blocks)
    seg = (blk // jnp.uint32(blocks_per_seg)).astype(jnp.int32)
    order = jnp.argsort(seg, stable=True)
    sorted_seg = seg[order]
    idx_in_run = (jnp.arange(n)
                  - jnp.searchsorted(sorted_seg, sorted_seg, side="left"))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(idx_in_run.astype(jnp.int32))
    keep = rank < capacity
    slot = jnp.where(keep, seg * capacity + rank, n_dev * capacity)
    send = jnp.zeros((n_dev * capacity + 1, 2), jnp.uint32).at[slot].set(
        keys, mode="drop")[:-1].reshape(n_dev, capacity, 2)
    valid = jnp.zeros((n_dev * capacity + 1,), jnp.uint8).at[slot].set(
        1, mode="drop")[:-1].reshape(n_dev, capacity)
    return send, valid, seg, rank, keep


def _exchange(send: jnp.ndarray, valid: jnp.ndarray, axis: str):
    """Each device's per-owner buckets to their owners: the owner gets
    (n_dev * cap, 2) keys and (n_dev * cap,) validity, one bucket from
    each device."""
    with jax.named_scope("sharded.exchange"):
        recv_k = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
        recv_v = jax.lax.all_to_all(valid, axis, 0, 0, tiled=False)
    return recv_k.reshape(-1, 2), recv_v.reshape(-1)


# The sharded bodies run with ``check_vma=False``: the Pallas row kernels
# inside them carry no varying-axes type, and under interpret mode their
# block slicing mixes varying and invariant values, which the check
# refuses. Every value in these bodies is per device anyway.

def sharded_add(spec: FilterSpec, mesh: Mesh, axis: str, capacity: int,
                words: jnp.ndarray, keys_sharded: jnp.ndarray) -> jnp.ndarray:
    """Route each device's (n_dev, n_local, 2) key shard to its segment owner
    (all_to_all), then run the row engine's update on the owner's
    segment."""
    n_dev = mesh.shape[axis]
    seg = segment_spec(spec, n_dev)

    def body(w, keys):
        with jax.named_scope("sharded.route"):
            send, valid, *_ = _route(spec, keys[0], n_dev, capacity)
        flat_k, flat_v = _exchange(send, valid, axis)
        return _segment_add(seg, w, flat_k, flat_v)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=P(axis), check_vma=False)
    return fn(words, keys_sharded)


# ---------------------------------------------------------------------------
# Bank-sharded deployment — the bank axis across the mesh (FilterBank)
# ---------------------------------------------------------------------------
# Device d owns B/n_dev whole member filters (each VMEM-small — exactly the
# multi-tenant regime the paper's cache-resident fast path wants). Routed
# ops compose TENANT routing with the existing key-routing machinery: keys
# ride a fixed-capacity all_to_all to their member's owner device, the
# owner runs the fused local bank op (core.variants.bank_*), and lookup
# results ride the inverse all_to_all home. Same overflow contract as the
# block-sharded filter: the default capacity is exact; under a smaller
# explicit one an overflowed lookup reports "present" (an allowed FP) and
# an overflowed add is dropped, a false negative when that key is asked.


def bankshard_init(spec: FilterSpec, mesh: Mesh, axis: str, bank: int
                   ) -> jnp.ndarray:
    """(bank, n_words) zeroed members, bank axis sharded along ``axis``."""
    n_dev = mesh.shape[axis]
    assert bank % n_dev == 0, (bank, n_dev)
    return _zeros((bank, spec.n_words), NamedSharding(mesh, P(axis)))


def _route_members(keys: jnp.ndarray, member: jnp.ndarray,
                   valid, n_dev: int, b_local: int, capacity: int):
    """Per-device: bucket local (key, member) pairs by owner device
    (member // b_local), fixed capacity. Members are rebased to the
    owner's local index before the send.

    The bucket-rank/scatter machinery is ``core.partition.route_by_id``
    (one implementation of the idiom); this adds only the member-rebase
    scatter and the caller-validity mask. Returns (send_k [n_dev, cap, 2],
    send_m [n_dev, cap], send_v [n_dev, cap], dest, rank, keep)."""
    from repro.core.partition import route_by_id
    member = jnp.asarray(member, jnp.int32)
    dest = member // jnp.int32(b_local)
    part = route_by_id(keys, dest, n_dev, capacity)
    # caller-invalid keys still travel in send_k (shape is fixed) but with
    # send_v = 0 they are masked no-ops at the owner
    ok = part.keep if valid is None else (part.keep & (valid > 0))
    slot = jnp.where(ok, dest * capacity + part.rank, n_dev * capacity)
    send_m = jnp.zeros((n_dev * capacity + 1,), jnp.int32).at[slot].set(
        member % jnp.int32(b_local), mode="drop")[:-1].reshape(n_dev, capacity)
    send_v = jnp.zeros((n_dev * capacity + 1,), jnp.uint8).at[slot].set(
        1, mode="drop")[:-1].reshape(n_dev, capacity)
    return part.keys_by_seg, send_m, send_v, dest, part.rank, part.keep


def bankshard_add(spec: FilterSpec, mesh: Mesh, axis: str, capacity: int,
                  words: jnp.ndarray, keys_sharded: jnp.ndarray,
                  member_sharded: jnp.ndarray, valid_sharded: jnp.ndarray
                  ) -> jnp.ndarray:
    """Route each device's flat (keys, member, valid) shard to the member's
    owner, then one fused bank add into the owner's resident members."""
    n_dev = mesh.shape[axis]
    b_local = words.shape[0] // n_dev

    def body(w, keys, member, valid):
        send_k, send_m, send_v, *_ = _route_members(
            keys[0], member[0], valid[0], n_dev, b_local, capacity)
        rk = jax.lax.all_to_all(send_k, axis, 0, 0, tiled=False)
        rm = jax.lax.all_to_all(send_m, axis, 0, 0, tiled=False)
        rv = jax.lax.all_to_all(send_v, axis, 0, 0, tiled=False)
        return V.bank_add_rows(spec, w, rk.reshape(-1, 2), rm.reshape(-1),
                               valid=rv.reshape(-1))

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis), P(axis)),
                       out_specs=P(axis), check_vma=True)
    return fn(words, keys_sharded, member_sharded, valid_sharded)


def bankshard_contains(spec: FilterSpec, mesh: Mesh, axis: str,
                       capacity: int, words: jnp.ndarray,
                       keys_sharded: jnp.ndarray,
                       member_sharded: jnp.ndarray) -> jnp.ndarray:
    """(n_dev, n_local) bool, sharded like the keys; each key tested only
    against its member's filter. Overflowed keys report "present"."""
    n_dev = mesh.shape[axis]
    b_local = words.shape[0] // n_dev

    def body(w, keys, member):
        k, m = keys[0], member[0]
        send_k, send_m, _, dest, rank, keep = _route_members(
            k, m, None, n_dev, b_local, capacity)
        rk = jax.lax.all_to_all(send_k, axis, 0, 0, tiled=False)
        rm = jax.lax.all_to_all(send_m, axis, 0, 0, tiled=False)
        res = V.bank_contains_rows(spec, w, rk.reshape(-1, 2),
                                   rm.reshape(-1))
        back = jax.lax.all_to_all(res.reshape(n_dev, capacity), axis, 0, 0,
                                  tiled=False)
        mine = back.reshape(-1)[dest * capacity + rank]
        return jnp.where(keep, mine, True)[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=P(axis), check_vma=True)
    return fn(words, keys_sharded, member_sharded)


def sharded_contains(spec: FilterSpec, mesh: Mesh, axis: str, capacity: int,
                     words: jnp.ndarray, keys_sharded: jnp.ndarray
                     ) -> jnp.ndarray:
    """Returns (n_dev, n_local) bool, sharded like the keys. A key that
    overflowed its bucket reports "present" (an allowed false positive)."""
    n_dev = mesh.shape[axis]
    seg_spec = segment_spec(spec, n_dev)

    def body(w, keys):
        with jax.named_scope("sharded.route"):
            send, valid, seg, rank, keep = _route(spec, keys[0], n_dev,
                                                  capacity)
        flat_k, flat_v = _exchange(send, valid, axis)
        res = _segment_contains(seg_spec, w, flat_k, flat_v)
        with jax.named_scope("sharded.exchange"):
            back = jax.lax.all_to_all(res.reshape(n_dev, capacity), axis, 0,
                                      0, tiled=False)     # (n_dev, cap)
        mine = back.reshape(-1)[seg * capacity + rank]
        return jnp.where(keep, mine, True)[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=P(axis), check_vma=False)
    return fn(words, keys_sharded)


