"""Distributed engines: the replicated and sharded deployments re-homed
behind the uniform ``Filter`` protocol.

Both accept **flat** ``(n, 2)`` key batches like every other engine: keys
are padded (repeating the last key — OR-idempotent) to a device multiple
and split ``(n_dev, n_local, 2)`` before entering the ``shard_map``
transforms in ``repro.core.distributed``; lookup results ride home and the
padding is dropped. The old ``add_local``/``add`` naming split disappears —
``add`` means the same thing on every engine.

Semantics under the uniform protocol:

* ``replicated``: ``add`` ORs each device's slice into its own replica (no
  collectives — replicas stay eventually-consistent); ``contains`` tests
  against the butterfly-OR of all replicas, so a key added through *any*
  device is always found (no false negatives). ``dense_words``/checkpoint
  state is the global OR.
* ``sharded``: ``add``/``contains`` route keys to their segment owner via
  fixed-capacity ``all_to_all``, and the owner runs the Pallas HBM row
  kernels on its segment. The default capacity (``options.capacity`` is
  None) is the per-device batch size, the exact one: nothing overflows.
  An explicit smaller capacity bounds the exchange's memory and is lossy:
  an overflowed lookup reports "present", and an overflowed add is
  dropped, so that key later tests absent (a false negative).
  ``ShardedBackend.registry`` counts calls, keys and exchange slots.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import distributed as D
from repro.core.variants import FilterSpec
from repro.telemetry import MetricsRegistry
from repro.api.registry import (Backend, SelectionContext, flat_members,
                                register)


def _n_dev(options) -> int:
    return options.mesh.shape[options.axis]


def _pad_split(keys: jnp.ndarray, n_dev: int):
    """(n, 2) -> ((n_dev, n_local, 2), n) with OR-idempotent padding."""
    n = keys.shape[0]
    n_local = -(-n // n_dev)
    pad = n_dev * n_local - n
    if pad:
        keys = jnp.concatenate([keys, jnp.broadcast_to(keys[-1:], (pad, 2))])
    return keys.reshape(n_dev, n_local, 2), n


class _DistBackend(Backend):
    def supports(self, spec: FilterSpec, ctx: SelectionContext) -> bool:
        # counting/fingerprint specs and windowed (generations) contexts
        # belong to the single-host forgetting engines for now; banks are
        # opt-in per engine (sharded shards the bank axis, replicated
        # declines)
        return (ctx.mesh is not None and not spec.is_counting
                and not spec.is_fingerprint
                and ctx.generations is None and ctx.bank is None)

    def init(self, spec: FilterSpec, options) -> jnp.ndarray:
        raise NotImplementedError


class ReplicatedBackend(_DistBackend):
    """Full replica per device; local adds, butterfly-OR merged lookups.
    Best when the filter fits per-device memory and add volume dominates."""

    name = "replicated"
    words_ndim = 2                      # (n_dev, n_words) per filter

    def cost(self, spec: FilterSpec, ctx: SelectionContext) -> float:
        # adds are collective-free; lookups pay one butterfly. Prefer over
        # sharded unless the sharded geometry constraint holds.
        return 1.5

    def init(self, spec, options):
        return D.replicated_init(spec, options.mesh, options.axis)

    def add(self, spec, words, keys, options):
        keys_sh, _ = _pad_split(keys, _n_dev(options))
        return D.replicated_add_local(spec, options.mesh, options.axis,
                                      words, keys_sh)

    def contains(self, spec, words, keys, options):
        keys_sh, n = _pad_split(keys, _n_dev(options))
        hits = D.replicated_contains_merged(spec, options.mesh, options.axis,
                                            words, keys_sh)
        return hits.reshape(-1)[:n]

    def to_dense(self, spec, words, options):
        dense = words[0]
        for i in range(1, words.shape[0]):   # static fold over replicas
            dense = dense | words[i]
        return dense

    def from_dense(self, spec, dense, options):
        n_dev = _n_dev(options)
        return jnp.broadcast_to(dense[None], (n_dev, dense.shape[0]))


class ShardedBackend(_DistBackend):
    """Block-range segment per device; all_to_all ownership routing keeps
    every filter byte resident on exactly one device (m/n_dev memory).

    **Banks** shard the *bank axis* instead of the block axis: device d
    owns B/n_dev whole member filters, routed ops compose tenant routing
    (member -> owner device, all_to_all) with the same fixed-capacity
    machinery the scalar key routing uses, and the owner runs the fused
    local bank op (``V.bank_*``) on its resident members."""

    name = "sharded"
    supports_bank = True
    tpu_impl = "pallas"

    def __init__(self):
        # Counters of the scalar add / contains calls, per ``op``:
        # ``sharded.calls``, ``sharded.keys`` (keys offered) and
        # ``sharded.exchange_slots`` (n_dev^2 x capacity, the slots the
        # all_to_all moves).
        self.registry = MetricsRegistry()

    def count_call(self, op, spec, n_keys, options):
        n_dev = _n_dev(options)
        cap = self._capacity(options, -(-n_keys // n_dev))
        reg = self.registry
        reg.counter("sharded.calls", op=op).inc()
        reg.counter("sharded.keys", op=op).inc(n_keys)
        reg.counter("sharded.exchange_slots", op=op).inc(n_dev * n_dev * cap)

    def supports(self, spec: FilterSpec, ctx: SelectionContext) -> bool:
        if (ctx.mesh is None or spec.is_counting or spec.is_fingerprint
                or ctx.generations is not None):
            return False
        if spec.variant == "cbf":
            return False   # classical filter has no block locality to shard
        n_dev = ctx.mesh.shape[ctx.axis]
        if (n_dev & (n_dev - 1)) != 0:
            return False
        if ctx.bank is not None:
            return ctx.bank % n_dev == 0      # bank axis sharded across mesh
        return spec.n_blocks % n_dev == 0

    def cost(self, spec: FilterSpec, ctx: SelectionContext) -> float:
        return 1.2   # preferred over replicated when geometry allows

    def init(self, spec, options):
        return D.sharded_init(spec, options.mesh, options.axis)

    def _capacity(self, options, n_local: int) -> int:
        # None -> exact (a (src,dst) lane can never carry more than one
        # device's whole batch, so per-device batch size is overflow-free)
        return options.capacity if options.capacity is not None else n_local

    def add(self, spec, words, keys, options):
        keys_sh, _ = _pad_split(keys, _n_dev(options))
        cap = self._capacity(options, keys_sh.shape[1])
        return D.sharded_add(spec, options.mesh, options.axis, cap,
                             words, keys_sh)

    def contains(self, spec, words, keys, options):
        keys_sh, n = _pad_split(keys, _n_dev(options))
        cap = self._capacity(options, keys_sh.shape[1])
        hits = D.sharded_contains(spec, options.mesh, options.axis, cap,
                                  words, keys_sh)
        return hits.reshape(-1)[:n]

    # words are already the dense (n_words,) array (device-sharded)
    def from_dense(self, spec, dense, options):
        return dense

    # -- bank-axis sharding ---------------------------------------------------
    def init_bank(self, spec, bank_shape, options):
        if len(bank_shape) != 1:
            raise ValueError("sharded banks are 1-D (the bank axis maps onto "
                             f"the mesh axis); got bank_shape={bank_shape}")
        return D.bankshard_init(spec, options.mesh, options.axis,
                                bank_shape[0])

    def _pad_split_routed(self, keys, member, valid, n_dev):
        """Flat routed triples -> per-device (n_dev, n_local, ...) shards.
        Padding repeats the last key/member with valid=0 (dropped by the
        add path, sliced off by the contains path)."""
        n = keys.shape[0]
        n_local = -(-n // n_dev)
        pad = n_dev * n_local - n
        if valid is None:
            valid = jnp.ones((n,), jnp.uint8)
        valid = valid.astype(jnp.uint8)
        member = jnp.asarray(member, jnp.int32)
        if pad:
            keys = jnp.concatenate(
                [keys, jnp.broadcast_to(keys[-1:], (pad, 2))])
            member = jnp.concatenate(
                [member, jnp.broadcast_to(member[-1:], (pad,))])
            valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.uint8)])
        return (keys.reshape(n_dev, n_local, 2),
                member.reshape(n_dev, n_local),
                valid.reshape(n_dev, n_local), n)

    def add_bank(self, spec, words, keys, options, valid=None, state=None):
        flat, member = flat_members(keys)
        vf = None if valid is None else valid.reshape(-1)
        return self.add_bank_routed(spec, words, flat, member, options,
                                    valid=vf)

    def contains_bank(self, spec, words, keys, options, state=None):
        flat, member = flat_members(keys)
        return self.contains_bank_routed(spec, words, flat, member, options
                                         ).reshape(keys.shape[:2])

    def add_bank_routed(self, spec, words, keys, member, options, valid=None,
                        state=None):
        n_dev = _n_dev(options)
        k_sh, m_sh, v_sh, _ = self._pad_split_routed(keys, member, valid,
                                                     n_dev)
        cap = self._capacity(options, k_sh.shape[1])
        return D.bankshard_add(spec, options.mesh, options.axis, cap,
                               words, k_sh, m_sh, v_sh)

    def contains_bank_routed(self, spec, words, keys, member, options,
                             state=None):
        n_dev = _n_dev(options)
        k_sh, m_sh, _, n = self._pad_split_routed(keys, member, None, n_dev)
        cap = self._capacity(options, k_sh.shape[1])
        hits = D.bankshard_contains(spec, options.mesh, options.axis, cap,
                                    words, k_sh, m_sh)
        return hits.reshape(-1)[:n]


def register_all():
    register(ReplicatedBackend())
    register(ShardedBackend())
