"""Single-host engines: jnp reference, two Pallas regimes, and the
forgetting and fingerprint engines (counting, windowed, cuckoo, quotient).

Cost model (relative, lower = better): the jnp engine is the baseline at
1.0 on every platform. On TPU the Pallas kernels win (the whole point of
the paper); off-TPU they run in interpret mode — bit-exact but orders of
magnitude slower, so ``"auto"`` keeps them for validation only.

The ``counting`` and ``windowed`` engines claim their workloads
*exclusively*: ``countingbf`` specs belong to ``counting`` and a context
with ``generations`` set belongs to ``windowed``, so the plain bit engines
decline both (see ``_plain_bits``).

Which engines run Pallas kernels on a TPU is declared per engine
(``Backend.tpu_impl``, shown by ``registry.describe()``): ``pallas-vmem``,
``pallas-hbm`` and ``counting`` do; ``windowed``, ``cuckoo`` and
``quotient`` run their XLA (jnp) implementation there, because their
kernels use in-kernel sorts, gathers and vector slicing the chip's
compiler refuses. Their kernels stay reachable in interpret mode
(cuckoo/quotient ``options.impl="pallas"``, ``repro.kernels.ops``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import variants as V
from repro.core.variants import FilterSpec
from repro.api.registry import (Backend, SelectionContext, flat_members
                                as _flat_members, register)

# Interpret-mode Pallas (any non-TPU platform) is for validation, not speed.
_INTERPRET_PENALTY = 50.0


def _single_host(ctx: SelectionContext) -> bool:
    return ctx.mesh is None


def _plain_bits(spec: FilterSpec, ctx: SelectionContext) -> bool:
    """Workloads the ordinary bit engines compete for: not a counting or
    fingerprint spec, not a windowed (generations) context."""
    return (not spec.is_counting and not spec.is_fingerprint
            and ctx.generations is None)


class JnpBackend(Backend):
    """Vectorized pure-jnp reference: one row gather per lookup
    (``contains_rows``) and the sorted segmented-OR bulk insert
    (``add_rows``). Fast path off-TPU; the semantic oracle everywhere.
    Banks run natively as one super-filter op (``V.bank_*``): member-offset
    block ids turn B filters into B*n_blocks blocks, one gather/scatter."""

    name = "jnp"
    supports_bank = True

    def supports(self, spec: FilterSpec, ctx: SelectionContext) -> bool:
        return _single_host(ctx) and _plain_bits(spec, ctx)

    def cost(self, spec: FilterSpec, ctx: SelectionContext) -> float:
        return 1.0

    def init(self, spec: FilterSpec, options) -> jnp.ndarray:
        return V.init(spec)

    def add(self, spec, words, keys, options):
        return V.add_rows(spec, words, keys)

    def contains(self, spec, words, keys, options):
        return V.contains_rows(spec, words, keys)

    # -- native bank path (blocked variants; cbf falls back to vmap) ---------
    def add_bank(self, spec, words, keys, options, valid=None, state=None):
        if spec.variant == "cbf":
            return super().add_bank(spec, words, keys, options, valid=valid,
                                    state=state)
        flat, member = _flat_members(keys)
        vf = None if valid is None else valid.reshape(-1)
        return V.bank_add_rows(spec, words, flat, member, valid=vf)

    def contains_bank(self, spec, words, keys, options, state=None):
        if spec.variant == "cbf":
            return super().contains_bank(spec, words, keys, options,
                                         state=state)
        flat, member = _flat_members(keys)
        return V.bank_contains_rows(spec, words, flat, member
                                    ).reshape(keys.shape[:2])

    def add_bank_routed(self, spec, words, keys, member, options, valid=None,
                        state=None):
        if spec.variant == "cbf":
            return super().add_bank_routed(spec, words, keys, member, options,
                                           valid=valid, state=state)
        return V.bank_add_rows(spec, words, keys, member, valid=valid)

    def contains_bank_routed(self, spec, words, keys, member, options,
                             state=None):
        if spec.variant == "cbf":
            return super().contains_bank_routed(spec, words, keys, member,
                                                options, state=state)
        return V.bank_contains_rows(spec, words, keys, member)


class _PallasBackend(Backend):
    regime = "auto"
    tpu_impl = "pallas"

    def _fits_vmem(self, spec: FilterSpec) -> bool:
        from repro.kernels.sbf import VMEM_FILTER_BYTES
        return spec.n_words * 4 <= VMEM_FILTER_BYTES

    def init(self, spec: FilterSpec, options) -> jnp.ndarray:
        return V.init(spec)

    def _kw(self, options):
        kw = {"regime": self.regime, "probe": options.probe,
              "coop": options.coop, "mix": options.mix}
        if options.layout is not None:
            kw["layout"] = options.layout
        if options.tile is not None:
            kw["tile"] = options.tile
        return kw

    def add(self, spec, words, keys, options):
        from repro.kernels import ops
        return ops.bloom_add(spec, words, keys, **self._kw(options))

    def contains(self, spec, words, keys, options):
        from repro.kernels import ops
        # depth only applies to the HBM streaming pipeline; the kernels
        # resolve None through core.tuning.tune_plan.
        return ops.bloom_contains(spec, words, keys, depth=options.depth,
                                  **self._kw(options))


class PallasVmemBackend(_PallasBackend):
    """Pallas TPU kernels with the filter pinned in VMEM — the paper's
    cache-resident regime (Θ layout selectable via options.layout).
    Banks run natively: the whole (B, n_words) bank is pinned in VMEM and
    B members execute as ONE launch (member-offset block starts)."""

    name = "pallas-vmem"
    regime = "vmem"
    supports_bank = True

    def _bank_kw(self, options):
        kw = {"probe": options.probe, "mix": options.mix}
        if options.layout is not None:
            kw["layout"] = options.layout
        if options.tile is not None:
            kw["tile"] = options.tile
        return kw

    def supports(self, spec: FilterSpec, ctx: SelectionContext) -> bool:
        from repro.kernels import ops
        if not (_single_host(ctx) and _plain_bits(spec, ctx)
                and ops.kernel_supported(spec)):
            return False
        if spec.variant == "cbf" and ctx.platform != "cpu":
            return False          # the classical kernel does not lower
        if ctx.bank is not None:
            # the bank kernels need block locality and a whole-bank VMEM fit
            return (spec.variant != "cbf"
                    and ops.bank_vmem_resident(spec, ctx.bank))
        return self._fits_vmem(spec)

    def cost(self, spec: FilterSpec, ctx: SelectionContext) -> float:
        return 0.4 if ctx.platform == "tpu" else _INTERPRET_PENALTY

    def add_bank(self, spec, words, keys, options, valid=None, state=None):
        flat, member = _flat_members(keys)
        vf = None if valid is None else valid.reshape(-1)
        return self.add_bank_routed(spec, words, flat, member, options,
                                    valid=vf)

    def contains_bank(self, spec, words, keys, options, state=None):
        flat, member = _flat_members(keys)
        return self.contains_bank_routed(spec, words, flat, member, options
                                         ).reshape(keys.shape[:2])

    def add_bank_routed(self, spec, words, keys, member, options, valid=None,
                        state=None):
        from repro.kernels import ops
        return ops.bloom_bank_add(spec, words, keys, member, valid=valid,
                                  **self._bank_kw(options))

    def contains_bank_routed(self, spec, words, keys, member, options,
                             state=None):
        from repro.kernels import ops
        return ops.bloom_bank_contains(spec, words, keys, member,
                                       **self._bank_kw(options))


class PallasHbmBackend(_PallasBackend):
    """Pallas TPU kernels with the filter left in HBM, blocks streamed
    through a double-buffered DMA scratch — the DRAM-resident regime."""

    name = "pallas-hbm"
    regime = "hbm"

    def supports(self, spec: FilterSpec, ctx: SelectionContext) -> bool:
        from repro.kernels import ops
        # the classical variant has no block locality to stream by
        return (_single_host(ctx) and _plain_bits(spec, ctx)
                and ops.kernel_supported(spec) and spec.variant != "cbf")

    def cost(self, spec: FilterSpec, ctx: SelectionContext) -> float:
        base = 0.7 if ctx.platform == "tpu" else _INTERPRET_PENALTY + 10.0
        # dispreferred while the filter still fits in VMEM
        return base if not self._fits_vmem(spec) else base + 0.5


class CountingBackend(Backend):
    """Counting Bloom filter (variant='countingbf'): packed 4-bit saturating
    counters enabling ``remove`` and ``decay``. Pallas kernels on TPU (the
    sbf row engine on counter rows; sequential-ownership RMW instead of
    atomicAdd), jnp bit-plane reference on the CPU. 4x the memory of the
    equivalent bit filter.
    Banks run natively (counter super-filter; one launch in VMEM) — the
    generic fill-trick fallback is FORBIDDEN here because counting updates
    are not idempotent."""

    name = "counting"
    tpu_impl = "pallas"
    supports_remove = True
    supports_decay = True
    supports_bank = True
    supports_count = True              # counting_count multiplicity bound

    def supports(self, spec: FilterSpec, ctx: SelectionContext) -> bool:
        return (_single_host(ctx) and spec.is_counting
                and ctx.generations is None)

    def bits_per_key(self, target_fpr: float = None) -> float:
        """4-bit counters store 4x the equivalent bit filter."""
        return 4.0 * super().bits_per_key(
            target_fpr if target_fpr is not None else self.REF_FPR)

    def cost(self, spec: FilterSpec, ctx: SelectionContext) -> float:
        return 1.0   # sole claimant of countingbf specs

    def init(self, spec: FilterSpec, options) -> jnp.ndarray:
        return V.init(spec)                      # (4*n_words,) counters

    def _tpu(self) -> bool:
        from repro.kernels import ops
        return not ops._interpret()

    def _kw(self, options):
        kw = {"layout": options.layout, "probe": options.probe,
              "coop": options.coop, "mix": options.mix}
        if options.tile is not None:
            kw["tile"] = options.tile
        return kw

    def add(self, spec, words, keys, options):
        if self._tpu():
            from repro.kernels import ops
            return ops.counting_add(spec, words, keys, **self._kw(options))
        return V.counting_add(spec, words, keys)

    def remove(self, spec, words, keys, options):
        if self._tpu():
            from repro.kernels import ops
            return ops.counting_remove(spec, words, keys, **self._kw(options))
        return V.counting_remove(spec, words, keys)

    def contains(self, spec, words, keys, options):
        if self._tpu():
            from repro.kernels import ops
            return ops.counting_contains(spec, words, keys,
                                         depth=options.depth,
                                         **self._kw(options))
        return V.counting_contains(spec, words, keys)

    def decay(self, spec, words, options):
        if self._tpu():
            from repro.kernels import ops
            return ops.counting_decay(spec, words)
        return V.counting_decay(spec, words)

    def merge(self, spec, a, b, options):
        """Counter-true union: nibble-wise saturating add (NOT bitwise OR —
        merged counts must support the merged removes). Elementwise SWAR,
        so whole banks merge member-wise with the same call."""
        return V.nib_sat_add_words(a, b)

    def to_dense(self, spec, words, options):
        """Canonical view is the occupancy bit filter (counts are an engine
        detail; cross-engine merge/checkpoint interop stays uniform)."""
        return V.counting_to_bloom(spec, words)

    def from_dense(self, spec, dense, options):
        """Occupancy -> counters at 1. Membership-preserving, count-lossy."""
        return V.counting_from_bloom(spec, dense)

    # -- native bank path ----------------------------------------------------
    def _bank_update(self, spec, words, keys, member, valid, op, options):
        if self._tpu():
            from repro.kernels import ops
            kw = {"probe": options.probe, "layout": options.layout,
                  "mix": options.mix}
            if options.tile is not None:
                kw["tile"] = options.tile
            return ops.counting_bank_update(spec, words, keys, member, op,
                                            valid=valid, **kw)
        return V.bank_counting_update(spec, words, keys,
                                      jnp.asarray(member, jnp.int32),
                                      valid, op)

    def add_bank(self, spec, words, keys, options, valid=None, state=None):
        flat, member = _flat_members(keys)
        vf = None if valid is None else valid.reshape(-1)
        return self._bank_update(spec, words, flat, member, vf, "add",
                                 options)

    def remove_bank(self, spec, words, keys, options, valid=None, state=None):
        flat, member = _flat_members(keys)
        vf = None if valid is None else valid.reshape(-1)
        return self._bank_update(spec, words, flat, member, vf, "remove",
                                 options)

    def contains_bank(self, spec, words, keys, options, state=None):
        flat, member = _flat_members(keys)
        return self.contains_bank_routed(spec, words, flat, member, options
                                         ).reshape(keys.shape[:2])

    def add_bank_routed(self, spec, words, keys, member, options, valid=None,
                        state=None):
        return self._bank_update(spec, words, keys, member, valid, "add",
                                 options)

    def remove_bank_routed(self, spec, words, keys, member, options,
                           valid=None, state=None):
        return self._bank_update(spec, words, keys, member, valid, "remove",
                                 options)

    def contains_bank_routed(self, spec, words, keys, member, options,
                             state=None):
        if self._tpu():
            from repro.kernels import ops
            kw = {}
            if options.tile is not None:
                kw["tile"] = options.tile
            return ops.counting_bank_contains(spec, words, keys, member, **kw)
        return V.bank_counting_contains(spec, words, keys,
                                        jnp.asarray(member, jnp.int32))

    def decay_bank(self, spec, words, options):
        """Aging is elementwise on packed counters — the bank decays whole."""
        return V.decay_word(words)


class WindowedBackend(Backend):
    """Generation-ring sliding window (``options.generations`` = G):
    inserts land in the head generation, queries OR the ring in one fused
    pass, ``advance()`` retires the oldest generation in O(1). Forgets by
    *age class*, not per key — 1x memory per generation. The head index is
    TRACED per-filter state (``Filter.state``), so advancing is a pure
    device rotation: no pytree-structure change, no retrace under
    jit/scan, and banks carry one head per member."""

    name = "windowed"
    supports_advance = True
    words_ndim = 2                     # (G, n_words) per member

    def supports(self, spec: FilterSpec, ctx: SelectionContext) -> bool:
        return (_single_host(ctx) and ctx.generations is not None
                and not spec.is_counting and not spec.is_fingerprint
                and spec.variant != "cbf")

    def cost(self, spec: FilterSpec, ctx: SelectionContext) -> float:
        return 1.0   # sole claimant of generations contexts

    def bits_per_key(self, target_fpr: float = None) -> Optional[float]:
        return None      # G generations: cost depends on the ring length

    def init(self, spec: FilterSpec, options) -> jnp.ndarray:
        from repro.window.ring import ring_init
        return ring_init(spec, options.generations)

    def init_state(self, spec: FilterSpec, options):
        return jnp.zeros((), jnp.int32)          # insert head (traced)

    def add(self, spec, words, keys, options, state=None):
        from repro.window.ring import ring_add
        head = jnp.zeros((), jnp.int32) if state is None else state
        return ring_add(spec, words, keys, head)

    def contains(self, spec, words, keys, options, state=None):
        from repro.window.ring import ring_contains_dispatch
        return ring_contains_dispatch(spec, words, keys)

    def advance(self, spec, words, options, state=None):
        from repro.window.ring import ring_advance
        head = jnp.zeros((), jnp.int32) if state is None else state
        return ring_advance(words, head)

    def to_dense(self, spec, words, options):
        from repro.window.ring import ring_dense
        return ring_dense(words)

    def from_dense(self, spec, dense, options):
        """Restore the whole window into generation 0 and reset the head
        (age classes are not recoverable from the canonical form)."""
        words = jnp.zeros((options.generations, dense.shape[0]), jnp.uint32)
        return words.at[0].set(dense)


class CuckooBackend(Backend):
    """Bucketed cuckoo fingerprint filter (variant='cuckoo'): u8/u16
    fingerprints in 4-slot buckets, partial-key hashing, bounded-kick
    eviction. ``remove`` at ~1x storage — half to a quarter of the
    counting filter's 4-bit counters — with an EXPLICIT insert-failure
    signal accumulated in the traced ``Filter.state`` leaf
    (``Filter.insert_failures``); no counters, no decay. jnp reference on
    every platform; ``options.impl="pallas"`` runs the Pallas VMEM kernels
    (whole-tile gather contains, block-sorted sequential inserts) in
    interpret mode — bit-identical by construction; they do not lower for
    the TPU. Banks run through the
    generic vmap machinery with proper valid masks — the OR-idempotent
    fill trick is FORBIDDEN (fingerprint inserts are not idempotent)."""

    name = "cuckoo"
    supports_remove = True
    supports_merge = False             # slots hold values, not OR-able bits
    stateful_ops = True

    def supports(self, spec: FilterSpec, ctx: SelectionContext) -> bool:
        return (_single_host(ctx) and spec.variant == "cuckoo"
                and ctx.generations is None)

    def cost(self, spec: FilterSpec, ctx: SelectionContext) -> float:
        return 1.0   # sole claimant of fingerprint specs

    def bits_per_key(self, target_fpr: float = None) -> Optional[float]:
        """f/0.95: the slot width meeting the target, at the standard
        0.95 achievable load of 4-slot buckets."""
        from repro.core import fingerprint as F
        f = F.slot_bits_for_fpr(
            target_fpr if target_fpr is not None else self.REF_FPR)
        return None if f is None else f / F.CUCKOO_MAX_LOAD

    def init(self, spec: FilterSpec, options) -> jnp.ndarray:
        return V.init(spec)

    def init_state(self, spec: FilterSpec, options):
        return jnp.zeros((), jnp.uint32)   # cumulative failed inserts

    def _use_kernels(self, spec: FilterSpec, options) -> bool:
        assert options.impl in (None, "jnp", "pallas"), options.impl
        return options.impl == "pallas"

    def _tile(self, options):
        return options.tile        # None -> fingerprint.CUCKOO_ADD_TILE

    def _update(self, spec, words, keys, options, state, valid, op):
        from repro.core import fingerprint as F
        if self._use_kernels(spec, options):
            from repro.kernels import ops
            fn = ops.cuckoo_add if op == "add" else ops.cuckoo_remove
        else:
            fn = F.cuckoo_add if op == "add" else F.cuckoo_remove
        new, flags = fn(spec, words, keys, valid=valid,
                        tile=self._tile(options))
        st = jnp.zeros((), jnp.uint32) if state is None else state
        if op == "add":
            # the failure signal is never dropped: it accumulates into the
            # traced state leaf, surviving jit/scan like any other carry
            st = st + jnp.sum(~flags).astype(jnp.uint32)
        return new, st

    def add(self, spec, words, keys, options, state=None, valid=None):
        return self._update(spec, words, keys, options, state, valid, "add")

    def remove(self, spec, words, keys, options, state=None, valid=None):
        return self._update(spec, words, keys, options, state, valid,
                            "remove")

    def contains(self, spec, words, keys, options, state=None):
        if self._use_kernels(spec, options):
            from repro.kernels import ops
            return ops.cuckoo_contains(
                spec, words, keys,
                tile=options.tile if options.tile else None,
                coop=options.coop)
        from repro.core import fingerprint as F
        return F.cuckoo_contains(spec, words, keys)

    def merge(self, spec, a, b, options):
        raise NotImplementedError(
            "cuckoo filters cannot be merged by elementwise union (slots "
            "hold fingerprint values, not OR-able bits); re-insert the "
            "other filter's keys, or use variant='quotient' (lossless "
            "fingerprint merge) when union is required")

    # -- banks: vmapped scalar ops with REAL valid masks ---------------------
    # The base-class fill trick re-adds a key per padding slot — fatal for
    # non-idempotent fingerprint inserts — so both write ops override with
    # an explicit mask; state (the failure counter) is per member.

    def _bank_state(self, words, state):
        return (jnp.zeros((words.shape[0],), jnp.uint32)
                if state is None else state)

    def _bank_update(self, spec, words, keys, options, valid, state, op):
        B, n = words.shape[0], keys.shape[1]
        v = (jnp.ones((B, n), jnp.bool_) if valid is None
             else valid.astype(jnp.bool_))
        run = jax.vmap(lambda w, k, vv, s: self._update(
            spec, w, k, options, s, vv, op))
        return run(words, keys, v, self._bank_state(words, state))

    def add_bank(self, spec, words, keys, options, valid=None, state=None):
        return self._bank_update(spec, words, keys, options, valid, state,
                                 "add")

    def remove_bank(self, spec, words, keys, options, valid=None,
                    state=None):
        return self._bank_update(spec, words, keys, options, valid, state,
                                 "remove")

    def contains_bank(self, spec, words, keys, options, state=None):
        return jax.vmap(
            lambda w, k: self.contains(spec, w, k, options))(words, keys)


class QuotientBackend(CuckooBackend):
    """Counting quotient filter (variant='quotient'): p-bit fingerprints
    split into a q-bit home slot and an r-bit stored remainder, with
    three metadata bits (occupied/continuation/shifted) packing runs into
    clusters. The ONLY engine combining ``remove`` with **lossless**
    ``merge`` and ``resize``: the metadata makes every stored fingerprint
    exactly recoverable, so union = decode both + rebuild, and resize =
    re-split p = q + r at the new table size — no raw keys anywhere
    (DESIGN.md §15). Capacity failures accumulate in the traced
    ``Filter.insert_failures`` state leaf exactly like cuckoo's. jnp
    reference on every platform; ``options.impl="pallas"`` runs the Pallas
    VMEM kernels (fused run-scan contains, sequential-ownership
    decode+rebuild updates) in interpret mode — bit-identical by
    construction. Banks: vmapped scalar ops with REAL valid masks
    (fingerprint inserts are not idempotent)."""

    name = "quotient"
    supports_remove = True
    supports_merge = True
    supports_resize = True
    stateful_ops = True

    def supports(self, spec: FilterSpec, ctx: SelectionContext) -> bool:
        return (_single_host(ctx) and spec.is_quotient
                and ctx.generations is None)

    def bits_per_key(self, target_fpr: float = None) -> Optional[float]:
        """lane/0.9: the remainder meeting the target FPR at 0.90 load,
        snapped up to the smallest u8/u16/u32 slot lane that holds it
        (+3 metadata bits)."""
        from repro.core import quotient as Q
        t = target_fpr if target_fpr is not None else self.REF_FPR
        if not 0.0 < t < 1.0:
            raise ValueError(f"target_fpr must be in (0, 1): {t}")
        r = Q.r_bits_for_fpr(t, 20)        # q barely moves the needle
        for sb in V.QUOTIENT_SLOT_BITS:
            if r <= sb - V.QF_META_BITS:
                return sb / Q.QUOTIENT_MAX_LOAD
        return None

    def _update(self, spec, words, keys, options, state, valid, op):
        from repro.core import quotient as Q
        if self._use_kernels(spec, options):
            from repro.kernels import ops
            fn = ops.quotient_add if op == "add" else ops.quotient_remove
        else:
            fn = Q.quotient_add if op == "add" else Q.quotient_remove
        new, flags = fn(spec, words, keys, valid=valid,
                        tile=self._tile(options))
        st = jnp.zeros((), jnp.uint32) if state is None else state
        if op == "add":
            st = st + jnp.sum(~flags).astype(jnp.uint32)
        return new, st

    def contains(self, spec, words, keys, options, state=None):
        if self._use_kernels(spec, options):
            from repro.kernels import ops
            return ops.quotient_contains(
                spec, words, keys,
                tile=options.tile if options.tile else None,
                coop=options.coop)
        from repro.core import quotient as Q
        return Q.quotient_contains(spec, words, keys)

    def merge(self, spec, a, b, options):
        """Lossless union: decode both multisets, rebuild the canonical
        layout — bit-identical to a table built from the concatenated key
        streams. Eager (host-side) capacity check: overflow would silently
        violate losslessness, so it is refused up front; banks merge
        member-wise and every member must fit."""
        from repro.core import quotient as Q
        fa = a.reshape((-1, a.shape[-1]))
        fb = b.reshape((-1, b.shape[-1]))
        total = (Q.occupied_slots(spec, fa).astype(jnp.int32)
                 + Q.occupied_slots(spec, fb).astype(jnp.int32))
        worst = int(jnp.max(total))
        cap = spec.n_slots - 1
        if worst > cap:
            raise ValueError(
                f"quotient merge overflows: {worst} combined fingerprints "
                f"> capacity {cap} of {spec}; resize() one side first")
        out = jax.vmap(lambda x, y: Q.quotient_merge(spec, x, y))(fa, fb)
        return out.reshape(a.shape)

    def resize(self, spec, words, new_m_bits, options):
        """(new_spec, new_words): re-split p = q + r at the new size and
        re-home every stored fingerprint. Shrinks are refused (eagerly,
        host-side) when any member stores more than the new capacity."""
        from repro.core import quotient as Q
        new_spec = Q.spec_for_resize(spec, int(new_m_bits))
        flat = words.reshape((-1, words.shape[-1]))
        if new_spec.n_slots < spec.n_slots:
            worst = int(jnp.max(Q.occupied_slots(spec, flat)))
            cap = new_spec.n_slots - 1
            if worst > cap:
                raise ValueError(
                    f"cannot shrink {spec} to m_bits={new_m_bits}: a "
                    f"member stores {worst} fingerprints > new capacity "
                    f"{cap}")
        out = jax.vmap(lambda w: Q.quotient_resize(spec, w, new_spec))(flat)
        return new_spec, out.reshape(words.shape[:-1] + (new_spec.n_words,))


def tuned_options(spec: FilterSpec, op: str = "contains",
                  regime: str = "auto", tile: int = None):
    """Pin a ``BackendOptions`` to the autotuner's plan for (spec, op).

    ``make_filter(probe="auto")`` already resolves lazily per call; this
    helper materializes the tuned (layout, probe, depth) eagerly — useful
    when the caller wants the plan recorded in the pytree aux data (one
    cached-jit executable per pinned plan) or inspected/logged.
    """
    from repro.core import tuning
    from repro.kernels import ops as kops
    from repro.kernels.sbf import DEFAULT_TILE
    from repro.api.filter import BackendOptions
    tile = tile or DEFAULT_TILE
    plan = tuning.tune_plan(spec, op, regime=kops._regime(spec, regime),
                            tile=tile)
    return BackendOptions(layout=plan.layout, tile=tile, probe=plan.probe,
                          depth=plan.depth, coop=plan.coop, mix=plan.mix)


def register_all():
    register(JnpBackend())
    register(PallasVmemBackend())
    register(PallasHbmBackend())
    register(CountingBackend())
    register(WindowedBackend())
    register(CuckooBackend())
    register(QuotientBackend())
