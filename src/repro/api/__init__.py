"""``repro.api`` — the public Bloom-filter surface.

One immutable, pytree-registered :class:`Filter` over every execution
engine, and a :mod:`registry <repro.api.registry>` of named backends
replacing scattered dispatch branches:

    import repro.api as api

    f = api.filter_for_n_items(1_000_000, bits_per_key=16)   # backend="auto"
    f = f.add(keys)                       # immutable: returns a new Filter
    hits = f.contains(keys)
    g = api.union(f, other)               # OR-union, cross-engine OK

    api.backends()                        # ('jnp', 'pallas-hbm', ...)
    f2 = api.make_filter("sbf", m_bits=1 << 24, k=8, backend="pallas-vmem")

Filters pass through ``jax.jit`` / ``jax.lax.scan`` / checkpointing like
any other pytree; see DESIGN.md §5 for the protocol contract.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax.numpy as jnp

from repro.core import variants as _V
from repro.core.variants import FilterSpec
from repro.api import registry
from repro.api.filter import BackendOptions, Filter, as_keys
from repro.api import backends as _backends
from repro.api import dist_backends as _dist_backends

_backends.register_all()
_dist_backends.register_all()


def _legacy_pallas(spec: FilterSpec, ctx: registry.SelectionContext) -> str:
    """Alias for the old ``backend="pallas"`` spelling: pick the regime the
    old facade would have (VMEM while the filter fits, else HBM)."""
    if registry.get("pallas-vmem").supports(spec, ctx):
        return "pallas-vmem"
    return "pallas-hbm"


registry.register_alias("pallas", _legacy_pallas)


def make_filter(variant: str = "sbf", m_bits: int = 1 << 20, k: int = 8,
                block_bits: int = 256, z: int = 1, backend: str = "auto",
                layout=None, tile: Optional[int] = None,
                coop: str = "auto", mesh=None,
                axis: str = "data", capacity: Optional[int] = None,
                generations: Optional[int] = None,
                slot_bits: int = 8, slots_per_bucket: int = 4,
                r_bits: int = 0, impl: Optional[str] = None) -> Filter:
    """Build an empty :class:`Filter` for an explicit geometry.

    ``backend="auto"`` runs the registry's ranked query (pass ``mesh=`` to
    bring the distributed engines into the candidate set). Forgetting
    filters: ``variant="countingbf"`` selects the counting engine
    (``remove``/``decay``); ``generations=G`` selects the windowed engine
    (``advance``); ``variant="cuckoo"`` selects the fingerprint engine
    (``remove`` at ~1x storage, ``slot_bits``/``slots_per_bucket``
    geometry, ``impl`` pins its jnp vs Pallas path);
    ``variant="quotient"`` selects the counting quotient engine
    (``remove`` + lossless ``merge``/``resize``; ``r_bits`` sets the
    stored remainder width). Kernel knobs: ``layout`` (Θ) and ``tile``
    override the defaults of the Bloom and counting kernels, whose
    schedule follows from the filter's bytes; ``coop`` picks the cuckoo
    and quotient contains schedule (``"subtile"`` the bit-exact early
    exit, ``"auto"`` the perfmodel's choice)."""
    spec = FilterSpec(variant=variant, m_bits=m_bits, k=k,
                      block_bits=block_bits, z=z, slot_bits=slot_bits,
                      slots_per_bucket=slots_per_bucket, r_bits=r_bits)
    options = BackendOptions(layout=layout, tile=tile, coop=coop, mesh=mesh,
                             axis=axis, capacity=capacity,
                             generations=generations, impl=impl)
    eng = registry.select(spec, backend, options.ctx())
    return Filter(spec=spec, words=eng.init(spec, options), backend=eng.name,
                  options=options, state=eng.init_state(spec, options))


def make_filter_bank(bank, variant: str = "sbf", m_bits: int = 1 << 14,
                     k: int = 8, block_bits: int = 256, z: int = 1,
                     backend: str = "auto", layout=None,
                     tile: Optional[int] = None, coop: str = "auto",
                     mesh=None,
                     axis: str = "data", capacity: Optional[int] = None,
                     generations: Optional[int] = None,
                     slot_bits: int = 8, slots_per_bucket: int = 4,
                     r_bits: int = 0, impl: Optional[str] = None) -> Filter:
    """Build an empty :class:`Filter` **bank**: ``bank`` independent
    same-spec member filters behind one value, with the bank dims leading
    the words leaf.

    ``bank`` is an int (1-D bank) or a shape tuple. ``m_bits`` is the size
    of EACH member — the multi-tenant sweet spot is many VMEM-small
    members, which is exactly the regime where the native bank engines
    fuse B members into one device launch. Per-filter batches address
    members positionally (``keys: bank_shape + (n, 2)``); routed ops take
    flat ``(keys, tenants)`` with ``tenants`` indexing the bank axis.
    The remaining knobs match :func:`make_filter` (mesh/axis/capacity
    select the bank-axis-sharded distributed engine, generations the
    windowed one)."""
    bank_shape = (int(bank),) if isinstance(bank, (int, np.integer)) \
        else tuple(int(d) for d in bank)
    if not bank_shape or any(d <= 0 for d in bank_shape):
        raise ValueError(f"bank shape must be non-empty and positive; "
                         f"got {bank_shape}")
    spec = FilterSpec(variant=variant, m_bits=m_bits, k=k,
                      block_bits=block_bits, z=z, slot_bits=slot_bits,
                      slots_per_bucket=slots_per_bucket, r_bits=r_bits)
    options = BackendOptions(layout=layout, tile=tile, coop=coop, mesh=mesh,
                             axis=axis, capacity=capacity,
                             generations=generations, impl=impl)
    total = 1
    for d in bank_shape:
        total *= d
    eng = registry.select(spec, backend, options.ctx(bank=total))
    words = eng.init_bank(spec, bank_shape, options)
    state = eng.init_state(spec, options)
    if state is not None:
        state = jnp.zeros(bank_shape + state.shape, state.dtype)
    return Filter(spec=spec, words=words, backend=eng.name, options=options,
                  state=state)


def route(keys, tenants, n_tenants: int, capacity: Optional[int] = None):
    """Scatter flat routed keys into fixed-shape per-tenant batches.

    Returns ``(keys_by_tenant (T, cap, 2), valid (T, cap))`` — the
    explicit form of the scatter path the generic bank fallback uses for
    ``(keys, tenants)`` ops on engines without a native routed kernel.
    ``capacity`` defaults to ``len(keys)`` (nothing can overflow); a
    smaller static capacity bounds memory and drops the overflow (use the
    native routed ops when exactness matters)."""
    from repro.core.partition import route_by_id
    keys = as_keys(keys)
    part = route_by_id(keys, jnp.asarray(tenants, jnp.int32), int(n_tenants),
                       int(capacity or max(keys.shape[0], 1)))
    return part.keys_by_seg, part.valid


def filter_for_n_items(n: int, bits_per_key: float = 16.0,
                       variant: str = "sbf", block_bits: int = 256,
                       k: Optional[int] = None, bank=None,
                       target_fpr: Optional[float] = None, **kw) -> Filter:
    """Size a filter for ~n items at c = bits_per_key (m rounded to pow2),
    choosing k near the space-optimal k* = c ln 2 (Eq. 2), snapped to the
    variant's structural constraints (k ≡ 0 mod s for SBF, mod z for CSBF).
    ``bank=B`` sizes each of B members for ~n items and returns the bank.

    ``variant="cuckoo"`` sizes buckets for ~n keys at load factor <=
    ``fingerprint.CUCKOO_MAX_LOAD`` (0.95) instead: the slot width comes
    from ``target_fpr`` when given (smallest u8/u16 meeting it), else from
    ``bits_per_key`` (u8 fits under ~12 bits/key, u16 above); pass
    ``slot_bits=`` to pin it. ``variant="quotient"`` sizes a quotient
    table for ~n keys at load <= ``quotient.QUOTIENT_MAX_LOAD`` (0.90),
    deriving the q/r split from ``target_fpr`` (pass ``slot_bits=`` to
    pin the lane width)."""
    if variant == "quotient":
        from repro.core import quotient as Q
        spec = Q.spec_for_n(n, target_fpr=target_fpr,
                            slot_bits=kw.pop("slot_bits", None))
        common = dict(m_bits=spec.m_bits, slot_bits=spec.slot_bits,
                      r_bits=spec.r_bits, **kw)
        if bank is not None:
            return make_filter_bank(bank, variant="quotient", **common)
        return make_filter(variant="quotient", **common)
    if variant == "cuckoo":
        from repro.core import fingerprint as F
        sb = kw.pop("slot_bits", None)
        spb = kw.pop("slots_per_bucket", 4)
        if sb is None and target_fpr is None:
            sb = 8 if bits_per_key <= 12.0 else 16
        spec = F.spec_for_n(n, target_fpr=target_fpr, slot_bits=sb,
                            slots_per_bucket=spb)
        common = dict(m_bits=spec.m_bits, k=spec.k, slot_bits=spec.slot_bits,
                      slots_per_bucket=spec.slots_per_bucket, **kw)
        if bank is not None:
            return make_filter_bank(bank, variant="cuckoo", **common)
        return make_filter(variant="cuckoo", **common)
    if target_fpr is not None:
        # iso-error sizing for the Bloom families: the exact inverse the
        # AMQ comparison harness needs — smallest pow2 m whose
        # variant-aware analytic FPR meets the target at load n
        bits_per_key = _V.space_optimal_c(
            variant, block_bits, kw.get("z", 1), n, target_fpr)
    m = 1 << max(int(np.ceil(np.log2(max(n, 1) * bits_per_key))), 10)
    if k is None:
        k = _V.snap_k(variant, m / max(n, 1), block_bits, kw.get("z", 1))
    if bank is not None:
        return make_filter_bank(bank, variant=variant, m_bits=m, k=k,
                                block_bits=block_bits, **kw)
    return make_filter(variant=variant, m_bits=m, k=k, block_bits=block_bits,
                       **kw)


def filter_for_workload(n: int, target_fpr: float = 1e-3,
                        needs_remove: bool = False,
                        needs_decay: bool = False,
                        needs_count: bool = False,
                        needs_merge: bool = False,
                        needs_resize: bool = False,
                        bank=None, **kw) -> Filter:
    """Capability- and memory-aware ``"auto"``: pick the cheapest engine
    (by ``bits_per_key`` at ``target_fpr``, see ``registry.describe()``)
    whose flags cover the requested ops, then size it for ~n keys.

    The interesting crossover this encodes: ``needs_remove=True`` alone
    selects the cuckoo fingerprint engine (~f/0.95 bits/key) over the
    counting engine (4x the bit filter); adding ``needs_decay`` or
    ``needs_count`` — capabilities only counters provide — flips it back;
    adding ``needs_merge`` or ``needs_resize`` — union / grow-in-place,
    which value slots can't OR — selects the quotient engine instead."""
    engine = registry.cheapest_engine(needs_remove=needs_remove,
                                      needs_decay=needs_decay,
                                      needs_count=needs_count,
                                      needs_merge=needs_merge,
                                      needs_resize=needs_resize,
                                      target_fpr=target_fpr)
    variant = {"counting": "countingbf", "cuckoo": "cuckoo",
               "quotient": "quotient"}.get(engine, "sbf")
    kw.setdefault("backend", "auto")   # the variant pins the engine family
    return filter_for_n_items(n, variant=variant, target_fpr=target_fpr,
                              bank=bank, **kw)


def union(*filters: Filter) -> Filter:
    """OR-union of same-spec filters (cross-engine allowed); the result
    lives on the first filter's engine."""
    if not filters:
        raise ValueError("union() needs at least one filter")
    out = filters[0]
    for f in filters[1:]:
        out = out.merge(f)
    return out


def backends() -> tuple:
    """Registered engine names (see ``describe_backends`` for details)."""
    return registry.names()


def describe_backends() -> tuple:
    return registry.describe()


def get_backend(name: str) -> registry.Backend:
    return registry.get(name)


__all__ = ["Filter", "FilterSpec", "BackendOptions", "as_keys", "registry",
           "make_filter", "make_filter_bank", "route", "filter_for_n_items",
           "filter_for_workload", "union", "backends", "describe_backends",
           "get_backend"]
