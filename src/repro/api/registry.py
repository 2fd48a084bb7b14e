"""Backend registry: named, introspectable Bloom-filter engines.

Replaces the ad-hoc ``_use_pallas()`` branching of the old ``BloomFilter``
facade with a ranked query: every engine declares

* ``supports(spec, ctx)`` — can it execute this :class:`FilterSpec` in this
  context (platform, mesh, options)?
* ``cost(spec, ctx)``     — a relative cost estimate (lower is better);
  ``"auto"`` selection is ``min(cost)`` over the supporting engines.

Engines registered by ``repro.api``:

========== ==================================================================
name       execution strategy
========== ==================================================================
jnp        vectorized pure-jnp reference (row gather / segmented-OR insert)
pallas-vmem Pallas TPU kernels, filter pinned in VMEM (cache-resident regime)
pallas-hbm  Pallas TPU kernels, filter streamed from HBM via DMA scratch
counting   packed 4-bit counters (remove/decay/count); sole countingbf owner
windowed   generation-ring sliding window (advance); sole generations owner
cuckoo     bucketed fingerprint filter (remove at ~1x storage); sole owner
           of variant="cuckoo" specs — Pallas kernels on TPU, jnp elsewhere
quotient   counting quotient filter (remove + lossless merge/resize); sole
           owner of variant="quotient" specs — Pallas on TPU, jnp elsewhere
replicated  one replica per mesh device; local adds + butterfly OR merges
sharded     block-range segments per device; all_to_all ownership routing
========== ==================================================================

The registry is open: downstream code can ``register()`` additional engines
(e.g. a GPU Triton port) and they become reachable from every call site that
says ``backend="auto"`` — the seam the paper's modular design argues for.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.variants import FilterSpec


def flat_members(keys: jnp.ndarray):
    """(B, n, 2) per-member batches -> flat (keys (B*n, 2), member (B*n,)).

    The one batch-to-routed flattening convention, shared by every engine
    with a native routed path (single-host and sharded alike)."""
    B, n = keys.shape[0], keys.shape[1]
    member = jnp.repeat(jnp.arange(B, dtype=jnp.int32), n)
    return keys.reshape(-1, 2), member


@dataclasses.dataclass(frozen=True)
class SelectionContext:
    """Everything ``supports``/``cost`` may rank on, besides the spec."""

    platform: str                      # jax.default_backend(): "cpu"/"tpu"/...
    mesh: Optional[object] = None      # jax.sharding.Mesh for distributed
    axis: str = "data"
    n_keys_hint: Optional[int] = None  # expected bulk-op batch size
    generations: Optional[int] = None  # ring size -> selects the windowed engine
    bank: Optional[int] = None         # FilterBank member count (None = scalar)

    @classmethod
    def current(cls, mesh=None, axis: str = "data",
                n_keys_hint: Optional[int] = None,
                generations: Optional[int] = None,
                bank: Optional[int] = None) -> "SelectionContext":
        return cls(platform=jax.default_backend(), mesh=mesh, axis=axis,
                   n_keys_hint=n_keys_hint, generations=generations,
                   bank=bank)


class Backend:
    """Engine interface. Subclasses are stateless; all state (spec, words,
    mesh, layout, ...) travels in the :class:`repro.api.Filter` pytree.

    ``words`` layout is engine-defined (dense ``(n_words,)`` for single-host
    engines, ``(n_dev, n_words)`` replicas for ``replicated`` ...); engines
    translate to/from the canonical dense form via ``to_dense``/``from_dense``
    so filters checkpoint and migrate across engines uniformly.
    """

    name: str = "?"

    # Capability flags: which beyond-insert ops this engine implements.
    # ``Filter.remove``/``decay``/``advance`` check these before dispatch so
    # unsupported engines fail with a clear error instead of an attribute
    # surprise deep in jit. ``supports_bank`` marks a NATIVE banked path
    # (one fused device op over the whole bank); engines without it still
    # serve banks through the generic vmap fallback below unless their
    # ``supports()`` declines a ``ctx.bank`` outright.
    supports_remove: bool = False      # per-key deletion (counting/cuckoo)
    supports_decay: bool = False       # uniform aging step (counting)
    supports_advance: bool = False     # window slide (generation ring)
    supports_bank: bool = False        # native single-launch bank ops
    supports_count: bool = False       # per-key multiplicity estimates
    # Structural capability flags. ``supports_merge`` defaults True (bit
    # filters OR-union losslessly); value engines whose slots are not
    # OR-able (cuckoo) opt OUT. ``supports_resize`` defaults False: only
    # engines that can re-home their stored content into a different
    # geometry without the raw keys (quotient) opt in.
    supports_merge: bool = True        # same-spec union of two filters
    supports_resize: bool = False      # lossless grow/shrink in place

    # Stateful engines: add/remove return ``(words, state)`` instead of
    # words alone — the second value is the traced per-filter state leaf
    # (the cuckoo engine's cumulative insert-failure counter). The Filter
    # jit entry points unpack accordingly.
    stateful_ops: bool = False

    # Leading array dims of ONE filter's words: a bank prepends its shape
    # in front of these, which is how ``Filter.bank_shape`` is derived
    # (and why ``jax.vmap`` over the bank axis sees valid scalar filters).
    words_ndim: int = 1

    # What this engine runs on a TPU: "pallas" where its kernels compile
    # for the chip, "xla" where it runs its jnp implementation there.
    tpu_impl: str = "xla"

    # -- capability / ranking ------------------------------------------------
    def supports(self, spec: FilterSpec, ctx: SelectionContext) -> bool:
        raise NotImplementedError

    def cost(self, spec: FilterSpec, ctx: SelectionContext) -> float:
        """Relative cost (lower wins ``"auto"``). Dimensionless heuristic:
        ~ memory traffic per key, scaled by platform efficiency."""
        raise NotImplementedError

    # Reference FPR at which engines quote their memory cost. 1e-3 is the
    # usual dedup/contamination operating point and sits right at the
    # crossover the cost model exists to expose: a u16-fingerprint cuckoo
    # filter beats 4-bit counters ~3.4x there.
    REF_FPR = 1e-3

    def bits_per_key(self, target_fpr: float = REF_FPR) -> Optional[float]:
        """Storage bits per stored key this engine needs to hit
        ``target_fpr`` — the memory axis of ``"auto"``-style selection
        (capability flags say what an engine CAN do; this says what that
        costs). Default: the information-theoretic Bloom sizing
        c = ln(1/eps)/ln(2)^2 — bit-filter engines store exactly the
        filter. None = not meaningful for this engine (e.g. windowed,
        whose cost depends on the ring length)."""
        import math
        if not 0.0 < target_fpr < 1.0:
            raise ValueError(f"target_fpr must be in (0, 1): {target_fpr}")
        return math.log(1.0 / target_fpr) / (math.log(2.0) ** 2)

    def describe(self) -> Dict[str, str]:
        try:
            bpk = self.bits_per_key()
        except NotImplementedError:
            bpk = None
        return {"name": self.name, "doc": (self.__doc__ or "").strip(),
                "supports_remove": self.supports_remove,
                "supports_decay": self.supports_decay,
                "supports_advance": self.supports_advance,
                "supports_bank": self.supports_bank,
                "supports_count": self.supports_count,
                "supports_merge": self.supports_merge,
                "supports_resize": self.supports_resize,
                "tpu_impl": self.tpu_impl,
                "bits_per_key_at_ref_fpr":
                    None if bpk is None else round(bpk, 2),
                "ref_fpr": self.REF_FPR}

    # -- storage -------------------------------------------------------------
    def init(self, spec: FilterSpec, options) -> jnp.ndarray:
        raise NotImplementedError

    def init_state(self, spec: FilterSpec, options):
        """Optional traced per-filter state (second ``Filter`` pytree leaf).
        Only the windowed engine uses it (the ring head); ``None`` for
        everyone else keeps the pytree structure of PR-1 filters."""
        return None

    def init_bank(self, spec: FilterSpec, bank_shape: Tuple[int, ...],
                  options) -> jnp.ndarray:
        """Zeroed words for a whole bank: bank dims lead the words leaf."""
        base = self.init(spec, options)
        return jnp.zeros(tuple(bank_shape) + base.shape, base.dtype)

    def to_dense(self, spec: FilterSpec, words: jnp.ndarray, options
                 ) -> jnp.ndarray:
        """Canonical single-host ``(n_words,)`` view (global OR of all
        device-local state)."""
        return words

    def from_dense(self, spec: FilterSpec, dense: jnp.ndarray, options
                   ) -> jnp.ndarray:
        """Inverse of ``to_dense`` — engine-local storage holding the same
        logical filter."""
        return dense

    # -- bulk ops (the paper's seam) -----------------------------------------
    def add(self, spec: FilterSpec, words: jnp.ndarray, keys: jnp.ndarray,
            options) -> jnp.ndarray:
        """OR ``keys`` (n, 2) uint32 into the filter; returns new words."""
        raise NotImplementedError

    def contains(self, spec: FilterSpec, words: jnp.ndarray,
                 keys: jnp.ndarray, options) -> jnp.ndarray:
        """(n,) bool membership for ``keys`` (n, 2) uint32."""
        raise NotImplementedError

    def count_call(self, op: str, spec: FilterSpec, n_keys: int,
                   options) -> None:
        """Eager hook: ``Filter.add`` / ``Filter.contains`` call it on the
        host once per call, before the jitted op (whose body runs only
        when it is traced). Engines that count their calls override it."""

    def merge(self, spec: FilterSpec, a: jnp.ndarray, b: jnp.ndarray,
              options) -> jnp.ndarray:
        """OR-union of two same-shape word arrays (default: elementwise)."""
        return a | b

    def resize(self, spec: FilterSpec, words: jnp.ndarray, new_m_bits: int,
               options) -> Tuple[FilterSpec, jnp.ndarray]:
        """Lossless capacity change: returns ``(new_spec, new_words)`` with
        every stored element re-homed (``supports_resize`` engines only)."""
        raise NotImplementedError(
            f"engine {self.name!r} does not support resize(); use "
            f"variant='quotient' (engine 'quotient') for lossless "
            f"grow-in-place")

    # -- forgetting ops (counting / windowed engines only) -------------------
    def remove(self, spec: FilterSpec, words: jnp.ndarray, keys: jnp.ndarray,
               options) -> jnp.ndarray:
        """Delete ``keys`` (counting engines); returns new words."""
        raise NotImplementedError(
            f"engine {self.name!r} does not support remove(); use the "
            f"'counting' engine (variant='countingbf')")

    def decay(self, spec: FilterSpec, words: jnp.ndarray, options
              ) -> jnp.ndarray:
        """One uniform aging step (counting engines); returns new words."""
        raise NotImplementedError(
            f"engine {self.name!r} does not support decay(); use the "
            f"'counting' engine (variant='countingbf')")

    def advance(self, spec: FilterSpec, words: jnp.ndarray, options,
                state=None):
        """Slide the window (windowed engine): returns (words, state)."""
        raise NotImplementedError(
            f"engine {self.name!r} does not support advance(); use the "
            f"'windowed' engine (generations=...)")

    # -- bank ops (FilterBank axis) ------------------------------------------
    # Batched form: ``words`` (B, *base), per-member key batches (B, n, 2),
    # optional validity (B, n). Routed form: flat keys (N, 2) + member ids
    # (N,). The defaults below are the GENERIC VMAP FALLBACK — correct for
    # every engine whose scalar ops are jax-transformable (vmap of a Pallas
    # kernel batches into one launch with an extra grid dim); engines with
    # a native member-offset path override them and set ``supports_bank``.

    def add_bank(self, spec: FilterSpec, words: jnp.ndarray,
                 keys: jnp.ndarray, options, valid=None, state=None
                 ) -> jnp.ndarray:
        if state is None:
            run = jax.vmap(lambda w, k: self.add(spec, w, k, options))
        else:
            run = jax.vmap(
                lambda w, k, st: self.add(spec, w, k, options, state=st))
        if valid is None:
            return run(words, keys) if state is None \
                else run(words, keys, state)
        # OR-idempotent fill: each member's invalid slots repeat one of its
        # valid keys (re-adding a key is a no-op for bit filters); a member
        # with NO valid keys keeps its words verbatim. Engines with
        # non-idempotent adds (counting) must override, not inherit.
        v = valid.astype(bool)
        any_v = v.any(axis=1)                                   # (B,)
        fill = jnp.take_along_axis(
            keys, jnp.argmax(v, axis=1)[:, None, None], axis=1)  # (B, 1, 2)
        k2 = jnp.where(v[..., None], keys, fill)
        new = run(words, k2) if state is None else run(words, k2, state)
        sel = any_v.reshape((-1,) + (1,) * (words.ndim - 1))
        return jnp.where(sel, new, words)

    def contains_bank(self, spec: FilterSpec, words: jnp.ndarray,
                      keys: jnp.ndarray, options, state=None) -> jnp.ndarray:
        return jax.vmap(
            lambda w, k: self.contains(spec, w, k, options))(words, keys)

    def remove_bank(self, spec: FilterSpec, words: jnp.ndarray,
                    keys: jnp.ndarray, options, valid=None, state=None
                    ) -> jnp.ndarray:
        raise NotImplementedError(
            f"engine {self.name!r} does not support remove(); use the "
            f"'counting' engine (variant='countingbf')")

    def decay_bank(self, spec: FilterSpec, words: jnp.ndarray, options
                   ) -> jnp.ndarray:
        return jax.vmap(lambda w: self.decay(spec, w, options))(words)

    def advance_bank(self, spec: FilterSpec, words: jnp.ndarray, options,
                     state):
        return jax.vmap(
            lambda w, st: self.advance(spec, w, options, state=st)
        )(words, state)

    # Fallback routed ops materialize a (B, N) scatter (capacity = N so no
    # key can overflow — exactness over memory). Beyond this many slots the
    # cost is certainly a mistake: fail loudly and point at the native
    # alternatives instead of silently allocating gigabytes.
    _ROUTE_FALLBACK_MAX_SLOTS = 1 << 22

    def _route(self, words: jnp.ndarray, keys: jnp.ndarray,
               member: jnp.ndarray, valid=None):
        """Fallback scatter of flat routed keys into per-member batches
        (capacity = N, so nothing can overflow). Returns
        (keys (B, N, 2), valid (B, N), rank (N,)).

        O(B·N) memory and member-batch work — acceptable for the engines
        that land here (windowed/HBM banks at serving batch sizes), not
        for bulk routed traffic: use an engine with native routed support
        (jnp, pallas-vmem, counting, sharded) or ``api.route()`` with an
        explicit capacity for that."""
        from repro.core.partition import route_by_id
        B, n = words.shape[0], keys.shape[0]
        if B * n > self._ROUTE_FALLBACK_MAX_SLOTS:
            raise ValueError(
                f"routed fallback on engine {self.name!r} would scatter "
                f"{B} members x {n} keys = {B * n} slots; route this "
                f"traffic through an engine with native bank support or "
                f"pre-scatter with repro.api.route(..., capacity=...)")
        part = route_by_id(keys, member, B, capacity=max(n, 1))
        v = part.valid
        if valid is not None:
            # caller validity rides along: scatter it to the same slots
            flat_v = jnp.zeros(v.shape, jnp.uint8).reshape(-1)
            slot = member.astype(jnp.int32) * v.shape[1] + part.rank
            flat_v = flat_v.at[slot].set(valid.astype(jnp.uint8))
            v = v * flat_v.reshape(v.shape)
        return part.keys_by_seg, v, part.rank

    def add_bank_routed(self, spec: FilterSpec, words: jnp.ndarray,
                        keys: jnp.ndarray, member: jnp.ndarray, options,
                        valid=None, state=None) -> jnp.ndarray:
        kb, vb, _ = self._route(words, keys, member, valid)
        return self.add_bank(spec, words, kb, options, valid=vb, state=state)

    def contains_bank_routed(self, spec: FilterSpec, words: jnp.ndarray,
                             keys: jnp.ndarray, member: jnp.ndarray, options,
                             state=None) -> jnp.ndarray:
        kb, _, rank = self._route(words, keys, member)
        res = self.contains_bank(spec, words, kb, options, state=state)
        return res[member.astype(jnp.int32), rank]

    def remove_bank_routed(self, spec: FilterSpec, words: jnp.ndarray,
                           keys: jnp.ndarray, member: jnp.ndarray, options,
                           valid=None, state=None) -> jnp.ndarray:
        kb, vb, _ = self._route(words, keys, member, valid)
        return self.remove_bank(spec, words, kb, options, valid=vb,
                                state=state)


_REGISTRY: Dict[str, Backend] = {}

# legacy spellings accepted by select(); resolved against the live registry
_ALIASES: Dict[str, Callable[[FilterSpec, SelectionContext], str]] = {}


def register(backend: Backend, overwrite: bool = False) -> Backend:
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def register_alias(name: str,
                   resolve: Callable[[FilterSpec, SelectionContext], str]):
    _ALIASES[name] = resolve


def get(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def describe() -> Tuple[Dict[str, str], ...]:
    return tuple(_REGISTRY[n].describe() for n in names())


def cheapest_engine(needs_remove: bool = False, needs_decay: bool = False,
                    needs_count: bool = False, needs_merge: bool = False,
                    needs_resize: bool = False,
                    target_fpr: float = Backend.REF_FPR) -> str:
    """Rank registered engines by :meth:`Backend.bits_per_key` among those
    whose capability flags cover the required ops; returns the cheapest
    engine's name.

    This is the memory-aware half of ``"auto"`` selection the capability
    flags alone couldn't express: with ``needs_remove=True`` the cuckoo
    engine (~f/0.95 bits/key) beats the counting engine (4x the bit
    filter) unless per-key counts/decay are also required — exactly the
    deletable-AMQ trade the fingerprint literature documents. Adding
    ``needs_merge=True`` or ``needs_resize=True`` rules cuckoo out and
    selects the quotient engine — the only structure combining deletion
    with lossless union and grow-in-place."""
    best = None
    for name in names():
        eng = get(name)
        if needs_remove and not eng.supports_remove:
            continue
        if needs_decay and not eng.supports_decay:
            continue
        if needs_count and not eng.supports_count:
            continue
        if needs_merge and not eng.supports_merge:
            continue
        if needs_resize and not eng.supports_resize:
            continue
        try:
            bpk = eng.bits_per_key(target_fpr)
        except NotImplementedError:
            bpk = None
        if bpk is None:
            continue
        if best is None or bpk < best[0]:
            best = (bpk, name)
    if best is None:
        raise ValueError(
            f"no registered engine satisfies needs_remove={needs_remove}, "
            f"needs_decay={needs_decay}, needs_count={needs_count}, "
            f"needs_merge={needs_merge}, needs_resize={needs_resize} at "
            f"fpr {target_fpr:g}")
    return best[1]


def select(spec: FilterSpec, backend: str = "auto",
           ctx: Optional[SelectionContext] = None) -> Backend:
    """Resolve a backend name (or ``"auto"``/alias) to an engine.

    ``"auto"`` ranks every supporting engine by ``cost(spec, ctx)`` and
    returns the cheapest — the scattered if/else of the old facade, as one
    ordered query.
    """
    ctx = ctx or SelectionContext.current()
    if backend in _ALIASES:
        backend = _ALIASES[backend](spec, ctx)
    if backend != "auto":
        eng = get(backend)
        if not eng.supports(spec, ctx):
            raise ValueError(f"backend {backend!r} does not support {spec} "
                             f"in context {ctx}")
        return eng
    ranked = sorted(((eng.cost(spec, ctx), name)
                     for name, eng in _REGISTRY.items()
                     if eng.supports(spec, ctx)))
    if not ranked:
        raise ValueError(f"no registered backend supports {spec} ({ctx})")
    return _REGISTRY[ranked[0][1]]
