"""The pytree-native ``Filter``: one immutable interface over every engine.

A ``Filter`` is a registered JAX pytree: the word array is its first leaf,
an optional traced ``state`` scalar (the windowed engine's ring head) is
the second; the spec, engine name and engine options are static aux data.
That means a filter value can

* cross ``jax.jit`` / ``jax.lax.scan`` / ``shard_map`` boundaries like any
  array (no host round-trips — XLA retraces per (spec, backend, options)
  structure, exactly the role the old per-spec ``lru_cache`` jit wrappers
  played, now delegated to jit's own pytree-structure cache);
* be checkpointed by ``repro.checkpoint`` like any other model state;
* be OR-merged (``merge`` / ``repro.api.union``) with another filter of the
  same spec, even one built by a *different* engine.

**Banks.** A filter may carry a leading **bank axis**: ``bank_shape`` is
derived from the words leaf (``words.shape[:-engine.words_ndim]``), so a
``(B, n_words)`` words array IS a bank of B independent same-spec filters
— and ``jax.vmap``/``scan``/``shard_map`` over the leading axis see valid
scalar filters with no extra protocol. Bank ops accept **per-filter key
batches** (``bank_shape + (n, 2)``) or **routed flat keys**
(``keys (n, 2)`` plus ``tenants (n,)`` member ids); on engines with native
bank support a whole B-member bank executes as ONE fused device op (one
Pallas launch in the VMEM regime). See DESIGN.md §12.

All mutating-looking operations return a new ``Filter``; the word arrays
are shared/functional underneath (JAX arrays), so this costs nothing.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hashing as H
from repro.core import variants as V
from repro.core.variants import FilterSpec
from repro.api import registry


@dataclasses.dataclass(frozen=True)
class BackendOptions:
    """Static (hashable) engine parameters carried in the pytree aux data.

    Unused fields are ignored by engines that don't need them: ``layout``
    / ``tile`` steer the Pallas kernels, ``mesh``/``axis``/``capacity`` the
    distributed engines. The Bloom and counting kernels have one schedule
    per regime, chosen from the filter's bytes (``repro.kernels.ops``), so
    there is nothing else to pick; ``coop`` is read by the cuckoo and
    quotient engines alone.

    Note the windowed ring *head* is NOT here: it is traced per-filter
    state (``Filter.state``), so ``advance()`` never changes the pytree
    structure (no retrace under jit/scan).
    """

    layout: Optional[object] = None    # kernels.sbf.Layout
    tile: Optional[int] = None         # Pallas key-tile override
    coop: str = "auto"                 # cuckoo/quotient contains schedule:
                                       # "none"|"subtile"|"auto"
    mesh: Optional[object] = None      # jax.sharding.Mesh
    axis: str = "data"
    capacity: Optional[int] = None     # sharded routing capacity per (src,dst)
    generations: Optional[int] = None  # windowed engine: ring size G
    impl: Optional[str] = None         # cuckoo engine: "jnp"|"pallas"|None
                                       # (None = platform dispatch)

    def ctx(self, n_keys_hint: Optional[int] = None,
            bank: Optional[int] = None) -> registry.SelectionContext:
        return registry.SelectionContext.current(
            mesh=self.mesh, axis=self.axis, n_keys_hint=n_keys_hint,
            generations=self.generations, bank=bank)


def as_keys(keys) -> jnp.ndarray:
    """Accept u64x2 uint32 (..., 2), np.uint64 (...,), or uint32 keys.
    Leading dims are preserved, so per-member bank batches pass through."""
    if isinstance(keys, np.ndarray) and keys.dtype == np.uint64:
        from repro.core.hashing import u64x2_from_u64
        keys = u64x2_from_u64(keys)
    keys = jnp.asarray(keys)
    if keys.dtype != jnp.uint32:
        keys = keys.astype(jnp.uint32)
    return keys


def check_hash_layout(layout) -> None:
    """Refuse words written under another hash layout (``None``: a state
    saved before the layout was recorded, i.e. layout 1). Their keys sit in
    other blocks, so such a filter would answer 'absent' for keys it holds;
    it has to be rebuilt from its keys."""
    if layout != H.HASH_LAYOUT:
        raise ValueError(
            f"filter state written under hash layout {layout or 1}; this "
            f"build reads layout {H.HASH_LAYOUT} and would give false "
            "negatives on it. Rebuild the filter from its keys.")


def _prod(shape: Tuple[int, ...]) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(frozen=True, eq=False)
class Filter:
    """Immutable Bloom filter (or filter bank) bound to a registry engine.

    Construct via :func:`repro.api.make_filter` /
    :func:`repro.api.make_filter_bank` /
    :func:`repro.api.filter_for_n_items`, or :meth:`from_state`.

    ``eq=False``: identity semantics. A dataclass-generated ``__eq__``
    would compare the traced word array (ambiguous-truth-value crash);
    compare ``dense_words()`` explicitly to test filter equality.
    """

    spec: FilterSpec
    words: jnp.ndarray
    backend: str = "jnp"
    options: BackendOptions = BackendOptions()
    state: Optional[jnp.ndarray] = None   # traced engine state (ring head)

    # -- pytree protocol -----------------------------------------------------
    def tree_flatten_with_keys(self):
        return (((jax.tree_util.GetAttrKey("words"), self.words),
                 (jax.tree_util.GetAttrKey("state"), self.state)),
                (self.spec, self.backend, self.options))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        spec, backend, options = aux
        return cls(spec=spec, words=leaves[0], backend=backend,
                   options=options, state=leaves[1])

    # -- engine plumbing -----------------------------------------------------
    @property
    def engine(self) -> registry.Backend:
        return registry.get(self.backend)

    def replace(self, **kw) -> "Filter":
        return dataclasses.replace(self, **kw)

    # -- bank geometry -------------------------------------------------------
    @property
    def bank_shape(self) -> Tuple[int, ...]:
        """Leading bank dims of the words leaf; ``()`` for a scalar filter.
        Derived from the array shape, so a vmapped-over member (words minus
        its leading dim) is automatically a scalar filter again."""
        nd = self.words.ndim - self.engine.words_ndim
        return tuple(int(d) for d in self.words.shape[:max(nd, 0)])

    @property
    def bank_size(self) -> int:
        """Total member count (1 for a scalar filter)."""
        return _prod(self.bank_shape)

    @property
    def head(self):
        """Windowed engines: the traced ring head (bank-shaped for banks)."""
        return self.state

    def _base_shape(self) -> Tuple[int, ...]:
        return tuple(self.words.shape[len(self.bank_shape):])

    def _flat(self):
        """(words (B, *base), state (B,) or None) for bank dispatch.
        Per-member state is a scalar (the ring head), so it flattens to
        one entry per member."""
        B = self.bank_size
        wf = self.words.reshape((B,) + self._base_shape())
        st = None if self.state is None else self.state.reshape((B,))
        return wf, st

    def select(self, idx) -> "Filter":
        """Index the bank axis: ``select(3)`` returns member 3 as a scalar
        filter; an array index returns a sub-bank. Zero-copy (a view)."""
        if not self.bank_shape:
            raise ValueError("select() needs a bank; this is a scalar filter")
        state = None if self.state is None else self.state[idx]
        return self.replace(words=self.words[idx], state=state)

    def scatter_update(self, idx, sub: "Filter") -> "Filter":
        """Functionally replace member(s) ``idx`` with ``sub``'s words —
        the write half of ``select``; spec/backend must match."""
        if not self.bank_shape:
            raise ValueError("scatter_update() needs a bank")
        if sub.spec != self.spec or sub.backend != self.backend:
            raise ValueError("scatter_update: spec/backend mismatch")
        words = self.words.at[idx].set(sub.words)
        state = self.state
        if state is not None:
            state = state.at[idx].set(sub.state)
        return self.replace(words=words, state=state)

    # -- bulk ops ------------------------------------------------------------
    def _check_routed(self, tenants):
        if not self.bank_shape:
            raise ValueError(
                "routed (keys, tenants) ops need a bank; build one with "
                "repro.api.make_filter_bank(...)")
        if len(self.bank_shape) != 1:
            raise ValueError("routed ops address a 1-D bank axis; "
                             f"bank_shape={self.bank_shape}")

    def add(self, keys, tenants=None, valid=None) -> "Filter":
        """OR keys in; returns the updated filter (self unchanged).

        Scalar filter: ``keys (n, 2)``. Bank: either per-member batches
        ``bank_shape + (n, 2)`` (optionally valid-masked with
        ``valid bank_shape + (n,)``), or routed flat keys ``(n, 2)`` with
        ``tenants (n,)`` member ids (optionally ``valid (n,)``)."""
        keys = as_keys(keys)
        if tenants is not None:
            self._check_routed(tenants)
            if keys.shape[0] == 0:
                return self
            return _jit_add_routed(self, keys,
                                   jnp.asarray(tenants, jnp.int32), valid)
        if self.bank_shape:
            if keys.shape[-2] == 0:
                return self
            return _jit_add_bank(self, keys, valid)
        if valid is not None:
            # non-idempotent engines (cuckoo) pad with valid masks even in
            # scalar form — repeat-key padding would double-insert
            if not self.engine.stateful_ops:
                raise ValueError("valid= masks apply to bank ops only; "
                                 "filter the keys instead for a scalar add")
            if keys.shape[0] == 0:
                return self
            return _jit_add_valid(self, keys, jnp.asarray(valid))
        if keys.shape[0] == 0:
            return self
        self.engine.count_call("add", self.spec, keys.shape[0], self.options)
        return _jit_add(self, keys)

    def contains(self, keys, tenants=None) -> jnp.ndarray:
        """Membership: no false negatives, FPR-bounded positives.

        Scalar: (n,) bool. Bank batches: ``bank_shape + (n,)`` bool.
        Routed: flat (n,) bool, each key tested against its tenant's
        member filter only."""
        keys = as_keys(keys)
        if tenants is not None:
            self._check_routed(tenants)
            if keys.shape[0] == 0:
                return jnp.zeros((0,), jnp.bool_)
            return _jit_contains_routed(self, keys,
                                        jnp.asarray(tenants, jnp.int32))
        if self.bank_shape:
            if keys.shape[-2] == 0:
                return jnp.zeros(self.bank_shape + (0,), jnp.bool_)
            return _jit_contains_bank(self, keys)
        if keys.shape[0] == 0:
            return jnp.zeros((0,), jnp.bool_)
        self.engine.count_call("contains", self.spec, keys.shape[0],
                               self.options)
        return _jit_contains(self, keys)

    def remove(self, keys, tenants=None, valid=None) -> "Filter":
        """Delete keys (counting and cuckoo engines; same shapes as
        :meth:`add`). Counting: guarded decrements — no false negatives
        for keys still present, even if the removed key was never added.
        Cuckoo: each key clears ONE slot holding its fingerprint — only
        remove keys that were actually inserted, or a colliding key's
        fingerprint may be cleared and gain a false negative
        (DESIGN.md §13)."""
        if not self.engine.supports_remove:
            raise NotImplementedError(
                f"backend {self.backend!r} cannot remove keys; build the "
                f"filter with variant='countingbf' (engine 'counting'), "
                f"variant='cuckoo' or variant='quotient' (~1x storage)")
        keys = as_keys(keys)
        if tenants is not None:
            self._check_routed(tenants)
            if keys.shape[0] == 0:
                return self
            return _jit_remove_routed(self, keys,
                                      jnp.asarray(tenants, jnp.int32), valid)
        if self.bank_shape:
            if keys.shape[-2] == 0:
                return self
            return _jit_remove_bank(self, keys, valid)
        if valid is not None:
            if not self.engine.stateful_ops:
                raise ValueError("valid= masks apply to bank ops only; "
                                 "filter the keys instead for a scalar "
                                 "remove")
            if keys.shape[0] == 0:
                return self
            return _jit_remove_valid(self, keys, jnp.asarray(valid))
        if keys.shape[0] == 0:
            return self
        return _jit_remove(self, keys)

    def decay(self, steps: int = 1) -> "Filter":
        """Age the filter (or every bank member): ``steps`` uniform
        decrements of every counter (counting engine only). Keys inserted
        once disappear after one step; keys re-inserted every step persist
        — time-decayed membership."""
        if not self.engine.supports_decay:
            raise NotImplementedError(
                f"backend {self.backend!r} cannot decay; build the filter "
                f"with variant='countingbf' (engine 'counting')")
        out = self
        for _ in range(steps):
            out = _jit_decay(out)
        return out

    def advance(self) -> "Filter":
        """Slide the window one generation (windowed engine only): the
        oldest generation is retired in O(1) and becomes the new insert
        target. The head index is traced state, so this is a pure device
        rotation — jit/scan-safe, no retrace, banks advance in one op."""
        if not self.engine.supports_advance:
            raise NotImplementedError(
                f"backend {self.backend!r} cannot advance; build the filter "
                f"with generations=G (engine 'windowed')")
        return _jit_advance(self)

    def _check_merge_supported(self):
        """Uniform up-front capability check: engines whose slots hold
        values rather than OR-able bits (cuckoo) cannot union, and the
        error should say so before any engine-deep dispatch."""
        if not self.engine.supports_merge:
            raise ValueError(
                f"engine {self.backend!r} does not support merge(); the "
                f"nearest deletable engine with lossless union is "
                f"'quotient' (variant='quotient') — or rebuild from the "
                f"combined key stream")

    def _merge_windowed(self, other: "Filter") -> jnp.ndarray:
        """Windowed merge: OR the other window's dense union into MY head
        generation. Rings can NOT be merged slot-by-slot — the heads
        generally differ, so slot g is a different age class in each ring
        and a naive OR would retire the other filter's keys early (a
        false negative inside the window). Landing the union in the head
        is conservative: merged keys join the newest age class."""
        from repro.window.ring import ring_merge_dense
        dense = other.dense_words()
        if not self.bank_shape:
            return ring_merge_dense(self.words, self.state, dense)
        wf, st = self._flat()
        df = dense.reshape((wf.shape[0],) + dense.shape[len(self.bank_shape):])
        new = jax.vmap(ring_merge_dense)(wf, st, df)
        return new.reshape(self.words.shape)

    def merge(self, other: "Filter") -> "Filter":
        """OR-union. Same spec required; engines may differ (the other
        filter's state is densified and re-homed into self's engine).
        Banks merge member-wise when backend and bank shape match
        (see :meth:`bank_merge`)."""
        if other.spec != self.spec:
            raise ValueError(f"cannot merge {other.spec} into {self.spec}")
        self._check_merge_supported()
        if self.engine.supports_advance:
            # windowed self: regardless of the other engine, its dense
            # union lands in MY head generation — generation 0 (or any
            # slot-wise OR) would misalign age classes against my traced
            # head and let the next advance() retire the merged keys
            if other.bank_shape != self.bank_shape:
                raise ValueError(
                    "windowed merge needs matching bank shapes; got "
                    f"{other.bank_shape} vs {self.bank_shape}")
            new = self._merge_windowed(other)
        elif other.backend == self.backend and other.words.shape == self.words.shape:
            new = self.engine.merge(self.spec, self.words, other.words,
                                    self.options)
        elif self.bank_shape or other.bank_shape:
            raise ValueError(
                "cross-engine/shape merge is not defined for banks; use "
                "bank_merge on same-backend banks, or select() members")
        else:
            dense = other.engine.to_dense(other.spec, other.words,
                                          other.options)
            mine = self.engine.to_dense(self.spec, self.words, self.options)
            new = self.engine.from_dense(self.spec, mine | dense, self.options)
        return self.replace(words=new)

    __or__ = merge

    def bank_merge(self, other: "Filter") -> "Filter":
        """Member-wise union of two same-shape banks (member i ∪ member i).
        Bit banks OR; counting banks saturating-add their counters;
        windowed banks land the other window's union in each member's
        head generation (age classes cannot be slot-merged)."""
        if not self.bank_shape:
            raise ValueError("bank_merge() needs banks; use merge()")
        if (other.spec != self.spec or other.backend != self.backend
                or other.bank_shape != self.bank_shape):
            raise ValueError(
                f"bank_merge needs matching (spec, backend, bank_shape); "
                f"got {other.spec}/{other.backend}/{other.bank_shape} vs "
                f"{self.spec}/{self.backend}/{self.bank_shape}")
        self._check_merge_supported()
        if self.engine.supports_advance:
            new = self._merge_windowed(other)
        else:
            new = self.engine.merge(self.spec, self.words, other.words,
                                    self.options)
        return self.replace(words=new)

    def resize(self, new_m_bits: int) -> "Filter":
        """Lossless capacity change (``supports_resize`` engines — the
        quotient filter): every stored fingerprint re-homes into the new
        geometry with the p = q + r split moved, NO raw keys needed.
        Membership is exactly preserved; the FPR follows the analytic
        curve at the new size. Banks resize member-wise (one shared new
        spec); shrinks below any member's stored count raise. Returns a
        new ``Filter`` — the failure-counter state carries over, so
        escalation policies (service grow-in-place) keep their history."""
        if not self.engine.supports_resize:
            raise ValueError(
                f"engine {self.backend!r} does not support resize(); the "
                f"nearest engine with lossless grow-in-place is 'quotient' "
                f"(variant='quotient') — other variants must be rebuilt "
                f"from their key stream")
        new_spec, new_words = self.engine.resize(
            self.spec, self.words, int(new_m_bits), self.options)
        return self.replace(spec=new_spec, words=new_words)

    # -- introspection -------------------------------------------------------
    def dense_words(self) -> jnp.ndarray:
        """Canonical uint32 view: (n_words,) for a scalar filter,
        ``bank_shape + (n_words,)`` for a bank (global OR of device state,
        occupancy bits for counting engines)."""
        if not self.bank_shape:
            return self.engine.to_dense(self.spec, self.words, self.options)
        wf, _ = self._flat()
        dense = jax.vmap(
            lambda w: self.engine.to_dense(self.spec, w, self.options))(wf)
        return dense.reshape(self.bank_shape + dense.shape[1:])

    def fill_fraction(self) -> float:
        """Aggregate fill of the (bank's) canonical bit view."""
        return float(V.fill_fraction(self.dense_words()))

    @property
    def insert_failures(self) -> jnp.ndarray:
        """Fingerprint engines: traced cumulative count of inserts whose
        bounded kick chain overflowed (scalar uint32; bank-shaped for
        banks). Nonzero means keys were NOT stored — resize the filter or
        shed load. Never silently reset by ops; flows through jit/scan as
        a pytree leaf."""
        if not self.engine.stateful_ops:
            raise NotImplementedError(
                f"backend {self.backend!r} has no insert-failure state; "
                f"only fingerprint engines (variant='cuckoo'/'quotient') "
                f"can fail an insert")
        return self.state

    def load_factor(self):
        """Fingerprint engines: occupied fraction of all slots (float;
        bank-shaped array for banks). The fill metric for slot tables —
        ``fill_fraction`` counts bits and is meaningless here."""
        if not self.spec.is_fingerprint:
            raise NotImplementedError(
                f"load_factor() is a fingerprint-filter metric; "
                f"{self.spec.variant!r} filters report fill_fraction()")
        if self.spec.is_quotient:
            from repro.core import quotient as Q
            lf = Q.quotient_load_factor(self.spec, self.words)
        else:
            from repro.core import fingerprint as F
            lf = F.cuckoo_load_factor(self.spec, self.words)
        return float(lf) if not self.bank_shape else lf

    def health(self) -> dict:
        """One JSON-able operational-health dict — the dashboard surface
        shared by ``Engine.stats()``, ``launch/serve.py`` and the service
        front end (which merges its own counters on top). Keys vary by
        engine: Bloom-family filters report ``fill_fraction`` (their
        FPR driver), fingerprint filters report ``load_factor`` (worst
        member) + cumulative ``insert_failures`` (nonzero = keys were
        dropped), windowed filters add generation-ring counters
        (``generations``, per-member ``head``)."""
        out = {"backend": self.backend, "variant": self.spec.variant,
               "bank_shape": list(self.bank_shape),
               "nbytes": self.nbytes,
               "approx_count": self.approx_count()}
        if self.spec.is_fingerprint:
            lf = np.atleast_1d(np.asarray(self.load_factor(), np.float64))
            fails = np.atleast_1d(np.asarray(self.state, np.int64))
            out["load_factor"] = float(lf.max())
            out["insert_failures"] = int(fails.sum())
        else:
            out["fill_fraction"] = self.fill_fraction()
        if self.engine.supports_advance and self.state is not None:
            heads = np.atleast_1d(np.asarray(self.state, np.int64))
            out["generations"] = int(self.options.generations)
            out["head"] = (heads.reshape(-1).tolist() if self.bank_shape
                           else int(heads[0]))
        return out

    def approx_count(self) -> float:
        """Estimated number of distinct keys inserted. Fingerprint
        filters count occupied slots exactly (minus failed inserts);
        Bloom variants use the Swamidass–Baldi fill estimator
        n̂ = -(M/k) · ln(1 − fill) with M the *total* bits across the
        bank (exact in expectation for the classical filter; a close
        upper-structure estimate for blocked variants)."""
        if self.spec.is_quotient:
            from repro.core import quotient as Q
            return float(jnp.sum(Q.occupied_slots(self.spec, self.words)))
        if self.spec.is_fingerprint:
            from repro.core import fingerprint as F
            return float(jnp.sum(F.occupied_slots(self.spec, self.words)))
        fill = min(self.fill_fraction(), 1.0 - 1e-12)
        m_total = self.spec.m_bits * max(self.bank_size, 1)
        return max(0.0, -(m_total / self.spec.k) * math.log(1.0 - fill))

    def fpr_theory(self, n: int) -> float:
        """Analytic FPR at load n (per member, for banks)."""
        return V.fpr_theory(self.spec, n)

    def measure_fpr(self, n_probe: int = 1 << 16, seed: int = 1234) -> float:
        """Empirical FPR against probes from the *reserved* keyspace
        (``hashing.probe_u64x2``) — structurally disjoint from every
        ``random_u64x2``-style insert set, so each hit really is false.
        Banks probe every member and report the mean."""
        from repro.core.hashing import probe_u64x2
        probes = as_keys(probe_u64x2(n_probe, seed=seed))
        if self.bank_shape:
            probes = jnp.broadcast_to(probes, self.bank_shape + probes.shape)
        return float(np.asarray(self.contains(probes)).mean())

    @property
    def nbytes(self) -> int:
        """Actual backing storage (counting: 4x the bit filter; windowed:
        G generations; replicated: one replica per device; banks: the sum
        over members)."""
        return int(self.words.size) * self.words.dtype.itemsize

    # -- checkpointing -------------------------------------------------------
    def to_state(self) -> dict:
        """Engine-independent state pytree: dense words + spec fields.

        ``checkpoint.save`` accepts either a ``Filter`` directly (it is a
        pytree) or this canonical form; the latter restores into *any*
        engine via :meth:`from_state`. Banks record ``bank_shape`` (the
        dense words already carry the bank dims); windowed filters record
        their ring geometry so the default round-trip re-selects the
        windowed engine (age classes are not part of the canonical form —
        see DESIGN.md §10)."""
        state = {"words": self.dense_words(),
                 "spec": dataclasses.asdict(self.spec),
                 "backend": self.backend,
                 "hash_layout": H.HASH_LAYOUT}
        if self.engine.stateful_ops and self.state is not None:
            # fingerprint engines: the table IS canonical and the failure
            # counter is real operational state — both round-trip exactly
            state["engine_state"] = self.state
        if self.bank_shape:
            state["bank_shape"] = list(self.bank_shape)
        if self.options.generations is not None:
            # the head is NOT recorded: the canonical form collapses age
            # classes, so from_state always restores the union into
            # generation 0 with a fresh head (rotation-invariant)
            state["options"] = {"generations": self.options.generations}
        return state

    @classmethod
    def from_state(cls, state: dict, backend: Optional[str] = None,
                   options: BackendOptions = BackendOptions()) -> "Filter":
        check_hash_layout(state.get("hash_layout"))
        spec = FilterSpec(**{k: (v if isinstance(v, str) else int(v))
                             for k, v in state["spec"].items()})
        name = backend or state.get("backend", "jnp")
        st_opts = state.get("options") or {}
        bank_shape = tuple(int(d) for d in state.get("bank_shape", ()))
        if name == "windowed" and options.generations is None \
                and "generations" in st_opts:
            # restore the ring geometry saved by to_state(); an explicit
            # non-windowed ``backend=`` re-homes the dense union instead
            options = dataclasses.replace(
                options, generations=int(st_opts["generations"]))
        eng = registry.select(spec, name,
                              options.ctx(bank=_prod(bank_shape) or None
                                          if bank_shape else None))
        dense = jnp.asarray(state["words"], jnp.uint32)
        if bank_shape:
            B = _prod(bank_shape)
            df = dense.reshape((B, -1))
            words = jax.vmap(
                lambda d: eng.from_dense(spec, d, options))(df)
            words = words.reshape(bank_shape + words.shape[1:])
            st = eng.init_state(spec, options)
            if st is not None:
                st = jnp.broadcast_to(st, bank_shape + st.shape)
        else:
            words = eng.from_dense(spec, dense, options)
            st = eng.init_state(spec, options)
        if (eng.stateful_ops and "engine_state" in state
                and eng.name == state.get("backend")):
            st = jnp.asarray(state["engine_state"], jnp.uint32)
            if bank_shape:
                st = st.reshape(bank_shape)
        return cls(spec=spec, words=words, backend=eng.name, options=options,
                   state=st)

    def __repr__(self):
        bank = f", bank={self.bank_shape}" if self.bank_shape else ""
        return (f"Filter({self.spec}, backend={self.backend!r}, "
                f"words={tuple(self.words.shape)}{bank})")


# One jitted entry point per op form; jax's cache keys on the pytree
# structure (spec/backend/options are aux data), replacing the old per-spec
# functools.lru_cache of jitted lambdas. Bank/routed forms are separate
# entry points so each compiles to its own stable executable.
@jax.jit
def _jit_add(filt: Filter, keys: jnp.ndarray) -> Filter:
    if filt.engine.stateful_ops:
        new, st = filt.engine.add(filt.spec, filt.words, keys, filt.options,
                                  state=filt.state)
        return filt.replace(words=new, state=st)
    if filt.state is None:
        new = filt.engine.add(filt.spec, filt.words, keys, filt.options)
    else:
        new = filt.engine.add(filt.spec, filt.words, keys, filt.options,
                              state=filt.state)
    return filt.replace(words=new)


@jax.jit
def _jit_add_valid(filt: Filter, keys: jnp.ndarray,
                   valid: jnp.ndarray) -> Filter:
    new, st = filt.engine.add(filt.spec, filt.words, keys, filt.options,
                              state=filt.state, valid=valid)
    return filt.replace(words=new, state=st)


@jax.jit
def _jit_remove_valid(filt: Filter, keys: jnp.ndarray,
                      valid: jnp.ndarray) -> Filter:
    new, st = filt.engine.remove(filt.spec, filt.words, keys, filt.options,
                                 state=filt.state, valid=valid)
    return filt.replace(words=new, state=st)


@jax.jit
def _jit_contains(filt: Filter, keys: jnp.ndarray) -> jnp.ndarray:
    if filt.state is None:
        return filt.engine.contains(filt.spec, filt.words, keys, filt.options)
    return filt.engine.contains(filt.spec, filt.words, keys, filt.options,
                                state=filt.state)


@jax.jit
def _jit_remove(filt: Filter, keys: jnp.ndarray) -> Filter:
    if filt.engine.stateful_ops:
        new, st = filt.engine.remove(filt.spec, filt.words, keys,
                                     filt.options, state=filt.state)
        return filt.replace(words=new, state=st)
    new = filt.engine.remove(filt.spec, filt.words, keys, filt.options)
    return filt.replace(words=new)


@jax.jit
def _jit_decay(filt: Filter) -> Filter:
    if filt.bank_shape:
        wf, _ = filt._flat()
        new = filt.engine.decay_bank(filt.spec, wf, filt.options)
        return filt.replace(words=new.reshape(filt.words.shape))
    new = filt.engine.decay(filt.spec, filt.words, filt.options)
    return filt.replace(words=new)


@jax.jit
def _jit_advance(filt: Filter) -> Filter:
    if filt.bank_shape:
        wf, st = filt._flat()
        words, state = filt.engine.advance_bank(filt.spec, wf, filt.options,
                                                st)
        return filt.replace(words=words.reshape(filt.words.shape),
                            state=state.reshape(filt.bank_shape))
    words, state = filt.engine.advance(filt.spec, filt.words, filt.options,
                                       state=filt.state)
    return filt.replace(words=words, state=state)


def _repack_bank(filt: Filter, new) -> Filter:
    """Reshape a bank op's result back to the filter's bank shape;
    stateful engines return (words, state) and both leaves repack."""
    if filt.engine.stateful_ops:
        words, st = new
        return filt.replace(words=words.reshape(filt.words.shape),
                            state=st.reshape(filt.bank_shape or st.shape))
    return filt.replace(words=new.reshape(filt.words.shape))


@jax.jit
def _jit_add_bank(filt: Filter, keys: jnp.ndarray, valid) -> Filter:
    wf, st = filt._flat()
    B = wf.shape[0]
    kf = keys.reshape((B,) + keys.shape[len(filt.bank_shape):])
    vf = None if valid is None else valid.reshape((B, kf.shape[1]))
    new = filt.engine.add_bank(filt.spec, wf, kf, filt.options, valid=vf,
                               state=st)
    return _repack_bank(filt, new)


@jax.jit
def _jit_contains_bank(filt: Filter, keys: jnp.ndarray) -> jnp.ndarray:
    wf, st = filt._flat()
    B = wf.shape[0]
    kf = keys.reshape((B,) + keys.shape[len(filt.bank_shape):])
    out = filt.engine.contains_bank(filt.spec, wf, kf, filt.options, state=st)
    return out.reshape(filt.bank_shape + (kf.shape[1],))


@jax.jit
def _jit_remove_bank(filt: Filter, keys: jnp.ndarray, valid) -> Filter:
    wf, st = filt._flat()
    B = wf.shape[0]
    kf = keys.reshape((B,) + keys.shape[len(filt.bank_shape):])
    vf = None if valid is None else valid.reshape((B, kf.shape[1]))
    new = filt.engine.remove_bank(filt.spec, wf, kf, filt.options, valid=vf,
                                  state=st)
    return _repack_bank(filt, new)


@jax.jit
def _jit_add_routed(filt: Filter, keys: jnp.ndarray, tenants: jnp.ndarray,
                    valid) -> Filter:
    wf, st = filt._flat()
    new = filt.engine.add_bank_routed(filt.spec, wf, keys, tenants,
                                      filt.options, valid=valid, state=st)
    return _repack_bank(filt, new)


@jax.jit
def _jit_contains_routed(filt: Filter, keys: jnp.ndarray,
                         tenants: jnp.ndarray) -> jnp.ndarray:
    wf, st = filt._flat()
    return filt.engine.contains_bank_routed(filt.spec, wf, keys, tenants,
                                            filt.options, state=st)


@jax.jit
def _jit_remove_routed(filt: Filter, keys: jnp.ndarray, tenants: jnp.ndarray,
                       valid) -> Filter:
    wf, st = filt._flat()
    new = filt.engine.remove_bank_routed(filt.spec, wf, keys, tenants,
                                         filt.options, valid=valid, state=st)
    return _repack_bank(filt, new)
