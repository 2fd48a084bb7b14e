"""Generation-ring aging: sliding-window membership without per-key deletes.

A :class:`WindowedFilter` holds G same-spec generation sub-filters stacked
``(G, n_words)`` plus a head index:

* ``add`` inserts into the **head** generation only;
* ``contains`` ORs the whole ring *inside the probe* — a fold + row
  gather in jnp on every platform (the ``kernels.ring`` Pallas pass runs
  in interpret mode only); the head index is irrelevant to queries, so
  advancing never invalidates
  compiled query code;
* ``advance()`` rotates the head to the oldest slot and zeroes it — O(1)
  in keys (one sub-filter memset, no rehashing), retiring every key whose
  last insert was >= G advances ago.

A key inserted into generation g stays queryable for at least G-1 and at
most G advances — the classic "double-buffered Bloom filter" generalized
to G slots: sizing each generation for W/G keys with G=2..8 trades memory
for eviction granularity.

The pure ``ring_*`` functions are the engine seam: both the
:class:`WindowedFilter` convenience class and the ``"windowed"`` registry
engine (repro.api.backends) call them, so the two surfaces stay
bit-identical.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import variants as V
from repro.core.variants import FilterSpec


# ---------------------------------------------------------------------------
# Pure ring transforms (engine seam)
# ---------------------------------------------------------------------------

def ring_init(spec: FilterSpec, generations: int) -> jnp.ndarray:
    assert generations >= 2, "a ring needs >= 2 generations to slide"
    assert not spec.is_counting, "ring generations are bit filters"
    return jnp.zeros((generations, spec.n_words), jnp.uint32)


def ring_add(spec: FilterSpec, rings: jnp.ndarray, keys: jnp.ndarray,
             head) -> jnp.ndarray:
    """Insert into the head generation (single-filter bulk add).

    ``head`` may be a Python int or a traced/device int32 scalar — the
    dynamic index keeps add() retrace-free when the head is carried as
    traced state (see :class:`WindowedFilter` / ``Filter.state``)."""
    return rings.at[head].set(V.add_rows(spec, rings[head], keys))


def ring_contains_dispatch(spec: FilterSpec, rings: jnp.ndarray,
                           keys: jnp.ndarray) -> jnp.ndarray:
    """Fused OR-ring membership (jnp fold). The ring Pallas kernels
    (``ops.ring_contains``) do not lower for the TPU, so the windowed
    engine runs this XLA path on every platform."""
    from repro.kernels.ring import ring_contains_ref
    return ring_contains_ref(spec, rings, keys)


def ring_advance(rings: jnp.ndarray, head) -> tuple:
    """Retire the oldest generation: it becomes the new (empty) head.

    O(1) in inserted keys — one sub-filter zeroing, no rehash, no copy of
    the surviving generations. ``head`` may be traced (device int32): the
    rotation is a dynamic row update, so advancing never changes pytree
    structure or forces a retrace under ``jit``/``scan``."""
    new_head = (head + 1) % rings.shape[0]
    return rings.at[new_head].set(jnp.uint32(0)), new_head


def ring_merge_dense(rings: jnp.ndarray, head, dense: jnp.ndarray
                     ) -> jnp.ndarray:
    """OR a dense key-set union into the HEAD generation.

    The well-defined windowed merge: two rings' generation arrays cannot
    be ORed slot-by-slot (their heads generally differ, so slot g holds a
    *different age class* in each ring — a naive OR misaligns ages and
    later advances retire keys early, a false negative inside the
    window). Collapsing the other ring to its dense union and landing it
    in the head instead is conservative: merged-in keys join the newest
    age class and live at least G-1 more advances."""
    return rings.at[head].set(rings[head] | dense)


def ring_dense(rings: jnp.ndarray) -> jnp.ndarray:
    """Canonical (n_words,) view: OR-fold of all generations."""
    dense = rings[0]
    for g in range(1, rings.shape[0]):          # static fold (G is small)
        dense = dense | rings[g]
    return dense


# ---------------------------------------------------------------------------
# WindowedFilter — the convenience surface
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(frozen=True, eq=False)
class WindowedFilter:
    """Immutable sliding-window Bloom filter over a generation ring.

    The ring array AND the head index are pytree leaves — the head is a
    traced device scalar, so ``advance()`` only rotates data: the pytree
    *structure* is invariant and jitted/scanned code never retraces on a
    window slide (it used to, when the head was static aux data).
    """

    spec: FilterSpec
    rings: jnp.ndarray              # (G, n_words) uint32
    head: jnp.ndarray = None        # () int32 — insert generation (traced)

    def __post_init__(self):
        if self.head is None:
            object.__setattr__(self, "head", jnp.zeros((), jnp.int32))

    # -- pytree protocol -----------------------------------------------------
    def tree_flatten_with_keys(self):
        return (((jax.tree_util.GetAttrKey("rings"), self.rings),
                 (jax.tree_util.GetAttrKey("head"), self.head)),
                (self.spec,))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        (spec,) = aux
        return cls(spec=spec, rings=leaves[0], head=leaves[1])

    # -- construction --------------------------------------------------------
    @classmethod
    def create(cls, variant: str = "sbf", m_bits: int = 1 << 20, k: int = 8,
               block_bits: int = 256, z: int = 1, generations: int = 4
               ) -> "WindowedFilter":
        spec = FilterSpec(variant=variant, m_bits=m_bits, k=k,
                          block_bits=block_bits, z=z)
        return cls(spec=spec, rings=ring_init(spec, generations))

    @classmethod
    def for_window(cls, window_keys: int, bits_per_key: float = 16.0,
                   generations: int = 4, variant: str = "sbf",
                   block_bits: int = 256) -> "WindowedFilter":
        """Size the ring for a sliding window of ``window_keys`` at c
        bits/key.

        Generations share hash functions, so the queried union behaves like
        ONE m-bit filter holding the whole window — each generation must
        therefore be sized for the full window load, and the ring costs
        G x m bits total. That G-fold amplification is the price of O(1)
        eviction (cf. the 2x of the classic double-buffered Bloom filter);
        the counting filter makes the opposite trade (4x memory, per-key
        deletes)."""
        n = max(window_keys, 1)
        m = 1 << max(int(np.ceil(np.log2(n * bits_per_key))), 10)
        s = block_bits // V.WORD_BITS
        k = max(int(round(V.optimal_k(m / n))), 1)
        if variant == "sbf":
            k = max(s, (k // s) * s) if k >= s else k
        k = min(k, 32)
        return cls.create(variant=variant, m_bits=m, k=k,
                          block_bits=block_bits, generations=generations)

    # -- ops -----------------------------------------------------------------
    @property
    def generations(self) -> int:
        return self.rings.shape[0]

    def add(self, keys) -> "WindowedFilter":
        from repro.api.filter import as_keys
        keys = as_keys(keys)
        if keys.shape[0] == 0:
            return self
        return dataclasses.replace(
            self, rings=ring_add(self.spec, self.rings, keys, self.head))

    def contains(self, keys) -> jnp.ndarray:
        from repro.api.filter import as_keys
        keys = as_keys(keys)
        if keys.shape[0] == 0:
            return jnp.zeros((0,), jnp.bool_)
        return ring_contains_dispatch(self.spec, self.rings, keys)

    def advance(self) -> "WindowedFilter":
        """Slide the window: drop the oldest generation, open a fresh head."""
        rings, head = ring_advance(self.rings, self.head)
        return dataclasses.replace(self, rings=rings, head=head)

    # -- introspection -------------------------------------------------------
    def dense_words(self) -> jnp.ndarray:
        return ring_dense(self.rings)

    def fill_fraction(self) -> float:
        """Fill of the ring union (the quantity governing the window FPR)."""
        return float(V.fill_fraction(self.dense_words()))

    def generation_fill(self) -> np.ndarray:
        """(G,) per-generation fill — a saw-tooth in steady state."""
        return np.array([float(V.fill_fraction(self.rings[g]))
                         for g in range(self.generations)])

    def fpr_theory(self, window_n: int) -> float:
        """Analytic FPR with ``window_n`` keys spread across the ring.

        Union of G independent same-spec filters at load n/G each ~ one
        filter at load n (same expected fill), so the single-filter model
        applies to the ring union."""
        return V.fpr_theory(self.spec, window_n)

    def measure_fpr(self, n_probe: int = 1 << 16, seed: int = 1234) -> float:
        from repro.core.hashing import probe_u64x2
        probes = probe_u64x2(n_probe, seed=seed)
        return float(np.asarray(self.contains(probes)).mean())

    @property
    def nbytes(self) -> int:
        return self.generations * self.spec.m_bits // 8

    def __repr__(self):
        try:
            head = int(self.head)
        except Exception:               # traced head inside jit
            head = "<traced>"
        return (f"WindowedFilter({self.spec}, G={self.generations}, "
                f"head={head})")
