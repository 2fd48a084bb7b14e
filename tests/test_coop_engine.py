"""The cuckoo and quotient engines' cooperative contains.

``coop="subtile"`` is a SCHEDULE option of these two interpret-only
families (a tile-wide early exit): it must be bit-exact with the plain
scan, stay single-launch, and thread from ``make_filter`` through
``BackendOptions`` to the kernels. The Bloom and counting engines have no
such axis: one schedule per regime (``tests/test_row_engine.py``).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.core import fingerprint as F
from repro.core import hashing as H
from repro.core import quotient as Q
from repro.core import variants as V
from repro.kernels import ops


def _keys(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


def _n_pallas(jaxpr):
    return sum(1 for e in jaxpr.jaxpr.eqns if "pallas" in e.primitive.name)


# ---------------------------------------------------------------------------
# Bit-exact parity
# ---------------------------------------------------------------------------

def test_cuckoo_coop_parity():
    spec = V.FilterSpec("cuckoo", 1 << 14, 1, slot_bits=16)
    keys = _keys(400, seed=13)
    table, _ = F.cuckoo_add(spec, F.init(spec), keys)
    probe = jnp.concatenate([keys, _keys(400, seed=14)])
    want = F.cuckoo_contains(spec, table, probe)
    for coop in ("none", "subtile"):
        got = ops.cuckoo_contains(spec, table, probe, coop=coop)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quotient_coop_parity():
    spec = V.FilterSpec("quotient", 1 << 13, 1, slot_bits=16, r_bits=9)
    keys = _keys(300, seed=15)
    table, _ = Q.quotient_add(spec, Q.init(spec), keys)
    probe = jnp.concatenate([keys, _keys(300, seed=16)])
    want = Q.quotient_contains(spec, table, probe)
    for coop in ("none", "subtile"):
        got = ops.quotient_contains(spec, table, probe, coop=coop)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Single-launch: cooperation never adds a second pallas_call
# ---------------------------------------------------------------------------

def test_coop_fingerprint_single_pallas_call():
    ck = V.FilterSpec("cuckoo", 1 << 14, 1, slot_bits=16)
    qt = V.FilterSpec("quotient", 1 << 13, 1, slot_bits=16, r_bits=9)
    keys = _keys(512, seed=1)
    for spec, op, init in ((ck, ops.cuckoo_contains, F.init),
                           (qt, ops.quotient_contains, Q.init)):
        jaxpr = jax.make_jaxpr(
            lambda f, k, o=op, s=spec: o(s, f, k, coop="subtile"))(
            init(spec), keys)
        assert _n_pallas(jaxpr) == 1, jaxpr


# ---------------------------------------------------------------------------
# API threading: make_filter -> BackendOptions -> kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant,kw", [
    ("cuckoo", dict(slot_bits=16)),
    ("quotient", dict(slot_bits=16, r_bits=9)),
])
def test_make_filter_coop_options_bit_exact(variant, kw):
    keys = _keys(300, seed=21)
    probe = jnp.concatenate([keys, _keys(200, seed=22)])
    base = api.make_filter(variant=variant, m_bits=1 << 14, **kw)
    coop = api.make_filter(variant=variant, m_bits=1 << 14, coop="subtile",
                           impl="pallas", **kw)
    assert coop.options.coop == "subtile"
    b, c = base.add(keys), coop.add(keys)
    np.testing.assert_array_equal(np.asarray(b.words), np.asarray(c.words))
    np.testing.assert_array_equal(np.asarray(b.contains(probe)),
                                  np.asarray(c.contains(probe)))


def test_backend_options_defaults_are_auto():
    from repro.api.filter import BackendOptions
    o = BackendOptions()
    assert o.coop == "auto"
    for gone in ("probe", "depth", "mix"):
        assert not hasattr(o, gone)
