"""The row engine's other paths against the plain reference: banks of 1, 4
and 16 members, routed, with invalid slots; the sharded engine's segments
at 0%, 25% and 100% occupancy; and every dispatch entry point with
``REPRO_TUNING_CACHE`` naming a file of poisoned plans — nothing opens
it, and the answers stay exact. The single filter's regimes and batch sizes
are in ``tests/test_row_engine.py``.
"""
import builtins
import io
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import distributed as D
from repro.core import hashing as H
from repro.core import variants as V
from repro.kernels import ops, ref
from repro.kernels import sbf as sbf_k

M = 1 << 16


def _keys(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


def _absent(n, seed=0):
    """Keys from the reserved keyspace: never added by ``_keys``."""
    return jnp.asarray(H.probe_u64x2(n, seed=seed))


def _eq(got, want):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Banks: routed keys, invalid slots
# ---------------------------------------------------------------------------

BANK_MEMBER_BITS = 1 << 12


@pytest.mark.parametrize("n", [100, 700])
@pytest.mark.parametrize("counting", [False, True], ids=["bits", "counts"])
@pytest.mark.parametrize("B", [1, 4, 16])
def test_bank_routed_with_invalid_slots(B, counting, n):
    """``n`` routed keys into a B-member bank, about a quarter of them
    marked invalid: the bank kernel's words equal the reference bank's
    (invalid slots set nothing), and its answers equal the reference's
    for the keys and for absent keys."""
    variant = "countingbf" if counting else "sbf"
    spec = V.FilterSpec(variant, BANK_MEMBER_BITS, 8, block_bits=256)
    assert ops.bank_vmem_resident(spec, B)
    rng = np.random.default_rng(B * 10 + counting)
    keys = _keys(n, seed=B + 100)
    member = jnp.asarray(rng.integers(0, B, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) >= 0.25, jnp.uint8)
    bank = jnp.zeros((B, spec.storage_words), jnp.uint32)
    absent = _absent(n, seed=B)
    if counting:
        got = ops.counting_bank_update(spec, bank, keys, member, "add",
                                       valid=valid)
        want = V.bank_counting_update(spec, bank, keys, member, valid, "add")
        _eq(got, want)
        for probe in (keys, absent):
            _eq(ops.counting_bank_contains(spec, want, probe, member),
                V.bank_counting_contains(spec, want, probe, member))
        _eq(ops.counting_bank_update(spec, want, keys, member, "remove",
                                     valid=valid),
            V.bank_counting_update(spec, want, keys, member, valid,
                                   "remove"))
        return
    got = ops.bloom_bank_add(spec, bank, keys, member, valid=valid)
    want = V.bank_add_rows(spec, bank, keys, member, valid=valid)
    _eq(got, want)
    for probe in (keys, absent):
        _eq(ops.bloom_bank_contains(spec, want, probe, member),
            V.bank_contains_rows(spec, want, probe, member))
    hits = np.asarray(ops.bloom_bank_contains(spec, want, keys, member))
    assert hits[np.asarray(valid) > 0].all()


# ---------------------------------------------------------------------------
# The sharded engine's segments
# ---------------------------------------------------------------------------

SEG = D.segment_spec(V.FilterSpec("sbf", M, 8, block_bits=256), 4)


@pytest.mark.parametrize("prefilled", [False, True], ids=["empty", "filled"])
@pytest.mark.parametrize("occupancy", [0.0, 0.25, 1.0])
def test_segment_ops_at_occupancy(occupancy, prefilled):
    """600 exchange slots (padded to 3 tiles) of which ``occupancy`` carry
    a key, into an empty segment or one already holding other keys: the
    update adds exactly the carried keys, and the probe answers as the
    reference for every carried slot."""
    n = 600
    rng = np.random.default_rng(int(occupancy * 100) + prefilled)
    keys = _keys(n, seed=61)
    valid = np.zeros(n, np.uint8)
    valid[: int(n * occupancy)] = 1
    valid = rng.permutation(valid)
    seg = V.init(SEG)
    if prefilled:
        seg = V.add_scatter(SEG, seg, _keys(400, seed=62))
    out = ops.segment_add(SEG, seg, keys, jnp.asarray(valid))
    want = V.add_scatter(SEG, seg, keys[valid > 0])
    _eq(out, want)
    probe = jnp.concatenate([keys[:300], _absent(300, seed=63)])
    pvalid = np.concatenate([valid[:300], np.ones(300, np.uint8)])
    got = np.asarray(ops.segment_contains(SEG, out, probe,
                                          jnp.asarray(pvalid)))
    carried = pvalid > 0
    _eq(got[carried], np.asarray(V.contains(SEG, want, probe))[carried])


# ---------------------------------------------------------------------------
# The regime follows from the bytes, at the VMEM budget's edge
# ---------------------------------------------------------------------------

BUDGET_BITS = sbf_k.VMEM_FILTER_BYTES * 8


@pytest.mark.parametrize("variant,m_bits,bank,impl", [
    ("sbf", BUDGET_BITS, None, "pallas-vmem"),
    ("sbf", 2 * BUDGET_BITS, None, "pallas-hbm"),
    ("countingbf", BUDGET_BITS // 4, None, "pallas-vmem"),
    ("countingbf", BUDGET_BITS // 2, None, "pallas-hbm"),
    ("sbf", 1 << 20, 32, "pallas-vmem"),
    ("sbf", 1 << 20, 33, "xla"),
], ids=["bits-at-budget", "bits-over", "counts-at-budget", "counts-over",
        "bank-at-budget", "bank-over"])
def test_regime_follows_the_bytes(variant, m_bits, bank, impl):
    """``regime="auto"`` takes the VMEM schedule while the filter's (or the
    whole bank's) words fit ``VMEM_FILTER_BYTES``, and the HBM schedule
    (a bank: the jnp super-filter) past it. Traced only: nothing runs."""
    spec = V.FilterSpec(variant, m_bits, 8, block_bits=256)
    shape = (spec.storage_words,) if bank is None else (
        bank, spec.storage_words)
    words = jax.ShapeDtypeStruct(shape, jnp.uint32)
    keys = jax.ShapeDtypeStruct((256, 2), jnp.uint32)
    if bank is not None:
        op = "bank_contains"
        fn = lambda w, k: ops.bloom_bank_contains(
            spec, w, k, jnp.arange(256, dtype=jnp.int32) % bank)
    elif spec.is_counting:
        op, fn = "counting_contains", lambda w, k: ops.counting_contains(
            spec, w, k)
    else:
        op, fn = "bloom_contains", lambda w, k: ops.bloom_contains(spec, w, k)
    before = ops.dispatched()
    jax.make_jaxpr(fn)(words, keys)
    after = ops.dispatched()
    ran = {i for (o, i), c in after.items() if o == op
           and c > before.get((o, i), 0)}
    assert ran == {impl}


# ---------------------------------------------------------------------------
# No tuner, no plan file: every entry point
# ---------------------------------------------------------------------------

BITS = V.FilterSpec("sbf", M, 8, block_bits=256)
CNT = V.FilterSpec("countingbf", M, 8, block_bits=256)
BANK_BITS = V.FilterSpec("sbf", BANK_MEMBER_BITS, 8, block_bits=256)
BANK_CNT = V.FilterSpec("countingbf", BANK_MEMBER_BITS, 8, block_bits=256)


def _poisoned_plans(path):
    """A plan file as a tuner would have left it, with every entry naming
    a schedule, hash mix and DMA depth the row engine does not have."""
    plan = {"theta": 4, "probe": "gather", "depth": 1, "n_segments": 8,
            "coop": "subtile", "mix": "full"}
    with open(path, "w") as f:
        json.dump({f"plan2|cpu|{s}|{op}|{r}|structural|tile{t}"
                   f"|coop:auto|mix:auto": plan
                   for s in (BITS, CNT, BANK_BITS, BANK_CNT)
                   for op in ("contains", "add") for r in ("vmem", "hbm")
                   for t in (8, 256)}, f)


def _bank_inputs(spec, n=300, B=4):
    keys = _keys(n, seed=71)
    member = jnp.arange(n, dtype=jnp.int32) % B
    return jnp.zeros((B, spec.storage_words), jnp.uint32), keys, member


def _ep_bloom_contains():
    f = ref.bloom_add_ref(BITS, V.init(BITS), _keys(300, seed=72))
    probe = jnp.concatenate([_keys(300, seed=72), _absent(300, seed=72)])
    return (ops.bloom_contains(BITS, f, probe),
            ref.bloom_contains_ref(BITS, f, probe))


def _ep_bloom_add():
    k = _keys(300, seed=73)
    return (ops.bloom_add(BITS, V.init(BITS), k, regime="hbm"),
            ref.bloom_add_ref(BITS, V.init(BITS), k))


def _ep_bloom_add_jit():
    k = _keys(300, seed=74)
    return (ops.bloom_add_jit(BITS, V.init(BITS), k, donate=False),
            ref.bloom_add_ref(BITS, V.init(BITS), k))


def _ep_bloom_contains_jit():
    f = ref.bloom_add_ref(BITS, V.init(BITS), _keys(300, seed=75))
    probe = jnp.concatenate([_keys(100, seed=75), _absent(200, seed=75)])
    return (ops.bloom_contains_jit(BITS, f, probe, regime="hbm"),
            ref.bloom_contains_ref(BITS, f, probe))


def _ep_bloom_add_partitioned():
    k = _keys(300, seed=76)
    return (ops.bloom_add_partitioned(BITS, V.init(BITS), k),
            ref.bloom_add_ref(BITS, V.init(BITS), k))


def _ep_segment_add():
    k = _keys(300, seed=77)
    valid = jnp.ones((300,), jnp.uint8)
    return (ops.segment_add(SEG, V.init(SEG), k, valid),
            V.add_scatter(SEG, V.init(SEG), k))


def _ep_segment_contains():
    k = _keys(300, seed=78)
    f = V.add_scatter(SEG, V.init(SEG), k[:150])
    return (ops.segment_contains(SEG, f, k, jnp.ones((300,), jnp.uint8)),
            V.contains(SEG, f, k))


def _ep_bloom_bank_add():
    bank, keys, member = _bank_inputs(BANK_BITS)
    return (ops.bloom_bank_add(BANK_BITS, bank, keys, member),
            V.bank_add_rows(BANK_BITS, bank, keys, member))


def _ep_bloom_bank_contains():
    bank, keys, member = _bank_inputs(BANK_BITS)
    bank = V.bank_add_rows(BANK_BITS, bank, keys[:150], member[:150])
    return (ops.bloom_bank_contains(BANK_BITS, bank, keys, member),
            V.bank_contains_rows(BANK_BITS, bank, keys, member))


def _ep_counting_add():
    k = _keys(300, seed=79)
    return (ops.counting_add(CNT, V.init(CNT), k, regime="hbm"),
            V.counting_add(CNT, V.init(CNT), k))


def _ep_counting_remove():
    k = _keys(300, seed=80)
    f = V.counting_add(CNT, V.init(CNT), k)
    return (ops.counting_remove(CNT, f, k[:100]),
            V.counting_remove(CNT, f, k[:100]))


def _ep_counting_contains():
    k = _keys(300, seed=81)
    f = V.counting_add(CNT, V.init(CNT), k[:150])
    return (ops.counting_contains(CNT, f, k, regime="hbm"),
            V.counting_contains(CNT, f, k))


def _ep_counting_update_jit():
    k = _keys(300, seed=82)
    return (ops.counting_update_jit(CNT, V.init(CNT), k, "add",
                                    donate=False),
            V.counting_add(CNT, V.init(CNT), k))


def _ep_counting_update_partitioned():
    k = _keys(300, seed=83)
    return (ops.counting_update_partitioned(CNT, V.init(CNT), k),
            V.counting_add(CNT, V.init(CNT), k))


def _ep_counting_bank_update():
    bank, keys, member = _bank_inputs(BANK_CNT)
    return (ops.counting_bank_update(BANK_CNT, bank, keys, member),
            V.bank_counting_update(BANK_CNT, bank, keys, member, None,
                                   "add"))


def _ep_counting_bank_contains():
    bank, keys, member = _bank_inputs(BANK_CNT)
    bank = V.bank_counting_update(BANK_CNT, bank, keys[:150], member[:150],
                                  None, "add")
    return (ops.counting_bank_contains(BANK_CNT, bank, keys, member),
            V.bank_counting_contains(BANK_CNT, bank, keys, member))


ENTRY_POINTS = {name[len("_ep_"):]: fn for name, fn in sorted(globals().items())
                if name.startswith("_ep_")}


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_dispatch_reads_no_plan_file(entry, tmp_path, monkeypatch):
    """With ``REPRO_TUNING_CACHE`` naming a file of poisoned plans, each
    entry point gives the reference's result, and nothing opens the
    file: the schedule follows from the filter's bytes alone."""
    path = str(tmp_path / "plans.json")
    _poisoned_plans(path)
    monkeypatch.setenv("REPRO_TUNING_CACHE", path)
    opened = []
    real_open, real_io_open, real_os_open = builtins.open, io.open, os.open

    def _watch(real):
        def wrapped(file, *a, **k):
            if isinstance(file, (str, bytes, os.PathLike)) and \
                    os.path.abspath(os.fsdecode(file)) == path:
                opened.append(file)
            return real(file, *a, **k)
        return wrapped

    monkeypatch.setattr(builtins, "open", _watch(real_open))
    monkeypatch.setattr(io, "open", _watch(real_io_open))
    monkeypatch.setattr(os, "open", _watch(real_os_open))
    ops.jit_cache_clear()
    got, want = ENTRY_POINTS[entry]()
    _eq(got, want)
    assert opened == []
    assert os.path.getsize(path) > 0           # left as it was written
