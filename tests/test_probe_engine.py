"""Probe-engine contracts: the Θ layouts of the VMEM walk, the HBM probe's
fixed DMA ring, the deferred tile test shared by the probe kernels, the
device-resident partitioned add and the cached-jit donation layer.

The jit/scan tests prove the partitioned bulk add never leaves the device
(no host numpy partition, no callbacks in the jaxpr).
"""
import functools
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import hashing as H
from repro.core import partition as P
from repro.core import variants as V
from repro.kernels import ops, ref
from repro.kernels import sbf as sbf_k
from repro.kernels.sbf import Layout

M = 1 << 16


def _keys(n, seed=0):
    return jnp.asarray(H.random_u64x2(n, seed=seed))


# ---------------------------------------------------------------------------
# VMEM regime: the Θ layouts of the per-key walk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("theta,block_bits", [(1, 256), (1, 512), (2, 256),
                                              (8, 128)])
def test_layout_invariance(theta, block_bits):
    """Θ keys a loop trip changes the schedule, never the words or the
    answers: every layout matches the reference on a padded batch."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=block_bits)
    keys = _keys(513, seed=9)           # non-tile-multiple: padding on
    lay = Layout(theta)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_ker = ops.bloom_add(spec, V.init(spec), keys, layout=lay,
                          regime="vmem")
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))
    c = ops.bloom_contains(spec, f_ref, keys, layout=lay, regime="vmem")
    np.testing.assert_array_equal(
        np.asarray(c), np.asarray(ref.bloom_contains_ref(spec, f_ref, keys)))


# ---------------------------------------------------------------------------
# HBM regime: the probe's fixed ring, the coalesced add
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,tile", [(6, 8), (16, 16), (27, 32), (600, 256)],
                         ids=["tile8", "tile16", "tile32", "tile256"])
@pytest.mark.parametrize("counting", [False, True], ids=["bits", "counts"])
def test_hbm_probe_ring_over_tiles(counting, n, tile, monkeypatch):
    """The ring of ``HBM_PROBE_DEPTH`` row slots over every key tile
    ``_clamp_tile`` produces: at a tile of 8 the ring is as deep as the
    tile (its prologue starts 7 of the tile's 8 rows), above it the ring
    wraps within the tile. Half the batch is present, half absent."""
    assert sbf_k.HBM_PROBE_DEPTH == 8
    spec = V.FilterSpec("countingbf" if counting else "sbf", M, 8,
                        block_bits=256)
    tiles = []
    real = sbf_k.probe_rows_hbm

    def probe_rows_hbm(*a, **k):
        tiles.append(k["tile"])
        return real(*a, **k)

    monkeypatch.setattr(sbf_k, "probe_rows_hbm", probe_rows_hbm)
    present = _keys(n // 2, seed=31)
    probe = jnp.concatenate([present,
                             jnp.asarray(H.probe_u64x2(n - n // 2, seed=32))])
    if counting:
        f = V.counting_add(spec, V.init(spec), present)
        got = ops.counting_contains(spec, f, probe, regime="hbm")
        want = np.asarray(V.counting_contains(spec, f, probe))
    else:
        f = ref.bloom_add_ref(spec, V.init(spec), present)
        got = ops.bloom_contains(spec, f, probe, regime="hbm")
        want = np.asarray(ref.bloom_contains_ref(spec, f, probe))
    assert tiles == [tile]
    np.testing.assert_array_equal(np.asarray(got), want)
    assert want[: n // 2].all() and not want.all()


def test_hbm_coalesced_add_duplicate_blocks():
    """The block-sorted HBM add must OR same-block keys into ONE RMW —
    adversarial input: every key hashes into a tiny block range."""
    spec = V.FilterSpec("sbf", 1 << 12, 8, block_bits=256)   # 16 blocks
    keys = _keys(256, seed=3)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_ker = ops.bloom_add(spec, V.init(spec), keys, regime="hbm")
    np.testing.assert_array_equal(np.asarray(f_ker), np.asarray(f_ref))


# ---------------------------------------------------------------------------
# The deferred tile test shared by the probe kernels
# ---------------------------------------------------------------------------

def _probe_rows(words, starts, masks, regime, held):
    if regime == "vmem":
        return sbf_k.probe_rows_vmem(words, starts, masks, theta=1, tile=256,
                                     interpret=True, held=held)
    return sbf_k.probe_rows_hbm(words, starts, masks, tile=256,
                                interpret=True, held=held)


@pytest.mark.parametrize("regime", ["vmem", "hbm"])
@pytest.mark.parametrize("n_words", [128, 4096], ids=["one_row", "32_rows"])
@pytest.mark.parametrize("edge", ["lane0", "last_lanes"])
@pytest.mark.parametrize("counting", [False, True], ids=["bits", "counts"])
def test_probe_rows_blocks_at_row_edges(regime, n_words, edge, counting):
    """Every key's block at lane 0 or at ``lanes - s`` of its row, about
    half of them present: each answer is the AND over exactly its own s
    lanes of the row."""
    s, n, lanes = 8, 512, sbf_k.row_lanes(n_words)
    rng = np.random.default_rng(n_words + len(edge) + counting)
    col = 0 if edge == "lane0" else lanes - s
    starts = rng.integers(0, n_words // lanes, n) * lanes + col
    held = V.nib_nonzero if counting else (lambda w: w)
    bits = np.uint32(0x11111111) if counting else np.uint32(0xFFFFFFFF)
    words = rng.integers(0, 1 << 32, n_words, dtype=np.uint32)
    masks = (rng.integers(0, 1 << 32, (n, s), dtype=np.uint32)
             & rng.integers(0, 1 << 32, (n, s), dtype=np.uint32)
             & rng.integers(0, 1 << 32, (n, s), dtype=np.uint32) & bits)
    h = np.asarray(held(jnp.asarray(words)))
    keep = rng.random(n) < 0.5                # these keys' bits are all held
    masks[keep] &= h[starts[keep, None] + np.arange(s)]
    want = ((h[starts[:, None] + np.arange(s)] & masks) == masks).all(-1)
    assert want.any() and not want.all()
    got = _probe_rows(jnp.asarray(words), jnp.asarray(starts, jnp.int32),
                      jnp.asarray(masks), regime, held)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("regime", ["vmem", "hbm"])
@pytest.mark.parametrize("fill", ["empty", "full", "built"])
@pytest.mark.parametrize("variant", ["sbf", "countingbf"])
def test_contains_whole_tiles_match_reference(regime, fill, variant):
    """Tiles all absent (an empty filter), all present (every bit or
    every counter set) and mixed, through ``ops`` with a batch that
    ``_pad_keys`` pads, against ``ref`` / ``V.counting_contains``."""
    spec = V.FilterSpec(variant, M, 8, block_bits=256)
    keys = _keys(600, seed=51)
    counting = spec.is_counting
    if fill == "built":
        f = (V.counting_add(spec, V.init(spec), keys[:300]) if counting
             else ref.bloom_add_ref(spec, V.init(spec), keys[:300]))
    else:
        word = 0 if fill == "empty" else (0x11111111 if counting
                                          else 0xFFFFFFFF)
        f = jnp.full((spec.storage_words,), word, jnp.uint32)
    if counting:
        got = ops.counting_contains(spec, f, keys, regime=regime)
        want = V.counting_contains(spec, f, keys)
    else:
        got = ops.bloom_contains(spec, f, keys, regime=regime)
        want = ref.bloom_contains_ref(spec, f, keys)
    want = np.asarray(want)
    np.testing.assert_array_equal(np.asarray(got), want)
    if fill == "empty":
        assert not want.any()
    else:
        assert want.all() if fill == "full" else want[:300].all()


def _mosaic_loop_bodies(fn, *args, monkeypatch, capsys):
    """The bodies of the ``scf.for`` loops of the Mosaic module(s) that
    lowering ``fn`` for the TPU prints, with ``pallas_call(debug=True)``."""
    from jax.experimental import pallas as pl
    orig = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        lambda *a, **k: orig(*a, **dict(k, debug=True)))
    jax.clear_caches()
    capsys.readouterr()
    jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = capsys.readouterr().out
    assert "The Mosaic module" in text
    mosaic = text[text.index("The Mosaic module"):]
    bodies, at = [], mosaic.find("scf.for")
    while at >= 0:
        depth, j = 0, mosaic.index("{", at)
        for j in range(j, len(mosaic)):
            depth += {"{": 1, "}": -1}.get(mosaic[j], 0)
            if depth == 0:
                break
        bodies.append(mosaic[at:j])
        at = mosaic.find("scf.for", j)
    return mosaic, bodies


@pytest.mark.parametrize("kernel", ["hbm", "vmem"])
def test_probe_key_loop_has_no_per_key_reduction(kernel, monkeypatch,
                                                 capsys):
    """The probe kernels' key loops hold no reduction and no vector-to-
    scalar extract: every key stores its missing bits, and the tile is
    reduced once, after the loop. The HBM pipeline unrolls 8 keys a trip."""
    keys = jax.ShapeDtypeStruct((4096, 2), jnp.uint32)
    if kernel == "vmem":
        spec = V.FilterSpec("sbf", 1 << 20, 8)
        fn = functools.partial(sbf_k.probe_rows_vmem, theta=1, tile=256,
                               interpret=False)
        args = (jax.ShapeDtypeStruct((16 * spec.n_words,), jnp.uint32),
                jax.ShapeDtypeStruct((4096,), jnp.int32),
                jax.ShapeDtypeStruct((4096, spec.s), jnp.uint32))
    else:
        spec = V.FilterSpec("sbf", 1 << 28, 8)
        fn = functools.partial(sbf_k.contains_hbm, spec, interpret=False)
        args = (jax.ShapeDtypeStruct((spec.n_words,), jnp.uint32), keys)
    mosaic, bodies = _mosaic_loop_bodies(fn, *args, monkeypatch=monkeypatch,
                                         capsys=capsys)
    assert bodies
    for body in bodies:
        assert not re.search(r"vector\.extract\s", body), body
        assert "vector.multi_reduction" not in body, body
    assert "vector.multi_reduction" in mosaic      # the one tile test
    if kernel == "hbm":
        assert bodies[0].count("tpu.wait_dma2") == sbf_k.HBM_PROBE_UNROLL


# ---------------------------------------------------------------------------
# Device-resident partitioned add: jit / scan, overflow, no host sync
# ---------------------------------------------------------------------------

def test_partition_jit_reports_overflow():
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(512, seed=41)
    part = P.partition_jit(spec, keys, 8, capacity=8)   # far too small
    n_kept = int(np.asarray(part.keep).sum())
    assert int(part.overflow) == 512 - n_kept > 0
    assert int(np.asarray(part.valid).sum()) == n_kept


def test_partitioned_add_escalates_capacity_concrete():
    """Concrete keys + undersized capacity: dispatch doubles capacity until
    nothing overflows — bit-exact, no silent key loss."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(1000, seed=43)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_par = ops.bloom_add_partitioned(spec, V.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(f_par), np.asarray(f_ref))


def test_partitioned_add_traced_residual_exact():
    """Under jit the capacity is static; overflowed keys must flow through
    the vectorized residual pass — still bit-exact."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(800, seed=47)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)
    f_par = jax.jit(
        lambda f, k: ops.bloom_add_partitioned(spec, f, k, capacity=8)
    )(V.init(spec), keys)
    np.testing.assert_array_equal(np.asarray(f_par), np.asarray(f_ref))


def test_partitioned_add_jit_scan_no_host_partition(monkeypatch):
    """The acceptance criterion: Filter.add-style partitioned bulk add runs
    under jit + lax.scan with ZERO host transfers. partition_host is
    booby-trapped; any host sync raises."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)

    def boom(*a, **k):                                  # pragma: no cover
        raise AssertionError("host partition called on the jit path")

    monkeypatch.setattr(P, "partition_host", boom)

    keys = _keys(1024, seed=53)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys)

    @jax.jit
    def bulk(f, chunks):
        def step(f, k):
            return ops.bloom_add_partitioned(spec, f, k, capacity=256), None
        f, _ = jax.lax.scan(step, f, chunks)
        return f

    f_out = bulk(V.init(spec), keys.reshape(4, 256, 2))
    np.testing.assert_array_equal(np.asarray(f_out), np.asarray(f_ref))


def test_partitioned_add_jaxpr_has_no_callbacks():
    """No pure_callback / io_callback / debug_callback primitives anywhere
    in the traced computation — it is device-resident by construction."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    keys = _keys(256, seed=59)
    jaxpr = jax.make_jaxpr(
        lambda f, k: ops.bloom_add_partitioned(spec, f, k, capacity=128)
    )(V.init(spec), keys)
    assert "callback" not in str(jaxpr)


# ---------------------------------------------------------------------------
# Cached-jit dispatch layer (donation)
# ---------------------------------------------------------------------------

def test_bloom_add_jit_correct_and_cached():
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    ops.jit_cache_clear()
    keys1, keys2 = _keys(512, seed=61), _keys(512, seed=67)
    f_ref = ref.bloom_add_ref(spec, V.init(spec), keys1)
    f_ref = ref.bloom_add_ref(spec, f_ref, keys2)
    f = ops.bloom_add_jit(spec, V.init(spec), keys1, donate=True)
    (n_exec,) = ops.jit_cache_info()
    f = ops.bloom_add_jit(spec, f, keys2, donate=True)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
    # the second same-shape call reused the compiled executable
    assert ops.jit_cache_info() == (n_exec,)
    hits = ops.bloom_contains_jit(spec, f, keys1)
    assert bool(np.asarray(hits).all())


def test_bloom_add_jit_donation_consumes_buffer():
    """donate=True aliases the output onto the input filter — no second
    filter-sized allocation. XLA only honors donation on TPU/GPU; on CPU it
    ignores the hint, so the deletion assert is platform-gated (the
    correctness + cache contract above runs everywhere)."""
    spec = V.FilterSpec("sbf", M, 8, block_bits=256)
    filt = V.init(spec) | jnp.uint32(0)        # fresh, owned buffer
    keep = ops.bloom_add_jit(spec, filt, _keys(256, seed=71), donate=False)
    assert not filt.is_deleted()
    del keep
    if jax.default_backend() in ("tpu", "gpu"):
        ops.bloom_add_jit(spec, filt, _keys(256, seed=71), donate=True)
        assert filt.is_deleted()


def test_counting_update_jit_donation_path():
    spec = V.FilterSpec("countingbf", M, 8, block_bits=256)
    keys = _keys(300, seed=73)
    f_ref = V.counting_add(spec, V.init(spec), keys)
    f = ops.counting_update_jit(spec, V.init(spec), keys, "add", donate=True)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
    f2 = ops.counting_update_jit(spec, f, keys[:100], "remove", donate=True)
    np.testing.assert_array_equal(
        np.asarray(f2), np.asarray(V.counting_remove(spec, f_ref, keys[:100])))
