"""Compile the main-path Pallas kernels for a TPU v5e at deployment size.

Nothing runs: the TPU compiler installed with jaxlib compiles for a chip
that is described, not attached, and refuses what the chip's compiler
would refuse (unaligned or unsupported vector ops, more VMEM than a kernel
may use). Each compile takes a second or two. The topology is described
inside a fixture, so the tests skip where it cannot be described.
"""
from __future__ import annotations

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.variants import FilterSpec
from repro.kernels import sbf
from repro.kernels.sbf import VMEM_FILTER_BYTES, default_layout

os.environ.setdefault("TPU_LOG_DIR", "disabled")

VMEM_EDGE = FilterSpec("sbf", VMEM_FILTER_BYTES * 8, 8)     # 4 MiB of words
HBM_1GIB = FilterSpec("sbf", 1 << 33, 8)
BANK = FilterSpec("sbf", 1 << 20, 8)
N_KEYS = 1 << 12


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("data",))


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _words(spec, dev, bank=None):
    shape = (spec.n_words,) if bank is None else (bank, spec.n_words)
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=dev)


def _keys(dev, n=N_KEYS):
    return jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=dev)


@pytest.mark.parametrize("op", ["contains", "add"])
def test_vmem_kernels_compile_at_budget_edge(one_chip, op):
    entry = sbf.contains_vmem if op == "contains" else sbf.add_vmem
    fn = functools.partial(entry, VMEM_EDGE,
                           layout=default_layout(VMEM_EDGE, op),
                           interpret=False)
    c = _compile(fn, _words(VMEM_EDGE, one_chip), _keys(one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("tile", [8, 256])
def test_hbm_contains_compiles_at_1gib(one_chip, tile):
    """The probe's ring of ``HBM_PROBE_DEPTH`` slots, at a full tile and
    at the 8-key tile a small batch is clamped to."""
    fn = functools.partial(sbf.contains_hbm, HBM_1GIB, interpret=False,
                           tile=tile)
    c = _compile(fn, _words(HBM_1GIB, one_chip), _keys(one_chip))
    assert "tpu_custom_call" in c.as_text()
    # the row view of the filter is a bitcast, not a 1 GiB copy
    assert c.memory_analysis().temp_size_in_bytes < HBM_1GIB.n_words * 4


def test_hbm_add_compiles_at_1gib(one_chip):
    fn = functools.partial(sbf.add_hbm, HBM_1GIB, interpret=False)
    c = _compile(fn, _words(HBM_1GIB, one_chip), _keys(one_chip))
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < HBM_1GIB.n_words * 4


@pytest.mark.parametrize("op", ["contains", "add"])
def test_sharded_engine_compiles_at_16gib_on_four_chips(four_chips,
                                                        monkeypatch, op):
    """The sharded engine's add and contains over four v5e chips, 4 GiB a
    segment, 2^18 keys a device: the segment's row kernel is in the
    program, beside the all_to_all. The update aliases its segment: one
    DMA of a whole 4 GiB segment is past what the chip's DMA carries."""
    from repro.core import distributed as D
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)   # the chip's path
    spec = FilterSpec("sbf", 1 << 37, 8)
    n_local = 1 << 18
    on_mesh = NamedSharding(four_chips, P("data"))
    words = jax.ShapeDtypeStruct((spec.n_words,), jnp.uint32,
                                 sharding=on_mesh)
    keys = jax.ShapeDtypeStruct((4, n_local, 2), jnp.uint32,
                                sharding=on_mesh)
    entry = D.sharded_add if op == "add" else D.sharded_contains
    c = _compile(functools.partial(entry, spec, four_chips, "data", n_local),
                 words, keys)
    text = c.as_text()
    assert "tpu_custom_call" in text and "all-to-all" in text
    # the segment's row view is a bitcast: no temporary a segment in size
    assert c.memory_analysis().temp_size_in_bytes < spec.n_words * 4 // 8


@pytest.mark.parametrize("op", ["contains", "add"])
def test_bank_kernels_compile(one_chip, op):
    member = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip)
    keys = _keys(one_chip, 4096)
    bank = _words(BANK, one_chip, bank=16)
    layout = default_layout(BANK, op)
    if op == "contains":
        fn = functools.partial(sbf.bank_contains_vmem, BANK, layout=layout,
                               interpret=False)
        c = _compile(fn, bank, keys, member)
    else:
        valid = jax.ShapeDtypeStruct((4096,), jnp.uint8, sharding=one_chip)
        fn = functools.partial(sbf.bank_add_vmem, BANK, layout=layout,
                               interpret=False)
        c = _compile(fn, bank, keys, member, valid)
    assert "tpu_custom_call" in c.as_text()


def test_partitioned_add_compiles(one_chip):
    spec = FilterSpec("sbf", 1 << 28, 8)                 # 32 MiB, 8 x 4 MiB
    by_seg = jax.ShapeDtypeStruct((8, 8192, 2), jnp.uint32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((8, 8192), jnp.uint8, sharding=one_chip)
    fn = functools.partial(sbf.add_partitioned, spec, n_segments=8,
                           interpret=False)
    c = _compile(fn, _words(spec, one_chip), by_seg, valid)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("regime,op", [("vmem", "contains"), ("vmem", "add"),
                                       ("hbm", "contains"), ("hbm", "add"),
                                       ("bank", "contains"), ("bank", "add")])
def test_counting_kernels_compile(one_chip, regime, op):
    from repro.kernels import countingbf as C
    counters = {"vmem": VMEM_FILTER_BYTES * 2, "hbm": 1 << 31,
                "bank": 1 << 18}[regime]                  # 4 MiB / 1 GiB
    spec = FilterSpec("countingbf", counters, 8)
    words = jax.ShapeDtypeStruct((spec.storage_words,), jnp.uint32,
                                 sharding=one_chip)
    keys = _keys(one_chip)
    valid = jax.ShapeDtypeStruct((N_KEYS,), jnp.uint8, sharding=one_chip)
    member = jax.ShapeDtypeStruct((N_KEYS,), jnp.int32, sharding=one_chip)
    if regime == "bank":
        words = jax.ShapeDtypeStruct((16, spec.storage_words), jnp.uint32,
                                     sharding=one_chip)
        if op == "contains":
            fn = functools.partial(C.bank_contains_vmem, spec,
                                   interpret=False)
            c = _compile(fn, words, keys, member)
        else:
            fn = functools.partial(C.bank_update_vmem, spec, op="add",
                                   interpret=False)
            c = _compile(fn, words, keys, member, valid)
    elif op == "contains":
        entry = C.contains_vmem if regime == "vmem" else C.contains_hbm
        c = _compile(functools.partial(entry, spec, interpret=False),
                     words, keys)
    else:
        entry = C.update_vmem if regime == "vmem" else C.update_hbm
        c = _compile(functools.partial(entry, spec, op="add",
                                       interpret=False), words, keys, valid)
    assert "tpu_custom_call" in c.as_text()


def test_counting_decay_compiles(one_chip):
    from repro.kernels import countingbf as C
    spec = FilterSpec("countingbf", 1 << 31, 8)           # 1 GiB counters
    words = jax.ShapeDtypeStruct((spec.storage_words,), jnp.uint32,
                                 sharding=one_chip)
    c = _compile(functools.partial(C.decay, spec, interpret=False), words)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("op", ["add", "remove"])
def test_counting_reference_fits_the_chip(one_chip, op):
    """The jnp counting reference (the ``jnp`` engine's, and the bank and
    residual fallbacks of ``kernels.ops``) at 128 MiB of counters: every
    temporary stays lane-dense, so it needs a few times the counters, not
    the 48 GiB that a (words, 32) count table padded to 128 lanes took."""
    from repro.core import variants as V
    spec = FilterSpec("countingbf", 1 << 28, 8)
    words = jax.ShapeDtypeStruct((spec.storage_words,), jnp.uint32,
                                 sharding=one_chip)
    fn = V.counting_add if op == "add" else V.counting_remove
    c = _compile(functools.partial(fn, spec), words, _keys(one_chip))
    assert c.memory_analysis().temp_size_in_bytes <= 16 * spec.storage_words * 4

