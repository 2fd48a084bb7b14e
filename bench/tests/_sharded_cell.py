"""Run a tiny ``corpus_dedup.build4`` on four CPU devices, with a fault
planted in the program if asked, and print its result object.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/_sharded_cell.py <case>

``bench/tests/test_bench_sharded.py`` runs it in a subprocess, so that the
test process keeps its single CPU device.
"""
from __future__ import annotations

import copy
import io
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.lib import catalog  # noqa: E402

CELL = "corpus_dedup.build4"
# 2^16 bits a shard in pieces of 512 words, so the check's exchange and
# its pieces are both exercised; 4 batches of 1024 keys a build.
TINY_CONFIG = {"m_bits": 1 << 18, "build_keys": 4096}
TINY_TRAFFIC = {"batch": 1024}
PIECE_WORDS = 512


def tiny_cell() -> catalog.Cell:
    cell = copy.deepcopy(catalog.cell(CELL))
    cell.config.update(TINY_CONFIG)
    cell.traffic.update(TINY_TRAFFIC)
    return cell


def _flip_a_word_of_shard_2():
    from repro.api.filter import Filter
    original = Filter.add

    def add(self, keys, tenants=None, valid=None):
        out = original(self, keys, tenants, valid)
        at = 2 * out.words.shape[0] // 4 + 7
        return out.replace(words=out.words.at[at].set(~out.words[at]))

    Filter.add = add


def _leave_out_one_shards_exchange():
    from repro.core import distributed as D
    original = D._exchange

    def exchange(send, valid, axis):
        keys, carried = original(send, valid, axis)
        mine = jax.lax.axis_index(axis) == 1
        return keys, jnp.where(mine, jnp.zeros_like(carried), carried)

    D._exchange = exchange


def main(case: str) -> None:
    from bench.kinds import sharded_build
    from bench.lib.harness import run_cell
    from bench.readings import control_program
    sharded_build.PIECE_WORDS = PIECE_WORDS
    cell = tiny_cell()
    program = None
    if case == "control":
        program = control_program(cell.config)
    elif case == "flipped_word":
        _flip_a_word_of_shard_2()
    elif case == "exchange_left_out":
        _leave_out_one_shards_exchange()
    else:
        assert case in ("sound", "traced", "no_seconds"), case
    devices = jax.devices()[:cell.chips]
    assert len(devices) == cell.chips, devices
    out, err = io.StringIO(), io.StringIO()
    seconds = 0.0 if case == "no_seconds" else 0.3
    result = run_cell(cell, (1 << 33) + 5, seconds, case == "traced", devices,
                      time.perf_counter(), out=out, err=err, program=program)
    print(json.dumps({"result": result, "out": out.getvalue(),
                      "err": err.getvalue()}))


if __name__ == "__main__":
    main(sys.argv[1])
