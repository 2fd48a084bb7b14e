"""The four-chip cell ``corpus_dedup.build4`` at tiny sizes on four CPU
devices (a subprocess each, ``bench/tests/_sharded_cell.py``), the faults
its check must catch, and its per-layer readers."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench.lib import catalog
from bench.lib import trace as T
from bench.lib.drive import Window
from bench.lib.harness import LayerContext

CELL = "corpus_dedup.build4"
READERS = ["device_idle_share.build4", "xla_ns_per_key.build4",
           "collective_ns_per_key.build4", "pallas_ns_per_key.build4",
           "build4_roofline", "sharded.exchange_occupancy"]


def _run(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, os.path.join(catalog.BENCH_DIR, "tests",
                                      "_sharded_cell.py"), case],
        cwd=catalog.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_tiny_cell_is_correct():
    run = _run("sound")
    result = run["result"]
    assert result["correct"], run["err"]
    assert result["device"]["count"] == 4
    assert set(result["metrics"]) == {"build_keys_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["checks"]) == {"keys_missing", "words_differing",
                                     "built_words_differing"}
    assert all(c["limit"] == 0 for c in result["checks"].values())
    info = run["out"]
    assert 'info dispatched: {"sharded_add:pallas-hbm": 1}' in info
    assert "info compile_events_in_window: {}" in info


@pytest.mark.parametrize("case", ["control", "flipped_word",
                                  "exchange_left_out"])
def test_faults_are_not_correct(case):
    """The k/2 control, one word of one shard flipped after each add, and
    the keys one device sends left out of the exchange."""
    result = _run(case)["result"]
    assert not result["correct"]
    checks = result["checks"]
    assert (checks["words_differing"]["value"]
            + checks["built_words_differing"]["value"]) > 0


def test_window_makes_its_fewest_calls_whatever_its_seconds():
    """A window of no seconds still makes ``MIN_CALLS`` calls, across
    several builds (4 batches a build at the tiny sizes), and checks."""
    from bench.kinds.sharded_build import MIN_CALLS
    run = _run("no_seconds")
    assert run["result"]["correct"], run["err"]
    assert f"info calls: {MIN_CALLS}\n" in run["out"]
    assert f"info builds_completed: {MIN_CALLS // 4}\n" in run["out"]
    assert run["result"]["attempted"] == MIN_CALLS * 1024


def test_traced_tiny_cell_reads_the_exchange_occupancy():
    result = _run("traced")["result"]
    assert result["correct"]
    # the CPU trace has no device plane: device metrics stay silent
    assert set(result["metrics"]) == {"sharded.exchange_occupancy"}
    # 1024 keys a call over 4 devices: 4 x 4 buckets of 256 slots
    assert result["metrics"]["sharded.exchange_occupancy"]["value"] == 25.0


def _ctx(trace=None, counters=None, kind="build"):
    host = {} if counters is None else {"counters": counters}
    win = Window(t0=0.0, t1=1.0, kind=kind, attempted=1000, failed=0,
                 end_to_end={}, keys=1000, bytes_needed=40000, host=host)
    return LayerContext(cell=None, window=win, trace=trace,
                        peaks={"hbm_bytes_per_s": 1e9})


def _summary(kind_s):
    return T.Summary(window_s=1.0, busy_s=0.5, kind_s=kind_s, ops=[],
                     idle_gaps=[], n_devices=4)


@pytest.mark.parametrize("metric", READERS)
def test_readers_are_silent_without_their_spans_or_counters(metric):
    read = catalog.reader(metric)
    assert read(_ctx()) is None
    assert read(_ctx(counters={"flushes": 3})) is None
    # a parent's program: no engine counters; a trace with no collective
    # and no Pallas kernel
    no_kind = read(_ctx(trace=_summary({"xla": 0.25})))
    if metric in ("collective_ns_per_key.build4", "pallas_ns_per_key.build4",
                  "sharded.exchange_occupancy"):
        assert no_kind is None
    assert read(_ctx(trace=_summary({"pallas": 0.25}), kind="lookup")) \
        is None


def test_readers_read_the_trace_and_the_counters():
    ctx = _ctx(trace=_summary({"xla": 0.1, "collective": 0.01,
                               "pallas": 0.3}),
               counters={"sharded.keys{op=add}": 1000,
                         "sharded.exchange_slots{op=add}": 4000})
    got = {m: catalog.reader(m)(ctx) for m in READERS}
    assert got == pytest.approx({
        "device_idle_share.build4": 50.0,
        "xla_ns_per_key.build4": 1e9 * 0.1 * 4 / 1000,
        "collective_ns_per_key.build4": 1e9 * 0.01 * 4 / 1000,
        "pallas_ns_per_key.build4": 1e9 * 0.3 * 4 / 1000,
        "build4_roofline": 100.0 * 40000 / (1e9 * 4) / 0.5,
        "sharded.exchange_occupancy": 25.0})
