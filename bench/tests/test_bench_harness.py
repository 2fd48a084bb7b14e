"""The harness end to end at tiny sizes on the CPU, and its refusal to run
without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.lib import catalog
from bench.tests.tiny import run_tiny

ONE_CHIP = ["q3_prefilter.probe", "q3_prefilter.build", "ycsb_c.service"]


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(catalog.ROOT, "src"), env.get("PYTHONPATH", "")])
    return env


def test_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "q3_prefilter.probe",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=catalog.ROOT, env=_cpu_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(catalog.BENCHMARK_JSON, tmp_path / "BENCHMARK.json")
    shutil.copytree(catalog.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "q3_prefilter.probe",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name", ONE_CHIP)
def test_tiny_cell_is_correct(name):
    result, out, err = run_tiny(name, seed=(1 << 33) + 17, seconds=0.3)
    assert result["correct"], err
    assert list(result)[-1] == "checks"
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    cell = catalog.cell(name)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert err.strip().splitlines()[-1].startswith("check ")


def test_traced_service_reads_its_program_metrics():
    result, _, _ = run_tiny("ycsb_c.service", seed=5, seconds=0.3,
                            trace=True)
    assert result["correct"]
    got = set(result["metrics"])
    # the CPU trace has no device plane: device metrics stay silent
    assert got == {"loadgen.lag_p99_ms", "service.batch_occupancy",
                   "service.host_ms_per_flush"}
    assert 0 < result["metrics"]["service.batch_occupancy"]["value"] <= 100


def test_same_seed_same_work():
    a, _, _ = run_tiny("ycsb_c.service", seed=9, seconds=0.3)
    b, _, _ = run_tiny("ycsb_c.service", seed=9, seconds=0.3)
    assert a["attempted"] == b["attempted"]
