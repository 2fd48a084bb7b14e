"""Layout autotuner + CLI driver smoke tests."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import variants as V
from repro.core.tuning import structural_score, tune_layout, valid_layouts
from repro.kernels.sbf import Layout


def test_valid_layouts_respect_constraints():
    spec = V.FilterSpec("sbf", 1 << 16, 16, block_bits=512)   # s=16
    lays = valid_layouts(spec)
    assert [lay.theta for lay in lays] == [1, 2, 4, 8, 16]
    for lay in lays:
        assert lay.theta <= max(spec.s, 8)
    small = V.FilterSpec("sbf", 1 << 16, 8, block_bits=256)    # s=8
    assert max(lay.theta for lay in valid_layouts(small)) == 8
    assert [lay.theta for lay in valid_layouts(spec, tile=4)] == [1, 2, 4]


def test_structural_tuner_matches_paper_rules():
    """Θ̂_contains grows with B; add prefers horizontal coverage."""
    small = V.FilterSpec("sbf", 1 << 16, 8, block_bits=128)
    big = V.FilterSpec("sbf", 1 << 16, 16, block_bits=512)
    best_small, _ = tune_layout(small, "contains")
    best_big, _ = tune_layout(big, "contains")
    assert best_small.theta <= best_big.theta
    best_add, _ = tune_layout(big, "add")
    assert best_add.theta >= best_big.theta              # wider coverage


def test_measured_tuner_runs():
    spec = V.FilterSpec("sbf", 1 << 14, 8, block_bits=256)
    best, table = tune_layout(spec, "contains", mode="measure", n_keys=256)
    assert len(table) >= 3
    assert isinstance(best, Layout)


def test_measured_tuner_best_of_k(monkeypatch):
    """measure mode runs each candidate `repeats` times post-warmup and
    scores by the minimum."""
    import repro.core.tuning as T
    calls = {"n": 0}
    real_counter = T.time.perf_counter

    def counting_counter():
        calls["n"] += 1
        return real_counter()

    monkeypatch.setattr(T.time, "perf_counter", counting_counter)
    spec = V.FilterSpec("sbf", 1 << 12, 8, block_bits=256)
    _, table = tune_layout(spec, "add", mode="measure", n_keys=64, repeats=2)
    # 2 perf_counter calls per timed rep, 2 reps per candidate
    assert calls["n"] == 2 * 2 * len(table)
    # distinct repeats values are distinct cache keys (lru_cache)
    _, table3 = tune_layout(spec, "add", mode="measure", n_keys=64, repeats=1)
    assert len(table3) == len(table)


def test_train_driver_cli_smoke():
    from repro.launch.train import main
    rc = main(["--arch", "rwkv6-3b", "--steps", "4", "--batch", "2",
               "--seq", "64"])
    assert rc == 0


def test_serve_driver_cli_smoke():
    from repro.launch.serve import main
    rc = main(["--arch", "mistral-nemo-12b", "--requests", "2", "--batch",
               "2", "--prompt-len", "8", "--new-tokens", "4", "--guard"])
    assert rc == 0


def test_serve_driver_decayed_guard_smoke():
    from repro.launch.serve import main
    rc = main(["--arch", "mistral-nemo-12b", "--requests", "2", "--batch",
               "2", "--prompt-len", "8", "--new-tokens", "4",
               "--guard-decay-every", "4"])
    assert rc == 0
