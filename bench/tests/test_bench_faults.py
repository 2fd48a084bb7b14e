"""``correct`` comes out false for the control and for each fault a cell
can have, planted in the program underneath a tiny run."""
from __future__ import annotations

import pytest

from bench.readings import control_program
from bench.tests.tiny import run_tiny, tiny_cell

ONE_CHIP = ["q3_prefilter.probe", "q3_prefilter.build", "ycsb_c.service"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_is_not_correct(name):
    program = control_program(tiny_cell(name).config)
    assert program["k"] < tiny_cell(name).config["k"]
    result, _, _ = run_tiny(name, seed=21, seconds=0.3, program=program)
    assert not result["correct"]


def _patch_add(monkeypatch, fn):
    from repro.api.filter import Filter
    original = Filter.add

    def add(self, keys, tenants=None, valid=None):
        return fn(original, self, keys, tenants, valid)

    monkeypatch.setattr(Filter, "add", add)


def _unchanged(original, self, keys, tenants, valid):
    return self


def _half_left_out(original, self, keys, tenants, valid):
    half = keys.shape[0] // 2
    return original(self, keys[:half],
                    None if tenants is None else tenants[:half],
                    None if valid is None else valid[:half])


@pytest.mark.parametrize("name", ONE_CHIP)
@pytest.mark.parametrize("fault", [_unchanged, _half_left_out],
                         ids=["state_unchanged", "half_batch_left_out"])
def test_add_faults_are_not_correct(monkeypatch, name, fault):
    _patch_add(monkeypatch, fault)
    result, _, _ = run_tiny(name, seed=22, seconds=0.3)
    assert not result["correct"]


@pytest.mark.parametrize("name", ["q3_prefilter.probe", "ycsb_c.service"])
def test_altered_answer_is_not_correct(monkeypatch, name):
    from repro.api.filter import Filter
    original = Filter.contains

    def contains(self, keys, tenants=None):
        out = original(self, keys, tenants=tenants)
        return out.at[0].set(~out[0])

    monkeypatch.setattr(Filter, "contains", contains)
    result, _, err = run_tiny(name, seed=23, seconds=0.3)
    assert not result["correct"]
    assert result["checks"]["answers_differing"]["value"] > 0, err
