"""PR-9 performance-model contracts: first-principles OpCost invariants,
calibration measurement/caching protocol, speed-of-light ceilings, the
shared report helpers, and the warn-only model-sanity gate.

These tests pin MODEL STRUCTURE (which configuration should cost less and
why), never absolute times — the machine constants are injected so nothing
here depends on host speed or on a calibration file left on disk.
"""
import json
import os

import pytest

from repro import perfmodel as PM
from repro.core import variants as V
from repro.perfmodel.calibrate import Calibration, default_calibration

SBF = V.FilterSpec("sbf", 1 << 18, 8, block_bits=256)
CNT = V.FilterSpec("countingbf", 1 << 16, 4, block_bits=256)

# Deterministic machine constants for every prediction in this file.
CAL = Calibration(backend="test", bw_hbm_gbs=100.0, bw_res_gbs=400.0,
                  gops=100.0, launch_us=5.0, step_us=1.0, measured=True)


# ---------------------------------------------------------------------------
# OpCost invariants
# ---------------------------------------------------------------------------

def test_ceiling_never_exceeds_prediction():
    for spec, regime in ((SBF, "vmem"), (SBF, "hbm"), (CNT, "vmem")):
        for coop in ("none", "subtile"):
            c = PM.op_cost(spec, "contains", regime, coop=coop,
                           n_keys=1 << 12)
            assert PM.ceiling_us(c, CAL) <= PM.predict_us(c, CAL)
            assert c.launches == 1.0          # single-launch design
            assert c.bytes_hbm > 0 and c.flops > 0


def test_cheap_mix_strictly_fewer_flops():
    """The fused double-hash shares lane products: fewer flops, same
    bytes — the model must rank it ahead on ties."""
    full = PM.op_cost(SBF, "contains", "vmem", mix="full", n_keys=1024)
    cheap = PM.op_cost(SBF, "contains", "vmem", mix="cheap", n_keys=1024)
    assert cheap.flops < full.flops
    assert cheap.bytes_hbm == full.bytes_hbm
    assert cheap.bytes_res == full.bytes_res


def test_counting_pays_the_4x_counter_stream():
    """Counting contains reads counter words (4x expansion) — its resident
    traffic must exceed the plain Bloom's at the same geometry."""
    sbf = V.FilterSpec("sbf", 1 << 16, 4, block_bits=256)
    b = PM.op_cost(sbf, "contains", "vmem", n_keys=1024)
    c = PM.op_cost(CNT, "contains", "vmem", n_keys=1024)
    assert c.bytes_res > b.bytes_res


def test_coop_reduces_resident_traffic_vmem():
    """Early-exit touches an expected fraction of the probe columns."""
    base = PM.op_cost(SBF, "contains", "vmem", coop="none", n_keys=1024)
    coop = PM.op_cost(SBF, "contains", "vmem", coop="subtile", n_keys=1024)
    assert coop.bytes_res < base.bytes_res


def test_coop_dedups_hbm_dmas():
    """Cooperative HBM contains issues one DMA per UNIQUE block row."""
    base = PM.op_cost(SBF, "contains", "hbm", coop="none", n_keys=1 << 12)
    coop = PM.op_cost(SBF, "contains", "hbm", coop="subtile", n_keys=1 << 12)
    assert coop.bytes_hbm < base.bytes_hbm


def test_add_rmw_doubles_touched_words():
    rd = PM.op_cost(SBF, "contains", "vmem", n_keys=1024)
    wr = PM.op_cost(SBF, "add", "vmem", n_keys=1024)
    assert wr.bytes_res > rd.bytes_res


def test_opcost_scaled():
    c = PM.op_cost(SBF, "contains", "vmem", n_keys=1024)
    d = c.scaled(2.0)
    assert d.bytes_res == 2 * c.bytes_res and d.flops == 2 * c.flops


def test_ceiling_mops_amortizes_launch():
    """More keys per launch -> higher ceiling throughput (launch overhead
    amortized), which is exactly why the kernels are single-launch."""
    lo = PM.ceiling_mops(SBF, "contains", "vmem", n_keys=1 << 8, calib=CAL)
    hi = PM.ceiling_mops(SBF, "contains", "vmem", n_keys=1 << 14, calib=CAL)
    assert hi > lo > 0


def test_fingerprint_and_quotient_costed():
    ck = V.FilterSpec("cuckoo", 1 << 14, 1, slot_bits=16, slots_per_bucket=4)
    qt = V.FilterSpec("quotient", 1 << 13, 1, slot_bits=16, r_bits=9)
    for spec in (ck, qt):
        base = PM.op_cost(spec, "contains", "vmem", coop="none", n_keys=512)
        coop = PM.op_cost(spec, "contains", "vmem", coop="subtile",
                          n_keys=512)
        assert coop.bytes_res < base.bytes_res
        assert PM.predict_us(coop, CAL) > 0


def test_choose_coop_returns_valid_axes():
    coop, mix = PM.choose_coop(SBF, "contains", "vmem", 256)
    assert coop in ("none", "subtile") and mix in ("full", "cheap")


# ---------------------------------------------------------------------------
# Calibration protocol
# ---------------------------------------------------------------------------

def test_calibration_roundtrip():
    d = CAL.to_dict()
    assert Calibration.from_dict(d) == CAL
    assert d["schema"] == 1


def test_default_calibration_is_unmeasured():
    c = default_calibration("cpu")
    assert not c.measured and c.backend == "cpu"
    v5e = default_calibration("TPU v5 lite")
    assert v5e.launch_us < c.launch_us and v5e.bw_hbm_gbs == 819.0


def test_unknown_device_kind_has_no_default():
    """An unlisted device must never borrow another's constants."""
    with pytest.raises(ValueError, match="device kind"):
        default_calibration("tpu")


def test_get_calibration_defaults_without_measure(tmp_path, monkeypatch):
    """Library code (the autotuner) must be able to call get_calibration
    at trace time without triggering any timing: no cache file + no
    measure request -> per-backend defaults, and nothing written."""
    cache = tmp_path / "calib.json"
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(cache))
    monkeypatch.delenv("REPRO_CALIB_MEASURE", raising=False)
    c = PM.get_calibration()
    assert not c.measured
    assert not cache.exists()


def test_get_calibration_disk_cache(tmp_path, monkeypatch):
    """A stored measurement short-circuits later lookups for the same
    backend (the fig4 harness measures once per machine)."""
    import jax

    from repro.perfmodel import calibrate as C
    cache = tmp_path / "calib.json"
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(cache))
    backend = jax.default_backend()
    stored = Calibration(backend=backend, bw_hbm_gbs=1.0,
                         bw_res_gbs=2.0, gops=3.0, launch_us=4.0,
                         step_us=5.0, measured=True)
    C._store_disk(f"calib|{C._SCHEMA}|{backend}", stored.to_dict())
    got = PM.get_calibration()
    assert got == stored
    # corrupt file degrades to defaults, never raises
    cache.write_text("{not json")
    assert not PM.get_calibration().measured


def test_measured_calibration_is_positive_and_cached(tmp_path, monkeypatch):
    """The microbench suite returns finite positive constants and persists
    them (any individual probe failure falls back to the default)."""
    cache = tmp_path / "calib.json"
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(cache))
    c = PM.get_calibration(measure=True)
    assert c.measured
    for v in (c.bw_hbm_gbs, c.bw_res_gbs, c.gops, c.launch_us, c.step_us):
        assert v > 0
    assert cache.exists()
    assert PM.get_calibration() == c        # second call hits the disk


# ---------------------------------------------------------------------------
# Shared report helpers (roofline <-> perfmodel)
# ---------------------------------------------------------------------------

def test_report_utils_formatters(tmp_path):
    from repro.roofline.report_utils import (fmt_bytes, fmt_float, fmt_rate,
                                             load_reports)
    assert fmt_bytes(1536) == "1.5KB"
    assert fmt_bytes(None) == "-"
    assert fmt_float(1.23456, 2) == "1.23"
    assert fmt_float("oops") == "-"
    assert fmt_rate(1234567, "ops") == "1.2Mops"
    assert fmt_rate(None) == "-"
    (tmp_path / "b.json").write_text(json.dumps({"x": 2}))
    (tmp_path / "a.json").write_text(json.dumps({"x": 1}))
    assert [r["x"] for r in load_reports(str(tmp_path))] == [1, 2]


def test_roofline_report_reexports():
    """test_dryrun-era callers import the underscore names from report."""
    from repro.roofline import report
    assert report._fmt_bytes(2048) == "2.0KB"
    assert report._s(0.5, 1) == "0.5"


# ---------------------------------------------------------------------------
# Warn-only model-sanity gate + bench record plumbing
# ---------------------------------------------------------------------------

def test_model_sanity_gate_warns_never_fails(capsys):
    from benchmarks.run import model_sanity
    recs = [
        {"name": "fast", "us_per_call": 50.0, "predicted_us": 1.0},  # < floor
        {"name": "ok", "us_per_call": 20000.0, "predicted_us": 9000.0},
        {"name": "off", "us_per_call": 400000.0, "predicted_us": 100.0},
        {"name": "nopred", "us_per_call": 50000.0},
    ]
    warned = model_sanity(recs)              # must not raise / exit
    assert warned == 1
    out = capsys.readouterr().out
    assert "MODEL-SANITY WARNING off" in out
    assert "2 records checked" in out


def test_csv_records_carry_predicted_us():
    from benchmarks.common import Csv
    csv = Csv()
    csv.add("a", 10.0, n_ops=100, predicted_us=12.5)
    csv.add("b", 10.0)
    assert csv.records[0]["predicted_us"] == 12.5
    assert csv.records[0]["mops"] == 10.0
    assert "predicted_us" not in csv.records[1]
