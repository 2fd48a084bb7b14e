"""The reduction from a profiler trace to device numbers, on a small
recorded trace built by hand."""
from __future__ import annotations

import pytest

from bench.lib import trace as T

US = 1000.0  # ns


def _op(dev, name, start_us, dur_us, category=""):
    stats = (("hlo_category", category),) if category else ()
    return T.Ev(f"/device:TPU:{dev}", T.OPS_LINE, name, start_us * US,
                dur_us * US, stats)


def _host(name, start_us, dur_us):
    return T.Ev("/host:CPU", "python", name, start_us * US, dur_us * US)


def _hlo(name, opcode, extra=""):
    return f"%{name} = u32[8]{{0}} {opcode}(u32[8]{{0}} %p){extra}"


PALLAS = _hlo("_jit_contains.1", "custom-call",
              ', custom_call_target="tpu_custom_call"')
FUSION = _hlo("fusion.3", "fusion", ", kind=kLoop")
A2A = _hlo("all-to-all.1", "all-to-all")


def _events():
    """A 100 us window. Device 0: a Pallas kernel 10-40 us, an XLA fusion
    30-50 us (overlapping it), an all-to-all 60-70 us. Device 1: one
    fusion 0-20 us. Outside the window, a fusion that must not count."""
    return [
        _host(T.WINDOW, 0, 100),
        _host("bench.lookup_call", 0, 10),
        _host("bench.wait", 50, 45),
        _op(0, PALLAS, 10, 30),
        _op(0, FUSION, 30, 20),
        _op(0, A2A, 60, 10),
        _op(1, FUSION, 0, 20),
        _op(0, _hlo("fusion.9", "fusion"), 150, 20),
    ]


def test_busy_idle_and_kinds():
    s = T.summarize(_events(), devices=[0, 1])
    assert s.window_s == pytest.approx(100e-6)
    # device 0 busy 10-50 and 60-70 (50 us), device 1 busy 20 us
    assert s.busy_s == pytest.approx((50e-6 + 20e-6) / 2)
    assert s.idle_share == pytest.approx(1 - 35e-6 / 100e-6)
    assert s.kind_s["pallas"] == pytest.approx(30e-6 / 2)
    assert s.kind_s["xla"] == pytest.approx((20e-6 + 20e-6) / 2)
    assert s.kind_s["collective"] == pytest.approx(10e-6 / 2)
    assert s.n_devices == 2


def test_op_totals_and_idle_gaps():
    s = T.summarize(_events(), devices=[0, 1])
    ops = dict(s.ops)
    assert ops["fusion.3 [xla]"] == pytest.approx((20e-6 + 20e-6) / 2)
    assert ops["_jit_contains.1 [pallas]"] == pytest.approx(30e-6 / 2)
    assert "fusion.9 [xla]" not in ops
    gaps = dict(s.idle_gaps)
    # device 0 idles 0-10 (dispatching), 50-60 and 70-100 (waiting): a
    # gap goes whole to the annotation covering its middle
    assert gaps == pytest.approx({"bench.lookup_call": 10e-6,
                                  "bench.wait": 40e-6})


def test_gap_outside_every_annotation():
    events = [_host(T.WINDOW, 0, 100), _op(0, FUSION, 0, 40)]
    assert dict(T.summarize(events).idle_gaps) == pytest.approx(
        {"host.other": 60e-6})


def test_kind_by_opcode():
    assert T.kind(_op(0, A2A, 0, 1)) == "collective"
    assert T.kind(_op(0, _hlo("all-reduce-start.2", "all-reduce-start"), 0,
                      1)) == "collective"
    assert T.kind(_op(0, PALLAS, 0, 1)) == "pallas"
    # XLA's own custom calls are not kernels
    assert T.kind(_op(0, _hlo("custom-call", "custom-call",
                              ', custom_call_target="ConcatBitcast"'),
                      0, 1)) == "xla"
    # a fusion that reads a collective's result is no collective
    assert T.kind(_op(0, "%fusion.2 = u32[8]{0} fusion(u32[8]{0} "
                      "%all-to-all.1), kind=kLoop", 0, 1)) == "xla"
    assert T.kind(_op(0, "my_kernel", 0, 1, "custom-call")) == "pallas"
    assert T.hlo_parts("%copy-start = (u32[8]{0:T(128)S(1)}, u32[]{:S(2)}) "
                       "copy-start(u32[8]{0:T(128)} %constant)") == (
        "copy-start", "copy-start")


def test_no_device_ops_reads_nothing():
    assert T.summarize([_host(T.WINDOW, 0, 100)]) is None
    with pytest.raises(ValueError):
        T.summarize([_op(0, FUSION, 0, 1)])


XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 10 str_value: "custom-call" } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "probe_rows_hbm" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.3" } }
  stat_metadata { key: 10 value { id: 10 name: "hlo_category" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction" } }
}
"""


def test_reads_a_recorded_xspace():
    from jax.profiler import ProfileData
    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    events = T.events_of(profile)
    names = sorted(e.name for e in events)
    # the device's module line and the host's own events are left out
    assert names == ["bench.window", "fusion.3", "probe_rows_hbm"]
    s = T.summarize(events, devices=[0])
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(3e-6)
    assert s.kind_s == pytest.approx({"pallas": 2e-6, "xla": 1e-6})
