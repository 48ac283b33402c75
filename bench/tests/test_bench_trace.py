"""The reduction from trace events to busy time, kernel time and idle
gaps attributed to host spans."""
import json
import os

import pytest

from bench import trace
from bench.trace import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
DATA = os.path.join(os.path.dirname(__file__), "data")


def _ev(plane, name, start_ms, dur_ms, line=None):
    line = line or (trace.DEVICE_OPS_LINE if plane == DEV else "python")
    return Event(plane, line, name, start_ms * 1e6, dur_ms * 1e6)


def _small():
    return [
        _ev(HOST, "window", 0, 100),
        _ev(HOST, "fe.step", 0, 40), _ev(HOST, "generate", 40, 10),
        _ev(HOST, "fe.step", 50, 50),
        _ev(DEV, "fusion.1", 5, 10), _ev(DEV, "fused_probe.3", 10, 10),
        _ev(DEV, "fusion.1", 60, 20), _ev(DEV, "copy", -5, 10),
    ]


def test_busy_time_is_the_union_of_device_ops_in_the_window():
    r = trace.reduce(_small())
    assert r.window_s == pytest.approx(0.1)
    # [0,5] clipped copy, [5,20] merged, [60,80]
    assert r.busy_s == pytest.approx(0.040)
    assert r.devices == 1
    assert r.op_seconds["fusion.1"] == pytest.approx(0.030)
    assert r.op_seconds["copy"] == pytest.approx(0.005)


def test_kernel_time_by_name():
    r = trace.reduce(_small())
    s, names = r.kernel_seconds(("fused_probe",))
    assert s == pytest.approx(0.010) and names == ["fused_probe.3"]
    assert r.kernel_seconds(("absent",)) == (0, [])


def test_idle_gaps_go_to_the_innermost_host_span():
    r = trace.reduce(_small())
    # gaps: [20,60] midpoint 40 -> generate starts at 40 (innermost),
    # [80,100] midpoint 90 -> fe.step
    assert r.idle_by_span["generate"] == pytest.approx(0.040)
    assert r.idle_by_span["fe.step"] == pytest.approx(0.020)
    b = trace.breakdown(r)
    assert b["idle_gaps"][0] == ["generate", pytest.approx(0.040)]
    assert b["device_ops"][0][0] == "fusion.1"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_window_is_an_error():
    with pytest.raises(RuntimeError):
        trace.reduce([_ev(DEV, "fusion", 0, 1)])


def test_recorded_chip_trace():
    path = os.path.join(DATA, "trace_events.json")
    raw = json.load(open(path))
    ev = [Event(*e) for e in raw["events"]]
    r = trace.reduce(ev)
    want = raw["expected"]
    assert r.window_s == pytest.approx(want["window_s"])
    assert r.busy_s == pytest.approx(want["busy_s"])
    assert 0 < r.busy_s < r.window_s
    s, names = r.kernel_seconds(("fused_probe",))
    assert s == pytest.approx(want["kernel_s"]) and names
