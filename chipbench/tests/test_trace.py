"""Trace reduction: busy union, kernel time by name, idle time by the
innermost host span, on a hand-made trace and on a small trace recorded
on a TPU v5e (three XOR parity calls under harness spans)."""
from pathlib import Path

import pytest

from chipbench import trace

RECORDED = Path(__file__).parent / "data" / "tpu_small.xplane.pb"
MS = 1_000_000


def _hand_made():
    # window 0-100 ms; device ops overlap at 10-30 and 20-40, then 70-80;
    # host: op span 5-90 holding a kernel_call span 50-75
    return trace.Trace(
        device={"/device:TPU:0": [("xor_parity", 10 * MS, 30 * MS),
                                  ("fusion", 20 * MS, 40 * MS),
                                  ("xor_parity", 70 * MS, 80 * MS)]},
        spans=[("window", 0, 100 * MS), ("write", 5 * MS, 90 * MS),
               ("kernel_call", 50 * MS, 75 * MS)])


def test_op_name():
    assert trace.op_name("%xor_parity.1 = s32[262144]{0:T(1024)} "
                         "custom-call(s32[4,262144] %p)") == "xor_parity"
    assert trace.op_name("fusion.12") == "fusion"
    assert trace.op_name("copy-start.3 = (f32[]) copy-start()") == \
        "copy-start"


def test_busy_is_the_union_of_device_ops():
    t = _hand_made()
    assert trace.window_s(t) == pytest.approx(0.1)
    assert trace.busy_s(t) == pytest.approx(0.040)      # 10-40, 70-80


def test_kernel_time_by_name():
    got = trace.op_seconds(_hand_made())
    assert got == pytest.approx({"xor_parity": 0.030, "fusion": 0.020})
    assert trace.top(got, 1) == [["xor_parity", pytest.approx(0.030)]]


def test_idle_by_innermost_span():
    got = trace.idle_by_span(_hand_made())
    # idle: 0-5 no span, 5-10 write, 40-50 write, 50-70 kernel_call,
    # 80-90 write, 90-100 no span
    assert got == pytest.approx({"host": 0.015, "write": 0.025,
                                 "kernel_call": 0.020})
    assert sum(got.values()) == pytest.approx(
        trace.window_s(_hand_made()) - trace.busy_s(_hand_made()))


def test_enclosing_ops_keep_their_self_time():
    t = trace.Trace(
        device={"/device:TPU:0": [("while", 10 * MS, 50 * MS),
                                  ("fusion", 12 * MS, 20 * MS),
                                  ("fusion", 30 * MS, 45 * MS),
                                  ("copy", 60 * MS, 61 * MS)]},
        spans=[("window", 0, 100 * MS)])
    assert trace.op_seconds(t) == pytest.approx(
        {"while": 0.017, "fusion": 0.023, "copy": 0.001})
    assert trace.busy_s(t) == pytest.approx(0.041)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.window_s(trace.Trace({}, []))


def test_recorded_tpu_trace():
    t = trace.from_xplane(RECORDED)
    assert list(t.device) == ["/device:TPU:0"]
    ops = trace.op_seconds(t)
    calls = [e for e in t.device["/device:TPU:0"] if e[0] == "xor_parity"]
    assert len(calls) == 3
    assert 0 < ops["xor_parity"] < trace.busy_s(t) + 1e-12
    labels = {lab for lab, _, _ in t.spans}
    assert labels == {"window", "write", "kernel_call"}
    idle = trace.idle_by_span(t)
    assert sum(idle.values()) == pytest.approx(
        trace.window_s(t) - trace.busy_s(t))
    assert idle["write"] > 0.005              # three 2 ms sleeps
