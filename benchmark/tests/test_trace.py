"""The trace reduction: on hand-made events, and on a small trace recorded
on the chip (recorded/predict_small.xplane.pb: PERF.md says how it was
taken)."""
import os

import pytest

import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded", "predict_small.xplane.pb")

# times in ns; the window is [1000, 11000)
HOST = [(tr.WINDOW_SPAN, 1000.0, 10000.0),
        ("call_0", 1000.0, 4000.0), ("upload", 1500.0, 1000.0),
        ("call_1", 5000.0, 6000.0)]
DEVICE = [("while", 2000.0, 2000.0),      # holds the two below
          ("fusion.1", 2100.0, 500.0), ("hist", 2700.0, 1000.0),
          ("fusion.1", 6000.0, 1000.0),
          ("copy", 10500.0, 1000.0),      # runs past the window's end
          ("early", 0.0, 500.0)]          # before the window


def test_busy_union_and_idle_share():
    s = tr.reduce_events({"/device:TPU:0": DEVICE}, HOST)
    assert s.window_s == pytest.approx(10000e-9)
    # [2000,4000) + [6000,7000) + [10500,11000)
    assert s.busy_s == pytest.approx(3500e-9)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.65)


def test_self_time_takes_nested_operations_out():
    s = tr.reduce_events({"/device:TPU:0": DEVICE}, HOST)
    assert s.op_self_s["while"] == pytest.approx(500e-9)
    assert s.op_self_s["fusion.1"] == pytest.approx(1500e-9)
    assert s.op_self_s["hist"] == pytest.approx(1000e-9)
    assert "early" not in s.op_self_s
    assert s.n_device_events == 5


def test_gaps_are_named_by_the_innermost_host_span():
    s = tr.reduce_events({"/device:TPU:0": DEVICE}, HOST)
    gaps = dict(s.idle_gaps)
    # [1000,2000) mid 1500: upload (inside call_0); [4000,6000) mid 5000:
    # call_1; [7000,10500) mid 8750: call_1
    assert gaps["upload"] == pytest.approx(1000e-9)
    assert gaps["call_1"] == pytest.approx(5500e-9)
    b = tr.breakdown(s)
    assert b["idle_gaps"][0][0] == "call_1"
    assert b["device_ops"][0][0] == "fusion.1"


def test_busy_is_the_mean_over_devices():
    s = tr.reduce_events({"/device:TPU:0": DEVICE,
                          "/device:TPU:1": [("x", 1000.0, 10000.0)]}, HOST)
    assert s.busy_s == pytest.approx((3500e-9 + 10000e-9) / 2)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events({"/device:TPU:0": DEVICE}, HOST[1:])


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded trace beside the tests")
def test_recorded_chip_trace():
    s = tr.reduce(RECORDED)
    assert s.n_device_events > 0
    assert 0 < s.busy_s <= s.window_s
    assert tr.breakdown(s)["device_ops"]


def test_op_name_keeps_what_tells_operations_apart():
    assert tr.op_name("%fusion.59 = f32[49152]{0} fusion(f32[4096,28] "
                      "%copy-done.7), kind=kCustom, "
                      "calls=%fused_computation.6.clone") == \
        "fusion.59:fused_computation.6.clone"
    assert tr.op_name("%while.3 = (s32[]) while(%tuple.1)") == "while.3"
    assert tr.op_name("plain") == "plain"
