"""The reduction's arithmetic on hand-made events, and the whole path
on the small trace recorded on the chip (``record_trace.py``)."""

import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(os.path.dirname(HERE), "testdata")


def test_busy_union_counts_nesting_and_overlap_once():
    events = [("while", 0.0, 100.0), ("fusion.1", 10.0, 30.0),
              ("fusion.2", 50.0, 40.0), ("copy", 150.0, 20.0),
              ("copy", 160.0, 30.0)]
    assert trace_reduce.busy_union(events) == [[0.0, 100.0], [150.0, 190.0]]


def test_self_time_leaves_out_what_ran_nested():
    events = [("while", 0.0, 100.0), ("fusion.1", 10.0, 30.0),
              ("fusion.1", 50.0, 40.0), ("inner", 60.0, 10.0),
              ("copy", 150.0, 20.0)]
    assert trace_reduce.self_times(events) == {
        "while": 30.0, "fusion.1": 60.0, "inner": 10.0, "copy": 20.0}


def test_reduce_hand_made_trace():
    trace = {
        "devices": {
            "/device:TPU:0": [("a", 0.0, 4e8), ("b", 6e8, 2e8)],
            "/device:TPU:1": [("a", 0.0, 2e8)],
        },
        "host": [("flush", 3e8, 4e8), ("inner_wait", 4.5e8, 1e8)],
    }
    red = trace_reduce.reduce(trace, window_s=1.0)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((0.6 + 0.2) / 2)
    assert red["device_ops"] == [["a", pytest.approx(0.3)],
                                 ["b", pytest.approx(0.1)]]
    # the one gap (0.4 s -> 0.6 s on device 0) by the innermost host span
    assert red["idle_gaps"] == [["inner_wait", pytest.approx(0.2)]]
    trace["host"] = []
    assert trace_reduce.reduce(trace, 1.0)["idle_gaps"][0][0] == "unattributed"


def test_op_name_keeps_name_type_and_opcode():
    assert trace_reduce.op_name(
        "%fusion.8 = f32[512,512]{1,0:T(8,128)S(1)} fusion(f32[512,512]"
        "{1,0:T(8,128)S(1)} %copy.11), kind=kOutput, calls=%fused"
    ) == "%fusion.8 f32[512,512] fusion"
    assert trace_reduce.op_name(
        "%while = (s32[]{:T(128)}, f32[512,512]{1,0:T(8,128)S(1)}) "
        "while((s32[]{:T(128)}) %tuple.13), condition=%c, body=%b"
    ) == "%while tuple while"
    assert trace_reduce.op_name("plain") == "plain"


def test_no_device_plane_reduces_to_nothing():
    assert trace_reduce.reduce({"devices": {}, "host": []}, 1.0) is None


def test_recorded_trace_from_the_chip():
    """Three launches of a four-round scan with 5 ms sleeps between
    them, recorded on a TPU v5 lite."""
    pytest.importorskip("jax")
    trace = trace_reduce.load(RECORDED)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    events = trace["devices"]["/device:TPU:0"]
    union = trace_reduce.busy_union(events)
    red = trace_reduce.reduce(trace, window_s=EXPECT["window_s"])
    assert len(union) >= 3                      # three launches, apart
    assert red["busy_s"] == pytest.approx(
        sum(b - a for a, b in union) / 1e9)
    assert red["busy_s"] == pytest.approx(EXPECT["busy_s"], rel=1e-6)
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["device_ops"][0][0] == EXPECT["top_op"]
    assert sum(s for _, s in red["device_ops"]) <= red["busy_s"] * 1.0001
    gaps = [s for _, s in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and gaps[0] > 0.004


#: what ``record_trace.py`` printed on the chip for the recorded file
EXPECT = {"window_s": 0.06971468800000125, "busy_s": 0.000104002,
          "top_op": "%fusion.8 f32[512,512] fusion"}
