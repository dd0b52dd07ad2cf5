"""The reduction from trace to device metrics, on a trace recorded on the
chip and on a hand-made one with idle gaps."""

import json
import os

import pytest

from benchmark.readers import device_trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_merges_overlaps_and_keeps_gaps():
    assert device_trace.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [
        (0, 4), (5, 10)]


def test_recorded_trace_busy_union_and_ops():
    with open(os.path.join(DATA, "trace_forest_saturate.json")) as f:
        trace = json.load(f)
    s = device_trace.summarize(trace, window_s=0.7, batches=2)
    ops = [e for p in trace["planes"] if p["name"].startswith("/device")
           for line in p["lines"] for e in line["events"]]
    # a while loop's event covers its body's, so the union (0.696 s) is
    # less than the sum of the durations; the device was busy all through
    # (my chip run, PR 23: idle 0.3 % over the whole traced window)
    assert s["busy_s"] == pytest.approx(0.696368151)
    assert s["busy_s"] < sum(e[2] for e in ops) / 1e9
    assert 0.0 <= s["idle_pct"] < 1.0
    assert s["device_step_ms"] == pytest.approx(s["busy_s"] / 2 * 1e3)
    assert len(s["device_ops"]) == 10
    assert all(name.startswith("%") and " = " not in name
               for name, _ in s["device_ops"])
    assert s["device_ops"][0][1] >= s["device_ops"][-1][1]
    assert s["idle_gaps"] == []  # no gap of 1 ms in it


def test_gaps_go_to_the_span_that_covers_most_of_them():
    ms = 1_000_000
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_step", 0, 100 * ms]]},
            {"name": "XLA Ops", "events": [
                ["%a = f32[] x()", 0, 10 * ms],
                ["%b = f32[] y()", 10 * ms, 5 * ms],       # no gap
                ["%a = f32[] x()", 45 * ms, 10 * ms],      # 30 ms gap
                ["%a = f32[] x()", 55 * ms + 500_000, 4 * ms],  # 0.5 ms gap
                ["%b = f32[] y()", 80 * ms, 5 * ms]]}]},   # 20.5 ms gap
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["source_poll", 14 * ms, 8 * ms], ["sink_write", 22 * ms, 25 * ms],
            ["result_wait", 60 * ms, 19 * ms]]}]},
    ]}
    s = device_trace.summarize(trace, window_s=0.1, batches=4)
    assert s["busy_s"] == pytest.approx(0.034)
    assert s["idle_pct"] == pytest.approx(66.0)
    assert s["device_ops"] == [["%a", 0.024], ["%b", 0.010]]
    assert s["idle_gaps"] == [["sink_write", 0.030], ["result_wait", 0.0205]]


def test_no_device_operation_reads_as_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["source_poll", 0, 5]]}]}]}
    assert device_trace.summarize(trace, 1.0, 1) is None
    assert device_trace.read({}, "idle_pct") is None
