"""The reduction from trace to device metrics: on two traces recorded on
the chip (``data/steps_*.json``, cut by ``record_trace.py``) and on
hand-made ones with idle gaps and cut executions."""

import json
import os

import pytest

from benchmark.readers import device_scopes, device_trace
from benchmark.tests.record_trace import trace_of

DATA = os.path.join(os.path.dirname(__file__), "data")


def recording(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_union_merges_overlaps_and_keeps_gaps():
    assert device_trace.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [
        (0, 4), (5, 10)]


def test_the_module_line_and_the_steps_name_are_the_chips():
    """The two things ``whole_steps`` takes from what a chip trace shows:
    the line's name and the step program's."""
    rec = recording("steps_forest_saturate.json")
    names = {m[0] for m in rec["scopes"]["modules"]}
    assert names and all(device_trace.STEP_MODULE.match(n) for n in names)
    assert all(n.startswith("jit_step(") for n in names)
    cold = {m[0].split("(")[0] for m in
            recording("steps_forest_cold.json")["scopes"]["modules"]}
    assert cold == {"jit_step", "jit_compact", "jit_convert_element_type"}
    assert device_trace.STEP_MODULE.match("jit_outer(77)")  # the mesh's
    assert not device_trace.STEP_MODULE.match("jit_compact(7)")
    assert not device_trace.STEP_MODULE.match("jit_promote(7)")
    assert not device_trace.STEP_MODULE.match("jit_step_helper(7)")


def test_five_acknowledgements_and_four_whole_steps_read_the_four_steps():
    """0.4 s of ``forest.saturate``: a step cut at the head, four whole,
    one cut at the end. Whatever the harness counted as acknowledged in
    it (the writer runs up to a write behind the steps), the step is the
    four executions' mean: 88.99 ms, where busy time ÷ 5 acknowledgements
    read 79.99 and ÷ 4 would have read 99.99."""
    trace = trace_of(recording("steps_forest_saturate.json"))
    s = device_trace.summarize(trace, window_s=0.4, batches=5)
    assert (s["steps"], s["acks"]) == (4, 5)
    modules = trace["planes"][0]["lines"][0]["events"]
    assert len(modules) == 6
    whole = [d for _, _, d in modules[1:-1]]
    assert s["device_step_ms"] == pytest.approx(sum(whole) / 4 / 1e6)
    assert 88.9 < s["device_step_ms"] < 89.1
    assert s["busy_s"] / 5 * 1e3 == pytest.approx(79.99, abs=0.01)
    for acks in (3, 4, 6):  # the count the harness passes divides nothing
        again = device_trace.summarize(trace, 0.4, acks)
        assert again["device_step_ms"] == s["device_step_ms"]
    assert s["span_s"] == pytest.approx(0.35597, abs=1e-5)
    # the rest of the summary is what it was: the union over the whole
    # traced window, the operations by name
    assert s["busy_s"] == pytest.approx(0.3999515)
    assert 0.0 <= s["idle_pct"] < 0.1
    assert len(s["device_ops"]) == 10
    assert all(name.startswith("%") and " = " not in name
               for name, _ in s["device_ops"])
    assert s["device_ops"][0][1] >= s["device_ops"][-1][1]
    assert s["idle_gaps"] == []  # no gap of 1 ms in it


def test_a_compaction_inside_the_trace_is_not_in_the_step():
    """0.6 s of ``forest-cold.saturate`` with a pass between its steps:
    ``device_step_ms`` is the three whole steps' mean (128.05), not the
    busy time ÷ anything (it read 138-356 in this cell with the passes
    divided in); the pass is read beside it, per step."""
    rec = recording("steps_forest_cold.json")
    s = device_trace.summarize(trace_of(rec), window_s=0.6, batches=4)
    assert s["steps"] == 3
    assert s["device_step_ms"] == pytest.approx(128.047, abs=0.01)
    assert s["busy_s"] / 4 * 1e3 > 135  # what the old divisor read
    # the idle chip after the pass is still an idle gap, booked as before
    assert [name for name, _ in s["idle_gaps"]] == ["sink_write"]
    assert s["idle_pct"] == pytest.approx(9.43, abs=0.01)
    t = device_scopes.per_step(rec["scopes"]["ops"], rec["scopes"]["modules"])
    assert t["n_steps"] == 3
    read = lambda **kw: device_scopes.read({device_scopes.CTX_KEY: t}, **kw)
    compact = read(scopes=["rtfds.compact"])
    demote = read(scopes=["rtfds.demote"])
    # one pass of 81.4 ms over three steps, all of it under its two scopes
    # or unnamed
    assert 20 < compact < 23 and 1 < demote < 3
    in_step = sum(read(scopes=sc) for sc in (
        ["rtfds.update/rtfds.stamp"], ["rtfds.update/rtfds.reset"],
        ["rtfds.update/rtfds.scatter"], ["rtfds.query"],
        ["rtfds.scale", "rtfds.classify", "rtfds.fused_step"],
        ["rtfds.keydir"], ["rtfds.cms"]))
    unscoped = read(stat="unscoped_pct") / 100 * s["device_step_ms"]
    assert 0.99 * s["device_step_ms"] < in_step + unscoped <= s[
        "device_step_ms"]
    assert in_step + compact + demote > s["device_step_ms"]


def test_gaps_go_to_the_span_that_covers_most_of_them():
    ms = 1_000_000
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step(1)", 0, 15 * ms],        # cut at the head
                ["jit_step(1)", 45 * ms, 15 * ms],  # whole
                ["jit_step(1)", 80 * ms, 5 * ms]]},  # nothing after it
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
    assert (s["steps"], s["device_step_ms"]) == (1, pytest.approx(15.0))


def test_whole_steps_by_the_operations_around_them():
    ops = [["%x", 10, 5], ["%x", 20, 30], ["%x", 60, 30], ["%x", 100, 5]]
    step = lambda s, d: ["jit_step(9)", s, d]
    # a step the trace's first operation opens is taken for cut, so is one
    # no operation follows; a program of another name is no step
    assert device_trace.whole_steps(
        [step(10, 5), step(20, 30), ["jit_compact(3)", 50, 5], step(60, 30),
         step(100, 5)], ops, per_us=1) == [(20, 50), (60, 90)]
    assert device_trace.whole_steps([step(10, 5), step(100, 5)], ops,
                                    per_us=1) == []
    assert device_trace.whole_steps([step(20, 30)], [], per_us=1) == []
    # an edge shared with the trace's first or last operation to within a
    # microsecond is a cut, whichever side of it the nanoseconds fall
    # (t_2 of PR 40's runs: the 2 ms stub at a trace's end ended 1 ns
    # before its last operation began and read as a step)
    ns = [["%x", 1_000, 5], ["%x", 2_000_000, 5], ["%x", 4_000_003, 1]]
    cut = [step(999, 1_500_000), step(2_000_000, 1_000_000),
           step(3_500_000, 500_002)]
    assert device_trace.whole_steps(cut, ns) == [(2_000_000, 3_000_000)]


def test_a_trace_without_a_whole_step_has_no_step_time():
    trace = trace_of(recording("steps_forest_saturate.json"))
    trace["planes"][0]["lines"] = trace["planes"][0]["lines"][1:]  # ops only
    s = device_trace.summarize(trace, 0.4, 5)
    assert s["steps"] == 0 and "device_step_ms" not in s
    assert device_trace.read({"trace_summary": s}, "device_step_ms") is None


def test_no_device_operation_reads_as_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["source_poll", 0, 5]]}]}]}
    assert device_trace.summarize(trace, 1.0, 1) is None
    assert device_trace.read({}, "idle_pct") is None
