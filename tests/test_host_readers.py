"""The two readers that turn the program's spans into the benchmark's
host-side metrics (``benchmark/readers/tracer_spans.py``: whole-window
shares from the Tracer's ring; ``benchmark/readers/loop_idle.py``: the
chip's idle gaps booked to what the loop thread was doing, and the wait
of a dispatched batch for the chip), on a timeline recorded on the chip
(``tests/data/host_timeline_*.json``, cut by
``tools/record_host_timeline.py``) and on hand-made ones."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.readers import loop_idle, tracer_spans  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e-3


def row(id, parent, name, t0, t1, role="loop", batch=0):
    return {"id": id, "parent": parent, "name": name, "role": role,
            "batch": batch, "t0": t0 * MS, "t1": t1 * MS}


def two_passes():
    """Two 100 ms passes under one run, ms on the ring's clock::

        pass 1:  poll 0-30 (decode 2-27) | prep 30-32 | dispatch 32-34 |
                 result_wait 34-94 (device_wait 34-90, fetch 90-94) |
                 sink_enqueue 94-95
        pass 2:  the same, 100 later, with a state_compact 195-199
                 (compact_fetch 195-198)
        writer:  writer_queue then sink_write 96-166 and 196-266
    """
    rows = [row(1, 0, "run", 0, 270)]
    i = 10
    for b, base in ((1, 0), (2, 100)):
        lap = i
        rows.append(row(lap, 1, "loop_pass", base, base + 100, batch=b))
        rows += [
            row(i + 1, lap, "source_poll", base, base + 30, batch=b),
            row(i + 2, i + 1, "decode", base + 2, base + 27, batch=b),
            row(i + 3, lap, "host_prep", base + 30, base + 32, batch=b),
            row(i + 4, lap, "dispatch", base + 32, base + 34, batch=b),
            row(i + 5, lap, "result_wait", base + 34, base + 94, batch=b),
            row(i + 6, i + 5, "device_wait", base + 34, base + 90, batch=b),
            row(i + 7, i + 5, "fetch", base + 90, base + 94, batch=b),
            row(i + 8, lap, "sink_enqueue", base + 94, base + 95, batch=b),
            row(i + 9, 0, "writer_queue", base + 95, base + 96, "writer", b),
            row(i + 10, 0, "sink_write", base + 96, base + 166, "writer",
                b),
            row(i + 11, i + 10, "sink/encode", base + 100, base + 150,
                "writer", b),
        ]
        i += 20
    rows += [row(60, 30, "state_compact", 195, 199, batch=2),
             row(61, 60, "compact_fetch", 195, 198, batch=2)]
    return rows


# -- tracer_spans --------------------------------------------------------------


def test_shares_medians_and_self_time_over_the_last_run():
    # an earlier run (the history fill's) and its writer are not the window's
    old = [row(900, 0, "run", -1000, -500),
           row(901, 900, "loop_pass", -1000, -600),
           row(902, 0, "sink_write", -900, -800, "writer")]
    tree = tracer_spans.last_run(old + two_passes())
    assert tree["root"]["id"] == 1
    assert {r["id"] for r in tree["rows"]}.isdisjoint({900, 901, 902})
    stat = tracer_spans.stat_of
    assert stat(tree, ["loop_pass"], "p50_ms") == pytest.approx(100.0)
    assert stat(tree, ["decode"], "mean_ms") == pytest.approx(25.0)
    # chip wait: 2 x 56 of device_wait + 3 of compact_fetch, of 270
    assert stat(tree, ["device_wait", "compact_fetch"], "share_pct") == \
        pytest.approx(100 * 115 / 270)
    assert stat(tree, ["sink_join", "sink_enqueue"], "share_pct") == \
        pytest.approx(100 * 2 / 270)
    # the writer is in the run though no parent link leads to it
    assert stat(tree, ["sink_write"], "share_pct") == \
        pytest.approx(100 * 140 / 270)
    assert stat(tree, ["writer_queue"], "p50_ms") == pytest.approx(1.0)
    # unspanned: the run's 70 after its passes, and 5 + 1 a pass (pass 2's
    # compaction covers 4 of its 5)
    assert stat(tree, ["run", "loop_pass"], "self_share_pct") == \
        pytest.approx(100 * (70 + 5 + 1) / 270)
    assert stat(tree, ["source_poll"], "self_share_pct") == \
        pytest.approx(100 * 10 / 270)
    # a name nothing recorded: no median, and a share of nothing
    assert stat(tree, ["checkpoint"], "p50_ms") is None
    assert stat(tree, ["checkpoint"], "share_pct") == 0.0
    with pytest.raises(ValueError):
        stat(tree, ["run"], "p99_ms")


def test_no_run_root_reads_none_and_a_dropped_span_is_an_error(monkeypatch):
    # a program from before spans had parents: names, no ids
    flat = [dict(row(0, 0, n, 0, 10)) for n in ("source_poll", "host_prep")]
    assert tracer_spans.last_run(flat) is None
    monkeypatch.setattr(tracer_spans, "ring", lambda: flat)
    monkeypatch.setattr(tracer_spans, "dropped", lambda: 7)
    ctx = {}
    assert tracer_spans.read(ctx, ["loop_pass"], "p50_ms") is None
    assert loop_idle.read(ctx, ["source_poll"], "idle_pct") is None
    # a whole tree in a ring that lost spans: the window is there in part
    monkeypatch.setattr(tracer_spans, "ring", two_passes)
    with pytest.raises(RuntimeError, match="dropped 7 spans"):
        tracer_spans.read({}, ["loop_pass"], "p50_ms")
    monkeypatch.setattr(tracer_spans, "dropped", lambda: 0)
    ctx = {}
    assert tracer_spans.read(ctx, ["loop_pass"], "p50_ms") == \
        pytest.approx(100.0)
    # built once a run: the second metric does not read the ring again
    monkeypatch.setattr(tracer_spans, "ring", lambda: 1 / 0)
    assert tracer_spans.read(ctx, ["fetch"], "p50_ms") == pytest.approx(4.0)


def test_rows_of_reads_spans_with_and_without_parents():
    from real_time_fraud_detection_system_tpu.utils.trace import Tracer

    tr = Tracer().configure(enabled=True, annotate=False)
    tr.set_role("loop")
    with tr.span("run"):
        with tr.span("loop_pass", batch="b00000004"):
            pass
    tr.set_role("other")
    rows = tracer_spans.rows_of(tr.snapshot())
    tree = tracer_spans.last_run(rows)
    assert [r["name"] for r in tree["rows"]] == ["run", "loop_pass"]
    assert tree["rows"][1]["batch"] == 4 and tree["rows"][1]["role"] == "loop"

    class Old:  # a Span of the parent commit
        name, batch, t0, t1 = "host_prep", 3, 0.0, 1.0

    (r,) = tracer_spans.rows_of([Old()])
    assert (r["id"], r["parent"], r["role"]) == (0, 0, "other")


# -- loop_idle -----------------------------------------------------------------


def _booked(tree, a_ms, b_ms):
    return {path: round(s * 1e3, 6)
            for s, path in loop_idle.book(tree, a_ms * MS, b_ms * MS)}


def test_every_instant_of_a_gap_goes_to_the_deepest_loop_span_open():
    tree = tracer_spans.last_run(two_passes())
    poll = ("run", "loop_pass", "source_poll")
    # inside the decode: four levels down, whole
    assert _booked(tree, 5, 20) == {poll + ("decode",): 15.0}
    # the end of the poll (the decode closed at 27) and a little of the prep
    assert _booked(tree, 28, 31) == {
        poll: 2.0, ("run", "loop_pass", "host_prep"): 1.0}
    # the wait for the pass
    assert _booked(tree, 195.5, 197.5) == {
        ("run", "loop_pass", "state_compact", "compact_fetch"): 2.0}
    # a gap that runs from one pass into the next — the idle after a
    # compaction: the landing's millisecond, the pass's own, the poll
    assert _booked(tree, 198.5, 203) == {
        ("run", "loop_pass", "state_compact"): 0.5,
        ("run", "loop_pass"): 1.0,
        ("run",): 3.0}  # the run ended its passes at 200
    assert _booked(tree, 93, 101.5) == {
        ("run", "loop_pass", "result_wait", "fetch"): 1.0,
        ("run", "loop_pass", "sink_enqueue"): 1.0,
        ("run", "loop_pass"): 5.0, poll: 1.5}
    # after the last pass: the root's own; beyond the run: nobody's
    assert _booked(tree, 210, 260) == {("run",): 50.0}
    assert _booked(tree, 265, 400) == {("run",): 5.0, (): 130.0}
    for a, b in ((5, 20), (28, 31), (93, 101.5), (265, 400)):
        assert sum(_booked(tree, a, b).values()) == pytest.approx(b - a)


def test_a_writer_thread_span_never_takes_a_gap():
    """``sink_write`` runs 96-166 and covers ALL of a gap at 120-127; the
    loop thread was in the second pass's decode."""
    tree = tracer_spans.last_run(two_passes())
    decode = ("run", "loop_pass", "source_poll", "decode")
    assert _booked(tree, 120, 127) == {decode: 7.0}
    booked = (loop_idle.book(tree, 120 * MS, 127 * MS)
              + loop_idle.book(tree, 196 * MS, 198 * MS)
              + loop_idle.book(tree, 210 * MS, 260 * MS))
    under = loop_idle.idle_under
    assert under(booked, ["sink_write", "writer_queue", "sink/encode"]) == 0
    assert under(booked, ["source_poll"]) == pytest.approx(0.007)
    assert under(booked, ["state_compact"]) == pytest.approx(0.002)
    assert under(booked, ["result_wait"]) == 0.0
    assert under(booked, []) == pytest.approx(0.050)  # unspanned
    # a pass that polled nothing and drained the pipeline is a `pace` with
    # a finish inside it: the gap under its wait is the fetch's, once
    booked.append((0.004, ("run", "pace", "result_wait", "device_wait")))
    booked.append((0.001, ("run", "pace")))
    assert under(booked, ["result_wait"]) == pytest.approx(0.004)
    assert under(booked, ["pace", "sink_join"]) == pytest.approx(0.001)
    assert sum(under(booked, s) for s in (
        ["source_poll"], ["host_prep", "state_promote", "dispatch"],
        ["result_wait"], ["state_compact"], [],
        ["sink_join", "sink_enqueue", "checkpoint", "pace", "hooks"],
    )) == pytest.approx(sum(s for s, _ in booked))


def _capture(offset_s, jitter=()):
    """The capture two_passes() would leave: a step program over most of
    each pass, annotations ``offset_s`` off the ring's clock."""
    ann = [[r["name"], r["batch"], r["t0"] + offset_s]
           for r in two_passes() if r["batch"]]
    for i, d in enumerate(jitter):
        ann[i][2] += d
    busy = [[x * MS + offset_s, y * MS + offset_s] for x, y in (
        (-60, 20), (27, 80), (80.4, 120), (135, 200))]
    modules = [["jit_step(1)", (-60) * MS + offset_s, 20 * MS + offset_s],
               ["jit_step(1)", 27 * MS + offset_s, 120 * MS + offset_s],
               ["jit_compact(3)", 196 * MS + offset_s, 199 * MS + offset_s]]
    return {"busy": busy, "modules": modules, "annotations": ann}


def test_the_clocks_join_by_the_annotations_and_gaps_are_booked():
    tree = tracer_spans.last_run(two_passes())
    out = loop_idle.reduce(tree, _capture(1234.5, jitter=(40e-6, -25e-6)))
    assert out["offset_s"] == pytest.approx(1234.5, abs=1e-7)
    assert out["scatter"]["max_us"] == pytest.approx(40.0, abs=0.5)
    assert out["scatter"]["pairs"] == 26
    # three gaps; the one of 0.4 ms is under the floor: 20-27 lies in the
    # first decode, 120-135 runs from the second through the rest of the
    # poll, the prep and the dispatch into the wait for the device
    assert out["gaps"] == 2
    lap = ("run", "loop_pass")
    by = {}
    for sec, path in out["booked"]:
        by[path] = by.get(path, 0.0) + sec * 1e3
    assert by == pytest.approx({
        lap + ("source_poll", "decode"): 14.0, lap + ("source_poll",): 3.0,
        lap + ("host_prep",): 2.0, lap + ("dispatch",): 2.0,
        lap + ("result_wait", "device_wait"): 1.0})
    ctx = {"trace_summary": {"window_s": 0.26},
           loop_idle.CTX_KEY: out, tracer_spans.CTX_KEY: tree}
    assert loop_idle.read(ctx, ["source_poll"], "idle_pct") == \
        pytest.approx(100 * 0.017 / 0.26)
    assert loop_idle.read(
        ctx, ["host_prep", "state_promote", "dispatch"], "idle_pct") == \
        pytest.approx(100 * 0.004 / 0.26)
    # a span that is there and met no gap reads 0.0, not None
    assert loop_idle.read(ctx, ["sink_enqueue"], "idle_pct") == 0.0
    assert loop_idle.read(ctx, [], "idle_pct") == 0.0
    with pytest.raises(ValueError):
        loop_idle.read(ctx, [], "p50_ms")
    # annotations that name no span of the run: the clocks cannot be joined
    cap = _capture(0.0)
    cap["annotations"] = [["precompile", 0, 1.0]]
    assert loop_idle.reduce(tree, cap) is None


def test_a_dispatched_batch_waits_for_the_chip_from_its_dispatch():
    """Batch 1's dispatch closes at 34 and its step runs 36-88: 2 ms in
    the chip's queue; batch 2's closes at 134 and its first program, a
    promote, starts at 135: 1 ms. A compaction between them is nobody's
    step. Steps are told by their ends (each before its own batch's
    ``device_wait`` ends, a step after the batch's before)."""
    tree = tracer_spans.last_run(two_passes())
    modules = [["jit_step(1)", 36 * MS, 88 * MS],
               ["jit_compact(3)", 120 * MS, 125 * MS],
               ["jit_promote(7)", 135 * MS, 140 * MS],
               ["jit_step(1)", 140 * MS, 188 * MS]]
    waits = loop_idle.queue_waits(tree, modules, 0.0)
    assert [round(w * 1e3, 3) for w in waits] == [2.0, 1.0]
    # the capture of batch 2's step alone
    assert [round(w * 1e3, 3) for w in loop_idle.queue_waits(
        tree, modules[2:], 0.0)] == [1.0]
    # a step that ran before any batch of this run was dispatched does not
    # line up with it: nothing is reported rather than a guess
    early = [["jit_step(1)", -60 * MS, 20 * MS]] + modules
    assert loop_idle.queue_waits(tree, early, 0.0) == []
    # the mesh's step is `jit_outer`; a one-op program is nobody's step
    assert loop_idle.program_kind("jit_outer(5)", 0.075) == "step"
    assert loop_idle.program_kind("jit_convert_element_type", 2e-5) == \
        "other"
    assert loop_idle.program_kind("jit_promote(7)", 2e-5) == "promote"


# -- a timeline recorded on the chip --------------------------------------------


@pytest.fixture(scope="module")
def forest_saturate():
    """The first second of `forest.saturate`'s traced 3 s (my chip run,
    PR 37): the chip paces the cell, so the capture has no idle gap."""
    with open(os.path.join(DATA, "host_timeline_forest_saturate.json")) as f:
        return json.load(f)


def test_recorded_ring_is_one_tree_with_its_writer(forest_saturate):
    tree = tracer_spans.last_run(forest_saturate["ring"])
    by_id = {r["id"]: r for r in tree["rows"]}
    names = {r["name"] for r in tree["rows"]}
    assert {"run", "loop_pass", "source_poll", "decode", "host_prep",
            "dispatch", "result_wait", "device_wait", "fetch",
            "sink_enqueue", "writer_queue", "sink_write", "sink/parquet",
            "sink/convert", "sink/encode", "sink/commit"} <= names
    for r in tree["rows"]:
        up = by_id.get(r["parent"])
        if r["name"] in ("run", "writer_queue", "sink_write"):
            assert up is None
            continue
        # a child lies inside its parent, on its parent's thread
        assert up["t0"] <= r["t0"] and r["t1"] <= up["t1"], r
        assert up["role"] == r["role"]
    assert {r["role"] for r in tree["rows"]
            if r["name"].startswith(("sink_write", "sink/", "writer_"))} \
        == {"writer"}
    stat = tracer_spans.stat_of
    # the pass is the chip's step; most of it the loop waits for the chip,
    # the write (53-55 ms of encoding) runs beside it
    assert 86 < stat(tree, ["loop_pass"], "p50_ms") < 92
    assert 45 < stat(tree, ["device_wait"], "p50_ms") < 56
    assert stat(tree, ["device_wait"], "p50_ms") <= \
        stat(tree, ["result_wait"], "p50_ms")
    assert 25 < stat(tree, ["decode"], "p50_ms") < \
        stat(tree, ["source_poll"], "p50_ms") < 40
    assert 50 < stat(tree, ["sink/encode"], "p50_ms") < 60
    assert 10 < stat(tree, ["sink/convert"], "p50_ms") < 20
    assert stat(tree, ["writer_queue"], "p50_ms") < 1.0
    # the passes of the cut tile it: what they keep for themselves is small
    passes = [r for r in tree["rows"] if r["name"] == "loop_pass"]
    own = sum(tracer_spans.self_s(tree, r) for r in passes)
    assert own / sum(r["t1"] - r["t0"] for r in passes) < 0.02


def test_recorded_clocks_join_to_microseconds_and_no_gap_is_booked(
        forest_saturate):
    tree = tracer_spans.last_run(forest_saturate["ring"])
    out = loop_idle.reduce(tree, forest_saturate["capture"])
    assert out["scatter"]["pairs"] >= 150
    assert out["scatter"]["iqr_us"] < 5 and out["scatter"]["max_us"] < 50
    assert out["gaps"] == 0 and out["booked"] == []
    ctx = {"trace_summary": {"window_s": forest_saturate["window_s"]},
           loop_idle.CTX_KEY: out, tracer_spans.CTX_KEY: tree}
    for spans in (["source_poll"], ["result_wait"], ["state_compact"], []):
        assert loop_idle.read(ctx, spans, "idle_pct") == 0.0
    # every step of the capture found its batch: dispatched ~43 ms into
    # the step before it, a batch waits out the rest of that step
    assert len(out["queue_s"]) == len(forest_saturate["capture"]["modules"])
    assert 40 < loop_idle.read(ctx, [], "device_queue_ms") < 56
    # a second off, the clocks no longer join the steps to their batches
    assert loop_idle.queue_waits(
        tree, forest_saturate["capture"]["modules"],
        out["offset_s"] + 1.0) == []


@pytest.fixture(scope="module")
def forest_cold():
    """The first 1.6 s of `forest-cold.saturate`'s traced 3 s (my chip run,
    PR 37): two steps with their promotes, a compaction pass, 87 ms of
    idle chip after it, three more steps."""
    with open(os.path.join(DATA, "host_timeline_forest_cold.json")) as f:
        return json.load(f)


def test_recorded_idle_after_a_pass_is_the_next_batchs_host_work(forest_cold):
    """The gap the ledger booked to `sink_write`: the pass ends with
    nothing dispatched behind it, and the chip waits out the next batch's
    poll, host prep and promote — each gets its part, the writer none."""
    tree = tracer_spans.last_run(forest_cold["ring"])
    out = loop_idle.reduce(tree, forest_cold["capture"])
    assert out["scatter"]["iqr_us"] < 5 and out["scatter"]["max_us"] < 50
    (g0, g1), = [(a[1], b[0]) for a, b in zip(
        forest_cold["capture"]["busy"], forest_cold["capture"]["busy"][1:])]
    assert out["gaps"] == 1
    assert sum(s for s, _ in out["booked"]) == pytest.approx(g1 - g0)
    assert 0.080 < g1 - g0 < 0.095
    # the writer was writing through all of it, and takes none of it
    lo, hi = g0 - out["offset_s"], g1 - out["offset_s"]
    writing = [r for r in tree["rows"] if r["name"] == "sink_write"
               and r["t0"] < hi and r["t1"] > lo]
    assert writing and all(r["role"] == "writer" for r in writing)
    names = {name for _, path in out["booked"] for name in path}
    assert not names & {"sink_write", "writer_queue", "sink/parquet",
                        "sink/convert", "sink/encode", "sink/commit"}
    ctx = {"trace_summary": {"window_s": forest_cold["window_s"]},
           loop_idle.CTX_KEY: out, tracer_spans.CTX_KEY: tree}

    def idle_ms(spans):
        return loop_idle.read(ctx, spans, "idle_pct") * 16.0  # of 1.6 s

    poll, prep = idle_ms(["source_poll"]), idle_ms(
        ["host_prep", "state_promote", "dispatch"])
    tail = idle_ms(["state_compact"])
    other = idle_ms(["sink_join", "sink_enqueue", "checkpoint", "pace",
                     "hooks"])
    assert 30 < poll < 45      # the decode, 34 ms of it
    assert 35 < prep < 55      # cold_detect 16 + promote 27 + the rest
    assert 2 < tail < 10       # the pass's own end; nothing was landed
    assert idle_ms(["result_wait"]) == 0.0 and other < 0.5
    assert idle_ms([]) < 1.0   # next to nothing under no named span
    assert poll + prep + tail + other + idle_ms([]) == \
        pytest.approx((g1 - g0) * 1e3)
    by = {}
    for s, path in out["booked"]:
        by[path[-1]] = by.get(path[-1], 0.0) + s
    assert by["decode"] > by["state_promote"] > by["cold_detect"] > 0.01


def test_recorded_promotes_lead_their_steps_in_the_chips_queue(forest_cold):
    tree = tracer_spans.last_run(forest_cold["ring"])
    cap = forest_cold["capture"]
    out = loop_idle.reduce(tree, cap)
    kinds = [loop_idle.program_kind(n, e - s) for n, s, e in cap["modules"]]
    assert kinds.count("step") == 5 and kinds.count("compact") == 1
    assert kinds.count("promote") == 4 and kinds.count("other") == 1
    waits = [w * 1e3 for w in out["queue_s"]]
    assert len(waits) == 5
    # the batch dispatched while the pass ran starts the moment the pass
    # ends — its promote, dispatched ahead of its `dispatch` span, even
    # before that closes: no wait; the others wait out the step before
    assert waits[2] == 0.0
    assert all(40 < w < 140 for i, w in enumerate(waits) if i != 2)
    stat = tracer_spans.stat_of
    assert 700 < stat(tree, ["compact_fetch"], "mean_ms") < \
        stat(tree, ["state_compact"], "mean_ms") < 1000
    assert stat(tree, ["cold_append"], "p50_ms") is None  # nothing demoted
