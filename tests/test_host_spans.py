"""The host side measured from inside (PR 37): one ``engine.run()`` is one
span tree — every instant of the loop thread under a span with a parent,
the writer thread and the queue in front of it as spans of their own —
and every phase is measured once: its ``rtfds_phase_seconds`` observation
IS its span's duration.

Three configurations reach every name of the tree (README, Tracing): the
plain one-chip engine, ``key_mode=exact`` with the cold store
(``compact_fetch``, ``cold_append``, ``cold_detect``, ``state_promote``)
and the four-device CPU mesh (``partition``, ``assemble``).
"""

import os
import sys
import time

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import RuntimeConfig
from real_time_fraud_detection_system_tpu.io.sink import ParquetSink
from real_time_fraud_detection_system_tpu.utils.metrics import (
    MetricsRegistry,
)
from real_time_fraud_detection_system_tpu.utils.trace import get_tracer

from test_cold_exact import (  # noqa: E402 (pytest adds tests/ to path)
    _Source,
    _build,
    _churn,
    _fcfg,
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.readers import tracer_spans  # noqa: E402

# what one pass is made of, and where each span hangs
LOOP_TREE = {
    "loop_pass": "run", "source_poll": "loop_pass",
    "host_prep": "loop_pass", "dispatch": "loop_pass",
    "result_wait": "loop_pass", "device_wait": "result_wait",
    "fetch": "result_wait", "sink_enqueue": "loop_pass",
}
WRITER_TREE = {"writer_queue": None, "sink_write": None,
               "sink/parquet": "sink_write", "sink/convert": "sink/parquet",
               "sink/encode": "sink/parquet", "sink/commit": "sink/parquet"}
COLD_TREE = {"cold_detect": "host_prep", "state_promote": "loop_pass",
             "state_compact": "loop_pass", "compact_fetch": "state_compact",
             "cold_append": "state_compact"}
MESH_TREE = {"partition": "host_prep", "assemble": "fetch"}
# phases whose histogram series must hold exactly their spans' readings
MEASURED_ONCE = ("loop_pass", "source_poll", "host_prep", "dispatch",
                 "result_wait", "device_wait", "fetch", "sink_enqueue",
                 "sink_write", "writer_queue")


@pytest.fixture
def tracer():
    tr = get_tracer()
    was = tr.enabled
    tr.configure(enabled=True, annotate=False)
    tr.clear()
    yield tr
    tr.clear()
    tr.enabled = was


class _SlowSource(_Source):
    """A poll that takes 20 ms, as a decode does: a toy pass is otherwise
    ~3-10 ms of device and host work, against which the ~0.2-0.5 ms of
    Python between the spans of a pass (the bookkeeping of the spans
    themselves, a hand-over of the interpreter lock to the cold tier's
    segment writer) would read as several per cent unspanned."""

    def poll_batch(self):
        time.sleep(0.02)
        return super().poll_batch()


def _traced_run(tmp_path, fcfg, devices=1, batches=None, **run_kw):
    rt = RuntimeConfig(batch_buckets=(64,), max_batch_rows=64,
                       pipeline_depth=2, precompile=True)
    reg = MetricsRegistry()
    eng = _build(fcfg, rt, reg, devices)
    eng.precompile()
    batches = batches if batches is not None else _churn(7, 16, 64, 1024)
    # A toy pass is ~10 ms, and a loop thread that waits out the default
    # 5 ms switch interval for the interpreter lock — wherever between two
    # spans the writer's column conversion takes it — would read as
    # unspanned: what is held here is the coverage, not the scheduler.
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        stats = eng.run(_SlowSource(batches),
                        ParquetSink(str(tmp_path / "out")), **run_kw)
    finally:
        sys.setswitchinterval(interval)
    tree = tracer_spans.last_run(tracer_spans.ring())
    return eng, reg, stats, tree


def _direct(tmp_path):
    return dict(customer_capacity=2048, terminal_capacity=2048)


def _cold(tmp_path):
    return _fcfg(str(tmp_path / "cold"), cap=256, demote=64)


@pytest.mark.parametrize("fcfg,devices,expect", [
    (_direct, 1, {}), (_cold, 1, COLD_TREE), (_direct, 4, MESH_TREE),
], ids=["one-chip", "exact-cold", "mesh"])
def test_run_is_one_tree_measured_once(tmp_path, tracer, fcfg, devices,
                                       expect):
    eng, reg, stats, tree = _traced_run(tmp_path, fcfg(tmp_path), devices)
    assert tree is not None and stats["batches"] == 16
    by_id = {r["id"]: r for r in tree["rows"]}
    names = {r["name"] for r in tree["rows"]}
    # (b) every name the configuration can reach, under its parent
    for name, parent in {**LOOP_TREE, **WRITER_TREE, **expect}.items():
        mine = [r for r in tree["rows"] if r["name"] == name]
        assert mine, f"no {name} span"
        # the drain after the last pass hangs a batch's finish on the root
        ok = {parent, "run"} if name in (
            "result_wait", "sink_enqueue", "state_compact") else {parent}
        for r in mine:
            up = by_id.get(r["parent"])
            assert (up["name"] if up else None) in ok, (name, up)
    # (a) the loop thread is covered: next to nothing under no span
    unspanned = tracer_spans.stat_of(tree, ["run", "loop_pass"],
                                     "self_share_pct")
    assert unspanned < 5.0, unspanned
    # chip wait + writer wait + own work are the whole run
    shares = [tracer_spans.stat_of(tree, s, "share_pct") for s in (
        ["device_wait", "compact_fetch"], ["sink_join", "sink_enqueue"])]
    assert 0.0 <= sum(shares) <= 100.0
    # (d) roles: the writer's spans on the writer thread only, the tree
    # under `run` on the loop thread only
    for r in tree["rows"]:
        want = "writer" if r["name"] in WRITER_TREE else "loop"
        assert r["role"] == want, r
    loop_tid = {s.tid for s in tracer.snapshot() if s.role == "loop"}
    writer_tid = {s.tid for s in tracer.snapshot() if s.role == "writer"}
    assert len(loop_tid) == 1 and len(writer_tid) == 1
    assert loop_tid != writer_tid
    # (c) one measurement a phase: the histogram's observations are the
    # spans' own durations — as many, and the same seconds to the last
    # bit a float sum allows
    for name in MEASURED_ONCE + tuple(
            n for n in expect if n not in ("state_compact",)):
        durs = [r["t1"] - r["t0"] for r in tree["rows"]
                if r["name"] == name]
        h = reg.get("rtfds_phase_seconds", phase=name)
        assert h is not None and h.count == len(durs), (name, len(durs))
        assert h.sum == pytest.approx(sum(durs), rel=0, abs=1e-9), name
    if "state_compact" in expect:
        durs = [r["t1"] - r["t0"] for r in tree["rows"]
                if r["name"] == "state_compact"]
        h = reg.get("rtfds_state_compact_seconds")
        assert h.count == len(durs) == 16
        assert h.sum == pytest.approx(sum(durs), rel=0, abs=1e-9)
        # the wait for the pass is inside the pass's span, and is most
        # of nothing else
        for r in tree["rows"]:
            if r["name"] == "compact_fetch":
                up = by_id[r["parent"]]
                assert up["t0"] <= r["t0"] and r["t1"] <= up["t1"]
    # every batch: one queue wait and one write, the wait ending where
    # the write starts
    writes = {r["batch"]: r for r in tree["rows"]
              if r["name"] == "sink_write"}
    queues = {r["batch"]: r for r in tree["rows"]
              if r["name"] == "writer_queue"}
    assert sorted(writes) == sorted(queues) == list(
        range(eng.state.batches_done - 15, eng.state.batches_done + 1))
    for b, q in queues.items():
        assert q["t1"] == writes[b]["t0"] and q["t0"] <= q["t1"]
    assert "pace" not in names and "checkpoint" not in names


def test_run_stats_and_flight_record_carry_the_spans_readings(
        tmp_path, tracer):
    """The run's percentiles and the flight record's ``phases`` are the
    same readings too: a batch's ``result_wait`` in its flight record is
    its span's duration."""
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        FlightRecorder,
    )

    rt = RuntimeConfig(batch_buckets=(64,), max_batch_rows=64,
                       pipeline_depth=2)
    eng = _build(_direct(tmp_path), rt, MetricsRegistry())
    path = str(tmp_path / "flight.jsonl")
    eng.recorder = FlightRecorder(path)
    eng.run(_Source(_churn(3, 8, 64, 512)),
            ParquetSink(str(tmp_path / "out")))
    eng.recorder.close()
    tree = tracer_spans.last_run(tracer_spans.ring())
    _, records = FlightRecorder.read(path)
    batches = {r["batch"]: r for r in records if r["kind"] == "batch"}
    assert len(batches) == 8
    for name in ("host_prep", "dispatch", "result_wait", "sink_write"):
        for r in tree["rows"]:
            if r["name"] == name:
                assert batches[r["batch"]]["phases"][name] == \
                    pytest.approx(r["t1"] - r["t0"], rel=0, abs=1e-9)
    for b in batches.values():
        assert b["trace_id"] == f"b{b['batch']:08d}"


class _QuietSource:
    """Rows, then ``quiet`` empty polls, then rows again, then the end."""

    def __init__(self, batches, quiet):
        self._polls = ([dict(b) for b in batches[:2]] + [{}] * quiet
                       + [dict(b) for b in batches[2:]])
        self.offsets = [0]

    def poll_batch(self):
        if not self._polls:
            return None
        cols = self._polls.pop(0)
        if cols:
            self.offsets = [self.offsets[0] + len(cols["tx_id"])]
        else:
            time.sleep(0.0002)
        return cols


def test_a_quiet_source_folds_into_one_pace_span(tmp_path, tracer):
    """Empty polls open no ``loop_pass`` of their own and leave no
    ``source_poll``: the quiet stretch is one ``pace`` span (after the
    pass that flushed the batches in flight), so a quiet source neither
    fills the ring nor drags ``loop_pass``'s median to the sleep's
    length."""
    rt = RuntimeConfig(batch_buckets=(64,), max_batch_rows=64,
                       pipeline_depth=2, trigger_seconds=0.0)
    reg = MetricsRegistry()
    eng = _build(_direct(tmp_path), rt, reg)
    src = _QuietSource(_churn(5, 4, 64, 512), quiet=300)
    stats = eng.run(src, ParquetSink(str(tmp_path / "out")))
    assert stats["batches"] == 4
    tree = tracer_spans.last_run(tracer_spans.ring())
    paces = [r for r in tree["rows"] if r["name"] == "pace"]
    # the first empty poll's pass drained the pipeline (it has children
    # and stands alone); the other 299 are one span
    assert 1 <= len(paces) <= 2
    assert sum(r["t1"] - r["t0"] for r in paces) >= 300 * 0.0002
    folded = [s for s in tracer.snapshot() if s.name == "pace"
              and (s.args or {}).get("folded")]
    assert sum(s.args["folded"] for s in folded) >= 299
    polls = [r for r in tree["rows"] if r["name"] == "source_poll"]
    assert len(polls) == 5  # four with rows and the end of the stream
    passes = [r for r in tree["rows"] if r["name"] == "loop_pass"]
    assert len(passes) == 5
    assert len(tree["rows"]) < 120  # not a span a poll
    # the histogram still counts every poll, and every quiet pass
    assert reg.get("rtfds_phase_seconds", phase="source_poll").count == 305
    assert reg.get("rtfds_phase_seconds", phase="pace").count == 300
    unspanned = tracer_spans.stat_of(tree, ["run", "loop_pass"],
                                     "self_share_pct")
    assert unspanned < 5.0


def test_checkpoint_pace_and_hooks_are_spans_of_the_pass(tmp_path, tracer):
    """What a pass can also hold: the trigger's sleep (``pace``), the
    checkpoint (its writer drain inside it) and the between-batch hooks —
    so nothing of a pass is unnamed."""
    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        Checkpointer,
    )

    rt = RuntimeConfig(batch_buckets=(64,), max_batch_rows=64,
                       pipeline_depth=2, checkpoint_every_batches=4)
    eng = _build(_direct(tmp_path), rt, MetricsRegistry())
    calls = []
    eng.run(_Source(_churn(9, 8, 64, 512)),
            ParquetSink(str(tmp_path / "out")),
            checkpointer=Checkpointer(str(tmp_path / "ckpt")),
            trigger_seconds=0.005,
            model_reload=lambda: calls.append(1))
    tree = tracer_spans.last_run(tracer_spans.ring())
    by_id = {r["id"]: r for r in tree["rows"]}
    count = {}
    for r in tree["rows"]:
        count[r["name"]] = count.get(r["name"], 0) + 1
    assert count["checkpoint"] == 2 and count["hooks"] == 8 == len(calls)
    assert count["pace"] >= 4
    for r in tree["rows"]:
        if r["name"] in ("checkpoint", "hooks", "pace"):
            assert by_id[r["parent"]]["name"] in ("loop_pass", "run")
    assert tracer_spans.stat_of(tree, ["run", "loop_pass"],
                                "self_share_pct") < 5.0
    assert np.isfinite(tracer_spans.stat_of(tree, ["pace"], "share_pct"))
