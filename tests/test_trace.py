"""Tracing layer: span API, Chrome-trace export validity, ring-buffer
bounds, /trace endpoint, XLA recompile detection, flight-record
rotation, log-level env + JSON log formatter, and the overhead bounds
the ISSUE acceptance criteria name."""

import json
import logging
import os
import time
import urllib.request

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.utils.metrics import (
    FlightRecorder,
    MetricsRegistry,
    MetricsServer,
)
from real_time_fraud_detection_system_tpu.utils.trace import (
    Tracer,
    get_tracer,
    summarize_chrome,
)
from real_time_fraud_detection_system_tpu.utils.xla_telemetry import (
    RecompileDetector,
    compile_count,
    install_compile_telemetry,
    step_signature,
)

START_EPOCH_S = 1_743_465_600  # 2025-04-01


@pytest.fixture
def global_tracer():
    """The process tracer, enabled for the test and restored after."""
    tr = get_tracer()
    was = tr.enabled
    tr.configure(enabled=True, annotate=False)
    tr.clear()
    yield tr
    tr.clear()
    tr.enabled = was


# ---------------------------------------------------------------------------
# span API + Chrome-trace export validity
# ---------------------------------------------------------------------------

def test_chrome_trace_export_is_valid(tmp_path):
    tr = Tracer(capacity=64).configure(enabled=True, annotate=False)
    for b in (1, 2):
        tid = tr.begin_batch(b)
        assert tid == f"b{b:08d}"
        with tr.span("host_prep", rows=10):
            pass
        with tr.span("dispatch"):
            with tr.span("inner"):
                pass
        tr.instant("marker", note="x")
    path = str(tmp_path / "trace.json")
    man = tr.export(path)
    assert man["trace"] == path

    # the exported file loads with plain json.loads (the Perfetto
    # contract) and every event carries the catapult-required keys
    with open(path, encoding="utf-8") as f:
        trace = json.loads(f.read())
    events = trace["traceEvents"]
    assert len(events) == man["events"] >= 8  # 7 spans + process meta
    for ev in events:
        for key in ("ph", "ts", "pid", "tid", "name"):
            assert key in ev, (key, ev)
    # duration events are sorted by ts: a streaming consumer sees a
    # monotone timeline even though nested spans complete outer-last
    xs = [e["ts"] for e in events if e["ph"] == "X"]
    assert xs == sorted(xs)
    # per-batch trace ids ride in args; durations are non-negative
    for e in events:
        if e["ph"] != "X":
            continue
        assert e["args"]["trace_id"].startswith("b")
        assert e["dur"] >= 0
    # batch 2's spans attribute to batch 2, not batch 1
    ids = {e["args"]["trace_id"] for e in events if e["ph"] == "X"}
    assert ids == {"b00000001", "b00000002"}


def test_span_batch_override_and_current_ids():
    tr = Tracer().configure(enabled=True, annotate=False)
    tid1 = tr.begin_batch(7)
    assert tr.current_ids() == ("b00000007", 7)
    tr.begin_batch(8)
    # pipelined finish: batch 7's result_wait completes while batch 8
    # is current — the explicit override keeps attribution honest
    with tr.span("result_wait", batch=tid1):
        pass
    spans = tr.snapshot()
    assert spans[-1].trace_id == "b00000007"
    assert spans[-1].batch == 7


def test_ring_buffer_eviction():
    tr = Tracer(capacity=8).configure(enabled=True, annotate=False)
    tr.begin_batch(1)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 8
    names = [s.name for s in tr.snapshot()]
    assert names == [f"s{i}" for i in range(12, 20)]  # oldest evicted
    # export reports the drop so "covered everything" can't be assumed
    assert len(tr.export_chrome()["traceEvents"]) == 9  # 8 + meta


def test_disabled_tracer_is_inert_and_returns_empty_ids():
    tr = Tracer()  # disabled by default
    assert tr.begin_batch(3) == ""
    assert tr.current_ids() == ("", 0)
    with tr.span("x"):
        pass
    tr.add_span("y", 0.0, 1.0)
    tr.instant("z")
    assert len(tr) == 0


# ---------------------------------------------------------------------------
# the span tree: ids, parents by the thread's own stack, roles, self time
# ---------------------------------------------------------------------------

def test_parent_is_the_innermost_open_span_of_the_same_thread():
    import threading

    tr = Tracer().configure(enabled=True, annotate=False)
    tr.set_role("loop")
    other = {}

    def writer():
        tr.set_role("writer")
        with tr.span("sink_write") as w:
            with tr.span("sink/encode"):
                pass
        other["write"] = w.id

    with tr.span("run") as run:
        with tr.span("loop_pass") as lap:
            with tr.span("host_prep") as prep:
                # a thread started under an open span owes it nothing:
                # the stack is the thread's own, not containment in time
                t = threading.Thread(target=writer)
                t.start()
                t.join(timeout=10)
                assert not t.is_alive()
                with tr.span("cold_detect"):
                    pass
            tr.instant("marker")
    by = {s.name: s for s in tr.snapshot()}
    assert by["run"].parent == 0 and by["run"].id == run.id
    assert by["loop_pass"].parent == run.id
    assert by["host_prep"].parent == lap.id
    assert by["cold_detect"].parent == prep.id
    assert by["marker"].parent == lap.id
    assert by["sink_write"].parent == 0
    assert by["sink/encode"].parent == other["write"]
    assert {s.role for s in tr.snapshot()
            if s.name.startswith("sink")} == {"writer"}
    assert by["run"].role == by["cold_detect"].role == "loop"
    assert len({s.id for s in tr.snapshot()}) == len(tr.snapshot())
    # a thread that never named its role
    t = threading.Thread(target=lambda: tr.instant("elsewhere"))
    t.start()
    t.join(timeout=10)
    assert tr.snapshot()[-1].role == "other"
    tr.set_role("other")


def test_self_time_is_duration_minus_what_the_children_cover():
    tr = Tracer().configure(enabled=True, annotate=False)
    with tr.span("result_wait"):
        with tr.span("device_wait"):
            time.sleep(0.02)
        with tr.span("fetch"):
            time.sleep(0.005)
        time.sleep(0.01)  # result_wait's own
    by = {s.name: s for s in tr.snapshot()}
    rw = by["result_wait"]
    assert rw.child_s == pytest.approx(
        by["device_wait"].dur_s + by["fetch"].dur_s)
    assert rw.self_s == pytest.approx(rw.dur_s - rw.child_s)
    assert 0.009 < rw.self_s < rw.dur_s - 0.024
    assert by["fetch"].self_s == by["fetch"].dur_s  # a leaf


def test_add_span_takes_an_explicit_parent_or_the_stack():
    tr = Tracer().configure(enabled=True, annotate=False)
    t = time.perf_counter()
    with tr.span("sink_write", batch="b00000007") as w:
        # a wait measured across threads hangs where it is told to
        tr.add_span("writer_queue", t - 0.5, t, batch="b00000007", parent=0)
        # work already timed inside an open span is its child, and takes
        # its batch
        tr.add_span("xla_compile", t, t + 0.001)
    tr.add_span("late", t, t + 0.001, parent=w.id)
    by = {s.name: s for s in tr.snapshot()}
    assert by["writer_queue"].parent == 0
    assert by["writer_queue"].dur_s == pytest.approx(0.5)
    assert by["xla_compile"].parent == w.id
    assert by["xla_compile"].trace_id == "b00000007"
    assert by["late"].parent == w.id
    assert by["sink_write"].child_s == pytest.approx(0.001)


def test_a_span_without_a_batch_takes_its_parents():
    tr = Tracer().configure(enabled=True, annotate=False)
    tr.begin_batch(9)  # the batch the loop is on now
    with tr.span("source_poll", batch="b00000010"):
        with tr.span("decode"):
            pass
    with tr.span("host_prep"):
        pass
    by = {s.name: s for s in tr.snapshot()}
    assert by["decode"].batch == 10 and by["decode"].trace_id == "b00000010"
    assert by["host_prep"].batch == 9


def test_cancel_leaves_no_record_and_fold_lengthens_the_last():
    tr = Tracer().configure(enabled=True, annotate=False)
    with tr.span("run") as run:
        for _ in range(50):  # a quiet source's passes
            with tr.span("loop_pass") as lap:
                with tr.span("source_poll") as poll:
                    poll.cancel()
                lap.fold("pace")
        with tr.span("loop_pass") as lap:  # a pass with work in it
            with tr.span("source_poll"):
                pass
        for _ in range(3):
            with tr.span("loop_pass") as lap:
                lap.fold("pace")
    spans = tr.snapshot()
    assert [s.name for s in spans] == [
        "pace", "source_poll", "loop_pass", "pace", "run"]
    assert spans[0].args["folded"] == 50 and spans[3].args["folded"] == 3
    assert spans[0].parent == spans[3].parent == run.id
    assert spans[0].t1 <= spans[1].t0
    assert spans[-1].child_s == pytest.approx(
        sum(s.dur_s for s in spans if s.parent == run.id), abs=1e-4)
    assert tr.dropped == 0
    # a cancelled span's children still close cleanly under its parent
    with tr.span("outer") as outer:
        with tr.span("gone") as gone:
            gone.cancel()
        with tr.span("kept"):
            pass
    assert tr.snapshot()[-2].name == "kept"
    assert tr.snapshot()[-2].parent == outer.id


def test_a_span_left_open_by_an_exception_does_not_adopt_the_rest():
    tr = Tracer().configure(enabled=True, annotate=False)
    with tr.span("run") as run:
        with pytest.raises(ValueError):
            with tr.span("source_poll"):
                tr.span("source/kafka").open()  # never closed: it raised
                raise ValueError("poll failed")
        with tr.span("host_prep"):
            pass
    by = {s.name: s for s in tr.snapshot()}
    assert by["host_prep"].parent == run.id
    assert "source/kafka" not in by


def test_chrome_export_carries_the_tree():
    tr = Tracer().configure(enabled=True, annotate=False)
    tr.set_role("loop")
    with tr.span("run"):
        with tr.span("loop_pass"):
            pass
    tr.set_role("other")
    events = {e["name"]: e for e in tr.export_chrome()["traceEvents"]
              if e["ph"] == "X"}
    assert events["run"]["args"]["parent"] == 0
    assert events["loop_pass"]["args"]["parent"] == \
        events["run"]["args"]["id"] > 0
    assert events["loop_pass"]["args"]["role"] == "loop"


def test_disabled_span_takes_every_call_of_a_live_one():
    span = Tracer().span("x")
    assert span.open() is span
    span.cancel()
    span.fold("pace")
    span.close(0.0, 1.0, rows=3)
    with span:
        pass


def _batch_of_spans(tr):
    """One serving batch's worth of tracer traffic: 5 live phase spans
    + 2 retroactive source/sink spans."""
    for name in ("source_poll", "host_prep", "dispatch",
                 "result_wait", "sink_write"):
        with tr.span(name):
            pass
    tr.add_span("source/replay", 0.0, 1e-4, rows=1)
    tr.add_span("sink/parquet", 0.0, 1e-4, rows=1)


def _per_batch_cost(tr, n=2000, trials=3):
    """Best-of-N-trials per-batch cost — microbenchmark hygiene on a
    shared CI core (a single trial eats scheduler noise)."""
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(n):
            _batch_of_spans(tr)
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def test_tracer_overhead_bounds():
    """ISSUE acceptance: <50 µs/batch enabled, ~0 disabled. A batch is
    7 spans (source_poll, source/<kind>, host_prep, dispatch,
    result_wait, sink_write, sink/<kind>)."""
    tr = Tracer(capacity=1024).configure(enabled=True, annotate=False)
    tr.begin_batch(1)
    per_batch_enabled = _per_batch_cost(tr)
    assert per_batch_enabled < 50e-6, \
        f"enabled tracer {per_batch_enabled * 1e6:.1f}µs/batch"

    per_batch_disabled = _per_batch_cost(Tracer())  # disabled
    assert per_batch_disabled < 5e-6, \
        f"disabled tracer {per_batch_disabled * 1e6:.2f}µs/batch"


def test_summarize_chrome_critical_path_and_topk():
    tr = Tracer().configure(enabled=True, annotate=False)
    tr.begin_batch(1)
    tr.add_span("host_prep", 0.0, 0.001)
    tr.add_span("dispatch", 0.001, 0.011)   # dominant
    tr.begin_batch(2)
    tr.add_span("host_prep", 0.02, 0.022)
    s = summarize_chrome(tr.export_chrome(), top_k=2)
    assert len(s["batches"]) == 2
    b1 = s["batches"][0]
    assert b1["trace_id"] == "b00000001"
    assert b1["critical_phase"] == "dispatch"
    assert b1["phases_ms"]["dispatch"] == pytest.approx(10.0, abs=0.1)
    assert s["slowest_spans"][0]["name"] == "dispatch"


def test_summarize_chrome_on_a_nested_trace():
    """With parents, a batch's total is its outermost spans' duration —
    not every span's, which counts a child twice — and its critical
    phase the largest SELF time, not the longest span (the pass itself)."""
    tr = Tracer().configure(enabled=True, annotate=False)
    tr.set_role("loop")
    t = 100.0
    tr.begin_batch(3)
    with tr.span("loop_pass") as lap:
        pass
    root = tr.snapshot()[-1]
    tr.clear()
    # hand-timed: loop_pass 100 ms = poll 30 (decode 25) + prep 10
    # (cold_detect 8) + wait 50 (device_wait 49) + 10 of its own
    tr.add_span("loop_pass", t, t + 0.100, parent=0)
    lap_id = tr.snapshot()[-1].id
    tr.add_span("source_poll", t, t + 0.030, parent=lap_id)
    tr.add_span("decode", t + 0.002, t + 0.027,
                parent=tr.snapshot()[-1].id)
    tr.add_span("host_prep", t + 0.030, t + 0.040, parent=lap_id)
    tr.add_span("cold_detect", t + 0.031, t + 0.039,
                parent=tr.snapshot()[-1].id)
    tr.add_span("result_wait", t + 0.040, t + 0.090, parent=lap_id)
    tr.add_span("device_wait", t + 0.040, t + 0.089,
                parent=tr.snapshot()[-1].id)
    tr.set_role("writer")
    tr.add_span("sink_write", t + 0.050, t + 0.120, parent=0)
    tr.add_span("sink/encode", t + 0.060, t + 0.110,
                parent=tr.snapshot()[-1].id)
    tr.set_role("other")
    s = summarize_chrome(tr.export_chrome(), top_k=3)
    (b,) = s["batches"]
    assert b["total_ms"] == pytest.approx(100.0 + 70.0, abs=0.01)
    assert sum(b["phases_ms"].values()) == pytest.approx(392.0, abs=0.05)
    assert b["critical_phase"] == "sink/encode"
    assert b["critical_ms"] == pytest.approx(50.0, abs=0.01)
    assert b["self_ms"]["loop_pass"] == pytest.approx(10.0, abs=0.01)
    assert b["self_ms"]["source_poll"] == pytest.approx(5.0, abs=0.01)
    assert b["self_ms"]["device_wait"] == pytest.approx(49.0, abs=0.01)
    assert sum(b["self_ms"].values()) == pytest.approx(170.0, abs=0.05)
    top = s["self_time"][0]
    assert (top["role"], top["name"]) == ("writer", "sink/encode")
    loop = [r for r in s["self_time"] if r["role"] == "loop"]
    assert loop[0]["name"] == "device_wait"
    assert sum(r["self_ms"] for r in loop) == pytest.approx(100.0, abs=0.05)
    assert root.name == "loop_pass" and lap.id == root.id


def test_ascii_waterfall_render():
    from real_time_fraud_detection_system_tpu.io.dashboard import (
        render_trace_waterfall,
    )

    tr = Tracer().configure(enabled=True, annotate=False)
    tr.begin_batch(5)
    tr.add_span("host_prep", 0.0, 0.004)
    tr.add_span("dispatch", 0.004, 0.010)
    out = render_trace_waterfall(tr.export_chrome())
    assert "trace b00000005" in out
    assert "host_prep" in out and "dispatch" in out
    assert "#" in out
    # unknown trace id: an actionable message, not a traceback
    miss = render_trace_waterfall(tr.export_chrome(), trace_id="nope")
    assert "not in trace" in miss
    assert render_trace_waterfall({"traceEvents": []}) == \
        "no spans in trace"


# ---------------------------------------------------------------------------
# /trace endpoint
# ---------------------------------------------------------------------------

def test_trace_endpoint_smoke(global_tracer):
    global_tracer.begin_batch(1)
    with global_tracer.span("host_prep"):
        pass
    server = MetricsServer(port=0, registry=MetricsRegistry()).start()
    try:
        with urllib.request.urlopen(server.url + "/trace", timeout=5) as r:
            assert r.status == 200
            assert r.headers.get("Content-Type", "").startswith(
                "application/json")
            trace = json.loads(r.read())
    finally:
        server.stop()
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    assert "host_prep" in names


# ---------------------------------------------------------------------------
# XLA compile telemetry + recompile detection
# ---------------------------------------------------------------------------

def test_compile_listener_counts_and_times_compiles():
    import jax
    import jax.numpy as jnp

    assert install_compile_telemetry()
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        get_registry,
    )

    reg = get_registry()
    before = reg.counter("rtfds_xla_compiles_total").value
    h_before = reg.histogram("rtfds_xla_compile_seconds").count
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(16)).block_until_ready()
    assert reg.counter("rtfds_xla_compiles_total").value > before
    assert reg.histogram("rtfds_xla_compile_seconds").count > h_before
    assert compile_count() > 0


def test_recompile_detector_fires_on_shape_change_only():
    import jax
    import jax.numpy as jnp

    assert install_compile_telemetry()
    reg = MetricsRegistry()
    det = RecompileDetector(warmup_calls=2, registry=reg, name="t")
    f = jax.jit(lambda x: x + 1)

    def call(shape):
        x = jnp.ones(shape)
        with det.step(step_signature(x, static=("k", "donate0"))):
            f(x).block_until_ready()

    call((4,))   # warmup compile: expected
    call((4,))   # cache hit
    call((4,))   # steady state, past warmup: no compile, no alarm
    assert det.recompiles == 0
    call((16,))  # shape change after warmup: compile -> alarm
    assert det.recompiles >= 1
    fired = det.recompiles
    call((4,))   # back to a cached shape: no compile, no new alarm
    assert det.recompiles == fired


def test_recompile_detector_blind_without_compiles():
    # no compile observed during the window -> silent even on new sigs
    reg = MetricsRegistry()
    det = RecompileDetector(warmup_calls=0, registry=reg)
    for shape in ((1,), (2,), (3,)):
        with det.step(step_signature(np.ones(shape))):
            pass  # nothing compiles
    assert det.recompiles == 0
    assert det.calls == 3


def _synth_cols(rng, n, base_id):
    return {
        "tx_id": np.arange(base_id, base_id + n, dtype=np.int64),
        "tx_datetime_us": (START_EPOCH_S * 1_000_000
                           + np.arange(n, dtype=np.int64) * 1_000_000),
        "customer_id": rng.integers(0, 100, n).astype(np.int64),
        "terminal_id": rng.integers(0, 200, n).astype(np.int64),
        "tx_amount_cents": rng.integers(100, 10_000, n).astype(np.int64),
        "kafka_ts_ms": np.full(n, START_EPOCH_S * 1000, dtype=np.int64),
    }


@pytest.fixture(scope="module")
def steady_engine():
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.models.logreg import (
        LogRegParams,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine

    cfg = Config(
        features=FeatureConfig(customer_capacity=256,
                               terminal_capacity=512),
        runtime=RuntimeConfig(batch_buckets=(256, 1024)),
    )
    n_feat = 15
    params = LogRegParams(w=jnp.zeros(n_feat, jnp.float32),
                          b=jnp.float32(0.0))
    scaler = Scaler(mean=jnp.zeros(n_feat, jnp.float32),
                    scale=jnp.ones(n_feat, jnp.float32))
    reg = MetricsRegistry()
    eng = ScoringEngine(cfg, "logreg", params, scaler, metrics=reg)
    return eng, reg


def test_engine_steady_state_recompiles_stay_zero(steady_engine):
    """ISSUE acceptance: rtfds_xla_recompiles_total stays 0 over a
    100-batch steady-state CPU engine run."""
    eng, reg = steady_engine
    rng = np.random.default_rng(0)
    for i in range(100):
        eng.process_batch(_synth_cols(rng, 256, base_id=i * 1000))
    assert reg.get("rtfds_xla_recompiles_total").value == 0
    assert eng._recompile.calls >= 100


def test_engine_recompile_fires_on_bucket_change(steady_engine):
    """A batch that jumps to a new jit bucket after warmup compiles in
    the serving loop — the detector must say so (runs after the
    100-batch steady test: well past warmup)."""
    eng, reg = steady_engine
    rng = np.random.default_rng(1)
    before = reg.get("rtfds_xla_recompiles_total").value
    eng.process_batch(_synth_cols(rng, 800, base_id=10_000_000))  # 1024
    assert reg.get("rtfds_xla_recompiles_total").value > before


def test_engine_memory_gauges_are_cpu_silent(steady_engine):
    # CPU devices expose no memory_stats(): the sampler must turn
    # itself off rather than publish fake zeros
    eng, reg = steady_engine
    assert eng._devmem._dead is True
    assert reg.get("rtfds_device_memory_bytes",
                   device="0", kind="in_use") is None


def test_engine_run_records_trace_ids_in_flight_record(
        global_tracer, steady_engine, tmp_path):
    from real_time_fraud_detection_system_tpu.runtime.sources import (
        ReplaySource,
    )
    from real_time_fraud_detection_system_tpu.data.generator import (
        Transactions,
    )

    eng, _ = steady_engine
    n = 1024
    rng = np.random.default_rng(2)
    txs = Transactions(
        tx_id=np.arange(n, dtype=np.int64),
        tx_time_seconds=np.arange(n, dtype=np.int64),
        tx_time_days=np.zeros(n, dtype=np.int32),
        customer_id=rng.integers(0, 100, n).astype(np.int64),
        terminal_id=rng.integers(0, 200, n).astype(np.int64),
        amount_cents=rng.integers(100, 10_000, n).astype(np.int64),
        tx_fraud=np.zeros(n, dtype=np.int8),
        tx_fraud_scenario=np.zeros(n, dtype=np.int8),
    )
    path = str(tmp_path / "fl.jsonl")
    rec = FlightRecorder(path, manifest={"model_kind": "logreg"})
    eng.recorder = rec
    try:
        # max_batches compares against the engine's LIFETIME batch
        # counter; the shared module engine has already served batches
        eng.run(ReplaySource(txs, START_EPOCH_S, batch_rows=256),
                max_batches=eng.state.batches_done + 3)
    finally:
        eng.recorder = None
        rec.close()
    _, records = FlightRecorder.read(path)
    batches = [r for r in records if r["kind"] == "batch"]
    assert len(batches) == 3
    for b in batches:
        # cross-reference into the span trace: every batch record names
        # its trace id, and the trace holds spans under that id
        assert b["trace_id"].startswith("b")
    ids_in_trace = {s.trace_id for s in global_tracer.snapshot()}
    assert {b["trace_id"] for b in batches} <= ids_in_trace


# ---------------------------------------------------------------------------
# flight-record rotation (satellite)
# ---------------------------------------------------------------------------

def test_flight_record_rotation_cap(tmp_path):
    path = str(tmp_path / "fl.jsonl")
    rec = FlightRecorder(path, manifest={"model_kind": "x"},
                         max_bytes=2000)
    for i in range(100):
        rec.record_batch(i, 256, {"host_prep": 0.001, "dispatch": 0.002})
    rec.close()
    # rotation happened: live file stays under ~cap + one segment
    # header, previous generation parked at .1
    assert os.path.exists(path + ".1")
    assert os.path.getsize(path) <= 2000 + 500
    manifest, records = FlightRecorder.read(path)
    assert manifest["model_kind"] == "x"  # fresh segment re-manifested
    rotated = [r for r in records
               if r["kind"] == "event" and r["event"] == "rotated"]
    assert rotated and rotated[0]["previous"] == path + ".1"
    assert rotated[0]["previous_bytes"] > 0
    # both generations stay line-parseable
    for p in (path, path + ".1"):
        with open(p, encoding="utf-8") as f:
            for line in f:
                json.loads(line)
    # batches keep flowing into the fresh generation
    assert any(r["kind"] == "batch" for r in records)


def test_flight_record_no_cap_never_rotates(tmp_path):
    path = str(tmp_path / "fl.jsonl")
    rec = FlightRecorder(path, manifest={})
    for i in range(200):
        rec.record_batch(i, 1, {})
    rec.close()
    assert not os.path.exists(path + ".1")


# ---------------------------------------------------------------------------
# logging satellites: RTFDS_LOG_LEVEL + JSON formatter w/ trace ids
# ---------------------------------------------------------------------------

def test_json_log_formatter_carries_trace_id(global_tracer):
    from real_time_fraud_detection_system_tpu.utils.logging import (
        JsonLineFormatter,
    )

    global_tracer.begin_batch(42)
    rec = logging.LogRecord("rtfds.engine", logging.WARNING, __file__,
                            1, "slow batch: %d ms", (250,), None)
    out = json.loads(JsonLineFormatter().format(rec))
    assert out["level"] == "WARNING"
    assert out["logger"] == "rtfds.engine"
    assert out["msg"] == "slow batch: 250 ms"
    assert out["trace_id"] == "b00000042"
    assert out["batch"] == 42
    # disabled tracer -> no trace keys (never a fake id)
    global_tracer.enabled = False
    out2 = json.loads(JsonLineFormatter().format(rec))
    assert "trace_id" not in out2
    global_tracer.enabled = True


def test_log_level_env_honored(monkeypatch):
    import real_time_fraud_detection_system_tpu.utils.logging as ulog

    root = logging.getLogger("rtfds")
    old_level = root.level
    old_handlers = list(root.handlers)
    try:
        for h in old_handlers:
            root.removeHandler(h)
        monkeypatch.setattr(ulog, "_configured", False)
        monkeypatch.setenv("RTFDS_LOG_LEVEL", "DEBUG")
        ulog.get_logger("x")
        assert root.level == logging.DEBUG
        # unknown level: keeps INFO instead of crashing the CLI
        for h in list(root.handlers):
            root.removeHandler(h)
        monkeypatch.setattr(ulog, "_configured", False)
        monkeypatch.setenv("RTFDS_LOG_LEVEL", "LOUD")
        ulog.get_logger("x")
        assert root.level == logging.INFO
    finally:
        for h in list(root.handlers):
            root.removeHandler(h)
        for h in old_handlers:
            root.addHandler(h)
        root.setLevel(old_level)
        monkeypatch.setattr(ulog, "_configured", True)


@pytest.fixture()
def cache_config():
    """Restore jax's cache settings after a test of the cache rule."""
    import jax

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)


def _recorded_updates(monkeypatch):
    import jax

    calls = []
    real = jax.config.update

    def update(name, value):
        calls.append(name)
        real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    return calls


def test_compilation_cache_env_dir_wins_and_nothing_is_set_in_code(
        monkeypatch, cache_config, tmp_path):
    from real_time_fraud_detection_system_tpu.utils.tracing import (
        enable_compilation_cache,
    )

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _recorded_updates(monkeypatch)
    enable_compilation_cache()
    assert "jax_compilation_cache_dir" not in calls
    assert calls  # the threshold is still lowered


def test_compilation_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config, tmp_path):
    """Unset: ``<checkout>/.jax_cache`` — the same for two calls and for
    two processes started from different directories (the directory is
    part of the cache key: one that moves never hits)."""
    import subprocess
    import sys

    from real_time_fraud_detection_system_tpu.utils.tracing import (
        enable_compilation_cache,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = enable_compilation_cache(), enable_compilation_cache()
    assert first == second == os.path.join(repo, ".jax_cache")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    code = ("from real_time_fraud_detection_system_tpu.utils import "
            "enable_compilation_cache as e; print(e())")
    seen = set()
    for cwd in (str(tmp_path), repo):
        r = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-500:]
        seen.add(r.stdout.strip().splitlines()[-1])
    assert seen == {first}
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compilation_cache_threshold_admits_the_step_programs(
        monkeypatch, cache_config):
    """The forest step compiles for a v5e in 2-6 s and the small buckets
    in well under a second: the minimum-compile-time floor sits below
    them (and above zero, so one-op eager programs stay out), and a cache
    that cannot be enabled raises (no broad except)."""
    import jax

    from real_time_fraud_detection_system_tpu.utils.tracing import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    # step programs (0.3-6 s) are written, one-op eager programs are not
    assert 0 < jax.config.jax_persistent_cache_min_compile_time_secs <= 0.25

    def boom(*a, **k):
        raise RuntimeError("no such config")

    with monkeypatch.context() as m, pytest.raises(RuntimeError):
        m.setattr(jax.config, "update", boom)
        enable_compilation_cache()


def test_cli_trace_subcommand(tmp_path, capsys):
    from real_time_fraud_detection_system_tpu import cli

    tr = Tracer().configure(enabled=True, annotate=False)
    tr.begin_batch(1)
    tr.add_span("host_prep", 0.0, 0.002)
    tr.add_span("dispatch", 0.002, 0.010)
    path = str(tmp_path / "t.json")
    tr.export(path)

    assert cli.main(["trace", "--trace", path, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["batches"][0]["critical_phase"] == "dispatch"

    assert cli.main(["trace", "--trace", path]) == 0
    out = capsys.readouterr().out
    assert "slowest batches" in out
    assert "self time by span" in out and "dispatch=8.00/8.00" in out
    assert "trace b00000001" in out  # the ASCII waterfall rendered

    rc = cli.main(["trace", "--trace", str(tmp_path / "missing.json")])
    assert rc == 2
