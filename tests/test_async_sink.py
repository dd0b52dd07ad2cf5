"""AsyncSink — the ordered writer thread — and the engine loop that owns
one per run: ordering, backpressure, error propagation, the join rule
(write-then-poll without a backlog, overlap with one), the crash/replay
drain contract (checkpoint offsets trail durable output), and that no
thread outlives a run."""

import os
import threading
import time

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    DataConfig,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.io import Checkpointer
from real_time_fraud_detection_system_tpu.io.sink import (
    AsyncSink,
    MemorySink,
    ParquetSink,
)
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime import (
    FlakySource,
    ReplaySource,
    ScoringEngine,
    run_with_recovery,
)
EPOCH0 = 1_743_465_600  # 2025-04-01


def _res(i, n=4):
    from real_time_fraud_detection_system_tpu.runtime.engine import (
        BatchResult,
    )

    ids = np.arange(n, dtype=np.int64) + i * n
    return BatchResult(
        tx_id=ids,
        tx_datetime_us=ids * 10**6,
        customer_id=ids % 7,
        terminal_id=ids % 5,
        amount_cents=ids * 10 + 1,
        features=np.zeros((n, 15), np.float32),
        probs=np.zeros(n, np.float32),
        latency_s=0.0,
        batch_index=i,
    )


class _SlowSink(MemorySink):
    """MemorySink with a per-append delay (forces queueing)."""

    def __init__(self, delay_s=0.01):
        super().__init__()
        self.delay_s = delay_s
        self.order = []

    def append(self, res):
        time.sleep(self.delay_s)
        self.order.append(res.batch_index)
        super().append(res)


def test_async_sink_ordered_appends():
    inner = _SlowSink(delay_s=0.002)
    sink = AsyncSink(inner, max_queue=4)
    for i in range(1, 21):
        sink.append(_res(i))
    sink.drain()
    assert inner.order == list(range(1, 21))
    out = sink.concat()  # drains, then delegates
    assert len(out["tx_id"]) == 20 * 4
    sink.close()


def test_async_sink_backpressure_bounded_and_counted():
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )

    reg = MetricsRegistry()
    inner = _SlowSink(delay_s=0.05)
    sink = AsyncSink(inner, max_queue=1, registry=reg)
    for i in range(1, 5):
        sink.append(_res(i))
    sink.drain()
    sink.close()
    bp = reg.get(
        "rtfds_sink_backpressure_seconds_total", sink="_SlowSink")
    assert bp is not None and bp.value > 0.05  # blocked, and accounted
    assert inner.order == [1, 2, 3, 4]


def test_async_sink_error_propagates_with_original_type():
    class _Failing(MemorySink):
        def __init__(self):
            super().__init__()
            self.n = 0

        def append(self, res):
            self.n += 1
            if self.n == 2:
                raise OSError("disk on fire")
            super().append(res)

    sink = AsyncSink(_Failing(), max_queue=8)
    sink.append(_res(1))
    sink.append(_res(2))
    # the failure surfaces on the LOOP thread with its original type
    # (the supervisor's recover_on policy is type-based)
    with pytest.raises(OSError, match="disk on fire"):
        sink.drain()
    # re-raise cleared the box: a recovered incarnation resumes writing
    sink.append(_res(3))
    sink.drain()
    # batch 2's write failed (it replays from the checkpoint in real
    # serving); batches 1 and 3 landed
    assert [b["tx_id"][0] for b in sink.inner.batches] == [4, 12]
    sink.close()


def test_async_sink_flush_and_truncate_drain_first(tmp_path):
    pq = ParquetSink(str(tmp_path / "parts"))
    sink = AsyncSink(pq, max_queue=8)
    for i in range(1, 6):
        sink.append(_res(i))
    # truncate must see the queued parts (drain first), then fence
    sink.truncate_after(3)
    names = sorted(os.listdir(pq.directory))
    assert names == [f"part-{i:08d}.parquet" for i in (1, 2, 3)]
    sink.close()


def _small_setup(small_dataset, every=2, **runtime):
    _, _, _, txs = small_dataset
    cfg = Config(
        data=DataConfig(n_customers=50, n_terminals=100, n_days=30),
        features=FeatureConfig(customer_capacity=128, terminal_capacity=256,
                               cms_width=1 << 10),
        runtime=RuntimeConfig(checkpoint_every_batches=every,
                              batch_buckets=(256,), max_batch_rows=256,
                              **runtime),
    )
    params = init_logreg(15)
    scaler = Scaler(mean=np.zeros(15, np.float32),
                    scale=np.ones(15, np.float32))

    def make_engine():
        import jax.numpy as jnp

        return ScoringEngine(
            cfg, kind="logreg", params=params,
            scaler=Scaler(jnp.asarray(scaler.mean),
                          jnp.asarray(scaler.scale)),
        )

    return cfg, txs, make_engine


# Polls of 256 rows fill the one 256-row bucket (a backlog: writes
# overlap the next poll); polls of 200 do not (every write is joined).
FULL, SHORT = 256, 200


def _metered_engine(cfg, reg):
    """An engine whose counters and phases land in ``reg`` alone."""
    return ScoringEngine(cfg, kind="logreg", params=init_logreg(15),
                         scaler=Scaler(mean=np.zeros(15, np.float32),
                                       scale=np.ones(15, np.float32)),
                         metrics=reg)


def _writer_threads():
    return [t for t in threading.enumerate()
            if t.name == "rtfds-sink-writer" and t.is_alive()]


def test_async_sink_crash_replay_exactly_once(small_dataset, tmp_path):
    """Kill the stream with results still queued behind the loop's
    writer (between enqueue and landing), recover from the checkpoint,
    and verify the replay overwrites its own parts: NO duplicated and NO
    missing batch_index in the parquet lineage — and the rows equal a
    clean run's."""
    _, txs, make_engine = _small_setup(small_dataset)
    part = txs.slice(slice(0, 2048))

    # clean synchronous reference
    ref = ParquetSink(str(tmp_path / "ref"))
    make_engine().run(ReplaySource(part, EPOCH0, batch_rows=256), sink=ref)
    clean = ref.read_all()

    # faulty run: slow inner writer so the queue holds results when the
    # crash lands (the "kill mid-queue" scenario)
    class _SlowParquet(ParquetSink):
        def append(self, res):
            time.sleep(0.01)
            super().append(res)

    ckpt = Checkpointer(str(tmp_path / "ck"))
    sink = _SlowParquet(str(tmp_path / "out"))
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(3, 6))
    stats = run_with_recovery(make_engine, src, ckpt, sink=sink,
                              max_restarts=5)
    assert stats["restarts"] == 2
    assert not _writer_threads()  # three incarnations, none left a thread

    # sink-side fence: indexed parts are exactly 1..batches, no dup/gap
    stems = sorted(
        int(f[len("part-"):-len(".parquet")])
        for f in os.listdir(str(tmp_path / "out"))
        if f.startswith("part-") and f.endswith(".parquet")
    )
    assert stems == list(range(1, stats["batches"] + 1))

    out = sink.read_all()
    assert np.array_equal(np.sort(out["tx_id"]), np.sort(clean["tx_id"]))
    i1, i2 = np.argsort(out["tx_id"]), np.argsort(clean["tx_id"])
    np.testing.assert_allclose(out["prediction"][i1],
                               clean["prediction"][i2], atol=1e-6)


def test_checkpoint_drains_async_sink(small_dataset, tmp_path):
    """Every checkpoint save, every source commit and the return of run()
    find the loop's writer fully landed: checkpointed progress never
    leads durable sink output, though the sink sleeps and every poll
    shows a backlog (so writes do run behind the loop)."""
    _, txs, make_engine = _small_setup(small_dataset, every=2)
    part = txs.slice(slice(0, 1024))

    landed = []
    at_commit = []

    class _Committing(ReplaySource):
        def commit(self):
            at_commit.append(list(landed))

    class _Probe(ParquetSink):
        def append(self, res):
            time.sleep(0.005)
            super().append(res)
            landed.append(res.batch_index)

    class _CkptProbe(Checkpointer):
        def __init__(self, d):
            super().__init__(d)
            self.at_save = []

        def save(self, engine_state):
            self.at_save.append(
                (engine_state.batches_done, list(landed)))
            return super().save(engine_state)

    ck = _CkptProbe(str(tmp_path / "ck"))
    sink = _Probe(str(tmp_path / "out"))
    stats = make_engine().run(_Committing(part, EPOCH0, batch_rows=FULL),
                              sink=sink, checkpointer=ck)
    assert landed == list(range(1, stats["batches"] + 1))  # at the return
    assert ck.at_save  # checkpoints actually happened
    for batches_done, landed_then in ck.at_save:
        assert landed_then == list(range(1, batches_done + 1))
    assert at_commit == [then for _, then in ck.at_save]


def test_engine_writer_lands_parts_in_loop_order(small_dataset):
    """The loop's writer is one thread, FIFO: the sink sees the loop's
    order though it is slow and the polls show a backlog."""
    _, txs, make_engine = _small_setup(small_dataset)
    names = set()

    class _Named(_SlowSink):
        def append(self, res):
            names.add(threading.current_thread().name)
            super().append(res)

    sink = _Named(delay_s=0.003)
    stats = make_engine().run(
        ReplaySource(txs.slice(slice(0, 4096)), EPOCH0, batch_rows=FULL),
        sink=sink)
    assert stats["batches"] == 16
    assert sink.order == list(range(1, 17))
    assert names == {"rtfds-sink-writer"}
    assert len(sink.concat()["tx_id"]) == stats["rows"]


def test_an_async_sink_handed_in_is_not_wrapped_twice(small_dataset):
    """run() takes the inner sink of an AsyncSink it is given: one writer
    thread of its own serves it, and the caller's stays idle and usable."""
    _, txs, make_engine = _small_setup(small_dataset)
    idents = set()

    class _Who(MemorySink):
        def append(self, res):
            idents.add(threading.get_ident())
            super().append(res)

    given = AsyncSink(_Who(), max_queue=8)
    try:
        stats = make_engine().run(
            ReplaySource(txs.slice(slice(0, 1024)), EPOCH0,
                         batch_rows=FULL), sink=given)
        assert len(idents) == 1
        assert idents.isdisjoint({given._thread.ident,
                                  threading.get_ident()})
        assert _writer_threads() == [given._thread]
        assert len(given.concat()["tx_id"]) == stats["rows"]
    finally:
        given.close()


@pytest.mark.parametrize("ending", ["returns", "raises"])
def test_no_writer_thread_outlives_run(small_dataset, ending):
    _, txs, make_engine = _small_setup(small_dataset)
    src = ReplaySource(txs.slice(slice(0, 2048)), EPOCH0, batch_rows=FULL)
    sink = _SlowSink(delay_s=0.002)
    if ending == "raises":
        src = FlakySource(src, fail_at=(4,))
        with pytest.raises(Exception, match="injected poll failure"):
            make_engine().run(src, sink=sink)
        # what was queued when the loop died still landed, in order
        assert sink.order == list(range(1, len(sink.order) + 1))
    else:
        make_engine().run(src, sink=sink)
        assert sink.order == list(range(1, 9))
    assert not _writer_threads()


@pytest.mark.parametrize("rows", [FULL, SHORT])
def test_writer_error_reaches_the_loop_typed_in_the_same_run(
        small_dataset, rows):
    """A failed write surfaces on the loop thread, in this run(), as the
    exception the sink raised (the supervisor's recover_on policy reads
    the type) — at the next join without a backlog, at the next enqueue
    or drain with one — and no thread is left behind."""
    _, txs, make_engine = _small_setup(small_dataset)

    class _Failing(MemorySink):
        def append(self, res):
            if res.batch_index == 3:
                raise OSError("disk on fire")
            super().append(res)

    sink = _Failing()
    with pytest.raises(OSError, match="disk on fire"):
        make_engine().run(
            ReplaySource(txs.slice(slice(0, 4096)), EPOCH0,
                         batch_rows=rows), sink=sink)
    assert len(sink.batches) == 2  # nothing past the failed write landed
    assert not _writer_threads()


@pytest.mark.parametrize("rows", [FULL, SHORT])
def test_join_rule_follows_the_backlog(small_dataset, rows):
    """Short polls (the source had nothing more to give): every write is
    joined, the writer is idle at each poll — the inline order. Full
    polls (a backlog): the loop polls at once and the writes overlap."""
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )

    cfg, txs, _ = _small_setup(small_dataset)
    reg = MetricsRegistry()
    eng = _metered_engine(cfg, reg)
    sink = _SlowSink(delay_s=0.01)
    handed_at_poll, landed_at_poll = [], []

    class _Watching(ReplaySource):
        def poll_batch(self):
            handed_at_poll.append(
                reg.get("rtfds_sink_batches_total").value)
            landed_at_poll.append(len(sink.order))
            return super().poll_batch()

    stats = eng.run(_Watching(txs.slice(slice(0, 12 * rows)), EPOCH0,
                              batch_rows=rows), sink=sink)
    n = stats["batches"]
    assert n == 12
    assert reg.get("rtfds_sink_batches_total").value == n
    overlapped = reg.get("rtfds_sink_overlapped_batches_total").value
    if rows == SHORT:
        assert overlapped == 0
        assert landed_at_poll == handed_at_poll  # idle at every poll
    else:
        # all but what was still in flight at the last poll
        assert overlapped == n - (stats["pipeline_depth"] - 1)
        assert landed_at_poll != handed_at_poll  # the writes run behind


@pytest.mark.parametrize("rows", [FULL, SHORT])
def test_sink_write_reads_the_write_and_sink_wait_the_block(
        small_dataset, rows):
    """With a slow sink, ``sink_write_p50_ms`` (and the registry's phase)
    is the write, timed where it runs; ``sink_wait`` is what the loop
    thread was blocked: the whole write when it joins, next to nothing
    when it overlaps — never the enqueue under sink_write's name."""
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )

    cfg, txs, _ = _small_setup(small_dataset, sink_queue_batches=64)
    reg = MetricsRegistry()
    eng = _metered_engine(cfg, reg)
    stats = eng.run(
        ReplaySource(txs.slice(slice(0, 12 * rows)), EPOCH0,
                     batch_rows=rows), sink=_SlowSink(delay_s=0.02))
    write = reg.get("rtfds_phase_seconds", phase="sink_write")
    wait = reg.get("rtfds_phase_seconds", phase="sink_wait")
    assert write.count == wait.count == stats["batches"] == 12
    assert stats["sink_write_p50_ms"] >= 20.0
    assert write.sum >= 12 * 0.02
    if rows == SHORT:
        assert stats["sink_wait_p50_ms"] >= 15.0  # the join is the write
    else:
        assert stats["sink_wait_p50_ms"] <= 2.0  # an enqueue
        assert wait.sum < 0.1 * write.sum


def test_flight_record_and_phases_under_a_short_switch_interval(
        small_dataset, tmp_path):
    """The loop thread and its writer share the queue, the phase
    histograms and each batch's flight record. With the interpreter
    switching threads every 10 µs: one record a batch, in order, written
    once the write has its duration (``sink_write``) beside what the loop
    paid (``sink_wait``); one observation a batch in every phase; the
    sink sees the loop's order."""
    import sys

    from real_time_fraud_detection_system_tpu.runtime.engine import PHASES
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        FlightRecorder,
        MetricsRegistry,
    )

    cfg, txs, _ = _small_setup(small_dataset)
    reg = MetricsRegistry()
    eng = _metered_engine(cfg, reg)
    path = str(tmp_path / "flight.jsonl")
    eng.recorder = FlightRecorder(path)
    sink = _SlowSink(delay_s=0.001)
    n = 60
    part = txs.slice(slice(0, 40 * FULL))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        # full polls (overlapped), then short ones (joined), one run each
        # (max_batches counts the engine's batches, not the run's)
        stats = [eng.run(ReplaySource(part, EPOCH0, batch_rows=rows),
                         sink=sink, max_batches=upto)
                 for rows, upto in ((FULL, n // 2), (SHORT, n))]
    finally:
        sys.setswitchinterval(interval)
        eng.recorder.close()
    assert [s["batches"] for s in stats] == [n // 2, n // 2]
    assert sink.order == list(range(1, n + 1))
    for ph in PHASES:
        assert reg.get("rtfds_phase_seconds", phase=ph).count == n, ph
    _, records = FlightRecorder.read(path)
    batches = [r for r in records if r["kind"] == "batch"]
    assert [b["batch"] for b in batches] == list(range(1, n + 1))
    for b in batches:
        assert set(b["phases"]) == set(PHASES)
        assert b["phases"]["sink_write"] >= 0.001
    # joined: the loop waited out each write; overlapped: it did not
    waits = [b["phases"]["sink_wait"] for b in batches]
    assert np.median(waits[n // 2 + 1:]) >= 0.001
    assert np.median(waits[:n // 2]) < 0.001


def _simulated_loop(rate, rule, seconds=30.0, step=0.091, write=0.046,
                    cap=65536, stall_at=(5.0, 15.0), stall=0.115):
    """The engine's loop at depth 2 on a simulated clock: a chip-paced
    step that costs the same whatever the batch holds, a writer behind
    the loop, an open-loop source at ``rate`` rows/s with two pauses of
    the whole machine. → (median creation-to-acknowledgement seconds,
    share of polls made ahead of the write in the last 10 s)."""
    t = cursor = writer_free = chip_free = 0.0
    stalls, in_flight, lat, ahead_late = list(stall_at), None, [], []
    while t < seconds:
        if stalls and t >= stalls[0]:
            t += stall
            stalls.pop(0)
        ahead = rule.ahead
        if not ahead:
            t = max(t, writer_free)  # the join
        if t > seconds - 10.0:
            ahead_late.append(ahead)
        rows = min(cap, rate * t - cursor)  # every row due, up to a bucket
        t += 0.004 + 0.38e-6 * rows + 0.003  # poll + prep + dispatch
        rule.launched(rows, False)
        done = max(t, chip_free) + step
        chip_free = done
        if in_flight is not None:
            first, last, its_done = in_flight
            t = max(t, its_done)  # the fetch waits for the chip
            writer_free = max(writer_free, t) + write
            lat.append(writer_free - (first + last) / 2 / rate)
        in_flight = (cursor, cursor + rows, done)
        cursor += rows
    return float(np.median(lat)), float(np.mean(ahead_late))


class _AlwaysJoin:
    ahead = False

    def launched(self, rows, carry):
        pass


@pytest.mark.parametrize("share_of_knee", [0.6, 0.7, 0.8, 0.9])
def test_poll_ahead_does_not_feed_on_its_own_echo(share_of_knee):
    """Below the chip-paced rate a pause leaves a backlog, the loop polls
    ahead while it lasts, and then goes back to write-then-poll FOR GOOD:
    the join that ends a spell makes one interval a write longer, that
    interval's rows can fill the bucket alone, and a rule that believed
    every full batch would flip full / short for ever (measured on the
    chip at 0.7: +12 % at the median). The median wait is the inline
    order's."""
    from real_time_fraud_detection_system_tpu.runtime.engine import PollAhead

    rate = share_of_knee * 65536 / 0.091
    inline_p50, _ = _simulated_loop(rate, _AlwaysJoin())
    rule = PollAhead(65536)
    p50, ahead_late = _simulated_loop(rate, rule)
    assert ahead_late == 0.0
    assert p50 == pytest.approx(inline_p50, rel=0.01)
    assert rule.need > 1  # it met the echo, and learned from it


def test_poll_ahead_under_saturation_and_with_a_carry():
    from real_time_fraud_detection_system_tpu.runtime.engine import PollAhead

    rule = PollAhead(256)
    assert not rule.ahead  # nothing launched yet: join (a no-op)
    for _ in range(50):
        rule.launched(256, False)
        assert rule.ahead
    assert rule.need == 1
    rule.launched(10, False)  # the backlog ends after a long spell
    assert not rule.ahead and rule.need == 1
    rule.launched(100, True)  # cut short by a carry: more was waiting
    assert rule.ahead
    # above the chip-paced rate every poll fills: ahead from the start
    _, ahead_late = _simulated_loop(1.2 * 65536 / 0.091, PollAhead(65536))
    assert ahead_late == 1.0
