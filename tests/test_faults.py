"""Failure detection, retries, fault injection, checkpoint recovery
(SURVEY §5.3/§5.4 — the build must exceed the reference's compose-level
resilience)."""

import time

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    DataConfig,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.io.checkpoint import Checkpointer
from real_time_fraud_detection_system_tpu.io.sink import MemorySink
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime.engine import ScoringEngine
from real_time_fraud_detection_system_tpu.io.sink import DeadLetterSink
from real_time_fraud_detection_system_tpu.runtime.faults import (
    FlakySource,
    Heartbeat,
    PoisonRowError,
    PoisonSource,
    RetryPolicy,
    TransientError,
    corrupt_messages,
    poison_messages,
    run_with_recovery,
    with_retries,
)
from real_time_fraud_detection_system_tpu.runtime.sources import ReplaySource
from real_time_fraud_detection_system_tpu.utils.metrics import get_registry

EPOCH0 = 1_743_465_600


class _ListSource:
    """Explicit batch list behind the poll/offsets/seek protocol — for
    tests that must hold batch BOUNDARIES fixed across a clean run and a
    poisoned run (bit-identical score comparisons need identical
    batching, which row-count slicing can't give once rows are removed)."""

    def __init__(self, batches):
        self.batches = [dict(b) for b in batches]
        self._pos = 0

    def poll_batch(self):
        if self._pos >= len(self.batches):
            return None
        b = self.batches[self._pos]
        self._pos += 1
        return {k: np.array(v, copy=True) for k, v in b.items()}

    @property
    def offsets(self):
        return [self._pos]

    def seek(self, offsets):
        self._pos = int(offsets[0])


def _batches_from(part, batch_rows=256):
    src = ReplaySource(part, EPOCH0, batch_rows=batch_rows)
    out = []
    while True:
        cols = src.poll_batch()
        if cols is None:
            return out
        out.append(cols)


def _dedup_latest(out: dict) -> dict:
    _, last_idx = np.unique(out["tx_id"][::-1], return_index=True)
    keep = len(out["tx_id"]) - 1 - last_idx
    return {k: v[keep] for k, v in out.items()}


def test_with_retries_succeeds_after_failures():
    calls = {"n": 0}
    sleeps = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientError("boom")
        return 42

    out = with_retries(flaky, RetryPolicy(max_attempts=4, base_delay_s=5.0),
                       sleep=sleeps.append)
    assert out == 42
    assert calls["n"] == 3
    assert sleeps == [5.0, 5.0]  # reference's constant 5s cadence


def test_with_retries_exhausts_and_raises():
    def always():
        raise TransientError("nope")

    with pytest.raises(TransientError):
        with_retries(always, RetryPolicy(max_attempts=2, base_delay_s=0.0),
                     sleep=lambda _: None)


def test_with_retries_nonlisted_exception_propagates_immediately():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("fatal")

    with pytest.raises(ValueError):
        with_retries(bad, RetryPolicy(max_attempts=5, base_delay_s=0.0),
                     sleep=lambda _: None)
    assert calls["n"] == 1


def test_retry_policy_backoff_capped():
    p = RetryPolicy(base_delay_s=1.0, multiplier=10.0, max_delay_s=30.0)
    assert p.delay(0) == 1.0
    assert p.delay(1) == 10.0
    assert p.delay(2) == 30.0  # capped


def test_heartbeat_detects_stall():
    t = {"now": 0.0}
    hb = Heartbeat(timeout_s=10.0, clock=lambda: t["now"])
    assert hb.healthy()
    t["now"] = 5.0
    hb.beat()
    t["now"] = 14.0
    assert hb.healthy()
    t["now"] = 16.0
    assert not hb.healthy()
    assert hb.seconds_since_beat() == 11.0
    assert hb.beats == 1


def test_corrupt_messages_masked_by_decoder(small_dataset):
    from real_time_fraud_detection_system_tpu.core.envelope import (
        decode_transaction_envelopes_fast,
        encode_transaction_envelopes,
    )

    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 100))
    msgs = encode_transaction_envelopes(
        part.tx_id, part.epoch_us(EPOCH0), part.customer_id,
        part.terminal_id, part.amount_cents,
    )
    bad = corrupt_messages(msgs, corrupt_every=10)
    cols, invalid = decode_transaction_envelopes_fast(bad)
    assert invalid.sum() == 10  # every 10th truncated and masked
    good = ~invalid
    np.testing.assert_array_equal(cols["tx_id"][good],
                                  part.tx_id[np.flatnonzero(good)])


def _mk(small_dataset, tmp_path, every=2):
    dcfg, _, _, txs = small_dataset
    cfg = Config(
        data=dcfg,
        features=FeatureConfig(customer_capacity=256, terminal_capacity=512,
                               cms_width=1 << 10),
        runtime=RuntimeConfig(checkpoint_every_batches=every,
                              batch_buckets=(256,), max_batch_rows=256),
    )
    params = init_logreg(15)
    scaler = Scaler(mean=np.zeros(15, np.float32),
                    scale=np.ones(15, np.float32))

    def make_engine():
        import jax.numpy as jnp

        return ScoringEngine(
            cfg, kind="logreg",
            params=params, scaler=Scaler(jnp.asarray(scaler.mean),
                                         jnp.asarray(scaler.scale)),
        )

    return cfg, txs, make_engine


def test_run_with_recovery_exactly_once(small_dataset, tmp_path):
    """Crash mid-stream → restore → final output ≡ clean run (by tx_id,
    latest wins on replays)."""
    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 2048))

    # Clean reference run.
    clean_sink = MemorySink()
    src = ReplaySource(part, EPOCH0, batch_rows=256)
    make_engine().run(src, sink=clean_sink)
    clean = clean_sink.concat()

    # Faulty run: two injected crashes.
    ckpt = Checkpointer(str(tmp_path / "ck"))
    sink = MemorySink()
    src2 = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                       fail_at=(3, 6))
    stats = run_with_recovery(make_engine, src2, ckpt, sink=sink,
                              max_restarts=5)
    assert stats["restarts"] == 2

    out = sink.concat()
    # Replayed batches may duplicate rows: dedup by tx_id keeping the last.
    _, last_idx = np.unique(out["tx_id"][::-1], return_index=True)
    keep = len(out["tx_id"]) - 1 - last_idx
    assert len(keep) == len(clean["tx_id"])  # no gaps
    a = np.argsort(out["tx_id"][keep])
    b = np.argsort(clean["tx_id"])
    np.testing.assert_array_equal(out["tx_id"][keep][a],
                                  clean["tx_id"][b])
    np.testing.assert_allclose(out["prediction"][keep][a],
                               clean["prediction"][b], rtol=1e-5)


def test_retry_policy_rejects_zero_attempts():
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)


def test_recovery_crash_before_first_checkpoint(small_dataset, tmp_path):
    """A crash before ANY checkpoint must rewind to the stream start, or
    the fresh engine's feature state would silently miss early batches."""
    cfg, txs, make_engine = _mk(small_dataset, tmp_path, every=100)
    part = txs.slice(slice(0, 1024))

    clean_sink = MemorySink()
    make_engine().run(ReplaySource(part, EPOCH0, batch_rows=256),
                      sink=clean_sink)
    clean = clean_sink.concat()

    ckpt = Checkpointer(str(tmp_path / "ck3"))
    sink = MemorySink()
    hb = Heartbeat(timeout_s=1e9)
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(1,))  # batch 0 processed, then crash, no ckpt
    stats = run_with_recovery(make_engine, src, ckpt, sink=sink,
                              max_restarts=3, heartbeat=hb)
    assert stats["restarts"] == 1
    assert hb.beats > 0  # heartbeat wired into the batch loop

    out = sink.concat()
    _, last_idx = np.unique(out["tx_id"][::-1], return_index=True)
    keep = len(out["tx_id"]) - 1 - last_idx
    assert len(keep) == len(clean["tx_id"])
    a = np.argsort(out["tx_id"][keep])
    b = np.argsort(clean["tx_id"])
    np.testing.assert_allclose(out["prediction"][keep][a],
                               clean["prediction"][b], rtol=1e-5)


def test_recovery_rerun_fresh_with_resume_false(small_dataset, tmp_path):
    """A second supervised run with resume=False must re-score the stream
    instead of silently resuming past the end of it."""
    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 512))
    ckpt = Checkpointer(str(tmp_path / "ck4"))

    s1 = MemorySink()
    run_with_recovery(make_engine,
                      ReplaySource(part, EPOCH0, batch_rows=256),
                      ckpt, sink=s1, max_restarts=1)
    assert len(s1.concat()["tx_id"]) == 512

    # resume=True (default): continues from the end-of-stream checkpoint.
    s2 = MemorySink()
    stats = run_with_recovery(make_engine,
                              ReplaySource(part, EPOCH0, batch_rows=256),
                              ckpt, sink=s2, max_restarts=1)
    assert s2.concat() == {}

    # resume=False: fresh pass, full output again.
    s3 = MemorySink()
    run_with_recovery(make_engine,
                      ReplaySource(part, EPOCH0, batch_rows=256),
                      ckpt, sink=s3, max_restarts=1, resume=False)
    assert len(s3.concat()["tx_id"]) == 512


def test_resume_false_never_restores_foreign_checkpoint(small_dataset,
                                                        tmp_path):
    """resume=False + a stale checkpoint from a PREVIOUS run + a crash
    before this run's first save: the crash incarnation must restart from
    the stream beginning, not silently resume the foreign checkpoint the
    caller asked to ignore."""
    cfg, txs, make_engine = _mk(small_dataset, tmp_path, every=100)
    part = txs.slice(slice(0, 512))
    ckpt = Checkpointer(str(tmp_path / "ck_fence"))

    # Previous run leaves a checkpoint at end-of-stream.
    run_with_recovery(make_engine,
                      ReplaySource(part, EPOCH0, batch_rows=256),
                      ckpt, sink=MemorySink(), max_restarts=1)
    assert ckpt.latest() is not None

    # New run, resume=False, crash on poll 1 (batch 0 done, nothing saved:
    # checkpoint_every=100). Without fencing, the restart restores the
    # stale end-of-stream checkpoint and outputs nothing further.
    sink = MemorySink()
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(1,))
    stats = run_with_recovery(make_engine, src, ckpt, sink=sink,
                              max_restarts=3, resume=False)
    assert stats["restarts"] == 1
    out = sink.concat()
    # Full fresh pass: every tx scored (batch 0 replayed after restart).
    assert len(np.unique(out["tx_id"])) == 512


def test_recovery_catches_oserror(small_dataset, tmp_path):
    """Real-world transient faults (OSError family) are supervised too."""
    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 512))

    class OsFlaky:
        def __init__(self, inner):
            self.inner = inner
            self._polls = 0

        def poll_batch(self):
            self._polls += 1
            if self._polls == 2:
                raise ConnectionResetError("broker hiccup")
            return self.inner.poll_batch()

        @property
        def offsets(self):
            return self.inner.offsets

        def seek(self, offsets):
            self.inner.seek(offsets)

    ckpt = Checkpointer(str(tmp_path / "ck5"))
    sink = MemorySink()
    stats = run_with_recovery(
        make_engine, OsFlaky(ReplaySource(part, EPOCH0, batch_rows=256)),
        ckpt, sink=sink, max_restarts=2,
    )
    assert stats["restarts"] == 1
    out = sink.concat()
    assert len(np.unique(out["tx_id"])) == 512


def test_run_with_recovery_gives_up(small_dataset, tmp_path):
    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 1024))
    ckpt = Checkpointer(str(tmp_path / "ck2"))
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(0, 1, 2, 3, 4, 5, 6, 7, 8))
    with pytest.raises(TransientError):
        run_with_recovery(make_engine, src, ckpt, max_restarts=2)


def _drain_zombies(release, timeout_s: float = 15.0):
    """Wake abandoned engine-incarnation threads and let them exit before
    the interpreter tears down (a daemon thread killed inside jax/XLA can
    abort the process)."""
    import threading

    release.set()
    deadline = time.time() + timeout_s
    for t in threading.enumerate():
        if t.name == "engine-incarnation" and t is not threading.current_thread():
            t.join(max(0.0, deadline - time.time()))


def test_watchdog_recovers_from_silent_hang(small_dataset, tmp_path):
    """A source that HANGS (never raises) must be detected by the stall
    watchdog and recovered via restart — the round-2 gap: a Heartbeat
    nobody watched meant a wedged source stalled the engine forever.

    The stall budget must exceed worst-case step latency (a restarted
    incarnation re-traces its jitted step, seconds on CPU) or slow
    compiles read as stalls — same sizing rule as production.
    """
    from real_time_fraud_detection_system_tpu.runtime.faults import (
        HangingSource,
    )

    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 1024))

    clean_sink = MemorySink()
    make_engine().run(ReplaySource(part, EPOCH0, batch_rows=256),
                      sink=clean_sink)
    clean = clean_sink.concat()

    ckpt = Checkpointer(str(tmp_path / "ck_hang"))
    sink = MemorySink()
    first_src = []

    def make_source():
        rs = ReplaySource(part, EPOCH0, batch_rows=256)
        if not first_src:  # incarnation 1's session hangs at poll 2
            src = HangingSource(rs, hang_at=(2,), max_hang_s=120.0)
            first_src.append(src)
            return src
        return rs  # restarted incarnations get a clean session

    try:
        t0 = time.perf_counter()
        stats = run_with_recovery(make_engine, checkpointer=ckpt, sink=sink,
                                  max_restarts=3, stall_timeout_s=6.0,
                                  make_source=make_source)
        wall = time.perf_counter() - t0
        # ≥1: the injected hang must be detected. A slow machine may
        # false-stall once more during a restart's recompile — harmless
        # (checkpoint replay is idempotent), so don't pin the exact count.
        assert stats["restarts"] >= 1
        assert wall < 60.0  # detected via stall budget, not max_hang_s

        # Assert while the zombie incarnation is still blocked (it would
        # otherwise resume the shared source and append stale results).
        out = sink.concat()
        _, last_idx = np.unique(out["tx_id"][::-1], return_index=True)
        keep = len(out["tx_id"]) - 1 - last_idx
        assert len(keep) == len(clean["tx_id"])  # no gaps after recovery
        a = np.argsort(out["tx_id"][keep])
        b = np.argsort(clean["tx_id"])
        np.testing.assert_allclose(out["prediction"][keep][a],
                                   clean["prediction"][b], rtol=1e-5)
    finally:
        _drain_zombies(first_src[0].release)


def test_watchdog_escalates_permanent_hang(small_dataset, tmp_path):
    """Every incarnation hangs at its FIRST poll (before any compile) →
    StallError propagates after max_restarts (bounded, not an infinite
    restart loop)."""
    from real_time_fraud_detection_system_tpu.runtime.faults import (
        HangingSource,
        StallError,
    )

    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 512))
    ckpt = Checkpointer(str(tmp_path / "ck_hang2"))
    src = HangingSource(ReplaySource(part, EPOCH0, batch_rows=256),
                        hang_at=(0, 1, 2, 3, 4), max_hang_s=120.0)
    try:
        with pytest.raises(StallError):
            run_with_recovery(make_engine, src, ckpt, sink=MemorySink(),
                              max_restarts=2, stall_timeout_s=0.4)
    finally:
        _drain_zombies(src.release)


def test_recovery_stats_report_whole_session(small_dataset, tmp_path):
    """A recovered session's stats cover ALL rows scored across restarts,
    not just the last incarnation's delta."""
    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 1024))
    ckpt = Checkpointer(str(tmp_path / "ck_tot"))
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(3,))
    stats = run_with_recovery(make_engine, src, ckpt, sink=MemorySink(),
                              max_restarts=2)
    assert stats["restarts"] == 1
    assert stats["rows"] >= 1024  # replays may add, never subtract


def test_recovery_with_store_checkpointer(small_dataset, tmp_path):
    """Crash recovery works over an object-store checkpointer (the
    reference's checkpointLocation-on-s3a role): the fence must use the
    storage-agnostic lineage API, not os.path.exists."""
    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        StoreCheckpointer,
    )
    from real_time_fraud_detection_system_tpu.io.store import LocalStore

    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 1024))

    clean_sink = MemorySink()
    make_engine().run(ReplaySource(part, EPOCH0, batch_rows=256),
                      sink=clean_sink)
    clean = clean_sink.concat()

    store = LocalStore(str(tmp_path / "obj"))
    # Stale higher-numbered lineage from a previous run: must be
    # quarantined on the fresh run's first save, not resurrected and not
    # allowed to trick retention GC into deleting the new run's saves.
    stale_state = make_engine().state
    stale_state.batches_done = 900
    stale_state.offsets = [999999]
    stale_ck = StoreCheckpointer(store)
    stale_ck.save(stale_state)

    ck = StoreCheckpointer(store)
    sink = MemorySink()
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(3,))
    stats = run_with_recovery(make_engine, src, ck, sink=sink,
                              max_restarts=3, resume=False)
    assert stats["restarts"] == 1

    out = sink.concat()
    _, last_idx = np.unique(out["tx_id"][::-1], return_index=True)
    keep = len(out["tx_id"]) - 1 - last_idx
    assert len(keep) == len(clean["tx_id"])  # recovery actually restored
    a = np.argsort(out["tx_id"][keep])
    b = np.argsort(clean["tx_id"])
    np.testing.assert_allclose(out["prediction"][keep][a],
                               clean["prediction"][b], rtol=1e-5)
    # The stale lineage is quarantined, not current.
    latest = ck.latest()
    assert latest is not None and "ckpt-0000000900" not in latest


def test_recovery_parquet_sink_exactly_once(small_dataset, tmp_path):
    """Crash-replay must not duplicate rows in the analyzed Parquet
    output: replayed batches overwrite their own part files (batch-index
    naming), so the landed table equals a clean run's without any
    read-side dedup."""
    import pyarrow.parquet as pq

    from real_time_fraud_detection_system_tpu.io.sink import ParquetSink

    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 1536))

    ckpt = Checkpointer(str(tmp_path / "ck_pq"))
    sink = ParquetSink(str(tmp_path / "analyzed"))
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(3,))
    stats = run_with_recovery(make_engine, src, ckpt, sink=sink,
                              max_restarts=3)
    assert stats["restarts"] == 1

    files = sorted((tmp_path / "analyzed").glob("part-*.parquet"))
    total = sum(pq.read_table(str(f)).num_rows for f in files)
    assert total == 1536  # zero duplicate rows on disk
    assert len(files) == 6  # one part per batch, replays overwrote
    back = sink.read_all()
    assert sorted(back["tx_id"].tolist()) == sorted(part.tx_id.tolist())


def test_parquet_sink_truncate_after(tmp_path):
    from real_time_fraud_detection_system_tpu.io.sink import ParquetSink

    sink = ParquetSink(str(tmp_path / "a"))
    for i in (1, 2, 3, 4, 5):
        (tmp_path / "a" / f"part-{i:08d}.parquet").write_bytes(b"x")
    (tmp_path / "a" / "part-1700000000000-000001.parquet").write_bytes(b"x")
    sink.truncate_after(2)
    names = sorted(p.name for p in (tmp_path / "a").iterdir())
    assert names == ["part-00000001.parquet", "part-00000002.parquet",
                     "part-1700000000000-000001.parquet"]  # legacy kept


def test_recovery_rebatched_replay_no_stale_parts(small_dataset, tmp_path):
    """Replay that re-batches the backlog differently (bigger polls after
    restart) must not leave stale higher-index parts double-counting rows
    on disk — the sink-side restore fence."""
    import pyarrow.parquet as pq

    from real_time_fraud_detection_system_tpu.io.sink import ParquetSink

    cfg, txs, make_engine = _mk(small_dataset, tmp_path, every=100)
    part = txs.slice(slice(0, 1024))

    # First (unsupervised) pass writes 8 parts of 128 rows, no checkpoint
    # ever lands. A later supervised fresh run over the SAME sink dir
    # re-batches at 256 rows (4 parts) — the fence must clear parts 5..8.
    sink = ParquetSink(str(tmp_path / "analyzed"))
    make_engine().run(ReplaySource(part, EPOCH0, batch_rows=128), sink=sink)
    assert len(list((tmp_path / "analyzed").glob("part-*.parquet"))) == 8

    ckpt = Checkpointer(str(tmp_path / "ck_fence"))
    run_with_recovery(make_engine,
                      ReplaySource(part, EPOCH0, batch_rows=256),
                      ckpt, sink=sink, max_restarts=1, resume=False)
    files = list((tmp_path / "analyzed").glob("part-*.parquet"))
    assert len(files) == 4
    total = sum(pq.read_table(str(f)).num_rows for f in files)
    assert total == 1024  # zero stale/duplicate rows


def test_recovery_exactly_once_store_parquet_sink(small_dataset, tmp_path):
    """Crash-replay with the object-store sink: the part-per-batch
    overwrite + truncate_after restore fence must leave the store's
    content ≡ a clean run's (the reference's MinIO landing under Spark's
    sink-commit protocol)."""
    from real_time_fraud_detection_system_tpu.io.sink import StoreParquetSink
    from real_time_fraud_detection_system_tpu.io.store import S3Store
    from test_store import FakeS3Client

    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 2048))

    clean = StoreParquetSink(
        S3Store("commerce", prefix="clean", client=FakeS3Client()))
    make_engine().run(ReplaySource(part, EPOCH0, batch_rows=256), sink=clean)
    want = clean.read_all()

    ckpt = Checkpointer(str(tmp_path / "ck_store"))
    sink = StoreParquetSink(
        S3Store("commerce", prefix="analyzed", client=FakeS3Client()))
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(3, 6))
    stats = run_with_recovery(make_engine, src, ckpt, sink=sink,
                              max_restarts=5)
    assert stats["restarts"] == 2

    got = sink.read_all()
    # part-per-batch overwrite: replays land on the same object keys, so
    # the store holds each row exactly once — no host-side dedup needed.
    assert len(got["tx_id"]) == len(want["tx_id"])
    a, b = np.argsort(got["tx_id"]), np.argsort(want["tx_id"])
    np.testing.assert_array_equal(got["tx_id"][a], want["tx_id"][b])
    np.testing.assert_allclose(got["prediction"][a],
                               want["prediction"][b], rtol=1e-5)


# ---------------------------------------------------------------------------
# PR 4: crash-loop breaker, bisection to the dead-letter queue, backoff
# ---------------------------------------------------------------------------


def test_retry_policy_jitter_fraction():
    p = RetryPolicy(base_delay_s=10.0, jitter=0.5)
    assert p.delay(0) == 10.0  # planning value stays deterministic
    assert p.sleep_s(0, rand=lambda: 0.0) == 10.0
    assert p.sleep_s(0, rand=lambda: 1.0) == 5.0
    full = RetryPolicy(base_delay_s=10.0, jitter=1.0)  # full jitter
    assert full.sleep_s(0, rand=lambda: 0.25) == 7.5
    assert RetryPolicy(base_delay_s=10.0).sleep_s(0) == 10.0  # default: none
    with pytest.raises(ValueError, match="jitter"):
        RetryPolicy(jitter=1.5)


def test_with_retries_outcome_metrics():
    reg = get_registry()
    retried = reg.counter("rtfds_retry_attempts_total", outcome="retried")
    exhausted = reg.counter("rtfds_retry_attempts_total",
                            outcome="exhausted")
    r0, e0 = retried.value, exhausted.value

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientError("boom")
        return 1

    with_retries(flaky, RetryPolicy(max_attempts=4, base_delay_s=0.0),
                 sleep=lambda _: None)
    assert retried.value - r0 == 2
    assert exhausted.value - e0 == 0

    def always():
        raise TransientError("nope")

    with pytest.raises(TransientError):
        with_retries(always, RetryPolicy(max_attempts=2, base_delay_s=0.0),
                     sleep=lambda _: None)
    assert retried.value - r0 == 3
    assert exhausted.value - e0 == 1


def test_restart_backoff_metered(small_dataset, tmp_path):
    """Transient restarts back off (exponential, capped) instead of
    re-entering the loop hot; slept time lands in
    rtfds_restart_backoff_seconds_total."""
    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 1024))
    ckpt = Checkpointer(str(tmp_path / "ck_bo"))
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(3, 6))  # two crashes at DIFFERENT offsets
    sleeps = []
    m = get_registry().counter("rtfds_restart_backoff_seconds_total")
    b0 = m.value
    stats = run_with_recovery(
        make_engine, src, ckpt, sink=MemorySink(), max_restarts=5,
        restart_backoff=RetryPolicy(base_delay_s=0.05, multiplier=2.0,
                                    max_delay_s=1.0),
        sleep=sleeps.append)
    assert stats["restarts"] == 2
    assert sleeps == [0.05, 0.1]  # doubling, no jitter configured
    assert abs((m.value - b0) - 0.15) < 1e-9


def test_poison_source_and_messages_inject_negative_amounts(small_dataset):
    from real_time_fraud_detection_system_tpu.core.envelope import (
        decode_transaction_envelopes_fast,
        encode_transaction_envelopes,
    )

    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 64))
    ids = part.tx_id[10:12].tolist()
    src = PoisonSource(ReplaySource(part, EPOCH0, batch_rows=64),
                       poison_tx_ids=ids)
    cols = src.poll_batch()
    mask = np.isin(cols["tx_id"], ids)
    assert (cols["tx_amount_cents"][mask] < 0).all()
    assert (cols["tx_amount_cents"][~mask] >= 0).all()

    msgs = encode_transaction_envelopes(
        part.tx_id, part.epoch_us(EPOCH0), part.customer_id,
        part.terminal_id, part.amount_cents)
    bad = poison_messages(msgs, poison_at=(3, 5))
    out, invalid = decode_transaction_envelopes_fast(bad)
    assert not invalid.any()  # poison DECODES fine — that's the point
    assert (out["tx_amount_cents"][[3, 5]] < 0).all()
    keep = np.ones(len(msgs), bool)
    keep[[3, 5]] = False
    np.testing.assert_array_equal(out["tx_amount_cents"][keep],
                                  part.amount_cents[keep])


def test_poison_pill_end_to_end_exactly_once(small_dataset, tmp_path):
    """The headline acceptance: a stream with injected always-crashing
    rows COMPLETES; the DLQ holds exactly those rows with their error
    metadata; every other row's score is bit-identical to a run that
    never contained them; crash_loops == 1 and restarts are bounded by
    the crash-loop K — all asserted from the metrics registry."""
    cfg, txs, make_engine = _mk(small_dataset, tmp_path, every=1)
    part = txs.slice(slice(0, 1024))
    batches = _batches_from(part)
    poison_ids = [int(i) for i in batches[2]["tx_id"][10:13]]

    # Clean reference: the SAME batch boundaries minus the poison rows.
    clean_batches = [
        {k: v[~np.isin(b["tx_id"], poison_ids)] for k, v in b.items()}
        for b in batches
    ]
    clean_sink = MemorySink()
    make_engine().run(_ListSource(clean_batches), sink=clean_sink)
    clean = clean_sink.concat()

    reg = get_registry()
    m_restarts = reg.counter("rtfds_engine_restarts_total", cause="crash")
    m_loops = reg.counter("rtfds_crash_loops_total")
    m_dlq = reg.counter("rtfds_dead_letter_rows_total", reason="crash")
    r0, c0, d0 = m_restarts.value, m_loops.value, m_dlq.value

    dlq = DeadLetterSink(str(tmp_path / "dlq.jsonl"))
    sink = MemorySink()
    ckpt = Checkpointer(str(tmp_path / "ck_poison"))
    src = PoisonSource(_ListSource(batches), poison_tx_ids=poison_ids)
    stats = run_with_recovery(make_engine, src, ckpt, sink=sink,
                              max_restarts=5, crash_loop_k=2,
                              dead_letter=dlq)
    assert stats["batches"] == len(batches)  # the stream did NOT die
    assert m_loops.value - c0 == 1
    assert m_restarts.value - r0 == 2  # bounded by K=2
    assert m_dlq.value - d0 == 3

    assert dlq.tx_ids() == sorted(poison_ids)
    for rec in dlq.read_all():
        assert rec["reason"] == "crash"
        assert "PoisonRowError" in rec["error"]
        assert rec["batch_index"] == 3
        assert rec["columns"]["tx_amount_cents"] < 0  # the envelope image
        assert rec["offsets"] == [3]

    out = _dedup_latest(sink.concat())
    a = np.argsort(out["tx_id"])
    b = np.argsort(clean["tx_id"])
    np.testing.assert_array_equal(out["tx_id"][a], clean["tx_id"][b])
    # bit-identical, not allclose: survivors scored from the identical
    # pre-batch state through the identical padded step
    np.testing.assert_array_equal(out["prediction"][a],
                                  clean["prediction"][b])


def test_crash_loop_without_dlq_diagnoses_but_keeps_budget(small_dataset,
                                                           tmp_path):
    """No dead-letter sink: the breaker DIAGNOSES the loop (metric +
    log, exactly once per streak) but keeps the budgeted retry — a
    same-point transient must not die earlier than it would have before
    the breaker existed, and a true poison loop is still bounded by
    max_restarts exactly as before."""
    cfg, txs, make_engine = _mk(small_dataset, tmp_path, every=1)
    part = txs.slice(slice(0, 512))
    poison_ids = [int(part.tx_id[300])]
    ckpt = Checkpointer(str(tmp_path / "ck_nodlq"))
    src = PoisonSource(ReplaySource(part, EPOCH0, batch_rows=256),
                       poison_tx_ids=poison_ids)
    reg = get_registry()
    m_loops = reg.counter("rtfds_crash_loops_total")
    m_restarts = reg.counter("rtfds_engine_restarts_total", cause="crash")
    c0, r0 = m_loops.value, m_restarts.value
    with pytest.raises(PoisonRowError):
        run_with_recovery(make_engine, src, ckpt, sink=MemorySink(),
                          max_restarts=3, crash_loop_k=2)
    assert m_loops.value - c0 == 1  # diagnosed once, not per restart
    assert m_restarts.value - r0 == 3  # full budget used, as pre-breaker


def test_dlq_idempotent_by_tx_id_across_resume(small_dataset, tmp_path):
    """Kill-mid-bisection contract: rows already written by a dead
    incarnation's bisection are neither lost nor duplicated when the
    resumed supervisor re-isolates the same batch (idempotent by tx_id),
    and a later resume of the finished stream adds nothing."""
    cfg, txs, make_engine = _mk(small_dataset, tmp_path, every=1)
    part = txs.slice(slice(0, 768))
    batches = _batches_from(part)
    poison_ids = [int(i) for i in batches[1]["tx_id"][5:7]]

    path = str(tmp_path / "dlq.jsonl")
    # Simulate the prior incarnation that died mid-bisection: it already
    # quarantined the rows but never advanced the checkpoint.
    pre = DeadLetterSink(path)
    seed_cols = {k: v[np.isin(batches[1]["tx_id"], poison_ids)]
                 for k, v in batches[1].items()}
    seed_cols = dict(seed_cols)
    seed_cols["tx_amount_cents"] = -np.abs(seed_cols["tx_amount_cents"]) - 1
    pre.put_rows(seed_cols, reason="crash", error="PoisonRowError: boom",
                 batch_index=2, offsets=[2])
    pre.close()

    dlq = DeadLetterSink(path)  # reopened: seen-set reloads from disk
    assert len(dlq) == 2
    ckpt = Checkpointer(str(tmp_path / "ck_idem"))
    sink = MemorySink()
    src = PoisonSource(_ListSource(batches), poison_tx_ids=poison_ids)
    stats = run_with_recovery(make_engine, src, ckpt, sink=sink,
                              max_restarts=5, crash_loop_k=2,
                              dead_letter=dlq)
    assert stats["batches"] == len(batches)
    recs = dlq.read_all()
    assert [r["tx_id"] for r in recs] == sorted(poison_ids)  # no dups
    assert len(np.unique(sink.concat()["tx_id"])) == 768 - 2

    # Resuming the finished stream: nothing replays, nothing new lands.
    s2 = MemorySink()
    run_with_recovery(make_engine,
                      PoisonSource(_ListSource(batches),
                                   poison_tx_ids=poison_ids),
                      ckpt, sink=s2, max_restarts=2, dead_letter=dlq)
    assert s2.concat() == {}
    assert [r["tx_id"] for r in dlq.read_all()] == sorted(poison_ids)


def test_nan_guard_quarantines_before_state_contamination(tmp_path):
    """Acceptance: an injected non-finite row lands in the DLQ with
    reason=nonfinite, and the customer's SUBSEQUENT window aggregates
    match a run that never saw the row — the NaN never reached the
    running feature state."""
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.models.logreg import (
        LogRegParams,
    )

    def mk_batch(txs_rows):
        tx, ts, cust, term, cents = zip(*txs_rows)
        return {
            "tx_id": np.array(tx, np.int64),
            "tx_datetime_us": np.array(ts, np.int64),
            "customer_id": np.array(cust, np.int64),
            "terminal_id": np.array(term, np.int64),
            "tx_amount_cents": np.array(cents, np.int64),
            "kafka_ts_ms": np.array(ts, np.int64) // 1000,
        }

    H = 3_600_000_000  # 1h in us
    batches = [
        mk_batch([(1, 1 * H, 5, 9, 1000), (2, 2 * H, 6, 9, 2500)]),
        # tx 3 is the poison: its TX_AMOUNT hits the degenerate scaler
        # column exactly (0/0 -> NaN score)
        mk_batch([(3, 3 * H, 5, 9, 66600), (4, 4 * H, 6, 8, 1234)]),
        # customer 5 again: its window aggregates prove whether tx 3's
        # amount contaminated the state
        mk_batch([(5, 5 * H, 5, 9, 2000), (6, 6 * H, 6, 8, 700)]),
    ]
    clean_batches = [
        {k: v[b["tx_id"] != 3] for k, v in b.items()} for b in batches
    ]

    cfg = Config(
        features=FeatureConfig(customer_capacity=128, terminal_capacity=256,
                               cms_width=1 << 10),
        runtime=RuntimeConfig(batch_buckets=(64,), max_batch_rows=64,
                              nan_guard=True),
    )
    cfg_clean = cfg.replace(runtime=RuntimeConfig(
        batch_buckets=(64,), max_batch_rows=64))
    # Degenerate scaler artifact: zero variance recorded for TX_AMOUNT
    # with mean == the poison amount -> (666 - 666) / 0 = NaN for that
    # row, +/-inf (finite sigmoid) for every other.
    mean = np.zeros(15, np.float32)
    scale = np.ones(15, np.float32)
    mean[0], scale[0] = 666.0, 0.0
    params = LogRegParams(w=jnp.full(15, 0.01, jnp.float32),
                          b=jnp.float32(0.0))
    scaler = Scaler(mean=jnp.asarray(mean), scale=jnp.asarray(scale))

    clean_sink = MemorySink()
    ScoringEngine(cfg_clean, kind="logreg", params=params,
                  scaler=scaler).run(_ListSource(clean_batches),
                                     sink=clean_sink)
    clean = clean_sink.concat()

    dlq = DeadLetterSink(str(tmp_path / "dlq_nan.jsonl"))
    sink = MemorySink()
    engine = ScoringEngine(cfg, kind="logreg", params=params,
                           scaler=scaler, dead_letter=dlq)
    engine.run(_ListSource(batches), sink=sink)

    recs = dlq.read_all()
    assert [r["tx_id"] for r in recs] == [3]
    assert recs[0]["reason"] == "nonfinite"
    out = sink.concat()
    assert np.isfinite(out["prediction"]).all()  # NaN never reached sink
    a, b = np.argsort(out["tx_id"]), np.argsort(clean["tx_id"])
    np.testing.assert_array_equal(out["tx_id"][a], clean["tx_id"][b])
    # predictions AND emitted window-feature columns are bit-identical
    # to the run that never saw the row: zero state contamination
    np.testing.assert_array_equal(out["prediction"][a],
                                  clean["prediction"][b])
    for col in clean:
        if col.startswith("customer_id_") or col.startswith("terminal_id_"):
            np.testing.assert_array_equal(out[col][a], clean[col][b], col)


def test_nan_guard_requires_dead_letter(tmp_path):
    cfg = Config(runtime=RuntimeConfig(batch_buckets=(64,),
                                       max_batch_rows=64, nan_guard=True))
    params = init_logreg(15)
    scaler = Scaler(mean=np.zeros(15, np.float32),
                    scale=np.ones(15, np.float32))
    with pytest.raises(ValueError, match="dead-letter"):
        ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler)


def test_guarded_source_post_poll_drop_kills_zombie():
    """The zombie double-fault race (documented in _GuardedSource): a
    poll already in flight when the watchdog abandons the incarnation
    returns AFTER abandonment — the post-poll fence check must drop that
    batch and kill the zombie rather than hand consumed rows to a dead
    incarnation."""
    import threading

    from real_time_fraud_detection_system_tpu.runtime.faults import (
        StallError,
        _AbandonFence,
        _GuardedSource,
    )

    class SlowInner:
        def __init__(self):
            self.gate = threading.Event()
            self.in_poll = threading.Event()
            self.consumed = 0

        def poll_batch(self):
            self.in_poll.set()
            assert self.gate.wait(10.0)  # the hang
            self.consumed += 1  # rows irrevocably consumed on release
            return {"tx_id": np.array([1], np.int64)}

        @property
        def offsets(self):
            return [self.consumed]

        def seek(self, offsets):
            pass

    inner = SlowInner()
    fence = _AbandonFence()
    g = _GuardedSource(inner, fence)
    box = {}

    def zombie():
        try:
            box["out"] = g.poll_batch()
        except BaseException as e:
            box["err"] = e

    t = threading.Thread(target=zombie, name="zombie-poll")
    t.start()
    assert inner.in_poll.wait(5.0)  # the poll is in flight...
    fence.abandoned = True  # ...when the watchdog abandons it
    inner.gate.set()  # the hang releases AFTER abandonment
    t.join(5.0)
    assert not t.is_alive()  # zombie died
    assert inner.consumed == 1  # the rows WERE consumed...
    assert isinstance(box.get("err"), StallError)  # ...but dropped
    assert "out" not in box


def test_shared_source_zombie_lineage_contiguous(small_dataset, tmp_path):
    """Integration twin: shared source + hang + restart, then the hang
    releases — the zombie's late poll dies on the post-poll fence and
    the restarted incarnation's sink lineage stays gap/dup-free."""
    import pyarrow.parquet as pq

    from real_time_fraud_detection_system_tpu.io.sink import ParquetSink
    from real_time_fraud_detection_system_tpu.runtime.faults import (
        HangingSource,
    )

    cfg, txs, make_engine = _mk(small_dataset, tmp_path)
    part = txs.slice(slice(0, 1024))
    src = HangingSource(ReplaySource(part, EPOCH0, batch_rows=256),
                        hang_at=(2,), max_hang_s=120.0)
    sink = ParquetSink(str(tmp_path / "analyzed_z"))
    ckpt = Checkpointer(str(tmp_path / "ck_z"))
    try:
        stats = run_with_recovery(make_engine, src, ckpt, sink=sink,
                                  max_restarts=3, stall_timeout_s=6.0)
        assert stats["restarts"] >= 1
    finally:
        # Release the hang: the zombie's in-flight poll now returns and
        # must die on the fence instead of appending stale output.
        _drain_zombies(src.release)
    parts = sorted((tmp_path / "analyzed_z").glob("part-*.parquet"))
    idxs = [int(p.name[len("part-"):-len(".parquet")]) for p in parts]
    assert idxs == list(range(1, len(idxs) + 1))  # no dup, no gap
    total = sum(pq.read_table(str(f)).num_rows for f in parts)
    assert total == 1024
