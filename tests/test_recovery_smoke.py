"""`make recovery-smoke` — the tier-1 dirty-recovery chaos matrix.

Every cell scripts one durable-state failure mode — kill-during-save,
corrupt-latest (byte-flip and truncation), a flaky store, a torn PUT, a
broken delta chain — against BOTH checkpoint planes (local directory and
object store) and asserts the recovery contract END TO END from the
metrics registry and the sink's ``batch_index`` lineage (never prints):

- the stream COMPLETES: restore quarantines the corrupt entry, falls back
  down the lineage to the newest valid checkpoint, and the supervisor
  replays from the older fence instead of dying;
- exact ``rtfds_checkpoint_corrupt_total{reason=…}`` and
  ``rtfds_checkpoint_fallbacks_total`` deltas;
- flaky-store ops retry (``rtfds_retry_attempts_total``) instead of
  killing the stream, with zero false corruption;
- gap/dup-free ``batch_index`` part lineage and the complete row set in
  the Parquet sink after recovery (replays overwrite, never duplicate).
"""

import os
from types import SimpleNamespace

import numpy as np
import pyarrow.parquet as pq
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.io.checkpoint import (
    Checkpointer,
    StoreCheckpointer,
)
from real_time_fraud_detection_system_tpu.io.sink import ParquetSink
from real_time_fraud_detection_system_tpu.io.store import LocalStore
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime.engine import ScoringEngine
from real_time_fraud_detection_system_tpu.runtime.faults import (
    FlakySource,
    FlakyStore,
    TornStore,
    run_with_recovery,
)
from real_time_fraud_detection_system_tpu.runtime.sources import ReplaySource
from real_time_fraud_detection_system_tpu.utils.metrics import get_registry

EPOCH0 = 1_743_465_600
REASONS = ("checksum", "truncated", "incompatible")


def _counters():
    reg = get_registry()
    vals = {r: reg.counter("rtfds_checkpoint_corrupt_total",
                           reason=r).value for r in REASONS}
    vals["fallbacks"] = reg.counter(
        "rtfds_checkpoint_fallbacks_total").value
    vals["retried"] = reg.counter("rtfds_retry_attempts_total",
                                  outcome="retried").value
    return vals


def _mk(small_dataset, rows: int):
    dcfg, _, _, txs = small_dataset
    part = txs.slice(slice(0, rows))
    cfg = Config(
        data=dcfg,
        features=FeatureConfig(customer_capacity=256, terminal_capacity=512,
                               cms_width=1 << 10),
        runtime=RuntimeConfig(checkpoint_every_batches=2,
                              batch_buckets=(256,), max_batch_rows=256),
    )
    params = init_logreg(15)

    def make_engine():
        import jax.numpy as jnp

        return ScoringEngine(
            cfg, kind="logreg", params=params,
            scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
        )

    return part, make_engine


@pytest.fixture(params=["local", "store"])
def plane(request, tmp_path):
    """One durable-state plane per run: a local checkpoint directory or
    an object store (LocalStore-backed, so cells can reach under the
    API to corrupt the stored bytes — exactly what a bit-flipping disk
    or a torn multipart PUT does)."""
    kind = request.param
    if kind == "local":
        d = str(tmp_path / "ck")

        def make(**kw):
            return Checkpointer(d, **kw)

        def file_of(path):
            return path
    else:
        root = str(tmp_path / "obj")

        def make(**kw):
            return StoreCheckpointer(LocalStore(root), **kw)

        def file_of(key):
            return os.path.join(root, key)

    return SimpleNamespace(kind=kind, make=make, file_of=file_of,
                           tmp_path=tmp_path)


def _phase1(make_engine, part, ckpt, sink_dir, max_batches):
    """Run the first stretch of the stream, checkpointing — the state a
    crash/corruption then lands on."""
    eng = make_engine()
    src = ReplaySource(part, EPOCH0, batch_rows=256)
    eng.run(src, sink=ParquetSink(sink_dir), checkpointer=ckpt,
            max_batches=max_batches)
    return eng


def _phase2(make_engine, part, ckpt, sink_dir, max_restarts=3):
    """Resume the stream supervised (a restarted deployment): restore —
    verified, with fallback — then complete."""
    src = ReplaySource(part, EPOCH0, batch_rows=256)
    return run_with_recovery(
        make_engine, src, ckpt, sink=ParquetSink(sink_dir),
        max_restarts=max_restarts)


def _assert_lineage(sink_dir, part, n_parts):
    """Gap/dup-free batch_index lineage + the complete row set."""
    parts = sorted((p for p in os.listdir(sink_dir)
                    if p.startswith("part-")),)
    idxs = [int(p[len("part-"):-len(".parquet")]) for p in parts]
    assert idxs == list(range(1, n_parts + 1))
    total = sum(pq.read_table(os.path.join(sink_dir, f)).num_rows
                for f in parts)
    assert total == part.n
    back = ParquetSink(sink_dir).read_all()
    assert sorted(np.unique(back["tx_id"]).tolist()) == sorted(
        part.tx_id.tolist())


def test_corrupt_latest_byte_flip(plane, tmp_path, small_dataset):
    """A bit-flip in the newest checkpoint: restore detects it
    (reason=checksum), quarantines the file, falls back one fence and
    replays to a complete, gap-free stream."""
    part, make_engine = _mk(small_dataset, 1536)
    sink_dir = str(tmp_path / "analyzed")
    ckpt = plane.make()
    _phase1(make_engine, part, ckpt, sink_dir, max_batches=4)
    latest = ckpt.latest()
    f = plane.file_of(latest)
    data = open(f, "rb").read()
    with open(f, "r+b") as fh:
        fh.seek(len(data) // 2)
        fh.write(bytes([data[len(data) // 2] ^ 0xFF]))

    base = _counters()
    stats = _phase2(make_engine, part, plane.make(), sink_dir)
    after = _counters()

    assert stats["batches"] == 6 and stats["rows"] >= 1536
    assert after["checksum"] - base["checksum"] == 1
    assert after["truncated"] == base["truncated"]
    assert after["incompatible"] == base["incompatible"]
    assert after["fallbacks"] - base["fallbacks"] == 1
    # the corrupt bytes are quarantined (stashed, not deleted) for
    # forensics; the replay re-created the fence under the same name,
    # and the post-recovery lineage re-verifies clean end to end
    fresh = plane.make()
    assert sum(1 for n in fresh._backend.list_names()
               if n.startswith("stale-")) == 1
    assert all(e["valid"] for e in fresh.verify_all())
    _assert_lineage(sink_dir, part, 6)


def test_corrupt_latest_truncation(plane, tmp_path, small_dataset):
    """A torn write leaves the newest checkpoint half-length: restore
    classifies it truncated and replays from the previous fence."""
    part, make_engine = _mk(small_dataset, 1536)
    sink_dir = str(tmp_path / "analyzed")
    ckpt = plane.make()
    _phase1(make_engine, part, ckpt, sink_dir, max_batches=4)
    f = plane.file_of(ckpt.latest())
    data = open(f, "rb").read()
    with open(f, "wb") as fh:
        fh.write(data[: len(data) // 3])

    base = _counters()
    stats = _phase2(make_engine, part, plane.make(), sink_dir)
    after = _counters()

    assert stats["batches"] == 6
    assert after["truncated"] - base["truncated"] == 1
    assert after["checksum"] == base["checksum"]
    assert after["fallbacks"] - base["fallbacks"] == 1
    _assert_lineage(sink_dir, part, 6)


def test_kill_during_save_local(tmp_path, small_dataset):
    """Local plane killed between the tmp write and os.replace: the
    committed lineage is intact (atomic rename), the orphan ``.tmp`` is
    swept at construction, and recovery replays with ZERO corruption
    counted — a clean kill must not look like corruption."""
    part, make_engine = _mk(small_dataset, 1536)
    d = str(tmp_path / "ck")
    sink_dir = str(tmp_path / "analyzed")
    ckpt = Checkpointer(d)
    _phase1(make_engine, part, ckpt, sink_dir, max_batches=4)
    # the save at batch 4 "died mid-write": its file never committed,
    # its tmp remains
    latest = ckpt.latest()
    os.remove(latest)
    orphan = latest + ".tmp"
    with open(orphan, "wb") as fh:
        fh.write(b"half a checkpoint, interrupted")

    base = _counters()
    stats = _phase2(make_engine, part, Checkpointer(d), sink_dir)
    after = _counters()

    assert stats["batches"] == 6
    assert not os.path.exists(orphan)  # swept at construction
    assert after == base  # no corruption, no fallback, no retries
    _assert_lineage(sink_dir, part, 6)


def test_kill_during_save_store_torn_put(tmp_path, small_dataset):
    """Store plane killed mid-PUT (torn multipart upload that still
    'succeeded'): only restore-time verification catches the truncated
    object; recovery falls back one fence and completes."""
    part, make_engine = _mk(small_dataset, 1536)
    root = str(tmp_path / "obj")
    sink_dir = str(tmp_path / "analyzed")
    torn = TornStore(LocalStore(root), tear_at=1, keep_bytes=256)
    _phase1(make_engine, part, StoreCheckpointer(torn), sink_dir,
            max_batches=4)  # save @2 lands, save @4 lands TORN

    base = _counters()
    stats = _phase2(make_engine, part,
                    StoreCheckpointer(LocalStore(root)), sink_dir)
    after = _counters()

    assert stats["batches"] == 6
    assert after["truncated"] - base["truncated"] == 1
    assert after["fallbacks"] - base["fallbacks"] == 1
    _assert_lineage(sink_dir, part, 6)
    assert get_registry().counter(
        "rtfds_faults_injected_total", kind="torn_store_put").value >= 1


def test_flaky_store_hardening(tmp_path, small_dataset):
    """A flaky store (scripted PUT and GET failures) plus a mid-stream
    crash: every checkpoint op retries with original-typed errors, the
    post-crash restore succeeds through the flake, and NOTHING is
    counted corrupt — flakiness is not corruption."""
    part, make_engine = _mk(small_dataset, 1536)
    root = str(tmp_path / "obj")
    sink_dir = str(tmp_path / "analyzed")
    flaky = FlakyStore(LocalStore(root), fail_puts=(0,), fail_gets=(0,))
    ckpt = StoreCheckpointer(flaky, op_attempts=3)
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(3,))

    base = _counters()
    stats = run_with_recovery(
        make_engine, src, ckpt, sink=ParquetSink(sink_dir),
        max_restarts=3)
    after = _counters()

    assert stats["batches"] == 6
    assert stats["restarts"] == 1  # the scripted poll crash, recovered
    assert after["retried"] - base["retried"] >= 2  # PUT + GET retried
    for r in REASONS:
        assert after[r] == base[r]  # zero false corruption
    assert after["fallbacks"] == base["fallbacks"]
    _assert_lineage(sink_dir, part, 6)


def test_delta_chain_break(plane, tmp_path, small_dataset):
    """Delta lineage with a corrupted mid-chain entry: the tip's chain
    no longer resolves, both dead entries are quarantined, and restore
    falls back to the last valid FULL checkpoint — then the supervisor
    replays the gap and the stream completes."""
    part, make_engine = _mk(small_dataset, 2048)
    sink_dir = str(tmp_path / "analyzed")
    ckpt = plane.make(full_every=10)  # one full, then deltas
    _phase1(make_engine, part, ckpt, sink_dir, max_batches=6)
    names = [os.path.basename(p) for p in ckpt.list_checkpoints()]
    assert names == ["ckpt-0000000002.npz",
                     "ckpt-0000000004-delta.npz",
                     "ckpt-0000000006-delta.npz"]
    mid = ckpt.list_checkpoints()[1]
    with open(plane.file_of(mid), "wb") as fh:
        fh.write(b"garbage where a delta used to be")

    base = _counters()
    stats = _phase2(make_engine, part, plane.make(full_every=10),
                    sink_dir)
    after = _counters()

    assert stats["batches"] == 8
    # the tip (whose chain reads the garbage) AND the garbage entry
    # itself both count + quarantine; the full at batch 2 serves
    assert after["truncated"] - base["truncated"] == 2
    assert after["fallbacks"] - base["fallbacks"] == 1
    # both dead entries sit in the quarantine stash, and the lineage the
    # replay rebuilt (fresh full + chain) re-verifies clean end to end
    fresh = plane.make()
    assert sum(1 for n in fresh._backend.list_names()
               if n.startswith("stale-")) == 2
    report = fresh.verify_all()
    assert report and all(e["valid"] for e in report)
    _assert_lineage(sink_dir, part, 8)


def test_recovery_events_in_flight_record(tmp_path, small_dataset):
    """The flight record tells the fallback story: one
    ``checkpoint_fallback`` event per quarantined entry plus the final
    restored-fence event — the trail the ops dashboard renders."""
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        FlightRecorder,
        set_active_recorder,
    )

    part, make_engine = _mk(small_dataset, 1536)
    d = str(tmp_path / "ck")
    sink_dir = str(tmp_path / "analyzed")
    ckpt = Checkpointer(d)
    _phase1(make_engine, part, ckpt, sink_dir, max_batches=4)
    latest = ckpt.latest()
    with open(latest, "wb") as fh:
        fh.write(b"garbage")

    rec = FlightRecorder(str(tmp_path / "flight.jsonl"))
    set_active_recorder(rec)
    try:
        _phase2(make_engine, part, Checkpointer(d), sink_dir)
    finally:
        set_active_recorder(None)
        rec.close()
    _, records = FlightRecorder.read(str(tmp_path / "flight.jsonl"))
    evs = [r for r in records if r.get("kind") == "event"
           and r.get("event") == "checkpoint_fallback"]
    assert any(e.get("path") == os.path.basename(latest)
               and e.get("reason") == "truncated" for e in evs)
    assert any(e.get("restored") and e.get("skipped") == 1 for e in evs)


# -- cold tier (features.cold_store) ----------------------------------------


def _mk_cold(small_dataset, rows: int, cold_dir: str):
    """A cold-tier variant of :func:`_mk`: the hot tier holds a batch's
    keys but not the customers' history (256 slots kept at 64 occupied,
    120 customers), so every compaction demotes and recurring customers
    are promoted — before the step that scores them — every batch."""
    dcfg, _, _, txs = small_dataset
    part = txs.slice(slice(0, rows))
    cfg = Config(
        data=dcfg,
        features=FeatureConfig(
            key_mode="exact", customer_capacity=256,
            terminal_capacity=512, keydir_probes=16,
            cms_width=1 << 10, compact_every=1, cold_store=cold_dir,
            cold_demote_slots=128, cold_highwater=0.25),
        runtime=RuntimeConfig(checkpoint_every_batches=2,
                              batch_buckets=(256,), max_batch_rows=256),
    )
    params = init_logreg(15)

    def make_engine():
        import jax.numpy as jnp

        return ScoringEngine(
            cfg, kind="logreg", params=params,
            scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
        )

    return part, make_engine


def test_cold_crash_mid_promotion_resume_exactly_once(
        tmp_path, small_dataset):
    """SIGKILL between two checkpoints, emulated the way the
    kill-during-save cells do: the dying incarnation leaves a
    POST-checkpoint cold segment (demotions flushed after the last
    fence; promotions are never in flight across a batch boundary now:
    they land before the step they are for). Resume must prune the
    post-checkpoint segment from the cold store (replay regenerates
    those demotions — exactly-once across the tier boundary), survive a
    second scripted crash mid-replay, and complete with a gap/dup-free
    sink lineage, ZERO corruption counted and no key degraded."""
    cold_dir = str(tmp_path / "cold")
    part, make_engine = _mk_cold(small_dataset, 1536, cold_dir)
    d = str(tmp_path / "ck")
    sink_dir = str(tmp_path / "analyzed")
    eng = _phase1(make_engine, part, Checkpointer(d), sink_dir,
                  max_batches=4)
    assert eng._cold.keys_count > 0, "phase 1 must demote"
    man = Checkpointer(d).manifest(Checkpointer(d).latest())
    lineage = man["meta"]["cold_lineage"]
    assert lineage["segments"], "checkpoint must record cold lineage"

    assert not eng._degraded_keys  # promoted before scored, every one
    # the crash artifact: a post-checkpoint segment
    nb = eng.cfg.features.n_day_buckets
    eng._cold.append(
        "customer", np.array([999_999], np.uint32),
        np.full((1, nb), 20_000, np.int32),
        np.ones((1, nb), np.float32), np.ones((1, nb), np.float32),
        np.zeros((1, nb), np.float32))
    orphan_seq = eng._cold.flush()
    assert orphan_seq is not None

    base = _counters()
    src = FlakySource(ReplaySource(part, EPOCH0, batch_rows=256),
                      fail_at=(1,))  # a SECOND crash, mid-replay
    stats = run_with_recovery(
        make_engine, src, Checkpointer(d), sink=ParquetSink(sink_dir),
        max_restarts=3)
    after = _counters()

    assert stats["batches"] == 6 and stats["restarts"] == 1
    for r in REASONS:
        assert after[r] == base[r]  # cold replay is not corruption
    assert after["fallbacks"] == base["fallbacks"]
    _assert_lineage(sink_dir, part, 6)
    # the post-checkpoint segment was pruned at restore (its seq number
    # may be legitimately reused by post-restore demotions): the crash
    # incarnation's key appears in NO manifest and no index
    import json

    for n in os.listdir(cold_dir):
        if n.startswith("seg-") and n.endswith(".json"):
            man_keys = json.loads(
                open(os.path.join(cold_dir, n)).read())["keys"]
            assert 999_999 not in man_keys.get("customer", [])
    from real_time_fraud_detection_system_tpu.io.coldstore import ColdStore

    assert not ColdStore(cold_dir).contains("customer", 999_999)


def test_cold_torn_manifest_degrades_honestly(tmp_path, small_dataset):
    """A torn cold-segment manifest (half-written JSON): re-open
    quarantines it, restore warns that the checkpoint's lineage lists a
    now-missing segment, its keys serve from CMS honestly, and the
    resumed stream still completes gap/dup-free — cold-tier damage
    never becomes checkpoint corruption or a dead stream."""
    cold_dir = str(tmp_path / "cold")
    part, make_engine = _mk_cold(small_dataset, 1536, cold_dir)
    d = str(tmp_path / "ck")
    sink_dir = str(tmp_path / "analyzed")
    _phase1(make_engine, part, Checkpointer(d), sink_dir, max_batches=4)
    lineage = Checkpointer(d).manifest(
        Checkpointer(d).latest())["meta"]["cold_lineage"]
    assert lineage["segments"]
    seq = int(lineage["segments"][0]["seq"])
    man_file = os.path.join(cold_dir, f"seg-{seq:08d}.json")
    data = open(man_file, "rb").read()
    with open(man_file, "wb") as fh:
        fh.write(data[: len(data) // 2])

    base = _counters()
    stats = _phase2(make_engine, part, Checkpointer(d), sink_dir)
    after = _counters()

    assert stats["batches"] == 6
    for r in REASONS:
        assert after[r] == base[r]
    _assert_lineage(sink_dir, part, 6)
    names = os.listdir(cold_dir)
    assert f"quarantine-seg-{seq:08d}.json" in names
    assert f"seg-{seq:08d}.npz" not in names  # uncommitted blob swept


def test_cold_byte_flip_poisons_segment_not_stream(
        tmp_path, small_dataset):
    """Bit-flipped cold-segment blobs: CRC verification catches them at
    promotion-read time, the segments quarantine, the affected keys
    degrade to CMS (rows=None poison isolation — the promoter never
    wedges, the exact tier never ingests garbage) and the resumed
    stream completes gap/dup-free."""
    cold_dir = str(tmp_path / "cold")
    part, make_engine = _mk_cold(small_dataset, 1536, cold_dir)
    d = str(tmp_path / "ck")
    sink_dir = str(tmp_path / "analyzed")
    _phase1(make_engine, part, Checkpointer(d), sink_dir, max_batches=4)
    blobs = [n for n in os.listdir(cold_dir) if n.endswith(".npz")]
    assert blobs
    for n in blobs:
        f = os.path.join(cold_dir, n)
        data = open(f, "rb").read()
        with open(f, "r+b") as fh:
            fh.seek(len(data) // 2)
            fh.write(bytes([data[len(data) // 2] ^ 0xFF]))

    base = _counters()
    stats = _phase2(make_engine, part, Checkpointer(d), sink_dir)
    after = _counters()

    assert stats["batches"] == 6
    for r in REASONS:
        assert after[r] == base[r]  # cold damage ≠ checkpoint corruption
    _assert_lineage(sink_dir, part, 6)
    # at least one poisoned read fired during replay and quarantined
    assert any(n.startswith("quarantine-seg-")
               for n in os.listdir(cold_dir))
