"""Checkpoint format v2: verified manifests, corruption fallback, delta
chains, flaky-store hardening, crash hygiene.

The durable-state plane must trust NOTHING on restore: every array is
re-checksummed against the embedded manifest, structural compatibility is
checked against the restore template, delta chains verify every link, and
any mismatch quarantines the corrupt entry and falls back down the lineage
— asserted here from the metrics registry, never from prints.
"""

import json
import os
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.io.checkpoint import (
    Checkpointer,
    CorruptCheckpointError,
    StoreCheckpointer,
    make_checkpointer,
)
from real_time_fraud_detection_system_tpu.io.store import LocalStore
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime.engine import EngineState
from real_time_fraud_detection_system_tpu.runtime.faults import (
    FlakyStore,
    TornStore,
)
from real_time_fraud_detection_system_tpu.utils.metrics import get_registry


def mk_state(batches: int, n: int = 1024) -> EngineState:
    return EngineState(
        feature_state={"w": jnp.arange(float(n)) * (batches + 1),
                       "c": jnp.ones(64, jnp.int32) * batches},
        params=init_logreg(15),
        scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
        offsets=[batches, batches * 2],
        batches_done=batches,
        rows_done=batches * 100,
    )


def leaves_equal(a: EngineState, b: EngineState) -> None:
    import jax

    la = jax.tree_util.tree_leaves(
        (a.feature_state, a.params, a.scaler))
    lb = jax.tree_util.tree_leaves(
        (b.feature_state, b.params, b.scaler))
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def corrupt_base(reason: str):
    reg = get_registry()
    return reg.counter(
        "rtfds_checkpoint_corrupt_total",
        "checkpoints that failed restore verification, by reason",
        reason=reason).value


class TestManifestV2:
    def test_manifest_written_and_inspectable(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"))
        path = ck.save(mk_state(3))
        man = ck.manifest(path)
        assert man["format"] == 2
        assert man["kind"] == "full"
        assert man["incarnation"] == ck.incarnation
        assert man["batches_done"] == 3
        # a CRC per logical-state leaf, all of them stored inline
        assert set(man["stored"]) == set(man["crcs"])
        assert all(k.startswith(("fs_", "p_", "s_"))
                   for k in man["crcs"])
        assert man["base"] is None
        # the fingerprint matches the spec it claims to hash
        from real_time_fraud_detection_system_tpu.io.checkpoint import (
            _fingerprint,
        )

        assert man["fingerprint"] == _fingerprint(man["spec"])

    def test_verified_roundtrip(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"))
        ck.save(mk_state(1))
        ck.save(mk_state(2))
        out = ck.restore(mk_state(0))
        assert out is not None and out.batches_done == 2
        leaves_equal(out, mk_state(2))
        report = ck.verify_all()
        assert [e["valid"] for e in report] == [True, True]
        assert all(e["kind"] == "full" for e in report)

    def test_v1_checkpoint_still_restores(self, tmp_path):
        """Pre-manifest (v1) checkpoints written by older deployments
        restore in place — no manifest means no verification, exactly
        the historical trust level."""
        from real_time_fraud_detection_system_tpu.io.checkpoint import (
            write_state_npz,
        )

        d = tmp_path / "ck"
        d.mkdir()
        with open(d / "ckpt-0000000005.npz", "wb") as f:
            write_state_npz(f, mk_state(5))
        ck = Checkpointer(str(d))
        out = ck.restore(mk_state(0))
        assert out is not None and out.batches_done == 5
        leaves_equal(out, mk_state(5))
        report = ck.verify_all()
        assert report[0]["valid"] and report[0]["kind"] == "v1"


class TestCorruptionFallback:
    def test_byte_flip_quarantines_and_falls_back(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"))
        ck.save(mk_state(1))
        latest = ck.save(mk_state(2))
        base_ck = corrupt_base("checksum")
        base_fb = get_registry().counter(
            "rtfds_checkpoint_fallbacks_total").value
        with open(latest, "r+b") as f:
            data = f.read()
            f.seek(len(data) // 2)
            f.write(bytes([data[len(data) // 2] ^ 0xFF]))
        out = ck.restore(mk_state(0))
        # fell back down the lineage to the older valid checkpoint
        assert out is not None and out.batches_done == 1
        leaves_equal(out, mk_state(1))
        assert corrupt_base("checksum") - base_ck == 1
        assert get_registry().counter(
            "rtfds_checkpoint_fallbacks_total").value - base_fb == 1
        assert get_registry().gauge(
            "rtfds_checkpoint_serving_fallback").value == 1
        # corrupt bytes are QUARANTINED (forensics), not deleted
        stash = [f for f in os.listdir(tmp_path / "ck")
                 if f.startswith("stale-")]
        assert len(stash) == 1
        assert os.path.basename(latest) not in os.listdir(tmp_path / "ck")
        # the next save restores durable-plane health
        ck.save(out)
        assert get_registry().gauge(
            "rtfds_checkpoint_serving_fallback").value == 0

    def test_tampered_array_caught_by_manifest_crc(self, tmp_path):
        """A rewrite whose zip layer is self-consistent (valid npz, wrong
        content) is caught by OUR per-leaf CRCs, not the container's."""
        ck = Checkpointer(str(tmp_path / "ck"))
        ck.save(mk_state(1))
        latest = ck.save(mk_state(2))
        with np.load(latest, allow_pickle=False) as z:
            entries = {k: z[k] for k in z.files}
        w = np.array(entries["fs_1"], copy=True)
        w.flat[0] += 1.0  # plausible but wrong bytes
        entries["fs_1"] = w
        np.savez(latest, **entries)  # fresh, self-consistent zip
        base_ck = corrupt_base("checksum")
        out = ck.restore(mk_state(0))
        assert out is not None and out.batches_done == 1
        assert corrupt_base("checksum") - base_ck == 1

    def test_truncation_falls_back(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"))
        ck.save(mk_state(1))
        latest = ck.save(mk_state(2))
        base_tr = corrupt_base("truncated")
        data = open(latest, "rb").read()
        with open(latest, "wb") as f:
            f.write(data[: len(data) // 3])
        out = ck.restore(mk_state(0))
        assert out is not None and out.batches_done == 1
        assert corrupt_base("truncated") - base_tr == 1

    def test_incompatible_template_rejected(self, tmp_path):
        """A checkpoint whose feature-spec/shape contract disagrees with
        the restore template must be refused (reason=incompatible), not
        silently unflattened into the wrong leaves."""
        ck = Checkpointer(str(tmp_path / "ck"))
        ck.save(mk_state(1, n=1024))
        base_in = corrupt_base("incompatible")
        out = ck.restore(mk_state(0, n=512))  # narrower template
        assert out is None  # whole lineage incompatible -> fresh start
        assert corrupt_base("incompatible") - base_in == 1

    def test_all_corrupt_returns_none(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"))
        p1 = ck.save(mk_state(1))
        p2 = ck.save(mk_state(2))
        for p in (p1, p2):
            with open(p, "wb") as f:
                f.write(b"garbage")
        assert ck.restore(mk_state(0)) is None

    def test_corruption_stash_accumulates(self, tmp_path):
        """The corruption path must NOT clear earlier stashes (the
        fresh-start fence does): a fallback cascade keeps every corrupt
        file it stepped over."""
        ck = Checkpointer(str(tmp_path / "ck"))
        p1 = ck.save(mk_state(1))
        p2 = ck.save(mk_state(2))
        ck.save(mk_state(3))
        for p in (p1, p2):
            with open(p, "wb") as f:
                f.write(b"garbage" * 10)
        # explicit-path restore of the middle entry: the tip stays live
        out = ck.restore(mk_state(0), path=p2)
        assert out is None  # p2 and p1 both corrupt, nothing older
        stash = [f for f in os.listdir(tmp_path / "ck")
                 if f.startswith("stale-")]
        assert len(stash) == 2


class TestDeltaChains:
    def test_delta_restore_bit_identical_to_full(self, tmp_path):
        """restore(full@K + delta chain) must be leaf-exact vs a
        full-checkpoint restore of the same state."""
        ck_d = Checkpointer(str(tmp_path / "d"), full_every=3)
        ck_f = Checkpointer(str(tmp_path / "f"))  # always full
        for b in (1, 2, 3):
            st = mk_state(b)
            ck_d.save(st)
            ck_f.save(st)
        names = [os.path.basename(p) for p in ck_d.list_checkpoints()]
        assert names == ["ckpt-0000000001.npz",
                         "ckpt-0000000002-delta.npz",
                         "ckpt-0000000003-delta.npz"]
        # deltas carry only the churned leaves (params/scaler static)
        man = ck_d.manifest(ck_d.list_checkpoints()[-1])
        assert man["kind"] == "delta"
        assert set(man["stored"]) == {"fs_0", "fs_1"}  # c and w changed
        out_d = ck_d.restore(mk_state(0))
        out_f = ck_f.restore(mk_state(0))
        assert out_d.batches_done == out_f.batches_done == 3
        leaves_equal(out_d, out_f)
        leaves_equal(out_d, mk_state(3))

    def test_delta_bytes_bounded(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"), full_every=4)
        sizes = []
        for b in (1, 2, 3, 4):
            p = ck.save(mk_state(b))
            sizes.append(os.path.getsize(p))
        reg = get_registry()
        assert reg.gauge("rtfds_checkpoint_bytes", kind="delta").value > 0
        assert reg.gauge("rtfds_checkpoint_bytes", kind="full").value > 0
        # a delta (changed feature leaves only) is smaller than a full
        assert sizes[1] < sizes[0]
        assert sizes[2] < sizes[0]

    def test_broken_chain_link_falls_back_to_full(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"), full_every=3)
        for b in (1, 2, 3):
            ck.save(mk_state(b))
        full, mid_delta, tip_delta = ck.list_checkpoints()
        with open(mid_delta, "wb") as f:
            f.write(b"torn")  # the tip's base is gone
        base_fb = get_registry().counter(
            "rtfds_checkpoint_fallbacks_total").value
        out = ck.restore(mk_state(0))
        # tip's chain is broken AND the mid delta itself is corrupt:
        # both quarantined, the last valid FULL serves
        assert out is not None and out.batches_done == 1
        leaves_equal(out, mk_state(1))
        assert get_registry().counter(
            "rtfds_checkpoint_fallbacks_total").value - base_fb == 1
        assert [os.path.basename(p) for p in ck.list_checkpoints()] == [
            "ckpt-0000000001.npz"]

    def test_missing_base_is_truncated(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"), full_every=3)
        for b in (1, 2):
            ck.save(mk_state(b))
        full, delta = ck.list_checkpoints()
        os.remove(full)
        base_tr = corrupt_base("truncated")
        assert ck.restore(mk_state(0)) is None
        assert corrupt_base("truncated") - base_tr == 1

    def test_gc_keeps_base_of_live_deltas(self, tmp_path):
        """Retention must never delete a full that kept deltas compose
        from — the chain stays restorable as the lineage rolls."""
        ck = Checkpointer(str(tmp_path / "ck"), keep=2, full_every=4)
        for b in (1, 2, 3, 4):
            ck.save(mk_state(b))
        names = [os.path.basename(p) for p in ck.list_checkpoints()]
        # keep=2 keeps the two newest deltas PLUS their whole ancestor
        # chain (each delta bases on its predecessor, back to the full)
        assert names == ["ckpt-0000000001.npz",
                         "ckpt-0000000002-delta.npz",
                         "ckpt-0000000003-delta.npz",
                         "ckpt-0000000004-delta.npz"]
        out = ck.restore(mk_state(0))
        assert out is not None and out.batches_done == 4
        leaves_equal(out, mk_state(4))

    def test_same_step_resave_never_self_chains(self, tmp_path):
        """A second save at the SAME batch counter (clean-exit save on a
        checkpoint-cadence boundary) must not chain a delta to its own
        name — it falls back to a full overwrite."""
        ck = Checkpointer(str(tmp_path / "ck"), full_every=4)
        ck.save(mk_state(1))
        ck.save(mk_state(2))
        p = ck.save(mk_state(2))  # same step again
        # the delta name would equal its own base -> full fallback
        assert p.endswith("ckpt-0000000002.npz")
        assert ck.manifest(p)["kind"] == "full"
        out = ck.restore(mk_state(0))
        assert out is not None and out.batches_done == 2
        leaves_equal(out, mk_state(2))

    def test_fallback_invalidates_writer_delta_base(self, tmp_path):
        """When a writer's own fallback restore quarantines its last
        save, the next save must NOT chain a delta to the quarantined
        base (it no longer exists under its lineage name) — it is
        forced full, so every later delta stays restorable."""
        ck = Checkpointer(str(tmp_path / "ck"), full_every=10)
        ck.save(mk_state(1))
        tip = ck.save(mk_state(2))
        assert tip.endswith("-delta.npz")
        with open(tip, "r+b") as f:
            f.write(b"garbage")  # corrupt the writer's own delta base
        out = ck.restore(mk_state(0))  # quarantines tip, falls back
        assert out is not None and out.batches_done == 1
        p = ck.save(mk_state(3))
        assert ck.manifest(p)["kind"] == "full"
        out2 = ck.restore(mk_state(0))
        assert out2 is not None and out2.batches_done == 3
        leaves_equal(out2, mk_state(3))

    def test_shallow_verify_is_listing_only(self, tmp_path):
        """verify_all(deep=False) (the cheap `rtfds ckpt` listing) reads
        each entry once and misses a broken chain link; deep=True (the
        --verify preflight) catches it."""
        ck = Checkpointer(str(tmp_path / "ck"), full_every=3)
        for b in (1, 2):
            ck.save(mk_state(b))
        full, delta = ck.list_checkpoints()
        os.remove(full)
        shallow = {os.path.basename(e["path"]): e["valid"]
                   for e in ck.verify_all(deep=False)}
        assert shallow[os.path.basename(delta)] is True
        deep = {os.path.basename(e["path"]): e
                for e in ck.verify_all()}
        bad = deep[os.path.basename(delta)]
        assert bad["valid"] is False and bad["reason"] == "truncated"


class TestCrashHygiene:
    def test_orphan_tmp_swept_on_construction(self, tmp_path):
        d = tmp_path / "ck"
        ck = Checkpointer(str(d))
        ck.save(mk_state(1))
        orphan = d / "ckpt-0000000009.npz.tmp"
        orphan.write_bytes(b"half a checkpoint")
        ck2 = Checkpointer(str(d))  # restart sweeps the crash artifact
        assert not orphan.exists()
        assert [os.path.basename(p) for p in ck2.list_checkpoints()] == [
            "ckpt-0000000001.npz"]

    def test_tmp_never_listed(self, tmp_path):
        d = tmp_path / "ck"
        ck = Checkpointer(str(d))
        ck.save(mk_state(1))
        # planted AFTER construction: list_checkpoints must still skip it
        (d / "ckpt-0000000009.npz.tmp").write_bytes(b"x")
        assert all(".tmp" not in p for p in ck.list_checkpoints())
        assert "0000000009" not in (ck.latest() or "")


class TestStoreHardening:
    def test_flaky_put_and_get_retried(self, tmp_path):
        reg = get_registry()
        base = reg.counter("rtfds_retry_attempts_total",
                           outcome="retried").value
        store = FlakyStore(LocalStore(str(tmp_path / "obj")),
                           fail_puts=(0,), fail_gets=(0,))
        ck = StoreCheckpointer(store, op_attempts=3)
        ck.save(mk_state(1))  # first PUT fails, retry lands it
        out = ck.restore(mk_state(0))  # first GET fails, retry reads it
        assert out is not None and out.batches_done == 1
        leaves_equal(out, mk_state(1))
        assert reg.counter("rtfds_retry_attempts_total",
                           outcome="retried").value - base >= 2

    def test_exhausted_retries_propagate_original_type(self, tmp_path):
        store = FlakyStore(LocalStore(str(tmp_path / "obj")),
                           fail_puts=(0, 1, 2, 3))
        ck = StoreCheckpointer(store, op_attempts=2)
        with pytest.raises(ConnectionError, match="injected store PUT"):
            ck.save(mk_state(1))

    def test_missing_key_not_retried(self, tmp_path):
        """KeyError (missing object) is a real answer, not flakiness —
        it must propagate immediately without burning retry attempts."""
        reg = get_registry()
        base = reg.counter("rtfds_retry_attempts_total",
                           outcome="retried").value
        ck = StoreCheckpointer(LocalStore(str(tmp_path / "obj")),
                               op_attempts=3)
        assert ck.restore(mk_state(0)) is None  # empty lineage
        with pytest.raises(KeyError):
            ck._backend.read("ckpt-0000000099.npz")
        assert reg.counter("rtfds_retry_attempts_total",
                           outcome="retried").value == base

    def test_per_op_timeout_surfaces_hang_as_transient(self, tmp_path):
        import time as _time

        from real_time_fraud_detection_system_tpu.runtime.faults import (
            TransientError,
        )

        class HangingStore:
            def __init__(self, inner):
                self.inner = inner

            def get(self, key):
                _time.sleep(5.0)  # a wedged GET
                return self.inner.get(key)

            def __getattr__(self, name):
                return getattr(self.inner, name)

        inner = LocalStore(str(tmp_path / "obj"))
        ck0 = StoreCheckpointer(inner)
        ck0.save(mk_state(1))
        ck = StoreCheckpointer(HangingStore(inner), op_timeout_s=0.1,
                               op_attempts=2)
        t0 = _time.monotonic()
        with pytest.raises(TransientError, match="timed out"):
            ck._backend.read("ckpt-0000000001.npz")
        assert _time.monotonic() - t0 < 2.0  # never waits out the hang

    def test_torn_put_detected_and_fallback(self, tmp_path):
        """A silently-truncated PUT (torn write) reports success; only
        restore-time verification catches it — and falls back."""
        store = TornStore(LocalStore(str(tmp_path / "obj")), tear_at=1,
                          keep_bytes=128)
        ck = StoreCheckpointer(store)
        ck.save(mk_state(1))
        ck.save(mk_state(2))  # this PUT lands torn, "successfully"
        base_tr = corrupt_base("truncated")
        out = ck.restore(mk_state(0))
        assert out is not None and out.batches_done == 1
        leaves_equal(out, mk_state(1))
        assert corrupt_base("truncated") - base_tr == 1

    def test_store_delta_chain_roundtrip(self, tmp_path):
        ck = StoreCheckpointer(LocalStore(str(tmp_path / "obj")),
                               full_every=3)
        for b in (1, 2, 3):
            ck.save(mk_state(b))
        out = ck.restore(mk_state(0))
        assert out is not None and out.batches_done == 3
        leaves_equal(out, mk_state(3))
        report = ck.verify_all()
        assert [e["valid"] for e in report] == [True] * 3
        assert [e["kind"] for e in report] == ["full", "delta", "delta"]


class TestCkptCLI:
    """`rtfds ckpt` — the lineage triage/preflight tool."""

    def _lineage(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"), full_every=3)
        for b in (1, 2, 3):
            ck.save(mk_state(b))
        return ck

    def test_list_and_verify_clean(self, tmp_path, capsys):
        from real_time_fraud_detection_system_tpu.cli import main as cli_main

        self._lineage(tmp_path)
        assert cli_main(["ckpt", "--path", str(tmp_path / "ck")]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()]
        assert lines[0]["checkpoints"] == 3 and lines[0]["corrupt"] == 0
        assert [e["kind"] for e in lines[1:]] == ["full", "delta", "delta"]
        assert all(e["valid"] for e in lines[1:])
        assert all(e["size"] > 0 and e["age_s"] is not None
                   for e in lines[1:])
        assert cli_main(["ckpt", "--path", str(tmp_path / "ck"),
                         "--verify"]) == 0

    def test_verify_fails_on_corruption(self, tmp_path, capsys):
        from real_time_fraud_detection_system_tpu.cli import main as cli_main

        ck = self._lineage(tmp_path)
        latest = ck.list_checkpoints()[-1]
        with open(latest, "wb") as f:
            f.write(b"torn")
        assert cli_main(["ckpt", "--path", str(tmp_path / "ck"),
                         "--verify"]) == 1
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()]
        assert lines[0]["corrupt"] == 1
        bad = [e for e in lines[1:] if not e["valid"]]
        assert len(bad) == 1 and bad[0]["reason"] == "truncated"
        # verify is read-only: nothing was quarantined by the preflight
        assert len(ck.list_checkpoints()) == 3

    def test_inspect_dumps_manifest(self, tmp_path, capsys):
        from real_time_fraud_detection_system_tpu.cli import main as cli_main

        self._lineage(tmp_path)
        assert cli_main(["ckpt", "--path", str(tmp_path / "ck"),
                         "--inspect", "ckpt-0000000002-delta.npz"]) == 0
        man = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert man["kind"] == "delta"
        assert man["base"] == "ckpt-0000000001.npz"
        assert man["stored"] == ["fs_0", "fs_1"]
        assert cli_main(["ckpt", "--path", str(tmp_path / "ck"),
                         "--inspect", "nope.npz"]) == 2


def test_make_checkpointer_forwards_knobs(tmp_path):
    ck = make_checkpointer(str(tmp_path / "ck"), keep=5, full_every=4)
    assert isinstance(ck, Checkpointer)
    assert ck.keep == 5 and ck.full_every == 4


def test_corrupt_error_reasons_closed_set():
    with pytest.raises(AssertionError):
        CorruptCheckpointError("bogus")


# -- window tables on disk: [cap, NB], whatever the state holds in memory ----


def _window_engine_state(batches=2):
    """A small engine's state after a few batches (flat window columns)."""
    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine

    cfg = Config(
        features=FeatureConfig(customer_capacity=64, terminal_capacity=128),
        runtime=RuntimeConfig(batch_buckets=(64,), max_batch_rows=64))
    eng = ScoringEngine(cfg, kind="logreg", params=init_logreg(15),
                        scaler=Scaler(mean=np.zeros(15, np.float32),
                                      scale=np.ones(15, np.float32)))
    rng = np.random.default_rng(7)
    for b in range(batches):
        us = ((20_000 + b) * 86400 + np.arange(50) * 60).astype(
            np.int64) * 1_000_000
        eng.process_batch({
            "tx_id": np.arange(50, dtype=np.int64) + 1000 * b,
            "tx_datetime_us": us,
            "customer_id": rng.integers(0, 60, 50).astype(np.int64),
            "terminal_id": rng.integers(0, 120, 50).astype(np.int64),
            "tx_amount_cents": rng.integers(100, 90_000, 50).astype(
                np.int64),
            "kafka_ts_ms": us // 1000,
        })
    return eng.state


def _as_parent_held_it(feature_state):
    """The feature state as the commits before the flat layout held it —
    and so wrote it: each window table a NamedTuple of four ``[cap, NB]``
    arrays under the same field names (same pytree paths, same order)."""
    from collections import namedtuple

    tables = namedtuple("WindowState",
                        ["bucket_day", "count", "amount", "fraud"])
    return feature_state._replace(
        customer=tables(*feature_state.customer.tables()),
        terminal=tables(*feature_state.terminal.tables()))


@pytest.mark.parametrize("written_by", ["parent", "this_commit"])
def test_window_tables_are_cap_by_nb_on_disk(tmp_path, written_by):
    """A checkpoint whose window leaves are ``[cap, NB]``, as the parent
    commit writes them, restores into the flat state to the bit; this
    commit's own file has the same leaf names, shapes, dtypes and CRCs, so
    the parent restores it too."""
    import dataclasses as dc

    state = _window_engine_state()
    as_parent = dc.replace(
        state, feature_state=_as_parent_held_it(state.feature_state))
    ck = Checkpointer(str(tmp_path / written_by))
    man = ck.manifest(ck.save(
        as_parent if written_by == "parent" else state))
    nb = state.feature_state.customer.n_buckets
    assert [man["spec"][f"fs_{i}"][0] for i in range(8)] == (
        [[64, nb]] * 4 + [[128, nb]] * 4)
    other = Checkpointer(str(tmp_path / "other"))
    man_other = other.manifest(other.save(
        state if written_by == "parent" else as_parent))
    for key in ("spec", "crcs", "fingerprint", "stored"):
        assert man[key] == man_other[key], key

    template = _window_engine_state(batches=0)
    assert all(c.ndim == 1 for c in template.feature_state.terminal.columns())
    out = ck.restore(template)
    assert out is not None and out.batches_done == state.batches_done
    leaves_equal(out, state)
    assert out.feature_state.terminal.n_buckets == nb
    assert not np.array_equal(  # the batches did land in the tables
        np.asarray(out.feature_state.terminal.count),
        np.zeros(128 * nb, np.float32))


# -- the key width travels with the state (PR 41) --------------------------


def _exact_engine(key_bits, batches=3):
    """A small ``key_mode="exact"`` engine after a few batches of ids of
    card-number width in fold-twin pairs."""
    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine

    cfg = Config(
        features=FeatureConfig(customer_capacity=64, terminal_capacity=128,
                               cms_width=1 << 10, key_mode="exact",
                               keydir_probes=16, key_bits=key_bits),
        runtime=RuntimeConfig(batch_buckets=(64,), max_batch_rows=64))
    eng = ScoringEngine(cfg, kind="logreg", params=init_logreg(15),
                        scaler=Scaler(mean=np.zeros(15, np.float32),
                                      scale=np.ones(15, np.float32)))
    rng = np.random.default_rng(7)
    base = rng.integers(10 ** 15, 10 ** 16, 20, dtype=np.int64)
    ids = np.concatenate([base, base ^ 0x5 ^ (0x5 << 32)])  # fold twins
    for b in range(batches):
        us = ((20_000 + b) * 86400 + np.arange(50) * 60).astype(
            np.int64) * 1_000_000
        eng.process_batch({
            "tx_id": np.arange(50, dtype=np.int64) + 1000 * b,
            "tx_datetime_us": us,
            "customer_id": ids[rng.integers(0, 40, 50)],
            "terminal_id": ids[rng.integers(0, 40, 50)] + 1,
            "tx_amount_cents": rng.integers(100, 90_000, 50).astype(
                np.int64),
            "kafka_ts_ms": us // 1000,
        })
    return eng


def test_wide_state_saves_and_restores_with_its_whole_keys(tmp_path):
    """A ``key_bits=64`` state round-trips to the bit — the directories'
    two key-word leaves among the rest — and an engine restored from it serves the next batch as the engine that
    never stopped does."""
    eng = _exact_engine(64)
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(eng.state)
    fresh = _exact_engine(64, batches=0)
    out = ck.restore(fresh.state)
    assert out is not None and out.batches_done == 3
    leaves_equal(out, eng.state)
    kd = out.feature_state.customer_dir
    assert kd.keys_lo is not None and kd.keys_hi is not None
    live = np.asarray(kd.slots) >= 0
    assert (np.asarray(kd.keys_hi)[live] != 0).all()  # whole keys
    # the restored engine and the one that never stopped agree on what
    # comes next, fold twins and all
    rng = np.random.default_rng(99)
    us = ((20_003) * 86400 + np.arange(50) * 60).astype(
        np.int64) * 1_000_000
    kd0 = eng.state.feature_state.customer_dir
    stored = (np.asarray(kd0.keys_lo)[live].astype(np.uint64)
              | (np.asarray(kd0.keys_hi)[live].astype(np.uint64)
                 << np.uint64(32))).view(np.int64)
    cols = {"tx_id": np.arange(50, dtype=np.int64) + 9000,
            "tx_datetime_us": us,
            "customer_id": stored[rng.integers(0, len(stored), 50)],
            "terminal_id": stored[rng.integers(0, len(stored), 50)] + 1,
            "tx_amount_cents": np.full(50, 1234, np.int64),
            "kafka_ts_ms": us // 1000}
    a = eng.process_batch(dict(cols))
    b = fresh.process_batch(dict(cols))
    np.testing.assert_array_equal(a.features, b.features)
    np.testing.assert_array_equal(a.probs, b.probs)


def test_a_32_bit_checkpoint_restores_unchanged_and_says_no_width(tmp_path):
    """The default width writes what it always wrote: no ``key_bits`` in
    the meta (absent = 32), the parent's leaves — and restores into a
    32-bit engine to the bit."""
    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        _state_arrays,
    )

    eng = _exact_engine(32)
    arrays, meta = _state_arrays(eng.state)
    assert "key_bits" not in meta
    assert meta["n_fs"] == 23 and not [
        v for v in meta["fs_leaves"].values() if "keys_" in v]
    _, meta64 = _state_arrays(_exact_engine(64, batches=0).state)
    assert meta64["key_bits"] == 64 and meta64["n_fs"] == 27
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(eng.state)
    out = ck.restore(_exact_engine(32, batches=0).state)
    assert out is not None
    leaves_equal(out, eng.state)


@pytest.mark.parametrize("written,restored", [(32, 64), (64, 32)])
def test_a_width_mismatch_is_refused_by_name(tmp_path, written, restored):
    """A checkpoint of the other width is healthy and not this engine's:
    restore REFUSES, naming both widths — it neither quarantines the
    entry nor falls back down a lineage that shares the width."""
    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        CheckpointKeyWidthError,
    )

    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(_exact_engine(written, batches=1).state)
    ck.save(_exact_engine(written, batches=2).state)
    before = corrupt_base("incompatible")
    with pytest.raises(CheckpointKeyWidthError,
                       match=f"key_bits={written}.*key_bits={restored}"):
        ck.restore(_exact_engine(restored, batches=0).state)
    assert corrupt_base("incompatible") == before
    assert len(ck.list_checkpoints()) == 2  # nothing quarantined
    # and it still restores where it belongs
    assert ck.restore(
        _exact_engine(written, batches=0).state).batches_done == 2
