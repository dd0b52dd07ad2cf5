"""`make overload-smoke` — the tier-1 overload-survival gate.

ONE scripted run under an injected traffic burst (a backlog far above
``overload.lag_high_rows``, the stand-in for sustained traffic above
capacity) must prove the whole ladder, every claim asserted from the
metrics registry and the flight record:

- the controller climbs rung-by-rung (1: optional work shed + sampled
  flight recording; 2: largest AOT bucket forced + alerts-only
  emission; 3: whole-batch deferral to the durable spill);
- when pressure subsides the ladder descends FULLY, replaying every
  deferred batch in order through the normal scoring path before live
  traffic resumes;
- no silent loss: ``scored == injected`` and ``shed == replayed`` at
  quiescence (``scored + deferred-pending == polled`` throughout), with
  gap/dup-free sink ``batch_index`` lineage;
- zero mid-stream recompiles across the full climb+descend cycle (the
  emission/batching switches are host-side only — every dispatch stays
  a signature from ``dispatch_inventory()``);
- final scores are BIT-identical to an unthrottled control run over the
  same rows (deferral is ordered and whole-batch, so the window/feature
  state cannot diverge).

Unit cells pin the hysteresis core (dwell counts, the anti-flap dead
band, action ordering on climb/descend), the rung-1 pause hooks, the
spill-cap replay-head behavior, and the ``/healthz`` overload block.
"""

import json
import os
import urllib.request
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    OverloadConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.io.sink import (
    ParquetSink,
    read_dead_letter,
)
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime import (
    LadderActions,
    OverloadController,
    ReplaySource,
    ScoringEngine,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (
    FlightRecorder,
    MetricsRegistry,
    MetricsServer,
    get_registry,
    set_active_recorder,
)

EPOCH0 = 1_743_465_600
N_ROWS = 6144          # 24 batches of 256: burst + drain + recovery
LAG_HIGH = 4000        # backlog >= this == pressure 1.0 (burst injected
                       # by starting with a 6144-row backlog)

_METRICS = {
    "climbs": ("rtfds_overload_transitions_total",
               {"direction": "climb"}),
    "descends": ("rtfds_overload_transitions_total",
                 {"direction": "descend"}),
    "shed": ("rtfds_shed_rows_total", {}),
    "replayed": ("rtfds_shed_replayed_rows_total", {}),
    "scored": ("rtfds_rows_total", {}),
    "recompiles": ("rtfds_xla_recompiles_total", {}),
}


def _snap() -> dict:
    reg = get_registry()
    out = {}
    for key, (name, labels) in _METRICS.items():
        m = reg.get(name, **labels)
        out[key] = float(m.value) if m is not None else 0.0
    return out


def _cfg(dcfg, tmp, enabled: bool, **overload_kw) -> Config:
    ok = dict(enabled=enabled, spill_path=str(tmp / "spill"),
              lag_high_rows=LAG_HIGH, climb_dwell_batches=2,
              descend_dwell_batches=2, recorder_sample_every=4)
    ok.update(overload_kw)
    return Config(
        data=dcfg,
        features=FeatureConfig(customer_capacity=256,
                               terminal_capacity=512, cms_width=1 << 10),
        # sink_queue_batches: the toy loop outruns its Parquet writer, and
        # a writer queue of the default 8 would fill and hold the ladder
        # up by its own pressure signal; the burst under test is the lag
        runtime=RuntimeConfig(batch_buckets=(256,), max_batch_rows=256,
                              precompile=True, autobatch=True,
                              sink_queue_batches=64,
                              overload=OverloadConfig(**ok)),
    )


def _engine(cfg) -> ScoringEngine:
    return ScoringEngine(
        cfg, kind="logreg", params=init_logreg(15),
        scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)))


@pytest.fixture(scope="module")
def overload_run(small_dataset, tmp_path_factory):
    """The scripted burst run plus the unthrottled control twin."""
    dcfg, _, _, txs = small_dataset
    part = txs.slice(slice(0, N_ROWS))
    tmp = tmp_path_factory.mktemp("overload_smoke")

    cfg = _cfg(dcfg, tmp, enabled=True)
    engine = _engine(cfg)
    recorder = FlightRecorder(str(tmp / "flight.jsonl"))
    set_active_recorder(recorder)
    base = _snap()
    try:
        stats = engine.run(ReplaySource(part, EPOCH0, batch_rows=256),
                           sink=ParquetSink(str(tmp / "analyzed")))
    finally:
        set_active_recorder(None)
        recorder.close()
    final = _snap()

    # Unthrottled control: identical rows, batches and model — the
    # ladder (and only the ladder) is the difference under test.
    c_engine = _engine(_cfg(dcfg, tmp, enabled=False))
    c_engine.run(ReplaySource(part, EPOCH0, batch_rows=256),
                 sink=ParquetSink(str(tmp / "analyzed_control")))

    records = [json.loads(line) for line in open(tmp / "flight.jsonl")
               if line.strip()]
    return SimpleNamespace(
        tmp=tmp, engine=engine, stats=stats,
        delta={k: final[k] - base[k] for k in final},
        out=ParquetSink(str(tmp / "analyzed")).read_all(),
        control=ParquetSink(str(tmp / "analyzed_control")).read_all(),
        batch_records=[r for r in records if r.get("kind") == "batch"],
        events=[r for r in records if r.get("kind") == "event"],
    )


def _events(run, name):
    return [e for e in run.events if e.get("event") == name]


class TestOverloadSmoke:
    def test_ladder_climbs_rung_by_rung(self, overload_run):
        climbs = _events(overload_run, "overload_climb")
        assert [e["rung"] for e in climbs] == [1, 2, 3]
        assert overload_run.delta["climbs"] == 3
        # climbs were driven by the injected burst (the lag signal)
        assert all(e.get("lag", 0) >= 1.0 for e in climbs)

    def test_ladder_descends_fully(self, overload_run):
        descends = _events(overload_run, "overload_descend")
        assert [e["rung"] for e in descends] == [2, 1, 0]
        assert overload_run.delta["descends"] == 3
        assert get_registry().get("rtfds_overload_rung").value == 0.0
        # every degrade reverted on the engine itself
        assert overload_run.engine._shed_features is False
        assert overload_run.engine.shadow_paused is False

    def test_rung3_sheds_and_replays_every_row(self, overload_run):
        d = overload_run.delta
        assert d["shed"] > 0, "the burst never reached rung 3"
        assert d["shed"] == d["replayed"]
        assert get_registry().get("rtfds_shed_pending_rows").value == 0.0
        shed_ev = _events(overload_run, "shed")
        replay_ev = _events(overload_run, "replay")
        assert sum(e["rows"] for e in shed_ev) == d["shed"]
        # replay is strictly FIFO: the spill sequence replays in order
        assert [e["seq"] for e in replay_ev] == \
            sorted(e["seq"] for e in shed_ev)

    def test_no_silent_loss_scored_equals_injected(self, overload_run):
        assert overload_run.delta["scored"] == N_ROWS
        assert overload_run.stats["rows"] == N_ROWS
        assert len(overload_run.out["tx_id"]) == N_ROWS

    def test_sink_lineage_gap_dup_free(self, overload_run):
        parts = sorted(
            f for f in os.listdir(overload_run.tmp / "analyzed")
            if f.startswith("part-") and f.endswith(".parquet"))
        idx = [int(f[len("part-"):-len(".parquet")]) for f in parts]
        assert idx == list(range(1, len(idx) + 1)), idx
        assert len(np.unique(overload_run.out["tx_id"])) == N_ROWS

    def test_zero_midstream_recompiles_across_cycle(self, overload_run):
        # the emission-mode and batching switches are host-side only:
        # every dispatch across climb+descend is a precompiled signature
        # from dispatch_inventory() (rtfds verify-device proves the
        # same inventory statically)
        assert overload_run.delta["recompiles"] == 0

    def test_scores_bit_identical_to_unthrottled_control(
            self, overload_run):
        a, b = overload_run.out, overload_run.control
        oa, ob = np.argsort(a["tx_id"]), np.argsort(b["tx_id"])
        assert np.array_equal(a["tx_id"][oa], b["tx_id"][ob])
        assert np.array_equal(a["prediction"][oa], b["prediction"][ob])

    def test_rung2_degraded_emission_engaged(self, overload_run):
        # alerts-only batches persist zero feature columns; the control
        # run's window counts are >= 1 for every row (the row itself)
        col = "customer_id_nb_tx_1day_window"
        assert int((overload_run.control[col] == 0).sum()) == 0
        assert int((overload_run.out[col] == 0).sum()) > 0

    def test_recorder_sampled_while_degraded(self, overload_run):
        # rung 1 thins batch records to every 4th; events always land
        assert len(overload_run.batch_records) < N_ROWS // 256
        assert len(_events(overload_run, "shed")) > 0

    def test_spill_is_durable_and_triageable(self, overload_run):
        rows = read_dead_letter(str(overload_run.tmp / "spill"))
        assert len(rows) == overload_run.delta["shed"]
        assert all(r["reason"] == "shed" for r in rows)
        spilled = {r["tx_id"] for r in rows}
        assert spilled <= set(overload_run.out["tx_id"].tolist())

    def test_invariant_ledger_balanced(self, overload_run):
        # re-derive the no-silent-loss ledger from the registry the way
        # the controller's invariant() does, at quiescence
        reg = get_registry()
        pending = reg.get("rtfds_shed_pending_rows").value
        assert pending == 0.0
        assert overload_run.delta["scored"] + pending == N_ROWS

    def test_healthz_degraded_while_rung_active(self, overload_run):
        # synthetic registry: rung 2 active, rows awaiting replay
        reg = MetricsRegistry()
        reg.gauge("rtfds_overload_rung").set(2)
        reg.gauge("rtfds_shed_pending_rows").set(512)
        reg.counter("rtfds_shed_rows_total").inc(768)
        reg.counter("rtfds_shed_replayed_rows_total").inc(256)
        reg.gauge("rtfds_source_lag_trend_rows_per_s").set(-120.5)
        server = MetricsServer(port=0, registry=reg).start()
        try:
            with urllib.request.urlopen(server.url + "/healthz",
                                        timeout=5) as r:
                assert r.status == 200  # degraded, not unhealthy
                body = json.loads(r.read())
        finally:
            server.stop()
        assert body["status"] == "degraded"
        ov = body["overload"]
        assert ov["rung"] == 2
        assert ov["shed_rows_pending_replay"] == 512
        assert ov["shed_rows"] == 768
        assert ov["replayed_rows"] == 256
        assert ov["lag_trend_rows_per_s"] == -120.5

    def test_healthz_ok_after_full_recovery(self, overload_run):
        reg = MetricsRegistry()
        reg.gauge("rtfds_overload_rung").set(0)
        reg.gauge("rtfds_shed_pending_rows").set(0)
        server = MetricsServer(port=0, registry=reg).start()
        try:
            ok, body = server.health()
        finally:
            server.stop()
        assert ok and body["status"] == "ok"
        assert body["overload"]["rung"] == 0


class _Gauge:
    def __init__(self, v=0.0):
        self.value = v


class _FakeRegistry(MetricsRegistry):
    """Real registry plus a scripted rtfds_source_lag_rows series."""

    def __init__(self):
        super().__init__()
        self.lag = _Gauge()

    def get(self, name, **labels):
        if name == "rtfds_source_lag_rows":
            return self.lag
        return super().get(name, **labels)


def _controller(lag0=0.0, actions=None, **overload_kw):
    ok = dict(enabled=True, spill_path="", lag_high_rows=1000,
              climb_dwell_batches=3, descend_dwell_batches=2)
    ok.update(overload_kw)
    rcfg = RuntimeConfig(overload=OverloadConfig(**ok))
    reg = _FakeRegistry()
    reg.lag.value = lag0
    ctl = OverloadController(rcfg, registry=reg, actions=actions)
    return ctl, reg


class TestLadderHysteresis:
    def test_climb_needs_full_dwell(self):
        ctl, reg = _controller(lag0=5000.0)
        ctl.observe_batch(256, 0.01)
        ctl.observe_batch(256, 0.01)
        assert ctl.rung == 0  # dwell is 3: two highs are not enough
        ctl.observe_batch(256, 0.01)
        assert ctl.rung == 1

    def test_dead_band_cannot_flap(self):
        # pressure between descend (0.6) and climb (1.0) thresholds:
        # streaks reset every observation, the ladder never moves
        ctl, reg = _controller(lag0=5000.0, climb_dwell_batches=1)
        ctl.observe_batch(256, 0.01)
        assert ctl.rung == 1
        reg.lag.value = 800.0  # 0.8: inside the hysteresis band
        for _ in range(50):
            ctl.observe_batch(256, 0.01)
        assert ctl.rung == 1  # neither climbed back nor descended

    def test_descend_needs_distinct_threshold_and_dwell(self):
        ctl, reg = _controller(lag0=5000.0, climb_dwell_batches=1,
                               descend_dwell_batches=3)
        ctl.observe_batch(256, 0.01)
        assert ctl.rung == 1
        reg.lag.value = 100.0  # 0.1: well under descend_pressure
        ctl.observe_batch(256, 0.01)
        ctl.observe_batch(256, 0.01)
        assert ctl.rung == 1
        ctl.observe_batch(256, 0.01)
        assert ctl.rung == 0

    def test_actions_apply_and_revert_in_ladder_order(self):
        calls = []
        acts = LadderActions(
            shed_optional=lambda on: calls.append(("shed", on)),
            degrade_emission=lambda on: calls.append(("emit", on)),
            force_max_batch=lambda on: calls.append(("batch", on)))
        ctl, reg = _controller(lag0=5000.0, climb_dwell_batches=1,
                               descend_dwell_batches=1, actions=acts)
        for _ in range(3):
            ctl.observe_batch(256, 0.01)
        assert ctl.rung == 3
        assert calls == [("shed", True), ("batch", True), ("emit", True)]
        calls.clear()
        reg.lag.value = 0.0
        for _ in range(3):
            ctl.observe_batch(256, 0.01)
        assert ctl.rung == 0
        # descent reverts in reverse order: emission before shadow/learn
        assert calls == [("emit", False), ("batch", False),
                         ("shed", False)]

    def test_spill_cap_replays_head_to_make_room(self):
        ctl, reg = _controller(lag0=5000.0, climb_dwell_batches=1,
                               max_deferred_batches=2)
        for _ in range(3):
            ctl.observe_batch(256, 0.01)
        assert ctl.rung == 3 and ctl.should_defer()
        cols = {"tx_id": np.arange(4, dtype=np.int64)}
        ctl.defer(cols, [0])
        assert not ctl.want_replay()  # under the cap: keep deferring
        ctl.defer(cols, [1])
        assert ctl.want_replay()      # at the cap: head must replay
        item = ctl.next_replay()
        assert item.seq == 0          # strictly FIFO
        assert ctl.should_defer()     # still rung 3: new polls defer
        ctl.note_replayed(item.rows)
        assert not ctl.want_replay()  # room again

    def test_stream_end_force_drains(self):
        ctl, reg = _controller(lag0=5000.0, climb_dwell_batches=1)
        for _ in range(3):
            ctl.observe_batch(256, 0.01)
        cols = {"tx_id": np.arange(4, dtype=np.int64)}
        ctl.defer(cols, [0])
        assert not ctl.want_replay()
        ctl.finish_stream()
        assert ctl.want_replay()
        item = ctl.next_replay()
        ctl.note_replayed(item.rows)
        assert ctl.rung == 2  # drain completion is the 3 -> 2 descent
        assert ctl.invariant()["shed_rows"] == \
            ctl.invariant()["replayed_rows"]


class _QuietAfterBurst:
    """A live-source shape: serves the burst, then idle (zero-row)
    polls for a while, then ends — the Kafka-on-a-quiet-topic pattern
    the idle-tick recovery path exists for."""

    def __init__(self, inner, idle_polls=40):
        self.inner = inner
        self.left = idle_polls
        self._empty = None

    def poll_batch(self):
        cols = self.inner.poll_batch()
        if cols is not None:
            self._empty = {k: v[:0] for k, v in cols.items()}
            return cols
        if self.left > 0 and self._empty is not None:
            self.left -= 1
            return dict(self._empty)
        return None

    @property
    def offsets(self):
        return self.inner.offsets

    def seek(self, offsets):
        self.inner.seek(offsets)


def test_quiet_source_still_descends_and_replays(small_dataset,
                                                 tmp_path):
    """Regression: a burst followed by SILENCE (idle zero-row polls,
    not source exhaustion) must still descend the ladder and replay the
    deferred backlog — the idle branch ticks the controller, so
    recovery does not wait for traffic that may never return."""
    dcfg, _, _, txs = small_dataset
    part = txs.slice(slice(0, 2048))
    cfg = _cfg(dcfg, tmp_path, enabled=True, lag_high_rows=10)
    engine = _engine(cfg)
    reg = get_registry()
    base = _snap()
    src = _QuietAfterBurst(ReplaySource(part, EPOCH0, batch_rows=256))
    engine.run(src, sink=None)
    d = {k: _snap()[k] - base[k] for k in base}
    assert d["shed"] > 0, "the burst never reached rung 3"
    # every deferred row replayed DURING the quiet window (the source
    # was still alive — this is the idle-tick path, not finish_stream)
    assert d["shed"] == d["replayed"]
    assert d["scored"] == 2048
    assert reg.get("rtfds_shed_pending_rows").value == 0.0
    assert reg.get("rtfds_overload_rung").value == 0.0
    assert d["descends"] == d["climbs"] == 3


class _CountingHeartbeat:
    def __init__(self):
        self.beats = 0

    def beat(self):
        self.beats += 1


def test_end_of_stream_drain_beats_heartbeat(small_dataset, tmp_path):
    """Regression: the force-drain replay loop at stream end must beat
    the watchdog per replayed batch — a large deferred backlog is a
    healthy drain, not a stall."""
    dcfg, _, _, txs = small_dataset
    part = txs.slice(slice(0, 2048))
    # lag_high tiny: pressure stays >= 1 to the very end, so the tail
    # of the stream defers and only the end-of-stream drain replays it
    cfg = _cfg(dcfg, tmp_path, enabled=True, lag_high_rows=10)
    engine = _engine(cfg)
    base = _snap()
    hb = _CountingHeartbeat()
    engine.run(ReplaySource(part, EPOCH0, batch_rows=256),
               heartbeat=hb)
    d = {k: _snap()[k] - base[k] for k in base}
    assert d["shed"] == d["replayed"] > 0
    assert d["scored"] == 2048
    # one beat per main-loop pass (8 polls + the None poll) PLUS one
    # per end-drain replay + its terminating check: strictly more beats
    # than loop passes proves the drain loop beats on its own
    polls = 2048 // 256 + 1
    replays = int(d["shed"] // 256)
    assert hb.beats >= polls + replays


def test_max_batches_cap_wins_over_replay(small_dataset, tmp_path):
    """A max_batches stop must NOT blow through its cap replaying the
    deferred queue: the cap wins, pending rows stay durably spilled,
    and state.offsets stays BEHIND them so a resumed run re-polls them
    (scored + deferred-pending == polled still balances)."""
    dcfg, _, _, txs = small_dataset
    part = txs.slice(slice(0, N_ROWS))
    cfg = _cfg(dcfg, tmp_path, enabled=True)
    engine = _engine(cfg)
    reg = get_registry()
    shed0 = _snap()["shed"]
    scored0 = _snap()["scored"]
    src = ReplaySource(part, EPOCH0, batch_rows=256)
    engine.run(src, max_batches=8)
    assert engine.state.batches_done == 8
    pending = reg.get("rtfds_shed_pending_rows").value
    assert pending > 0, "the cap landed before any deferral happened"
    d_shed = _snap()["shed"] - shed0
    d_scored = _snap()["scored"] - scored0
    # replayed rows were scored; never-replayed rows stay owed
    assert d_shed > d_shed - pending >= 0
    # offsets trail the deferred rows: a resume re-polls them
    consumed = engine.state.offsets[0] if engine.state.offsets else 0
    assert consumed <= d_scored
    # the spill still holds every deferred row durably
    rows = read_dead_letter(str(tmp_path / "spill"))
    assert len(rows) == d_shed


class _FakeLearning:
    """The pause-hook contract the rung-1 action drives."""

    def __init__(self):
        self.calls = []

    def attach(self, engine):
        self.calls.append("attach")

    def pause(self):
        self.calls.append("pause")

    def resume(self):
        self.calls.append("resume")

    def on_batch(self, engine):
        pass

    def note_external_swap(self, *a, **k):
        pass


def test_rung1_pauses_learning_and_resumes(small_dataset,
                                           tmp_path):
    """The rung-1 action drives the EXISTING pause hooks: learner
    training pauses on the climb and resumes on the descent, and the
    engine's shadow_paused flag gates dual-scoring meanwhile."""
    dcfg, _, _, txs = small_dataset
    part = txs.slice(slice(0, N_ROWS))
    cfg = _cfg(dcfg, tmp_path, enabled=True)
    engine = _engine(cfg)
    learning = _FakeLearning()
    engine.run(ReplaySource(part, EPOCH0, batch_rows=256),
               learning=learning)
    assert "pause" in learning.calls and "resume" in learning.calls
    assert learning.calls.index("pause") < learning.calls.index("resume")
    assert engine.shadow_paused is False  # restored on descent


def test_shadow_scoring_skipped_while_paused(small_dataset):
    """_emit_result must not hand rows to a paused shadow scorer (rung
    1 sheds exactly this optional work)."""
    dcfg, _, _, txs = small_dataset
    part = txs.slice(slice(0, 512))
    cfg = Config(
        data=dcfg,
        features=FeatureConfig(customer_capacity=256,
                               terminal_capacity=512, cms_width=1 << 10),
        runtime=RuntimeConfig(batch_buckets=(256,), max_batch_rows=256))
    engine = _engine(cfg)

    class _Shadow:
        def __init__(self):
            self.rows = 0

        def score_batch(self, tx_id, feats, probs):
            self.rows += len(tx_id)

    shadow = _Shadow()
    engine.set_shadow(shadow)
    engine.shadow_paused = True
    engine.run(ReplaySource(part, EPOCH0, batch_rows=256))
    assert shadow.rows == 0
    engine.shadow_paused = False
    engine.run(ReplaySource(part.slice(slice(0, 256)), EPOCH0,
                            batch_rows=256))
    assert shadow.rows == 256


def test_degraded_emission_refused_for_host_side_consumers(
        small_dataset):
    """set_degraded_emission must refuse (and leave serving unchanged)
    when a host-side consumer needs the feature rows."""
    dcfg, _, _, _ = small_dataset
    cfg = Config(
        data=dcfg,
        features=FeatureConfig(customer_capacity=256,
                               terminal_capacity=512, cms_width=1 << 10),
        runtime=RuntimeConfig(batch_buckets=(256,), max_batch_rows=256))
    engine = _engine(cfg)
    assert engine.set_degraded_emission(True) is True
    assert engine._emit_features_now() is False
    assert engine.set_degraded_emission(False) is True
    assert engine._emit_features_now() is True
    # a feature cache is a host-side consumer: degrade refused
    from real_time_fraud_detection_system_tpu.runtime import FeatureCache

    cached = ScoringEngine(
        cfg, kind="logreg", params=init_logreg(15),
        scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
        feature_cache=FeatureCache(capacity=1 << 10))
    assert cached.set_degraded_emission(True) is False
    assert cached._emit_features_now() is True
