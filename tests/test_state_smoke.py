"""`make state-smoke` — the tiered-feature-store tier-1 gate.

One scripted drive of the tentpole: a Zipf-skewed stream over a key
universe ≫ the hot-tier capacity must complete under ``--precompile``
with ZERO mid-stream recompiles (compaction and sketch-tier overflow
both active, both enumerated in ``dispatch_inventory``), exact tier
counters (``dense + cms == rows × keyspaces``, from the registry — not
prints), recency compaction actually firing AND reclaiming, and a
gap/dup-free sink ``batch_index`` lineage."""

import numpy as np

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.data.generator import (
    ZipfKeySampler,
    zipf_stream_cols,
)
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime.engine import (
    ScoringEngine,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (
    MetricsRegistry,
)

HOT_SLOTS = 64  # per table — the universe below is 100× bigger
UNIVERSE = 8_192
ROWS = 128
N_BATCHES = 12
COMPACT_EVERY = 3
DAY0 = 20200
# horizon = delay(7) + max window(30); jump days fast enough that early
# batches' slots are provably dead mid-stream
DAYS_PER_BATCH = 10


class _ZipfDriftSource:
    """Zipf keys with the day marching DAYS_PER_BATCH per batch, so the
    working set drifts and compaction has dead slots to reclaim."""

    def __init__(self, n_batches: int, rows: int):
        sampler = ZipfKeySampler(UNIVERSE, skew=1.2)
        rng = np.random.default_rng(17)
        self._batches = [
            zipf_stream_cols(rng, rows, sampler, n_terminals=UNIVERSE,
                             day=DAY0 + b * DAYS_PER_BATCH,
                             tx_id_start=b * rows)
            for b in range(n_batches)
        ]
        self._i = 0

    def poll_batch(self):
        if self._i >= len(self._batches):
            return None
        b = self._batches[self._i]
        self._i += 1
        return b

    @property
    def offsets(self):
        return [self._i]

    def seek(self, offsets):
        self._i = int(offsets[0])


class _LineageSink:
    def __init__(self):
        self.indices = []
        self.rows = 0

    def append(self, res):
        self.indices.append(res.batch_index)
        self.rows += len(res.tx_id)


def test_state_smoke():
    cfg = Config(
        features=FeatureConfig(
            key_mode="exact",
            customer_capacity=HOT_SLOTS,
            terminal_capacity=HOT_SLOTS,
            cms_width=1 << 12,
            compact_every=COMPACT_EVERY,
            state_hbm_budget_mb=16.0,
        ),
        runtime=RuntimeConfig(batch_buckets=(ROWS,), max_batch_rows=ROWS,
                              precompile=True),
    )
    reg = MetricsRegistry()
    eng = ScoringEngine(
        cfg, kind="logreg", params=init_logreg(15),
        scaler=Scaler(mean=np.zeros(15, np.float32),
                      scale=np.ones(15, np.float32)),
        metrics=reg)

    # the compact variant is enumerated and AOT-compiled with the buckets
    keys = [s.key for s in eng.dispatch_inventory()]
    assert ("compact",) in keys and ("step", 7, ROWS) in keys

    sink = _LineageSink()
    stats = eng.run(_ZipfDriftSource(N_BATCHES, ROWS), sink=sink)

    # 1) the stream completed, every row scored
    assert stats["rows"] == N_BATCHES * ROWS
    assert sink.rows == N_BATCHES * ROWS

    # 2) zero mid-stream recompiles under precompile, with compaction +
    #    overflow both active; no AOT fallbacks either
    rc = reg.get("rtfds_xla_recompiles_total")
    assert rc is None or rc.value == 0, "mid-stream recompile"
    assert reg.get("rtfds_aot_fallbacks_total").value == 0
    assert reg.get("rtfds_precompiled_steps_total").value == len(keys)

    # 3) exact tier accounting: every (row × keyspace) admission landed
    #    in exactly one tier, and the tiny hot tier provably overflowed
    dense = reg.get("rtfds_feature_tier_rows_total", tier="dense").value
    cms = reg.get("rtfds_feature_tier_rows_total", tier="cms").value
    assert dense + cms == N_BATCHES * ROWS * 2
    assert cms > 0, "a 100x-oversubscribed hot tier must overflow"
    assert dense > 0, "the hot set must still be served dense"

    # 4) compaction fired on its cadence and actually reclaimed (the day
    #    marches 10/batch past the 37-day horizon)
    reclaimed = reg.family_total("rtfds_feature_slots_reclaimed_total")
    assert reclaimed and reclaimed > 0, "compaction never reclaimed"
    occ = reg.get("rtfds_feature_slots_occupied", table="terminal")
    assert occ is not None and 0 <= occ.value <= HOT_SLOTS

    # 5) gap/dup-free sink lineage
    assert sink.indices == list(range(1, N_BATCHES + 1))

    # 6) /healthz surfaces the feature_state block with these numbers
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsServer,
    )

    _, body = MetricsServer(registry=reg).health()
    fs = body["feature_state"]
    assert fs["tier_rows"]["dense"] == dense
    assert fs["slots_reclaimed"] == reclaimed
    assert 0.0 < fs["dense_hit_rate"] < 1.0
    assert fs["state_bytes"] <= fs["budget_bytes"]


N_DEV = 4


def test_state_smoke_sharded():
    """The sharded cell: the SAME 100×-oversubscribed Zipf drive through
    the sharded engine (4 virtual devices) under --precompile — zero
    mid-stream recompiles with per-shard compaction + sketch overflow
    active, exact per-shard tier counters (shard sums == table totals ==
    rows × keyspaces), compaction reclaiming on EVERY shard, and
    gap/dup-free sink lineage."""
    from real_time_fraud_detection_system_tpu.runtime.sharded_engine \
        import ShardedScoringEngine

    cfg = Config(
        features=FeatureConfig(
            key_mode="exact",
            customer_capacity=HOT_SLOTS,
            terminal_capacity=HOT_SLOTS,
            cms_width=1 << 12,
            compact_every=COMPACT_EVERY,
            state_hbm_budget_mb=64.0,
        ),
        runtime=RuntimeConfig(batch_buckets=(ROWS,), max_batch_rows=ROWS,
                              precompile=True),
    )
    reg = MetricsRegistry()
    eng = ShardedScoringEngine(
        cfg, kind="logreg", params=init_logreg(15),
        scaler=Scaler(mean=np.zeros(15, np.float32),
                      scale=np.ones(15, np.float32)),
        n_devices=N_DEV, metrics=reg)

    # all three sharded variants are enumerated and AOT-compiled
    keys = [s.key for s in eng.dispatch_inventory()]
    assert ("compact",) in keys
    assert ("sharded", False) in keys and ("sharded", True) in keys

    sink = _LineageSink()
    stats = eng.run(_ZipfDriftSource(N_BATCHES, ROWS), sink=sink)

    # 1) the stream completed, every row scored
    assert stats["rows"] == N_BATCHES * ROWS
    assert sink.rows == N_BATCHES * ROWS

    # 2) zero mid-stream recompiles under precompile with per-shard
    #    compaction + overflow both active; no AOT fallbacks
    rc = reg.get("rtfds_xla_recompiles_total")
    assert rc is None or rc.value == 0, "mid-stream recompile"
    assert reg.get("rtfds_aot_fallbacks_total").value == 0
    assert reg.get("rtfds_precompiled_steps_total").value == len(keys)

    # 3) exact tier accounting, globally AND per shard
    dense = reg.get("rtfds_feature_tier_rows_total", tier="dense").value
    cms = reg.get("rtfds_feature_tier_rows_total", tier="cms").value
    assert dense + cms == N_BATCHES * ROWS * 2
    assert cms > 0 and dense > 0
    for tier, total in (("dense", dense), ("cms", cms)):
        per_shard = [
            reg.get("rtfds_feature_tier_rows_total", tier=tier,
                    shard=str(s)).value
            for s in range(N_DEV)
        ]
        assert sum(per_shard) == total, tier

    # 4) compaction reclaimed on EVERY shard (the day marches 10/batch
    #    past the 37-day horizon; Zipf keys spread over all residues)
    for s in range(N_DEV):
        rec = reg.get("rtfds_feature_slots_reclaimed_total",
                      table="terminal", shard=str(s))
        assert rec is not None and rec.value > 0, f"shard {s}"
        occ = reg.get("rtfds_feature_slots_occupied", table="terminal",
                      shard=str(s))
        assert occ is not None and 0 <= occ.value <= HOT_SLOTS // N_DEV

    # 5) gap/dup-free sink lineage
    assert sink.indices == list(range(1, N_BATCHES + 1))

    # 6) /healthz: global view unchanged + the per-shard breakdown
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsServer,
    )

    _, body = MetricsServer(registry=reg).health()
    fs = body["feature_state"]
    assert fs["tier_rows"]["dense"] == dense
    assert 0.0 < fs["dense_hit_rate"] < 1.0
    assert set(fs["slots_occupied_per_shard"]) == {
        str(s) for s in range(N_DEV)}
    assert fs["worst_shard"]["occupied"] == max(
        fs["slots_occupied_per_shard"].values())


class _ScriptedSource:
    """Deterministic pre-built batches (the cold cell needs exact
    eviction → re-touch choreography, not a Zipf draw)."""

    def __init__(self, batches):
        self._batches = list(batches)
        self._i = 0

    def poll_batch(self):
        if self._i >= len(self._batches):
            return None
        b = self._batches[self._i]
        self._i += 1
        return {k: v.copy() for k, v in b.items()}

    @property
    def offsets(self):
        return [self._i]

    def seek(self, offsets):
        self._i = int(offsets[0])


def _cold_cols(cust, term, day):
    cust = np.asarray(cust, np.int64)
    term = np.asarray(term, np.int64)
    n = len(cust)
    us = (day * 86400 + np.arange(n) % 86400).astype(np.int64) * 1_000_000
    return {
        "tx_id": np.arange(n, dtype=np.int64),
        "tx_datetime_us": us,
        "customer_id": cust,
        "terminal_id": term,
        "tx_amount_cents": np.full(n, 1234, np.int64),
        "kafka_ts_ms": us // 1000,
    }


def test_state_smoke_cold(tmp_path):
    """The cold-tier cell: an oversubscribed hot tier demotes under
    pressure, evicted keys are forcibly re-touched (promoted before
    the step that scores them), and the promotion traffic is EXACT —
    counters equal the host-computed cold∩ping intersection, with the
    ``("promote", table, width)`` signatures in the precompiled
    inventory and zero mid-stream recompiles."""
    from real_time_fraud_detection_system_tpu.core.batch import fold_key

    cfg = Config(
        features=FeatureConfig(
            key_mode="exact",
            customer_capacity=128,
            terminal_capacity=128,
            cms_width=1 << 12,
            compact_every=2,
            cold_store=str(tmp_path / "cold"),
            cold_demote_slots=16,
            cold_highwater=0.25,
        ),
        runtime=RuntimeConfig(batch_buckets=(64,), max_batch_rows=64,
                              precompile=True),
    )
    reg = MetricsRegistry()
    eng = ScoringEngine(
        cfg, kind="logreg", params=init_logreg(15),
        scaler=Scaler(mean=np.zeros(15, np.float32),
                      scale=np.ones(15, np.float32)),
        metrics=reg)

    # the promote variant joins compact in the precompiled inventory
    keys = [s.key for s in eng.dispatch_inventory()]
    assert ("compact",) in keys and ("promote", "customer", 64) in keys
    a = np.arange(0, 48)
    b = np.arange(1000, 1032)
    demote_phase = [
        _cold_cols(a, a + 10000, DAY0),
        _cold_cols(a, a + 10000, DAY0),
        _cold_cols(b, b + 10000, DAY0 + 2),
        _cold_cols(b, b + 10000, DAY0 + 3),
        _cold_cols(b, b + 10000, DAY0 + 4),
    ]
    sink = _LineageSink()
    stats1 = eng.run(_ScriptedSource(demote_phase), sink=sink)
    assert stats1["batches"] == len(demote_phase)
    assert reg.get("rtfds_feature_cold_demotions_total").value > 0
    assert reg.get("rtfds_feature_cold_keys").value > 0

    # host-computed ground truth: which pinged keys are actually cold
    expected = 0
    ping_c, ping_t = a[:16], a[:16] + 10000
    cold_row = np.zeros(16, bool)  # rows with a cold customer or terminal
    for table, ids in (("customer", ping_c), ("terminal", ping_t)):
        snap = eng._cold.index_snapshot(table)
        folded = fold_key(np.asarray(ids))
        expected += int(np.isin(folded, snap).sum())
        cold_row |= np.isin(folded, snap)
    assert expected > 0, "the ping must hit demoted keys"

    # ping: evicted keys return — promoted ahead of the ping's own step
    stats2 = eng.run(
        _ScriptedSource([_cold_cols(ping_c, ping_t, DAY0 + 5)]),
        sink=sink)
    assert stats2["batches"] == 1

    # promotion traffic is EXACT: every cold∩ping key was promoted
    # exactly once, before its row was scored, and none served degraded
    assert reg.get(
        "rtfds_feature_cold_promotions_total").value == expected
    assert stats2["exactness_degraded_keys"] == 0
    assert reg.get(
        "rtfds_feature_cold_rows_total").value == cold_row.sum()
    assert reg.get(
        "rtfds_feature_cold_promote_lanes_total").value == 2 * 64
    landed = reg.get("rtfds_phase_seconds", phase="cold_append")
    assert landed is not None and landed.count > 0

    # zero mid-stream recompiles / AOT fallbacks across BOTH runs
    rc = reg.get("rtfds_xla_recompiles_total")
    assert rc is None or rc.value == 0, "mid-stream recompile"
    assert reg.get("rtfds_aot_fallbacks_total").value == 0
    assert reg.get("rtfds_precompiled_steps_total").value == len(keys)

    # gap/dup-free sink lineage across the demote + ping runs
    assert sink.indices == list(range(1, len(demote_phase) + 2))

    # /healthz surfaces the cold block with these numbers
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsServer,
    )

    _, body = MetricsServer(registry=reg).health()
    cold = body["feature_state"]["cold"]
    assert cold["keys"] == reg.get("rtfds_feature_cold_keys").value
    assert cold["promotions"] == expected
    assert cold["demotions"] == reg.get(
        "rtfds_feature_cold_demotions_total").value
    assert cold["rows"] == cold_row.sum()
    assert cold["promote_lanes"] == 2 * 64
