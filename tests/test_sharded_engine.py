"""Multi-chip streaming serve: the sharded ScoringEngine.

Round-1 coverage proved single sharded *steps*; these tests run the full
stream contract — source → partition → sharded step → sink → checkpoint →
feedback — on the 8-virtual-device CPU mesh, and pin parity with the
single-chip engine on the same stream (the reference's scaled-out serving
story, ``fraud_detection.py:204-211`` + SURVEY §2.3 items 1-2).
"""

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
    TrainConfig,
)
from real_time_fraud_detection_system_tpu.io import MemorySink
from real_time_fraud_detection_system_tpu.io.checkpoint import Checkpointer
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.metrics import roc_auc
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.parallel.step import (
    partition_batch_spill,
)
from real_time_fraud_detection_system_tpu.runtime import (
    ReplaySource,
    ScoringEngine,
    ShardedScoringEngine,
)

EPOCH0 = 1_743_465_600
N_DEV = 8


def _cfg(max_rows=1024):
    return Config(
        features=FeatureConfig(customer_capacity=512,
                               terminal_capacity=1024,
                               cms_width=1 << 10),
        train=TrainConfig(),
        runtime=RuntimeConfig(batch_buckets=(max_rows,),
                              max_batch_rows=max_rows,
                              trigger_seconds=0.0),
    )


def _model():
    import jax.numpy as jnp

    params = init_logreg(15)
    scaler = Scaler(mean=jnp.zeros(15), scale=jnp.ones(15))
    return params, scaler


class TestPartitionSpill:
    def _cols(self, cust):
        n = len(cust)
        return {
            "customer_id": np.asarray(cust, dtype=np.int64),
            "x": np.arange(n, dtype=np.int64),
        }

    def test_balanced_single_chunk(self):
        chunks = partition_batch_spill(self._cols(np.arange(16)), 4, 4)
        assert len(chunks) == 1
        out, rows, pos = chunks[0]
        assert out["__valid__"].all()
        np.testing.assert_array_equal(np.sort(rows), np.arange(16))
        # row i landed at pos[i]; payload column follows
        np.testing.assert_array_equal(out["x"][pos], rows)

    def test_hot_key_spills_densely(self):
        # every row hits shard 1: 10 rows / capacity 4 → owner-local chunk
        # of 4 + ONE dense routed chunk of 6 (not ceil(10/4)=3 chunks with
        # 1/n_dev occupancy)
        chunks = partition_batch_spill(self._cols(np.full(10, 5)), 4, 4)
        assert len(chunks) == 2
        sizes = [len(rows) for _, rows, _ in chunks]
        assert sizes == [4, 6]
        assert chunks[0][0]["__routed__"] is False
        assert chunks[1][0]["__routed__"] is True
        # the dense chunk spreads over ALL shards, not just the hot one
        _, _, pos1 = chunks[1]
        assert len(np.unique(pos1 // 4)) == 4
        # every input row appears exactly once across chunks
        all_rows = np.concatenate([rows for _, rows, _ in chunks])
        np.testing.assert_array_equal(np.sort(all_rows), np.arange(10))
        # payload stays row-aligned in every chunk
        for out, rows, pos in chunks:
            np.testing.assert_array_equal(out["x"][pos], rows)

    def test_balanced_stays_local(self):
        chunks = partition_batch_spill(self._cols(np.arange(16)), 4, 4)
        assert len(chunks) == 1
        assert chunks[0][0]["__routed__"] is False

    def test_empty_batch(self):
        chunks = partition_batch_spill(self._cols(np.array([])), 4, 4)
        assert len(chunks) == 1
        assert not chunks[0][0]["__valid__"].any()


def test_sharded_engine_matches_single_chip(small_dataset):
    """Same stream, same model: 8-device serve must reproduce the
    single-chip probabilities (and hence AUC) exactly."""
    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 6144))
    cfg = _cfg()
    params, scaler = _model()

    s1, s8 = MemorySink(), MemorySink()
    ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler).run(
        ReplaySource(part, EPOCH0, batch_rows=1024), sink=s1)
    eng = ShardedScoringEngine(cfg, kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV)
    stats = eng.run(ReplaySource(part, EPOCH0, batch_rows=1024), sink=s8)
    assert stats["batches"] > 1  # a real multi-batch stream, not one step

    out1, out8 = s1.concat(), s8.concat()
    a, b = np.argsort(out1["tx_id"]), np.argsort(out8["tx_id"])
    np.testing.assert_array_equal(out1["tx_id"][a], out8["tx_id"][b])
    np.testing.assert_allclose(out1["prediction"][a],
                               out8["prediction"][b], atol=1e-6)
    y = part.tx_fraud
    order = np.argsort(part.tx_id)
    auc1 = roc_auc(y[order], out1["prediction"][a])
    auc8 = roc_auc(y[order], out8["prediction"][b])
    assert auc1 == pytest.approx(auc8, abs=1e-9)


def test_sharded_engine_precompile_both_variants(small_dataset):
    """AOT precompile on the mesh builds BOTH step variants (local +
    routed spill) before the first poll, serves the stream without a
    single counted recompile or AOT fallback, and reproduces the
    plain-jit probabilities exactly."""
    import dataclasses

    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )

    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 4096))
    cfg = _cfg()
    cfg = cfg.replace(runtime=dataclasses.replace(cfg.runtime,
                                                  precompile=True))
    params, scaler = _model()

    reg = MetricsRegistry()
    eng = ShardedScoringEngine(cfg, kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV, metrics=reg)
    man = eng.precompile()
    assert man["variants"] == 2
    assert set(eng._aot) == {("sharded", False), ("sharded", True)}
    s8 = MemorySink()
    stats = eng.run(ReplaySource(part, EPOCH0, batch_rows=1024), sink=s8)
    assert stats["batches"] > 1
    assert reg.get("rtfds_xla_recompiles_total").value == 0
    assert reg.get("rtfds_aot_fallbacks_total").value == 0
    assert eng._aot  # still serving from the executables

    s1 = MemorySink()
    ref = ShardedScoringEngine(_cfg(), kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV)
    ref.run(ReplaySource(part, EPOCH0, batch_rows=1024), sink=s1)
    out1, out8 = s1.concat(), s8.concat()
    a, b = np.argsort(out1["tx_id"]), np.argsort(out8["tx_id"])
    np.testing.assert_array_equal(out1["tx_id"][a], out8["tx_id"][b])
    np.testing.assert_allclose(out1["prediction"][a],
                               out8["prediction"][b], atol=1e-6)


def test_sharded_engine_forest_kind(small_dataset):
    """The flagship forest scorer serves sharded too (replicated params,
    GEMM classify per shard)."""
    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 2048))
    cfg = _cfg()
    from real_time_fraud_detection_system_tpu.models.forest import fit_forest

    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (512, 15))
    yy = (x[:, 0] > 0.5).astype(np.int32)
    ens = fit_forest(x, yy, n_trees=10, max_depth=4)
    _, scaler = _model()

    s1, s8 = MemorySink(), MemorySink()
    ScoringEngine(cfg, kind="forest", params=ens, scaler=scaler).run(
        ReplaySource(part, EPOCH0, batch_rows=1024), sink=s1)
    ShardedScoringEngine(cfg, kind="forest", params=ens, scaler=scaler,
                         n_devices=N_DEV).run(
        ReplaySource(part, EPOCH0, batch_rows=1024), sink=s8)
    out1, out8 = s1.concat(), s8.concat()
    a, b = np.argsort(out1["tx_id"]), np.argsort(out8["tx_id"])
    np.testing.assert_allclose(out1["prediction"][a],
                               out8["prediction"][b], atol=1e-6)


def test_sharded_engine_absorbs_hot_key(small_dataset):
    """A single dominant customer (shard overflow) must spill into extra
    sub-steps, not kill the stream."""
    _, _, _, txs = small_dataset
    cfg = _cfg(max_rows=512)
    params, scaler = _model()
    n = 512
    cols = {
        "tx_id": np.arange(n, dtype=np.int64),
        "tx_datetime_us": (20200 * 86_400_000_000
                           + np.arange(n, dtype=np.int64) * 1_000_000),
        "customer_id": np.full(n, 3, dtype=np.int64),  # ONE hot customer
        "terminal_id": (np.arange(n) % 7).astype(np.int64),
        "tx_amount_cents": np.full(n, 1000, dtype=np.int64),
        "kafka_ts_ms": np.zeros(n, dtype=np.int64),
    }
    eng = ShardedScoringEngine(cfg, kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV)
    res = eng.process_batch(cols)
    assert len(res.probs) == n
    assert np.isfinite(res.probs).all()
    # the hot shard's load (512 rows) far exceeds rows_per_shard (128×2)
    assert eng.rows_per_shard < n


def test_sharded_engine_checkpoint_roundtrip(small_dataset, tmp_path):
    """Crash-resume: restore re-shards the state and the stream continues
    to the same outputs as an uninterrupted run."""
    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 3072))
    cfg = _cfg()
    params, scaler = _model()

    clean = MemorySink()
    ShardedScoringEngine(cfg, kind="logreg", params=params, scaler=scaler,
                         n_devices=N_DEV).run(
        ReplaySource(part, EPOCH0, batch_rows=1024), sink=clean)

    # Run 1: stop after 1 batch, checkpoint.
    ck = Checkpointer(str(tmp_path / "ck"))
    sink = MemorySink()
    eng = ShardedScoringEngine(cfg, kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV)
    src = ReplaySource(part, EPOCH0, batch_rows=1024)
    eng.run(src, sink=sink, checkpointer=ck, max_batches=1)
    ck.save(eng.state)

    # Run 2: fresh engine, restore, finish the stream.
    eng2 = ShardedScoringEngine(cfg, kind="logreg", params=params,
                                scaler=scaler, n_devices=N_DEV)
    assert ck.restore(eng2.state) is not None
    src2 = ReplaySource(part, EPOCH0, batch_rows=1024)
    src2.seek(eng2.state.offsets)
    eng2.run(src2, sink=sink)

    out, ref = sink.concat(), clean.concat()
    a, b = np.argsort(out["tx_id"]), np.argsort(ref["tx_id"])
    assert len(out["tx_id"]) == len(ref["tx_id"])
    np.testing.assert_allclose(out["prediction"][a], ref["prediction"][b],
                               atol=1e-6)


def test_sharded_engine_feedback_loop(small_dataset):
    """The labeled-feedback topic composes with the sharded engine: late
    fraud labels raise the (owner-partitioned) terminal risk windows."""
    from real_time_fraud_detection_system_tpu.core.batch import US_PER_DAY
    from real_time_fraud_detection_system_tpu.features.spec import (
        FEATURE_NAMES,
    )
    from real_time_fraud_detection_system_tpu.runtime import (
        FEEDBACK_TOPIC,
        FeatureCache,
        FeedbackLoop,
        InProcBroker,
    )
    from real_time_fraud_detection_system_tpu.runtime import (
        encode_feedback_envelopes,
    )

    cfg = _cfg(max_rows=512)
    params, scaler = _model()
    cache = FeatureCache(capacity=1 << 10)
    eng = ShardedScoringEngine(cfg, kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV,
                               feature_cache=cache)
    delay = cfg.features.delay_days
    day0 = 20200
    n = 8

    def cols_for(day, tx0):
        return {
            "tx_id": np.arange(tx0, tx0 + n, dtype=np.int64),
            "tx_datetime_us": np.full(n, day, np.int64) * US_PER_DAY + 1,
            "customer_id": np.arange(n, dtype=np.int64),
            "terminal_id": np.full(n, 7, dtype=np.int64),
            "tx_amount_cents": np.full(n, 1000, dtype=np.int64),
            "kafka_ts_ms": np.zeros(n, dtype=np.int64),
        }

    eng.process_batch(cols_for(day0, 0))
    broker = InProcBroker(2)
    broker.produce_many(
        FEEDBACK_TOPIC, [b""] * n,
        encode_feedback_envelopes(np.arange(n), np.ones(n, np.int64)),
    )
    assert FeedbackLoop(eng, broker).poll_and_apply() == n
    res = eng.process_batch(cols_for(day0 + delay + 1, 100))
    risk_cols = [i for i, nm in enumerate(FEATURE_NAMES) if "RISK" in nm]
    assert res.features[:, risk_cols].max() > 0
    # risk is a fraction: n frauds / n transactions at that terminal = 1
    assert res.features[:, risk_cols].max() <= 1.0 + 1e-6


def test_sharded_engine_online_sgd_updates_params(small_dataset):
    """In-band labels drive the psum'd online-SGD path: params move and
    stay replicated across the mesh."""
    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 1024))
    cfg = _cfg()
    params, scaler = _model()
    eng = ShardedScoringEngine(cfg, kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV,
                               online_lr=1e-2)
    w0 = np.asarray(params.w).copy()
    eng.run(ReplaySource(part, EPOCH0, batch_rows=1024, with_labels=True))
    w1 = np.asarray(eng.state.params.w)
    assert not np.allclose(w0, w1)  # learning happened
    assert np.isfinite(w1).all()


def test_sharded_engine_rejects_indivisible_capacity():
    cfg = Config(
        features=FeatureConfig(customer_capacity=4,  # pow2, but not /8
                               terminal_capacity=1024),
    )
    params, scaler = _model()
    with pytest.raises(ValueError, match="customer_capacity"):
        ShardedScoringEngine(cfg, kind="logreg", params=params,
                             scaler=scaler, n_devices=N_DEV)


def _cms_cfg(max_rows=1024):
    return Config(
        features=FeatureConfig(customer_capacity=512,
                               terminal_capacity=1024,
                               customer_source="cms",
                               cms_depth=4, cms_width=1 << 12),
        train=TrainConfig(),
        runtime=RuntimeConfig(batch_buckets=(max_rows,),
                              max_batch_rows=max_rows,
                              trigger_seconds=0.0),
    )


def test_sharded_cms_matches_single_chip(small_dataset):
    """BASELINE config 3 (CMS velocity) × config 5 (8-way serve) compose:
    with collision-free sketches both paths are exact, so the sharded
    probabilities must equal the single-chip ones."""
    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 4096))
    cfg = _cms_cfg()
    params, scaler = _model()

    s1, s8 = MemorySink(), MemorySink()
    ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler).run(
        ReplaySource(part, EPOCH0, batch_rows=1024), sink=s1)
    eng = ShardedScoringEngine(cfg, kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV)
    stats = eng.run(ReplaySource(part, EPOCH0, batch_rows=1024), sink=s8)
    assert stats["batches"] > 1

    out1, out8 = s1.concat(), s8.concat()
    a, b = np.argsort(out1["tx_id"]), np.argsort(out8["tx_id"])
    np.testing.assert_array_equal(out1["tx_id"][a], out8["tx_id"][b])
    np.testing.assert_allclose(out1["prediction"][a],
                               out8["prediction"][b], atol=1e-6)


def test_sharded_cms_estimates_are_upper_bounds(small_dataset):
    """Per-device sketches keep the CMS guarantee: estimated window counts
    never undercount the exact (dense-table) ones, even with narrow,
    collision-heavy sketches."""
    from real_time_fraud_detection_system_tpu.features.spec import (
        FEATURE_NAMES,
    )

    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 2048))
    params, scaler = _model()
    narrow = Config(
        features=FeatureConfig(customer_capacity=512,
                               terminal_capacity=1024,
                               customer_source="cms",
                               cms_depth=2, cms_width=1 << 6),
        runtime=RuntimeConfig(batch_buckets=(1024,), max_batch_rows=1024,
                              trigger_seconds=0.0),
    )
    exact_cfg = _cfg()

    s_cms, s_exact = MemorySink(), MemorySink()
    ShardedScoringEngine(narrow, kind="logreg", params=params,
                         scaler=scaler, n_devices=N_DEV).run(
        ReplaySource(part, EPOCH0, batch_rows=1024), sink=s_cms)
    ShardedScoringEngine(exact_cfg, kind="logreg", params=params,
                         scaler=scaler, n_devices=N_DEV).run(
        ReplaySource(part, EPOCH0, batch_rows=1024), sink=s_exact)

    cms_out, exact_out = s_cms.concat(), s_exact.concat()
    a = np.argsort(cms_out["tx_id"])
    b = np.argsort(exact_out["tx_id"])
    count_cols = [nm.lower() for nm in FEATURE_NAMES
                  if "CUSTOMER_ID_NB_TX" in nm]
    for col in count_cols:
        assert (cms_out[col][a] >= exact_out[col][b] - 1e-5).all(), col


def test_sharded_cms_hot_key_spill(small_dataset):
    """CMS mode survives a hot-key spill (one customer dominating)."""
    cfg = _cms_cfg(max_rows=512)
    params, scaler = _model()
    n = 512
    cols = {
        "tx_id": np.arange(n, dtype=np.int64),
        "tx_datetime_us": (20200 * 86_400_000_000
                           + np.arange(n, dtype=np.int64) * 1_000_000),
        "customer_id": np.full(n, 3, dtype=np.int64),
        "terminal_id": (np.arange(n) % 7).astype(np.int64),
        "tx_amount_cents": np.full(n, 1000, dtype=np.int64),
        "kafka_ts_ms": np.zeros(n, dtype=np.int64),
    }
    eng = ShardedScoringEngine(cfg, kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV)
    res = eng.process_batch(cols)
    assert len(res.probs) == n
    assert np.isfinite(res.probs).all()


def test_sharded_cms_checkpoint_roundtrip(small_dataset, tmp_path):
    """The owner-sharded sketch checkpoints and restores (re-sharded) to
    the same continuation outputs."""
    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 3072))
    cfg = _cms_cfg()
    params, scaler = _model()

    clean = MemorySink()
    ShardedScoringEngine(cfg, kind="logreg", params=params, scaler=scaler,
                         n_devices=N_DEV).run(
        ReplaySource(part, EPOCH0, batch_rows=1024), sink=clean)

    ck = Checkpointer(str(tmp_path / "ck"))
    sink = MemorySink()
    eng = ShardedScoringEngine(cfg, kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV)
    src = ReplaySource(part, EPOCH0, batch_rows=1024)
    eng.run(src, sink=sink, checkpointer=ck, max_batches=1)
    ck.save(eng.state)

    eng2 = ShardedScoringEngine(cfg, kind="logreg", params=params,
                                scaler=scaler, n_devices=N_DEV)
    assert ck.restore(eng2.state) is not None
    src2 = ReplaySource(part, EPOCH0, batch_rows=1024)
    src2.seek(eng2.state.offsets)
    eng2.run(src2, sink=sink)

    out, ref = sink.concat(), clean.concat()
    a, b = np.argsort(out["tx_id"]), np.argsort(ref["tx_id"])
    assert len(out["tx_id"]) == len(ref["tx_id"])
    np.testing.assert_allclose(out["prediction"][a], ref["prediction"][b],
                               atol=1e-6)


@pytest.mark.parametrize("source", ["table", "cms"])
def test_dense_spill_matches_single_chip(source):
    """The routed spill path (customers exchanged to owner like terminals)
    reproduces single-chip results exactly, for both the dense table and
    the CMS velocity source — chunk boundaries aligned so in-batch
    visibility semantics match."""
    from real_time_fraud_detection_system_tpu.core.batch import US_PER_DAY

    n, rps, n_dev = 128, 16, N_DEV
    rng = np.random.default_rng(3)
    cols = {
        "tx_id": np.arange(n, dtype=np.int64),
        "tx_datetime_us": np.full(n, 20200, np.int64) * US_PER_DAY
        + np.arange(n, dtype=np.int64) * 1_000_000,
        "customer_id": np.full(n, 3, dtype=np.int64),  # ONE hot customer
        "terminal_id": (np.arange(n) % 13).astype(np.int64),
        "tx_amount_cents": rng.integers(100, 30000, n).astype(np.int64),
        "kafka_ts_ms": np.zeros(n, dtype=np.int64),
    }
    fc = FeatureConfig(customer_capacity=512, terminal_capacity=1024,
                       customer_source=source,
                       cms_depth=4, cms_width=1 << 12)
    cfg = Config(features=fc,
                 runtime=RuntimeConfig(batch_buckets=(rps, n - rps),
                                       max_batch_rows=n,
                                       trigger_seconds=0.0))
    params, scaler = _model()

    # Single-chip reference, batched exactly like the sharded chunks:
    # chunk 0 = first rps rows (owner-local), spill chunk = the rest.
    single = ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler)
    r1 = single.process_batch({k: v[:rps] for k, v in cols.items()})
    r2 = single.process_batch({k: v[rps:] for k, v in cols.items()})
    probs_single = np.concatenate([r1.probs, r2.probs])

    eng = ShardedScoringEngine(cfg, kind="logreg", params=params,
                               scaler=scaler, n_devices=n_dev,
                               rows_per_shard=rps)
    res = eng.process_batch(cols)
    assert eng._sharded_step_routed is not None  # spill path exercised
    np.testing.assert_allclose(res.probs, probs_single, atol=1e-6)
    # rtol accommodates fp32 accumulation-order differences in the window
    # sums (the exchange changes reduction order, not semantics).
    np.testing.assert_allclose(res.features,
                               np.concatenate([r1.features, r2.features]),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("skew,bucket", [("balanced", 8), ("dense", 64),
                                         ("hot_terminal", 64)])
def test_exchange_capacity_branches_match_single_chip(skew, bucket):
    """The owner exchange's two capacity branches both reproduce
    single-chip results, and the rows pick the branch: balanced terminals
    ride the tight bucket (2x the balanced load of the BATCH spread over
    the mesh, whatever headroom the chunk's width adds), terminals that
    lean on one owner as a chunk dense to its width would, and a hot
    terminal, outgrow it and take the psum-uniform fallback to the
    always-correct full-capacity exchange."""
    from real_time_fraud_detection_system_tpu.core.batch import US_PER_DAY
    from real_time_fraud_detection_system_tpu.parallel.step import (
        tight_bucket,
    )
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )

    n, n_dev = 256, N_DEV
    # the engine's own chunk width, 2*ceil(256/8) = 64 slots a device for
    # the 32 rows it holds: the tight bucket is 2*ceil(32/8) = 8
    assert tight_bucket(64, n_dev, n) == 8
    rng = np.random.default_rng(5)
    i = np.arange(n)
    # row i stands on device i % 8 as that sender's k-th row; 40 = 0 mod 8
    k = i // n_dev
    spread = k + 40 * (i % n_dev)  # owner k % 8: 4 rows a (sender, owner)
    terminal = {
        "balanced": spread,
        # 3 rows in 8 of every sender pay owner 0's terminals: 12 a pair
        "dense": np.where(k % 8 < 3, 8 * (i % 11), spread),
        # all 32 rows of every sender pay one terminal
        "hot_terminal": np.full(n, 5),
    }[skew].astype(np.int64)
    cols = {
        "tx_id": np.arange(n, dtype=np.int64),
        "tx_datetime_us": np.full(n, 20200, np.int64) * US_PER_DAY
        + np.arange(n, dtype=np.int64) * 1_000_000,
        "customer_id": np.arange(n, dtype=np.int64),
        "terminal_id": terminal,
        "tx_amount_cents": rng.integers(100, 30000, n).astype(np.int64),
        "kafka_ts_ms": np.zeros(n, dtype=np.int64),
    }
    cfg = Config(
        features=FeatureConfig(customer_capacity=512,
                               terminal_capacity=1024),
        runtime=RuntimeConfig(batch_buckets=(n,), max_batch_rows=n,
                              trigger_seconds=0.0))
    params, scaler = _model()

    single = ScoringEngine(cfg, kind="logreg", params=params,
                           scaler=scaler).process_batch(cols)
    reg = MetricsRegistry()
    eng = ShardedScoringEngine(
        cfg, kind="logreg", params=params, scaler=scaler,
        n_devices=n_dev, metrics=reg)
    assert eng.rows_per_shard == 64
    res = eng.process_batch(cols)
    np.testing.assert_allclose(res.probs, single.probs, atol=1e-6)
    np.testing.assert_allclose(res.features, single.features,
                               rtol=1e-5, atol=1e-4)
    # which branch ran, from what the step handed the host
    snap = reg.snapshot()

    def count(name):
        return sum(r["value"] for r in snap[name]["series"])

    assert count("rtfds_exchange_lanes_total") == n_dev * n_dev * bucket
    assert count("rtfds_exchange_rows_total") == n
    assert count("rtfds_exchange_overflow_total") == (bucket == 64)


def test_sharded_alerts_only_same_probs_zero_features(small_dataset):
    """emit_features=False on the mesh: identical probabilities, zero
    feature payload (the per-shard feats D2H is skipped)."""
    import dataclasses

    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 4096))
    cfg = _cfg()
    params, scaler = _model()

    s_full, s_alerts = MemorySink(), MemorySink()
    ShardedScoringEngine(cfg, kind="logreg", params=params, scaler=scaler,
                         n_devices=N_DEV).run(
        ReplaySource(part, EPOCH0, batch_rows=1024), sink=s_full)
    acfg = cfg.replace(runtime=dataclasses.replace(
        cfg.runtime, emit_features=False))
    ShardedScoringEngine(acfg, kind="logreg", params=params, scaler=scaler,
                         n_devices=N_DEV).run(
        ReplaySource(part, EPOCH0, batch_rows=1024), sink=s_alerts)

    f, a = s_full.concat(), s_alerts.concat()
    np.testing.assert_array_equal(f["tx_id"], a["tx_id"])
    np.testing.assert_allclose(f["prediction"], a["prediction"],
                               atol=1e-6)
    assert np.all(a["customer_id_nb_tx_7day_window"] == 0)
    assert np.any(f["customer_id_nb_tx_7day_window"] != 0)


def test_reshard_feature_state_single_to_mesh_exact(small_dataset):
    """Elastic recovery for the window state: stream on ONE chip, reshard
    the state 1→8, continue on the mesh — the mesh's scores for the next
    batches must equal a single-chip engine that never stopped."""
    _, _, _, txs = small_dataset
    warm = txs.slice(slice(0, 3072))
    rest = txs.slice(slice(3072, 5120))
    cfg = _cfg()
    params, scaler = _model()

    # single-chip engine streams the warm prefix
    eng1 = ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler)
    eng1.run(ReplaySource(warm, EPOCH0, batch_rows=1024))

    # ... keeps going single-chip (the oracle)
    s_ref = MemorySink()
    eng1.run(ReplaySource(rest, EPOCH0, batch_rows=1024), sink=s_ref)

    # a second single-chip engine streams the same prefix, then its state
    # is elastically resharded onto the 8-device mesh
    eng2 = ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler)
    eng2.run(ReplaySource(warm, EPOCH0, batch_rows=1024))
    # engine-internal reshard: the engine converts the single-chip state
    # to its own mesh width (the layout count it trusts is its own)
    eng8 = ShardedScoringEngine(cfg, kind="logreg", params=params,
                                scaler=scaler, n_devices=N_DEV,
                                feature_state=eng2.state.feature_state,
                                feature_state_n_old=1)
    s_mesh = MemorySink()
    eng8.run(ReplaySource(rest, EPOCH0, batch_rows=1024), sink=s_mesh)

    a, b = s_ref.concat(), s_mesh.concat()
    oa, ob = np.argsort(a["tx_id"]), np.argsort(b["tx_id"])
    np.testing.assert_array_equal(a["tx_id"][oa], b["tx_id"][ob])
    np.testing.assert_allclose(a["prediction"][oa], b["prediction"][ob],
                               atol=1e-6)


def test_reshard_feature_state_roundtrip_identity(small_dataset):
    """1→8→4→1 must return the exact original tables."""
    import jax

    from real_time_fraud_detection_system_tpu.parallel import (
        reshard_feature_state,
    )

    _, _, _, txs = small_dataset
    cfg = _cfg()
    params, scaler = _model()
    eng = ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler)
    eng.run(ReplaySource(txs.slice(slice(0, 2048)), EPOCH0,
                         batch_rows=1024))
    st = eng.state.feature_state
    s8 = reshard_feature_state(st, cfg, 1, 8)
    s4 = reshard_feature_state(s8, cfg, 8, 4)
    s1 = reshard_feature_state(s4, cfg, 4, 1)
    for orig, back in zip(jax.tree.leaves(st), jax.tree.leaves(s1)):
        np.testing.assert_array_equal(np.asarray(orig), np.asarray(back))


def test_reshard_feature_state_rejects_bad_shapes():
    import pytest as _pytest

    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
    )
    from real_time_fraud_detection_system_tpu.features.online import (
        init_feature_state,
    )
    from real_time_fraud_detection_system_tpu.parallel import (
        reshard_feature_state,
    )

    cfg = Config(features=FeatureConfig(customer_capacity=256,
                                        terminal_capacity=512))
    st = init_feature_state(cfg.features)
    bad = Config(features=FeatureConfig(customer_capacity=512,
                                        terminal_capacity=512))
    with _pytest.raises(ValueError, match="rows"):
        reshard_feature_state(st, bad, 1, 2)
    hash_cfg = Config(features=FeatureConfig(
        customer_capacity=256, terminal_capacity=512, key_mode="hash"))
    with _pytest.raises(ValueError, match="direct"):
        reshard_feature_state(st, hash_cfg, 1, 2)


def test_reshard_feature_state_cms_upper_bound(small_dataset):
    """CMS reshard preserves the upper-bound guarantee: single→sharded
    replicates (warm start), sharded→single sums — estimates never
    shrink below the originals."""
    import dataclasses

    import jax

    from real_time_fraud_detection_system_tpu.parallel import (
        reshard_feature_state,
    )

    _, _, _, txs = small_dataset
    cfg = _cfg()
    cfg = cfg.replace(features=dataclasses.replace(
        cfg.features, customer_source="cms"))
    params, scaler = _model()
    eng = ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler)
    eng.run(ReplaySource(txs.slice(slice(0, 2048)), EPOCH0,
                         batch_rows=1024))
    st = eng.state.feature_state
    assert st.cms is not None
    s4 = reshard_feature_state(st, cfg, 1, 4)
    # deferred expansion: the CMS stays single-layout (warm-start base);
    # shard_feature_state replicates per-device at placement — never
    # n copies of a production-size sketch in host RAM
    assert np.asarray(s4.cms.slice_day).ndim == 1
    np.testing.assert_array_equal(np.asarray(s4.cms.count),
                                  np.asarray(st.cms.count))
    s1 = reshard_feature_state(s4, cfg, 4, 1)
    # the merge never undercounts (upper-bound guarantee preserved)
    assert np.all(np.asarray(s1.cms.count) >=
                  np.asarray(st.cms.count) - 1e-6)
    np.testing.assert_array_equal(np.asarray(s1.cms.slice_day),
                                  np.asarray(st.cms.slice_day))
    # window tables round-trip exactly regardless of the cms leg
    for a, b in zip(jax.tree.leaves(st.terminal),
                    jax.tree.leaves(s1.terminal)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reshard_cms_merge_tolerates_lagging_shards():
    """A quiet shard's day ring lags (slices only advance with traffic);
    the merge takes the newest stamp per slice and zeroes stale devices'
    contributions — exact-preserving, never a hard failure."""
    import dataclasses

    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
    )
    from real_time_fraud_detection_system_tpu.features.online import (
        FeatureState,
        init_feature_state,
    )
    from real_time_fraud_detection_system_tpu.ops.cms import CountMinSketch
    from real_time_fraud_detection_system_tpu.parallel import (
        reshard_feature_state,
    )

    cfg = Config(features=FeatureConfig(
        customer_capacity=256, terminal_capacity=256,
        customer_source="cms", cms_depth=2, cms_width=16,
        n_day_buckets=4))
    base = init_feature_state(cfg.features)
    nd, d, w = 4, 2, 16
    # device 0 saw day 10 in slice 10%4=2; device 1 is quiet and still
    # holds day 6 there (stale ring) with counts that must NOT merge in
    days = np.tile(np.array([8, 9, 10, 7], np.int32), (2, 1))
    days[1, 2] = 6
    count = np.zeros((2, nd, d, w), np.float32)
    count[0, 2] = 5.0  # fresh day-10 traffic on device 0
    count[1, 2] = 99.0  # stale day-6 leftovers on device 1
    count[:, 1] = 1.0  # day 9 agreed on both: additive
    cms = CountMinSketch(
        slice_day=np.asarray(days),
        count=np.asarray(count),
        amount=np.zeros_like(count),
    )
    st = FeatureState(customer=base.customer, terminal=base.terminal,
                      cms=cms)
    merged = reshard_feature_state(st, cfg, 2, 1).cms
    np.testing.assert_array_equal(np.asarray(merged.slice_day),
                                  [8, 9, 10, 7])
    got = np.asarray(merged.count)
    assert np.all(got[2] == 5.0)  # stale 99s zeroed, fresh 5s kept
    assert np.all(got[1] == 2.0)  # agreed slices sum across devices


def test_checkpoint_cross_width_restore_auto_reshards(small_dataset,
                                                      tmp_path):
    """A checkpoint records its layout width; restoring it into an engine
    of a DIFFERENT width converts the state automatically — single-chip
    checkpoint → 8-way mesh and back, byte-identical continuations."""
    _, _, _, txs = small_dataset
    warm = txs.slice(slice(0, 3072))
    rest = txs.slice(slice(3072, 5120))
    cfg = _cfg()
    params, scaler = _model()

    # single-chip run writes a checkpoint
    ck = Checkpointer(str(tmp_path / "ck"))
    eng1 = ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler)
    eng1.run(ReplaySource(warm, EPOCH0, batch_rows=1024), checkpointer=ck)
    ck.save(eng1.state)
    s_ref = MemorySink()
    eng1.run(ReplaySource(rest, EPOCH0, batch_rows=1024), sink=s_ref)

    # restore into an 8-way mesh engine: auto-resharded continuation
    eng8 = ShardedScoringEngine(cfg, kind="logreg", params=params,
                                scaler=scaler, n_devices=N_DEV)
    restored = ck.restore(eng8.state)
    assert restored is not None and restored.layout_devices == 1
    s_mesh = MemorySink()
    eng8.run(ReplaySource(rest, EPOCH0, batch_rows=1024), sink=s_mesh)
    assert eng8.state.layout_devices == N_DEV

    a, b = s_ref.concat(), s_mesh.concat()
    oa, ob = np.argsort(a["tx_id"]), np.argsort(b["tx_id"])
    np.testing.assert_allclose(a["prediction"][oa], b["prediction"][ob],
                               atol=1e-6)

    # and the mesh's checkpoint restores back into a single-chip engine
    ck8 = Checkpointer(str(tmp_path / "ck8"))
    ck8.save(eng8.state)
    eng1b = ScoringEngine(cfg, kind="logreg", params=params,
                          scaler=scaler)
    restored8 = ck8.restore(eng1b.state)
    assert restored8 is not None and restored8.layout_devices == N_DEV
    tail = txs.slice(slice(5120, 6144))
    s_tail_mesh = MemorySink()
    eng8.run(ReplaySource(tail, EPOCH0, batch_rows=1024),
             sink=s_tail_mesh)
    s_tail_one = MemorySink()
    eng1b.run(ReplaySource(tail, EPOCH0, batch_rows=1024),
              sink=s_tail_one)
    x, y = s_tail_mesh.concat(), s_tail_one.concat()
    ox, oy = np.argsort(x["tx_id"]), np.argsort(y["tx_id"])
    np.testing.assert_allclose(x["prediction"][ox], y["prediction"][oy],
                               atol=1e-6)


def test_state_feedback_after_cross_width_restore(small_dataset, tmp_path):
    """Delayed-label feedback right after a cross-width restore must land
    in the CORRECT terminals' windows (the scatter converts the layout
    first, like every scoring entry point)."""
    _, _, _, txs = small_dataset
    warm = txs.slice(slice(0, 2048))
    cfg = _cfg()
    params, scaler = _model()

    # mesh engine streams, checkpoints
    ck = Checkpointer(str(tmp_path / "ck"))
    eng8 = ShardedScoringEngine(cfg, kind="logreg", params=params,
                                scaler=scaler, n_devices=N_DEV)
    eng8.run(ReplaySource(warm, EPOCH0, batch_rows=1024))
    ck.save(eng8.state)

    # restore into single-chip, apply feedback BEFORE any scoring call
    term = np.asarray([5, 9, 5], dtype=np.int64)
    days = np.full(3, 20200, dtype=np.int32)
    labs = np.ones(3, dtype=np.int32)
    eng1 = ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler)
    assert ck.restore(eng1.state) is not None
    eng1.apply_state_feedback(term, days, labs)

    # oracle: mesh engine applying the same feedback natively
    eng8.apply_state_feedback(term, days, labs)
    # compare terminal fraud tables key-by-key via the layout permutation
    from real_time_fraud_detection_system_tpu.parallel.mesh import (
        _layout_perm,
    )

    cap = cfg.features.terminal_capacity
    p8 = _layout_perm(cap, N_DEV)
    a, b = (np.asarray(e.state.feature_state.terminal.tables()[3])
            for e in (eng1, eng8))  # the [cap, NB] fraud tables
    np.testing.assert_array_equal(a, b[p8])  # single[k] == mesh[perm[k]]


def test_sharded_emit_bf16_predictions_exact(small_dataset):
    """emit_dtype='bfloat16' over the mesh: predictions identical to the
    f32 sharded engine; emitted features within bf16 rounding."""
    import dataclasses

    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 2048))
    cfg = _cfg()
    params, scaler = _model()
    outs = {}
    for dtype in ("float32", "bfloat16"):
        c = dataclasses.replace(
            cfg, runtime=dataclasses.replace(cfg.runtime, emit_dtype=dtype))
        sink = MemorySink()
        ShardedScoringEngine(c, kind="logreg", params=params, scaler=scaler,
                             n_devices=N_DEV).run(
            ReplaySource(part, EPOCH0, batch_rows=1024), sink=sink)
        o = sink.concat()
        order = np.argsort(o["tx_id"])
        outs[dtype] = o, order
    f32, a = outs["float32"]
    bf, b = outs["bfloat16"]
    np.testing.assert_array_equal(f32["prediction"][a], bf["prediction"][b])
    fcols = [c for c in f32 if "window" in c]
    assert fcols
    for c in fcols:
        np.testing.assert_allclose(bf[c][b], f32[c][a], rtol=1e-2, atol=1e-2)


def test_commit_replicated_inspects_all_leaves():
    """A params tree with a MIXED committed/uncommitted leaf set (e.g. a
    hot reload that swapped one leaf to a host array) must be
    re-committed: deciding from the first device leaf alone would skip
    it and silently reintroduce the per-call retrace (ADVICE r5)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    params, scaler = _model()
    eng = ShardedScoringEngine(_cfg(), kind="logreg", params=params,
                               scaler=scaler, n_devices=N_DEV)
    rep = NamedSharding(eng.mesh, P())
    committed = eng.state.params
    assert isinstance(committed.w.sharding, NamedSharding)
    commits0 = eng._m_commits.value
    # already fully committed: a no-op
    eng._commit_replicated()
    assert eng._m_commits.value == commits0

    # first leaf committed, second leaf a fresh host/default-device array
    # — the old first-leaf-wins check skipped this tree
    mixed = committed._replace(b=jnp.zeros(()))
    assert isinstance(mixed.w.sharding, NamedSharding)
    assert not (isinstance(mixed.b.sharding, NamedSharding)
                and mixed.b.sharding.mesh.shape == eng.mesh.shape)
    eng.state.params = mixed
    eng._commit_replicated()
    assert eng._m_commits.value == commits0 + 1
    for leaf in jax.tree.leaves(eng.state.params):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.mesh.shape == eng.mesh.shape
    assert leaf.sharding == rep

    # a raw NUMPY leaf has no .sharding at all — it is a host leaf and
    # must trigger the commit too (skipping it would ride a host array
    # into every sharded step call)
    committed = eng.state.params
    eng.state.params = committed._replace(b=np.zeros(()))
    eng._commit_replicated()
    assert eng._m_commits.value == commits0 + 2
    for leaf in jax.tree.leaves(eng.state.params):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.mesh.shape == eng.mesh.shape
