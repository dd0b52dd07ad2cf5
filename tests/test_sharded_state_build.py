"""The sharded engine builds its window state sharded, and says what the
mesh adds to a batch.

A mesh exists to hold more state than one chip can (the four-chip
benchmark deployment: 2^24 + 2^25 slots, 32.2 GB, on four 16 GB chips), so
no step of the engine's construction may put a whole column on one device.
On four virtual CPU devices, at toy sizes:

- a fresh ``ShardedScoringEngine`` (direct, hash, exact) never calls the
  unsharded builder, and every window column is born with the mesh's
  sharding, one ``capacity / n_dev · NB`` slice a device;
- that state equals ``shard_feature_state(init_feature_state(...))`` leaf
  for leaf, bit for bit, and placing a placed state copies nothing;
- the four-device engine agrees with the benchmark's plain reference
  (``benchmark/reference.py`` + the plain classifier of
  ``benchmark/models/forest.py``) under the benchmark's own limits, with
  the exchange in its capacity branch, in its overflow branch, and with a
  routed spill chunk;
- a checkpoint written at width 4 restores at width 4 and at width 1;
- the per-run counters (chunks, slots, valid rows, fullest shard, exchange
  overflows) and the ``partition`` / ``assemble`` phases read what the
  batch implies.
"""

import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.core.batch import US_PER_DAY
from real_time_fraud_detection_system_tpu.features import online
from real_time_fraud_detection_system_tpu.features.online import (
    init_feature_state,
)
from real_time_fraud_detection_system_tpu.io import MemorySink
from real_time_fraud_detection_system_tpu.io.checkpoint import Checkpointer
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.ops.windows import COLUMNS
from real_time_fraud_detection_system_tpu.parallel.mesh import (
    init_sharded_feature_state,
    make_mesh,
    shard_feature_state,
)
from real_time_fraud_detection_system_tpu.parallel.step import (
    partition_batch_spill,
)
from real_time_fraud_detection_system_tpu.runtime import (
    ReplaySource,
    ScoringEngine,
    ShardedScoringEngine,
    engine as engine_module,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (
    MetricsRegistry,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EPOCH0 = 1_743_465_600
N_DEV = 4
MODES = {
    "direct": {"key_mode": "direct"},
    "hash": {"key_mode": "hash"},
    "exact": {"key_mode": "exact"},
    "cms": {"key_mode": "hash", "customer_source": "cms"},
}


def _fcfg(mode, customers=512, terminals=1024):
    return FeatureConfig(customer_capacity=customers,
                         terminal_capacity=terminals, cms_width=1 << 10,
                         **MODES[mode])


def _logreg():
    return init_logreg(15), Scaler(mean=np.zeros(15, np.float32),
                                   scale=np.ones(15, np.float32))


def _engine(cfg, n_dev=N_DEV, registry=None, **kw):
    params, scaler = _logreg()
    return ShardedScoringEngine(
        cfg, kind="logreg", params=params, scaler=scaler, n_devices=n_dev,
        metrics=registry or MetricsRegistry(), **kw)


def _cfg(mode="direct", rows=256):
    return Config(features=_fcfg(mode),
                  runtime=RuntimeConfig(batch_buckets=(rows,),
                                        max_batch_rows=rows,
                                        trigger_seconds=0.0))


# -- (1) never a whole column on one device ----------------------------------


@pytest.mark.parametrize("mode", ["direct", "hash", "exact"])
def test_fresh_sharded_engine_never_holds_a_whole_column(mode, monkeypatch):
    def unsharded(*a, **k):
        raise AssertionError("the base engine built a single-device state")

    real_init = online.init_window_state

    def sharded_only(capacity, n_buckets, sharding=None):
        assert sharding is not None, "a window table built on one device"
        return real_init(capacity, n_buckets, sharding)

    monkeypatch.setattr(engine_module, "init_feature_state", unsharded)
    monkeypatch.setattr(online, "init_window_state", sharded_only)
    cfg = _cfg(mode)
    eng = _engine(cfg)
    nb = cfg.features.n_day_buckets
    devices = set(eng.mesh.devices.flat)
    for table, cap in (("customer", cfg.features.customer_capacity),
                       ("terminal", cfg.features.terminal_capacity)):
        ws = getattr(eng.state.feature_state, table)
        for name in COLUMNS:
            col = getattr(ws, name)
            assert isinstance(col.sharding, NamedSharding)
            assert col.sharding.mesh.shape == eng.mesh.shape
            assert col.shape == (cap * nb,)
            shards = col.addressable_shards
            assert {s.device for s in shards} == devices
            assert all(s.data.shape == (cap // N_DEV * nb,)
                       for s in shards), (table, name)


# -- (2) the same state, bit for bit -----------------------------------------


@pytest.mark.parametrize("mode", sorted(MODES))
def test_sharded_first_state_equals_the_placed_unsharded_state(mode):
    fcfg = _fcfg(mode)
    mesh = make_mesh(N_DEV)
    born = init_sharded_feature_state(fcfg, mesh)
    placed = shard_feature_state(
        init_feature_state(fcfg, n_shards=N_DEV), mesh)
    assert jax.tree.structure(born) == jax.tree.structure(placed)
    for a, b in zip(jax.tree.leaves(born), jax.tree.leaves(placed)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.sharding == b.sharding
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_placing_a_placed_state_copies_nothing():
    mesh = make_mesh(N_DEV)
    born = init_sharded_feature_state(_fcfg("exact"), mesh)
    again = shard_feature_state(born, mesh)
    for a, b in zip(jax.tree.leaves(born), jax.tree.leaves(again)):
        assert [s.data.unsafe_buffer_pointer()
                for s in a.addressable_shards] == [
            s.data.unsafe_buffer_pointer() for s in b.addressable_shards]


def test_width_one_mesh_takes_the_same_path(monkeypatch):
    monkeypatch.setattr(
        engine_module, "init_feature_state",
        lambda *a, **k: pytest.fail("single-device builder called"))
    eng = _engine(_cfg("direct"), n_dev=1)
    res = eng.process_batch(_day_cols(64, 20_200, np.random.default_rng(1)))
    assert len(res.probs) == 64 and np.isfinite(res.probs).all()


# -- (3) four devices against the plain reference ----------------------------


def _day_cols(n, day, rng, hot_customer=0.0, hot_terminal=0.0,
              customers=512, terminals=1024, tx0=0):
    cust = rng.integers(0, customers, n)
    term = rng.integers(0, terminals, n)
    cust[rng.random(n) < hot_customer] = 5  # owner 1
    term[rng.random(n) < hot_terminal] = 7  # owner 3
    us = day * US_PER_DAY + np.sort(rng.integers(0, US_PER_DAY, n))
    return {
        "tx_id": np.arange(tx0, tx0 + n, dtype=np.int64),
        "tx_datetime_us": us.astype(np.int64),
        "customer_id": cust.astype(np.int64),
        "terminal_id": term.astype(np.int64),
        "tx_amount_cents": rng.integers(100, 90_000, n).astype(np.int64),
        "kafka_ts_ms": (us // 1000).astype(np.int64),
    }


def _counter(reg, name, **labels):
    rows = reg.snapshot().get(name, {}).get("series", [])
    return sum(r["value"] for r in rows
               if all(r["labels"].get(k) == v for k, v in labels.items()))


# bl = 128 slots a device for the 64 rows a balanced batch leaves it, 32
# rows a (sender, owner) bucket: a tenth of the rows on one terminal fits
# (~20 of a device's 64 go to its owner); the hot customer's device holds
# ~100 rows, so 0.9 of them to one terminal owner outgrow a bucket; 0.6 of
# 256 rows on one customer outgrow its device, whose 128 slots, dense to
# their width, outgrow a bucket too
CASES = {
    "capacity_branch": dict(hot_customer=0.0, hot_terminal=0.1),
    "overflow_branch": dict(hot_customer=0.2, hot_terminal=0.9),
    "routed_spill": dict(hot_customer=0.6, hot_terminal=0.2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_four_devices_agree_with_the_plain_reference(case):
    from benchmark import reference
    from benchmark.models import forest as plain_forest

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "forest-rf100-d8-x4.json")) as f:
        config = json.load(f)
    config["features"].update(customer_capacity=512, terminal_capacity=1024)
    config["key_universe"] = {"customers": 512, "terminals": 1024}
    config["model_params"].update(n_estimators=10, max_depth=4,
                                  fit_rows=512, nominal_rows_per_day=256)
    model = plain_forest.build(config, seed=28)
    feats = dict(config["features"], windows=tuple(
        config["features"]["windows"]))
    cfg = Config(features=FeatureConfig(**feats),
                 runtime=RuntimeConfig(batch_buckets=(256,),
                                       max_batch_rows=256,
                                       trigger_seconds=0.0))
    reg = MetricsRegistry()
    eng = ShardedScoringEngine(
        cfg, kind="forest", params=model["params"], scaler=model["scaler"],
        n_devices=N_DEV, metrics=reg)
    assert eng.rows_per_shard == 128
    rng = np.random.default_rng(2800 + sorted(CASES).index(case))
    day0, n_days = 20_200, 10
    whole, ref = (reference.WindowReference(config["features"], 512, 1024,
                                            day0, n_days) for _ in range(2))
    emitted, probs, want, want_whole = [], [], [], []
    for d in range(n_days):  # terminal windows lag 7 days: go past them
        cols = _day_cols(256, day0 + d, rng, tx0=256 * d, **CASES[case])
        res = eng.process_batch(cols)
        emitted.append(np.asarray(res.features, np.float32))
        probs.append(np.asarray(res.probs, np.float64))
        # The contract (benchmark/reference.py): a row's windows hold its
        # whole batch (update, then query).
        whole.update(cols)
        want_whole.append(whole.features(cols))
        if case != "routed_spill":
            want.append(want_whole[-1])
            continue
        # PINS TODAY'S BEHAVIOUR, not the contract (ROADMAP B12): a batch
        # that spills is absorbed as several steps, so a row's windows
        # hold the batch-mates of its own and of earlier chunks only.
        # This reference follows the chunks the partitioner names, so it
        # cannot catch that divergence; the assertion after the loop
        # keeps it on record until the contract is settled.
        feats = np.empty((256, 15))
        for _, rows, _ in partition_batch_spill(cols, N_DEV, 128):
            part = {k: v[rows] for k, v in cols.items()}
            ref.update(part)
            feats[rows] = ref.features(part)
        want.append(feats)
    emitted, probs = np.concatenate(emitted), np.concatenate(probs)
    numbers = reference.compare(emitted, probs, np.concatenate(want),
                                model["reference_proba"](emitted),
                                config["limits"])
    assert all(n["ok"] for n in numbers), numbers
    assert emitted[:, 9:].any()  # the delayed terminal windows filled
    if case == "routed_spill":
        # the divergence from the contract, on record: when a spill
        # counts the whole batch this fails, and the chunk-following
        # reference above goes
        off = reference.compare(emitted, probs, np.concatenate(want_whole),
                                model["reference_proba"](emitted),
                                config["limits"])
        assert off[0]["name"] == "exact_columns_wrong" and off[0]["value"] > 0
    overflows = _counter(reg, "rtfds_exchange_overflow_total")
    routed = _counter(reg, "rtfds_shard_chunks_total", routed="1")
    assert (overflows > 0) == (case != "capacity_branch"), overflows
    assert (routed > 0) == (case == "routed_spill"), routed


# -- (4) checkpoints after the constructor change ----------------------------


@pytest.mark.parametrize("restore_width", [4, 1])
def test_width_four_checkpoint_restores(small_dataset, tmp_path,
                                        restore_width):
    _, _, _, txs = small_dataset
    warm, rest = txs.slice(slice(0, 2048)), txs.slice(slice(2048, 4096))
    cfg = _cfg("direct", rows=1024)
    eng4 = _engine(cfg)
    eng4.run(ReplaySource(warm, EPOCH0, batch_rows=1024))
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(eng4.state)
    want = MemorySink()
    eng4.run(ReplaySource(rest, EPOCH0, batch_rows=1024), sink=want)

    if restore_width == 1:
        params, scaler = _logreg()
        eng = ScoringEngine(cfg, kind="logreg", params=params,
                            scaler=scaler, metrics=MetricsRegistry())
    else:
        eng = _engine(cfg)
    restored = ck.restore(eng.state)
    assert restored is not None and restored.layout_devices == 4
    got = MemorySink()
    eng.run(ReplaySource(rest, EPOCH0, batch_rows=1024), sink=got)
    a, b = want.concat(), got.concat()
    oa, ob = np.argsort(a["tx_id"]), np.argsort(b["tx_id"])
    np.testing.assert_array_equal(a["tx_id"][oa], b["tx_id"][ob])
    np.testing.assert_allclose(a["prediction"][oa], b["prediction"][ob],
                               atol=1e-6)


# -- (5) what the mesh adds to a batch, counted ------------------------------


def test_mesh_counters_read_what_the_batch_implies():
    reg = MetricsRegistry()
    eng = _engine(_cfg("direct", rows=64), registry=reg, rows_per_shard=16)
    n = 40
    us = 20_200 * US_PER_DAY + np.arange(n, dtype=np.int64) * 1_000_000
    cols = {
        "tx_id": np.arange(n, dtype=np.int64),
        "tx_datetime_us": us,
        # every customer is owned by device 1, every row pays terminal 7
        "customer_id": (1 + 4 * (np.arange(n) % 50)).astype(np.int64),
        "terminal_id": np.full(n, 7, np.int64),
        "tx_amount_cents": np.full(n, 1_000, np.int64),
        "kafka_ts_ms": us // 1000,
    }
    res = eng.process_batch(cols)
    assert len(res.probs) == n
    # chunk 0: device 1's 16 slots full, the other 24 rows spill into one
    # routed chunk dealt round-robin, 6 a device
    assert _counter(reg, "rtfds_shard_chunks_total", routed="0") == 1
    assert _counter(reg, "rtfds_shard_chunks_total", routed="1") == 1
    assert _counter(reg, "rtfds_shard_slots_total") == 2 * N_DEV * 16
    assert _counter(reg, "rtfds_shard_valid_rows_total") == n
    assert _counter(reg, "rtfds_shard_rows_max_total") == n
    assert _counter(reg, "rtfds_shard_rows_mean_total") == n / N_DEV
    # buckets hold 2 * ceil(16 / 4) = 8 rows a (sender, owner) pair: chunk
    # 0 sends device 1's 16 rows to terminal 7's owner (over), the routed
    # chunk sends 6 a device to the customers' owner and on to the
    # terminal's (both fit)
    assert _counter(reg, "rtfds_exchange_overflow_total") == 1
    # the lanes those three exchanges served on the four devices together
    # (64 rows a batch over four devices: the tight bucket is 8 of a
    # chunk's 16 slots a device, in the routed program as in the
    # owner-placed one) and the rows in them
    assert _counter(reg, "rtfds_exchange_lanes_total") == (
        N_DEV * N_DEV * 16 + 2 * N_DEV * N_DEV * 8)
    assert _counter(reg, "rtfds_exchange_rows_total") == 16 + 2 * 24
    phases = {r["labels"]["phase"]: r for r in
              reg.snapshot()["rtfds_phase_seconds"]["series"]}
    for phase in ("partition", "assemble"):
        assert phases[phase]["count"] == 1 and phases[phase]["sum"] > 0
    # a second, balanced batch: one local chunk, nothing over
    cols2 = _day_cols(40, 20_201, np.random.default_rng(3), tx0=1000)
    eng.process_batch(cols2)
    assert _counter(reg, "rtfds_shard_chunks_total", routed="0") == 2
    assert _counter(reg, "rtfds_shard_chunks_total", routed="1") == 1
    assert _counter(reg, "rtfds_exchange_overflow_total") == 1
    assert _counter(reg, "rtfds_exchange_lanes_total") == (
        512 + N_DEV * N_DEV * 8)
    assert _counter(reg, "rtfds_exchange_rows_total") == 64 + 40
    assert _counter(reg, "rtfds_shard_rows_max_total") == n + int(
        np.bincount(cols2["customer_id"] % N_DEV, minlength=N_DEV).max())
    assert _counter(reg, "rtfds_shard_rows_mean_total") == 2 * n / N_DEV
