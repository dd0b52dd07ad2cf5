"""Tiered device-resident feature store (key_mode="exact"): collision
semantics, exactness vs direct mode, overflow to the CMS tier, recency
compaction, feedback routing, and the config-level guard rails."""

import dataclasses as dc
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.core.batch import (
    make_batch,
    pad_batch,
)
from real_time_fraud_detection_system_tpu.features.online import (
    TablePlane,
    apply_feedback,
    assemble,
    compact_feature_state,
    fraud_of,
    init_feature_state,
    state_bytes,
    update_and_featurize,
    update_and_featurize_exact,
)
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.ops.cms import (
    chunk_rows,
    cms_query,
    cms_query_fraud,
    cms_query_where,
)
from real_time_fraud_detection_system_tpu.ops.hashing import slot_of
from real_time_fraud_detection_system_tpu.ops.windows import query_windows
from real_time_fraud_detection_system_tpu.runtime.engine import (
    ScoringEngine,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (
    MetricsRegistry,
)

DAY0 = 20200


def _fcfg(**kw):
    base = dict(customer_capacity=128, terminal_capacity=256,
                cms_width=1 << 12)
    base.update(kw)
    return FeatureConfig(**base)


def _batch(rng, n=256, n_cust=40, n_term=80, day0=DAY0, spread=3):
    return jax.tree.map(jnp.asarray, make_batch(
        customer_id=rng.integers(0, n_cust, n).astype(np.int64),
        terminal_id=rng.integers(0, n_term, n).astype(np.int64),
        tx_datetime_us=(
            (day0 + rng.integers(0, spread, n)) * 86400
            + rng.integers(0, 86400, n)
        ).astype(np.int64) * 1_000_000,
        amount_cents=rng.integers(100, 50000, n).astype(np.int64),
    ))


# ---------------------------------------------------------------------------
# satellite: capacity guard rails
# ---------------------------------------------------------------------------

def test_non_pow2_capacity_refused():
    """direct mode masks with capacity-1 (features/online.py::_slot):
    a non-pow2 capacity would silently alias keys — must refuse."""
    with pytest.raises(ValueError, match="power of two"):
        _fcfg(customer_capacity=100)
    with pytest.raises(ValueError, match="power of two"):
        _fcfg(terminal_capacity=3000)
    _fcfg(customer_capacity=1024)  # pow2 fine


def test_exact_config_validation():
    with pytest.raises(ValueError, match="key_mode"):
        _fcfg(key_mode="fancy")
    with pytest.raises(ValueError, match="keydir_probes"):
        _fcfg(key_mode="exact", keydir_probes=0)
    with pytest.raises(ValueError, match="compact_every"):
        _fcfg(key_mode="exact", compact_every=-1)
    with pytest.raises(ValueError, match="state_hbm_budget_mb"):
        _fcfg(state_hbm_budget_mb=-1.0)


# ---------------------------------------------------------------------------
# satellite: collision semantics pinned per mode
# ---------------------------------------------------------------------------

def test_hash_mode_merges_colliding_keys_exact_mode_does_not():
    """Two keys that collide under slot_of MERGE windows in hash mode
    (the documented degradation) and must NOT merge in exact mode."""
    cap = 64
    # find two distinct keys with the same hashed slot
    keys = np.arange(10_000, dtype=np.uint32)
    slots = np.asarray(slot_of(jnp.asarray(keys), cap))
    a = 0
    twins = np.flatnonzero(slots == slots[a])
    b = int(twins[twins != a][0])
    cfg_h = _fcfg(customer_capacity=cap, terminal_capacity=cap,
                  key_mode="hash")
    cfg_e = _fcfg(customer_capacity=cap, terminal_capacity=cap,
                  key_mode="exact")

    def feats_for(cfg, exact):
        st = init_feature_state(cfg)
        b1 = jax.tree.map(jnp.asarray, make_batch(
            customer_id=np.array([a, b], np.int64),
            terminal_id=np.array([1, 2], np.int64),
            tx_datetime_us=np.array([DAY0 * 86400 * 1_000_000] * 2,
                                    np.int64),
            amount_cents=np.array([10_000, 50_000], np.int64),
        ))
        if exact:
            st, f, _ = update_and_featurize_exact(st, b1, cfg)
        else:
            st, f = update_and_featurize(st, b1, cfg)
        return np.asarray(f)

    f_h = feats_for(cfg_h, exact=False)
    f_e = feats_for(cfg_e, exact=True)
    # 1-day customer count (feature col 3): hash mode sees BOTH rows in
    # one merged window; exact mode keeps per-key counts of 1
    assert f_h[0, 3] == 2.0 and f_h[1, 3] == 2.0
    assert f_e[0, 3] == 1.0 and f_e[1, 3] == 1.0


# ---------------------------------------------------------------------------
# tentpole: exactness — hot tier big enough ⇒ bit-identical to direct
# ---------------------------------------------------------------------------

def _engine(cfg, reg=None):
    return ScoringEngine(
        cfg, kind="logreg", params=init_logreg(15),
        scaler=Scaler(mean=np.zeros(15, np.float32),
                      scale=np.ones(15, np.float32)),
        metrics=reg if reg is not None else MetricsRegistry(),
    )


def _cols(rng, n=200, n_cust=40, n_term=80, day0=DAY0, spread=3):
    us = ((day0 + rng.integers(0, spread, n)) * 86400
          + rng.integers(0, 86400, n)).astype(np.int64) * 1_000_000
    return {
        "tx_id": np.arange(n, dtype=np.int64),
        "tx_datetime_us": us,
        "customer_id": rng.integers(0, n_cust, n).astype(np.int64),
        "terminal_id": rng.integers(0, n_term, n).astype(np.int64),
        "tx_amount_cents": rng.integers(100, 50000, n).astype(np.int64),
        "kafka_ts_ms": us // 1000,
    }


def test_exact_engine_bit_identical_to_direct_with_aot():
    """Acceptance bar: hot tier sized to hold every key ⇒ exact-mode
    scores AND features bit-identical to direct mode, under precompile
    (AOT) and plain jit alike — and the AOT run pays zero mid-stream
    recompiles with the compact variant enumerated and compiled."""
    rt = RuntimeConfig(batch_buckets=(64, 256), max_batch_rows=256,
                       precompile=True)
    cfg_d = Config(features=_fcfg(), runtime=rt)
    cfg_e = Config(features=_fcfg(key_mode="exact", compact_every=3),
                   runtime=rt)
    reg_e = MetricsRegistry()
    eng_d = _engine(cfg_d)
    eng_e = _engine(cfg_e, reg_e)
    inv = eng_e.dispatch_inventory()
    assert ("compact",) in [s.key for s in inv]
    eng_d.precompile()
    eng_e.precompile()
    rng_d, rng_e = (np.random.default_rng(5) for _ in range(2))
    for i in range(7):
        rd = eng_d.process_batch(_cols(rng_d))
        re = eng_e.process_batch(_cols(rng_e))
        np.testing.assert_array_equal(rd.probs, re.probs)
        np.testing.assert_array_equal(rd.features, re.features)
    rc = reg_e.get("rtfds_xla_recompiles_total")
    assert rc is None or rc.value == 0
    assert reg_e.get("rtfds_aot_fallbacks_total").value == 0
    # every (row × keyspace) admission was dense: the tier counters say so
    dense = reg_e.get("rtfds_feature_tier_rows_total", tier="dense").value
    cms = reg_e.get("rtfds_feature_tier_rows_total", tier="cms").value
    assert dense == 7 * 200 * 2 and cms == 0


def test_overflow_serves_cms_tier_and_counts_it():
    """Hot tier much smaller than the key universe: the stream still
    completes, misses are served (features finite, probs valid) and the
    cms tier counter records exactly the misses."""
    cfg = Config(
        features=_fcfg(customer_capacity=16, terminal_capacity=16,
                       key_mode="exact"),
        runtime=RuntimeConfig(batch_buckets=(256,), max_batch_rows=256),
    )
    reg = MetricsRegistry()
    eng = _engine(cfg, reg)
    rng = np.random.default_rng(9)
    for _ in range(4):
        res = eng.process_batch(_cols(rng, n_cust=500, n_term=500))
        assert np.isfinite(res.features).all()
        assert np.isfinite(res.probs).all()
    dense = reg.get("rtfds_feature_tier_rows_total", tier="dense").value
    cms = reg.get("rtfds_feature_tier_rows_total", tier="cms").value
    assert dense + cms == 4 * 200 * 2
    assert cms > 0  # 500 keys cannot fit 16 slots
    # CMS-tier counts keep the overestimate-only contract: the 30-day
    # customer count can never undercount the key's true row count
    assert dense > 0


# ---------------------------------------------------------------------------
# the sketch tier's read rule: only the rows it serves, in chunks (PR 33)
# ---------------------------------------------------------------------------

READ_ROWS, READ_VALID = 640, 600  # 40 padding rows, scattered
READ_K = 256  # chunk_rows(640)


@functools.lru_cache(maxsize=None)  # immutable arrays, twelve cases
def _updated_plane(table):
    """One table's plane under ``exact`` after two batches, every key
    admitted: ``(plane, tstate, slot, adm, key, day, valid)`` of the
    second — 640 rows of which 40, scattered, are padding, the keys drawn
    from 100 (so most are duplicated), days on both sides of the
    terminal delay."""
    cfg = _fcfg(key_mode="exact")
    plane = TablePlane(table, cfg)
    ts = plane.of(init_feature_state(cfg))
    rng = np.random.default_rng(33)
    for step in range(2):
        key = jnp.asarray(rng.integers(0, 100, READ_ROWS).astype(np.uint32))
        day = jnp.asarray(
            (DAY0 + 9 * step + rng.integers(0, 9, READ_ROWS)).astype(
                np.int32))
        amount = jnp.asarray(
            rng.integers(100, 50000, READ_ROWS).astype(np.float32) / 100)
        fraud = jnp.asarray(
            (rng.random(READ_ROWS) < 0.3).astype(np.float32))
        valid = np.zeros(READ_ROWS, bool)
        valid[rng.permutation(READ_ROWS)[:READ_VALID]] = True
        valid = jnp.asarray(valid)
        ts, slot, adm = plane.update(ts, key, day, amount, fraud, valid)
    assert bool((adm == valid).all())  # admit_slots: padding is not admitted
    return plane, ts, slot, adm, key, day, valid


def _whole_batch_read(plane, ts, slot, adm, key, day, valid):
    """The read as it stood before PR 33, the plain reference: the
    sketch read for EVERY row of the batch, then picked per row."""
    windows = tuple(plane.cfg.windows)
    customer = plane.table == "customer"
    delay = 0 if customer else plane.cfg.delay_days
    pick = (0, 1) if customer else (0, 2)
    got = query_windows(ts.windows, slot, day, windows, delay=delay)
    hot = [got[i] for i in pick]
    got = (cms_query(ts.sketch, key, day, windows) if customer
           else cms_query_fraud(ts.sketch, key, day, windows, delay=delay))
    cold = [got[i] for i in pick]
    mat = jnp.concatenate(
        [jnp.where(adm[:, None], h, c) for h, c in zip(hot, cold)], axis=1)
    tier = ts.tier + jnp.stack([
        jnp.sum((valid & adm).astype(jnp.float32)),
        jnp.sum((valid & ~adm).astype(jnp.float32))])
    return mat, tier


@pytest.mark.parametrize("table", ["customer", "terminal"])
@pytest.mark.parametrize(
    "missed", [0, 1, READ_K - 1, READ_K, READ_K + 1, READ_VALID],
    ids=["none", "one", "K-1", "K", "K+1", "all"])
def test_sketch_is_read_for_the_rows_that_missed_and_no_others(table,
                                                               missed):
    """``TablePlane.query`` under ``exact`` with ``missed`` of the 600
    delivered rows forced off the hot tier: on every delivered row the
    window sums, and the ``tier`` counts, are the whole-batch read's bit
    for bit; the sketch was read in ⌈missed ÷ K⌉ chunks of K rows; the
    40 padding rows are not misses (they cost no trip) and read 0.0."""
    assert chunk_rows(READ_ROWS) == READ_K
    plane, ts, slot, adm, key, day, valid = _updated_plane(table)
    rows = np.flatnonzero(np.asarray(valid))
    off = np.random.default_rng(missed).permutation(rows)[:missed]
    adm = adm.at[jnp.asarray(off, jnp.int32)].set(False)
    assert int((valid & ~adm).sum()) == missed
    assert int((~adm).sum()) == missed + READ_ROWS - READ_VALID

    want, want_tier = _whole_batch_read(plane, ts, slot, adm, key, day,
                                        valid)
    got_ts, got = jax.jit(plane.query)(ts, slot, adm, key, day, valid)
    v = np.asarray(valid)
    np.testing.assert_array_equal(np.asarray(got)[v], np.asarray(want)[v])
    np.testing.assert_array_equal(np.asarray(got)[~v], 0.0)
    np.testing.assert_array_equal(np.asarray(got_ts.tier),
                                  np.asarray(want_tier))
    assert float(got_ts.tier[1]) == missed
    # a missed row's counts are the sketch's: never under the hot tier's
    nw = len(plane.cfg.windows)
    hot = _whole_batch_read(plane, ts, slot, valid, key, day, valid)[0]
    assert (np.asarray(got)[off, :nw] >= np.asarray(hot)[off, :nw]).all()

    columns = ("count", "amount" if table == "customer" else "fraud")
    _, trips = jax.jit(
        lambda sk, k, d, m: cms_query_where(
            sk, columns, k, d, m, tuple(plane.cfg.windows),
            0 if table == "customer" else plane.cfg.delay_days))(
        ts.sketch, key, day, valid & ~adm)
    assert int(trips) == -(-missed // READ_K)


def test_a_dry_free_stack_is_served_in_chunks_like_the_whole_batch():
    """The same rule through the directory itself: a hot tier of 16
    slots under 500 keys misses most rows of every batch, and the
    features of every delivered row are the whole-batch read's."""
    cfg = _fcfg(customer_capacity=16, terminal_capacity=16,
                key_mode="exact")
    st = init_feature_state(cfg)
    rng = np.random.default_rng(9)
    served = 0
    for _ in range(3):
        b = _batch(rng, n=200, n_cust=500, n_term=500)
        b = jax.tree.map(jnp.asarray, pad_batch(
            jax.tree.map(np.asarray, b), 256))
        want = []
        for plane, key in ((TablePlane("customer", cfg), b.customer_key),
                           (TablePlane("terminal", cfg), b.terminal_key)):
            ts, slot, adm = plane.update(
                plane.of(st), key, b.day, b.amount, fraud_of(b), b.valid)
            want.append(_whole_batch_read(plane, ts, slot, adm, key, b.day,
                                          b.valid))
        st, feats, tier = jax.jit(
            lambda s, x: update_and_featurize_exact(s, x, cfg))(st, b)
        ref = assemble(b, cfg, want[0][0], want[1][0])
        np.testing.assert_array_equal(np.asarray(feats)[:200],
                                      np.asarray(ref)[:200])
        assert np.isfinite(np.asarray(feats)).all()
        np.testing.assert_array_equal(  # [dense, cms]; [2:] the rounds
            np.asarray(tier)[:2], np.asarray(want[0][1] + want[1][1]))
        served += float(tier[1])
    assert served > 3 * 200  # most of both key spaces missed


def test_compaction_reclaims_dead_slots_and_preserves_live():
    cfg = _fcfg(key_mode="exact")
    st = init_feature_state(cfg)
    rng = np.random.default_rng(1)
    st, _, _ = update_and_featurize_exact(st, _batch(rng, day0=DAY0), cfg)
    occupied0 = int(cfg.customer_capacity
                    - np.asarray(st.customer_dir.free_top))
    assert occupied0 > 0
    horizon = cfg.delay_days + max(cfg.windows)
    # not yet past the horizon: nothing reclaims
    st1, rec = compact_feature_state(
        st, jnp.int32(DAY0 + horizon), cfg)
    assert int(np.asarray(rec).sum()) == 0
    # all history dead: everything reclaims, windows reset
    st2, rec2 = compact_feature_state(
        st, jnp.int32(DAY0 + horizon + 3), cfg)
    assert int(np.asarray(rec2).sum()) > 0
    assert int(np.asarray(st2.customer_dir.free_top)) \
        == cfg.customer_capacity
    assert int(np.asarray(st2.terminal_dir.free_top)) \
        == cfg.terminal_capacity
    assert (np.asarray(st2.customer.bucket_day) == -1).all()


def test_exact_feedback_routes_hits_to_table_misses_to_sketch():
    cfg = _fcfg(key_mode="exact")
    st = init_feature_state(cfg)
    rng = np.random.default_rng(2)
    b = _batch(rng, n=64, n_term=8, day0=DAY0, spread=1)
    st, _, _ = update_and_featurize_exact(st, b, cfg)
    frd0 = np.asarray(st.terminal.fraud).sum()
    cms0 = np.asarray(st.terminal_cms.fraud).sum()
    # a key the directory knows + one it has never seen
    known = np.asarray(b.terminal_key)[0]
    keys = jnp.asarray(np.array([known, 4_000_011], np.uint32))
    day = jnp.asarray(np.array([DAY0, DAY0], np.int32))
    lab = jnp.asarray(np.array([1, 1], np.int32))
    st = apply_feedback(st, keys, day, lab, jnp.ones(2, bool), cfg)
    assert np.asarray(st.terminal.fraud).sum() == frd0 + 1  # table hit
    assert np.asarray(st.terminal_cms.fraud).sum() > cms0  # sketch miss


# ---------------------------------------------------------------------------
# budget + engine guard rails
# ---------------------------------------------------------------------------

def test_state_budget_validated_at_engine_build():
    over = Config(features=_fcfg(key_mode="exact",
                                 state_hbm_budget_mb=0.5))
    with pytest.raises(ValueError, match="state_hbm_budget_mb"):
        _engine(over)
    sb = state_bytes(over.features)
    ok = Config(features=_fcfg(
        key_mode="exact",
        state_hbm_budget_mb=sb["total"] / 2 ** 20 + 1.0))
    _engine(ok)  # fits: builds fine


def test_state_bytes_accounting_matches_live_state():
    cfg = _fcfg(key_mode="exact")
    sb = state_bytes(cfg)
    st = init_feature_state(cfg)
    live = sum(np.asarray(leaf).nbytes for leaf in jax.tree.leaves(st))
    assert sb["total"] == live
    assert sb["dense"] + sb["directory"] + sb["cms"] == sb["total"]


def test_sharded_engine_serves_exact_mode():
    """The PR-13 refusal is gone: the sharded engine builds per-shard
    directories and serves exact mode (full coverage, incl. the pinned
    errors for the combos that STAY unsupported, lives in
    tests/test_sharded_exact.py — this pins that the old refusal does
    not resurface)."""
    from real_time_fraud_detection_system_tpu.runtime.sharded_engine \
        import ShardedScoringEngine

    cfg = Config(features=_fcfg(key_mode="exact"),
                 runtime=RuntimeConfig(batch_buckets=(64,),
                                       max_batch_rows=64))
    eng = ShardedScoringEngine(
        cfg, "logreg", init_logreg(15),
        Scaler(mean=np.zeros(15, np.float32),
               scale=np.ones(15, np.float32)),
        n_devices=2)
    assert eng.state.feature_state.terminal_dir is not None
    # stacked per-shard layout: one directory per device
    import numpy as _np

    assert _np.asarray(
        eng.state.feature_state.terminal_dir.keys).shape[0] == 2


def test_sequence_kind_refuses_exact_mode():
    cfg = Config(features=_fcfg(key_mode="exact"))
    # the guard fires before params are ever touched
    with pytest.raises(ValueError, match="sequence"):
        ScoringEngine(cfg, "sequence", params=None, scaler=None,
                      metrics=MetricsRegistry())
