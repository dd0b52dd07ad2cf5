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
from real_time_fraud_detection_system_tpu.ops import keydir
from real_time_fraud_detection_system_tpu.ops.hashing import slot_of
from real_time_fraud_detection_system_tpu.ops.keydir import (
    admit_slots,
    lookup_slots,
)
from real_time_fraud_detection_system_tpu.ops.windows import (
    WindowState,
    query_windows,
)
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


# -- the pass follows what it vacates (PR 38) --------------------------------
#
# The entry-wide form the pass had — one lane a directory entry for the
# ``newest[slot]`` gather, the free-stack scatter and the flag scatter —
# kept as a plain NumPy oracle: the lane-packed pass has to leave its
# state, its counts and its demote payload bit for bit.

NB = 40
EMPTY = np.uint32(0xFFFFFFFF)
FILLS = (np.int32(-1), np.float32(0), np.float32(0), np.float32(0))


def _entry_wide_compaction(state, now_day, cfg, demote_slots=0):
    """→ (state as numpy leaves, reclaimed [2], payload | None)."""
    horizon = cfg.delay_days + max(cfg.windows)
    cutoff = now_day - horizon
    out, counts, payload = {}, [], {}
    for table in ("customer", "terminal"):
        kd = getattr(state, f"{table}_dir")
        keys, slots, free = (np.array(x) for x in (kd.keys, kd.slots,
                                                   kd.free))
        top = int(kd.free_top)
        cols = [np.array(t) for t in getattr(state, table).tables()]
        cap = cols[0].shape[0]
        live = slots >= 0
        newest_e = cols[0].max(axis=1)[np.clip(slots, 0, cap - 1)]
        dead = live & (newest_e < cutoff)
        sel = np.zeros_like(dead)
        if demote_slots:
            k = min(demote_slots, len(keys))
            occupied = cap - top - int(dead.sum())
            n_evict = int(np.clip(
                occupied - int(cfg.cold_highwater * cap), 0, k))
            cand = np.flatnonzero(live & ~dead & (newest_e < now_day))
            # the oldest first, a tie to the lowest entry index
            cand = cand[np.lexsort((cand, newest_e[cand]))][:n_evict]
            sel[cand] = True
            cand = cand[np.argsort(keys[cand], kind="stable")]
            pay = [np.full((k,), EMPTY, np.uint32)] + [
                np.full((k, NB), f, f.dtype) for f in FILLS]
            pay[0][:len(cand)] = keys[cand]
            for lane_rows, col in zip(pay[1:], cols):
                lane_rows[:len(cand)] = col[slots[cand]]
            payload[table] = tuple(pay)
        gone = np.flatnonzero(dead | sel)  # in entry order
        free[top:top + len(gone)] = slots[gone]
        for col, fill in zip(cols, FILLS):
            col[slots[gone]] = fill
        keys[gone], slots[gone] = EMPTY, -1
        out[table] = (keys, slots, free, np.int32(top + len(gone)),
                      *(c.reshape(-1) for c in cols))
        counts.append(len(gone))
    return out, np.asarray(counts, np.int32), payload or None


def _assert_pass_equals_oracle(state, now_day, cfg, demote_slots=0):
    """Run the pass (jitted, as the engine does) and the oracle on
    ``state``; every leaf the pass writes, the counts and the payload
    equal to the bit. Returns the pass's (state, reclaimed)."""
    before = jax.tree.map(np.array, state)
    out = jax.jit(lambda st, day: compact_feature_state(
        st, day, cfg, demote_slots=demote_slots))(state, jnp.int32(now_day))
    assert_recorded_pass_equals_oracle(
        before, now_day, jax.tree.map(np.asarray, out), cfg, demote_slots)
    # untouched by the pass: the sketches
    for a, b in zip(jax.tree.leaves((out[0].cms, out[0].terminal_cms)),
                    jax.tree.leaves((before.cms, before.terminal_cms))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    return out[0], np.asarray(out[1])


def _built_state(cfg, customers, terminals, seed=0):
    """A state whose tables hold ``{key: newest day | None}``: each key
    admitted through the directory, its row stamped with two days up to
    its newest (``None``: granted a slot, the row still empty) and seeded
    values in the other columns."""
    rng = np.random.default_rng(seed)
    st = init_feature_state(cfg)
    placed = {}
    for table, held in (("customer", customers), ("terminal", terminals)):
        keys = jnp.asarray(np.fromiter(held, np.uint32, len(held)))
        kd, slot, adm, _, _ = admit_slots(
            getattr(st, f"{table}_dir"), keys, jnp.ones(len(held), bool),
            n_probes=cfg.keydir_probes)
        assert np.asarray(adm).all()
        bd = np.full((len(held), NB), -1, np.int32)
        for i, day in enumerate(held.values()):
            for d in ((day - 1, day) if day is not None else ()):
                bd[i, d % NB] = d
        vals = [np.where(bd >= 0, rng.random(bd.shape), 0).astype(
            np.float32) for _ in range(3)]
        ws = getattr(st, table).set_rows(slot, jnp.asarray(bd), *map(
            jnp.asarray, vals))
        placed[f"{table}_dir"], placed[table] = kd, ws
    return st._replace(**placed)


NOW = DAY0 + 100
HORIZON = 37  # delay 7 + the 30-day window: older than NOW - 37 is dead
DEAD, OLD, MID, TODAY = NOW - 50, NOW - 30, NOW - 10, NOW


def _ages(n, day, first=0):
    return {first + i: day for i in range(n)}


def _case_nothing_goes():
    # live history everywhere, the tier under its target: no sweep
    return (_ages(20, MID), _ages(30, TODAY, 100)), 0, {}, (0, 0)


def _case_dead_only():
    return ({**_ages(9, DEAD), **_ages(11, MID, 50)},
            {**_ages(5, DEAD, 100), **_ages(25, TODAY, 200)}), 0, {}, (9, 5)


def _case_dead_over_k():
    # 41 + 23 dead entries, 4 lanes a trip: 11 and 6 trips, the last
    # ones part-filled
    return ({**_ages(41, DEAD), **_ages(7, MID, 50)},
            {**_ages(23, DEAD, 100), **_ages(9, MID, 200)}), 0, {
                "pack_lanes": 4}, (41, 23)


def _case_tie_at_the_threshold():
    # target 16 of 64 slots, 40 occupied after 4 dead go: the quota is 8
    # of k = 8; 3 are older, the 10 of age OLD tie for the other 5 and
    # the lowest entry indices win; today's are never taken
    return ({**_ages(4, DEAD), **_ages(3, OLD - 2, 50),
             **_ages(10, OLD, 100), **_ages(12, MID, 200),
             **_ages(15, TODAY, 300)},
            _ages(6, TODAY, 400)), 8, {"cold_highwater": 0.25}, (12, 0)


def _case_quota_over_the_eligible():
    # 50 occupied over a target of 16, a quota of 32 — and only 6 keys
    # that were not touched today: all 6 go, nothing else
    return ({**_ages(6, MID), **_ages(44, TODAY, 100)},
            _ages(6, TODAY, 400)), 32, {"cold_highwater": 0.25}, (6, 0)


def _case_one_table_gives():
    # the customers demote and hold dead history, the terminals are
    # under their target with live history: one sweep of two
    return ({**_ages(5, DEAD), **_ages(30, OLD, 50),
             **_ages(10, TODAY, 300)},
            {**_ages(8, MID, 400), **_ages(6, TODAY, 500)}), 16, {
                "cold_highwater": 0.25, "pack_lanes": 4}, (21, 0)


def _case_empty_rows_are_dead():
    # a granted slot whose row was never written has no history to keep:
    # dead entry by entry and by the dense count alike
    return ({0: None, 1: None, **_ages(5, MID, 50)},
            {7: None, **_ages(3, TODAY, 100)}), 0, {}, (2, 1)


def _case_before_the_first_horizon():
    # now_day under the horizon: the cutoff is negative, every slot
    # passes the dense compare — the free ones too — and nothing is dead,
    # not even a granted slot with an empty row; the quota still takes
    # what is eligible (the empty rows and yesterday's)
    return ({0: None, **_ages(20, 4, 50), **_ages(8, 5, 100)},
            {7: None, **_ages(3, 5, 100)}), 16, {
                "cold_highwater": 0.25, "now": 5}, (13, 0)


@pytest.mark.parametrize("case", [
    pytest.param(_case_nothing_goes, id="nothing-dead-no-quota"),
    pytest.param(_case_dead_only, id="dead-only"),
    pytest.param(_case_dead_over_k, id="dead-over-K-several-trips"),
    pytest.param(_case_tie_at_the_threshold, id="quota-tie-at-threshold"),
    pytest.param(_case_quota_over_the_eligible, id="quota-over-eligible"),
    pytest.param(_case_one_table_gives, id="one-table-gives"),
    pytest.param(_case_empty_rows_are_dead, id="empty-rows-are-dead"),
    pytest.param(_case_before_the_first_horizon, id="negative-cutoff"),
])
def test_compaction_equals_the_entry_wide_oracle(case, monkeypatch):
    """Directory, free stack (push order included), ``free_top``, the
    four window columns of both tables, ``reclaimed`` and the demote
    payload lane for lane: the entry-wide oracle's, in every shape of
    input the pass tells apart — and ``reclaimed`` is 0 exactly for a
    table whose sweep the dense counts skipped."""
    (customers, terminals), demote, knobs, want_n = case()
    if "pack_lanes" in knobs:
        monkeypatch.setattr(keydir, "PACK_LANES", knobs["pack_lanes"])
    cfg = _fcfg(customer_capacity=64, terminal_capacity=64,
                key_mode="exact", keydir_probes=16,
                cold_highwater=knobs.get("cold_highwater", 0.75))
    now = knobs.get("now", NOW)
    st = _built_state(cfg, customers, terminals)
    st2, rec = _assert_pass_equals_oracle(st, now, cfg, demote)
    assert tuple(rec) == want_n
    # a second pass on what the first left finds nothing dead; with a
    # quota it goes on demoting, as the oracle does
    _assert_pass_equals_oracle(st2, now, cfg, demote)


def spy_on_passes(eng):
    """Record every compaction ``eng`` dispatches from here on:
    ``[(state before, now_day, outputs)]``, all on the host (the pass
    donates its input)."""
    seen, inner = [], eng._compact

    def spy(fstate, day):
        before = jax.tree.map(np.array, fstate)
        out = inner(fstate, day)
        seen.append((before, int(day), jax.tree.map(np.asarray, out)))
        return out

    eng._compact = spy
    return seen


def _device_share(st, n_dev, s):
    """Device ``s``'s share of a mesh's state (host leaves): its block of
    every window column, its row of the stacked directories; the state
    itself when ``n_dev`` is 0."""
    if not n_dev:
        return st
    return st._replace(**{
        t: dc.replace(getattr(st, t), **{
            c: getattr(getattr(st, t), c).reshape(n_dev, -1)[s]
            for c in ("bucket_day", "count", "amount", "fraud")})
        for t in ("customer", "terminal")}, **{
        f"{t}_dir": jax.tree.map(lambda x: x[s], getattr(st, f"{t}_dir"))
        for t in ("customer", "terminal")})


def assert_recorded_pass_equals_oracle(before, day, out, fcfg, demote,
                                       n_dev=0):
    """One recorded pass against the oracle — a device of the mesh at a
    time when ``n_dev``: each runs the pass on its own directory and its
    own block of the window columns. Returns the tables swept,
    ``{table: count}``: those that gave something up."""
    swept = {"customer": 0, "terminal": 0}
    for s in range(max(n_dev, 1)):
        def mine(x):
            return x[s] if n_dev else x

        shard_of = functools.partial(_device_share, n_dev=n_dev, s=s)
        want, want_n, want_pay = _entry_wide_compaction(
            shard_of(before), day, fcfg, demote)
        got = shard_of(out[0])
        np.testing.assert_array_equal(mine(out[1]), want_n)
        for table in ("customer", "terminal"):
            kd, ws = getattr(got, f"{table}_dir"), getattr(got, table)
            for a, b in zip((kd.keys, kd.slots, kd.free, kd.free_top,
                             *ws.columns()), want[table]):
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
            if demote:
                for a, b in zip(out[2][table], want_pay[table]):
                    assert mine(a).tobytes() == b.tobytes(), table
            swept[table] += int(want_n[("customer", "terminal").index(
                table)] > 0)
    return swept


def _drifting(n_batches, rows=256, step=10):
    """A working set that drifts (batch i touches keys [64 i, 64 i + 64)
    only) while the day marches ``step`` a batch: earlier batches' slots
    go dead past the 37-day horizon."""
    rng = np.random.default_rng(3)
    out = []
    for i in range(n_batches):
        n = rows
        out.append({
            "tx_id": np.arange(i * n, (i + 1) * n, dtype=np.int64),
            "tx_datetime_us": ((DAY0 + i * step) * 86400 + rng.integers(
                0, 86400, n)).astype(np.int64) * 1_000_000,
            "customer_id": (i * 64 + rng.integers(0, 64, n)).astype(
                np.int64),
            "terminal_id": (i * 64 + rng.integers(0, 64, n)).astype(
                np.int64),
            "tx_amount_cents": (rng.integers(1, 500, n) * 100).astype(
                np.int64),
            "kafka_ts_ms": np.zeros(n, dtype=np.int64),
        })
    return out


def assert_sweeps_counted(reg, passes, swept):
    """``rtfds_state_compact_sweeps_total{table}`` = the tables (a
    device's, on the mesh) that gave something up, summed over the
    recorded passes; registered at 0 from the build."""
    assert reg.get("rtfds_state_compactions_total").value == passes
    for table, n in swept.items():
        assert reg.get("rtfds_state_compact_sweeps_total",
                       table=table).value == n, table


def test_engine_passes_equal_the_oracle_and_the_sweeps_are_counted():
    """Through ``engine.run()``: every pass the loop dispatches leaves
    the oracle's state, and the host's sweep counter — read off the
    ``reclaimed`` it already fetched — counts exactly the tables that
    gave something up: the first passes find nothing dead (0 sweeps),
    the later ones reclaim from both tables."""
    from test_sharded_exact import _Src

    cfg = Config(
        features=_fcfg(customer_capacity=512, terminal_capacity=512,
                       cms_width=1 << 10, key_mode="exact",
                       compact_every=2),
        runtime=RuntimeConfig(batch_buckets=(256,), max_batch_rows=256,
                              trigger_seconds=0.0, precompile=False))
    reg = MetricsRegistry()
    eng = _engine(cfg, reg)
    for table in ("customer", "terminal"):
        assert reg.get("rtfds_state_compact_sweeps_total",
                       table=table).value == 0
    seen = spy_on_passes(eng)
    eng.run(_Src(_drifting(10)))
    assert len(seen) == 5
    swept = {"customer": 0, "terminal": 0}
    for before, day, out in seen:
        for t, n in assert_recorded_pass_equals_oracle(
                before, day, out, cfg.features, 0).items():
            swept[t] += n
    assert 0 < swept["customer"] < len(seen)  # some passes swept nothing
    assert_sweeps_counted(reg, len(seen), swept)
    # no compaction configured, no series
    plain = MetricsRegistry()
    _engine(Config(features=_fcfg(key_mode="exact")), plain)
    assert plain.get("rtfds_state_compact_sweeps_total",
                     table="customer") is None


def test_a_vacated_probe_prefix_still_resolves_after_the_pass():
    """The pass vacates entries that sit on live keys' probe paths (half
    the keys of a directory at load 0.5 are dead): every survivor is
    found again in its old slot, with its row, and a dead key that
    returns is admitted afresh into an empty row."""
    cfg = _fcfg(customer_capacity=64, terminal_capacity=64,
                key_mode="exact", keydir_probes=16)
    held = {**_ages(30, DEAD), **_ages(30, MID, 1000)}
    st = _built_state(cfg, held, held)
    slot_of_key = {}
    for k in held:
        s, hit = lookup_slots(st.customer_dir, jnp.asarray([k], jnp.uint32),
                              jnp.ones(1, bool), n_probes=16)
        assert bool(hit[0])
        slot_of_key[k] = int(s[0])
    st2, rec = _assert_pass_equals_oracle(st, NOW, cfg)
    assert tuple(rec) == (30, 30)
    keys = jnp.asarray(np.fromiter(held, np.uint32, len(held)))
    kd, slot, adm, _, _ = admit_slots(st2.customer_dir, keys,
                                   jnp.ones(len(held), bool), n_probes=16)
    assert np.asarray(adm).all()
    before = st.customer.tables()
    after = st2.customer.tables()
    for k, s in zip(held, np.asarray(slot).tolist()):
        if held[k] == MID:  # a survivor: its slot, its row
            assert s == slot_of_key[k]
            for a, b in zip(after, before):
                np.testing.assert_array_equal(a[s], b[s])
        else:  # came back: a fresh grant of a cleared row
            assert (np.asarray(after[0][s]) == -1).all()
    assert int(kd.free_top) == 64 - 60


# -- the pass's table-wide part works on the flat columns (PR 54) -------------
#
# ``WindowState.newest`` and ``clear_slots`` read and write a column as it
# is stored; what they have to equal is the plain ``[cap, nb]`` view, and
# the pass built on them the pass the parent built on that view — kept
# here, as the parent wrote it, as the reference.

FLAT_SHAPES = [
    pytest.param(16, 40, id="16x40-one-period"),
    pytest.param(48, 40, id="48x40-three-periods"),
    pytest.param(4096, 40, id="4096x40-whole-tiles"),
    pytest.param(64, 8, id="64x8-sixteen-slots-a-row"),
    pytest.param(16, 30, id="16x30-32-lanes"),
    pytest.param(100, 30, id="100x30-8-lanes"),
    pytest.param(5, 40, id="5x40-no-multiple-of-128"),
    pytest.param(64, 4, id="64x4-two-words-of-flags"),
]


def _random_table(cap, nb, seed):
    rng = np.random.default_rng(seed)
    bd = rng.integers(-1, 30000, (cap, nb)).astype(np.int32)
    bd[rng.random(cap) < 0.2] = -1  # whole rows still empty
    cols = [bd] + [rng.random((cap, nb), dtype=np.float32)
                   for _ in range(3)]
    return rng, cols, WindowState.from_tables(*map(jnp.asarray, cols))


@pytest.mark.parametrize("cap, nb", FLAT_SHAPES)
def test_flat_newest_equals_the_tables_row_maximum(cap, nb):
    _, cols, ws = _random_table(cap, nb, cap + nb)
    got = jax.jit(WindowState.newest)(ws)
    assert got.shape == (cap,) and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), cols[0].max(axis=1))


@pytest.mark.parametrize("cap, nb", FLAT_SHAPES)
def test_flat_clear_equals_the_tables_row_select(cap, nb):
    """The flagged slots' rows at their fills and every other element
    the bits it held; under a count of 0 no trip runs, whatever is
    flagged."""
    rng, cols, ws = _random_table(cap, nb, 7 * cap + nb)
    clear = jax.jit(WindowState.clear_slots)
    for share in (0.0, 0.3, 1.0):
        vacated = rng.random(cap) < share
        got = clear(ws, jnp.asarray(vacated), jnp.int32(vacated.sum()))
        for a, col, fill in zip(got.tables(), cols, FILLS):
            want = np.where(vacated[:, None], fill, col)
            assert np.asarray(a).tobytes() == want.tobytes()
    kept = clear(ws, jnp.ones(cap, bool), jnp.int32(0))
    for a, col in zip(kept.tables(), cols):
        assert np.asarray(a).tobytes() == col.tobytes()


def _parents_newest(ws):
    return jnp.max(ws.tables()[0], axis=1)


def _parents_clear_slots(ws, vacated, n_vacated):
    cap, nb = ws.capacity, ws.n_buckets

    def clear(col, fill):
        return jnp.where(vacated[:, None], fill,
                         col.reshape(cap, nb)).reshape(-1)

    return WindowState(*(clear(c, f) for c, f in zip(ws.columns(), FILLS)),
                       n_buckets=nb)


def _assert_pass_equals_the_parents(before, day, out, cfg, demote,
                                    monkeypatch):
    """``out`` — what the pass left of ``before`` (one chip's state, host
    leaves) — against the parent's formulation run on the same state:
    every leaf of the state, the counts and the payload to the bit."""
    with monkeypatch.context() as m:
        m.setattr(WindowState, "newest", _parents_newest)
        m.setattr(WindowState, "clear_slots", _parents_clear_slots)
        want = jax.jit(lambda st, d: compact_feature_state(
            st, d, cfg, demote_slots=demote))(before, jnp.int32(day))
    got, want = jax.tree.leaves(out), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    return np.asarray(out[1])


@pytest.mark.parametrize("case, swept", [
    pytest.param(_case_nothing_goes, (False, False), id="nothing-dead"),
    pytest.param(_case_one_table_gives, (True, False),
                 id="one-table-gives"),
    pytest.param(_case_dead_over_k, (True, True), id="both-give"),
    pytest.param(_case_tie_at_the_threshold, (True, False),
                 id="demote-variant"),
])
def test_pass_on_flat_columns_equals_the_parents_formulation(
        case, swept, monkeypatch):
    (customers, terminals), demote, knobs, _ = case()
    if "pack_lanes" in knobs:
        monkeypatch.setattr(keydir, "PACK_LANES", knobs["pack_lanes"])
    cfg = _fcfg(customer_capacity=64, terminal_capacity=64,
                key_mode="exact", keydir_probes=16,
                cold_highwater=knobs.get("cold_highwater", 0.75))
    st = jax.tree.map(np.array, _built_state(cfg, customers, terminals))
    out = jax.jit(lambda s, d: compact_feature_state(
        s, d, cfg, demote_slots=demote))(st, jnp.int32(NOW))
    n = _assert_pass_equals_the_parents(st, NOW, out, cfg, demote,
                                        monkeypatch)
    assert tuple(n > 0) == swept


def test_wide_directory_passes_equal_the_parents_formulation(monkeypatch):
    """Two key words an entry (``key_bits=64``) through ``engine.run()``:
    the same pass, the same change."""
    from test_sharded_exact import _Src

    cfg = Config(
        features=_fcfg(customer_capacity=512, terminal_capacity=512,
                       cms_width=1 << 10, key_mode="exact", key_bits=64,
                       compact_every=2),
        runtime=RuntimeConfig(batch_buckets=(256,), max_batch_rows=256,
                              trigger_seconds=0.0, precompile=False))
    eng = _engine(cfg)
    assert eng.state.feature_state.customer_dir.wide
    seen = spy_on_passes(eng)
    eng.run(_Src(_drifting(10)))
    assert len(seen) == 5
    gave = [_assert_pass_equals_the_parents(
        before, day, out, cfg.features, 0, monkeypatch).sum()
        for before, day, out in seen]
    assert min(gave) == 0 < max(gave)  # empty passes and giving ones


@pytest.mark.parametrize("cold", [False, True],
                         ids=["dead-only", "cold-tier-demotes"])
def test_mesh_passes_equal_the_parents_formulation(cold, tmp_path,
                                                   monkeypatch):
    """Four CPU devices: the pass under ``shard_map``, a device's block of
    every column a quarter of the table — against the parent's
    formulation run on each device's share of the state."""
    from real_time_fraud_detection_system_tpu.runtime.sharded_engine import (
        ShardedScoringEngine,
    )
    from test_sharded_exact import _Src, _model
    from test_sharded_exact import _cfg as mesh_cfg

    n_dev = 4
    if cold:
        from test_cold_exact import _churn

        cfg = mesh_cfg(cust_cap=256, term_cap=256, rows=64,
                       keydir_probes=16, compact_every=1,
                       cold_store=str(tmp_path / "cold"),
                       cold_demote_slots=64, cold_highwater=0.5)
        batches, demote = _churn(7, 8, 64, 1024), 64
    else:
        cfg = mesh_cfg(compact_every=3)
        batches, demote = _drifting(12), 0
    cfg = dc.replace(cfg, runtime=dc.replace(cfg.runtime,
                                             precompile=False))
    eng = ShardedScoringEngine(cfg, "logreg", *_model(), n_devices=n_dev)
    seen = spy_on_passes(eng)
    eng.run(_Src(batches))
    assert len(seen) == (8 if cold else 4)
    gave = []
    for before, day, out in seen:
        for s in range(n_dev):
            mine = functools.partial(_device_share, n_dev=n_dev, s=s)
            share = (mine(out[0]), out[1][s]) + tuple(
                jax.tree.map(lambda x: x[s], pay) for pay in out[2:])
            gave.append(_assert_pass_equals_the_parents(
                mine(before), day, share, cfg.features, demote,
                monkeypatch).sum())
    assert min(gave) == 0 < max(gave)


def test_the_pass_chunks_never_hold_the_whole_input():
    """K is half of a small input at most (``ops/cms.chunk_rows``' rule:
    a loop whose one chunk is the input gets hoisted) and 16,384 — the
    width chosen on the chip — at the benchmark's sizes."""
    assert keydir.pack_lanes(1 << 24) == keydir.pack_lanes(131072) == 16384
    assert keydir.pack_lanes(64) == 32 and keydir.pack_lanes(1) == 1


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
