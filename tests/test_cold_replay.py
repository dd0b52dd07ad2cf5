"""The cold tier under a calendar that moves (`features.cold_store` with
``--source replay``): cards come back after days or weeks, and what no
window can see any more is forgotten — by the hot tier's compaction and,
with it, by the cold store.

Held through ``engine.run()`` against the plain NumPy walk of
``tests/test_replay_days.py`` (one count and one sum a (key, day), nothing
forgotten, no tier) and against an engine whose hot tier holds every key:
the 160-day replay with a hot tier a quarter of the ids a horizon touches;
a key promoted into a ring that has lapped one of its cold buckets; a key
whose cold rows are all dead when it returns; a batch of two event days
that promotes for both; more returning keys than the widest promote
program, batch after batch; the store's resident keys against the ids
touched inside the horizon; and the demote pass's selection against a
sort, over every age a live key can have.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import RuntimeConfig
from real_time_fraud_detection_system_tpu.features.online import (
    _select_oldest,
)
from real_time_fraud_detection_system_tpu.utils.metrics import MetricsRegistry

from test_cold_exact import (  # noqa: E402 (pytest adds tests/ to path)
    EMPTY,
    _all_hot,
    _assert_exact_counters,
    _assert_same_rows,
    _build,
    _cols,
    _fcfg,
    _Sink,
    _Source,
)
from test_replay_days import (  # noqa: E402
    BATCH,
    DAY0,
    N_DAYS,
    US_PER_DAY,
    TableSource,
    agree,
    reference_features,
    replay_rows,
)

HORIZON = 37  # delay_days + max(windows): what any window can see


def value(reg, name, **labels):
    m = reg.get(name, **labels)
    return 0.0 if m is None else m.value


# -- the 160-day replay -------------------------------------------------------


class Watch(_Sink):
    """Collects the results and, beside each, what the store held when it
    was appended (the writer thread runs a batch or two behind the loop)."""

    def __init__(self, engine):
        super().__init__()
        self._cold, self.resident = engine._cold, []

    def append(self, res):
        super().append(res)
        self.resident.append(self._cold.keys_count)


@pytest.fixture(scope="module")
def replay():
    cols = replay_rows()
    want, _ = reference_features(cols, BATCH)
    rt = RuntimeConfig(batch_buckets=(BATCH,), max_batch_rows=BATCH,
                       precompile=True)
    hot = _Sink()
    _all_hot(_fcfg("", cap=256, demote=64), rt).run(TableSource(cols), hot)
    return {"cols": cols, "want": want, "hot": hot.results,
            "day": cols["tx_datetime_us"] // US_PER_DAY - DAY0}


@pytest.mark.parametrize("depth,precompile", [
    (1, True), (2, True), (1, False), (2, False),
], ids=["d1-aot", "d2-aot", "d1-jit", "d2-jit"])
def test_cold_armed_replay_of_160_days_equals_reference_and_all_hot(
        tmp_path, replay, depth, precompile):
    """A hot tier of 128 slots a table kept at 8 by a pass after every
    batch, under ~60 ids a table in use inside a horizon: most of a
    batch's ~25 keys a table come back from the store, retired ids expire
    there, and every delivered row is the plain reference's and the
    all-hot engine's."""
    rt = RuntimeConfig(batch_buckets=(BATCH,), max_batch_rows=BATCH,
                       pipeline_depth=depth, precompile=precompile)
    reg = MetricsRegistry()
    eng = _build(_fcfg(str(tmp_path / "cold"), cap=128, demote=64,
                       highwater=0.0625), rt, reg)
    sink = Watch(eng)
    stats = eng.run(TableSource(replay["cols"]), sink)
    _assert_same_rows(sink.results, replay["hot"])
    _assert_exact_counters(eng, reg, stats, precompile)
    got = np.concatenate([r.features for r in sink.results])
    agree({"got": got.astype(np.float64), "want": replay["want"]},
          np.arange(len(got)))
    promoted = value(reg, "rtfds_feature_cold_promotions_total")
    demoted = value(reg, "rtfds_feature_cold_demotions_total")
    expired = value(reg, "rtfds_feature_cold_expired_total")
    assert promoted > 2000 and demoted > promoted
    # an id retires after 24 (32) days and is never seen again: all but
    # the newest horizon's worth were dropped as dead
    assert expired > 2 * (N_DAYS - HORIZON - 40)
    # a key leaves the store promoted, expired or as a dead return
    dead = value(reg, "rtfds_feature_cold_dead_returns_total")
    assert demoted - promoted - expired - dead == eng._cold.keys_count
    assert value(reg, "rtfds_feature_cold_keys") == eng._cold.keys_count
    # ages at demotion: a live key's, 1..37 days
    age = value(reg, "rtfds_feature_cold_demote_age_days_total")
    assert 1.0 <= age / demoted <= HORIZON
    assert reg.get("rtfds_phase_seconds",
                   phase="cold_expire").count == len(sink.results)


def test_the_store_holds_no_more_than_the_horizon_touched(tmp_path, replay):
    """Resident keys ≤ the ids touched inside the horizon, all the way
    through the 160 days (5 days of room either side: the writer thread
    that reads the count runs behind the loop, and a dead key waits for
    the next pass). Without the horizon every retired id stays: ~300 keys
    at the end against ~130."""
    rt = RuntimeConfig(batch_buckets=(BATCH,), max_batch_rows=BATCH,
                       pipeline_depth=2, precompile=True)
    eng = _build(_fcfg(str(tmp_path / "cold"), cap=128, demote=64,
                       highwater=0.0625), rt, MetricsRegistry())
    sink = Watch(eng)
    eng.run(TableSource(replay["cols"]), sink)
    cols, day = replay["cols"], replay["day"]
    for i, held in enumerate(sink.resident):
        now = day[min((i + 1) * BATCH, len(day)) - 1]
        near = (day >= now - HORIZON - 5) & (day <= now + 5)
        touched = (np.unique(cols["customer_id"][near]).size
                   + np.unique(cols["terminal_id"][near]).size)
        assert held <= touched, (i, now, held, touched)
    assert max(sink.resident) > 50  # the tier was in use
    ever = (np.unique(cols["customer_id"]).size
            + np.unique(cols["terminal_id"]).size)
    assert sink.resident[-1] < ever / 2


# -- crafted streams ------------------------------------------------------------


ROWS = 64


def stream(days_and_keys):
    """``[(day | [day of each row], customer ids)]`` → batches of ``ROWS``
    rows; a terminal is its customer + 1,000,000."""
    out = []
    for i, (day, cust) in enumerate(days_and_keys):
        cust = np.asarray(cust, np.int64)
        assert len(cust) == ROWS
        cols = _cols(cust, cust + 1_000_000, DAY0, first_tx=i * ROWS)
        us = (DAY0 + np.broadcast_to(np.asarray(day), (ROWS,))) * US_PER_DAY \
            + np.arange(ROWS) * 1_000_000
        cols["tx_datetime_us"] = us.astype(np.int64)
        out.append(cols)
    return out


def fillers(base, distinct=40):
    """``ROWS`` rows over ``distinct`` keys nobody else uses."""
    return base + np.arange(ROWS) % distinct


def with_key(key, times, rest):
    rest = np.array(rest)
    rest[:times] = key
    return rest


def spy_on_promotes(eng, log):
    """``log`` gets ``(table, index of the batch it goes ahead of, live
    keys)`` of every promote program dispatched."""
    dispatch = eng._dispatch_step
    steps = [0]

    def spy(key, fn, *args):
        if key[0] == "promote":
            lanes = np.asarray(args[1][key[1]][0]).reshape(-1)
            log.append((key[1], steps[0], lanes[lanes != EMPTY]))
        elif key[0] == "step":
            steps[0] += 1
        return dispatch(key, fn, *args)

    eng._dispatch_step = spy


def served(tmp_path, batches, every, depth=2, spy_promotes=None):
    """The cold-armed engine's results for ``batches`` (held to the
    all-hot engine's and to the plain reference), its registry and
    itself."""
    rt = RuntimeConfig(batch_buckets=(ROWS,), max_batch_rows=ROWS,
                       pipeline_depth=depth, precompile=True)
    fcfg = _fcfg(str(tmp_path / "cold"), cap=128, demote=64, highwater=0.125,
                 every=every)
    reg = MetricsRegistry()
    eng = _build(fcfg, rt, reg)
    if spy_promotes is not None:
        spy_on_promotes(eng, spy_promotes)
    sink, ctrl = _Sink(), _Sink()
    stats = eng.run(_Source(batches), sink)
    _all_hot(fcfg, rt).run(_Source(batches), ctrl)
    _assert_same_rows(sink.results, ctrl.results)
    _assert_exact_counters(eng, reg, stats, aot=True)
    cols = {k: np.concatenate([b[k] for b in batches]) for k in batches[0]}
    want, _ = reference_features(cols, ROWS)
    got = np.concatenate([r.features for r in sink.results])
    agree({"got": got.astype(np.float64), "want": want}, np.arange(len(got)))
    return got, want, cols, reg, eng


K = 7  # the key the crafted streams follow


@pytest.mark.parametrize("depth", [1, 2], ids=["d1", "d2"])
def test_a_promoted_key_whose_ring_was_lapped_answers_as_the_reference(
        tmp_path, depth):
    """K pays on days 0, 10 and 12, is demoted, and comes back on day 40:
    its newest day is inside the horizon, so its rows are promoted — the
    bucket of day 0 with them, into the ring position day 40 writes. The
    1-day count is day 40's alone, the 30-day count day 12's and day
    40's, and the delayed terminal window still sees days 10 and 12."""
    promotes = []
    batches = stream([
        (0, with_key(K, 3, fillers(100))),
        (10, with_key(K, 2, fillers(200))),
        (12, with_key(K, 4, fillers(300))),
        (20, fillers(400)), (30, fillers(500)), (36, fillers(600)),
        (40, with_key(K, 5, fillers(700))),
        (41, with_key(K, 1, fillers(800))),
    ])
    got, want, cols, reg, eng = served(tmp_path, batches, every=1,
                                       depth=depth, spy_promotes=promotes)
    back = np.flatnonzero((cols["customer_id"] == K)
                          & (cols["tx_datetime_us"] // US_PER_DAY
                             == DAY0 + 40))
    assert len(back) == 5
    # [amount, weekend, night, c1 n, c1 avg, c7 n, c7 avg, c30 n, c30 avg,
    #  t1 n, t1 risk, t7 n, t7 risk, t30 n, t30 risk]
    assert (got[back, 3] == 5).all()  # not 5 + day 0's 3
    assert (got[back, 7] == 5 + 4).all()  # day 12 is 28 days back
    assert (got[back, 13] == 2 + 4).all()  # days 4..33: 10 and 12
    for table, key in (("customer", K), ("terminal", K + 1_000_000)):
        # hot through day 12 (a key touched on the pass's newest day
        # stays), demoted by day 20's pass, back once
        assert [b for t, b, keys in promotes
                if t == table and key in keys] == [6], table
    assert value(reg, "rtfds_feature_cold_dead_returns_total") == 0


@pytest.mark.parametrize("depth", [1, 2], ids=["d1", "d2"])
def test_a_key_whose_cold_rows_are_dead_returns_afresh(tmp_path, depth):
    """K pays on day 0 and is demoted by the first pass; the second pass
    (newest day 36) may not drop it yet; on day 38 it is back, and day 0
    is 38 days away: no lane, no promotion, a count of one a table, the
    store has forgotten it, and its rows answer as a new key's."""
    promotes = []
    few = 16  # a pass every 4 batches has to find room for what they admit
    batches = stream([
        (0, with_key(K, 3, fillers(100, few))), (1, fillers(200, few)),
        (2, fillers(300, few)), (3, fillers(400, few)),
        (10, fillers(500, few)), (20, fillers(600, few)),
        (30, fillers(700, few)), (36, fillers(800, few)),
        (38, with_key(K, 2, fillers(900, few))),
        (39, with_key(K, 1, fillers(1000, few))),
        (40, fillers(1100, few)), (41, fillers(1200, few)),
    ])
    got, want, cols, reg, eng = served(tmp_path, batches, every=4,
                                       depth=depth, spy_promotes=promotes)
    assert value(reg, "rtfds_feature_cold_demotions_total") > 100
    assert value(reg, "rtfds_feature_cold_dead_returns_total") == 2
    for table, key in (("customer", K), ("terminal", K + 1_000_000)):
        assert not [b for t, b, keys in promotes
                    if t == table and key in keys], table
        # what the store holds of it now, if a later pass demoted it
        # again, is its new life: no bucket of day 0
        for bd, *_ in eng._cold.get_rows(table, [key]).values():
            assert bd.max() >= DAY0 + 38 and (bd[bd >= 0] >= DAY0 + 38).all()
    back = np.flatnonzero(cols["customer_id"] == K)[3:]
    assert (got[back[:2], 7] == 2).all() and got[back[2], 7] == 3
    # the fillers of days 0..3 came back never: the third pass (newest
    # day 41) dropped them
    assert value(reg, "rtfds_feature_cold_expired_total") >= 2 * 3 * few


def test_a_batch_of_two_event_days_promotes_for_both(tmp_path):
    """A and B are demoted; one batch then holds A on day 9 and B on day
    10 — the day rolls over inside it. Both are promoted ahead of that
    one step and both answer with their history."""
    a, b = 7, 8
    promotes = []
    two_days = np.r_[np.full(ROWS // 2, 9), np.full(ROWS // 2, 10)]
    keys = fillers(600)
    keys[:3], keys[ROWS // 2:ROWS // 2 + 2] = a, b
    batches = stream([
        (0, with_key(a, 4, fillers(100))),
        (1, with_key(b, 6, fillers(200))),
        (5, fillers(300)), (6, fillers(400)), (8, fillers(500)),
        (two_days, keys),
    ])
    got, want, cols, reg, eng = served(tmp_path, batches, every=1,
                                       spy_promotes=promotes)
    for table, off in (("customer", 0), ("terminal", 1_000_000)):
        at = [set(keys.tolist()) for t, batch, keys in promotes
              if t == table and batch == 5]
        assert at and {a + off, b + off} <= set().union(*at), table
    last = slice(5 * ROWS, 6 * ROWS)
    rows_a = np.flatnonzero(cols["customer_id"][last] == a) + 5 * ROWS
    rows_b = np.flatnonzero(cols["customer_id"][last] == b) + 5 * ROWS
    assert (got[rows_a, 7] == 4 + 3).all() and (got[rows_a, 3] == 3).all()
    assert (got[rows_b, 7] == 6 + 2).all() and (got[rows_b, 3] == 2).all()
    assert value(reg, "rtfds_batches_multi_day_total") == 1


def test_more_returning_keys_than_the_widest_program_batch_after_batch(
        tmp_path):
    """Three sets of 600 keys take turns, a day a batch: the set a batch
    brings back was demoted two passes earlier, so EVERY batch from the
    fourth on returns 600 keys a table against a ladder that stops at 256
    lanes — three payloads a table, every time."""
    rows, ladder = 1024, (256,)
    rt = RuntimeConfig(batch_buckets=(rows,), max_batch_rows=rows,
                       pipeline_depth=2, precompile=True)
    fcfg = _fcfg(str(tmp_path / "cold"), cap=4096, demote=2048,
                 highwater=0.1)
    reg = MetricsRegistry()
    eng = _build(fcfg, rt, reg)
    eng._promote_widths = ladder
    sets = [10_000 * (s + 1) + np.arange(rows) % 600 for s in range(3)]
    batches = [_cols(sets[i % 3], sets[i % 3] + 1_000_000, 20200 + i,
                     first_tx=i * rows) for i in range(9)]
    promotes, lanes = [], {}
    spy_on_promotes(eng, promotes)
    sink, ctrl = _Sink(), _Sink()
    stats = eng.run(_Source(batches), sink)
    _all_hot(fcfg, rt).run(_Source(batches), ctrl)
    _assert_same_rows(sink.results, ctrl.results)
    _assert_exact_counters(eng, reg, stats, aot=True)
    for table, batch, keys in promotes:
        lanes.setdefault((table, batch), []).append(len(keys))
    for table in ("customer", "terminal"):
        wide = [b for (t, b), n in lanes.items()
                if t == table and sum(n) == 600]
        assert wide == [3, 4, 5, 6, 7, 8], (table, lanes)
        assert all(lanes[table, b] == [256, 256, 88] for b in wide)
    assert value(reg, "rtfds_feature_cold_promotions_total") >= 2 * 5 * 600


# -- the demote pass's selection ------------------------------------------------


@pytest.mark.parametrize("quota", [0, 1, 100, 1000, 5000],
                         ids=lambda q: f"quota{q}")
@pytest.mark.parametrize("ages", ["1..37", "one-age", "two-ages"])
def test_select_oldest_is_the_sort_over_every_age_a_live_key_has(
        ages, quota):
    """``_select_oldest`` (an age histogram, no sort) takes what a stable
    sort by age would: the ``quota`` oldest eligible entries, ties to the
    lowest index, all of them where fewer are eligible — with ages spread
    over the whole 1..37 a moving calendar gives, and bunched on one or
    two as a standing one does."""
    rng = np.random.default_rng(53)
    n = 4096
    age = {"1..37": rng.integers(1, HORIZON + 1, n),
           "one-age": np.full(n, 1),
           "two-ages": rng.choice([1, 36], n)}[ages]
    eligible = rng.random(n) < 0.6
    got = np.asarray(_select_oldest(
        jnp.asarray(eligible), jnp.asarray(age, jnp.int32),
        jnp.int32(quota), HORIZON))
    idx = np.flatnonzero(eligible)
    order = idx[np.argsort(-age[idx], kind="stable")][:quota]
    want = np.zeros(n, bool)
    want[order] = True
    assert np.array_equal(got, want)
    assert got.sum() == min(quota, eligible.sum())
