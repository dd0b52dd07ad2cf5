"""Event time that moves: ``ScoringEngine`` with ``key_mode="exact"`` and
compaction on, over a replay of 160 event days (more than twice the ring's
40 buckets plus the 37 days any window can see), against a plain NumPy
reference of the same semantics.

The reference keeps one count and one dollar sum a (key, day) and forgets
nothing: window w at day d covers days [d-w+1, d], the terminal windows
shifted back by the label delay; a batch's rows enter first and are then
queried, each on its own day. The engine keeps 40 day buckets a key, stamps
and resets a bucket that a later day reuses, masks a bucket out by its age,
and every ``compact_every`` batches gives back the slots of keys whose
newest day no query can see — none of which may show in an answer.
"""

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime.engine import (
    ScoringEngine,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (
    MetricsRegistry,
)

US_PER_DAY = 86_400_000_000
DAY0 = 20_250  # 2025-06-11, a Wednesday
N_DAYS, ROWS_PER_DAY, BATCH = 160, 50, 64  # a batch is never whole days
WINDOWS, DELAY = (1, 7, 30), 7
ACTIVE_C, ACTIVE_T = 24, 32  # ids in use on one day; one is issued and
# one retires every day, so an id lives 24 (32) days
HOT_C, HOT_T = 900_001, 900_002  # in use every day: every bucket reused
BACK_C, BACK_T = 900_003, 900_004  # seen on days 2-3, then from day 100
AVG_COLUMNS = (4, 6, 8)
EXACT_COLUMNS = tuple(j for j in range(15) if j not in AVG_COLUMNS)


def replay_rows():
    """The replayed table, in ``tx_id`` order: ascending event time, ids
    from a window of the id space that moves up one a day."""
    rng = np.random.default_rng(50)
    n = N_DAYS * ROWS_PER_DAY
    k = np.arange(n)
    day = k // ROWS_PER_DAY
    us = (DAY0 + day) * US_PER_DAY \
        + (k % ROWS_PER_DAY) * US_PER_DAY // ROWS_PER_DAY
    customer = 1000 + day + rng.integers(0, ACTIVE_C, n)
    terminal = 5000 + day + rng.integers(0, ACTIVE_T, n)
    hot = rng.random(n) < 0.15
    customer[hot], terminal[hot] = HOT_C, HOT_T
    back = ((day == 2) | (day == 3) | (day >= 100)) & (rng.random(n) < 0.2)
    customer[back], terminal[back] = BACK_C, BACK_T
    return {
        "tx_id": k.astype(np.int64),
        "tx_datetime_us": us.astype(np.int64),
        "customer_id": customer.astype(np.int64),
        "terminal_id": terminal.astype(np.int64),
        "tx_amount_cents": rng.integers(100, 50_000, n).astype(np.int64),
        "kafka_ts_ms": np.full(n, DAY0 * 86_400_000, np.int64),  # wall time
    }


class TableSource:
    """The engine's source protocol over the table, ``BATCH`` rows a poll."""

    def __init__(self, cols):
        self._cols, self._at = cols, 0
        self.offsets = [0]

    def seek(self, offsets):
        self._at = int(offsets[0])

    def poll_batch(self):
        s = self._at
        if s >= len(self._cols["tx_id"]):
            return None
        self._at = s + BATCH
        self.offsets = [self._at]
        return {k: v[s:s + BATCH] for k, v in self._cols.items()}


class Collect:
    def __init__(self):
        self.batches = []

    def append(self, res):
        self.batches.append(res)


def reference_features(cols, batch_rows):
    """[n, 15] float64 by the plain semantics, and per row the 30-day
    customer count a reference WITHOUT expiry would give."""
    n = len(cols["tx_id"])
    day = cols["tx_datetime_us"] // US_PER_DAY
    tod_s = (cols["tx_datetime_us"] % US_PER_DAY) // 1_000_000
    amount = (cols["tx_amount_cents"] / 100.0).astype(np.float32)
    c_cnt, c_amt, t_cnt = {}, {}, {}
    c_days = {}  # customer -> {day: count}, for the count without expiry
    out = np.zeros((n, 15))
    ever = np.zeros(n)
    for s in range(0, n, batch_rows):
        rows = range(s, min(s + batch_rows, n))
        for i in rows:  # update ...
            kc = (cols["customer_id"][i], day[i])
            c_cnt[kc] = c_cnt.get(kc, 0) + 1
            c_amt[kc] = c_amt.get(kc, 0.0) + float(amount[i])
            c_days.setdefault(kc[0], {})[kc[1]] = c_cnt[kc]
            kt = (cols["terminal_id"][i], day[i])
            t_cnt[kt] = t_cnt.get(kt, 0) + 1
        for i in rows:  # ... then query
            c, t, d = cols["customer_id"][i], cols["terminal_id"][i], day[i]
            f = [float(amount[i]), float((d + 3) % 7 >= 5),
                 float(tod_s[i] // 3600 <= 6)]
            for w in WINDOWS:
                cnt = sum(c_cnt.get((c, x), 0) for x in range(d - w + 1,
                                                              d + 1))
                amt = sum(c_amt.get((c, x), 0.0) for x in range(d - w + 1,
                                                                d + 1))
                f += [cnt, amt / max(cnt, 1)]
            for w in WINDOWS:
                f += [sum(t_cnt.get((t, x), 0) for x in
                          range(d - DELAY - w + 1, d - DELAY + 1)), 0.0]
            out[i] = f
            ever[i] = sum(v for x, v in c_days[c].items() if x <= d)
    return out, ever


@pytest.fixture(scope="module")
def replayed():
    cols = replay_rows()
    cfg = Config(
        features=FeatureConfig(
            customer_capacity=256, terminal_capacity=256,
            windows=WINDOWS, delay_days=DELAY, n_day_buckets=40,
            key_mode="exact", keydir_probes=16, compact_every=4,
            cms_width=1 << 10),
        runtime=RuntimeConfig(batch_buckets=(BATCH,), max_batch_rows=BATCH,
                              precompile=True))
    registry = MetricsRegistry()
    engine = ScoringEngine(
        cfg, kind="logreg", params=init_logreg(15),
        scaler=Scaler(mean=np.zeros(15, np.float32),
                      scale=np.ones(15, np.float32)),
        metrics=registry)
    engine.precompile()
    sink = Collect()
    stats = engine.run(TableSource(cols), sink)
    assert stats["rows"] == len(cols["tx_id"])
    got = np.concatenate([b.features for b in sink.batches])
    ids = np.concatenate([b.tx_id for b in sink.batches])
    assert np.array_equal(ids, cols["tx_id"])
    want, ever = reference_features(cols, BATCH)
    return {"cols": cols, "got": got.astype(np.float64), "want": want,
            "ever": ever, "registry": registry,
            "day": cols["tx_datetime_us"] // US_PER_DAY - DAY0}


def agree(r, rows) -> None:
    """Integer columns equal, averages within 5e-6 relative, on ``rows``."""
    got, want = r["got"][rows], r["want"][rows]
    assert len(got) > 0
    wrong = got[:, EXACT_COLUMNS] != want[:, EXACT_COLUMNS]
    assert not wrong.any(), np.argwhere(wrong)[:5]
    rel = np.abs(got[:, AVG_COLUMNS] - want[:, AVG_COLUMNS]) / np.maximum(
        np.abs(want[:, AVG_COLUMNS]), 1e-30)
    assert rel.max() <= 5e-6, rel.max()


def counter(r, name, **labels):
    m = r["registry"].get(name, **labels)
    return 0.0 if m is None else m.value


def a_window_expires(r):
    # rows whose customer has history older than 30 days that the
    # 30-day count must have dropped
    rows = np.flatnonzero(r["ever"] > r["want"][:, 7])
    assert (r["cols"]["customer_id"][rows] == HOT_C).any()
    agree(r, rows)
    agree(r, np.arange(len(r["got"])))


def a_bucket_is_reused(r):
    # the hot keys are seen every day: on day d >= 40 their row lands in
    # the bucket day d - 40 stamped, and day d - 80's before it
    day, c = r["day"], r["cols"]["customer_id"]
    rows = np.flatnonzero((c == HOT_C) & (day >= 80))
    seen = set(day[c == HOT_C].tolist())
    assert any(d - 40 in seen and d - 80 in seen for d in day[rows])
    agree(r, rows)
    # its 1-day count is today's rows alone, not today's + day d-40's
    assert (r["got"][rows, 3] == r["want"][rows, 3]).all()
    assert r["want"][rows, 3].max() < r["ever"][rows].min()


def a_batch_holds_two_days(r):
    day = r["day"]
    two = [s for s in range(0, len(day), BATCH)
           if day[s] != day[min(s + BATCH, len(day)) - 1]]
    assert len(two) > 100
    agree(r, np.concatenate([np.arange(s, min(s + BATCH, len(day)))
                             for s in two]))


def a_reclaimed_key_comes_back(r):
    # BACK_* are last seen on day 3: dead from day 41 on, their slots
    # given back by the next pass, admitted afresh on day 100
    day, c = r["day"], r["cols"]["customer_id"]
    first_back = np.flatnonzero((c == BACK_C) & (day >= 100))[0]
    assert (c[day <= 3] == BACK_C).sum() > 0
    assert r["want"][first_back, 7] == r["got"][first_back, 7] >= 1
    assert r["want"][first_back, 7] < r["ever"][first_back]
    agree(r, np.flatnonzero(c == BACK_C))
    agree(r, np.flatnonzero(r["cols"]["terminal_id"] == BACK_T))
    # every id but the newest retires and is reclaimed: far more slots
    # were given back than the tables hold
    for table, live in (("customer", ACTIVE_C), ("terminal", ACTIVE_T)):
        back = counter(r, "rtfds_feature_slots_reclaimed_total", table=table)
        assert back >= N_DAYS - 37 - 20 - live, (table, back)
    assert counter(r, "rtfds_feature_tier_rows_total", tier="cms") == 0


def weekends_and_nights(r):
    for col in (1, 2):
        assert set(np.unique(r["want"][:, col])) == {0.0, 1.0}
        assert (r["got"][:, col] == r["want"][:, col]).all()
    # 2025-06-14 is a Saturday
    assert r["want"][r["day"] == 3, 1].all()
    assert not r["want"][r["day"] == 2, 1].any()


def the_two_counters(r):
    day = r["day"]
    first_batch_day = day[:BATCH].max()
    assert counter(r, "rtfds_event_day_rollovers_total") \
        == day.max() - first_batch_day
    two = sum(1 for s in range(0, len(day), BATCH)
              if day[s] != day[min(s + BATCH, len(day)) - 1])
    assert counter(r, "rtfds_batches_multi_day_total") == two
    assert counter(r, "rtfds_batches_total") == -(-len(day) // BATCH)
    assert counter(r, "rtfds_state_compactions_total") \
        == (len(day) // BATCH + (len(day) % BATCH > 0)) // 4


@pytest.mark.parametrize("case", [
    a_window_expires, a_bucket_is_reused, a_batch_holds_two_days,
    a_reclaimed_key_comes_back, weekends_and_nights, the_two_counters,
], ids=lambda f: f.__name__)
def test_a_replay_of_160_event_days_answers_as_the_plain_reference(
        replayed, case):
    case(replayed)


@pytest.fixture(scope="module")
def generated(tmp_path_factory):
    """A generated table of 100 days and a model fit on it, through the
    console script's own sub-commands."""
    from real_time_fraud_detection_system_tpu.cli import main as cli_main

    work = tmp_path_factory.mktemp("replay")
    txs, model = str(work / "txs.npz"), str(work / "model.npz")
    assert cli_main(["datagen", "--out", txs, "--customers", "120",
                     "--terminals", "240", "--days", "100"]) == 0
    assert cli_main(["train", "--data", txs, "--model", "logreg",
                     "--out-model", model, "--delta-train", "40",
                     "--delta-delay", "7", "--delta-test", "20",
                     "--epochs", "1"]) == 0
    return work, txs, model


def scored(work, txs, model, name, *options):
    import pyarrow.parquet as pq

    from real_time_fraud_detection_system_tpu.cli import main as cli_main

    out = str(work / name)
    assert cli_main(["score", "--data", txs, "--model-file", model,
                     "--source", "replay", "--batch-rows", "256",
                     "--out", out, *options]) == 0
    table = pq.read_table(out).to_pandas().sort_values("tx_id")
    return table.reset_index(drop=True)


@pytest.mark.parametrize("every", [2, 16])
def test_console_script_replay_with_exact_keys_and_compaction_matches_direct(
        generated, every, capsys):
    """``rtfds score --source replay --key-mode exact
    --state-compact-every N`` over 100 event days (two and a half turns of
    the ring; ~3 batches an event day, so a pass every 2 batches is more
    than one a day and one every 16 is one every ~5 days): every column of
    the sink but the wall clock's equals the ``direct`` run's, bit for bit."""
    work, txs, model = generated
    direct = scored(work, txs, model, "direct")
    exact = scored(work, txs, model, f"exact{every}", "--key-mode", "exact",
                   "--state-compact-every", str(every))
    stats = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    assert len(direct) == len(exact) > 10_000
    days = direct["tx_datetime_us"] // US_PER_DAY
    assert days.max() - days.min() >= 2 * 40
    for column in direct.columns.drop("processed_at_us"):  # wall time
        assert (direct[column].to_numpy() == exact[column].to_numpy()).all(), \
            column
    assert stats  # the run's JSON line was printed
