"""The plain reference keeps its tables over the keys a run touched; its
answers are those of the dense ``[universe, days]`` tables it replaced
(``dense_reference.py``, the oracle), to the bit: on seeded toy traffic
with repeated keys, keys that come back after the ring forgot them, keys
asked for that were never seen, ids far past any slot count, and in the
control's lower precision."""

import numpy as np
import pytest

from benchmark import reference
from benchmark.tests.dense_reference import DenseWindowReference

FEATURES = {"windows": [1, 7, 30], "delay_days": 7, "n_day_buckets": 40,
            "night_end_hour": 6, "weekend_start_weekday": 5}
DAY0 = 20_250


def batch(rng, n, day, n_customers, n_terminals, hot=0.0):
    c = rng.integers(0, n_customers, n)
    c[rng.random(n) < hot] = 7  # one customer many times a batch
    return {
        "customer_id": c.astype(np.int64),
        "terminal_id": rng.integers(0, n_terminals, n).astype(np.int64),
        "tx_datetime_us": (day * reference.US_PER_DAY + rng.integers(
            0, reference.US_PER_DAY, n)).astype(np.int64),
        "tx_amount_cents": rng.integers(1, 250_000, n).astype(np.int64),
    }


@pytest.mark.parametrize("low", [False, True])
@pytest.mark.parametrize("n_days", [12, 47])  # 47 > the ring's 40 days
def test_sparse_equals_dense_to_the_bit(low, n_days):
    nc, nt = 3000, 5000
    rng = np.random.default_rng(4000 + n_days + low)
    args = (FEATURES, nc, nt, DAY0, n_days)
    sparse = reference.WindowReference(*args, lower_precision=low)
    dense = DenseWindowReference(*args, lower_precision=low)
    seen = set()
    for i in range(3 * n_days):
        # days advance and never go back (no late rows), keys repeat
        # within a batch, across batches and across the ring's horizon
        cols = batch(rng, 700, DAY0 + i // 3, nc, nt, hot=0.1)
        sparse.update(cols)
        dense.update(cols)
        seen.update(cols["customer_id"].tolist())
        got, want = sparse.features(cols), dense.features(cols)
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
        # asked without an update: keys the run has seen and keys it never
        # will (a third of these ids are past the universe's busiest part)
        probe = batch(rng, 300, DAY0 + i // 3, nc, nt)
        assert sparse.features(probe).tobytes() == \
            dense.features(probe).tobytes()
    # the tables hold the touched keys, not the universe
    assert sparse.c_rows.count == len(seen) <= len(sparse.c_cnt)


def test_memory_follows_the_touched_keys_not_the_universe():
    """Ids drawn from a universe no dense table could hold (10^15: card
    numbers): a few thousand rows, a few thousand table rows; a key never
    seen reads as an empty history."""
    rng = np.random.default_rng(5)
    ref = reference.WindowReference(FEATURES, 10 ** 15, 10 ** 15, DAY0, 3)
    cols = batch(rng, 5000, DAY0, 10 ** 15, 10 ** 15)
    ref.update(cols)
    ref.update(cols)  # the same keys again: no new row
    assert ref.c_rows.count == len(np.unique(cols["customer_id"]))
    assert len(ref.c_cnt) < 3 * 5000
    f = ref.features(cols)
    assert (f[:, 3] >= 2).all()  # both updates are in the 1-day count
    never = dict(cols, customer_id=cols["customer_id"] + 1,
                 terminal_id=cols["terminal_id"] + 1)
    g = ref.features(never)
    assert (g[:, 3:] == 0).all()


def test_key_rows_merge_keeps_every_key_its_row():
    rows = reference.KeyRows()
    rows.MERGE = 64  # merge often
    rng = np.random.default_rng(9)
    seen = {}
    for _ in range(50):
        keys = rng.integers(0, 2000, 97).astype(np.int64)
        got = rows.admit(keys)
        for k, r in zip(keys.tolist(), got.tolist()):
            assert seen.setdefault(k, r) == r
        np.testing.assert_array_equal(rows.find(keys), got)
    assert sorted(seen.values()) == list(range(rows.count))
    assert (rows.find(np.array([5000, -3], np.int64)) == -1).all()
