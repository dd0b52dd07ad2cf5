"""Op-level tests: window ring buffers vs brute force, CMS bounds, dedup."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.ops import (
    cms_init,
    cms_query,
    cms_update,
    hash_u32,
    init_window_state,
    latest_wins_mask,
    latest_wins_mask_np,
    multi_hash,
    query_windows,
    slot_of,
    update_windows,
)


def _brute_windows(events, key, day, windows, delay=0):
    """events: list of (key, day, amount, fraud). Sums over [day-delay-w+1, day-delay]."""
    out = []
    for w in windows:
        lo, hi = day - delay - w + 1, day - delay
        sel = [(a, f) for k, d, a, f in events if k == key and lo <= d <= hi]
        out.append(
            (len(sel), sum(a for a, _ in sel), sum(f for _, f in sel))
        )
    return out


def test_windows_match_brute_force(rng):
    windows = (1, 7, 30)
    state = init_window_state(64, 40)
    events = []
    day0 = 20000
    for step in range(6):
        b = 32
        keys = rng.integers(0, 8, b).astype(np.uint32)
        days = (day0 + step * 2 + rng.integers(0, 2, b)).astype(np.int32)
        amts = rng.uniform(1, 100, b).astype(np.float32)
        frauds = (rng.random(b) < 0.2).astype(np.float32)
        valid = np.ones(b, bool)
        slot = slot_of(jnp.asarray(keys), 64)
        state = update_windows(
            state, slot, jnp.asarray(days), jnp.asarray(amts),
            jnp.asarray(frauds), jnp.asarray(valid),
        )
        events += [
            (int(k), int(d), float(a), float(f))
            for k, d, a, f in zip(keys, days, amts, frauds)
        ]
    # distinct keys 0..7 hash to distinct slots in a 64-slot table? verify:
    slots = np.asarray(slot_of(jnp.arange(8, dtype=jnp.uint32), 64))
    assert len(set(slots.tolist())) == 8, "collision in test setup; adjust capacity"

    qday = day0 + 11
    for key in range(8):
        s = slot_of(jnp.asarray([key], dtype=jnp.uint32), 64)
        c, a, f = query_windows(state, s, jnp.asarray([qday], dtype=jnp.int32), windows)
        for i, w in enumerate(windows):
            bc, ba, bf = _brute_windows(events, key, qday, [w])[0]
            assert int(c[0, i]) == bc
            assert abs(float(a[0, i]) - ba) < 1e-2
            assert int(f[0, i]) == bf
    # delayed query
    for key in range(8):
        s = slot_of(jnp.asarray([key], dtype=jnp.uint32), 64)
        c, a, f = query_windows(
            state, s, jnp.asarray([qday], dtype=jnp.int32), windows, delay=7
        )
        for i, w in enumerate(windows):
            bc, ba, bf = _brute_windows(events, key, qday, [w], delay=7)[0]
            assert int(c[0, i]) == bc
            assert int(f[0, i]) == bf


def test_windows_ring_eviction():
    """Buckets wrap after n_buckets days; old days must vanish, not alias."""
    nb = 8
    state = init_window_state(16, nb)
    one = jnp.ones(1, jnp.float32)
    v = jnp.ones(1, bool)
    s0 = jnp.zeros(1, jnp.int32)
    d = lambda x: jnp.asarray([x], jnp.int32)
    state = update_windows(state, s0, d(100), one, one * 0, v)
    c, _, _ = query_windows(state, s0, d(100), (1,))
    assert int(c[0, 0]) == 1
    # day 108 maps to the same bucket (108 % 8 == 100 % 8): evicts day 100
    state = update_windows(state, s0, d(108), one, one * 0, v)
    c, _, _ = query_windows(state, s0, d(108), (1,))
    assert int(c[0, 0]) == 1  # only the new day
    # stale late event for day 100 must be dropped, not corrupt day 108
    state = update_windows(state, s0, d(100), one, one * 0, v)
    c, _, _ = query_windows(state, s0, d(108), (1,))
    assert int(c[0, 0]) == 1
    c, _, _ = query_windows(state, s0, d(100), (1,))
    assert int(c[0, 0]) == 0


@pytest.mark.parametrize(
    "skipped", [("amount",), ("fraud",), ("amount", "fraud")],
    ids="_and_".join)
def test_windows_unmaintained_column_is_not_touched(skipped):
    """Which aggregate columns a table maintains is fixed for its life.
    The update leaves an unmaintained column byte for byte as it was,
    also where the batch advances its buckets (no reset, no scatter); the
    maintained columns, the stamps and every window read from them are
    what an update that maintains all three gives."""
    import dataclasses

    import jax

    kw = {f"track_{c}": False for c in skipped}
    cap = 16
    state = init_window_state(cap, _NB)
    # day 100 and 101 everywhere it matters, all columns maintained ...
    first = _rows((2, 100, 5, 1, True), (2, 100, 7, 0, True),
                  (3, 101, 4, 1, True), (9, 100, 2, 1, True))
    state = update_windows(state, *map(jnp.asarray, first))
    # ... and a pattern no real table holds in the unmaintained columns,
    # so that any write to them would show
    mark = jnp.arange(cap * _NB, dtype=jnp.float32) + 0.5
    state = dataclasses.replace(state, **{c: mark for c in skipped})
    # slot 2 and 9 advance to day 108 (the ring bucket of day 100), slot 3
    # gets a same-day row, a late row and an invalid one
    second = _rows((2, 108, 3, 1, True), (9, 108, 6, 0, True),
                   (3, 101, 1, 1, True), (3, 93, 9, 1, True),
                   (5, 108, 9, 1, False))
    cols = tuple(map(jnp.asarray, second))
    got = jax.jit(lambda st: update_windows(st, *cols, **kw))(state)
    want = update_windows(state, *cols)  # maintains all three
    assert int(want.bucket_day[2 * _NB + 108 % _NB]) == 108  # advanced
    for c in skipped:
        assert (np.asarray(getattr(got, c)).tobytes()
                == np.asarray(mark).tobytes())
    maintained = [c for c in ("bucket_day", "count", "amount", "fraud")
                  if c not in skipped]
    for c in maintained:
        np.testing.assert_array_equal(np.asarray(getattr(got, c)),
                                      np.asarray(getattr(want, c)))
    slot = jnp.asarray(np.repeat(np.arange(cap, dtype=np.int32), 3))
    day = jnp.asarray(np.tile(np.asarray([101, 108, 110], np.int32), cap))
    names = ("count", "amount", "fraud")
    for delay in (0, 2):
        for name, g, w in zip(
                names, query_windows(got, slot, day, (1, 3, _NB), delay),
                query_windows(want, slot, day, (1, 3, _NB), delay)):
            if name in maintained:
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_maintained_columns_are_defined_once():
    """The customer table maintains (count, amount), the terminal table
    (count, fraud): one definition in ``features/online.py``, and ONE
    ``update_windows`` / ``query_windows`` call outside ``ops/``, the
    table plane's, passes it — the one-chip step, the sharded step and the
    engine reach the tables through the plane and repeat neither the call
    nor a literal. Late labels write a column its table maintains, and
    nothing else."""
    import ast
    import inspect

    from real_time_fraud_detection_system_tpu.features import online
    from real_time_fraud_detection_system_tpu.features import step as fstep
    from real_time_fraud_detection_system_tpu.parallel import step
    from real_time_fraud_detection_system_tpu.runtime import (
        engine,
        sharded_engine,
    )

    names = ("CUSTOMER_COLUMNS", "TERMINAL_COLUMNS")
    assert dict(online.CUSTOMER_COLUMNS) == {"track_amount": True,
                                             "track_fraud": False}
    assert dict(online.TERMINAL_COLUMNS) == {"track_amount": False,
                                             "track_fraud": True}
    for module in (online, fstep, step, engine, sharded_engine):
        tree = ast.parse(inspect.getsource(module))
        # the two names are bound once, at module level, in online.py only
        bound = [t.id for node in ast.walk(tree)
                 if isinstance(node, ast.Assign)
                 for t in node.targets if isinstance(t, ast.Name)
                 and t.id in names]
        assert len(bound) == (2 if module is online else 0), bound
        calls = {fn: [node for node in ast.walk(tree)
                      if isinstance(node, ast.Call)
                      and getattr(node.func, "id", None) == fn]
                 for fn in ("update_windows", "query_windows")}
        for fn, found in calls.items():
            assert len(found) == (module is online), (module.__name__, fn)
        for call in calls["update_windows"]:
            (kw,) = call.keywords  # one ``**`` and no literal flag
            assert kw.arg is None
            assert {n.id for n in ast.walk(kw.value)
                    if isinstance(n, ast.Name)} >= set(names)

    state = online.FeatureState(
        customer=init_window_state(8, _NB),
        terminal=update_windows(
            init_window_state(8, _NB), *map(jnp.asarray, _rows(
                (2, 100, 5, 0, True))), **online.TERMINAL_COLUMNS),
        cms=None)
    after = online.apply_feedback_at_slot(
        state, jnp.asarray([2], jnp.int32), jnp.asarray([100], jnp.int32),
        jnp.asarray([1], jnp.int32), jnp.asarray([True]))
    changed = [c for c, a, b in zip(
        ("bucket_day", "count", "amount", "fraud"),
        state.terminal.columns(), after.terminal.columns())
        if not np.array_equal(np.asarray(a), np.asarray(b))]
    assert changed == ["fraud"]
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(
        state.customer.columns(), after.customer.columns()))


def test_windows_invalid_rows_ignored():
    state = init_window_state(16, 8)
    s0 = jnp.zeros(4, jnp.int32)
    days = jnp.full(4, 50, jnp.int32)
    amts = jnp.ones(4, jnp.float32)
    valid = jnp.asarray([True, False, True, False])
    state = update_windows(state, s0, days, amts, amts * 0, valid)
    c, a, _ = query_windows(state, jnp.zeros(1, jnp.int32), jnp.asarray([50], jnp.int32), (1,))
    assert int(c[0, 0]) == 2
    assert abs(float(a[0, 0]) - 2.0) < 1e-6


class _TablesOracle:
    """The window semantics on plain NumPy ``[cap, NB]`` tables, one batch
    at a time: stamp = max(existing, the batch's valid days), a bucket
    whose stamp advanced starts from zero, a row counts iff its day is the
    bucket's stamp after the batch. Whole-dollar amounts in the cases
    below, so every sum is exact in any order and the comparison is to
    the bit."""

    def __init__(self, cap, nb):
        self.nb = nb
        self.bd = np.full((cap, nb), -1, np.int32)
        self.cnt, self.amt, self.frd = (
            np.zeros((cap, nb), np.float32) for _ in range(3))

    def update(self, slot, day, amount, fraud, valid,
               track_amount=True, track_fraud=True):
        old = self.bd.copy()
        for s, d, v in zip(slot, day, valid):
            if v:
                self.bd[s, d % self.nb] = max(self.bd[s, d % self.nb], d)
        # only the columns the table maintains are reset (or written)
        for t, kept in ((self.cnt, True), (self.amt, track_amount),
                        (self.frd, track_fraud)):
            if kept:
                t[self.bd > old] = 0.0
        for s, d, a, f, v in zip(slot, day, amount, fraud, valid):
            b = d % self.nb
            if v and self.bd[s, b] == d:
                self.cnt[s, b] += 1.0
                if track_amount:
                    self.amt[s, b] += a
                if track_fraud:
                    self.frd[s, b] += f

    def query(self, slot, day, windows, delay=0):
        age = day[:, None] - delay - self.bd[slot]
        live = (self.bd[slot] >= 0) & (age >= 0)
        return tuple(
            np.stack([(t[slot] * (live & (age < w))).sum(axis=1)
                      for w in windows], axis=1).astype(np.float32)
            for t in (self.cnt, self.amt, self.frd))

    def tables(self):
        return self.bd, self.cnt, self.amt, self.frd


def _rows(*rows):
    """(slot, day, amount, fraud, valid) rows → one batch of columns."""
    slot, day, amt, frd, valid = zip(*rows)
    return (np.asarray(slot, np.int32), np.asarray(day, np.int32),
            np.asarray(amt, np.float32), np.asarray(frd, np.float32),
            np.asarray(valid, bool))


_NB = 8  # the cases' ring: day d and day d + 8 share a bucket
# case → (batches, update_windows keywords)
_FLAT_STATE_CASES = {
    "duplicate_slot_day_rows": ([
        _rows((3, 100, 5, 1, True), (3, 100, 7, 0, True),
              (3, 100, 2, 1, True), (9, 100, 4, 0, True),
              (3, 101, 1, 0, True)),
        _rows((3, 100, 3, 0, True), (3, 100, 3, 1, True))], {}),
    "ring_wrap_in_one_batch": ([
        _rows((2, 100, 5, 0, True), (2, 108, 7, 1, True),
              (5, 100, 2, 0, True), (2, 108, 1, 0, True))], {}),
    "ring_wrap_across_batches": ([
        _rows((2, 100, 5, 1, True), (5, 103, 2, 0, True)),
        _rows((2, 108, 7, 0, True), (5, 104, 3, 1, True)),
        _rows((2, 116, 1, 1, True), (2, 109, 6, 0, True))], {}),
    "late_rows_are_dropped": ([
        _rows((4, 108, 5, 0, True), (6, 107, 2, 1, True)),
        _rows((4, 100, 9, 1, True), (6, 99, 9, 1, True),
              (4, 108, 1, 1, True), (6, 106, 4, 0, True))], {}),
    "invalid_rows": ([
        _rows((1, 100, 5, 1, True), (1, 100, 9, 1, False),
              (1, 108, 9, 1, False), (7, 101, 9, 0, False)),
        _rows((1, 116, 9, 1, False), (1, 100, 2, 0, True))], {}),
    "amount_not_tracked": ([
        _rows((2, 100, 5, 1, True), (2, 100, 7, 0, True)),
        _rows((2, 108, 3, 1, True), (3, 100, 4, 1, True))],
        {"track_amount": False}),
    "fraud_not_tracked": ([
        _rows((2, 100, 5, 1, True), (2, 100, 7, 0, True)),
        _rows((2, 108, 3, 1, True), (3, 100, 4, 1, True))],
        {"track_fraud": False}),
}


@pytest.mark.parametrize("case", sorted(_FLAT_STATE_CASES))
def test_flat_state_matches_tables_oracle(case):
    """``update_windows`` + ``query_windows`` on the flat slot-major state
    against the ``[cap, NB]`` oracle: the state after every batch, and
    every window of every touched slot at several days."""
    import jax

    batches, kw = _FLAT_STATE_CASES[case]
    cap, windows = 16, (1, 3, _NB)
    state = init_window_state(cap, _NB)
    assert state.capacity == cap and state.n_buckets == _NB
    assert all(c.shape == (cap * _NB,) for c in state.columns())
    oracle = _TablesOracle(cap, _NB)
    update = jax.jit(lambda st, *cols: update_windows(st, *cols, **kw))
    for cols in batches:
        state = update(state, *map(jnp.asarray, cols))
        oracle.update(*cols, **kw)
        for got, want in zip(state.tables(), oracle.tables()):
            np.testing.assert_array_equal(np.asarray(got), want)
    slot = np.repeat(np.arange(cap, dtype=np.int32), 4)
    day = np.tile(np.asarray([100, 108, 110, 117], np.int32), cap)
    for delay in (0, 2):
        got = query_windows(state, jnp.asarray(slot), jnp.asarray(day),
                            windows, delay=delay)
        for g, w in zip(got, oracle.query(slot, day, windows, delay)):
            np.testing.assert_array_equal(np.asarray(g), w)


def _table_wide_update(state, slot, day, amount, fraud, valid,
                       track_amount=True, track_fraud=True):
    """``update_windows`` as it was written until PR 43, kept as the
    oracle of the batch-merged form: combine INTO the table — scatter-max
    the stamps, compare old and new stamps over the whole table, zero
    what advanced, scatter-add."""
    from real_time_fraud_detection_system_tpu.ops.windows import WindowState

    nb = state.n_buckets
    flat = (slot * nb + jnp.remainder(day, nb)).astype(jnp.int32)
    day_in = jnp.where(valid, day, -1).astype(jnp.int32)
    bd, count, amt, frd = state.columns()
    new_bd = bd.at[flat].max(day_in)
    advanced = new_bd > bd
    w = (valid & (day_in == new_bd[flat])).astype(jnp.float32)
    count = jnp.where(advanced, 0.0, count).at[flat].add(w)
    if track_amount:
        amt = jnp.where(advanced, 0.0, amt).at[flat].add(amount * w)
    if track_fraud:
        frd = jnp.where(advanced, 0.0, frd).at[flat].add(fraud * w)
    return WindowState(new_bd, count, amt, frd, n_buckets=nb)


def _random_batches(n, rows, cap, seed=7):
    """Days rolling over the ring, rows older than their bucket, a hot
    slot's duplicates, a fifth of the rows invalid; dollars and cents."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        slot = rng.integers(0, cap, rows).astype(np.int32)
        slot[rng.random(rows) < 0.15] = cap - 1
        out.append((
            slot, (100 + 3 * i + rng.integers(-10, 3, rows)).astype(np.int32),
            rng.uniform(1, 500, rows).round(2).astype(np.float32),
            (rng.random(rows) < 0.3).astype(np.float32),
            rng.random(rows) < 0.8))
    return out


_CAP = 16
# case → batches, applied in turn; cents in the amounts, so that the order
# of a bucket's additions shows wherever a bucket takes two rows
_MERGED_UPDATE_CASES = {
    "duplicates_of_one_slot_and_day": [
        _rows((3, 100, 5.25, 1, True), (3, 100, 7.1, 0, True),
              (3, 100, 2.3, 1, True), (9, 100, 4.7, 0, True),
              (3, 101, 1.9, 0, True)),
        _rows((3, 100, 3.3, 0, True), (3, 100, 3.3, 1, True))],
    "two_days_share_a_bucket_in_one_batch": [
        _rows((2, 100, 5.5, 0, True), (2, 108, 7.7, 1, True),
              (5, 100, 2.2, 0, True), (2, 108, 1.1, 0, True),
              (2, 116, 9.9, 1, True), (2, 100, 3.3, 1, True))],
    "a_row_older_than_its_buckets_stamp": [
        _rows((4, 108, 5.5, 0, True), (6, 107, 2.2, 1, True)),
        _rows((4, 100, 9.9, 1, True), (6, 99, 9.9, 1, True),
              (4, 108, 1.1, 1, True), (6, 106, 4.4, 0, True))],
    "one_bucket_advances_and_one_does_not": [
        _rows((2, 100, 5.5, 1, True), (5, 103, 2.2, 0, True),
              (7, 101, 6.6, 1, True)),
        _rows((2, 108, 7.7, 0, True), (5, 103, 3.3, 1, True),
              (7, 101, 1.1, 0, True), (7, 109, 8.8, 1, True),
              (7, 101, 4.4, 1, True))],
    "invalid_rows": [
        _rows((1, 100, 5.5, 1, True), (1, 100, 9.9, 1, False),
              (1, 108, 9.9, 1, False), (7, 101, 9.9, 0, False)),
        _rows((1, 116, 9.9, 1, False), (1, 100, 2.2, 0, True))],
    "an_all_invalid_batch": [
        _rows((1, 100, 5.5, 1, True), (2, 101, 1.1, 0, True)),
        _rows((1, 108, 9.9, 1, False), (2, 101, 9.9, 1, False),
              (3, 102, 9.9, 0, False))],
    "a_batch_of_one_run": [
        _rows((6, 100, 1.1, 1, True)),
        _rows(*[(6, 108, 0.1 * (i + 1), i % 2, True) for i in range(7)])],
    "the_last_slot": [
        _rows((_CAP - 1, 100 + _NB - 1, 5.5, 1, True),
              (_CAP - 1, 100 + _NB - 1, 2.2, 0, True), (0, 96, 1.1, 1, True)),
        _rows((_CAP - 1, 100 + 2 * _NB - 1, 3.3, 1, True),
              (_CAP - 1, 100, 4.4, 0, True), (_CAP - 1, 100, 6.6, 0, False))],
    "padded_rows": [
        # what core.batch pads a bucket with: slot 0, day 0, not valid
        _rows((0, 100, 5.5, 1, True), (3, 101, 2.2, 0, True),
              *[(0, 0, 0.0, 0, False)] * 5),
        _rows((0, 100, 1.1, 0, True), *[(0, 0, 0.0, 0, False)] * 7)],
    "a_batch_of_no_rows": [
        _rows((1, 100, 5.5, 1, True), (2, 101, 1.1, 0, True)),
        tuple(np.zeros(0, dt) for dt in (np.int32, np.int32, np.float32,
                                         np.float32, bool)),
        _rows((1, 100, 2.2, 0, True))],
    "random_batches": _random_batches(10, 96, _CAP),
}
_COLUMN_SETS = {
    "customer": {"track_amount": True, "track_fraud": False},
    "terminal": {"track_amount": False, "track_fraud": True},
    "all_three": {},
}


@pytest.mark.parametrize("write", ["sorted", "plain"])
@pytest.mark.parametrize("jitted", [True, False], ids=["jit", "eager"])
@pytest.mark.parametrize("columns", sorted(_COLUMN_SETS))
@pytest.mark.parametrize("case", sorted(_MERGED_UPDATE_CASES))
def test_merged_update_matches_the_table_wide_form(case, columns, jitted,
                                                   write, monkeypatch):
    """The update merges its batch first and touches the table at the
    batch's buckets only; the table-wide form it replaced is the oracle.
    Stamps, counts and fraud sums — integers — to the bit after every
    batch; a dollar sum to the bit where its bucket took one row of the
    batch, else within a rounding an addition (the additions of a bucket's
    rows run in another order), and to the bit between two runs of one
    batch; an unmaintained column stays the buffer's bytes. Under both
    forms of the column write, which the update chooses between from its
    shapes (``_sorted_write_pays``)."""
    import jax

    from real_time_fraud_detection_system_tpu.ops import windows

    monkeypatch.setattr(windows, "_sorted_write_pays",
                        lambda rows, n: write == "sorted")
    kw = _COLUMN_SETS[columns]
    update = lambda st, *cols: update_windows(st, *cols, **kw)  # noqa: E731
    if jitted:
        update = jax.jit(update)
    got = want = init_window_state(_CAP, _NB)
    mark = jnp.arange(_CAP * _NB, dtype=jnp.float32) + 0.5
    for batch in _MERGED_UPDATE_CASES[case]:
        cols = tuple(map(jnp.asarray, batch))
        before = got
        got = update(before, *cols)
        want = _table_wide_update(want, *cols, **kw)
        again = update(before, *cols)
        slot, day, _, _, valid = batch
        taken = np.bincount((slot * _NB + day % _NB)[valid],
                            minlength=_CAP * _NB)
        for name in ("bucket_day", "count", "amount", "fraud"):
            g, w = (np.asarray(getattr(st, name)) for st in (got, want))
            assert g.tobytes() == np.asarray(getattr(again, name)).tobytes()
            if name == "amount" and kw.get("track_amount", True):
                one = taken <= 1
                np.testing.assert_array_equal(g[one], w[one])
                assert (np.abs(g - w) <= taken * np.float32(2.0 ** -23)
                        * np.abs(w)).all(), name
                want = dataclasses.replace(want, amount=got.amount)
            else:
                np.testing.assert_array_equal(g, w, err_msg=name)
    for name, tracked in (("amount", "track_amount"),
                          ("fraud", "track_fraud")):
        if not kw.get(tracked, True):
            marked = dataclasses.replace(got, **{name: mark})
            cols = tuple(map(jnp.asarray, _MERGED_UPDATE_CASES[case][-1]))
            assert (np.asarray(getattr(update(marked, *cols), name))
                    .tobytes() == np.asarray(mark).tobytes())


def test_merged_update_adds_a_buckets_rows_in_their_own_order():
    """The order of a bucket's in-batch amount additions is a fixed tree
    over ITS rows in batch order: renumbering the slots (what another key
    mode does) and interleaving other keys' rows leaves every bucket's
    sum the same bits — ``key_mode=exact`` equals ``direct`` bit for bit
    (tests/test_exact_store.py) because of this."""
    rng = np.random.default_rng(11)
    rows, cap = 256, 64
    slot = rng.integers(0, 8, rows).astype(np.int32)  # ~32 rows a bucket
    day = np.full(rows, 100, np.int32)
    amount = rng.uniform(1, 500, rows).round(2).astype(np.float32)
    zero, ok = np.zeros(rows, np.float32), np.ones(rows, bool)
    renumber = rng.permutation(cap).astype(np.int32)
    a = update_windows(init_window_state(cap, _NB), *map(
        jnp.asarray, (slot, day, amount, zero, ok)))
    # the same rows under other slot numbers, other keys' rows between
    other = (8 + rng.integers(0, 8, rows)).astype(np.int32)
    mixed = np.stack([renumber[slot], renumber[other]], 1).reshape(-1)
    twice = lambda x: np.repeat(x, 2)  # noqa: E731
    b = update_windows(init_window_state(cap, _NB), *map(
        jnp.asarray, (mixed, twice(day), twice(amount), twice(zero),
                      twice(ok))))
    ta, tb = np.asarray(a.tables()[2]), np.asarray(b.tables()[2])
    for s in range(8):
        assert ta[s].tobytes() == tb[renumber[s]].tobytes(), s


def test_terminal_columns_are_the_same_bits_under_any_row_order():
    """The table that maintains no dollar sum sorts its batch by the
    bucket alone, so the order of a bucket's rows is the sort's: counts
    and 0/1 fraud labels are small integers, exact in any order, and the
    columns come out the same bytes under any permutation of the rows
    (and equal the table-wide form's)."""
    rng = np.random.default_rng(5)
    kw = _COLUMN_SETS["terminal"]
    for batch in _random_batches(4, 192, _CAP, seed=13):
        cols = tuple(map(jnp.asarray, batch))
        start = update_windows(init_window_state(_CAP, _NB), *map(
            jnp.asarray, _random_batches(1, 64, _CAP, seed=3)[0]), **kw)
        want = _table_wide_update(start, *cols, **kw)
        for _ in range(3):
            perm = rng.permutation(len(batch[0]))
            got = update_windows(start, *(c[perm] for c in cols), **kw)
            for name in ("bucket_day", "count", "amount", "fraud"):
                assert (np.asarray(getattr(got, name)).tobytes()
                        == np.asarray(getattr(want, name)).tobytes()), name


@pytest.mark.parametrize("rows,n,sorted_write", [
    # the benchmark's tables, 2^22 and 2^23 slots of 40 buckets
    (65536, 40 << 22, True), (65536, 40 << 23, True),
    (16384, 40 << 22, False), (16384, 40 << 23, False),
    (256, 40 << 22, False), (256, 40 << 23, False),
    # a shard of the four-chip cell's tables: a quarter of 2^24 / 2^25
    (65536, 40 << 23, True), (32768, 40 << 22, True),
    # past ~460 M elements a pass costs more than 65,536 plain updates
    (65536, 40 << 24, False),
    # toy tables: a pass's fixed 0.32 ms is 3,571 plain updates
    (256, 1 << 20, False), (4096, 1 << 20, True), (4, 1 << 10, False)])
def test_the_column_write_is_chosen_from_the_two_shapes(rows, n,
                                                        sorted_write):
    """``.set`` on sorted indices is a pass over the column on a v5e
    (0.32 ms + 12.75 ps an element), the plain one 89.6 ns an update: the
    update marks its writes sorted where the pass is the cheaper."""
    from real_time_fraud_detection_system_tpu.ops.windows import (
        _sorted_write_pays,
    )

    assert _sorted_write_pays(rows, n) is sorted_write


def test_update_and_query_trace_in_few_equations():
    """``engine.precompile()`` traces the step once a batch bucket, and the
    update and the query once a table in each: what they cost there is
    their number of equations (PERF.md, PR 43: the update was 125 as first
    written over ``jnp`` indexing and ``fori_loop``, the query 420, and
    with them the warm set-up went over its bound). A budget with a
    little room, at the benchmark's 40 buckets."""
    import jax

    rows, cap, nb = 4096, 1 << 12, 40
    state = jax.eval_shape(lambda: init_window_state(cap, nb))
    col = lambda dt: jax.ShapeDtypeStruct((rows,), dt)  # noqa: E731
    batch = (col(jnp.int32), col(jnp.int32), col(jnp.float32),
             col(jnp.float32), col(jnp.bool_))
    for kw in _COLUMN_SETS.values():
        update = jax.make_jaxpr(
            lambda st, *c: update_windows(st, *c, **kw))(state, *batch)
        assert len(update.jaxpr.eqns) <= 90, len(update.jaxpr.eqns)
    query = jax.make_jaxpr(
        lambda st, s, d: query_windows(st, s, d, (1, 7, 30)))(
            state, col(jnp.int32), col(jnp.int32))
    assert len(query.jaxpr.eqns) <= 310, len(query.jaxpr.eqns)


def test_cms_overestimates_and_windows(rng):
    sk = cms_init(depth=4, width=1 << 10, n_days=8)
    keys = rng.integers(0, 50, 400).astype(np.uint32)
    days = rng.integers(100, 103, 400).astype(np.int32)
    amts = np.ones(400, np.float32)
    sk = cms_update(sk, jnp.asarray(keys), jnp.asarray(amts), jnp.asarray(days),
                    jnp.ones(400, bool))
    qc, qa = cms_query(sk, jnp.asarray(keys), jnp.asarray(days), (1, 7))
    # exact per-(key,day) counts
    for i in range(0, 400, 37):
        true_1d = np.sum((keys == keys[i]) & (days == days[i]))
        true_7d = np.sum((keys == keys[i]) & (days <= days[i]) & (days > days[i] - 7))
        assert qc[i, 0] >= true_1d  # CMS never underestimates
        assert qc[i, 1] >= true_7d
        assert qc[i, 0] <= true_1d + 40  # loose collision bound
    # amounts track counts here (unit amounts)
    assert np.allclose(np.asarray(qc), np.asarray(qa), atol=1e-3)


def test_dedup_matches_numpy(rng):
    b = 256
    keys = rng.integers(0, 40, b)
    ts = rng.integers(0, 10, b)
    valid = rng.random(b) < 0.9
    m_np = latest_wins_mask_np(keys, ts, valid)
    m_j = np.asarray(
        latest_wins_mask(
            jnp.asarray(keys.astype(np.uint32)), jnp.asarray(ts.astype(np.int32)),
            jnp.asarray(valid),
        )
    )
    assert np.array_equal(m_np, m_j)
    # exactly one winner per valid key
    for k in np.unique(keys[valid]):
        sel = m_np & (keys == k)
        assert sel.sum() == 1
        i = np.nonzero(sel)[0][0]
        group = (keys == k) & valid
        assert ts[i] == ts[group].max()
    # winner is the LAST occurrence among max-ts rows (Kafka log order)
    keys2 = np.zeros(4, dtype=np.int64)
    ts2 = np.asarray([5, 5, 3, 5])
    m = latest_wins_mask_np(keys2, ts2)
    assert m.tolist() == [False, False, False, True]


def test_hashing_ranges_and_dispersion():
    keys = jnp.arange(10000, dtype=jnp.uint32)
    s = np.asarray(slot_of(keys, 1 << 10))
    assert s.min() >= 0 and s.max() < (1 << 10)
    counts = np.bincount(s, minlength=1 << 10)
    assert counts.max() < 40  # ~9.8 expected; catastrophic clustering fails
    h = np.asarray(multi_hash(keys, 4, 1 << 12))
    assert h.shape == (4, 10000)
    # rows must be (near-)independent
    assert (h[0] == h[1]).mean() < 0.01
    # determinism
    assert np.array_equal(np.asarray(hash_u32(keys)), np.asarray(hash_u32(keys)))


def test_pack_unpack_batch_bitexact():
    """The single-array H2D packing must round-trip every TxBatch field
    bit-exactly (uint32 high bits, float32 amounts, -1 labels, padding)."""
    import numpy as np
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.core.batch import (
        make_batch,
        pack_batch,
        unpack_batch,
    )

    rng = np.random.default_rng(3)
    n = 200
    b = make_batch(
        rng.integers(0, 2**63 - 1, n), rng.integers(0, 2**63 - 1, n),
        rng.integers(0, 2**45, n), rng.integers(0, 10**7, n),
        label=rng.integers(-1, 2, n), pad_to=256,
    )
    packed = pack_batch(b)
    assert packed.shape == (7, 256) and packed.dtype == np.int32
    u = unpack_batch(jnp.asarray(packed))
    for name, a, c in zip(b._fields, b, u):
        assert np.asarray(c).dtype == np.asarray(a).dtype, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                      err_msg=name)


def test_div_ieee_equals_numpy_division_bit_for_bit():
    """On the CPU the plain quotient is already correctly rounded and the
    correction adds nothing; on the chip (chip_smoke.py's oracle and
    kernels phases) it is what makes the quotient equal NumPy's."""
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.ops.numerics import div_ieee

    rng = np.random.default_rng(0)
    a = (rng.normal(size=(4096, 15)) * rng.choice(
        [1e-3, 1.0, 1e3, 1e6], size=(4096, 15))).astype(np.float32)
    b = rng.uniform(1e-3, 3e3, size=(15,)).astype(np.float32)
    got = np.asarray(div_ieee(jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(got, a / b)
    # integer-valued operands (window counts, fraud counts): exact too
    cnt = rng.integers(1, 500, size=(4096, 1)).astype(np.float32)
    frd = rng.integers(0, 500, size=(4096, 1)).astype(np.float32)
    assert np.array_equal(
        np.asarray(div_ieee(jnp.asarray(frd), jnp.asarray(cnt))), frd / cnt)


def test_div_ieee_keeps_the_meaning_of_nonfinite_quotients():
    """x/0 stays ±inf and 0/0 stays NaN: the nan-guard's quarantine (a
    degenerate scaler column) keys on exactly that."""
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.ops.numerics import div_ieee

    a = jnp.asarray([1.0, -2.0, 0.0, np.inf, 3.0], jnp.float32)
    b = jnp.asarray([0.0, 0.0, 0.0, 2.0, np.inf], jnp.float32)
    got = np.asarray(div_ieee(a, b))
    assert got[0] == np.inf and got[1] == -np.inf
    assert np.isnan(got[2])
    assert got[3] == np.inf and got[4] == 0.0


@pytest.mark.parametrize("n", [1, 2, 5, 40, 64, 100])
def test_sum_fixed_order_is_the_written_tree(n):
    """`sum_fixed_order` is an explicit balanced tree of adds over the
    zero-padded axis — the same bits as that tree written out in NumPy,
    under jit or not, for any axis, and exact for integer-valued data."""
    import jax

    from real_time_fraud_detection_system_tpu.ops.numerics import (
        sum_fixed_order,
    )

    rng = np.random.default_rng(n)
    x = (rng.normal(size=(7, n)) * 1e3).astype(np.float32)

    def written(a):
        p = 1 << max(n - 1, 0).bit_length()
        a = np.concatenate(
            [a, np.zeros((a.shape[0], p - n), np.float32)], axis=1)
        while p > 1:
            p //= 2
            a = a[:, :p] + a[:, p:]
        return a[:, 0]

    want = written(x)
    assert np.array_equal(np.asarray(sum_fixed_order(jnp.asarray(x), 1)),
                          want)
    assert np.array_equal(
        np.asarray(jax.jit(lambda a: sum_fixed_order(a, axis=0))(
            jnp.asarray(x.T))), want)
    kept = sum_fixed_order(jnp.asarray(x), axis=1, keepdims=True)
    assert kept.shape == (7, 1) and np.array_equal(
        np.asarray(kept)[:, 0], want)
    np.testing.assert_allclose(want, x.astype(np.float64).sum(axis=1),
                               rtol=1e-5)
    counts = rng.integers(0, 1000, size=(7, n)).astype(np.float32)
    assert np.array_equal(
        np.asarray(sum_fixed_order(jnp.asarray(counts), 1)),
        counts.sum(axis=1))


@pytest.mark.parametrize("key_mode", ["direct", "hash"])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_key_slot_is_the_rule_every_layout_used(n_shards, key_mode):
    """``ops/hashing.key_slot`` / ``key_row`` equal the expressions they
    replaced, kept here as literals: ``features/online._slot`` (one
    chip), the sharded step's inlined local slot, the sharded engine's
    NumPy global row for late labels, and ``mesh._layout_perm``."""
    from real_time_fraud_detection_system_tpu.ops.hashing import (
        key_row,
        key_slot,
        slot_of,
    )
    from real_time_fraud_detection_system_tpu.parallel.mesh import (
        _layout_perm,
    )

    cap, n_dev = 1 << 10, n_shards
    cap_local = cap // n_dev
    key = np.random.default_rng(n_shards).integers(
        0, 1 << 32, 4096, dtype=np.uint32)
    jkey = jnp.asarray(key)
    if n_shards == 1 and key_mode == "hash":
        want = slot_of(jkey, cap)
    elif n_shards == 1:
        want = (jkey & jnp.uint32(cap - 1)).astype(jnp.int32)
    else:  # a mesh's layout is owner-modulo under either mode
        want = ((jkey // jnp.uint32(n_dev))
                & jnp.uint32(cap_local - 1)).astype(jnp.int32)
    got = key_slot(jkey, cap, key_mode, n_shards)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="key directory"):
        key_slot(jkey, cap, "exact", n_shards)
    if key_mode == "hash":
        return
    # NumPy callers share the rule: late labels' global row ...
    np.testing.assert_array_equal(key_slot(key, cap, "direct", n_shards),
                                  np.asarray(want))
    gslot = (
        (key % np.uint32(n_dev)).astype(np.int64) * cap_local
        + ((key // np.uint32(n_dev)) & np.uint32(cap_local - 1))
    ).astype(np.int32)
    np.testing.assert_array_equal(
        key_row(key, cap, "direct", n_shards), gslot)
    # ... and the layout permutation of a reshard: owner × cap_local +
    # local slot
    k = np.arange(cap)
    perm = _layout_perm(cap, n_dev)
    np.testing.assert_array_equal(
        perm, k if n_dev == 1 else (k % n_dev) * (cap // n_dev) + k // n_dev)
    np.testing.assert_array_equal(
        perm, (k % n_dev) * cap_local + key_slot(k, cap, "direct", n_dev))
