"""The plain reference and the comparison that decides ``correct``.

The reference shares nothing with the program: NumPy daily aggregates per
key in float64 (after ``chip_smoke.py::reference_features``, PR 21), fed
from the benchmark's own schedule. It imports nothing of the package and
takes no weights, scales or tables the program made.

Semantics (the configuration's ``features`` block): window w at day d
covers days [d-w+1, d], the terminal windows shifted back by the label
delay; a row's windows include its batch-mates (update, then query), so
batch membership is part of the answer and is read from the sink's own
part files; a key keeps ``n_day_buckets`` days in a ring, so day s is
forgotten once day s + n_day_buckets arrives for that key. No labels are
served, so every terminal risk is 0.
"""

from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

US_PER_DAY = 86_400_000_000

FEATURE_NAMES = (
    "TX_AMOUNT", "TX_DURING_WEEKEND", "TX_DURING_NIGHT",
    "CUSTOMER_ID_NB_TX_1DAY_WINDOW", "CUSTOMER_ID_AVG_AMOUNT_1DAY_WINDOW",
    "CUSTOMER_ID_NB_TX_7DAY_WINDOW", "CUSTOMER_ID_AVG_AMOUNT_7DAY_WINDOW",
    "CUSTOMER_ID_NB_TX_30DAY_WINDOW", "CUSTOMER_ID_AVG_AMOUNT_30DAY_WINDOW",
    "TERMINAL_ID_NB_TX_1DAY_WINDOW", "TERMINAL_ID_RISK_1DAY_WINDOW",
    "TERMINAL_ID_NB_TX_7DAY_WINDOW", "TERMINAL_ID_RISK_7DAY_WINDOW",
    "TERMINAL_ID_NB_TX_30DAY_WINDOW", "TERMINAL_ID_RISK_30DAY_WINDOW",
)
AVG_COLUMNS = [j for j, n in enumerate(FEATURE_NAMES) if "AVG_AMOUNT" in n]
EXACT_COLUMNS = [j for j in range(len(FEATURE_NAMES)) if j not in AVG_COLUMNS]


def bf16_round(x: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even), kept in
    float32: what a bf16 store or a bf16 arithmetic step does to them."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


class KeyRows:
    """key → row of a table that grows by one row a new key, rows in order
    of first sight. Nothing here is sized by the id universe: two sorted
    key arrays (a large one and the recent arrivals, merged into it every
    ``MERGE`` keys) and two searches a lookup."""

    MERGE = 1 << 18

    def __init__(self):
        empty = np.empty(0, np.int64)
        self._levels = [(empty, empty), (empty, empty)]  # (keys, rows)
        self.count = 0

    def find(self, keys: np.ndarray) -> np.ndarray:
        """Row of every key; -1 for a key never admitted."""
        uniq, inverse = np.unique(keys, return_inverse=True)
        return self._find_sorted(uniq)[inverse]

    def _find_sorted(self, uniq: np.ndarray) -> np.ndarray:
        # sorted needles: the search walks each level once, front to back
        out = np.full(len(uniq), -1, np.int64)
        for ks, rs in self._levels:
            if len(ks):
                pos = np.minimum(np.searchsorted(ks, uniq), len(ks) - 1)
                hit = ks[pos] == uniq
                out[hit] = rs[pos[hit]]
        return out

    def admit(self, keys: np.ndarray) -> np.ndarray:
        """Row of every key, new keys given the next rows in sorted
        order."""
        uniq, inverse = np.unique(keys, return_inverse=True)
        rows = self._find_sorted(uniq)
        miss = rows < 0
        if miss.any():
            new = uniq[miss]
            ks, rs = self._levels[1]
            at = np.searchsorted(ks, new)
            fresh = self.count + np.arange(len(new), dtype=np.int64)
            self._levels[1] = (np.insert(ks, at, new),
                               np.insert(rs, at, fresh))
            self.count += len(new)
            rows[miss] = fresh
            if len(self._levels[1][0]) > self.MERGE:
                (mk, mr), (ks, rs) = self._levels
                at = np.searchsorted(mk, ks)
                self._levels = [(np.insert(mk, at, ks),
                                 np.insert(mr, at, rs)),
                                (ks[:0], rs[:0])]
        return rows[inverse]


def grown(table: np.ndarray, rows: int) -> np.ndarray:
    """``table`` with room for at least ``rows`` rows, the new ones zero.
    Grown in place (``realloc``: a large block is remapped, not copied)
    an eighth at a time; no view of the table may be alive."""
    if rows > len(table):
        table.resize((max(rows, len(table) + len(table) // 8, 1 << 12),)
                     + table.shape[1:], refcheck=False)
    return table


def add_in_row_order(counts_flat: np.ndarray, sums_flat: np.ndarray,
                     cells: np.ndarray, values: np.ndarray) -> None:
    """``np.add.at(counts_flat, cells, 1)`` and ``np.add.at(sums_flat,
    cells, values)`` to the bit — every cell takes its values one by one
    in row order — in one sort and a few vectorised passes: the k-th pass
    adds, to every cell, the k-th of its rows."""
    order = np.argsort(cells, kind="stable")
    cs, vs = cells[order], values[order]
    first = np.flatnonzero(np.r_[True, cs[1:] != cs[:-1]])
    counts = np.diff(np.r_[first, len(cs)])
    counts_flat[cs[first]] += counts.astype(counts_flat.dtype)
    sums_flat[cs[first]] += vs[first]
    if counts.max() > 1:
        rank = np.arange(len(cs)) - np.repeat(first, counts)
        for k in range(1, int(counts.max())):
            again = np.flatnonzero(rank == k)
            sums_flat[cs[again]] += vs[again]


def count_in(flat: np.ndarray, cells: np.ndarray) -> None:
    """``np.add.at(flat, cells, 1)``: integers, so the order is nothing."""
    uniq, counts = np.unique(cells, return_counts=True)
    flat[uniq] += counts.astype(flat.dtype)


class WindowReference:
    """Daily aggregates per key over the days a run touches, one table
    row a key the run has shown it (``KeyRows``): memory and time follow
    the touched keys, not the id universe. The arithmetic a key sees is
    that of a dense ``[universe, days]`` table, to the bit
    (``benchmark/tests/dense_reference.py`` keeps that one as the tests'
    oracle).

    ``lower_precision=True`` is the CONTROL, not a reference: the same
    arithmetic with every stored sum and every emitted feature rounded to
    bfloat16, the step below the float32 the configuration states."""

    def __init__(self, features: dict, n_customers: int, n_terminals: int,
                 first_day: int, n_days: int, lower_precision: bool = False):
        self.windows = tuple(int(w) for w in features["windows"])
        self.delay = int(features["delay_days"])
        self.ring = int(features["n_day_buckets"])
        self.night_end_hour = int(features["night_end_hour"])
        self.weekend_start = int(features["weekend_start_weekday"])
        self.first_day, self.n_days = int(first_day), int(n_days)
        self.low = bool(lower_precision)
        self.c_rows, self.t_rows = KeyRows(), KeyRows()
        self.c_cnt = np.zeros((0, self.n_days), np.int32)
        self.c_amt = np.zeros((0, self.n_days), np.float64)
        self.t_cnt = np.zeros((0, self.n_days), np.int32)

    def _day_index(self, t_us: np.ndarray) -> np.ndarray:
        d = t_us // US_PER_DAY - self.first_day
        if d.min() < 0 or d.max() >= self.n_days:
            raise ValueError("an event day lies outside the reference's days")
        return d

    def update(self, cols: dict) -> None:
        """One batch's rows enter the aggregates."""
        c = self.c_rows.admit(np.asarray(cols["customer_id"], np.int64))
        t = self.t_rows.admit(np.asarray(cols["terminal_id"], np.int64))
        self.c_cnt = grown(self.c_cnt, self.c_rows.count)
        self.c_amt = grown(self.c_amt, self.c_rows.count)
        self.t_cnt = grown(self.t_cnt, self.t_rows.count)
        d = self._day_index(cols["tx_datetime_us"])
        amount = (cols["tx_amount_cents"] / 100.0).astype(np.float32)
        add_in_row_order(self.c_cnt.reshape(-1), self.c_amt.reshape(-1),
                         c * self.n_days + d, amount.astype(np.float64))
        count_in(self.t_cnt.reshape(-1), t * self.n_days + d)
        if self.low:
            self.c_amt[c, d] = bf16_round(self.c_amt[c, d])
        for back in range(self.ring, self.n_days, self.ring):
            old = d >= back  # the ring forgets
            self.c_cnt[c[old], d[old] - back] = 0
            self.c_amt[c[old], d[old] - back] = 0.0
            self.t_cnt[t[old], d[old] - back] = 0

    def _window_sums(self, table, row, last_day) -> np.ndarray:
        """[n, len(windows)]: table[row, last_day-w+1 .. last_day]; a key
        never seen (row -1) has nothing on any day."""
        rows = np.zeros((len(row), self.n_days))
        seen = row >= 0
        rows[seen] = table[row[seen]]
        pre = np.concatenate(
            [np.zeros((len(rows), 1)), np.cumsum(rows, axis=1)], axis=1)
        ok = last_day >= 0
        hi = np.where(ok, last_day, 0)
        r = np.arange(len(rows))
        return np.stack(
            [np.where(ok, pre[r, hi + 1]
                      - pre[r, np.maximum(hi - w + 1, 0)], 0.0)
             for w in self.windows], axis=1)

    def features(self, cols: dict) -> np.ndarray:
        """The 15 features of a batch whose rows have already entered."""
        c = self.c_rows.find(np.asarray(cols["customer_id"], np.int64))
        t = self.t_rows.find(np.asarray(cols["terminal_id"], np.int64))
        us = cols["tx_datetime_us"]
        d = self._day_index(us)
        day, tod = us // US_PER_DAY, (us % US_PER_DAY) // 1_000_000
        amount = (cols["tx_amount_cents"] / 100.0).astype(np.float32)
        cc = self._window_sums(self.c_cnt, c, d)
        ca = self._window_sums(self.c_amt, c, d)
        tc = self._window_sums(self.t_cnt, t, d - self.delay)
        f = [amount.astype(np.float64),
             ((day + 3) % 7 >= self.weekend_start).astype(np.float64),
             (tod // 3600 <= self.night_end_hour).astype(np.float64)]
        for i in range(len(self.windows)):
            f += [cc[:, i], ca[:, i] / np.maximum(cc[:, i], 1.0)]
        for i in range(len(self.windows)):
            f += [tc[:, i], np.zeros(len(d))]
        out = np.stack(f, axis=1)
        if self.low:
            out = bf16_round(out).astype(np.float64)
        return out


def emitted_features(cols: dict) -> np.ndarray:
    """The 15 feature columns of sink rows, in FEATURE_NAMES order, through
    float32 as the engine emitted them (the sink stores the amount as exact
    cents / 100 in float64, every other column is float32 or int32)."""
    return np.stack([np.asarray(cols[n.lower()], np.float32)
                     for n in FEATURE_NAMES], axis=1)


def compare(emitted: np.ndarray, probs: np.ndarray, ref: np.ndarray,
            ref_probs: np.ndarray, limits: dict) -> List[dict]:
    """The numbers compared, each beside its limit."""
    dev = emitted.astype(np.float64)
    wrong = int((dev[:, EXACT_COLUMNS] != ref[:, EXACT_COLUMNS]).sum())
    rel = float((np.abs(dev[:, AVG_COLUMNS] - ref[:, AVG_COLUMNS])
                 / np.maximum(np.abs(ref[:, AVG_COLUMNS]), 1e-30)).max())
    delta = float(np.abs(probs - ref_probs).max())
    # a decision may differ only where the reference itself lies within the
    # probability limit of the threshold
    clear = np.abs(ref_probs - 0.5) > limits["prob_max_abs"]
    flips = int((((probs >= 0.5) != (ref_probs >= 0.5)) & clear).sum())
    return [
        number("exact_columns_wrong", wrong, limits),
        number("avg_amount_max_rel", rel, limits),
        number("prob_max_abs", delta, limits),
        number("decision_flips", flips, limits),
    ]


def number(name: str, value, limits: dict) -> dict:
    limit = limits[name]
    return {"name": name, "value": value, "limit": limit,
            "ok": bool(value <= limit)}


def check_rows(parts: Dict[int, dict], batch_ids: Dict[int, np.ndarray],
               sample: List[int], traffic, config: dict,
               reference_proba: Callable,
               lower_precision: bool = False,
               window_reference=None) -> List[dict]:
    """Run the reference over every batch in sink order and compare the
    sampled batches.

    ``batch_ids``: batch_index → the tx_ids of that part file (every batch,
    in order: each one's rows enter the aggregates). ``parts``: batch_index
    → the full columns of the sampled part files. With ``lower_precision``
    the CONTROL takes the program's place: what is compared is the bf16
    reference's features and the classifier behind a bf16 scaler.
    ``window_reference`` puts another class in ``WindowReference``'s place
    (the builder's comparison with the dense oracle of the tests)."""
    make_reference = window_reference or WindowReference
    order = sorted(batch_ids)
    first = int(traffic.lookup(batch_ids[order[0]])
                ["tx_datetime_us"].min() // US_PER_DAY)
    last = int(traffic.lookup(batch_ids[order[-1]])
               ["tx_datetime_us"].max() // US_PER_DAY)
    uni = config["key_universe"]

    def make(low: bool) -> WindowReference:
        return make_reference(
            config["features"], int(uni["customers"]), int(uni["terminals"]),
            first, last - first + 1, lower_precision=low)

    ref, ctl = make(False), (make(True) if lower_precision else None)
    want = set(sample)
    got_e, got_p, ref_f, echo_wrong = [], [], [], 0
    for b in order:
        cols = traffic.lookup(batch_ids[b])
        ref.update(cols)
        if ctl is not None:
            ctl.update(cols)
        if b not in want:
            continue
        ref_f.append(ref.features(cols))
        if ctl is not None:
            f = ctl.features(cols).astype(np.float32)
            got_e.append(f)
            got_p.append(reference_proba(f, lower_precision=True))
            continue
        part = parts[b]
        for k in ("tx_datetime_us", "customer_id", "terminal_id"):
            echo_wrong += int((np.asarray(part[k]) != cols[k]).sum())
        echo_wrong += int((np.rint(np.asarray(part["tx_amount"]) * 100.0)
                           .astype(np.int64) != cols["tx_amount_cents"]).sum())
        got_e.append(emitted_features(part))
        got_p.append(np.asarray(part["prediction"], np.float64))
    emitted, probs = np.concatenate(got_e), np.concatenate(got_p)
    # the classifier stage is held on the features the program emitted: a
    # float32 average one ulp off the float64 reference's may cross a split
    # threshold, which is rounding of the features (held here to 5e-6),
    # not a classifier fault
    limits = config["limits"]
    out = compare(emitted, probs, np.concatenate(ref_f),
                  reference_proba(emitted), limits)
    out.append(number("echo_columns_wrong", echo_wrong, limits))
    out.append({"name": "rows_compared", "value": len(probs), "limit": None,
                "ok": len(probs) > 0})
    return out
