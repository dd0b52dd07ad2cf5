"""The dense ``[universe, days]`` form of ``benchmark/reference.py``'s
``WindowReference``, as it stood until PR 40: the tests' oracle for the
sparse one (``test_reference.py``), nothing a run imports. Its tables are
sized by the id universe (6.9 GB of host memory at the exact cells'
universe, 13.8 GB at the four-chip cell's), which is why it went."""

from __future__ import annotations

import numpy as np

from benchmark.reference import US_PER_DAY, bf16_round


class DenseWindowReference:
    """Daily aggregates per key over the days a run touches.

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
        self.c_cnt = np.zeros((n_customers, n_days), np.int32)
        self.c_amt = np.zeros((n_customers, n_days), np.float64)
        self.t_cnt = np.zeros((n_terminals, n_days), np.int32)

    def _day_index(self, t_us: np.ndarray) -> np.ndarray:
        d = t_us // US_PER_DAY - self.first_day
        if d.min() < 0 or d.max() >= self.n_days:
            raise ValueError("an event day lies outside the reference's days")
        return d

    def update(self, cols: dict) -> None:
        """One batch's rows enter the aggregates."""
        c, t = cols["customer_id"], cols["terminal_id"]
        d = self._day_index(cols["tx_datetime_us"])
        amount = (cols["tx_amount_cents"] / 100.0).astype(np.float32)
        # flat views: ufunc.at is fast on one-dimensional indices
        np.add.at(self.c_cnt.reshape(-1), c * self.n_days + d, 1)
        np.add.at(self.c_amt.reshape(-1), c * self.n_days + d,
                  amount.astype(np.float64))
        np.add.at(self.t_cnt.reshape(-1), t * self.n_days + d, 1)
        if self.low:
            self.c_amt[c, d] = bf16_round(self.c_amt[c, d])
        for back in range(self.ring, self.n_days, self.ring):
            old = d >= back  # the ring forgets
            self.c_cnt[c[old], d[old] - back] = 0
            self.c_amt[c[old], d[old] - back] = 0.0
            self.t_cnt[t[old], d[old] - back] = 0

    def _window_sums(self, table, key, last_day) -> np.ndarray:
        """[n, len(windows)]: table[key, last_day-w+1 .. last_day]."""
        rows = table[key].astype(np.float64)
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
        c, t = cols["customer_id"], cols["terminal_id"]
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
