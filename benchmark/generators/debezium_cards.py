"""Card-payment traffic as Debezium envelopes, drawn from a seed.

One general generator; a traffic mix is a JSON file of its parameters
(``benchmark/traffic/<name>.json``). It makes, from ``--seed``:

- the key draws (customers with weight proportional to a per-customer
  rate, terminals Zipf through a seeded permutation, amounts as the
  reference generator draws them) for the history fill and the window;
- one pool of envelope bytes at the real width, which every poll sends
  through the program's own decoder before the schedule's values replace
  what was decoded (see ``assumed`` in the configuration files);
- two sources with the engine's source protocol (``poll_batch() → column
  dict | {} when idle | None at the end``, ``offsets``, ``seek``): the
  history fill and the measured window.

The window source never slows when the engine does: in an open-loop mix a
row becomes visible at its due time, whatever the engine is doing.
"""

from __future__ import annotations

import base64
import calendar
import time
from typing import Callable, List, Optional, Tuple

import numpy as np

US_PER_DAY = 86_400_000_000

_ENVELOPE = (
    '{"schema":{"type":"struct","name":"debezium.payment.transactions.'
    'Envelope"},"payload":{"before":null,"after":{"tx_id":%d,'
    '"tx_datetime":%d,"customer_id":%d,"terminal_id":%d,"tx_amount":"%s"},'
    '"source":{"connector":"postgresql","db":"postgres","schema":"payment",'
    '"table":"transactions","ts_ms":%d},"op":"c","ts_ms":%d}}'
)


def decimal_cents(cents: int) -> str:
    """int cents → base64 of the minimal big-endian signed bytes, as
    Debezium writes a DECIMAL(10,2)."""
    n = int(cents)
    raw = n.to_bytes(max(1, (n.bit_length() + 8) // 8), "big", signed=True)
    return base64.b64encode(raw).decode("ascii")


def encode_envelopes(tx_id, t_us, customer, terminal, cents) -> List[bytes]:
    """Columns → Debezium change-event envelopes (op "c"), one per row."""
    return [
        (_ENVELOPE % (i, t, c, m, decimal_cents(a), t // 1000, t // 1000))
        .encode("ascii")
        for i, t, c, m, a in zip(tx_id.tolist(), t_us.tolist(),
                                 customer.tolist(), terminal.tolist(),
                                 cents.tolist())
    ]


def start_epoch_us(start_utc: str) -> int:
    return calendar.timegm(time.strptime(start_utc, "%Y-%m-%dT%H:%M:%S")) \
        * 1_000_000


class Draws:
    """Seeded key and amount draws: who pays whom how much."""

    def __init__(self, traffic: dict, n_customers: int, n_terminals: int,
                 seed: int):
        self.n_customers, self.n_terminals = n_customers, n_terminals
        self.zipf_s = float(traffic["terminal_zipf_s"])
        if not 0.0 < self.zipf_s < 1.0:
            raise ValueError("terminal_zipf_s must lie in (0, 1)")
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0xCA2D]))
        self._perm_c = rng.permutation(n_customers).astype(np.int64)
        self._perm_t = rng.permutation(n_terminals).astype(np.int64)
        lo, hi = traffic["amount_mean_range"]
        self._mean_amount = rng.uniform(lo, hi, n_customers)

    def draw(self, rng: np.random.Generator, n: int) -> Tuple[
            np.ndarray, np.ndarray, np.ndarray]:
        """→ (customer_id, terminal_id, amount_cents), each int64 [n]."""
        # rate of the customer of rank i is rate_max*(i+0.5)/N, so the
        # CDF over ranks is (i/N)^2 and its inverse a square root
        rank_c = np.minimum(
            (self.n_customers * np.sqrt(rng.random(n))).astype(np.int64),
            self.n_customers - 1)
        customer = self._perm_c[rank_c]
        # Zipf(s) over ranks 1..N by the continuous inverse CDF
        e = 1.0 - self.zipf_s
        x = (1.0 + rng.random(n) * (self.n_terminals ** e - 1.0)) ** (1.0 / e)
        rank_t = np.minimum(x.astype(np.int64) - 1, self.n_terminals - 1)
        terminal = self._perm_t[rank_t]
        mean = self._mean_amount[customer]
        amount = rng.normal(mean, mean / 2.0)
        neg = amount < 0
        amount[neg] = rng.uniform(0.0, 2.0 * mean[neg])
        cents = np.maximum(np.rint(amount * 100.0), 1.0).astype(np.int64)
        return customer, terminal, cents


class _EnvelopeSource:
    """Serves rows by schedule index: a slice of the envelope pool goes
    through the program's decoder (round-robin over the partitions, as a
    consumer of a partitioned topic polls), then the schedule's values
    replace the decoded ones."""

    def __init__(self, pool_parts: List[List[bytes]], decode: Callable):
        self._parts = pool_parts
        self._decode = decode
        self._offsets = [0] * len(pool_parts)

    @property
    def offsets(self) -> List[int]:
        return list(self._offsets)

    def seek(self, offsets) -> None:
        self._offsets = [int(o) for o in offsets]

    def _messages(self, n: int) -> List[bytes]:
        msgs: List[bytes] = []
        n_parts = len(self._parts)
        per, extra = divmod(n, n_parts)
        for p, part in enumerate(self._parts):
            k = per + (1 if p < extra else 0)
            start = self._offsets[p] % len(part)
            take = part[start:start + k]
            while len(take) < k:  # wrap around the pool
                take = take + part[:k - len(take)]
            msgs += take
            self._offsets[p] += k
        return msgs

    def _emit(self, tx_id, t_us, customer, terminal, cents) -> dict:
        cols, invalid = self._decode(self._messages(len(tx_id)), t_us // 1000)
        if invalid.any():
            raise RuntimeError("the envelope pool holds an invalid message")
        cols["tx_id"] = tx_id
        cols["tx_datetime_us"] = t_us
        cols["customer_id"] = customer
        cols["terminal_id"] = terminal
        cols["tx_amount_cents"] = cents
        return cols


class FillSource(_EnvelopeSource):
    """History fill: ``fill_batches`` polls of ``fill_batch_rows`` rows,
    one event day each, the days before the window's."""

    def __init__(self, traffic_obj: "Traffic", pool_parts, decode):
        super().__init__(pool_parts, decode)
        self._t = traffic_obj
        self._next = 0

    def poll_batch(self) -> Optional[dict]:
        t = self._t
        if self._next >= t.fill_batches:
            return None
        s = self._next * t.fill_batch_rows
        e = s + t.fill_batch_rows
        self._next += 1
        return self._emit(np.arange(s, e, dtype=np.int64), t.fill_us[s:e],
                          t.fill_customer[s:e], t.fill_terminal[s:e],
                          t.fill_cents[s:e])


class WindowSource(_EnvelopeSource):
    """The measured window. ``arrivals="backlogged"``: every poll returns
    ``max_poll_rows`` rows created now. ``arrivals="poisson"``: a poll
    returns every row whose due time has passed (at most ``max_poll_rows``;
    the rest stay queued). The window opens at the first poll and closes
    ``seconds`` later; rows due before the close are still served after
    it, then the source ends.

    ``timers``: ``[(seconds after the open, fn)]``, each fired once from
    the poll that first sees its time (the loop thread; used to start and
    stop the profiler in a traced run)."""

    def __init__(self, traffic_obj: "Traffic", pool_parts, decode,
                 seconds: float, timers=()):
        super().__init__(pool_parts, decode)
        self._t = traffic_obj
        self.seconds = float(seconds)
        self._timers = sorted(timers, key=lambda tf: tf[0])
        self.t_open: Optional[float] = None
        self._cursor = 0  # window rows handed out so far
        self.polls: List[Tuple[float, int, int]] = []  # (t_rel, start, n)
        self.poll_s: List[float] = []  # seconds spent inside each poll

    def _fire(self, rel: float) -> None:
        while self._timers and rel >= self._timers[0][0]:
            self._timers.pop(0)[1]()

    def poll_batch(self) -> Optional[dict]:
        t = self._t
        now = time.perf_counter()
        if self.t_open is None:
            self.t_open = now
        rel = now - self.t_open
        self._fire(rel)
        closed = rel >= self.seconds
        if t.arrivals == "backlogged":
            if closed:
                return None
            n = t.max_poll_rows
            event_us = np.full(n, t.start_us + int(rel * 1e6), np.int64)
        else:
            due = t.due_s
            hi = len(due) if closed else int(
                np.searchsorted(due, rel, side="right"))
            n = min(hi - self._cursor, t.max_poll_rows)
            if n <= 0:
                if closed:
                    return None
                time.sleep(0.0002)  # nothing is due yet
                return {}
            event_us = t.start_us + (
                due[self._cursor:self._cursor + n] * 1e6).astype(np.int64)
        s = self._cursor
        self._cursor += n
        self.polls.append((rel, s, n))
        j = np.arange(s, s + n) % t.draw_rows
        cols = self._emit(np.arange(t.n_fill + s, t.n_fill + s + n,
                                    dtype=np.int64), event_us,
                          t.win_customer[j], t.win_terminal[j],
                          t.win_cents[j])
        self.poll_s.append(time.perf_counter() - now)
        return cols

    @property
    def rows_polled(self) -> int:
        return self._cursor


class Traffic:
    """Everything one run's traffic is: draws, pool, sources, and the
    schedule's values by ``tx_id`` for the plain reference."""

    def __init__(self, traffic: dict, config: dict, seed: int,
                 seconds: float, decode: Callable):
        if float(traffic.get("late_share", 0)) != 0.0:
            raise ValueError("this generator draws no late rows yet: "
                             "late_share must be 0")
        self.arrivals = traffic["arrivals"]
        if self.arrivals not in ("backlogged", "poisson"):
            raise ValueError(f"unknown arrivals {self.arrivals!r}")
        self.seconds = float(seconds)
        self.max_poll_rows = int(traffic["max_poll_rows"])
        self.fill_batches = int(traffic["fill_batches"])
        self.fill_batch_rows = int(traffic["fill_batch_rows"])
        self.n_fill = self.fill_batches * self.fill_batch_rows
        self.draw_rows = int(traffic["draw_rows"])
        self.start_us = start_epoch_us(traffic["start_utc"])
        self._decode = decode
        uni = config["key_universe"]
        draws = Draws(traffic, int(uni["customers"]), int(uni["terminals"]),
                      seed)
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0x7A3B]))
        self.fill_customer, self.fill_terminal, self.fill_cents = draws.draw(
            rng, self.n_fill)
        # one event day per fill batch, the days before the window's day,
        # times of day uniform
        day0 = self.start_us // US_PER_DAY - self.fill_batches
        self.fill_us = (
            (day0 + np.arange(self.n_fill) // self.fill_batch_rows)
            * US_PER_DAY + rng.integers(0, US_PER_DAY, self.n_fill)
        ).astype(np.int64)
        self.win_customer, self.win_terminal, self.win_cents = draws.draw(
            rng, self.draw_rows)
        self.rate = 0.0
        self.due_s = np.empty(0)
        if self.arrivals == "poisson":
            self.rate = float(traffic["rate_rows_per_s"])
            if self.rate <= 0:
                raise ValueError("rate_rows_per_s must be a positive number")
            n = int(self.rate * self.seconds * 1.02) + 10_000
            due = np.cumsum(rng.exponential(1.0 / self.rate, n))
            self.due_s = due[due < self.seconds]
            if len(self.due_s) == n:
                raise RuntimeError("arrival draw too short for the window")
        # the envelope pool, split into partitions
        n_pool = int(traffic["pool_envelopes"])
        n_parts = int(config["ingest"]["partitions"])
        pc, pt, pa = draws.draw(rng, n_pool)
        pool = encode_envelopes(
            np.arange(n_pool, dtype=np.int64),
            self.start_us + np.arange(n_pool, dtype=np.int64), pc, pt, pa)
        self.envelope_bytes = sum(map(len, pool)) / n_pool
        self._parts = [pool[p::n_parts] for p in range(n_parts)]
        self.window: Optional[WindowSource] = None
        self._event_us: Optional[np.ndarray] = None

    def fill_source(self) -> FillSource:
        return FillSource(self, self._parts, self._decode)

    def window_source(self, timers=()) -> WindowSource:
        self.window = WindowSource(self, self._parts, self._decode,
                                   self.seconds, timers)
        return self.window

    # -- the schedule, by tx_id, for the reference and the latencies -----

    def _window_event_us(self) -> np.ndarray:
        """Event time of every window row served; built once, after the
        window (the check looks rows up batch by batch)."""
        w = self.window
        if self._event_us is None or len(self._event_us) != w.rows_polled:
            if self.arrivals == "poisson":
                self._event_us = self.start_us + (
                    self.due_s[:w.rows_polled] * 1e6).astype(np.int64)
            else:
                stamps = np.asarray([self.start_us + int(rel * 1e6)
                                     for rel, _, _ in w.polls], np.int64)
                self._event_us = np.repeat(stamps,
                                           [n for _, _, n in w.polls])
        return self._event_us

    def lookup(self, tx_id: np.ndarray) -> dict:
        """Schedule values of rows the sources have served."""
        tx_id = np.asarray(tx_id, np.int64)
        out = {k: np.empty(len(tx_id), np.int64) for k in
               ("tx_datetime_us", "customer_id", "terminal_id",
                "tx_amount_cents")}
        f = tx_id < self.n_fill
        i = tx_id[f]
        out["tx_datetime_us"][f] = self.fill_us[i]
        out["customer_id"][f] = self.fill_customer[i]
        out["terminal_id"][f] = self.fill_terminal[i]
        out["tx_amount_cents"][f] = self.fill_cents[i]
        if (~f).any():
            k = tx_id[~f] - self.n_fill
            j = k % self.draw_rows
            out["tx_datetime_us"][~f] = self._window_event_us()[k]
            out["customer_id"][~f] = self.win_customer[j]
            out["terminal_id"][~f] = self.win_terminal[j]
            out["tx_amount_cents"][~f] = self.win_cents[j]
        return out

    def due_rel_s(self, k: np.ndarray) -> np.ndarray:
        """Seconds after the window's open at which window row ``k`` was
        created: its due time (open loop) or its poll (backlogged)."""
        if self.arrivals == "poisson":
            return self.due_s[k]
        rel = np.repeat([r for r, _, _ in self.window.polls],
                        [n for _, _, n in self.window.polls])
        return rel[k]

    def rows_due(self) -> int:
        """Rows created inside the window (``attempted``)."""
        if self.arrivals == "poisson":
            return len(self.due_s)
        return self.window.rows_polled

    def draw_stats(self) -> dict:
        """How far the window went into its draw: the rows it polled, the
        draw's length, and how many times the draw was begun again
        (``draw_wraps``: 0 while every window row is a draw of its own)."""
        polled = self.window.rows_polled
        return {"rows_polled": polled, "draw_rows": self.draw_rows,
                "draw_wraps": max(polled - 1, 0) // self.draw_rows}

    def queue_stats(self) -> dict:
        """Entry-queue readings of the window, for the ``traffic`` reader."""
        w = self.window
        polls = [p for p in w.polls if p[0] < self.seconds]
        out = {"polls": len(polls)}
        if polls:
            out["batch_rows_p50"] = float(np.median([n for _, _, n in polls]))
        if self.arrivals == "poisson" and polls:
            lag = np.concatenate([
                rel - self.due_s[s:s + n] for rel, s, n in polls])
            out["poll_lag_p50_ms"] = float(np.median(lag) * 1e3)
            taken = polls[-1][1] + polls[-1][2]
            out["backlog_rows_end"] = float(len(self.due_s) - taken)
        return out


def build(traffic: dict, config: dict, seed: int, seconds: float,
          decode: Callable) -> Traffic:
    return Traffic(traffic, config, seed, seconds, decode)
