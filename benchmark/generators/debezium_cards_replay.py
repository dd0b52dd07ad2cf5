"""``debezium_cards_active`` replayed: the same traffic over an active set
inside a larger id universe, with an event clock that MOVES and an active
set that moves with it.

A scorer is not live until its window state is rebuilt from history: the
reference bootstraps with ``make load_initial_data`` (day-partitioned
history), this system by running the history through ``rtfds score
--source replay`` at the loop's full rate. Months of event time pass in
minutes of wall time. Three things differ from ``debezium_cards_active``:

- **event time is the row's position in the replay**, not the poll's wall
  time: window row *k* has event day ``k // rows_per_event_day`` and time
  of day ``(k % rows_per_event_day) / rows_per_event_day`` of 24 h,
  ascending (a table replayed in ``tx_id`` order), counted from
  ``start_utc``. ``kafka_ts_ms`` stays the poll's wall time. The history
  fill is ``debezium_cards``'s own (one event day a batch, the days before
  ``start_utc``);
- **ids are issued serially and retire for good** (the reference's
  ``postgres/init.sql``: ``SERIAL``). The ids are an ASCENDING seeded
  sample of the universe (``active_customer_ids`` / ``active_terminal_ids``:
  sample position → id), made as a running sum of seeded gaps, not by
  permuting the universe. On event day *d* the ids in use are the
  ``active_keys`` consecutive sample positions from ``issued_per_event_day
  × (d + fill_batches)``: every day the lowest ``issued_per_event_day``
  retire and as many new ones are issued above;
- **a rank reaches an id that is stable for that id's whole life**: ranks
  are drawn by ``debezium_cards``'s two laws over ``active_keys`` dense
  indices, and an index *p* on a day whose first position is *off* sits at
  sample position ``off + ((p − off) mod A)``, ``A = active_keys`` — the
  one position of the day's ``A`` whose residue mod ``A`` is *p*. A card
  keeps its rate and a terminal its popularity from issue to retirement,
  the id that retires hands its rank to the one issued in its place, and
  a customer's mean amount belongs to the residue.

The window's draw is made a block of rows at a time, each block from a
stream of its own: the temporaries of a block stay in the cache and in
pages the allocator already holds, where one draw of 2^25 rows spends
most of its time faulting fresh pages in (0.10 against 0.44 us a row on
the builder's machine; threads on top made it slower).

The sources, the envelope pool and everything the harness and the
reference call (``fill_source``, ``window_source``, ``lookup``,
``due_rel_s``, ``rows_due``, ``queue_stats``, ``draw_stats``,
``envelope_bytes``) keep ``debezium_cards``'s contracts. After the window
``draw_stats`` also prints, on a ``[traffic]`` line, the event days the
window spanned, the polls that held two of them and the ids issued.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from benchmark.generators import debezium_cards as base
from benchmark.generators import debezium_cards_active as active

US_PER_DAY = base.US_PER_DAY
BLOCK_ROWS = 1 << 20  # rows a block of the window's draw


def ascending_ids(rng: np.random.Generator, universe: int,
                  count: int) -> np.ndarray:
    """``count`` distinct ids of ``[0, universe)``, int64, ASCENDING: a
    running sum of seeded gaps, uniform on ``[1, 2g − 1]`` with ``g =
    universe // count`` their mean (so the ids spread over ``g × count``
    of the universe, and are consecutive where ``g`` is 1)."""
    if not 0 < count <= universe:
        raise ValueError("the ids in use over the run (active_keys + "
                         "issued_per_event_day x its event days) must lie "
                         "in (0, key_universe]")
    g = universe // count
    ids = np.cumsum(rng.integers(1, 2 * g, size=count, dtype=np.int64)) - 1
    if ids[-1] >= universe:  # ~sqrt(count) x g / 2 over a mean of g x count
        raise ValueError("the seeded gaps ran past key_universe")
    return ids


class _Window(base.WindowSource):
    """``WindowSource`` whose rows carry the replay's event time."""

    def _emit(self, tx_id, t_us, customer, terminal, cents) -> dict:
        # t_us, the poll's wall time, still stamps kafka_ts_ms
        cols = super()._emit(tx_id, t_us, customer, terminal, cents)
        cols["tx_datetime_us"] = self._t.event_us(tx_id - self._t.n_fill)
        return cols


class Traffic(active.Traffic):
    """``debezium_cards_active.Traffic`` under a moving clock.
    ``active_customer_ids`` / ``active_terminal_ids`` are the ascending
    samples (sample position → id)."""

    def __init__(self, traffic: dict, config: dict, seed: int,
                 seconds: float, decode: Callable):
        if traffic["arrivals"] != "backlogged":
            raise ValueError("a replay is backlogged: the table is there")
        uni, act = config["key_universe"], config["active_keys"]
        issued = config["issued_per_event_day"]
        self.rows_per_event_day = int(traffic["rows_per_event_day"])
        if self.rows_per_event_day <= 0:
            raise ValueError("rows_per_event_day must be positive")
        draw_rows = int(traffic["draw_rows"])
        # the base classes draw the fill and the pool over active_keys
        # dense indices (their "sample" of a universe of active_keys ids
        # is one more seeded permutation of the indices); the window's
        # draw is made below
        super().__init__(dict(traffic, draw_rows=0),
                         dict(config, key_universe=act), seed, seconds,
                         decode)
        self.draw_rows = draw_rows
        index_c, index_t = self.active_customer_ids, self.active_terminal_ids
        draw_days = -(-draw_rows // self.rows_per_event_day)
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0x2E91A]))
        self._tables = {}
        for name in ("customers", "terminals"):
            a, per_day = int(act[name]), int(issued[name])
            ids = ascending_ids(
                rng, int(uni[name]),
                a + per_day * (self.fill_batches + draw_days))
            self._tables[name] = (a, per_day, ids)
        self.active_customer_ids = self._tables["customers"][2]
        self.active_terminal_ids = self._tables["terminals"][2]
        fill_day = np.arange(self.n_fill) // self.fill_batch_rows \
            - self.fill_batches
        self.fill_customer = self._ids("customers", self.fill_customer,
                                       fill_day)
        self.fill_terminal = self._ids("terminals", self.fill_terminal,
                                       fill_day)
        # the base class keeps no handle on its Draws: the same seed makes
        # the same permutations and mean amounts again (~0.4 s)
        draws = base.Draws(traffic, int(act["customers"]),
                           int(act["terminals"]), seed)
        # np.full writes each column whole, so that the blocks below write
        # into pages that are there: a block's first touch of fresh pages
        # costs ten times a second touch
        self.win_customer = np.full(draw_rows, 0, np.int64)
        self.win_terminal = np.full(draw_rows, 0, np.int64)
        self.win_cents = np.full(draw_rows, 0, np.int64)
        for b, s in enumerate(range(0, draw_rows, BLOCK_ROWS)):
            e = min(s + BLOCK_ROWS, draw_rows)
            c, t, self.win_cents[s:e] = draws.draw(np.random.default_rng(
                np.random.SeedSequence([seed, 0x2E91B, b])), e - s)
            day = np.arange(s, e) // self.rows_per_event_day
            self.win_customer[s:e] = self._ids("customers", index_c[c], day)
            self.win_terminal[s:e] = self._ids("terminals", index_t[t], day)

    def _ids(self, name: str, index: np.ndarray,
             day: np.ndarray) -> np.ndarray:
        """Ids of dense indices on event days (0 = ``start_utc``'s)."""
        a, per_day, ids = self._tables[name]
        off = per_day * (day + self.fill_batches)
        return ids[off + (index - off) % a]

    def event_us(self, k: np.ndarray) -> np.ndarray:
        """Event time of window rows ``k``: their place in the replay."""
        r = self.rows_per_event_day
        return self.start_us + (k // r) * US_PER_DAY \
            + (k % r) * US_PER_DAY // r

    def window_source(self, timers=()) -> base.WindowSource:
        self.window = _Window(self, self._parts, self._decode, self.seconds,
                              timers)
        return self.window

    def _window_event_us(self) -> np.ndarray:
        polled = self.window.rows_polled
        if self._event_us is None or len(self._event_us) != polled:
            self._event_us = self.event_us(np.arange(polled, dtype=np.int64))
        return self._event_us

    def replay_stats(self) -> dict:
        """The event days the window spanned, the polls that held two of
        them, and the ids that were in use on some day up to its last."""
        r, polls = self.rows_per_event_day, self.window.polls
        last_day = max(self.window.rows_polled - 1, 0) // r
        out = {"event_days_spanned": last_day + 1,
               "multi_day_polls": sum(
                   1 for _, s, n in polls if s // r != (s + n - 1) // r),
               "polls": len(polls)}
        for name, (a, per_day, _) in self._tables.items():
            out[f"ids_issued_{name}"] = a + per_day * (
                self.fill_batches + last_day)
        return out

    def draw_stats(self) -> dict:
        # the harness asks once, after the window: the place to say it
        print("[traffic] generator=debezium_cards_replay " + " ".join(
            f"{k}={v}" for k, v in self.replay_stats().items()), flush=True)
        return super().draw_stats()


def build(traffic: dict, config: dict, seed: int, seconds: float,
          decode: Callable) -> Traffic:
    return Traffic(traffic, config, seed, seconds, decode)
