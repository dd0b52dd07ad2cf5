"""``debezium_cards`` for ids that outgrow the keys in use: the same
traffic, drawn over an *active set* inside a larger id universe.

A processor issues ids serially and never reuses one (the reference's
``postgres/init.sql``: ``SERIAL``), so the highest id runs past the cards
and merchants active inside the days a window can see. The configuration
states both: ``key_universe`` (ids lie in ``[0, universe)``) and
``active_keys`` (how many of them the traffic touches). This generator
changes one thing of ``debezium_cards``: ranks are drawn over the active
keys — the same laws, a linear per-customer rate and a Zipf over
terminals — and rank → id goes through a seeded sample without
replacement of the universe. The classes, the sources, the envelope pool
and everything the harness and the reference call (``fill_source``,
``window_source``, ``lookup``, ``due_rel_s``, ``rows_due``,
``queue_stats``, ``envelope_bytes``) are ``debezium_cards``'s own.

How: ``debezium_cards.Traffic`` draws over a universe of ``active_keys``
— its seeded permutation takes a rank to one of ``active`` dense indices,
a customer's mean amount belongs to its index — and the schedule's key
columns then go from index to id through the sample. The pool's
envelopes keep the indices they were encoded with: every poll overwrites
the decoded ids with the schedule's, as in every cell (``assumed`` in the
configuration files).
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from benchmark.generators import debezium_cards as base


def sample_ids(rng: np.random.Generator, universe: int,
               active: int) -> np.ndarray:
    """``active`` distinct ids of ``[0, universe)``, int64, in draw order."""
    if not 0 < active <= universe:
        raise ValueError("active_keys must lie in (0, key_universe]")
    return rng.permutation(universe)[:active].astype(np.int64)


class Traffic(base.Traffic):
    """``debezium_cards.Traffic`` whose keys are the active ids.
    ``active_customer_ids`` / ``active_terminal_ids`` are the two samples
    (index → id)."""

    def __init__(self, traffic: dict, config: dict, seed: int,
                 seconds: float, decode: Callable):
        uni, active = config["key_universe"], config["active_keys"]
        super().__init__(traffic, dict(config, key_universe=active), seed,
                         seconds, decode)
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0xAC71]))
        self.active_customer_ids = sample_ids(
            rng, int(uni["customers"]), int(active["customers"]))
        self.active_terminal_ids = sample_ids(
            rng, int(uni["terminals"]), int(active["terminals"]))
        self.fill_customer = self.active_customer_ids[self.fill_customer]
        self.win_customer = self.active_customer_ids[self.win_customer]
        self.fill_terminal = self.active_terminal_ids[self.fill_terminal]
        self.win_terminal = self.active_terminal_ids[self.win_terminal]


def build(traffic: dict, config: dict, seed: int, seconds: float,
          decode: Callable) -> Traffic:
    return Traffic(traffic, config, seed, seconds, decode)
