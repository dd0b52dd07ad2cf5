"""``debezium_cards_active`` for ids as wide as a card number: the same
traffic over an active set, its ids drawn from ``[key_floor,
key_universe)`` where the universe is 16 digits or 63 bits wide.

A processor that keys its stream by what is printed on the card (ISO/IEC
7812 PANs, 16 digits: the public Sparkov set's ``cc_num``) and names a
merchant by a 64-bit hash (IBM's TabFormer set) sends ids that no 32-bit
word holds. This generator is to ``debezium_cards_active`` what that one
is to ``debezium_cards``: the same laws over the active keys — a linear
per-customer rate, a Zipf over terminals — and rank → id through a seeded
sample of the universe. Two things differ:

- the sample is drawn BY REJECTION (draw, find the repeats, draw those
  again): ``rng.permutation(universe)`` cannot reach 10^16. Ids are
  uniform distinct draws — no Luhn digit, no issuer prefix — so their
  32-bit xor-fold is uniform too and the number of pairs of active ids
  that fold alike follows the law n^2 / 2^33; :func:`fold_alias_pairs`
  counts them, and the set-up says the count (``[traffic]`` line);
- the envelope pool is encoded again with ids of the stated width, so
  that every poll sends 16- to 19-digit ids through the program's decoder
  and ``envelope_bytes`` is what such a stream weighs.

``planted_fold_pairs`` (a configuration key no cell's file sets; the
benchmark's own rehearsal does) turns the last k ids of a sample into
fold twins of its first k: a toy of 8,192 ids has none by the law.

The classes, the sources and everything the harness and the reference
call (``fill_source``, ``window_source``, ``lookup``, ``due_rel_s``,
``rows_due``, ``queue_stats``, ``draw_stats``, ``envelope_bytes``) are
``debezium_cards``'s own.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from benchmark.generators import debezium_cards as base


def sample_wide_ids(rng: np.random.Generator, floor: int, universe: int,
                    active: int) -> np.ndarray:
    """``active`` distinct ids of ``[floor, universe)``, int64, in draw
    order: uniform draws, the later of every repeat drawn again until
    none is left (a universe of 10^16 and 2 M draws: ~0.2 repeats)."""
    if not 0 <= floor < universe <= 1 << 63:
        raise ValueError("ids must lie in [key_floor, key_universe) within "
                         "the non-negative int64s")
    if not 0 < active <= universe - floor:
        raise ValueError("active_keys must lie in (0, key_universe - "
                         "key_floor]")
    ids = rng.integers(floor, universe, size=active, dtype=np.int64)
    while True:
        in_order = np.sort(ids)
        if bool((in_order[1:] != in_order[:-1]).all()):
            return ids
        _, first = np.unique(ids, return_index=True)
        again = np.ones(active, bool)
        again[first] = False
        ids[again] = rng.integers(floor, universe, size=int(again.sum()),
                                  dtype=np.int64)


def plant_fold_pairs(rng: np.random.Generator, ids: np.ndarray, pairs: int,
                     floor: int, universe: int) -> np.ndarray:
    """``ids`` with its last ``pairs`` entries replaced by fold twins of
    its first ``pairs``: the same bits flipped in both words, so the xor
    of the words stands and the id does not; still distinct, still in
    ``[floor, universe)``."""
    if not pairs:
        return ids
    if 2 * pairs > len(ids):
        raise ValueError("planted_fold_pairs takes at most half the ids")
    ids = ids.copy()
    todo = np.arange(pairs)
    while todo.size:
        m = rng.integers(1, 1 << 12, size=todo.size, dtype=np.int64)
        twin = ids[todo] ^ m ^ (m << 32)
        ids[len(ids) - pairs + todo] = twin
        held, count = np.unique(ids, return_counts=True)
        bad = ((twin < floor) | (twin >= universe)
               | np.isin(twin, held[count > 1]))
        todo = todo[bad]
    return ids


def fold_alias_pairs(ids: np.ndarray) -> int:
    """Pairs of ``ids`` whose two 32-bit words xor alike — the pairs a
    32-bit deployment (``core/batch.fold_key``) serves as one key."""
    v = np.asarray(ids, np.int64).view(np.uint64)
    fold = ((v ^ (v >> np.uint64(32))) & np.uint64(0xFFFFFFFF))
    _, n = np.unique(fold, return_counts=True)
    return int((n * (n - 1) // 2).sum())


class Traffic(base.Traffic):
    """``debezium_cards.Traffic`` whose keys are wide active ids.
    ``active_customer_ids`` / ``active_terminal_ids`` are the two samples
    (index → id), ``fold_alias_pairs`` their counts of equal-fold pairs."""

    def __init__(self, traffic: dict, config: dict, seed: int,
                 seconds: float, decode: Callable):
        uni, active = config["key_universe"], config["active_keys"]
        floor = config["key_floor"]
        super().__init__(traffic, dict(config, key_universe=active), seed,
                         seconds, decode)
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0x1D64]))
        planted = config.get("planted_fold_pairs", {})

        def sample(table: str) -> np.ndarray:
            lo, hi = int(floor[table]), int(uni[table])
            return plant_fold_pairs(
                rng, sample_wide_ids(rng, lo, hi, int(active[table])),
                int(planted.get(table, 0)), lo, hi)

        self.active_customer_ids = sample("customers")
        self.active_terminal_ids = sample("terminals")
        self.fill_customer = self.active_customer_ids[self.fill_customer]
        self.win_customer = self.active_customer_ids[self.win_customer]
        self.fill_terminal = self.active_terminal_ids[self.fill_terminal]
        self.win_terminal = self.active_terminal_ids[self.win_terminal]
        self.fold_alias_pairs = {
            "customers": fold_alias_pairs(self.active_customer_ids),
            "terminals": fold_alias_pairs(self.active_terminal_ids)}
        # the pool again, at the ids' real width (the base encoded one
        # from the dense indices it draws over)
        draws = base.Draws(traffic, int(active["customers"]),
                           int(active["terminals"]), seed)
        n_pool = int(traffic["pool_envelopes"])
        n_parts = int(config["ingest"]["partitions"])
        pc, pt, pa = draws.draw(rng, n_pool)
        pool = base.encode_envelopes(
            np.arange(n_pool, dtype=np.int64),
            self.start_us + np.arange(n_pool, dtype=np.int64),
            self.active_customer_ids[pc], self.active_terminal_ids[pt], pa)
        self.envelope_bytes = sum(map(len, pool)) / n_pool
        self._parts = [pool[p::n_parts] for p in range(n_parts)]
        print("[traffic] generator=debezium_cards_id64 "
              f"fold_alias_pairs_customers={self.fold_alias_pairs['customers']} "
              f"fold_alias_pairs_terminals={self.fold_alias_pairs['terminals']} "
              f"envelope_bytes={self.envelope_bytes:.1f}", flush=True)


def build(traffic: dict, config: dict, seed: int, seconds: float,
          decode: Callable) -> Traffic:
    return Traffic(traffic, config, seed, seconds, decode)
