"""Exact on-device key directory: open-addressing uint32 key→slot table.

The dense ``WindowState`` tier historically coupled its capacity to the
key universe: ``key_mode="direct"`` needs capacity ≥ max(key) + 1 (a
10M-customer corpus is ~5 GB of HBM window state before donation
double-buffering), while ``key_mode="hash"`` silently MERGES colliding
keys' windows. This module decouples the two: the hot tier is sized to
the *active working set* (``slot_capacity`` rows) and an open-addressing
hash directory (``dir_capacity`` = 2× slots → load factor ≤ 0.5) maps
keys to slots *exactly* — a key either owns a private slot or it misses
admission and is served from the count-min sketch tier, but two keys
never share window state.

Everything is vectorized, fixed-shape and jit/shard_map-friendly:

- **probing** is double hashing over a power-of-two table
  (``h1 + j·(h2|1)``, an odd stride walks the whole table) with a FIXED
  probe depth — lookups scan all P candidate positions and pick the
  match, so the LOOKUP has no early-exit data dependence and deleted
  entries need no tombstones;
- **batched insert** resolves scatter races with claim rounds: round j's
  writers scatter-min their key into still-empty positions, re-read, and
  the losers continue to probe j+1. Batch duplicates of one new key all
  win the same entry; a scatter-min of the row index picks ONE owner to
  pop the free-slot stack, so one key costs one slot. The rounds do end
  early, and exactly: they follow the full-depth lookup, only rows whose
  key it did not find take part, and a round is an identity for every
  placed row — so the rounds run while one is left and a row is
  unplaced (P at most; none for a batch of known keys; ~log(new keys) ÷
  log(1 ÷ load) while keys arrive) and their answers are the P rounds'.
  And they run as wide as what is unplaced: over the batch while more
  rows are unplaced than ``CLAIM_LANES``, then over those rows alone,
  packed once into that many lanes — what is unplaced thins by the
  directory's load a round, a round costs what its rows cost, so all
  but a batch's first round or two are narrow (``_claim_rounds``);
- **the free-slot stack** (``free``/``free_top``) is the admission
  bound: when it runs dry the claimed entry is rolled back and the row
  reports ``admitted=False`` — a full hot tier degrades to the sketch
  tier instead of clobbering a live slot;
- **reclaim** pushes dead slots back on the stack and vacates their
  directory entries (no tombstones needed — see probing above), which is
  what the engine's recency compaction pass calls; its indexed work is
  lane-packed, as wide as what it vacates.

Sentinel note: ``EMPTY_KEY`` (0xFFFFFFFF) is reserved; a real key equal
to it is remapped to 0xFFFFFFFE (``fold_key`` output collides with that
one value in 2^32 — the same order of aliasing the 32-bit fold already
accepts).

**Wide keys** (``FeatureConfig.key_bits=64``; a key is ``uint32 [2, B]``,
low words then high words). The TPU has no 64-bit integer lane worth
using, so the probe table stays what it is — ``keys`` holds a 32-bit
FINGERPRINT of the entry's key, the xor-fold of its two words (what the
32-bit deployment stores as the key itself), probed by the same double
hashing — and the key itself sits beside it in two more ``[dir_cap]``
leaves, ``keys_lo`` / ``keys_hi``, read once a row at the entry whose
fingerprint matched. Two ids that fold alike share a fingerprint and a
probe path, never an entry:

- the lookup verifies both words at the first fingerprint match; a row
  whose match holds ANOTHER key (an alias) strikes that position and
  looks at its next match, in a ``lax.while_loop`` that runs while such a
  row is left — one trip for a batch without aliases, and
  ``rtfds_keydir_alias_rows_total`` counts the rows that took more;
- the claim rounds elect by fingerprint as they always did; the grant's
  owner (lowest row of an entry) writes both words, every claimant
  re-reads them, and claimants that find another key there — two NEW ids
  of one fingerprint raced for one position in one round — go through
  the rounds and the grant again (a pass), in a loop that runs while
  such a row is left: ~(new keys)^2 / 2^33 of a second pass a batch;
- vacancy is the fingerprint's (``EMPTY_KEY``, never a fingerprint:
  ``_canon``), so NO id is unrepresentable in the directory. One pattern
  is reserved all the same, ``0xFFFFFFFF_FFFFFFFF`` (int64 -1): it marks
  a padding lane of the demote and promote payloads, and a row that
  carries it is never admitted (the sketch tier serves and counts it).
  ``keys_lo`` / ``keys_hi`` of a vacant entry are stale, never read.
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from real_time_fraud_detection_system_tpu.ops.hashing import hash_u32
from real_time_fraud_detection_system_tpu.utils.trace import step_scope

# np scalar, NOT jnp: a module-level jnp constant would run a JAX
# computation at import time, which breaks jax.distributed.initialize
# in multiprocess workers (same idiom as ops/hashing._M1/_M2)
EMPTY_KEY = np.uint32(0xFFFFFFFF)


class KeyDirectory(NamedTuple):
    """Pytree: the directory + the free-slot stack (all HBM-resident).

    Invariant: an entry is either vacant (``keys[e] == EMPTY_KEY`` and
    ``slots[e] == -1``) or owns exactly one live slot; every slot id is
    either owned by exactly one entry or sits on the free stack
    (``free[:free_top]``)."""

    keys: jnp.ndarray  # uint32 [dir_cap]; EMPTY_KEY = vacant
    slots: jnp.ndarray  # int32 [dir_cap]; slot owned by the entry, -1 vacant
    free: jnp.ndarray  # int32 [slot_cap]; free[:free_top] = free slot ids
    free_top: jnp.ndarray  # int32 [] — live height of the free stack
    # key_bits=64 only (None keeps the 32-bit pytree what it always was):
    # the entry's key, word by word — ``keys`` then holds its fingerprint
    keys_lo: Optional[jnp.ndarray] = None  # uint32 [dir_cap]
    keys_hi: Optional[jnp.ndarray] = None  # uint32 [dir_cap]

    @property
    def wide(self) -> bool:
        return self.keys_lo is not None

    @property
    def dir_capacity(self) -> int:
        return int(self.keys.shape[0])

    @property
    def slot_capacity(self) -> int:
        return int(self.free.shape[0])


def init_keydir(dir_capacity: int, slot_capacity: int,
                key_bits: int = 32) -> KeyDirectory:
    assert dir_capacity & (dir_capacity - 1) == 0, \
        "dir_capacity must be a power of 2"
    assert slot_capacity <= dir_capacity, \
        "more slots than directory entries can never all be reachable"

    def vacant():
        return jnp.full((dir_capacity,), EMPTY_KEY, dtype=jnp.uint32)

    return KeyDirectory(
        keys=vacant(),
        slots=jnp.full((dir_capacity,), -1, dtype=jnp.int32),
        # low slot ids pop first (free[top-1] is the next grant)
        free=jnp.arange(slot_capacity - 1, -1, -1, dtype=jnp.int32),
        free_top=jnp.int32(slot_capacity),
        keys_lo=vacant() if key_bits == 64 else None,
        keys_hi=vacant() if key_bits == 64 else None,
    )


def _canon(key: jnp.ndarray) -> jnp.ndarray:
    key = key.astype(jnp.uint32)
    return jnp.where(key == EMPTY_KEY, jnp.uint32(0xFFFFFFFE), key)


def _probe_position(key: jnp.ndarray, j, dir_cap: int) -> jnp.ndarray:
    """Position of probe ``j`` (broadcast against ``key``): double hashing
    over a power-of-two table, an odd stride walks the whole of it."""
    h1 = hash_u32(key, seed=0)
    h2 = hash_u32(key, seed=1) | jnp.uint32(1)
    return ((h1 + j * h2) & jnp.uint32(dir_cap - 1)).astype(jnp.int32)


def _probe_positions(key: jnp.ndarray, dir_cap: int,
                     n_probes: int) -> jnp.ndarray:
    """[B] keys → [B, P] probe positions."""
    return _probe_position(
        key[:, None], jnp.arange(n_probes, dtype=jnp.uint32)[None, :],
        dir_cap)


def fingerprint(key: jnp.ndarray) -> jnp.ndarray:
    """Wide key ``[2, B]`` → its uint32 ``[B]`` fingerprint: the xor-fold
    of the two words (``core/batch.fold_key``), never ``EMPTY_KEY``."""
    return _canon(key[0] ^ key[1])


def reserved(key: jnp.ndarray) -> jnp.ndarray:
    """bool [B]: wide keys that carry the one reserved pattern."""
    return (key[0] == EMPTY_KEY) & (key[1] == EMPTY_KEY)


def _find_wide(kd: KeyDirectory, key: jnp.ndarray, fp: jnp.ndarray,
               valid: jnp.ndarray, n_probes: int):
    """The wide lookup → ``(entry [B], hit [B], alias [2])``: the entry
    that holds each valid row's key, both words verified, and ``[rows
    that met another key under their fingerprint, trips the loop ran]``.

    One ``[B, P]`` gather of fingerprints, as at 32 bits; then a loop
    that, while a row has an unverified match, reads both words at each
    such row's FIRST match — its entry if they are its own, a position to
    strike if they are another key's. A batch without aliases runs one
    trip; one in which nothing matched, none."""
    dir_cap = kd.dir_capacity
    lo, hi = key[0], key[1]
    pos = _probe_positions(fp, dir_cap, n_probes)  # [B, P]
    cand = (kd.keys[pos] == fp[:, None]) & valid[:, None]  # [B, P]
    probe = jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1)

    def check(carry):
        trips, cand, entry, hit, aliased = carry
        pidx = jnp.argmax(cand, axis=1).astype(jnp.int32)
        first = probe == pidx[:, None]
        e = jnp.sum(jax.lax.select(first, pos, jnp.zeros_like(pos)),
                    axis=1)
        has = cand.any(axis=1) & ~hit
        same = (kd.keys_lo[e] == lo) & (kd.keys_hi[e] == hi)
        ok = has & same
        other = has & ~same
        return (trips + 1, cand & ~(first & other[:, None]),
                jax.lax.select(ok, e, entry), hit | ok, aliased | other)

    def unverified(carry):
        _, cand, _, hit, _ = carry
        return (cand.any(axis=1) & ~hit).any()

    zeros = jnp.zeros(valid.shape, bool)
    trips, _, entry, hit, aliased = jax.lax.while_loop(
        unverified, check,
        (jnp.int32(0), cand, jnp.zeros(valid.shape, jnp.int32), zeros,
         zeros))
    alias = jnp.stack([jnp.sum(aliased.astype(jnp.int32)), trips])
    return entry, hit, alias


def lookup_slots(
    kd: KeyDirectory,
    key: jnp.ndarray,  # uint32 [B] ([2, B] for a wide directory)
    valid: jnp.ndarray,  # bool [B]
    n_probes: int = 8,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Read-only probe: (slot [B] int32, hit [B] bool). Missing/invalid
    rows return slot 0 with ``hit=False`` — mask before scattering."""
    if kd.wide:
        entry, found, _ = _find_wide(
            kd, key, fingerprint(key), valid & ~reserved(key), n_probes)
        slot = kd.slots[entry]
        hit = found & (slot >= 0)
        return jnp.where(hit, slot, 0), hit
    key = _canon(key)
    pos = _probe_positions(key, kd.dir_capacity, n_probes)  # [B, P]
    found = kd.keys[pos] == key[:, None]  # [B, P]
    pidx = jnp.argmax(found, axis=1)
    entry = jnp.take_along_axis(pos, pidx[:, None], axis=1)[:, 0]
    slot = kd.slots[entry]
    hit = valid & found.any(axis=1) & (slot >= 0)
    return jnp.where(hit, slot, 0), hit


def init_stacked_keydir(dir_capacity: int, slot_capacity: int,
                        n_shards: int) -> KeyDirectory:
    """``n_shards`` independent per-shard directories as ONE pytree with
    a leading shard axis on every leaf (``keys``/``slots``
    [n, dir_cap], ``free`` [n, slot_cap], ``free_top`` [n]) — the
    layout the sharded engine places over the mesh (one directory per
    device, sharded on axis 0). Inside ``shard_map`` each device
    squeezes the axis off and runs the plain single-shard ops. One-word
    keys only: the mesh refuses ``key_bits=64`` at construction."""
    kd = init_keydir(dir_capacity, slot_capacity)
    return KeyDirectory(
        keys=jnp.broadcast_to(kd.keys[None], (n_shards,) + kd.keys.shape),
        slots=jnp.broadcast_to(kd.slots[None],
                               (n_shards,) + kd.slots.shape),
        free=jnp.broadcast_to(kd.free[None], (n_shards,) + kd.free.shape),
        free_top=jnp.full((n_shards,), slot_capacity, dtype=jnp.int32),
    )


def lookup_slots_stacked(
    kd: KeyDirectory,  # STACKED layout: [n_shards, ...] leaves
    owner: jnp.ndarray,  # int32 [B] — shard that owns each row's key
    key: jnp.ndarray,  # uint32 [B]
    valid: jnp.ndarray,  # bool [B]
    n_probes: int = 8,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Read-only probe into a stacked directory: row i probes shard
    ``owner[i]``'s table. Returns (local_slot [B] int32, hit [B] bool) —
    the slot is LOCAL to the owner shard (callers compose the global
    table row as ``owner * slot_capacity + slot``). Off the hot path
    (feedback); GSPMD inserts the cross-shard gathers."""
    key = _canon(key)
    dir_cap = int(kd.keys.shape[1])
    pos = _probe_positions(key, dir_cap, n_probes)  # [B, P]
    found = kd.keys[owner[:, None], pos] == key[:, None]  # [B, P]
    pidx = jnp.argmax(found, axis=1)
    entry = jnp.take_along_axis(pos, pidx[:, None], axis=1)[:, 0]
    slot = kd.slots[owner, entry]
    hit = valid & found.any(axis=1) & (slot >= 0)
    return jnp.where(hit, slot, 0), hit


def admit_slots(
    kd: KeyDirectory,
    key: jnp.ndarray,  # uint32 [B] ([2, B] for a wide directory)
    valid: jnp.ndarray,  # bool [B]
    n_probes: int = 8,
) -> Tuple[KeyDirectory, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           Optional[jnp.ndarray]]:
    """Lookup-or-insert a batch of keys; the hot path's admission op.

    Returns ``(kd', slot [B] int32, admitted [B] bool, rounds [2] int32,
    alias)``.
    A row is admitted iff its key already owned a slot or could claim a
    directory entry within ``n_probes`` probes AND a free slot remained;
    batch duplicates of one key share a single slot. Non-admitted rows
    return slot 0 and MUST be masked out of dense-tier scatters (the
    caller serves them from the sketch tier). ``rounds`` is ``[claim
    rounds run, those of them run narrow]`` (:func:`_claim_rounds`), each
    0..``n_probes``: none when the lookup found every key
    (``rtfds_keydir_claim_rounds_total`` and
    ``rtfds_keydir_narrow_rounds_total`` count them). A wide directory
    (``key`` is ``[2, B]``) goes through :func:`admit_wide`, whose
    ``alias`` counts are the fifth value; a one-word directory has none
    to count and returns ``None`` there.
    """
    if kd.wide:
        return admit_wide(kd, key, valid, n_probes)
    with step_scope("keydir"):
        dir_cap = kd.dir_capacity
        slot_cap = kd.slot_capacity
        B = int(key.shape[0])
        keys = kd.keys
        with step_scope("lookup"):
            key = _canon(key)
            pos = _probe_positions(key, dir_cap, n_probes)  # [B, P]
            # FULL-depth lookup FIRST, claims only for keys with no
            # existing entry: reclaim_entries can vacate a position on a
            # live key's probe-path PREFIX, and a claim-as-you-probe insert
            # would grab that vacancy before ever reaching the key's real
            # entry — duplicating the key, resetting its window history,
            # and leaking its old slot. (lookup_slots scans all P positions
            # for the same reason; this is the insert-side half of the
            # no-tombstones argument.)
            found = keys[pos] == key[:, None]  # [B, P]
            pidx = jnp.argmax(found, axis=1)
            hit0 = found.any(axis=1) & valid
            entry = jnp.where(
                hit0,
                jnp.take_along_axis(pos, pidx[:, None], axis=1)[:, 0], 0)
        with step_scope("claim"):
            # claimed: matched via a claim made NOW
            keys, entry, placed, claimed, rounds = _claim_rounds(
                keys, key, entry, ~valid | hit0, n_probes, match=True)
        with step_scope("grant"):
            # One owner per newly claimed entry (batch duplicates of one
            # new key all carry claimed=True on the same entry; exactly one
            # pops a slot).
            rows = jnp.arange(B, dtype=jnp.int32)
            owner = jnp.full((dir_cap,), B, jnp.int32).at[
                jnp.where(claimed, entry, dir_cap)].min(rows, mode="drop")
            new = claimed & (owner[entry] == rows)
            # Grant free slots to owners in row order; owners past the
            # stack height roll their claim back (their duplicates then
            # miss too).
            rank = jnp.cumsum(new.astype(jnp.int32)) - 1  # [B]
            avail = kd.free_top
            has = new & (rank < avail)
            slot_new = kd.free[jnp.clip(avail - 1 - rank, 0, slot_cap - 1)]
            slots = kd.slots.at[jnp.where(has, entry, dir_cap)].set(
                slot_new, mode="drop")
            revert = new & ~(rank < avail)
            keys = keys.at[jnp.where(revert, entry, dir_cap)].set(
                EMPTY_KEY, mode="drop")
            free_top = avail - jnp.sum(has.astype(jnp.int32))
            # Final resolution covers every case at once: hits, fresh
            # grants, batch duplicates of grants, rolled-back claims
            # (keys[entry] no longer matches), and rows that never placed
            # (probe overflow).
            slot = slots[entry]
            admitted = placed & valid & (keys[entry] == key) & (slot >= 0)
            slot = jnp.where(admitted, slot, 0)
        return (
            kd._replace(keys=keys, slots=slots, free_top=free_top),
            slot,
            admitted,
            rounds,
            None,
        )


@contextlib.contextmanager
def _part(name: str):
    """``rtfds.keydir/rtfds.<name>``: one part of the wide admission."""
    with step_scope("keydir"), step_scope(name):
        yield


def admit_wide(
    kd: KeyDirectory,  # wide: keys_lo / keys_hi present
    key: jnp.ndarray,  # uint32 [2, B]
    valid: jnp.ndarray,  # bool [B]
    n_probes: int = 8,
) -> Tuple[KeyDirectory, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """:func:`admit_slots` for 64-bit keys → ``(kd', slot, admitted,
    rounds [2], alias [2] int32)``: two ids are one key only if both words
    agree. ``alias`` is the lookup's ``[rows that met another key under
    their fingerprint, verify trips]``
    (``rtfds_keydir_alias_rows_total`` / ``…_trips_total``).

    The rounds elect by fingerprint — the parent's scatter-min, its
    cost a round unchanged — and what a fingerprint cannot tell is
    settled at the grant: the owner of a claimed entry writes its two
    words there, every claimant reads them back, and a claimant that
    reads another key (a second new id of the same fingerprint won the
    same position in the same round) is unplaced again. Rounds and grant
    are one PASS of a loop that runs while such a row is left, at most
    ``n_probes`` passes: one for nearly every batch. Ids of one
    fingerprint share a probe path, so at most ``n_probes`` of them are
    resident at a time; the others miss admission like any key whose
    positions are taken.

    No in-round match is looked for (the 32-bit rounds' ``hit``): every
    row of one key is unplaced or placed together — the lookup found the
    key or it did not, duplicates win or lose a position together — so a
    key is never written while a row of it is still probing."""
    dir_cap = kd.dir_capacity
    slot_cap = kd.slot_capacity
    B = int(valid.shape[0])
    with _part("lookup"):
        lo, hi = key[0], key[1]
        valid = valid & ~reserved(key)
        fp = fingerprint(key)
        entry, hit0, alias = _find_wide(kd, key, fp, valid, n_probes)
        unplaced = valid & ~hit0

    def one_pass(carry):
        (n, rounds, keys, klo, khi, slots, free_top, entry, todo,
         got) = carry
        with _part("claim"):
            keys, entry, _, claimed, ran = _claim_rounds(
                keys, fp, entry, ~todo, n_probes, match=False)
        with _part("grant"):
            rows = jnp.arange(B, dtype=jnp.int32)
            drop = jnp.full_like(entry, dir_cap)
            owner = jnp.full((dir_cap,), B, jnp.int32).at[
                jax.lax.select(claimed, entry, drop)].min(
                    rows, mode="drop")
            new = claimed & (owner[entry] == rows)
            # the owner names the entry; its batch duplicates read
            # their own key back, a fingerprint twin reads another's
            mine_at = jax.lax.select(new, entry, drop)
            klo = klo.at[mine_at].set(lo, mode="drop")
            khi = khi.at[mine_at].set(hi, mode="drop")
            mine = claimed & (klo[entry] == lo) & (khi[entry] == hi)
            rank = jax.lax.cumsum(new.astype(jnp.int32)) - 1
            has = new & (rank < free_top)
            slot_new = kd.free[
                jnp.clip(free_top - 1 - rank, 0, slot_cap - 1)]
            slots = slots.at[jax.lax.select(has, entry, drop)].set(
                slot_new, mode="drop")
            revert = new & ~has
            keys = keys.at[jax.lax.select(revert, entry, drop)].set(
                EMPTY_KEY, mode="drop")
            free_top = free_top - jnp.sum(has.astype(jnp.int32))
            return (n + 1, rounds + ran, keys, klo, khi, slots, free_top,
                    entry, claimed & ~mine, got | mine)

    def a_twin_is_unplaced(carry):
        n, todo = carry[0], carry[8]
        with _part("claim"):
            return (n < n_probes) & todo.any()

    # The first pass is every batch's; further passes are a loop that runs
    # while a fingerprint twin is unplaced (~0.1 of a pass a batch at
    # 26,000 new keys). The loop stands BESIDE rtfds.keydir and its
    # condition and each part of its body open keydir/<part> anew: a later
    # pass's rounds book under rtfds.keydir/rtfds.claim and its grant
    # under rtfds.keydir/rtfds.grant, as the first pass's do (a scope
    # opened around the loop would put "while/body" between the two names,
    # and a reader of "rtfds.keydir/rtfds.grant" would miss the grant).
    (_, rounds, keys, klo, khi, slots, free_top, entry, _,
     got) = jax.lax.while_loop(
        a_twin_is_unplaced, one_pass,
        one_pass((jnp.int32(0), jnp.zeros(2, jnp.int32), kd.keys,
                  kd.keys_lo, kd.keys_hi, kd.slots, kd.free_top, entry,
                  unplaced, hit0)))
    with _part("grant"):
        # An entry a row got holds that row's key for good, or was
        # rolled back (the free stack ran dry: its fingerprint is
        # gone, and whoever claims it next finds no slot either) —
        # so the fingerprint and the slot decide, as at 32 bits.
        slot = slots[entry]
        admitted = got & (keys[entry] == fp) & (slot >= 0)
        slot = jnp.where(admitted, slot, 0)
    return (
        kd._replace(keys=keys, slots=slots, free_top=free_top,
                    keys_lo=klo, keys_hi=khi),
        slot,
        admitted,
        rounds,
        alias,
    )


# K: the lanes one trip of a lane-packed pass works on (reclaim_entries'
# vacate, the demote pass's packing and payload gather in
# features/online.py). A trip's cost follows K, not what it packs, so K is
# one static width chosen on the chip (PERF.md, PR 38) and no option.
PACK_LANES = 16384


def pack_lanes(n: int) -> int:
    """K for an ``n``-lane input: :data:`PACK_LANES`, or half of a
    smaller input — never the whole of it (``ops/cms.chunk_rows``' rule:
    a loop whose one chunk is the input reads nothing that depends on the
    trip, and the compiler hoists the read out for every pass to pay)."""
    return max(1, min(PACK_LANES, n // 2))


def packed_entries(running: jnp.ndarray, first, lanes: int) -> jnp.ndarray:
    """Lane-packing by rank: ``running`` is the cumulative count of a
    ``[dir_cap]`` selection, lane ``j`` of the ``lanes`` returned holds
    the index of the selected entry of rank ``first + j`` (in entry
    order), or ``dir_cap`` past the last one. ``lanes`` binary searches
    in the running count (the first index whose count reaches the
    lane's rank + 1: ``jnp.searchsorted``'s ``side="left"``, written in
    ``lax`` so every op carries the caller's scope) — not a scatter of
    every directory entry."""
    (n,) = running.shape
    want = first + jnp.arange(1, lanes + 1, dtype=jnp.int32)

    def halve(_, bounds):
        lo, hi = bounds  # the answer lies in [lo, hi]
        mid = (lo + hi) >> 1
        right = (lo < hi) & (running[jnp.minimum(mid, n - 1)] < want)
        return (jax.lax.select(right, mid + 1, lo),
                jax.lax.select(right | (lo >= hi), hi, mid))

    lo, _ = jax.lax.fori_loop(
        0, n.bit_length(), halve,
        (jnp.zeros((lanes,), jnp.int32), jnp.full((lanes,), n, jnp.int32)))
    return lo


# The lanes the claim rounds narrow to once no more rows than that are
# unplaced. A round's cost follows its rows (a hash, a gather, a
# scatter-min, a gather) and what is unplaced thins by the directory's
# load a round, so all but the first round or two of a batch place a few
# thousand rows and fewer. One static width chosen on the chip (PERF.md,
# PR 51: a round over the batch's 65,536 rows is 1.6 ms, over 2,048 lanes
# 0.13; the pack's binary search 0.3 — at 8,192 lanes 0.49 and 1.1, at
# 512 lanes every batch with more new keys runs another wide round), and
# no option.
CLAIM_LANES = 2048


def claim_lanes(n: int) -> int:
    """The claim rounds' narrow width for an ``n``-row batch:
    :data:`CLAIM_LANES`, or by :func:`pack_lanes`' rule half of a
    smaller batch."""
    return max(1, min(CLAIM_LANES, n // 2))


def _claim_rounds(keys, key, entry, placed, n_probes: int, match: bool,
                  lanes: Optional[int] = None):
    """The claim rounds of one admit → ``(keys', entry', placed',
    claimed, rounds [2])``: round j's unplaced rows scatter-min their
    ``key`` (a wide directory's fingerprint) into probe position j where
    it is vacant, re-read, and the losers go on to j + 1. ``claimed``
    flags the rows placed by a claim made now, ``rounds`` is ``[rounds
    run, those of them run narrow]``. ``match``: a row also takes a
    position that holds its key already — a batch duplicate of a key
    claimed in an EARLIER round, which the lookup could not see
    (:func:`admit_wide` says why its rows never need that).

    ONE loop body (unrolled, 2 x 16 rounds made each of the five bucket
    programs compile ~16 s on the chip: PERF.md, PR 32), run while a
    round is left and a row is unplaced. With a row placed ``hit`` and
    ``want`` are false and its candidate is EMPTY_KEY, whose scatter-min
    changes nothing: a round is an identity for every placed row and the
    rounds not run are identities, so the answers are the fixed P
    rounds' to the bit (tests/test_keydir.py pins them), a batch of
    known keys runs none, and a key no round can place holds its batch
    to all P.

    And a round may leave the placed rows out. Two loops in a row: the
    body over the batch's ``[B]`` rows while MORE than K =
    :func:`claim_lanes` of them are unplaced, then — the unplaced rows'
    indices packed into K lanes by rank (:func:`packed_entries`), their
    keys gathered once — the same body over the ``[K]`` lanes, ``j``
    carried on, while a lane is unplaced; lanes past the count are
    placed from the start. One K-lane scatter brings what the lanes
    placed back to ``[B]``. Every round still sees every unplaced row
    at once, so nothing is decided differently; a batch with more than
    K rows no round can place runs the wide loop to P and the narrow
    loop no trip. (Not a ``lax.cond`` around the directory, which copies
    it; not a round chunked over time, whose later chunks would read the
    earlier ones' claims.)"""
    (dir_cap,), (B,) = keys.shape, key.shape
    K = claim_lanes(B) if lanes is None else lanes
    assert dir_cap <= 1 << 30  # an entry and a flag share an int32 below

    def rounds_over(key):
        vacant = jnp.full_like(key, EMPTY_KEY)

        def claim_round(carry):
            j, keys, entry, placed, claimed = carry
            p = _probe_position(key, j.astype(jnp.uint32), dir_cap)
            cur = keys[p]
            if match:
                hit = ~placed & (cur == key)
                entry = jax.lax.select(hit, p, entry)
                placed = placed | hit
            # among racing writers the smallest key wins, losers re-probe
            want = ~placed & (cur == EMPTY_KEY)
            keys = keys.at[p].min(jax.lax.select(want, key, vacant))
            won = want & (keys[p] == key)
            return (j + 1, keys, jax.lax.select(won, p, entry),
                    placed | won, claimed | won)

        return claim_round

    def more_than_k_unplaced(carry):
        j, _, _, placed, _ = carry
        return (j < n_probes) & (
            jnp.sum((~placed).astype(jnp.int32)) > K)

    def a_lane_unplaced(carry):
        j, _, _, placed, _ = carry
        return (j < n_probes) & ~placed.all()

    wide, keys, entry, placed, claimed = jax.lax.while_loop(
        more_than_k_unplaced, rounds_over(key),
        (jnp.int32(0), keys, entry, placed, jnp.zeros(B, dtype=bool)))
    row = packed_entries(  # [K]: the unplaced rows in row order, then B
        jax.lax.cumsum((~placed).astype(jnp.int32)), 0, K)
    empty = row >= B
    ran, keys, at, got, mine = jax.lax.while_loop(
        a_lane_unplaced, rounds_over(key[jnp.minimum(row, B - 1)]),
        (wide, keys, jnp.zeros(K, jnp.int32), empty,
         jnp.zeros(K, dtype=bool)))
    # what the lanes placed, back to their rows: entry and flag in one
    code = jnp.full((B,), -1, jnp.int32).at[
        jax.lax.select(got & ~empty, row, jnp.full_like(row, B))].set(
            at * 2 + mine.astype(jnp.int32), mode="drop")
    back = code >= 0
    return (keys, jax.lax.select(back, code >> 1, entry), placed | back,
            claimed | (back & ((code & 1) == 1)),
            jnp.stack([ran, ran - wide]))


def reclaim_entries(
    kd: KeyDirectory,
    dead_entry: jnp.ndarray,  # bool [dir_cap] — entries to vacate
) -> Tuple[KeyDirectory, jnp.ndarray, jnp.ndarray]:
    """Vacate ``dead_entry`` positions and push their slots back on the
    free stack, in entry order. Returns ``(kd', vacated [slot_cap] bool,
    n_reclaimed [])`` — ``vacated`` flags the slots given up, which the
    caller uses to reset their window rows
    (``WindowState.clear_slots``).

    The indexed work is as wide as what is vacated, not as wide as the
    directory: the dead entries are ranked by one cumulative sum and
    packed ``K = pack_lanes(dir_cap)`` lanes a trip of a
    ``lax.while_loop`` of ⌈n ÷ K⌉ trips — a K-lane gather of their
    slots, a K-lane scatter onto the free stack at ``free_top + rank``, a
    K-lane scatter of flags into ``vacated``; nothing dead, no trip. The
    directory itself is vacated by two dense selects."""
    slot_cap, dir_cap = kd.slot_capacity, kd.dir_capacity
    lanes = pack_lanes(dir_cap)
    dead = dead_entry & (kd.slots >= 0)
    running = jnp.cumsum(dead.astype(jnp.int32))
    n = running[-1]
    lane = jnp.arange(lanes, dtype=jnp.int32)

    def vacate(carry):
        trip, free, vacated = carry
        rank = trip * lanes + lane
        entry = packed_entries(running, trip * lanes, lanes)
        slot = kd.slots[jnp.minimum(entry, dir_cap - 1)]
        ok, drop = rank < n, jnp.full_like(rank, slot_cap)
        free = free.at[jax.lax.select(ok, kd.free_top + rank, drop)].set(
            slot, mode="drop")
        vacated = vacated.at[jax.lax.select(ok, slot, drop)].set(
            True, mode="drop")
        return trip + 1, free, vacated

    _, free, vacated = jax.lax.while_loop(
        lambda carry: carry[0] * lanes < n, vacate,
        (jnp.int32(0), kd.free, jnp.zeros((slot_cap,), bool)))
    return (
        kd._replace(
            keys=jnp.where(dead, EMPTY_KEY, kd.keys),
            slots=jnp.where(dead, -1, kd.slots),
            free=free,
            free_top=kd.free_top + n,
        ),
        vacated,
        n,
    )


def occupied_slots(kd: KeyDirectory) -> jnp.ndarray:
    """Live slot count (int32 scalar): slots granted and not reclaimed."""
    return jnp.int32(kd.slot_capacity) - kd.free_top
