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
  key it did not find take part, and a round with no unplaced row is an
  identity — so the loop runs while a round is left and a row is
  unplaced (P at most; none for a batch of known keys; ~log(new keys) ÷
  log(1 ÷ load) while keys arrive) and its answers are the P rounds';
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
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

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

    @property
    def dir_capacity(self) -> int:
        return int(self.keys.shape[0])

    @property
    def slot_capacity(self) -> int:
        return int(self.free.shape[0])


def init_keydir(dir_capacity: int, slot_capacity: int) -> KeyDirectory:
    assert dir_capacity & (dir_capacity - 1) == 0, \
        "dir_capacity must be a power of 2"
    assert slot_capacity <= dir_capacity, \
        "more slots than directory entries can never all be reachable"
    return KeyDirectory(
        keys=jnp.full((dir_capacity,), EMPTY_KEY, dtype=jnp.uint32),
        slots=jnp.full((dir_capacity,), -1, dtype=jnp.int32),
        # low slot ids pop first (free[top-1] is the next grant)
        free=jnp.arange(slot_capacity - 1, -1, -1, dtype=jnp.int32),
        free_top=jnp.int32(slot_capacity),
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


def lookup_slots(
    kd: KeyDirectory,
    key: jnp.ndarray,  # uint32 [B]
    valid: jnp.ndarray,  # bool [B]
    n_probes: int = 8,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Read-only probe: (slot [B] int32, hit [B] bool). Missing/invalid
    rows return slot 0 with ``hit=False`` — mask before scattering."""
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
    squeezes the axis off and runs the plain single-shard ops."""
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
    key: jnp.ndarray,  # uint32 [B]
    valid: jnp.ndarray,  # bool [B]
    n_probes: int = 8,
) -> Tuple[KeyDirectory, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Lookup-or-insert a batch of keys; the hot path's admission op.

    Returns ``(kd', slot [B] int32, admitted [B] bool, rounds [] int32)``.
    A row is admitted iff its key already owned a slot or could claim a
    directory entry within ``n_probes`` probes AND a free slot remained;
    batch duplicates of one key share a single slot. Non-admitted rows
    return slot 0 and MUST be masked out of dense-tier scatters (the
    caller serves them from the sketch tier). ``rounds`` is how many claim
    rounds ran, 0..``n_probes``: none when the lookup found every key
    (``rtfds_keydir_claim_rounds_total`` counts them).
    """
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
            # The rounds as ONE loop body (unrolled, 2 x 16 rounds of a
            # scatter and two gathers made each of the five bucket programs
            # compile ~16 s on the chip: PERF.md, PR 32), run WHILE a round
            # is left and a row is still unplaced. With every row placed
            # ``hit`` and ``want`` are all false and ``cand`` is all
            # EMPTY_KEY, whose scatter-min changes nothing: the rounds not
            # run are identities, so the answers are the fixed P rounds' to
            # the bit (tests/test_keydir.py pins them) and a batch of known
            # keys runs none. A key no round can place holds its batch to
            # all P.
            def claim_round(carry):
                j, keys, entry, placed, claimed = carry
                p = _probe_position(  # pos[:, j]
                    key, j.astype(jnp.uint32), dir_cap)
                cur = keys[p]
                # batch duplicates of a key claimed in an EARLIER round
                # match here (pre-call lookup could not see that claim)
                hit = (~placed) & (cur == key)
                entry = jnp.where(hit, p, entry)
                placed = placed | hit
                # Claim attempt: scatter-min our key into still-empty
                # positions; among racing writers the smallest key wins,
                # losers re-probe.
                want = (~placed) & (cur == EMPTY_KEY)
                cand = jnp.where(want, key, EMPTY_KEY)
                keys = keys.at[p].min(cand)
                won = want & (keys[p] == key)
                entry = jnp.where(won, p, entry)
                return j + 1, keys, entry, placed | won, claimed | won

            def unplaced_with_a_round_left(carry):
                j, _, _, placed, _ = carry
                return (j < n_probes) & ~placed.all()

            # claimed: matched via a claim made NOW
            rounds, keys, entry, placed, claimed = jax.lax.while_loop(
                unplaced_with_a_round_left, claim_round,
                (jnp.int32(0), keys, entry, ~valid | hit0,
                 jnp.zeros(B, dtype=bool)))
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
            KeyDirectory(keys=keys, slots=slots, free=kd.free,
                         free_top=free_top),
            slot,
            admitted,
            rounds,
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
        KeyDirectory(
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
