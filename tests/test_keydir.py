"""Exact on-device key directory (ops/keydir.py): batched insert race
resolution, duplicate coalescing, free-list-bounded admission, read-only
lookup, and reclaim — the primitives the tiered feature store
(key_mode="exact") is built from."""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.ops import keydir
from real_time_fraud_detection_system_tpu.core.batch import (
    join_key,
    split_key,
)
from real_time_fraud_detection_system_tpu.ops.keydir import (
    EMPTY_KEY,
    KeyDirectory,
    _probe_positions,
    admit_slots,
    init_keydir,
    lookup_slots,
    occupied_slots,
    reclaim_entries,
)


def wide_id(k):
    """Test key k as a 64-bit id: keys 2j and 2j + 1 are FOLD TWINS —
    different words, the same xor of them — so every case that runs at
    width 64 runs on pairs a 32-bit deployment would merge."""
    j, b = divmod(int(k), 2)
    hi = 0x10 + b * 0x3039
    fold = (j * 2654435761 + 1) & 0xFFFFFFFF
    return (hi << 32) | (fold ^ hi)


def _keys(keys, width=32):
    """The device's key column for test keys: uint32 ``[n]`` as they
    stand, or their :func:`wide_id` s split words-first, ``[2, n]``."""
    if width == 32:
        return jnp.asarray(np.asarray(keys, np.uint32))
    ids = np.asarray([wide_id(k) for k in np.asarray(keys).tolist()],
                     np.uint64)
    return jnp.asarray(split_key(ids.view(np.int64)))


def _stored(kd):
    """The keys of the occupied entries, as the directory holds them."""
    live = np.asarray(kd.slots) >= 0
    if kd.wide:
        return join_key(np.stack([np.asarray(kd.keys_lo)[live],
                                  np.asarray(kd.keys_hi)[live]]))
    return np.asarray(kd.keys)[live]


@pytest.fixture(params=[32, 64])
def width(request):
    return request.param


def _admit(kd, keys, valid=None):
    k = _keys(keys, 64 if kd.wide else 32)
    v = (jnp.ones(k.shape[-1], bool) if valid is None
         else jnp.asarray(valid))
    return admit_slots(kd, k, v)[:3]


def test_admit_assigns_unique_slots_and_coalesces_duplicates(width):
    kd = init_keydir(64, 16, width)
    kd, slot, adm = _admit(kd, [5, 5, 7, 9, 5, 11])
    slot, adm = np.asarray(slot), np.asarray(adm)
    assert adm.all()
    # batch duplicates of one key share ONE slot (and one grant)
    assert slot[0] == slot[1] == slot[4]
    assert len({slot[0], slot[2], slot[3], slot[5]}) == 4
    assert int(occupied_slots(kd)) == 4


def test_admit_is_stable_across_batches(width):
    kd = init_keydir(64, 16, width)
    kd, s1, _ = _admit(kd, [100, 200, 300])
    kd, s2, adm = _admit(kd, [300, 100, 200])
    np.testing.assert_array_equal(
        np.asarray(s2), np.asarray(s1)[[2, 0, 1]])
    assert np.asarray(adm).all()
    assert int(occupied_slots(kd)) == 3  # no double-allocation


def test_admission_bounded_by_free_list_then_recovers(width):
    kd = init_keydir(64, 8, width)
    kd, _, adm = _admit(kd, np.arange(12))
    # exactly slot_capacity keys admitted; the rest overflow gracefully
    assert int(np.asarray(adm).sum()) == 8
    assert int(kd.free_top) == 0
    # a full table still serves existing keys and refuses new ones
    kd, slot, adm2 = _admit(kd, [0, 999])
    adm2 = np.asarray(adm2)
    assert bool(adm2[0]) and not bool(adm2[1])
    # reclaim everything → the same 12 keys now all admit again
    kd, _, n = reclaim_entries(kd, jnp.ones(64, bool))
    assert int(n) == 8 and int(kd.free_top) == 8
    kd, _, adm3 = _admit(kd, np.arange(8))
    assert np.asarray(adm3).all()


def test_invalid_rows_never_place(width):
    kd = init_keydir(64, 16, width)
    kd, slot, adm = _admit(kd, [1, 2, 3], valid=[True, False, True])
    assert not bool(np.asarray(adm)[1])
    assert int(occupied_slots(kd)) == 2
    _, hit = lookup_slots(kd, _keys([2], width), jnp.ones(1, bool))
    assert not bool(hit[0])


def test_lookup_is_read_only_and_exact(width):
    kd = init_keydir(64, 16, width)
    kd, slot, _ = _admit(kd, [42, 43])  # fold twins at width 64
    got, hit = lookup_slots(kd, _keys([43, 42, 44], width),
                            jnp.ones(3, bool))
    hit = np.asarray(hit)
    assert bool(hit[0]) and bool(hit[1]) and not bool(hit[2])
    np.testing.assert_array_equal(np.asarray(got)[:2],
                                  np.asarray(slot)[[1, 0]])
    # lookup never allocates
    assert int(occupied_slots(kd)) == 2


def test_reclaim_frees_entries_and_slots_consistently(width):
    kd = init_keydir(64, 16, width)
    kd, slot, _ = _admit(kd, [1, 2, 3, 4])
    # vacate exactly key 2's entry
    target = int(np.asarray(slot)[1])
    dead_entry = np.asarray(kd.slots) == target
    kd, dead, n = reclaim_entries(kd, jnp.asarray(dead_entry))
    assert int(n) == 1 and int(occupied_slots(kd)) == 3
    _, hit = lookup_slots(kd, _keys([2], width), jnp.ones(1, bool))
    assert not bool(hit[0])
    # the other keys are untouched (3 is 2's fold twin at width 64)
    got, hit = lookup_slots(kd, _keys([1, 3, 4], width),
                            jnp.ones(3, bool))
    assert np.asarray(hit).all()
    # the freed slot is re-grantable
    kd, s5, adm = _admit(kd, [50])
    assert bool(np.asarray(adm)[0])


def test_readmission_survives_probe_prefix_vacancy(width):
    """Review-pass regression: reclaiming an entry that sits on a LIVE
    key's probe-path prefix must not make re-admission duplicate the key
    (claim the vacancy, pop a fresh slot, reset its history). The insert
    path must look up the FULL probe depth before claiming anything."""
    kd = init_keydir(64, 32, width)
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 10_000, 24).astype(np.uint32)
    if width == 64:  # half the keys come with their fold twin
        keys[12:] = keys[:12] ^ 1
    kd, slot0, adm0 = _admit(kd, keys)
    assert np.asarray(adm0).all()
    occ0 = int(occupied_slots(kd))
    slots_by_key = dict(zip(keys.tolist(), np.asarray(slot0).tolist()))
    # vacate HALF the entries (whichever they are, some sit on the
    # survivors' probe prefixes in a 64-entry directory)
    live_entries = np.flatnonzero(np.asarray(kd.slots) >= 0)
    dead = np.zeros(64, bool)
    dead[live_entries[::2]] = True
    kd, dead_mask, n = reclaim_entries(kd, jnp.asarray(dead))
    reclaimed_slots = set(
        np.asarray(slot0)[np.isin(np.asarray(slot0),
                                  np.asarray(kd.free)[
                                      :int(kd.free_top)])].tolist())
    # re-admit EVERY original key: survivors must keep their exact slot
    kd, slot1, adm1 = _admit(kd, keys)
    assert np.asarray(adm1).all()
    for k, s1 in zip(keys.tolist(), np.asarray(slot1).tolist()):
        if slots_by_key[k] not in reclaimed_slots:
            assert s1 == slots_by_key[k], \
                f"live key {k} moved {slots_by_key[k]} -> {s1}"
    # every key owns exactly ONE directory entry (no duplicates)
    stored = _stored(kd)
    assert len(stored) == len(np.unique(stored))
    assert int(occupied_slots(kd)) == occ0


def test_sentinel_key_is_remapped_not_lost():
    kd = init_keydir(64, 16)
    kd, _, adm = _admit(kd, [0xFFFFFFFF])
    assert bool(np.asarray(adm)[0])
    _, hit = lookup_slots(kd, jnp.asarray(np.array([0xFFFFFFFF],
                                                   np.uint32)),
                          jnp.ones(1, bool))
    assert bool(hit[0])
    # the directory never stores the sentinel itself
    assert not np.any(np.asarray(kd.keys)[np.asarray(kd.slots) >= 0]
                      == np.uint32(0xFFFFFFFF))


def test_admit_under_jit_matches_eager(width):
    kd_e = init_keydir(128, 32, width)
    kd_j = init_keydir(128, 32, width)
    rng = np.random.default_rng(3)
    jitted = jax.jit(admit_slots, static_argnames="n_probes")
    for _ in range(4):
        keys = rng.integers(0, 200, 64).astype(np.uint32)
        kd_e, s_e, a_e = _admit(kd_e, keys)
        kd_j, s_j, a_j, *_ = jitted(kd_j, _keys(keys, width),
                                    jnp.ones(64, bool))
        np.testing.assert_array_equal(np.asarray(s_e), np.asarray(s_j))
        np.testing.assert_array_equal(np.asarray(a_e), np.asarray(a_j))
    for a, b in zip(jax.tree.leaves(kd_e), jax.tree.leaves(kd_j)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n_keys,slot_cap", [(500, 512), (2000, 256)])
def test_admission_exactness_property(n_keys, slot_cap, width):
    """Random stream: every admitted key maps to a UNIQUE slot; the
    mapping is a function (same key → same slot, always); occupancy
    equals the number of distinct admitted keys."""
    kd = init_keydir(2 * 1024, slot_cap, width)
    rng = np.random.default_rng(7)
    seen = {}
    for _ in range(12):
        keys = rng.integers(0, n_keys, 256).astype(np.uint32)
        kd, slot, adm = _admit(kd, keys)
        slot, adm = np.asarray(slot), np.asarray(adm)
        for k, s, a in zip(keys.tolist(), slot.tolist(), adm.tolist()):
            if not a:
                continue
            if k in seen:
                assert seen[k] == s, "key moved slots without reclaim"
            seen[k] = s
    slots = list(seen.values())
    assert len(set(slots)) == len(slots) <= slot_cap
    assert int(occupied_slots(kd)) == len(seen)
    # a key never gets two entries, and the directory names it whole
    stored = _stored(kd)
    assert len(np.unique(stored)) == len(stored) == len(seen)
    if width == 64:
        assert set(stored.tolist()) == {wide_id(k) for k in seen}


@pytest.mark.parametrize("n_probes", [2, 4, 8])
@pytest.mark.parametrize("load", [0.25, 0.375])
def test_keys_that_miss_admission_follow_load_and_probe_depth(load,
                                                              n_probes):
    """The rule README's "Feature-state playbook" states: a key misses
    admission FOR GOOD when all P of its probe positions are taken, so
    filling a directory of D entries to load alpha (alpha·D distinct
    keys, each admitted at the load it finds) loses
    ``D · alpha^(P+1) / (P+1)`` keys in expectation: 137 of 3.1 M at
    alpha 0.375 and P = 8 in a 2^23-entry directory, 3e-5 at alpha 0.25
    and P = 16 (``benchmark/configs/forest-rf100-d8-exact.json``). Held
    here on a small directory, within sampling error."""
    dir_cap, batch = 1 << 16, 1024
    n_keys = int(load * dir_cap)
    rng = np.random.default_rng([dir_cap, n_probes, int(load * 1000)])
    keys = rng.choice(1 << 31, size=n_keys, replace=False).astype(np.uint32)
    admit = jax.jit(admit_slots, static_argnames="n_probes")
    kd = init_keydir(dir_cap, dir_cap // 2)  # the free stack never runs dry
    missed = 0
    for i in range(0, n_keys, batch):
        kd, _, adm, *_ = admit(kd, jnp.asarray(keys[i:i + batch]),
                               jnp.ones(batch, bool), n_probes=n_probes)
        missed += batch - int(np.asarray(adm).sum())
    assert int(occupied_slots(kd)) == n_keys - missed
    expected = dir_cap * load ** (n_probes + 1) / (n_probes + 1)
    # the count is a sum of rare independent events: Poisson-wide
    assert abs(missed - expected) <= 4.0 * np.sqrt(expected) + 2.0, (
        missed, expected)


def _admit_slots_unrolled(kd, key, valid, n_probes):
    """``admit_slots`` as it was written before PR 32: the P claim rounds
    unrolled in Python over the ``[B, P]`` probe positions. Kept here as
    the pin for the loops the program now runs."""
    return _unrolled(kd, key, valid, n_probes)[:3]


def _unrolled(kd, key, valid, n_probes):
    """→ ``(kd', slot, admitted, left [P] int32)``: the fixed rounds'
    answers, and how many rows were unplaced as each round began."""
    from real_time_fraud_detection_system_tpu.ops.keydir import (
        KeyDirectory,
        _canon,
        _probe_positions,
    )

    dir_cap, slot_cap = kd.dir_capacity, kd.slot_capacity
    key = _canon(key)
    B = int(key.shape[0])
    pos = _probe_positions(key, dir_cap, n_probes)
    keys = kd.keys
    found = keys[pos] == key[:, None]
    pidx = jnp.argmax(found, axis=1)
    hit0 = found.any(axis=1) & valid
    entry = jnp.where(
        hit0, jnp.take_along_axis(pos, pidx[:, None], axis=1)[:, 0], 0)
    placed = ~valid | hit0
    claimed = jnp.zeros(B, dtype=bool)
    left = []
    for j in range(n_probes):
        left.append(jnp.sum(~placed))
        p = pos[:, j]
        cur = keys[p]
        hit = (~placed) & (cur == key)
        entry = jnp.where(hit, p, entry)
        placed = placed | hit
        want = (~placed) & (cur == EMPTY_KEY)
        keys = keys.at[p].min(jnp.where(want, key, EMPTY_KEY))
        won = want & (keys[p] == key)
        entry = jnp.where(won, p, entry)
        claimed = claimed | won
        placed = placed | won
    rows = jnp.arange(B, dtype=jnp.int32)
    owner = jnp.full((dir_cap,), B, jnp.int32).at[
        jnp.where(claimed, entry, dir_cap)].min(rows, mode="drop")
    new = claimed & (owner[entry] == rows)
    rank = jnp.cumsum(new.astype(jnp.int32)) - 1
    avail = kd.free_top
    has = new & (rank < avail)
    slot_new = kd.free[jnp.clip(avail - 1 - rank, 0, slot_cap - 1)]
    slots = kd.slots.at[jnp.where(has, entry, dir_cap)].set(
        slot_new, mode="drop")
    revert = new & ~(rank < avail)
    keys = keys.at[jnp.where(revert, entry, dir_cap)].set(
        EMPTY_KEY, mode="drop")
    slot = slots[entry]
    admitted = placed & valid & (keys[entry] == key) & (slot >= 0)
    return (KeyDirectory(keys=keys, slots=slots, free=kd.free,
                         free_top=avail - jnp.sum(has.astype(jnp.int32))),
            jnp.where(admitted, slot, 0), admitted, jnp.stack(left))


def _jit_admit(n_probes, lanes=None, rounds=None):
    """``admit_slots`` under jit with its claim rounds narrowing at
    ``lanes`` unplaced rows (None: ``claim_lanes``' own choice for the
    batch), or with ``rounds`` standing in for them. The width is the
    private helper's keyword; a fresh function a call, so no trace made
    under another width answers."""
    stand_in = rounds or functools.partial(keydir._claim_rounds,
                                           lanes=lanes)

    def admit(kd, key, valid):
        with mock.patch.object(keydir, "_claim_rounds", stand_in):
            return admit_slots(kd, key, valid, n_probes=n_probes)

    return jax.jit(admit)


def _rounds_of(left, lanes, n_probes):
    """``[rounds run, those of them run narrow]`` that ``left`` (the
    fixed rounds' unplaced rows as each round began) asks for: rounds
    while a row is unplaced, the wide ones while more than ``lanes``
    are."""
    left = np.asarray(left).tolist()
    ran = next((j for j in range(n_probes) if left[j] == 0), n_probes)
    wide = next((j for j in range(n_probes) if left[j] <= lanes), n_probes)
    return [ran, ran - min(wide, ran)]


# how the widths can fall at a 128-row batch: claim_lanes' own half of a
# small batch; no fewer lanes than rows (nothing runs wide); wide rounds,
# then narrow ones; so few lanes that most batches run wide to the end
LANES_128 = [None, 128, 16, 2]


@pytest.mark.parametrize("lanes", LANES_128)
@pytest.mark.parametrize("n_probes", [1, 3, 16])
def test_claim_rounds_as_a_loop_equal_the_unrolled_rounds_bit_for_bit(
        n_probes, lanes):
    """Racing new keys, batch duplicates of a new key, returning keys,
    invalid rows, reclaimed vacancies on a probe path and a free stack
    that runs dry mid-batch: the directory and the answers of the two
    loops are the unrolled rounds' to the bit, batch after batch,
    wherever the rounds go narrow — and they go narrow where the
    unplaced rows of the fixed rounds say."""
    rng = np.random.default_rng(n_probes)
    loop = _jit_admit(n_probes, lanes)
    plain = jax.jit(_unrolled, static_argnames="n_probes")
    k = keydir.claim_lanes(128) if lanes is None else lanes
    assert k == (64 if lanes is None else lanes)
    kd_a = kd_b = init_keydir(256, 96)
    ran_dry = False
    ran = np.zeros(2, int)
    for step in range(12):
        keys = jnp.asarray(rng.integers(0, 400, 128).astype(np.uint32))
        valid = jnp.asarray(rng.random(128) < 0.9)
        kd_a, slot_a, adm_a, rounds, _ = loop(kd_a, keys, valid)
        kd_b, slot_b, adm_b, left = plain(kd_b, keys, valid,
                                          n_probes=n_probes)
        assert np.asarray(rounds).tolist() == _rounds_of(left, k, n_probes)
        ran += np.asarray(rounds)
        ran_dry = ran_dry or int(kd_a.free_top) == 0
        if step % 4 == 3:  # vacate a third of the live entries
            dead = jnp.asarray(rng.random(256) < 0.33)
            kd_a, kd_b = (reclaim_entries(kd, dead)[0]
                          for kd in (kd_a, kd_b))
        for a, b in zip(jax.tree.leaves((kd_a, slot_a, adm_a)),
                        jax.tree.leaves((kd_b, slot_b, adm_b))):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert ran_dry or n_probes == 1  # one probe loses keys first
    # the case is the one its width names: every round narrow, or both
    # kinds, or (a dry stack's rolled-back claims come again, more than
    # two a batch) nearly every round wide
    wide, narrow = ran[0] - ran[1], ran[1]
    assert (wide == 0) == (k >= 128)
    assert narrow > 0 or n_probes < 16
    assert wide > narrow or k > 2


# -- the claim rounds end when every row is placed (PR 35) -----------------

P, DIR, SLOTS, ROWS = 16, 64, 32, 8
FILLER = 1_000_000  # keys that only occupy positions; nobody looks them up


def _probes(key):
    """The P probe positions of ``key`` in a DIR-entry directory."""
    return np.asarray(_probe_positions(
        jnp.asarray([key], jnp.uint32), DIR, P))[0].tolist()


def _built(taken, slot_cap=SLOTS):
    """A directory with ``taken`` = {position: key} occupied, entry i
    owning slot i, the rest of the slots on the free stack."""
    keys = np.full(DIR, EMPTY_KEY, np.uint32)
    slots = np.full(DIR, -1, np.int32)
    for i, (pos, key) in enumerate(taken.items()):
        keys[pos], slots[pos] = key, i
    return KeyDirectory(
        keys=jnp.asarray(keys), slots=jnp.asarray(slots),
        free=jnp.arange(slot_cap - 1, -1, -1, dtype=jnp.int32),
        free_top=jnp.int32(slot_cap - len(taken)))


def _chain(key, r):
    """``key``'s first r - 1 probe positions taken by other keys: its
    claim lands in round r - 1, so r rounds run."""
    return _built({pos: FILLER + i
                   for i, pos in enumerate(_probes(key)[:r - 1])})


def _off_path(key, n):
    """n positions that are none of ``key``'s probes."""
    return [p for p in range(DIR) if p not in _probes(key)][:n]


def _racing_pair():
    """Two keys whose FIRST probe position is the same entry."""
    first = {}
    for key in range(1, 500):
        other = first.setdefault(_probes(key)[0], key)
        if other != key:
            return other, key
    raise AssertionError("no two of 500 keys share a first position")


def _case_known():
    kd = _admit(init_keydir(DIR, SLOTS), [11, 12, 13])[0]
    return kd, [13, 11, 12, 11], None, 0, lambda kd2, slot, adm: (
        adm[:4].all() and slot[1] == slot[3])


def _case_padding():
    return init_keydir(DIR, SLOTS), [5, 6, 7], [False] * 3, 0, (
        lambda kd2, slot, adm: not adm.any()
        and (np.asarray(kd2.keys) == EMPTY_KEY).all())


def _case_chain(r):
    def case():
        return _chain(777, r), [777], None, r, lambda kd2, slot, adm: (
            adm[0] and np.asarray(kd2.keys)[_probes(777)[r - 1]] == 777)
    return case


def _case_unplaceable():
    # all P positions taken: every round runs, and the key still misses
    return _chain(777, P + 1), [777], None, P, (
        lambda kd2, slot, adm: not adm[0])


def _case_two_unplaceable():
    # two keys with all P positions taken: more rows than one lane are
    # unplaced to the end, so with one lane every round runs wide
    taken = {pos: FILLER + i for i, pos in enumerate(
        dict.fromkeys(_probes(777) + _probes(778)))}
    return _built(taken), [777, 778], None, P, (
        lambda kd2, slot, adm: not adm[:2].any())


def _case_mixed():
    # a known key, a new key placed in round 0 and one that needs three:
    # the loop runs for the slowest row, not the first
    kd = _chain(777, 3)
    kd = _admit(kd, [42])[0]
    return kd, [42, 777, 43], None, 3, lambda kd2, slot, adm: adm[:3].all()


def _case_duplicates():
    return init_keydir(DIR, SLOTS), [9, 9, 9], None, 1, (
        lambda kd2, slot, adm: adm[:3].all() and len(set(slot[:3])) == 1
        and int(occupied_slots(kd2)) == 1)


def _case_race():
    # the smaller key wins the shared entry in round 0, the loser claims
    # its second position in round 1
    a, b = _racing_pair()
    return init_keydir(DIR, SLOTS), [b, a], None, 2, (
        lambda kd2, slot, adm: adm[:2].all() and slot[0] != slot[1]
        and np.asarray(kd2.keys)[_probes(a)[0]] == a
        and np.asarray(kd2.keys)[_probes(b)[1]] == b)


def _case_dry_stack():
    # no slot is free: the claim of round 0 is rolled back by the grant,
    # so the key comes again, and costs its one round, every batch
    kd = _built({pos: FILLER + i
                 for i, pos in enumerate(_off_path(777, 2))}, slot_cap=2)
    return kd, [777], None, 1, lambda kd2, slot, adm: (
        not adm[0] and np.array_equal(np.asarray(kd2.keys),
                                      np.asarray(kd.keys)))


def _case_vacated_prefix():
    # a live key at its third position, its first vacated by a reclaim:
    # the full-depth lookup finds it, no round runs, the vacancy stays
    a, b, c = _probes(321)[:3]
    kd = _built({a: FILLER, b: FILLER + 1, c: 321})
    kd = reclaim_entries(kd, jnp.arange(DIR) == a)[0]
    return kd, [321], None, 0, lambda kd2, slot, adm: (
        adm[0] and slot[0] == 2
        and np.asarray(kd2.keys)[a] == EMPTY_KEY)


# at 8 rows: claim_lanes' own 4, which holds every case's new keys from
# the start; one lane (wide rounds while two rows are unplaced); no
# fewer lanes than rows
@pytest.mark.parametrize("lanes", [None, 1, 8])
@pytest.mark.parametrize("case", [
    pytest.param(_case_known, id="all-keys-known"),
    pytest.param(_case_padding, id="padding-only"),
    pytest.param(_case_chain(1), id="chain-1"),
    pytest.param(_case_chain(3), id="chain-3"),
    pytest.param(_case_chain(P), id="chain-P"),
    pytest.param(_case_unplaceable, id="no-round-can-place"),
    pytest.param(_case_two_unplaceable, id="no-round-can-place-two"),
    pytest.param(_case_mixed, id="slowest-row-decides"),
    pytest.param(_case_duplicates, id="duplicates-of-a-new-key"),
    pytest.param(_case_race, id="two-keys-race-for-one-position"),
    pytest.param(_case_dry_stack, id="dry-free-stack"),
    pytest.param(_case_vacated_prefix, id="vacated-probe-prefix"),
])
def test_claim_rounds_end_when_every_row_is_placed(case, lanes):
    """``admit_slots`` runs its claim rounds while a row is unplaced, P
    at most: ``(kd', slot, admitted)`` are the fixed P rounds' bit for
    bit under jit, the round count is what the case's construction
    needs — and of it the narrow rounds are those that began with no
    more rows unplaced than there are lanes — and a second admit of the
    same batch on the first's directory agrees again (a rolled-back
    claim comes again; a placed key runs no round)."""
    kd, keys, valid, rounds, holds = case()
    k = keydir.claim_lanes(ROWS) if lanes is None else lanes
    assert k == (4 if lanes is None else lanes)
    key = np.zeros(ROWS, np.uint32)
    key[:len(keys)] = keys
    ok = np.zeros(ROWS, bool)
    ok[:len(keys)] = True if valid is None else valid
    key, ok = jnp.asarray(key), jnp.asarray(ok)
    loop = _jit_admit(P, lanes)
    plain = jax.jit(_unrolled, static_argnames="n_probes")

    *got, ran, _ = loop(kd, key, ok)
    *want, left = plain(kd, key, ok, n_probes=P)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert ran.dtype == jnp.int32 and ran.shape == (2,)
    assert np.asarray(ran).tolist() == _rounds_of(left, k, P)
    assert int(ran[0]) == rounds
    if len(keys) <= k:  # nothing to run wide for
        assert int(ran[1]) == rounds
    assert holds(got[0], np.asarray(got[1]), np.asarray(got[2]))

    admitted = bool(np.asarray(got[2])[np.asarray(ok)].all())
    *again, ran_again, _ = loop(got[0], key, ok)
    *want_again, left = plain(want[0], key, ok, n_probes=P)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(want_again)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert np.asarray(ran_again).tolist() == _rounds_of(left, k, P)
    assert int(ran_again[0]) == (0 if admitted else rounds)


# -- 64-bit keys: two ids are one key only if all 64 bits agree (PR 41) -----


def _ids64(ids):
    """int64 / uint64 ids as they come → the device's ``[2, n]`` column."""
    return jnp.asarray(split_key(
        np.asarray(ids, dtype=np.uint64).view(np.int64)))


def _admit64(kd, ids, valid=None, n_probes=16):
    v = jnp.ones(len(ids), bool) if valid is None else jnp.asarray(valid)
    kd, slot, adm, rounds, alias = admit_slots(kd, _ids64(ids), v, n_probes)
    return kd, np.asarray(slot), np.asarray(adm), int(rounds[0]), np.asarray(
        alias)


def _twins(n, seed=0):
    """n pairs of ids of card-number width whose words xor alike."""
    rng = np.random.default_rng(seed)
    a = rng.integers(10 ** 15, 10 ** 16, n).astype(np.uint64)
    m = rng.integers(1, 1 << 20, n).astype(np.uint64)
    b = a ^ m ^ (m << np.uint64(32))
    return a, b


def test_fold_twins_get_two_slots_and_keep_them_through_a_reclaim():
    from real_time_fraud_detection_system_tpu.core.batch import fold_key

    a, b = _twins(20, seed=1)
    assert (fold_key(a.view(np.int64)) == fold_key(b.view(np.int64))).all()
    kd = init_keydir(256, 128, 64)
    kd, slot_a, adm, _, alias = _admit64(kd, a)
    assert adm.all() and alias[0] == 0  # nothing to mistake them for yet
    kd, slot_b, adm, _, alias = _admit64(kd, b)
    # every twin met its sibling under its fingerprint: one trip struck
    # the sibling's entry and left nothing to verify, so they were claimed
    assert adm.all() and alias[0] == 20 and alias[1] == 1
    # now both are resident: the second of a pair looks twice
    _, _, _, _, alias = _admit64(kd, b)
    assert alias[0] == 20 and alias[1] == 2
    assert len(set(slot_a) | set(slot_b)) == 40
    # vacate the FIRST-admitted sibling of every other pair: it sits on
    # the probe-path prefix of the second, which must keep its own entry
    gone = np.zeros(256, bool)
    gone[np.isin(np.asarray(kd.slots), slot_a[::2])] = True
    kd, _, n = reclaim_entries(kd, jnp.asarray(gone))
    assert int(n) == 10
    kd, again, adm, rounds, _ = _admit64(kd, b)
    np.testing.assert_array_equal(again, slot_b)
    assert adm.all() and rounds == 0 and int(occupied_slots(kd)) == 30
    _, hit = lookup_slots(kd, _ids64(a), jnp.ones(20, bool), 16)
    np.testing.assert_array_equal(np.asarray(hit), np.arange(20) % 2 == 1)
    stored = _stored(kd)
    assert len(np.unique(stored)) == len(stored) == 30


@pytest.mark.parametrize("order", ["ab", "ba"])
def test_fold_twins_racing_in_one_batch_end_as_sequential_insertion(order):
    """Both NEW, in one batch, with duplicates of each: the rounds elect
    both at one position (one fingerprint), the grant's owner names the
    entry, the other is unplaced again and takes a second pass. What
    comes out — who is admitted, one slot a key, the directory's
    contents — is what one-at-a-time insertion gives."""
    a, b = _twins(6, seed=2)
    first, second = (a, b) if order == "ab" else (b, a)
    batch = np.concatenate([first, second, first[:3], second[3:]])
    kd, slot, adm, rounds, _ = _admit64(init_keydir(128, 64, 64), batch)
    assert adm.all()
    by_id = {}
    for i, s in zip(batch.tolist(), slot.tolist()):
        assert by_id.setdefault(i, s) == s  # duplicates share the slot
    assert len(set(by_id.values())) == len(by_id) == 12
    assert int(occupied_slots(kd)) == 12
    seq = init_keydir(128, 64, 64)
    for i in batch:
        seq, _, ok, _, _ = _admit64(seq, [i])
        assert ok.all()
    assert int(occupied_slots(seq)) == 12
    np.testing.assert_array_equal(np.sort(_stored(kd)), np.sort(_stored(seq)))
    np.testing.assert_array_equal(np.sort(_stored(kd)),
                                  np.sort(np.concatenate([a, b])))
    # and they are found again, every one at its own slot
    got, hit = lookup_slots(kd, _ids64(batch), jnp.ones(len(batch), bool),
                            16)
    assert np.asarray(hit).all()
    np.testing.assert_array_equal(np.asarray(got), slot)


def _rounds_over_the_batch(keys, key, entry, placed, n_probes, match):
    """``ops/keydir._claim_rounds`` as the rounds stood before PR 51: one
    loop, every round over the whole batch, while a row is unplaced. The
    pin for the wide admit, which has no unrolled twin."""
    dir_cap = keys.shape[0]

    def claim_round(carry):
        j, keys, entry, placed, claimed = carry
        p = keydir._probe_position(key, j.astype(jnp.uint32), dir_cap)
        cur = keys[p]
        if match:
            hit = ~placed & (cur == key)
            entry, placed = jnp.where(hit, p, entry), placed | hit
        want = ~placed & (cur == EMPTY_KEY)
        keys = keys.at[p].min(jnp.where(want, key, EMPTY_KEY))
        won = want & (keys[p] == key)
        return (j + 1, keys, jnp.where(won, p, entry), placed | won,
                claimed | won)

    ran, keys, entry, placed, claimed = jax.lax.while_loop(
        lambda c: (c[0] < n_probes) & ~c[3].all(), claim_round,
        (jnp.int32(0), keys, entry, placed, jnp.zeros_like(placed)))
    return keys, entry, placed, claimed, jnp.stack([ran, jnp.int32(0)])


@pytest.mark.parametrize("lanes", LANES_128)
@pytest.mark.parametrize("width", [32, 64])
def test_narrowed_claim_rounds_equal_the_rounds_over_the_batch(width,
                                                               lanes):
    """Both admits, wherever their rounds go narrow, against the one
    loop over the batch they ran until PR 51: directory, slots,
    admissions, round count and alias counts to the bit, batch after
    batch. At 64 bits every key comes with its fold twin (``wide_id``),
    so new twins race in one batch, the loser is unplaced again and the
    second pass's rounds find it among the packed rows — or, with fewer
    lanes than losers, run wide for it."""
    rng = np.random.default_rng(width)
    narrowed = _jit_admit(16, lanes)
    whole = _jit_admit(16, rounds=_rounds_over_the_batch)
    k = keydir.claim_lanes(128) if lanes is None else lanes
    kd_a = kd_b = init_keydir(512, 192, width)
    ran = np.zeros(2, int)
    for step in range(8):
        keys = _keys(rng.integers(0, 600, 128), width)
        valid = jnp.asarray(rng.random(128) < 0.9)
        kd_a, slot_a, adm_a, rounds_a, alias_a = narrowed(kd_a, keys, valid)
        kd_b, slot_b, adm_b, rounds_b, alias_b = whole(kd_b, keys, valid)
        if step % 4 == 3:
            dead = jnp.asarray(rng.random(512) < 0.33)
            kd_a, kd_b = (reclaim_entries(kd, dead)[0]
                          for kd in (kd_a, kd_b))
        for a, b in zip(
                jax.tree.leaves((kd_a, slot_a, adm_a, rounds_a[0], alias_a)),
                jax.tree.leaves((kd_b, slot_b, adm_b, rounds_b[0], alias_b))):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        ran += np.asarray(rounds_a)
    wide, narrow = ran[0] - ran[1], ran[1]
    assert narrow > 0 and (wide == 0) == (k >= 128)


@pytest.mark.parametrize("lanes", [None, 1, 24])
def test_a_fingerprint_twin_among_the_packed_rows_takes_its_second_pass(
        lanes):
    """Six pairs of NEW fold twins and duplicates of each in one 24-row
    batch: the first pass's rounds elect both of a pair at one position,
    the grant unplaces the six losers with their duplicates (12 rows),
    and the second pass places them one position on — from the packed
    lanes when they fit (``claim_lanes(24)`` is 12: exactly), over the
    batch when there is one lane. The answers are the same."""
    a, b = _twins(6, seed=2)
    batch = np.concatenate([a, b, a[:3], b[3:]])
    key, valid = _ids64(batch), jnp.ones(len(batch), bool)
    kd = init_keydir(128, 64, 64)
    *got, ran, alias = _jit_admit(16, lanes)(kd, key, valid)
    *want, ran_whole, alias_whole = _jit_admit(
        16, rounds=_rounds_over_the_batch)(kd, key, valid)
    for x, y in zip(jax.tree.leaves((got, alias)),
                    jax.tree.leaves((want, alias_whole))):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
    assert np.asarray(got[2]).all()
    assert int(ran[0]) == int(ran_whole[0]) >= 3  # a second pass ran
    k = keydir.claim_lanes(24) if lanes is None else lanes
    wide, narrow = int(ran[0] - ran[1]), int(ran[1])
    # 24 rows unplaced as the first pass begins, 12 as the second does
    assert {12: wide >= 1 and narrow >= 2, 1: wide >= 3,
            24: wide == 0}[k], (wide, narrow)


def test_three_new_ids_of_one_fingerprint_take_three_passes():
    rng = np.random.default_rng(3)
    base = np.uint64(rng.integers(10 ** 15, 10 ** 16))
    trio = np.asarray([base ^ np.uint64(m) ^ (np.uint64(m) << np.uint64(32))
                       for m in (0, 5, 9)], np.uint64)
    kd, slot, adm, rounds, _ = _admit64(init_keydir(64, 32, 64), trio)
    assert adm.all() and len(set(slot.tolist())) == 3
    assert rounds == 1 + 2 + 3  # each pass walks past the entries before
    np.testing.assert_array_equal(np.sort(_stored(kd)), np.sort(trio))


def test_more_ids_of_one_fingerprint_than_probes_miss_and_never_share():
    rng = np.random.default_rng(4)
    base = np.uint64(rng.integers(10 ** 15, 10 ** 16))
    ids = np.asarray([base ^ np.uint64(m) ^ (np.uint64(m) << np.uint64(32))
                      for m in range(6)], np.uint64)
    kd, slot, adm, _, _ = _admit64(init_keydir(64, 32, 64), ids, n_probes=4)
    # one probe path of 4 positions: four of the six are resident, the
    # other two are the sketch tier's, and no two share a slot
    assert adm.sum() == 4 and len(set(slot[adm].tolist())) == 4
    assert int(occupied_slots(kd)) == 4
    kd, slot2, adm2, _, _ = _admit64(kd, ids, n_probes=4)
    np.testing.assert_array_equal(adm2, adm)
    np.testing.assert_array_equal(slot2[adm], slot[adm])


BOUNDARY_IDS = [
    0, 1, 1 << 32, (1 << 32) + 1,
    0xFFFFFFFE, (1 << 32) - 1,  # 0xFFFFFFFF: folds to the vacant marker
    0x00000001_FFFFFFFE,  # folds to 0xFFFFFFFF too: an alias of the last
    0x00000001_FFFFFFFF,  # folds to 0xFFFFFFFE: what _canon maps it to
    0xFFFFFFFF_00000000,  # its words xor to the vacant marker as well
    (1 << 63) - 1, 1 << 63,  # int64 max, int64 min read as uint64
    0xFFFFFFFF_FFFFFFFE,  # int64 -2: the reserved pattern's neighbour
    0xFFFFFFFE_FFFFFFFF,
]
RESERVED_ID = 0xFFFFFFFF_FFFFFFFF  # int64 -1


def test_boundary_ids_are_distinct_keys_and_the_reserved_one_is_refused():
    ids = np.asarray(BOUNDARY_IDS + [RESERVED_ID], np.uint64)
    # negative int64s are their bit patterns
    as_int64 = ids.view(np.int64)
    assert as_int64[-1] == -1 and as_int64[-3] == -2
    np.testing.assert_array_equal(join_key(split_key(as_int64)), ids)
    kd, slot, adm, _, _ = _admit64(init_keydir(128, 64, 64), ids)
    assert adm[:-1].all() and not adm[-1]  # refused, not merged unseen
    assert len(set(slot[:-1].tolist())) == len(BOUNDARY_IDS)
    np.testing.assert_array_equal(np.sort(_stored(kd)),
                                  np.sort(ids[:-1]))
    # the stored fingerprints never hold the vacant marker
    live = np.asarray(kd.slots) >= 0
    assert not (np.asarray(kd.keys)[live] == EMPTY_KEY).any()
    # again, one at a time and all at once: the same slots, no new entry
    kd2, slot2, adm2, rounds, _ = _admit64(kd, ids)
    np.testing.assert_array_equal(slot2, slot)
    np.testing.assert_array_equal(adm2, adm)
    assert rounds == 0 and int(occupied_slots(kd2)) == len(BOUNDARY_IDS)
    got, hit = lookup_slots(kd, _ids64(ids), jnp.ones(len(ids), bool), 16)
    np.testing.assert_array_equal(np.asarray(hit), adm)
    np.testing.assert_array_equal(np.asarray(got)[:-1], slot[:-1])
    # a 32-bit directory merges what the fold merges: fewer slots
    from real_time_fraud_detection_system_tpu.core.batch import fold_key

    narrow = init_keydir(128, 64)
    narrow, _, _, _, none = admit_slots(
        narrow, jnp.asarray(fold_key(as_int64)), jnp.ones(len(ids), bool),
        n_probes=16)
    assert none is None  # a one-word directory has no alias to count
    assert int(occupied_slots(narrow)) < len(BOUNDARY_IDS)


def test_the_32_bit_directory_is_the_parents_pytree():
    """At ``key_bits=32`` the directory has the parent's four leaves,
    shapes and dtypes; the two key words exist at 64 only."""
    kd = init_keydir(64, 16)
    leaves = jax.tree.leaves(kd)
    assert [(leaf.shape, str(leaf.dtype)) for leaf in leaves] == [
        ((64,), "uint32"), ((64,), "int32"), ((16,), "int32"),
        ((), "int32")]
    assert kd.keys_lo is None and kd.keys_hi is None and not kd.wide
    kd = admit_slots(kd, jnp.arange(8, dtype=jnp.uint32),
                     jnp.ones(8, bool))[0]
    assert len(jax.tree.leaves(kd)) == 4
    assert len(jax.tree.leaves(reclaim_entries(
        kd, jnp.ones(64, bool))[0])) == 4
    wide = init_keydir(64, 16, 64)
    assert [(leaf.shape, str(leaf.dtype))
            for leaf in jax.tree.leaves(wide)[4:]] == [
        ((64,), "uint32")] * 2
