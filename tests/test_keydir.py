"""Exact on-device key directory (ops/keydir.py): batched insert race
resolution, duplicate coalescing, free-list-bounded admission, read-only
lookup, and reclaim — the primitives the tiered feature store
(key_mode="exact") is built from."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


def _admit(kd, keys, valid=None):
    k = jnp.asarray(np.asarray(keys, np.uint32))
    v = jnp.ones(k.shape, bool) if valid is None else jnp.asarray(valid)
    return admit_slots(kd, k, v)[:3]


def test_admit_assigns_unique_slots_and_coalesces_duplicates():
    kd = init_keydir(64, 16)
    kd, slot, adm = _admit(kd, [5, 5, 7, 9, 5, 11])
    slot, adm = np.asarray(slot), np.asarray(adm)
    assert adm.all()
    # batch duplicates of one key share ONE slot (and one grant)
    assert slot[0] == slot[1] == slot[4]
    assert len({slot[0], slot[2], slot[3], slot[5]}) == 4
    assert int(occupied_slots(kd)) == 4


def test_admit_is_stable_across_batches():
    kd = init_keydir(64, 16)
    kd, s1, _ = _admit(kd, [100, 200, 300])
    kd, s2, adm = _admit(kd, [300, 100, 200])
    np.testing.assert_array_equal(
        np.asarray(s2), np.asarray(s1)[[2, 0, 1]])
    assert np.asarray(adm).all()
    assert int(occupied_slots(kd)) == 3  # no double-allocation


def test_admission_bounded_by_free_list_then_recovers():
    kd = init_keydir(64, 8)
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


def test_invalid_rows_never_place():
    kd = init_keydir(64, 16)
    kd, slot, adm = _admit(kd, [1, 2, 3], valid=[True, False, True])
    assert not bool(np.asarray(adm)[1])
    assert int(occupied_slots(kd)) == 2
    _, hit = lookup_slots(kd, jnp.asarray(np.uint32(2))[None],
                          jnp.ones(1, bool))
    assert not bool(hit[0])


def test_lookup_is_read_only_and_exact():
    kd = init_keydir(64, 16)
    kd, slot, _ = _admit(kd, [42, 43])
    got, hit = lookup_slots(kd, jnp.asarray(np.array([43, 42, 44],
                                                     np.uint32)),
                            jnp.ones(3, bool))
    hit = np.asarray(hit)
    assert bool(hit[0]) and bool(hit[1]) and not bool(hit[2])
    np.testing.assert_array_equal(np.asarray(got)[:2],
                                  np.asarray(slot)[[1, 0]])
    # lookup never allocates
    assert int(occupied_slots(kd)) == 2


def test_reclaim_frees_entries_and_slots_consistently():
    kd = init_keydir(64, 16)
    kd, slot, _ = _admit(kd, [1, 2, 3, 4])
    # vacate exactly key 2's entry
    target = int(np.asarray(slot)[1])
    dead_entry = np.asarray(kd.slots) == target
    kd, dead, n = reclaim_entries(kd, jnp.asarray(dead_entry))
    assert int(n) == 1 and int(occupied_slots(kd)) == 3
    _, hit = lookup_slots(kd, jnp.asarray(np.array([2], np.uint32)),
                          jnp.ones(1, bool))
    assert not bool(hit[0])
    # the other keys are untouched
    got, hit = lookup_slots(kd, jnp.asarray(np.array([1, 3, 4],
                                                     np.uint32)),
                            jnp.ones(3, bool))
    assert np.asarray(hit).all()
    # the freed slot is re-grantable
    kd, s5, adm = _admit(kd, [50])
    assert bool(np.asarray(adm)[0])


def test_readmission_survives_probe_prefix_vacancy():
    """Review-pass regression: reclaiming an entry that sits on a LIVE
    key's probe-path prefix must not make re-admission duplicate the key
    (claim the vacancy, pop a fresh slot, reset its history). The insert
    path must look up the FULL probe depth before claiming anything."""
    kd = init_keydir(64, 32)
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 10_000, 24).astype(np.uint32)
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
    stored = np.asarray(kd.keys)[np.asarray(kd.slots) >= 0]
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


def test_admit_under_jit_matches_eager():
    kd_e = init_keydir(128, 32)
    kd_j = init_keydir(128, 32)
    rng = np.random.default_rng(3)
    jitted = jax.jit(admit_slots, static_argnames="n_probes")
    for _ in range(4):
        keys = rng.integers(0, 200, 64).astype(np.uint32)
        kd_e, s_e, a_e = _admit(kd_e, keys)
        kd_j, s_j, a_j, _ = jitted(kd_j, jnp.asarray(keys),
                                   jnp.ones(64, bool))
        np.testing.assert_array_equal(np.asarray(s_e), np.asarray(s_j))
        np.testing.assert_array_equal(np.asarray(a_e), np.asarray(a_j))
    np.testing.assert_array_equal(np.asarray(kd_e.keys),
                                  np.asarray(kd_j.keys))


@pytest.mark.parametrize("n_keys,slot_cap", [(500, 512), (2000, 256)])
def test_admission_exactness_property(n_keys, slot_cap):
    """Random stream: every admitted key maps to a UNIQUE slot; the
    mapping is a function (same key → same slot, always); occupancy
    equals the number of distinct admitted keys."""
    kd = init_keydir(2 * 1024, slot_cap)
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
        kd, _, adm, _ = admit(kd, jnp.asarray(keys[i:i + batch]),
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
    the pin for the loop the program now runs."""
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
    for j in range(n_probes):
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
            jnp.where(admitted, slot, 0), admitted)


@pytest.mark.parametrize("n_probes", [1, 3, 16])
def test_claim_rounds_as_a_loop_equal_the_unrolled_rounds_bit_for_bit(
        n_probes):
    """Racing new keys, batch duplicates of a new key, returning keys,
    invalid rows, reclaimed vacancies on a probe path and a free stack
    that runs dry mid-batch: the directory and the answers of the loop
    are the unrolled rounds' to the bit, batch after batch."""
    rng = np.random.default_rng(n_probes)
    loop = jax.jit(admit_slots, static_argnames="n_probes")
    plain = jax.jit(_admit_slots_unrolled, static_argnames="n_probes")
    kd_a = kd_b = init_keydir(256, 96)
    ran_dry = False
    for step in range(12):
        keys = jnp.asarray(rng.integers(0, 400, 128).astype(np.uint32))
        valid = jnp.asarray(rng.random(128) < 0.9)
        kd_a, slot_a, adm_a, _ = loop(kd_a, keys, valid, n_probes=n_probes)
        kd_b, slot_b, adm_b = plain(kd_b, keys, valid, n_probes=n_probes)
        ran_dry = ran_dry or int(kd_a.free_top) == 0
        if step % 4 == 3:  # vacate a third of the live entries
            dead = jnp.asarray(rng.random(256) < 0.33)
            kd_a, kd_b = (reclaim_entries(kd, dead)[0]
                          for kd in (kd_a, kd_b))
        for a, b in zip(jax.tree.leaves((kd_a, slot_a, adm_a)),
                        jax.tree.leaves((kd_b, slot_b, adm_b))):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert ran_dry or n_probes == 1  # one probe loses keys first


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


@pytest.mark.parametrize("case", [
    pytest.param(_case_known, id="all-keys-known"),
    pytest.param(_case_padding, id="padding-only"),
    pytest.param(_case_chain(1), id="chain-1"),
    pytest.param(_case_chain(3), id="chain-3"),
    pytest.param(_case_chain(P), id="chain-P"),
    pytest.param(_case_unplaceable, id="no-round-can-place"),
    pytest.param(_case_mixed, id="slowest-row-decides"),
    pytest.param(_case_duplicates, id="duplicates-of-a-new-key"),
    pytest.param(_case_race, id="two-keys-race-for-one-position"),
    pytest.param(_case_dry_stack, id="dry-free-stack"),
    pytest.param(_case_vacated_prefix, id="vacated-probe-prefix"),
])
def test_claim_rounds_end_when_every_row_is_placed(case):
    """``admit_slots`` runs its claim rounds while a row is unplaced, P
    at most: ``(kd', slot, admitted)`` are the fixed P rounds' bit for
    bit under jit, the round count is what the case's construction
    needs, and a second admit of the same batch on the first's
    directory agrees again (a rolled-back claim comes again; a placed
    key runs no round)."""
    kd, keys, valid, rounds, holds = case()
    key = np.zeros(ROWS, np.uint32)
    key[:len(keys)] = keys
    ok = np.zeros(ROWS, bool)
    ok[:len(keys)] = True if valid is None else valid
    key, ok = jnp.asarray(key), jnp.asarray(ok)
    loop = jax.jit(admit_slots, static_argnames="n_probes")
    plain = jax.jit(_admit_slots_unrolled, static_argnames="n_probes")

    *got, ran = loop(kd, key, ok, n_probes=P)
    want = plain(kd, key, ok, n_probes=P)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert ran.dtype == jnp.int32 and ran.shape == ()
    assert int(ran) == rounds
    assert holds(got[0], np.asarray(got[1]), np.asarray(got[2]))

    admitted = bool(np.asarray(got[2])[np.asarray(ok)].all())
    *again, ran_again = loop(got[0], key, ok, n_probes=P)
    want_again = plain(want[0], key, ok, n_probes=P)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(want_again)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert int(ran_again) == (0 if admitted else rounds)
