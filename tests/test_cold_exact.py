"""The cold tier's contract: exact per row (`features.cold_store`).

A returning key's exact window rows are in the hot tier BEFORE the step
that scores its row runs, so an engine whose hot tier is oversubscribed,
with the cold store armed, delivers — bit for bit, on every row — what
an engine whose hot tier holds every key delivers. Held here through
``engine.run()`` for pipeline depth 1 and 2, ``precompile`` on and off,
one device and the mesh, a stream whose days advance (the history fill's
shape) and one that stays on one day (the window's), keys that return in
the batch right after their demotion, and more returning keys in a batch
than the smallest promote width holds (and than the 64 the old bounded
queue held). Then the four orderings that cannot lose a key, and the
store's batch read against its per-key read.
"""

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.io.coldstore import ColdStore
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime.engine import (
    ColdPromoteError,
    ScoringEngine,
    promote_widths,
)
from real_time_fraud_detection_system_tpu.utils.metrics import MetricsRegistry

DAY0 = 20200
EMPTY = np.uint32(0xFFFFFFFF)


class _Source:
    def __init__(self, batches):
        self._it = iter(batches)
        self.offsets = [0]

    def poll_batch(self):
        cols = next(self._it, None)
        if cols is not None:
            self.offsets = [self.offsets[0] + len(cols["tx_id"])]
            cols = {k: v.copy() for k, v in cols.items()}
        return cols


class _Sink:
    def __init__(self):
        self.results = []

    def append(self, res):
        self.results.append(res)


def _cols(cust, term, day, first_tx=0):
    cust = np.asarray(cust, np.int64)
    term = np.asarray(term, np.int64)
    n = len(cust)
    us = (day * 86400 + np.arange(n) % 86400).astype(np.int64) * 1_000_000
    return {
        "tx_id": first_tx + np.arange(n, dtype=np.int64),
        "tx_datetime_us": us,
        "customer_id": cust,
        "terminal_id": term,
        "tx_amount_cents": (1000 + 7 * (cust % 97)).astype(np.int64),
        "kafka_ts_ms": us // 1000,
    }


def _churn(seed, n_batches, rows, universe, day_step=1, stride=1):
    """Random keys of a universe several times the hot tier, a new event
    day every ``day_step`` batches (0: one day throughout)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_batches):
        cust = rng.integers(0, universe, rows) * stride
        term = rng.integers(0, universe, rows) * stride + 1_000_000
        day = DAY0 + (i // day_step if day_step else 0)
        out.append(_cols(cust, term, day, first_tx=i * rows))
    return out


def _fcfg(cold_store, cap, demote, highwater=0.5, every=1):
    return dict(customer_capacity=cap, terminal_capacity=cap,
                cms_width=1 << 12, key_mode="exact", compact_every=every,
                # the sizing rule: at this load 8 probes lose a key every
                # few thousand admissions, 16 none (README, Cold tier)
                keydir_probes=16,
                cold_store=cold_store, cold_demote_slots=demote,
                cold_highwater=highwater)


def _build(fcfg, rt, reg, devices=1):
    params = init_logreg(15)
    scaler = Scaler(mean=np.zeros(15, np.float32),
                    scale=np.ones(15, np.float32))
    cfg = Config(features=FeatureConfig(**fcfg), runtime=rt)
    if devices > 1:
        from real_time_fraud_detection_system_tpu.runtime import (
            ShardedScoringEngine,
        )

        return ShardedScoringEngine(cfg, kind="logreg", params=params,
                                    scaler=scaler, n_devices=devices,
                                    metrics=reg)
    return ScoringEngine(cfg, kind="logreg", params=params, scaler=scaler,
                         metrics=reg)


def _all_hot(fcfg, rt):
    """The control: a hot tier that holds every key, nothing compacted,
    no cold store — one device whatever the engine under test."""
    fc = dict(fcfg)
    fc.update(customer_capacity=1 << 14, terminal_capacity=1 << 14,
              cold_store="", compact_every=0)
    return _build(fc, rt, MetricsRegistry())


def _assert_same_rows(got, want):
    assert len(got) == len(want)
    for r_e, r_c in zip(got, want):
        assert r_e.batch_index == r_c.batch_index
        np.testing.assert_array_equal(r_e.tx_id, r_c.tx_id)
        np.testing.assert_array_equal(np.asarray(r_e.features),
                                      np.asarray(r_c.features))
        np.testing.assert_array_equal(np.asarray(r_e.probs),
                                      np.asarray(r_c.probs))


def _assert_exact_counters(eng, reg, stats, aot):
    assert stats["exactness_degraded_keys"] == 0 and not eng._degraded_keys
    cms = reg.get("rtfds_feature_tier_rows_total", tier="cms")
    assert cms is None or cms.value == 0
    lanes = reg.get("rtfds_feature_cold_promote_lanes_total").value
    assert lanes >= reg.get("rtfds_feature_cold_promotions_total").value
    if aot:
        rc = reg.get("rtfds_xla_recompiles_total")
        assert (rc.value if rc else 0) == 0
        assert reg.get("rtfds_aot_fallbacks_total").value == 0


def _spy(eng):
    """Record, in loop order, the keys each compaction demoted and the
    keys each batch promoted: ``[("demote"|"promote", table, keys)]``."""
    log = []
    append, mark = eng._cold.append, eng._cold.mark_promoted

    def spy_append(table, keys, *rows, **kw):
        keys = np.asarray(keys).reshape(-1)
        log.append(("demote", table, np.sort(keys[keys != EMPTY])))
        return append(table, keys, *rows, **kw)

    def spy_mark(table, keys):
        log.append(("promote", table, np.sort(np.asarray(keys))))
        return mark(table, keys)

    eng._cold.append = spy_append
    eng._cold.mark_promoted = spy_mark
    return log


# -- cold-armed ≡ all-hot, bit for bit on every delivered row ----------------


@pytest.mark.parametrize("devices,depth,precompile", [
    (1, 1, True), (1, 2, True), (1, 1, False), (1, 2, False),
    (4, 1, True), (4, 2, False),
], ids=["one-d1-aot", "one-d2-aot", "one-d1-jit", "one-d2-jit",
        "mesh-d1-aot", "mesh-d2-jit"])
def test_cold_armed_equals_all_hot(tmp_path, devices, depth, precompile):
    """The fill's shape: a new event day every batch, a compaction after
    every batch, keys drawn from a universe four times the hot tier —
    keys are demoted and come back throughout, some in the very batch
    after the pass that demoted them."""
    rt = RuntimeConfig(batch_buckets=(64,), max_batch_rows=64,
                       pipeline_depth=depth, precompile=precompile)
    fcfg = _fcfg(str(tmp_path / "cold"), cap=256, demote=64)
    reg = MetricsRegistry()
    eng = _build(fcfg, rt, reg, devices)
    log = _spy(eng)
    # ids are multiples of 4 on the mesh so that one shard owns them all:
    # its lane block is the one that has to hold a batch's returning keys
    batches = _churn(7, 24, 64, 1024)
    sink, ctrl_sink = _Sink(), _Sink()
    stats = eng.run(_Source(batches), sink)
    _all_hot(fcfg, rt).run(_Source(batches), ctrl_sink)
    _assert_same_rows(sink.results, ctrl_sink.results)
    _assert_exact_counters(eng, reg, stats, precompile)
    assert reg.get("rtfds_feature_cold_demotions_total").value > 200
    assert reg.get("rtfds_feature_cold_promotions_total").value > 50
    # a key promoted by the first batch prepared after the pass that
    # demoted it: promote-before-score had one host prep to see it in
    back_to_back = 0
    for table in ("customer", "terminal"):
        events = [(kind, keys) for kind, t, keys in log if t == table]
        for (k0, keys0), (k1, keys1) in zip(events, events[1:]):
            if (k0, k1) == ("demote", "promote"):
                back_to_back += np.intersect1d(keys0, keys1).size
    assert back_to_back > 0


def _wide_ids(k):
    """Dense test ids → 64-bit ids in FOLD-TWIN pairs: 2j and 2j + 1 get
    different words with the same xor, so ``key_bits=32`` would serve
    each pair as one key — in the directory and in the cold store."""
    k = np.asarray(k, np.uint64)
    hi = np.uint64(0x2386F2) + (k % np.uint64(2)) * np.uint64(0x3039)
    fold = ((k // np.uint64(2)) * np.uint64(2654435761)
            + np.uint64(1)) & np.uint64(0xFFFFFFFF)
    return ((hi << np.uint64(32)) | (fold ^ hi)).view(np.int64)


@pytest.mark.parametrize("depth,precompile", [(2, True), (1, False)],
                         ids=["d2-aot", "d1-jit"])
def test_cold_armed_equals_all_hot_on_wide_ids(tmp_path, depth, precompile):
    """``key_bits=64`` through the tier: fold twins are demoted as two
    keys with two rows, one of a pair comes back while its twin stays
    cold, the segment files and manifests carry whole uint64 keys — and
    every delivered row equals the all-hot engine's bit for bit."""
    import json

    from real_time_fraud_detection_system_tpu.core.batch import fold_key

    rt = RuntimeConfig(batch_buckets=(64,), max_batch_rows=64,
                       pipeline_depth=depth, precompile=precompile)
    fcfg = dict(_fcfg(str(tmp_path / "cold"), cap=256, demote=64),
                key_bits=64, cold_segment_mb=0.01)
    batches = _churn(7, 24, 64, 1024)
    for b in batches:
        b["customer_id"] = _wide_ids(b["customer_id"])
        b["terminal_id"] = _wide_ids(b["terminal_id"])
    reg = MetricsRegistry()
    eng = _build(fcfg, rt, reg)
    demoted, promoted = [], []
    append, mark = eng._cold.append, eng._cold.mark_promoted

    def spy_append(table, keys, *rows, **kw):
        keys = np.asarray(keys)
        assert keys.dtype == np.uint64 and keys.ndim == 1
        live = keys[keys != np.iinfo(np.uint64).max]
        assert (np.diff(live.astype(object)) > 0).all()  # key order
        demoted.append((table, live))
        return append(table, keys, *rows, **kw)

    def spy_mark(table, keys):
        promoted.append((table, np.asarray(keys)))
        return mark(table, keys)

    eng._cold.append, eng._cold.mark_promoted = spy_append, spy_mark
    sink, ctrl_sink = _Sink(), _Sink()
    stats = eng.run(_Source(batches), sink)
    _all_hot(fcfg, rt).run(_Source(batches), ctrl_sink)
    _assert_same_rows(sink.results, ctrl_sink.results)
    _assert_exact_counters(eng, reg, stats, precompile)
    assert reg.get("rtfds_feature_cold_demotions_total").value > 200
    assert reg.get("rtfds_feature_cold_promotions_total").value > 50
    for table in ("customer", "terminal"):
        gone = np.concatenate([k for t, k in demoted if t == table])
        back = np.concatenate([k for t, k in promoted if t == table])
        assert gone.dtype == back.dtype == np.uint64
        assert (gone >> np.uint64(32) != 0).all()  # whole keys, not folds
        # a pair demoted as two keys (one fold, two rows) ...
        folds, n = np.unique(fold_key(np.unique(gone).view(np.int64)),
                             return_counts=True)
        assert (n == 2).sum() > 10
        # ... and one of the two promoted back without its twin
        twin = (back.view(np.int64) ^ np.int64(0x3039 << 32 | 0x3039)
                ).view(np.uint64)
        assert (~np.isin(twin, back)).sum() > 10
    # the durable copy carries whole keys too
    mans = sorted((tmp_path / "cold").glob("seg-*.json"))
    assert mans
    man = json.loads(mans[0].read_text())
    assert man["key_bits"] == 64
    assert all(k >> 32 for ks in man["keys"].values() for k in ks)
    # the same stream at 32 bits merges the pairs: rows differ
    narrow = _build(dict(fcfg, key_bits=32,
                         cold_store=str(tmp_path / "cold32")), rt,
                    MetricsRegistry())
    narrow_sink = _Sink()
    narrow.run(_Source(batches), narrow_sink)
    assert any((np.asarray(a.features) != np.asarray(b.features)).any()
               for a, b in zip(narrow_sink.results, ctrl_sink.results))


@pytest.mark.parametrize("devices", [1, 4], ids=["one-device", "mesh"])
def test_claim_rounds_of_steps_and_promotes_are_counted(tmp_path, devices):
    """``rtfds_keydir_claim_rounds_total{table=…}`` is every round every
    admit ran: the step's, carried beside the tier rows a batch's finish
    fetches anyway, and each promote program's, the third column of the
    stats ``_check_promotes`` reads — summed over the mesh's shards. A
    promoted key is new to its directory, so the promotes run rounds.
    ``rtfds_keydir_narrow_rounds_total{table=…}`` is those of them that
    ran over the packed lanes, from the columns beside: at 64 rows a few
    new keys a batch fit the lanes, so most rounds are narrow."""
    rt = RuntimeConfig(batch_buckets=(64,), max_batch_rows=64)
    reg = MetricsRegistry()
    eng = _build(_fcfg(str(tmp_path / "cold"), cap=256, demote=64), rt,
                 reg, devices)
    ran = {"step": np.zeros(4), "promote": np.zeros(4)}
    programs = {"step": 0, "promote": 0}
    dispatch = eng._dispatch_step

    def spy(key, fn, *args):
        out = dispatch(key, fn, *args)
        # → [customer, terminal] rounds, then the narrow ones of them
        if key[0] in ("step", "sharded"):  # [shards,] [dense, cms, + 4]
            ran["step"] += np.asarray(out[4]).reshape(-1, 6).sum(0)[2:]
            programs["step"] += 1
        elif key[0] == "promote":  # [shards,] table x [adm, drop, + 2]
            ran["promote"] += np.asarray(out[1]).reshape(
                -1, 2, 4).sum(0)[:, 2:].T.reshape(-1)
            programs["promote"] += 1
        return out

    eng._dispatch_step = spy
    eng.run(_Source(_churn(7, 24, 64, 1024)), _Sink())
    assert programs["step"] == 24 and programs["promote"] > 10
    assert (ran["promote"] > 0).all() and (ran["step"] > 0).all()
    for i, table in enumerate(("customer", "terminal")):
        got = reg.get("rtfds_keydir_claim_rounds_total", table=table).value
        assert got == ran["step"][i] + ran["promote"][i], table
        # two admits a step and one a promote, 16 rounds each at most
        assert got < 16 * devices * (programs["step"] + programs["promote"])
        narrow = reg.get("rtfds_keydir_narrow_rounds_total",
                         table=table).value
        assert narrow == ran["step"][2 + i] + ran["promote"][2 + i], table
        assert got / 2 < narrow <= got, (table, narrow, got)


@pytest.mark.parametrize("devices,ladder", [
    (1, (256, 1024)), (4, (256, 1024)), (1, (256,)), (4, (256,)),
], ids=["one", "mesh", "one-chunked", "mesh-chunked"])
def test_many_keys_return_in_one_batch(tmp_path, devices, ladder):
    """The window's shape: one event day after a fill of older days, so
    only fill-era keys can be demoted — and then hundreds of them return
    in ONE batch: more than the smallest promote width (256), more than
    the 64 the old bounded queue held. The wide payload is dispatched at
    the ladder's next width, its lanes unique keys; with a ladder that
    stops at 256 lanes (as the real one stops at 16,384) the same keys
    go in several payloads."""
    # one shard owns every id on the mesh (ids are multiples of 4), so a
    # shard gets the slots the one device has: its lane block is the one
    # that has to hold the batch's returning keys
    rows, cap = 1024, 4096 * devices
    assert promote_widths(rows) == (256, 1024)
    rt = RuntimeConfig(batch_buckets=(rows,), max_batch_rows=rows,
                       pipeline_depth=2, precompile=True)
    fcfg = _fcfg(str(tmp_path / "cold"), cap=cap, demote=1024,
                 highwater=0.25, every=2)
    reg = MetricsRegistry()
    eng = _build(fcfg, rt, reg, devices)
    eng._promote_widths = ladder  # read by the inventory and the dispatch
    stride = 4 if devices > 1 else 1
    old = np.arange(1500) * stride
    fill = [_cols(old[i::2][:rows], old[i::2][:rows] + 1_000_000, DAY0 + i,
                  first_tx=i * rows) for i in range(2)]
    # day 5: new keys push the fill's out (passes after batches 2 and 4),
    # then 600 of the demoted return at once, then the rest
    new = (5000 + np.arange(3 * 400)) * stride
    window = [_cols(new[i * 400:(i + 1) * 400],
                    new[i * 400:(i + 1) * 400] + 1_000_000, DAY0 + 5,
                    first_tx=(2 + i) * rows) for i in range(3)]
    window.append(_cols(old[:600], old[:600] + 1_000_000, DAY0 + 5,
                        first_tx=5 * rows))
    window.append(_cols(old[600:], old[600:] + 1_000_000, DAY0 + 5,
                        first_tx=6 * rows))
    widths = []
    dispatch = eng._dispatch_step

    def spy_dispatch(key, fn, *args):
        if key[0] == "promote":
            lanes = np.asarray(args[1][key[1]][0]).reshape(-1)
            live = lanes[lanes != EMPTY]
            assert live.size == np.unique(live).size, "a key on two lanes"
            widths.append((key[2], live.size))
        return dispatch(key, fn, *args)

    eng._dispatch_step = spy_dispatch
    sink, ctrl_sink = _Sink(), _Sink()
    stats = eng.run(_Source(fill + window), sink)
    _all_hot(fcfg, rt).run(_Source(fill + window), ctrl_sink)
    _assert_same_rows(sink.results, ctrl_sink.results)
    _assert_exact_counters(eng, reg, stats, aot=True)
    if ladder == (256,):
        assert {w for w, _n in widths} == {256}
        assert sum(n == 256 for _w, n in widths) >= 2  # full payloads
    else:
        assert max(n for _w, n in widths) > 256
        assert {w for w, n in widths if n > 256} == {1024}
        assert all(w == 256 for w, n in widths if n <= 256)
    promoted = reg.get("rtfds_feature_cold_promotions_total").value
    assert promoted == sum(n for _w, n in widths) > 1000
    assert reg.get("rtfds_feature_cold_rows_total").value >= 1000


# -- orderings that cannot lose a key ----------------------------------------


def _small(tmp_path, depth=2, every=1, cap=256, demote=64, rows=64):
    rt = RuntimeConfig(batch_buckets=(rows,), max_batch_rows=rows,
                       pipeline_depth=depth, precompile=True)
    fcfg = _fcfg(str(tmp_path / "cold"), cap=cap, demote=demote,
                 every=every)
    reg = MetricsRegistry()
    return _build(fcfg, rt, reg), reg, fcfg, rt


def test_order_a_demoted_keys_are_known_before_the_next_host_prep(tmp_path):
    """(a) A pass's demoted keys are in the store's index, rows and all,
    when ``_maybe_compact`` returns — so before the host prep of any
    batch dispatched after that pass — however long the segment write
    (the durable copy) takes on its thread."""
    import time

    eng, reg, fcfg, rt = _small(tmp_path)
    log = _spy(eng)
    flush = eng._cold.flush_if_full
    eng._cold.flush_if_full = lambda: (time.sleep(0.1), flush())[1]
    compact, passes = eng._maybe_compact, []

    def spy_compact():
        n = len(log)
        compact()
        for kind, table, keys in log[n:]:
            assert kind == "demote"
            assert eng._cold.cold_mask(table, keys).all()
            found = eng._cold.read_rows(table, keys)[0]
            assert found.all()
            passes.append(keys.size)

    eng._maybe_compact = spy_compact
    batches = _churn(11, 16, 64, 768)
    sink, ctrl_sink = _Sink(), _Sink()
    stats = eng.run(_Source(batches), sink)
    assert sum(passes) > 0, "no pass demoted a key"
    _all_hot(fcfg, rt).run(_Source(batches), ctrl_sink)
    _assert_same_rows(sink.results, ctrl_sink.results)
    _assert_exact_counters(eng, reg, stats, aot=True)


def test_order_b_a_key_in_flight_is_never_demoted_under_it(tmp_path):
    """(b) Pipeline depth 2, days advancing every batch, a pass after
    every batch: the pass dispatched when batch N finishes runs behind
    batch N + 1's step, and never takes a key of N + 1 (its newest day
    is the pass's ``now_day``)."""
    eng, reg, fcfg, rt = _small(tmp_path)
    batches = _churn(13, 16, 64, 768)
    started = []
    start = eng._start_batch

    def spy_start(cols):
        handle = start(cols)
        started.append(handle["cols"])
        return handle

    eng._start_batch = spy_start
    log = []
    append = eng._cold.append

    def spy_append(table, keys, *rows, **kw):
        log.append((len(started), table, np.asarray(keys).reshape(-1)))
        return append(table, keys, *rows, **kw)

    eng._cold.append = spy_append
    sink, ctrl_sink = _Sink(), _Sink()
    stats = eng.run(_Source(batches), sink)
    _all_hot(fcfg, rt).run(_Source(batches), ctrl_sink)
    _assert_same_rows(sink.results, ctrl_sink.results)
    _assert_exact_counters(eng, reg, stats, aot=True)
    from real_time_fraud_detection_system_tpu.core.batch import fold_key

    assert log
    for n_started, table, keys in log:
        in_flight = started[n_started - 1]  # dispatched, not finished
        col = "customer_id" if table == "customer" else "terminal_id"
        assert not np.isin(fold_key(in_flight[col]), keys).any()


def test_order_c_a_key_demoted_twice_returns_with_its_newer_rows(tmp_path):
    """(c) Demoted, promoted, written, demoted again: the store's newest
    rows win and the second return is exact."""
    eng, reg, fcfg, rt = _small(tmp_path, cap=128, demote=64, every=1)
    log = _spy(eng)
    a = np.arange(60)

    def phase(keys, day, i):
        return _cols(keys, keys + 1_000_000, day, first_tx=i * 64)

    batches, i = [], 0
    for day, keys in ((0, a), (2, 1000 + a), (3, 2000 + a), (4, a),
                      (6, 3000 + a), (7, 4000 + a), (8, a), (9, a)):
        batches.append(phase(keys, DAY0 + day, i))
        i += 1
    sink, ctrl_sink = _Sink(), _Sink()
    stats = eng.run(_Source(batches), sink)
    _all_hot(fcfg, rt).run(_Source(batches), ctrl_sink)
    _assert_same_rows(sink.results, ctrl_sink.results)
    _assert_exact_counters(eng, reg, stats, aot=True)
    demoted = np.concatenate([k for kind, t, k in log
                              if kind == "demote" and t == "customer"])
    keys, times = np.unique(demoted, return_counts=True)
    assert (times[np.isin(keys, a)] >= 2).any(), "no key went cold twice"


def test_order_d_an_unadmitted_lane_fails_the_run_loudly(tmp_path):
    """(d) Promote lanes are unique keys, and a lane the free stack
    cannot admit is not served from the sketch in silence: the run stops
    with ``ColdPromoteError`` before that batch is delivered."""
    eng, reg, fcfg, rt = _small(tmp_path, depth=1, cap=64, demote=64,
                                rows=64)
    a = np.arange(60)
    batches = [
        _cols(a, a + 1_000_000, DAY0, first_tx=0),
        # day 3: a pass after this batch demotes the 60 down to 32 slots
        _cols(1000 + np.arange(8), 1_001_000 + np.arange(8), DAY0 + 3,
              first_tx=64),
        # 48 new keys fill the tier to the brim...
        _cols(2000 + np.arange(48), 1_002_000 + np.arange(48), DAY0 + 3,
              first_tx=128),
        # ...and the demoted come back: no slot for them
        _cols(a, a + 1_000_000, DAY0 + 3, first_tx=192),
    ]
    sink = _Sink()
    with pytest.raises(ColdPromoteError, match="cold_demote_slots"):
        eng.run(_Source(batches), sink)
    assert [r.batch_index for r in sink.results] == [1, 2, 3]


# -- the store: the batch read is the per-key read ---------------------------


def test_batch_read_equals_per_key_read(tmp_path):
    """``read_rows`` over keys spread across the flush buffer, several
    segments (resident and re-opened from disk), re-demotions and keys
    never stored equals one ``get_rows`` a key."""
    nb = 5
    rng = np.random.default_rng(3)
    d = str(tmp_path / "cold")
    cs = ColdStore(d, segment_mb=1e-3)  # ~1 KB: a segment every append

    def rows(n):
        return (rng.integers(0, 100, (n, nb)).astype(np.int32),
                rng.random((n, nb), dtype=np.float32),
                rng.random((n, nb), dtype=np.float32),
                rng.random((n, nb), dtype=np.float32))

    for lo in (0, 40, 80, 20):  # the last one re-demotes 20..59
        keys = np.arange(lo, lo + 40, dtype=np.uint32)
        keys[3] = EMPTY
        cs.append("customer", keys, *rows(40))
    cs._segment_bytes = 1 << 30
    cs.append("customer", np.arange(200, 210, dtype=np.uint32), *rows(10))
    cs.mark_promoted("customer", [5, 6, 205])
    probe = rng.permutation(np.arange(0, 260, dtype=np.uint32))
    for store in (cs, ColdStore(d)):  # the second: flushed segments only
        found, bd, cnt, amt, frd = store.read_rows("customer", probe)
        per_key = [store.get_rows("customer", [k]).get(int(k))
                   for k in probe]
        assert found.tolist() == [r is not None for r in per_key]
        assert found.sum() > 100 and not found.all()
        for j, i in enumerate(np.flatnonzero(found)):
            for got, want in zip((bd, cnt, amt, frd), per_key[i]):
                np.testing.assert_array_equal(got[j], want)
        assert store.cold_mask("customer", probe).tolist() == found.tolist()
        assert not store.read_rows("terminal", probe)[0].any()


@pytest.mark.parametrize("live", [60, 7], ids=["dense-view", "sparse-copy"])
def test_a_sorted_payload_lands_as_any_other(tmp_path, live):
    """The pass's payload comes key-sorted with its padding last and is
    landed as it stands (a view where most lanes are live, a copy where
    that would pin mostly padding): the store answers what it answers
    for the same lanes handed over in any order, before and after the
    segment write."""
    nb, k = 5, 64
    rng = np.random.default_rng(live)
    keys = np.full(k, EMPTY, np.uint32)
    keys[:live] = np.sort(rng.choice(10_000, live, replace=False))
    rows = (rng.integers(0, 100, (k, nb)).astype(np.int32),
            rng.random((k, nb), dtype=np.float32),
            rng.random((k, nb), dtype=np.float32),
            rng.random((k, nb), dtype=np.float32))
    shuffle = rng.permutation(k)
    a = ColdStore(str(tmp_path / "sorted"), segment_mb=1 << 10)
    b = ColdStore(str(tmp_path / "shuffled"), segment_mb=1 << 10)
    assert a.append("customer", keys, *rows) == live
    assert b.append("customer", keys[shuffle],
                    *(r[shuffle] for r in rows)) == live
    held = a._data[-1]["customer"][0]
    assert (held.base is rows[0]) == (live == 60)  # view, or its own copy
    probe = np.append(keys[:live], np.uint32(10_001))
    for gone in range(2):  # then: its first key promoted, and flushed
        got, want = a.read_rows("customer", probe), b.read_rows(
            "customer", probe)
        assert got[0].tolist() == [True] * (live - gone) + [False]
        for g, w, r in zip(got[1:], want[1:], rows):
            np.testing.assert_array_equal(g, w)
            np.testing.assert_array_equal(g, r[gone:live])
        a.mark_promoted("customer", probe[:1])
        b.mark_promoted("customer", probe[:1])
        probe = probe[1:]
        a.flush(), b.flush()


def test_the_pass_hands_its_lanes_over_in_key_order(tmp_path):
    """Every demotion payload a run lands: live keys ascending, padding
    (``EMPTY_KEY``) behind them — what lets the store skip the gather."""
    eng, reg, fcfg, rt = _small(tmp_path)
    seen = []
    append = eng._cold.append

    def spy_append(table, keys, *rows, **kw):
        seen.append(np.asarray(keys).reshape(-1).copy())
        return append(table, keys, *rows, **kw)

    eng._cold.append = spy_append
    eng.run(_Source(_churn(17, 12, 64, 768)), _Sink())
    assert seen
    for keys in seen:
        n = int((keys != EMPTY).sum())
        assert n and (keys[n:] == EMPTY).all()
        assert (np.diff(keys[:n].astype(np.int64)) > 0).all()


def test_fresh_store_is_empty_private_and_gone(tmp_path, monkeypatch):
    """``tmp://name``: a new directory under the system's temporary
    directory for every store, removed at close; refused with a
    checkpointer (its lineage could not outlive the process)."""
    import os
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    a, b = ColdStore("tmp://unit"), ColdStore("tmp://unit")
    assert a.ephemeral and a.path != b.path
    assert os.path.dirname(a.path) == str(tmp_path)
    assert os.path.basename(a.path).startswith("unit-")
    a.append("customer", [1], np.zeros((1, 2), np.int32),
             *(np.zeros((1, 2), np.float32),) * 3)
    a.flush()
    assert os.listdir(a.path) and not os.listdir(b.path)
    a.close()
    b.close()
    assert not os.path.exists(a.path) and not os.path.exists(b.path)
    assert not ColdStore(str(tmp_path / "durable")).ephemeral
    with pytest.raises(ValueError, match="bare name"):
        ColdStore("tmp://a/b")

    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        Checkpointer,
    )

    eng, _reg, _f, _rt = _small(tmp_path)
    eng2 = _build(dict(_f, cold_store="tmp://"), _rt, MetricsRegistry())
    with pytest.raises(ValueError, match="fresh store"):
        eng2.run(_Source([]), _Sink(),
                 checkpointer=Checkpointer(str(tmp_path / "ck")))
    eng2._cold.close()
