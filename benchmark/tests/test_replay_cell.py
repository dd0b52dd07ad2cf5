"""The replay cell ``forest-replay.saturate``: it resolves to its files, its
configuration is ``forest-rf100-d8-exact`` but for the ids' scale and churn
and the clock, its traffic is ``saturate-arriving`` through another
generator under a moving event day, that generator stamps rows by their
place in the replay and issues ids in ascending order so that an id keeps
its rank for its whole life, and a rehearsal on the CPU over more event
days than the ring has buckets ends ``correct`` with every metric the cell
brings on a traced line — windows that expire, buckets that are reused,
batches that hold two days, passes that reclaim. With the query's age mask
taken out the same rehearsal is not ``correct``: the check catches it."""

import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.tests.test_exact_cell import NEW as EXACT_NEW
from benchmark.tests.test_exact_cell import install_program_trace

ROOT = harness.ROOT
CELL, TWIN = "forest-replay.saturate", "forest-exact.saturate"
US_PER_DAY = 86_400_000_000
NEW = ["event_days.sat", "multi_day_batches_pct.sat",
       "slots_reclaimed_per_day.sat"]
# 40 fill days of 512 rows, then 700 rows an event day in polls of 512:
# two polls in three hold two days; 4,096 ids a table in use on a day,
# 64 issued and retired a day, in a universe of 8 x the 16,384 slots; a
# pass every 16 batches (~12 event days)
TOY = {
    "config": {
        "features": {"customer_capacity": 16384, "terminal_capacity": 16384,
                     "compact_every": 16},
        "key_universe": {"customers": 131072, "terminals": 131072},
        "active_keys": {"customers": 4096, "terminals": 4096},
        "issued_per_event_day": {"customers": 64, "terminals": 64},
        "runtime": {"precompile": True, "batch_buckets": [256, 512],
                    "max_batch_rows": 512},
        # ten shallow trees: the CPU then steps ~4 x as often, and the
        # clock moves by the batch
        "model_params": {"fit_rows": 512, "nominal_rows_per_day": 512,
                         "n_estimators": 10, "max_depth": 5},
    },
    "traffic": {
        "fill_batches": 40, "fill_batch_rows": 512, "pool_envelopes": 4096,
        "draw_rows": 131072, "max_poll_rows": 512, "rows_per_event_day": 700,
        "check_window_rows": 1 << 20,
    },
}
SEED = 5_000_000_123


def test_the_cell_resolves_to_its_files_and_only_scale_and_clock_differ():
    manifest = harness.load_manifest()
    cell = harness.Cell(ROOT, manifest, CELL)
    twin = harness.Cell(ROOT, manifest, TWIN)
    assert cell.chips == 1 and cell.regime == "sat"
    assert [m["name"] for m in cell.end_to_end()] == ["rows_per_s",
                                                      "setup_s"]
    assert cell.entry["traffic"] == "saturate-replay"
    # the traffic: saturate-arriving but for the generator, the clock and
    # a draw that outlasts a faster window
    mine, theirs = dict(cell.traffic), dict(twin.traffic)
    assert mine.pop("generator") == "debezium_cards_replay"
    assert theirs.pop("generator") == "debezium_cards_active"
    assert mine.pop("start_utc") == "2025-06-11T00:00:00"  # midnight
    assert theirs.pop("start_utc") == "2025-06-11T10:00:00"
    rows_a_day = mine.pop("rows_per_event_day")
    assert rows_a_day == 250_000 and rows_a_day % mine["max_poll_rows"]
    assert mine.pop("draw_rows") == 2 * theirs.pop("draw_rows")
    assert mine.pop("why") != theirs.pop("why")
    assert mine["derived_from"].pop("cell") == CELL
    assert theirs["derived_from"].pop("cell") == TWIN
    for key in ("rule", "run_seconds", "headroom"):
        assert mine["derived_from"][key] == theirs["derived_from"][key], key
    mine.pop("derived_from"), theirs.pop("derived_from")
    assert mine == theirs
    # the configuration: forest-rf100-d8-exact but for the keys named
    rep, ex = dict(cell.config), dict(twin.config)
    differ = {"source", "deployment", "key_universe", "reduced", "assumed"}
    assert {k for k in ex if rep.get(k) != ex[k]} == differ
    assert set(rep) - set(ex) == {"issued_per_event_day",
                                  "rows_per_event_day"}
    slots = {"customers": rep["features"]["customer_capacity"],
             "terminals": rep["features"]["terminal_capacity"]}
    assert rep["key_universe"] == {k: 8 * v for k, v in slots.items()}
    assert all(v < 1 << 32 for v in rep["key_universe"].values())
    assert rep["active_keys"] == {k: v // 2 for k, v in slots.items()}
    assert rep["issued_per_event_day"] == {"customers": 8192,
                                           "terminals": 16384}
    entry = {c["name"]: c for c in manifest["configs"]}[
        cell.entry["config"]]
    assert entry["reduced"] == rep["reduced"] == ["rows_per_event_day"]
    assert rep["rows_per_event_day"]["here"] == rows_a_day
    assert entry["source"] == rep["source"] and len(rep["source"]) <= 200
    assert "load_initial_data" in rep["source"]
    # every .sat metric of the exact cell, and three of its own
    mine = {m["name"]: m for m in cell.per_layer()}
    theirs = {m["name"]: m for m in twin.per_layer()}
    assert set(mine) - set(theirs) == set(NEW)
    assert set(theirs) <= set(mine) and set(EXACT_NEW) <= set(mine)
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["reader"] in ("registry", "registry_ratio")


def traffic_of(seed, seconds=1.0, over=None):
    cell = harness.Cell(ROOT, harness.load_manifest(), CELL,
                        harness.merge(TOY, over))
    gen = cell.plugin("generators", cell.traffic["generator"])
    return gen.build(cell.traffic, cell.config, seed, seconds, None), cell


def test_event_time_is_the_rows_place_and_ids_are_issued_in_order():
    t, cell = traffic_of(SEED)
    again, _ = traffic_of(SEED)
    other, _ = traffic_of(SEED + 1)
    r = cell.traffic["rows_per_event_day"]
    k = np.arange(t.draw_rows)
    us = t.event_us(k)
    day = (us - t.start_us) // US_PER_DAY
    np.testing.assert_array_equal(day, k // r)
    assert (np.diff(us) > 0).all()  # ascending, as a table in tx_id order
    tod = (us - t.start_us) % US_PER_DAY
    assert tod[0] == 0 and tod[r - 1] == (r - 1) * US_PER_DAY // r
    assert t.start_us % US_PER_DAY == 0
    # weekends and nights come as the calendar has them
    assert {int((d + t.start_us // US_PER_DAY + 3) % 7 >= 5)
            for d in day[::r].tolist()} == {0, 1}
    assert 0.25 < (tod // 3_600_000_000 <= 6).mean() < 0.33
    for table in ("customer", "terminal"):
        a = cell.config["active_keys"][table + "s"]
        per_day = cell.config["issued_per_event_day"][table + "s"]
        ids = getattr(t, f"active_{table}_ids")
        assert (np.diff(ids) > 0).all()  # ascending, distinct
        assert ids[0] >= 0 and ids[-1] < cell.config["key_universe"][
            table + "s"]
        assert len(ids) == a + per_day * (
            t.fill_batches + -(-t.draw_rows // r))
        win = getattr(t, f"win_{table}")
        fill = getattr(t, f"fill_{table}")
        for part in ("fill", "win"):
            np.testing.assert_array_equal(
                getattr(t, f"{part}_{table}"),
                getattr(again, f"{part}_{table}"))
        assert not np.array_equal(win, getattr(other, f"win_{table}"))
        # the ids in use on day d are the sample's positions
        # [per_day x (d + 40), + active_keys): serial issue, the oldest
        # retire for good
        pos = np.searchsorted(ids, win)
        np.testing.assert_array_equal(ids[pos], win)
        off = per_day * (day + t.fill_batches)
        assert (pos >= off).all() and (pos < off + a).all()
        fill_day = np.arange(t.n_fill) // t.fill_batch_rows
        fpos = np.searchsorted(ids, fill)
        assert (fpos >= per_day * fill_day).all()
        assert (fpos < per_day * fill_day + a).all()
        # an id keeps its rank for its whole life: its share of a day's
        # rows is the same on every day it is in use. The busiest residue
        # of the window (mod active_keys) is one id while it lives and
        # the id issued in its place after it
        top = np.bincount(pos % a, minlength=a).argmax()
        held = pos[pos % a == top]
        assert (np.diff(held) >= 0).all() and len(np.unique(held)) >= 2
        assert set(np.diff(np.unique(held)).tolist()) == {a}
    # the lookup the harness and the reference call
    look = t.lookup(np.arange(0, t.n_fill, 97))
    np.testing.assert_array_equal(look["customer_id"],
                                  t.fill_customer[::97])
    gen = cell.plugin("generators", "debezium_cards_replay")
    with pytest.raises(ValueError):
        gen.ascending_ids(np.random.default_rng(0), 10, 11)
    with pytest.raises(ValueError):
        traffic_of(SEED, over={"traffic": {"arrivals": "poisson",
                                           "rate_rows_per_s": 1000}})


def test_a_window_is_stamped_by_row_and_counts_its_draw():
    def decode(msgs, ts):
        from real_time_fraud_detection_system_tpu.core.envelope import (
            decode_transaction_envelopes,
        )

        return decode_transaction_envelopes(msgs, ts)

    cell = harness.Cell(ROOT, harness.load_manifest(), CELL, harness.merge(
        TOY, {"traffic": {"draw_rows": 2048}}))
    gen = cell.plugin("generators", cell.traffic["generator"])
    t = gen.build(cell.traffic, cell.config, SEED, 60.0, decode)
    w = t.window_source()
    polls = [w.poll_batch() for _ in range(5)]  # 2,560 rows of a 2,048 draw
    r = cell.traffic["rows_per_event_day"]
    ids = np.concatenate([c["tx_id"] for c in polls])
    us = np.concatenate([c["tx_datetime_us"] for c in polls])
    np.testing.assert_array_equal(ids, t.n_fill + np.arange(2560))
    np.testing.assert_array_equal(us, t.event_us(np.arange(2560)))
    # Kafka time is the poll's wall time: one stamp a poll, at start_utc
    for c in polls:
        assert len(set(c["kafka_ts_ms"].tolist())) == 1
        assert 0 <= c["kafka_ts_ms"][0] - t.start_us // 1000 < 60_000
    look = t.lookup(ids)
    np.testing.assert_array_equal(look["tx_datetime_us"], us)
    np.testing.assert_array_equal(
        look["customer_id"],
        np.concatenate([c["customer_id"] for c in polls]))
    stats = t.replay_stats()
    assert stats["event_days_spanned"] == 2559 // r + 1
    assert stats["multi_day_polls"] == sum(
        1 for s in range(0, 2560, 512) if s // r != (s + 511) // r)
    # the draw rule's count: the window began its draw again once
    assert t.draw_stats() == {"rows_polled": 2560, "draw_rows": 2048,
                              "draw_wraps": 1}
    assert cell.traffic["limits"] == {"draw_wraps": 0}


def rehearse(monkeypatch, trace, seconds=3.0, sabotage=None):
    seen = {}
    if trace:
        install_program_trace(monkeypatch, seen)

    def note(engine, sink):
        seen.update(engine=engine, sink=sink)
        if sabotage is not None:
            sabotage()

    result = harness.run_cell(
        CELL, SEED, seconds, trace, time.perf_counter(), allow_cpu=True,
        overrides=TOY, sabotage=note)
    return result, seen


def test_rehearsal_is_correct_over_more_days_than_the_ring_has_buckets(
        monkeypatch):
    result, seen = rehearse(monkeypatch, trace=True)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    line = result["metrics"]
    for name in NEW + EXACT_NEW:
        assert name in line, (name, sorted(line))
    value = {n: line[n]["value"] for n in NEW + EXACT_NEW}
    r = TOY["traffic"]["rows_per_event_day"]
    # the fill ends on the eve of day 0: the clock moved by the window's
    # days, the generator's event_days_spanned
    days = (result["attempted"] - 1) // r + 1
    assert value["event_days.sat"] == days > 40  # the ring turned over
    assert 50.0 < value["multi_day_batches_pct.sat"] < 80.0
    assert value["compactions.sat"] >= 3
    assert value["tier_cms_rows.sat"] == 0.0  # every active key owns a slot
    assert line["recompiles.sat"]["value"] == 0.0
    # every pass gives back what was issued ~37 event days before it: 64
    # ids a table and day, those that were never touched apart
    assert 64 < value["slots_reclaimed_per_day.sat"] <= 128
    assert value["slots_reclaimed.sat"] * value["compactions.sat"] \
        == pytest.approx(value["slots_reclaimed_per_day.sat"] * days)
    # every window row was compared: expired windows, reused buckets and
    # keys that came back from a reclaim answered as the reference does
    rows = {c["name"]: c["value"] for c in result["checks"]}
    assert rows["rows_compared"] >= result["attempted"]
    assert rows["draw_wraps"] == 0


def test_without_the_age_mask_the_rehearsal_is_not_correct(monkeypatch):
    from real_time_fraud_detection_system_tpu.ops import windows

    inner = windows.query_gathered

    def no_age_mask(bucket_day, count, amount, fraud, day, ws, delay=0):
        # every window as wide as the ring: nothing ever expires
        return inner(bucket_day, count, amount, fraud, day,
                     [1 << 20 for _ in ws], delay)

    result, _ = rehearse(
        monkeypatch, trace=False, seconds=1.0,
        sabotage=lambda: monkeypatch.setattr(windows, "query_gathered",
                                             no_age_mask))
    assert result["correct"] is False
    assert "exact_columns_wrong" in {
        c["name"] for c in result["checks"] if not c["ok"]}
