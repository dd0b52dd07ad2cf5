"""The wide-id cell ``forest-id64.saturate``: it resolves to its files, its
configuration is ``forest-rf100-d8-exact`` but for the key width, the ids'
range, the forest's stated population and what follows from them, its
traffic is ``saturate-arriving`` through another generator, that
generator draws distinct seeded ids of the stated range whose equal-fold
pairs follow the law n^2 / 2^33, and a rehearsal on the CPU with PLANTED
equal-fold pairs ends ``correct`` with every metric the cell brings on a
traced line. The same rehearsal at ``key_bits=32`` folds the planted pairs
into one key each and is not ``correct``: the check catches the merge."""

import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.tests.test_exact_cell import NEW as EXACT_NEW
from benchmark.tests.test_exact_cell import install_program_trace

ROOT = harness.ROOT
CELL, TWIN = "forest-id64.saturate", "forest-exact.saturate"
NEW = ["wide_id_rows_pct.sat", "keydir_fold_alias_rows.sat",
       "keydir_alias_trips.sat"]
# 8,192 active ids a table at the cell's own ranges alias by the law
# 8192^2 / 2^33 = 0.008 pairs: the toy PLANTS 1,024 a table (the
# generator's planted_fold_pairs, a key no cell's file sets) where the
# real cell has ~2,560 among 6.3 M keys
PLANTED = 1024
TOY = {
    "config": {
        "features": {"customer_capacity": 16384, "terminal_capacity": 16384,
                     "compact_every": 42},
        "planted_fold_pairs": {"customers": PLANTED, "terminals": PLANTED},
        "active_keys": {"customers": 8192, "terminals": 8192},
        "runtime": {"precompile": True, "batch_buckets": [256, 512],
                    "max_batch_rows": 512},
        "model_params": {"fit_rows": 512, "nominal_rows_per_day": 512,
                         "key_population": {"customers": 32768,
                                            "terminals": 32768}},
    },
    "traffic": {
        "fill_batches": 40, "fill_batch_rows": 512, "pool_envelopes": 4096,
        "draw_rows": 131072, "max_poll_rows": 512,
        "check_window_rows": 1 << 20,
    },
}
SEED = 4_100_000_123


def test_the_cell_resolves_to_its_files_and_only_the_width_differs():
    manifest = harness.load_manifest()
    cell = harness.Cell(ROOT, manifest, CELL)
    twin = harness.Cell(ROOT, manifest, TWIN)
    assert cell.chips == 1 and cell.regime == "sat"
    assert [m["name"] for m in cell.end_to_end()] == ["rows_per_s",
                                                      "setup_s"]
    assert cell.entry["traffic"] == "saturate-arriving-id64"
    # the traffic: saturate-arriving but for the generator
    assert cell.traffic.pop("generator") == "debezium_cards_id64"
    assert twin.traffic.pop("generator") == "debezium_cards_active"
    assert cell.traffic["derived_from"].pop("cell") == CELL
    assert twin.traffic["derived_from"].pop("cell") == TWIN
    assert cell.traffic == twin.traffic
    # the configuration: forest-rf100-d8-exact but for the keys named
    wide, ex = dict(cell.config), dict(twin.config)
    differ = {"source", "deployment", "features", "state_bytes",
              "state_bytes_by_tier", "key_universe", "key_floor", "model",
              "model_params", "assumed", "exactness_here"}
    assert {k for k in wide if wide[k] != ex.get(k)} == differ
    assert set(ex) <= set(wide)
    for key in ("limits", "guarantees", "runtime", "ingest", "reduced",
                "chips", "active_keys"):
        assert wide[key] == ex[key], key
    fw = dict(wide["features"])
    assert fw.pop("key_bits") == 64 and fw == ex["features"]
    mw = dict(wide["model_params"])
    assert mw.pop("key_population") == ex["key_universe"]
    assert mw == ex["model_params"]
    assert (wide["model"], ex["model"]) == ("forest_active", "forest")
    assert wide["key_floor"] == {"customers": 10 ** 15,
                                 "terminals": 1 << 32}
    assert wide["key_universe"] == {"customers": 10 ** 16,
                                    "terminals": 1 << 63}
    assert wide["exactness_here"].startswith(ex["exactness_here"])
    from real_time_fraud_detection_system_tpu.config import FeatureConfig
    from real_time_fraud_detection_system_tpu.features.online import (
        state_bytes,
    )

    by_tier = state_bytes(FeatureConfig(**dict(
        wide["features"], windows=tuple(fw["windows"]))))
    assert wide["state_bytes"] == by_tier.pop("total") == 8_610_906_440
    assert wide["state_bytes_by_tier"] == by_tier
    # 8 bytes more a directory entry than the 32-bit deployment's
    assert wide["state_bytes"] - ex["state_bytes"] == 8 * 2 * (
        fw["customer_capacity"] + fw["terminal_capacity"])
    entry = {c["name"]: c for c in manifest["configs"]}[
        cell.entry["config"]]
    assert entry["source"] == wide["source"] and len(entry["source"]) <= 200
    assert "7812" in entry["source"] and entry["reduced"] == []
    # every .sat metric of the exact cell, and the three the width adds
    mine = {m["name"]: m for m in cell.per_layer()}
    theirs = {m["name"] for m in twin.per_layer()}
    assert theirs <= set(mine) and set(mine) - theirs == set(NEW)
    assert all(mine[n]["workloads"] == [CELL] for n in NEW)
    assert all(mine[n]["reader"] == "registry_ratio" for n in NEW)


def traffic_of(seed, seconds=1.0, over=TOY):
    cell = harness.Cell(ROOT, harness.load_manifest(), CELL, over)
    gen = cell.plugin("generators", cell.traffic["generator"])
    return gen.build(cell.traffic, cell.config, seed, seconds, None), cell


def test_ids_are_distinct_in_range_seeded_and_alias_by_the_law():
    t, cell = traffic_of(SEED)
    again, _ = traffic_of(SEED)
    other, _ = traffic_of(SEED + 1)
    floor, uni = cell.config["key_floor"], cell.config["key_universe"]
    for table in ("customer", "terminal"):
        ids = getattr(t, f"active_{table}_ids")
        assert ids.dtype == np.int64
        assert len(np.unique(ids)) == len(ids) == 8192
        assert ids.min() >= floor[table + "s"] >= 1 << 32
        assert ids.max() < uni[table + "s"]
        drawn = np.concatenate([getattr(t, f"fill_{table}"),
                                getattr(t, f"win_{table}")])
        assert np.isin(drawn, ids).all()
        np.testing.assert_array_equal(
            getattr(t, f"win_{table}"), getattr(again, f"win_{table}"))
        assert not np.array_equal(ids, getattr(other, f"active_{table}_ids"))
    gen = cell.plugin("generators", "debezium_cards_id64")
    # the law at the real width: n uniform ids of [10^15, 10^16) fold to
    # uniform 32-bit words, so n^2 / 2^33 pairs alias (+- 4 sigma)
    rng = np.random.default_rng(7)
    n = 1 << 19
    ids = gen.sample_wide_ids(rng, 10 ** 15, 10 ** 16, n)
    want = n * (n - 1) / 2 / 2 ** 32
    assert abs(gen.fold_alias_pairs(ids) - want) < 4 * np.sqrt(want)
    ids = gen.sample_wide_ids(rng, 1 << 32, 1 << 63, n)
    assert abs(gen.fold_alias_pairs(ids) - want) < 4 * np.sqrt(want)
    # the count is of pairs: three ids of one fold are three pairs
    assert gen.fold_alias_pairs(np.asarray(
        [1, 1 << 32, (5 << 32) | 4, 7], np.int64)) == 3
    # rejection ends with every id of a full range drawn once
    assert sorted(gen.sample_wide_ids(rng, 5, 69, 64).tolist()) == list(
        range(5, 69))
    with pytest.raises(ValueError):
        gen.sample_wide_ids(rng, 0, 10, 11)
    # the toy plants the pairs the rehearsal needs, and counts them
    assert t.fold_alias_pairs == {"customers": PLANTED,
                                  "terminals": PLANTED}
    unplanted, _ = traffic_of(SEED, over=harness.merge(TOY, {"config": {
        "planted_fold_pairs": {"customers": 0, "terminals": 0}}}))
    assert unplanted.fold_alias_pairs == {"customers": 0, "terminals": 0}
    # the pool is encoded at the ids' width: 16-digit and 10-digit ids
    assert t.envelope_bytes > 361 + 16
    look = t.lookup(np.arange(0, t.n_fill, 97))
    np.testing.assert_array_equal(look["customer_id"],
                                  t.fill_customer[::97])


def test_the_forest_is_the_exact_cells_for_a_seed():
    manifest = harness.load_manifest()
    wide = harness.Cell(ROOT, manifest, CELL, {"config": {
        "model_params": {"fit_rows": 512}}})
    ex = harness.Cell(ROOT, manifest, TWIN, {"config": {
        "model_params": {"fit_rows": 512}}})
    a = wide.plugin("models", wide.config["model"]).build(wide.config, 11)
    b = ex.plugin("models", ex.config["model"]).build(ex.config, 11)
    import jax

    for x, y in zip(jax.tree.leaves(a["params"]),
                    jax.tree.leaves(b["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(a["scaler"].mean, b["scaler"].mean)


def rehearse(monkeypatch, trace, key_bits=64, seconds=3.0):
    seen = {}
    over = harness.merge(
        TOY, {"config": {"features": {"key_bits": key_bits}}})
    if trace:
        install_program_trace(monkeypatch, seen)
    result = harness.run_cell(
        CELL, SEED, seconds, trace, time.perf_counter(), allow_cpu=True,
        overrides=over,
        sabotage=lambda engine, sink: seen.update(engine=engine, sink=sink))
    return result, seen


def test_rehearsal_with_planted_pairs_is_correct_with_the_cells_metrics(
        monkeypatch):
    result, seen = rehearse(monkeypatch, trace=True)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    line = result["metrics"]
    # what the exact cell brought and what the width adds (the metrics
    # of the host loop's idle gaps need a chip's trace)
    for name in EXACT_NEW + NEW + ["keydir_claim_rounds.sat"]:
        assert name in line, (name, sorted(line))
    value = {n: line[n]["value"] for n in line}
    assert value["wide_id_rows_pct.sat"] == 100.0  # every id is >= 2^32
    assert value["keydir_fold_alias_rows.sat"] > 0  # the path is exercised
    assert value["keydir_alias_trips.sat"] > 2.0  # second trips were run
    assert value["tier_cms_rows.sat"] == 0.0  # every active key owns a slot
    assert value["recompiles.sat"] == 0.0
    assert value["compactions.sat"] >= 1
    assert min(value[f"step_keydir_{p}_ms.sat"]
               for p in ("lookup", "claim", "grant")) > 0
    rows = {c["name"]: c["value"] for c in result["checks"]}
    assert rows["exact_columns_wrong"] == 0 and rows["draw_wraps"] == 0
    assert rows["rows_compared"] >= result["attempted"]
    # the planted pairs were met: rows of ids that fold alike were served
    eng = seen["engine"]
    assert eng.metrics.get("rtfds_keydir_alias_rows_total").value > 0


def test_the_same_rehearsal_at_32_bits_merges_the_pairs_and_is_not_correct(
        monkeypatch):
    result, seen = rehearse(monkeypatch, trace=False, key_bits=32,
                            seconds=1.0)
    assert result["correct"] is False
    assert "exact_columns_wrong" in {
        c["name"] for c in result["checks"] if not c["ok"]}
    # and the program said what it was doing to them
    wide = seen["engine"].metrics.get("rtfds_wide_id_rows_total")
    assert wide is not None and wide.value > 0
