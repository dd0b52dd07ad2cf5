"""The cell ``forest-cold-replay.saturate``: it resolves to its files — found
in the manifest by name, wherever later entries put them — its
configuration is ``forest-rf100-d8-cold`` key for key but for the ids'
scale and churn, the clock's cut and the tier's three sizes, its traffic is
``saturate-replay`` with a draw sized by the file's own rule, and a
rehearsal on the CPU over more event days than the ring has buckets ends
``correct`` with every metric the cell brings on a traced line: keys
demoted, promoted days later, expired in the store, returned dead. With the
promotion taken out the same rehearsal is not ``correct``."""

import os
import time

from benchmark import harness
from benchmark.tests.test_exact_cell import install_program_trace

ROOT = harness.ROOT
CELL, CONFIG = "forest-cold-replay.saturate", "forest-rf100-d8-cold-replay"
COLD, REPLAY = "forest-cold.saturate", "forest-replay.saturate"
NEW = ["cold_expire_ms.sat", "cold_expired_keys.sat", "cold_dead_returns.sat",
       "cold_demote_age_days.sat", "cold_store_keys_end.sat"]
# 40 fill days of 512 rows, then 700 rows an event day in polls of 512;
# 8,192 ids a table in use on a day = the slots, 32 issued and retired a
# day, in a universe of 8 x the slots; the hot tier kept at 0.35 of the
# slots by a pass every 2 batches that demotes up to 2,048 keys a table
TOY = {
    "config": {
        "features": {"customer_capacity": 8192, "terminal_capacity": 8192,
                     "compact_every": 2, "cold_demote_slots": 2048},
        "key_universe": {"customers": 65536, "terminals": 65536},
        "active_keys": {"customers": 8192, "terminals": 8192},
        "issued_per_event_day": {"customers": 32, "terminals": 32},
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
SEED = 5_300_000_123


def by_name(entries):
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    return dict(zip(names, entries))


def test_the_manifest_names_the_cell_its_configuration_and_its_metrics():
    manifest = harness.load_manifest()
    entry = by_name(manifest["workloads"])[CELL]
    assert entry["config"] == CONFIG and entry["chips"] == 1
    assert entry["traffic"] == "saturate-replay-cold"
    config = by_name(manifest["configs"])[CONFIG]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["reduced"] == ["rows_per_event_day"]
    assert len(config["source"]) <= 200 and len(entry["why"]) <= 200
    # one cell on this configuration, one configuration in this file
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == CONFIG] == [CELL]
    metrics = by_name(manifest["per_layer"])
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL], name
        assert metrics[name]["layer"] == "cold tier"
        assert metrics[name]["moves"] == "rows_per_s"
    assert CELL in by_name(manifest["end_to_end"])["rows_per_s"]["workloads"]
    # whatever the cold cell or the replay cell reports, this cell reports
    cell = harness.Cell(ROOT, manifest, CELL)
    mine = {m["name"]: m for m in cell.per_layer()}
    cold = {m["name"] for m in harness.Cell(ROOT, manifest, COLD).per_layer()}
    replay = {m["name"] for m in
              harness.Cell(ROOT, manifest, REPLAY).per_layer()}
    assert set(mine) == cold | replay | set(NEW)
    # a reader that the benchmark had, or the one that reads a gauge
    for name in NEW:
        spec = harness.load_json(os.path.join(
            ROOT, "benchmark", "metrics", name + ".json"))
        assert spec["regime"] == "sat" and spec["reader"] in (
            "registry", "registry_ratio", "registry_end"), name
    assert [m["name"] for m in cell.end_to_end()] == ["rows_per_s",
                                                      "setup_s"]


def test_the_configuration_is_the_cold_one_under_the_replays_calendar():
    manifest = harness.load_manifest()
    cell = harness.Cell(ROOT, manifest, CELL)
    cold = harness.Cell(ROOT, manifest, COLD)
    replay = harness.Cell(ROOT, manifest, REPLAY)
    mine, theirs = dict(cell.config), dict(cold.config)
    fm, ft = dict(mine.pop("features")), dict(theirs.pop("features"))
    # the three sizes, set by the rules the file's `assumed` states
    sizes = {k: fm.pop(k) for k in ("cold_highwater", "compact_every",
                                    "cold_demote_slots")}
    was = {k: ft.pop(k) for k in sizes}
    assert was == {"cold_highwater": 0.2, "compact_every": 4,
                   "cold_demote_slots": 131072}
    assert 0.2 < sizes["cold_highwater"] < 0.5
    slots = {"customers": fm["customer_capacity"],
             "terminals": fm["terminal_capacity"]}
    # (a) occupancy between two passes stays under half the slots
    admitted = 36_000  # a batch, returning + new: the generator's count
    assert sizes["cold_highwater"] + sizes["compact_every"] * admitted \
        / slots["customers"] < 0.5
    # (b) a pass can take what the batches before it admitted
    assert sizes["cold_demote_slots"] / sizes["compact_every"] >= admitted
    assert fm == ft  # windows, delay, buckets, slots, probes, the store
    assert fm["cold_store"].startswith("tmp://")
    differ = {"source", "deployment", "key_universe", "reduced", "assumed"}
    assert {k for k in theirs if mine.get(k) != theirs[k]} == differ
    assert set(mine) - set(theirs) == {"issued_per_event_day",
                                       "rows_per_event_day"}
    for said in ("guarantees", "limits", "exactness_here", "model",
                 "model_params", "runtime", "ingest", "state_bytes",
                 "state_bytes_by_tier", "chips"):
        assert mine[said] == theirs[said], said
    assert mine["active_keys"] == slots == theirs["active_keys"]
    # the replay's population law at twice its active set
    rep = replay.config
    assert mine["key_universe"] == rep["key_universe"] == {
        k: 8 * v for k, v in slots.items()}
    assert mine["issued_per_event_day"] == {
        k: 2 * v for k, v in rep["issued_per_event_day"].items()}
    assert {k: v / mine["active_keys"][k] for k, v in
            mine["issued_per_event_day"].items()} == {
        k: v / rep["active_keys"][k] for k, v in
        rep["issued_per_event_day"].items()}
    assert mine["reduced"] == rep["reduced"] == ["rows_per_event_day"]
    assert mine["rows_per_event_day"]["here"] == cell.traffic[
        "rows_per_event_day"] == 250_000
    assert mine["source"] == by_name(manifest["configs"])[CONFIG]["source"]
    for part in ("model_training.ipynb cell 59", "load_initial_data",
                 "SERIAL", "Cold tier"):
        assert part in mine["source"], part
    said = " ".join(mine["assumed"])
    for word in ("cold_highwater", "compact_every", "cold_demote_slots",
                 "horizon"):
        assert word in said, word


def test_the_traffic_is_the_replays_with_a_draw_by_the_files_own_rule():
    manifest = harness.load_manifest()
    mine = dict(harness.Cell(ROOT, manifest, CELL).traffic)
    theirs = dict(harness.Cell(ROOT, manifest, REPLAY).traffic)
    d, was = mine.pop("derived_from"), theirs.pop("derived_from")
    assert mine.pop("why") != theirs.pop("why")
    assert (mine.pop("draw_rows"), theirs.pop("draw_rows")) == (1 << 24,
                                                                1 << 25)
    assert mine == theirs  # generator, clock, fill, laws, polls, limits
    assert mine["limits"] == {"draw_wraps": 0} and mine["late_share"] == 0
    assert d["rule"] == was["rule"] and d["cell"] == CELL
    assert (d["headroom"], d["run_seconds"]) == (1.5, 20)
    need = d["headroom"] * d["rows_per_s"] * d["run_seconds"]
    assert d["rows_needed"] == int(need)
    assert (1 << 24) // 2 < need <= 1 << 24  # rounded UP to a power of two
    assert d["holds_until_rows_per_s"] == (1 << 24) // d["run_seconds"]
    assert "chip" in d["rows_per_s_source"]


def rehearse(monkeypatch, trace, sabotage=None, seconds=3.0):
    seen = {}
    if trace:
        install_program_trace(monkeypatch, seen,
                              between=("promote", "compact"))

    def note(engine, sink):
        seen.update(engine=engine, sink=sink)
        if sabotage is not None:
            sabotage(engine)

    result = harness.run_cell(
        CELL, SEED, seconds, trace, time.perf_counter(), allow_cpu=True,
        overrides=TOY, sabotage=note)
    return result, seen


def test_rehearsal_is_correct_with_the_tier_at_work_under_a_moving_day(
        monkeypatch):
    result, seen = rehearse(monkeypatch, trace=True)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    line = result["metrics"]
    value = {n: line[n]["value"] for n in line}
    for name in NEW + ["step_promote_ms.sat", "step_demote_ms.sat",
                       "event_days.sat", "multi_day_batches_pct.sat",
                       "slots_reclaimed_per_day.sat", "cold_rows_pct.sat"]:
        assert name in line, (name, sorted(line))
    eng = seen["engine"]
    days = (result["attempted"] - 1) // TOY["traffic"][
        "rows_per_event_day"] + 1
    assert value["event_days.sat"] == days > 40  # the ring turned over
    assert value["cold_promotions.sat"] > 0 and value["cold_demotions.sat"] > 0
    # keys last seen more than 37 event days ago went, with their segments'
    # arrays; what came back later than that came back as new
    assert value["cold_expired_keys.sat"] > 0
    assert value["cold_dead_returns.sat"] >= 0
    assert value["cold_expire_ms.sat"] >= 0
    assert 1.0 <= value["cold_demote_age_days.sat"] <= 37.0
    assert value["cold_store_keys_end.sat"] == eng._cold.keys_count > 0
    assert value["tier_cms_rows.sat"] == 0.0 and not eng._degraded_keys
    assert value["recompiles.sat"] == 0.0
    rows = {c["name"]: c["value"] for c in result["checks"]}
    assert rows["rows_compared"] >= result["attempted"]
    assert rows["draw_wraps"] == 0


def test_without_the_promotion_the_rehearsal_is_not_correct(monkeypatch):
    def no_promotion(engine):
        engine._returning_keys = lambda cols: None

    result, seen = rehearse(monkeypatch, trace=False,
                            sabotage=no_promotion, seconds=1.0)
    assert seen["engine"].metrics.get(
        "rtfds_feature_cold_demotions_total").value > 0
    assert result["correct"] is False
    assert "exact_columns_wrong" in {
        c["name"] for c in result["checks"] if not c["ok"]}
