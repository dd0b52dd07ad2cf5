"""The cold-tier cell ``forest-cold.saturate``: it resolves to its files,
its configuration is ``forest-rf100-d8-exact`` with twice the active keys
and the host cold tier armed, its traffic is that cell's own file, and a
rehearsal on the CPU ends ``correct`` with every metric the cell brings on
a traced line — through passes that demote keys and batches that bring
them back, each promoted before its row is scored. With the promotion
taken out the same rehearsal scores returning keys on an empty history
and is not ``correct``: the check is what holds the tier to its
contract."""

import os
import time

from benchmark import harness
from benchmark.tests.test_exact_cell import install_program_trace

ROOT = harness.ROOT
CELL = "forest-cold.saturate"
NEW = ["step_promote_ms.sat", "step_demote_ms.sat", "cold_promotions.sat",
       "cold_demotions.sat", "cold_rows_pct.sat", "cold_detect_ms.sat",
       "cold_append_ms.sat", "promote_pad_pct.sat"]
# 40 fill days of 512 rows over 8,192 active keys a table = the slots, in
# a universe of 16,384 ids: the fill touches ~7,000 customers, the target
# is 1,638 occupied (cold_highwater 0.2), so passes (every 2 batches, up
# to 2,048 keys a table) demote from the fill's 4th batch on; the window's
# rows cycle over 8,192 draws of one event day, so the fill's demoted
# customers come back
TOY = {
    "config": {
        "features": {"customer_capacity": 8192, "terminal_capacity": 8192,
                     "compact_every": 2, "cold_demote_slots": 2048},
        "key_universe": {"customers": 16384, "terminals": 16384},
        "active_keys": {"customers": 8192, "terminals": 8192},
        "runtime": {"precompile": True, "batch_buckets": [256, 512],
                    "max_batch_rows": 512},
        "model_params": {"fit_rows": 512, "nominal_rows_per_day": 512},
    },
    "traffic": {
        "fill_batches": 40, "fill_batch_rows": 512, "pool_envelopes": 4096,
        "draw_rows": 8192, "max_poll_rows": 512,
        "check_window_rows": 1 << 20,
    },
}
SEED = 3_400_000_123


def test_the_cell_is_the_exact_cell_with_more_keys_than_slots_hold():
    manifest = harness.load_manifest()
    cell = harness.Cell(ROOT, manifest, CELL)
    exact = harness.Cell(ROOT, manifest, "forest-exact.saturate")
    assert cell.chips == 1 and cell.regime == "sat"
    assert [m["name"] for m in cell.end_to_end()] == ["rows_per_s",
                                                      "setup_s"]
    # the exact cell's mix but for its draw: this cell keeps the 64-batch
    # draw until its event days advance inside the window (PERF.md §7)
    assert cell.entry["traffic"] == "saturate-active"
    mine_t, theirs_t = dict(cell.traffic), dict(exact.traffic)
    assert (mine_t.pop("draw_rows"), theirs_t.pop("draw_rows")) == (
        1 << 22, 1 << 24)
    assert theirs_t.pop("limits") == {"draw_wraps": 0}
    assert "rule" in theirs_t.pop("derived_from")
    assert mine_t.pop("why") != theirs_t.pop("why")
    assert mine_t == theirs_t
    cold, ex = dict(cell.config), dict(exact.config)
    fc, fe = dict(cold.pop("features")), dict(ex.pop("features"))
    # the table of ISSUE 34, and nothing else
    assert fc.pop("cold_store") == "tmp://rtfds-cold"
    assert fc.pop("cold_highwater") == 0.2
    assert fc.pop("cold_demote_slots") == 131072
    assert (fc.pop("compact_every"), fe.pop("compact_every")) == (4, 64)
    assert fc == fe  # windows, delay, buckets, slots, probes: the same
    slots = {"customers": fc["customer_capacity"],
             "terminals": fc["terminal_capacity"]}
    assert cold.pop("active_keys") == slots
    assert ex.pop("active_keys") == {k: v // 2 for k, v in slots.items()}
    for said in ("source", "deployment", "exactness_here"):
        assert cold[said] != ex[said]
        assert cold.pop(said).startswith(ex.pop(said)[:40])
    assert len(cold.pop("assumed")) == len(ex.pop("assumed")) + 2
    assert cold == ex  # limits, guarantees, runtime, ingest, model, state...
    assert cold["reduced"] == [] and cold["limits"]["exact_columns_wrong"] == 0
    from real_time_fraud_detection_system_tpu.config import FeatureConfig
    from real_time_fraud_detection_system_tpu.features.online import (
        state_bytes,
    )

    by_tier = state_bytes(FeatureConfig(**dict(
        cell.config["features"], windows=tuple(fc["windows"]))))
    assert cold["state_bytes"] == by_tier.pop("total") == 8_409_579_848
    assert cold["state_bytes_by_tier"] == by_tier  # the tier is the host's
    mine = {m["name"]: m for m in cell.per_layer()}
    theirs = {m["name"] for m in exact.per_layer()}
    assert set(NEW) <= set(mine) and not set(NEW) & theirs
    assert {mine[n]["layer"] for n in NEW} == {"cold tier"}
    assert all(mine[n]["workloads"] == [CELL] for n in NEW)
    assert all(mine[n]["moves"] == "rows_per_s" for n in NEW)
    assert theirs <= set(mine)  # every metric of forest-exact.saturate
    # new files are data over the readers the benchmark has
    for n in NEW:
        spec = harness.load_json(os.path.join(
            ROOT, "benchmark", "metrics", n + ".json"))
        assert spec["reader"] in ("device_scopes", "registry",
                                  "registry_ratio"), n


def rehearse(monkeypatch, trace, sabotage=None, seconds=3.0):
    seen = {}
    if trace:
        # the names a chip's trace would carry for step_promote_ms and
        # step_demote_ms: a promote and the compaction between two steps
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


def test_rehearsal_is_correct_through_demotions_and_promotions(monkeypatch):
    result, seen = rehearse(monkeypatch, trace=True)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    line = result["metrics"]
    for name in NEW:
        assert name in line, (name, sorted(line))
    value = {n: line[n]["value"] for n in line}
    eng = seen["engine"]
    assert eng._cold.ephemeral and os.path.isdir(eng._cold.path)
    assert os.path.dirname(eng._cold.path) != ROOT  # under the temp dir
    # the tier did its work inside the window (how many batches a window
    # holds is the CPU's business: counts, not sizes), and exactly
    assert value["cold_promotions.sat"] > 0
    assert value["cold_demotions.sat"] > 0
    assert 0 < value["cold_rows_pct.sat"] <= 100
    assert 0 <= value["promote_pad_pct.sat"] < 100
    assert value["tier_cms_rows.sat"] == 0.0  # no row from the sketch
    assert not eng._degraded_keys
    assert value["recompiles.sat"] == 0.0  # every promote width was AOT
    assert value["compactions.sat"] >= 1
    assert value["cold_detect_ms.sat"] > 0
    assert value["cold_append_ms.sat"] > 0
    # the stages that live inside the step are siblings: with the
    # unscoped rest they never add up to more than the step; the programs
    # of their own (promote, compaction, demote) are read per step beside
    # it, each a sibling of the others
    assert value["step_promote_ms.sat"] > 0
    assert value["step_demote_ms.sat"] > 0
    assert value["step_compact_ms.sat"] > 0
    stages = ("stamp", "reset", "scatter", "query", "classify", "cms")
    total = sum(value[f"step_{s}_ms.sat"] for s in stages)
    # the fabricated trace holds one promote's admit between its two
    # steps: step_keydir_ms is the steps' admits plus half of that one
    assert value["step_keydir_ms.sat"] > 0
    total += value["step_unscoped_pct.sat"] / 100 * value[
        "device_step_ms.sat"]
    assert 0.5 * value["device_step_ms.sat"] < total <= value[
        "device_step_ms.sat"] * (1 + 1e-9)
    # every window row was compared: the keys that came back answered as
    # the reference does, from their own history
    rows = {c["name"]: c["value"] for c in result["checks"]}
    assert rows["rows_compared"] >= result["attempted"]


def test_without_the_promotion_the_rehearsal_is_not_correct(monkeypatch):
    """The cell's control at toy size: a returning key that is not
    promoted is admitted afresh, on an empty history — the comparison
    with the plain reference refuses the run by its own limits."""
    def no_promotion(engine):
        engine._returning_keys = lambda cols: None

    result, seen = rehearse(monkeypatch, trace=False,
                            sabotage=no_promotion, seconds=1.0)
    assert seen["engine"].metrics.get(
        "rtfds_feature_cold_demotions_total").value > 0
    assert result["correct"] is False
    assert "exact_columns_wrong" in {
        c["name"] for c in result["checks"] if not c["ok"]}
