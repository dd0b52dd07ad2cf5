"""The four-chip exact cell ``forest-x4-exact.saturate``: its
configuration is ``forest-rf100-d8-x4`` with ``forest-rf100-d8-exact``'s
key path and ids, its traffic ``saturate-arriving.json`` with a draw
derived for this cell, every shard's directory sits at the load the
one-chip exact cell's does, the reader over the ``shard`` label reads what
its docstring says, and a rehearsal on four virtual CPU devices — through
a compaction, with a device plane made from the mesh's own compiled
programs — ends ``correct`` with every per-layer metric the cell lists on
its traced line and the compaction counted as no step."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness
from benchmark.readers import registry_shards

ROOT = harness.ROOT
CELL = "forest-x4-exact.saturate"
X4_FAMILY = ["step_exchange_ms.sat", "host_partition_ms.sat",
             "host_assemble_ms.sat", "shard_pad_pct.sat",
             "shard_chunks_per_batch.sat", "shard_imbalance.sat",
             "exchange_overflows.sat"]
EXACT_FAMILY = ["step_keydir_ms.sat", "step_keydir_lookup_ms.sat",
                "step_keydir_claim_ms.sat", "step_keydir_grant_ms.sat",
                "step_cms_ms.sat", "step_compact_ms.sat",
                "keydir_claim_rounds.sat", "tier_cms_rows.sat",
                "compactions.sat", "compact_fetch_ms.sat",
                "compact_wait_ms.sat", "compact_sweeps.sat",
                "idle_pass_pct.sat"]
NEW = ["keydir_occupancy_max.sat", "keydir_claim_rounds_spread.sat",
       "slots_reclaimed_mesh.sat"]


def _cells():
    manifest = harness.load_manifest()
    return manifest, {name: harness.Cell(ROOT, manifest, name) for name in (
        CELL, "forest-x4.saturate", "forest-exact.saturate")}


def test_the_configuration_is_the_x4_one_with_the_exact_ones_key_path():
    manifest, cells = _cells()
    cell, x4, ex = (cells[n].config for n in (
        CELL, "forest-x4.saturate", "forest-exact.saturate"))
    assert cells[CELL].chips == 4 and cells[CELL].regime == "sat"
    assert [m["name"] for m in cells[CELL].end_to_end()] == [
        "rows_per_s", "setup_s"]
    for key in ("chips", "ingest", "guarantees", "limits", "runtime",
                "model", "model_params", "reduced"):
        assert cell[key] == x4[key], key
    assert cell["runtime"] == {"precompile": True} and cell["reduced"] == []
    feats, f4, fe = (dict(c["features"]) for c in (cell, x4, ex))
    for f in (feats, fe):
        assert (f.pop("key_mode"), f.pop("keydir_probes"),
                f.pop("compact_every")) == ("exact", 16, 64)
    assert f4.pop("key_mode") == "direct"
    assert feats == f4  # windows, delay, buckets, 2^24 + 2^25 slots
    assert "key_bits" not in feats and "cold_store" not in feats
    slots = {"customers": feats["customer_capacity"],
             "terminals": feats["terminal_capacity"]}
    assert slots == {"customers": 1 << 24, "terminals": 1 << 25}
    assert cell["key_universe"] == {k: 2 * v for k, v in slots.items()}
    assert cell["active_keys"] == {k: v // 2 for k, v in slots.items()}
    assert max(cell["key_universe"].values()) < 1 << 32
    # the exact cell's universe and active set, four times over
    assert cell["key_universe"] == {k: 4 * v for k, v in
                                    ex["key_universe"].items()}
    assert cell["active_keys"] == {k: 4 * v for k, v in
                                   ex["active_keys"].items()}
    assert "on every shard" in cell["exactness_here"]
    from real_time_fraud_detection_system_tpu.config import FeatureConfig
    from real_time_fraud_detection_system_tpu.features.online import (
        state_bytes,
    )

    by_tier = state_bytes(FeatureConfig(**dict(
        cell["features"], windows=tuple(feats["windows"]))), n_shards=4)
    assert cell["state_bytes"] == by_tier.pop("total") == 33_638_319_392
    assert cell["state_bytes_by_tier"] == by_tier
    assert by_tier["dense"] == x4["state_bytes"]
    # a chip holds what the one-chip exact configuration holds
    assert cell["state_bytes_per_chip"] * 4 == cell["state_bytes"]
    assert cell["state_bytes_per_chip"] == ex["state_bytes"]
    sources = [c["source"] for c in manifest["configs"]]
    assert len(set(sources)) == len(sources)
    entry = manifest["configs"][-1]
    assert entry["name"] == "forest-rf100-d8-x4-exact"
    assert entry["source"] == cell["source"] and len(entry["source"]) <= 200
    assert "init.sql" in entry["source"] and "4" in entry["source"]


def test_the_cell_is_on_the_lists_of_both_families_and_no_others():
    manifest, cells = _cells()
    mine = {m["name"]: m for m in cells[CELL].per_layer()}
    x4 = {m["name"] for m in cells["forest-x4.saturate"].per_layer()}
    ex = {m["name"] for m in cells["forest-exact.saturate"].per_layer()}
    assert set(X4_FAMILY) <= x4 and not set(X4_FAMILY) & ex
    assert set(EXACT_FAMILY) <= ex and not set(EXACT_FAMILY) & x4
    assert x4 <= set(mine)  # every metric of the direct four-chip cell
    # every metric of the one-chip exact cell but the one its reader
    # would double on a mesh (tests/test_sharded_exact.py shows it)
    assert ex - set(mine) == {"slots_reclaimed.sat"}
    assert set(mine) - x4 - ex == set(NEW)
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["reader"] == "registry_shards"
        assert mine[name]["layer"] == "key directory and sketch tier"
        assert mine[name]["moves"] == "rows_per_s"
    # appended, nothing before them moved: 8 cells, 2 on four chips
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert [m["name"] for m in manifest["per_layer"]][-3:] == NEW
    chips = [w["chips"] for w in manifest["workloads"]]
    assert len(chips) == 8 and chips.count(4) == 2 == len(chips) // 4
    assert len(manifest["workloads"][-1]["why"]) <= 200


def test_the_traffic_is_saturate_arriving_with_a_draw_of_its_own():
    from test_generator import _draw_faults

    manifest, cells = _cells()
    mine = dict(cells[CELL].traffic)
    theirs = dict(cells["forest-exact.saturate"].traffic)
    assert cells[CELL].entry["traffic"] == "saturate-arriving-x4"
    d, d0 = mine.pop("derived_from"), theirs.pop("derived_from")
    draw = mine.pop("draw_rows")
    theirs.pop("draw_rows")
    assert mine == theirs  # generator, fill, pool, laws, limits, regime
    assert mine["generator"] == "debezium_cards_active"
    assert mine["limits"] == {"draw_wraps": 0}
    assert d["rule"] == d0["rule"] and d["cell"] == CELL
    # the file's own rule, applied to this cell's measured pace
    assert _draw_faults(cells[CELL].traffic, manifest["run_seconds"]) == []
    need = d["headroom"] * d["rows_per_s"] * d["run_seconds"]
    assert d["rows_needed"] == round(need) <= draw
    assert draw == 1 << int(np.ceil(np.log2(need)))
    assert d["holds_until_rows_per_s"] == draw // manifest["run_seconds"]
    assert d["rows_per_s"] < d["holds_until_rows_per_s"]
    # 16,777,216 up to 559,240 rows/s, 33,554,432 above it
    assert (draw == 1 << 25) == (d["rows_per_s"] > (1 << 24) / 30)


def test_every_shards_directory_sits_at_the_exact_cells_load():
    """By the configuration's numbers: a uniform sample of ids owned by
    ``id % 4`` gives every shard a quarter of the active keys over a
    quarter of the entries — load 0.25, the one-chip exact cell's — and
    the law ``D·α^(P+1)/(P+1)`` loses ~3.4e-4 keys over the mesh's eight
    directories. By a toy sample: the shares are a quarter."""
    _, cells = _cells()
    cfg, ex = cells[CELL].config, cells["forest-exact.saturate"].config
    probes, n = cfg["features"]["keydir_probes"], cfg["chips"]
    lost = 0.0
    for table in ("customer", "terminal"):
        entries = 2 * cfg["features"][f"{table}_capacity"] // n
        assert entries == 2 * ex["features"][f"{table}_capacity"]
        load = cfg["active_keys"][table + "s"] / n / entries
        assert load == 0.25 == (ex["active_keys"][table + "s"]
                                / (2 * ex["features"][f"{table}_capacity"]))
        lost += n * entries * load ** (probes + 1) / (probes + 1)
    assert lost == pytest.approx(3.447e-4, rel=0.001)
    gen = cells[CELL].plugin("generators", "debezium_cards_active")
    ids = gen.sample_ids(np.random.default_rng(44), 1 << 21, 1 << 19)
    shares = np.bincount(ids % n, minlength=n) / len(ids)
    assert np.abs(shares - 0.25).max() < 4 * np.sqrt(0.1875 / len(ids))


def _snap(**metrics):
    return {k: {"series": [{"labels": lab, "value": v} for lab, v in rows]}
            for k, rows in metrics.items()}


def test_a_statistic_over_the_shard_label():
    def lab(table, shard=None):
        return dict({"table": table}, **(
            {} if shard is None else {"shard": str(shard)}))

    before = _snap(
        rounds=[(lab("customer", 0), 10.0), (lab("customer", 1), 10.0),
                (lab("terminal", 0), 5.0), (lab("terminal", 1), 5.0)],
        reclaimed=[(lab("customer"), 100.0), (lab("customer", 0), 40.0),
                   (lab("customer", 1), 60.0)],
        passes=[({}, 1.0)])
    after = _snap(
        rounds=[(lab("customer", 0), 40.0), (lab("customer", 1), 50.0),
                (lab("terminal", 0), 15.0), (lab("terminal", 1), 35.0)],
        reclaimed=[(lab("customer"), 400.0), (lab("customer", 0), 140.0),
                   (lab("customer", 1), 260.0)],
        passes=[({}, 3.0)],
        occupied=[(lab("customer"), 300.0), (lab("customer", 0), 100.0),
                  (lab("customer", 1), 200.0), (lab("terminal", 0), 90.0),
                  (lab("terminal", 1), 30.0)],
        capacity=[(lab("customer", 0), 400.0), (lab("customer", 1), 400.0),
                  (lab("terminal", 0), 100.0), (lab("terminal", 1), 100.0)],
        table_only=[(lab("customer"), 7.0)])
    ctx = {"registry_before": before, "registry_after": after}
    read = registry_shards.read
    # shard 0 ran 30 + 10 rounds in the window, shard 1 40 + 30
    assert read(ctx, "rounds", "max_over_mean",
                by_shard=True) == pytest.approx(70 / 55)
    assert read(ctx, "rounds", "max_over_mean", by_shard=True,
                labels={"table": "customer"}) == pytest.approx(40 / 35)
    # the shard series alone: the table-level 300 is not counted again
    assert read(ctx, "reclaimed", "sum") == pytest.approx(300.0)
    assert read(ctx, "reclaimed", "sum", per=["passes"]) == pytest.approx(150)
    # a gauge over a gauge, series by series, as they stand at the end
    assert read(ctx, "occupied", "max", over="capacity") == pytest.approx(0.9)
    assert read(ctx, "occupied", "max", over="capacity",
                labels={"table": "customer"}) == pytest.approx(0.5)
    # nothing to read: no such metric (the parent commit), no shard
    # label, one shard, a divisor that stood still
    assert read(ctx, "absent", "max") is None
    assert read(ctx, "table_only", "sum") is None
    assert read(ctx, "rounds", "max", labels={"shard": "0",
                                              "table": "customer"}) is None
    assert read(ctx, "reclaimed", "sum", per=["absent"]) is None
    assert read({"registry_before": after, "registry_after": after},
                "rounds", "max_over_mean", by_shard=True) is None
    with pytest.raises(ValueError):
        read(ctx, "rounds", "median")


# The rehearsal in a process of its own (it alone needs four virtual
# devices). The CPU's trace has no device plane, so the reduction gets one
# made from the mesh's own compiled programs, each under the name its
# module has: the step, the compaction, the step again, a microsecond an
# operation (test_exact_cell.py's, for the mesh's engine).
REHEARSAL = """
import json, re, sys, time
from benchmark import harness
from benchmark.readers import device_scopes

US = 1_000_000
seen, made = {}, {}

def program(engine, variant):
    sig = next(s for s in engine.dispatch_inventory()
               if s.variant == variant)
    text = engine.signature_step(sig).lower(
        *engine.signature_templates(sig)).compile().as_text()
    return (re.match(r"HloModule (\\w+)", text).group(1),
            re.findall(r'op_name="([^"]*)"', text))

def fabricated():
    if made:
        return made["t"]
    step = program(seen["engine"], "sharded-local")
    programs = [step, program(seen["engine"], "compact"), step]
    ops, modules, t = [["%lead", 0, US, ""]], [], 2 * US
    for name, op_names in programs:
        modules.append([name + "(1)", t, len(op_names) * US])
        for op_name in op_names:
            ops.append(["%op", t, US, op_name])
            t += US
    ops.append(["%tail", t + US, US, ""])
    lines = [{"name": device_scopes.device_trace.MODULE_LINE, "events": [
        [n, s // 1000, d // 1000] for n, s, d in modules]},
        {"name": device_scopes.OP_LINE, "events": [
            [n, s // 1000, d // 1000] for n, s, d, _ in ops]}]
    made["t"] = ({"planes": [{"name": "/device:TPU:0", "lines": lines}]},
                 ops, modules)
    return made["t"]

harness.device_trace.load_xplane = lambda path: fabricated()[0]
inner = harness.traced_metrics

def with_the_programs_scopes(cell, trace_dir, traced, done, device, ctx):
    _, ops, modules = fabricated()
    ctx[device_scopes.CTX_KEY] = device_scopes.per_step(ops, modules)
    return inner(cell, trace_dir, traced, done, device, ctx)

harness.traced_metrics = with_the_programs_scopes
result = harness.run_cell(
    sys.argv[1], 4_400_000_123, 3.0, True, time.perf_counter(),
    allow_cpu=True, overrides=json.loads(sys.argv[2]),
    sabotage=lambda engine, sink: seen.update(engine=engine))
_, ops, modules = fabricated()
result["modules"] = [[m[0], m[2] // US] for m in modules]
print(json.dumps(result))
"""
TOY = {
    "config": {
        "features": {"customer_capacity": 16384, "terminal_capacity": 32768,
                     "compact_every": 42},
        "key_universe": {"customers": 32768, "terminals": 65536},
        "active_keys": {"customers": 8192, "terminals": 16384},
        "runtime": {"precompile": True, "batch_buckets": [256, 512],
                    "max_batch_rows": 512},
        "model_params": {"fit_rows": 512, "nominal_rows_per_day": 512},
    },
    "traffic": {
        "fill_batches": 40, "fill_batch_rows": 512, "pool_envelopes": 4096,
        "draw_rows": 131072, "max_poll_rows": 512,
        "check_window_rows": 1 << 20,
    },
}


def test_rehearsal_on_four_devices_reports_every_metric_the_cell_lists():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run(
        [sys.executable, "-c", REHEARSAL, CELL, json.dumps(TOY)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-3000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    assert result["device"]["count"] == 4 and result["failed"] == 0
    line = {k: v["value"] for k, v in result["metrics"].items()}
    _, cells = _cells()
    listed = {m["name"] for m in cells[CELL].per_layer()}
    # a CPU run has no device memory statistics, and a made-up plane no
    # host annotations to line a batch's dispatch up with its first
    # program; all else is on the line
    assert listed - set(line) <= {"peak_hbm_gb.sat",
                                  "device_queue_ms.sat"}, listed - set(line)
    # the mesh's programs under their own names: the compaction is no step
    (step, n_step), (compact, n_compact), _ = result["modules"]
    assert step == "jit_outer(1)" and compact == "jit_compact(1)"
    assert line["device_step_ms.sat"] == pytest.approx(n_step * 1e-3)
    parts = [line[f"step_keydir_{p}_ms.sat"]
             for p in ("lookup", "claim", "grant")]
    assert min(parts) > 0
    assert sum(parts) == pytest.approx(line["step_keydir_ms.sat"], rel=1e-9)
    assert line["step_cms_ms.sat"] > 0 and line["step_exchange_ms.sat"] > 0
    # the pass between the two steps, over their count
    assert 0 < line["step_compact_ms.sat"] <= n_compact * 1e-3 / 2
    assert line["tier_cms_rows.sat"] == 0.0
    assert line["exchange_overflows.sat"] == 0.0
    assert line["shard_chunks_per_batch.sat"] == pytest.approx(1.0)
    assert line["recompiles.sat"] == 0.0  # ("compact",) was AOT
    assert line["compactions.sat"] >= 1 and line["compact_wait_ms.sat"] > 0
    assert 0 < line["compact_sweeps.sat"] <= 8  # a sweep a table and shard
    assert line["slots_reclaimed_mesh.sat"] > 0
    assert 0 < line["keydir_occupancy_max.sat"] < 0.5
    assert 1.0 <= line["keydir_claim_rounds_spread.sat"] < 1.5
    # rounds a batch, all four shards' summed: new keys in every batch
    assert line["keydir_claim_rounds.sat"] > 4
