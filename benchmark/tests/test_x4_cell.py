"""The four-chip cell ``forest-x4.saturate``: it resolves to its files, its
configuration is ``forest-rf100-d8`` at four chips' worth of state and
nothing else, and a rehearsal on four virtual CPU devices ends with the
metrics this cell brings on a traced line."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness
from benchmark.readers import device_scopes, registry_ratio

ROOT = harness.ROOT
DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "forest-x4.saturate"
NEW = ["step_exchange_ms.sat", "host_partition_ms.sat",
       "host_assemble_ms.sat", "shard_pad_pct.sat",
       "shard_chunks_per_batch.sat", "shard_imbalance.sat",
       "exchange_overflows.sat"]


def test_the_cell_resolves_to_its_files_and_only_scale_differs():
    manifest = harness.load_manifest()
    cell = harness.Cell(ROOT, manifest, CELL)
    one = harness.Cell(ROOT, manifest, "forest.saturate")
    assert cell.chips == 4 and cell.regime == "sat"
    assert cell.traffic == one.traffic  # the same mix, file and all
    assert [m["name"] for m in cell.end_to_end()] == ["rows_per_s",
                                                      "setup_s"]
    x4, x1 = cell.config, one.config
    for key in ("limits", "guarantees", "runtime", "ingest", "model",
                "model_params", "reduced"):
        assert x4[key] == x1[key], key
    f4, f1 = dict(x4["features"]), dict(x1["features"])
    assert f4.pop("customer_capacity") == 4 * f1.pop("customer_capacity")
    assert f4.pop("terminal_capacity") == 4 * f1.pop("terminal_capacity")
    assert f4 == f1
    slots = (x4["features"]["customer_capacity"]
             + x4["features"]["terminal_capacity"])
    assert x4["key_universe"] == {
        "customers": x4["features"]["customer_capacity"],
        "terminals": x4["features"]["terminal_capacity"]}
    assert x4["state_bytes"] == slots * x4["features"]["n_day_buckets"] * 16
    assert x4["state_bytes_per_chip"] * 4 == x4["state_bytes"]
    assert x4["state_bytes_per_chip"] == x1["state_bytes"]
    assert x4["chips"] == 4 and x4["source"] != x1["source"]
    mine = {m["name"]: m for m in cell.per_layer()}
    assert set(NEW) <= set(mine)
    theirs = {m["name"] for m in one.per_layer()}
    assert not set(NEW) & theirs  # read nothing on one chip: not joined
    assert theirs <= set(mine)  # every .sat metric of one chip is joined
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert {mine[n]["layer"] for n in NEW} <= layers | {"sharded step"}


def test_ratio_of_two_counters_over_the_window():
    def snap(**values):
        return {k: {"series": [{"labels": lab, "value": v}
                               for lab, v in rows]}
                for k, rows in values.items()}

    ctx = {
        "registry_before": snap(
            slots=[({}, 1000.0)], valid=[({}, 400.0)],
            chunks=[({"routed": "0"}, 10.0), ({"routed": "1"}, 1.0)],
            batches=[({}, 10.0)]),
        "registry_after": snap(
            slots=[({}, 5000.0)], valid=[({}, 2400.0)],
            chunks=[({"routed": "0"}, 30.0), ({"routed": "1"}, 3.0)],
            batches=[({}, 30.0)], idle=[({}, 7.0)]),
    }
    read = registry_ratio.read
    assert read(ctx, ["valid"], ["slots"]) == pytest.approx(0.5)
    assert read(ctx, ["valid"], ["slots"], scale=-100.0,
                offset=100.0) == pytest.approx(50.0)
    # every series of a name is summed: local and routed chunks
    assert read(ctx, ["chunks"], ["batches"]) == pytest.approx(1.1)
    # a program without the counter, or a denominator that stood still
    assert read(ctx, ["absent"], ["slots"]) is None
    assert read(ctx, ["valid"], ["absent"]) is None
    assert read(ctx, ["valid"], ["idle"]) == pytest.approx(2000.0 / 7.0)
    ctx["registry_before"]["idle"] = ctx["registry_after"]["idle"]
    assert read(ctx, ["valid"], ["idle"]) is None


def test_exchange_metric_reads_the_exchange_and_its_parts():
    """``step_exchange_ms.sat`` on hand-made events: everything under
    ``rtfds.exchange`` (the collective, the ranking, the packing, the
    back-gather) and nothing of the owner's table work."""
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "metrics", "step_exchange_ms.sat.json"))
    assert spec["reader"] == "device_scopes"
    step = "jit(outer)/jit(main)/jit(shmap_body)/"
    events = [
        ["%sort", 0, 10, step + "rtfds.exchange/rtfds.route/sort"],
        ["%scatter", 10, 20, step + "branch_1_fun/rtfds.exchange/"
                                    "rtfds.pack/scatter"],
        ["%all-to-all", 30, 5, step + "branch_1_fun/rtfds.exchange/"
                                      "all_to_all"],
        ["%fusion", 35, 40, step + "branch_1_fun/rtfds.terminal/"
                                   "rtfds.update/rtfds.scatter/scatter-add"],
        ["%gather", 75, 15, step + "branch_1_fun/rtfds.exchange/"
                                   "rtfds.unpack/gather"],
        ["%copy", 90, 10, ""],
    ]
    # the mesh's step is ``jit_outer`` on the module line; two whole
    # executions between a lead and a tail operation
    us = device_scopes.PS_PER_US
    ops, modules = [["%lead", 0, 5 * us, ""]], []
    for start in (10, 120):
        ops += [[n, (start + s) * us, d * us, op] for n, s, d, op in events]
        modules.append(["jit_outer(99)", start * us, 100 * us])
    ops.append(["%tail", 230 * us, 5 * us, ""])
    ctx = {device_scopes.CTX_KEY: device_scopes.per_step(ops, modules)}
    got = device_scopes.read(ctx, **spec["args"])
    assert got == pytest.approx(
        (10 + 20 + 5 + 15) * us / device_scopes.PS_PER_MS)
    assert device_scopes.read(ctx, stat="unscoped_pct") == pytest.approx(10)


# The rehearsal in a process of its own: only it needs four (virtual)
# devices, and XLA reads the flag once, before the first look for one. The
# CPU's trace has no device plane, so the reduction gets a recorded one
# and the line is built the way a chip run builds it.
REHEARSAL = """
import json, sys, time
from benchmark import harness
from benchmark.tests.record_trace import trace_of
with open(sys.argv[1]) as f:
    canned = trace_of(json.load(f))
harness.device_trace.load_xplane = lambda path: canned
result = harness.run_cell(
    sys.argv[3], 2_800_000_123, 2.0, True, time.perf_counter(),
    allow_cpu=True, overrides=harness.load_json(sys.argv[2]))
print(json.dumps(result))
"""


def test_rehearsal_on_four_devices_ends_with_the_cells_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run(
        [sys.executable, "-c", REHEARSAL,
         os.path.join(DATA, "steps_forest_saturate.json"),
         os.path.join(DATA, "toy_overrides.json"), CELL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    assert result["device"]["count"] == 4
    line = result["metrics"]
    # what a CPU run can read of the new metrics: all but the device's
    for name in NEW[1:]:
        assert name in line, (name, sorted(line))
    assert line["shard_chunks_per_batch.sat"]["value"] == pytest.approx(1.0)
    # 2,048 rows in 4 x 2 x 512 slots: half padding
    assert line["shard_pad_pct.sat"]["value"] == pytest.approx(50.0)
    assert 1.0 <= line["shard_imbalance.sat"]["value"] < 1.2
    assert line["exchange_overflows.sat"]["value"] == 0.0
    assert line["host_partition_ms.sat"]["value"] > 0.0
    assert line["host_assemble_ms.sat"]["value"] > 0.0
    for name in ("host_prep_ms.sat", "dispatch_ms.sat", "recompiles.sat",
                 "result_wait_ms.sat", "sink_write_ms.sat",
                 "source_poll_ms.sat", "ack_gap_p50_ms.sat",
                 "device_step_ms.sat", "device_idle_pct.sat"):
        assert name in line, name
