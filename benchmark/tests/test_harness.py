"""The manifest keeps to the contract's characters and cross-references,
and a cell, a metric and a reader can be added as new files plus new
entries in BENCHMARK.json, with no edit to a file that is there."""

import json
import os
import re
import shutil
import time

import pytest

from benchmark import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_manifest_names_units_and_cross_references():
    m = harness.load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    e2e = {x["name"]: x for x in m["end_to_end"]}
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for group in (m["configs"], m["workloads"], m["end_to_end"],
                  m["per_layer"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        on_disk = harness.load_json(os.path.join(ROOT, c["file"]))
        assert on_disk["reduced"] == c["reduced"] == []
        assert on_disk["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in m["workloads"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
        assert set(x.get("workloads", cells)) <= set(cells)
    for name in cells:
        cell = harness.Cell(ROOT, m, name)
        mine = [x["name"] for x in cell.end_to_end()]
        assert "setup_s" in mine and len(mine) >= 2
        layer = cell.per_layer()
        assert layer, name
        # every per-layer metric moves an end-to-end metric its cell reports
        assert all(x["moves"] in mine for x in layer), name
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES and x["moves"] in e2e
        assert "\n" not in x["layer"] and len(x["layer"]) <= 200
        spec = harness.load_json(harness.find(ROOT, m, "metrics", x["name"],
                                              ".json"))
        assert spec["name"] == x["name"]
        harness.find(ROOT, m, "readers", spec["reader"], ".py")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_a_cell_a_metric_and_a_reader_added_as_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(os.path.join(root, "benchmark"))
              for p in fs}
    toy = harness.load_json(os.path.join(
        ROOT, "benchmark", "tests", "data", "toy_overrides.json"))
    extra = os.path.join(root, "benchmark_more")
    for d in ("configs", "traffic", "metrics", "readers"):
        os.makedirs(os.path.join(extra, d))
    config = harness.merge(harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "logreg-15f.json")), toy["config"])
    traffic = harness.merge(harness.load_json(os.path.join(
        ROOT, "benchmark", "traffic", "saturate.json")), toy["traffic"])
    json.dump(config, open(os.path.join(extra, "configs", "toy.json"), "w"))
    json.dump(traffic, open(os.path.join(extra, "traffic", "toy-mix.json"),
                            "w"))
    json.dump({"name": "toy_batches.sat", "regime": "sat",
               "reader": "toy_reader", "args": {"times": 2}},
              open(os.path.join(extra, "metrics", "toy_batches.sat.json"),
                   "w"))
    with open(os.path.join(extra, "readers", "toy_reader.py"), "w") as f:
        f.write("def read(ctx, times):\n"
                "    return ctx['run_stats']['batches'] * times\n")
    m = harness.load_manifest(root)
    m["paths"].append("benchmark_more")
    m["configs"].append({"name": "toy", "source": config["source"],
                         "file": "benchmark_more/configs/toy.json",
                         "reduced": [], "why": "a toy"})
    m["workloads"].append({"name": "toy.cell", "config": "toy",
                           "traffic": "toy-mix", "chips": 1, "why": "a toy"})
    m["per_layer"].append({"name": "toy_batches.sat", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "entry queue", "moves": "rows_per_s"})
    # every metric lists its cells: the new cell joins the ones it reports
    for x in m["end_to_end"] + m["per_layer"]:
        if x["name"] in ("rows_per_s", "source_poll_ms.sat"):
            x["workloads"].append("toy.cell")
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = harness.Cell(root, harness.load_manifest(root), "toy.cell")
    assert [x["name"] for x in cell.end_to_end()] == ["rows_per_s", "setup_s"]
    layer = {x["name"]: x for x in cell.per_layer()}
    assert "toy_batches.sat" in layer and "source_poll_ms.sat" in layer
    assert "sink_write_ms.sat" not in layer  # lists its cells, not this one
    spec = layer["toy_batches.sat"]
    assert cell.plugin("readers", spec["reader"]).read(
        {"run_stats": {"batches": 21}}, **spec["args"]) == 42
    result = harness.run_cell("toy.cell", 12, 1.0, False,
                              time.perf_counter(), root=root, allow_cpu=True)
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"rows_per_s", "setup_s"}
    assert result["metrics"]["rows_per_s"]["value"] > 0
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(os.path.join(root, "benchmark"))
             for p in fs if "__pycache__" not in dp and ".cache" not in dp}
    assert all(after[p] == before[p] for p in before if p in after)


def test_every_per_layer_metric_lists_the_cells_that_report_it():
    """A cell added later (the four-chip one) is then not held to a metric
    it has no reader for; and every cell reports the host loop's gaps."""
    m = harness.load_manifest()
    cells = {w["name"] for w in m["workloads"]}
    for x in m["per_layer"]:
        assert x.get("workloads") and set(x["workloads"]) <= cells, x["name"]
    for name in cells:
        cell = harness.Cell(ROOT, m, name)
        mine = {x["name"]: x for x in cell.per_layer()}
        gaps = [n for n in mine if n.startswith("ack_gap_")]
        assert sorted(g.split(".")[0] for g in gaps) == [
            "ack_gap_max_ms", "ack_gap_p50_ms"], name
        assert all(mine[g]["layer"] == "host loop"
                   and mine[g]["source"] == "host_clock" for g in gaps)


def test_steadiness_of_a_window_and_its_reader():
    """Five acknowledgements 100 ms apart but for one 400 ms stall: the
    median gap ignores the stall, the longest gap is it, and the reader
    hands a metric file's key over (nothing where nothing was read)."""
    acks = [10.0, 10.1, 10.2, 10.6, 10.7]
    st = harness.steadiness_of(acks, [0.05, 0.05, 0.25, 0.05],
                               [0.03, 0.03, 0.03, 0.04])
    assert st["ack_gap_p50_ms"] == pytest.approx(100.0)
    assert st["ack_gap_max_ms"] == pytest.approx(400.0)
    assert st["ack_gap_max_at"] == 3 and st["ack_gaps_over_1p5_p50"] == 1
    assert st["sink_write_max_ms"] == pytest.approx(250.0)
    assert st["poll_p50_ms"] == pytest.approx(30.0)
    assert harness.steadiness_of([10.0], [0.05], [0.03]) == {}
    m = harness.load_manifest()
    cell = harness.Cell(ROOT, m, "forest.steady")
    spec = {x["name"]: x for x in cell.per_layer()}["ack_gap_max_ms.steady"]
    read = cell.plugin("readers", spec["reader"]).read
    assert read({"steadiness": st}, **spec["args"]) == pytest.approx(400.0)
    assert read({"steadiness": {}}, **spec["args"]) is None


def test_no_workload_no_run():
    with pytest.raises(harness.HarnessError):
        harness.Cell(ROOT, harness.load_manifest(), "no.such.cell")
