"""The exact-key cell ``forest-exact.saturate``: it resolves to its files,
its configuration is ``forest-rf100-d8`` with the key path switched and
the ids spread, its traffic is ``saturate`` through another generator,
that generator draws ``debezium_cards``'s laws over an active set inside
the universe, and a rehearsal on the CPU ends ``correct`` with every
metric the cell brings on a traced line — through a compaction that
reclaims keys which then come back. With one probe instead of sixteen
the same rehearsal serves rows from the sketch tier and is not
``correct``: the check catches it."""

import os
import re
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.readers import device_scopes

ROOT = harness.ROOT
CELL = "forest-exact.saturate"
US_PER_DAY = 86_400_000_000
NEW = ["step_keydir_ms.sat", "step_keydir_lookup_ms.sat",
       "step_keydir_claim_ms.sat", "step_keydir_grant_ms.sat",
       "step_cms_ms.sat", "step_compact_ms.sat", "tier_cms_rows.sat",
       "slots_reclaimed.sat", "compactions.sat", "compact_wait_ms.sat"]
SHARDED = {"step_exchange_ms.sat", "host_partition_ms.sat",
           "host_assemble_ms.sat", "shard_pad_pct.sat",
           "shard_chunks_per_batch.sat", "shard_imbalance.sat",
           "exchange_overflows.sat"}
# 40 fill days of 512 rows, 8,192 active keys a table in a universe of
# 32,768 ids and 16,384 slots: the directories fill to a load under 0.25;
# the first compaction of the window comes after batch 42
TOY = {
    "config": {
        "features": {"customer_capacity": 16384, "terminal_capacity": 16384,
                     "compact_every": 42},
        "key_universe": {"customers": 32768, "terminals": 32768},
        "active_keys": {"customers": 8192, "terminals": 8192},
        "runtime": {"precompile": True, "batch_buckets": [256, 512],
                    "max_batch_rows": 512},
        "model_params": {"fit_rows": 512, "nominal_rows_per_day": 512},
    },
    "traffic": {
        "fill_batches": 40, "fill_batch_rows": 512, "pool_envelopes": 4096,
        # a draw no rehearsal's window outlasts: the file's draw_wraps 0
        # holds here too
        "draw_rows": 131072, "max_poll_rows": 512,
        "check_window_rows": 1 << 20,
    },
}
SEED = 3_200_000_123


def test_the_cell_resolves_to_its_files_and_only_the_key_path_differs():
    manifest = harness.load_manifest()
    cell = harness.Cell(ROOT, manifest, CELL)
    one = harness.Cell(ROOT, manifest, "forest.saturate")
    assert cell.chips == 1 and cell.regime == "sat"
    assert [m["name"] for m in cell.end_to_end()] == ["rows_per_s",
                                                      "setup_s"]
    assert cell.entry["traffic"] == "saturate-arriving"
    assert cell.traffic.pop("generator") == "debezium_cards_active"
    assert one.traffic.pop("generator") == "debezium_cards"
    # saturate key for key, but for a draw that outlasts the window and
    # the rule that keeps it so (test_generator.py holds the arithmetic)
    assert cell.traffic.pop("draw_rows") == 4 * one.traffic.pop("draw_rows")
    assert cell.traffic.pop("limits") == {"draw_wraps": 0}
    assert cell.traffic.pop("derived_from")["cell"] == CELL
    assert cell.traffic.pop("why") != one.traffic.pop("why")
    assert cell.traffic == one.traffic
    ex, d8 = cell.config, one.config
    for key in ("limits", "guarantees", "runtime", "ingest", "model",
                "model_params", "reduced", "chips"):
        assert ex[key] == d8[key], key
    fe, f8 = dict(ex["features"]), dict(d8["features"])
    assert (fe.pop("key_mode"), f8.pop("key_mode")) == ("exact", "direct")
    assert fe.pop("keydir_probes") == 16 and fe.pop("compact_every") == 64
    assert fe == f8  # windows, delay, buckets, slots: the same
    slots = {"customers": fe["customer_capacity"],
             "terminals": fe["terminal_capacity"]}
    assert ex["key_universe"] == {k: 2 * v for k, v in slots.items()}
    assert ex["active_keys"] == {k: v // 2 for k, v in slots.items()}
    from real_time_fraud_detection_system_tpu.config import FeatureConfig
    from real_time_fraud_detection_system_tpu.features.online import (
        state_bytes,
    )

    by_tier = state_bytes(FeatureConfig(**dict(
        ex["features"], windows=tuple(fe["windows"]))))
    assert ex["state_bytes"] == by_tier.pop("total") == 8_409_579_848
    assert ex["state_bytes_by_tier"] == by_tier
    assert by_tier["dense"] == d8["state_bytes"]
    assert ex["source"] != d8["source"] and "init.sql" in ex["source"]
    mine = {m["name"]: m for m in cell.per_layer()}
    theirs = {m["name"] for m in one.per_layer()}
    assert set(NEW) <= set(mine) and not set(NEW) & theirs
    assert {mine[n]["layer"] for n in NEW} == {
        "key directory and sketch tier"}
    assert all(CELL in mine[n]["workloads"] for n in NEW)
    assert theirs <= set(mine)  # every .sat metric of forest.saturate
    assert not SHARDED & set(mine)


def traffic_of(seed, seconds=1.0):
    cell = harness.Cell(ROOT, harness.load_manifest(), CELL, TOY)
    gen = cell.plugin("generators", cell.traffic["generator"])
    return gen.build(cell.traffic, cell.config, seed, seconds, None), cell


def test_ids_are_a_seeded_sample_of_the_universe_under_the_same_laws():
    t, cell = traffic_of(SEED)
    again, _ = traffic_of(SEED)
    other, _ = traffic_of(SEED + 1)
    uni, active = cell.config["key_universe"], cell.config["active_keys"]
    for table, cap in (("customer", 16384), ("terminal", 16384)):
        ids = getattr(t, f"active_{table}_ids")
        n, u = active[table + "s"], uni[table + "s"]
        assert len(ids) == len(np.unique(ids)) == n  # without replacement
        assert ids.min() >= 0 and ids.max() < u
        # direct and hash would merge keys: about half the ids lie at or
        # past the slot count (u = 2 x slots)
        assert 0.45 < (ids >= cap).mean() < 0.55
        drawn = np.concatenate([getattr(t, f"fill_{table}"),
                                getattr(t, f"win_{table}")])
        assert np.isin(drawn, ids).all()
        for part in ("fill", "win"):
            np.testing.assert_array_equal(
                getattr(t, f"{part}_{table}"),
                getattr(again, f"{part}_{table}"))
        assert not np.array_equal(ids, getattr(other, f"active_{table}_ids"))
    # the laws are debezium_cards's over the ACTIVE keys. Customers: rate
    # linear in the rank, so the busiest half of the active customers
    # draws 3/4 of the rows
    n = active["customers"]
    drawn = np.concatenate([t.fill_customer, t.win_customer])
    hits = np.bincount(np.searchsorted(np.sort(t.active_customer_ids),
                                       drawn), minlength=n)
    assert (hits > 0).sum() <= n
    top_half = np.sort(hits)[n // 2:].sum() / hits.sum()
    assert 0.74 < top_half < 0.80  # 0.75 plus what sorting by draws adds
    # Terminals: Zipf(0.99) over the active terminals, whose busiest
    # takes 1 / (1 + (N^e - 1) / e) of the rows, e = 0.01 (the continuous
    # inverse CDF's first rank)
    n = active["terminals"]
    drawn = np.concatenate([t.fill_terminal, t.win_terminal])
    top = np.bincount(np.searchsorted(np.sort(t.active_terminal_ids),
                                      drawn), minlength=n).max()
    e = 1.0 - cell.traffic["terminal_zipf_s"]
    want = (2.0 ** e - 1.0) / (n ** e - 1.0)
    assert abs(top / len(drawn) - want) < 4 * np.sqrt(want / len(drawn))
    # what the harness and the reference call keeps its meaning
    look = t.lookup(np.arange(0, t.n_fill, 97))
    np.testing.assert_array_equal(look["customer_id"],
                                  t.fill_customer[::97])
    with pytest.raises(ValueError):
        cell.plugin("generators", "debezium_cards_active").sample_ids(
            np.random.default_rng(0), 10, 11)


US = 1_000_000  # picoseconds: every fabricated op runs a microsecond


def _op_names(engine, sig):
    text = engine.signature_step(sig).lower(
        *engine.signature_templates(sig)).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def program_trace(engine, between=("compact",)):
    """A device plane as the chip's trace would carry it, made from the
    program's own compiled ``op_name``s (the CPU's trace has no device
    plane): the engine's largest step, the programs named in ``between``
    (the compaction; the cold cell adds a promote), the step again — one
    op of a microsecond for every named op, one ``XLA Modules`` event a
    program — between two ops that make both steps whole. A metric file
    whose scope the program does not open reads 0 here as it would on the
    chip. → ``(trace for device_trace.summarize, ops, modules)``, the
    last two on ``device_scopes.load_lines``'s picosecond clock."""
    sigs = engine.dispatch_inventory()
    step = max((s for s in sigs if s.variant == "step"),
               key=lambda s: s.bucket)
    programs = [("jit_step", _op_names(engine, step))]
    for variant in between:
        sig = next(s for s in sigs if s.variant == variant)
        programs.append((f"jit_{variant}", _op_names(engine, sig)))
    programs.append(programs[0])
    ops, modules, t = [["%lead", 0, US, ""]], [], 2 * US
    for name, op_names in programs:
        modules.append([f"{name}(1)", t, len(op_names) * US])
        for op_name in op_names:
            ops.append(["%op", t, US, op_name])
            t += US
    ops.append(["%tail", t + US, US, ""])  # a hand-over later
    ns = [{"name": device_scopes.device_trace.MODULE_LINE, "events": [
        [n, s // 1000, d // 1000] for n, s, d in modules]},
        {"name": device_scopes.OP_LINE, "events": [
            [n, s // 1000, d // 1000] for n, s, d, _ in ops]}]
    return {"planes": [{"name": "/device:TPU:0", "lines": ns}]}, ops, modules


def rehearse(monkeypatch, trace, probes=None, seconds=3.0):
    seen = {}
    over = TOY if probes is None else harness.merge(
        TOY, {"config": {"features": {"keydir_probes": probes}}})
    if trace:
        install_program_trace(monkeypatch, seen)
    result = harness.run_cell(
        CELL, SEED, seconds, trace, time.perf_counter(), allow_cpu=True,
        overrides=over,
        sabotage=lambda engine, sink: seen.update(engine=engine, sink=sink))
    return result, seen


def install_program_trace(monkeypatch, seen, between=("compact",)):
    """The reduction reads ``program_trace`` of the engine the run built
    (``seen["engine"]``) in place of the profiler's file."""
    made = {}

    def fabricated():
        if not made:
            made["t"] = program_trace(seen["engine"], between)
        return made["t"]

    monkeypatch.setattr(harness.device_trace, "load_xplane",
                        lambda path: fabricated()[0])
    inner = harness.traced_metrics

    def with_the_programs_scopes(cell, trace_dir, traced, done, device,
                                 ctx):
        _, ops, modules = fabricated()
        ctx[device_scopes.CTX_KEY] = device_scopes.per_step(ops, modules)
        return inner(cell, trace_dir, traced, done, device, ctx)

    monkeypatch.setattr(harness, "traced_metrics", with_the_programs_scopes)


def reclaimed_and_back(t, done, n_before=4):
    """Keys the window's first compaction must have reclaimed (last seen
    on a fill day no query of the window's day can see, and in none of the
    window's first ``n_before`` batches: the compaction comes after the
    2nd, and two more may be in flight) that a later batch brings back."""
    day0 = t.start_us // US_PER_DAY
    horizon = 7 + 30
    out = {}
    for table in ("customer", "terminal"):
        fill, win = getattr(t, f"fill_{table}"), getattr(t, f"win_{table}")
        last = {}
        for k, d in zip(fill.tolist(),
                        (t.fill_us // US_PER_DAY - day0).tolist()):
            last[k] = d
        dead = {k for k, d in last.items() if d < -horizon}
        ids = [ids - t.n_fill for _, b, ids in done if b > t.fill_batches]
        before = set(win[np.concatenate(ids[:n_before]) % t.draw_rows]
                     .tolist())
        after = set(win[np.concatenate(ids[n_before:]) % t.draw_rows]
                    .tolist())
        out[table] = (dead - before, (dead - before) & after)
    return out


def test_rehearsal_is_correct_through_a_compaction_with_the_cells_metrics(
        monkeypatch):
    result, seen = rehearse(monkeypatch, trace=True)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    line = result["metrics"]
    for name in NEW:
        assert name in line, (name, sorted(line))
    value = {n: line[n]["value"] for n in NEW}
    parts = sum(value[f"step_keydir_{p}_ms.sat"]
                for p in ("lookup", "claim", "grant"))
    assert value["step_keydir_ms.sat"] > 0
    assert parts == pytest.approx(value["step_keydir_ms.sat"], rel=1e-9)
    assert min(value[f"step_keydir_{p}_ms.sat"]
               for p in ("lookup", "claim", "grant")) > 0
    assert value["step_cms_ms.sat"] > 0 and value["step_compact_ms.sat"] > 0
    assert value["tier_cms_rows.sat"] == 0.0  # every active key owns a slot
    assert value["compactions.sat"] >= 1 and value["compact_wait_ms.sat"] > 0
    assert line["recompiles.sat"]["value"] == 0.0  # ("compact",) was AOT
    t, _ = traffic_of(SEED, 3.0)
    back = reclaimed_and_back(t, seen["sink"].done)
    gone = sum(len(dead) for dead, _ in back.values())
    assert all(len(b) > 0 for _, b in back.values()), {
        k: (len(d), len(b)) for k, (d, b) in back.items()}
    # at least these were reclaimed in the window (others may have died
    # between the fill's last compaction and this one)
    n = value["slots_reclaimed.sat"] * value["compactions.sat"]
    assert n >= gone > 0
    # and every window row was compared: the keys that came back answered
    # as the reference does, from a fresh slot
    rows = {c["name"]: c["value"] for c in result["checks"]}
    assert rows["rows_compared"] >= result["attempted"]


def test_one_probe_serves_rows_from_the_sketch_and_is_not_correct(
        monkeypatch):
    result, seen = rehearse(monkeypatch, trace=False, probes=1, seconds=1.0)
    tier = seen["engine"].metrics.get("rtfds_feature_tier_rows_total",
                                      tier="cms")
    assert tier is not None and tier.value > 0
    assert result["correct"] is False
    assert "exact_columns_wrong" in {
        c["name"] for c in result["checks"] if not c["ok"]}
