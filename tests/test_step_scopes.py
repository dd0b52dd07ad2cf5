"""The step's stages are named from inside the program.

``utils/trace.STEP_SCOPES`` is the one vocabulary; every stage of the
jitted step opens its ``rtfds.<stage>`` with ``jax.named_scope`` where the
work is written, so a device op's HLO ``op_name`` reads e.g.
``jit(step)/rtfds.terminal/rtfds.update/rtfds.reset/select_n`` and the
benchmark's ``readers/device_scopes.py`` can split ``device_step_ms`` by
stage. For every variant of the step that compiles here at toy size:

- the lowered HLO (what the program says, before any compiler folds a
  reshape into a bitcast) carries every scope the variant should have,
  and no ``rtfds.`` component outside the vocabulary;
- in the COMPILED HLO every ``rtfds.update`` op sits under exactly one of
  ``rtfds.customer`` / ``rtfds.terminal``;
- the update writes no pass over a whole window column: it merges its
  batch first (``rtfds.update/rtfds.merge``) and touches a column at the
  batch's buckets only, so the one instruction under ``rtfds.update``
  whose result is a column is a scatter (``stamp`` / ``scatter``);
- scopes are metadata: the step's outputs are bit-equal to a build with
  the scopes patched to no-ops.
"""

import contextlib
import dataclasses as dc
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.features.spec import N_FEATURES
from real_time_fraud_detection_system_tpu.models.forest import (
    for_device,
    synthetic_ensemble,
)
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime import (
    ScoringEngine,
    ShardedScoringEngine,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (
    MetricsRegistry,
)
from real_time_fraud_detection_system_tpu.utils.trace import (
    STEP_SCOPES,
    get_tracer,
    step_scope,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmark.readers import device_scopes  # noqa: E402

from test_tpu_compile import (  # noqa: E402 (pytest adds tests/ to path)
    claim_loops,
    reads_a_mask_of,
    sketch_read_loops,
    sketch_table_gathers,
)

PKG = "real_time_fraud_detection_system_tpu"
TABLE = {"customer", "terminal"}
# `relayout` left the vocabulary with the stage it named: the window
# columns are stored flat, the layout `update_windows` works in (PR 25),
# and the metric files that read it went in PR 40
UPDATE = {"update", "merge", "stamp", "reset", "scatter"}
QUERY = {"query", "gather", "sum"}
COMMON = {"unpack", "assemble", "scale", "classify"} | TABLE | UPDATE | QUERY
# a tree ensemble's classify names its two parts (models/forest.py): the
# selector contraction with the threshold compare, and the z contraction
# with the leaf match, select and pinned-order sum
FOREST_PARTS = {"decide", "leaves"}
# admit_slots names its three parts: the full-depth probe, the claim
# rounds (over the batch while more rows are unplaced than the narrow
# lanes hold, the pack, then over the lanes until every row is placed),
# and the owner / free-stack / roll-back / final resolution
KEYDIR_PARTS = {"lookup", "claim", "grant"}

# variant → (kind, FeatureConfig overrides, RuntimeConfig overrides,
#            sharded over n virtual devices, scopes it must carry)
VARIANTS = {
    "forest": ("forest", {}, {}, 0, COMMON | FOREST_PARTS),
    "logreg": ("logreg", {}, {}, 0, COMMON),
    "exact": ("logreg", {"key_mode": "exact", "compact_every": 4}, {}, 0,
              COMMON | {"keydir", "cms"} | KEYDIR_PARTS),
    # key_bits=64: the same stages, the same names — the lookup's verify
    # loop under lookup, the owner's key words under grant, and a further
    # pass's rounds and grant under claim and grant (ops/keydir.admit_wide)
    "exact64": ("logreg", {"key_mode": "exact", "compact_every": 4,
                           "key_bits": 64}, {}, 0,
                COMMON | {"keydir", "cms"} | KEYDIR_PARTS),
    "cms": ("logreg", {"customer_source": "cms"}, {}, 0,
            COMMON | {"cms"}),
    "selective": ("forest", {}, {"emit_threshold": 0.4}, 0,
                  COMMON | FOREST_PARTS | {"emit"}),
    "online_sgd": ("logreg", {}, {"online_lr": 0.01}, 0,
                   COMMON | {"learn"}),
    # the exchange names its parts: ranking rows by owner, packing the
    # send buffer, and (under the engine's own `unpack`) the back-gather
    "sharded": ("forest", {}, {}, 2,
                COMMON | FOREST_PARTS | {"exchange", "route", "pack"}),
    # a mesh of one reaches both tables by a local call: the one-chip
    # step's body inside shard_map, scope path for scope path
    "sharded_1dev": ("forest", {}, {}, 1, COMMON | FOREST_PARTS),
}


def _engine(variant):
    kind, feat, run, n_dev, _ = VARIANTS[variant]
    run = dict(run)
    online_lr = run.pop("online_lr", 0.0)
    cfg = Config(
        features=FeatureConfig(customer_capacity=128, terminal_capacity=256,
                               cms_width=1 << 10, **feat),
        runtime=dc.replace(
            RuntimeConfig(batch_buckets=(64,), max_batch_rows=64), **run))
    params = (init_logreg(N_FEATURES) if kind == "logreg" else
              for_device(synthetic_ensemble(4, 3, N_FEATURES), N_FEATURES))
    scaler = Scaler(mean=np.full(N_FEATURES, 0.5, np.float32),
                    scale=np.full(N_FEATURES, 2.0, np.float32))
    kw = dict(kind=kind, params=params, scaler=scaler,
              metrics=MetricsRegistry(), online_lr=online_lr)
    if n_dev:
        return ShardedScoringEngine(cfg, n_devices=n_dev,
                                    rows_per_shard=32, **kw)
    return ScoringEngine(cfg, **kw)


def _op_names(hlo_text):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def _scopes(op_name):
    """The ``rtfds.`` components of one op_name, in order, bare."""
    return [c[len("rtfds."):] for c in op_name.split("/")
            if c.startswith("rtfds.")]


def _control_flow(hlo_text):
    """``(while | conditional, op_name)`` of every one in a program."""
    return re.findall(
        r' (while|conditional)\(.*op_name="([^"]*)"', hlo_text)


_COLUMN_OP = re.compile(
    r"^\s*(?:ROOT )?[\w.\-]+ = \w+\[(\d+)\]\S* ([\w\-]+)\(.*"
    r'op_name="([^"]*rtfds\.update[^"]*)"')


def _column_passes_under_update(hlo_text, column_sizes):
    """``(op, op_name)`` of every instruction under ``rtfds.update`` whose
    result is a whole window column, the scatters aside."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLUMN_OP.match(line)
        if (m and int(m.group(1)) in column_sizes
                and m.group(2) != "scatter"):
            out.append((m.group(2), m.group(3)))
    return out


def _lowered_steps(eng):
    return [eng.signature_step(sig).lower(*eng.signature_templates(sig))
            for sig in eng.dispatch_inventory()
            if sig.variant not in ("compact", "promote")]


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_step_hlo_carries_the_variants_scopes(variant):
    eng = _engine(variant)
    lowered = _lowered_steps(eng)
    assert lowered
    want = VARIANTS[variant][4]
    fcfg, n_dev = eng.cfg.features, max(1, VARIANTS[variant][3])
    column_sizes = {cap * fcfg.n_day_buckets // n_dev for cap in (
        fcfg.customer_capacity, fcfg.terminal_capacity)}
    for low in lowered:
        said_text = low.as_text(dialect="hlo", debug_info=True)
        said = {s for n in _op_names(said_text) for s in _scopes(n)}
        assert said <= set(STEP_SCOPES)
        assert want <= said, sorted(want - said)
        assert f"[{max(column_sizes)}]" in said_text  # the sizes are right
        assert not _column_passes_under_update(said_text, column_sizes)
        compiled = _op_names(low.compile().as_text())
        kept = {s for n in compiled for s in _scopes(n)}
        assert "relayout" not in STEP_SCOPES and "relayout" not in said
        # the compiler keeps the names on what it keeps of the ops
        assert (want - {"unpack"}) <= kept, sorted(want - kept)
        updates = [_scopes(n) for n in compiled if "rtfds.update" in n]
        assert updates
        for path in updates:
            assert len(TABLE & set(path)) == 1, path
            assert path.index("update") > min(
                path.index(t) for t in TABLE & set(path)), path
        # every table's update holds operations under each of its four
        # stages: a ``step_*_ms`` metric that reads none is a null
        for table in {t for path in updates for t in TABLE & set(path)}:
            held = {s for path in updates if table in path for s in path}
            assert UPDATE <= held, (table, sorted(UPDATE - held))
    if variant == "sharded_1dev":
        # one body, two engines: a per-layer metric file that names a
        # scope path reads both for as long as they share it
        # (the routed variant too: at one device every reach is local)
        (chip_step,) = _lowered_steps(_engine("forest"))
        chip, *mesh = [{tuple(_scopes(n)) for n in _op_names(
            low.as_text(dialect="hlo", debug_info=True))}
            for low in [chip_step] + lowered]
        assert len(mesh) == 2
        for paths in mesh:
            assert paths == chip, sorted(paths ^ chip)


@pytest.mark.parametrize("variant,rows", [
    ("forest", 64), ("forest", 2 * 8192 + 64), ("sharded", 64)])
def test_classify_names_its_two_parts(variant, rows):
    """``rtfds.decide`` and ``rtfds.leaves`` are opened inside
    ``rtfds.classify`` and nowhere else, directly under it as the scopes
    go (past ``LEAF_SLAB_ROWS`` the slab loop's own ``while/body/…`` sit
    between, which is why the benchmark's metric files name the part
    alone), every contraction of the stage is in one of them, and the
    files that read them find them."""
    eng = _engine(variant)
    if rows != 64:
        eng = ScoringEngine(
            dc.replace(eng.cfg, runtime=dc.replace(
                eng.cfg.runtime, batch_buckets=(rows,),
                max_batch_rows=rows)),
            kind="forest", params=eng.state.params,
            scaler=eng.state.scaler, metrics=MetricsRegistry())
    metrics = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "metrics")
    for low in _lowered_steps(eng):
        names = _op_names(low.compile().as_text())
        paths = [_scopes(n) for n in names]
        held = {part: [n for n, p in zip(names, paths) if part in p]
                for part in FOREST_PARTS}
        for n, p in zip(names, paths):
            for part in FOREST_PARTS & set(p):
                # (this backend's compiler names what the slab loop calls
                # from the call down; the chip's keeps the whole path:
                # tests/test_tpu_compile.py reads it at 65,536 rows)
                assert p[-2:] == ["classify", part] or (
                    rows != 64 and p == [part]), n
            if "classify" in p and n.endswith("dot_general"):
                assert FOREST_PARTS & set(p), n
        for part, under in held.items():
            assert any(n.endswith("dot_general") for n in under), part
            if rows != 64:  # the parts live in the slab loop's body
                assert all("while" in n.split("/") for n in under
                           if n.endswith("dot_general")), part
            for regime in ("sat", "steady"):
                with open(os.path.join(
                        metrics,
                        f"step_classify_{part}_ms.{regime}.json")) as f:
                    spec = json.load(f)
                assert spec["reader"] == "device_scopes"
                read = [n for n in names if any(
                    device_scopes.matches(n, s)
                    for s in spec["args"]["scopes"])]
                assert read == under, (part, regime)
        if rows != 64:
            continue
        # the whole stage is still one reading: both parts are inside it
        with open(os.path.join(metrics, "step_classify_ms.sat.json")) as f:
            whole = json.load(f)["args"]["scopes"]
        assert all(any(device_scopes.matches(n, s) for s in whole)
                   for under in held.values() for n in under)


@pytest.mark.parametrize("variant", ["exact", "exact64"])
def test_exact_key_path_names_its_parts_under_their_table(variant):
    """``key_mode="exact"``, at either key width: every op of
    ``admit_slots`` sits under ``<table>/rtfds.keydir/<part>`` with one of the three parts (the
    benchmark's ``step_keydir_{lookup,claim,grant}_ms`` add up to
    ``step_keydir_ms``), the sketch's update AND its query sit under
    ``<table>/rtfds.cms``, and the compaction — a program of its own —
    carries ``rtfds.compact`` on everything it names."""
    eng = _engine(variant)
    (low,) = _lowered_steps(eng)
    paths = [_scopes(n) for n in _op_names(low.compile().as_text())]
    keydir = [p for p in paths if "keydir" in p]
    assert keydir
    for p in keydir:
        i = p.index("keydir")
        assert i >= 1 and p[i - 1] in TABLE, p
        assert len(p) > i + 1 and p[i + 1] in KEYDIR_PARTS, p
    assert {p[p.index("keydir") + 1] for p in keydir} == KEYDIR_PARTS
    assert not [p for p in paths if KEYDIR_PARTS & set(p)
                and "keydir" not in p]
    said = [(_scopes(n), n) for n in _op_names(
        low.as_text(dialect="hlo", debug_info=True))]
    cms = [(p, n) for p, n in said if "cms" in p]
    for p, _ in cms:
        assert p[p.index("cms") - 1] in TABLE, p
    # the query's gathers as well as the update's scatter-adds
    assert any(n.endswith("gather") for _, n in cms)
    assert any("scatter" in n.rsplit("/", 1)[-1] for _, n in cms)
    if variant == "exact64":
        # the admit's further passes (a loop beside rtfds.keydir): their
        # rounds and their grant carry the two names side by side, as a
        # reader of "rtfds.keydir/rtfds.<part>" needs them
        later = [n for _, n in said if "/while/body/rtfds.keydir/" in n]
        for part in ("claim", "grant"):
            assert any(f"/while/body/rtfds.keydir/rtfds.{part}/" in n
                       for n in later), part
        assert all(re.search(r"rtfds\.keydir/rtfds\.(lookup|claim|grant)(/|$)",
                             n) for p, n in said if "keydir" in p)
    (sig,) = [s for s in eng.dispatch_inventory() if s.variant == "compact"]
    compact = eng.signature_step(sig).lower(*eng.signature_templates(sig))
    named = [_scopes(n) for n in _op_names(
        compact.as_text(dialect="hlo", debug_info=True))
        if n.startswith("jit(")]  # the rest: parameters, reducers' bodies
    assert named and all(p[:1] == ["compact"] for p in named), [
        p for p in named if p[:1] != ["compact"]][:3]
    text = compact.compile().as_text()
    kept = [_scopes(n) for n in _op_names(text)]
    assert any(p[:1] == ["compact"] for p in kept)
    # the pass's control flow — a conditional around the entry-wide
    # gather, a loop of packed trips, a table — is the stage's too
    flow = _control_flow(text)
    assert sorted(kind for kind, _ in flow).count("conditional") == 2
    assert all(_scopes(n)[:1] == ["compact"] for _, n in flow), flow


@pytest.mark.parametrize("n_dev,key_bits", [(0, 32), (2, 32), (0, 64)],
                         ids=["one-chip", "mesh", "one-chip-64"])
def test_cold_tier_programs_name_their_stages_as_siblings(n_dev, key_bits,
                                                          tmp_path):
    """With the cold store armed the compaction gains the demote pass
    and the engine a family of promote programs. Every named op of the
    compaction sits under ``rtfds.compact`` OR ``rtfds.demote`` — never
    both — and every named op of a promote under ``rtfds.keydir/<part>``
    (its admit, named as in the step) OR ``rtfds.promote``: siblings, so
    the benchmark's ``step_compact_ms`` + ``step_demote_ms`` and
    ``step_keydir_ms`` + ``step_promote_ms`` add up to their programs and
    no device time is read twice. Without the store the compaction names
    ``rtfds.compact`` alone, as before."""
    feat = {"key_mode": "exact", "compact_every": 4, "keydir_probes": 16,
            "cold_store": str(tmp_path / "cold"), "cold_demote_slots": 16,
            "key_bits": key_bits}
    VARIANTS["_cold"] = ("logreg", feat, {}, n_dev, set())
    try:
        eng = _engine("_cold")
    finally:
        del VARIANTS["_cold"]
    by_variant, flows = {}, {}
    for sig in eng.dispatch_inventory():
        if sig.variant in ("compact", "promote"):
            low = eng.signature_step(sig).lower(
                *eng.signature_templates(sig))
            names = _op_names(low.as_text(dialect="hlo", debug_info=True))
            paths = [p for p in map(_scopes, names) if p]
            assert paths and {s for p in paths for s in p} <= set(
                STEP_SCOPES)
            if not n_dev:  # (shard_map's own squeezes carry no stage)
                bare = [n for n in names if n.startswith("jit(")
                        and "/" in n and not _scopes(n)]
                # key_bits=64: the loop of the admit's further passes
                # stands beside rtfds.keydir — the `while` alone; its
                # condition and body name keydir/claim and keydir/grant
                assert set(bare) <= ({"jit(promote)/while"}
                                     if key_bits == 64 else set()), bare
            text = low.compile().as_text()
            kept = [_scopes(n) for n in _op_names(text)]
            by_variant.setdefault(sig.variant, []).append((paths, kept))
            flows[sig.variant] = _control_flow(text)
    ((paths, kept),) = by_variant["compact"]
    for p in paths:
        assert p[:1] in (["compact"], ["demote"]) and not (
            {"compact", "demote"} <= set(p)), p
    assert any(p[:1] == ["demote"] for p in kept)
    assert any(p[:1] == ["compact"] for p in kept)
    # the conditionals and loops of the pass, whatever the compiler kept
    # of them, belong to one of the two stages: none is unscoped time
    assert flows["compact"] and all(
        _scopes(n)[:1] in (["compact"], ["demote"])
        for _, n in flows["compact"]), flows["compact"]
    assert len(by_variant["promote"]) == 2 * len(eng._promote_widths)
    for paths, kept in by_variant["promote"]:
        for p in paths:
            assert p[:1] in (["keydir"], ["promote"]) and not (
                {"keydir", "promote"} <= set(p)), p
            if p[:1] == ["keydir"]:
                assert len(p) > 1 and p[1] in KEYDIR_PARTS, p
        assert any(p[:1] == ["promote"] for p in kept)
        assert {p[1] for p in kept if p[:1] == ["keydir"]} == KEYDIR_PARTS
    # the store not armed: no demote, no promote, in name or in program
    plain = _engine("exact")
    assert not [s for s in plain.dispatch_inventory()
                if s.variant == "promote"]
    (sig,) = [s for s in plain.dispatch_inventory()
              if s.variant == "compact"]
    said = {s for n in _op_names(plain.signature_step(sig).lower(
        *plain.signature_templates(sig)).as_text(
            dialect="hlo", debug_info=True)) for s in _scopes(n)}
    assert said == {"compact"}


def test_sketch_read_loop_is_all_under_cms_and_only_exact_has_one():
    """``key_mode="exact"`` reads the sketch in a ``while`` of chunks, a
    table: in the COMPILED step the loop, its condition, and every named
    op of its body — the chunk's gathers, the per-row arithmetic, the
    write-back — sit under ``<table>/rtfds.cms``, so ``step_cms_ms`` holds
    the whole cost of the tier, and no gather from a sketch table stands
    outside a loop. The steps that bypass the rule have no such loop:
    ``customer_source="cms"`` reads the sketch for the whole batch, where
    every row IS served from it, and the ``direct`` steps name no
    ``rtfds.cms`` op at all."""
    def compiled(variant):
        eng = _engine(variant)
        low, *_ = _lowered_steps(eng)  # sharded: the local chunk's step
        text = low.compile().as_text()
        f = eng.cfg.features
        return text, sketch_read_loops(text), sketch_table_gathers(
            text, (f.n_day_buckets, f.cms_depth, f.cms_width))

    text, loops, gathers = compiled("exact")
    assert sorted(_scopes(op)[0] for op, _ in loops) == sorted(TABLE)
    for op, inside in loops:
        assert _scopes(op)[-1] == "cms", op
        named = [n for c in inside for n in _op_names(c)
                 if n.startswith("jit(")]  # the rest: reducers' bodies
        assert any(n.endswith("/gather") for n in named)
        assert any(n.endswith("/scatter") for n in named)
        off = [n for n in named if f"rtfds.{_scopes(op)[0]}/rtfds.cms/"
               not in n]
        assert not off, off[:3]
    # count + amount, count + fraud: [depth 4, a chunk's rows (half of
    # this toy bucket's 64), 30 days] elements each
    assert gathers == [(True, 4 * 32 * 30)] * 4, gathers
    text, loops, gathers = compiled("cms")
    assert not loops and "rtfds.cms" in text
    assert gathers == [(False, 4 * 64 * 30)] * 2, gathers
    for variant in ("forest", "logreg", "sharded"):
        text, loops, gathers = compiled(variant)
        assert not loops and not gathers and "rtfds.cms" not in text


def test_unknown_scope_is_refused():
    with pytest.raises(ValueError):
        step_scope("windows")


def _cols(n, day):
    rng = np.random.default_rng(day)
    us = (day * 86400 + np.arange(n) * 60).astype(np.int64) * 1_000_000
    return {
        "tx_id": np.arange(n, dtype=np.int64) + day * 1000,
        "tx_datetime_us": us,
        "customer_id": rng.integers(0, 100, n).astype(np.int64),
        "terminal_id": rng.integers(0, 200, n).astype(np.int64),
        "tx_amount_cents": rng.integers(100, 90_000, n).astype(np.int64),
        "kafka_ts_ms": us // 1000,
    }


def _run_two_batches(variant):
    eng = _engine(variant)
    out = []
    for day in (20_000, 20_001):
        res = eng.process_batch(_cols(50, day))
        out += [np.asarray(res.probs), np.asarray(res.features)]
    out += [np.asarray(x) for x in jax.tree.leaves(
        eng.state.feature_state)]
    return out


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_scopes_change_no_output_bit(variant, monkeypatch):
    with_scopes = _run_two_batches(variant)
    for name, mod in list(sys.modules.items()):
        if name.startswith(PKG) and hasattr(mod, "step_scope"):
            monkeypatch.setattr(
                mod, "step_scope", lambda name: contextlib.nullcontext())
    low = _lowered_steps(_engine(variant))[0]
    assert not any(_scopes(n) for n in _op_names(
        low.as_text(dialect="hlo", debug_info=True)))  # the patch took
    without = _run_two_batches(variant)
    assert len(with_scopes) == len(without)
    for a, b in zip(with_scopes, without):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def test_profile_to_turns_the_tracer_on_for_the_capture(tmp_path):
    from real_time_fraud_detection_system_tpu.utils import profile_to

    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=False)
    try:
        with profile_to(str(tmp_path / "trace")):
            assert tracer.enabled
            with tracer.span("dispatch"):
                pass
        assert not tracer.enabled
        assert list((tmp_path / "trace").rglob("*.xplane.pb"))
        with profile_to(None):  # no directory: nothing is touched
            assert not tracer.enabled
    finally:
        tracer.configure(enabled=was)


def test_claim_rounds_are_two_loops_a_table_that_end_on_the_placed_masks():
    """``key_mode="exact"``: the COMPILED step holds the claim rounds as
    two ``while`` s a table — not P unrolled rounds, not a fixed trip
    count: a batch of known keys runs no round. The first's condition
    counts the batch's placed mask against the lanes (rounds over the
    batch while more rows are unplaced than the lanes hold), the
    second's reduces the ``[lanes]`` mask of the packed rows; between
    them the pack, whose binary search is a third loop with no scatter
    in it. The loops, their conditions and every named op of their
    bodies sit under ``<table>/rtfds.keydir/rtfds.claim`` — the scope
    ``step_keydir_claim_ms`` reads, which is in the vocabulary already.
    The steps that take their slot from ``key_slot`` name no
    ``rtfds.keydir`` op and hold no such loop."""
    from real_time_fraud_detection_system_tpu.ops.keydir import claim_lanes

    eng = _engine("exact")
    (low,) = _lowered_steps(eng)
    text = low.compile().as_text()
    loops = claim_loops(text)
    rows, lanes = 64, claim_lanes(64)
    for table in TABLE:
        mine = [loop for loop in loops if _scopes(loop[0])[0] == table]
        rounds = [loop for loop in mine
                  if any(" scatter(" in c for c in loop[2])]
        assert len(mine) == 3 and len(rounds) == 2, [op for op, *_ in mine]
        assert sorted(reads_a_mask_of(cond, rows) + 2 * reads_a_mask_of(
            cond, lanes) for _, cond, _ in rounds) == [1, 2]
    for op, condition, inside in loops:
        assert _scopes(op)[1:] == ["keydir", "claim"], op
        named = [n for c in [condition] + inside for n in _op_names(c)
                 if n.startswith("jit(")]  # the rest: reducers' bodies
        if any(" scatter(" in c for c in inside):
            assert any(n.endswith("/scatter-min") for n in named)
        off = [n for n in named if _scopes(n)[:3] != _scopes(op)]
        assert not off, off[:3]
    assert "claim" in STEP_SCOPES
    for variant in ("forest", "logreg", "cms", "sharded"):
        low, *_ = _lowered_steps(_engine(variant))
        text = low.compile().as_text()
        assert not claim_loops(text) and "rtfds.keydir" not in text
