"""Ask the installed TPU compiler — no chip needed — whether the serving
programs of the main path compile for a v5e at the real sizes.

The sandbox has no accelerator, but libtpu can compile for a chip that is
DESCRIBED (``v5e:2x2``) and not attached. Interpret-mode parity tests
cannot see what this sees: a kernel whose VMEM demand the chip's compiler
refuses, a program that does not fit HBM, a step that cannot be
partitioned. (PR 21 found one: the fused forest kernel was refused at
65,536 rows with 1024-row tiles.) A compile that passes here is not a
chip run — ``chip_smoke.py`` is.

Rules this file keeps (one process may hold libtpu; xdist workers each
import every test file): the topology is described inside a
module-scoped fixture, never at import / in ``skipif`` / in
``parametrize`` arguments; everything built from it is built in a fixture
or a test; the compile runs in the test's own process; the persistent
compilation cache is off around the compiles (an entry written for a
described chip cannot be read back without one).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

CUSTOMER_SLOTS = 1 << 20
TERMINAL_SLOTS = 1 << 21
N_TREES, DEPTH, N_FEAT = 100, 8, 15


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    # whatever libtpu raises when it cannot describe a chip here
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_chip(monkeypatch):
    """The repo's own backend checks (``resolve_z_mode``, the kernels'
    ``interpret`` default, the bf16 z dtype) see the CPU here; steer them
    in the test — not through a new option — to the branch the chip
    takes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _on(sharding, tree):
    """Shape-only templates of ``tree`` placed by ``sharding``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fcfg():
    from real_time_fraud_detection_system_tpu.config import FeatureConfig

    return FeatureConfig(customer_capacity=CUSTOMER_SLOTS,
                         terminal_capacity=TERMINAL_SLOTS)


def _state_shapes(fcfg, **kw):
    from real_time_fraud_detection_system_tpu.features.online import (
        init_feature_state,
    )

    return jax.eval_shape(lambda: init_feature_state(fcfg, **kw))


def _forest():
    """The flagship ensemble's shapes: T=100, depth 8, 15 features."""
    from real_time_fraud_detection_system_tpu.models.forest import (
        synthetic_ensemble,
        to_gemm,
    )

    return to_gemm(synthetic_ensemble(N_TREES, DEPTH, N_FEAT), N_FEAT)


def _vec(sharding):
    return jax.ShapeDtypeStruct((N_FEAT,), jnp.float32, sharding=sharding)


def _packed(rows, sharding):
    return jax.ShapeDtypeStruct((7, rows), jnp.int32, sharding=sharding)


@pytest.fixture(scope="module")
def compiled_steps():
    """(kind, bucket) → the engine's compiled step: two tests read the
    forest's, and a compile at this size takes a quarter of a minute."""
    return {}


def _compiled_step(cache, one_chip, kind, bucket, fcfg=None):
    """The DEFAULT served step (`rtfds score`, no flags) of the engine
    itself, at 2^20 + 2^21 state slots (or ``fcfg``'s) and the on-chip
    z_mode."""
    from real_time_fraud_detection_system_tpu.config import (
        Config,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.models.forest import (
        resolve_z_mode,
    )
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine

    key = (kind, bucket, fcfg)
    if key in cache:
        return cache[key]
    assert resolve_z_mode("auto") == "int8"  # what the chip resolves
    fcfg = fcfg or _fcfg()
    cfg = Config(features=fcfg, runtime=RuntimeConfig(
        z_mode="int8", batch_buckets=(bucket,), max_batch_rows=bucket))
    eng = ScoringEngine(
        cfg, kind=kind,
        params=_forest() if kind == "forest" else init_logreg(N_FEAT),
        scaler=Scaler(mean=np.zeros(N_FEAT, np.float32),
                      scale=np.ones(N_FEAT, np.float32)),
        # shapes only: nothing is allocated
        feature_state=_on(one_chip, _state_shapes(fcfg)))
    (sig,) = eng.dispatch_inventory()
    assert sig.bucket == bucket
    cache[key] = eng.signature_step(sig).lower(
        *_on(one_chip, eng.signature_templates(sig))).compile()
    return cache[key]


@pytest.mark.parametrize("bucket", [256, 4096, 65536])
def test_xla_forest_step_compiles(topo, one_chip, as_on_chip,
                                  compiled_steps, bucket):
    compiled = _compiled_step(compiled_steps, one_chip, "forest", bucket)
    mem = compiled.memory_analysis()
    # the state alone is ~1.9 GB of arguments; all of it fits one chip
    assert mem.argument_size_in_bytes > 1.8e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16e9
    assert "tpu_custom_call" not in compiled.as_text()  # pure XLA


_HLO_CONV = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* convolution\("
    r"%([\w.\-]+), %([\w.\-]+)\)(.*)$")
_HLO_TYPED = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]")


def convolutions_under(hlo_text, scope):
    """``{"op_name", "result": (dtype, dims), "operands": [(dtype, dims),
    (dtype, dims)], "highest": bool}`` of every convolution (what a
    contraction compiles to on the chip) whose ``op_name`` holds
    ``scope``."""
    typed = {}
    for line in hlo_text.splitlines():
        m = _HLO_TYPED.match(line)
        if m:
            typed[m.group(1)] = (
                m.group(2), tuple(int(d) for d in m.group(3).split(",") if d))
    out = []
    for line in hlo_text.splitlines():
        m = _HLO_CONV.match(line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not m or not name or scope not in name.group(1).split("/"):
            continue
        out.append({
            "op_name": name.group(1),
            "result": typed[m.group(1)],
            "operands": [typed[m.group(4)], typed[m.group(5)]],
            "highest": "highest" in m.group(6).lower()})
    return out


def check_one_pass_selector(hlo_text, slab_rows):
    """The forest's ``classify`` as the chip's compiler kept it: ONE
    selector contraction, under ``rtfds.classify`` › ``rtfds.decide``, of
    bfloat16 operands 3·F deep (x in three parts beside the selector
    three times, ``models/forest.split_bf16x3``) into float32 — one pass
    of the MXU — and no float32 × float32 contraction left in the stage:
    at ``HIGHEST`` that is six passes, 11 ms more a 65,536-row step (my
    chip runs, PR 47), at anything less a rounded feature."""
    convs = convolutions_under(hlo_text, "rtfds.classify")
    decide = [c for c in convs if "rtfds.decide" in c["op_name"].split("/")]
    leaves = [c for c in convs if "rtfds.leaves" in c["op_name"].split("/")]
    assert len(decide) == 1 and len(leaves) == 1 and len(convs) == 2, convs
    (c,) = decide
    assert c["result"][0] == "f32", c
    assert sorted(c["result"][1]) == sorted(
        (slab_rows, N_TREES, 2 ** DEPTH - 1)), c
    for dtype, dims in c["operands"]:
        assert dtype == "bf16" and 3 * N_FEAT in dims, c
        assert N_FEAT not in dims, c
    # the stage's own metric still reads both parts: the whole path is on
    # every operation, the slab loop's `while/body/…` between the two
    for c in convs:
        scopes = [p for p in c["op_name"].split("/") if p.startswith("rtfds.")]
        assert scopes[-2] == "rtfds.classify", c
        assert not c["highest"], c
        assert all(dtype != "f32" for dtype, _ in c["operands"]), c


_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")
_LAYOUT_MOVES = {"copy", "reshape", "transpose", "dynamic-update-slice"}


def whole_column_moves(hlo_text, column_sizes):
    """``(op, dtype, dims)`` of every instruction, fused ones included,
    that moves a table column as a whole: a ``copy`` / ``reshape`` /
    ``transpose`` / ``dynamic-update-slice`` whose result has as many
    elements as a column of either table."""
    out = []
    for line in hlo_text.splitlines():
        m = _HLO_RESULT.match(line)
        if not m or m.group(3) not in _LAYOUT_MOVES:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        if int(np.prod(dims)) in column_sizes:
            out.append((m.group(3), m.group(1), tuple(dims)))
    return out


@pytest.mark.parametrize("bucket", [4096, 65536])
@pytest.mark.parametrize("kind", ["forest", "logreg"])
def test_step_moves_no_table_between_layouts(topo, one_chip, as_on_chip,
                                             compiled_steps, kind, bucket):
    """The window columns are stored in the layout their update works in
    (flat, slot-major), so the chip's compiler has no column to re-lay
    out: before PR 25 each stored [cap, 40] column went copy → reshape →
    update → reshape → copy, 206.7 of a 305.4 ms step at the benchmark's
    size (PERF.md). Nor is a column copied: the update reads and writes
    it in place at the batch's buckets (until PR 43 one flat int32 copy
    of the day stamps a table remained, the old stamps set aside for the
    table-wide compare)."""
    fcfg = _fcfg()
    nb = fcfg.n_day_buckets
    text = _compiled_step(compiled_steps, one_chip, kind, bucket).as_text()
    for cap in (fcfg.customer_capacity, fcfg.terminal_capacity):
        assert not whole_column_moves(text, {cap * nb})


@pytest.mark.parametrize("bucket", [256, 4096, 65536])
def test_forest_picks_its_split_features_in_one_bf16_pass(
        topo, one_chip, as_on_chip, compiled_steps, bucket):
    from real_time_fraud_detection_system_tpu.models.forest import (
        LEAF_SLAB_ROWS,
    )

    text = _compiled_step(compiled_steps, one_chip, "forest",
                          bucket).as_text()
    check_one_pass_selector(text, min(bucket, LEAF_SLAB_ROWS))


_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
_HLO_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_ELEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s32": 4, "u32": 4, "f32": 4}
# instructions that hand buffers on without streaming them
_NO_PASS = {"parameter", "tuple", "get-tuple-element", "bitcast",
            "conditional", "while", "call", "optimization-barrier"}


def _computations(hlo_text):
    """name → body of every computation; the entry's is ``"ENTRY"``."""
    return {("ENTRY" if m.group(1) else m.group(2)): m.group(3)
            for m in re.finditer(
                r"^(ENTRY )?%([\w.\-]+) [^\n]*\{\n(.*?)^\}", hlo_text,
                re.S | re.M)}


def _reached_from(bodies, roots):
    """The bodies of the computations ``roots`` call, theirs too
    (fusions, reducers, nested loops)."""
    seen, todo = {}, list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in bodies:
            continue
        seen[name] = bodies[name]
        todo += re.findall(
            r"(?:calls|to_apply|condition|body)=%([\w.\-]+)", bodies[name])
    return seen


def _loops_under(hlo_text, scope):
    """``(op_name, condition, body)`` — the computations' names — of every
    ``while`` whose op_name holds ``scope``."""
    loop = re.compile(
        r" while\(.*condition=%([\w.\-]+), body=%([\w.\-]+)"
        r'.*op_name="([^"]*' + re.escape(scope) + r'[^"]*)"')
    return [(m.group(3), m.group(1), m.group(2))
            for m in map(loop.search, hlo_text.splitlines()) if m]


def _sketch_read_loops(hlo_text, bodies):
    """``(op_name, {computation it runs: body})`` of every ``while`` named
    under ``rtfds.cms``."""
    return [(op, _reached_from(bodies, (cond, body)))
            for op, cond, body in _loops_under(hlo_text, "rtfds.cms")]


def sketch_read_loops(hlo_text):
    """``(op_name, [bodies of the computations it runs])`` of every
    ``while`` of a compiled step named under ``rtfds.cms``: the sketch
    tier's chunked read, one a table."""
    return [(op, list(inside.values())) for op, inside in
            _sketch_read_loops(hlo_text, _computations(hlo_text))]


def claim_loops(hlo_text):
    """``(op_name, its condition's text, [bodies of the computations its
    body runs])`` of every ``while`` of a compiled step named under
    ``rtfds.claim``: the directory's claim rounds, one loop a table."""
    bodies = _computations(hlo_text)
    return [(op, bodies[cond], list(_reached_from(bodies, [body]).values()))
            for op, cond, body in _loops_under(hlo_text, "rtfds.claim")]


def reads_a_mask_of(condition, rows):
    """Whether a ``while`` condition works on a ``pred[rows]`` of its
    carry — the placed mask, reduced to "a row is unplaced" however the
    compiler writes the reduction (a fixed trip count compares a counter
    alone and never takes the mask out of the tuple)."""
    masks = set(re.findall(
        r"%([\w.\-]+) = pred\[" + str(rows) + r"\]", condition))
    for line in condition.splitlines():
        m = _HLO_INSTR.match(line)
        if m and m.group(3) not in _NO_PASS and masks & set(
                re.findall(r"%([\w.\-]+)", m.group(4).split(")", 1)[0])):
            return True
    return False


def sketch_table_gathers(hlo_text, table_dims):
    """``(inside a sketch read loop, elements read)`` of every ``gather``
    of a compiled step whose operand is a whole sketch table
    (``[days, depth, width]``, whatever type the compiler reads it
    as)."""
    bodies = _computations(hlo_text)
    in_loops = {name for _, inside in _sketch_read_loops(hlo_text, bodies)
                for name in inside}
    dims = ",".join(map(str, table_dims))
    out = []
    for name, body in bodies.items():
        shape = {m.group(1): m.group(2) for m in re.finditer(
            r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]", body, re.M)}
        for m in re.finditer(
                r"= \w+\[([\d,]*)\]\S* gather\(%([\w.\-]+),", body):
            if shape.get(m.group(2)) == dims:
                out.append((name in in_loops, int(np.prod(
                    [int(d) for d in m.group(1).split(",")]))))
    return out


def column_passes(hlo_text, n):
    """``(name, op, bytes_read, bytes_written, operands)``, bytes per
    element, of every instruction of the entry computation that streams
    an ``n``-element array: counted from the instruction's operand and
    result shapes, so a fusion counts once whatever it holds. A fusion
    around a scatter or a gather updates or reads its table in place at
    the batch's buckets only and is no pass."""
    bodies = _computations(hlo_text)
    shapes, out = {}, []
    for line in bodies["ENTRY"].splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name, result, op, rest = m.groups()
        shapes[name] = [_ELEM_BYTES[d] for d, dims in
                        _HLO_ARRAY.findall(result) if dims == str(n)]
        if op in _NO_PASS:
            continue
        called = re.search(r"calls=%([\w.\-]+)", rest)
        body = bodies.get(called.group(1), "") if called else ""
        if " scatter(" in body or " gather(" in body:
            continue
        operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
        read = sum(sum(shapes.get(o, ())) for o in operands)
        if read or shapes[name]:
            out.append((name, op, read, sum(shapes[name]), operands))
    return out


def _root_operands(hlo_text):
    (root,) = [ln for ln in _computations(hlo_text)["ENTRY"].splitlines()
               if ln.lstrip().startswith("ROOT ")]
    return re.findall(r"%([\w.\-]+)", root.split(" tuple(", 1)[1])


@pytest.mark.parametrize("bucket", [4096, 65536])
@pytest.mark.parametrize("kind", ["forest", "logreg"])
def test_update_makes_no_pass_over_a_window_column(topo, one_chip,
                                                   as_on_chip,
                                                   compiled_steps, kind,
                                                   bucket):
    """Nothing the step's program says follows the tables' size: the
    update merges its batch first and reads and writes a column at the
    batch's buckets only, so no instruction of the entry computation
    streams a whole window column — until PR 43 the reset did, 34 bytes a
    bucket and table (25 of an 89 ms step at 2^22 + 2^23 slots), and
    until PR 29 50. The column a table does not maintain goes from the
    step's input to its output as the same buffer. (What the chip's
    sorted scatter does inside its fusion is the next test's.)"""
    fcfg = _fcfg()
    nb = fcfg.n_day_buckets
    text = _compiled_step(compiled_steps, one_chip, kind, bucket).as_text()
    root = _root_operands(text)
    for cap, table, unmaintained in (
            (fcfg.customer_capacity, "customer", "fraud"),
            (fcfg.terminal_capacity, "terminal", "amount")):
        assert not column_passes(text, cap * nb), table
        (param,) = {o for o in root
                    if o.startswith(f"fstate_{table}_{unmaintained}")}
        assert re.search(rf"%{re.escape(param)} = \S+ parameter\(", text)


def test_column_passes_counts_the_table_wide_update(topo, one_chip):
    """``column_passes`` finds nothing in the step; that it can find
    something: the table-wide form the update had until PR 43 (kept as
    ``tests/test_ops.py``'s oracle), compiled for the chip, streams its
    columns — the compare and a reset a maintained column."""
    from real_time_fraud_detection_system_tpu.ops.windows import (
        init_window_state,
    )
    from test_ops import _table_wide_update

    cap, nb, rows = 1 << 16, 40, 4096
    state = _on(one_chip, jax.eval_shape(
        lambda: init_window_state(cap, nb)))
    cols = [jax.ShapeDtypeStruct((rows,), dt, sharding=one_chip)
            for dt in (jnp.int32, jnp.int32, jnp.float32, jnp.float32,
                       jnp.bool_)]
    text = jax.jit(_table_wide_update, donate_argnums=(0,)).lower(
        state, *cols).compile().as_text()
    passes = column_passes(text, cap * nb)
    assert sum(p[2] + p[3] for p in passes) >= 3 * 8, passes


def window_column_scatters(hlo_text, column_sizes):
    """The text of every ``scatter`` of a compiled program whose operand
    is a whole window column (a result of ``column_sizes`` elements)."""
    sizes = "|".join(str(n) for n in sorted(column_sizes))
    return re.findall(
        rf"^\s*(?:ROOT )?%[\w.\-]+ = \w+\[(?:{sizes})\]\S* scatter\(.*$",
        hlo_text, re.M)


def merge_sort_compares(hlo_text):
    """table → the number of ``compare`` instructions in the comparator
    of the sort under ``rtfds.<table>/rtfds.update/rtfds.merge``: one for
    a sort by one key, three (``a < b or (a == b and c < d)``) for two."""
    bodies = _computations(hlo_text)
    out = {}
    for region, table in re.findall(
            r' sort\(.*to_apply=%([\w.\-]+),.*op_name="[^"]*'
            r'rtfds\.(\w+)/rtfds\.update/rtfds\.merge/sort"', hlo_text):
        assert table not in out, table
        out[table] = bodies[region].count(" compare(")
    return out


def test_update_writes_its_columns_sorted_at_the_benchmarks_size(
        topo, one_chip, as_on_chip, compiled_steps):
    """What the chip's compiler made of the update at the benchmark's
    2^22 + 2^23 slots and 65,536 rows, which the 2^20 + 2^21 fixtures of
    this file cannot show: the compiler sorts a scatter's indices itself,
    and marks it sorted, only under ~1,024 operand elements an update —
    42 M / 84 M-element columns at 65,536 rows are on that side, the
    benchmark's 168 M / 335 M are not, and an ``x.at[i].add(v)`` there
    is the bare scatter that costs 89.6 ns a row (PERF.md, PR 43). The
    update sorts its batch once a table and says so on every write
    where a pass over the column is the cheaper (``_sorted_write_pays``:
    here it is): three scatters a table into window columns, each
    ``indices_are_sorted=true`` (every lane of a run writes the run's
    one value, so the indices are not unique and do not say so); the
    two sorts under ``rtfds.update`` are the program's own
    (``rtfds.merge/sort``), and the compiler added none to any
    scatter of the update. The customer table, which maintains a dollar
    sum, sorts by (bucket, lane); the terminal table by the bucket
    alone: ~4 s less of the chip's compile."""
    fcfg, compiled = _compiled_exact(compiled_steps, one_chip, "step")
    text = compiled.as_text()
    nb = fcfg.n_day_buckets
    writes = window_column_scatters(
        text, {fcfg.customer_capacity * nb, fcfg.terminal_capacity * nb})
    assert len(writes) == 6, writes
    for w in writes:
        assert "indices_are_sorted=true" in w, w
        assert "rtfds.update/" in w, w
    sorts = re.findall(r' sort\(.*op_name="([^"]*rtfds\.update[^"]*)"',
                       text)
    assert sorted(s.split("jit(step)/")[-1] for s in sorts) == [
        f"rtfds.{t}/rtfds.update/rtfds.merge/sort"
        for t in ("customer", "terminal")], sorts
    assert merge_sort_compares(text) == {"customer": 3, "terminal": 1}


def test_update_writes_its_columns_plain_at_256_rows(topo, one_chip,
                                                     as_on_chip,
                                                     compiled_steps):
    """The same tables under the engine's smallest bucket: three passes a
    table would be 19.7 ms for a batch that 6 × 256 plain updates serve
    in 0.14 (PERF.md, PR 43), so no write says ``indices_are_sorted`` —
    and the compiler, which would sort them itself only into a column of
    under ~1,024 elements an update, adds no sort of its own."""
    from real_time_fraud_detection_system_tpu.config import FeatureConfig

    fcfg = FeatureConfig(customer_capacity=1 << 22,
                         terminal_capacity=1 << 23)
    text = _compiled_step(compiled_steps, one_chip, "logreg", 256,
                          fcfg).as_text()
    nb = fcfg.n_day_buckets
    writes = window_column_scatters(
        text, {fcfg.customer_capacity * nb, fcfg.terminal_capacity * nb})
    assert len(writes) == 6, writes
    for w in writes:
        assert "indices_are_sorted" not in w, w
    assert len(re.findall(r" sort\(", text)) == 2
    assert merge_sort_compares(text) == {"customer": 3, "terminal": 1}


@pytest.mark.parametrize("z_mode", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("bucket", [4096, 65536])
def test_fused_forest_step_compiles(topo, one_chip, as_on_chip, bucket,
                                    z_mode):
    """``fused_forest_leaf_sum`` via ``update_and_score_pallas_forest`` —
    refused by the v5e compiler at 65,536 rows before PR 21 (16.23 MB of
    scoped VMEM against a 16 MB limit, 1024-row tiles)."""
    from real_time_fraud_detection_system_tpu.core.batch import unpack_batch
    from real_time_fraud_detection_system_tpu.features.online import (
        update_and_score_pallas_forest,
    )
    from real_time_fraud_detection_system_tpu.ops.pallas_forest import (
        to_pallas,
    )

    fcfg = _fcfg()

    def step(fstate, g, mean, scale, packed):
        pf = to_pallas(g, z_mode)
        fstate, leaf, feats = update_and_score_pallas_forest(
            fstate, unpack_batch(packed), fcfg, mean, scale, pf)
        return fstate, leaf / pf.n_trees, feats

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        _on(one_chip, _state_shapes(fcfg)), _on(one_chip, _forest()),
        _vec(one_chip), _vec(one_chip), _packed(bucket, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_leaf_sum_compiles(topo, one_chip, as_on_chip):
    """The classify-only kernel (the predict swap `--use-pallas` serves
    in exact/CMS/sharded modes) at the largest default bucket."""
    from real_time_fraud_detection_system_tpu.ops.pallas_forest import (
        pallas_leaf_sum,
        to_pallas,
    )

    x = jax.ShapeDtypeStruct((65536, N_FEAT), jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(
        lambda g, x: pallas_leaf_sum(to_pallas(g, "int8"), x)).lower(
            _on(one_chip, _forest()), x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_logreg_step_compiles(topo, one_chip, as_on_chip):
    from real_time_fraud_detection_system_tpu.core.batch import unpack_batch
    from real_time_fraud_detection_system_tpu.features.online import (
        update_and_score_pallas,
    )

    fcfg = _fcfg()

    def step(fstate, mean, scale, w, b, packed):
        return update_and_score_pallas(
            fstate, unpack_batch(packed), fcfg, mean, scale, w, b)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        _on(one_chip, _state_shapes(fcfg)), _vec(one_chip), _vec(one_chip),
        _vec(one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        _packed(65536, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_step_compiles_on_four_chips(topo, as_on_chip):
    """What `score --devices 4` serves for a 65,536-row micro-batch: the
    shard_map step over a four-device mesh (chunk = 4 × 2 × 16,384 rows),
    with the terminal exchange's two all_to_alls at the tight bucket and
    at the chunk's width, and a quarter of the state on each device."""
    from real_time_fraud_detection_system_tpu.config import Config
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.parallel.step import (
        make_sharded_step,
    )
    from real_time_fraud_detection_system_tpu.runtime.engine import (
        predict_fn_for,
    )

    n_dev = 4
    mesh = Mesh(np.asarray(topo.devices[:n_dev]), ("data",))
    fcfg = _fcfg()
    cfg = Config(features=fcfg)
    rows_per_shard = 2 * (cfg.runtime.max_batch_rows // n_dev)
    fstate = _state_shapes(fcfg)
    slots = NamedSharding(mesh, P("data"))  # flat slot-major columns
    rep = NamedSharding(mesh, P())
    fstate = fstate._replace(customer=_on(slots, fstate.customer),
                             terminal=_on(slots, fstate.terminal))
    params, scaler = _on(rep, _forest()), Scaler(mean=_vec(rep),
                                                 scale=_vec(rep))
    packed = _packed(n_dev * rows_per_shard,
                     NamedSharding(mesh, P(None, "data")))
    build = make_sharded_step(cfg, predict_fn_for("forest", z_mode="int8"),
                              mesh=mesh, axis="data", packed=True,
                              batch_rows=cfg.runtime.max_batch_rows)
    compiled = build(fstate, params, scaler, packed).lower(
        fstate, params, scaler, packed).compile()
    text = compiled.as_text()
    # the owner-placed program's terminal planes: one a branch, the
    # smaller n_dev x 2 x the balanced load of the batch's 16,384 rows a
    # chip ([n_dev, bucket, 5] uint32 forward, [n_dev, bucket, 6] float32
    # back)
    widths = sorted(n_dev * int(b) for b in re.findall(
        r"= u32\[4,(\d+),5\]\S* all-to-all\(", text))
    assert widths == [32768, 131072], widths
    assert text.count(" all-to-all(") == 2 * len(widths)
    assert len(re.findall(r" conditional\(", text)) == 1
    # 32,768 rows a chip: four slabs of the one-chip step's classify
    assert rows_per_shard == 4 * 8192
    check_one_pass_selector(text, 8192)
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert 0.2 * 1.9e9 < per_device < 0.3 * 2.1e9  # ~a quarter of the state


def test_sharded_exact_compaction_fits_four_chips(topo, as_on_chip):
    """The mesh's ``("compact",)`` program at the size of the benchmark's
    ``forest-rf100-d8-x4-exact`` (2^24 + 2^25 slots over four chips,
    stacked directories of twice that): per device the one-chip exact
    cell's pass over the one-chip cell's state, donated, its table-wide
    part on the flat columns as on one chip — the program PR 32 found the
    chip's compiler refusing outright in its first form, and which no
    chip had run inside ``shard_map`` before PR 44. (The two step variants
    take ~45 s each to compile here: the verify skill has the recipe.)"""
    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
    )
    from real_time_fraud_detection_system_tpu.parallel.step import (
        make_sharded_compact,
    )

    n_dev = 4
    mesh = Mesh(np.asarray(topo.devices[:n_dev]), ("data",))
    fcfg = FeatureConfig(customer_capacity=1 << 24,
                         terminal_capacity=1 << 25, key_mode="exact",
                         keydir_probes=16, compact_every=64)
    dev, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    shapes = _state_shapes(fcfg, n_shards=n_dev)
    # the sketches in the per-device layout the mesh gives them
    shapes = shapes._replace(**{
        name: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((n_dev,) + a.shape, a.dtype),
            getattr(shapes, name)) for name in ("cms", "terminal_cms")})
    compact = make_sharded_compact(Config(features=fcfg), mesh)
    compiled = compact.lower(
        _on(dev, shapes),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    assert compiled.as_text().startswith("HloModule jit_compact")
    mem = compiled.memory_analysis()
    state = 8_409_579_848  # features/online.state_bytes(n_shards=4) / 4
    assert state <= mem.argument_size_in_bytes < state + 1e6
    assert mem.alias_size_in_bytes >= state  # donated, updated in place
    # the one-chip pass's 0.346 GB: no padded view, no column copied
    assert mem.temp_size_in_bytes < 0.39e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9
    text = compiled.as_text()
    assert "all-to-all" not in text  # a shard's own business
    nb = fcfg.n_day_buckets
    for cap in (fcfg.customer_capacity // n_dev,
                fcfg.terminal_capacity // n_dev):
        assert not whole_column_moves(text, {cap * nb})
        check_swept_inside_one_trip_loop(text, cap * nb)


EXACT_ROWS = 65536


def _compiled_exact(cache, one_chip, variant, rows=EXACT_ROWS, cold=False,
                    key_bits=32):
    """``key_mode="exact"`` at the size of the benchmark's
    ``forest-rf100-d8-exact`` (2^22 + 2^23 slots, directories of twice
    that, 16 probes, sketches at their defaults): the ``rows``-row step
    or the ``("compact",)`` program of the engine itself, compiled for
    one v5e → (features config, compiled). ``cold``: with
    ``forest-rf100-d8-cold``'s tier armed (a pass every 4 batches that
    demotes up to 131,072 keys a table over a fifth of the slots).
    ``key_bits=64``: ``forest-rf100-d8-id64``'s width."""
    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine

    tier = dict(compact_every=4, cold_store="tmp://rtfds-compile",
                cold_demote_slots=131072, cold_highwater=0.2) if cold \
        else dict(compact_every=64)
    fcfg = FeatureConfig(customer_capacity=1 << 22,
                         terminal_capacity=1 << 23, key_mode="exact",
                         keydir_probes=16, key_bits=key_bits, **tier)
    key = ("exact", variant, rows, cold, key_bits)
    if key not in cache:
        eng = ScoringEngine(
            Config(features=fcfg, runtime=RuntimeConfig(
                z_mode="int8", batch_buckets=(rows,), max_batch_rows=rows)),
            kind="forest", params=_forest(),
            scaler=Scaler(mean=np.zeros(N_FEAT, np.float32),
                          scale=np.ones(N_FEAT, np.float32)),
            feature_state=_on(one_chip, _state_shapes(fcfg)))
        (sig,) = [s for s in eng.dispatch_inventory()
                  if s.variant == variant]
        cache[key] = eng.signature_step(sig).lower(
            *_on(one_chip, eng.signature_templates(sig))).compile()
    return fcfg, cache[key]


@pytest.mark.parametrize("variant", ["step", "compact"])
def test_exact_key_programs_fit_the_chip_at_the_benchmarks_size(
        topo, one_chip, as_on_chip, compiled_steps, variant):
    """The exact step and the compaction compile for one v5e with their
    state donated and room to spare. The compaction is the one the CPU
    cannot vouch for: written with a lane per directory entry into
    ``set_rows``, its ``[2^24, 40]`` element indices alone made the
    chip's compiler refuse it — 18.41 GB of 15.75 (PERF.md, PR 32) —
    while every CPU test passed."""
    _, compiled = _compiled_exact(compiled_steps, one_chip, variant)
    mem = compiled.memory_analysis()
    state = 8_409_579_848  # features/online.state_bytes
    assert mem.argument_size_in_bytes >= state
    assert mem.alias_size_in_bytes >= state  # donated, updated in place
    # the chip has 15.75 GB for a program; the direct step's temporaries
    # are 1.7 GB, the compaction's 0.35 (4.37 while it viewed a column
    # as a padded [2^23, 40]: PERF.md, PR 54)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9
    if variant == "compact":
        assert mem.temp_size_in_bytes < 0.39e9


def indexed_ops(hlo_text):
    """``(gather | scatter, lanes, inside a conditional's branch)`` of
    every one in a compiled program — lanes: how many indices it works
    on, the index operand's longest side."""
    bodies = _computations(hlo_text)
    branches = _reached_from(bodies, [
        name for group in re.findall(
            r"branch_computations=\{([^}]*)\}", hlo_text)
        for name in re.findall(r"%([\w.\-]+)", group)] + re.findall(
            r"(?:true|false)_computation=%([\w.\-]+)", hlo_text))
    out = []
    for name, body in bodies.items():
        shape = {m.group(1): [int(d) for d in m.group(2).split(",") if d]
                 for m in re.finditer(
                     r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]", body,
                     re.M)}
        for m in re.finditer(
                r" (gather|scatter)\(%[\w.\-]+, %([\w.\-]+)", body):
            out.append((m.group(1), max(shape.get(m.group(2)) or [1]),
                        name in branches))
    return out


def one_trip_sweeps(hlo_text, n):
    """``[{the computations its body runs}]`` of every ``while`` of a
    compiled pass that carries the four ``n``-element window columns of
    a table and can run one trip at most: its condition is ``carry[0] >
    0`` and its body hands back a constant 0 there
    (``WindowState.clear_slots``)."""
    bodies = _computations(hlo_text)
    out = []
    for m in re.finditer(
            r"= \((.*?)\) while\(.*condition=%([\w.\-]+), "
            r"body=%([\w.\-]+)", hlo_text):
        carried, cond, body = m.groups()
        if len(re.findall(rf"\w+\[{n}\]", carried)) != 4:
            continue
        test = re.findall(
            r"ROOT %[\w.\-]+ = pred\[\]\S* compare\(%([\w.\-]+), "
            r"%([\w.\-]+)\), direction=GT", bodies[cond])
        if not test:  # the demote payload's loop reads the columns too
            continue
        (test,) = test
        assert re.search(rf"%{re.escape(test[0])} = s32\[\]\S* "
                         r"get-tuple-element\(.*index=0", bodies[cond])
        assert re.search(rf"%{re.escape(test[1])} = s32\[\]\S* "
                         r"constant\(0\)", bodies[cond])
        (root,) = [ln for ln in bodies[body].splitlines()
                   if ln.lstrip().startswith("ROOT ")]
        left = re.search(r" tuple\(%([\w.\-]+)", root).group(1)
        assert re.search(
            rf"%{re.escape(left)} = s32\[\]\S* (?:copy\(%[\w.\-]+\)|"
            r"constant\(0\))", bodies[body]), root[:200]
        out.append(set(_reached_from(bodies, [body])))
    return out


def column_selects(hlo_text, n):
    """The names of the computations that hold a ``select`` whose result
    is a flat ``[n]`` array — a column's new value (``newest``'s masked
    reads are ``[groups, rows, lanes]`` inside their reduce)."""
    return {name for name, body in _computations(hlo_text).items()
            for line in body.splitlines()
            for m in [_HLO_RESULT.match(line)]
            if m and m.group(3) == "select" and m.group(2) == str(n)}


def check_swept_inside_one_trip_loop(hlo_text, n):
    """A table's ``n``-element columns are written — one flat select
    each — only inside the one at-most-one-trip loop that carries them."""
    (sweep,) = one_trip_sweeps(hlo_text, n)
    selects = column_selects(hlo_text, n)
    assert selects and selects <= sweep, selects - sweep


@pytest.mark.parametrize("cold", [False, True],
                         ids=["forest-exact", "forest-cold"])
def test_compaction_is_as_wide_as_what_it_vacates(
        topo, one_chip, as_on_chip, compiled_steps, cold):
    """What the chip's compiler made of the pass at the two exact cells'
    size: the one operation with a lane a directory entry — the
    ``newest[slot]`` gather, 2^23 and 2^24 lanes — sits inside a
    conditional's branch, a table, so a table that gives nothing up
    never runs it; no ``scatter`` is that wide anywhere (the parent's
    two, a table, were 333 ms of a 0.71 s pass: PERF.md, PR 35); every
    other gather and scatter works on a packed chunk of 16,384 lanes, or
    on the demote payload's 131,072. The conditionals yield
    ``[dir_cap]`` vectors alone: no window column and no directory comes
    out of one, or is copied anywhere in the program — nor viewed as
    ``[cap, 40]``: ``newest`` and the clear work on the flat columns, and
    the clear's selects stand inside a loop of one trip at most, a table
    (until PR 54 the padded views were 4.37 GB of temporaries and 74-88
    ms of every pass, whatever it took)."""
    from real_time_fraud_detection_system_tpu.ops.keydir import pack_lanes

    fcfg, compiled = _compiled_exact(compiled_steps, one_chip, "compact",
                                     cold=cold)
    text = compiled.as_text()
    dirs = (2 * fcfg.customer_capacity, 2 * fcfg.terminal_capacity)
    ops = indexed_ops(text)
    wide = [op for op in ops if op[1] >= min(dirs)]
    assert sorted(wide) == [("gather", d, True) for d in dirs], wide
    lanes = pack_lanes(dirs[0])
    assert lanes == 16384
    narrow = {n for _, n, _ in ops if n < min(dirs)}
    assert narrow <= {lanes, fcfg.cold_demote_slots}, narrow
    assert sum(kind == "scatter" for kind, _, _ in ops) == 4
    for m in re.finditer(r"= \((.*?)\) conditional\(", text):
        sizes = {int(n) for n in re.findall(r"\w+\[(\d+)\]", m.group(1))}
        assert sizes <= set(dirs), m.group(0)[:200]
    nb = fcfg.n_day_buckets
    columns = {cap * nb for cap in (fcfg.customer_capacity,
                                    fcfg.terminal_capacity)}
    # the table-wide part works on the columns as they are stored (PR
    # 54): no [cap, 40] view of one, no broadcast mask re-laid flat
    assert not whole_column_moves(text, columns)
    assert f"[{fcfg.terminal_capacity},{nb}]" not in text
    assert f"[{fcfg.customer_capacity},{nb}]" not in text
    # and a table is swept — one select a column — only inside the
    # at-most-one-trip loop that carries its four columns
    for n in columns:
        check_swept_inside_one_trip_loop(text, n)
    copied = [ln.strip()[:120] for ln in text.splitlines() if re.search(
        rf"= \w+\[({dirs[0]}|{dirs[1]})\]\S* copy\(", ln)]
    # the running counts' layout changes apart (s32 [n/128, 128] views),
    # the compiler copies one directory-sized vector a table: the vacated
    # ``slots`` on their way out — never a window column
    assert len(copied) <= 2, copied
    # 0.346 GB: a table's per-element mask (pred, a quarter of a column)
    # and vectors a directory entry wide — less than the smaller table's
    # one column (0.67 GB), so the sweep's carry is updated in place; the
    # demote variant holds its payload's loop-carried buffers and its
    # selection's vectors beside (1.382 GB; the parent's 4.30 / 4.80)
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < (1.53e9 if cold else 0.39e9), temps


@pytest.mark.parametrize("rows", [256, EXACT_ROWS])
def test_exact_step_reads_the_sketch_a_chunk_at_a_time_inside_its_loops(
        topo, one_chip, as_on_chip, compiled_steps, rows):
    """What the chip's compiler made of the sketch tier's read rule
    (``ops/cms.cms_query_where``): no gather from a sketch table stands
    outside a ``while`` named under ``rtfds.cms`` — a batch in which no
    row missed admission runs no trip and reads no sketch — and the
    gathers inside read ``[depth, K, 30 days]`` elements, a chunk's, not
    the batch's ``[depth, 65,536, 30]`` that made four 99 ms operations
    of a 567 ms step (PERF.md, PR 32). One loop a table, two columns
    each: the terminal side asks for count and fraud and gets no amount
    gather for the compiler to find dead. At the smallest bucket too: a
    chunk that were the whole batch would be a loop-invariant read,
    which the compiler hoists out of the loop for every batch to pay."""
    from real_time_fraud_detection_system_tpu.ops.cms import chunk_rows

    fcfg, compiled = _compiled_exact(compiled_steps, one_chip, "step", rows)
    text = compiled.as_text()
    loops = sketch_read_loops(text)
    assert sorted(op.split("/")[1] for op, _ in loops) == [
        "rtfds.customer", "rtfds.terminal"], [op for op, _ in loops]
    gathers = sketch_table_gathers(
        text, (fcfg.n_day_buckets, fcfg.cms_depth, fcfg.cms_width))
    k = chunk_rows(rows)
    assert k == {256: 128, EXACT_ROWS: 256}[rows]
    assert gathers == [(True, fcfg.cms_depth * k * max(fcfg.windows))] * 4, \
        gathers


def check_claim_rounds(hlo_text, loops, rows, probes, dir_caps):
    """``loops`` (``claim_loops``' triples, one table's, one admit's) are
    the two loops of rounds and the pack's search between them; nothing
    in them or anywhere in the program copies a directory column."""
    from real_time_fraud_detection_system_tpu.ops.keydir import claim_lanes

    lanes = claim_lanes(rows)
    rounds = [loop for loop in loops
              if sum(c.count(" scatter(") for c in loop[2])]
    # the search holds no scatter and is handed no directory
    assert len(loops) == 3 and len(rounds) == 2, [op for op, *_ in loops]
    (wide,) = [loop for loop in rounds if reads_a_mask_of(loop[1], rows)]
    (narrow,) = [loop for loop in rounds if reads_a_mask_of(loop[1], lanes)]
    # the wide rounds run while MORE rows are unplaced than the lanes hold
    assert f"constant({lanes})" in wide[1]
    for op, condition, inside in rounds:
        table = op.split("/")[1]
        assert f"constant({probes})" in condition
        named = [n for c in inside for n in re.findall(
            r'op_name="([^"]*)"', c) if n.startswith("jit(")]
        # one round a trip: ONE scatter in everything the body runs
        assert sum(c.count(" scatter(") for c in inside) == 1
        assert any(n.endswith("/scatter-min") for n in named)
        off = [n for n in named if n.split("/")[1] != table
               or "/rtfds.keydir/rtfds.claim/" not in n]
        assert not off, off[:3]
    copies = [ln.strip()[:160] for ln in hlo_text.splitlines() if re.search(
        r"= \w+\[(%s)\]\S* copy(-start)?\(" % "|".join(
            map(str, dir_caps)), ln)]
    assert not copies, copies[:2]


@pytest.mark.parametrize("rows", [256, EXACT_ROWS])
def test_exact_step_runs_its_claim_rounds_while_a_row_is_unplaced(
        topo, one_chip, as_on_chip, compiled_steps, rows):
    """What the chip's compiler made of ``admit_slots``' claim rounds:
    two ``while`` s a table — not 16 unrolled rounds, not a fixed trip
    count: the first's condition counts the ``[rows]`` placed mask
    against the narrow lanes beside its ``j < 16``, the second's reduces
    the ``[lanes]`` mask of the packed rows — with every named op of
    their bodies under ``<table>/rtfds.keydir/rtfds.claim``
    (``step_keydir_claim_ms`` holds the two loops, the pack between them
    and nothing else), the scatter-min updating the directory's keys in
    place: no copy of a directory-sized array in a trip, between the
    loops or anywhere else (PERF.md, PR 35: the 48.6 ms the 2 x 16 fixed
    rounds cost a 168.9 ms step; PR 51: a round costs what its rows
    cost)."""
    fcfg, compiled = _compiled_exact(compiled_steps, one_chip, "step", rows)
    text = compiled.as_text()
    loops = claim_loops(text)
    dirs = {"rtfds.customer": 2 * fcfg.customer_capacity,
            "rtfds.terminal": 2 * fcfg.terminal_capacity}
    assert {op.split("/")[1] for op, _, _ in loops} == set(dirs)
    for table in dirs:
        check_claim_rounds(
            text, [loop for loop in loops if loop[0].split("/")[1] == table],
            rows, fcfg.keydir_probes, dirs.values())


@pytest.mark.parametrize("variant", ["step", "compact"])
def test_wide_key_programs_fit_the_chip_at_the_benchmarks_size(
        topo, one_chip, as_on_chip, compiled_steps, variant):
    """``key_bits=64`` at ``forest-rf100-d8-id64``'s size: the step and
    the compaction compile for one v5e with their state — 8 bytes a
    directory entry more than the 32-bit deployment's — donated and
    updated in place, and the loops the wide admit adds (the lookup's
    verify trips, the passes after the first) copy no directory-sized
    array a trip: the owner's key words are scattered into the leaves
    they live in."""
    fcfg, compiled = _compiled_exact(compiled_steps, one_chip, variant,
                                     key_bits=64)
    mem = compiled.memory_analysis()
    state = 8_610_906_440  # features/online.state_bytes
    assert mem.argument_size_in_bytes >= state
    assert mem.alias_size_in_bytes >= state
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.7e9
    if variant == "compact":
        assert mem.temp_size_in_bytes < 0.39e9  # the 32-bit pass's 0.346
        return
    text = compiled.as_text()
    bodies = _computations(text)
    keydir = _loops_under(text, "rtfds.keydir")
    # a table: the verify loop, the first pass's rounds and the rounds of
    # a further pass (each: the rounds over the batch, the pack's search,
    # the rounds over the packed lanes) under rtfds.keydir; the loop of
    # further passes beside it, under the table alone (its body names
    # keydir/<part>)
    assert len(keydir) == 14, [op for op, _, _ in keydir]
    claims = claim_loops(text)
    for table in ("customer", "terminal"):
        for a_pass in (f"rtfds.{table}/rtfds.keydir/",
                       f"rtfds.{table}/while/body/rtfds.keydir/"):
            check_claim_rounds(
                text, [loop for loop in claims if a_pass in loop[0]],
                EXACT_ROWS, fcfg.keydir_probes,
                (2 * fcfg.customer_capacity, 2 * fcfg.terminal_capacity))
    passes = [loop for loop in _loops_under(text, "rtfds.")
              if re.search(r"rtfds\.(customer|terminal)/while$", loop[0])]
    assert len(passes) == 2, [op for op, _, _ in passes]
    for op, cond, body in keydir + passes:
        inside = _reached_from(bodies, (cond, body)).values()
        for dir_cap in (2 * fcfg.customer_capacity,
                        2 * fcfg.terminal_capacity):
            copies = [ln for c in inside for ln in c.splitlines()
                      if re.search(
                          rf"= \w+\[{dir_cap}\]\S* copy(-start)?\(", ln)]
            assert not copies, (op, copies[:2])
