"""The split of the device step's time by stage, on two steps of
``forest.saturate`` recorded on the chip and on hand-made events."""

import json
import os
import sys

import pytest

from benchmark.readers import device_scopes

DATA = os.path.join(os.path.dirname(__file__), "data")
STAGES = {
    "relayout": ["rtfds.update/rtfds.relayout"],
    "stamp": ["rtfds.update/rtfds.stamp"],
    "reset": ["rtfds.update/rtfds.reset"],
    "scatter": ["rtfds.update/rtfds.scatter"],
    "query": ["rtfds.query"],
    "classify": ["rtfds.scale", "rtfds.classify", "rtfds.fused_step"],
}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "scopes_forest_saturate.json")) as f:
        return json.load(f)["events"]


def test_recorded_stages_and_the_rest_add_up_to_the_busy_union(recorded):
    t = device_scopes.table(recorded)
    busy = sum(e - s for s, e in _union(recorded))
    assert t["busy"] == busy
    parts = {k: device_scopes.under(t, v) for k, v in STAGES.items()}
    unscoped = device_scopes.under(t, None)
    other = device_scopes.under(t, [
        "rtfds.unpack", "rtfds.assemble", "rtfds.emit", "rtfds.learn",
        "rtfds.keydir", "rtfds.cms", "rtfds.exchange"])
    assert all(v > 0 for v in parts.values()), parts
    assert 0 < unscoped < busy
    # the seven metrics never count an instant twice ...
    assert sum(parts.values()) + unscoped <= busy
    # ... and with the stages no metric reads they are the whole step
    assert sum(parts.values()) + unscoped + other == pytest.approx(
        busy, rel=1e-3)
    # the recorded order of sizes (my chip run, PR 24): the relayout
    # passes and the compiler's unnamed layout copies are two thirds of it
    assert parts["relayout"] > parts["scatter"] > parts["stamp"]
    assert (parts["relayout"] + unscoped) / busy > 0.6


def test_recorded_while_and_its_body_count_once(recorded):
    whiles = [e for e in recorded if e[0].startswith("%while")]
    assert whiles  # the forest's slab loop
    covered = sum(d for _, _, d, _ in whiles)
    inside = [e for e in recorded if any(
        w[1] <= e[1] and e[1] + e[2] <= w[1] + w[2] and e is not w
        for w in whiles)]
    assert inside
    t = device_scopes.table(recorded)
    # the loop's time goes to its body's names (classify), once: summing
    # event durations would count it twice
    assert sum(d for _, _, d, _ in recorded) >= t["busy"] + 0.9 * covered
    classify = device_scopes.under(t, STAGES["classify"])
    assert classify >= 0.9 * covered


def _union(events):
    out = []
    for s, e in sorted((s, s + d) for _, s, d, _ in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _ev(name, start, dur, op_name=""):
    return [f"%{name} = f32[] x()", start, dur, op_name]


HAND = [
    _ev("copy.1", 0, 10, "fstate.terminal.count"),  # a parameter's name
    _ev("reshape.1", 10, 20,
        "jit(step)/rtfds.terminal/rtfds.update/rtfds.relayout/reshape"),
    _ev("while.2", 30, 50),  # no stat of its own
    _ev("fusion.3", 31, 20, "jit(step)/rtfds.classify/while/body/dot"),
    _ev("fusion.4", 52, 27, "jit(step)/rtfds.classify/while/body/add"),
    _ev("copy.9", 80, 5),  # compiler-inserted, no metadata
    _ev("fusion.5", 90, 10,  # after an idle gap of 5
        "jit(step)/rtfds.customer/rtfds.query/rtfds.gather/gather"),
    _ev("fusion.6", 100, 4, "jit(step)/rtfds.customer/rtfds.querying/x"),
]


def test_hand_made_events():
    t = device_scopes.table(HAND)
    assert t["busy"] == 85 + 14
    # the while's 50: 47 under its body's names, 3 of its own (unscoped)
    assert device_scopes.under(t, ["rtfds.classify"]) == 47
    assert device_scopes.under(t, ["rtfds.update/rtfds.relayout"]) == 20
    assert device_scopes.under(t, ["rtfds.terminal"]) == 20
    # components match whole and in sequence
    assert device_scopes.under(t, ["rtfds.query"]) == 10
    assert device_scopes.under(t, ["rtfds.customer/rtfds.query"]) == 10
    assert device_scopes.under(t, ["rtfds.customer/rtfds.gather"]) == 0
    assert device_scopes.under(t, ["rtfds.update"]) == 20
    # no rtfds. component: the parameter's copy, the bare copy, the
    # while's own 3
    assert device_scopes.under(t, None) == 10 + 5 + 3
    # a scope listed twice, or two that cover one op, count it once
    assert device_scopes.under(
        t, ["rtfds.classify", "rtfds.classify/while"]) == 47


def _steps(n, ops_in_a_step, between=()):
    """``n`` executions of a step of ``ops_in_a_step`` (each ``(length,
    op_name)``) with ``between`` after the first, a lead op before and a
    tail op after: → (ops, modules)."""
    ops, modules, t = [_ev("lead", 0, 7 * US)], [], 10 * US
    for i in range(n):
        start = t
        for j, (length, op_name) in enumerate(ops_in_a_step):
            ops.append(_ev(f"fusion.{j}", t, length * US, op_name))
            t += length * US
        modules.append(["jit_step(42)", start, t - start])
        if i == 0:
            for name, length, op_name in between:
                ops.append(_ev("pass", t, length * US, op_name))
                modules.append([name, t, length * US])
                t += length * US
        t += 2 * US  # the hand-over between two programs
    ops.append(_ev("tail", t, 5 * US))
    return ops, modules


US = device_scopes.PS_PER_US  # the hand-made steps count microseconds
STEP = [(30, "jit(step)/rtfds.terminal/rtfds.update/rtfds.reset/select_n"),
        (47, "jit(step)/rtfds.classify/while/body/dot"),
        (3, ""), (20, "jit(step)/rtfds.customer/rtfds.query/x")]


def test_read_divides_by_the_steps_the_trace_holds():
    ops, modules = _steps(4, STEP)
    t = device_scopes.per_step(ops, modules)
    assert t["n_steps"] == 4
    ctx = {device_scopes.CTX_KEY: t}
    ps = device_scopes.PS_PER_MS / US
    got = device_scopes.read(ctx, scopes=["rtfds.classify"], stat="ms")
    assert got == pytest.approx(47 / ps)  # 4 x 47 under the scope, 4 steps
    assert device_scopes.read(
        ctx, scopes=["rtfds.update/rtfds.reset"]) == pytest.approx(30 / ps)
    assert device_scopes.read(ctx, stat="unscoped_pct") == pytest.approx(3.0)
    with pytest.raises(ValueError):
        device_scopes.read(ctx, scopes=[], stat="share")
    # an execution the trace's head cut (it begins with the trace's first
    # operation) and one in flight at its end are no steps: their time
    # under a scope is outside the span and divides into nothing
    cut_ops = [e for e in ops if e[0] != "%lead = f32[] x()"]
    assert device_scopes.per_step(cut_ops, modules)["n_steps"] == 3
    no_tail = [e for e in ops if e[0] != "%tail = f32[] x()"]
    t3 = device_scopes.per_step(no_tail, modules)
    assert t3["n_steps"] == 3
    assert device_scopes.read({device_scopes.CTX_KEY: t3},
                              scopes=["rtfds.classify"]) == pytest.approx(
        47 / ps)


def test_a_program_of_its_own_is_read_per_step_beside_the_step():
    """A compaction between two steps: its scope reads its device time ÷
    the steps, the step's own stages and the unscoped share do not hold
    it."""
    between = [("jit_compact(7)", 400, "jit(compact)/rtfds.compact/gather"),
               ("jit_compact(7)", 100, "")]  # what it leaves unnamed
    ops, modules = _steps(4, STEP, between)
    ctx = {device_scopes.CTX_KEY: device_scopes.per_step(ops, modules)}
    ps = device_scopes.PS_PER_MS / US
    assert device_scopes.read(
        ctx, scopes=["rtfds.compact"]) == pytest.approx(400 / 4 / ps)
    assert device_scopes.read(
        ctx, scopes=["rtfds.classify"]) == pytest.approx(47 / ps)
    assert device_scopes.read(ctx, stat="unscoped_pct") == pytest.approx(3.0)
    # a pass after the last whole step is outside the span
    ops, modules = _steps(1, STEP, between)
    ctx = {device_scopes.CTX_KEY: device_scopes.per_step(ops, modules)}
    assert device_scopes.read(ctx, scopes=["rtfds.compact"]) == 0.0


def test_a_step_without_scopes_reads_as_nothing():
    bare = [_ev("fusion.1", 0, 10, "jit(step)/scatter-add"),
            _ev("copy.1", 10, 5)]
    assert device_scopes.table(bare) is None
    ops, modules = _steps(3, [(10, "jit(step)/scatter-add"), (5, "")])
    assert device_scopes.per_step(ops, modules) is None
    ops, modules = _steps(3, STEP)
    assert device_scopes.per_step(ops, []) is None  # no whole step
    ctx = {device_scopes.CTX_KEY: None}
    assert device_scopes.read(ctx, scopes=["rtfds.query"]) is None
    assert device_scopes.read(ctx, stat="unscoped_pct") is None


def test_no_trace_reads_as_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(device_scopes.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run.py", "--trace", "1"])
    assert device_scopes.find_trace() is None
    ctx = {}
    assert device_scopes.read(ctx, scopes=["rtfds.query"]) is None
    assert ctx[device_scopes.CTX_KEY] is None  # looked for once
    # an empty directory named on the command line: still nothing
    monkeypatch.setattr(sys, "argv", ["run.py", "--trace-dir",
                                      str(tmp_path / "kept")])
    assert device_scopes.find_trace() is None


def test_the_trace_file_is_found_and_parsed(tmp_path, monkeypatch):
    """A profiler trace written here (the CPU's: no device plane) is
    found by either rule and parses to no events."""
    import jax

    d = tmp_path / "rtfds-trace-abc"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=options)
    jax.numpy.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    monkeypatch.setattr(device_scopes.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run.py"])
    found = device_scopes.find_trace()
    assert found and found.endswith(".xplane.pb")
    monkeypatch.setattr(sys, "argv", ["run.py", f"--trace-dir={d}"])
    assert device_scopes.find_trace() == found
    assert device_scopes.load_lines(found) == ([], [])
    assert device_scopes.read({}, stat="unscoped_pct") is None
