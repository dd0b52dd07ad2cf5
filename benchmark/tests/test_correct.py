"""`correct` at a size a test run can hold (the toy overrides, on the CPU,
the look for a chip skipped): sound runs come out true; the lower-precision
control and a timed path broken underneath come out false."""

import os
import time

import numpy as np
import pytest

from benchmark import harness

TOY = harness.load_json(os.path.join(
    harness.ROOT, "benchmark", "tests", "data", "toy_overrides.json"))


def run(workload, seed, overrides=TOY, **kw):
    return harness.run_cell(workload, seed, 1.0, False, time.perf_counter(),
                            allow_cpu=True, overrides=overrides, **kw)


def failed(numbers):
    return sorted(n["name"] for n in numbers if not n["ok"])


@pytest.mark.parametrize("workload", ["forest.saturate", "forest.steady",
                                      "logreg.saturate"])
def test_sound_run_is_correct_and_the_control_is_not(workload):
    result = run(workload, 4_100_000_017, control=True)
    assert result["correct"] is True, failed(result["checks"])
    assert result["failed"] == 0 and result["attempted"] > 0
    # bf16 in the reference's place: the integer-valued columns, the
    # averages and the probabilities all leave their limits
    assert {"exact_columns_wrong", "avg_amount_max_rel",
            "prob_max_abs"} <= set(failed(result["control"]))
    by = {n["name"]: n for n in result["control"]}
    assert by["avg_amount_max_rel"]["value"] > 100 * 5e-6
    assert by["prob_max_abs"]["value"] > 100 * 1e-5


def alter_an_answer(engine, sink):
    """One probability moved by a tenth of a tree vote where the engine
    produces it."""
    inner = engine._finish_batch

    def finish(handle):
        res = inner(handle)
        res.probs = np.array(res.probs)
        res.probs[0] += 1e-3
        return res

    engine._finish_batch = finish


def drop_part_of_a_batch(engine, sink):
    """The step leaves out the last row of every batch."""
    inner = engine._start_batch
    engine._start_batch = lambda cols: inner(
        {k: v[:-1] for k, v in cols.items()})


def forget_a_window_update(engine, sink):
    """Every 4th batch's step returns its state unchanged (the state of
    before the batch is put back after the step)."""
    import jax

    inner = engine._start_batch
    calls = [0]

    def start(cols):
        calls[0] += 1
        if calls[0] % 4:
            return inner(cols)
        keep = jax.tree.map(lambda x: x.copy(), engine.state.feature_state)
        handle = inner(cols)
        engine.state.feature_state = keep
        return handle

    engine._start_batch = start


def lose_an_acknowledged_batch(engine, sink):
    """The sink acknowledges the 5th batch without writing it."""
    inner = sink.inner.append
    sink.inner.append = lambda res: None if res.batch_index == 5 \
        else inner(res)


@pytest.mark.parametrize("sabotage, caught_by", [
    (alter_an_answer, {"prob_max_abs"}),
    (drop_part_of_a_batch, {"rows_not_delivered"}),
    (forget_a_window_update, {"exact_columns_wrong"}),
    (lose_an_acknowledged_batch, {"sink_rows_off", "sink_part_gaps"}),
])
def test_a_broken_timed_path_is_not_correct(sabotage, caught_by):
    result = run("forest.saturate", 4_100_000_018, sabotage=sabotage)
    assert result["correct"] is False
    assert caught_by <= set(failed(result["checks"])), failed(
        result["checks"])


def test_a_window_that_outruns_its_draw_is_not_correct():
    """A traffic file that carries the draw rule states ``draw_wraps`` 0:
    a window that polls more rows than the draw holds begins it again, no
    key is new from there on, and the run fails its check by that number
    alone — every answer is still right."""
    short = harness.merge(TOY, {"traffic": {"draw_rows": 4096,
                                            "limits": {"draw_wraps": 0}}})
    result = run("forest.saturate", 4_100_000_019, overrides=short)
    assert result["correct"] is False
    assert failed(result["checks"]) == ["draw_wraps"]
    by = {n["name"]: n for n in result["checks"]}
    assert by["draw_wraps"]["value"] >= 1 and by["draw_wraps"]["limit"] == 0
    # the same window on a draw that outlasts it
    long = harness.merge(TOY, {"traffic": {"draw_rows": 1 << 20,
                                           "limits": {"draw_wraps": 0}}})
    result = run("forest.saturate", 4_100_000_019, overrides=long)
    assert result["correct"] is True, failed(result["checks"])
    assert {n["name"]: n["value"] for n in result["checks"]}[
        "draw_wraps"] == 0
