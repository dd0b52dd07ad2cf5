"""The model builders give every seed the same amount of work."""

import os

import numpy as np
import pytest

from benchmark import harness
from benchmark.models import logreg

CONFIG = harness.merge(
    harness.load_json(os.path.join(harness.ROOT, "benchmark", "configs",
                                   "logreg-15f.json")),
    harness.load_json(os.path.join(harness.ROOT, "benchmark", "tests", "data",
                                   "toy_overrides.json"))["config"])


@pytest.mark.parametrize("seed", [7, 2_700_000_205, 4_100_000_017])
def test_logistic_weights_keep_their_signs_and_draw_their_sizes(seed):
    """The signs decide how the probability column saturates, and with it
    what the Parquet writer has to do (PERF.md, PR 27): they come from the
    configuration, only the sizes from the seed."""
    mp = CONFIG["model_params"]
    w = np.asarray(logreg.build(CONFIG, seed)["params"].w)
    lo, hi = mp["weight_abs_range"]
    assert np.array_equal(np.sign(w), mp["weight_signs"])
    assert (np.abs(w) >= lo).all() and (np.abs(w) <= hi).all()
    other = np.asarray(logreg.build(CONFIG, seed + 1)["params"].w)
    assert not np.array_equal(w, other)


def test_logistic_weight_signs_are_checked():
    bad = harness.merge(CONFIG, {"model_params": {"weight_signs": [1, 0]}})
    with pytest.raises(ValueError):
        logreg.build(bad, 1)
