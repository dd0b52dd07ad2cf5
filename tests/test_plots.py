"""Evaluation plots (reference ``shared_functions.py:925-1302``)."""

import os

import matplotlib
matplotlib.use("Agg")

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.models.plots import (
    plot_execution_times,
    plot_model_comparison,
    plot_precision_recall,
    plot_prequential_summary,
    plot_roc,
    plot_threshold_metrics,
    pr_points,
    roc_points,
    save_plots,
)


@pytest.fixture(scope="module")
def scored():
    rng = np.random.default_rng(0)
    n = 2000
    y = (rng.random(n) < 0.1).astype(np.float64)
    s = np.clip(0.3 * y + 0.2 * rng.random(n), 0, 1)
    return y, s


def test_curves_degrade_gracefully_on_empty_input():
    empty = np.array([])
    fpr, tpr = roc_points(empty, empty)
    assert len(fpr) == len(tpr) >= 2
    rec, prec = pr_points(empty, empty)
    assert len(rec) == len(prec) >= 2
    # The figures build too (would previously IndexError).
    plot_roc(empty, empty)
    plot_precision_recall(empty, empty)


def test_roc_points_match_sklearn(scored):
    from sklearn.metrics import roc_curve

    y, s = scored
    fpr, tpr = roc_points(y, s)
    fpr_sk, tpr_sk, _ = roc_curve(y, s)
    # Same curve: trapezoid areas agree.
    area = np.trapezoid(tpr, fpr)
    area_sk = np.trapezoid(tpr_sk, fpr_sk)
    assert abs(area - area_sk) < 1e-9


def test_pr_points_match_sklearn(scored):
    from sklearn.metrics import precision_recall_curve

    y, s = scored
    recall, precision = pr_points(y, s)
    p_sk, r_sk, _ = precision_recall_curve(y, s)
    # Compare the step-integral (average precision style).
    ap = np.sum(np.diff(recall) * precision[1:])
    ap_sk = np.sum(np.diff(r_sk[::-1]) * p_sk[::-1][1:])
    assert abs(ap - ap_sk) < 1e-9


def test_figures_build(scored):
    y, s = scored
    assert plot_roc(y, s, "m") is not None
    assert plot_precision_recall(y, s, "m") is not None
    assert plot_threshold_metrics(y, s) is not None
    assert plot_model_comparison(
        {"logreg": {"auc_roc": 0.8, "average_precision": 0.4},
         "forest": {"auc_roc": 0.9, "average_precision": 0.6}}
    ) is not None
    assert plot_execution_times(
        {"logreg": {"fit_seconds": 1.0, "predict_seconds": 0.1}}
    ) is not None


def test_prequential_summary_plot():
    from real_time_fraud_detection_system_tpu.models.selection import (
        FoldPerformance,
    )

    rows = [
        FoldPerformance(params={"d": d}, fold=f, expe_type=e,
                        metrics={"auc_roc": 0.7 + 0.05 * d + 0.01 * f},
                        fit_seconds=1.0, predict_seconds=0.1,
                        n_train=10, n_test=5)
        for d in (1, 2) for f in (0, 1) for e in ("validation", "test")
    ]
    assert plot_prequential_summary(rows) is not None


def test_save_plots(tmp_path, scored):
    y, s = scored
    out = save_plots(str(tmp_path / "report.png"), y, s, "forest")
    assert os.path.exists(out)
    assert os.path.getsize(out) > 10_000  # a real rendered PNG


def test_tx_stats_plot():
    from real_time_fraud_detection_system_tpu.config import DataConfig
    from real_time_fraud_detection_system_tpu.data import generate_dataset
    from real_time_fraud_detection_system_tpu.models.plots import (
        plot_tx_stats,
    )

    _, _, txs = generate_dataset(
        DataConfig(n_customers=50, n_terminals=100, n_days=10))
    fig = plot_tx_stats(txs)
    assert fig is not None
    ax = fig.axes[0]
    # the volume line spans the FULL calendar range (zero-days plot as 0,
    # never interpolated away)
    assert len(ax.lines[0].get_xdata()) == int(txs.tx_time_days.max()) + 1
    assert ax.lines[0].get_ydata().sum() == txs.n


def test_decision_boundary_plot():
    from real_time_fraud_detection_system_tpu.models.plots import (
        plot_decision_boundary,
    )

    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (200, 4)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0.5).astype(np.int32)

    def predict(grid):
        return 1.0 / (1.0 + np.exp(-(grid[:, 0] + grid[:, 1] - 0.5)))

    fig = plot_decision_boundary(predict, x, y, resolution=24)
    assert fig is not None
