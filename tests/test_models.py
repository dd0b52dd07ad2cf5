"""Model-layer tests: sklearn parity for scaler/forest/metrics; training."""

import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.models import (
    average_precision,
    ensemble_from_sklearn,
    ensemble_predict_proba,
    fit_scaler,
    roc_auc,
    threshold_based_metrics,
    train_logreg,
    transform,
)
from real_time_fraud_detection_system_tpu.models.logreg import (
    logreg_predict_proba,
)


@pytest.fixture(scope="module")
def xy():
    rng = np.random.default_rng(0)
    n, f = 3000, 15
    x = rng.normal(0, 1, (n, f))
    w = rng.normal(0, 1, f)
    logits = x @ w - 2.0
    y = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(np.float64)
    return x, y


def test_scaler_matches_sklearn(xy):
    from sklearn.preprocessing import StandardScaler

    x, _ = xy
    ours = fit_scaler(x)
    theirs = StandardScaler().fit(x)
    np.testing.assert_allclose(np.asarray(ours.mean), theirs.mean_, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ours.scale), theirs.scale_, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(transform(ours, jnp.asarray(x, jnp.float32))),
        theirs.transform(x),
        atol=1e-3,
    )


def test_forest_gemm_exactly_matches_sklearn(xy):
    """The tensorized traversal must reproduce sklearn predict_proba."""
    from sklearn.ensemble import RandomForestClassifier

    x, y = xy
    clf = RandomForestClassifier(n_estimators=20, max_depth=6, random_state=0)
    clf.fit(x, y)
    ens = ensemble_from_sklearn(clf, x.shape[1])
    # Production inputs are f32; the oracle sees the same f32-quantized rows.
    x32 = x.astype(np.float32)
    ours = np.asarray(ensemble_predict_proba(ens, jnp.asarray(x32)))
    theirs = clf.predict_proba(x32.astype(np.float64))[:, 1]
    np.testing.assert_allclose(ours, theirs, atol=1e-5)
    # ranking must be essentially identical
    assert abs(roc_auc(y, ours) - roc_auc(y, theirs)) < 1e-3
    # the GEMM formulation must agree with the gather traversal
    from real_time_fraud_detection_system_tpu.models.forest import (
        gemm_predict_proba,
        to_gemm,
    )

    g = to_gemm(ens, x.shape[1])
    ours_gemm = np.asarray(gemm_predict_proba(g, jnp.asarray(x32)))
    np.testing.assert_allclose(ours_gemm, ours, atol=1e-5)
    # every z-contraction arithmetic mode is decision-exact (operands are
    # tiny integers in all of them) — including threshold-sitting inputs.
    # Off-TPU the "bf16" mode degrades to f32 (no bf16 dot on CPU XLA),
    # so here its assert only pins the dispatch; the real bf16-vs-f32 and
    # int8-on-MXU exactness evidence is tools/hw_parity_check.py on the
    # TPU backend.
    x_thr = np.asarray(g.thresh).ravel()
    x_thr = x_thr[np.isfinite(x_thr)][:64]
    probe = np.concatenate(
        [x32, np.tile(x_thr[:, None], (1, x.shape[1])).astype(np.float32)])
    base = np.asarray(gemm_predict_proba(g, jnp.asarray(probe), "f32"))
    for mode in ("bf16", "int8"):
        alt = np.asarray(gemm_predict_proba(g, jnp.asarray(probe), mode))
        np.testing.assert_array_equal(alt, base, err_msg=mode)


def test_decision_tree_depth2(xy):
    """The reference's DT-2 baseline model family."""
    from sklearn.tree import DecisionTreeClassifier

    x, y = xy
    clf = DecisionTreeClassifier(max_depth=2, random_state=0).fit(x, y)
    ens = ensemble_from_sklearn(clf, x.shape[1])
    ours = np.asarray(ensemble_predict_proba(ens, jnp.asarray(x, jnp.float32)))
    np.testing.assert_allclose(ours, clf.predict_proba(x)[:, 1], atol=1e-4)


def test_metrics_match_sklearn(xy, rng):
    from sklearn.metrics import average_precision_score, roc_auc_score

    x, y = xy
    score = rng.random(len(y))
    assert abs(roc_auc(y, score) - roc_auc_score(y, score)) < 1e-9
    assert (
        abs(average_precision(y, score) - average_precision_score(y, score)) < 1e-9
    )
    # with heavy ties
    score_t = np.round(score, 1)
    assert abs(roc_auc(y, score_t) - roc_auc_score(y, score_t)) < 1e-9
    assert (
        abs(average_precision(y, score_t) - average_precision_score(y, score_t))
        < 1e-9
    )


def test_threshold_metrics_consistency(xy, rng):
    _, y = xy
    score = rng.random(len(y))
    m = threshold_based_metrics(y, score, thresholds=(0.5,))[0.5]
    assert 0 <= m["TPR"] <= 1 and 0 <= m["FPR"] <= 1
    assert abs(m["G-mean"] - np.sqrt(m["TPR"] * m["TNR"])) < 1e-9


def test_logreg_learns(xy):
    x, y = xy
    params = train_logreg(x.astype(np.float32), y, epochs=10, batch_size=512)
    p = np.asarray(logreg_predict_proba(params, jnp.asarray(x, jnp.float32)))
    assert roc_auc(y, p) > 0.85


def test_card_precision_top_k():
    from real_time_fraud_detection_system_tpu.models import card_precision_top_k

    # 1 day, 5 customers; top-2 by max score are customers 4 (fraud) and 3 (not)
    days = np.zeros(6)
    cust = np.asarray([0, 1, 2, 3, 4, 4])
    score = np.asarray([0.1, 0.2, 0.3, 0.8, 0.5, 0.9])
    fraud = np.asarray([0, 0, 0, 0, 1, 1])
    assert card_precision_top_k(fraud, score, days, cust, k=2) == 0.5


def test_for_device_dispatch(xy):
    """for_device picks GEMM for bounded forests, descent for huge trees;
    the unified predict_proba dispatches both; GBT gemm matches descent."""
    from sklearn.ensemble import RandomForestClassifier

    from real_time_fraud_detection_system_tpu.models.forest import (
        GemmEnsemble,
        for_device,
        predict_proba,
    )

    x, y = xy
    clf = RandomForestClassifier(n_estimators=10, max_depth=5, random_state=0)
    clf.fit(x, y)
    ens = ensemble_from_sklearn(clf, x.shape[1])
    dev = for_device(ens, x.shape[1])
    assert isinstance(dev, GemmEnsemble)
    x32 = jnp.asarray(x, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(predict_proba(dev, x32)),
        np.asarray(predict_proba(ens, x32)),
        atol=1e-5,
    )
    # over-budget ensembles stay in descent form
    assert for_device(ens, x.shape[1], max_gemm_bytes=16) is ens


def test_gbt_device_form_matches(xy):
    from real_time_fraud_detection_system_tpu.models.gbt import (
        gbt_for_device,
        gbt_predict_proba,
        train_gbt,
    )

    x, y = xy
    x32 = x.astype(np.float32)
    model = train_gbt(x32, y.astype(np.float32), n_trees=8, max_depth=3)
    dev = gbt_for_device(model, x.shape[1])
    np.testing.assert_allclose(
        np.asarray(gbt_predict_proba(dev, jnp.asarray(x32))),
        np.asarray(gbt_predict_proba(model, jnp.asarray(x32))),
        atol=1e-5,
    )


def test_fit_split_to_days_identity_and_scaling():
    from real_time_fraud_detection_system_tpu.models.train import (
        fit_split_to_days,
    )

    # fits: unchanged (the reference's 245-day dataset, 153/30/30)
    assert fit_split_to_days(245, 153, 30, 30) == (153, 30, 30)
    # shorter dataset: scaled proportionally, spans never overflow it
    for n_days in (120, 60, 45, 10, 3, 2):
        tr, de, te = fit_split_to_days(n_days, 153, 30, 30)
        assert tr >= 1 and te >= 1 and de >= 0
        assert tr + de + te <= n_days
        # shape roughly preserved on non-degenerate sizes
        if n_days >= 30:
            assert tr > de and tr > te
    # a <=1-day dataset cannot hold disjoint train+test windows
    assert fit_split_to_days(1, 153, 30, 30) == (1, 0, 0)
    assert fit_split_to_days(0, 153, 30, 30) == (0, 0, 0)


def test_train_model_short_dataset_has_metrics(small_dataset):
    """`make run-all DAYS=60`-style runs must not produce NaN metrics
    (the configured 153/30/30 split is auto-scaled to the dataset)."""
    from real_time_fraud_detection_system_tpu.config import (
        Config,
        DataConfig,
        FeatureConfig,
        TrainConfig,
    )
    from real_time_fraud_detection_system_tpu.models import train_model

    _, _, _, txs = small_dataset  # 45 days << 153/30/30
    cfg = Config(
        data=DataConfig(n_customers=120, n_terminals=240, n_days=45, seed=7),
        train=TrainConfig(epochs=2, batch_size=512),  # default 153/30/30
        features=FeatureConfig(customer_capacity=512,
                               terminal_capacity=1024),
    )
    _, metrics = train_model(txs, cfg, kind="logreg")
    assert np.isfinite(metrics["auc_roc"]), metrics
    assert 0.5 <= metrics["auc_roc"] <= 1.0
