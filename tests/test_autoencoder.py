"""Autoencoder anomaly scorer — live successor to the reference's dormant
torch autoencoder (``shared_functions.py:1312-1707``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    DataConfig,
    FeatureConfig,
    TrainConfig,
)
from real_time_fraud_detection_system_tpu.models.autoencoder import (
    autoencoder_loss,
    autoencoder_predict_proba,
    init_autoencoder,
    reconstruction_error,
    train_autoencoder,
)
from real_time_fraud_detection_system_tpu.models.train import train_model


@pytest.fixture(scope="module")
def blob_data():
    rng = np.random.default_rng(0)
    # Legit: tight gaussian blob; anomalies: far-out shell.
    n, f = 3000, 15
    x_legit = rng.normal(0, 1.0, (n, f)).astype(np.float32)
    x_fraud = rng.normal(0, 1.0, (200, f)).astype(np.float32) + 6.0
    x = np.vstack([x_legit, x_fraud])
    y = np.r_[np.zeros(n), np.ones(200)].astype(np.float32)
    return x, y


def test_autoencoder_separates_anomalies(blob_data):
    x, y = blob_data
    params = train_autoencoder(x, y, hidden=(8, 3), epochs=20,
                               batch_size=512, seed=0)
    err = np.asarray(reconstruction_error(params, jnp.asarray(x)))
    assert err[y == 1].mean() > 3 * err[y == 0].mean()
    probs = np.asarray(autoencoder_predict_proba(params, jnp.asarray(x)))
    assert probs.min() >= 0.0 and probs.max() <= 1.0
    from real_time_fraud_detection_system_tpu.models.metrics import roc_auc

    assert roc_auc(y, probs) > 0.95


def test_loss_masks_frauds_and_invalid():
    params = init_autoencoder(4, (3, 2), seed=1)
    x = jnp.ones((6, 4))
    y = jnp.array([0, 0, 1, 1, 0, 0])
    valid = jnp.array([1, 1, 1, 1, 0, 0])
    full = autoencoder_loss(params, x)
    masked = autoencoder_loss(params, x, y, valid)
    # Identical rows → identical per-row error → means agree.
    np.testing.assert_allclose(float(full), float(masked), rtol=1e-6)
    # All-masked batch must not NaN.
    z = autoencoder_loss(params, x, jnp.ones(6), jnp.zeros(6))
    assert np.isfinite(float(z))


def test_train_model_autoencoder_end_to_end(small_dataset):
    dcfg, _, _, txs = small_dataset
    cfg = Config(
        data=dcfg,
        features=FeatureConfig(customer_capacity=256, terminal_capacity=512,
                               cms_width=1 << 10),
        train=TrainConfig(delta_train_days=15, delta_delay_days=5,
                          delta_test_days=5, epochs=6, batch_size=512),
    )
    model, metrics = train_model(txs, cfg, kind="autoencoder")
    # Unsupervised AUC is not gated here: the delay-filtered test window of
    # this tiny dataset is dominated by scenario-2 frauds (compromised
    # terminals, unchanged amounts) that are invisible without labels.
    # Separation quality is gated by test_autoencoder_separates_anomalies.
    assert 0.0 <= metrics["auc_roc"] <= 1.0
    assert np.isfinite(metrics["average_precision"])

    # NumPy CPU path ≡ device path.
    feats = np.asarray(
        np.random.default_rng(3).normal(0, 1, (64, 15)), dtype=np.float32
    )
    np.testing.assert_allclose(
        model.predict_proba_np(feats), model.predict_proba(feats),
        rtol=1e-4, atol=1e-5,
    )

    # Artifact round-trip (.npz, pickle-free).
    import tempfile

    from real_time_fraud_detection_system_tpu.io.artifacts import (
        load_model,
        save_model,
    )

    with tempfile.TemporaryDirectory() as d:
        path = d + "/ae.npz"
        save_model(path, model)
        back = load_model(path)
    np.testing.assert_allclose(
        back.predict_proba(feats), model.predict_proba(feats),
        rtol=1e-5, atol=1e-6,
    )


def test_train_autoencoder_empty_train_set_raises():
    x = np.ones((4, 5), dtype=np.float32)
    with pytest.raises(ValueError, match="no legitimate rows"):
        train_autoencoder(x, np.ones(4))


def test_engine_runs_autoencoder(small_dataset):
    from real_time_fraud_detection_system_tpu.models.scaler import fit_scaler
    from real_time_fraud_detection_system_tpu.runtime.engine import (
        ScoringEngine,
    )
    from real_time_fraud_detection_system_tpu.runtime.sources import (
        ReplaySource,
    )

    dcfg, _, _, txs = small_dataset
    cfg = Config(
        data=dcfg,
        features=FeatureConfig(customer_capacity=256, terminal_capacity=512,
                               cms_width=1 << 10),
    )
    params = init_autoencoder(15, (8, 3), seed=0)
    scaler = fit_scaler(np.zeros((2, 15), dtype=np.float32) + [[0.0] * 15,
                                                               [1.0] * 15])
    eng = ScoringEngine(cfg, kind="autoencoder", params=params, scaler=scaler,
                        online_lr=1e-3)
    src = ReplaySource(txs, 1_743_465_600, batch_rows=512)
    stats = eng.run(src, max_batches=3)
    assert stats["rows"] > 0
