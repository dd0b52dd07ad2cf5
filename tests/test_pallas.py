"""Pallas fused kernel parity vs the jnp composition (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np

from real_time_fraud_detection_system_tpu.config import FeatureConfig
from real_time_fraud_detection_system_tpu.core.batch import make_batch
from real_time_fraud_detection_system_tpu.features.online import (
    init_feature_state,
    update_and_featurize,
    update_and_score_pallas,
)
from real_time_fraud_detection_system_tpu.models.logreg import (
    init_logreg,
    logreg_predict_proba,
)
from real_time_fraud_detection_system_tpu.models.scaler import Scaler, transform


def _batch(rng, n=256, with_labels=True):
    return make_batch(
        customer_id=rng.integers(0, 200, n).astype(np.int64),
        terminal_id=rng.integers(0, 400, n).astype(np.int64),
        tx_datetime_us=((20200 + rng.integers(0, 40, n)) * 86400
                        + rng.integers(0, 86400, n)).astype(np.int64) * 1_000_000,
        amount_cents=rng.integers(100, 50000, n).astype(np.int64),
        label=rng.integers(0, 2, n).astype(np.int32) if with_labels else None,
    )


def test_fused_kernel_matches_jnp_path(rng):
    cfg = FeatureConfig(customer_capacity=256, terminal_capacity=512)
    params = init_logreg(15)
    params = params._replace(
        w=jnp.asarray(rng.normal(0, 0.3, 15).astype(np.float32))
    )
    scaler = Scaler(
        mean=jnp.asarray(rng.normal(0, 1, 15).astype(np.float32)),
        scale=jnp.asarray(rng.uniform(0.5, 2.0, 15).astype(np.float32)),
    )

    state_a = init_feature_state(cfg)
    state_b = init_feature_state(cfg)
    for _ in range(3):  # multiple batches so ring state is exercised
        batch = jax.tree.map(jnp.asarray, _batch(rng))
        state_a, feats = update_and_featurize(state_a, batch, cfg)
        ref_probs = jnp.where(
            batch.valid,
            logreg_predict_proba(params, transform(scaler, feats)),
            0.0,
        )
        state_b, probs, feats_k = update_and_score_pallas(
            state_b, batch, cfg, scaler.mean, scaler.scale,
            params.w, params.b,
        )
        np.testing.assert_allclose(
            np.asarray(feats_k), np.asarray(feats), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(probs), np.asarray(ref_probs), rtol=1e-5, atol=1e-6
        )
    # states identical after the same updates
    for a, b in zip(jax.tree.leaves(state_a), jax.tree.leaves(state_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_pallas_path_matches(small_dataset):
    import dataclasses

    from real_time_fraud_detection_system_tpu.config import small_config
    from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
    from real_time_fraud_detection_system_tpu.runtime import (
        ReplaySource,
        ScoringEngine,
    )

    _, _, _, txs = small_dataset
    cfg = small_config()
    cfg_p = dataclasses.replace(
        cfg, runtime=dataclasses.replace(cfg.runtime, use_pallas=True)
    )
    params = init_logreg(15)
    scaler = Scaler(mean=jnp.zeros(15), scale=jnp.ones(15))
    outs = []
    for c in (cfg, cfg_p):
        eng = ScoringEngine(c, kind="logreg", params=params, scaler=scaler)
        src = ReplaySource(txs.slice(slice(0, 400)), 1_743_465_600,
                           batch_rows=128)
        probs = []
        while True:
            cols = src.poll_batch()
            if cols is None:
                break
            probs.append(eng.process_batch(cols).probs)
        outs.append(np.concatenate(probs))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)


def test_fused_kernel_jit_and_padding(rng):
    """Padded rows (valid=False) and a multi-tile grid must match the jnp
    path: 100 real rows padded to 256, scored with block_rows=128 → grid=(2,)
    where the second tile is mostly padding."""
    from real_time_fraud_detection_system_tpu.features.online import (
        _update_and_gather,
    )
    from real_time_fraud_detection_system_tpu.ops.pallas_kernels import (
        fused_featurize_score,
    )

    cfg = FeatureConfig(customer_capacity=256, terminal_capacity=512)
    params = init_logreg(15)
    params = params._replace(
        w=jnp.asarray(rng.normal(0, 0.3, 15).astype(np.float32))
    )
    scaler = Scaler(mean=jnp.zeros(15), scale=jnp.ones(15))
    raw = _batch(rng, n=100, with_labels=False)
    padded = make_batch(
        customer_id=np.asarray(raw.customer_key, np.int64),
        terminal_id=np.asarray(raw.terminal_key, np.int64),
        tx_datetime_us=np.asarray(raw.day, np.int64) * 86400_000_000
        + np.asarray(raw.tod_s, np.int64) * 1_000_000,
        amount_cents=(np.asarray(raw.amount) * 100).astype(np.int64),
        pad_to=256,
    )
    assert int(np.asarray(padded.valid).sum()) == 100
    batch = jax.tree.map(jnp.asarray, padded)

    # reference: jnp composition on the same padded batch
    state_ref, feats_ref = update_and_featurize(
        init_feature_state(cfg), batch, cfg
    )
    probs_ref = jnp.where(
        batch.valid,
        logreg_predict_proba(params, transform(scaler, feats_ref)),
        0.0,
    )

    # kernel with a 2-tile grid (256 / 128)
    state, c_rows, t_rows = _update_and_gather(
        init_feature_state(cfg), batch, cfg
    )
    probs, feats = fused_featurize_score(
        c_rows, t_rows,
        batch.day, batch.tod_s, batch.amount, batch.valid,
        scaler.mean, scaler.scale, params.w, params.b,
        windows=tuple(cfg.windows), delay=cfg.delay_days,
        weekend_start=cfg.weekend_start_weekday,
        night_end=cfg.night_end_hour, block_rows=128,
    )
    np.testing.assert_allclose(
        np.asarray(feats), np.asarray(feats_ref), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(probs), np.asarray(probs_ref), rtol=1e-5, atol=1e-5
    )
    assert (np.asarray(probs)[100:] == 0.0).all()
