"""Multi-chip tests on the virtual 8-device CPU mesh.

The sharded step's (customer-local + terminal-all_to_all) feature values
must equal the single-device kernel's on identically routed data.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import Config, DataConfig, FeatureConfig
from real_time_fraud_detection_system_tpu.core.batch import make_batch
from real_time_fraud_detection_system_tpu.features.online import (
    init_feature_state,
    update_and_featurize,
)
from real_time_fraud_detection_system_tpu.models.logreg import (
    init_logreg,
    logreg_loss,
    logreg_predict_proba,
)
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.parallel import (
    make_mesh,
    make_sharded_step,
    partition_batch_by_customer,
    shard_feature_state,
)

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N_DEV, "conftest must force 8 CPU devices"
    return make_mesh(N_DEV)


@pytest.fixture(scope="module")
def cfg():
    return Config(
        features=FeatureConfig(customer_capacity=1024, terminal_capacity=2048),
    )


def _random_cols(rng, n, n_cust=300, n_term=600, day0=20200):
    return {
        "tx_id": np.arange(n, dtype=np.int64),
        "tx_datetime_us": (
            (day0 * 86400 + rng.integers(0, 86400, n)) * 1_000_000
            + rng.integers(0, 3, n) * 86400 * 1_000_000
        ).astype(np.int64),
        "customer_id": rng.integers(0, n_cust, n).astype(np.int64),
        "terminal_id": rng.integers(0, n_term, n).astype(np.int64),
        "tx_amount_cents": rng.integers(100, 50000, n).astype(np.int64),
        "label": (rng.random(n) < 0.1).astype(np.int32),
    }


def test_sharded_step_matches_single_device(mesh, cfg, rng):
    n = 512
    rows_per_shard = 256
    cols = _random_cols(rng, n)

    # ---- single-device reference
    ref_state = init_feature_state(cfg.features)
    batch1 = make_batch(
        customer_id=cols["customer_id"],
        terminal_id=cols["terminal_id"],
        tx_datetime_us=cols["tx_datetime_us"],
        amount_cents=cols["tx_amount_cents"],
        label=cols["label"],
    )
    _, ref_feats = update_and_featurize(
        ref_state, jax.tree.map(jnp.asarray, batch1), cfg.features
    )
    ref_feats = np.asarray(ref_feats)

    # ---- sharded
    params = init_logreg(15)
    scaler = Scaler(mean=jnp.zeros(15), scale=jnp.ones(15))
    build = make_sharded_step(
        cfg, logreg_predict_proba, mesh=mesh
    )
    part_cols, pos = partition_batch_by_customer(cols, N_DEV, rows_per_shard)
    batch = make_batch(
        customer_id=part_cols["customer_id"],
        terminal_id=part_cols["terminal_id"],
        tx_datetime_us=part_cols["tx_datetime_us"],
        amount_cents=part_cols["tx_amount_cents"],
        label=np.where(part_cols["__valid__"], part_cols["label"], -1),
    )
    batch = batch._replace(valid=jnp.asarray(part_cols["__valid__"]))
    fstate = shard_feature_state(init_feature_state(cfg.features), mesh)
    jb = jax.tree.map(jnp.asarray, batch)
    step = build(fstate, params, scaler, jb)
    fstate2, params2, probs, feats = step(fstate, params, scaler, jb)[:4]
    feats = np.asarray(feats)[pos]  # back to input row order
    probs = np.asarray(probs)[pos]

    np.testing.assert_allclose(feats, ref_feats, rtol=1e-5, atol=1e-4)
    assert np.all((probs > 0) & (probs < 1))


def test_sharded_online_sgd_replicated_params(mesh, cfg, rng):
    n = 512
    cols = _random_cols(rng, n)
    params = init_logreg(15)
    scaler = Scaler(mean=jnp.zeros(15), scale=jnp.ones(15))
    build = make_sharded_step(
        cfg, logreg_predict_proba, loss_fn=logreg_loss, online_lr=1e-2,
        mesh=mesh,
    )
    part_cols, pos = partition_batch_by_customer(cols, N_DEV, 256)
    batch = make_batch(
        customer_id=part_cols["customer_id"],
        terminal_id=part_cols["terminal_id"],
        tx_datetime_us=part_cols["tx_datetime_us"],
        amount_cents=part_cols["tx_amount_cents"],
        label=np.where(part_cols["__valid__"], part_cols["label"], -1),
    )
    batch = batch._replace(valid=jnp.asarray(part_cols["__valid__"]))
    fstate = shard_feature_state(init_feature_state(cfg.features), mesh)
    jb = jax.tree.map(jnp.asarray, batch)
    step = build(fstate, params, scaler, jb)
    _, params2, _, _ = step(fstate, params, scaler, jb)[:4]
    w2 = np.asarray(params2.w)
    assert not np.allclose(np.asarray(params.w), w2)  # learned something
    # params must stay replicated — fetching from the sharded result is a
    # single consistent array
    assert w2.shape == (15,)


def test_state_stays_sharded_across_steps(mesh, cfg, rng):
    """Feature state must remain device-resident and sharded between calls
    (HBM residency contract)."""
    params = init_logreg(15)
    scaler = Scaler(mean=jnp.zeros(15), scale=jnp.ones(15))
    build = make_sharded_step(cfg, logreg_predict_proba, mesh=mesh)
    cols = _random_cols(rng, 256)
    part_cols, _ = partition_batch_by_customer(cols, N_DEV, 128)
    batch = make_batch(
        customer_id=part_cols["customer_id"],
        terminal_id=part_cols["terminal_id"],
        tx_datetime_us=part_cols["tx_datetime_us"],
        amount_cents=part_cols["tx_amount_cents"],
    )
    batch = batch._replace(valid=jnp.asarray(part_cols["__valid__"]))
    fstate = shard_feature_state(init_feature_state(cfg.features), mesh)
    jb = jax.tree.map(jnp.asarray, batch)
    step = build(fstate, params, scaler, jb)
    for _ in range(3):
        fstate, params, probs, feats = step(fstate, params, scaler, jb)[:4]
    shard_count = len(fstate.customer.count.addressable_shards)
    assert shard_count == N_DEV
