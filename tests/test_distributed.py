"""Multi-host distributed helpers: hybrid DCN×ICI mesh on the virtual
8-device CPU mesh (2 emulated hosts × 4 devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import Config, FeatureConfig
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
    initialize_distributed,
    make_hybrid_mesh,
    make_sharded_step,
    mesh_axes,
    partition_batch_by_customer,
    process_local_batch_slice,
    shard_feature_state,
)

N_DEV = 8


@pytest.fixture(scope="module")
def hybrid_mesh():
    assert len(jax.devices()) >= N_DEV
    return make_hybrid_mesh(n_hosts=2, devices_per_host=4)


@pytest.fixture(scope="module")
def cfg():
    return Config(
        features=FeatureConfig(customer_capacity=1024, terminal_capacity=2048),
    )


def test_hybrid_mesh_shape(hybrid_mesh):
    assert hybrid_mesh.devices.shape == (2, 4)
    assert mesh_axes(hybrid_mesh) == ("dcn", "ici")


def test_hybrid_mesh_defaults():
    m = make_hybrid_mesh()  # 8 devices -> 2 x 4 by default
    assert m.devices.size == 8
    assert m.devices.shape[0] == 2
    with pytest.raises(ValueError, match="device"):
        make_hybrid_mesh(n_hosts=4, devices_per_host=4)
    with pytest.raises(ValueError, match="device"):
        make_hybrid_mesh(devices_per_host=16)  # 8//16 == 0 hosts


def test_initialize_distributed_single_process_noop():
    assert initialize_distributed() is False  # no env config: no-op


def test_process_local_batch_slice_single_process(hybrid_mesh):
    s = process_local_batch_slice(1024, hybrid_mesh)
    # Single process owns all devices → the full range.
    assert (s.start, s.stop) == (0, 1024)


def test_hybrid_step_matches_single_device(hybrid_mesh, cfg, rng):
    """The (dcn, ici) 2-axis step must produce the same features as the
    single-device kernel — collectives over the axis pair are semantically
    one flattened axis."""
    n = 512
    cols = {
        "tx_id": np.arange(n, dtype=np.int64),
        "tx_datetime_us": (
            (20200 * 86400 + rng.integers(0, 86400, n)) * 1_000_000
        ).astype(np.int64),
        "customer_id": rng.integers(0, 300, n).astype(np.int64),
        "terminal_id": rng.integers(0, 600, n).astype(np.int64),
        "tx_amount_cents": rng.integers(100, 50000, n).astype(np.int64),
        "label": (rng.random(n) < 0.1).astype(np.int32),
    }

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

    params = init_logreg(15)
    scaler = Scaler(mean=jnp.zeros(15), scale=jnp.ones(15))
    axes = mesh_axes(hybrid_mesh)
    build = make_sharded_step(
        cfg, logreg_predict_proba, loss_fn=logreg_loss, online_lr=1e-2,
        mesh=hybrid_mesh, axis=axes, batch_rows=n,
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
    fstate = shard_feature_state(
        init_feature_state(cfg.features), hybrid_mesh, axis=axes
    )
    jb = jax.tree.map(jnp.asarray, batch)
    step = build(fstate, params, scaler, jb)
    fstate2, params2, probs, feats = step(fstate, params, scaler, jb)[:4]

    feats = np.asarray(feats)[pos]
    np.testing.assert_allclose(feats, ref_feats, rtol=1e-5, atol=1e-4)
    # Online SGD ran and params stayed replicated.
    assert not np.allclose(np.asarray(params.w), np.asarray(params2.w))
    assert np.asarray(params2.w).shape == (15,)
    # State sharded across all 8 devices.
    assert len(fstate2.customer.count.addressable_shards) == N_DEV
