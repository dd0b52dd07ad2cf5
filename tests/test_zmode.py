"""z_mode serving-path exactness (the round-9 int8 MXU promotion).

``gemm_leaf_sum``'s dominant z contraction is exact in EVERY reduced-
precision mode (d is 0/1, path is ±1/0, z counts ≤ depth), and the int8
mode is additionally BIT-identical to f32: integer z arithmetic, the same
leaf match, the same f32-HIGHEST proj and pinned-order leaf sum. These tests pin
that contract across every configured batch-bucket size — including
threshold-edge inputs — and re-assert the engine-level AOT≡jit parity
with ``z_mode="int8"`` forced, so the serving default flip on TPU
(``runtime.z_mode="auto"`` → int8) can never change a decision.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    DataConfig,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.models.forest import (
    fit_forest,
    for_device,
    gemm_predict_proba,
    resolve_z_mode,
)
from real_time_fraud_detection_system_tpu.models.scaler import Scaler

N_FEAT = 15
BUCKETS = (64, 256, 1024)


@pytest.fixture(scope="module")
def gemm_forest():
    rng = np.random.default_rng(21)
    x = rng.normal(size=(600, N_FEAT)).astype(np.float32)
    y = (x[:, 0] + 0.4 * x[:, 2] > 0.3).astype(np.int32)
    ens = fit_forest(x, y, n_trees=7, max_depth=5)
    return for_device(ens, N_FEAT)


def _edge_rows(g, rng, n):
    """Rows whose entries sit EXACTLY on thresholds — the decision edge
    where a lossy z scheme would flip first."""
    th = np.asarray(g.thresh).ravel()
    th = th[np.isfinite(th)]
    return rng.choice(th, size=(n, N_FEAT)).astype(np.float32)


@pytest.mark.parametrize("rows", BUCKETS)
def test_int8_bit_identical_to_f32_every_bucket(gemm_forest, rows):
    g = gemm_forest
    rng = np.random.default_rng(rows)
    x = rng.normal(size=(rows, N_FEAT)).astype(np.float32)
    x[: rows // 2] = _edge_rows(g, rng, rows // 2)
    p_f32 = np.asarray(gemm_predict_proba(g, jnp.asarray(x), z_mode="f32"))
    p_i8 = np.asarray(gemm_predict_proba(g, jnp.asarray(x), z_mode="int8"))
    # the exact contraction: BIT identity, not tolerance
    assert float(np.abs(p_i8 - p_f32).max()) == 0.0
    assert np.array_equal(p_i8 >= 0.5, p_f32 >= 0.5)


@pytest.mark.parametrize("z_mode", ["f32", "int8"])
def test_leaf_sum_row_slabs_equal_one_pass(gemm_forest, z_mode):
    """Past LEAF_SLAB_ROWS the three contractions run over row slabs (the
    v5e compiler returned wrong per-tree values for one pass at 32,768+
    rows): every row's sum is bit-equal to scoring that row in a small
    batch, ragged tail included."""
    from real_time_fraud_detection_system_tpu.models.forest import (
        LEAF_SLAB_ROWS,
        gemm_leaf_sum,
    )

    g = gemm_forest
    rows = 2 * LEAF_SLAB_ROWS + 37
    rng = np.random.default_rng(9)
    x = rng.normal(size=(rows, N_FEAT)).astype(np.float32)
    x[:64] = _edge_rows(g, rng, 64)
    whole = np.asarray(gemm_leaf_sum(g, jnp.asarray(x), z_mode))
    assert whole.shape == (rows,)
    step = LEAF_SLAB_ROWS // 2 + 1  # chunks that never line up with slabs
    parts = np.concatenate([
        np.asarray(gemm_leaf_sum(g, jnp.asarray(x[i:i + step]), z_mode))
        for i in range(0, rows, step)])
    assert np.array_equal(whole, parts)


def test_bf16_decision_identical_every_bucket(gemm_forest):
    g = gemm_forest
    rng = np.random.default_rng(5)
    for rows in BUCKETS:
        x = rng.normal(size=(rows, N_FEAT)).astype(np.float32)
        x[: rows // 2] = _edge_rows(g, rng, rows // 2)
        p_f32 = np.asarray(
            gemm_predict_proba(g, jnp.asarray(x), z_mode="f32"))
        p_bf = np.asarray(
            gemm_predict_proba(g, jnp.asarray(x), z_mode="bf16"))
        assert np.array_equal(p_bf >= 0.5, p_f32 >= 0.5)


def test_gbt_int8_bit_identical(gemm_forest):
    from real_time_fraud_detection_system_tpu.models.gbt import (
        GBTModel,
        gbt_predict_proba,
    )

    model = GBTModel(trees=gemm_forest, base_score=jnp.float32(-0.7))
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(256, N_FEAT)).astype(np.float32))
    a = np.asarray(gbt_predict_proba(model, x, z_mode="f32"))
    b = np.asarray(gbt_predict_proba(model, x, z_mode="int8"))
    assert float(np.abs(a - b).max()) == 0.0


def test_resolve_z_mode():
    import jax

    on_tpu = jax.default_backend() == "tpu"
    want_auto = "int8" if on_tpu else "f32"
    assert resolve_z_mode("auto") == want_auto
    assert resolve_z_mode(None) == want_auto
    for m in ("f32", "bf16", "int8"):
        assert resolve_z_mode(m) == m
    with pytest.raises(ValueError):
        resolve_z_mode("fp8")


def test_config_rejects_unknown_z_mode():
    with pytest.raises(ValueError):
        RuntimeConfig(z_mode="int4")


# -- engine level ----------------------------------------------------------


def _cols(rng, n, at=0):
    ts = (20200 * 86400 + rng.integers(0, 86400, n)).astype(np.int64)
    return {
        "tx_id": np.arange(at, at + n, dtype=np.int64),
        "tx_datetime_us": ts * 1_000_000,
        "customer_id": rng.integers(0, 100, n).astype(np.int64),
        "terminal_id": rng.integers(0, 200, n).astype(np.int64),
        "tx_amount_cents": rng.integers(100, 50000, n).astype(np.int64),
        "kafka_ts_ms": ts * 1000,
    }


def _forest_cfg(z_mode="auto", precompile=False):
    return Config(
        data=DataConfig(n_customers=50, n_terminals=100, n_days=30),
        features=FeatureConfig(customer_capacity=128, terminal_capacity=256,
                               cms_width=1 << 10),
        runtime=RuntimeConfig(batch_buckets=(64, 256), max_batch_rows=256,
                              z_mode=z_mode, precompile=precompile),
    )


def _serve(engine, sizes, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    at = 0
    for n in sizes:
        out.append(engine.process_batch(_cols(rng, n, at)).probs)
        at += n
    return np.concatenate(out)


@pytest.fixture(scope="module")
def tree_params():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(400, N_FEAT)).astype(np.float32)
    y = (x[:, 1] > 0.1).astype(np.int32)
    return fit_forest(x, y, n_trees=5, max_depth=4)


def test_engine_aot_jit_parity_with_int8_forced(tree_params):
    """AOT dispatch serves the SAME int8 program as plain jit: forcing
    z_mode="int8" under precompile must be bit-identical to the jit
    engine with the same forced mode, across every bucket."""
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine

    scaler = Scaler(mean=jnp.zeros(N_FEAT), scale=jnp.ones(N_FEAT))
    sizes = [60, 200, 60, 200]
    outs = {}
    for pre in (False, True):
        eng = ScoringEngine(_forest_cfg("int8", precompile=pre),
                            kind="forest", params=tree_params,
                            scaler=scaler)
        assert eng.z_mode == "int8"
        if pre:
            man = eng.precompile()
            assert man["buckets"] == [64, 256]
        outs[pre] = _serve(eng, sizes)
    np.testing.assert_array_equal(outs[True], outs[False])


def test_engine_int8_decision_identical_to_f32(tree_params):
    """The serving step with z_mode=int8 is bit-identical to the f32
    engine on CPU (the engine-level face of the gemm matrix above)."""
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine

    scaler = Scaler(mean=jnp.zeros(N_FEAT), scale=jnp.ones(N_FEAT))
    sizes = [60, 200, 200]
    outs = {}
    for zm in ("f32", "int8"):
        eng = ScoringEngine(_forest_cfg(zm), kind="forest",
                            params=tree_params, scaler=scaler)
        outs[zm] = _serve(eng, sizes)
    np.testing.assert_array_equal(outs["int8"], outs["f32"])


def test_run_stats_and_gauges_surface_z_mode(tree_params):
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
        MetricsServer,
    )

    reg = MetricsRegistry()
    scaler = Scaler(mean=jnp.zeros(N_FEAT), scale=jnp.ones(N_FEAT))
    eng = ScoringEngine(_forest_cfg("int8"), kind="forest",
                        params=tree_params, scaler=scaler, metrics=reg)

    class _Src:
        def __init__(self):
            self._done = False

        def poll_batch(self):
            if self._done:
                return None
            self._done = True
            return _cols(np.random.default_rng(0), 60)

        @property
        def offsets(self):
            return [1 if self._done else 0]

        def seek(self, offsets):
            self._done = bool(offsets[0])

    stats = eng.run(_Src())
    assert stats["z_mode"] == "int8"
    assert reg.get("rtfds_z_mode", mode="int8").value == 1.0
    assert reg.get("rtfds_z_mode", mode="f32").value == 0.0
    assert reg.get("rtfds_use_pallas").value == 0.0
    # /healthz device_plane block reads the gauges
    _, body = MetricsServer(registry=reg).health()
    assert body["device_plane"] == {"z_mode": "int8", "use_pallas": False}


@pytest.mark.parametrize("kind,key_mode,served,warns", [
    ("logreg", "exact", None, True),              # XLA composition
    ("forest", "exact", "forest_classify", True),  # predict swap only
    ("forest", "direct", "fused_forest", False),   # what was asked
])
def test_use_pallas_reports_what_is_served(tree_params, kind, key_mode,
                                           served, warns):
    """`--use-pallas` falling back to XLA says so once at WARNING with
    the reason, and rtfds_use_pallas reports the SERVED step, not the
    config flag."""
    import logging

    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )

    base = _forest_cfg("f32")
    cfg = base.replace(
        features=dataclasses.replace(base.features, key_mode=key_mode),
        runtime=dataclasses.replace(base.runtime, use_pallas=True))
    seen = []
    handler = logging.Handler()
    handler.emit = seen.append
    log = logging.getLogger("rtfds.engine")
    log.addHandler(handler)
    reg = MetricsRegistry()
    try:
        eng = ScoringEngine(
            cfg, kind=kind,
            params=tree_params if kind == "forest" else init_logreg(N_FEAT),
            scaler=Scaler(mean=jnp.zeros(N_FEAT), scale=jnp.ones(N_FEAT)),
            metrics=reg)
    finally:
        log.removeHandler(handler)
    warnings = [r for r in seen if r.levelno == logging.WARNING
                and "use_pallas was asked" in r.getMessage()]
    assert len(warnings) == (1 if warns else 0)
    if warns:
        assert "key_mode='exact'" in warnings[0].getMessage()
    assert eng._pallas_kernel == served
    assert reg.get("rtfds_use_pallas").value == (1.0 if served else 0.0)


def test_use_pallas_reload_reannounces(tree_params):
    """What the gate serves is re-announced whenever it changes. A hot
    reload (`_note_params_swap`) to descent-form trees, which no Pallas
    kernel takes, is said like a build-time refusal: one WARNING, gauge
    to 0, `_pallas_kernel` cleared, and the retraced step really is the
    XLA composition's answer. An in-place restore (`state.params = ...`,
    what the checkpointer does) has no hook: there the step's own
    trace-time gate reports the change."""
    import logging

    from real_time_fraud_detection_system_tpu.models.forest import (
        ensemble_predict_proba,
    )
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )

    base = _forest_cfg("f32")
    cfg = base.replace(
        runtime=dataclasses.replace(base.runtime, use_pallas=True))
    reg = MetricsRegistry()
    eng = ScoringEngine(
        cfg, kind="forest", params=tree_params,
        scaler=Scaler(mean=jnp.zeros(N_FEAT), scale=jnp.ones(N_FEAT)),
        metrics=reg)
    gemm = eng.state.params
    assert eng._pallas_kernel == "fused_forest"
    assert reg.get("rtfds_use_pallas").value == 1.0
    eng.process_batch(_cols(np.random.default_rng(1), 60))

    seen = []
    handler = logging.Handler()
    handler.emit = seen.append
    log = logging.getLogger("rtfds.engine")
    log.addHandler(handler)

    def said():
        return [r.getMessage() for r in seen if r.levelno == logging.WARNING
                and "use_pallas was asked" in r.getMessage()]

    try:
        # in place, as a restore does: no hook runs, so the step's own
        # trace-time gate is what reports it (the raw node tables are
        # what a reload of a too-deep ensemble holds)
        eng.state.params = tree_params
        assert reg.get("rtfds_use_pallas").value == 1.0
        res = eng.process_batch(_cols(np.random.default_rng(2), 60, at=60))
        assert len(said()) == 1 and "descent form" in said()[0]
        assert eng._pallas_kernel is None
        assert reg.get("rtfds_use_pallas").value == 0.0
        want = np.asarray(ensemble_predict_proba(
            tree_params, jnp.asarray(res.features)))
        np.testing.assert_allclose(res.probs, want, rtol=1e-6, atol=1e-7)

        # through the reload hook: said at the swap, before any batch
        eng.state.params = eng._note_params_swap(gemm)
        assert eng._pallas_kernel == "fused_forest"
        assert reg.get("rtfds_use_pallas").value == 1.0
        assert len(said()) == 1  # serving what was asked: nothing to say
        eng.state.params = eng._note_params_swap(tree_params)
        eng.state.params = eng._note_params_swap(tree_params)  # no repeat
        assert len(said()) == 2
        assert eng._pallas_kernel is None
        assert reg.get("rtfds_use_pallas").value == 0.0
    finally:
        log.removeHandler(handler)
