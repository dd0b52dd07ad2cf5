"""z_mode serving-path exactness (the round-9 int8 MXU promotion).

``gemm_leaf_sum``'s dominant z contraction is exact in EVERY reduced-
precision mode (d is 0/1, path is ±1/0, z counts ≤ depth), and the int8
mode is additionally BIT-identical to f32: integer z arithmetic, the same
leaf match, the same exact proj and pinned-order leaf sum. These tests pin
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
def tree_forest():
    rng = np.random.default_rng(21)
    x = rng.normal(size=(600, N_FEAT)).astype(np.float32)
    y = (x[:, 0] + 0.4 * x[:, 2] > 0.3).astype(np.int32)
    return fit_forest(x, y, n_trees=7, max_depth=5)


@pytest.fixture(scope="module")
def gemm_forest(tree_forest):
    return for_device(tree_forest, N_FEAT)


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


# -- the selector contraction in one bf16 pass (PR 47) ----------------------
#
# On the chip ``proj`` is one bfloat16 pass over x split into three parts;
# here the f32 contraction runs. CPU XLA multiplies bf16 by bf16 into f32
# too, so the chip's FORM is held here by steering the one backend check
# (``forest._selector``) in the test, on inputs drawn to break a split.

F32_MAX = np.finfo(np.float32).max
ROW_KINDS = ("threshold", "above", "below", "zero", "pow2", "max", "bits")


@pytest.fixture()
def split_form(monkeypatch):
    """``forest._selector`` answers as on the chip. (Not a patched
    ``jax.default_backend``: that also asks for the bf16 z contraction,
    which has a batch dimension and no CPU kernel.)"""
    from real_time_fraud_detection_system_tpu.models import forest

    monkeypatch.setattr(forest, "_selector", forest.selector_bf16x3)


def _jitted(fn, *args):
    """``fn`` traced anew (jit's cache may hold the other form's trace)
    and compiled: both forms go through the same compiler."""
    import jax

    return np.asarray(jax.jit(lambda *a: fn(*a))(*args))


def _thresholds(g):
    th = np.asarray(g.thresh).ravel()
    return th[np.isfinite(th)]


def _rows_of(kind, g, rng, n):
    """``[n, N_FEAT]`` f32 rows of one adversarial kind."""
    th = rng.choice(_thresholds(g), size=(n, N_FEAT)).astype(np.float32)
    if kind == "threshold":
        return th
    if kind == "above":
        return np.nextafter(th, np.float32(np.inf), dtype=np.float32)
    if kind == "below":
        return np.nextafter(th, np.float32(-np.inf), dtype=np.float32)
    if kind == "zero":
        return np.where(rng.random((n, N_FEAT)) < 0.5,
                        np.float32(0.0), np.float32(-0.0))
    sign = np.where(rng.random((n, N_FEAT)) < 0.5, 1, -1).astype(np.float32)
    if kind == "pow2":
        return sign * np.exp2(rng.integers(-120, 121, size=(n, N_FEAT))
                              ).astype(np.float32)
    if kind == "max":
        return sign * F32_MAX
    assert kind == "bits"  # any f32 bit pattern but NaN / Inf
    x = rng.integers(0, 1 << 32, size=(n, N_FEAT), dtype=np.uint64
                     ).astype(np.uint32).view(np.float32)
    return np.where(np.isfinite(x), x, th)


def _adversarial(g, rng, rows):
    """Every kind in one batch of ``rows``, in equal shares."""
    per = -(-rows // len(ROW_KINDS))
    return np.concatenate(
        [_rows_of(k, g, rng, per) for k in ROW_KINDS])[:rows]


def _bits(a):
    return np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)


def _lossless(x):
    """Where the three parts hold every bit of x on a backend that flushes
    subnormals: x is 0, or normal with its lowest set bit worth 2^-126 or
    more. (Below, ``split_bf16x3`` says what is lost.)"""
    bits = _bits(x).astype(np.int64)
    exp, man = (bits >> 23) & 0xFF, (bits & 0x7FFFFF) | (1 << 23)
    low = exp - 127 - 23 + np.log2(man & -man).astype(np.int64)
    return (x == 0) | ((exp > 0) & (low >= -126))


@pytest.mark.parametrize("kind", ROW_KINDS)
def test_three_bf16_parts_sum_to_x_bitwise(gemm_forest, kind):
    from real_time_fraud_detection_system_tpu.models.forest import (
        split_bf16x3,
    )

    x = _rows_of(kind, gemm_forest, np.random.default_rng(47), 512)
    parts = split_bf16x3(jnp.asarray(x))
    assert parts.dtype == jnp.bfloat16 and parts.shape == (512, 3 * N_FEAT)
    h, m, l = np.split(np.asarray(parts.astype(jnp.float32)), 3, axis=1)
    assert np.isfinite(h).all()  # a ROUNDED top part of F32_MAX is inf
    ok = _lossless(x)
    for total in ((h + m) + l, h + (m + l), (h + l) + m):  # any order
        # (-0.0 - -0.0 is +0.0: a zero's sign ends with the top part,
        # and no contraction would carry it past the other features' +0)
        assert np.array_equal(total[ok], x[ok])
        assert np.array_equal(_bits(total)[ok & (x != 0)],
                              _bits(x)[ok & (x != 0)])
        # what a flushed low part leaves is less than the smallest normal
        assert (np.abs(total - x)[~ok] < np.finfo(np.float32).tiny).all()
    if kind != "bits":
        assert ok.all()


@pytest.mark.parametrize("rows", BUCKETS)
@pytest.mark.parametrize("form", ["f32", "split"])
def test_proj_is_the_selected_feature_bitwise(gemm_forest, form, rows,
                                              monkeypatch):
    import jax

    from real_time_fraud_detection_system_tpu.models.forest import (
        _project,
        _selector,
    )

    g = gemm_forest
    if form == "split":  # the compile test's way: the chip's own branch
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pick = _selector(g.sel)
    assert pick.dtype == (jnp.bfloat16 if form == "split" else jnp.float32)
    assert pick.shape[1] == (3 if form == "split" else 1) * N_FEAT
    x = _adversarial(g, np.random.default_rng(rows), rows)
    proj = np.asarray(_project(pick, jnp.asarray(x)))
    sel = np.asarray(g.sel)
    want = np.where(sel.any(axis=1)[None],  # a padding node selects nothing
                    x[:, sel.argmax(axis=1)], np.float32(0.0))
    assert proj.shape == want.shape == (rows,) + sel.shape[::2]
    ok = np.broadcast_to(_lossless(x)[:, sel.argmax(axis=1)], want.shape)
    assert np.array_equal(proj[ok], want[ok])  # ±0 compare equal
    nonzero = ok & (want != 0)
    assert np.array_equal(_bits(proj)[nonzero], _bits(want)[nonzero])
    # and the descent's own comparison follows, whatever was flushed
    th = np.asarray(g.thresh)[None]
    assert np.array_equal(proj <= th, want <= th)


@pytest.mark.parametrize("rows", BUCKETS)
@pytest.mark.parametrize("z_mode", ["f32", "bf16", "int8"])
def test_split_form_scores_as_the_f32_form_and_the_descent(
        tree_forest, gemm_forest, z_mode, rows, monkeypatch, request):
    from real_time_fraud_detection_system_tpu.models.forest import (
        ensemble_predict_proba,
    )

    g = gemm_forest
    x = jnp.asarray(_adversarial(g, np.random.default_rng(rows + 1), rows))

    def score(g, x):
        return gemm_predict_proba(g, x, z_mode=z_mode)

    p_f32 = _jitted(score, g, x)
    descent = np.asarray(ensemble_predict_proba(tree_forest, x))
    request.getfixturevalue("split_form")
    p_split = _jitted(score, g, x)
    assert np.array_equal(_bits(p_split), _bits(p_f32))
    # one flipped node moves a row by a leaf's vote, 1/7 of ~0.1 and more;
    # the two forms add the seven votes in different orders
    assert float(np.abs(p_split - descent).max()) < 1e-6
    sure = np.abs(descent - 0.5) > 1e-6
    assert np.array_equal((p_split >= 0.5)[sure], (descent >= 0.5)[sure])


@pytest.mark.parametrize("z_mode", ["f32", "bf16", "int8"])
def test_gbt_split_form_bit_identical(tree_forest, gemm_forest, z_mode,
                                      request):
    from real_time_fraud_detection_system_tpu.models.gbt import (
        GBTModel,
        gbt_predict_proba,
    )

    model = GBTModel(trees=gemm_forest, base_score=jnp.float32(-0.7))
    walked = GBTModel(trees=tree_forest, base_score=jnp.float32(-0.7))
    x = jnp.asarray(_adversarial(gemm_forest, np.random.default_rng(9), 256))

    def score(m, x):
        return gbt_predict_proba(m, x, z_mode=z_mode)

    a = _jitted(score, model, x)
    w = np.asarray(gbt_predict_proba(walked, x))
    request.getfixturevalue("split_form")
    b = _jitted(score, model, x)
    assert np.array_equal(_bits(a), _bits(b))
    assert float(np.abs(b - w).max()) < 1e-6


def test_slabs_share_one_selector(gemm_forest, split_form):
    """The tripled selector is made once a call, beside the slab loop: the
    loop takes it as an input and no slab makes it again."""
    import jax

    from real_time_fraud_detection_system_tpu.models.forest import (
        LEAF_SLAB_ROWS,
        gemm_leaf_sum,
    )

    x = jnp.zeros((2 * LEAF_SLAB_ROWS, N_FEAT), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda g, x: gemm_leaf_sum(g, x, "int8"))(gemm_forest, x)
    (loop,) = [e for e in jaxpr.jaxpr.eqns if str(e.primitive) == "scan"]
    t, f, i = gemm_forest.sel.shape

    def tripled(v):
        return (getattr(v.aval, "shape", None) == (t, 3 * f, i)
                and v.aval.dtype == jnp.bfloat16)

    assert sum(tripled(v) for v in loop.invars) == 1
    body = loop.params["jaxpr"].jaxpr
    assert not any(tripled(v) for e in body.eqns for v in e.outvars)


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
