"""Forest-kernel variant race + hot-path stage timing on the live backend.

The GEMM forest (forest.py:226-256) measures ~5% MFU on v5e. Its three
stages have very different hardware shapes:

  proj  einsum bf,tfi->bti  on the chip x in three bf16 parts, K=45,
                            ONE pass (PR 47; f32 HIGHEST, K=15, was six)
  z     einsum bti,til->btl bf16->f32    (the FLOPs; K=I~100)
  leaf  einsum btl,tl->b    f32 HIGHEST  (reduction)

This script times (a) each stage in isolation, (b) whole-kernel variants
that keep decision-exactness, on whatever backend is live:

  current   — the shipping kernel
  projHIGH  — proj at HIGH (3-pass) [exactness check reported; known to
              flip decisions for threshold-sitting inputs — measured here]
  gatherD   — d via constant-index take_along_axis instead of the sel
              matmul (static feat indices; no precision question)
  flatproj  — proj as ONE [B,15]x[15,T*I] matmul (reshape of sel) at
              HIGHEST; same math, different tiling
  int8z     — the z contraction in int8×int8→int32 (d is 0/1, path is
              ±1/0, z counts ≤ depth: all exactly representable; v5e
              MXU int8 peak is 2× bf16)

Prints one JSON line; run it on the machine that holds the chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.utils import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    from sklearn.ensemble import RandomForestClassifier

    from real_time_fraud_detection_system_tpu.models.forest import (
        _project,
        _selector,
        ensemble_from_sklearn,
        gemm_predict_proba,
        to_gemm,
    )

    rng = np.random.default_rng(0)
    xtr = rng.normal(0, 1, (2048, 15))
    ytr = (xtr[:, 0] + 0.5 * xtr[:, 1] > 0.8).astype(np.int32)
    skl = RandomForestClassifier(n_estimators=100, max_depth=8,
                                 random_state=0, n_jobs=-1).fit(xtr, ytr)
    ens = ensemble_from_sklearn(skl, 15)
    g = to_gemm(ens, 15)
    T, F, I = (int(s) for s in g.sel.shape)
    L = int(g.path.shape[2])

    # 262144 RESOURCE_EXHAUSTs a v5e when all five raced variants hold
    # their buffers at once (observed 2026-07-30); 65536 fits.
    B = int(os.environ.get("PROFILE_ROWS", "65536"))
    x = jnp.asarray(rng.normal(0, 1, (B, 15)).astype(np.float32))
    xh = np.asarray(x)
    oracle = skl.predict_proba(xh)[:, 1]

    dev = jax.devices()[0]
    hi = jax.lax.Precision.HIGHEST
    on_tpu = jax.default_backend() == "tpu"
    zdt = jnp.bfloat16 if on_tpu else jnp.float32

    feat_flat = jnp.asarray(
        np.argmax(np.asarray(g.sel), axis=1).astype(np.int32))  # [T, I]
    # nodes whose sel column is all-zero are padding; mark with feature 0
    # (their thresh is +inf so the decision is always True — same as the
    # matmul form where proj=0 <= inf).

    def stage_proj(x):  # the shipping form of this backend
        return _project(_selector(g.sel), x)

    def stage_z(d):
        return jnp.einsum("bti,til->btl", d, g.path.astype(zdt),
                          preferred_element_type=jnp.float32)

    def stage_leaf(onehot):
        return jnp.einsum("btl,tl->b", onehot, g.leaf_val, precision=hi)

    def kernel_current(x):
        return gemm_predict_proba(g, x)

    def kernel_projHIGH(x):
        proj = jnp.einsum("bf,tfi->bti", x, g.sel,
                          precision=jax.lax.Precision.HIGH)
        d = (proj <= g.thresh[None]).astype(zdt)
        z = stage_z(d)
        onehot = (jnp.abs(z - g.target[None]) < 0.5).astype(jnp.float32)
        return stage_leaf(onehot) / T

    def kernel_gatherD(x):
        # x[:, feat[t,i]] via one gather with STATIC indices
        xg = x[:, feat_flat.reshape(-1)].reshape(x.shape[0], T, I)
        d = (xg <= g.thresh[None]).astype(zdt)
        z = stage_z(d)
        onehot = (jnp.abs(z - g.target[None]) < 0.5).astype(jnp.float32)
        return stage_leaf(onehot) / T

    sel_flat = jnp.transpose(g.sel, (1, 0, 2)).reshape(F, T * I)

    def kernel_flatproj(x):
        proj = jnp.einsum("bf,fj->bj", x, sel_flat,
                          precision=hi).reshape(x.shape[0], T, I)
        d = (proj <= g.thresh[None]).astype(zdt)
        z = stage_z(d)
        onehot = (jnp.abs(z - g.target[None]) < 0.5).astype(jnp.float32)
        return stage_leaf(onehot) / T

    def kernel_int8z(x):
        # the SHIPPED int8 kernel (forest.gemm_leaf_sum z_mode="int8"),
        # not a hand-rolled copy — the race must time what serving runs
        return gemm_predict_proba(g, x, "int8")

    def bench(fn, *args, iters=20):
        if not on_tpu:
            iters = max(1, iters // 10)  # GEMM-on-CPU is ~1000x slower
        f = jax.jit(fn)
        out = f(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters, out

    results = {"device_kind": dev.device_kind, "B": B,
               "T": T, "I": I, "L": L}

    # stage timings (proj output is big — stage timing includes HBM
    # round-trip the fused kernel avoids; still ranks relative cost)
    t_proj, proj = bench(stage_proj, x, iters=5)
    d = (proj <= g.thresh[None]).astype(zdt)
    t_z, z = bench(stage_z, d, iters=5)
    onehot = (jnp.abs(z - g.target[None]) < 0.5).astype(jnp.float32)
    t_leaf, _ = bench(stage_leaf, onehot, iters=5)
    results["stage_ms"] = {"proj": round(t_proj * 1e3, 2),
                           "z": round(t_z * 1e3, 2),
                           "leaf": round(t_leaf * 1e3, 2)}
    del proj, d, z, onehot

    for name, fn in [("current", kernel_current),
                     ("projHIGH", kernel_projHIGH),
                     ("gatherD", kernel_gatherD),
                     ("flatproj", kernel_flatproj),
                     ("int8z", kernel_int8z)]:
        try:
            t, out = bench(fn, x)
            p = np.asarray(out)
            results[name] = {
                "ms": round(t * 1e3, 2),
                "rows_per_s": round(B / t, 0),
                "max_abs_diff_vs_sklearn": float(np.max(np.abs(p - oracle))),
            }
        except Exception as e:
            results[name] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}

    print(json.dumps(results))


if __name__ == "__main__":
    main()
