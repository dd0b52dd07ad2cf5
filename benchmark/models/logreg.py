"""The reference's LogisticRegression baseline (model_training.ipynb cell
50) on the same 15 features: weights on standardized features with the
configuration's fixed signs and seeded sizes (the sink's cost follows the
signs: a probability column that saturates at 1.0 has few distinct values,
one that falls toward 0 has one per row). The plain reference is the
logistic of a float64 dot product."""

from __future__ import annotations

import numpy as np

from benchmark.models._rows import standardize, synthetic_rows


def build(config: dict, seed: int) -> dict:
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.models.logreg import (
        LogRegParams,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler

    mp = config["model_params"]
    _, _, mean, scale = synthetic_rows(config, seed)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x106]))
    lo, hi = mp["weight_abs_range"]
    signs = np.asarray(mp["weight_signs"], np.float64)
    if signs.shape != (int(mp["n_features"]),) or set(signs) - {1.0, -1.0}:
        raise ValueError("weight_signs must be n_features values of +-1")
    w = (signs * rng.uniform(lo, hi, len(signs))).astype(np.float32)
    b = np.float32(mp["bias"])

    def reference_proba(features: np.ndarray,
                        lower_precision: bool = False) -> np.ndarray:
        z = standardize(features, mean, scale, lower_precision)
        logit = z.astype(np.float64) @ w.astype(np.float64) + float(b)
        return 1.0 / (1.0 + np.exp(-logit))

    return {"kind": "logreg",
            "params": LogRegParams(w=jnp.asarray(w), b=jnp.asarray(b)),
            "scaler": Scaler(mean=mean, scale=scale),
            "z_mode": None, "reference_proba": reference_proba}
