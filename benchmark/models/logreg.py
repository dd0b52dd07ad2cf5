"""The reference's LogisticRegression baseline (model_training.ipynb cell
50) on the same 15 features: seeded weights on standardized features. The
plain reference is the logistic of a float64 dot product."""

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
    w = rng.normal(0.0, float(mp["weight_std"]),
                   int(mp["n_features"])).astype(np.float32)
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
