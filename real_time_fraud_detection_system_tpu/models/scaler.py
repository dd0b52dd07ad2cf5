"""Feature standardization — sklearn-StandardScaler-compatible, jnp transform.

The reference fits a ``StandardScaler`` offline and applies it per batch
inside the scoring UDF (``shared_functions.py:114-120`` scaleData,
``fraud_detection.py:183-195``). Here the (mean, scale) pair is a pytree that
lives on device, and the transform fuses into the scoring kernel under jit.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from real_time_fraud_detection_system_tpu.ops.numerics import div_ieee
from real_time_fraud_detection_system_tpu.utils.trace import step_scope


class Scaler(NamedTuple):
    mean: jnp.ndarray  # float32 [F]
    scale: jnp.ndarray  # float32 [F] — stddev, zero-variance cols → 1.0


def fit_scaler(x: np.ndarray) -> Scaler:
    """Fit on host (numpy), matching sklearn: ddof=0, zero-var → scale 1."""
    mean = np.asarray(x, dtype=np.float64).mean(axis=0)
    std = np.asarray(x, dtype=np.float64).std(axis=0)
    std[std == 0.0] = 1.0
    return Scaler(
        mean=jnp.asarray(mean, dtype=jnp.float32),
        scale=jnp.asarray(std, dtype=jnp.float32),
    )


def transform(scaler: Scaler, x: jnp.ndarray) -> jnp.ndarray:
    # div_ieee: the chip's own f32 divide is 1 ulp off sklearn's in ~32%
    # of values, enough to flip a tree's vote on a threshold-sitting row
    with step_scope("scale"):
        return div_ieee(x - scaler.mean, scaler.scale)


def inverse_transform(scaler: Scaler, x: jnp.ndarray) -> jnp.ndarray:
    return x * scaler.scale + scaler.mean
