"""Small arithmetic the harness and its tests share."""

from __future__ import annotations

import statistics
from typing import Sequence

import numpy as np


def percentile(values: np.ndarray, q: float) -> float:
    """The q-th percentile by linear interpolation (NumPy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and the third quartile as a share of the
    median, as the driver reads it (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def decision_latency_ms(done_s: np.ndarray, due_s: np.ndarray) -> np.ndarray:
    """Creation → decision, per row: the return of ``sink.append`` for the
    row's batch less the row's due time, in milliseconds."""
    return (np.asarray(done_s, np.float64)
            - np.asarray(due_s, np.float64)) * 1e3
