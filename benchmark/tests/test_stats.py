"""Percentile, spread and lateness arithmetic on hand-made numbers."""

import numpy as np
import pytest

from benchmark import stats


def test_decision_latency_from_due_time_to_ack():
    # three rows due at 0.10, 0.20, 0.25 s, acknowledged in one batch at 0.40
    lat = stats.decision_latency_ms([0.40, 0.40, 0.40], [0.10, 0.20, 0.25])
    assert lat == pytest.approx([300.0, 200.0, 150.0])
    assert stats.percentile(lat, 50) == pytest.approx(200.0)
    assert stats.percentile(lat, 95) == pytest.approx(290.0)


def test_spread_is_the_drivers():
    # statistics.quantiles' quartiles (exclusive), not NumPy's
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    assert stats.spread(vals) == pytest.approx((104.25 - 100.75) / 102.5)
    assert stats.spread(vals) > (np.percentile(vals, 75)
                                 - np.percentile(vals, 25)) / 102.5
