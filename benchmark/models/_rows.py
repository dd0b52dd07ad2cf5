"""Seeded synthetic raw feature rows, shaped like the traffic's features,
that the model builders fit their scaler (and the forest its trees) on.
Not a builder: ``run.py`` finds builders by the configuration's ``model``."""

from __future__ import annotations

import numpy as np


def synthetic_rows(config: dict, seed: int):
    """→ (x [n, 15] float32 raw features, y [n] labels, mean, scale).

    Counts are Poisson around what a key of a drawn rank sees in 1, 7 and
    30 days at ``nominal_rows_per_day``; averages scatter around a
    per-customer mean amount; terminal risks are 0 (no labels are served).
    ``mean`` and ``scale`` are the StandardScaler's (ddof 0, zero variance
    → 1), in float32."""
    mp = config["model_params"]
    n, per_day = int(mp["fit_rows"]), float(mp["nominal_rows_per_day"])
    uni = config["key_universe"]
    windows = config["features"]["windows"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x30DE1]))
    mean_amt = rng.uniform(5.0, 100.0, n)
    amount = np.abs(rng.normal(mean_amt, mean_amt / 2.0))
    # a customer drawn by rate has rate share 2*sqrt(u)/N of the traffic
    c_share = 2.0 * np.sqrt(rng.random(n)) / float(uni["customers"])
    # a terminal drawn by Zipf(0.99) weight: share ~ rank^-0.99 / H
    nt = float(uni["terminals"])
    rank = (1.0 + rng.random(n) * (nt ** 0.01 - 1.0)) ** 100.0
    t_share = rank ** -0.99 / ((nt ** 0.01 - 1.0) / 0.01)
    cols = [amount, (rng.random(n) < 2 / 7), (rng.random(n) < 7 / 24)]
    for w in windows:
        cnt = 1.0 + rng.poisson(c_share * per_day * w)
        cols += [cnt, np.abs(rng.normal(mean_amt, mean_amt / 2.0
                                        / np.sqrt(cnt)))]
    for w in windows:
        cols += [rng.poisson(t_share * per_day * w), np.zeros(n)]
    x = np.stack(cols, axis=1).astype(np.float32)
    mean = x.astype(np.float64).mean(axis=0)
    std = x.astype(np.float64).std(axis=0)
    std[std == 0.0] = 1.0
    mean, scale = mean.astype(np.float32), std.astype(np.float32)
    z = (x - mean) / scale
    # a label the trees can learn: large amounts against the customer's
    # habit at busy terminals, with noise
    score = z[:, 0] - 0.6 * z[:, 8] + 0.4 * z[:, 13] + 0.3 * z[:, 3] \
        + rng.normal(0.0, 0.7, n)
    y = (score > np.quantile(score, 0.8)).astype(np.int32)
    return x, y, mean, scale


def standardize(x: np.ndarray, mean: np.ndarray, scale: np.ndarray,
                lower_precision: bool = False) -> np.ndarray:
    """StandardScaler.transform in float32 (NumPy's divide is IEEE). The
    CONTROL's ``lower_precision`` does the subtraction and the division in
    bfloat16."""
    x = np.asarray(x, np.float32)
    if not lower_precision:
        return (x - mean) / scale
    from benchmark.reference import bf16_round as r

    return r(r(r(x) - r(mean)) / r(scale))
