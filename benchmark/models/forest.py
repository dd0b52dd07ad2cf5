"""The reference's served model: a random forest, T=100, depth 8, on the
15 features (model_training.ipynb cell 59).

Every tree is COMPLETE (255 internal nodes, 256 leaves): the program's
GEMM form pads to the largest tree of the forest, so a forest fit on a few
thousand synthetic rows would compile to another shape with every seed
(139 x 140 and its neighbours were read on the chip) and never find its
programs in the cache, while a forest fit on a processor's history fills
depth 8. Splits test a random non-constant feature at a random quantile of
seeded synthetic rows; a leaf's value is the share of fraud among the
synthetic rows that reach it. The plain reference descends the node tables
in NumPy; the program gets its own form of the same tables."""

from __future__ import annotations

import numpy as np

from benchmark.models._rows import standardize, synthetic_rows


def complete_trees(z: np.ndarray, y: np.ndarray, n_trees: int, depth: int,
                   rng: np.random.Generator):
    """→ (feat, thresh, prob): heap-ordered tables [T, 2^(depth+1) - 1];
    node n's children are 2n+1 and 2n+2; go left iff x[feat] <= thresh."""
    n_int, n_all = 2 ** depth - 1, 2 ** (depth + 1) - 1
    usable = np.flatnonzero(z.std(axis=0) > 0)
    feat = np.zeros((n_trees, n_all), np.int32)
    thresh = np.zeros((n_trees, n_all), np.float32)
    feat[:, :n_int] = rng.choice(usable, size=(n_trees, n_int))
    q = rng.uniform(0.1, 0.9, size=(n_trees, n_int))
    ordered = np.sort(z, axis=0)
    thresh[:, :n_int] = ordered[(q * (len(z) - 1)).astype(np.int64),
                                feat[:, :n_int]]
    prob = np.full((n_trees, n_all), np.float32(y.mean()))
    for t in range(n_trees):
        leaf = descend(feat[t], thresh[t], z, depth)
        hits = np.bincount(leaf, minlength=n_all)
        frauds = np.bincount(leaf, weights=y, minlength=n_all)
        seen = hits > 0
        prob[t, seen] = (frauds[seen] / hits[seen]).astype(np.float32)
    return feat, thresh, prob


def descend(feat: np.ndarray, thresh: np.ndarray, z: np.ndarray,
            depth: int) -> np.ndarray:
    """The leaf each row of ``z`` reaches in one heap-ordered tree."""
    node = np.zeros(len(z), np.int64)
    rows = np.arange(len(z))
    for _ in range(depth):
        left = z[rows, feat[node]] <= thresh[node]
        node = 2 * node + np.where(left, 1, 2)
    return node


def build(config: dict, seed: int) -> dict:
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.models.forest import (
        TreeEnsemble,
        for_device,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler

    mp = config["model_params"]
    n_trees, depth = int(mp["n_estimators"]), int(mp["max_depth"])
    x, y, mean, scale = synthetic_rows(config, seed)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xF0237]))
    feat, thresh, prob = complete_trees(
        standardize(x, mean, scale), y, n_trees, depth, rng)
    n_int, n_all = 2 ** depth - 1, 2 ** (depth + 1) - 1
    idx = np.arange(n_all, dtype=np.int32)
    internal = idx < n_int
    left = np.where(internal, 2 * idx + 1, idx).astype(np.int32)
    right = np.where(internal, 2 * idx + 2, idx).astype(np.int32)
    ens = TreeEnsemble(
        feat=jnp.asarray(feat), thresh=jnp.asarray(thresh),
        left=jnp.asarray(np.broadcast_to(left, feat.shape)),
        right=jnp.asarray(np.broadcast_to(right, feat.shape)),
        prob=jnp.asarray(prob), max_depth=depth)
    params = for_device(ens, int(mp["n_features"]))

    def reference_proba(features: np.ndarray,
                        lower_precision: bool = False) -> np.ndarray:
        z = standardize(features, mean, scale, lower_precision)
        total = np.zeros(len(z), np.float64)
        for t in range(n_trees):
            total += prob[t, descend(feat[t], thresh[t], z, depth)]
        return total / n_trees

    return {"kind": "forest", "params": params,
            "scaler": Scaler(mean=mean, scale=scale),
            "z_mode": mp["z_mode"], "reference_proba": reference_proba}
