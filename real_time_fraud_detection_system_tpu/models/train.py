"""Offline training pipeline: split → scale → fit → assess.

Re-implements the reference's training protocol
(``model_training.ipynb · cells 8,26,50``; ``shared_functions.py:133-188``):
a time-based train/delay/test split (153/30/30 days by default) where test
days drop transactions of customers already known compromised — known =
defrauded in the train window, plus frauds discovered up to each test day
minus the delay. Features come from :func:`..features.offline
.compute_features_replay` so the model trains on exactly the serving
semantics.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from real_time_fraud_detection_system_tpu.utils.logging import get_logger

from real_time_fraud_detection_system_tpu.config import Config
from real_time_fraud_detection_system_tpu.data.generator import Transactions
from real_time_fraud_detection_system_tpu.features.offline import (
    compute_features_replay,
)
from real_time_fraud_detection_system_tpu.models.forest import (
    TreeEnsemble,
    fit_forest,
    for_device,
)
from real_time_fraud_detection_system_tpu.models.forest import (
    predict_proba as forest_predict_proba,
)
from real_time_fraud_detection_system_tpu.models.logreg import (
    LogRegParams,
    logreg_predict_proba,
    train_logreg,
)
from real_time_fraud_detection_system_tpu.models.mlp import (
    mlp_predict_proba,
    train_mlp,
)
from real_time_fraud_detection_system_tpu.models.metrics import (
    performance_assessment,
)
from real_time_fraud_detection_system_tpu.models.scaler import (
    Scaler,
    fit_scaler,
    transform,
)


def fit_split_to_days(
    n_days: int, delta_train: int, delta_delay: int, delta_test: int
) -> Tuple[int, int, int]:
    """Shrink a (train, delay, test) day split to fit an n_days dataset.

    The reference pins 153/30/30 for its 245-day dataset
    (``model_training.ipynb · cell 8``); smaller datasets (docs examples,
    tests, `make run-all DAYS=...`) would get an EMPTY test window and NaN
    metrics with those absolutes. When the spans don't fit, scale them
    proportionally (preserving the 153:30:30 shape), keeping train/test
    ≥ 1 day; leftover days go to train. A ≤1-day dataset cannot hold
    disjoint train and test windows at all — it gets (n_days, 0, 0), and
    the caller's metrics are honestly NaN."""
    need = delta_train + delta_delay + delta_test
    if n_days >= need or need <= 0:
        return delta_train, delta_delay, delta_test
    if n_days <= 1:
        return max(n_days, 0), 0, 0
    f = n_days / need
    test = max(1, int(delta_test * f))
    delay = int(delta_delay * f)
    train = max(1, n_days - delay - test)
    if train + delay + test > n_days:
        delay = max(0, n_days - train - test)
    return train, delay, test


def scale_split_to_txs(
    txs: Transactions,
    delta_train: int,
    delta_delay: int,
    delta_test: int,
    start_day: int = 0,
    logger_name: str = "train",
) -> Tuple[int, int, int]:
    """:func:`fit_split_to_days` against the span actually available to a
    split anchored at ``start_day`` (days [start_day, dataset end)), with
    the scale-down warning. Shared by :func:`train_model` and
    ``selection.prequential_split``."""
    n_days = int(txs.tx_time_days.max()) + 1 if txs.n else 0
    avail = max(0, n_days - start_day)
    scaled = fit_split_to_days(avail, delta_train, delta_delay, delta_test)
    if scaled != (delta_train, delta_delay, delta_test):
        get_logger(logger_name).warning(
            "%d days available from day %d < configured %d/%d/%d split; "
            "scaled to %d/%d/%d",
            avail, start_day, delta_train, delta_delay, delta_test, *scaled,
        )
    return scaled


def train_delay_test_split(
    txs: Transactions,
    start_day: int = 0,
    delta_train: int = 153,
    delta_delay: int = 30,
    delta_test: int = 30,
) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (train_mask, test_mask) over txs rows.

    Test-day filtering matches ``shared_functions.py:146-171``: a customer
    enters the known-compromised pool if they have a fraud in the train
    window, or a fraud on day (test_day - delay) as days advance; their
    transactions are excluded from the test set.
    """
    days = txs.tx_time_days
    train_mask = (days >= start_day) & (days < start_day + delta_train)

    known = set(np.unique(txs.customer_id[train_mask & (txs.tx_fraud == 1)]).tolist())
    test_mask = np.zeros(txs.n, dtype=bool)
    test_start = start_day + delta_train + delta_delay
    for d in range(delta_test):
        # Frauds discovered by this test day (delay days after they happened).
        disc_day = start_day + delta_train + d - 1
        disc = (days == disc_day) & (txs.tx_fraud == 1)
        known.update(np.unique(txs.customer_id[disc]).tolist())
        day_mask = days == test_start + d
        if known:
            known_arr = np.fromiter(known, dtype=np.int64)
            day_mask &= ~np.isin(txs.customer_id, known_arr)
        test_mask |= day_mask
    return train_mask, test_mask


@dataclass
class TrainedModel:
    """Scaler + fitted classifier params, ready for the serving step."""

    kind: str
    scaler: Scaler
    params: object  # LogRegParams | MLPParams | TreeEnsemble

    def _device_params(self, convert):
        """Lazily convert params to the fast device form, once."""
        dev = getattr(self, "_dev_cache", None)
        if dev is None:
            dev = convert(self.params)
            object.__setattr__(self, "_dev_cache", dev)
        return dev

    # Rows per device call of :meth:`predict_proba`. The GEMM-form tree
    # contraction materializes [B, T, I] / [B, T, L] f32 intermediates
    # (100 KB per row at T=100, depth 8): the whole 170k-row test split in
    # one call asked a 16 GB v5e for a 6.84 GB buffer on top of what it
    # held and was refused (PR 21's first chip run). 8,192 rows keep a
    # call under 1 GB per intermediate on any backend.
    PREDICT_BLOCK_ROWS = 8192

    def predict_proba(self, features: np.ndarray) -> np.ndarray:
        n = int(np.shape(features)[0])
        blk = self.PREDICT_BLOCK_ROWS
        if n <= blk:
            return self._predict_block(features)
        return np.concatenate([self._predict_block(features[i:i + blk])
                               for i in range(0, n, blk)])

    def _predict_block(self, features: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        x = transform(self.scaler, jnp.asarray(features, dtype=jnp.float32))
        if self.kind == "logreg":
            return np.asarray(logreg_predict_proba(self.params, x))
        if self.kind == "mlp":
            return np.asarray(mlp_predict_proba(self.params, x))
        if self.kind == "gbt":
            from real_time_fraud_detection_system_tpu.models.gbt import (
                gbt_for_device,
                gbt_predict_proba,
            )

            nf = int(x.shape[1])
            dev = self._device_params(lambda p: gbt_for_device(p, nf))
            return np.asarray(gbt_predict_proba(dev, x))
        if self.kind in ("tree", "forest"):
            nf = int(x.shape[1])
            dev = self._device_params(lambda p: for_device(p, nf))
            return np.asarray(forest_predict_proba(dev, x))
        if self.kind == "autoencoder":
            from real_time_fraud_detection_system_tpu.models.autoencoder import (
                autoencoder_predict_proba,
            )

            return np.asarray(autoencoder_predict_proba(self.params, x))
        raise ValueError(f"unknown model kind {self.kind}")

    def _np_params(self):
        """One-time device→host conversion of params for the NumPy path."""
        cached = getattr(self, "_np_cache", None)
        if cached is None:
            if self.kind == "logreg":
                cached = (np.asarray(self.params.w), float(self.params.b))
            elif self.kind == "mlp":
                cached = [(np.asarray(w), np.asarray(b)) for w, b in self.params]
            elif self.kind == "autoencoder":
                cached = (
                    [(np.asarray(w), np.asarray(b)) for w, b in self.params.layers],
                    float(self.params.err_scale),
                )
            elif self.kind in ("tree", "forest", "gbt"):
                trees = self.params.trees if self.kind == "gbt" else self.params
                cached = {
                    "feat": np.asarray(trees.feat),
                    "thresh": np.asarray(trees.thresh),
                    "left": np.asarray(trees.left),
                    "right": np.asarray(trees.right),
                    "prob": np.asarray(trees.prob),
                    "max_depth": int(trees.max_depth),
                    "base": float(self.params.base_score)
                    if self.kind == "gbt" else 0.0,
                }
            object.__setattr__(self, "_np_cache", cached)
        scaler = getattr(self, "_np_scaler", None)
        if scaler is None:
            scaler = (np.asarray(self.scaler.mean), np.asarray(self.scaler.scale))
            object.__setattr__(self, "_np_scaler", scaler)
        return cached, scaler

    def predict_proba_np(self, features: np.ndarray) -> np.ndarray:
        """Pure-NumPy host scoring — the ``--scorer cpu`` baseline path
        (reference semantics: scaler.transform + predict_proba on CPU,
        ``fraud_detection.py:183-195``), no accelerator involved. Params are
        converted device→host once and cached."""
        params, (mean, scale) = self._np_params()
        x = ((features.astype(np.float32) - mean) / scale).astype(np.float32)
        if self.kind == "logreg":
            w, b = params
            z = x @ w + b
            return 1.0 / (1.0 + np.exp(-z))
        if self.kind == "mlp":
            h = x
            for w, b in params[:-1]:
                h = np.maximum(h @ w + b, 0.0)
            w, b = params[-1]
            z = (h @ w + b)[:, 0]
            return 1.0 / (1.0 + np.exp(-z))
        if self.kind == "autoencoder":
            layers, err_scale = params
            h = x
            for w, b in layers[:-1]:
                h = np.maximum(h @ w + b, 0.0)
            w, b = layers[-1]
            err = np.mean((h @ w + b - x) ** 2, axis=1)
            return 1.0 - np.exp(-err / max(err_scale, 1e-12))
        if self.kind in ("tree", "forest", "gbt"):
            feat = params["feat"]
            thresh = params["thresh"]
            left = params["left"]
            right = params["right"]
            prob = params["prob"]
            t = feat.shape[0]
            b_ = x.shape[0]
            node = np.zeros((b_, t), dtype=np.int64)
            tree_idx = np.arange(t)[None, :]
            for _ in range(params["max_depth"]):
                f = feat[tree_idx, node]
                xv = np.take_along_axis(x, f.reshape(b_, -1), axis=1).reshape(b_, t)
                go_left = xv <= thresh[tree_idx, node]
                node = np.where(go_left, left[tree_idx, node],
                                right[tree_idx, node])
            leaves = prob[tree_idx, node]
            if self.kind == "gbt":
                z = params["base"] + leaves.sum(axis=1)
                return 1.0 / (1.0 + np.exp(-z))
            return leaves.mean(axis=1)
        raise ValueError(f"unknown model kind {self.kind}")


def fit_classifier(
    kind: str,
    xs: np.ndarray,
    y_train: np.ndarray,
    cfg: Config,
    pos_weight: Optional[float] = None,
):
    """Fit one classifier of the 5-model zoo on pre-scaled features.

    Dispatch shared by :func:`train_model` and the model-selection machinery
    (``models/selection.py``); reference equivalent is the classifier dict of
    ``model_training.ipynb · cell 50``.
    """
    if pos_weight is None:
        from real_time_fraud_detection_system_tpu.models.metrics import (
            rebalance_pos_weight,
        )

        pos_weight = rebalance_pos_weight(y_train)

    if kind == "logreg":
        params = train_logreg(
            xs, y_train,
            learning_rate=cfg.train.learning_rate,
            batch_size=cfg.train.batch_size,
            epochs=cfg.train.epochs,
            pos_weight=pos_weight,
            seed=cfg.model.seed,
        )
    elif kind == "mlp":
        params = train_mlp(
            xs, y_train,
            hidden=tuple(cfg.model.mlp_hidden),
            batch_size=cfg.train.batch_size,
            epochs=cfg.train.epochs,
            pos_weight=pos_weight,
            seed=cfg.model.seed,
        )
    elif kind in ("tree", "forest"):
        params = fit_forest(
            xs, y_train,
            n_trees=cfg.model.forest_n_trees,
            max_depth=(cfg.model.tree_max_depth if kind == "tree"
                       else cfg.model.forest_max_depth),
            seed=cfg.model.seed,
            kind=kind,
        )
    elif kind == "gbt":
        from real_time_fraud_detection_system_tpu.models.gbt import train_gbt

        params = train_gbt(
            xs, y_train,
            n_trees=cfg.model.forest_n_trees,
            max_depth=cfg.model.forest_max_depth,
        )
    elif kind == "autoencoder":
        from real_time_fraud_detection_system_tpu.models.autoencoder import (
            train_autoencoder,
        )

        params = train_autoencoder(
            xs, y_train,
            hidden=tuple(cfg.model.autoencoder_hidden),
            batch_size=cfg.train.batch_size,
            epochs=cfg.train.epochs,
            seed=cfg.model.seed,
        )
    else:
        raise ValueError(f"unknown model kind {kind}")
    return params


def fit_and_assess(
    txs: Transactions,
    features: np.ndarray,
    cfg: Config,
    kind: str,
    train_mask: np.ndarray,
    test_mask: np.ndarray,
) -> Tuple[TrainedModel, dict, float, float, np.ndarray]:
    """scale → fit → predict → assess on one (train, test) mask pair.

    Shared by :func:`train_model` and the model-selection sweeps; returns
    (model, test metrics, fit_seconds, predict_seconds, test_probs) — the
    timing pair is the reference's per-classifier execution-time hook
    (``shared_functions.py:312-320``); the probs let callers plot/report
    without re-running the (timed) inference pass.
    """
    import time

    import jax.numpy as jnp

    x_train = features[train_mask]
    y_train = txs.tx_fraud[train_mask].astype(np.float32)
    scaler = fit_scaler(x_train)
    xs = np.asarray(transform(scaler, jnp.asarray(x_train, dtype=jnp.float32)))
    t0 = time.perf_counter()
    params = fit_classifier(kind, xs, y_train, cfg)
    fit_s = time.perf_counter() - t0
    model = TrainedModel(kind=kind, scaler=scaler, params=params)
    t0 = time.perf_counter()
    probs = model.predict_proba(features[test_mask])
    predict_s = time.perf_counter() - t0
    metrics = performance_assessment(
        txs.tx_fraud[test_mask],
        probs,
        days=txs.tx_time_days[test_mask],
        customer_ids=txs.customer_id[test_mask],
    )
    return model, metrics, fit_s, predict_s, probs


def fit_and_assess_sequence(
    txs: Transactions,
    cfg: Config,
    train_mask: np.ndarray,
    test_mask: np.ndarray,
    start_date: Optional[str] = None,
) -> Tuple[TrainedModel, dict, float, float, np.ndarray]:
    """Sequence-family counterpart of :func:`fit_and_assess`: train on
    the train-window sequences, evaluate by streaming the table through
    the ONLINE history step (the exact serving path — train/serve skew
    shows up here, not in production). Returns (model, test metrics,
    fit_seconds, predict_seconds, test_probs)."""
    import time

    import jax
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.core.batch import make_batch
    from real_time_fraud_detection_system_tpu.features.history import (
        init_history_state,
        update_and_score,
    )
    from real_time_fraud_detection_system_tpu.models.sequence import (
        build_sequences,
        train_transformer,
    )
    from real_time_fraud_detection_system_tpu.utils.timing import (
        date_to_epoch_s,
    )

    epoch0 = date_to_epoch_s(start_date or cfg.data.start_date)
    m = cfg.model
    seqs = build_sequences(
        txs.slice(train_mask), max_len=cfg.features.history_len,
        start_epoch_s=epoch0)
    t0 = time.perf_counter()
    params = train_transformer(
        seqs,
        d_model=m.seq_d_model,
        n_heads=m.seq_n_heads,
        n_layers=m.seq_n_layers,
        d_ff=m.seq_d_ff,
        epochs=cfg.train.epochs,
        seed=cfg.data.seed,
    )
    fit_s = time.perf_counter() - t0

    # serving-path evaluation: stream the table through the online step
    t_us = txs.epoch_us(epoch0)
    state = init_history_state(cfg.features)
    step = jax.jit(update_and_score, static_argnums=(3,))
    probs = np.zeros(txs.n, dtype=np.float64)
    rows = 4096
    t0 = time.perf_counter()
    for s in range(0, txs.n, rows):
        e = min(s + rows, txs.n)
        batch = make_batch(
            customer_id=txs.customer_id[s:e],
            terminal_id=txs.terminal_id[s:e],
            tx_datetime_us=t_us[s:e],
            amount_cents=txs.amount_cents[s:e],
            pad_to=rows,
        )
        state, p = step(state, params, jax.tree.map(jnp.asarray, batch),
                        cfg.features)
        probs[s:e] = np.asarray(p)[: e - s]
    predict_s = time.perf_counter() - t0
    metrics = performance_assessment(
        txs.tx_fraud[test_mask],
        probs[test_mask],
        days=txs.tx_time_days[test_mask],
        customer_ids=txs.customer_id[test_mask],
    )
    scaler = Scaler(mean=jnp.zeros(15, jnp.float32),
                    scale=jnp.ones(15, jnp.float32))
    model = TrainedModel(kind="sequence", scaler=scaler, params=params)
    return model, metrics, fit_s, predict_s, probs[test_mask]


def train_sequence_model(
    txs: Transactions,
    cfg: Config,
    start_date: Optional[str] = None,
) -> Tuple[TrainedModel, dict]:
    """Offline training of the sequence (causal transformer) family —
    see :func:`fit_and_assess_sequence` for the train/eval contract."""
    dtr, dde, dte = scale_split_to_txs(
        txs,
        cfg.train.delta_train_days,
        cfg.train.delta_delay_days,
        cfg.train.delta_test_days,
    )
    train_mask, test_mask = train_delay_test_split(
        txs, delta_train=dtr, delta_delay=dde, delta_test=dte
    )
    model, metrics, _, _, _ = fit_and_assess_sequence(
        txs, cfg, train_mask, test_mask, start_date=start_date)
    return model, metrics


def train_model(
    txs: Transactions,
    cfg: Config,
    features: Optional[np.ndarray] = None,
    kind: Optional[str] = None,
) -> Tuple[TrainedModel, dict]:
    """End-to-end offline training; returns (model, test metrics)."""
    kind = kind or cfg.model.kind
    if kind == "sequence":
        # the sequence family trains on event histories, not the replayed
        # aggregate features — dispatch before any replay work
        return train_sequence_model(txs, cfg)
    if features is None:
        features = compute_features_replay(
            txs, cfg.features, start_date=cfg.data.start_date
        )
    dtr, dde, dte = scale_split_to_txs(
        txs,
        cfg.train.delta_train_days,
        cfg.train.delta_delay_days,
        cfg.train.delta_test_days,
    )
    train_mask, test_mask = train_delay_test_split(
        txs, delta_train=dtr, delta_delay=dde, delta_test=dte
    )
    model, metrics, _, _, _ = fit_and_assess(
        txs, features, cfg, kind, train_mask, test_mask
    )
    return model, metrics
