"""The device step, written once.

    unpack → reach(customer plane) → reach(terminal plane) → tail

``reach`` is how a table's owner runs its plane
(:class:`~.online.TablePlane`): a local call on one chip
(:func:`make_step`, below), the bucketed exchange on a mesh
(``parallel/step.py::make_sharded_step``, which wraps the same
:func:`~.online.run_planes` and the same tail in its ``shard_map``). The
tail — assemble → scale → classify → optional SGD → emission — and the
selective packing have one writer each, here.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from real_time_fraud_detection_system_tpu.config import Config
from real_time_fraud_detection_system_tpu.core.batch import unpack_batch
from real_time_fraud_detection_system_tpu.features.online import (
    assemble,
    run_planes,
    update_and_score_pallas,
    update_and_score_pallas_forest,
)
from real_time_fraud_detection_system_tpu.models.scaler import transform
from real_time_fraud_detection_system_tpu.utils.trace import step_scope


def make_tail(cfg: Config, predict_fn: Optional[Callable],
              loss_fn: Optional[Callable] = None, online_lr: float = 0.0,
              reduce_grads: Optional[Callable] = None):
    """→ ``tail(params, scaler, batch, c_mat, t_mat) -> (params', probs,
    emitted features)``, the scoring half of the step.

    ``predict_fn=None`` is ``scorer="cpu"``: the classifier runs host-side
    on the returned features, so the device emits a zero column instead
    of a predict whose output is discarded. ``reduce_grads(grads,
    labeled) -> (grads, any_labeled)`` is the mesh's gradient reduction;
    one chip passes none. ``fused=(probs, feats)`` is a fused kernel's
    answer standing in for assemble and classify."""
    learn = online_lr > 0.0 and loss_fn is not None

    def tail(params, scaler, batch, c_mat=None, t_mat=None, fused=None):
        feats = (fused[1] if fused is not None
                 else assemble(batch, cfg.features, c_mat, t_mat))
        x = transform(scaler, feats)
        if fused is not None:
            probs = fused[0]
        elif predict_fn is None:
            probs = jnp.zeros(batch.valid.shape, jnp.float32)
        else:
            with step_scope("classify"):
                probs = jnp.where(batch.valid, predict_fn(params, x), 0.0)
        if learn:
            with step_scope("learn"):
                labeled = batch.valid & (batch.label >= 0)
                y = jnp.maximum(batch.label, 0)
                g = jax.grad(loss_fn)(params, x, y, labeled)
                if reduce_grads is None:
                    has = jnp.any(labeled)
                else:
                    g, has = reduce_grads(g, labeled)
                has = has.astype(jnp.float32)
                params = jax.tree.map(
                    lambda p, gi: p - online_lr * has * gi, params, g)
        if cfg.runtime.emit_dtype == "bfloat16":
            # halve the emitted matrix's D2H bytes; the classifier above
            # consumed the f32 features (predictions unaffected)
            with step_scope("emit"):
                feats = feats.astype(jnp.bfloat16)
        return params, probs, feats

    return tail


def selective(cfg: Config) -> bool:
    """Whether the step packs its emission (``emit_threshold > 0``)."""
    return cfg.runtime.emit_features and cfg.runtime.emit_threshold > 0.0


def pack_selective(cfg: Config, valid, probs, feats) -> dict:
    """Selective emission's one transfer: the flagged rows' feature
    vectors gathered into a fixed-capacity buffer, then ``probs | count |
    idx | feats[idx]`` as ONE flat f32 array — a batch costs a single D2H
    copy instead of a full [B, 15] matrix (``engine._unpack_selective``
    and ``sharded_engine._finish_batch`` read this layout). Indices ride
    as f32, exact for any batch ≤ 2^24 rows; over a mesh they are global
    chunk slots, the gather on the re-assembled chunk. The full matrix is
    returned beside it (it exists already; untouched HBM until fetched)
    as the fallback when the flagged rows overflow the cap."""
    cap = max(8, int(valid.shape[0] * cfg.runtime.emit_cap_fraction))
    with step_scope("emit"):
        flagged = valid & (probs >= float(cfg.runtime.emit_threshold))
        idx = jnp.nonzero(flagged, size=cap, fill_value=0)[0]
        count = jnp.sum(flagged).astype(jnp.float32)
        packed = jnp.concatenate([
            probs, count[None], idx.astype(jnp.float32),
            feats[idx].reshape(-1)])
    return {"packed": packed, "full": feats}


def make_step(cfg: Config, predict_fn: Optional[Callable],
              loss_fn: Optional[Callable] = None, online_lr: float = 0.0,
              kernel_of: Callable = lambda params: None,
              z_mode: Optional[str] = None):
    """The one-chip step: ``step(feature_state, params, scaler, packed
    [7, B] int32) -> (feature_state, params, probs, emission[, tier rows
    under key_mode="exact"])`` — every engine config has ONE static arity,
    so the dispatch signatures stay enumerable and AOT-coverable.

    ``kernel_of(params)`` names the fused Pallas kernel to serve, or None
    (``engine._announce_pallas``): a trace-time fact read from the config
    and the params pytree's FORM and static shapes, never a traced value,
    so a reload that changes the form retraces, as intended."""
    fcfg = cfg.features
    tail = make_tail(cfg, predict_fn, loss_fn, online_lr)

    def step(fstate, params, scaler, packed):
        # One packed H2D array per batch (see core.batch.pack_batch):
        # the unpack is free bitcasts inside the fused program.
        with step_scope("unpack"):
            batch = unpack_batch(packed)
        fused = c_mat = t_mat = tier = None
        kernel = kernel_of(params)
        # rtfdslint: disable=jit-recompile-hazard (kernel is a str computed from static facts only — config, isinstance on the params pytree, admit_block over static .shape; no traced VALUE is branched on)
        if kernel == "fused_logreg":
            fstate, probs, feats = update_and_score_pallas(
                fstate, batch, fcfg, scaler.mean, scaler.scale,
                params.w, params.b)
            fused = (probs, feats)
        # rtfdslint: disable=jit-recompile-hazard (same static str as the branch above)
        elif kernel == "fused_forest":
            from real_time_fraud_detection_system_tpu.ops.pallas_forest \
                import to_pallas

            pf = to_pallas(params, z_mode)
            fstate, leaf, feats = update_and_score_pallas_forest(
                fstate, batch, fcfg, scaler.mean, scaler.scale, pf)
            with step_scope("fused_step"):
                fused = (jnp.where(batch.valid, leaf / pf.n_trees, 0.0),
                         feats)
        else:
            fstate, c_mat, t_mat, tier, _ = run_planes(fstate, batch, fcfg)
        params, probs, feats = tail(params, scaler, batch, c_mat, t_mat,
                                    fused)
        emit = (pack_selective(cfg, batch.valid, probs, feats)
                if selective(cfg) else feats)
        return (fstate, params, probs, emit) + (
            () if tier is None else (tier,))

    return step
