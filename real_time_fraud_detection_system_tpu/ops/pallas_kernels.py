"""Pallas TPU kernels for the scoring hot path.

``fused_featurize_score``: one kernel for window-aggregate → 15-feature
assembly → standardize → linear classify. XLA already fuses much of this
chain; the kernel guarantees it — one VMEM-resident pass per batch tile,
zero intermediate HBM traffic between featurization and the classifier —
and is the template for deeper fusions (the state *gather* stays outside:
Mosaic has no vectorized dynamic row-gather, while XLA's TPU gather emitter
handles it well; the measured split keeps each side on its fastest path).

Everything inside is VPU/MXU-friendly: comparisons, selects, lane
reductions over the NB day-bucket axis, and a [B,15]·[15] contraction — no
data-dependent indexing, so the kernel lowers cleanly through Mosaic.

Replaces (with ``RuntimeConfig.use_pallas``) the jnp composition
``query_gathered`` (`ops/windows.py`) + ``_flags``+stack
(`features/online.py`) + ``scaler.transform``+``logreg_predict_proba``
(`models/`), which together re-implement the reference's per-batch Spark
chain: enrichment SQL + feature join (``fraud_detection.py:100-132``) +
``scale_and_predict_udf`` (``:183-195``).

On non-TPU backends the kernel runs in interpreter mode (slow, exact) so
CPU tests validate the identical code path the TPU compiles.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from real_time_fraud_detection_system_tpu.ops.numerics import (
    div_ieee,
    sum_fixed_order,
)


def _on_tpu() -> bool:
    """The default for ``interpret``: compiled on the chip, interpreted
    everywhere else. No fallback — a backend that cannot be asked is an
    error, not a reason to interpret."""
    return jax.default_backend() == "tpu"


def assemble_features(
    c_bd, c_cnt, c_amt,  # [Bt, NB] customer rows (bucket_day, count, amount)
    t_bd, t_cnt, t_frd,  # [Bt, NB] terminal rows (bucket_day, count, fraud)
    day, tod, amount,  # [Bt, 1] per-row scalars (int32, int32, f32)
    *,
    windows: Tuple[int, ...],
    delay: int,
    weekend_start: int,
    night_end: int,
) -> jnp.ndarray:
    """Gathered state rows → raw [Bt, F] feature block (age-mask form).

    The in-kernel twin of ``ops/windows.py::query_gathered`` +
    ``features/online.py::_flags`` + column stack — pure VPU math
    (compares, selects, lane reductions over the NB axis), shared by the
    linear fused kernel below and the forest fused step
    (``ops/pallas_forest.py``). Feature order matches
    ``features/spec.py::FEATURE_NAMES``."""
    age_c = day - c_bd  # [Bt, NB]
    live_c = (c_bd >= 0) & (age_c >= 0)
    age_t = day - delay - t_bd
    live_t = (t_bd >= 0) & (age_t >= 0)

    cols = [amount]
    # flags
    weekday = jnp.remainder(day + 3, 7)
    cols.append((weekday >= weekend_start).astype(jnp.float32))
    cols.append((tod // 3600 <= night_end).astype(jnp.float32))
    for w in windows:
        sel = jnp.where(live_c & (age_c < w), 1.0, 0.0)
        cnt = jnp.sum(c_cnt * sel, axis=1, keepdims=True)
        # dollars: the order is pinned, as in query_gathered
        amt = sum_fixed_order(c_amt * sel, axis=1, keepdims=True)
        cols.append(cnt)
        cols.append(jnp.where(
            cnt > 0, div_ieee(amt, jnp.maximum(cnt, 1.0)), 0.0))
    for w in windows:
        sel = jnp.where(live_t & (age_t < w), 1.0, 0.0)
        cnt = jnp.sum(t_cnt * sel, axis=1, keepdims=True)
        frd = jnp.sum(t_frd * sel, axis=1, keepdims=True)
        cols.append(cnt)
        cols.append(jnp.where(
            cnt > 0, div_ieee(frd, jnp.maximum(cnt, 1.0)), 0.0))
    return jnp.concatenate(cols, axis=1)  # [Bt, F]


def _score_kernel(
    c_bd_ref,  # int32 [Bt, NB] customer bucket days
    c_cnt_ref,  # f32 [Bt, NB]
    c_amt_ref,  # f32 [Bt, NB]
    t_bd_ref,  # int32 [Bt, NB] terminal bucket days
    t_cnt_ref,  # f32 [Bt, NB]
    t_frd_ref,  # f32 [Bt, NB]
    ivec_ref,  # int32 [Bt, 2] (day, tod_s)
    fvec_ref,  # f32 [Bt, 2] (amount, valid)
    pvec_ref,  # f32 [4, F] rows: (mean, scale, w, b-broadcast)
    probs_ref,  # f32 [Bt, 1] out
    feats_ref,  # f32 [Bt, F] out
    *,
    windows: Tuple[int, ...],
    delay: int,
    weekend_start: int,
    night_end: int,
):
    day = ivec_ref[:, 0:1]  # [Bt, 1]
    tod = ivec_ref[:, 1:2]
    amount = fvec_ref[:, 0:1]
    valid = fvec_ref[:, 1:2]

    feats = assemble_features(
        c_bd_ref[:], c_cnt_ref[:], c_amt_ref[:],
        t_bd_ref[:], t_cnt_ref[:], t_frd_ref[:],
        day, tod, amount,
        windows=windows, delay=delay, weekend_start=weekend_start,
        night_end=night_end,
    )
    feats_ref[:] = feats

    # --- standardize + logistic score
    mean = pvec_ref[0:1, :]
    scale = pvec_ref[1:2, :]
    w_row = pvec_ref[2:3, :]
    bias = pvec_ref[3:4, 0:1]
    x = div_ieee(feats - mean, scale)
    z = jnp.sum(x * w_row, axis=1, keepdims=True) + bias
    probs_ref[:] = jax.nn.sigmoid(z) * valid


def fused_featurize_score(
    c_rows: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],  # (bd, cnt, amt)
    t_rows: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],  # (bd, cnt, frd)
    day: jnp.ndarray,  # int32 [B]
    tod_s: jnp.ndarray,  # int32 [B]
    amount: jnp.ndarray,  # f32 [B]
    valid: jnp.ndarray,  # bool [B]
    scaler_mean: jnp.ndarray,  # f32 [F]
    scaler_scale: jnp.ndarray,  # f32 [F]
    w: jnp.ndarray,  # f32 [F]
    b: jnp.ndarray,  # f32 scalar
    windows: Sequence[int] = (1, 7, 30),
    delay: int = 7,
    weekend_start: int = 5,
    night_end: int = 6,
    block_rows: int = 512,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (probs [B], features [B, F]); batch tiled over a 1-D grid.

    ``block_rows=512``: every row operand is lane-padded to 128 in VMEM,
    and at 1024-row tiles the v5e compiler counted 16.45 MB of scoped VMEM
    against its 16 MB limit at 65,536 rows (the same arithmetic as
    ``pallas_forest.FUSED_BLOCK_ROWS``; ``tests/test_tpu_compile.py``
    asks the compiler)."""
    c_bd, c_cnt, c_amt = c_rows
    t_bd, t_cnt, t_frd = t_rows
    bsz, nb = c_bd.shape
    n_feat = scaler_mean.shape[0]
    bt = min(block_rows, bsz)
    if bsz % bt != 0:  # static shapes: caller pads batches to buckets
        raise ValueError(f"batch {bsz} not divisible by block_rows {bt}")
    grid = (bsz // bt,)
    if interpret is None:
        interpret = not _on_tpu()

    ivec = jnp.stack([day.astype(jnp.int32), tod_s.astype(jnp.int32)], axis=1)
    fvec = jnp.stack(
        [amount.astype(jnp.float32), valid.astype(jnp.float32)], axis=1
    )
    pvec = jnp.stack(
        [
            scaler_mean.astype(jnp.float32),
            scaler_scale.astype(jnp.float32),
            w.astype(jnp.float32),
            jnp.full((n_feat,), b, dtype=jnp.float32),
        ],
        axis=0,
    )

    row_spec = lambda width: pl.BlockSpec(  # noqa: E731
        (bt, width), lambda i: (i, 0), memory_space=pltpu.VMEM,
    )
    kernel = functools.partial(
        _score_kernel,
        windows=tuple(windows),
        delay=delay,
        weekend_start=weekend_start,
        night_end=night_end,
    )
    probs, feats = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            row_spec(nb), row_spec(nb), row_spec(nb),
            row_spec(nb), row_spec(nb), row_spec(nb),
            row_spec(2), row_spec(2),
            pl.BlockSpec((4, n_feat), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(row_spec(1), row_spec(n_feat)),
        out_shape=(
            jax.ShapeDtypeStruct((bsz, 1), jnp.float32),
            jax.ShapeDtypeStruct((bsz, n_feat), jnp.float32),
        ),
        interpret=interpret,
    )(c_bd, c_cnt, c_amt, t_bd, t_cnt, t_frd, ivec, fvec, pvec)
    return probs[:, 0], feats
