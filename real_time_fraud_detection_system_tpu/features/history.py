"""HBM-resident per-customer event histories — long-context serving state.

The sequence family (``models/sequence.py``, the live successor of the
reference's dormant seq2seq fraud model, ``shared_functions.py:
1312-1707``) scores a transaction from its card's event history. Offline
that history comes from ``build_sequences`` over a full table; ONLINE it
must live on-device and update per micro-batch, exactly like the window
state. (The tiered ``key_mode="exact"`` store applies to the WINDOWS
plane only — histories keep their direct/hash slotting, and the engine
refuses the combination rather than serve a half-tiered state; growing
this ring a directory + sketch-summary tier is the natural follow-up
once the windows-plane tiering is sharded.) This module is that state:

- a ring buffer of the last K event-feature vectors per customer slot
  (``events [C+1, K, 8]``), with each cell's absolute event index
  (``pos``) so partially-overwritten histories are detected, not
  silently mixed;
- one fused, fully-vectorized ``update_and_score``: sort the batch into
  per-customer time order, scatter the new events, gather every row's
  own causal history (events strictly up to and including itself — later
  same-batch events are excluded by position), and score the row at its
  own sequence position with the causal transformer.

Event features mirror :func:`..models.sequence.event_features` channel
for channel (amount, Δt, time-of-day/weekday phases, presence), so a
transformer trained offline on ``build_sequences`` serves unchanged.

Row ``C`` of every array is a write sink: padding rows route their
scatters there, keeping scatter indices unique without host-side
filtering.

Key→slot is the window state's rule (``ops/hashing.key_slot``):
``direct`` mode is collision-free while ids < capacity; past capacity
(or in ``hash`` mode) colliding customers MERGE into one interleaved
history — same degradation mode as the window tables, size capacity
accordingly. Exactly-once across restarts also mirrors the window
state: the ring buffers live in the checkpointed engine state, so a
crash replay restores the snapshot and re-applies rows once.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from real_time_fraud_detection_system_tpu.config import FeatureConfig
from real_time_fraud_detection_system_tpu.core.batch import TxBatch
from real_time_fraud_detection_system_tpu.ops.hashing import key_slot
from real_time_fraud_detection_system_tpu.models.sequence import (
    N_EVENT_FEATURES,
    transformer_last_logit,
)


def _attn_fn_for(cfg: FeatureConfig, k: int):
    """Serving attention policy (see FeatureConfig.seq_attn).

    None → transformer_logits' naive causal attention ([B, H, K, K]
    scores — fine for short rings, 137 GB at K=512/B=64k); blockwise →
    the flash recurrence from parallel/ring_attention.py, whose score
    memory is [B, H, K, block] (linear in K at fixed block), exact same
    math (online softmax), so long histories serve on one chip."""
    mode = cfg.seq_attn
    if mode == "naive" or (mode == "auto" and k <= cfg.seq_attn_block):
        return None
    from real_time_fraud_detection_system_tpu.parallel.ring_attention import (
        blockwise_attention,
    )

    block = max(16, min(cfg.seq_attn_block, k))
    return lambda q, kk, v: blockwise_attention(
        q, kk, v, block_size=block, causal=True)


class HistoryState(NamedTuple):
    """Per-customer event ring buffers (+1 sink row for padded writes)."""

    events: jnp.ndarray  # f32 [C+1, K, N_EVENT_FEATURES]
    pos: jnp.ndarray  # int32 [C+1, K] — absolute event index in cell, -1 empty
    count: jnp.ndarray  # int32 [C+1] — events written per slot
    last_t: jnp.ndarray  # int32 [C+1] — epoch-seconds of newest event

    @property
    def capacity(self) -> int:
        return int(self.events.shape[0]) - 1

    @property
    def history_len(self) -> int:
        return int(self.events.shape[1])


def init_history_state(cfg: FeatureConfig) -> HistoryState:
    c, k = cfg.customer_capacity, cfg.history_len
    return HistoryState(
        events=jnp.zeros((c + 1, k, N_EVENT_FEATURES), jnp.float32),
        pos=jnp.full((c + 1, k), -1, jnp.int32),
        count=jnp.zeros(c + 1, jnp.int32),
        last_t=jnp.zeros(c + 1, jnp.int32),
    )


def _event_features_dev(
    amount: jnp.ndarray,  # f32 [B] dollars
    day: jnp.ndarray,  # int32 [B]
    tod_s: jnp.ndarray,  # int32 [B]
    dt_s: jnp.ndarray,  # f32 [B] seconds since the previous event (0 first)
) -> jnp.ndarray:
    """[B, 8] — must match models.sequence.event_features bit-for-bit in
    semantics (that fn computes dt via diff with first=0; here dt is
    supplied because the previous event may live in state)."""
    tod = tod_s.astype(jnp.float32) / 86400.0
    weekday = ((day + 3) % 7).astype(jnp.float32) / 7.0
    two_pi = 2.0 * np.pi
    return jnp.stack(
        [
            jnp.log1p(jnp.maximum(amount, 0.0)),
            amount / 100.0,
            jnp.log1p(jnp.maximum(dt_s, 0.0)) / 10.0,
            jnp.sin(two_pi * tod),
            jnp.cos(two_pi * tod),
            jnp.sin(two_pi * weekday),
            jnp.cos(two_pi * weekday),
            jnp.ones_like(tod),
        ],
        axis=1,
    )


def update_and_score(
    state: HistoryState,
    params,
    batch: TxBatch,
    cfg: FeatureConfig,
    slot_fn=None,
    order_key: "jnp.ndarray | None" = None,
) -> Tuple[HistoryState, jnp.ndarray]:
    """One fused history-update + causal-score step (jit-safe).

    Returns ``(new_state, probs [B])`` in the BATCH's row order, with
    padded rows scored 0. Each row is scored from events strictly before
    it plus itself — same-batch later events never leak in (their
    absolute positions exceed the row's own).

    ``slot_fn(customer_key) -> slot`` overrides the key→slot mapping
    (the sharded layout addresses a device-local block: owner shard
    already selected, ``key_slot`` at the mesh's width).

    ``order_key`` [B] int32 breaks same-second timestamp ties (default:
    the row index). The routed sharded path passes each row's ORIGINAL
    chunk position, because the all_to_all regroups rows source-device-
    major — without it, same-second events of one customer could land in
    the ring in a different order than the single-chip engine's.
    """
    c, k = state.capacity, state.history_len
    b = batch.size
    valid = batch.valid
    if slot_fn is None:
        slot = key_slot(batch.customer_key, c, cfg.key_mode)
    else:
        slot = slot_fn(batch.customer_key).astype(jnp.int32)
    slot = jnp.where(valid, slot, c)  # padding → sink row
    t_s = batch.day * 86400 + batch.tod_s  # int32, ok until 2038

    # --- sort into (slot, time, tie) order so same-customer rows form
    # contiguous time-ordered groups
    idx = jnp.arange(b, dtype=jnp.int32)
    tie = idx if order_key is None else order_key.astype(jnp.int32)
    order = jnp.lexsort((tie, t_s, slot))
    s_slot = slot[order]
    s_t = t_s[order]
    s_valid = valid[order]

    first = jnp.concatenate(
        [jnp.ones(1, bool), s_slot[1:] != s_slot[:-1]])
    last = jnp.concatenate([s_slot[1:] != s_slot[:-1], jnp.ones(1, bool)])
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(first, idx, 0))
    seg_end = jax.lax.associative_scan(
        jnp.minimum, jnp.where(last, idx, b - 1), reverse=True)
    rank = idx - seg_start
    gsize = seg_end - seg_start + 1

    # --- Δt: rank 0 reaches back into state (0 for a brand-new customer)
    prev_in_batch = jnp.concatenate([s_t[:1], s_t[:-1]])
    has_state = state.count[s_slot] > 0
    dt_state = jnp.where(has_state, s_t - state.last_t[s_slot], 0)
    dt = jnp.where(rank == 0, dt_state, s_t - prev_in_batch)
    f = _event_features_dev(
        batch.amount[order],
        batch.day[order],
        batch.tod_s[order],
        dt.astype(jnp.float32),
    )

    # --- scatter the new events at their absolute positions
    p = state.count[s_slot] + rank  # absolute event index [B]
    cell = p % k
    # only the last K of an oversized group materialize (earlier ones
    # would be overwritten anyway); keeps (slot, cell) pairs unique
    write = s_valid & (rank >= gsize - k)
    w_slot = jnp.where(write, s_slot, c)
    events = state.events.at[w_slot, cell].set(f)
    pos = state.pos.at[w_slot, cell].set(p)
    count = state.count.at[w_slot].add(
        jnp.where(s_valid & last, gsize, 0))
    last_t = state.last_t.at[
        jnp.where(s_valid & last, s_slot, c)].set(s_t)
    new_state = HistoryState(
        events=events, pos=pos, count=count, last_t=last_t)

    # --- gather each row's causal history, left-aligned, own event last.
    # Two sources: positions q >= count_old come from THIS batch's
    # feature rows (only the newest K were scattered, and later same-
    # batch events may already occupy ring cells); positions q <
    # count_old come from the PRE-scatter buffer, where every position
    # in (p - K, count_old) is guaranteed still present.
    count_old = state.count[s_slot]  # [B] (pre-update)
    length = jnp.minimum(p + 1, k)  # [B]
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    q = p[:, None] - (length[:, None] - 1) + j  # [B, K] absolute positions
    in_batch = q >= count_old[:, None]
    bidx = jnp.clip(seg_start[:, None] + (q - count_old[:, None]), 0, b - 1)
    ev_batch = f[bidx]  # [B, K, F]
    cellq = q % k
    ev_old = state.events[s_slot[:, None], cellq]
    pos_old = state.pos[s_slot[:, None], cellq]
    ev = jnp.where(in_batch[..., None], ev_batch, ev_old)
    ok = (q >= 0) & (q <= p[:, None]) & (in_batch | (pos_old == q))
    hist = jnp.where(ok[..., None], ev, 0.0)
    # Training semantics (build_sequences → event_features on the
    # truncated window): the FIRST event of a window always has Δt = 0 —
    # its true predecessor fell outside the window. Stored features keep
    # the true Δt (correct for every other window position); patch the
    # Δt channel of position 0 at gather time.
    hist = hist.at[:, 0, 2].set(0.0)

    # Serving consumes only each row's own-event logit, so the last
    # transformer block + head run single-query (models/sequence.py::
    # transformer_last_logit) — exact vs the full [B, K] form, with the
    # last block's score tensor [B, H, K] instead of [B, H, K, K]
    # (measured ~time-neutral on v5e; the win is serving memory at long K).
    own = transformer_last_logit(
        params, hist, length - 1, attn_fn=_attn_fn_for(cfg, k))
    probs = jnp.where(s_valid, jax.nn.sigmoid(own), 0.0)

    # --- back to the batch's original row order
    return new_state, jnp.zeros(b, jnp.float32).at[order].set(probs)
