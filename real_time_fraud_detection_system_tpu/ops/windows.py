"""HBM-resident rolling-window state: per-key day-bucket ring buffers.

This op family replaces the reference's *static* feature tables
(``nessie.payment.feature_customer`` / ``feature_terminal``, joined at score
time in ``fraud_detection.py:100-123``) with *online* state that lives in HBM
and is updated by every micro-batch — the windowed aggregates the offline
pipeline computed with pandas rolling windows
(``feature_transformation.ipynb · cells 17,25``).

Layout: for each of ``capacity`` key slots, ``n_buckets`` daily buckets in a
ring (``bucket = day % n_buckets``), each holding (count, amount-sum,
fraud-sum) for one absolute day, stamped with that day. A window query sums
the buckets whose stamp falls inside the window; stale buckets (overwritten
by the ring) simply don't match and contribute zero.

Canonical window semantics (documented deviation from the reference): windows
are **trailing calendar days including the current day** — window w at day d
covers days [d-w+1, d]; with ``delay`` (terminal risk label latency,
``feature_transformation.ipynb · cell 25``) it covers [d-delay-w+1, d-delay].
The reference's pandas ``rolling('Nd')`` is a trailing wall-clock window;
day-granular buckets are the streaming-friendly approximation, and training
uses the SAME kernel via replay, so there is zero train/serve skew.

All updates are O(B) scatters and all queries O(B × max_window) gathers —
fully vectorized, jit/shard_map friendly, no data-dependent shapes.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax.numpy as jnp

from real_time_fraud_detection_system_tpu.ops.numerics import sum_fixed_order
from real_time_fraud_detection_system_tpu.utils.trace import step_scope


class WindowState(NamedTuple):
    """Ring-buffer day aggregates for one key space (pytree of [cap, NB])."""

    bucket_day: jnp.ndarray  # int32 [cap, NB]; -1 = empty
    count: jnp.ndarray  # float32 [cap, NB]
    amount: jnp.ndarray  # float32 [cap, NB] — sum of amounts that day
    fraud: jnp.ndarray  # float32 [cap, NB] — sum of fraud labels that day

    @property
    def capacity(self) -> int:
        return int(self.bucket_day.shape[0])

    @property
    def n_buckets(self) -> int:
        return int(self.bucket_day.shape[1])


def init_window_state(capacity: int, n_buckets: int) -> WindowState:
    return WindowState(
        bucket_day=jnp.full((capacity, n_buckets), -1, dtype=jnp.int32),
        count=jnp.zeros((capacity, n_buckets), dtype=jnp.float32),
        amount=jnp.zeros((capacity, n_buckets), dtype=jnp.float32),
        fraud=jnp.zeros((capacity, n_buckets), dtype=jnp.float32),
    )


def update_windows(
    state: WindowState,
    slot: jnp.ndarray,  # int32 [B] in [0, capacity)
    day: jnp.ndarray,  # int32 [B] absolute day index
    amount: jnp.ndarray,  # float32 [B]
    fraud: jnp.ndarray,  # float32 [B] — 0/1, or 0 when label unknown
    valid: jnp.ndarray,  # bool [B]
    track_amount: bool = True,
    track_fraud: bool = True,
) -> WindowState:
    """Scatter one micro-batch into the ring buffers.

    Semantics: a bucket is (lazily) reset the first time a *newer* day maps
    onto it; rows older than what a bucket currently holds are dropped
    (bounded-lateness policy — the ring holds n_buckets days of history).
    Duplicate (slot, day) rows within the batch accumulate correctly
    (jnp scatter-add applies all duplicates).

    ``track_amount`` / ``track_fraud``: scatters are the hot path's most
    expensive op on TPU (~7 ms per 1M updates, serialized emitter;
    reformulations — segment_sum, sorted/unique hints, one wide scatter —
    all measured equal or worse). A table whose consumer never reads a
    column may skip its scatter: the 15-feature spec reads customer
    (count, amount) and terminal (count, fraud) only, so the engine drops
    one scatter per keyspace (§``features/online._update_state``). A
    skipped column still gets the (cheap, full-table) stale-bucket reset,
    so its buckets never mix days: it simply misses this batch's
    contributions — safe even if a later update re-enables tracking.
    """
    nb = state.n_buckets
    cap = state.capacity
    with step_scope("update"):
        bucket = jnp.remainder(day, nb)
        flat = (slot * nb + bucket).astype(jnp.int32)
        # invalid rows stamp -1 which never wins
        day_in = jnp.where(valid, day, -1).astype(jnp.int32)

        with step_scope("relayout"):
            bd = state.bucket_day.reshape(-1)
            count = state.count.reshape(-1)
            amt = state.amount.reshape(-1)
            frd = state.fraud.reshape(-1)

        # Day stamp each touched bucket with max(existing, incoming).
        with step_scope("stamp"):
            new_bd = bd.at[flat].max(day_in)

        # Buckets whose stamp advanced hold a stale (older) day: reset
        # aggregates.
        with step_scope("reset"):
            advanced = new_bd > bd
            count = jnp.where(advanced, 0.0, count)
            amt = jnp.where(advanced, 0.0, amt)
            frd = jnp.where(advanced, 0.0, frd)

        with step_scope("scatter"):
            # A row contributes only if its day is the bucket's (possibly
            # new) stamp.
            fresh = valid & (day_in == new_bd[flat])
            w = fresh.astype(jnp.float32)
            count = count.at[flat].add(w)
            if track_amount:
                amt = amt.at[flat].add(amount * w)
            if track_fraud:
                frd = frd.at[flat].add(fraud * w)

        with step_scope("relayout"):
            return WindowState(
                bucket_day=new_bd.reshape(cap, nb),
                count=count.reshape(cap, nb),
                amount=amt.reshape(cap, nb),
                fraud=frd.reshape(cap, nb),
            )


def gather_state_rows(
    state: WindowState, slot: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One row-gather per table: (bucket_day, count, amount, fraud)[slot],
    each [B, NB]. The single embedding-style gather the query needs."""
    with step_scope("query"), step_scope("gather"):
        return (
            state.bucket_day[slot],
            state.count[slot],
            state.amount[slot],
            state.fraud[slot],
        )


def query_gathered(
    bucket_day: jnp.ndarray,  # int32 [B, NB]
    count: jnp.ndarray,  # float32 [B, NB]
    amount: jnp.ndarray,  # float32 [B, NB]
    fraud: jnp.ndarray,  # float32 [B, NB]
    day: jnp.ndarray,  # int32 [B]
    windows: Sequence[int],
    delay: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Window sums from pre-gathered state rows — age-mask formulation.

    A bucket holding absolute day s contributes to window w iff its age
    ``a = day - delay - s`` satisfies ``0 <= a < w`` (empty buckets carry
    stamp -1 and only match impossible ages). No per-window modulo gathers:
    one [B, NB] age computation + a [B, NB] @ [NB→NW] masked contraction,
    entirely VPU/MXU-friendly (and the form the Pallas fused kernel uses).
    """
    with step_scope("query"), step_scope("sum"):
        age = day[:, None] - jnp.int32(delay) - bucket_day  # [B, NB]
        live = (bucket_day >= 0) & (age >= 0)
        out_c, out_a, out_f = [], [], []
        for w in windows:
            sel = (live & (age < w)).astype(jnp.float32)
            # counts and fraud labels are integers: exact in any order. The
            # dollar amounts are not, so their order is pinned — the fused
            # kernels (assemble_features) add the same tree.
            out_c.append(jnp.sum(count * sel, axis=1))
            out_a.append(sum_fixed_order(amount * sel, axis=1))
            out_f.append(jnp.sum(fraud * sel, axis=1))
        return (
            jnp.stack(out_c, axis=1),
            jnp.stack(out_a, axis=1),
            jnp.stack(out_f, axis=1),
        )


def query_windows(
    state: WindowState,
    slot: jnp.ndarray,  # int32 [B]
    day: jnp.ndarray,  # int32 [B]
    windows: Sequence[int],
    delay: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Gather per-row window aggregates.

    Returns (counts, amount_sums, fraud_sums), each [B, len(windows)], where
    window w sums days [day-delay-w+1, day-delay]. One row-gather per table
    plus dense age-mask reductions (see :func:`query_gathered`) — TPU-
    friendlier than per-(row, day-offset) flat gathers.
    """
    bd, cnt, amt, frd = gather_state_rows(state, slot)
    return query_gathered(bd, cnt, amt, frd, day, windows, delay)
