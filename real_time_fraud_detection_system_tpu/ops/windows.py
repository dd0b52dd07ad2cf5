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

Stored form: each of the four columns is ONE flat ``[capacity · n_buckets]``
array, slot-major (``flat = slot · n_buckets + day % n_buckets``) — stored,
carried through the step, donated and restored from a checkpoint that way.
That is the layout the update works in: the TPU compiler's scatter wants a
flat operand whatever the program writes, and it keeps a ``[cap, 40]``
array with ``cap`` on the lanes (40 is no multiple of 128), so a column
stored as a table was copied and reshaped to flat and back in every step —
at 2^22 + 2^23 slots 206.7 of a 305.4 ms step moved tables between two
layouts and computed nothing (PERF.md, PR 24 / PR 25). A flat column is
also, byte for byte, its ``[n/128, 128]`` view, which is what the row
gather of the query reads (:meth:`WindowState.rows`). Code that thinks in
``[cap, NB]`` (compaction, promotion, reshard, the checkpoint's leaves)
goes through :meth:`WindowState.tables` / ``rows`` / ``set_rows``.

Canonical window semantics (documented deviation from the reference): windows
are **trailing calendar days including the current day** — window w at day d
covers days [d-w+1, d]; with ``delay`` (terminal risk label latency,
``feature_transformation.ipynb · cell 25``) it covers [d-delay-w+1, d-delay].
The reference's pandas ``rolling('Nd')`` is a trailing wall-clock window;
day-granular buckets are the streaming-friendly approximation, and training
uses the SAME kernel via replay, so there is zero train/serve skew.

Updates are O(B) scatters plus a lazy reset that is a pass over the table;
queries are O(B × max_window) gathers — fully vectorized, jit/shard_map
friendly, no data-dependent shapes.

Cost of :func:`update_windows` on a v5e (PERF.md, PR 29). What follows the
batch: ~6 ms a scatter of 65,536 rows, whatever the table's size (one for
the stamps, one a maintained column). What follows the table: the reset,
which is written table-wide — set the old stamps aside (4 bytes a bucket
read + 4 written), reset the first maintained column where the stamp
advanced (old stamps, new stamps and the column read, the column and a
1-byte mask written: 12 + 5), reset the second from the mask (5 + 4):
**34 bytes a bucket** for a table that maintains two aggregate columns,
counted from the operand and result shapes of the step compiled for the
chip (``tests/test_tpu_compile.py`` holds it there). The passes run at
~675 of the chip's 819 GB/s (25.4 ms a step for 2^22 + 2^23 slots × 40
buckets), so their time is their bytes: an unmaintained column is not
touched at all, and the stamps make one round trip beside the reset, not
two (until PR 29: 50 bytes, 37.3 ms). The chip's compiler keeps the two
resets apart, with the mask between them, however the selects are
written; one fused pass would be 32. A reset that follows the batch
instead — gather the old stamps at the batch's buckets, scatter zeros
into those that advance, a scatter a maintained column — costs ~12.5 ms
a table at 65,536 rows whatever its size, against 34 bytes × buckets ÷
675 GB/s table-wide: 8.5 ms at 2^22 slots, 16.9 ms at 2^23. By that
arithmetic the two meet near 6 M slots × 40 buckets; the batch-following
form has not been measured (ROADMAP A1).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from real_time_fraud_detection_system_tpu.ops.numerics import sum_fixed_order
from real_time_fraud_detection_system_tpu.utils.trace import step_scope

COLUMNS = ("bucket_day", "count", "amount", "fraud")


@partial(jax.tree_util.register_dataclass, data_fields=list(COLUMNS),
         meta_fields=["n_buckets"])
@dataclasses.dataclass(frozen=True)
class WindowState:
    """Ring-buffer day aggregates for one key space: a pytree of four flat
    ``[capacity · n_buckets]`` columns (see the module docstring), with
    ``n_buckets`` as static metadata — it cannot be read from a flat
    shape. Everything that wants ``[cap, NB]`` goes through
    :meth:`tables` / :meth:`rows` / :meth:`set_rows`."""

    bucket_day: jnp.ndarray  # int32 [cap · NB]; -1 = empty
    count: jnp.ndarray  # float32 [cap · NB]
    amount: jnp.ndarray  # float32 [cap · NB] — sum of amounts that day
    fraud: jnp.ndarray  # float32 [cap · NB] — sum of fraud labels that day
    n_buckets: int

    @property
    def capacity(self) -> int:
        return int(self.bucket_day.shape[0]) // self.n_buckets

    def columns(self) -> Tuple[jnp.ndarray, ...]:
        return tuple(getattr(self, c) for c in COLUMNS)

    def tables(self) -> Tuple[jnp.ndarray, ...]:
        """The four columns as ``[cap, NB]``. Free on host arrays (a flat
        slot-major array reshapes to rows without a copy); on the chip it
        is a pass over the table, so nothing on the per-batch path takes
        it."""
        return tuple(c.reshape(self.capacity, self.n_buckets)
                     for c in self.columns())

    @classmethod
    def from_tables(cls, bucket_day, count, amount, fraud) -> "WindowState":
        """The inverse of :meth:`tables`."""
        return cls(bucket_day.reshape(-1), count.reshape(-1),
                   amount.reshape(-1), fraud.reshape(-1),
                   n_buckets=int(np.shape(bucket_day)[1]))

    def rows(self, slot: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
        """``(bucket_day, count, amount, fraud)`` of ``slot`` [B] in
        ``[0, capacity)``, each ``[B, NB]``.

        A flat column is read through its ``[n/128, 128]`` view — on the
        chip the same bytes as the flat array, so the view is free — with
        plain row takes, the one gather the chip's compiler emits well:
        a slot's NB entries start at lane ``slot·NB mod 128``, a multiple
        of ``gcd(NB, 128)``, inside the row that holds the start plus the
        next one(s); the rows are taken whole and the NB lanes picked by
        one select per possible start lane. On a v5e, 65,536 rows of a
        2^23-slot column: 2.9 ms, against 3.6 for ``table[slot]`` on a
        stored ``[cap, 40]`` table, 47 for an element gather at
        ``slot·NB + arange(NB)`` and 76 for a ``lax.gather`` with
        ``slice_sizes=(NB,)``, which the compiler turns into a loop over
        the batch (my chip run, PR 25). A column whose length is no
        multiple of 128 (toy sizes) takes the widest view that divides
        it; the arithmetic is the same."""
        nb = self.n_buckets
        n = int(self.bucket_day.shape[0])
        lanes = math.gcd(n, 128)
        step = math.gcd(nb, lanes)  # a slot starts at a multiple of this
        n_take = -(-(lanes - step + nb) // lanes)
        start = slot.astype(jnp.int32) * nb
        row = start // lanes
        lane = start - row * lanes
        last = n // lanes - 1

        def take(col):
            view = col.reshape(-1, lanes)
            win = jnp.concatenate(
                [view[jnp.minimum(row + i, last)] for i in range(n_take)],
                axis=1)
            out = win[:, :nb]
            for k in range(step, lanes, step):
                out = jnp.where((lane == k)[:, None], win[:, k:k + nb], out)
            return out

        return tuple(take(c) for c in self.columns())

    def set_rows(self, slot: jnp.ndarray, bucket_day, count, amount,
                 fraud) -> "WindowState":
        """Overwrite whole rows: ``slot`` [K], a value ``[K, NB]`` (or a
        scalar) per column. A slot of ``capacity`` or more is dropped, which
        is how callers mask lanes out."""
        nb = self.n_buckets
        idx = (slot.astype(jnp.int32) * nb)[:, None] + jnp.arange(
            nb, dtype=jnp.int32)
        return WindowState(
            *(c.at[idx].set(v, mode="drop")
              for c, v in zip(self.columns(),
                              (bucket_day, count, amount, fraud))),
            n_buckets=nb)


    def clear_slots(self, vacated: jnp.ndarray) -> "WindowState":
        """Empty the rows of the slots flagged in ``vacated`` (bool
        ``[capacity]``): one dense select a column, no indexed work — the
        compaction flags the slots it gives up a packed chunk at a time
        (``ops/keydir.reclaim_entries``) and sweeps once. For slots of
        the order of the table :meth:`set_rows` is the wrong tool: its
        ``[K, NB]`` element indices alone are 2.7 GB at 2^24 lanes, and
        the chip's compiler refuses the program (18.4 GB of 15.75;
        compiled for a described v5e, PR 32)."""
        cap, nb = self.capacity, self.n_buckets

        def clear(col, fill):
            return jnp.where(vacated[:, None], fill,
                             col.reshape(cap, nb)).reshape(-1)

        return WindowState(
            *(clear(c, f) for c, f in zip(
                self.columns(), (jnp.int32(-1), 0.0, 0.0, 0.0))),
            n_buckets=nb)


def init_window_state(capacity: int, n_buckets: int,
                      sharding=None) -> WindowState:
    """An empty table. With ``sharding`` (the mesh's slot-axis
    ``NamedSharding``) every device allocates only its own
    ``capacity / n_dev · n_buckets`` entries of each column: the fill is
    compiled with that output sharding, so no device ever holds a whole
    column (a four-chip table may be larger than one chip)."""
    n = capacity * n_buckets

    def empty() -> WindowState:
        return WindowState(
            bucket_day=jnp.full((n,), -1, dtype=jnp.int32),
            count=jnp.zeros((n,), dtype=jnp.float32),
            amount=jnp.zeros((n,), dtype=jnp.float32),
            fraud=jnp.zeros((n,), dtype=jnp.float32),
            n_buckets=n_buckets,
        )

    if sharding is None:
        return empty()
    return jax.jit(empty, out_shardings=sharding)()


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

    ``track_amount`` / ``track_fraud`` say which aggregate columns the
    table MAINTAINS (day stamps and counts always are). That set is fixed
    for a table's life and decided in one place per key space
    (``features/online.CUSTOMER_COLUMNS`` / ``TERMINAL_COLUMNS``: the
    15-feature spec reads customer (count, amount) and terminal (count,
    fraud) only). An unmaintained column is not touched: no scatter, no
    reset — it leaves the step as the donated buffer it came in as and
    stays what :func:`init_window_state` made it, so no feature may read
    it, and a table whose flags changed mid-life would mix days in it.

    What the update costs follows two things (module docstring, "Cost").
    The batch: a scatter of 65,536 rows is ~6.2 ms a column on a v5e
    whatever the table's size (ledger, PR 28: ``step_scatter_ms`` 25.5
    for four, ``step_stamp_ms`` 12.0 for two). The table: the reset is a
    pass over every bucket, 34 bytes a bucket with two maintained
    columns.
    """
    nb = state.n_buckets
    with step_scope("update"):
        bucket = jnp.remainder(day, nb)
        flat = (slot * nb + bucket).astype(jnp.int32)
        # invalid rows stamp -1 which never wins
        day_in = jnp.where(valid, day, -1).astype(jnp.int32)
        bd, count, amt, frd = state.columns()

        with step_scope("reset"):
            # The stamps as they were, set aside in ONE pass before the
            # scatter-max overwrites them: the reset compares old with
            # new. Written as a clamp at the empty stamp (a no-op: stamps
            # are >= -1) behind a barrier so that it is a pass of its
            # own, ahead of the scatter. Left to itself the compiler
            # reads the old stamps from the donated buffer, scatters in a
            # copy of it and copies the result back: two table passes.
            old_bd = jax.lax.optimization_barrier(jnp.maximum(bd, -1))

        # Day stamp each touched bucket with max(existing, incoming), in
        # the donated buffer.
        with step_scope("stamp"):
            new_bd = bd.at[flat].max(day_in)

        # Buckets whose stamp advanced hold a stale (older) day: reset
        # the aggregates the table maintains.
        with step_scope("reset"):
            advanced = new_bd > old_bd
            count = jnp.where(advanced, 0.0, count)
            if track_amount:
                amt = jnp.where(advanced, 0.0, amt)
            if track_fraud:
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

        return WindowState(new_bd, count, amt, frd, n_buckets=nb)


def gather_state_rows(
    state: WindowState, slot: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One row-gather per table: (bucket_day, count, amount, fraud)[slot],
    each [B, NB]. The single embedding-style gather the query needs."""
    with step_scope("query"), step_scope("gather"):
        return state.rows(slot)


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
