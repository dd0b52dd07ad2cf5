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
``[cap, NB]`` goes through :meth:`WindowState.rows` / ``set_rows``
(promotion, the demote payload), :meth:`WindowState.newest` /
``clear_slots`` (the compaction's table-wide part, on the flat bytes) or,
on the host, :meth:`WindowState.tables` (reshard, the checkpoint's
leaves).

Canonical window semantics (documented deviation from the reference): windows
are **trailing calendar days including the current day** — window w at day d
covers days [d-w+1, d]; with ``delay`` (terminal risk label latency,
``feature_transformation.ipynb · cell 25``) it covers [d-delay-w+1, d-delay].
The reference's pandas ``rolling('Nd')`` is a trailing wall-clock window;
day-granular buckets are the streaming-friendly approximation, and training
uses the SAME kernel via replay, so there is zero train/serve skew.

Updates follow the batch: it is merged by bucket first, and the program
reads and writes a column at the batch's buckets only — it makes no pass
over a table; queries are O(B × max_window) gathers — fully vectorized,
jit/shard_map friendly, no data-dependent shapes.

Cost of :func:`update_windows` on a v5e (PERF.md, PR 43; the figures of
the forms are the refused PR 42's builder's, its ledger lines the cells':
65,536 rows, the 2^22-slot customer and the 2^23-slot terminal table, 40
buckets: 24.5 ms a step for both, where combining into the table — stamps
scatter-maxed, a table-wide compare and reset, scatter-adds — was 63.2):

- *The merge, 0.4 ms a table.* One ``lax.sort`` of the bucket indices
  with the lane beside them runs in 0.03 ms; what a sort costs is its
  compile for the chip, which grows with its keys and with what it
  carries (the whole forest step at 65,536 rows: the table-wide form 6.7
  s, one key unstable 7.9, (bucket, lane) as two keys unstable 11.5, one
  key stable 14.2; a carried operand ~6 s each). So nothing is carried —
  the payload is taken by the permutation (:func:`_picker`, 0.15 ms a
  column) — and only the table that keeps a dollar sum sorts by two
  keys. The run totals are two loops of dense steps, 0.04 ms each
  (:func:`_run_totals`); as ``segment_max`` / ``segment_sum`` into
  ``[rows]`` arrays they were 0.57 ms each.
- *The old values, 0.55 ms a column and table* (:func:`_picker`),
  whatever the table's size: 1.7 ms a table.
- *The writes: 2.3 ms into a 168 M-element column, 4.3 into a
  335 M-element one*, three a table — 19.7 of the 24.5 ms. ``set`` on
  indices marked sorted is the chip's sorted scatter, and that streams
  its operand through: 0.32 ms + 12.75 ps an element (8 bytes at ~680
  GB/s), a pass inside the fusion, whatever the number of updates. Its
  other scatter — what an index vector that promises nothing gets, the
  table-wide form's — takes 89.6 ns an update whatever the operand: 5.9
  ms at 65,536 rows, and ``unique_indices`` alone changes nothing. The
  update takes the cheaper of the two from its shapes
  (:func:`_sorted_write_pays`): sorted at 65,536 rows, plain at 16,384
  and below.

What the update and the query cost the host: each is traced once a table
and batch bucket, ten times in ``engine.precompile()``, so their helpers
are written in ``lax`` and share what they can (the update: 72 equations
a trace; on this sandbox's CPU 8 ms of tracing and 22 of lowering where
``jnp`` indexing, ``.at[].set`` and ``fori_loop`` made it 30 + 37, and
the table-wide form was 7 + 12; :meth:`WindowState.rows`: 294 equations
and 53 ms where it was 420 and 110).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from real_time_fraud_detection_system_tpu.ops.numerics import sum_fixed_order
from real_time_fraud_detection_system_tpu.utils.trace import step_scope

COLUMNS = ("bucket_day", "count", "amount", "fraud")
# whole rows of a column's ``[n/128, 128]`` view; single elements of a
# flat column
_ROW_TAKE = lax.GatherDimensionNumbers(
    offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,))
_ELEMENT_WRITE = lax.ScatterDimensionNumbers(
    update_window_dims=(), inserted_window_dims=(0,),
    scatter_dims_to_operand_dims=(0,))


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
        """The four columns as ``[cap, NB]``, for the host (checkpoints,
        the mesh's reshard, tests): free on host arrays (a flat
        slot-major array reshapes to rows without a copy). On the chip
        the view pads 40 lanes to 128 — a pass over the table that
        writes 3.2 × its bytes — so no device program takes it: the
        step never did, and the compaction reads and writes the flat
        columns (:meth:`newest`, :meth:`clear_slots`; PR 54)."""
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
        b = int(slot.shape[0])
        start = slot.astype(jnp.int32) * nb
        row = start // lanes
        lane = start - row * lanes
        # What every column shares is built once, in ``lax``: the step is
        # traced for every batch bucket (``engine.precompile``), and four
        # columns × 15 start lanes of ``jnp`` indexing and ``where`` were
        # 420 equations and four fifths of what a step costs to lower
        # (PERF.md, PR 43). The program is the same.
        n_rows = n // lanes
        takes = []
        for i in range(n_take):
            at = jnp.minimum(row + i, n_rows - 1)
            at = lax.select(lax.lt(at, np.int32(0)),
                            lax.add(at, np.int32(n_rows)), at)
            takes.append(lax.reshape(at, (b, 1)))
        starts_at = {
            k: lax.broadcast_in_dim(lax.eq(lane, np.int32(k)), (b, nb), (0,))
            for k in range(step, lanes, step)}

        def take(col):
            view = lax.reshape(col, (n_rows, lanes))
            win = lax.concatenate([
                lax.gather(view, at, _ROW_TAKE, (1, lanes),
                           mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
                for at in takes], 1)
            out = lax.slice(win, (0, 0), (b, nb))
            for k, here in starts_at.items():
                out = lax.select(here, lax.slice(win, (0, k), (b, k + nb)),
                                 out)
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

    def _slot_groups(self):
        """How the compaction reads a flat column without re-laying it
        out → ``(groups, rows, lanes, slots, seg, first, reach)``.

        A slot's NB entries are NB consecutive elements, and NB is no
        divisor of the 128 lanes, so a slot's lanes differ from row to row
        of the ``[n/128, 128]`` view — but the pattern repeats: lcm(NB,
        128) elements hold whole rows AND whole slots (640 = 5 rows = 16
        slots at NB = 40), and eight such periods are whole (8, 128)
        tiles, so ``[groups, rows, lanes]`` with ``rows`` = 40 is the
        stored bytes viewed in place (compiled for a v5e: a ``bitcast``)
        and a group's ``slots`` = 128 results are whole rows of the
        ``[cap]`` answer. Sizes by arithmetic from the column's length
        and NB, as :meth:`rows` does it: a column whose length is no
        multiple of 128, or that holds no eight periods (toy sizes),
        takes the widest view that divides it.

        The patterns, built once in ``lax`` for every column of a call:
        ``seg`` int32 ``[rows, lanes]`` numbers the slots a row touches
        0, 1, ... along its lanes, ``first`` int32 ``[rows, 1]`` is the
        slot (of the group's) a row starts in, and ``reach`` (static) is
        the most slots one row touches — 4 at NB = 40."""
        nb = self.n_buckets
        n = int(self.bucket_day.shape[0])
        lanes = math.gcd(n, 128)
        group = math.lcm(nb, lanes)
        if n % (8 * group) == 0:
            group *= 8
        rows, slots = group // lanes, group // nb
        at = lax.mul(lax.broadcasted_iota(jnp.int32, (rows, lanes), 0),
                     np.int32(lanes))
        first = lax.div(at, np.int32(nb))
        seg = lax.sub(
            lax.div(lax.add(at, lax.broadcasted_iota(
                jnp.int32, (rows, lanes), 1)), np.int32(nb)), first)
        reach = (lanes - math.gcd(nb, lanes) + nb - 1) // nb + 1
        return (n // group, rows, lanes, slots, seg,
                lax.slice(first, (0, 0), (rows, 1)), reach)

    def newest(self) -> jnp.ndarray:
        """Every slot's newest ``bucket_day``, int32 ``[capacity]`` —
        ``max(tables()[0], axis=1)`` read from the flat column as it is
        stored (:meth:`_slot_groups`): for each of the ``reach`` slots a
        row can touch — a ``lax.while_loop``, one copy of the body in the
        program — a maximum along the lanes under that slot's lane
        pattern (``[groups, rows]`` partials), moved onto its slot's lane
        of the group's result by a select and a maximum over the rows. No
        ``[cap, NB]`` array exists: on the chip that view pads 40 lanes
        to 128, 4.3 GB for the 2^23-slot table's stamps. On a v5e 11.6
        ms for that table where the reduce over the padded view took
        15.4 — lane reduces, not bytes, are what it costs (1.6 ms at
        819 GB/s; PERF.md, PR 54)."""
        groups, rows, lanes, slots, seg, first, reach = self._slot_groups()
        x = lax.reshape(self.bucket_day, (groups, rows, lanes))
        lowest = np.int32(np.iinfo(np.int32).min)
        slot = lax.broadcasted_iota(jnp.int32, (rows, slots), 1)

        def fold(carry):
            k, out = carry
            here = lax.broadcast_in_dim(lax.eq(seg, k), x.shape, (1, 2))
            part = lax.reduce_max(
                lax.select(here, x, lax.full_like(x, lowest)), (2,))
            onto = lax.broadcast_in_dim(lax.eq(lax.add(first, k), slot),
                                        (groups, rows, slots), (1, 2))
            moved = lax.reduce_max(lax.select(
                onto,
                lax.broadcast_in_dim(part, (groups, rows, slots), (0, 1)),
                lax.full((groups, rows, slots), lowest, jnp.int32)), (1,))
            return lax.add(k, np.int32(1)), lax.max(out, moved)

        _, out = lax.while_loop(
            lambda carry: lax.lt(carry[0], np.int32(reach)), fold,
            (np.int32(0), lax.full((groups, slots), lowest, jnp.int32)))
        return lax.reshape(out, (groups * slots,))

    def clear_slots(self, vacated: jnp.ndarray,
                    n_vacated: jnp.ndarray) -> "WindowState":
        """Empty the rows of the slots flagged in ``vacated`` (bool
        ``[capacity]``, ``n_vacated`` int32 [] of them): the compaction
        flags the slots it gives up a packed chunk at a time
        (``ops/keydir.reclaim_entries``) and sweeps once — if there is
        anything to sweep. The sweep is one flat select a column under a
        per-element mask, the slots' flags spread over their lanes by the
        patterns of :meth:`_slot_groups`: a one-hot product on the MXU
        brings each row the flags of the slots it touches, bit k of a
        word for its k-th (powers of two, exact in bfloat16; 16 to a
        word, so the float32 sum is exact too), and an element's flag is
        the bit its ``seg`` names. Nothing is viewed as ``[cap, NB]``
        (on a v5e 19.2 ms for the 2^23-slot table's four columns where
        the padded mask's way took 28.9; PERF.md, PR 54).
        It runs as the one trip of a ``lax.while_loop`` whose carry is
        the four columns, updated in place; ``n_vacated`` = 0, no trip,
        and the columns leave as the buffers they came in as (a
        ``lax.cond`` that yields a column may copy it). For slots of the
        order of the table :meth:`set_rows` is the wrong tool: its
        ``[K, NB]`` element indices alone are 2.7 GB at 2^24 lanes, and
        the chip's compiler refuses the program (18.4 GB of 15.75;
        compiled for a described v5e, PR 32)."""
        groups, rows, lanes, slots, seg, first, reach = self._slot_groups()
        shape = (groups, rows, lanes)
        word = 16
        # which of a row's slots (counted from its first) is the group's j
        nth = lax.sub(lax.broadcasted_iota(jnp.int32, (rows, slots), 1),
                      first)
        fills = (np.int32(-1), np.float32(0), np.float32(0), np.float32(0))

        def bit_of(k):
            """``k`` int32 counted from a word's first bit → (whether it
            is inside the word, ``k`` clamped into it)."""
            inside = lax.bitwise_and(lax.ge(k, np.int32(0)),
                                     lax.lt(k, np.int32(word)))
            return inside, lax.clamp(np.int32(0), k, np.int32(word - 1))

        def sweep(carry):
            _, cols = carry
            flags = lax.convert_element_type(
                lax.reshape(vacated, (groups, slots)), jnp.bfloat16)
            mask = None
            for base in range(0, reach, word):
                inside, k = bit_of(lax.sub(nth, np.int32(base)))
                weights = lax.select(inside,
                                     lax.shift_left(lax.full_like(k, 1), k),
                                     lax.full_like(k, 0))
                words = lax.convert_element_type(lax.dot_general(
                    flags, lax.convert_element_type(weights, jnp.bfloat16),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32), jnp.int32)
                inside, k = bit_of(lax.sub(seg, np.int32(base)))
                hit = lax.bitwise_and(
                    lax.broadcast_in_dim(inside, shape, (1, 2)),
                    lax.ne(lax.bitwise_and(lax.shift_right_logical(
                        lax.broadcast_in_dim(words, shape, (0, 1)),
                        lax.broadcast_in_dim(k, shape, (1, 2))),
                        np.int32(1)), np.int32(0)))
                mask = hit if mask is None else lax.bitwise_or(mask, hit)
            return np.int32(0), tuple(
                lax.reshape(lax.select(mask, lax.full(shape, fill, c.dtype),
                                       lax.reshape(c, shape)), c.shape)
                for c, fill in zip(cols, fills))

        _, cols = lax.while_loop(
            lambda carry: lax.gt(carry[0], np.int32(0)), sweep,
            (lax.convert_element_type(n_vacated, jnp.int32), self.columns()))
        return WindowState(*cols, n_buckets=self.n_buckets)


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


def _sorted_write_pays(rows: int, n: int) -> bool:
    """Whether ``rows`` updates on sorted indices go into a column of
    ``n`` elements faster as the chip's sorted scatter than as its plain
    one — the one decision of :func:`update_windows` that follows sizes,
    taken at trace time from the two shapes it has. On a v5e (PERF.md,
    PR 43, measured by PR 42's builder on ``.set`` into float32 and int32
    columns): a scatter whose indices are marked sorted streams its
    operand through, 0.32 ms + 12.75 ps an operand element whatever the
    number of updates (2.30 ms into 167.8 M elements, 4.28 into 335.5 M);
    one that promises nothing takes 89.6 ns an update whatever the
    operand (5.87 ms at 65,536 rows). Sorted at 65,536 rows for both of
    the benchmark's tables; plain at 16,384 rows and below, where the
    passes would cost a 256-row batch 19.7 ms; plain into a column past
    ~460 M elements at any batch the engine pads to."""
    return 0.32e-3 + 12.75e-12 * n < 89.6e-9 * rows


def _picker(idx: jnp.ndarray, n: int):
    """``pick(col) = col[idx]`` for flat columns of ``n`` elements and
    ``idx`` int32 [B] in ``[0, n]`` (one past the end reads as zero:
    callers drop those lanes): a take of whole rows of the
    ``[n/128, 128]`` view and a select of the lane, the gather the chip's
    compiler emits well (:meth:`WindowState.rows`). On a v5e, 65,536
    elements: 0.55 ms from a 335 M-element column where the element
    gather takes 0.80-0.93, 0.15 from a ``[65,536]`` array where it takes
    0.56 (PERF.md, PR 43). The row numbers and the lane mask are built
    once for every column picked at ``idx``, in ``lax``: the update is
    traced ten times a process (``engine.precompile``), and what it costs
    there is its number of equations."""
    rows = int(idx.shape[0])
    lanes = math.gcd(n, 128)
    row = lax.min(lax.div(idx, np.int32(lanes)), np.int32(n // lanes - 1))
    hit = lax.eq(
        lax.broadcast_in_dim(lax.sub(idx, lax.mul(row, np.int32(lanes))),
                             (rows, lanes), (0,)),
        lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    row = lax.reshape(row, (rows, 1))

    def pick(col):
        taken = lax.gather(lax.reshape(col, (n // lanes, lanes)), row,
                           _ROW_TAKE, (1, lanes),
                           mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        return lax.reduce_sum(
            lax.select(hit, taken, lax.full_like(taken, 0)), (1,))

    return pick


def _run_totals(first: jnp.ndarray, day: jnp.ndarray,
                values: Tuple[jnp.ndarray, ...]):
    """For rows sorted by bucket, ``first`` flagging each run's first
    lane: at every lane of a run the run's newest day, and over its rows
    of that day their number and the sum of each of ``values`` — the
    rows that can count (:func:`update_windows`).

    Two doubling loops of log2(rows) dense steps, written in ``lax``
    (every op keeps the stage's scope, and the loops' bodies are most of
    what tracing the update costs): forward, a lane gathers the lanes
    from its run's first to itself, 1, 2, 4, ... back at a time (a lane
    stops looking back once its window holds the run's first lane);
    backward, every lane takes what its run's last lane gathered. A
    run's additions happen in the order this tree gives them, a function
    of the run's own offsets: the same bits on every run, whatever else
    the batch holds. 0.04 ms a loop at 65,536 rows on a v5e, where one
    ``segment_sum`` into a ``[rows]`` array is 0.57 and
    ``lax.associative_scan`` 0.33 (and four times the loop's compile;
    PERF.md, PR 43). Never differences of a batch-wide prefix sum: dollar
    amounts would cancel."""
    rows = int(day.shape[0])
    steps = (rows - 1).bit_length()
    zero = lax.full((rows,), 0.0, jnp.float32)

    def seen_from(xs, start):
        """Lane ``l`` sees lane ``l + start`` of every x, around the end."""
        return [lax.dynamic_slice(lax.concatenate([x, x], 0), (start,),
                                  (rows,), allow_negative_indices=False)
                for x in xs]

    def gather_back(i, carry):
        whole, newest, *sums = carry
        d = lax.shift_left(np.int32(1), i)
        whole_a, newest_a, *sums_a = seen_from(
            carry, lax.sub(np.int32(rows), d))
        top = lax.max(newest_a, newest)
        keep_a = lax.bitwise_and(lax.eq(newest_a, top),
                                 lax.bitwise_not(whole))
        keep = lax.bitwise_or(lax.eq(newest, top), whole)
        return (lax.bitwise_or(whole, whole_a),
                lax.select(whole, newest, top),
                *(lax.add(lax.select(keep_a, a, zero),
                          lax.select(keep, b, zero))
                  for a, b in zip(sums_a, sums)))

    def take_from_last(i, carry):
        done, *totals = carry
        done_b, *totals_b = seen_from(carry, lax.shift_left(np.int32(1), i))
        return (lax.bitwise_or(done, done_b),
                *(lax.select(done, t, t_b)
                  for t, t_b in zip(totals, totals_b)))

    def loop(body, carry):
        """``fori_loop(0, steps, body, carry)`` as the ``while`` it ends
        as: through ``scan`` the body is traced a second time when the
        program is lowered."""
        def step(at):
            return lax.add(at[0], np.int32(1)), tuple(body(*at))
        return lax.while_loop(lambda at: lax.lt(at[0], np.int32(steps)),
                              step, (np.int32(0), tuple(carry)))[1]

    _, *totals = loop(gather_back, (
        first, day, lax.full((rows,), 1.0, jnp.float32), *values))
    # a run's last lane is the one before a first lane; lane rows - 1
    # sees lane 0, which is one
    (last,) = seen_from((first,), np.int32(1 % rows))
    _, newest, on_it, *sums = loop(take_from_last, (last, *totals))
    return newest, on_it, sums


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
    """Merge one micro-batch into the ring buffers.

    Semantics: a bucket is (lazily) reset the first time a *newer* day maps
    onto it; rows older than what a bucket currently holds are dropped
    (bounded-lateness policy — the ring holds n_buckets days of history).
    Of two days that share a bucket inside one batch only the newer
    counts. Duplicate (slot, day) rows within the batch accumulate. A row
    that is not ``valid`` counts nowhere. Stamps only grow.

    ``track_amount`` / ``track_fraud`` say which aggregate columns the
    table MAINTAINS (day stamps and counts always are). That set is fixed
    for a table's life and decided in one place per key space
    (``features/online.CUSTOMER_COLUMNS`` / ``TERMINAL_COLUMNS``: the
    15-feature spec reads customer (count, amount) and terminal (count,
    fraud) only). An unmaintained column is not touched: no gather, no
    write — it leaves the step as the donated buffer it came in as and
    stays what :func:`init_window_state` made it, so no feature may read
    it, and a table whose flags changed mid-life would mix days in it.

    The batch is merged first and a column is touched at the batch's
    buckets only (module docstring, "Cost"): rows sorted by bucket, a run
    of equal buckets reduced to its newest day ``m``, its rows of that day
    and their sums — a row of an older day than ``m`` can never count, and
    the rows of ``m`` count iff ``m`` is at least the bucket's stamp — so a
    bucket's final value is ``where(advanced, 0, old) + the run's total``
    from one gather of the old value, written once with a ``set``: every
    lane of a run writes the run's one final value, so duplicate indices
    are harmless, and the indices are sorted, which the write says where
    the chip's sorted scatter is the faster one
    (:func:`_sorted_write_pays`).

    The order of a bucket's in-batch additions is a fixed tree over the
    bucket's rows (:func:`_run_totals`) in the order the sort left them.
    Only a float sum cares — counts and 0/1 ``fraud`` labels are small
    integers, exact in any order — so a table that maintains ``amount``
    sorts by (bucket, lane): a bucket's rows stay in batch order, the same
    bits on every run, whatever else the batch holds and however slots
    are numbered (key modes that number slots differently agree to the
    bit) — not the sequential sum in batch order. A table that does not
    sorts by the bucket alone, which compiles faster for the chip (module
    docstring); its columns are the same bits under any permutation of
    the batch's rows only while ``fraud`` holds integers.
    """
    nb = state.n_buckets
    n, rows = int(state.bucket_day.shape[0]), int(slot.shape[0])
    if rows == 0:
        return state
    # the sums the table maintains beside its counts, and what a row adds
    added = {}
    if track_amount:
        added["amount"] = amount
    if track_fraud:
        added["fraud"] = fraud
    maintained = ("count", *added)
    sorted_write = _sorted_write_pays(rows, n)
    with step_scope("update"):
        with step_scope("merge"):
            # A row that is not valid takes the key past the end: it sorts
            # last, into a run whose writes are dropped.
            flat = jnp.where(valid, slot * nb + jnp.remainder(day, nb),
                             n).astype(jnp.int32)
            flat, order = lax.sort(
                (flat, lax.iota(jnp.int32, rows)),
                num_keys=2 if track_amount else 1, is_stable=False)
            first = lax.concatenate(
                [lax.full((1,), True),
                 lax.ne(lax.slice(flat, (1,), (rows,)),
                        lax.slice(flat, (0,), (rows - 1,)))], 0)
            # the payload is taken by the permutation, not carried
            # through the sort: a carried operand is ~6 s of the chip's
            # compile and nothing at run time
            from_batch = _picker(order, rows)
            newest, on_it, sums = _run_totals(
                first, from_batch(day.astype(jnp.int32)),
                tuple(from_batch(v) for v in added.values()))
            totals = {"count": on_it, **dict(zip(added, sums))}
            at = lax.reshape(flat, (rows, 1))

        def write(col, value):
            return lax.scatter(col, at, value, _ELEMENT_WRITE,
                               indices_are_sorted=sorted_write, mode="drop")

        # Day stamp each touched bucket with max(existing, incoming), in
        # the donated buffer.
        with step_scope("stamp"):
            from_table = _picker(flat, n)
            old_bd = from_table(state.bucket_day)
            new_bd = lax.max(old_bd, newest)
            bd = write(state.bucket_day, new_bd)

        # A bucket whose stamp advanced holds a stale (older) day: the
        # aggregates the table maintains start again from zero.
        with step_scope("reset"):
            kept = lax.le(new_bd, old_bd)
            zero = lax.full((rows,), 0.0, jnp.float32)
            base = {name: lax.select(kept, from_table(getattr(state, name)),
                                     zero)
                    for name in maintained}

        with step_scope("scatter"):
            # The rows of the run's newest day count iff that day is the
            # bucket's (possibly new) stamp.
            counts = lax.eq(newest, new_bd)
            written = {
                name: write(getattr(state, name),
                            lax.add(base[name],
                                    lax.select(counts, totals[name], zero)))
                for name in maintained}

        return dataclasses.replace(state, bucket_day=bd, **written)


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
