"""Day-ringed count-min sketch — velocity features for unbounded keys.

The dense ``WindowState`` table is exact-per-slot but hashes keys modulo a
fixed capacity; when the key universe outgrows it (billions of cards), the
count-min sketch bounds memory with a provable overestimate-only error:
est ≥ true, P[est > true + εN] ≤ δ with width=⌈e/ε⌉, depth=⌈ln 1/δ⌉.

To support *windowed* velocity (count / amount over trailing days) each day
gets its own sketch slice in a ring of ``n_days`` slices; a slice is lazily
reset when its ring position is claimed by a newer day. Query = per-day
min-over-depth estimate, summed over the window — matching the window
semantics of :mod:`.windows` (trailing calendar days, inclusive).

A key is ``uint32 [B]``, or ``[2, B]`` at ``key_bits=64``: the column
hashes (``ops/hashing.multi_hash``) then mix both words, so two ids that
fold alike share a cell only as often as any two keys do.

This is BASELINE.json config 3 ("HBM-resident count-min sketch per-card /
per-merchant velocity features"); the reference has no equivalent (its
features are precomputed static joins, ``fraud_detection.py:100-123``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from real_time_fraud_detection_system_tpu.ops.hashing import multi_hash
from real_time_fraud_detection_system_tpu.utils.trace import step_scope


class CountMinSketch(NamedTuple):
    """Pytree: ring of daily CMS slices.

    ``fraud`` is an OPTIONAL third column (fraud-label sums) used by the
    tiered feature store's sketch tier so terminal *risk* degrades
    gracefully when a key misses hot-tier admission. ``None`` (the
    default, and every pre-tiering config) keeps the pytree leaf
    structure — and therefore checkpoints — identical to the historical
    2-column sketch."""

    slice_day: jnp.ndarray  # int32 [ND] — absolute day held by each slice
    count: jnp.ndarray  # float32 [ND, depth, width]
    amount: jnp.ndarray  # float32 [ND, depth, width]
    fraud: Optional[jnp.ndarray] = None  # float32 [ND, depth, width] | None

    @property
    def n_days(self) -> int:
        return int(self.slice_day.shape[0])

    @property
    def depth(self) -> int:
        return int(self.count.shape[1])

    @property
    def width(self) -> int:
        return int(self.count.shape[2])


def cms_init(depth: int, width: int, n_days: int = 40,
             track_fraud: bool = False) -> CountMinSketch:
    return CountMinSketch(
        slice_day=jnp.full((n_days,), -1, dtype=jnp.int32),
        count=jnp.zeros((n_days, depth, width), dtype=jnp.float32),
        amount=jnp.zeros((n_days, depth, width), dtype=jnp.float32),
        fraud=jnp.zeros((n_days, depth, width), dtype=jnp.float32)
        if track_fraud else None,
    )


def cms_update(
    sk: CountMinSketch,
    key: jnp.ndarray,  # uint32 [B]
    amount: jnp.ndarray,  # float32 [B]
    day: jnp.ndarray,  # int32 [B]
    valid: jnp.ndarray,  # bool [B]
    fraud: Optional[jnp.ndarray] = None,  # float32 [B] 0/1 (labeled rows)
) -> CountMinSketch:
    with step_scope("cms"):
        nd, depth, width = sk.count.shape
        sl = jnp.remainder(day, nd)  # [B]
        day_in = jnp.where(valid, day, -1).astype(jnp.int32)
        new_slice_day = sk.slice_day.at[sl].max(day_in)

        # Reset slices that advanced to a newer day.
        advanced = (new_slice_day > sk.slice_day)[:, None, None]
        count = jnp.where(advanced, 0.0, sk.count)
        amt = jnp.where(advanced, 0.0, sk.amount)

        fresh = valid & (day_in == new_slice_day[sl])
        w = fresh.astype(jnp.float32)  # [B]
        cols = multi_hash(key, depth, width)  # [depth, B]
        # The cell of (slice, depth row, column) in the table laid flat,
        # and the scatter-add written on that flat view: given the three
        # indices the chip's compiler flattens them itself and the fusion
        # it makes carries no op_name, so 11.5 ms a step of sketch updates
        # read as unscoped (PERF.md, PR 32). Same cells, same order.
        flat = ((sl[None, :] * depth
                 + jnp.arange(depth, dtype=jnp.int32)[:, None]) * width
                + cols).reshape(-1)
        wb = jnp.broadcast_to(w[None, :], cols.shape)

        def add(table, values):  # values [depth, B]
            return table.reshape(-1).at[flat].add(
                values.reshape(-1)).reshape(table.shape)

        count = add(count, wb)
        amt = add(amt, wb * amount[None, :])
        frd = sk.fraud
        if frd is not None:
            # Same slice-reset + fresh-mask discipline as count/amount; a
            # sketch without the column (every pre-tiering config) takes a
            # bit-identical count/amount path through this function.
            frd = jnp.where(advanced, 0.0, frd)
            f_in = (jnp.zeros_like(w) if fraud is None
                    else fraud.astype(jnp.float32))
            frd = add(frd, wb * f_in[None, :])
        return CountMinSketch(slice_day=new_slice_day, count=count,
                              amount=amt, fraud=frd)


def cms_add_fraud(
    sk: CountMinSketch,
    key: jnp.ndarray,  # uint32 [B]
    day: jnp.ndarray,  # int32 [B] — the ORIGINAL transaction's day
    label: jnp.ndarray,  # int32/float32 [B] 0/1
    valid: jnp.ndarray,  # bool [B]
    owner: Optional[jnp.ndarray] = None,  # int32 [B] — shard per row
) -> CountMinSketch:
    """Late fraud-label feedback into the sketch tier: add fraud sums to
    the slice still holding ``day`` (counts unchanged — the row was
    already counted when it streamed through). Labels for days the ring
    has wrapped past are dropped, mirroring the dense tier's
    bounded-lateness policy.

    ``owner`` selects the sharded form: ``sk`` then carries STACKED
    per-shard tables (``[n_shards, ND, depth, width]``) and row i lands
    in shard ``owner[i]``'s replica — ONE bounded-lateness policy for
    the single-chip and sharded feedback paths."""
    if sk.fraud is None:
        return sk
    nd, depth, width = sk.count.shape[-3:]
    sl = jnp.remainder(day, nd)
    live_day = (sk.slice_day[sl] if owner is None
                else sk.slice_day[owner, sl])
    live = valid & (live_day == day)
    w = live.astype(jnp.float32) * label.astype(jnp.float32)
    cols = multi_hash(key, depth, width)  # [depth, B]
    rows = jnp.broadcast_to(
        jnp.arange(depth, dtype=jnp.int32)[:, None], cols.shape)
    slc = jnp.broadcast_to(sl[None, :], cols.shape)
    wb = jnp.broadcast_to(w[None, :], cols.shape)
    if owner is None:
        return sk._replace(fraud=sk.fraud.at[slc, rows, cols].add(wb))
    ob = jnp.broadcast_to(owner[None, :], cols.shape)
    return sk._replace(fraud=sk.fraud.at[ob, slc, rows, cols].add(wb))


def _cms_query_tables(
    sk: CountMinSketch,
    tables: Sequence[jnp.ndarray],  # each [ND, depth, width]
    key: jnp.ndarray,  # uint32 [B]
    day: jnp.ndarray,  # int32 [B]
    windows: Sequence[int],
    delay: int = 0,
) -> Tuple[jnp.ndarray, ...]:
    """Shared windowed min-over-depth estimator over N parallel tables.

    Window w sums the per-day estimates for days
    [day-delay-w+1, day-delay] — the same delay-shift semantics as
    :func:`..windows.query_windows` (``delay=0`` is the historical
    count/amount path, bit-identical arithmetic)."""
    with step_scope("cms"):
        nd, depth, width = sk.count.shape
        max_w = max(windows)
        offsets = jnp.arange(max_w, dtype=jnp.int32)  # [W]
        # [B, W]
        wanted = day[:, None] - jnp.int32(delay) - offsets[None, :]
        sl = jnp.remainder(wanted, nd)  # [B, W]
        live = (sk.slice_day[sl] == wanted) & (wanted >= 0)  # [B, W]

        cols = multi_hash(key, depth, width)  # [depth, B]
        sel = jnp.stack(
            [(offsets < w).astype(jnp.float32) for w in windows], axis=0
        )  # [NW, W]
        out = []
        for t in tables:
            # Gather [depth, B, W] then min over depth.
            g = t[sl[None, :, :], jnp.arange(depth)[:, None, None],
                  cols[:, :, None]]
            out.append((jnp.min(g, axis=0) * live) @ sel.T)
        return tuple(out)


def chunk_rows(n_rows: int) -> int:
    """K: the rows one trip of :func:`cms_query_where` reads — 256, or
    half the batch bucket where that is smaller. Chosen on a v5e at the
    65,536 bucket from 256 / 512 / 1,024 / 2,048 / 4,096 / 8,192
    (PERF.md, PR 33): a trip's two table-gathers cost 0.74 / 1.65 / 3.8 /
    7.3 / 14.5 / 28.7 ms, and a batch in which every row missed 214 /
    215 / 244 / 235 / 233 / 230 ms where the whole-batch read costs 200:
    the smallest chunk is the cheapest a row at both ends, and a trip's
    fixed cost (~0.1 ms) forbids going much lower (128: 0.43 and 222).
    Inside the whole step, every row missed, 256 read 435 ms against
    4,096's 422 and the whole-batch read's 412: the price of a point no
    deployment should stand at, paid for a miss that costs a twentieth
    where deployments do. Never the whole of a
    bucket, half of a small one: a loop whose one chunk is the batch
    reads nothing that depends on the trip, the compiler hoists the read
    out of it, and every batch pays the read again."""
    return min(256, -(-n_rows // 2))


def cms_query_where(
    sk: CountMinSketch,
    columns: Sequence[str],  # of "count" | "amount" | "fraud"
    key: jnp.ndarray,  # uint32 [B]
    day: jnp.ndarray,  # int32 [B]
    rows: jnp.ndarray,  # bool [B]: the rows the sketch serves
    windows: Sequence[int],
    delay: int = 0,
) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """The windowed estimates of ``columns`` for the rows where ``rows``
    is true and for no others, at a cost that follows their count →
    ``(one [B, NW] a column, trips)``.

    The served rows are ranked (a cumulative sum, one scatter of row
    indices by rank) and read ``chunk_rows(B)`` at a time by
    :func:`_cms_query_tables` — the whole-batch read's arithmetic, so a
    served row gets the bits :func:`cms_query` gives it — in a
    ``lax.while_loop`` of ``trips = ⌈served ÷ K⌉`` turns: none served,
    no sketch table is touched. A row that is not served reads 0.0 in
    every column."""
    with step_scope("cms"):
        b = key.shape[-1]  # a wide key is [2, B]
        k = chunk_rows(b)
        span = -(-b // k) * k
        upto = jnp.cumsum(rows.astype(jnp.int32))  # served rows ≤ here
        served = upto[-1]
        # order[r] = the r-th served row; past the last one, b: out of
        # range, so the write-back below drops it
        order = jnp.full((span,), b, jnp.int32).at[
            jnp.where(rows, upto - 1, span)].set(
                jnp.arange(b, dtype=jnp.int32), mode="drop")
        tables = tuple(getattr(sk, c) for c in columns)

        def read_chunk(carry):
            trip, out = carry
            at = jax.lax.dynamic_slice(order, (trip * k,), (k,))
            src = jnp.minimum(at, b - 1)
            got = _cms_query_tables(sk, tables, key[..., src], day[src],
                                    windows, delay)
            return trip + 1, tuple(
                o.at[at].set(g, mode="drop") for o, g in zip(out, got))

        zeros = jnp.zeros((b, len(windows)), jnp.float32)
        trips, out = jax.lax.while_loop(
            lambda carry: carry[0] * k < served, read_chunk,
            (jnp.int32(0), (zeros,) * len(tables)))
        return out, trips


def cms_query(
    sk: CountMinSketch,
    key: jnp.ndarray,  # uint32 [B]
    day: jnp.ndarray,  # int32 [B]
    windows: Sequence[int],
    delay: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Windowed velocity estimates: (counts, amount_sums), each [B, NW].

    Window w sums the per-day min-over-depth estimates for days
    [day-delay-w+1, day-delay] (``delay=0``: [day-w+1, day], the
    historical behavior, bit-identical).
    """
    return _cms_query_tables(sk, (sk.count, sk.amount), key, day, windows,
                             delay)


def cms_query_fraud(
    sk: CountMinSketch,
    key: jnp.ndarray,  # uint32 [B]
    day: jnp.ndarray,  # int32 [B]
    windows: Sequence[int],
    delay: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """3-column windowed estimates: (counts, amount_sums, fraud_sums),
    each [B, NW]. Requires a fraud-tracking sketch (``cms_init(...,
    track_fraud=True)``). Both count and fraud are overestimate-only, so
    a risk RATIO derived from them is an estimate, not a bound — the
    documented sketch-tier degradation."""
    if sk.fraud is None:
        raise ValueError(
            "cms_query_fraud needs a fraud-tracking sketch "
            "(cms_init(..., track_fraud=True))")
    return _cms_query_tables(sk, (sk.count, sk.amount, sk.fraud), key, day,
                             windows, delay)
