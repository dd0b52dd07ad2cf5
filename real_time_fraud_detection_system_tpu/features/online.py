"""Online feature computation: update HBM state, emit the 15-feature matrix.

One call per micro-batch does what the reference needed three systems for
(Spark SQL join of precomputed feature tables + weekend/night SQL flags +
pandas UDF, ``fraud_detection.py:100-132``): scatter the batch into the
rolling-window state, then gather the feature vector for every row — all
inside jit, state resident in HBM across batches.

Terminal fraud labels arrive *delayed* (feedback events); risk windows are
delay-shifted (``feature_transformation.ipynb · cell 25``), so current-batch
label updates never contaminate the queried window.
"""

from __future__ import annotations

import dataclasses
from types import MappingProxyType
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from real_time_fraud_detection_system_tpu.config import FeatureConfig
from real_time_fraud_detection_system_tpu.core.batch import TxBatch
from real_time_fraud_detection_system_tpu.ops.cms import (
    CountMinSketch,
    cms_add_fraud,
    cms_init,
    cms_query,
    cms_query_where,
    cms_update,
)
from real_time_fraud_detection_system_tpu.ops.hashing import key_slot
from real_time_fraud_detection_system_tpu.ops.numerics import div_ieee
from real_time_fraud_detection_system_tpu.ops.keydir import (
    EMPTY_KEY,
    KeyDirectory,
    admit_slots,
    init_keydir,
    lookup_slots,
    pack_lanes,
    packed_entries,
    reclaim_entries,
    reserved,
)
from real_time_fraud_detection_system_tpu.ops.windows import (
    WindowState,
    gather_state_rows,
    init_window_state,
    query_windows,
    update_windows,
)
from real_time_fraud_detection_system_tpu.utils.trace import step_scope


class FeatureState(NamedTuple):
    """All HBM-resident feature state (a pytree; shard over the mesh).

    The three trailing fields exist only under ``key_mode="exact"`` (the
    tiered feature store): exact key→slot directories for both hot-tier
    tables and a fraud-tracking terminal sketch for graceful overflow.
    ``None`` defaults keep the pytree leaf structure — and therefore
    every existing checkpoint — identical for direct/hash configs."""

    customer: WindowState
    terminal: WindowState
    cms: Optional[CountMinSketch]
    customer_dir: Optional[KeyDirectory] = None
    terminal_dir: Optional[KeyDirectory] = None
    terminal_cms: Optional[CountMinSketch] = None


# The aggregate columns each key space's window table maintains beside its
# day stamps and counts, as ``update_windows``' keywords. Decided here and
# nowhere else, from the 15-feature spec: customer features are count and
# average amount, terminal features count and risk (the fraud share). The
# set is fixed for a table's life: the update neither reads nor writes an
# unmaintained column (a gather and a write saved, each: 2.9 ms for the
# customer table's, 4.8 for the terminal table's at the benchmark's size
# and 65,536 rows, PERF.md, PR 43), so customer ``fraud`` and terminal
# ``amount`` stay the zeros ``init_window_state`` made and no feature may
# read them. It also decides how the update orders a bucket's in-batch
# additions: the table that keeps a dollar sum sorts its batch by
# (bucket, lane), the other by the bucket alone — ``fraud`` is 0/1
# (:func:`fraud_of`) and counts are integers, exact in any order. Every
# update of either table, one chip (below) and sharded
# (``parallel/step.py``), passes these; late labels
# (:func:`apply_feedback_at_slot`) write terminal ``fraud``, a column its
# table maintains.
CUSTOMER_COLUMNS = MappingProxyType(
    {"track_amount": True, "track_fraud": False})
TERMINAL_COLUMNS = MappingProxyType(
    {"track_amount": False, "track_fraud": True})


def init_feature_state(
    cfg: FeatureConfig, with_cms: Optional[bool] = None,
    n_shards: int = 1, window_sharding=None,
) -> FeatureState:
    """``window_sharding`` (a mesh's slot-axis ``NamedSharding``) creates
    the window tables — all but a few MB of the state in ``direct`` mode
    — already spread over that mesh, each device allocating only its
    share (:func:`~..parallel.mesh.init_sharded_feature_state` is the
    caller). With ``n_shards > 1`` the stacked directories and the
    sketches (then born in the per-device layout ``shard_feature_state``
    would give them) are created the same way: at 2^22 + 2^23 slots a
    device they are 0.36 GB a device, and built on the default device
    first they stood there four times over beside its share of the
    tables (``peak_bytes_in_use`` 9.79 GB on the first of four v5e chips
    against 8.63 born sharded; PERF.md, PR 44).

    ``n_shards > 1`` builds the SHARDED exact layout: the window
    tables stay global ``[capacity · NB]`` columns (placed ``P(axis)``, so
    shard s owns slots ``[s*cap/n, (s+1)*cap/n)``), but each shard gets
    its OWN key directory over its local slot range — stacked
    ``[n_shards, ...]`` leaves (:func:`~..ops.keydir.
    init_stacked_keydir`). Without ``window_sharding`` sketches keep the
    single-chip layout here and :func:`~..parallel.mesh.
    shard_feature_state` expands them per-device at placement time.
    Non-exact key modes ignore
    ``n_shards`` (their layouts are width-independent)."""
    exact = cfg.key_mode == "exact"
    if with_cms is None:
        # exact mode always carries the customer sketch: it is the
        # overflow tier for rows that miss hot-tier admission
        with_cms = cfg.customer_source == "cms" or exact
    customer_dir = terminal_dir = terminal_cms = None
    born_sharded = window_sharding is not None and n_shards > 1

    def spread(build):
        """``build()``, whose leaves carry a leading shard axis — each
        device allocating its own block where the state is born
        sharded."""
        if not born_sharded:
            return build()
        return jax.jit(build, out_shardings=window_sharding)()

    def sketch(track_fraud: bool = False):
        def one():
            return cms_init(cfg.cms_depth, cfg.cms_width,
                            cfg.n_day_buckets, track_fraud=track_fraud)

        if not born_sharded:
            return one()
        return spread(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_shards,) + a.shape),
            one()))

    if exact:
        # Directory at 2x the slot capacity: load factor <= 0.5 keeps
        # fixed-depth probing effectively lossless until the free-slot
        # list itself runs dry (THE admission bound).
        def _dir(cap: int):
            if n_shards > 1:
                if cfg.key_bits == 64:
                    raise ValueError(
                        "key_bits=64 has no sharded layout: the mesh's "
                        "owner exchange and its stacked directories "
                        "carry one-word keys (ROADMAP B14)")
                if cap % n_shards:
                    raise ValueError(
                        f"capacity {cap} must divide by n_shards "
                        f"{n_shards}")
                from real_time_fraud_detection_system_tpu.ops.keydir \
                    import init_stacked_keydir

                local = cap // n_shards
                return spread(lambda: init_stacked_keydir(
                    2 * local, local, n_shards))
            return init_keydir(2 * cap, cap, cfg.key_bits)

        if cfg.customer_source != "cms":
            customer_dir = _dir(cfg.customer_capacity)
        terminal_dir = _dir(cfg.terminal_capacity)
        terminal_cms = sketch(track_fraud=True)
    return FeatureState(
        customer=init_window_state(cfg.customer_capacity, cfg.n_day_buckets,
                                   window_sharding),
        terminal=init_window_state(cfg.terminal_capacity, cfg.n_day_buckets,
                                   window_sharding),
        cms=sketch() if with_cms else None,
        customer_dir=customer_dir,
        terminal_dir=terminal_dir,
        terminal_cms=terminal_cms,
    )


def state_bytes(cfg: FeatureConfig, n_shards: int = 1) -> dict:
    """Static per-tier HBM accounting for the feature state a config
    would build (init_feature_state shapes × dtype bytes; no device
    access, no allocation). Keys: ``dense`` (window tables),
    ``directory`` (key directories + free lists), ``cms`` (all
    sketches), ``total``. The ``--state-hbm-budget-mb`` engine-build
    check reads this; the bytes a chip really holds are the ledger's
    ``peak_hbm_gb``. ``n_shards``: the sharded engine passes its width — window
    tables and directories partition (same total bytes, plus one
    free_top scalar per shard), but each shard carries its OWN sketch
    replica, so the cms tier multiplies."""
    exact = cfg.key_mode == "exact"
    nb = cfg.n_day_buckets
    # WindowState: bucket_day i32 + count/amount/fraud f32 = 16 B/bucket.
    dense = (cfg.customer_capacity + cfg.terminal_capacity) * nb * 16
    directory = 0
    cms = 0
    n_sketches = 0
    if cfg.customer_source == "cms" or exact:
        n_sketches += 1  # customer count+amount sketch
    if exact:
        n_sketches += 1  # terminal sketch...
    sketch_cols = 2
    cms = n_sketches * (nb * 4  # slice_day
                        + sketch_cols * nb * cfg.cms_depth * cfg.cms_width * 4)
    if exact:
        # ...whose fraud column is a third table on the terminal sketch
        cms += nb * cfg.cms_depth * cfg.cms_width * 4
        # KeyDirectory: keys u32 + slots i32 over 2x slots, free i32 +
        # free_top i32 per table (one free_top per shard); at key_bits=64
        # the entry's two key words beside its fingerprint, 8 B more.
        entry_bytes = 16 if cfg.key_bits == 64 else 8
        for cap, present in ((cfg.customer_capacity,
                              cfg.customer_source != "cms"),
                             (cfg.terminal_capacity, True)):
            if present:
                directory += (2 * cap * entry_bytes + cap * 4
                              + 4 * max(n_shards, 1))
    # per-device sketch replicas over the mesh (disjoint key partitions:
    # each device sketches only its owners' traffic)
    cms *= max(n_shards, 1)
    return {
        "dense": int(dense),
        "directory": int(directory),
        "cms": int(cms),
        "total": int(dense + directory + cms),
    }


def _flags(batch: TxBatch, cfg: FeatureConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(is_weekend, is_night) float32 flags from (day, tod_s).

    Unix day 0 (1970-01-01) was a Thursday → weekday(Mon=0) = (day+3) % 7.
    """
    weekday = jnp.remainder(batch.day + 3, 7)
    is_weekend = (weekday >= cfg.weekend_start_weekday).astype(jnp.float32)
    hour = batch.tod_s // 3600
    is_night = (hour <= cfg.night_end_hour).astype(jnp.float32)
    return is_weekend, is_night


class TableState(NamedTuple):
    """One table's share of the :class:`FeatureState` on its owner, the
    state a :class:`TablePlane` call carries (a pytree: it crosses the
    sharded exchange's ``lax.cond``)."""

    windows: WindowState
    directory: Optional[KeyDirectory] = None  # key_mode="exact"
    sketch: Optional[CountMinSketch] = None
    tier: Optional[jnp.ndarray] = None  # exact: [dense, cms] rows served
    # exact: [claim rounds the admit ran, those of them run narrow]
    rounds: Optional[jnp.ndarray] = None
    # key_bits=64: [rows that met another key under their fingerprint,
    # verify trips] of the admit's lookup
    alias: Optional[jnp.ndarray] = None


@dataclasses.dataclass(frozen=True)
class TablePlane:
    """THE table plane: update-then-query of one key space's windows, as
    its owner runs it. ``plane(tstate, key, day, amount, fraud, valid) ->
    (tstate', [rows, 2·NW])`` — counts beside amount sums (customer) or
    fraud sums (terminal). The one-chip step calls it where it stands; the
    sharded step runs the same body behind ``exchanged_compute``
    (``parallel/step.py``), with ``n_shards`` the mesh's width.

    Per key mode: ``direct`` / ``hash`` take the slot from
    :func:`~..ops.hashing.key_slot`; ``exact`` admits the key through the
    owner's directory, keeps rows that miss admission OUT of the dense
    scatter and serves them from the sketch tier (overestimate-only
    counts/amounts; terminal risk a ratio of two overestimates — an
    estimate, not a bound), counting both in ``tier``. A sketch the state
    carries is updated with EVERY row, so its estimate stays a valid
    overestimate whether or not the key holds a hot slot;
    ``customer_source="cms"`` serves the customer side from it alone.

    ``key`` is ``uint32 [rows]``, or ``[2, rows]`` at ``key_bits=64``
    (``exact`` only): the directory and the sketches take it whole."""

    table: str  # "customer" | "terminal": the scope, the column set
    cfg: FeatureConfig
    n_shards: int = 1

    @property
    def _customer(self) -> bool:
        return self.table == "customer"

    @property
    def sketch_only(self) -> bool:
        return self._customer and self.cfg.customer_source == "cms"

    def of(self, state: FeatureState) -> TableState:
        win, kd, sk = (
            (state.customer, state.customer_dir, state.cms)
            if self._customer else
            (state.terminal, state.terminal_dir, state.terminal_cms))
        exact = self.cfg.key_mode == "exact"
        return TableState(win, kd, sk,
                          jnp.zeros(2, jnp.float32) if exact else None,
                          jnp.zeros(2, jnp.float32) if exact else None,
                          jnp.zeros(2, jnp.float32)
                          if self.cfg.key_bits == 64 else None)

    def update(self, ts: TableState, key, day, amount, fraud, valid):
        """The scatter half → (tstate', slot, admitted | None)."""
        cfg = self.cfg
        win, kd, sketch, tier, rounds, alias = ts
        slot = adm = None
        if self.sketch_only and sketch is None:
            raise ValueError(
                "customer_source='cms' but the feature state has no "
                "sketch (init_feature_state must be built from the "
                "same config)")
        if not self.sketch_only:
            if cfg.key_mode == "exact":
                kd, slot, adm, ran, met = admit_slots(
                    kd, key, valid, n_probes=cfg.keydir_probes)
                rounds = rounds + ran.astype(jnp.float32)
                if met is not None:  # a wide directory counts its aliases
                    alias = alias + met.astype(jnp.float32)
                valid_hot = valid & adm
            else:
                capacity = (cfg.customer_capacity if self._customer
                            else cfg.terminal_capacity)
                slot = key_slot(key, capacity, cfg.key_mode, self.n_shards)
                valid_hot = valid
            win = update_windows(
                win, slot, day, amount, fraud, valid_hot,
                **(CUSTOMER_COLUMNS if self._customer
                   else TERMINAL_COLUMNS))
        if sketch is not None:
            sketch = cms_update(sketch, key, amount, day, valid,
                                fraud=None if self._customer else fraud)
        return TableState(win, kd, sketch, tier, rounds, alias), slot, adm

    def query(self, ts: TableState, slot, adm, key, day, valid):
        """The gather half → (tstate' (tier counted), [rows, 2·NW]).

        Under ``exact`` the sketch is read for the rows it serves —
        delivered rows that missed admission, ``valid & ~adm`` — and for
        no others (:func:`~..ops.cms.cms_query_where`: nothing missed,
        no sketch table touched). A padding row (``~valid``) is no miss:
        its features hold the sketch read's fill, 0.0."""
        windows = tuple(self.cfg.windows)
        delay = 0 if self._customer else self.cfg.delay_days
        if slot is None:  # customer_source="cms": the sketch serves all
            return ts, jnp.concatenate(
                cms_query(ts.sketch, key, day, windows), axis=1)
        got = query_windows(ts.windows, slot, day, windows, delay=delay)
        served = [got[i] for i in ((0, 1) if self._customer else (0, 2))]
        if adm is not None:
            miss = valid & ~adm
            cold, _ = cms_query_where(
                ts.sketch, ("count", "amount" if self._customer else "fraud"),
                key, day, miss, windows, delay)
            served = [jnp.where(adm[:, None], h, c)
                      for h, c in zip(served, cold)]
            ts = ts._replace(tier=ts.tier + jnp.stack([
                jnp.sum((valid & adm).astype(jnp.float32)),
                jnp.sum(miss.astype(jnp.float32))]))
        return ts, jnp.concatenate(served, axis=1)

    def __call__(self, ts: TableState, key, day, amount, fraud, valid):
        with step_scope(self.table):
            ts, slot, adm = self.update(ts, key, day, amount, fraud, valid)
            return self.query(ts, slot, adm, key, day, valid)


def fraud_of(batch: TxBatch) -> jnp.ndarray:
    """Labeled rows (``label >= 0``) carry their fraud flag into the
    terminal table (the feedback path); unlabeled rows contribute 0."""
    with step_scope("terminal"):
        return jnp.maximum(batch.label, 0).astype(jnp.float32)


def run_planes(state: FeatureState, batch: TxBatch, cfg: FeatureConfig,
               n_shards: int = 1, reach_customer=None, reach_terminal=None):
    """unpacked batch → reach(customer plane) → reach(terminal plane):
    the state half of THE device step, written once.

    ``reach(plane, tstate, key, fraud) -> (tstate', mat, exchange)`` is
    how a table's owner is reached: ``None`` calls the plane where the
    rows stand (one chip; a mesh's owner-placed customers), the sharded
    step passes its exchange, whose int32 counts (``parallel/step.py``'s
    ``EXCHANGE_TELEMETRY``) the two tables' reaches sum to ``exchange``,
    0 where nothing travelled. ``state`` is one owner's view (a mesh
    unstacks its per-device leaves first). Returns ``(state', customer
    [B, 2·NW], terminal [B, 2·NW], tier [6] | None, exchange)`` — under
    ``exact`` ``tier`` is ``[dense rows, cms rows, customer claim rounds,
    terminal claim rounds, customer narrow rounds, terminal narrow
    rounds]``, the one small vector a batch's finish fetches for the
    registry; at ``key_bits=64`` two more ride behind, ``[…, alias rows,
    alias verify trips]``, both tables summed.
    """
    fraud = fraud_of(batch)

    def local(plane, ts, key, fraud):
        return plane(ts, key, batch.day, batch.amount, fraud,
                     batch.valid) + (jnp.zeros((), jnp.int32),)

    c_plane = TablePlane("customer", cfg, n_shards)
    t_plane = TablePlane("terminal", cfg, n_shards)
    c, c_mat, c_xchg = (reach_customer or local)(
        c_plane, c_plane.of(state), batch.customer_key, fraud)
    t, t_mat, t_xchg = (reach_terminal or local)(
        t_plane, t_plane.of(state), batch.terminal_key, fraud)
    tier = None if t.tier is None else jnp.concatenate(
        [c.tier + t.tier, jnp.stack([c.rounds, t.rounds], 1).reshape(-1)]
        + ([] if t.alias is None else
           [t.alias if c.alias is None else c.alias + t.alias]))
    state = FeatureState(
        customer=c.windows, terminal=t.windows, cms=c.sketch,
        customer_dir=c.directory, terminal_dir=t.directory,
        terminal_cms=t.sketch)
    return state, c_mat, t_mat, tier, c_xchg + t_xchg


def update_and_featurize(
    state: FeatureState,
    batch: TxBatch,
    cfg: FeatureConfig,
) -> Tuple[FeatureState, jnp.ndarray]:
    """Returns (new_state, features [B, 15]): the planes run locally.

    Update-then-query: a row's windows include the current transaction and
    its batch-mates of the same key/day — matching the offline pandas
    ``rolling(...).count()`` which includes the current row
    (``feature_transformation.ipynb · cell 17``), at micro-batch granularity.
    """
    state, c_mat, t_mat, _, _ = run_planes(state, batch, cfg)
    return state, assemble(batch, cfg, c_mat, t_mat)


def assemble(batch, cfg, c_mat, t_mat) -> jnp.ndarray:
    """The planes' window sums (``[B, 2·NW]`` each: counts, then amount |
    fraud sums) → the [B, 15] matrix: the two averages, the calendar
    flags, the column stack."""
    nw = len(cfg.windows)
    c_count, c_amount = c_mat[:, :nw], c_mat[:, nw:]
    t_count, t_fraud = t_mat[:, :nw], t_mat[:, nw:]
    with step_scope("assemble"):
        # div_ieee: averages bit-equal to NumPy's and to the fused kernels'
        c_avg = jnp.where(
            c_count > 0, div_ieee(c_amount, jnp.maximum(c_count, 1.0)), 0.0)
        t_risk = jnp.where(
            t_count > 0, div_ieee(t_fraud, jnp.maximum(t_count, 1.0)), 0.0)
        is_weekend, is_night = _flags(batch, cfg)
        # Feature order must match features/spec.py::FEATURE_NAMES.
        cols = [batch.amount, is_weekend, is_night]
        for i in range(nw):
            cols.append(c_count[:, i])
            cols.append(c_avg[:, i])
        for i in range(nw):
            cols.append(t_count[:, i])
            cols.append(t_risk[:, i])
        return jnp.stack(cols, axis=1)


def update_and_featurize_exact(
    state: FeatureState,
    batch: TxBatch,
    cfg: FeatureConfig,
) -> Tuple[FeatureState, jnp.ndarray, jnp.ndarray]:
    """:func:`update_and_featurize` under ``key_mode="exact"``.

    Returns (new_state, features [B, 15], tier [6] float32) where
    ``tier[:2] = [dense, cms]`` counts (row × keyspace) admissions this
    batch — the device-side source of
    ``rtfds_feature_tier_rows_total{tier=…}`` — ``tier[2:4]`` the claim
    rounds the customer and the terminal admit ran
    (``rtfds_keydir_claim_rounds_total{table=…}``) and ``tier[4:6]``
    those of them that ran narrow
    (``rtfds_keydir_narrow_rounds_total{table=…}``). With the hot tier
    sized to hold every key this path is bit-identical to ``direct`` mode.
    """
    state, c_mat, t_mat, tier, _ = run_planes(state, batch, cfg)
    return state, assemble(batch, cfg, c_mat, t_mat), tier


def _update_and_gather(state: FeatureState, batch: TxBatch,
                       cfg: FeatureConfig):
    """The fused kernels' state half: the planes' update, then the raw
    rows the kernels window themselves → (state', customer (bucket_day,
    count, amount), terminal (bucket_day, count, fraud))."""
    fraud = fraud_of(batch)
    rows = []
    for plane, key in ((TablePlane("customer", cfg), batch.customer_key),
                       (TablePlane("terminal", cfg), batch.terminal_key)):
        with step_scope(plane.table):
            ts, slot, _ = plane.update(
                plane.of(state), key, batch.day, batch.amount, fraud,
                batch.valid)
            rows.append((ts, gather_state_rows(ts.windows, slot)))
    (c, (c_bd, c_cnt, c_amt, _)), (t, (t_bd, t_cnt, _, t_frd)) = rows
    state = state._replace(customer=c.windows, terminal=t.windows,
                           cms=c.sketch)
    return state, (c_bd, c_cnt, c_amt), (t_bd, t_cnt, t_frd)


def update_and_score_pallas(
    state: FeatureState,
    batch: TxBatch,
    cfg: FeatureConfig,
    scaler_mean: jnp.ndarray,
    scaler_scale: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    interpret: Optional[bool] = None,
) -> Tuple[FeatureState, jnp.ndarray, jnp.ndarray]:
    """Scatter-update state, then run the fused Pallas featurize+score
    kernel (``ops/pallas_kernels.py``) on the gathered state rows.

    Returns (new_state, probs [B], features [B, 15]) — the linear-model
    equivalent of :func:`update_and_featurize` + scale + logreg in ONE
    device kernel after the updates.
    """
    from real_time_fraud_detection_system_tpu.ops.pallas_kernels import (
        fused_featurize_score,
    )

    state, c_rows, t_rows = _update_and_gather(state, batch, cfg)
    with step_scope("fused_step"):
        probs, feats = fused_featurize_score(
            c_rows,
            t_rows,
            batch.day,
            batch.tod_s,
            batch.amount,
            batch.valid,
            scaler_mean, scaler_scale, w, b,
            windows=tuple(cfg.windows),
            delay=cfg.delay_days,
            weekend_start=cfg.weekend_start_weekday,
            night_end=cfg.night_end_hour,
            interpret=interpret,
        )
    return state, probs, feats


def update_and_score_pallas_forest(
    state: FeatureState,
    batch: TxBatch,
    cfg: FeatureConfig,
    scaler_mean: jnp.ndarray,
    scaler_scale: jnp.ndarray,
    pf,  # ops.pallas_forest.PallasForest (tables in the serving z_mode)
    interpret: Optional[bool] = None,
) -> Tuple[FeatureState, jnp.ndarray, jnp.ndarray]:
    """Scatter-update state, then run the fused forest featurize→score
    kernel (``ops/pallas_forest.py::fused_forest_leaf_sum``) on the
    gathered state rows.

    Returns (new_state, leaf_sum [B], features [B, 15]) — the
    tree-ensemble equivalent of :func:`update_and_featurize` + scale +
    ``gemm_leaf_sum`` with the feature block VMEM-resident end-to-end
    (the scatter/gather boundary XLA cannot fuse through stays in XLA,
    whose TPU gather emitter wins). The caller divides by ``pf.n_trees``
    (bagging) or adds the base logit (boosting) and masks invalid rows.
    """
    from real_time_fraud_detection_system_tpu.ops.pallas_forest import (
        fused_forest_leaf_sum,
    )

    state, c_rows, t_rows = _update_and_gather(state, batch, cfg)
    with step_scope("fused_step"):
        leaf_sum, feats = fused_forest_leaf_sum(
            pf,
            c_rows,
            t_rows,
            batch.day,
            batch.tod_s,
            batch.amount,
            scaler_mean, scaler_scale,
            windows=tuple(cfg.windows),
            delay=cfg.delay_days,
            weekend_start=cfg.weekend_start_weekday,
            night_end=cfg.night_end_hour,
            interpret=interpret,
        )
    return state, leaf_sum, feats


def compact_feature_state(
    state: FeatureState,
    now_day: jnp.ndarray,  # int32 [] — newest day the stream has seen
    cfg: FeatureConfig,
    demote_slots: int = 0,
):
    """Recency compaction (``key_mode="exact"``): reclaim the hot-tier
    slots that hold only dead history, at a cost that follows what the
    pass vacates.

    A slot whose NEWEST ``bucket_day`` is older than
    ``now_day - (delay_days + max(windows))`` can never contribute to
    any window query again (the age mask already excludes every bucket
    it holds) — its directory entry is vacated, the slot returns to the
    free list, and its window row is reset so a later grant starts
    clean. Returns (new_state, reclaimed [2] int32 = [customer,
    terminal]). Fixed shapes throughout: this is a ``DispatchSignature``
    variant of the compiled step family, not a recompile.

    What a table gives up is known from dense counts before any indexed
    work (:func:`_table_gives`): a table that gives nothing up runs no
    entry-wide gather, no selection and no trip of the vacate — its
    ``reclaimed`` is 0, which is how the host counts the sweeps
    (``rtfds_state_compact_sweeps_total``); one that does pays one
    gather a directory entry to find the entries, and packs what it
    vacates K lanes a trip (``ops/keydir.reclaim_entries``), and then
    sweeps its four window columns once — one flat select a column, in
    a loop that runs no trip for a table that vacated nothing
    (``WindowState.clear_slots``). What does not follow the input: the
    per-slot ``newest`` of both tables, read from the flat stamps
    (``WindowState.newest``), and two dense selects over each directory.
    On a v5e at 2^22 + 2^23 slots this table-wide part is 17.7 ms of a
    pass that takes nothing and 46.9 of one in which both tables give,
    where it was 67.4 of every pass through padded ``[cap, 40]`` views
    (PERF.md, PR 54).

    ``demote_slots > 0`` adds the cold tier's PRESSURE eviction behind
    the dead reclaim: when a table still sits above
    ``cold_highwater * slot_capacity`` occupied slots, the oldest
    (strictly pre-``now_day``) entries — up to ``demote_slots`` per
    table, a static ``top_k`` width — are DEMOTED: their exact window
    rows are gathered into a fixed-shape payload BEFORE the slots are
    vacated, and the return becomes ``(state, reclaimed[2], payload)``
    where ``payload[table] = (keys u32 [K], bucket_day i32 [K, NB],
    count/amount/fraud f32 [K, NB])`` with unselected lanes masked to
    ``EMPTY_KEY``/empty rows. The host appends the payload to
    ``io/coldstore.py`` — demote, don't discard.
    """
    # Every op below sits under rtfds.compact — but for the demote pass's
    # selection and payload gather (rtfds.demote, in _select_oldest and
    # _demoted_rows), a SIBLING scope and not a part: the stage metrics
    # that read the two then add up to the program, and no op counts twice.
    demote = int(demote_slots)
    with step_scope("compact"):
        now = now_day.astype(jnp.int32)
    out = {}
    counts = []
    payload = {}
    for dir_name, ws_name in (("customer_dir", "customer"),
                              ("terminal_dir", "terminal")):
        kd = getattr(state, dir_name)
        ws = getattr(state, ws_name)
        if kd is None:
            out[dir_name], out[ws_name] = kd, ws
            counts.append(jnp.int32(0))
            payload[ws_name] = None
            continue
        out[dir_name], out[ws_name], n, payload[ws_name] = _compact_table(
            kd, ws, now, cfg, min(demote, kd.dir_capacity))
        counts.append(n)
    new_state = state._replace(
        customer=out["customer"], terminal=out["terminal"],
        customer_dir=out["customer_dir"],
        terminal_dir=out["terminal_dir"],
    )
    with step_scope("compact"):
        reclaimed = jnp.stack(counts)
    if demote > 0:
        return new_state, reclaimed, payload
    return new_state, reclaimed


def _compact_table(
    kd: KeyDirectory,
    ws: WindowState,
    now_day: jnp.ndarray,  # int32 []
    cfg: FeatureConfig,
    demote_slots: int,  # the payload's lanes a table; 0 = no cold tier
):
    """One table's share of :func:`compact_feature_state`. Returns
    ``(kd, ws, n_reclaimed, payload | None)``.

    The two conditionals yield a ``[dir_cap]`` vector each and touch
    neither the window columns nor the directory: state a conditional
    returned, it might copy. The columns are read and written as they
    are stored, flat (``WindowState.newest`` / ``clear_slots``), and
    written only by a table that gives something up."""
    horizon = int(cfg.delay_days + max(cfg.windows))
    with step_scope("compact"):
        cutoff = now_day - jnp.int32(horizon)
        newest = ws.newest()  # [slot_cap]
        live = kd.slots >= 0
        gives, demotes, n_evict = _table_gives(
            kd, newest, cutoff, now_day,
            jnp.int32(int(cfg.cold_highwater * ws.capacity)), demote_slots)
        # one lane a directory entry — for a table that gives something
        # up; the fill makes no entry dead and none eligible
        newest_e = jax.lax.cond(
            gives,
            lambda: newest[jnp.clip(kd.slots, 0, ws.capacity - 1)],
            lambda: jnp.full_like(kd.slots, now_day))
        dead_entry = live & (newest_e < cutoff)
    payload = None
    if demote_slots:
        # payload gathered BEFORE the vacate; pressure eviction EXTENDS
        # the dead mask, so the demote variant pays ONE combined reclaim
        # + window sweep, not a second on top
        with step_scope("demote"):
            sel = jax.lax.cond(
                demotes,
                lambda: _select_oldest(
                    live & ~dead_entry & (newest_e < now_day),
                    now_day - newest_e, n_evict, horizon),
                lambda: jnp.zeros_like(live))
            payload = _demoted_rows(kd, ws, sel, demote_slots)
    with step_scope("compact"):
        if demote_slots:
            dead_entry = dead_entry | sel
        kd, vacated, n = reclaim_entries(kd, dead_entry)
        ws = ws.clear_slots(vacated, n)
    return kd, ws, n, payload


def _table_gives(
    kd: KeyDirectory,
    newest: jnp.ndarray,  # int32 [slot_cap] — newest bucket_day per slot
    cutoff: jnp.ndarray,  # int32 [] — history older than this is dead
    now_day: jnp.ndarray,  # int32 []
    target: jnp.ndarray,  # int32 [] — occupied slots the tier may keep
    demote_slots: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """What a pass will take from one table, from dense per-slot counts
    and the free stack's height — before any gather through the
    directory. Returns ``(gives [] bool, demotes [] bool, n_evict []
    int32)``: whether anything is vacated at all, whether the pressure
    eviction selects anything, and its quota.

    A free slot's row is empty (``bucket_day`` −1: the initial fill, a
    rolled-back grant and ``clear_slots`` all leave it so), so with
    ``cutoff`` ≥ 0 every slot whose newest day reaches it is occupied
    and ``occupied − count(newest ≥ cutoff)`` is the number of dead
    entries exactly — an occupied slot whose row is still empty counts
    as dead here as it does entry by entry. With ``cutoff`` ≤ −1 every
    slot passes the compare, the free ones too: they are taken off, and
    nothing is dead. The demote quota and the count of slots it may take
    (live, last touched before ``now_day``) are scalars the same way."""
    free_top = kd.free_top.astype(jnp.int32)
    occupied = jnp.int32(kd.slot_capacity) - free_top
    n_recent = (jnp.sum((newest >= cutoff).astype(jnp.int32))
                - jnp.where(cutoff < 0, free_top, 0))
    n_dead = occupied - n_recent
    if not demote_slots:
        return n_dead > 0, jnp.bool_(False), jnp.int32(0)
    n_evict = jnp.clip(occupied - n_dead - target, 0, demote_slots)
    n_today = jnp.sum((newest >= now_day).astype(jnp.int32))
    demotes = jnp.minimum(n_evict, n_recent - n_today) > 0
    return (n_dead > 0) | demotes, demotes, n_evict


def _select_oldest(
    eligible: jnp.ndarray,  # bool [dir_cap] — live, not dead, pre-now_day
    age_days: jnp.ndarray,  # int32 [dir_cap] — now_day - newest bucket
    n_evict: jnp.ndarray,  # int32 [] — the quota (post-dead-reclaim
    #                        occupancy over the highwater target, ≤ K)
    horizon: int,  # days — dead-history cutoff distance (static)
) -> jnp.ndarray:
    """Pressure eviction's selection for one table: the ``n_evict``
    oldest eligible directory entries (strictly pre-``now_day`` newest
    bucket; an entry touched today is never evicted under the feet of
    the batch that just wrote it), all of them when fewer are eligible.
    Returns the ``[dir_cap]`` bool selection.

    It runs WITHOUT a ``top_k`` sort: eligible ages live in
    ``[1, horizon]`` (anything older is already in the dead mask), so an
    age histogram + suffix sum finds the threshold age and a cumsum rank
    breaks the tie at the threshold by lowest index — the exact set
    ``lax.top_k`` would pick (its ties also resolve to the lowest
    index), at O(n) cost instead of an O(n log k) sort over the whole
    directory. Named by its caller's ``rtfds.demote``."""
    hzn = max(int(horizon), 1)
    # Age histogram over [1, hzn] (bucket 0 holds the ineligible mass
    # and is never selectable; eligible entries have age >= 1 because
    # newest_e < now_day, and age <= hzn because older is dead).
    age = jnp.clip(jnp.where(eligible, age_days, 0),
                   0, hzn).astype(jnp.int32)
    # one compare-and-count an age, not a scatter-add of every
    # directory entry into ~40 bins (the chip serialises those)
    hist = jnp.sum(
        age[None, :] == jnp.arange(hzn + 3, dtype=jnp.int32)[:, None],
        axis=1, dtype=jnp.int32)
    incl = jnp.cumsum(hist[::-1])[::-1]  # incl[a] = #entries age >= a
    # Threshold t* = max age with incl >= n_evict (monotone, so a count
    # of satisfied ages IS the argmax); floor 1 covers the
    # n_evict > #eligible case, where every eligible entry is taken.
    thresh = jnp.maximum(
        jnp.sum((incl >= n_evict)[1:hzn + 2].astype(jnp.int32)),
        jnp.int32(1))
    quota = n_evict - incl[thresh + 1]  # lanes left for age == t*
    at_t = age == thresh
    rank_t = jnp.cumsum(at_t.astype(jnp.int32)) - 1
    return (age > thresh) | (at_t & (rank_t < quota))


def _demoted_rows(
    kd: KeyDirectory,
    ws: WindowState,
    sel: jnp.ndarray,  # bool [dir_cap] — _select_oldest's selection
    demote_slots: int,  # the payload's lanes (≥ the selection's size)
):
    """The demote payload of one table: keys and exact window rows of
    the selected entries in ``demote_slots`` fixed lanes, gathered
    BEFORE the vacate — ``(keys u32 [k], bucket_day i32 [k, NB],
    count/amount/fraud f32 [k, NB])``, unselected lanes ``EMPTY_KEY`` /
    empty rows. Named by its caller's ``rtfds.demote``. A wide directory
    hands out ``keys u32 [2, k]``, both words of every entry, padding
    lanes ``EMPTY_KEY`` in both (the reserved pattern).

    Lanes go out in KEY order (one sort of k keys; EMPTY_KEY, the
    largest u32, keeps the padding last; a wide key sorts by its high
    word, then its low: the order of the uint64 it is): the store's index
    is sorted by key, so the host lands such a payload as it stands,
    without gathering every row into order once more. The indexed work follows
    the selection: entries are packed into lanes by rank (lane j holds
    the (j+1)-th selected entry, ``ops/keydir.packed_entries``) and the
    rows gathered K lanes a trip of two ``lax.while_loop``s of
    ⌈selected ÷ K⌉ trips each — nothing selected, no trip, and the
    payload is the empty one it was born as."""
    slot_cap, dir_cap = int(ws.capacity), kd.dir_capacity
    nb = int(ws.bucket_day.shape[0]) // slot_cap  # = ws.n_buckets
    k = int(demote_slots)
    lanes = pack_lanes(k)
    trips = -(-k // lanes)
    lane = jnp.arange(lanes, dtype=jnp.int32)
    taken = jnp.cumsum(sel.astype(jnp.int32))
    n_sel = taken[-1]

    def more(carry):
        return carry[0] * lanes < n_sel

    # the words of an entry's key: the key itself, or (low, high)
    words = (kd.keys_lo, kd.keys_hi) if kd.wide else (kd.keys,)

    def pack(carry):
        trip, keys, eidx = carry
        entry = jnp.minimum(
            packed_entries(taken, trip * lanes, lanes), dir_cap - 1)
        key = tuple(w[entry] for w in words)
        live = trip * lanes + lane < n_sel
        return (trip + 1,
                tuple(k.at[trip].set(jax.lax.select(
                    live, w, jnp.full_like(w, EMPTY_KEY)))
                    for k, w in zip(keys, key)),
                eidx.at[trip].set(entry))

    _, keys, eidx = jax.lax.while_loop(more, pack, (
        jnp.int32(0),
        tuple(jnp.full((trips, lanes), EMPTY_KEY, jnp.uint32)
              for _ in words),
        jnp.zeros((trips, lanes), jnp.int32)))
    keys, eidx = tuple(k.reshape(-1) for k in keys), eidx.reshape(-1)
    if kd.wide:
        # the selected lanes first, by (high, low): the uint64's order
        hi, lo, eidx = jax.lax.sort((keys[1], keys[0], eidx), num_keys=2)
        keys, eidx = jnp.stack([lo, hi]), eidx.reshape(trips, lanes)
    else:
        keys, = keys
        by_key = jnp.argsort(keys)  # the selected lanes first, by key
        keys, eidx = keys[by_key], eidx[by_key].reshape(trips, lanes)
    fills = (jnp.int32(-1), 0.0, 0.0, 0.0)

    def fetch(carry):
        trip, rows = carry
        slot = jnp.clip(kd.slots[eidx[trip]], 0, slot_cap - 1)
        ok = (trip * lanes + lane < n_sel)[:, None]
        return trip + 1, tuple(
            buf.at[trip].set(jnp.where(ok, row, fill))
            for buf, row, fill in zip(rows, ws.rows(slot), fills))

    _, rows = jax.lax.while_loop(more, fetch, (jnp.int32(0), tuple(
        jnp.full((trips, lanes, nb), fill, col.dtype)
        for fill, col in zip(fills, ws.columns()))))
    return (keys[..., :k],) + tuple(
        r.reshape(trips * lanes, nb)[:k] for r in rows)


def promote_rows(
    state: FeatureState,
    payload: dict,  # {"customer": (keys, bd, cnt, amt, frd)|None, ...}
    cfg: FeatureConfig,
) -> Tuple[FeatureState, jnp.ndarray]:
    """Promotion: merge cold-store rows back into the hot tier, ahead of
    the step that scores the rows of the keys they belong to.

    Per table: ``admit_slots`` grants (or finds) a slot for every
    non-``EMPTY_KEY`` payload lane, then a per-bucket DAY-DOMINANCE
    merge takes the cold bucket only where its ``bucket_day`` is
    strictly newer than the resident one — never a float add, so
    promotion is IDEMPOTENT (re-promoting a resident key is a no-op)
    and a key that accrued fresh hot rows while its promotion was in
    flight converges to exactly the never-evicted state: eviction
    required every cold bucket to be strictly pre-eviction-day, and
    post-return writes land on days >= the return day, so cold and hot
    buckets never contend for the same day. Returns ``(state,
    stats [2, 4] int32)`` = per-table ``[admitted, dropped, claim
    rounds, narrow rounds]`` (dropped: the free list ran dry or every
    probe position was taken — the engine stops the run before that
    batch is delivered; rounds: what the admit ran, for
    ``rtfds_keydir_claim_rounds_total`` and
    ``rtfds_keydir_narrow_rounds_total``). The caller guarantees unique
    keys per dispatch.
    """
    out = {}
    stats = []
    for dir_name, ws_name in (("customer_dir", "customer"),
                              ("terminal_dir", "terminal")):
        kd = getattr(state, dir_name)
        ws = getattr(state, ws_name)
        pay = payload.get(ws_name)
        if kd is None or pay is None:
            out[dir_name], out[ws_name] = kd, ws
            stats.append(jnp.zeros((4,), jnp.int32))
            continue
        keys, bd, cnt, amt, frd = pay
        # the admit carries rtfds.keydir and its parts, as in the step;
        # everything else of the program carries rtfds.promote — siblings
        with step_scope("promote"):
            valid = (~reserved(keys) if kd.wide
                     else keys != jnp.uint32(EMPTY_KEY))
        kd, slot, adm, rounds, _ = admit_slots(kd, keys, valid,
                                            n_probes=cfg.keydir_probes)
        with step_scope("promote"):
            slot_c = jnp.clip(slot, 0, ws.capacity - 1)
            hot = ws.rows(slot_c)
            take = adm[:, None] & (bd > hot[0])
            tgt = jnp.where(adm, slot, ws.capacity)
            out[dir_name] = kd
            out[ws_name] = ws.set_rows(tgt, *(
                jnp.where(take, cold, row)
                for cold, row in zip((bd, cnt, amt, frd), hot)))
            adm_n = jnp.sum(adm.astype(jnp.int32))
            drop_n = jnp.sum((valid & ~adm).astype(jnp.int32))
            stats.append(jnp.concatenate(
                [jnp.stack([adm_n, drop_n]), rounds]))
    with step_scope("promote"):
        stats = jnp.stack(stats)
    return (
        state._replace(
            customer=out["customer"], terminal=out["terminal"],
            customer_dir=out["customer_dir"],
            terminal_dir=out["terminal_dir"],
        ),
        stats,
    )


def apply_feedback(
    state: FeatureState,
    terminal_key: jnp.ndarray,  # uint32 [B]
    day: jnp.ndarray,  # int32 [B] — the day of the original transaction
    label: jnp.ndarray,  # int32 [B] 0/1
    valid: jnp.ndarray,  # bool [B]
    cfg: FeatureConfig,
) -> FeatureState:
    """Late fraud-label feedback: scatter fraud counts into past day buckets.

    The ingest path calls this for the labeled-feedback topic (BASELINE.json
    config 4). Counts are NOT incremented (the transaction was already
    counted when it streamed through); only the fraud sums change, which the
    delay-shifted risk windows will pick up.

    ``key_mode="exact"``: labels route by directory LOOKUP (never an
    insert — feedback must not evict live traffic's slots). Hits land in
    the dense terminal windows exactly as before; misses (the key was
    never admitted, or its slot was compacted away) land in the terminal
    sketch's fraud column so the sketch-tier risk estimate still learns.
    """
    if cfg.key_mode == "exact":
        term_slot, hit = lookup_slots(
            state.terminal_dir, terminal_key, valid,
            n_probes=cfg.keydir_probes)
        state = apply_feedback_at_slot(state, term_slot, day, label,
                                       valid & hit)
        return state._replace(terminal_cms=cms_add_fraud(
            state.terminal_cms, terminal_key, day, label, valid & ~hit))
    term_slot = key_slot(terminal_key, cfg.terminal_capacity, cfg.key_mode)
    return apply_feedback_at_slot(state, term_slot, day, label, valid)


def apply_feedback_sharded_exact(
    state: FeatureState,
    terminal_key: jnp.ndarray,  # uint32 [B] (already fold_key'd)
    day: jnp.ndarray,  # int32 [B] — the day of the original transaction
    label: jnp.ndarray,  # int32 [B] 0/1
    valid: jnp.ndarray,  # bool [B]
    cfg: FeatureConfig,
) -> FeatureState:
    """Sharded-exact twin of :func:`apply_feedback`: ownership is
    ``key % n_shards`` (the same modulo the step's owner exchange
    routes by), the slot comes from THAT shard's directory — a LOOKUP,
    never an insert (feedback must not evict live traffic's slots).
    Hits land in the owner's dense window rows (global table row =
    ``owner * cap_local + local_slot``); misses land in the owner's
    sketch replica's fraud column (``cms_add_fraud``'s owner-indexed
    form — ONE bounded-lateness policy with the single-chip path).
    Plain jitted global-array ops — GSPMD inserts the (off-hot-path)
    collectives."""
    from real_time_fraud_detection_system_tpu.ops.keydir import (
        lookup_slots_stacked,
    )

    kd = state.terminal_dir
    n_shards = int(kd.keys.shape[0])
    cap_local = state.terminal.capacity // n_shards
    owner = (terminal_key.astype(jnp.uint32)
             % jnp.uint32(n_shards)).astype(jnp.int32)
    slot, hit = lookup_slots_stacked(kd, owner, terminal_key, valid,
                                     n_probes=cfg.keydir_probes)
    grow = owner * cap_local + slot
    state = apply_feedback_at_slot(state, grow, day, label, valid & hit)
    if state.terminal_cms is None:  # defensive: exact states carry one
        return state
    return state._replace(terminal_cms=cms_add_fraud(
        state.terminal_cms, terminal_key, day, label, valid & ~hit,
        owner=owner))


def apply_feedback_at_slot(
    state: FeatureState,
    term_slot: jnp.ndarray,  # int32 [B] — row into the terminal table
    day: jnp.ndarray,
    label: jnp.ndarray,
    valid: jnp.ndarray,
) -> FeatureState:
    """Slot-addressed core of :func:`apply_feedback`.

    Separated so layouts with a different key→slot mapping (the sharded
    engine's owner-partitioned terminal table, ``parallel/step.py``) can
    land labels without re-deriving the single-chip mapping."""
    nb = state.terminal.n_buckets
    bucket = jnp.remainder(day, nb)
    flat = term_slot * nb + bucket
    # Only land the label if the bucket still holds that day (ring not wrapped).
    # ``fraud`` is a column the terminal table maintains (TERMINAL_COLUMNS).
    live = valid & (state.terminal.bucket_day[flat] == day)
    frd = state.terminal.fraud.at[flat].add(
        label.astype(jnp.float32) * live.astype(jnp.float32)
    )
    return state._replace(
        terminal=dataclasses.replace(state.terminal, fraud=frd))
