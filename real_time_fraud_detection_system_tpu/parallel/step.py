"""Sharded micro-batch step: shard_map over the mesh, all_to_all on ICI.

Distribution contract (SURVEY §2.3 mapping):

- rows arrive partitioned by **customer** (Kafka partition = customer key
  mod P, one partition per device), so customer window state is updated and
  queried purely device-locally;
- **terminal** windows are owned by ``terminal_key mod n_dev``; since a
  device's rows reference foreign terminals, the step routes
  (key, day, amount, fraud) records to owners with one ``all_to_all``,
  updates/queries the owner's shard, and routes the window aggregates back
  with a second ``all_to_all`` — the ICI exchange that replaces the
  reference's shared Iceberg feature tables (``fraud_detection.py:100-123``);
- params/scaler are replicated; online-SGD gradients are ``psum``-reduced,
  so every device applies the identical update (data-parallel training,
  BASELINE.json config 4).

The body is the one-chip step's (``features/step.py``): the same
``run_planes`` and tail run inside ``shard_map``, with the exchange
below as the way a table's owner is reached.

Everything is static-shape: the exchange buffer is [n_dev × B_local] per
field (worst case: every local row targets one owner).

``key_mode="exact"`` (the tiered feature store) keeps this exact wire
contract — ownership is still ``key % n_dev``, so the host partitioner
and the owner exchange route identically — but the slot WITHIN a shard
comes from that shard's private key directory instead of
``ops/hashing.key_slot``'s modulo math: each owner resolves
its received (key, row) records through ``admit_slots`` locally,
admission misses are served from the owner's per-device sketch replica,
and per-shard [dense, cms] tier counts, with the claim rounds each
shard's two admits ran, leave the step stacked [n_dev, 6]. :func:`make_sharded_compact` runs the recency-compaction
pass per shard under the same ``shard_map``.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from real_time_fraud_detection_system_tpu.config import Config
from real_time_fraud_detection_system_tpu.core.batch import TxBatch
from real_time_fraud_detection_system_tpu.features.online import (
    FeatureState,
    run_planes,
)
from real_time_fraud_detection_system_tpu.features.step import (
    make_tail,
    pack_selective,
    selective,
)
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.utils.trace import step_scope


def partition_batch_spill(
    cols: dict, n_dev: int, rows_per_shard: int
) -> "list[Tuple[dict, np.ndarray, np.ndarray]]":
    """Host-side partitioner with densely-packed hot-key spill: one or
    more [n_dev × rows_per_shard] layouts.

    Partition of a row is ``customer_id % n_dev`` — the broker's key-hash
    analogue, sticky per customer. Rows that fit their shard's budget form
    chunk 0, laid out owner-locally (``__routed__ = False``): customer
    state is touched with zero collectives. A skewed key distribution can
    put more than ``rows_per_shard`` rows on one shard; the overflow is
    **re-packed densely** across ALL shards into follow-on chunks
    (``__routed__ = True``): every device carries an equal share of the
    hot key's rows, and the step routes customers to their owner over ICI
    exactly like terminals — utilization stays ~100% instead of
    collapsing to 1/n_dev right when load spikes.

    Returns a list of (columns dict with every array length
    n_dev*rows_per_shard plus ``__valid__`` mask and ``__routed__`` flag,
    input_rows, pos): ``input_rows[j]`` is the original row index of the
    chunk's j-th occupied slot and ``pos[j]`` its position in the chunk
    layout — for re-assembling results in input order.
    """
    cust = cols["customer_id"]
    n = len(cust)
    if n_dev == 1:
        # Degenerate mesh: every row lands on the one shard in input
        # order — skip the argsort/searchsorted rank machinery (host
        # cost that buys nothing at width 1).
        part = np.zeros(n, dtype=np.int64)
        rank = np.arange(n, dtype=np.int64)
    else:
        part = (cust % n_dev).astype(np.int64)
        order = np.argsort(part, kind="stable")
        part_sorted = part[order]
        rank_sorted = (
            np.arange(n) - np.searchsorted(part_sorted, part_sorted,
                                           "left")
        )
        rank = np.empty(n, dtype=np.int64)
        rank[order] = rank_sorted
    total = n_dev * rows_per_shard

    def _mk_chunk(rows, pos, routed):
        out = {}
        for k, v in cols.items():
            buf = np.zeros(total, dtype=v.dtype)
            buf[pos] = v[rows]
            out[k] = buf
        valid = np.zeros(total, dtype=bool)
        valid[pos] = True
        out["__valid__"] = valid
        out["__routed__"] = routed
        return out, rows, pos

    fits = rank < rows_per_shard
    rows0 = np.flatnonzero(fits)
    pos0 = part[rows0] * rows_per_shard + rank[rows0]
    chunks = [_mk_chunk(rows0, pos0, False)]
    overflow = np.flatnonzero(~fits)  # original order preserved
    for s in range(0, len(overflow), total):
        rows = overflow[s : s + total]
        i = np.arange(len(rows), dtype=np.int64)
        # Row-robin across devices so even a partial final chunk spreads
        # its rows over the whole mesh.
        pos = (i % n_dev) * rows_per_shard + i // n_dev
        chunks.append(_mk_chunk(rows, pos, True))
    return chunks


def partition_batch_by_customer(
    cols: dict, n_dev: int, rows_per_shard: int
) -> Tuple[dict, np.ndarray]:
    """Single-chunk partitioner: layout rows as [n_dev × rows_per_shard].

    Returns (columns dict with every array length n_dev*rows_per_shard,
    gather_index) where ``gather_index[i]`` is the output position of input
    row i. Raises on shard overflow — callers that must survive hot keys
    use :func:`partition_batch_spill` (the sharded engine does).
    """
    chunks = partition_batch_spill(cols, n_dev, rows_per_shard)
    if len(chunks) > 1:
        raise ValueError(
            f"partition overflow: >{rows_per_shard} rows on one shard; "
            f"raise rows_per_shard, poll smaller batches, or use "
            f"partition_batch_spill"
        )
    out, rows, pos_chunk = chunks[0]
    n = len(cols["customer_id"])
    pos = np.empty(n, dtype=np.int64)
    pos[rows] = pos_chunk
    return out, pos


def _route(
    dest: jnp.ndarray,  # int32 [B] in [0, n_dev)
    valid: jnp.ndarray,  # bool [B]
    n_dev: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compute (send_pos [B], recv layout capacity) for bucketed all_to_all.

    send_pos[i] = dest[i] * B + rank-of-i-within-its-dest-bucket. Invalid
    rows route to bucket slots but are masked by the caller.
    """
    b = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    rank_sorted = jnp.arange(b, dtype=jnp.int32) - jnp.searchsorted(
        sorted_dest, sorted_dest, side="left"
    ).astype(jnp.int32)
    rank = jnp.zeros(b, dtype=jnp.int32).at[order].set(rank_sorted)
    return dest * b + rank, rank


@contextlib.contextmanager
def _exchange_scope(part: str):
    """``rtfds.exchange/rtfds.<part>``: what the exchange makes a device
    do around its two all_to_alls (``route``: ranking rows by owner,
    ``pack``: filling the send buffer, ``unpack``: reading the receive
    buffer and the back-gather), so a trace prices the exchange whole."""
    with step_scope("exchange"), step_scope(part):
        yield


def _make_xchg(axis, n_dev: int, cap: int):
    """The bucketed all_to_all: [n_dev·cap, ...] laid out owner-major →
    same shape with bucket b holding what every peer sent to owner b.
    Its own inverse (routing results back is ``xchg(...)[send_pos]``);
    carries arbitrary trailing feature dims."""

    def xchg(x):
        rest = x.shape[1:]
        with step_scope("exchange"):
            return jax.lax.all_to_all(
                x.reshape((n_dev, cap) + rest), axis, split_axis=0,
                concat_axis=0, tiled=False,
            ).reshape((n_dev * cap,) + rest)

    return xchg


# What the step's last output carries about its exchanges, uniform over
# the mesh: exchanges that took the full-capacity branch, the
# receive-buffer lanes served on all devices together, the valid rows
# that travelled.
EXCHANGE_TELEMETRY = ("overflows", "lanes", "rows")


def tight_bucket(bl: int, n_dev: int, batch_rows: int) -> int:
    """The per-(sender, owner) bucket the window exchange runs at unless
    a pair outgrows it: 2 × the balanced load a pair carries when a batch
    of at most ``batch_rows`` rows is spread evenly over the mesh
    (``ceil(batch_rows / n_dev)`` rows a sender, whatever headroom the
    chunk's width ``bl`` adds), and never more than ``bl``, which holds
    any skew."""
    return min(bl, 2 * -(-(-(-batch_rows // n_dev)) // n_dev))


def owner_route(
    dest: jnp.ndarray,  # int32 [bl] owner device per row
    valid: jnp.ndarray,  # bool [bl]
    n_dev: int,
    axis,
    bl: int,
):
    """Bucketed-``all_to_all`` primitives shared by the sequence and
    expert routed paths: → (send_pos, xchg, scatter).

    ``scatter(x)`` lays local rows into the [n_dev × bl, ...] send buffer
    at their owner bucket; ``xchg`` runs the all_to_all. Buckets are
    worst-case-sized (``bl`` per pair — any skew fits); the window path
    (``exchanged_compute``) instead runs capacity-bounded buffers with a
    skew fallback."""
    send_pos, _ = _route(dest, valid, n_dev)
    xchg = _make_xchg(axis, n_dev, bl)

    def scatter(x, fill=0):
        buf = jnp.full((n_dev * bl,) + x.shape[1:], fill, dtype=x.dtype)
        return buf.at[send_pos].set(x)

    return send_pos, xchg, scatter


def make_sharded_step(
    cfg: Config,
    predict_fn: Callable,
    loss_fn: Optional[Callable] = None,
    online_lr: float = 0.0,
    mesh: Optional[Mesh] = None,
    axis: "str | Tuple[str, ...]" = "data",
    route_customers: bool = False,
    packed: bool = False,
    *,
    batch_rows: int,
):
    """Build the jitted multi-chip step.

    step(feature_state, params, scaler, batch) -> (feature_state, params,
    probs, features[, tier rows in exact mode], exchange); batch leaves
    are [n_dev*B_local] sharded on axis 0. ``exchange``, always last, is
    one int32 ``[3]`` vector (:data:`EXCHANGE_TELEMETRY`), uniform over
    the mesh: how many of this step's exchanges took the full-capacity
    branch (0 when every (sender, owner) pair fitted
    :func:`tight_bucket`), the receive-buffer lanes they served on all
    devices together, and the valid rows that travelled. It is the psum
    the ``lax.cond`` already branches on; the serving engine counts the
    three (``rtfds_exchange_overflow_total``, ``rtfds_exchange_lanes_total``,
    ``rtfds_exchange_rows_total``).

    ``batch_rows`` is the bound on the rows of ONE batch that the caller
    cuts its chunks from (the engine sized ``rows_per_shard`` from it):
    it places the tight bucket, in the owner-placed and the routed
    program alike.

    ``packed=True`` makes the built step take ONE ``[7, n_dev*B_local]``
    int32 array (:func:`~..core.batch.pack_batch` layout) instead of a
    TxBatch pytree — a batch then crosses host→device as a single copy
    (one transfer's fixed overhead instead of seven), and the bitcast
    unpack runs inside the jit before ``shard_map``. The serving engine
    uses this; direct callers that already hold device-side TxBatch
    leaves keep the default.

    ``axis`` may be a single mesh axis name or a tuple of names (e.g.
    ``("dcn", "ici")`` from :func:`.distributed.make_hybrid_mesh`): rows
    shard over the flattened super-axis and every collective runs over the
    pair — cross-host hops ride DCN, intra-host ICI.

    ``route_customers=False`` (the common case) assumes rows are placed on
    their customer-owner device (:func:`partition_batch_spill` chunk 0):
    customer state is touched with zero collectives. ``True`` builds the
    densely-packed spill variant: rows sit on ANY device and customers are
    routed to their owner over ICI exactly like terminals — one extra
    ``all_to_all`` round buys full-mesh utilization under hot keys.
    """
    assert mesh is not None
    n_dev = mesh.devices.size
    fcfg = cfg.features
    exact = fcfg.key_mode == "exact"
    if fcfg.key_mode == "hash":
        # the mesh's layout is owner-modulo at every width, 1 included
        # (ops/hashing.key_slot): it has never hashed
        fcfg = dataclasses.replace(fcfg, key_mode="direct")
    for nm, cap in (("customer", fcfg.customer_capacity),
                    ("terminal", fcfg.terminal_capacity)):
        # key_slot masks with `& (cap_local - 1)`, which is a modulo only
        # for powers of two; a non-pow2 local capacity would silently
        # alias distinct keys' window state.
        cl = cap // n_dev
        if cl <= 0 or (cl & (cl - 1)):
            raise ValueError(
                f"{nm}_capacity / n_devices must be a power of two, "
                f"got {cl}")

    def reduce_grads(g, labeled):
        """Data-parallel SGD: psum'd gradients, so every device applies
        the identical update."""
        return (jax.tree.map(lambda gi: jax.lax.psum(gi, axis) / n_dev, g),
                jnp.any(jax.lax.psum(labeled.astype(jnp.int32), axis) > 0))

    tail = make_tail(cfg, predict_fn, loss_fn, online_lr, reduce_grads)
    def per_device(fstate: FeatureState, f):
        """``f`` over the leaves that carry a leading shard axis ([1, ...]
        local blocks under P(axis): directories, sketch replicas); the
        window columns are flat and shard as they are."""
        return fstate._replace(**{
            name: jax.tree.map(f, getattr(fstate, name))
            for name in ("cms", "customer_dir", "terminal_dir",
                         "terminal_cms")})

    def local_step(fstate: FeatureState, params, scaler: Scaler, batch: TxBatch):
        bl = batch.customer_key.shape[0]

        def exchanged_compute(fn, state, key, fraud):
            """Route (key, day, amount, fraud, valid) to the key's owner
            device, run ``fn(state, key, day, amount, fraud, valid) ->
            (state', mat)`` there, and route ``mat``'s per-row aggregates
            back to the sending rows: → (state', local_mat [bl, K],
            int32 [3] of :data:`EXCHANGE_TELEMETRY` for this exchange).

            Wire format: ONE all_to_all carries the 5 forward fields as
            a packed [*, 5] uint32 matrix (32-bit fields travel as bit
            patterns — all_to_all is pure data movement, bitcasts are
            exact) and ONE carries the result columns back.

            Receive-buffer sizing is the multi-chip scaling lever. A
            bucketed all_to_all with per-(sender,owner) bucket capacity
            ``bl`` is always correct but hands every device an
            [n_dev × bl] buffer — per-device window scatter work then
            equals a SINGLE chip processing the whole batch, so adding
            chips stops helping (measured: the virtual-mesh curve decayed
            ~4× from width 1 → 8). Under the balanced load a uniform key
            hash delivers, a sender holds only its rows ÷ n_dev for each
            owner — so the common case runs with the bucket of
            :func:`tight_bucket`: 2 × the balanced load of the BATCH
            spread evenly over the mesh, not of the chunk's padded width
            (per-device work SHRINKS with width, and does not grow back
            with the chunk's headroom). ``rank`` counts valid rows only,
            so padding fills no bucket. Skew beyond the headroom (a hot
            terminal, a chunk dense to its width) is detected with a
            psum'd overflow flag — uniform across devices, so the
            ``lax.cond`` fallback to the always-correct ``bl``-a-pair
            exchange takes the same branch everywhere and the collectives
            inside stay matched. The branches differ in the bucket alone:
            the rows, their order inside a bucket and the answers are the
            same in both. Exactness is never capacity-dependent.
            """
            if n_dev == 1:
                # Width-1 mesh: every key is owner-local already; the
                # exchange machinery is pure overhead (measured as most
                # of the round-4 29% single-device tax).
                return fn(state, key, batch.day, batch.amount, fraud,
                          batch.valid) + (
                    jnp.zeros(len(EXCHANGE_TELEMETRY), jnp.int32),)
            with _exchange_scope("route"):
                dest = (key % jnp.uint32(n_dev)).astype(jnp.int32)
                # Rank VALID rows only (invalid rows sort into a trailing
                # pseudo-bucket): padding never inflates a valid row's
                # rank into a spurious overflow fallback, never occupies
                # receive slots, and the compact branch's efficiency stops
                # depending on partition_batch_spill's valid-rows-first
                # layout.
                _, rank = _route(
                    jnp.where(batch.valid, dest, n_dev).astype(jnp.int32),
                    batch.valid, n_dev)
            with _exchange_scope("pack"):
                pk = jnp.stack(
                    [
                        key,
                        jax.lax.bitcast_convert_type(batch.day, jnp.uint32),
                        jax.lax.bitcast_convert_type(
                            batch.amount, jnp.uint32),
                        jax.lax.bitcast_convert_type(fraud, jnp.uint32),
                        batch.valid.astype(jnp.uint32),
                    ],
                    axis=1,
                )

            def run(b_pair):
                def go(st):
                    # invalid rows and overflow rows (rank >= b_pair) get
                    # an out-of-bounds position: scatters DROP them (jax
                    # semantics), the back-gather clamps — harmless,
                    # because the capacity branch is only taken when no
                    # VALID row overflows and invalid rows are masked
                    # downstream
                    with _exchange_scope("pack"):
                        pos = jnp.where(
                            batch.valid & (rank < b_pair),
                            dest * b_pair + rank, n_dev * b_pair)
                        send = jnp.zeros((n_dev * b_pair, 5),
                                         jnp.uint32).at[pos].set(pk)
                    xchg = _make_xchg(axis, n_dev, b_pair)
                    r = xchg(send)
                    with _exchange_scope("unpack"):
                        got = (
                            r[:, 0],
                            jax.lax.bitcast_convert_type(r[:, 1], jnp.int32),
                            jax.lax.bitcast_convert_type(r[:, 2],
                                                         jnp.float32),
                            jax.lax.bitcast_convert_type(r[:, 3],
                                                         jnp.float32),
                            r[:, 4].astype(bool),
                        )
                    st, mat = fn(st, *got)
                    back = xchg(mat)
                    with _exchange_scope("unpack"):
                        return st, back[pos]

                return go

            cap_pair = tight_bucket(bl, n_dev, batch_rows)
            with _exchange_scope("route"):
                # ONE psum: the senders with a pair that outgrew the
                # tight bucket, and the valid rows that travel
                over, rows = jax.lax.psum(jnp.stack([
                    (batch.valid & (rank >= cap_pair)).any().astype(
                        jnp.int32),
                    batch.valid.sum(dtype=jnp.int32)]), axis)
                over = over > 0
            if cap_pair < bl:
                state, mat = jax.lax.cond(over, run(bl), run(cap_pair),
                                          state)
            else:  # the tight bucket is the chunk: nothing to choose
                state, mat = run(bl)(state)
            with _exchange_scope("route"):
                lanes = jnp.where(over, bl, cap_pair) * (n_dev * n_dev)
                return state, mat, jnp.stack(
                    [over.astype(jnp.int32), lanes, rows])

        # unstack → reach(customer plane) → reach(terminal plane) → tail
        # → restack. Customers are owner-local (chunk 0: rows placed by
        # customer % n_dev) or routed like terminals (dense spill chunks:
        # rows anywhere); terminals always travel to their owner over ICI.
        # Exact mode ships the same (key, row) wire records: each owner
        # resolves slots through ITS directory and serves admission
        # misses from ITS sketch replica, and the tier counts accumulate
        # OWNER-side (skew is a per-shard property), leaving as a
        # [n_dev, 6] stack (the claim rounds beside them: each device's
        # loops end on its own rows, no collective is in them).
        fstate, c_mat, t_mat, tier, exchange = run_planes(
            per_device(fstate, lambda x: jnp.squeeze(x, 0)),
            batch, fcfg, n_dev,
            reach_customer=exchanged_compute if route_customers else None,
            reach_terminal=exchanged_compute)
        params, probs, feats = tail(params, scaler, batch, c_mat, t_mat)
        fstate = per_device(fstate, lambda x: x[None])
        # exchange: uniform over the mesh (psum'd), leaves replicated
        return (fstate, params, probs, feats) + (
            () if tier is None else (tier[None],)) + (exchange,)

    from real_time_fraud_detection_system_tpu.parallel.mesh import (
        compat_shard_map,
    )

    def _shard_map(f, in_specs, out_specs):
        return compat_shard_map(f, mesh, in_specs, out_specs)

    def spec_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def build(fstate_template, params_template, scaler_template, batch_template):
        from real_time_fraud_detection_system_tpu.core.batch import (
            unpack_batch,
        )

        # specs need only the pytree STRUCTURE; in packed mode the
        # caller's template is the [7, B] array, so synthesize a TxBatch
        batch_t = TxBatch(*([0] * 7)) if packed else batch_template

        def dev_stacked(t):
            # per-shard leaves with a leading device axis (directories,
            # sketch replicas): shard axis 0, one block per device
            return (spec_like(t, P(axis)) if t is not None else None)

        in_specs = (
            FeatureState(
                # flat slot-major columns: a shard is cap/n · NB
                # contiguous entries
                customer=spec_like(fstate_template.customer, P(axis)),
                terminal=spec_like(fstate_template.terminal, P(axis)),
                # Owner-sharded sketch: leading device axis (mesh.py).
                cms=dev_stacked(fstate_template.cms),
                customer_dir=dev_stacked(fstate_template.customer_dir),
                terminal_dir=dev_stacked(fstate_template.terminal_dir),
                terminal_cms=dev_stacked(fstate_template.terminal_cms),
            ),
            spec_like(params_template, P()),
            spec_like(scaler_template, P()),
            spec_like(batch_t, P(axis)),
        )
        out_specs = (
            in_specs[0],
            in_specs[1],
            P(axis),
            P(axis, None),
        ) + ((P(axis, None),) if exact else ()  # [n_dev, 6] tier rows
             ) + (P(),)  # EXCHANGE_TELEMETRY
        fn = _shard_map(local_step, in_specs, out_specs)

        def outer(fstate, params, scaler, batch_in):
            with step_scope("unpack"):
                batch = unpack_batch(batch_in) if packed else batch_in
            # after the four: the tier rows (exact), the exchange's counts
            fstate, params, probs, feats, *extra = fn(
                fstate, params, scaler, batch)
            if selective(cfg):
                # on the GLOBAL arrays outside shard_map (XLA inserts the
                # gather collectives)
                feats = pack_selective(cfg, batch.valid, probs, feats)
            return (fstate, params, probs, feats, *extra)

        return jax.jit(outer, donate_argnums=(0,))

    return build


def make_sharded_compact(
    cfg: Config,
    mesh: Mesh,
    axis: "str | Tuple[str, ...]" = "data",
    demote_slots: int = 0,
):
    """Per-shard recency compaction under ``shard_map`` — the sharded
    twin of the single-chip ``("compact",)`` dispatch variant.

    ``compact(fstate, now_day) -> (fstate', reclaimed [n_dev, 2])``:
    every device runs :func:`~..features.online.compact_feature_state`
    over ITS window-table block and ITS key directory (purely local —
    zero collectives; a shard's dead slots are its own business), and
    the per-shard reclaim counts come back stacked so the engine can
    meter skew per shard. Fixed shapes throughout: one more
    ``DispatchSignature``, AOT-compiled at warmup, never a recompile.

    With ``demote_slots`` > 0 (``features.cold_store`` configured) the
    per-shard compaction also emits its demotion payload — each shard's
    oldest live keys and their exact window rows, gathered BEFORE the
    slots are vacated — stacked on a leading device axis
    (``keys [n_dev, K]``, rows ``[n_dev, K, NB]``) so the engine can
    append every shard's evictions to the host cold store. Routing is
    free: a key demoted by shard *i* re-promotes to shard *i* because
    owner-modulo placement is a pure function of the key.
    """
    from real_time_fraud_detection_system_tpu.features.online import (
        compact_feature_state,
    )
    from real_time_fraud_detection_system_tpu.parallel.mesh import (
        compat_shard_map,
    )

    fcfg = cfg.features
    has_cdir = fcfg.customer_source != "cms"

    def spec_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def _payload_spec():
        # per-table (keys [n_dev, K], bd/cnt/amt/frd [n_dev, K, NB])
        leaf = (P(axis, None),) + (P(axis, None, None),) * 4
        return {
            "customer": leaf if has_cdir else None,
            "terminal": leaf,
        }

    # named as the one-chip programs are (``jit_compact``, ``jit_promote``
    # in a trace's ``XLA Modules`` line): ``jit_outer`` is the mesh's STEP,
    # and a reader that counts step executions by that name would take a
    # pass that falls inside its trace for one
    def compact(fstate: FeatureState, now_day: jnp.ndarray):
        def local(customer, terminal, c_kd, t_kd, day):
            st = FeatureState(
                customer=customer, terminal=terminal, cms=None,
                customer_dir=jax.tree.map(lambda x: jnp.squeeze(x, 0),
                                          c_kd)
                if c_kd is not None else None,
                terminal_dir=jax.tree.map(lambda x: jnp.squeeze(x, 0),
                                          t_kd),
                terminal_cms=None,
            )
            out = compact_feature_state(st, day, fcfg,
                                        demote_slots=demote_slots)
            if demote_slots > 0:
                new, reclaimed, payload = out
            else:
                new, reclaimed = out
            parts = (
                new.customer,
                new.terminal,
                jax.tree.map(lambda x: x[None], new.customer_dir)
                if new.customer_dir is not None else None,
                jax.tree.map(lambda x: x[None], new.terminal_dir),
                reclaimed[None],  # [1, 2] → [n_dev, 2]
            )
            if demote_slots > 0:
                parts += (jax.tree.map(lambda x: x[None], payload),)
            return parts

        dev = P(axis)
        in_specs = (
            spec_like(fstate.customer, dev),
            spec_like(fstate.terminal, dev),
            spec_like(fstate.customer_dir, dev) if has_cdir else None,
            spec_like(fstate.terminal_dir, dev),
            P(),
        )
        out_specs = in_specs[:4] + (P(axis, None),)
        if demote_slots > 0:
            out_specs += (_payload_spec(),)
        fn = compat_shard_map(local, mesh, in_specs, out_specs)
        outs = fn(
            fstate.customer, fstate.terminal,
            fstate.customer_dir if has_cdir else None,
            fstate.terminal_dir, now_day)
        customer, terminal, c_kd, t_kd, reclaimed = outs[:5]
        new_state = fstate._replace(
            customer=customer, terminal=terminal,
            customer_dir=c_kd if has_cdir else fstate.customer_dir,
            terminal_dir=t_kd)
        if demote_slots > 0:
            return new_state, reclaimed, outs[5]
        return new_state, reclaimed

    return jax.jit(compact, donate_argnums=(0,))


def make_sharded_promote(
    cfg: Config,
    mesh: Mesh,
    axis: "str | Tuple[str, ...]" = "data",
):
    """Per-shard cold-tier promotion under ``shard_map`` — the sharded
    twin of the single-chip ``("promote",)`` dispatch variant.

    ``promote(fstate, payload) -> (fstate', stats [n_dev, 2, 4])``: the
    engine groups promoted keys host-side by owner shard (the same
    ``key % n_shards`` modulo the ingest router uses) and pads each
    shard's block to a width of its lane ladder with ``EMPTY_KEY`` (the
    jit retraces a table and a width; ``precompile`` compiles them all),
    so every device
    runs :func:`~..features.online.promote_rows` over ITS block and ITS
    directory — purely local, zero collectives, one fixed shape. Stats
    come back stacked per shard ([admitted, dropped, claim rounds, narrow
    rounds] per table) for the promotion counters.
    """
    from real_time_fraud_detection_system_tpu.features.online import (
        promote_rows,
    )
    from real_time_fraud_detection_system_tpu.parallel.mesh import (
        compat_shard_map,
    )

    fcfg = cfg.features
    has_cdir = fcfg.customer_source != "cms"

    def spec_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def _payload_spec(payload):
        # a payload carries ONE table's lanes (the other is None): the
        # engine dispatches a promote a table and a width of its ladder
        leaf = (P(axis, None),) + (P(axis, None, None),) * 4
        return {t: (leaf if payload.get(t) is not None else None)
                for t in ("customer", "terminal")}

    def promote(fstate: FeatureState, payload):
        def local(customer, terminal, c_kd, t_kd, pay):
            st = FeatureState(
                customer=customer, terminal=terminal, cms=None,
                customer_dir=jax.tree.map(lambda x: jnp.squeeze(x, 0),
                                          c_kd)
                if c_kd is not None else None,
                terminal_dir=jax.tree.map(lambda x: jnp.squeeze(x, 0),
                                          t_kd),
                terminal_cms=None,
            )
            new, stats = promote_rows(
                st, jax.tree.map(lambda x: jnp.squeeze(x, 0), pay),
                fcfg)
            return (
                new.customer,
                new.terminal,
                jax.tree.map(lambda x: x[None], new.customer_dir)
                if new.customer_dir is not None else None,
                jax.tree.map(lambda x: x[None], new.terminal_dir),
                stats[None],  # [1, 2, 4] → [n_dev, 2, 4]
            )

        dev = P(axis)
        in_specs = (
            spec_like(fstate.customer, dev),
            spec_like(fstate.terminal, dev),
            spec_like(fstate.customer_dir, dev) if has_cdir else None,
            spec_like(fstate.terminal_dir, dev),
            _payload_spec(payload),
        )
        out_specs = in_specs[:4] + (P(axis, None, None),)
        fn = compat_shard_map(local, mesh, in_specs, out_specs)
        customer, terminal, c_kd, t_kd, stats = fn(
            fstate.customer, fstate.terminal,
            fstate.customer_dir if has_cdir else None,
            fstate.terminal_dir, payload)
        return fstate._replace(
            customer=customer, terminal=terminal,
            customer_dir=c_kd if has_cdir else fstate.customer_dir,
            terminal_dir=t_kd), stats

    return jax.jit(promote, donate_argnums=(0,))
