"""Multi-chip serving of the sequence (long-context) scorer.

The history state is the easiest of the engine states to shard: it is
keyed ONLY by customer, so with rows partitioned by ``customer % n_dev``
(the same Kafka-partition→device affinity as the window state,
``partition_batch_spill`` chunk 0) every update and gather is device-
local — zero collectives on the common path. Transformer params are
replicated (tiny next to the state).

Hot-key spill chunks place rows on arbitrary devices; those run the
ROUTED variant: (key, day, tod, amount) quadruples ride one
``all_to_all`` to the customer's owner, the owner runs the same fused
history step, and the probabilities ride the inverse ``all_to_all``
back — exactly the terminal-routing pattern of :mod:`.step`.

Cross-chunk semantics match the window state's: a spill chunk sees
prior chunks' state updates, i.e. chunks behave like consecutive
micro-batches. Within any one chunk the fused step time-sorts rows, so
ordering semantics equal the single-chip engine whenever the source
delivers per-customer rows in time order (the Kafka per-partition
guarantee).

Sharded layout: every :class:`~..features.history.HistoryState` leaf
gains a leading device axis ([n_dev, cap_local+1, ...], sharded on it);
each device block is a self-contained local HistoryState (with its own
padding-sink row), so the single-chip kernel runs unchanged inside
``shard_map``. Local slot for key k on its owner:
``ops/hashing.key_slot`` at the mesh's width — the window layout's rule,
requiring ``key_mode="direct"`` here.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from real_time_fraud_detection_system_tpu.config import Config
from real_time_fraud_detection_system_tpu.core.batch import TxBatch
from real_time_fraud_detection_system_tpu.ops.hashing import key_slot
from real_time_fraud_detection_system_tpu.parallel.mesh import (
    compat_shard_map,
)

# NOTE: features.history imports models.sequence, which imports
# parallel.ring_attention — importing history at module top would close
# an import cycle through this package's __init__; defer to call time.


def _stacked_blank(fcfg, n_dev: int, as_jnp: bool):
    """ONE source of truth for the sharded layout: n_dev stacked local
    blocks, each a self-contained HistoryState (own sink row)."""
    import numpy as np

    from real_time_fraud_detection_system_tpu.features.history import (
        init_history_state,
    )

    local = init_history_state(
        dataclasses.replace(
            fcfg, customer_capacity=fcfg.customer_capacity // n_dev))
    if as_jnp:
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_dev,) + a.shape), local)
    return jax.tree.map(
        lambda a: np.broadcast_to(
            np.asarray(a)[None], (n_dev,) + a.shape).copy(), local)


def _require_pow2_local(cap_local: int) -> None:
    """``key_slot`` masks with ``cap_local - 1`` — a modulo only
    when cap_local is a power of two. A non-pow2 local capacity would pass
    the divisibility check yet silently merge distinct customers' history
    (breaking the EXACT elastic-reshard contract), so reject it here."""
    if cap_local <= 0 or (cap_local & (cap_local - 1)):
        raise ValueError(
            f"customer_capacity / n_devices must be a power of two, got "
            f"{cap_local}")


def init_sharded_history_state(
    cfg: Config, mesh: Mesh, axis: "str | tuple" = "data"
):
    """[n_dev, cap_local+1, ...] leaves, sharded on the device axis."""
    n_dev = int(mesh.devices.size)
    fcfg = cfg.features
    if fcfg.customer_capacity % n_dev:
        raise ValueError("customer_capacity must divide by n_devices")
    _require_pow2_local(fcfg.customer_capacity // n_dev)
    if fcfg.key_mode != "direct":
        raise ValueError(
            "sharded sequence serving requires key_mode='direct' "
            "(owner = key % n_dev, local slot = key // n_dev)")
    stacked = _stacked_blank(fcfg, n_dev, as_jnp=True)
    sh = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda a: jax.device_put(a, sh), stacked)


def shard_history_state(
    state, mesh: Mesh, axis: "str | tuple" = "data"
):
    """Re-place an already-stacked state onto the mesh (checkpoint
    restore)."""
    sh = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda a: jax.device_put(a, sh), state)


def reshard_history_state(state, cfg: Config, n_dev_new: int):
    """Elastic re-layout of a history state between device counts.

    In ``direct`` key mode with ids < capacity the maps are bijective
    (single-chip slot = key; sharded owner = key % n, local slot =
    key // n), so conversion is EXACT — restore a single-chip
    checkpoint into an 8-way sharded engine, or re-shard n→m after a
    topology change, with identical serving behavior (SURVEY §5.3's
    elastic-recovery role for the long-context state).

    Accepts either layout (single-chip ``[C+1, ...]`` leaves or stacked
    ``[n, C/n+1, ...]``) and returns host-side arrays in the target
    layout (``n_dev_new == 1`` → single-chip); callers place them on a
    mesh with :func:`shard_history_state`.
    """
    import numpy as np

    from real_time_fraud_detection_system_tpu.features.history import (
        HistoryState,
        init_history_state,
    )

    fcfg = cfg.features
    cap = fcfg.customer_capacity
    if fcfg.key_mode != "direct":
        raise ValueError("elastic re-shard requires key_mode='direct'")

    def to_single(s) -> HistoryState:
        leaves = [np.asarray(a) for a in s]
        if leaves[0].ndim == 3:  # already single-chip [C+1, K, F]
            if leaves[0].shape[0] != cap + 1:
                raise ValueError(
                    f"state capacity {leaves[0].shape[0] - 1} != "
                    f"config capacity {cap}")
            return HistoryState(*leaves)
        n_old = leaves[0].shape[0]
        cap_local = leaves[0].shape[1] - 1
        _require_pow2_local(cap_local)
        if n_old * cap_local != cap:
            raise ValueError(
                f"state layout {n_old}x{cap_local} != config "
                f"capacity {cap} — re-sharding a checkpoint taken under "
                "a different customer_capacity would silently merge or "
                "drop customers")
        single = jax.tree.map(
            np.asarray, init_history_state(fcfg))
        out = [np.array(a) for a in single]
        keys = np.arange(cap)
        owner, local = keys % n_old, key_slot(keys, cap, "direct", n_old)
        for i, a in enumerate(leaves):
            out[i][keys] = a[owner, local]
        return HistoryState(*out)

    single = to_single(state)
    if n_dev_new == 1:
        return HistoryState(*[jnp.asarray(a) for a in single])
    if cap % n_dev_new:
        raise ValueError("customer_capacity must divide by n_dev_new")
    cap_local = cap // n_dev_new
    _require_pow2_local(cap_local)
    out = list(_stacked_blank(fcfg, n_dev_new, as_jnp=False))
    keys = np.arange(cap)
    owner, local = keys % n_dev_new, key_slot(keys, cap, "direct",
                                              n_dev_new)
    for i, a in enumerate(single):
        out[i][owner, local] = np.asarray(a)[keys]
    return HistoryState(*[jnp.asarray(a) for a in out])


def make_sharded_sequence_step(
    cfg: Config,
    mesh: Mesh,
    axis: "str | tuple" = "data",
    route: bool = False,
):
    """→ jitted ``step(hstate, params, batch, order_key) -> (hstate, probs)``.

    ``batch`` leaves are [n_dev * B_local], sharded on axis 0 (the
    engine's partitioned chunk); ``order_key`` [n_dev * B_local] int32
    carries each row's ORIGINAL batch position (the same-second
    tiebreaker — chunk packing and routing both permute rows).
    ``route=False`` expects owner-placed rows; ``route=True`` exchanges
    rows to their owner first and routes probabilities back (spill
    chunks).
    """
    from real_time_fraud_detection_system_tpu.features.history import (
        init_history_state,
        update_and_score,
    )

    n_dev = int(mesh.devices.size)
    fcfg = cfg.features
    cap_local = fcfg.customer_capacity // n_dev
    lcfg = dataclasses.replace(fcfg, customer_capacity=cap_local)


    def slot_fn(key):
        return key_slot(key, fcfg.customer_capacity, "direct", n_dev)

    def local_step(hstate, params, batch: TxBatch, order_key):
        from real_time_fraud_detection_system_tpu.parallel.step import (
            owner_route,
        )

        hs = jax.tree.map(lambda x: jnp.squeeze(x, 0), hstate)
        bl = batch.customer_key.shape[0]

        if route:
            dest = (batch.customer_key % jnp.uint32(n_dev)).astype(jnp.int32)
            send_pos, xchg, scatter = owner_route(
                dest, batch.valid, n_dev, axis, bl)
            rb = TxBatch(
                customer_key=xchg(scatter(batch.customer_key)),
                terminal_key=jnp.zeros(n_dev * bl, jnp.uint32),
                day=xchg(scatter(batch.day)),
                tod_s=xchg(scatter(batch.tod_s)),
                amount=xchg(scatter(batch.amount)),
                label=jnp.full(n_dev * bl, -1, jnp.int32),
                valid=xchg(scatter(batch.valid, fill=False)),
            )
            # the ORIGINAL batch row index rides along as the same-second
            # tiebreaker — both the dense spill packing (round-robin
            # across devices) and the all_to_all regrouping would
            # otherwise reorder ties relative to the single-chip engine
            r_order = xchg(scatter(order_key))
            hs, r_probs = update_and_score(
                hs, params, rb, lcfg, slot_fn, order_key=r_order)
            probs = xchg(r_probs)[send_pos]
        else:
            hs, probs = update_and_score(
                hs, params, batch, lcfg, slot_fn, order_key=order_key)

        return jax.tree.map(lambda x: x[None], hs), probs

    # eval_shape: spec structure without allocating a throwaway state
    state_spec = jax.tree.map(
        lambda _: P(axis),
        jax.eval_shape(lambda: init_history_state(lcfg)))
    batch_spec = jax.tree.map(
        lambda _: P(axis),
        TxBatch(*([0] * len(TxBatch._fields))))
    fn = compat_shard_map(
        local_step,
        mesh,
        # P() prefix: params replicated; order_key sharded like the batch
        (state_spec, P(), batch_spec, P(axis)),
        (state_spec, P(axis)),
    )
    return jax.jit(fn, donate_argnums=(0,))
