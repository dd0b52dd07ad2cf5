"""Device mesh + sharding layout.

The reference's only scale-out axes are Kafka topic partitions and Spark
``local[*]`` cores (SURVEY §2.3). Here the axis is a 1-D ``jax.sharding.Mesh``
over TPU chips: Kafka partition p maps to mesh position p (DCN carries the
consumer traffic to hosts; ICI carries the in-step collectives).

Sharding layout:
- batch rows: sharded along axis 0 ("data") — each device scores the rows
  of its partitions;
- customer window state: sharded along the slot axis — rows arrive
  partitioned by customer key, so a device's rows only touch its own shard
  (no collective needed);
- terminal window state: sharded along the slot axis by terminal-key
  ownership — rows reference terminals owned by other devices, so the step
  exchanges (key, day, amount, fraud) quadruples via ``all_to_all`` on ICI,
  updates/queries on the owner, and returns features by the inverse
  exchange (see :mod:`.step`);
- model params + scaler: replicated (tiny), gradients ``psum``-reduced for
  the online-SGD path.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from real_time_fraud_detection_system_tpu.features.online import (
    FeatureState,
    init_feature_state,
)
from real_time_fraud_detection_system_tpu.ops.hashing import key_row
from real_time_fraud_detection_system_tpu.ops.windows import COLUMNS


def compat_shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication checker off (our specs
    declare replication explicitly; the checker predates several of the
    collectives used here)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(n_devices: int = 0, axis: str = "data") -> Mesh:
    devs = jax.devices()
    if n_devices == 0:
        n_devices = len(devs)
    if n_devices > len(devs):
        raise ValueError(
            f"requested {n_devices} devices, only {len(devs)} visible "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N for "
            f"virtual CPU devices)"
        )
    return Mesh(np.asarray(devs[:n_devices]), (axis,))


def make_local_mesh(n_devices: int = 0, axis: str = "data") -> Mesh:
    """The PROCESS-LOCAL serving mesh: this process's own devices only.

    Identical to :func:`make_mesh` single-process. Under
    ``jax.distributed`` the two diverge — ``jax.devices()`` spans every
    process, and a per-process engine jitting over non-addressable
    devices is exactly the mistake that turns a host-local step into a
    cross-process computation — so multi-host serving builds its mesh
    here (one engine per process, owner exchange on local ICI) and
    leaves :func:`make_process_mesh` to code that has proven the
    backend's cross-process collectives.
    """
    devs = jax.local_devices()
    if n_devices == 0:
        n_devices = len(devs)
    if n_devices > len(devs):
        raise ValueError(
            f"requested {n_devices} local devices, process "
            f"{jax.process_index()} has {len(devs)}"
        )
    return Mesh(np.asarray(devs[:n_devices]), (axis,))


def make_process_mesh(axis: str = "data") -> Mesh:
    """The process-SPANNING 1-D serving mesh: every process's devices,
    ordered so process p's local devices occupy the contiguous block
    ``[p·L, (p+1)·L)`` — the same block the residue ownership of
    :class:`~..runtime.distributed.ProcessTopology` assigns it, so a
    spanning-mesh step and the partitioned per-process deployment agree
    on which device owns which key.

    Computations over this mesh are cross-process collectives (DCN
    between hosts, ICI within): gate on
    :func:`cross_process_collectives_supported` first — CPU jaxlib
    builds without Gloo/MPI refuse them at dispatch, deep inside
    serving, which is the wrong place to find out.
    """
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    return Mesh(np.asarray(devs), (axis,))


def cross_process_collectives_supported(mesh: Mesh) -> Optional[str]:
    """None when the backend can run computations over ``mesh``'s full
    device set; otherwise the backend's capability error string (the
    precise-skip sentinel the multiprocess tests print as ``MPSKIP``).

    Single-process meshes trivially pass. Multi-process, every process
    must call this together (it compiles+runs one tiny SPMD program —
    the cheapest thing that exercises the cross-process dispatch path).
    Only the known capability refusal is swallowed; any other failure
    is a real bug and propagates."""
    if int(jax.process_count()) == 1:
        return None
    import jax.numpy as jnp

    try:
        out = jax.jit(
            lambda: jnp.zeros((int(mesh.devices.size),), jnp.float32),
            out_shardings=NamedSharding(mesh, P(mesh.axis_names[0])),
        )()
        jax.block_until_ready(out)
        return None
    except (RuntimeError, ValueError, NotImplementedError) as e:
        if "Multiprocess computations aren't implemented" in str(e):
            return str(e).splitlines()[-1]
        raise


def init_sharded_feature_state(
    fcfg, mesh: Mesh, axis: "str | tuple[str, ...]" = "data"
) -> FeatureState:
    """A fresh state in this mesh's layout, built where it will live.

    The window tables are created under the mesh's slot-axis sharding:
    each device allocates its own ``capacity / n_dev · n_buckets`` slice
    of every column and no device ever holds a whole one, so a mesh holds
    as many slots as its chips have memory for together (2^24 + 2^25
    slots, 32.2 GB, on four 16 GB chips). Sketches and key directories
    (MBs) are built in the mesh-width layout and placed by
    :func:`shard_feature_state`, which finds the tables already in place.
    One path for every width and key mode, ``n_dev == 1`` included.
    Bit-equal to ``shard_feature_state(init_feature_state(fcfg,
    n_shards=n_dev), mesh)``."""
    state = init_feature_state(
        fcfg, n_shards=int(mesh.devices.size),
        window_sharding=NamedSharding(mesh, P(axis)))
    return shard_feature_state(state, mesh, axis=axis)


def shard_feature_state(
    state: FeatureState, mesh: Mesh, axis: "str | tuple[str, ...]" = "data"
) -> FeatureState:
    """Place a state over the mesh: window tables sharded along the slot
    axis; CMS sharded by customer owner. A leaf that already has the
    mesh's sharding (a state from :func:`init_sharded_feature_state`, or
    one placed before) stays where it is — ``device_put`` to the sharding
    an array has copies nothing — so this is also the cheap "make sure"
    after a restore; a *provided* state (elastic recovery, a checkpoint)
    is spread from wherever it was built.

    The sketch gets a leading device axis ([n_dev, ND, depth, width]):
    rows are partitioned by ``customer_id % n_dev``, so each device keeps
    a private sketch of ITS customers — updates and queries are purely
    device-local (zero collectives on the hot path) and each sketch sees
    ~1/n_dev of the key universe, so collisions (the CMS error term)
    shrink as the mesh grows. A rank-base sketch (single-chip layout,
    e.g. a restored single-chip checkpoint) is broadcast to every device
    as a warm start — estimates stay valid upper bounds.

    ``axis`` may be one mesh axis name or a tuple (hybrid DCN×ICI meshes,
    see :mod:`.distributed`)."""
    dev_sharded = NamedSharding(mesh, P(axis))
    n_dev = int(mesh.devices.size)

    def place_windows(ws):
        # flat slot-major columns: a shard is cap/n · NB contiguous entries
        return jax.tree.map(lambda a: jax.device_put(a, dev_sharded), ws)

    def place_sketch(cms):
        if cms is None:
            return None
        if cms.slice_day.ndim == 1:  # single-chip layout: add device axis
            # Build the per-device replicas shard-by-shard: each device
            # materializes ONE [1, ...] copy of the base sketch — never
            # n_dev copies on a single device (a production sketch is
            # hundreds of MB; broadcasting would OOM exactly when the
            # feature matters).
            def _expand(leaf):
                base = np.asarray(leaf)[None]
                return jax.make_array_from_callback(
                    (n_dev,) + leaf.shape, dev_sharded,
                    lambda idx, b=base: b,
                )

            return jax.tree.map(_expand, cms)
        return jax.tree.map(lambda a: jax.device_put(a, dev_sharded), cms)

    def place_dir(kd, name: str):
        if kd is None:
            return None
        # Per-shard key directories are built stacked ([n_shards, ...]
        # leaves, init_feature_state(n_shards=...)): shapes are
        # layout-carrying, so a width mismatch is detectable here —
        # unlike the window tables, whose permutations are
        # shape-identical.
        if np.ndim(kd.keys) == 1 and n_dev == 1:
            # degenerate mesh: a single-chip directory IS the one
            # shard's directory — adopt it under the stacked layout
            # (shard_map wants the leading shard axis even at width 1)
            kd = jax.tree.map(lambda a: jax.numpy.asarray(a)[None], kd)
        lead = int(np.shape(kd.keys)[0]) if np.ndim(kd.keys) == 2 else 1
        if np.ndim(kd.keys) != 2 or lead != n_dev:
            raise ValueError(
                f"{name} is laid out for {lead} shard(s), mesh has "
                f"{n_dev} — build the state with init_feature_state("
                "n_shards=mesh width) or convert via "
                "reshard_feature_state (pass feature_state_n_old to the "
                "engine)")
        return jax.tree.map(lambda a: jax.device_put(a, dev_sharded), kd)

    return FeatureState(
        customer=place_windows(state.customer),
        terminal=place_windows(state.terminal),
        cms=place_sketch(state.cms),
        customer_dir=place_dir(state.customer_dir, "customer_dir"),
        terminal_dir=place_dir(state.terminal_dir, "terminal_dir"),
        terminal_cms=place_sketch(state.terminal_cms),
    )


def _host_tables(ws):
    """A window state's four columns as host ``[cap, NB]`` arrays (a flat
    slot-major column reshapes to rows for free in NumPy)."""
    return jax.tree.map(np.asarray, ws).tables()


def _layout_perm(cap: int, n_dev: int) -> np.ndarray:
    """Global table row of key k under the n-device owner layout.

    ``ops/hashing.key_row``, the one layout rule: row = k on one chip;
    on a mesh device ``k % n`` owns contiguous rows and places k at local
    slot ``k // n``. A bijection for pow2 cap/n, which the sharded step
    validates."""
    return key_row(np.arange(cap), cap, "direct", n_dev)


def reshard_feature_state(
    state: FeatureState, cfg, n_old: int, n_new: int
) -> FeatureState:
    """Elastic re-layout of the window feature state between device
    counts — the :func:`..parallel.sequence_step.reshard_history_state`
    analogue for the flagship state (SURVEY §5.3 elastic recovery).

    In ``direct`` key mode the slot maps are bijections, so converting a
    single-chip checkpoint into an 8-way layout (or n→m after a topology
    change) is EXACT for the customer/terminal window tables: restore,
    reshard, and serving continues as if the stream had always run at the
    new width. ``exact`` key mode delegates to :func:`_reshard_exact`
    (directory entries re-homed by ``key % n_new``, bit-exact for every
    admitted key). Layouts are positional, so the CALLER states ``n_old``
    (the checkpoint's device count; shapes alone cannot distinguish
    layouts). Returns host-side arrays; place them with
    :func:`shard_feature_state` (or use directly at ``n_new == 1``).

    The CMS is approximate by nature and its conversion preserves the
    upper-bound guarantee rather than exactness: sharded→single merges
    per-slice with the NEWEST day stamp winning (quiet shards whose ring
    lags contribute zero for days they provably never saw — lag-tolerant
    and exact-preserving), which over-counts any replicated warm-start
    base — still a valid CMS upper bound, noted here because it is the
    one non-exact leg. The returned CMS always carries the SINGLE-chip
    layout: :func:`shard_feature_state` expands it per-device at
    placement time (shard-by-shard, so a production-size sketch is never
    replicated n× in host RAM).
    """
    fcfg = cfg.features
    if fcfg.key_mode == "exact":
        return _reshard_exact(state, fcfg, n_old, n_new)
    if fcfg.key_mode != "direct":
        raise ValueError(
            "elastic re-shard requires key_mode='direct' or 'exact' "
            "(hash mode merges colliding keys — a permutation cannot "
            "un-merge them)")
    for n in (n_old, n_new):
        if n < 1:
            raise ValueError(f"device counts must be >= 1, got {n}")
        for name, cap in (("customer", fcfg.customer_capacity),
                          ("terminal", fcfg.terminal_capacity)):
            if cap % n:
                raise ValueError(
                    f"{name}_capacity {cap} must divide by {n}")
            local = cap // n
            if local & (local - 1):
                raise ValueError(
                    f"{name}_capacity / {n} must be a power of two, "
                    f"got {local}")

    def convert(ws, cap: int):
        p_old = _layout_perm(cap, n_old)
        p_new = _layout_perm(cap, n_new)

        def re(table):
            a = np.asarray(table)
            if a.shape[0] != cap:
                raise ValueError(
                    f"state table has {a.shape[0]} rows, config says "
                    f"{cap} — re-sharding a checkpoint taken under a "
                    "different capacity would merge or drop keys")
            out = np.empty_like(a)
            out[p_new] = a[p_old]
            return out

        return type(ws).from_tables(*map(re, _host_tables(ws)))

    cms = _merge_sketch(state.cms, n_old)

    # _replace: the tiered-store fields are None on every DIRECT-mode
    # state (exact mode branched into _reshard_exact above); keep the
    # passthrough so the structure survives whatever is attached
    return state._replace(
        customer=convert(state.customer, fcfg.customer_capacity),
        terminal=convert(state.terminal, fcfg.terminal_capacity),
        cms=cms,
    )


def _merge_sketch(cms, n_old: int):
    """Sharded sketch replicas → ONE single-layout sketch (host-side).

    fraud is Optional (None on every pre-tiering config): merge only the
    tables that exist, keep None as None. The returned sketch always
    carries the SINGLE-chip layout: :func:`shard_feature_state` expands
    it per-device at placement time (shard-by-shard, never n_new host
    copies of a production-size sketch — the OOM its ``_expand`` branch
    exists to avoid).

    Warm-start caveat (pre-existing, restated because exact mode now
    SERVES sketch-tier features): expansion replicates the merged
    sketch to every device so per-device estimates stay upper bounds
    for every key; a LATER merge then sums those n warm-start copies
    plus deltas, so repeated merge→expand cycles inflate pre-cycle
    counts by up to n× per cycle. Still a valid upper bound (the CMS
    contract), and the ring bounds it in time — an inflated slice
    rotates out after ``n_day_buckets`` days of traffic. Dropping the
    replication would break the bound (a key would query an empty
    replica after re-homing), so the inflation is the documented cost
    of elastic reshard on the approximate tier; the dense tier — the
    serving majority — re-homes exactly."""
    if cms is None:
        return None
    leaves = [None if a is None else np.asarray(a) for a in cms]
    if n_old > 1 and leaves[0].ndim > 1:
        if leaves[0].shape[0] != n_old:
            raise ValueError(
                f"cms device axis {leaves[0].shape[0]} != n_old "
                f"{n_old}")
        # Disjoint key partitions make counts additive — but a quiet
        # shard's day ring lags (slices only advance when that device
        # sees traffic for the day). Exact-preserving merge: per
        # slice, take the NEWEST stamp and sum only devices holding
        # it (a stale slice would have been reset when that day
        # arrived there, and its device provably saw no such-day
        # traffic).
        days = leaves[0]  # [n, ND]
        max_day = days.max(axis=0)  # [ND]
        fresh = (days == max_day[None]).astype(leaves[1].dtype)
        return type(cms)(
            max_day,
            *[None if a is None
              else (a * fresh[..., None, None]).sum(axis=0)
              for a in leaves[1:]],
        )
    # already single-layout (n_old == 1, or a prior reshard's
    # deferred-expansion output where only the windows carry the
    # n_old layout)
    return type(cms)(*leaves)


def _rebuild_exact_table(name: str, ctx: str, ws_type, kd_type,
                         keys: np.ndarray, vals: dict,
                         cap: int, n_new: int, n_probes: int):
    """Rebuild one (window table, key directory) pair in the
    ``n_new``-shard layout from extracted live entries — the shared tail
    of :func:`_reshard_exact` (elastic N→M) and
    :func:`merge_process_states` (per-process fleets → one state), so
    the slot discipline cannot diverge between them.

    ``keys`` [K] uint32 (must be unique — ownership means a key lives in
    exactly one source shard/process); ``vals`` maps window-leaf name →
    its [K, ...] gathered rows. Owner = ``key % n_new``, slot ids within
    a shard are assigned in sorted-key order (deterministic: two rebuilds
    of the same entries are byte-identical), directories are rebuilt
    with the same double-hash probe discipline ``admit_slots`` uses at
    serve time. Loud failures, never silent state loss; ``ctx`` names
    the operation in every error."""
    from real_time_fraud_detection_system_tpu.ops.keydir import (
        EMPTY_KEY,
        _probe_positions,
    )
    import jax.numpy as jnp

    cap_local_new = cap // n_new
    owner = (keys % np.uint32(n_new)).astype(np.int64)
    order = np.lexsort((keys, owner))
    owner_s, keys_s = owner[order], keys[order]
    if len(keys_s) > 1 and (keys_s[:-1] == keys_s[1:]).any():
        dup = keys_s[:-1][keys_s[:-1] == keys_s[1:]][:4]
        raise ValueError(
            f"{ctx}: duplicate {name} key(s) {dup.tolist()} across "
            "source shards — the ownership contract places each key in "
            "exactly one shard/process, so a duplicate means two "
            "engines served the same key (partition-affinity breach); "
            "merging would corrupt its window history")
    counts = np.bincount(owner_s, minlength=n_new)
    if counts.max(initial=0) > cap_local_new:
        worst = int(np.argmax(counts))
        raise ValueError(
            f"{ctx}: new shard {worst} would own "
            f"{int(counts[worst])} live {name} keys but holds only "
            f"{cap_local_new} slots — run compaction before shrinking "
            "the mesh, or keep more shards")
    rank = (np.arange(len(owner_s))
            - np.concatenate(([0], np.cumsum(counts)))[owner_s])
    new_rows = owner_s * cap_local_new + rank
    # ---- move the window rows (bit-exact copies) ------------------------
    fills = {"bucket_day": -1, "count": 0.0, "amount": 0.0, "fraud": 0.0}

    def rehome(leaf_name):
        src = np.asarray(vals[leaf_name])[order]
        fresh = np.full((cap,) + src.shape[1:], fills[leaf_name],
                        dtype=src.dtype)
        fresh[new_rows] = src
        return fresh

    ws_new = ws_type.from_tables(**{k: rehome(k) for k in fills})
    # ---- rebuild the per-shard directories ------------------------------
    dir_cap_new = 2 * cap_local_new
    nkeys = np.full((n_new, dir_cap_new), EMPTY_KEY, np.uint32)
    nslots = np.full((n_new, dir_cap_new), -1, np.int32)
    pos = np.asarray(_probe_positions(
        jnp.asarray(keys_s), dir_cap_new, n_probes))  # [K, P]
    flat_keys = nkeys.reshape(-1)
    flat_slots = nslots.reshape(-1)
    placed = np.zeros(len(keys_s), dtype=bool)
    for j in range(n_probes):
        active = ~placed
        if not active.any():
            break
        gpos = owner_s * dir_cap_new + pos[:, j]
        want = active & (flat_keys[gpos] == EMPTY_KEY)
        # scatter-min claim rounds, the np mirror of admit_slots: among
        # same-position racers the smallest key wins (keys are unique
        # per shard, so every key wins exactly one round)
        np.minimum.at(flat_keys, gpos[want], keys_s[want])
        won = want & (flat_keys[gpos] == keys_s)
        flat_slots[gpos[won]] = rank[won].astype(np.int32)
        placed |= won
    if not placed.all():
        miss = int((~placed).sum())
        raise ValueError(
            f"{ctx}: {miss} {name} key(s) could not place within "
            f"{n_probes} probes of the rebuilt directory — raise "
            "keydir_probes or grow the hot tier (admitted-key state "
            "must survive a rebuild bit-exactly, so dropping them is "
            "not an option)")
    free = np.broadcast_to(
        np.arange(cap_local_new - 1, -1, -1, dtype=np.int32),
        (n_new, cap_local_new)).copy()
    kd_new_leaves = dict(
        keys=nkeys, slots=nslots, free=free,
        free_top=(cap_local_new - counts).astype(np.int32))
    if n_new == 1:
        kd_new_leaves = {
            k: (v[0] if k != "free_top" else np.int32(v[0]))
            for k, v in kd_new_leaves.items()}
    return ws_new, kd_type(**kd_new_leaves)


def _extract_exact_table(name: str, ws, kd, n_old: int, cap: int):
    """Live (key, window-row) pairs of one exact-mode table: keys [K],
    vals (leaf name → gathered [K, ...] rows). The extraction half
    shared by reshard and merge."""
    keys = np.asarray(kd.keys)
    slots = np.asarray(kd.slots)
    if keys.ndim == 1:
        keys, slots = keys[None], slots[None]
    if keys.shape[0] != n_old:
        raise ValueError(
            f"{name}_dir is laid out for {keys.shape[0]} shard(s), "
            f"caller says n_old={n_old}")
    tables = _host_tables(ws)
    if tables[0].shape[0] != cap:
        raise ValueError(
            f"state table has {tables[0].shape[0]} rows, config says "
            f"{cap} — re-sharding a checkpoint taken under a "
            "different capacity would merge or drop keys")
    cap_local_old = cap // n_old
    shard_idx, entry_idx = np.nonzero(slots >= 0)
    lkeys = keys[shard_idx, entry_idx]
    old_rows = (shard_idx * cap_local_old
                + slots[shard_idx, entry_idx].astype(np.int64))
    return lkeys, {k: t[old_rows] for k, t in zip(COLUMNS, tables)}


def _reshard_exact(state: FeatureState, fcfg, n_old: int, n_new: int,
                   owner_filter=None) -> FeatureState:
    """Elastic N→M re-home of the TIERED exact state (directories +
    windows + sketches) with bit-exact admitted-key state.

    Unlike direct mode (a fixed layout permutation), exact-mode slot
    placement is dynamic: each shard's directory granted slots in
    admission order. Re-homing therefore works at the (key, window-row)
    level: every live directory entry is extracted, its key's new owner
    is ``key % n_new`` (the SAME modulo the step's owner exchange
    routes by), its window row moves to the new owner's block, and each
    new shard's directory is rebuilt with the same double-hash probe
    discipline ``admit_slots`` uses at serve time. Slot ids within a
    shard are assigned in sorted-key order — deterministic, so two
    reshards of the same checkpoint are byte-identical. Sketches merge
    via the newest-day rule (:func:`_merge_sketch`) and re-expand at
    placement.

    Loud failures, never silent state loss: a new shard whose key set
    exceeds its local slot capacity (ownership skew after shrinking the
    mesh — possible because total occupancy ≤ capacity does not bound
    any single residue class) and a key that cannot place within
    ``keydir_probes`` probes both raise, with the fix named.

    ``owner_filter`` (keys → bool mask): keep only these keys' state —
    the process-adoption path (:func:`adopt_process_slice`): a
    single-process global checkpoint restored into a P-process fleet
    keeps, per process, exactly the residue block it owns.
    """
    n_probes = fcfg.keydir_probes
    ctx = f"elastic reshard {n_old}→{n_new}"
    out = {}
    for name, cap, present in (
            ("customer", fcfg.customer_capacity,
             fcfg.customer_source != "cms"),
            ("terminal", fcfg.terminal_capacity, True)):
        ws = getattr(state, name)
        kd = getattr(state, f"{name}_dir")
        if not present:
            if kd is not None:
                raise ValueError(
                    f"{name}_dir present but customer_source="
                    f"{fcfg.customer_source!r} builds none — the state "
                    "does not match this config")
            out[name] = jax.tree.map(np.asarray, ws)
            out[f"{name}_dir"] = None
            continue
        if kd is None:
            raise ValueError(
                f"key_mode='exact' reshard needs the {name} key "
                "directory; this state carries none (was it built "
                "under a different key_mode?)")
        for n, who in ((n_old, "n_old"), (n_new, "n_new")):
            if n < 1 or cap % n or ((cap // n) & (cap // n - 1)):
                raise ValueError(
                    f"{name}_capacity {cap} / {who}={n} must be a "
                    "power of two")
        lkeys, vals = _extract_exact_table(name, ws, kd, n_old, cap)
        if owner_filter is not None:
            keep = np.asarray(owner_filter(lkeys), dtype=bool)
            lkeys = lkeys[keep]
            vals = {k: v[keep] for k, v in vals.items()}
        out[name], out[f"{name}_dir"] = _rebuild_exact_table(
            name, ctx, type(ws), type(kd), lkeys, vals,
            cap, n_new, n_probes)
    return state._replace(
        customer=out["customer"], terminal=out["terminal"],
        cms=_merge_sketch(state.cms, n_old),
        customer_dir=out["customer_dir"],
        terminal_dir=out["terminal_dir"],
        terminal_cms=_merge_sketch(state.terminal_cms, n_old),
    )


def adopt_process_slice(state: FeatureState, cfg, n_old: int, topology
                        ) -> FeatureState:
    """A single-process GLOBAL feature state (checkpoint written by a
    1-process deployment at ``n_old`` devices) → THIS process's local
    layout — the 1→P leg of multi-host elastic topology changes,
    routed through the same exact re-home machinery as every other
    reshard.

    Exact mode keeps only the keys whose residue block this process
    owns (``topology.owns``, bit-exact for every owned admitted key;
    unowned keys simply move to their own process's adoption of the
    same checkpoint). Direct mode keeps the full tables: unowned slots
    are inert — their keys never arrive on this process, and the
    direct-mode contract (keys < capacity) means they alias nothing an
    owned key probes. Sketches merge to the single layout and stay
    whole (a CMS upper bound holds for every key, owned or not).
    Returns host-side arrays in the stacked local layout."""
    fcfg = cfg.features
    if fcfg.key_mode == "exact":
        return _reshard_exact(state, fcfg, n_old, topology.local_devices,
                              owner_filter=topology.owns)
    return reshard_feature_state(state, cfg, n_old,
                                 topology.local_devices)


def merge_process_states(states, cfg, n_locals) -> FeatureState:
    """Merge a P-process fleet's per-process feature states into ONE
    single-chip-layout global state — the P→1 leg of multi-host
    topology changes (shrink/regrow the fleet: merge every process's
    final checkpoint, then restore the merged state at the new
    topology, where :func:`adopt_process_slice` re-slices it).

    ``n_locals[i]``: process i's local device count (its state's shard
    layout). Exact mode extracts every process's live (key, window-row)
    entries — disjoint by the ownership contract, loudly verified — and
    rebuilds the global directory through the same
    :func:`_rebuild_exact_table` tail as elastic reshard. Direct mode
    combines row-wise by residue ownership (row r holds key ≡ r mod
    capacity under the direct layout, so each row's authoritative copy
    is its owner process's; requires a homogeneous fleet and
    capacity % (P·L) == 0). Hash mode cannot merge (colliding keys
    cannot be attributed to owners) and refuses, like elastic reshard.
    Sketches merge per-process then across processes under the
    newest-day rule (upper bounds preserved). Returns host arrays."""
    fcfg = cfg.features
    if not states or len(states) != len(n_locals):
        raise ValueError(
            f"merge_process_states: {len(states)} state(s) vs "
            f"{len(n_locals)} n_locals")
    n_proc = len(states)
    if n_proc == 1:
        return reshard_feature_state(states[0], cfg, n_locals[0], 1)
    if fcfg.key_mode == "hash":
        raise ValueError(
            "process merge requires key_mode='direct' or 'exact' (hash "
            "mode merges colliding keys — rows cannot be attributed to "
            "their owner process)")

    def merge_cms(getter):
        per = []
        for st, n_loc in zip(states, n_locals):
            m = _merge_sketch(getter(st), n_loc)
            if m is None:
                return None
            per.append(m)
        stacked = type(per[0])(*[
            None if any(le is None for le in leaves)
            else np.stack([np.asarray(le) for le in leaves])
            for leaves in zip(*per)])
        return _merge_sketch(stacked, n_proc)

    if fcfg.key_mode == "exact":
        out = {}
        for name, cap, present in (
                ("customer", fcfg.customer_capacity,
                 fcfg.customer_source != "cms"),
                ("terminal", fcfg.terminal_capacity, True)):
            if not present:
                # customer_source="cms": the table is dead weight (the
                # sketch serves the features) — any process's copy is as
                # good as any other's
                out[name] = jax.tree.map(
                    np.asarray, getattr(states[0], name))
                out[f"{name}_dir"] = None
                continue
            keys_all, vals_all = [], []
            ws = kd = None
            for pid, (st, n_loc) in enumerate(zip(states, n_locals)):
                ws, kd = getattr(st, name), getattr(st, f"{name}_dir")
                if kd is None:
                    raise ValueError(
                        f"process {pid}'s state carries no {name} key "
                        "directory (was it built under a different "
                        "key_mode?)")
                k, v = _extract_exact_table(name, ws, kd, n_loc, cap)
                keys_all.append(k)
                vals_all.append(v)
            keys = np.concatenate(keys_all)
            vals = {k: np.concatenate([v[k] for v in vals_all])
                    for k in vals_all[0]}
            out[name], out[f"{name}_dir"] = _rebuild_exact_table(
                name, f"process merge {n_proc}→1", type(ws), type(kd),
                keys, vals, cap, 1, fcfg.keydir_probes)
        return states[0]._replace(
            customer=out["customer"], terminal=out["terminal"],
            cms=merge_cms(lambda s: s.cms),
            customer_dir=out["customer_dir"],
            terminal_dir=out["terminal_dir"],
            terminal_cms=merge_cms(lambda s: s.terminal_cms))

    # direct mode: fixed layout permutations; merge row-wise by residue
    # ownership (row r ↔ key r under the single-chip direct layout)
    if len(set(int(n) for n in n_locals)) != 1:
        raise ValueError(
            "direct-mode process merge needs a homogeneous fleet (every "
            f"process the same local width), got n_locals={list(n_locals)}"
            " — exact mode re-homes by stored key and has no such limit")
    n_local = int(n_locals[0])
    n_total = n_proc * n_local
    singles = [reshard_feature_state(st, cfg, n_local, 1)
               for st in states]

    def combine(name, cap):
        if cap % n_total:
            raise ValueError(
                f"direct-mode process merge needs {name}_capacity {cap} "
                f"divisible by n_processes×local_devices = {n_total} "
                "(row residue = key residue is what attributes each row "
                "to its owner)")
        owner = (np.arange(cap) % n_total) // n_local
        ws0 = getattr(singles[0], name)

        def one(*tables):  # one column's [cap, NB] table per process
            merged = np.empty_like(tables[0])
            for p in range(n_proc):
                m = owner == p
                merged[m] = tables[p][m]
            return merged

        return type(ws0).from_tables(*map(
            one, *(_host_tables(getattr(s, name)) for s in singles)))

    return states[0]._replace(
        customer=combine("customer", fcfg.customer_capacity),
        terminal=combine("terminal", fcfg.terminal_capacity),
        cms=merge_cms(lambda s: s.cms),
    )


def reshard_engine_state(kind: str, state, cfg, n_old: int, n_new: int,
                         stacked: bool = False):
    """Kind-dispatched elastic reshard: window feature state vs sequence
    history state — the ONE conversion path every engine entry point
    uses, so the semantics cannot diverge between call sites.

    ``stacked``: return the ``[n, ...]`` stacked layout even at
    ``n_new == 1`` (the sharded sequence step's form; the single-chip
    engine wants the flat layout). Returns host-side arrays; callers
    place them (``shard_feature_state`` / ``shard_history_state`` or a
    plain ``jnp.asarray`` tree-map).
    """
    if kind == "sequence":
        from real_time_fraud_detection_system_tpu.parallel.sequence_step import (
            reshard_history_state,
        )

        st = reshard_history_state(state, cfg, n_new)
        if stacked and n_new == 1:
            st = jax.tree.map(lambda a: jax.numpy.asarray(a)[None], st)
        return st
    return reshard_feature_state(state, cfg, n_old, n_new)
