"""Multi-chip streaming engine: the sharded serving loop.

Round 1 proved the sharded *step* (``parallel/step.py``: customer-sharded
window state, terminal ``all_to_all`` exchange, psum'd online SGD) on
single dry-run steps; this module makes it a *serving engine* — the same
source → dedup → step → sink → checkpoint stream contract as
:class:`~.engine.ScoringEngine`, but the step runs under ``shard_map``
over a ``jax.sharding.Mesh``. This is the TPU-native analogue of the
reference's scaled-out deployment (8-partition Kafka stream feeding
parallel Spark executors, SURVEY §2.3 items 1-2;
``fraud_detection.py:204-211`` is the loop being replaced).

Row → device placement is ``customer_id % n_devices`` (the broker's
key-hash partition analogue), computed host-side by
:func:`~..parallel.step.partition_batch_spill`; a hot-key shard overflow
spills into follow-on sub-steps instead of failing the stream.

``key_mode="exact"`` (the tiered device-resident feature store) serves
sharded too: ownership keeps the stable modulo above, but the slot
WITHIN a shard comes from that shard's private key directory —
per-shard ``keydir`` + hot tier + sketch replica, per-shard recency
compaction as the ``("compact",)`` dispatch variant, and per-shard
tier/occupancy telemetry (the ``shard`` label). With each shard's hot
tier sized to hold its keys, sharded exact is bit-identical to
single-engine exact (tests/test_sharded_exact.py).

The engine inherits the single-chip engine's run loop, feedback-SGD path,
and feature-cache plumbing; it overrides batch processing (partition →
sharded step → re-assemble) and state feedback (the terminal table lives
in owner-partitioned layout: global row = owner * cap_local + local_slot).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from real_time_fraud_detection_system_tpu.config import Config
from real_time_fraud_detection_system_tpu.core.batch import (
    fold_key,
    make_batch,
    pack_batch,
)
from real_time_fraud_detection_system_tpu.core.batch import bucket_size
from real_time_fraud_detection_system_tpu.features.online import (
    apply_feedback_at_slot,
)
from real_time_fraud_detection_system_tpu.features.spec import N_FEATURES
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.ops.dedup import (
    latest_wins_mask_host,
)
from real_time_fraud_detection_system_tpu.ops.hashing import key_row
from real_time_fraud_detection_system_tpu.parallel.mesh import (
    init_sharded_feature_state,
    make_mesh,
    shard_feature_state,
)
from real_time_fraud_detection_system_tpu.parallel.step import (
    make_sharded_step,
    partition_batch_spill,
)
from real_time_fraud_detection_system_tpu.runtime.engine import (
    BatchResult,
    ScoringEngine,
    blank_lanes,
    one_table_payload,
)
from real_time_fraud_detection_system_tpu.utils.xla_telemetry import (
    step_signature,
)


class ShardedScoringEngine(ScoringEngine):
    """Streaming engine over an n-device mesh.

    Same interface as :class:`ScoringEngine` (``process_batch`` /
    ``run`` / ``apply_feedback`` / ``apply_state_feedback`` / checkpoint
    state), so sources, sinks, the feedback loop, and
    :func:`~.faults.run_with_recovery` compose unchanged.

    ``rows_per_shard`` fixes the per-device step width (static shapes keep
    the jit cache to ONE entry); a micro-batch is absorbed as
    ceil(max_shard_load / rows_per_shard) sub-steps.
    """

    _FUSED_STEP = False  # the mesh step takes the predict swap only

    def __init__(
        self,
        cfg: Config,
        kind: str,
        params,
        scaler: Scaler,
        mesh: Optional[Mesh] = None,
        n_devices: int = 0,
        rows_per_shard: int = 0,
        axis: "str | tuple" = "data",
        online_lr: float = 0.0,
        feature_cache=None,
        feature_state=None,
        feature_state_n_old: Optional[int] = None,
        metrics=None,
        dead_letter=None,
        topology=None,
    ):
        """``feature_state``: a pre-built state for elastic recovery of a
        checkpoint taken at a different device count. Pass
        ``feature_state_n_old`` (the checkpoint's device count; 1 for a
        single-chip checkpoint) and the engine reshards it to THIS mesh
        itself via :func:`~.parallel.mesh.reshard_feature_state` /
        :func:`~.parallel.sequence_step.reshard_history_state` — the
        safest path, since window layouts are shape-identical
        permutations that nothing else can tell apart. Omit
        ``feature_state_n_old`` only when the state is already in this
        mesh's layout. Default: fresh state.

        ``topology``: this process's place in a multi-host fleet
        (:class:`~.distributed.ProcessTopology`). The engine itself runs
        UNCHANGED — ingest affinity guarantees every polled key's
        residue is local, so the local ``key % n_dev`` placement equals
        the global layout's (the residue-block construction) — but the
        mesh is built from the process's OWN devices (a fleet under
        ``jax.distributed`` sees every process's devices in
        ``jax.devices()``), shard telemetry carries global shard ids +
        a ``process`` label, strict ingest refuses rows this process
        does not own, and checkpoints stamp the writer's topology."""
        if topology is not None and kind == "sequence":
            raise ValueError(
                "multi-host serving is not wired for kind='sequence' "
                "(history-state process adoption does not exist yet); "
                "serve the sequence scorer single-process")
        if cfg.features.key_bits == 64:
            # No path folds a wide id silently: the owner exchange, the
            # stacked per-device directories and the multi-process
            # fleet's ownership (key % n) all carry one-word keys.
            raise ValueError(
                "key_bits=64 is not wired for the sharded engine "
                "(--devices > 1 / multi-host): its owner exchange and "
                "per-device directories carry one-word keys. Serve "
                "64-bit ids on one chip with key_mode='exact', or keep "
                "key_bits=32 on the mesh (ROADMAP B14)")
        if cfg.runtime.nan_guard:
            # The sharded step donates state inside shard_map and a batch
            # spans several chunk steps — there is no pre-batch anchor to
            # roll back to. Poison/non-finite isolation for mesh serving
            # goes through the supervisor's bisection path instead
            # (run_with_recovery --dead-letter), which replays whole
            # batches through process_batch.
            raise ValueError(
                "runtime.nan_guard is not wired for the sharded engine; "
                "serve single-chip with --nan-guard, or rely on the "
                "supervisor's crash-loop bisection (--dead-letter)")
        if mesh is None:
            if topology is not None:
                # multi-host: THIS process's devices only — jax.devices()
                # spans the fleet under jax.distributed, and a mesh over
                # non-addressable devices turns every step into a
                # cross-process computation
                from real_time_fraud_detection_system_tpu.parallel.mesh \
                    import make_local_mesh

                mesh = make_local_mesh(
                    n_devices or topology.local_devices)
            else:
                mesh = make_mesh(n_devices)
        n_mesh = int(mesh.devices.size)
        if topology is not None and n_mesh != topology.local_devices:
            raise ValueError(
                f"mesh is {n_mesh} device(s) wide but the topology says "
                f"this process serves {topology.local_devices} — the "
                "residue-block ownership is sized n_processes × "
                "local_devices, so the two must agree")
        # state_bytes accounting needs the width BEFORE the base
        # constructor runs its budget check / bytes gauges; topology
        # likewise (the state-telemetry override labels per-shard series
        # with global shard ids inside the base constructor)
        self.topology = topology
        self.n_dev = n_mesh
        exact = cfg.features.key_mode == "exact" and kind != "sequence"
        if exact:
            # Per-shard tiered store: validate the partition up front
            # (the base class would only catch it after building state).
            for nm in ("customer_capacity", "terminal_capacity"):
                cap = getattr(cfg.features, nm)
                local = cap // n_mesh if cap % n_mesh == 0 else 0
                if local <= 0 or (local & (local - 1)):
                    raise ValueError(
                        f"key_mode='exact' on a {n_mesh}-wide mesh needs "
                        f"{nm} / n_devices to be a power of two, got "
                        f"{cap} / {n_mesh}")
        if exact and feature_state is not None \
                and feature_state_n_old is None:
            # Exact-mode layouts are shape-carrying (stacked per-shard
            # directories), so a mislaid state is detectable — refuse
            # with the fix named instead of serving split key histories.
            kd = feature_state.terminal_dir
            # metadata only — .ndim/.shape exist on numpy AND jax
            # arrays, so no device-to-host copy of a possibly-huge
            # directory leaf just to read its layout
            lead = (int(kd.keys.shape[0])
                    if kd is not None
                    and getattr(kd.keys, "ndim", 1) == 2 else 1)
            if kd is None or lead != n_mesh:
                raise ValueError(
                    f"provided exact feature_state is laid out for "
                    f"{lead} shard(s), mesh has {n_mesh} — pass "
                    "feature_state_n_old to let the engine re-home the "
                    "directory entries (elastic reshard)")
        if feature_state is not None and feature_state_n_old is not None:
            from real_time_fraud_detection_system_tpu.parallel.mesh import (
                reshard_engine_state,
            )

            feature_state = reshard_engine_state(
                kind, feature_state, cfg, feature_state_n_old, n_mesh,
                stacked=True)
        elif feature_state is not None and kind != "sequence":
            # Claimed mesh layout: cross-check what little IS checkable
            # (layout permutations are shape-identical, so only a
            # device-axis-carrying CMS betrays a width mismatch).
            cms = feature_state.cms
            if cms is not None and np.asarray(cms.slice_day).ndim > 1 \
                    and np.asarray(cms.slice_day).shape[0] != n_mesh:
                raise ValueError(
                    f"feature_state CMS is laid out for "
                    f"{np.asarray(cms.slice_day).shape[0]} devices, mesh "
                    f"has {n_mesh} — pass feature_state_n_old to let the "
                    "engine reshard it")
        pre_state = None
        if kind == "sequence" and feature_state is not None:
            from real_time_fraud_detection_system_tpu.parallel.sequence_step import (
                shard_history_state,
            )

            pre_state = shard_history_state(feature_state, mesh, axis=axis)
        elif kind == "sequence":
            # build the owner-sharded state FIRST and hand it to the base
            # constructor — a throwaway full-size single-chip HistoryState
            # would transiently double the state's HBM footprint
            from real_time_fraud_detection_system_tpu.parallel.sequence_step import (
                init_sharded_history_state,
            )

            pre_state = init_sharded_history_state(cfg, mesh, axis=axis)
        if kind != "sequence":
            # The base constructor never builds the state: its fresh one
            # is the whole table on ONE device, which a mesh that holds
            # more than a chip's worth cannot allocate
            # (RESOURCE_EXHAUSTED at 2^24 + 2^25 slots on four 16 GB
            # chips). A provided state is handed through as it is; a
            # fresh one is created already spread over the mesh, in the
            # mesh-width layout (exact mode's per-shard directories
            # included) — every key mode, every width.
            pre_state = feature_state
            if pre_state is None:
                pre_state = init_sharded_feature_state(
                    cfg.features, mesh, axis=axis)
        self.mesh = mesh  # _build_step, inside the base constructor
        self.axis = axis
        super().__init__(
            cfg, kind, params, scaler, feature_state=pre_state,
            online_lr=online_lr, feature_cache=feature_cache,
            metrics=metrics, dead_letter=dead_letter,
        )
        self.state.layout_devices = self.n_dev
        if self.topology is not None:
            # the writer's topology travels WITH the state: a per-process
            # checkpoint holds only its residue block's keys
            self.state.process_count = self.topology.n_processes
            self.state.process_id = self.topology.process_id
        # Mesh-level telemetry: per-shard row placement (imbalance is THE
        # sharded-serving failure mode worth watching), replicated-leaf
        # commits, and sharded-step (re)builds — a retrace inside the
        # serving loop costs ~1 s and should be visible, not inferred.
        self._m_shard_rows = [
            self.metrics.gauge(
                "rtfds_shard_rows",
                "rows routed to this shard in the last batch",
                **self._shard_labels(i))
            for i in range(self.n_dev)
        ]
        self._m_commits = self.metrics.counter(
            "rtfds_replicated_commits_total",
            "params/scaler trees committed to the mesh (each avoided a "
            "silent in-loop retrace)")
        self._m_step_builds = self.metrics.counter(
            "rtfds_sharded_step_builds_total",
            "sharded step compilations (local + routed variants)")
        # What the mesh adds to a batch, summed over a run (the gauges
        # above show the last batch only): the two host phases inside
        # host_prep / result_wait, the chunks a batch became, the slots
        # the devices computed on against the rows in them (the rest is
        # padding), the fullest shard's rows and the mean shard's
        # (imbalance = max ÷ mean), and what the step's exchanges ran at:
        # the receive-buffer lanes they served against the rows in them,
        # and how often one outgrew its buckets.
        self._m_phase.update({
            ph: self.metrics.histogram(
                "rtfds_phase_seconds",
                "per-batch loop-time decomposition by phase", phase=ph)
            for ph in ("partition", "assemble")
        })
        self._m_chunks = {
            routed: self.metrics.counter(
                "rtfds_shard_chunks_total",
                "chunk steps dispatched (routed=1: a dense spill chunk "
                "whose customers travel to their owner)",
                routed=str(int(routed)))
            for routed in (False, True)
        }
        self._m_slots = self.metrics.counter(
            "rtfds_shard_slots_total",
            "row slots dispatched to the mesh (n_devices x rows_per_shard "
            "a chunk)")
        self._m_valid_rows = self.metrics.counter(
            "rtfds_shard_valid_rows_total",
            "rows in those slots; the rest is padding every device "
            "computes on")
        self._m_rows_max = self.metrics.counter(
            "rtfds_shard_rows_max_total",
            "rows of each batch's fullest shard, summed")
        self._m_rows_mean = self.metrics.counter(
            "rtfds_shard_rows_mean_total",
            "each batch's rows / n_devices, summed (max / mean = "
            "imbalance, at whatever width each batch was served)")
        # one counter a field of parallel/step.EXCHANGE_TELEMETRY
        self._m_xchg = (
            self.metrics.counter(
                "rtfds_exchange_overflow_total",
                "exchanges that took the full-capacity branch: a (sender, "
                "owner) pair held more rows than its bucket"),
            self.metrics.counter(
                "rtfds_exchange_lanes_total",
                "receive-buffer lanes the exchanges served, all devices "
                "(n_devices^2 x the bucket each ran at)"),
            self.metrics.counter(
                "rtfds_exchange_rows_total",
                "valid rows that travelled in those lanes; the rest is "
                "padding every owner computes on"),
        )
        # Commit replicated leaves (params, scaler) to the mesh NOW: the
        # step's out_specs return them mesh-committed, so leaving the
        # build-time copies on the default device makes the SECOND step
        # call see different input shardings and silently retrace — ~1 s
        # of recompile paid inside the serving loop (measured: the first
        # post-warmup batch at width 1 cost 969 ms vs 8 ms steady-state).
        self._commit_replicated()
        if cfg.features.customer_capacity % self.n_dev:
            raise ValueError("customer_capacity must divide by n_devices")
        # Default: 2× the balanced per-device load, so ordinary partition
        # imbalance stays in ONE chunk (shared by both engine kinds).
        self.rows_per_shard = rows_per_shard or max(
            2 * -(-cfg.runtime.max_batch_rows // self.n_dev), 16
        )
        if kind == "sequence":
            # Long-context serving over the mesh: customer-owner-sharded
            # history state, same partition/spill machinery, routed spill
            # chunks exchange rows to their owner over ICI.
            from real_time_fraud_detection_system_tpu.parallel.sequence_step import (
                make_sharded_sequence_step,
            )

            # feature_state is already the owner-sharded HistoryState
            # (pre_state above)
            self._seq_step = make_sharded_sequence_step(
                cfg, self.mesh, axis=self.axis)
            self._seq_step_routed = make_sharded_sequence_step(
                cfg, self.mesh, axis=self.axis, route=True)
            return
        if cfg.features.terminal_capacity % self.n_dev:
            raise ValueError("terminal_capacity must divide by n_devices")
        # a fresh state was created on the mesh (above) and stays where
        # it is; a provided one is spread over the mesh here
        self.state.feature_state = shard_feature_state(
            self.state.feature_state, self.mesh, axis=self.axis,
        )
        self._sharded_sf = None
        self._sharded_sf_exact = None
        if self._exact:
            # replace the base class's single-chip compaction jit with
            # the shard_map'd per-shard pass (same ("compact",) dispatch
            # key, same donation, per-shard reclaim counts out)
            from real_time_fraud_detection_system_tpu.parallel.step import (
                make_sharded_compact,
                make_sharded_promote,
            )

            self._compact = make_sharded_compact(
                cfg, self.mesh, axis=self.axis,
                demote_slots=self._demote_slots)
            if self._demote_slots:
                # and the promote-merge's sharded twin: owner-grouped
                # payload blocks, purely shard-local admission
                self._promote = make_sharded_promote(cfg, self.mesh,
                                                     axis=self.axis)

    def _build_step(self) -> None:
        """The mesh's two step builders in the one-chip step's place:
        owner-placed rows, and the dense-spill variant (customers routed
        to their owner like terminals; compiled lazily on the first
        hot-key overflow). Both are built on their first batch (they need
        templates). ``self._predict``, not a fresh ``predict_fn_for``:
        the base constructor may have swapped in the Pallas tree scorer
        (use_pallas) and the mesh must serve the same kernel."""
        self._sharded_build, self._sharded_build_routed = (
            make_sharded_step(
                self.cfg, self._predict, loss_fn=self._loss,
                online_lr=self.online_lr, mesh=self.mesh, axis=self.axis,
                route_customers=routed,
                packed=True,  # one H2D copy per chunk (see _start_batch)
                # what rows_per_shard was sized from: the exchange's
                # bucket follows the batch, not the chunk's headroom
                batch_rows=self.cfg.runtime.max_batch_rows,
            ) for routed in (False, True))
        self._sharded_step = None
        self._sharded_step_routed = None

    # -- per-shard feature-state telemetry ---------------------------------

    def _state_shards(self) -> int:
        # set before super().__init__ so the base budget check and bytes
        # gauges account the per-device sketch replicas
        return int(getattr(self, "n_dev", 1) or 1)

    def _shard_labels(self, local_shard: int) -> dict:
        """Label set of per-shard series: single-process keeps the
        historical ``shard=<local>``; a fleet labels GLOBALLY
        (``shard = shard_offset + local``, matching the shard id the
        single (P·L)-device engine would use for the same keys) and adds
        the ``process`` label, so a coordinator-side aggregation over
        every worker's registry reads as ONE engine's shard space."""
        topo = getattr(self, "topology", None)
        if topo is None or topo.n_processes <= 1:
            return {"shard": str(local_shard)}
        return {"shard": str(topo.shard_offset + local_shard),
                "process": str(topo.process_id)}

    def _init_state_telemetry(self) -> None:
        """Base series (the healthz/global view) PLUS the per-shard
        breakdown — skew is the failure mode modulo ownership hides, so
        every tier/occupancy/reclaim series also exists with a
        ``shard`` label."""
        super()._init_state_telemetry()
        self._m_tier_shard = None
        self._m_slots_occ_shard = None
        self._m_slots_rec_shard = None
        self._m_claim_rounds_shard = None
        if not self._exact:
            return
        reg = self.metrics
        n = self._state_shards()
        fcfg = self.cfg.features
        tables = [t for t, present in
                  (("customer", fcfg.customer_source != "cms"),
                   ("terminal", True)) if present]
        self._m_tier_shard = {
            (t, s): reg.counter(
                "rtfds_feature_tier_rows_total",
                "row x keyspace feature reads served per tier "
                "(dense = private hot-tier slot; cms = count-min "
                "sketch fallback after an admission miss)",
                tier=t, **self._shard_labels(s))
            for t in ("dense", "cms") for s in range(n)
        }
        self._m_slots_occ_shard = {
            (t, s): reg.gauge(
                "rtfds_feature_slots_occupied",
                "hot-tier slots currently owned by a key "
                "(updated at compaction cadence)",
                table=t, **self._shard_labels(s))
            for t in tables for s in range(n)
        }
        self._m_slots_rec_shard = {
            (t, s): reg.counter(
                "rtfds_feature_slots_reclaimed_total",
                "hot-tier slots reclaimed by recency compaction "
                "(the slot held only history older than "
                "delay + max(window))",
                table=t, **self._shard_labels(s))
            for t in tables for s in range(n)
        }
        # A name of its own, not a ``shard`` label on the table-level
        # series: a reader that sums every series of a name
        # (rounds ÷ batches) would count each round twice.
        self._m_claim_rounds_shard = {
            (t, s): reg.counter(
                "rtfds_keydir_shard_claim_rounds_total",
                "claim rounds this shard's admit ran in the step (each "
                "device's loop ends on its own rows; the step ends when "
                "the slowest has): the fullest shard against the mean "
                "says whether one chip's admission holds the mesh",
                table=t, **self._shard_labels(s))
            for t in tables for s in range(n)
        }
        for t in tables:
            local = getattr(fcfg, f"{t}_capacity") // n
            for s in range(n):
                reg.gauge(
                    "rtfds_feature_slots_capacity",
                    "hot-tier slots this shard's directory can grant "
                    "(table capacity / n_devices): what "
                    "rtfds_feature_slots_occupied fills",
                    table=t, **self._shard_labels(s)).set(local)

    def _record_compaction(self, fstate, reclaimed) -> None:
        """Per-shard compaction metering: ``reclaimed`` arrives
        ``[n_dev, 2]`` ([customer, terminal] per shard) from the
        shard_map'd pass; occupancy reads come from the stacked
        ``free_top`` leaves. The base (table-level) series are fed the
        shard sums, so the single-chip healthz/dashboard contracts hold
        unchanged on the mesh."""
        rec = np.asarray(reclaimed)  # [n_dev, 2]
        occupied = {}
        occupied_per_shard = [0] * self.n_dev
        cap_total = 0
        for i, table in enumerate(("customer", "terminal")):
            if table in (self._m_slots_rec or {}):
                self._m_slots_rec[table].inc(int(rec[:, i].sum()))
            if table in (self._m_sweeps or {}):
                # each device sweeps its own directory
                self._m_sweeps[table].inc(int((rec[:, i] > 0).sum()))
            kd = getattr(fstate, f"{table}_dir")
            if kd is None:
                continue
            cap_local = int(kd.free.shape[1])
            cap_total += cap_local * self.n_dev
            tops = np.asarray(kd.free_top)  # [n_dev]
            occ_t = 0
            for s in range(self.n_dev):
                occ = cap_local - int(tops[s])
                occ_t += occ
                occupied_per_shard[s] += occ
                if self._m_slots_occ_shard is not None:
                    self._m_slots_occ_shard[(table, s)].set(occ)
                if self._m_slots_rec_shard is not None:
                    self._m_slots_rec_shard[(table, s)].inc(
                        int(rec[s, i]))
            if table in (self._m_slots_occ or {}):
                self._m_slots_occ[table].set(occ_t)
            occupied[table] = occ_t
        from real_time_fraud_detection_system_tpu.utils.metrics import (
            active_recorder,
        )

        recorder = self.recorder if self.recorder is not None \
            else active_recorder()
        if recorder is not None:
            tiers = {t: m.value for t, m in (self._m_tier or {}).items()}
            extra = {}
            if self._cold is not None:
                # cold-tier depth + promotion backlog ride the same
                # flight event the dashboard Feature-store tile reads
                extra = {
                    "cold_keys": int(self._cold.keys_count),
                    "cold_bytes": int(self._cold.bytes),
                }
            recorder.record_event(
                "feature_state", reclaimed=int(rec.sum()),
                occupied=sum(occupied.values()),
                capacity=cap_total,
                occupied_per_shard=occupied_per_shard,
                dense_rows=tiers.get("dense", 0.0),
                cms_rows=tiers.get("cms", 0.0),
                batch=self.state.batches_done, **extra)

    # -- cold tier over the mesh -------------------------------------------

    def _promote_payload_sds(self, table: str, width: int) -> dict:
        """Stacked per-shard promote-payload template: ``[n_dev, W]``
        keys / ``[n_dev, W, NB]`` rows for the one table (the shard_map
        splits the leading device axis)."""
        nb = self.cfg.features.n_day_buckets
        n = self.n_dev
        lanes = (jax.ShapeDtypeStruct((n, width), jnp.uint32),
                 jax.ShapeDtypeStruct((n, width, nb), jnp.int32)) + (
            jax.ShapeDtypeStruct((n, width, nb), jnp.float32),) * 3
        return one_table_payload(table, lanes)

    def _promote_lanes(self, table: str, keys: np.ndarray,
                       rows: tuple) -> list:
        """Owner-modulo-grouped promote payloads: key ``k`` lands in
        shard ``k % n_dev``'s lane block — the same stable modulo the
        ingest partitioner and the owner exchange route by, so a key
        demoted by shard *i* promotes back into shard *i*'s directory.
        A payload is as wide as its fullest shard's block needs; a
        shard with more keys than the widest program holds spreads them
        over several payloads."""
        n, top = self.n_dev, self._promote_widths[-1]
        owner = (keys % np.uint32(n)).astype(np.int64)
        order = np.argsort(owner, kind="stable")
        counts = np.bincount(owner, minlength=n)
        shard = owner[order]
        lane = np.arange(keys.size) - (np.cumsum(counts) - counts)[shard]
        out = []
        for lo in range(0, int(counts.max()), top):
            pick = (lane >= lo) & (lane < lo + top)
            w = next(w for w in self._promote_widths
                     if w >= min(int(counts.max()) - lo, top))
            at, src = (shard[pick], lane[pick] - lo), order[pick]
            lanes = blank_lanes((n, w), rows)
            for block, rows_of in zip(lanes, (keys,) + rows):
                block[at] = rows_of[src]
            out.append((w, one_table_payload(table, lanes)))
        return out

    # -- sharding upkeep ---------------------------------------------------

    def _ensure_layout(self) -> None:
        """Adopt a restored checkpoint written at a different width or
        process topology: convert to THIS mesh's layout via the elastic
        reshards (exact for the window/history tables)."""
        n_old = int(getattr(self.state, "layout_devices", 1) or 1)
        restored_pc = int(getattr(self.state, "process_count", 1) or 1)
        my_pc = (self.topology.n_processes
                 if self.topology is not None else 1)
        if self.topology is not None and restored_pc == my_pc \
                and my_pc > 1 and n_old != self.n_dev:
            # Defense in depth behind Checkpointer._check_topology's
            # refusal (states can arrive without a checkpoint restore):
            # a per-process width change at fixed P moves residue
            # blocks between processes — no per-process reshard is
            # sound.
            raise ValueError(
                f"restored state was laid out at {n_old} device(s) per "
                f"process; this engine serves {self.n_dev} — in a "
                f"{my_pc}-process fleet that changes residue-block "
                "ownership (key % (P·L)): merge the fleet's "
                "checkpoints (parallel.mesh.merge_process_states) and "
                "re-slice at the new topology")
        if self.topology is not None and restored_pc != my_pc:
            # Checkpointer.restore refuses every other topology change;
            # the one that reaches here is the sanctioned 1→P adoption
            # (a global single-process checkpoint re-sliced per process).
            if restored_pc != 1:
                raise ValueError(
                    f"restored state was written by a {restored_pc}"
                    f"-process fleet; this engine serves a {my_pc}"
                    "-process topology — merge the per-process "
                    "checkpoints first (parallel.mesh."
                    "merge_process_states; README multi-host playbook)")
            from real_time_fraud_detection_system_tpu.parallel.mesh \
                import adopt_process_slice

            self.state.feature_state = adopt_process_slice(
                self.state.feature_state, self.cfg, n_old, self.topology)
            self.state.layout_devices = self.n_dev
            self.state.process_count = self.topology.n_processes
            self.state.process_id = self.topology.process_id
            return
        if n_old == self.n_dev:
            return
        from real_time_fraud_detection_system_tpu.parallel.mesh import (
            reshard_engine_state,
        )

        self.state.feature_state = reshard_engine_state(
            self.kind, self.state.feature_state, self.cfg, n_old,
            self.n_dev, stacked=True)
        self.state.layout_devices = self.n_dev
        # placement over the mesh happens in _ensure_sharded

    def _commit_replicated(self) -> None:
        """Place params + scaler on the mesh with the replicated sharding
        the step RETURNS them in. Skipped when already committed (cheap
        host-side sharding check). Without this, the first step call
        after construction, a checkpoint restore, or a hot model reload
        sees differently-sharded inputs than the previous call produced
        and silently RETRACES inside the serving loop (measured: 969 ms
        vs 8 ms steady-state at width 1)."""
        rep = NamedSharding(self.mesh, P())

        def needs(t) -> bool:
            # Inspect ALL leaves, not just the first one carrying a
            # .sharding: a partially swapped params tree (e.g. a hot
            # reload that replaced some leaves with host arrays) would
            # otherwise be skipped on the strength of its one committed
            # leaf, silently reintroducing the per-call retrace this
            # guard exists to prevent. A leaf WITHOUT a .sharding at all
            # (numpy array, python scalar) is a host leaf and equally
            # needs the commit — after it, every leaf is a committed
            # device array, so this stays a one-shot.
            for leaf in jax.tree.leaves(t):
                sh = getattr(leaf, "sharding", None)
                if sh is None:
                    return True  # host leaf: commit
                if not (isinstance(sh, NamedSharding)
                        and sh.mesh.shape == self.mesh.shape):
                    return True
            return False  # every leaf already mesh-committed (or empty)

        for name in ("params", "scaler"):
            t = getattr(self.state, name)
            if needs(t):
                self._m_commits.inc()
                setattr(self.state, name, jax.tree.map(
                    lambda x: jax.device_put(jnp.asarray(x), rep), t))

    def _ensure_sharded(self) -> None:
        """Re-place the feature state after an external restore.

        ``Checkpointer.restore`` rebuilds leaves as plain device arrays;
        the sharded step wants them laid out over the mesh (jit would
        auto-reshard every call otherwise — correct but wasteful)."""
        self._commit_replicated()  # restore/reload leave them uncommitted
        if self.kind == "sequence":
            from real_time_fraud_detection_system_tpu.parallel.sequence_step import (
                shard_history_state,
            )

            leaf = self.state.feature_state.count
            sh = getattr(leaf, "sharding", None)
            if not (isinstance(sh, NamedSharding) and sh.mesh.shape
                    == self.mesh.shape):
                self.state.feature_state = shard_history_state(
                    self.state.feature_state, self.mesh, axis=self.axis)
            return
        leaf = self.state.feature_state.customer.count
        sh = getattr(leaf, "sharding", None)
        if not (isinstance(sh, NamedSharding) and sh.mesh.shape
                == self.mesh.shape):
            self.state.feature_state = shard_feature_state(
                self.state.feature_state, self.mesh, axis=self.axis
            )

    # -- AOT precompilation over the mesh ----------------------------------

    def dispatch_inventory(self) -> list:
        """Enumerate every sharded dispatch signature — ONE shape family
        (chunks are always ``[7, n_dev * rows_per_shard]``) × TWO step
        variants: the owner-local step and the dense-spill ROUTED step
        (``partition_batch_spill`` overflow re-packing) — plus the
        per-shard ``("compact",)`` recency-compaction pass when the
        tiered exact store runs with a cadence. Same
        single-source-of-truth contract as the single-chip inventory:
        ``precompile`` compiles this list, ``_start_batch`` dispatches
        under these keys, and ``tools/rtfdsverify`` proves contracts
        over it. ``kind='sequence'`` has no AOT path (pytree batches) —
        empty inventory, skipped warmup, nothing to prove."""
        from real_time_fraud_detection_system_tpu.runtime.engine import (
            DispatchSignature,
        )

        if self.kind == "sequence":
            return []
        zmode_kinds = ("tree", "forest", "gbt")
        total = self.n_dev * self.rows_per_shard
        sigs = [
            DispatchSignature(
                key=("sharded", routed),
                variant="sharded-routed" if routed else "sharded-local",
                kind=self.kind,
                z_mode=self.z_mode if self.kind in zmode_kinds else None,
                bucket=total,
                donate=(0,),  # make_sharded_step donates the state tree
                selective=bool(self._selective),
                emit_dtype=self.cfg.runtime.emit_dtype,
                use_pallas=bool(self.cfg.runtime.use_pallas),
            )
            for routed in (False, True)
        ]
        if self._compact_every:
            # Per-shard recency compaction is part of the compiled step
            # family on the mesh too: ONE shape (the sharded state + an
            # int32 day scalar), fired from the same batch cadence —
            # enumerated so precompile/verify cover it and the cadence
            # can never pay a mid-stream compile.
            sigs.append(DispatchSignature(
                key=("compact",),
                variant="compact",
                kind=self.kind,
                z_mode=None,
                bucket=0,
                donate=(0,),
                selective=False,
                emit_dtype=self.cfg.runtime.emit_dtype,
                use_pallas=False,
            ))
        sigs.extend(self._promote_signatures((0,)))
        return sigs

    def _ensure_step(self, routed: bool):
        """THE lazy build+cache+meter point for both step variants —
        shared by the hot path (``_start_batch``), warmup
        (``precompile`` via ``signature_step``) and the verifier, so
        the serving program, the compiled program and the proven
        program are one object. Templates carry pytree structure only
        (``_sds``); the built jit serves live arrays identically."""
        cached = (self._sharded_step_routed if routed
                  else self._sharded_step)
        if cached is not None:
            return cached
        build = (self._sharded_build_routed if routed
                 else self._sharded_build)
        total = self.n_dev * self.rows_per_shard
        step = build(
            self._sds(self.state.feature_state),
            self._sds(self.state.params),
            self._sds(self.state.scaler),
            jax.ShapeDtypeStruct((7, total), jnp.int32),
        )
        self._m_step_builds.inc()
        if routed:
            self._sharded_step_routed = step
        else:
            self._sharded_step = step
        return step

    def signature_step(self, sig):
        """The shard_map step the signature dispatches to — the same
        lazily-built jit object ``_start_batch`` serves, so a
        lower/trace of this callable IS the serving program."""
        if sig.variant == "compact":
            return self._compact
        if sig.variant == "promote":
            return self._promote
        return self._ensure_step(sig.variant == "sharded-routed")

    def precompile(self) -> dict:
        """AOT-compile BOTH sharded step variants before the first poll.

        Iterates :meth:`dispatch_inventory` (the routed variant
        otherwise first compiles on a hot-key overflow deep into serving
        — a real mid-stream compile, 969 ms measured vs 8 ms
        steady-state, landing exactly when load spikes) via the same
        ``.lower(...).compile()`` path as the single-chip engine
        (shape-only templates; no step executes).
        """
        inventory = self.dispatch_inventory()
        if not inventory:  # kind='sequence' (no AOT path: pytree batches)
            return {"buckets": [], "variants": 0, "seconds": 0.0,
                    "skipped": "sequence"}
        t0 = time.perf_counter()
        self._ensure_layout()
        self._ensure_sharded()
        self.state.params = jax.tree.map(jnp.asarray, self.state.params)
        self._aot_params_sig = self._params_sig(self.state.params)
        variants = len(self._compile_signatures(inventory))
        return {
            "buckets": sorted({s.bucket for s in inventory}),
            "variants": variants,
            "seconds": round(time.perf_counter() - t0, 3),
        }

    # -- the sharded hot path ----------------------------------------------

    def _validate_sharded(self, cols: dict) -> None:
        """Strict-ingest check with CHUNK-level attribution: beyond the
        single-chip engine's row facts, the PoisonRowError names the
        shard placements (``customer_id % n_dev``) the corrupt rows were
        headed for — so a crash-loop diagnosis on a mesh points at the
        chunks, not just the batch. The predicate itself lives in ONE
        place (validate_ingest_rows); only the attribution is added here
        (computed solely on failure)."""
        from real_time_fraud_detection_system_tpu.runtime.engine import (
            validate_ingest_rows,
        )

        def detail(bad):
            shards = sorted(set(
                (np.asarray(cols["customer_id"])[bad]
                 % self.n_dev).astype(int).tolist()))
            return f"shard placement(s) {shards[:8]}"

        validate_ingest_rows(cols, detail_fn=detail)
        topo = self.topology
        if (topo is not None and topo.strict_affinity
                and len(cols["tx_id"])):
            # Partition-affinity contract: every polled row's customer
            # residue must be ours. A breach means two processes would
            # serve the same key's history — fail fast before any state
            # diverges, naming the mis-wired side.
            owner = topo.owner_process(cols["customer_id"])
            mine = owner == topo.process_id
            if not mine.all():
                others = sorted(set(owner[~mine].tolist()))
                raise ValueError(
                    f"partition-affinity breach: {int((~mine).sum())} "
                    f"polled row(s) belong to process(es) {others[:4]} "
                    f"but this is process {topo.process_id} — fix the "
                    "launcher's source slicing (PartitionAffineSource "
                    "residues / Kafka partition blocks), or pass "
                    "strict_affinity=False for a broker-partitioned "
                    "fleet whose keys are not residue-aligned")

    def _start_batch(self, cols: dict) -> dict:
        """Dedup → partition (spill) → launch sharded step(s), async.

        Device results stay futures in the handle; :meth:`_finish_batch`
        materializes and re-assembles them in input order — so
        :meth:`~.engine.ScoringEngine.run`'s double-buffering overlaps the
        next batch's partition + H2D with this batch's mesh compute.
        """
        with self._phase("host_prep") as prep:
            keep = latest_wins_mask_host(cols["tx_id"], cols["kafka_ts_ms"])
            cols = {k: v[keep] for k, v in cols.items()}
            self._validate_sharded(cols)
            returning = self._returning_keys(cols)
            n = len(cols["tx_id"])
            self._count_wide_ids(cols)
            self._ensure_sharded()
            if n:
                # Same placement rule as partition_batch_spill
                # (customer_id % n_dev): one bincount per batch, so the
                # dashboard can see hot-key imbalance the moment it
                # starts spilling.
                loads = np.bincount(
                    (cols["customer_id"] % self.n_dev).astype(np.int64),
                    minlength=self.n_dev)
                for i, g in enumerate(self._m_shard_rows):
                    g.set(int(loads[i]))
                self._m_rows_max.inc(int(loads.max()))
                self._m_rows_mean.inc(n / self.n_dev)

            with self._phase("partition"):
                chunks = partition_batch_spill(
                    cols, self.n_dev, self.rows_per_shard
                ) if n else []
        # promote before score, ahead of the first chunk's step
        promoted = (self._promote_returning(returning)
                    if returning is not None else ())
        # host prep ended above: the chunk loop below is dispatch (make_
        # batch + H2D + jit launches), split out so the sharded loop's
        # phase decomposition matches the single-chip engine's — one
        # dispatch phase over all chunk launches (the per-chunk jit calls
        # are its children on the profiler timeline).
        parts = []
        tier_parts = []  # exact mode: per-chunk [n_dev, 6] tier vectors
        exchange_parts = []  # per-chunk [3] exchange counts
        with self._phase("dispatch", chunks=len(chunks)) as disp:
            t_fetch = self._dispatch_chunks(
                chunks, parts, tier_parts, exchange_parts)
        handle = {"cols": cols, "n": n, "parts": parts, "t0": prep.t0,
                  "prep_s": prep.seconds, "dispatch_s": disp.seconds,
                  "fetch_issue_t": t_fetch}
        if tier_parts:
            handle["tier_shard"] = tier_parts
        if exchange_parts:
            handle["exchange"] = exchange_parts
        # notify compaction's recency cutoff (the base engine does this
        # in its own _start_batch; the sharded path overrides it wholesale)
        self._note_batch_days(cols)
        handle["promote_checks"] = promoted
        return handle

    def _dispatch_chunks(self, chunks, parts: list, tier_parts: list,
                         exchange_parts: list) -> Optional[float]:
        """Launch one sharded step a chunk, filling the three lists the
        batch's finish reads; → the last async fetch's issue time."""
        t_fetch = None
        for part_cols, rows, pos in chunks:
            batch = make_batch(
                customer_id=part_cols["customer_id"],
                terminal_id=part_cols["terminal_id"],
                tx_datetime_us=part_cols["tx_datetime_us"],
                amount_cents=part_cols["tx_amount_cents"],
                label=np.where(
                    part_cols["__valid__"],
                    part_cols.get(
                        "label",
                        np.full(len(part_cols["__valid__"]), -1, np.int64),
                    ),
                    -1,
                ),
            )
            batch = batch._replace(valid=part_cols["__valid__"])
            if self.kind != "sequence":
                # One packed H2D copy per chunk (pack_batch layout); the
                # packed step bitcasts it back inside the jit. Seven
                # separate leaf transfers pay seven per-call overheads —
                # most of the sharded loop's fixed cost on a remote chip.
                jbatch = jnp.asarray(pack_batch(batch))
            else:
                jbatch = jax.tree.map(jnp.asarray, batch)
            routed = bool(part_cols.get("__routed__", False))
            self._m_chunks[routed].inc()
            self._m_slots.inc(len(part_cols["__valid__"]))
            self._m_valid_rows.inc(len(rows))
            if self.kind == "sequence":
                step = self._seq_step_routed if routed else self._seq_step
                # original batch row index per chunk slot — the
                # same-second tiebreaker (chunk packing permutes rows)
                okey = np.zeros(len(part_cols["__valid__"]), np.int32)
                okey[pos] = rows.astype(np.int32)
                sig = step_signature(
                    *jax.tree.leaves(jbatch),
                    static=(self.kind, routed, self.n_dev))
                with self._recompile.step(sig):
                    hstate, probs = step(
                        self.state.feature_state, self.state.params,
                        jbatch, jnp.asarray(okey))
                self.state.feature_state = hstate
                t_fetch = self._issue_host_fetch(probs, None) or t_fetch
                # the sequence scorer has no engineered feature matrix;
                # None skips the feats copy (_finish_batch's buffer is 0)
                parts.append((rows, pos, probs, None))
                continue
            # The detector window covers the lazy step BUILD too: a
            # routed variant first compiled on a hot-key overflow deep
            # into serving is a real in-loop compile and must alarm.
            # z_mode rides the statics: the sharded step closes over the
            # base engine's z-mode-aware predict.
            sig = step_signature(
                jbatch,
                static=(self.kind, routed, self.n_dev, self.z_mode))
            with self._recompile.step(sig):
                step = self._ensure_step(routed)
                out = self._dispatch_step(
                    ("sharded", routed), step,
                    self.state.feature_state, self.state.params,
                    self.state.scaler, jbatch,
                )
            fstate, params, probs, feats = out[:4]
            if self._exact:
                # [n_dev, 6] per-shard [dense, cms] rows served this
                # chunk and the two admits' claim rounds, all of them
                # and the narrow ones — accumulated
                # across chunks, materialized at finish (scalar-sized;
                # no async fetch needed)
                tier_parts.append(out[4])
            # three counts beside probs, read at finish like the tier rows
            out[-1].copy_to_host_async()
            exchange_parts.append(out[-1])
            self.state.feature_state = fstate
            self.state.params = params
            # async D2H per chunk: each chunk's transfer starts the
            # moment ITS compute finishes, overlapping later chunk
            # dispatches and the next batch's host prep
            t_fetch = self._issue_host_fetch(probs, feats) or t_fetch
            parts.append((rows, pos, probs, feats))
        return t_fetch

    def _finish_batch(self, handle: dict) -> BatchResult:
        n = handle["n"]
        tid = handle.get("trace_id")
        self._meter_fetch_overlap(handle)
        # _emit_features_now, not the raw config flag: the overload
        # ladder's rung-2 degrade (inherited run() loop) switches the
        # mesh engine to alerts-only emission the same host-side way —
        # the shard_map step and both AOT variants are untouched.
        emit = self._emit_features_now()
        with self._phase("device_wait", batch=tid):
            # the wait for the device: every chunk's results on the host
            # (selective emission: one packed fetch a chunk; alerts-only
            # skips the per-shard feature D2H, same contract as the
            # single-chip engine)
            fetched = [
                (np.asarray(feats["packed"]), None)
                if isinstance(feats, dict) else
                (np.asarray(probs),
                 np.asarray(feats) if feats is not None and emit else None)
                for _, _, probs, feats in handle["parts"]]
        with self._phase("fetch", batch=tid):
            self._check_promotes(handle)
            probs_np = np.zeros(n, dtype=np.float32)
            if self.kind == "sequence" or not emit:
                # nothing below writes the feature matrix on these paths
                # (sequence parts carry feats=None; alerts-only skips the
                # per-shard feats copy) — share the read-only staging
                # buffer
                feats_np = self._zero_features(n)
            else:
                feats_np = np.zeros((n, N_FEATURES), dtype=np.float32)
            if handle["parts"]:
                # Re-assembly = the host's permutation of each chunk's
                # slots back into input order, apart from the wait for
                # the device that precedes it.
                with self._phase("assemble", batch=tid):
                    overflowed = self._assemble(
                        handle["parts"], fetched, probs_np, feats_np)
                if overflowed:
                    # once per BATCH however many chunks overflow,
                    # matching the single-chip counter semantics
                    # (engine.py: "batches whose flagged-row count
                    # overflowed")
                    self.selective_overflows += 1
            for x in handle.pop("exchange", ()):
                for counter, v in zip(self._m_xchg, np.asarray(x)):
                    counter.inc(int(v))
            tier_parts = handle.pop("tier_shard", None)
            if tier_parts is not None:
                # per-shard tier accounting ([n_dev, 6] summed over
                # chunks): shard-labeled counters get their own rows, the
                # base table-level counters get the shard sums — so the
                # global healthz/dashboard contract is identical on the
                # mesh. The claim rounds (columns 2, 3: customer,
                # terminal) keep their per-shard counts under a name of
                # their own; the narrow ones (4, 5) have the table-level
                # series alone.
                tier = np.zeros((self.n_dev, 6), np.float64)
                for t in tier_parts:
                    tier += np.asarray(t)
                if self._m_tier_shard is not None:
                    for s in range(self.n_dev):
                        self._m_tier_shard[("dense", s)].inc(
                            float(tier[s, 0]))
                        self._m_tier_shard[("cms", s)].inc(
                            float(tier[s, 1]))
                    col = {"customer": 2, "terminal": 3}
                    for (table, s), m in self._m_claim_rounds_shard.items():
                        m.inc(float(tier[s, col[table]]))
                handle["tier"] = tier.sum(axis=0)  # global, as one chip's
            return self._emit_result(handle, probs_np, feats_np)

    @staticmethod
    def _assemble(parts, fetched, probs_np, feats_np) -> bool:
        """Permute every chunk's fetched results into ``probs_np`` /
        ``feats_np`` (input order); → whether a chunk's flagged rows
        overflowed the selective-emission cap."""
        overflowed = False
        for (rows, pos, _, feats), (head, feats_host) in zip(parts,
                                                              fetched):
            if isinstance(feats, dict):
                # selective emission: the packed fetch carries
                # [probs(pad) | count | idx(cap) | feats(cap·15)] — the
                # same layout the single-chip engine unpacks; indices are
                # chunk SLOTS, mapped back to original batch rows via the
                # chunk's (pos → rows) placement.
                flat = head
                pad = feats["full"].shape[0]
                cap = ((feats["packed"].shape[0] - pad - 1)
                       // (1 + N_FEATURES))
                probs_np[rows] = flat[:pad][pos]
                count = int(flat[pad])
                if count > cap:
                    overflowed = True
                    feats_np[rows] = np.asarray(feats["full"])[pos]
                elif count:
                    idx = flat[pad + 1:pad + 1 + count].astype(np.int64)
                    sel = flat[pad + 1 + cap:
                               pad + 1 + cap + count * N_FEATURES]
                    slot_to_row = np.full(pad, -1, np.int64)
                    slot_to_row[pos] = rows
                    # flagged slots are valid by construction, so every
                    # target is a real batch row
                    feats_np[slot_to_row[idx]] = sel.reshape(
                        count, N_FEATURES)
                continue
            probs_np[rows] = head[pos]
            if feats_host is not None:
                feats_np[rows] = feats_host[pos]
        return overflowed

    # -- feedback into the owner-partitioned terminal table ----------------

    def apply_state_feedback(
        self,
        terminal_ids: np.ndarray,
        days: np.ndarray,
        labels: np.ndarray,
    ) -> None:
        """Land delayed fraud labels in the sharded terminal risk windows.

        The sharded layout places terminal key k at global row
        ``ops/hashing.key_row`` (owner shard × local slot, the step's own
        rule). The scatter runs as a plain jitted global-array op — GSPMD
        inserts the (off-hot-path) collectives."""
        # cross-width restored state must convert before any slot scatter
        self._ensure_layout()
        if self.kind == "sequence":
            raise ValueError(
                "the labeled-feedback loop is not wired for "
                "kind='sequence'")
        labels = np.asarray(labels)
        mask = labels >= 0
        if not mask.any():
            return
        self._ensure_sharded()
        key = fold_key(np.asarray(terminal_ids)[mask]).astype(np.uint32)
        if self._exact:
            # Directory-routed feedback: ownership is key % n_dev (the
            # step's routing modulo), the slot is a LOOKUP into the
            # owner's directory — hits land in the owner's dense window
            # rows, misses in the owner's sketch replica's fraud column
            # (features/online.apply_feedback_sharded_exact; never an
            # insert, so feedback cannot evict live traffic's slots).
            if self._sharded_sf_exact is None:
                from real_time_fraud_detection_system_tpu.features.online \
                    import apply_feedback_sharded_exact

                fcfg = self.cfg.features

                def sfx(fstate, tk, dd, yy, valid):
                    return apply_feedback_sharded_exact(
                        fstate, tk, dd, yy, valid, fcfg)

                self._sharded_sf_exact = jax.jit(sfx, donate_argnums=(0,))
        elif self._sharded_sf is None:
            self._sharded_sf = jax.jit(
                apply_feedback_at_slot, donate_argnums=(0,)
            )
        if not self._exact:
            gslot = key_row(key, self.cfg.features.terminal_capacity,
                            "direct", self.n_dev)
        d = np.asarray(days)[mask].astype(np.int32)
        y = labels[mask].astype(np.int32)
        # Bucket-pad like the single-chip path (engine.py) so a stream of
        # ever-different label counts hits ONE jit cache entry, not one
        # compile per length.
        biggest = max(self.cfg.runtime.batch_buckets)
        for s in range(0, len(y), biggest):
            m = len(y[s : s + biggest])
            pad = bucket_size(m, self.cfg.runtime.batch_buckets)
            dd = np.zeros(pad, dtype=np.int32)
            dd[:m] = d[s : s + m]
            yy = np.zeros(pad, dtype=np.int32)
            yy[:m] = y[s : s + m]
            valid = np.zeros(pad, dtype=bool)
            valid[:m] = True
            if self._exact:
                tk = np.zeros(pad, dtype=np.uint32)
                tk[:m] = key[s : s + m]
                self.state.feature_state = self._sharded_sf_exact(
                    self.state.feature_state, jnp.asarray(tk),
                    jnp.asarray(dd), jnp.asarray(yy), jnp.asarray(valid),
                )
                continue
            gs = np.zeros(pad, dtype=np.int32)
            gs[:m] = gslot[s : s + m]
            self.state.feature_state = self._sharded_sf(
                self.state.feature_state, jnp.asarray(gs), jnp.asarray(dd),
                jnp.asarray(yy), jnp.asarray(valid),
            )
