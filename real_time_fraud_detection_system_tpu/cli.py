"""Command-line entry points — the reference Makefile UX, one binary.

Reference targets (``Makefile:2-58``) → subcommands:

- ``make load_initial_data`` / datagen container → ``datagen`` (generate a
  synthetic table to .npz) and ``warmstart`` happens inside ``score``;
- offline notebook chain → ``train`` (features via replay, model fit,
  metrics, artifacts out);
- ``make fraud_detection`` → ``score --scorer {cpu,tpu}`` (the north-star
  switch): stream a table through the engine, Parquet out;
- ``make job3`` (CDC ingestion incl. envelope decode) → ``score
  --mode envelope`` replays through Debezium-format envelopes.

Usage::

    python -m real_time_fraud_detection_system_tpu.cli datagen --out txs.npz
    python -m real_time_fraud_detection_system_tpu.cli train --data txs.npz \
        --model forest --out-model model.npz
    python -m real_time_fraud_detection_system_tpu.cli score --data txs.npz \
        --model-file model.npz --scorer tpu --out analyzed/
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np


def _platform_setup(platform: str | None) -> None:
    """Honour ``--platform`` / ``JAX_PLATFORMS`` and enable the compile
    cache — nothing else. A command that needs the device and finds
    none fails with jax's own error; no child process ever probes the
    backend (a chip belongs to one process at a time)."""
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
        import jax

        jax.config.update("jax_platforms", platform)
    from real_time_fraud_detection_system_tpu.utils import (
        enable_compilation_cache,
    )

    enable_compilation_cache()


def _json_line(obj) -> str:
    """Strict-JSON dump: NaN/Inf floats become null (json.dumps would emit
    the non-standard literals and break jq/JSON.parse consumers)."""

    def clean(v):
        if isinstance(v, dict):
            return {k: clean(x) for k, x in v.items()}
        if isinstance(v, (list, tuple)):
            return [clean(x) for x in v]
        if isinstance(v, float) and not np.isfinite(v):
            return None
        return v

    return json.dumps(clean(obj), allow_nan=False)


def _start_epoch_s(start_date: str) -> int:
    from real_time_fraud_detection_system_tpu.utils.timing import (
        date_to_epoch_s,
    )

    return date_to_epoch_s(start_date)


def cmd_datagen(args) -> int:
    from real_time_fraud_detection_system_tpu.config import DataConfig
    from real_time_fraud_detection_system_tpu.data import generate_dataset
    from real_time_fraud_detection_system_tpu.io.artifacts import save_transactions
    from real_time_fraud_detection_system_tpu.utils import get_logger

    log = get_logger("datagen")
    cfg = DataConfig(
        n_customers=args.customers,
        n_terminals=args.terminals,
        n_days=args.days,
        radius=args.radius,
        seed=args.seed,
        start_date=args.start_date,
    )
    customers, terminals, txs = generate_dataset(cfg)
    save_transactions(args.out, txs)
    log.info(
        "generated %d txs (%d customers, %d terminals, %d days) "
        "fraud_rate=%.4f -> %s",
        txs.n, cfg.n_customers, cfg.n_terminals, cfg.n_days,
        txs.tx_fraud.mean(), args.out,
    )
    if args.pg_dsn:
        # Live-OLTP seeding (the reference datagen container's role,
        # datagen/data_gen.py:67-147): rows land in real Postgres for a
        # Debezium connector to CDC out. --pg-rate > 0 drip-feeds.
        from real_time_fraud_detection_system_tpu.io.pg import PgLive
        from real_time_fraud_detection_system_tpu.utils.timing import (
            date_to_epoch_s,
        )

        pg = PgLive(args.pg_dsn)
        pg.ensure_schema()
        pg.upsert_dimension("customers", "customer_id",
                            customers.customer_id, customers.x,
                            customers.y)
        pg.upsert_dimension("terminals", "terminal_id",
                            terminals.terminal_id, terminals.x,
                            terminals.y)
        n = pg.upsert_transactions(
            {
                "tx_id": txs.tx_id,
                "tx_datetime_us": txs.epoch_us(
                    date_to_epoch_s(cfg.start_date)),
                "customer_id": txs.customer_id,
                "terminal_id": txs.terminal_id,
                "tx_amount_cents": txs.amount_cents,
            },
            rate_per_s=args.pg_rate,
        )
        log.info("seeded live postgres with %d transactions", n)
    return 0


def cmd_train(args) -> int:
    from real_time_fraud_detection_system_tpu.config import Config, TrainConfig
    from real_time_fraud_detection_system_tpu.io.artifacts import (
        load_transactions,
        save_model,
    )
    from real_time_fraud_detection_system_tpu.models import train_model
    from real_time_fraud_detection_system_tpu.utils import get_logger

    log = get_logger("train")
    txs = load_transactions(args.data)
    cfg = Config(
        train=TrainConfig(
            delta_train_days=args.delta_train,
            delta_delay_days=args.delta_delay,
            delta_test_days=args.delta_test,
            epochs=args.epochs,
        )
    )
    model, metrics = train_model(txs, cfg, kind=args.model)
    save_model(args.out_model, model)
    log.info("model=%s metrics=%s -> %s", args.model,
             {k: round(v, 4) for k, v in metrics.items()}, args.out_model)
    print(_json_line({"model": args.model, **metrics}))
    return 0


def _make_model_reloader(path: str, kind: str, every_batches: int, log,
                         seed_initial: bool = False, sig_state=None):
    """Hot model reload for serving: every N batches, re-read the model
    artifact and swap weights into the live engine between device steps
    (the reference picks up a retrained pickle only by restarting the
    Spark job, ``fraud_detection.py:59-82``). Local paths gate on mtime,
    ``s3://`` artifacts on HEAD metadata (ETag + size), so an unchanged
    artifact costs one stat/HEAD per interval — the body is downloaded
    only when the metadata changed (stores without ``head()``, or with
    degenerate metadata, fall back to a GET + content digest gate).

    ``seed_initial=False`` (plain serving): the FIRST due interval
    always reloads — a fresh reloader is built per supervisor
    incarnation, and crash recovery restores pre-swap weights from the
    checkpoint, so the new incarnation must re-apply the latest artifact
    rather than trust a stale signature. ``seed_initial=True``
    (``--learn-registry`` active): the file's signature is captured and
    only a CHANGE after startup triggers a reload — the registry's
    champion pointer, not the bootstrap file, is the record of what
    should serve, and the forced first reload would silently clobber an
    adopted promotion with the stale file params. In that mode the
    caller passes ``sig_state`` (one dict shared across supervisor
    incarnations, seeded ONCE): re-baselining per incarnation would
    silently drop a file update that landed between the previous
    incarnation's last poll and its crash.

    The serving kind is pinned — an artifact of a different kind is
    refused (the jitted step's shape family would change under the
    engine)."""
    import hashlib
    import os as _os

    from real_time_fraud_detection_system_tpu.io.artifacts import (
        _split_s3_url,
        load_model,
        load_model_bytes,
    )
    from real_time_fraud_detection_system_tpu.io.store import make_store
    from real_time_fraud_detection_system_tpu.runtime.engine import (
        device_params_for,
    )

    # "n" (poll cadence) is per-incarnation; "sig" lives in sig_state
    # when the caller shares one across incarnations.
    state = sig_state if sig_state is not None else {}
    state.setdefault("sig", None)
    state["n"] = 0
    is_local = not path.startswith("s3://")
    url = key = None
    if not is_local:
        url, key = _split_s3_url(path)

    def _meta_sig(md):
        # the ONE signature format for store artifacts (ETag + size, or
        # None to force the GET+digest fallback) — the seed baseline and
        # poll's change gate must always agree on it
        if md.get("etag") or md.get("size") is not None:
            return f"{md.get('etag')}:{md.get('size')}"
        return None

    if seed_initial and state["sig"] is None:
        try:
            if is_local:
                state["sig"] = _os.stat(path).st_mtime_ns
            else:
                store = make_store(url)
                head = getattr(store, "head", None)
                md = head(key) if head is not None else {}
                state["sig"] = _meta_sig(md) or hashlib.sha256(
                    store.get(key)).hexdigest()
        # rtfdslint: disable=broad-exception-catch (any store/head/hash failure degrades to a forced first-interval reload, warn-logged; reload polling must never kill serving)
        except Exception as e:
            log.warning("could not baseline %s for change-gated reload "
                        "(%s); the first interval will reload it", path, e)
            state["sig"] = None

    def poll():
        state["n"] += 1
        if state["n"] % every_batches:
            return None
        try:
            if is_local:
                sig = _os.stat(path).st_mtime_ns
                if state["sig"] is not None and sig == state["sig"]:
                    return None
                m = load_model(path)
            else:
                store = make_store(url)
                # Change-gate on HEAD metadata (ETag/size) so an
                # unchanged artifact costs one HEAD per interval, not a
                # full GET. When metadata says it changed, the STORED
                # signature comes from the GET response itself
                # (get_with_meta) so it always describes the bytes
                # actually loaded — a pre-GET HEAD sig could belong to an
                # older version overwritten between the two requests
                # (safe direction, but one redundant swap per overwrite).
                # Stores without head() (older fakes) fall back to the
                # GET+digest gate.
                head = getattr(store, "head", None)
                get_with_meta = getattr(store, "get_with_meta", None)
                meta = head(key) if head is not None else {}
                sig = _meta_sig(meta)
                if sig is not None:
                    if state["sig"] is not None and sig == state["sig"]:
                        return None
                    if get_with_meta is not None:
                        data, gmeta = get_with_meta(key)
                        sig = _meta_sig(gmeta) or sig
                    else:
                        data = store.get(key)
                else:
                    # no head() or degenerate metadata: digest-gate (the
                    # digest is computed from the loaded bytes, so it is
                    # always self-consistent)
                    data = store.get(key)
                    sig = hashlib.sha256(data).hexdigest()
                    if state["sig"] is not None and sig == state["sig"]:
                        return None
                m = load_model_bytes(data)
        # rtfdslint: disable=broad-exception-catch (a failed reload poll of ANY kind keeps serving on current weights, warn-logged; next interval retries)
        except Exception as e:
            log.warning("model reload from %s failed (%s); serving "
                        "continues on the current weights", path, e)
            return None
        if m.kind != kind:
            log.warning("model reload skipped: artifact kind %r != "
                        "serving kind %r", m.kind, kind)
            return None
        state["sig"] = sig
        log.info("hot-swapped model weights from %s", path)
        return device_params_for(kind, m.params), m.scaler

    # Shared-baseline mode: expose the dict so the supervisor's zombie
    # fence can roll back a signature a fenced-off incarnation committed
    # for a swap that can never land (faults._run_watched).
    poll.sig_state = state if sig_state is not None else None
    return poll


def _resume_merge_adopt(make_engine, ckpt, cfg, topology, spec,
                        cold_srcs, log):
    """Adopt a drained old-generation fleet's final checkpoints into
    THIS worker's own (empty) checkpoint lineage — the retopologize leg
    of an elastic fleet resize.

    ``spec`` is the parsed ``--resume-merge`` tuple ``(src_root, old_p,
    old_l, reason)``. Every old process's final checkpoint restores
    into a template state, the per-process feature states merge through
    :func:`parallel.mesh.merge_process_states` (checkpointed terminal-
    CMS partials are locals-only, so same-day shard sums stay exact),
    old cold-store generations consolidate into this worker's cold dir,
    and ONE single-chip global checkpoint lands in this worker's
    lineage with the stream cursor rewound to the fleet-wide minimum
    floor. Per-old-owner floors ride in a ``resize_epochs`` record so
    re-polled rows another old process already sank are dropped at
    ingest (:class:`runtime.OwnershipFloorSource`) — no row lost, none
    double-scored. Idempotent: a worker relaunched after its merge
    already landed re-reads the floors from its newest manifest instead
    of re-merging.

    Returns the per-old-owner floor list (possibly empty = no floor
    filtering needed) or ``None`` on failure — the caller exits rc 2,
    because serving without the merged state would break exactly-once.
    """
    import copy as _copy

    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        make_checkpointer,
    )
    from real_time_fraud_detection_system_tpu.parallel.mesh import (
        merge_process_states,
    )

    src_root, old_p, old_l, reason = spec
    latest = ckpt.latest()
    if latest is not None:
        # Crash AFTER the merge committed: this worker's lineage already
        # starts from the merged state — re-merging would clobber
        # progress. The floors live in the stamped resize epoch.
        try:
            meta = (ckpt.manifest(latest) or {}).get("meta") or {}
        # rtfdslint: disable=broad-exception-catch (an unreadable tip manifest here only degrades the floor filter; restore itself re-verifies and falls back down the lineage)
        except Exception:
            meta = {}
        epochs = meta.get("resize_epochs") or []
        if epochs:
            rec = epochs[-1]
            log.info("resume-merge: lineage already merged (epoch %s, "
                     "%s->%s); resuming from it",
                     len(epochs), rec.get("from_processes"),
                     rec.get("to_processes"))
            return [int(f) for f in rec.get("floors", [])]
        log.warning("resume-merge: %s already has ordinary checkpoints; "
                    "skipping the merge and resuming from them", latest)
        return []
    tmpl = make_engine()
    eng_l = int(getattr(tmpl.state, "layout_devices", 1) or 1)
    if old_l != eng_l:
        log.error("--resume-merge: old fleet served %d device(s) per "
                  "process but this worker serves %d — resize the "
                  "process count at fixed width, then change width "
                  "separately (the per-process reshard path)",
                  old_l, eng_l)
        return None
    states, floors, rows_done = [], [], 0
    prior_epochs: list = []
    model_version = None
    for pid in range(old_p):
        src_dir = (os.path.join(src_root, f"proc-{pid:02d}")
                   if old_p > 1 else src_root)
        try:
            src = make_checkpointer(
                src_dir,
                op_timeout_s=cfg.runtime.checkpoint_op_timeout_s,
                op_attempts=cfg.runtime.checkpoint_op_attempts)
        # rtfdslint: disable=broad-exception-catch (any backend open failure means the old generation's state is unreachable — report and refuse, whatever the type)
        except Exception as e:
            log.error("resume-merge: cannot open old checkpoints at "
                      "%s: %s", src_dir, e)
            return None
        st = _copy.deepcopy(tmpl.state)
        st.process_count, st.process_id = old_p, pid
        restored = src.restore(st)
        if restored is None:
            log.error("resume-merge: old process %d has no restorable "
                      "checkpoint under %s — a resize must drain to a "
                      "final checkpoint first", pid, src_dir)
            return None
        if len(restored.offsets) > 1:
            log.error("resume-merge: old process %d carries %d stream "
                      "cursors; only single-cursor sources resize "
                      "(broker fleets keep per-partition offsets)",
                      pid, len(restored.offsets))
            return None
        # no cursor at all = the process drained before its first poll
        # (a resize can land during warmup): its floor is stream start
        floors.append(int(restored.offsets[0]) if restored.offsets
                      else 0)
        rows_done += int(restored.rows_done)
        if model_version is None:
            model_version = getattr(restored, "model_version", None)
        if not prior_epochs:
            prior_epochs = list(
                getattr(restored, "resize_epochs", None) or [])
        states.append(restored.feature_state)
    try:
        merged_fs = merge_process_states(states, cfg, [old_l] * old_p)
    except ValueError as e:
        log.error("resume-merge: %s", e)
        return None
    out = _copy.deepcopy(tmpl.state)
    out.feature_state = merged_fs
    out.offsets = [min(floors)]
    out.batches_done = 0  # fresh per-generation sink lineage
    out.rows_done = rows_done
    out.layout_devices = 1
    out.process_count = 1  # global state; restore re-slices per process
    out.process_id = 0
    out.model_version = model_version
    new_p = topology.n_processes if topology is not None else 1
    out.resize_epochs = prior_epochs + [{
        "epoch": len(prior_epochs) + 1,
        "from_processes": old_p,
        "to_processes": new_p,
        "old_local_devices": old_l,
        "reason": reason,
        "floors": floors,
        "min_offset": min(floors),
    }]
    if cold_srcs:
        from real_time_fraud_detection_system_tpu.io.coldstore import (
            ColdStoreCorruptError,
            consolidate_cold_stores,
        )

        try:
            dest = consolidate_cold_stores(
                cold_srcs, cfg.features.cold_store,
                segment_mb=cfg.features.cold_segment_mb)
        except (OSError, ValueError, ColdStoreCorruptError) as e:
            log.error("resume-merge: cold-store consolidation failed: "
                      "%s", e)
            return None
        out.cold_lineage = dest.lineage()
        log.info("resume-merge: consolidated %d cold generation(s) "
                 "into %s (%d keys)", len(cold_srcs),
                 cfg.features.cold_store,
                 int(out.cold_lineage.get("total_keys", 0)))
    saved = ckpt.save(out)
    log.info("resume-merge: adopted %d-process generation at %s -> %s "
             "(floors %s, min offset %d, reason %r)",
             old_p, src_root, saved, floors, min(floors), reason)
    return floors


def cmd_score(args) -> int:
    from real_time_fraud_detection_system_tpu.config import Config
    from real_time_fraud_detection_system_tpu.io import make_parquet_sink
    from real_time_fraud_detection_system_tpu.io.artifacts import (
        load_model,
        load_transactions,
    )
    from real_time_fraud_detection_system_tpu.io.checkpoint import make_checkpointer
    from real_time_fraud_detection_system_tpu.runtime import (
        ReplaySource,
        ScoringEngine,
    )
    from real_time_fraud_detection_system_tpu.utils import get_logger

    log = get_logger("score")
    if args.source != "kafka" and not args.data:
        log.error("--data is required unless --source kafka")
        return 2
    # Failure-handling flags fail fast BEFORE any artifact loads.
    if args.nan_guard and not args.dead_letter:
        log.error("--nan-guard needs --dead-letter: quarantined rows "
                  "must land somewhere an operator can triage them")
        return 2
    multihost = args.num_processes > 1 or bool(args.coordinator)
    if args.nan_guard and (args.devices > 1 or multihost):
        log.error("--nan-guard is not wired for the sharded engine "
                  "(--devices > 1 / multi-host); rely on the "
                  "supervisor's crash-loop bisection (--dead-letter + "
                  "--max-restarts) there")
        return 2
    if multihost and args.num_processes < 1:
        log.error("--num-processes must be >= 1, got %s",
                  args.num_processes)
        return 2
    if args.max_batch_rows < 0:
        log.error("--max-batch-rows must be >= 0, got %s",
                  args.max_batch_rows)
        return 2
    import dataclasses as _dc

    # Multi-host bootstrap FIRST: jax.distributed.initialize refuses to
    # run after any jax computation, and artifact loading below builds
    # device arrays. The topology is config; everything after threads it.
    topology = None
    dist_cfg = None
    if multihost:
        from real_time_fraud_detection_system_tpu.config import (
            DistributedConfig,
        )
        from real_time_fraud_detection_system_tpu.runtime.distributed \
            import bootstrap_distributed

        try:
            dist_cfg = DistributedConfig(
                coordinator=args.coordinator,
                num_processes=max(args.num_processes, 1),
                process_id=args.process_id,
                # Kafka fleets slice by broker partition; residue
                # membership is the producer's contract, not checkable
                # per polled row
                strict_affinity=args.source != "kafka",
            )
            topology = bootstrap_distributed(
                dist_cfg, local_devices=max(args.devices, 1))
        except (ValueError, RuntimeError) as e:
            log.error("multi-host bootstrap failed: %s", e)
            return 2
        if topology is not None:
            log.info(
                "multi-host: process %d/%d, %d local device(s), global "
                "shards [%d, %d) of %d, coordinator %s",
                topology.process_id, topology.n_processes,
                topology.local_devices, topology.owned_shards.start,
                topology.owned_shards.stop, topology.n_shards_total,
                args.coordinator or "(uncoordinated)")
    if args.crash_loop_k < 1:
        log.error("--crash-loop-k must be >= 1, got %s", args.crash_loop_k)
        return 2
    if args.restart_backoff_ms < 0:
        log.error("--restart-backoff-ms must be >= 0, got %s",
                  args.restart_backoff_ms)
        return 2
    if args.checkpoint_full_every < 1:
        log.error("--checkpoint-full-every must be >= 1, got %s",
                  args.checkpoint_full_every)
        return 2
    if args.checkpoint_op_attempts < 1 or args.checkpoint_op_timeout < 0:
        log.error("--checkpoint-op-attempts must be >= 1 and "
                  "--checkpoint-op-timeout >= 0, got %s / %s",
                  args.checkpoint_op_attempts, args.checkpoint_op_timeout)
        return 2
    # replay reads a generated .npz; raw-table reads a table DIRECTORY
    txs = (load_transactions(args.data)
           if args.data and args.source == "replay" else None)
    model = load_model(args.model_file)
    if args.reload_model_every > 0 and args.scorer == "cpu":
        # the cpu oracle classifies host-side via the startup-captured
        # model object; a swap would re-scale features with the new
        # scaler while the OLD sklearn model predicts — actively wrong
        log.error("--reload-model-every does not compose with "
                  "--scorer cpu (the oracle model is fixed at startup)")
        return 2
    # With --learn-registry the registry's champion pointer, not the
    # bootstrap file, is the record of what should serve: seed the
    # reloader's signature baseline so only a file CHANGE after startup
    # triggers a swap — the forced first reload would silently clobber
    # an adopted promotion with stale file params. The signature dict is
    # shared across supervisor incarnations (seeded once): a fresh
    # baseline per incarnation would silently drop a file update landing
    # in the last-poll→crash window.
    _reload_sig: dict = {}
    make_reloader = (
        (lambda: _make_model_reloader(
            args.model_file, model.kind, args.reload_model_every, log,
            seed_initial=bool(args.learn_registry),
            sig_state=_reload_sig if args.learn_registry else None))
        if args.reload_model_every > 0 else None)
    cfg = Config()
    if args.alerts_only and (args.scorer == "cpu"
                             or args.feedback_bootstrap):
        log.error("--alerts-only keeps features in HBM; it does not "
                  "compose with --scorer cpu or the feedback loop "
                  "(both consume host-side feature rows)")
        return 2
    if args.alerts_only and args.out:
        log.warning("--alerts-only: the analyzed output at %s will carry "
                    "zero feature columns (predictions only)", args.out)
    if args.emit_bf16 and (args.scorer == "cpu" or args.feedback_bootstrap):
        log.error("--emit-bf16 rounds the emitted feature columns; "
                  "--scorer cpu and the feedback loop re-consume them "
                  "and would drift — keep float32 emission")
        return 2
    if not 0.0 <= args.emit_threshold <= 1.0:
        log.error("--emit-threshold must be a probability in [0, 1], "
                  "got %s", args.emit_threshold)
        return 2
    if args.emit_threshold > 0:
        bad = None
        if args.alerts_only:
            bad = ("--emit-threshold emits flagged rows' features; "
                   "--alerts-only emits none — pick one")
        elif args.emit_bf16:
            bad = ("--emit-threshold already cuts feature D2H ~100x at "
                   "alert-rate traffic; it does not compose with "
                   "--emit-bf16 (the packed transfer is f32)")
        elif args.scorer == "cpu" or args.feedback_bootstrap:
            bad = ("--emit-threshold keeps clean rows' features in HBM; "
                   "--scorer cpu and the feedback loop consume every "
                   "row's features host-side")
        if bad:
            log.error(bad)
            return 2
        if args.out:
            log.info("selective emission: feature columns at %s are "
                     "populated only for rows with prob >= %.3g "
                     "(zeros elsewhere)", args.out, args.emit_threshold)
    if args.latency_slo_ms < 0:
        log.error("--latency-slo-ms must be >= 0, got %s",
                  args.latency_slo_ms)
        return 2
    if args.decode_workers < 0 or args.prefetch_batches < 0:
        log.error("--decode-workers and --prefetch-batches must be >= 0, "
                  "got %s / %s", args.decode_workers, args.prefetch_batches)
        return 2
    try:
        overload_cfg = _dc.replace(
            cfg.runtime.overload,
            enabled=args.overload,
            spill_path=args.overload_spill,
            lag_high_rows=args.overload_lag_high,
            climb_pressure=args.overload_climb_pressure,
            descend_pressure=args.overload_descend_pressure,
            climb_dwell_batches=args.overload_climb_dwell,
            descend_dwell_batches=args.overload_descend_dwell,
            max_deferred_batches=args.overload_max_deferred,
        )
    except ValueError as e:
        log.error("--overload thresholds: %s", e)
        return 2
    if args.overload:
        log.info(
            "overload ladder on: climb >= %.2f for %d, descend <= %.2f "
            "for %d, lag high %s rows, spill %r",
            overload_cfg.climb_pressure, overload_cfg.climb_dwell_batches,
            overload_cfg.descend_pressure,
            overload_cfg.descend_dwell_batches,
            overload_cfg.lag_high_rows or "off",
            overload_cfg.spill_path or "(memory only)")
    cfg = cfg.replace(runtime=_dc.replace(
        cfg.runtime,
        max_batch_rows=(args.max_batch_rows
                        or cfg.runtime.max_batch_rows),
        distributed=dist_cfg or cfg.runtime.distributed,
        emit_features=not args.alerts_only,
        emit_dtype="bfloat16" if args.emit_bf16 else "float32",
        emit_threshold=args.emit_threshold,
        pipeline_depth=args.pipeline_depth,
        coalesce_rows=args.coalesce_rows,
        use_pallas=args.use_pallas,
        z_mode=args.z_mode,
        precompile=args.precompile,
        # an SLO implies the controller: the knob is the intent
        autobatch=args.autobatch or args.latency_slo_ms > 0,
        latency_slo_ms=args.latency_slo_ms,
        sink_queue_batches=args.sink_queue_batches,
        decode_workers=args.decode_workers,
        prefetch_batches=args.prefetch_batches,
        fetch_overlap=not args.no_fetch_overlap,
        nan_guard=args.nan_guard,
        dead_letter=args.dead_letter,
        crash_loop_k=args.crash_loop_k,
        restart_backoff_ms=args.restart_backoff_ms,
        checkpoint_full_every=args.checkpoint_full_every,
        checkpoint_op_timeout_s=args.checkpoint_op_timeout,
        checkpoint_op_attempts=args.checkpoint_op_attempts,
        overload=overload_cfg,
    ))
    # Feature-plane knobs (the tiered device-resident feature store).
    if args.state_compact_every > 0 and args.key_mode != "exact":
        log.error("--state-compact-every only applies to --key-mode "
                  "exact (direct/hash tables have no slot allocator to "
                  "reclaim into)")
        return 2
    try:
        cfg = cfg.replace(features=_dc.replace(
            cfg.features,
            key_mode=args.key_mode,
            key_bits=args.key_bits,
            compact_every=args.state_compact_every,
            state_hbm_budget_mb=args.state_hbm_budget_mb,
            cold_store=args.cold_store,
            cold_segment_mb=args.cold_segment_mb,
        ))
    except ValueError as e:
        log.error("feature-plane config: %s", e)
        return 2
    if txs is not None:
        cfg = cfg.replace(features=_cover_replay_ids(cfg.features, txs, log))
    if args.state_hbm_budget_mb > 0:
        # pre-validate with the CLI convention (rc 2 + a log line, not a
        # constructor traceback); the engines enforce the same check at
        # build for non-CLI callers
        from real_time_fraud_detection_system_tpu.features.online import (
            state_bytes as _state_bytes,
        )

        need = _state_bytes(cfg.features,
                            n_shards=max(args.devices, 1))["total"]
        if need > args.state_hbm_budget_mb * 2 ** 20:
            log.error(
                "--state-hbm-budget-mb %g cannot hold the configured "
                "feature state (%.1f MB: run with a larger budget, or "
                "shrink customer/terminal capacity or cms_width)",
                args.state_hbm_budget_mb, need / 2 ** 20)
            return 2
    if args.key_mode == "exact":
        from real_time_fraud_detection_system_tpu.features.online import (
            state_bytes,
        )

        sb = state_bytes(cfg.features, n_shards=max(args.devices, 1))
        log.info(
            "tiered feature store: hot tier %d+%d slots, compaction "
            "every %s batches, state %.1f MB (dense %.1f, directory "
            "%.1f, cms %.1f)%s",
            cfg.features.customer_capacity, cfg.features.terminal_capacity,
            args.state_compact_every or "off",
            sb["total"] / 2 ** 20, sb["dense"] / 2 ** 20,
            sb["directory"] / 2 ** 20, sb["cms"] / 2 ** 20,
            f" of {args.state_hbm_budget_mb:g} MB budget"
            if args.state_hbm_budget_mb > 0 else "")
        if cfg.features.cold_store:
            log.info(
                "host cold tier: %s (segment %.1f MB) — evicted keys "
                "demote with exact rows and are promoted back before "
                "the step that scores their next row",
                cfg.features.cold_store, cfg.features.cold_segment_mb)
    cfg = cfg.replace(learn=_dc.replace(
        cfg.learn,
        registry_path=args.learn_registry,
        publish_every_labels=args.publish_every_labels,
        promote_min_labels=args.promote_min_labels,
        promote_margin=args.promote_margin,
        rollback_min_labels=args.rollback_min_labels,
        rollback_margin=args.rollback_margin,
    ))
    if args.learn_registry:
        bad = None
        if args.devices > 1 or multihost:
            bad = ("--learn-registry is not wired for the sharded "
                   "engine (--devices > 1 / multi-host)")
        elif args.scorer == "cpu":
            bad = ("--learn-registry promotes by swapping on-device "
                   "params; --scorer cpu classifies host-side with a "
                   "model fixed at startup")
        elif model.kind == "sequence":
            bad = ("shadow scoring is not wired for kind='sequence' "
                   "(no host-side feature matrix to dual-score)")
        elif args.alerts_only or args.emit_threshold > 0 or args.emit_bf16:
            bad = ("shadow scoring re-consumes every row's features "
                   "host-side; it does not compose with --alerts-only, "
                   "--emit-threshold or --emit-bf16")
        if bad:
            log.error(bad)
            return 2
        if not args.feedback_bootstrap:
            log.warning(
                "continuous learning without --feedback-bootstrap: no "
                "live labels arrive, so the shadow's live precision/"
                "recall windows stay empty and promotion never fires "
                "(the registry lineage still records reloads)")
    # Unconditional (0 resolves to auto): publishes the
    # rtfds_decode_workers gauge the README's host-plane reading uses,
    # in auto mode too.
    from real_time_fraud_detection_system_tpu.core import native

    log.info("ingest decode workers: %d",
             native.set_decode_workers(args.decode_workers))
    if model.kind in ("tree", "forest", "gbt"):
        from real_time_fraud_detection_system_tpu.models.forest import (
            resolve_z_mode,
        )

        log.info("device plane: z_mode=%s (requested %r), use_pallas=%s",
                 resolve_z_mode(args.z_mode), args.z_mode, args.use_pallas)
    cpu_model = None
    if args.scorer == "cpu":
        cpu_model = model  # TrainedModel.predict_proba runs host-side numpy

    if (args.devices > 1 or multihost) and args.scorer == "cpu":
        log.error("--scorer cpu is the single-host sklearn oracle; it does "
                  "not compose with --devices > 1 or multi-host (the "
                  "sharded engine always scores on-device)")
        return 2
    if multihost and model.kind == "sequence":
        log.error("multi-host serving is not wired for kind='sequence' "
                  "(no history-state process adoption); serve it "
                  "single-process")
        return 2

    if model.kind == "sequence":
        # fail fast with the CLI convention instead of constructor
        # tracebacks (the engines raise the same constraints)
        bad = None
        if args.scorer == "cpu":
            bad = ("--scorer cpu does not apply to kind='sequence' "
                   "(no sklearn oracle for the transformer)")
        elif args.online_lr > 0:
            bad = "online SGD is not wired for kind='sequence'"
        elif args.feedback_bootstrap:
            bad = ("the labeled-feedback loop is not wired for "
                   "kind='sequence'")
        elif args.emit_threshold > 0:
            bad = ("--emit-threshold has no effect for kind='sequence' "
                   "(no feature matrix leaves the device)")
        if bad:
            log.error(bad)
            return 2

    feature_cache = None
    make_feedback = None
    if args.feedback_bootstrap:
        from real_time_fraud_detection_system_tpu.runtime import (
            FeatureCache,
            FeedbackLoop,
            KafkaFeedbackSource,
        )

        feature_cache = FeatureCache()

        def make_feedback(engine):
            # Fresh consumer session per incarnation (group fencing).
            # Non-blocking polls: the loop runs in the scoring hot path
            # between batches, and the feedback topic is usually quiet
            # (labels arrive days late) — a blocking poll would cap
            # serving throughput.
            return FeedbackLoop(
                engine,
                KafkaFeedbackSource(args.feedback_bootstrap,
                                    topic=args.feedback_topic,
                                    poll_timeout_s=0.0),
            )

    dead_letter = None
    if args.dead_letter:
        from real_time_fraud_detection_system_tpu.io.sink import (
            make_dead_letter_sink,
        )

        dead_letter = make_dead_letter_sink(args.dead_letter)
        log.info("dead-letter queue: %s (%d row(s) already quarantined)",
                 args.dead_letter, len(dead_letter))

    learning = None
    if args.learn_registry:
        from real_time_fraud_detection_system_tpu.io.registry import (
            make_model_registry,
        )
        from real_time_fraud_detection_system_tpu.runtime.engine import (
            loss_fn_for,
        )
        from real_time_fraud_detection_system_tpu.runtime.learner import (
            LearningLoop,
            StreamingLearner,
        )

        model_registry = make_model_registry(
            args.learn_registry,
            op_timeout_s=cfg.runtime.checkpoint_op_timeout_s,
            op_attempts=cfg.runtime.checkpoint_op_attempts)
        # Restart continuity: a registry with a champion pointer is the
        # record of what should be serving — a promotion must survive a
        # process restart, so the champion artifact supersedes the
        # (bootstrap-era) --model-file params. Without this the lineage,
        # metrics and rollback baselines would all describe a model that
        # is not actually serving.
        champ_v = model_registry.champion_version()
        # False when a champion exists but could not be adopted: the
        # engines then serve --model-file params, and the learning
        # loop's version stamp must not claim they are the champion's.
        model_is_champion = True
        if champ_v is not None:
            model_is_champion = False
            try:
                champ = model_registry.champion()
            # rtfdslint: disable=broad-exception-catch (corrupt/missing champion falls back to the --model-file params; the registry names the repair path)
            except Exception as e:
                log.warning(
                    "registry champion v%s failed verification (%s: %s); "
                    "serving the --model-file params instead — repair "
                    "with `rtfds registry --verify` / --rollback",
                    champ_v, type(e).__name__, e)
            else:
                if champ.kind != model.kind:
                    log.error(
                        "registry champion v%s is kind=%r but "
                        "--model-file is kind=%r; point --learn-registry "
                        "at this model's registry or retrain",
                        champ_v, champ.kind, model.kind)
                    return 2
                log.info("serving registry champion v%s (supersedes "
                         "--model-file)", champ_v)
                model = champ
                model_is_champion = True
        learner = None
        if loss_fn_for(model.kind) is not None:
            learner = StreamingLearner(
                model.kind, model.params, model.scaler, cfg,
                model_registry,
                publish_every_labels=cfg.learn.publish_every_labels,
                window_rows=cfg.learn.window_rows,
                epochs=cfg.learn.epochs,
                max_queue=cfg.learn.queue_chunks,
                learning_rate=cfg.learn.learning_rate or None)
        else:
            log.info("model kind %r has no gradient path: the registry "
                     "records lineage and shadow-scores externally "
                     "published candidates, but no streaming learner "
                     "runs (tree ensembles retrain offline and publish "
                     "via `rtfds registry`)", model.kind)
        learning = LearningLoop(model_registry, cfg, model.kind,
                                model=model, learner=learner,
                                model_is_champion=model_is_champion)
        log.info("continuous learning on: registry %s (champion v%s)",
                 args.learn_registry, learning.champion_version)

    def make_engine():
        if args.devices > 1 or topology is not None:
            from real_time_fraud_detection_system_tpu.runtime import (
                ShardedScoringEngine,
            )

            return ShardedScoringEngine(
                cfg,
                kind=model.kind,
                params=model.params,
                scaler=model.scaler,
                n_devices=args.devices,
                online_lr=args.online_lr,
                feature_cache=feature_cache,
                dead_letter=dead_letter,
                topology=topology,
            )
        return ScoringEngine(
            cfg,
            kind=model.kind,
            params=model.params,
            scaler=model.scaler,
            scorer=args.scorer,
            cpu_model=cpu_model,
            online_lr=args.online_lr,
            feature_cache=feature_cache,
            dead_letter=dead_letter,
        )

    ckpt_dir, out_path, raw_path = (args.checkpoint_dir, args.out,
                                    args.raw_table)
    if topology is not None:
        # Shard-aware durable state: each process owns its residue
        # block's lineage under proc-NN/ of the shared roots (same
        # paths across restarts, so --resume finds the right block; a
        # topology change is refused at restore with the merge path
        # named). Sink parts split the same way — per-process
        # batch_index lineages stay individually gap/dup-free — and so
        # does the cold tier (two processes appending segments into one
        # directory would collide on segment seq numbers).
        sub = f"proc-{topology.process_id:02d}"
        ckpt_dir = os.path.join(ckpt_dir, sub) if ckpt_dir else ckpt_dir
        out_path = os.path.join(out_path, sub) if out_path else out_path
        raw_path = os.path.join(raw_path, sub) if raw_path else raw_path
        if cfg.features.cold_store:
            cfg = cfg.replace(features=_dc.replace(
                cfg.features,
                cold_store=os.path.join(cfg.features.cold_store, sub)))
    ckpt = make_checkpointer(
        ckpt_dir,
        full_every=cfg.runtime.checkpoint_full_every,
        op_timeout_s=cfg.runtime.checkpoint_op_timeout_s,
        op_attempts=cfg.runtime.checkpoint_op_attempts,
    ) if ckpt_dir else None

    # --- elastic-fleet seams (tools/multihost_launcher.py --autoscale) --
    drain_ev = None
    if args.drain_on_sigterm:
        import signal as _signal
        import threading as _threading

        drain_ev = _threading.Event()
        # idempotent: repeated SIGTERMs keep the same drain in flight;
        # the engine breaks at the NEXT batch boundary (no batch is
        # abandoned mid-flight, offsets stay behind durable output)
        _signal.signal(_signal.SIGTERM,
                       lambda _sig, _frm: drain_ev.set())
        log.info("drain-on-sigterm armed: SIGTERM = coordinated drain "
                 "to a final checkpoint, not a kill")
    cms_exchange = None
    if args.cms_exchange and topology is None:
        # Not an error: an elastic fleet passes uniform worker args and
        # legitimately shrinks to one process, where local terminal
        # aggregates are already global.
        log.info("--cms-exchange idle: single-process terminal "
                 "aggregates are already global")
    elif args.cms_exchange:
        from real_time_fraud_detection_system_tpu.runtime import (
            SketchExchange,
        )

        cms_exchange = SketchExchange(
            args.cms_exchange, topology.process_id,
            topology.n_processes)
        log.info("terminal-sketch exchange: %s (fleet-wide merge at "
                 "checkpoint boundaries, locals-only partials in "
                 "checkpoints)", args.cms_exchange)
    if drain_ev is not None or cms_exchange is not None:
        _make_engine_plain = make_engine

        def make_engine():
            eng = _make_engine_plain()
            eng.stop_event = drain_ev
            eng.cms_exchange = cms_exchange
            return eng

    resume_floors = None
    merge_old_p = merge_old_l = 0
    if args.resume_merge:
        try:
            src_root, p_s, l_s, merge_reason = \
                args.resume_merge.rsplit(":", 3)
            merge_old_p, merge_old_l = int(p_s), int(l_s)
            if not src_root or merge_old_p < 1 or merge_old_l < 1:
                raise ValueError(args.resume_merge)
        except ValueError:
            log.error("--resume-merge wants OLD_CKPT_ROOT:P:L:REASON, "
                      "got %r", args.resume_merge)
            return 2
        bad = None
        if ckpt is None:
            bad = "--resume-merge requires --checkpoint-dir"
        elif not args.resume:
            bad = ("--resume-merge requires --resume (the merged "
                   "checkpoint is what this worker resumes from)")
        elif args.source == "kafka":
            bad = ("--resume-merge does not apply to --source kafka "
                   "(broker fleets carry per-partition offsets through "
                   "a resize; no single-cursor merge is needed)")
        elif args.resume_merge_cold and not cfg.features.cold_store:
            bad = "--resume-merge-cold requires --cold-store"
        if bad:
            log.error(bad)
            return 2
        resume_floors = _resume_merge_adopt(
            make_engine, ckpt, cfg, topology,
            (src_root, merge_old_p, merge_old_l, merge_reason),
            [d for d in args.resume_merge_cold.split(",") if d],
            log)
        if resume_floors is None:
            return 2

    source_factory = None
    if args.source == "kafka":
        from real_time_fraud_detection_system_tpu.runtime.sources import (
            make_kafka_source,
        )

        kafka_kw = {}
        if topology is not None:
            # Partition-affine ingest: this process consumes ONLY its
            # block of broker partitions (manual assign — the framework
            # owns placement, not the consumer group), so no row ever
            # crosses a process boundary on the host plane.
            kafka_kw = dict(
                partitions=topology.kafka_partitions(
                    cfg.runtime.n_partitions),
                n_partitions=cfg.runtime.n_partitions,
                group_id=f"rtfds-scorer-p{topology.process_id}",
            )
            log.info("kafka partition affinity: consuming partitions %s "
                     "of %d", kafka_kw["partitions"],
                     cfg.runtime.n_partitions)

        def source_factory():
            # Fresh consumer per incarnation: a zombie session's partitions
            # are fenced off by the broker's group generation.
            return make_kafka_source(
                args.bootstrap, topic=args.topic,
                batch_rows=args.batch_rows,
                idle_timeout_s=args.idle_timeout or None,
                **kafka_kw,
            )

        source = source_factory()
    elif args.source == "raw-table":
        from real_time_fraud_detection_system_tpu.runtime.sources import (
            RawTableSource,
        )

        try:
            source = RawTableSource(
                args.data,
                batch_rows=args.batch_rows,
                from_day=args.from_date or None,
                to_day=args.to_date or None,
            )
        except (FileNotFoundError, ValueError) as e:
            log.error("%s", e)
            return 2
        log.info("raw-table backfill: %d rows", source.n)
    else:
        source = ReplaySource(
            txs,
            _start_epoch_s(args.start_date),
            batch_rows=args.batch_rows,
            mode=args.mode,
            with_labels=args.online_lr > 0,
        )
    if resume_floors and len(set(resume_floors)) > 1:
        # Post-merge resume with DIVERGED old-process cursors: drop
        # re-polled rows the further-ahead old owners already sank.
        # Inside the affine wrap below — floors index the shared
        # stream's positions, pre-slicing.
        from real_time_fraud_detection_system_tpu.runtime import (
            OwnershipFloorSource,
        )

        source = OwnershipFloorSource(source, resume_floors,
                                      merge_old_p, merge_old_l)
        log.info("per-owner resume floors active: %s (pure passthrough "
                 "past position %d)", resume_floors, max(resume_floors))
    if topology is not None and args.source != "kafka":
        # Residue-sliced ingest for partition-less sources: this process
        # serves only its owned customer residues of the shared stream
        # (Kafka fleets got true partition assignment above instead).
        # Wrapped INSIDE any prefetch below, so the producer thread
        # prefetches already-sliced batches.
        from real_time_fraud_detection_system_tpu.runtime import (
            PartitionAffineSource,
        )

        source = PartitionAffineSource(source, topology)
        log.info("partition-affine ingest: serving residues [%d, %d) "
                 "of %d", topology.owned_shards.start,
                 topology.owned_shards.stop, topology.n_shards_total)
    if cfg.runtime.prefetch_batches > 0:
        # Background source prefetch: poll + decode run ahead of the
        # loop on a producer thread. Wrapped OUTSIDE any fault injectors
        # the source may carry, and re-wrapped per incarnation via the
        # factory (supervised mode) so each restart owns a fresh
        # producer generation. Offsets commit on consumption; poison
        # isolation flips the wrapper to synchronous serving.
        from real_time_fraud_detection_system_tpu.runtime import (
            PrefetchSource,
        )

        depth = cfg.runtime.prefetch_batches
        if source_factory is not None:
            inner_factory = source_factory

            def source_factory():
                return PrefetchSource(inner_factory(), max_batches=depth)

        source = PrefetchSource(source, max_batches=depth)
        log.info("source prefetch on (queue depth %d)", depth)
    sink = make_parquet_sink(out_path) if out_path else None
    raw_table = None
    if args.raw_table:
        from real_time_fraud_detection_system_tpu.io import (
            RawTransactionsTable,
        )
        from real_time_fraud_detection_system_tpu.io.sink import FanoutSink

        raw_table = RawTransactionsTable(raw_path,
                                         flush_every_batches=64)
        sink = FanoutSink(sink, raw_table)
    if args.max_restarts > 0 and ckpt is None:
        log.error("--max-restarts requires --checkpoint-dir "
                  "(there is nothing to recover from without checkpoints)")
        return 2
    if args.stall_timeout > 0 and not (args.max_restarts > 0 and ckpt):
        log.error("--stall-timeout requires supervised mode "
                  "(--max-restarts with --checkpoint-dir); without it the "
                  "watchdog has no restart path to escalate into")
        return 2
    from real_time_fraud_detection_system_tpu.utils import profile_to

    if args.trace_dir and args.source == "kafka" and not args.max_batches:
        # jax.profiler buffers the whole trace in host memory until
        # stop_trace; an unbounded live stream would grow it without limit.
        log.warning(
            "--trace-dir on an unbounded Kafka stream traces the ENTIRE "
            "run and buffers it in host memory; bound the run with "
            "--max-batches for a usable trace"
        )

    server = None
    recorder = None
    tracer = None
    if args.trace_out or args.metrics_port:
        from real_time_fraud_detection_system_tpu.utils.trace import (
            get_tracer,
        )

        # Span tracing for the serving run: per-batch waterfalls as
        # Chrome-trace JSON (Perfetto / chrome://tracing / `rtfds
        # trace`). The ring buffer keeps the most recent spans, so an
        # unbounded stream stays memory-bounded — unlike --trace-dir's
        # full jax.profiler capture. A --metrics-port run enables it
        # too (µs/batch): GET /trace must serve a live timeline, not a
        # silently empty one.
        tracer = get_tracer().configure(enabled=True)
        if args.trace_out:
            log.info("span tracing on: will export %s", args.trace_out)
        else:
            log.info("span tracing on: GET /trace serves the live "
                     "span ring buffer")
    if args.metrics_port or args.flight_record:
        from real_time_fraud_detection_system_tpu.utils.metrics import (
            FlightRecorder,
            MetricsServer,
            run_manifest,
            set_active_recorder,
        )
    if args.metrics_port:
        # Opt-in ops endpoints for the serve loop: /metrics (Prometheus
        # text), /metrics.json, /healthz (source lag + last-batch-age).
        # 0.0.0.0 so a scrape sidecar / probe can reach it in-container.
        server = MetricsServer(
            port=args.metrics_port, host="0.0.0.0",
            max_batch_age_s=args.healthz_max_batch_age,
            max_source_lag_rows=args.healthz_max_lag_rows or None)
        server.start()
        log.info("telemetry: /metrics /metrics.json /healthz on port %d",
                 server.port)
    if args.flight_record:
        recorder = FlightRecorder(
            args.flight_record,
            manifest=run_manifest(
                cfg=cfg, model_kind=model.kind, scorer=args.scorer,
                source=args.source, devices=args.devices),
            max_bytes=int(args.flight_record_max_mb * 2 ** 20)
            if args.flight_record_max_mb > 0 else None)
        # process-wide: the engine loop, checkpointer, supervisor, and
        # fault injectors all append to this run's record
        set_active_recorder(recorder)
        log.info("flight record: %s", args.flight_record)

    fb = None
    try:
        with profile_to(args.trace_dir or None):
            if ckpt is not None and args.max_restarts > 0:
                # Supervised mode: restart-on-failure with checkpoint replay
                # (the compose `restart: on-failure` + Spark checkpoint
                # contract).
                from real_time_fraud_detection_system_tpu.runtime.faults import (
                    RetryPolicy,
                    run_with_recovery,
                )

                backoff = None
                if args.restart_backoff_ms > 0:
                    # doubling, full jitter, capped at 30 s — the
                    # fleet-safe default curve; the knob sets the base
                    backoff = RetryPolicy(
                        base_delay_s=args.restart_backoff_ms / 1000.0,
                        multiplier=2.0, max_delay_s=30.0, jitter=1.0)
                stats = run_with_recovery(
                    make_engine, source, ckpt, sink=sink,
                    max_restarts=args.max_restarts, max_batches=args.max_batches,
                    resume=args.resume, stall_timeout_s=args.stall_timeout,
                    make_source=source_factory, make_feedback=make_feedback,
                    make_model_reload=make_reloader,
                    learning=learning,
                    crash_loop_k=args.crash_loop_k,
                    dead_letter=dead_letter,
                    restart_backoff=backoff,
                )
            else:
                engine = make_engine()
                if ckpt is not None and args.resume:
                    restored = ckpt.restore(engine.state)
                    if restored is not None:
                        source.seek(engine.state.offsets)
                        log.info("resumed from batch %d",
                                 engine.state.batches_done)
                    truncate = getattr(sink, "truncate_after", None)
                    if truncate is not None:
                        truncate(engine.state.batches_done)
                fb = make_feedback(engine) if make_feedback else None
                stats = engine.run(
                    source, sink=sink, checkpointer=ckpt,
                    max_batches=args.max_batches, feedback=fb,
                    model_reload=make_reloader() if make_reloader else None,
                    learning=learning,
                )
                if drain_ev is not None and ckpt is not None:
                    # Drain-armed worker: run() ended (SIGTERM break OR
                    # natural stream end) at a batch boundary with the
                    # sink drained and cold lineage refreshed — pin the
                    # FINAL checkpoint to that exact frontier so a
                    # resize merge resumes gap/dup-free (deferred/shed
                    # rows sit behind these offsets by the overload
                    # defer contract and re-poll under the new fleet; a
                    # stale cadence checkpoint would replay rows the
                    # sink already holds).
                    ckpt.save(engine.checkpoint_state())
                    if drain_ev.is_set():
                        stats["drained_at_batch"] = \
                            engine.state.batches_done
                        log.info("coordinated drain complete: final "
                                 "checkpoint at batch %d",
                                 engine.state.batches_done)
    finally:
        close = getattr(source, "close", None)
        if close is not None:
            close()
        if fb is not None:
            fb.close()
        if learning is not None:
            learning.close()
        if recorder is not None:
            set_active_recorder(None)
            recorder.close()
        if server is not None:
            server.stop()
        if args.metrics_dump:
            # success or failure: the registry snapshot is how the
            # multihost smoke asserts recompile counts per worker
            # without scraping a live port
            from real_time_fraud_detection_system_tpu.utils.metrics \
                import get_registry

            try:
                with open(args.metrics_dump, "w", encoding="utf-8") as f:
                    json.dump(get_registry().snapshot(), f)
            except OSError as e:
                log.warning("metrics dump to %s failed: %s",
                            args.metrics_dump, e)
        if tracer is not None and args.trace_out:
            # export even on a failed run — a crash mid-stream is
            # exactly when the last batches' waterfalls matter
            try:
                man = tracer.export(args.trace_out)
                log.info("span trace: %s (%d events) — summarize with "
                         "`rtfds trace --trace %s`, or load in "
                         "ui.perfetto.dev", man["trace"], man["events"],
                         args.trace_out)
            except OSError as e:
                log.warning("span trace export to %s failed: %s",
                            args.trace_out, e)
    if raw_table is not None:
        raw_table.flush()
        stats["raw_tx_rows"] = len(raw_table)
    if dead_letter is not None:
        stats["dead_letter_rows"] = len(dead_letter)
        close_dlq = getattr(dead_letter, "close", None)
        if close_dlq is not None:
            close_dlq()
    if topology is not None:
        stats.update(
            num_processes=topology.n_processes,
            process_id=topology.process_id,
            owned_shards=[topology.owned_shards.start,
                          topology.owned_shards.stop],
        )
    log.info("done: %s", stats)
    print(_json_line({"scorer": args.scorer, **stats}))
    return 0


def cmd_warmup(args) -> int:
    """AOT-compile the serving step for every batch bucket, then exit.

    Run once per deploy (or in an init container): every bucket size ×
    step variant is ``.lower(...).compile()``d through the persistent
    compilation cache (``utils.enable_compilation_cache``), so the
    serving process that follows — with or without ``--precompile`` —
    starts warm instead of paying per-bucket XLA compiles inside the
    stream (969 ms measured vs 8 ms steady-state per first-touch
    bucket). Pass the same serving-shape flags you will serve with
    (``--devices``, ``--online-lr``, emission mode): they change the
    step's compiled program."""
    import dataclasses as _dc
    import time as _time

    from real_time_fraud_detection_system_tpu.config import Config
    from real_time_fraud_detection_system_tpu.io.artifacts import load_model
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine
    from real_time_fraud_detection_system_tpu.utils import get_logger

    log = get_logger("warmup")
    model = load_model(args.model_file)
    cfg = Config()
    cfg = cfg.replace(runtime=_dc.replace(
        cfg.runtime,
        emit_features=not args.alerts_only,
        emit_threshold=args.emit_threshold,
        emit_dtype="bfloat16" if args.emit_bf16 else "float32",
        use_pallas=args.use_pallas,
        z_mode=args.z_mode,
        precompile=True,
    ))
    t0 = _time.perf_counter()
    if args.devices > 1:
        from real_time_fraud_detection_system_tpu.runtime import (
            ShardedScoringEngine,
        )

        engine = ShardedScoringEngine(
            cfg, kind=model.kind, params=model.params, scaler=model.scaler,
            n_devices=args.devices, online_lr=args.online_lr)
    else:
        engine = ScoringEngine(
            cfg, kind=model.kind, params=model.params, scaler=model.scaler,
            online_lr=args.online_lr)
    man = engine.precompile()
    out = {
        "kind": model.kind,
        "devices": args.devices,
        "buckets": man["buckets"],
        "variants": man["variants"],
        "compile_seconds": man["seconds"],
        "total_seconds": round(_time.perf_counter() - t0, 3),
    }
    log.info("warmup done: %s", out)
    print(_json_line(out))
    return 0


def cmd_dlq(args) -> int:
    """Inspect / replay dead-letter-queue rows (the poison quarantine).

    Inspection prints a one-line summary (rows by reason/error) plus up
    to ``--limit`` row records as JSON lines. ``--replay`` re-scores the
    quarantined rows through a fresh engine built from ``--model-file``
    — the post-fix triage tool: rows that now score cleanly print a
    prediction, rows that still crash print their error and stay
    quarantined. Replay runs against FRESH feature state (window
    aggregates start empty), so it answers "does this row still crash?",
    not "what would its production score have been" — re-run the stream
    for that."""
    from real_time_fraud_detection_system_tpu.io.sink import (
        read_dead_letter,
    )
    from real_time_fraud_detection_system_tpu.utils import get_logger

    log = get_logger("dlq")
    try:
        rows = read_dead_letter(args.path)
    except FileNotFoundError as e:
        print(_json_line({"error": str(e)}))
        return 2
    by_reason: dict = {}
    by_error: dict = {}
    for r in rows:
        by_reason[r.get("reason", "?")] = \
            by_reason.get(r.get("reason", "?"), 0) + 1
        etype = str(r.get("error", ""))[:60].split(":")[0] or "?"
        by_error[etype] = by_error.get(etype, 0) + 1
    summary = {
        "path": args.path,
        "rows": len(rows),
        "by_reason": by_reason,
        "by_error_type": by_error,
        "batches": sorted({int(r.get("batch_index", -1)) for r in rows}),
    }
    if not args.replay:
        print(_json_line(summary))
        for r in rows[: max(args.limit, 0)]:
            print(_json_line(r))
        if args.limit and len(rows) > args.limit:
            print(_json_line({"truncated": True, "limit": args.limit}))
        return 0
    if not args.model_file:
        log.error("--replay needs --model-file")
        return 2
    if not rows:
        print(_json_line({**summary, "replayed": 0}))
        return 0
    from real_time_fraud_detection_system_tpu.config import Config
    from real_time_fraud_detection_system_tpu.io.artifacts import load_model
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine

    model = load_model(args.model_file)
    need = ("tx_id", "tx_datetime_us", "customer_id", "terminal_id",
            "tx_amount_cents", "kafka_ts_ms")

    def row_cols(recs):
        return {k: np.asarray([int(r["columns"].get(k, 0)) for r in recs],
                              dtype=np.int64) for k in need}

    def fresh_engine():
        return ScoringEngine(Config(), kind=model.kind,
                             params=model.params, scaler=model.scaler)

    out = []
    try:
        res = fresh_engine().process_batch(row_cols(rows))
        probs = {int(t): float(p) for t, p in zip(res.tx_id, res.probs)}
        for r in rows:
            out.append({"tx_id": r["tx_id"], "reason": r.get("reason"),
                        "prediction": probs.get(int(r["tx_id"]))})
    # rtfdslint: disable=broad-exception-catch (DLQ replay triage: the batch probe exists to catch WHATEVER the poison rows throw, then re-probe row-by-row)
    except Exception:
        # at least one row still crashes: probe row-by-row so the clean
        # ones still get a score and the poison names itself
        for r in rows:
            try:
                res = fresh_engine().process_batch(row_cols([r]))
                out.append({
                    "tx_id": r["tx_id"], "reason": r.get("reason"),
                    "prediction": float(res.probs[0]) if len(res.probs)
                    else None})
            # rtfdslint: disable=broad-exception-catch (per-row triage: a still-poison row reports its error type in the JSON verdict instead of aborting the replay)
            except Exception as e:
                out.append({"tx_id": r["tx_id"], "reason": r.get("reason"),
                            "error": f"{type(e).__name__}: {e}"[:200],
                            "still_poison": True})
    print(_json_line({**summary, "replayed": len(out)}))
    for o in out:
        print(_json_line(o))
    return 0


def cmd_ckpt(args) -> int:
    """Inspect / verify the checkpoint lineage (the durable-state plane).

    Default: list every live checkpoint with kind (full/delta/v1), size,
    age, batch counter, and a cheap validity verdict. ``--verify``
    re-checksums every live checkpoint AND its delta chain (the deploy
    preflight: exit 1 on any corruption, so a rollout gates on a
    restorable lineage). ``--inspect NAME`` dumps one checkpoint's
    manifest (per-leaf CRCs, fingerprint, incarnation, chain link).
    """
    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        make_checkpointer,
    )
    from real_time_fraud_detection_system_tpu.utils import get_logger

    log = get_logger("ckpt")
    try:
        ck = make_checkpointer(args.path)
    # rtfdslint: disable=broad-exception-catch (bad URL/creds/store backend → rc 2 usage error with the cause printed; a triage CLI must report, not traceback)
    except Exception as e:
        log.error("cannot open checkpoint lineage at %s: %s", args.path, e)
        return 2
    if args.inspect:
        try:
            man = ck.manifest(args.inspect)
        except KeyError:
            log.error("no checkpoint named %s under %s", args.inspect,
                      args.path)
            return 2
        # rtfdslint: disable=broad-exception-catch (corrupt manifest is the FINDING this preflight exists to report — rc 1 with the error, whatever its type)
        except Exception as e:
            print(_json_line({"path": args.inspect, "valid": False,
                              "error": f"{type(e).__name__}: {e}"[:300]}))
            return 1
        from real_time_fraud_detection_system_tpu.io.checkpoint import (
            feature_state_report,
        )

        fs = feature_state_report(man)
        if fs is not None:
            # named feature-state leaves with per-shard byte attribution
            # + writer-recorded directory occupancy: state skew visible
            # from the manifest, no restore needed
            man = {**man, "feature_state": fs}
        meta = man.get("meta") or {}
        pc = int(meta.get("process_count", 1) or 1)
        ld = int(meta.get("layout_devices", 1) or 1)
        # writer topology from the manifest alone: which residue block
        # this entry holds, and how wide the fleet's shard space was —
        # the preflight that catches a topology-mismatched relaunch
        # before restore refuses it
        man = {**man, "topology": {
            "process_count": pc,
            "process_id": int(meta.get("process_id", 0) or 0),
            "layout_devices": ld,
            "fleet_shards_total": pc * ld,
        }}
        if meta.get("resize_epochs"):
            # Elastic-resize lineage from the manifest alone: every
            # fleet P→P′ this state lived through, with the per-old-
            # owner resume floors that made the transition exact.
            man = {**man, "resize_epochs": meta["resize_epochs"]}
        print(_json_line({"path": args.inspect, **man}))
        return 0
    # listing stays cheap (one read per entry); only --verify pays for
    # the full chain re-checksum
    report = ck.verify_all(deep=bool(args.verify))
    n_bad = sum(1 for e in report if not e.get("valid"))
    summary = {
        "path": args.path,
        "checkpoints": len(report),
        "corrupt": n_bad,
        "latest": ck.latest(),
    }
    print(_json_line(summary))
    for e in report:
        if not args.verify:
            # listing mode: drop the verbose corruption detail
            e = {k: v for k, v in e.items() if k != "detail"}
        print(_json_line(e))
    if args.verify and n_bad:
        log.error("%d corrupt checkpoint(s) in the lineage — restore "
                  "would fall back past them; quarantine or rebuild "
                  "before deploying", n_bad)
        return 1
    return 0


def cmd_registry(args) -> int:
    """Inspect / verify / roll back the versioned model registry (the
    continuous-learning artifact plane — `rtfds ckpt`'s model twin).

    Default: one row per live version (kind, size, parent lineage,
    source, labels trained, champion flag). ``--verify`` re-hashes every
    artifact against its manifest AND its internal content hash (deploy
    preflight: exit 1 on any corruption — a corrupt candidate must never
    reach a promotion gate). ``--inspect N`` dumps one version's
    manifest. ``--promote N`` verifies THEN moves the champion pointer;
    ``--rollback`` pops it back to the previous champion.
    """
    from real_time_fraud_detection_system_tpu.io.artifacts import (
        CorruptModelError,
    )
    from real_time_fraud_detection_system_tpu.io.registry import (
        make_model_registry,
    )
    from real_time_fraud_detection_system_tpu.utils import get_logger

    log = get_logger("registry")
    try:
        reg = make_model_registry(args.path)
    # rtfdslint: disable=broad-exception-catch (bad URL/creds/store backend → rc 2 usage error with the cause printed; a triage CLI must report, not traceback)
    except Exception as e:
        log.error("cannot open model registry at %s: %s", args.path, e)
        return 2
    if args.publish:
        from real_time_fraud_detection_system_tpu.io.artifacts import (
            load_model,
        )

        try:
            m = load_model(args.publish)  # content-hash verified
        except CorruptModelError as e:
            log.error("refusing to publish %s: artifact failed "
                      "verification (%s)", args.publish, e.reason)
            return 1
        # rtfdslint: disable=broad-exception-catch (missing file / bad npz / OS error all mean "cannot publish this artifact" → rc 2 with the cause)
        except Exception as e:
            log.error("cannot load model artifact %s: %s",
                      args.publish, e)
            return 2
        v = reg.publish(m, parent=reg.champion_version(), source="cli",
                        note=args.publish)
        print(_json_line({"published": v, "kind": m.kind,
                          "parent": reg.champion_version()}))
        return 0
    if args.rollback:
        prev = reg.rollback()
        if prev is None:
            log.error("no promotion history to roll back to")
            return 1
        print(_json_line({"champion": prev, "by": "rollback"}))
        return 0
    if args.promote:
        try:
            reg.get(args.promote)  # verify AT the gate, like the loop
        except KeyError:
            log.error("no version %d in the registry", args.promote)
            return 2
        except CorruptModelError as e:
            log.error("version %d failed verification (%s) and was "
                      "quarantined — it can never be promoted",
                      args.promote, e.reason)
            return 1
        ptr = reg.promote(args.promote, by="cli")
        print(_json_line(ptr))
        return 0
    if args.inspect:
        try:
            man = reg.meta(args.inspect)
        except KeyError:
            log.error("no version %d in the registry", args.inspect)
            return 2
        except CorruptModelError as e:
            log.error("manifest for version %d is corrupt (%s)",
                      args.inspect, e.reason)
            return 1
        print(_json_line(man))
        return 0
    if args.verify:
        report = reg.verify_all()
        n_bad = sum(1 for e in report if not e.get("valid"))
        print(_json_line({"path": args.path, "versions": len(report),
                          "corrupt": n_bad,
                          "champion": reg.champion_version()}))
        for e in report:
            print(_json_line(e))
        if n_bad:
            log.error("%d corrupt artifact(s) still listed in the "
                      "registry (the preflight never quarantines; each "
                      "will be quarantined on its first read and can "
                      "never be promoted) — republish or roll back "
                      "before deploying", n_bad)
            return 1
        return 0
    print(_json_line({"path": args.path,
                      "champion": reg.champion_version()}))
    for row in reg.list_versions():
        print(_json_line(row))
    return 0


def cmd_demo(args) -> int:
    """Full E2E demo: generate → CDC envelopes → sink jobs → score.

    The in-process equivalent of the reference's `make up && make
    load_initial_data && make connectors && make run-all` flow (README.md:
    31-43) with the datagen container driving it.
    """
    from real_time_fraud_detection_system_tpu.config import (
        Config,
        DataConfig,
        FeatureConfig,
        TrainConfig,
    )
    from real_time_fraud_detection_system_tpu.runtime.pipeline import run_demo
    from real_time_fraud_detection_system_tpu.utils.logging import get_logger

    log = get_logger("demo")
    if args.out.startswith("s3://"):
        # run_demo also lands a local raw table + dashboard beside the
        # analyzed parts; object-store output is the serving path's job.
        log.error("rtfds demo writes a local output directory (analyzed "
                  "parts + raw table + dashboard); for s3:// output use "
                  "rtfds score --out s3://...")
        return 2
    cfg = Config(
        data=DataConfig(
            n_customers=args.customers,
            n_terminals=args.terminals,
            n_days=args.days,
            seed=args.seed,
        ),
        features=FeatureConfig(
            customer_capacity=_pow2_capacity_for(args.customers),
            terminal_capacity=_pow2_capacity_for(args.terminals),
        ),
        train=TrainConfig(
            delta_train_days=args.delta_train,
            delta_delay_days=args.delta_delay,
            delta_test_days=args.delta_test,
        ),
    )
    model = None
    if args.model_file:
        from real_time_fraud_detection_system_tpu.io.artifacts import (
            load_model,
        )

        model = load_model(args.model_file)
        log.info("loaded model %s from %s", model.kind, args.model_file)
    summary = run_demo(
        cfg,
        model=model,
        model_kind=args.model,
        out_dir=args.out or None,
        batch_rows=args.batch_rows,
        n_devices=args.devices,
    )
    if args.out:
        # Close the loop the way the reference demo does — README.md:31-43
        # ends at the Superset dashboard; here it ends at the static one.
        # A dashboard failure must not discard the already-computed summary.
        from real_time_fraud_detection_system_tpu.io.dashboard import (
            write_dashboard,
        )

        try:
            dash = write_dashboard(
                args.out, os.path.join(args.out, "dashboard.html"))
            summary["dashboard"] = dash["dashboard"]
        except OSError as e:
            log.warning("dashboard render failed: %s", e)
            summary["dashboard_error"] = str(e)
    print(_json_line(summary))
    return 0


def _cover_replay_ids(features, txs, log):
    """``key_mode="direct"`` maps a key to slot ``key & (capacity - 1)``:
    ids past the configured capacities would share window state in
    silence. A replay knows its ids before it builds the engine, so grow
    the tables (never shrink them) to the power of two that holds the
    largest one. Live sources (kafka, raw-table) cannot be asked; there
    the configured capacities stand."""
    if features.key_mode != "direct" or not len(txs.customer_id):
        return features
    grown = {}
    for field, ids in (("customer_capacity", txs.customer_id),
                       ("terminal_capacity", txs.terminal_id)):
        need = _pow2_capacity_for(-(-(int(ids.max()) + 1) // 2))
        if need > getattr(features, field):
            log.info("%s %d -> %d: the replay's largest id is %d",
                     field, getattr(features, field), need, int(ids.max()))
            grown[field] = need
    import dataclasses

    return dataclasses.replace(features, **grown) if grown else features


def _pow2_capacity_for(n: int) -> int:
    """Smallest power of two >= 2n — direct-mode slot capacity with 2x
    headroom over the live key count."""
    p = 1
    while p < 2 * n:
        p *= 2
    return p


def cmd_query(args) -> int:
    """Dashboard reports over analyzed output (the Trino/Superset role)."""
    from real_time_fraud_detection_system_tpu.io.query import (
        load_analyzed,
        raw_transactions_report,
        report,
    )

    if args.report == "transactions":
        # Raw-table report: --data is the day-partitioned table directory
        # (e.g. <demo-out>/transactions).
        try:
            print(_json_line(raw_transactions_report(args.data)))
        except FileNotFoundError as e:
            print(_json_line({"error": str(e)}))
            return 2
        return 0
    cols = load_analyzed(args.data)
    out = report(cols, kind=args.report, threshold=args.threshold,
                 k=args.top_k, bucket=args.bucket)
    print(_json_line(out))
    return 0


def cmd_sql(args) -> int:
    """Ad-hoc SQL over the analyzed output — the Trino role, in-process.

    Mounts the ParquetSink directory as an ``analyzed`` table (DuckDB
    when installed, else pyarrow+sqlite; latest-wins dedup view either
    way) and prints the result as JSON lines, one object per row.
    """
    from real_time_fraud_detection_system_tpu.io.sqlquery import (
        AnalyzedSql,
    )

    limit = max(0, args.limit)  # <= 0 means unlimited
    try:
        db = AnalyzedSql(args.data)
    # rtfdslint: disable=broad-exception-catch (the JSON error contract holds for EVERY open failure — corrupt part file, permissions, missing dir — not just FileNotFoundError)
    except Exception as e:
        # corrupt part file / permissions / missing dir: the JSON error
        # contract holds for every failure, not just FileNotFoundError
        print(_json_line({"error": f"{type(e).__name__}: {e}"}))
        return 2
    try:
        # fetch one row past the limit: bounds memory on huge results
        # while still detecting truncation
        names, rows = db.query(args.query,
                               max_rows=limit + 1 if limit else 0)
    # rtfdslint: disable=broad-exception-catch (same JSON error contract for query execution: sqlite/duckdb/pyarrow each raise their own types)
    except Exception as e:
        print(_json_line({"error": f"{type(e).__name__}: {e}"}))
        return 2
    finally:
        db.close()
    shown = rows[:limit] if limit else rows
    for r in shown:
        print(_json_line(dict(zip(names, r))))
    if limit and len(rows) > limit:
        print(_json_line({"truncated": True, "limit": limit}))
    return 0


def cmd_import_model(args) -> int:
    """Convert the reference's pickled artifacts into the npz model.

    The reference ships ``trained_model.pkl`` (a fitted sklearn
    classifier, uploaded to S3 by ``load_initial_data.py:269-287``) and
    ``scaler.pkl`` (joblib StandardScaler, ``model_training.ipynb ·
    cell 31``). This imports both into the framework's pickle-free npz
    (``io/artifacts.py``) so existing reference artifacts serve on TPU
    unchanged: RandomForest/DecisionTree → flat node tables, XGBClassifier
    → GBT leaf-sum form (xgboost import-gated), LogisticRegression →
    logreg weights. Unpickling EXECUTES code — import only artifacts you
    trust (your own training output)."""
    import pickle

    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.features.spec import (
        FEATURE_NAMES,
    )
    from real_time_fraud_detection_system_tpu.io.artifacts import save_model
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import TrainedModel
    from real_time_fraud_detection_system_tpu.utils import get_logger

    log = get_logger("import-model")
    n_features = len(FEATURE_NAMES)
    if args.model_pkl.startswith("s3://"):
        # the reference keeps trained_model.pkl in the object store
        # (s3://commerce/trained_model.pkl, load_initial_data.py:269-287)
        import io as _io

        from real_time_fraud_detection_system_tpu.io.artifacts import (
            _split_s3_url,
        )
        from real_time_fraud_detection_system_tpu.io.store import make_store

        try:
            url, key = _split_s3_url(args.model_pkl)
        except ValueError as e:
            log.error("%s", e)
            return 2
        clf = pickle.load(_io.BytesIO(make_store(url).get(key)))
    else:
        with open(args.model_pkl, "rb") as f:
            clf = pickle.load(f)

    # Fail loudly on shape/class mismatches: a 20-feature or multiclass
    # model would otherwise import cleanly and serve silently-wrong
    # probabilities (tree feature gathers clamp out-of-range indices).
    n_in = getattr(clf, "n_features_in_", None)
    if n_in is not None and int(n_in) != n_features:
        log.error("model was fitted on %d features; the serving feature "
                  "vector has %d (features/spec.py)", int(n_in), n_features)
        return 2
    classes = getattr(clf, "classes_", None)
    if classes is not None and len(classes) != 2:
        log.error("binary classifiers only: model has %d classes",
                  len(classes))
        return 2
    # Same count in a different COLUMN ORDER would also serve
    # silently-wrong probabilities; when the pickle recorded its fitted
    # feature names (sklearn ≥1.0 with a DataFrame fit), require them to
    # match the serving order exactly.
    names = getattr(clf, "feature_names_in_", None)
    if names is not None:
        from real_time_fraud_detection_system_tpu.features.spec import (
            FEATURE_NAMES,
        )

        got = [str(x) for x in names]
        if got != list(FEATURE_NAMES):
            log.error(
                "model was fitted on feature names/order %s; the serving "
                "vector is %s (features/spec.py) — re-export the model "
                "with the serving column order", got, list(FEATURE_NAMES))
            return 2

    if args.scaler_pkl:
        import joblib  # ships with sklearn

        sk_scaler = joblib.load(args.scaler_pkl)
        if len(np.asarray(sk_scaler.mean_)) != n_features:
            log.error("scaler was fitted on %d features; expected %d",
                      len(np.asarray(sk_scaler.mean_)), n_features)
            return 2
        scaler = Scaler(
            mean=jnp.asarray(sk_scaler.mean_, jnp.float32),
            scale=jnp.asarray(sk_scaler.scale_, jnp.float32),
        )
    else:
        # identity scaling (model trained on raw features)
        scaler = Scaler(mean=jnp.zeros(n_features, jnp.float32),
                        scale=jnp.ones(n_features, jnp.float32))

    name = type(clf).__name__
    if name in ("RandomForestClassifier", "ExtraTreesClassifier",
                "DecisionTreeClassifier"):
        from real_time_fraud_detection_system_tpu.models.forest import (
            ensemble_from_sklearn,
        )

        kind = "tree" if name == "DecisionTreeClassifier" else "forest"
        params = ensemble_from_sklearn(clf, n_features)
    elif name == "XGBClassifier":
        from real_time_fraud_detection_system_tpu.models.gbt import (
            gbt_from_xgboost,
        )

        kind = "gbt"
        params = gbt_from_xgboost(clf, n_features)
    elif name == "LogisticRegression":
        from real_time_fraud_detection_system_tpu.models.logreg import (
            LogRegParams,
        )

        kind = "logreg"
        params = LogRegParams(
            w=jnp.asarray(clf.coef_[0], jnp.float32),
            b=jnp.asarray(clf.intercept_[0], jnp.float32),
        )
    else:
        log.error("unsupported classifier type %s (supported: "
                  "RandomForest/ExtraTrees/DecisionTree/XGB/"
                  "LogisticRegression)", name)
        return 2

    model = TrainedModel(kind=kind, scaler=scaler, params=params)
    save_model(args.out_model, model)
    log.info("imported %s (%s) -> %s", args.model_pkl, kind, args.out_model)
    print(_json_line({"kind": kind, "out_model": args.out_model,
                      "n_features": n_features}))
    return 0


def cmd_connectors(args) -> int:
    """Register the Debezium Postgres source connector with Kafka Connect.

    The reference's ``make connectors`` POSTs its connector JSON to the
    Connect REST API (``Makefile:21-22`` → ``:8083/connectors/``, config
    at ``connect/pg-src-connector.json``: PostgresConnector, tasks.max 1,
    schema include ``payment``, topic prefix ``debezium``). Same here,
    stdlib-only; 409 Conflict (already registered) is success."""
    import urllib.error
    import urllib.request

    from real_time_fraud_detection_system_tpu.utils import get_logger

    log = get_logger("connectors")
    body = {
        "name": args.name,
        "config": {
            "connector.class":
                "io.debezium.connector.postgresql.PostgresConnector",
            "tasks.max": "1",
            "database.hostname": args.db_host,
            "database.port": str(args.db_port),
            "database.user": args.db_user,
            "database.password": args.db_password,
            "database.dbname": args.db_name,
            "database.include.list": args.db_name,
            "schema.include.list": args.schema,
            "topic.prefix": args.topic_prefix,
        },
    }
    url = args.connect_url.rstrip("/") + "/connectors/"
    req = urllib.request.Request(
        url,
        data=json.dumps(body).encode(),
        headers={"Accept": "application/json",
                 "Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=args.timeout) as resp:
            raw = resp.read() or b"{}"
            try:
                payload = json.loads(raw)
            except json.JSONDecodeError:
                # a 2xx from something that is NOT Kafka Connect
                log.error("non-JSON response from %s (is this really the "
                          "Connect REST API?): %r", url, raw[:120])
                return 1
            # Connect echoes the full config back — redact the secret
            # before it can reach stdout/CI logs
            if isinstance(payload, dict):
                cfg_echo = payload.get("config")
                if isinstance(cfg_echo, dict) and "database.password" in cfg_echo:
                    cfg_echo["database.password"] = "***"
            out = {"status": resp.status,
                   "connector": args.name,
                   "response": payload}
    except urllib.error.HTTPError as e:
        if e.code == 409:
            out = {"status": 409, "connector": args.name,
                   "already_registered": True}
        else:
            log.error("connect REST error %s: %s", e.code,
                      e.read()[:200].decode(errors="replace"))
            return 1
    except (urllib.error.URLError, OSError) as e:
        log.error("cannot reach Kafka Connect at %s: %s", url, e)
        return 1
    print(_json_line(out))
    return 0


def cmd_dashboard(args) -> int:
    """Render the static-HTML ops dashboard (the Superset role)."""
    from real_time_fraud_detection_system_tpu.io.dashboard import (
        write_dashboard,
        write_ops_dashboard,
    )

    if bool(args.data) == bool(args.flight_record):
        # exactly one input: each view is a full page written to --out,
        # so taking both would silently drop one of them
        print(_json_line(
            {"error": "pass exactly one of --data (analyzed view) or "
                      "--flight-record (ops-health view); render them "
                      "to separate --out files"}))
        return 2
    try:
        if args.flight_record:
            # Ops-health view over the serving run's flight record.
            manifest = write_ops_dashboard(
                args.flight_record, args.out, title=args.title)
        else:
            manifest = write_dashboard(
                args.data,
                args.out,
                threshold=args.threshold,
                top_k=args.top_k,
                bucket=args.bucket,
                title=args.title,
            )
    except FileNotFoundError as e:
        print(_json_line({"error": str(e)}))
        return 2
    print(_json_line(manifest))
    return 0


def cmd_trace(args) -> int:
    """Summarize an exported span trace: per-batch critical path, top-K
    slowest spans, XLA compile/recompile events, and an ASCII waterfall
    of the slowest (or a chosen) batch.

    Input is the Chrome-trace JSON written by ``rtfds score
    --trace-out``, fetched from the serving loop's ``GET /trace``, or
    produced by ``make trace-demo`` — the same file loads graphically
    in ui.perfetto.dev / chrome://tracing."""
    from real_time_fraud_detection_system_tpu.io.dashboard import (
        render_trace_waterfall,
    )
    from real_time_fraud_detection_system_tpu.utils.trace import (
        summarize_chrome,
    )

    try:
        with open(args.trace, encoding="utf-8") as f:
            trace = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(_json_line({"error": f"{type(e).__name__}: {e}"}))
        return 2
    summary = summarize_chrome(trace, top_k=args.top_k)
    if args.json:
        print(_json_line(summary))
        return 0
    batches = summary["batches"]
    print(f"{summary['n_events']} span events, {len(batches)} batches, "
          f"{len(summary['compile_events'])} XLA compile events")
    if batches:
        worst = sorted(batches, key=lambda b: -b["total_ms"])[:args.top_k]
        print(f"\nslowest batches (top {len(worst)}), critical phase "
              "(largest self time) per batch, phases as self/total ms:")
        for b in worst:
            # each phase as self/total: what it spent itself, and with
            # the spans it opened
            phases = " ".join(
                f"{k}={b['self_ms'][k]:.2f}/{v:.2f}"
                for k, v in b["phases_ms"].items())
            print(f"  {b['trace_id']}  total {b['total_ms']:9.3f} ms  "
                  f"critical {b['critical_phase']} "
                  f"({b['critical_ms']:.3f} ms self)  [{phases}]")
    if summary["self_time"]:
        rows = summary["self_time"][:max(args.top_k, 10)]
        print(f"\nself time by span (top {len(rows)}): where each "
              "thread's time went and was not handed further down")
        print(f"  {'role':<7} {'span':<16} {'count':>6} "
              f"{'self ms':>11} {'total ms':>11}")
        for r in rows:
            print(f"  {r['role'] or '-':<7} {r['name']:<16} "
                  f"{r['count']:>6} {r['self_ms']:>11.3f} "
                  f"{r['total_ms']:>11.3f}")
    if summary["slowest_spans"]:
        print(f"\nslowest spans (top {len(summary['slowest_spans'])}):")
        for s in summary["slowest_spans"]:
            print(f"  {s['dur_ms']:9.3f} ms  {s['name']:<16} "
                  f"{s['trace_id'] or '-'}")
    if summary["compile_events"]:
        print("\nXLA compile/recompile events:")
        for c in summary["compile_events"]:
            extra = (" " + ", ".join(f"{k}={v}" for k, v in
                                     c["args"].items())
                     if c.get("args") else "")
            print(f"  {c['name']:<14} {c['dur_ms']:9.3f} ms  "
                  f"{c['trace_id'] or '-'}{extra}")
    print()
    print(render_trace_waterfall(trace, trace_id=args.batch or None))
    return 0


def cmd_compare(args) -> int:
    """Fit every requested model kind on one shared split and report
    metrics + fit/predict wall-clock per kind — the reference's
    5-classifier comparison (``model_training.ipynb · cells 50-56``,
    timing hooks ``shared_functions.py:312-320``) as one command.
    Optionally saves the ROC/PR/threshold PNG report per kind."""
    from real_time_fraud_detection_system_tpu.config import Config, TrainConfig
    from real_time_fraud_detection_system_tpu.features.offline import (
        compute_features_replay,
    )
    from real_time_fraud_detection_system_tpu.io.artifacts import (
        load_transactions,
    )
    from real_time_fraud_detection_system_tpu.models.train import (
        fit_and_assess,
        fit_and_assess_sequence,
        scale_split_to_txs,
        train_delay_test_split,
    )
    from real_time_fraud_detection_system_tpu.utils import get_logger

    log = get_logger("compare")
    txs = load_transactions(args.data)
    cfg = Config(
        train=TrainConfig(
            delta_train_days=args.delta_train,
            delta_delay_days=args.delta_delay,
            delta_test_days=args.delta_test,
            epochs=args.epochs,
        )
    )
    # the sequence family scores from event histories, not the replayed
    # aggregate features — skip the (minutes-at-scale) replay if no
    # feature-matrix kind was requested
    features = (
        compute_features_replay(
            txs, cfg.features, start_date=cfg.data.start_date)
        if any(k != "sequence" for k in args.models) else None
    )
    dtr, dde, dte = scale_split_to_txs(
        txs, cfg.train.delta_train_days, cfg.train.delta_delay_days,
        cfg.train.delta_test_days,
    )
    train_mask, test_mask = train_delay_test_split(
        txs, delta_train=dtr, delta_delay=dde, delta_test=dte
    )
    if args.plots_dir:
        from real_time_fraud_detection_system_tpu.models.plots import (
            save_plots,
        )

        os.makedirs(args.plots_dir, exist_ok=True)
    rows = []
    for kind in args.models:
        if kind == "sequence":
            _, metrics, fit_s, pred_s, probs = fit_and_assess_sequence(
                txs, cfg, train_mask, test_mask
            )
        else:
            _, metrics, fit_s, pred_s, probs = fit_and_assess(
                txs, features, cfg, kind, train_mask, test_mask
            )
        row = {
            "model": kind,
            **{k: round(float(v), 4) for k, v in metrics.items()},
            "fit_seconds": round(fit_s, 3),
            "predict_seconds": round(pred_s, 3),
        }
        rows.append(row)
        log.info("%s", row)
        if args.plots_dir:
            save_plots(
                os.path.join(args.plots_dir, f"{kind}.png"),
                txs.tx_fraud[test_mask], probs, label=kind,
            )
    print(_json_line({"split_days": [dtr, dde, dte], "models": rows}))
    return 0


def cmd_select(args) -> int:
    """Prequential hyper-parameter selection — the reference's
    ``prequential_grid_search`` / ``model_selection_wrapper`` notebooks
    (``shared_functions.py:774-872``) as one command. ``--grid`` takes
    ``field=v1,v2,...`` pairs over ModelConfig/TrainConfig fields."""
    from real_time_fraud_detection_system_tpu.config import Config, TrainConfig
    from real_time_fraud_detection_system_tpu.features.offline import (
        compute_features_replay,
    )
    from real_time_fraud_detection_system_tpu.io.artifacts import (
        load_transactions,
    )
    from real_time_fraud_detection_system_tpu.models.selection import (
        execution_times,
        model_selection_wrapper,
        summarize_performances,
    )
    from real_time_fraud_detection_system_tpu.utils import get_logger

    import dataclasses

    from real_time_fraud_detection_system_tpu.config import ModelConfig

    log = get_logger("select")
    # Validate the grid BEFORE the (minutes-long at scale) data load and
    # feature replay: spec syntax and field names both.
    known = {f.name for f in dataclasses.fields(ModelConfig)} | {
        f.name for f in dataclasses.fields(TrainConfig)
    }
    grid = {}
    for spec in args.grid:
        field, _, vals = spec.partition("=")
        if not vals:
            log.error("--grid expects field=v1,v2,... (got %r)", spec)
            return 2
        if field not in known:
            log.error("--grid field %r is not a ModelConfig/TrainConfig "
                      "field (known: %s)", field, ", ".join(sorted(known)))
            return 2
        parsed = []
        for v in vals.split(","):
            try:
                parsed.append(int(v))
            except ValueError:
                try:
                    parsed.append(float(v))
                except ValueError:
                    parsed.append(v)
        grid[field] = parsed
    txs = load_transactions(args.data)
    cfg = Config(train=TrainConfig(epochs=args.epochs))
    features = compute_features_replay(
        txs, cfg.features, start_date=cfg.data.start_date
    )
    rows = model_selection_wrapper(
        txs, features, cfg, args.model, grid,
        start_day_training_for_valid=args.start_valid,
        start_day_training_for_test=args.start_test,
        n_folds=args.folds,
    )
    summaries = summarize_performances(rows)
    out = {
        "model": args.model,
        "grid": grid,
        "metrics": {
            m: {
                "best_params": s.best_params,
                "validation": [round(s.validation_mean, 4),
                               round(s.validation_std, 4)],
                "test": [round(s.test_mean, 4), round(s.test_std, 4)],
            }
            for m, s in summaries.items()
        },
        "execution_times": execution_times(rows),
    }
    log.info("best by auc_roc: %s", summaries["auc_roc"].best_params)
    print(_json_line(out))
    return 0


def cmd_lint(args) -> int:
    """Project-native static analysis (tools/rtfdslint).

    The analyzer lives beside the repo, not inside the installed
    package — it lints SOURCE (including README and tests), so it only
    makes sense in a checkout. ``make lint-static`` and the tier-1 gate
    (tests/test_lint_static.py) are the two canonical callers; this
    subcommand is the operator spelling with the same exit contract
    (1 = unbaselined P0/P1 findings, 2 = usage/config error)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tools_dir = os.path.join(repo_root, "tools")
    if not os.path.isdir(os.path.join(tools_dir, "rtfdslint")):
        print("rtfds lint: tools/rtfdslint not found beside the package "
              "(installed without the repo checkout?) — run from a "
              "source tree", file=sys.stderr)
        return 2
    sys.path.insert(0, tools_dir)
    from rtfdslint.cli import main as lint_main

    # rtfdslint.cli is the AUTHORITATIVE flag surface (python -m
    # rtfdslint); this subcommand mirrors the stable subset below —
    # a new analyzer flag must be added to the lint subparser AND this
    # forwarding block to be reachable via `rtfds lint`.
    fwd = ["--root", repo_root]
    for flag in ("json", "strict", "verbose", "no_baseline",
                 "update_baseline", "list_rules", "verify_device"):
        if getattr(args, flag):
            fwd.append("--" + flag.replace("_", "-"))
    if args.reason:
        fwd += ["--reason", args.reason]
    if args.baseline:
        fwd += ["--baseline", args.baseline]
    for r in args.rule or ():
        fwd += ["--rule", r]
    return lint_main(fwd + list(args.paths))


def cmd_verify_device(args) -> int:
    """Jaxpr-level device-contract verifier (tools/rtfdsverify).

    The semantic sibling of ``rtfds lint``: instead of parsing source,
    it builds weightless template engines, loads their dispatch
    signature inventories, and proves the device-plane contracts (AOT
    coverage, z-mode exactness, donation safety, Pallas VMEM
    admission) on the traced programs — CPU-only, before any stream
    starts. Same exit contract as lint (1 = unbaselined P0/P1,
    2 = usage/config error)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tools_dir = os.path.join(repo_root, "tools")
    if not os.path.isdir(os.path.join(tools_dir, "rtfdsverify")):
        print("rtfds verify-device: tools/rtfdsverify not found beside "
              "the package (installed without the repo checkout?) — "
              "run from a source tree", file=sys.stderr)
        return 2
    sys.path.insert(0, tools_dir)
    from rtfdsverify.cli import main as verify_main

    fwd = ["--root", repo_root]
    for flag in ("json", "strict", "verbose", "no_baseline",
                 "update_baseline", "list_checks"):
        if getattr(args, flag):
            fwd.append("--" + flag.replace("_", "-"))
    if args.reason:
        fwd += ["--reason", args.reason]
    if args.baseline:
        fwd += ["--baseline", args.baseline]
    for c in args.check or ():
        fwd += ["--check", c]
    return verify_main(fwd)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="rtfds", description="TPU-native real-time fraud detection"
    )
    ap.add_argument("--platform", choices=["cpu", "tpu"], default=None,
                    help="force a JAX platform (default: environment)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("datagen", help="generate a synthetic transaction table")
    p.add_argument("--out", required=True)
    p.add_argument("--customers", type=int, default=5000)
    p.add_argument("--terminals", type=int, default=10000)
    p.add_argument("--days", type=int, default=245)
    p.add_argument("--radius", type=float, default=5.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--start-date", default="2025-04-01")
    p.add_argument("--pg-dsn", default=None,
                   help="also seed a live Postgres (psycopg2 DSN) — the "
                        "reference datagen container's role")
    p.add_argument("--pg-rate", type=float, default=0.0,
                   help="paced rows/s for --pg-dsn (0 = bulk)")
    p.set_defaults(fn=cmd_datagen)

    p = sub.add_parser("train", help="offline training on a generated table")
    p.add_argument("--data", required=True)
    p.add_argument("--model", default="forest",
                   choices=["logreg", "mlp", "tree", "forest", "gbt",
                            "autoencoder", "sequence"])
    p.add_argument("--out-model", required=True)
    p.add_argument("--delta-train", type=int, default=153)
    p.add_argument("--delta-delay", type=int, default=30)
    p.add_argument("--delta-test", type=int, default=30)
    p.add_argument("--epochs", type=int, default=5)
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("score", help="stream-score a table through the engine")
    p.add_argument("--data", default="",
                   help="transactions .npz (required unless --source kafka)")
    p.add_argument("--model-file", required=True)
    p.add_argument("--scorer", default="tpu", choices=["cpu", "tpu"])
    p.add_argument("--mode", default="columnar", choices=["columnar", "envelope"])
    p.add_argument("--source", default="replay",
                   choices=["replay", "kafka", "raw-table"],
                   help="replay a generated table (.npz), consume the "
                        "Debezium transaction topic from a real Kafka "
                        "cluster, or backfill from a persistent raw-"
                        "transactions table directory (--data <dir>, the "
                        "reference's stream-read of nessie.payment."
                        "transactions history)")
    p.add_argument("--from-date", default="",
                   help="raw-table backfill start day (YYYY-MM-DD, incl.)")
    p.add_argument("--to-date", default="",
                   help="raw-table backfill end day (YYYY-MM-DD, incl.)")
    p.add_argument("--bootstrap", default="localhost:9092",
                   help="Kafka bootstrap servers (--source kafka)")
    p.add_argument("--topic", default="debezium.payment.transactions")
    p.add_argument("--idle-timeout", type=float, default=0.0,
                   help="stop when the Kafka topic is idle this long "
                        "(0 = serve forever)")
    p.add_argument("--feedback-bootstrap", default="",
                   help="consume delayed fraud labels from this Kafka "
                        "cluster's feedback topic between micro-batches "
                        "(online learning, BASELINE config 4)")
    p.add_argument("--feedback-topic", default="payment.feedback")
    p.add_argument("--out", default="",
                   help="analyzed output: local directory (ParquetSink) "
                        "or s3://bucket/prefix (StoreParquetSink; "
                        "RTFDS_S3_ENDPOINT targets MinIO)")
    p.add_argument("--raw-table", default="",
                   help="also land raw transactions in a day-partitioned "
                        "parquet table at this directory (the reference's "
                        "nessie.payment.transactions)")
    p.add_argument("--batch-rows", type=int, default=4096)
    p.add_argument("--key-mode", default="direct",
                   choices=["direct", "hash", "exact"],
                   help="feature-state key→slot placement: direct "
                        "(dense serial ids, capacity >= key universe), "
                        "hash (bounded memory, colliding keys MERGE "
                        "windows), exact (tiered store: on-device key "
                        "directory, hot tier sized to the working set, "
                        "admission misses served from the count-min "
                        "sketch — README 'Feature-state playbook')")
    p.add_argument("--key-bits", type=int, default=32, choices=[32, 64],
                   help="width of an id on the device: 32 xor-folds "
                        "every int64 id to one word (exact for ids under "
                        "2^32; wider ids that fold alike MERGE, counted "
                        "in rtfds_wide_id_rows_total), 64 carries both "
                        "words through the batch, the directory, the "
                        "sketches, the cold store and checkpoints — two "
                        "ids share state only if all 64 bits agree. 64 "
                        "needs --key-mode exact on one chip "
                        "(--devices > 1 and multi-host refuse it)")
    p.add_argument("--state-compact-every", type=int, default=0,
                   help="recency compaction cadence for --key-mode "
                        "exact: every N batches a full-table vector "
                        "pass reclaims hot-tier slots whose newest day "
                        "is older than delay + max(window) (dead "
                        "history; counted in "
                        "rtfds_feature_slots_reclaimed_total). 0 = off")
    p.add_argument("--state-hbm-budget-mb", type=float, default=0.0,
                   help="HBM budget for the whole feature state (dense "
                        "tier + directories + sketches), validated at "
                        "engine build from the static state_bytes() "
                        "accounting — fail fast instead of OOMing "
                        "mid-stream. 0 = unchecked")
    p.add_argument("--cold-store", default="",
                   help="host cold tier for --key-mode exact: directory "
                        "or s3:// url where compaction demotes evicted "
                        "keys' exact window rows instead of discarding "
                        "them; a returning key is promoted back before "
                        "the step that scores its row (README "
                        "'Feature-state playbook' § Cold tier). "
                        "tmp://[name] = a fresh store under the system's "
                        "temporary directory, gone with the process (no "
                        "checkpoints). Requires --state-compact-every. "
                        "Empty = off (evictions degrade to the sketch)")
    p.add_argument("--cold-segment-mb", type=float, default=4.0,
                   help="cold-store flush threshold: buffered demotions "
                        "become one durable segment (blob + CRC'd "
                        "manifest) once they exceed this many MB")
    p.add_argument("--alerts-only", action="store_true",
                   help="serve predictions only: the feature matrix "
                        "never leaves the device (the highest-throughput "
                        "mode; incompatible with --scorer cpu/feedback)")
    p.add_argument("--pipeline-depth", type=int, default=2,
                   help="micro-batches in flight (2 = double-buffering; "
                        "deeper hides per-dispatch overhead)")
    p.add_argument("--coalesce-rows", type=int, default=0,
                   help="merge consecutive source polls into one device "
                        "batch up to this many rows (0 = off)")
    p.add_argument("--precompile", action="store_true",
                   help="AOT-compile the jitted step for every batch "
                        "bucket before the first poll, so no bucket's "
                        "first touch pays a mid-stream XLA compile "
                        "(rtfds_xla_recompiles_total stays 0); see also "
                        "`rtfds warmup`")
    p.add_argument("--autobatch", action="store_true",
                   help="adaptive micro-batching: move the coalesce "
                        "target between the batch buckets from observed "
                        "latency (maximize throughput, or hold "
                        "--latency-slo-ms when set)")
    p.add_argument("--latency-slo-ms", type=float, default=0.0,
                   help="p50 micro-batch latency target for the "
                        "adaptive batch controller (implies --autobatch;"
                        " 0 = no SLO, maximize throughput)")
    p.add_argument("--decode-workers", type=int, default=0,
                   help="ingest-decode worker threads: each envelope "
                        "byte-batch is sharded into contiguous slabs "
                        "decoded concurrently (bit-identical to serial "
                        "decode). 0 = auto (min(8, cores)); 1 = serial")
    p.add_argument("--prefetch-batches", type=int, default=0,
                   help="background source prefetch: poll + decode run "
                        "ahead of the loop into a bounded queue of this "
                        "many batches (offsets commit on consumption, so "
                        "checkpoint replay semantics are unchanged; "
                        "poison isolation runs unprefetched). 0 = off")
    p.add_argument("--no-fetch-overlap", action="store_true",
                   help="disable overlapped result fetch (async D2H "
                        "copies issued at dispatch time); on by default")
    p.add_argument("--sink-queue-batches", type=int, default=8,
                   help="bounded queue depth (batch results) of the "
                        "loop's sink writer thread; a full queue "
                        "backpressures the loop thread")
    p.add_argument("--use-pallas", action="store_true",
                   help="serve with the fused Pallas kernels where "
                        "available (tree/forest fused featurize+score, "
                        "gbt leaf-sum, logreg featurize+score) instead "
                        "of the XLA composition")
    p.add_argument("--z-mode", default="auto",
                   choices=["auto", "f32", "bf16", "int8"],
                   help="tree-ensemble z-contraction arithmetic on the "
                        "MXU (auto = int8 on TPU, f32 elsewhere); every "
                        "mode is decision-identical by the exactness "
                        "contract — int8 is additionally bit-identical "
                        "to f32 (README § Device plane)")
    p.add_argument("--emit-threshold", type=float, default=0.0,
                   help="selective emission: transfer + persist the 15 "
                        "feature columns only for rows whose fraud "
                        "probability clears this threshold (probs land "
                        "for every row; flagged rows' features are "
                        "bit-identical to full emission, clean rows "
                        "carry zeros) — near-alerts-only throughput with "
                        "the full analyzed schema for flagged traffic "
                        "(0 = emit features for every row)")
    p.add_argument("--emit-bf16", action="store_true",
                   help="emit the analyzed feature columns in bfloat16 "
                        "(half the device->host bytes; predictions stay "
                        "f32-exact, features lose ~3 decimal digits; "
                        "incompatible with --scorer cpu / feedback)")
    p.add_argument("--reload-model-every", type=int, default=0,
                   help="hot model reload: every N batches re-read "
                        "--model-file (mtime-gated for local paths) and "
                        "swap weights into the live loop — retrain + "
                        "overwrite the artifact, no serving restart "
                        "(0 = off)")
    p.add_argument("--start-date", default="2025-04-01")
    p.add_argument("--checkpoint-dir", default="")
    p.add_argument("--checkpoint-full-every", type=int, default=1,
                   help="write a FULL checkpoint every K saves and "
                        "cheap deltas (changed leaves only, checksum-"
                        "chained to their base) in between; restore "
                        "composes and verifies the chain, falling back "
                        "to the last valid full on any broken link "
                        "(1 = every save full)")
    p.add_argument("--checkpoint-op-timeout", type=float, default=0.0,
                   help="per-op timeout in seconds for object-store "
                        "checkpoint PUT/GET/LIST (a hung call surfaces "
                        "as a retryable transient instead of wedging "
                        "the supervisor; 0 = wait indefinitely)")
    p.add_argument("--checkpoint-op-attempts", type=int, default=3,
                   help="retry attempts per object-store checkpoint op "
                        "(original-typed error propagation after "
                        "exhaustion; 1 = no retry)")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--drain-on-sigterm", action="store_true",
                   help="SIGTERM stops the stream at the next batch "
                        "boundary instead of killing the process: "
                        "in-flight batches finish, the sink drains, "
                        "and a final checkpoint lands at that exact "
                        "frontier — the coordinated-drain leg of an "
                        "elastic fleet resize (deferred/shed rows stay "
                        "behind the committed offsets for the next "
                        "topology to re-poll)")
    p.add_argument("--resume-merge", default="",
                   help="OLD_CKPT_ROOT:P:L:REASON — adopt a drained "
                        "P-process fleet's final checkpoints (under "
                        "proc-NN/ of the root, or the root itself when "
                        "P=1, each written at L devices/process) into "
                        "this worker's --checkpoint-dir before "
                        "serving: states merge to one global "
                        "checkpoint, the stream cursor rewinds to the "
                        "fleet-wide minimum with per-old-owner resume "
                        "floors (no row lost, none double-scored), and "
                        "a resize epoch is stamped into the lineage "
                        "(`rtfds ckpt --inspect` surfaces it). "
                        "Idempotent: skipped when this worker's "
                        "lineage already has a checkpoint. Requires "
                        "--resume; not for --source kafka")
    p.add_argument("--resume-merge-cold", default="",
                   help="comma-separated old-generation cold-store "
                        "directories to consolidate into --cold-store "
                        "during --resume-merge (restore then re-homes "
                        "ownership to the new topology)")
    p.add_argument("--cms-exchange", default="",
                   help="shared directory for cross-process terminal-"
                        "sketch exchange at checkpoint boundaries: "
                        "terminal risk aggregates (NOT co-partitioned "
                        "by the customer-residue ingest split) merge "
                        "fleet-wide under the newest-day rule, while "
                        "checkpoints keep locals-only partials so "
                        "resize merges stay exact (multi-host only)")
    p.add_argument("--max-batches", type=int, default=0)
    p.add_argument("--online-lr", type=float, default=0.0)
    p.add_argument("--max-restarts", type=int, default=0,
                   help="supervised mode: restart-on-failure with "
                        "checkpoint replay (requires --checkpoint-dir)")
    p.add_argument("--stall-timeout", type=float, default=0.0,
                   help="watchdog: restart the engine if it makes no "
                        "progress for this many seconds (supervised mode "
                        "only; 0 = off)")
    p.add_argument("--dead-letter", default="",
                   help="dead-letter queue for poison rows (*.jsonl = "
                        "JSONL file, else a parquet directory): the "
                        "supervisor bisects a crash-looping micro-batch "
                        "down to the failing rows, quarantines them here "
                        "with envelope + error metadata, and the stream "
                        "continues; inspect/replay with `rtfds dlq`")
    p.add_argument("--crash-loop-k", type=int, default=2,
                   help="consecutive supervised crashes at the SAME "
                        "resume point before the failure is reclassified "
                        "from transient to poison (bisect + dead-letter "
                        "instead of burning the restart budget)")
    p.add_argument("--restart-backoff-ms", type=float, default=0.0,
                   help="base backoff between crash-caused restarts "
                        "(doubles per restart, full jitter, 30 s cap; "
                        "0 = restart hot); stall restarts never back "
                        "off — they already waited the stall budget")
    p.add_argument("--nan-guard", action="store_true",
                   help="data-plane guard: rows producing NaN/Inf "
                        "features or scores are quarantined to "
                        "--dead-letter (reason=nonfinite) and the batch "
                        "is re-scored without them BEFORE the running "
                        "feature state is contaminated (serializes the "
                        "pipeline to depth 1 while on)")
    p.add_argument("--overload", action="store_true",
                   help="overload-survival ladder: under sustained "
                        "pressure (batch p50 vs --latency-slo-ms, "
                        "source lag, queue fill) shed optional work, "
                        "then force the largest AOT bucket + alerts-"
                        "only emission, then defer whole micro-batches "
                        "to a durable spill and replay them in order "
                        "on recovery — degrade, never die (README "
                        "section 'Overload survival playbook')")
    p.add_argument("--overload-spill", default="overload_spill",
                   help="durable spill for rung-3 deferred batches "
                        "(*.jsonl = JSONL, else a parquet directory; "
                        "idempotent by tx_id, reason=shed)")
    p.add_argument("--overload-lag-high", type=int, default=0,
                   help="source-lag normalization: this many backlogged "
                        "rows == pressure 1.0 (0 = lag signal off)")
    p.add_argument("--overload-climb-pressure", type=float, default=1.0,
                   help="climb one rung after --overload-climb-dwell "
                        "consecutive observations at or above this "
                        "normalized pressure")
    p.add_argument("--overload-descend-pressure", type=float,
                   default=0.6,
                   help="descend one rung after --overload-descend-"
                        "dwell consecutive observations at or below "
                        "this pressure (must be < climb: the gap is "
                        "the anti-flap hysteresis band)")
    p.add_argument("--overload-climb-dwell", type=int, default=3,
                   help="consecutive high-pressure observations before "
                        "each climb")
    p.add_argument("--overload-descend-dwell", type=int, default=6,
                   help="consecutive low-pressure observations before "
                        "each descent")
    p.add_argument("--overload-max-deferred", type=int, default=512,
                   help="memory bound on deferred micro-batches; at the "
                        "cap the queue head replays through scoring to "
                        "make room and the rest of the backlog stays "
                        "in the source/broker")
    p.add_argument("--devices", type=int, default=1,
                   help="serve on an N-device mesh (sharded engine: "
                        "customer-partitioned rows, all_to_all terminal "
                        "exchange); 1 = single-chip engine. In a "
                        "multi-host fleet this is the PER-PROCESS width")
    p.add_argument("--max-batch-rows", type=int, default=0,
                   help="cap assembled micro-batches at this many rows "
                        "(0 = config default 65536). The sharded "
                        "engine's per-chunk step width derives from it "
                        "(2x the balanced per-device load), so smoke "
                        "fleets size their compiled step with this "
                        "knob")
    p.add_argument("--coordinator", default="",
                   help="host:port of process 0's jax.distributed "
                        "coordination service — multi-host fleets "
                        "(tools/multihost_launcher.py passes it); \"\" "
                        "with --num-processes > 1 = uncoordinated "
                        "fleet (no cross-process jax state; see the "
                        "README multi-host playbook)")
    p.add_argument("--num-processes", type=int, default=1,
                   help="total processes in the multi-host fleet; this "
                        "process serves the customer residue block "
                        "[pid*devices, (pid+1)*devices) of the "
                        "num-processes*devices global shard space")
    p.add_argument("--process-id", type=int, default=-1,
                   help="this process's id in [0, num-processes); -1 = "
                        "resolve from JAX_PROCESS_ID")
    p.add_argument("--metrics-dump", default="",
                   help="write the final registry snapshot "
                        "(/metrics.json content) to this path at run "
                        "end, success or failure — the artifact the "
                        "multihost smoke asserts zero recompiles from "
                        "without scraping a live port")
    p.add_argument("--trace-dir", default="",
                   help="capture a jax.profiler/TensorBoard trace of the "
                        "serving run into this directory")
    p.add_argument("--metrics-port", type=int, default=0,
                   help="serve /metrics (Prometheus text), /metrics.json "
                        "and /healthz on this port while scoring "
                        "(0 = off)")
    p.add_argument("--healthz-max-batch-age", type=float, default=300.0,
                   help="/healthz goes 503 when the last finished batch "
                        "is older than this many seconds")
    p.add_argument("--healthz-max-lag-rows", type=float, default=0.0,
                   help="/healthz goes 503 when the source backlog "
                        "(rtfds_source_lag_rows) exceeds this many rows "
                        "(0 = lag check off)")
    p.add_argument("--flight-record", default="",
                   help="append one JSONL record per micro-batch (per-"
                        "phase timings, queue depth) plus checkpoint/"
                        "feedback/fault events to this file; render it "
                        "with `rtfds dashboard --flight-record`")
    p.add_argument("--flight-record-max-mb", type=float, default=256.0,
                   help="rotate the flight record when it exceeds this "
                        "many MB (previous generation kept at <path>.1; "
                        "a `rotated` event marks the trip; 0 = "
                        "unbounded)")
    p.add_argument("--trace-out", default="",
                   help="export per-batch span waterfalls as Chrome-"
                        "trace JSON to this file at run end (load in "
                        "ui.perfetto.dev or summarize with `rtfds "
                        "trace`); bounded ring buffer — safe on "
                        "unbounded streams, unlike --trace-dir")
    p.add_argument("--learn-registry", default="",
                   help="continuous learning: versioned model registry "
                        "at this path (directory or s3:// prefix). The "
                        "serving model bootstraps as v1; a streaming "
                        "learner trains a candidate on labeled feedback "
                        "(needs --feedback-bootstrap for live labels), "
                        "shadow-scores it beside the champion, and "
                        "promotes/rolls back on live precision-recall. "
                        "Inspect with `rtfds registry`")
    p.add_argument("--publish-every-labels", type=int, default=512,
                   help="publish a candidate version after this many new "
                        "labeled rows trained since the last publish")
    p.add_argument("--promote-min-labels", type=int, default=256,
                   help="labeled rows BOTH models need in the live "
                        "comparison window before promotion can fire")
    p.add_argument("--promote-margin", type=float, default=0.01,
                   help="live recall improvement the candidate must show "
                        "over the champion to be promoted")
    p.add_argument("--rollback-min-labels", type=int, default=256,
                   help="labeled rows after a promotion before the "
                        "canary verdict (hold baseline or roll back)")
    p.add_argument("--rollback-margin", type=float, default=0.05,
                   help="live recall drop below the promotion baseline "
                        "that triggers automatic rollback")
    p.set_defaults(fn=cmd_score)

    p = sub.add_parser(
        "warmup",
        help="AOT-compile the serving step for every batch bucket "
             "(fills the persistent compilation cache, then exits)")
    p.add_argument("--model-file", required=True)
    p.add_argument("--devices", type=int, default=1,
                   help="warm the N-device sharded step instead of the "
                        "single-chip one")
    p.add_argument("--online-lr", type=float, default=0.0,
                   help="match the serving flag: online SGD changes the "
                        "compiled step")
    p.add_argument("--alerts-only", action="store_true",
                   help="match the serving flag (emit_features=False "
                        "compiles a different step tail)")
    p.add_argument("--emit-threshold", type=float, default=0.0,
                   help="match the serving flag (selective emission "
                        "compiles a different step tail)")
    p.add_argument("--emit-bf16", action="store_true",
                   help="match the serving flag")
    p.add_argument("--use-pallas", action="store_true",
                   help="match the serving flag")
    p.add_argument("--z-mode", default="auto",
                   choices=["auto", "f32", "bf16", "int8"],
                   help="match the serving flag (the z-contraction mode "
                        "is part of the compiled step)")
    p.set_defaults(fn=cmd_warmup)

    p = sub.add_parser(
        "dlq",
        help="inspect / replay dead-letter-queue rows (poison quarantine)")
    p.add_argument("--path", required=True,
                   help="DLQ written by --dead-letter (JSONL file or "
                        "parquet directory)")
    p.add_argument("--limit", type=int, default=20,
                   help="max row records printed when inspecting "
                        "(0 = summary only)")
    p.add_argument("--replay", action="store_true",
                   help="re-score the quarantined rows through a fresh "
                        "engine (post-fix triage; rows that still crash "
                        "report their error and stay quarantined)")
    p.add_argument("--model-file", default="",
                   help="model artifact for --replay")
    p.set_defaults(fn=cmd_dlq)

    p = sub.add_parser(
        "ckpt",
        help="inspect / verify the checkpoint lineage (durable state)")
    p.add_argument("--path", required=True,
                   help="checkpoint directory or s3:// prefix "
                        "(the --checkpoint-dir of the serving run)")
    p.add_argument("--verify", action="store_true",
                   help="re-checksum every live checkpoint + delta "
                        "chain; exit 1 on any corruption (deploy "
                        "preflight)")
    p.add_argument("--inspect", default="",
                   help="dump one checkpoint's manifest (name or full "
                        "path, e.g. ckpt-0000000004.npz)")
    p.set_defaults(fn=cmd_ckpt)

    p = sub.add_parser(
        "registry",
        help="inspect / verify / promote / roll back the versioned "
             "model registry (continuous learning)")
    p.add_argument("--path", required=True,
                   help="registry directory or s3:// prefix (the "
                        "--learn-registry of the serving run)")
    p.add_argument("--verify", action="store_true",
                   help="re-hash every artifact against its manifest + "
                        "internal content hash; exit 1 on any corruption "
                        "(deploy preflight)")
    p.add_argument("--inspect", type=int, default=0,
                   help="dump one version's manifest (versions start "
                        "at 1)")
    p.add_argument("--promote", type=int, default=0,
                   help="verify, then move the champion pointer to this "
                        "version (manual canary override)")
    p.add_argument("--rollback", action="store_true",
                   help="pop the champion pointer back to the previous "
                        "champion (one pointer move; no artifact bytes "
                        "change)")
    p.add_argument("--publish", default="",
                   help="register a model artifact (.npz, e.g. an "
                        "offline-retrained forest/GBT) as a new "
                        "candidate version; a serving run with "
                        "--learn-registry picks it up for shadow "
                        "scoring on its next registry poll")
    p.set_defaults(fn=cmd_registry)

    p = sub.add_parser("demo",
                       help="full E2E demo: datagen → CDC → sinks → scorer")
    p.add_argument("--customers", type=int, default=500)
    p.add_argument("--terminals", type=int, default=1000)
    p.add_argument("--days", type=int, default=90)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--model", default="forest",
                   choices=["logreg", "mlp", "tree", "forest", "gbt",
                            "autoencoder", "sequence"])
    p.add_argument("--model-file", default="")
    p.add_argument("--delta-train", type=int, default=45)
    p.add_argument("--delta-delay", type=int, default=10)
    p.add_argument("--delta-test", type=int, default=20)
    p.add_argument("--batch-rows", type=int, default=4096)
    p.add_argument("--out", default="")
    p.add_argument("--devices", type=int, default=1,
                   help="serve the scoring leg on an N-device mesh")
    p.set_defaults(fn=cmd_demo)

    p = sub.add_parser("query",
                       help="dashboard reports over analyzed parquet output")
    p.add_argument("--data", required=True,
                   help="analyzed output directory (ParquetSink); for "
                        "--report transactions, the raw day-partitioned "
                        "table directory (tx_date=*/ layout)")
    p.add_argument("--report", default="summary",
                   choices=["summary", "timeseries", "terminals",
                            "customers", "alerts", "drift",
                            "transactions"])
    p.add_argument("--threshold", type=float, default=0.5)
    p.add_argument("--top-k", type=int, default=10)
    p.add_argument("--bucket", default="day", choices=["hour", "day"])
    p.set_defaults(fn=cmd_query)

    p = sub.add_parser(
        "sql",
        help="ad-hoc SQL over analyzed parquet output (Trino's role, "
             "in-process; table name: analyzed)",
    )
    p.add_argument("--data", required=True,
                   help="analyzed output directory (ParquetSink)")
    p.add_argument("query", help="SQL, e.g. \"SELECT COUNT(*) FROM "
                                 "analyzed WHERE prediction >= 0.5\"")
    p.add_argument("--limit", type=int, default=1000,
                   help="max rows printed (default 1000; 0 = unlimited)")
    p.set_defaults(fn=cmd_sql)

    p = sub.add_parser(
        "import-model",
        help="convert the reference's pickled artifacts "
             "(trained_model.pkl [+ scaler.pkl]) into the npz model "
             "format — existing reference models serve on TPU unchanged",
    )
    p.add_argument("--model-pkl", required=True,
                   help="pickled sklearn/xgboost classifier "
                        "(the reference's trained_model.pkl; unpickling "
                        "executes code — trusted artifacts only)")
    p.add_argument("--scaler-pkl", default="",
                   help="joblib StandardScaler (the reference's "
                        "scaler.pkl); omit for identity scaling")
    p.add_argument("--out-model", required=True)
    p.set_defaults(fn=cmd_import_model)

    p = sub.add_parser(
        "connectors",
        help="register the Debezium Postgres source connector "
             "(the reference's make connectors)",
    )
    p.add_argument("--connect-url", default="http://localhost:8083")
    p.add_argument("--name", default="pg-src-connector")
    p.add_argument("--db-host", default="postgres")
    p.add_argument("--db-port", type=int, default=5432)
    p.add_argument("--db-user", default="postgres")
    p.add_argument("--db-password", default="postgres")
    p.add_argument("--db-name", default="postgres")
    p.add_argument("--schema", default="payment")
    p.add_argument("--topic-prefix", default="debezium")
    p.add_argument("--timeout", type=float, default=10.0)
    p.set_defaults(fn=cmd_connectors)

    p = sub.add_parser(
        "dashboard",
        help="render the static-HTML ops dashboard (the Superset role)",
    )
    p.add_argument("--data", default="",
                   help="analyzed output directory (ParquetSink)")
    p.add_argument("--flight-record", default="",
                   help="render the ops-health view from a flight-record "
                        "JSONL (per-phase latency series + event strip) "
                        "instead of the analyzed-output view")
    p.add_argument("--out", default="dashboard.html")
    p.add_argument("--threshold", type=float, default=0.5)
    p.add_argument("--top-k", type=int, default=10)
    p.add_argument("--bucket", default="day", choices=["hour", "day"])
    p.add_argument("--title", default=None,
                   help="page title (default set in io.dashboard)")
    p.set_defaults(fn=cmd_dashboard)

    p = sub.add_parser(
        "trace",
        help="summarize an exported span trace (critical path, top-K "
             "slowest spans, recompiles, ASCII waterfall)",
    )
    p.add_argument("--trace", required=True,
                   help="Chrome-trace JSON from `rtfds score "
                        "--trace-out`, GET /trace, or make trace-demo")
    p.add_argument("--top-k", type=int, default=10,
                   help="slowest batches/spans to list")
    p.add_argument("--batch", default="",
                   help="trace id (e.g. b00000042) to render the "
                        "waterfall for (default: the slowest batch)")
    p.add_argument("--json", action="store_true",
                   help="print the machine-readable summary as one "
                        "JSON line instead of the text report")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser(
        "compare",
        help="fit several model kinds on one split; metrics + timings",
    )
    p.add_argument("--data", required=True)
    p.add_argument("--models", nargs="+",
                   default=["logreg", "tree", "forest", "gbt", "mlp"],
                   choices=["logreg", "mlp", "tree", "forest", "gbt",
                            "autoencoder", "sequence"])
    p.add_argument("--delta-train", type=int, default=153)
    p.add_argument("--delta-delay", type=int, default=30)
    p.add_argument("--delta-test", type=int, default=30)
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--plots-dir", default="",
                   help="write <kind>.png ROC/PR/threshold reports here")
    p.set_defaults(fn=cmd_compare)

    p = sub.add_parser(
        "select",
        help="prequential hyper-parameter selection (validation+test sweeps)",
    )
    p.add_argument("--data", required=True)
    p.add_argument("--model", default="tree",
                   choices=["logreg", "mlp", "tree", "forest", "gbt"])
    p.add_argument("--grid", nargs="+", required=True,
                   metavar="FIELD=V1,V2",
                   help="e.g. tree_max_depth=2,4,8 epochs=3,5")
    p.add_argument("--start-valid", type=int, required=True,
                   help="training-start day for the validation sweep")
    p.add_argument("--start-test", type=int, required=True,
                   help="training-start day for the test sweep (later; "
                        "windows stay disjoint per the wrapper contract)")
    p.add_argument("--folds", type=int, default=4)
    p.add_argument("--epochs", type=int, default=3)
    p.set_defaults(fn=cmd_select)

    p = sub.add_parser(
        "lint",
        help="static analysis: recompile hazards, thread races, "
             "exception taxonomy, metric drift (tools/rtfdslint)")
    p.add_argument("paths", nargs="*",
                   help="files/dirs to lint (default: the package)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.add_argument("--strict", action="store_true",
                   help="P2 findings also fail the gate")
    p.add_argument("--verbose", action="store_true",
                   help="also list suppressed/baselined findings")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline file")
    p.add_argument("--update-baseline", action="store_true",
                   help="absorb current P0/P1 findings (needs --reason)")
    p.add_argument("--reason", default="",
                   help="reason recorded on new baseline entries")
    p.add_argument("--baseline", default="",
                   help="override the baseline file path")
    p.add_argument("--rule", action="append",
                   help="run only this rule (repeatable)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    p.add_argument("--verify-device", action="store_true",
                   help="also run the jaxpr-level device-contract "
                        "verifier (tools/rtfdsverify) and fold its "
                        "findings into the report/gate (--json carries "
                        "them under \"verifier\")")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser(
        "verify-device",
        help="device-contract verifier: prove AOT coverage, z-mode "
             "exactness, donation safety and Pallas VMEM admission on "
             "the traced step programs (tools/rtfdsverify; CPU-only, "
             "no weights)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.add_argument("--strict", action="store_true",
                   help="P2 findings also fail the gate")
    p.add_argument("--verbose", action="store_true",
                   help="also list baselined findings")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline file")
    p.add_argument("--update-baseline", action="store_true",
                   help="absorb current P0/P1 findings (needs --reason)")
    p.add_argument("--reason", default="",
                   help="reason recorded on new baseline entries")
    p.add_argument("--baseline", default="",
                   help="override the baseline file path")
    p.add_argument("--check", action="append",
                   help="run only this check (repeatable)")
    p.add_argument("--list-checks", action="store_true",
                   help="print the check catalog and exit")
    p.set_defaults(fn=cmd_verify_device)

    args = ap.parse_args(argv)
    _platform_setup(args.platform)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
