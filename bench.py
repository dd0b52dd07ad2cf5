"""Benchmark harness — full-detail JSON line, then a compact headline line.

Measures sustained scoring throughput (transactions/second) of the full
jitted hot path — feature-state update + window gather + scale + classify —
plus classify-latency percentiles and an MFU estimate, and compares against
the CPU baseline (the reference-equivalent sklearn pipeline).

    {"metric": "score_txns_per_sec", "value": N, "unit": "txns/s",
     "vs_baseline": speedup_over_cpu_sklearn, "detail": {...}}

It measures in the process that was started: it starts no child, and it
holds the chip for the whole run (a chip belongs to one process at a
time). Every result names the ``platform``, ``device_kind`` and device
count it ran on.

- A run that finds no TPU exits non-zero. The one exception is an explicit
  ``JAX_PLATFORMS=cpu`` — the tests' labelled smoke run of the control
  flow; its figures are CPU wall-clock, never device metrics.
- ``_peak_flops`` raises on a ``device_kind`` it does not know: a device
  that is not in the table is an error, not a default.
- A section that raises is still reported in the JSON (under its own
  ``error`` key, so the other sections' numbers survive) and named under
  ``detail.section_errors`` — and the exit code is then non-zero.
- Batch size starts modest (16k) and scales up, keeping the best
  successful size.
- TWO lines are printed: the full-detail result JSON, then a compact
  headline line (same schema, detail reduced to the device and the failed
  sections) — a reader that keeps only a tail window of stdout still gets
  one complete, parseable result.

The CPU-mesh tools (``tools/sharded_scaling_bench.py``,
``tools/sharded_state_scale_bench.py``, ``tools/multihost_scaling_bench.py``,
``tools/elastic_absorb_bench.py``) are NOT part of this run: a process
holding the chip does not time CPU fleets into a device result. Run them
by hand.

Run directly: ``python bench.py`` (add ``--quick`` for a fast smoke run).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# Sections that raised, in order: each is still reported in the JSON under
# its own key, and any entry here makes the exit code non-zero.
_SECTION_ERRORS: list = []


def _progress(msg: str) -> None:
    """Breadcrumb on stderr: which section is compiling or measuring."""
    print(f"# bench: {msg}", file=sys.stderr, flush=True)


def _section_error(section: str, e: BaseException) -> dict:
    """Record a failed section → the ``{"error": ...}`` dict reported in
    its place (the run goes on; the exit code will be non-zero)."""
    msg = f"{type(e).__name__}: {str(e)[:160]}"
    _SECTION_ERRORS.append(f"{section}: {msg}")
    _progress(f"SECTION FAILED {section}: {msg}")
    return {"error": msg}


# Peak dense bf16 matmul FLOP/s per chip, by device_kind substring
# (public spec sheets; v5e: Google Cloud documentation, "TPU v5e"). MFU
# here is model-FLOPs / (wall · peak): a lower bound, since the proj
# pass (one bf16 pass 45 deep since PR 47, six at f32 HIGHEST before)
# fills a third of the MXU's depth.
_PEAK_FLOPS = (
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 61.5e12),
    ("v2", 22.5e12),
)


def _peak_flops(device_kind: str) -> float:
    kind = device_kind.lower()
    for sub, peak in _PEAK_FLOPS:
        if sub in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device_kind!r}: add it "
        "to _PEAK_FLOPS with its source — a peak is never assumed")


def _build_model(model_kind: str, rng):
    """Returns (params, predict, skl_model_or_None)."""
    import jax.numpy as jnp  # noqa: F401  (keeps jax import localized)

    if model_kind == "forest":
        from sklearn.ensemble import RandomForestClassifier

        from real_time_fraud_detection_system_tpu.models.forest import (
            ensemble_from_sklearn,
            for_device,
        )
        from real_time_fraud_detection_system_tpu.models.forest import (
            predict_proba as forest_predict_proba,
        )

        xtr = rng.normal(0, 1, (2048, 15))
        ytr = (xtr[:, 0] + 0.5 * xtr[:, 1] > 0.8).astype(np.int32)
        skl = RandomForestClassifier(n_estimators=100, max_depth=8,
                                     random_state=0, n_jobs=-1).fit(xtr, ytr)
        params = for_device(ensemble_from_sklearn(skl, 15), 15)
        return params, forest_predict_proba, skl

    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
        logreg_predict_proba,
    )

    return init_logreg(15), logreg_predict_proba, None


def _model_flops_per_row(params) -> float:
    """Static model FLOPs per scored row (the classify kernel only; the
    feature scatter/gather contributes negligible FLOPs)."""
    from real_time_fraud_detection_system_tpu.models.forest import (
        GemmEnsemble,
    )

    if isinstance(params, GemmEnsemble):
        t, f, i = params.sel.shape
        l = params.path.shape[2]
        # proj [B,F]x[T,F,I] + z [B,T,I]x[T,I,L] + leaf [B,T,L]x[T,L]
        return 2.0 * t * i * (f + l) + 2.0 * t * l
    if hasattr(params, "w"):  # logreg
        return 2.0 * int(np.prod(np.shape(params.w)))
    return 0.0


def _make_batch_cols(rng, n: int) -> dict:
    return {
        "customer_id": rng.integers(0, 5000, n).astype(np.int64),
        "terminal_id": rng.integers(0, 10000, n).astype(np.int64),
        "tx_datetime_us": (
            (20200 * 86400 + rng.integers(0, 86400, n)).astype(np.int64)
            * 1_000_000
        ),
        "amount_cents": rng.integers(100, 50000, n).astype(np.int64),
    }


class _ProbsCap:
    """Sink stub that keeps only the served probabilities — the capture
    half of every engine-level exactness A/B."""

    def __init__(self):
        self.probs: list = []

    def append(self, res):
        self.probs.append(res.probs)

    def concat(self):
        return np.concatenate(self.probs)


class _RandSource:
    """Pre-generated random micro-batches for the engine-loop measurement
    (generation cost excluded from the measured loop)."""

    def __init__(self, n_batches: int, rows: int, seed: int = 2):
        rng = np.random.default_rng(seed)
        self._batches = []
        for b in range(n_batches):
            c = _make_batch_cols(rng, rows)
            self._batches.append({
                "tx_id": np.arange(b * rows, (b + 1) * rows, dtype=np.int64),
                "tx_datetime_us": c["tx_datetime_us"],
                "customer_id": c["customer_id"],
                "terminal_id": c["terminal_id"],
                "tx_amount_cents": c["amount_cents"],
                "kafka_ts_ms": c["tx_datetime_us"] // 1000,
            })
        self._i = 0

    def poll_batch(self):
        if self._i >= len(self._batches):
            return None
        b = self._batches[self._i]
        self._i += 1
        return b

    @property
    def offsets(self):
        return [self._i]

    def seek(self, offsets):
        self._i = int(offsets[0])


def _measure(args) -> dict:
    """The measurement, in this process. → the result dict."""
    import jax
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.utils import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.features.online import (
        init_feature_state,
        update_and_featurize,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import (
        Scaler,
        transform,
    )

    dev = jax.devices()[0]
    _progress(f"platform={dev.platform} device_kind={dev.device_kind} "
              f"devices={len(jax.devices())}")
    on_cpu = jax.default_backend() == "cpu"
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        # never a CPU figure under a device metric's name: only an
        # EXPLICIT JAX_PLATFORMS=cpu (the labelled smoke run) may go on
        raise SystemExit(
            f"bench: no TPU (jax sees platform {dev.platform!r}); set "
            "JAX_PLATFORMS=cpu for the labelled CPU smoke run")
    # All measurement sections, scaled down (CI coverage of the TPU-only
    # code paths on CPU; never set by the driver).
    full = (not (on_cpu or args.quick)
            or os.environ.get("BENCH_FULL_SECTIONS") == "1")
    rng = np.random.default_rng(0)

    cfg = Config(
        features=FeatureConfig(customer_capacity=8192,
                               terminal_capacity=16384)
    )
    fcfg = cfg.features
    params, predict, skl = _build_model(args.model, rng)
    headline_z_mode = None
    if args.model == "forest":
        # The headline hot path measures the SERVING default arithmetic
        # (runtime.z_mode="auto" → int8 on TPU / f32 on CPU) — what
        # `rtfds score` actually runs since round 9, decision-identical
        # by the gemm_leaf_sum exactness contract.
        from real_time_fraud_detection_system_tpu.models.forest import (
            resolve_z_mode,
        )

        headline_z_mode = resolve_z_mode("auto")
        _forest_predict = predict

        def predict(p, x, _zm=headline_z_mode):  # noqa: F811
            return _forest_predict(p, x, _zm)
    scaler = Scaler(mean=jnp.zeros(15), scale=jnp.ones(15))

    def _step_body(fstate, params, batch):
        fstate, feats = update_and_featurize(fstate, batch, fcfg)
        probs = predict(params, transform(scaler, feats))
        return fstate, jnp.where(batch.valid, probs, 0.0)

    step = jax.jit(_step_body, donate_argnums=(0,))

    from real_time_fraud_detection_system_tpu.core.batch import make_batch

    def _measure(n_rows: int, seconds: float):
        """→ (txns_per_sec, per_batch_ms). Compiles on first call."""
        c = _make_batch_cols(rng, n_rows)
        batch = jax.tree.map(jnp.asarray, make_batch(**c))
        fstate = init_feature_state(fcfg)
        fstate, probs = step(fstate, params, batch)  # warmup/compile
        jax.block_until_ready(probs)
        # Sync every `chunk` steps so the dispatch queue stays bounded.
        chunk = 8
        t0 = time.perf_counter()
        iters = 0
        while time.perf_counter() - t0 < seconds:
            for _ in range(chunk):
                fstate, probs = step(fstate, params, batch)
            jax.block_until_ready(probs)
            iters += chunk
        wall = time.perf_counter() - t0
        return iters * n_rows / wall, wall / iters * 1e3

    # ---- throughput: start modest, scale up, keep the best ----
    if args.quick or on_cpu:
        sizes = [4096]
    else:
        sizes = [16384, 262144, 1048576]
    seconds = min(args.seconds, 2.0) if on_cpu else args.seconds
    by_size = {}
    best_tps, best_rows, best_ms = 0.0, 0, 0.0
    size_error = None
    for n_rows in sizes:
        _progress(f"measuring size={n_rows}")
        try:
            tps, ms = _measure(n_rows, seconds)
        except Exception as e:  # alloc/compile failure: keep smaller sizes
            size_error = f"{n_rows}: " + _section_error(
                f"throughput size={n_rows}", e)["error"]
            break
        _progress(f"size={n_rows} tps={tps:.0f}")
        by_size[str(n_rows)] = round(tps, 1)
        if tps > best_tps:
            best_tps, best_rows, best_ms = tps, n_rows, ms

    if best_rows == 0:
        raise RuntimeError(f"no batch size succeeded ({size_error})")

    # (The round-4 z-mode shootout — bf16 vs int8 gemm_leaf_sum microbench
    # — graduated: z_mode is now a serving knob (runtime.z_mode) and the
    # A/B moved to the engine-level detail.device_plane block below, which
    # measures the serving step rather than the isolated contraction.)

    # ---- classify latency: p50/p99 across serving batch sizes ----------
    _progress("latency percentiles")
    serve_rows = 4096
    # Engine-loop batch: on TPU, per-call dispatch overhead swamps a
    # 4k-row batch — serve at a size where the device does real work per
    # dispatch, like the throughput headline does.
    engine_rows = 65536 if not (args.quick or on_cpu) else serve_rows
    lat_iters = 10 if args.quick or on_cpu else 40
    lat_sizes = ([1024, 4096, 16384, 65536] if (full and not on_cpu)
                 else [1024, serve_rows] if full else [serve_rows])
    latency_by_batch = {}
    step_p50_ms = step_p99_ms = 0.0
    for n_rows in lat_sizes:
        c = _make_batch_cols(rng, n_rows)
        sbatch = jax.tree.map(jnp.asarray, make_batch(**c))
        sstate = init_feature_state(fcfg)
        sstate, probs = step(sstate, params, sbatch)  # warmup/compile
        jax.block_until_ready(probs)
        lats = []
        for _ in range(lat_iters):
            t0 = time.perf_counter()
            sstate, probs = step(sstate, params, sbatch)
            jax.block_until_ready(probs)
            lats.append(time.perf_counter() - t0)
        lats = np.asarray(lats)
        p50 = float(np.percentile(lats, 50) * 1e3)
        p99 = float(np.percentile(lats, 99) * 1e3)
        latency_by_batch[str(n_rows)] = {"p50_ms": round(p50, 3),
                                         "p99_ms": round(p99, 3)}
        if n_rows == serve_rows:
            step_p50_ms, step_p99_ms = p50, p99
        _progress(f"latency size={n_rows} p50={p50:.1f}ms")

    # ---- device-side step latency: chained dependent steps -------------
    # The per-call timings above include dispatch and fetch overhead.
    # Protocol: run the FULL hot-path step n times back-to-back inside ONE
    # jitted ``fori_loop`` — the feature state carries through, so
    # iterations are data-dependent and cannot overlap — with n a TRACED
    # trip count (one compile serves every n). The two-point form
    # (t(n2)-t(n1))/(n2-n1) cancels dispatch and fetch cost, leaving
    # device step time.
    device_latency_by_batch = {}
    if full or os.environ.get("BENCH_FULL_SECTIONS") == "1":
        _progress("chained device latency")

        def _chained(fstate, params, batch, n):
            def body(i, carry):
                fs, acc = carry
                fs, p = _step_body(fs, params, batch)
                return (fs, acc + p.sum())

            _, acc = jax.lax.fori_loop(
                0, n, body, (fstate, jnp.float32(0)))
            return acc

        chained = jax.jit(_chained)
        n_lo, n_hi = 8, 72
        trials = 3 if (on_cpu or args.quick) else 5
        for n_rows in lat_sizes:
            try:
                c = _make_batch_cols(rng, n_rows)
                dbatch = jax.tree.map(jnp.asarray, make_batch(**c))
                dstate = init_feature_state(fcfg)
                np.asarray(chained(dstate, params, dbatch,
                                   jnp.int32(n_lo)))  # compile
                per_step = []
                for _ in range(trials):
                    t0 = time.perf_counter()
                    np.asarray(chained(dstate, params, dbatch,
                                       jnp.int32(n_lo)))
                    t_lo = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    np.asarray(chained(dstate, params, dbatch,
                                       jnp.int32(n_hi)))
                    t_hi = time.perf_counter() - t0
                    per_step.append((t_hi - t_lo) / (n_hi - n_lo))
                ps = np.asarray(per_step) * 1e3
                p50_ms = float(np.percentile(ps, 50))
                device_latency_by_batch[str(n_rows)] = {
                    "step_ms_p50": round(p50_ms, 4),
                    "step_ms_max": round(float(ps.max()), 4),
                    # device-side throughput the chained steps imply.
                    # None when the differenced timing is
                    # jitter-dominated (<= 0).
                    "device_rows_per_s": (
                        round(n_rows / (p50_ms / 1e3), 1)
                        if p50_ms > 0 else None),
                    "chained_n": [n_lo, n_hi],
                    "trials": trials,
                }
                _progress(
                    f"device step size={n_rows} p50={p50_ms:.3f}ms")
            except Exception as e:
                device_latency_by_batch[str(n_rows)] = _section_error(
                    f"device_latency_by_batch[{n_rows}]", e)

    # ---- engine-loop latency (host decode + device step per micro-batch)
    _progress("engine loop")
    engine_stats = None
    phase_p50 = None
    host_plane = None
    device_plane = None
    if args.model == "forest":
        from real_time_fraud_detection_system_tpu.runtime.engine import (
            ScoringEngine,
        )

        n_eng = 8 if args.quick or on_cpu else 50
        # Depth-8 pipelining on TPU: per-dispatch overhead overlaps
        # across in-flight batches instead of serializing the loop.
        depth = 2 if (args.quick or on_cpu) else 8
        ecfg = Config(
            features=FeatureConfig(customer_capacity=8192,
                                   terminal_capacity=16384),
            runtime=RuntimeConfig(batch_buckets=(engine_rows,),
                                  max_batch_rows=engine_rows,
                                  trigger_seconds=0.0,
                                  pipeline_depth=depth),
        )
        def _engine_stats(e, rows=None, n=None) -> dict:
            """Warmup run (jit compile outside the stats), measured run,
            rounded stats dict — shared by every engine-loop variant."""
            rows = rows or engine_rows
            n = n or n_eng
            e.run(_RandSource(1, rows, seed=3), trigger_seconds=0.0)
            s = e.run(_RandSource(n, rows), trigger_seconds=0.0)
            return {
                "batch_rows": rows,
                "rows_per_s": round(s["rows_per_s"], 1),
                "latency_p50_ms": round(s["latency_p50_ms"], 3),
                "latency_p99_ms": round(s["latency_p99_ms"], 3),
                "host_prep_p50_ms": round(s["host_prep_p50_ms"], 3),
                "dispatch_p50_ms": round(s["dispatch_p50_ms"], 3),
                "result_wait_p50_ms": round(s["result_wait_p50_ms"], 3),
                "pipeline_depth": s["pipeline_depth"],
            }

        import dataclasses as _dc

        def _alerts_cfg(base: Config) -> Config:
            """emit_features=False twin of an engine config: the [B, 15]
            feature matrix never leaves HBM — the dominant per-batch D2H.
            Same scores, no feature columns."""
            return Config(
                features=base.features,
                runtime=_dc.replace(base.runtime, emit_features=False),
            )

        def _guarded(key: str, fn) -> None:
            """A failed variant records ITS OWN error key and never
            clobbers earlier successful measurements."""
            _progress(f"engine variant {key}")
            try:
                engine_stats[key] = fn()
            except Exception as e:
                engine_stats[key] = _section_error(f"engine_loop.{key}", e)

        engine_stats = _engine_stats(
            ScoringEngine(ecfg, kind="forest", params=params, scaler=scaler)
        )

        # ---- registry-backed before/after evidence (ROADMAP PR-1 note):
        # per-phase p50s with a parquet sink and with precompile off/on,
        # straight from the run-stats trackers + the engine's registry.
        _progress("engine loop phase p50 before/after")

        def _phase_p50_block():
            import dataclasses as _pdc
            import shutil
            import tempfile

            from real_time_fraud_detection_system_tpu.io.sink import (
                ParquetSink,
            )
            from real_time_fraud_detection_system_tpu.utils.metrics import (
                MetricsRegistry,
            )

            def _phases(s):
                return {
                    k: round(s[f"{k}_p50_ms"], 4)
                    for k in ("host_prep", "dispatch", "result_wait",
                              "sink_wait", "sink_write")
                }

            out = {}
            # sink: the parquet write on the loop's writer thread
            # (sink_write) and what the loop thread waited for it
            # (sink_wait)
            d = tempfile.mkdtemp(prefix="rtfds_bench_sink_")
            sink = ParquetSink(d)
            e = ScoringEngine(ecfg, kind="forest", params=params,
                              scaler=scaler)
            e.run(_RandSource(1, engine_rows, seed=3), sink=sink,
                  trigger_seconds=0.0)
            s = e.run(_RandSource(n_eng, engine_rows), sink=sink,
                      trigger_seconds=0.0)
            shutil.rmtree(d, ignore_errors=True)
            out["sink"] = {"rows_per_s": round(s["rows_per_s"], 1),
                           **_phases(s)}

            # precompile: the second bucket size first lands MID-STREAM
            # (after the recompile detector's warmup) — precompile off
            # pays that compile inside the loop, on dispatches a ready
            # executable and the counter stays 0
            small = max(256, engine_rows // 4)

            class _Scripted:
                def __init__(self, sizes, seed=2):
                    srng = np.random.default_rng(seed)
                    self._b = []
                    at = 0
                    for n in sizes:
                        c = _make_batch_cols(srng, n)
                        self._b.append({
                            "tx_id": np.arange(at, at + n, dtype=np.int64),
                            "tx_datetime_us": c["tx_datetime_us"],
                            "customer_id": c["customer_id"],
                            "terminal_id": c["terminal_id"],
                            "tx_amount_cents": c["amount_cents"],
                            "kafka_ts_ms": c["tx_datetime_us"] // 1000,
                        })
                        at += n
                    self._i = 0

                def poll_batch(self):
                    if self._i >= len(self._b):
                        return None
                    b = self._b[self._i]
                    self._i += 1
                    return b

                @property
                def offsets(self):
                    return [self._i]

                def seek(self, offsets):
                    self._i = int(offsets[0])

            sizes = [engine_rows] * 5 + [small, engine_rows, small]
            for label, pre in (("precompile_off", False),
                               ("precompile_on", True)):
                reg = MetricsRegistry()
                pcfg = Config(
                    features=ecfg.features,
                    runtime=_pdc.replace(
                        ecfg.runtime, batch_buckets=(small, engine_rows),
                        precompile=pre),
                )
                e = ScoringEngine(pcfg, kind="forest", params=params,
                                  scaler=scaler, metrics=reg)
                # warmup run triggers the precompile hook (when on), so
                # the measured stream never includes build-time compiles
                e.run(_RandSource(1, engine_rows, seed=3),
                      trigger_seconds=0.0)
                s = e.run(_Scripted(sizes), trigger_seconds=0.0)
                rc = reg.get("rtfds_xla_recompiles_total")
                out[label] = {
                    "rows_per_s": round(s["rows_per_s"], 1),
                    "latency_p99_ms": round(s["latency_p99_ms"], 3),
                    "mid_stream_recompiles": int(rc.value) if rc else 0,
                    **_phases(s),
                }
            return out

        try:
            phase_p50 = _phase_p50_block()
        except Exception as e:
            phase_p50 = _section_error("phase_p50", e)

        # ---- host data plane off/on (registry-backed, same protocol):
        # the engine loop over a decode-heavy (envelope) source with the
        # host-plane features off (serial decode, synchronous polling,
        # blocking fetch) vs on (parallel slab decode + background
        # prefetch + overlapped result fetch). The r05 session measured
        # the device step at ~10 ms/batch while the loop delivered one
        # every ~280 ms — this block is the before/after for closing
        # that host gap.
        _progress("host data plane off/on")

        def _host_plane_block():
            import dataclasses as _hdc

            from real_time_fraud_detection_system_tpu.core import (
                native as _nat,
            )
            from real_time_fraud_detection_system_tpu.core.envelope import (
                decode_transaction_envelopes,
                encode_transaction_envelopes,
            )
            from real_time_fraud_detection_system_tpu.runtime import (
                PrefetchSource,
            )
            from real_time_fraud_detection_system_tpu.utils.metrics import (
                MetricsRegistry,
            )

            hp_rows = 4096 if (on_cpu or args.quick) else engine_rows
            hp_batches = 6 if (on_cpu or args.quick) else 12
            rng_hp = np.random.default_rng(5)
            corpus = []
            for b in range(hp_batches + 1):  # +1: the warmup batch
                c = _make_batch_cols(rng_hp, hp_rows)
                corpus.append(encode_transaction_envelopes(
                    np.arange(b * hp_rows, (b + 1) * hp_rows,
                              dtype=np.int64),
                    c["tx_datetime_us"], c["customer_id"],
                    c["terminal_id"], c["amount_cents"]))

            class _EnvSource:
                """Kafka-shaped source: each poll decodes one envelope
                byte-batch with an explicit worker count."""

                def __init__(self, msgs_list, workers):
                    self._b = msgs_list
                    self._i = 0
                    self._w = workers

                def poll_batch(self):
                    if self._i >= len(self._b):
                        return None
                    msgs = self._b[self._i]
                    self._i += 1
                    if _nat.native_available():
                        cols, invalid = \
                            _nat.decode_transaction_envelopes_native(
                                msgs, workers=self._w)
                    else:
                        cols, invalid = decode_transaction_envelopes(msgs)
                    if invalid.any():
                        keep = ~invalid
                        cols = {k: v[keep] for k, v in cols.items()}
                    return cols

                @property
                def offsets(self):
                    return [self._i]

                def seek(self, offsets):
                    self._i = int(offsets[0])

            def _variant(workers, prefetch, overlap):
                reg = MetricsRegistry()
                vcfg = Config(
                    features=ecfg.features,
                    runtime=_hdc.replace(ecfg.runtime,
                                         fetch_overlap=overlap))
                e = ScoringEngine(vcfg, kind="forest", params=params,
                                  scaler=scaler, metrics=reg)
                e.run(_EnvSource(corpus[:1], workers),
                      trigger_seconds=0.0)  # compile outside the stats
                src = _EnvSource(corpus[1:], workers)
                if prefetch:
                    src = PrefetchSource(src, max_batches=4, registry=reg)
                s = e.run(src, trigger_seconds=0.0)
                if prefetch:
                    src.close()
                poll = reg.get("rtfds_phase_seconds", phase="source_poll")
                out = {
                    "decode_workers": workers,
                    "prefetch_batches": 4 if prefetch else 0,
                    "fetch_overlap": overlap,
                    "rows_per_s": round(s["rows_per_s"], 1),
                    "source_poll_p50_ms": round(
                        poll.percentile(50) * 1e3, 3)
                    if poll is not None and poll.count else None,
                    "result_wait_p50_ms": round(
                        s["result_wait_p50_ms"], 3),
                }
                ov = reg.get("rtfds_fetch_overlap_seconds_total")
                if ov is not None and ov.value:
                    out["fetch_overlap_s_total"] = round(ov.value, 4)
                return out

            return {
                "batch_rows": hp_rows,
                "batches": hp_batches,
                "off": _variant(1, False, False),
                "on": _variant(max(2, _nat.get_decode_workers()), True,
                               True),
            }

        try:
            host_plane = _host_plane_block()
        except Exception as e:
            host_plane = _section_error("host_plane", e)

        # ---- device plane off/on (the round-9 A/B): the SERVING engine
        # step measured over z_mode {f32, int8} × fused Pallas step
        # {off, on} under precompile, with exactness asserted from the
        # served probabilities (the int8 arm must be decision-identical
        # — on CPU bit-identical — to the f32 control). Folds the old
        # gemm_leaf_sum z-mode microbench shootout into an engine-level
        # measurement; per-arm mfu/mfu_of_ceiling are annotated once the
        # roofline ceiling is computed below.
        _progress("device plane z_mode x fused")

        def _device_plane_block():
            import dataclasses as _zdc

            from real_time_fraud_detection_system_tpu.utils.metrics import (
                MetricsRegistry,
            )

            out = {"batch_rows": engine_rows, "batches": n_eng}
            probs_by = {}

            def _arm(label, z, fused):
                _progress(f"device plane {label}")
                reg = MetricsRegistry()
                acfg = Config(
                    features=ecfg.features,
                    runtime=_zdc.replace(ecfg.runtime, z_mode=z,
                                         use_pallas=fused,
                                         precompile=True))
                e = ScoringEngine(acfg, kind="forest", params=params,
                                  scaler=scaler, metrics=reg)
                cap = _ProbsCap()
                # warmup run triggers precompile: the measured stream
                # never includes build-time compiles
                e.run(_RandSource(1, engine_rows, seed=3),
                      trigger_seconds=0.0)
                s = e.run(_RandSource(n_eng, engine_rows), sink=cap,
                          trigger_seconds=0.0)
                rc = reg.get("rtfds_xla_recompiles_total")
                probs_by[label] = cap.concat()
                out[label] = {
                    "z_mode": e.z_mode,
                    "use_pallas": fused,
                    "rows_per_s": round(s["rows_per_s"], 1),
                    "latency_p50_ms": round(s["latency_p50_ms"], 3),
                    "mid_stream_recompiles": int(rc.value) if rc else 0,
                }

            _arm("z_f32_fused_off", "f32", False)
            _arm("z_int8_fused_off", "int8", False)
            if on_cpu and not os.environ.get("BENCH_FULL_SECTIONS"):
                # the fused kernel only interprets off-TPU — measuring it
                # there times the interpreter, not the device plane
                out["fused_arms_skipped"] = "cpu (interpret-only)"
            else:
                _arm("z_f32_fused_on", "f32", True)
                _arm("z_int8_fused_on", "int8", True)
            a, b = (probs_by["z_f32_fused_off"],
                    probs_by["z_int8_fused_off"])
            out["max_abs_delta_int8_vs_f32"] = float(np.abs(a - b).max())
            out["decision_flips_int8_vs_f32"] = int(
                ((a >= 0.5) != (b >= 0.5)).sum())
            if "z_int8_fused_on" in probs_by:
                f = probs_by["z_int8_fused_on"]
                out["max_abs_delta_fused_vs_unfused"] = float(
                    np.abs(f - b).max())
                out["decision_flips_fused_vs_unfused"] = int(
                    ((f >= 0.5) != (b >= 0.5)).sum())
            return out

        try:
            device_plane = _device_plane_block()
        except Exception as e:
            device_plane = _section_error("device_plane", e)

        if full:
            _progress("engine loop alerts-only")
            _guarded("alerts_only", lambda: _engine_stats(
                ScoringEngine(_alerts_cfg(ecfg), kind="forest",
                              params=params, scaler=scaler)))
        if full:
            # Big-batch loop: amortize the per-batch fixed costs further
            # (the serving analogue of the 1M-row throughput headline).
            _progress("engine loop 262k")
            big = 262144 if not on_cpu else 8192
            bcfg = Config(
                features=FeatureConfig(customer_capacity=8192,
                                       terminal_capacity=16384),
                runtime=RuntimeConfig(batch_buckets=(big,),
                                      max_batch_rows=big,
                                      trigger_seconds=0.0,
                                      pipeline_depth=depth),
            )
            _guarded("big_batch", lambda: _engine_stats(
                ScoringEngine(bcfg, kind="forest", params=params,
                              scaler=scaler), rows=big, n=12))
            _guarded("big_batch_alerts", lambda: _engine_stats(
                ScoringEngine(_alerts_cfg(bcfg), kind="forest",
                              params=params, scaler=scaler),
                rows=big, n=12))
            # bf16 feature emission: halves the feature D2H;
            # predictions stay f32-exact.
            _guarded("big_batch_bf16", lambda: _engine_stats(
                ScoringEngine(
                    bcfg.replace(runtime=_dc.replace(
                        bcfg.runtime, emit_dtype="bfloat16")),
                    kind="forest", params=params, scaler=scaler),
                rows=big, n=12))

            # Selective emission: probs for EVERY row, feature columns
            # only for rows clearing the alert threshold — the full
            # analyzed schema lands for flagged traffic while clean rows
            # skip the dominant D2H (one packed transfer per batch, same
            # round-trip count as alerts-only). Threshold = this random
            # stream's own q99, i.e. ~1% flagged — the reference's alert
            # regime (0.88% test-set fraud rate).
            def _selective():
                # Calibrate on the EVOLVED feature state: the probability
                # tail drifts as the window state accumulates, so a
                # fresh-state probe under-sets the threshold and every
                # batch overflows the compaction cap. Run a full-emission
                # probe engine over the exact stream the measurement will
                # see (same seeds, same batching) and take q99 of the
                # probabilities it actually serves.
                cal = _ProbsCap()
                probe = ScoringEngine(bcfg, kind="forest", params=params,
                                      scaler=scaler)
                probe.run(_RandSource(1, big, seed=3), trigger_seconds=0.0)
                probe.run(_RandSource(12, big), sink=cal,
                          trigger_seconds=0.0)
                allp = cal.concat()
                # The forest's probability mass is discrete (tree-vote
                # averages): the q99 VALUE can carry a fat atom, and the
                # engine flags with >=, so thresholding AT q99 can flag
                # far more than 1% (measured: 29% — every batch
                # overflowed). Step just above the atom instead.
                thr = float(np.nextafter(
                    np.float32(np.quantile(allp, 0.99)), np.float32(2.0)))
                thr = min(max(thr, 1e-6), 1.0)
                e = ScoringEngine(
                    bcfg.replace(runtime=_dc.replace(
                        bcfg.runtime, emit_threshold=thr,
                        # true flagged rate ~1% ⇒ 1/32 still 3× headroom,
                        # and the packed transfer shrinks toward the
                        # alerts-only floor (probs dominate it)
                        emit_cap_fraction=1 / 32)),
                    kind="forest", params=params, scaler=scaler)
                st = _engine_stats(e, rows=big, n=12)
                st["emit_threshold_q99"] = round(thr, 6)
                st["flagged_fraction"] = round(
                    float((allp >= thr).mean()), 5)
                st["overflow_batches"] = e.selective_overflows
                return st

            _progress("engine loop 262k selective emission")
            _guarded("big_batch_selective", _selective)
        if not (on_cpu or args.quick):
            # Sharded serving loop on a 1-chip mesh: the shard_map step +
            # partition/spill machinery running on real hardware (the
            # multi-chip path minus the extra chips; four chips are
            # `python chip_smoke.py --chips 4`). Guarded: a failure here
            # must not discard the already-measured headline numbers.
            _progress("sharded engine loop (1-device mesh)")
            from real_time_fraud_detection_system_tpu.runtime import (
                ShardedScoringEngine,
            )

            try:
                engine_stats["sharded_1dev"] = _engine_stats(
                    ShardedScoringEngine(
                        ecfg, kind="forest", params=params, scaler=scaler,
                        n_devices=1, rows_per_shard=engine_rows,
                    )
                )
            except Exception as e:
                engine_stats["sharded_1dev"] = _section_error(
                    "engine_loop.sharded_1dev", e)
        if on_cpu and skl is not None:
            # The CPU serving path users actually get (--scorer cpu):
            # framework feature engine + host-side sklearn classify. This
            # is the loop to compare with cpu_sklearn_txns_per_sec — the
            # GEMM loop above is a TPU kernel interpreted on CPU.
            _progress("cpu-oracle engine loop")

            class _SklOracle:
                def __init__(self, inner):
                    self._inner = inner

                def predict_proba(self, x):
                    return self._inner.predict_proba(x)[:, 1]

            engine_stats = {
                "gemm_on_cpu": engine_stats,
                "cpu_oracle": _engine_stats(
                    ScoringEngine(ecfg, kind="forest", params=params,
                                  scaler=scaler, scorer="cpu",
                                  cpu_model=_SklOracle(skl))
                ),
            }

    def _timed_rows_per_s(run_once, rows: int, seconds: float) -> float:
        """Chunked-dispatch timing shared by the kernel-comparison blocks:
        ``run_once()`` returns the value to sync on; the caller has already
        made one warmed call (compile excluded from the clock)."""
        t0 = time.perf_counter()
        iters = 0
        while time.perf_counter() - t0 < seconds:
            for _ in range(4):
                out = run_once()
            jax.block_until_ready(out)
            iters += 4
        return round(iters * rows / (time.perf_counter() - t0), 1)

    # ---- fused Pallas featurize+score vs plain-jnp composition ---------
    # The linear-scorer kernel (ops/pallas_kernels.py). On CPU it only
    # interprets (slow, exact) — measured on TPU only:
    # quantify the fused kernel against XLA's own fusion.
    pallas_stats = None
    if full:
        _progress("pallas fused vs unfused")
        try:
            from real_time_fraud_detection_system_tpu.features.online import (
                update_and_score_pallas,
            )
            from real_time_fraud_detection_system_tpu.models.logreg import (
                init_logreg,
                logreg_predict_proba,
            )

            lp = init_logreg(15)
            pl_rows = 65536 if not on_cpu else 1024
            c = _make_batch_cols(rng, pl_rows)
            pbatch = jax.tree.map(jnp.asarray, make_batch(**c))

            def unfused(fstate, batch):
                fstate, feats = update_and_featurize(fstate, batch, fcfg)
                pr = logreg_predict_proba(lp, transform(scaler, feats))
                return fstate, jnp.where(batch.valid, pr, 0.0)

            def fused(fstate, batch):
                fstate, pr, _ = update_and_score_pallas(
                    fstate, batch, fcfg, scaler.mean, scaler.scale,
                    lp.w, lp.b)
                return fstate, jnp.where(batch.valid, pr, 0.0)

            pallas_stats = {}
            outs = {}
            for name, fn in (("unfused", unfused), ("fused", fused)):
                jfn = jax.jit(fn, donate_argnums=(0,))
                fs = init_feature_state(fcfg)
                fs, pr = jfn(fs, pbatch)
                jax.block_until_ready(pr)
                outs[name] = np.asarray(pr)

                def once(jfn=jfn):
                    nonlocal fs
                    fs, pr = jfn(fs, pbatch)
                    return pr

                pallas_stats[f"{name}_rows_per_s"] = _timed_rows_per_s(
                    once, pl_rows, min(args.seconds, 3.0))
            pallas_stats["max_abs_delta"] = float(
                np.abs(outs["fused"] - outs["unfused"]).max())
        except Exception as e:
            pallas_stats = _section_error("pallas_fused", e)

    # ---- fused Pallas forest kernel vs XLA's GEMM fusion ---------------
    # The flagship classify chain (ops/pallas_forest.py): does a hand-tiled
    # VMEM-resident kernel beat XLA's automatic fusion of the three-GEMM
    # composition? Measured classify-only so the halves are isolated from
    # the featurize cost. (Round-4 measurement: XLA wins — its fusion of
    # this chain is already intermediate-free; the kernel stays an opt-in
    # proof of hand-fusibility, not the default.)
    pallas_forest_stats = None
    if args.model == "forest" and full and not on_cpu:
        _progress("pallas forest kernel vs xla gemm")
        try:
            from real_time_fraud_detection_system_tpu.models.forest import (
                gemm_predict_proba,
            )
            from real_time_fraud_detection_system_tpu.ops.pallas_forest import (
                pallas_predict_proba,
                to_pallas,
            )

            pfr = 262_144
            xq = jnp.asarray(
                rng.normal(0, 1, (pfr, 15)).astype(np.float32))
            pf = to_pallas(params)
            fns = {
                "xla_gemm": jax.jit(lambda x: gemm_predict_proba(params, x)),
                "pallas_kernel": jax.jit(
                    lambda x: pallas_predict_proba(pf, x, block_rows=2048,
                                                   interpret=False)),
            }
            pallas_forest_stats = {"rows": pfr}
            pouts = {}
            for name, fn in fns.items():
                pr = fn(xq)
                jax.block_until_ready(pr)
                pouts[name] = np.asarray(pr)
                pallas_forest_stats[f"{name}_rows_per_s"] = \
                    _timed_rows_per_s(lambda fn=fn: fn(xq), pfr,
                                      min(args.seconds, 3.0))
            pallas_forest_stats["max_abs_delta"] = float(
                np.abs(pouts["xla_gemm"] - pouts["pallas_kernel"]).max())

            # hot-path split: featurize-only throughput at the same size,
            # so headline = harmonic composition of the two halves is on
            # record (classify-only is the xla_gemm row above)
            def _feat_only(fstate, batch):
                fstate, feats = update_and_featurize(fstate, batch, fcfg)
                return fstate, feats.sum()

            jfeat = jax.jit(_feat_only, donate_argnums=(0,))
            fbatch = jax.tree.map(
                jnp.asarray, make_batch(**_make_batch_cols(rng, pfr)))
            fs = init_feature_state(fcfg)
            fs, s = jfeat(fs, fbatch)
            jax.block_until_ready(s)

            def _feat_once():
                nonlocal fs
                fs, s = jfeat(fs, fbatch)
                return s

            pallas_forest_stats["featurize_only_rows_per_s"] = \
                _timed_rows_per_s(_feat_once, pfr, min(args.seconds, 3.0))
        except Exception as e:
            pallas_forest_stats = _section_error("pallas_forest", e)

    # ---- training throughput on the device -----------------------------
    # The reference records per-classifier training_execution_time hooks
    # (shared_functions.py:312-320) but never publishes values; here the
    # jax training loops (logreg SGD + MLP) are timed on whatever backend
    # is live — the on-chip analogue of those hooks.
    train_stats = None
    if full:
        _progress("train throughput")
        try:
            from real_time_fraud_detection_system_tpu.models.logreg import (
                train_logreg,
            )
            from real_time_fraud_detection_system_tpu.models.mlp import (
                train_mlp,
            )

            tr_rows = 262_144 if not on_cpu else 16_384
            xtr2 = rng.normal(0, 1, (tr_rows, 15)).astype(np.float32)
            ytr2 = (xtr2[:, 0] - 0.3 * xtr2[:, 2] > 0.7).astype(np.int32)
            train_stats = {"rows": tr_rows, "batch_size": 16384}

            def _timed_fit(fit, epochs: int) -> float:
                t0 = time.perf_counter()
                params_out = fit(epochs)
                jax.block_until_ready(jax.tree.leaves(params_out))
                return time.perf_counter() - t0

            for name, fit in (
                ("logreg", lambda e: train_logreg(
                    xtr2, ytr2, batch_size=16384, epochs=e)),
                ("mlp", lambda e: train_mlp(
                    xtr2, ytr2, hidden=(64, 32), batch_size=16384,
                    epochs=e)),
            ):
                # train_* builds its jitted step per call, so any single
                # call includes one compile. Report the cold number (what
                # one call costs) AND a warm steady-state figure from
                # differencing a 1-epoch and an N-epoch call — the
                # compile cancels, leaving N-1 epochs of step time. The
                # epoch ladder grows until the delta clears the noise
                # floor (round 4 used a fixed 8-epoch delta, which on TPU
                # finished under the threshold and silently dropped the
                # warm number — the figure the training story owes).
                _progress(f"train {name} cold")
                w1 = _timed_fit(fit, 1)
                train_stats[f"{name}_cold_rows_per_s"] = round(
                    tr_rows / w1, 1)
                for hi in (41, 201):
                    _progress(f"train {name} warm x{hi}")
                    whi = _timed_fit(fit, hi)
                    if whi - w1 > 0.25:
                        train_stats[f"{name}_warm_rows_per_s"] = round(
                            (hi - 1) * tr_rows / (whi - w1), 1)
                        train_stats[f"{name}_warm_epochs"] = hi - 1
                        break

        except Exception as e:
            train_stats = _section_error("train", e)

        # Tree-ensemble fit wall-clock (the reference's
        # training_execution_time hook for its RandomForest; full
        # reference-scale fits are recorded by `rtfds compare`, see
        # BASELINE.md). Own guard: a forest failure must not discard the
        # logreg/mlp warm figures measured above.
        _progress("train forest fit")
        try:
            from real_time_fraud_detection_system_tpu.models.forest import (
                fit_forest,
            )

            n_fit = 32_768 if not on_cpu else 8_192
            xtrf = rng.normal(0, 1, (n_fit, 15)).astype(np.float32)
            ytrf = (xtrf[:, 0] - 0.3 * xtrf[:, 2] > 0.7).astype(np.int32)
            t0 = time.perf_counter()
            fit_forest(xtrf, ytrf, n_trees=100, max_depth=8)
            w = time.perf_counter() - t0
            train_stats = train_stats if isinstance(train_stats, dict) \
                else {}
            train_stats["forest_fit"] = {
                "rows": n_fit, "n_trees": 100, "max_depth": 8,
                "wall_s": round(w, 2),
                "rows_per_s": round(n_fit / w, 1),
            }
        except Exception as e:
            err = _section_error("train.forest_fit", e)
            if isinstance(train_stats, dict):
                train_stats["forest_fit"] = err

    # ---- long-context scorer: sequence serving throughput --------------
    # The fused history step (features/history.py): per-customer ring
    # update + causal-transformer score per row. Guarded — a failure here
    # must never discard the headline numbers.
    _progress("sequence scorer")
    seq_stats = None
    try:
        from real_time_fraud_detection_system_tpu.features.history import (
            init_history_state,
            update_and_score,
        )
        from real_time_fraud_detection_system_tpu.models.sequence import (
            init_transformer,
        )

        tparams = init_transformer(
            d_model=32, n_heads=2, n_layers=2, d_ff=64, seed=0)
        seq_step = jax.jit(update_and_score, static_argnums=(3,),
                           donate_argnums=(0,))

        def _measure_seq(history_len: int, rows: int, iters: int) -> dict:
            """One sequence-scorer measurement: build, warmup, timed
            loop, stats — shared by the K=32 base and long-K variants."""
            from real_time_fraud_detection_system_tpu.features.history import (
                _attn_fn_for,
            )

            cfg_k = FeatureConfig(
                customer_capacity=8192, terminal_capacity=1024,
                history_len=history_len)
            c = _make_batch_cols(rng, rows)
            b = jax.tree.map(jnp.asarray, make_batch(**c))
            st = init_history_state(cfg_k)
            st, p = seq_step(st, tparams, b, cfg_k)
            jax.block_until_ready(p)
            t0 = time.perf_counter()
            for _ in range(iters):
                st, p = seq_step(st, tparams, b, cfg_k)
            jax.block_until_ready(p)
            return {
                "txns_per_sec": round(
                    iters * rows / (time.perf_counter() - t0), 1),
                "batch_rows": rows,
                "history_len": history_len,
                # derived from the real dispatch, never hardcoded
                "attn": ("naive" if _attn_fn_for(cfg_k, history_len)
                         is None else "blockwise"),
            }

        seq_rows = 4096 if (args.quick or on_cpu) else 65536
        seq_stats = _measure_seq(
            32, seq_rows, iters=2 if (args.quick or on_cpu) else 20)
        seq_stats["d_model"] = 32
        seq_stats["backend"] = jax.default_backend()

        if full:
            # Long-context variant: K past seq_attn_block so the serving
            # transformer runs the blockwise (flash) attention — the
            # [B, H, K, K] naive form would OOM at production batch
            # sizes (137 GB at K=512/B=64k). Own guard: a failure here
            # records its own error key, never the base measurement's.
            _progress("sequence scorer long-history")
            try:
                lh_rows = 8192 if not on_cpu else 1024
                seq_stats["long_history"] = _measure_seq(
                    256, lh_rows, iters=2 if on_cpu else 10)
                # the point of this row is the flash path — refuse to
                # record a mislabeled naive measurement if the auto
                # threshold ever moves past 256
                assert seq_stats["long_history"]["attn"] == "blockwise"
                # Decomposition of the K=32 → K=256 gap (round-4 verdict:
                # the 11× drop mixed batch-size and attention cost).
                # K=32 at the SAME small batch isolates the batch-size
                # share; K=256 at the full batch (guarded — big
                # activations) isolates the attention share. Each row
                # guards itself so a failure never clobbers the
                # already-recorded long_history measurement.
                try:
                    seq_stats["k32_same_small_batch"] = _measure_seq(
                        32, lh_rows, iters=2 if on_cpu else 10)
                except Exception as e:
                    seq_stats["k32_same_small_batch"] = _section_error(
                        "sequence_scorer.k32_same_small_batch", e)
                try:
                    seq_stats["long_history_full_batch"] = _measure_seq(
                        256, seq_rows, iters=2 if on_cpu else 5)
                except Exception as e:
                    seq_stats["long_history_full_batch"] = _section_error(
                        "sequence_scorer.long_history_full_batch", e)
            except Exception as e:
                seq_stats["long_history"] = _section_error(
                    "sequence_scorer.long_history", e)
    except Exception as e:
        seq_stats = _section_error("sequence_scorer", e)

    # ---- host ingress: Debezium envelope decode rate --------------------
    # SURVEY's hard part: 1M txns/s of JSON envelopes bottlenecks on parse
    # before the TPU; the C++ scanner is the line-rate path.
    _progress("ingest decode rate")
    from real_time_fraud_detection_system_tpu.core import native
    from real_time_fraud_detection_system_tpu.core.envelope import (
        decode_transaction_envelopes_fast,
        encode_transaction_envelopes,
    )

    n_env = 20_000 if args.quick or on_cpu else 100_000
    env_cols = _make_batch_cols(rng, n_env)
    msgs = encode_transaction_envelopes(
        np.arange(n_env, dtype=np.int64), env_cols["tx_datetime_us"],
        env_cols["customer_id"], env_cols["terminal_id"],
        env_cols["amount_cents"],
    )
    decode_transaction_envelopes_fast(msgs[:256])  # warm (builds C++ lib)
    t0 = time.perf_counter()
    decode_transaction_envelopes_fast(msgs)
    ingest_rate = n_env / (time.perf_counter() - t0)

    # ---- MFU (model FLOPs only, bf16 peak denominator: a lower bound) ---
    flops_row = _model_flops_per_row(params)
    # no peak (and so no MFU) for the labelled CPU smoke run; on any
    # accelerator an unknown device_kind raises
    peak = 0.0 if on_cpu else _peak_flops(dev.device_kind)
    mfu = best_tps * flops_row / peak if peak > 0 else None
    # Roofline ceiling: the hot path is bound by the featurize half —
    # scatter/gather passes over the window state in HBM (random access,
    # ~7 ms per 1M-row pass on v5e; ~20 passes for 3 windows × {count,
    # value} × {update, query} × {customer, terminal}) — NOT by the MXU.
    # The measured featurize-only rate IS that memory roofline, so the
    # achievable MFU ceiling for this op mix is featurize_rate ×
    # classify_flops / peak; mfu_of_ceiling says how much of the
    # achievable ceiling the headline captures (DESIGN.md §Roofline).
    # Measured UNCONDITIONALLY (round 9): the headline detail always
    # carries mfu/mfu_ceiling/mfu_of_ceiling, so every session's device-
    # plane claims have the same denominator on record (the pallas_forest
    # block's featurize figure is reused when it already measured one).
    mfu_ceiling = None
    mfu_of_ceiling = None
    featurize_rate = None
    if (isinstance(pallas_forest_stats, dict)
            and pallas_forest_stats.get("featurize_only_rows_per_s")):
        featurize_rate = float(
            pallas_forest_stats["featurize_only_rows_per_s"])
    else:
        _progress("featurize-only roofline")
        try:
            feat_rows = min(best_rows, 4096 if (on_cpu or args.quick)
                            else 262_144)

            def _feat_only(fstate, batch):
                fstate, feats = update_and_featurize(fstate, batch, fcfg)
                return fstate, feats.sum()

            jfeat = jax.jit(_feat_only, donate_argnums=(0,))
            fbatch = jax.tree.map(
                jnp.asarray, make_batch(**_make_batch_cols(rng, feat_rows)))
            ffs = init_feature_state(fcfg)
            ffs, fsum = jfeat(ffs, fbatch)
            jax.block_until_ready(fsum)

            def _feat_once():
                nonlocal ffs
                ffs, fsum = jfeat(ffs, fbatch)
                return fsum

            featurize_rate = _timed_rows_per_s(
                _feat_once, feat_rows, min(args.seconds, 2.0))
        except Exception as e:
            _section_error("featurize_only_roofline", e)
    if featurize_rate and peak > 0:
        mfu_ceiling = round(featurize_rate * flops_row / peak, 4)
        if mfu_ceiling > 0:
            mfu_of_ceiling = round(mfu / mfu_ceiling, 3)
    if isinstance(device_plane, dict) and peak > 0:
        # per-arm MFU annotation: the engine-level A/B reads as
        # mfu_of_ceiling before/after, not just rows/s
        device_plane["mfu_ceiling"] = mfu_ceiling
        for arm in device_plane.values():
            if isinstance(arm, dict) and "rows_per_s" in arm:
                arm_mfu = arm["rows_per_s"] * flops_row / peak
                arm["mfu"] = round(arm_mfu, 4)
                if mfu_ceiling:
                    arm["mfu_of_ceiling"] = round(arm_mfu / mfu_ceiling, 3)

    # ---- tiered feature-store scale curve (detail.state_scale) ----------
    # ROADMAP item 2's proof shape, extended to the host cold tier: key
    # universe 64k → 10M two-tier, then 100M with features.cold_store
    # (demote-don't-discard + promote-before-score) × Zipf skew
    # with a BOUNDED hot tier (key_mode="exact") — loop rows/s must stay
    # flat (the state never grows past the working set), per-tier state
    # bytes must hold under --state-hbm-budget-mb (validated at engine
    # build), and the dense-tier hit rate quantifies what the sketch
    # tier absorbs. Also measures v2 delta-checkpoint bytes + restore
    # time of the bounded state against the dense-at-10M control's
    # static footprint.
    _progress("state scale")
    state_scale = None
    try:
        state_scale = _state_scale_block(args, on_cpu)
    except Exception as e:
        state_scale = _section_error("state_scale", e)

    # ---- CPU sklearn baseline (the reference-equivalent predict_proba) --
    # Measured at the headline batch size, capped at 65,536 rows per call
    # to bound a single predict_proba's cost; sklearn RF throughput is
    # batch-size-flat at that scale, so vs_baseline stays a fair
    # per-row-throughput comparison (cap recorded as cpu_baseline_rows).
    _progress("cpu baseline")
    vs = 0.0
    cpu_tps = None
    if skl is not None:
        base_rows = min(best_rows, 65536)  # bound a single call's cost
        feats = np.random.default_rng(1).normal(0, 1, (base_rows, 15))
        t0 = time.perf_counter()
        cpu_iters = 0
        while cpu_iters == 0 or time.perf_counter() - t0 < 2.0:
            skl.predict_proba(feats)
            cpu_iters += 1
        cpu_tps = cpu_iters * base_rows / (time.perf_counter() - t0)
        vs = best_tps / cpu_tps if cpu_tps > 0 else 0.0

    detail = {
        "model": args.model,
        "batch_rows": best_rows,
        "per_batch_ms": round(best_ms, 3),
        "txns_per_sec_by_batch": by_size,
        "p50_classify_ms": round(step_p50_ms, 3),
        "p99_classify_ms": round(step_p99_ms, 3),
        "latency_by_batch": latency_by_batch,
        "device_latency_by_batch": device_latency_by_batch,
        "engine_loop": engine_stats,
        "mfu": None if mfu is None else round(mfu, 4),
        "mfu_ceiling": mfu_ceiling,
        "mfu_of_ceiling": mfu_of_ceiling,
        "headline_z_mode": headline_z_mode,
        "model_flops_per_row": flops_row,
        "peak_flops": peak or None,
        "device": str(dev),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "backend": jax.default_backend(),
        "ingest_envelopes_per_sec": round(ingest_rate, 1),
        "ingest_decoder": "native" if native.native_available() else
        "python",
    }
    if phase_p50 is not None:
        # before/after per-phase p50 evidence: sync vs async sink,
        # precompile off vs on (mid_stream_recompiles is the proof)
        detail["phase_p50_ms"] = phase_p50
    if host_plane is not None:
        # engine-loop rows/s over a decode-heavy source with the host
        # data plane off vs on (parallel decode + prefetch + overlapped
        # fetch), same run protocol — the host-gap before/after
        detail["host_plane"] = host_plane
    if device_plane is not None:
        # serving-engine z_mode {f32,int8} × fused-step {off,on} A/B
        # under precompile, exactness asserted from served probs — the
        # engine-level successor of the round-4 z-mode microbench
        detail["device_plane"] = device_plane
    if train_stats is not None:
        detail["train"] = train_stats
    if pallas_stats is not None:
        detail["pallas_fused"] = pallas_stats
    if pallas_forest_stats is not None:
        detail["pallas_forest"] = pallas_forest_stats
    if seq_stats is not None:
        detail["sequence_scorer"] = seq_stats
    if cpu_tps is not None:
        detail["cpu_sklearn_txns_per_sec"] = round(cpu_tps, 1)
        detail["cpu_baseline_rows"] = base_rows
    if size_error:
        detail["size_scale_stopped"] = size_error
    if state_scale is not None:
        detail["state_scale"] = state_scale

    # Registry snapshot beside the headline (ROADMAP PR-1 note): the
    # engine loops above populated rtfds_phase_seconds / rtfds_batch_
    # latency_seconds / rtfds_xla_* in the process registry — dump the
    # /metrics.json shape to a sidecar file so bench claims can cite
    # per-phase p50s.
    snap_path = os.environ.get("BENCH_METRICS_OUT", "BENCH_METRICS.json")
    try:
        from real_time_fraud_detection_system_tpu.utils.metrics import (
            get_registry,
        )

        with open(snap_path, "w", encoding="utf-8") as f:
            json.dump(get_registry().snapshot(), f)
        detail["metrics_snapshot"] = snap_path
    except OSError as e:
        detail["metrics_snapshot_error"] = _section_error(
            "metrics_snapshot", e)["error"]

    value = round(best_tps, 1)
    if on_cpu and cpu_tps:
        # On CPU the framework serves via the sklearn oracle
        # (``--scorer cpu`` — the reference-equivalent pipeline), so THAT
        # is the honest CPU headline. The MXU-shaped GEMM kernel run on
        # CPU is reported alongside, clearly labeled — it is a TPU kernel
        # being interpreted on the wrong hardware, not a regression.
        detail["cpu_headline"] = "sklearn_oracle (--scorer cpu path)"
        detail["jax_cpu_txns_per_sec"] = round(best_tps, 1)
        value = round(cpu_tps, 1)
        vs = 1.0
    detail["section_errors"] = list(_SECTION_ERRORS)
    return {
        "metric": "score_txns_per_sec",
        "value": value,
        "unit": "txns/s",
        "vs_baseline": round(vs, 3),
        "detail": detail,
    }


class _ZipfSource:
    """Pre-generated Zipf-skewed micro-batches over an ``n_keys``
    universe with the day advancing every few batches (so recency
    compaction has dead history to reclaim). Generation cost stays
    outside the measured loop, like ``_RandSource``."""

    def __init__(self, n_batches: int, rows: int, sampler, day_every: int,
                 seed: int = 2):
        from real_time_fraud_detection_system_tpu.data.generator import (
            zipf_stream_cols,
        )

        rng = np.random.default_rng(seed)
        self._batches = [
            zipf_stream_cols(rng, rows, sampler,
                             n_terminals=max(sampler.n_keys // 8, 64),
                             day=20200 + b // day_every,
                             tx_id_start=b * rows)
            for b in range(n_batches)
        ]
        self._i = 0

    def poll_batch(self):
        if self._i >= len(self._batches):
            return None
        b = self._batches[self._i]
        self._i += 1
        return b

    @property
    def offsets(self):
        return [self._i]

    def seek(self, offsets):
        self._i = int(offsets[0])


def _state_scale_block(args, on_cpu: bool) -> dict:
    """The ``detail.state_scale`` measurement (see call-site comment)."""
    import dataclasses as _dc
    import tempfile

    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.data.generator import (
        ZipfKeySampler,
    )
    from real_time_fraud_detection_system_tpu.features.online import (
        state_bytes,
    )
    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        Checkpointer,
    )
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.runtime.engine import (
        ScoringEngine,
    )
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )

    small = on_cpu or args.quick
    rows = 4096 if small else 65536
    n_batches = 8 if small else 24
    skew = 1.1
    budget_mb = args.state_hbm_budget_mb or 256.0
    fcfg = FeatureConfig(
        key_mode="exact",
        customer_capacity=1 << 15,
        terminal_capacity=1 << 15,
        cms_width=1 << 15,
        compact_every=4,
        state_hbm_budget_mb=budget_mb,
    )
    cfg = Config(
        features=fcfg,
        runtime=RuntimeConfig(batch_buckets=(rows,), max_batch_rows=rows,
                              precompile=True),
    )
    scaler = Scaler(mean=np.zeros(15, np.float32),
                    scale=np.ones(15, np.float32))
    sb = state_bytes(fcfg)
    out = {
        "skew": skew,
        "batch_rows": rows,
        "hot_tier_slots": fcfg.customer_capacity + fcfg.terminal_capacity,
        "hbm_budget_mb": budget_mb,
        "state_bytes": sb,
        "within_budget": sb["total"] <= budget_mb * 2 ** 20,
        "universes": {},
    }
    base_rate = None
    last_engine = None
    for n_keys in (65536, 1 << 20, 10_000_000):
        _progress(f"state scale universe {n_keys}")
        sampler = ZipfKeySampler(n_keys, skew)
        reg = MetricsRegistry()
        eng = ScoringEngine(cfg, kind="logreg", params=init_logreg(15),
                            scaler=scaler, metrics=reg)
        eng.run(_ZipfSource(2, rows, sampler, day_every=1, seed=7))  # warm
        stats = eng.run(_ZipfSource(n_batches, rows, sampler,
                                    day_every=max(n_batches // 6, 1)))
        dense = reg.get("rtfds_feature_tier_rows_total", tier="dense")
        cms = reg.get("rtfds_feature_tier_rows_total", tier="cms")
        d = dense.value if dense is not None else 0.0
        c = cms.value if cms is not None else 0.0
        rec = reg.family_total("rtfds_feature_slots_reclaimed_total") or 0
        recompiles = reg.get("rtfds_xla_recompiles_total")
        rate = stats["rows_per_s"]
        if base_rate is None:
            base_rate = rate
        out["universes"][str(n_keys)] = {
            "rows_per_s": round(rate, 1),
            "vs_64k": round(rate / base_rate, 3) if base_rate else None,
            "dense_hit_rate": round(d / (d + c), 4) if d + c else 1.0,
            "slots_reclaimed": int(rec),
            "mid_stream_recompiles": (recompiles.value
                                      if recompiles is not None else 0.0),
        }
        last_engine = eng
    # ---- 100M-key cold-tier cell ------------------------------------
    # The third tier: a bounded hot tier, 10× the 10M directory sweep —
    # compaction DEMOTES evicted keys' exact rows to host segments
    # (features.cold_store) instead of discarding them, and a returning
    # key is promoted back BEFORE the step that scores its row. The tier
    # is sized by its rule (README, Cold tier): the slots hold eight
    # batches' keys, half of them are kept free (cold_highwater 0.5) and
    # a pass can demote what the four batches between two passes admit —
    # so every key is exact (dense_hit_rate 1.0, exactness_degraded_keys
    # 0) and a promote lane always finds a slot. The hot tier is the
    # sweep's 2×32k slots at the quick size; HBM stays the static
    # state_bytes() (the cold tier is host memory/disk). A CPU figure.
    n_cold = 100_000_000
    _progress(f"state scale universe {n_cold} (cold tier)")
    with tempfile.TemporaryDirectory() as td_cold:
        cold_slots = max(fcfg.customer_capacity, 8 * rows)
        cold_fcfg = _dc.replace(
            fcfg, cold_store=td_cold, keydir_probes=16,
            customer_capacity=cold_slots, terminal_capacity=cold_slots,
            cold_highwater=0.5, cold_demote_slots=4 * rows,
            state_hbm_budget_mb=0.0)
        cold_cfg = cfg.replace(features=cold_fcfg)
        sampler = ZipfKeySampler(n_cold, skew)
        reg = MetricsRegistry()
        eng = ScoringEngine(cold_cfg, kind="logreg",
                            params=init_logreg(15), scaler=scaler,
                            metrics=reg)
        eng.run(_ZipfSource(2, rows, sampler, day_every=1, seed=7))
        stats = eng.run(_ZipfSource(n_batches, rows, sampler,
                                    day_every=max(n_batches // 6, 1)))
        dense = reg.get("rtfds_feature_tier_rows_total", tier="dense")
        cms = reg.get("rtfds_feature_tier_rows_total", tier="cms")
        d = dense.value if dense is not None else 0.0
        c = cms.value if cms is not None else 0.0
        recompiles = reg.get("rtfds_xla_recompiles_total")

        def _mval(name):
            m = reg.get(name)
            return m.value if m is not None else 0.0

        rate = stats["rows_per_s"]
        out["universes"][str(n_cold)] = {
            "rows_per_s": round(rate, 1),
            "vs_64k": round(rate / base_rate, 3) if base_rate else None,
            "dense_hit_rate": round(d / (d + c), 4) if d + c else 1.0,
            "mid_stream_recompiles": (recompiles.value
                                      if recompiles is not None else 0.0),
            "exactness_degraded_keys": int(
                stats.get("exactness_degraded_keys", 0)),
            "cold": {
                "keys": int(_mval("rtfds_feature_cold_keys")),
                "bytes": int(_mval("rtfds_feature_cold_bytes")),
                "demotions": int(
                    _mval("rtfds_feature_cold_demotions_total")),
                "promotions": int(
                    _mval("rtfds_feature_cold_promotions_total")),
            },
        }
        out["flat_100m_within_15pct"] = (
            bool(rate >= 0.85 * base_rate) if base_rate else None)
    # delta-checkpoint cost of the bounded state vs the dense-at-10M
    # control (static accounting: direct mode needs capacity >= universe)
    dense_cap = 1 << 24  # next pow2 >= 10M
    dense_fcfg = _dc.replace(fcfg, key_mode="direct",
                             customer_capacity=dense_cap,
                             compact_every=0, state_hbm_budget_mb=0.0)
    out["dense_control_state_bytes"] = state_bytes(dense_fcfg)
    with tempfile.TemporaryDirectory() as td:
        ck = Checkpointer(td, full_every=4)
        ck.save(last_engine.state)  # full
        sizes0 = {f: os.path.getsize(os.path.join(td, f))
                  for f in os.listdir(td) if f.endswith(".npz")}
        sampler = ZipfKeySampler(10_000_000, skew)
        last_engine.run(_ZipfSource(2, rows, sampler, day_every=1,
                                    seed=11))
        ck.save(last_engine.state)  # delta vs the full above
        sizes1 = {f: os.path.getsize(os.path.join(td, f))
                  for f in os.listdir(td) if f.endswith(".npz")}
        delta_files = sorted(set(sizes1) - set(sizes0))
        t0 = time.perf_counter()
        ck.restore(last_engine.state)
        restore_s = time.perf_counter() - t0
        out["checkpoint"] = {
            "full_bytes": max(sizes0.values()),
            "delta_bytes": (sizes1[delta_files[0]] if delta_files
                            else None),
            "restore_s": round(restore_s, 3),
        }
    return out


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--model", default="forest",
                    choices=["forest", "logreg"])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--state-hbm-budget-mb", type=float, default=0.0,
                    help="HBM budget for the detail.state_scale curve's "
                         "tiered feature state, validated at engine "
                         "build (0 = the block's 256 MB default)")
    return ap.parse_args(argv)


def _emit_final(result: dict) -> None:
    """Print the full result JSON, then a compact headline line LAST.

    A reader that keeps only a tail window of stdout loses the leading
    ``"metric"/"value"`` keys of the (long) full line. The compact line —
    same schema, ``detail`` reduced to the device it ran on and the
    sections that failed — is printed last so the tail window always
    contains one complete, parseable result line.
    """
    print(json.dumps(result), flush=True)
    detail = result.get("detail", {}) or {}
    compact = {
        "metric": result.get("metric", "score_txns_per_sec"),
        "value": result.get("value", 0.0),
        "unit": result.get("unit", "txns/s"),
        "vs_baseline": result.get("vs_baseline", 0.0),
        "detail": {
            "platform": detail.get("platform"),
            "device_kind": detail.get("device_kind"),
            "device_count": detail.get("device_count"),
            "section_errors": len(detail.get("section_errors") or ()),
            "full_detail": "see the full JSON line above",
        },
    }
    print(json.dumps(compact), flush=True)


def main(argv=None) -> None:
    """Parse arguments and measure — in THIS process. Exit 0 only when a
    TPU (or the explicit CPU smoke run) was measured and no section
    raised."""
    args = _parse_args(argv)
    del _SECTION_ERRORS[:]
    _emit_final(_measure(args))
    if _SECTION_ERRORS:
        _progress(f"{len(_SECTION_ERRORS)} section(s) failed: "
                  + " | ".join(_SECTION_ERRORS))
        sys.exit(1)


if __name__ == "__main__":
    main()
