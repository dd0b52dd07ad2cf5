"""Fast CPU perf gate (`make perf-smoke`, also tier-1).

Asserts the hot-loop invariants the perf tentpoles establish:

1. With a ``ParquetSink`` under full polls, the LOOP THREAD's cost of a
   write (registry ``rtfds_phase_seconds{phase=sink_wait}``) is
   enqueue-bounded (≤ 100 µs on CPU CI) while ``phase=sink_write`` still
   reads the write, timed on the loop's writer thread, and the rows
   written are identical to the joined (write, then poll) order.
2. With precompile on, a stream that visits EVERY bucket size records
   ``rtfds_xla_recompiles_total == 0`` — and the same stream WITHOUT
   precompile pays a detectable mid-stream compile, so the zero is the
   optimization working, not the detector sleeping.
3. Host data plane (input side): 4-worker slab decode is bit-identical
   to serial decode and ≥ 1.5× faster (ratio gated on the box actually
   having usable CPU parallelism — the correctness half always runs);
   with a ``PrefetchSource`` the loop thread's ``source_poll`` phase p50
   collapses to dequeue scale (≤ 1 ms) while rows stay identical.
4. Device plane (round 9): a forest engine with ``z_mode="int8"``
   forced under ``--precompile`` serves decisions bit-identical to the
   f32 control across every bucket size AND pays zero mid-stream
   recompiles — asserted from ``rtfds_xla_recompiles_total``, not
   prints.
"""

import dataclasses
import time

import numpy as np

from real_time_fraud_detection_system_tpu.config import (
    Config,
    DataConfig,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.io.sink import ParquetSink
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime import (
    ReplaySource,
    ScoringEngine,
)
from real_time_fraud_detection_system_tpu.utils.metrics import MetricsRegistry

EPOCH0 = 1_743_465_600


def _cfg(buckets=(256,), max_rows=256):
    return Config(
        data=DataConfig(n_customers=50, n_terminals=100, n_days=30),
        features=FeatureConfig(customer_capacity=128, terminal_capacity=256,
                               cms_width=1 << 10),
        runtime=RuntimeConfig(batch_buckets=buckets, max_batch_rows=max_rows),
    )


def _engine(cfg, reg=None):
    return ScoringEngine(
        cfg, kind="logreg", params=init_logreg(15),
        scaler=Scaler(mean=np.zeros(15, np.float32),
                      scale=np.ones(15, np.float32)),
        metrics=reg if reg is not None else MetricsRegistry(),
    )


def test_async_sink_write_phase_is_enqueue_bounded(small_dataset, tmp_path):
    """Under full polls (a backlog) the LOOP THREAD's cost of a write —
    the ``sink_wait`` phase — is enqueue-bounded, while ``sink_write``
    still reads the write itself, timed on the writer thread; the rows
    written are those of the joined (write, then poll) order."""
    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 7680))  # 30 batches of 256

    # joined reference: 256-row polls never fill a 512-row bucket
    joined_sink = ParquetSink(str(tmp_path / "joined"))
    joined_reg = MetricsRegistry()
    _engine(_cfg(buckets=(512,), max_rows=512), joined_reg).run(
        ReplaySource(part, EPOCH0, batch_rows=256), sink=joined_sink)
    assert joined_reg.get("rtfds_sink_overlapped_batches_total").value == 0

    # overlapped run under its own registry so the histograms are clean;
    # the queue holds the whole run, so nothing measured is backpressure
    cfg = _cfg()
    cfg = dataclasses.replace(cfg, runtime=dataclasses.replace(
        cfg.runtime, sink_queue_batches=64))
    reg = MetricsRegistry()
    sink = ParquetSink(str(tmp_path / "overlapped"))
    stats = _engine(cfg, reg).run(
        ReplaySource(part, EPOCH0, batch_rows=256), sink=sink)
    assert reg.get("rtfds_sink_overlapped_batches_total").value >= 28

    wait = reg.get("rtfds_phase_seconds", phase="sink_wait")
    write = reg.get("rtfds_phase_seconds", phase="sink_write")
    assert wait is not None and wait.count == stats["batches"]
    assert write is not None and write.count == stats["batches"]
    assert wait.percentile(50) <= 100e-6, (
        f"loop-thread sink_wait p50 {wait.percentile(50) * 1e6:.1f} µs "
        "is not enqueue-bounded")
    assert write.percentile(50) > 3 * wait.percentile(50), (
        "sink_write reads an enqueue, not the parquet write")
    assert stats["sink_write_p50_ms"] > 3 * stats["sink_wait_p50_ms"]
    # identical durable output
    a = sink.read_all()
    s = joined_sink.read_all()
    assert len(a["tx_id"]) == len(s["tx_id"]) == 7680
    assert np.array_equal(a["tx_id"], s["tx_id"])  # part order is loop order


class _SizedSource:
    """Yields scripted batch sizes from a transactions table — drives a
    stream through every jit bucket on demand."""

    def __init__(self, txs, sizes, epoch0=EPOCH0):
        self.inner = ReplaySource(txs, epoch0,
                                  batch_rows=max(sizes))
        self.sizes = list(sizes)
        self._i = 0
        self._buf = None

    def poll_batch(self):
        if self._i >= len(self.sizes):
            return None
        want = self.sizes[self._i]
        self._i += 1
        cols = self.inner.poll_batch()
        if cols is None:
            return None
        return {k: v[:want] for k, v in cols.items()}

    @property
    def offsets(self):
        return self.inner.offsets

    def seek(self, offsets):
        self.inner.seek(offsets)


def _recompiles(reg):
    c = reg.get("rtfds_xla_recompiles_total")
    return 0.0 if c is None else c.value


def test_precompile_zero_recompiles_across_all_buckets(small_dataset):
    """Visit the large bucket only AFTER the detector's warmup window:
    without precompile that first touch is a counted mid-stream compile;
    with precompile it dispatches a ready executable and the counter
    stays 0 by construction."""
    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 4096))
    cfg = _cfg(buckets=(64, 256), max_rows=256)
    # five 60-row batches (bucket 64) burn the warmup, then 200-row
    # batches land in bucket 256 for the first time
    sizes = [60] * 5 + [200, 60, 200]

    reg_off = MetricsRegistry()
    eng_off = _engine(cfg, reg_off)
    s_off = eng_off.run(_SizedSource(part, sizes))
    assert s_off["batches"] == len(sizes)
    assert _recompiles(reg_off) > 0, (
        "control run saw no mid-stream compile; the precompile "
        "assertion below would be vacuous")

    reg_on = MetricsRegistry()
    cfg_on = cfg.replace(runtime=dataclasses.replace(
        cfg.runtime, precompile=True))
    eng_on = _engine(cfg_on, reg_on)
    s_on = eng_on.run(_SizedSource(part, sizes))
    assert s_on["batches"] == len(sizes)
    assert len(eng_on._aot) == 2  # one executable per bucket, still live
    assert _recompiles(reg_on) == 0
    assert reg_on.get("rtfds_aot_fallbacks_total").value == 0
    assert reg_on.get("rtfds_precompiled_steps_total").value == 2


def _envelope_corpus(n):
    from real_time_fraud_detection_system_tpu.core.envelope import (
        encode_transaction_envelopes,
    )

    rng = np.random.default_rng(11)
    return encode_transaction_envelopes(
        np.arange(n, dtype=np.int64),
        rng.integers(1_700_000_000, 1_800_000_000, n) * 1_000_000,
        rng.integers(0, 5000, n),
        rng.integers(0, 10000, n),
        rng.integers(100, 50000, n),
    )


def _raw_scan_parallelism() -> float:
    """Calibrate: two threads of the GIL-released C scan over disjoint
    halves vs one serial scan of the same corpus → the speedup this box
    can physically deliver. Sandboxed CI boxes sometimes report nproc=2
    while delivering ~1 core of throughput (measured here: 1.0-1.3×) —
    a fixed speedup gate there would only measure the scheduler. The
    bit-identical half of the decode gate runs regardless."""
    import threading

    from real_time_fraud_detection_system_tpu.core import native

    msgs = _envelope_corpus(20000)
    n = len(msgs)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.fromiter((len(m) for m in msgs), np.int64, count=n),
              out=offsets[1:])
    buf = b"".join(msgs)

    def outs():
        return ([np.zeros(n, np.int64) for _ in range(5)]
                + [np.zeros(n, np.int8), np.zeros(n, np.uint8)])

    o = outs()
    t0 = time.perf_counter()
    native.decode_envelopes_slab(buf, offsets, 0, n, *o)
    serial = time.perf_counter() - t0
    o1, o2 = outs(), outs()
    th = [threading.Thread(target=native.decode_envelopes_slab,
                           args=(buf, offsets, 0, n // 2, *o1)),
          threading.Thread(target=native.decode_envelopes_slab,
                           args=(buf, offsets, n // 2, n, *o2))]
    t0 = time.perf_counter()
    for t in th:
        t.start()
    for t in th:
        t.join()
    par = time.perf_counter() - t0
    return serial / max(par, 1e-9)


def test_parallel_decode_bit_identical_and_scales():
    """Host-plane gate, input side: multi-worker slab decode returns the
    EXACT columns of serial decode (always asserted), runs one slab per
    worker (asserted from rtfds_decode_slab_seconds), and on a box with
    real CPU parallelism is ≥ 1.5× faster at 4 workers."""
    from real_time_fraud_detection_system_tpu.core import native
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        get_registry,
    )

    if not native.native_available():
        import pytest

        pytest.skip("native decoder unavailable")
    msgs = _envelope_corpus(40000)

    hist = get_registry().histogram("rtfds_decode_slab_seconds")
    c0 = hist.count
    ref, ref_inv = native.decode_transaction_envelopes_native(
        msgs, workers=1)
    assert hist.count == c0 + 1  # serial: one slab
    cols, inv = native.decode_transaction_envelopes_native(
        msgs, workers=4)
    assert hist.count == c0 + 5  # parallel: one slab per worker
    assert np.array_equal(ref_inv, inv)
    for k in ref:
        assert np.array_equal(ref[k], cols[k]), k

    raw = _raw_scan_parallelism()
    if raw < 1.8:
        import pytest

        pytest.skip(f"box delivers only {raw:.2f}x on the raw 2-thread "
                    "scan (needs ~2 real cores to attest the 1.5x "
                    "gate); bit-identity asserted, speedup gate skipped")

    # INTERLEAVED serial/parallel timing, best-of-reps: a transient CI
    # load spike then degrades both arms of the same rep instead of
    # landing wholly on one side of the ratio (the PR-8-era flake:
    # back-to-back timing blocks measured the scheduler, not us).
    t1 = t4 = None
    for _ in range(5):
        t0 = time.perf_counter()
        native.decode_transaction_envelopes_native(msgs, workers=1)
        t1 = min(t1, time.perf_counter() - t0) if t1 else \
            time.perf_counter() - t0
        t0 = time.perf_counter()
        native.decode_transaction_envelopes_native(msgs, workers=4)
        t4 = min(t4, time.perf_counter() - t0) if t4 else \
            time.perf_counter() - t0
    if t1 / t4 < 1.5:
        # Re-calibrate before failing (the PR-11 pattern from
        # test_instrumentation_overhead_bounded, applied to the raw-scan
        # guard): if concurrent CI load arrived BETWEEN the calibration
        # above and the measurement, the raw scan has degraded too — the
        # box changed, not the decoder. Only a box that still attests
        # 2-thread parallelism while the 4-worker decode can't reach
        # 1.5x is a real regression.
        import pytest

        raw_after = _raw_scan_parallelism()
        if raw_after < 1.8:
            pytest.skip(
                f"load arrived mid-test: raw scan fell {raw:.2f}x -> "
                f"{raw_after:.2f}x; bit-identity asserted, speedup gate "
                "skipped")
    assert t1 / t4 >= 1.5, (
        f"4-worker decode {t4 * 1e3:.1f} ms vs serial {t1 * 1e3:.1f} ms "
        f"({t1 / t4:.2f}x) — below the 1.5x host-plane gate; raw scan "
        f"still attests {raw:.2f}x, so this is the decoder, not the box")


def test_prefetch_collapses_loop_thread_source_poll(small_dataset,
                                                    tmp_path):
    """Host-plane gate, loop side: with a PrefetchSource the loop
    thread's source_poll phase p50 drops to dequeue scale (≤ 1 ms on
    CPU smoke) while the synchronous twin pays the full per-poll decode
    cost — and the scored rows are identical."""
    from real_time_fraud_detection_system_tpu.io import MemorySink
    from real_time_fraud_detection_system_tpu.runtime import (
        PrefetchSource,
    )

    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 5120))  # 20 batches of 256
    cfg = _cfg()

    class _CostlyPoll:
        """ReplaySource with a fixed per-poll host cost (the stand-in
        for envelope decode)."""

        def __init__(self, cost_s=0.004):
            self.inner = ReplaySource(part, EPOCH0, batch_rows=256)
            self.cost_s = cost_s

        def poll_batch(self):
            cols = self.inner.poll_batch()
            if cols is not None:
                time.sleep(self.cost_s)
            return cols

        @property
        def offsets(self):
            return self.inner.offsets

        def seek(self, offsets):
            self.inner.seek(offsets)

    class _SlowSink(MemorySink):
        """Paces the loop so the producer can stay ahead (a real loop
        is paced by the device step + sink; CPU smoke steps are ~ms)."""

        def append(self, res):
            time.sleep(0.008)
            super().append(res)

    def run(prefetch):
        reg = MetricsRegistry()
        src = _CostlyPoll()
        if prefetch:
            src = PrefetchSource(src, max_batches=4, registry=reg)
        sink = _SlowSink()
        _engine(cfg, reg).run(src, sink=sink)
        if prefetch:
            src.close()
        hist = reg.get("rtfds_phase_seconds", phase="source_poll")
        return hist, sink.concat()

    h_sync, out_sync = run(False)
    h_pre, out_pre = run(True)
    assert np.array_equal(out_sync["tx_id"], out_pre["tx_id"])
    np.testing.assert_allclose(out_sync["prediction"],
                               out_pre["prediction"], atol=1e-7)
    assert h_sync.percentile(50) >= 3e-3, (
        "control run did not pay the per-poll cost; the prefetch "
        "assertion below would be vacuous")
    assert h_pre.percentile(50) <= 1e-3, (
        f"loop-thread source_poll p50 "
        f"{h_pre.percentile(50) * 1e3:.2f} ms with prefetch on is not "
        "dequeue-scale")


def test_device_plane_int8_decision_identical_zero_recompiles(
        small_dataset):
    """Device-plane gate: the promoted int8 serving path (z_mode=int8 +
    precompile) streams through EVERY bucket — visiting the second
    bucket only after the recompile detector's warmup — with

    - probabilities BIT-identical to the f32 jit control (the
      gemm_leaf_sum exactness contract, at engine level), and
    - ``rtfds_xla_recompiles_total == 0`` (the AOT executables cover the
      active z_mode), with zero AOT fallbacks.
    """
    from real_time_fraud_detection_system_tpu.models.forest import (
        fit_forest,
    )

    rng = np.random.default_rng(31)
    x = rng.normal(size=(400, 15)).astype(np.float32)
    y = (x[:, 0] > 0.2).astype(np.int32)
    ens = fit_forest(x, y, n_trees=5, max_depth=4)
    scaler = Scaler(mean=np.zeros(15, np.float32),
                    scale=np.ones(15, np.float32))

    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 4096))
    sizes = [60] * 5 + [200, 60, 200]

    def run(z_mode, precompile):
        reg = MetricsRegistry()
        cfg = _cfg(buckets=(64, 256), max_rows=256)
        cfg = cfg.replace(runtime=dataclasses.replace(
            cfg.runtime, z_mode=z_mode, precompile=precompile))
        eng = ScoringEngine(cfg, kind="forest", params=ens, scaler=scaler,
                            metrics=reg)
        from real_time_fraud_detection_system_tpu.io import MemorySink

        sink = MemorySink()
        stats = eng.run(_SizedSource(part, sizes), sink=sink)
        assert stats["batches"] == len(sizes)
        assert stats["z_mode"] == z_mode
        return reg, sink.concat()

    reg_ctl, out_f32 = run("f32", precompile=False)
    reg_i8, out_i8 = run("int8", precompile=True)
    np.testing.assert_array_equal(out_i8["tx_id"], out_f32["tx_id"])
    # bit identity, not a tolerance: int8 z arithmetic is exact
    np.testing.assert_array_equal(out_i8["prediction"],
                                  out_f32["prediction"])
    assert _recompiles(reg_i8) == 0
    assert reg_i8.get("rtfds_aot_fallbacks_total").value == 0
    assert reg_i8.get("rtfds_precompiled_steps_total").value == 2
    assert reg_i8.get("rtfds_z_mode", mode="int8").value == 1.0


def test_precompile_preserves_scores(small_dataset):
    """AOT dispatch is the same program: predictions are bit-identical
    to plain jit dispatch over the same stream."""
    from real_time_fraud_detection_system_tpu.io import MemorySink

    _, _, _, txs = small_dataset
    part = txs.slice(slice(0, 1024))
    cfg = _cfg(buckets=(64, 256), max_rows=256)

    def run(precompile):
        rcfg = dataclasses.replace(
            cfg.runtime, precompile=precompile)
        eng = _engine(cfg.replace(runtime=rcfg))
        sink = MemorySink()
        eng.run(_SizedSource(part, [60, 200, 60, 200, 60]), sink=sink)
        return sink.concat()

    a, b = run(True), run(False)
    np.testing.assert_array_equal(a["tx_id"], b["tx_id"])
    np.testing.assert_array_equal(a["prediction"], b["prediction"])
