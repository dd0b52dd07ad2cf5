"""Micro-batch scoring engine — the Spark Structured Streaming replacement.

The reference's hot loop (``fraud_detection.py:204-211`` + SURVEY §3.1) is:
Iceberg snapshot scan → SQL join → Arrow → Python UDF → sklearn → Iceberg
append, crossing four process boundaries per batch. Here the loop is: source
poll → host dedup/pad → ``device_put`` → ONE jitted ``step`` (feature state
scatter/gather + scale + classify [+ online SGD]) → sink append. The
feature state and weights never leave HBM; the jit cache is keyed by bucket
size only.

``--scorer {cpu,tpu}`` (reference north star): ``tpu`` runs the jitted
classifier; ``cpu`` runs the sklearn oracle on the same features, for parity
and baseline measurement.
"""

from __future__ import annotations

import copy
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from real_time_fraud_detection_system_tpu.config import Config
from real_time_fraud_detection_system_tpu.core.batch import (
    US_PER_DAY,
    TxBatch,
    bucket_size,
    device_keys,
    host_keys,
    join_key,
    make_batch,
    pack_batch,
    packed_rows,
    unpack_batch,
    wide_id_rows,
)
from real_time_fraud_detection_system_tpu.features.online import (
    FeatureState,
    init_feature_state,
    state_bytes,
)
from real_time_fraud_detection_system_tpu.features.step import make_step
from real_time_fraud_detection_system_tpu.features.spec import N_FEATURES
from real_time_fraud_detection_system_tpu.models.forest import (
    TreeEnsemble,
    for_device,
    resolve_z_mode,
)
from real_time_fraud_detection_system_tpu.models.forest import (
    predict_proba as forest_predict_proba,
)
from real_time_fraud_detection_system_tpu.models.logreg import (
    logreg_loss,
    logreg_predict_proba,
)
from real_time_fraud_detection_system_tpu.models.mlp import (
    mlp_loss,
    mlp_predict_proba,
)
from real_time_fraud_detection_system_tpu.models.scaler import Scaler, transform
from real_time_fraud_detection_system_tpu.core import native
from real_time_fraud_detection_system_tpu.io.sink import AsyncSink
from real_time_fraud_detection_system_tpu.ops.dedup import (
    latest_wins_mask_host,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (
    active_recorder,
    get_registry,
)
from real_time_fraud_detection_system_tpu.utils.timing import LatencyTracker
from real_time_fraud_detection_system_tpu.utils.trace import (
    get_tracer,
    step_scope,
)
from real_time_fraud_detection_system_tpu.utils.tracing import (
    keep_scopes_in_cache_key,
)
from real_time_fraud_detection_system_tpu.utils.xla_telemetry import (
    DeviceMemoryTelemetry,
    RecompileDetector,
    install_compile_telemetry,
    step_signature,
)

# The per-batch loop-time decomposition every layer reports under
# (rtfds_phase_seconds{phase=...} and the flight record's "phases" dict):
# source poll → host prep (dedup+pack) → dispatch (H2D + jit call) →
# result wait (device compute minus overlap + unpack) → sink wait (the
# loop thread blocked on its writer: the join before a poll, a full
# queue, a drain). Those five are serial on the loop thread and sum to
# its wall time; ``sink_write`` is the write itself, timed on the writer
# thread where it runs, beside them.
PHASES = ("source_poll", "host_prep", "dispatch", "result_wait",
          "sink_wait", "sink_write")
# The cold tier's own phases of the same histogram, registered only when
# the tier is armed: cold_detect (inside host_prep), state_promote (row
# read, payload build and promote dispatch, between host_prep and
# dispatch), cold_append (one pass's payload fetch and landing, inside
# state_compact), cold_expire (the store forgetting, after a pass, the
# keys no window can see any more, inside state_compact).
COLD_PHASES = ("cold_detect", "state_promote", "cold_append", "cold_expire")
# What the loop thread's pass is made of besides (PR 37; README, Tracing:
# the span tree), each a span and a series of the same histogram:
# loop_pass (one pass of run()'s loop), sink_join and sink_enqueue (the
# two waits sink_wait adds up, with a checkpoint's drain), device_wait
# and fetch (the two halves of result_wait), hooks (feedback, model
# reload, learner), checkpoint, pace (the trigger's sleep; a pass whose
# poll came back empty), and on the writer thread writer_queue (enqueue
# return to write start). compact_fetch joins them where compaction is
# armed.
LOOP_PHASES = ("loop_pass", "sink_join", "sink_enqueue", "device_wait",
               "fetch", "hooks", "checkpoint", "pace", "writer_queue")


class _Phase:
    """One measurement of one phase: the clock is read twice, and those
    two readings are the phase's histogram observation, its entry in the
    run's percentile tracker and, when tracing is on, its span."""

    __slots__ = ("_eng", "name", "span", "_hist", "t0", "t1")

    def __init__(self, eng: "ScoringEngine", name: str, span, hist):
        self._eng = eng
        self.name = name
        self.span = span
        self._hist = hist

    def __enter__(self) -> "_Phase":
        self.span.open()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = t1 = time.perf_counter()
        self.span.close(self.t0, t1)
        if self._hist is not None:
            self._hist.observe(t1 - self.t0)
        tracker = self._eng._trackers.get(self.name)
        if tracker is not None:
            tracker.record(t1 - self.t0)
        return False

    def fold(self, name: str) -> None:
        """Count and record this phase as ``name``, one span with the
        ``name`` before it where neither had a child."""
        self.name = name
        self._hist = self._eng._m_phase.get(name)
        self.span.fold(name)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class ColdPromoteError(RuntimeError):
    """A returning key's exact rows could not be put back into the hot
    tier before the step that scored its row."""


PROMOTE_LANES_MAX = 16384

# ``precompile()``'s compiles run beside its lowering and each other: an
# inventory is 5 programs (10-11 with a directory's compaction and a cold
# tier's promotes), and the hosts have 13 cores a chip.
_COMPILE_THREADS = 4


def blank_lanes(shape: tuple, rows: tuple, key_bits: int = 32) -> list:
    """An all-padding promote payload of lane shape ``shape`` (``(W,)``,
    or ``(n_dev, W)`` on the mesh): ``EMPTY_KEY`` keys and empty rows as
    wide as ``rows``' (bd, cnt, amt, frd), for the caller to fill. At
    ``key_bits=64`` the key lanes are ``[2, W]``, both words
    ``EMPTY_KEY``: the reserved pattern."""
    key_shape = ((2,) if key_bits == 64 else ()) + tuple(shape)
    return [np.full(key_shape, 0xFFFFFFFF, np.uint32)] + [
        np.full(shape + r.shape[1:], fill, r.dtype)
        for r, fill in zip(rows, (-1, 0.0, 0.0, 0.0))]


def one_table_payload(table: str, lanes) -> dict:
    """The promote programs take one table's lanes a dispatch."""
    return {t: (tuple(lanes) if t == table else None)
            for t in ("customer", "terminal")}


def promote_widths(max_rows: int) -> tuple:
    """The lane ladder of the ``("promote", table, width)`` programs:
    the largest batch's rows (no batch holds more distinct keys than
    rows) or ``PROMOTE_LANES_MAX``, whichever is less, and every quarter
    of it down to 256 lanes — what ``batch_buckets`` is to the step. A
    promote costs by its lanes (the directory's admit), so a few thousand
    returning keys must not pay for a whole batch's; and a batch that
    brings back more keys than the widest program holds dispatches
    several (a 65,536-lane program is a quarter of a minute of compile a
    table that a run may never use)."""
    top = max(1, min(int(max_rows), PROMOTE_LANES_MAX))
    widths = [top]
    while widths[-1] // 4 >= 256:
        widths.append(widths[-1] // 4)
    return tuple(sorted(widths))


class PollAhead:
    """The loop's join rule: may the next poll go ahead of the sink write
    before it, or does the loop first wait for its writer to go idle?

    A poll freezes a batch. Polling ahead of the write is free only when
    the rows it freezes were waiting anyway — the source holds a backlog —
    and then the write overlaps the poll, the prep, the dispatch and the
    chip. Without a backlog an early poll only freezes the next batch a
    write's time sooner than the chip can take it, and every row in it
    waits that much longer: there the loop joins first (write, then poll:
    the inline order).

    The sign of a backlog is a launched batch that filled the largest
    bucket (or left a carry). One such batch is believed at first
    (``need`` = 1). But the sign has an echo: the join that ends a spell of
    polling ahead makes one interval between polls a write's time longer,
    that interval's rows can fill the bucket on their own, and believing
    it starts the next spell — full, short, full, short, at 0.7 of the
    chip-paced rate and above (PERF.md §6, PR 31: ``forest.steady`` read
    +12 % at p50 that way). A spell whose FIRST early poll already comes
    back short was such an echo, and raises ``need`` by one: it takes that
    many full batches in a row from then on. A real backlog fills every
    poll and pays ``need`` - 1 joined writes once; under saturation
    ``need`` stays 1.
    """

    def __init__(self, cap: int):
        self.cap = int(cap)
        self.need = 1
        self.streak = 0  # full batches launched in a row
        self.spell = 0  # batches launched from polls made ahead, in a row
        self.ahead = False  # read before each poll; False: join first

    def launched(self, rows: int, carry: bool) -> None:
        """A batch of ``rows`` polled rows was launched (``carry``: a
        further poll is already waiting for the next one)."""
        self.spell = self.spell + 1 if self.ahead else 0
        if carry or rows >= self.cap:
            self.streak += 1
        else:
            if self.spell == 1:
                self.need += 1
            self.streak = 0
        self.ahead = self.streak >= self.need


# Budget for ONE double-buffered Pallas tree block — the ensemble-
# dependent VMEM term (ops/pallas_forest.admit_block). It bounds the
# tables only; that the whole kernel (row tiles, intermediates) fits the
# chip's ~16MB scoped VMEM is what tests/test_tpu_compile.py asks the
# compiler. Decided at TRACE time from the live params' static shapes, so
# a checkpoint restore that swaps in a deeper ensemble retraces into the
# XLA composition instead of a VMEM-overflowing kernel.
_PALLAS_BLOCK_BUDGET = 4 * 2 ** 20


def device_params_for(kind: str, params):
    """Engine-ready params: tree-ensemble kinds convert to the fast GEMM
    form once (the step then serves them unchanged). Used at engine build
    AND by hot model reloads, which swap ``state.params`` in place."""
    if kind in ("tree", "forest") and isinstance(params, TreeEnsemble):
        return for_device(params, N_FEATURES)
    if kind == "gbt":
        from real_time_fraud_detection_system_tpu.models.gbt import (
            gbt_for_device,
        )

        return gbt_for_device(params, N_FEATURES)
    return params


def predict_fn_for(kind: str, z_mode: Optional[str] = None) -> Callable:
    """Device predict for ``kind``. ``z_mode`` (a RESOLVED mode —
    f32/bf16/int8, see ``models/forest.resolve_z_mode``) selects the
    tree-ensemble z-contraction arithmetic; non-ensemble kinds have no
    contraction and ignore it."""
    if kind == "logreg":
        return logreg_predict_proba
    if kind == "mlp":
        return mlp_predict_proba
    if kind == "gbt":
        from real_time_fraud_detection_system_tpu.models.gbt import (
            gbt_predict_proba,
        )

        if z_mode is None:
            return gbt_predict_proba
        return lambda p, x: gbt_predict_proba(p, x, z_mode)
    if kind in ("tree", "forest"):
        if z_mode is None:
            return forest_predict_proba
        return lambda p, x: forest_predict_proba(p, x, z_mode)
    if kind == "autoencoder":
        from real_time_fraud_detection_system_tpu.models.autoencoder import (
            autoencoder_predict_proba,
        )

        return autoencoder_predict_proba
    raise ValueError(f"unknown model kind {kind}")


def loss_fn_for(kind: str) -> Optional[Callable]:
    if kind == "logreg":
        return logreg_loss
    if kind == "mlp":
        return mlp_loss
    if kind == "autoencoder":
        from real_time_fraud_detection_system_tpu.models.autoencoder import (
            autoencoder_loss,
        )

        return autoencoder_loss
    return None  # tree ensembles have no gradient path


@dataclass
class EngineState:
    """Host-visible engine state (device pytrees + offsets + counters)."""

    feature_state: FeatureState
    params: object
    scaler: Scaler
    offsets: List[int] = field(default_factory=list)
    batches_done: int = 0
    rows_done: int = 0
    # Device count whose owner layout feature_state carries (window/
    # history layouts are shape-identical permutations, so the width must
    # travel WITH the state). Checkpoints record it; restore compares it
    # to the serving engine's own width and auto-reshards on mismatch.
    layout_devices: int = 1
    # Registry version the params descend from (continuous learning).
    # Travels WITH the state so a checkpoint restore tells the learning
    # loop exactly which champion the restored params are: a crash
    # between a promotion/reload swap and the next save restores
    # pre-swap weights, and the stamp mismatch is how attach() knows to
    # re-apply the registry champion instead of serving them stale.
    model_version: Optional[int] = None
    # Multi-host topology the writer served under: the fleet's process
    # count and THIS state's process id (its residue block). Like
    # layout_devices, it must travel with the state — a per-process
    # checkpoint holds only its block's keys, so restoring it under a
    # different topology would silently drop every other block.
    # Checkpoints record both; restore refuses a mismatch (except the
    # sanctioned 1→P adoption, which re-slices a global checkpoint).
    process_count: int = 1
    process_id: int = 0


@dataclass(frozen=True)
class DispatchSignature:
    """One (shape × static-facts) combination the engine can dispatch.

    The **dispatch signature inventory** (:meth:`ScoringEngine.
    dispatch_inventory`) enumerates every signature the runtime can ever
    hand to the device: ``key`` is simultaneously the AOT-cache key
    ``precompile()`` compiles under AND the key ``_dispatch_step``
    looks up at serve time, so the coverage proof and the warmup path
    cannot drift — there is one enumeration, and both consume it.
    ``tools/rtfdsverify`` abstract-interprets each signature's traced
    program (CPU-only, no weights) to prove the device-plane contracts
    (AOT coverage, z-mode exactness, donation safety, Pallas admission)
    before a stream ever starts."""

    key: tuple           # AOT cache key == runtime dispatch key
    variant: str         # "step" | "sharded-local" | "sharded-routed"
    kind: str            # model kind the step closes over
    z_mode: Optional[str]  # resolved z mode (tree-ensemble kinds; else None)
    bucket: int          # padded batch rows of this signature
    donate: tuple        # donated argnums of the jitted step
    selective: bool      # selective-emission packing compiled in
    emit_dtype: str      # emitted feature matrix dtype ("float32"/"bfloat16")
    use_pallas: bool     # a fused Pallas kernel is reachable at trace time

    def describe(self) -> str:
        """Stable human/fingerprint label (rtfdsverify finding context)."""
        return (f"{self.variant}[kind={self.kind} z={self.z_mode} "
                f"bucket={self.bucket} selective={self.selective} "
                f"emit={self.emit_dtype} pallas={self.use_pallas} "
                f"donate={','.join(map(str, self.donate)) or '-'}]")


@dataclass
class BatchResult:
    tx_id: np.ndarray
    tx_datetime_us: np.ndarray
    customer_id: np.ndarray
    terminal_id: np.ndarray
    amount_cents: np.ndarray
    features: np.ndarray  # [n, 15]
    probs: np.ndarray  # [n]
    latency_s: float
    # Monotone engine batch counter (survives checkpoint restore): a
    # replayed batch carries the SAME index, so idempotent sinks can
    # overwrite instead of duplicating (exactly-once sink output — the
    # role of Spark's sink commit protocol).
    batch_index: int = -1


def empty_batch_result(batch_index: int) -> BatchResult:
    """A zero-row result claiming ``batch_index`` — what a batch whose
    every row was quarantined to the dead-letter queue leaves behind, so
    the sink's ``batch_index`` lineage stays gap-free."""
    return BatchResult(
        tx_id=np.empty(0, np.int64),
        tx_datetime_us=np.empty(0, np.int64),
        customer_id=np.empty(0, np.int64),
        terminal_id=np.empty(0, np.int64),
        amount_cents=np.empty(0, np.int64),
        features=np.zeros((0, N_FEATURES), np.float32),
        probs=np.empty(0, np.float32),
        latency_s=0.0,
        batch_index=int(batch_index),
    )


def validate_ingest_rows(cols: dict, detail_fn=None) -> None:
    """Strict-ingest boundary check: values that decoded structurally
    but are IMPOSSIBLE (today: negative amounts — the generator, the
    OLTP schema, and the decimal codec all make them unrepresentable on
    the legitimate path) mean a corrupt or malicious envelope. Garbage
    must never scatter into the feature state, so the batch crashes
    loudly with :class:`~.faults.PoisonRowError`; under
    :func:`~.faults.run_with_recovery` + a dead-letter sink the crash
    loop is diagnosed and exactly these rows are quarantined while the
    stream continues. One vectorized compare per batch (~free).
    ``detail_fn(bad_mask) -> str`` lets callers append attribution (the
    sharded engine names shard placements) without re-running the
    predicate — it is invoked only on failure."""
    amounts = np.asarray(cols["tx_amount_cents"])
    if len(amounts) == 0:
        return
    bad = amounts < 0
    if bad.any():
        from real_time_fraud_detection_system_tpu.runtime.faults import (
            PoisonRowError,
        )

        ids = np.asarray(cols["tx_id"])[bad]
        detail = detail_fn(bad) if detail_fn is not None else ""
        raise PoisonRowError(
            f"corrupt row(s): negative amount_cents for "
            f"{int(bad.sum())} row(s), tx_id(s) {ids[:5].tolist()}"
            + (f" ({detail})" if detail else ""))


class ScoringEngine:
    """Drives source → jitted step → sink.

    ``online_lr > 0`` enables in-step online SGD from labeled rows
    (BASELINE.json config 4) for differentiable model kinds.
    """

    def __init__(
        self,
        cfg: Config,
        kind: str,
        params,
        scaler: Scaler,
        feature_state: Optional[FeatureState] = None,
        scorer: Optional[str] = None,
        cpu_model=None,
        online_lr: float = 0.0,
        feature_cache=None,
        metrics=None,
        dead_letter=None,
    ):
        self.cfg = cfg
        self.kind = kind
        self.scorer = scorer or cfg.runtime.scorer
        self.cpu_model = cpu_model
        self.online_lr = online_lr
        # Serving z_mode, resolved ONCE at build (auto → int8 on TPU /
        # f32 elsewhere): the tree-ensemble z-contraction arithmetic the
        # jitted step closes over — so precompile() compiles, and every
        # dispatch serves, the active mode. Decision-identical to f32 by
        # the gemm_leaf_sum exactness contract (int8 additionally
        # BIT-identical; engine-level gate in make perf-smoke).
        self.z_mode = resolve_z_mode(cfg.runtime.z_mode)
        # Data-plane guard (opt-in, runtime.nan_guard): rows whose step
        # outputs cross the host boundary non-finite are quarantined to
        # the dead-letter sink and the batch is re-scored from the
        # pre-batch state WITHOUT them — a NaN never contaminates the
        # running feature state (see _quarantine_nonfinite).
        self.dead_letter = dead_letter
        self._nan_guard = bool(cfg.runtime.nan_guard)
        if self._nan_guard and dead_letter is None:
            raise ValueError(
                "runtime.nan_guard needs a dead-letter sink to quarantine "
                "into — pass dead_letter=DeadLetterSink(...) "
                "(CLI: --nan-guard requires --dead-letter)")
        # The guard needs the PRE-batch state to stay alive across the
        # step (it re-runs the batch from it on detection), so donation
        # of the feature-state buffers is disabled while it is on.
        self._donate = () if self._nan_guard else (0,)
        self._init_telemetry(metrics)
        # Tiered-store attrs exist on EVERY engine (the shared batch path
        # reads them); only the non-sequence constructor below can arm
        # them.
        self._exact = False
        self._compact_every = 0
        self._compact = None
        self._max_day = 0
        self._m_tier = None
        self._m_slots_occ = None
        self._m_slots_rec = None
        # Host cold tier (features.cold_store, key_mode="exact"): armed
        # by _init_cold below; the defaults keep every shared-path
        # None-check cheap for sequence/direct/hash engines.
        self._cold = None  # io.coldstore.ColdStore
        self._cold_writer = None  # io.coldstore.SegmentWriter
        self._promote = None  # jitted features.online.promote_rows
        self._demote_slots = 0
        self._promote_widths = ()  # lane ladder of the promote programs
        self._degraded_keys = set()  # cold rows lost to a corrupt segment
        self._cold_synced = False
        # Elastic-fleet seams (armed by the CLI, None everywhere else):
        # a threading.Event the launcher's coordinated drain sets via
        # SIGTERM — run() breaks at the next batch boundary with offsets
        # resumable — and the cross-process terminal-sketch exchange
        # (runtime.cms_exchange.SketchExchange) run at checkpoint
        # cadence.
        self.stop_event = None
        self.cms_exchange = None
        if cfg.runtime.emit_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"emit_dtype must be float32|bfloat16, "
                f"got {cfg.runtime.emit_dtype!r}")
        self._key_bits = int(cfg.features.key_bits)
        # rows with an id past 32 bits, counted at either width; at 32
        # they are folded, and the first such batch says so once
        self._m_wide_ids = self.metrics.counter(
            "rtfds_wide_id_rows_total",
            "rows whose customer or terminal id does not fit 32 bits "
            "(counted on the host from the decoded int64 columns): "
            "carried whole at key_bits=64, xor-folded — and possibly "
            "merged with another id — at key_bits=32")
        self._wide_ids_warned = False
        if kind == "sequence":
            # Long-context serving: per-customer event histories in HBM
            # scored by the causal transformer — a different state and
            # step shape, built in its own branch.
            if self._key_bits == 64:
                raise ValueError(
                    "key_bits=64 is carried by the windows plane's key "
                    "directory only; kind='sequence' keys its history "
                    "ring by one folded word (keep key_bits=32)")
            if cfg.features.key_mode == "exact":
                raise ValueError(
                    "key_mode='exact' is the windows-plane tiered "
                    "feature store; kind='sequence' serves from its own "
                    "history state (keep key_mode direct/hash)")
            if self.scorer == "cpu":
                raise ValueError(
                    "kind='sequence' has no sklearn oracle — "
                    "--scorer cpu does not apply")
            if online_lr > 0.0:
                raise ValueError(
                    "online SGD is not wired for kind='sequence'")
            if cfg.runtime.emit_dtype != "float32":
                # the sequence scorer never transfers a feature matrix
                # (zeros, built host-side) — a bf16 request would change
                # nothing; reject rather than let the operator believe
                # D2H bytes were halved
                raise ValueError(
                    "emit_dtype='bfloat16' has no effect for "
                    "kind='sequence' (no feature matrix leaves the "
                    "device); keep float32")
            if cfg.runtime.emit_threshold > 0.0:
                # the sequence scorer's feature matrix is definitionally
                # zeros — a threshold would change nothing; reject rather
                # than let the operator believe D2H bytes were cut
                raise ValueError(
                    "emit_threshold has no effect for kind='sequence' "
                    "(no feature matrix leaves the device); keep 0")
            self._announce_pallas(params)
            self._init_sequence(cfg, params, scaler, feature_state,
                                feature_cache)
            return
        # Optional runtime.feedback.FeatureCache: every scored row's raw
        # feature vector is cached for the labeled-feedback join.
        self.feature_cache = feature_cache
        if not cfg.runtime.emit_features and (
            self.scorer == "cpu" or feature_cache is not None
        ):
            raise ValueError(
                "emit_features=False (alerts-only serving) cannot be "
                "combined with --scorer cpu or a feature cache: both "
                "consume host-side feature rows")
        if cfg.runtime.emit_dtype != "float32" and (
            self.scorer == "cpu" or feature_cache is not None
        ):
            raise ValueError(
                "emit_dtype='bfloat16' is lossy on the emitted feature "
                "columns; --scorer cpu and the feedback feature cache "
                "re-consume those rows and would drift — keep float32")
        thresh = float(cfg.runtime.emit_threshold)
        if not 0.0 <= thresh <= 1.0:
            raise ValueError(
                f"emit_threshold must be in [0, 1], got {thresh}")
        if thresh > 0.0 and not cfg.runtime.emit_features:
            # same principle as the sequence-kind rejection above: never
            # let the operator believe flagged rows' features will land
            # when alerts-only mode keeps the matrix in HBM entirely
            raise ValueError(
                "emit_threshold > 0 (selective emission) contradicts "
                "emit_features=False (alerts-only): pick one")
        self._selective = thresh > 0.0
        if self._selective:
            if self.scorer == "cpu" or feature_cache is not None:
                raise ValueError(
                    "selective emission (emit_threshold > 0) cannot be "
                    "combined with --scorer cpu or a feature cache: both "
                    "consume every row's features host-side")
            if cfg.runtime.emit_dtype != "float32":
                raise ValueError(
                    "selective emission already cuts feature D2H by "
                    "~1/emit_cap_fraction; emit_dtype='bfloat16' is not "
                    "supported on the packed selective transfer — keep "
                    "float32")
            if not 0.0 < cfg.runtime.emit_cap_fraction <= 1.0:
                raise ValueError(
                    "emit_cap_fraction must be in (0, 1], got "
                    f"{cfg.runtime.emit_cap_fraction}")
        # Batches whose flagged-row count overflowed the compaction cap
        # (each fell back to a full feature fetch — correct, just slower).
        self.selective_overflows = 0
        self._feedback_step = None
        self._state_feedback_step = None
        # Tiered feature store (key_mode="exact"): the step routes slots
        # through the exact key directory, serves admission misses from
        # the sketch tier, and returns per-batch tier counts; a periodic
        # compaction step (its own DispatchSignature variant, see
        # dispatch_inventory) reclaims dead hot-tier slots.
        self._exact = cfg.features.key_mode == "exact"
        self._compact_every = (cfg.features.compact_every
                               if self._exact else 0)
        self._check_state_budget()
        self._init_state_telemetry()
        # Depth-bounded tree ensembles score ~100× faster on TPU in the GEMM
        # form (see models/forest.py::predict_proba); convert once at build.
        params = device_params_for(kind, params)
        self.state = EngineState(
            feature_state=feature_state or init_feature_state(cfg.features),
            params=params,
            scaler=scaler,
        )
        self._predict = predict_fn_for(kind, z_mode=self.z_mode)
        self._loss = loss_fn_for(kind)
        fcfg = cfg.features
        # What --use-pallas serves is decided in ONE place,
        # _pallas_choice(params). The step and the predict swap
        # (_maybe_use_pallas_forest) take its answer through
        # _announce_pallas at TRACE time, so the gauge / WARNING are
        # written by the code that makes the choice — here at build, on
        # every params swap, and whenever the step retraces.
        self._maybe_use_pallas_forest(kind)
        self._announce_pallas(params)
        self._build_step()
        if self._exact:
            from real_time_fraud_detection_system_tpu.features.online \
                import compact_feature_state

            # Cold tier armed: compaction DEMOTES pressure-evicted keys'
            # rows into a fixed-shape payload (K = cold_demote_slots per
            # table) instead of discarding — one static return arity per
            # engine config, same principle as the exact 5-tuple step.
            demote = (int(fcfg.cold_demote_slots)
                      if getattr(fcfg, "cold_store", "") else 0)
            self._demote_slots = demote

            def compact(fstate: FeatureState, now_day):
                return compact_feature_state(fstate, now_day, fcfg,
                                             demote_slots=demote)

            self._compact = jax.jit(compact, donate_argnums=self._donate)
            if demote:
                self._init_cold(fcfg)

    def _build_step(self) -> None:
        """The jitted one-chip step (``features/step.py::make_step``).
        ``self._predict`` is read when the step traces, not now: the
        verifier's fixtures and a params swap replace it on a built
        engine."""
        self._step = jax.jit(
            make_step(
                self.cfg,
                None if self.scorer == "cpu"
                else (lambda p, x: self._predict(p, x)),
                self._loss, self.online_lr,
                kernel_of=self._announce_pallas, z_mode=self.z_mode),
            donate_argnums=self._donate)

    def _init_telemetry(self, metrics) -> None:
        """Resolve the registry series ONCE at build time: the hot loop
        then pays one method call per event, never a name lookup. A
        ``FlightRecorder`` can be attached via ``self.recorder`` (the CLI
        installs a process-wide one; ``run`` falls back to it)."""
        self.recorder = None
        reg = metrics if metrics is not None else get_registry()
        self.metrics = reg
        self._m_batches = reg.counter(
            "rtfds_batches_total", "micro-batches scored")
        self._m_rows = reg.counter("rtfds_rows_total", "rows scored")
        self._m_lat = reg.histogram(
            "rtfds_batch_latency_seconds",
            "end-to-end micro-batch latency (poll wait excluded)")
        self._m_phase = {
            ph: reg.histogram(
                "rtfds_phase_seconds",
                "per-batch loop-time decomposition by phase", phase=ph)
            for ph in PHASES + LOOP_PHASES
        }
        # run()'s percentile trackers by phase name, for its length
        self._trackers: dict = {}
        # How often the loop's join rule let a write overlap the next poll
        # (two names: a ratio reader sums every series of a name).
        self._m_sink_batches = reg.counter(
            "rtfds_sink_batches_total",
            "batches handed to the loop's sink writer")
        self._m_sink_overlapped = reg.counter(
            "rtfds_sink_overlapped_batches_total",
            "batches whose write the next poll did not wait for (the "
            "source showed a backlog)")
        self._m_last = reg.gauge(
            "rtfds_last_batch_unix_seconds",
            "wall-clock time the last batch finished (healthz input)")
        self._m_qdepth = reg.gauge(
            "rtfds_queue_depth", "micro-batches currently in flight")
        # Tracing + XLA/device telemetry: the tracer is the process-wide
        # one (disabled by default — span() is then one attribute check);
        # compile counters are process-global (the jit cache is), while
        # the recompile alarm and memory gauges honor THIS registry.
        self.tracer = get_tracer()
        install_compile_telemetry()
        keep_scopes_in_cache_key()
        self._recompile = RecompileDetector(registry=reg)
        self._devmem = DeviceMemoryTelemetry(reg)
        # AOT-precompiled step executables (see precompile()): dispatch
        # key -> jax Compiled. Empty = plain jit dispatch.
        self._aot = {}
        self._aot_params_sig = None
        self._m_precompiled = reg.counter(
            "rtfds_precompiled_steps_total",
            "step executables AOT-compiled at warmup (bucket sizes x "
            "variants)")
        self._m_aot_fallbacks = reg.counter(
            "rtfds_aot_fallbacks_total",
            "dispatches that fell back from an AOT executable to jit "
            "(input signature drifted from the precompiled one)")
        # Overlapped result fetch (runtime.fetch_overlap): D2H copies are
        # issued async the moment a step's handle resolves, so the
        # transfer runs while the loop preps/dispatches later batches.
        # The counter accumulates the head start each batch's transfer
        # got before the blocking materialization — result_wait then
        # reflects device time + residual transfer, not full transfer
        # serialization.
        self._fetch_overlap = bool(self.cfg.runtime.fetch_overlap)
        self._m_fetch_overlap = reg.counter(
            "rtfds_fetch_overlap_seconds_total",
            "seconds of D2H head start granted by async result fetch "
            "(copy_to_host_async issue to blocking materialization)")
        # Per-bucket zero feature matrices, shared read-only across
        # batches (see _zero_features).
        self._zeros_cache: dict = {}
        # Continuous-learning hooks (runtime/learner.py): a ShadowScorer
        # dual-scores emitted batches beside the champion; feedback_tap
        # hands labeled rows to the streaming learner. Both None unless
        # a LearningLoop attaches.
        self.shadow = None
        self.feedback_tap = None
        # Overload-ladder host-side degrade flags (runtime/overload.py).
        # shadow_paused gates shadow scoring without detaching it (rung
        # 1 sheds it, descent restores it); _shed_features switches to
        # alerts-only emission WITHOUT touching the compiled step — the
        # feature matrix simply stays in HBM unfetched, so every
        # dispatch remains a signature from dispatch_inventory().
        self.shadow_paused = False
        self._shed_features = False
        # Param-swap accounting (hot reload × online SGD): True once any
        # online update (in-step SGD on labeled rows, or a feedback SGD
        # step) landed since the last wholesale params swap — a reload
        # then CLOBBERS those updates, and the operator must be able to
        # count it, not read a one-time warning.
        self._online_dirty = False
        # Device-plane config gauges (healthz's device_plane block reads
        # them): which z_mode the jitted step closes over, and whether
        # the opt-in fused Pallas path is enabled.
        self._m_zmode = {
            m: reg.gauge(
                "rtfds_z_mode",
                "active tree-ensemble z-contraction mode (1 = the mode "
                "the serving step compiled with; exactness contract in "
                "README § Device plane)", mode=m)
            for m in ("f32", "bf16", "int8")
        }
        for m, g in self._m_zmode.items():
            g.set(1.0 if m == self.z_mode else 0.0)
        self._m_use_pallas = reg.gauge(
            "rtfds_use_pallas",
            "1 when a Pallas kernel is in the SERVED step (fused "
            "featurize-score or classify-only); 0 when use_pallas is off "
            "or the engine serves the XLA composition instead")
        self._pallas_kernel = None  # set by _announce_pallas
        self._m_reloads = {
            o: reg.counter(
                "rtfds_model_reloads_total",
                "hot model reloads by outcome (clobbered_online_updates "
                "= the swap discarded on-device online-SGD updates "
                "accumulated since the previous artifact)", outcome=o)
            for o in ("clean", "clobbered_online_updates")
        }

    def _phase(self, name: str, batch: Optional[str] = None, hist=None,
               **args) -> _Phase:
        """``with self._phase(name): ...`` — the one way a phase of the
        loop is timed (:class:`_Phase`). ``hist``: a histogram other than
        ``rtfds_phase_seconds{phase=name}``."""
        return _Phase(self, name, self.tracer.span(name, batch=batch, **args),
                      hist if hist is not None else self._m_phase.get(name))

    # -- tiered feature store (key_mode="exact") ---------------------------

    def _state_shards(self) -> int:
        """Shard count the static ``state_bytes`` accounting uses: 1 for
        the single-chip engine; the sharded engine reports its mesh
        width (per-device sketch replicas multiply the cms tier)."""
        return 1

    def _check_state_budget(self) -> None:
        """``features.state_hbm_budget_mb``: fail the BUILD, not the
        stream, when the configured feature state cannot fit the budget
        (static ``state_bytes`` accounting)."""
        fcfg = self.cfg.features
        if fcfg.state_hbm_budget_mb <= 0:
            return
        sb = state_bytes(fcfg, n_shards=self._state_shards())
        budget = int(fcfg.state_hbm_budget_mb * 2 ** 20)
        if sb["total"] > budget:
            raise ValueError(
                f"feature state needs {sb['total']} bytes "
                f"(dense {sb['dense']}, directory {sb['directory']}, "
                f"cms {sb['cms']}) against a state_hbm_budget_mb="
                f"{fcfg.state_hbm_budget_mb:g} budget ({budget} bytes) — "
                "shrink the hot tier (customer_capacity/"
                "terminal_capacity), the sketch (cms_width), or raise "
                "the budget")

    def _init_state_telemetry(self) -> None:
        """Tiered-store observability (registered only when the tier
        machinery is live, so plain direct/hash runs keep /healthz
        clean; bytes gauges also register whenever a budget is set)."""
        reg = self.metrics
        fcfg = self.cfg.features
        self._m_tier = None
        self._m_claim_rounds = None
        self._m_narrow_rounds = None
        self._m_alias = None
        self._m_slots_occ = None
        self._m_slots_rec = None
        self._m_compactions = None
        self._m_compact_s = None
        self._m_sweeps = None
        self._m_day_rollovers = None
        self._m_multi_day = None
        if self._exact:
            self._m_compactions = reg.counter(
                "rtfds_state_compactions_total",
                "recency-compaction passes run (features.compact_every)")
            self._m_compact_s = reg.histogram(
                "rtfds_state_compact_seconds",
                "loop-thread seconds in one compaction pass: the dispatch "
                "and the wait for its reclaimed counts, which drains the "
                "steps in flight")
            self._m_phase["compact_fetch"] = reg.histogram(
                "rtfds_phase_seconds",
                "per-batch loop-time decomposition by phase",
                phase="compact_fetch")
            self._m_tier = {
                t: reg.counter(
                    "rtfds_feature_tier_rows_total",
                    "row x keyspace feature reads served per tier "
                    "(dense = private hot-tier slot; cms = count-min "
                    "sketch fallback after an admission miss)", tier=t)
                for t in ("dense", "cms")
            }
            tables = (("customer", fcfg.customer_source != "cms"),
                      ("terminal", True))
            self._m_claim_rounds = {
                t: reg.counter(
                    "rtfds_keydir_claim_rounds_total",
                    "claim rounds the directory's admit ran (the step's "
                    "and the cold tier's promotes'): none for a batch of "
                    "known keys, keydir_probes for one that holds a key "
                    "no round can place", table=t)
                for t, present in tables if present
            }
            self._m_narrow_rounds = {
                t: reg.counter(
                    "rtfds_keydir_narrow_rounds_total",
                    "those of the claim rounds that ran over the packed "
                    "lanes of the rows still unplaced "
                    "(ops/keydir.CLAIM_LANES), not over the batch",
                    table=t)
                for t in self._m_claim_rounds
            }
            if fcfg.key_bits == 64:
                self._m_alias = {
                    "rows": reg.counter(
                        "rtfds_keydir_alias_rows_total",
                        "rows whose first fingerprint match in the key "
                        "directory held ANOTHER 64-bit key (an id that "
                        "folds like theirs), so that the lookup verified "
                        "a further match for them; both tables, the "
                        "step's admits"),
                    "trips": reg.counter(
                        "rtfds_keydir_alias_trips_total",
                        "trips of the wide lookup's verify loop, both "
                        "tables: one an admit whose batch holds a known "
                        "key, more only while an alias row is left"),
                }
            self._m_slots_occ = {
                t: reg.gauge(
                    "rtfds_feature_slots_occupied",
                    "hot-tier slots currently owned by a key "
                    "(updated at compaction cadence)", table=t)
                for t, present in tables if present
            }
            self._m_slots_rec = {
                t: reg.counter(
                    "rtfds_feature_slots_reclaimed_total",
                    "hot-tier slots reclaimed by recency compaction "
                    "(the slot held only history older than "
                    "delay + max(window))", table=t)
                for t, present in tables if present
            }
            if self._compact_every:
                self._m_sweeps = {
                    t: reg.counter(
                        "rtfds_state_compact_sweeps_total",
                        "table sweeps of the compaction passes that ran "
                        "their entry-wide part: the table gave something "
                        "up (a pass whose dense counts find nothing dead "
                        "and nothing to demote in a table skips it)",
                        table=t)
                    for t, present in tables if present
                }
                self._m_day_rollovers = reg.counter(
                    "rtfds_event_day_rollovers_total",
                    "event days by which the newest day the stream has "
                    "seen (compaction's now_day) moved forward; the "
                    "stream's first batch sets it and counts nothing")
                self._m_multi_day = reg.counter(
                    "rtfds_batches_multi_day_total",
                    "batches that held rows of more than one event day")
        if self._exact or fcfg.state_hbm_budget_mb > 0:
            sb = state_bytes(fcfg, n_shards=self._state_shards())
            for tier in ("dense", "directory", "cms", "total"):
                reg.gauge(
                    "rtfds_feature_state_bytes",
                    "HBM bytes of the configured feature state per tier "
                    "(static accounting, features/online.state_bytes)",
                    tier=tier).set(float(sb[tier]))
            reg.gauge(
                "rtfds_feature_state_budget_bytes",
                "configured feature-state HBM budget "
                "(state_hbm_budget_mb; 0 = unchecked)").set(
                float(fcfg.state_hbm_budget_mb * 2 ** 20))

    # -- host cold tier (features.cold_store) ------------------------------

    def _cold_tables(self) -> tuple:
        """Tables with a key directory (demotable/promotable)."""
        if self.cfg.features.customer_source == "cms":
            return ("terminal",)
        return ("customer", "terminal")

    def _init_cold(self, fcfg) -> None:
        """Arm the host cold tier: the keyed store, its segment-writer
        thread, the jitted promote-merge step, the lane ladder and the
        telemetry."""
        from real_time_fraud_detection_system_tpu.features.online import (
            promote_rows,
        )
        from real_time_fraud_detection_system_tpu.io.coldstore import (
            ColdStore,
            SegmentWriter,
        )

        self._cold = ColdStore(fcfg.cold_store,
                               segment_mb=fcfg.cold_segment_mb,
                               key_bits=fcfg.key_bits)
        self._cold_writer = SegmentWriter(self._cold)
        # days of event time any window can see: what compaction reclaims
        # in the hot tier, the store forgets
        self._cold_horizon = int(fcfg.delay_days + max(fcfg.windows))
        self._promote_widths = promote_widths(
            max(self.cfg.runtime.batch_buckets))

        def promote(fstate, payload):
            return promote_rows(fstate, payload, fcfg)

        self._promote = jax.jit(promote, donate_argnums=self._donate)
        reg = self.metrics
        self._m_phase.update({
            ph: reg.histogram(
                "rtfds_phase_seconds",
                "per-batch loop-time decomposition by phase", phase=ph)
            for ph in COLD_PHASES
        })
        self._m_cold_keys = reg.gauge(
            "rtfds_feature_cold_keys",
            "keys resident in the host cold tier (demoted, not yet "
            "promoted back)")
        self._m_cold_bytes = reg.gauge(
            "rtfds_feature_cold_bytes",
            "host bytes of live cold-tier segments + flush buffer")
        self._m_cold_prom = reg.counter(
            "rtfds_feature_cold_promotions_total",
            "cold-tier keys promoted back into the hot tier")
        self._m_cold_dem = reg.counter(
            "rtfds_feature_cold_demotions_total",
            "hot-tier keys demoted to the cold tier by compaction "
            "pressure eviction")
        self._m_cold_rows = reg.counter(
            "rtfds_feature_cold_rows_total",
            "rows (not keys) whose customer or terminal was in the cold "
            "tier when their batch was prepared, so promoted before the "
            "step that scored them")
        self._m_cold_lanes = reg.counter(
            "rtfds_feature_cold_promote_lanes_total",
            "lanes of the promote programs dispatched, padding included "
            "(live lanes = rtfds_feature_cold_promotions_total)")
        self._m_cold_expired = reg.counter(
            "rtfds_feature_cold_expired_total",
            "cold-tier keys the store dropped as dead: their newest event "
            "day fell behind now_day - (delay_days + max(windows))")
        self._m_cold_dead = reg.counter(
            "rtfds_feature_cold_dead_returns_total",
            "returning keys whose cold rows no window could see any more: "
            "admitted afresh, no promote lane taken")
        self._m_cold_age = reg.counter(
            "rtfds_feature_cold_demote_age_days_total",
            "event days since their last row, summed over the keys "
            "demoted (over rtfds_feature_cold_demotions_total: the mean "
            "age at demotion)")

    def _meter_cold_store(self) -> None:
        self._m_cold_keys.set(float(self._cold.keys_count))
        self._m_cold_bytes.set(float(self._cold.bytes))

    def _land_demotions(self, payload: dict) -> None:
        """Land one compaction pass's demotion payload in the store, here
        on the loop thread: when ``_maybe_compact`` returns, every
        demoted key is in the store's index and its rows are readable —
        before the host prep of any batch dispatched after this pass.
        The pass itself was waited out by ``_maybe_compact``'s
        ``compact_fetch``; the rows' copies off the device (up to
        ``cold_demote_slots x 16 NB`` bytes a table) are started
        together and only for a table that demoted something. The
        segment write follows on the writer thread, asked for by the
        NEXT pass while this thread waits for the device. The
        sharded engine's stacked ``[n_dev, K, ...]`` leaves need nothing
        special: the store flattens lanes."""
        from real_time_fraud_detection_system_tpu.io.coldstore import (
            newest_days,
        )

        parts = []
        for table in self._cold_tables():
            pay = payload.get(table)
            if pay is None:
                continue
            keys = np.asarray(pay[0])
            # [2, K] words at key_bits=64 -> uint64 [K]; the mesh's
            # stacked one-word lanes flatten
            keys = (join_key(keys) if self._key_bits == 64
                    else keys.reshape(-1))
            live = keys != np.iinfo(keys.dtype).max
            if not live.any():
                continue  # nothing demoted: the rows are not fetched
            for leaf in pay[1:]:
                leaf.copy_to_host_async()
            parts.append((table, keys, live, pay[1:]))
        if not parts:
            return
        total = age = 0
        with self._phase("cold_append"):
            for table, keys, live, rows in parts:
                # the day stamps arrive first: their row maxima are taken
                # while the three float columns are still on their way
                stamps = np.asarray(rows[0])
                days = newest_days(stamps.reshape(keys.size, -1))
                total += self._cold.append(
                    table, keys, stamps, *(np.asarray(r) for r in rows[1:]),
                    flush=False, days=days)
                age += int((self._max_day - days[live].astype(np.int64))
                           .sum())
        self._m_cold_dem.inc(total)
        self._m_cold_age.inc(age)
        self._meter_cold_store()

    def _expire_cold(self) -> None:
        """After a pass: the store forgets every key whose newest event
        day the pass's own cutoff has left behind — rows no window can
        see, which the hot tier gives back to its free stack in the same
        pass."""
        with self._phase("cold_expire"):
            gone = self._cold.expire(self._max_day - self._cold_horizon)
        self._m_cold_expired.inc(gone)
        self._meter_cold_store()

    def _promote_lanes(self, table: str, keys: np.ndarray,
                       rows: tuple) -> list:
        """Resolved cold rows of one table → the payloads to dispatch,
        each ``(width, {"customer": lanes|None, "terminal": ...})`` with
        ``lanes = (keys [W], bd, cnt, amt, frd [W, NB])``,
        ``EMPTY_KEY``-padded to the smallest precompiled width that
        holds them (more keys than the widest: several payloads). The
        sharded engine overrides with owner-grouped ``[n_dev, W, ...]``
        leaves."""
        top = self._promote_widths[-1]
        out = []
        for lo in range(0, keys.size, top):
            n = min(top, keys.size - lo)
            w = next(w for w in self._promote_widths if w >= n)
            lanes = blank_lanes((w,), rows, self._key_bits)
            lanes[0][..., :n] = device_keys(keys[lo:lo + n])
            for lane, src in zip(lanes[1:], rows):
                lane[:n] = src[lo:lo + n]
            out.append((w, one_table_payload(table, lanes)))
        return out

    def _returning_keys(self, cols: dict):
        """Host prep's cold-tier part: which of this batch's keys sit in
        the cold store. The host WROTE the store, so it knows: one
        sorted-index lookup a table. → ``{table: unique keys}``, or
        None: the tier is not armed, or (the quiet batch) nothing
        returns and nothing more is done."""
        if self._cold is None:
            return None
        hits, cold_row = {}, None
        with self._phase("cold_detect"):
            us = cols["tx_datetime_us"]
            # older than this, no row of the batch can see it
            dead_before = (int(np.min(us) // US_PER_DAY)
                           - self._cold_horizon) if len(us) else 0
            for table, col in (("customer", "customer_id"),
                               ("terminal", "terminal_id")):
                ids = cols.get(col)
                if (table not in self._cold_tables() or ids is None
                        or not len(ids)):
                    continue
                keys = host_keys(ids, self._key_bits)
                if self._key_bits == 32:
                    # the directory canonicalizes EMPTY_KEY collisions
                    # the same way (ops/keydir._canon) — mirror it or
                    # miss those keys. (A wide key is stored as it is;
                    # the one reserved pattern is never admitted, so
                    # never demoted, so never found here.)
                    keys = np.where(keys == np.uint32(0xFFFFFFFF),
                                    np.uint32(0xFFFFFFFE), keys)
                mask, newest = self._cold.find_days(table, keys)
                dead = mask & (newest < dead_before)
                if dead.any():
                    # every cold row of the key is dead history: the step
                    # admits it afresh, as it does a key a pass reclaimed
                    gone = np.unique(keys[dead])
                    self._cold.mark_promoted(table, gone)
                    self._m_cold_dead.inc(int(gone.size))
                    mask &= ~dead
                if mask.any():
                    hits[table] = np.unique(keys[mask])
                    cold_row = mask if cold_row is None \
                        else cold_row | mask
        if not hits:
            return None
        self._m_cold_rows.inc(int(cold_row.sum()))
        return hits

    def _promote_returning(self, hits: dict) -> list:
        """Promote before score: the exact window rows of every returning
        key of the batch about to be dispatched go back into the hot tier
        through a precompiled ``("promote", table, width)`` program,
        dispatched BEFORE that batch's step — device order is dispatch
        order, so the rows are resident when the step reads them. A batch
        with no returning key never gets here. → ``[(table, stats)]`` of
        the promotes dispatched, for the batch's handle:
        ``_check_promotes`` reads them when the batch is finished.

        The one way a detected key is NOT promoted is a corrupt segment
        (quarantined by the store): its keys are counted in
        ``exactness_degraded_keys`` and served from the sketch, as a key
        never demoted but unadmitted would be."""
        from real_time_fraud_detection_system_tpu.io.coldstore import (
            ColdStoreCorruptError,
        )

        checks = []
        with self._phase("state_promote"):
            for table, keys in hits.items():
                while True:
                    # a corrupt segment quarantines itself on its first
                    # touch, so the retry terminates: one per segment
                    try:
                        found, *rows = self._cold.read_rows(table, keys)
                        break
                    except ColdStoreCorruptError as e:
                        from real_time_fraud_detection_system_tpu.utils \
                            import get_logger

                        get_logger("engine").error(
                            "cold tier: %s — its keys are served from the "
                            "sketch (exactness_degraded_keys)", e)
                lost = keys[~found]
                self._degraded_keys.update(
                    (table, int(k)) for k in lost)  # corruption only
                keys = keys[found]
                if not keys.size:
                    continue
                for width, payload in self._promote_lanes(
                        table, keys, tuple(rows)):
                    with self._recompile.step(step_signature(
                            static=(self.kind, "promote", table, width))):
                        fstate, stats = self._dispatch_step(
                            ("promote", table, width), self._promote,
                            self.state.feature_state, payload)
                    self.state.feature_state = fstate
                    checks.append((table, stats))
                    self._m_cold_lanes.inc(
                        int(np.prod(payload[table][0].shape)))
                self._cold.mark_promoted(table, keys)
                self._m_cold_prom.inc(int(keys.size))
        self._meter_cold_store()
        return checks

    def _check_promotes(self, handle: dict) -> None:
        """Before a batch's result is built: every lane of the promotes
        dispatched ahead of its step was admitted. A lane the directory
        could not admit (the free stack ran dry, or all its probe
        positions were taken) means the row was scored from the sketch:
        the run stops here, before that batch is delivered, rather than
        hand on an inexact row as exact."""
        for table, stats in handle.pop("promote_checks", ()):
            # [admitted, dropped, claim rounds, narrow rounds] a table,
            # summed over shards
            stats = np.asarray(stats).reshape(-1, 2, 4).sum(axis=0)
            self._count_claim_rounds(stats[:, 2], stats[:, 3])
            dropped = int(stats[:, 1].sum())
            if dropped:
                raise ColdPromoteError(
                    f"cold tier: {dropped} returning {table} key(s) could "
                    "not be admitted to the hot tier before their rows "
                    "were scored (free slots ran out between compaction "
                    "passes). Size the tier by its three rules (README, "
                    "Cold tier): cold_demote_slots / compact_every >= the "
                    "keys a batch admits, returning and new; occupancy "
                    "between two passes <= 0.5 of the slots; and every "
                    "pass finds keys last touched before its newest event "
                    "day to demote — under a calendar that moves that is "
                    "the keys of the days before, so the hot set is what "
                    "cold_highwater x slots holds of the newest days. "
                    "Raise cold_demote_slots, lower compact_every or "
                    "cold_highwater, or add slots")

    def _count_claim_rounds(self, rounds, narrow) -> None:
        """``rounds`` = [customer, terminal] claim rounds one program ran,
        ``narrow`` = those of them that ran narrow."""
        for table, n, k in zip(("customer", "terminal"), rounds, narrow):
            if table in self._m_claim_rounds:
                self._m_claim_rounds[table].inc(float(n))
                self._m_narrow_rounds[table].inc(float(k))

    def _settle_cold(self) -> None:
        """Everything demoted so far is in the store and durable, and the
        state carries the lineage a checkpoint save will record."""
        self._cold_writer.wait()
        self._cold.flush()  # what the buffer still holds, full or not
        self.state.cold_lineage = self._cold.lineage()

    def _sync_cold_after_restore(self) -> None:
        """Adopt a restored checkpoint's cold lineage exactly once:
        prune post-checkpoint segments (replay regenerates them —
        exactly-once across the tier boundary) and drop what was on its
        way to the store."""
        if self._cold is None or self._cold_synced:
            return
        lineage = getattr(self.state, "cold_lineage", None)
        if lineage is None:
            return
        self._cold_synced = True
        self._cold_writer.wait()
        self._cold.sync_to(lineage)
        topo = getattr(self, "topology", None)
        if topo is not None and topo.n_processes > 1:
            # Fleet resize seam: the adopted lineage may carry keys the
            # NEW topology homes elsewhere (a consolidated shrink-merge
            # store fanned back out, or a grown fleet adopting a
            # 1-process store). Cold keys are hot-tier directory keys —
            # already residue-foldable — so prune to this process's
            # residue block; the owning peer promotes the rest from ITS
            # copy of the store.
            dropped = self._cold.rehome(lambda _t, ks: topo.owns(ks))
            if dropped:
                from real_time_fraud_detection_system_tpu.utils import (
                    get_logger,
                )

                get_logger("engine").info(
                    "cold tier re-homed for process %d/%d: dropped %d "
                    "foreign key(s)", topo.process_id,
                    topo.n_processes, dropped)
        self._meter_cold_store()

    def checkpoint_state(self) -> EngineState:
        """The state a checkpoint save should persist. With a terminal-
        sketch exchange armed this strips adopted PEER content back out
        of ``terminal_cms`` (checkpoints always store the locals-only
        partial form, so the P→1 resize merge's same-day sketch SUM
        stays exact regardless of exchange timing); otherwise it is
        ``self.state`` itself. Dynamic lineage attrs (cold_lineage,
        resize_epochs) ride along on the shallow copy."""
        xch = self.cms_exchange
        fs = self.state.feature_state
        if xch is None or fs is None or fs.terminal_cms is None:
            return self.state
        partial = xch.checkpoint_cms(fs.terminal_cms)
        if partial is None:
            return self.state
        view = copy.copy(self.state)
        view.feature_state = fs._replace(terminal_cms=partial)
        return view

    def _maybe_exchange_cms(self) -> None:
        """Run one terminal-sketch exchange round (checkpoint cadence,
        between device steps): publish this process's cumulative local
        contributions, adopt whatever peer partials are present, and
        install the merged view back into the serving state with each
        leaf re-placed under its original sharding."""
        xch = self.cms_exchange
        fs = self.state.feature_state
        if xch is None or fs is None or fs.terminal_cms is None:
            return
        from real_time_fraud_detection_system_tpu.runtime.cms_exchange \
            import install_logical

        merged = xch.exchange(fs.terminal_cms)
        if merged is None:
            return
        new_cms = install_logical(fs.terminal_cms, merged)

        def _place(old, new):
            if new is None or old is None:
                return None
            arr = jnp.asarray(np.asarray(new), dtype=old.dtype)
            sharding = getattr(old, "sharding", None)
            return jax.device_put(arr, sharding) if sharding is not None \
                else arr

        self.state.feature_state = fs._replace(
            terminal_cms=new_cms._replace(
                slice_day=_place(fs.terminal_cms.slice_day,
                                 new_cms.slice_day),
                count=_place(fs.terminal_cms.count, new_cms.count),
                amount=_place(fs.terminal_cms.amount, new_cms.amount),
                fraud=_place(fs.terminal_cms.fraud, new_cms.fraud)))

    def _note_batch_days(self, cols: dict) -> None:
        """Track the newest day the stream has seen — compaction's
        recency cutoff input (one vectorized max and min per batch) —
        and count how far it moves: live, a day is 86,400 s of batches;
        under a replay it may be a handful."""
        if not self._compact_every:
            return
        us = cols.get("tx_datetime_us")
        if us is not None and len(us):
            newest = int(np.max(us) // US_PER_DAY)
            if int(np.min(us) // US_PER_DAY) != newest:
                self._m_multi_day.inc()
            if newest > self._max_day:
                if self._max_day:
                    self._m_day_rollovers.inc(newest - self._max_day)
                self._max_day = newest

    def _maybe_compact(self) -> None:
        """Run the recency-compaction step on its cadence (called once
        per finished batch, between device steps — the same
        single-threaded contract as feedback). Dispatch chains through
        ``state.feature_state`` like every step, so in-flight batches
        (dispatched earlier) are unaffected and the next batch serves
        post-compaction state."""
        if (not self._compact_every
                or self.state.batches_done % self._compact_every != 0):
            return
        with self._phase("state_compact", hist=self._m_compact_s,
                         day=self._max_day):
            day = jnp.asarray(np.int32(self._max_day))
            with self._recompile.step(step_signature(
                    day, static=(self.kind, "compact"))):
                out = self._dispatch_step(
                    ("compact",), self._compact,
                    self.state.feature_state, day)
            fstate, reclaimed = out[:2]
            if self._demote_slots:
                # the durable copy of what the LAST pass landed is made
                # now, while this thread waits for the device: a segment's
                # serialisation holds the interpreter's lock for a few
                # tenths of a second, and right after a landing it would
                # take them from the next batch's poll, prep and promote
                # with the chip idle (PERF.md, PR 53)
                self._cold_writer.kick()
            with self._phase("compact_fetch"):
                # the wait for the pass, and for the steps in flight
                # ahead of it: the pass's other outputs are ready with
                # this one
                reclaimed = np.asarray(reclaimed)
            if self._demote_slots:
                self._land_demotions(out[2])
                self._expire_cold()
            self.state.feature_state = fstate
            self._record_compaction(fstate, reclaimed)
            self._m_compactions.inc()

    def _record_compaction(self, fstate, reclaimed) -> None:
        """Meter one compaction pass (counters, gauges, flight event) —
        the sharded engine overrides with the per-shard breakdown."""
        rec = np.asarray(reclaimed)  # [customer, terminal], on the host
        occupied = {}
        for i, table in enumerate(("customer", "terminal")):
            if table in self._m_slots_rec:
                self._m_slots_rec[table].inc(int(rec[i]))
            if table in (self._m_sweeps or {}):
                # a table swept <=> it gave something up: the pass's
                # dense pre-check is exact
                self._m_sweeps[table].inc(int(rec[i] > 0))
            kd = getattr(fstate, f"{table}_dir")
            if kd is not None and table in self._m_slots_occ:
                # compact_fetch already waited the pass out, so this
                # scalar read is free
                occ = int(kd.slot_capacity) - int(np.asarray(kd.free_top))
                self._m_slots_occ[table].set(occ)
                occupied[table] = occ
        rec_now = int(rec.sum())
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
                "feature_state", reclaimed=rec_now,
                occupied=sum(occupied.values()),
                capacity=sum(
                    getattr(fstate, f"{t}_dir").slot_capacity
                    for t in occupied),
                dense_rows=tiers.get("dense", 0.0),
                cms_rows=tiers.get("cms", 0.0),
                batch=self.state.batches_done, **extra)

    # -- AOT bucket precompilation ----------------------------------------

    @staticmethod
    def _sds(tree):
        """Pytree → ShapeDtypeStruct pytree for .lower() (shapes, dtypes
        and — when leaves carry one — shardings; never touches buffers,
        so donation at trace time is free)."""
        def one(x):
            sh = getattr(x, "sharding", None)
            if sh is not None:
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
            a = np.asarray(x)
            return jax.ShapeDtypeStruct(a.shape, jnp.asarray(a).dtype)

        return jax.tree.map(one, tree)

    @staticmethod
    def _params_sig(params) -> tuple:
        """(shape, dtype) fingerprint of a params tree — the facts an AOT
        step executable was compiled against. A hot model reload that
        changes it invalidates the AOT cache (jit would retrace; the
        compiled executables would just reject the call)."""
        return tuple(
            (tuple(np.shape(leaf)), str(jnp.asarray(leaf).dtype))
            for leaf in jax.tree.leaves(params)
        )

    def dispatch_inventory(self) -> "List[DispatchSignature]":
        """Enumerate EVERY dispatch signature this engine can serve.

        The single source of truth for the device plane's reachable
        program set: every micro-batch pads to a ``runtime.batch_buckets``
        size (``core.batch.bucket_size``), and the step's static facts
        (kind, z_mode, selective packing, emission dtype, donation
        layout, Pallas gating) are fixed at build — so the runtime
        dispatch key is always ``("step", 7, bucket)`` for an enumerable
        bucket (``("step", 9, bucket)`` at ``key_bits=64``: the packed
        batch's two more rows, ``core.batch.packed_rows``). :meth:`precompile` compiles exactly this list and
        ``tools/rtfdsverify`` proves contracts over exactly this list;
        neither re-derives its own enumeration, so they cannot drift.
        """
        zmode_kinds = ("tree", "forest", "gbt")
        sigs = [
            DispatchSignature(
                key=("step", packed_rows(self._key_bits), int(b)),
                variant="step",
                kind=self.kind,
                z_mode=self.z_mode if self.kind in zmode_kinds else None,
                bucket=int(b),
                donate=tuple(self._donate),
                selective=bool(self._selective),
                emit_dtype=self.cfg.runtime.emit_dtype,
                use_pallas=bool(self.cfg.runtime.use_pallas),
            )
            for b in sorted(set(self.cfg.runtime.batch_buckets))
        ]
        if self._compact_every:
            # The recency-compaction pass is part of the compiled step
            # family: ONE shape (the full state + an int32 day scalar),
            # AOT-compiled at warmup like every bucket, so the cadence
            # can fire mid-stream without a recompile. No z contraction,
            # no emission, no Pallas — the per-signature checks that key
            # on those facts correctly skip it.
            sigs.append(DispatchSignature(
                key=("compact",),
                variant="compact",
                kind=self.kind,
                z_mode=None,
                bucket=0,
                donate=tuple(self._donate),
                selective=False,
                emit_dtype=self.cfg.runtime.emit_dtype,
                use_pallas=False,
            ))
        sigs.extend(self._promote_signatures(tuple(self._donate)))
        return sigs

    def _promote_signatures(self, donate: tuple) -> list:
        """The cold tier's promote programs as inventory entries: one a
        table with a directory and a width of the lane ladder, keyed as
        ``_promote_returning`` dispatches them, so a returning key never
        pays a mid-stream compile whatever the batch brings back."""
        return [
            DispatchSignature(
                key=("promote", table, int(w)),
                variant="promote",
                kind=self.kind,
                z_mode=None,
                bucket=int(w),
                donate=donate,
                selective=False,
                emit_dtype=self.cfg.runtime.emit_dtype,
                use_pallas=False,
            )
            for table in (self._cold_tables() if self._demote_slots else ())
            for w in self._promote_widths
        ]

    def _promote_payload_sds(self, table: str, width: int) -> dict:
        """Shape-only template of one promote payload (the sharded
        engine overrides with its stacked per-shard layout)."""
        nb = self.cfg.features.n_day_buckets
        key_shape = ((2,) if self._key_bits == 64 else ()) + (width,)
        lanes = (jax.ShapeDtypeStruct(key_shape, jnp.uint32),
                 jax.ShapeDtypeStruct((width, nb), jnp.int32)) + (
            jax.ShapeDtypeStruct((width, nb), jnp.float32),) * 3
        return one_table_payload(table, lanes)

    def signature_templates(self, sig: DispatchSignature) -> tuple:
        """Shape-only argument templates for ``sig`` — what
        ``signature_step(sig).lower(...)`` / ``.trace(...)`` take.
        Never touches buffers (``_sds``), so tracing is free of device
        work; callers that need runtime-exact dtypes (precompile, the
        verifier) must commit scalar param leaves to arrays first (see
        :meth:`precompile`)."""
        if sig.variant == "compact":
            return (
                self._sds(self.state.feature_state),
                jax.ShapeDtypeStruct((), jnp.int32),
            )
        if sig.variant == "promote":
            return (
                self._sds(self.state.feature_state),
                self._promote_payload_sds(sig.key[1], sig.key[2]),
            )
        return (
            self._sds(self.state.feature_state),
            self._sds(self.state.params),
            self._sds(self.state.scaler),
            jax.ShapeDtypeStruct(
                (packed_rows(self._key_bits), sig.bucket), jnp.int32),
        )

    def signature_step(self, sig: DispatchSignature):
        """The jitted callable ``sig`` dispatches to (one shared step
        for the single-chip engine plus the compaction/promotion
        variants; the sharded engine overrides with its per-variant
        builds)."""
        if sig.variant == "compact":
            return self._compact
        if sig.variant == "promote":
            return self._promote
        return self._step

    def precompile(self) -> dict:
        """AOT-compile the jitted step for EVERY enumerable signature.

        Iterates :meth:`dispatch_inventory` — the same enumeration the
        device-contract verifier proves coverage over — and
        ``.lower(...).compile()``s each signature from shape-only
        templates (no step executes, no state is touched), so a stream
        that visits a bucket size for the first time mid-serve
        dispatches a ready executable instead of paying a mid-stream
        XLA compile (969 ms measured vs 8 ms steady-state on this
        hardware). Composes with the persistent compilation cache
        (``utils.enable_compilation_cache``): a ``rtfds warmup`` run
        leaves the cache hot for later serving processes too.

        Returns a manifest (bucket sizes, variants, wall seconds) for CLI
        printing. Idempotent — already-compiled keys are skipped.
        """
        t0 = time.perf_counter()
        # Scalar leaves (python floats in some param trees) trace as weak
        # types under jit but compile strong under an SDS; commit them to
        # arrays once so runtime calls match the AOT signature.
        self.state.params = jax.tree.map(jnp.asarray, self.state.params)
        self._aot_params_sig = self._params_sig(self.state.params)
        done = self._compile_signatures(self.dispatch_inventory())
        return {
            "buckets": [sig.bucket for sig in done],
            "variants": 1,
            "seconds": round(time.perf_counter() - t0, 3),
        }

    def _compile_signatures(self, inventory) -> list:
        """``.lower(...).compile()`` every signature of ``inventory``
        that has no executable yet, and return those. The lowering is
        Python and runs here, one signature after another; each lowered
        program's ``compile()`` — XLA's, or the persistent cache's read,
        both of which release the interpreter lock — goes to a small
        pool, so it runs beside the next signature's lowering and beside
        the other compiles. Last of the inventory first: it lists the
        batch buckets in rising order and a program's compile grows with
        its rows, so the longest compile starts first. The same programs
        under the same keys, taken in the inventory's order, as one after
        another; a compile that raises is raised from here, and the
        compiles not yet started are dropped."""
        todo = [sig for sig in inventory if sig.key not in self._aot]
        pool = ThreadPoolExecutor(max_workers=_COMPILE_THREADS,
                                  thread_name_prefix="rtfds-compile")
        try:
            with self.tracer.span("precompile"):
                compiling = {
                    sig.key: pool.submit(self.signature_step(sig).lower(
                        *self.signature_templates(sig)).compile)
                    for sig in reversed(todo)}
                for sig in todo:
                    self._aot[sig.key] = compiling[sig.key].result()
                    self._m_precompiled.inc()
        finally:
            pool.shutdown(wait=True, cancel_futures=True)
        return todo

    def _note_params_swap(self, params):
        """Hot-reload hook: keep AOT serving only while the swapped-in
        params match the precompiled shape family; otherwise drop the
        cache (fall back to jit, which retraces — slower, correct)."""
        self._announce_pallas(params)
        if not self._aot:
            return params
        params = jax.tree.map(jnp.asarray, params)
        if self._params_sig(params) != self._aot_params_sig:
            from real_time_fraud_detection_system_tpu.utils import (
                get_logger,
            )

            get_logger("engine").warning(
                "model reload changed the params shape family; dropping "
                "%d AOT step executables (dispatch falls back to jit — "
                "rerun precompile/warmup for the new shapes)",
                len(self._aot))
            self._aot = {}
            self._aot_params_sig = None
        return params

    def set_shadow(self, shadow) -> None:
        """Attach a shadow scorer (``runtime/learner.ShadowScorer``): the
        candidate dual-scores every emitted batch on the SAME host
        feature rows. Needs the full f32 feature matrix host-side —
        exactly the modes the feedback loop already requires."""
        if self.kind == "sequence":
            raise ValueError(
                "shadow scoring is not wired for kind='sequence' "
                "(no host-side feature matrix to dual-score)")
        if not self.cfg.runtime.emit_features or self._selective:
            raise ValueError(
                "shadow scoring consumes every row's features host-side; "
                "it does not compose with alerts-only or selective "
                "emission")
        if self.cfg.runtime.emit_dtype != "float32":
            raise ValueError(
                "shadow scoring re-consumes the emitted features; "
                "emit_dtype='bfloat16' would drift the candidate's "
                "scores — keep float32")
        self.shadow = shadow

    def clear_shadow(self) -> None:
        self.shadow = None

    def _emit_features_now(self) -> bool:
        """Whether the feature matrix crosses to the host for the batch
        being finished: the static config gate AND the overload ladder's
        dynamic rung-2 degrade (host-side only — the compiled step is
        identical either way, the matrix just stays in HBM unfetched)."""
        return self.cfg.runtime.emit_features and not self._shed_features

    def set_degraded_emission(self, on: bool) -> bool:
        """Overload rung 2: switch to alerts-only emission at runtime.

        Refused (returns False, serving unchanged) when some consumer
        needs host-side feature rows — the cpu oracle, a feedback
        feature cache, selective emission's packed transfer, or the
        sequence kind (already alerts-shaped). Shadow scoring is not a
        blocker: the ladder pauses it at rung 1 before rung 2 can
        degrade emission, and ``_emit_result`` additionally skips it
        while features are shed."""
        if not on:
            self._shed_features = False
            return True
        ok = (self.kind != "sequence"
              and self.cfg.runtime.emit_features
              and not self._selective
              and self.scorer != "cpu"
              and self.feature_cache is None)
        self._shed_features = bool(ok)
        if not ok:
            from real_time_fraud_detection_system_tpu.utils import (
                get_logger,
            )

            get_logger("engine").info(
                "overload rung 2: alerts-only degrade not applicable to "
                "this serving mode (a host-side feature consumer is "
                "wired); batch forcing still applies")
        return self._shed_features

    def _dispatch_step(self, key, jit_fn, *args):
        """Serve from the AOT executable when one exists for ``key``;
        an input-signature rejection permanently falls back to plain jit
        dispatch for the whole cache — correctness first, the
        optimization second. Only PRE-EXECUTION rejections (TypeError/
        ValueError from the compiled call's argument check) fall back:
        they leave the donated buffers intact, so the jit retry is safe.
        A runtime failure (e.g. an XLA OOM mid-execution) propagates
        unwrapped — retrying it on possibly-donated inputs would mask
        the real error behind an 'array deleted' crash."""
        fn = self._aot.get(key) if self._aot else None
        if fn is not None:
            try:
                return fn(*args)
            except (TypeError, ValueError) as e:
                self._m_aot_fallbacks.inc()
                from real_time_fraud_detection_system_tpu.utils import (
                    get_logger,
                )

                get_logger("engine").warning(
                    "AOT step dispatch for %s rejected the call (%s: "
                    "%s); disabling the AOT cache and falling back to "
                    "jit", key, type(e).__name__, str(e)[:200])
                self._aot = {}
        return jit_fn(*args)

    def _zero_features(self, n: int) -> np.ndarray:
        """Per-bucket zero [n, 15] matrix, allocated once and shared
        READ-ONLY across batches. Alerts-only and sequence serving emit a
        definitionally-zero feature matrix every batch — reallocating it
        per batch is pure host-plane overhead (every sink consumer copies
        on use: parquet astype, memory-concat). Write-protected so an
        accidental in-place mutation fails loudly instead of silently
        editing an already-emitted BatchResult."""
        buf = self._zeros_cache.get(n)
        if buf is None:
            buf = np.zeros((n, N_FEATURES), np.float32)
            buf.setflags(write=False)
            self._zeros_cache[n] = buf
        return buf

    def _issue_host_fetch(self, probs, feats) -> Optional[float]:
        """Start device→host copies for exactly the leaves
        ``_finish_batch`` will materialize — probs unless the cpu oracle
        ignores them, the feature matrix only when it actually leaves
        the device (never under alerts-only/sequence; the packed array,
        not the full fallback matrix, under selective emission). Returns
        the issue time for overlap metering, or None when disabled or
        nothing was issued (an array without the async-copy API keeps
        its blocking fetch)."""
        if not self._fetch_overlap:
            return None
        targets = []
        if isinstance(feats, dict):
            # selective emission: the packed array ALREADY carries the
            # probs — fetching handle["probs"] too would re-pay the very
            # padded-batch transfer the packing exists to avoid
            targets.append(feats["packed"])
        else:
            if self.scorer != "cpu":
                targets.append(probs)
            if (feats is not None and self.kind != "sequence"
                    and self._emit_features_now()):
                targets.append(feats)
        issued = False
        for x in targets:
            f = getattr(x, "copy_to_host_async", None)
            if f is None:
                continue
            try:
                f()
                issued = True
            # rtfdslint: disable=broad-exception-catch (copy_to_host_async is a backend-optional API probed per leaf; ANY failure degrades to the blocking fetch — the overlap optimization must never break the fetch itself)
            except Exception:
                return None
        return time.perf_counter() if issued else None

    def _meter_fetch_overlap(self, handle: dict) -> None:
        ti = handle.pop("fetch_issue_t", None)
        if ti is not None:
            self._m_fetch_overlap.inc(
                max(0.0, time.perf_counter() - ti))

    # The single-chip step can take the fused featurize→score kernels;
    # the mesh step (ShardedScoringEngine) only takes the predict swap.
    _FUSED_STEP = True

    def _pallas_choice(self, params):
        """THE gate on ``use_pallas`` → (kernel, refusal).

        ``kernel`` names the Pallas kernel the step serves for ``params``
        (``"fused_logreg"``, ``"fused_forest"``, ``"forest_classify"``)
        or is None for the XLA composition; ``refusal`` is why the
        asked-for (fused) path is NOT what is served, or None. Static
        facts only (config, the params pytree's form and shapes), so it
        answers the same for live arrays and for tracers: the jitted step
        and the swapped predict branch on it at trace time, and
        :meth:`_announce_pallas` reports it — one predicate, so the
        served kernel and the reported one cannot drift."""
        kind = self.kind
        if not self.cfg.runtime.use_pallas:
            return None, None
        if kind not in ("logreg", "tree", "forest", "gbt"):
            return None, f"kind={kind!r} has no Pallas kernel"
        # Both fused featurize→score kernels read gathered hot-tier rows
        # and know nothing of a sketch: CMS sources and the tiered exact
        # store keep the XLA featurize (the classify swap still applies).
        fc = self.cfg.features
        no_fused = None
        if not self._FUSED_STEP:
            no_fused = "the sharded step has no fused featurize kernel"
        elif fc.customer_source != "table":
            no_fused = (f"customer_source={fc.customer_source!r} has its "
                        "own sketch layout")
        elif fc.key_mode == "exact":
            no_fused = ("key_mode='exact' serves admission misses from the "
                        "sketch tier, which the fused kernels do not read")
        if kind == "logreg":
            return (None, no_fused) if no_fused else ("fused_logreg", None)
        if self.scorer == "cpu":
            return None, "scorer=cpu classifies on the host"
        from real_time_fraud_detection_system_tpu.models.forest import (
            GemmEnsemble,
        )
        from real_time_fraud_detection_system_tpu.ops.pallas_forest import (
            admit_block,
        )

        trees = getattr(params, "trees", params)
        # rtfdslint: disable=jit-recompile-hazard (called at trace time by design: isinstance on the params pytree's FORM, not a traced value)
        if not isinstance(trees, GemmEnsemble):
            return None, ("the ensemble is in descent form (too deep for "
                          "the GEMM tables)")
        adm = admit_block(trees, self.z_mode, _PALLAS_BLOCK_BUDGET)
        # rtfdslint: disable=jit-recompile-hazard (admit_block reads static .shape tuples only — the same predicate rtfdsverify proves)
        if not adm.fits:
            return None, (
                f"admit_block refused the tree tables (block "
                f"{adm.block_bytes} B, budget {adm.budget} B, "
                f"aligned={adm.tiles_aligned})")
        if kind == "gbt" or no_fused:  # the predict swap is what is left
            return "forest_classify", None if kind == "gbt" else no_fused
        return "fused_forest", None

    def _announce_pallas(self, params) -> Optional[str]:
        """Ask :meth:`_pallas_choice` and report its answer → the kernel.

        Every consumer of the choice comes through here (engine build,
        params swap, the step and the swapped predict at trace time), so
        the gauge carries the served fact, never the config flag, and an
        asked-for kernel the engine does not serve is said at WARNING with
        the reason — once per change of answer, not once per caller."""
        choice = self._pallas_choice(params)
        # rtfdslint: disable=jit-recompile-hazard (choice is a pair of str/None computed from static facts — see _pallas_choice; reached at trace time by design)
        if choice == getattr(self, "_pallas_said", None):
            return choice[0]
        self._pallas_said = choice
        self._pallas_kernel, refusal = choice
        self._m_use_pallas.set(1.0 if self._pallas_kernel else 0.0)
        # rtfdslint: disable=jit-recompile-hazard (refusal is a str or None, never a traced value)
        if refusal:
            from real_time_fraud_detection_system_tpu.utils import (
                get_logger,
            )

            get_logger("engine").warning(
                "use_pallas was asked but %s: %s",
                "only the classify kernel is served (featurize stays in "
                "XLA)" if self._pallas_kernel else
                "the step serves the XLA composition", refusal)
        return self._pallas_kernel

    def _maybe_use_pallas_forest(self, kind: str) -> None:
        """Swap the tree-ensemble scorer for the Pallas classify kernel
        wherever :meth:`_pallas_choice` says ``"forest_classify"``.

        A pure predict swap: engine state (and checkpoints) keep the
        ``GemmEnsemble``, and the padded kernel tables are re-derived from
        the LIVE params inside the jitted step (µs of pad writes) — so a
        checkpoint restore that overwrites ``state.params`` in place is
        served, never a stale build-time copy.
        """
        if not self.cfg.runtime.use_pallas or kind not in (
                "tree", "forest", "gbt"):
            return  # keep the pallas import lazy for non-ensemble kinds
        from real_time_fraud_detection_system_tpu.ops.pallas_forest import (
            pallas_leaf_sum,
            pallas_predict_proba,
            to_pallas,
        )

        xla_predict = self._predict
        z_mode = self.z_mode

        def _pred(p, x):
            if self._announce_pallas(p) != "forest_classify":
                return xla_predict(p, x)
            if kind == "gbt":
                return jax.nn.sigmoid(
                    p.base_score
                    + pallas_leaf_sum(to_pallas(p.trees, z_mode), x))
            return pallas_predict_proba(to_pallas(p, z_mode), x)

        self._predict = _pred

    def _init_sequence(self, cfg, params, scaler, feature_state,
                       feature_cache):
        """kind='sequence' setup: HistoryState + fused history step.

        The emitted feature matrix is all-zeros ([n, 15]) — the sequence
        scorer consumes raw event channels, not the engineered features;
        the analyzed schema stays stable for sinks/queries."""
        from real_time_fraud_detection_system_tpu.features.history import (
            init_history_state,
            update_and_score,
        )

        if feature_cache is not None:
            # FeedbackLoop scatters into FeatureState.terminal risk
            # windows, which a HistoryState does not have
            raise ValueError(
                "the labeled-feedback loop is not wired for "
                "kind='sequence'")
        self.feature_cache = None
        self._feedback_step = None
        self._state_feedback_step = None
        self._selective = False
        self.selective_overflows = 0
        self.state = EngineState(
            feature_state=feature_state or init_history_state(cfg.features),
            params=params,
            scaler=scaler,
        )
        self._predict = None
        self._loss = None
        fcfg = cfg.features

        def step(hstate, params, scaler, packed):
            with step_scope("unpack"):
                batch = unpack_batch(packed)
            hstate, probs = update_and_score(hstate, params, batch, fcfg)
            feats = jnp.zeros((batch.size, N_FEATURES), jnp.float32)
            return hstate, params, probs, feats

        self._step = jax.jit(step, donate_argnums=self._donate)

    def _start_batch(self, cols: dict) -> dict:
        """Host prep + async device dispatch (does NOT block on results).

        The returned handle holds device futures; :meth:`_finish_batch`
        materializes them. Splitting the two lets :meth:`run` stage batch
        N+1's H2D transfer and dispatch while batch N still computes —
        the double-buffered overlap of SURVEY §2.3 item 3.
        """
        # Latest-wins dedup by tx_id (reference ROW_NUMBER/MERGE semantics,
        # kafka_s3_sink_transactions.py:173-222) on host — tx_ids are
        # int64. The C++ path (native/hostprep.cc) is the same math in
        # one O(n) hash pass + one fused pack pass, bit-identical
        # (differential-pinned); it lifts the host ceiling past what a
        # locally attached chip can consume. NumPy is the fallback.
        with self._phase("host_prep") as prep:
            use_native = native.hostprep_available()
            keep = latest_wins_mask_host(cols["tx_id"], cols["kafka_ts_ms"])
            cols = {k: v[keep] for k, v in cols.items()}
            validate_ingest_rows(cols)
            returning = self._returning_keys(cols)
            n = len(cols["tx_id"])
            self._count_wide_ids(cols)
            pad = bucket_size(n, self.cfg.runtime.batch_buckets)
            if use_native:
                packed = native.pack_rows(
                    cols["tx_datetime_us"], cols["customer_id"],
                    cols["terminal_id"], cols["tx_amount_cents"],
                    cols.get("label"), pad, self._key_bits,
                )
            else:
                packed = pack_batch(make_batch(
                    customer_id=cols["customer_id"],
                    terminal_id=cols["terminal_id"],
                    tx_datetime_us=cols["tx_datetime_us"],
                    amount_cents=cols["tx_amount_cents"],
                    label=cols.get("label"),
                    pad_to=pad,
                    key_bits=self._key_bits,
                ))
            # the phase closes after ALL host packing on both paths, so
            # prep_s/dispatch_s attribute the same stages either way
        promoted = (self._promote_returning(returning)
                    if returning is not None else ())
        pre_state = None
        if self._nan_guard:
            # Donation is off under the guard, so these references stay
            # valid after the step — the rollback anchor for a re-score
            # without the non-finite rows.
            pre_state = (self.state.feature_state, self.state.params,
                         self.state.batches_done, self.state.rows_done)
        with self._phase("dispatch", rows=n, pad=pad) as disp:
            jbatch = jnp.asarray(packed)
            # Steady-state recompile alarm: the signature keys on what
            # the jit cache keys on from the engine's side — the packed
            # batch's (shape, dtype) bucket plus the step's static facts
            # (kind, donation layout, z_mode). A compile observed inside
            # this window after warmup is a retrace paid in the serving
            # loop.
            with self._recompile.step(step_signature(
                    jbatch, static=(self.kind, "donate0", self.z_mode))):
                out = self._dispatch_step(
                    ("step",) + tuple(jbatch.shape), self._step,
                    self.state.feature_state, self.state.params,
                    self.state.scaler, jbatch,
                )
            fstate, params, probs, feats = out[:4]
            tier = out[4] if self._exact else None
            self.state.feature_state = fstate
            self.state.params = params
            self._note_batch_days(cols)
            # Start the D2H copies NOW (they queue behind the step's
            # compute): by the time _finish_batch blocks, the transfer
            # has been running since compute finished.
            t_fetch = self._issue_host_fetch(probs, feats)
        return {"cols": cols, "n": n, "probs": probs, "feats": feats,
                "tier": tier, "t0": prep.t0, "prep_s": prep.seconds,
                "dispatch_s": disp.seconds, "pre_state": pre_state,
                "fetch_issue_t": t_fetch,
                "promote_checks": promoted}

    def _count_wide_ids(self, cols: dict) -> None:
        """Host prep's one look at the ids' width: rows with an id past
        32 bits go on ``rtfds_wide_id_rows_total``; a 32-bit deployment
        that meets one says once what its fold does to it."""
        wide = wide_id_rows(cols["customer_id"], cols["terminal_id"])
        if not wide:
            return
        self._m_wide_ids.inc(wide)
        if self._key_bits == 32 and not self._wide_ids_warned:
            self._wide_ids_warned = True
            from real_time_fraud_detection_system_tpu.utils import (
                get_logger,
            )

            get_logger("engine").warning(
                "%d row(s) of this batch carry a customer or terminal id "
                "that does not fit 32 bits. key_bits=32 xor-folds every "
                "id to one word: two ids whose words xor alike are ONE "
                "key from here on (one window history, one sketch row), "
                "~n^2/2^33 pairs among n keys, and nothing downstream "
                "can tell. Serve wide ids with --key-mode exact "
                "--key-bits 64 (rtfds_wide_id_rows_total counts them)",
                wide)

    def _finish_batch(self, handle: dict) -> BatchResult:
        """Block on the handle's device futures (``device_wait``), then
        build the BatchResult on the host (``fetch``)."""
        n = handle["n"]
        tid = handle.get("trace_id")
        self._meter_fetch_overlap(handle)
        # alerts-only mode (configured, or the overload ladder's rung-2
        # degrade): the feature matrix stays in HBM. The sequence
        # scorer's matrix is definitionally zeros (raw event channels
        # replace engineered features) — never worth a D2H, and the
        # host-side filler is a shared read-only buffer.
        emit = self._emit_features_now() and self.kind != "sequence"
        with self._phase("device_wait", batch=tid):
            # the blocking materialization of exactly the leaves
            # _issue_host_fetch started copying
            if self._selective:
                packed = np.asarray(handle["feats"]["packed"])
            else:
                feats_host = np.asarray(handle["feats"]) if emit else None
                probs_host = (np.asarray(handle["probs"])
                              if self.scorer != "cpu" else None)
        with self._phase("fetch", batch=tid):
            self._check_promotes(handle)
            if self._selective:
                probs_np, feats_np = self._unpack_selective(handle, packed)
                return self._finish_result(handle, probs_np, feats_np)
            if emit:
                # astype: under emit_dtype="bfloat16" the transfer was
                # bf16 (half the bytes); widen back for sinks/consumers
                feats_np = feats_host[:n].astype(np.float32, copy=False)
            else:
                feats_np = self._zero_features(n)
            if self.scorer == "cpu":
                # parity/baseline oracle: host-side pipeline on the same
                # features (sklearn pipeline, or a TrainedModel's
                # pure-NumPy path)
                fn = getattr(self.cpu_model, "predict_proba_np", None) or (
                    self.cpu_model.predict_proba
                )
                probs_np = fn(feats_np.astype(np.float64))
            else:
                probs_np = probs_host[:n]
            return self._finish_result(handle, probs_np, feats_np)

    def _finish_result(self, handle: dict, probs_np: np.ndarray,
                       feats_np: np.ndarray) -> BatchResult:
        """Host-boundary tail shared by every materialize path: run the
        non-finite guard (when on), then emit."""
        if self._nan_guard:
            res = self._quarantine_nonfinite(handle, probs_np, feats_np)
            if res is not None:
                return res
        return self._emit_result(handle, probs_np, feats_np)

    def _quarantine_nonfinite(self, handle: dict, probs_np: np.ndarray,
                              feats_np: np.ndarray):
        """The opt-in data-plane guard (``runtime.nan_guard``): rows whose
        score or emitted feature vector crossed the host boundary
        non-finite are routed to the dead-letter queue
        (``reason=nonfinite``) and the batch is re-scored from the
        pre-batch state WITHOUT them — so a NaN/Inf never lands in the
        running window aggregates, where it would silently poison every
        later batch for that customer/terminal. Returns the clean
        re-scored BatchResult, or None when the batch was already clean.
        Note the guard sees only what crosses the boundary: under
        alerts-only serving that is the scores alone."""
        n = handle["n"]
        bad = ~np.isfinite(probs_np[:n])
        if feats_np is not None and feats_np.shape[0] >= n:
            bad |= ~np.isfinite(feats_np[:n]).all(axis=1)
        if not bad.any():
            return None
        cols = handle["cols"]
        bad_idx = np.flatnonzero(bad)
        self.dead_letter.put_rows(
            {k: np.asarray(v)[bad_idx] for k, v in cols.items()},
            reason="nonfinite",
            error="non-finite feature/score at the host boundary",
            batch_index=self.state.batches_done + 1,
            trace_id=handle.get("trace_id") or "",
        )
        from real_time_fraud_detection_system_tpu.utils import get_logger

        get_logger("engine").warning(
            "nan-guard: %d/%d row(s) produced non-finite outputs; "
            "quarantined to the dead-letter queue and re-scoring the "
            "batch without them", len(bad_idx), n)
        # Roll the engine back to the pre-batch anchor (donation is off
        # under the guard, so the references are intact) and re-run.
        fs, params, b_done, r_done = handle["pre_state"]
        self.state.feature_state = fs
        self.state.params = params
        self.state.batches_done = b_done
        self.state.rows_done = r_done
        good = np.flatnonzero(~bad)
        if len(good) == 0:
            self.state.batches_done += 1
            res = empty_batch_result(self.state.batches_done)
            res.latency_s = time.perf_counter() - handle["t0"] \
                - handle.get("waited", 0.0)
            return res
        h2 = self._start_batch(
            {k: np.asarray(v)[good] for k, v in cols.items()})
        for key in ("index", "trace_id", "source_offsets", "waited", "t0"):
            if key in handle:
                h2[key] = handle[key]
        # recurses through the guard: terminates because each pass
        # strictly shrinks the surviving row set
        return self._finish_batch(h2)

    def _unpack_selective(self, handle: dict, flat: np.ndarray) -> tuple:
        """Decode the packed selective-emission transfer ``flat``.

        One flat f32 fetch carries [probs(pad) | count(1) | idx(cap) |
        feats(cap·15)]. Flagged rows' feature vectors land bit-identical
        to full emission (they ride the packed array as raw f32); rows
        below the threshold carry zeros. A count above the compaction cap
        falls back to fetching that batch's full matrix — still on device
        precisely for this — so correctness never depends on the cap.
        """
        n = handle["n"]
        em = handle["feats"]
        pad = em["full"].shape[0]
        cap = (em["packed"].shape[0] - pad - 1) // (1 + N_FEATURES)
        # copy: a view into the packed fetch would pin the whole
        # pad+1+(1+15)·cap f32 buffer (~MBs/batch at the 262k big-batch
        # cap) for as long as any sink retains BatchResult.probs
        probs_np = flat[:n].copy()
        count = int(flat[pad])
        feats_np = np.zeros((n, N_FEATURES), np.float32)
        if count > cap:
            self.selective_overflows += 1
            feats_np = np.asarray(em["full"])[:n].astype(
                np.float32, copy=False)
        elif count:
            idx = flat[pad + 1:pad + 1 + count].astype(np.int64)
            sel = flat[pad + 1 + cap:pad + 1 + cap + count * N_FEATURES]
            feats_np[idx] = sel.reshape(count, N_FEATURES)
        return probs_np, feats_np

    def _emit_result(self, handle: dict, probs_np: np.ndarray,
                     feats_np: np.ndarray) -> BatchResult:
        """Shared result tail: feature-cache put, counters, BatchResult."""
        cols = handle["cols"]
        n = handle["n"]
        if self.feature_cache is not None and n:
            in_band = cols.get("label")
            self.feature_cache.put_batch(
                cols["tx_id"], feats_np,
                terminal_ids=cols["terminal_id"],
                days=(cols["tx_datetime_us"] // US_PER_DAY).astype(np.int32),
                # In-band labels were already scattered into the risk state
                # by the step; mark them so feedback events can't re-land.
                labeled=(np.asarray(in_band) >= 0)
                if in_band is not None else None,
            )
        if self.shadow is not None and not self.shadow_paused and n:
            # Dual-score the SAME host feature rows with the candidate
            # (runtime/learner.ShadowScorer): one extra jitted predict on
            # a bucket-padded copy — the serving step's compiled program
            # is untouched, so shadow mode can never recompile it.
            with self.tracer.span("shadow_score",
                                  batch=handle.get("trace_id")):
                self.shadow.score_batch(cols["tx_id"], feats_np, probs_np)
        if (self.online_lr > 0.0 and self._loss is not None
                and cols.get("label") is not None
                and (np.asarray(cols["label"]) >= 0).any()):
            # in-step online SGD consumed this batch's in-band labels:
            # the on-device params now lead the last published artifact
            self._online_dirty = True
        tier = handle.get("tier")
        if tier is not None and self._m_tier is not None:
            # [dense, cms] row x keyspace admissions this batch, then
            # the two admits' claim rounds and how many of them ran
            # narrow; the step already materialized, so this tiny fetch
            # is free
            t = np.asarray(tier)
            self._m_tier["dense"].inc(float(t[0]))
            self._m_tier["cms"].inc(float(t[1]))
            self._count_claim_rounds(t[2:4], t[4:6])
            if self._m_alias is not None:
                # key_bits=64: the lookups' [alias rows, verify trips]
                self._m_alias["rows"].inc(float(t[6]))
                self._m_alias["trips"].inc(float(t[7]))
        self.state.batches_done += 1
        self.state.rows_done += n
        self._m_batches.inc()
        self._m_rows.inc(n)
        self._m_last.set(time.time())
        return BatchResult(
            tx_id=cols["tx_id"],
            tx_datetime_us=cols["tx_datetime_us"],
            customer_id=cols["customer_id"],
            terminal_id=cols["terminal_id"],
            amount_cents=cols["tx_amount_cents"],
            features=feats_np,
            probs=probs_np,
            latency_s=0.0,  # _close_batch's to say
            batch_index=self.state.batches_done,
        )

    def _close_batch(self, handle: dict) -> BatchResult:
        """``result_wait`` — the handle's :meth:`_finish_batch` — and what
        follows a finished batch between device steps: the compaction on
        its cadence, the memory gauges, and the batch's latency, which
        ends here."""
        with self._phase("result_wait", batch=handle.get("trace_id")) as wait:
            res = self._finish_batch(handle)
        handle["wait_s"] = wait.seconds
        self._maybe_compact()
        # Device-memory gauges ride the batch cadence; on backends
        # without memory stats (CPU) this is a single boolean check.
        self._devmem.sample()
        res.latency_s = (time.perf_counter() - handle["t0"]
                         - handle.get("waited", 0.0))
        self._m_lat.observe(res.latency_s)
        return res

    def _ensure_layout(self) -> None:
        """Adopt a restored checkpoint written at a different device
        count: ``state.layout_devices`` records the writer's width, and
        the slot layouts are shape-identical permutations — so convert
        (exactly, via the elastic reshard) rather than serve silently
        permuted state."""
        n_old = int(getattr(self.state, "layout_devices", 1) or 1)
        if n_old == 1:
            return
        from real_time_fraud_detection_system_tpu.parallel.mesh import (
            reshard_engine_state,
        )

        self.state.feature_state = jax.tree.map(
            jnp.asarray,
            reshard_engine_state(self.kind, self.state.feature_state,
                                 self.cfg, n_old, 1))
        self.state.layout_devices = 1

    def process_batch(self, cols: dict) -> BatchResult:
        """One micro-batch: dedup → pad → device step → host result."""
        self._ensure_layout()
        tid = self.tracer.begin_batch(self.state.batches_done + 1)
        handle = self._start_batch(cols)
        handle["trace_id"] = tid
        return self._close_batch(handle)

    @property
    def supports_online_sgd(self) -> bool:
        """True for model kinds with a gradient path (logreg/mlp/autoencoder)."""
        return self._loss is not None

    def apply_state_feedback(
        self,
        terminal_ids: np.ndarray,
        days: np.ndarray,
        labels: np.ndarray,
    ) -> None:
        """Land delayed fraud labels in the terminal risk windows.

        The in-state analogue of the reference's delayed terminal-risk
        computation (``feature_transformation.ipynb · cell 25``): fraud
        sums of PAST day buckets change; delay-shifted queries pick them
        up. Model-independent (works for tree kinds too). No-op rows:
        label < 0 (pending) and buckets whose ring slot has already
        advanced past the transaction's day.
        """
        from real_time_fraud_detection_system_tpu.features.online import (
            apply_feedback as state_feedback,
        )

        # labels scatter by slot math — a restored cross-width state must
        # convert BEFORE any scatter, same as the scoring entry points
        self._ensure_layout()
        labels = np.asarray(labels)
        mask = labels >= 0
        if not mask.any():
            return
        if self._state_feedback_step is None:
            fcfg = self.cfg.features

            def sf(fstate, term_key, day, label, valid):
                return state_feedback(
                    fstate, term_key, day, label, valid, fcfg
                )

            self._state_feedback_step = jax.jit(sf, donate_argnums=(0,))
        biggest = max(self.cfg.runtime.batch_buckets)
        t_ids = np.asarray(terminal_ids)[mask]
        d = np.asarray(days)[mask]
        y = labels[mask]
        for s in range(0, len(y), biggest):
            n = len(y[s : s + biggest])
            pad = bucket_size(n, self.cfg.runtime.batch_buckets)
            tk = np.zeros(
                ((2,) if self._key_bits == 64 else ()) + (pad,), np.uint32)
            tk[..., :n] = device_keys(
                host_keys(t_ids[s : s + n], self._key_bits))
            dd = np.zeros(pad, dtype=np.int32)
            dd[:n] = d[s : s + n]
            yy = np.zeros(pad, dtype=np.int32)
            yy[:n] = y[s : s + n]
            valid = np.zeros(pad, dtype=bool)
            valid[:n] = True
            self.state.feature_state = self._state_feedback_step(
                self.state.feature_state, jnp.asarray(tk), jnp.asarray(dd),
                jnp.asarray(yy), jnp.asarray(valid),
            )

    def apply_feedback(self, features: np.ndarray, labels: np.ndarray) -> None:
        """One SGD step from delayed labels (the feedback-topic path,
        BASELINE.json config 4; see ``runtime/feedback.py``).

        ``features`` are RAW feature rows (as cached by the scorer);
        scaling happens inside the jitted update with the engine's scaler,
        so the gradient is on exactly the serving representation.
        """
        if self._loss is None:
            raise ValueError(
                f"model kind {self.kind!r} has no gradient path for "
                "feedback updates"
            )
        lr = self.online_lr or self.cfg.train.online_learning_rate
        if self._feedback_step is None:
            loss = self._loss

            def fb(params, scaler, x_raw, y, valid, lr):
                # Backtracking step: the raw serving features can carry
                # large magnitudes (amounts in cents), so a fixed lr can
                # OVERSHOOT — one step that makes the loss worse, and
                # re-deliveries would compound it. Returning the loss at
                # both ends lets the host halve lr until the step
                # CONTRACTS (classic Armijo-style backtracking); a step
                # that cannot contract is skipped entirely, so the
                # feedback loop is monotone non-increasing by
                # construction.
                x = transform(scaler, x_raw)
                l0 = loss(params, x, y, valid)
                g = jax.grad(loss)(params, x, y, valid)
                new = jax.tree.map(lambda p, gi: p - lr * gi, params, g)
                l1 = loss(new, x, y, valid)
                return new, l0, l1

            self._feedback_step = jax.jit(fb)
        labels = np.asarray(labels)
        total = len(labels)
        if total == 0:
            return
        # A label backlog can exceed the largest jit bucket: chunk it.
        biggest = max(self.cfg.runtime.batch_buckets)
        for s in range(0, total, biggest):
            lab = labels[s : s + biggest]
            n = len(lab)
            pad = bucket_size(n, self.cfg.runtime.batch_buckets)
            x = np.zeros((pad, features.shape[1]), dtype=np.float32)
            x[:n] = features[s : s + n]
            y = np.zeros(pad, dtype=np.int32)
            y[:n] = np.maximum(lab, 0)
            valid = np.zeros(pad, dtype=bool)
            # label < 0 is the 'unlabeled' sentinel everywhere in this
            # codebase (engine step masks it the same way) — never train
            # on it.
            valid[:n] = lab >= 0
            if not valid.any():
                continue
            jx, jy, jv = jnp.asarray(x), jnp.asarray(y), jnp.asarray(valid)
            step_lr = float(lr)
            for _ in range(8):  # halvings; lr is a traced arg: no retrace
                new_params, l0, l1 = self._feedback_step(
                    self.state.params, self.state.scaler, jx, jy, jv,
                    jnp.float32(step_lr),
                )
                if bool(l1 <= l0):
                    self.state.params = new_params
                    # the on-device params now lead the last published
                    # artifact: a wholesale reload would clobber this
                    self._online_dirty = True
                    break
                step_lr *= 0.5
            # 8 failed halvings: the chunk cannot contract from here
            # (already at a minimum for these labels) — skip it rather
            # than apply a step that provably makes the model worse

    def run(
        self,
        source,
        sink=None,
        max_batches: int = 0,
        checkpointer=None,
        trigger_seconds: Optional[float] = None,
        heartbeat=None,
        feedback=None,
        model_reload=None,
        learning=None,
    ) -> dict:
        """Stream until the source is exhausted (or max_batches).

        ``feedback`` (a :class:`~.feedback.FeedbackLoop`) is polled once
        per finished batch, BETWEEN device steps — the single-threaded
        contract the loop requires (its updates touch
        ``state.params``/``state.feature_state``). This closes BASELINE
        config 4 in serving: delayed fraud labels land in the terminal
        risk windows and (for differentiable models) drive online SGD
        while the stream keeps scoring.

        The loop is software-pipelined to ``runtime.pipeline_depth``
        batches in flight: batch N+k is polled, host-prepped,
        ``device_put`` and dispatched while batch N's device step still
        runs — H2D and dispatch overhead overlap compute (SURVEY §2.3
        item 3; depth 2 is classic double-buffering, deeper depths keep
        the device fed when per-dispatch overhead exceeds step
        compute). ``runtime.coalesce_rows`` further
        merges consecutive polls into one device batch. The pipeline
        drains to depth 0 before every checkpoint save, so a saved
        (offsets, state) pair never includes an in-flight batch's effects
        (a replay after restore would double-apply them otherwise).

        ``sink.append`` runs on ONE ordered writer thread that this call
        owns (:class:`~..io.sink.AsyncSink` around the sink it was given,
        started below and ended in the ``finally``; a sink that already
        is one is unwrapped, not wrapped twice). A batch is acknowledged
        when the inner ``append`` returned, there. Before a poll the loop
        waits for the writer to go idle unless the source showed a
        backlog (:class:`PollAhead`: the batches last launched filled the
        largest bucket): without one, polling earlier would only freeze
        the next batch's rows sooner (write, then poll: the inline
        order); with one the loop polls at once and the write overlaps
        the poll, the prep, the dispatch and the chip. The writer is
        drained before every checkpoint save and before this returns;
        ``state.offsets`` may lead durable output only between two
        drains.

        ``heartbeat`` (a :class:`~.faults.Heartbeat`) is beaten once per
        loop pass — including idle polls — so a watchdog can tell a quiet
        stream from a silently hung source or device step.

        Returns run stats (rows, batches, throughput, latency percentiles).
        """
        self._ensure_layout()  # cross-width checkpoint restores convert
        # Restored state carries cold-tier segment lineage: reconcile the
        # host store to it (prune post-checkpoint segments, fence the
        # promoter) BEFORE any batch can touch a demoted key.
        self._sync_cold_after_restore()
        if (self._cold is not None and self._cold.ephemeral
                and checkpointer is not None):
            raise ValueError(
                f"cold_store={self._cold.url!r} is a fresh store that is "
                "gone with this process: a checkpoint's cold lineage "
                "could not be restored from it. Give --cold-store a "
                "directory or an s3:// location to run with checkpoints")
        if self.cfg.runtime.precompile and not self._aot:
            # AOT bucket precompilation: every bucket size compiles NOW,
            # before the first poll — no first-touch compile ever lands
            # mid-stream (rtfds_xla_recompiles_total stays 0).
            self.precompile()
        if learning is not None:
            # Continuous-learning controller (runtime/learner.py):
            # installs the shadow scorer + learner tap now, then gets
            # polled once per finished batch (after feedback, before the
            # checkpoint — the same between-device-steps contract).
            learning.attach(self)
        trigger = (
            self.cfg.runtime.trigger_seconds
            if trigger_seconds is None
            else trigger_seconds
        )
        every = self.cfg.runtime.checkpoint_every_batches
        # The nan-guard's rollback-and-rescore is only sound when no later
        # batch has been dispatched from the (possibly contaminated)
        # state — the guard serializes the pipeline. Documented cost of
        # the opt-in.
        depth = 1 if self._nan_guard else max(
            1, self.cfg.runtime.pipeline_depth)
        coalesce = self.cfg.runtime.coalesce_rows
        # Per-run percentile trackers (bounded reservoirs, exact within
        # the window) — the run-report twin of the process-lifetime
        # rtfds_phase_seconds registry histograms.
        trackers = {
            "latency": LatencyTracker(),
            "host_prep": LatencyTracker(),
            "dispatch": LatencyTracker(),
            "result_wait": LatencyTracker(),
            "sink_wait": LatencyTracker(),
            "sink_write": LatencyTracker(),
        }
        auto = None
        if self.cfg.runtime.autobatch:
            from real_time_fraud_detection_system_tpu.runtime.autobatch \
                import AutoBatchController

            auto = AutoBatchController(
                self.cfg.runtime.batch_buckets,
                latency_slo_ms=self.cfg.runtime.latency_slo_ms,
                registry=self.metrics)
        recorder = self.recorder if self.recorder is not None \
            else active_recorder()
        overload = None
        if self.cfg.runtime.overload.enabled:
            # Overload-survival ladder (runtime/overload.py): the
            # controller decides from registry signals; these closures
            # are the engine-side effects of each rung, all reversible.
            from real_time_fraud_detection_system_tpu.runtime.overload \
                import LadderActions, OverloadController

            ocfg = self.cfg.runtime.overload

            def _act_shed_optional(on: bool) -> None:
                # rung 1: optional work off the stream — shadow scoring
                # and learner training pause through their existing
                # hooks; the flight recorder thins to sampled records
                self.shadow_paused = bool(on)
                if learning is not None:
                    if on:
                        learning.pause()
                    else:
                        learning.resume()
                if recorder is not None:
                    recorder.set_sample_every(
                        ocfg.recorder_sample_every if on else 1)

            def _act_degrade_emission(on: bool) -> None:
                # rung 2: alerts-only emission, host-side only (the
                # compiled step — and dispatch_inventory() — unchanged)
                self.set_degraded_emission(on)

            def _act_force_max(on: bool) -> None:
                # rung 2: pin autobatch to the largest AOT bucket
                if auto is not None:
                    if on:
                        auto.force_max()
                    else:
                        auto.release_force()

            overload = OverloadController(
                self.cfg.runtime, registry=self.metrics,
                actions=LadderActions(
                    shed_optional=_act_shed_optional,
                    degrade_emission=_act_degrade_emission,
                    force_max_batch=_act_force_max),
                recorder_fn=lambda: recorder)
        self._trackers = trackers
        tracer = self.tracer
        # Source-poll time since the last finished batch — attributed to
        # the NEXT batch's flight record so per-batch phases sum to the
        # loop's wall time (minus trigger pacing, reported separately).
        # Likewise the time the loop thread was blocked on its writer.
        # "handed": batches given to the writer since the last poll.
        pending = {"poll_s": 0.0, "sink_wait_s": 0.0, "handed": 0}
        t_start = time.perf_counter()
        # CPU time of the serving loop proper (precompile excluded —
        # the AOT block above ran before this line). rows / cpu_s is the
        # load-immune per-process rate tools/multihost_launcher.py
        # reports: on shared CI cores, wall-clock rows/s of N
        # concurrent processes measures the box, not the coordination
        # cost this repo is accountable for.
        t_cpu0 = time.process_time()
        rows0 = self.state.rows_done  # report THIS run's throughput, not
        batches0 = self.state.batches_done  # lifetime totals (warmup runs)
        ovf0 = self.selective_overflows
        degraded0 = len(self._degraded_keys)
        from collections import deque

        # rtfdslint: disable=unbounded-queue (loop-local in-flight handle FIFO, drained to below pipeline_depth on every dispatch (`while len(q) >= depth: _finish`) — bounded at `depth` by construction; a maxlen would silently drop dispatched device work)
        q: deque = deque()  # in-flight batch handles, FIFO
        if feedback is not None and checkpointer is not None:
            # Feedback offsets must TRAIL the state checkpoint (the same
            # invariant as the source commit below): defer the loop's
            # broker commits to the checkpoint cadence.
            feedback.auto_commit = False

        def _write(inner, res, ctx, t_queued) -> None:
            # WRITER THREAD. The acknowledgement is this append's return;
            # the phase is timed here, where the write runs, so
            # sink_write reads the write and never an enqueue — that wait
            # is writer_queue, from the enqueue's return to this start.
            trace_id, record = ctx
            tracer.set_role("writer")
            with self._phase("sink_write", batch=trace_id) as write:
                if t_queued is None or t_queued > write.t0:
                    t_queued = write.t0  # taken off the queue at once
                tracer.add_span("writer_queue", t_queued, write.t0,
                                batch=trace_id, parent=0)
                self._m_phase["writer_queue"].observe(write.t0 - t_queued)
                inner.append(res)
            if record is not None:
                record(write.seconds)

        writer = None
        if sink is not None:
            inner = sink
            if isinstance(sink, AsyncSink):
                sink.drain()
                inner = sink.inner
            writer = AsyncSink(
                inner, max_queue=self.cfg.runtime.sink_queue_batches,
                registry=self.metrics, write=_write)
        rule = PollAhead(max(self.cfg.runtime.batch_buckets))

        def _sink_blocked(name: str, fn, *args, batch=None) -> None:
            # the loop thread waiting on its writer, under the span that
            # says at which point of the pass; together: sink_wait
            with self._phase(name, batch=batch) as blocked:
                fn(*args)
            pending["sink_wait_s"] += blocked.seconds

        def _join_writer() -> None:
            # an idle writer is not waited for (drain() then only
            # re-raises a failure it parked): no span a quiet pass
            if writer.idle:
                writer.drain()
            else:
                _sink_blocked("sink_join", writer.drain)

        hooks = (feedback is not None or model_reload is not None
                 or learning is not None)

        def _run_hooks(res) -> None:
            if feedback is not None:
                # Between-batch label application (before the checkpoint,
                # so saved state includes the landed labels).
                applied = feedback.poll_and_apply()
                if recorder is not None and applied:
                    recorder.record_event("feedback", applied=applied,
                                          batch=res.batch_index)
            if model_reload is not None:
                # Hot model swap (the reference restarts the Spark job to
                # pick up a retrained pickle; here the loop swaps weights
                # between device steps — same single-threaded contract as
                # feedback). The callable returns None (no change) or
                # (params, scaler) ready for the engine's kind; a shape
                # change simply retraces the jitted step. Eventual-swap
                # semantics: up to pipeline_depth batches already in
                # flight complete on the old weights.
                swap = model_reload()
                if swap is not None:
                    new_params, new_scaler = swap
                    # Reload × online SGD: a wholesale swap discards any
                    # on-device SGD updates accumulated since the last
                    # swap/artifact. That used to be a one-time startup
                    # warning; now EVERY swap is counted by outcome, so
                    # the operator can see exactly how many reloads
                    # clobbered learned updates.
                    outcome = ("clobbered_online_updates"
                               if self._online_dirty else "clean")
                    self._m_reloads[outcome].inc()
                    self._online_dirty = False
                    self.state.params = self._note_params_swap(new_params)
                    if new_scaler is not None:
                        self.state.scaler = new_scaler
                    if recorder is not None:
                        recorder.record_event("model_reload",
                                              outcome=outcome)
                    if learning is not None:
                        # a reload is a versioned event: register the
                        # swapped params in the registry lineage
                        # (publish + promote, source=reload)
                        learning.note_external_swap(
                            self.state.params, self.state.scaler, outcome,
                            engine=self)
            if learning is not None:
                # candidate install / promotion / rollback decisions ride
                # the batch cadence, between device steps
                learning.on_batch(self)

        def _checkpoint() -> None:
            # Drain the writer BEFORE the state save: checkpointed
            # offsets must TRAIL durable sink output (a crash then
            # replays rows into parts that already landed — the
            # exactly-once overwrite — never records progress for
            # writes still sitting in a queue).
            if writer is not None:
                _join_writer()
            if self._cold is not None:
                # Buffered demotions become durable segments NOW so
                # the lineage the checkpoint records is on disk, and
                # restore can rebuild the exact cold index from
                # manifests alone.
                self._settle_cold()
            self._maybe_exchange_cms()
            checkpointer.save(self.checkpoint_state())
            # Broker-side offsets (sources that have them, e.g. Kafka)
            # are committed only AFTER the framework checkpoint lands:
            # they trail it, never lead, so a crash replays — never
            # skips — rows. Same for consumed feedback labels.
            commit = getattr(source, "commit", None)
            if commit is not None:
                commit()
            if feedback is not None:
                feedback.commit()
            if self._cold is not None:
                # Only after the checkpoint (and its offset commits)
                # landed is it safe to delete fully-promoted
                # segments: a crash before this point restores a
                # lineage that still lists them.
                self._cold.gc()

        def _finish(handle: dict) -> None:
            # the handle's own trace id: with pipeline_depth > 1 it is
            # OLDER than the tracer's current batch
            tid = handle.get("trace_id")
            res = self._close_batch(handle)
            trackers["latency"].record(res.latency_s, rows=len(res.tx_id))
            self.state.offsets = handle["source_offsets"]
            record = None
            if recorder is not None:
                extra = {}
                if handle.get("trace_id"):
                    # cross-reference: a slow batch in the flight record
                    # names its span waterfall in the exported trace
                    extra["trace_id"] = handle["trace_id"]
                # Loop-time decomposition: host prep (dedup + pad) vs
                # H2D + dispatch (the per-step overhead pipelining
                # hides) vs the result wait (device compute minus
                # overlap) — the phases' own readings.
                phases = {"source_poll": pending["poll_s"],
                          "host_prep": handle.get("prep_s", 0.0),
                          "dispatch": handle.get("dispatch_s", 0.0),
                          "result_wait": handle["wait_s"]}
                pending["poll_s"] = 0.0
                depth_now = len(q)

                def record(sink_s: Optional[float] = None) -> None:
                    # with a sink: on the writer thread, once the write
                    # has a duration (the loop thread's phases were
                    # fixed before the enqueue)
                    if sink_s is not None:
                        phases["sink_write"] = sink_s
                    recorder.record_batch(
                        res.batch_index, len(res.tx_id), phases,
                        queue_depth=depth_now, latency_s=res.latency_s,
                        **extra)
            if writer is not None:
                # What the loop thread was blocked on its writer since
                # the last batch went to it — the join before a poll, a
                # checkpoint's drain, that enqueue on a full queue — is
                # this batch's sink_wait (the next-batch attribution of
                # poll_s above).
                sink_wait_s = pending["sink_wait_s"]
                pending["sink_wait_s"] = 0.0
                self._m_phase["sink_wait"].observe(sink_wait_s)
                trackers["sink_wait"].record(sink_wait_s)
                if record is not None:
                    phases["sink_wait"] = sink_wait_s
                _sink_blocked("sink_enqueue", writer.append, res,
                              (tid, record), batch=tid)
                self._m_sink_batches.inc()
                pending["handed"] += 1
            elif record is not None:
                record()
            if auto is not None:
                auto.observe(len(res.tx_id), res.latency_s)
            if overload is not None:
                rr = handle.pop("overload_replay_rows", None)
                if rr is not None:
                    # counted at FINISH: replay accounting reflects
                    # state updates that landed, not dispatches
                    overload.note_replayed(rr)
                overload.observe_batch(len(res.tx_id), res.latency_s)
            if hooks:
                # feedback, model reload and the learner ride the batch
                # cadence between device steps: one span for the three
                with self._phase("hooks", batch=tid):
                    _run_hooks(res)
            if checkpointer is not None and self.state.batches_done % every == 0:
                with self._phase("checkpoint", batch=tid):
                    _checkpoint()
            # NOTE: trigger pacing used to sleep HERE, once per finished
            # handle — so _drain() stacked one sleep per queued batch
            # before every checkpoint/idle flush. Pacing now happens once
            # per loop pass on the poll side (see the main loop).

        def _next_id() -> Optional[str]:
            # the trace id of the batch the loop will launch next
            return (f"b{self.state.batches_done + len(q) + 1:08d}"
                    if tracer.enabled else None)

        def _add_wait(dt: float) -> None:
            # Waiting for the NEXT batch to arrive is not part of any
            # in-flight batch's processing latency — subtract it so the
            # reported percentiles (and trigger pacing) measure the
            # pipeline, not source quiescence.
            for h in q:
                h["waited"] = h.get("waited", 0.0) + dt

        def _drain() -> None:
            while q:
                _finish(q.popleft())

        def _poll():
            if writer is not None:
                # The join rule (PollAhead): no backlog → the inline
                # order, write then poll; a backlog → poll now.
                if not rule.ahead:
                    _join_writer()
                elif pending["handed"]:
                    self._m_sink_overlapped.inc(pending["handed"])
                pending["handed"] = 0
            # Attribute the poll to the batch that will CONSUME it (the
            # same next-batch attribution the flight record uses via
            # pending["poll_s"]): begin_batch(idx) only runs after the
            # poll returns, so the current trace id here is still the
            # PREVIOUS batch's.
            with self._phase("source_poll", batch=_next_id()) as poll:
                c = source.poll_batch()
                if c is not None and not len(next(iter(c.values()), ())):
                    # nothing arrived: the pass it belongs to is a
                    # `pace`, and a quiet source's polls leave no span
                    # each (the histogram still counts them)
                    poll.span.cancel()
            _add_wait(poll.seconds)
            pending["poll_s"] += poll.seconds
            return c

        def _launch(cols, offs, replay_rows=None) -> None:
            """Dispatch one assembled batch into the pipeline (shared by
            live traffic and overload replay — a replayed deferred batch
            takes EXACTLY the live path, so its state updates and sink
            lineage are indistinguishable from never having deferred)."""
            nonlocal t_last_start
            if checkpointer is not None and any(
                h["index"] % every == 0 for h in q
            ):
                # A queued batch's completion will checkpoint: drain
                # first so no newer batch is in flight at save time.
                _drain()
            idx = self.state.batches_done + len(q) + 1
            tid = self.tracer.begin_batch(idx)
            rule.launched(len(next(iter(cols.values()), ())),
                          carry is not None)
            handle = self._start_batch(cols)
            t_last_start = time.perf_counter()
            handle["index"] = idx
            handle["trace_id"] = tid
            handle["source_offsets"] = offs
            if replay_rows is not None:
                handle["overload_replay_rows"] = replay_rows
            q.append(handle)
            self._m_qdepth.set(len(q))
            while len(q) >= depth:
                _finish(q.popleft())
                self._m_qdepth.set(len(q))

        exhausted = False
        capped = False  # max_batches stopped the run (resumable break)
        carry = None  # (cols, offsets): a poll beyond the coalesce cap
        cap = max(self.cfg.runtime.batch_buckets)
        t_last_start = None  # previous batch's dispatch time (pacing)

        def _pass(lap: _Phase) -> bool:
            """One pass of the loop; → False to leave it."""
            nonlocal exhausted, capped, carry
            if heartbeat is not None:
                heartbeat.beat()
            started = self.state.batches_done + len(q)
            if max_batches and started >= max_batches:
                capped = True
                return False
            if self.stop_event is not None and self.stop_event.is_set():
                # Coordinated drain (fleet resize / graceful SIGTERM):
                # stop at a batch boundary with the capped-run tail —
                # deferred/shed batches stay behind the checkpointed
                # offsets by the defer() contract, so the caller's final
                # checkpoint resumes them exactly-once under the next
                # topology instead of force-draining them here.
                capped = True
                return False
            if trigger > 0 and t_last_start is not None:
                # Trigger pacing, once per loop pass on the POLL side:
                # batch starts stay >= trigger apart while already-
                # dispatched batches keep computing through the sleep.
                # (Pacing used to run inside _finish, stacking one sleep
                # per queued handle on every drain.) The slept time is
                # credited as wait so in-flight latencies measure the
                # pipeline, not the pacing.
                dt = trigger - (time.perf_counter() - t_last_start)
                if dt > 0:
                    with self._phase("pace"):
                        # rtfdslint: disable=blocking-call-on-loop-thread (sanctioned pacing wait point: --trigger-interval spacing on the poll side, slept time credited as wait; regression-pinned in test_runtime trigger-pacing tests)
                        time.sleep(dt)
                    _add_wait(dt)
            if overload is not None and overload.want_replay():
                # Descending from rung 3 (or the spill hit its memory
                # cap): the deferred FIFO's head replays through the
                # normal scoring path BEFORE any live poll — rows reach
                # the feature state in exactly the order a
                # never-overloaded run would have seen them.
                item = overload.next_replay()
                if item is not None:
                    _launch(item.cols, item.offsets,
                            replay_rows=item.rows)
                    return True
            if carry is not None:
                cols, offs = carry
                carry = None
            else:
                cols = _poll()
                if cols is None:
                    return False
                if len(next(iter(cols.values()), ())) == 0:
                    # Idle live source (e.g. KafkaSource on a quiet
                    # topic): not a batch — no sink append, no step, no
                    # checkpoint cadence, no max_batches consumption.
                    # Flush the in-flight batches (their results must not
                    # wait for future traffic), then wait a trigger. The
                    # pass is a `pace`, one span with its like.
                    lap.fold("pace")
                    _drain()
                    if overload is not None:
                        # the quiet period is the ladder's recovery
                        # window: tick the controller so descend dwell
                        # accumulates and deferred batches replay even
                        # if live traffic never returns
                        overload.idle_tick()
                    if trigger > 0:
                        # rtfdslint: disable=blocking-call-on-loop-thread (sanctioned wait point: idle live source with nothing in flight — sleeping one trigger IS the correct behavior, there is no work to stall)
                        time.sleep(trigger)
                    return True
                offs = list(source.offsets)
            # The adaptive controller overrides the static coalesce
            # target while active (it only MERGES small polls upward —
            # an oversized poll still bucket-pads as before).
            assemble = auto.target_rows() if auto is not None else coalesce
            if assemble > 0:
                # Never assemble past the largest jit bucket: a poll that
                # would overflow is carried into the NEXT batch, and its
                # rows stay excluded from this batch's checkpoint offsets
                # (a crash must replay them, not skip them).
                target = min(assemble, cap)
                parts = [cols]
                total = len(next(iter(cols.values())))
                while total < target:
                    more = _poll()
                    if more is None:
                        exhausted = True  # serve the tail, then stop
                        break
                    m = len(next(iter(more.values()), ()))
                    if m == 0:
                        break  # idle: serve what we have now
                    if total + m > cap:
                        carry = (more, list(source.offsets))
                        break
                    parts.append(more)
                    total += m
                    offs = list(source.offsets)
                if len(parts) > 1:
                    cols = {k: np.concatenate([p[k] for p in parts])
                            for k in parts[0]}
            if overload is not None and overload.should_defer():
                # Rung 3 admission control: the whole assembled batch
                # defers to the durable spill instead of dispatching. It
                # consumes no batch_index (sink lineage stays gap-free)
                # and state.offsets stays at the last SCORED batch, so a
                # crash replays deferred rows from the checkpoint.
                # Batches dispatched BEFORE the climb finish first —
                # rung 3 holds nothing in flight, so their results land
                # instead of idling in the pipeline behind the deferral.
                _drain()
                overload.defer(cols, offs)
                return True
            _launch(cols, offs)
            return True

        def _stream() -> None:
            while not exhausted:
                # one pass under one span, so that every instant of
                # run() has a parent
                with self._phase("loop_pass", batch=_next_id()) as lap:
                    if not _pass(lap):
                        break
            if overload is not None and not capped:
                # Source exhausted with batches still deferred: the
                # stream must not end owing rows — force-drain the FIFO
                # through the normal scoring path (scored == polled).
                # A max_batches stop is different: the cap wins, and the
                # deferred rows stay durably spilled with state.offsets
                # still BEHIND them, so a resumed run re-polls them.
                overload.finish_stream()
                while True:
                    if heartbeat is not None:
                        # a large deferred backlog drains for minutes —
                        # beat per replayed batch so the stall watchdog
                        # can tell this healthy drain from a wedge
                        heartbeat.beat()
                    item = overload.next_replay()
                    if item is None:
                        break
                    _launch(item.cols, item.offsets,
                            replay_rows=item.rows)
            _drain()
            # The writer drains before run() returns: the caller's
            # follow-up (final checkpoint save, offset commits, reading
            # the output) must see fully-landed writes, and a deferred
            # writer error must surface in THIS run, with its own type.
            if writer is not None:
                _join_writer()

        # `run` is the root of this run's span tree; the loop thread's
        # role is set here, the writer's in _write
        role = tracer.set_role("loop")
        try:
            with self._phase("run"):
                try:
                    _stream()
                finally:
                    if overload is not None:
                        # revert every engine-side degrade so a later
                        # run() on this engine starts clean (rung metrics
                        # stay honest)
                        overload.deactivate()
                    if writer is not None:
                        # no thread outlives a run; on the way out of an
                        # exception the queued writes still land (the
                        # restore fence or the replay's overwrite deals
                        # with them) and the loop's exception stays the
                        # one that propagates
                        writer.stop()
                self._m_qdepth.set(0)
                if self._cold is not None:
                    # Persist buffered demotions so the caller's
                    # follow-up save records fresh segment lineage.
                    with self._phase("cold_settle"):
                        self._settle_cold()
        finally:
            tracer.set_role(role)
            self._trackers = {}
        wall = time.perf_counter() - t_start
        cpu_s = time.process_time() - t_cpu0
        # LatencyTracker-backed snapshots: exact percentiles over the
        # bounded recent window (identical to the old full-list math for
        # runs under the window size, O(1) memory beyond it).
        snaps = {k: t.snapshot() for k, t in trackers.items()}
        stats = {
            "rows": self.state.rows_done - rows0,
            "batches": self.state.batches_done - batches0,
            "wall_s": wall,
            "cpu_s": cpu_s,
            "rows_per_s": (
                (self.state.rows_done - rows0) / wall if wall > 0 else 0.0
            ),
            "latency_p50_ms": snaps["latency"].get("p50_ms", 0.0),
            "latency_p99_ms": snaps["latency"].get("p99_ms", 0.0),
            "host_prep_p50_ms": snaps["host_prep"].get("p50_ms", 0.0),
            "dispatch_p50_ms": snaps["dispatch"].get("p50_ms", 0.0),
            "result_wait_p50_ms": snaps["result_wait"].get("p50_ms", 0.0),
            # the loop thread blocked on its writer, and the write itself
            # (timed on the writer thread): per batch
            "sink_wait_p50_ms": snaps["sink_wait"].get("p50_ms", 0.0),
            "sink_write_p50_ms": snaps["sink_write"].get("p50_ms", 0.0),
            "pipeline_depth": depth,
            # the z-contraction mode the serving step compiled with —
            # the run-report twin of the rtfds_z_mode gauge
            "z_mode": self.z_mode,
        }
        if auto is not None:
            stats["autobatch_target_rows"] = auto.target_rows()
            stats["autobatch_adjustments"] = auto.adjustments
        if self._selective:
            # per-run delta, like rows/batches — nonzero tells the
            # operator the threshold/cap calibration is sending full
            # fetches (correct output, just slower; recalibrate
            # emit_threshold or raise emit_cap_fraction)
            stats["selective_overflows"] = self.selective_overflows - ovf0
        if self._cold is not None:
            # Returning keys scored from the CMS sketch because their
            # cold rows were lost to a corrupt segment — the honest scope
            # of the exactness claim. A sound store reads 0: every
            # returning key is promoted before its row is scored.
            stats["exactness_degraded_keys"] = (
                len(self._degraded_keys) - degraded0)
        return stats
