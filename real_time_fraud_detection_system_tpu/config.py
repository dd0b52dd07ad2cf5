"""Single typed configuration for the whole framework.

The reference scatters constants across every job (S3 creds + catalog URIs
duplicated in ``pyspark/scripts/fraud_detection.py:15-23`` and each
``kafka_s3_sink_*.py:7-15``; SparkConf blocks copy-pasted per job). Here one
frozen dataclass tree is the only source of truth, built once and threaded
through every layer.

Canonical feature definitions
-----------------------------
The reference disagrees with itself about two of the 15 model features:

- night: offline training uses ``hour <= 6``
  (``feature_transformation.ipynb · cell 12``) but online serving uses
  ``hour >= 20`` (``fraud_detection.py:104``);
- weekend: offline uses python ``weekday() >= 5`` (Sat/Sun) but online uses
  Spark ``dayofweek() >= 5`` (Thu/Fri/Sat, since Spark's Sunday==1).

Training/serving skew is a bug, not a behavior to reproduce. This framework
uses ONE definition everywhere — the offline one that the model was actually
trained with: ``is_night = hour <= night_end_hour (6)`` and
``is_weekend = weekday >= 5`` with Monday==0. Both are configurable below.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Sequence


@dataclass(frozen=True)
class DataConfig:
    """Synthetic data generator knobs (reference ``data_generator.ipynb · cell 34``)."""

    n_customers: int = 5000
    n_terminals: int = 10000
    n_days: int = 245
    radius: float = 5.0
    start_date: str = "2025-04-01"
    seed: int = 0
    # Fraud scenarios (reference ``data_generator.ipynb · cell 42``).
    scenario1_amount_threshold: float = 220.0
    scenario2_terminals_per_day: int = 2
    scenario2_compromise_days: int = 28
    scenario3_customers_per_day: int = 3
    scenario3_compromise_days: int = 14
    scenario3_amount_multiplier: float = 5.0
    scenario3_fraction: float = 1.0 / 3.0


@dataclass(frozen=True)
class FeatureConfig:
    """Stateful windowed feature computation.

    Windows and delay follow ``feature_transformation.ipynb · cells 17,25``:
    customer {1,7,30}-day count+avg-amount; terminal {1,7,30}-day count+risk
    shifted back by ``delay_days`` (fraud labels arrive late).
    """

    windows: Sequence[int] = (1, 7, 30)
    delay_days: int = 7
    # Day-bucket ring buffers must cover delay + max(window) days of history.
    n_day_buckets: int = 40
    # Dense per-key state capacity (power of 2).
    customer_capacity: int = 8192
    terminal_capacity: int = 16384
    # Slot placement: "direct" (key & (cap-1)) is collision-free for dense
    # serial PKs (the reference's SERIAL ids, postgres/init.sql) as long as
    # capacity >= #keys; "hash" mixes first — use for sparse/adversarial key
    # spaces (collisions then merge keys, CMS bounds the error story);
    # "exact" routes through the on-device key directory (ops/keydir.py):
    # the hot tier is sized to the ACTIVE WORKING SET (capacity = hot-tier
    # slots, decoupled from the key universe), admitted keys are
    # collision-exact, and rows that miss admission are served from the
    # count-min sketch tier (overestimate-only degradation, observable via
    # rtfds_feature_tier_rows_total).
    key_mode: str = "direct"
    # The width of an id as the device sees it, a stated, static property
    # of a deployment (the programs are compiled before a row is seen).
    # 32: the host xor-folds every int64 id to one uint32 word
    # (core/batch.fold_key) — exact for ids under 2^32 (SERIAL keys); two
    # wider ids whose words xor alike are ONE key from there on (counted:
    # rtfds_wide_id_rows_total, and warned about once). 64: the id travels
    # as its two uint32 words — the batch, the directory, the sketches,
    # the cold store and a checkpoint carry the whole key, and two ids
    # share state only if all 64 bits agree (16-digit card numbers, a
    # 64-bit merchant hash, BIGSERIAL). Needs key_mode="exact": a direct
    # table would need capacity above the highest id, and a hashed one
    # merges keys by design. One bit pattern is reserved at 64,
    # 0xFFFFFFFF_FFFFFFFF (int64 -1, the cold tier's padding lane): a row
    # that carries it is never admitted to the hot tier and is served
    # from — and counted on — the sketch tier.
    key_bits: int = 32
    # key_mode="exact" knobs: fixed probe depth P of the directory's double
    # hashing (the directory has D = 2x the slot capacity entries), and the
    # recency-compaction cadence. A key misses admission FOR GOOD — it is
    # served from the sketch tier until a compaction frees one of its
    # positions — when all P of its probe positions are taken, so filling
    # the directory to load alpha loses D * alpha^(P+1) / (P+1) keys in
    # expectation (tests/test_keydir.py holds the count to that): occupancy
    # and P are chosen TOGETHER. Worked, D = 2^23 (2^22 slots): three
    # quarters of the slots live (alpha 0.375) at P = 8 -> 137 keys on the
    # sketch; half the slots (alpha 0.25) at P = 8 -> 3.6; alpha 0.375 at
    # P = 16 -> 0.03; alpha 0.25 at P = 16 -> 3e-5 (what the benchmark's
    # forest-rf100-d8-exact states, since its limit on wrong answers is 0).
    # The default 8 is for a deployment that tolerates a few keys on the
    # sketch. Whether or not the sketch tier serves any row, EVERY row
    # updates both sketches and reads them (README, "Feature-state
    # playbook": what that costs on the chip). Compaction — every
    # N batches a pass reclaims slots whose newest bucket_day is older
    # than delay_days + max(windows) (dead history: no query can ever
    # see it), at a cost that follows what it vacates. 0 = compaction
    # off.
    keydir_probes: int = 8
    compact_every: int = 0
    # HBM budget for the whole feature state (dense tier + directory +
    # sketches), validated at ENGINE BUILD against the static
    # state_bytes() accounting — a config that cannot fit fails fast
    # instead of OOMing mid-stream. 0 = no budget check.
    state_hbm_budget_mb: float = 0.0
    # Host cold tier for key_mode="exact": compaction DEMOTES pressure-
    # evicted keys' exact window rows to an append+compact keyed store on
    # the host (io/coldstore.py) instead of discarding them. EXACT PER
    # ROW: a returning key is detected in host prep against the store's
    # index (the host wrote it) and its rows are PROMOTED back into the
    # hot tier by a ("promote", table, width) program dispatched before
    # the step that scores its row — width from a ladder of precompiled
    # lane counts (engine.promote_widths: the largest batch bucket, at
    # most 16,384, and its quarters down to 256; more keys than that go
    # in several payloads), so zero mid-stream recompiles. A pass's
    # payload is landed by the loop thread before _maybe_compact
    # returns (readable at once; the segment write follows on the
    # store's writer thread). Resident on the host: the rows of every
    # key in the store, 4 + 16 x n_day_buckets bytes each — and the
    # store has the windows' own horizon: after every pass it drops the
    # keys whose newest event day is older than now_day - (delay_days +
    # max(windows)), rows no query can see (a key back later than that
    # is admitted afresh, as one the pass reclaimed from the hot tier).
    # So it holds the keys touched inside the horizon and not hot, not
    # every key ever demoted; the tier reaches as far as host memory
    # does over ONE horizon.
    # Empty string disables the tier (evictions discard, PR 13 behavior).
    # Accepts a local directory, an s3:// URL (flaky-store retries and
    # CRC verification inherited from the checkpoint backends), or
    # tmp://[name]: a fresh directory under the system's temporary
    # directory, private to this process and removed when it ends (runs
    # without checkpoints: a benchmark, a replay).
    cold_store: str = ""
    # Cold segment flush threshold (MB of buffered demoted rows before a
    # segment blob + manifest is written). Checkpoints always flush.
    cold_segment_mb: float = 4.0
    # Max keys demoted per table per compaction pass (the width of the
    # demotion payload — one compiled shape). Sizing: cold_demote_slots
    # / compact_every is the demotion capacity a batch, and it has to
    # exceed the keys a batch admits (new keys + promotions) or the hot
    # tier fills and a promote lane finds no slot (ColdPromoteError).
    # Under a calendar that moves the promotions are most of it: the
    # hot set is the keys of the newest few event days, and whoever
    # pays again after longer comes back through a lane (the
    # benchmark's forest-rf100-d8-cold-replay: ~35,500 customers a
    # 65,536-row batch, so 262,144 at a pass every 6 batches).
    cold_demote_slots: int = 1024
    # Hot-tier occupancy target: compaction demotes oldest-first down to
    # ceil(highwater * slot_capacity) occupied slots per table. Tied to
    # keydir_probes: the directory has 2 x slots entries, so its load is
    # half the occupancy, and a key whose P probe positions are all
    # taken is served from the sketch for good — expected keys lost over
    # a fill to load a of D entries: D a^(P+1)/(P+1). 0.5 with P = 16 is
    # exact in practice (3e-5 keys at 2^23 entries); 0.75 needs P >= 16.
    # The target is what a pass leaves; between two passes occupancy is
    # cold_highwater + compact_every x admissions a batch / slots, and
    # THAT is what the probes are sized for. A key touched on the
    # stream's newest event day is never demoted: on one event day its
    # distinct keys sit on top of the target (forest-rf100-d8-cold: 0.2,
    # peak 0.46); under a calendar that moves yesterday's keys go today
    # and the target holds (forest-rf100-d8-cold-replay: 0.35, peak
    # 0.40) — README, Cold tier, has both worked.
    cold_highwater: float = 0.75
    # Count-min sketch for unbounded key cardinality (velocity features).
    cms_depth: int = 4
    cms_width: int = 1 << 15
    # Where customer velocity features come from: "table" = exact dense
    # window state (keys must fit customer_capacity); "cms" = the count-min
    # sketch (BASELINE config 3) — bounded memory for billions of cards,
    # overestimate-only error. Terminal risk always uses the table (the
    # sketch holds no fraud sums).
    customer_source: str = "table"
    # Per-customer event-history ring length for the sequence scorer
    # (features/history.py) — the serving-side max_len of
    # models/sequence.build_sequences.
    history_len: int = 32
    # Attention form for the serving transformer over the history ring:
    # "naive" materializes [B, H, K, K] scores (fastest for short K),
    # "blockwise" runs the flash recurrence ([B, H, K, block] memory —
    # long histories on one chip), "auto" switches to blockwise once
    # history_len exceeds seq_attn_block (naive at K=512/B=64k wants a
    # 137 GB score tensor; blockwise caps it at K/block that).
    seq_attn: str = "auto"
    seq_attn_block: int = 128
    # Canonical flag definitions (see module docstring).
    night_end_hour: int = 6
    weekend_start_weekday: int = 5  # Monday == 0

    def __post_init__(self):
        if self.customer_source not in ("table", "cms"):
            raise ValueError(
                f"customer_source must be 'table' or 'cms', "
                f"got {self.customer_source!r}"
            )
        if self.key_mode not in ("direct", "hash", "exact"):
            raise ValueError(
                f"key_mode must be 'direct', 'hash' or 'exact', "
                f"got {self.key_mode!r}"
            )
        if self.key_bits not in (32, 64):
            raise ValueError(
                f"key_bits must be 32 or 64, got {self.key_bits!r}")
        if self.key_bits == 64 and self.key_mode != "exact":
            raise ValueError(
                "key_bits=64 requires key_mode='exact': 'direct' needs a "
                "table above the highest id and 'hash' merges keys by "
                "design — only the key directory holds a 64-bit id whole, "
                f"got key_mode={self.key_mode!r}")
        # direct mode masks with (capacity - 1) (ops/hashing.key_slot) and
        # the hash/exact placements assume pow2 tables — a non-pow2
        # capacity would silently ALIAS keys today, so refuse it loudly.
        for name in ("customer_capacity", "terminal_capacity"):
            cap = getattr(self, name)
            if cap < 1 or cap & (cap - 1):
                raise ValueError(
                    f"{name} must be a power of two (direct mode masks "
                    f"with capacity-1; non-pow2 silently aliases keys), "
                    f"got {cap}")
        if self.keydir_probes < 1:
            raise ValueError(
                f"keydir_probes must be >= 1, got {self.keydir_probes}")
        if self.compact_every < 0:
            raise ValueError(
                f"compact_every must be >= 0 (0 = off), "
                f"got {self.compact_every}")
        if self.state_hbm_budget_mb < 0:
            raise ValueError(
                f"state_hbm_budget_mb must be >= 0 (0 = unchecked), "
                f"got {self.state_hbm_budget_mb}")
        if self.cold_segment_mb <= 0:
            raise ValueError(
                f"cold_segment_mb must be > 0, got {self.cold_segment_mb}")
        if self.cold_demote_slots < 1:
            raise ValueError(
                f"cold_demote_slots must be >= 1, "
                f"got {self.cold_demote_slots}")
        if not 0 < self.cold_highwater <= 1:
            raise ValueError(
                f"cold_highwater must be in (0, 1], "
                f"got {self.cold_highwater}")
        if self.cold_store:
            if self.key_mode != "exact":
                raise ValueError(
                    "cold_store requires key_mode='exact' (only the "
                    "keyed hot tier has per-key rows to demote), got "
                    f"key_mode={self.key_mode!r}")
            if self.compact_every <= 0:
                raise ValueError(
                    "cold_store requires compact_every > 0 (demotion "
                    "rides the compaction cadence)")
        if self.seq_attn not in ("naive", "blockwise", "auto"):
            raise ValueError(
                f"seq_attn must be 'naive', 'blockwise' or 'auto', "
                f"got {self.seq_attn!r}"
            )


@dataclass(frozen=True)
class ModelConfig:
    """Classifier selection, mirroring the reference's 5-model zoo
    (``model_training.ipynb · cell 50``: LogReg, DT-2, DT, RF, XGBoost)."""

    kind: str = "logreg"  # logreg | mlp | tree | forest | gbt | autoencoder
    n_features: int = 15
    mlp_hidden: Sequence[int] = (64, 32)
    # Unsupervised anomaly scorer (successor to the dormant torch
    # autoencoder, shared_functions.py:1312-1707); encoder widths, the last
    # entry is the bottleneck.
    autoencoder_hidden: Sequence[int] = (32, 8)
    forest_n_trees: int = 100
    forest_max_depth: int = 8
    tree_max_depth: int = 2
    # Sequence (causal transformer) family dims — models/sequence.py.
    seq_d_model: int = 32
    seq_n_heads: int = 2
    seq_n_layers: int = 2
    seq_d_ff: int = 64
    dtype: str = "float32"
    seed: int = 0


@dataclass(frozen=True)
class TrainConfig:
    """Offline training protocol (``model_training.ipynb · cell 8``)."""

    delta_train_days: int = 153
    delta_delay_days: int = 30
    delta_test_days: int = 30
    learning_rate: float = 1e-2
    batch_size: int = 4096
    epochs: int = 5
    weight_decay: float = 0.0
    # Online SGD (BASELINE.json config 4).
    online_learning_rate: float = 1e-3


@dataclass(frozen=True)
class OverloadConfig:
    """Overload-survival ladder (``runtime/overload.py``).

    The reference has no overload story at all: Spark micro-batches just
    fall behind and Kafka lag grows without bound. When enabled, the
    engine runs an explicit hysteresis state machine over the registry
    signals it already emits (windowed batch latency vs
    ``latency_slo_ms``, source lag, prefetch/sink queue fill) and climbs
    a reversible degradation ladder: rung 1 sheds optional work (shadow
    scoring, learner training, flight-recorder sampling), rung 2 forces
    the largest AOT batch bucket + alerts-only emission, rung 3 defers
    whole micro-batches to a durable spill and replays them in order
    once pressure subsides — the stream degrades and recovers, it never
    dies and never silently drops a row (``scored + deferred ==
    polled``)."""

    enabled: bool = False
    # Durable overflow spill for rung-3 deferral (the PR 4 dead-letter
    # machinery, reason=shed, idempotent by tx_id): ``*.jsonl`` = JSONL
    # file, anything else = parquet part directory. "" = memory-only
    # deferral (still ordered and replayed, but a crash loses the
    # spilled copy and relies on checkpoint replay alone).
    spill_path: str = "overload_spill"
    # Hysteresis: climb one rung after ``climb_dwell_batches``
    # consecutive observations at pressure >= ``climb_pressure``;
    # descend one rung after ``descend_dwell_batches`` consecutive
    # observations at pressure <= ``descend_pressure``. The gap between
    # the two thresholds plus the dwell counts is what makes flapping
    # impossible: a single spike can neither climb nor descend.
    climb_pressure: float = 1.0
    descend_pressure: float = 0.6
    climb_dwell_batches: int = 3
    descend_dwell_batches: int = 6
    # Source-lag normalization: lag of this many rows == pressure 1.0
    # (0 disables the lag signal; latency/queue signals still work).
    lag_high_rows: int = 0
    # Windowed p50 batch-latency signal (vs runtime.latency_slo_ms).
    latency_window_batches: int = 8
    # Host-memory bound on rung-3 deferral: at most this many deferred
    # micro-batches are held (spilled + in memory) at once. At the cap
    # the controller replays the queue head through scoring to make
    # room, so the backlog beyond it stays in the source/broker — the
    # one buffer that is allowed to be unbounded, and visibly so via
    # rtfds_source_lag_rows.
    max_deferred_batches: int = 512
    # Flight-recorder sampling while any rung is active (rung 1's
    # "drop the recorder to sampled mode"): record every k-th batch.
    recorder_sample_every: int = 16

    def __post_init__(self):
        if not 0.0 <= self.descend_pressure < self.climb_pressure:
            raise ValueError(
                "overload hysteresis needs 0 <= descend_pressure < "
                f"climb_pressure, got {self.descend_pressure} / "
                f"{self.climb_pressure}")
        if self.climb_dwell_batches < 1 or self.descend_dwell_batches < 1:
            raise ValueError("overload dwell counts must be >= 1")
        if self.max_deferred_batches < 1:
            raise ValueError("overload.max_deferred_batches must be >= 1")
        if self.recorder_sample_every < 1:
            raise ValueError("overload.recorder_sample_every must be >= 1")


@dataclass(frozen=True)
class DistributedConfig:
    """Multi-host process topology (``runtime/distributed.py``).

    The reference scales by adding Spark executors behind one Kafka
    topic; the TPU-native analogue is N OS processes (one per host),
    each owning a contiguous block of the global shard space. Ownership
    is residue-based — process p of P, serving L local devices, owns the
    customer residues ``key % (P·L) ∈ [p·L, (p+1)·L)`` — chosen so the
    sharded step's internal ``key % L`` placement equals the global
    residue minus the block base: the per-process engine runs UNCHANGED
    and the fleet's shard layout matches a single (P·L)-device engine's
    exactly. Ingest is partition-affine (each process polls only its
    owners' traffic), so the host plane never pays a cross-process
    all-to-all; the owner exchange stays on the device fabric."""

    # host:port of process 0's jax.distributed coordination service.
    # "" = uncoordinated fleet: processes still partition the shard
    # space but skip jax.distributed.initialize (no spanning mesh is
    # possible; per-worker restart becomes safe — see the README
    # multi-host playbook's failure-semantics table).
    coordinator: str = ""
    # Total processes in the fleet; 1 = single-process (everything off).
    num_processes: int = 1
    # This process's id in [0, num_processes); -1 = resolve from
    # JAX_PROCESS_ID (the launcher always passes it explicitly).
    process_id: int = -1
    # Refuse polled rows whose customer residue this process does not
    # own (catches mis-wired launchers before state diverges). Applies
    # to residue-sliced sources (replay/synthetic/raw-table); Kafka
    # fleets partition by broker partition, where residue membership is
    # the producer's contract, not checkable per row — the CLI disables
    # the check there.
    strict_affinity: bool = True
    # jax.distributed.initialize barrier timeout.
    init_timeout_s: float = 120.0

    def __post_init__(self):
        if self.num_processes < 1:
            raise ValueError(
                f"distributed.num_processes must be >= 1, "
                f"got {self.num_processes}")
        if self.num_processes > 1 and self.process_id >= self.num_processes:
            raise ValueError(
                f"distributed.process_id {self.process_id} out of range "
                f"for {self.num_processes} process(es)")
        if self.init_timeout_s <= 0:
            raise ValueError(
                f"distributed.init_timeout_s must be > 0, "
                f"got {self.init_timeout_s}")


@dataclass(frozen=True)
class RuntimeConfig:
    """Micro-batch engine (replaces Spark Structured Streaming triggers:
    5 s sinks ``kafka_s3_sink_customers.py:179``, 10 s scorer
    ``fraud_detection.py:208``)."""

    scorer: str = "tpu"  # cpu | tpu
    # Fused Pallas featurize+score kernels (ops/pallas_kernels.py for the
    # linear scorer, ops/pallas_forest.py::fused_forest_leaf_sum for tree
    # ensembles). Interpreted (slow, exact) off-TPU.
    # Stays opt-in: no ledger cell runs either kernel, so their speed
    # against the XLA composition is not measured on chip (ROADMAP C3);
    # chip_smoke.py holds their results to XLA's. The forest fused step
    # attacks the scatter boundary XLA cannot fuse through.
    use_pallas: bool = False
    # MXU arithmetic for the tree-ensemble z contraction
    # (models/forest.py::gemm_leaf_sum — the dominant classify matmul,
    # exact in EVERY mode because its operands are tiny integers):
    # "auto" = int8 on TPU (2× bf16 MXU peak on v5e, bit-exact vs f32;
    # the chip's sweep of the three is a tie, ROADMAP C4), f32 elsewhere
    # (the only float mode CPU XLA lowers natively). Forced "int8"/"bf16"/
    # "f32" pin the mode on any backend; decisions are identical by the
    # exactness contract (README § Device plane).
    z_mode: str = "auto"
    trigger_seconds: float = 0.0  # 0 => score as fast as batches arrive
    # Max micro-batches in flight on the device at once (the engine's
    # software pipeline). 2 = classic double-buffering (batch N+1's host
    # prep + H2D overlap batch N's compute); deeper keeps the device fed
    # when per-dispatch overhead exceeds the step's compute time. Steps still chain through the feature state,
    # so depth buys dispatch overlap, not device concurrency.
    pipeline_depth: int = 2
    # Coalesce consecutive source polls into one device batch of up to
    # this many rows (0 = off: one poll = one batch). Amortizes per-step
    # dispatch overhead when the source hands out small batches.
    coalesce_rows: int = 0
    # False = alerts-only serving: BatchResult.features is zeros and the
    # [B, 15] feature matrix never leaves the device — the dominant D2H
    # cost per batch. Only valid with the device
    # scorer and no feature cache (both consume host-side features);
    # sinks that persist feature columns (the analyzed table) should
    # keep the default.
    emit_features: bool = True
    # "bfloat16" halves the feature D2H bytes (the largest per-batch
    # transfer of full-featured serving). Lossy (~3 decimal digits on the
    # 15 feature columns; predictions are NOT affected — the classifier
    # consumes the f32 features in-device), so it is opt-in and refused
    # when the host re-consumes features (scorer=cpu, feature cache).
    emit_dtype: str = "float32"  # "float32" | "bfloat16"
    # Selective emission (> 0 enables): probabilities are emitted for
    # EVERY row, but the 15 feature columns are transferred only for rows
    # whose fraud probability clears this threshold — the reference's
    # analyzed_transactions schema lands complete for every flagged row
    # (`fraud_detection.py:136-163`), while clean traffic (~99% at the
    # 0.88% fraud rate) skips the dominant D2H cost. The step compacts
    # flagged rows on-device and packs probs+count+indices+features into
    # ONE flat array, so a batch costs a single transfer (same round-trip
    # count as alerts-only serving). Rows below the threshold carry zero
    # feature columns in BatchResult/sinks. Requires the device scorer
    # and no feature cache (both consume every row's features host-side).
    emit_threshold: float = 0.0
    # On-device compaction capacity as a fraction of the batch rows. If a
    # batch flags more rows than this, the engine falls back to fetching
    # that batch's full feature matrix (kept on device for exactly this) —
    # correctness never depends on the cap, only the D2H savings do.
    emit_cap_fraction: float = 1 / 16
    # Pad/bucket micro-batches to these row counts to keep the jit cache warm.
    batch_buckets: Sequence[int] = (256, 1024, 4096, 16384, 65536)
    max_batch_rows: int = 65536
    # AOT bucket precompilation: at run start, .lower(...).compile() the
    # jitted step for EVERY batch_buckets size (× the engine's donation
    # signature) and serve from the compiled executables — no first-touch
    # bucket size ever pays a mid-stream XLA compile (969 ms measured vs
    # 8 ms steady-state; rtfds_xla_recompiles_total stays 0 by
    # construction). Composes with the persistent compilation cache, so
    # `rtfds warmup` makes later serving restarts warm too.
    precompile: bool = False
    # Adaptive micro-batch controller (runtime/autobatch.py): the
    # coalesce target moves BETWEEN the configured batch_buckets from
    # observed per-batch latency — hold latency_slo_ms when set, else
    # hill-climb for throughput. Overrides coalesce_rows while active.
    autobatch: bool = False
    # p50 micro-batch latency target in ms for the autobatch controller
    # (0 = no SLO: maximize throughput instead).
    latency_slo_ms: float = 0.0
    # Ingest-decode worker threads (core/native.py): each polled
    # envelope byte-batch is sharded into contiguous offset slabs decoded
    # concurrently by a thread pool (the ctypes scanner releases the
    # GIL) into disjoint slices of one columnar staging buffer —
    # bit-identical to single-worker decode, scales with cores. 0 = auto
    # (min(8, cores)); 1 = serial.
    decode_workers: int = 0
    # Background source prefetch (runtime/prefetch.py::PrefetchSource):
    # poll + decode run ahead of the serving loop on a producer thread
    # into a bounded queue of this many batches; the loop thread's
    # source_poll phase collapses to a dequeue. Offsets commit only on
    # CONSUMPTION (checkpoint/replay semantics unchanged: a crash
    # replays prefetched-but-unconsumed batches, never skips them), and
    # poison isolation switches the source back to synchronous polling.
    # 0 = off.
    prefetch_batches: int = 0
    # Overlapped result fetch: issue device→host copies asynchronously
    # (copy_to_host_async) the moment a step's handle resolves, so the
    # D2H transfer runs while the loop thread preps/dispatches later
    # batches instead of serializing into result_wait. Free on CPU; the
    # head start is metered as rtfds_fetch_overlap_seconds_total.
    fetch_overlap: bool = True
    # Bounded queue depth (batch results) of the loop's sink writer
    # (engine.run() owns one io/sink.py::AsyncSink thread per run); a full
    # queue backpressures the loop thread
    # (rtfds_sink_backpressure_seconds_total counts the blocked time).
    sink_queue_batches: int = 8
    checkpoint_dir: str = "checkpoints"
    checkpoint_every_batches: int = 50
    # Incremental checkpoints: write a FULL snapshot every K saves and
    # deltas (only the leaves whose bytes changed — feature state churns
    # every batch, params/scaler are static between hot-reloads) in
    # between, chained to their base by checksum. 1 = every save full
    # (the v1 cost model). Restore composes full + verified chain and is
    # bit-identical to a full restore or it falls back.
    checkpoint_full_every: int = 1
    # Flaky-store hardening for object-store checkpointers: per-op
    # timeout in seconds (a hung S3 GET/PUT surfaces as a retryable
    # transient instead of wedging the supervisor; 0 = wait) and retry
    # attempts per op (1 = no retry).
    checkpoint_op_timeout_s: float = 0.0
    checkpoint_op_attempts: int = 3
    n_partitions: int = 8
    # Data-plane non-finite guard (engine host boundary): rows whose
    # score/feature vector crosses the boundary NaN/Inf are quarantined
    # to the dead-letter sink and the batch is re-scored from pre-batch
    # state without them — contamination of the running window
    # aggregates is impossible. Opt-in: it disables step-state donation
    # and serializes the pipeline (depth 1) while on, and it requires a
    # dead_letter sink.
    nan_guard: bool = False
    # Dead-letter queue path for quarantined rows (``*.jsonl`` = JSONL
    # file, anything else = parquet part directory; "" = no DLQ — a
    # crash loop then fails fast instead of quarantining).
    dead_letter: str = ""
    # Crash-loop breaker: this many CONSECUTIVE crash-caused supervisor
    # failures at the same resume point reclassify the failure from
    # transient to poison (bisect + dead-letter instead of replay).
    crash_loop_k: int = 2
    # Backoff between crash-caused supervisor restarts (full jitter,
    # doubling, capped; 0 = the legacy hot restart loop). Stall restarts
    # never back off — they already waited out the stall budget.
    restart_backoff_ms: float = 0.0
    # Overload-survival degradation ladder (see OverloadConfig).
    overload: OverloadConfig = field(default_factory=OverloadConfig)
    # Multi-host process topology (see DistributedConfig): coordinator,
    # process count/id, ingest-affinity strictness.
    distributed: DistributedConfig = field(
        default_factory=DistributedConfig)

    def __post_init__(self):
        if self.z_mode not in ("auto", "f32", "bf16", "int8"):
            raise ValueError(
                f"z_mode must be 'auto', 'f32', 'bf16' or 'int8', "
                f"got {self.z_mode!r}"
            )


@dataclass(frozen=True)
class LearnConfig:
    """Continuous learning: streaming retrain → versioned registry →
    shadow scoring → gated canary promotion (``runtime/learner.py``,
    ``io/registry.py``). The reference's only path to a better model is
    retrain offline, overwrite the pickle, restart the Spark job; here a
    candidate warm-starts from the champion, fits the labeled-feedback
    window off the loop thread, shadow-scores the same live batches, and
    is promoted (and auto-rolled-back) from live precision/recall."""

    # Registry location: a local directory, or ``s3://bucket/prefix``
    # (store-backed; inherits the checkpoint plane's flaky-store
    # hardening). "" = no registry (learning disabled).
    registry_path: str = ""
    # Publish a candidate version after this many NEW labeled rows have
    # been trained since the last publish.
    publish_every_labels: int = 512
    # Bounded replay window of recent labeled rows the learner re-fits
    # per submission (host memory ≈ window_rows × 15 × 4 bytes).
    window_rows: int = 4096
    # Fit passes over the replay window per submitted label chunk.
    epochs: int = 2
    # Bounded learner queue (label chunks); a full queue DROPS (counted
    # in rtfds_learner_dropped_labels_total) — serving never waits.
    queue_chunks: int = 8
    # Learner SGD step size (0 = inherit train.online_learning_rate).
    learning_rate: float = 0.0
    # Shadow score cache rows (tx_id → champion/candidate probs kept
    # until the label arrives; direct-mapped like the FeatureCache).
    shadow_cache_rows: int = 1 << 16
    # Fraud decision threshold used for live precision/recall and for
    # divergence (decision-flip) counting.
    decision_threshold: float = 0.5
    # |p_candidate − p_champion| above this counts as divergence even
    # without a decision flip.
    divergence_threshold: float = 0.25
    # Promotion gate: BOTH models must have this many labeled rows in
    # the current comparison window, AND the candidate's live recall
    # must beat the champion's by promote_margin without giving up more
    # than precision_tolerance of live precision.
    promote_min_labels: int = 256
    promote_margin: float = 0.01
    precision_tolerance: float = 0.02
    # Post-promotion canary watch: after rollback_min_labels labeled
    # rows, the new champion must hold its pre-promotion recall baseline
    # within rollback_margin or the promotion is rolled back.
    rollback_min_labels: int = 256
    rollback_margin: float = 0.05
    # Without an in-stream learner (tree kinds: forest/GBT retrain
    # offline and publish via `rtfds registry`), the loop polls the
    # registry for externally published candidates every this many
    # batches (one backend listing per poll). 0 disables.
    external_poll_batches: int = 64


@dataclass(frozen=True)
class MeshConfig:
    """Device mesh: data axis shards Kafka partitions across chips (ICI)."""

    n_devices: int = 0  # 0 => use all visible devices
    data_axis: str = "data"


@dataclass(frozen=True)
class Config:
    data: DataConfig = field(default_factory=DataConfig)
    features: FeatureConfig = field(default_factory=FeatureConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)
    learn: LearnConfig = field(default_factory=LearnConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)

    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw)


def small_config() -> Config:
    """A tiny config for tests and CPU smoke runs."""
    return Config(
        data=DataConfig(n_customers=50, n_terminals=100, n_days=30, seed=0),
        features=FeatureConfig(customer_capacity=128, terminal_capacity=256,
                               cms_width=1 << 10),
        train=TrainConfig(delta_train_days=15, delta_delay_days=5,
                          delta_test_days=5, epochs=2, batch_size=512),
        runtime=RuntimeConfig(batch_buckets=(64, 256), max_batch_rows=256,
                              n_partitions=4),
    )
