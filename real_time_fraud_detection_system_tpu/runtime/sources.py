"""Stream sources: partitioned in-process broker, replay, live synthesis.

The reference's transport is Kafka topics fed by Debezium
(``docker-compose.yml:14-51``); partitioning is its data-parallel unit
(SURVEY §2.3). For dev/test/bench without Docker the framework provides:

- :class:`InProcBroker` — a Kafka-semantics in-process log: topics ×
  partitions, append-only, offset-addressed, key-hash partition assignment.
  Producers/consumers share it; consumers poll (partition, offset) ranges.
- :class:`ReplaySource` — replays a generated :class:`Transactions` table
  through the broker as Debezium envelopes (exercising the codec) or as
  raw columnar slices (the zero-parse benchmark path).
- :class:`SyntheticSource` — paced live generator, the ``datagen`` container
  analogue (``datagen/data_gen.py:116-135``, one tx/10 s demo rate, here
  configurable up to line rate).

A real ``KafkaSource`` (confluent-kafka/kafka-python) plugs in behind the
same ``poll_batch`` interface; the client libraries are not present in this
image, so it is import-gated.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from real_time_fraud_detection_system_tpu.core.envelope import (
    decode_transaction_envelopes_fast,
    encode_transaction_envelopes,
)
from real_time_fraud_detection_system_tpu.data.generator import (
    Transactions,
)
from real_time_fraud_detection_system_tpu.utils.metrics import get_registry


class _SourceTelemetry:
    """Shared per-source instrumentation: poll latency histogram, rows
    ingested counter, seek/replay counter, and (for sources that know
    their backlog) the ``rtfds_source_lag_rows`` gauge that ``/healthz``
    applies its lag threshold to. Series resolve once at construction."""

    def _init_source_metrics(self, source_kind: str) -> None:
        from real_time_fraud_detection_system_tpu.utils.trace import (
            get_tracer,
        )

        reg = get_registry()
        self._tracer = get_tracer()
        self._source_kind = source_kind
        self._m_poll = reg.histogram(
            "rtfds_source_poll_seconds", "source poll_batch wall time",
            source=source_kind)
        self._m_ingested = reg.counter(
            "rtfds_source_rows_total", "rows ingested", source=source_kind)
        self._m_seeks = reg.counter(
            "rtfds_source_seeks_total",
            "checkpoint-resume / replay seeks", source=source_kind)
        # The lag gauge is registered LAZILY on first set: a source that
        # cannot compute a backlog (Kafka) must not create a permanent-0
        # series, or /healthz's lag threshold would check the fake zero
        # and report healthy while the consumer falls behind. Unlabeled
        # on purpose: /healthz reads it without knowing which source
        # implementation is serving.
        self._m_lag = None

    def _begin_poll(self) -> tuple:
        """→ ``(t0, span)``: the poll's start and its open
        ``source/<kind>`` span, a child of whatever span the calling
        thread has open (the engine's ``source_poll``, whose batch it
        takes)."""
        span = self._tracer.span(f"source/{self._source_kind}").open()
        return time.perf_counter(), span

    def _observe_poll(self, t0: float, span, cols: Optional[dict],
                      lag: Optional[int] = None) -> None:
        t1 = time.perf_counter()
        self._m_poll.observe(t1 - t0)
        n = 0
        if cols is not None:
            n = len(next(iter(cols.values()), ()))
            if n:
                self._m_ingested.inc(n)
            else:
                span.cancel()  # nothing arrived: no span a quiet poll
        span.close(t0, t1, rows=n)
        if lag is not None:
            if self._m_lag is None:
                self._m_lag = get_registry().gauge(
                    "rtfds_source_lag_rows",
                    "known backlog: rows available but not yet served")
            self._m_lag.set(lag)


@dataclass
class _Record:
    offset: int
    ts_ms: int
    key: bytes
    value: bytes


class InProcBroker:
    """Partitioned append-only log with Kafka offset semantics."""

    def __init__(self, n_partitions: int = 8):
        self.n_partitions = n_partitions
        self._topics: Dict[str, List[List[_Record]]] = {}
        self._lock = threading.Lock()

    def _topic(self, name: str) -> List[List[_Record]]:
        with self._lock:
            if name not in self._topics:
                self._topics[name] = [[] for _ in range(self.n_partitions)]
            return self._topics[name]

    def partition_of(self, key: bytes) -> int:
        # FNV-1a over the key bytes — stable across runs/processes.
        h = 2166136261
        for byte in key:
            h = ((h ^ byte) * 16777619) & 0xFFFFFFFF
        return h % self.n_partitions

    def produce(
        self, topic: str, key: bytes, value: bytes, ts_ms: int = 0,
        partition: Optional[int] = None,
    ) -> Tuple[int, int]:
        part = self.partition_of(key) if partition is None else partition
        log = self._topic(topic)[part]
        with self._lock:
            off = len(log)
            log.append(_Record(off, ts_ms, key, value))
        return part, off

    def produce_many(
        self, topic: str, keys: Sequence[bytes], values: Sequence[bytes],
        ts_ms: Optional[Sequence[int]] = None,
    ) -> None:
        for i, (k, v) in enumerate(zip(keys, values)):
            self.produce(topic, k, v, ts_ms[i] if ts_ms is not None else 0)

    def poll(
        self, topic: str, partition: int, offset: int, max_records: int
    ) -> List[_Record]:
        log = self._topic(topic)[partition]
        with self._lock:
            return log[offset : offset + max_records]

    def end_offsets(self, topic: str) -> List[int]:
        t = self._topic(topic)
        with self._lock:
            return [len(p) for p in t]


class ReplaySource(_SourceTelemetry):
    """Serves micro-batches from a transactions table.

    ``mode='columnar'`` returns numpy column dicts directly (zero-parse
    benchmark path); ``mode='envelope'`` round-trips rows through Debezium
    JSON envelopes in an :class:`InProcBroker`, exercising decode exactly as
    a Kafka deployment would.
    """

    def __init__(
        self,
        txs: Transactions,
        start_epoch_s: int,
        batch_rows: int = 4096,
        mode: str = "columnar",
        n_partitions: int = 8,
        with_labels: bool = False,
    ):
        self.txs = txs
        self.start_epoch_s = start_epoch_s
        self.batch_rows = batch_rows
        self.mode = mode
        self.with_labels = with_labels
        self.n_partitions = n_partitions
        self._pos = 0
        self._init_source_metrics("replay")
        if mode == "envelope":
            self.broker = InProcBroker(n_partitions)
            t_us = txs.epoch_us(start_epoch_s)
            msgs = encode_transaction_envelopes(
                txs.tx_id, t_us, txs.customer_id, txs.terminal_id,
                txs.amount_cents,
            )
            keys = [str(int(c)).encode() for c in txs.customer_id]
            self.broker.produce_many(
                "debezium.payment.transactions", keys, msgs,
                ts_ms=(t_us // 1000).tolist(),
            )
            self._offsets = [0] * n_partitions

    def poll_batch(self) -> Optional[dict]:
        """Next micro-batch as a column dict (None when exhausted)."""
        t0, span = self._begin_poll()
        cols = self._poll_inner()
        if self.mode == "columnar":
            lag = self.txs.n - self._pos
        else:
            lag = sum(self.broker.end_offsets(
                "debezium.payment.transactions")) - sum(self._offsets)
        self._observe_poll(t0, span, cols, lag=lag)
        return cols

    def _poll_inner(self) -> Optional[dict]:
        if self.mode == "columnar":
            n = self.txs.n
            if self._pos >= n:
                return None
            s, e = self._pos, min(self._pos + self.batch_rows, self.txs.n)
            self._pos = e
            part = self.txs.slice(slice(s, e))
            cols = {
                "tx_id": part.tx_id,
                "tx_datetime_us": part.epoch_us(self.start_epoch_s),
                "customer_id": part.customer_id,
                "terminal_id": part.terminal_id,
                "tx_amount_cents": part.amount_cents,
                "kafka_ts_ms": part.epoch_us(self.start_epoch_s) // 1000,
            }
            if self.with_labels:
                cols["label"] = part.tx_fraud.astype(np.int32)
            return cols

        # envelope mode: round-robin partition polling up to batch_rows
        per = max(1, self.batch_rows // self.n_partitions)
        msgs: List[bytes] = []
        ts: List[int] = []
        for p in range(self.n_partitions):
            recs = self.broker.poll(
                "debezium.payment.transactions", p, self._offsets[p], per
            )
            self._offsets[p] += len(recs)
            msgs += [r.value for r in recs]
            ts += [r.ts_ms for r in recs]
        if not msgs:
            return None
        cols, invalid = decode_transaction_envelopes_fast(msgs, ts)
        if invalid.any():
            keep = ~invalid
            cols = {k: v[keep] for k, v in cols.items()}
        return cols

    @property
    def offsets(self) -> List[int]:
        if self.mode == "columnar":
            return [self._pos]
        return list(self._offsets)

    def seek(self, offsets: Sequence[int]) -> None:
        """Restore consumption position (checkpoint resume)."""
        self._m_seeks.inc()
        if self.mode == "columnar":
            self._pos = int(offsets[0])
        else:
            self._offsets = list(offsets)


class SyntheticSource(_SourceTelemetry):
    """Paced live generator — the ``datagen`` container analogue.

    Yields batches at ``rate_tps`` transactions/second of wall-clock (or as
    fast as possible when 0), drawing from a pre-generated table.
    Telemetry lands under ``source="synthetic"`` (poll latency includes
    the pacing sleep — that IS this source's poll behavior); the inner
    replay cursor is polled via ``_poll_inner`` so rows are not
    double-counted under ``source="replay"``.
    """

    def __init__(
        self,
        txs: Transactions,
        start_epoch_s: int,
        rate_tps: float = 0.0,
        batch_rows: int = 4096,
    ):
        self._replay = ReplaySource(txs, start_epoch_s, batch_rows, "columnar")
        self.rate_tps = rate_tps
        self._init_source_metrics("synthetic")

    def poll_batch(self) -> Optional[dict]:
        t0, span = self._begin_poll()
        cols = self._replay._poll_inner()
        if cols is not None and self.rate_tps > 0:
            time.sleep(len(cols["tx_id"]) / self.rate_tps)
        self._observe_poll(t0, span, cols,
                           lag=self._replay.txs.n - self._replay._pos)
        return cols

    @property
    def offsets(self) -> List[int]:
        return self._replay.offsets

    def seek(self, offsets: Sequence[int]) -> None:
        self._m_seeks.inc()
        # inner seek counts under source="replay" too; its counter exists
        # but stays untouched here (we never call the inner poll_batch)
        self._replay._pos = int(offsets[0])


class RawTableSource(_SourceTelemetry):
    """Stream the persistent raw-transactions table back through the
    engine — backfill / re-score-after-retrain.

    The reference's scorer stream-reads the Iceberg transactions table,
    history included (``fraud_detection.py:91-93``:
    ``readStream.format("iceberg").load("nessie.payment.transactions")``),
    so re-running it after retraining re-scores everything already
    landed. This source gives the framework the same workflow over its
    own day-partitioned Parquet table (:class:`~.io.tables.
    RawTransactionsTable`).

    The table snapshot is loaded once at construction (latest-wins
    across parts), sorted into temporal order — window features require
    time-ordered ingestion — optionally restricted to
    ``[from_day, to_day]`` (inclusive ``YYYY-MM-DD`` strings), then
    served as ``batch_rows`` micro-batches behind the standard
    ``poll_batch``/``offsets``/``seek`` protocol. Rows written to the
    table after construction are not seen (snapshot isolation, matching
    the read_all contract).

    Checkpoint-resume across re-constructions is watermark-guarded:
    ``offsets`` carries ``[pos, n_snapshot, max_ts, max_tx_id]``, and
    ``seek`` verifies the first ``n_snapshot`` sorted rows still match
    that construction-time watermark. Appends beyond the watermark are
    safe (they sort after the snapshot and get served once the resumed
    stream reaches them); late data at-or-below it raises instead of
    silently corrupting the resume positions.
    """

    def __init__(
        self,
        directory: str,
        batch_rows: int = 4096,
        from_day: Optional[str] = None,
        to_day: Optional[str] = None,
    ):
        from real_time_fraud_detection_system_tpu.io.tables import (
            RawTransactionsTable,
        )

        cols = RawTransactionsTable(directory).read_all()
        if not cols:
            raise FileNotFoundError(
                f"no raw-transactions partitions under {directory!r} "
                "(expected tx_date=*/part-*.parquet)"
            )
        if from_day or to_day:
            from real_time_fraud_detection_system_tpu.core.batch import (
                US_PER_DAY,
            )
            from real_time_fraud_detection_system_tpu.utils.timing import (
                date_to_epoch_s,
            )

            def _day_num(s: str) -> int:
                try:
                    return date_to_epoch_s(s) // 86400
                except ValueError as e:
                    raise ValueError(
                        f"bad day filter {s!r} (want YYYY-MM-DD): {e}"
                    ) from None

            days = cols["tx_datetime_us"] // US_PER_DAY
            keep = np.ones(len(days), dtype=bool)
            if from_day:
                keep &= days >= _day_num(from_day)
            if to_day:
                keep &= days <= _day_num(to_day)
            cols = {k: v[keep] for k, v in cols.items()}
        order = np.lexsort((cols["tx_id"], cols["tx_datetime_us"]))
        self._cols = {k: np.ascontiguousarray(v[order])
                      for k, v in cols.items()}
        self.batch_rows = batch_rows
        self._pos = 0
        # Snapshot watermark for checkpoint-resume: offsets are positions
        # into THIS lexsort, so they stay valid across a re-construction
        # only if the first n_snap sorted rows are unchanged. Rows appended
        # later with (ts, tx_id) beyond the watermark sort strictly after
        # every snapshot row (resume correct, new rows served at the end);
        # late data at-or-before it shifts positions — seek() detects that
        # and raises instead of silently skipping/re-serving rows.
        n = len(self._cols["tx_id"])
        if n:
            self._snapshot = (n, int(self._cols["tx_datetime_us"][-1]),
                              int(self._cols["tx_id"][-1]))
        else:
            self._snapshot = (0, -1, -1)
        self._init_source_metrics("raw_table")

    @property
    def n(self) -> int:
        return len(self._cols["tx_id"])

    def poll_batch(self) -> Optional[dict]:
        t0, span = self._begin_poll()
        if self._pos >= self.n:
            self._observe_poll(t0, span, None, lag=0)
            return None
        s, e = self._pos, min(self._pos + self.batch_rows, self.n)
        self._pos = e
        out = {k: v[s:e] for k, v in self._cols.items()}
        # replayed history: event time doubles as the transport timestamp
        out["kafka_ts_ms"] = out["tx_datetime_us"] // 1000
        self._observe_poll(t0, span, out, lag=self.n - self._pos)
        return out

    @property
    def offsets(self) -> List[int]:
        n_snap, wts, wtx = self._snapshot
        return [self._pos, n_snap, wts, wtx]

    def seek(self, offsets: Sequence[int]) -> None:
        if len(offsets) >= 4:
            _, n_snap, wts, wtx = (int(x) for x in offsets[:4])
            ts = self._cols["tx_datetime_us"]
            tid = self._cols["tx_id"]
            in_snap = (ts < wts) | ((ts == wts) & (tid <= wtx))
            got = int(in_snap.sum())
            if got != n_snap or not bool(in_snap[:got].all()):
                raise ValueError(
                    "RawTableSource resume: the table changed at or below "
                    f"the checkpoint watermark (ts={wts}, tx_id={wtx}): "
                    f"expected {n_snap} snapshot rows, found {got}. Late "
                    "or rewritten data shifts sort positions, so resuming "
                    "by offset would skip or re-serve rows — re-run the "
                    "backfill from scratch (or bound it with "
                    "from_day/to_day)."
                )
        self._m_seeks.inc()
        self._pos = int(offsets[0])


class PartitionAffineSource(_SourceTelemetry):
    """Residue slice of an inner source — multi-host partition-affine
    ingest for sources that have no broker partitions to assign.

    Each fleet process wraps the SAME underlying stream (a replay table,
    a synthetic generator, a raw-table backfill) and serves only the
    rows whose customer residue its :class:`~.distributed.
    ProcessTopology` block owns; the other rows are someone else's
    traffic and are dropped here, host-side, before any decode-adjacent
    work the engine would pay (``rtfds_affine_skipped_rows_total``
    counts them — at production scale the broker's partition assignment
    replaces this wrapper precisely so that polling cost disappears).

    Replay-identical boundaries per owner: the filter is a pure function
    of the inner batch, so a checkpoint resume (``seek`` passes through
    to the inner source, offsets ARE the inner offsets) re-serves
    exactly the same per-process micro-batches — poison bisection and
    sink-lineage fencing work per process, unchanged.
    """

    def __init__(self, inner, topology):
        self.inner = inner
        self.topology = topology
        self._init_source_metrics("affine")
        self._m_skipped = get_registry().counter(
            "rtfds_affine_skipped_rows_total",
            "polled rows owned by another process (residue-sliced "
            "ingest; a broker-partitioned fleet never polls them at "
            "all)", process=str(topology.process_id))

    def poll_batch(self) -> Optional[dict]:
        t0, span = self._begin_poll()
        cols = self.inner.poll_batch()
        if cols is not None and len(next(iter(cols.values()), ())):
            mine = self.topology.owns(cols["customer_id"])
            n_skip = int((~mine).sum())
            if n_skip:
                self._m_skipped.inc(n_skip)
                cols = {k: v[mine] for k, v in cols.items()}
        # a fully-filtered batch surfaces as 0 rows, which the engine
        # treats as an idle poll and polls again — the inner cursor has
        # advanced, so the stream still terminates
        self._observe_poll(t0, span, cols)
        return cols

    @property
    def offsets(self) -> List[int]:
        return list(self.inner.offsets)

    def seek(self, offsets: Sequence[int]) -> None:
        self._m_seeks.inc()
        self.inner.seek(offsets)

    def commit(self, offsets: Optional[Sequence[int]] = None) -> None:
        commit = getattr(self.inner, "commit", None)
        if commit is not None:
            if offsets is None:
                commit()
            else:
                commit(offsets=offsets)

    def close(self) -> None:
        close = getattr(self.inner, "close", None)
        if close is not None:
            close()


class OwnershipFloorSource(_SourceTelemetry):
    """Per-old-owner resume floors after a fleet SHRINK merge.

    When P processes merge into P′ < P, each old process p had its own
    stream cursor; the merged checkpoint can only carry ONE offset, the
    MINIMUM of the per-process floors (anything earlier is scored by
    everyone). Rows between that minimum and old-owner p's floor were
    already scored and sunk by p — re-scoring them would duplicate
    ``tx_id``s in the global sink. This wrapper re-derives each polled
    row's OLD owner (the pre-resize residue block over ``customer_id``)
    and drops the row iff its global stream position is still below that
    owner's floor; once the cursor passes ``max(floors)`` it is pure
    passthrough. Sits INSIDE any :class:`PartitionAffineSource` (floors
    are positions in the shared stream, so they must be applied before
    the new topology's residue filter re-indexes nothing — the affine
    wrapper drops rows without advancing positions).

    Single-cursor sources only (columnar replay / synthetic / raw-table:
    ``offsets == [pos]``); a broker-partitioned fleet carries per-
    partition committed offsets through the resize instead and never
    needs this wrapper.
    """

    def __init__(self, inner, floors: Sequence[int], old_processes: int,
                 old_local_devices: int):
        from real_time_fraud_detection_system_tpu.runtime.distributed import (
            _fold_u32,
        )

        if len(inner.offsets) != 1:
            raise ValueError(
                "OwnershipFloorSource requires a single-cursor inner "
                f"source, got {len(inner.offsets)} offsets")
        if len(floors) != old_processes:
            raise ValueError(
                f"{len(floors)} floors for {old_processes} old processes")
        self.inner = inner
        self.floors = np.asarray([int(f) for f in floors], dtype=np.int64)
        self._hi = int(self.floors.max())
        self._fold = _fold_u32
        self._n_total = old_processes * old_local_devices
        self._l = old_local_devices
        self._init_source_metrics("floor")
        self._m_floor_skipped = get_registry().counter(
            "rtfds_resume_floor_skipped_rows_total",
            "rows dropped on resume because the pre-resize owner "
            "process had already scored them (per-owner resume floors "
            "after a fleet shrink merge)")

    def poll_batch(self) -> Optional[dict]:
        t0, span = self._begin_poll()
        pos = int(self.inner.offsets[0])  # global position of next row
        cols = self.inner.poll_batch()
        n = 0 if cols is None else len(next(iter(cols.values()), ()))
        if n and pos < self._hi:
            owner = (self._fold(np.asarray(
                cols["customer_id"], dtype=np.uint32))
                % np.uint32(self._n_total)).astype(np.int64) // self._l
            keep = (pos + np.arange(n, dtype=np.int64)) >= self.floors[owner]
            n_skip = int((~keep).sum())
            if n_skip:
                self._m_floor_skipped.inc(n_skip)
                cols = {k: v[keep] for k, v in cols.items()}
        self._observe_poll(t0, span, cols)
        return cols

    @property
    def offsets(self) -> List[int]:
        return list(self.inner.offsets)

    def seek(self, offsets: Sequence[int]) -> None:
        self._m_seeks.inc()
        self.inner.seek(offsets)

    def commit(self, offsets: Optional[Sequence[int]] = None) -> None:
        commit = getattr(self.inner, "commit", None)
        if commit is not None:
            if offsets is None:
                commit()
            else:
                commit(offsets=offsets)

    def close(self) -> None:
        close = getattr(self.inner, "close", None)
        if close is not None:
            close()


def raise_for_kafka_error(ck, err) -> bool:
    """Shared poll-error policy for all Kafka consumers in this runtime.

    Returns True for the end-of-partition marker (caller skips it);
    raises ``ConnectionError`` for retriable transport/broker errors (the
    type :func:`~.faults.run_with_recovery`'s default ``recover_on``
    restarts through — and an honest signal for un-supervised callers,
    who must not mistake a dead broker for a quiet topic); raises
    ``KafkaException`` for fatal errors (auth, config)."""
    if getattr(err, "code", lambda: None)() == getattr(
        ck.KafkaError, "_PARTITION_EOF", -191
    ):
        return True
    if getattr(err, "retriable", lambda: False)():
        raise ConnectionError(f"kafka transient error: {err}")
    raise ck.KafkaException(err)


class KafkaSource(_SourceTelemetry):
    """Real Kafka consumer → columnar micro-batches.

    The production ingress of the reference is the Debezium transaction
    topic (``docker-compose.yml:14-34``, consumed by Spark at
    ``kafka_s3_sink_transactions.py:51-56``). This source subscribes to the
    same topic, polls up to ``batch_rows`` Debezium-JSON messages per
    micro-batch, and decodes them in one vectorized pass
    (:func:`decode_transaction_envelopes_fast`) into the engine's column
    dict.

    Offset contract (aligned with :class:`io.checkpoint.Checkpointer`):

    - ``offsets`` is a dense per-partition list of NEXT offsets to consume
      (Kafka commit semantics); ``-1`` marks a partition this consumer has
      never consumed (left to the broker's ``auto.offset.reset``).
    - ``seek(offsets)`` re-assigns those positions — checkpoint resume.
    - ``commit()`` commits the tracked offsets to the broker
      (at-least-once; exactly-once lands in the engine's
      checkpoint + latest-wins dedup, which absorbs replayed rows the
      same way the reference's ROW_NUMBER/MERGE does).

    Auto-commit is disabled: the broker's committed offsets trail the
    framework checkpoint, never lead it, so a crash can only replay —
    never skip — rows.

    Two assignment modes:

    - ``partitions=None`` (default): consumer-group ``subscribe`` with a
      rebalance callback; on assignment, partitions we hold checkpointed
      offsets for are seeked back to them (so a rebalance can't skip
      uncheckpointed rows).
    - explicit ``partitions=[...]``: manual ``assign`` — the
      partition→device-affinity mode used by the sharded engine, where the
      framework owns placement (SURVEY §2.3 item 1).

    ``consumer_factory`` defaults to ``confluent_kafka.Consumer``; tests
    inject a fake ``confluent_kafka`` module via ``sys.modules``.
    """

    TOPIC_DEFAULT = "debezium.payment.transactions"

    def __init__(
        self,
        bootstrap_servers: str,
        topic: str = TOPIC_DEFAULT,
        group_id: str = "rtfds-scorer",
        batch_rows: int = 4096,
        poll_timeout_s: float = 1.0,
        idle_timeout_s: Optional[float] = None,
        partitions: Optional[Sequence[int]] = None,
        n_partitions: Optional[int] = None,
        config: Optional[dict] = None,
        consumer_factory=None,
    ):
        import confluent_kafka as ck

        self._ck = ck
        self.topic = topic
        self.batch_rows = batch_rows
        self.poll_timeout_s = poll_timeout_s
        self.idle_timeout_s = idle_timeout_s
        conf = {
            "bootstrap.servers": bootstrap_servers,
            "group.id": group_id,
            "enable.auto.commit": False,
            "auto.offset.reset": "earliest",
            **(config or {}),
        }
        factory = consumer_factory or ck.Consumer
        self._consumer = factory(conf)
        self._init_source_metrics("kafka")
        self._next: Dict[int, int] = {}  # partition -> next offset
        self._n_partitions = n_partitions
        self._manual = partitions is not None
        if self._manual:
            self._assigned = sorted(int(p) for p in partitions)
            self._consumer.assign(
                [ck.TopicPartition(topic, p) for p in self._assigned]
            )
        else:
            self._assigned = []
            self._consumer.subscribe(
                [topic], on_assign=self._on_assign, on_revoke=self._on_revoke
            )

    # -- rebalance callbacks (subscribe mode) --------------------------
    def _on_assign(self, consumer, tps) -> None:
        for tp in tps:
            p = tp.partition
            if p not in self._assigned:
                self._assigned.append(p)
            if p in self._next:
                # We own the offset state: resume from the checkpointed
                # position, not the group's committed one.
                tp.offset = self._next[p]
        self._assigned.sort()
        consumer.assign(tps)

    def _on_revoke(self, consumer, tps) -> None:
        for tp in tps:
            if tp.partition in self._assigned:
                self._assigned.remove(tp.partition)
        # _next is kept: if the partition comes back we resume correctly,
        # and `offsets` keeps reporting progress made while we owned it.

    # -- source protocol ----------------------------------------------
    def poll_batch(self) -> Optional[dict]:
        """Poll up to ``batch_rows`` messages, decode, return columns.

        Returns whatever arrived within ``poll_timeout_s`` (a partial
        batch keeps latency bounded at low traffic). ``None`` — the
        engine's end-of-stream signal — only when ``idle_timeout_s`` is
        set and no message arrives within it; an unbounded live source
        (the default) returns an empty poll as a zero-row wait instead,
        by polling again on the next engine trigger.
        """
        t0, span = self._begin_poll()
        cols = self._poll_inner()
        # no lag gauge: a broker high-watermark query per poll is an
        # extra RPC on the hot path; scrape consumer-group lag from the
        # broker's own exporter instead
        self._observe_poll(t0, span, cols)
        return cols

    def _poll_inner(self) -> Optional[dict]:
        import time as _time

        msgs: List[bytes] = []
        ts_ms: List[int] = []
        deadline = _time.monotonic() + self.poll_timeout_s
        idle_deadline = (
            _time.monotonic() + self.idle_timeout_s
            if self.idle_timeout_s is not None
            else None
        )
        while len(msgs) < self.batch_rows:
            now = _time.monotonic()
            if msgs and now >= deadline:
                break
            if not msgs and idle_deadline is not None and now >= idle_deadline:
                return None
            msg = self._consumer.poll(
                min(self.poll_timeout_s, 0.1) if msgs else self.poll_timeout_s
            )
            if msg is None:
                if msgs:
                    break
                if idle_deadline is None:
                    break  # empty poll: engine will trigger again
                continue
            err = msg.error()
            if err is not None:
                if getattr(err, "code", lambda: None)() == getattr(
                    self._ck.KafkaError, "_PARTITION_EOF", -191
                ):
                    continue  # end-of-partition marker, not an error
                if msgs:
                    # Never discard buffered rows (their offsets are
                    # already tracked in _next — dropping them here would
                    # turn a transient error into silent row loss when
                    # those offsets get committed). Return the partial
                    # batch; a persistent error re-surfaces on the next
                    # poll with an empty buffer.
                    break
                raise_for_kafka_error(self._ck, err)
            if msg.value() is None:
                # Tombstone (CDC delete). Deletes of transactions don't
                # re-score anything; advance past it.
                self._next[msg.partition()] = msg.offset() + 1
                continue
            self._next[msg.partition()] = msg.offset() + 1
            msgs.append(msg.value())
            t = msg.timestamp()
            ts_ms.append(int(t[1]) if t and t[1] and t[1] > 0 else 0)
        if not msgs:
            if idle_deadline is not None:
                return None
            # Zero-row batch with the decoder's exact column contract
            # (same keys/dtypes as the non-empty path below).
            return decode_transaction_envelopes_fast([], [])[0]
        cols, invalid = decode_transaction_envelopes_fast(msgs, ts_ms)
        if invalid.any():
            keep = ~invalid
            cols = {k: v[keep] for k, v in cols.items()}
        return cols

    @property
    def offsets(self) -> List[int]:
        """Dense next-offset list, length = max partition seen + 1 (or
        ``n_partitions`` when given); -1 = never consumed."""
        n = self._n_partitions
        if n is None:
            seen = list(self._next) + list(self._assigned)
            n = (max(seen) + 1) if seen else 0
        out = [-1] * n
        for p, off in self._next.items():
            if p < n:
                out[p] = off
        return out

    def seek(self, offsets: Sequence[int]) -> None:
        """Restore consumption positions (checkpoint resume).

        Manual-assignment mode re-``assign``s with explicit offsets —
        librdkafka only allows ``seek()`` on a partition whose fetcher has
        started (first ``poll`` after assign), so a resume-before-poll must
        go through ``assign``. Subscribe mode records the offsets; they are
        applied by the rebalance callback on (re-)assignment, and with
        ``seek()`` on partitions already being consumed.
        """
        self._m_seeks.inc()
        ck = self._ck
        for p, off in enumerate(offsets):
            if int(off) >= 0:
                self._next[p] = int(off)
        if self._manual:
            parts = sorted(set(self._assigned) | set(self._next))
            self._consumer.assign([
                ck.TopicPartition(self.topic, p, self._next.get(p, -1001))
                for p in parts
            ])
            self._assigned = parts
            return
        for p in list(self._assigned):
            if p in self._next:
                self._consumer.seek(
                    ck.TopicPartition(self.topic, p, self._next[p])
                )

    def commit(self, offsets: Optional[Sequence[int]] = None) -> None:
        """Commit next-offsets to the broker (post-checkpoint).

        ``offsets`` (dense list, -1 = skip, same layout as the
        ``offsets`` property) overrides the tracked positions — the
        prefetcher passes its CONSUMED offsets here so a broker commit
        never records the producer's read-ahead (committed offsets must
        trail the framework checkpoint, or a crash could skip rows)."""
        ck = self._ck
        if offsets is not None:
            pairs = [(p, int(off)) for p, off in enumerate(offsets)
                     if int(off) >= 0]
        else:
            pairs = sorted(self._next.items())
        tps = [ck.TopicPartition(self.topic, p, off) for p, off in pairs]
        if tps:
            self._consumer.commit(offsets=tps, asynchronous=False)

    def close(self) -> None:
        self._consumer.close()


def make_kafka_source(
    bootstrap_servers: str, **kwargs
) -> "KafkaSource":
    """Factory for the production Kafka ingress (import-gated).

    The confluent-kafka client is not baked into this image; in
    production images it is, and tests inject a fake module.
    """
    try:
        import confluent_kafka  # noqa: F401
    except ImportError as e:
        raise ImportError(
            "confluent-kafka is not installed in this environment; use "
            "InProcBroker/ReplaySource for dev, or install a Kafka client "
            "in production images."
        ) from e
    return KafkaSource(bootstrap_servers, **kwargs)
