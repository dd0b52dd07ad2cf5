"""Output sinks — the ``analyzed_transactions`` append path.

The reference appends scored rows to an Iceberg table that Trino/Superset
read (``fraud_detection.py:204-211``). The framework writes the same
column layout (``core/schema.py::ANALYZED_TRANSACTIONS_FIELDS``):

- :class:`ParquetSink` — one Parquet part-file per micro-batch under a
  directory; any Iceberg/Trino/DuckDB reader can mount it. Columns are
  byte-compatible with the reference table (µs timestamps, f64 amounts).
- :class:`MemorySink` — accumulates in RAM (tests, metrics).
- :class:`ConsoleSink` — the reference's ``.show()`` debugging analogue.

An ``IcebergSink`` (pyiceberg catalog append) belongs here too; pyiceberg is
not in this image, so it is import-gated the same way KafkaSource is.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import List, Optional

import numpy as np

from real_time_fraud_detection_system_tpu.features.spec import FEATURE_NAMES
from real_time_fraud_detection_system_tpu.utils.metrics import get_registry


class _SinkTelemetry:
    """Shared sink instrumentation: write latency, rows, bytes, failures
    (labeled by sink kind). Series resolve once per sink instance."""

    def _init_sink_metrics(self, sink_kind: str,
                           parts: bool = False) -> None:
        """``parts``: the sink writes Parquet part files itself, and counts
        how :func:`_encode_part` stored their columns."""
        from real_time_fraud_detection_system_tpu.utils.trace import (
            get_tracer,
        )

        reg = get_registry()
        self._tracer = get_tracer()
        self._sink_kind = sink_kind
        self._m_write = reg.histogram(
            "rtfds_sink_write_seconds", "sink append wall time",
            sink=sink_kind)
        self._m_rows = reg.counter(
            "rtfds_sink_rows_total", "rows written", sink=sink_kind)
        self._m_bytes = reg.counter(
            "rtfds_sink_bytes_total", "bytes written", sink=sink_kind)
        self._m_failures = reg.counter(
            "rtfds_sink_failures_total", "failed appends", sink=sink_kind)
        if parts:
            self._m_plain_cols = reg.counter(
                "rtfds_sink_plain_columns_total",
                "part-file columns stored plain: no dictionary page, or "
                "the writer left its dictionary with nine tenths of the "
                "rows still to write (uncompressed chunk bytes less the "
                "dictionary page limit >= 0.9 x rows x value width)",
                sink=sink_kind)
            self._m_dict_cols = reg.counter(
                "rtfds_sink_dict_columns_total",
                "part-file columns that kept their dictionary (every "
                "column that rtfds_sink_plain_columns_total does not "
                "count)", sink=sink_kind)

    def _begin_write(self, res) -> tuple:
        """→ ``(t0, span)``: the append's start and its open
        ``sink/<kind>`` span, a child of whatever span the calling thread
        has open (the engine's ``sink_write``), under ``res``'s batch."""
        idx = getattr(res, "batch_index", -1)
        span = self._tracer.span(
            f"sink/{self._sink_kind}",
            batch=f"b{idx:08d}" if idx >= 0 else "").open()
        return time.perf_counter(), span

    def _part(self, name: str):
        """``with self._part("encode"):`` — one part of an append, as the
        span ``sink/<name>``."""
        return self._tracer.span(f"sink/{name}")

    def _observe_write(self, t0: float, span, rows: int, nbytes: int,
                       columns: Optional[tuple] = None) -> None:
        """``columns``: the part's ``(plain, dictionary)`` column counts,
        from a sink that has ``parts``."""
        t1 = time.perf_counter()
        self._m_write.observe(t1 - t0)
        self._m_rows.inc(rows)
        if nbytes:
            self._m_bytes.inc(nbytes)
        if columns is not None:
            self._m_plain_cols.inc(columns[0])
            self._m_dict_cols.inc(columns[1])
        span.close(t0, t1, rows=rows, bytes=nbytes)

    def _fail_write(self, t0: float, span) -> None:
        self._m_failures.inc()
        span.close(t0, time.perf_counter(), failed=True)


def _result_to_columns(res) -> dict:
    """BatchResult → analyzed_transactions column dict."""
    now_us = int(time.time() * 1e6)
    n = len(res.tx_id)
    cols = {
        "tx_id": res.tx_id.astype(np.int64),
        "tx_datetime_us": res.tx_datetime_us.astype(np.int64),
        "customer_id": res.customer_id.astype(np.int64),
        "terminal_id": res.terminal_id.astype(np.int64),
        "tx_amount": res.amount_cents.astype(np.float64) / 100.0,
    }
    # feature columns, lower-cased like the reference table DDL
    for i, name in enumerate(FEATURE_NAMES):
        if name == "TX_AMOUNT":
            continue
        dt = np.int32 if ("NB_TX" in name or "DURING" in name) else np.float64
        cols[name.lower()] = res.features[:, i].astype(dt)
    cols["processed_at_us"] = np.full(n, now_us, dtype=np.int64)
    cols["prediction"] = res.probs.astype(np.float64)
    return cols


# A column keeps its dictionary while the dictionary page is smaller than
# this many bytes: 1,024 eight-byte values, as many as Arrow's writer takes
# between two looks at the limit, so a smaller one does the same. Past it
# the writer leaves the page as it is and stores the rest of the column
# plain. pyarrow's own limit is 1 MiB, under which a 65,536-row column of
# ids, averages or probabilities hashes every value into a table that
# outgrows the cache and then writes a dictionary as large as the values:
# 21 of a 58 ms write on the benchmark's host, and a larger file (PERF.md,
# PR 45, which also tried 4,096 to 65,536 and a list of columns).
_DICTIONARY_PAGE_LIMIT = 8_192

# Parquet physical type -> bytes a value stored plain
_PLAIN_WIDTH = {"INT32": 4, "FLOAT": 4, "INT64": 8, "DOUBLE": 8}


def _encode_part(table, where, tracer) -> tuple:
    """Encode one batch's table as its Parquet part, under the span
    ``sink/encode``: the one way a sink writes a part. ``where`` is a path,
    or None for the bytes. → ``(data, columns)``: the bytes (None for a
    path) and how many columns ended up stored each way, ``(plain,
    dictionary)`` (:func:`_part_columns`).

    pyarrow's defaults (format and data-page version, snappy, column
    statistics), but for ``_DICTIONARY_PAGE_LIMIT``: what a column holds
    decides its encoding, column by column and batch by batch, and a
    reader sees the same table either way."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    collected: list = []
    with tracer.span("sink/encode"):
        out = pa.BufferOutputStream() if where is None else where
        pq.write_table(table, out,
                       dictionary_pagesize_limit=_DICTIONARY_PAGE_LIMIT,
                       metadata_collector=collected)
        data = out.getvalue().to_pybytes() if where is None else None
        columns = _part_columns(collected[0])
    return data, columns


def _part_columns(metadata) -> tuple:
    """→ ``(plain, dictionary)`` column counts of a part, from the
    ``FileMetaData`` its writer returned (nothing is read back).

    A column that fell back to plain keeps its first dictionary page, so
    the chunk's ``encodings`` and ``has_dictionary_page`` read alike either
    way; its size tells. With a kept dictionary (under the limit, so at
    most 11-bit indices) the chunk's uncompressed bytes less the limit stay
    under 1.4 bytes a row; after a fall-back they are at least the width
    of every row written after it. Plain: no dictionary page, or those
    bytes reach nine tenths of rows x width."""
    groups = [metadata.row_group(g) for g in range(metadata.num_row_groups)]
    plain = 0
    for j in range(metadata.num_columns):
        chunks = [group.column(j) for group in groups]  # one, as a rule
        width = _PLAIN_WIDTH.get(metadata.schema.column(j).physical_type)
        stored = sum(c.total_uncompressed_size for c in chunks)
        rows = sum(c.num_values for c in chunks)
        plain += not any(c.has_dictionary_page for c in chunks) or (
            width is not None
            and stored - _DICTIONARY_PAGE_LIMIT * len(chunks)
            >= 0.9 * rows * width)
    return plain, metadata.num_columns - plain


class FanoutSink:
    """Append to several sinks; ``flush()`` propagates to those that have it
    (the raw-transactions table needs a flush; Parquet/memory don't)."""

    def __init__(self, *sinks):
        self.sinks = [s for s in sinks if s is not None]

    def append(self, res) -> None:
        for s in self.sinks:
            s.append(res)

    def flush(self) -> None:
        for s in self.sinks:
            f = getattr(s, "flush", None)
            if f is not None:
                f()

    def truncate_after(self, batch_index: int) -> None:
        for s in self.sinks:
            f = getattr(s, "truncate_after", None)
            if f is not None:
                f(batch_index)


class _SinkError:
    """Box for the writer thread's first failure (kept with its batch
    index so the re-raise on the loop thread names what was lost)."""

    __slots__ = ("exc", "batch_index")

    def __init__(self, exc: BaseException, batch_index: int):
        self.exc = exc
        self.batch_index = batch_index


class AsyncSink:
    """One ordered writer thread for ``append`` — the engine loop's own
    (``runtime/engine.py::ScoringEngine.run`` wraps the sink it is given
    in one for the length of the run; nothing else does).

    A sink write (parquet encode + rename, an object-store PUT, an
    Iceberg commit) needs neither the chip nor the loop's state, so it
    runs here while the loop thread polls, preps and dispatches:

    - **Ordered**: one writer thread drains a FIFO queue, so the inner
      sink sees appends in exactly the loop's order (part-file naming,
      raw-table flush cadence, and fanout ordering are unchanged).
    - **Bounded + backpressured**: the queue holds at most
      ``max_queue`` batch results; a full queue blocks the loop thread
      (never unbounded host memory), and the blocked time is accounted
      in ``rtfds_sink_backpressure_seconds_total`` so a sink that can't
      keep up is visible, not silent. Queue occupancy rides
      ``rtfds_sink_queue_depth``.
    - **Errors propagate**: a writer-thread failure is re-raised on the
      loop thread at the next ``append``/``drain``/``flush`` — with its
      ORIGINAL exception type, so the supervisor's type-based
      ``recover_on`` policy (OSError is recoverable, a bug is not)
      applies exactly as it would to an inline write. The stream crashes
      (and recovery replays) instead of silently dropping output; while
      the failure is pending the writer discards queued results (their
      batches replay from the checkpoint anyway), and the re-raise
      clears it so a recovered incarnation resumes writing.
    - **Drain contract**: ``drain()`` blocks until every queued append
      has landed in the inner sink. ``flush``/``truncate_after``/
      ``read_all``/``concat`` drain first, and the engine drains before
      every checkpoint save and before ``run()`` returns — so
      checkpointed offsets keep TRAILING durable sink output (the
      exactly-once invariant in ``runtime/engine.py``'s checkpoint
      block: a crash replays rows, never skips them, and replayed
      ``batch_index`` parts overwrite).

    ``write(inner, res, ctx, t_queued)``, where given, runs on the writer
    thread in place of ``inner.append(res)``: the engine's hook for what
    belongs around the write where it happens (its span, its duration).
    ``ctx`` is whatever ``append`` was handed beside the result;
    ``t_queued`` the ``perf_counter`` reading as the enqueue returned
    (None where the writer took the batch off before that), so the hook
    can say how long the batch lay in the queue.
    """

    _STOP = object()

    def __init__(self, inner, max_queue: int = 8, registry=None,
                 write=None):
        if inner is None:
            raise ValueError("AsyncSink needs an inner sink")
        self.inner = inner
        self._write = write
        try:
            # The sinks import pyarrow lazily, inside append — which runs
            # on a thread that ends with the run. pyarrow (25.0) first
            # imported by a thread that has since exited segfaults in a
            # later pyarrow.dataset read (tools/parquet_sql_check.py did):
            # import it on the thread that starts the writer.
            import pyarrow  # noqa: F401
        except ImportError:
            pass
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(max_queue)))
        self._error: Optional[_SinkError] = None
        # injectable like the engine's registry, so per-run before/after
        # measurements don't cross-contaminate the process-wide series
        reg = registry if registry is not None else get_registry()
        kind = type(inner).__name__
        self._m_depth = reg.gauge(
            "rtfds_sink_queue_depth",
            "batch results queued for the async sink writer", sink=kind)
        self._m_backpressure = reg.counter(
            "rtfds_sink_backpressure_seconds_total",
            "loop-thread seconds blocked on a full async sink queue",
            sink=kind)
        self._thread = threading.Thread(
            target=self._writer, daemon=True, name="rtfds-sink-writer")
        self._thread.start()

    # -- writer thread -----------------------------------------------------

    def _writer(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is self._STOP:
                    return
                if self._error is None:
                    res, ctx, t_queued = item
                    try:
                        if self._write is None:
                            # rtfdslint: disable=cross-thread-race (drain() is the guard: every loop-side inner access — flush/truncate_after/read_all/concat — calls drain() first, and q.join() orders every writer append strictly before it; crash/replay lineage tests pin the contract)
                            self.inner.append(res)
                        else:
                            self._write(self.inner, res, ctx, t_queued)
                    # rtfdslint: disable=broad-exception-catch (thread-boundary transport: the writer parks the ORIGINAL exception; append/drain re-raise it typed on the loop thread for the supervisor's recover_on policy)
                    except BaseException as e:  # propagate to loop thread
                        self._error = _SinkError(
                            e, int(getattr(res, "batch_index", -1)))
                        from real_time_fraud_detection_system_tpu.utils \
                            import get_logger

                        get_logger("sink").warning(
                            "async sink write failed on batch %d (%s: %s);"
                            " surfacing to the serving loop",
                            self._error.batch_index, type(e).__name__, e)
                # while a failure is pending: keep draining (so drain()
                # never deadlocks) but write nothing — those batches
                # replay from the checkpoint after recovery
            finally:
                self._q.task_done()
                self._m_depth.set(self._q.qsize())

    def _raise_pending(self) -> None:
        err = self._error
        if err is not None:
            # Clear-then-raise: the raise hands ownership to the engine/
            # supervisor; a recovered incarnation (same sink object,
            # replayed batches) must resume writing, not re-crash on a
            # stale box. The ORIGINAL exception object is raised so the
            # supervisor's recover_on type policy sees what an inline
            # write would have thrown.
            self._error = None
            raise err.exc

    # -- sink API (loop thread) --------------------------------------------

    def append(self, res, ctx=None) -> None:
        self._raise_pending()
        t0 = time.perf_counter()
        item = [res, ctx, None]
        # blocks when full: bounded-memory backpressure
        self._q.put(item)
        item[2] = t1 = time.perf_counter()
        waited = t1 - t0
        if waited > 1e-4:  # an uncontended put is ~µs; only count blocks
            self._m_backpressure.inc(waited)
        self._m_depth.set(self._q.qsize())

    @property
    def idle(self) -> bool:
        """Nothing queued and nothing being written (read on the thread
        that appends, nothing can arrive meanwhile)."""
        return self._q.unfinished_tasks == 0

    def drain(self) -> None:
        """Block until every queued append has landed (or failed) in the
        inner sink; re-raise any writer failure on this thread."""
        self._q.join()
        self._raise_pending()

    def flush(self) -> None:
        self.drain()
        f = getattr(self.inner, "flush", None)
        if f is not None:
            f()

    def truncate_after(self, batch_index: int) -> None:
        # drain first: a queued part beyond the fence must land before
        # the fence can see (and remove) it
        self.drain()
        f = getattr(self.inner, "truncate_after", None)
        if f is not None:
            f(batch_index)

    def read_all(self) -> dict:
        self.drain()
        return self.inner.read_all()

    def concat(self) -> dict:
        self.drain()
        return self.inner.concat()

    def stop(self) -> None:
        """Let every queued append land, then end the writer thread. Raises
        nothing: for a ``finally`` in which the loop's own exception, if
        there is one, must stay the one that propagates."""
        if self._thread.is_alive():
            self._q.join()
            self._q.put(self._STOP)
            self._thread.join(timeout=30.0)

    def close(self) -> None:
        """Drain, stop the writer thread, and surface any pending error."""
        self.stop()
        self._raise_pending()


class MemorySink:
    def __init__(self):
        self.batches: List[dict] = []

    def append(self, res) -> None:
        self.batches.append(_result_to_columns(res))

    def concat(self) -> dict:
        if not self.batches:
            return {}
        keys = self.batches[0].keys()
        return {k: np.concatenate([b[k] for b in self.batches]) for k in keys}


class ConsoleSink:
    def __init__(self, every: int = 1, limit: int = 5):
        self.every = every
        self.limit = limit
        self._n = 0

    def append(self, res) -> None:
        self._n += 1
        if self._n % self.every:
            return
        n = len(res.tx_id)
        print(f"[batch {self._n}] rows={n} p(fraud): "
              f"mean={res.probs.mean():.4f} max={res.probs.max():.4f}")
        for i in range(min(self.limit, n)):
            print(
                f"  tx {res.tx_id[i]} cust {res.customer_id[i]} "
                f"amt {res.amount_cents[i] / 100:.2f} -> {res.probs[i]:.4f}"
            )


def _part_order(name: str):
    """Deterministic part ordering for mixed naming schemes.

    Indexed parts (``part-<batch_index>``, checkpointed runs) sort
    NUMERICALLY first — lexicographic order breaks once an 8-digit index
    and a 13-digit ms-timestamp stem share a leading digit. Timestamp
    parts (``part-<ms>-<seq>``, un-checkpointed runs) follow, by name
    (their stems are zero-padded, so name order is write order). Mixing
    the two schemes under one directory/prefix means the run switched
    checkpointing mid-lineage; ``truncate_after`` fences only the indexed
    lineage (timestamp parts carry no replay semantics to fence).
    """
    base = name.rsplit("/", 1)[-1]
    stem = base[len("part-"):-len(".parquet")] \
        if base.startswith("part-") and base.endswith(".parquet") else ""
    if stem.isdigit():
        return (0, int(stem), "")
    return (1, 0, name)


class ParquetSink(_SinkTelemetry):
    """One part file per batch: ``<dir>/part-<batch_index>.parquet``.

    Exactly-once across crash-replay: part files are named by the
    engine's monotone ``batch_index`` (which survives checkpoint
    restore), so a replayed batch atomically OVERWRITES its own part
    instead of appending a duplicate — the role Spark's sink commit
    protocol plays for the reference's Iceberg append
    (``fraud_detection.py:204-211``). Writes are tmp+rename, never
    torn for concurrent readers. Results without an index (direct
    ``append`` of hand-built batches) fall back to sequence naming.
    """

    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self._seq = 0
        self._init_sink_metrics("parquet", parts=True)

    def append(self, res) -> None:
        import pyarrow as pa

        t0, span = self._begin_write(res)
        try:
            with self._part("convert"):
                cols = _result_to_columns(res)
                table = pa.table({k: pa.array(v) for k, v in cols.items()})
            idx = getattr(res, "batch_index", -1)
            if idx >= 0:
                name = f"part-{idx:08d}.parquet"
            else:
                name = (f"part-{int(time.time() * 1e3)}-"
                        f"{self._seq:06d}.parquet")
                self._seq += 1
            path = os.path.join(self.directory, name)
            tmp = path + ".tmp"
            _, columns = _encode_part(table, tmp, self._tracer)
            with self._part("commit"):
                nbytes = os.path.getsize(tmp)
                os.replace(tmp, path)
        except Exception:
            self._fail_write(t0, span)
            raise
        self._observe_write(t0, span, len(res.tx_id), nbytes, columns)

    def truncate_after(self, batch_index: int) -> None:
        """Drop indexed parts beyond ``batch_index`` — the sink-side
        restore fence. Replay after a checkpoint restore may re-batch the
        backlog differently (e.g. a Kafka drain coalescing into fewer,
        larger batches), so parts the replay won't overwrite must go, or
        their rows would double on disk. A fresh run (restore to 0)
        clears the whole indexed lineage."""
        for f in os.listdir(self.directory):
            if not (f.startswith("part-") and f.endswith(".parquet")):
                continue
            stem = f[len("part-"):-len(".parquet")]
            if stem.isdigit() and int(stem) > batch_index:
                os.remove(os.path.join(self.directory, f))

    def read_all(self) -> dict:
        import pyarrow.parquet as pq
        import pyarrow as pa

        files = sorted(
            (os.path.join(self.directory, f)
             for f in os.listdir(self.directory)
             if f.endswith(".parquet")),
            key=_part_order,
        )
        if not files:
            return {}
        table = pa.concat_tables([pq.read_table(f) for f in files])
        return {c: table[c].to_numpy() for c in table.column_names}


class StoreParquetSink(_SinkTelemetry):
    """:class:`ParquetSink` semantics over an object store (S3/MinIO).

    The reference lands all streaming output on MinIO
    (``s3a://commerce/warehouse``, ``kafka_s3_sink_transactions.py`` /
    ``fraud_detection.py:204-211``); this sink writes the same
    part-per-batch parquet layout through any :mod:`..io.store` object.
    Exactly-once naming is identical to :class:`ParquetSink`
    (``part-<batch_index>`` overwrite-on-replay); object PUTs are atomic,
    so there is no tmp+rename dance. ``truncate_after`` is the same
    sink-side restore fence.
    """

    def __init__(self, store):
        self.store = store
        self._seq = 0
        self._init_sink_metrics("store_parquet", parts=True)

    def append(self, res) -> None:
        import pyarrow as pa

        t0, span = self._begin_write(res)
        try:
            with self._part("convert"):
                cols = _result_to_columns(res)
                table = pa.table({k: pa.array(v) for k, v in cols.items()})
            idx = getattr(res, "batch_index", -1)
            if idx >= 0:
                name = f"part-{idx:08d}.parquet"
            else:
                name = (f"part-{int(time.time() * 1e3)}-"
                        f"{self._seq:06d}.parquet")
                self._seq += 1
            data, columns = _encode_part(table, None, self._tracer)
            with self._part("commit"):
                self.store.put(name, data)
        except Exception:
            self._fail_write(t0, span)
            raise
        self._observe_write(t0, span, len(res.tx_id), len(data), columns)

    def truncate_after(self, batch_index: int) -> None:
        for key in self.store.list(""):
            f = key.rsplit("/", 1)[-1]
            if not (f.startswith("part-") and f.endswith(".parquet")):
                continue
            stem = f[len("part-"):-len(".parquet")]
            if stem.isdigit() and int(stem) > batch_index:
                self.store.delete(key)

    def read_all(self) -> dict:
        import io as _io

        import pyarrow as pa
        import pyarrow.parquet as pq

        keys = sorted((k for k in self.store.list("")
                       if k.endswith(".parquet")), key=_part_order)
        if not keys:
            return {}
        table = pa.concat_tables(
            [pq.read_table(_io.BytesIO(self.store.get(k))) for k in keys]
        )
        return {c: table[c].to_numpy() for c in table.column_names}


def _dlq_row_record(cols: dict, i: int, *, reason: str, error: str,
                    batch_index: int, offsets, trace_id: str,
                    envelope: Optional[bytes]) -> dict:
    """One quarantined row as a JSON-able record: decoded columns where
    available, the raw envelope bytes when the caller still has them,
    and the error/lineage metadata an operator needs to triage it."""
    def scalar(v):
        x = v[i]
        try:
            return x.item()
        except AttributeError:
            return x

    rec = {
        "tx_id": int(cols["tx_id"][i]),
        "reason": reason,
        "error": str(error)[:500],
        "batch_index": int(batch_index),
        "offsets": [int(o) for o in offsets] if offsets is not None
        else None,
        "trace_id": trace_id or "",
        "t": time.time(),
        "columns": {k: scalar(v) for k, v in cols.items()},
    }
    if envelope is not None:
        import base64

        rec["envelope_b64"] = base64.b64encode(bytes(envelope)).decode()
    return rec


class _DeadLetterTelemetry:
    """Shared DLQ instrumentation + flight-record events. The absolute
    row gauge (``rtfds_dead_letter_rows``) is what ``/healthz`` keys its
    ``degraded`` state on.

    ``recorder_fn`` overrides where flight events land (a zero-arg
    callable returning a recorder or None): the overload spill reuses
    this machinery with a private registry and its own ``shed`` events —
    deferred-for-replay rows are NOT a triage backlog and must not trip
    the DLQ ``degraded`` state or the dead-letter dashboard tile."""

    def _init_dlq_metrics(self, registry=None, recorder_fn=None) -> None:
        from real_time_fraud_detection_system_tpu.utils.metrics import (
            active_recorder,
        )

        self._reg = registry if registry is not None else get_registry()
        self._recorder = (recorder_fn if recorder_fn is not None
                          else active_recorder)
        self._m_gauge = self._reg.gauge(
            "rtfds_dead_letter_rows",
            "rows currently quarantined in the dead-letter queue")

    def _observe_put(self, written: int, reason: str, batch_index: int,
                     total: int) -> None:
        if written:
            self._reg.counter(
                "rtfds_dead_letter_rows_total",
                "rows quarantined to the dead-letter queue by reason",
                reason=reason).inc(written)
        self._m_gauge.set(total)
        rec = self._recorder()
        if rec is not None and written:
            rec.record_event("dead_letter", rows=written, reason=reason,
                             batch=int(batch_index))


class DeadLetterSink(_DeadLetterTelemetry):
    """JSONL dead-letter queue — one record per quarantined row.

    The quarantine side of the supervisor's poison-isolation path
    (``runtime/faults.run_with_recovery``) and the engine's non-finite
    guard: instead of a poison row killing the stream (or silently
    contaminating feature state), its raw envelope bytes (when known),
    decoded columns, error type/message, batch index, offsets, and trace
    id land here and the stream continues past it. **Idempotent by
    tx_id**: already-quarantined rows are skipped on write (the seen-set
    is rebuilt from the file on open), so a crash mid-bisection followed
    by checkpoint replay neither loses nor duplicates DLQ rows, and
    ``read_all`` additionally dedups latest-wins. Inspect/replay with
    ``rtfds dlq``.
    """

    def __init__(self, path: str, registry=None, recorder_fn=None):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._lock = threading.Lock()
        self._seen: set = set()
        self._init_dlq_metrics(registry, recorder_fn)
        if os.path.exists(path):
            for rec in self._iter_file():
                self._seen.add(int(rec["tx_id"]))
        self._f = open(path, "a", encoding="utf-8")
        self._m_gauge.set(len(self._seen))

    def _iter_file(self):
        import json

        with open(self.path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail after a crash: skip
                if "tx_id" in rec:
                    yield rec

    def put_rows(self, cols: dict, *, reason: str, error: str = "",
                 errors: Optional[List[str]] = None, batch_index: int = -1,
                 offsets=None, trace_id: str = "",
                 envelopes: Optional[List[bytes]] = None) -> int:
        """Quarantine every row of ``cols`` (a columnar dict as polled);
        rows whose tx_id is already quarantined are skipped. ``errors``
        optionally carries a per-row message (bisection knows each row's
        exception); ``error`` is the shared fallback. Returns the number
        of rows actually written."""
        import json

        n = len(cols["tx_id"])
        written = 0
        with self._lock:
            for i in range(n):
                tx = int(cols["tx_id"][i])
                if tx in self._seen:
                    continue
                rec = _dlq_row_record(
                    cols, i, reason=reason,
                    error=errors[i] if errors is not None else error,
                    batch_index=batch_index, offsets=offsets,
                    trace_id=trace_id,
                    envelope=envelopes[i] if envelopes is not None
                    else None)
                self._f.write(json.dumps(rec, separators=(",", ":"),
                                         default=str) + "\n")
                self._seen.add(tx)
                written += 1
            self._f.flush()
        self._observe_put(written, reason, batch_index, len(self._seen))
        return written

    def read_all(self) -> List[dict]:
        """Quarantined rows, deduped by tx_id (latest record wins),
        ordered by (batch_index, tx_id)."""
        with self._lock:
            self._f.flush()
        by_tx = {}
        for rec in self._iter_file():
            by_tx[int(rec["tx_id"])] = rec
        return sorted(by_tx.values(),
                      key=lambda r: (r.get("batch_index", -1), r["tx_id"]))

    def tx_ids(self) -> List[int]:
        return sorted(self._seen)

    def __len__(self) -> int:
        return len(self._seen)

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()


class ParquetDeadLetterSink(_DeadLetterTelemetry):
    """:class:`DeadLetterSink` semantics as parquet parts under a
    directory — the variant whose output any Iceberg/Trino/DuckDB reader
    can mount next to the analyzed table. One part per quarantine call
    (``dlq-<batch_index>-<reason>.parquet``), so a checkpoint replay
    that re-isolates the same batch atomically OVERWRITES its own part
    instead of duplicating rows — the same exactly-once naming trick as
    :class:`ParquetSink`. The tx_id seen-set is rebuilt from the parts
    on open (write-side idempotence across restarts)."""

    def __init__(self, directory: str, registry=None, recorder_fn=None):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self._lock = threading.Lock()
        self._seen: set = set()
        self._init_dlq_metrics(registry, recorder_fn)
        for rec in self.read_all():
            self._seen.add(int(rec["tx_id"]))
        self._m_gauge.set(len(self._seen))

    def put_rows(self, cols: dict, *, reason: str, error: str = "",
                 errors: Optional[List[str]] = None, batch_index: int = -1,
                 offsets=None, trace_id: str = "",
                 envelopes: Optional[List[bytes]] = None) -> int:
        import json

        import pyarrow as pa
        import pyarrow.parquet as pq

        n = len(cols["tx_id"])
        recs = []
        with self._lock:
            for i in range(n):
                tx = int(cols["tx_id"][i])
                if tx in self._seen:
                    continue
                recs.append(_dlq_row_record(
                    cols, i, reason=reason,
                    error=errors[i] if errors is not None else error,
                    batch_index=batch_index, offsets=offsets,
                    trace_id=trace_id,
                    envelope=envelopes[i] if envelopes is not None
                    else None))
            if recs:
                flat = [{
                    **{k: v for k, v in r.items()
                       if k not in ("columns", "offsets")},
                    "columns_json": json.dumps(r["columns"], default=str),
                    "offsets_json": json.dumps(r["offsets"]),
                } for r in recs]
                name = f"dlq-{max(int(batch_index), 0):08d}-{reason}.parquet"
                path = os.path.join(self.directory, name)
                if os.path.exists(path):
                    # A later quarantine for the SAME (batch, reason) —
                    # e.g. the nan-guard rescore flushing out a second
                    # row — must MERGE with the part, not replace it:
                    # the seen-set skips rows already on disk, so a
                    # plain overwrite would silently drop them.
                    new_ids = {int(r["tx_id"]) for r in flat}
                    keys = list(flat[0])
                    flat = [{k: row.get(k) for k in keys}
                            for row in pq.read_table(path).to_pylist()
                            if int(row.get("tx_id", -1)) not in new_ids
                            ] + flat
                table = pa.table({
                    k: pa.array([r.get(k) for r in flat])
                    for k in flat[0]
                })
                tmp = path + ".tmp"
                pq.write_table(table, tmp)
                os.replace(tmp, path)
                for r in recs:
                    self._seen.add(int(r["tx_id"]))
        self._observe_put(len(recs), reason, batch_index, len(self._seen))
        return len(recs)

    def read_all(self) -> List[dict]:
        import json

        import pyarrow.parquet as pq

        by_tx = {}
        if not os.path.isdir(self.directory):
            return []
        for f in sorted(os.listdir(self.directory)):
            if not (f.startswith("dlq-") and f.endswith(".parquet")):
                continue
            table = pq.read_table(os.path.join(self.directory, f))
            for row in table.to_pylist():
                rec = dict(row)
                rec["columns"] = json.loads(rec.pop("columns_json", "{}"))
                off = rec.pop("offsets_json", "null")
                rec["offsets"] = json.loads(off) if off else None
                by_tx[int(rec["tx_id"])] = rec
        return sorted(by_tx.values(),
                      key=lambda r: (r.get("batch_index", -1), r["tx_id"]))

    def tx_ids(self) -> List[int]:
        return sorted(self._seen)

    def __len__(self) -> int:
        return len(self._seen)

    def close(self) -> None:
        pass


def make_dead_letter_sink(path: str, registry=None, recorder_fn=None):
    """``*.jsonl`` (or an existing plain file) → :class:`DeadLetterSink`;
    anything else → :class:`ParquetDeadLetterSink` directory."""
    if path.endswith(".jsonl") or os.path.isfile(path):
        return DeadLetterSink(path, registry=registry,
                              recorder_fn=recorder_fn)
    return ParquetDeadLetterSink(path, registry=registry,
                                 recorder_fn=recorder_fn)


def read_dead_letter(path: str) -> List[dict]:
    """Read-only DLQ load for inspection/replay (``rtfds dlq``): never
    creates the file/directory, raises FileNotFoundError when absent."""
    if os.path.isfile(path):
        s = DeadLetterSink(path)
        try:
            return s.read_all()
        finally:
            s.close()
    if os.path.isdir(path):
        return ParquetDeadLetterSink(path).read_all()
    raise FileNotFoundError(f"no dead-letter queue at {path!r}")


def make_parquet_sink(path_or_url: str, **store_kwargs):
    """``s3://bucket/prefix`` → :class:`StoreParquetSink` (via
    :func:`..io.store.make_store`, which honors ``RTFDS_S3_ENDPOINT`` for
    MinIO); local path → :class:`ParquetSink`."""
    if path_or_url.startswith("s3://"):
        from real_time_fraud_detection_system_tpu.io.store import make_store

        return StoreParquetSink(make_store(path_or_url, **store_kwargs))
    return ParquetSink(path_or_url)


class IcebergSink(_SinkTelemetry):
    """Append scored rows to an Iceberg ``analyzed_transactions`` table.

    The reference's scorer streams into ``nessie.payment.
    analyzed_transactions`` (DDL at ``fraud_detection.py:136-163``,
    appended at ``:204-211``), which Trino/Superset read. This sink
    appends the same column layout through a pyiceberg catalog:
    timestamps as µs-precision Arrow timestamps, amount/prediction as
    doubles, window counts as int32.

    ``catalog`` is injectable (duck-typed ``load_table``/``create_table``)
    so tests run against a fake without pyiceberg; production use goes
    through :func:`make_iceberg_sink`, which builds a real catalog from
    ``pyiceberg.catalog.load_catalog``.
    """

    TABLE_DEFAULT = "payment.analyzed_transactions"

    def __init__(self, catalog, table_name: str = TABLE_DEFAULT):
        self.catalog = catalog
        self.table_name = table_name
        self.table = self._load_or_create(catalog, table_name)
        self._init_sink_metrics("iceberg")

    @staticmethod
    def arrow_schema():
        import pyarrow as pa

        fields = [
            ("tx_id", pa.int64()),
            ("tx_datetime", pa.timestamp("us")),
            ("customer_id", pa.int64()),
            ("terminal_id", pa.int64()),
            ("tx_amount", pa.float64()),
        ]
        for name in FEATURE_NAMES:
            if name == "TX_AMOUNT":
                continue
            t = (
                pa.int32()
                if ("NB_TX" in name or "DURING" in name)
                else pa.float64()
            )
            fields.append((name.lower(), t))
        fields += [
            ("processed_at", pa.timestamp("us")),
            ("prediction", pa.float64()),
        ]
        return pa.schema(fields)

    def _load_or_create(self, catalog, name: str):
        exists = getattr(catalog, "table_exists", None)
        if exists is not None and not exists(name):
            return catalog.create_table(name, schema=self.arrow_schema())
        try:
            return catalog.load_table(name)
        except Exception as e:
            # Only a missing table warrants create; transient catalog
            # errors (network/auth) must surface, not turn into a
            # confusing create-conflict downstream.
            if type(e).__name__ in ("NoSuchTableError", "KeyError"):
                return catalog.create_table(name, schema=self.arrow_schema())
            raise

    def _to_arrow(self, res):
        import pyarrow as pa

        cols = _result_to_columns(res)
        arrays, names = [], []
        for field in self.arrow_schema():
            if field.name == "tx_datetime":
                v = cols["tx_datetime_us"]
            elif field.name == "processed_at":
                v = cols["processed_at_us"]
            else:
                v = cols[field.name]
            arrays.append(pa.array(v).cast(field.type))
            names.append(field.name)
        return pa.table(dict(zip(names, arrays)))

    def append(self, res) -> None:
        t0, span = self._begin_write(res)
        try:
            with self._part("convert"):
                tbl = self._to_arrow(res)
            with self._part("commit"):
                self.table.append(tbl)
        except Exception:
            self._fail_write(t0, span)
            raise
        self._observe_write(t0, span, len(res.tx_id), tbl.nbytes)


def make_iceberg_sink(
    table_name: str = IcebergSink.TABLE_DEFAULT,
    catalog_name: str = "default",
    catalog: Optional[object] = None,
    **catalog_props,
) -> IcebergSink:
    """Production Iceberg sink factory (import-gated on pyiceberg).

    ``catalog_props`` go straight to ``pyiceberg.catalog.load_catalog``
    (URI, warehouse, credentials — the values the reference spreads over
    ``docker-compose.yml:58-68`` and every SparkConf block).
    """
    if catalog is None:
        try:
            from pyiceberg.catalog import load_catalog
        except ImportError as e:
            raise ImportError(
                "pyiceberg is not installed; ParquetSink output is Iceberg-"
                "compatible (add files to a table via any catalog), or "
                "install pyiceberg in production images."
            ) from e
        catalog = load_catalog(catalog_name, **catalog_props)
    return IcebergSink(catalog, table_name)
