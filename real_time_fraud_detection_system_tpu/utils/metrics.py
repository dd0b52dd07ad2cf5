"""Unified telemetry: metrics registry, renderers, flight recorder, HTTP.

The reference's observability is ``print()`` plus whatever the Spark UI
happens to show (SURVEY §5.1/§5.5); the framework previously had only
fragments (``utils/timing.LatencyTracker``, per-module log lines). This
module is the one measurement substrate every layer reports into:

- :class:`MetricsRegistry` — process-wide, thread-safe Counter / Gauge /
  Histogram registry (histograms use fixed log-spaced latency buckets so
  series from different runs are mergeable), with two renderers: the
  Prometheus text exposition format (:meth:`~MetricsRegistry.
  render_prometheus`) and a JSON snapshot (:meth:`~MetricsRegistry.
  snapshot`).
- :class:`FlightRecorder` — one JSONL record per micro-batch (batch id,
  rows, per-phase timings, queue depth) plus event records (checkpoint,
  feedback, fault injection, restart), all under a run manifest
  (:func:`run_manifest`: config hash, backend, mesh shape, model kind,
  start time). The per-phase breakdown is what makes bottleneck
  attribution — and therefore every later perf PR — possible
  (arXiv:1612.01437's lesson for Spark ML pipelines applies verbatim).
- :class:`MetricsServer` — a stdlib-only background HTTP server exposing
  ``/metrics`` (Prometheus text), ``/metrics.json`` (snapshot) and
  ``/healthz`` (source-lag + last-batch-age thresholds), opt-in from the
  CLI via ``--metrics-port``.

Everything here is stdlib + nothing: importable from the hottest paths
(sources, sinks, the engine loop) without pulling jax/numpy.
"""

from __future__ import annotations

import json
import threading
import time
from bisect import bisect_left
from typing import Dict, List, Optional, Tuple

# Fixed log-spaced latency ladder (1-2.5-5 per decade, 10µs .. 60s).
# Shared by every duration histogram in the framework so per-phase,
# source, sink, and checkpoint series line up bucket-for-bucket.
LATENCY_BUCKETS_S: Tuple[float, ...] = (
    1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
    1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 60.0,
)


def _fmt_num(v: float) -> str:
    """Prometheus sample/`le` formatting: shortest exact-ish repr."""
    if v == float("inf"):
        return "+Inf"
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _label_str(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label(str(v))}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class Counter:
    """Monotone float counter."""

    __slots__ = ("labels", "_v", "_lock")

    def __init__(self, labels: Dict[str, str]):
        self.labels = labels
        self._v = 0.0
        self._lock = threading.Lock()

    def inc(self, v: float = 1.0) -> None:
        if v < 0:
            raise ValueError("counters only go up; use a Gauge")
        with self._lock:
            self._v += v

    @property
    def value(self) -> float:
        return self._v


class Gauge:
    """Last-write-wins instantaneous value."""

    __slots__ = ("labels", "_v", "_lock")

    def __init__(self, labels: Dict[str, str]):
        self.labels = labels
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._v = float(v)

    def inc(self, v: float = 1.0) -> None:
        with self._lock:
            self._v += v

    @property
    def value(self) -> float:
        return self._v


class Histogram:
    """Fixed-bucket histogram (Prometheus cumulative-`le` semantics).

    Percentiles are estimated by linear interpolation inside the owning
    bucket — good to a bucket width, plenty for dashboards; exact
    percentiles stay the job of :class:`~.timing.LatencyTracker`'s
    reservoir where the engine needs them.
    """

    __slots__ = ("labels", "bounds", "_counts", "_sum", "_count", "_lock")

    def __init__(self, labels: Dict[str, str],
                 buckets: Tuple[float, ...] = LATENCY_BUCKETS_S):
        self.labels = labels
        self.bounds = tuple(sorted(float(b) for b in buckets))
        if not self.bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self._counts = [0] * (len(self.bounds) + 1)  # +1: the +Inf bucket
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        i = bisect_left(self.bounds, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def cumulative(self) -> List[Tuple[float, int]]:
        """[(le_bound, cumulative_count)] including (+Inf, total)."""
        out = []
        acc = 0
        with self._lock:
            counts = list(self._counts)
        for b, c in zip(self.bounds, counts):
            acc += c
            out.append((b, acc))
        out.append((float("inf"), acc + counts[-1]))
        return out

    def percentile(self, q: float) -> float:
        """Estimated q-th percentile (q in [0, 100]) in observed units."""
        with self._lock:
            counts = list(self._counts)
            total = self._count
        if total == 0:
            return 0.0
        target = total * min(max(q, 0.0), 100.0) / 100.0
        acc = 0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            lo_acc = acc
            acc += c
            if acc >= target:
                lo = 0.0 if i == 0 else self.bounds[i - 1]
                hi = self.bounds[i] if i < len(self.bounds) else lo
                frac = (target - lo_acc) / c
                return lo + (hi - lo) * frac
        return self.bounds[-1]


_TYPES = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class MetricsRegistry:
    """Thread-safe name → (typed metric family) → labeled series store.

    ``counter/gauge/histogram(name, help, **labels)`` is get-or-create:
    hot paths may resolve their series once and hold the object (zero
    lookup cost per event), or re-resolve by name (one dict get under a
    lock). Re-registering a name as a different type raises — a name
    means one thing process-wide.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._meta: Dict[str, Tuple[str, str]] = {}  # name -> (type, help)
        self._series: Dict[str, Dict[Tuple, object]] = {}
        self._hist_buckets: Dict[str, Tuple[float, ...]] = {}

    def _get(self, typ: str, name: str, help_: str, labels: Dict[str, str],
             **kwargs):
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        with self._lock:
            meta = self._meta.get(name)
            if meta is None:
                self._meta[name] = (typ, help_)
                self._series[name] = {}
            elif meta[0] != typ:
                raise ValueError(
                    f"metric {name!r} already registered as {meta[0]}, "
                    f"requested {typ}")
            elif help_ and not meta[1]:
                self._meta[name] = (typ, help_)
            if typ == "histogram":
                # One bucket ladder per family (series must be mergeable
                # and a name means one thing process-wide): an explicit
                # mismatch raises like a type mismatch would; omitted
                # buckets adopt the family's ladder.
                want = kwargs.pop("buckets", None)
                have = self._hist_buckets.get(name)
                if want is not None:
                    want = tuple(sorted(float(b) for b in want))
                    if have is not None and want != have:
                        raise ValueError(
                            f"histogram {name!r} already registered with "
                            f"buckets {have}, requested {want}")
                kwargs["buckets"] = want or have or LATENCY_BUCKETS_S
                self._hist_buckets.setdefault(name, kwargs["buckets"])
            fam = self._series[name]
            m = fam.get(key)
            if m is None:
                m = _TYPES[typ]({k: str(v) for k, v in labels.items()},
                                **kwargs)
                fam[key] = m
            return m

    def counter(self, name: str, help: str = "", **labels) -> Counter:
        return self._get("counter", name, help, labels)

    def gauge(self, name: str, help: str = "", **labels) -> Gauge:
        return self._get("gauge", name, help, labels)

    def histogram(self, name: str, help: str = "",
                  buckets: Optional[Tuple[float, ...]] = None,
                  **labels) -> Histogram:
        """``buckets=None`` adopts the family's ladder (or the default
        :data:`LATENCY_BUCKETS_S` on first registration); an explicit
        ladder that disagrees with the family's raises."""
        return self._get("histogram", name, help, labels, buckets=buckets)

    def get(self, name: str, **labels):
        """Existing series or None (never creates) — the read-side API
        the health checks use."""
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        with self._lock:
            return self._series.get(name, {}).get(key)

    def family_total(self, name: str) -> Optional[float]:
        """Sum of a counter/gauge family's values across ALL label sets
        (e.g. ``rtfds_engine_restarts_total`` over its ``cause`` labels),
        or None when the family was never registered. Read-only — never
        creates. Histogram families have no single total and return
        None."""
        with self._lock:
            fam = self._series.get(name)
            if fam is None:
                return None
            vals = [m.value for m in fam.values()
                    if not isinstance(m, Histogram)]
        if not vals:
            return None
        return float(sum(vals))

    def family_series(self, name: str) -> List[Tuple[Dict[str, str],
                                                     float]]:
        """Read-only ``[(labels, value)]`` rows for a counter/gauge
        family (histograms excluded; [] when never registered) — the
        introspection the healthz per-shard breakdowns use. Never
        creates."""
        with self._lock:
            fam = self._series.get(name)
            if fam is None:
                return []
            return [(dict(m.labels), m.value) for m in fam.values()
                    if not isinstance(m, Histogram)]

    def clear(self) -> None:
        """Drop every registered family (test isolation)."""
        with self._lock:
            self._meta.clear()
            self._series.clear()
            self._hist_buckets.clear()

    def _families(self):
        with self._lock:
            return [
                (name, *self._meta[name], list(fam.values()))
                for name, fam in sorted(self._series.items())
            ]

    # -- renderers -----------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-able snapshot of every family and series."""
        out: Dict[str, dict] = {}
        for name, typ, help_, series in self._families():
            rows = []
            for m in series:
                if isinstance(m, Histogram):
                    rows.append({
                        "labels": m.labels,
                        "count": m.count,
                        "sum": m.sum,
                        "buckets": [[b if b != float("inf") else "+Inf", c]
                                    for b, c in m.cumulative()],
                        "p50": m.percentile(50),
                        "p99": m.percentile(99),
                    })
                else:
                    rows.append({"labels": m.labels, "value": m.value})
            out[name] = {"type": typ, "help": help_, "series": rows}
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition format (version 0.0.4)."""
        lines: List[str] = []
        for name, typ, help_, series in self._families():
            if help_:
                lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} {typ}")
            for m in series:
                if isinstance(m, Histogram):
                    for b, c in m.cumulative():
                        lab = dict(m.labels)
                        lab["le"] = _fmt_num(b)
                        lines.append(
                            f"{name}_bucket{_label_str(lab)} {c}")
                    ls = _label_str(m.labels)
                    lines.append(f"{name}_sum{ls} {_fmt_num(m.sum)}")
                    lines.append(f"{name}_count{ls} {m.count}")
                else:
                    lines.append(
                        f"{name}{_label_str(m.labels)} {_fmt_num(m.value)}")
        return "\n".join(lines) + "\n"


_default_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide default registry every layer reports into."""
    return _default_registry


# ---------------------------------------------------------------------------
# Multi-host aggregation: per-process snapshots → one fleet view
# ---------------------------------------------------------------------------

def merge_process_snapshots(snaps: Dict[str, dict]) -> dict:
    """Merge per-process registry snapshots (``MetricsRegistry.
    snapshot()`` / ``/metrics.json`` payloads) into ONE fleet-wide
    snapshot — the coordinator-side ``/metrics`` aggregation view.

    ``snaps`` maps process id → snapshot. Every series gains a
    ``process=<pid>`` label unless the worker already stamped one (the
    sharded engine labels its per-shard series itself, with GLOBAL
    shard ids, so the merged view reads as one engine's shard space).
    Values are never summed here: aggregation is the scraper's job;
    this view only makes the per-process series distinguishable."""
    out: Dict[str, dict] = {}
    for pid, snap in sorted(snaps.items(), key=lambda kv: str(kv[0])):
        for name, fam in (snap or {}).items():
            dst = out.setdefault(name, {
                "type": fam.get("type"), "help": fam.get("help"),
                "series": []})
            for row in fam.get("series", []):
                labels = dict(row.get("labels") or {})
                labels.setdefault("process", str(pid))
                dst["series"].append({**row, "labels": labels})
    return out


def render_snapshot_prometheus(snap: dict) -> str:
    """Prometheus text for a snapshot dict — the fleet aggregator's
    renderer, emitting the same exposition format as
    :meth:`MetricsRegistry.render_prometheus` (histograms re-expanded
    from their snapshot bucket rows)."""
    lines: List[str] = []
    for name in sorted(snap):
        fam = snap[name]
        if fam.get("help"):
            lines.append(f"# HELP {name} {fam['help']}")
        lines.append(f"# TYPE {name} {fam.get('type', 'gauge')}")
        for row in fam.get("series", []):
            labels = dict(row.get("labels") or {})
            if "buckets" in row:
                for b, c in row["buckets"]:
                    lab = dict(labels)
                    lab["le"] = (str(b) if isinstance(b, str)
                                 else _fmt_num(float(b)))
                    lines.append(f"{name}_bucket{_label_str(lab)} {c}")
                ls = _label_str(labels)
                lines.append(
                    f"{name}_sum{ls} {_fmt_num(float(row['sum']))}")
                lines.append(f"{name}_count{ls} {row['count']}")
            else:
                lines.append(
                    f"{name}{_label_str(labels)} "
                    f"{_fmt_num(float(row['value']))}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def run_manifest(cfg=None, model_kind: str = "", **extra) -> dict:
    """Build the flight-record manifest: everything needed to interpret
    the per-batch records later (config hash, backend, mesh shape, model
    kind, start time). jax is imported lazily so non-jax processes can
    still write flight records."""
    man = {
        "model_kind": model_kind,
        "start_unix_s": time.time(),
        **extra,
    }
    if cfg is not None:
        import dataclasses
        import hashlib

        try:
            blob = json.dumps(dataclasses.asdict(cfg), sort_keys=True,
                              default=str)
        except TypeError:
            blob = repr(cfg)
        man["config_hash"] = hashlib.sha256(blob.encode()).hexdigest()[:16]
    try:
        import jax

        man.setdefault("backend", jax.default_backend())
        man.setdefault("n_devices", jax.device_count())
    except (ImportError, RuntimeError, AttributeError):
        pass  # no backend in this process: manifest still valid
    return man


class FlightRecorder:
    """Append-only JSONL event log, one record per micro-batch.

    Line 1 is the run manifest (``{"kind": "manifest", ...}``); batch
    records carry ``{"kind": "batch", "batch": i, "rows": n, "phases":
    {phase: seconds}, "queue_depth": d, "t": unix}``; everything else
    (checkpoints, feedback applications, fault injections, restarts)
    lands as ``{"kind": "event", "event": name, ...}``. Thread-safe —
    the supervisor and engine threads may interleave events. Writes are
    line-buffered appends: a crash loses at most the current line, and
    every preceding line stays parseable (the same tail-tolerance as a
    Kafka log).
    """

    def __init__(self, path: str, manifest: Optional[dict] = None,
                 max_bytes: Optional[int] = None):
        """``max_bytes`` caps the JSONL's size: when an append pushes the
        file past it, the file rotates to ``<path>.1`` (one generation,
        overwritten on the next trip — disk use is bounded at ~2×cap)
        and the fresh file opens with the manifest plus a ``rotated``
        event. ``None``/0 = unbounded (the pre-rotation behavior)."""
        self.path = path
        self.max_bytes = int(max_bytes) if max_bytes else 0
        self._lock = threading.Lock()
        # Sampled mode (overload rung 1): record every k-th batch
        # record; event records always land. 1 = every batch.
        self._sample_every = 1
        self._batch_tick = 0
        self._f = open(path, "a", encoding="utf-8")
        self.manifest = dict(manifest or {})
        self.manifest.setdefault("start_unix_s", time.time())
        if self._f.tell() > 0:
            # Resuming an existing record: if the previous writer died
            # mid-line, start on a fresh line so the torn tail corrupts
            # exactly one record, not two.
            with open(path, "rb") as rf:
                rf.seek(-1, 2)
                if rf.read(1) != b"\n":
                    self._f.write("\n")
                    self._f.flush()
        # EVERY open writes its manifest — a segment marker. A second
        # run appending to the same path (new config/model) must not be
        # silently attributed to the first run's manifest; read() hands
        # back the LAST segment's manifest.
        self._write({"kind": "manifest", **self.manifest})

    def _write(self, obj: dict) -> None:
        line = json.dumps(obj, separators=(",", ":"), default=str)
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()
            if self.max_bytes and self._f.tell() > self.max_bytes:
                self._rotate_locked()

    def _rotate_locked(self) -> None:
        """Size-cap rotation (caller holds the lock): current file moves
        to ``<path>.1``; a fresh segment opens with the manifest and a
        ``rotated`` event, so readers of the live path see an honest
        marker instead of silently missing history."""
        import os

        rotated_bytes = self._f.tell()
        self._f.close()
        os.replace(self.path, self.path + ".1")
        self._f = open(self.path, "a", encoding="utf-8")
        for obj in (
            {"kind": "manifest", **self.manifest},
            {"kind": "event", "t": time.time(), "event": "rotated",
             "previous": self.path + ".1",
             "previous_bytes": rotated_bytes},
        ):
            self._f.write(json.dumps(obj, separators=(",", ":"),
                                     default=str) + "\n")
        self._f.flush()

    def set_sample_every(self, k: int) -> None:
        """Batch-record sampling (overload rung 1 drops the recorder to
        sampled mode; 1 restores full recording). Events — rung
        transitions, shed/replay, faults — are NEVER sampled out: the
        record must stay a complete account of what degraded and why,
        only the per-batch bulk thins."""
        with self._lock:
            self._sample_every = max(1, int(k))
            self._batch_tick = 0

    def record_batch(self, batch_index: int, rows: int,
                     phases: Dict[str, float], queue_depth: int = 0,
                     **extra) -> None:
        with self._lock:
            self._batch_tick += 1
            if self._sample_every > 1 \
                    and self._batch_tick % self._sample_every != 1:
                return
        self._write({
            "kind": "batch", "t": time.time(), "batch": int(batch_index),
            "rows": int(rows),
            "phases": {k: float(v) for k, v in phases.items()},
            "queue_depth": int(queue_depth), **extra,
        })

    def record_event(self, event: str, **fields) -> None:
        self._write({"kind": "event", "t": time.time(), "event": event,
                     **fields})

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()

    @staticmethod
    def read_segments(path: str) -> List[Tuple[Optional[dict], List[dict]]]:
        """Replay a flight record as run segments: → [(manifest,
        records), ...]. Each writer open appends a manifest marker that
        starts a new segment; unparseable lines (torn final write after
        a crash) are skipped. Records before any manifest land in a
        leading ``(None, records)`` segment."""
        segments: List[Tuple[Optional[dict], List[dict]]] = []
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if obj.get("kind") == "manifest":
                    segments.append((obj, []))
                else:
                    if not segments:
                        segments.append((None, []))
                    segments[-1][1].append(obj)
        return segments

    @staticmethod
    def read(path: str) -> Tuple[Optional[dict], List[dict]]:
        """→ the LAST run segment's (manifest, records): the most recent
        run owns the record's interpretation, and its batches are never
        mixed with an earlier run's appended to the same path. Use
        :meth:`read_segments` for the full history."""
        segments = FlightRecorder.read_segments(path)
        return segments[-1] if segments else (None, [])


_active_recorder: Optional[FlightRecorder] = None


def set_active_recorder(rec: Optional[FlightRecorder]) -> None:
    """Install the process-wide flight recorder (CLI serve loop does
    this). Layers without an engine handle — fault injectors, the
    checkpointer, the recovery supervisor — record through it."""
    global _active_recorder
    _active_recorder = rec


def active_recorder() -> Optional[FlightRecorder]:
    return _active_recorder


# ---------------------------------------------------------------------------
# HTTP endpoints
# ---------------------------------------------------------------------------

class MetricsServer:
    """Stdlib-only background HTTP server: ``/metrics`` (Prometheus
    text), ``/metrics.json`` (snapshot), ``/healthz``, ``/trace``
    (the process tracer's span ring buffer as Chrome-trace JSON —
    save the response body to a file and load it in ui.perfetto.dev).

    ``/healthz`` is 200 when the serving loop is making progress:

    - last-batch age (now − ``rtfds_last_batch_unix_seconds``) is within
      ``max_batch_age_s`` — a hung source or device step trips it the
      same way the :class:`~..runtime.faults.Heartbeat` watchdog does;
      before the first batch lands the check passes (startup grace).
    - source lag (``rtfds_source_lag_rows``, set by sources that can
      compute a backlog) is within ``max_source_lag_rows`` when that
      threshold is configured.

    The body additionally reports the failure-handling counters ops
    alert on — ``restarts`` (``rtfds_engine_restarts_total`` summed over
    causes), ``crash_loops`` and ``dead_letter_rows`` — and a ``status``
    field: ``"ok"``, ``"unhealthy"`` (503), or ``"degraded"`` (still
    200: the stream is alive and making progress, but rows sit
    quarantined in the dead-letter queue awaiting triage, serving runs
    off a fallback restore, or the overload ladder is active /
    deferred rows await replay — the ``overload`` block then carries
    the rung, shed rows pending replay, and the lag trend).

    ``port=0`` binds an ephemeral port (tests); the bound port is
    ``self.port`` after :meth:`start`.
    """

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 registry: Optional[MetricsRegistry] = None,
                 max_batch_age_s: float = 300.0,
                 max_source_lag_rows: Optional[float] = None):
        self.host = host
        self.port = int(port)
        self.registry = registry if registry is not None else get_registry()
        self.max_batch_age_s = float(max_batch_age_s)
        self.max_source_lag_rows = max_source_lag_rows
        self._httpd = None
        self._thread = None

    def health(self) -> Tuple[bool, dict]:
        checks: Dict[str, dict] = {}
        ok = True
        last = self.registry.get("rtfds_last_batch_unix_seconds")
        if last is not None and last.value > 0:
            # rtfdslint: disable=wall-clock-duration (liveness age vs a wall-clock gauge the serving process stamps; /healthz may be queried from any process, so both ends must be wall clock)
            age = time.time() - last.value
            good = age <= self.max_batch_age_s
            checks["last_batch_age_s"] = {
                "value": round(age, 3), "max": self.max_batch_age_s,
                "ok": good}
            ok = ok and good
        else:
            checks["last_batch_age_s"] = {"value": None, "ok": True,
                                          "note": "no batches yet"}
        lag = self.registry.get("rtfds_source_lag_rows")
        if lag is not None and self.max_source_lag_rows is not None:
            good = lag.value <= self.max_source_lag_rows
            checks["source_lag_rows"] = {
                "value": lag.value, "max": self.max_source_lag_rows,
                "ok": good}
            ok = ok and good
        elif lag is not None:
            checks["source_lag_rows"] = {"value": lag.value, "ok": True,
                                         "note": "no threshold set"}
        # Durable-state plane: age of the last checkpoint save, lineage
        # depth, and corruption/fallback counters — present only once
        # the serving loop checkpoints, so a checkpoint-less run's body
        # stays clean.
        last_ck = self.registry.get("rtfds_last_checkpoint_unix_seconds")
        if last_ck is not None and last_ck.value > 0:
            checks["last_checkpoint_age_s"] = {
                # rtfdslint: disable=wall-clock-duration (age vs the wall-clock checkpoint stamp — same cross-process contract as last_batch_age_s above)
                "value": round(time.time() - last_ck.value, 3), "ok": True}
        # Failure-handling counters (degraded-but-alive serving): present
        # only once their families exist, so a clean run's body stays
        # clean.
        extras: Dict[str, float] = {}
        for fam, key in (("rtfds_engine_restarts_total", "restarts"),
                         ("rtfds_crash_loops_total", "crash_loops"),
                         ("rtfds_dead_letter_rows", "dead_letter_rows"),
                         ("rtfds_checkpoint_corrupt_total",
                          "checkpoint_corrupt_total"),
                         ("rtfds_checkpoint_fallbacks_total",
                          "checkpoint_fallbacks"),
                         ("rtfds_checkpoint_lineage_depth",
                          "checkpoint_lineage_depth")):
            v = self.registry.family_total(fam)
            if v is not None:
                extras[key] = v
        # Feedback feature cache: shadow/live precision-recall quality
        # silently degrades when labeled rows miss the cache (their
        # labels are dropped on the floor) — surface the hit rate so the
        # operator can SEE it, not infer it from starved metric windows.
        c_hit = self.registry.get("rtfds_feature_cache_lookups_total",
                                  outcome="hit")
        c_miss = self.registry.get("rtfds_feature_cache_lookups_total",
                                   outcome="miss")
        if c_hit is not None or c_miss is not None:
            hits = c_hit.value if c_hit is not None else 0.0
            misses = c_miss.value if c_miss is not None else 0.0
            total = hits + misses
            cache: Dict[str, float] = {
                "hit_rate": round(hits / total, 4) if total else 1.0,
                "lookups": total,
            }
            occ = self.registry.get("rtfds_feature_cache_occupancy")
            cap = self.registry.get("rtfds_feature_cache_capacity")
            if occ is not None:
                cache["occupancy"] = occ.value
            if cap is not None:
                cache["capacity"] = cap.value
            ev = self.registry.family_total(
                "rtfds_feature_cache_evictions_total")
            if ev is not None:
                cache["evictions"] = ev
            extras["feature_cache"] = cache
        # Tiered feature store (key_mode="exact"): per-table hot-tier
        # occupancy, compaction reclaim totals, the dense-tier hit rate,
        # and state bytes vs the configured HBM budget — present only
        # once an exact-mode engine registered the occupancy gauges, so
        # direct/hash runs keep a clean body.
        occ_tables: Dict[str, float] = {}
        for table in ("customer", "terminal"):
            g = self.registry.get("rtfds_feature_slots_occupied",
                                  table=table)
            if g is not None:
                occ_tables[table] = g.value
        if occ_tables:
            fstate: Dict[str, object] = {"slots_occupied": occ_tables}
            # Sum the TABLE-level series only: the sharded engine also
            # registers shard-labeled rows of the same family (they
            # break the same totals down, so a blind family_total would
            # double-count).
            rec_rows = [
                v for labels, v in self.registry.family_series(
                    "rtfds_feature_slots_reclaimed_total")
                if "shard" not in labels]
            if rec_rows:
                fstate["slots_reclaimed"] = float(sum(rec_rows))
            # Per-shard breakdown (sharded exact serving): occupancy per
            # shard summed over tables, plus the worst shard — skew is
            # the failure mode the modulo ownership hides, so it gets a
            # first-class health surface.
            shard_occ: Dict[str, float] = {}
            for labels, v in self.registry.family_series(
                    "rtfds_feature_slots_occupied"):
                s = labels.get("shard")
                if s is not None:
                    shard_occ[s] = shard_occ.get(s, 0.0) + v
            if shard_occ:
                fstate["slots_occupied_per_shard"] = {
                    s: shard_occ[s]
                    for s in sorted(shard_occ, key=int)}
                worst = max(shard_occ, key=lambda s: shard_occ[s])
                fstate["worst_shard"] = {
                    "shard": int(worst), "occupied": shard_occ[worst]}
                shard_tiers: Dict[str, Dict[str, float]] = {}
                for labels, v in self.registry.family_series(
                        "rtfds_feature_tier_rows_total"):
                    s = labels.get("shard")
                    if s is not None:
                        shard_tiers.setdefault(
                            s, {})[labels.get("tier", "?")] = v
                if shard_tiers:
                    fstate["tier_rows_per_shard"] = {
                        s: shard_tiers[s]
                        for s in sorted(shard_tiers, key=int)}
            dense = self.registry.get("rtfds_feature_tier_rows_total",
                                      tier="dense")
            cms_t = self.registry.get("rtfds_feature_tier_rows_total",
                                      tier="cms")
            if dense is not None or cms_t is not None:
                d = dense.value if dense is not None else 0.0
                c = cms_t.value if cms_t is not None else 0.0
                fstate["tier_rows"] = {"dense": d, "cms": c}
                total = d + c
                # both tiers serve correct-contract features; the hit
                # rate tells the operator how EXACT the serving mix is
                fstate["dense_hit_rate"] = (round(d / total, 4)
                                            if total else 1.0)
            sb = self.registry.get("rtfds_feature_state_bytes",
                                   tier="total")
            if sb is not None:
                fstate["state_bytes"] = sb.value
                budget = self.registry.get(
                    "rtfds_feature_state_budget_bytes")
                if budget is not None and budget.value > 0:
                    fstate["budget_bytes"] = budget.value
                    fstate["budget_used"] = round(
                        sb.value / budget.value, 4)
            # Host cold tier (features.cold_store): depth and promotion
            # traffic — present only once an
            # engine armed the cold store, so two-tier runs keep the
            # block absent rather than zero-filled.
            ck = self.registry.get("rtfds_feature_cold_keys")
            if ck is not None:
                cold: Dict[str, float] = {"keys": ck.value}
                for name, key in (
                        ("rtfds_feature_cold_bytes", "bytes"),
                        ("rtfds_feature_cold_promotions_total",
                         "promotions"),
                        ("rtfds_feature_cold_demotions_total",
                         "demotions"),
                        ("rtfds_feature_cold_rows_total", "rows"),
                        ("rtfds_feature_cold_promote_lanes_total",
                         "promote_lanes")):
                    m = self.registry.get(name)
                    if m is not None:
                        cold[key] = m.value
                fstate["cold"] = cold
            extras["feature_state"] = fstate
        # Device plane: the z-contraction mode the serving step compiled
        # with and whether the fused Pallas path is on — present only
        # once an engine registered the gauges, so non-serving processes
        # stay clean.
        active_z = None
        for mode in ("f32", "bf16", "int8"):
            g = self.registry.get("rtfds_z_mode", mode=mode)
            if g is not None and g.value > 0:
                active_z = mode
        if active_z is not None:
            device_plane: Dict[str, object] = {"z_mode": active_z}
            up = self.registry.get("rtfds_use_pallas")
            if up is not None:
                device_plane["use_pallas"] = bool(up.value)
            extras["device_plane"] = device_plane
        # Continuous-learning plane: which versions are serving/shadowing
        # and whether promotions/rollbacks have fired — present only once
        # a registry/learning loop exists, so other runs stay clean.
        champ = self.registry.get("rtfds_model_version", role="champion")
        if champ is not None:
            learning: Dict[str, float] = {
                "champion_version": champ.value}
            cand = self.registry.get("rtfds_model_version",
                                     role="candidate")
            if cand is not None:
                learning["candidate_version"] = cand.value
            # promotions/refusals are DIFFERENT outcomes of one family —
            # summing them would report a refused corrupt candidate as a
            # successful promotion
            for outcome, key in (("promoted", "promotions"),
                                 ("refused_corrupt", "refusals")):
                m = self.registry.get("rtfds_model_promotions_total",
                                      outcome=outcome)
                if m is not None:
                    learning[key] = m.value
            for fam, key in (
                    ("rtfds_model_rollbacks_total", "rollbacks"),
                    ("rtfds_shadow_divergence_total",
                     "shadow_divergence"),
                    ("rtfds_model_artifact_corrupt_total",
                     "model_artifact_corrupt")):
                v = self.registry.family_total(fam)
                if v is not None:
                    learning[key] = v
            extras["learning"] = learning
        # Overload ladder (runtime/overload.py): present only once a
        # controller registered the rung gauge. Degraded-but-alive while
        # any rung is active OR deferred rows await replay — the same
        # 200-with-status-"degraded" contract as the DLQ and
        # fallback-restore states (the stream is serving; an operator
        # should look before the spill fills).
        rung = self.registry.get("rtfds_overload_rung")
        if rung is not None:
            overload: Dict[str, float] = {"rung": rung.value}
            pend = self.registry.get("rtfds_shed_pending_rows")
            if pend is not None:
                overload["shed_rows_pending_replay"] = pend.value
            for fam, key in (("rtfds_shed_rows_total", "shed_rows"),
                             ("rtfds_shed_replayed_rows_total",
                              "replayed_rows"),
                             ("rtfds_overload_transitions_total",
                              "transitions")):
                v = self.registry.family_total(fam)
                if v is not None:
                    overload[key] = v
            trend = self.registry.get("rtfds_source_lag_trend_rows_per_s")
            if trend is not None:
                overload["lag_trend_rows_per_s"] = trend.value
            extras["overload"] = overload
        status = "ok" if ok else "unhealthy"
        if ok and rung is not None and (
                rung.value > 0
                or extras["overload"].get("shed_rows_pending_replay",
                                          0) > 0):
            status = "degraded"
        if ok and extras.get("dead_letter_rows", 0) > 0:
            # alive and progressing, but quarantined rows await triage
            status = "degraded"
        fb = self.registry.get("rtfds_checkpoint_serving_fallback")
        if ok and fb is not None and fb.value > 0:
            # the engine restored PAST a corrupt checkpoint and is
            # serving off an older fence — alive (200) but an operator
            # should look at the quarantined lineage before the next
            # incident eats the remaining fallback depth
            status = "degraded"
            extras["serving_off_fallback_restore"] = True
        return ok, {"healthy": ok, "status": status, "checks": checks,
                    **extras}

    def start(self) -> "MetricsServer":
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        server = self

        class Handler(BaseHTTPRequestHandler):
            def _send(self, code: int, body: bytes, ctype: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler API
                path = self.path.split("?", 1)[0]
                try:
                    if path == "/metrics":
                        self._send(
                            200,
                            server.registry.render_prometheus().encode(),
                            "text/plain; version=0.0.4; charset=utf-8")
                    elif path == "/metrics.json":
                        self._send(
                            200,
                            json.dumps(server.registry.snapshot()).encode(),
                            "application/json")
                    elif path == "/healthz":
                        ok, body = server.health()
                        self._send(200 if ok else 503,
                                   json.dumps(body).encode(),
                                   "application/json")
                    elif path == "/trace":
                        # lazy import: metrics stays importable without
                        # the trace module (and vice versa — trace
                        # imports metrics for its span counter)
                        from real_time_fraud_detection_system_tpu.utils \
                            .trace import get_tracer

                        self._send(
                            200,
                            json.dumps(get_tracer().export_chrome())
                            .encode(),
                            "application/json")
                    else:
                        self._send(404, b'{"error":"not found"}',
                                   "application/json")
                except BrokenPipeError:  # client went away mid-write
                    pass

            def log_message(self, *a):  # endpoint scrapes are not log news
                pass

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.2},
            daemon=True, name="rtfds-metrics")
        self._thread.start()
        return self

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
