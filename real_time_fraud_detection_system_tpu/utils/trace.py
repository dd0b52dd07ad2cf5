"""Per-batch trace spans with Chrome-trace/Perfetto export.

The metrics registry (``utils/metrics.py``) answers *how long* each phase
takes in aggregate; this module answers *what happened inside a batch*:
every phase of a micro-batch becomes a named span under that batch's
trace id, completed spans land in a bounded in-memory ring buffer, and
the buffer exports as Chrome-trace (catapult) JSON — the format
Perfetto, ``chrome://tracing``, and TensorBoard's trace viewer all load.
Each live host span is additionally wrapped in
``jax.profiler.TraceAnnotation`` (when jax is importable), so a
``jax.profiler`` device capture taken over the same run shows the host
phases aligned with the XLA device timeline in one view.

Design constraints, in order:

1. **Disabled is free.** The serving hot loop calls :meth:`Tracer.span`
   per phase whether or not anyone is tracing; the disabled path is one
   attribute check returning a shared no-op context manager (measured
   ~0.1 µs/span, bounded by ``tests/test_trace.py``).
2. **Enabled is cheap.** A span is two ``perf_counter`` reads, one small
   object, and a deque append — no locks on the single-threaded engine
   loop path beyond the deque's internal thread safety; ~2-5 µs/span,
   <50 µs for a full 7-span batch.
3. **Bounded.** The ring buffer holds the most recent ``capacity``
   completed spans (default 16384 ≈ 2000+ batches of 7 spans); long
   ``score`` runs cannot grow host memory.
4. **Stdlib-only import.** jax is imported lazily and only when
   annotation is possible; the module stays importable from any process
   (the same contract as ``utils/metrics.py``).

Usage::

    tracer = get_tracer()
    tracer.configure(enabled=True)
    tid = tracer.begin_batch(42)            # per-batch trace id "b00000042"
    with tracer.span("host_prep", rows=4096):
        ...
    tracer.export("trace.json")             # load in ui.perfetto.dev
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

__all__ = [
    "Span",
    "Tracer",
    "get_tracer",
    "current_ids",
    "STEP_SCOPES",
    "step_scope",
    "summarize_chrome",
]


class Span:
    """One completed span: name, trace id, [t0, t1) in tracer-relative
    seconds, owning thread, and free-form args."""

    __slots__ = ("name", "trace_id", "batch", "t0", "t1", "tid", "args")

    def __init__(self, name: str, trace_id: str, batch: int,
                 t0: float, t1: float, tid: int, args: Optional[dict]):
        self.name = name
        self.trace_id = trace_id
        self.batch = batch
        self.t0 = t0
        self.t1 = t1
        self.tid = tid
        self.args = args

    @property
    def dur_s(self) -> float:
        return self.t1 - self.t0


class _NoopSpan:
    """Shared disabled-path context manager: enter/exit do nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _LiveSpan:
    """Enabled-path context manager: records the span on exit and keeps
    an optional ``jax.profiler.TraceAnnotation`` open for its duration so
    host phases line up with the device timeline in a jax trace."""

    __slots__ = ("_tracer", "_name", "_trace_id", "_batch", "_args",
                 "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 batch: int, args: Optional[dict]):
        self._tracer = tracer
        self._name = name
        self._trace_id = trace_id
        self._batch = batch
        self._args = args
        self._ann = None

    def __enter__(self):
        ann_cls = self._tracer._annotation_cls
        if ann_cls is not None:
            # name#batch keeps repeated phases distinguishable on the
            # profiler timeline without exploding the name cardinality
            self._ann = ann_cls(f"rtfds.{self._name}#{self._batch}")
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._record(Span(
            self._name, self._trace_id, self._batch,
            self._t0 - self._tracer._t0, t1 - self._tracer._t0,
            threading.get_ident(), self._args))
        return False


class Tracer:
    """Span collector with per-batch trace ids and a bounded ring buffer.

    The engine loop is single-threaded, so the "current batch" context is
    a plain attribute (spans from other threads — the metrics server, a
    supervisor — attribute to whatever batch is current, which is the
    honest answer for a process-wide timeline). Spans may also name
    their batch explicitly (``span(..., batch=...)``) — the pipelined
    engine does this for ``result_wait``/``sink_write``, which complete
    for batch N while batch N+k is already current.
    """

    def __init__(self, capacity: int = 16384, enabled: bool = False):
        self.enabled = bool(enabled)
        self._buf: deque = deque(maxlen=int(capacity))
        self._lock = threading.Lock()  # buffer swaps/exports only
        self._t0 = time.perf_counter()
        self._epoch_unix_s = time.time()
        self._cur_id = ""
        self._cur_batch = 0
        self._seq = 0
        self._annotation_cls = None
        self._m_spans = None  # rtfds_trace_spans_total, resolved lazily

    # -- configuration -------------------------------------------------

    def configure(self, enabled: Optional[bool] = None,
                  capacity: Optional[int] = None,
                  annotate: bool = True) -> "Tracer":
        """Enable/disable and (re)size the buffer. ``annotate=True``
        wires ``jax.profiler.TraceAnnotation`` around live spans when
        jax is importable; pass False for jax-free processes."""
        if capacity is not None and capacity != self._buf.maxlen:
            with self._lock:
                self._buf = deque(self._buf, maxlen=int(capacity))
        if enabled is not None:
            self.enabled = bool(enabled)
        if self.enabled and annotate and self._annotation_cls is None:
            try:
                import jax

                self._annotation_cls = jax.profiler.TraceAnnotation
            except (ImportError, AttributeError, RuntimeError):
                # stdlib-only process, or a broken jax/jaxlib pairing
                # (raises RuntimeError at import): tracing degrades to
                # plain spans, never kills the run
                self._annotation_cls = None
        if not annotate:
            self._annotation_cls = None
        if self.enabled and self._m_spans is None:
            from real_time_fraud_detection_system_tpu.utils.metrics import (
                get_registry,
            )

            self._m_spans = get_registry().counter(
                "rtfds_trace_spans_total", "completed trace spans recorded")
        return self

    @property
    def capacity(self) -> int:
        return self._buf.maxlen or 0

    def __len__(self) -> int:
        return len(self._buf)

    # -- trace-id context ----------------------------------------------

    def begin_batch(self, batch_index: Optional[int] = None) -> str:
        """Start a new per-batch trace id; subsequent spans attribute to
        it. Returns the id ("" when disabled — callers can cheaply skip
        cross-referencing it into flight records)."""
        if not self.enabled:
            return ""
        if batch_index is None:
            self._seq += 1
            batch_index = self._seq
        self._cur_batch = int(batch_index)
        self._cur_id = f"b{int(batch_index):08d}"
        return self._cur_id

    def current_ids(self) -> Tuple[str, int]:
        """→ (trace_id, batch_index) of the current batch ("" / 0 when
        disabled or before the first batch). The JSON log formatter uses
        this for log↔span correlation."""
        return (self._cur_id, self._cur_batch) if self.enabled else ("", 0)

    # -- span recording ------------------------------------------------

    def span(self, name: str, batch: Optional[str] = None, **args):
        """Context manager for a live span. ``batch`` overrides the
        current trace id (the pipelined engine finishes batch N while
        batch N+k is current). Extra kwargs land in the exported event's
        ``args``."""
        if not self.enabled:
            return _NOOP
        if batch is None:
            trace_id, bidx = self._cur_id, self._cur_batch
        else:
            trace_id = batch
            try:
                bidx = int(batch.lstrip("b")) if batch else 0
            except ValueError:
                bidx = 0
        return _LiveSpan(self, name, trace_id, bidx, args or None)

    def add_span(self, name: str, t0_perf: float, t1_perf: float,
                 batch: Optional[str] = None, **args) -> None:
        """Record an already-measured span from raw ``perf_counter``
        readings — for call sites that already timed the work (source
        polls, sink writes) and must not pay a second pair of clock
        reads. No TraceAnnotation (the work already happened)."""
        if not self.enabled:
            return
        trace_id = self._cur_id if batch is None else batch
        bidx = self._cur_batch
        if batch is not None:
            try:
                bidx = int(batch.lstrip("b")) if batch else 0
            except ValueError:
                bidx = 0
        self._record(Span(name, trace_id, bidx, t0_perf - self._t0,
                          t1_perf - self._t0, threading.get_ident(),
                          args or None))

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker (recompile events, model reloads)."""
        if not self.enabled:
            return
        t = time.perf_counter() - self._t0
        self._record(Span(name, self._cur_id, self._cur_batch, t, t,
                          threading.get_ident(), args or None))

    def _record(self, span: Span) -> None:
        self._buf.append(span)  # deque append is atomic + O(1) eviction
        if self._m_spans is not None:
            self._m_spans.inc()

    # -- export --------------------------------------------------------

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def export_chrome(self) -> dict:
        """→ Chrome-trace (catapult) JSON object: ``{"traceEvents":
        [...], "displayTimeUnit": "ms", ...}``. Events are complete
        ("ph": "X") spans with µs timestamps, sorted by ``ts`` so any
        streaming consumer sees a monotone timeline; per-batch trace ids
        ride in ``args.trace_id``. Loadable in ui.perfetto.dev /
        chrome://tracing as-is."""
        import os

        pid = os.getpid()
        spans = self.snapshot()
        events: List[dict] = [{
            # process metadata: names the track in Perfetto's UI
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "ts": 0, "args": {"name": "rtfds"},
        }]
        for s in sorted(spans, key=lambda s: s.t0):
            ev = {
                "ph": "X",
                "name": s.name,
                "cat": "rtfds",
                "ts": round(s.t0 * 1e6, 3),     # µs, tracer-relative
                "dur": round((s.t1 - s.t0) * 1e6, 3),
                "pid": pid,
                "tid": s.tid,
                "args": {"trace_id": s.trace_id, "batch": s.batch},
            }
            if s.args:
                ev["args"].update(s.args)
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "rtfds",
                # an empty /trace response must say WHY it is empty
                "tracing_enabled": self.enabled,
                "epoch_unix_s": self._epoch_unix_s,
                "spans_dropped_by_ring": max(
                    0, (self._m_spans.value if self._m_spans else 0)
                    - len(spans)),
            },
        }

    def export(self, path: str) -> dict:
        """Write the Chrome-trace JSON to ``path``; returns a small
        manifest (path, event count) for CLI printing."""
        trace = self.export_chrome()
        with open(path, "w", encoding="utf-8") as f:
            json.dump(trace, f, separators=(",", ":"))
        return {"trace": path, "events": len(trace["traceEvents"])}


_default_tracer = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer every layer records into (disabled until
    ``configure(enabled=True)`` — the CLI's ``--trace-out`` does that)."""
    return _default_tracer


def current_ids() -> Tuple[str, int]:
    """(trace_id, batch_index) of the default tracer's current batch —
    the log formatter's hook (see ``utils/logging.py``)."""
    return _default_tracer.current_ids()


# The host spans above stop at the dispatch; these carry the same
# ``rtfds.`` vocabulary down into the jitted step. Each name is one
# component of a device op's HLO ``op_name`` (components nest, e.g.
# ``.../rtfds.terminal/rtfds.update/rtfds.reset/select_n``), which is what a
# profiler trace's device events are grouped by. Metadata only: a scope
# changes no instruction. Where each is opened: PERF.md section 3.
STEP_SCOPES = (
    "unpack",                                      # engine.step
    "customer", "terminal",                        # which table
    "update", "stamp", "reset", "scatter",         # ops/windows
    # no stage opens this one since PR 25 (the columns are stored in the
    # layout the update works in); the benchmark's step_relayout_ms
    # names it and reads 0, and a later layout move goes under it
    "relayout",
    "query", "gather", "sum",                      # ops/windows
    "keydir", "cms",                               # ops/keydir, ops/cms
    "lookup", "claim", "grant",                    # the parts of keydir
    "compact",                                     # features/online
    # the cold tier's two: the demote pass's selection and payload gather
    # inside compact, and the whole ("promote", table, width) program
    "demote", "promote",                           # features/online
    "assemble",                                    # features/online
    "scale",                                       # models/scaler
    "classify", "fused_step", "learn", "emit",     # engine.step
    "exchange", "route", "pack",                   # parallel/step
)


def step_scope(name: str):
    """``jax.named_scope("rtfds.<name>")`` for one stage of the step."""
    if name not in STEP_SCOPES:
        raise ValueError(f"{name!r} is not one of STEP_SCOPES")
    import jax

    return jax.named_scope("rtfds." + name)


# ---------------------------------------------------------------------------
# Trace analysis (the `rtfds trace` subcommand's engine)
# ---------------------------------------------------------------------------

def _batch_events(events: List[dict]) -> Dict[str, List[dict]]:
    """Group duration events by their per-batch trace id (events with no
    trace id — compiles outside any batch — group under "")."""
    by: Dict[str, List[dict]] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        tid = str((ev.get("args") or {}).get("trace_id", ""))
        by.setdefault(tid, []).append(ev)
    return by


def summarize_chrome(trace: dict, top_k: int = 10) -> dict:
    """Digest a Chrome-trace JSON object (as exported above) into the
    per-batch critical path, the top-K slowest spans, and the XLA
    compile/recompile events — everything ``rtfds trace`` prints.

    Per batch: total span time, per-phase durations, and the *critical
    phase* (the longest span — in a serial per-batch waterfall that IS
    the critical path's dominant edge)."""
    events = [e for e in trace.get("traceEvents", [])
              if e.get("ph") == "X"]
    batches = []
    for tid, evs in sorted(_batch_events(events).items()):
        if not tid:
            continue
        phases: Dict[str, float] = {}
        for e in evs:
            phases[e["name"]] = phases.get(e["name"], 0.0) \
                + float(e.get("dur", 0.0))
        crit = max(phases.items(), key=lambda kv: kv[1]) \
            if phases else ("", 0.0)
        batches.append({
            "trace_id": tid,
            "batch": (evs[0].get("args") or {}).get("batch"),
            "total_ms": round(sum(phases.values()) / 1e3, 3),
            "critical_phase": crit[0],
            "critical_ms": round(crit[1] / 1e3, 3),
            "phases_ms": {k: round(v / 1e3, 3)
                          for k, v in sorted(phases.items())},
        })
    slowest = sorted(events, key=lambda e: -float(e.get("dur", 0.0)))
    top = [{
        "name": e["name"],
        "dur_ms": round(float(e.get("dur", 0.0)) / 1e3, 3),
        "trace_id": (e.get("args") or {}).get("trace_id", ""),
        "ts_ms": round(float(e.get("ts", 0.0)) / 1e3, 3),
    } for e in slowest[:top_k]]
    compiles = [{
        "name": e["name"],
        "dur_ms": round(float(e.get("dur", 0.0)) / 1e3, 3),
        "trace_id": (e.get("args") or {}).get("trace_id", ""),
        "args": {k: v for k, v in (e.get("args") or {}).items()
                 if k not in ("trace_id", "batch")},
    } for e in events if e["name"] in ("xla_compile", "xla_recompile")]
    return {
        "batches": batches,
        "slowest_spans": top,
        "compile_events": compiles,
        "n_events": len(events),
    }
