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

A span records its name, start, end, batch, thread, the thread's role and
**the span that caused it**: ``parent`` is the innermost span open on the
same thread when it opened (a per-thread stack), so one ``engine.run()``
is one tree (README, Tracing) and a span's *self time* — its duration
minus what its children cover — is what it did not hand further down.

Design constraints, in order:

1. **Disabled is free.** The serving hot loop calls :meth:`Tracer.span`
   per phase whether or not anyone is tracing; the disabled path is one
   attribute check returning a shared no-op span (measured ~0.1 µs/span,
   bounded by ``tests/test_trace.py``).
2. **Enabled is cheap.** A span is two ``perf_counter`` reads (its own,
   or the caller's: ``open()`` … ``close(t0, t1)``), one small object, a
   thread-local read and a deque append — no lock beyond the deque's own
   and the span counter's; ~2-5 µs/span, <50 µs for a full 7-span batch.
3. **Bounded.** The ring buffer holds the most recent ``capacity``
   completed spans (default 16384 ≈ 700 batches of ~23 spans); long
   ``score`` runs cannot grow host memory, and a quiet source's empty
   passes fold into one span (``fold``).
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

import itertools
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
    seconds, owning thread and its role, the span that caused it
    (``parent``: the id of the innermost span open on the same thread
    when this one opened, 0 for a thread's outermost), and free-form
    args. ``child_s`` is the time its children cover, summed as they
    close (children of one parent on one thread never overlap)."""

    __slots__ = ("name", "trace_id", "batch", "t0", "t1", "tid", "args",
                 "id", "parent", "role", "child_s")

    def __init__(self, name: str, trace_id: str, batch: int,
                 t0: float, t1: float, tid: int, args: Optional[dict],
                 id: int = 0, parent: int = 0, role: str = "other",
                 child_s: float = 0.0):
        self.name = name
        self.trace_id = trace_id
        self.batch = batch
        self.t0 = t0
        self.t1 = t1
        self.tid = tid
        self.args = args
        self.id = id
        self.parent = parent
        self.role = role
        self.child_s = child_s

    @property
    def dur_s(self) -> float:
        return self.t1 - self.t0

    @property
    def self_s(self) -> float:
        """Duration minus the part its children cover."""
        return max(0.0, self.t1 - self.t0 - self.child_s)


class _NoopSpan:
    """Shared disabled-path span: every method does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def open(self):
        return self

    def close(self, t0: float = 0.0, t1: float = 0.0, **args) -> None:
        return None

    def cancel(self) -> None:
        return None

    def fold(self, name: str) -> None:
        return None


_NOOP = _NoopSpan()


class _LiveSpan:
    """Enabled-path span. ``open()`` makes it the innermost open span of
    its thread (its children's parent) and opens a
    ``jax.profiler.TraceAnnotation`` so the span is on a device
    capture's clock too; ``close(t0, t1)`` records it with the caller's
    two clock readings. As a context manager it reads the clock itself.

    ``cancel()``: close without a record (a poll that came back empty).
    ``fold(name)``: record under ``name``, and where the thread's last
    record is a childless ``name`` of the same parent and this span had
    no child either, lengthen that record instead of adding one — a
    quiet source's passes become one span, not thousands."""

    __slots__ = ("_tracer", "name", "_trace_id", "_batch", "_args",
                 "_t0", "_ann", "id", "_up", "_child_s", "_fold",
                 "_cancelled")

    def __init__(self, tracer: "Tracer", name: str,
                 batch: Optional[str], args: Optional[dict]):
        self._tracer = tracer
        self.name = name
        self._trace_id = batch
        self._batch = 0
        self._args = args
        self._ann = None
        self._up = None
        self._child_s = 0.0
        self._fold = False
        self._cancelled = False
        self.id = 0

    def open(self) -> "_LiveSpan":
        tracer = self._tracer
        local = tracer._local
        self._up = up = getattr(local, "top", None)
        local.top = self
        self.id = next(tracer._ids)
        self._trace_id, self._batch = tracer._batch_of(self._trace_id, up)
        ann_cls = tracer._annotation_cls
        if ann_cls is not None:
            # name#batch keeps repeated phases distinguishable on the
            # profiler timeline without exploding the name cardinality
            self._ann = ann_cls(f"rtfds.{self.name}#{self._batch}")
            self._ann.__enter__()
        return self

    def cancel(self) -> None:
        self._cancelled = True

    def fold(self, name: str) -> None:
        self.name = name
        self._fold = True

    def close(self, t0: float, t1: float, **args) -> None:
        tracer = self._tracer
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        local = tracer._local
        up = self._up
        top = getattr(local, "top", None)
        while top is not None and top is not self:
            top = top._up  # a child an exception left open goes with us
        if top is self:
            local.top = up
        if self._cancelled:
            return
        if args:
            self._args = dict(self._args or (), **args)
        parent = up.id if up is not None else 0
        t0 -= tracer._t0
        t1 -= tracer._t0
        if self._fold and not self._child_s:
            last = getattr(local, "last", None)
            if (last is not None and last.name == self.name
                    and last.parent == parent and not last.child_s
                    and (last.args or {}).get("folded")):
                if up is not None:
                    up._child_s += t1 - last.t1
                last.t1 = t1
                last.args["folded"] += 1
                return
            self._args = dict(self._args or (), folded=1)
        if up is not None:
            up._child_s += t1 - t0
        tracer._record(Span(
            self.name, self._trace_id, self._batch, t0, t1,
            threading.get_ident(), self._args, self.id, parent,
            getattr(local, "role", "other"), self._child_s))

    def __enter__(self):
        self.open()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.close(self._t0, time.perf_counter())
        return False


class Tracer:
    """Span collector with per-batch trace ids and a bounded ring buffer.

    The "current batch" context is a plain attribute set by the engine's
    loop thread (spans from other threads attribute to whatever batch is
    current unless they name theirs). Spans may name their batch
    explicitly (``span(..., batch=...)``) — the pipelined engine does
    this for ``result_wait``/``sink_write``, which complete for batch N
    while batch N+k is already current; one that names none takes its
    parent's.

    Every span has an ``id`` and a ``parent``: the innermost span open on
    the SAME thread when it opened (a per-thread stack, not containment
    in time), and the ``role`` its thread was given (``set_role``:
    ``loop``, ``writer``; ``other`` otherwise).
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
        self._ids = itertools.count(1)
        # per thread: top (innermost open span), last (its last record),
        # role
        self._local = threading.local()
        self._annotation_cls = None
        self._m_spans = None  # rtfds_trace_spans_total, resolved lazily
        self._spans_base = 0.0  # that counter when the ring was last empty

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
            self._spans_base = self._m_spans.value - len(self._buf)
        return self

    @property
    def capacity(self) -> int:
        return self._buf.maxlen or 0

    def __len__(self) -> int:
        return len(self._buf)

    def set_role(self, role: str) -> str:
        """Name the calling thread's role for the spans it records from
        now on; → the role it had (``other`` by default)."""
        local = self._local
        was = getattr(local, "role", "other")
        local.role = role
        return was

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

    def _batch_of(self, batch: Optional[str], up) -> Tuple[str, int]:
        """(trace id, batch index) of a span: the batch it names, else
        its parent's, else the current one."""
        if batch is None:
            if up is not None:
                return up._trace_id, up._batch
            return self._cur_id, self._cur_batch
        try:
            return batch, int(batch.lstrip("b")) if batch else 0
        except ValueError:
            return batch, 0

    # -- span recording ------------------------------------------------

    def span(self, name: str, batch: Optional[str] = None, **args):
        """A live span: a context manager, or ``open()`` … ``close(t0,
        t1)`` for a caller that reads the clock itself. ``batch``
        overrides the current trace id (the pipelined engine finishes
        batch N while batch N+k is current). Extra kwargs land in the
        exported event's ``args``."""
        if not self.enabled:
            return _NOOP
        return _LiveSpan(self, name, batch, args or None)

    def add_span(self, name: str, t0_perf: float, t1_perf: float,
                 batch: Optional[str] = None,
                 parent: Optional[int] = None, **args) -> None:
        """Record an already-measured span from raw ``perf_counter``
        readings — for what cannot be a live span: a wait between two
        threads (``writer_queue``), an event reported after the fact
        (``xla_compile``). ``parent``: a span id (0: none); default, the
        innermost span open on this thread. No TraceAnnotation (the work
        already happened): work that should show in a profiler capture
        is a live span."""
        if not self.enabled:
            return
        local = self._local
        up = getattr(local, "top", None) if parent is None else None
        trace_id, bidx = self._batch_of(batch, up)
        if parent is None:
            parent = 0
            if up is not None:
                parent = up.id
                up._child_s += t1_perf - t0_perf
        self._record(Span(name, trace_id, bidx, t0_perf - self._t0,
                          t1_perf - self._t0, threading.get_ident(),
                          args or None, next(self._ids), parent,
                          getattr(local, "role", "other")))

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker (recompile events, model reloads)."""
        if not self.enabled:
            return
        t = time.perf_counter()
        self.add_span(name, t, t, **args)

    def _record(self, span: Span) -> None:
        self._buf.append(span)  # deque append is atomic + O(1) eviction
        self._local.last = span
        if self._m_spans is not None:
            self._m_spans.inc()

    # -- export --------------------------------------------------------

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            if self._m_spans is not None:
                self._spans_base = self._m_spans.value

    @property
    def dropped(self) -> int:
        """Spans recorded since the ring was last empty that it no
        longer holds."""
        if self._m_spans is None:
            return 0
        return max(0, int(self._m_spans.value - self._spans_base)
                   - len(self._buf))

    def export_chrome(self) -> dict:
        """→ Chrome-trace (catapult) JSON object: ``{"traceEvents":
        [...], "displayTimeUnit": "ms", ...}``. Events are complete
        ("ph": "X") spans with µs timestamps, sorted by ``ts`` so any
        streaming consumer sees a monotone timeline; per-batch trace ids
        ride in ``args.trace_id``, the tree in ``args.id`` /
        ``args.parent`` and the thread's role in ``args.role``. Loadable
        in ui.perfetto.dev / chrome://tracing as-is."""
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
                "args": {"trace_id": s.trace_id, "batch": s.batch,
                         "id": s.id, "parent": s.parent, "role": s.role},
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
                "spans_dropped_by_ring": self.dropped,
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
    "update", "merge", "stamp", "reset", "scatter",  # ops/windows
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
    # the two parts of the tree ensembles' classify (models/forest.py):
    # selector contraction + threshold compare; z contraction + leaf sum
    "decide", "leaves",
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


def covered(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


def self_times_us(events: List[dict]) -> Dict[int, float]:
    """``id(event)`` → the event's self time in µs: its duration minus
    what the events naming it as ``args.parent`` cover of it. An event
    without ``args.id`` (a trace from before spans had parents) has no
    children and keeps its whole duration."""
    kids: Dict[int, List[Tuple[float, float]]] = {}
    for e in events:
        parent = (e.get("args") or {}).get("parent")
        if parent:
            ts = float(e.get("ts", 0.0))
            kids.setdefault(int(parent), []).append(
                (ts, ts + float(e.get("dur", 0.0))))
    out = {}
    for e in events:
        ts, dur = float(e.get("ts", 0.0)), float(e.get("dur", 0.0))
        mine = kids.get(int((e.get("args") or {}).get("id") or 0), ())
        cover = covered([(max(s, ts), min(t, ts + dur)) for s, t in mine
                         if t > ts and s < ts + dur])
        out[id(e)] = max(0.0, dur - cover)
    return out


def summarize_chrome(trace: dict, top_k: int = 10) -> dict:
    """Digest a Chrome-trace JSON object (as exported above) into the
    per-batch critical path, the top-K slowest spans, self time by span
    name and thread role, and the XLA compile/recompile events —
    everything ``rtfds trace`` prints.

    Per batch (the spans carrying its trace id): ``total_ms`` is the
    duration of its outermost spans (those whose parent is not one of the
    batch's own: nested spans are in their parent's duration already),
    ``phases_ms`` each name's duration, ``self_ms`` each name's self time
    (duration minus what its children cover), and the *critical phase*
    the name with the most self time: where the batch's time went and
    was not handed further down."""
    events = [e for e in trace.get("traceEvents", [])
              if e.get("ph") == "X"]
    self_us = self_times_us(events)
    batches = []
    for tid, evs in sorted(_batch_events(events).items()):
        if not tid:
            continue
        own = {(e.get("args") or {}).get("id") for e in evs} - {None, 0}
        phases: Dict[str, float] = {}
        selfs: Dict[str, float] = {}
        total = 0.0
        for e in evs:
            dur = float(e.get("dur", 0.0))
            phases[e["name"]] = phases.get(e["name"], 0.0) + dur
            selfs[e["name"]] = selfs.get(e["name"], 0.0) + self_us[id(e)]
            if (e.get("args") or {}).get("parent") not in own:
                total += dur
        crit = max(selfs.items(), key=lambda kv: kv[1]) \
            if selfs else ("", 0.0)
        batches.append({
            "trace_id": tid,
            "batch": (evs[0].get("args") or {}).get("batch"),
            "total_ms": round(total / 1e3, 3),
            "critical_phase": crit[0],
            "critical_ms": round(crit[1] / 1e3, 3),
            "phases_ms": {k: round(v / 1e3, 3)
                          for k, v in sorted(phases.items())},
            "self_ms": {k: round(v / 1e3, 3)
                        for k, v in sorted(selfs.items())},
        })
    by_name: Dict[Tuple[str, str], List[float]] = {}
    for e in events:
        key = (str((e.get("args") or {}).get("role", "")), e["name"])
        acc = by_name.setdefault(key, [0, 0.0, 0.0])
        acc[0] += 1
        acc[1] += float(e.get("dur", 0.0))
        acc[2] += self_us[id(e)]
    self_time = [{
        "role": role, "name": name, "count": n,
        "total_ms": round(dur / 1e3, 3), "self_ms": round(own / 1e3, 3),
    } for (role, name), (n, dur, own) in sorted(
        by_name.items(), key=lambda kv: -kv[1][2])]
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
                 if k not in ("trace_id", "batch", "id", "parent", "role")},
    } for e in events if e["name"] in ("xla_compile", "xla_recompile")]
    return {
        "batches": batches,
        "slowest_spans": top,
        "self_time": self_time,
        "compile_events": compiles,
        "n_events": len(events),
    }
