"""The reduction from a profiler trace to device metrics.

``load_xplane`` turns the profiler's ``.xplane.pb`` into plain lists (so a
small recorded trace can be kept as JSON for the test); ``summarize`` takes
the device planes' op line and the host plane's engine spans and returns

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
- ``idle_pct``: 1 - busy over the traced window;
- ``steps``, ``device_step_ms``: the whole executions of the step program
  the first device's trace holds, and their mean duration (below);
- ``device_ops``: the ten operations with most device time, by name;
- ``idle_gaps``: idle gaps longer than 1 ms on the first device, each put
  down to the engine span that covers most of it, summed by span, the ten
  largest.

**Per step, since PR 40.** A device plane carries, beside its op line, a
line with one event an execution of a whole program (``MODULE_LINE``; the
names are the jitted functions': ``jit_step(<fingerprint>)`` on one chip,
``jit_outer(...)`` for the mesh's step, ``jit_compact``, ``jit_promote``;
looked at on v5e traces, my chip runs, PRs 37 and 40, and kept in
``benchmark/tests/data/steps_*.json``). The step's executions are the
events ``STEP_MODULE`` matches. The profiler cuts the execution in flight
at its start to the trace's first instant and the one in flight at its
stop to its last (a 42.4 ms and a 2.1 ms ``jit_step`` around 33 of 89.0
in ``forest.saturate``), with nothing on the event to say so: an execution
is *whole* when an operation of the plane began before its start and
another after its end, each by a microsecond or more (``whole_steps``; a
cut execution's edge lies within nanoseconds of the trace's first or last
operation, on either side of it). ``device_step_ms`` is the summed duration of
the whole executions ÷ their count — the step alone, no compaction or
promote program in it, no acknowledgement counted. ``span`` runs from the
first whole execution's start to the last one's end; the stage metrics
(``device_scopes``) read what ran inside it and divide by the same count.
On four chips the count is the first chip's, as the stages are.

``read`` is the per-layer reader: a key of that summary.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
STEP_MODULE = re.compile(r"^jit_(step|outer)\(")
ENGINE_SPANS = ("source_poll", "host_prep", "dispatch", "result_wait",
                "sink_write")
# the engine's Tracer opens its TraceAnnotations as rtfds.<span>#<batch>
SPAN_EVENT = re.compile(r"^rtfds\.([a-z_]+)#")
MIN_GAP_NS = 1_000_000


def read(ctx: dict, key: str):
    return (ctx.get("trace_summary") or {}).get(key)


def _span_name(event_name: str) -> Optional[str]:
    m = SPAN_EVENT.match(event_name)
    return m.group(1) if m and m.group(1) in ENGINE_SPANS else None


def load_xplane(path: str) -> dict:
    """→ ``{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, duration_ns], ...]}]}]}``. Device planes keep every line;
    host planes keep only the engine's spans, under the span's name."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events]
            else:
                events = [[_span_name(e.name), int(e.start_ns),
                           int(e.duration_ns)] for e in line.events
                          if _span_name(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals; → disjoint, sorted."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _overlap(a: Tuple[int, int], spans: List[Tuple[int, int, str]]) -> str:
    """The span name covering most of interval ``a``."""
    best, share = "(no engine span)", 0
    by_name: Dict[str, int] = defaultdict(int)
    for s, e, name in spans:
        if e <= a[0] or s >= a[1]:
            continue
        by_name[name] += min(e, a[1]) - max(s, a[0])
    for name, ns in by_name.items():
        if ns > share:
            best, share = name, ns
    return best


def whole_steps(modules: List[list], ops: List[list],
                per_us: int = 1000) -> List[Tuple[int, int]]:
    """``[(start, end)]`` of the step program's executions on one plane
    that the trace holds whole, in time order. ``modules`` and ``ops`` are
    that plane's two lines as ``[name, start, duration, ...]`` in one time
    unit, ``per_us`` of it a microsecond (nanoseconds by default). Whole:
    an operation began before the execution's start (the trace was already
    running) and one began after its end (it was still running), each by
    a microsecond or more — an execution the trace cuts shares its edge
    with the trace's first or last operation to within a few nanoseconds,
    two programs in a row lie 4-9 us apart — so an execution at either end
    of the trace that cannot show both is taken for cut."""
    if not ops:
        return []
    first_op = min(e[1] for e in ops)
    last_op = max(e[1] for e in ops)
    steps = sorted((e[1], e[1] + e[2]) for e in modules
                   if STEP_MODULE.match(e[0]) and e[2] > 0)
    return [(s, e) for s, e in steps
            if first_op + per_us <= s and e + per_us <= last_op]


def summarize(trace: dict, window_s: float, batches: int) -> Optional[dict]:
    """Device metrics of one traced window; None when no operation ran on
    a device in it. ``batches`` (the acknowledgements the harness counted
    inside the trace) is kept on the summary and divides nothing."""
    device_planes = sorted(
        (p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])),
        key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))
    busy_ns, op_ns, first_union, steps = [], defaultdict(int), None, []
    for plane in device_planes:
        ops = [ev for line in plane["lines"] if line["name"] == OP_LINE
               for ev in line["events"]]
        if not ops:
            continue
        merged = union([(s, s + d) for _, s, d in ops])
        busy_ns.append(sum(e - s for s, e in merged))
        if first_union is None:
            first_union = merged
            steps = whole_steps(
                [ev for line in plane["lines"]
                 if line["name"] == MODULE_LINE for ev in line["events"]],
                ops)
        for name, _, d in ops:
            # "%fusion.12 = f32[...] fusion(...)": the name before the " = "
            op_ns[name.split(" = ", 1)[0]] += d
    if not busy_ns or sum(busy_ns) == 0:
        return None
    n_dev = len(busy_ns)
    busy_s = sum(busy_ns) / n_dev / 1e9
    spans = [(s, s + d, name) for p in trace["planes"]
             if not DEVICE_PLANE.match(p["name"])
             for line in p["lines"] for name, s, d in line["events"]
             if name in ENGINE_SPANS]
    gap_ns: Dict[str, int] = defaultdict(int)
    for (_, e0), (s1, _) in zip(first_union, first_union[1:]):
        if s1 - e0 >= MIN_GAP_NS:
            gap_ns[_overlap((e0, s1), spans)] += s1 - e0

    def top(d: Dict[str, int]) -> list:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    out = {
        "busy_s": busy_s,
        "window_s": float(window_s),
        "devices": n_dev,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "device_ops": top({k: v // n_dev for k, v in op_ns.items()}),
        "idle_gaps": top(gap_ns),
    }
    out["acks"] = int(batches)
    out["steps"] = len(steps)
    if steps:
        out["device_step_ms"] = sum(e - s for s, e in steps) / len(steps) / 1e6
        out["span_s"] = (steps[-1][1] - steps[0][0]) / 1e9
    return out
