"""The reduction from a profiler trace to device metrics.

``load_xplane`` turns the profiler's ``.xplane.pb`` into plain lists (so a
small recorded trace can be kept as JSON for the test); ``summarize`` takes
the device planes' op line and the host plane's engine spans and returns

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
- ``idle_pct``: 1 - busy over the traced window;
- ``device_step_ms``: busy time per batch completed in the traced window;
- ``device_ops``: the ten operations with most device time, by name;
- ``idle_gaps``: idle gaps longer than 1 ms on the first device, each put
  down to the engine span that covers most of it, summed by span, the ten
  largest.

``read`` is the per-layer reader: a key of that summary.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OP_LINE = "XLA Ops"
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


def summarize(trace: dict, window_s: float, batches: int) -> Optional[dict]:
    """Device metrics of one traced window; None when no operation ran on
    a device in it."""
    device_planes = sorted(
        (p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])),
        key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))
    busy_ns, op_ns, first_union = [], defaultdict(int), None
    for plane in device_planes:
        ops = [ev for line in plane["lines"] if line["name"] == OP_LINE
               for ev in line["events"]]
        if not ops:
            continue
        merged = union([(s, s + d) for _, s, d in ops])
        busy_ns.append(sum(e - s for s, e in merged))
        if first_union is None:
            first_union = merged
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
    if batches > 0:
        out["device_step_ms"] = busy_s / batches * 1e3
    return out
