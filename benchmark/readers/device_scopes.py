"""The device step's time by stage: the busy time of the first device,
split by the ``rtfds.<stage>`` scopes the program puts on its step
(``utils/trace.STEP_SCOPES``, opened with ``jax.named_scope``).

``read(ctx, scopes=[...], stat="ms")`` is the device time that ran under
any of ``scopes`` inside the trace's span of whole steps
(``device_trace.whole_steps``: first whole execution's start → last one's
end) ÷ the number of those steps, in milliseconds. ``device_step_ms`` is the
same steps' mean duration, so the stage metrics and ``device_step_ms`` have
one denominator — the steps the trace holds, never the acknowledgements —
and the stages whose scopes live inside the step plus the unscoped rest
never exceed it; a scope opened in a program of its own (``rtfds.compact``,
``rtfds.demote``, ``rtfds.promote``) reads that program's device time
inside the span ÷ the same count. ``stat="unscoped_pct"`` is the share, in
per cent, of the whole steps' own busy time under no ``rtfds.`` component
at all (what a compaction leaves unnamed is not in it). A scope is given
as ``"rtfds.update/rtfds.reset"`` and matches as consecutive components
of an operation's HLO ``op_name``
(``jit(step)/rtfds.terminal/rtfds.update/rtfds.reset/select_n``), so
``"rtfds.query"`` takes both tables' and a later
``"rtfds.terminal/rtfds.update/rtfds.reset"`` one table's.

Every picosecond of the busy union counts once, under the ``op_name`` of
the *shortest* event covering it: a ``while``'s event covers its body's
events, and the body's names are the ones that say what ran.

**Where the ``op_name`` comes from** (looked at by hand on a v5e trace, my
chip run, PR 24): the ``tf_op`` stat of the event's *metadata* on the
device plane, which xprof fills with ``<op_name>:<op_type>`` from the HLO
instruction's metadata. ``jax.profiler.ProfileData`` shows an event's own
stats (``device_offset_ps``, ``device_duration_ps``) but not its
metadata's, so this file reads the ``.xplane.pb`` itself: protobuf's wire
format, the few fields of ``XSpace`` it needs, no dependency. The HLO
module protos the trace also carries (``/host:metadata``, stat
``Hlo Proto``) were the second choice and are not needed: every fusion,
reshape, copy and gather of the step has the stat. What lacks it: ``while``
itself (only its body's events carry one; the body covers all but the loop's
own few microseconds) and what the compiler inserts with no metadata
(layout ``copy`` passes, ``copy-start``/``-done``, ``slice-done``). A fusion
has its root's ``op_name``. All of these land in ``unscoped`` unless a
shorter scoped event covers them, and ``step_unscoped_pct`` is their sum.

**Where the file comes from.** The harness hands readers no path, so:
``--trace-dir`` from ``sys.argv`` when given, else the newest
``rtfds-trace-*`` directory under ``tempfile.gettempdir()`` (the harness's
``mkdtemp`` prefix; it lives until ``run_cell``'s ``finally``, after the
readers ran). The table is kept in ``ctx`` (readers are loaded anew for
every metric). No trace, no device plane, or a program whose step carries
no ``rtfds.`` scope (a commit before PR 24): ``None``, and the metric is
left out of the line.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
import tempfile
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchmark.readers import device_trace

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OP_LINE = "XLA Ops"
OP_NAME_STAT = "tf_op"
PREFIX = "rtfds."
CTX_KEY = "device_scopes"
PS_PER_MS = 1e9
PS_PER_US = 1_000_000


def read(ctx: dict, scopes: Sequence[str] = (), stat: str = "ms"):
    if CTX_KEY not in ctx:
        path = find_trace()
        ctx[CTX_KEY] = per_step(*load_lines(path)) if path else None
    t = ctx[CTX_KEY]
    if t is None:
        return None
    counted = (ctx.get("trace_summary") or {}).get("steps", t["n_steps"])
    if counted != t["n_steps"]:
        raise RuntimeError(
            f"the trace's whole steps: {counted} by device_trace, "
            f"{t['n_steps']} by device_scopes")
    if stat == "unscoped_pct":
        return 100.0 * under(t["steps"], None) / t["steps"]["busy"]
    if stat != "ms":
        raise ValueError(f"unknown stat {stat!r}")
    return under(t["span"], scopes) / t["n_steps"] / PS_PER_MS


# -- the arithmetic ----------------------------------------------------------


def components(op_name: str) -> List[str]:
    return [c.strip() for c in op_name.split("/")]


def matches(op_name: str, scope: str) -> bool:
    """``scope``'s components appear consecutively in ``op_name``'s."""
    have, want = components(op_name), components(scope)
    return any(have[i:i + len(want)] == want
               for i in range(len(have) - len(want) + 1))


def scoped(op_name: str) -> bool:
    return any(c.startswith(PREFIX) for c in components(op_name))


def under(t: dict, scopes: Optional[Sequence[str]]) -> int:
    """Busy time under any of ``scopes``; ``None``: under no ``rtfds.``
    component."""
    if scopes is None:
        return sum(v for k, v in t["by_op_name"].items() if not scoped(k))
    return sum(v for k, v in t["by_op_name"].items()
               if any(matches(k, s) for s in scopes))


def table(events: List[list]) -> Optional[dict]:
    """``[[name, start, duration, op_name], ...]`` (one device's op line,
    any one time unit) → ``{"busy": the union of the events, "by_op_name":
    {op_name: its part of that union}}``, every instant under the shortest
    event covering it. ``None`` when no event carries an ``rtfds.`` scope."""
    if not any(scoped(e[3]) for e in events):
        return None
    evs = sorted(((s, s + d, d, op) for _, s, d, op in events if d > 0),
                 key=lambda e: e[0])
    points = sorted({p for s, e, _, _ in evs for p in (s, e)})
    by_op: Dict[str, int] = defaultdict(int)
    active: List[Tuple[int, int, int, str]] = []
    nxt = 0
    for t0, t1 in zip(points, points[1:]):
        while nxt < len(evs) and evs[nxt][0] <= t0:
            active.append(evs[nxt])
            nxt += 1
        active = [e for e in active if e[1] > t0]
        if active:
            by_op[min(active, key=lambda e: e[2])[3]] += t1 - t0
    return {"busy": sum(by_op.values()), "by_op_name": dict(by_op)}


def clip(events: List[list], spans: List[Tuple[int, int]]) -> List[list]:
    """The parts of ``events`` (``[name, start, duration, ...]``) that lie
    inside the disjoint, sorted ``spans``."""
    ends = [b for _, b in spans]
    out = []
    for ev in events:
        s, e = ev[1], ev[1] + ev[2]
        for a, b in spans[bisect.bisect_right(ends, s):]:
            if a >= e:
                break
            out.append([ev[0], max(s, a), min(e, b) - max(s, a)] + ev[3:])
    return out


def per_step(ops: List[list], modules: List[list]) -> Optional[dict]:
    """One device's op line (``[[name, start_ps, duration_ps, op_name]]``)
    and module line (``[[name, start_ps, duration_ps]]``) → ``{"n_steps",
    "span": table of what ran from the first whole step's start to the
    last one's end, "steps": table of what ran inside the whole steps}``.
    ``None`` when the step carries no ``rtfds.`` scope or the trace holds
    no whole step."""
    steps = device_trace.whole_steps(modules, ops, per_us=PS_PER_US)
    if not steps:
        return None
    span = table(clip(ops, [(steps[0][0], steps[-1][1])]))
    inside = table(clip(ops, steps))
    if span is None or inside is None:
        return None
    return {"n_steps": len(steps), "span": span, "steps": inside}


# -- the file ----------------------------------------------------------------


def find_trace() -> Optional[str]:
    """The ``.xplane.pb`` the harness just wrote, or None."""
    root = None
    argv = sys.argv
    for i, a in enumerate(argv):
        if a == "--trace-dir" and i + 1 < len(argv):
            root = argv[i + 1]
        elif a.startswith("--trace-dir="):
            root = a.split("=", 1)[1]
    if not root:
        dirs = glob.glob(os.path.join(tempfile.gettempdir(),
                                      "rtfds-trace-*"))
        if not dirs:
            return None
        root = max(dirs, key=os.path.getmtime)
    return find_trace_under(root)


def find_trace_under(root: str) -> Optional[str]:
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _varint(buf, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for anything with a length or a fixed width."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in the trace")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(v) -> Tuple[int, object]:
    d = dict(fields(v))
    return d.get(1, 0), d.get(2, b"")


def load_lines(path: str) -> Tuple[List[list], List[list]]:
    """The first device's ``XLA Ops`` line as ``[[name, start_ps,
    duration_ps, op_name], ...]`` (``op_name`` is "" where the event's
    metadata has no ``tf_op`` stat) and its ``XLA Modules`` line as
    ``[[name, start_ps, duration_ps], ...]``, both on one clock (a line's
    ``timestamp_ns`` plus the event's offset). Field numbers are those of
    ``tsl/profiler/protobuf/xplane.proto``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    best = None
    for num, plane in fields(space):
        if num != 1:  # XSpace.planes
            continue
        name = next((_text(v) for n, v in fields(plane) if n == 2), "")
        m = DEVICE_PLANE.match(name)
        if m and (best is None or int(m.group(1)) < best[0]):
            best = (int(m.group(1)), plane)
    if best is None:
        return [], []
    lines, event_meta, stat_names = [], {}, {}
    for num, v in fields(best[1]):
        if num == 3:  # XPlane.lines
            lines.append(v)
        elif num == 4:  # XPlane.event_metadata: map<int64, XEventMetadata>
            k, meta = _map_entry(v)
            event_meta[k] = meta
        elif num == 5:  # XPlane.stat_metadata: map<int64, XStatMetadata>
            k, meta = _map_entry(v)
            stat_names[k] = _text(dict(fields(meta)).get(2, b""))
    names: Dict[int, Tuple[str, str]] = {}
    for k, meta in event_meta.items():
        name, op_name = "", ""
        for num, v in fields(meta):
            if num == 2:  # XEventMetadata.name
                name = _text(v)
            elif num == 5:  # XEventMetadata.stats
                s = dict(fields(v))
                if stat_names.get(s.get(1)) != OP_NAME_STAT:
                    continue
                if 5 in s:  # XStat.str_value
                    op_name = _text(s[5])
                elif 7 in s:  # XStat.ref_value: a string kept as a stat name
                    op_name = stat_names.get(s[7], "")
        # "<op_name>:<op_type>"
        names[k] = (name, op_name.rsplit(":", 1)[0])
    ops, modules = [], []
    for line in lines:
        events, line_name, t0_ps = [], "", 0
        for num, v in fields(line):
            if num == 4:  # XLine.events
                events.append(v)
            elif num == 2:  # XLine.name
                line_name = _text(v)
            elif num == 3:  # XLine.timestamp_ns
                t0_ps = int(v) * 1000
        if line_name not in (OP_LINE, device_trace.MODULE_LINE):
            continue
        for ev in events:
            e = dict(fields(ev))  # metadata_id 1, offset_ps 2, duration_ps 3
            name, op_name = names.get(e.get(1, 0), ("", ""))
            start, dur = t0_ps + int(e.get(2, 0)), int(e.get(3, 0))
            if line_name == OP_LINE:
                ops.append([name, start, dur, op_name])
            else:
                modules.append([name, start, dur])
    return ops, modules
