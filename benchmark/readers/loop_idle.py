"""The chip's idle gaps, each put down to what the loop thread was doing —
and how long a dispatched batch lay waiting for the chip.

The profiler's capture (the traced window's last 3 s) gives the first
device's busy union and its ``XLA Modules`` line; the process ``Tracer``'s
ring (``readers/tracer_spans.py``: the last ``run``'s tree) gives the
program's spans with their parents and thread roles. The two clocks are
joined by the ``rtfds.<name>#<batch>`` annotations the Tracer opens around
its live spans: every annotation whose ``(name, batch)`` names exactly one
ring span votes its start minus that span's start, the offset is the
median vote and its scatter (quartile distance, farthest vote) is printed
on the ``[loop_idle]`` line.

**Booking.** Every gap of at least 1 ms between two busy intervals is
split among the ``role == "loop"`` spans of the tree: each instant of it
goes to the deepest span open at that instant (a gap inside one span goes
to it whole; the idle after a compaction pass runs through the landing,
the hand-over to the writer, the next pass's poll, prep and promote, and
each gets its part). A writer-thread span is never a candidate — it
overlaps anything. ``stat="idle_pct"`` is the sum of the gaps booked to
or inside a pass-level span (``phase_of``: the child of ``loop_pass`` on
the gap's path) named in ``spans``, over the traced window, in per cent;
``spans=[]`` takes the gaps booked to ``run`` or ``loop_pass`` themselves
or to nothing (the loop thread under no named span). A tree in which no
such gap fell reads 0.0.

**``stat="device_queue_ms"``.** Programs run in dispatch order, so the
capture's step programs (``XLA Modules`` events of a millisecond or more
that are neither a ``promote`` nor a ``compact``; a batch's promotes lead
its step) line up
one to one with the ring's ``dispatch`` spans: the k-th step of the
capture is batch ``i0 + k``, with ``i0`` the first alignment under which
every step ends before its own batch's ``device_wait`` does (a step ends
a whole step after the ``device_wait`` of the batch before). The stat is
the median over the traced steps of
(first event of the batch's first program − end of its ``dispatch``
span), floored at 0. A batch that spilled into several chunks (several
step programs) is not told apart from several batches: the cells do not
spill.

No capture, no ``run`` root (a program from before PR 37), or no
annotation to join the clocks by: ``None``, the metric is left out.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.readers import device_scopes, device_trace, tracer_spans

CTX_KEY = "loop_idle"
ANNOTATION = re.compile(r"^rtfds\.(.+)#(\d+)$")
MODULE_LINE = device_trace.MODULE_LINE
MIN_GAP_S = 1e-3
UNSPANNED = ("run", "loop_pass")


def read(ctx: dict, spans: Sequence[str] = (), stat: str = "idle_pct"):
    t = table(ctx)
    if t is None:
        return None
    if stat == "idle_pct":
        window_s = ctx["trace_summary"]["window_s"]
        return 100.0 * idle_under(t["booked"], spans) / window_s
    if stat == "device_queue_ms":
        waits = t["queue_s"]
        return tracer_spans.median(waits) * 1e3 if waits else None
    raise ValueError(f"unknown stat {stat!r}")


def table(ctx: dict) -> Optional[dict]:
    """Booked gaps and queue waits, computed once a run of the harness."""
    if CTX_KEY not in ctx:
        ctx[CTX_KEY] = None
        tree = tracer_spans.window_tree(ctx)
        path = device_scopes.find_trace() if tree is not None else None
        if path:
            ctx[CTX_KEY] = reduce(tree, load_capture(path))
    return ctx[CTX_KEY]


# -- the capture ---------------------------------------------------------------


def load_capture(path: str) -> dict:
    """→ ``{"busy": [[start, end], ...] (the first device's op union),
    "modules": [[name, start, end], ...] (its ``XLA Modules`` line),
    "annotations": [[name, batch, start], ...]}``, seconds on the
    capture's clock."""
    from jax.profiler import ProfileData

    first, annotations = None, []
    for plane in ProfileData.from_file(path).planes:
        m = device_scopes.DEVICE_PLANE.match(plane.name)
        if m:
            if first is None or int(m.group(1)) < first[0]:
                first = (int(m.group(1)), plane)
            continue
        for line in plane.lines:
            for e in line.events:
                a = ANNOTATION.match(e.name)
                if a:
                    annotations.append(
                        [a.group(1), int(a.group(2)), e.start_ns / 1e9])
    ops, modules = [], []
    for line in (first[1].lines if first else ()):
        if line.name == device_scopes.OP_LINE:
            ops = [(e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                   for e in line.events]
        elif line.name == MODULE_LINE:
            modules = [[e.name, e.start_ns / 1e9,
                        (e.start_ns + e.duration_ns) / 1e9]
                       for e in line.events]
    return {"busy": device_trace.union(ops), "modules": modules,
            "annotations": annotations}


# -- the reduction -------------------------------------------------------------


def reduce(tree: dict, capture: dict) -> Optional[dict]:
    """→ ``{"booked": [(seconds, names from the root down), ...], "gaps",
    "queue_s": [...], "offset_s", "scatter", "programs"}``; ``None`` where
    the clocks cannot be joined."""
    joined = clock_offset(tree, capture["annotations"])
    if joined is None:
        return None
    offset, scatter = joined
    busy = capture["busy"]
    booked, gaps = [], 0
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        if s1 - e0 >= MIN_GAP_S:
            gaps += 1
            booked += book(tree, e0 - offset, s1 - offset)
    queue_s = queue_waits(tree, capture["modules"], offset)
    programs: Dict[str, int] = {}
    for name, _, _ in capture["modules"]:
        programs[name] = programs.get(name, 0) + 1
    out = {"booked": booked, "gaps": gaps, "queue_s": queue_s,
           "offset_s": offset, "scatter": scatter, "programs": programs}
    say(out)
    return out


def clock_offset(tree: dict, annotations) -> Optional[Tuple[float, dict]]:
    """Capture clock minus ring clock: the median over the annotations
    that name exactly one ring span (and are themselves the only one of
    their name and batch); and how far the votes lie apart."""
    ring: Dict[Tuple[str, int], List[float]] = {}
    for r in tree["rows"]:
        ring.setdefault((r["name"], r["batch"]), []).append(r["t0"])
    seen: Dict[Tuple[str, int], List[float]] = {}
    for name, batch, start in annotations:
        seen.setdefault((name, batch), []).append(start)
    votes = sorted(starts[0] - ring[key][0] for key, starts in seen.items()
                   if len(starts) == 1 and len(ring.get(key, ())) == 1)
    if not votes:
        return None
    offset = tracer_spans.median(votes)
    q1, q3 = votes[len(votes) // 4], votes[(3 * len(votes)) // 4]
    return offset, {"pairs": len(votes), "iqr_us": (q3 - q1) * 1e6,
                    "max_us": max(abs(v - offset) for v in votes) * 1e6}


def book(tree: dict, a: float, b: float) -> List[Tuple[float, tuple]]:
    """The gap ``[a, b)`` (ring clock) split among the loop-thread spans:
    every instant of it goes to the deepest span open at that instant.
    → ``[(seconds, names from the root down), ...]``; ``()`` for what lies
    outside the root."""
    root = tree["root"]
    lo, hi = max(a, root["t0"]), min(b, root["t1"])
    out = [(b - a - max(0.0, hi - lo), ())]

    def split(node: dict, lo: float, hi: float, path: tuple) -> None:
        path += (node["name"],)
        own = hi - lo
        for k in tree["kids"].get(node["id"], ()):
            s, e = max(k["t0"], lo), min(k["t1"], hi)
            if k["role"] == "loop" and e > s:
                split(k, s, e, path)
                own -= e - s
                lo = e  # children of one thread's span do not overlap
        out.append((own, path))

    if hi > lo:
        split(root, lo, hi, ())
    return [(s, path) for s, path in out if s > 1e-9]


def phase_of(path: Sequence[str]) -> Optional[str]:
    """The pass-level span on a booked path: the child of ``loop_pass`` —
    or of a ``pace`` pass (an empty poll that still drained the batches
    in flight), or of ``run`` in the drain after the last pass — that the
    gap lies under. ``None``: under ``run`` / ``loop_pass`` themselves or
    under nothing. Every gap has one, so metrics whose ``spans`` share no
    name never count a gap twice."""
    inner = [name for name in path if name not in UNSPANNED]
    if len(inner) > 1 and inner[0] == "pace":
        return inner[1]
    return inner[0] if inner else None


def idle_under(booked, spans: Sequence[str]) -> float:
    """Seconds of the gaps booked to, or inside, a pass-level span named
    in ``spans``; ``spans`` empty: the gaps with none."""
    if spans:
        return sum(s for s, path in booked if phase_of(path) in spans)
    return sum(s for s, path in booked if phase_of(path) is None)


def program_kind(name: str, seconds: float) -> str:
    """A program on the ``XLA Modules`` line: the cold tier's ``promote``
    and the ``compact`` pass by their jitted functions' names; any other
    that runs a millisecond or more is a batch's step (``jit_step`` on one
    chip, ``jit_outer`` on the mesh); the rest (one-op programs) nobody's."""
    if "promote" in name:
        return "promote"
    if "compact" in name:
        return "compact"
    return "step" if seconds >= MIN_GAP_S else "other"


def queue_waits(tree: dict, modules, offset: float) -> List[float]:
    """Seconds each traced batch's first program started after its
    ``dispatch`` span closed (floored at 0), by the alignment the module
    docstring gives; ``[]`` where the capture and the ring do not line
    up."""
    loop = [r for r in tree["rows"] if r["role"] == "loop"]
    dispatches = sorted((r for r in loop if r["name"] == "dispatch"),
                        key=lambda r: r["t0"])
    wait_end = {r["batch"]: r["t1"] for r in loop
                if r["name"] == "device_wait"}
    # (first program's start, the step's start, its end), ring clock
    steps, lead = [], None
    for name, start, end in sorted(modules, key=lambda m: m[1]):
        kind = program_kind(name, end - start)
        if kind == "promote" and lead is None:
            lead = start
        elif kind == "step":
            steps.append(((start if lead is None else lead) - offset,
                          start - offset, end - offset))
            lead = None
    if not steps or not dispatches:
        return []
    slack = 5e-4  # the clocks' join is good to well under this
    ends = [wait_end.get(d["batch"], float("inf")) for d in dispatches]
    # a step is its batch's if it ended before that batch's device_wait
    # did and after the batch's before: the first such batch, for every
    # step, and one shift for all of them
    i0 = max(next((i for i, e in enumerate(ends) if e >= end - slack),
                  len(ends)) - k for k, (_, _, end) in enumerate(steps))
    if i0 < 0 or i0 + len(steps) > len(dispatches) or any(
            start < dispatches[i0 + k]["t0"] - slack
            for k, (_, start, _) in enumerate(steps)):
        return []  # a step ahead of its own dispatch: not lined up
    # (a promote is dispatched ahead of its batch's `dispatch` span and may
    # start before that closes: no wait)
    return [max(0.0, first - dispatches[i0 + k]["t1"])
            for k, (first, _, _) in enumerate(steps)]


def say(t: dict) -> None:
    by: Dict[str, float] = {}
    for s, path in t["booked"]:
        key = "/".join(path) or "(no span)"
        by[key] = by.get(key, 0.0) + s
    gaps = " ".join(f"{k}={v:.4f}" for k, v in
                    sorted(by.items(), key=lambda kv: -kv[1]))
    sc = t["scatter"]
    print(f"[loop_idle] offset_pairs={sc['pairs']} "
          f"offset_iqr_us={sc['iqr_us']:.1f} offset_max_us={sc['max_us']:.1f} "
          f"gaps={t['gaps']} gap_s={sum(s for s, _ in t['booked']):.4f} "
          f"queue_steps={len(t['queue_s'])} programs={t['programs']} "
          f"booked: {gaps}", flush=True)
