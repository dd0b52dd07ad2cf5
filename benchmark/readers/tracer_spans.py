"""The host side of the window, from the program's own spans: the ring of
the process ``Tracer`` (``utils/trace.py``), which in a traced run holds
every span of the whole window — the harness enables it before the window
opens — and not the profiler's last 3 s.

``read(ctx, spans=[...], stat=...)`` keeps the tree of the **last ``run``
root** (one ``engine.run()``: the window and its drain) and the spans of
that run's writer thread, and reports over the spans named in ``spans``:

- ``p50_ms`` / ``mean_ms``: of their durations (``None``: none was
  recorded, the metric is left out of the line);
- ``share_pct``: the sum of their durations over the root's (0.0 where
  none was recorded: the run never did that);
- ``self_share_pct``: the same of their *self* times — a span's duration
  minus what its children cover of it. Of ``run`` and ``loop_pass`` it is
  the share of the run the loop thread spent under no named span: the
  health of the instrumentation, as ``step_unscoped_pct`` is the step's.

No ``run`` root in the ring — a program from before spans had parents, or
tracing off — is ``None`` for every stat. A ring that dropped spans is an
error: the window would be read in part.

The arithmetic works on plain rows (``rows_of``: ``{id, parent, name,
role, batch, t0, t1}``, seconds on the tracer's clock), so a recorded ring
kept as JSON is read the same way (``tests/test_host_readers.py``).
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

CTX_KEY = "tracer_spans"
ROOT = "run"


def read(ctx: dict, spans: Sequence[str] = (), stat: str = "p50_ms"):
    tree = window_tree(ctx)
    return None if tree is None else stat_of(tree, spans, stat)


def window_tree(ctx: dict) -> Optional[dict]:
    """The last run's tree, built once a run of the harness (readers are
    loaded anew for every metric; ``ctx`` is not)."""
    if CTX_KEY not in ctx:
        tree = last_run(ring())
        if tree is not None:
            if dropped():
                raise RuntimeError(
                    f"the Tracer's ring dropped {dropped()} spans: the "
                    "window's spans are not all there (raise its capacity)")
            say(tree)
        ctx[CTX_KEY] = tree
    return ctx[CTX_KEY]


# -- the ring ------------------------------------------------------------------


def rows_of(spans) -> List[dict]:
    """``Span`` objects → plain rows. A span without ``id`` / ``parent``
    (a program from before PR 37) becomes a root of its own."""
    return [{"id": int(getattr(s, "id", 0)),
             "parent": int(getattr(s, "parent", 0)),
             "name": s.name, "role": getattr(s, "role", "other"),
             "batch": int(s.batch), "t0": float(s.t0), "t1": float(s.t1)}
            for s in spans]


def ring() -> List[dict]:
    """The process Tracer's ring, as rows."""
    from real_time_fraud_detection_system_tpu.utils.trace import get_tracer

    return rows_of(get_tracer().snapshot())


def dropped() -> int:
    """Spans the process Tracer's ring no longer holds (asked only of a
    Tracer whose ring held a ``run`` root: one that has the count)."""
    from real_time_fraud_detection_system_tpu.utils.trace import get_tracer

    return int(get_tracer().dropped)


# -- the tree ------------------------------------------------------------------


def last_run(rows: List[dict]) -> Optional[dict]:
    """→ ``{"root", "rows", "kids"}``: the last ``run`` root, its
    descendants by parent links, and the spans of its writer thread
    (``role == "writer"`` roots that start inside it, with theirs);
    ``kids`` maps a span id to its children, by start. ``None`` without a
    root."""
    roots = [r for r in rows if r["name"] == ROOT and not r["parent"]
             and r["id"]]
    if not roots:
        return None
    root = max(roots, key=lambda r: r["t0"])
    kids: Dict[int, List[dict]] = defaultdict(list)
    for r in sorted(rows, key=lambda r: r["t0"]):
        kids[r["parent"]].append(r)
    tops = [root] + [r for r in kids[0] if r["role"] == "writer"
                     and root["t0"] <= r["t0"] <= root["t1"]]
    members, stack = [], list(tops)
    while stack:
        r = stack.pop()
        members.append(r)
        if r["id"]:
            stack.extend(kids.get(r["id"], ()))
    return {"root": root, "rows": members, "kids": kids}


def covered(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


def self_s(tree: dict, row: dict) -> float:
    """``row``'s duration minus what its children cover of it."""
    mine = [(max(k["t0"], row["t0"]), min(k["t1"], row["t1"]))
            for k in tree["kids"].get(row["id"], ()) if row["id"]]
    return max(0.0, row["t1"] - row["t0"] - covered(
        [(s, e) for s, e in mine if e > s]))


def median(values: List[float]) -> float:
    v = sorted(values)
    mid = len(v) // 2
    return v[mid] if len(v) % 2 else 0.5 * (v[mid - 1] + v[mid])


def stat_of(tree: dict, spans: Sequence[str], stat: str):
    named = [r for r in tree["rows"] if r["name"] in spans]
    whole = tree["root"]["t1"] - tree["root"]["t0"]
    if stat in ("p50_ms", "mean_ms"):
        durs = [(r["t1"] - r["t0"]) * 1e3 for r in named]
        if not durs:
            return None
        return median(durs) if stat == "p50_ms" else sum(durs) / len(durs)
    if whole <= 0:
        return None
    if stat == "share_pct":
        return 100.0 * sum(r["t1"] - r["t0"] for r in named) / whole
    if stat == "self_share_pct":
        return 100.0 * sum(self_s(tree, r) for r in named) / whole
    raise ValueError(f"unknown stat {stat!r}")


def say(tree: dict) -> None:
    """The whole-window table on a line of its own: by role and name, how
    many, their summed duration and self time (s) and median (ms)."""
    by: Dict[Tuple[str, str], List[float]] = defaultdict(list)
    selfs: Dict[Tuple[str, str], float] = defaultdict(float)
    for r in tree["rows"]:
        by[(r["role"], r["name"])].append(r["t1"] - r["t0"])
        selfs[(r["role"], r["name"])] += self_s(tree, r)
    parts = [f"{role}:{name}=n{len(d)},sum{sum(d):.4f},self{selfs[role, name]:.4f},"
             f"p50ms{median(d) * 1e3:.3f}"
             for (role, name), d in sorted(by.items())]
    print("[tracer_spans] " + " ".join(parts), flush=True)
