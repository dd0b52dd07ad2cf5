"""Reduce a profiler trace kept with ``--trace-dir`` to the JSON the tests
under ``benchmark/tests/data`` are cut from.

    chiprun -- sh -c 'python benchmark/run.py --workload forest.saturate \\
        --seed 1 --seconds 20 --trace 1 --trace-dir out/t && \\
        python benchmark/tests/record_trace.py out/t chiprun_out/t.json.gz'
    python benchmark/tests/record_trace.py --cut chiprun_out/t.json.gz \\
        0.01 0.41 benchmark/tests/data/steps_forest_saturate.json "what it is"

The first form (on the machine that holds the trace) writes ``{"planes":
what device_trace.load_xplane gives, "scopes": {"ops", "modules"} as
device_scopes.load_lines gives them}`` with every operation's name cut to
what stands before its `` = ``. The second keeps what begins inside
``[first operation + FROM, first operation + TO)`` seconds of the first
device — cut as the profiler cuts: an execution in flight at FROM begins
there — as ``{"recorded", "host_planes", "scopes": {"ops", "modules"}}``:
the device's two lines once, on the picosecond clock, with every
operation's ``op_name``; ``trace_of`` gives ``device_trace.summarize`` its
view of them.
"""

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.readers import device_scopes, device_trace  # noqa: E402


def short(name: str) -> str:
    return name.split(" = ", 1)[0]


def reduce(trace_dir: str, out: str) -> None:
    path = device_scopes.find_trace_under(trace_dir)
    trace = device_trace.load_xplane(path)
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                ev[0] = short(ev[0])
    ops, modules = device_scopes.load_lines(path)
    for ev in ops:
        ev[0] = short(ev[0])
    with gzip.open(out, "wt") as f:
        json.dump({"planes": trace["planes"],
                   "scopes": {"ops": ops, "modules": modules}}, f)


def cut(src: str, start_s: float, end_s: float, out: str,
        recorded: str = "") -> None:
    with gzip.open(src, "rt") as f:
        full = json.load(f)
    first = next(p for p in full["planes"]
                 if device_trace.DEVICE_PLANE.match(p["name"]))
    op0 = min(ev[1] for line in first["lines"]
              if line["name"] == device_trace.OP_LINE
              for ev in line["events"])
    t0, t1 = op0 + int(start_s * 1e9), op0 + int(end_s * 1e9)
    host = []
    for plane in full["planes"]:
        if device_trace.DEVICE_PLANE.match(plane["name"]):
            continue  # the first device's two lines are under "scopes"
        lines = [dict(line, events=device_scopes.clip(line["events"],
                                                      [(t0, t1)]))
                 for line in plane["lines"]]
        lines = [line for line in lines if line["events"]]
        if lines:
            host.append({"name": plane["name"], "lines": lines})
    a, b = t0 * 1000, t1 * 1000
    scopes = {k: device_scopes.clip(v, [(a, b)])
              for k, v in full["scopes"].items()}
    with open(out, "w") as f:
        json.dump({"recorded": recorded, "host_planes": host,
                   "scopes": scopes}, f, separators=(",", ":"))


def trace_of(recording: dict) -> dict:
    """A cut recording → what ``device_trace.load_xplane`` gave for it:
    the host planes and the first device's module and op lines, in
    nanoseconds."""
    lines = [{"name": name, "events": [[e[0], e[1] // 1000, e[2] // 1000]
                                       for e in recording["scopes"][key]]}
             for name, key in ((device_trace.MODULE_LINE, "modules"),
                               (device_trace.OP_LINE, "ops"))]
    return {"planes": [{"name": "/device:TPU:0", "lines": lines}]
            + recording["host_planes"]}


if __name__ == "__main__":
    if sys.argv[1] == "--cut":
        cut(sys.argv[2], float(sys.argv[3]), float(sys.argv[4]), sys.argv[5],
            " ".join(sys.argv[6:]))
    else:
        reduce(sys.argv[1], sys.argv[2])
