"""Record one traced run's host timeline as JSON: the Tracer's ring and
what ``benchmark/readers/loop_idle.py`` reduces the profiler's capture to.

    chiprun -- python tools/record_host_timeline.py \\
        --workload forest.saturate --seed 3700000001 --out chiprun_out/x.json

The run is the benchmark's own (``benchmark/harness.run_cell`` with
``--trace 1``): its result line is printed, then the file is written —
``{"recorded", "window_s", "ring": [rows], "capture": {busy, modules,
annotations}}`` — from which ``tests/data/host_timeline_*.json`` were cut
(``--cut SECONDS`` keeps the ring rows and the capture of the trace's
first SECONDS, and the tree's root). Needs the chip, like the benchmark.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def cut(ring: list, capture: dict, seconds: float, offset: float) -> tuple:
    """The capture's first ``seconds`` — its busy intervals merged across
    pauses under 0.1 ms, a tenth of the readers' floor, and times rounded
    to the nanosecond — and the ring rows that overlap them, with the
    ``run`` roots."""
    t0 = capture["busy"][0][0]
    t1 = t0 + seconds
    busy = []
    for s, e in capture["busy"]:
        if s >= t1:
            break
        if busy and s - busy[-1][1] < 1e-4:
            busy[-1][1] = round(e, 9)
        else:
            busy.append([round(s, 9), round(e, 9)])
    capture = {
        "busy": busy,
        "modules": [m for m in capture["modules"] if m[1] < t1],
        "annotations": [a for a in capture["annotations"] if a[2] < t1],
    }
    ring = [dict(r, t0=round(r["t0"], 9), t1=round(r["t1"], 9))
            for r in ring if r["name"] == "run" or (
                r["t1"] + offset >= t0 - 0.2 and r["t0"] + offset <= t1 + 0.2)]
    return ring, capture


def main(argv=None) -> int:
    from benchmark import harness
    from benchmark.readers import device_scopes, loop_idle, tracer_spans

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cut", type=float, default=0.0)
    args = ap.parse_args(argv)
    trace_dir = tempfile.mkdtemp(prefix="rtfds-trace-")
    sys.argv += ["--trace-dir", trace_dir]  # where the readers look
    result = harness.run_cell(args.workload, args.seed, args.seconds, True,
                              T_START, trace_dir=trace_dir)
    print(json.dumps(result), flush=True)
    ring = tracer_spans.ring()
    capture = loop_idle.load_capture(device_scopes.find_trace())
    tree = tracer_spans.last_run(ring)
    offset, scatter = loop_idle.clock_offset(tree, capture["annotations"])
    if args.cut:
        ring, capture = cut(ring, capture, args.cut, offset)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"recorded": f"{args.workload}, seed {args.seed}, "
                               f"{result['device']['kind']}",
                   "window_s": result["device"].get("window_s"),
                   "offset_s": offset, "scatter": scatter,
                   "ring": ring, "capture": capture}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
