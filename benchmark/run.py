"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX. It fails (non-zero, no result
line) when JAX finds no TPU, fewer chips than the cell asks for, or a
``device_kind`` that ``benchmark/peaks.json`` does not hold; it never falls
back to the CPU. The last line of standard output is the one JSON object of
the contract; everything else goes on earlier lines, and the numbers
compared, each beside its limit, are also the last lines of standard error.
The process then leaves through ``os._exit`` (``leave``): nothing is left
to flush or join. What it skips is the interpreter's and the runtime's
shutdown in user space; the process still takes ~3 s on one chip and far
longer on four to be gone after its last line (the kernel releasing the
devices: PERF.md, PR 40).

``--rehearse-cpu`` (``JAX_PLATFORMS=cpu`` only) drives the same control
flow at toy sizes, prints ``"correct": false`` and exits 4: it proves the
script, never the system.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmark import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--trace-dir", default="",
                    help="keep the profiler's trace here (default: a "
                         "temporary directory, deleted)")
    ap.add_argument("--overrides", default="",
                    help="a JSON file {config: {...}, traffic: {...}} laid "
                         "over the cell's files: for the builder's sweeps "
                         "and controls, never for a run that is reported")
    ap.add_argument("--control", type=int, choices=[0, 1], default=0,
                    help="1: after the check, put the lower-precision "
                         "reference in the program's place and print what "
                         "the comparison says of it (the benchmark's own "
                         "runs never do)")
    ap.add_argument("--oracle", type=int, choices=[0, 1], default=0,
                    help="1: repeat the comparison with the tests' dense "
                         "[universe, days] reference in the sparse one's "
                         "place and print whether every number agrees "
                         "(the builder's; it needs the universe's worth "
                         "of host memory)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="JAX_PLATFORMS=cpu only: toy sizes, ends with "
                         "correct=false and exit code 4")
    args = ap.parse_args(argv)
    overrides = None
    if args.rehearse_cpu:
        overrides = harness.load_json(os.path.join(
            ROOT, "benchmark", "tests", "data", "toy_overrides.json"))
    elif args.overrides:
        overrides = harness.load_json(args.overrides)
        print(f"[overrides] {json.dumps(overrides)}", flush=True)
    oracle = None
    if args.oracle:
        from benchmark.tests.dense_reference import DenseWindowReference

        oracle = DenseWindowReference
    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            T_START, allow_cpu=args.rehearse_cpu, overrides=overrides,
            trace_dir=args.trace_dir, control=bool(args.control),
            oracle=oracle)
    except harness.HarnessError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    if args.rehearse_cpu:
        result["correct"] = False  # a CPU run proves the script only
    for n in result["checks"]:
        print(f"[check] {n['name']}={n['value']} limit={n['limit']} "
              f"ok={n['ok']}", file=sys.stderr)
    print(f"[check] correct={result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 4 if args.rehearse_cpu else 0


def leave(code: int) -> None:
    """End the process now, without the interpreter's and the runtime's
    shutdown. ``os._exit`` skips the exit handlers, so what the program
    registered through ``weakref.finalize`` (a ``tmp://`` cold store
    removes its directory under ``TMPDIR``) is run here first."""
    import weakref

    sys.stdout.flush()
    sys.stderr.flush()
    weakref.finalize._exitfunc()
    os._exit(code)


if __name__ == "__main__":
    code = 1
    try:
        code = main()
    except SystemExit as e:  # argparse's
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
    leave(code)
