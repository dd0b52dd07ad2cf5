"""One run of one cell: set-up → history fill → window → drain → check.

Driven by data. ``BENCHMARK.json`` names the cell's configuration and
traffic mix; their files, the model builder, the traffic generator, the
per-layer metric files and their readers are found by name under the
benchmark's ``paths``. Nothing here knows a cell, a model or a metric by
name, so a later PR adds any of them as new files plus new entries.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402
from benchmark.readers import device_trace  # noqa: E402
from benchmark.readers import registry as registry_reader  # noqa: E402
from benchmark.stats import decision_latency_ms, percentile  # noqa: E402
TRACE_SECONDS = 3.0


class HarnessError(RuntimeError):
    """The run cannot be made (no chip, a name that resolves to no file)."""


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


# -- the manifest and the files it names -------------------------------------


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(root: str, manifest: dict, kind: str, name: str, ext: str) -> str:
    """``<path>/<kind>/<name><ext>`` under the first of ``paths`` that has
    it."""
    for p in manifest["paths"]:
        cand = os.path.join(root, p, kind, name + ext)
        if os.path.isfile(cand):
            return cand
    raise HarnessError(f"no {kind}/{name}{ext} under {manifest['paths']}")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "_bench_" + os.path.relpath(path, ROOT).replace(os.sep, "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base: dict, over: Optional[dict]) -> dict:
    """``base`` with ``over``'s keys laid over it, nested dicts merged."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


class Cell:
    """A cell of ``BENCHMARK.json`` resolved to its files."""

    def __init__(self, root: str, manifest: dict, workload: str,
                 overrides: Optional[dict] = None):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if workload not in cells:
            raise HarnessError(f"no workload {workload!r} in BENCHMARK.json")
        self.root, self.manifest = root, manifest
        self.entry = cells[workload]
        self.name, self.chips = workload, int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in manifest["configs"]}[
            self.entry["config"]]
        overrides = overrides or {}
        self.config = merge(load_json(os.path.join(root, cfg_entry["file"])),
                            overrides.get("config"))
        self.traffic = merge(load_json(find(
            root, manifest, "traffic", self.entry["traffic"], ".json")),
            overrides.get("traffic"))
        self.regime = self.traffic["regime"]

    def reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> List[dict]:
        return [m for m in self.manifest["end_to_end"] if self.reports(m)]

    def per_layer(self) -> List[dict]:
        """Per-layer metrics of this cell: the manifest's entries whose
        metric file carries the traffic's regime (and, where an entry
        lists ``workloads``, that name this cell)."""
        out = []
        for m in self.manifest["per_layer"]:
            spec = load_json(find(self.root, self.manifest, "metrics",
                                  m["name"], ".json"))
            if spec["regime"] == self.regime and self.reports(m):
                out.append(dict(m, reader=spec["reader"],
                                args=spec.get("args", {})))
        return out

    def plugin(self, kind: str, name: str):
        return load_module(find(self.root, self.manifest, kind, name, ".py"))


# -- the device --------------------------------------------------------------


def compile_cache_dir(root: str) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else a fixed path inside the
    checkout (the path is part of the cache's key)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, "benchmark", ".cache", "xla")


def claim_device(root: str, chips: int, allow_cpu: bool):
    """→ the devices used. Fails unless JAX sees a TPU with at least
    ``chips`` chips whose kind is in ``peaks.json``."""
    t0 = time.perf_counter()
    import jax

    t1 = time.perf_counter()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          compile_cache_dir(root))
    # no floor: the set-up's many one-op eager programs (the GEMM form of
    # the forest, the state's zeros) are then read back too, not compiled
    # again in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    say("device", import_jax_s=round(t1 - t0, 2),
        claim_s=round(time.perf_counter() - t1, 2))
    if allow_cpu:  # the rehearsal and the benchmark's own tests
        if devs[0].platform != "cpu":
            raise HarnessError("--rehearse-cpu is for JAX_PLATFORMS=cpu only")
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise HarnessError(
            f"no accelerator: jax sees platform {devs[0].platform!r}; the "
            "benchmark never measures on the CPU")
    if len(devs) < chips:
        raise HarnessError(f"the cell needs {chips} chips, jax sees "
                           f"{len(devs)}")
    peaks = load_json(os.path.join(root, "benchmark", "peaks.json"))["peaks"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise HarnessError(f"no peaks on record for device_kind {kind!r}: "
                           "add it to benchmark/peaks.json with its source")
    return devs[:chips]


# -- the system under test ---------------------------------------------------


def build_engine(cell: Cell, model: dict, registry):
    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.runtime import (
        ScoringEngine,
        ShardedScoringEngine,
    )

    feats = dict(cell.config["features"])
    feats["windows"] = tuple(feats["windows"])
    runtime = dict(cell.config["runtime"])
    if "batch_buckets" in runtime:
        runtime["batch_buckets"] = tuple(runtime["batch_buckets"])
    if model.get("z_mode"):
        runtime["z_mode"] = model["z_mode"]
    cfg = Config(features=FeatureConfig(**feats),
                 runtime=RuntimeConfig(**runtime))
    if cell.chips > 1:
        return ShardedScoringEngine(
            cfg, kind=model["kind"], params=model["params"],
            scaler=model["scaler"], n_devices=cell.chips, metrics=registry)
    return ScoringEngine(cfg, kind=model["kind"], params=model["params"],
                         scaler=model["scaler"], metrics=registry)


def compile_telemetry() -> tuple:
    """(count, seconds) of XLA backend compiles so far in this process: a
    persistent-cache hit is not one."""
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        get_registry,
    )

    h = get_registry().get("rtfds_xla_compile_seconds")
    return (0, 0.0) if h is None else (int(h.count), float(h.sum))


class StampingSink:
    """The program's Parquet sink, with the clock read as each ``append``
    returns: the moment a batch's decisions are acknowledged."""

    def __init__(self, inner):
        self.inner = inner
        self.done: List[tuple] = []  # (t, batch_index, tx_id)
        self.write_s: List[float] = []

    def append(self, res) -> None:
        t0 = time.perf_counter()
        self.inner.append(res)
        t1 = time.perf_counter()
        self.done.append((t1, int(res.batch_index), res.tx_id))
        self.write_s.append(t1 - t0)


# -- the check ---------------------------------------------------------------


def part_index(path: str) -> int:
    return int(os.path.basename(path)[len("part-"):-len(".parquet")])


def read_sink(out_dir: str) -> Dict[int, np.ndarray]:
    """batch_index → the tx_ids in that part file."""
    import pyarrow.parquet as pq

    return {part_index(f): pq.read_table(f, columns=["tx_id"])["tx_id"]
            .to_numpy() for f in glob.glob(os.path.join(out_dir,
                                                        "part-*.parquet"))}


def read_parts(out_dir: str, batches: List[int]) -> Dict[int, dict]:
    import pyarrow.parquet as pq

    out = {}
    for b in batches:
        t = pq.read_table(os.path.join(out_dir, f"part-{b:08d}.parquet"))
        out[b] = {c: t[c].to_numpy() for c in t.column_names}
    return out


def sample_batches(batch_ids: Dict[int, np.ndarray], n_fill_batches: int,
                   traffic_cfg: dict, seed: int) -> List[int]:
    """The batches compared in full: of the history fill the last and
    seeded others; of the window the first, the last and seeded others, up
    to ``check_window_rows`` rows."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5A3D]))
    order = sorted(batch_ids)
    fill = [b for b in order if b <= n_fill_batches]
    win = [b for b in order if b > n_fill_batches]
    chosen = fill[-1:]
    rest = fill[:-1]
    k = min(int(traffic_cfg["check_fill_batches"]) - 1, len(rest))
    chosen += [int(b) for b in rng.choice(rest, size=k, replace=False)]
    if win:
        budget = int(traffic_cfg["check_window_rows"])
        picks = [win[0]] + ([win[-1]] if len(win) > 1 else [])
        middle = list(win[1:-1])
        rng.shuffle(middle)
        rows = sum(len(batch_ids[b]) for b in picks)
        for b in middle:
            if rows + len(batch_ids[b]) > budget:
                break
            picks.append(int(b))
            rows += len(batch_ids[b])
        chosen += picks
    return sorted(chosen)


def sink_numbers(batch_ids: Dict[int, np.ndarray], rows_acked: int,
                 batches_acked: int, limits: dict) -> List[dict]:
    number = reference.number
    ids = np.concatenate([batch_ids[b] for b in sorted(batch_ids)]) \
        if batch_ids else np.empty(0, np.int64)
    idx = sorted(batch_ids)
    gaps = (idx[-1] - idx[0] + 1 - len(idx)) if idx else 0
    gaps += abs(len(idx) - batches_acked)
    return [
        number("sink_rows_off", abs(len(ids) - rows_acked), limits),
        number("sink_duplicate_tx_ids", len(ids) - len(np.unique(ids)),
               limits),
        number("sink_part_gaps", gaps, limits),
    ]


def steadiness_of(ack_t: List[float], write_s: List[float],
                  poll_s: List[float]) -> dict:
    """Where a stall would show, in milliseconds: the waits between the
    window's acknowledgements (median, 95th percentile, longest and its
    place, how many exceed 1.5 medians), its sink writes and its polls.
    Empty under two acknowledgements."""
    if len(ack_t) < 2:
        return {}
    gaps = np.diff(ack_t) * 1e3
    p50 = percentile(gaps, 50)
    out = {"ack_gap_p50_ms": p50,
           "ack_gap_p95_ms": percentile(gaps, 95),
           "ack_gap_max_ms": float(gaps.max()),
           "ack_gap_max_at": int(gaps.argmax()) + 1,
           "ack_gaps_over_1p5_p50": int((gaps > 1.5 * p50).sum())}
    for name, secs in (("sink_write", write_s), ("poll", poll_s)):
        ms = np.asarray(secs) * 1e3
        out.update({f"{name}_p50_ms": percentile(ms, 50),
                    f"{name}_p95_ms": percentile(ms, 95),
                    f"{name}_max_ms": float(ms.max())})
    return out


# -- one run -----------------------------------------------------------------


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: str = ROOT, allow_cpu: bool = False,
             overrides: Optional[dict] = None, trace_dir: str = "",
             control: bool = False,
             sabotage: Optional[Callable] = None,
             oracle=None) -> dict:
    """→ the result object of the contract's last line (and, under
    ``checks``, the numbers compared).

    ``allow_cpu`` skips the look for a chip (the rehearsal, the tests).
    ``control`` also puts the lower-precision reference in the program's
    place after the check and reports what the comparison says of it under
    ``control``. ``sabotage(engine, sink)`` is for the benchmark's own
    tests: it breaks the timed path underneath before the fill starts.
    ``oracle`` (a class with ``WindowReference``'s interface; the builder's
    ``--oracle 1`` passes the tests' dense one) repeats the comparison with
    that class in the reference's place and reports, under ``oracle``,
    whether every number came out the same."""
    manifest = load_manifest(root)
    cell = Cell(root, manifest, workload, overrides)
    devices = claim_device(root, cell.chips, allow_cpu)
    import jax

    from real_time_fraud_detection_system_tpu.core.envelope import (
        decode_transaction_envelopes_fast,
    )
    from real_time_fraud_detection_system_tpu.io.sink import ParquetSink
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )
    from real_time_fraud_detection_system_tpu.utils.trace import get_tracer

    say("device", platform=devices[0].platform,
        kind=repr(devices[0].device_kind), used=len(devices),
        bytes_limit=(devices[0].memory_stats() or {}).get("bytes_limit"),
        compile_cache=jax.config.jax_compilation_cache_dir,
        imports_s=round(time.perf_counter() - t_start, 2))

    t0 = time.perf_counter()
    model = cell.plugin("models", cell.config["model"]).build(
        cell.config, seed)
    t1 = time.perf_counter()
    traffic = cell.plugin("generators", cell.traffic["generator"]).build(
        cell.traffic, cell.config, seed, seconds,
        decode_transaction_envelopes_fast)
    t2 = time.perf_counter()
    registry = MetricsRegistry()
    engine = build_engine(cell, model, registry)
    t2b = time.perf_counter()
    out_dir = tempfile.mkdtemp(prefix="rtfds-bench-")
    own_trace_dir = ""
    try:
        sink = StampingSink(ParquetSink(out_dir))
        if sabotage is not None:
            sabotage(engine, sink)
        c0 = compile_telemetry()
        engine.precompile()
        t3 = time.perf_counter()
        c1 = compile_telemetry()
        fill_stats = engine.run(traffic.fill_source(), sink)
        t4 = time.perf_counter()
        say("setup", model_s=round(t1 - t0, 2), traffic_s=round(t2 - t1, 2),
            engine_s=round(t2b - t2, 2), precompile_s=round(t3 - t2b, 2),
            backend_compiles=c1[0] - c0[0],
            backend_compile_s=round(c1[1] - c0[1], 2),
            fill_s=round(t4 - t3, 2), fill_rows=fill_stats["rows"],
            envelope_bytes=round(traffic.envelope_bytes, 1))

        # -- the window ------------------------------------------------
        timers, traced = [], {}
        if trace:
            own_trace_dir = trace_dir or tempfile.mkdtemp(
                prefix="rtfds-trace-")
            get_tracer().configure(enabled=True)
            length = min(TRACE_SECONDS, 0.5 * seconds)

            def start() -> None:
                # the Python tracer would log every call of the loop
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(own_trace_dir,
                                         profiler_options=options)
                traced["t0"] = time.perf_counter()

            def stop() -> None:
                traced["t1"] = time.perf_counter()
                jax.profiler.stop_trace()

            timers = [(seconds - length, start), (seconds, stop)]
        window = traffic.window_source(timers)
        n_fill_done = len(sink.done)
        before = registry.snapshot()
        run_stats = engine.run(window, sink)
        after = registry.snapshot()
        t_open = window.t_open
        setup_s = t_open - t_start
        t_close = t_open + seconds
        if trace and "t1" not in traced:
            raise RuntimeError("the profiler was never stopped")
        mem = [d.memory_stats() or {} for d in devices]
        peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)

        # -- what the window produced ------------------------------------
        done = sink.done[n_fill_done:]
        acks = [(t, len(ids)) for t, _, ids in done if t <= t_close]
        rows_in_window = sum(n for _, n in acks)
        # Between the first and the last acknowledgement inside the window:
        # whole batches over the time they took. (Rows acknowledged in the
        # window over its seconds moves in steps of one batch, 1.4 % at 70
        # batches a window; it is printed on the [window] line.)
        rows_per_s = float("nan")
        if len(acks) >= 2 and acks[-1][0] > acks[0][0]:
            rows_per_s = sum(n for _, n in acks[1:]) / (
                acks[-1][0] - acks[0][0])
        attempted = traffic.rows_due()
        if done:
            ids = np.concatenate([ids for _, _, ids in done]) - traffic.n_fill
            t_done = np.repeat([t for t, _, _ in done],
                               [len(i) for _, _, i in done]) - t_open
            due = ids < attempted
            delivered = len(np.unique(ids[due]))
            lat = decision_latency_ms(t_done[due], traffic.due_rel_s(ids[due]))
            p50, p95 = percentile(lat, 50), percentile(lat, 95)
        else:
            delivered, p50, p95 = 0, float("nan"), float("nan")
        failed = attempted - delivered
        values = {
            "rows_per_s": rows_per_s,
            "decision_p50_ms": p50,
            "decision_p95_ms": p95,
            "setup_s": setup_s,
        }
        queue_stats = traffic.queue_stats()
        draw = traffic.draw_stats()
        say("window", seconds=seconds, batches=run_stats["batches"],
            rows=run_stats["rows"], rows_polled=draw["rows_polled"],
            draw_rows=draw["draw_rows"], rows_acked_in_window=rows_in_window,
            acks_in_window=len(acks),
            rows_in_window_per_s=rows_in_window / seconds,
            rows_per_s=rows_per_s, decision_p50_ms=p50,
            decision_p95_ms=p95, attempted=attempted, failed=failed,
            drain_s=round(time.perf_counter() - t_close, 3),
            queue=json.dumps(queue_stats))
        say("run_stats", **{k: v for k, v in run_stats.items()})
        steadiness = steadiness_of([t for t, _ in acks],
                                   sink.write_s[n_fill_done:], window.poll_s)
        if steadiness:
            say("steadiness", **steadiness)

        # -- correct -----------------------------------------------------
        t5 = time.perf_counter()
        limits = cell.config["limits"]
        batch_ids = read_sink(out_dir)
        numbers = sink_numbers(
            batch_ids, fill_stats["rows"] + run_stats["rows"],
            fill_stats["batches"] + run_stats["batches"], limits)
        numbers.append(reference.number("rows_not_delivered", failed, limits))
        recompiles = registry_reader.read(
            {"registry_before": before, "registry_after": after},
            ["rtfds_xla_recompiles_total", "rtfds_aot_fallbacks_total"],
            "delta")
        numbers.append(reference.number("recompiles_in_window", recompiles,
                                        limits))
        # a traffic file that states limits of its own (the draw rule's
        # draw_wraps) is held to them by what its generator counted
        for name in cell.traffic.get("limits", {}):
            numbers.append(reference.number(name, draw[name],
                                            cell.traffic["limits"]))
        sample = sample_batches(batch_ids, fill_stats["batches"],
                                cell.traffic, seed)
        parts = read_parts(out_dir, sample)
        t5b = time.perf_counter()
        compared = reference.check_rows(
            parts, batch_ids, sample, traffic, cell.config,
            model["reference_proba"])
        numbers += compared
        for n in numbers:
            say("check", **n)
        correct = all(n["ok"] for n in numbers)
        flagged = sum(int((p["prediction"] >= 0.5).sum())
                      for p in parts.values())
        say("check", correct=correct, batches_compared=len(sample),
            flagged=flagged, check_s=round(time.perf_counter() - t5, 2),
            sink_read_s=round(t5b - t5, 2),
            reference_s=round(time.perf_counter() - t5b, 2))
        controlled = None
        if control:
            controlled = reference.check_rows(
                {}, batch_ids, sample, traffic, cell.config,
                model["reference_proba"], lower_precision=True)
            for n in controlled:
                say("control", **n)
        by_oracle = None
        if oracle is not None:
            t6 = time.perf_counter()
            by_oracle = {"checks": reference.check_rows(
                parts, batch_ids, sample, traffic, cell.config,
                model["reference_proba"], window_reference=oracle)}
            same = by_oracle["checks"] == compared
            if control:
                by_oracle["control"] = reference.check_rows(
                    {}, batch_ids, sample, traffic, cell.config,
                    model["reference_proba"], lower_precision=True,
                    window_reference=oracle)
                same = same and by_oracle["control"] == controlled
            by_oracle["equal"] = bool(same)
            say("oracle", equal=same, numbers=json.dumps(by_oracle),
                oracle_s=round(time.perf_counter() - t6, 2))

        # -- the line ----------------------------------------------------
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": int(peak)}
        result = {"correct": bool(correct), "attempted": int(attempted),
                  "failed": int(failed)}
        if not trace:
            result["metrics"] = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end()}
        else:
            result.update(traced_metrics(
                cell, own_trace_dir, traced, done, device, {
                    "run_stats": run_stats, "registry_before": before,
                    "registry_after": after,
                    "queue_stats": queue_stats, "steadiness": steadiness,
                    "device_memory": {"peak_bytes_in_use": peak}}))
        result["device"] = device
        result["checks"] = numbers
        if controlled is not None:
            result["control"] = controlled
        if by_oracle is not None:
            result["oracle"] = by_oracle
        return result
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
        if own_trace_dir and not trace_dir:
            shutil.rmtree(own_trace_dir, ignore_errors=True)


def traced_metrics(cell: Cell, trace_dir: str, traced: dict, done: list,
                   device: dict, ctx: dict) -> dict:
    """The per-layer metrics of a traced run, and its breakdown."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    window_s = traced["t1"] - traced["t0"]
    batches = sum(1 for t, _, _ in done if traced["t0"] <= t <= traced["t1"])
    summary = device_trace.summarize(
        device_trace.load_xplane(max(files, key=os.path.getmtime)),
        window_s, batches)
    if summary is None:
        raise RuntimeError("no operation ran on the device in the trace")
    if not summary["steps"]:
        raise RuntimeError("the trace holds no whole step of the program")
    ctx["trace_summary"] = summary
    metrics = {}
    for m in cell.per_layer():
        value = cell.plugin("readers", m["reader"]).read(ctx, **m["args"])
        if value is not None:  # nothing to read: left out of the line
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device["busy_s"] = summary["busy_s"]
    device["window_s"] = summary["window_s"]
    say("trace", acks_in_trace=batches, steps_in_trace=summary["steps"],
        step_span_s=summary["span_s"], window_s=window_s,
        busy_s=summary["busy_s"])
    return {"metrics": metrics,
            "breakdown": {"device_ops": summary["device_ops"],
                          "idle_gaps": summary["idle_gaps"]}}
