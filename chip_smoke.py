"""chip_smoke.py — prove `rtfds score` runs end to end on the attached TPU.

    python chip_smoke.py            # one chip: device, main path, kernels
    python chip_smoke.py --chips 4  # ONLY the sharded engine on four chips
                                    # and the one-chip engine it must equal

One process, and the only one that touches JAX (a chip belongs to one
process at a time). Every phase prints its own lines and then asserts;
nothing is caught and carried past, so any failed phase ends the run with
a traceback and a non-zero exit code. On success the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

With no TPU the script exits non-zero before any phase runs; it never
scores on the CPU. The one exception is the rehearsal of the control flow,
``--rehearse-tiny-on-cpu`` under ``JAX_PLATFORMS=cpu``: toy sizes, Pallas
in interpret mode, ends with ``"ok": false`` and exit code 4 — it proves
the script, never the system.

All timings printed here are SMOKE timings (one cold run, compilation and
host set-up included) — information for the reader, not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# The deployment the main path is sized as: the reference generator's own
# scale (Makefile: 5,000 customers, 10,000 terminals), days cut from 245
# to 90 to bound host time (the 30-day windows fill, the 7-day label
# delay passes), a feature state of 2^20 + 2^21 slots (~1.9 GB on device),
# the default batch buckets up to 65,536 rows.
FULL = dict(customers=5000, terminals=10000, days=90,
            train=("50", "7", "20"), customer_slots=1 << 20,
            terminal_slots=1 << 21, batch_rows=65536, min_full_batches=8,
            z_mode="int8", min_state_bytes=1.8e9)
TINY = dict(customers=300, terminals=600, days=60,
            train=("30", "7", "15"), customer_slots=1 << 12,
            terminal_slots=1 << 13, batch_rows=2048, min_full_batches=2,
            z_mode="f32", min_state_bytes=0)


START_DATE = "2025-04-01"  # day 0 of the generated stream


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


@contextlib.contextmanager
def cli_state_scale(customer_slots: int, terminal_slots: int):
    """A GAP OF THE MAIN PATH, not a convenience: `rtfds score` sizes its
    feature state from ``Config()``'s defaults (8,192 + 16,384 slots,
    grown only as far as a replay's largest id needs) and has no capacity
    input, so a user of the console script cannot ask for the 2^20 + 2^21
    slots a processor's key space needs (PERF.md section 7, ROADMAP B8).
    Until it has one, the smoke reaches that scale by making the
    deployment's capacities the defaults for the duration of a
    ``cli.main`` call — the command, its argument parsing and everything
    below it run as the console script runs them."""
    from real_time_fraud_detection_system_tpu import config

    base = config.Config
    feats = dataclasses.replace(
        config.FeatureConfig(), customer_capacity=customer_slots,
        terminal_capacity=terminal_slots)

    @dataclasses.dataclass(frozen=True)
    class ConfigAtScale(base):
        features: config.FeatureConfig = feats

    config.Config = ConfigAtScale
    try:
        yield
    finally:
        config.Config = base


@contextlib.contextmanager
def engines_built():
    """Collect the engines ``cli.main`` builds, so the smoke can look at
    where their state lives after the command returns."""
    from real_time_fraud_detection_system_tpu.runtime.engine import (
        ScoringEngine,
    )

    built: list = []
    init = ScoringEngine.__init__

    def spy(self, *a, **kw):
        init(self, *a, **kw)
        built.append(self)

    ScoringEngine.__init__ = spy
    try:
        yield built
    finally:
        ScoringEngine.__init__ = init


def run_cli(argv: list) -> float:
    """One console-script command, in this process. → wall seconds."""
    from real_time_fraud_detection_system_tpu import cli

    t0 = time.perf_counter()
    rc = cli.main(argv)
    assert rc == 0, f"rtfds {argv[0]} exited {rc}"
    return time.perf_counter() - t0


def registry_value(name: str, **labels) -> float:
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        get_registry,
    )

    m = get_registry().get(name, **labels)
    return 0.0 if m is None else float(m.value)


def compile_seconds() -> tuple:
    """(count, seconds) of XLA backend compiles so far in this process —
    a persistent-cache hit is not a backend compile."""
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        get_registry,
    )

    h = get_registry().get("rtfds_xla_compile_seconds")
    return (0, 0.0) if h is None else (int(h.count), float(h.sum))


def state_leaves(engine) -> list:
    import jax

    return [x for x in jax.tree.leaves(engine.state.feature_state)
            if isinstance(x, jax.Array)]


def feature_parity(phase: str, what: str, f0: np.ndarray,
                   f1: np.ndarray) -> np.ndarray:
    """Two [N, 15] feature matrices from two different device programs,
    column by column. → the mask of rows whose 15 features are all
    bit-equal, and prints which columns are not.

    Every count, flag, the amount and the terminal risk are integer-valued
    sums and IEEE quotients: bit-identical or the phase fails. The three
    average-amount columns are f32 sums of dollar amounts. Across the 40
    day buckets their order is pinned (``ops/numerics.sum_fixed_order``),
    so the fused kernels and XLA agree to the bit. WITHIN a batch the
    scatter-add combines same-customer-same-day rows in an order the
    compiler chooses per program, so one chip and the mesh may differ in
    the last bits there; the caller bounds that."""
    from real_time_fraud_detection_system_tpu.features.spec import (
        FEATURE_NAMES,
    )

    differ = {FEATURE_NAMES[j]: int((f1[:, j] != f0[:, j]).sum())
              for j in range(f0.shape[1]) if (f1[:, j] != f0[:, j]).any()}
    avg = [j for j, name in enumerate(FEATURE_NAMES)
           if "AVG_AMOUNT" in name]
    exact = [j for j in range(f0.shape[1]) if j not in avg]
    rel = float((np.abs(f1[:, avg] - f0[:, avg]) / np.maximum(
        np.abs(f0[:, avg]), 1e-30)).max())
    same = (f1 == f0).all(axis=1)
    say(phase, parity=what, rows=len(f0),
        columns_not_bit_identical=differ or "none",
        rows_all_15_bit_equal=int(same.sum()),
        avg_amount_max_rel_delta=rel)
    assert np.array_equal(f1[:, exact], f0[:, exact]), (
        f"{what}: a count/flag/amount/risk column differs")
    # measured 3.06e-7 (under 3 ulps) on the chip, PR 21; 4 ulps is the pin
    assert rel <= 2.0 ** -21, f"{what}: average amounts beyond 4 ulps"
    return same


def analyzed_features(cols: dict) -> np.ndarray:
    """The 15 feature columns of an analyzed table, in FEATURE_NAMES
    order (the sink writes TX_AMOUNT as ``tx_amount``)."""
    from real_time_fraud_detection_system_tpu.features.spec import (
        FEATURE_NAMES,
    )

    # through f32, as the engine emitted them: the sink stores the amount
    # as exact cents / 100 in f64, every other column is f32 already
    return np.stack([np.asarray(cols[n.lower()], np.float32)
                     for n in FEATURE_NAMES], axis=1).astype(np.float64)


def reference_features(txs_path: str, size: dict) -> tuple:
    """The 15 features of the whole stream from an implementation that
    shares nothing with the device feature engine: NumPy daily aggregates
    per key, prefix sums over days, float64. → (tx_id ascending,
    features [N, 15] in that order, the batch sizes the source served).

    Semantics (``ops/windows.py``): window w at day d covers days
    [d-w+1, d], the terminal windows shifted back by the label delay; a
    row's windows include its batch-mates (update, then query); a key
    keeps ``n_day_buckets`` days in a ring, so day s is forgotten once day
    s + n_day_buckets arrives for that key — which a replay whose batch
    spans a week does reach: the delayed 30-day window looks 37 days back
    in a 40-day ring. Batch
    membership is therefore part of the answer, and it is the source's
    own — the envelope replay polls eight partitions round-robin — so the
    same ``ReplaySource`` `rtfds score` builds is polled again here, on
    the host only. `score` carries no labels, so every risk is 0."""
    from real_time_fraud_detection_system_tpu import cli
    from real_time_fraud_detection_system_tpu.config import FeatureConfig
    from real_time_fraud_detection_system_tpu.io.artifacts import (
        load_transactions,
    )
    from real_time_fraud_detection_system_tpu.runtime import ReplaySource

    fc = FeatureConfig()
    windows, delay = tuple(fc.windows), int(fc.delay_days)
    ring = int(fc.n_day_buckets)
    reach = max(windows) + delay
    src = ReplaySource(
        load_transactions(txs_path), cli._start_epoch_s(START_DATE),
        batch_rows=size["batch_rows"], mode="envelope")
    n_days = size["days"] + 2 * reach
    c_cnt = np.zeros((size["customers"], n_days))
    c_amt = np.zeros((size["customers"], n_days))
    t_cnt = np.zeros((size["terminals"], n_days))
    day0, ids, rows, sizes = None, [], [], []

    def window_sums(table, key, last_day):
        """[n, len(windows)]: table[key, last_day-w+1 .. last_day]."""
        pre = np.concatenate(
            [np.zeros((len(table), 1)), np.cumsum(table, axis=1)], axis=1)
        ok = last_day >= 0
        hi = np.where(ok, last_day, 0)
        return np.stack(
            [np.where(ok, pre[key, hi + 1]
                      - pre[key, np.maximum(hi - w + 1, 0)], 0.0)
             for w in windows], axis=1)

    while (cols := src.poll_batch()) is not None:
        us = cols["tx_datetime_us"]
        day, tod = us // 86_400_000_000, (us % 86_400_000_000) // 1_000_000
        if day0 is None:
            day0 = int(day.min()) - reach
        d = (day - day0).astype(np.int64)
        assert d.min() >= 0 and d.max() < n_days
        amount = (cols["tx_amount_cents"] / 100.0).astype(np.float32)
        c, t = cols["customer_id"], cols["terminal_id"]
        np.add.at(c_cnt, (c, d), 1.0)
        np.add.at(c_amt, (c, d), amount.astype(np.float64))
        np.add.at(t_cnt, (t, d), 1.0)
        for back in range(ring, n_days, ring):  # the ring forgets
            old = d >= back
            c_cnt[c[old], d[old] - back] = 0.0
            c_amt[c[old], d[old] - back] = 0.0
            t_cnt[t[old], d[old] - back] = 0.0
        cc, ca = window_sums(c_cnt, c, d), window_sums(c_amt, c, d)
        tc = window_sums(t_cnt, t, d - delay)
        f = [amount.astype(np.float64),
             ((day + 3) % 7 >= fc.weekend_start_weekday).astype(np.float64),
             (tod // 3600 <= fc.night_end_hour).astype(np.float64)]
        for i in range(len(windows)):
            f += [cc[:, i], ca[:, i] / np.maximum(cc[:, i], 1.0)]
        for i in range(len(windows)):
            f += [tc[:, i], np.zeros(len(d))]
        ids.append(cols["tx_id"])
        rows.append(np.stack(f, axis=1))
        sizes.append(len(d))
    ids, rows = np.concatenate(ids), np.concatenate(rows)
    order = np.argsort(ids, kind="stable")
    return ids[order], rows[order], sizes


def check_against_reference(got: dict, txs_path: str, size: dict) -> None:
    """The device feature engine at scale against the NumPy reference:
    counts, flags, amount and risk exactly; average amounts to the f32
    rounding of a 30-term sum."""
    from real_time_fraud_detection_system_tpu.features.spec import (
        FEATURE_NAMES,
    )

    t0 = time.perf_counter()
    ids, ref, sizes = reference_features(txs_path, size)
    assert np.array_equal(ids, got["tx_id"]), "reference rows != scored rows"
    full = sum(n == size["batch_rows"] for n in sizes)
    dev = analyzed_features(got)
    avg = [j for j, name in enumerate(FEATURE_NAMES)
           if "AVG_AMOUNT" in name]
    exact = [j for j in range(ref.shape[1]) if j not in avg]
    wrong = {FEATURE_NAMES[j]: int((dev[:, j] != ref[:, j]).sum())
             for j in exact if (dev[:, j] != ref[:, j]).any()}
    rel = float((np.abs(dev[:, avg] - ref[:, avg])
                 / np.maximum(np.abs(ref[:, avg]), 1e-30)).max())
    say("main", reference="NumPy daily aggregates (float64)",
        rows=len(ref), batches_served=len(sizes),
        batches_of_batch_rows=full, smallest_batch=min(sizes),
        exact_columns_wrong=wrong or "none",
        avg_amount_max_rel_delta=rel,
        max_customer_30d_count=int(ref[:, 7].max()),
        max_terminal_30d_count=int(ref[:, 13].max()),
        smoke_reference_s=round(time.perf_counter() - t0, 1))
    assert full >= size["min_full_batches"] and min(sizes) < max(sizes), (
        "the stream must serve full batches and a ragged tail", sizes)
    assert not wrong, f"device features differ from the reference: {wrong}"
    assert rel <= 5e-6, f"average amounts differ from the reference: {rel}"


# -- phases -----------------------------------------------------------------


def phase_device(rehearse: bool, chips: int):
    """Fail at once unless the first device is a TPU."""
    import jax
    import jaxlib

    from real_time_fraud_detection_system_tpu.utils import (
        enable_compilation_cache,
    )

    cache_dir = enable_compilation_cache()
    devs = jax.devices()
    dev = devs[0]
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devs), jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu_version, compile_cache=cache_dir,
        cache_entries_at_start=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0)
    if dev.platform != "tpu" and not rehearse:
        raise SystemExit(
            f"chip_smoke: no TPU (jax sees platform {dev.platform!r}); "
            "this script never runs its phases on the CPU")
    if rehearse and dev.platform != "cpu":
        raise SystemExit("chip_smoke: --rehearse-tiny-on-cpu is for "
                         "JAX_PLATFORMS=cpu only")
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, jax sees "
            f"{len(devs)}")
    return dev


def phase_native() -> str:
    """Rebuild both native units from source: the tool that runs this on
    the chip copies the tree as it is on disk, so a stale git-ignored
    ``.so`` could ride along. → the envelope decoder in use."""
    from real_time_fraud_detection_system_tpu.core import native

    for so in glob.glob(os.path.join(ROOT, "native", "lib*.so")):
        os.remove(so)
    t0 = time.perf_counter()
    decoder = "native" if native.native_available() else "python"
    hostprep = "native" if native.hostprep_available() else "numpy"
    say("native", envelope_decoder=decoder, hostprep=hostprep,
        gxx=shutil.which("g++") or "MISSING",
        build_s=round(time.perf_counter() - t0, 2))
    if decoder == "python" or hostprep == "numpy":
        # allowed only where there is no compiler, and never silently
        assert shutil.which("g++") is None, (
            "g++ is present but a native unit failed to build")
        say("native", WARNING="no g++ here: the pure-Python envelope "
            "decoder / NumPy host prep serve this run")
    return decoder


def make_artifacts(work: str, size: dict, seed: int) -> tuple:
    """`rtfds datagen` → `rtfds train --model forest` (T=100, depth 8,
    the 15 features: ModelConfig's defaults, the paper's model)."""
    txs, model = os.path.join(work, "txs.npz"), os.path.join(work, "m.npz")
    t_gen = run_cli(["datagen", "--out", txs,
                     "--customers", str(size["customers"]),
                     "--terminals", str(size["terminals"]),
                     "--days", str(size["days"]), "--seed", str(seed)])
    tr, dl, te = size["train"]
    t_train = run_cli(["train", "--data", txs, "--model", "forest",
                       "--out-model", model, "--delta-train", tr,
                       "--delta-delay", dl, "--delta-test", te])
    from real_time_fraud_detection_system_tpu.io.artifacts import (
        load_model,
        load_transactions,
    )

    m = load_model(model)
    n_rows = int(load_transactions(txs).n)
    assert m.kind == "forest"
    assert int(m.params.feat.shape[0]) == 100 and m.params.max_depth == 8
    say("artifacts", rows=n_rows, trees=int(m.params.feat.shape[0]),
        depth=m.params.max_depth, smoke_datagen_s=round(t_gen, 1),
        smoke_train_s=round(t_train, 1))
    return txs, model, n_rows


def score(work: str, tag: str, txs: str, model: str, size: dict,
          extra: list) -> tuple:
    """`rtfds score --mode envelope` with Parquet sink and checkpoint dir,
    state at the deployment's scale. → (analyzed columns sorted by tx_id,
    the engine, wall seconds)."""
    from real_time_fraud_detection_system_tpu.io.query import load_analyzed

    out = os.path.join(work, f"analyzed_{tag}")
    with cli_state_scale(size["customer_slots"], size["terminal_slots"]), \
            engines_built() as built:
        wall = run_cli(["score", "--data", txs, "--model-file", model,
                        "--mode", "envelope", "--out", out,
                        "--start-date", START_DATE,
                        "--checkpoint-dir", os.path.join(work, f"ck_{tag}"),
                        "--batch-rows", str(size["batch_rows"]),
                        "--precompile"] + extra)
    assert len(built) == 1, f"expected one engine, saw {len(built)}"
    cols = load_analyzed(out)
    order = np.argsort(cols["tx_id"], kind="stable")
    return {k: v[order] for k, v in cols.items()}, built[0], wall


def check_same_rows(a: dict, b: dict) -> None:
    assert np.array_equal(a["tx_id"], b["tx_id"]), "tx_id sets differ"


def phase_main_path(dev, work: str, size: dict, seed: int,
                    decoder: str) -> str:
    """→ the trained model's path (the kernels phase reuses it)."""
    import jax

    txs, model, n_rows = make_artifacts(work, size, seed)
    # enough rows for the full batches the smoke must stream and a tail
    # (what the source really served is counted in check_against_reference)
    assert n_rows > (size["min_full_batches"] + 1) * size["batch_rows"]

    c0, s0 = compile_seconds()
    tpu, eng, wall = score(work, "tpu", txs, model, size,
                           ["--scorer", "tpu"])
    c1, s1 = compile_seconds()
    recompiles = registry_value("rtfds_xla_recompiles_total")
    aot_fallbacks = registry_value("rtfds_aot_fallbacks_total")
    leaves = state_leaves(eng)
    state_bytes = sum(x.nbytes for x in leaves)
    on_dev = all(x.devices() == {dev} for x in leaves) and all(
        x.devices() == {dev} for x in jax.tree.leaves(eng.state.params)
        if isinstance(x, jax.Array))
    # the served step's outputs: where the compiled program puts them
    out_devs = set()
    for compiled in eng._aot.values():
        for sh in jax.tree.leaves(compiled.output_shardings):
            out_devs |= set(sh.device_set)
    mem = dev.memory_stats() or {}
    p = tpu["prediction"]
    say("main", rows_in=n_rows, rows_out=len(p),
        batch_rows=size["batch_rows"],
        z_mode=eng.z_mode, envelope_decoder=decoder,
        customer_slots=size["customer_slots"],
        terminal_slots=size["terminal_slots"],
        state_bytes=state_bytes, state_on_device=on_dev,
        outputs_on=sorted(str(d) for d in out_devs),
        precompiled_buckets=sorted(k[2] for k in eng._aot),
        smoke_score_wall_s=round(wall, 1),
        smoke_compiles=c1 - c0, smoke_compile_s=round(s1 - s0, 2),
        recompiles_after_warmup=int(recompiles),
        aot_fallbacks=int(aot_fallbacks),
        peak_bytes_in_use=mem.get("peak_bytes_in_use", "n/a"))
    assert len(p) == n_rows, "rows out != rows in"
    assert np.isfinite(p).all() and (p >= 0).all() and (p <= 1).all()
    assert on_dev, "engine state or params are not on the device"
    assert out_devs == {dev}, f"step outputs land on {out_devs}"
    assert recompiles == 0, "the recompile detector fired after warm-up"
    assert aot_fallbacks == 0, "a dispatch fell back from the AOT table"
    assert sorted(k[2] for k in eng._aot) == sorted(
        eng.cfg.runtime.batch_buckets), "not every bucket was precompiled"
    assert eng.z_mode == size["z_mode"], "what z_mode=auto resolves to here"
    assert state_bytes >= size["min_state_bytes"], state_bytes
    del eng, leaves
    gc.collect()
    check_against_reference(tpu, txs, size)

    # the oracle: same stream, same device feature engine, classifier on
    # the host in pure NumPy tree descent (`--scorer cpu`)
    cpu, eng_cpu, wall_cpu = score(work, "cpu", txs, model, size,
                                   ["--scorer", "cpu"])
    del eng_cpu
    gc.collect()
    check_same_rows(tpu, cpu)
    q = cpu["prediction"]
    delta = float(np.abs(p - q).max())
    flips = int(((p >= 0.5) != (q >= 0.5)).sum())
    say("main", oracle="--scorer cpu", max_abs_delta=delta,
        decision_flips=flips, flagged=int((p >= 0.5).sum()),
        smoke_oracle_wall_s=round(wall_cpu, 1))
    assert delta <= 1e-5, f"device vs oracle probabilities differ by {delta}"
    assert flips == 0, f"{flips} decisions differ from the oracle at 0.5"
    return model


def kernel_pair(kind: str, params, scaler, size: dict,
                batches: list) -> None:
    """One kind's fused Pallas step against its XLA composition, both
    served by ScoringEngine on the same batches."""
    import jax

    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.runtime import ScoringEngine
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )

    rows = size["batch_rows"]
    want = {"forest": "fused_forest", "logreg": "fused_logreg"}[kind]
    got = {}
    for fused in (False, True):
        cfg = Config(
            features=FeatureConfig(
                customer_capacity=size["customer_slots"],
                terminal_capacity=size["terminal_slots"]),
            runtime=RuntimeConfig(batch_buckets=(rows,),
                                  max_batch_rows=rows, use_pallas=fused,
                                  precompile=True))
        reg = MetricsRegistry()
        eng = ScoringEngine(cfg, kind=kind, params=params, scaler=scaler,
                            metrics=reg)
        t0 = time.perf_counter()
        eng.precompile()
        (key, compiled), = eng._aot.items()
        has_kernel = "tpu_custom_call" in compiled.as_text()
        res = [eng.process_batch(b) for b in batches]
        got[fused] = (np.concatenate([r.probs for r in res]),
                      np.concatenate([r.features for r in res]))
        say("kernels", kind=kind, use_pallas=fused,
            served=eng._pallas_kernel, bucket=key[2],
            tpu_custom_call_in_step=has_kernel,
            gauge_use_pallas=reg.get("rtfds_use_pallas").value,
            aot_fallbacks=reg.get("rtfds_aot_fallbacks_total").value,
            smoke_wall_s=round(time.perf_counter() - t0, 1))
        assert reg.get("rtfds_aot_fallbacks_total").value == 0
        assert reg.get("rtfds_use_pallas").value == float(fused)
        assert eng._pallas_kernel == (want if fused else None)
        # off the chip (the rehearsal) Pallas interprets: no custom call
        assert has_kernel == (fused and jax.default_backend() == "tpu"), (
            "the compiled step does not hold what the engine says it "
            "serves")
        del eng, compiled
        gc.collect()
    (p0, f0), (p1, f1) = got[False], got[True]
    n_trees = int(params.feat.shape[0]) if kind == "forest" else 1
    d_prob = float(np.abs(p1 - p0).max())
    flips = int(((p1 >= 0.5) != (p0 >= 0.5)).sum())
    say("kernels", kind=kind, rows=len(p0), max_abs_delta_prob=d_prob,
        max_abs_delta_leaf_sum=d_prob * n_trees, decision_flips=flips)
    same = feature_parity("kernels", f"{kind}: fused vs XLA", f0, f1)
    assert same.all(), "fused and XLA features are not bit-identical"
    assert d_prob <= 1e-5 and flips == 0


def check_division(seed: int) -> None:
    """The root cause behind the oracle and kernel parity, asked directly:
    the chip's own f32 divide is not NumPy's, ``div_ieee`` is."""
    import jax
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.ops.numerics import div_ieee

    rng = np.random.default_rng(seed)
    a = (rng.normal(size=(1 << 16, 16)) * rng.choice(
        [1.0, 10.0, 100.0, 1000.0], size=(1 << 16, 16))).astype(np.float32)
    b = rng.uniform(0.3, 300.0, size=(16,)).astype(np.float32)
    ref = a / b
    # the divisor is a run-time argument, as the scaler's is in the step
    # (a constant one XLA may turn into a reciprocal multiply)
    plain = np.asarray(jax.jit(lambda x, y: x / y)(a, b))
    fixed = np.asarray(jax.jit(div_ieee)(jnp.asarray(a), jnp.asarray(b)))
    say("kernels", check="f32 division vs NumPy", quotients=ref.size,
        plain_divide_mismatches=int((plain != ref).sum()),
        div_ieee_mismatches=int((fixed != ref).sum()))
    assert np.array_equal(fixed, ref), "div_ieee is not NumPy's quotient"


def phase_kernels(model_path: str, size: dict, seed: int) -> None:
    from real_time_fraud_detection_system_tpu.io.artifacts import load_model
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )

    check_division(seed)
    m = load_model(model_path)
    rng = np.random.default_rng(seed)
    rows = size["batch_rows"]
    batches = []
    for b in range(3):  # three days, so window state carries across batches
        batches.append({
            "tx_id": np.arange(b * rows, (b + 1) * rows, dtype=np.int64),
            "tx_datetime_us": ((20200 + b) * 86400 + rng.integers(
                0, 86400, rows)).astype(np.int64) * 1_000_000,
            "customer_id": rng.integers(
                0, size["customers"], rows).astype(np.int64),
            "terminal_id": rng.integers(
                0, size["terminals"], rows).astype(np.int64),
            "tx_amount_cents": rng.integers(
                100, 50000, rows).astype(np.int64),
        })
        batches[-1]["kafka_ts_ms"] = batches[-1]["tx_datetime_us"] // 1000
    kernel_pair("forest", m.params, m.scaler, size, batches)
    kernel_pair("logreg", init_logreg(15, seed=seed), m.scaler, size,
                batches)


def phase_four_chips(work: str, size: dict, seed: int, chips: int) -> None:
    """ShardedScoringEngine on ``chips`` devices (`score --devices N`)
    against the one-chip engine: same model, same stream, collision-free
    capacities; and the state must really be spread over the devices.

    What is held, and why it is not plain equality of every row. The two
    engines are two XLA programs. Every reduction the repo writes has a
    pinned order (``sum_fixed_order``: the day buckets, the trees), so
    equal features give bit-equal probabilities, and that is asserted on
    every row whose 15 features are bit-equal. One order is still the
    compiler's: the f32 scatter-add that combines a customer's same-day
    rows WITHIN a batch, into dollars already in the bucket. There the
    average-amount columns may differ in the last bits (held to 4 ulps),
    and a row whose standardized average then lands on the other side of
    a split threshold moves by a leaf's worth: held to one tree vote, no
    decision flipped, and the sharded probabilities equal to the oracle
    (`--scorer cpu`'s classifier, NumPy tree descent) on the sharded
    engine's OWN features within 1e-5. Exact equality of every row needs
    integer-cent sums in the store (ROADMAP B9); on the CPU's virtual
    mesh the scatter order happens to agree and every row is bit-equal
    (tests, rehearsal)."""
    import jax

    from real_time_fraud_detection_system_tpu.io.artifacts import load_model

    txs, model, n_rows = make_artifacts(work, size, seed)
    one, eng1, wall1 = score(work, "one", txs, model, size,
                             ["--scorer", "tpu"])
    n_trees = int(eng1.state.params.sel.shape[0])
    del eng1
    gc.collect()
    many, eng, wall_n = score(work, "sharded", txs, model, size,
                              ["--scorer", "tpu", "--devices", str(chips)])
    devs = jax.devices()[:chips]
    per_dev = {d.id: 0 for d in devs}
    for x in state_leaves(eng):
        for sh in x.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use", "n/a")
              for d in devs}
    say("chips", engine=type(eng).__name__, devices=chips, rows=n_rows,
        state_bytes_per_device=per_dev, bytes_in_use_per_device=in_use,
        recompiles_after_warmup=int(
            registry_value("rtfds_xla_recompiles_total")),
        smoke_one_chip_wall_s=round(wall1, 1),
        smoke_sharded_wall_s=round(wall_n, 1))
    assert type(eng).__name__ == "ShardedScoringEngine"
    sizes = np.asarray(list(per_dev.values()), dtype=np.float64)
    assert len(sizes) == chips and sizes.min() > 0, per_dev
    assert sizes.max() <= 1.1 * sizes.min(), (
        f"state is not spread evenly over the devices: {per_dev}")
    assert registry_value("rtfds_xla_recompiles_total") == 0

    check_same_rows(one, many)
    assert len(many["prediction"]) == n_rows
    f_many = analyzed_features(many)
    same = feature_parity("chips", "sharded vs one chip",
                          analyzed_features(one), f_many)
    a, b = one["prediction"], many["prediction"]
    delta = np.abs(a - b)
    flips = int(((a >= 0.5) != (b >= 0.5)).sum())
    say("chips", parity="sharded vs one chip", rows_out=len(b),
        rows_not_bit_equal=int((a != b).sum()),
        not_bit_equal_though_features_equal=int((a != b)[same].sum()),
        max_abs_delta_prob=float(delta.max()),
        rows_delta_over_1e_6=int((delta > 1e-6).sum()),
        one_tree_vote=1.0 / n_trees, decision_flips=flips,
        flagged=int((a >= 0.5).sum()))
    assert np.array_equal(a[same], b[same]), (
        "equal features, unequal probabilities: a reduction has no "
        "pinned order")
    assert delta.max() <= 1.0 / n_trees + 1e-6, "beyond one tree vote"
    assert flips == 0, f"{flips} decisions differ at 0.5"

    oracle = load_model(model).predict_proba_np(f_many)
    d_or = float(np.abs(b - oracle).max())
    f_or = int(((b >= 0.5) != (oracle >= 0.5)).sum())
    say("chips", oracle="--scorer cpu's classifier on the sharded "
        "engine's own features", max_abs_delta=d_or, decision_flips=f_or)
    assert d_or <= 1e-5 and f_or == 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 = run ONLY the sharded engine on four chips "
                         "and the one-chip engine it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated data and random weights")
    ap.add_argument("--rehearse-tiny-on-cpu", action="store_true",
                    help="JAX_PLATFORMS=cpu only: run the control flow at "
                         "toy sizes; ends with ok=false and exit code 4")
    args = ap.parse_args()
    rehearse = args.rehearse_tiny_on_cpu
    size = TINY if rehearse else FULL
    t0 = time.perf_counter()

    dev = phase_device(rehearse, args.chips)
    decoder = phase_native()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips > 1:
            phase_four_chips(work, size, args.seed, args.chips)
        else:
            model = phase_main_path(dev, work, size, args.seed, decoder)
            phase_kernels(model, size, args.seed)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    say("done", smoke_total_wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({
        "ok": not rehearse,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": args.chips},
    }), flush=True)
    return 4 if rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
