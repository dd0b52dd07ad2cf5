"""CLI subcommands, artifact round-trips, utils."""

import json
import os

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.cli import main as cli_main


@pytest.fixture(scope="module")
def workdir(tmp_path_factory):
    return tmp_path_factory.mktemp("cli")


def test_cli_datagen_train_score_roundtrip(workdir, capsys):
    txs_path = str(workdir / "txs.npz")
    model_path = str(workdir / "model.npz")
    out_dir = str(workdir / "analyzed")

    assert cli_main([
        "datagen", "--out", txs_path, "--customers", "120", "--terminals",
        "240", "--days", "40",
    ]) == 0
    assert os.path.exists(txs_path)

    assert cli_main([
        "train", "--data", txs_path, "--model", "forest", "--out-model",
        model_path, "--delta-train", "20", "--delta-delay", "5",
        "--delta-test", "10", "--epochs", "2",
    ]) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    metrics = json.loads(out)
    assert metrics["auc_roc"] > 0.65

    assert cli_main([
        "score", "--data", txs_path, "--model-file", model_path,
        "--scorer", "tpu", "--out", out_dir, "--batch-rows", "2048",
    ]) == 0
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert stats["rows"] > 0
    files = os.listdir(out_dir)
    assert any(f.endswith(".parquet") for f in files)


def test_cli_cpu_scorer_matches_tpu(workdir, capsys):
    txs_path = str(workdir / "txs.npz")
    model_path = str(workdir / "model.npz")
    assert cli_main([
        "score", "--data", txs_path, "--model-file", model_path,
        "--scorer", "cpu", "--max-batches", "2", "--batch-rows", "1024",
        "--out", str(workdir / "cpu_out"),
    ]) == 0
    capsys.readouterr()


@pytest.mark.parametrize("key_mode,max_cust,want", [
    ("direct", 8191, 8192),     # fits the default: untouched
    ("direct", 8192, 16384),    # one id past it: the next power of two
    ("direct", 600_000, 1 << 20),
    ("hash", 600_000, 8192),    # hashed keys: capacity is a budget
])
def test_score_replay_capacities_cover_the_ids(key_mode, max_cust, want):
    """`rtfds score` on a replay sizes direct-mode tables to hold the
    data's largest id, so keys never share a slot in silence."""
    import dataclasses
    import logging
    import types

    from real_time_fraud_detection_system_tpu.cli import _cover_replay_ids
    from real_time_fraud_detection_system_tpu.config import FeatureConfig

    txs = types.SimpleNamespace(
        customer_id=np.array([3, max_cust], np.int64),
        terminal_id=np.array([7, 11], np.int64))
    f = _cover_replay_ids(
        dataclasses.replace(FeatureConfig(), key_mode=key_mode), txs,
        logging.getLogger("test"))
    assert f.customer_capacity == want
    assert f.terminal_capacity == FeatureConfig().terminal_capacity


def test_model_artifact_roundtrip_all_kinds(small_dataset, workdir):
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.config import Config, FeatureConfig, TrainConfig
    from real_time_fraud_detection_system_tpu.features import compute_features_replay
    from real_time_fraud_detection_system_tpu.io.artifacts import (
        load_model,
        save_model,
    )
    from real_time_fraud_detection_system_tpu.models import train_model

    _, _, _, txs = small_dataset
    cfg = Config(
        features=FeatureConfig(customer_capacity=256, terminal_capacity=512),
        train=TrainConfig(delta_train_days=20, delta_delay_days=5,
                          delta_test_days=10, epochs=1),
    )
    feats = compute_features_replay(txs, cfg.features)
    probe = feats[:256]
    for kind in ("logreg", "mlp", "tree", "forest"):
        model, _ = train_model(txs, cfg, features=feats, kind=kind)
        path = str(workdir / f"m_{kind}.npz")
        save_model(path, model)
        loaded = load_model(path)
        np.testing.assert_allclose(
            loaded.predict_proba(probe), model.predict_proba(probe), atol=1e-6
        )
        # numpy host path must agree with the jax path
        np.testing.assert_allclose(
            loaded.predict_proba_np(probe), model.predict_proba(probe),
            atol=1e-4,
        )


def test_transactions_artifact_roundtrip(small_dataset, workdir):
    from real_time_fraud_detection_system_tpu.io.artifacts import (
        load_transactions,
        save_transactions,
    )

    _, _, _, txs = small_dataset
    path = str(workdir / "txs_rt.npz")
    save_transactions(path, txs)
    back = load_transactions(path)
    assert np.array_equal(back.amount_cents, txs.amount_cents)
    assert np.array_equal(back.tx_fraud, txs.tx_fraud)


def test_warm_start_state_equals_streaming(small_dataset):
    """Bootstrap-from-history must equal having streamed from day 0."""
    import jax
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.config import FeatureConfig
    from real_time_fraud_detection_system_tpu.features.offline import (
        warm_start_state,
    )
    from real_time_fraud_detection_system_tpu.features.online import (
        init_feature_state,
        update_and_featurize,
    )
    from real_time_fraud_detection_system_tpu.core.batch import make_batch

    _, _, _, txs = small_dataset
    fcfg = FeatureConfig(customer_capacity=256, terminal_capacity=512)
    warm = warm_start_state(txs, fcfg, chunk=1024)

    state = init_feature_state(fcfg)
    step = jax.jit(lambda s, b: update_and_featurize(s, b, fcfg)[0])
    start_epoch_us = 1_743_465_600 * 1_000_000
    for s in range(0, txs.n, 1024):
        part = txs.slice(slice(s, min(s + 1024, txs.n)))
        batch = make_batch(
            customer_id=part.customer_id,
            terminal_id=part.terminal_id,
            tx_datetime_us=start_epoch_us + part.tx_time_seconds * 1_000_000,
            amount_cents=part.amount_cents,
            label=part.tx_fraud.astype(np.int32),
            pad_to=1024,
        )
        state = step(state, jax.tree.map(jnp.asarray, batch))
    np.testing.assert_allclose(
        np.asarray(warm.customer.count), np.asarray(state.customer.count)
    )
    np.testing.assert_allclose(
        np.asarray(warm.terminal.fraud), np.asarray(state.terminal.fraud)
    )


def test_latency_tracker():
    from real_time_fraud_detection_system_tpu.utils import LatencyTracker

    t = LatencyTracker(window=64)
    for i in range(100):
        t.record(0.001 * (i % 10 + 1), rows=10)
    snap = t.snapshot()
    assert snap["count"] == 100 and snap["rows"] == 1000
    assert 0 < snap["p50_ms"] <= snap["p99_ms"] <= snap["max_ms"] <= 10.01


def test_cli_compare(workdir, capsys):
    """`rtfds compare` — the reference's 5-classifier comparison
    (model_training.ipynb · cells 50-56) as one command: shared split,
    metrics + fit/predict timings per kind, one JSON line out."""
    txs_path = str(workdir / "txs_cmp.npz")
    plots_dir = str(workdir / "plots")
    assert cli_main([
        "datagen", "--out", txs_path, "--customers", "100", "--terminals",
        "200", "--days", "40",
    ]) == 0
    assert cli_main([
        "compare", "--data", txs_path, "--models", "logreg", "tree",
        "--epochs", "2", "--plots-dir", plots_dir,
    ]) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line)
    assert [m["model"] for m in out["models"]] == ["logreg", "tree"]
    for m in out["models"]:
        assert np.isfinite(m["auc_roc"]) and m["fit_seconds"] > 0
    # scaled split recorded; spans fit the 40-day table
    assert sum(out["split_days"]) <= 40
    assert {f"{k}.png" for k in ("logreg", "tree")} <= set(
        os.listdir(plots_dir)
    )


def test_cli_score_trace_dir(workdir, capsys):
    """`score --trace-dir` captures a jax.profiler trace of the serving
    run (SURVEY §5.1: tracing built into the step loop)."""
    txs_path = str(workdir / "txs.npz")      # from the roundtrip test
    model_path = str(workdir / "model.npz")
    trace_dir = str(workdir / "trace")
    assert cli_main([
        "score", "--data", txs_path, "--model-file", model_path,
        "--scorer", "tpu", "--batch-rows", "2048", "--max-batches", "1",
        "--trace-dir", trace_dir,
    ]) == 0
    capsys.readouterr()
    found = []
    for dirpath, _, files in os.walk(trace_dir):
        found += [f for f in files if f.endswith((".pb", ".json.gz"))]
    assert found, f"no trace artifacts under {trace_dir}"


def test_cli_select(workdir, capsys):
    """`rtfds select` — the reference's prequential grid search
    (shared_functions.py:774-872) as one command."""
    txs_path = str(workdir / "txs.npz")  # from the roundtrip test
    assert cli_main([
        "select", "--data", txs_path, "--model", "tree",
        "--grid", "tree_max_depth=2,4",
        "--start-valid", "15", "--start-test", "20",
        "--folds", "2", "--epochs", "2",
    ]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["grid"] == {"tree_max_depth": [2, 4]}
    s = out["metrics"]["auc_roc"]
    assert s["best_params"]["tree_max_depth"] in (2, 4)
    assert len(out["execution_times"]) == 2
    # malformed grid spec / unknown field: usage errors (exit 2), not
    # crashes — and rejected BEFORE the data load (nonexistent path).
    assert cli_main([
        "select", "--data", txs_path, "--grid", "oops",
        "--start-valid", "15", "--start-test", "20",
    ]) == 2
    assert cli_main([
        "select", "--data", "/nonexistent.npz",
        "--grid", "tree_maxdepth=2",
        "--start-valid", "15", "--start-test", "20",
    ]) == 2


def test_score_alerts_only_flag(tmp_path):
    """--alerts-only serves predictions with zero feature columns; the
    incompatible --scorer cpu combination fails fast."""
    import subprocess
    import sys

    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def cli(*a):
        return subprocess.run(
            [sys.executable, "-m",
             "real_time_fraud_detection_system_tpu.cli", *a],
            capture_output=True, text=True, cwd=repo, env=env)

    p = cli("datagen", "--out", str(tmp_path / "txs.npz"),
            "--customers", "60", "--terminals", "120", "--days", "25")
    assert p.returncode == 0, p.stderr[-500:]
    p = cli("train", "--data", str(tmp_path / "txs.npz"),
            "--out-model", str(tmp_path / "m.npz"), "--model", "logreg")
    assert p.returncode == 0, p.stderr[-500:]
    p = cli("score", "--data", str(tmp_path / "txs.npz"),
            "--model-file", str(tmp_path / "m.npz"),
            "--out", str(tmp_path / "analyzed"),
            "--alerts-only", "--pipeline-depth", "4",
            "--coalesce-rows", "2048")
    assert p.returncode == 0, p.stderr[-800:]
    from real_time_fraud_detection_system_tpu.io.query import load_analyzed

    cols = load_analyzed(str(tmp_path / "analyzed"))
    assert len(cols["prediction"]) > 0
    assert np.all(cols["customer_id_nb_tx_7day_window"] == 0)
    # incompatible combination fails fast with rc 2
    p = cli("score", "--data", str(tmp_path / "txs.npz"),
            "--model-file", str(tmp_path / "m.npz"),
            "--alerts-only", "--scorer", "cpu")
    assert p.returncode == 2


def test_score_emit_threshold_flag(tmp_path):
    """--emit-threshold P: predictions identical to full emission for
    every row, feature columns populated only for rows with prob >= P;
    incompatible combinations fail fast with rc 2."""
    import subprocess
    import sys

    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def cli(*a):
        return subprocess.run(
            [sys.executable, "-m",
             "real_time_fraud_detection_system_tpu.cli", *a],
            capture_output=True, text=True, cwd=repo, env=env)

    p = cli("datagen", "--out", str(tmp_path / "txs.npz"),
            "--customers", "60", "--terminals", "120", "--days", "25")
    assert p.returncode == 0, p.stderr[-500:]
    p = cli("train", "--data", str(tmp_path / "txs.npz"),
            "--out-model", str(tmp_path / "m.npz"), "--model", "logreg")
    assert p.returncode == 0, p.stderr[-500:]
    common = ("score", "--data", str(tmp_path / "txs.npz"),
              "--model-file", str(tmp_path / "m.npz"),
              "--pipeline-depth", "4", "--coalesce-rows", "2048")
    p = cli(*common, "--out", str(tmp_path / "full"))
    assert p.returncode == 0, p.stderr[-800:]

    from real_time_fraud_detection_system_tpu.io.query import load_analyzed

    full = load_analyzed(str(tmp_path / "full"))
    # calibrate on the served distribution (logreg probs are continuous,
    # so a quantile threshold flags a predictable fraction). 0.97 keeps
    # ~3% flagged — 2x under the default emit_cap_fraction (1/16), so no
    # batch overflows into the full-fetch fallback that would put real
    # features on clean rows
    thr = float(np.quantile(full["prediction"], 0.97))
    p = cli(*common, "--out", str(tmp_path / "sel"),
            "--emit-threshold", repr(thr))
    assert p.returncode == 0, p.stderr[-800:]

    sel = load_analyzed(str(tmp_path / "sel"))
    np.testing.assert_array_equal(sel["prediction"], full["prediction"])
    flagged = full["prediction"] >= thr
    assert flagged.any() and not flagged.all()
    feat = "customer_id_nb_tx_7day_window"
    np.testing.assert_array_equal(sel[feat][flagged], full[feat][flagged])
    assert np.all(sel[feat][~flagged] == 0)

    # incompatible combinations fail fast with rc 2 (in-process — the
    # validation runs before any device work, no subprocess needed)
    for extra in (("--alerts-only",), ("--emit-bf16",),
                  ("--scorer", "cpu"), ("--emit-threshold", "1.5")):
        args = list(common) + list(extra)
        if "--emit-threshold" not in extra:
            args += ["--emit-threshold", "0.5"]
        assert cli_main(args) == 2, extra


def test_import_model_from_reference_pickles(tmp_path):
    """rtfds import-model: the reference's pickled trained_model.pkl +
    scaler.pkl (sklearn RF + joblib StandardScaler,
    load_initial_data.py:269-287 / model_training.ipynb · cell 31)
    convert to the npz format and serve with identical probabilities."""
    import pickle
    import subprocess
    import sys

    import joblib
    import numpy as np
    from sklearn.ensemble import RandomForestClassifier
    from sklearn.preprocessing import StandardScaler

    rng = np.random.default_rng(3)
    x = rng.normal(size=(400, 15))
    y = (x[:, 0] + 0.3 * x[:, 4] > 0.5).astype(np.int32)
    sc = StandardScaler().fit(x)
    clf = RandomForestClassifier(n_estimators=8, max_depth=4,
                                 random_state=0).fit(sc.transform(x), y)
    pkl = tmp_path / "trained_model.pkl"
    spkl = tmp_path / "scaler.pkl"
    with open(pkl, "wb") as f:
        pickle.dump(clf, f)
    joblib.dump(sc, spkl)

    out = tmp_path / "model.npz"
    r = subprocess.run(
        [sys.executable, "-m", "real_time_fraud_detection_system_tpu.cli",
         "import-model", "--model-pkl", str(pkl),
         "--scaler-pkl", str(spkl), "--out-model", str(out)],
        capture_output=True, text=True, cwd="/root/repo",
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr
    import json
    assert json.loads(r.stdout.strip().splitlines()[-1])["kind"] == "forest"

    from real_time_fraud_detection_system_tpu.io.artifacts import load_model

    model = load_model(str(out))
    xq = rng.normal(size=(128, 15)).astype(np.float32)
    ours = model.predict_proba(xq.astype(np.float64))
    want = clf.predict_proba(sc.transform(xq.astype(np.float64)))[:, 1]
    np.testing.assert_allclose(ours, want, atol=1e-5)


def test_import_model_logreg(tmp_path):
    import pickle
    import subprocess
    import sys

    import numpy as np
    from sklearn.linear_model import LogisticRegression

    rng = np.random.default_rng(5)
    x = rng.normal(size=(300, 15))
    y = (x[:, 1] > 0).astype(np.int32)
    clf = LogisticRegression().fit(x, y)
    pkl = tmp_path / "m.pkl"
    with open(pkl, "wb") as f:
        pickle.dump(clf, f)
    out = tmp_path / "model.npz"
    r = subprocess.run(
        [sys.executable, "-m", "real_time_fraud_detection_system_tpu.cli",
         "import-model", "--model-pkl", str(pkl), "--out-model", str(out)],
        capture_output=True, text=True, cwd="/root/repo",
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr

    from real_time_fraud_detection_system_tpu.io.artifacts import load_model

    model = load_model(str(out))
    xq = rng.normal(size=(64, 15))
    np.testing.assert_allclose(
        model.predict_proba(xq), clf.predict_proba(xq)[:, 1], atol=1e-5)


def test_import_model_rejects_mismatched_artifacts(tmp_path):
    """Feature-count and multiclass mismatches must fail loudly (rc 2):
    tree gathers clamp out-of-range feature indices, so a silent import
    would serve wrong probabilities."""
    import pickle
    import subprocess
    import sys

    import numpy as np
    from sklearn.ensemble import RandomForestClassifier
    from sklearn.linear_model import LogisticRegression

    rng = np.random.default_rng(7)
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}

    def run_import(clf):
        pkl = tmp_path / "m.pkl"
        with open(pkl, "wb") as f:
            pickle.dump(clf, f)
        return subprocess.run(
            [sys.executable, "-m",
             "real_time_fraud_detection_system_tpu.cli", "import-model",
             "--model-pkl", str(pkl),
             "--out-model", str(tmp_path / "out.npz")],
            capture_output=True, text=True, cwd="/root/repo", env=env)

    # 20-feature forest vs the 15-feature serving vector
    x20 = rng.normal(size=(200, 20))
    y = (x20[:, 0] > 0).astype(np.int32)
    r = run_import(RandomForestClassifier(n_estimators=3, max_depth=3,
                                          random_state=0).fit(x20, y))
    assert r.returncode == 2 and "15" in r.stderr

    # 3-class logreg
    x = rng.normal(size=(300, 15))
    y3 = rng.integers(0, 3, 300)
    r = run_import(LogisticRegression(max_iter=200).fit(x, y3))
    assert r.returncode == 2 and "classes" in r.stderr


def test_import_model_from_s3_url(tmp_path, monkeypatch):
    """--model-pkl s3://... — the reference's actual artifact location
    (s3://commerce/trained_model.pkl) — via the make_store client
    injection (the test_store.py pattern)."""
    import pickle

    import numpy as np
    from sklearn.linear_model import LogisticRegression
    from test_store import FakeS3Client

    import real_time_fraud_detection_system_tpu.io.store as store_mod

    rng = np.random.default_rng(11)
    x = rng.normal(size=(200, 15))
    y = (x[:, 2] > 0).astype(np.int32)
    clf = LogisticRegression(max_iter=200).fit(x, y)
    fake = FakeS3Client()
    fake.objects[("commerce", "trained_model.pkl")] = pickle.dumps(clf)

    real_make = store_mod.make_store
    monkeypatch.setattr(
        store_mod, "make_store",
        lambda url, **kw: real_make(url, client=fake, **kw))

    out = tmp_path / "model.npz"
    import real_time_fraud_detection_system_tpu.cli as cli

    rc = cli.main(["import-model",
                   "--model-pkl", "s3://commerce/trained_model.pkl",
                   "--out-model", str(out)])
    assert rc == 0

    from real_time_fraud_detection_system_tpu.io.artifacts import load_model

    model = load_model(str(out))
    xq = rng.normal(size=(32, 15))
    np.testing.assert_allclose(
        model.predict_proba(xq), clf.predict_proba(xq)[:, 1], atol=1e-5)


def test_model_reloader_semantics(tmp_path, monkeypatch):
    """_make_model_reloader: first due interval always loads (a fresh
    per-incarnation reloader must re-apply the artifact after a
    checkpoint restore reverted weights), unchanged signatures gate
    subsequent polls, changed artifacts swap, kind mismatches refuse."""
    import logging

    import jax.numpy as jnp
    import numpy as np

    from real_time_fraud_detection_system_tpu.cli import _make_model_reloader
    from real_time_fraud_detection_system_tpu.io.artifacts import save_model
    from real_time_fraud_detection_system_tpu.models.logreg import (
        LogRegParams,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import TrainedModel

    log = logging.getLogger("t")
    path = str(tmp_path / "m.npz")

    def write(w0):
        save_model(path, TrainedModel(
            kind="logreg",
            scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
            params=LogRegParams(w=jnp.full(15, w0), b=jnp.zeros(()))))

    write(1.0)
    r = _make_model_reloader(path, "logreg", every_batches=2, log=log)
    assert r() is None           # off-interval
    got = r()                    # first due interval: ALWAYS loads
    assert got is not None
    np.testing.assert_allclose(np.asarray(got[0].w), 1.0)
    assert r() is None and r() is None  # unchanged mtime → gated

    import os
    import time

    write(2.0)
    os.utime(path, ns=(time.time_ns(), time.time_ns() + 10**9))
    assert r() is None
    got = r()
    np.testing.assert_allclose(np.asarray(got[0].w), 2.0)

    # a FRESH incarnation re-applies the unchanged artifact once
    r2 = _make_model_reloader(path, "logreg", every_batches=1, log=log)
    assert r2() is not None
    assert r2() is None

    # kind mismatch refused
    r3 = _make_model_reloader(path, "forest", every_batches=1, log=log)
    assert r3() is None


def test_model_reloader_shared_sig_survives_restart(tmp_path):
    """--learn-registry mode: the signature baseline is seeded ONCE and
    shared across supervisor incarnations. A file update landing between
    the previous incarnation's last poll and its crash must still be
    applied by the next incarnation — a per-incarnation re-baseline
    would capture the NEW file's signature and silently drop the update
    forever."""
    import logging
    import os
    import time

    import jax.numpy as jnp
    import numpy as np

    from real_time_fraud_detection_system_tpu.cli import _make_model_reloader
    from real_time_fraud_detection_system_tpu.io.artifacts import save_model
    from real_time_fraud_detection_system_tpu.models.logreg import (
        LogRegParams,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import TrainedModel

    log = logging.getLogger("t")
    path = str(tmp_path / "m.npz")

    def write(w0, bump_ns=0):
        save_model(path, TrainedModel(
            kind="logreg",
            scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
            params=LogRegParams(w=jnp.full(15, w0), b=jnp.zeros(()))))
        if bump_ns:
            os.utime(path, ns=(time.time_ns(), time.time_ns() + bump_ns))

    write(1.0)
    sig: dict = {}
    r1 = _make_model_reloader(path, "logreg", every_batches=1, log=log,
                              seed_initial=True, sig_state=sig)
    # seeded baseline: no forced first reload (the registry champion,
    # not the bootstrap file, is what should serve)
    assert r1() is None
    # the update lands; the incarnation crashes BEFORE its next poll
    write(2.0, bump_ns=10**9)
    r2 = _make_model_reloader(path, "logreg", every_batches=1, log=log,
                              seed_initial=True, sig_state=sig)
    got = r2()  # next incarnation: baseline survives → change detected
    assert got is not None
    np.testing.assert_allclose(np.asarray(got[0].w), 2.0)
    assert r2() is None  # the applied signature gates from here


def test_zombie_reloader_cannot_poison_shared_sig(tmp_path):
    """A reload poll whose incarnation is abandoned MID-CALL (store GET
    stalled past the watchdog) commits the new file signature to the
    shared cross-incarnation baseline, but its swap can never land
    (fenced). The fence wrapper must restore the pre-call signature so
    the LIVE incarnation's next poll still detects the update — else
    the update is silently dropped forever."""
    import logging
    import os
    import time

    import jax.numpy as jnp
    import numpy as np
    import pytest

    from real_time_fraud_detection_system_tpu.cli import _make_model_reloader
    from real_time_fraud_detection_system_tpu.io.artifacts import save_model
    from real_time_fraud_detection_system_tpu.models.logreg import (
        LogRegParams,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import TrainedModel
    from real_time_fraud_detection_system_tpu.runtime.faults import (
        StallError,
        _AbandonFence,
        _fence_model_reload,
    )

    log = logging.getLogger("t")
    path = str(tmp_path / "m.npz")

    def write(w0, bump_ns=0):
        save_model(path, TrainedModel(
            kind="logreg",
            scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
            params=LogRegParams(w=jnp.full(15, w0), b=jnp.zeros(()))))
        if bump_ns:
            os.utime(path, ns=(time.time_ns(), time.time_ns() + bump_ns))

    write(1.0)
    sig: dict = {}
    zombie_poll = _make_model_reloader(path, "logreg", every_batches=1,
                                       log=log, seed_initial=True,
                                       sig_state=sig)
    fence = _AbandonFence()
    fenced = _fence_model_reload(zombie_poll, fence)
    assert fenced() is None  # seeded: no forced first reload

    # the update lands while the zombie is mid-poll; the watchdog
    # abandons it before the poll returns
    orig_poll = zombie_poll

    def abandoned_mid_call():
        write(2.0, bump_ns=10**9)
        fence.abandoned = True
        return orig_poll()

    abandoned_mid_call.sig_state = sig
    fenced2 = _fence_model_reload(abandoned_mid_call, fence)
    with pytest.raises(StallError):
        fenced2()
    # the zombie's swap never landed, and neither did its sig commit
    live_poll = _make_model_reloader(path, "logreg", every_batches=1,
                                     log=log, seed_initial=True,
                                     sig_state=sig)
    got = live_poll()
    assert got is not None
    np.testing.assert_allclose(np.asarray(got[0].w), 2.0)


def test_model_reloader_s3_head_gates_get(tmp_path, monkeypatch):
    """s3:// reload polling: an unchanged artifact costs one HEAD per
    interval, never a GET — the full download happens only when the
    ETag/size metadata changed (ADVICE r4: a large model polled at small
    intervals was re-downloaded every poll)."""
    import logging

    import jax.numpy as jnp
    import numpy as np
    from test_store import FakeS3Client

    import real_time_fraud_detection_system_tpu.io.store as store_mod
    from real_time_fraud_detection_system_tpu.cli import _make_model_reloader
    from real_time_fraud_detection_system_tpu.io.artifacts import save_model
    from real_time_fraud_detection_system_tpu.models.logreg import (
        LogRegParams,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import TrainedModel

    def blob(w0) -> bytes:
        p = tmp_path / "m.npz"
        save_model(str(p), TrainedModel(
            kind="logreg",
            scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
            params=LogRegParams(w=jnp.full(15, w0), b=jnp.zeros(()))))
        return p.read_bytes()

    fake = FakeS3Client()
    fake.objects[("commerce", "model.npz")] = blob(1.0)
    gets = []
    orig_get = fake.get_object

    def counting_get(Bucket, Key):
        gets.append(Key)
        return orig_get(Bucket=Bucket, Key=Key)

    fake.get_object = counting_get

    real_make = store_mod.make_store
    monkeypatch.setattr(
        store_mod, "make_store",
        lambda url, **kw: real_make(url, client=fake, **kw))

    r = _make_model_reloader("s3://commerce/model.npz", "logreg",
                             every_batches=1, log=logging.getLogger("t"))
    got = r()  # first due interval downloads + swaps
    assert got is not None
    np.testing.assert_allclose(np.asarray(got[0].w), 1.0)
    assert len(gets) == 1
    assert r() is None and r() is None  # unchanged: HEAD-gated, no GET
    assert len(gets) == 1

    fake.objects[("commerce", "model.npz")] = blob(2.0)
    got = r()  # metadata changed → one GET + swap
    assert got is not None
    np.testing.assert_allclose(np.asarray(got[0].w), 2.0)
    assert len(gets) == 2


def test_import_model_rejects_wrong_feature_order(tmp_path):
    """A pickle fitted on the same 15 features in a DIFFERENT column
    order must be refused (it would import cleanly and serve
    silently-wrong probabilities otherwise; ADVICE r4)."""
    import pickle

    import numpy as np
    import pandas as pd
    from sklearn.linear_model import LogisticRegression

    import real_time_fraud_detection_system_tpu.cli as cli
    from real_time_fraud_detection_system_tpu.features.spec import (
        FEATURE_NAMES,
    )

    rng = np.random.default_rng(3)
    x = rng.normal(size=(200, 15))
    y = (x[:, 0] > 0).astype(np.int32)

    shuffled = list(FEATURE_NAMES)[::-1]
    clf_bad = LogisticRegression(max_iter=200).fit(
        pd.DataFrame(x, columns=shuffled), y)
    pkl = tmp_path / "bad.pkl"
    pkl.write_bytes(pickle.dumps(clf_bad))
    rc = cli.main(["import-model", "--model-pkl", str(pkl),
                   "--out-model", str(tmp_path / "m.npz")])
    assert rc == 2

    clf_ok = LogisticRegression(max_iter=200).fit(
        pd.DataFrame(x, columns=list(FEATURE_NAMES)), y)
    pkl2 = tmp_path / "ok.pkl"
    pkl2.write_bytes(pickle.dumps(clf_ok))
    rc = cli.main(["import-model", "--model-pkl", str(pkl2),
                   "--out-model", str(tmp_path / "m2.npz")])
    assert rc == 0


def test_cli_dlq_inspect_and_replay(tmp_path, capsys):
    """`rtfds dlq`: inspect prints the summary + row records; --replay
    re-scores quarantined rows through a fresh engine, and rows that
    still fail validation report their error instead of a score."""
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.io.artifacts import save_model
    from real_time_fraud_detection_system_tpu.io.sink import DeadLetterSink
    from real_time_fraud_detection_system_tpu.models.logreg import (
        LogRegParams,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import (
        TrainedModel,
    )

    dlq = DeadLetterSink(str(tmp_path / "dlq.jsonl"))
    cols = {
        "tx_id": np.array([41, 42], np.int64),
        "tx_datetime_us": np.array([10**12, 10**12 + 1], np.int64),
        "customer_id": np.array([3, 4], np.int64),
        "terminal_id": np.array([5, 6], np.int64),
        # row 41 was quarantined for a then-current bug and is fine now;
        # row 42 is genuinely corrupt (negative amount) and must re-crash
        "tx_amount_cents": np.array([1500, -200], np.int64),
        "kafka_ts_ms": np.array([10**9, 10**9], np.int64),
    }
    dlq.put_rows(cols, reason="crash", error="PoisonRowError: corrupt",
                 batch_index=2, offsets=[7])
    dlq.close()

    rc = cli_main(["--platform", "cpu", "dlq", "--path",
                   str(tmp_path / "dlq.jsonl")])
    assert rc == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[0]["rows"] == 2
    assert lines[0]["by_reason"] == {"crash": 2}
    assert {r["tx_id"] for r in lines[1:]} == {41, 42}

    model_path = str(tmp_path / "m.npz")
    save_model(model_path, TrainedModel(
        kind="logreg",
        scaler=Scaler(mean=jnp.zeros(15, jnp.float32),
                      scale=jnp.ones(15, jnp.float32)),
        params=LogRegParams(w=jnp.zeros(15, jnp.float32),
                            b=jnp.float32(0.0))))
    rc = cli_main(["--platform", "cpu", "dlq", "--path",
                   str(tmp_path / "dlq.jsonl"), "--replay",
                   "--model-file", model_path])
    assert rc == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[0]["replayed"] == 2
    by_tx = {r["tx_id"]: r for r in lines[1:]}
    assert 0.0 <= by_tx[41]["prediction"] <= 1.0  # scores cleanly now
    assert by_tx[42].get("still_poison") is True  # stays quarantined
    assert "PoisonRowError" in by_tx[42]["error"]


def test_cli_score_nan_guard_flag_validation(tmp_path, capsys):
    rc = cli_main(["--platform", "cpu", "score", "--data", "x.npz",
                   "--model-file", "m.npz", "--nan-guard"])
    assert rc == 2  # --nan-guard without --dead-letter
    capsys.readouterr()


def test_load_model_v0_unhashed_back_compat(tmp_path):
    """Artifacts written before the content-hash stamp (v0: no
    ``format`` / ``content_sha256`` in the meta) still load — existing
    deployments upgrade in place on their next save, which is stamped
    v1."""
    import io

    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.io.artifacts import (
        ARTIFACT_FORMAT,
        dump_model_bytes,
        load_model,
        load_model_bytes,
    )
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import (
        TrainedModel,
    )

    model = TrainedModel(
        kind="logreg",
        scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
        params=init_logreg(15, seed=5))
    data = dump_model_bytes(model)
    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    # strip the v1 stamps → a byte-faithful v0 (pre-hash) artifact
    assert meta.pop("format") == ARTIFACT_FORMAT
    assert meta.pop("content_sha256")
    buf = io.BytesIO()
    np.savez(buf, __meta__=json.dumps(meta), **arrays)
    v0_bytes = buf.getvalue()

    got = load_model_bytes(v0_bytes)
    assert got.kind == "logreg"
    np.testing.assert_allclose(np.asarray(got.params.w),
                               np.asarray(model.params.w))
    # the file path loads too (no quarantine on a healthy v0)
    path = tmp_path / "v0.npz"
    path.write_bytes(v0_bytes)
    assert load_model(str(path)).kind == "logreg"
    assert path.exists()
    # its next save is stamped v1 with a verifiable content hash
    with np.load(io.BytesIO(dump_model_bytes(got)),
                 allow_pickle=False) as z2:
        meta2 = json.loads(str(z2["__meta__"]))
    assert meta2["format"] == ARTIFACT_FORMAT
    assert len(meta2["content_sha256"]) == 64


def test_cli_registry_list_inspect_promote_rollback_verify(tmp_path,
                                                           capsys):
    """`rtfds registry`: list shows lineage + roles, --inspect dumps one
    manifest, --promote verifies then moves the champion pointer,
    --rollback pops it, and --verify exits 1 on a corrupt artifact —
    which --promote then refuses."""
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.io.registry import (
        make_model_registry,
    )
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import (
        TrainedModel,
    )

    def _m(seed):
        return TrainedModel(
            kind="logreg",
            scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
            params=init_logreg(15, seed=seed))

    root = str(tmp_path)
    reg = make_model_registry(root)
    v1 = reg.publish(_m(0), source="bootstrap")
    reg.publish(_m(1), parent=v1, source="learner", labels_trained=64)
    reg.promote(v1, by="bootstrap")

    rc = cli_main(["--platform", "cpu", "registry", "--path", root])
    assert rc == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[0]["champion"] == 1
    assert [r["version"] for r in lines[1:]] == [1, 2]
    assert [r["role"] for r in lines[1:]] == ["champion", "candidate"]

    rc = cli_main(["--platform", "cpu", "registry", "--path", root,
                   "--inspect", "2"])
    assert rc == 0
    man = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert man["parent"] == 1 and man["source"] == "learner"
    assert man["labels_trained"] == 64

    rc = cli_main(["--platform", "cpu", "registry", "--path", root,
                   "--promote", "2"])
    assert rc == 0
    ptr = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert ptr["version"] == 2 and ptr["history"] == [1]

    rc = cli_main(["--platform", "cpu", "registry", "--path", root,
                   "--rollback"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["champion"] == 1

    # rot the candidate: --verify is the deploy preflight and exits 1
    npz = tmp_path / "model-v0000002.npz"
    data = bytearray(npz.read_bytes())
    data[len(data) // 2] ^= 0xFF
    npz.write_bytes(bytes(data))
    rc = cli_main(["--platform", "cpu", "registry", "--path", root,
                   "--verify"])
    assert rc == 1
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[0]["corrupt"] == 1
    bad = [e for e in lines[1:] if not e["valid"]]
    assert [e["version"] for e in bad] == [2]

    # a corrupt candidate can never be promoted, from the CLI either
    rc = cli_main(["--platform", "cpu", "registry", "--path", root,
                   "--promote", "2"])
    assert rc == 1
    capsys.readouterr()
    rc = cli_main(["--platform", "cpu", "registry", "--path", root])
    assert rc == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[0]["champion"] == 1
    assert [r["version"] for r in lines[1:]] == [1]  # v2 quarantined


def test_cli_registry_publish_external_candidate(tmp_path, capsys):
    """`rtfds registry --publish m.npz`: the offline-retrain entry point
    (tree kinds) — the artifact is verified, registered as a candidate
    with the champion as parent, and a corrupt file is refused."""
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.io.artifacts import save_model
    from real_time_fraud_detection_system_tpu.io.registry import (
        make_model_registry,
    )
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import (
        TrainedModel,
    )

    root = str(tmp_path / "reg")
    reg = make_model_registry(root)
    v1 = reg.publish(TrainedModel(
        kind="logreg",
        scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
        params=init_logreg(15, seed=0)), source="bootstrap")
    reg.promote(v1, by="bootstrap")

    mfile = tmp_path / "retrained.npz"
    save_model(str(mfile), TrainedModel(
        kind="logreg",
        scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
        params=init_logreg(15, seed=3)))
    rc = cli_main(["--platform", "cpu", "registry", "--path", root,
                   "--publish", str(mfile)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["published"] == 2 and out["kind"] == "logreg"
    man = reg.meta(2)
    assert man["source"] == "cli" and man["parent"] == 1
    # the champion pointer does NOT move: the serving loop's live-metric
    # gate (or an explicit --promote) decides, never a bare publish
    assert reg.champion_version() == 1

    # a corrupt artifact is refused at publish
    data = bytearray(mfile.read_bytes())
    data[len(data) // 2] ^= 0xFF
    mfile.write_bytes(bytes(data))
    rc = cli_main(["--platform", "cpu", "registry", "--path", root,
                   "--publish", str(mfile)])
    assert rc == 1
    capsys.readouterr()
    assert [m["version"] for m in reg.list_versions()] == [1, 2]


def test_load_model_truncated_raises_without_quarantine(tmp_path):
    """A short read (torn concurrent write of an operator-shipped file)
    raises but does NOT rename the file away — the next reload poll must
    find the completed write at the same path. A failed CONTENT hash is
    definitive corruption and IS quarantined."""
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.io.artifacts import (
        CorruptModelError,
        dump_model_bytes,
        load_model,
    )
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import (
        TrainedModel,
    )

    data = dump_model_bytes(TrainedModel(
        kind="logreg",
        scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)),
        params=init_logreg(15)))
    torn = tmp_path / "torn.npz"
    torn.write_bytes(data[:48])
    with pytest.raises(CorruptModelError) as ei:
        load_model(str(torn))
    assert ei.value.reason == "truncated"
    assert torn.exists()  # still there: the in-flight copy can finish
    assert not [n for n in os.listdir(tmp_path) if n.startswith("stale-")]

    # definitive content-hash corruption: rebuild the npz with one array
    # value changed but the writer's v1 hash stamp intact — the zip layer
    # is happy, the recomputed content sha256 is not
    import io

    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        meta_raw = str(z["__meta__"])
        arrays = {k: np.array(z[k]) for k in z.files if k != "__meta__"}
    arrays["w"].flat[0] += 1.0
    buf = io.BytesIO()
    np.savez(buf, __meta__=meta_raw, **arrays)
    rotted = tmp_path / "rotted.npz"
    rotted.write_bytes(buf.getvalue())
    with pytest.raises(CorruptModelError) as ei2:
        load_model(str(rotted))
    assert ei2.value.reason == "checksum"
    assert not rotted.exists()  # bit-rot: quarantined
    assert [n for n in os.listdir(tmp_path) if n.startswith("stale-")]


def test_score_learn_registry_restart_adopts_champion(tmp_path):
    """On restart with a non-empty registry, the engine must serve the
    registry's champion — a promotion survives the process, and the
    lineage/metrics describe the model that is actually serving — and a
    kind-mismatched champion fails fast instead of silently serving the
    wrong thing."""
    import subprocess
    import sys

    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.io.query import load_analyzed
    from real_time_fraud_detection_system_tpu.io.registry import (
        make_model_registry,
    )
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.models.train import (
        TrainedModel,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def cli(*a):
        return subprocess.run(
            [sys.executable, "-m",
             "real_time_fraud_detection_system_tpu.cli", *a],
            capture_output=True, text=True, cwd=repo, env=env)

    p = cli("datagen", "--out", str(tmp_path / "txs.npz"),
            "--customers", "60", "--terminals", "120", "--days", "25")
    assert p.returncode == 0, p.stderr[-500:]
    p = cli("train", "--data", str(tmp_path / "txs.npz"),
            "--out-model", str(tmp_path / "m.npz"), "--model", "logreg")
    assert p.returncode == 0, p.stderr[-500:]
    reg_dir = str(tmp_path / "reg")
    p = cli("score", "--data", str(tmp_path / "txs.npz"),
            "--model-file", str(tmp_path / "m.npz"),
            "--out", str(tmp_path / "run1"),
            "--learn-registry", reg_dir, "--max-batches", "2")
    assert p.returncode == 0, p.stderr[-800:]
    reg = make_model_registry(reg_dir)
    assert reg.champion_version() == 1  # bootstrapped from the file

    # out-of-band promotion (e.g. `rtfds registry --promote` after an
    # offline retrain): a flag-everything model, distinctive on purpose
    scaler = Scaler(mean=jnp.zeros(15), scale=jnp.ones(15))
    v2 = reg.publish(
        TrainedModel(kind="logreg", scaler=scaler,
                     params=init_logreg(15)._replace(
                         b=jnp.asarray(6.0, jnp.float32))),
        parent=1, source="learner")
    reg.promote(v2)

    # restart: same flags, same --model-file — v2 must serve
    p = cli("score", "--data", str(tmp_path / "txs.npz"),
            "--model-file", str(tmp_path / "m.npz"),
            "--out", str(tmp_path / "run2"),
            "--learn-registry", reg_dir, "--max-batches", "2")
    assert p.returncode == 0, p.stderr[-800:]
    assert "serving registry champion v2" in p.stderr
    fresh = make_model_registry(reg_dir)
    assert fresh.champion_version() == 2
    assert fresh.versions() == [1, 2]  # no duplicate bootstrap
    cols = load_analyzed(str(tmp_path / "run2"))
    # b=+6 champion flags everything — provably not the file model
    assert float(np.mean(cols["prediction"])) > 0.9

    # a champion of a DIFFERENT kind fails fast, never silently serves
    p = cli("train", "--data", str(tmp_path / "txs.npz"),
            "--out-model", str(tmp_path / "forest.npz"),
            "--model", "forest", "--epochs", "2")
    assert p.returncode == 0, p.stderr[-500:]
    p = cli("score", "--data", str(tmp_path / "txs.npz"),
            "--model-file", str(tmp_path / "forest.npz"),
            "--out", str(tmp_path / "run3"),
            "--learn-registry", reg_dir, "--max-batches", "2")
    assert p.returncode == 2
