"""The standalone hardware tools must at least run clean on CPU.

tests/conftest.py pins pytest itself to the virtual CPU mesh, so the
tools are exercised as subprocesses with an explicit ``JAX_PLATFORMS=cpu``
— the invocation a chip run uses, minus the real device."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, extra_env=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        cmd, cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout,
    )


def test_hw_parity_check_cpu():
    p = _run([sys.executable, "tools/hw_parity_check.py"])
    assert p.returncode == 0, p.stderr[-800:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["ok"] is True
    assert out["forest_gemm_max_abs_diff"] < 1e-5
    assert out["feature_kernel_max_abs_diff"] < 1e-4
    assert out["auc_abs_gap"] < 1e-3


def test_parquet_sql_check():
    """The SQL read-back proof must pass on the bare image (sqlite path;
    uses DuckDB instead when installed)."""
    p = _run([sys.executable, "tools/parquet_sql_check.py"], timeout=600)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-800:])
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["ok"] is True
    assert out["mismatches"] == []
    assert out["rows"] > 1000


def test_parquet_sql_check_dedups_replayed_parts(tmp_path):
    """A directory holding re-scored rows (crash-replay) must still pass:
    both the SQL view and the numpy oracle apply latest-wins by tx_id."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    d = tmp_path / "analyzed"
    d.mkdir()
    rng = np.random.default_rng(0)

    def part(path, tx_ids, processed_at, pred):
        n = len(tx_ids)
        pq.write_table(pa.table({
            "tx_id": pa.array(tx_ids, pa.int64()),
            "tx_datetime_us": pa.array(
                np.sort(rng.integers(0, 5 * 86_400_000_000, n)),
                pa.int64()),
            "customer_id": pa.array(rng.integers(0, 10, n), pa.int64()),
            "terminal_id": pa.array(rng.integers(0, 20, n), pa.int64()),
            "tx_amount": pa.array(rng.uniform(1, 100, n), pa.float64()),
            "prediction": pa.array(pred, pa.float64()),
            "processed_at_us": pa.array(
                np.full(n, processed_at), pa.int64()),
        }), str(path))

    part(d / "part-00000001.parquet", np.arange(100), 1_000_000,
         rng.uniform(0, 1, 100))
    # replay re-scores rows 50..99 later with different predictions
    part(d / "part-00000002.parquet", np.arange(50, 100), 2_000_000,
         rng.uniform(0, 1, 50))
    p = _run([sys.executable, "tools/parquet_sql_check.py",
              "--dir", str(d)], timeout=300)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-800:])
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["ok"] is True and out["rows"] == 100


def _run_chip_smoke(cwd, script, *args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """`python chip_smoke.py` as the driver runs it, in a sandbox with no
    accelerator: non-zero exit, no phase run on the CPU, never ok=true."""
    r = _run_chip_smoke(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout
    assert "[main]" not in r.stdout and "[kernels]" not in r.stdout


def test_benchmark_refuses_to_run_without_a_tpu():
    """`python benchmark/run.py`, the one way to measure, in a sandbox with
    no accelerator: exit 3, no result line, never a CPU figure under a
    device metric's name."""
    r = _run_chip_smoke(REPO, os.path.join(REPO, "benchmark", "run.py"),
                        "--workload", "forest.saturate", "--seed", "1",
                        "--seconds", "4", "--trace", "0")
    assert r.returncode == 3, r.stderr[-800:]
    assert "never measures on the CPU" in r.stderr
    assert "rows_per_s" not in r.stdout and '"correct"' not in r.stdout


class _FakeChip:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind,seen,chips,says", [
    ("Quantum Abacus 9000", 1, 1, "no peaks on record"),
    ("TPU v5 lite", 1, 4, "needs 4 chips"),
    ("TPU v5 lite", 4, 4, None),
])
def test_benchmark_claims_only_chips_it_can_judge(monkeypatch, kind, seen,
                                                  chips, says):
    """A device that is not in ``benchmark/peaks.json`` is an error, never
    an assumed v5e; so are fewer chips than the cell asks for."""
    import jax

    from benchmark import harness

    monkeypatch.setattr(jax, "devices", lambda: [_FakeChip(kind)] * seen)
    # claim_device points the persistent cache at the benchmark's own
    monkeypatch.setattr(jax.config, "update", lambda name, value: None)
    if says is None:
        assert len(harness.claim_device(REPO, chips, allow_cpu=False)) == chips
    else:
        with pytest.raises(harness.HarnessError, match=says):
            harness.claim_device(REPO, chips, allow_cpu=False)


def test_chip_smoke_alone_without_the_program_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it must fail and print no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run_chip_smoke(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_chip_smoke_rehearsal_runs_every_one_chip_phase(tmp_path):
    """`--rehearse-tiny-on-cpu` drives the one-chip control flow end to
    end at toy sizes — device, native build, datagen → train → score,
    the NumPy feature reference, the `--scorer cpu` oracle, both fused
    kernels against XLA — and still refuses to call it a pass: exit code
    4, last line ok=false. Run from a copy of the sources with no built
    `.so` (what the driver's checkout holds), so the rebuild it starts
    with cannot disturb the other tests' native libraries."""
    import shutil

    for d in ("real_time_fraud_detection_system_tpu", "native"):
        shutil.copytree(os.path.join(REPO, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("*.so",
                                                      "__pycache__"))
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse-tiny-on-cpu"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 4, (r.stdout[-1500:], r.stderr[-1500:])
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu",
                                "count": 1}}
    for phase in ("[device]", "[native]", "[artifacts]", "[main]",
                  "[kernels]", "[done]"):
        assert any(ln.startswith(phase) for ln in lines), phase
    assert any("reference=NumPy" in ln and "exact_columns_wrong=none" in ln
               for ln in lines)
    assert any(ln.startswith("[native]") and "envelope_decoder=native"
               in ln for ln in lines) or shutil.which("g++") is None
