"""Packaging surface: pyproject + Makefile (the reference's installable-
system role, ``pyproject.toml:1-30`` + ``Makefile:1-58``)."""

import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Capability skip, not a collection error: tomllib is stdlib only from
# py3.11 — on 3.10 the pyproject test SKIPS with a precise reason
# instead of erroring the whole file's collection under
# --continue-on-collection-errors (the Makefile/bench tests below don't
# need tomllib and keep running).
_HAS_TOMLLIB = importlib.util.find_spec("tomllib") is not None


@pytest.mark.skipif(
    not _HAS_TOMLLIB,
    reason="tomllib is stdlib from py3.11; pyproject parsing needs it")
def test_pyproject_parses_and_script_resolves():
    import tomllib

    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        meta = tomllib.load(f)
    proj = meta["project"]
    assert proj["name"] == "real-time-fraud-detection-system-tpu"
    target = proj["scripts"]["rtfds"]
    mod_name, attr = target.split(":")
    import importlib

    mod = importlib.import_module(mod_name)
    assert callable(getattr(mod, attr))


def test_makefile_mirrors_reference_targets():
    with open(os.path.join(REPO, "Makefile")) as f:
        mk = f.read()
    for target in ("demo:", "datagen:", "train:", "score:", "run-all:",
                   "bench:", "test:", "install:"):
        assert target in mk, target


def _import_bench():
    import sys

    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    return bench


def test_bench_emit_final_compact_line_last(capsys):
    """A reader that keeps only a tail window of bench stdout must find a
    complete, parseable result JSON in the LAST line, and that line names
    the device the run was on."""
    import json

    bench = _import_bench()
    result = {
        "metric": "score_txns_per_sec", "value": 123.4, "unit": "txns/s",
        "vs_baseline": 2.0,
        "detail": {"platform": "tpu", "device_kind": "TPU v5 lite",
                   "device_count": 1, "section_errors": [],
                   "huge": "x" * 20000},
    }
    bench._emit_final(result)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert len(lines) == 2
    full = json.loads(lines[0])
    assert full["detail"]["huge"]  # full detail preserved first
    compact = json.loads(lines[-1])
    assert compact["metric"] == "score_txns_per_sec"
    assert compact["value"] == 123.4
    assert compact["vs_baseline"] == 2.0
    assert compact["detail"]["platform"] == "tpu"
    assert compact["detail"]["device_kind"] == "TPU v5 lite"
    assert compact["detail"]["device_count"] == 1
    assert len(lines[-1]) < 400  # fits any sane tail window


def test_bench_peak_flops_raises_on_unknown_device_kind():
    """A device that is not in the table is an error, never an assumed
    v5e."""
    import pytest

    bench = _import_bench()
    assert bench._peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="never assumed"):
        bench._peak_flops("Quantum Abacus 9000")


def test_bench_exits_nonzero_when_it_finds_no_tpu(monkeypatch, capsys):
    """No TPU and no explicit JAX_PLATFORMS=cpu: the run fails before it
    measures anything — never a CPU figure in a device result. (The test
    process itself is pinned to the CPU by conftest's config update, so
    unsetting the variable is the 'found no TPU' case.)"""
    import pytest

    bench = _import_bench()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as e:
        bench.main(["--quick"])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert "score_txns_per_sec" not in capsys.readouterr().out


def test_bench_failed_section_is_reported_and_fails_the_run(monkeypatch,
                                                            capsys):
    """A section that raises keeps its place in the JSON (the other
    sections' numbers survive) but the exit code is non-zero."""
    import json

    import pytest

    bench = _import_bench()

    def measure(args):
        err = bench._section_error("state_scale", RuntimeError("boom"))
        return {"metric": "score_txns_per_sec", "value": 1.0,
                "unit": "txns/s", "vs_baseline": 0.0,
                "detail": {"platform": "tpu", "device_kind": "TPU v5 lite",
                           "device_count": 1, "state_scale": err,
                           "section_errors": list(bench._SECTION_ERRORS)}}

    monkeypatch.setattr(bench, "_measure", measure)
    with pytest.raises(SystemExit) as e:
        bench.main([])
    assert e.value.code == 1
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    full, compact = json.loads(lines[0]), json.loads(lines[-1])
    assert full["detail"]["state_scale"] == {"error": "RuntimeError: boom"}
    assert full["detail"]["section_errors"] == [
        "state_scale: RuntimeError: boom"]
    assert compact["detail"]["section_errors"] == 1
