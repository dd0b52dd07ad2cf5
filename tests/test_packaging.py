"""Packaging surface: pyproject + Makefile (the reference's installable-
system role, ``pyproject.toml:1-30`` + ``Makefile:1-58``)."""

import functools
import importlib.util
import json
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Capability skip, not a collection error: tomllib is stdlib only from
# py3.11 — on 3.10 the pyproject test SKIPS with a precise reason
# instead of erroring the whole file's collection under
# --continue-on-collection-errors (the Makefile/document tests below don't
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
                   "test:", "install:"):
        assert target in mk, target


@functools.lru_cache(maxsize=None)
def _repo_files():
    """The files git would commit (a checkout without ``.git``: the files
    on disk, which are then those alone)."""
    p = subprocess.run(["git", "ls-files"], cwd=REPO, capture_output=True,
                       text=True)
    files = [f for f in p.stdout.splitlines()
             if os.path.exists(os.path.join(REPO, f))]
    if p.returncode != 0 or not files:
        files = [os.path.relpath(os.path.join(d, f), REPO)
                 for d, _, fs in os.walk(REPO) for f in fs]
    return files


# a backticked token that names a file: a path ending in one of this repo's
# file types, then perhaps `:line`, `:lo-hi` or `::name`
_FILE_TOKEN = re.compile(
    r"`([\w./-]+\.(?:py|jsonl|json|md))(?::[\d,-]+|::[\w:\[\]-]+)?`")
# not this repo's to hold: the reference repository's files (SURVEY.md),
# which the documents cite by line beside what replaces them, and what a
# run writes (a registry's manifests, a launcher's and the chip tool's
# reports)
_REFERENCE_PATHS = ("pyspark/", "fraud_detection_model/", "datagen/",
                    "postgres/", "trino/", "superset/")
_NOT_OURS = {"fraud_detection.py", "load_initial_data.py",
             "kafka_s3_sink_transactions.py", "kafka_s3_sink_customers.py",
             "shared_functions.py", "data_gen.py", "pg-src-connector.json",
             "champion.json", "model-v0000001.json", "launcher-metrics.json",
             ".last_call.json"}


@pytest.mark.parametrize("document", [
    "README.md", "DESIGN.md", "MIGRATION.md",
    ".claude/skills/verify/SKILL.md"])
def test_documents_name_only_files_that_exist(document):
    """Every backticked path in a document that says how to use or check
    the system is a file of this repo: whole, under the package, or by a
    unique enough tail (``runtime/engine.py``, ``engine.py``). A document
    that sends its reader to a deleted tool or record fails here."""
    path = os.path.join(REPO, document)
    if not os.path.exists(path):  # the skill is the builder's, not shipped
        pytest.skip(f"{document} is not in this checkout")
    files = _repo_files()
    with open(path) as f:
        text = f.read()
    missing = []
    for m in _FILE_TOKEN.finditer(text):
        name = m.group(1)
        if (name.startswith(("/",) + _REFERENCE_PATHS)
                or os.path.basename(name) in _NOT_OURS):
            continue
        tail = name[2:] if name.startswith("./") else name
        if not any(f == tail or f.endswith("/" + tail) for f in files):
            missing.append(m.group(0))
    assert not missing, f"{document} names files that do not exist: " \
        f"{sorted(set(missing))}"


def test_makefile_recipes_name_only_files_that_exist():
    """Every ``.py`` file and ``tests/…`` path a recipe runs is there."""
    with open(os.path.join(REPO, "Makefile")) as f:
        recipes = [ln for ln in f if ln.startswith("\t")]
    named = set()
    for ln in recipes:
        named.update(re.findall(r"(?<![\w./-])([\w./-]+\.py|tests/[\w./-]*)",
                                ln))
    assert {"chip_smoke.py", "tools/parquet_sql_check.py",
            "tests/test_perf_smoke.py"} <= named  # the pattern still bites
    missing = sorted(n for n in named
                     if not os.path.exists(os.path.join(REPO, n)))
    assert not missing, missing


def _console_script(argv, capsys):
    from real_time_fraud_detection_system_tpu import cli

    with pytest.raises(SystemExit) as e:
        cli.main(argv)
    io = capsys.readouterr()
    return e.value.code, io.out + io.err


def test_console_script_help_lists_no_bench(capsys):
    """One way to measure the system: ``benchmark/run.py``. The console
    script has no sub-command that measures, and keeps every other."""
    code, said = _console_script(["--help"], capsys)
    assert code == 0
    # argparse wraps the list at the terminal's width; {cpu,tpu} comes first
    commands = re.findall(r"\{([\w,-]+)\}",
                          re.sub(r"\s+", "", said))[1].split(",")
    assert "bench" not in commands
    assert {"datagen", "train", "score", "warmup", "dlq", "ckpt",
            "registry", "demo", "query", "sql", "import-model",
            "connectors", "dashboard", "trace", "compare", "select",
            "lint", "verify-device"} <= set(commands)


def test_console_script_refuses_bench(capsys):
    code, said = _console_script(["bench"], capsys)
    assert code == 2
    assert "invalid choice: 'bench'" in said


def _readme_performance_rows():
    """(cell, PR, figures) of each row of README's Performance table."""
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    section = text.split("\n## Performance\n", 1)[1].split("\n## ", 1)[0]
    rows = []
    for ln in section.splitlines():
        m = re.match(r"\| `([\w.-]+)` \|", ln)
        if m:
            end_to_end = ln.split("|")[3]  # | cell | what runs | end to end |
            # a cell the ledger has no line for yet (the PR that adds it)
            # says "(my chip runs, PR n)": held to nothing but its row
            on_ledger = re.search(r"\(ledger, PR (\d+)\)", end_to_end)
            if on_ledger is None:
                assert re.search(r"\(my chip runs, PR \d+\)", end_to_end), ln
            pr = int(on_ledger.group(1)) if on_ledger else None
            figures = [float(x.replace(",", "")) for x in re.findall(
                r"(\d[\d,]*(?:\.\d+)?) (?:rows/s|ms)", end_to_end)]
            rows.append((m.group(1), pr, figures))
    return rows


@pytest.mark.parametrize("cell", [
    "forest.saturate", "forest.steady", "logreg.saturate",
    "forest-x4.saturate", "forest-exact.saturate", "forest-cold.saturate",
    "forest-id64.saturate", "forest-x4-exact.saturate",
    "forest-replay.saturate", "forest-cold-replay.saturate"])
def test_readme_performance_says_what_the_ledger_says(cell):
    """Every cell has one row in README's table, its rates and latencies
    marked "(ledger, PR n)"; while the ledger still holds that PR's line
    for the cell (the driver trims old lines), each figure is the change
    side of an end-to-end metric on it."""
    row = [r for r in _readme_performance_rows() if r[0] == cell]
    assert len(row) == 1, f"README's Performance table has no row {cell}"
    _, pr, figures = row[0]
    assert figures, f"{cell}: no figure in the row"
    with open(os.path.join(REPO, "PERF_LEDGER.jsonl")) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    for line in lines:
        if line.get("pr") == pr and line.get("workload") == cell:
            held = [v[1] for k, v in line["end_to_end"].items()
                    if k != "setup_s"]
            for x in figures:
                assert x in held, f"{cell}: {x} is not on the line {held}"
