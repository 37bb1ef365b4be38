"""Every cell of ``BENCHMARK.json`` runs in tier-1: its files resolve, its
job agrees with its float64 reference through the benchmark's own harness,
and one fault planted under its timed path comes out not ``correct``.

On the CPU harness at 20,000 rows, as ``benchmarks/tests`` runs them. The
cells are read from ``BENCHMARK.json``, so a later cell is covered the day
it lands — and fails here until it comes with a fault its checks catch.
Nothing here asserts a time, a rate or a share.
"""

import gc
import glob
import importlib
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402

ROWS = 20_000
BENCH = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]


def planted_faults():
    """``{cell: {name: tamper}}`` over every ``benchmarks/tools/faults*.py``."""
    merged = {}
    for path in sorted(glob.glob(os.path.join(REPO, "benchmarks", "tools",
                                              "faults*.py"))):
        stem = os.path.splitext(os.path.basename(path))[0]
        table = importlib.import_module(f"benchmarks.tools.{stem}").FAULTS
        for cell, by_name in table.items():
            merged.setdefault(cell, {}).update(by_name)
    return merged


def run_cell(cell, tamper=None):
    return harness.execute(cell, 7, 0.5, 0, REPO, require_tpu=False,
                           rows=ROWS, tamper=tamper)


@pytest.fixture(scope="module", autouse=True)
def _thaw_the_collector():
    yield
    gc.unfreeze()      # ``execute`` freezes what is alive before its window


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve_and_every_layer_metric_has_its_reader(cell):
    spec = harness.load_cell(cell, REPO)
    assert spec["cfg"] and callable(spec["cfg_mod"].make_table)
    assert {"job", "loop", "params", "limits"} <= set(spec["traffic"])
    for part in ("Job", "reference", "compare"):
        assert callable(getattr(spec["job_mod"], part)), part
    assert callable(spec["loop_mod"].run)
    assert spec["per_layer"]      # the entries that apply to this cell
    for m in spec["per_layer"]:
        assert callable(harness.load_module("layer_metrics", m["name"],
                                            spec["root"]).read), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_and_reports_its_declared_metrics(cell):
    line = run_cell(cell)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    declared = {m["name"] for m in BENCH["end_to_end"]
                if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == declared


@pytest.mark.parametrize("cell", CELLS)
def test_a_planted_fault_is_not_correct(cell):
    table = planted_faults().get(cell)
    assert table, f"no fault planted for {cell} in benchmarks/tools/faults*.py"
    name, fault = next(iter(table.items()))
    undo = []

    def tamper(job):
        fault(job)
        undo.append(getattr(job, "_undo", lambda: None))

    try:
        line = run_cell(cell, tamper=tamper)
    finally:
        for u in undo:
            u()
    assert line["correct"] is False, (name, line["checks"])
    assert any(c["value"] > c["limit"] for c in line["checks"].values()), name
