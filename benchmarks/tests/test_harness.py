"""The benchmark's own tests. Run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They pin the yardstick's arithmetic and the harness's plumbing on the CPU at
20,000 rows; no number from them is a device metric.
"""

import copy
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks import harness, refmath, trace_reduce  # noqa: E402
from benchmarks.tools import faults  # noqa: E402

ROWS = 20_000
CELLS = ("higgs_fit", "catering_dq_lasso")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def run_cell(cell, seed=7, trace=0, root=REPO, tamper=None, tmp=None):
    return harness.execute(cell, seed, 0.5, trace, root, require_tpu=False,
                           rows=ROWS, scratch=tmp, tamper=tamper)


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(REPO, "BENCHMARK.json"))


# -- the jobs agree with their references, and the line is the contract's --

@pytest.mark.parametrize("cell", CELLS)
def test_job_agrees_with_its_reference(cell, bench):
    line = run_cell(cell, seed=2_600_000_011)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    json.dumps(line)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_the_layer_metrics_it_can_read(cell, tmp_path):
    line = run_cell(cell, trace=1, tmp=str(tmp_path))
    assert line["correct"] is True
    # no TPU plane on the CPU: readers of the trace and of the peaks find
    # nothing and are left out; the span readers report
    assert set(line["metrics"]) == {"dq_sql_ms", "fit_ms",
                                    "setup_after_claim_s"}


# -- the control and the faults come out as not correct --------------------

@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_fails_a_limit(cell):
    import jax

    spec = harness.load_cell(cell, REPO)
    cfg, mod, traffic = spec["cfg"], spec["cfg_mod"], spec["traffic"]
    for seed in (1, 2, 3):
        host = jax.device_get(mod.make_table(cfg, seed, ROWS))
        want = spec["job_mod"].reference(cfg, mod, traffic["params"], host)
        low = spec["job_mod"].reference(cfg, mod, traffic["params"], host,
                                        q=refmath.round_bf16)
        gaps = spec["job_mod"].compare(low, want)
        failed = [k for k, v in gaps.items() if v > traffic["limits"][k]]
        assert failed, gaps


FAULTS = [(cell, name) for cell, by_name in faults.FAULTS.items()
          for name in by_name]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    undo = []

    def tamper(job):
        faults.FAULTS[cell][fault](job)
        undo.append(getattr(job, "_undo", lambda: None))

    try:
        line = run_cell(cell, tamper=tamper)
    finally:
        for u in undo:
            u()
    assert line["correct"] is False, line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


# -- no chip, no result ------------------------------------------------------

def test_runner_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "higgs_fit", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no result" in done.stderr


# -- a cell, a configuration and a layer metric are files, not edits --------

def test_new_cell_config_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    b = root / "benchmarks"
    cfg = harness.load_json(b / "configs" / "higgs-logistic.json")
    cfg["name"] = "susy-logistic"
    cfg["features"] = 18
    cfg["assumed"]["beta"] = cfg["assumed"]["beta"][:18]
    cfg["assumed"]["positive_columns"] = [0, 3, 5]
    (b / "configs" / "susy-logistic.json").write_text(json.dumps(cfg))
    shutil.copy(b / "configs" / "higgs-logistic.py",
                b / "configs" / "susy-logistic.py")
    traffic = harness.load_json(b / "traffic" / "filter_fit_score.json")
    traffic["name"] = "one_filter_fit"
    traffic["params"]["filters"] = [["x0", 0.5]]
    (b / "traffic" / "one_filter_fit.json").write_text(json.dumps(traffic))
    (b / "layer_metrics" / "score_ms.py").write_text(
        "def read(run):\n"
        "    return 1e3 * run['median']([j['spans']['score']"
        " for j in run['jobs']])\n")
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    bench["configs"].append({
        "name": "susy-logistic", "source": "UCI SUSY", "reduced": [],
        "file": "benchmarks/configs/susy-logistic.json", "why": "test"})
    bench["workloads"].append({
        "name": "susy_fit", "config": "susy-logistic",
        "traffic": "one_filter_fit", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "score_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "fit data pass and solver",
        "moves": "job_p50_ms", "workloads": ["susy_fit"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    line = run_cell("susy_fit", trace=1, root=str(root), tmp=str(tmp_path))
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["score_ms"]["value"] > 0
    line = run_cell("higgs_fit", trace=1, root=str(root), tmp=str(tmp_path))
    assert "score_ms" not in line["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before, "a file that was there had to change"


# -- BENCHMARK.json keeps to the contract's letters and shapes --------------

def test_benchmark_json_names_units_and_shapes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        held = harness.load_json(os.path.join(REPO, c["file"]))
        assert held["reduced"] == c["reduced"]
        names.append(c["name"])
    cells = [w["name"] for w in bench["workloads"]]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and w["config"] in names
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) \
        == len(cells)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
        layers.add(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    assert {m["unit"] for m in bench["end_to_end"]} == {"rows/s", "ms", "s"}
    for n in names + cells:
        assert NAME.match(n)
    all_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(all_names)) == len(all_names)
    perf = open(os.path.join(REPO, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10


def test_unknown_device_kind_is_an_error():
    peaks = harness.load_json(os.path.join(BENCH, "peaks.json"))
    assert harness.peaks_for(peaks, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for(peaks, "TPU v9 imaginary")


# -- the trace reduction's arithmetic, on a hand-built event list ------------

def test_trace_reduction_on_hand_built_events():
    dev = {"/device:TPU:0": [
        ("fusion.1", 1.0, 1.5), ("fusion.1", 1.4, 2.0),     # overlap: 1.0
        ("sort.2", 3.0, 3.5),                               # 0.5
        ("fusion.1", 5.5, 6.5),       # second job: 1.0, half outside `fit`
        ("copy.3", 9.0, 9.5)]}                              # after the window
    spans = [("job", 0.5, 4.0), ("dq_sql", 0.5, 2.5), ("fit", 2.5, 4.0),
             ("job", 5.0, 7.0), ("dq_sql", 5.0, 6.0), ("fit", 6.0, 7.0)]
    r = trace_reduce.reduce(dev, spans)
    assert r["window_s"] == pytest.approx(6.5)
    assert r["busy_s"] == pytest.approx(2.5)
    assert r["idle_share"] == pytest.approx(1 - 2.5 / 6.5)
    assert r["span_device_s"]["dq_sql"] == pytest.approx([1.0, 0.5])
    assert r["span_device_s"]["fit"] == pytest.approx([0.5, 0.5])
    assert r["span_host_s"]["fit"] == pytest.approx([1.5, 1.0])
    ops = dict(map(tuple, r["device_ops"]))
    assert ops == pytest.approx({"fusion.1": 2.0, "sort.2": 0.5})
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps == pytest.approx({"dq_sql": 1.5, "fit": 1.5,
                                  "between_jobs": 1.0})
    assert sum(gaps.values()) == pytest.approx(6.5 - 2.5)
    assert trace_reduce.reduce(dev, []) is None
    assert trace_reduce.reduce({}, spans) is None
    two = dict(dev, **{"/device:TPU:1": [("fusion.1", 1.0, 2.5)]})
    assert trace_reduce.reduce(two, spans)["busy_s"] == pytest.approx(2.0)
    # a while holds its body's operations: each is counted once
    nested = [("%while.3 = (f32[29]) while(...)", 0.0, 1.0),
              ("%fusion.7 = f32[29,29] fusion(...)", 0.1, 0.4),
              ("%fusion.7 = f32[29,29] fusion(...)", 0.5, 0.8),
              ("%copy.1 = f32[8] copy(...)", 1.0, 1.25)]
    assert trace_reduce.self_seconds(nested, 0.0, 2.0) == pytest.approx(
        {"while.3": 0.4, "fusion.7": 0.6, "copy.1": 0.25})


def test_union_and_bf16_rounding():
    assert trace_reduce.union([(2, 3), (0, 1), (0.5, 1.5), (3, 3)]) == \
        [(0, 1.5), (2, 3)]
    assert refmath.round_bf16(1.0) == 1.0
    assert refmath.round_bf16(1.00390625) == 1.0          # tie to even
    assert refmath.round_bf16(1.01171875) == 1.015625     # tie to even, up
    assert refmath.round_bf16(19.97) == 20.0
    assert refmath.rel_gap([1.0, 2.0], [1.0, 2.2]) == pytest.approx(0.2 / 2.2)
    assert refmath.mismatches([1, 2, 3], [1, 5, 3]) == 1.0
    assert refmath.rel_gap([np.nan], [1.0]) == float("inf")


def test_percentile_is_nearest_rank():
    assert harness.percentile(list(range(1, 21)), 0.95) == 19
    assert harness.percentile([5.0], 0.95) == 5.0
