"""The ``hibench_kmeans`` cell's own tests, beside ``test_higgs_gbt.py``.
Run by hand, not part of tier-1 (``tests/test_kmeans_device.py`` and
``tests/test_benchmark_cells.py`` are):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

On the CPU at 20,000 rows; no number from them is a device metric.
"""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks import harness, refmath  # noqa: E402
from benchmarks.tools import faults_hibench_kmeans  # noqa: E402

ROWS = 20_000
CELL = "hibench_kmeans"


def run_cell(seed=7, trace=0, tamper=None, tmp=None):
    return harness.execute(CELL, seed, 0.5, trace, REPO, require_tpu=False,
                           rows=ROWS, scratch=tmp, tamper=tamper)


@pytest.fixture(scope="module")
def spec():
    return harness.load_cell(CELL, REPO)


def test_cell_configuration_and_metrics_are_found_by_name(spec):
    cfg = spec["cfg"]
    assert spec["cell"]["config"] == "hibench-kmeans"
    assert spec["cell"]["chips"] == 1
    assert spec["traffic"]["job"] == "filter_kmeans_cost"
    assert cfg["reduced"] == ["rows"] and cfg["rows"] == 50_000_000
    assert cfg["published"]["rows"] == 100_000_000
    # HiBench's huge profile and MLlib 2.4's defaults, nothing cut but rows
    assert cfg["features"] == 20 and cfg["generated_clusters"] == 5
    assert cfg["estimator"] == {
        "class": "KMeans", "k": 10, "max_iter": 5,
        "init_mode": "k-means||", "init_steps": 2, "tol": 1e-4, "seed": 20}
    names = {m["name"] for m in spec["per_layer"]}
    assert {"kmeans_init_ms", "kmeans_lloyd_ms", "kmeans_hbm_roofline",
            "job_hbm_roofline", "device_idle_share",
            "setup_after_claim_s"} <= names
    assert not names & {"fit_ms", "tree_bin_ms", "grouped_ms", "join_ms"}
    # the thresholds are exact in float32
    for _, t in spec["traffic"]["params"]["filters"]:
        assert float(np.float32(t)) == t


def test_least_bytes_by_hand(spec):
    # 1,000 rows x 20 features x 4 B, read 2 + 1 + 5 = 8 times
    want = 1_000 * 20 * 4 * 8
    assert spec["job_mod"].kmeans_least_bytes(
        spec["cfg"], spec["cfg_mod"], 1_000) == want == 640_000


def test_the_filter_keeps_nine_rows_in_ten(spec):
    import jax

    cfg, mod, traffic = spec["cfg"], spec["cfg_mod"], spec["traffic"]
    for seed in (1, 2_600_000_011, 3_000_000_019):
        host = jax.device_get(mod.make_table(cfg, seed, 200_000))
        keep = np.ones(200_000, bool)
        for col, t in traffic["params"]["filters"]:
            keep &= host[col] > t
        assert 0.88 <= keep.mean() <= 0.92, (seed, keep.mean())


def test_job_agrees_with_its_reference():
    line = run_cell(seed=2_600_000_011)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["checks"]) == {
        "rows_kept_diff", "iterations_diff", "candidates_not_rows",
        "candidate_weights_diff", "init_distinct_diff", "init_cost_ratio",
        "step_rel", "sizes_diff", "cost_rel", "score_sizes_diff",
        "score_cost_rel", "jobs_failed", "degraded_paths"}
    for name in ("rows_kept_diff", "iterations_diff", "candidates_not_rows",
                 "init_distinct_diff", "sizes_diff", "score_sizes_diff"):
        assert line["checks"][name]["value"] == 0.0, name


def test_a_traced_run_reports_the_three_program_metrics(tmp_path):
    line = run_cell(trace=1, tmp=str(tmp_path))
    assert line["correct"] is True, line["checks"]
    # no device plane on the CPU: the span metrics need the reduced trace
    assert "kmeans_hbm_roofline" not in line["metrics"]


def test_reference_lloyd_step_by_brute_force(spec):
    mod = spec["cfg_mod"]
    rng = np.random.default_rng(5)
    X = rng.normal(size=(3000, 6)).astype(np.float32) * 3
    keep = rng.random(3000) < 0.8
    centres = X[keep][:7].astype(np.float64)
    cols = [X[:, j] for j in range(6)]
    sums, sizes, cost, new = mod.lloyd_step(cols, keep, centres)
    Xk = X[keep].astype(np.float64)
    d2 = ((Xk[:, None, :] - centres[None]) ** 2).sum(-1)
    arg = d2.argmin(1)
    assert list(sizes) == list(np.bincount(arg, minlength=7))
    assert cost == pytest.approx(d2.min(1).sum(), rel=1e-12)
    for j in range(7):
        assert np.allclose(new[j], Xk[arg == j].mean(0), rtol=1e-12)
    assert np.allclose(sums, new * sizes[:, None])


def test_bf16_control_fails_a_limit(spec):
    import jax

    cfg, mod, traffic = spec["cfg"], spec["cfg_mod"], spec["traffic"]
    for seed in (1, 2, 3):
        host = jax.device_get(mod.make_table(cfg, seed, ROWS))
        want = spec["job_mod"].reference(cfg, mod, traffic["params"], host)
        low = spec["job_mod"].reference(cfg, mod, traffic["params"], host,
                                        q=refmath.round_bf16)
        gaps = spec["job_mod"].compare(low, want)
        failed = [k for k, v in gaps.items() if v > traffic["limits"][k]]
        assert "step_rel" in failed, gaps


@pytest.mark.parametrize("fault", list(faults_hibench_kmeans.FAULTS[CELL]))
def test_a_broken_timed_path_is_not_correct(fault):
    undo = []

    def tamper(job):
        faults_hibench_kmeans.FAULTS[CELL][fault](job)
        undo.append(getattr(job, "_undo", lambda: None))

    try:
        line = run_cell(tamper=tamper)
    finally:
        for u in undo:
            u()
    assert line["correct"] is False, line["checks"]
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    want = {"half_pass": {"step_rel"},
            "dropped_rows_vote": {"step_rel", "sizes_diff"},
            "one_step_short": {"step_rel"},
            "foreign_candidate": {"candidates_not_rows"}}[fault]
    assert want <= failing, failing


def test_the_default_precision_variant_is_exact_on_the_cpu():
    """Off the TPU a matmul at default precision is exact: the variant is
    no fault here (on the chip its operands are bfloat16: PERF.md)."""
    undo = []

    def tamper(job):
        faults_hibench_kmeans.VARIANTS[CELL]["default_precision"](job)
        undo.append(job._undo)

    try:
        line = run_cell(tamper=tamper)
    finally:
        for u in undo:
            u()
    assert line["correct"] is True, line["checks"]


def test_a_fit_that_leaves_the_device_entry_is_an_error():
    """The job raises where ``kmeans.fit_device`` does not move once a job
    or a degraded counter does (here: a seeding round that overflows)."""
    from sparkdq4ml_tpu.models import clustering

    bucket = clustering.init_bucket
    undo = []

    def tamper(job):
        clustering.init_bucket = lambda k: 8
        undo.append(lambda: (setattr(clustering, "init_bucket", bucket),
                             job.spark.stop()))

    try:
        with pytest.raises(RuntimeError, match="degraded path"):
            run_cell(tamper=tamper)
    finally:
        for u in undo:
            u()
