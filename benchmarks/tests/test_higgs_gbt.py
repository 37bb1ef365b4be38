"""The ``higgs_gbt`` cell's own tests, beside ``test_tpch_q3.py``. Run by
hand, not part of tier-1 (``tests/test_tree_device.py`` and
``tests/test_benchmark_cells.py`` are):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

On the CPU at 2,000 and 20,000 rows; no number from them is a device metric.
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
from benchmarks.tools import faults_higgs_gbt  # noqa: E402

ROWS = 20_000
CELL = "higgs_gbt"


def run_cell(seed=7, trace=0, tamper=None, tmp=None):
    return harness.execute(CELL, seed, 0.5, trace, REPO, require_tpu=False,
                           rows=ROWS, scratch=tmp, tamper=tamper)


@pytest.fixture(scope="module")
def spec():
    return harness.load_cell(CELL, REPO)


def test_cell_configuration_and_metrics_are_found_by_name(spec):
    cfg, mod = spec["cfg"], spec["cfg_mod"]
    assert spec["cell"]["config"] == "higgs-gbt"
    assert spec["cell"]["chips"] == 1
    assert spec["traffic"]["job"] == "filter_gbt_score"
    assert cfg["reduced"] == [] and cfg["rows"] == 11_000_000
    # MLlib 2.4's documented GBTClassifier defaults
    assert cfg["estimator"] == {
        "class": "GBTClassifier", "max_iter": 20, "max_depth": 5,
        "max_bins": 32, "step_size": 0.1, "subsampling_rate": 1.0,
        "min_instances_per_node": 1, "min_info_gain": 0.0,
        "loss": "logistic"}
    # the table is higgs-logistic's generator, shared and not copied
    shared = harness.load_module("configs", "higgs-logistic")
    logistic = harness.load_json(os.path.join(
        BENCH, "configs", "higgs-logistic.json"))
    for key in ("positive_columns", "positive_sigma", "beta", "intercept"):
        assert cfg["assumed"][key] == logistic["assumed"][key], key
    assert mod.table_bytes(cfg) == shared.table_bytes(logistic)
    assert "def generate" not in open(os.path.join(
        BENCH, "configs", "higgs-gbt.py")).read()
    names = {m["name"] for m in spec["per_layer"]}
    assert {"tree_bin_ms", "tree_boost_ms", "gbt_hbm_roofline",
            "job_hbm_roofline", "device_idle_share",
            "setup_after_claim_s"} <= names
    assert not names & {"fit_ms", "dq_sql_ms", "grouped_ms", "join_ms"}


def test_least_bytes_by_hand(spec):
    # 1,000 rows x 28 features: the table once (28 x 4 B + the mask's
    # byte), then 20 rounds of 5 levels at 28 + 12 + 1 = 41 B a row and a
    # gradient pass of 12 B a row
    want = 1_000 * 113 + 20 * 1_000 * (5 * 41 + 12)
    assert spec["job_mod"].gbt_least_bytes(spec["cfg"], spec["cfg_mod"],
                                           1_000) == want == 4_453_000


def test_job_agrees_with_its_reference():
    line = run_cell(seed=2_600_000_011)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["checks"]) == {
        "rows_kept_diff", "edges_diff", "node_rows_diff", "leaf_rel",
        "split_regret", "mean_score_rel", "positives_rel",
        "probe_prob_abs", "jobs_failed", "degraded_paths"}
    assert set(line["metrics"]) == {"rows_per_s", "job_p50_ms",
                                    "job_p95_ms", "setup_s"}


def _brute_force_tree(X, y, edges, est, f0):
    """One round by exhaustion, from the raw values: every node's rows as
    an index list, every (feature, threshold) candidate's left and right
    sums by direct summation — no bins, no histogram, no cumulative sum."""
    depth = est["max_depth"]
    p = 1.0 / (1.0 + np.exp(-f0))
    g = y - p
    nodes = {0: np.arange(len(y))}
    found = {}
    for level in range(depth):
        for i in range(2 ** level - 1, 2 ** (level + 1) - 1):
            rows = nodes.get(i)
            if rows is None or not len(rows):
                continue

            def sse(r):
                return float(np.sum(g[r] ** 2) - np.sum(g[r]) ** 2
                             / max(len(r), 1e-12))

            best = (-np.inf, None, None)
            for f in range(X.shape[1]):
                for b, t in enumerate(edges[f]):
                    if not np.isfinite(t):
                        break
                    left = rows[X[rows, f] <= t]
                    right = rows[X[rows, f] > t]
                    if len(left) < est["min_instances_per_node"] \
                            or len(right) < est["min_instances_per_node"]:
                        continue
                    gain = sse(rows) - sse(left) - sse(right)
                    if gain > best[0] + 1e-9 * abs(gain):   # ties: first
                        best = (gain, f, t)
            if best[1] is None or best[0] <= 1e-12:
                continue
            found[i] = best
            nodes[2 * i + 1] = rows[X[rows, best[1]] <= best[2]]
            nodes[2 * i + 2] = rows[X[rows, best[1]] > best[2]]
    return found, nodes


def test_reference_agrees_with_a_brute_force_exact_split_tree(spec):
    mod = spec["cfg_mod"]
    rng = np.random.default_rng(5)
    n, d = 2_000, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X[:, 0] - X[:, 3])))) \
        .astype(np.float64)
    # leaves of 50 rows: no exact ties between features (see
    # tests/test_tree_device.py)
    est = dict(spec["cfg"]["estimator"], max_iter=1, max_depth=3,
               min_instances_per_node=50)
    cols = list(X.T)
    edges = mod.thresholds(cols, np.ones(n, bool), est["max_bins"])
    f0, trees, F, _ = mod.grow(mod.bin_rows(cols, edges), edges, y, est)
    found, nodes = _brute_force_tree(X.astype(np.float64), y, edges, est,
                                     f0)
    split = np.flatnonzero(~trees["is_leaf"][0])
    assert sorted(found) == split.tolist()
    for i, (gain, f, t) in found.items():
        assert trees["feature"][0, i] == f and trees["threshold"][0, i] == t
        assert trees["gain"][0, i] == pytest.approx(gain, rel=1e-9)
    for i, rows in nodes.items():
        assert trees["value"][0, i, 0] == len(rows)


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
        assert failed, gaps


@pytest.mark.parametrize("fault", sorted(faults_higgs_gbt.FAULTS[CELL]))
def test_a_broken_timed_path_is_not_correct(fault):
    undo = []

    def tamper(job):
        faults_higgs_gbt.FAULTS[CELL][fault](job)
        undo.append(getattr(job, "_undo", lambda: None))

    try:
        line = run_cell(tamper=tamper)
    finally:
        for u in undo:
            u()
    assert line["correct"] is False, line["checks"]
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    if fault == "altered_leaves":
        assert failing == {"leaf_rel"}
    else:
        assert failing & {"node_rows_diff", "split_regret"}, failing


def test_a_fit_that_leaves_the_device_entry_is_an_error():
    """The job raises where ``tree.fit_device`` does not move once a job
    (or a fallback counter does), so a tree fit that bins on the host
    fails fast."""
    from sparkdq4ml_tpu.utils.profiling import counters

    undo = []

    def tamper(job):
        sql = job.spark.sql

        def counted(query):
            counters.increment("pipeline.fallback")
            return sql(query)

        job.spark.sql = counted
        undo.append(lambda: (setattr(job.spark, "sql", sql),
                             job.spark.stop()))

    try:
        with pytest.raises(RuntimeError, match="degraded path"):
            run_cell(tamper=tamper)
    finally:
        for u in undo:
            u()
