"""The TPC-H Q1 cell's own tests, beside ``test_harness.py``. Run by hand,
not part of tier-1:

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
from benchmarks.tools import faults_tpch_q1  # noqa: E402

ROWS = 20_000
CELL = "tpch_q1_sf30"


def run_cell(seed=7, trace=0, tamper=None, tmp=None):
    return harness.execute(CELL, seed, 0.5, trace, REPO, require_tpu=False,
                           rows=ROWS, scratch=tmp, tamper=tamper)


@pytest.fixture(scope="module")
def spec():
    return harness.load_cell(CELL, REPO)


def test_cell_configuration_and_metrics_are_found_by_name(spec):
    assert spec["cell"]["config"] == "tpch-q1-lineitem"
    assert spec["cell"]["chips"] == 1
    assert spec["traffic"]["job"] == "tpch_q1"
    assert spec["cfg"]["rows"] == 6_001_215 * spec["cfg"]["scale_factor"]
    assert spec["cfg_mod"].table_bytes(spec["cfg"]) == 180_036_450 * 28
    names = {m["name"] for m in spec["per_layer"]}
    assert {"grouped_ms", "scan_flush_ms", "q1_hbm_roofline",
            "job_hbm_roofline", "device_idle_share",
            "setup_after_claim_s"} <= names
    assert not names & {"fit_ms", "dq_sql_ms", "host_reads"}
    for name in ("grouped_ms", "scan_flush_ms", "q1_hbm_roofline"):
        assert callable(harness.load_module(
            "layer_metrics", name, spec["root"]).read)


def test_job_agrees_with_its_reference():
    line = run_cell(seed=2_600_000_011)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["checks"]) == {"groups_diff", "count_diff", "sum_rel",
                                   "avg_rel", "jobs_failed",
                                   "degraded_paths"}
    assert set(line["metrics"]) == {"rows_per_s", "job_p50_ms",
                                    "job_p95_ms", "setup_s"}


def test_traced_run_reports_what_it_can_read_on_the_cpu(tmp_path):
    line = run_cell(trace=1, tmp=str(tmp_path))
    assert line["correct"] is True, line["checks"]
    # no TPU plane on the CPU: the readers of the trace, of the peaks and
    # of the profiled jobs' spans find nothing and are left out
    assert set(line["metrics"]) == {"setup_after_claim_s"}


def test_generated_table_follows_the_published_rules(spec):
    import jax

    cfg, mod = spec["cfg"], spec["cfg_mod"]
    host = jax.device_get(mod.make_table(cfg, 5, 200_000))
    g = cfg["generator"]
    assert set(host) == set(mod.column_names(cfg))
    assert host["l_quantity"].min() == 1 and host["l_quantity"].max() == 50
    cents = np.rint(host["l_extendedprice"].astype(np.float64) * 100)
    retail = cents / host["l_quantity"]
    assert retail.min() >= g["retail_cents_min"]
    assert retail.max() <= g["retail_cents_max"]
    assert set(np.unique(host["l_returnflag"])) == {0, 1, 2}
    open_ = host["l_shipdate"] > g["current_date"]
    assert np.array_equal(host["l_linestatus"] == 1, open_)
    assert np.all(host["l_returnflag"][open_] == 1)       # open => N
    want = mod.q1(cfg, host, 90)
    assert list(zip(want["l_returnflag"], want["l_linestatus"])) == [
        ("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]
    share = want["count_order"] / 200_000
    assert share == pytest.approx([0.246, 0.0065, 0.487, 0.246], abs=0.01)
    assert share.sum() == pytest.approx(0.986, abs=0.003)
    again = jax.device_get(mod.make_table(cfg, 5, 200_000))
    assert all(np.array_equal(host[k], again[k]) for k in host)
    other = jax.device_get(mod.make_table(cfg, 2**31 + 5, 200_000))
    assert not np.array_equal(host["l_shipdate"], other["l_shipdate"])


def test_bf16_control_fails_a_limit(spec):
    import jax

    cfg, mod, traffic = spec["cfg"], spec["cfg_mod"], spec["traffic"]
    for seed in (1, 2, 3):
        host = jax.device_get(mod.make_table(cfg, seed, ROWS))
        want = spec["job_mod"].reference(cfg, mod, traffic["params"], host)
        low = spec["job_mod"].reference(cfg, mod, traffic["params"], host,
                                        q=refmath.round_bf16)
        gaps = spec["job_mod"].compare(low, want)
        assert gaps["groups_diff"] == 0 and gaps["count_diff"] == 0
        failed = [k for k, v in gaps.items() if v > traffic["limits"][k]]
        assert failed, gaps


@pytest.mark.parametrize("fault", sorted(faults_tpch_q1.FAULTS[CELL]))
def test_a_broken_timed_path_is_not_correct(fault):
    undo = []

    def tamper(job):
        faults_tpch_q1.FAULTS[CELL][fault](job)
        undo.append(getattr(job, "_undo", lambda: None))

    try:
        line = run_cell(tamper=tamper)
    finally:
        for u in undo:
            u()
    assert line["correct"] is False, line["checks"]
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    if fault == "half_reduce":
        assert "count_diff" in failing
    else:
        assert failing <= {"sum_rel", "avg_rel"} and failing


def test_a_job_that_falls_back_is_an_error():
    """The job raises where a degraded-path counter moves, so a tree whose
    grouped reduction cannot hold the table fails fast."""
    from sparkdq4ml_tpu.utils.profiling import counters

    undo = []

    def tamper(job):
        sql = job.spark.sql

        def counted(query):
            counters.increment("grouped.fallback")
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
