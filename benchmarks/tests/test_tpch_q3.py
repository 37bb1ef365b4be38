"""The TPC-H Q3 cell's own tests, beside ``test_tpch_q1.py``. Run by hand,
not part of tier-1 (``tests/test_device_join.py`` and
``tests/test_benchmark_cells.py`` are):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

On the CPU at 20,000 lineitem rows (5,000 orders, 500 customers); no number
from them is a device metric.
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
from benchmarks.tools import faults_tpch_q3  # noqa: E402

ROWS = 20_000
CELL = "tpch_q3_join"


def run_cell(seed=7, trace=0, tamper=None, tmp=None):
    return harness.execute(CELL, seed, 0.5, trace, REPO, require_tpu=False,
                           rows=ROWS, scratch=tmp, tamper=tamper)


@pytest.fixture(scope="module")
def spec():
    return harness.load_cell(CELL, REPO)


def test_cell_configuration_and_metrics_are_found_by_name(spec):
    cfg, mod = spec["cfg"], spec["cfg_mod"]
    assert spec["cell"]["config"] == "tpch-q3-join"
    assert spec["cell"]["chips"] == 1
    assert spec["traffic"]["job"] == "tpch_q3"
    assert mod.sizes(cfg) == (150_000 * 40, 1_500_000 * 40, 6_001_215 * 40)
    assert mod.sizes(cfg, ROWS) == (500, 5_000, 20_000)
    assert mod.table_bytes(cfg) == 4 * (2 * 6_000_000 + 4 * 60_000_000
                                        + 4 * 240_048_600)
    names = {m["name"] for m in spec["per_layer"]}
    assert {"join_ms", "q3_hbm_roofline", "job_hbm_roofline",
            "device_idle_share", "setup_after_claim_s"} <= names
    assert not names & {"fit_ms", "dq_sql_ms", "grouped_ms", "host_reads"}
    assert spec["traffic"]["params"]["tie_rel"] \
        == spec["traffic"]["limits"]["revenue_rel"]


def test_job_agrees_with_its_reference():
    line = run_cell(seed=2_600_000_011)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["checks"]) == {"rows_diff", "revenue_rel", "jobs_failed",
                                   "degraded_paths"}
    assert set(line["metrics"]) == {"rows_per_s", "job_p50_ms",
                                    "job_p95_ms", "setup_s"}


def test_traced_run_reports_what_it_can_read_on_the_cpu(tmp_path):
    line = run_cell(trace=1, tmp=str(tmp_path))
    assert line["correct"] is True, line["checks"]
    # no TPU plane on the CPU: the readers of the trace, of the peaks and
    # of the profiled jobs' spans find nothing and are left out
    assert set(line["metrics"]) == {"setup_after_claim_s"}


def test_generated_tables_follow_the_published_rules(spec):
    import jax

    cfg, mod = spec["cfg"], spec["cfg_mod"]
    host = jax.device_get(mod.make_table(cfg, 5, 200_000))
    cust, orders, lines = host["customer"], host["orders"], host["lineitem"]
    g = cfg["generator"]
    assert {t: sorted(c) for t, c in host.items()} == {
        t: sorted(c) for t, c in mod.column_names(cfg).items()}
    assert (len(cust["c_custkey"]), len(orders["o_orderkey"]),
            len(lines["l_orderkey"])) == (5_000, 50_000, 200_000)
    assert np.array_equal(cust["c_custkey"], np.arange(1, 5_001))
    assert set(np.unique(cust["c_mktsegment"])) == {0, 1, 2, 3, 4}
    i = np.arange(50_000)
    assert np.array_equal(orders["o_orderkey"], (i // 8) * 32 + i % 8 + 1)
    assert not np.any(orders["o_custkey"] % 3 == 0)
    assert orders["o_custkey"].min() >= 1 \
        and orders["o_custkey"].max() <= 5_000
    assert orders["o_orderdate"].min() >= g["order_date_min"]
    assert orders["o_orderdate"].max() <= g["order_date_max"]
    assert not orders["o_shippriority"].any()
    # lineitem in order-key order, 1 to 7 lines an order, every order has
    # lines, and a line ships 1..121 days after ITS order's date
    assert np.all(np.diff(lines["l_orderkey"]) >= 0)
    keys, counts = np.unique(lines["l_orderkey"], return_counts=True)
    assert np.array_equal(keys, orders["o_orderkey"])
    assert counts.min() >= 1 and counts.max() <= 7
    date_of = dict(zip(orders["o_orderkey"].tolist(),
                       orders["o_orderdate"].tolist()))
    days = lines["l_shipdate"] - np.asarray(
        [date_of[k] for k in lines["l_orderkey"].tolist()])
    assert days.min() >= 1 and days.max() <= 121
    cents = np.rint(lines["l_extendedprice"].astype(np.float64) * 100)
    assert cents.min() >= g["retail_cents_min"]
    assert cents.max() <= 50 * g["retail_cents_max"]
    # the same row counts and line-count multiset for every seed
    again = jax.device_get(mod.make_table(cfg, 5, 200_000))
    assert all(np.array_equal(host[t][c], again[t][c])
               for t in host for c in host[t])
    other = jax.device_get(mod.make_table(cfg, 2**31 + 5, 200_000))
    assert not np.array_equal(orders["o_orderdate"],
                              other["orders"]["o_orderdate"])
    assert np.array_equal(
        np.sort(counts),
        np.sort(np.unique(other["lineitem"]["l_orderkey"],
                          return_counts=True)[1]))
    want = mod.q3(cfg, host, 1, 9204, 10)
    assert want["joined_rows"] / 200_000 == pytest.approx(0.005, abs=0.002)
    assert np.all(np.diff(want["revenue"]) <= 0)


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


@pytest.mark.parametrize("fault", sorted(faults_tpch_q3.FAULTS[CELL]))
def test_a_broken_timed_path_is_not_correct(fault):
    undo = []

    def tamper(job):
        faults_tpch_q3.FAULTS[CELL][fault](job)
        undo.append(getattr(job, "_undo", lambda: None))

    try:
        line = run_cell(tamper=tamper)
    finally:
        for u in undo:
            u()
    assert line["correct"] is False, line["checks"]
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    if fault == "altered_revenue":
        assert failing == {"revenue_rel"}
    else:
        assert "rows_diff" in failing


def test_a_job_that_joins_on_the_host_is_an_error():
    """The job raises where ``join.host`` (or a grouped or pipeline fallback
    counter) moves, so a tree whose join pulls its keys fails fast."""
    from sparkdq4ml_tpu.utils.profiling import counters

    undo = []

    def tamper(job):
        sql = job.spark.sql

        def counted(query):
            counters.increment("join.host")
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
