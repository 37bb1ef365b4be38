"""The TPC-H Q18 cell's own tests, beside ``test_tpch_q3.py``. Run by hand,
not part of tier-1 (``tests/test_sql_in_semi_join.py`` and
``tests/test_benchmark_cells.py`` are):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

On the CPU at 20,000 lineitem rows (5,000 orders, 500 customers). At that
size the published threshold keeps no order (an order passes only with
seven lines summing over 300: about 0.2 of 5,000), so the control and the
faults are read with the threshold lowered to 220, which keeps about a
hundred; no number from them is a device metric.
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
from benchmarks.tools import faults_tpch_q18  # noqa: E402

ROWS = 20_000
CELL = "tpch_q18_volume"
LOW_QUANTITY = 220


def run_cell(seed=7, trace=0, tamper=None, tmp=None):
    return harness.execute(CELL, seed, 0.5, trace, REPO, require_tpu=False,
                           rows=ROWS, scratch=tmp, tamper=tamper)


@pytest.fixture
def low_threshold(monkeypatch):
    """The cell's traffic with the HAVING's threshold at ``LOW_QUANTITY``."""
    load = harness.load_cell

    def load_cell(workload, repo_root):
        spec = load(workload, repo_root)
        spec["traffic"]["params"]["quantity"] = LOW_QUANTITY
        return spec

    monkeypatch.setattr(harness, "load_cell", load_cell)


@pytest.fixture(scope="module")
def spec():
    return harness.load_cell(CELL, REPO)


def test_cell_configuration_and_metrics_are_found_by_name(spec):
    cfg, mod = spec["cfg"], spec["cfg_mod"]
    assert spec["cell"]["config"] == "tpch-q18-volume"
    assert spec["cell"]["chips"] == 1
    assert spec["traffic"]["job"] == "tpch_q18"
    assert spec["traffic"]["params"] == {"quantity": 300, "limit": 100}
    assert mod.sizes(cfg) == (150_000 * 40, 1_500_000 * 40, 6_001_215 * 40)
    assert mod.sizes(cfg, ROWS) == (500, 5_000, 20_000)
    assert mod.table_bytes(cfg) == 4 * (2 * 6_000_000 + 4 * 60_000_000
                                        + 2 * 240_048_600)
    names = {m["name"] for m in spec["per_layer"]}
    assert {"in_subquery_ms", "q18_hbm_roofline", "join_ms", "grouped_ms",
            "host_wait_ms", "host_active_ms", "job_hbm_roofline",
            "device_idle_share", "setup_after_claim_s"} <= names
    assert not names & {"fit_ms", "dq_sql_ms", "q3_hbm_roofline"}


def test_job_agrees_with_its_reference():
    line = run_cell(seed=2_600_000_011)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["checks"]) == {"rows_diff", "sum_qty_diff",
                                   "jobs_failed", "degraded_paths"}
    assert set(line["metrics"]) == {"rows_per_s", "job_p50_ms",
                                    "job_p95_ms", "setup_s"}


def test_job_agrees_with_its_reference_where_orders_qualify(low_threshold):
    line = run_cell(seed=2_600_000_029)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0


def test_traced_run_reports_what_it_can_read_on_the_cpu(tmp_path):
    line = run_cell(trace=1, tmp=str(tmp_path))
    assert line["correct"] is True, line["checks"]
    # no TPU plane on the CPU: the readers of the trace and of the peaks
    # find nothing; the profiled jobs' spans are not read without a trace
    assert set(line["metrics"]) == {"setup_after_claim_s"}


def test_generated_tables_follow_the_published_rules(spec):
    import jax

    cfg, mod = spec["cfg"], spec["cfg_mod"]
    host = jax.device_get(mod.make_table(cfg, 5, 200_000))
    cust, orders, lines = host["customer"], host["orders"], host["lineitem"]
    assert {t: sorted(c) for t, c in host.items()} == {
        t: sorted(c) for t, c in mod.column_names(cfg).items()}
    assert np.array_equal(cust["c_custkey"], np.arange(1, 5_001))
    assert np.array_equal(cust["c_name"], cust["c_custkey"])
    # keys, dates and line counts are tpch-q3-join's, and so are the
    # quantities its prices are made from
    q3 = jax.device_get(mod.Q3.make_table(cfg, 5, 200_000))
    for column in ("o_orderkey", "o_custkey", "o_orderdate"):
        assert np.array_equal(orders[column], q3["orders"][column])
    assert np.array_equal(lines["l_orderkey"], q3["lineitem"]["l_orderkey"])
    quantity = lines["l_quantity"]
    assert np.array_equal(quantity, np.rint(quantity))
    assert quantity.min() == 1 and quantity.max() == 50
    retail = q3["lineitem"]["l_extendedprice"].astype(np.float64) / quantity
    assert retail.min() >= 900 - 1e-3 and retail.max() <= 2098.99 + 1e-3
    # dbgen's total: the order's lines' charge, tax 0..8 %, discount 0..10 %
    at = np.searchsorted(orders["o_orderkey"], lines["l_orderkey"])
    plain = np.bincount(at, weights=q3["lineitem"]["l_extendedprice"],
                        minlength=50_000)
    ratio = orders["o_totalprice"] / plain
    assert ratio.min() >= 0.9 * 1.0 - 1e-5 and ratio.max() <= 1.08 + 1e-5
    other = jax.device_get(mod.make_table(cfg, 2**31 + 5, 200_000))
    assert not np.array_equal(orders["o_totalprice"],
                              other["orders"]["o_totalprice"])
    want = mod.q18(cfg, host, 300, 100)
    low = mod.q18(cfg, host, LOW_QUANTITY, 100)
    assert want["qualifying"] <= 10 < low["qualifying"]
    assert np.all(np.diff(low["o_totalprice"]) <= 0)
    assert np.all(low["sum_qty"] > LOW_QUANTITY)


def test_bf16_control_fails_a_limit(spec):
    import jax

    cfg, mod, traffic = spec["cfg"], spec["cfg_mod"], spec["traffic"]
    params = dict(traffic["params"], quantity=LOW_QUANTITY)
    for seed in (1, 2, 3):
        host = jax.device_get(mod.make_table(cfg, seed, ROWS))
        want = spec["job_mod"].reference(cfg, mod, params, host)
        low = spec["job_mod"].reference(cfg, mod, params, host,
                                        q=refmath.round_bf16)
        gaps = spec["job_mod"].compare(low, want)
        failed = [k for k, v in gaps.items() if v > traffic["limits"][k]]
        assert failed, gaps


def test_compare_takes_rows_tied_in_both_sort_keys_in_either_order():
    from benchmarks.jobs import tpch_q18

    want = {"c_name": [1, 2, 3], "c_custkey": [1, 2, 3],
            "o_orderkey": [5, 6, 7], "o_orderdate": [9, 9, 9],
            "o_totalprice": [3.0, 2.0, 2.0], "sum_qty": [301, 302, 303],
            "limit": 3, "qualifying": 3}
    swapped = {k: (v[:1] + v[2:0:-1] if isinstance(v, list) else v)
               for k, v in want.items()}
    assert tpch_q18.compare(swapped, want) == {"rows_diff": 0.0,
                                               "sum_qty_diff": 0.0}
    moved = dict(swapped, o_orderdate=[9, 9, 8])
    assert tpch_q18.compare(moved, want)["rows_diff"] == 1.0
    short = {k: (v[:2] if isinstance(v, list) else v)
             for k, v in want.items()}
    assert tpch_q18.compare(short, want)["rows_diff"] == 1.0


@pytest.mark.parametrize("fault",
                         list(faults_tpch_q18.FAULTS[CELL]))
def test_a_broken_timed_path_is_not_correct(fault, low_threshold):
    undo = []

    def tamper(job):
        faults_tpch_q18.FAULTS[CELL][fault](job)
        undo.append(getattr(job, "_undo", lambda: None))

    try:
        line = run_cell(seed=2_600_000_029, tamper=tamper)
    finally:
        for u in undo:
            u()
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["rows_diff"]["value"] > 0


def test_the_first_fault_is_caught_where_no_order_qualifies():
    """``tests/test_benchmark_cells.py`` plants the first fault at the
    published threshold, where the answer is empty."""
    undo = []
    name, fault = next(iter(faults_tpch_q18.FAULTS[CELL].items()))

    def tamper(job):
        fault(job)
        undo.append(getattr(job, "_undo", lambda: None))

    try:
        line = run_cell(tamper=tamper)
    finally:
        for u in undo:
            u()
    assert line["correct"] is False, (name, line["checks"])


def test_a_job_that_inlines_its_subquery_is_an_error():
    """The job raises where ``subquery.literal_in`` (or ``join.host``, or
    a grouped or pipeline fallback counter) moves."""
    from sparkdq4ml_tpu.utils.profiling import counters

    undo = []

    def tamper(job):
        sql = job.spark.sql

        def counted(query):
            counters.increment("subquery.literal_in")
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


def test_a_program_without_the_semi_join_is_refused_at_once(monkeypatch):
    """The parent of the rewrite plans the IN subquery as a filter: the
    job's constructor refuses before any job runs."""
    from sparkdq4ml_tpu.sql import parser

    plan = parser.plan_summary
    monkeypatch.setattr(parser, "plan_summary", lambda q: plan(q).replace(
        "Join[left_semi] <- ", ""))
    with pytest.raises(RuntimeError, match="plans no semi join"):
        run_cell()
