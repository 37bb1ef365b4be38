"""The readers of the program's own spans and counters. Run by hand, with
the benchmark's other tests (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Arithmetic on hand-built span lists and hand-built runs; no number here is a
device metric.
"""

import os
import statistics
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks import harness, program_spans  # noqa: E402

NEW_METRICS = ("fit_prepare_ms", "fit_solve_ms", "sql_plan_ms",
               "host_read_mb", "host_reads")


def span(name, sid, parent, start, dur):
    return {"name": name, "sid": sid, "parent": parent, "start_s": start,
            "dur_s": dur}


def metric(name):
    return harness.load_module("layer_metrics", name, BENCH)


def hand_run(spans, jobs, traced=True):
    return {"trace": {"jobs": len(jobs)} if traced else None, "jobs": jobs,
            "median": statistics.median, "program_spans": spans}


# two jobs: [0, 1] and [1, 2]; in each a fit root holding prepare (with two
# children that overlap by 0.01 s) and solve, and two SQL statements
SPANS = [
    span("fit.logistic_regression", 1, None, 0.10, 0.50),
    span("fit.prepare", 2, 1, 0.11, 0.20),
    span("fit.extract", 3, 2, 0.11, 0.05),
    span("fit.validate", 4, 2, 0.15, 0.10),     # overlaps extract by 0.01
    span("fit.solve", 5, 1, 0.32, 0.25),
    span("sql.parse", 6, None, 0.01, 0.002),
    span("sql.optimize", 7, None, 0.02, 0.001),
    span("sql.parse", 8, None, 0.70, 0.004),
    span("fit.logistic_regression", 11, None, 1.10, 0.70),
    span("fit.prepare", 12, 11, 1.11, 0.30),
    span("fit.solve", 15, 11, 1.42, 0.35),
    span("sql.parse", 16, None, 1.01, 0.003),
    span("sql.optimize", 17, None, 1.02, 0.002),
]
JOBS = [
    {"submit": 0.0, "done": 1.0, "counters": {"host.reads": 5,
                                              "host.read_bytes": 55_000_000}},
    {"submit": 1.0, "done": 2.0, "counters": {"host.reads": 5,
                                              "host.read_bytes": 55_000_000}},
    {"submit": 2.0, "done": 3.0, "counters": {"host.reads": 5,
                                              "host.read_bytes": 55_000_000}},
]


def test_covered_is_the_union_clipped_to_the_parent():
    assert program_spans.covered([], 0.0, 1.0) == 0.0
    assert program_spans.covered([(0.2, 0.4), (0.3, 0.6)], 0.0, 1.0) \
        == pytest.approx(0.4)
    assert program_spans.covered([(-1.0, 0.5), (0.9, 2.0)], 0.0, 1.0) \
        == pytest.approx(0.6)
    assert program_spans.covered([(0.1, 0.2), (0.1, 0.2)], 0.0, 1.0) \
        == pytest.approx(0.1)


def test_self_time_nested_repeated_and_childless():
    named = program_spans.by_name(SPANS)
    roots = named["fit.logistic_regression"]
    assert [r["sid"] for r in roots] == [1, 11]          # start order
    # root 1: 0.50 less prepare 0.20 and solve 0.25
    assert roots[0]["self_s"] == pytest.approx(0.05)
    assert roots[1]["self_s"] == pytest.approx(0.05)
    # prepare 2: children cover [0.11, 0.25] = 0.14 (the overlap once)
    assert named["fit.prepare"][0]["self_s"] == pytest.approx(0.06)
    # a parent with no children keeps all of its time
    assert named["fit.prepare"][1]["self_s"] == pytest.approx(0.30)
    assert named["fit.solve"][0]["self_s"] == pytest.approx(0.25)
    # a child whose parent is not in the list is a root of its own
    orphan = program_spans.by_name([span("x", 9, 99, 0.0, 1.0)])
    assert orphan["x"][0]["self_s"] == pytest.approx(1.0)


def test_median_per_name_and_per_job_sums():
    run = hand_run(SPANS, JOBS)
    assert program_spans.median_ms(run, "fit.prepare") == pytest.approx(250.0)
    assert program_spans.median_ms(run, "fit.solve") == pytest.approx(300.0)
    assert program_spans.median_ms(run, "fit.prepare", "self_s") \
        == pytest.approx(180.0)
    assert program_spans.median_ms(run, "no.such.span") is None
    # job 0: 2 + 1 + 4 ms, job 1: 3 + 2 ms, job 2 holds none: median of two
    assert program_spans.per_job_ms(run, ("sql.parse", "sql.optimize")) \
        == pytest.approx(6.0)
    assert program_spans.per_job_ms(run, ("no.such.span",)) is None


def test_each_new_metric_reads_a_hand_built_run():
    run = hand_run(SPANS, JOBS)
    assert metric("fit_prepare_ms").read(run) == pytest.approx(250.0)
    assert metric("fit_solve_ms").read(run) == pytest.approx(300.0)
    assert metric("sql_plan_ms").read(run) == pytest.approx(6.0)
    assert metric("host_read_mb").read(run) == pytest.approx(55.0)
    assert metric("host_reads").read(run) == 5


@pytest.mark.parametrize("name", NEW_METRICS)
def test_nothing_recorded_reads_none(name):
    # a program without the spans and counters (the parent): no span in
    # memory, no such counter in any job
    bare = [{"submit": 0.0, "done": 1.0, "counters": {"solver.fits": 1}}]
    assert metric(name).read(hand_run([], bare)) is None
    # a run that was not traced on a device: nothing says which jobs were
    # profiled, so nothing is read even where spans exist
    assert metric(name).read(hand_run(SPANS, JOBS, traced=False)) is None


def test_recorded_reads_the_programs_tracer(tmp_path):
    """``recorded`` against the real tracer: off it holds nothing; under a
    profiler session the program records by itself, with a start on the
    harness's clock and parents that the self-time arithmetic can use."""
    import time

    import jax

    from sparkdq4ml_tpu.utils import observability as obs

    obs.TRACER.clear()
    with obs.span("outside", cat="t"):
        pass
    assert program_spans.recorded() == []
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("outer", cat="t"):
            with obs.span("inner", cat="t"):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    t1 = time.perf_counter()
    got = program_spans.by_name(program_spans.recorded())
    obs.TRACER.clear()
    assert set(got) == {"outer", "inner"}
    outer, inner = got["outer"][0], got["inner"][0]
    assert inner["parent"] == outer["sid"]
    assert t0 <= outer["start_s"] <= inner["start_s"] <= t1
    assert inner["dur_s"] >= 0.01
    assert outer["self_s"] == pytest.approx(outer["dur_s"] - inner["dur_s"])


def test_new_metrics_are_declared_for_both_cells():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW_METRICS)
    for name in NEW_METRICS:
        m = declared[name]
        assert m["workloads"] == ["higgs_fit", "catering_dq_lasso"]
        assert m["moves"] == "job_p50_ms" and m["better"] == "lower"
