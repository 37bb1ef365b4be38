"""``host_wait_ms`` and ``host_active_ms``: a job split at its blocking
device->host reads (tier-1).

The arithmetic of ``benchmarks/host_split.py`` on hand-built span lists,
and each cell of ``BENCHMARK.json`` run once at 20,000 rows under the
tracer's flag (``tests/test_benchmark_cells.py`` runs the cells untraced,
so the readers see no span there) with a ``run`` built by hand around it.
The cells are read from ``BENCHMARK.json``, so a later cell is covered the
day it lands. Nothing here asserts a time: only that the parts add up and
that the spans and the counters count the same reads.
"""

import os
import statistics
import sys
import time

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

from benchmarks import harness, host_split  # noqa: E402

ROWS = 20_000
BENCH = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
BENCH_ROOT = os.path.join(REPO, "benchmarks")


def span(name, sid, parent, start, dur):
    return {"name": name, "sid": sid, "parent": parent, "start_s": start,
            "dur_s": dur}


def read(sid, parent, start, dur):
    return span("host.read", sid, parent, start, dur)


def metric(name):
    return harness.load_module("layer_metrics", name, BENCH_ROOT)


def hand_run(spans, jobs, traced=True):
    return {"trace": {"jobs": len(jobs)} if traced else None, "jobs": jobs,
            "median": statistics.median, "program_spans": spans}


def job(submit, done):
    return {"submit": submit, "done": done, "counters": {}}


# ---------------------------------------------------------------------------
# the arithmetic, on hand-built lists
# ---------------------------------------------------------------------------

# job 0, [0, 1]: a count holding one read, a fit whose solve holds a read
# that holds another (a nested read counts once), and a read of its own
JOB0 = [
    span("frame.count", 1, None, 0.05, 0.10),
    read(2, 1, 0.06, 0.08),
    span("fit.solve", 3, None, 0.20, 0.50),
    read(4, 3, 0.30, 0.40),
    read(5, 4, 0.35, 0.10),
    read(6, None, 0.80, 0.10),
]
# job 1, [1, 2]: spans and no read; job 2, [2, 3]: nothing recorded (the
# profiler had stopped); job 3, [3, 4]: a read that ends after ``done``
JOB1 = [span("sql.query", 10, None, 1.10, 0.30)]
JOB3 = [span("frame.to_pydict", 20, None, 3.60, 0.50),
        read(21, 20, 3.70, 0.40)]
JOBS = [job(0.0, 1.0), job(1.0, 2.0), job(2.0, 3.0), job(3.0, 4.0)]


def test_a_nested_read_counts_once():
    (only,) = host_split.split_jobs(JOB0, JOBS[:1])
    assert only["wait_s"] == pytest.approx(0.08 + 0.40 + 0.10)
    assert only["active_s"] == pytest.approx(1.0 - 0.58)
    assert [r["sid"] for r in only["reads"]] == [2, 4, 5, 6]


def test_a_job_with_spans_and_no_read_is_all_active():
    split = host_split.split_jobs(JOB0 + JOB1, JOBS[:2])
    assert [j["wait_s"] for j in split] == [pytest.approx(0.58), 0.0]
    assert split[1]["active_s"] == pytest.approx(1.0)


def test_a_job_with_no_span_was_not_profiled_and_is_left_out():
    split = host_split.split_jobs(JOB0 + JOB1, JOBS[:3])
    assert [j["submit"] for j in split] == [0.0, 1.0]


def test_a_read_that_straddles_done_is_clipped_to_the_job():
    split = host_split.split_jobs(JOB3, JOBS)
    (last,) = split
    assert last["wait_s"] == pytest.approx(0.30)        # [3.7, 4.0]
    assert last["active_s"] == pytest.approx(0.70)
    assert last["wait_s"] + last["active_s"] == pytest.approx(last["job_s"])


def test_no_read_anywhere_reads_none():
    # the parent of the PR that brought the span records none
    assert host_split.split_jobs(JOB1, JOBS) is None
    assert host_split.split_jobs([], JOBS) is None
    run = hand_run(JOB1, JOBS)
    assert metric("host_wait_ms").read(run) is None
    assert metric("host_active_ms").read(run) is None
    # ... and an untraced run is not looked at
    run = hand_run(JOB0, JOBS, traced=False)
    assert metric("host_wait_ms").read(run) is None


def test_the_metrics_are_medians_over_the_profiled_jobs(capsys):
    run = hand_run(JOB0 + JOB1 + JOB3, JOBS)
    # wait 0.58, 0.0, 0.30 and active 0.42, 1.0, 0.70
    assert metric("host_wait_ms").read(run) == pytest.approx(300.0)
    assert metric("host_active_ms").read(run) == pytest.approx(700.0)
    err = capsys.readouterr().err
    assert err.count("[host]") == 1                     # logged once
    assert "3 profiled jobs" in err


def test_self_time_leaves_the_reads_out_and_the_report_names_sites():
    split = host_split.split_jobs(JOB0, JOBS[:1])
    selfs = host_split.self_time_by_name(JOB0, split)
    assert selfs == {"frame.count": pytest.approx(0.02),
                     "fit.solve": pytest.approx(0.10)}
    sites = {2: "frame.count", 4: "fit.result", 5: "fit.result",
             6: "frame.to_pydict"}
    by_site = host_split.wait_by_site(split, sites)
    assert by_site == {"frame.count": [pytest.approx(0.08)],
                       "fit.result": [pytest.approx(0.50)],
                       "frame.to_pydict": [pytest.approx(0.10)]}
    line = host_split.report(split, JOB0, sites, statistics.median)
    assert line.startswith("[host] 1 profiled jobs: job 1000.000 ms = wait "
                           "580.000 + active 420.000; 4 `host.read` spans a job")
    assert "fit.result 500.000, frame.to_pydict 100.000" in line
    assert "fit.solve 100.000, frame.count 20.000" in line


# ---------------------------------------------------------------------------
# every cell's job, once, under the tracer's flag
# ---------------------------------------------------------------------------


@pytest.fixture
def session():
    """A session a cell, as ``harness.execute`` makes one a run."""
    import sparkdq4ml_tpu as dq

    spark = (dq.TpuSession.builder().app_name("host_split")
             .master("local[*]").get_or_create())
    yield spark
    spark.stop()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_splits_into_wait_and_active(cell, session):
    from sparkdq4ml_tpu.utils import observability as obs
    from sparkdq4ml_tpu.utils.profiling import counters

    spec = harness.load_cell(cell, REPO)
    declared = {m["name"] for m in spec["per_layer"]}
    # the two metrics list the cells the benchmark had when they came
    # (PR 37); a cell a later ``model_config`` PR adds is split all the
    # same — the readers are loaded by name below — and is appended to
    # the lists by a ``benchmark`` PR (``hibench_kmeans``: PERF.md 7)
    assert {"host_wait_ms", "host_active_ms"} <= declared \
        or cell == "hibench_kmeans"
    table = spec["cfg_mod"].make_table(spec["cfg"], 7, ROWS)
    work = spec["job_mod"].Job(session, spec["cfg"], spec["cfg_mod"],
                               spec["traffic"]["params"], table)
    try:
        work.run(harness.Stages(False))                 # compiles
        obs.disable()
        obs.reset()
        before = counters.snapshot()
        obs.enable()
        try:
            submit = time.perf_counter()
            work.run(harness.Stages(True))
            done = time.perf_counter()
        finally:
            obs.disable()
        moved = harness.counter_delta(counters.snapshot(), before)
        run = {"jobs": [{"submit": submit, "done": done, "counters": moved}],
               "trace": {"jobs": 1}, "median": statistics.median}
        wait = metric("host_wait_ms").read(run)
        active = metric("host_active_ms").read(run)
        reads = [s for s in obs.TRACER.spans() if s.name == "host.read"]
    finally:
        work.close()
        obs.reset()
    assert wait is not None and active is not None
    assert wait > 0.0 and active > 0.0
    assert wait + active == pytest.approx(1e3 * (done - submit))
    assert all(s.attrs["site"] and s.parent_id is not None for s in reads)
    # a span a counted read, and each says where and how much (a wait
    # that brings nothing to the host — the tree fit's binning — is timed
    # like a read, carries no ``bytes`` and is counted nowhere)
    reads = [s for s in reads if "bytes" in s.attrs]
    assert moved["host.reads"] == len(reads)
    assert moved["host.read_bytes"] == sum(s.attrs["bytes"] for s in reads)
    # what the benchmark reports beside them reads the same counters
    if "host_reads" in declared:
        assert metric("host_reads").read(run) == len(reads)
