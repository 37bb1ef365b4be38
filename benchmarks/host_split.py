"""A job split into what the host waited for and what the host did, from
the program's ``host.read`` spans.

The program wraps every blocking device->host read in a span ``host.read``
(``sparkdq4ml_tpu.utils.observability.host_reading``): from before the
blocking call to the host copy in hand, with the attributes ``site`` and
``bytes``. Inside one the host does nothing but wait — for the transfer and
for everything the device still had queued before it. Outside one it runs
its own code, and the device runs what was queued, or starves.

    wait_s    seconds of [submit, done] that the union of the job's
              ``host.read`` spans covers (a read nested in a read counts
              once; a read is clipped to the job)
    active_s  done - submit - wait_s

over the **profiled** jobs: those that hold any span of the program at all
(the tracer records while the profiler is on, and the profiler stops
between two jobs). A program that records no ``host.read`` (the parent of
the PR that brought the span) gives None, and the metrics are left out of
the line.

The arithmetic is ``program_spans``' (``spans_of``, ``covered``,
``with_self_time``) on plain dicts, pinned on hand-built lists by
``tests/test_host_split.py``. ``sites`` is the one thing read from the
tracer here: ``program_spans.recorded`` keeps no attribute.
"""

import bisect
import sys

from benchmarks import program_spans

READ_SPAN = "host.read"
TOP_SPANS = 8


def sites():
    """{sid: site} of every ``host.read`` span the program's tracer holds;
    {} where the program records none."""
    try:
        from sparkdq4ml_tpu.utils.observability import TRACER

        return {s.sid: str(s.attrs.get("site", "?"))
                for s in TRACER.spans() if s.name == READ_SPAN}
    except Exception:
        return {}


def interval(span):
    return span["start_s"], span["start_s"] + span["dur_s"]


def inside(span, job):
    return job["submit"] <= span["start_s"] <= job["done"]


def split_jobs(spans, jobs):
    """One {"submit", "done", "job_s", "wait_s", "active_s", "reads"} for
    each of ``jobs`` that holds a span; None where ``spans`` holds no
    read."""
    reads = [s for s in spans if s["name"] == READ_SPAN]
    if not reads:
        return None
    starts = sorted(s["start_s"] for s in spans)
    out = []
    for job in jobs:
        lo, hi = job["submit"], job["done"]
        if bisect.bisect_left(starts, lo) == bisect.bisect_right(starts, hi):
            continue                    # no span inside: not profiled
        mine = [r for r in reads if inside(r, job)]
        wait = program_spans.covered([interval(r) for r in mine], lo, hi)
        out.append({"submit": lo, "done": hi, "job_s": hi - lo,
                    "wait_s": wait, "active_s": (hi - lo) - wait,
                    "reads": mine})
    return out or None


def self_time_by_name(spans, split):
    """{name: seconds of self time, summed over the profiled jobs}, reads
    left out: a span's self time is already without its child reads."""
    total = {}
    for s in program_spans.with_self_time(spans):
        if s["name"] != READ_SPAN and any(inside(s, j) for j in split):
            total[s["name"]] = total.get(s["name"], 0.0) + s["self_s"]
    return total


def wait_by_site(split, site_of):
    """{site: [seconds in each profiled job that read there]}."""
    by_site = {}
    for job in split:
        mine = {}
        for r in job["reads"]:
            site = site_of.get(r["sid"], "?")
            mine[site] = mine.get(site, 0.0) + r["dur_s"]
        for site, seconds in mine.items():
            by_site.setdefault(site, []).append(seconds)
    return by_site


def report(split, spans, site_of, median):
    """The ``[host]`` line: per job, the wait by ``site`` and the self
    time of the spans with most of it."""
    n = len(split)
    waits = sorted(((site, median(v), len(v))
                    for site, v in wait_by_site(split, site_of).items()),
                   key=lambda kv: -kv[1])
    selfs = sorted(self_time_by_name(spans, split).items(),
                   key=lambda kv: -kv[1])[:TOP_SPANS]
    return (
        f"[host] {n} profiled jobs: job "
        f"{1e3 * median([j['job_s'] for j in split]):.3f} ms = wait "
        f"{1e3 * median([j['wait_s'] for j in split]):.3f} + active "
        f"{1e3 * median([j['active_s'] for j in split]):.3f}; "
        f"{median([len(j['reads']) for j in split]):g} `host.read` spans a job; "
        "wait by site (ms, median of the jobs that read there): "
        + ", ".join(f"{site} {1e3 * sec:.3f}"
                    + ("" if k == n else f" [{k} jobs]")
                    for site, sec, k in waits)
        + "; self time a job by span (ms): "
        + ", ".join(f"{name} {1e3 * sec / n:.3f}" for name, sec in selfs))


def of_run(run):
    """``split_jobs`` of the run's profiled jobs (once per run), or None.
    The first reading also logs the ``[host]`` line to stderr."""
    if "host_split" not in run:
        spans = program_spans.spans_of(run)
        run["host_split"] = split_jobs(spans, run["jobs"])
        if run["host_split"]:
            print(report(run["host_split"], spans, sites(), run["median"]),
                  file=sys.stderr, flush=True)
    return run["host_split"]


def median_ms(run, key):
    split = of_run(run)
    if not split:
        return None
    return 1e3 * run["median"]([j[key] for j in split])
