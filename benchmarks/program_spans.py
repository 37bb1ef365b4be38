"""The program's own spans and counters, read in-process after the window.

The program's tracer (``sparkdq4ml_tpu.utils.observability.TRACER``) records
by itself while a jax profiler session is active, so in a traced run
(``--trace 1``) it holds the spans of the profiled jobs — the first
``run["trace"]["jobs"]`` of the window: the profiler starts with the window
and stops between two jobs, so none is from warm-up and none is cut. A
program without such a tracer (the parent of the PR that brought it) holds
no span, and every reader here then returns None: the metric is left out of
the line.

Two steps, like ``trace_reduce``: ``recorded`` turns the tracer's spans into
plain dicts, and the rest is arithmetic on those, pinned on hand-built lists
by ``tests/test_program_spans.py``.

A span's **self time** is its duration less the part of its interval that
its child spans cover (the union of their intervals, clipped to the parent).
"""


def recorded():
    """[{"name", "sid", "parent", "start_s", "dur_s"}] of every finished
    span the program's tracer holds, ``start_s`` on ``time.perf_counter``'s
    clock (the harness's); [] where the program records none."""
    try:
        from sparkdq4ml_tpu.utils.observability import TRACER

        spans = TRACER.spans()
    except Exception:
        return []
    out = []
    for s in spans:
        start = getattr(s, "start_s", None)
        if s.dur_us is None or start is None:
            continue
        out.append({"name": s.name, "sid": s.sid, "parent": s.parent_id,
                    "start_s": float(start), "dur_s": s.dur_us * 1e-6})
    return out


def covered(intervals, lo, hi):
    """Seconds of [lo, hi] that the union of ``intervals`` covers."""
    from benchmarks import trace_reduce

    return trace_reduce.clipped(trace_reduce.union(intervals), lo, hi)


def with_self_time(spans):
    """The same dicts, each with ``self_s``."""
    children = {}
    for s in spans:
        children.setdefault(s["parent"], []).append(s)
    out = []
    for s in spans:
        lo, hi = s["start_s"], s["start_s"] + s["dur_s"]
        inside = covered([(c["start_s"], c["start_s"] + c["dur_s"])
                          for c in children.get(s["sid"], [])], lo, hi)
        out.append({**s, "self_s": s["dur_s"] - inside})
    return out


def by_name(spans):
    """{name: [span, ...]} in start order, each span with ``self_s``."""
    out = {}
    for s in sorted(with_self_time(spans), key=lambda s: s["start_s"]):
        out.setdefault(s["name"], []).append(s)
    return out


def spans_of(run):
    """The spans of the run's profiled jobs, or [] where the run was not
    traced on a device (no reduced trace says which jobs were profiled)."""
    if not run.get("trace"):
        return []
    if "program_spans" not in run:      # read once per run
        run["program_spans"] = recorded()
    return run["program_spans"]


def median_ms(run, name, key="dur_s"):
    """Median over every occurrence of span ``name``, in ms; None where
    there is none."""
    found = by_name(spans_of(run)).get(name)
    if not found:
        return None
    return 1e3 * run["median"]([s[key] for s in found])


def per_job_ms(run, names):
    """Median over the profiled jobs of the summed duration of the spans
    named in ``names`` that started inside the job, in ms; None where no
    job holds one."""
    spans = [s for s in spans_of(run) if s["name"] in names]
    sums = []
    for job in run["jobs"]:
        inside = [s["dur_s"] for s in spans
                  if job["submit"] <= s["start_s"] <= job["done"]]
        if inside:
            sums.append(sum(inside))
    return 1e3 * run["median"](sums) if sums else None


def counter_per_job(run, name):
    """Median over the window's jobs of how far counter ``name`` moved in a
    job; None where the program has no such counter (it never moved)."""
    if not run.get("trace"):
        return None
    moved = [j["counters"][name] for j in run["jobs"]
             if name in j.get("counters", {})]
    return run["median"](moved) if moved else None
