"""Median over the profiled jobs of the summed ``sql.parse`` and
``sql.optimize`` spans of the job's statements (host clock, read from
inside): what a statement costs the host before anything executes."""

from benchmarks import program_spans


def read(run):
    return program_spans.per_job_ms(run, ("sql.parse", "sql.optimize"))
