"""Median over the profiled jobs of the job's summed ``frame.grouped.flush``
spans — the statement's GROUP BY and its ORDER BY — (host clock, read from
inside: each from the grouped program's dispatch to the host read of its few
scalars, group count and verdict, so the first waits for the reduction and
for whatever the device still had queued before it). Per job, not per span:
a job holds a 100 ms reduction and a 1 ms four-row sort, and a median over
both kinds would be neither."""

from benchmarks import program_spans


def read(run):
    return program_spans.per_job_ms(run, ("frame.grouped.flush",))
