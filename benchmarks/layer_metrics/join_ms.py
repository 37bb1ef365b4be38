"""Median over the profiled jobs of the job's summed ``frame.join`` spans
(host clock, read from inside: each from the join's entry to its result
frame — on the device path the dispatch of the join's program and the host
read of the result's row count, so it waits for the join and for whatever
the device still had queued before it). Per job, not per span: a job holds
a small join and a large one. None where the program records no such span."""

from benchmarks import program_spans


def read(run):
    return program_spans.per_job_ms(run, ("frame.join",))
