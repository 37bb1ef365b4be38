"""Median over the profiled jobs of the summed ``frame.pipeline.flush``
spans of the job (host clock, read from inside): what the scan's filter and
the projection that feeds the aggregate cost the host — dispatch, not the
device's work, which is asynchronous."""

from benchmarks import program_spans


def read(run):
    return program_spans.per_job_ms(run, ("frame.pipeline.flush",))
