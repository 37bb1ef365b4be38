"""Blocking device->host reads a job makes: the program's counter
``host.reads``, median per job of the window. A count: it repeats exactly
from job to job."""

from benchmarks import program_spans


def read(run):
    return program_spans.counter_per_job(run, "host.reads")
