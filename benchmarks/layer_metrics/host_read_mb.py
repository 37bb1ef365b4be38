"""Megabytes (1e6 B) a job pulls from the device in blocking reads: the
program's counter ``host.read_bytes``, median per job of the window. A
count, not a time: it repeats exactly from job to job."""

from benchmarks import program_spans


def read(run):
    moved = program_spans.counter_per_job(run, "host.read_bytes")
    return None if moved is None else moved / 1e6
