"""Median over the profiled jobs of the seconds of a job that the union of
its ``host.read`` spans covers (host clock, read from inside): how long the
host stood blocked on the chip — on the transfer and on everything the
device still had queued before it. The program wraps every blocking
device->host read in such a span (``observability.host_reading``); a read
nested in a read counts once. Beside ``host_active_ms``: the two add up to
the job."""

from benchmarks import host_split


def read(run):
    return host_split.median_ms(run, "wait_s")
