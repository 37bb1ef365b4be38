"""Median over the profiled fits of the tree fit's ``fit.solve`` span (host
clock, read from inside: the boosting rounds dispatched back to back, to
the one read of the packed tree arrays — so it is the device's time for the
rounds). None where the program records no such span."""

from benchmarks import program_spans


def read(run):
    return program_spans.median_ms(run, "fit.solve")
