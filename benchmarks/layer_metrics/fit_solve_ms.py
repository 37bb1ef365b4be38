"""Median over the profiled fits of the program's own ``fit.solve`` span
(host clock, read from inside: dispatch of the compiled fit to its result
on the host, so it also waits for whatever the device still had queued —
assemble and pack)."""

from benchmarks import program_spans


def read(run):
    return program_spans.median_ms(run, "fit.solve")
