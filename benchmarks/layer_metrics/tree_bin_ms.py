"""Median over the profiled fits of the program's own ``fit.tree.bin`` span
(host clock, read from inside: the dispatch of the thresholds-and-bins
program — a sort a feature, 31 compares a value — to its completion on the
device; the span waits for the program). None where the program records no
such span."""

from benchmarks import program_spans


def read(run):
    return program_spans.median_ms(run, "fit.tree.bin")
