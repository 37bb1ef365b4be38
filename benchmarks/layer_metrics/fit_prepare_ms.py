"""Median over the profiled fits of the program's own ``fit.prepare`` span
(host clock, read from inside: ``Estimator.fit`` from its first line to the
packed design handed to the compiled fit — extract, the label/mask pull and
its validation, pack and place)."""

from benchmarks import program_spans


def read(run):
    return program_spans.median_ms(run, "fit.prepare")
