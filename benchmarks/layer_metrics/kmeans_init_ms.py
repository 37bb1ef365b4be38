"""Median over the profiled fits of the program's own ``fit.kmeans.init``
span (host clock, read from inside: it opens after the validation's read
and closes with the k initial centres picked on the host, so it waits for
k-means‖'s passes on the device, reads the candidates and reduces them).
None where the program records no such span."""

from benchmarks import program_spans


def read(run):
    return program_spans.median_ms(run, "fit.kmeans.init")
