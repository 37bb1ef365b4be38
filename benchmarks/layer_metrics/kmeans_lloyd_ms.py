"""Median over the profiled fits of the ``fit.solve`` spans that are
children of a ``fit.kmeans`` span (host clock, read from inside: Lloyd's
loop and the final cost's pass dispatched as one program, to the one read
of its history, sizes and cost — so it is the device's time for them).
None where the program records no such span."""

from benchmarks import program_spans


def read(run):
    spans = program_spans.spans_of(run)
    fits = {s["sid"] for s in spans if s["name"] == "fit.kmeans"}
    solves = [s["dur_s"] for s in spans
              if s["name"] == "fit.solve" and s["parent"] in fits]
    return 1e3 * run["median"](solves) if solves else None
