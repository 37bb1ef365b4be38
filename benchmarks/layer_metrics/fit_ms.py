"""Median per job of the benchmark's span around assemble + fit (host
clock; the fit ends in a host read of its coefficients)."""


def read(run):
    spans = [j["spans"]["fit"] for j in run["jobs"] if "fit" in j["spans"]]
    return 1e3 * run["median"](spans) if spans else None
