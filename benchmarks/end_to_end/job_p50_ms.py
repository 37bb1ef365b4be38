"""Median over all jobs of the window of due -> result on the host."""


def read(run):
    return 1e3 * run["median"]([j["done"] - j["due"] for j in run["jobs"]])
