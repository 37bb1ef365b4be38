"""95th percentile (nearest rank) over all jobs of the window of due ->
result on the host."""


def read(run):
    return 1e3 * run["percentile"](
        [j["done"] - j["due"] for j in run["jobs"]], 0.95)
