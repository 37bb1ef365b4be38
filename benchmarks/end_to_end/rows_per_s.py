"""Input rows of every job that completed in the window over the whole
window (host clock)."""


def read(run):
    return run["rows_in"] * len(run["jobs"]) / run["window_s"]
