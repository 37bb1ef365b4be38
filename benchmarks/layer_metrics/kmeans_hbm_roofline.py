"""The k-means fit's share of the HBM roofline: the least bytes the fit
must read (the job file's ``kmeans_least_bytes``: the feature columns once
a seeding round, once for the candidates' weights, once an iteration) over
the peak bandwidth, divided by the time the device was busy inside the
``fit`` stage (device trace, median per traced job). It reads the same
work whatever implements a pass and cannot pass 100 %: no implementation
reads less, and the stage's busy time also holds the assembler, the
validation and the final cost. None where the job file has no such
function (another cell) or nothing was traced."""


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    least_bytes = getattr(run["job_mod"], "kmeans_least_bytes", None)
    if not trace or not peaks or not run["jobs"] or least_bytes is None:
        return None
    busy = [s for s in trace["span_device_s"].get("fit", []) if s > 0.0]
    if not busy:
        return None
    least = least_bytes(run["cfg"], run["cfg_mod"], run["rows"])
    return 100.0 * (least / peaks["hbm_bytes_per_s"]) / run["median"](busy)
