"""The boosted fit's share of the HBM roofline: the least bytes the fit must
read (the job file's ``gbt_least_bytes``: the table once for the bins, then
a level's 41 B a row and a round's gradient pass) over the peak bandwidth,
divided by the time the device was busy inside the ``fit`` stage (device
trace, median per traced job). It reads the same work whatever implements
the histogram and cannot pass 100 %: no implementation reads less. The
histogram is a contraction on the MXU, so the fit stands far under it."""


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    if not trace or not peaks or not run["jobs"]:
        return None
    busy = [s for s in trace["span_device_s"].get("fit", []) if s > 0.0]
    if not busy:
        return None
    least = run["job_mod"].gbt_least_bytes(run["cfg"], run["cfg_mod"],
                                           run["rows"])
    return 100.0 * (least / peaks["hbm_bytes_per_s"]) / run["median"](busy)
