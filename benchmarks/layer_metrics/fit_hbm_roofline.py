"""The fit's share of the HBM roofline: the least bytes its algorithm reads
(the job file's ``fit_least_bytes``) over the peak bandwidth, divided by
the time the device was busy inside the ``fit`` spans (device trace, median
per traced job). Bound by bandwidth, not by FLOPs: a (d+2)-wide f32 Gramian
does (d+2)/2 FLOP per byte, far under the chip's ridge."""


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    if not trace or not peaks or not run["jobs"]:
        return None
    busy = [s for s in trace["span_device_s"].get("fit", []) if s > 0.0]
    if not busy:
        return None
    job = run["jobs"][0]
    least = run["job_mod"].fit_least_bytes(run["cfg"], job["result"],
                                           job["counters"])
    if least <= 0:
        return None
    return 100.0 * (least / peaks["hbm_bytes_per_s"]) / run["median"](busy)
