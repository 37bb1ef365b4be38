"""The statement's share of the HBM roofline: the least bytes it must read
(every row of the seven columns it names, once: the job file's
``q1_least_bytes``) over the peak bandwidth, divided by the time the device
was busy inside the ``q1`` stage (device trace, median per traced job). It
reads the same work whatever implements it — filter, projection and grouped
reduction together — and cannot pass 100 %: no implementation reads less.
Bound by bandwidth: about 30 FLOP per 28-byte row."""


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    if not trace or not peaks or not run["jobs"]:
        return None
    busy = [s for s in trace["span_device_s"].get("q1", []) if s > 0.0]
    if not busy:
        return None
    least = run["job_mod"].q1_least_bytes(run["cfg"], run["cfg_mod"],
                                          run["rows"])
    return 100.0 * (least / peaks["hbm_bytes_per_s"]) / run["median"](busy)
