"""The statement's share of the HBM roofline: the least bytes it must read
(every row of the eight columns it names, once: the job file's
``q18_least_bytes``) over the peak bandwidth, divided by the time the
device was busy inside the ``q18`` stage (device trace, median per traced
job). It reads the same work whatever implements the subquery and the
joins — grouped reduction, HAVING, semi join, joins, the small GROUP BY
and the sort together — and cannot pass 100 %: no implementation reads
less."""


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    if not trace or not peaks or not run["jobs"]:
        return None
    busy = [s for s in trace["span_device_s"].get("q18", []) if s > 0.0]
    if not busy:
        return None
    least = run["job_mod"].q18_least_bytes(run["cfg"], run["cfg_mod"],
                                           run["rows"])
    return 100.0 * (least / peaks["hbm_bytes_per_s"]) / run["median"](busy)
