"""A whole-job metric, not a device reading: the bytes of the job's input
columns, read once, over the peak bandwidth, divided by the median job time
on the host's clock (table to result, host work included). It is the share
of the chip's bandwidth that the whole job reaches, and still bounds a gain
after a kernel has been replaced; this system runs no network, so there is
no ``mfu``."""


def read(run):
    peaks = run["peaks"]
    if not peaks or not run["jobs"]:
        return None
    table = run["cfg_mod"].table_bytes(run["cfg"], run["rows"])
    seconds = run["median"]([j["done"] - j["submit"] for j in run["jobs"]])
    return 100.0 * (table / peaks["hbm_bytes_per_s"]) / seconds
