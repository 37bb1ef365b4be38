"""Job ``tpch_q1``: TPC-H Q1, the Pricing Summary Report, from the resident
``lineitem`` to its result rows on the host.

    q1    the one published statement through ``spark.sql`` (date filter,
          two expressions, GROUP BY the two flags with four sums, three
          averages and a count, ORDER BY the flags)
    read  ``to_pydict()`` of the result rows; the flag codes back to letters

The statement is not split into views: the engine stays free to fuse the
projection into the reduction. Traffic parameters (``params``):
``delta_days``, Q1's substitution parameter DELTA (90 is the validation
value); the date bound is the configuration's end date less DELTA.

A job that answers through a degraded path is an error, not a slow job: if
a grouped or pipeline fallback counter moves during a job, ``run`` raises
(a tree whose grouped reduction cannot hold this table would otherwise
spend minutes per job on the host lowering).
"""

import numpy as np

SPANS = ("q1", "read")
VIEW = "lineitem"
DEGRADED = ("grouped.fallback", "grouped.fault_fallback",
            "pipeline.oom_chunked")
STATEMENT = """
    SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty,
           sum(l_extendedprice) AS sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
           avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price,
           avg(l_discount) AS avg_disc, count(*) AS count_order
    FROM lineitem WHERE l_shipdate <= {bound}
    GROUP BY l_returnflag, l_linestatus
    ORDER BY l_returnflag, l_linestatus"""
NUMBERS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
           "avg_qty", "avg_price", "avg_disc")


class Job:
    def __init__(self, spark, cfg, cfg_mod, params, table):
        self.spark, self.cfg = spark, cfg
        self.frame = spark.create_data_frame(
            {name: table[name] for name in cfg_mod.column_names(cfg)})
        self.frame.create_or_replace_temp_view(VIEW)
        self.query = " ".join(STATEMENT.format(
            bound=cfg_mod.cutoff(cfg, params["delta_days"])).split())

    def rows_in(self):
        return int(self.frame.num_slots)

    def run(self, stage):
        """One job, from the table to the result on the host. ``stage``
        gives each span; its ``sync`` waits for the statement's result
        columns in a traced run only."""
        from sparkdq4ml_tpu.utils.profiling import counters

        before = [counters.get(k) for k in DEGRADED]
        with stage("q1") as sync:
            rows = self.spark.sql(self.query)
            sync(lambda: [rows.mask] + [rows._column_values(c)
                                        for c in rows.columns])
        with stage("read"):
            host = rows.to_pydict()
        moved = [k for k, b in zip(DEGRADED, before) if counters.get(k) != b]
        if moved:
            raise RuntimeError(f"Q1 answered through a degraded path: {moved}")
        codes = self.cfg["codes"]
        result = {
            "l_returnflag": [codes["l_returnflag"][int(c)]
                             for c in host["l_returnflag"]],
            "l_linestatus": [codes["l_linestatus"][int(c)]
                             for c in host["l_linestatus"]],
            "count_order": np.asarray(host["count_order"], np.int64),
        }
        for name in NUMBERS:
            result[name] = np.asarray(host[name], np.float64)
        return result

    def close(self):
        self.spark.catalog.drop(VIEW)
        self.frame = None


def q1_least_bytes(cfg, cfg_mod, rows=None):
    """The least the statement must read from HBM in one job: every row of
    the seven columns it names, once."""
    return cfg_mod.table_bytes(cfg, rows)


def reference(cfg, cfg_mod, params, host, q=None):
    """The job's answers in float64 numpy from the host copy of the table
    (or, with ``q``, in the lower precision that ``q`` rounds to)."""
    return cfg_mod.q1(cfg, host, params["delta_days"], q)


def compare(got, want):
    """{name: gap}: every number held to a limit of the cell. Keys and
    counts exactly; each sum and each average against its own group's
    reference (a group of 0.65 % of the rows is held like the others)."""
    from benchmarks.refmath import mismatches

    same_groups = (list(got["l_returnflag"]) == list(want["l_returnflag"])
                   and list(got["l_linestatus"])
                   == list(want["l_linestatus"]))
    if not same_groups:
        return {"groups_diff": float("inf"), "count_diff": float("inf"),
                "sum_rel": float("inf"), "avg_rel": float("inf")}

    def worst(names):
        gaps = [np.abs(np.asarray(got[n], np.float64) - want[n])
                / np.maximum(np.abs(want[n]), 1e-30) for n in names]
        worst_gap = float(np.max(gaps))
        return worst_gap if np.isfinite(worst_gap) else float("inf")

    return {
        "groups_diff": 0.0,
        "count_diff": mismatches(got["count_order"], want["count_order"]),
        "sum_rel": worst(("sum_qty", "sum_base_price", "sum_disc_price",
                          "sum_charge")),
        "avg_rel": worst(("avg_qty", "avg_price", "avg_disc")),
    }
