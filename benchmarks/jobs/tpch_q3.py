"""Job ``tpch_q3``: TPC-H Q3, the Shipping Priority query, from the
resident ``customer``, ``orders`` and ``lineitem`` to its ten result rows
on the host.

    q3    the one published statement through ``spark.sql``: a comma FROM
          list whose WHERE clause holds the two join equalities and one
          filter a table, GROUP BY the order's three columns with the
          revenue sum, ORDER BY revenue DESC and the date, LIMIT
    read  ``to_pydict()`` of the result rows

The statement is not split into views and no join is prepared outside the
job: the engine plans and probes both joins inside every job. Traffic
parameters (``params``): ``segment`` (the market segment's name; the
statement's literal is its code in the configuration's ``codes``), ``date``
(days since 1970-01-01; 9204 is 1995-03-15), ``limit``, and ``tie_rel``, the
cell's ``revenue_rel`` limit, which the comparison uses for ties.

A job that answers through a degraded path is an error, not a slow job: if
``join.host`` or a grouped or pipeline fallback counter moves during a job,
``run`` raises (a tree whose join pulls 1.2 GB of keys to the host, or whose
grouped reduction cannot hold the joined rows, would otherwise spend minutes
per job).
"""

import numpy as np

SPANS = ("q3", "read")
VIEWS = ("customer", "orders", "lineitem")
DEGRADED = ("join.host", "grouped.fallback", "grouped.fault_fallback",
            "pipeline.oom_chunked")
STATEMENT = """
    SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
           o_orderdate, o_shippriority
    FROM customer, orders, lineitem
    WHERE c_mktsegment = {segment} AND c_custkey = o_custkey
      AND l_orderkey = o_orderkey AND o_orderdate < {date}
      AND l_shipdate > {date}
    GROUP BY l_orderkey, o_orderdate, o_shippriority
    ORDER BY revenue DESC, o_orderdate LIMIT {limit}"""
KEYS = ("l_orderkey", "o_orderdate", "o_shippriority")


def segment_code(cfg, params):
    return cfg["codes"]["c_mktsegment"].index(params["segment"])


class Job:
    def __init__(self, spark, cfg, cfg_mod, params, table):
        self.spark = spark
        self.frames = {}
        for view, columns in cfg_mod.column_names(cfg).items():
            self.frames[view] = spark.create_data_frame(
                {name: table[view][name] for name in columns})
            self.frames[view].create_or_replace_temp_view(view)
        self.query = " ".join(STATEMENT.format(
            segment=segment_code(cfg, params), date=int(params["date"]),
            limit=int(params["limit"])).split())

    def rows_in(self):
        return sum(int(f.num_slots) for f in self.frames.values())

    def run(self, stage):
        """One job, from the tables to the result on the host. ``stage``
        gives each span; its ``sync`` waits for the statement's result
        columns in a traced run only."""
        from sparkdq4ml_tpu.utils.profiling import counters

        before = [counters.get(k) for k in DEGRADED]
        with stage("q3") as sync:
            rows = self.spark.sql(self.query)
            sync(lambda: [rows.mask] + [rows._column_values(c)
                                        for c in rows.columns])
        with stage("read"):
            host = rows.to_pydict()
        moved = [k for k, b in zip(DEGRADED, before) if counters.get(k) != b]
        if moved:
            raise RuntimeError(f"Q3 answered through a degraded path: {moved}")
        result = {name: np.asarray(host[name], np.int64) for name in KEYS}
        result["revenue"] = np.asarray(host["revenue"], np.float64)
        return result

    def close(self):
        for view in VIEWS:
            self.spark.catalog.drop(view)
        self.frames = {}


def q3_least_bytes(cfg, cfg_mod, rows=None):
    """The least the statement must read from HBM in one job: every row of
    the ten columns it names, once."""
    return cfg_mod.table_bytes(cfg, rows)


def reference(cfg, cfg_mod, params, host, q=None):
    """The job's answers in float64 numpy from the host copies of the
    tables (or, with ``q``, in the lower precision that ``q`` rounds to),
    a few rows past the limit, with the tie tolerance."""
    want = cfg_mod.q3(cfg, host, segment_code(cfg, params),
                      int(params["date"]), int(params["limit"]), q)
    want["tie_rel"] = float(params["tie_rel"])
    return want


def compare(got, want):
    """{name: gap}: ``rows_diff`` counts the result rows whose (l_orderkey,
    o_orderdate, o_shippriority) is not the reference's at that rank —
    exact, except that a row found at another rank of the reference (which
    hands over a few rows past the limit) passes where the two ranks'
    reference revenues lie within ``tie_rel`` of each other: float32 may
    order such neighbours either way. ``revenue_rel`` is the largest
    relative gap of the revenues, each against its own order's reference.
    How often a seed has such a pair among its first ten: in none of the
    windows read at full size did the tolerance come into play — every
    ranking equalled the reference's rank for rank (PERF.md section 2)."""
    limit = int(want["limit"])
    ref = list(zip(*(np.asarray(want[k]).tolist() for k in KEYS)))
    rows = list(zip(*(np.asarray(got[k]).tolist()[:limit] for k in KEYS)))
    if len(rows) != min(limit, len(ref)):
        return {"rows_diff": float("inf"), "revenue_rel": float("inf")}
    revenue = np.asarray(got["revenue"], np.float64)
    diff, worst = 0, 0.0
    for rank, row in enumerate(rows):
        if row not in ref:
            diff += 1
            continue
        at = ref.index(row)
        mine = float(want["revenue"][at])
        if at != rank and abs(mine - float(want["revenue"][rank])) \
                > want["tie_rel"] * abs(mine):
            diff += 1
        gap = abs(float(revenue[rank]) - mine) / max(abs(mine), 1e-30)
        worst = max(worst, gap if np.isfinite(gap) else float("inf"))
    return {"rows_diff": float(diff), "revenue_rel": worst}
