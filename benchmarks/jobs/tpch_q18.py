"""Job ``tpch_q18``: TPC-H Q18, the Large Volume Customer query, from the
resident ``customer``, ``orders`` and ``lineitem`` to its result rows on
the host.

    q18   the one published statement through ``spark.sql``: a comma FROM
          list whose WHERE clause holds ``o_orderkey IN (SELECT l_orderkey
          FROM lineitem GROUP BY l_orderkey HAVING sum(l_quantity) > Q)``
          and the two join equalities, GROUP BY the five columns with the
          quantity sum, ORDER BY o_totalprice DESC and the date, LIMIT
    read  ``to_pydict()`` of the result rows

The statement is not split into views and nothing is prepared outside the
job: the engine groups every line, filters the groups, joins the orders
that pass and the other two tables inside every job. Traffic parameters
(``params``): ``quantity`` (the HAVING's threshold) and ``limit``.

The program must plan the IN subquery as a left-semi join (Spark's rewrite):
the constructor raises at once where it does not (the plan of the statement
shows no ``Join[left_semi``), because the literal path would read the
subquery's 2,545 order keys to the host and filter 6e7 orders by an OR of
2,545 equalities. A job that answers through a degraded path is an error,
not a slow job: if ``join.host``, a grouped or pipeline fallback counter or
``subquery.literal_in`` moves during a job, or ``subquery.semi_join`` does
not, ``run`` raises.
"""

import numpy as np

SPANS = ("q18", "read")
VIEWS = ("customer", "orders", "lineitem")
DEGRADED = ("join.host", "grouped.fallback", "grouped.fault_fallback",
            "pipeline.oom_chunked", "subquery.literal_in")
STATEMENT = """
    SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    FROM customer, orders, lineitem
    WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem
                         GROUP BY l_orderkey
                         HAVING sum(l_quantity) > {quantity})
      AND c_custkey = o_custkey AND o_orderkey = l_orderkey
    GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    ORDER BY o_totalprice DESC, o_orderdate LIMIT {limit}"""
KEYS = ("c_name", "c_custkey", "o_orderkey", "o_orderdate")
SUM = "sum(l_quantity)"


class Job:
    def __init__(self, spark, cfg, cfg_mod, params, table):
        from sparkdq4ml_tpu.sql import parser

        self.spark = spark
        self.query = " ".join(STATEMENT.format(
            quantity=int(params["quantity"]),
            limit=int(params["limit"])).split())
        plan = parser.plan_summary(parser.parse(self.query))
        if "Join[left_semi" not in plan:
            raise RuntimeError("this program plans no semi join for an IN "
                               f"subquery (plan: {plan})")
        self.frames = {}
        for view, columns in cfg_mod.column_names(cfg).items():
            self.frames[view] = spark.create_data_frame(
                {name: table[view][name] for name in columns})
            self.frames[view].create_or_replace_temp_view(view)

    def rows_in(self):
        return sum(int(f.num_slots) for f in self.frames.values())

    def run(self, stage):
        """One job, from the tables to the result on the host. ``stage``
        gives each span; its ``sync`` waits for the statement's result
        columns in a traced run only."""
        from sparkdq4ml_tpu.utils.profiling import counters

        watched = DEGRADED + ("subquery.semi_join",)
        before = [counters.get(k) for k in watched]
        with stage("q18") as sync:
            rows = self.spark.sql(self.query)
            sync(lambda: [rows.mask] + [rows._column_values(c)
                                        for c in rows.columns])
        with stage("read"):
            host = rows.to_pydict()
        moved = [k for k, b in zip(watched, before) if counters.get(k) != b]
        if any(k in DEGRADED for k in moved) \
                or "subquery.semi_join" not in moved:
            raise RuntimeError(
                f"Q18 answered through a degraded path: moved {moved}")
        result = {name: np.asarray(host[name], np.int64) for name in KEYS}
        result["o_totalprice"] = np.asarray(host["o_totalprice"], np.float64)
        result["sum_qty"] = np.asarray(host[SUM], np.float64)
        return result

    def close(self):
        for view in VIEWS:
            self.spark.catalog.drop(view)
        self.frames = {}


def q18_least_bytes(cfg, cfg_mod, rows=None):
    """The least the statement must read from HBM in one job: every row of
    the eight columns it names, once."""
    return cfg_mod.table_bytes(cfg, rows)


def reference(cfg, cfg_mod, params, host, q=None):
    """The job's answers in float64 numpy from the host copies of the
    tables (or, with ``q``, in the lower precision that ``q`` rounds to),
    a few rows past the limit."""
    return cfg_mod.q18(cfg, host, float(params["quantity"]),
                       int(params["limit"]), q)


def _rows(answer, n):
    cols = ("c_name", "c_custkey", "o_orderkey", "o_orderdate",
            "o_totalprice", "sum_qty")
    return list(zip(*(np.asarray(answer[k]).tolist()[:n] for k in cols)))


def compare(got, want):
    """{name: gap}: ``rows_diff`` counts the result rows not equal, in all
    six values, to the reference's row at that rank — except that a row
    found at another rank of the reference (which hands over a few rows
    past the limit) passes where the two ranks' reference rows are equal
    in both sort keys (o_totalprice, o_orderdate): such rows may come in
    either order — plus the rows missing or over the reference's count.
    ``sum_qty_diff`` is the largest gap of a row's quantity sum against the
    reference's sum for the same order (integer sums, exact in float32),
    over the rows whose order the reference ranks."""
    limit = int(want["limit"])
    ref = _rows(want, limit + 64)
    rows = _rows(got, limit)
    expect = min(limit, int(want["qualifying"]))
    diff = abs(len(rows) - expect)
    sums = {r[2]: r[5] for r in ref}
    worst = 0.0
    for rank, row in enumerate(rows[:expect]):
        if row[2] in sums:
            worst = max(worst, abs(row[5] - sums[row[2]]))
        if rank < len(ref) and row == ref[rank]:
            continue
        at = ref.index(row) if row in ref else -1
        if at < 0 or rank >= len(ref) \
                or ref[at][3:5] != ref[rank][3:5]:
            diff += 1
    return {"rows_diff": float(diff), "sum_qty_diff": worst}
