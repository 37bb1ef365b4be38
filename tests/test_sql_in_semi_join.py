"""An uncorrelated ``expr IN (SELECT c ...)`` conjunct runs as a left-semi
join against the subquery's frame (Spark's rewrite), and a GROUP BY of one
integer key over a table stored in that key's order reduces its runs with
no sort (``ops/segments.py``'s ordered lowering) — together TPC-H Q18's
plan. Each is held against a plain numpy reference and against the literal
path the rewrite replaces (the same IN under an ``OR FALSE`` stays a list
of literals), bit for bit in keys and sums."""

import numpy as np
import pytest

from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.frame.aggregates import AggExpr
from sparkdq4ml_tpu.sql import parser
from sparkdq4ml_tpu.utils.profiling import counters

Q18 = """
    SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    FROM customer, orders, lineitem
    WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem
                         GROUP BY l_orderkey
                         HAVING sum(l_quantity) > {quantity})
      AND c_custkey = o_custkey AND o_orderkey = l_orderkey
    GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    ORDER BY o_totalprice DESC, o_orderdate LIMIT {limit}"""


def _moved(before, names=("subquery.semi_join", "subquery.literal_in",
                          "join.host", "join.device", "grouped.fallback")):
    return {k: counters.get(k) - before[k] for k in names}


def _snapshot():
    return {k: counters.get(k) for k in (
        "subquery.semi_join", "subquery.literal_in", "join.host",
        "join.device", "grouped.fallback")}


def tpch_tables(session, n_cust, n_orders, seed=18):
    """The three tables at a small scale factor, by dbgen's shapes: sparse
    order keys in key order, 1..7 lines an order, lineitem in order-key
    order, whole quantities 1..50, float32 prices; registered as views."""
    rng = np.random.default_rng(seed)
    index = np.arange(n_orders)
    okey = ((index // 8) * 32 + index % 8 + 1).astype(np.int32)
    counts = rng.integers(1, 8, n_orders)
    tables = {
        "customer": {"c_custkey": np.arange(1, n_cust + 1, dtype=np.int32),
                     "c_name": np.arange(1, n_cust + 1, dtype=np.int32)},
        "orders": {"o_orderkey": okey,
                   "o_custkey": rng.integers(1, n_cust + 1, n_orders)
                   .astype(np.int32),
                   "o_orderdate": rng.integers(8035, 10441, n_orders)
                   .astype(np.int32),
                   "o_totalprice": rng.integers(100_000, 50_000_000, n_orders)
                   .astype(np.float32) / np.float32(100)},
        "lineitem": {"l_orderkey": np.repeat(okey, counts),
                     "l_quantity": rng.integers(1, 51, counts.sum())
                     .astype(np.float32)},
    }
    for name, cols in tables.items():
        session.create_data_frame(cols).create_or_replace_temp_view(name)
    return tables


@pytest.fixture
def tpch(session):
    """The three tables at a tiny scale factor."""
    tables = tpch_tables(session, 300, 3_000)
    yield tables
    for name in tables:
        session.catalog.drop(name)


def q18_reference(t, quantity, limit):
    """Q18 in plain numpy: per order the sum of its lines' quantities,
    HAVING, the customer of each order that passes, ranked."""
    orders, lines = t["orders"], t["lineitem"]
    at = np.searchsorted(orders["o_orderkey"], lines["l_orderkey"])
    qty = np.bincount(at, weights=lines["l_quantity"].astype(np.float64),
                      minlength=orders["o_orderkey"].size)
    chosen = np.nonzero(qty > quantity)[0]
    price = orders["o_totalprice"][chosen]
    date = orders["o_orderdate"][chosen]
    rank = chosen[np.lexsort((date, -price.astype(np.float64)))][:limit]
    return {"c_name": orders["o_custkey"][rank],
            "c_custkey": orders["o_custkey"][rank],
            "o_orderkey": orders["o_orderkey"][rank],
            "o_orderdate": orders["o_orderdate"][rank],
            "o_totalprice": orders["o_totalprice"][rank],
            "sum(l_quantity)": qty[rank]}


@pytest.mark.parametrize("quantity", [300, 220, 150])
def test_q18_through_sql_equals_numpy(session, tpch, quantity):
    """At the published threshold a tiny table keeps (nearly) no order; the
    lower thresholds keep dozens and hundreds."""
    before = _snapshot()
    got = session.sql(Q18.format(quantity=quantity, limit=100)).to_pydict()
    want = q18_reference(tpch, quantity, 100)
    assert _moved(before) == {"subquery.semi_join": 1,
                              "subquery.literal_in": 0, "join.host": 0,
                              "join.device": 3, "grouped.fallback": 0}
    assert list(got) == list(want)
    for name in want:
        assert np.array_equal(np.asarray(got[name]), want[name]), name
    if quantity < 300:
        assert len(want["o_orderkey"]) >= 24


def test_q18_joins_a_few_thousand_orders_by_lookups(session):
    """At 270,000 customers and orders (eight rows of 2^15 and more on
    every probe side) the published threshold keeps a few dozen orders:
    the semi join, the customer join and the ``lineitem`` join each
    search those keys into a probe side stored in key order."""
    from sparkdq4ml_tpu.ops import joins

    tables = tpch_tables(session, 270_000, 270_000, seed=44)
    try:
        before = counters.snapshot()
        got = session.sql(Q18.format(quantity=300, limit=100)).to_pydict()
        now = counters.snapshot()
    finally:
        for name in tables:
            session.catalog.drop(name)
    delta = {k: now[k] - before.get(k, 0) for k in now
             if now[k] != before.get(k, 0)}
    assert delta["join.lookup"] == 3 and delta["join.device"] == 3
    assert "join.merge" not in delta and "join.merge_miss" not in delta
    assert "join.host" not in delta and "grouped.fallback" not in delta
    want = q18_reference(tables, 300, 100)
    assert 8 <= len(want["o_orderkey"]) <= 100
    assert joins._takes_lookup("left_semi", 1, len(want["o_orderkey"]),
                               270_000)
    assert list(got) == list(want)
    for name in want:
        assert np.array_equal(np.asarray(got[name]), want[name]), name


def test_q18_plan_shows_the_semi_join_and_reads_no_subquery_value(
        session, tpch):
    query = Q18.format(quantity=150, limit=100)
    assert "Join[left_semi] <- " in parser.plan_summary(parser.parse(query))
    text = "\n".join(session.sql("EXPLAIN " + query).to_pydict()["plan"])
    assert "Join[left_semi]" in text
    qualifying = int(np.sum(np.bincount(
        np.searchsorted(tpch["orders"]["o_orderkey"],
                        tpch["lineitem"]["l_orderkey"]),
        weights=tpch["lineitem"]["l_quantity"]) > 150))
    assert qualifying > 100
    from sparkdq4ml_tpu.utils import observability as obs

    with obs.query_stats() as qs:
        session.sql(query).count()
    (sub,) = [s for s in qs.spans if s.name == "sql.subquery.in"]
    assert sub.attrs["how"] == "left_semi"
    assert sub.attrs["build_rows"] == qualifying
    inside, parents = [], {sub.sid}
    for s in sorted(qs.spans, key=lambda s: s.start_s):
        if s.parent_id in parents:
            parents.add(s.sid)
            inside.append(s)
    reads = [s for s in inside if s.name == "host.read"]
    # counts and verdicts only (the groups', the build side's, the
    # join's): the qualifying keys, 4 bytes each, would be more
    assert reads and all(s.attrs["site"] != "frame.to_pydict"
                         for s in reads)
    assert sum(s.attrs.get("bytes", 0) for s in reads) < 4 * qualifying


def test_optimizer_moves_the_semi_join_into_the_scan_it_filters(tpch):
    from sparkdq4ml_tpu.sql import optimizer
    from sparkdq4ml_tpu.sql.catalog import default_catalog

    q, rewrites = optimizer.optimize(
        parser.parse(Q18.format(quantity=200, limit=10)), default_catalog())
    pushed = [str(r) for r in rewrites if r.rule == "pushdown"]
    assert any("IN" in r or "o_orderkey" in r for r in pushed), rewrites
    tree = parser.plan_tree(q).render()
    # the orders scan carries the semi join; the outer chain has none
    outer = parser.plan_summary(q)
    assert "left_semi" not in outer and "Join[left_semi]" in tree


def _frames(session, left, right):
    session.create_data_frame(left).create_or_replace_temp_view("l")
    session.create_data_frame(right).create_or_replace_temp_view("r")


def _both(session, where, select="SELECT * FROM l"):
    """The statement with the IN as a conjunct (the semi join) and under
    ``OR FALSE`` (the literal list): both answers and the counters."""
    before = _snapshot()
    semi = session.sql(f"{select} WHERE {where}").to_pydict()
    moved = _moved(before)
    literal = session.sql(f"{select} WHERE ({where}) OR 1 = 0").to_pydict()
    return semi, literal, moved


CASES = {
    "duplicates": ({"k": np.arange(40, dtype=np.int32) % 13,
                    "v": np.arange(40, dtype=np.float32)},
                   {"x": np.array([3, 3, 5, 12, 12, 12, 99], np.int32)},
                   "k IN (SELECT x FROM r)"),
    "empty": ({"k": np.arange(20, dtype=np.int32),
               "v": np.ones(20, np.float32)},
              {"x": np.arange(5, dtype=np.int32)},
              "k IN (SELECT x FROM r WHERE x > 100)"),
    "nulls": ({"k": np.array([1.0, np.nan, 2.0, 3.0, np.nan, 4.0],
                             np.float32),
               "v": np.arange(6, dtype=np.float32)},
              {"x": np.array([np.nan, 2.0, 4.0, np.nan], np.float32)},
              "k IN (SELECT x FROM r)"),
    "expression": ({"k": np.arange(30, dtype=np.int32),
                    "v": np.arange(30, dtype=np.float32)},
                   {"x": np.array([2, 7, 11], np.int32)},
                   "k + 1 IN (SELECT x FROM r)"),
    "same_name": ({"k": np.arange(30, dtype=np.int32) % 7,
                   "v": np.arange(30, dtype=np.float32)},
                  {"k": np.array([0, 6, 6], np.int32)},
                  "k IN (SELECT k FROM r)"),
    "having": ({"k": np.arange(60, dtype=np.int32) % 9,
                "v": np.arange(60, dtype=np.float32)},
               {"x": np.arange(200, dtype=np.int32) % 17,
                "w": np.arange(200, dtype=np.float32)},
               "k IN (SELECT x FROM r GROUP BY x HAVING sum(w) > 1200)"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_semi_join_equals_the_literal_path(session, case):
    left, right, where = CASES[case]
    _frames(session, left, right)
    semi, literal, moved = _both(session, where)
    assert moved["subquery.semi_join"] == 1
    assert moved["subquery.literal_in"] == 0 and moved["join.host"] == 0
    assert list(semi) == list(literal)
    for name in semi:
        assert np.array_equal(np.asarray(semi[name]),
                              np.asarray(literal[name]), equal_nan=True)
    if case == "nulls":
        assert np.asarray(semi["v"]).tolist() == [2.0, 5.0]
    if case == "empty":
        assert len(semi["k"]) == 0


def test_not_in_keeps_the_literal_path(session):
    left, right, _ = CASES["nulls"]
    _frames(session, left, right)
    before = _snapshot()
    got = session.sql("SELECT * FROM l WHERE k NOT IN (SELECT x FROM r)") \
        .to_pydict()
    assert _moved(before)["subquery.semi_join"] == 0
    assert _moved(before)["subquery.literal_in"] == 1
    assert len(got["k"]) == 0            # a NULL in the set: never TRUE
    _frames(session, {"k": np.arange(6, dtype=np.int32)},
            {"x": np.array([1, 4], np.int32)})
    got = session.sql("SELECT k FROM l WHERE k NOT IN (SELECT x FROM r)") \
        .to_pydict()
    assert np.asarray(got["k"]).tolist() == [0, 2, 3, 5]
    assert "left_semi" not in parser.plan_summary(parser.parse(
        "SELECT k FROM l WHERE k NOT IN (SELECT x FROM r)"))


def test_an_integer_against_a_float_keeps_the_literal_path(session):
    _frames(session, {"k": np.arange(10, dtype=np.int32)},
            {"x": np.array([2.0, 2.5, 7.0], np.float32)})
    before = _snapshot()
    got = session.sql("SELECT k FROM l WHERE k IN (SELECT x FROM r)") \
        .to_pydict()
    assert _moved(before)["subquery.literal_in"] == 1
    assert np.asarray(got["k"]).tolist() == [2, 7]


def _ordered_table(n, groups, seed):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(np.arange(1, 40 * groups, dtype=np.int32),
                              size=groups, replace=False))
    k = np.sort(rng.choice(keys, size=n)).astype(np.int32)
    v = rng.integers(1, 51, n).astype(np.float32)
    v[rng.integers(0, n, 50)] = np.nan
    iv = rng.integers(-100, 100, n).astype(np.int32)
    mask = rng.random(n) > 0.1
    return k, v, iv, mask


AGGS = [AggExpr("sum", "v"), AggExpr("count", None), AggExpr("count", "v"),
        AggExpr("min", "iv"), AggExpr("max", "v"), AggExpr("avg", "iv"),
        AggExpr("sum", "iv")]


@pytest.mark.parametrize("shuffled", [False, True],
                         ids=["in_order", "shuffled"])
def test_whole_table_group_by_over_more_groups_than_the_dense_range(
        shuffled):
    """More rows than the exact threshold (2^17) and more groups than the
    dense table holds: keys in order take the ordered lowering (runs, no
    sort), shuffled keys the sorted one; both equal numpy exactly."""
    n = 400_001
    k, v, iv, mask = _ordered_table(n, 220_000, 7)
    if shuffled:
        p = np.random.default_rng(8).permutation(n)
        k, v, iv, mask = k[p], v[p], iv[p], mask[p]
    before = {c: counters.get(c) for c in ("grouped.ordered",
                                           "grouped.order_miss",
                                           "grouped.fallback")}
    out = Frame({"k": k, "v": v, "iv": iv}, mask=mask) \
        .group_by("k").agg(*AGGS)
    got = out.to_pydict()
    moved = {c: counters.get(c) - b for c, b in before.items()}
    assert moved == {"grouped.ordered": 0 if shuffled else 1,
                     "grouped.order_miss": 1 if shuffled else 0,
                     "grouped.fallback": 0}
    assert out.num_slots == (n if not shuffled else out.num_slots)
    kk, vv, ii = k[mask], v[mask], iv[mask]
    keys, at = np.unique(kk, return_inverse=True)
    assert len(keys) > 1 << 17
    nn = ~np.isnan(vv)
    cnt_v = np.bincount(at, weights=nn, minlength=keys.size)
    sum_v = np.bincount(at[nn], weights=vv[nn].astype(np.float64),
                        minlength=keys.size)
    max_v = np.full(keys.size, -np.inf)
    np.maximum.at(max_v, at[nn], vv[nn])
    min_i = np.full(keys.size, np.iinfo(np.int32).max)
    np.minimum.at(min_i, at, ii)
    sum_i = np.bincount(at, weights=ii.astype(np.float64),
                        minlength=keys.size)
    count = np.bincount(at, minlength=keys.size)
    assert np.array_equal(np.asarray(got["k"]), keys)
    assert np.array_equal(np.asarray(got["count"]), count)
    assert np.array_equal(np.asarray(got["count(v)"]), cnt_v)
    assert np.array_equal(np.asarray(got["sum(v)"]),
                          np.where(cnt_v > 0, sum_v, np.nan), equal_nan=True)
    assert np.array_equal(np.asarray(got["max(v)"]),
                          np.where(cnt_v > 0, max_v, np.nan), equal_nan=True)
    assert np.array_equal(np.asarray(got["min(iv)"]), min_i)
    assert np.array_equal(np.asarray(got["sum(iv)"]), sum_i)
    assert np.allclose(np.asarray(got["avg(iv)"]), sum_i / count)


def test_ordered_lowering_names_itself_on_its_span():
    from sparkdq4ml_tpu.utils import observability as obs

    k, v, iv, mask = _ordered_table(200_000, 140_000, 9)
    with obs.query_stats() as qs:
        Frame({"k": k, "v": v}, mask=mask).group_by("k") \
            .agg(AggExpr("sum", "v")).count()
    spans = [s for s in qs.spans if s.name == "frame.grouped.flush"]
    assert spans and spans[-1].attrs.get("lowering") == "ordered"


@pytest.mark.parametrize("n,valid", [(1_000_003, 300), (200_000, 0),
                                     (6_400, 6), (50_000, 20_000)],
                         ids=["sparse", "none", "last_block", "dense"])
def test_compact_rows_keeps_the_valid_rows_in_order(n, valid):
    """The semi join's build side, compacted to its valid rows: by block
    counts where they are far sparser than the slots, by one sort else."""
    import jax.numpy as jnp

    from sparkdq4ml_tpu.ops import joins

    rng = np.random.default_rng(n)
    mask = np.zeros(n, bool)
    mask[rng.choice(n, valid, replace=False)] = True
    if n == 6_400:
        mask[-1] = True
    col = rng.integers(0, 1 << 30, n).astype(np.int32)
    (got,), out_mask, rows = joins.compact_rows([jnp.asarray(col)],
                                                jnp.asarray(mask))
    assert rows == mask.sum()
    assert np.asarray(out_mask).sum() == rows
    assert np.array_equal(np.asarray(got)[np.asarray(out_mask)], col[mask])
