"""The join planned on the device (``ops/joins.py``) against the host plan
(``frame._vector_join_plan``), case by case; which joins take which path;
the SQL forms that reach it (comma FROM, ``ON a = b`` over two names); and
TPC-H Q3 through ``spark.sql`` against its configuration's float64
reference. On the CPU backend at small sizes: nothing here is a time.
"""

import os
import sys

import numpy as np
import pytest

from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.utils.profiling import counters

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

HOWS = ("inner", "left", "left_semi", "left_anti")


def frame(seed, n, key_range, value, keys=("k",), valid=0.8, base=0):
    r = np.random.default_rng(seed)
    data = {k: (base + r.integers(0, key_range, n)).astype(np.int32)
            for k in keys}
    data[value] = r.normal(size=n).astype(np.float32)
    mask = r.random(n) < valid if valid < 1.0 else None
    return Frame(data, mask=mask)


def unique_keys(seed, n, value, base=0):
    r = np.random.default_rng(seed)
    return Frame({"k": (base + r.permutation(n)).astype(np.int32),
                  value: r.normal(size=n).astype(np.float32)})


BIG = 1 << 25      # int32 keys that float32 cannot tell apart


def sides(case):
    if case == "one_key_duplicates_on_both":
        return frame(1, 60, 12, "a"), frame(2, 50, 12, "b"), ["k"]
    if case == "one_key_duplicates_on_the_left":
        return (frame(3, 80, 20, "a", valid=1.0), unique_keys(4, 20, "b"),
                ["k"])
    if case == "one_key_duplicates_on_the_right":
        return unique_keys(5, 20, "a"), frame(6, 80, 20, "b"), ["k"]
    if case == "two_keys":
        return (frame(7, 70, 4, "a", keys=("k", "j")),
                frame(8, 60, 4, "b", keys=("k", "j")), ["k", "j"])
    if case == "masked_rows_on_the_left_only":
        return (frame(9, 40, 10, "a", valid=0.5),
                frame(10, 30, 10, "b", valid=1.0), ["k"])
    if case == "masked_rows_on_the_right_only":
        return (frame(11, 40, 10, "a", valid=1.0),
                frame(12, 30, 10, "b", valid=0.5), ["k"])
    if case == "an_empty_left_side":
        return (frame(13, 16, 5, "a", valid=0.0), frame(14, 12, 5, "b"),
                ["k"])
    if case == "an_empty_right_side":
        return (frame(15, 16, 5, "a"), frame(16, 12, 5, "b", valid=0.0),
                ["k"])
    if case == "no_match_at_all":
        return (frame(17, 30, 10, "a"), frame(18, 30, 10, "b", base=100),
                ["k"])
    if case == "int32_keys_over_2_24_that_differ_in_the_last_bit":
        # 2^25 + {0..7}: float32 holds one of every four of them
        return (frame(19, 64, 8, "a", base=BIG),
                frame(20, 48, 8, "b", base=BIG), ["k"])
    if case == "float_keys_with_nan_and_signed_zero":
        left = Frame({"k": np.asarray([0.0, -0.0, np.nan, 1.5, 2.5, 1.5],
                                      np.float32),
                      "a": np.arange(6, dtype=np.float32)})
        right = Frame({"k": np.asarray([-0.0, np.nan, 1.5, 1.5, 3.5],
                                       np.float32),
                       "b": np.arange(5, dtype=np.float32)})
        return left, right, ["k"]
    raise KeyError(case)


CASES = (
    "one_key_duplicates_on_both", "one_key_duplicates_on_the_left",
    "one_key_duplicates_on_the_right", "two_keys",
    "masked_rows_on_the_left_only", "masked_rows_on_the_right_only",
    "an_empty_left_side", "an_empty_right_side", "no_match_at_all",
    "int32_keys_over_2_24_that_differ_in_the_last_bit",
    "float_keys_with_nan_and_signed_zero",
)


def moved(before):
    now = counters.snapshot()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


def same_rows(got, want):
    """Equal columns, row for row: emission order is part of the answer."""
    g, w = got.to_pydict(), want.to_pydict()
    assert list(g) == list(w)
    for name in g:
        assert g[name].dtype.kind == w[name].dtype.kind, name
        assert np.array_equal(g[name], w[name], equal_nan=True), name


@pytest.mark.parametrize("build", [None, "left"])
@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("case", CASES)
def test_device_join_equals_the_host_plan(case, how, build):
    left, right, keys = sides(case)
    # (with NaN keys the host's vector plan declines and its dict plan
    # answers: no NaN matches there either)
    want = left._host_join(right, keys, how,
                           build == "left" and how == "inner", None)
    before = counters.snapshot()
    got = left.join(right, keys, how, build=build)
    delta = moved(before)
    same_rows(got, want)
    assert delta.get("join.device") == 1 and "join.host" not in delta
    # a scalar a run: the result's size (twice where the first bucket was
    # outgrown); no mask, no key column
    assert delta["host.read_bytes"] < 100 and delta["host.reads"] <= 2
    assert delta["join.rows_probed"] >= min(left.num_slots,
                                            right.num_slots)


def test_emission_order_is_left_then_right_row_order():
    left = Frame({"k": np.asarray([2, 1, 2, 3], np.int32),
                  "a": np.asarray([0., 1., 2., 3.], np.float32)})
    right = Frame({"k": np.asarray([2, 3, 2, 1, 2], np.int32),
                   "b": np.asarray([0., 1., 2., 3., 4.], np.float32)})
    for build in (None, "left"):
        d = left.join(right, "k", "inner", build=build).to_pydict()
        assert d["a"].tolist() == [0., 0., 0., 1., 2., 2., 2., 3.]
        assert d["b"].tolist() == [0., 2., 4., 3., 0., 2., 4., 1.]


def test_a_second_run_of_one_join_builds_nothing_and_reads_one_scalar():
    left, right, keys = sides("one_key_duplicates_on_both")
    left.join(right, keys, "inner")
    before = counters.snapshot()
    left.join(right, keys, "inner")
    delta = moved(before)
    assert delta.get("join.hit") == 1 and "join.compile" not in delta
    assert delta["host.reads"] == 1 and delta["host.read_bytes"] <= 8


@pytest.mark.parametrize("how, keys", [
    ("right", "numeric"), ("outer", "numeric"), ("inner", "string"),
    ("left", "string"), ("inner", "integer_against_float"),
    ("cross", "none")])
def test_what_stays_on_the_host_answers_there_and_says_so(how, keys):
    if keys == "string":
        left = Frame({"k": ["a", "b", "c", "b"], "x": [1., 2., 3., 4.]})
        right = Frame({"k": ["b", "c", "d"], "y": [5., 6., 7.]})
    elif keys == "integer_against_float":
        left = Frame({"k": np.asarray([1, 2, 3, 2], np.int32),
                      "x": [1., 2., 3., 4.]})
        right = Frame({"k": np.asarray([2., 3., 4.], np.float32),
                       "y": [5., 6., 7.]})
    else:
        left = Frame({"k": [1., 2., 3., 2.], "x": [1., 2., 3., 4.]})
        right = Frame({"k": [2., 3., 4.], "y": [5., 6., 7.]})
    before = counters.snapshot()
    out = left.join(right, None if how == "cross" else "k", how)
    delta = moved(before)
    assert delta.get("join.host") == 1 and "join.device" not in delta
    # the host plan's mask and key pulls are counted reads now
    assert delta["host.reads"] >= 2
    d = out.to_pydict()
    if how == "cross":
        assert len(d["x"]) == 12
    elif how == "right":
        assert sorted(d["y"].tolist()) == [5., 5., 6., 7.]
        assert np.isnan(d["x"]).sum() == 1
    elif how == "outer":
        assert len(d["x"]) == 5 and np.isnan(d["y"]).sum() == 1
    elif how == "left":
        assert d["x"].tolist() == [1., 2., 3., 4.]
        assert np.isnan(d["y"]).sum() == 1
    else:
        assert d["x"].tolist() == [2., 3., 4.]
        assert d["y"].tolist() == [5., 6., 5.]


def test_a_key_pair_joins_two_names_and_keeps_both():
    orders = Frame({"o_key": np.asarray([10, 11, 12], np.int32),
                    "o_cust": np.asarray([1, 3, 1], np.int32)})
    cust = Frame({"c_key": np.asarray([1, 2], np.int32),
                  "c_seg": np.asarray([7, 8], np.int32)})
    inner = orders.join(cust, [("o_cust", "c_key")], "inner")
    d = inner.to_pydict()
    assert inner.columns == ["o_key", "o_cust", "c_seg", "c_key"]
    assert d["o_key"].tolist() == [10, 12]
    assert d["c_key"].tolist() == d["o_cust"].tolist() == [1, 1]
    left = orders.join(cust, [("o_cust", "c_key")], "left").to_pydict()
    assert left["o_cust"].tolist() == [1, 3, 1]
    assert np.isnan(left["c_key"][1]) and left["c_key"][0] == 1
    semi = orders.join(cust, [("o_cust", "c_key")], "left_anti")
    assert semi.to_pydict()["o_key"].tolist() == [11]
    with pytest.raises(ValueError, match="shared column name"):
        orders.join(cust.with_column_renamed("c_seg", "o_cust"),
                    [("o_cust", "c_key")], "inner")


# ---------------------------------------------------------------------------
# SQL: the comma FROM list and ON over two names plan as USING does
# ---------------------------------------------------------------------------

@pytest.fixture
def tables(session):
    r = np.random.default_rng(0)
    cust = Frame({"c_key": np.arange(1, 41, dtype=np.int32),
                  "c_seg": r.integers(0, 3, 40).astype(np.int32)})
    orders = Frame({"o_key": np.arange(100, 300, dtype=np.int32),
                    "o_cust": r.integers(1, 61, 200).astype(np.int32),
                    "o_total": r.normal(size=200).astype(np.float32)})
    same = orders.with_column_renamed("o_cust", "c_key")
    cust.create_or_replace_temp_view("cust")
    orders.create_or_replace_temp_view("orders")
    same.create_or_replace_temp_view("orders_using")
    return cust, orders


FORMS = {
    "comma_from": "SELECT o_key, c_seg FROM cust, orders "
                  "WHERE c_key = o_cust AND c_seg = 1 AND o_total > 0",
    "on_two_names": "SELECT o_key, c_seg FROM cust JOIN orders "
                    "ON c_key = o_cust WHERE c_seg = 1 AND o_total > 0",
    "on_turned_and_qualified": "SELECT o_key, c_seg FROM cust c JOIN orders o "
                               "ON o.o_cust = c.c_key "
                               "WHERE c.c_seg = 1 AND o.o_total > 0",
}
USING = ("SELECT o_key, c_seg FROM cust JOIN orders_using USING (c_key) "
         "WHERE c_seg = 1 AND o_total > 0")


def plan_of(session, sql):
    text = str(session.sql("EXPLAIN " + sql).to_pydict()["plan"][0])
    return text.replace("orders_using", "orders")


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_new_forms_give_the_plan_and_the_rows_using_gives(
        session, tables, form):
    want = session.sql(USING).to_pydict()
    before = counters.snapshot()
    got = session.sql(FORMS[form]).to_pydict()
    assert moved(before).get("join.device") == 1
    assert got["o_key"].tolist() == want["o_key"].tolist()
    assert got["c_seg"].tolist() == want["c_seg"].tolist()
    physical, rewrites = plan_of(session, FORMS[form]) \
        .split("== Before Optimization ==")[0].split("== Rewrites ==")
    want_physical, want_rewrites = plan_of(session, USING) \
        .split("== Before Optimization ==")[0].split("== Rewrites ==")
    # the same tree: one inner join, both filters pushed under it
    assert "Join[inner" in physical and rewrites.count("pushdown") == 2

    def shape(text):
        return [ln.split("(est")[0].rstrip() for ln in text.splitlines()]

    assert shape(physical) == shape(want_physical)


def test_a_comma_list_without_an_equality_is_a_cross_join(session, tables):
    out = session.sql("SELECT o_key FROM cust, orders WHERE c_seg = 9")
    assert out.count() == 0
    assert session.sql("SELECT count(*) AS n FROM cust, orders") \
        .to_pydict()["n"].tolist() == [40 * 200]


@pytest.mark.parametrize("on", [
    "cust.c_key = cust.c_seg",         # both of one side
    "c_key < o_cust",                  # no equality
    "c_key = nowhere"])                # a name neither side has
def test_an_on_that_is_no_equality_of_one_column_a_side_keeps_its_error(
        session, tables, on):
    with pytest.raises(ValueError):
        session.sql(f"SELECT o_key FROM cust JOIN orders ON {on}")


def test_on_without_the_optimizer_settles_its_keys_at_execution(
        session, tables):
    from sparkdq4ml_tpu.config import config

    want = session.sql(USING).to_pydict()["o_key"].tolist()
    config.optimizer_enabled = False
    try:
        for form in sorted(FORMS):
            assert session.sql(FORMS[form]).to_pydict()["o_key"].tolist() \
                == want
    finally:
        config.optimizer_enabled = True


# ---------------------------------------------------------------------------
# TPC-H Q3 as published, against the configuration's float64 reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def q3_cell():
    from benchmarks import harness

    return harness.load_cell("tpch_q3_join", REPO)


@pytest.mark.parametrize("seed", [3, 2_600_000_011])
def test_q3_through_spark_sql_equals_its_float64_reference(
        session, q3_cell, seed):
    import jax

    cfg, mod, job_mod = q3_cell["cfg"], q3_cell["cfg_mod"], \
        q3_cell["job_mod"]
    params = q3_cell["traffic"]["params"]
    table = mod.make_table(cfg, seed, 24_000)      # 6,000 orders
    job = job_mod.Job(session, cfg, mod, params, table)
    try:
        plan = str(session.sql("EXPLAIN " + job.query)
                   .to_pydict()["plan"][0])
        physical, rest = plan.split("== Rewrites ==")
        # two joins over three filtered scans, the first join innermost
        assert physical.count("Join[inner") == 2
        assert physical.count("Filter") == 3
        assert physical.index("Scan[customer]") \
            < physical.index("Scan[orders]") \
            < physical.index("Scan[lineitem]")
        assert rest.count("pushdown") == 3
        before = counters.snapshot()
        rows = session.sql(job.query)
        got = {k: np.asarray(v) for k, v in rows.to_pydict().items()}
        delta = moved(before)
    finally:
        job.close()
    assert delta.get("join.device") == 2 and "join.host" not in delta
    assert "grouped.fallback" not in delta
    want = job_mod.reference(cfg, mod, params, jax.device_get(table))
    assert want["groups"] > 10 and len(got["revenue"]) == 10
    gaps = job_mod.compare(got, want)
    assert gaps["rows_diff"] == 0
    assert gaps["revenue_rel"] < q3_cell["traffic"]["limits"]["revenue_rel"]
    assert np.all(np.diff(got["revenue"]) <= 0)


def test_limit_over_a_compact_frame_is_a_slice():
    f = Frame({"a": np.arange(100, dtype=np.float32)}).sort("a")
    cut = f.limit(10)
    assert cut.num_slots == 10
    assert cut.to_pydict()["a"].tolist() == list(range(10))
    masked = Frame({"a": np.arange(8, dtype=np.float32)},
                   mask=np.arange(8) % 2 == 0).limit(2)
    assert masked.num_slots == 8
    assert masked.to_pydict()["a"].tolist() == [0.0, 2.0]


def test_a_many_group_result_keeps_a_bucket_of_slots_under_a_mask():
    n = 300_000
    f = Frame({"k": np.arange(n, dtype=np.int32) // 2,
               "v": np.ones(n, np.float32)})
    out = f.group_by("k").agg({"v": "sum"})
    assert out.num_slots >= n // 2 and out.count() == n // 2
    top = out.sort("k", ascending=False).limit(3).to_pydict()
    assert top["k"].tolist() == [n // 2 - 1, n // 2 - 2, n // 2 - 3]


@pytest.mark.parametrize("share", [0.0, 0.0005, 0.002])
def test_the_chunked_compaction_finds_what_the_full_sort_finds(share):
    """A small result over a large input compacts inside chunks
    (``ops/joins._compact``): the same positions as the one sort gives."""
    import jax.numpy as jnp

    from sparkdq4ml_tpu.ops import joins

    n, bucket = 3 * joins._CHUNK + 1234, 512
    sel = np.random.default_rng(4).random(n) < share
    sel[-1] = share > 0                       # the ragged last chunk holds one
    want = np.nonzero(sel)[0]
    assert len(want) <= bucket and bucket * 16 <= n
    got = np.asarray(joins._compact(jnp.asarray(sel), n, bucket))
    assert np.array_equal(got[:len(want)], want)
    assert got.min() >= 0 and got.max() < n
