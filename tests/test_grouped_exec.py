"""Device-resident grouped execution (ops/segments.py + frame wiring).

Covers the ISSUE-4 acceptance surface:

* host-vs-device equivalence sweeps over the full compilable aggregate
  family × NaN keys × masked rows (the engine's mask IS the row weight)
  × empty / all-masked / single-group degenerates, on both the dense
  (sort-free) and sorted lowerings,
* a pandas oracle for the core aggregates with null keys,
* bit-exact float64 parity on integer-valued data (where every
  intermediate sum is exactly representable, accumulation order can't
  diverge),
* sort / distinct / dropDuplicates device-path parity (directions,
  NULLS FIRST/LAST markers, first-occurrence order, NaN-key folding),
* ``spark.groupedExec.enabled=false`` restores the exact legacy path;
  string keys / host-object aggregates silently fall back with a
  ``grouped.fallback`` increment and identical results,
* plan-cache reuse (repeated query + different-length same-bucket input
  = zero new compiles), host-sync pinning (device grouped agg = ONE
  sync), the empty-right-side join regression, golden DQ/RMSE numbers
  on and off, and the numpy-free lint for the device module.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import dataset_path, prepare_features, run_dq_pipeline

pytestmark = pytest.mark.grouped_exec

from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame import aggregates as A
from sparkdq4ml_tpu.frame.frame import Frame
from sparkdq4ml_tpu.ops import expressions as E
from sparkdq4ml_tpu.ops import segments
from sparkdq4ml_tpu.utils.profiling import counters


@pytest.fixture(autouse=True)
def _fresh_grouped_state():
    saved = config.grouped_exec
    config.grouped_exec = True
    segments.clear_cache()
    counters.clear("grouped")
    counters.clear("frame.")
    yield
    config.grouped_exec = saved
    segments.clear_cache()


def _hostpath(fn):
    """Run ``fn`` with grouped execution disabled (the legacy path)."""
    config.grouped_exec = False
    try:
        return fn()
    finally:
        config.grouped_exec = True


def _rows(frame):
    d = frame.to_pydict()
    cols = list(d)
    n = len(d[cols[0]]) if cols else 0
    return [tuple(d[c][i] for c in cols) for i in range(n)]


def _assert_frames_match(dev, host, rtol=1e-12, exact=False):
    assert dev.columns == host.columns
    dd, dh = dev.to_pydict(), host.to_pydict()
    for name in host.columns:
        a = np.asarray(dd[name], np.float64)
        b = np.asarray(dh[name], np.float64)
        assert a.shape == b.shape, name
        if exact:
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            np.testing.assert_allclose(a, b, rtol=rtol, atol=0,
                                       equal_nan=True, err_msg=name)


_ALL_AGGS = lambda col: [  # noqa: E731 - table-of-aggs, not a function
    A.AggExpr("count", None), A.count(col), A.sum(col), A.avg(col),
    A.min(col), A.max(col), A.stddev(col), A.variance(col),
    A.stddev_pop(col), A.var_pop(col), A.first(col), A.last(col),
    A.first(col, ignorenulls=True), A.last(col, ignorenulls=True),
    A.count_distinct(col), A.sum_distinct(col),
]


def _mixed_frame(seed, n=80, int_keys=True):
    rng = np.random.default_rng(seed)
    k = rng.integers(-3, 4, n).astype(np.float64)
    if not int_keys:
        k = k + rng.choice([0.0, 0.25, 0.5], n)
    k[rng.random(n) < 0.15] = np.nan
    v = rng.integers(-5, 12, n).astype(np.float64)
    v[rng.random(n) < 0.25] = np.nan
    i = rng.integers(-40, 90, n).astype(np.int32)
    b = rng.random(n) < 0.4
    f = Frame({"k": k, "v": v, "i": i, "b": b})
    # mask-weighted semantics: a filtered frame keeps all row slots but
    # only valid rows may contribute to any group
    return f.filter(E.col("i") < 75)


# ---------------------------------------------------------------------------
# Host-vs-device equivalence sweeps (dense and sorted lowerings)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(5))
def test_grouped_agg_device_matches_host_float_col(seed):
    f = _mixed_frame(seed)
    aggs = _ALL_AGGS("v")
    dev = f.group_by("k").agg(*aggs)
    host = _hostpath(lambda: f.group_by("k").agg(*aggs))
    assert counters.get("grouped.fallback") == 0
    _assert_frames_match(dev, host)


@pytest.mark.parametrize("seed", range(3))
def test_grouped_agg_device_matches_host_int_bool_cols(seed):
    f = _mixed_frame(seed)
    aggs = [A.sum("i"), A.min("i"), A.max("i"), A.avg("i"),
            A.count("i"), A.first("i"), A.last("i"),
            A.sum("b"), A.min("b"), A.max("b"), A.count_distinct("i")]
    dev = f.group_by("k").agg(*aggs)
    host = _hostpath(lambda: f.group_by("k").agg(*aggs))
    assert counters.get("grouped.fallback") == 0
    _assert_frames_match(dev, host)


@pytest.mark.parametrize("seed", range(3))
def test_grouped_agg_multi_key(seed):
    f = _mixed_frame(seed)
    aggs = [A.count(), A.sum("v"), A.avg("v"), A.min("i"), A.max("b")]
    dev = f.group_by("k", "i").agg(*aggs)
    host = _hostpath(lambda: f.group_by("k", "i").agg(*aggs))
    _assert_frames_match(dev, host)
    # bool + float key combination
    dev2 = f.group_by("b", "k").agg(*aggs)
    host2 = _hostpath(lambda: f.group_by("b", "k").agg(*aggs))
    _assert_frames_match(dev2, host2)


def test_grouped_agg_bit_exact_on_integer_valued_float64():
    """On float64 integer-valued data every intermediate sum is exactly
    representable, so accumulation order cannot round: the device path
    must BIT-match the host path (dense and sorted lowerings)."""
    rng = np.random.default_rng(7)
    n = 200
    k = rng.integers(0, 6, n).astype(np.float64)
    k[rng.random(n) < 0.1] = np.nan
    v = rng.integers(-8, 9, n).astype(np.float64)
    v[rng.random(n) < 0.2] = np.nan
    f = Frame({"k": k, "v": v})
    aggs = [A.AggExpr("count", None), A.count("v"), A.sum("v"),
            A.min("v"), A.max("v"), A.first("v"), A.last("v"),
            A.first("v", ignorenulls=True), A.sum_distinct("v"),
            A.count_distinct("v")]
    dev = f.group_by("k").agg(*aggs)
    host = _hostpath(lambda: f.group_by("k").agg(*aggs))
    _assert_frames_match(dev, host, exact=True)


def test_grouped_agg_dense_miss_reroutes_to_sorted():
    """Non-integer float keys can't pack into the dense table: the plan
    reroutes to the sorted program (one dense_miss), results identical."""
    f = _mixed_frame(3, int_keys=False)
    aggs = [A.count(), A.avg("v"), A.min("v")]
    dev = f.group_by("k").agg(*aggs)
    assert counters.get("grouped.dense_miss") == 1
    assert counters.get("grouped.fallback") == 0
    host = _hostpath(lambda: f.group_by("k").agg(*aggs))
    _assert_frames_match(dev, host)


def test_grouped_agg_huge_key_range_reroutes():
    """Integer-valued keys whose RANGE exceeds the dense table also
    reroute (the packed size gate), with identical results."""
    rng = np.random.default_rng(11)
    k = rng.integers(0, 2**30, 50).astype(np.float64)
    f = Frame({"k": k, "v": rng.normal(size=50)})
    dev = f.group_by("k").agg(A.count(), A.sum("v"))
    assert counters.get("grouped.dense_miss") == 1
    host = _hostpath(lambda: f.group_by("k").agg(A.count(), A.sum("v")))
    _assert_frames_match(dev, host)


def test_grouped_agg_degenerates():
    # single group
    f1 = Frame({"k": [2.0] * 6, "v": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]})
    dev = f1.group_by("k").agg(A.count(), A.avg("v"), A.stddev("v"))
    host = _hostpath(
        lambda: f1.group_by("k").agg(A.count(), A.avg("v"),
                                     A.stddev("v")))
    _assert_frames_match(dev, host)
    # all rows masked out → empty result on the device path
    f2 = Frame({"k": [1.0, 2.0], "v": [1.0, 2.0]}).filter(
        E.col("v") > 99.0)
    out = f2.group_by("k").agg(A.count(), A.sum("v"))
    assert out.count() == 0
    assert counters.get("grouped.fallback") == 0
    # zero-slot frame → host fallback (counts as one)
    f3 = Frame({"k": np.asarray([], np.float64),
                "v": np.asarray([], np.float64)})
    out3 = f3.group_by("k").agg(A.count())
    assert out3.count() == 0
    assert counters.get("grouped.fallback") == 1
    # all-null value column in one group → NULL aggregates
    f4 = Frame({"k": [1.0, 1.0, 2.0], "v": [np.nan, np.nan, 5.0]})
    dev4 = f4.group_by("k").agg(A.sum("v"), A.avg("v"), A.min("v"),
                                A.max("v"), A.count("v"))
    host4 = _hostpath(
        lambda: f4.group_by("k").agg(A.sum("v"), A.avg("v"), A.min("v"),
                                     A.max("v"), A.count("v")))
    _assert_frames_match(dev4, host4, exact=True)


def test_grouped_agg_single_row_bucket_floor():
    f = Frame({"k": [5.0], "v": [3.5]})
    dev = f.group_by("k").agg(A.count(), A.sum("v"))
    host = _hostpath(lambda: f.group_by("k").agg(A.count(), A.sum("v")))
    _assert_frames_match(dev, host)


# ---------------------------------------------------------------------------
# Pandas oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_grouped_agg_matches_pandas(seed):
    pd = pytest.importorskip("pandas")
    rng = np.random.default_rng(seed)
    n = 60
    k = rng.integers(0, 5, n).astype(np.float64)
    k[rng.random(n) < 0.15] = np.nan
    v = rng.normal(size=n)
    v[rng.random(n) < 0.2] = np.nan
    out = Frame({"k": k, "v": v}).group_by("k").agg(
        A.count(), A.sum("v"), A.avg("v"), A.min("v"), A.max("v"),
        A.stddev("v")).to_pydict()
    pdf = pd.DataFrame({"k": k, "v": v})
    ref = pdf.groupby("k", dropna=False, sort=True)["v"].agg(
        ["size", "sum", "mean", "min", "max", "std"])
    # engine order: null group FIRST; pandas sorts NaN last → realign
    ref = ref.reindex(sorted(ref.index, key=lambda x: (x == x, x)))
    np.testing.assert_array_equal(np.asarray(out["count"]),
                                  ref["size"].to_numpy())
    for ours, theirs in [("avg(v)", "mean"), ("min(v)", "min"),
                         ("max(v)", "max"), ("stddev(v)", "std")]:
        np.testing.assert_allclose(
            np.asarray(out[ours], np.float64), ref[theirs].to_numpy(),
            rtol=1e-9, equal_nan=True, err_msg=ours)
    # pandas sums all-NaN groups to 0.0; Spark (and we) yield NULL —
    # compare only groups with at least one non-null value
    has = ~np.isnan(np.asarray(out["avg(v)"], np.float64))
    np.testing.assert_allclose(
        np.asarray(out["sum(v)"], np.float64)[has],
        ref["sum"].to_numpy()[has], rtol=1e-9)


# ---------------------------------------------------------------------------
# Fallbacks + conf gate
# ---------------------------------------------------------------------------

def test_string_key_falls_back_with_counter():
    f = Frame({"city": ["ny", "sf", "ny", None], "v": [1.0, 2.0, 3.0, 4.0]})
    out = f.group_by("city").agg(A.sum("v"))
    assert counters.get("grouped.fallback") == 1
    assert counters.get("grouped.compile") == 0
    host = _hostpath(lambda: f.group_by("city").agg(A.sum("v")))
    dd, dh = out.to_pydict(), host.to_pydict()
    assert list(dd["city"]) == list(dh["city"])
    np.testing.assert_array_equal(dd["sum(v)"], dh["sum(v)"])


@pytest.mark.parametrize("agg", [
    A.collect_list("v"), A.percentile_approx("v", 0.5), A.median("v"),
    A.corr("v", "w"), A.AggExpr("max_by", "v", column2="w"), A.mode("v"),
    A.skewness("v"),
], ids=["collect_list", "percentile", "median", "corr", "max_by", "mode",
        "skewness"])
def test_host_object_aggs_fall_back_with_counter(agg):
    f = Frame({"k": [1.0, 1.0, 2.0], "v": [1.0, 2.0, 3.0],
               "w": [5.0, 4.0, 3.0]})
    out = f.group_by("k").agg(agg)
    assert counters.get("grouped.fallback") == 1
    host = _hostpath(lambda: f.group_by("k").agg(agg))
    for r1, r2 in zip(_rows(out), _rows(host)):
        for x, y in zip(r1, r2):
            assert x == y or (x != x and y != y), (r1, r2)


def test_conf_off_restores_legacy_path_and_session_scoped():
    from sparkdq4ml_tpu.session import TpuSession

    f = _mixed_frame(0)
    on = f.group_by("k").agg(A.sum("v"), A.count())
    sess = TpuSession(conf={"spark.groupedExec.enabled": "false"})
    try:
        assert config.grouped_exec is False
        counters.clear("grouped")
        off = f.group_by("k").agg(A.sum("v"), A.count())
        assert counters.get("grouped.compile") == 0
        assert counters.get("grouped.fallback") == 0
        _assert_frames_match(on, off)
    finally:
        sess.stop()
    assert config.grouped_exec is True     # restored by stop()


# ---------------------------------------------------------------------------
# Plan cache: replay + shape buckets
# ---------------------------------------------------------------------------

def test_repeated_agg_compiles_once():
    f = _mixed_frame(1)
    aggs = [A.count(), A.sum("v"), A.avg("v")]
    f.group_by("k").agg(*aggs)
    cold = counters.get("grouped.compile")
    assert cold >= 1
    f.group_by("k").agg(*aggs)
    _mixed_frame(2).group_by("k").agg(*aggs)   # same bucket, new values
    assert counters.get("grouped.compile") == cold
    assert counters.get("grouped.hit") >= 2


def test_different_length_same_bucket_replays():
    aggs = [A.count(), A.sum("v")]

    def frame_of(n):
        rng = np.random.default_rng(n)
        return Frame({"k": rng.integers(0, 4, n).astype(np.float64),
                      "v": rng.normal(size=n)})

    frame_of(40).group_by("k").agg(*aggs)      # bucket 64
    cold = counters.get("grouped.compile")
    frame_of(60).group_by("k").agg(*aggs)      # same bucket 64
    assert counters.get("grouped.compile") == cold
    frame_of(100).group_by("k").agg(*aggs)     # bucket 128 → retrace
    assert counters.get("grouped.compile") > cold


def test_sort_cache_replays():
    f = _mixed_frame(1).select("k", "i", "v")
    f.sort("k", "i")
    cold = counters.get("grouped.compile")
    f.sort("k", "i")
    assert counters.get("grouped.compile") == cold


# ---------------------------------------------------------------------------
# Host-sync pinning (the satellite counters)
# ---------------------------------------------------------------------------

def test_grouped_agg_device_path_syncs():
    f = _mixed_frame(0).select("k", "v")
    f.count()                                  # settle the mask
    counters.clear("frame.host_sync")
    f.group_by("k").agg(A.count(), A.avg("v"))
    # ONE sync: the fused fit-verdict + group-count scalar pull
    assert counters.get("frame.host_sync") == 1


def test_dense_miss_costs_at_most_two_syncs():
    f = _mixed_frame(0, int_keys=False).select("k", "v")
    f.count()
    counters.clear("frame.host_sync")
    f.group_by("k").agg(A.count())
    assert counters.get("frame.host_sync") <= 2


def test_sort_and_distinct_device_path_syncs():
    f = _mixed_frame(0).select("k", "i", "v")
    f.count()
    counters.clear("frame.host_sync")
    f.sort("k")
    assert counters.get("frame.host_sync") == 1
    counters.clear("frame.host_sync")
    f.select("k", "i").distinct()
    assert counters.get("frame.host_sync") == 1
    counters.clear("frame.host_sync")
    f.drop_duplicates(["k"])
    assert counters.get("frame.host_sync") == 1


def test_join_counts_key_pull_syncs():
    a = Frame({"k": [1.0, 2.0, 3.0], "x": [1.0, 2.0, 3.0]})
    b = Frame({"k": [2.0, 3.0], "y": [5.0, 6.0]})
    a.count(), b.count()
    counters.clear("frame.host_sync")
    a.join(b, on="k", how="inner")
    # planned on the device: one scalar read, the result's row count
    assert counters.get("frame.host_sync") == 1
    counters.clear("frame.host_sync")
    a.join(b, on="k", how="outer")
    # the host plan: two mask pulls + two key-column batches
    assert counters.get("frame.host_sync") == 4


# ---------------------------------------------------------------------------
# Sort / distinct / dropDuplicates device-path parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_sort_device_matches_host(seed):
    f = _mixed_frame(seed)
    for cols, kw in [
        (("k",), {}),
        (("k",), {"ascending": False}),
        (("k", "i"), {"ascending": [False, True]}),
        ((E.col("k").asc_nulls_last(),), {}),
        ((E.col("k").desc_nulls_first(), "i"), {}),
        (("b", "v"), {}),
    ]:
        dev = f.sort(*cols, **kw)
        host = _hostpath(lambda: f.sort(*cols, **kw))
        assert counters.get("grouped.fallback") == 0
        drows, hrows = _rows(dev), _rows(host)
        assert len(drows) == len(hrows)
        for r1, r2 in zip(drows, hrows):
            for x, y in zip(r1, r2):
                assert (x != x and y != y) or x == y, (r1, r2)


def test_sort_string_key_falls_back_identically():
    f = Frame({"s": ["b", "a", None, "c"], "v": [1.0, 2.0, 3.0, 4.0]})
    dev = f.sort("s")
    assert counters.get("grouped.fallback") == 1
    host = _hostpath(lambda: f.sort("s"))
    assert _rows(dev) == _rows(host)


def test_sort_string_payload_gathers_on_host():
    f = Frame({"k": [3.0, 1.0, 2.0], "s": ["c", "a", "b"]})
    out = f.sort("k")
    assert list(out.to_pydict()["s"]) == ["a", "b", "c"]


@pytest.mark.parametrize("seed", range(3))
def test_distinct_and_dropdup_device_match_host(seed):
    f = _mixed_frame(seed)
    for mk in [lambda: f.select("k", "i").distinct(),
               lambda: f.select("k", "b").distinct(),
               lambda: f.drop_duplicates(["k"]),
               lambda: f.drop_duplicates(["k", "i"])]:
        dev = mk()
        host = _hostpath(mk)
        drows, hrows = _rows(dev), _rows(host)
        assert len(drows) == len(hrows)
        for r1, r2 in zip(drows, hrows):
            for x, y in zip(r1, r2):
                assert (x != x and y != y) or x == y, (r1, r2)
    assert counters.get("grouped.fallback") == 0


def test_distinct_keeps_first_occurrence_order():
    f = Frame({"k": [3.0, 1.0, 3.0, 2.0, 1.0],
               "v": [9.0, 8.0, 7.0, 6.0, 5.0]})
    out = f.select("k").distinct()
    assert list(np.asarray(out.to_pydict()["k"])) == [3.0, 1.0, 2.0]
    dd = f.drop_duplicates(["k"])
    assert _rows(dd) == [(3.0, 9.0), (1.0, 8.0), (2.0, 6.0)]


def test_distinct_nan_keys_fold():
    f = Frame({"k": [np.nan, 1.0, np.nan, 1.0]})
    out = f.distinct().to_pydict()["k"]
    assert len(out) == 2
    host = _hostpath(lambda: f.distinct().to_pydict()["k"])
    assert len(host) == 2


def test_distinct_vector_column_on_device():
    f = Frame({"vec": np.asarray([[1.0, 2.0], [1.0, 2.0], [3.0, 4.0]])})
    out = f.distinct()
    assert counters.get("grouped.fallback") == 0
    assert out.count() == 2
    host = _hostpath(lambda: f.distinct())
    assert out.count() == host.count()


def test_dropdup_string_subset_falls_back():
    f = Frame({"s": ["a", "a", "b"], "v": [1.0, 2.0, 3.0]})
    dev = f.drop_duplicates(["s"])
    assert counters.get("grouped.fallback") == 1
    host = _hostpath(lambda: f.drop_duplicates(["s"]))
    assert _rows(dev) == _rows(host)


# ---------------------------------------------------------------------------
# Empty-right-side join regression (the frame.py:135 satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["masked", "zeroslot"])
@pytest.mark.parametrize("how", ["inner", "left", "right", "outer",
                                 "left_semi", "left_anti"])
def test_join_empty_right_side(kind, how):
    import jax.numpy as jnp

    left = Frame({"k": [1.0, 2.0, 3.0], "v": [10.0, 20.0, 30.0]})
    if kind == "masked":
        right = Frame({"k": [1.0], "w": [99.0]},
                      mask=jnp.asarray([False]))
    else:
        right = Frame({"k": np.asarray([], np.float64),
                       "w": np.asarray([], np.float64)})
    out = left.join(right, on="k", how=how)
    rows = _rows(out)
    if how in ("inner", "right", "left_semi"):
        assert rows == []
    elif how == "left_anti":
        assert rows == [(1.0, 10.0), (2.0, 20.0), (3.0, 30.0)]
    else:                                   # left / outer: null-filled w
        assert [r[:2] for r in rows] == [(1.0, 10.0), (2.0, 20.0),
                                         (3.0, 30.0)]
        assert all(r[2] != r[2] for r in rows)


def test_join_empty_left_side_right_and_outer():
    left = Frame({"k": np.asarray([], np.float64),
                  "v": np.asarray([], np.float64)})
    right = Frame({"k": [1.0, 2.0], "w": [5.0, 6.0]})
    for how in ("right", "outer"):
        rows = _rows(left.join(right, on="k", how=how))
        assert sorted(r[0] for r in rows) == [1.0, 2.0]
        assert all(r[1] != r[1] for r in rows)     # v is null
    assert _rows(left.join(right, on="k", how="inner")) == []


# ---------------------------------------------------------------------------
# SQL integration + plan summary
# ---------------------------------------------------------------------------

def test_sql_group_by_device_matches_legacy(session):
    rng = np.random.default_rng(5)
    n = 120
    Frame({"g": rng.integers(0, 7, n).astype(np.float64),
           "p": rng.normal(size=n) * 10}).create_or_replace_temp_view("t")
    q = ("SELECT g, COUNT(*) c, SUM(p) s, AVG(p) a, MIN(p) lo, "
         "MAX(p) hi FROM t GROUP BY g ORDER BY g")
    dev = session.sql(q)
    host = _hostpath(lambda: session.sql(q))
    _assert_frames_match(dev, host)
    assert counters.get("grouped.compile") >= 1


def test_plan_summary_markers():
    from sparkdq4ml_tpu.sql.parser import parse, plan_summary

    seg = plan_summary(parse(
        "SELECT g, SUM(p) FROM t GROUP BY g ORDER BY g"))
    assert "SegmentedAggregate[groupBy:1]" in seg
    assert "DeviceSort[1]" in seg
    # a host-object aggregate keeps the legacy Aggregate rendering
    host_agg = plan_summary(parse(
        "SELECT g, percentile_approx(p, 0.5) FROM t GROUP BY g"))
    assert "SegmentedAggregate" not in host_agg
    assert "Aggregate[groupBy:1]" in host_agg
    # conf off restores both legacy markers
    config.grouped_exec = False
    try:
        off = plan_summary(parse(
            "SELECT g, SUM(p) FROM t GROUP BY g ORDER BY g"))
    finally:
        config.grouped_exec = True
    assert "Sort[1]" in off and "DeviceSort" not in off
    assert "Aggregate[groupBy:1]" in off and "SegmentedAggregate" not in off


def test_grouped_flush_span(session):
    from sparkdq4ml_tpu.utils import observability as obs

    obs.enable()
    try:
        _mixed_frame(0).group_by("k").agg(A.count(), A.avg("v"))
        spans = [s for s in obs.TRACER.spans()
                 if s.name == "frame.grouped.flush"]
        assert spans
        s = spans[-1]
        assert s.attrs["op"] == "group_by"
        assert s.attrs["lowering"] in ("dense", "dense-tile", "sorted")
        assert s.attrs["cache"] in ("compile", "hit")
        assert s.attrs["groups"] >= 1
    finally:
        obs.disable()
        obs.TRACER.clear()   # don't leak spans into later suites


# ---------------------------------------------------------------------------
# Golden regression gates: DQ row counts + example-app RMSE, on and off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("enabled", [True, False],
                         ids=["grouped_on", "grouped_off"])
def test_golden_dq_counts_and_rmse(session, enabled):
    from sparkdq4ml_tpu.models import LinearRegression

    config.grouped_exec = enabled
    df = run_dq_pipeline(session, dataset_path("abstract"))
    assert df.count() == 24
    df = prepare_features(df)
    model = (LinearRegression().setMaxIter(40).setRegParam(1)
             .setElasticNetParam(1)).fit(df)
    assert model.summary.root_mean_squared_error == pytest.approx(
        2.809940, abs=1e-4)


# ---------------------------------------------------------------------------
# Default-dtype regime (x64 OFF → float32 accumulator): integer aggregates
# must stay exact. The suite runs with x64 forced on (conftest), so this
# regression drives a subprocess with the engine's real default config.
# ---------------------------------------------------------------------------

_X64_OFF_SCRIPT = r"""
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame.frame import Frame
from sparkdq4ml_tpu.frame import aggregates as A
from sparkdq4ml_tpu.utils.profiling import counters

# int sums past 2^24 would round in a float32 accumulator: the dense
# lowering must reduce them in the integer domain (bit-equal to host)
rng = np.random.default_rng(0)
n = 60_000
f = Frame({"k": rng.integers(0, 4, n).astype(np.float64),
           "v": rng.integers(900, 1100, n).astype(np.int32)})
aggs = [A.sum("v"), A.count(), A.min("v"), A.max("v"), A.first("v"),
        A.last("v")]
counters.clear("grouped")
dev = f.group_by("k").agg(*aggs).to_pydict()
assert counters.get("grouped.dense_miss") == 0
assert counters.get("grouped.fallback") == 0
config.grouped_exec = False
host = f.group_by("k").agg(*aggs).to_pydict()
config.grouped_exec = True
for c in host:
    assert np.array_equal(np.asarray(dev[c]), np.asarray(host[c])), c

# adjacent large ints alias in float32: distinct-run detection must
# compare in the column's own dtype (sorted lowering)
f2 = Frame({"k": np.zeros(100),
            "v": np.asarray([16777216, 16777217] * 50, np.int32)})
d2 = f2.group_by("k").agg(A.count_distinct("v"),
                          A.sum_distinct("v")).to_pydict()
assert int(d2["count(DISTINCT v)"][0]) == 2
assert int(d2["sum(DISTINCT v)"][0]) == 16777216 + 16777217
print("X64OFF-OK")
"""


def test_integer_aggs_exact_without_x64():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_ENABLE_X64", None)
    proc = subprocess.run(
        [sys.executable, "-c", _X64_OFF_SCRIPT],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "X64OFF-OK" in proc.stdout


# ---------------------------------------------------------------------------
# CI/tooling satellite: the numpy-free device-module lint
# ---------------------------------------------------------------------------

class TestSegmentsNumpyLint:
    REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    SCRIPT = os.path.join(REPO, "scripts", "check_segments_np.py")

    def test_module_is_clean(self):
        proc = subprocess.run([sys.executable, self.SCRIPT, self.REPO],
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_lint_catches_offender(self, tmp_path):
        ops = tmp_path / "sparkdq4ml_tpu" / "ops"
        ops.mkdir(parents=True)
        (ops / "segments.py").write_text(
            "import numpy as np\n"
            "x = np.asarray([1.0])\n"
            "# --- BEGIN HOST FALLBACK\n"
            "y = np.asarray([2.0])\n"
            "# --- END HOST FALLBACK\n")
        proc = subprocess.run(
            [sys.executable, self.SCRIPT, str(tmp_path)],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 1
        # both the top-level import and the compute-path np.asarray are
        # outside the region; the in-region one is allowed
        assert "segments.py:1" in proc.stdout
        assert "segments.py:2" in proc.stdout
        assert "segments.py:4" not in proc.stdout
