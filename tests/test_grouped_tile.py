"""The dense lowering's tile tier (ops/segments.py: ``_tile_tables``).

A packed key range that fits one lane tile (<= 128 slots) reduces by
blocked masked passes over the rows instead of an S-slot scatter. Pinned
here on the CPU at small sizes:

* the tier against the host-numpy lowering over key kinds (two int keys, a
  float key with NULLs, a bool key), every aggregate the dense program
  lowers, masked rows, an all-NULL group, a key range of exactly 128 and of
  129 slots (the second takes the scatter tier and agrees), block counts
  that do not divide the rows, under both tiers;
* TPC-H Q1 through ``spark.sql`` against the benchmark's plain float64
  reference (``benchmarks/configs/tpch-q1-lineitem.py``), which shares no
  code with the program;
* what follows the verdict: the result's slices, a plan's pads and a sort's
  takes are one jitted call each, and the accelerators' sort program reads
  its row count only for an input that carries a mask;
* the program traced at 1e8 rows without running it: no intermediate whose
  major dimension is n and whose minor dimension is under 128 lanes (the
  ``(n, C)`` stacks that asked for 61 GB at 1.2e8 rows).
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame import aggregates as A
from sparkdq4ml_tpu.frame.frame import Frame
from sparkdq4ml_tpu.ops import expressions as E
from sparkdq4ml_tpu.ops import segments
from sparkdq4ml_tpu.utils import observability as obs
from sparkdq4ml_tpu.utils.profiling import counters

pytestmark = pytest.mark.grouped_exec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmarks", "configs")


@pytest.fixture(autouse=True)
def _fresh_grouped_state():
    saved = config.grouped_exec
    config.grouped_exec = True
    segments.clear_cache()
    counters.clear("grouped")
    counters.clear("frame.")
    counters.clear("host.")
    yield
    config.grouped_exec = saved
    segments.clear_cache()
    obs.disable()
    obs.reset()


def _hostpath(fn):
    config.grouped_exec = False
    try:
        return fn()
    finally:
        config.grouped_exec = True


def _assert_match(dev, host, rtol=1e-11):
    assert dev.columns == host.columns
    dd, dh = dev.to_pydict(), host.to_pydict()
    for name in host.columns:
        a = np.asarray(dd[name], np.float64)
        b = np.asarray(dh[name], np.float64)
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=rtol, atol=0, equal_nan=True,
                                   err_msg=name)


def _grouped(frame, keys, aggs):
    """(device result, host result, the flush span's attributes)."""
    obs.reset()
    obs.enable()
    before = counters.snapshot()
    dev = frame.group_by(*keys).agg(*aggs)
    span = [s for s in obs.TRACER.spans()
            if s.name == "frame.grouped.flush"][-1]
    obs.disable()
    attrs = dict(span.attrs)
    # what the one grouped plan moved, before anything is read back
    attrs["moved"] = {k: v - before.get(k, 0)
                      for k, v in counters.snapshot().items()
                      if v != before.get(k, 0)}
    host = _hostpath(lambda: frame.group_by(*keys).agg(*aggs))
    return dev, host, attrs


def _frame(seed, n, keys):
    """Rows with the key kinds asked for, a float column with NULLs, an
    int column, and a mask that drops a fifth of the rows."""
    rng = np.random.default_rng(seed)
    cols = {}
    if "i1" in keys:
        cols["i1"] = rng.integers(-2, 3, n).astype(np.int32)
    if "i2" in keys:
        cols["i2"] = rng.integers(100, 104, n).astype(np.int64)
    if "f" in keys:
        f = rng.integers(0, 6, n).astype(np.float64)
        f[rng.random(n) < 0.1] = np.nan
        cols["f"] = f
    if "b" in keys:
        cols["b"] = rng.random(n) < 0.3
    v = rng.normal(50.0, 20.0, n)
    v[rng.random(n) < 0.2] = np.nan
    cols["v"] = v
    cols["w"] = rng.integers(-1000, 1000, n).astype(np.int32)
    cols["pick"] = rng.random(n)
    return Frame(cols).filter(E.col("pick") < 0.8)


_FLOAT_AGGS = [
    A.AggExpr("count", None), A.count("v"), A.sum("v"), A.avg("v"),
    A.min("v"), A.max("v"), A.stddev("v"), A.variance("v"),
    A.stddev_pop("v"), A.var_pop("v"), A.first("v"), A.last("v"),
    A.first("v", ignorenulls=True), A.last("v", ignorenulls=True)]
_INT_AGGS = [A.count("w"), A.sum("w"), A.avg("w"), A.min("w"), A.max("w"),
             A.first("w"), A.last("w"), A.variance("w")]

KEY_SETS = {"two_int": ("i1", "i2"), "float_nulls": ("f",), "bool": ("b",),
            "float_and_bool": ("f", "b"), "int_and_float": ("i2", "f")}


@pytest.mark.parametrize("agg", range(len(_FLOAT_AGGS) + len(_INT_AGGS)))
@pytest.mark.parametrize("keys", sorted(KEY_SETS))
def test_tile_tier_agrees_with_the_host_lowering(keys, agg):
    """Every aggregate the dense program lowers, alone beside count(*),
    over every key kind, on masked rows."""
    one = (_FLOAT_AGGS + _INT_AGGS)[agg]
    frame = _frame(7 + agg, 3_000, KEY_SETS[keys])
    aggs = [one] if agg == 0 else [A.AggExpr("count", None), one]
    dev, host, attrs = _grouped(frame, KEY_SETS[keys], aggs)
    assert attrs["lowering"] == "dense-tile", attrs
    moved = attrs["moved"]
    assert moved["grouped.tile"] == 1 and moved["grouped.compile"] == 1
    assert "grouped.fallback" not in moved
    assert moved["frame.host_sync"] == 1 and moved["host.reads"] == 1
    _assert_match(dev, host)


@pytest.mark.parametrize("keys", sorted(KEY_SETS))
def test_tile_tier_whole_plan_agrees(keys):
    frame = _frame(3, 5_000, KEY_SETS[keys])
    dev, host, attrs = _grouped(frame, KEY_SETS[keys],
                                _FLOAT_AGGS + _INT_AGGS)
    assert attrs["lowering"] == "dense-tile"
    assert attrs["groups"] == len(host.to_pydict()[host.columns[0]])
    _assert_match(dev, host)


def test_integer_sums_and_counts_are_exact():
    rng = np.random.default_rng(1)
    n = 40_000
    frame = Frame({"k": rng.integers(0, 7, n).astype(np.int32),
                   "w": rng.integers(0, 20_000, n).astype(np.int32)})
    dev, host, attrs = _grouped(frame, ("k",), [A.sum("w"), A.count("w"),
                                                A.min("w"), A.max("w")])
    assert attrs["lowering"] == "dense-tile"
    assert host.to_pydict()["sum(w)"].min() > 1 << 24   # past float32's ints
    for name in host.columns:
        np.testing.assert_array_equal(dev.to_pydict()[name],
                                      host.to_pydict()[name], err_msg=name)


def test_an_all_null_group_and_a_gap_in_the_keys():
    """Group 4 holds only NULL values (empty -> NULL for sum/min/avg, 0 for
    count); key 2 has no row at all, so its slot stays empty."""
    k = np.array([0, 0, 1, 1, 3, 3, 4, 4, np.nan, np.nan])
    v = np.array([1.0, 2.0, 3.0, np.nan, 5.0, 6.0, np.nan, np.nan, 9.0, 1.0])
    dev, host, attrs = _grouped(
        Frame({"k": k, "v": v}), ("k",),
        [A.count("v"), A.sum("v"), A.avg("v"), A.min("v"), A.max("v"),
         A.first("v", ignorenulls=True), A.AggExpr("count", None)])
    assert attrs["lowering"] == "dense-tile" and attrs["groups"] == 5
    got = dev.to_pydict()
    assert np.isnan(got["k"][0]) and list(got["k"][1:]) == [0, 1, 3, 4]
    assert got["count(v)"][-1] == 0 and np.isnan(got["sum(v)"][-1])
    assert np.isnan(got["min(v)"][-1]) and np.isnan(got["avg(v)"][-1])
    _assert_match(dev, host)


@pytest.mark.parametrize("slots,lowering", [(128, "dense-tile"),
                                            (129, "dense"), (96, "dense-tile"),
                                            (500, "dense")])
@pytest.mark.parametrize("kind", ["f", "i"])
def test_key_range_at_the_tile_edge(slots, lowering, kind):
    """One float key spanning ``slots`` - 1 values packs to ``slots`` slots
    (the NULL digit included), one int key spanning ``slots`` values too:
    128 is the last range the tile tier takes; 129 takes the scatter tier,
    in the same program, and agrees."""
    rng = np.random.default_rng(slots)
    n = 20_000
    if kind == "f":
        k = rng.integers(0, slots - 1, n).astype(np.float64)
        k[:2] = (0, slots - 2)                   # the whole range is there
        k[2:40] = np.nan
    else:
        k = rng.integers(-5, slots - 5, n).astype(np.int32)
        k[:2] = (-5, slots - 6)
    frame = Frame({"k": k, "v": rng.normal(size=n),
                   "w": rng.integers(0, 9, n).astype(np.int32)})
    aggs = [A.AggExpr("count", None), A.sum("v"), A.avg("v"), A.max("v"),
            A.sum("w"), A.min("w"), A.stddev("v")]
    dev, host, attrs = _grouped(frame, ("k",), aggs)
    assert attrs["lowering"] == lowering
    moved = attrs["moved"]
    assert moved.get("grouped.tile", 0) == (lowering == "dense-tile")
    assert moved["grouped.compile"] == 1 and moved["frame.host_sync"] == 1
    assert moved["grouped.rows"] == 32_768        # the bucket of 20,000
    assert "grouped.dense_miss" not in moved
    _assert_match(dev, host)


@pytest.mark.parametrize("n", [140_003, 200_000, 131_073])
@pytest.mark.parametrize("block", [4_096, 1 << 16, 1 << 19])
def test_blocks_that_do_not_divide_the_rows(monkeypatch, n, block):
    """Above the exact-bucket threshold the program sees n itself: whole
    blocks in a loop, then the shorter rest."""
    monkeypatch.setattr(segments, "_TILE_BLOCK", block)
    rng = np.random.default_rng(n)
    frame = Frame({"a": rng.integers(0, 3, n).astype(np.int32),
                   "b": rng.integers(0, 2, n).astype(np.int32),
                   "v": rng.normal(1e4, 3e3, n)})
    dev, host, attrs = _grouped(
        frame, ("a", "b"), [A.sum("v"), A.avg("v"), A.AggExpr("count", None),
                            A.min("v"), A.last("v")])
    size = segments._tile_blocks(n)[0]
    assert attrs["lowering"] == "dense-tile"
    assert attrs["blocks"] == -(-n // size) and attrs["rows"] == n
    assert n % size, "the case must leave a rest"
    _assert_match(dev, host)


@pytest.mark.parametrize("n", [140_003, 262_144])
def test_scatter_tier_reads_rows_block_by_block_too(monkeypatch, n):
    monkeypatch.setattr(segments, "_SCATTER_BLOCK", 1 << 15)
    rng = np.random.default_rng(n)
    frame = Frame({"k": rng.integers(0, 5_000, n).astype(np.int32),
                   "v": rng.normal(size=n),
                   "w": rng.integers(-9, 9, n).astype(np.int32)})
    dev, host, attrs = _grouped(
        frame, ("k",), [A.sum("v"), A.variance("v"), A.max("w"), A.sum("w"),
                        A.first("v"), A.AggExpr("count", None)])
    assert attrs["lowering"] == "dense"
    assert "grouped.tile" not in attrs["moved"]
    _assert_match(dev, host, rtol=1e-9)


def test_float32_sums_stay_within_a_few_ulps():
    """The error the cell's limit holds: float32 values of 4e4 summed to
    1e9 a group. A running float32 total drifts by 1e-5 and more here; the
    blocked, pairwise reduction stays near one ulp of the result."""
    n = 400_000
    rng = np.random.default_rng(9)
    k = jnp.asarray(rng.integers(0, 4, n), jnp.int32)
    v = jnp.asarray(rng.uniform(900.0, 105_000.0, n), jnp.float32)
    program = segments._build_dense_agg_program(
        ("i",), (("sum", 0, False),), ("f",), segments._DENSE_MAX)()
    _, (sums,), groups, ok, tiled = jax.jit(program)(
        (k,), (v,), jnp.ones(n, bool))
    assert bool(ok) and bool(tiled) and int(groups) == 4
    assert sums.dtype == jnp.float32
    want = np.bincount(np.asarray(k), weights=np.asarray(v, np.float64))
    rel = np.abs(np.asarray(sums[:4], np.float64) - want) / want
    assert rel.max() < 5e-7, rel


# -- TPC-H Q1 against the benchmark's plain reference ----------------------

def _config_module():
    spec = importlib.util.spec_from_file_location(
        "tpch_q1_lineitem", os.path.join(CONFIGS, "tpch-q1-lineitem.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    with open(os.path.join(CONFIGS, "tpch-q1-lineitem.json")) as f:
        return module, json.load(f)


Q1 = """SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty,
    sum(l_extendedprice) AS sum_base_price,
    sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
    sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
    avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price,
    avg(l_discount) AS avg_disc, count(*) AS count_order
    FROM lineitem WHERE l_shipdate <= {bound}
    GROUP BY l_returnflag, l_linestatus
    ORDER BY l_returnflag, l_linestatus"""


@pytest.mark.parametrize("seed", [1, 2_600_000_011])
def test_q1_through_sql_agrees_with_the_plain_reference(session, seed):
    mod, cfg = _config_module()
    table = mod.make_table(cfg, seed, 50_000)
    host = jax.device_get(table)
    session.create_data_frame(table).create_or_replace_temp_view("lineitem")
    obs.enable()
    got = session.sql(Q1.format(bound=mod.cutoff(cfg, 90))).to_pydict()
    flushes = [s.attrs for s in obs.TRACER.spans()
               if s.name == "frame.grouped.flush"]
    obs.disable()
    assert counters.get("grouped.tile") == 1
    assert counters.get("grouped.fallback") == 0
    assert counters.get("grouped.compile") == 1
    grouped = [a for a in flushes if a["op"] == "group_by"]
    assert len(grouped) == 1 and grouped[0]["lowering"] == "dense-tile"
    assert grouped[0]["groups"] == 4 and grouped[0]["rows"] == 50_000
    want = mod.q1(cfg, host, 90)
    flags, statuses = cfg["codes"]["l_returnflag"], \
        cfg["codes"]["l_linestatus"]
    assert [flags[c] for c in got["l_returnflag"]] == want["l_returnflag"]
    assert [statuses[c] for c in got["l_linestatus"]] == \
        want["l_linestatus"]
    np.testing.assert_array_equal(got["count_order"], want["count_order"])
    for name in mod.SUMS + mod.AVGS:
        # float32 columns, float32 expressions, float32 result columns
        np.testing.assert_allclose(got[name], want[name], rtol=2e-6,
                                   err_msg=name)


# -- no n-row operand with a narrow minor dimension ------------------------

def _shapes(jaxpr, out):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                out.append(tuple(v.aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, out)
    return out


PLANS = {
    "q1": (("i", "i"), (jnp.int32, jnp.int32), ("f",) * 5,
           (("sum", 0, False), ("sum", 1, False), ("sum", 2, False),
            ("sum", 3, False), ("avg", 0, False), ("avg", 1, False),
            ("avg", 4, False), ("count", -1, False))),
    "guest": (("i",), (jnp.int32,), ("f",),
              (("count", -1, False), ("avg", 0, False), ("max", 0, False))),
    "variance": (("f",), (jnp.float32,), ("f", "i"),
                 (("stddev", 0, False), ("sum", 1, False),
                  ("first", 0, True), ("min", 1, False))),
}


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_dense_program_at_1e8_rows_builds_no_padded_operand(plan):
    n = 100_000_000
    key_kinds, key_types, val_kinds, agg_ops = PLANS[plan]
    program = segments._build_dense_agg_program(
        key_kinds, agg_ops, val_kinds, segments._DENSE_MAX)()
    keys = tuple(jax.ShapeDtypeStruct((n,), t) for t in key_types)
    vals = tuple(jax.ShapeDtypeStruct(
        (n,), jnp.float32 if k == "f" else jnp.int32) for k in val_kinds)
    closed = jax.make_jaxpr(program)(keys, vals,
                                     jax.ShapeDtypeStruct((n,), jnp.bool_))
    shapes = _shapes(closed.jaxpr, [])
    assert len(shapes) > 50
    padded = [s for s in shapes
              if len(s) >= 2 and s[0] >= n and s[-1] < 128]
    assert not padded, padded[:5]
    # and nothing holds more than one column's worth of rows
    assert max(int(np.prod(s)) for s in shapes) <= n


# ---------------------------------------------------------------------------
# What follows the verdict: one dispatch each, and no read for a compact sort
# ---------------------------------------------------------------------------

@pytest.fixture
def accelerator_sort(monkeypatch):
    """``device_sort`` takes its ``lax.sort`` program (the accelerators'
    branch) although the tests run on XLA:CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("masked", [False, True])
def test_sort_program_reads_its_row_count_only_for_masked_input(
        accelerator_sort, masked):
    rng = np.random.default_rng(5)
    k = rng.integers(0, 50, 300).astype(np.int32)
    v = rng.normal(size=300).astype(np.float32)
    frame = Frame({"k": k, "v": v})
    keep = np.ones(300, bool)
    if masked:
        frame = frame.filter(E.col("v") > 0)
        keep = v > 0
    assert frame._every_slot_valid() is (not masked)
    frame._data                                   # settle the filter
    counters.clear("host.")
    counters.clear("frame.")
    out = frame.sort("k", "v")
    assert counters.get("host.reads") == (1 if masked else 0)
    assert counters.get("frame.host_sync") == (1 if masked else 0)
    got = out.to_pydict()
    order = np.lexsort((v[keep], k[keep]))
    np.testing.assert_array_equal(got["k"], k[keep][order])
    np.testing.assert_array_equal(got["v"], v[keep][order])
    assert out.num_slots == int(keep.sum()) and out._every_slot_valid()


def test_a_frame_with_a_given_or_derived_mask_is_not_called_compact():
    frame = Frame({"a": np.arange(4)})
    assert frame._every_slot_valid()
    assert not Frame({"a": np.arange(4)},
                     mask=np.ones(4, bool))._every_slot_valid()
    assert frame.select("a")._every_slot_valid()       # the same mask
    assert not frame.filter(E.col("a") > 1)._every_slot_valid()
    assert not frame.with_column("b", E.col("a") + 1)._every_slot_valid()


def test_grouped_result_and_its_sort_dispatch_one_program_each(
        accelerator_sort, monkeypatch):
    """The k+m result slices, the plan inputs' pads and the payload's takes
    are one jitted call each, not one eager dispatch per column."""
    calls = []
    for name in ("_unpad_tree", "_pad_tree", "_take_tree"):
        inner = getattr(segments, name)
        monkeypatch.setattr(
            segments, name,
            lambda *a, _inner=inner, _name=name: (calls.append(_name),
                                                  _inner(*a))[1])
    frame = _frame(11, 3_000, ("i1", "i2"))
    out = frame.group_by("i1", "i2").agg(
        A.sum("v"), A.avg("w"), A.AggExpr("count", None))
    assert calls == ["_pad_tree", "_unpad_tree"]
    want = out.to_pydict()
    del calls[:]
    got = out.sort("i1", "i2").to_pydict()
    assert calls == ["_pad_tree", "_take_tree"]   # 20 groups, bucket 32
    for name in want:       # grouped output is already in key order
        np.testing.assert_array_equal(got[name], want[name])


def test_q1_reads_the_host_twice_on_the_accelerator_branch(
        session, accelerator_sort):
    """The grouped verdict and ``to_pydict``: the ORDER BY of a GROUP BY's
    compact result needs no row count from the device."""
    mod, cfg = _config_module()
    table = mod.make_table(cfg, 3, 20_000)
    host = jax.device_get(table)
    session.create_data_frame(table).create_or_replace_temp_view("lineitem")
    query = Q1.format(bound=mod.cutoff(cfg, 90))
    session.sql(query).to_pydict()
    counters.clear("host.")
    counters.clear("frame.")
    got = session.sql(query).to_pydict()
    assert counters.get("host.reads") == 2
    assert counters.get("frame.host_sync") == 2
    want = mod.q1(cfg, host, 90)
    flags = cfg["codes"]["l_returnflag"]
    assert [flags[c] for c in got["l_returnflag"]] == want["l_returnflag"]
    np.testing.assert_array_equal(got["count_order"], want["count_order"])
