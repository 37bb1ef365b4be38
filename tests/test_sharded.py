"""Sharded frames (ISSUE 13): row-partitioned Frame/SQL execution.

The acceptance surface of the sharded-frames refactor:

* **bit-parity** — the full compilable-op sweep over masked rows must
  produce BIT-identical results with ``spark.shard.enabled`` on vs off
  (the elementwise shard_map lowering makes this a construction
  property), across 2/4/8 forced host devices and the edge shapes
  (all-masked, one-row-per-shard, rows < devices);
* **structural pins on CPU** — one fused program per flush with ZERO
  counted host syncs, grouped aggregation = ONE sync, collect = ONE
  sync, steady-state cache replay = zero new compiles, sharded and
  single-device plans coexisting in one cache;
* **degradation ladders** — ``shard_flush`` (device fault → gather to
  single-device → eager replay) and ``shard_merge`` (fault in the merge
  collective → gather) keep results correct under injected chaos;
* **integration** — session conf save/restore, sharded ingest hand-off,
  EXPLAIN's ``ShardedStage``/``Exchange`` operators, statstore keys,
  program-audit handles (mesh + guard declared), the fit-packing
  pass-through, and serving under concurrency.

The golden workload (dataset-abstract: count 24 / RMSE 2.809940;
dataset-full: RMSE 1.805140) is pinned with sharding ON.
"""

import contextlib
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame.frame import Frame
from sparkdq4ml_tpu.ops import compiler
from sparkdq4ml_tpu.ops import expressions as E
from sparkdq4ml_tpu.ops import segments
from sparkdq4ml_tpu.parallel import mesh as pmesh
from sparkdq4ml_tpu.parallel import shard
from sparkdq4ml_tpu.utils import faults, profiling
from sparkdq4ml_tpu.utils.recovery import RECOVERY_LOG

DATA_DIR = os.path.join(os.path.dirname(__file__), "..", "data")

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs the conftest's 8 forced host devices")


@contextlib.contextmanager
def sharding(min_rows=8, devices=0):
    """Enable the shard context over the forced-host-device mesh for one
    test block, with full save/restore (the session-free equivalent of
    ``spark.shard.*`` conf)."""
    saved = (config.shard_enabled, config.shard_min_rows,
             config.shard_devices)
    config.shard_enabled = True
    config.shard_min_rows = min_rows
    config.shard_devices = devices
    shard.configure(pmesh.make_mesh())
    try:
        yield
    finally:
        (config.shard_enabled, config.shard_min_rows,
         config.shard_devices) = saved
        shard.reset()


def _frame(n=100, seed=0, with_nan=True, mask_frac=0.3):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=n)
    if with_nan and n:
        a[rng.integers(0, n, max(n // 7, 1))] = np.nan
    cols = {
        "a": a,
        "b": rng.integers(-5, 9, n).astype(np.int64),
        "c": rng.uniform(0.1, 10.0, n),
        "flag": rng.integers(0, 2, n).astype(bool),
    }
    f = Frame(cols)
    if mask_frac and n:
        keep = jnp.asarray(rng.random(n) >= mask_frac)
        f = f._with(mask=jnp.logical_and(f._mask, keep))
    return f


def _eq(a: dict, b: dict):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"column {k!r}")


#: The compilable-op sweep: every family the pipeline compiler defers.
SWEEP = [
    ("arith", lambda f: f.with_column("o", E.col("a") * 2.5 + E.col("c"))),
    ("div_mod", lambda f: f.with_column("o", E.col("c") / 3.0)
        .with_column("p", E.col("b") % 4)),
    ("cmp_filter", lambda f: f.filter(E.col("a") > 0.1)),
    ("bool_ops", lambda f: f.filter((E.col("c") > 1.0) & ~E.col("flag")
                                    | (E.col("b") == 2))),
    ("neg_cast", lambda f: f.with_column("o", (-E.col("a")).cast("int"))),
    ("isnull", lambda f: f.with_column("o", E.col("a").is_null())),
    ("case_when", lambda f: f.with_column(
        "o", E.when(E.col("a") > 0, E.col("c")).otherwise(E.col("b")))),
    ("isin", lambda f: f.filter(E.col("b").isin(1, 2, 5))),
    ("funcs", lambda f: f.with_column("o", E.Func("sqrt", [E.col("c")]))
        .with_column("p", E.Func("pow", [E.col("c"), E.Lit(2)]))),
    ("with_columns", lambda f: f.with_columns(
        {"o": E.col("a") + 1, "a": E.col("a") * 0.0})),
    ("chain20", lambda f: _chain20(f)),
    # a registered rule whose function is row-local runs inside the
    # shard_map program like a builtin (ISSUE 31)
    ("udf_rule", lambda f: _rule_chain(f)),
    ("udf_rule_nested", lambda f: f.filter(
        E.UdfCall("floorRule", [E.col("c") * 3.0], _RULES) > 0)),
    ("fused_select", lambda f: f.filter(E.col("c") > 0.5).select(
        (E.col("c") * 2).alias("o"), (E.col("b") + 1).alias("p"))),
]


def _floor_rule(x):
    return jnp.where(x < 4.0, -1.0, x)


def _pair_rule(x, k):
    bad = jnp.logical_or(jnp.isnan(x), jnp.logical_and(k < 0, x > 5.0))
    return jnp.where(bad, -1.0, x)


def _make_rules():
    from sparkdq4ml_tpu.ops.udf import UDFRegistry

    reg = UDFRegistry()
    reg.register("floorRule", _floor_rule, "double")
    reg.register("pairRule", _pair_rule, "double")
    return reg


_RULES = _make_rules()


def _rule_chain(f):
    f = f.with_column("r1", E.UdfCall("floorRule", [E.col("c")], _RULES))
    f = f.filter(E.col("r1") > 0)
    f = f.with_column(
        "r2", E.UdfCall("pairRule", [E.col("a"), E.col("b")], _RULES))
    return f.filter(E.col("r2") > 0)


def _chain20(f):
    for i in range(10):
        f = f.with_column(f"x{i}", E.col("c") * float(i + 1) - 0.5)
        f = f.filter(E.col(f"x{i}") > float(-10 - i))
    return f


class TestBitParity:
    @pytest.mark.parametrize("name,op", SWEEP, ids=[n for n, _ in SWEEP])
    def test_sweep_bit_identical(self, name, op):
        f = _frame()
        ref = op(f).to_pydict()
        with sharding():
            out = op(shard.shard_frame(f)).to_pydict()
        _eq(ref, out)

    def test_deferred_rule_on_a_sharded_frame_is_one_program(self):
        """The rule defers on a sharded frame as on its single-device
        twin: one shard_map program a flush, no eager evaluation, no
        degraded path, the same rows."""
        f = _frame(200, seed=11)
        ref = _rule_chain(f)
        assert len(ref._pending) == 4
        ref = ref.to_pydict()
        profiling.counters.clear("dq.")
        profiling.counters.clear("pipeline")
        with sharding():
            g = _rule_chain(shard.shard_frame(f))
            assert len(g._pending) == 4 and g._shard is not None
            out = g.to_pydict()
        _eq(ref, out)
        assert profiling.counters.get("pipeline.flush") == 1
        assert profiling.counters.get("dq.rule_in_flush") == 2
        assert profiling.counters.get("dq.rule_eager") == 0
        assert profiling.counters.get("pipeline.fallback") == 0
        assert profiling.counters.get("pipeline.shard_gather") == 0

    @pytest.mark.parametrize("devices", [2, 4, 8])
    def test_device_counts(self, devices):
        f = _frame(seed=3)
        ref = _chain20(f).to_pydict()
        with sharding(devices=devices):
            g = shard.shard_frame(f)
            assert g._shard.devices == devices
            _eq(ref, _chain20(g).to_pydict())

    def test_edge_shapes(self):
        with sharding(min_rows=1):
            # all-masked
            f = _frame(32, seed=5)
            f = f._with(mask=jnp.zeros((f.num_slots,), jnp.bool_))
            ref = _chain20(f).to_pydict()
            _eq(ref, _chain20(shard.shard_frame(f)).to_pydict())
            # rows < devices
            f3 = _frame(3, seed=6, mask_frac=0.0)
            _eq(_chain20(f3).to_pydict(),
                _chain20(shard.shard_frame(f3)).to_pydict())

    def test_one_row_per_shard(self):
        saved = config.pipeline_min_bucket
        config.pipeline_min_bucket = 1
        try:
            with sharding(min_rows=1):
                f = _frame(8, seed=7, mask_frac=0.0)
                g = shard.shard_frame(f)
                assert g._shard.bucket == 1 and g.num_slots == 8
                _eq(_chain20(f).to_pydict(), _chain20(g).to_pydict())
        finally:
            config.pipeline_min_bucket = saved

    def test_empty_frame_never_shards(self):
        with sharding(min_rows=1):
            f = Frame({"a": np.asarray([], np.float64)})
            assert shard.maybe_shard_frame(f) is f

    def test_below_min_rows_never_shards(self):
        with sharding(min_rows=1000):
            f = _frame(50)
            assert shard.maybe_shard_frame(f) is f

    def test_raw_column_at_true_row_count_places(self):
        with sharding():
            f = _frame(40, mask_frac=0.0)
            g = shard.shard_frame(f)
            vals = np.arange(40, dtype=np.float64)
            out = g.with_column("raw", vals)
            ref = f.with_column("raw", vals)
            _eq(ref.to_pydict(), out.to_pydict())


class TestStructuralPins:
    def test_flush_zero_host_syncs_and_one_program(self):
        with sharding():
            g = shard.shard_frame(_frame(200, seed=9))
            g = _chain20(g)
            before_sync = profiling.counters.get("frame.host_sync")
            before_flush = profiling.counters.get("pipeline.flush")
            jax.block_until_ready(g._mask)          # forces the flush
            assert profiling.counters.get("frame.host_sync") \
                == before_sync
            assert profiling.counters.get("pipeline.flush") \
                == before_flush + 1                  # ONE fused program

    def test_collect_is_one_sync(self):
        with sharding():
            g = shard.shard_frame(_frame(64, seed=10))
            g._mask                                  # settle pending
            before = profiling.counters.get("frame.host_sync")
            g.to_pydict()
            assert profiling.counters.get("frame.host_sync") == before + 1

    def test_grouped_is_one_sync(self):
        with sharding():
            g = shard.shard_frame(_frame(128, seed=11))
            g._mask
            before = profiling.counters.get("frame.host_sync")
            g.group_by("b").agg({"c": "sum"})
            assert profiling.counters.get("frame.host_sync") == before + 1

    def test_cache_replay_zero_new_compiles(self):
        with sharding():
            g1 = shard.shard_frame(_frame(77, seed=12))
            _chain20(g1).to_pydict()
            before = profiling.counters.get("pipeline.compile")
            g2 = shard.shard_frame(_frame(77, seed=13))
            _chain20(g2).to_pydict()
            assert profiling.counters.get("pipeline.compile") == before

    def test_sharded_and_single_plans_coexist(self):
        compiler.clear_cache()
        f = _frame(66, seed=14)
        step = lambda fr: fr.with_column("o", E.col("c") * 7.0)  # noqa: E731
        step(f).to_pydict()
        with sharding():
            step(shard.shard_frame(f)).to_pydict()
        keys = [e["program_key"] for e in compiler.cache_stats()["entries"]]
        tagged = [k for k in keys if k.startswith("shard[")]
        plain = [k for k in keys if not k.startswith("shard[")]
        assert tagged and plain
        # and the single-device plan still replays cleanly
        before = profiling.counters.get("pipeline.compile")
        step(f._with()).to_pydict()
        assert profiling.counters.get("pipeline.compile") == before

    def test_sharded_layout_in_explain_string(self):
        with sharding():
            g = shard.shard_frame(_frame(40, mask_frac=0.0))
            text = g.explain_string()
            assert "row-sharded over 8 device(s)" in text


class TestGroupedSharded:
    def _cmp(self, ref, out, int_cols=()):
        assert set(ref) == set(out)
        for k in ref:
            r, o = np.asarray(ref[k]), np.asarray(out[k])
            if k in int_cols or r.dtype.kind in "iub":
                np.testing.assert_array_equal(r, o, err_msg=k)
            else:
                np.testing.assert_allclose(r, o, rtol=1e-9, atol=1e-12,
                                           equal_nan=True, err_msg=k)

    def test_full_agg_family_parity(self):
        f = _frame(300, seed=20)
        aggs = {"a": "avg", "c": "sum"}
        ref = f.group_by("b").agg(aggs).to_pydict()
        with sharding():
            out = shard.shard_frame(f).group_by("b").agg(aggs).to_pydict()
        self._cmp(ref, out)

    @pytest.mark.parametrize("fn", ["count", "sum", "avg", "min", "max",
                                    "variance", "stddev", "var_pop",
                                    "stddev_pop"])
    def test_each_fn(self, fn):
        f = _frame(200, seed=21)
        ref = f.group_by("b").agg({"a": fn, "c": fn}).to_pydict()
        with sharding():
            out = shard.shard_frame(f).group_by("b") \
                .agg({"a": fn, "c": fn}).to_pydict()
        self._cmp(ref, out)

    def test_int_sums_exact(self):
        f = _frame(500, seed=22)
        ref = f.group_by("flag").agg({"b": "sum"}).to_pydict()
        with sharding():
            out = shard.shard_frame(f).group_by("flag") \
                .agg({"b": "sum"}).to_pydict()
        self._cmp(ref, out, int_cols=("sum(b)",))

    def test_float_keys_with_nulls(self):
        f = _frame(150, seed=23)
        ref = f.group_by("a").count().to_pydict()
        with sharding():
            out = shard.shard_frame(f).group_by("a").count().to_pydict()
        self._cmp(ref, out)

    def test_unsupported_aggs_gather_and_stay_correct(self):
        f = _frame(120, seed=24)
        for aggs in ({"c": "first"}, {"b": "count_distinct"}):
            ref = f.group_by("flag").agg(aggs).to_pydict()
            with sharding():
                out = shard.shard_frame(f).group_by("flag") \
                    .agg(aggs).to_pydict()
            self._cmp(ref, out)

    def test_dense_range_miss_reroutes_correctly(self):
        # huge key spread defeats the dense table → sorted single-device
        rng = np.random.default_rng(25)
        f = Frame({"k": rng.integers(0, 2**40, 90).astype(np.float64),
                   "v": rng.normal(size=90)})
        ref = f.group_by("k").agg({"v": "sum"}).to_pydict()
        with sharding():
            before = profiling.counters.get("grouped.dense_miss")
            out = shard.shard_frame(f).group_by("k") \
                .agg({"v": "sum"}).to_pydict()
            assert profiling.counters.get("grouped.dense_miss") > before
        self._cmp(ref, out)

    def test_distinct_parity_and_order(self):
        f = _frame(140, seed=26)
        ref = f.select("b", "flag").distinct().to_pydict()
        with sharding():
            out = shard.shard_frame(f).select("b", "flag") \
                .distinct().to_pydict()
        _eq(ref, out)

    def test_drop_duplicates_parity(self):
        f = _frame(90, seed=27)
        ref = f.drop_duplicates(["b"]).to_pydict()
        with sharding():
            out = shard.shard_frame(f).drop_duplicates(["b"]).to_pydict()
        _eq(ref, out)

    def test_sort_parity(self):
        f = _frame(80, seed=28)
        ref = f.sort("a", "b").to_pydict()
        with sharding():
            out = shard.shard_frame(f).sort("a", "b").to_pydict()
        _eq(ref, out)


class TestJoinSharded:
    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer",
                                     "left_semi", "left_anti"])
    def test_parity(self, how):
        rng = np.random.default_rng(30)
        l = Frame({"k": rng.integers(0, 12, 70).astype(np.float64),
                   "v": rng.normal(size=70)})
        r = Frame({"k": rng.integers(0, 15, 50).astype(np.float64),
                   "w": rng.normal(size=50)})
        ref = l.join(r, "k", how).to_pydict()
        with sharding():
            before = profiling.counters.get("shard.join_partitioned")
            out = shard.shard_frame(l).join(shard.shard_frame(r),
                                            "k", how).to_pydict()
            assert profiling.counters.get("shard.join_partitioned") \
                == before + 1
        _eq(ref, out)

    def test_multi_key_and_nan_keys(self):
        rng = np.random.default_rng(31)
        k1 = rng.integers(0, 5, 60).astype(np.float64)
        k1[::9] = np.nan
        l = Frame({"k1": k1, "k2": rng.integers(0, 3, 60).astype(np.float64),
                   "v": rng.normal(size=60)})
        r = Frame({"k1": k1[:40].copy(), "k2": rng.integers(0, 3, 40)
                   .astype(np.float64), "w": rng.normal(size=40)})
        ref = l.join(r, ["k1", "k2"], "inner").to_pydict()
        with sharding():
            out = shard.shard_frame(l).join(shard.shard_frame(r),
                                            ["k1", "k2"],
                                            "inner").to_pydict()
        _eq(ref, out)

    def test_below_min_rows_host_fallback(self):
        rng = np.random.default_rng(32)
        l = Frame({"k": rng.integers(0, 5, 30).astype(np.float64)})
        r = Frame({"k": rng.integers(0, 5, 20).astype(np.float64)})
        ref = l.join(r, "k", "inner").to_pydict()
        with sharding(min_rows=8):
            ls, rs = shard.shard_frame(l), shard.shard_frame(r)
            config.shard_min_rows = 10_000   # join below the bound
            before = profiling.counters.get("shard.join_partitioned")
            out = ls.join(rs, "k", "inner").to_pydict()
            assert profiling.counters.get("shard.join_partitioned") \
                == before
        _eq(ref, out)


class TestLadders:
    def test_shard_flush_device_error_recovers(self):
        f = _frame(100, seed=40)
        ref = _chain20(f).to_pydict()
        with sharding():
            g = shard.shard_frame(f)
            with faults.inject_faults("shard_flush:device_error:1",
                                      seed=3) as plan:
                out = _chain20(g).to_pydict()
            assert plan.fired
        _eq(ref, out)

    def test_persistent_fault_gathers_and_degrades(self):
        f = _frame(100, seed=41)
        ref = _chain20(f).to_pydict()
        RECOVERY_LOG.clear()
        with sharding():
            g = _chain20(shard.shard_frame(f))
            with faults.inject_faults(
                    "shard_flush:device_error:1,2,3,4,5,6,7,8", seed=3):
                out = g.to_pydict()
            ev = RECOVERY_LOG.events(site="shard_flush",
                                     action="fallback")
            assert ev and ev[-1].rung == "gather"
            assert g._shard is None          # layout degraded, data safe
        _eq(ref, out)

    def test_shard_merge_fault_gathers(self):
        f = _frame(100, seed=42)
        ref = f.group_by("b").agg({"c": "sum"}).to_pydict()
        RECOVERY_LOG.clear()
        with sharding():
            g = shard.shard_frame(f)
            before = profiling.counters.get("grouped.shard_gather")
            with faults.inject_faults("shard_merge:device_error:1",
                                      seed=3) as plan:
                out = g.group_by("b").agg({"c": "sum"}).to_pydict()
            assert plan.fired
            assert profiling.counters.get("grouped.shard_gather") \
                == before + 1
        for k in ref:
            np.testing.assert_allclose(np.asarray(ref[k]),
                                       np.asarray(out[k]), rtol=1e-9)

    def test_distinct_merge_fault_gathers(self):
        f = _frame(100, seed=43)
        ref = f.select("b").distinct().to_pydict()
        with sharding():
            g = shard.shard_frame(f)
            with faults.inject_faults("shard_merge:device_error:1",
                                      seed=3) as plan:
                out = g.select("b").distinct().to_pydict()
            assert plan.fired
        _eq(ref, out)

    def test_oom_budget_degrades_to_chunked(self):
        f = _frame(200, seed=44)
        ref = _chain20(f).to_pydict()
        RECOVERY_LOG.clear()
        with sharding():
            g = _chain20(shard.shard_frame(f))
            before = profiling.counters.get("pipeline.oom_chunked")
            with faults.inject_faults("oom:oom:1:n=64", seed=3):
                out = g.to_pydict()
            assert profiling.counters.get("pipeline.oom_chunked") \
                == before + 1
            ev = RECOVERY_LOG.events(site="shard_flush",
                                     action="fallback")
            assert ev and ev[-1].rung == "chunked"
        _eq(ref, out)

    def test_nan_corruption_arm_still_validates(self):
        f = _frame(100, seed=45, with_nan=False, mask_frac=0.0)
        ref = f.with_column("o", E.col("c") * 2).to_pydict()
        with sharding():
            g = shard.shard_frame(f)
            with faults.inject_faults("pipeline_flush:nan:1", seed=5):
                out = g.with_column("o", E.col("c") * 2).to_pydict()
        _eq(ref, out)


class TestSessionConfAndIngest:
    def _session(self, **extra):
        import sparkdq4ml_tpu as dq

        b = (dq.TpuSession.builder().app_name("shard-test")
             .master("local[*]")
             .config("spark.shard.enabled", "true")
             .config("spark.shard.minRows", "8"))
        for k, v in extra.items():
            b = b.config(k, v)
        return b.get_or_create()

    def test_conf_applies_and_stop_restores(self):
        prev = (config.shard_enabled, config.shard_min_rows)
        s = self._session()
        try:
            assert config.shard_enabled is True
            assert config.shard_min_rows == 8
            assert shard.active_mesh() is not None
        finally:
            s.stop()
        assert (config.shard_enabled, config.shard_min_rows) == prev
        assert shard.active_mesh() is None

    def test_read_csv_lands_sharded_and_explain_renders(self):
        import sparkdq4ml_tpu as dq

        s = self._session()
        try:
            dq.register_builtin_rules()
            df = (s.read.format("csv").option("inferSchema", "true")
                  .load(os.path.join(DATA_DIR, "dataset-abstract.csv")))
            assert df._shard is not None
            assert df._shard.devices == 8
            df.create_or_replace_temp_view("prices")
            plan = s.sql("EXPLAIN SELECT _c1 p FROM prices "
                         "WHERE _c1 > 0").to_pydict()["plan"][0]
            assert "ShardedStage[8]" in plan
            assert "rows_per_shard" in plan
            agg_plan = s.sql(
                "EXPLAIN SELECT _c0, count(*) c FROM prices "
                "GROUP BY _c0").to_pydict()["plan"][0]
            assert "Exchange[merge:psum]" in agg_plan
        finally:
            s.stop()

    def test_golden_workload_sharded(self):
        import sparkdq4ml_tpu as dq
        from sparkdq4ml_tpu.models import LinearRegression, VectorAssembler

        s = self._session()
        try:
            dq.register_builtin_rules()
            df = (s.read.format("csv").option("inferSchema", "true")
                  .load(os.path.join(DATA_DIR, "dataset-abstract.csv")))
            df = df.with_column_renamed("_c0", "guest") \
                   .with_column_renamed("_c1", "price")
            df = df.with_column(
                "price_no_min",
                dq.call_udf("minimumPriceRule", dq.col("price")))
            df.create_or_replace_temp_view("price")
            df = s.sql("SELECT cast(guest as int) guest, price_no_min AS "
                       "price FROM price WHERE price_no_min > 0")
            df = df.with_column(
                "price_correct_correl",
                dq.call_udf("priceCorrelationRule", dq.col("price"),
                            dq.col("guest")))
            df.create_or_replace_temp_view("price")
            df = s.sql("SELECT guest, price_correct_correl AS price "
                       "FROM price WHERE price_correct_correl > 0")
            assert df.count() == 24
            df = df.with_column("label", df.col("price"))
            df = VectorAssembler(["guest"], "features").transform(df)
            model = LinearRegression(max_iter=40, reg_param=1.0,
                                     elastic_net_param=1.0).fit(df)
            assert model.summary.root_mean_squared_error == pytest.approx(
                2.809940, rel=1e-3)
        finally:
            s.stop()

    def test_serving_soak_with_sharding(self):
        """8 concurrent golden queries through the QueryServer with
        sharding active: bounded results, golden numbers, no deadlock
        (the shard execution guard serializes multi-device dispatch)."""
        import sparkdq4ml_tpu as dq
        from sparkdq4ml_tpu.serve import QueryServer

        s = self._session()
        path = os.path.join(DATA_DIR, "dataset-abstract.csv")

        def job(ctx):
            df = (ctx.read.format("csv").option("inferSchema", "true")
                  .load(path))
            ctx.register_view("t", df)
            out = ctx.sql("SELECT count(*) c FROM t WHERE _c1 > 0")
            return int(out.to_pydict()["c"][0])

        try:
            with QueryServer(s, workers=4, metrics_port=0) as srv:
                futs = [srv.submit(job, tenant=f"t{i % 3}")
                        for i in range(8)]
                results = [f.result(timeout=120) for f in futs]
            assert all(r.ok for r in results)
            assert len({r.value for r in results}) == 1
        finally:
            s.stop()


class TestObservatoryAndAudit:
    def test_statstore_records_shard_tagged_key(self):
        from sparkdq4ml_tpu.utils import statstore

        with sharding():
            f = _frame(120, seed=50)
            # a uniquely-NAMED filter column ⇒ a fresh selectivity entry
            # (plan keys carry column names; literals are hoisted)
            f = f._with(data={**f._data, "selbase50": f._data["c"]})
            g = shard.shard_frame(f)
            g.filter(E.col("selbase50") > 1.0)._mask  # one sharded flush
            statstore.STORE.drain_pending()
            rep = statstore.STORE.report(drain=False)
            tagged = [e for e in rep["entries"]
                      if "shard[" in e["key"] and e["kind"] == "pipeline"]
            assert tagged
            # selectivity evidence landed (the deferred per-shard counts)
            sel = [e for e in rep["entries"]
                   if e["kind"] == "filter" and "selbase50" in e["key"]]
            assert sel and sel[0]["sel_observations"] == 1
            # baseline is TRUE rows (120), never the padded slot count
            # (128) — the layout-stripped entry is shared with the
            # single-device twin and must not skew by the padding factor
            assert sel[0]["rows_in"] == 120

    def test_selectivity_key_is_layout_agnostic(self):
        from sparkdq4ml_tpu.utils.statstore import selectivity_key

        plain = "f8/i8|F:B(>,C('c':f8),Lf)"
        assert selectivity_key("shard[8]|" + plain) \
            == selectivity_key(plain)

    def test_program_handles_declare_mesh_and_guard(self):
        from sparkdq4ml_tpu.utils import observability as obs

        compiler.clear_cache()
        segments.clear_cache()
        with sharding():
            g = shard.shard_frame(_frame(64, seed=51))
            g.with_column("o", E.col("c") + 1)._mask
            g.group_by("b").agg({"c": "sum"})
        handles, errors = obs.CACHES.programs()
        assert not errors
        sharded = [h for h in handles
                   if getattr(h.mesh, "devices", None) is not None
                   and h.mesh.devices.size > 1]
        assert sharded, "no sharded ProgramHandle registered"
        assert all(h.guarded for h in sharded)

    def test_audit_collective_detector_clean(self):
        from sparkdq4ml_tpu.analysis.program import detectors as det
        from sparkdq4ml_tpu.utils import observability as obs

        compiler.clear_cache()
        segments.clear_cache()
        with sharding():
            g = shard.shard_frame(_frame(64, seed=52))
            g.group_by("b").agg({"c": "avg"})
            handles, _ = obs.CACHES.programs()
            target = [h for h in handles if "GDH" in h.program_key]
            assert target
            ctx = det.AuditContext.from_config()
            (rule,) = det.get_detectors(["audit-collective"])
            findings = []
            for h in target:
                findings.extend(rule.check(h, ctx))
            assert not findings, [f.message for f in findings]


class TestFitPassthrough:
    def test_place_sharded_consumes_shard_partials(self):
        from sparkdq4ml_tpu.parallel.distributed import place_sharded

        with sharding():
            g = shard.shard_frame(
                Frame({"x": np.arange(64, dtype=np.float64),
                       "y": np.arange(64, dtype=np.float64) * 2}))
            X = jnp.asarray(g._data["x"])[:, None]
            # a 2-D feature matrix in the frame's layout
            X = jax.device_put(X, g._shard.sharding())
            y = jnp.asarray(g._data["y"])
            m = g._mask
            before = profiling.counters.get("shard.fit_passthrough")
            Xo, yo, mo = place_sharded(X, y, m, g._shard.mesh)
            assert profiling.counters.get("shard.fit_passthrough") \
                == before + 1
            assert Xo is X and yo is y and mo is m


class TestChaosSmoke:
    @pytest.mark.slow
    def test_five_seed_soak_with_sharding(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "chaos_soak", os.path.join(os.path.dirname(__file__), "..",
                                       "scripts", "chaos_soak.py"))
        soak = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(soak)
        summary = soak.run_soak(seeds=5, clients=3, queries=1, workers=4)
        assert summary["ok"], summary["failed_seeds"]
