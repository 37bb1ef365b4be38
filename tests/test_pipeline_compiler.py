"""Fused expression-pipeline compiler (ops/compiler.py + frame deferral).

Covers the ISSUE-3 acceptance surface:

* eager-vs-fused equivalence property tests over the compilable expression
  op surface (bit-identical results, NaN-aware),
* plan-keyed jit cache reuse: a second identical SQL query and a second
  CSV load of a *different* row count within the same bucket each add
  ZERO new compiles (literal hoisting + shape-bucketed padding),
* golden DQ row counts (40→34→24) and the example-app RMSE with the
  pipeline on vs off,
* ``spark.pipeline.enabled=false`` restores the exact eager path,
* the batched host-sync / honest ``cache()`` satellites,
* fusion as counts: a 10-op chain is one flush of one compiled program.
"""

import os
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dataset_path, prepare_features, run_dq_pipeline

pytestmark = pytest.mark.pipeline_compiler
from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame.frame import Frame
from sparkdq4ml_tpu.ops import compiler
from sparkdq4ml_tpu.ops import expressions as E
from sparkdq4ml_tpu.utils.profiling import counters


@pytest.fixture(autouse=True)
def _fresh_pipeline_state():
    """Each test sees a clean plan cache / counters and pipeline ON."""
    saved = config.pipeline
    config.pipeline = True
    compiler.clear_cache()
    counters.clear("pipeline")
    counters.clear("frame.")
    yield
    config.pipeline = saved
    compiler.clear_cache()


def _eager(fn):
    """Run ``fn`` with the pipeline disabled (the exact legacy path)."""
    config.pipeline = False
    try:
        return fn()
    finally:
        config.pipeline = True


def _frames_equal(a: Frame, b: Frame):
    assert a.columns == b.columns
    da, db = a.to_pydict(), b.to_pydict()
    for name in a.columns:
        va, vb = np.asarray(da[name]), np.asarray(db[name])
        assert va.shape == vb.shape, name
        if va.dtype == object:
            assert list(va) == list(vb), name
        else:
            assert va.dtype == vb.dtype, name
            np.testing.assert_array_equal(va, vb, err_msg=name)


def _base_frame():
    return Frame({
        "price": [10.0, 25.5, 3.0, 95.0, float("nan"), 7.25],
        "guest": [2, 5, 1, 20, 8, 3],
        "flag": [True, False, True, True, False, True],
        "city": ["ny", "sf", None, "la", "ny", "sf"],
    })


# ---------------------------------------------------------------------------
# Eager-vs-fused equivalence over the compilable op surface
# ---------------------------------------------------------------------------

def _op_surface():
    c = E.col
    return [
        ("arith", lambda f: (c("price") * 2.0 + c("guest") - 1.5)),
        ("div_null", lambda f: c("price") / (c("guest") - 2)),   # /0 → NULL
        ("mod", lambda f: c("price") % 4),
        ("neg", lambda f: -c("price")),
        ("cmp_chain", lambda f: (c("price") > 5.0) & (c("guest") <= 8)),
        ("or_not", lambda f: (c("price") < 4) | ~(c("guest") == 5)),
        ("isnull", lambda f: c("price").is_null()),
        ("isnotnull", lambda f: c("price").is_not_null()),
        ("cast_int", lambda f: c("price").cast("int")),
        ("cast_double", lambda f: c("guest").cast("double")),
        ("cast_bool_int", lambda f: c("flag").cast("int")),
        ("between", lambda f: c("price").between(5, 30)),
        ("isin", lambda f: c("guest").isin(1, 5, 20)),
        ("not_isin_null", lambda f: E.InList(
            c("guest"), [E.Lit(1), E.Lit(None)], negated=True)),
        ("case_when", lambda f: E.when(c("price") < 5.0, -1.0)
         .when(c("price") > 90.0, 99.0).otherwise(c("price"))),
        ("case_no_else", lambda f: E.when(c("price") < 5.0, 1.0)),
        ("func_sqrt", lambda f: E.fn("sqrt", c("price"))),
        ("func_pow", lambda f: E.fn("pow", c("guest"), E.Lit(2))),
        ("func_greatest", lambda f: E.fn("greatest", c("price"),
                                         c("guest"))),
        ("func_coalesce", lambda f: E.fn("coalesce", c("price"),
                                         c("guest"))),
        ("func_isnan", lambda f: E.fn("isnan", c("price"))),
        ("func_pmod", lambda f: E.fn("pmod", -c("price"), c("guest"))),
        ("alias", lambda f: (c("price") + 1).alias("bumped")),
    ]


@pytest.mark.parametrize("name,build",
                         _op_surface(), ids=[n for n, _ in _op_surface()])
def test_with_column_eager_fused_equivalence(name, build):
    fused = _base_frame().with_column("out", build(None))
    assert fused._pending, f"{name} did not defer (compilable surface)"
    eager = _eager(lambda: _base_frame().with_column("out", build(None)))
    assert not eager._pending
    _frames_equal(fused, eager)
    # the fused result must come from the COMPILED program, not a silent
    # eager-replay rescue
    assert counters.get("pipeline.fallback") == 0, name


@pytest.mark.parametrize("name,build",
                         _op_surface(), ids=[n for n, _ in _op_surface()])
def test_filter_eager_fused_equivalence(name, build):
    """Every surface expr as a WHERE predicate (numeric → SQL truthiness,
    NULL drops the row — both paths must agree)."""
    fused = _base_frame().filter(build(None))
    eager = _eager(lambda: _base_frame().filter(build(None)))
    assert fused.count() == eager.count(), name
    _frames_equal(fused, eager)


def _mixed_chain(f):
    """Intermediate columns feed later filters."""
    f = f.with_column("p2", f["price"] * 2.0)
    f = f.with_column("tier", E.when(E.col("p2") > 50.0, 2.0)
                      .otherwise(1.0))
    f = f.filter(f["price"] > 1.0)
    f = f.with_column("adj", E.col("p2") + E.col("tier"))
    f = f.filter(E.col("adj") < 200.0)
    f = f.with_column("g2", f["guest"].cast("double") / 2)
    return f


def _ten_op_chain(f):
    for i in range(5):
        f = f.with_column(f"c{i}", E.col("v") * float(i + 1) + 0.5)
        f = f.filter(E.col(f"c{i}") > -1.0)
    return f


@pytest.mark.parametrize("make,chain,ops", [
    (_base_frame, _mixed_chain, 6),
    (lambda: Frame({"v": np.arange(200_000, dtype=np.float64)}),
     _ten_op_chain, 10),
], ids=["mixed_chain", "ten_op_chain"])
def test_chained_pipeline_equivalence(make, chain, ops):
    """What fusion is for, as counts: a chain of deferrable operations is
    ONE flush of ONE compiled program, a second run of the chain replays
    that program, and columns and mask are the eager path's, where every
    operation has run by the time its call returns."""
    fused = chain(make())
    assert len(fused._pending) == ops
    eager = _eager(lambda: chain(make()))
    assert not eager._pending                       # each op ran when called
    assert counters.get("pipeline.flush") == 0      # nothing fused ran yet
    _frames_equal(fused, eager)                     # the read flushes `fused`
    np.testing.assert_array_equal(np.asarray(fused._mask),
                                  np.asarray(eager._mask))
    assert counters.get("pipeline.flush") == 1
    assert counters.get("pipeline.compile") == 1    # ONE program, all ops
    chain(make())._flush()
    assert counters.get("pipeline.flush") == 2
    assert counters.get("pipeline.compile") == 1    # replayed, not rebuilt
    assert counters.get("pipeline.hit") == 1
    assert counters.get("pipeline.fallback") == 0


def test_with_columns_batch_semantics():
    """withColumns resolves every expr against the INPUT frame (Spark):
    replacing a column and referencing it elsewhere sees the original."""
    def run(f):
        return f.with_columns({"price": f["price"] * 0.0,
                               "orig": f["price"] + 1.0})

    fused = run(_base_frame())
    eager = _eager(lambda: run(_base_frame()))
    _frames_equal(fused, eager)
    assert counters.get("pipeline.fallback") == 0


def test_read_then_replace_column_compiles():
    """A step that READS a column a later step REPLACES must receive the
    base column as a program input (the step-evolved schema), not fall
    back to eager replay — and the base frame's buffer stays intact."""
    f = _base_frame()
    g = f.with_column("p2", E.col("price") * 2.0).with_column(
        "price", E.col("price") + 1.0).filter(E.col("price") > 5.0)
    d = g.to_pydict()
    np.testing.assert_allclose(np.asarray(d["p2"]),
                               np.asarray(d["price"]) * 2 - 2)
    assert counters.get("pipeline.fallback") == 0
    assert counters.get("pipeline.compile") == 1
    # the source frame still sees the ORIGINAL prices
    assert f.to_pydict()["price"][0] == 10.0


def test_non_compilable_exprs_stay_eager():
    f = _base_frame()
    g = f.with_column("up", E.fn("upper", f["city"]))     # host string fn
    assert not g._pending
    h = f.filter(f["city"].like("n%"))                    # host matcher
    assert not h._pending
    r = f.with_column("r", E.RowFunc("rand", 7))          # row generator
    assert not r._pending
    # round: jit would strength-reduce its constant divisor (1-ULP
    # divergence), so it is excluded from the compilable surface
    rd = f.with_column("rd", E.fn("round", f["price"], E.Lit(1)))
    assert not rd._pending
    eager = _eager(
        lambda: _base_frame().with_column(
            "rd", E.fn("round", E.col("price"), E.Lit(1))))
    _frames_equal(rd, eager)


def test_wrong_arity_builtin_raises_at_call_site():
    """hypot(one_arg) must not defer (arity gate) — the eager path
    raises immediately, same as with the pipeline off."""
    f = _base_frame()
    with pytest.raises(TypeError):
        f.with_column("bad", E.Func("hypot", [E.col("price")]))


def test_failed_flush_keeps_pending_and_keeps_raising(monkeypatch):
    """If the compiler bails AND the eager replay raises, the error must
    surface on EVERY read — never a silent revert to the pre-op frame."""
    from sparkdq4ml_tpu.ops import compiler as pc

    f = _base_frame().with_column("x", E.col("price") + 1.0)
    assert f._pending

    def boom(*a, **k):
        raise pc.PipelineError("forced")

    import sparkdq4ml_tpu.frame.frame as frame_mod

    real_replay = frame_mod.Frame._eager_replay

    def bad_replay(self, steps):
        raise RuntimeError("replay exploded")

    monkeypatch.setattr(frame_mod.Frame, "_eager_replay", bad_replay)
    monkeypatch.setattr(pc, "run_pipeline", boom)
    with pytest.raises(RuntimeError, match="replay exploded"):
        f.to_pydict()
    assert f._pending                 # ops NOT silently dropped
    assert "x" in f.columns
    with pytest.raises(RuntimeError, match="replay exploded"):
        f.count()                     # raises consistently, every read
    # restore the replay: the frame recovers and produces the op's result
    monkeypatch.setattr(frame_mod.Frame, "_eager_replay", real_replay)
    assert f.to_pydict()["x"][0] == 11.0


def test_plan_summary_fused_marker_is_honest():
    """FusedStage only prints when the WHERE + projections are
    structurally compilable; string predicates keep Project <- Filter."""
    from sparkdq4ml_tpu.sql.parser import parse, plan_summary

    fused = plan_summary(parse("SELECT a, a+1 b FROM t WHERE a > 1"))
    assert "FusedStage(Project[2] <- Filter)" in fused
    stringy = plan_summary(
        parse("SELECT name FROM t WHERE name LIKE 'x%'"))
    assert "FusedStage" not in stringy
    assert "Project[1] <- Filter" in stringy
    udf = plan_summary(parse("SELECT a FROM t WHERE myudf(a) > 0"))
    assert "FusedStage" not in udf


def test_sibling_frames_share_prefix_safely():
    """Two frames deferring off one parent must not corrupt each other
    (donation only ever touches fresh padded buffers)."""
    f = _base_frame().with_column("p2", E.col("price") * 2.0)
    a = f.filter(E.col("price") > 5.0)
    b = f.filter(E.col("price") > 90.0)
    na, nb = a.count(), b.count()
    assert (na, nb) == (4, 1)
    # the parent (and its base arrays) stay fully usable after both flush
    assert f.count() == 6
    assert _base_frame().count() == 6


def test_mask_composes_with_prior_filters():
    f = _base_frame().filter(E.col("guest") > 1)     # defers
    g = f.filter(E.col("price") < 50.0)              # same program
    eager = _eager(lambda: _base_frame().filter(E.col("guest") > 1)
                   .filter(E.col("price") < 50.0))
    assert g.count() == eager.count()
    _frames_equal(g, eager)


def test_numpy_scalar_literals_stay_eager():
    """np.int64/np.bool_ literals take Lit.eval's host object-array
    branch, so they must not defer (and must not share a plan key with
    the Python-int literal whose eval differs)."""
    from sparkdq4ml_tpu.ops.compiler import is_compilable, schema_of

    f = _base_frame()
    g = f.with_column("x", E.when(f["guest"] > 2, E.Lit(np.int64(5)))
                      .otherwise(E.Lit(np.int64(1))))
    assert not g._pending
    schema = schema_of(f._data_store)
    assert not is_compilable(E.Lit(np.int64(5)), schema)
    assert not is_compilable(E.Lit(np.bool_(True)), schema)
    # np.float64 IS a float subclass and evals on device — it may defer
    assert is_compilable(E.Lit(np.float64(5.0)), schema)


def test_pipeline_conf_is_session_scoped():
    """A session disabling the pipeline must not leave the process on
    the eager path after stop() (same scoping rule as the fault plan)."""
    import sparkdq4ml_tpu as dq

    assert config.pipeline is True
    s = (dq.TpuSession.builder().app_name("scoped")
         .config("spark.pipeline.enabled", "false")
         .config("spark.pipeline.minBucket", 16).get_or_create())
    assert config.pipeline is False
    assert config.pipeline_min_bucket == 16
    s.stop()
    assert config.pipeline is True
    assert config.pipeline_min_bucket == 8


def test_enabled_false_restores_exact_eager_path():
    config.pipeline = False
    f = _base_frame()
    g = f.with_column("x", f["price"] + 1).filter(f["price"] > 5)
    assert not g._pending
    assert counters.get("pipeline.flush") == 0
    assert counters.get("pipeline.compile") == 0


# ---------------------------------------------------------------------------
# Plan key: literal hoisting + shape buckets
# ---------------------------------------------------------------------------

def test_bucket_size_rule():
    assert compiler.bucket_size(1) == config.pipeline_min_bucket
    assert compiler.bucket_size(8) == 8
    assert compiler.bucket_size(9) == 16
    assert compiler.bucket_size(600) == 1024
    assert compiler.bucket_size(1024) == 1024
    assert compiler.bucket_size(1025) == 2048
    # above the exact-shape threshold the bucket IS n (pad+slice copies
    # are O(n) and outweigh an occasional retrace at this scale)
    big = config.pipeline_exact_threshold + 12345
    assert compiler.bucket_size(big) == big


def test_literal_hoisting_shares_one_program():
    """price < 3 and price < 4 (and < 7.5) are ONE compiled program."""
    for threshold in (3.0, 4.0, 7.5):
        f = _base_frame().filter(E.col("price") < threshold)
        f._flush()
    assert counters.get("pipeline.compile") == 1
    assert counters.get("pipeline.hit") == 2
    # ... and the results use the right literal, not the cached one
    assert _base_frame().filter(E.col("price") < 4.0).count() == 1
    assert _base_frame().filter(E.col("price") < 90.0).count() == 4


def test_func_literal_args_hoist_and_share():
    """pow(x, 2) and pow(x, 3) are one compiled program (the exponent is
    a hoisted runtime scalar — also keeps XLA from strength-reducing the
    constant form into a 1-ULP divergence)."""
    for exponent in (2, 3, 5):
        f = _base_frame().with_column(
            "p", E.fn("pow", E.col("guest"), E.Lit(exponent)))
        f._flush()
    assert counters.get("pipeline.compile") == 1
    assert counters.get("pipeline.hit") == 2
    out = _base_frame().with_column(
        "p", E.fn("pow", E.col("guest"), E.Lit(3))).to_pydict()["p"]
    assert out[0] == 8.0


def test_different_lengths_same_bucket_share_one_program():
    def load(n):
        return Frame({"v": np.arange(n, dtype=np.float64)})

    a = load(600).with_column("w", E.col("v") * 3.0)
    a._flush()
    compiles = counters.get("pipeline.compile")
    b = load(700).with_column("w", E.col("v") * 3.0)   # same 1024 bucket
    b._flush()
    assert counters.get("pipeline.compile") == compiles   # 0 new compiles
    assert b.to_pydict()["w"][-1] == 699.0 * 3.0
    c = load(1500).with_column("w", E.col("v") * 3.0)  # 2048: new trace
    c._flush()
    assert counters.get("pipeline.compile") == compiles + 1


def test_dtype_config_flip_is_not_served_stale():
    """`/` bakes float_dtype() into the program; flipping the engine
    float dtype must miss the plan cache, not serve the old dtype."""
    import jax.numpy as jnp

    col = jnp.asarray([1.0, 2.0, 3.0], jnp.float64)
    out64 = Frame({"a": col}).with_column("h", E.col("a") / 2)
    assert np.asarray(out64.to_pydict()["h"]).dtype == np.float64
    saved = config.default_float_dtype
    config.default_float_dtype = jnp.float32
    try:
        out32 = Frame({"a": col}).with_column("h", E.col("a") / 2)
        assert np.asarray(out32.to_pydict()["h"]).dtype == np.float32
    finally:
        config.default_float_dtype = saved


def test_adversarial_column_names_cannot_collide_plan_keys():
    """Names containing the key's own delimiter syntax must not alias a
    structurally different plan (names are repr-escaped in the key)."""
    base = Frame({"b": [1.0, 2.0]})
    first = base.with_column("a", E.col("b")).with_column("c", E.Lit(1.0))
    first._flush()
    evil_name = "a)=C('b':<f8)|W(c"
    evil = base.with_column(evil_name, E.Lit(1.0))
    evil._flush()
    assert counters.get("pipeline.compile") == 2      # distinct plans
    assert evil.columns == ["b", evil_name]
    assert np.asarray(evil._data[evil_name]).tolist() == [1.0, 1.0]


def test_structural_mismatch_recompiles():
    _base_frame().filter(E.col("price") < 3.0)._flush()
    _base_frame().filter(E.col("price") <= 3.0)._flush()   # different op
    assert counters.get("pipeline.compile") == 2


# ---------------------------------------------------------------------------
# SQL wiring: repeated queries are cache hits
# ---------------------------------------------------------------------------

def _sql_frame(session, n, name="t"):
    rng = np.random.default_rng(3)
    Frame({"guest": rng.integers(1, 40, n).astype(np.float64),
           "price": rng.uniform(1.0, 120.0, n)}
          ).create_or_replace_temp_view(name)


def test_second_identical_sql_query_adds_zero_compiles(session):
    _sql_frame(session, 600)
    q = ("SELECT cast(guest as int) guest, price * 2 AS p2 "
         "FROM t WHERE price > 50")
    first = session.sql(q)
    first.count()
    compiles = counters.get("pipeline.compile")
    assert compiles >= 1
    second = session.sql(q)
    second.count()
    assert counters.get("pipeline.compile") == compiles   # pure cache hit
    assert first.count() == second.count()


def test_second_csv_of_different_length_adds_zero_compiles(session):
    """The two-loads scenario from the issue: different row counts within
    one padding bucket replay the same compiled plan."""
    def write_csv(n):
        rng = np.random.default_rng(n)
        fd, path = tempfile.mkstemp(suffix=".csv")
        with os.fdopen(fd, "w") as fh:
            for _ in range(n):
                fh.write(f"{rng.integers(1, 40)},"
                         f"{rng.uniform(1.0, 120.0):.2f}\n")
        return path

    q = ("SELECT cast(_c0 as int) guest, _c1 * 1.1 AS price "
         "FROM v WHERE _c1 > 20")
    paths = [write_csv(520), write_csv(760)]     # both bucket 1024
    try:
        df = (session.read.format("csv").option("inferSchema", "true")
              .load(paths[0]))
        df.create_or_replace_temp_view("v")
        session.sql(q).count()
        compiles = counters.get("pipeline.compile")
        df2 = (session.read.format("csv").option("inferSchema", "true")
               .load(paths[1]))
        df2.create_or_replace_temp_view("v")
        session.sql(q).count()
        assert counters.get("pipeline.compile") == compiles
    finally:
        for p in paths:
            os.remove(p)


def test_sql_results_identical_pipeline_on_off(session):
    _sql_frame(session, 300)
    q = ("SELECT guest, price / 2 AS half, price * guest AS tot "
         "FROM t WHERE price > 30 AND guest < 35")
    on = session.sql(q)
    off = _eager(lambda: session.sql(q))
    _frames_equal(on, off)


# ---------------------------------------------------------------------------
# Golden regression gates: DQ row counts + example-app RMSE, on and off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("enabled", [True, False],
                         ids=["pipeline_on", "pipeline_off"])
def test_golden_dq_counts_and_rmse(session, enabled):
    from sparkdq4ml_tpu.models import LinearRegression

    config.pipeline = enabled
    df = run_dq_pipeline(session, dataset_path("abstract"))
    assert df.count() == 24
    df = prepare_features(df)
    model = (LinearRegression().setMaxIter(40).setRegParam(1)
             .setElasticNetParam(1)).fit(df)
    assert model.summary.root_mean_squared_error == pytest.approx(
        2.809940, abs=1e-4)


# ---------------------------------------------------------------------------
# Satellites: batched host sync, honest cache(), counters
# ---------------------------------------------------------------------------

def test_to_pydict_is_one_batched_sync():
    f = _base_frame()
    f.count()                       # materialize everything first
    counters.clear("frame.host_sync")
    f.to_pydict()
    assert counters.get("frame.host_sync") == 1      # mask + columns batch


def test_show_limited_sync_count():
    f = _base_frame()
    f.count()
    counters.clear("frame.host_sync")
    f.show_string(2)
    # total count (1 mask pull) + limited to_pydict (mask + column batch)
    assert counters.get("frame.host_sync") <= 3


def test_cache_materializes_and_counts():
    f = _base_frame().with_column("p2", E.col("price") * 2.0)
    out = f.cache()
    assert out is f
    assert not f._pending            # cache() is a materialization point
    assert counters.get("frame.cache") == 1
    assert counters.get("pipeline.flush") == 1


def test_cache_emits_span(session):
    from sparkdq4ml_tpu.utils import observability as obs

    obs.enable()
    try:
        _base_frame().cache()
        assert any(s.name == "frame.cache" for s in obs.TRACER.spans())
    finally:
        obs.disable()


def test_flush_span_attrs(session):
    from sparkdq4ml_tpu.utils import observability as obs

    obs.enable()
    try:
        f = _base_frame().filter(E.col("price") > 5.0)
        f.count()
        spans = [s for s in obs.TRACER.spans()
                 if s.name == "frame.pipeline.flush"]
        assert spans
        assert spans[0].attrs["steps"] == 1
        assert spans[0].attrs["bucket"] == 8
        assert spans[0].attrs["cache"] in ("compile", "hit")
    finally:
        obs.disable()


# ---------------------------------------------------------------------------
# Registered UDFs: a row-local function defers like a builtin (ISSUE 31)
# ---------------------------------------------------------------------------

def _registry(**fns):
    """A registry of its own for a test: nothing leaks into the default."""
    from sparkdq4ml_tpu.ops.udf import UDFRegistry

    reg = UDFRegistry()
    for name, fn in fns.items():
        reg.register(name, fn, "double")
    return reg


def _rule_frame(n=6):
    """Prices around both rules' thresholds, NaN rows included; any length
    (the six of ``_base_frame`` first)."""
    rng = np.random.default_rng(31)
    price = np.concatenate([
        [10.0, 25.5, 3.0, 95.0, float("nan"), 7.25],
        rng.uniform(1.0, 120.0, max(n - 6, 0))])[:n]
    guest = np.concatenate([[2, 5, 1, 20, 8, 3],
                            rng.integers(1, 40, max(n - 6, 0))])[:n]
    price[7::11] = np.nan
    return Frame({"price": price, "guest": guest.astype(np.int64)})


def _builtin_rule_calls(reg):
    return {
        "minimumPriceRule": lambda: E.UdfCall(
            "minimumPriceRule", [E.col("price")], reg),
        "priceCorrelationRule": lambda: E.UdfCall(
            "priceCorrelationRule", [E.col("price"), E.col("guest")], reg),
    }


def _rules_registry():
    from sparkdq4ml_tpu.ops import rules

    return _registry(minimumPriceRule=rules.minimum_price_rule,
                     priceCorrelationRule=rules.price_correlation_rule)


@pytest.mark.parametrize("rule", ["minimumPriceRule",
                                  "priceCorrelationRule"])
def test_builtin_rule_defers_and_equals_eager(rule):
    """Both rules of the reference app are row-local: the call defers, the
    rule and the filter behind it are one program, and values and mask
    are the eager path's bit for bit — NaN rows included (rule 1 lets a
    NaN through, rule 2 answers -1 for one)."""
    counters.clear("dq.")
    call = _builtin_rule_calls(_rules_registry())[rule]

    def run(f):
        return f.with_column("r", call()).filter(E.col("r") > 0)

    fused = run(_rule_frame(40))
    assert len(fused._pending) == 2, "the rule did not defer"
    eager = _eager(lambda: run(_rule_frame(40)))
    assert not eager._pending
    np.testing.assert_array_equal(np.asarray(fused._mask),
                                  np.asarray(eager._mask))
    np.testing.assert_array_equal(       # every slot, masked ones too
        np.asarray(fused._data["r"]), np.asarray(eager._data["r"]))
    _frames_equal(fused, eager)
    assert counters.get("pipeline.flush") == 1
    assert counters.get("pipeline.compile") == 1
    assert counters.get("pipeline.fallback") == 0
    assert counters.get("dq.rule_in_flush") == 1
    assert counters.get("dq.rule_eager") == 1      # the eager twin's


def test_rule_filter_and_cast_are_one_flush_replayed_as_a_hit(session):
    """The reference app's first SQL statement behind its first rule: the
    rule, ``WHERE rule > 0`` and the projection's cast are ONE flush of
    ONE program; the same statement again replays it."""
    import sparkdq4ml_tpu as dq

    dq.register_builtin_rules()

    def job():
        rng = np.random.default_rng(3)
        df = Frame({"guest": rng.integers(1, 40, 600).astype(np.float64),
                    "price": rng.uniform(1.0, 120.0, 600)})
        df = df.with_column(
            "price_no_min", dq.call_udf("minimumPriceRule", dq.col("price")))
        df.create_or_replace_temp_view("t")
        out = session.sql("SELECT cast(guest as int) guest, price_no_min "
                          "AS price FROM t WHERE price_no_min > 0")
        return out, out.count()

    counters.clear("dq.")
    first, rows = job()
    assert counters.get("pipeline.flush") == 1
    assert counters.get("pipeline.compile") == 1
    assert counters.get("dq.rule_in_flush") == 1
    second, rows2 = job()
    assert rows2 == rows
    assert counters.get("pipeline.flush") == 2
    assert counters.get("pipeline.compile") == 1     # replayed
    assert counters.get("pipeline.hit") == 1
    assert counters.get("pipeline.fallback") == 0
    assert counters.get("dq.rule_eager") == 0
    off, rows_off = _eager(job)
    assert rows_off == rows
    _frames_equal(first, off)


def _np_on_input(x):
    return jnp.asarray(np.asarray(x) * 2.0)


_NOT_ROW_LOCAL = {
    "whole_column_mean": lambda x: x - jnp.mean(x),
    "cumulative_sum": lambda x: jnp.cumsum(x),
    "numpy_on_its_input": _np_on_input,
    "two_dimensional": lambda x: jnp.stack([x, x], axis=1),
    "python_branch_on_a_value": lambda x: x if x[0] > 0 else -x,
    "captured_array": lambda x, _t=np.arange(6.0): x + jnp.asarray(_t),
    "reversed": lambda x: x[::-1],
}

@pytest.mark.parametrize("kind", sorted(_NOT_ROW_LOCAL))
def test_udf_that_is_not_row_local_stays_eager(kind):
    """Padding, row slices and shards would change what such a function
    sees (or it cannot be traced at all): it keeps the eager path and the
    eager accounting, and is no degraded path."""
    counters.clear("dq.")
    reg = _registry(f=_NOT_ROW_LOCAL[kind])
    reg.register("f", _NOT_ROW_LOCAL[kind])        # its natural dtype
    f = Frame({"price": [10.0, 25.5, 3.0, 95.0, 1.0, 7.25]})
    g = f.with_column("r", E.UdfCall("f", [E.col("price")], reg))
    assert not g._pending, f"{kind} deferred"
    assert counters.get("dq.rule_eager") == 1
    assert counters.get("dq.rule_in_flush") == 0
    assert counters.get("pipeline.fallback") == 0
    assert counters.get("pipeline.flush") == 0
    want = np.asarray(_NOT_ROW_LOCAL[kind](
        jnp.asarray(f._data["price"])))
    np.testing.assert_array_equal(np.asarray(g._data["r"]), want)


def test_reregistered_name_misses_the_cache_and_gives_the_new_values():
    reg = _registry(r=lambda x: jnp.where(x < 20.0, -1.0, x))

    def run():
        return _rule_frame().with_column(
            "r", E.UdfCall("r", [E.col("price")], reg)).to_pydict()["r"]

    first = np.asarray(run())
    assert counters.get("pipeline.compile") == 1
    np.testing.assert_array_equal(
        first, [-1.0, 25.5, -1.0, 95.0, np.nan, -1.0])
    reg.register("r", lambda x: x * 2.0, "double")   # another function
    second = np.asarray(run())
    assert counters.get("pipeline.compile") == 2     # not the old program
    np.testing.assert_array_equal(
        second, [20.0, 51.0, 6.0, 190.0, np.nan, 14.5])
    assert counters.get("pipeline.fallback") == 0


def _udf_key(reg, name="r", args=("price",)):
    f = _rule_frame().with_column(
        "r", E.UdfCall(name, [E.col(a) for a in args], reg))
    key = compiler._linearize(f._pending, (), f._pipe_schema())[0]
    assert key.count("|") == len(f._pending), key   # no | in a fragment
    return key


@pytest.mark.parametrize("case", ["same_function_twice",
                                  "another_literal_inside",
                                  "another_return_type",
                                  "two_registries_one_name"])
def test_udf_plan_key_names_the_function(case):
    """The key holds a fingerprint of what the function does: the same
    function under one name shares a program however often it is
    registered, and nothing else does."""
    def floor(at):
        return lambda x: jnp.where(x < at, -1.0, x)

    reg = _registry(r=floor(20.0))
    key = _udf_key(reg)
    if case == "same_function_twice":
        reg.register("r", floor(20.0), "double")
        assert _udf_key(reg) == key
        assert _udf_key(_registry(r=floor(20.0))) == key
    elif case == "another_literal_inside":
        reg.register("r", floor(21.0), "double")
        assert _udf_key(reg) != key
    elif case == "another_return_type":
        reg.register("r", floor(20.0), "float")
        assert _udf_key(reg) != key
    else:
        assert _udf_key(_registry(r=lambda x: x + 1.0)) != key


@pytest.mark.parametrize("name", ["a|b", "it's", 'say "r"', "r)|W('x')=V(1"])
def test_adversarial_rule_names_keep_the_key_whole(name):
    reg = _registry(**{name: lambda x: x + 1.0})
    key = _udf_key(reg, name)
    other = _udf_key(_registry(r=lambda x: x + 1.0))
    assert key != other


@pytest.mark.parametrize("rows", [6, 1000, 1025])
def test_rule_over_a_padded_tail_equals_eager(rows):
    """A length that is no bucket size: the padded tail rides a False
    mask and the rule never sees it in the result."""
    calls = _builtin_rule_calls(_rules_registry())

    def run():
        f = _rule_frame(rows)
        return (f.with_column("r1", calls["minimumPriceRule"]())
                .filter(E.col("r1") > 0)
                .with_column("r2", calls["priceCorrelationRule"]())
                .filter(E.col("r2") > 0))

    fused = run()
    assert len(fused._pending) == 4
    eager = _eager(run)
    _frames_equal(fused, eager)
    np.testing.assert_array_equal(np.asarray(fused._mask),
                                  np.asarray(eager._mask))
    assert counters.get("pipeline.flush") == 1
    assert counters.get("pipeline.fallback") == 0


def test_rule_in_a_row_chunked_flush_equals_eager():
    """An over-budget flush runs in row slices: sound for a rule because
    only a row-local function is ever in a flush."""
    from sparkdq4ml_tpu.utils import faults

    counters.clear("dq.")
    calls = _builtin_rule_calls(_rules_registry())

    def run():
        f = _rule_frame(4096)
        return (f.with_column("r2", calls["priceCorrelationRule"]())
                .filter(E.col("r2") > 0))

    eager = _eager(run)
    counters.clear("dq.")
    with faults.inject_faults("oom:oom:1:n=64", seed=3):
        fused = run()
        _frames_equal(fused, eager)
    np.testing.assert_array_equal(np.asarray(fused._mask),
                                  np.asarray(eager._mask))
    assert counters.get("pipeline.oom_chunked") == 1
    assert counters.get("dq.rule_in_flush") == 1     # one flush, one rule
    assert counters.get("dq.rule_eager") == 0
    assert counters.get("pipeline.fallback") == 0


@pytest.mark.parametrize("shape", ["nested_in_a_filter",
                                   "argument_is_an_expression",
                                   "argument_is_a_pending_column",
                                   "literal_argument",
                                   "aliased", "with_columns"])
def test_udf_call_shapes_defer_and_equal_eager(shape):
    reg = _registry(
        floor=lambda x: jnp.where(x < 20.0, -1.0, x),
        scaled=lambda x, k: x * k)

    def run():
        f = _rule_frame(40)
        floor = lambda a: E.UdfCall("floor", [a], reg)   # noqa: E731
        if shape == "nested_in_a_filter":
            return f.filter(floor(E.col("price")) > 0)
        if shape == "argument_is_an_expression":
            return f.with_column("r", floor(E.col("price") / 2 + 1))
        if shape == "argument_is_a_pending_column":
            return (f.with_column("half", E.col("guest") / 2)
                    .with_column("r", floor(E.col("half"))))
        if shape == "literal_argument":
            return f.with_column(
                "r", E.UdfCall("scaled", [E.col("price"), E.Lit(3)], reg))
        if shape == "aliased":
            return f.with_column("r", floor(E.col("price")).alias("x"))
        return f.with_columns({"price": floor(E.col("price")),
                               "was": E.col("price") + 0.0})

    fused = run()
    assert fused._pending, f"{shape} did not defer"
    eager = _eager(run)
    _frames_equal(fused, eager)
    np.testing.assert_array_equal(np.asarray(fused._mask),
                                  np.asarray(eager._mask))
    assert counters.get("pipeline.flush") == 1
    assert counters.get("pipeline.fallback") == 0


def test_udf_literal_arguments_share_one_program():
    reg = _registry(scaled=lambda x, k: x * k)

    def run(k):
        return _rule_frame().with_column(
            "r", E.UdfCall("scaled", [E.col("price"), E.Lit(k)], reg)
        ).to_pydict()["r"]

    a, b = np.asarray(run(2.0)), np.asarray(run(3.0))
    np.testing.assert_array_equal(b[:4], a[:4] * 1.5)
    assert counters.get("pipeline.compile") == 1
    assert counters.get("pipeline.hit") == 1


@pytest.mark.parametrize("fn,sql", [
    ("abs", "SELECT abs(price - 50) AS v, guest FROM t WHERE guest > 3"),
    ("upper", "SELECT upper(city) AS v, guest FROM t WHERE guest > 3"),
])
def test_unregistered_sql_builtin_behaves_as_before(session, fn, sql):
    """The parser builds a UdfCall for every function call; a name the
    registry lacks is no rule: it resolves through the builtin table,
    eagerly, and moves neither rule counter."""
    from sparkdq4ml_tpu.ops.compiler import is_compilable

    counters.clear("dq.")
    f = _base_frame()
    call = E.UdfCall(fn, [E.col("city" if fn == "upper" else "price")])
    assert not is_compilable(call, f._pipe_schema())
    assert not f.with_column("v", call)._pending
    f.create_or_replace_temp_view("t")
    on = session.sql(sql)
    off = _eager(lambda: session.sql(sql))
    _frames_equal(on, off)
    assert counters.get("dq.rule_in_flush") == 0
    assert counters.get("dq.rule_eager") == 0
    assert counters.get("pipeline.fallback") == 0


@pytest.mark.parametrize("what,fn,dtypes,admitted", [
    ("rule_1", "minimum_price_rule", ["float32"], True),
    ("rule_2", "price_correlation_rule", ["float32", "int32"], True),
    ("rule_2_f64", "price_correlation_rule", ["float64", "int64"], True),
    ("jnp_compositions", lambda x: jnp.clip(jnp.nan_to_num(x), 0, 1)
     + jnp.sign(x) * jnp.maximum(x, 0.0) + jnp.tanh(x), ["float32"], True),
    ("captured_scalar", lambda x, _k=np.float32(3.0): x * _k,
     ["float32"], True),
    ("integer_ops", lambda x: (x // 3) % 5 + (x << 1), ["int32"], True),
    ("roll", lambda x: jnp.roll(x, 1), ["float32"], False),
    ("sort", lambda x: jnp.sort(x), ["float32"], False),
    ("row_number", lambda x: x + jnp.arange(x.shape[0]), ["float32"],
     False),
    ("scalar_result", lambda x: jnp.float32(1.0), ["float32"], False),
    ("tuple_result", lambda x: (x, x), ["float32"], False),
    ("argmax_gather", lambda x: x[jnp.argmax(x)] + x, ["float32"], False),
    ("wrong_arity", lambda x, y: x + y, ["float32"], False),
])
def test_probe_admits_row_local_functions_only(what, fn, dtypes, admitted):
    from sparkdq4ml_tpu.ops import rules
    from sparkdq4ml_tpu.ops.udf import probe_elementwise

    if isinstance(fn, str):
        fn = getattr(rules, fn)
    fp = probe_elementwise(fn, [np.dtype(d) for d in dtypes])
    assert (fp is not None) == admitted
    if admitted:
        assert fp == probe_elementwise(fn, [np.dtype(d) for d in dtypes])
        assert "|" not in fp


def test_probe_verdict_is_cached_on_the_entry_and_goes_with_it():
    calls = []

    def fn(x):
        calls.append(1)
        return x + 1.0

    reg = _registry(r=fn)
    f32 = [np.dtype("float32")]
    first = reg.elementwise("r", f32)
    assert first is not None and first[0] is fn
    n = len(calls)
    assert reg.elementwise("r", f32) == first
    assert len(calls) == n                          # not traced again
    reg.register("r", lambda x: jnp.cumsum(x), "double")
    assert reg.elementwise("r", f32) is None        # the new function's
    assert reg.elementwise("missing", f32) is None
    assert reg.lookup("r")[1] == np.dtype("float64")
