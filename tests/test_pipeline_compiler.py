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
