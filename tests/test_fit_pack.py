"""The design is packed inside the compiled fit (ISSUE 29).

A compiled fit has two entries, told apart by the form of its first
argument: the frame's columns (``DesignColumns``: what ``_extract_xy``
returned, on one device) and a packed ``Z`` (``pack_design``: callers
that hold one, and the sharded path). Both run one core. The cases below
hold the two entries to the same ``FitResult``, the columns entry to its
mask (a NaN in a filtered slot reaches no sum), the assembler to one
program and to the matrix it always built, the lowered programs to their
parameters at the benchmark's sizes, and the counters to what they say.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.models import (LinearRegression, LinearSVC,
                                   LogisticRegression, VectorAssembler)
from sparkdq4ml_tpu.models import feature
from sparkdq4ml_tpu.models.classification import (fused_logistic_fit_packed,
                                                  fused_softmax_fit_packed,
                                                  fused_svc_fit_packed)
from sparkdq4ml_tpu.parallel.distributed import (DesignColumns,
                                                 fused_linear_fit_packed,
                                                 pack_design,
                                                 pack_design_weighted)
from sparkdq4ml_tpu.parallel.mesh import make_mesh
from sparkdq4ml_tpu.utils import observability as obs
from sparkdq4ml_tpu.utils.profiling import counters

N, D = 240, 3


def _data(seed=0, classes=2):
    """(X, y_regression, y_class, mask, w): a fifth of the rows masked,
    some valid weights zero."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, D)) * np.array([1.0, 3.0, 0.5]) + 0.3
    beta = np.array([1.5, -0.7, 2.0])
    y = X @ beta + 4.0 + rng.normal(size=N) * 0.3
    if classes == 2:
        yc = (X @ beta + rng.logistic(size=N) > 0.5).astype(float)
    else:
        yc = np.argmax(X @ rng.normal(size=(D, classes))
                       + rng.gumbel(size=(N, classes)), axis=1).astype(float)
    mask = rng.uniform(size=N) > 0.2
    w = rng.integers(0, 4, size=N).astype(float)
    return tuple(jnp.asarray(a) for a in (X, y, yc, mask, w))


HYPER = {"lasso": (0.1, 1.0), "ridge": (0.1, 0.0), "plain": (0.0, 0.0),
         "elastic": (0.05, 0.5)}

# name -> (factory call, hyper, label kind, weighted): the eight fits the
# issue lists, each through its own compiled program
FITS = {
    "linear-fista": (lambda: fused_linear_fit_packed(
        None, "fista", 200, 1e-9, True, True), "lasso", "reg", False),
    "linear-normal": (lambda: fused_linear_fit_packed(
        None, "normal", 200, 1e-9, True, True), "ridge", "reg", False),
    "linear-owlqn": (lambda: fused_linear_fit_packed(
        None, "owlqn", 200, 1e-9, True, True), "elastic", "reg", False),
    "logistic-newton": (lambda: fused_logistic_fit_packed(
        None, 50, 1e-9, True, True, solver="newton"), "plain", "bin", False),
    "logistic-fista": (lambda: fused_logistic_fit_packed(
        None, 80, 1e-9, True, True, solver="fista"), "elastic", "bin",
        False),
    "logistic-weighted": (lambda: fused_logistic_fit_packed(
        None, 50, 1e-9, True, True, weighted=True, solver="newton"),
        "ridge", "bin", True),
    "softmax": (lambda: fused_softmax_fit_packed(
        None, 3, 50, 1e-9, True, True, solver="newton"), "ridge", "multi",
        False),
    "softmax-weighted-fista": (lambda: fused_softmax_fit_packed(
        None, 3, 80, 1e-9, True, True, weighted=True, solver="fista"),
        "elastic", "multi", True),
    "svc": (lambda: fused_svc_fit_packed(None, 80, 1e-9, True, True),
            "ridge", "bin", False),
}


def _case(name, seed=0):
    build, hyper, kind, weighted = FITS[name]
    X, y, yc, mask, w = _data(seed, classes=3 if kind == "multi" else 2)
    label = y if kind == "reg" else yc
    return (build(), jnp.asarray(HYPER[hyper]), X, label, mask,
            w if weighted else None)


def _packed(X, y, mask, w):
    if w is None:
        return pack_design(X, y, mask)
    return pack_design_weighted(X, y, mask, w)


@pytest.mark.parametrize("name", sorted(FITS))
def test_columns_entry_and_packed_entry_give_the_same_fit(name):
    """Coefficients, intercept, iterations, converged and the whole
    objective history: the flat result buffer, entry against entry."""
    fit, hyper, X, y, mask, w = _case(name)
    from_columns = np.array(fit(DesignColumns(X, y, mask, w), hyper))
    from_packed = np.array(fit(_packed(X, y, mask, w), hyper))
    assert from_columns.shape == from_packed.shape
    assert np.all(np.isfinite(from_columns))
    if FITS[name][2] == "multi":
        # the K unpenalised intercepts are fixed up to a common shift
        # (the estimator centres them): compare them centred
        b = slice(3 * D, 3 * D + 3)
        for flat in (from_columns, from_packed):
            flat[b] -= flat[b].mean()
    # float64 here: the entries differ by the order of a few sums, which
    # the softmax Newton's jittered solve amplifies to ~3e-7
    np.testing.assert_allclose(from_columns, from_packed, rtol=2e-6,
                               atol=1e-9)
    # a fit that did nothing would agree too
    assert np.abs(from_columns[:D]).max() > 1e-3


def test_weighted_linear_columns_entry_scales_rows_by_sqrt_w():
    """The linear fit's weighted design is ``[X, y, 1]·sqrt(w)``: the
    columns entry with ``w`` against the packed entry handed that scale."""
    X, y, _, mask, w = _data(3)
    fit = fused_linear_fit_packed(None, "fista", 200, 1e-9, True, True)
    hyper = jnp.asarray(HYPER["lasso"])
    got = np.asarray(fit(DesignColumns(X, y, mask, w), hyper))
    want = np.asarray(fit(pack_design(
        X, y, jnp.sqrt(jnp.where(mask, w, 0.0))), hyper))
    np.testing.assert_allclose(got, want, rtol=1e-8, atol=1e-10)


POISON = {"nan": np.nan, "inf": np.inf, "-inf": -np.inf}


@pytest.mark.parametrize("value", sorted(POISON))
@pytest.mark.parametrize("name,where", [
    (name, where) for name in sorted(FITS)
    for where in ("feature", "label", "weight")
    if where != "weight" or FITS[name][3]])  # unweighted: no such column
def test_poison_in_a_masked_row_changes_nothing(name, where, value):
    """Masked rows contribute nothing to any sum — whatever they hold."""
    fit, hyper, X, y, mask, w = _case(name, seed=1)
    clean = np.asarray(fit(DesignColumns(X, y, mask, w), hyper))
    dropped = np.flatnonzero(~np.asarray(mask))[:5]
    bad = POISON[value]
    if where == "feature":
        X = X.at[dropped, 1].set(bad).at[dropped[0], :].set(bad)
    elif where == "label":
        y = y.at[dropped].set(bad)
    else:
        w = w.at[dropped].set(bad)
    got = np.asarray(fit(DesignColumns(X, y, mask, w), hyper))
    assert np.all(np.isfinite(got))
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("value", sorted(POISON))
def test_pack_design_writes_zero_rows_over_poison(value):
    """The packed entry's builder keeps the same promise: a row whose
    mask (or scale) is 0 is written as zeros."""
    X, y, _, mask, w = _data(2)
    dropped = np.flatnonzero(~np.asarray(mask))[:4]
    bad = POISON[value]
    Xp, yp = X.at[dropped, 0].set(bad), y.at[dropped].set(bad)
    wp = w.at[dropped].set(bad)
    for host in (False, True):
        conv = np.asarray if host else (lambda a: a)
        Z = np.asarray(pack_design(conv(Xp), conv(yp), conv(mask)))
        np.testing.assert_array_equal(
            Z, np.asarray(pack_design(X, y, mask)))
        Zw = np.asarray(pack_design_weighted(conv(Xp), conv(yp), conv(mask),
                                             conv(wp)))
        np.testing.assert_array_equal(
            Zw, np.asarray(pack_design_weighted(X, y, mask, w)))
        assert np.all(Z[dropped] == 0) and np.all(Zw[dropped] == 0)


@pytest.mark.parametrize("estimator", ["linear", "linear-weighted",
                                       "logistic", "logistic-weighted",
                                       "svc"])
def test_estimators_ignore_poison_in_filtered_rows(estimator):
    """Through the public API: a frame whose filtered rows hold NaN in
    the feature vector, the label and the weight fits as the frame
    without those rows does."""
    X, y, yc, mask, w = (np.asarray(a) for a in _data(4))
    label = y if estimator.startswith("linear") else yc
    w = w + 1.0
    build = {
        "linear": lambda: LinearRegression(max_iter=60, reg_param=0.1,
                                           elastic_net_param=1.0),
        "linear-weighted": lambda: LinearRegression(
            max_iter=60, reg_param=0.1, weight_col="w"),
        "logistic": lambda: LogisticRegression(max_iter=40),
        "logistic-weighted": lambda: LogisticRegression(
            max_iter=40, reg_param=0.01, weight_col="w"),
        "svc": lambda: LinearSVC(max_iter=60, reg_param=0.01),
    }[estimator]

    def fit(Xa, la, wa, keep):
        f = Frame({"features": Xa, "label": la, "w": wa,
                   "keep": keep.astype(np.int32)})
        m = build().fit(f.filter(f.col("keep") > 0))
        return np.append(np.asarray(m.coefficients, np.float64),
                         m.intercept)

    Xb, lb, wb = X.copy(), label.copy(), w.copy()
    Xb[~mask], lb[~mask], wb[~mask] = np.nan, np.nan, np.nan
    poisoned = fit(Xb, lb, wb, mask)
    compact = fit(X[mask], label[mask], w[mask], np.ones(mask.sum()))
    assert np.all(np.isfinite(poisoned))
    np.testing.assert_allclose(poisoned, compact, rtol=1e-7, atol=1e-9)


# ---------------------------------------------------------------------------
# VectorAssembler: one program, the same matrix
# ---------------------------------------------------------------------------


def test_assembler_1d_and_2d_columns_build_the_same_matrix_as_before():
    rng = np.random.default_rng(5)
    a = rng.normal(size=17)
    b = rng.integers(0, 9, size=17).astype(np.int32)
    v = rng.normal(size=(17, 3))
    f = Frame({"a": a, "b": b}).with_column("v", jnp.asarray(v))
    out = VectorAssembler(["a", "v", "b"], "features").transform(f)
    got = out._column_values("features")
    assert isinstance(got, jax.Array) and got.dtype == jnp.float64
    want = np.concatenate([a[:, None], v, b[:, None].astype(float)], axis=1)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert out.columns == ["a", "b", "v", "features"]
    np.testing.assert_array_equal(np.asarray(out.mask), np.asarray(f.mask))
    # a vector column alone, and a single scalar column
    np.testing.assert_array_equal(np.asarray(VectorAssembler(
        ["v"], "x").transform(f)._column_values("x")), v)
    np.testing.assert_array_equal(np.asarray(VectorAssembler(
        ["b"], "x").transform(f)._column_values("x")),
        b[:, None].astype(float))


def test_assembler_blocks_meet_in_one_concatenate():
    """More 1-D columns than one chain takes, and a vector column between
    scalars: blocks in the columns' order, one concatenate."""
    n = 9
    wide = feature._CHAIN + 5
    cols = tuple(jnp.arange(n, dtype=jnp.float64) * (i + 1)
                 for i in range(wide))
    out = feature._assemble(cols, jnp.float64)
    np.testing.assert_array_equal(
        np.asarray(out), np.stack([np.asarray(c) for c in cols], axis=1))
    v = jnp.arange(n * 2, dtype=jnp.float64).reshape(n, 2)
    mixed = (cols[0], cols[1], v, cols[2])
    jaxpr = jax.make_jaxpr(
        lambda c: feature._assemble(c, jnp.float64))(mixed).jaxpr
    body = jaxpr.eqns[0].params["jaxpr"].jaxpr
    assert [e.primitive.name for e in body.eqns].count("concatenate") == 1
    np.testing.assert_array_equal(
        np.asarray(feature._assemble(mixed, jnp.float64)),
        np.concatenate([np.asarray(cols[0])[:, None],
                        np.asarray(cols[1])[:, None], np.asarray(v),
                        np.asarray(cols[2])[:, None]], axis=1))
    empty = feature._assemble((jnp.zeros((0,)), jnp.zeros((0, 2))),
                              jnp.float64)
    assert empty.shape == (0, 3)


def test_assembler_converts_a_host_column_on_the_host():
    """A 64-bit host column goes to ``float_dtype()`` as ``jnp.asarray(a,
    dt)`` took it there — not through jit's 32-bit canonical integers."""
    big = np.array([2**40 + 1, -2**35, 7], dtype=np.int64)
    out = feature._assemble((np.asarray(big, np.float64),
                             jnp.arange(3.0)), jnp.float64)
    np.testing.assert_array_equal(np.asarray(out)[:, 0],
                                  big.astype(np.float64))

    class Host:                    # a frame that holds a host column
        num_slots = 3
        mask = jnp.ones(3, bool)

        def _column_values(self, name):
            return big

        def with_column(self, name, values):
            return values

    got = VectorAssembler(["h"], "x").transform(Host())
    np.testing.assert_array_equal(np.asarray(got)[:, 0],
                                  big.astype(np.float64))


def test_assembler_on_28_columns_launches_one_program(monkeypatch):
    """The span says ``programs=1``; ``transform`` calls the one compiled
    entry once, hands it the frame's own columns and keeps what it
    returns; and that entry is one program, not a launch a column."""
    n = 64
    cols = {f"x{i}": np.arange(n, dtype=np.float64) + i for i in range(28)}
    f = Frame(cols)
    calls, results = [], []
    compiled = feature._assemble

    def counted(columns, dtype):
        calls.append((len(columns), dtype))
        assert all(c is f._column_values(name)      # the frame's own
                   for c, name in zip(columns, cols))
        results.append(compiled(columns, dtype))
        return results[-1]

    monkeypatch.setattr(feature, "_assemble", counted)
    obs.reset()
    obs.enable()
    try:
        out = VectorAssembler(list(cols), "features").transform(f)
    finally:
        obs.disable()
    monkeypatch.undo()
    assert calls == [(28, jnp.float64)]
    # what the program returned is the column, untouched by a later op
    assert out._column_values("features") is results[0]
    (span,) = [s for s in obs.TRACER.spans() if s.name == "feature.assemble"]
    assert span.attrs["programs"] == 1
    assert span.attrs["columns"] == 28 and span.attrs["width"] == 28
    np.testing.assert_array_equal(
        np.asarray(out._column_values("features")),
        np.stack([cols[c] for c in cols], axis=1))
    abstract = tuple(jax.ShapeDtypeStruct((n,), jnp.float64)
                     for _ in range(28))
    jaxpr = jax.make_jaxpr(
        lambda c: compiled(c, jnp.float64))(abstract).jaxpr
    (call,) = jaxpr.eqns                      # one launch: the pjit
    assert call.primitive.name in ("pjit", "jit")
    body = call.params["jaxpr"].jaxpr
    names = [e.primitive.name for e in body.eqns]
    assert "pjit" not in names and "jit" not in names
    # 28 scalar columns are one chain of selects — no concatenate, no
    # (n, 1) reshape a column — writing the (n, 28) matrix once
    assert "concatenate" not in names and "reshape" not in names
    assert names.count("select_n") == 27
    assert body.outvars[0].aval.shape == (n, 28)
    obs.reset()


# ---------------------------------------------------------------------------
# The lowered programs at the benchmark's sizes
# ---------------------------------------------------------------------------


def _abstract_columns(n, d, weighted=False):
    f32 = jnp.float32
    return DesignColumns(
        jax.ShapeDtypeStruct((n, d), f32), jax.ShapeDtypeStruct((n,), f32),
        jax.ShapeDtypeStruct((n,), jnp.bool_),
        jax.ShapeDtypeStruct((n,), f32) if weighted else None)


@pytest.mark.parametrize("n,d", [(120_000_000, 1), (11_000_000, 28)])
@pytest.mark.parametrize("family", ["linear", "linear-weighted",
                                    "logistic-newton", "svc"])
def test_columns_entry_has_no_packed_parameter_at_cell_size(family, n, d):
    """Lowered (never run) at the two cells' shapes, the columns entry
    takes the columns and the hyper-parameters: no ``(n, d+2)`` array is
    an argument of the program, as it was when ``pack_design`` fed it."""
    weighted = family == "linear-weighted"
    fit = {
        "linear": lambda: fused_linear_fit_packed(
            None, "fista", 40, 1e-6, True, True).jit_fn,
        "linear-weighted": lambda: fused_linear_fit_packed(
            None, "fista", 40, 1e-6, True, True).jit_fn,
        "logistic-newton": lambda: fused_logistic_fit_packed(
            None, 100, 1e-5, True, True, solver="newton"),
        "svc": lambda: fused_svc_fit_packed(None, 100, 1e-6, True, True),
    }[family]()
    hyper = jax.ShapeDtypeStruct((2,), jnp.float32)
    lowered = fit.lower(_abstract_columns(n, d, weighted), hyper)
    shapes = sorted(tuple(a.shape) for a in
                    jax.tree_util.tree_leaves(lowered.in_avals))
    want = [(2,), (n,), (n,), (n, d)] + ([(n,)] if weighted else [])
    assert shapes == sorted(want)
    assert (n, d + 2) not in shapes
    text = lowered.as_text()
    head = text[text.index("func.func public @main"):]
    head = head[:head.index("{\n")]
    assert f"tensor<{n}x{d + 2}x" not in head
    assert f"tensor<{n}x{d}xf32>" in head


def test_packed_entry_still_takes_z_at_cell_size():
    fit = fused_linear_fit_packed(None, "fista", 40, 1e-6, True, True)
    lowered = fit.jit_fn.lower(
        jax.ShapeDtypeStruct((120_000_000, 3), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.float32))
    shapes = [tuple(a.shape) for a in
              jax.tree_util.tree_leaves(lowered.in_avals)]
    assert shapes == [(120_000_000, 3), (2,)]


def test_linear_columns_entry_packs_under_its_scope():
    """``dq.fit.pack`` names what the program makes of the columns, and
    ``dq.fit.gram`` the contraction that reads it."""
    fit = fused_linear_fit_packed(None, "fista", 5, 1e-6, True, True)
    text = fit.jit_fn.lower(_abstract_columns(64, 2),
                            jax.ShapeDtypeStruct((2,), jnp.float32)
                            ).as_text(debug_info=True)
    assert "dq.fit.gram/dq.fit.pack/concatenate" in text
    assert "dq.fit.gram/dot_general" in text


# ---------------------------------------------------------------------------
# Counters and spans: which entry a fit went through
# ---------------------------------------------------------------------------


def _frame(kind="reg", n=96):
    X, y, yc, _, w = (np.asarray(a) for a in _data(6))
    f = Frame({"features": X[:n], "label": (y if kind == "reg" else yc)[:n],
               "w": w[:n] + 1.0, "keep": (np.arange(n) % 5 > 0)
               .astype(np.int32)})
    return f.filter(f.col("keep") > 0)


ESTIMATORS = {
    "linear": (lambda: LinearRegression(max_iter=20, reg_param=0.1,
                                        elastic_net_param=1.0), "reg"),
    "linear-weighted": (lambda: LinearRegression(
        max_iter=20, reg_param=0.1, weight_col="w"), "reg"),
    "logistic": (lambda: LogisticRegression(max_iter=20), "bin"),
    "logistic-weighted": (lambda: LogisticRegression(
        max_iter=20, reg_param=0.01, weight_col="w"), "bin"),
    "svc": (lambda: LinearSVC(max_iter=20, reg_param=0.01), "bin"),
}


def _moved(before, *names):
    after = counters.snapshot()
    return tuple(after.get(n, 0) - before.get(n, 0) for n in names)


@pytest.mark.parametrize("name", sorted(ESTIMATORS))
def test_one_device_fit_counts_pack_in_program(name):
    build, kind = ESTIMATORS[name]
    frame = _frame(kind)
    obs.reset()
    obs.enable()
    before = counters.snapshot()
    try:
        build().fit(frame)
    finally:
        obs.disable()
    assert _moved(before, "fit.pack_in_program", "fit.pack_eager") == (1, 0)
    (pack,) = [s for s in obs.TRACER.spans() if s.name == "fit.pack"]
    assert pack.attrs["lowering"] == "in-program"
    obs.reset()


@pytest.mark.parametrize("name", sorted(ESTIMATORS))
def test_sharded_fit_counts_pack_eager_and_agrees(name, session):
    """On the CPU's eight forced devices the fit goes through
    ``pack_design`` and a row-sharded ``Z`` — and finds the model the
    one-device columns entry finds."""
    assert session.mesh.devices.size > 1
    build, kind = ESTIMATORS[name]
    frame = _frame(kind)
    single = build().fit(frame, mesh=make_mesh(1))
    obs.reset()
    obs.enable()
    before = counters.snapshot()
    try:
        sharded = build().fit(frame, mesh=session.mesh)
    finally:
        obs.disable()
    assert _moved(before, "fit.pack_in_program", "fit.pack_eager") == (0, 1)
    (pack,) = [s for s in obs.TRACER.spans() if s.name == "fit.pack"]
    assert pack.attrs["lowering"] == "eager"
    np.testing.assert_allclose(np.asarray(sharded.coefficients),
                               np.asarray(single.coefficients),
                               rtol=1e-6, atol=1e-8)
    assert abs(sharded.intercept - single.intercept) < 1e-6
    obs.reset()


def test_pack_design_is_one_program_and_counts_eager():
    X, y, _, mask, w = _data(7)
    jaxpr = jax.make_jaxpr(lambda *a: pack_design(*a))(X, y, mask).jaxpr
    (call,) = jaxpr.eqns
    assert call.primitive.name in ("pjit", "jit")
    before = counters.snapshot()
    pack_design(X, y, mask)
    pack_design_weighted(X, y, mask, w)
    pack_design(np.asarray(X), np.asarray(y), np.asarray(mask))
    assert _moved(before, "fit.pack_eager") == (3,)
    assert isinstance(pack_design(np.asarray(X), np.asarray(y),
                                  np.asarray(mask)), np.ndarray)


def test_program_audit_lists_both_entries():
    """A fit program called through both entries registers a handle for
    each, and each re-traces at the calling convention it ran with."""
    from sparkdq4ml_tpu.parallel import distributed

    X, y, _, mask, _ = _data(8)
    fit = fused_linear_fit_packed(None, "fista", 7, 1e-6, True, True)
    hyper = jnp.asarray([0.1, 1.0])
    fit(DesignColumns(X, y, mask, None), hyper)
    fit(pack_design(X, y, mask), hyper)
    assert set(fit.examples) == {"columns", "packed"}
    mine = [h for h in distributed.fit_program_handles()
            if "'fista', 7," in h.program_key]
    assert len(mine) == 2
    assert sum(h.program_key.endswith("[columns]") for h in mine) == 1
    for h in mine:
        jax.make_jaxpr(h.fn)(*h.args)
    stats = distributed.fit_factory_cache_stats()["fused_linear_fit_packed"]
    assert any(e["called_through"] == ["columns", "packed"]
               for e in stats["entries"])
