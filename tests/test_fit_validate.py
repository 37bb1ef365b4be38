"""The fit's input validation on the device (``models/base.label_stats``).

``LogisticRegression._fit``, ``LinearSVC._fit`` and the weighted
``LinearRegression._fit`` used to pull the labels, the mask and the weights
to the host and ask numpy: any valid row at all, a negative label, a label
that is not an integer, the largest label, a weight that is not ``>= 0``.
The same questions are now one compiled reduction read back as five
scalars. Every case below holds the device's verdict to the numpy rule it
replaces, and every error to its type and message.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.models import (LinearRegression, LinearSVC,
                                   LogisticRegression)
from sparkdq4ml_tpu.models.base import label_stats, read_label_stats
from sparkdq4ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

N = 64
LABELS_MSG = "labels must be nonnegative integers"


def numpy_rule(y, mask, w=None):
    """What the three sites computed on host copies before this helper
    (+/-inf beside it: ``int(inf)`` raised OverflowError a line later)."""
    yv = np.asarray(y)[np.asarray(mask)]
    out = {"rows": len(yv)}
    if len(yv):
        out["label_bad"] = bool(np.any(yv != np.floor(yv))
                                or np.any(np.isinf(yv)))
        out["negative"] = bool(np.any(yv < 0))
        if not (out["label_bad"] or out["negative"]):
            out["label_max"] = float(yv.max())
    if w is not None:
        out["weight_bad"] = not bool(np.all(np.asarray(w)[np.asarray(mask)]
                                            >= 0))
    return out


def device_rule(y, mask, w=None):
    s = read_label_stats(label_stats(y, mask, w))
    out = {"rows": int(s.rows)}
    if s.rows:
        out["label_bad"] = s.label_bad
        # NaN compares false with everything: the flag has it
        out["negative"] = bool(s.label_min < 0)
        if not (out["label_bad"] or out["negative"]):
            out["label_max"] = s.label_max
    if w is not None:
        out["weight_bad"] = s.weight_bad
    return out


def _labels(kind):
    """(y, mask) of N rows: classes 0/1 with one planted value."""
    y = (np.arange(N) % 2).astype(np.float64)
    mask = np.ones(N, bool)
    mask[5::9] = False
    valid, masked = 10, 5                       # mask[10] kept, mask[5] not
    assert mask[valid] and not mask[masked]
    if kind == "clean":
        pass
    elif kind == "negative":
        y[valid] = -1.0
    elif kind == "fractional":
        y[valid] = 0.5
    elif kind == "nan":
        y[valid] = np.nan
    elif kind == "pos_inf":
        y[valid] = np.inf
    elif kind == "neg_inf":
        y[valid] = -np.inf
    elif kind == "masked_nan_and_negative":
        y[masked], y[14] = np.nan, -3.0         # 14 = 5 + 9: filtered too
        assert not mask[14]
    elif kind == "three_classes":
        y[valid] = 2.0
    elif kind == "all_masked":
        mask[:] = False
    else:
        raise AssertionError(kind)
    return y, mask


LABEL_KINDS = ["clean", "negative", "fractional", "nan", "pos_inf",
               "neg_inf", "masked_nan_and_negative", "three_classes",
               "all_masked"]


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("kind", LABEL_KINDS)
def test_label_verdict_equals_the_numpy_rule(kind, dtype):
    y, mask = _labels(kind)
    y = y.astype(dtype)
    got = device_rule(jnp.asarray(y), jnp.asarray(mask))
    assert got == numpy_rule(y, mask)
    # the vector is as wide as the labels, and five scalars long
    vec = label_stats(jnp.asarray(y), jnp.asarray(mask))
    assert vec.shape == (5,) and vec.dtype == dtype


def _weights(kind):
    w = np.linspace(0.0, 3.0, N)
    _, mask = _labels("clean")
    valid, masked = 10, 5
    if kind == "negative_valid":
        w[valid] = -0.5
    elif kind == "nan_valid":
        w[valid] = np.nan
    elif kind == "negative_masked":
        w[masked] = -0.5
    elif kind == "nan_masked":
        w[masked] = np.nan
    elif kind != "clean":
        raise AssertionError(kind)
    return w, mask


WEIGHT_KINDS = ["clean", "negative_valid", "nan_valid", "negative_masked",
                "nan_masked"]


@pytest.mark.parametrize("kind", WEIGHT_KINDS)
def test_weight_verdict_equals_the_numpy_rule(kind):
    w, mask = _weights(kind)
    y, _ = _labels("clean")
    want = numpy_rule(y, mask, w)
    assert want["weight_bad"] == kind.endswith("_valid")
    assert device_rule(jnp.asarray(y), jnp.asarray(mask),
                       jnp.asarray(w)) == want
    # weights alone, as LinearRegression asks: the label entries read 0
    s = read_label_stats(label_stats(None, jnp.asarray(mask),
                                     jnp.asarray(w)))
    assert s.weight_bad == want["weight_bad"]
    assert (s.label_min, s.label_max, s.label_bad) == (0.0, 0.0, False)
    assert s.rows == mask.sum()


@pytest.mark.parametrize("kind", ["clean", "nan", "negative",
                                  "masked_nan_and_negative", "all_masked"])
def test_sharded_operands_give_the_same_verdict(kind):
    y, mask = _labels(kind)
    w, _ = _weights("nan_masked")
    rows = NamedSharding(make_mesh(8), P(DATA_AXIS))
    one = device_rule(jnp.asarray(y), jnp.asarray(mask), jnp.asarray(w))
    many = device_rule(jax.device_put(y, rows), jax.device_put(mask, rows),
                       jax.device_put(w, rows))
    # (with a NaN among the valid labels min and max are the backend's
    # business — a cross-shard min may drop it — and label_bad the verdict)
    assert many == one == numpy_rule(y, mask, w)


def test_the_reduction_carries_its_scope():
    """As section 6 of test_trace_bridge.py: the helper's operations name
    ``dq.fit.validate`` in their metadata, in the lowered text and in the
    compiled HLO, and nowhere else."""
    y, mask = _labels("clean")
    args = (jnp.asarray(y), jnp.asarray(mask), jnp.asarray(_weights(
        "clean")[0]))
    lowered = label_stats.lower(*args)
    assert "dq.fit.validate/reduce_min" in lowered.as_text(debug_info=True)
    assert "dq.fit.validate/reduce_or" in lowered.as_text(debug_info=True)
    assert "dq.fit.validate" not in lowered.as_text()
    assert 'op_name="jit(label_stats)/dq.fit.validate/' in \
        lowered.compile().as_text()


# ---------------------------------------------------------------------------
# The estimators: every error keeps its type and message
# ---------------------------------------------------------------------------


def _frame(y, mask, w=None, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(len(y), 3))
    cols = {"features": jnp.asarray(X), "label": jnp.asarray(y)}
    if w is not None:
        cols["w"] = jnp.asarray(w)
    return Frame(cols).filter(jnp.asarray(mask))


@pytest.mark.parametrize("kind,message", [
    ("negative", LABELS_MSG), ("fractional", LABELS_MSG),
    ("nan", LABELS_MSG), ("pos_inf", LABELS_MSG), ("neg_inf", LABELS_MSG),
    ("all_masked", "LogisticRegression: no valid rows")])
@pytest.mark.parametrize("mesh", [None, 8], ids=["one_device", "mesh8"])
def test_logistic_rejects(kind, message, mesh):
    f = _frame(*_labels(kind))
    with pytest.raises(ValueError, match=message):
        LogisticRegression(max_iter=5).fit(
            f, mesh=make_mesh(mesh) if mesh else None)


def test_logistic_three_classes_under_binomial_keeps_its_message():
    f = _frame(*_labels("three_classes"))
    with pytest.raises(ValueError, match="binomial family requires binary "
                       "labels, found 3 classes; use family='multinomial'"):
        LogisticRegression(family="binomial", max_iter=5).fit(f)


def test_logistic_three_classes_under_auto_is_multinomial():
    m = LogisticRegression(max_iter=5).fit(_frame(*_labels("three_classes")))
    assert m.is_multinomial and m.num_classes == 3
    b = LogisticRegression(max_iter=5).fit(_frame(*_labels("clean")))
    assert not b.is_multinomial


@pytest.mark.parametrize("mesh", [None, 8], ids=["one_device", "mesh8"])
def test_logistic_masked_payload_fits_as_without_those_rows(mesh):
    """NaN and a negative label on filtered rows only: the fit succeeds and
    equals the fit of a table that never held those rows."""
    y, mask = _labels("masked_nan_and_negative")
    rng = np.random.default_rng(0)
    X = rng.normal(size=(N, 3))
    y = np.where(np.isfinite(y) & (y >= 0), (X[:, 0] > 0).astype(float), y)
    dirty = Frame({"features": jnp.asarray(X),
                   "label": jnp.asarray(y)}).filter(jnp.asarray(mask))
    clean = Frame({"features": jnp.asarray(X[mask]),
                   "label": jnp.asarray(y[mask])})
    mesh = make_mesh(mesh) if mesh else None
    a = LogisticRegression(max_iter=50, reg_param=0.1).fit(dirty, mesh=mesh)
    b = LogisticRegression(max_iter=50, reg_param=0.1).fit(clean, mesh=None)
    np.testing.assert_allclose(a.coefficients, b.coefficients, rtol=1e-8)
    assert a.intercept == pytest.approx(b.intercept, rel=1e-8)


@pytest.mark.parametrize("kind,message", [
    ("three_classes", "LinearSVC requires binary 0/1 labels"),
    ("negative", "LinearSVC requires binary 0/1 labels"),
    ("fractional", "LinearSVC requires binary 0/1 labels"),
    ("nan", "LinearSVC requires binary 0/1 labels"),
    ("all_masked", "LinearSVC: no valid rows")])
def test_linear_svc_rejects(kind, message):
    with pytest.raises(ValueError, match=message):
        LinearSVC(max_iter=5).fit(_frame(*_labels(kind)))


def test_linear_svc_accepts_masked_payload():
    y, mask = _labels("masked_nan_and_negative")
    m = LinearSVC(max_iter=5).fit(_frame(y, mask))
    assert np.all(np.isfinite(m.coefficients))


ESTIMATORS = {
    "logistic": lambda: LogisticRegression(max_iter=20, weight_col="w"),
    "linear": lambda: LinearRegression(max_iter=20, weight_col="w"),
}


@pytest.mark.parametrize("estimator", list(ESTIMATORS))
@pytest.mark.parametrize("kind", ["negative_valid", "nan_valid"])
def test_weight_on_a_valid_row_is_rejected(estimator, kind):
    w, mask = _weights(kind)
    y, _ = _labels("clean")
    with pytest.raises(ValueError, match="weights must be nonnegative"):
        ESTIMATORS[estimator]().fit(_frame(y, mask, w))


@pytest.mark.parametrize("estimator", list(ESTIMATORS))
@pytest.mark.parametrize("kind", ["negative_masked", "nan_masked"])
def test_weight_on_a_masked_row_passes(estimator, kind):
    """...and the fit equals the fit with a clean weight in that slot."""
    w, mask = _weights(kind)
    y, _ = _labels("clean")
    a = ESTIMATORS[estimator]().fit(_frame(y, mask, w))
    b = ESTIMATORS[estimator]().fit(_frame(y, mask, _weights("clean")[0]))
    assert np.all(np.isfinite(a.coefficients))
    np.testing.assert_array_equal(a.coefficients, b.coefficients)
    assert a.intercept == b.intercept


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
def test_label_column_of_either_width_fits(dtype):
    y, mask = _labels("clean")
    m = LogisticRegression(max_iter=5).fit(_frame(y.astype(dtype), mask))
    assert m.num_classes == 2
    with pytest.raises(ValueError, match=LABELS_MSG):
        LogisticRegression(max_iter=5).fit(
            _frame(_labels("fractional")[0].astype(dtype), mask))
