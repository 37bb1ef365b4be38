"""Factorization machines (MLlib ``org.apache.spark.ml.regression.FMRegressor``
/ ``classification.FMClassifier`` — shipped by the reference's mllib
dependency, pom.xml:29-32).

Model: ``ŷ(x) = b + xᵀw + ½ Σ_f [(xᵀV_f)² − (x²)ᵀ(V_f²)]`` — the rank-k
pairwise-interaction term is two MXU matmuls (the classic O(nk d) FM
identity), so the forward pass over all rows is three matmuls total.

TPU-first: loss + gradient via ``jax.value_and_grad`` over the batched
forward (squared loss for the regressor, logistic for the classifier),
optimized by a full-batch Adam ``lax.scan`` — one jitted program, zero
host round-trips; under a mesh the per-row loss reductions are psum'd
(MLlib instead runs minibatch gradient descent over RDD partitions).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import float_dtype
from ..frame.frame import Frame
from .base import Estimator, Model, persistable
from ..parallel.mesh import serialize_collectives


class FmFit(NamedTuple):
    intercept: jnp.ndarray
    linear: jnp.ndarray       # (d,)
    factors: jnp.ndarray      # (d, k)
    loss_history: jnp.ndarray


def fm_forward(X, b, w, V):
    """Batched FM score: three matmuls (the O(nkd) identity)."""
    s = X @ V                                     # (n, k)
    s2 = (X * X) @ (V * V)                        # (n, k)
    return b + X @ w + 0.5 * jnp.sum(s * s - s2, axis=1)


def _fm_core(X, y, mask, n, *, factor_size, loss, reg_param, max_iter, lr,
             init_std, seed, fit_intercept, fit_linear, axis=None):
    dt = X.dtype
    d = X.shape[1]
    wm = mask.astype(dt)
    Xm = X * wm[:, None]
    ym = y * wm

    # shard count: replicated objective terms are pre-divided by it so the
    # psum in psum_value_and_grad restores them exactly once
    nshards = (jax.lax.psum(jnp.asarray(1.0, dt), axis)
               if axis is not None else jnp.asarray(1.0, dt))

    def objective(params):
        # LOCAL share of the loss: solvers.psum_value_and_grad
        # differentiates its psum over the mesh
        b, w, V = params
        pred = fm_forward(Xm, b, w, V)
        if loss == "squared":
            per_row = (pred - ym) ** 2
        else:   # logistic: labels 0/1, stable softplus form
            z = (2.0 * ym - wm) * pred
            per_row = jnp.logaddexp(0.0, -z)
        data_loss = jnp.sum(jnp.where(mask, per_row, 0.0)) / n
        # L2 on every parameter group (MLlib's regParam)
        return data_loss + reg_param * (
            jnp.sum(w * w) + jnp.sum(V * V) + b * b) / nshards

    from .solvers import adam_scan, psum_value_and_grad

    key = jax.random.PRNGKey(seed)
    V0 = init_std * jax.random.normal(key, (d, factor_size), dt)
    params0 = (jnp.asarray(0.0, dt), jnp.zeros((d,), dt), V0)

    def grad_mask(g):
        if not fit_intercept:
            g = (jnp.zeros_like(g[0]),) + g[1:]
        if not fit_linear:
            g = (g[0], jnp.zeros_like(g[1]), g[2])
        return g

    (b, w, V), history = adam_scan(psum_value_and_grad(objective, axis),
                                   params0, max_iter, lr,
                                   grad_mask=grad_mask)
    return FmFit(b, w, V, history)


@functools.lru_cache(maxsize=None)
def _fm_fit_fn(mesh, factor_size, loss, reg_param, max_iter, lr, init_std,
               seed, fit_intercept, fit_linear):
    def run(X, y, mask, axis=None):
        wm = mask.astype(X.dtype)
        n = jnp.sum(wm)
        if axis is not None:
            n = jax.lax.psum(n, axis)
        return _fm_core(X, y, mask, n, factor_size=factor_size, loss=loss,
                        reg_param=reg_param, max_iter=max_iter, lr=lr,
                        init_std=init_std, seed=seed,
                        fit_intercept=fit_intercept, fit_linear=fit_linear,
                        axis=axis)

    if mesh is None:
        return jax.jit(lambda X, y, m: run(X, y, m))

    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, shard_map

    return serialize_collectives(jax.jit(shard_map(
        lambda X, y, m: run(X, y, m, DATA_AXIS), mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P())), mesh)


class _FMBase(Estimator):
    _persist_attrs = ('factor_size', 'reg_param', 'max_iter', 'step_size',
                      'init_std', 'fit_intercept', 'fit_linear', 'seed',
                      'features_col', 'label_col', 'prediction_col')

    def __init__(self, factor_size: int = 8, reg_param: float = 0.0,
                 max_iter: int = 100, step_size: float = 0.05,
                 init_std: float = 0.01, fit_intercept: bool = True,
                 fit_linear: bool = True, seed: int = 0,
                 features_col: str = "features", label_col: str = "label",
                 prediction_col: str = "prediction"):
        if factor_size < 1:
            raise ValueError("factor_size must be >= 1")
        self.factor_size = int(factor_size)
        self.reg_param = float(reg_param)
        self.max_iter = int(max_iter)
        self.step_size = float(step_size)
        self.init_std = float(init_std)
        self.fit_intercept = bool(fit_intercept)
        self.fit_linear = bool(fit_linear)
        self.seed = int(seed)
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col

    def set_factor_size(self, v):
        if v < 1:
            raise ValueError("factor_size must be >= 1")
        self.factor_size = int(v)
        return self

    def set_reg_param(self, v):
        self.reg_param = float(v)
        return self

    def set_max_iter(self, v):
        self.max_iter = int(v)
        return self

    def set_step_size(self, v):
        self.step_size = float(v)
        return self

    def set_init_std(self, v):
        self.init_std = float(v)
        return self

    def set_fit_intercept(self, v):
        self.fit_intercept = bool(v)
        return self

    def set_fit_linear(self, v):
        self.fit_linear = bool(v)
        return self

    def set_seed(self, v):
        self.seed = int(v)
        return self

    def set_features_col(self, v):
        self.features_col = v
        return self

    def set_label_col(self, v):
        self.label_col = v
        return self

    def set_prediction_col(self, v):
        self.prediction_col = v
        return self

    setFactorSize = set_factor_size
    setRegParam = set_reg_param
    setMaxIter = set_max_iter
    setStepSize = set_step_size
    setInitStd = set_init_std
    setFitIntercept = set_fit_intercept
    setFitLinear = set_fit_linear
    setSeed = set_seed
    setFeaturesCol = set_features_col
    setLabelCol = set_label_col
    setPredictionCol = set_prediction_col

    _loss = "squared"

    def _fit_arrays(self, frame, mesh):
        from ..parallel.distributed import pad_and_shard_rows
        from ..parallel.mesh import normalize_mesh

        mesh = normalize_mesh(mesh)
        dt = np.dtype(float_dtype())
        X = np.asarray(frame._column_values(self.features_col), dt)
        if X.ndim == 1:
            X = X[:, None]
        y = np.asarray(frame._column_values(self.label_col), np.float64)
        mask = np.asarray(frame.mask)
        if mask.sum() == 0:
            raise ValueError(f"{type(self).__name__}: no valid rows")
        if not np.all(np.isfinite(X[mask])):
            raise ValueError("feature matrix has NaN/inf in valid rows")
        if not np.all(np.isfinite(y[mask])):
            raise ValueError("label column has NaN/inf in valid rows")
        self._validate_labels(y[mask])
        Xh = np.where(mask[:, None], X, 0.0)
        yh = np.where(mask, y, 0.0)
        Xd, yd, md = pad_and_shard_rows(mesh, Xh.astype(dt),
                                        yh.astype(dt), mask)
        fit_fn = _fm_fit_fn(mesh, self.factor_size, self._loss,
                            self.reg_param, self.max_iter, self.step_size,
                            self.init_std, self.seed, self.fit_intercept,
                            self.fit_linear)
        r = jax.block_until_ready(fit_fn(Xd, yd, md))
        return (float(r.intercept), np.asarray(r.linear, np.float64),
                np.asarray(r.factors, np.float64),
                np.asarray(r.loss_history, np.float64).tolist())

    def _validate_labels(self, yv):
        pass

    def _params_dict(self):
        return {k: getattr(self, k) for k in self._persist_attrs}


@persistable
class FMRegressor(_FMBase):
    """MLlib ``FMRegressor``: squared loss."""

    def fit(self, frame: Frame, mesh=None) -> "FMRegressionModel":
        b, w, V, hist = self._fit_arrays(frame, mesh)
        return FMRegressionModel(b, w, V, self._params_dict(), hist)


@persistable
class FMClassifier(_FMBase):
    """MLlib ``FMClassifier``: binary 0/1 labels, logistic loss."""

    _loss = "logistic"
    _persist_attrs = _FMBase._persist_attrs + ('probability_col',
                                               'raw_prediction_col')

    def __init__(self, probability_col: str = "probability",
                 raw_prediction_col: str = "rawPrediction", **kw):
        super().__init__(**kw)
        self.probability_col = probability_col
        self.raw_prediction_col = raw_prediction_col

    def _validate_labels(self, yv):
        if not np.all((yv == 0) | (yv == 1)):
            raise ValueError("FMClassifier requires binary 0/1 labels")

    def fit(self, frame: Frame, mesh=None) -> "FMClassificationModel":
        b, w, V, hist = self._fit_arrays(frame, mesh)
        return FMClassificationModel(b, w, V, self._params_dict(), hist)


class _FMModelBase(Model):
    _persist_attrs = ('intercept', 'linear', 'factors', '_params',
                      'loss_history')

    def __init__(self, intercept, linear, factors, params=None,
                 loss_history=None):
        self.intercept = float(intercept)
        self.linear = np.asarray(linear, np.float64)
        self.factors = np.asarray(factors, np.float64)
        self._params = dict(params or {})
        self.loss_history = list(loss_history or [])

    def _p(self, k, default=None):
        return self._params.get(k, default)

    @property
    def factor_size(self):
        return int(self.factors.shape[1])

    factorSize = factor_size

    def _score(self, X):
        Xd = jnp.asarray(X, float_dtype())
        if Xd.ndim == 1:
            Xd = Xd[:, None]
        return fm_forward(Xd, jnp.asarray(self.intercept, Xd.dtype),
                          jnp.asarray(self.linear, Xd.dtype),
                          jnp.asarray(self.factors, Xd.dtype))


@persistable
class FMRegressionModel(_FMModelBase):
    def transform(self, frame: Frame) -> Frame:
        pred = self._score(frame._column_values(
            self._p("features_col", "features")))
        return frame.with_column(self._p("prediction_col", "prediction"),
                                 pred)

    def predict(self, features) -> float:
        x = np.asarray(features, np.float64).reshape(1, -1)
        return float(np.asarray(self._score(x))[0])


@persistable
class FMClassificationModel(_FMModelBase):
    def transform(self, frame: Frame) -> Frame:
        p = self._params
        F = self._score(frame._column_values(
            p.get("features_col", "features")))
        prob1 = jax.nn.sigmoid(F)
        out = frame.with_column(p.get("raw_prediction_col", "rawPrediction"),
                                jnp.stack([-F, F], axis=1))
        out = out.with_column(p.get("probability_col", "probability"),
                              jnp.stack([1.0 - prob1, prob1], axis=1))
        return out.with_column(p.get("prediction_col", "prediction"),
                               (F > 0).astype(float_dtype()))

    def predict(self, features) -> float:
        x = np.asarray(features, np.float64).reshape(1, -1)
        return float(np.asarray(self._score(x))[0] > 0)
