"""AFTSurvivalRegression (MLlib
``org.apache.spark.ml.regression.AFTSurvivalRegression`` — shipped by the
reference's mllib dependency, pom.xml:29-32).

Weibull accelerated-failure-time model: ``log t = β₀ + xᵀβ + σ·ε`` with
ε Gumbel-distributed; censored rows (censor=0) contribute the survival
term of the likelihood, events (censor=1) the density term.

TPU-first: the negative log-likelihood and its gradient are ONE fused
masked reduction over rows (psum'd over the data axis under a mesh), and
the optimizer is a full-batch Adam ``lax.scan`` on (β, β₀, log σ) — the
whole fit is a single jitted program with zero host round-trips, playing
the role of MLlib's LBFGS-over-treeAggregate. Features are standardized
internally like the other linear fits (MLlib does the same for AFT).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import float_dtype
from ..frame.frame import Frame
from .base import Estimator, Model, persistable
from ..parallel.mesh import serialize_collectives


class AftFit(NamedTuple):
    coefficients: jnp.ndarray
    intercept: jnp.ndarray
    scale: jnp.ndarray
    loss_history: jnp.ndarray


def _aft_core(X, logt, censor, mask, n, std, max_iter, lr, axis=None):
    """Adam on the mean Weibull-AFT negative log-likelihood.

    With ε = (log t − β₀ − xᵀβ)/σ and δ the event indicator:
        −ll_i = e^{ε_i} − δ_i·(ε_i − log σ)
    (the Gumbel density/survival split; MLlib's AFTAggregator computes the
    same quantity row-wise). All row reductions fuse into one psum'd
    vector under sharding.
    """
    dt = X.dtype
    d = X.shape[1]
    valid = std > 0
    sx = jnp.where(valid, std, 1.0)
    wm = mask.astype(dt)
    Xs = (X / sx) * wm[:, None]
    lt = logt * wm
    dl = censor * wm

    def reduce_(v):
        return jax.lax.psum(v, axis) if axis is not None else v

    def neg_ll(params):
        # LOCAL share of the likelihood: solvers.psum_value_and_grad
        # differentiates its psum over the mesh
        beta, b0, logsig = params[:d], params[d], params[d + 1]
        sig = jnp.exp(logsig)
        eps = (lt - b0 * wm - Xs @ beta) / sig
        # masked rows: wm=0 ⇒ eps=0 ⇒ e^0=1 would leak — gate every term
        term = jnp.where(mask, jnp.exp(eps) - dl * (eps - logsig), 0.0)
        return jnp.sum(term) / n

    from .solvers import adam_scan, psum_value_and_grad

    p0 = jnp.zeros((d + 2,), dt)
    # init β₀ to mean log t (the σ=1, β=0 stationary point neighborhood)
    b0_init = reduce_(jnp.sum(lt)) / n
    p0 = p0.at[d].set(b0_init)

    p, history = adam_scan(psum_value_and_grad(neg_ll, axis), p0,
                           max_iter, lr)
    beta = jnp.where(valid, p[:d] / sx, 0.0)   # unscale to raw features
    return AftFit(beta, p[d], jnp.exp(p[d + 1]), history)


@functools.lru_cache(maxsize=None)
def _aft_fit_fn(mesh, max_iter: int, lr: float):
    """Jitted (and sharded) AFT fit, cached per (mesh, config)."""
    def stats_and_fit(X, logt, censor, mask, axis=None):
        from .classification import _feature_stats, _sharded_feature_stats

        n, std = _feature_stats(X, logt, mask) if axis is None \
            else _sharded_feature_stats(X, mask)
        return _aft_core(X, logt, censor, mask, n, std, max_iter, lr, axis)

    if mesh is None:
        return jax.jit(lambda X, lt, c, m: stats_and_fit(X, lt, c, m))

    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, shard_map

    return serialize_collectives(jax.jit(shard_map(
        lambda X, lt, c, m: stats_and_fit(X, lt, c, m, DATA_AXIS),
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS)),
        out_specs=P())), mesh)


@persistable
class AFTSurvivalRegression(Estimator):
    """MLlib ``AFTSurvivalRegression`` builder surface: setMaxIter/
    setFeaturesCol/setLabelCol/setCensorCol/setPredictionCol/
    setQuantileProbabilities/setQuantilesCol (+ a ``step_size`` knob for
    the Adam loop)."""

    _persist_attrs = ('max_iter', 'step_size', 'features_col', 'label_col',
                      'censor_col', 'prediction_col',
                      'quantile_probabilities', 'quantiles_col')

    def __init__(self, max_iter: int = 300, step_size: float = 0.1,
                 features_col: str = "features", label_col: str = "label",
                 censor_col: str = "censor",
                 prediction_col: str = "prediction",
                 quantile_probabilities=(0.01, 0.05, 0.1, 0.25, 0.5, 0.75,
                                         0.9, 0.95, 0.99),
                 quantiles_col: Optional[str] = None):
        self.max_iter = int(max_iter)
        self.step_size = float(step_size)
        self.features_col = features_col
        self.label_col = label_col
        self.censor_col = censor_col
        self.prediction_col = prediction_col
        self.quantile_probabilities = self._check_probs(
            quantile_probabilities)
        self.quantiles_col = quantiles_col

    @staticmethod
    def _check_probs(v):
        probs = tuple(float(q) for q in v)
        if not probs:
            raise ValueError("quantile probabilities must be non-empty")
        if any(not 0.0 < q < 1.0 for q in probs):
            raise ValueError("quantile probabilities must be in (0, 1)")
        return probs

    def set_max_iter(self, v):
        self.max_iter = int(v)
        return self

    def set_censor_col(self, v):
        self.censor_col = v
        return self

    def set_features_col(self, v):
        self.features_col = v
        return self

    def set_label_col(self, v):
        self.label_col = v
        return self

    def set_quantile_probabilities(self, v):
        self.quantile_probabilities = self._check_probs(v)
        return self

    def set_quantiles_col(self, v):
        self.quantiles_col = v
        return self

    def set_prediction_col(self, v):
        self.prediction_col = v
        return self

    setMaxIter = set_max_iter
    setCensorCol = set_censor_col
    setFeaturesCol = set_features_col
    setLabelCol = set_label_col
    setQuantileProbabilities = set_quantile_probabilities
    setQuantilesCol = set_quantiles_col
    setPredictionCol = set_prediction_col

    def fit(self, frame: Frame, mesh=None) -> "AFTSurvivalRegressionModel":
        from ..parallel.distributed import pad_and_shard_rows
        from ..parallel.mesh import normalize_mesh

        mesh = normalize_mesh(mesh)
        dt = np.dtype(float_dtype())
        X = np.asarray(frame._column_values(self.features_col), dt)
        if X.ndim == 1:
            X = X[:, None]
        t = np.asarray(frame._column_values(self.label_col), np.float64)
        c = np.asarray(frame._column_values(self.censor_col), np.float64)
        mask = np.asarray(frame.mask)
        if mask.sum() == 0:
            raise ValueError("AFTSurvivalRegression: no valid rows")
        tv = t[mask]
        if not (np.all(np.isfinite(tv)) and np.all(tv > 0)):
            raise ValueError("survival times must be finite and > 0")
        cv = c[mask]
        if not np.all((cv == 0) | (cv == 1)):
            raise ValueError("censor column must be 0.0 or 1.0")
        if not np.all(np.isfinite(X[mask])):
            raise ValueError("feature matrix has NaN/inf in valid rows")

        # masked slots: zero features and log t (0 * NaN would poison)
        Xh = np.where(mask[:, None], X, 0.0)
        logt = np.where(mask, np.log(np.where(mask, t, 1.0)), 0.0)
        ch = np.where(mask, c, 0.0)
        Xd, ltd, cd, md = pad_and_shard_rows(
            mesh, Xh.astype(dt), logt.astype(dt), ch.astype(dt), mask)
        r = jax.block_until_ready(
            _aft_fit_fn(mesh, self.max_iter, self.step_size)(Xd, ltd, cd,
                                                             md))
        return AFTSurvivalRegressionModel(
            np.asarray(r.coefficients, np.float64), float(r.intercept),
            float(r.scale), self._params_dict(),
            np.asarray(r.loss_history, np.float64).tolist())

    def _params_dict(self):
        return {k: getattr(self, k) for k in self._persist_attrs}


@persistable
class AFTSurvivalRegressionModel(Model):
    """Fitted Weibull AFT: ``predict`` = exp(β₀ + xᵀβ) (MLlib's point
    prediction), ``predict_quantiles`` = exp(μ)·(−log(1−q))^σ."""

    _persist_attrs = ('coefficients', 'intercept', 'scale', '_params',
                      'loss_history')

    def __init__(self, coefficients, intercept, scale, params=None,
                 loss_history=None):
        self.coefficients = np.asarray(coefficients, np.float64)
        self.intercept = float(intercept)
        self.scale = float(scale)
        self._params = dict(params or {})
        self.loss_history = list(loss_history or [])

    def _p(self, k, default=None):
        return self._params.get(k, default)

    def _mu(self, X):
        Xd = jnp.asarray(X, float_dtype())
        if Xd.ndim == 1:
            Xd = Xd[:, None]
        return Xd @ jnp.asarray(self.coefficients, Xd.dtype) \
            + self.intercept

    def transform(self, frame: Frame) -> Frame:
        mu = self._mu(frame._column_values(
            self._p("features_col", "features")))
        out = frame.with_column(self._p("prediction_col", "prediction"),
                                jnp.exp(mu))
        qcol = self._p("quantiles_col")
        if qcol:
            qs = jnp.asarray(self._p("quantile_probabilities",
                                     (0.5,)), mu.dtype)
            q = jnp.exp(mu)[:, None] * \
                (-jnp.log1p(-qs))[None, :] ** self.scale
            out = out.with_column(qcol, q)
        return out

    def predict(self, features) -> float:
        x = np.asarray(features, np.float64).reshape(1, -1)
        return float(np.exp(np.asarray(self._mu(x))[0]))

    def predict_quantiles(self, features) -> np.ndarray:
        x = np.asarray(features, np.float64).reshape(1, -1)
        mu = float(np.asarray(self._mu(x))[0])
        qs = np.asarray(self._p("quantile_probabilities", (0.5,)))
        return np.exp(mu) * (-np.log1p(-qs)) ** self.scale

    predictQuantiles = predict_quantiles
