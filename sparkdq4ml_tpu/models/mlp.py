"""MultilayerPerceptronClassifier (MLlib
``org.apache.spark.ml.classification.MultilayerPerceptronClassifier`` —
shipped by the reference's mllib dependency, pom.xml:29-32).

MLlib's MLPC is a fixed topology: sigmoid hidden layers + softmax output,
cross-entropy loss, trained with LBFGS over treeAggregate. Here the whole
network is a stack of MXU matmuls, the loss/gradient come from
``jax.value_and_grad`` over the batched forward (per-row reductions psum
over the data axis under a mesh — gradients flow through the collective
with correct SPMD semantics), and training is the shared full-batch Adam
``lax.scan`` (models/solvers.adam_scan) — one jitted program, zero host
round-trips.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import float_dtype
from ..frame.frame import Frame
from .base import Estimator, Model, persistable
from ..parallel.mesh import serialize_collectives


def _mlp_forward(params, X):
    """Sigmoid hidden layers + linear output logits (softmax at the loss)."""
    h = X
    for i, (W, b) in enumerate(params):
        z = h @ W + b
        h = z if i == len(params) - 1 else jax.nn.sigmoid(z)
    return h


@functools.lru_cache(maxsize=None)
def _mlp_fit_fn(mesh, layers: tuple, max_iter: int, lr: float, seed: int):
    num_classes = layers[-1]

    def core(X, y, mask, axis=None):
        dt = X.dtype
        wm = mask.astype(dt)
        n = jnp.sum(wm)
        if axis is not None:
            n = jax.lax.psum(n, axis)
        Y1 = jax.nn.one_hot(y.astype(jnp.int32), num_classes,
                            dtype=dt) * wm[:, None]

        def objective(params):
            # invalid rows arrive zeroed (host-side) and pads are zero by
            # construction — no per-iteration re-masking needed. LOCAL
            # share only: solvers.psum_value_and_grad differentiates its
            # psum over the mesh.
            logits = _mlp_forward(params, X)
            lse = jax.nn.logsumexp(logits, axis=1)
            ll = jnp.where(mask,
                           lse - jnp.sum(logits * Y1, axis=1), 0.0)
            return jnp.sum(ll) / n

        key = jax.random.PRNGKey(seed)
        params0 = []
        for i in range(len(layers) - 1):
            key, k1 = jax.random.split(key)
            fan_in, fan_out = layers[i], layers[i + 1]
            # Glorot-uniform init (MLlib's default weight init family)
            limit = jnp.sqrt(6.0 / (fan_in + fan_out)).astype(dt)
            W = jax.random.uniform(k1, (fan_in, fan_out), dt,
                                   -limit, limit)
            params0.append((W, jnp.zeros((fan_out,), dt)))

        from .solvers import adam_scan, psum_value_and_grad

        params, history = adam_scan(psum_value_and_grad(objective, axis),
                                    tuple(params0), max_iter, lr)
        return tuple(params), history

    if mesh is None:
        return jax.jit(lambda X, y, m: core(X, y, m))

    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, shard_map

    return serialize_collectives(jax.jit(shard_map(
        lambda X, y, m: core(X, y, m, DATA_AXIS), mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P())), mesh)


@persistable
class MultilayerPerceptronClassifier(Estimator):
    """MLlib ``MultilayerPerceptronClassifier`` builder surface:
    setLayers/setMaxIter/setStepSize/setSeed(+cols). ``layers`` gives
    [input, hidden..., output] sizes; the output size is the class count."""

    _persist_attrs = ('layers', 'max_iter', 'step_size', 'seed',
                      'features_col', 'label_col', 'prediction_col',
                      'probability_col', 'raw_prediction_col')

    def __init__(self, layers: Sequence[int] = (), max_iter: int = 100,
                 step_size: float = 0.03, seed: int = 0,
                 features_col: str = "features", label_col: str = "label",
                 prediction_col: str = "prediction",
                 probability_col: str = "probability",
                 raw_prediction_col: str = "rawPrediction"):
        self.layers = [int(v) for v in layers]
        self.max_iter = int(max_iter)
        self.step_size = float(step_size)
        self.seed = int(seed)
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col
        self.probability_col = probability_col
        self.raw_prediction_col = raw_prediction_col

    def set_layers(self, v):
        self.layers = [int(x) for x in v]
        return self

    def set_max_iter(self, v):
        self.max_iter = int(v)
        return self

    def set_step_size(self, v):
        self.step_size = float(v)
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

    setLayers = set_layers
    setMaxIter = set_max_iter
    setStepSize = set_step_size
    setSeed = set_seed
    setFeaturesCol = set_features_col
    setLabelCol = set_label_col
    setPredictionCol = set_prediction_col

    def fit(self, frame: Frame, mesh=None) \
            -> "MultilayerPerceptronClassificationModel":
        from ..parallel.distributed import pad_and_shard_rows
        from ..parallel.mesh import normalize_mesh

        mesh = normalize_mesh(mesh)
        dt = np.dtype(float_dtype())
        X = np.asarray(frame._column_values(self.features_col), dt)
        if X.ndim == 1:
            X = X[:, None]
        y = np.asarray(frame._column_values(self.label_col), np.float64)
        mask = np.asarray(frame.mask)
        yv = y[mask]
        if len(yv) == 0:
            raise ValueError("MultilayerPerceptronClassifier: no valid rows")
        if not np.all(np.isfinite(yv)) or np.any(yv < 0) \
                or np.any(yv != np.floor(yv)):
            raise ValueError("labels must be nonnegative integers 0..k-1")
        if not np.all(np.isfinite(X[mask])):
            raise ValueError("feature matrix has NaN/inf in valid rows")
        num_classes = int(yv.max()) + 1

        layers = list(self.layers)
        if not layers:
            layers = [X.shape[1], num_classes]
        if len(layers) < 2:
            raise ValueError("layers needs at least [input, output] sizes")
        if layers[0] != X.shape[1]:
            raise ValueError(f"layers[0]={layers[0]} != feature size "
                             f"{X.shape[1]}")
        if layers[-1] < num_classes:
            raise ValueError(f"layers[-1]={layers[-1]} < {num_classes} "
                             "observed classes")

        Xh = np.where(mask[:, None], X, 0.0)
        yh = np.where(mask, y, 0.0)
        Xd, yd, md = pad_and_shard_rows(mesh, Xh.astype(dt),
                                        yh.astype(dt), mask)
        fit_fn = _mlp_fit_fn(mesh, tuple(layers), self.max_iter,
                             self.step_size, self.seed)
        params, history = jax.block_until_ready(fit_fn(Xd, yd, md))
        weights = [(np.asarray(W, np.float64), np.asarray(b, np.float64))
                   for W, b in params]
        return MultilayerPerceptronClassificationModel(
            layers, weights, self._params_dict(),
            np.asarray(history, np.float64).tolist())

    def _params_dict(self):
        return {k: getattr(self, k) for k in self._persist_attrs}


@persistable
class MultilayerPerceptronClassificationModel(Model):
    """Fitted MLP: ``weights`` is the [(W, b), ...] stack; transform adds
    rawPrediction (logits), probability (softmax), prediction (argmax)."""

    _persist_attrs = ('layers', 'flat_weights', '_params', 'loss_history')

    def __init__(self, layers, weights=None, params=None,
                 loss_history=None, flat_weights=None):
        self.layers = [int(v) for v in layers]
        if weights is not None:
            self.flat_weights = {f"W{i}": np.asarray(W)
                                 for i, (W, _) in enumerate(weights)}
            self.flat_weights.update(
                {f"b{i}": np.asarray(b)
                 for i, (_, b) in enumerate(weights)})
        else:
            self.flat_weights = {k: np.asarray(v)
                                 for k, v in (flat_weights or {}).items()}
        self._params = dict(params or {})
        self.loss_history = list(loss_history or [])

    def _post_load(self):
        self.layers = [int(v) for v in self.layers]
        self.flat_weights = {k: np.asarray(v)
                             for k, v in self.flat_weights.items()}

    def _p(self, k, default=None):
        return self._params.get(k, default)

    @property
    def weights(self):
        n = len(self.layers) - 1
        return [(self.flat_weights[f"W{i}"], self.flat_weights[f"b{i}"])
                for i in range(n)]

    @property
    def num_features(self):
        return int(self.layers[0])

    numFeatures = num_features

    def _logits(self, X):
        Xd = jnp.asarray(X, float_dtype())
        if Xd.ndim == 1:
            Xd = Xd[:, None]
        params = [(jnp.asarray(W, Xd.dtype), jnp.asarray(b, Xd.dtype))
                  for W, b in self.weights]
        return _mlp_forward(params, Xd)

    def transform(self, frame: Frame) -> Frame:
        p = self._params
        logits = self._logits(frame._column_values(
            p.get("features_col", "features")))
        prob = jax.nn.softmax(logits, axis=1)
        pred = jnp.argmax(logits, axis=1).astype(float_dtype())
        out = frame.with_column(p.get("raw_prediction_col", "rawPrediction"),
                                logits)
        out = out.with_column(p.get("probability_col", "probability"), prob)
        return out.with_column(p.get("prediction_col", "prediction"), pred)

    def predict(self, features) -> float:
        x = np.asarray(features, np.float64).reshape(1, -1)
        return float(np.argmax(np.asarray(self._logits(x))[0]))
