"""Model selection: ParamGridBuilder, CrossValidator, TrainValidationSplit
(BASELINE.json config: "CrossValidator grid (regParam × elasticNetParam)
pmapped across TPU cores").

TPU-first design — the grid axis is *grid-parallel* (SURVEY.md §5
"Parallelism strategies"): for linear regression every (fold × param) fit is
a tiny solve on sufficient statistics, so the whole cross-validation runs as

1. ONE data pass building ALL per-fold augmented Gramians from the packed
   design (``vmap`` over folds inside ``shard_map`` + one psum when a mesh
   is active),
2. train-fold Gramians by subtraction (``A_train = A_all − A_fold`` — the
   Gramian is additive, so k-fold CV needs no second data pass),
3. a single ``vmap`` over the flattened (param × fold) axis of the FISTA
   solver, with that cell axis SHARDED over the mesh — every core solves
   its slice of the grid simultaneously (the grid-parallel axis),
4. held-out metrics (rmse/mse/r2) computed from the fold Gramians directly.

Estimators without a sufficient-statistics path (LogisticRegression, custom)
take the generic fit-per-cell path, which still shares the session mesh.
"""

from __future__ import annotations

import copy
import functools
import itertools
import re
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame
from .base import Estimator, Model
from .evaluation import Evaluator, RegressionEvaluator
from .regression import LinearRegression, _extract_xy
from .solvers import fista_solve, resolve_solver
from ..parallel.mesh import serialize_collectives


def _snake(name: str) -> str:
    return re.sub(r"(?<!^)(?=[A-Z])", "_", name).lower()


class ParamGridBuilder:
    """``addGrid(param, values)`` builder; params are attribute names
    (snake_case or MLlib camelCase)."""

    def __init__(self):
        self._grids: dict[str, Sequence] = {}

    def add_grid(self, param: str, values: Sequence) -> "ParamGridBuilder":
        self._grids[_snake(param)] = list(values)
        return self

    addGrid = add_grid

    def base_on(self, params: dict) -> "ParamGridBuilder":
        for k, v in params.items():
            self._grids[_snake(k)] = [v]
        return self

    baseOn = base_on

    def build(self) -> list[dict]:
        names = list(self._grids)
        out = []
        for combo in itertools.product(*(self._grids[n] for n in names)):
            out.append(dict(zip(names, combo)))
        return out or [{}]


def _apply_params(estimator: Estimator, params: dict) -> Estimator:
    est = copy.copy(estimator)
    for k, v in params.items():
        if not hasattr(est, k):
            raise AttributeError(f"{type(est).__name__} has no param {k!r}")
        setattr(est, k, v)
    return est


def _best_index(metrics: np.ndarray, larger_better: bool) -> int:
    if np.all(np.isnan(metrics)):
        raise ValueError(
            "all cross-validation metrics are NaN — typically a fold with "
            "only one class (binary metrics) or an empty fold; use more data, "
            "fewer folds, or a different seed")
    return int(np.nanargmax(metrics) if larger_better else np.nanargmin(metrics))


def _fold_ids(n_slots: int, num_folds: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, num_folds, size=n_slots)


# --- fast path: linear regression on per-fold Gramians ----------------------

_FAST_METRICS = ("rmse", "mse", "r2")


def _holdout_metric_from_gram(A, coef, intercept, metric: str):
    """rmse/mse/r2 on a fold, from its Gramian and a raw-space model."""
    d = A.shape[0] - 2
    XtX = A[:d, :d]
    Xty = A[:d, d]
    sum_x = A[:d, d + 1]
    sum_y = A[d, d + 1]
    yy = A[d, d]
    n = A[d + 1, d + 1]
    sse = (yy - 2.0 * coef @ Xty - 2.0 * intercept * sum_y
           + 2.0 * intercept * (coef @ sum_x) + coef @ XtX @ coef
           + n * intercept * intercept)
    mse = sse / n
    if metric == "mse":
        return mse
    if metric == "rmse":
        return jnp.sqrt(jnp.maximum(mse, 0.0))
    ss_tot = yy - n * (sum_y / n) ** 2
    return 1.0 - sse / ss_tot


@functools.lru_cache(maxsize=8)
def _fold_ids_device(n_slots: int, num_folds: int, seed: int):
    """Fold assignment as a cached DEVICE array — the assignment is a pure
    function of (n, k, seed), so repeated ``fit`` calls must not pay the
    host→device transfer again. Bounded (unlike the program caches, this
    pins (n,)-sized device buffers in HBM, not compiled code)."""
    return jnp.asarray(_fold_ids(n_slots, num_folds, seed))


def _refit_solvers(estimator, param_maps: list[dict]) -> tuple:
    """Statically resolve the refit solver for every grid point — the grid
    only varies (reg_param, elastic_net_param), so MLlib's ``auto``
    resolution (normal vs iterative) is known at trace time per param."""
    out = []
    for p in param_maps:
        est = _apply_params(estimator, p)
        out.append(resolve_solver(est.solver, est.reg_param,
                                  est.elastic_net_param))
    return tuple(out)


def _cv_flat_layout(n_params: int, d: int, max_iter: int, refit: tuple):
    """(offset, history_len) per distinct solver in the packed CV output:
    ``[metrics(m) | best | per-solver (coef(d), intercept, iters,
    converged, history)]``."""
    distinct = tuple(dict.fromkeys(refit))
    off = n_params + 1
    layout = {}
    for s in distinct:
        hlen = 1 if s == "normal" else max_iter + 1
        layout[s] = (off, hlen)
        off += d + 3 + hlen
    return distinct, layout, off


@functools.lru_cache(maxsize=None)
def _cv_program_fn(mesh, num_folds: int, n_params: int, n_features: int,
                   max_iter: int, tol: float, fit_intercept: bool,
                   standardization: bool, metric: str, larger_better: bool,
                   refit: tuple):
    """The ENTIRE fast-path cross-validation as one jitted program — a
    single dispatch returning a single packed buffer.

    Inside: pack ``Z = [X, y, 1]·mask``, pad rows to the shard count, build
    ALL per-fold augmented Gramians in one data pass (for 0/1 fold weight
    ``w``, ``(Z·w)ᵀZ`` is the fold's masked Gramian; invalid rows are
    already zero in Z), train Gramians by subtraction (the Gramian is
    additive — k-fold CV needs no second data pass), solve every
    (param × fold) FISTA cell vmapped with the cell axis SHARDED over the
    mesh (the grid-parallel axis, BASELINE.json config e), fold-mean the
    held-out metrics, pick the winner, and REFIT the winning params on the
    all-data Gramian with each statically-resolved solver the grid can
    select (``refit``, per-param; ``auto`` ⇒ normal vs FISTA known at
    trace time) — GridSearchCV(refit=True) semantics, end to end on
    device.

    Everything rides out in ONE flat vector (see :func:`_cv_flat_layout`):
    the staged implementation (~a dozen dispatches + several blocking
    device→host reads per ``fit``) spent its wall-clock on dispatch and
    read latency, not on solving. One dispatch + one read is the floor
    for a fit whose results the caller materializes. Cached per configuration — constructing the jit inline
    would re-lower the grid program on every ``fit`` call."""
    from .owlqn import owlqn_solve
    from .solvers import normal_solve

    solver_fns = {
        "normal": lambda A, r, a: normal_solve(
            A, r, a, fit_intercept=fit_intercept,
            standardization=standardization),
        "fista": lambda A, r, a: fista_solve(
            A, r, a, max_iter=max_iter, tol=tol, fit_intercept=fit_intercept,
            standardization=standardization),
        "owlqn": lambda A, r, a: owlqn_solve(
            A, r, a, max_iter=max_iter, tol=tol, fit_intercept=fit_intercept,
            standardization=standardization),
    }
    distinct, _, _ = _cv_flat_layout(n_params, n_features, max_iter, refit)
    use_mesh = mesh is not None and mesh.devices.size > 1
    ndev = mesh.devices.size if use_mesh else 1
    k = num_folds
    m = n_params
    n_cells = m * k
    cell_pad = (-n_cells) % ndev
    # Wrap-around duplicates (works even when pad > n_cells, e.g. a 3-cell
    # grid on 8 devices); duplicates are trimmed by the [:n_cells] slice.
    cell_idx = np.arange(n_cells + cell_pad) % n_cells

    def fold_grams(Zs, fs):
        def one(f):
            w = (fs == f).astype(Zs.dtype)
            return (Zs * w[:, None]).T @ Zs
        return jax.vmap(one)(jnp.arange(k))

    def cell(A_tr, A_te, reg, alpha):
        # record_history=False: the trace is unused here
        r = fista_solve(A_tr, reg, alpha, max_iter=max_iter, tol=tol,
                        fit_intercept=fit_intercept,
                        standardization=standardization,
                        record_history=False)
        return _holdout_metric_from_gram(A_te, r.coefficients, r.intercept,
                                         metric)

    if use_mesh:
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS, shard_map

        grams_fn = shard_map(
            lambda Zs, fs: jax.lax.psum(fold_grams(Zs, fs), DATA_AXIS),
            mesh=mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS)), out_specs=P())
        # check_vma off: the FISTA scan's replicated init carry (w=0) meets
        # a device-varying Gramian inside the manual region, which the
        # varying-manual-axes checker rejects even though the computation is
        # per-device-pure (no collectives inside the scan).
        cells_fn = shard_map(
            jax.vmap(cell), mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS), check_vma=False)
    else:
        grams_fn = fold_grams
        cells_fn = jax.vmap(cell)

    def program(X, y, mask, fold, regs, alphas):
        Z = jnp.concatenate(
            [X, y[:, None], jnp.ones_like(y)[:, None]], axis=1)
        Z = Z * mask.astype(Z.dtype)[:, None]
        rem = (-Z.shape[0]) % ndev
        if rem:
            # Padding rows: zero in Z (no contribution) and fold −1 (no fold).
            Z = jnp.concatenate([Z, jnp.zeros((rem, Z.shape[1]), Z.dtype)])
            fold = jnp.concatenate([fold, jnp.full((rem,), -1, fold.dtype)])
        A_folds = grams_fn(Z, fold)                      # (k, d+2, d+2)
        A_all = jnp.sum(A_folds, axis=0)
        A_train = A_all[None] - A_folds

        # Flatten (param × fold); every cell solves simultaneously.
        A_rep = jnp.tile(A_train, (m, 1, 1))[cell_idx]
        A_hold = jnp.tile(A_folds, (m, 1, 1))[cell_idx]
        reg_rep = jnp.repeat(regs, k)[cell_idx]
        alpha_rep = jnp.repeat(alphas, k)[cell_idx]
        metrics_cells = cells_fn(A_rep, A_hold, reg_rep, alpha_rep)[:n_cells]
        metrics = metrics_cells.reshape(m, k).mean(axis=1)
        # NaN-safe winner (matches _best_index): a fold can go degenerate
        # for one param without poisoning the whole grid.
        guarded = jnp.where(jnp.isnan(metrics),
                            -jnp.inf if larger_better else jnp.inf, metrics)
        best = jnp.argmax(guarded) if larger_better else jnp.argmin(guarded)

        dt = metrics.dtype
        parts = [metrics, best.astype(dt).reshape(1)]
        for s in distinct:
            r = solver_fns[s](A_all, regs[best], alphas[best])
            parts += [r.coefficients.astype(dt),
                      r.intercept.astype(dt).reshape(1),
                      r.iterations.astype(dt).reshape(1),
                      r.converged.astype(dt).reshape(1),
                      r.objective_history.astype(dt)]
        return jnp.concatenate(parts)

    return serialize_collectives(jax.jit(program), mesh)


def cv_device_program(frame: Frame, estimator: LinearRegression,
                      param_maps: list[dict], metric: str, num_folds: int,
                      seed: int, mesh, larger_better: bool):
    """Build the fused CV program and its device arguments WITHOUT running
    it. Used by ``_linear_cv_fast`` and by the graft entry, which runs the
    device-complete program itself."""
    # _extract_xy already returns float-dtype device arrays with X 2-D
    X, y, mask = _extract_xy(frame, estimator.features_col, estimator.label_col)
    fold = _fold_ids_device(X.shape[0], num_folds, seed)

    regs = jnp.asarray([p.get("reg_param", estimator.reg_param)
                        for p in param_maps], X.dtype)
    alphas = jnp.asarray([p.get("elastic_net_param", estimator.elastic_net_param)
                          for p in param_maps], X.dtype)

    refit = _refit_solvers(estimator, param_maps)
    program = _cv_program_fn(
        mesh if (mesh is not None and mesh.devices.size > 1) else None,
        num_folds, len(param_maps), X.shape[1], estimator.max_iter,
        estimator.tol, estimator.fit_intercept, estimator.standardization,
        metric, larger_better, refit)
    args = (X, y, jnp.asarray(mask), fold, regs, alphas)
    return program, args, refit, X.shape[1]


def _linear_cv_fast(frame: Frame, estimator: LinearRegression,
                    param_maps: list[dict], metric: str, num_folds: int,
                    seed: int, mesh, larger_better: bool):
    """Run the fused CV program: one dispatch, one host read. Returns
    (metrics[num_params], best_index, best FitResult)."""
    from .solvers import FitResult

    program, args, refit, d = cv_device_program(
        frame, estimator, param_maps, metric, num_folds, seed, mesh,
        larger_better)
    flat = np.asarray(program(*args))                    # the ONE host read

    m = len(param_maps)
    metrics = flat[:m]
    if np.all(np.isnan(metrics)):
        _best_index(metrics, larger_better)              # raise the shared error
    best = int(flat[m])
    _, layout, _ = _cv_flat_layout(m, d, estimator.max_iter, refit)
    off, hlen = layout[refit[best]]
    result = FitResult(
        coefficients=flat[off:off + d],
        intercept=flat[off + d],
        iterations=np.int32(flat[off + d + 1]),
        objective_history=flat[off + d + 3:off + d + 3 + hlen],
        converged=bool(flat[off + d + 2]))
    return metrics, best, result


# --- public API --------------------------------------------------------------

class CrossValidatorModel(Model):
    def __init__(self, best_model: Model, avg_metrics: np.ndarray,
                 best_index: int, sub_models=None):
        self.best_model = best_model
        self.avg_metrics = np.asarray(avg_metrics)
        self.best_index = int(best_index)
        self.sub_models = sub_models

    bestModel = property(lambda self: self.best_model)
    avgMetrics = property(lambda self: self.avg_metrics)

    def transform(self, frame: Frame) -> Frame:
        return self.best_model.transform(frame)


class CrossValidator(Estimator):
    def __init__(self, estimator: Optional[Estimator] = None,
                 estimator_param_maps: Optional[list[dict]] = None,
                 evaluator: Optional[Evaluator] = None,
                 num_folds: int = 3, seed: int = 0,
                 collect_sub_models: bool = False,
                 parallelism: int = 1):
        self.estimator = estimator
        self.estimator_param_maps = estimator_param_maps or [{}]
        self.evaluator = evaluator or RegressionEvaluator()
        self.num_folds = num_folds
        self.seed = seed
        self.collect_sub_models = collect_sub_models
        # MLlib's thread-pool width; meaningless here because the grid is
        # vmapped (all cells run at once). Accepted for API parity.
        self.parallelism = parallelism

    def set_estimator(self, e): self.estimator = e; return self
    def set_estimator_param_maps(self, m): self.estimator_param_maps = m; return self
    def set_evaluator(self, e): self.evaluator = e; return self
    def set_num_folds(self, k): self.num_folds = int(k); return self
    def set_seed(self, s): self.seed = int(s); return self

    setEstimator = set_estimator
    setEstimatorParamMaps = set_estimator_param_maps
    setEvaluator = set_evaluator
    setNumFolds = set_num_folds
    setSeed = set_seed

    def _use_fast_path(self) -> bool:
        if not isinstance(self.estimator, LinearRegression):
            return False
        if getattr(self.estimator, "loss", "squaredError") != "squaredError":
            return False  # huber has no Gramian statistic: generic path
        if getattr(self.estimator, "weight_col", None):
            return False  # weighted fits take the generic fit-per-cell path
        if self.collect_sub_models:
            return False  # per-fold models only exist on the generic path
        if not isinstance(self.evaluator, RegressionEvaluator):
            return False
        if self.evaluator.metric_name not in _FAST_METRICS:
            return False
        # fast path solves every cell with FISTA; exact for any elastic net
        try:
            for p in self.estimator_param_maps:
                est = _apply_params(self.estimator, p)
                resolve_solver(est.solver, est.reg_param, est.elastic_net_param)
        except (ValueError, AttributeError):
            return False
        # grid must only vary solver-vmappable params
        varied = {k for p in self.estimator_param_maps for k in p}
        return varied <= {"reg_param", "elastic_net_param"}

    def fit(self, frame: Frame, mesh=None) -> CrossValidatorModel:
        if self.estimator is None:
            raise ValueError("CrossValidator: estimator not set")
        if mesh is None:
            from ..session import TpuSession

            active = TpuSession.active()
            mesh = active.mesh if active is not None else None

        larger_better = self.evaluator.is_larger_better()
        if self._use_fast_path():
            from .regression import LinearRegressionModel

            metrics, best, result = _linear_cv_fast(
                frame, self.estimator, self.estimator_param_maps,
                self.evaluator.metric_name, self.num_folds, self.seed, mesh,
                larger_better)
            best_est = _apply_params(self.estimator,
                                     self.estimator_param_maps[best])
            # best model was refit inside the fused program (all-data
            # Gramian) — no extra data pass, no extra dispatch
            best_model = LinearRegressionModel(
                coefficients=np.asarray(result.coefficients),
                intercept=float(result.intercept),
                params=best_est._params_dict())
            best_model._summary_source = (frame, result)
            return CrossValidatorModel(best_model, metrics, best)

        # generic path: fit/evaluate each (param, fold) cell
        fold = _fold_ids(frame.num_slots, self.num_folds, self.seed)
        fold_arr = jnp.asarray(fold)
        metrics = np.zeros(len(self.estimator_param_maps))
        sub_models = [] if self.collect_sub_models else None
        for pi, params in enumerate(self.estimator_param_maps):
            est = _apply_params(self.estimator, params)
            scores = []
            for f in range(self.num_folds):
                train = frame.filter(fold_arr != f)
                test = frame.filter(fold_arr == f)
                model = est.fit(train) if mesh is None else est.fit(train, mesh=mesh)
                scores.append(self.evaluator.evaluate(model.transform(test)))
                if sub_models is not None:
                    sub_models.append(model)
            metrics[pi] = float(np.mean(scores))
        best = _best_index(metrics, larger_better)
        best_est = _apply_params(self.estimator, self.estimator_param_maps[best])
        best_model = (best_est.fit(frame) if mesh is None
                      else best_est.fit(frame, mesh=mesh))
        return CrossValidatorModel(best_model, metrics, best, sub_models)


class TrainValidationSplitModel(CrossValidatorModel):
    @property
    def validation_metrics(self):
        return self.avg_metrics

    validationMetrics = validation_metrics


class TrainValidationSplit(CrossValidator):
    """Single random train/validation split (MLlib TrainValidationSplit);
    implemented as 1-fold holdout with ``train_ratio``."""

    def __init__(self, estimator=None, estimator_param_maps=None,
                 evaluator=None, train_ratio: float = 0.75, seed: int = 0):
        super().__init__(estimator, estimator_param_maps, evaluator,
                         num_folds=2, seed=seed)
        self.train_ratio = train_ratio

    def set_train_ratio(self, r): self.train_ratio = float(r); return self

    setTrainRatio = set_train_ratio

    def fit(self, frame: Frame, mesh=None) -> TrainValidationSplitModel:
        rng = np.random.default_rng(self.seed)
        is_val = jnp.asarray(rng.random(frame.num_slots) >= self.train_ratio)
        train = frame.filter(jnp.logical_not(is_val))
        val = frame.filter(is_val)
        larger_better = self.evaluator.is_larger_better()
        metrics = np.zeros(len(self.estimator_param_maps))
        for pi, params in enumerate(self.estimator_param_maps):
            est = _apply_params(self.estimator, params)
            model = est.fit(train) if mesh is None else est.fit(train, mesh=mesh)
            metrics[pi] = self.evaluator.evaluate(model.transform(val))
        best = _best_index(metrics, larger_better)
        best_est = _apply_params(self.estimator, self.estimator_param_maps[best])
        best_model = (best_est.fit(frame) if mesh is None
                      else best_est.fit(frame, mesh=mesh))
        return TrainValidationSplitModel(best_model, metrics, best)
