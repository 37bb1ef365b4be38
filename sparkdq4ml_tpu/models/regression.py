"""LinearRegression estimator/model/summary — the MLlib surface the
reference app exercises (`DataQuality4MachineLearningApp.java:120-154`):
``setMaxIter/setRegParam/setElasticNetParam``, ``fit``, ``transform``,
``summary`` (totalIterations, objectiveHistory, residuals, RMSE, r²),
``intercept``/``getRegParam``/``getTol``, and host-side ``predict``.

The fit path is the TPU-native design from :mod:`~sparkdq4ml_tpu.models.solvers`:
one masked-Gramian data pass (sharded over the session mesh with a ``psum``
when it has >1 device) + an on-device solver loop on the replicated
statistics. MLlib parameter defaults are preserved: ``maxIter=100``,
``regParam=0``, ``elasticNetParam=0``, ``tol=1e-6``, ``fitIntercept=True``,
``standardization=True``, ``solver="auto"``.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..config import float_dtype
from ..frame.frame import Frame
from ..ops.expressions import col
from ..utils import observability as _obs
from .base import (Estimator, Model, label_stats, persistable, read_json,
                   read_label_stats, write_json)
from .solvers import FitResult, resolve_solver


def _extract_xy(frame: Frame, features_col: str, label_col: str):
    X = jnp.asarray(frame._column_values(features_col), float_dtype())
    if X.ndim == 1:
        X = X[:, None]
    y = jnp.asarray(frame._column_values(label_col), float_dtype())
    return X, y, frame.mask


@persistable
class LinearRegression(Estimator):
    """Elastic-net linear regression, MLlib numeric convention."""

    # class-level default: estimators persisted before this param existed
    # load via setattr (base.load_stage) and must still resolve it
    weight_col = None

    _persist_attrs = ("max_iter", "reg_param", "elastic_net_param", "tol",
                      "fit_intercept", "standardization", "solver",
                      "features_col", "label_col", "prediction_col",
                      "weight_col", "aggregation_depth", "loss", "epsilon")

    # class-level defaults: stages persisted before these params existed
    loss = "squaredError"
    epsilon = 1.35

    def __init__(self, max_iter: int = 100, reg_param: float = 0.0,
                 elastic_net_param: float = 0.0, tol: float = 1e-6,
                 fit_intercept: bool = True, standardization: bool = True,
                 solver: str = "auto", features_col: str = "features",
                 label_col: str = "label", prediction_col: str = "prediction",
                 weight_col: Optional[str] = None,
                 aggregation_depth: int = 2, loss: str = "squaredError",
                 epsilon: float = 1.35):
        self.max_iter = max_iter
        self.reg_param = reg_param
        self.elastic_net_param = elastic_net_param
        self.tol = tol
        self.fit_intercept = fit_intercept
        self.standardization = standardization
        self.solver = solver
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col
        self.weight_col = weight_col
        # treeAggregate tree depth in MLlib; meaningless under psum (the ICI
        # all-reduce is already log-depth in hardware). Accepted for API parity.
        self.aggregation_depth = aggregation_depth
        if loss not in ("squaredError", "huber"):
            raise ValueError(f"unknown loss {loss!r} "
                             "(squaredError or huber)")
        self.loss = loss
        self.epsilon = float(epsilon)

    # -- MLlib-style fluent setters/getters --------------------------------
    def set_max_iter(self, v: int):
        self.max_iter = int(v); return self

    def set_reg_param(self, v: float):
        self.reg_param = float(v); return self

    def set_elastic_net_param(self, v: float):
        self.elastic_net_param = float(v); return self

    def set_tol(self, v: float):
        self.tol = float(v); return self

    def set_fit_intercept(self, v: bool):
        self.fit_intercept = bool(v); return self

    def set_standardization(self, v: bool):
        self.standardization = bool(v); return self

    def set_solver(self, v: str):
        self.solver = v; return self

    def set_features_col(self, v: str):
        self.features_col = v; return self

    def set_label_col(self, v: str):
        self.label_col = v; return self

    def set_prediction_col(self, v: str):
        self.prediction_col = v; return self

    def set_weight_col(self, v):
        self.weight_col = v; return self

    def set_aggregation_depth(self, v: int):
        self.aggregation_depth = int(v); return self

    setMaxIter = set_max_iter
    setRegParam = set_reg_param
    setElasticNetParam = set_elastic_net_param
    setTol = set_tol
    setFitIntercept = set_fit_intercept
    setStandardization = set_standardization
    setSolver = set_solver
    setFeaturesCol = set_features_col
    setLabelCol = set_label_col
    setPredictionCol = set_prediction_col
    setWeightCol = set_weight_col
    setAggregationDepth = set_aggregation_depth

    def get_max_iter(self): return self.max_iter
    def get_reg_param(self): return self.reg_param
    def get_elastic_net_param(self): return self.elastic_net_param
    def get_tol(self): return self.tol
    def get_fit_intercept(self): return self.fit_intercept
    def get_standardization(self): return self.standardization
    def get_solver(self): return self.solver

    getMaxIter = get_max_iter
    getRegParam = get_reg_param
    getElasticNetParam = get_elastic_net_param
    getTol = get_tol
    getFitIntercept = get_fit_intercept
    getStandardization = get_standardization
    getSolver = get_solver

    def _params_dict(self) -> dict:
        return {k: getattr(self, k) for k in (
            "max_iter", "reg_param", "elastic_net_param", "tol",
            "fit_intercept", "standardization", "solver", "features_col",
            "label_col", "prediction_col", "weight_col",
            "aggregation_depth", "loss", "epsilon")}

    # -- fit ----------------------------------------------------------------
    def fit(self, frame: Frame, mesh=None) -> "LinearRegressionModel":
        """Fit on the frame's valid rows. ``mesh`` defaults to the active
        session's device mesh (row-sharded psum path when >1 device)."""
        # Imported here, not at module top: parallel.distributed imports
        # models.solvers, so a top-level import would make package init
        # order-sensitive (importing parallel first used to crash).
        from ..parallel.distributed import fused_linear_fit_packed

        # Observability: ONE root span per fit, opened where fit begins,
        # with the cold-compile vs steady split (trace-cache probe on the
        # lru-cached jit factory) and any retry/fallback the resilience
        # layer took; fit.prepare and fit.solve are its children, so its
        # self time is what neither explains.
        with _obs.fit_span("fit.linear_regression", fused_linear_fit_packed,
                           max_iter=self.max_iter) as root:
            return self._fit(frame, mesh, root)

    def _fit(self, frame: Frame, mesh, root) -> "LinearRegressionModel":
        from ..parallel.distributed import (DesignColumns,
                                            fused_linear_fit_packed,
                                            pack_design, place_packed,
                                            row_scale, unpack_fit_result)
        from ..utils import faults as _faults
        from ..utils import recovery as _recovery
        from ..utils.profiling import counters
        from .solvers import downgrade_solver

        if mesh is None:
            from ..session import TpuSession

            active = TpuSession.active()
            mesh = active.mesh if active is not None else None
        if mesh is not None and mesh.devices.size <= 1:
            mesh = None  # unify the single-device cache key
        with _obs.span("fit.prepare", cat="fit") as prep:
            with _obs.span("fit.extract", cat="fit"):
                X, y, mask = _extract_xy(frame, self.features_col,
                                         self.label_col)
            d = X.shape[1]
            prep.set(rows=int(X.shape[0]), features=int(d))
            w = None
            if self.weight_col is not None:
                # Instance weights (MLlib weightCol): scaling packed rows
                # by sqrt(w) makes the Gramian ZᵀZ = Σ w·zzᵀ — every
                # moment the solver unpacks (n = Σw, weighted mean/std,
                # Gram, correlation) becomes its weighted form, so an
                # integer weight k is EXACTLY a row repeated k times (the
                # regression test for this path). Summary metrics remain
                # unweighted row statistics.
                # Masked rows' weight VALUES never participate: the
                # validation looks at valid rows only, and sqrt() sees 0
                # there (a NaN/negative payload in a filtered slot must
                # not poison Z). Validating is one small reduction on the
                # device and a read of its few scalars (base.label_stats).
                w = jnp.asarray(frame._column_values(self.weight_col),
                                float_dtype())
                with _obs.span("fit.validate", cat="fit") as val:
                    stats = read_label_stats(label_stats(None, mask, w))
                    val.set(host_read_bytes=stats.nbytes)
                    # NaN fails >= too: a NaN weight on a valid row must
                    # raise, not silently poison the Gramian
                    if stats.weight_bad:
                        raise ValueError("weights must be nonnegative")
            if self.loss == "huber":
                return self._fit_huber(frame, X, y, row_scale(mask, w))
            # fit.pack: what stands between the columns and the compiled
            # fit. On one device nothing does — the program takes the
            # columns and packs them itself (lowering="in-program"); a
            # mesh is handed a packed Z, placed row-sharded by the rung
            # that runs on it (lowering="eager": one pack program).
            with _obs.span("fit.pack", cat="fit",
                           lowering="eager" if mesh is not None
                           else "in-program"):
                columns = DesignColumns(X, y, mask, w)
                Z = None if mesh is None else \
                    pack_design(X, y, row_scale(mask, w))
                hyper = jnp.asarray([self.reg_param,
                                     self.elastic_net_param], float_dtype())
        solver_name = resolve_solver(self.solver, self.reg_param,
                                     self.elastic_net_param)

        def make_call(m, sname):
            # Everything stays inside the closure: fallback rungs must
            # cost nothing (no trace, no placement) unless they run.
            def call():
                _faults.inject("fit_packed")
                fit_fn = fused_linear_fit_packed(
                    m, sname, self.max_iter, self.tol, self.fit_intercept,
                    self.standardization)
                if m is None:
                    counters.increment("fit.pack_in_program")
                    design = columns
                else:
                    design = place_packed(Z, m)
                return _faults.corrupt(
                    "solver", unpack_fit_result(fit_fn(design, hyper), d))
            return call

        # Fallback ladder: sharded fit → single-device fit → closed-form
        # solver (when the penalty permits). Identical statistics on every
        # rung; only throughput/solver trajectory degrade. Rungs after the
        # first run only when the one before exhausted its retry policy.
        fallbacks = []
        if mesh is not None:
            fallbacks.append(("single_device", make_call(None, solver_name)))
        downgraded = downgrade_solver(solver_name, self.reg_param,
                                      self.elastic_net_param)
        if downgraded is not None:
            fallbacks.append((f"solver_{downgraded}",
                              make_call(None, downgraded)))
        root.set(rows=int(X.shape[0]), features=int(d), solver=solver_name,
                 shards=(mesh.devices.size if mesh is not None else 1))
        # fit.solve: dispatch of the compiled fit to its result on the
        # host (unpack_fit_result reads the one packed buffer — a read the
        # code makes anyway, so the solver trajectory below adds no sync)
        with _obs.span("fit.solve", cat="solver", solver=solver_name) as sv:
            result = _recovery.resilient_call(
                make_call(mesh, solver_name), site="fit_packed",
                policy=_recovery.active_policy("fit_packed"),
                validate=_recovery.result_validator(),
                fallbacks=fallbacks, breaker=_recovery.DEVICE_BREAKER)
            iters = int(result.iterations)
            sv.set(iterations=iters, converged=bool(result.converged))
        counters.increment("solver.fits")
        counters.increment("solver.iterations", iters)
        if root is not _obs._NOOP:
            from ..utils import meminfo as _meminfo

            hist = np.asarray(result.objective_history, np.float64)
            # input_bytes: static-shape estimate of the columns the fit
            # dispatched (the fit-node device-memory figure
            # EXPLAIN/memory_report cross-reference) — metadata only,
            # never a device read.
            root.set(iterations=iters, converged=bool(result.converged),
                     objective_final=float(
                         hist[min(iters, hist.shape[0] - 1)]),
                     input_bytes=_meminfo.estimated_bytes(columns))
        model = LinearRegressionModel(
            coefficients=np.asarray(result.coefficients),
            intercept=float(result.intercept),
            params=self._params_dict())
        # Summary is constructed lazily on first access: it needs a full
        # batch transform + host gather, which sweep-style callers that only
        # read coefficients should never pay for.
        model._summary_source = (frame, result)
        return model


    def _fit_huber(self, frame, X, y, mask) -> "LinearRegressionModel":
        """MLlib ``loss="huber"``: robust fit of Huber's concomitant-scale
        objective (see ``solvers.huber_fit``). L1 is unsupported exactly
        as in MLlib; the scale estimate surfaces as ``model.scale``.
        The robust loss has no Gramian sufficient statistic, so this
        path revisits rows per iteration inside one jitted while_loop
        (a mesh would psum the per-iteration gradient; the single-program
        form covers the reference's row counts with headroom)."""
        from .solvers import huber_fit

        if self.elastic_net_param not in (0, 0.0):
            raise ValueError("huber loss supports only L2 regularization "
                             "(elasticNetParam must be 0), as in MLlib")
        b_, c_, sigma, iters, obj = huber_fit(
            X, y, mask, epsilon=self.epsilon, reg_param=self.reg_param,
            fit_intercept=self.fit_intercept, max_iter=self.max_iter,
            tol=self.tol, standardization=self.standardization)
        model = LinearRegressionModel(
            coefficients=np.asarray(b_), intercept=float(c_),
            params=self._params_dict(), scale=float(sigma))
        fd = jnp.asarray(X).dtype
        result = FitResult(
            coefficients=jnp.asarray(b_), intercept=jnp.asarray(c_, fd),
            iterations=jnp.asarray(int(iters), jnp.int32),
            objective_history=jnp.asarray([float(obj)], fd),
            converged=jnp.asarray(int(iters) < self.max_iter))
        model._summary_source = (frame, result)
        return model

    def fit_from_gram(self, A, frame: Frame) -> "LinearRegressionModel":
        """Fit from a precomputed augmented Gramian — zero data passes.
        Used by CrossValidator's fast path to refit the best model from the
        already-reduced statistics."""
        from .solvers import solve

        result = solve(A, self.reg_param, self.elastic_net_param,
                       max_iter=self.max_iter, tol=self.tol,
                       fit_intercept=self.fit_intercept,
                       standardization=self.standardization,
                       solver=self.solver)
        model = LinearRegressionModel(
            coefficients=np.asarray(result.coefficients),
            intercept=float(result.intercept),
            params=self._params_dict())
        model._summary_source = (frame, result)
        return model


@persistable
class LinearRegressionModel(Model):
    def __init__(self, coefficients: np.ndarray, intercept: float,
                 params: Optional[dict] = None, scale: float = 1.0):
        self.coefficients = np.asarray(coefficients)
        self.intercept = float(intercept)
        # MLlib: 1.0 for squared-error fits; the fitted sigma for huber
        self.scale = float(scale)
        self._params = dict(params or {})
        self._training_summary: Optional[LinearRegressionTrainingSummary] = None
        self._summary_source = None  # (frame, FitResult) until first access

    # Parameter read-back used by the app (`App.java:141-146`)
    def get_reg_param(self): return self._params.get("reg_param", 0.0)
    def get_tol(self): return self._params.get("tol", 1e-6)
    def get_max_iter(self): return self._params.get("max_iter", 100)
    def get_elastic_net_param(self): return self._params.get("elastic_net_param", 0.0)

    getRegParam = get_reg_param
    getTol = get_tol
    getMaxIter = get_max_iter
    getElasticNetParam = get_elastic_net_param

    @property
    def features_col(self):
        return self._params.get("features_col", "features")

    @property
    def prediction_col(self):
        return self._params.get("prediction_col", "prediction")

    @property
    def label_col(self):
        return self._params.get("label_col", "label")

    @property
    def num_features(self) -> int:
        return int(self.coefficients.shape[0])

    # -- inference ----------------------------------------------------------
    def transform(self, frame: Frame) -> Frame:
        """Append the prediction column (batch inference, one fused matvec —
        `App.java:129`)."""
        with _obs.span("model.transform", cat="model",
                       model="linear_regression", rows=frame.num_slots):
            X = jnp.asarray(frame._column_values(self.features_col),
                            float_dtype())
            if X.ndim == 1:
                X = X[:, None]
            pred = X @ jnp.asarray(self.coefficients, X.dtype) \
                + self.intercept
            return frame.with_column(self.prediction_col, pred)

    def predict(self, features) -> float:
        """Host-side single-point inference (`App.java:149-151`) — a dot+add
        with no device round-trip, like MLlib's driver-local predict."""
        with _obs.span("model.predict", cat="model",
                       model="linear_regression", rows=1):
            v = np.asarray(features, dtype=np.float64).reshape(-1)
            return float(v @ self.coefficients.astype(np.float64)
                         + self.intercept)

    # -- summaries -----------------------------------------------------------
    @property
    def summary(self) -> "LinearRegressionTrainingSummary":
        if self._training_summary is None:
            if self._summary_source is None:
                raise RuntimeError("model was not fit with summary (loaded model?)")
            frame, result = self._summary_source
            self._training_summary = LinearRegressionTrainingSummary(
                self, frame, result)
        return self._training_summary

    @property
    def has_summary(self) -> bool:
        return self._training_summary is not None or self._summary_source is not None

    hasSummary = has_summary

    def evaluate(self, frame: Frame) -> "LinearRegressionSummary":
        return LinearRegressionSummary(self, frame)

    # -- persistence (capability upgrade over the reference; SURVEY.md §5
    #    "Checkpoint / resume") ---------------------------------------------
    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        write_json(os.path.join(path, "metadata.json"), {
            "class": "LinearRegressionModel",
            "intercept": self.intercept,
            "scale": self.scale,
            "params": self._params,
        })
        np.save(os.path.join(path, "coefficients.npy"), self.coefficients)

    @classmethod
    def load(cls, path: str) -> "LinearRegressionModel":
        meta = read_json(os.path.join(path, "metadata.json"))
        if meta.get("class") != "LinearRegressionModel":
            raise ValueError(f"not a LinearRegressionModel checkpoint: {path}")
        coef = np.load(os.path.join(path, "coefficients.npy"))
        return cls(coef, meta["intercept"], meta.get("params"),
                   scale=meta.get("scale", 1.0))

    # Pipeline-persistence hooks (base.save_stage/load_stage dispatch here).
    def _save_to_dir(self, path: str) -> None:
        self.save(path)

    @classmethod
    def _load_from_dir(cls, path: str, meta: dict):
        return cls.load(path)


class LinearRegressionSummary:
    """Evaluation metrics over a frame's valid rows (mask-weighted — the
    masked-filter semantics of SURVEY.md §7 never leak into the stats)."""

    def __init__(self, model: LinearRegressionModel, frame: Frame):
        self._model = model
        self._frame = frame
        pred_frame = model.transform(frame)
        d = pred_frame.to_pydict()
        self._label = d[model.label_col].astype(np.float64)
        self._pred = d[model.prediction_col].astype(np.float64)
        self._predictions_frame = pred_frame

    @property
    def predictions(self) -> Frame:
        return self._predictions_frame

    @property
    def num_instances(self) -> int:
        return int(self._label.shape[0])

    numInstances = num_instances

    @property
    def residuals(self) -> Frame:
        return Frame({"residuals": self._label - self._pred})

    @property
    def mean_squared_error(self) -> float:
        return float(np.mean((self._label - self._pred) ** 2))

    meanSquaredError = mean_squared_error

    @property
    def root_mean_squared_error(self) -> float:
        return float(np.sqrt(self.mean_squared_error))

    rootMeanSquaredError = root_mean_squared_error

    @property
    def mean_absolute_error(self) -> float:
        return float(np.mean(np.abs(self._label - self._pred)))

    meanAbsoluteError = mean_absolute_error

    @property
    def explained_variance(self) -> float:
        return float(np.var(self._pred))

    explainedVariance = explained_variance

    @property
    def r2(self) -> float:
        ss_res = float(np.sum((self._label - self._pred) ** 2))
        ss_tot = float(np.sum((self._label - np.mean(self._label)) ** 2))
        if ss_tot == 0.0:  # constant label: undefined, like MLlib's 0/0 → NaN
            return float("nan")
        return 1.0 - ss_res / ss_tot

    @property
    def r2adj(self) -> float:
        n = self.num_instances
        d = self._model.num_features
        return 1.0 - (1.0 - self.r2) * (n - 1) / (n - d - 1)

    @property
    def degrees_of_freedom(self) -> int:
        extra = 1 if self._model._params.get("fit_intercept", True) else 0
        return self.num_instances - self._model.num_features - extra

    degreesOfFreedom = degrees_of_freedom

    # -- inference statistics (MLlib: solver="normal" surface) -------------
    def _inference(self):
        """(std_errors, t_values, p_values), intercept LAST (MLlib's
        layout). Classical OLS covariance ``σ̂²(XᵀX)⁻¹`` — exact only for
        unpenalized, unweighted TRAINING fits, so anything else raises
        like MLlib's UnsupportedOperationException (evaluate() summaries
        have no valid Wald statistics; weighted fits should use the GLM
        gaussian path, which computes the weighted versions properly)."""
        cached = getattr(self, "_inference_cache", None)
        if cached is not None:
            return cached
        params = self._model._params or {}
        if float(params.get("reg_param", 0.0)) > 0.0:
            raise ValueError(
                "standard errors / t-values / p-values are available only "
                "for unpenalized fits (MLlib: solver='normal' without "
                "regularization); this model has regParam > 0")
        if params.get("weight_col") is not None:
            raise ValueError(
                "standard errors for weighted fits are not computed here; "
                "use GeneralizedLinearRegression(family='gaussian', "
                "weight_col=...) whose summary implements the weighted "
                "Wald statistics")
        if not isinstance(self, LinearRegressionTrainingSummary):
            raise ValueError(
                "inference statistics exist only on the TRAINING summary "
                "(MLlib: evaluate() summaries throw); held-out residuals "
                "do not form Wald statistics for the training estimate")
        from scipy import stats as _sstats

        Xd, _, mask = _extract_xy(self._frame, self._model.features_col,
                                  self._model.label_col)
        X = np.asarray(Xd, np.float64)[np.asarray(mask)]
        fit_intercept = bool(params.get("fit_intercept", True))
        A = np.concatenate([X, np.ones((len(X), 1))], axis=1) \
            if fit_intercept else X
        dof = self.degrees_of_freedom
        if dof <= 0:
            raise ValueError("non-positive degrees of freedom")
        G = A.T @ A                  # p×p Gram: rank check + inverse share it
        if np.linalg.matrix_rank(G) < A.shape[1]:
            # MLlib's normal solver fails on singular normal equations; a
            # pinv here would return finite-but-meaningless errors for an
            # unidentifiable (collinear) design
            raise ValueError(
                "design matrix is rank-deficient (collinear features); "
                "standard errors are not identifiable")
        resid = self._label - self._pred
        sigma2 = float(resid @ resid) / dof
        cov = sigma2 * np.linalg.pinv(G)
        se = np.sqrt(np.diag(cov))
        coef = np.asarray(self._model.coefficients, np.float64)
        beta = np.concatenate([coef, [self._model.intercept]]) \
            if fit_intercept else coef
        with np.errstate(divide="ignore", invalid="ignore"):
            t = beta / se
        p = 2.0 * _sstats.t.sf(np.abs(t), dof)
        self._inference_cache = (se, t, p)
        return self._inference_cache

    @property
    def coefficient_standard_errors(self) -> np.ndarray:
        return self._inference()[0]

    coefficientStandardErrors = coefficient_standard_errors

    @property
    def t_values(self) -> np.ndarray:
        return self._inference()[1]

    tValues = t_values

    @property
    def p_values(self) -> np.ndarray:
        return self._inference()[2]

    pValues = p_values


class LinearRegressionTrainingSummary(LinearRegressionSummary):
    """Training summary: evaluation metrics + solver trajectory
    (`App.java:132-139`)."""

    def __init__(self, model: LinearRegressionModel, frame: Frame,
                 result: FitResult):
        super().__init__(model, frame)
        self._iterations = int(result.iterations)
        hist = np.asarray(result.objective_history, dtype=np.float64)
        # history[0] is the initial objective; keep entries up to convergence.
        self._objective_history = hist[: self._iterations + 1]

    @property
    def total_iterations(self) -> int:
        return self._iterations

    totalIterations = total_iterations

    @property
    def objective_history(self) -> np.ndarray:
        return self._objective_history

    objectiveHistory = objective_history


# ---------------------------------------------------------------------------
# IsotonicRegression (MLlib org.apache.spark.ml.regression.IsotonicRegression)
# ---------------------------------------------------------------------------

@persistable
class IsotonicRegression(Estimator):
    """MLlib ``IsotonicRegression``: weighted isotonic (or antitonic) fit of
    label vs ONE feature, via pool-adjacent-violators.

    Design: PAVA is inherently sequential pooling — a host algorithm by
    nature (same rule as the KS test's sort, stat.py) — but it runs ONCE on
    ≤ n aggregated points; prediction is vectorized interpolation over the
    fitted boundaries and rides the device path through ``with_column``.
    MLlib semantics reproduced: points with equal feature values aggregate
    to their weighted-mean label first; prediction linearly interpolates
    between boundaries and is constant beyond them; ``isotonic=False``
    fits the antitonic (decreasing) function.
    """

    _persist_attrs = ("isotonic", "features_col", "label_col",
                      "prediction_col", "weight_col", "feature_index")

    def __init__(self, isotonic: bool = True, features_col: str = "features",
                 label_col: str = "label", prediction_col: str = "prediction",
                 weight_col: Optional[str] = None, feature_index: int = 0):
        self.isotonic = bool(isotonic)
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col
        self.weight_col = weight_col
        self.feature_index = int(feature_index)

    def set_isotonic(self, v):
        self.isotonic = bool(v)
        return self

    def set_feature_index(self, v):
        self.feature_index = int(v)
        return self

    def set_weight_col(self, v):
        self.weight_col = v
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

    setIsotonic = set_isotonic
    setFeatureIndex = set_feature_index
    setWeightCol = set_weight_col
    setFeaturesCol = set_features_col
    setLabelCol = set_label_col
    setPredictionCol = set_prediction_col

    def fit(self, frame: Frame) -> "IsotonicRegressionModel":
        X = np.asarray(frame._column_values(self.features_col), np.float64)
        if X.ndim > 1:
            X = X[:, self.feature_index]
        y = np.asarray(frame._column_values(self.label_col), np.float64)
        mask = np.asarray(frame.mask)
        w = np.ones_like(y) if self.weight_col is None else \
            np.asarray(frame._column_values(self.weight_col), np.float64)
        x, y, w = X[mask], y[mask], w[mask]
        if x.size == 0:
            raise ValueError("IsotonicRegression: no valid rows")
        if not (np.all(np.isfinite(x)) and np.all(np.isfinite(y))):
            raise ValueError("IsotonicRegression: non-finite feature/label "
                             "in valid rows")
        if np.any(w < 0):
            raise ValueError("weights must be nonnegative")

        sign = 1.0 if self.isotonic else -1.0
        order = np.argsort(x, kind="stable")
        xs, ys, ws = x[order], sign * y[order], w[order]

        # aggregate duplicate feature values: weighted mean label (MLlib)
        uniq, start = np.unique(xs, return_index=True)
        wsum = np.add.reduceat(ws, start)
        ysum = np.add.reduceat(ws * ys, start)
        keep = wsum > 0
        bx = uniq[keep]
        bw = wsum[keep]
        by = ysum[keep] / bw

        # pool adjacent violators (weighted), classic stack formulation
        vals: list = []
        wts: list = []
        xs_lo: list = []
        xs_hi: list = []
        for xi, yi, wi in zip(bx, by, bw):
            vals.append(yi)
            wts.append(wi)
            xs_lo.append(xi)
            xs_hi.append(xi)
            while len(vals) > 1 and vals[-2] > vals[-1]:
                y2, w2 = vals.pop(), wts.pop()
                hi2 = xs_hi.pop()          # merged pool spans (lo1, hi2)
                xs_lo.pop()
                y1, w1 = vals.pop(), wts.pop()
                xs_hi.pop()
                lo1 = xs_lo.pop()
                vals.append((y1 * w1 + y2 * w2) / (w1 + w2))
                wts.append(w1 + w2)
                xs_lo.append(lo1)
                xs_hi.append(hi2)

        # MLlib keeps each pool's boundary pair (lo, hi) with the pooled
        # value at both ends, then interpolates linearly between pools
        boundaries: list = []
        predictions: list = []
        for lo, hi, v in zip(xs_lo, xs_hi, vals):
            boundaries.append(lo)
            predictions.append(v)
            if hi != lo:
                boundaries.append(hi)
                predictions.append(v)
        return IsotonicRegressionModel(
            np.asarray(boundaries, np.float64),
            sign * np.asarray(predictions, np.float64),
            {"features_col": self.features_col,
             "prediction_col": self.prediction_col,
             "feature_index": self.feature_index,
             "isotonic": self.isotonic})


@persistable
class IsotonicRegressionModel(Model):
    """Fitted step/piecewise-linear function: ``boundaries`` (ascending) and
    ``predictions``; transform is vectorized interpolation with constant
    extrapolation (exactly ``np.interp``'s contract, which matches MLlib's
    predictionForX)."""

    _persist_attrs = ("boundaries", "predictions", "_params")

    def __init__(self, boundaries, predictions, params=None):
        self.boundaries = np.asarray(boundaries, np.float64)
        self.predictions = np.asarray(predictions, np.float64)
        self._params = dict(params or {})

    def _p(self, k, default=None):
        return self._params.get(k, default)

    def _predict_array(self, x):
        return np.interp(np.asarray(x, np.float64), self.boundaries,
                         self.predictions)

    def transform(self, frame: Frame) -> Frame:
        X = np.asarray(frame._column_values(
            self._p("features_col", "features")), np.float64)
        if X.ndim > 1:
            X = X[:, self._p("feature_index", 0)]
        pred = self._predict_array(X)
        return frame.with_column(self._p("prediction_col", "prediction"),
                                 jnp.asarray(pred, float_dtype()))

    def predict(self, feature: float) -> float:
        return float(self._predict_array([float(feature)])[0])
