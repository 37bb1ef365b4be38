"""Elastic-net linear solvers on sufficient statistics — the MLlib
``LinearRegression.train`` replacement, designed TPU-first.

MLlib's fit (SURVEY.md §3.3) is: one ``treeAggregate`` pass for feature/label
moments, then OWLQN iterations where every step broadcasts coefficients,
computes per-partition gradients row-by-row, and reduces over netty RPC — two
executor barriers per iteration.

The TPU design collapses all data passes into **one augmented Gramian**:
``A = ZᵀZ`` with ``Z = [X, y, 1] · mask`` — a single fused masked matmul on
the MXU (+ one ``psum`` over the mesh when sharded; see
``parallel/distributed.py``). Every quantity the solver needs — counts, means,
sample variances, the centered/standardized Gram matrix ``G``, the correlation
vector ``b``, and the label energy — unpacks from ``A`` on device. The whole
iteration loop (FISTA proximal gradient, or orthant-wise L-BFGS) then runs on
the tiny replicated ``(d×d)`` statistics inside one ``lax.scan`` — zero host
round-trips, zero per-iteration data passes, vs. Spark's 40×2 RPC barriers
(SURVEY.md §6 "Hard parts").

Numeric convention (validated against SURVEY.md §2.3 golden tables):

* sample std (n−1 denominator) for features and label (MLlib summarizer),
* solve in standardized space: ``x̂ = (x − x̄)/σ_x``, ``ŷ = (y − ȳ)/σ_y``
  (centering is implicit — it happens in the moment algebra, never on data),
* ``effectiveRegParam = regParam/σ_y``; L1/L2 split by ``elasticNetParam``,
* with ``standardization=False`` the penalty lands on the *raw* coefficients:
  L1 weight ``1/σ_xj``, L2 weight ``1/σ_xj²`` (MLlib semantics),
* unscale: ``w_j = ŵ_j σ_y/σ_xj``; ``intercept = ȳ − w·x̄``.
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class Moments(NamedTuple):
    """Unpacked sufficient statistics (all device scalars/vectors)."""
    n: jnp.ndarray           # valid-row count
    mean_x: jnp.ndarray      # (d,)
    mean_y: jnp.ndarray      # ()
    std_x: jnp.ndarray       # (d,) sample std
    std_y: jnp.ndarray       # ()
    G: jnp.ndarray           # (d,d) standardized (centered) Gram / n
    b: jnp.ndarray           # (d,)  standardized X'y / n
    yy: jnp.ndarray          # ()    standardized y'y / n  (≈ (n-1)/n)
    valid: jnp.ndarray       # (d,) bool — feature has nonzero variance


class FitResult(NamedTuple):
    coefficients: jnp.ndarray      # (d,) original scale
    intercept: jnp.ndarray         # ()
    iterations: jnp.ndarray        # () int32 — solver iterations run
    objective_history: jnp.ndarray  # (max_iter+1,) scaled-objective trace
    converged: jnp.ndarray         # () bool


def augmented_gram(X: jnp.ndarray, y: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """One-pass masked statistics: ``A = ZᵀZ``, ``Z = [X, y, 1]·mask``.

    Shape ``(d+2, d+2)``. This is the entire data touch of a linear fit — the
    ``treeAggregate`` analogue, as one MXU matmul per shard.
    """
    w = mask.astype(X.dtype)
    ones = jnp.ones_like(y)
    Z = jnp.concatenate([X, y[:, None], ones[:, None]], axis=1) * w[:, None]
    return Z.T @ Z


def unpack_moments(A: jnp.ndarray, fit_intercept: bool = True) -> Moments:
    """A → means/stds/standardized Gram. Pure device algebra, no data."""
    d = A.shape[0] - 2
    n = A[d + 1, d + 1]
    sum_x = A[:d, d + 1]
    sum_y = A[d, d + 1]
    mean_x = sum_x / n
    mean_y = sum_y / n
    # Centered second moments (always centered for std computation)
    Cxx = A[:d, :d] - n * jnp.outer(mean_x, mean_x)
    Cxy = A[:d, d] - n * mean_x * mean_y
    Cyy = A[d, d] - n * mean_y * mean_y
    denom = jnp.maximum(n - 1.0, 1.0)
    var_x = jnp.clip(jnp.diag(Cxx), 0.0) / denom
    var_y = jnp.clip(Cyy, 0.0) / denom
    std_x = jnp.sqrt(var_x)
    std_y = jnp.sqrt(var_y)
    valid = std_x > 0
    sx = jnp.where(valid, std_x, 1.0)
    sy = jnp.where(std_y > 0, std_y, 1.0)
    if not fit_intercept:
        # MLlib without intercept: no centering in the objective (std still
        # computed from centered moments above).
        Cxx = A[:d, :d]
        Cxy = A[:d, d]
        Cyy = A[d, d]
    G = Cxx / (n * jnp.outer(sx, sx))
    b = jnp.where(valid, Cxy / (n * sx * sy), 0.0)
    yy = Cyy / (n * sy * sy)
    # Zero out invalid (constant) features so they never move off 0.
    G = jnp.where(jnp.outer(valid, valid), G, jnp.where(
        jnp.eye(d, dtype=bool), 1.0, 0.0))
    return Moments(n, mean_x, mean_y, std_x, std_y, G, b, yy, valid)


def _penalty_weights(m: Moments, standardization: bool):
    """Per-feature multipliers (u1 for L1, u2 for L2) in standardized space.

    With ``standardization=False`` the penalty applies to the *raw*
    coefficient ``w_raw = ŵ/σ``: ``|w_raw| = |ŵ|/σ`` gives u1 = 1/σ, while
    ``w_raw² = ŵ²/σ²`` gives u2 = 1/σ² (MLlib's L2Regularization divides by
    std twice)."""
    if standardization:
        ones = jnp.ones_like(m.std_x)
        return ones, ones
    sx = jnp.where(m.valid, m.std_x, 1.0)
    u1 = jnp.where(m.valid, 1.0 / sx, 0.0)
    return u1, u1 * u1


def _objective(w, m: Moments, lam1, lam2):
    f = 0.5 * (m.yy - 2.0 * jnp.dot(m.b, w) + w @ m.G @ w)
    return f + jnp.sum(lam1 * jnp.abs(w)) + 0.5 * jnp.sum(lam2 * w * w)


def _soft(x, t):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


@functools.partial(jax.jit, static_argnames=("max_iter", "fit_intercept",
                                             "standardization",
                                             "record_history"))
def fista_solve(A: jnp.ndarray, reg_param, elastic_net_param,
                max_iter: int = 100, tol: float = 1e-6,
                fit_intercept: bool = True,
                standardization: bool = True,
                record_history: bool = True) -> FitResult:
    """Accelerated proximal gradient (FISTA) on the standardized objective.

    Reaches the same optimum as MLlib's OWLQN on the convex elastic net
    (parity is defined on the solution, SURVEY.md §7 "Hard parts"); the whole
    loop is one ``lax.scan`` with static shapes. ``objective_history[0]`` is
    the loss at w=0 (≈0.5), matching MLlib's convention of recording the
    initial objective.

    ``record_history=False`` drops the per-iteration objective trace
    (the returned history holds only the initial objective) — callers
    that solve many throwaway cells (the fused CV grid) skip the wasted
    stacking. The trace itself is accumulated in the scan CARRY with an
    explicit int32 ``dynamic_update_index_in_dim`` rather than as a
    stacked scan output, so ``record_history=False`` carries a
    zero-length buffer instead of stacking ``max_iter`` values.
    """
    m = unpack_moments(A, fit_intercept=fit_intercept)
    dt = A.dtype
    d = m.b.shape[0]
    eff = jnp.asarray(reg_param, dt) / jnp.where(m.std_y > 0, m.std_y, 1.0)
    alpha = jnp.asarray(elastic_net_param, dt)
    u1, u2 = _penalty_weights(m, standardization)
    lam1 = alpha * eff * u1
    lam2 = (1.0 - alpha) * eff * u2
    # Lipschitz bound: ‖G‖₂ ≤ ‖G‖_F for PSD G; + max ridge term.
    L = jnp.linalg.norm(m.G) + jnp.max(lam2, initial=0.0) + jnp.asarray(1e-12, dt)
    step = 1.0 / L

    w0 = jnp.zeros((d,), dt)
    obj0 = _objective(w0, m, lam1, lam2)
    hist0 = jnp.zeros((max_iter if record_history else 0,), dt)

    def body(state, i):
        w, w_prev, t, done, iters, last_obj, hist = state
        tn = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        v = w + ((t - 1.0) / tn) * (w - w_prev)
        grad = m.G @ v - m.b + lam2 * v
        w_new = _soft(v - step * grad, step * lam1)
        w_new = jnp.where(m.valid, w_new, 0.0)
        obj = _objective(w_new, m, lam1, lam2)
        # MLlib-style relative-improvement convergence test
        rel = jnp.abs(obj - last_obj) / jnp.maximum(jnp.abs(last_obj), 1e-12)
        now_done = jnp.logical_or(done, rel < tol)
        w_out = jnp.where(done, w, w_new)
        w_prev_out = jnp.where(done, w_prev, w)
        t_out = jnp.where(done, t, tn)
        obj_out = jnp.where(done, last_obj, obj)
        iters_out = iters + jnp.where(done, 0, 1).astype(jnp.int32)
        if record_history:
            hist = jax.lax.dynamic_update_index_in_dim(hist, obj_out, i, 0)
        return (w_out, w_prev_out, t_out, now_done, iters_out, obj_out,
                hist), None

    init = (w0, w0, jnp.asarray(1.0, dt), jnp.asarray(False),
            jnp.asarray(0, jnp.int32), obj0, hist0)
    (w, _, _, done, iters, _, hist), _ = jax.lax.scan(
        body, init, jnp.arange(max_iter, dtype=jnp.int32))

    sx = jnp.where(m.valid, m.std_x, 1.0)
    sy = jnp.where(m.std_y > 0, m.std_y, 1.0)
    coef = jnp.where(m.valid, w * sy / sx, 0.0)
    intercept = (m.mean_y - jnp.dot(coef, m.mean_x)) if fit_intercept else jnp.asarray(0.0, dt)
    history = (jnp.concatenate([obj0[None], hist]) if record_history
               else obj0[None])
    return FitResult(coef, intercept, iters, history, done)


@functools.partial(jax.jit, static_argnames=("fit_intercept", "standardization"))
def normal_solve(A: jnp.ndarray, reg_param, elastic_net_param=0.0,
                 fit_intercept: bool = True,
                 standardization: bool = True) -> FitResult:
    """Closed-form (normal-equations) path — MLlib's ``solver="normal"``,
    valid when there is no L1 term. One small Cholesky solve on device."""
    m = unpack_moments(A, fit_intercept=fit_intercept)
    dt = A.dtype
    d = m.b.shape[0]
    eff = jnp.asarray(reg_param, dt) / jnp.where(m.std_y > 0, m.std_y, 1.0)
    lam2 = (1.0 - jnp.asarray(elastic_net_param, dt)) * eff * _penalty_weights(m, standardization)[1]
    H = m.G + jnp.diag(lam2)
    w = jnp.linalg.solve(H, m.b)
    w = jnp.where(m.valid, w, 0.0)
    sx = jnp.where(m.valid, m.std_x, 1.0)
    sy = jnp.where(m.std_y > 0, m.std_y, 1.0)
    coef = jnp.where(m.valid, w * sy / sx, 0.0)
    intercept = (m.mean_y - jnp.dot(coef, m.mean_x)) if fit_intercept else jnp.asarray(0.0, dt)
    history = jnp.zeros((1,), dt)
    return FitResult(coef, intercept, jnp.asarray(0, jnp.int32), history,
                     jnp.asarray(True))


def resolve_solver(solver: str, reg_param: float, elastic_net_param: float) -> str:
    """Map MLlib's ``solver`` param to a concrete solver name, with
    ``auto`` semantics: normal equations when no L1 term is active, else the
    iterative proximal path."""
    has_l1 = (reg_param > 0.0) and (elastic_net_param > 0.0)
    if solver == "normal" or (solver == "auto" and not has_l1):
        if has_l1:
            raise ValueError("solver='normal' cannot apply an L1 penalty")
        return "normal"
    if solver in ("auto", "fista", "proximal"):
        return "fista"
    if solver in ("owlqn", "l-bfgs", "lbfgs"):
        return "owlqn"
    raise ValueError(f"unknown solver {solver!r}")


def downgrade_solver(solver_name: str, reg_param: float,
                     elastic_net_param: float) -> Optional[str]:
    """The resilience ladder's solver downgrade (``utils.recovery``):
    an iterative solver (``owlqn``/``fista``) that keeps failing degrades
    to the closed-form ``normal`` path — but only when no L1 term is
    active (normal equations cannot express the L1 penalty, exactly
    MLlib's restriction). Returns ``None`` when no downgrade exists."""
    has_l1 = (reg_param > 0.0) and (elastic_net_param > 0.0)
    if solver_name in ("owlqn", "fista") and not has_l1:
        return "normal"
    return None


def solve(A: jnp.ndarray, reg_param: float, elastic_net_param: float,
          max_iter: int, tol: float, fit_intercept: bool, standardization: bool,
          solver: str = "auto") -> FitResult:
    """Solver dispatch on a precomputed Gramian (see :func:`resolve_solver`).

    Host-level dispatch boundary, so it carries the ``solver`` fault-site
    hooks (``utils.faults``): a scheduled device error raises here before
    the jitted solve, and a scheduled NaN poisons the returned statistics
    — both exercised by the resilience suite. No-ops without a plan.
    """
    from ..utils import faults as _faults
    from ..utils import observability as _obs
    from ..utils.profiling import counters

    _faults.inject("solver")
    name = resolve_solver(solver, reg_param, elastic_net_param)
    counters.increment(f"solver.{name}_calls")
    _record_solver_example(name, A, reg_param, elastic_net_param,
                           max_iter, tol, fit_intercept, standardization)
    with _obs.span("solver.solve", cat="solver", solver=name,
                   features=int(A.shape[0]) - 2, max_iter=max_iter):
        if name == "normal":
            result = normal_solve(A, reg_param, elastic_net_param,
                                  fit_intercept=fit_intercept,
                                  standardization=standardization)
        elif name == "fista":
            result = fista_solve(A, reg_param, elastic_net_param,
                                 max_iter=max_iter, tol=tol,
                                 fit_intercept=fit_intercept,
                                 standardization=standardization)
        else:
            from .owlqn import owlqn_solve

            result = owlqn_solve(A, reg_param, elastic_net_param,
                                 max_iter=max_iter, tol=tol,
                                 fit_intercept=fit_intercept,
                                 standardization=standardization)
    return _faults.corrupt("solver", result)


def _jit_entry_size(fn) -> Optional[int]:
    """Compiled-program count of a ``jax.jit`` entry point (private-ish
    ``_cache_size`` API — None when unavailable, never an error)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return None


#: Abstract example calling conventions of the solver jit entry points,
#: keyed by a stable program key (solver name + Gramian spec + statics).
#: Recorded at the ``solve()`` dispatch boundary (shape/dtype metadata
#: only) so the program auditor can re-trace "the solver programs this
#: process actually serves" without guessing shapes. Bounded: one entry
#: per distinct (solver, shape, statics) signature.
_SOLVER_EXAMPLES: dict[str, tuple] = {}
_SOLVER_EXAMPLES_LOCK = threading.Lock()
_SOLVER_EXAMPLES_MAX = 64


def _record_solver_example(name: str, A, reg_param, elastic_net_param,
                           max_iter, tol, fit_intercept,
                           standardization) -> None:
    if name not in ("fista", "normal"):
        return            # owlqn is not a single jit entry point
    shape = tuple(getattr(A, "shape", ()))
    dtype = getattr(A, "dtype", None)
    if len(shape) != 2 or dtype is None:
        return
    key = (f"{name}_solve|A={shape[0]}x{shape[1]}:{np.dtype(dtype).str}"
           f"|maxIter={max_iter}|intercept={bool(fit_intercept)}"
           f"|std={bool(standardization)}")
    with _SOLVER_EXAMPLES_LOCK:
        if key in _SOLVER_EXAMPLES \
                or len(_SOLVER_EXAMPLES) >= _SOLVER_EXAMPLES_MAX:
            return
        aspec = jax.ShapeDtypeStruct(shape, dtype)
        if name == "normal":
            args = (aspec, float(reg_param), float(elastic_net_param))
            kwargs = {"fit_intercept": bool(fit_intercept),
                      "standardization": bool(standardization)}
            fn = normal_solve
        else:
            args = (aspec, float(reg_param), float(elastic_net_param))
            kwargs = {"max_iter": int(max_iter), "tol": float(tol),
                      "fit_intercept": bool(fit_intercept),
                      "standardization": bool(standardization)}
            fn = fista_solve
        _SOLVER_EXAMPLES[key] = (fn, args, kwargs)


def solver_program_handles() -> list:
    """Registry callback (CACHES.register_programs): the solver jit
    entry points at every calling convention this process dispatched.
    The variant re-traces at the next feature count — solver-loop
    structure must not depend on the Gramian size."""
    from ..utils import observability as _obs

    with _SOLVER_EXAMPLES_LOCK:
        items = list(_SOLVER_EXAMPLES.items())
    out = []
    for key, (fn, args, kwargs) in items:
        a = args[0]

        def wider(extra):
            return jax.ShapeDtypeStruct(
                (a.shape[0] + extra, a.shape[1] + extra), a.dtype)

        out.append(_obs.ProgramHandle(
            "solver", key, fn,
            args=args, kwargs=kwargs,
            # two fresh widths compared against each other (never the
            # possibly trace-cached recorded shape)
            variants={"shape": [((wider(1),) + args[1:], kwargs),
                                ((wider(2),) + args[1:], kwargs)]},
            mesh=None, guarded=None, meta={}))
    return out


def solver_cache_stats() -> dict:
    """Registry callback (observability.CACHES): compiled-program counts
    of the solver jit entry points plus the per-solver call counters —
    ``session.cache_report()['solver']``."""
    from ..utils.profiling import counters

    with _SOLVER_EXAMPLES_LOCK:
        entries = [{"key": k[:160], "program_key": k}
                   for k in _SOLVER_EXAMPLES]
    stats: dict = {
        "kind": "jax.jit entry points (sufficient-statistics solvers)",
        "programs": {"fista_solve": _jit_entry_size(fista_solve),
                     "normal_solve": _jit_entry_size(normal_solve)},
        "entries": entries,
    }
    calls = {name: counters.get(f"solver.{name}_calls")
             for name in ("fista", "normal", "owlqn")}
    stats["calls"] = {k: v for k, v in calls.items() if v}
    stats["fits"] = counters.get("solver.fits")
    stats["trace_hits"] = counters.get("jit.trace_hit")
    stats["trace_misses"] = counters.get("jit.trace_miss")
    return stats


def _register_cache_stats() -> None:
    from ..utils import observability as _obs

    _obs.CACHES.register("solver", solver_cache_stats)
    _obs.CACHES.register_programs("solver", solver_program_handles)


_register_cache_stats()


def psum_value_and_grad(local_objective, axis):
    """``value_and_grad`` of a data-parallel objective inside
    ``jax.shard_map(check_vma=True)``: differentiate the psum of the
    LOCAL objective.

    ``params`` enter the manual region replicated and the local objective
    is device-varying, so autodiff already reduces the cotangent of the
    replicated params over ``axis`` — the gradient that comes back is the
    full-data gradient, identical on every device, and must NOT be
    ``psum``'d again (that would scale it by the device count). Any
    replicated term in the local objective (regularizers on replicated
    params) must be pre-divided by the shard count so the psum restores
    it exactly once.

    ``axis=None`` returns plain ``jax.value_and_grad`` — the single-device
    path pays nothing.
    """
    if axis is None:
        return jax.value_and_grad(local_objective)
    return jax.value_and_grad(
        lambda params: jax.lax.psum(local_objective(params), axis))


def adam_scan(value_and_grad, params0, max_iter: int, lr: float,
              grad_mask=None, b1: float = 0.9, b2: float = 0.999,
              eps: float = 1e-8):
    """Full-batch Adam (bias-corrected) as ONE ``lax.scan`` over a params
    pytree — the shared optimizer of the non-Gramian fits (Weibull AFT,
    factorization machines). ``value_and_grad(params) -> (loss, grads)``;
    ``grad_mask`` optionally transforms the gradient pytree (e.g. zeroing
    frozen parameter groups). Returns (params, loss_history).
    """
    leaves = jax.tree_util.tree_leaves(params0)
    dt = leaves[0].dtype
    m0 = jax.tree_util.tree_map(jnp.zeros_like, params0)

    def body(state, i):
        p, m, v = state
        loss, g = value_and_grad(p)
        if grad_mask is not None:
            g = grad_mask(g)
        m = jax.tree_util.tree_map(lambda a, b_: b1 * a + (1 - b1) * b_,
                                   m, g)
        v = jax.tree_util.tree_map(
            lambda a, b_: b2 * a + (1 - b2) * b_ * b_, v, g)
        t = i + 1
        p = jax.tree_util.tree_map(
            lambda p_, m_, v_: p_ - lr * (m_ / (1 - b1 ** t))
            / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps), p, m, v)
        return (p, m, v), loss

    (params, _, _), history = jax.lax.scan(
        body, (params0, m0, m0), jnp.arange(max_iter, dtype=dt))
    return params, history


def huber_fit(X, y, mask, epsilon: float = 1.35, reg_param: float = 0.0,
              fit_intercept: bool = True, max_iter: int = 500,
              tol: float = 1e-8, standardization: bool = True):
    """MLlib's ``loss="huber"`` robust regression: joint minimization of
    Huber's concomitant-scale objective (Owen 2007 — the same objective
    sklearn's HuberRegressor and Spark's HuberAggregator use)

        L(beta, sigma) = sum_i m_i (sigma + H_eps(r_i / sigma) * sigma)
                         + reg_param * ||beta||^2,   r_i = y_i - x_i.b - c

    over (beta, intercept, log sigma) with full-batch Adam inside one
    jitted ``lax.while_loop`` — the robust loss has no Gramian
    sufficient statistic, so unlike the squared-error path this
    revisits the rows every iteration (still one fused device program,
    zero host round-trips). Initialized from the OLS solution.
    Returns (coefficients, intercept, sigma, iterations, history).
    """
    import jax

    fdt = jnp.asarray(X).dtype
    X = jnp.asarray(X)
    y = jnp.asarray(y, fdt)
    # callers pass the Gramian-convention mask (bool, or sqrt(w) when a
    # weightCol is set); the robust objective weights rows LINEARLY, so
    # square it — a no-op for booleans, exactly w for weighted fits
    m = jnp.square(jnp.asarray(mask, fdt))
    n = jnp.maximum(jnp.sum(m), 1.0)
    d = X.shape[1]

    # OLS warm start via the existing Gramian machinery (which expects
    # the sqrt-convention mask, i.e. the caller's original)
    A = augmented_gram(X, y, jnp.asarray(mask, fdt))
    moments = unpack_moments(A, fit_intercept)
    # MLlib penalizes the STANDARDIZED coefficients when
    # standardization=True: beta_std_j = beta_j * std_j
    pen_scale = (jnp.asarray(moments.std_x, fdt) if standardization
                 else jnp.ones((d,), fdt))
    ols = normal_solve(A, 0.0, 0.0, fit_intercept=fit_intercept)
    b0 = jnp.asarray(ols.coefficients, fdt)
    c0 = jnp.asarray(ols.intercept, fdt)
    r0 = (y - X @ b0 - c0) * m
    s0 = jnp.log(jnp.maximum(jnp.sqrt(jnp.sum(r0 * r0) / n), 1e-6))

    eps = jnp.asarray(epsilon, fdt)

    def objective(params):
        b, c, ls = params
        sigma = jnp.exp(ls)
        r = (y - X @ b - (c if fit_intercept else 0.0)) / sigma
        # H(z) = z^2 inside, 2*eps|z| - eps^2 outside — the convention
        # sklearn's HuberRegressor optimizes (Owen 2007 eq. 1), so the
        # fitted scale_ cross-checks directly
        h = jnp.where(jnp.abs(r) <= eps, r * r,
                      2.0 * eps * jnp.abs(r) - eps * eps)
        # MLlib cost: (1/n) sum(loss) + regParam * 0.5 ||b_std||^2 —
        # scaled through by n so the loss term stays a plain sum
        return (jnp.sum(m * (sigma + h * sigma))
                + reg_param * n * 0.5 * jnp.sum((b * pen_scale) ** 2))

    grad = jax.grad(objective)

    def step(state):
        i, params, mom, vel, _prev, obj = state
        g = grad(params)
        t = (i + 1).astype(fdt)
        lr = 0.05 * jnp.minimum(1.0, 10.0 / t)   # mild decay
        mom = jax.tree.map(lambda a, b_: 0.9 * a + 0.1 * b_, mom, g)
        vel = jax.tree.map(lambda a, b_: 0.999 * a + 0.001 * b_ * b_,
                           vel, g)
        mhat = jax.tree.map(lambda a: a / (1 - 0.9 ** t), mom)
        vhat = jax.tree.map(lambda a: a / (1 - 0.999 ** t), vel)
        params = jax.tree.map(
            lambda p, mh, vh: p - lr * mh / (jnp.sqrt(vh) + 1e-9),
            params, mhat, vhat)
        new_obj = objective(params)
        return (i + 1, params, mom, vel, obj, new_obj)

    def cont(state):
        i, _p, _m, _v, prev, obj = state
        return jnp.logical_and(i < max_iter,
                               jnp.abs(prev - obj) > tol * (1 + jnp.abs(obj)))

    params0 = (b0, c0, s0)
    zeros = jax.tree.map(jnp.zeros_like, params0)
    state = (jnp.asarray(0), params0, zeros, zeros,
             jnp.asarray(jnp.inf, fdt), objective(params0))
    i, (b, c, ls), _, _, _, obj = jax.lax.while_loop(cont, step, state)
    return b, (c if fit_intercept else jnp.asarray(0.0, fdt)), \
        jnp.exp(ls), i, obj
