"""LogisticRegression — binary elastic-net classifier, MLlib convention
(BASELINE.json config: "LogisticRegression binary classifier on DQ-filtered
rows"; the reference app itself has no classifier, so the API mirrors the
estimator surface its LinearRegression exercises at
`DataQuality4MachineLearningApp.java:120-151`).

TPU-first fit path: unlike the linear case (one Gramian suffices —
solvers.py), logistic loss needs per-iteration data passes. The whole FISTA
loop therefore runs inside ONE jitted ``lax.while_loop`` over the row-sharded data:
each iteration computes the local masked gradient and reduces the ``(d+2)``
gradient/loss vector with a single ``psum`` over the mesh — this is the true
per-iteration ``treeAggregate`` analogue (SURVEY.md §3.3), with the
coefficient "broadcast" implicit in SPMD replication and zero host syncs for
the entire optimization.

Numeric convention (MLlib LogisticRegression):

* features scaled by sample std (no centering — matches MLlib's
  sparsity-preserving choice); intercept fit unpenalized,
* mean log-loss objective; ``effectiveRegParam = regParam`` (no label
  scaling, unlike linear regression),
* with ``standardization=False`` the penalty lands on the raw coefficients:
  L1 weight ``1/σ_j``, L2 weight ``1/σ_j²``, as in the linear case.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..config import float_dtype
from ..frame.frame import Frame
from ..parallel.mesh import DATA_AXIS, serialize_collectives, shard_map
from ..utils import observability as _obs
from .base import (Estimator, Model, host_fetch, label_stats, persistable,
                   read_json, read_label_stats, write_json)
from .regression import _extract_xy
from .solvers import _soft


class LogisticFitResult(NamedTuple):
    coefficients: jnp.ndarray
    intercept: jnp.ndarray
    iterations: jnp.ndarray
    objective_history: jnp.ndarray
    converged: jnp.ndarray


def _feature_stats(X, y, mask):
    """Masked n, feature std (sample), for standardization — one pass.
    ``mask`` is the boolean mask or the instance weights (0 on masked
    rows); a row it zeroes is not read (``where``: a NaN there stays out
    of the sums)."""
    w = mask.astype(X.dtype)
    n = jnp.sum(w)
    X = jnp.where((w != 0)[:, None], X, 0)
    mean = (w @ X) / n
    var = (w @ (X * X)) / n - mean * mean
    denom = jnp.maximum(n - 1.0, 1.0)
    std = jnp.sqrt(jnp.clip(var * n / denom, 0.0))
    return n, std


def _sharded_feature_stats(X, mask):
    """Global masked n / sample std from inside shard_map — one fused psum
    of the [Σx, Σx², n] moment vector over the data axis."""
    w = mask.astype(X.dtype)
    parts = jnp.concatenate([w @ X, w @ (X * X), jnp.sum(w)[None]])
    parts = jax.lax.psum(parts, DATA_AXIS)
    d = X.shape[1]
    n = parts[2 * d]
    mean = parts[:d] / n
    var = parts[d: 2 * d] / n - mean * mean
    std = jnp.sqrt(jnp.clip(var * n / jnp.maximum(n - 1.0, 1.0), 0.0))
    return n, std


def _logistic_core(X, y, mask, reg_param, alpha, n, std,
                   max_iter, tol, fit_intercept, standardization, axis=None,
                   weights=None):
    """FISTA on mean log-loss over (possibly sharded) rows.

    When ``axis`` is set (inside shard_map), every per-row reduction is
    followed by a psum over that axis; n/std are passed in already global.
    ``weights``: optional per-row instance weights (MLlib weightCol); the
    default is the 0/1 mask. Margins always use the BOOLEAN mask — weights
    enter linearly through the per-row loss/gradient terms and ``n``.
    """
    dt = X.dtype
    d = X.shape[1]
    valid = std > 0
    sx = jnp.where(valid, std, 1.0)
    Xs = jnp.where(mask[:, None], X / sx, 0)   # standardized, masked rows
    yv = y.astype(dt) * mask.astype(dt)
    wm = mask.astype(dt)
    wv = wm if weights is None else weights.astype(dt)

    # penalty on raw coefficients when standardization=False: u1=1/sigma for
    # L1, u2=1/sigma^2 for L2 (see solvers._penalty_weights)
    u1 = jnp.ones((d,), dt) if standardization else jnp.where(valid, 1.0 / sx, 0.0)
    lam1 = alpha * reg_param * u1
    lam2 = (1.0 - alpha) * reg_param * (u1 if standardization else u1 * u1)

    def reduce_(v):
        return jax.lax.psum(v, axis) if axis is not None else v

    # Lipschitz bound: λmax(XᵀWX/n)/4 ≤ ‖√w·Xs‖_F²/(4n)
    sq = reduce_(jnp.sum(wv[:, None] * Xs * Xs))
    L = sq / (4.0 * n) + jnp.max(lam2, initial=0.0) + jnp.asarray(1e-12, dt)
    step = 1.0 / L

    def loss_grad(wb):
        w, b = wb[:d], wb[d]
        margin = Xs @ w + b * wm
        # stable log(1+exp(-z)) with z = (2y-1)*margin
        z = (2.0 * yv - wm) * margin
        ll = wv * jnp.logaddexp(0.0, -z)   # wv=0 zeroes masked rows
        p = jax.nn.sigmoid(margin)
        resid = (p - yv) * wv
        g_w = Xs.T @ resid
        g_b = jnp.sum(resid)
        packed = jnp.concatenate([g_w, jnp.array([g_b, jnp.sum(ll)])])
        packed = reduce_(packed)
        grad = packed[: d + 1] / n
        # ridge term belongs to the smooth part (L1 is handled by the prox)
        grad = grad.at[:d].add(lam2 * wb[:d])
        loss = packed[d + 1] / n
        if not fit_intercept:
            grad = grad.at[d].set(0.0)
        return loss, grad

    def objective(wb, loss):
        w = wb[:d]
        return loss + jnp.sum(lam1 * jnp.abs(w)) + 0.5 * jnp.sum(lam2 * w * w)

    def prox(cand):
        w_new = jnp.where(valid, _soft(cand[:d], step * lam1), 0.0)
        b_new = jnp.where(fit_intercept, cand[d], 0.0)
        return jnp.concatenate([w_new, b_new[None]])

    wb, done, iters, history = _fista_drive(loss_grad, objective, prox,
                                            step, d + 1, dt, max_iter, tol)
    coef = jnp.where(valid, wb[:d] / sx, 0.0)   # unscale to raw features
    intercept = wb[d]
    return LogisticFitResult(coef, intercept, iters, history, done)


def _logistic_newton_core(X, y, mask, reg_param, alpha, n, std,
                          max_iter, tol, fit_intercept, standardization,
                          axis=None, weights=None):
    """Damped Newton (IRLS) on mean log-loss — the L1-free fast path.

    Chosen automatically by ``LogisticRegression.fit`` when the penalty has
    no L1 part (``alpha`` is then 0 by construction and ignored here):
    Newton converges in ~5–10 iterations where FISTA needs its full budget,
    and each iteration is ONE fused pass — margin matvec, gradient, and the
    (d+1)² weighted Gramian Hessian (MXU-shaped) — psum'd once under a
    mesh (the per-iteration ``treeAggregate`` analogue, same as FISTA's).

    Robustness: the Hessian solve carries a tiny scaled diagonal jitter
    (separable unpenalized data drives p(1−p) → 0 and H toward singular),
    and each step is line-searched over {1, ½, ¼, ⅛}·δ — all four
    candidates evaluated in ONE batched matmul — keeping the objective
    monotone; when no candidate improves, the iterate stays put and the
    convergence latch closes. Same result contract as ``_logistic_core``
    (history length ``max_iter``+1, trailing entries frozen at the last
    objective).
    """
    del alpha  # L1-free by construction (router guarantees it)
    dt = X.dtype
    d = X.shape[1]
    valid = std > 0
    sx = jnp.where(valid, std, 1.0)
    with _obs.scope("fit.pack"):       # standardise + intercept column
        wm = mask.astype(dt)
        yv = y.astype(dt) * wm
        wv = wm if weights is None else weights.astype(dt)
        # Za = [X / sx, 1]·mask is the one (n, d+1) array the pack writes
        # and every pass of the loop re-reads. Written as a pad (the ones
        # column) under one select, it is one fusion over X; as
        # concatenate([Xs, wm[:, None]]) XLA wrote the standardised Xs as
        # a second n-row copy and relaid wm out for the concatenation.
        # The barrier keeps it from splitting Za @ v back into
        # Xs @ v[:d] + wm * v[d], which needs that copy again.
        Za = jax.lax.optimization_barrier(jnp.where(
            mask[:, None],
            jnp.pad(X, ((0, 0), (0, 1)), constant_values=1.0)
            / jnp.concatenate([sx, jnp.ones((1,), dt)]),
            jnp.zeros((), dt)))

    u1 = jnp.ones((d,), dt) if standardization \
        else jnp.where(valid, 1.0 / sx, 0.0)
    lam2 = reg_param * (u1 if standardization else u1 * u1)
    lam2_full = jnp.concatenate([lam2, jnp.zeros((1,), dt)])
    valid_full = jnp.concatenate([valid,
                                  jnp.full((1,), bool(fit_intercept))])

    def reduce_(v):
        return jax.lax.psum(v, axis) if axis is not None else v

    m = d + 1

    def stats(wb):
        """Gradient + Hessian at wb — one fused (psum'd) pass. (The loss
        is NOT computed here: the driver reads objectives only through
        ``batched_objective``, so packing a loss scalar would be dead
        O(n) work the psum forbids XLA from eliminating.)"""
        # the iteration's data pass, one scope a part (ROADMAP S3 asks
        # which of them the time goes to; XLA may still fuse across them)
        with _obs.scope("fit.newton.margin"):
            margin = Za @ wb
            p = jax.nn.sigmoid(margin)
        with _obs.scope("fit.newton.gradient"):
            resid = (p - yv) * wv
            g = Za.T @ resid                               # (m,)
        with _obs.scope("fit.newton.hessian"):
            s = wv * p * (1.0 - p)
            H = (Za * s[:, None]).T @ Za                   # (m, m)
        packed = reduce_(jnp.concatenate([H.ravel(), g]))
        H = packed[:m * m].reshape(m, m) / n
        g = packed[m * m:] / n
        g = g + lam2_full * wb
        H = H + jnp.diag(lam2_full)
        g = jnp.where(valid_full, g, 0.0)
        H = jnp.where(valid_full[:, None] & valid_full[None, :], H,
                      jnp.eye(m, dtype=dt))
        return g, H

    def batched_objective(C):
        """Objectives of a (4, m) candidate stack in one fused pass."""
        with _obs.scope("fit.newton.line_search"):
            margins = Za @ C.T                             # (n, 4)
            z = (2.0 * yv - wm)[:, None] * margins
            ll = jnp.sum(wv[:, None] * jnp.logaddexp(0.0, -z),
                         axis=0)                           # (4,)
            ll = reduce_(ll) / n
            return ll + 0.5 * jnp.sum(lam2_full[None, :] * C * C, axis=1)

    wb, ok, iters, history = _newton_drive(stats, batched_objective, m,
                                           valid_full, dt, max_iter, tol)
    coef = jnp.where(valid, wb[:d] / sx, 0.0)
    intercept = wb[d]
    return LogisticFitResult(coef, intercept, iters, history, ok)


def _fista_drive(loss_grad, objective, prox, step, M, dt, max_iter, tol):
    """Shared Nesterov/FISTA driver (binary + softmax + SVC cores):
    momentum extrapolation, gradient-prox step, convergence latch, and
    objective-history bookkeeping in ONE place.

    ``loss_grad(wb) -> (loss, grad)`` is the (psum'd) smooth pass;
    ``objective(wb, loss)`` adds the nonsmooth/ridge terms;
    ``prox(cand) -> wb`` applies the proximal map + validity masking.

    while_loop, not scan: each iteration is two O(n·d) data passes, so a
    fit that converges at iteration k must stop paying for the remaining
    ``max_iter − k`` passes (a scan with a done-latch keeps computing
    them just to freeze the carry). History tail is pinned to the final
    objective after the loop — same decode contract as before.

    Returns ``(wb, converged, iterations, history)`` with ``history`` of
    length ``max_iter + 1`` (entry 0 = objective at zero).
    """
    smooth_pass = loss_grad

    def loss_grad(wb):      # the O(n·d) data pass, named in a trace
        with _obs.scope("fit.fista.loss_grad"):
            return smooth_pass(wb)

    wb0 = jnp.zeros((M,), dt)
    loss0, _ = loss_grad(wb0)
    obj0 = objective(wb0, loss0)
    hist0 = jnp.full((max_iter + 1,), obj0, dt)

    def cond(state):
        _, _, _, done, iters, _, _ = state
        return jnp.logical_and(iters < max_iter, ~done)

    def body(state):
        wb, wb_prev, t, _, iters, last_obj, hist = state
        tn = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        v = wb + ((t - 1.0) / tn) * (wb - wb_prev)
        _, grad = loss_grad(v)
        wb_new = prox(v - step * grad)
        loss_new, _ = loss_grad(wb_new)
        obj = objective(wb_new, loss_new)
        rel = jnp.abs(obj - last_obj) / jnp.maximum(jnp.abs(last_obj), 1e-12)
        done = rel < tol
        hist = hist.at[iters + 1].set(obj)
        return (wb_new, wb, tn, done, iters + 1, obj, hist)

    init = (wb0, wb0, jnp.asarray(1.0, dt), jnp.asarray(False),
            jnp.asarray(0, jnp.int32), obj0, hist0)
    wb, _, _, done, iters, last_obj, hist = jax.lax.while_loop(
        cond, body, init)
    history = jnp.where(jnp.arange(max_iter + 1) <= iters, hist, last_obj)
    return wb, done, iters, history


def _newton_drive(stats, batched_objective, M, valid_full, dt,
                  max_iter, tol):
    """Shared damped-Newton driver (binary + softmax cores): jittered
    Hessian solve, batched {1, ½, ¼, ⅛}·δ line search, convergence latch,
    and objective-history bookkeeping — in ONE place so the two solvers'
    convergence behavior stays identical by construction.

    ``stats(wb) -> (g, H)`` must be the regularized gradient/Hessian pass;
    ``batched_objective(C)`` the objectives of a (c, M) candidate stack.

    while_loop, not scan: each Newton iteration is HEAVY (Gramian Hessian
    + solve + batched line search), so converged fits must stop computing
    — a scan with a done-latch would burn the full max_iter budget of
    Hessians to freeze the result. History is written into a preallocated
    buffer; the unfilled tail is pinned to the final objective after the
    loop (same decode contract as FISTA's scan).

    Returns ``(wb, converged, iterations, history)`` with ``history`` of
    length ``max_iter + 1`` (entry 0 = objective at zero).
    """
    wb0 = jnp.zeros((M,), dt)
    # matvec-width pass only — stats(wb0) would psum a full discarded
    # Hessian just to read this scalar
    obj0 = batched_objective(wb0[None, :])[0]
    steps = jnp.asarray([1.0, 0.5, 0.25, 0.125], dt)
    hist0 = jnp.full((max_iter + 1,), obj0, dt)

    def cond(state):
        _, halt, _, iters, _, _ = state
        return jnp.logical_and(iters < max_iter, ~halt)

    def body(state):
        wb, _, _, iters, last_obj, hist = state
        g, H = stats(wb)
        # Scaled jitter keeps the solve usable when H is near-singular
        # (e.g. the unpenalized-softmax shift degeneracy). Scale by the
        # dtype's eps: an absolute 1e-9 is BELOW half-ulp of a float32
        # diagonal (~1e-8 at O(1) entries) and would be bit-for-bit inert.
        with _obs.scope("fit.solve"):
            jitter = 100.0 * jnp.asarray(jnp.finfo(dt).eps, dt) * \
                (1.0 + jnp.max(jnp.abs(jnp.diag(H))))
            delta = jnp.linalg.solve(H + jitter * jnp.eye(M, dtype=dt), g)
            delta = jnp.where(valid_full, delta, 0.0)
        C = wb[None, :] - steps[:, None] * delta[None, :]  # (4, M)
        objs = batched_objective(C)
        objs = jnp.where(jnp.isfinite(objs), objs, jnp.inf)
        improving = objs < last_obj
        any_improving = jnp.any(improving)
        # first improving candidate (largest step), else stay put
        idx = jnp.argmax(improving)
        wb_new = jnp.where(any_improving, C[idx], wb)
        obj = jnp.where(any_improving, objs[idx], last_obj)
        rel = jnp.abs(obj - last_obj) / jnp.maximum(jnp.abs(last_obj), 1e-12)
        # Convergence: an accepted step whose relative decrease is < tol,
        # OR a stalled line search AT the optimum (gradient ~0 — at float
        # precision no candidate can improve there, the normal terminal
        # state for tiny tol). A stall with a LARGE gradient is a genuine
        # failure and must NOT report converged (sklearn's gtol analogue).
        gmax = jnp.max(jnp.abs(g))
        grad_small = gmax < 1e-4 * jnp.maximum(1.0, jnp.abs(last_obj))
        ok = jnp.logical_or(jnp.logical_and(rel < tol, any_improving),
                            jnp.logical_and(~any_improving, grad_small))
        halt = jnp.logical_or(ok, ~any_improving)
        hist = hist.at[iters + 1].set(obj)
        return (wb_new, halt, ok, iters + 1, obj, hist)

    init = (wb0, jnp.asarray(False), jnp.asarray(False),
            jnp.asarray(0, jnp.int32), obj0, hist0)
    wb, _, ok, iters, last_obj, hist = jax.lax.while_loop(cond, body, init)
    history = jnp.where(jnp.arange(max_iter + 1) <= iters, hist, last_obj)
    return wb, ok, iters, history


class SoftmaxFitResult(NamedTuple):
    coefficient_matrix: jnp.ndarray     # (K, d)
    intercept_vector: jnp.ndarray       # (K,)
    iterations: jnp.ndarray
    objective_history: jnp.ndarray
    converged: jnp.ndarray


def _softmax_core(X, y, mask, reg_param, alpha, n, std, num_classes,
                  max_iter, tol, fit_intercept, standardization, axis=None,
                  weights=None):
    """FISTA on the mean softmax cross-entropy over (possibly sharded) rows.

    MLlib ``family="multinomial"`` conventions: features scaled by sample
    std without centering; the (K, d) coefficient matrix penalized
    elementwise with the same elastic-net weights as the binary path; the
    K intercepts unpenalized. The whole loop is one ``lax.while_loop``
    (shared ``_fista_drive``) with a single fused ``(K·d + K + 1)`` psum
    per iteration when sharded — the per-iteration ``treeAggregate``
    analogue, exactly like the binary path.
    """
    dt = X.dtype
    d = X.shape[1]
    K = num_classes
    valid = std > 0
    sx = jnp.where(valid, std, 1.0)
    Xs = jnp.where(mask[:, None], X / sx, 0)   # standardized, masked rows
    wm = mask.astype(dt)
    wv = wm if weights is None else weights.astype(dt)
    Y1 = jax.nn.one_hot(y.astype(jnp.int32), K, dtype=dt) * wm[:, None]

    u1 = jnp.ones((d,), dt) if standardization \
        else jnp.where(valid, 1.0 / sx, 0.0)
    lam1 = alpha * reg_param * u1                       # (d,), same per class
    lam2 = (1.0 - alpha) * reg_param * (u1 if standardization else u1 * u1)

    def reduce_(v):
        return jax.lax.psum(v, axis) if axis is not None else v

    # Softmax Hessian w.r.t. margins is diag(p) − ppᵀ ⪯ ½·I, so
    # L ≤ ½‖Xs‖_F²/n (vs ¼ for the binary sigmoid).
    sq = reduce_(jnp.sum(wv[:, None] * Xs * Xs))
    L = 0.5 * sq / n + jnp.max(lam2, initial=0.0) + jnp.asarray(1e-12, dt)
    step = 1.0 / L

    m = K * d     # wb layout: [W.ravel() | b] with W (K, d), b (K,)

    def loss_grad(wb):
        W = wb[:m].reshape(K, d)
        b = wb[m:]
        margin = Xs @ W.T + b[None, :] * wm[:, None]        # (n, K)
        lse = jax.nn.logsumexp(margin, axis=1)
        ll = wv * jnp.where(mask, lse - jnp.sum(margin * Y1, axis=1), 0.0)
        p = jax.nn.softmax(margin, axis=1)
        resid = (p - Y1) * wv[:, None]                      # (n, K)
        g_W = resid.T @ Xs                                  # (K, d)
        g_b = jnp.sum(resid, axis=0)                        # (K,)
        packed = jnp.concatenate([g_W.ravel(), g_b, jnp.sum(ll)[None]])
        packed = reduce_(packed)
        grad = packed[: m + K] / n
        grad = grad.at[:m].add((lam2[None, :] * W).ravel())
        loss = packed[m + K] / n
        if not fit_intercept:
            grad = grad.at[m:].set(0.0)
        return loss, grad

    def objective(wb, loss):
        W = wb[:m].reshape(K, d)
        return (loss + jnp.sum(lam1[None, :] * jnp.abs(W))
                + 0.5 * jnp.sum(lam2[None, :] * W * W))

    lam1_full = jnp.concatenate([jnp.tile(lam1, K), jnp.zeros((K,), dt)])
    valid_full = jnp.concatenate([jnp.tile(valid, K),
                                  jnp.full((K,), fit_intercept)])

    def prox(cand):
        return jnp.where(valid_full, _soft(cand, step * lam1_full), 0.0)

    wb, done, iters, history = _fista_drive(loss_grad, objective, prox,
                                            step, m + K, dt, max_iter, tol)
    W = jnp.where(valid[None, :], wb[:m].reshape(K, d) / sx[None, :], 0.0)
    b = wb[m:]
    return SoftmaxFitResult(W, b, iters, history, done)


def _softmax_newton_core(X, y, mask, reg_param, alpha, n, std, num_classes,
                         max_iter, tol, fit_intercept, standardization,
                         axis=None, weights=None):
    """Damped Newton (IRLS) on mean softmax cross-entropy — the L1-free
    multinomial fast path (see ``_logistic_newton_core`` for the design;
    this is its K-class generalization).

    The softmax Hessian couples classes: block (k,l) is
    ``Σ_n s_nkl · za_n za_nᵀ`` with ``s_nkl = w_n (p_nk δ_kl − p_nk p_nl)``
    — built in ONE einsum over the batch (MXU-shaped contraction), psum'd
    once per iteration together with the gradient. The full
    ``(K(d+1))²`` system solves on device; the router caps ``K(d+1)`` so
    the solve stays trivial next to the data pass. For unpenalized fits
    the shift degeneracy (softmax invariance) makes H singular along the
    all-classes-shift direction — the scaled jitter handles it, and the
    caller's identifiability pivot (MLlib centering) fixes the gauge.
    """
    del alpha  # L1-free by construction (router guarantees it)
    dt = X.dtype
    d = X.shape[1]
    K = num_classes
    valid = std > 0
    sx = jnp.where(valid, std, 1.0)
    wm = mask.astype(dt)
    Xs = jnp.where(mask[:, None], X / sx, 0)
    wv = wm if weights is None else weights.astype(dt)
    Y1 = jax.nn.one_hot(y.astype(jnp.int32), K, dtype=dt) * wm[:, None]
    Za = jnp.concatenate([Xs, wm[:, None]], axis=1)      # (n, d+1)

    u1 = jnp.ones((d,), dt) if standardization \
        else jnp.where(valid, 1.0 / sx, 0.0)
    lam2 = reg_param * (u1 if standardization else u1 * u1)    # (d,)
    # wb layout: (K, d+1) ravelled — [W | b] per class row
    lam2_row = jnp.concatenate([lam2, jnp.zeros((1,), dt)])    # (d+1,)
    lam2_full = jnp.tile(lam2_row, K)
    valid_row = jnp.concatenate([valid,
                                 jnp.full((1,), bool(fit_intercept))])
    valid_full = jnp.tile(valid_row, K)
    M = K * (d + 1)

    def reduce_(v):
        return jax.lax.psum(v, axis) if axis is not None else v

    def margins_of(Wb):
        """(n, K) margins for a (K, d+1) coefficient block."""
        return Za @ Wb.T

    def stats(wb):
        """Gradient + block Hessian at wb — one fused (psum'd) pass (the
        loss lives only in ``batched_objective``; see the binary core)."""
        Wb = wb.reshape(K, d + 1)
        margin = margins_of(Wb)
        p = jax.nn.softmax(margin, axis=1)
        resid = (p - Y1) * wv[:, None]                     # (n, K)
        g = (resid.T @ Za).ravel()                         # (K(d+1),)
        # block Hessian: S_nkl = wv_n (p_nk δ_kl − p_nk p_nl)
        S = wv[:, None, None] * (
            jnp.einsum("nk,kl->nkl", p, jnp.eye(K, dtype=dt))
            - p[:, :, None] * p[:, None, :])               # (n, K, K)
        H = jnp.einsum("nkl,ni,nj->kilj", S, Za, Za).reshape(M, M)
        packed = reduce_(jnp.concatenate([H.ravel(), g]))
        H = packed[:M * M].reshape(M, M) / n
        g = packed[M * M:] / n
        g = g + lam2_full * wb
        H = H + jnp.diag(lam2_full)
        g = jnp.where(valid_full, g, 0.0)
        H = jnp.where(valid_full[:, None] & valid_full[None, :], H,
                      jnp.eye(M, dtype=dt))
        return g, H

    def batched_objective(C):
        """(c,) objectives of a (c, M) candidate stack in one fused pass."""
        Wc = C.reshape(-1, K, d + 1)
        margins = jnp.einsum("nj,ckj->nck", Za, Wc)        # (n, c, K)
        lse = jax.nn.logsumexp(margins, axis=2)            # (n, c)
        fitted = jnp.einsum("nck,nk->nc", margins, Y1)
        ll = jnp.sum(wv[:, None] * jnp.where(mask[:, None],
                                             lse - fitted, 0.0), axis=0)
        ll = reduce_(ll) / n
        return ll + 0.5 * jnp.sum(lam2_full[None, :] * C * C, axis=1)

    wb, ok, iters, history = _newton_drive(stats, batched_objective, M,
                                           valid_full, dt, max_iter, tol)
    Wb = wb.reshape(K, d + 1)
    W = jnp.where(valid[None, :], Wb[:, :d] / sx[None, :], 0.0)
    b = Wb[:, d]
    return SoftmaxFitResult(W, b, iters, history, ok)


def _unpack_z(Z):
    """Split the packed design ``Z = [X, y, 1]·mask`` (pack_design layout):
    the packed entry of the compiled fits (the columns entry skips this).

    The pre-masked columns are exactly what the logistic core consumes —
    it only ever reads X, y masked, and ``w² = w`` for a boolean mask, so
    masked moments (w@(X·w) = w@X etc.) are unchanged.
    """
    d = Z.shape[1] - 2
    X = Z[:, :d]
    y = Z[:, d]
    mask = Z[:, d + 1] > 0
    return X, y, mask



def _unpack_zw(Z):
    """Split the weighted packed design ``Z = [X·m, y·m, w·m]``
    (pack_design_weighted layout): the last column carries the REAL
    instance weights (zero on masked rows), so the boolean mask is
    ``w > 0`` and the weights ride the same single buffer."""
    d = Z.shape[1] - 2
    w = Z[:, d + 1]
    return Z[:, :d], Z[:, d], w > 0, w


def _split_design(design, weighted: bool = False):
    """``(X, y, mask, w)`` of a compiled fit's first argument, whichever
    entry it came through: the frame's columns (``DesignColumns``) or a
    packed ``Z`` (``pack_design`` / ``pack_design_weighted``, told apart
    by ``weighted``), sliced apart. ``w`` is ``None`` unweighted.

    Of the columns, ``y`` and ``w`` come back zeroed where the mask drops
    the row; ``X`` comes back as it is — the moments and the cores select
    by the mask where they read it (``where``, not ``x * 0``: a NaN in a
    filtered slot reaches no sum), and a cleaned ``X`` shared between
    them would be written out as an ``(n, d)`` copy."""
    from ..parallel.distributed import DesignColumns

    if isinstance(design, DesignColumns):
        X, y, mask, w = design
        return (X, jnp.where(mask, y, 0), mask,
                None if w is None else jnp.where(mask, w, 0))
    if weighted:
        return _unpack_zw(design)
    return _unpack_z(design) + (None,)


def _fit_design(X, y, mask, w, mesh):
    """What the estimators hand a compiled fit, inside the ``fit.pack``
    span: on one device the columns as they are (the program packs them:
    ``lowering="in-program"``, counter ``fit.pack_in_program``); for a
    mesh a packed ``Z`` placed row-sharded (``lowering="eager"``, counter
    ``fit.pack_eager``: one pack program and one ``device_put``)."""
    from ..parallel.distributed import (DesignColumns, pack_design,
                                        pack_design_weighted, place_packed)
    from ..utils.profiling import counters

    if mesh is None:
        with _obs.span("fit.pack", cat="fit", lowering="in-program"):
            counters.increment("fit.pack_in_program")
            return DesignColumns(X, y, mask, w)
    with _obs.span("fit.pack", cat="fit", lowering="eager"):
        Z = pack_design(X, y, mask) if w is None \
            else pack_design_weighted(X, y, mask, w)
        return place_packed(Z, mesh)


def _pack_logistic_result(r: "LogisticFitResult"):
    """One output buffer: [coef(d) | intercept | iters | converged | history]
    (same layout as the linear path; decode with
    distributed.unpack_fit_result)."""
    dt = r.coefficients.dtype
    scalars = jnp.stack([r.intercept.astype(dt), r.iterations.astype(dt),
                         r.converged.astype(dt)])
    return jnp.concatenate([r.coefficients, scalars,
                            r.objective_history.astype(dt)])


@functools.lru_cache(maxsize=None)
def fused_logistic_fit_packed(mesh: Optional[Mesh], max_iter: int, tol: float,
                              fit_intercept: bool, standardization: bool,
                              weighted: bool = False,
                              solver: str = "fista"):
    """One jitted program: stats pass + solver loop (+ per-iteration psum
    when sharded). Mirrors the linear path's ``fused_linear_fit_packed``,
    including its two entries: ``fit(design, hyper) -> flat`` with
    ``hyper = [regParam, elasticNetParam]`` and ``design`` either

    * the frame's columns, ``DesignColumns(X, y, mask, w)`` — what the
      estimator hands over on one device: the program masks, takes the
      moments and standardises under the scope ``dq.fit.pack``, and the
      only n-row matrix it writes is the one its loop re-reads; or
    * a packed ``Z = pack_design(X, y, mask)`` — callers that hold one,
      and the sharded path (row-sharded ``Z``), which slice it apart
      (``_unpack_z``) and run the same core. With ``weighted=True`` the
      packed input is ``pack_design_weighted(X, y, mask, w)`` — the last
      column carries real instance weights (MLlib weightCol), and
      n/std/loss/grad are their weighted forms; the columns entry carries
      the weights as ``w``.

    Which entry runs follows from the form of ``design`` alone.

    ``solver``: "fista" (the general elastic-net path) or "newton" (damped
    IRLS — L1-free penalties only; ``LogisticRegression.fit`` routes to it
    automatically, see ``_logistic_newton_core``)."""
    core = {"fista": _logistic_core,
            "newton": _logistic_newton_core}[solver]

    if mesh is None or mesh.devices.size <= 1:
        def fit(design, hyper):
            with _obs.scope("fit.pack"):    # split + the moments pass
                X, y, mask, w = _split_design(design, weighted)
                n, std = _feature_stats(X, y, mask if w is None else w)
            return _pack_logistic_result(core(
                X, y, mask, hyper[0], hyper[1], n, std, max_iter,
                tol, fit_intercept, standardization, weights=w))
    else:
        def local(Z, hyper):
            with _obs.scope("fit.pack"):
                X, y, mask, w = _split_design(Z, weighted)
                n, std = _sharded_feature_stats(X,
                                                mask if w is None else w)
            return _pack_logistic_result(core(
                X, y, mask, hyper[0], hyper[1], n, std, max_iter,
                tol, fit_intercept, standardization, axis=DATA_AXIS,
                weights=w))

        fit = shard_map(
            local, mesh=mesh,
            in_specs=(P(DATA_AXIS), P()),
            out_specs=P())

    return serialize_collectives(jax.jit(fit), mesh)


def _svc_core(X, y, mask, reg_param, n, std, max_iter, tol,
              fit_intercept, standardization, axis=None):
    """Accelerated gradient on the mean SQUARED hinge + L2 over (possibly
    sharded) rows — the MLlib ``LinearSVC`` role.

    MLlib minimizes the (subdifferentiable) hinge with OWLQN; the squared
    hinge is its smooth relative (sklearn's ``LinearSVC`` default), which
    maps onto the same zero-host-sync Nesterov ``lax.while_loop`` as the
    logistic path — one fused (d+2) psum per iteration when sharded.
    Decision boundaries agree with the hinge solution to test tolerance
    (asserted vs sklearn); conventions (std scaling without centering,
    unpenalized intercept, standardization-off 1/σ² penalty weights) match
    the logistic path / MLlib.
    """
    dt = X.dtype
    d = X.shape[1]
    valid = std > 0
    sx = jnp.where(valid, std, 1.0)
    Xs = jnp.where(mask[:, None], X / sx, 0)
    wm = mask.astype(dt)
    z = (2.0 * y.astype(dt) - 1.0) * wm         # ±1 labels, masked

    u1 = jnp.ones((d,), dt) if standardization \
        else jnp.where(valid, 1.0 / sx, 0.0)
    lam2 = reg_param * (u1 if standardization else u1 * u1)

    def reduce_(v):
        return jax.lax.psum(v, axis) if axis is not None else v

    # squared-hinge curvature ≤ 2 ⇒ L ≤ 2‖Xs‖_F²/n + max λ₂
    sq = reduce_(jnp.sum(Xs * Xs))
    L = 2.0 * sq / n + jnp.max(lam2, initial=0.0) + jnp.asarray(1e-12, dt)
    step = 1.0 / L

    def loss_grad(wb):
        w, b = wb[:d], wb[d]
        margin = Xs @ w + b * wm
        slack = jnp.maximum(0.0, wm - z * margin)   # masked rows: 0 − 0
        # d/dmargin ½slack² summed — resid drives both grad terms
        resid = -z * slack
        g_w = Xs.T @ resid
        g_b = jnp.sum(resid)
        packed = reduce_(jnp.concatenate(
            [g_w, jnp.array([g_b, jnp.sum(slack * slack)])]))
        grad = packed[: d + 1] * (2.0 / n)
        grad = grad.at[:d].add(lam2 * wb[:d])
        loss = packed[d + 1] / n
        if not fit_intercept:
            grad = grad.at[d].set(0.0)
        return loss, grad

    def objective(wb, loss):
        w = wb[:d]
        return loss + 0.5 * jnp.sum(lam2 * w * w)

    def prox(cand):
        return jnp.concatenate(
            [jnp.where(valid, cand[:d], 0.0),
             jnp.where(fit_intercept, cand[d], 0.0)[None]])

    wb, done, iters, history = _fista_drive(loss_grad, objective, prox,
                                            step, d + 1, dt, max_iter, tol)
    coef = jnp.where(valid, wb[:d] / sx, 0.0)
    return LogisticFitResult(coef, wb[d], iters, history, done)


@functools.lru_cache(maxsize=None)
def fused_svc_fit_packed(mesh: Optional[Mesh], max_iter: int, tol: float,
                         fit_intercept: bool, standardization: bool):
    """One jitted program for LinearSVC: stats pass + Nesterov loop
    (+ per-iteration psum when sharded); the same two entries as the
    logistic path (``DesignColumns`` on one device, a packed ``Z``
    otherwise). ``hyper = [regParam, 0]`` (second slot reserved — the SVC
    penalty is L2-only, like MLlib)."""

    if mesh is None or mesh.devices.size <= 1:
        def fit(design, hyper):
            with _obs.scope("fit.pack"):
                X, y, mask, _ = _split_design(design)
                n, std = _feature_stats(X, y, mask)
            return _pack_logistic_result(_svc_core(
                X, y, mask, hyper[0], n, std, max_iter, tol,
                fit_intercept, standardization))
    else:
        def local(Z, hyper):
            with _obs.scope("fit.pack"):
                X, y, mask = _unpack_z(Z)
                n, std = _sharded_feature_stats(X, mask)
            return _pack_logistic_result(_svc_core(
                X, y, mask, hyper[0], n, std, max_iter, tol,
                fit_intercept, standardization, axis=DATA_AXIS))

        fit = shard_map(
            local, mesh=mesh,
            in_specs=(P(DATA_AXIS), P()),
            out_specs=P())

    return serialize_collectives(jax.jit(fit), mesh)


def _pack_softmax_result(r: "SoftmaxFitResult"):
    """One output buffer: [W.ravel() | b | iters | converged | history]."""
    dt = r.coefficient_matrix.dtype
    scalars = jnp.stack([r.iterations.astype(dt), r.converged.astype(dt)])
    return jnp.concatenate([r.coefficient_matrix.ravel(),
                            r.intercept_vector.astype(dt), scalars,
                            r.objective_history.astype(dt)])


def unpack_softmax_result(flat, num_classes: int, d: int):
    """Host-side decode of the packed softmax fit output (one counted
    blocking read of the packed buffer)."""
    with _obs.host_reading("fit.result") as rd:
        flat = np.asarray(flat)
        rd.done(flat.nbytes)
    m = num_classes * d
    return SoftmaxFitResult(
        coefficient_matrix=flat[:m].reshape(num_classes, d),
        intercept_vector=flat[m: m + num_classes],
        iterations=np.int32(flat[m + num_classes]),
        objective_history=flat[m + num_classes + 2:],
        converged=bool(flat[m + num_classes + 1]))


@functools.lru_cache(maxsize=None)
def fused_softmax_fit_packed(mesh: Optional[Mesh], num_classes: int,
                             max_iter: int, tol: float,
                             fit_intercept: bool, standardization: bool,
                             weighted: bool = False,
                             solver: str = "fista"):
    """Multinomial analogue of ``fused_logistic_fit_packed`` — the same
    two entries (``DesignColumns`` or a packed ``Z``), one output buffer
    and per-iteration psum (and the same ``weighted`` / ``solver``
    contracts; "newton" is the L1-free block-Hessian IRLS, see
    ``_softmax_newton_core``)."""
    core = {"fista": _softmax_core,
            "newton": _softmax_newton_core}[solver]

    if mesh is None or mesh.devices.size <= 1:
        def fit(design, hyper):
            with _obs.scope("fit.pack"):
                X, y, mask, w = _split_design(design, weighted)
                n, std = _feature_stats(X, y, mask if w is None else w)
            return _pack_softmax_result(core(
                X, y, mask, hyper[0], hyper[1], n, std, num_classes,
                max_iter, tol, fit_intercept, standardization, weights=w))
    else:
        def local(Z, hyper):
            with _obs.scope("fit.pack"):
                X, y, mask, w = _split_design(Z, weighted)
                n, std = _sharded_feature_stats(X,
                                                mask if w is None else w)
            return _pack_softmax_result(core(
                X, y, mask, hyper[0], hyper[1], n, std, num_classes,
                max_iter, tol, fit_intercept, standardization,
                axis=DATA_AXIS, weights=w))

        fit = shard_map(
            local, mesh=mesh,
            in_specs=(P(DATA_AXIS), P()),
            out_specs=P())

    return serialize_collectives(jax.jit(fit), mesh)


@persistable
class LogisticRegression(Estimator):
    """Binary or multinomial logistic regression with elastic-net
    regularization (MLlib ``family`` semantics: auto / binomial /
    multinomial)."""

    weight_col = None    # back-compat default for pre-weightCol saves

    _persist_attrs = ("max_iter", "reg_param", "elastic_net_param", "tol",
                      "fit_intercept", "standardization", "threshold",
                      "family", "features_col", "label_col", "prediction_col",
                      "probability_col", "raw_prediction_col", "weight_col")

    def __init__(self, max_iter: int = 100, reg_param: float = 0.0,
                 elastic_net_param: float = 0.0, tol: float = 1e-6,
                 fit_intercept: bool = True, standardization: bool = True,
                 threshold: float = 0.5, family: str = "auto",
                 features_col: str = "features", label_col: str = "label",
                 prediction_col: str = "prediction",
                 probability_col: str = "probability",
                 raw_prediction_col: str = "rawPrediction",
                 weight_col: Optional[str] = None):
        if family not in ("auto", "binomial", "multinomial"):
            raise ValueError(f"unknown family {family!r}")
        self.max_iter = max_iter
        self.reg_param = reg_param
        self.elastic_net_param = elastic_net_param
        self.tol = tol
        self.fit_intercept = fit_intercept
        self.standardization = standardization
        self.threshold = threshold
        self.family = family
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col
        self.probability_col = probability_col
        self.raw_prediction_col = raw_prediction_col
        self.weight_col = weight_col

    # fluent setters (snake + camel)
    def set_max_iter(self, v): self.max_iter = int(v); return self
    def set_reg_param(self, v): self.reg_param = float(v); return self
    def set_elastic_net_param(self, v): self.elastic_net_param = float(v); return self
    def set_tol(self, v): self.tol = float(v); return self
    def set_fit_intercept(self, v): self.fit_intercept = bool(v); return self
    def set_standardization(self, v): self.standardization = bool(v); return self
    def set_threshold(self, v): self.threshold = float(v); return self
    def set_features_col(self, v): self.features_col = v; return self
    def set_label_col(self, v): self.label_col = v; return self
    def set_weight_col(self, v): self.weight_col = v; return self

    def set_family(self, v):
        if v not in ("auto", "binomial", "multinomial"):
            raise ValueError(f"unknown family {v!r}")
        self.family = v
        return self

    setFamily = set_family

    setMaxIter = set_max_iter
    setRegParam = set_reg_param
    setElasticNetParam = set_elastic_net_param
    setTol = set_tol
    setFitIntercept = set_fit_intercept
    setStandardization = set_standardization
    setThreshold = set_threshold
    setFeaturesCol = set_features_col
    setLabelCol = set_label_col
    setWeightCol = set_weight_col

    def get_reg_param(self): return self.reg_param
    def get_tol(self): return self.tol
    def get_threshold(self): return self.threshold

    getRegParam = get_reg_param
    getTol = get_tol
    getThreshold = get_threshold

    def _params_dict(self) -> dict:
        return {k: getattr(self, k) for k in (
            "max_iter", "reg_param", "elastic_net_param", "tol",
            "fit_intercept", "standardization", "threshold", "family",
            "features_col", "label_col", "prediction_col", "probability_col",
            "raw_prediction_col", "weight_col")}

    def fit(self, frame: Frame, mesh=None) -> "LogisticRegressionModel":

        # ONE root span per fit, opened where fit begins: fit.prepare
        # (extract, validate, pack) and fit.solve are its children, so its
        # self time is what neither explains.
        with _obs.fit_span("fit.logistic_regression",
                           fused_logistic_fit_packed,
                           fused_softmax_fit_packed,
                           max_iter=self.max_iter) as root:
            return self._fit(frame, mesh, root)

    def _fit(self, frame: Frame, mesh, root) -> "LogisticRegressionModel":
        from ..config import float_dtype
        from ..parallel.distributed import unpack_fit_result
        from ..utils.profiling import counters as _counters

        if mesh is None:
            from ..session import TpuSession

            active = TpuSession.active()
            mesh = active.mesh if active is not None else None
        if mesh is not None and mesh.devices.size <= 1:
            mesh = None
        weighted = self.weight_col is not None
        with _obs.span("fit.prepare", cat="fit") as prep:
            with _obs.span("fit.extract", cat="fit"):
                X, y, mask = _extract_xy(frame, self.features_col,
                                         self.label_col)
            prep.set(rows=int(X.shape[0]), features=int(X.shape[1]))
            w = None
            if weighted:
                # masked rows' weight values never participate (see the
                # LinearRegression weightCol note): the reduction looks
                # at valid rows only, and the packing zeroes the rest so
                # a NaN payload cannot poison it
                w = jnp.asarray(frame._column_values(self.weight_col),
                                float_dtype())
            with _obs.span("fit.validate", cat="fit") as val:
                # one small reduction on the device and a read of its few
                # scalars (base.label_stats): no n-row column comes to
                # the host. The read waits for the device to reach it —
                # whatever the frame still had queued — and no longer.
                stats = read_label_stats(label_stats(y, mask, w))
                val.set(host_read_bytes=stats.nbytes)
                if stats.rows == 0:
                    raise ValueError("LogisticRegression: no valid rows")
                # NaN and +/-inf on a valid row set label_bad
                if stats.label_bad or stats.label_min < 0:
                    raise ValueError(
                        "labels must be nonnegative integers 0..k-1")
                num_classes = int(stats.label_max) + 1
                family = self.family
                if family == "auto":
                    family = "binomial" if num_classes <= 2 \
                        else "multinomial"
                if family == "binomial" and num_classes > 2:
                    raise ValueError(
                        f"binomial family requires binary labels, found "
                        f"{num_classes} classes; use family='multinomial'")
                # NaN fails >= too (silent NaN poisoning must raise)
                if stats.weight_bad:
                    raise ValueError("weights must be nonnegative")
            design = _fit_design(X, y, mask, w, mesh)
            hyper = jnp.asarray([self.reg_param, self.elastic_net_param],
                                float_dtype())
        shards = mesh.devices.size if mesh is not None else 1
        l1_free = (self.elastic_net_param == 0.0 or self.reg_param == 0.0)

        if family == "multinomial":
            K = max(num_classes, 2)
            # Same routing as the binary path: L1-free penalties take the
            # block-Hessian Newton solver; the K(d+1) cap keeps the
            # on-device solve trivial next to the per-iteration data pass.
            sm_solver = "newton" if (l1_free
                                     and K * (X.shape[1] + 1) <= 256) \
                else "fista"
            root.set(family="multinomial", classes=K, rows=int(X.shape[0]),
                     features=int(X.shape[1]), solver=sm_solver,
                     shards=shards)
            # dispatch of the compiled fit to its result on the host (the
            # decode reads the one packed output buffer)
            with _obs.span("fit.solve", cat="solver", solver=sm_solver) as sv:
                fit_fn = fused_softmax_fit_packed(mesh, K, self.max_iter,
                                                  self.tol,
                                                  self.fit_intercept,
                                                  self.standardization,
                                                  weighted=weighted,
                                                  solver=sm_solver)
                result = unpack_softmax_result(fit_fn(design, hyper), K,
                                               X.shape[1])
                sv.set(iterations=int(result.iterations),
                       converged=bool(result.converged))
            _counters.increment("solver.fits")
            _counters.increment("solver.iterations", int(result.iterations))
            root.set(iterations=int(result.iterations),
                     converged=bool(result.converged))
            W = np.asarray(result.coefficient_matrix, np.float64)
            b = np.asarray(result.intercept_vector, np.float64)
            # Identifiability pivot (MLlib convention): the softmax loss is
            # invariant to a per-feature shift across classes; intercepts
            # are never penalized so they are always centered, coefficients
            # only when the fit was unpenalized.
            if self.fit_intercept:
                b = b - b.mean()
            if self.reg_param == 0.0:
                W = W - W.mean(axis=0, keepdims=True)
            result = SoftmaxFitResult(W, b, result.iterations,
                                      result.objective_history,
                                      result.converged)
            model = LogisticRegressionModel(
                coefficient_matrix=W, intercept_vector=b,
                params=self._params_dict())
            model._summary_source = (frame, result)
            return model

        # Solver routing (framework upgrade, solution-identical): the
        # elastic-net general case runs FISTA; an L1-free penalty
        # (elasticNetParam==0 or regParam==0 — incl. MLlib's defaults)
        # runs damped Newton/IRLS, which converges in ~5-10 fused
        # iterations instead of FISTA's O(100). Capped at d<=256 so the
        # per-iteration (d+1)^2 Hessian psum + host-free solve stays cheap.
        solver = "newton" if (l1_free and X.shape[1] <= 256) else "fista"
        root.set(family="binomial", classes=num_classes,
                 rows=int(X.shape[0]), features=int(X.shape[1]),
                 solver=solver, shards=shards)
        with _obs.span("fit.solve", cat="solver", solver=solver) as sv:
            fit_fn = fused_logistic_fit_packed(mesh, self.max_iter, self.tol,
                                               self.fit_intercept,
                                               self.standardization,
                                               weighted=weighted,
                                               solver=solver)
            result = LogisticFitResult(
                *unpack_fit_result(fit_fn(design, hyper), X.shape[1]))
            sv.set(iterations=int(result.iterations),
                   converged=bool(result.converged))
        _counters.increment("solver.fits")
        _counters.increment("solver.iterations", int(result.iterations))
        root.set(iterations=int(result.iterations),
                 converged=bool(result.converged))
        model = LogisticRegressionModel(
            coefficients=np.asarray(result.coefficients),
            intercept=float(result.intercept),
            params=self._params_dict())
        model._summary_source = (frame, result)
        return model


@persistable
class LogisticRegressionModel(Model):
    """Fitted logistic model. Binary fits expose ``coefficients`` /
    ``intercept``; multinomial fits expose ``coefficient_matrix`` (K, d) /
    ``intercept_vector`` (K,) — accessing the vector accessors on a
    multinomial model raises, exactly like MLlib."""

    def __init__(self, coefficients: Optional[np.ndarray] = None,
                 intercept: float = 0.0, params: Optional[dict] = None,
                 coefficient_matrix: Optional[np.ndarray] = None,
                 intercept_vector: Optional[np.ndarray] = None):
        if coefficient_matrix is not None:
            self._matrix = np.asarray(coefficient_matrix)
            self._intercepts = np.asarray(intercept_vector, np.float64)
            self._binary = False
        else:
            self._matrix = None
            self._intercepts = None
            self._binary = True
            self._coefficients = np.asarray(coefficients)
            self._intercept = float(intercept)
        self._params = dict(params or {})
        self._training_summary = None
        self._summary_source = None

    @property
    def is_multinomial(self) -> bool:
        return not self._binary

    @property
    def coefficients(self) -> np.ndarray:
        if not self._binary:
            raise RuntimeError(
                "coefficients is undefined for a multinomial model; "
                "use coefficient_matrix")
        return self._coefficients

    @property
    def intercept(self) -> float:
        if not self._binary:
            raise RuntimeError(
                "intercept is undefined for a multinomial model; "
                "use intercept_vector")
        return self._intercept

    @property
    def coefficient_matrix(self) -> np.ndarray:
        if self._binary:
            return self._coefficients[None, :]
        return self._matrix

    coefficientMatrix = coefficient_matrix

    @property
    def intercept_vector(self) -> np.ndarray:
        if self._binary:
            return np.asarray([self._intercept])
        return self._intercepts

    interceptVector = intercept_vector

    @property
    def num_classes(self) -> int:
        return 2 if self._binary else int(self._matrix.shape[0])

    numClasses = num_classes

    @property
    def num_features(self) -> int:
        return int(self.coefficient_matrix.shape[1])

    @property
    def threshold(self) -> float:
        return self._params.get("threshold", 0.5)

    def _margin(self, X):
        return X @ jnp.asarray(self.coefficients, X.dtype) + self.intercept

    def _margins_multi(self, X):
        W = jnp.asarray(self._matrix, X.dtype)
        b = jnp.asarray(self._intercepts, X.dtype)
        return X @ W.T + b[None, :]

    def transform(self, frame: Frame) -> Frame:
        """Append rawPrediction (margin), probability, and prediction columns
        — MLlib's classifier transform contract."""
        with _obs.span("model.transform", cat="model",
                       model="logistic_regression", rows=frame.num_slots):
            p = self._params
            X = jnp.asarray(frame._column_values(p.get("features_col", "features")),
                            float_dtype())
            if X.ndim == 1:
                X = X[:, None]
            if not self._binary:
                raw = self._margins_multi(X)
                prob = jax.nn.softmax(raw, axis=1)
                pred = jnp.argmax(raw, axis=1).astype(float_dtype())
                out = frame.with_column(
                    p.get("raw_prediction_col", "rawPrediction"), raw)
                out = out.with_column(p.get("probability_col", "probability"),
                                      prob)
                return out.with_column(p.get("prediction_col", "prediction"),
                                       pred)
            margin = self._margin(X)
            prob = jax.nn.sigmoid(margin)
            pred = (prob > self.threshold).astype(float_dtype())
            out = frame.with_column(p.get("raw_prediction_col", "rawPrediction"), margin)
            out = out.with_column(p.get("probability_col", "probability"), prob)
            return out.with_column(p.get("prediction_col", "prediction"), pred)

    def predict_raw(self, features):
        v = np.asarray(features, np.float64).reshape(-1)
        if not self._binary:
            return self._matrix.astype(np.float64) @ v + self._intercepts
        return float(v @ self.coefficients.astype(np.float64) + self.intercept)

    def predict_probability(self, features):
        raw = self.predict_raw(features)
        if not self._binary:
            e = np.exp(raw - raw.max())
            return e / e.sum()
        return float(1.0 / (1.0 + np.exp(-raw)))

    predictProbability = predict_probability

    def predict(self, features) -> float:
        with _obs.span("model.predict", cat="model",
                       model="logistic_regression", rows=1):
            if not self._binary:
                return float(np.argmax(self.predict_raw(features)))
            return (1.0 if self.predict_probability(features)
                    > self.threshold else 0.0)

    @property
    def summary(self):
        if self._training_summary is None:
            if self._summary_source is None:
                raise RuntimeError("model was not fit with summary (loaded model?)")
            frame, result = self._summary_source
            if self._binary:
                self._training_summary = \
                    BinaryLogisticRegressionTrainingSummary(self, frame,
                                                            result)
            else:
                self._training_summary = \
                    LogisticRegressionTrainingSummary(self, frame, result)
        return self._training_summary

    @property
    def has_summary(self) -> bool:
        return self._training_summary is not None or self._summary_source is not None

    hasSummary = has_summary

    def evaluate(self, frame: Frame):
        if not self._binary:
            return LogisticRegressionSummary(self, frame)
        return BinaryLogisticRegressionSummary(self, frame)

    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        write_json(os.path.join(path, "metadata.json"), {
            "class": "LogisticRegressionModel",
            "multinomial": not self._binary,
            "intercept": (self._intercept if self._binary
                          # dqlint: ok(host-sync): _intercepts is the host
                          # numpy copy materialized at fit time
                          else self._intercepts.tolist()),
            "params": self._params,
        })
        np.save(os.path.join(path, "coefficients.npy"),
                self._coefficients if self._binary else self._matrix)

    @classmethod
    def load(cls, path: str) -> "LogisticRegressionModel":
        meta = read_json(os.path.join(path, "metadata.json"))
        if meta.get("class") != "LogisticRegressionModel":
            raise ValueError(f"not a LogisticRegressionModel checkpoint: {path}")
        coef = np.load(os.path.join(path, "coefficients.npy"))
        if meta.get("multinomial"):
            return cls(coefficient_matrix=coef,
                       intercept_vector=np.asarray(meta["intercept"]),
                       params=meta.get("params"))
        return cls(coef, meta["intercept"], meta.get("params"))

    # Pipeline-persistence hooks (base.save_stage/load_stage dispatch here).
    def _save_to_dir(self, path: str) -> None:
        self.save(path)

    @classmethod
    def _load_from_dir(cls, path: str, meta: dict):
        return cls.load(path)


class BinaryLogisticRegressionSummary:
    """Evaluation over a frame's valid rows: accuracy, ROC, areaUnderROC."""

    def __init__(self, model: LogisticRegressionModel, frame: Frame):
        self._model = model
        pred_frame = model.transform(frame)
        d = pred_frame.to_pydict()
        p = model._params
        self._label = d[p.get("label_col", "label")].astype(np.float64)
        self._prob = d[p.get("probability_col", "probability")].astype(np.float64)
        self._pred = d[p.get("prediction_col", "prediction")].astype(np.float64)
        self._predictions_frame = pred_frame

    @property
    def predictions(self) -> Frame:
        return self._predictions_frame

    @property
    def accuracy(self) -> float:
        return float(np.mean(self._pred == self._label))

    @property
    def area_under_roc(self) -> float:
        """Exact AUC — delegates to the shared O(n log n) helper."""
        from .evaluation import area_under_roc

        return area_under_roc(self._label, self._prob)

    areaUnderROC = area_under_roc

    @property
    def roc(self) -> Frame:
        """(FPR, TPR) curve frame, MLlib's ``summary.roc()`` analogue."""
        from .evaluation import roc_points

        fpr, tpr = roc_points(self._label, self._prob)
        return Frame({"FPR": fpr, "TPR": tpr})

    @property
    def pr(self) -> Frame:
        """(recall, precision) curve, MLlib's ``summary.pr()``."""
        from .evaluation import pr_points

        _, precision, recall = pr_points(self._label, self._prob)
        return Frame({"recall": np.r_[0.0, recall],
                      "precision": np.r_[1.0, precision]})

    def _by_threshold(self, metric: str) -> Frame:
        from .evaluation import pr_points

        thr, precision, recall = pr_points(self._label, self._prob)
        if metric == "precision":
            vals = precision
        elif metric == "recall":
            vals = recall
        else:
            denom = np.maximum(precision + recall, 1e-30)
            vals = 2.0 * precision * recall / denom
        return Frame({"threshold": thr, metric: vals})

    @property
    def precision_by_threshold(self) -> Frame:
        return self._by_threshold("precision")

    precisionByThreshold = precision_by_threshold

    @property
    def recall_by_threshold(self) -> Frame:
        return self._by_threshold("recall")

    recallByThreshold = recall_by_threshold

    @property
    def f_measure_by_threshold(self) -> Frame:
        return self._by_threshold("F-Measure")

    fMeasureByThreshold = f_measure_by_threshold


class BinaryLogisticRegressionTrainingSummary(BinaryLogisticRegressionSummary):
    def __init__(self, model, frame, result: LogisticFitResult):
        super().__init__(model, frame)
        self._iterations = int(result.iterations)
        hist = np.asarray(result.objective_history, np.float64)
        self._objective_history = hist[: self._iterations + 1]

    @property
    def total_iterations(self) -> int:
        return self._iterations

    totalIterations = total_iterations

    @property
    def objective_history(self) -> np.ndarray:
        return self._objective_history

    objectiveHistory = objective_history


class LogisticRegressionSummary:
    """Multiclass evaluation over a frame's valid rows — MLlib's
    ``LogisticRegressionSummary``: accuracy, per-label precision/recall/F,
    weighted averages."""

    def __init__(self, model: "LogisticRegressionModel", frame: Frame):
        self._model = model
        pred_frame = model.transform(frame)
        d = pred_frame.to_pydict()
        p = model._params
        self._label = np.asarray(d[p.get("label_col", "label")], np.float64)
        self._pred = np.asarray(d[p.get("prediction_col", "prediction")],
                                np.float64)
        self._predictions_frame = pred_frame
        self._k = model.num_classes
        self._confusion_cache = None

    @property
    def predictions(self) -> Frame:
        return self._predictions_frame

    @property
    def labels(self) -> np.ndarray:
        return np.arange(self._k, dtype=np.float64)

    @property
    def accuracy(self) -> float:
        return float(np.mean(self._pred == self._label))

    def _confusion(self):
        if self._confusion_cache is None:
            k = self._k
            pred_i = self._pred.astype(np.int64)
            true_i = self._label.astype(np.int64)
            tp = np.bincount(pred_i[pred_i == true_i],
                             minlength=k)[:k].astype(np.float64)
            pred_c = np.bincount(pred_i, minlength=k)[:k].astype(np.float64)
            true_c = np.bincount(true_i, minlength=k)[:k].astype(np.float64)
            self._confusion_cache = (tp, pred_c, true_c)
        return self._confusion_cache

    @property
    def precision_by_label(self) -> np.ndarray:
        tp, pred_c, _ = self._confusion()
        return np.where(pred_c > 0, tp / np.maximum(pred_c, 1), 0.0)

    precisionByLabel = precision_by_label

    @property
    def recall_by_label(self) -> np.ndarray:
        tp, _, true_c = self._confusion()
        return np.where(true_c > 0, tp / np.maximum(true_c, 1), 0.0)

    recallByLabel = recall_by_label

    @property
    def f_measure_by_label(self) -> np.ndarray:
        p, r = self.precision_by_label, self.recall_by_label
        return np.where(p + r > 0, 2 * p * r / np.maximum(p + r, 1e-300), 0.0)

    fMeasureByLabel = f_measure_by_label

    def _weights(self):
        _, _, true_c = self._confusion()
        return true_c / max(true_c.sum(), 1.0)

    @property
    def weighted_precision(self) -> float:
        return float(self._weights() @ self.precision_by_label)

    weightedPrecision = weighted_precision

    @property
    def weighted_recall(self) -> float:
        return float(self._weights() @ self.recall_by_label)

    weightedRecall = weighted_recall

    @property
    def weighted_f_measure(self) -> float:
        return float(self._weights() @ self.f_measure_by_label)

    weightedFMeasure = weighted_f_measure


class LogisticRegressionTrainingSummary(LogisticRegressionSummary):
    def __init__(self, model, frame, result: "SoftmaxFitResult"):
        super().__init__(model, frame)
        self._iterations = int(result.iterations)
        hist = np.asarray(result.objective_history, np.float64)
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
# LinearSVC (MLlib org.apache.spark.ml.classification.LinearSVC)
# ---------------------------------------------------------------------------

@persistable
class LinearSVC(Estimator):
    """MLlib ``LinearSVC``: linear support-vector classifier, L2 penalty,
    binary 0/1 labels. Squared-hinge objective on device (see
    :func:`_svc_core`); builder surface mirrors MLlib
    (setMaxIter/setRegParam/setTol/setFitIntercept/setStandardization/
    setThreshold + the column setters)."""

    _persist_attrs = ("max_iter", "reg_param", "tol", "fit_intercept",
                      "standardization", "threshold", "features_col",
                      "label_col", "prediction_col", "raw_prediction_col")

    def __init__(self, max_iter: int = 100, reg_param: float = 0.0,
                 tol: float = 1e-6, fit_intercept: bool = True,
                 standardization: bool = True, threshold: float = 0.0,
                 features_col: str = "features", label_col: str = "label",
                 prediction_col: str = "prediction",
                 raw_prediction_col: str = "rawPrediction"):
        self.max_iter = int(max_iter)
        self.reg_param = float(reg_param)
        self.tol = float(tol)
        self.fit_intercept = bool(fit_intercept)
        self.standardization = bool(standardization)
        self.threshold = float(threshold)
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col
        self.raw_prediction_col = raw_prediction_col

    def set_max_iter(self, v):
        self.max_iter = int(v)
        return self

    def set_reg_param(self, v):
        self.reg_param = float(v)
        return self

    def set_tol(self, v):
        self.tol = float(v)
        return self

    def set_fit_intercept(self, v):
        self.fit_intercept = bool(v)
        return self

    def set_standardization(self, v):
        self.standardization = bool(v)
        return self

    def set_threshold(self, v):
        self.threshold = float(v)
        return self

    def set_features_col(self, v):
        self.features_col = v
        return self

    def set_label_col(self, v):
        self.label_col = v
        return self

    setMaxIter = set_max_iter
    setRegParam = set_reg_param
    setTol = set_tol
    setFitIntercept = set_fit_intercept
    setStandardization = set_standardization
    setThreshold = set_threshold
    setFeaturesCol = set_features_col
    setLabelCol = set_label_col

    def fit(self, frame: Frame, mesh=None) -> "LinearSVCModel":
        with _obs.fit_span("fit.linear_svc", fused_svc_fit_packed,
                           max_iter=self.max_iter) as root:
            return self._fit(frame, mesh, root)

    def _fit(self, frame: Frame, mesh, root) -> "LinearSVCModel":
        from ..parallel.distributed import unpack_fit_result
        from ..parallel.mesh import normalize_mesh

        if mesh is None:
            from ..session import TpuSession

            active = TpuSession.active()
            mesh = active.mesh if active is not None else None
        mesh = normalize_mesh(mesh)
        with _obs.span("fit.prepare", cat="fit") as prep:
            with _obs.span("fit.extract", cat="fit"):
                X, y, mask = _extract_xy(frame, self.features_col,
                                         self.label_col)
            prep.set(rows=int(X.shape[0]), features=int(X.shape[1]))
            with _obs.span("fit.validate", cat="fit") as val:
                stats = read_label_stats(label_stats(y, mask))
                val.set(host_read_bytes=stats.nbytes)
                if stats.rows == 0:
                    raise ValueError("LinearSVC: no valid rows")
                # integers within [0, 1] are 0 and 1
                if stats.label_bad or stats.label_min < 0 \
                        or stats.label_max > 1:
                    raise ValueError("LinearSVC requires binary 0/1 labels")
            design = _fit_design(X, y, mask, None, mesh)
            hyper = jnp.asarray([self.reg_param, 0.0], float_dtype())
        root.set(rows=int(X.shape[0]), features=int(X.shape[1]),
                 solver="fista")
        with _obs.span("fit.solve", cat="solver", solver="fista") as sv:
            fit_fn = fused_svc_fit_packed(mesh, self.max_iter, self.tol,
                                          self.fit_intercept,
                                          self.standardization)
            r = unpack_fit_result(fit_fn(design, hyper), X.shape[1])
            sv.set(iterations=int(r.iterations),
                   converged=bool(r.converged))
        root.set(iterations=int(r.iterations), converged=bool(r.converged))
        iters = int(r.iterations)
        # truncate the scan's padded tail (post-convergence repeats), the
        # LogisticRegressionTrainingSummary convention
        history = np.asarray(r.objective_history,
                             np.float64)[: iters + 1].tolist()
        return LinearSVCModel(np.asarray(r.coefficients),
                              float(r.intercept),
                              self._params_dict(),
                              objective_history=history,
                              iterations=iters)

    def _params_dict(self):
        return {k: getattr(self, k) for k in self._persist_attrs}


@persistable
class LinearSVCModel(Model):
    """Fitted linear SVC: ``rawPrediction`` = [−margin, margin];
    ``prediction`` thresholds the margin at ``threshold`` (MLlib)."""

    _persist_attrs = ("coefficients", "intercept", "_params",
                      "objective_history", "iterations")

    def __init__(self, coefficients, intercept, params=None,
                 objective_history=None, iterations=0):
        self.coefficients = np.asarray(coefficients)
        self.intercept = float(intercept)
        self._params = dict(params or {})
        self.objective_history = list(objective_history or [])
        self.iterations = int(iterations)

    def _p(self, k, default=None):
        return self._params.get(k, default)

    @property
    def num_features(self):
        return int(self.coefficients.shape[0])

    numFeatures = num_features
    getThreshold = lambda self: self._p("threshold", 0.0)

    def _margin(self, X):
        Xd = jnp.asarray(X, float_dtype())
        if Xd.ndim == 1:
            Xd = Xd[:, None]
        return Xd @ jnp.asarray(self.coefficients, Xd.dtype) + self.intercept

    def transform(self, frame: Frame) -> Frame:
        m = self._margin(frame._column_values(
            self._p("features_col", "features")))
        raw = jnp.stack([-m, m], axis=1)
        pred = (m > self._p("threshold", 0.0)).astype(float_dtype())
        out = frame.with_column(
            self._p("raw_prediction_col", "rawPrediction"), raw)
        return out.with_column(self._p("prediction_col", "prediction"),
                               pred)

    def predict(self, features) -> float:
        x = np.asarray(features, np.float64).reshape(1, -1)
        return float(np.asarray(self._margin(x))[0]
                     > self._p("threshold", 0.0))


# ---------------------------------------------------------------------------
# NaiveBayes (MLlib org.apache.spark.ml.classification.NaiveBayes)
# ---------------------------------------------------------------------------

def _nb_sufficient_stats(X, y, w, num_classes: int, psum_axis=None):
    """Per-class label counts and feature sums — one masked one-hot matmul
    (MXU), the whole NaiveBayes 'fit pass' in a single fused kernel.
    ``psum_axis`` reduces the (k,) + (k, d) statistics over the mesh's
    data axis (the treeAggregate analogue, SURVEY.md §3.3)."""
    onehot = jax.nn.one_hot(y.astype(jnp.int32), num_classes,
                            dtype=X.dtype) * w[:, None]    # (n, k)
    class_count = jnp.sum(onehot, axis=0)                  # (k,)
    feat_sum = onehot.T @ X                                # (k, d)
    if psum_axis is not None:
        class_count, feat_sum = jax.lax.psum((class_count, feat_sum),
                                             psum_axis)
    return class_count, feat_sum


@functools.lru_cache(maxsize=None)
def _nb_stats_fn(mesh, num_classes: int):
    """Jitted (and, under a mesh, shard_map'd) NaiveBayes statistics pass,
    cached per (mesh, k)."""
    if mesh is None:
        # close over num_classes — jit would trace a partial-bound int
        return jax.jit(
            lambda X, y, w: _nb_sufficient_stats(X, y, w, num_classes))

    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, shard_map

    return serialize_collectives(jax.jit(shard_map(
        lambda X, y, w: _nb_sufficient_stats(X, y, w, num_classes,
                                             DATA_AXIS),
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P()))), mesh)


@persistable
class NaiveBayes(Estimator):
    """MLlib ``NaiveBayes``: multinomial (default) or bernoulli model with
    Laplace ``smoothing`` (default 1.0). Labels must be 0..k-1 doubles (the
    StringIndexer convention); multinomial requires nonnegative features,
    bernoulli requires 0/1 features — both validated like Spark.

    TPU-first: the entire fit is one one-hot matmul for the per-class
    sufficient statistics (no per-row loop), and prediction is
    ``pi + X @ thetaᵀ`` — a single MXU matmul batched over rows."""

    weight_col = None    # back-compat default for pre-weightCol saves

    _persist_attrs = ('smoothing', 'model_type', 'features_col', 'label_col',
                      'prediction_col', 'probability_col',
                      'raw_prediction_col', 'weight_col')

    def __init__(self, smoothing: float = 1.0, model_type: str = "multinomial",
                 features_col: str = "features", label_col: str = "label",
                 prediction_col: str = "prediction",
                 probability_col: str = "probability",
                 raw_prediction_col: str = "rawPrediction",
                 weight_col: Optional[str] = None):
        if model_type not in ("multinomial", "bernoulli"):
            raise ValueError(f"model_type={model_type!r}")
        if smoothing < 0:
            raise ValueError("smoothing must be >= 0")
        self.smoothing = float(smoothing)
        self.model_type = model_type
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col
        self.probability_col = probability_col
        self.raw_prediction_col = raw_prediction_col
        self.weight_col = weight_col

    def set_smoothing(self, v):
        if v < 0:
            raise ValueError("smoothing must be >= 0")
        self.smoothing = float(v)
        return self

    setSmoothing = set_smoothing

    def set_model_type(self, v):
        if v not in ("multinomial", "bernoulli"):
            raise ValueError(f"model_type={v!r}")
        self.model_type = v
        return self

    setModelType = set_model_type

    def set_features_col(self, v):
        self.features_col = v
        return self

    setFeaturesCol = set_features_col

    def set_label_col(self, v):
        self.label_col = v
        return self

    setLabelCol = set_label_col

    def set_weight_col(self, v):
        self.weight_col = v
        return self

    setWeightCol = set_weight_col

    def fit(self, frame: Frame, mesh=None) -> "NaiveBayesModel":
        from ..parallel.mesh import normalize_mesh

        mesh = normalize_mesh(mesh)
        dt = np.dtype(float_dtype())
        X = np.asarray(frame._column_values(self.features_col), dt)
        if X.ndim == 1:
            X = X[:, None]
        y = np.asarray(frame._column_values(self.label_col), dt)
        mask = np.asarray(frame.mask)
        yv = y[mask]
        if len(yv) == 0:
            raise ValueError("NaiveBayes: no valid rows")
        if np.any(yv < 0) or np.any(yv != np.floor(yv)):
            raise ValueError("labels must be nonnegative integers 0..k-1")
        num_classes = int(yv.max()) + 1
        Xv = X[mask]
        if self.model_type == "multinomial":
            if not np.all(Xv >= 0):   # NaN fails >= too (Spark rejects it)
                raise ValueError("multinomial NaiveBayes requires "
                                 "nonnegative features")
        else:
            if not np.all((Xv == 0) | (Xv == 1)):
                raise ValueError("bernoulli NaiveBayes requires 0/1 features")

        from ..parallel.distributed import pad_and_shard_rows

        Xh = X if self.model_type == "multinomial" else (X > 0).astype(dt)
        # masked slots may hold NaN features/labels (dropna/filter keep
        # values in place); zero them — 0-weight × NaN would still poison
        # the stats matmul (0 * NaN = NaN)
        Xh = np.where(mask[:, None], Xh, 0.0)
        yh = np.where(mask, y, 0.0)
        row_w = mask.astype(dt)
        if self.weight_col is not None:
            # instance weights (MLlib weightCol): the per-class sufficient
            # statistics are one weighted one-hot matmul, so weights slot
            # straight into the row-weight vector; masked slots stay 0
            w = np.asarray(frame._column_values(self.weight_col), dt)
            if not np.all(w[mask] >= 0):   # NaN fails >= too
                raise ValueError("weights must be nonnegative")
            row_w = np.where(mask, w, 0.0).astype(dt)
        Xd, yd, wd = pad_and_shard_rows(mesh, Xh, yh, row_w)
        class_count, feat_sum = _nb_stats_fn(mesh, num_classes)(Xd, yd, wd)
        class_count = np.asarray(class_count, np.float64)
        feat_sum = np.asarray(feat_sum, np.float64)
        lam = self.smoothing
        n = class_count.sum()
        pi = np.log(class_count + lam) - np.log(n + num_classes * lam)
        if self.model_type == "multinomial":
            # log P(feature j | class c), normalized over the feature axis
            row_tot = feat_sum.sum(axis=1, keepdims=True)
            theta = np.log(feat_sum + lam) \
                - np.log(row_tot + lam * X.shape[1])
        else:
            # log P(x_j = 1 | class c); the complement handled at predict
            theta = np.log(feat_sum + lam) \
                - np.log(class_count[:, None] + 2.0 * lam)
        return NaiveBayesModel(pi, theta, self.model_type,
                               self._params_dict())

    def _params_dict(self):
        return {k: getattr(self, k) for k in (
            "smoothing", "model_type", "features_col", "label_col",
            "prediction_col", "probability_col", "raw_prediction_col",
            "weight_col")}


@persistable
class NaiveBayesModel(Model):
    """``pi`` (k,) log class priors; ``theta`` (k, d) log feature
    likelihoods. Prediction is one matmul; bernoulli adds the complement
    term exactly as MLlib's BernoulliNB does."""

    _persist_attrs = ('pi', 'theta', 'model_type', '_params')

    def __init__(self, pi, theta, model_type, params=None):
        self.pi = np.asarray(pi)
        self.theta = np.asarray(theta)
        self.model_type = model_type
        self._params = dict(params or {})

    @property
    def num_classes(self):
        return int(self.pi.shape[0])

    numClasses = num_classes

    @property
    def num_features(self):
        return int(self.theta.shape[1])

    numFeatures = num_features

    def _raw(self, X):
        pi = jnp.asarray(self.pi, X.dtype)
        theta = jnp.asarray(self.theta, X.dtype)
        if self.model_type == "multinomial":
            return pi + X @ theta.T
        Xb = (X > 0).astype(X.dtype)
        neg = jnp.log1p(-jnp.exp(jnp.minimum(theta, -1e-7)))   # log(1-p)
        return pi + jnp.sum(neg, axis=1) + Xb @ (theta - neg).T

    def transform(self, frame: Frame) -> Frame:
        p = self._params
        X = jnp.asarray(frame._column_values(p.get("features_col",
                                                   "features")),
                        float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        raw = self._raw(X)
        prob = jax.nn.softmax(raw, axis=1)
        pred = jnp.argmax(raw, axis=1).astype(float_dtype())
        out = frame.with_column(p.get("raw_prediction_col", "rawPrediction"),
                                raw)
        out = out.with_column(p.get("probability_col", "probability"), prob)
        return out.with_column(p.get("prediction_col", "prediction"), pred)

    def predict(self, features) -> float:
        x = jnp.asarray(np.asarray(features,
                                   np.dtype(float_dtype())).reshape(1, -1))
        return float(host_fetch(jnp.argmax(self._raw(x), axis=1))[0])


# ---------------------------------------------------------------------------
# OneVsRest (MLlib org.apache.spark.ml.classification.OneVsRest)
# ---------------------------------------------------------------------------

@persistable
class OneVsRest(Estimator):
    """MLlib ``OneVsRest``: reduce multiclass to k independent binary fits
    of any binary classifier estimator. The k fits are embarrassingly
    parallel and share the feature matrix already resident in HBM."""

    def __init__(self, classifier=None, features_col: str = "features",
                 label_col: str = "label",
                 prediction_col: str = "prediction"):
        self.classifier = classifier
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col

    def set_classifier(self, v):
        self.classifier = v
        return self

    setClassifier = set_classifier

    # composite persistence: the inner classifier is itself a stage
    def _save_to_dir(self, path: str) -> None:
        from .base import save_stage

        write_json(os.path.join(path, "metadata.json"),
                   {"class": "OneVsRest",
                    "features_col": self.features_col,
                    "label_col": self.label_col,
                    "prediction_col": self.prediction_col,
                    "has_classifier": self.classifier is not None})
        if self.classifier is not None:
            save_stage(self.classifier, os.path.join(path, "classifier"))

    @classmethod
    def _load_from_dir(cls, path: str, meta: dict) -> "OneVsRest":
        from .base import load_stage

        clf = load_stage(os.path.join(path, "classifier")) \
            if meta.get("has_classifier") else None
        return cls(clf, meta["features_col"], meta["label_col"],
                   meta["prediction_col"])

    def fit(self, frame: Frame, mesh=None) -> "OneVsRestModel":
        if self.classifier is None:
            raise ValueError("OneVsRest: classifier not set")
        import copy
        import inspect

        y = np.asarray(frame._column_values(self.label_col), np.float64)
        mask = np.asarray(frame.mask)
        yv = y[mask]
        if len(yv) == 0:
            raise ValueError("OneVsRest: no valid rows")
        if np.any(yv < 0) or np.any(yv != np.floor(yv)):
            raise ValueError("labels must be nonnegative integers 0..k-1")
        k = int(yv.max()) + 1
        models = []
        for c in range(k):
            binary = frame.with_column(
                self.label_col,
                jnp.asarray((y == c).astype(np.dtype(float_dtype()))))
            est = copy.deepcopy(self.classifier)
            if hasattr(est, "set_features_col"):
                est.set_features_col(self.features_col)
            if hasattr(est, "set_label_col"):
                est.set_label_col(self.label_col)
            # pass mesh only to estimators whose fit accepts it (a bare
            # try/except would swallow TypeErrors raised inside fit)
            if "mesh" in inspect.signature(est.fit).parameters:
                models.append(est.fit(binary, mesh=mesh))
            else:
                models.append(est.fit(binary))
        return OneVsRestModel(models, self.features_col,
                              self.prediction_col)


@persistable
class OneVsRestModel(Model):
    """k fitted binary models; prediction = argmax of their scores (the
    probability-of-positive column when available, else rawPrediction)."""

    def __init__(self, models, features_col="features",
                 prediction_col="prediction"):
        self.models = list(models)
        self.features_col = features_col
        self.prediction_col = prediction_col

    @property
    def num_classes(self):
        return len(self.models)

    numClasses = num_classes

    def _scores(self, frame: Frame):
        cols = []
        for m in self.models:
            out = m.transform(frame)
            p = getattr(m, "_params", {})
            prob_col = p.get("probability_col", "probability")
            raw_col = p.get("raw_prediction_col", "rawPrediction")
            name = prob_col if prob_col in out.columns else raw_col
            v = jnp.asarray(out._column_values(name))
            if v.ndim == 2:   # [P(neg), P(pos)] or [-margin, margin]
                v = v[:, -1]
            cols.append(v)
        return jnp.stack(cols, axis=1)

    def transform(self, frame: Frame) -> Frame:
        scores = self._scores(frame)
        pred = jnp.argmax(scores, axis=1).astype(float_dtype())
        return frame.with_column(self.prediction_col, pred)

    def _save_to_dir(self, path: str) -> None:
        import os

        from .base import save_stage, write_json

        write_json(os.path.join(path, "metadata.json"),
                   {"class": "OneVsRestModel",
                    "n": len(self.models),
                    "features_col": self.features_col,
                    "prediction_col": self.prediction_col})
        for i, m in enumerate(self.models):
            save_stage(m, os.path.join(path, f"model_{i}"))

    @classmethod
    def _load_from_dir(cls, path: str, meta: dict) -> "OneVsRestModel":
        import os

        from .base import load_stage

        models = [load_stage(os.path.join(path, f"model_{i}"))
                  for i in range(meta["n"])]
        return cls(models, meta["features_col"], meta["prediction_col"])
