"""Solver-level tests on synthetic multi-feature problems (beyond the 1-D
reference datasets): FISTA↔OWLQN agreement, L-BFGS history wrap-around,
constant features, and moment unpacking."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdq4ml_tpu.models.owlqn import owlqn_solve
from sparkdq4ml_tpu.models.solvers import (augmented_gram, fista_solve,
                                           normal_solve, resolve_solver,
                                           unpack_moments)


def _problem(d=5, n=400, rho=0.6, seed=0):
    """Correlated design so the solver needs many iterations."""
    rng = np.random.default_rng(seed)
    L = np.linalg.cholesky(rho * np.ones((d, d)) + (1 - rho) * np.eye(d))
    X = rng.normal(size=(n, d)) @ L.T
    w_true = np.asarray([3.0, -2.0, 0.0, 0.5, 0.0])[:d]
    y = X @ w_true + 1.7 + 0.1 * rng.normal(size=n)
    mask = np.ones(n, bool)
    return (jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask))


class TestOwlqnWraparound:
    def test_owlqn_matches_fista_beyond_history_window(self):
        """>10 iterations forces the rolling L-BFGS buffer to wrap; the
        two-loop recursion must keep visiting pairs newest→oldest."""
        X, y, mask = _problem()
        A = augmented_gram(X, y, mask)
        f = fista_solve(A, 0.3, 0.5, max_iter=500, tol=1e-14)
        o = owlqn_solve(A, 0.3, 0.5, max_iter=60, tol=1e-14)
        assert int(o.iterations) > 10  # must actually exercise the wrap
        np.testing.assert_allclose(np.asarray(o.coefficients),
                                   np.asarray(f.coefficients), atol=1e-6)

    def test_owlqn_sparsity_pattern(self):
        """Strong L1 must zero out the null coefficients exactly."""
        X, y, mask = _problem()
        A = augmented_gram(X, y, mask)
        o = owlqn_solve(A, 0.5, 1.0, max_iter=100, tol=1e-13)
        coef = np.asarray(o.coefficients)
        f = fista_solve(A, 0.5, 1.0, max_iter=2000, tol=1e-15)
        np.testing.assert_allclose(coef, np.asarray(f.coefficients), atol=1e-6)
        assert (coef == 0.0).any()  # lasso at this strength kills weak features


class TestMoments:
    def test_unpack_matches_numpy(self):
        X, y, mask = _problem(d=3)
        A = augmented_gram(X, y, mask)
        m = unpack_moments(A)
        Xh, yh = np.asarray(X), np.asarray(y)
        np.testing.assert_allclose(float(m.n), len(yh))
        np.testing.assert_allclose(np.asarray(m.mean_x), Xh.mean(0), rtol=1e-9)
        np.testing.assert_allclose(np.asarray(m.std_x), Xh.std(0, ddof=1), rtol=1e-9)
        np.testing.assert_allclose(float(m.std_y), yh.std(ddof=1), rtol=1e-9)

    def test_masked_moments_ignore_invalid_rows(self):
        X, y, _ = _problem(d=2)
        mask = np.zeros(X.shape[0], bool)
        mask[:100] = True
        A = augmented_gram(X, y, jnp.asarray(mask))
        m = unpack_moments(A)
        np.testing.assert_allclose(np.asarray(m.mean_x),
                                   np.asarray(X)[:100].mean(0), rtol=1e-9)

    def test_constant_feature_gets_zero_coef(self):
        n = 50
        rng = np.random.default_rng(1)
        X = np.c_[rng.normal(size=n), np.full(n, 7.0)]  # second col constant
        y = 2.0 * X[:, 0] + 3.0
        A = augmented_gram(jnp.asarray(X), jnp.asarray(y),
                           jnp.ones(n, jnp.bool_))
        for result in (fista_solve(A, 0.1, 1.0, max_iter=200),
                       normal_solve(A, 0.0),
                       owlqn_solve(A, 0.1, 1.0, max_iter=50)):
            coef = np.asarray(result.coefficients)
            assert coef[1] == 0.0
            assert np.isfinite(coef).all()


class TestMultiFeatureNormal:
    def test_normal_equals_numpy_lstsq(self):
        X, y, mask = _problem(d=4)
        A = augmented_gram(X, y, mask)
        r = normal_solve(A, 0.0)
        Xh = np.c_[np.asarray(X), np.ones(X.shape[0])]
        w, *_ = np.linalg.lstsq(Xh, np.asarray(y), rcond=None)
        np.testing.assert_allclose(np.asarray(r.coefficients), w[:-1], rtol=1e-7)
        assert float(r.intercept) == pytest.approx(w[-1], rel=1e-7)


class TestResolveSolver:
    def test_auto_routes(self):
        assert resolve_solver("auto", 0.0, 0.0) == "normal"
        assert resolve_solver("auto", 1.0, 0.0) == "normal"   # pure ridge
        assert resolve_solver("auto", 1.0, 0.5) == "fista"
        assert resolve_solver("lbfgs", 1.0, 1.0) == "owlqn"

    def test_normal_with_l1_rejected(self):
        with pytest.raises(ValueError):
            resolve_solver("normal", 1.0, 1.0)


class TestPsumValueAndGrad:
    """``psum_value_and_grad`` under ``jax.shard_map(check_vma=True)``:
    autodiff already reduces the cotangent of the replicated params, so
    the sharded gradient IS the single-device gradient — checked on the
    gradient itself, not through Adam (whose scale invariance hides a
    device-count factor to 1e-5)."""

    def test_sharded_gradient_equals_single_device_exactly(self):
        from jax.sharding import PartitionSpec as P

        from conftest import assert_devices
        from sparkdq4ml_tpu.models.solvers import psum_value_and_grad
        from sparkdq4ml_tpu.parallel.mesh import (DATA_AXIS, make_mesh,
                                                  shard_map)

        assert_devices(4)
        mesh = make_mesh(4)
        # small integers: every product and partial sum is exact, so the
        # two reduction orders agree to the last bit
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.integers(-3, 4, (32, 3)), jnp.float64)
        y = jnp.asarray(rng.integers(-3, 4, (32,)), jnp.float64)
        w0 = jnp.asarray([1.0, -2.0, 3.0])

        def value_and_grad(Xs, ys, axis, nshards):
            def local(w):     # data term + a replicated (pre-divided) term
                return jnp.sum((Xs @ w - ys) ** 2) + jnp.sum(w * w) / nshards
            return psum_value_and_grad(local, axis)(w0)

        v1, g1 = value_and_grad(X, y, None, 1.0)
        v4, g4 = jax.jit(shard_map(
            lambda Xs, ys: value_and_grad(Xs, ys, DATA_AXIS, 4.0),
            mesh=mesh, in_specs=(P(DATA_AXIS, None), P(DATA_AXIS)),
            out_specs=P()))(X, y)
        np.testing.assert_array_equal(np.asarray(g4), np.asarray(g1))
        assert float(v4) == float(v1)
        assert float(jnp.max(jnp.abs(g1))) > 0
