"""higgs-logistic: the table from the seed, and its plain float64 reference.

The table is made on the device in one jitted call (``make_table``) and is
pulled to the host once, after the window, for the reference. The reference
is numpy only: it imports nothing of the program and takes nothing the
program made. ``q`` rounds every stored intermediate; the identity gives the
float64 reference, ``refmath.round_bf16`` the lower-precision control.
"""

import numpy as np

BLOCK = 1 << 18


def column_names(cfg):
    return [f"x{j}" for j in range(cfg["features"])]


def make_table(cfg, seed, rows=None):
    """{name: device column}: 28 float32 features and the float32 label."""
    import jax
    import jax.numpy as jnp

    n, d = int(rows or cfg["rows"]), int(cfg["features"])
    a = cfg["assumed"]
    sigma = float(a["positive_sigma"])
    positive = np.zeros((d, 1), bool)
    positive[a["positive_columns"]] = True
    beta = np.asarray(a["beta"], np.float32)
    intercept = float(a["intercept"])

    @jax.jit
    def generate(key):
        kx, ku = jax.random.split(key)
        z = jax.random.normal(kx, (d, n), jnp.float32)
        x = jnp.where(positive, jnp.exp(sigma * z - 0.5 * sigma * sigma), z)
        margin = jnp.asarray(beta) @ x + intercept
        label = (jax.random.uniform(ku, (n,), jnp.float32)
                 < jax.nn.sigmoid(margin)).astype(jnp.float32)
        cols = {name: x[j] for j, name in enumerate(column_names(cfg))}
        cols["label"] = label
        return cols

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.block_until_ready(generate(key))


def table_bytes(cfg, rows=None):
    """Bytes of the input columns one job reads (features + label)."""
    return int(rows or cfg["rows"]) * (int(cfg["features"]) + 1) * 4


def _sigmoid(m):
    return 1.0 / (1.0 + np.exp(-m))


def _block(cols, lo, hi, q):
    """Rows lo..hi as a float64 (d + 1, rows) block, the last row ones:
    column-major, so that every copy and every product is contiguous (a
    row-major (rows, d) stack of 28 masked columns took 32 s alone)."""
    Xt = np.empty((len(cols) + 1, hi - lo))
    for j, col in enumerate(cols):
        Xt[j] = q(col[lo:hi])
    Xt[-1] = 1.0
    return Xt


def logistic_mle(cols, y, keep, cfg, q=None, iters=25, tol=1e-6):
    """Unpenalised logistic MLE over the rows where ``keep``, by Newton's
    method in blocks of rows. ``cols`` are the d float32 feature columns at
    full length, ``y`` the labels. Started from the generator's coefficients
    (they are the configuration's, not the program's); Newton converges
    quadratically, so a last step under ``tol`` leaves an error near tol^2.
    Returns (coef, intercept)."""
    rounded = q is not None
    q = q or (lambda v: v)
    n, d = y.shape[0], len(cols)
    w = q(np.append(np.asarray(cfg["assumed"]["beta"], np.float64),
                    float(cfg["assumed"]["intercept"])))
    for _ in range(8 if rounded else iters):
        g = np.zeros(d + 1)
        H = np.zeros((d + 1, d + 1))
        for lo in range(0, n, BLOCK):
            hi = min(lo + BLOCK, n)
            Xt = _block(cols, lo, hi, q)
            k = keep[lo:hi]
            p = q(_sigmoid(q(w @ Xt)))
            g += Xt @ (k * q(p - y[lo:hi]))
            H += q(Xt * (k * q(p * (1.0 - p)))) @ Xt.T
        step = np.linalg.solve(q(H), q(g))
        w = q(w - step)
        if not rounded and np.max(np.abs(step)) < tol:
            break
    return w[:d], float(w[d])


def scores(cols, coef, intercept, q=None):
    """P(label = 1) per row under (coef, intercept), every row of ``cols``."""
    q = q or (lambda v: v)
    n = cols[0].shape[0]
    w = q(np.append(coef, intercept))
    out = np.empty(n)
    for lo in range(0, n, BLOCK):
        hi = min(lo + BLOCK, n)
        out[lo:hi] = q(_sigmoid(q(w @ _block(cols, lo, hi, q))))
    return out
