"""catering-dq-lasso: the table from the seed, and its plain float64 reference.

Distribution of ``chip_smoke.py:write_catering_csv`` (copied, not imported):
guest uniform 1..40, price = round(max(5.2 guest + 12 + N(0, 8), 1), 2), with
one change: the price level rises along the table (``offset_drift``: the 12
goes from 12 - drift at the first row to 12 + drift at the last), so that a
pass that leaves a block of rows out fits another intercept, which the
comparison of coefficients sees; an i.i.d. table would hide it. The table is
made on the device in one jitted call and pulled to the host once, after the
window, for the reference. The reference is numpy only. ``q``
rounds every stored intermediate: the identity gives the float64 reference,
``refmath.round_bf16`` the lower-precision control.
"""

import numpy as np


def make_table(cfg, seed, rows=None):
    """{"guest": int32 device column, "price": float32 device column}."""
    import jax
    import jax.numpy as jnp

    n = int(rows or cfg["rows"])
    g = cfg["generator"]

    @jax.jit
    def generate(key):
        kg, kp = jax.random.split(key)
        guest = jax.random.randint(kg, (n,), g["guest_min"],
                                   g["guest_max"] + 1, jnp.int32)
        position = jax.lax.iota(jnp.float32, n) * (2.0 / n) - 1.0   # -1 .. 1
        raw = (g["slope"] * guest.astype(jnp.float32) + g["offset"]
               + g["offset_drift"] * position
               + g["noise_sigma"] * jax.random.normal(kp, (n,), jnp.float32))
        price = jnp.round(jnp.maximum(raw, g["price_floor"]) * 100.0) / 100.0
        return {"guest": guest, "price": price}

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.block_until_ready(generate(key))


def table_bytes(cfg, rows=None):
    """Bytes of the input columns one job reads (guest int32, price f32)."""
    return int(rows or cfg["rows"]) * 8


CENT_BINS = 1 << 16


def tabulate(guest, price):
    """The table as counts[guest, cents] plus the float32 price each cents
    bin holds: lossless for two-decimal prices, which is checked (every row's
    price is the value of its bin), and 3 passes over 1.2e8 rows where a
    sort or 40 masked passes took minutes. Everything below is float64
    arithmetic on this small table."""
    cents = np.rint(price.astype(np.float32) * np.float32(100.0)) \
        .astype(np.int64)
    if cents.min() < 0 or cents.max() >= CENT_BINS or guest.min() < 0:
        raise ValueError("price or guest outside the tabulated range")
    value = np.zeros(CENT_BINS, np.float32)
    value[cents] = price
    if not np.array_equal(value[cents], price):
        raise ValueError("prices are not one float32 value per cent")
    groups = int(guest.max()) + 1
    counts = np.bincount(guest.astype(np.int64) * CENT_BINS + cents,
                         minlength=groups * CENT_BINS)
    return counts.reshape(groups, CENT_BINS), value.astype(np.float64)


def rules(cfg, counts, value, q=None):
    """Both DQ rules and both SQL filters on the tabulated rows: the counts
    kept after rule 1 and after both, as tables like ``counts``."""
    q = q or (lambda v: v)
    r = cfg["rules"]
    guest = q(np.arange(counts.shape[0], dtype=np.float64))[:, None]
    price = q(value)[None, :]
    keep1 = ~(price < r["minimumPriceRule"]["min_price"])
    bad2 = ((guest < r["priceCorrelationRule"]["max_guests"])
            & (price > r["priceCorrelationRule"]["max_price"]))
    return counts * keep1, counts * (keep1 & ~bad2)


def moments(kept, value, q=None):
    """n and the means of x, y, x^2, y^2, xy over the kept rows (x guest,
    y price)."""
    q = q or (lambda v: v)
    x = q(np.arange(kept.shape[0], dtype=np.float64))[:, None]
    y = q(value)[None, :]
    n = float(kept.sum())
    return (n,) + tuple(q(float((kept * term).sum()) / n) for term in
                        (x + 0 * y, y + 0 * x, q(x * x) + 0 * y,
                         q(y * y) + 0 * x, q(x * y)))


def lasso(cfg, kept, value, q=None):
    """Closed form of the one-feature MLlib Lasso (standardised space,
    sample standard deviations, regParam / std_y as the L1 weight):
    (coefficient, intercept). The float64 reference centres before it
    squares; with ``q`` the moments are taken uncentred and rounded, as a
    one-pass program in that precision would hold them."""
    reg = float(cfg["estimator"]["reg_param"])
    n, mx, my, sxx, syy, sxy = moments(kept, value, q)
    f = n / (n - 1.0)
    if q is None:
        x = np.arange(kept.shape[0], dtype=np.float64)[:, None] - mx
        y = value[None, :] - my
        sx = np.sqrt(f * float((kept * (x * x)).sum()) / n)
        sy = np.sqrt(f * float((kept * (y * y)).sum()) / n)
        b = float((kept * (x * y)).sum()) / (n * sx * sy)
    else:
        sx = q(np.sqrt(max(q(f * q(sxx - q(mx * mx))), 1e-30)))
        sy = q(np.sqrt(max(q(f * q(syy - q(my * my))), 1e-30)))
        b = q(q(sxy - q(mx * my)) / q(sx * sy))
    g = (n - 1.0) / n
    w = np.sign(b) * max(abs(b) - reg / sy, 0.0) / g
    coef = w * sy / sx
    return float(coef), float(my - coef * mx)


def rmse(kept, value, coef, icpt, q=None):
    """Root of the mean squared residual of coef * guest + icpt over the
    kept rows."""
    q = q or (lambda v: v)
    x = q(np.arange(kept.shape[0], dtype=np.float64))[:, None]
    resid = q(q(q(coef) * x + q(icpt)) - q(value)[None, :])
    return float(q(np.sqrt(q(float((kept * q(resid * resid)).sum())
                             / float(kept.sum())))))
