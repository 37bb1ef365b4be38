"""``Frame.stat`` — Spark's ``DataFrameStatFunctions`` equivalent.

Thematically this is the reference's own subject: its second DQ rule is a
*price correlation* plausibility check (`PriceCorrelationDataQualityService
.java:5-10`), and Spark users inspect exactly these statistics
(``df.stat.corr("guest", "price")``) when designing such rules.

All statistics are mask-weighted single-pass device reductions — filtered
rows never contribute (SURVEY.md §7 "Masked-filter semantics")."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..config import float_dtype


@jax.jit
def _corr_cov(a, b, w):
    """Mask-weighted Pearson correlation and sample covariance, one pass."""
    n = jnp.sum(w)
    ma = jnp.sum(a * w) / n
    mb = jnp.sum(b * w) / n
    da = (a - ma) * w
    db = (b - mb) * w
    cov = jnp.sum(da * db) / jnp.maximum(n - 1.0, 1.0)
    va = jnp.sum(da * da) / jnp.maximum(n - 1.0, 1.0)
    vb = jnp.sum(db * db) / jnp.maximum(n - 1.0, 1.0)
    denom = jnp.sqrt(va * vb)
    corr = jnp.where(denom > 0, cov / denom, jnp.nan)
    return corr, cov


def _scalar(x, site: str) -> float:
    """A device scalar as a float: a counted frame host boundary and one
    host read."""
    from ..utils.observability import host_reading
    from ..utils.profiling import counters

    counters.increment("frame.host_sync")
    with host_reading(site) as rd:
        out = float(x)
        rd.done(x.dtype.itemsize)
    return out


class FrameStatFunctions:
    def __init__(self, frame):
        self._frame = frame

    def _pair(self, col1: str, col2: str):
        dt = float_dtype()
        a = jnp.asarray(self._frame._column_values(col1), dt)
        b = jnp.asarray(self._frame._column_values(col2), dt)
        w = self._frame.mask.astype(dt)
        return a, b, w

    def corr(self, col1: str, col2: str, method: str = "pearson") -> float:
        """Pearson (or Spearman rank) correlation of two numeric columns."""
        a, b, w = self._pair(col1, col2)
        if method == "spearman":
            a, b = _rank(a, w), _rank(b, w)
        elif method != "pearson":
            raise ValueError(f"unknown correlation method {method!r}")
        return _scalar(_corr_cov(a, b, w)[0], "stat.corr")

    def cov(self, col1: str, col2: str) -> float:
        """Sample covariance (n−1 denominator, like Spark)."""
        a, b, w = self._pair(col1, col2)
        return _scalar(_corr_cov(a, b, w)[1], "stat.cov")

    def approx_quantile(self, col: str, probabilities, relative_error=0.0):
        """Quantiles of a numeric column. Spark sketches (Greenwald-Khanna)
        to bound executor memory; here an exact device sort is both cheaper
        and exact at any size XLA can sort, so ``relative_error`` is
        accepted for API compatibility and ignored."""
        from ..utils.observability import host_reading
        from ..utils.profiling import counters

        a = jnp.asarray(self._frame._column_values(col), float_dtype())
        counters.increment("frame.host_sync")  # mask + column pull, one batch
        with host_reading("stat.quantile") as rd:
            keep, vals = jax.device_get((self._frame.mask, a))
            rd.done(keep.nbytes + vals.nbytes)
        vals = np.sort(vals[keep])
        if len(vals) == 0:
            return [float("nan") for _ in np.atleast_1d(probabilities)]
        qs = [float(vals[min(int(p * len(vals)), len(vals) - 1)])
              for p in np.atleast_1d(probabilities)]
        return qs

    approxQuantile = approx_quantile

    def crosstab(self, col1: str, col2: str):
        """Contingency table of two columns (Spark's ``stat.crosstab``)."""
        from .frame import Frame

        d = self._frame.to_pydict()
        a = [str(v) for v in d[col1]]
        b = [str(v) for v in d[col2]]
        rows = sorted(set(a))
        cols = sorted(set(b))
        counts = {(x, y): 0 for x in rows for y in cols}
        for x, y in zip(a, b):
            counts[(x, y)] += 1
        data = {f"{col1}_{col2}": np.asarray(rows, dtype=object)}
        for y in cols:
            data[y] = np.asarray([counts[(x, y)] for x in rows], np.int64)
        return Frame(data)

    def sample_by(self, col: str, fractions: dict, seed: int = 0):
        """Stratified Bernoulli sample without replacement
        (Spark ``stat.sampleBy``): each row whose ``col`` value appears in
        ``fractions`` is kept with that stratum's probability; strata
        absent from ``fractions`` sample at 0. Mask-composed — shapes stay
        static and column arrays are shared, like ``Frame.sample``."""
        import jax.numpy as jnp

        for k, f in fractions.items():
            if not 0.0 <= f <= 1.0:
                raise ValueError(
                    f"fraction for stratum {k!r} must be in [0, 1], got {f}")
        vals = self._frame._column_values(col)
        if vals.dtype == object:
            vals_h = np.asarray(vals, object)
        else:
            from ..utils.observability import host_reading
            from ..utils.profiling import counters

            counters.increment("frame.host_sync")  # device stratum pull
            with host_reading("stat.strata") as rd:
                vals_h = np.asarray(vals)
                rd.done(vals_h.nbytes)
        rng = np.random.default_rng(seed)
        u = rng.random(len(vals_h))
        frac = np.asarray([fractions.get(v, 0.0) for v in vals_h.tolist()])
        keep = jnp.asarray(u < frac)
        return self._frame._with(
            mask=jnp.logical_and(self._frame.mask, keep))

    sampleBy = sample_by

    def freq_items(self, cols, support: float = 0.01):
        """Per-column items with frequency ≥ support (Spark ``freqItems``)."""
        from .frame import Frame

        d = self._frame.to_pydict()
        out = {}
        n = max(len(next(iter(d.values()))), 1) if d else 1
        for c in cols:
            vals, counts = np.unique(np.asarray([str(v) for v in d[c]]),
                                     return_counts=True)
            keep = [v for v, k in zip(vals, counts) if k / n >= support]
            out[c + "_freqItems"] = np.asarray([keep], dtype=object)
        return Frame(out)

    freqItems = freq_items


def _rank(x, w):
    """Average ranks of the valid entries (invalid slots get rank 0 and are
    zero-weighted by the caller anyway)."""
    xn = np.asarray(x)
    keep = np.asarray(w) > 0
    import scipy.stats  # available via sklearn dependency

    ranks = np.zeros_like(xn)
    ranks[keep] = scipy.stats.rankdata(xn[keep])
    return jnp.asarray(ranks, x.dtype)
