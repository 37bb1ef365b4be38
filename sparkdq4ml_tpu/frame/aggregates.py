"""Aggregations: global (device, mask-weighted) and grouped (device-first).

Design note: global aggregates (``df.agg``, ``describe``) are masked device
reductions — one fused kernel per call, honoring the validity mask exactly
like the fit statistics. Grouped aggregation over NUMERIC keys and the
compilable aggregate family lowers to ONE jitted device program
(``ops/segments.py``: on-device lexicographic key sort + segment-boundary
discovery + ``segment_*`` reductions) whose only host sync is the final
group count. Everything outside that surface — string keys, host-object
aggregates (``collect_list``, ``percentile_approx``, the two-column
family), grouped-map UDFs — takes the original host boundary: group
discovery with numpy lexsort and vectorized per-group numpy reductions,
the same "gather at the boundary, never in the compute path" rule as
``Frame.to_pydict``. ``spark.groupedExec.enabled=false`` restores the
host path for everything (bit-identical results either way).
"""

from __future__ import annotations

import builtins
from typing import Optional, Union

import jax.numpy as jnp
import numpy as np

from ..ops.expressions import Expr

_AGGS = ("count", "sum", "avg", "mean", "min", "max", "stddev", "variance",
         "stddev_pop", "var_pop", "median", "mode", "percentile_approx",
         "count_distinct", "sum_distinct", "collect_list", "collect_set",
         "first", "last", "skewness", "kurtosis",
         "corr", "covar_samp", "covar_pop", "max_by", "min_by")
# two-column aggregates (Spark's F.corr(a, b), max_by(x, ord))
_TWO_COL = ("corr", "covar_samp", "covar_pop", "max_by", "min_by")
# windowed form exists only for the running aggregates (as in Spark ≤2.x SQL)
_WINDOWABLE = ("count", "sum", "avg", "min", "max")


def _dict_aggs(d: dict) -> list:
    """PySpark's dict form: ``agg({'col': 'fn'})`` → AggExpr list with
    Spark's generated ``fn(col)`` output names ('*' allowed for count)."""
    out = []
    for col, fn in d.items():
        out.append(AggExpr(fn, None if col == "*" else col))
    return out


class AggExpr:
    """An aggregate over a column, e.g. ``F.avg("price")`` or SQL ``AVG(price)``."""

    def __init__(self, fn: str, column: Optional[str],
                 alias: Optional[str] = None,
                 column2: Optional[str] = None,
                 ignore_nulls: bool = False,
                 param=None):
        fn = fn.lower()
        if fn not in _AGGS:
            raise ValueError(f"unknown aggregate {fn!r} (supported: {_AGGS})")
        self.fn = "avg" if fn == "mean" else fn
        if self.fn in _TWO_COL:
            if column is None or column2 is None:
                raise ValueError(f"{self.fn}(col1, col2) takes two columns")
        elif column2 is not None:
            raise ValueError(f"{self.fn}() takes one column")
        self.column = column  # None = count(*)
        self.column2 = column2
        self.ignore_nulls = bool(ignore_nulls)  # first/last only
        self.param = param                       # percentile_approx only
        self._alias = alias

    def alias(self, name: str) -> "AggExpr":
        return AggExpr(self.fn, self.column, name, self.column2,
                       self.ignore_nulls, self.param)

    @property
    def name(self) -> str:
        if self._alias:
            return self._alias
        if self.fn == "count" and self.column is None:
            return "count"
        if self.fn in _TWO_COL:
            return f"{self.fn}({self.column}, {self.column2})"
        if self.fn in ("count_distinct", "sum_distinct"):
            return f"{self.fn.split('_')[0]}(DISTINCT {self.column})"
        if self.fn in ("first", "last") and self.ignore_nulls:
            # Spark encodes the flag in the name ("first(x, true)");
            # also keeps the two variants from colliding in one agg() call
            return f"{self.fn}({self.column}, true)"
        if self.fn == "percentile_approx":
            return f"percentile_approx({self.column}, {self.param})"
        target = "1" if self.column is None else self.column
        return f"{self.fn}({target})"

    def __repr__(self):
        return self.name

    def over(self, spec) -> "Expr":
        """Bind as a window aggregate: ``F.sum("x").over(Window...)``.
        Running aggregates plus ``first``/``last`` (→ the
        first_value/last_value window forms) have windowed shapes."""
        from .window import window_agg

        if self.fn in ("first", "last"):
            if self.ignore_nulls:
                raise ValueError(f"windowed {self.fn}() does not support "
                                 "ignoreNulls")
            expr = window_agg(f"{self.fn}_value", self.column).over(spec)
            return expr.alias(self._alias) if self._alias else expr
        if self.fn not in _WINDOWABLE:
            raise ValueError(f"windowed {self.fn}() is not supported")
        expr = window_agg(self.fn, self.column).over(spec)
        return expr.alias(self._alias) if self._alias else expr


class AggOfExpr(AggExpr):
    """An aggregate over an EXPRESSION (``sum(price * qty)``): the
    expression materializes as a temp device column just before
    aggregation (one fused pass), then aggregates like any column.
    Constructed by the SQL parser and the fluent constructors when given
    an Expr instead of a name."""

    def __init__(self, fn: str, expr, alias: Optional[str] = None):
        fn = fn.lower()
        fn = "avg" if fn == "mean" else fn
        if fn not in _AGGS or fn in _TWO_COL:
            raise ValueError(
                f"aggregate {fn!r} does not take an expression argument")
        self.fn = fn
        self.expr = expr
        self.column = None
        self.column2 = None
        self.ignore_nulls = False
        self.param = None
        self._alias = alias

    def alias(self, name: str) -> "AggOfExpr":
        return AggOfExpr(self.fn, self.expr, name)

    @property
    def name(self) -> str:
        return self._alias if self._alias else f"{self.fn}({self.expr})"

    def over(self, spec):
        raise ValueError(
            "windowed aggregates over expressions are not supported — "
            "materialize the expression with withColumn first")


def materialize_agg_exprs(frame, aggs):
    """Expression-argument aggregates → temp columns + plain AggExprs.
    Returns (frame, rewritten aggs); shared by every aggregation entry
    (global, grouped, pivoted, rollup/cube)."""
    out = []
    for i, a in enumerate(aggs):
        if isinstance(a, AggOfExpr):
            tmp = f"__aggarg_{i}"
            frame = frame.with_column(tmp, a.expr)
            out.append(AggExpr(a.fn, tmp, alias=a.name))
        else:
            out.append(a)
    return frame, out


# functions-module-style constructors (org.apache.spark.sql.functions).
# Each accepts a column NAME or (like PySpark) a column EXPRESSION —
# F.sum(col("p") * 2) — which routes through AggOfExpr materialization.
def _agg_or_expr(fn: str, col):
    if isinstance(col, Expr):
        from ..ops.expressions import Col
        if isinstance(col, Col):
            return AggExpr(fn, col.name)
        return AggOfExpr(fn, col)
    return AggExpr(fn, col)


def count(col: Optional[str] = None) -> AggExpr:
    if isinstance(col, Expr):
        return _agg_or_expr("count", col)
    return AggExpr("count", None if col in (None, "*") else col)


def sum(col) -> AggExpr:       # noqa: A001 - mirrors Spark's name
    return _agg_or_expr("sum", col)


def avg(col) -> AggExpr:
    return _agg_or_expr("avg", col)


mean = avg


def min(col) -> AggExpr:       # noqa: A001
    return _agg_or_expr("min", col)


def max(col) -> AggExpr:       # noqa: A001
    return _agg_or_expr("max", col)


def stddev(col) -> AggExpr:
    return _agg_or_expr("stddev", col)


def variance(col) -> AggExpr:
    return _agg_or_expr("variance", col)


def stddev_pop(col: str) -> AggExpr:
    return AggExpr("stddev_pop", col)


def var_pop(col: str) -> AggExpr:
    return AggExpr("var_pop", col)


def median(col: str) -> AggExpr:
    return AggExpr("median", col)


def mode(col: str) -> AggExpr:
    return AggExpr("mode", col)


def percentile_approx(col: str, percentage: float,
                      accuracy: int = 10000) -> AggExpr:
    """Spark's approximate percentile; this engine computes the EXACT
    nearest-rank order statistic (groups are host-resident, the sort is
    cheaper than a sketch), so ``accuracy`` is accepted for API
    compatibility and the answer has zero error."""
    if not 0.0 <= float(percentage) <= 1.0:
        raise ValueError(f"percentage must be in [0, 1], got {percentage}")
    return AggExpr("percentile_approx", col, param=float(percentage))


def count_distinct(col: str) -> AggExpr:
    return AggExpr("count_distinct", col)


countDistinct = count_distinct


def approx_count_distinct(col: str, rsd: float = 0.05) -> AggExpr:
    """Spark's HLL sketch bounds executor memory; this engine's groups are
    host-resident so the EXACT count is cheaper than a sketch — ``rsd``
    is accepted for API compatibility and the answer has zero error."""
    if not 0.0 < rsd < 1.0:
        raise ValueError(f"rsd must be in (0, 1), got {rsd}")
    return AggExpr("count_distinct", col,
                   alias=f"approx_count_distinct({col})")


approxCountDistinct = approx_count_distinct


def sum_distinct(col: str) -> AggExpr:
    return AggExpr("sum_distinct", col)


sumDistinct = sum_distinct


def collect_list(col: str) -> AggExpr:
    return AggExpr("collect_list", col)


def collect_set(col: str) -> AggExpr:
    return AggExpr("collect_set", col)


def first(col: str, ignorenulls: bool = False) -> AggExpr:
    return AggExpr("first", col, ignore_nulls=ignorenulls)


def last(col: str, ignorenulls: bool = False) -> AggExpr:
    return AggExpr("last", col, ignore_nulls=ignorenulls)


def skewness(col: str) -> AggExpr:
    return AggExpr("skewness", col)


def kurtosis(col: str) -> AggExpr:
    return AggExpr("kurtosis", col)


def corr(col1: str, col2: str) -> AggExpr:
    return AggExpr("corr", col1, column2=col2)


def covar_samp(col1: str, col2: str) -> AggExpr:
    return AggExpr("covar_samp", col1, column2=col2)


def covar_pop(col1: str, col2: str) -> AggExpr:
    return AggExpr("covar_pop", col1, column2=col2)


def _group_plan(key_cols: list[np.ndarray], n: int):
    """Null-safe lexicographic group discovery shared by groupBy/pivot:
    returns (order, group_starts, group_ends) over the n rows. Delegates key
    decomposition to window._key_parts/_neq so None string keys don't crash
    lexsort and NaN float keys form one group, exactly like window
    partitioning."""
    from .window import _key_parts, _neq

    parts_list = [_key_parts(np.asarray(k)) for k in key_cols]
    # np.lexsort: primary key LAST → reverse keys, and components within one
    lex = [comp for parts in reversed(parts_list)
           for comp in reversed(parts)]
    order = np.lexsort(lex) if lex else np.arange(n)
    boundary = np.zeros(len(order), bool)
    if len(order):
        boundary[0] = True
    for parts in parts_list:
        for comp in parts:
            boundary[1:] |= _neq(comp[order])
    starts = np.flatnonzero(boundary)
    ends = np.r_[starts[1:], len(order)]
    return order, starts, ends


def _drop_nulls(values: np.ndarray) -> np.ndarray:
    if values.dtype == object:
        return values[np.asarray([x is not None for x in values], bool)]
    if np.issubdtype(values.dtype, np.floating):
        return values[~np.isnan(values)]
    return values


def _np_agg(fn: str, values: np.ndarray, ignore_nulls: bool = False,
            param=None):
    if fn in ("first", "last"):
        # Spark's first/last default ignoreNulls=false: the raw first/last
        # row value, null included
        v = _drop_nulls(values) if ignore_nulls else values
        if len(v) == 0:
            return float("nan")
        return v[0] if fn == "first" else v[-1]
    values = _drop_nulls(values)  # SQL semantics: aggregates skip nulls
    if fn == "count":
        return len(values)
    if fn == "count_distinct":
        return len(set(values.tolist()))
    if fn == "collect_list":
        return list(values.tolist())
    if fn == "collect_set":
        # first-appearance order (Spark's order is unspecified)
        return list(dict.fromkeys(values.tolist()))
    if len(values) == 0:
        return float("nan")
    if fn == "sum":
        return values.sum()
    if fn == "sum_distinct":
        return np.asarray(list(set(values.tolist()))).sum()
    if fn == "avg":
        return float(np.mean(values))
    if fn == "min":
        return values.min()
    if fn == "max":
        return values.max()
    if fn == "stddev":
        return float(np.std(values, ddof=1)) if len(values) > 1 else float("nan")
    if fn == "variance":
        return float(np.var(values, ddof=1)) if len(values) > 1 else float("nan")
    if fn == "stddev_pop":
        return float(np.std(values, ddof=0))
    if fn == "var_pop":
        return float(np.var(values, ddof=0))
    if fn == "median":
        return float(np.median(np.asarray(values, np.float64)))
    if fn == "mode":
        # most frequent value; ties break to the smallest (deterministic —
        # Spark leaves tie order unspecified)
        uniq, cnt = np.unique(np.asarray(values), return_counts=True)
        return uniq[np.lexsort((uniq, -cnt))[0]]
    if fn == "percentile_approx":
        # exact nearest-rank order statistic: the smallest value whose
        # cumulative rank >= ceil(p*n) (Spark's convention — e.g.
        # p=0.5 over [1, 5] is 1, not 5). Spark's sketch bounds memory;
        # the exact sort here is cheaper and has zero error.
        v = np.sort(np.asarray(values, np.float64))
        p = float(param if param is not None else 0.5)
        idx = builtins.max(int(np.ceil(p * len(v))) - 1, 0)
        return float(v[builtins.min(idx, len(v) - 1)])
    if fn in ("skewness", "kurtosis"):
        # Spark: population moments; kurtosis is EXCESS kurtosis
        v = np.asarray(values, np.float64)
        m2 = np.mean((v - v.mean()) ** 2)
        if m2 == 0:
            return float("nan")
        if fn == "skewness":
            return float(np.mean((v - v.mean()) ** 3) / m2 ** 1.5)
        return float(np.mean((v - v.mean()) ** 4) / m2 ** 2 - 3.0)
    raise ValueError(fn)


def _np_agg2(fn: str, a: np.ndarray, b: np.ndarray):
    """Two-column aggregates over pairwise non-null rows (SQL semantics)."""
    if fn in ("max_by", "min_by"):
        # value of a at the extreme of b (Spark max_by/min_by): only rows
        # with a null ORDERING are ignored — the selected VALUE returns
        # as-is, NULL included (Spark returns NULL when the row at the
        # extreme ordering has a null value; ADVICE.md #3). The value may
        # be any type (string max_by is the idiomatic use) and passes
        # through unconverted.
        a = np.asarray(a)
        bb = np.asarray(b, np.float64)
        ok = ~np.isnan(bb)
        if not ok.any():
            return None if a.dtype == object else float("nan")
        sel = np.flatnonzero(ok)
        pick = sel[int(np.argmax(bb[sel])) if fn == "max_by"
                   else int(np.argmin(bb[sel]))]
        v = a[pick]
        return v if a.dtype == object else float(v)
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    ok = ~(np.isnan(a) | np.isnan(b))
    a, b = a[ok], b[ok]
    n = len(a)
    if fn == "covar_pop":
        return float(np.mean((a - a.mean()) * (b - b.mean()))) if n else float("nan")
    if n < 2:
        return float("nan")
    if fn == "covar_samp":
        return float(((a - a.mean()) * (b - b.mean())).sum() / (n - 1))
    if fn == "corr":
        sa, sb = a.std(), b.std()
        if sa == 0 or sb == 0:
            return float("nan")
        return float(np.mean((a - a.mean()) * (b - b.mean())) / (sa * sb))
    raise ValueError(fn)


_DEVICE_AGGS = ("count", "sum", "avg", "min", "max", "stddev", "variance")


def _one_slot_obj(value):
    arr = np.empty(1, dtype=object)
    arr[0] = value
    return arr


def global_agg(frame, aggs: list[AggExpr]):
    """Masked device reductions over the whole frame → 1-row Frame.
    The order-/set-valued aggregates (collect_*, first/last, distinct,
    corr family, higher moments) take the host boundary like grouped
    aggregation — their outputs are host objects by nature."""
    from .frame import Frame

    mask = frame.mask
    w = mask.astype(jnp.float32)
    out = {}
    # (name, nonnull_count, value, null_result) for the aggregates whose
    # empty-input NULL decision is deferred to ONE host sync after the loop
    deferred: list = []
    for agg in aggs:
        if agg.fn == "count" and agg.column is None:
            out[agg.name] = jnp.sum(mask, dtype=jnp.int32)[None]
            continue
        if agg.fn in _TWO_COL:
            m = np.asarray(mask)
            a = np.asarray(frame._column_values(agg.column))[m]
            b = np.asarray(frame._column_values(agg.column2))[m]
            out[agg.name] = np.asarray([_np_agg2(agg.fn, a, b)])
            continue
        if agg.fn not in _DEVICE_AGGS:
            m = np.asarray(mask)
            vals = np.asarray(frame._column_values(agg.column))[m]
            res = _np_agg(agg.fn, vals, agg.ignore_nulls, agg.param)
            # list results AND non-numeric scalars (first/last of a string
            # column) must stay object slots — np.asarray would mint a
            # unicode array the device column layer rejects
            host_obj = (agg.fn in ("collect_list", "collect_set")
                        or vals.dtype == object)
            out[agg.name] = (_one_slot_obj(res) if host_obj
                             else np.asarray([res]))
            continue
        col = frame._column_values(agg.column)
        if isinstance(col, np.ndarray) and col.dtype == object:
            # string column: host path (count only meaningful)
            vals = col[np.asarray(mask)]
            out[agg.name] = np.asarray([_np_agg(agg.fn, vals)])
            continue
        v = jnp.asarray(col)
        if agg.fn in ("count", "sum") and jnp.issubdtype(v.dtype, jnp.integer):
            # exact integer arithmetic on host (Spark widens SUM to long;
            # a float32 device accumulation would round/saturate)
            vals = np.asarray(v)[np.asarray(mask)]
            out[agg.name] = np.asarray(
                [len(vals) if agg.fn == "count" else int(vals.sum(dtype=np.int64))],
                dtype=np.int64)
            continue
        vf = v.astype(jnp.float64 if v.dtype == jnp.float64 else jnp.float32)
        wf = w.astype(vf.dtype)
        # SQL semantics: aggregates over a column skip nulls (NaN)
        null = jnp.isnan(vf)
        valid = jnp.logical_and(mask, jnp.logical_not(null))
        wf = wf * jnp.logical_not(null).astype(vf.dtype)
        nv = jnp.sum(wf)
        vf = jnp.where(null, 0.0, vf)
        nan = jnp.asarray(jnp.nan, vf.dtype)
        # SQL NULL results over zero non-null rows (Spark): keyed on the
        # non-null ROW COUNT, not the weight sum (a zero weight sum over
        # non-null rows must yield 0.0 from sum(), ADVICE.md #5), and the
        # decision is deferred — one host sync after the loop instead of
        # an eager float() per aggregate.
        cnt = jnp.sum(valid, dtype=jnp.int32)
        if agg.fn == "count":
            out[agg.name] = cnt[None]
        elif agg.fn == "avg":
            out[agg.name] = (jnp.sum(vf * wf) / nv)[None]
        elif agg.fn == "sum":
            out[agg.name] = None  # placeholder keeps the column order
            deferred.append((agg.name, cnt, jnp.sum(vf * wf)[None],
                             nan[None]))
        elif agg.fn == "min":
            big = jnp.asarray(jnp.inf, vf.dtype)
            out[agg.name] = None
            deferred.append((agg.name, cnt, jnp.min(
                jnp.where(valid, vf, big)).astype(v.dtype)[None],
                nan[None]))
        elif agg.fn == "max":
            small = jnp.asarray(-jnp.inf, vf.dtype)
            out[agg.name] = None
            deferred.append((agg.name, cnt, jnp.max(
                jnp.where(valid, vf, small)).astype(v.dtype)[None],
                nan[None]))
        else:  # stddev / variance: sample (n-1); NaN when n < 2 (Spark)
            mu = jnp.sum(vf * wf) / nv
            ss = jnp.sum(wf * (vf - mu) ** 2)
            var = jnp.where(nv > 1.0, ss / jnp.maximum(nv - 1.0, 1.0),
                            jnp.asarray(jnp.nan, vf.dtype))
            out[agg.name] = (var if agg.fn == "variance" else jnp.sqrt(var))[None]
    if deferred:
        # the ONE deferred device->host pull per agg call (all empty-input
        # verdicts batch into a single stacked transfer) — counted, so the
        # span layer and EXPLAIN ANALYZE see it (dqlint host-sync)
        from ..utils.observability import host_reading
        from ..utils.profiling import counters

        counters.increment("frame.host_sync")
        stacked = jnp.stack([c for _, c, _, _ in deferred])
        with host_reading("agg.verdict") as rd:
            counts = np.asarray(stacked)
            rd.done(counts.nbytes)
        for (name, _, val, nanv), c in zip(deferred, counts):
            out[name] = val if int(c) > 0 else nanv
    return Frame(out)


class _AggShortcuts:
    """The RelationalGroupedDataset terminal shortcuts, shared by the
    grouped, pivoted, and rollup/cube frames — each delegates to
    ``self.agg``."""

    def count(self):
        return self.agg(AggExpr("count", None))

    def sum(self, *cols: str):
        return self.agg(*[AggExpr("sum", c) for c in cols])

    def avg(self, *cols: str):
        return self.agg(*[AggExpr("avg", c) for c in cols])

    mean = avg

    def min(self, *cols: str):
        return self.agg(*[AggExpr("min", c) for c in cols])

    def max(self, *cols: str):
        return self.agg(*[AggExpr("max", c) for c in cols])


class GroupedFrame(_AggShortcuts):
    """Result of ``Frame.group_by`` — terminal agg methods mirror Spark's
    ``RelationalGroupedDataset``."""

    def __init__(self, frame, keys: list[str]):
        if not keys:
            raise ValueError("group_by requires at least one key column")
        self._frame = frame
        self._keys = keys
        for k in keys:
            frame._column_values(k)  # validate early

    def apply_in_pandas(self, func, schema):
        """Spark 3's ``groupBy(...).applyInPandas(fn, schema)``: the
        grouped-map UDF. Each group materializes as a pandas DataFrame on
        the host, ``func`` maps it to a new DataFrame, and the pieces
        concatenate into one Frame cast to the DDL ``schema``. This is
        the escape hatch for per-group logic the fused aggregate path
        cannot express — it pays the host boundary once per group, so
        keep it off hot paths (the vectorized agg() stays the fast lane).
        """
        import pandas as pd

        from .csv import parse_ddl_schema
        from .frame import Frame

        fields = parse_ddl_schema(schema) if isinstance(schema, str) \
            else list(schema)
        pdf = self._frame.to_pandas()
        if len(pdf) == 0:
            groups = []
        else:
            groups = [g.reset_index(drop=True)
                      for _, g in pdf.groupby(self._keys, sort=True,
                                              dropna=False)]
        outs = []
        for g in groups:
            out = func(g)
            if not isinstance(out, pd.DataFrame):
                raise TypeError("applyInPandas function must return a "
                                f"pandas DataFrame, got {type(out).__name__}")
            outs.append(out)
        names = [n for n, _ in fields]
        if outs:
            cat = pd.concat(outs, ignore_index=True)
            missing = [n for n in names if n not in cat.columns]
            if missing:
                raise ValueError(f"applyInPandas output is missing schema "
                                 f"columns {missing}")
            data = {n: cat[n].to_numpy() for n in names}
        else:
            data = {n: np.asarray([], np.float64) for n in names}
        frame = Frame(data)
        for name, tname in fields:
            frame = frame.with_column(
                name, frame.col(name).cast(tname))
        return frame

    applyInPandas = apply_in_pandas

    def agg(self, *aggs: Union[AggExpr, str]):
        from .frame import Frame

        if len(aggs) == 1 and isinstance(aggs[0], dict):
            aggs = tuple(_dict_aggs(aggs[0]))
        agg_list = []
        for a in aggs:
            if isinstance(a, str):
                a = AggExpr(a, None)
            agg_list.append(a)
        if not agg_list:
            raise ValueError("agg() needs at least one aggregate")
        frame_src, agg_list = materialize_agg_exprs(self._frame, agg_list)

        # Device-resident path first (ops/segments.py): one jitted
        # segment-reduce program, one host sync (the group count). Any
        # ineligible plan (string keys, host-object aggs) or internal
        # failure falls back to the host path below via the shared
        # try_device protocol — the optimization layer must never change
        # results.
        from ..ops import segments

        out = segments.try_device(
            "grouped_agg",
            lambda: segments.grouped_agg(frame_src, self._keys, agg_list))
        if out is not None:
            return out

        d = frame_src.to_pydict()  # host boundary: one gather
        key_cols = [np.asarray(d[k]) for k in self._keys]
        order, group_starts, group_ends = _group_plan(
            key_cols, len(key_cols[0]) if key_cols else 0)
        if len(order) == 0:
            data = {k: [] for k in self._keys}
            data.update({a.name: [] for a in agg_list})
            return Frame(data)

        data: dict[str, list] = {k: [] for k in self._keys}
        for a in agg_list:
            data[a.name] = []
        for s, e in zip(group_starts, group_ends):
            idx = order[s:e]
            for k, kc in zip(self._keys, key_cols):
                data[k].append(kc[idx[0]])
            for a in agg_list:
                if a.fn == "count" and a.column is None:
                    data[a.name].append(len(idx))
                elif a.fn in _TWO_COL:
                    data[a.name].append(_np_agg2(
                        a.fn, np.asarray(d[a.column])[idx],
                        np.asarray(d[a.column2])[idx]))
                else:
                    data[a.name].append(_np_agg(
                        a.fn, np.asarray(d[a.column])[idx], a.ignore_nulls,
                        a.param))
        # list-valued aggregate columns must stay ragged object arrays
        for a in agg_list:
            if a.fn in ("collect_list", "collect_set"):
                from .frame import list_column

                data[a.name] = list_column(data[a.name])
        return Frame(data)

    def pivot(self, pivot_col: str, values=None) -> "PivotedFrame":
        """``groupBy(keys).pivot(col[, values]).agg(...)`` — rotate the
        distinct values of ``pivot_col`` into output columns (Spark's
        RelationalGroupedDataset.pivot). When ``values`` is omitted the
        distinct values are discovered from the data and sorted, as Spark
        does; passing them explicitly skips that pass and fixes the column
        order."""
        self._frame._column_values(pivot_col)
        return PivotedFrame(self._frame, self._keys, pivot_col, values)



class PivotedFrame(_AggShortcuts):
    """Result of ``GroupedFrame.pivot`` — terminal agg methods produce one
    output column per (pivot value × aggregate), Spark column naming:
    just the value for a single aggregate, ``value_aggname`` for several."""

    def __init__(self, frame, keys: list[str], pivot_col: str, values):
        self._frame = frame
        self._keys = keys
        self._pivot_col = pivot_col
        self._values = list(values) if values is not None else None

    def agg(self, *aggs: Union[AggExpr, str]):
        from .frame import Frame

        agg_list = [AggExpr(a, None) if isinstance(a, str) else a
                    for a in aggs]
        if not agg_list:
            raise ValueError("agg() needs at least one aggregate")
        frame_src, agg_list = materialize_agg_exprs(self._frame, agg_list)

        d = frame_src.to_pydict()  # host boundary: one gather
        pcol = np.asarray(d[self._pivot_col])
        if self._values is None:
            uniq = [x for x in set(pcol.tolist()) if x is not None]
            try:
                values = sorted(uniq)       # natural order (Spark parity)
            except TypeError:
                # mixed incomparable types (e.g. int + str): group by type,
                # natural order within each type
                values = sorted(uniq, key=lambda x: (str(type(x)), x))
        else:
            values = self._values

        key_cols = [np.asarray(d[k]) for k in self._keys]
        order, group_starts, group_ends = _group_plan(key_cols, len(pcol))

        # Output names are precomputed, de-colliding against group keys AND
        # each other (two pivot values may stringify identically, 1 vs "1").
        taken = set(self._keys)
        names: dict[tuple, str] = {}
        for vi, v in enumerate(values):
            for ai, a in enumerate(agg_list):
                base = str(v) if len(agg_list) == 1 else f"{v}_{a.name}"
                while base in taken:
                    base += "_pivot"
                taken.add(base)
                names[(vi, ai)] = base

        agg_arrays = {a.column: np.asarray(d[a.column])
                      for a in agg_list if a.column is not None}
        agg_arrays.update({a.column2: np.asarray(d[a.column2])
                           for a in agg_list if a.column2 is not None})

        data: dict[str, list] = {k: [] for k in self._keys}
        for nm in names.values():
            data[nm] = []
        for s, e in zip(group_starts, group_ends):
            idx = order[s:e]
            for k, kc in zip(self._keys, key_cols):
                data[k].append(kc[idx[0]])
            grp_pivot = pcol[idx]
            for vi, v in enumerate(values):
                sub = idx[np.asarray([x == v for x in grp_pivot], bool)]
                for ai, a in enumerate(agg_list):
                    if a.fn == "count" and a.column is None:
                        data[names[(vi, ai)]].append(len(sub))
                    elif len(sub) == 0:
                        # no rows for this cell → null (Spark), even for
                        # COUNT over a column (Spark yields null there too)
                        data[names[(vi, ai)]].append(float("nan"))
                    elif a.fn in _TWO_COL:
                        data[names[(vi, ai)]].append(_np_agg2(
                            a.fn, agg_arrays[a.column][sub],
                            agg_arrays[a.column2][sub]))
                    else:
                        data[names[(vi, ai)]].append(_np_agg(
                            a.fn, agg_arrays[a.column][sub], a.ignore_nulls,
                            a.param))
        from .frame import list_column

        for (vi, ai), nm in names.items():
            if agg_list[ai].fn in ("collect_list", "collect_set"):
                data[nm] = list_column(data[nm])
        return Frame(data)



class MultiGroupedFrame(_AggShortcuts):
    """``Frame.rollup``/``Frame.cube`` — aggregate at several grouping
    levels and union the results, Spark's subtotal semantics: key columns
    absent from a level come back null. Output key columns are nullable
    and therefore host object columns (None in subtotal rows) — keeping
    integer keys EXACT; a NaN filler would silently promote int keys to
    the device float dtype and corrupt values past its mantissa."""

    def __init__(self, frame, keys: list[str], levels: list[tuple]):
        if not keys:
            raise ValueError("rollup/cube require at least one key column")
        self._frame = frame
        self._keys = keys
        self._levels = levels
        for k in keys:
            frame._column_values(k)  # validate early

    def agg(self, *aggs: Union[AggExpr, str]):
        from .frame import Frame

        agg_list = [AggExpr(a, None) if isinstance(a, str) else a
                    for a in aggs]
        if not agg_list:
            raise ValueError("agg() needs at least one aggregate")

        frame_src, agg_list = materialize_agg_exprs(self._frame, agg_list)
        # One pass per level; a single concatenate per column at the end.
        key_parts: dict[str, list] = {k: [] for k in self._keys}
        agg_parts: dict[str, list] = {a.name: [] for a in agg_list}
        for kept in self._levels:
            if kept:
                out = GroupedFrame(frame_src, list(kept)).agg(*agg_list)
            else:
                out = global_agg(frame_src, agg_list)
            d = out.to_pydict()
            n = len(next(iter(d.values()))) if d else 0
            for k in self._keys:
                if k in d:
                    key_parts[k].append(np.asarray(d[k], object))
                else:
                    filler = np.empty(n, dtype=object)  # None slots
                    filler.fill(None)
                    key_parts[k].append(filler)
            for a in agg_list:
                agg_parts[a.name].append(np.asarray(d[a.name]))

        data: dict = {}
        for k in self._keys:
            data[k] = np.concatenate(key_parts[k])
        for a in agg_list:
            parts = agg_parts[a.name]
            if any(p.dtype == object for p in parts):
                parts = [np.asarray(p, object) for p in parts]
            data[a.name] = np.concatenate(parts)
        return Frame(data)


def rollup_levels(keys: list[str]) -> list[tuple]:
    """Prefixes, longest first, down to the grand total: Spark ROLLUP."""
    return [tuple(keys[:i]) for i in range(len(keys), -1, -1)]


def cube_levels(keys: list[str]) -> list[tuple]:
    """Every key subset (kept in key order), by descending size: CUBE."""
    import itertools as _it

    out = []
    for r in range(len(keys), -1, -1):
        out.extend(_it.combinations(keys, r))
    return out
