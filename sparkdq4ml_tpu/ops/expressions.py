"""Column expression trees.

This is the framework's equivalent of the Spark column-expression surface the
reference app exercises (``df.col``, ``callUDF``, ``cast``, comparisons in SQL
``WHERE`` — `DataQuality4MachineLearningApp.java:68-90`). An ``Expr`` is a
small host-side tree; evaluating it against a :class:`~sparkdq4ml_tpu.frame.Frame`
produces a device array over *all* row slots (filtering is a validity mask, so
shapes stay static for XLA — see SURVEY.md §7 step 1).

Unlike Spark, where a UDF crosses the codegen→JVM-object boundary per row (the
"UDF tax", SURVEY.md §3.2), every expression here is a vectorized jnp op that
XLA fuses — the per-row boundary does not exist.
"""

from __future__ import annotations

import base64 as _b64
import builtins
import functools
import hashlib
import math
import re
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import float_dtype, int_dtype

# Spark SQL type name → dtype factory. Mirrors the names printSchema uses.
_TYPE_NAMES: dict[str, Callable[[], Any]] = {
    "int": int_dtype,
    "integer": int_dtype,
    "long": lambda: jnp.int64 if jnp.zeros((), jnp.int64).dtype == jnp.int64 else jnp.int32,
    "float": lambda: jnp.float32,
    "double": float_dtype,
    "boolean": lambda: jnp.bool_,
    "string": lambda: np.dtype(object),
}


def spark_type_name(dtype) -> str:
    """dtype → Spark printSchema type name (integer/long/float/double/boolean/string)."""
    dt = np.dtype(dtype) if not isinstance(dtype, np.dtype) else dtype
    if dt == np.int32 or dt == np.int16 or dt == np.int8:
        return "integer"
    if dt == np.int64:
        return "long"
    if dt == np.float32:
        return "float"
    if dt == np.float64:
        return "double"
    if dt == np.bool_:
        return "boolean"
    return "string"


def resolve_type_name(name: str):
    try:
        return _TYPE_NAMES[name.lower()]()
    except KeyError:
        raise ValueError(f"unknown SQL type name: {name!r}") from None


class Expr:
    """Base column expression. Supports Python operators like Spark's Column."""

    def eval(self, frame):  # pragma: no cover - abstract
        raise NotImplementedError

    @property
    def name(self) -> str:
        """Default output-column name (Spark derives one from the expr string)."""
        return str(self)

    # -- fluent API (Spark Column methods) --------------------------------
    def alias(self, name: str) -> "Alias":
        return Alias(self, name)

    def cast(self, type_name: str) -> "Cast":
        return Cast(self, type_name)

    astype = cast   # PySpark alias

    def isin(self, *values) -> "Expr":
        """Membership test — ``col.isin(1, 2, 3)`` / SQL ``IN (…)``."""
        if len(values) == 1 and isinstance(values[0], (list, tuple, set)):
            values = tuple(values[0])
        return InList(self, [v if isinstance(v, Expr) else Lit(v)
                             for v in values])

    def between(self, lower, upper) -> "Expr":
        """``lower <= col <= upper`` (inclusive) — SQL ``BETWEEN``."""
        return (self >= lower) & (self <= upper)

    def like(self, pattern: str) -> "Expr":
        """SQL LIKE: ``%`` any run, ``_`` one char (string columns)."""
        return StringMatch("like", self, pattern)

    def rlike(self, pattern: str) -> "Expr":
        """Regex search (Spark ``rlike``)."""
        return StringMatch("rlike", self, pattern)

    def contains(self, sub: str) -> "Expr":
        return StringMatch("contains", self, sub)

    def startswith(self, prefix: str) -> "Expr":
        return StringMatch("startswith", self, prefix)

    def endswith(self, suffix: str) -> "Expr":
        return StringMatch("endswith", self, suffix)

    def is_null(self) -> "Expr":
        return UnaryOp("isnull", self)

    def is_not_null(self) -> "Expr":
        return UnaryOp("isnotnull", self)

    # Spark Column camelCase names
    isNull = is_null
    isNotNull = is_not_null

    def ilike(self, pattern: str) -> "Expr":
        """Case-insensitive LIKE (Spark ``ilike``): lower both sides."""
        return StringMatch("like", fn("lower", self), pattern.lower())

    def eq_null_safe(self, other) -> "Expr":
        """Null-safe equality (Spark ``eqNullSafe`` / SQL ``<=>``): true
        when both sides are null, false when exactly one is — composed
        from == and is_null, so NaN-null float columns and None-null
        string columns both follow Spark's truth table."""
        other = other if isinstance(other, Expr) else Lit(other)
        return (self == other) | (self.is_null() & other.is_null())

    eqNullSafe = eq_null_safe

    def substr(self, startPos, length) -> "Expr":
        """Spark ``col.substr(pos, len)`` (1-based) — the method form of
        ``substring``. pos/len may be ints or Columns (Spark's
        ``substr(Column, Column)`` overload); a null pos/len yields
        null."""
        p = startPos if isinstance(startPos, Expr) else Lit(startPos)
        ln = length if isinstance(length, Expr) else Lit(length)
        return fn("substring", self, p, ln)

    def get_item(self, key: int) -> "Expr":
        """Spark ``getItem``: 0-based array element; negative or
        out-of-range ordinals yield null (GetArrayItem semantics —
        ``element_at`` is the 1-based SQL form where negatives count from
        the end)."""
        return fn("get_item", self, Lit(int(key)))

    getItem = get_item

    def asc(self) -> "SortOrder":
        """Ascending sort marker for ``sort``/``orderBy``/window specs.
        Default null placement is Spark's: nulls first ascending, nulls
        last descending (the _nulls_first/_nulls_last variants pin it)."""
        return SortOrder(self, True)

    def desc(self) -> "SortOrder":
        """Descending sort marker (see ``asc`` for null placement)."""
        return SortOrder(self, False)

    def asc_nulls_first(self) -> "SortOrder":
        return SortOrder(self, True, nulls_first=True)

    def asc_nulls_last(self) -> "SortOrder":
        return SortOrder(self, True, nulls_first=False)

    def desc_nulls_first(self) -> "SortOrder":
        return SortOrder(self, False, nulls_first=True)

    def desc_nulls_last(self) -> "SortOrder":
        return SortOrder(self, False, nulls_first=False)

    # -- operators --------------------------------------------------------
    def _bin(self, op, other, reverse=False):
        other = other if isinstance(other, Expr) else Lit(other)
        return BinOp(op, other, self) if reverse else BinOp(op, self, other)

    def __add__(self, o):  return self._bin("+", o)
    def __radd__(self, o): return self._bin("+", o, True)
    def __sub__(self, o):  return self._bin("-", o)
    def __rsub__(self, o): return self._bin("-", o, True)
    def __mul__(self, o):  return self._bin("*", o)
    def __rmul__(self, o): return self._bin("*", o, True)
    def __truediv__(self, o):  return self._bin("/", o)
    def __rtruediv__(self, o): return self._bin("/", o, True)
    def __mod__(self, o):      return self._bin("%", o)
    def __rmod__(self, o):     return self._bin("%", o, True)
    def __neg__(self):     return UnaryOp("-", self)
    def __lt__(self, o):   return self._bin("<", o)
    def __le__(self, o):   return self._bin("<=", o)
    def __gt__(self, o):   return self._bin(">", o)
    def __ge__(self, o):   return self._bin(">=", o)
    def __eq__(self, o):   return self._bin("==", o)  # type: ignore[override]
    def __ne__(self, o):   return self._bin("!=", o)  # type: ignore[override]
    def __and__(self, o):  return self._bin("&", o)
    def __rand__(self, o): return self._bin("&", o, True)
    def __or__(self, o):   return self._bin("|", o)
    def __ror__(self, o):  return self._bin("|", o, True)
    def __invert__(self):  return UnaryOp("!", self)

    __hash__ = object.__hash__  # __eq__ is overloaded; keep Exprs hashable


class SortOrder:
    """Sort-direction marker from ``col.asc()`` / ``col.desc()`` (and the
    ``*_nulls_first/last`` variants) — consumed by ``Frame.sort``; not an
    evaluable expression. ``nulls_first=None`` means the Spark default
    for the direction: first when ascending, last when descending."""

    def __init__(self, child: "Expr", ascending: bool, nulls_first=None):
        self.child = child
        self.ascending = ascending
        self.nulls_first = nulls_first

    @property
    def name(self) -> str:
        return self.child.name


class Col(Expr):
    def __init__(self, name: str):
        self._name = name

    def eval(self, frame):
        return frame._column_values(self._name)

    @property
    def name(self) -> str:
        return self._name

    def __str__(self):
        return self._name


class Lit(Expr):
    def __init__(self, value):
        self.value = value

    def eval(self, frame):
        n = frame.num_slots
        if isinstance(self.value, bool):
            return jnp.full((n,), self.value, dtype=jnp.bool_)
        if isinstance(self.value, int):
            return jnp.full((n,), self.value, dtype=int_dtype())
        if isinstance(self.value, float):
            return jnp.full((n,), self.value, dtype=float_dtype())
        return np.full((n,), self.value, dtype=object)

    def __str__(self):
        return repr(self.value)


class Alias(Expr):
    def __init__(self, child: Expr, name: str):
        self.child = child
        self._name = name

    def eval(self, frame):
        return self.child.eval(frame)

    @property
    def name(self) -> str:
        return self._name

    def __str__(self):
        return f"{self.child} AS {self._name}"


def predicate_keep_mask(cond):
    """SQL WHERE truthiness of a predicate column: a NULL predicate (NaN
    in this engine's float encoding) drops the row — three-valued logic,
    where a bare ``NaN.astype(bool)`` would be True — and nonzero
    numerics are true. THE single definition shared by
    ``Frame._filter_eager`` and the pipeline compiler's fused filter, so
    the eager and compiled paths cannot diverge on null rows."""
    cond = jnp.asarray(cond)
    if jnp.issubdtype(cond.dtype, jnp.floating):
        return jnp.logical_and(jnp.logical_not(jnp.isnan(cond)), cond != 0)
    return cond.astype(jnp.bool_)


def _sql_divide(a, b):
    """Spark's non-ANSI division: x / 0 is NULL (incl. 0 / 0)."""
    return jnp.where(b == 0, jnp.nan, jnp.divide(a, b))


def _sql_mod(a, b):
    """Spark's % / mod(): sign follows the dividend; x % 0 is NULL."""
    return jnp.where(b == 0, jnp.nan, jnp.fmod(a, b))


_BIN_FNS = {
    "+": jnp.add,
    "-": jnp.subtract,
    "*": jnp.multiply,
    "/": _sql_divide,
    "%": _sql_mod,
    "<": jnp.less,
    "<=": jnp.less_equal,
    ">": jnp.greater,
    ">=": jnp.greater_equal,
    "==": jnp.equal,
    "!=": jnp.not_equal,
    "&": jnp.logical_and,
    "|": jnp.logical_or,
}


def _is_object(a) -> bool:
    return isinstance(a, np.ndarray) and a.dtype == object


def _promote(a, b):
    """Numeric promotion for mixed host/device operands."""
    return jnp.asarray(a), jnp.asarray(b)


class BinOp(Expr):
    def __init__(self, op: str, left: Expr, right: Expr):
        self.op, self.left, self.right = op, left, right

    def eval(self, frame):
        a, b = self.left.eval(frame), self.right.eval(frame)
        if _is_object(a) or _is_object(b):
            # String columns live on host; comparisons stay in numpy.
            np_fns = {"==": np.equal, "!=": np.not_equal}
            if self.op not in np_fns:
                raise TypeError(f"operator {self.op!r} unsupported on strings")
            return np_fns[self.op](np.asarray(a, object), np.asarray(b, object)
                                   ).astype(bool)
        a, b = _promote(a, b)
        if self.op in ("/", "%"):
            # Spark's / always yields double; % needs float for the
            # NULL-on-zero-divisor result
            a = jnp.asarray(a, float_dtype())
            b = jnp.asarray(b, float_dtype())
        return _BIN_FNS[self.op](a, b)

    def __str__(self):
        return f"({self.left} {self.op} {self.right})"


class UnaryOp(Expr):
    def __init__(self, op: str, child: Expr):
        self.op, self.child = op, child

    def eval(self, frame):
        v = self.child.eval(frame)
        if self.op == "-":
            return jnp.negative(v)
        if self.op == "!":
            return jnp.logical_not(v)
        if self.op in ("isnull", "isnotnull"):
            if _is_object(v):  # string columns: None marks null
                nulls = np.asarray([x is None for x in v], dtype=bool)
                nulls = jnp.asarray(nulls)
            elif hasattr(v, "dtype") and np.issubdtype(np.dtype(v.dtype), np.floating):
                nulls = jnp.isnan(v)
            else:
                nulls = jnp.zeros(v.shape[:1], jnp.bool_)
            return nulls if self.op == "isnull" else jnp.logical_not(nulls)
        raise ValueError(self.op)

    def __str__(self):
        return f"({self.op}{self.child})"


class Cast(Expr):
    """CAST(expr AS type) — Spark semantics: double→int truncates toward zero."""

    def __init__(self, child: Expr, type_name: str):
        self.child = child
        self.type_name = type_name

    _BOOL_TRUE = frozenset(("true", "t", "yes", "y", "1"))
    _BOOL_FALSE = frozenset(("false", "f", "no", "n", "0"))

    def eval(self, frame):
        v = self.child.eval(frame)
        dt = resolve_type_name(self.type_name)
        if isinstance(dt, np.dtype) and dt == object:
            # to string: null stays null (numeric NaN is this engine's
            # null, so it maps to None too, not the text 'nan')
            a = v if _is_object(v) else np.asarray(v)
            return np.asarray(
                [None if x is None
                 or (isinstance(x, (float, np.floating)) and np.isnan(x))
                 else str(x) for x in a], dtype=object)
        if _is_object(v):
            return self._cast_strings(v, dt)
        return jnp.asarray(v).astype(dt)

    def _cast_strings(self, v, dt):
        """Spark string→numeric/boolean cast: trim, parse; unparseable /
        null → null (NaN-float representation when nulls force it).
        Booleans accept the word literals; integer targets parse integral
        strings EXACTLY (no 2^53 float corruption) and truncate decimal
        forms toward zero; underscores and non-finite values are rejected
        for integer targets the way Spark rejects them."""
        if np.dtype(dt) == np.bool_:
            vals = []
            for x in v:
                if x is None:
                    vals.append(None)
                    continue
                s = str(x).strip().lower()
                vals.append(True if s in self._BOOL_TRUE else
                            False if s in self._BOOL_FALSE else None)
            if any(b is None for b in vals):
                return jnp.asarray(np.asarray(
                    [np.nan if b is None else float(b) for b in vals],
                    np.float64), float_dtype())
            return jnp.asarray(np.asarray(vals, np.bool_))

        int_target = np.issubdtype(np.dtype(dt), np.integer)
        parsed = np.empty(len(v), np.float64)
        exact = np.zeros(len(v), np.int64)
        all_exact_int = True
        for i, x in enumerate(v):
            if x is None:
                parsed[i] = np.nan
                all_exact_int = False
                continue
            s = str(x).strip()
            if "_" in s:                  # Python literal syntax, not SQL
                parsed[i] = np.nan
                all_exact_int = False
                continue
            try:
                exact[i] = int(s)         # exact (beyond 2^53) integral
                parsed[i] = float(exact[i])
                continue
            except (ValueError, OverflowError):
                all_exact_int = False
            try:
                parsed[i] = float(s)
            except ValueError:
                parsed[i] = np.nan
        if int_target:
            if all_exact_int:
                return jnp.asarray(exact.astype(dt))
            finite = np.isfinite(parsed)
            whole = np.where(finite, np.trunc(parsed), np.nan)
            return jnp.asarray(whole, float_dtype())
        return jnp.asarray(parsed, dt)

    @property
    def name(self) -> str:
        return f"CAST({self.child} AS {self.type_name.upper()})"

    def __str__(self):
        return self.name


class InList(Expr):
    """``expr IN (v1, v2, …)`` — vectorized membership, no row loop.

    Numeric columns fold to an OR-reduction of equalities on device; string
    columns test with host numpy. Null rows (None / NaN) are never members
    (SQL three-valued logic collapses to False in a WHERE mask).

    A NULL *in the value set* follows SQL three-valued logic too (Spark
    parity): ``x NOT IN (…, NULL)`` can never be TRUE (``x <> NULL`` is
    unknown), so NOT IN filters every row; plain ``IN`` drops the NULL
    from the list — a match still passes, a non-match becomes unknown and
    filters, which the boolean mask already expresses as False.
    """

    def __init__(self, child: Expr, values: Sequence[Expr],
                 negated: bool = False):
        self.child = child
        self.values = list(values)
        self.negated = negated

    @staticmethod
    def _is_null_lit(x) -> bool:
        return isinstance(x, Lit) and (
            x.value is None or (isinstance(x.value, float)
                                and math.isnan(x.value)))

    def eval(self, frame):
        values = self.values
        if any(self._is_null_lit(x) for x in values):
            if self.negated:
                return jnp.zeros((frame.num_slots,), jnp.bool_)
            values = [x for x in values if not self._is_null_lit(x)]
            if not values:      # IN (NULL): unknown for every row
                return jnp.zeros((frame.num_slots,), jnp.bool_)
        v = self.child.eval(frame)
        vals = [x.eval(frame) for x in values]
        if _is_object(v) or any(_is_object(x) for x in vals):
            va = np.asarray(v, object)
            hit = np.zeros(va.shape[0], bool)
            for x in vals:
                hit |= np.equal(va, np.asarray(x, object)).astype(bool)
            hit = jnp.asarray(hit)
            notnull = jnp.asarray(
                np.asarray([x is not None for x in va], bool))
        else:
            v = jnp.asarray(v)
            # an empty set (an IN subquery that selected no row) holds
            # nothing
            hit = functools.reduce(
                jnp.logical_or, [jnp.equal(v, jnp.asarray(x)) for x in vals]
            ) if vals else jnp.zeros(v.shape[:1], jnp.bool_)
            notnull = (jnp.logical_not(jnp.isnan(v))
                       if jnp.issubdtype(v.dtype, jnp.floating)
                       else jnp.ones(v.shape[:1], jnp.bool_))
        # NULL [NOT] IN (...) is NULL — False in a WHERE mask either way.
        out = jnp.logical_not(hit) if self.negated else hit
        return jnp.logical_and(out, notnull)

    def __str__(self):
        op = "NOT IN" if self.negated else "IN"
        return f"({self.child} {op} ({', '.join(map(str, self.values))}))"


class StringMatch(Expr):
    """LIKE / RLIKE / contains / startswith / endswith on string columns.

    Strings live host-side (object arrays), so matching runs in numpy; null
    (None) rows are False, mirroring SQL null semantics in WHERE.
    """

    def __init__(self, kind: str, child: Expr, pattern: str,
                 negated: bool = False):
        self.kind = kind
        self.child = child
        self.pattern = pattern
        self.negated = negated

    def _matcher(self):
        import re as _re

        if self.kind == "like":
            # Escape regex metachars, then translate SQL wildcards.
            pat = _re.escape(self.pattern).replace("%", ".*").replace("_", ".")
            rx = _re.compile(pat, _re.DOTALL)
            return lambda s: rx.fullmatch(s) is not None
        if self.kind == "rlike":
            rx = _re.compile(self.pattern)
            return lambda s: rx.search(s) is not None
        if self.kind == "contains":
            return lambda s: self.pattern in s
        if self.kind == "startswith":
            return lambda s: s.startswith(self.pattern)
        if self.kind == "endswith":
            return lambda s: s.endswith(self.pattern)
        raise ValueError(self.kind)

    def eval(self, frame):
        v = self.child.eval(frame)
        va = np.asarray(v, object) if not _is_object(v) else v
        match = self._matcher()
        notnull = np.asarray([x is not None for x in va], bool)
        hit = np.asarray([x is not None and match(str(x)) for x in va], bool)
        # NULL [NOT] LIKE ... is NULL — False in a WHERE mask either way.
        out = (~hit if self.negated else hit) & notnull
        return jnp.asarray(out)

    def __str__(self):
        neg = "NOT " if self.negated else ""
        return f"({self.child} {neg}{self.kind.upper()} {self.pattern!r})"


class UdfCall(Expr):
    """Invocation of a registered UDF by name — ``callUDF`` equivalent.

    Resolution happens at eval time against the registry, matching Spark's
    name-based lookup (`DataQuality4MachineLearningApp.java:68-69,86-87`).

    Two ways to run, chosen by what the function is seen to do
    (``ops/udf.py``), never by a switch. A call whose function is
    row-local — elementwise primitives only, one 1-D result, nothing
    captured — is a compilable expression: ``with_column`` defers it like a
    builtin and it runs inside the flush's compiled program, under the
    named scope ``dq.rule``, with the plan's own copy of the function
    (``_bound``: the one the plan key fingerprinted). A call whose function
    looks at more than its own row (``x - jnp.mean(x)``, a cumulative sum)
    or cannot be traced (numpy on its input, a Python branch on a value)
    is evaluated eagerly on the frame's real columns, as every call was
    before: padding, row slices and shards would change its answer.
    Names the registry lacks resolve through the builtin function table,
    eagerly, as before.
    """

    def __init__(self, udf_name: str, args: Sequence[Expr], registry=None):
        self.udf_name = udf_name
        self.args = list(args)
        self._registry = registry
        # (fn, return_dtype), set by the flush compiler on the node of a
        # rewritten plan so that the program and its key agree
        self._bound = None

    def registry(self):
        from .udf import default_registry

        return (self._registry if self._registry is not None
                else default_registry())

    def eval(self, frame):
        bound = self._bound
        if bound is None:
            try:
                bound = self.registry().lookup(self.udf_name)
            except KeyError:
                # Name-based fallback to the builtin function table, so SQL
                # `abs(x)`, `upper(s)` etc. resolve without UDF registration
                # (Spark's FunctionRegistry builtins behave the same way).
                key = self.udf_name.lower()
                if key in _ROW_FNS:     # frame-aware: need the row count
                    return _ROW_FNS[key](frame, self.args)
                if key in _BUILTIN_FNS:
                    return Func(key, self.args).eval(frame)
                raise
        fn, return_dtype = bound
        from ..config import config as _cfg
        from ..utils import observability as _obs
        from ..utils.profiling import counters

        def call(vals):
            out = fn(*vals)
            if return_dtype is not None:
                out = jnp.asarray(out, return_dtype)
            return out

        vals = [a.eval(frame) for a in self.args]
        if any(isinstance(v, jax.core.Tracer) for v in vals):
            # Inside a compiled program (a flush that admitted this call):
            # the rule's operations carry dq.rule in their op metadata, and
            # the span, the counters and the [rows, passed] tally are the
            # flush's to keep (ops/compiler.run_pipeline) — a trace runs
            # once, the program many times.
            with _obs.scope("rule"):
                return call(vals)
        # One span per evaluation of a registered rule, beside the
        # dq.rule_evals counter. (No named scope here: a scope opened on
        # the host does not reach the metadata of the eager one-operation
        # programs a rule runs — PERF.md section 3; the dispatching span
        # is what tells them apart in a capture.)
        counters.increment("dq.rule_eager")
        with _obs.span("dq.rule", cat="dq", rule=self.udf_name,
                       rows=frame.num_slots, lowering="eager"):
            out = call(vals)
            # Data-quality observatory gate (utils/dqprof.py): ONE flag
            # read. The eager tally counts every slot the rule saw.
            if _cfg.dq_profile_enabled:
                from ..utils import dqprof as _dqprof

                _dqprof.record_eval(self.udf_name, out)
        return out

    @property
    def name(self) -> str:
        return f"{self.udf_name}({', '.join(str(a) for a in self.args)})"

    def __str__(self):
        return self.name


def _null_mask(v):
    """Per-row null indicator: None for strings, NaN for floats."""
    if _is_object(v):
        return np.asarray([x is None for x in v], dtype=bool)
    if hasattr(v, "dtype") and np.issubdtype(np.dtype(v.dtype), np.floating):
        return jnp.isnan(v)
    return jnp.zeros(np.shape(v)[:1], jnp.bool_)


def _str_map(fn, *arrays):
    """Apply a per-row Python fn over host string columns (null-safe:
    None AND float NaN — a NULL literal reaches here as NaN — yield
    NULL instead of feeding a float into a str method)."""
    def null(x):
        return x is None or (isinstance(x, float) and x != x)

    out = []
    for row in zip(*[np.asarray(a, object) for a in arrays]):
        out.append(None if any(null(x) for x in row) else fn(*row))
    return np.asarray(out, dtype=object)


def _fn_coalesce(*vals):
    out = vals[-1]
    for v in reversed(vals[:-1]):
        m = _null_mask(v)
        if _is_object(v) or _is_object(out):
            out = np.where(np.asarray(m), np.asarray(out, object),
                           np.asarray(v, object))
        else:
            out = jnp.where(m, jnp.asarray(out, float_dtype()),
                            jnp.asarray(v, float_dtype()))
    return out


def _fn_round(v, digits=None):
    # Spark's round() is HALF_UP; jnp.round is half-even. Implement half-up
    # on device: floor(x * 10^d + 0.5 * sign(x)) / 10^d.
    d = int(np.asarray(digits)[0]) if digits is not None else 0
    v = jnp.asarray(v, float_dtype())
    scale = 10.0 ** d
    scaled = v * scale
    return jnp.where(v >= 0, jnp.floor(scaled + 0.5),
                     jnp.ceil(scaled - 0.5)) / scale


def _fn_length(s):
    """Spark ``length``: null → null. Results are int32; a column
    containing nulls promotes to float with NaN (the engine's numeric-null
    convention — same promotion as ``lag`` on ints). Numeric columns cast
    to their string rendering first, like Spark."""
    if _is_object(s):
        lens = [None if x is None else len(str(x)) for x in s]
    else:
        a = np.asarray(s)
        if np.issubdtype(a.dtype, np.floating):
            # str(numpy scalar) keeps the dtype's short repr; float(x)
            # would upcast f32→f64 and render the rounding error
            # ('0.10000000149011612' instead of '0.1')
            lens = [None if np.isnan(x) else len(str(x)) for x in a]
        elif np.issubdtype(a.dtype, np.bool_):
            lens = [len(str(bool(x))) for x in a]
        else:
            lens = [len(str(int(x))) for x in a]
    return _int_or_null(lens)


def _fn_sha2(s, n):
    """Spark ``sha2(col, bitLength)``: bitLength in {0, 224, 256, 384,
    512} (0 means 256); anything else yields null per row (Spark's
    behavior), validated ONCE — not a per-row hashlib error."""
    bits = _scalar_int(n)
    if bits == 0:
        bits = 256
    if bits not in (224, 256, 384, 512):
        a = np.asarray(s, object)
        return np.full(len(a), None, dtype=object)
    algo = f"sha{bits}"
    return _str_map(lambda x: hashlib.new(algo, x.encode()).hexdigest(), s)


def _fn_substring(s, pos, length):
    # Spark substring is 1-based; pos 0 behaves like 1. pos/length may be
    # scalar literals (broadcast columns) or per-row columns (Spark's
    # substr(Column, Column) overload); a null pos/length yields null.
    pa = np.asarray(pos).ravel()
    la = np.asarray(length).ravel()

    def _at(a, i):
        v = a[i] if a.size > 1 else a[0]
        if isinstance(v, (float, np.floating)) and np.isnan(v):
            return None
        return int(v)

    out = []
    for i, x in enumerate(s):
        p, ln = _at(pa, i), _at(la, i)
        if x is None or p is None or ln is None:
            out.append(None)
            continue
        start = max(p - 1, 0)
        out.append(x[start:start + ln])
    return np.asarray(out, object)


def _scalar_value(v):
    """A literal argument of any type, row-broadcast by Lit.eval — take
    the scalar back out. A column-valued argument (more than one distinct
    value) is rejected rather than silently collapsed to row 0's value.
    Single base for :func:`_scalar_str` / :func:`_scalar_int`."""
    if isinstance(v, jax.Array) and v.size > 1:
        # a numeric literal lies broadcast on the device: compare there and
        # read two scalars, not every row (1.1e7 rows: 44 MB and a second
        # of Python)
        from ..utils.observability import host_reading
        from ..utils.profiling import counters

        flat = v.reshape(-1)
        counters.increment("frame.host_sync")
        pair = jnp.stack(
            [flat[0], jnp.all(flat == flat[0]).astype(flat.dtype)])
        with host_reading("literal.head") as rd:
            head = np.asarray(pair)
            rd.done(head.nbytes)
        arr, uniform = head[:1], bool(head[1])
    else:
        arr = np.asarray(v, object).ravel()
        uniform = len(arr) <= 1 or not any(x != arr[0] for x in arr[1:])
    if not uniform:
        raise ValueError(
            "this function argument must be a literal, not a column "
            "(per-row values are not supported)")
    x = arr[0]
    return x.item() if hasattr(x, "item") else x


def _scalar_str(v) -> str:
    return _scalar_value(v)


def _scalar_int(v) -> int:
    return int(_scalar_value(v))


def _fn_concat(*ss):
    """Spark concat: NULL if ANY argument is null (None or float NaN —
    the engine's numeric null stringifies as 'nan' otherwise)."""
    def null(x):
        return x is None or (isinstance(x, float) and x != x)

    out = []
    for row in zip(*[np.asarray(a, object) for a in ss]):
        out.append(None if any(null(x) for x in row)
                   else "".join(str(x) for x in row))
    return np.asarray(out, dtype=object)


def _fn_concat_ws(sep, *ss):
    s = _scalar_str(sep)

    def null(x):
        # None (string null) or NaN (this engine's numeric null)
        return x is None or (isinstance(x, float) and x != x)

    out = []
    for row in zip(*[np.asarray(a, object) for a in ss]):
        # Spark concat_ws SKIPS nulls instead of nulling the result
        out.append(s.join(str(x) for x in row if not null(x)))
    return np.asarray(out, dtype=object)


def _fn_split(s, pattern):
    pat = re.compile(_scalar_str(pattern))
    return _str_map(lambda x: pat.split(x), s)


def _require_array_cells(arr, fn_name):
    """Spark's analyzer rejects array functions on non-array input; the
    equivalent here is a host check on the first non-null cell (a plain
    string column would otherwise give plausible character-level
    results)."""
    a = np.asarray(arr, object)
    for cell in a:
        if cell is None:
            continue
        if not isinstance(cell, (list, tuple, np.ndarray)):
            raise ValueError(
                f"{fn_name}() expects an array column (e.g. split() or "
                f"collect_list() output), got a {type(cell).__name__} cell")
        break
    return a


def _fn_array_contains(arr, value):
    """Spark ``array_contains(col, value)``: null cell → null; the value
    is a literal scalar. List cells come from ``split``/``collect_list``."""
    v = _scalar_value(value)
    out = []
    for cell in _require_array_cells(arr, "array_contains"):
        out.append(None if cell is None else bool(v in cell))
    if any(x is None for x in out):
        return jnp.asarray(np.asarray(
            [np.nan if x is None else float(x) for x in out], np.float64),
            float_dtype())
    return jnp.asarray(np.asarray(out, np.bool_))


def _is_device_vector(arr) -> bool:
    """A vector column on the device (a classifier's probability,
    VectorAssembler's features), not a host column of list cells."""
    return isinstance(arr, jax.Array) and arr.ndim == 2


def _vector_element(arr, pos):
    """Element ``pos`` (0-based) of every row of a device vector column:
    one strided slice, nothing pulled; out of range -> null (NaN)."""
    if not 0 <= pos < arr.shape[1]:
        return jnp.full(arr.shape[:1], jnp.nan, arr.dtype)
    return arr[:, pos]


def _fn_element_at(arr, index):
    """Spark ``element_at(col, i)``: 1-based, negative counts from the
    end, out-of-bounds / null cell → null."""
    i = _scalar_int(index)
    if i == 0:
        raise ValueError("element_at index is 1-based; 0 is invalid")
    if _is_device_vector(arr):
        return _vector_element(arr, i - 1 if i > 0 else arr.shape[1] + i)
    out = []
    for cell in _require_array_cells(arr, "element_at"):
        if cell is None:
            out.append(None)
            continue
        pos = i - 1 if i > 0 else len(cell) + i
        out.append(cell[pos] if 0 <= pos < len(cell) else None)
    return np.asarray(out, object)


def _fn_array(*cols):
    """``array(c1, c2, …)``: one array cell per row from scalar columns.
    Nulls become None inside the cell — including float NaN-nulls, so
    array_join/array_distinct/sort_array see them as nulls, not
    values."""
    if not cols:
        raise ValueError("array() needs at least one column")
    host = [np.asarray(c) for c in cols]  # one device→host fetch per column
    n = len(host[0])
    out = np.empty(n, object)
    for i in range(n):
        out[i] = np.asarray(
            [None if _cell_is_null(h[i]) else h[i] for h in host], object)
    return out


def _fn_sort_array(arr, *asc):
    """``sort_array``: nulls first ascending / last descending (Spark);
    SQL's second argument is optional, defaulting to ascending."""
    up = bool(np.asarray(asc[0]).ravel()[0]) if asc else True
    out = []
    for cell in _require_array_cells(arr, "sort_array"):
        if cell is None:
            out.append(None)
            continue
        vals = [v for v in cell if v is not None]
        nulls = [None] * (len(cell) - len(vals))
        vals.sort(reverse=not up)
        out.append(np.asarray(nulls + vals if up else vals + nulls, object))
    return np.asarray(out, object)


def _fn_array_distinct(arr):
    out = []
    for cell in _require_array_cells(arr, "array_distinct"):
        if cell is None:
            out.append(None)
            continue
        seen, vals = set(), []
        for v in cell:
            k = _elem_key(v)
            if k not in seen:
                seen.add(k)
                vals.append(v)
        out.append(np.asarray(vals, object))
    return np.asarray(out, object)


def _fn_array_join(arr, delim, *null_replacement):
    """``array_join(col, delim[, nullReplacement])``: nulls are dropped
    unless a replacement is given (Spark)."""
    d = str(np.asarray(delim).ravel()[0])
    rep = (str(np.asarray(null_replacement[0]).ravel()[0])
           if null_replacement else None)
    out = []
    for cell in _require_array_cells(arr, "array_join"):
        if cell is None:
            out.append(None)
            continue
        parts = [(rep if v is None else str(v)) for v in cell
                 if v is not None or rep is not None]
        out.append(d.join(parts))
    return np.asarray(out, object)


def _fn_slice(arr, start, length):
    """``slice(col, start, length)``: 1-based; negative start counts from
    the end; start 0 errors (Spark)."""
    s = _scalar_int(start)
    ln = _scalar_int(length)
    if s == 0:
        raise ValueError("slice start index is 1-based; 0 is invalid")
    if ln < 0:
        raise ValueError("slice length must be >= 0")
    out = []
    for cell in _require_array_cells(arr, "slice"):
        if cell is None:
            out.append(None)
            continue
        pos = s - 1 if s > 0 else len(cell) + s
        if pos < 0:
            out.append(np.asarray([], object))
        else:
            out.append(np.asarray(list(cell[pos:pos + ln]), object))
    return np.asarray(out, object)


def _fn_flatten(arr):
    """``flatten``: one level of nesting removed; a null inner array
    nulls the whole result cell (Spark). Requires array<array> input —
    a flat array column (whose inner cells are scalars/strings) is
    rejected like Spark's analyzer would, instead of silently exploding
    strings into characters."""
    out = []
    for cell in _require_array_cells(arr, "flatten"):
        if cell is None:
            out.append(None)
            continue
        vals: list = []
        for inner in cell:
            if inner is None:
                vals = None
                break
            if not isinstance(inner, (list, tuple, np.ndarray)):
                raise ValueError(
                    "flatten() expects an array-of-arrays column; inner "
                    f"cells here are {type(inner).__name__}")
            vals.extend(inner)
        out.append(None if vals is None else np.asarray(vals, object))
    return np.asarray(out, object)


def _fn_nanvl(a, b):
    """``nanvl(a, b)``: b where a is NaN (numeric columns; XLA fuses)."""
    a = jnp.asarray(a)
    return jnp.where(jnp.isnan(a), jnp.asarray(b, a.dtype), a)


def _fn_format_number(x, d):
    nd = _scalar_int(d)
    if nd < 0:
        raise ValueError("format_number decimal places must be >= 0")
    vals = np.asarray(x, np.float64)
    return np.asarray([None if np.isnan(v) else format(v, f",.{nd}f")
                       for v in vals], object)


def _fn_format_string(fmt, *cols):
    """printf formatting; a null argument in a row nulls that row's
    result (the engine's general null-propagation rule — Java's
    String.format would render %s nulls as 'null' but throw on %d)."""
    fa = np.asarray(fmt, object).ravel()  # Lit: frame-length column
    f = fa[0] if fa.size else ""
    host = [np.asarray(c, object) for c in cols]
    out = []
    for i in range(len(fa)):
        args = tuple(h[i] for h in host)
        if any(_cell_is_null(v) for v in args):
            out.append(None)
            continue
        out.append(f % args)
    return np.asarray(out, object)


def _cell_is_null(v) -> bool:
    return v is None or (isinstance(v, (float, np.floating)) and np.isnan(v))


def _fn_levenshtein(l, r):  # noqa: E741 - Spark's own argument names
    def dist(a, b):
        if a is None or b is None:
            return None
        if len(a) < len(b):
            a, b = b, a
        prev = list(range(len(b) + 1))
        for i, ca in enumerate(a, 1):
            cur = [i]
            for j, cb in enumerate(b, 1):
                cur.append(min(prev[j] + 1, cur[-1] + 1,
                               prev[j - 1] + (ca != cb)))
            prev = cur
        return prev[-1]

    la = np.asarray(l, object)
    ra = np.asarray(r, object)
    out = [dist(a, b) for a, b in zip(la, ra)]
    if any(v is None for v in out):
        return np.asarray(out, object)
    return np.asarray(out, np.int32)


def _fn_get_item(arr, index):
    """Spark ``getItem``: 0-based ordinal; negative or out-of-range (or a
    null cell) → null — Spark's GetArrayItem truth table, unlike
    ``element_at`` where negatives count from the end."""
    i = _scalar_int(index)
    if _is_device_vector(arr):
        return _vector_element(arr, i)
    out = []
    for cell in _require_array_cells(arr, "getItem"):
        if cell is None or i < 0 or i >= len(cell):
            out.append(None)
        else:
            out.append(cell[i])
    return np.asarray(out, object)


def _fn_array_size(arr):
    """Spark ``size(col)``: length of a list cell; null → -1. This is
    Spark 2.4's sizeOfNull=true default — the parity target here is the
    reference's pinned Spark 2.4.4 (`pom.xml:14`); Spark 3 flipped the
    default to null."""
    return jnp.asarray(np.asarray(
        [-1 if cell is None else len(cell)
         for cell in _require_array_cells(arr, "size")], np.int32))


def _elem_key(v):
    """Hashable identity for array-set operations: Spark's set functions
    (union/intersect/except/distinct) treat null as equal to null."""
    if v is None:
        return ("\0null",)
    if isinstance(v, (float, np.floating)) and np.isnan(v):
        return ("\0nan",)
    return v


def _fn_array_position(arr, value):
    """Spark ``array_position(col, value)``: 1-based index of the FIRST
    element equal to the literal; 0 when absent; null cell → null. Null
    elements never match (Spark's null-safe scan skips them)."""
    v = _scalar_value(value)
    out = []
    for cell in _require_array_cells(arr, "array_position"):
        if cell is None or v is None:
            out.append(None)
            continue
        pos = 0
        for i, x in enumerate(cell):
            if x is not None and x == v:
                pos = i + 1
                break
        out.append(pos)
    if any(x is None for x in out):
        return np.asarray(out, object)
    return jnp.asarray(np.asarray(out, np.int64))


def _fn_array_remove(arr, element):
    """Spark ``array_remove(col, element)``: drop ALL elements equal to
    the literal; null elements are kept (they compare null, not equal);
    null cell or null element → null."""
    v = _scalar_value(element)
    out = []
    for cell in _require_array_cells(arr, "array_remove"):
        if cell is None or v is None:
            out.append(None)
        else:
            out.append(np.asarray(
                [x for x in cell if x is None or x != v], object))
    return np.asarray(out, object)


def _array_set_op(name, candidates, keep):
    """Shared scan for the three array-set functions: one dedup pass over
    ``candidates(la, lb)`` keeping elements whose key passes
    ``keep(key, right_keyset)``; null ≡ null; either cell null → null."""

    def f(a, b):
        ca = _require_array_cells(a, name)
        cb = _require_array_cells(b, name)
        out = []
        for la, lb in zip(ca, cb):
            if la is None or lb is None:
                out.append(None)
                continue
            right = {_elem_key(x) for x in lb}
            seen, vals = set(), []
            for x in candidates(la, lb):
                k = _elem_key(x)
                if k not in seen and keep(k, right):
                    seen.add(k)
                    vals.append(x)
            out.append(np.asarray(vals, object))
        return np.asarray(out, object)

    return f


# Spark ``array_union``: a's first occurrences in order, then b's unseen
# ones. ``array_intersect``/``array_except``: deduplicated elements of a
# (in a's order) present/absent in b.
_fn_array_union = _array_set_op(
    "array_union", lambda la, lb: list(la) + list(lb), lambda k, r: True)
_fn_array_intersect = _array_set_op(
    "array_intersect", lambda la, lb: la, lambda k, r: k in r)
_fn_array_except = _array_set_op(
    "array_except", lambda la, lb: la, lambda k, r: k not in r)


def _fn_arrays_overlap(a, b):
    """Spark ``arrays_overlap``: true on a shared non-null element; if
    none and both sides are non-empty but either holds a null, the
    answer is unknowable → null; otherwise false. Null cell → null."""
    ca = _require_array_cells(a, "arrays_overlap")
    cb = _require_array_cells(b, "arrays_overlap")
    out = []
    for la, lb in zip(ca, cb):
        if la is None or lb is None:
            out.append(None)
            continue
        sa = {_elem_key(x) for x in la if x is not None}
        has_null = any(x is None for x in la) or any(x is None for x in lb)
        if any(x is not None and _elem_key(x) in sa for x in lb):
            out.append(True)
        elif len(la) and len(lb) and has_null:
            out.append(None)
        else:
            out.append(False)
    if any(x is None for x in out):
        return jnp.asarray(np.asarray(
            [np.nan if x is None else float(x) for x in out], np.float64),
            float_dtype())
    return jnp.asarray(np.asarray(out, np.bool_))


def _array_extreme(which):
    """``array_min`` / ``array_max``: null elements skipped; empty or
    all-null or null cell → null (Spark)."""
    pick = min if which == "min" else max

    def f(arr):
        out = []
        for cell in _require_array_cells(arr, f"array_{which}"):
            vals = (None if cell is None
                    else [x for x in cell if x is not None])
            out.append(pick(vals) if vals else None)
        if all(isinstance(x, str) for x in out if x is not None):
            return np.asarray(out, object)
        return jnp.asarray(np.asarray(
            [np.nan if x is None else float(x) for x in out], np.float64),
            float_dtype())

    return f


def _fn_array_repeat(elem, count):
    """Spark ``array_repeat(col, count)``: one array cell per row holding
    the row's (scalar) value ``count`` times; negative count → empty."""
    n = builtins.max(0, _scalar_int(count))
    host = np.asarray(elem, object) if _is_object(np.asarray(elem)) \
        else np.asarray(elem)
    out = np.empty(len(host), object)
    for i, x in enumerate(host):
        v = None if _cell_is_null(x) else x
        out[i] = np.asarray([v] * n, object)
    return out


def _fn_sequence(start, stop, *step):
    """Spark ``sequence(start, stop[, step])``: inclusive integer range
    per row; the default step is ±1 toward stop; a step of 0 or one
    pointing away from stop errors like Spark's runtime check."""
    sa = np.asarray(start, np.float64)
    so = np.asarray(stop, np.float64)
    st = np.asarray(step[0], np.float64) if step else None
    out = np.empty(len(sa), object)
    for i in range(len(sa)):
        if np.isnan(sa[i]) or np.isnan(so[i]) or \
                (st is not None and np.isnan(st[i])):
            out[i] = None
            continue
        lo, hi = int(sa[i]), int(so[i])
        s = int(st[i]) if st is not None else (1 if hi >= lo else -1)
        if s == 0 or (hi > lo and s < 0) or (hi < lo and s > 0):
            raise ValueError(
                f"sequence boundaries: {lo} to {hi} by {s} — the step "
                "must move toward stop (Spark's requirement)")
        out[i] = np.asarray(list(range(lo, hi + (1 if s > 0 else -1), s)),
                            object)
    return out


def _fn_arrays_zip(*arrs):
    """Spark ``arrays_zip``: element-wise tuples, padded with null to the
    longest input. Spark's cells are structs; struct columns do not
    exist in this engine, so each zipped element is a fixed-width list —
    positional access (`getItem`) behaves identically."""
    cells = [_require_array_cells(a, "arrays_zip") for a in arrs]
    out = []
    for row in zip(*cells):
        if any(c is None for c in row):
            out.append(None)
            continue
        width = builtins.max((len(c) for c in row), default=0)
        out.append(np.asarray(
            [np.asarray([c[j] if j < len(c) else None for c in row], object)
             for j in range(width)], object))
    return np.asarray(out, object)


def _fn_shuffle(arr, *seed):
    """Spark ``shuffle(col)``: random permutation per cell. Spark's is
    nondeterministic per query; here a seed of −1 (or SQL's one-argument
    form) means "draw one from the OS" and any other value makes the
    column reproducible (the same extension ``rand(seed)`` exposes)."""
    s = _scalar_int(seed[0]) if seed else -1
    rng = np.random.default_rng(None if s == -1 else s)
    out = []
    for cell in _require_array_cells(arr, "shuffle"):
        if cell is None:
            out.append(None)
        else:
            out.append(np.asarray(
                [cell[j] for j in rng.permutation(len(cell))], object))
    return np.asarray(out, object)


def _fn_reverse(v):
    """Spark ``reverse``: strings reverse characterwise, arrays
    elementwise — dispatched on the first non-null cell like the other
    array/string dual functions."""
    a = np.asarray(v, object)
    first = next((c for c in a if c is not None), None)
    if isinstance(first, (list, tuple, np.ndarray)):
        return np.asarray(
            [None if c is None else np.asarray(list(c)[::-1], object)
             for c in a], object)
    return _str_map(lambda x: x[::-1], v)


class Explode(Expr):
    """Marker expression for ``F.explode(col_or_expr)`` — a GENERATOR,
    not a scalar column: it multiplies rows, so only ``Frame.select``
    (one per select, Spark's rule) and ``Frame.explode`` understand it;
    evaluating it like a column raises. ``source`` is a column name or
    any array-valued expression (``explode(split(...))``)."""

    def __init__(self, source, outer: bool = False,
                 with_position: bool = False):
        self.source = source            # str | Expr
        self.outer = outer              # explode_outer: keep null rows
        self.with_position = with_position  # posexplode: (pos, col)

    def eval(self, frame):
        raise ValueError(
            "explode() is a generator — use it inside select() (one per "
            "select) or call Frame.explode(column) directly")

    def source_values(self, frame):
        """The array column being exploded, resolved against ``frame``."""
        if isinstance(self.source, str):
            return frame._column_values(self.source)  # friendly KeyError
        return self.source.eval(frame)

    @property
    def name(self) -> str:
        return "col"                    # Spark's default generator name

    def __str__(self):
        src = self.source if isinstance(self.source, str) else str(self.source)
        fn = "posexplode" if self.with_position else             ("explode_outer" if self.outer else "explode")
        return f"{fn}({src})"


def explode(col_) -> Explode:
    return Explode(col_ if isinstance(col_, str) else col_)


def explode_outer(col_) -> Explode:
    """Like ``explode`` but null/empty cells yield one null-element row."""
    return Explode(col_ if isinstance(col_, str) else col_, outer=True)


def posexplode(col_) -> Explode:
    """``explode`` plus a 0-based element position column ``pos``
    (Spark's default (pos, col) naming)."""
    return Explode(col_ if isinstance(col_, str) else col_,
                   with_position=True)


def _fn_regexp_replace(s, pattern, replacement):
    pat = re.compile(_scalar_str(pattern))
    rep = _scalar_str(replacement)
    return _str_map(lambda x: pat.sub(rep, x), s)


def _fn_regexp_extract(s, pattern, idx):
    pat = re.compile(_scalar_str(pattern))
    gi = _scalar_int(idx)

    def one(x):
        m = pat.search(x)
        return "" if m is None else (m.group(gi) or "")

    return _str_map(one, s)


def _int_or_null(vals):
    """int32 column, NaN-promoting to float when nulls are present (the
    engine's numeric-null convention; Spark: null in → null out)."""
    if any(v is None for v in vals):
        return jnp.asarray(np.asarray(
            [np.nan if v is None else float(v) for v in vals], np.float64),
            float_dtype())
    return jnp.asarray(np.asarray(vals, np.int32))


def _fn_instr(s, sub):
    needle = _scalar_str(sub)
    arr = np.asarray(s, object)
    return _int_or_null(
        [None if x is None else x.find(needle) + 1 for x in arr])


def _fn_locate(sub, s, pos=None):
    # Spark: locate(substr, str[, pos]) — note the flipped argument order
    needle = _scalar_str(sub)
    start = (_scalar_int(pos) if pos is not None else 1)
    arr = np.asarray(s, object)
    return _int_or_null(
        [None if x is None else x.find(needle, max(start - 1, 0)) + 1
         for x in arr])


def _fn_lpad(s, length, pad):
    ln = _scalar_int(length)
    p = _scalar_str(pad)

    def one(x):
        if ln <= 0:
            return ""                         # Spark: non-positive len → ""
        if len(x) >= ln:
            return x[:ln]
        fill = (p * ln)[:ln - len(x)] if p else ""
        return fill + x

    return _str_map(one, s)


def _fn_rpad(s, length, pad):
    ln = _scalar_int(length)
    p = _scalar_str(pad)

    def one(x):
        if ln <= 0:
            return ""                         # Spark: non-positive len → ""
        if len(x) >= ln:
            return x[:ln]
        fill = (p * ln)[:ln - len(x)] if p else ""
        return x + fill

    return _str_map(one, s)


def _fn_translate(s, matching, replace):
    # first occurrence of a repeated matching char wins (Spark semantics)
    mapping: dict = {}
    rep = _scalar_str(replace)
    for i, a in enumerate(_scalar_str(matching)):
        if a not in mapping:
            mapping[a] = rep[i] if i < len(rep) else None
    table = str.maketrans(mapping)
    return _str_map(lambda x: x.translate(table), s)


# Frame-aware nullary/row functions reached by NAME from SQL (the fluent
# constructors build RowFunc nodes directly): they need the row count or
# the evaluated argument's dtype, so they bypass the value-only builtin
# table and receive (frame, arg_exprs) from UdfCall.eval.
def _lit_arg(expr, what):
    if isinstance(expr, Lit):
        return expr.value
    if isinstance(expr, UnaryOp) and expr.op == "-" \
            and isinstance(expr.child, Lit):
        return -expr.child.value
    raise ValueError(f"{what} must be a literal")


def _row_generator(sql_name, kind, takes_seed=False):
    def f(frame, args):
        if not takes_seed and args:
            raise ValueError(f"{sql_name}() takes no arguments")
        if args and len(args) > 1:
            raise ValueError(f"{sql_name}([seed]) takes at most one "
                             "argument")
        seed = int(_lit_arg(args[0], f"{sql_name} seed")) if args else None
        # RowFunc.eval folds negative seeds, so SQL and fluent paths
        # produce identical streams for the same seed
        return RowFunc(kind, seed).eval(frame)
    return f


def _row_uuid(frame, args):
    if args:
        raise ValueError("uuid() takes no arguments")
    import uuid as _uuid

    return np.asarray([str(_uuid.uuid4()) for _ in range(frame.num_slots)],
                      dtype=object)


def _row_typeof(frame, args):
    if len(args) != 1:
        raise ValueError("typeof(expr) takes one argument")
    v = args[0].eval(frame)
    if _is_object(v):
        name = "string"
    else:
        dt = jnp.asarray(v).dtype
        name = ("boolean" if dt == jnp.bool_
                else "int" if jnp.issubdtype(dt, jnp.integer)
                else "double")
    return np.asarray([name] * frame.num_slots, dtype=object)


_ROW_FNS = {
    "monotonically_increasing_id":
        _row_generator("monotonically_increasing_id", "id"),
    "spark_partition_id": _row_generator("spark_partition_id",
                                         "partition_id"),
    "rand": _row_generator("rand", "rand", takes_seed=True),
    "randn": _row_generator("randn", "randn", takes_seed=True),
    "uuid": _row_uuid,
    "typeof": _row_typeof,
}


_BUILTIN_FNS = {
    # numeric (device, elementwise — XLA fuses into neighbors)
    "abs": lambda v: jnp.abs(v),
    "sqrt": lambda v: jnp.sqrt(jnp.asarray(v, float_dtype())),
    "exp": lambda v: jnp.exp(jnp.asarray(v, float_dtype())),
    "log": lambda v: jnp.log(jnp.asarray(v, float_dtype())),
    "log10": lambda v: jnp.log10(jnp.asarray(v, float_dtype())),
    "pow": lambda a, b: jnp.power(jnp.asarray(a, float_dtype()),
                                  jnp.asarray(b, float_dtype())),
    "power": lambda a, b: jnp.power(jnp.asarray(a, float_dtype()),
                                    jnp.asarray(b, float_dtype())),
    "floor": lambda v: jnp.floor(jnp.asarray(v, float_dtype())),
    "ceil": lambda v: jnp.ceil(jnp.asarray(v, float_dtype())),
    "round": _fn_round,
    "sign": lambda v: jnp.sign(jnp.asarray(v, float_dtype())),
    "signum": lambda v: jnp.sign(jnp.asarray(v, float_dtype())),
    # fmax/fmin skip NaN (Spark: greatest/least ignore nulls, NULL only
    # when every operand is null)
    "greatest": lambda *vs: functools.reduce(jnp.fmax,
                                             [jnp.asarray(v) for v in vs]),
    "least": lambda *vs: functools.reduce(jnp.fmin,
                                          [jnp.asarray(v) for v in vs]),
    "isnan": lambda v: jnp.isnan(jnp.asarray(v, float_dtype())),
    "coalesce": _fn_coalesce,
    "sin": lambda v: jnp.sin(jnp.asarray(v, float_dtype())),
    "cos": lambda v: jnp.cos(jnp.asarray(v, float_dtype())),
    "tan": lambda v: jnp.tan(jnp.asarray(v, float_dtype())),
    "asin": lambda v: jnp.arcsin(jnp.asarray(v, float_dtype())),
    "acos": lambda v: jnp.arccos(jnp.asarray(v, float_dtype())),
    "atan": lambda v: jnp.arctan(jnp.asarray(v, float_dtype())),
    "atan2": lambda a, b: jnp.arctan2(jnp.asarray(a, float_dtype()),
                                      jnp.asarray(b, float_dtype())),
    "sinh": lambda v: jnp.sinh(jnp.asarray(v, float_dtype())),
    "cosh": lambda v: jnp.cosh(jnp.asarray(v, float_dtype())),
    "tanh": lambda v: jnp.tanh(jnp.asarray(v, float_dtype())),
    "degrees": lambda v: jnp.degrees(jnp.asarray(v, float_dtype())),
    "radians": lambda v: jnp.radians(jnp.asarray(v, float_dtype())),
    "cbrt": lambda v: jnp.cbrt(jnp.asarray(v, float_dtype())),
    "expm1": lambda v: jnp.expm1(jnp.asarray(v, float_dtype())),
    "log1p": lambda v: jnp.log1p(jnp.asarray(v, float_dtype())),
    "log2": lambda v: jnp.log2(jnp.asarray(v, float_dtype())),
    "mod": lambda a, b: _sql_mod(jnp.asarray(a, float_dtype()),
                                 jnp.asarray(b, float_dtype())),
    # positive modulus (Spark pmod): result sign follows the DIVISOR
    "pmod": lambda a, b: jnp.where(
        jnp.asarray(b, float_dtype()) == 0, jnp.nan,
        jnp.mod(jnp.asarray(a, float_dtype()),
                jnp.asarray(b, float_dtype()))),
    "hypot": lambda a, b: jnp.hypot(jnp.asarray(a, float_dtype()),
                                    jnp.asarray(b, float_dtype())),
    "rint": lambda v: jnp.round(jnp.asarray(v, float_dtype())),
    # string (host object arrays; TPUs do not hold strings)
    "upper": lambda s: _str_map(str.upper, s),
    "lower": lambda s: _str_map(str.lower, s),
    "trim": lambda s: _str_map(str.strip, s),
    "ltrim": lambda s: _str_map(str.lstrip, s),
    "rtrim": lambda s: _str_map(str.rstrip, s),
    "length": _fn_length,
    "concat": lambda *ss: _fn_concat(*ss),
    "md5": lambda s: _str_map(
        lambda x: hashlib.md5(x.encode()).hexdigest(), s),
    "sha1": lambda s: _str_map(
        lambda x: hashlib.sha1(x.encode()).hexdigest(), s),
    "sha2": _fn_sha2,
    "base64": lambda s: _str_map(
        lambda x: _b64.b64encode(x.encode()).decode(), s),
    # Spark's unbase64 yields BINARY; string cells here hold the bytes as
    # latin-1 (lossless byte-per-char), so non-UTF8 payloads can't crash
    "unbase64": lambda s: _str_map(
        lambda x: _b64.b64decode(x.encode()).decode("latin-1"), s),
    "substring": _fn_substring,
    "substr": _fn_substring,
    "concat_ws": _fn_concat_ws,
    "split": _fn_split,
    "array_contains": _fn_array_contains,
    "element_at": _fn_element_at,
    "get_item": _fn_get_item,
    "array": _fn_array,
    "sort_array": _fn_sort_array,
    "array_distinct": _fn_array_distinct,
    "array_join": _fn_array_join,
    "slice": _fn_slice,
    "flatten": _fn_flatten,
    "nanvl": _fn_nanvl,
    "format_number": _fn_format_number,
    "format_string": _fn_format_string,
    "levenshtein": _fn_levenshtein,
    "size": _fn_array_size,
    "regexp_replace": _fn_regexp_replace,
    "regexp_extract": _fn_regexp_extract,
    "instr": _fn_instr,
    "locate": _fn_locate,
    "lpad": _fn_lpad,
    "rpad": _fn_rpad,
    # left/right are SQL keywords (join types); the parser special-cases
    # the call forms LEFT(s, n) / RIGHT(s, n) into these
    "left": lambda s, n: _str_map(
        lambda x: x[:_scalar_int(n)] if _scalar_int(n) > 0 else "", s),
    "right": lambda s, n: _str_map(
        lambda x: x[-_scalar_int(n):] if _scalar_int(n) > 0 else "", s),
    "overlay": lambda s, r, pos, ln=None: _str_map(
        lambda x, y: x[:_scalar_int(pos) - 1] + y
        + x[_scalar_int(pos) - 1
            + (_scalar_int(ln) if ln is not None else len(y)):], s, r),
    "repeat": lambda s, n: _str_map(
        lambda x: x * _scalar_int(n), s),
    "reverse": _fn_reverse,
    "array_position": _fn_array_position,
    "array_remove": _fn_array_remove,
    "array_union": _fn_array_union,
    "array_intersect": _fn_array_intersect,
    "array_except": _fn_array_except,
    "arrays_overlap": _fn_arrays_overlap,
    "array_min": _array_extreme("min"),
    "array_max": _array_extreme("max"),
    "array_repeat": _fn_array_repeat,
    "sequence": _fn_sequence,
    "arrays_zip": _fn_arrays_zip,
    "shuffle": _fn_shuffle,
    "initcap": lambda s: _str_map(
        lambda x: " ".join(w.capitalize() for w in x.split(" ")), s),
    "translate": _fn_translate,
}


class Func(Expr):
    """Builtin scalar function call (the ``org.apache.spark.sql.functions``
    scalar set). Numeric fns are jnp ops XLA fuses into neighboring
    expressions; string fns run host-side on object columns."""

    def __init__(self, fn_name: str, args: Sequence[Expr]):
        key = fn_name.lower()
        if key not in _BUILTIN_FNS:
            raise ValueError(f"unknown function {fn_name!r}")
        self.fn_name = key
        self.args = list(args)

    def eval(self, frame):
        vals = [a.eval(frame) for a in self.args]
        return _BUILTIN_FNS[self.fn_name](*vals)

    @property
    def name(self) -> str:
        return f"{self.fn_name}({', '.join(str(a) for a in self.args)})"

    def __str__(self):
        return self.name


class CaseWhen(Expr):
    """``when(cond, value).when(...).otherwise(value)`` / SQL CASE WHEN.

    Folds into nested ``jnp.where`` (one fused select chain on device).
    A missing ELSE yields null (NaN for numeric, None for strings) —
    Spark semantics.
    """

    def __init__(self, branches, otherwise=None):
        self.branches = list(branches)  # [(cond Expr, value Expr), ...]
        self.otherwise_expr = otherwise

    def when(self, condition: Expr, value) -> "CaseWhen":
        value = value if isinstance(value, Expr) else Lit(value)
        return CaseWhen(self.branches + [(condition, value)],
                        self.otherwise_expr)

    def otherwise(self, value) -> "CaseWhen":
        value = value if isinstance(value, Expr) else Lit(value)
        return CaseWhen(self.branches, value)

    def eval(self, frame):
        conds = [c.eval(frame) for c, _ in self.branches]
        vals = [v.eval(frame) for _, v in self.branches]
        stringy = any(_is_object(v) for v in vals)
        if self.otherwise_expr is not None:
            out = self.otherwise_expr.eval(frame)
            stringy = stringy or _is_object(out)
        elif stringy:
            out = np.full((frame.num_slots,), None, dtype=object)
        else:
            out = jnp.full((frame.num_slots,), jnp.nan, float_dtype())
        if stringy:
            out = np.asarray(out, object)
            for c, v in zip(reversed(conds), reversed(vals)):
                out = np.where(np.asarray(c, bool), np.asarray(v, object), out)
            return out
        for c, v in zip(reversed(conds), reversed(vals)):
            v = jnp.asarray(v)
            if jnp.issubdtype(jnp.asarray(out).dtype, jnp.floating) or \
                    jnp.issubdtype(v.dtype, jnp.floating):
                v = jnp.asarray(v, float_dtype())
                out = jnp.asarray(out, float_dtype())
            out = jnp.where(jnp.asarray(c), v, out)
        return out

    @property
    def name(self) -> str:
        parts = " ".join(f"WHEN {c} THEN {v}" for c, v in self.branches)
        tail = f" ELSE {self.otherwise_expr}" if self.otherwise_expr is not None else ""
        return f"CASE {parts}{tail} END"

    def __str__(self):
        return self.name


# -- public constructors (mirrors org.apache.spark.sql.functions) ----------

def col(name: str) -> Col:
    return Col(name)


def lit(value) -> Lit:
    return Lit(value)


def call_udf(name: str, *args) -> UdfCall:
    """``functions.callUDF`` equivalent; accepts Exprs or column names."""
    exprs = [a if isinstance(a, Expr) else Col(a) if isinstance(a, str) else Lit(a)
             for a in args]
    return UdfCall(name, exprs)


# Spark naming alias
callUDF = call_udf


def _coerce(a) -> Expr:
    return a if isinstance(a, Expr) else Col(a) if isinstance(a, str) else Lit(a)


def fn(name: str, *args) -> Func:
    """Builtin scalar function by name (``functions.expr``-style escape)."""
    return Func(name, [_coerce(a) for a in args])


def when(condition: Expr, value) -> CaseWhen:
    """``functions.when`` — start a CASE chain; extend with ``.when`` and
    close with ``.otherwise`` (missing otherwise ⇒ null)."""
    return CaseWhen([]).when(condition, value)


def _make_fn(fname: str):
    def f(*args):
        return fn(fname, *args)

    f.__name__ = fname
    f.__qualname__ = fname
    f.__doc__ = f"``functions.{fname}`` equivalent (builtin scalar fn)."
    return f


sql_abs = _make_fn("abs")
sqrt = _make_fn("sqrt")
exp = _make_fn("exp")
log = _make_fn("log")
log10 = _make_fn("log10")
pow = _make_fn("pow")
floor = _make_fn("floor")
ceil = _make_fn("ceil")
sql_round = _make_fn("round")
signum = _make_fn("signum")
greatest = _make_fn("greatest")
least = _make_fn("least")
isnan = _make_fn("isnan")
coalesce = _make_fn("coalesce")
nvl = _make_fn("coalesce")          # Spark: nvl(a, b) == coalesce(a, b)
md5 = _make_fn("md5")
sha1 = _make_fn("sha1")
sha2 = _make_fn("sha2")
base64 = _make_fn("base64")
def array_contains(col_, value) -> Func:
    """PySpark shape: the value is a plain literal (or a Lit), never a
    column reference."""
    return Func("array_contains",
                [_coerce(col_), value if isinstance(value, Expr)
                 else Lit(value)])


def element_at(col_, index: int) -> Func:
    return Func("element_at", [_coerce(col_), Lit(int(index))])


def size(col_) -> Func:
    return Func("size", [_coerce(col_)])
unbase64 = _make_fn("unbase64")
upper = _make_fn("upper")
lower = _make_fn("lower")
trim = _make_fn("trim")
ltrim = _make_fn("ltrim")
rtrim = _make_fn("rtrim")
length = _make_fn("length")
concat = _make_fn("concat")
substring = _make_fn("substring")
array = _make_fn("array")
array_distinct = _make_fn("array_distinct")
flatten = _make_fn("flatten")
nanvl = _make_fn("nanvl")
format_number = _make_fn("format_number")
levenshtein = _make_fn("levenshtein")


def format_string(fmt: str, *cols) -> "Func":
    """``format_string('%s: %d', c1, c2)`` — printf formatting; the
    format is a literal, not a column name (``fn`` would coerce a bare
    string to a Col)."""
    return fn("format_string", Lit(fmt), *cols)


def sort_array(col_, asc: bool = True) -> "Func":
    """``sort_array(col[, asc])``: nulls first ascending / last
    descending (Spark)."""
    return fn("sort_array", col_, Lit(bool(asc)))


def array_join(col_, delimiter: str, null_replacement=None) -> "Func":
    """``array_join(col, delim[, nullReplacement])``: nulls dropped
    unless a replacement is given (Spark)."""
    if null_replacement is None:
        return fn("array_join", col_, Lit(delimiter))
    return fn("array_join", col_, Lit(delimiter), Lit(null_replacement))


def slice(col_, start: int, length: int) -> "Func":  # noqa: A001 - Spark name
    """``slice(col, start, length)``: 1-based, negative start counts from
    the end (Spark)."""
    return fn("slice", col_, Lit(int(start)), Lit(int(length)))


def array_position(col_, value) -> Func:
    """``array_position(col, value)`` — 1-based first match, 0 if absent."""
    return Func("array_position",
                [_coerce(col_), value if isinstance(value, Expr)
                 else Lit(value)])


def array_remove(col_, element) -> Func:
    """``array_remove(col, element)`` — drop every equal element."""
    return Func("array_remove",
                [_coerce(col_), element if isinstance(element, Expr)
                 else Lit(element)])


array_union = _make_fn("array_union")
array_intersect = _make_fn("array_intersect")
array_except = _make_fn("array_except")
arrays_overlap = _make_fn("arrays_overlap")
array_min = _make_fn("array_min")
array_max = _make_fn("array_max")
arrays_zip = _make_fn("arrays_zip")


def array_repeat(col_, count: int) -> Func:
    """``array_repeat(col, count)`` — the count is a literal."""
    return Func("array_repeat", [_coerce(col_), Lit(int(count))])


def sequence(start, stop, step=None) -> Func:
    """``sequence(start, stop[, step])`` — inclusive range per row."""
    args = [_coerce(start), _coerce(stop)]
    if step is not None:
        args.append(_coerce(step))
    return Func("sequence", args)


def shuffle(col_, seed: int = None) -> Func:
    """``shuffle(col)`` — random per-cell permutation; the optional seed
    is an extension (Spark's is always nondeterministic)."""
    return Func("shuffle",
                [_coerce(col_), Lit(-1 if seed is None else int(seed))])


class RowFunc(Expr):
    """Frame-length generator column (``rand``/``randn``/row ids): knows
    nothing about other columns, only how many row slots the frame has.
    Seeded generators are deterministic per expression instance, like
    Spark's ``rand(seed)`` per plan node."""

    _KINDS = ("rand", "randn", "id", "partition_id")

    def __init__(self, kind: str, seed=None):
        if kind not in self._KINDS:
            raise ValueError(f"unknown row generator {kind!r}")
        self.kind = kind
        self.seed = seed

    def eval(self, frame):
        n = frame.num_slots
        if self.kind == "id":
            return jnp.arange(n, dtype=int_dtype())
        if self.kind == "partition_id":
            # one logical partition: the id is 0 everywhere (the same
            # no-op stance as repartition/coalesce)
            return jnp.zeros((n,), dtype=int_dtype())
        seed = self.seed
        if seed is not None and int(seed) < 0:
            # numpy's default_rng rejects negatives; fold deterministically
            seed = int(seed) & 0x7FFFFFFFFFFFFFFF
        rng = np.random.default_rng(seed)
        host = (rng.uniform(size=n) if self.kind == "rand"
                else rng.standard_normal(size=n))
        return jnp.asarray(host.astype(np.dtype(float_dtype())))

    @property
    def name(self) -> str:
        if self.kind == "id":
            return "monotonically_increasing_id()"
        if self.kind == "partition_id":
            return "spark_partition_id()"
        seed = "" if self.seed is None else str(self.seed)
        return f"{self.kind}({seed})"

    def __str__(self):
        return self.name


def rand(seed=None) -> RowFunc:
    """Uniform [0, 1) column (Spark ``rand``); deterministic per seed."""
    return RowFunc("rand", seed)


def randn(seed=None) -> RowFunc:
    """Standard-normal column (Spark ``randn``)."""
    return RowFunc("randn", seed)


def monotonically_increasing_id() -> RowFunc:
    """Row ids 0..n-1 (Spark's are only partition-monotone; one logical
    partition here makes them consecutive)."""
    return RowFunc("id")


def spark_partition_id() -> RowFunc:
    """Always 0 — one logical partition (see repartition's no-op note)."""
    return RowFunc("partition_id")


def expr(sql_text: str) -> Expr:
    """Spark ``F.expr``: one SQL expression (the same grammar as
    ``selectExpr`` items — CAST, arithmetic, functions, AS alias).
    Aggregates/window items are not scalar expressions; use
    ``selectExpr``/``session.sql`` for those."""
    from ..sql.parser import _Parser, tokenize

    p = _Parser(tokenize(sql_text))
    item = p.parse_select_item()
    p.expect("eof")  # trailing tokens = a typo, not a second expression
    if not isinstance(item, Expr):
        raise ValueError(
            f"expr({sql_text!r}) is not a scalar expression; use "
            "selectExpr()/session.sql() for aggregates and window items")
    return item


sin = _make_fn("sin")
cos = _make_fn("cos")
tan = _make_fn("tan")
asin = _make_fn("asin")
acos = _make_fn("acos")
atan = _make_fn("atan")
atan2 = _make_fn("atan2")
sinh = _make_fn("sinh")
cosh = _make_fn("cosh")
tanh = _make_fn("tanh")
degrees = _make_fn("degrees")
radians = _make_fn("radians")
cbrt = _make_fn("cbrt")
expm1 = _make_fn("expm1")
log1p = _make_fn("log1p")
log2 = _make_fn("log2")
hypot = _make_fn("hypot")
rint = _make_fn("rint")
repeat = _make_fn("repeat")
reverse = _make_fn("reverse")
initcap = _make_fn("initcap")


# String functions whose pattern/pad/separator arguments are LITERALS in
# Spark's signatures — a bare str there must not coerce to a column ref.
def concat_ws(sep: str, *cols) -> Func:
    return Func("concat_ws", [Lit(sep)] + [_coerce(c) for c in cols])


def split(col_, pattern: str) -> Func:
    return Func("split", [_coerce(col_), Lit(pattern)])


def regexp_replace(col_, pattern: str, replacement: str) -> Func:
    return Func("regexp_replace",
                [_coerce(col_), Lit(pattern), Lit(replacement)])


def regexp_extract(col_, pattern: str, idx: int) -> Func:
    return Func("regexp_extract", [_coerce(col_), Lit(pattern), Lit(idx)])


def instr(col_, substr: str) -> Func:
    return Func("instr", [_coerce(col_), Lit(substr)])


def locate(substr: str, col_, pos: int = 1) -> Func:
    return Func("locate", [Lit(substr), _coerce(col_), Lit(pos)])


def lpad(col_, length: int, pad: str) -> Func:
    return Func("lpad", [_coerce(col_), Lit(length), Lit(pad)])


def rpad(col_, length: int, pad: str) -> Func:
    return Func("rpad", [_coerce(col_), Lit(length), Lit(pad)])


def translate(col_, matching: str, replace: str) -> Func:
    return Func("translate", [_coerce(col_), Lit(matching), Lit(replace)])


def isnull(c) -> Expr:
    return _coerce(c).is_null()


# ---------------------------------------------------------------------------
# Date / time functions
#
# TPU-native representation: a DATE is a float device column of days since
# the Unix epoch with NaN as null — the engine's numeric-null convention,
# so null dates are visible to isnull()/filters/aggregates (an int
# sentinel would silently pass comparisons). Day counts are exact in
# float32 far past any calendar. Field extraction (year/month/day...) is
# vectorized integer math ON DEVICE (civil-from-days, Hinnant's
# algorithm), not a host datetime loop; fields come back float with NaN
# propagated. Parsing and formatting cross the host boundary like every
# string op. Epoch SECONDS exceed float32's exact-integer range, so
# unix_timestamp requires the x64 mode and yields float64.
# ---------------------------------------------------------------------------


def _strptime_format(java_fmt: str) -> str:
    """Translate a Spark/Java date pattern into strptime, run by run.
    Unsupported pattern letters raise instead of silently producing
    all-null columns."""
    runs = {"yyyy": "%Y", "yy": "%y", "MM": "%m", "M": "%m",
            "dd": "%d", "d": "%d", "HH": "%H", "H": "%H",
            "mm": "%M", "m": "%M", "ss": "%S", "s": "%S"}
    out = []
    i = 0
    while i < len(java_fmt):
        c = java_fmt[i]
        if c.isalpha():
            j = i
            while j < len(java_fmt) and java_fmt[j] == c:
                j += 1
            run = java_fmt[i:j]
            if run not in runs:
                raise ValueError(
                    f"unsupported date-format token {run!r} in "
                    f"{java_fmt!r} (supported: {sorted(runs)})")
            out.append(runs[run])
            i = j
        else:
            out.append("%%" if c == "%" else c)
            i += 1
    return "".join(out)


def _parse_dates(s, fmt: str, unit_seconds: bool):
    """Host parse of a string column → epoch days (engine float, NaN null)
    or epoch seconds (float64, x64 required). Unparseable / null rows →
    NaN (Spark yields null)."""
    import datetime as _dt

    py_fmt = _strptime_format(fmt)
    arr = np.asarray(s, object)
    out = np.empty(len(arr), np.float64)
    epoch = _dt.datetime(1970, 1, 1)
    for i, x in enumerate(arr):
        if x is None:
            out[i] = np.nan
            continue
        try:
            t = _dt.datetime.strptime(str(x).strip(), py_fmt)
        except ValueError:
            out[i] = np.nan
            continue
        delta = t - epoch
        out[i] = delta.total_seconds() if unit_seconds else delta.days
    if unit_seconds:
        import jax

        if not jax.config.jax_enable_x64:
            raise ValueError(
                "unix_timestamp requires jax_enable_x64: epoch seconds "
                "exceed float32's exact-integer range (use to_date for "
                "day-resolution work)")
        return jnp.asarray(out, jnp.float64)
    return jnp.asarray(out, float_dtype())


def _civil_from_days(z):
    """days-since-epoch → (year, month, day), vectorized integer device math
    (Howard Hinnant's civil_from_days)."""
    z = z + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097                                   # [0, 146096]
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)          # [0, 365]
    mp = (5 * doy + 2) // 153                                # [0, 11]
    d = doy - (153 * mp + 2) // 5 + 1                        # [1, 31]
    m = jnp.where(mp < 10, mp + 3, mp - 9)                   # [1, 12]
    return jnp.where(m <= 2, y + 1, y), m, d


def _days_from_civil(y, m, d):
    """(year, month, day) → days since epoch, device integer math."""
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _fn_to_date(s, fmt=None):
    f = _scalar_str(fmt) if fmt is not None else "yyyy-MM-dd"
    return _parse_dates(s, f, unit_seconds=False)


def _fn_unix_timestamp(s, fmt=None):
    f = _scalar_str(fmt) if fmt is not None else "yyyy-MM-dd HH:mm:ss"
    return _parse_dates(s, f, unit_seconds=True)


def _date_field(which: str):
    def f(days):
        days = _days_of(days)
        null = jnp.isnan(days)
        z = jnp.where(null, 0, days).astype(jnp.int32)
        y, m, d = _civil_from_days(z)
        if which == "year":
            v = y
        elif which == "month":
            v = m
        elif which == "dayofmonth":
            v = d
        elif which == "quarter":
            v = (m - 1) // 3 + 1
        elif which == "dayofweek":
            # Spark: 1 = Sunday ... 7 = Saturday; epoch day 0 was a Thursday
            v = (z + 4) % 7 + 1
        else:  # dayofyear
            v = z - _days_from_civil(y, jnp.ones_like(y),
                                     jnp.ones_like(y)) + 1
        return jnp.where(null, jnp.nan, v.astype(days.dtype))
    return f


_DATETIME_RE = None


def _parse_datetime_cell(x):
    """Spark's lenient implicit string→timestamp cast for one cell:
    ``yyyy[-M[-d]][ T hh:mm[:ss[.fff]]][anything]`` — partial dates
    default missing fields to 01/midnight, and trailing content
    (timezone suffixes, junk after a complete prefix) is ignored like
    Spark's ``stringToDate``/``stringToTimestamp``. Returns a datetime
    or None."""
    import datetime as _dt
    import re

    global _DATETIME_RE
    if _DATETIME_RE is None:
        _DATETIME_RE = re.compile(
            r"^(\d{4})(?:-(\d{1,2})(?:-(\d{1,2})"
            r"(?:[ T](\d{1,2}):(\d{2})(?::(\d{2})(?:\.\d+)?)?)?)?)?")
    if x is None:
        return None
    s = str(x).strip()
    m = _DATETIME_RE.match(s)
    if not m:
        return None
    y, mo, d, hh, mi, ss = m.groups()
    try:
        return _dt.datetime(int(y), int(mo or 1), int(d or 1),
                            int(hh or 0), int(mi or 0), int(ss or 0))
    except ValueError:          # e.g. month 13 / day 32
        return None


# Numeric date/time values carry no type tag in this engine (dates are
# epoch DAYS — to_date's output; timestamps epoch SECONDS —
# to_timestamp/unix_timestamp's output), so mixed compositions like
# hour(to_timestamp(s)) disambiguate by magnitude: |v| ≥ 1e8 is seconds
# (1e8 s = 1973-03-03; 1e8 days is year 275760, far past Spark's own
# 9999-12-31 ceiling). The one ambiguous window — timestamps inside
# 1966-10-31..1973-03-03 — would need day-resolution fallbacks; Spark's
# typed DATE/TIMESTAMP split has no such window, which is the cost of a
# float-only column model and is documented here deliberately.
_SECONDS_CUTOFF = 1e8


def _days_of(v):
    """Epoch-day view of a date operand with Spark's implicit cast: string
    (object) columns accept full dates, timestamp-shaped strings (the
    time part is dropped for day math), and partial 'yyyy[-MM]' forms —
    unparseable/null → NaN; numeric columns are epoch days (``to_date``)
    or epoch seconds (``to_timestamp``), split at ``_SECONDS_CUTOFF``."""
    if _is_object(v):
        import datetime as _dt

        epoch = _dt.date(1970, 1, 1)
        out = np.empty(len(v), np.float64)
        for i, x in enumerate(v):
            t = _parse_datetime_cell(x)
            out[i] = np.nan if t is None else (t.date() - epoch).days
        return jnp.asarray(out, float_dtype())
    arr = jnp.asarray(v, float_dtype())
    return jnp.where(jnp.abs(arr) >= _SECONDS_CUTOFF,
                     jnp.floor(arr / 86400.0), arr)


def _fn_datediff(end, start):
    return _days_of(end) - _days_of(start)         # NaN propagates


def _fn_date_add(days, n):
    return _days_of(days) + _scalar_int(n)


def _fn_date_sub(days, n):
    return _days_of(days) - _scalar_int(n)


def _fn_date_format(days, fmt):
    import datetime as _dt

    py_fmt = _strptime_format(_scalar_str(fmt))
    if _is_object(days):
        # string input: Spark casts to TIMESTAMP, so time-of-day survives
        # into HH/mm/ss format tokens
        return np.asarray(
            [None if (t := _parse_datetime_cell(x)) is None
             else t.strftime(py_fmt) for x in days], object)
    arr = np.asarray(days, np.float64)
    epoch = _dt.date(1970, 1, 1)
    return np.asarray(
        [None if np.isnan(v)
         else (epoch + _dt.timedelta(days=int(v))).strftime(py_fmt)
         for v in arr], object)


def _fn_from_unixtime(secs, fmt=None):
    import datetime as _dt

    py_fmt = _strptime_format(
        _scalar_str(fmt) if fmt is not None else "yyyy-MM-dd HH:mm:ss")
    arr = np.asarray(secs, np.float64)
    epoch = _dt.datetime(1970, 1, 1)
    return np.asarray(
        [None if np.isnan(v)
         else (epoch + _dt.timedelta(seconds=int(v))).strftime(py_fmt)
         for v in arr], object)


_BUILTIN_FNS.update({
    "to_date": _fn_to_date,
    "unix_timestamp": _fn_unix_timestamp,
    "from_unixtime": _fn_from_unixtime,
    "date_format": _fn_date_format,
    "datediff": _fn_datediff,
    "date_add": _fn_date_add,
    "date_sub": _fn_date_sub,
    "year": _date_field("year"),
    "month": _date_field("month"),
    "dayofmonth": _date_field("dayofmonth"),
    "dayofweek": _date_field("dayofweek"),
    "dayofyear": _date_field("dayofyear"),
    "quarter": _date_field("quarter"),
})


def to_date(col_, fmt: str = None) -> Func:
    args = [_coerce(col_)] + ([Lit(fmt)] if fmt is not None else [])
    return Func("to_date", args)


def unix_timestamp(col_, fmt: str = None) -> Func:
    args = [_coerce(col_)] + ([Lit(fmt)] if fmt is not None else [])
    return Func("unix_timestamp", args)


def from_unixtime(col_, fmt: str = None) -> Func:
    args = [_coerce(col_)] + ([Lit(fmt)] if fmt is not None else [])
    return Func("from_unixtime", args)


def date_format(col_, fmt: str) -> Func:
    return Func("date_format", [_coerce(col_), Lit(fmt)])


def date_add(col_, n: int) -> Func:
    return Func("date_add", [_coerce(col_), Lit(n)])


def date_sub(col_, n: int) -> Func:
    return Func("date_sub", [_coerce(col_), Lit(n)])


datediff = _make_fn("datediff")
year = _make_fn("year")
month = _make_fn("month")
dayofmonth = _make_fn("dayofmonth")
dayofweek = _make_fn("dayofweek")
dayofyear = _make_fn("dayofyear")
quarter = _make_fn("quarter")


def current_date() -> Expr:
    """Today as epoch days (host clock, evaluated at call time)."""
    import datetime as _dt

    return Lit(float((_dt.date.today() - _dt.date(1970, 1, 1)).days))


# -- timestamp-resolution family ------------------------------------------
# Date values are epoch DAYS (to_date's output); timestamps are epoch
# SECONDS and require jax_enable_x64 (seconds exceed float32's exact
# range — the same contract unix_timestamp enforces). A numeric input to
# the time-of-day extractors is epoch days, i.e. midnight, so
# hour/minute/second are 0 — exactly Spark's hour(CAST(x AS DATE)).


def _time_field(which: str):
    def f(v):
        if _is_object(v):
            sel = {"hour": lambda t: t.hour, "minute": lambda t: t.minute,
                   "second": lambda t: t.second}[which]
            out = [None if (t := _parse_datetime_cell(x)) is None else sel(t)
                   for x in np.asarray(v, object)]
            return jnp.asarray(np.asarray(
                [np.nan if x is None else float(x) for x in out], np.float64),
                float_dtype())
        # numeric: epoch seconds carry time-of-day; epoch days (below the
        # magnitude cutoff) are midnight ⇒ 0, Spark's hour(CAST AS DATE)
        host = np.asarray(v, np.float64)
        if np.any(np.abs(host[~np.isnan(host)]) >= _SECONDS_CUTOFF):
            # time-of-day of an epoch-second value needs sub-second
            # precision the f32 column cannot carry — same contract as
            # to_timestamp/unix_timestamp, raised instead of silently
            # returning minutes/seconds that are off by the f32 quantum
            _require_x64(f"{which}() on epoch-second (timestamp) values")
        arr = jnp.asarray(v, jnp.float64)
        sod = jnp.where(jnp.abs(arr) >= _SECONDS_CUTOFF,
                        jnp.mod(arr, 86400.0), 0.0)
        val = {"hour": sod // 3600.0,
               "minute": jnp.mod(sod, 3600.0) // 60.0,
               "second": jnp.mod(sod, 60.0) // 1.0}[which]
        return jnp.where(jnp.isnan(arr), jnp.nan,
                         val).astype(float_dtype())
    return f


def _fn_weekofyear(v):
    """ISO-8601 week number (Spark's WEEKOFYEAR). Host calendar math —
    the ISO rule (week containing the year's first Thursday) is not
    worth a branchless device expression for frame-sized date columns."""
    import datetime as _dt

    days = np.asarray(_days_of(v), np.float64)
    epoch = _dt.date(1970, 1, 1)
    out = [np.nan if np.isnan(d)
           else float((epoch + _dt.timedelta(days=int(d))).isocalendar()[1])
           for d in days]
    return jnp.asarray(np.asarray(out, np.float64), float_dtype())


def _fn_last_day(v):
    """``last_day(date)``: last day of the date's month, device civil
    math — the 1st of the next month minus one day."""
    days = _days_of(v)
    null = jnp.isnan(days)
    z = jnp.where(null, 0, days).astype(jnp.int32)
    y, m, _ = _civil_from_days(z)
    ny = jnp.where(m == 12, y + 1, y)
    nm = jnp.where(m == 12, 1, m + 1)
    out = _days_from_civil(ny, nm, jnp.ones_like(ny)) - 1
    return jnp.where(null, jnp.nan, out.astype(days.dtype))


def _days_in_month(y, m):
    ny = jnp.where(m == 12, y + 1, y)
    nm = jnp.where(m == 12, 1, m + 1)
    one = jnp.ones_like(y)
    return (_days_from_civil(ny, nm, one) - _days_from_civil(y, m, one))


def _fn_add_months(v, n):
    """``add_months(date, n)``: calendar month shift with Spark's
    day-of-month clamp (Jan 31 + 1 month = Feb 28/29)."""
    k = _scalar_int(n)
    days = _days_of(v)
    null = jnp.isnan(days)
    z = jnp.where(null, 0, days).astype(jnp.int32)
    y, m, d = _civil_from_days(z)
    total = y * 12 + (m - 1) + k
    ny = total // 12
    nm = total % 12 + 1
    nd = jnp.minimum(d, _days_in_month(ny, nm))
    out = _days_from_civil(ny, nm, nd)
    return jnp.where(null, jnp.nan, out.astype(days.dtype))


def _fn_months_between(end, start, *round_off):
    """Spark ``months_between``: whole calendar months when both dates
    fall on the same day-of-month or both on month-ends; otherwise the
    fractional remainder uses Spark's fixed /31 divisor. Day resolution
    (this engine's date values carry no time-of-day); roundOff (default
    true) rounds to 8 places like Spark."""
    ro = bool(_scalar_value(round_off[0])) if round_off else True
    d1 = _days_of(end)
    d2 = _days_of(start)
    null = jnp.isnan(d1) | jnp.isnan(d2)
    z1 = jnp.where(null, 0, d1).astype(jnp.int32)
    z2 = jnp.where(null, 0, d2).astype(jnp.int32)
    y1, m1, dd1 = _civil_from_days(z1)
    y2, m2, dd2 = _civil_from_days(z2)
    months = ((y1 - y2) * 12 + (m1 - m2)).astype(jnp.float64)
    both_last = (dd1 == _days_in_month(y1, m1)) & \
                (dd2 == _days_in_month(y2, m2))
    whole = (dd1 == dd2) | both_last
    frac = (dd1 - dd2).astype(jnp.float64) / 31.0
    out = jnp.where(whole, months, months + frac)
    if ro:
        out = jnp.round(out * 1e8) / 1e8
    return jnp.where(null, jnp.nan, out.astype(float_dtype()))


_DOW_NAMES = {"su": 1, "sun": 1, "sunday": 1, "mo": 2, "mon": 2,
              "monday": 2, "tu": 3, "tue": 3, "tuesday": 3, "we": 4,
              "wed": 4, "wednesday": 4, "th": 5, "thu": 5, "thursday": 5,
              "fr": 6, "fri": 6, "friday": 6, "sa": 7, "sat": 7,
              "saturday": 7}


def _fn_next_day(v, day_name):
    """``next_day(date, 'Mon')``: the first named weekday STRICTLY after
    the date; an unrecognized name yields null (Spark 2.4's behavior,
    not an error)."""
    name = str(_scalar_value(day_name) or "").strip().lower()
    target = _DOW_NAMES.get(name)
    days = _days_of(v)
    null = jnp.isnan(days)
    if target is None:
        return jnp.full_like(days, jnp.nan)
    z = jnp.where(null, 0, days).astype(jnp.int32)
    dow = (z + 4) % 7 + 1              # 1 = Sunday (epoch day 0: Thursday)
    delta = (target - dow) % 7
    delta = jnp.where(delta == 0, 7, delta)
    return jnp.where(null, jnp.nan, (z + delta).astype(days.dtype))


def _fn_trunc(v, fmt):
    """``trunc(date, fmt)``: year/month truncation to epoch days; an
    unsupported format yields null (Spark)."""
    f = str(_scalar_str(fmt)).lower()
    days = _days_of(v)
    null = jnp.isnan(days)
    z = jnp.where(null, 0, days).astype(jnp.int32)
    y, m, _ = _civil_from_days(z)
    one = jnp.ones_like(y)
    if f in ("year", "yyyy", "yy"):
        out = _days_from_civil(y, one, one)
    elif f in ("month", "mon", "mm"):
        out = _days_from_civil(y, m, one)
    else:
        return jnp.full_like(days, jnp.nan)
    return jnp.where(null, jnp.nan, out.astype(days.dtype))


def _require_x64(what: str):
    import jax

    if not jax.config.jax_enable_x64:
        raise ValueError(
            f"{what} requires jax_enable_x64: epoch seconds exceed "
            "float32's exact-integer range (use to_date/trunc for "
            "day-resolution work)")


def _seconds_of(v):
    """Epoch-seconds view: strings via the lenient timestamp cast;
    numeric epoch seconds pass through, epoch days (below the magnitude
    cutoff) are midnight of that day."""
    if _is_object(v):
        import datetime as _dt

        out = np.empty(len(v), np.float64)
        epoch = _dt.datetime(1970, 1, 1)
        for i, x in enumerate(np.asarray(v, object)):
            t = _parse_datetime_cell(x)
            out[i] = np.nan if t is None else (t - epoch).total_seconds()
        return out
    arr = np.asarray(v, np.float64)
    return np.where(np.abs(arr) >= _SECONDS_CUTOFF, arr, arr * 86400.0)


def _fn_to_timestamp(s, *fmt):
    """``to_timestamp(col[, fmt])`` → epoch seconds (float64, x64
    required). Without a format the lenient cast accepts partial
    dates/timestamps like Spark; with one, strict strptime like
    unix_timestamp."""
    _require_x64("to_timestamp")
    if fmt:
        return _parse_dates(s, _scalar_str(fmt[0]), unit_seconds=True)
    return jnp.asarray(_seconds_of(s), jnp.float64)


def _fn_date_trunc(fmt, v):
    """``date_trunc(fmt, col)`` → truncated epoch seconds (x64). Spark's
    argument order (format first) — the reverse of ``trunc``."""
    _require_x64("date_trunc")
    f = str(_scalar_str(fmt)).lower()
    secs = jnp.asarray(_seconds_of(v), jnp.float64)
    null = jnp.isnan(secs)
    if f in ("second", "minute", "hour", "day", "week"):
        width = {"second": 1.0, "minute": 60.0, "hour": 3600.0,
                 "day": 86400.0, "week": 7 * 86400.0}[f]
        # epoch day 0 is a Thursday; ISO weeks start Monday (epoch day 4)
        shift = 4 * 86400.0 if f == "week" else 0.0
        out = jnp.floor((secs - shift) / width) * width + shift
    elif f in ("year", "yyyy", "yy", "month", "mon", "mm", "quarter"):
        z = jnp.where(null, 0, jnp.floor(secs / 86400.0)).astype(jnp.int32)
        y, m, _ = _civil_from_days(z)
        one = jnp.ones_like(y)
        tm = one if f in ("year", "yyyy", "yy") else (
            ((m - 1) // 3) * 3 + 1 if f == "quarter" else m)
        out = _days_from_civil(y, tm, one).astype(jnp.float64) * 86400.0
    else:
        return jnp.full_like(secs, jnp.nan)
    return jnp.where(null, jnp.nan, out)


_BUILTIN_FNS.update({
    "hour": _time_field("hour"),
    "minute": _time_field("minute"),
    "second": _time_field("second"),
    "weekofyear": _fn_weekofyear,
    "last_day": _fn_last_day,
    "add_months": _fn_add_months,
    "months_between": _fn_months_between,
    "next_day": _fn_next_day,
    "trunc": _fn_trunc,
    "to_timestamp": _fn_to_timestamp,
    "date_trunc": _fn_date_trunc,
})


hour = _make_fn("hour")
minute = _make_fn("minute")
second = _make_fn("second")
weekofyear = _make_fn("weekofyear")
last_day = _make_fn("last_day")


def add_months(col_, n: int) -> Func:
    return Func("add_months", [_coerce(col_), Lit(int(n))])


def months_between(end, start, roundOff: bool = True) -> Func:  # noqa: N803
    return Func("months_between",
                [_coerce(end), _coerce(start), Lit(bool(roundOff))])


def next_day(col_, day_of_week: str) -> Func:
    return Func("next_day", [_coerce(col_), Lit(str(day_of_week))])


def trunc(col_, fmt: str) -> Func:
    return Func("trunc", [_coerce(col_), Lit(str(fmt))])


def date_trunc(fmt: str, col_) -> Func:
    return Func("date_trunc", [Lit(str(fmt)), _coerce(col_)])


def to_timestamp(col_, fmt: str = None) -> Func:
    args = [_coerce(col_)] + ([Lit(fmt)] if fmt is not None else [])
    return Func("to_timestamp", args)


def current_timestamp() -> Expr:
    """Now as epoch seconds (host clock, evaluated at call time). Exact
    under jax_enable_x64; under float32 the value quantizes to ~±64 s —
    use x64 for timestamp work (the same caveat as unix_timestamp)."""
    import time as _time

    return Lit(float(int(_time.time())))


# -- math / bitwise batch --------------------------------------------------


def _fn_bround(v, *digits):
    """Spark ``bround``: HALF_EVEN (banker's) rounding — jnp.round's
    native mode, unlike ``round``'s HALF_UP."""
    d = _scalar_int(digits[0]) if digits else 0
    v = jnp.asarray(v, float_dtype())
    scale = 10.0 ** d
    return jnp.round(v * scale) / scale


def _exact_int64_col(vals):
    """Column of 64-bit ints (Nones allowed). With x64 off, jnp would
    silently wrap these to int32 (the conftest turns x64 on, so the wrap
    would only bite library users) — exact host objects instead."""
    import jax

    if any(x is None for x in vals):
        return np.asarray(vals, object)
    if jax.config.jax_enable_x64:
        return jnp.asarray(np.asarray(vals, np.int64))
    return np.asarray(vals, object)


def _fn_factorial(v):
    """Spark ``factorial``: defined on 0..20 (long range), anything else
    → null. Host exact integers — 20! exceeds float64's exact range, so
    device float math would corrupt the top values."""
    import math

    arr = np.asarray(v, np.float64)
    out = [None if (np.isnan(x) or x < 0 or x > 20 or x != int(x))
           else math.factorial(int(x)) for x in arr]
    return _exact_int64_col(out)


def _int64_of(v):
    """Two's-complement int64 view of a numeric column (bit ops / radix
    formatting); NaN rows tracked separately by the caller."""
    arr = np.asarray(v, np.float64)
    mask = np.isnan(arr)
    return np.where(mask, 0, arr).astype(np.int64), mask


def _fn_hex(v):
    """Spark ``hex``: numbers → uppercase hex of the two's-complement
    long; strings → hex of the UTF-8 bytes."""
    a = np.asarray(v, object) if _is_object(v) else None
    if a is not None:
        return _str_map(lambda x: x.encode().hex().upper(), v)
    z, mask = _int64_of(v)
    return np.asarray(
        [None if m else format(int(x) & _MASK64, "X")
         for x, m in zip(z, mask)], object)


def _fn_unhex(s):
    """Spark ``unhex``: hex string → BINARY; bytes surface as latin-1
    text (the ``unbase64`` convention); malformed input → null."""
    def u(x):
        try:
            return bytes.fromhex(x).decode("latin-1")
        except ValueError:
            return None
    return _str_map(u, s)


def _fn_bin(v):
    """Spark ``bin``: binary text of the two's-complement long
    (Java ``Long.toBinaryString``)."""
    z, mask = _int64_of(v)
    return np.asarray(
        [None if m else format(int(x) & _MASK64, "b")
         for x, m in zip(z, mask)], object)


def _fn_conv(s, from_base, to_base):
    """Spark ``conv(num, fromBase, toBase)``: radix conversion over
    string digits, uppercase output, malformed input → null. A negative
    toBase renders signed output; otherwise the value is treated as an
    unsigned 64-bit quantity (Spark/Hive semantics)."""
    fb = _scalar_int(from_base)
    tb = _scalar_int(to_base)
    digits = "0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ"
    if not (2 <= fb <= 36 and 2 <= builtins.abs(tb) <= 36):
        return np.asarray([None] * len(np.asarray(s, object)), object)

    def one(x):
        t = str(x).strip().upper()
        neg = t.startswith("-")
        if neg:
            t = t[1:]
        try:
            val = int(t, fb) if t else None
        except ValueError:
            # Hive keeps the longest valid prefix
            for j in range(len(t), 0, -1):
                try:
                    val = int(t[:j], fb)
                    break
                except ValueError:
                    continue
            else:
                val = None
        if val is None:
            return None
        if neg:
            val = -val
        if tb > 0:
            val &= 0xFFFFFFFFFFFFFFFF          # unsigned 64-bit view
            base, sign = tb, ""
        else:
            if val < -(1 << 63) or val >= (1 << 63):
                val &= 0xFFFFFFFFFFFFFFFF
                val -= (1 << 64) if val >= (1 << 63) else 0
            base, sign = -tb, ("-" if val < 0 else "")
            val = builtins.abs(val)
        if val == 0:
            return "0"
        out = []
        while val:
            val, r = divmod(val, base)
            out.append(digits[r])
        return sign + "".join(reversed(out))

    return _str_map(one, s)


def _nullable_int32_col(vals):
    """Column of small ints with Nones: object array when any null,
    else a device int32 column (the 32-bit sibling of _exact_int64_col)."""
    if any(x is None for x in vals):
        return np.asarray(vals, object)
    return jnp.asarray(np.asarray(vals, np.int32))


def _fn_ascii(s):
    """Spark ``ascii``: code point of the first character; '' → 0."""
    return _nullable_int32_col(
        [None if x is None else (ord(str(x)[0]) if str(x) else 0)
         for x in np.asarray(s, object)])


def _fn_crc32(s):
    import zlib

    out = [None if x is None else zlib.crc32(str(x).encode())
           for x in np.asarray(s, object)]
    return _exact_int64_col(out)  # crc32 > 2^31 must not wrap int32


def _shift_fn(which: str):
    """shiftleft / shiftright (arithmetic) / shiftrightunsigned (logical)
    over the int32 view (Spark's int overloads; its long overloads need
    explicit casts there too)."""

    def f(v, n):
        k = _scalar_int(n) % 32
        arr = np.asarray(v, np.float64)
        mask = np.isnan(arr)
        z = np.where(mask, 0, arr).astype(np.int32)
        if which == "left":
            r = np.left_shift(z, k)
        elif which == "right":
            r = np.right_shift(z, k)
        else:
            r = np.right_shift(z.view(np.uint32), k).view(np.int32)
        out = r.astype(np.float64)
        return jnp.asarray(np.where(mask, np.nan, out), float_dtype()) \
            if mask.any() else jnp.asarray(r)

    return f


def _fn_bitwise_not(v):
    arr = np.asarray(v, np.float64)
    mask = np.isnan(arr)
    r = ~np.where(mask, 0, arr).astype(np.int32)
    if mask.any():
        return jnp.asarray(np.where(mask, np.nan, r.astype(np.float64)),
                           float_dtype())
    return jnp.asarray(r)


def _fn_nullif(a, b):
    """SQL ``nullif(a, b)``: null where equal, else a."""
    if _is_object(a) or _is_object(b):
        va = np.asarray(a, object)
        vb = np.asarray(b, object)
        return np.asarray(
            [None if (x is not None and y is not None and x == y) else x
             for x, y in zip(va, vb)], object)
    va = jnp.asarray(a, float_dtype())
    vb = jnp.asarray(b, float_dtype())
    return jnp.where(va == vb, jnp.nan, va)


def _fn_nvl2(a, b, c):
    """Spark ``nvl2(a, b, c)``: b where a is not null, else c."""
    nulls = _null_mask(a)
    if _is_object(b) or _is_object(c):
        vb = np.asarray(b, object)
        vc = np.asarray(c, object)
        m = np.asarray(nulls)
        return np.asarray([y if keep else x
                           for x, y, keep in zip(vc, vb, ~m)], object)
    return jnp.where(nulls, jnp.asarray(c, float_dtype()),
                     jnp.asarray(b, float_dtype()))


def _fn_substring_index(s, delim, count):
    """Spark ``substring_index(str, delim, count)``: everything before
    the count-th delimiter (from the left for positive counts, from the
    right for negative); count 0 → ''."""
    d = _scalar_str(delim)
    k = _scalar_int(count)

    def one(x):
        if k == 0 or not d:
            return ""
        parts = x.split(d)
        if k > 0:
            return d.join(parts[:k])
        return d.join(parts[builtins.max(len(parts) + k, 0):])

    return _str_map(one, s)


_SOUNDEX_CODES = {**{c: "1" for c in "BFPV"}, **{c: "2" for c in "CGJKQSXZ"},
                  **{c: "3" for c in "DT"}, "L": "4",
                  **{c: "5" for c in "MN"}, "R": "6"}


def _fn_soundex(s):
    """American Soundex (Spark/Hive variant): 4 chars, H/W transparent
    between same-coded consonants, non-alpha input passed through."""
    def one(x):
        if not x or not x[0].isalpha():
            return x
        u = x.upper()
        code = [u[0]]
        prev = _SOUNDEX_CODES.get(u[0], "")
        for ch in u[1:]:
            c = _SOUNDEX_CODES.get(ch)
            if c is None:
                # vowels reset the run; H/W do not
                if ch not in "HW":
                    prev = ""
                continue
            if c != prev:
                code.append(c)
                if len(code) == 4:
                    break
            prev = c
        return "".join(code).ljust(4, "0")

    return _str_map(one, s)


def _fn_encode(s, charset):
    cs = _scalar_str(charset)
    return _str_map(lambda x: x.encode(cs).decode("latin-1"), s)


def _fn_decode(s, charset):
    cs = _scalar_str(charset)
    return _str_map(lambda x: x.encode("latin-1").decode(cs), s)


def _fn_octet_length(s):
    return _nullable_int32_col(
        [None if x is None else len(str(x).encode())
         for x in np.asarray(s, object)])


def _fn_bit_length(s):
    return _nullable_int32_col(
        [None if x is None else len(str(x).encode()) * 8
         for x in np.asarray(s, object)])


# -- Spark hash functions --------------------------------------------------
# Spark's Murmur3_x86_32 (seed 42) and XxHash64 (seed 42), bit-exact to
# the JVM implementations for the types this engine holds: numeric
# columns hash as DOUBLE (doubleToLongBits → hashLong), strings as their
# UTF-8 bytes. Null children are skipped (the running hash passes
# through), like Spark's HashExpression.

_M3_C1 = 0xCC9E2D51
_M3_C2 = 0x1B873593
_MASK32 = 0xFFFFFFFF
_MASK64 = 0xFFFFFFFFFFFFFFFF


def _rotl32(x, r):
    return ((x << r) | (x >> (32 - r))) & _MASK32


def _m3_mix_k1(k1):
    k1 = (k1 * _M3_C1) & _MASK32
    k1 = _rotl32(k1, 15)
    return (k1 * _M3_C2) & _MASK32


def _m3_mix_h1(h1, k1):
    h1 ^= k1
    h1 = _rotl32(h1, 13)
    return (h1 * 5 + 0xE6546B64) & _MASK32


def _m3_fmix(h1, length):
    h1 ^= length
    h1 ^= h1 >> 16
    h1 = (h1 * 0x85EBCA6B) & _MASK32
    h1 ^= h1 >> 13
    h1 = (h1 * 0xC2B2AE35) & _MASK32
    return h1 ^ (h1 >> 16)


def _m3_hash_long(value, seed):
    low = value & _MASK32
    high = (value >> 32) & _MASK32
    h1 = _m3_mix_h1(seed, _m3_mix_k1(low))
    h1 = _m3_mix_h1(h1, _m3_mix_k1(high))
    return _m3_fmix(h1, 8)


def _m3_hash_bytes(data: bytes, seed: int) -> int:
    """Spark's hashUnsafeBytes: 4-byte little-endian blocks, then each
    remaining byte runs a FULL mix round on its SIGNED value — not the
    standard murmur3 tail, so only aligned inputs match public vectors."""
    h1 = seed
    n_aligned = len(data) - len(data) % 4
    for i in range(0, n_aligned, 4):
        block = int.from_bytes(data[i:i + 4], "little")
        h1 = _m3_mix_h1(h1, _m3_mix_k1(block))
    for i in range(n_aligned, len(data)):
        b = data[i]
        signed = b - 256 if b >= 128 else b
        h1 = _m3_mix_h1(h1, _m3_mix_k1(signed & _MASK32))
    return _m3_fmix(h1, len(data))


_XX_P1 = 0x9E3779B185EBCA87
_XX_P2 = 0xC2B2AE3D27D4EB4F
_XX_P3 = 0x165667B19E3779F9
_XX_P4 = 0x85EBCA77C2B2AE63
_XX_P5 = 0x27D4EB2F165667C5


def _rotl64(x, r):
    return ((x << r) | (x >> (64 - r))) & _MASK64


def _xx_fmix(h):
    h ^= h >> 33
    h = (h * _XX_P2) & _MASK64
    h ^= h >> 29
    h = (h * _XX_P3) & _MASK64
    return h ^ (h >> 32)


def _xx_round(acc, inp):
    acc = (acc + inp * _XX_P2) & _MASK64
    return (_rotl64(acc, 31) * _XX_P1) & _MASK64


def _xx_hash_long(value, seed):
    h = (seed + _XX_P5 + 8) & _MASK64
    h ^= _xx_round(0, value & _MASK64)
    h = (_rotl64(h, 27) * _XX_P1 + _XX_P4) & _MASK64
    return _xx_fmix(h)


def _xx_hash_bytes(data: bytes, seed: int) -> int:
    n = len(data)
    if n >= 32:
        v1 = (seed + _XX_P1 + _XX_P2) & _MASK64
        v2 = (seed + _XX_P2) & _MASK64
        v3 = seed
        v4 = (seed - _XX_P1) & _MASK64
        i = 0
        while i <= n - 32:
            v1 = _xx_round(v1, int.from_bytes(data[i:i + 8], "little"))
            v2 = _xx_round(v2, int.from_bytes(data[i + 8:i + 16], "little"))
            v3 = _xx_round(v3, int.from_bytes(data[i + 16:i + 24], "little"))
            v4 = _xx_round(v4, int.from_bytes(data[i + 24:i + 32], "little"))
            i += 32
        h = (_rotl64(v1, 1) + _rotl64(v2, 7) + _rotl64(v3, 12)
             + _rotl64(v4, 18)) & _MASK64
        for v in (v1, v2, v3, v4):
            h = ((h ^ _xx_round(0, v)) * _XX_P1 + _XX_P4) & _MASK64
    else:
        h = (seed + _XX_P5) & _MASK64
        i = 0
    h = (h + n) & _MASK64
    while i <= n - 8:
        h ^= _xx_round(0, int.from_bytes(data[i:i + 8], "little"))
        h = (_rotl64(h, 27) * _XX_P1 + _XX_P4) & _MASK64
        i += 8
    if i <= n - 4:
        h ^= (int.from_bytes(data[i:i + 4], "little") * _XX_P1) & _MASK64
        h = (_rotl64(h, 23) * _XX_P2 + _XX_P3) & _MASK64
        i += 4
    while i < n:
        h ^= (data[i] * _XX_P5) & _MASK64
        h = (_rotl64(h, 11) * _XX_P1) & _MASK64
        i += 1
    return _xx_fmix(h)


def _spark_hash(cols, seed, hash_long, hash_bytes, signed_bits):
    """The HashExpression fold: the running hash seeds each child's hash;
    null children pass through."""
    import struct

    host = [np.asarray(c, object) if _is_object(c) else np.asarray(c)
            for c in cols]
    n = len(host[0]) if host else 0
    out = []
    for i in range(n):
        h = seed
        for col_vals in host:
            x = col_vals[i]
            if x is None or (isinstance(x, (float, np.floating))
                             and np.isnan(x)):
                continue
            if isinstance(x, str):
                h = hash_bytes(x.encode(), h)
            else:
                bits = struct.unpack("<q", struct.pack("<d", float(x)))[0]
                h = hash_long(bits, h)
        # two's-complement back to signed
        if h >= (1 << (signed_bits - 1)):
            h -= (1 << signed_bits)
        out.append(h)
    if signed_bits == 32:
        return jnp.asarray(np.asarray(out, np.int32))
    return _exact_int64_col(out)  # 64-bit hashes must not wrap under x64-off


def _fn_hash(*cols):
    return _spark_hash(cols, 42, _m3_hash_long, _m3_hash_bytes, 32)


def _fn_xxhash64(*cols):
    return _spark_hash(cols, 42, _xx_hash_long, _xx_hash_bytes, 64)


# -- JSON ------------------------------------------------------------------


_JSON_SEG_RE = None


def _json_traverse(doc, path: str):
    """Walk ``$.key[idx].key…``; returns a sentinel-wrapped value, or None
    for missing values AND malformed paths — every character of the path
    must belong to a valid segment (Spark yields null on bad paths, so a
    skipped-garbage walk like finditer would invent answers)."""
    import re as _re

    global _JSON_SEG_RE
    if _JSON_SEG_RE is None:
        _JSON_SEG_RE = _re.compile(
            r"\.([A-Za-z_][A-Za-z0-9_]*)|\[(\d+)\]")
    if not path.startswith("$"):
        return None
    cur = doc
    pos = 1
    while pos < len(path):
        m = _JSON_SEG_RE.match(path, pos)
        if m is None:
            return None                      # malformed residue
        pos = m.end()
        key, idx = m.group(1), m.group(2)
        if key is not None:
            if not isinstance(cur, dict) or key not in cur:
                return None
            cur = cur[key]
        else:
            j = int(idx)
            if not isinstance(cur, list) or j >= len(cur):
                return None
            cur = cur[j]
    return (cur,)


def _json_render(v):
    """Spark's get_json_object rendering: strings bare, scalars via
    their JSON lexeme, containers as compact JSON text."""
    import json as _json

    if v is None:
        return None
    if isinstance(v, str):
        return v
    if v is True or v is False:
        return "true" if v else "false"
    if isinstance(v, (dict, list)):
        return _json.dumps(v, separators=(",", ":"))
    return repr(v) if not isinstance(v, float) else _json.dumps(v)


def _fn_get_json_object(s, path):
    import json as _json

    p = _scalar_str(path)

    def one(x):
        try:
            doc = _json.loads(x)
        except (ValueError, TypeError):
            return None
        hit = _json_traverse(doc, p)
        return None if hit is None else _json_render(hit[0])

    return _str_map(one, s)


_BUILTIN_FNS.update({
    "bround": _fn_bround,
    "factorial": _fn_factorial,
    "hex": _fn_hex,
    "unhex": _fn_unhex,
    "bin": _fn_bin,
    "conv": _fn_conv,
    "ascii": _fn_ascii,
    "crc32": _fn_crc32,
    "shiftleft": _shift_fn("left"),
    "shiftright": _shift_fn("right"),
    "shiftrightunsigned": _shift_fn("unsigned"),
    "bitwise_not": _fn_bitwise_not,
    "nullif": _fn_nullif,
    "nvl2": _fn_nvl2,
    "ifnull": _fn_coalesce,
    "nvl": _fn_coalesce,
    "substring_index": _fn_substring_index,
    "soundex": _fn_soundex,
    "encode": _fn_encode,
    "decode": _fn_decode,
    "bit_length": _fn_bit_length,
    "octet_length": _fn_octet_length,
    "hash": _fn_hash,
    "xxhash64": _fn_xxhash64,
    "get_json_object": _fn_get_json_object,
})


def bround(col_, scale: int = 0) -> Func:
    return Func("bround", [_coerce(col_), Lit(int(scale))])


factorial = _make_fn("factorial")
hex = _make_fn("hex")  # noqa: A001 - Spark name
unhex = _make_fn("unhex")
bin = _make_fn("bin")  # noqa: A001 - Spark name
ascii = _make_fn("ascii")  # noqa: A001 - Spark name
crc32 = _make_fn("crc32")
soundex = _make_fn("soundex")
bit_length = _make_fn("bit_length")
octet_length = _make_fn("octet_length")
hash = _make_fn("hash")  # noqa: A001 - Spark name
xxhash64 = _make_fn("xxhash64")
nullif = _make_fn("nullif")
nvl2 = _make_fn("nvl2")
ifnull = _make_fn("ifnull")


def conv(col_, from_base: int, to_base: int) -> Func:
    return Func("conv", [_coerce(col_), Lit(int(from_base)),
                         Lit(int(to_base))])


def shiftleft(col_, n: int) -> Func:
    return Func("shiftleft", [_coerce(col_), Lit(int(n))])


def shiftright(col_, n: int) -> Func:
    return Func("shiftright", [_coerce(col_), Lit(int(n))])


def shiftrightunsigned(col_, n: int) -> Func:
    return Func("shiftrightunsigned", [_coerce(col_), Lit(int(n))])


def bitwiseNOT(col_) -> Func:  # noqa: N802 - Spark name
    return Func("bitwise_not", [_coerce(col_)])


def substring_index(col_, delim: str, count: int) -> Func:
    return Func("substring_index",
                [_coerce(col_), Lit(str(delim)), Lit(int(count))])


def encode(col_, charset: str) -> Func:
    return Func("encode", [_coerce(col_), Lit(str(charset))])


def decode(col_, charset: str) -> Func:
    return Func("decode", [_coerce(col_), Lit(str(charset))])


def get_json_object(col_, path: str) -> Func:
    return Func("get_json_object", [_coerce(col_), Lit(str(path))])


class JsonTuple(Expr):
    """``json_tuple(col, 'f1', 'f2', …)`` — a multi-COLUMN generator
    (Spark's only non-row-multiplying generator): one output column per
    requested top-level field, default names c0…cN. ``Frame.select``
    expands it; evaluating it as a scalar column raises, like Explode."""

    def __init__(self, source, fields):
        self.source = _coerce(source)
        self.fields = [str(f) for f in fields]
        if not self.fields:
            raise ValueError("json_tuple needs at least one field name")

    def eval(self, frame):
        raise ValueError(
            "json_tuple() is a generator producing multiple columns — "
            "use it as a top-level select item")

    def columns(self, frame):
        """→ [(name, object-array), …] for Frame.select."""
        import json as _json

        src = np.asarray(self.source.eval(frame), object)
        cols = {f: np.empty(len(src), object) for f in self.fields}
        for i, x in enumerate(src):
            try:
                doc = _json.loads(x) if x is not None else None
            except (ValueError, TypeError):
                doc = None
            for f in self.fields:
                v = None
                if isinstance(doc, dict) and f in doc:
                    v = _json_render(doc[f])
                cols[f][i] = v
        return [(f"c{j}", cols[f]) for j, f in enumerate(self.fields)]


def json_tuple(col_, *fields) -> JsonTuple:
    return JsonTuple(col_, fields)


# -- higher-order array functions (Spark 2.4's lambda family) --------------
#
# transform/filter/exists evaluate the lambda body ONCE, vectorized, over
# a scope frame holding every element of every cell flattened into one
# column (outer columns repeat per element, so `x -> x + other_col`
# works); results regroup by cell length. aggregate folds over element
# POSITIONS — one vectorized body eval per position j updating the rows
# whose cells reach j — so the eval count is max_len, not total
# elements. Array cells are host objects, so this is host orchestration
# around device-capable body evals, the same split as the rest of the
# array family.


class Lambda:
    """``x -> body`` / ``(acc, x) -> body``: parameter names plus a body
    Expr in which the parameters appear as Col references (the scope
    frame binds them, shadowing outer columns like Spark)."""

    def __init__(self, params, body: Expr):
        self.params = [str(p) for p in params]
        self.body = body


_LAM_COUNTER = [0]


def _fresh_lambda(fn, n_params):
    """PySpark-3-style fluent lambda: the Python callable receives Col
    expressions for freshly named parameters and returns the body."""
    names = []
    for _ in range(n_params):
        names.append(f"_lam_x{_LAM_COUNTER[0]}")
        _LAM_COUNTER[0] += 1
    body = fn(*[Col(n) for n in names])
    if not isinstance(body, Expr):
        body = Lit(body)
    return Lambda(names, body)


def _host_col(vals):
    return np.asarray(vals, object) if _is_object(vals) else np.asarray(vals)


def _column_from_elems(elems):
    """Element list (Nones allowed) → engine column: strings stay host
    objects, everything else becomes a NaN-null float column."""
    if any(isinstance(v, str) for v in elems):
        return np.asarray(elems, object)
    return jnp.asarray(np.asarray(
        [np.nan if v is None or (isinstance(v, (float, np.floating))
                                 and np.isnan(v)) else float(v)
         for v in elems], np.float64), float_dtype())


def _referenced_cols(e, out: set):
    """Col names reachable from an Expr tree — generic attribute walk, so
    new Expr kinds are covered without registration. Used to repeat only
    the outer columns a lambda body actually touches."""
    if isinstance(e, Col):
        out.add(e.name)
        return
    if not isinstance(e, Expr):
        return
    for v in vars(e).values():
        if isinstance(v, Expr):
            _referenced_cols(v, out)
        elif isinstance(v, (list, tuple)):
            for x in v:
                if isinstance(x, (list, tuple)):
                    for y in x:
                        _referenced_cols(y, out)
                else:
                    _referenced_cols(x, out)


_NULL_ABSORBERS = {"isnull", "isnan", "coalesce", "ifnull", "nvl", "nvl2",
                   "nullif"}


def _null_defined_on(body: Expr, param: str) -> bool:
    """True iff the body's value on a null ``param`` is itself non-null —
    conservatively: every reference to the param is wrapped in a
    null-absorbing function. A bare comparison like ``x > 4`` is
    null-propagating, so exists() must report unknown for null elements;
    ``NOT isnull(x)`` is defined (false) on null, so computed values are
    the truth."""
    def ok(e) -> bool:
        if isinstance(e, Col):
            return e.name != param
        if isinstance(e, Func) and e.fn_name in _NULL_ABSORBERS:
            return True
        if isinstance(e, UnaryOp) and e.op in ("isnull", "isnotnull"):
            return True
        if isinstance(e, UdfCall) and e.udf_name.lower() in _NULL_ABSORBERS:
            return True
        if not isinstance(e, Expr):
            return True
        for v in vars(e).values():
            kids = v if isinstance(v, (list, tuple)) else [v]
            for k in kids:
                inner = k if isinstance(k, (list, tuple)) else [k]
                for x in inner:
                    if isinstance(x, Expr) and not ok(x):
                        return False
        return True

    return ok(body)


def _scope_frame(parent, lens, bindings, needed=None):
    """Per-element scope: outer columns repeated by cell length, lambda
    params appended last so they shadow same-named outer columns.
    ``needed`` limits the repeat to the columns the body references
    (repeating a wide frame per element for an ``x -> x + 1`` lambda
    would multiply host copies by the column count for nothing)."""
    from ..frame.frame import Frame

    reps = np.asarray(lens, np.int64)
    data = {}
    for name, vals in parent._data.items():
        if needed is not None and name not in needed:
            continue
        data[name] = np.repeat(_host_col(vals), reps, axis=0)
    data.update(bindings)
    return Frame(data)


def _row_frame(parent, bindings, needed=None):
    """Per-row scope (aggregate): outer columns as-is, params appended.
    ``needed`` matters doubly here — this frame is rebuilt once per
    element position."""
    from ..frame.frame import Frame

    data = {name: _host_col(vals) for name, vals in parent._data.items()
            if needed is None or name in needed}
    data.update(bindings)
    return Frame(data)


def _elem_of(out_host, k):
    v = out_host[k]
    if v is None or (isinstance(v, (float, np.floating)) and np.isnan(v)):
        return None
    return v


class HigherOrder(Expr):
    """transform / filter (element predicate) / exists / aggregate."""

    _KINDS = ("transform", "filter", "exists", "aggregate")

    def __init__(self, kind, source, lam: Lambda, init: Expr = None,
                 finish: Lambda = None):
        if kind not in self._KINDS:
            raise ValueError(f"unknown higher-order function {kind!r}")
        want = 2 if kind == "aggregate" else 1
        if len(lam.params) != want:
            raise ValueError(
                f"{kind}() lambda takes {want} parameter(s), "
                f"got {len(lam.params)}")
        self.kind = kind
        self.source = _coerce(source)
        self.lam = lam
        self.init = init
        self.finish = finish

    def eval(self, frame):
        cells = _require_array_cells(
            np.asarray(self.source.eval(frame), object), self.kind)
        if self.kind == "aggregate":
            return self._eval_aggregate(frame, cells)
        lens = [0 if c is None else len(c) for c in cells]
        flat = [e for c in cells if c is not None for e in c]
        bindings = {self.lam.params[0]: _column_from_elems(flat)}
        needed: set = set()
        _referenced_cols(self.lam.body, needed)
        try:
            out = self.lam.body.eval(
                _scope_frame(frame, lens, bindings, needed=needed))
        except KeyError:
            # an Expr kind the attribute walk missed referenced a column
            # indirectly — fall back to the full (correct, wider) scope
            out = self.lam.body.eval(_scope_frame(frame, lens, bindings))
        # exists needs to know whether the predicate is DEFINED on null
        # (isnull-style bodies return a real boolean for a null element;
        # comparisons return null, which NaN math renders as False — an
        # evaluation probe cannot tell the two Falses apart, so the check
        # is structural: every reference to the param must sit under a
        # null-absorbing function).
        null_defined = (self.kind == "exists"
                        and _null_defined_on(self.lam.body,
                                             self.lam.params[0]))
        out_host = _host_col(out)
        results = []
        k = 0
        for c, ln in zip(cells, lens):
            if c is None:
                results.append(None)
                continue
            start, k = k, k + ln
            seg = range(start, start + ln)
            if self.kind == "transform":
                results.append(np.asarray(
                    [_elem_of(out_host, j) for j in seg], object))
            elif self.kind == "filter":
                results.append(np.asarray(
                    [c[j - start] for j in seg
                     if (v := _elem_of(out_host, j)) is not None and bool(v)],
                    object))
            else:  # exists — three-valued like SQL ANY
                vals = [_elem_of(out_host, j) for j in seg]
                # a null INPUT element makes the predicate unknown —
                # unless the null-probe above showed the body is defined
                # on null (isnull-style), in which case the computed
                # values are the truth
                null_in = (not null_defined
                           and any(_cell_is_null(x) for x in c))
                if any(v is not None and bool(v) for v in vals):
                    results.append(True)
                elif null_in or any(v is None for v in vals):
                    results.append(None)
                else:
                    results.append(False)
        if self.kind == "exists":
            if any(r is None for r in results):
                return jnp.asarray(np.asarray(
                    [np.nan if r is None else float(r) for r in results],
                    np.float64), float_dtype())
            return jnp.asarray(np.asarray(results, np.bool_))
        return np.asarray(results, object)

    def _eval_aggregate(self, frame, cells):
        acc_name, x_name = self.lam.params
        acc = _host_col(self.init.eval(frame) if self.init is not None
                        else Lit(0.0).eval(frame))
        max_len = builtins.max((0 if c is None else len(c) for c in cells),
                               default=0)
        needed: set = set()
        _referenced_cols(self.lam.body, needed)
        if self.finish is not None:
            _referenced_cols(self.finish.body, needed)
        needed |= {acc_name, x_name}
        for j in range(max_len):
            xj = [None if c is None or j >= len(c) else c[j] for c in cells]
            bindings = {acc_name: acc, x_name: _column_from_elems(xj)}
            try:
                env = _row_frame(frame, bindings, needed=needed)
                new_acc = _host_col(self.lam.body.eval(env))
            except KeyError:   # attribute walk missed a reference
                needed = None
                env = _row_frame(frame, bindings)
                new_acc = _host_col(self.lam.body.eval(env))
            active = np.asarray(
                [c is not None and j < len(c) for c in cells])
            if _is_object(acc) or _is_object(new_acc):
                acc = np.asarray(
                    [n if a else o
                     for o, n, a in zip(acc, new_acc, active)], object)
            else:
                acc = np.where(active, new_acc, acc)
        if self.finish is not None:
            env = _row_frame(frame, {self.finish.params[0]: acc})
            acc = _host_col(self.finish.body.eval(env))
        # null cells → null result
        null_rows = np.asarray([c is None for c in cells])
        if _is_object(acc):
            return np.asarray([None if nr else v
                               for v, nr in zip(acc, null_rows)], object)
        out = np.asarray(acc, np.float64)
        return jnp.asarray(np.where(null_rows, np.nan, out), float_dtype())


def transform(col_, f) -> HigherOrder:
    """``transform(col, x -> …)`` — per-element map. ``f`` is a Python
    callable over a Col (PySpark-3 shape) or a prebuilt Lambda."""
    lam = f if isinstance(f, Lambda) else _fresh_lambda(f, 1)
    return HigherOrder("transform", col_, lam)


def filter(col_, f) -> HigherOrder:  # noqa: A001 - Spark name
    """``filter(col, x -> predicate)`` — keep matching elements; a null
    predicate drops the element (SQL semantics)."""
    lam = f if isinstance(f, Lambda) else _fresh_lambda(f, 1)
    return HigherOrder("filter", col_, lam)


def exists(col_, f) -> HigherOrder:
    """``exists(col, x -> predicate)`` — three-valued ANY over the
    elements."""
    lam = f if isinstance(f, Lambda) else _fresh_lambda(f, 1)
    return HigherOrder("exists", col_, lam)


def aggregate(col_, initial_value, merge, finish=None) -> HigherOrder:
    """``aggregate(col, init, (acc, x) -> …[, acc -> …])`` — sequential
    fold per cell, vectorized across rows by element position."""
    lam = merge if isinstance(merge, Lambda) else _fresh_lambda(merge, 2)
    fin = None
    if finish is not None:
        fin = finish if isinstance(finish, Lambda) \
            else _fresh_lambda(finish, 1)
    init = initial_value if isinstance(initial_value, Expr) \
        else Lit(initial_value)
    return HigherOrder("aggregate", col_, lam, init=init, finish=fin)
