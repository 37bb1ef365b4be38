"""Fused expression-pipeline compiler: plan-keyed jit cache + bucketed padding.

The frame engine is eager by design (frame.py docstring: Spark's lazy DAG is
deliberately not replicated) — but in eager JAX every ``with_column`` /
``filter`` node dispatches as its *own* XLA computation, and the fusion the
design banks on only happens **inside** ``jax.jit``; without it the op
sweep is pinned at interpreter-dispatch cost, not FLOPs. This module is the
missing compilation layer: chains of compilable frame ops coalesce (see
``Frame._defer``) and materialize as ONE jitted XLA program per *plan shape*.

Three pieces, mirroring the hierarchy lesson of Snap ML (PAPERS.md — keep the
hot loop in one compiled unit) and the graph-level-optimization approach of
"Memory Safe Computations with XLA Compiler" (PAPERS.md):

* **Structural plan key** — an ``Expr`` tree linearizes to a string of op
  kinds, referenced-column dtypes, and vector widths. Python literals in
  comparison/arithmetic positions are *hoisted out of the key* and passed as
  runtime scalar arguments, so ``price < 3`` and ``price < 4`` share one
  compiled program (``_lower`` rewrites the hoisted ``Lit`` into an
  :class:`_ArgLit` that broadcasts the runtime scalar at trace time).

* **Plan-keyed jit cache** — one ``jax.jit`` callable per plan key (bounded
  LRU). The program computes every pending column expression and the
  filter-mask AND in a single XLA computation, with buffer donation on the
  (padded) mask and on padded inputs of replaced columns.

* **Shape-bucketed row padding** — inputs pad up to the next power-of-two
  bucket with a ``False`` mask tail, so two CSV loads of different lengths
  hit the same compiled program instead of retracing; outputs slice back to
  the true row count.

Observability: ``pipeline.flush`` / ``pipeline.compile`` / ``pipeline.hit``
/ ``pipeline.fallback`` counters in :data:`utils.profiling.counters`, and a
``frame.pipeline.flush`` span (steps, bucket, rows, cache verdict) when
tracing is on. Disable the whole layer with
``.config("spark.pipeline.enabled", "false")`` (→ ``config.pipeline``),
which restores the exact per-op eager path.

Semantics are bit-identical to eager evaluation: the compiled program runs
the *same* ``Expr.eval`` methods (against a :class:`_TraceFrame` shim whose
columns are tracers), so every null rule, dtype promotion, and division
corner is the one the eager path implements. Anything outside the compilable
subset (strings, row generators, array cells) never defers. A registered UDF
is inside it when its function is seen to be row-local (``ops/udf.py``: a
probe of the traced function, no flag) — the reference app's DQ rules are —
and outside it otherwise: a whole-column function keeps the eager path,
because padding, row slices and shards would change what it sees.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import logging
import math
import re
import threading
import time
import warnings
from collections import OrderedDict
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import config, float_dtype, int_dtype
from ..utils import faults as _faults
from ..utils import observability as _obs
from ..utils.profiling import counters
from . import expressions as E
from .udf import PROBE_ROWS

__all__ = [
    "bucket_size", "pad_rows", "dtype_tag", "is_compilable",
    "run_pipeline", "clear_cache", "cache_len", "PipelineError",
    "plan_namespace", "plan_namespace_tag",
    "coalesce_scope", "run_batched", "coalesce_batch_bucket",
]


logger = logging.getLogger("sparkdq4ml_tpu.ops.compiler")


class PipelineError(RuntimeError):
    """Internal compile/run failure — callers fall back to eager replay."""


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------

def bucket_size(n: int) -> int:
    """Row-slot bucket for ``n`` rows: the next power of two, floored at
    ``config.pipeline_min_bucket``. Two frames whose lengths land in the
    same bucket execute the same compiled program (the padded tail rides
    a ``False`` validity mask, so no masked reduction ever sees it).

    Above ``config.pipeline_exact_threshold`` the bucket IS ``n``: the
    pad-in + slice-out copies are O(n) per flush and at that scale cost
    more than the occasional retrace they avoid, while the small-frame
    regime (repeated queries over varying batch sizes) keeps full
    cross-length sharing."""
    lo = max(int(config.pipeline_min_bucket), 1)
    if n <= lo:
        return lo
    if n > int(config.pipeline_exact_threshold):
        return n
    return 1 << (n - 1).bit_length()


def result_bucket(rows: int) -> int:
    """Row slots for a data-dependent result of ``rows`` rows (a join's
    pairs, a many-group GROUP BY's groups): ``rows`` rounded up to three
    significant bits — at most an eighth more — so that results of nearly
    one size, the same statement over another day's data, share the
    programs behind them. The tail rides a ``False`` mask."""
    rows = max(int(rows), 8)
    step = 1 << max(rows.bit_length() - 3, 0)
    return -(-rows // step) * step


# ---------------------------------------------------------------------------
# Compilability — the subset of Expr that traces under jit
# ---------------------------------------------------------------------------

# Pure-jnp builtin scalar functions (device columns in, device column out).
# Everything else in _BUILTIN_FNS is host-side (strings/arrays) or needs a
# host-extracted literal in a non-trailing position.
_NUMERIC_FUNCS = frozenset({
    "abs", "sqrt", "exp", "log", "log10", "pow", "power", "floor", "ceil",
    "sign", "signum", "greatest", "least", "isnan", "coalesce", "sin",
    "cos", "tan", "asin", "acos", "atan", "atan2", "sinh", "cosh", "tanh",
    "degrees", "radians", "cbrt", "expm1", "log1p", "log2", "mod", "pmod",
    "hypot", "rint", "nanvl",
})
# round(col, d) is deliberately NOT compilable: its ``/ 10**d`` uses a
# compile-time-constant divisor, which XLA strength-reduces to a
# reciprocal multiply under jit — a 1-ULP divergence from the eager op.
# (Hoisted BinOp literals dodge this: a runtime-scalar divisor is not
# strength-reduced.) Bit-identical semantics outrank fusing one op.
_LIT_TAIL_FUNCS: frozenset = frozenset()

# (min, max) argument counts; None = unbounded. Wrong-arity calls must
# NOT defer — the eager path raises the TypeError at the call site, and
# deferring would postpone (or, pre-fix, swallow) that error.
_FUNC_ARITY = {
    "pow": (2, 2), "power": (2, 2), "atan2": (2, 2), "hypot": (2, 2),
    "mod": (2, 2), "pmod": (2, 2), "nanvl": (2, 2),
    "greatest": (1, None), "least": (1, None), "coalesce": (1, None),
    "round": (1, 2),
}


def _arity_ok(fn_name: str, n_args: int) -> bool:
    lo, hi = _FUNC_ARITY.get(fn_name, (1, 1))
    return n_args >= lo and (hi is None or n_args <= hi)


def _lit_compilable(v) -> bool:
    """Mirrors ``Lit.eval``'s type dispatch EXACTLY: only Python
    bool/int/float take the device path there (np.float64 passes as a
    float subclass; np.int64/np.bool_ do NOT subclass int/bool and fall
    to the host object-array branch, so they must not defer — and their
    repr could collide with the Python literal's plan key)."""
    return isinstance(v, (bool, int, float))


def _col_spec(arr) -> str:
    """Plan-key spec of a referenced base column: dtype + vector width
    (``f64``, ``f32x4``, …). Host object columns report ``h`` and are
    rejected by :func:`is_compilable`."""
    if isinstance(arr, np.ndarray) and arr.dtype == object:
        return "h"
    a = jnp.asarray(arr)
    w = f"x{a.shape[1]}" if a.ndim == 2 else ""
    return f"{np.dtype(a.dtype).str}{w}"


def schema_of(data: dict, pending_names: Sequence[str] = ()) -> dict:
    """name → key spec for the compilability walk: base device columns map
    to their dtype spec, host columns to ``h``, and columns produced by
    earlier pending steps to ``p`` (their dtype is determined by plan
    structure, so the spec carries no dtype)."""
    spec = {name: _col_spec(arr) for name, arr in data.items()}
    for name in pending_names:
        spec[name] = "p"
    return spec


class LazySchema:
    """``get``-only schema of a frame's STORED columns that resolves
    column specs ON DEMAND — the per-op ``_can_defer`` check runs once per
    deferred call, and eagerly spec-ing every stored column made deferral
    O(frame width) per op on wide frames; an expression only needs the
    handful of columns it references. Columns that pending steps produce
    lie over it in a :class:`_SchemaOverlay` (:func:`pending_schema`)."""

    def __init__(self, data: dict):
        self._data = data
        self._cache: dict = {}

    def aval(self, name):
        """The column as an abstract probe-length array (what a UDF
        call's probe is given), or None for a host column."""
        arr = self._data.get(name)
        if arr is None or self.get(name) == "h":
            return None
        return jax.ShapeDtypeStruct(
            (PROBE_ROWS,) + tuple(np.shape(arr)[1:]),
            jax.dtypes.canonicalize_dtype(arr.dtype))

    def get(self, name, default=None):
        try:
            return self._cache[name]
        except KeyError:
            pass
        arr = self._data.get(name)
        if arr is None:
            return default
        spec = self._cache[name] = _col_spec(arr)
        return spec


def _dtype_tag() -> str:
    """Engine dtype fingerprint prefixed to every plan key: expression
    eval bakes ``float_dtype()``/``int_dtype()`` into the program (e.g.
    ``/`` casts to the configured float), so a config flip (tests switch
    float32 ↔ float64) must miss the cache, not serve stale dtypes.

    Shared plan-key infrastructure: ``ops/segments.py`` (the grouped
    execution engine) prefixes its grouped/sort/unique plan keys with the
    same tag, and reuses :func:`bucket_size`/:func:`pad_rows` so both
    caches share one bucketing discipline."""
    return f"{np.dtype(float_dtype()).str}/{np.dtype(int_dtype()).str}"


# public aliases for the cross-module plan-cache contract (segments.py)
dtype_tag = _dtype_tag


def is_compilable(expr, schema: dict) -> bool:
    """True when ``expr`` evaluates entirely on device under jit: numeric
    column refs, numeric literals, arithmetic/comparison/boolean ops,
    numeric casts, CASE WHEN, IN over literal values, the pure-jnp
    builtin functions, and calls of registered UDFs whose function is
    row-local (:func:`_udf_admission`). Strings, row generators, subquery
    markers, array-cell functions and every other UDF call are not (they
    stay on the eager path)."""
    if isinstance(expr, E.Col):
        s = schema.get(expr.name)
        return s is not None and s != "h"
    if isinstance(expr, E.Lit):
        return _lit_compilable(expr.value)
    if isinstance(expr, E.Alias):
        return is_compilable(expr.child, schema)
    if isinstance(expr, E.BinOp):
        return (is_compilable(expr.left, schema)
                and is_compilable(expr.right, schema))
    if isinstance(expr, E.UnaryOp):
        return expr.op in ("-", "!", "isnull", "isnotnull") \
            and is_compilable(expr.child, schema)
    if isinstance(expr, E.Cast):
        try:
            dt = E.resolve_type_name(expr.type_name)
        except ValueError:
            return False
        if isinstance(dt, np.dtype) and dt == object:
            return False            # → string: host path
        return is_compilable(expr.child, schema)
    if isinstance(expr, E.InList):
        return (is_compilable(expr.child, schema)
                and all(isinstance(v, E.Lit)
                        and (_lit_compilable(v.value)
                             or E.InList._is_null_lit(v))
                        for v in expr.values))
    if isinstance(expr, E.CaseWhen):
        return (all(is_compilable(c, schema) and is_compilable(v, schema)
                    for c, v in expr.branches)
                and (expr.otherwise_expr is None
                     or is_compilable(expr.otherwise_expr, schema)))
    if isinstance(expr, E.Func):
        if not _arity_ok(expr.fn_name, len(expr.args)):
            return False
        if expr.fn_name in _LIT_TAIL_FUNCS:
            return (is_compilable(expr.args[0], schema)
                    and all(isinstance(a, E.Lit)
                            and _lit_compilable(a.value)
                            for a in expr.args[1:]))
        if expr.fn_name in _NUMERIC_FUNCS:
            return all(is_compilable(a, schema) for a in expr.args)
        return False
    if isinstance(expr, E.UdfCall):
        # the SQL parser builds a UdfCall for EVERY function call: names
        # the registry lacks (abs, upper, ...) leave here at once
        return (expr.udf_name in expr.registry() and bool(expr.args)
                and all(is_compilable(a, schema) for a in expr.args)
                and _udf_admission(expr, schema) is not None)
    return False


def _walk(expr):
    """Every node of an expression tree, parents first, children in the
    one order all plan walks share."""
    yield expr
    for attr in ("left", "right", "child", "otherwise_expr"):
        v = getattr(expr, attr, None)
        if isinstance(v, E.Expr):
            yield from _walk(v)
    for v in getattr(expr, "args", None) or ():
        yield from _walk(v)
    for v in getattr(expr, "values", None) or ():
        yield from _walk(v)
    for c, v in getattr(expr, "branches", None) or ():
        yield from _walk(c)
        yield from _walk(v)


def _col_aval(schema, name):
    """A referenced column as an abstract probe-length array, or None
    where the schema cannot tell: a host column, or a plain dict of key
    specs (``schema_of``, the static memory estimate), which holds no
    arrays — a UDF call checked against one is not admitted."""
    f = getattr(schema, "aval", None)
    return f(name) if f is not None else None


def _udf_admission(expr, schema):
    """``(fn, return_dtype, fingerprint)`` when this call of a registered
    UDF may run inside a flush program, else None.

    The flush compiler pads rows to a bucket, may slice them
    (:func:`_run_chunked`) and runs shards alone — sound for row-local
    work only. So the answer is the registry's probe of the function at
    the dtypes this call would hand it (``ops/udf.probe_elementwise``,
    cached on the registry entry). The dtypes come from the schema: a
    stored column's own, or — where an argument is an expression or a
    column that a pending step produces — from an abstract evaluation of
    it (``jax.eval_shape``: nothing is computed)."""
    env = {}
    for a in expr.args:
        for e in _walk(a):
            if isinstance(e, E.Col) and e.name not in env:
                av = _col_aval(schema, e.name)
                if av is None:
                    return None
                env[e.name] = av
    if all(isinstance(a, E.Col) for a in expr.args):
        avals = [env[a.name] for a in expr.args]
    else:
        try:
            avals = jax.eval_shape(
                lambda cols: tuple(
                    a.eval(_TraceFrame(cols, PROBE_ROWS))
                    for a in expr.args), env)
        except Exception:
            return None
    if any(len(av.shape) != 1 for av in avals):
        return None                     # a vector column: not 1-D
    return expr.registry().elementwise(
        expr.udf_name, [av.dtype for av in avals])


# ---------------------------------------------------------------------------
# Plan lowering: key string + literal hoisting (one traversal, lockstep)
# ---------------------------------------------------------------------------

class _ArgLit(E.Expr):
    """A hoisted literal: broadcasts the ``i``-th runtime scalar argument
    at its original ``Lit`` dtype. Exists only inside cached rewritten
    plans — never escapes the compiler."""

    def __init__(self, index: int, kind: str):
        self.index = index
        self.kind = kind            # "b" | "i" | "f"

    def eval(self, frame):
        val = _RUNTIME_LITS.lits[self.index]
        dt = (jnp.bool_ if self.kind == "b"
              else int_dtype() if self.kind == "i" else float_dtype())
        return jnp.full((frame.num_slots,), val, dt)

    def __str__(self):
        return f"?lit{self.index}"


class _HostConstLit(E.Expr):
    """A literal evaluated as a HOST numpy array: the lit-tail arguments
    of :data:`_LIT_TAIL_FUNCS` (e.g. ``round``'s digit count) are
    host-extracted inside the builtin (``int(np.asarray(d)[0])``), and
    under jit even a constant ``jnp.full`` is staged into a tracer that
    ``np.asarray`` rejects. Exists only inside rewritten plans."""

    def __init__(self, value):
        self.value = value

    def eval(self, frame):
        return np.full((frame.num_slots,), self.value)

    def __str__(self):
        return repr(self.value)


class _Lits(threading.local):
    lits: tuple = ()                # per-thread default (trace-time only)


_RUNTIME_LITS = _Lits()


def _lit_kind(v) -> str:
    if isinstance(v, (bool, np.bool_)):
        return "b"
    if isinstance(v, (int, np.integer)):
        return "i"
    return "f"


def _hoistable_lit(expr) -> Optional[E.Lit]:
    """The ``price < LITERAL`` case: a numeric (non-bool, non-NaN-sentinel)
    Lit in a BinOp/UnaryOp('-') operand position hoists to a runtime
    scalar. Bools and NaN stay in the key: NaN drives *static* null-rule
    branches elsewhere (InList), and bools are two values — hoisting buys
    nothing and loses constant-folding."""
    if isinstance(expr, E.Lit) and isinstance(expr.value, (int, float)) \
            and not isinstance(expr.value, bool) \
            and not (isinstance(expr.value, float)
                     and math.isnan(expr.value)):
        return expr
    return None


def _lower(expr, schema: dict, lits: list):
    """One traversal returning ``(key_fragment, rewritten_expr)``.

    ``lits`` collects the hoisted ``Lit`` nodes in traversal order; the
    rewritten tree holds matching :class:`_ArgLit` placeholders at the
    same positions. Key equality ⇒ identical traversal ⇒ later frames
    extract their literal values in exactly the cached program's order.
    """
    if isinstance(expr, E.Col):
        return f"C({expr.name!r}:{schema.get(expr.name)})", expr
    if isinstance(expr, E.Lit):
        return f"V({expr.value!r})", expr
    if isinstance(expr, E.Alias):
        k, ch = _lower(expr.child, schema, lits)
        return k, (expr if ch is expr.child else E.Alias(ch, expr._name))
    if isinstance(expr, E.BinOp):

        def operand(side):
            h = _hoistable_lit(side)
            if h is not None:
                idx = len(lits)
                lits.append(h)
                kind = _lit_kind(h.value)
                return f"L{kind}", _ArgLit(idx, kind)
            return _lower(side, schema, lits)

        lk, le = operand(expr.left)
        rk, re = operand(expr.right)
        return (f"B({expr.op},{lk},{rk})",
                expr if le is expr.left and re is expr.right
                else E.BinOp(expr.op, le, re))
    if isinstance(expr, E.UnaryOp):
        h = _hoistable_lit(expr.child) if expr.op == "-" else None
        if h is not None:
            idx = len(lits)
            lits.append(h)
            kind = _lit_kind(h.value)
            return (f"U(-,L{kind})",
                    E.UnaryOp("-", _ArgLit(idx, kind)))
        k, ch = _lower(expr.child, schema, lits)
        return (f"U({expr.op},{k})",
                expr if ch is expr.child else E.UnaryOp(expr.op, ch))
    if isinstance(expr, E.Cast):
        k, ch = _lower(expr.child, schema, lits)
        return (f"T({expr.type_name.lower()},{k})",
                expr if ch is expr.child else E.Cast(ch, expr.type_name))
    if isinstance(expr, E.InList):
        k, ch = _lower(expr.child, schema, lits)
        vals = ",".join("NULL" if E.InList._is_null_lit(v)
                        else repr(v.value) for v in expr.values)
        return (f"I({int(expr.negated)},{k},[{vals}])",
                expr if ch is expr.child
                else E.InList(ch, expr.values, expr.negated))
    if isinstance(expr, E.CaseWhen):
        parts = []
        branches = []
        changed = False
        for c, v in expr.branches:
            ck, ce = _lower(c, schema, lits)
            vk, ve = _lower(v, schema, lits)
            parts.append(f"{ck}:{vk}")
            changed = changed or ce is not c or ve is not v
            branches.append((ce, ve))
        if expr.otherwise_expr is not None:
            ok, oe = _lower(expr.otherwise_expr, schema, lits)
            changed = changed or oe is not expr.otherwise_expr
        else:
            ok, oe = "_", None
        return (f"W([{';'.join(parts)}],{ok})",
                expr if not changed else E.CaseWhen(branches, oe))
    if isinstance(expr, E.Func):
        parts, args, changed = _lower_args(
            expr.args, schema, lits, expr.fn_name in _LIT_TAIL_FUNCS)
        return (f"F({expr.fn_name},{','.join(parts)})",
                expr if not changed else E.Func(expr.fn_name, args))
    if isinstance(expr, E.UdfCall):
        # The key names the FUNCTION, not only the rule: its fingerprint
        # is the probed program's text, literals inside it included (they
        # are the function's own and are not hoisted). Two functions
        # under one name — a re-registration, another registry — never
        # share a program; one function registered twice does. The node
        # of the rewritten plan carries the function the key was made
        # from, so a registration that changes between the key and the
        # trace cannot put another function behind this key.
        admitted = _udf_admission(expr, schema)
        if admitted is None:
            raise PipelineError(
                f"UDF {expr.udf_name!r} is not admitted to a flush")
        fn, return_dtype, fingerprint = admitted
        parts, args, _ = _lower_args(expr.args, schema, lits)
        call = E.UdfCall(expr.udf_name, args, expr._registry)
        call._bound = (fn, return_dtype)
        rule = repr(expr.udf_name).replace("|", "\\x7c")   # keys split on |
        rt = "_" if return_dtype is None else np.dtype(return_dtype).name
        return f"R({rule}#{fingerprint}:{rt},{','.join(parts)})", call
    raise PipelineError(f"non-compilable node reached _lower: {expr!r}")


def _lower_args(call_args, schema, lits: list, lit_tail: bool = False):
    """Lower the arguments of a builtin or UDF call: ``(key fragments,
    rewritten args, whether any changed)``."""
    parts = []
    args = []
    changed = False
    for i, a in enumerate(call_args):
        if lit_tail and i > 0:
            # host-extracted literal args (is_compilable guarantees
            # Lits here): evaluate as host numpy, bake into the key
            parts.append(f"V({a.value!r})")
            args.append(_HostConstLit(a.value))
            changed = True
            continue
        # literal args hoist like BinOp operands: pow(x, 2)/pow(x, 3)
        # share one program, AND the exponent stays a runtime scalar so
        # XLA cannot strength-reduce constant forms (pow(x, 2) → x*x)
        # into 1-ULP divergence from the eager op.
        h = _hoistable_lit(a)
        if h is not None:
            idx = len(lits)
            lits.append(h)
            kind = _lit_kind(h.value)
            parts.append(f"L{kind}")
            args.append(_ArgLit(idx, kind))
            changed = True
            continue
        ak, ae = _lower(a, schema, lits)
        parts.append(ak)
        changed = changed or ae is not a
        args.append(ae)
    return parts, args, changed


def _referenced_base_cols(expr, schema: dict, out: list) -> None:
    """Column names an expression reads from the frame's STORED columns
    (names the step-evolved ``schema`` does not map to ``p``), in
    first-seen order — the compiled program's array inputs. A name read
    before a later step replaces it resolves to base here because the
    caller marks outputs ``p`` only after lowering the step that
    produces them."""
    for e in _walk(expr):
        if (isinstance(e, E.Col) and e.name not in out
                and schema.get(e.name) not in (None, "p")):
            out.append(e.name)


# ---------------------------------------------------------------------------
# The compiled program
# ---------------------------------------------------------------------------

class _TraceFrame:
    """Frame shim the compiled program evaluates expressions against: its
    columns are jit tracers and ``num_slots`` is the (static) bucket
    size, so ``Expr.eval`` runs unmodified — same nulls, same dtype
    promotion, same division corners as the eager path."""

    def __init__(self, env: dict, n: int):
        self._env = env
        self._n = n

    @property
    def num_slots(self) -> int:
        return self._n

    def _column_values(self, name: str):
        try:
            return self._env[name]
        except KeyError:
            raise KeyError(f"pipeline program has no column {name!r}; "
                           f"inputs: {sorted(self._env)}") from None


class _SchemaOverlay:
    """Mutable step-output overlay over a base schema (dict or
    :class:`LazySchema`) — _linearize marks produced columns ``p``
    without copying or eagerly materializing the base. It keeps each
    producing step's expressions, so that the dtype of a produced column
    can be told when a UDF call reads one (:meth:`aval`): rarely, and by
    one abstract replay of the steps so far."""

    def __init__(self, base):
        self._base = base
        self._over: dict = {}
        self._groups: list = []     # one tuple of (name, expr) per step
        self._avals: Optional[dict] = None

    def get(self, name, default=None):
        if name in self._over:
            return self._over[name]
        return self._base.get(name, default)

    def define(self, pairs) -> None:
        """The outputs of one producing step: ``p`` for later steps."""
        self._groups.append(tuple(pairs))
        for name, _ in pairs:
            self._over[name] = "p"
        self._avals = None

    def aval(self, name):
        if name not in self._over:
            return _col_aval(self._base, name)
        if self._avals is None:
            self._avals = self._replay()
        return self._avals.get(name)

    def _replay(self) -> dict:
        base = {}
        for group in self._groups:
            for _, ex in group:
                for e in _walk(ex):
                    if isinstance(e, E.Col) and e.name not in base:
                        av = _col_aval(self._base, e.name)
                        if av is not None:
                            base[e.name] = av

        def run(cols):
            env = dict(cols)
            for group in self._groups:
                fr = _TraceFrame(dict(env), PROBE_ROWS)   # pre-step state
                env.update({name: ex.eval(fr) for name, ex in group})
            return {name: env[name] for name in self._over}

        try:
            return jax.eval_shape(run, base)
        except Exception:
            return {}


def pending_schema(data: dict, pending_steps=()) -> _SchemaOverlay:
    """The schema a frame checks deferral against: its stored columns,
    lazily, under what its pending steps produce."""
    schema = _SchemaOverlay(LazySchema(data))
    for s in pending_steps:
        if s[0] == "with_column":
            schema.define(((s[1], s[2]),))
        elif s[0] == "with_columns":
            schema.define(s[1])
    return schema


def _linearize(steps, extra, base_schema):
    """THE single plan walk — used by both the cache probe and plan
    construction, so the key, the hoisted-literal order, and the
    rewritten trees can never drift apart (a divergence would make every
    lookup miss, or worse, bind literal values to the wrong _ArgLit
    slots). ``base_schema`` holds only the frame's stored columns; it
    evolves step-by-step (each step's outputs become ``p`` for LATER
    steps) so a step that reads a column *before* a later step replaces
    it keys on — and receives — the BASE column as a program input.

    Returns ``(key, lit_nodes, lowered_steps, lowered_extra, refs)``.
    """
    lits: list = []
    key_parts: list = []
    lowered_steps: list = []
    lowered_extra: list = []
    refs: list = []
    schema = _SchemaOverlay(base_schema)
    for step in steps:
        if step[0] == "with_column":
            k, ex = _lower(step[2], schema, lits)
            _referenced_base_cols(step[2], schema, refs)
            key_parts.append(f"W({step[1]!r})={k}")
            lowered_steps.append(("with_column", step[1], ex))
            schema.define(((step[1], step[2]),))
        elif step[0] == "with_columns":
            pairs = []
            ks = []
            for name, sub in step[1]:
                k, ex = _lower(sub, schema, lits)
                _referenced_base_cols(sub, schema, refs)
                ks.append(f"{name!r}={k}")
                pairs.append((name, ex))
            key_parts.append(f"WS({';'.join(ks)})")
            lowered_steps.append(("with_columns", tuple(pairs)))
            schema.define(step[1])
        elif step[0] == "filter":
            k, ex = _lower(step[1], schema, lits)
            _referenced_base_cols(step[1], schema, refs)
            key_parts.append(f"F:{k}")
            lowered_steps.append(("filter", ex))
        else:
            raise PipelineError(f"unknown pipeline step {step[0]!r}")
    for name, sub in extra:
        k, ex = _lower(sub, schema, lits)
        _referenced_base_cols(sub, schema, refs)
        key_parts.append(f"O({name!r})={k}")
        lowered_extra.append((name, ex))
    key = _dtype_tag() + "|" + "|".join(key_parts)
    return key, lits, lowered_steps, lowered_extra, refs


class _Plan:
    """One cache entry: the jitted program plus its calling convention
    (see :func:`_linearize` for the key/lowering walk).

    With a :class:`~..parallel.shard.ShardedStore` layout the SAME body
    lowers as ONE ``shard_map``-wrapped program over the store's mesh —
    the compilable step surface is purely elementwise, so per-shard
    execution is bit-identical by construction and the program carries
    **zero cross-shard traffic** (the one extra output, the per-shard
    valid-row count, is shard-local too; the statstore drains it host-
    side later). Sharded plans key with the store's layout tag, so
    sharded and single-device programs coexist in this cache."""

    def __init__(self, steps, extra, walk, shard=None):
        # ``walk``: the cache probe's own _linearize result for these
        # steps, so the key and the program come from ONE walk
        key, lits, lowered_steps, lowered_extra, refs = walk
        replaced = {s[1] for s in steps if s[0] == "with_column"}
        for s in steps:
            if s[0] == "with_columns":
                replaced |= {name for name, _ in s[1]}
        # donate the padded inputs of columns the program both reads and
        # replaces (their old buffers die at flush); everything else rides
        # the kept dict and may alias the frame's own buffers.
        self.donated = tuple(r for r in refs if r in replaced)
        self.kept = tuple(r for r in refs if r not in replaced)
        self.extra_names = tuple(name for name, _ in lowered_extra)
        # produced columns + projection outputs — the term the cheap
        # pre-execution memory estimate (_est_flush_bytes) charges per row
        self.n_outputs = (
            sum(1 for s in lowered_steps if s[0] == "with_column")
            + sum(len(s[1]) for s in lowered_steps
                  if s[0] == "with_columns")
            + len(lowered_extra))
        self.key = key
        self.n_lits = len(lits)
        # whether this program ANDs a filter into the mask — the flushes
        # whose output mask carries a selectivity observation (statstore)
        self.has_filter = any(s[0] == "filter" for s in lowered_steps)
        # the registered rules this program runs: every one for the
        # flush's span and counter, and those whose output is a column
        # the flush hands back for the dq profile's [rows, passed] tally
        self.rule_names, self.rule_cols = _plan_rules(steps, extra)
        # Introspection (observability.CACHES / EXPLAIN ANALYZE): per-plan
        # replay count and bucket histogram, updated under _CACHE_LOCK.
        self.hits = 0
        self.compiles = 0
        self.buckets: dict[int, int] = {}
        # Per-plan trace count: the compile-vs-hit verdict in run_pipeline
        # compares THIS plan's count across the call, not the global
        # pipeline.compile counter — a concurrent worker tracing a
        # different plan (the normal state of the serving thread-pool)
        # must not turn another plan's replay into a phantom "compile".
        self.traces = 0
        self._trace_lock = threading.Lock()

        donated_names = self.donated
        extra_pairs = tuple(lowered_extra)
        step_tuple = tuple(lowered_steps)
        # Abstract argument specs of the first real execution
        # (ShapeDtypeStructs + literal scalars) — the auditor's re-trace
        # surface (observability.ProgramHandle). None until first run.
        self.example: Optional[tuple] = None

        def steps_and_extras(kept, donated, mask, lit_args):
            # The pure program logic — shared by the jitted entry below
            # and the auditor's abstract re-trace (which must not count
            # as a compile nor bump the replay-verdict trace counter).
            _RUNTIME_LITS.lits = lit_args
            try:
                env = dict(kept)
                env.update(zip(donated_names, donated))
                fr = _TraceFrame(env, mask.shape[0])
                new_mask = mask
                changed = {}
                for st in step_tuple:
                    if st[0] == "with_column":
                        v = st[2].eval(fr)
                        env[st[1]] = v
                        changed[st[1]] = v
                    elif st[0] == "with_columns":
                        # Spark withColumns: every expression resolves
                        # against the *pre-step* frame state.
                        vals = {name: ex.eval(fr) for name, ex in st[1]}
                        env.update(vals)
                        changed.update(vals)
                    else:
                        # SQL three-valued logic — the SAME helper the
                        # eager Frame._filter_eager path calls
                        keep = E.predicate_keep_mask(st[1].eval(fr))
                        new_mask = jnp.logical_and(new_mask, keep)
                extras = {name: ex.eval(fr) for name, ex in extra_pairs}
                return changed, new_mask, extras
            finally:
                _RUNTIME_LITS.lits = ()

        def body(kept, donated, mask, lit_args):
            # every operation of the fused flush carries dq.flush in its
            # op metadata (the layer's name in a trace)
            with _obs.scope("flush"):
                return steps_and_extras(kept, donated, mask, lit_args)

        if shard is not None:
            # ONE shard_map-wrapped program per flush: rows partition
            # over the data axis, literals replicate, and every output
            # (including the filter mask) stays row-sharded. The 4th
            # output is the per-shard post-filter valid count — shape
            # (1,) per shard → (devices,) global — so the statstore's
            # selectivity observation needs no eager cross-shard
            # reduction on the hot path.
            from jax.sharding import PartitionSpec as _P

            from ..parallel.mesh import (DATA_AXIS, serialize_collectives,
                                         shard_map)

            def sharded_body(kept, donated, mask, lit_args):
                changed, new_mask, extras = body(kept, donated, mask,
                                                 lit_args)
                valid = jnp.sum(new_mask, dtype=jnp.int32)[None]
                return changed, new_mask, extras, valid

            pd = _P(DATA_AXIS)
            sharded = shard_map(
                sharded_body, mesh=shard.mesh,
                in_specs=(pd, pd, pd, _P()),
                out_specs=(pd, pd, pd, pd))

            def program(kept, donated, mask, lit_args):
                counters.increment("pipeline.compile")
                with self._trace_lock:
                    self.traces += 1
                return sharded(kept, donated, mask, lit_args)

            self.trace_body = sharded
            # dispatch-to-completion under the process-wide collective
            # lock: the program is collective-free, but multi-device
            # executions on XLA:CPU share the rendezvous machinery and
            # the PR-6 discipline is "every mesh-bearing program
            # serializes" — sharded flushes are no exception.
            self.fn = serialize_collectives(jax.jit(program), shard.mesh)
            self.donates = False
            self.mesh = shard.mesh
            self.guarded = True
            return

        def program(kept, donated, mask, lit_args):
            # Body runs at trace time only → this counts XLA compiles.
            counters.increment("pipeline.compile")
            with self._trace_lock:
                self.traces += 1
            return body(kept, donated, mask, lit_args)

        self.trace_body = body
        self.mesh = None
        self.guarded = None

        # Buffer donation of the replaced columns only pays on
        # accelerators, where the donated HBM buffer is reused for the
        # output; on XLA:CPU (unified memory) aliasing buys nothing and
        # measurably slows the call (~25% on a 20-op chain), so
        # the CPU path keeps the plain signature. The mask is never
        # donated: the dq-profile hook reads the flush's INPUT mask after
        # the dispatch (run_pipeline), and a donated array is deleted.
        self.donates = _donates()
        if self.donates:
            self.fn = jax.jit(program, donate_argnums=(1,))
        else:
            self.fn = jax.jit(program)


def _plan_rules(steps, extra):
    """``(names, cols)`` of the UDF calls in a plan (every one was
    admitted, or the plan would not exist). ``names``: one entry per
    call, nested ones too. ``cols``: ``(rule, "changed" | "extras",
    column)`` for each call that IS a column the flush returns — the
    whole expression of a ``with_column`` that no later step replaces, or
    of a projection. A call inside a larger expression has no column of
    its own: its values exist inside the program only, so it has a span
    and counts as run in a flush, and has no tally."""
    names = []
    produced: dict = {}                 # column -> rule that made it last

    def top_rule(ex):
        while isinstance(ex, E.Alias):
            ex = ex.child
        return ex.udf_name if isinstance(ex, E.UdfCall) else None

    def note(ex):
        names.extend(e.udf_name for e in _walk(ex)
                     if isinstance(e, E.UdfCall))

    for s in steps:
        if s[0] == "filter":
            note(s[1])
            continue
        for name, ex in (((s[1], s[2]),) if s[0] == "with_column"
                         else s[1]):
            note(ex)
            produced[name] = top_rule(ex)
    cols = [(rule, "changed", name) for name, rule in produced.items()]
    for name, ex in extra:
        note(ex)
        cols.append((top_rule(ex), "extras", name))
    return tuple(names), tuple(c for c in cols if c[0] is not None)


def _note_rules(plan, rows: int) -> None:
    """The host's account of the registered rules a flush program has
    just been dispatched with: the counter that says the mechanism
    engaged and one ``dq.rule`` span a rule, ``lowering="in-flush"`` — a
    child of the flush span with nothing dispatched under it, since the
    rule's operations are inside the flush's program (its scope
    ``dq.rule`` names them in a capture)."""
    if not plan.rule_names:
        return
    counters.increment("dq.rule_in_flush", len(plan.rule_names))
    for rule in plan.rule_names:
        with _obs.span("dq.rule", cat="dq", rule=rule, rows=rows,
                       lowering="in-flush"):
            pass


def _donates() -> bool:
    """Whether fused single-device plans donate their replaced-column
    inputs (see ``_Plan.__init__``)."""
    return jax.default_backend() != "cpu"


_CACHE: "OrderedDict[str, _Plan]" = OrderedDict()
_CACHE_LOCK = threading.Lock()

# ---------------------------------------------------------------------------
# Cache namespaces (the serving layer's shared-plan-cache switch)
# ---------------------------------------------------------------------------

#: Plan-key namespace for the current execution context. Empty (the
#: default) means every caller shares one process-wide plan cache — the
#: structural keys make cross-tenant reuse safe by construction, so this
#: is the production configuration. The serving layer
#: (``serve/server.py``) sets a per-tenant namespace only when its
#: shared-plan-cache mode is OFF, which partitions the cache by tenant
#: (only tests turn it off, to pin that isolated tenants compile their
#: own programs). A contextvar, not a global: each worker thread/context
#: scopes its own queries without affecting concurrent ones.
_PLAN_NS: contextvars.ContextVar[str] = contextvars.ContextVar(
    "sparkdq4ml_plan_namespace", default="")


def plan_namespace_tag() -> str:
    """Key prefix for the active cache namespace (empty in shared mode).
    Prepended to pipeline plan keys here and to grouped-execution plan
    keys in ``ops/segments.py`` — both engines partition together."""
    ns = _PLAN_NS.get()
    return f"ns:{ns!r}|" if ns else ""


@contextlib.contextmanager
def plan_namespace(ns: str):
    """Scope plan-cache keys to namespace ``ns`` for the duration of the
    block (thread/context-local). ``ns=""`` is the shared namespace."""
    token = _PLAN_NS.set(str(ns))
    try:
        yield
    finally:
        _PLAN_NS.reset(token)


def clear_cache() -> None:
    """Drop every compiled plan (tests; conf flips) — the coalesced
    batched-dispatch cache too, since its entries close over base plans
    this cache just dropped."""
    with _CACHE_LOCK:
        _CACHE.clear()
        _BATCHED.clear()


def cache_len() -> int:
    with _CACHE_LOCK:
        return len(_CACHE)


def _lookup_plan(steps, extra, base_schema, shard=None):
    # Probe via the SAME _linearize walk that builds plans: key equality
    # guarantees the probe's lit order matches the cached program's
    # _ArgLit slots (the lowered trees are discarded on a hit, and are the
    # new plan's on a miss).
    walk = _linearize(steps, extra, base_schema)
    key, lits = walk[0], walk[1]
    if shard is not None:
        key = shard.tag() + "|" + key
    key = plan_namespace_tag() + key
    lit_values = tuple(
        # dqlint: ok(host-sync): hoisted literals are host scalars (numpy
        # or python) by Lit construction — never device arrays
        v.value.item() if hasattr(v.value, "item") else v.value
        for v in lits)
    with _CACHE_LOCK:
        plan = _CACHE.get(key)
        if plan is not None:
            _CACHE.move_to_end(key)
            return plan, lit_values
    plan = _Plan(steps, extra, walk, shard)
    plan.key = key                 # namespace rides the cached identity
    with _CACHE_LOCK:
        # Insert-if-absent: two threads can race past the probe and both
        # build this plan. Keeping the FIRST inserted object (instead of
        # overwriting) means every later hit/compile stat lands on the
        # one entry cache_report() sees — an overwrite would strand the
        # winner's stats on an evicted object (lost updates under the
        # 16-thread hammer test).
        existing = _CACHE.get(key)
        if existing is not None:
            _CACHE.move_to_end(key)
            return existing, lit_values
        _CACHE[key] = plan
        while len(_CACHE) > int(config.pipeline_cache_size):
            _CACHE.popitem(last=False)
            counters.increment("pipeline.evict")
    return plan, lit_values


# ---------------------------------------------------------------------------
# Padding + execution
# ---------------------------------------------------------------------------

def _pad(arr, b: int, fresh: bool):
    """Pad a device column to ``b`` row slots (zero tail). ``fresh``
    forces a copy even when no padding is needed — required for buffers
    the compiled call donates (the frame may share the original).
    Public as :data:`pad_rows` — the grouped engine (``ops/segments.py``)
    pads its key/value/mask inputs with the same helper."""
    a = jnp.asarray(arr)
    n = a.shape[0]
    if n == b:
        return jnp.copy(a) if fresh else a
    fill = jnp.zeros((b - n,) + a.shape[1:], a.dtype)
    return jnp.concatenate([a, fill], axis=0)


pad_rows = _pad


@functools.partial(jax.jit, static_argnums=1)
def _unpad_tree(tree, n: int):
    """Slice every padded output back to ``n`` rows in ONE dispatch —
    un-jitted per-array ``a[:n]`` slices cost a dispatch each (~1 ms × 11
    outputs on a 20-op chain, dominating the flush). A trivial
    memcpy program; its per-(shapes, n) retrace is not a pipeline
    compile."""
    return jax.tree_util.tree_map(lambda a: a[:n], tree)


def _flush_budget() -> Optional[int]:
    """Device-byte budget for ONE flush, or None (the production default,
    where the check costs one None check + one int check). Sources, in
    priority order: an injected ``oom`` fault (``utils.faults`` —
    deterministic shrunken budget, the chaos arm) and an explicit
    ``spark.audit.deviceBudget`` conf scaled by
    ``spark.audit.memoryFraction`` (the PR-9 static-bound threshold,
    promoted here from an audit-time annotation to a live pre-execution
    sensor). The allocator ``bytes_limit`` is deliberately NOT consulted
    on the hot path — reading it per flush is backend-API traffic the
    no-budget case must not pay."""
    shrunk = _faults.shrunk_budget("oom")
    if shrunk is not None:
        return shrunk
    budget = int(config.audit_device_budget)
    if budget > 0:
        return int(budget * float(config.audit_memory_fraction))
    return None


def flush_budget() -> Optional[int]:
    """Public read of the per-flush device-byte budget (None = no bound
    configured): the adaptive re-planner (``sql/adaptive.py``) re-checks
    a re-bucketed stage's static byte bound against the SAME budget the
    flush-time chunking ladder enforces, so the two layers can never
    disagree on what fits."""
    return _flush_budget()


def _est_flush_bytes(plan, data: dict, b: int) -> int:
    """Cheap, import-free over-approximation of the flush program's
    resident bytes at bucket ``b``: padded inputs + mask + 2× one
    engine-float column per produced output (value + one temporary). The
    precise instrument is the dqaudit jaxpr bound (``analysis/program``),
    but the flush hot path must never import the analysis package (the
    PR-9 hot-path pin), so the degrade decision uses this coarser mirror
    — linear in referenced columns, no tracing, only over-counts the
    per-row footprint."""
    total = b   # bool mask
    out_itemsize = np.dtype(float_dtype()).itemsize
    for name in plan.kept + plan.donated:
        a = data[name]
        width = a.shape[1] if getattr(a, "ndim", 1) == 2 else 1
        total += b * width * np.dtype(a.dtype).itemsize
    total += 2 * b * out_itemsize * max(plan.n_outputs, 1)
    return total


def _run_chunked(plan, lit_values, data: dict, mask, n: int,
                 budget: int, est: int):
    """Row-chunked execution of an over-budget flush — degrade to bounded
    memory BEFORE the allocator dies, instead of an OOM backtrace after.

    Sound because the compilable step surface is purely elementwise
    (strings and aggregates never defer, a UDF only when its function is
    seen to be row-local; a filter's mask AND is row-local), so slicing
    rows, replaying the SAME cached plan per
    slice, and concatenating is semantics-preserving — the chunk rows are
    a power of two, so all chunks but the tail share one compiled
    program. Counted ``pipeline.oom_chunked`` + a ``recovery.fallback``
    event at site ``oom`` (rung ``chunked``)."""
    counters.increment("pipeline.oom_chunked")
    # rows per chunk: scale the estimate down to the budget, snap to a
    # power of two (bucket reuse), floor at the bucket floor so even a
    # 1-byte injected budget makes progress
    per_row = max(1.0, est / float(max(n, 1)))
    m = max(1, int(budget / per_row))
    m = 1 << max(m.bit_length() - 1, 0)
    m = max(m, max(int(config.pipeline_min_bucket), 1))
    m = min(m, n)
    nchunks = -(-n // m)
    from ..utils.recovery import RECOVERY_LOG

    RECOVERY_LOG.record(
        "oom", "fallback", rung="chunked",
        detail=f"est {est} B > budget {budget} B; "
               f"{nchunks} chunk(s) of {m} rows")
    mask = jnp.asarray(mask, jnp.bool_)
    before = plan.traces
    stats_on = config.stats_enabled
    t_stats = time.perf_counter() if stats_on else 0.0
    pieces_changed: dict[str, list] = {}
    pieces_mask: list = []
    pieces_extras: dict[str, list] = {}
    bucket_counts: dict[int, int] = {}
    with _obs.span("frame.pipeline.flush", cat="frame", rows=n, bucket=m,
                   chunks=nchunks, oom_budget=budget, est_bytes=est,
                   plan_key=plan.key):
        # same chaos hook as the unchunked dispatch (one fire per FLUSH,
        # inside the flush span): an over-budget flush is still a flush,
        # and a scheduled pipeline_flush fault must reach the
        # Frame._flush ladder in the memory-constrained regime too
        _faults.inject("pipeline_flush")
        for start in range(0, n, m):
            rows = min(start + m, n) - start
            cb = bucket_size(rows)
            kept = {name: _pad(data[name][start:start + rows], cb,
                               fresh=False)
                    for name in plan.kept}
            donated = tuple(_pad(data[name][start:start + rows], cb,
                                 fresh=plan.donates)
                            for name in plan.donated)
            mask_in = _pad(mask[start:start + rows], cb, fresh=False)
            if plan.example is None:
                # same idempotent recording as the unchunked path — a
                # plan whose FIRST execution is chunked must still be
                # enumerable by the PR-9 program auditor
                plan.example = (
                    {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for k, v in kept.items()},
                    tuple(jax.ShapeDtypeStruct(v.shape, v.dtype)
                          for v in donated),
                    jax.ShapeDtypeStruct(mask_in.shape, mask_in.dtype),
                    lit_values)
            with warnings.catch_warnings():
                # same unusable-donation suppression as the unchunked
                # dispatch — chunked compiles must not spam stderr
                warnings.filterwarnings(
                    "ignore", message=".*[Dd]onated.*",
                    category=UserWarning)
                changed, new_mask, extras = plan.fn(
                    kept, donated, mask_in, lit_values)
            if cb != rows:
                changed, new_mask, extras = _unpad_tree(
                    (changed, new_mask, extras), rows)
            bucket_counts[cb] = bucket_counts.get(cb, 0) + 1
            for k, v in changed.items():
                pieces_changed.setdefault(k, []).append(v)
            pieces_mask.append(new_mask)
            for k, v in extras.items():
                pieces_extras.setdefault(k, []).append(v)
        _note_rules(plan, n)
    compiled = plan.traces - before
    if nchunks > compiled:
        counters.increment("pipeline.hit", nchunks - compiled)
    with _CACHE_LOCK:   # per-entry stats stay dispatch-coherent
        plan.compiles += compiled
        plan.hits += nchunks - compiled
        # per-BUCKET tallies (the tail chunk's smaller bucket included):
        # the retrace detector's expected_traces is len(buckets), so
        # folding the tail into m would misread the tail compile as a
        # retrace leak
        for cb, c in bucket_counts.items():
            plan.buckets[cb] = plan.buckets.get(cb, 0) + c

    def cat(vs):
        return vs[0] if len(vs) == 1 else jnp.concatenate(vs)

    changed = {k: cat(vs) for k, vs in pieces_changed.items()}
    extras = {k: cat(vs) for k, vs in pieces_extras.items()}
    new_data = dict(data)
    new_data.update(changed)
    new_mask = cat(pieces_mask)
    if config.dq_profile_enabled:
        # ONE profile per flush, over the whole columns (a tally per chunk
        # would count one evaluation of a rule as many)
        _record_dq_profile(plan, changed, extras, new_mask, mask, n, None)
    if stats_on:
        # one record per flush (the chunked execution IS one logical
        # execution of this plan) — the heaviest plans are exactly the
        # history the est-rows/CBO store most needs
        _record_flush_stats(
            plan, data, m, n,
            (time.perf_counter() - t_stats) * 1e3, compiled > 0,
            new_mask, est=est)
    return new_data, new_mask, extras


def _record_flush_stats(plan, data, b: int, n: int,
                        wall_ms: float, compiled: bool, new_mask,
                        est=None, sel_scalar=None) -> None:
    """Plan-stats observatory hand-off (``utils/statstore.py``): one
    ``record_flush`` per execution of this plan (wall/compile digest,
    static byte estimate) and — when the flush carried a filter — a
    DEFERRED selectivity observation: ``sum(new_mask)`` is dispatched as
    one tiny async device reduction here and pulled in a batched,
    counted drain on the cold paths (report/EXPLAIN/save), never a sync
    on this path. Called only when ``spark.stats.enabled``; any failure
    is swallowed — statistics must never take a flush down."""
    from ..utils import statstore as _stats

    try:
        _stats.STORE.record_flush(
            plan.key, "pipeline", wall_ms=wall_ms, compiled=compiled,
            est_bytes=(est if est is not None
                       else _est_flush_bytes(plan, data, b)))
        if plan.has_filter:
            skey = _stats.selectivity_key(plan.key)
            if skey is not None:
                # sharded flushes hand over the program's own per-shard
                # valid counts — an eager sum over the sharded mask here
                # would dispatch a cross-shard collective on the hot path
                _stats.STORE.defer_rows(
                    skey, "filter", n,
                    sel_scalar if sel_scalar is not None
                    else jnp.sum(new_mask))
    except Exception:
        logger.debug("stats hand-off failed", exc_info=True)


def _record_dq_profile(plan, changed, extras, new_mask, mask_in, b: int,
                       shard) -> None:
    """Data-quality observatory hand-off (``utils/dqprof.py``): enqueue
    deferred column-sketch reductions over this flush's outputs, plus a
    ``[rows, passed]`` reduction for every output column that is a
    registered DQ rule's (``plan.rule_cols``) — counted against the
    flush's INPUT mask, because the reference app fuses ``rule`` and
    ``WHERE rule > 0`` into one flush and the output mask has already
    swallowed the violations. Called only when
    ``spark.dq.profile.enabled``; any failure is swallowed — profiling
    must never take a flush down (dqprof degrades itself through the
    ``dq_profile`` fault ladder besides)."""
    from ..utils import dqprof as _dqprof

    try:
        outs = {"changed": changed, "extras": extras}
        rules = [(rule, outs[where][name])
                 for rule, where, name in plan.rule_cols]
        _dqprof.observe_flush(changed, new_mask, b, shard=shard,
                              rules=rules, mask_in=mask_in)
    except Exception:
        logger.debug("dq-profile hand-off failed", exc_info=True)


#: Stage-boundary placement (cost-based optimizer, level >= 2): minimum
#: pending-step count for a chain to count as a "mega-stage" worth
#: probing, and the minimum recorded compile cost (statstore p50) of the
#: warm prefix for a split to pay — below it the two extra dispatches
#: cost more than the avoided recompile.
_SPLIT_MIN_STEPS = 6
_SPLIT_MIN_COMPILE_MS = 5.0


def _split_point(steps, extra, schema) -> Optional[int]:
    """Fused-stage boundary placement, informed by recorded compile-cost
    digests (ISSUE 14 / ``utils.statstore``): when a mega-stage's full
    plan is COLD (about to compile) but its first-half prefix is already
    compiled-and-cached with a recorded compile cost that dominates
    replay savings, split the flush at that boundary — the prefix
    replays as a cache hit and only the (smaller) tail compiles. The
    merge direction needs no hook: deferral already coalesces adjacent
    cheap stages into one program.

    Pure host-side planning: one ``_linearize`` walk plus two cache
    probes; only reached at ``spark.optimizer.level >= 2``. Returns the
    step index to split at, or None. Sound for ANY split point: the
    compilable step surface is purely elementwise-and-mask-AND, so
    running the same steps as two sequential programs is
    semantics-preserving (the row-chunked degrade's argument, applied
    along the step axis instead of the row axis)."""
    try:
        key, _lits, _s, _e, _r = _linearize(steps, tuple(extra), schema)
    except Exception:
        return None
    ns = plan_namespace_tag()
    parts = key.split("|")
    if len(parts) != 1 + len(steps) + len(extra):
        return None          # a key fragment embeds '|': stay unsplit
    with _CACHE_LOCK:
        if ns + key in _CACHE:
            return None      # warm mega-plan: replay beats any split
        k = len(steps) // 2
        prefix_key = ns + "|".join(parts[:1 + k])
        if prefix_key not in _CACHE:
            return None
    from ..utils import statstore as _stats

    cost = _stats.STORE.compile_ms_p50(prefix_key)
    if cost is None or cost < _SPLIT_MIN_COMPILE_MS:
        return None
    return k


def _history_bytes(key: str) -> Optional[int]:
    """Remembered resident-byte bound for a plan key (max of the static
    estimate and the measured peak across sessions) — the memory-aware
    chunking input the optimizer promotes from a fault-ladder rung to a
    planned decision. None = no history; never raises."""
    from ..utils import statstore as _stats

    try:
        return _stats.STORE.bytes_bound(key)
    except Exception:
        return None


def selectivity_key_for(where_steps, schema) -> Optional[str]:
    """The selectivity-entry key a flush of ``where_steps`` over
    ``schema`` would record under — computed WITHOUT executing anything
    (the same ``_linearize`` walk that builds plan keys, then the
    statstore's filter-part extraction). EXPLAIN uses this to address
    persisted history from a parsed query's WHERE clause on a fresh
    session. Returns None when the steps are not structurally
    compilable (those flushes take the eager path and record nothing)."""
    from ..utils import statstore as _stats

    try:
        key, _lits, _s, _e, _r = _linearize(tuple(where_steps), (),
                                            schema)
    except Exception:
        return None
    return _stats.selectivity_key(key)


def run_pipeline(data: dict, mask, n: int, steps, extra=(), shard=None):
    """Execute pending ``steps`` (+ ``extra`` projection expressions) over
    the base column dict as one compiled program.

    Returns ``(new_data, new_mask, extras)`` where ``new_data`` is a fresh
    column dict (replaced columns in place, new columns appended),
    ``new_mask`` the post-filter validity mask, and ``extras`` maps the
    requested projection names to their arrays — everything sliced back
    to ``n`` rows. Raises :class:`PipelineError` on any internal failure;
    callers must fall back to the eager path (never lose correctness to
    an optimization layer).

    ``shard`` (a ``parallel.shard.ShardedStore``) selects the sharded
    lowering: the frame's arrays are already laid out at the store's
    padded slot count, so ``n == slots``, no bucket padding or unpad
    slicing happens, and the plan dispatches as one ``shard_map``
    program under the collective guard — still zero counted host syncs.
    """
    counters.increment("pipeline.flush")
    # BASE schema only (lazy: only referenced columns get dtype probes) —
    # _lookup_plan/_Plan evolve it step-by-step so a column read before a
    # later step replaces it stays a base input.
    schema = LazySchema(data)
    try:
        b = n if shard is not None else bucket_size(n)
        # Stage-boundary placement (cost-based optimizer, level >= 2 —
        # default off): a cold mega-stage with a warm, compile-heavy
        # prefix splits into prefix-replay + tail-compile. Each half is
        # a full flush of this same entry point (its own stats, spans,
        # chunking, ladder).
        if (shard is None and n > 0
                and config.optimizer_enabled
                and int(config.optimizer_level) >= 2
                and len(steps) >= _SPLIT_MIN_STEPS):
            k = _split_point(steps, extra, schema)
            if k:
                counters.increment("optimizer.split")
                mid_data, mid_mask, _ = run_pipeline(
                    data, mask, n, steps[:k], ())
                return run_pipeline(mid_data, mid_mask, n, steps[k:],
                                    extra)
        plan, lit_values = _lookup_plan(steps, tuple(extra), schema, shard)
        # Pre-execution memory degrade (ISSUE 11 / arxiv 2206.14148):
        # when a device-byte budget is known (explicit
        # spark.audit.deviceBudget conf, or an injected `oom` fault
        # shrinking it) and the static estimate for this flush exceeds
        # it, execute row-chunked BEFORE the allocator can die — the
        # production default (no budget, no fault plan) costs one int
        # check and one None check.
        if n > 0:   # n==0 first, so a zero-row flush (where chunking is
            # meaningless) can never burn a one-shot injected oom fault
            budget = _flush_budget()
            if budget is not None:
                if shard is not None:
                    # per-SHARD resident bytes against the budget; an
                    # over-budget sharded flush degrades one rung to
                    # single-device row-chunked execution (gather first)
                    est = _est_flush_bytes(plan, data, shard.bucket)
                    if est > budget:
                        from ..parallel.shard import gather_arrays
                        from ..utils.recovery import RECOVERY_LOG

                        RECOVERY_LOG.record(
                            "shard_flush", "fallback", rung="chunked",
                            detail=f"per-shard est {est} B > budget "
                                   f"{budget} B; gathered to "
                                   "single-device chunked execution")
                        arrs = gather_arrays(
                            shard, mask, *(data[name] for name in
                                           plan.kept + plan.donated))
                        mask = arrs[0]
                        data = dict(data)
                        data.update(zip(plan.kept + plan.donated,
                                        arrs[1:]))
                        plan, lit_values = _lookup_plan(
                            steps, tuple(extra), schema)
                        est = _est_flush_bytes(plan, data, bucket_size(n))
                        return _run_chunked(plan, lit_values, data, mask,
                                            n, budget, est)
                else:
                    est = _est_flush_bytes(plan, data, b)
                    if (est <= budget and config.optimizer_enabled
                            and config.stats_enabled):
                        # memory-aware chunking as a PLANNED decision
                        # (ISSUE 14): a plan whose REMEMBERED byte bound
                        # (measured peaks included, persisted across
                        # sessions) exceeds the budget chunks up front
                        # even when the cheap static mirror under-counts
                        hist = _history_bytes(plan.key)
                        if hist is not None and hist > budget:
                            counters.increment("optimizer.mem_chunk")
                            est = hist
                    if est > budget:
                        return _run_chunked(plan, lit_values, data, mask,
                                            n, budget, est)
        before = plan.traces
        kept = {name: _pad(data[name], b, fresh=False)
                for name in plan.kept}
        # freshness only matters for buffers the call donates (the frame
        # may share the originals); _pad's zero fill is False for bool,
        # so the padded mask tail is invalid by construction
        donated = tuple(_pad(data[name], b, fresh=plan.donates)
                        for name in plan.donated)
        mask_in = _pad(jnp.asarray(mask, jnp.bool_), b, fresh=False)
        if plan.example is None:
            # Abstract specs only (shape/dtype metadata, no device read);
            # idempotent, so the benign cross-thread race needs no lock.
            plan.example = (
                {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in kept.items()},
                tuple(jax.ShapeDtypeStruct(v.shape, v.dtype)
                      for v in donated),
                jax.ShapeDtypeStruct(mask_in.shape, mask_in.dtype),
                lit_values)
        # Plan-stats observatory gate: ONE flag read; disabled mode pays
        # nothing else on this path (test-pinned, chaos-pin style).
        stats_on = config.stats_enabled
        t_stats = time.perf_counter() if stats_on else 0.0
        # Cross-request coalescing scope (serve/coalesce.py): the serving
        # worker arms it per job; everywhere else (and in serve's
        # disabled / light-load modes) it is None and the dispatch below
        # is byte-for-byte the per-request path — ONE None check,
        # test-pinned like the chaos hooks. Sharded flushes never
        # coalesce (they already serialize on the mesh).
        coal = _COALESCE.get()
        with warnings.catch_warnings():
            # donation of a replaced column whose output dtype differs
            # (int column replaced by a float expression) is unusable —
            # harmless, and the warning would spam every compile
            warnings.filterwarnings(
                "ignore", message=".*[Dd]onated.*", category=UserWarning)
            span_cm = _obs.TRACER.span(
                "frame.pipeline.flush", cat="frame", steps=len(steps),
                outputs=len(extra), rows=n, bucket=b,
                # the cost-observatory join handle: EXPLAIN ANALYZE maps
                # this span's operator node to its cached CostProfile by
                # plan key (an attribute read, never formatting)
                plan_key=plan.key)
            if span_cm is _obs._NOOP:   # the gate, read once, was off
                span_cm = None
            # chaos hook at the dispatch boundary (one None check without
            # a plan): a due device_error raises HERE — inside the flush
            # span, so EXPLAIN ANALYZE attributes the fault to the
            # operator whose flush absorbed it — and escapes un-wrapped
            # for the Frame._flush recovery ladder below.
            shard_valid = None
            if span_cm is None:
                _faults.inject("pipeline_flush")
                if shard is not None:
                    _faults.inject("shard_flush")
                    changed, new_mask, extras, shard_valid = plan.fn(
                        kept, donated, mask_in, lit_values)
                elif coal is not None:
                    changed, new_mask, extras = coal.dispatch(
                        plan, b, kept, donated, mask_in, lit_values)
                else:
                    changed, new_mask, extras = plan.fn(
                        kept, donated, mask_in, lit_values)
                _note_rules(plan, n)
                compiled = plan.traces > before
            else:
                with span_cm as sp:
                    _faults.inject("pipeline_flush")
                    if shard is not None:
                        _faults.inject("shard_flush")
                        changed, new_mask, extras, shard_valid = plan.fn(
                            kept, donated, mask_in, lit_values)
                        sp.set(shards=shard.devices)
                    elif coal is not None:
                        changed, new_mask, extras = coal.dispatch(
                            plan, b, kept, donated, mask_in, lit_values)
                        sp.set(coalesce=True)
                    else:
                        changed, new_mask, extras = plan.fn(
                            kept, donated, mask_in, lit_values)
                    _note_rules(plan, n)
                    compiled = plan.traces > before
                    sp.set(cache="compile" if compiled else "hit")
        if not compiled:
            counters.increment("pipeline.hit")
        with _CACHE_LOCK:     # per-entry stats for cache_report()
            if compiled:
                plan.compiles += 1
            else:
                plan.hits += 1
            plan.buckets[b] = plan.buckets.get(b, 0) + 1
        # Data-quality observatory gate (utils/dqprof.py): ONE flag
        # read; disabled mode pays nothing else on this path
        # (test-pinned, chaos-pin style). Runs on the PADDED bucket
        # arrays so sketch programs retrace per power-of-two bucket,
        # never per raw row count.
        if config.dq_profile_enabled:
            _record_dq_profile(plan, changed, extras, new_mask, mask_in,
                               b, shard)
        if b != n:
            changed, new_mask, extras = _unpad_tree(
                (changed, new_mask, extras), n)
        if stats_on:
            # selectivity baseline = TRUE rows: a sharded frame's n is
            # the padded slot count, while its single-device twin (which
            # shares the layout-stripped selectivity entry) reports its
            # unpadded slots — mixing the two would skew the shared
            # history by the padding factor
            _record_flush_stats(
                plan, data, b, shard.rows if shard is not None else n,
                (time.perf_counter() - t_stats) * 1e3, compiled, new_mask,
                sel_scalar=shard_valid)
        new_data = dict(data)
        new_data.update(changed)
        return new_data, new_mask, extras
    except PipelineError:
        counters.increment("pipeline.fallback")
        raise
    except jax.errors.JaxRuntimeError:
        # A DEVICE fault (real or injected), not a compiler failure: it
        # escapes un-wrapped so the Frame._flush degradation ladder can
        # retry-then-degrade it through the recovery engine — wrapping it
        # as PipelineError would silently eat it as an eager fallback.
        raise
    except Exception as e:          # any jax/trace surprise → eager replay
        counters.increment("pipeline.fallback")
        raise PipelineError(str(e)) from e


# ---------------------------------------------------------------------------
# Cache introspection (observability.CACHES — see session.cache_report())
# ---------------------------------------------------------------------------

def cache_stats() -> dict:
    """Registry callback: size/capacity, hit/miss/eviction counters, and
    one entry per cached program (stable ``program_key``, replay count,
    bucket histogram) — the per-program lines EXPLAIN ANALYZE prints."""
    with _CACHE_LOCK:
        entries = [{"key": p.key[:160], "program_key": p.key,
                    "hits": p.hits,
                    "compiles": p.compiles, "buckets": dict(p.buckets),
                    "runtime_literals": p.n_lits}
                   for p in _CACHE.values()]
    return {
        "kind": "plan-keyed jit cache (fused expression pipeline)",
        "size": len(entries),
        "capacity": int(config.pipeline_cache_size),
        "hits": counters.get("pipeline.hit"),
        "misses": counters.get("pipeline.compile"),
        "evictions": counters.get("pipeline.evict"),
        "fallbacks": counters.get("pipeline.fallback"),
        "entries": entries,
    }


#: Numeric literal tokens of the plan-key grammar (``V(3)``/``V(3.5)``/
#: ``V(1e-06)``) — the positions literal hoisting should have emptied.
#: Bool (``V(True)``), NaN, and string literals stay distinct: the
#: compiler keys them deliberately (see ``_hoistable_lit``).
_NUM_LIT_RE = re.compile(r"V\((-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)\)")


def _bucket_variant(example, factor: int):
    """The example specs re-bucketed ``factor`` powers-of-two up — every
    padded input shares the row axis, so scaling the leading dim of each
    array spec is exactly "the same plan at a later shape bucket". The
    retrace detector compares TWO such variants (x2 vs x4) so both
    traces are fresh under the current config — never jax's possibly
    stale cached trace of the recorded shape."""
    kept, donated, mask, lits = example

    def up(s):
        shape = (s.shape[0] * factor,) + tuple(s.shape[1:])
        return jax.ShapeDtypeStruct(shape, s.dtype)

    return (({k: up(v) for k, v in kept.items()},
             tuple(up(v) for v in donated), up(mask), lits), {})


def program_handles() -> list:
    """Registry callback (observability.CACHES.register_programs): one
    :class:`~..utils.observability.ProgramHandle` per cached plan that
    has executed at least once. ``fn`` is the UN-counted trace body —
    re-tracing it is invisible to ``pipeline.compile`` and to the
    per-plan replay-verdict counter. ``expected_traces`` is the number
    of distinct shape buckets the plan served: a healthy plan compiles
    once per bucket, so ``observed > expected`` is a retrace leak."""
    with _CACHE_LOCK:
        plans = list(_CACHE.values())
    out = []
    for p in plans:
        if p.example is None:
            continue
        kept, donated, mask, lits = p.example
        out.append(_obs.ProgramHandle(
            "pipeline", p.key, p.trace_body,
            args=(kept, donated, mask, lits),
            variants={"bucket": [_bucket_variant(p.example, 2),
                                 _bucket_variant(p.example, 4)]},
            mesh=p.mesh, guarded=p.guarded,
            meta={"expected_traces": max(len(p.buckets), 1),
                  "observed_traces": p.traces,
                  # the literal-erased key: two plans colliding here are
                  # one program cached per literal VALUE — the hoisting
                  # regression the retrace detector's finalize pass
                  # closes (numeric V(...) tokens only; bool/NaN/string
                  # literals are deliberately key-resident)
                  "dedup_key": _NUM_LIT_RE.sub("V(#)", p.key),
                  "runtime_literals": p.n_lits}))
    return out


# ---------------------------------------------------------------------------
# Cross-request coalescing: vmapped batched dispatch (serve/coalesce.py)
# ---------------------------------------------------------------------------

#: Coalescing scope for the CURRENT execution context. None (the
#: default, and the only state outside an armed serving worker) keeps
#: ``run_pipeline``'s dispatch byte-for-byte the per-request path — one
#: None check, test-pinned. A serving worker whose job qualifies for
#: coalescing (conf-enabled, queue depth at/over ``minQueueDepth``,
#: deadline headroom) sets a sink whose ``dispatch()`` may rendezvous
#: this flush with concurrent same-plan flushes into ONE stacked device
#: program (see :func:`run_batched`). A contextvar, not a global: each
#: worker scopes its own job without affecting concurrent ones.
_COALESCE: contextvars.ContextVar = contextvars.ContextVar(
    "sparkdq4ml_coalesce", default=None)


@contextlib.contextmanager
def coalesce_scope(sink):
    """Route this context's unsharded pipeline flushes through ``sink``
    (an object with ``dispatch(plan, b, kept, donated, mask, lits)`` —
    the serving layer's :class:`~..serve.coalesce.Coalescer` member
    handle) for the duration of the block. ``sink=None`` restores the
    per-request path."""
    token = _COALESCE.set(sink)
    try:
        yield
    finally:
        _COALESCE.reset(token)


def coalesce_batch_bucket(n: int) -> int:
    """Member-count bucket for a coalesced batch: the next power of two,
    so a burst of 3 and a burst of 4 share one batched program (the pad
    member rides along and its outputs are discarded, exactly the row-
    padding argument applied to the member axis)."""
    return 1 << max(int(n) - 1, 0).bit_length()


class _BatchedPlan:
    """One coalesced-dispatch cache entry: ``jax.vmap`` of the base
    plan's UN-counted trace body over a new leading member axis, jitted
    once per (plan key, member-count bucket). The vmapped body is the
    auditor's re-trace surface (:func:`coalesce_program_handles`);
    the jitted entry counts its own traces for the retrace verdict —
    never the base plan's, whose replay stats stay per-request.

    The jitted entry takes the MEMBERS' argument tuples directly and
    does the stack, the vmapped body, and the per-member de-interleave
    inside ONE program: host-side ``jnp.stack`` per input array plus a
    separate split dispatch would cost a framework round-trip per array
    — more per-dispatch overhead than the solo flushes it replaces on
    dispatch-bound backends. XLA fuses the concatenates and slices into
    the body, so a coalesced flush is exactly one host->device call."""

    __slots__ = ("base", "batch", "key", "vbody", "fn", "hits",
                 "compiles", "traces", "buckets", "example",
                 "_trace_lock")

    def __init__(self, plan: _Plan, batch: int):
        self.base = plan
        self.batch = int(batch)
        self.key = f"coalesce[x{self.batch}]|{plan.key}"
        vbody = jax.vmap(plan.trace_body)
        self.vbody = vbody
        self.hits = 0
        self.compiles = 0
        self.traces = 0
        self.buckets: dict[int, int] = {}
        self.example: Optional[tuple] = None
        self._trace_lock = threading.Lock()
        n_don = len(plan.donated)
        n_lits = plan.n_lits
        kept_names = tuple(plan.kept)

        def program(members):
            with self._trace_lock:
                self.traces += 1
            kept_s = {name: jnp.stack([m[0][name] for m in members])
                      for name in kept_names}
            donated_s = tuple(jnp.stack([m[1][i] for m in members])
                              for i in range(n_don))
            mask_s = jnp.stack([m[2] for m in members])
            lits_s = tuple(jnp.stack([m[3][i] for m in members])
                           for i in range(n_lits))
            out = vbody(kept_s, donated_s, mask_s, lits_s)
            return [jax.tree_util.tree_map(lambda a, i=i: a[i], out)
                    for i in range(len(members))]

        # No donation even on accelerators: the member buffers must
        # survive for the degrade path's per-request replay.
        self.fn = jax.jit(program)


_BATCHED: "OrderedDict[tuple, _BatchedPlan]" = OrderedDict()
_BATCHED_EVICTIONS = 0


def _lookup_batched(plan: _Plan, batch: int) -> _BatchedPlan:
    global _BATCHED_EVICTIONS
    key = (plan.key, batch)
    with _CACHE_LOCK:
        bp = _BATCHED.get(key)
        if bp is not None:
            _BATCHED.move_to_end(key)
            return bp
    bp = _BatchedPlan(plan, batch)
    with _CACHE_LOCK:
        # same insert-if-absent discipline as _lookup_plan: the FIRST
        # inserted object keeps the stats every later dispatch lands on
        existing = _BATCHED.get(key)
        if existing is not None:
            _BATCHED.move_to_end(key)
            return existing
        _BATCHED[key] = bp
        while len(_BATCHED) > int(config.pipeline_cache_size):
            _BATCHED.popitem(last=False)
            _BATCHED_EVICTIONS += 1
    return bp


def est_member_bytes(plan: _Plan, kept: dict, donated, b: int) -> int:
    """Per-member resident-byte estimate of a coalesced flush, computed
    from the already-padded member inputs (the coalescer prices the
    STACKED batch as ``members × this`` against the admission budget —
    the same cheap static mirror as :func:`_est_flush_bytes`, fed from
    buffers instead of the frame dict)."""
    total = b   # bool mask
    out_itemsize = np.dtype(float_dtype()).itemsize
    for a in list(kept.values()) + list(donated):
        total += int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
    total += 2 * b * out_itemsize * max(plan.n_outputs, 1)
    return total


def run_batched(plan: _Plan, b: int, members):
    """Execute ``members`` — each ``(kept, donated, mask, lit_values)``,
    every one already padded to row bucket ``b`` by its own
    ``run_pipeline`` frame — as ONE stacked device dispatch of the
    vmapped plan body, and return the per-member ``(changed, new_mask,
    extras)`` list in member order.

    Inputs stack along a new leading member axis (hoisted literals
    included: each scalar slot becomes a ``(batch,)`` argument the
    vmapped ``_ArgLit`` broadcasts per member, so queries differing only
    in literal VALUES still share the one batched program). The member
    count pads up to :func:`coalesce_batch_bucket` by repeating member
    0, whose extra outputs are dropped at the de-interleave."""
    n = len(members)
    batch = coalesce_batch_bucket(n)
    if batch > n:
        members = list(members) + [members[0]] * (batch - n)
    # normalized pytree structure (dict / tuple / leaf / tuple per
    # member): a list-vs-tuple drift between callers must not retrace
    margs = tuple((dict(m[0]), tuple(m[1]), m[2], tuple(m[3]))
                  for m in members)
    bp = _lookup_batched(plan, batch)
    before = bp.traces
    out = bp.fn(margs)
    if bp.example is None:
        # abstract specs of the STACKED form the vmapped body consumes
        # (the auditor re-traces ``bp.vbody``, not the member-tuple
        # wrapper), idempotent (the benign cross-thread race needs no
        # lock) — literals are (batch,) ARRAY specs here, not the base
        # plan's host scalars: the batched calling convention
        m0 = margs[0]

        def stacked(v):
            a = jnp.asarray(v)
            return jax.ShapeDtypeStruct((batch,) + tuple(a.shape),
                                        a.dtype)

        bp.example = (
            {k: stacked(v) for k, v in m0[0].items()},
            tuple(stacked(v) for v in m0[1]),
            stacked(m0[2]),
            tuple(stacked(v) for v in m0[3]))
    compiled = bp.traces > before
    with _CACHE_LOCK:   # per-entry stats stay dispatch-coherent
        if compiled:
            bp.compiles += 1
        else:
            bp.hits += 1
        bp.buckets[b] = bp.buckets.get(b, 0) + 1
    return out[:n]


def coalesce_cache_stats() -> dict:
    """Registry callback (observability.CACHES): the coalesced-dispatch
    cache next to the per-request plan cache in ``cache_report()`` /
    ``/metrics`` — one entry per (plan key, member-count bucket), its
    program key carrying the ``coalesce[xN]`` batch-bucket tag."""
    with _CACHE_LOCK:
        entries = [{"key": bp.key[:160], "program_key": bp.key,
                    "hits": bp.hits, "compiles": bp.compiles,
                    "buckets": dict(bp.buckets), "batch": bp.batch,
                    "runtime_literals": bp.base.n_lits}
                   for bp in _BATCHED.values()]
        evicts = _BATCHED_EVICTIONS
    return {
        "kind": "coalesced batched-dispatch cache (vmapped plans)",
        "size": len(entries),
        "capacity": int(config.pipeline_cache_size),
        "hits": sum(e["hits"] for e in entries),
        "misses": sum(e["compiles"] for e in entries),
        "evictions": evicts,
        "entries": entries,
    }


def _coalesce_variant(example, factor: int):
    """The batched example specs scaled ``factor`` up along the MEMBER
    axis (every stacked input shares it, literal columns included) —
    "the same vmapped plan at a later batch bucket", the structural-
    stability probe the retrace detector compares x2 vs x4."""
    kept, donated, mask, lits = example

    def up(s):
        shape = (s.shape[0] * factor,) + tuple(s.shape[1:])
        return jax.ShapeDtypeStruct(shape, s.dtype)

    return (({k: up(v) for k, v in kept.items()},
             tuple(up(v) for v in donated), up(mask),
             tuple(up(v) for v in lits)), {})


def coalesce_program_handles() -> list:
    """Registry callback (observability.CACHES.register_programs): one
    ProgramHandle per executed batched plan, so dqaudit's program tier
    and the costprof observatory enumerate the coalesced hot path
    exactly like per-request plans — ``fn`` is the un-counted vmapped
    body; ``expected_traces`` is the row buckets served at this batch
    bucket (each is one legitimate trace of the one jitted entry)."""
    with _CACHE_LOCK:
        plans = list(_BATCHED.values())
    out = []
    for bp in plans:
        if bp.example is None:
            continue
        out.append(_obs.ProgramHandle(
            "coalesce", bp.key, bp.vbody,
            args=bp.example,
            variants={"bucket": [_coalesce_variant(bp.example, 2),
                                 _coalesce_variant(bp.example, 4)]},
            meta={"expected_traces": max(len(bp.buckets), 1),
                  "observed_traces": bp.traces,
                  # literal-erased like the pipeline handles; the
                  # coalesce[xN] tag stays, so batch buckets are
                  # distinct programs, not dedup collisions
                  "dedup_key": _NUM_LIT_RE.sub("V(#)", bp.key),
                  "runtime_literals": bp.base.n_lits}))
    return out


_obs.CACHES.register("pipeline", cache_stats)
_obs.CACHES.register_programs("pipeline", program_handles)
_obs.CACHES.register("coalesce", coalesce_cache_stats)
_obs.CACHES.register_programs("coalesce", coalesce_program_handles)
