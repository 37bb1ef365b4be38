"""UDF registry — the engine capability behind ``spark.udf().register``.

The reference registers two data-quality UDFs with an explicit return dtype
(`DataQuality4MachineLearningApp.java:46-49`); registered names are callable
from column expressions (``call_udf``) and from the SQL subset. Functions must
be vectorized array→array (jnp) functions: the per-row boxed-object UDF call
path of Spark (SURVEY.md §3.2) is replaced by whole-column ops XLA can fuse.

**Which calls run inside a flush's compiled program.** The fused pipeline
(``ops/compiler.py``) takes three liberties with the rows of a flush: it
pads them to a bucket with an invalid tail, it may run them in row slices
(the over-budget degrade), and on a mesh it runs each shard alone. All three
are sound only for row-local work, and a registered function is free to be
anything: ``x - jnp.mean(x)`` is a legal UDF whose answer depends on every
row. So a call defers only when its function is *seen* to be row-local
(:func:`probe_elementwise`): traced once on abstract 1-D arguments of the
call's dtypes, it must trace at all (a host-only function raises), use
nothing but elementwise primitives, return one 1-D column of the probe's
length, and capture no array. Anything else — a whole-column function, a
cumulative one, one that calls numpy on its input — keeps the eager path,
where the function sees the frame's real columns and nothing is assumed of
it. There is no flag to set: the verdict follows from the function, is
cached on its registry entry per argument dtypes, and goes when ``register``
replaces the entry. (The probe calls the function once more than its
evaluations do, with abstract arguments: a function with side effects sees
that call.)
"""

from __future__ import annotations

import hashlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as _jcore

from .expressions import resolve_type_name

#: Rows of the probe's abstract arguments. Small (nothing is computed) and
#: prime, so that a constant-length array a function builds by itself does
#: not pass for a column by coincidence.
PROBE_ROWS = 13

#: Primitives that map row i of their operands to row i of their result.
#: Not here, so never admitted: reductions, cumulative ops, sort, gather,
#: scatter, dot, slice, pad, concatenate, reshape, iota, random bits,
#: control flow and callbacks.
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "neg", "abs", "sign", "max", "min",
    "pow", "integer_pow", "square", "sqrt", "rsqrt", "cbrt", "exp", "exp2",
    "log", "log1p", "expm1", "logistic", "sin", "cos", "tan", "asin",
    "acos", "atan", "atan2", "sinh", "cosh", "tanh", "asinh", "acosh",
    "atanh", "erf", "erfc", "erf_inv", "lgamma", "digamma", "floor", "ceil",
    "round", "is_finite", "nextafter", "clamp", "eq", "ne", "lt", "le",
    "gt", "ge", "and", "or", "xor", "not", "select_n",
    "convert_element_type", "reduce_precision", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "population_count",
    "clz", "copy",
})
#: Call-like primitives: judged by their bodies.
_CALLS = frozenset({
    "jit", "pjit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "checkpoint",
})


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        if isinstance(v, _jcore.ClosedJaxpr):
            yield v.jaxpr, v.consts
        elif isinstance(v, _jcore.Jaxpr):
            yield v, ()


def _canonical(jaxpr, consts, out: list) -> bool:
    """Append a canonical text of ``jaxpr`` to ``out`` — primitives,
    operand positions, dtypes, literal and captured scalar values, the
    plain parameters — and say whether every equation is elementwise.
    Variables are numbered in order of appearance and function objects,
    meshes and shardings are left out, so two traces of one function give
    one text and a changed literal gives another."""
    if any(np.ndim(c) != 0 for c in consts):
        return False                # a captured array: not row-local
    names: dict = {}

    def ref(v):
        if isinstance(v, _jcore.Literal):
            return f"{v.val!r}:{v.aval.dtype}"
        return f"v{names.setdefault(v, len(names))}:{v.aval.str_short()}"

    for var, c in zip(jaxpr.constvars, consts):
        # dqlint: ok(host-sync): a captured scalar, read once per
        # registered function and dtype signature, at probe time
        out.append(f"{ref(var)}={np.asarray(c).item()!r}")
    out.append("(" + ",".join(ref(v) for v in jaxpr.invars) + ")")
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = ",".join(ref(v) for v in eqn.invars)
        if prim in _CALLS:
            out.append(f"call({ins}){{")
            subs = list(_sub_jaxprs(eqn))
            if not subs or not all(_canonical(j, c, out) for j, c in subs):
                return False
            out.append("}")
        elif prim == "broadcast_in_dim":
            if eqn.invars[0].aval.ndim != 0:
                return False        # only a scalar may be spread over rows
            out.append(f"bcast({ins})")
        elif prim in _ELEMENTWISE:
            plain = ",".join(
                f"{k}={v}" for k, v in sorted(eqn.params.items())
                if isinstance(v, (bool, int, float, str, type(None),
                                  np.dtype)))
            out.append(f"{prim}[{plain}]({ins})")
        else:
            return False
        out.append("->" + ",".join(ref(v) for v in eqn.outvars))
    out.append("=>" + ",".join(ref(v) for v in jaxpr.outvars))
    return True


def probe_elementwise(fn: Callable, dtypes) -> Optional[str]:
    """A fingerprint of ``fn`` if it is row-local on 1-D arguments of
    ``dtypes``, else None (the module docstring says why and what for).

    The fingerprint is a hash of the traced program's canonical text, so
    the same function registered twice — or in two registries — has one
    fingerprint, and a function with another literal inside has another:
    the flush compiler keys its programs on it."""
    try:
        closed = jax.make_jaxpr(lambda *a: jnp.asarray(fn(*a)))(
            *(jax.ShapeDtypeStruct((PROBE_ROWS,), dt) for dt in dtypes))
    except Exception:
        return None                 # host-only, or wrong for these dtypes
    if [tuple(a.shape) for a in closed.out_avals] != [(PROBE_ROWS,)]:
        return None
    text: list = []
    if not _canonical(closed.jaxpr, closed.consts, text):
        return None
    return hashlib.sha1("\n".join(text).encode()).hexdigest()[:16]


class _Entry:
    """One registration: the function, its declared return dtype, and what
    the probe said of it per argument-dtype signature."""

    __slots__ = ("fn", "return_dtype", "verdicts")

    def __init__(self, fn, return_dtype):
        self.fn = fn
        self.return_dtype = return_dtype
        self.verdicts: dict = {}


class UDFRegistry:
    """Name → (vectorized fn, return dtype). One per session; a process-wide
    default registry backs sessions and bare ``call_udf`` use."""

    def __init__(self):
        self._fns: dict[str, _Entry] = {}

    def register(self, name: str, fn: Callable, return_type=None) -> Callable:
        """Register ``fn`` under ``name``.

        ``return_type`` may be a Spark SQL type name ("double", "integer", …)
        — mirroring ``DataTypes.DoubleType`` at the registration site — or a
        numpy/jnp dtype, or None to keep the fn's natural dtype.
        Registering a name again replaces the function and everything
        learnt about the old one.
        """
        if isinstance(return_type, str):
            return_type = resolve_type_name(return_type)
        self._fns[name] = _Entry(fn, return_type)
        return fn

    def lookup(self, name: str):
        try:
            e = self._fns[name]
            return e.fn, e.return_dtype
        except KeyError:
            raise KeyError(
                f"UDF {name!r} is not registered "
                f"(registered: {sorted(self._fns)})") from None

    def elementwise(self, name: str, dtypes):
        """``(fn, return_dtype, fingerprint)`` when ``name`` is registered
        here and its function is row-local on 1-D arguments of ``dtypes``
        (:func:`probe_elementwise`, asked once per signature); else None.
        A name the registry lacks is None too: the SQL builtins that
        ``UdfCall`` resolves by name are not this registry's."""
        e = self._fns.get(name)
        if e is None:
            return None
        sig = tuple(np.dtype(dt).name for dt in dtypes)
        if sig not in e.verdicts:
            e.verdicts[sig] = probe_elementwise(e.fn, dtypes)
        fp = e.verdicts[sig]
        return None if fp is None else (e.fn, e.return_dtype, fp)

    def __contains__(self, name: str) -> bool:
        return name in self._fns

    def names(self):
        return sorted(self._fns)


_DEFAULT = UDFRegistry()


def default_registry() -> UDFRegistry:
    return _DEFAULT


def register_udf(name: str, fn: Callable, return_type=None) -> Callable:
    """Module-level convenience mirroring ``spark.udf().register(name, fn, type)``."""
    return _DEFAULT.register(name, fn, return_type)
