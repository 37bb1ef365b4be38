"""Shared jaxpr machinery for the program auditor (dqaudit).

Everything here operates on the output of ``jax.make_jaxpr`` — pure
abstract evaluation: no XLA compile, no device execution, no host sync.
That property is the audit tier's whole contract ("Memory Safe
Computations with XLA", arxiv 2206.14148: program properties worth
gating on can be computed from the IR, before anything runs).

Three tools:

* :func:`trace` — abstract-trace a cached program from its recorded
  calling convention (``ShapeDtypeStruct`` leaves + host scalars);
* :func:`structural_signature` — a canonical hash of the program's
  STRUCTURE: primitive sequence, operand/output dtypes, nested jaxprs,
  and captured-constant skeleton, with concrete dimension sizes erased
  so the same plan traced at two shape buckets hashes identically
  (a difference ⇒ the program specializes on shape ⇒ steady-state
  retraces in serving);
* :func:`peak_bytes` — a liveness walk over eqn outvars: allocate each
  equation's outputs, free operands past their last use, track the
  running high-water mark. Aliasing/donation is deliberately ignored,
  so the result is an UPPER bound on XLA's buffer peak.
"""

from __future__ import annotations

import hashlib
from typing import Iterator, Optional

import jax
import numpy as np

__all__ = [
    "trace", "structural_signature", "peak_bytes", "iter_eqns",
    "collective_eqns", "callback_eqns",
    "COLLECTIVE_FAMILY", "CALLBACK_PRIMS",
]

#: Cross-device communication primitives of the installed JAX — every one
#: must resolve its axis names against the installed mesh
#: (collective-topology detector) — mapped to the family name the
#: detectors and cost tables report. Inside ``jax.shard_map``
#: (``check_vma=True``) ``lax.psum`` binds ``psum_invariant`` and
#: ``lax.all_gather`` binds ``all_gather_invariant``.
COLLECTIVE_FAMILY = {
    "psum": "psum", "psum_invariant": "psum",
    "pmin": "pmin", "pmax": "pmax",
    "all_gather": "all_gather", "all_gather_invariant": "all_gather",
    "all_to_all": "all_to_all", "ragged_all_to_all": "all_to_all",
    "reduce_scatter": "reduce_scatter",
    "ppermute": "ppermute", "pbroadcast": "pbroadcast",
}

#: Host-callback primitives of the installed JAX — a hidden host
#: round-trip inside a jitted body (hidden-sync detector).
#: ``jax.debug.print`` binds ``debug_print``, ``jax.debug.callback``
#: binds ``debug_callback``.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
})


def trace(fn, args=(), kwargs=None):
    """``jax.make_jaxpr`` over a recorded calling convention. Keyword
    arguments are closed over (make_jaxpr only maps positional args to
    avals); array-spec leaves stay abstract throughout — nothing
    compiles, nothing executes."""
    kwargs = kwargs or {}
    if kwargs:
        return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return jax.make_jaxpr(fn)(*args)


def _sub_jaxprs(value) -> Iterator:
    """Nested jaxprs inside one eqn param value (pjit/scan carry a
    ClosedJaxpr, cond a tuple of branches, shard_map an open Jaxpr)."""
    if hasattr(value, "jaxpr") and hasattr(value, "consts"):
        yield value                       # ClosedJaxpr
    elif hasattr(value, "eqns") and hasattr(value, "invars"):
        yield value                       # open Jaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _open(j):
    """The open Jaxpr under either representation."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def iter_eqns(closed) -> Iterator:
    """Every eqn of the program, recursing through nested jaxprs
    (pjit bodies, scan/while/cond carriers, shard_map regions)."""
    stack = [_open(closed)]
    while stack:
        jaxpr = stack.pop()
        for eqn in jaxpr.eqns:
            yield eqn
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    stack.append(_open(sub))


def collective_eqns(closed) -> list:
    """``(family_name, axis_names)`` per collective eqn. Axis names
    come from the ``axes``/``axis_name`` params; integer (positional)
    axes are dropped — only named axes bind to a mesh."""
    out = []
    for eqn in iter_eqns(closed):
        family = COLLECTIVE_FAMILY.get(eqn.primitive.name)
        if family is None:
            continue
        axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
        if isinstance(axes, str):
            axes = (axes,)
        names = tuple(a for a in (axes or ()) if isinstance(a, str))
        out.append((family, names))
    return out


def callback_eqns(closed) -> list:
    """Callback primitive names present in the program (with their
    callback target where the param exposes one)."""
    out = []
    for eqn in iter_eqns(closed):
        if eqn.primitive.name in CALLBACK_PRIMS:
            target = eqn.params.get("callback",
                                    eqn.params.get("callback_func"))
            out.append((eqn.primitive.name,
                        getattr(target, "__name__", None)
                        or type(target).__name__ if target is not None
                        else ""))
    return out


# ---------------------------------------------------------------------------
# Structural signature
# ---------------------------------------------------------------------------

#: Eqn params whose VALUES are structural (axis selections, dtype
#: targets, comparison directions) rather than size-dependent. Every
#: other param contributes its key only — a param like ``iota``'s
#: ``shape`` or ``dynamic_slice`` sizes would otherwise leak concrete
#: bucket dimensions into the hash.
_STRUCTURAL_PARAMS = frozenset({
    "axis", "axis_name", "axis_index_groups", "new_dtype", "weak_type",
    "direction", "is_stable", "num_keys", "dimension", "comparator",
    "preferred_element_type", "reverse", "unroll", "accuracy",
})


def _aval_sig(aval) -> str:
    if aval is None:
        return "?"
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    weak = "~" if getattr(aval, "weak_type", False) else ""
    rank = len(shape) if shape is not None else -1
    return f"{dtype}{weak}r{rank}"


def _const_sig(c, with_values: bool) -> str:
    shape = tuple(getattr(c, "shape", ()))
    dtype = getattr(c, "dtype", type(c).__name__)
    sig = f"{dtype}r{len(shape)}"
    if with_values and int(np.prod(shape or (1,))) <= 64:
        try:
            sig += ":" + hashlib.sha1(
                np.asarray(c).tobytes()).hexdigest()[:12]
        except Exception:
            pass
    return sig


def _sig_lines(jaxpr, lines: list, with_const_values: bool) -> None:
    lines.append("in=" + ",".join(_aval_sig(v.aval)
                                  for v in jaxpr.invars))
    for eqn in jaxpr.eqns:
        parts = [eqn.primitive.name]
        ins = []
        for v in eqn.invars:
            if hasattr(v, "val"):         # Literal: dtype only — values
                ins.append("lit:" + _aval_sig(v.aval))  # may encode sizes
            else:
                ins.append(_aval_sig(getattr(v, "aval", None)))
        parts.append("(" + ",".join(ins) + ")")
        parts.append("->" + ",".join(_aval_sig(v.aval)
                                     for v in eqn.outvars))
        for k in sorted(eqn.params):
            v = eqn.params[k]
            subs = list(_sub_jaxprs(v))
            if subs:
                parts.append(f"{k}=[")
                for sub in subs:
                    op = _open(sub)
                    _sig_lines(op, lines, with_const_values)
                    consts = getattr(sub, "consts", ())
                    for c in consts:
                        lines.append("const=" + _const_sig(
                            c, with_const_values))
                parts.append("]")
            elif k in _STRUCTURAL_PARAMS:
                parts.append(f"{k}={v!r}")
            else:
                parts.append(k)
        lines.append(" ".join(parts))
    lines.append("out=" + ",".join(
        _aval_sig(getattr(v, "aval", None)) for v in jaxpr.outvars))


def structural_signature(closed, with_const_values: bool = False) -> str:
    """Canonical structural hash: stable across shape buckets (concrete
    sizes are erased — dtypes, ranks, primitive order, structural params
    and the captured-constant skeleton remain). Two traces of one
    healthy plan at different buckets hash identically; a program that
    branches on shape, weak-type, or a baked literal does not."""
    lines: list = []
    _sig_lines(_open(closed), lines, with_const_values)
    for c in getattr(closed, "consts", ()):
        lines.append("const=" + _const_sig(c, with_const_values))
    return hashlib.sha1("\n".join(lines).encode()).hexdigest()


# ---------------------------------------------------------------------------
# Static peak-memory bound (liveness walk)
# ---------------------------------------------------------------------------

def _nbytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    except Exception:
        return 0


def peak_bytes(closed) -> int:
    """Upper-bound peak device bytes of one program: a liveness walk
    over the (recursively flattened) eqn list. Entry cost is the args +
    captured consts; each eqn allocates its outputs on top of the live
    set, operands free at their last use; nested jaxprs contribute
    their own peak *minus* their entry (their inputs alias buffers the
    outer walk already counts). No aliasing/donation credit — the bound
    only ever over-counts."""
    jaxpr = _open(closed)
    entry = sum(_nbytes(v.aval) for v in jaxpr.invars)
    constvars = getattr(jaxpr, "constvars", ())
    entry += sum(_nbytes(v.aval) for v in constvars)
    if not constvars:
        # a ClosedJaxpr binds its consts to the constvars above — count
        # the concrete arrays only when no constvars carry their avals
        # (counting both would double every captured constant)
        entry += sum(_nbytes(c) for c in getattr(closed, "consts", ()))
    eqns = jaxpr.eqns
    last_use: dict = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not hasattr(v, "val"):
                last_use[v] = i
    # outvars may contain Literals (a program returning a constant) —
    # they carry no buffer and are unhashable; only real Vars matter
    outvars = {v for v in jaxpr.outvars if not hasattr(v, "val")}
    for v in outvars:
        last_use[v] = len(eqns)
    live = entry
    peak = entry
    freed: set = set()
    for i, eqn in enumerate(eqns):
        inner_extra = 0
        for pv in eqn.params.values():
            for sub in _sub_jaxprs(pv):
                sj = _open(sub)
                sub_entry = sum(_nbytes(v.aval) for v in sj.invars)
                sub_entry += sum(_nbytes(v.aval)
                                 for v in getattr(sj, "constvars", ()))
                inner_extra = max(inner_extra,
                                  peak_bytes(sub) - sub_entry)
        out_bytes = sum(_nbytes(v.aval) for v in eqn.outvars)
        live += out_bytes
        peak = max(peak, live + max(inner_extra, 0))
        for v in eqn.invars:
            if hasattr(v, "val") or v in freed or v in outvars:
                continue
            if last_use.get(v) == i:
                live -= _nbytes(v.aval)
                freed.add(v)
    return int(peak)
