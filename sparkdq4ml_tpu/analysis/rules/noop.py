"""Rule ``noop`` — disabled-mode observability must stay allocation-free.

The PR-2 contract (dynamically asserted by test_observability, statically
pinned here): when tracing is off, a span site costs one flag read and
returns the shared ``_NOOP`` singleton — **no Span allocation, no string
formatting**. The subtle leak is at call sites: arguments to
``span(...)`` / ``TRACER.span(...)`` / ``current_span().set(...)``
evaluate *before* the enabled check inside the callee, so an f-string or
``.format`` in the argument list allocates on every disabled-mode call.

Flagged, in any engine file (``utils/observability.py`` itself is
exempt — it owns the gate):

* a span-sink call (``span`` / ``fit_span`` / ``begin`` / ``.set`` on a
  span) whose argument contains eager string formatting (f-string with a
  hole, ``%`` / ``+`` on a string literal, ``.format(...)``, or
  ``", ".join(...)``), unless the call is statically guarded by an
  enclosing ``if ... enabled ...`` / ``if ... recording ...`` branch (or a
  preceding ``if not ... enabled ...: return`` early-out);
* direct ``Span(...)`` construction outside the tracer.
"""

from __future__ import annotations

import ast

from ..core import Finding, Rule, SourceFile

_EXEMPT = ("sparkdq4ml_tpu/utils/observability.py",)

#: Call names that hand their arguments to the span layer.
_SINK_NAMES = frozenset({"span", "fit_span", "begin"})


#: The tracer's gate: the explicit flag, and the one predicate that also
#: follows a jax profiler session (``Tracer.recording``).
_GATE_NAMES = frozenset({"enabled", "recording"})


def _mentions_enabled(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in _GATE_NAMES:
            return True
        if isinstance(n, ast.Name) and n.id in _GATE_NAMES:
            return True
    return False


def _formats_string(node: ast.AST) -> bool:
    """Does evaluating this expression allocate a formatted string?"""
    for n in ast.walk(node):
        if isinstance(n, ast.JoinedStr) and any(
                isinstance(v, ast.FormattedValue) for v in n.values):
            return True
        if isinstance(n, ast.BinOp) and isinstance(n.op, (ast.Mod, ast.Add)):
            for side in (n.left, n.right):
                if isinstance(side, ast.Constant) \
                        and isinstance(side.value, str):
                    return True
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr in ("format", "join"):
            recv = n.func.value
            if n.func.attr == "format" or (
                    isinstance(recv, ast.Constant)
                    and isinstance(recv.value, str)):
                return True
    return False


class NoopContractRule(Rule):
    name = "noop"
    description = ("span-site arguments must not format strings (they "
                   "evaluate before the enabled gate) and Span objects "
                   "are only allocated by the tracer — the disabled-mode "
                   "near-zero no-op contract")

    def visit(self, src: SourceFile):
        if src.rel in _EXEMPT:
            return ()
        out: list[Finding] = []

        def is_sink(call: ast.Call, span_vars: set) -> str:
            f = call.func
            if isinstance(f, ast.Name) and f.id in _SINK_NAMES:
                return f.id
            if isinstance(f, ast.Attribute):
                if f.attr in _SINK_NAMES:
                    return f.attr
                if f.attr == "set":
                    recv = f.value
                    if isinstance(recv, ast.Call):
                        rf = recv.func
                        rname = rf.attr if isinstance(rf, ast.Attribute) \
                            else getattr(rf, "id", "")
                        if rname == "current_span":
                            return "current_span().set"
                    if isinstance(recv, ast.Name) and recv.id in span_vars:
                        return f"{recv.id}.set"
            return ""

        def scan(stmts, guarded, span_vars):
            """Walk a statement list tracking (a) enabled-guarded regions
            and (b) names bound to spans by ``with span(...) as s``."""
            for stmt in stmts:
                g = guarded
                if isinstance(stmt, ast.If):
                    test = stmt.test
                    body_guarded = g or _mentions_enabled(test)
                    scan(stmt.body, body_guarded, span_vars)
                    scan(stmt.orelse, g, span_vars)
                    # early-out: `if not ...enabled...: return` guards the
                    # rest of the suite
                    if (isinstance(test, ast.UnaryOp)
                            and isinstance(test.op, ast.Not)
                            and _mentions_enabled(test.operand)
                            and stmt.body
                            and isinstance(stmt.body[-1],
                                           (ast.Return, ast.Raise))
                            and not stmt.orelse):
                        guarded = True
                    continue
                if isinstance(stmt, (ast.With, ast.AsyncWith)):
                    vars_here = set(span_vars)
                    for item in stmt.items:
                        ce = item.context_expr
                        if isinstance(ce, ast.Call) \
                                and is_sink(ce, span_vars) \
                                and isinstance(item.optional_vars, ast.Name):
                            vars_here.add(item.optional_vars.id)
                        check_exprs(ce, g, span_vars)
                    scan(stmt.body, g, vars_here)
                    continue
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    scan(stmt.body, False, set())
                    continue
                if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                    header = stmt.iter if isinstance(
                        stmt, (ast.For, ast.AsyncFor)) else stmt.test
                    check_exprs(header, g, span_vars)
                    scan(stmt.body, g, span_vars)
                    scan(stmt.orelse, g, span_vars)
                    continue
                if isinstance(stmt, ast.Try):
                    scan(stmt.body, g, span_vars)
                    for h in stmt.handlers:
                        scan(h.body, g, span_vars)
                    scan(stmt.orelse, g, span_vars)
                    scan(stmt.finalbody, g, span_vars)
                    continue
                if isinstance(stmt, ast.ClassDef):
                    scan(stmt.body, False, set())
                    continue
                check_exprs(stmt, g, span_vars)

        def check_exprs(node, guarded, span_vars):
            for n in ast.walk(node):
                if not isinstance(n, ast.Call):
                    continue
                sink = is_sink(n, span_vars)
                if sink and not guarded:
                    for arg in list(n.args) + [k.value for k in n.keywords]:
                        if _formats_string(arg):
                            f = src.finding(
                                self.name, n,
                                f"argument of {sink}(...) formats a string"
                                " eagerly — it evaluates even when tracing"
                                " is disabled, breaking the near-zero"
                                " no-op contract; guard the call with"
                                " `if ...enabled` or pass raw values")
                            if f:
                                out.append(f)
                            break
                fn = n.func
                if isinstance(fn, ast.Name) and fn.id == "Span":
                    f = src.finding(
                        self.name, n,
                        "direct Span(...) allocation outside the tracer —"
                        " spans must come from TRACER.span()/begin() so"
                        " the disabled path allocates nothing")
                    if f:
                        out.append(f)

        scan(src.tree.body, False, set())
        return out
