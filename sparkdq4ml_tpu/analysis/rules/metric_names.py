"""Rule ``metric-name`` — every metric literal resolves to the registry.

Counters, gauges, and histograms are matched BY NAME at runtime: a
typo'd ``counters.increment("pipleine.hit")`` compiles, runs, and
silently creates a ghost series no dashboard, benchmark or test ever
reads — while the real series quietly stops moving. The registry is
``utils/observability.py::METRIC_NAMES`` (name → (type, help)) plus
``METRIC_NAME_PREFIXES`` for the dynamic per-site/per-tenant families
(``recovery.<action>``, ``serve.e2e_ms.<tenant>``, …) — both pure
literals, parsed statically like the conf-key registry parses
``config.CONF_KEYS``.

Checks, receiver-qualified (an unrelated object's ``observe`` method
cannot trip the rule):

1. **Literal name**: every ``counters.increment(name)`` (receiver chain
   ending in ``counters``) and every ``METRICS.set_gauge/observe/
   histogram(name)`` (receiver chain ending in ``METRICS``) must pass a
   string literal, an f-string whose literal head starts with a declared
   prefix family, or a conditional whose arms are both literal — a fully
   computed name cannot be statically checked.
2. **Registered name**: a plain literal must be a ``METRIC_NAMES`` key
   or start with a ``METRIC_NAME_PREFIXES`` family prefix.
"""

from __future__ import annotations

import ast
from typing import Optional

from ..core import Finding, Rule, SourceFile, attr_chain

_OBS_REL = "sparkdq4ml_tpu/utils/observability.py"

#: hook method name → receiver-chain tail that qualifies it
_HOOKS = {
    "increment": ("counters",),
    "set_gauge": ("METRICS",),
    "observe": ("METRICS",),
    "histogram": ("METRICS",),
}


def _literal_head(node: ast.JoinedStr) -> Optional[str]:
    if node.values and isinstance(node.values[0], ast.Constant) \
            and isinstance(node.values[0].value, str):
        return node.values[0].value
    return None


class MetricNameRule(Rule):
    name = "metric-name"
    description = ("counters.increment / METRICS.set_gauge/observe/"
                   "histogram literal names must be registered in"
                   " observability.METRIC_NAMES (or a declared prefix"
                   " family) — a typo'd name creates a ghost series")

    def __init__(self):
        # (src, call_node, hook, name_node)
        self._usages: list = []
        self._obs_src: Optional[SourceFile] = None

    # -- per-file collection ------------------------------------------------
    def visit(self, src: SourceFile):
        if src.rel == _OBS_REL:
            self._obs_src = src
            # the registry file still CONTAINS call sites (span_ms
            # histograms, trace.dropped_spans) — fall through and check
            # them like any other module
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not (isinstance(f, ast.Attribute) and f.attr in _HOOKS):
                continue
            chain = attr_chain(f.value)
            if chain is None:
                continue
            tail = chain.split(".")[-1]
            if tail not in _HOOKS[f.attr]:
                continue
            kwargs = {k.arg: k.value for k in node.keywords if k.arg}
            name = node.args[0] if node.args else kwargs.get("name")
            if name is None:
                continue
            self._usages.append((src, node, f.attr, name))
        return ()

    # -- registry parse -----------------------------------------------------
    @staticmethod
    def _parse_registry(src: SourceFile):
        names: dict = {}
        prefixes: dict = {}
        for node in src.tree.body:
            if not isinstance(node, ast.Assign) or len(node.targets) != 1 \
                    or not isinstance(node.targets[0], ast.Name):
                continue
            target = node.targets[0].id
            if target not in ("METRIC_NAMES", "METRIC_NAME_PREFIXES"):
                continue
            try:
                value = ast.literal_eval(node.value)
            except (ValueError, SyntaxError):
                continue
            if target == "METRIC_NAMES" and isinstance(value, dict):
                names = value
            elif target == "METRIC_NAME_PREFIXES" \
                    and isinstance(value, dict):
                prefixes = value
        return names, prefixes

    # -- cross-file check ---------------------------------------------------
    def finalize(self, files):
        out: list[Finding] = []
        if self._obs_src is None:
            return out   # partial trees in tests: nothing to check against
        names, prefixes = self._parse_registry(self._obs_src)
        if not names:
            out.append(Finding(
                rule=self.name, path=self._obs_src.rel, line=0,
                message="utils/observability.py declares no METRIC_NAMES"
                        " literal registry — every metric name must be"
                        " declared there"))
            return out

        def literal_values(node) -> Optional[list]:
            """Fully-literal name candidates of a name argument: a
            constant, or a conditional whose arms both resolve. None =
            not statically checkable."""
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, str):
                return [node.value]
            if isinstance(node, ast.IfExp):
                a = literal_values(node.body)
                b = literal_values(node.orelse)
                if a is not None and b is not None:
                    return a + b
            return None

        for src, call, hook, name_node in self._usages:
            if isinstance(name_node, ast.JoinedStr):
                head = _literal_head(name_node)
                if head and any(head.startswith(p) or p.startswith(head)
                                for p in prefixes):
                    continue
                f = src.finding(
                    self.name, call,
                    f"dynamic metric name in {hook}(...) must start with"
                    " a family prefix declared in"
                    " observability.METRIC_NAME_PREFIXES — an undeclared"
                    " family is unscrapable cardinality with no help"
                    " text")
                if f:
                    out.append(f)
                continue
            values = literal_values(name_node)
            if values is None:
                f = src.finding(
                    self.name, call,
                    f"metric name in {hook}(...) must be a string"
                    " LITERAL (or an f-string with a declared family"
                    " head) — a computed name cannot be statically"
                    " checked and a typo creates a ghost series")
                if f:
                    out.append(f)
                continue
            for value in values:
                if value in names or any(value.startswith(p)
                                         for p in prefixes):
                    continue
                f = src.finding(
                    self.name, call,
                    f"metric name {value!r} is not registered in"
                    " observability.METRIC_NAMES (nor covered by a"
                    " METRIC_NAME_PREFIXES family) — register it with"
                    " its type/help or fix the typo")
                if f:
                    out.append(f)
        return out
