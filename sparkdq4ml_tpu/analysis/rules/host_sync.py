"""Rule ``host-sync`` — device→host transfers only inside counted
wrappers.

The engine's standing constraint (ROADMAP): every device path pins its
``frame.host_sync`` count, so an uncounted transfer is invisible to
EXPLAIN ANALYZE, to the span layer's per-op sync deltas, and to the
pinning tests — it silently re-introduces the host round-trips the
engine was built to remove. Until now each sync site was pinned by a
hand-written test; this rule closes the class.

Flagged site kinds (in the device-touching layers ``frame/``, ``ops/``,
``models/``, ``sql/``, ``parallel/``, ``serve/``):

* ``jax.device_get(...)`` — the canonical batched pull;
* ``.item()`` / ``.tolist()`` on receivers not statically known to be
  host data (see below);
* ``float(...)`` / ``int(...)`` / ``bool(...)`` wrapping a ``jnp.*``
  computation — a scalar pull;
* ``np.asarray/np.array(...)`` of a ``jnp.*`` expression or of frame
  device state (``._data`` / ``._mask``) — a whole-array pull;
* ``jax.pure_callback`` / ``jax.experimental.io_callback`` /
  ``jax.debug.print``/``debug_callback`` call sites — sync-bearing: a
  host round-trip EVERY execution of the jitted body they are staged
  into (the jaxpr-level ``audit-sync`` detector in ``analysis/program``
  is the ground truth for these; this source rule catches them before
  the program is ever cached).

A site is sanctioned when its enclosing function is a **counted
wrapper** — it increments ``frame.host_sync`` itself, delegates to one
(``collect`` / ``to_pydict`` / ``_host_pair`` / ``_host_mask``) or pulls
inside ``host_reading``, the counted and timed wrapper of a blocking read
— or when it carries a reasoned ``# dqlint: ok(host-sync): ...`` pragma.

Host-data tracking (to keep numpy post-processing quiet): a receiver is
known-host when its expression is rooted at ``np.`` / ``numpy.``, at a
``jax.device_get`` result, or at a name assigned from such an expression
in the same function (flow-insensitive single-assignment tracking).
"""

from __future__ import annotations

import ast
from typing import Optional

from ..core import Finding, Rule, SourceFile, attr_chain, call_name

_SCOPE_DIRS = ("frame/", "ops/", "models/", "sql/", "parallel/", "serve/")
_PKG = "sparkdq4ml_tpu/"

#: Functions whose call makes the *caller* a counted wrapper: each counts
#: its one batched transfer internally. ``host_reading`` is THE wrapper of
#: a blocking read (``utils.observability``): the pull runs inside its
#: ``with``, is counted in ``host.reads`` / ``host.read_bytes`` and, while
#: the tracer records, timed as a ``host.read`` span.
_COUNTED_CALLS = frozenset({"collect", "to_pydict", "_host_pair",
                            "_host_mask", "host_fetch", "toPandas",
                            "to_pandas", "host_reading"})
_NP_ROOTS = ("np", "numpy")
_JNP_ROOTS = ("jnp",)

#: Callback-staging calls: sync-bearing at every execution of the jitted
#: body. ``debug_print`` covers ``jax.debug.print`` via the attr-chain
#: check below (bare ``print`` must not match).
_CALLBACK_CALLS = frozenset({"pure_callback", "io_callback",
                             "debug_callback"})
#: Dotted suffixes that make a ``print`` call the jax.debug one.
_DEBUG_PRINT_CHAINS = ("jax.debug.print", "debug.print")


def _in_scope(rel: str) -> bool:
    return rel.startswith(_PKG) and any(
        rel[len(_PKG):].startswith(d) for d in _SCOPE_DIRS)


def _root_name(node: ast.AST) -> Optional[str]:
    """Leftmost name of an attribute/call/subscript chain."""
    while True:
        if isinstance(node, ast.Attribute):
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, ast.Subscript):
            node = node.value
        elif isinstance(node, ast.Name):
            return node.id
        else:
            return None


def _contains_jnp_call(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Call) and _root_name(n.func) in _JNP_ROOTS:
            return True
    return False


def _contains_device_state(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in ("_data", "_mask"):
            return True
    return False


def _is_increment(node: ast.Call) -> bool:
    return (call_name(node) == "increment" and node.args
            and isinstance(node.args[0], ast.Constant)
            and node.args[0].value == "frame.host_sync")


class _FnInfo:
    """Per-function facts: counted-wrapper status and host-rooted names."""

    def __init__(self, fn: ast.AST, nodes: list,
                 module_aliases: frozenset = frozenset()):
        self.counted = False
        self.host_names: set[str] = set()
        self._module_aliases = module_aliases
        for n in nodes:
            if isinstance(n, ast.Call):
                if _is_increment(n) or self._counted_call(n):
                    self.counted = True
        # parameters annotated as host numpy are host data by signature
        args_obj = getattr(fn, "args", None)
        if args_obj is not None:
            for a in (args_obj.posonlyargs + args_obj.args
                      + args_obj.kwonlyargs):
                ann = a.annotation
                if ann is not None and _root_name(ann) in _NP_ROOTS:
                    self.host_names.add(a.arg)
        # flow-insensitive: iterate assignments until the host-rooted name
        # set stops growing (handles a = np.x(...); b = a[0])
        grew = True
        while grew:
            grew = False
            for n in nodes:
                if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                        and isinstance(n.targets[0], ast.Name):
                    name = n.targets[0].id
                    if name not in self.host_names \
                            and self.is_host(n.value):
                        self.host_names.add(name)
                        grew = True

    def _counted_call(self, n: ast.Call) -> bool:
        """A delegation to a counted wrapper — with the receiver
        qualified so e.g. ``gc.collect()`` (a call on an imported
        MODULE, not a Frame) can never sanction unrelated syncs."""
        if call_name(n) not in _COUNTED_CALLS:
            return False
        f = n.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                and f.value.id in self._module_aliases:
            return False
        return True

    def is_host(self, node: ast.AST) -> bool:
        """Expression statically known to produce HOST data."""
        if isinstance(node, ast.Call):
            nm = call_name(node)
            if nm == "device_get" or self._counted_call(node):
                return True
            root = _root_name(node.func)
            if root in _NP_ROOTS:
                return True
            # method chain on a host expression (arr.ravel(), a.astype())
            if isinstance(node.func, ast.Attribute):
                return self.is_host(node.func.value)
            return False
        if isinstance(node, ast.Attribute):
            return self.is_host(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_host(node.value)
        if isinstance(node, ast.BinOp):
            return self.is_host(node.left) or self.is_host(node.right)
        if isinstance(node, ast.Name):
            return node.id in self.host_names or node.id in _NP_ROOTS
        return False


class HostSyncRule(Rule):
    name = "host-sync"
    description = ("device->host transfers (device_get/.item()/.tolist()/"
                   "float(jnp...)/np.asarray(jnp...)) only inside counted"
                   " wrappers that increment frame.host_sync")

    def visit(self, src: SourceFile):
        if not _in_scope(src.rel):
            return ()
        out: list[Finding] = []
        # names bound by plain `import X [as Y]` — the receiver
        # qualification for counted-wrapper calls
        module_aliases = frozenset(
            (a.asname or a.name.split(".")[0])
            for n in ast.walk(src.tree) if isinstance(n, ast.Import)
            for a in n.names)

        def emit(node, what):
            f = src.finding(
                self.name, node,
                f"{what} is a device->host transfer outside a counted"
                " wrapper — increment('frame.host_sync') in this function"
                " (or route through collect()/to_pydict()/_host_pair),"
                " or carry a reasoned '# dqlint: ok(host-sync): ...'"
                " pragma if the data is host-resident by construction")
            if f:
                out.append(f)

        def scan_function(fn: ast.AST, stack_counted: bool):
            # counted status considers the whole subtree (an increment in
            # a nested helper sanctions the factory around it — lenient
            # by design: the wrapper boundary is the outermost function);
            # host-name tracking and the site scan stay per-body
            nested = []

            def body_nodes(node):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                        nested.append(child)
                        continue
                    yield child
                    yield from body_nodes(child)

            body = list(body_nodes(fn))
            is_func = isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
            subtree = list(ast.walk(fn)) if is_func else body
            info = _FnInfo(fn, subtree, module_aliases)
            if is_func and (stack_counted or info.counted):
                return   # counted wrapper: entire subtree sanctioned
            # module level has no wrapper by definition — every site is a
            # finding; its nested functions are still scanned below
            emit_here = is_func or not info.counted
            info = _FnInfo(fn, body, module_aliases)
            for node in body if emit_here else ():
                if not isinstance(node, ast.Call):
                    continue
                nm = call_name(node)
                if nm == "device_get":
                    emit(node, "jax.device_get(...)")
                elif nm in ("item", "tolist") and not node.args:
                    recv = node.func.value \
                        if isinstance(node.func, ast.Attribute) else None
                    if recv is not None and not info.is_host(recv):
                        emit(node, f".{nm}()")
                elif nm in ("float", "int", "bool") \
                        and isinstance(node.func, ast.Name) \
                        and len(node.args) == 1 \
                        and _contains_jnp_call(node.args[0]):
                    emit(node, f"{nm}(<jnp expression>)")
                elif nm in ("asarray", "array") \
                        and _root_name(node.func) in _NP_ROOTS \
                        and node.args \
                        and (_contains_jnp_call(node.args[0])
                             or _contains_device_state(node.args[0])):
                    emit(node, f"np.{nm}(<device expression>)")
                elif nm in _CALLBACK_CALLS:
                    emit(node, f"{nm}(...) (host callback staged into a"
                               " jitted body)")
                elif nm == "print":
                    chain = attr_chain(node.func) \
                        if isinstance(node.func, ast.Attribute) else None
                    if chain and (chain in _DEBUG_PRINT_CHAINS
                                  or chain.endswith(".debug.print")):
                        emit(node, "jax.debug.print(...) (host callback"
                                   " staged into a jitted body)")
            for sub in nested:
                scan_function(sub, False)

        # one pass from the module node: scans module-level statements
        # (import-time transfers are uncounted by definition) and recurses
        # into every function/method it collects along the way
        scan_function(src.tree, False)
        return out
