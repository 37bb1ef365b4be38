"""dqaudit detectors — the four jaxpr-level program invariants.

Each detector inspects ONE cached program (an
``observability.ProgramHandle``) through its abstract trace and emits
:class:`~..core.Finding` records. Findings address programs, not source
lines: ``path`` is ``program:<cache>`` and the baseline fingerprint is
the stable ``program_key``, so the PR-8 baseline/suppression workflow
(``dqlint_baseline.json``, stale-entry reporting) applies unchanged.

The source-level dqlint rules (``analysis/rules``) police what the code
SAYS; these detectors police what the traced program actually IS — the
jaxpr is ground truth for hidden transfers, collective topology, baked
literals, and memory shape that no AST walk can see.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ..core import Finding
from . import jaxpr_tools as JT

__all__ = ["AuditContext", "Detector", "ALL_DETECTORS",
           "audit_budget_bytes", "get_detectors", "program_finding"]


def program_finding(rule: str, handle, message: str) -> Finding:
    """A finding addressed to a cached program: path names the producer
    cache, fingerprint is the stable program key (baseline identity)."""
    return Finding(rule=rule, path=f"program:{handle.cache}", line=0,
                   message=message, fingerprint=handle.program_key)


def _key_prefix(handle, n: int = 72) -> str:
    k = handle.program_key
    return k if len(k) <= n else k[:n] + "…"


def audit_budget_bytes(explicit: int = 0) -> Optional[int]:
    """THE device byte budget the static-memory gate checks against —
    one definition shared by the audit-memory detector and EXPLAIN's
    ``!! est peak`` warning (they must never disagree about the same
    plan): ``spark.audit.deviceBudget`` when set, else the smallest
    allocator ``bytes_limit`` the backend exposes (None on XLA:CPU,
    which reports no allocator stats — the bound is still surfaced,
    just not gated)."""
    if explicit > 0:
        return int(explicit)
    from ...utils import meminfo

    limits = [s["bytes_limit"] for s in meminfo.device_stats()
              if "bytes_limit" in s]
    return min(limits) if limits else None


@dataclasses.dataclass
class AuditContext:
    """Shared per-audit state: conf thresholds, the device budget, and a
    trace cache so four detectors cost one ``make_jaxpr`` per program."""

    memory_fraction: float = 0.9
    device_budget: int = 0           # explicit bytes; 0 = allocator limit
    const_bytes: int = 4096
    _traces: dict = dataclasses.field(default_factory=dict)
    #: program_key → facts the detectors computed (est peak bytes, trace
    #: status, signatures) — the audit_report() payload.
    program_stats: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def from_config(cls) -> "AuditContext":
        from ...config import config

        return cls(
            memory_fraction=float(config.audit_memory_fraction),
            device_budget=int(config.audit_device_budget),
            const_bytes=int(config.audit_const_bytes))

    def trace(self, handle):
        """Abstract-trace ``handle`` once; later detectors reuse it."""
        key = id(handle)
        if key not in self._traces:
            self._traces[key] = JT.trace(handle.fn, handle.args,
                                         handle.kwargs)
        return self._traces[key]

    def stats_for(self, handle) -> dict:
        return self.program_stats.setdefault(
            handle.program_key, {"cache": handle.cache})

    def budget_bytes(self) -> Optional[int]:
        """See :func:`audit_budget_bytes` (the shared definition)."""
        return audit_budget_bytes(self.device_budget)


class Detector:
    name = "detector"
    description = ""

    def check(self, handle, ctx: AuditContext) -> list:
        return []

    def finalize(self, handles, ctx: AuditContext) -> list:
        """Cross-program pass over every successfully-traced handle
        (for invariants one program alone cannot witness)."""
        return []


class StaticMemoryDetector(Detector):
    """Liveness walk over eqn outvars → peak-bytes upper bound, checked
    against the device budget × ``spark.audit.memoryFraction``. The
    bound is recorded in ``ctx.program_stats`` either way — it is the
    ``est peak`` figure EXPLAIN surfaces and the constraint the future
    cost-based optimizer consumes."""

    name = "audit-memory"
    description = ("static per-program peak-bytes bound (liveness walk"
                   " over the jaxpr) must fit spark.audit.memoryFraction"
                   " of the device byte budget")

    def check(self, handle, ctx: AuditContext):
        closed = ctx.trace(handle)
        peak = JT.peak_bytes(closed)
        ctx.stats_for(handle)["est_peak_bytes"] = peak
        budget = ctx.budget_bytes()
        if budget is None:
            return []
        limit = int(ctx.memory_fraction * budget)
        if peak <= limit:
            return []
        return [program_finding(
            self.name, handle,
            f"static peak estimate {peak} bytes exceeds "
            f"{ctx.memory_fraction:g} of the device budget ({budget}"
            f" bytes) — chunk the plan or raise spark.audit."
            f"memoryFraction [{_key_prefix(handle)}]")]


class HiddenSyncDetector(Detector):
    """Callback primitives and large captured constants inside jitted
    bodies. A ``pure_callback``/``io_callback``/``debug_callback`` eqn
    is a host round-trip every execution — invisible to the source-level
    host-sync rule when smuggled through a helper. A large captured
    constant is host data baked into the program: it re-ships with every
    compile and usually means frame data leaked into a plan closure."""

    name = "audit-sync"
    description = ("no callback primitives (pure_callback/io_callback/"
                   "debug prints) and no large host constants captured"
                   " inside cached jitted programs")

    def check(self, handle, ctx: AuditContext):
        closed = ctx.trace(handle)
        out = []
        callbacks = JT.callback_eqns(closed)
        for prim, target in callbacks:
            what = f"{prim}" + (f" -> {target}" if target else "")
            out.append(program_finding(
                self.name, handle,
                f"hidden host callback inside jitted body: {what} — a"
                " device->host round-trip on every execution; hoist it"
                f" out of the program [{_key_prefix(handle)}]"))
        for c in getattr(closed, "consts", ()):
            nb = JT._nbytes(c)
            if nb > ctx.const_bytes:
                shape = tuple(getattr(c, "shape", ()))
                out.append(program_finding(
                    self.name, handle,
                    f"host constant capture: {nb}-byte const "
                    f"{shape} baked into the jaxpr (> spark.audit."
                    f"constBytes={ctx.const_bytes}) — a cache-key-miss"
                    " symptom: pass it as a program input"
                    f" [{_key_prefix(handle)}]"))
        ctx.stats_for(handle)["callbacks"] = len(callbacks)
        return out


class CollectiveTopologyDetector(Detector):
    """Every collective eqn's axis names must resolve against the
    handle's mesh, and any collective-bearing program on a multi-device
    mesh must be declared ``collective_guard``-wrapped — closing the
    PR-6 gap where a guarded factory jits an *unguarded* inner
    collective (overlapping psum dispatch deadlocks XLA:CPU)."""

    name = "audit-collective"
    description = ("collective eqn axis names resolve against the"
                   " installed mesh; multi-device collective programs"
                   " declare collective_guard wrapping")

    def check(self, handle, ctx: AuditContext):
        closed = ctx.trace(handle)
        colls = JT.collective_eqns(closed)
        ctx.stats_for(handle)["collectives"] = len(colls)
        if not colls:
            return []
        out = []
        mesh = handle.mesh
        axis_names = set(getattr(mesh, "axis_names", ()) or ())
        multi = mesh is not None and getattr(
            getattr(mesh, "devices", None), "size", 1) > 1
        for prim, names in colls:
            missing = [n for n in names if n not in axis_names]
            if missing or not names:
                where = (f"axis {missing} not on the mesh"
                         if names else "no named axis")
                have = sorted(axis_names) if axis_names else "none"
                out.append(program_finding(
                    self.name, handle,
                    f"collective {prim} cannot bind: {where}"
                    f" (mesh axes: {have}) — the program would fail or"
                    " silently reduce over the wrong topology"
                    f" [{_key_prefix(handle)}]"))
        if multi and handle.guarded is not True:
            out.append(program_finding(
                self.name, handle,
                f"{len(colls)} collective eqn(s) on a multi-device mesh"
                " but the producer does not declare collective_guard"
                " wrapping — overlapping dispatch deadlocks XLA:CPU"
                " (route the entry through mesh.serialize_collectives)"
                f" [{_key_prefix(handle)}]"))
        return out


class RetraceHazardDetector(Detector):
    """Steady-state recompile hazards, three ways:

    * the producer's trace accounting shows MORE compiles than distinct
      shape signatures served (a weak-type/dtype flip is retracing a
      plan the cache thinks it replays);
    * re-tracing at a producer-declared variant (second shape bucket,
      weak-type literal twin, wider Gramian) changes the structural
      jaxpr hash — the program specializes on shape/weak-type and will
      recompile per size in serving;
    * two cached entries in one cache whose producer-declared
      literal-erased keys (``meta["dedup_key"]``) collide — the same
      program cached once per literal VALUE, the classic
      literal-hoisting regression in ``ops/compiler.py`` (``price < 3``
      and ``price < 4`` must share one compiled program). Known
      limitation: CaseWhen branch literals are deliberately un-hoisted
      (constant-folding wins there), so intentional literal-variant
      CASE plans need a baseline entry.
    """

    name = "audit-retrace"
    description = ("structural jaxpr hash stable across shape-bucket/"
                   "weak-type re-traces; no excess observed traces; no"
                   " scalar consts in literal-hoisting plans")

    def check(self, handle, ctx: AuditContext):
        out = []
        closed = ctx.trace(handle)
        base_sig = JT.structural_signature(closed)
        ctx.stats_for(handle)["signature"] = base_sig[:16]
        exp = handle.meta.get("expected_traces")
        obs = handle.meta.get("observed_traces")
        if exp is not None and obs is not None and obs > exp:
            out.append(program_finding(
                self.name, handle,
                f"{obs} observed trace(s) for {exp} distinct shape"
                " signature(s) served — something beyond shape (weak"
                " types, dtype flips) is re-tracing this plan in steady"
                f" state [{_key_prefix(handle)}]"))
        for vname, spec in sorted(handle.variants.items()):
            # one (args, kwargs) pair → compare against the base trace;
            # a LIST of pairs → compare the fresh variant traces among
            # themselves. The list form is what real producers declare
            # (bucket x2 vs x4): jax serves the base avals from its
            # internal trace cache, which may predate a config flip
            # (the float dtype, say) — two FRESH traces under the
            # current config are the apples-to-apples comparison.
            pairs = spec if isinstance(spec, list) else [spec]
            ref_sig, ref_name = base_sig, "base"
            for i, (vargs, vkwargs) in enumerate(pairs):
                try:
                    vjaxpr = JT.trace(handle.fn, vargs, vkwargs)
                except Exception as e:
                    out.append(program_finding(
                        self.name, handle,
                        f"re-trace at variant {vname!r} raised"
                        f" {type(e).__name__}: {e} — the plan cannot"
                        " serve its next shape bucket"
                        f" [{_key_prefix(handle)}]"))
                    break
                vsig = JT.structural_signature(vjaxpr)
                if len(pairs) > 1 and i == 0:
                    ref_sig, ref_name = vsig, f"{vname}[0]"
                    continue
                if vsig != ref_sig:
                    out.append(program_finding(
                        self.name, handle,
                        f"structural jaxpr hash changed between"
                        f" {ref_name} and variant {vname!r}"
                        f" ({ref_sig[:12]} -> {vsig[:12]}) — the"
                        " program specializes on shape/weak-type and"
                        " will retrace per bucket in serving"
                        f" [{_key_prefix(handle)}]"))
        return out

    def finalize(self, handles, ctx: AuditContext):
        """Literal-hoisting regression: group by the producer's
        literal-erased key — more than one cached program in a group
        means the cache compiles once per literal value."""
        groups: dict = {}
        for h in handles:
            dk = h.meta.get("dedup_key")
            if dk:
                groups.setdefault((h.cache, dk), []).append(h)
        out = []
        for (_cache, dk), members in sorted(groups.items()):
            if len(members) < 2:
                continue
            for h in members:
                out.append(program_finding(
                    self.name, h,
                    f"{len(members)} cached programs share one"
                    " literal-erased plan shape — the literal is in the"
                    " cache key instead of a hoisted runtime argument,"
                    " so every new literal value recompiles"
                    f" [{_key_prefix(h)}]"))
        return out


ALL_DETECTORS = (
    StaticMemoryDetector,
    HiddenSyncDetector,
    CollectiveTopologyDetector,
    RetraceHazardDetector,
)


def get_detectors(names=None):
    """Instantiate the requested detectors (all four by default)."""
    classes = ALL_DETECTORS
    if names:
        wanted = set(names)
        classes = [c for c in ALL_DETECTORS if c.name in wanted]
        unknown = wanted - {c.name for c in classes}
        if unknown:
            known = ", ".join(c.name for c in ALL_DETECTORS)
            raise ValueError(
                f"unknown detector(s) {sorted(unknown)}; known: {known}")
    return [c() for c in classes]
