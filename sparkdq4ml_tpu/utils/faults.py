"""Deterministic fault injection — the chaos layer of the resilience stack.

The reference app inherits Spark's failure machinery (task retry, lineage
recomputation, checkpointing) but none of it is *testable* there: you
cannot ask `local[*]` to lose an executor on the third task. Here failures
are first-class: a :class:`FaultPlan` schedules failures at named **sites**
in the execution path, keyed by a per-site attempt counter and a seed, so
every injected failure is reproducible run-to-run — the property the
``tests/test_faults.py`` suite is built on.

Failure classes (``kind``):

* ``device_error`` — raises :class:`InjectedDeviceError`, a
  ``jax.errors.JaxRuntimeError`` subclass, i.e. exactly the exception type
  a real XLA device fault (OOM, interconnect reset, preempted device)
  surfaces as. The production catch paths cannot tell the difference,
  which is the point.
* ``nan`` — poisons one leaf of a result pytree with NaN (a diverged
  solver / flaky transfer), at a seeded element position.
* ``preempt`` — raises :class:`Preemption` (NOT a device error): the
  mid-fit preemption that ``recovery.fit_or_resume`` turns into a
  checkpoint-resume instead of a crash.
* ``device_drop`` — shrinks a mesh by ``n`` devices (default 1), the
  lost-worker scenario; ``parallel.mesh.normalize_mesh`` semantics apply
  to whatever survives.

Sites instrumented in production code are registered in
:data:`FAULT_SITES` (the dqlint ``fault-site`` rule's vocabulary — a
hook call naming an unregistered site would silently never fire). The
model-fit sites (``gram_sharded``/``fit_packed``/``solver``/``fit``/
``mesh``) came with PR 1; the post-PR-1 subsystems each carry their own:
``pipeline_flush`` (the fused expression-pipeline dispatch,
``ops/compiler.py`` + the ``Frame._flush`` ladder), ``grouped_flush``
(the segment-reduce grouped program, ``ops/segments.try_device``),
``ingest_native`` (the native streaming CSV reader,
``frame/native_csv.py``: I/O error, torn chunk, prefetch-thread death,
bind-pool exhaustion), ``serve_exec``/``serve_admit`` (the QueryServer
worker and admission gates, ``serve/``), ``coalesce`` (the cross-request
batched dispatch, ``serve/coalesce.py``: device error, wedged batch
stall, stacked-bytes OOM — every rung degrades the whole batch to
per-request replay of the same cached plan), and ``oom`` (memory pressure as
a schedulable fault: a shrunken device budget makes the pre-execution
static bound trip and the flush degrade to row-chunked execution).
Injection happens at host-level dispatch boundaries only — never inside
a traced/jitted function, where a Python-level raise would fire at trace
time, not run time.

Activation: programmatic (:func:`install_plan`, or the
:func:`inject_faults` context manager tests use) or env-driven — set
``SPARKDQ4ML_FAULTS`` (or session conf ``spark.faults``) to a
semicolon-separated spec list, e.g.::

    SPARKDQ4ML_FAULTS="gram_sharded:device_error:1,2;solver:nan:1"
    SPARKDQ4ML_FAULTS="fit:preempt:p=0.25:seed=7;mesh:device_drop:n=2"

Spec grammar: ``site:kind[:a1,a2,...][:p=prob][:n=count][:seed=s]`` —
an explicit 1-based attempt list fires deterministically on those
attempts; ``p=`` fires as a seeded Bernoulli draw per attempt (still
reproducible: the draw is a pure function of (seed, site, attempt));
with neither, the fault fires on attempt 1 only.

When no plan is installed every hook is a no-op behind one ``is None``
check — the chaos layer costs nothing in production.

See README.md "Failure model & fault injection" for the recovery side:
retry policy knobs, circuit breaker, and the fallback ladder.
"""

from __future__ import annotations

import logging
import os
import threading
import zlib
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

logger = logging.getLogger("sparkdq4ml_tpu.faults")

ENV_VAR = "SPARKDQ4ML_FAULTS"

KINDS = ("device_error", "nan", "preempt", "device_drop",
         "io_error", "torn_chunk", "thread_death", "pool_exhaust",
         "breaker_trip", "oom", "conn_reset", "partial_write",
         "stall", "slow_client")

#: THE fault-site registry: site → the kinds its production hooks honor.
#: Every ``inject``/``corrupt``/``fired``/``shrunk_budget``/
#: ``degrade_mesh`` call site must name a key of this dict — enforced
#: statically by the dqlint ``fault-site`` rule
#: (``analysis/rules/fault_sites.py``), because the plan matches sites by
#: string equality and a typo'd site silently never fires (the chaos test
#: behind it then passes vacuously). Kept a PURE LITERAL so the rule can
#: ``ast.literal_eval`` it without importing the engine. The README
#: "Chaos & degradation ladders" table documents each site's ladder.
FAULT_SITES = {
    "gram_sharded": ("device_error", "preempt", "nan"),
    "fit_packed": ("device_error", "preempt", "nan"),
    "solver": ("device_error", "preempt", "nan"),
    "fit": ("device_error", "preempt"),
    "mesh": ("device_drop",),
    "pipeline_flush": ("device_error", "nan"),
    "grouped_flush": ("device_error",),
    "shard_flush": ("device_error",),
    "shard_merge": ("device_error",),
    "ingest_native": ("io_error", "torn_chunk", "thread_death",
                      "pool_exhaust"),
    "serve_exec": ("device_error",),
    "serve_admit": ("breaker_trip", "oom"),
    "coalesce": ("device_error", "stall", "oom"),
    "oom": ("oom",),
    "stats_persist": ("io_error", "torn_chunk"),
    "incident": ("io_error",),
    "optimizer": ("device_error",),
    "aqe": ("device_error", "stall"),
    "cost_profile": ("device_error",),
    "dq_profile": ("device_error",),
    "net_accept": ("conn_reset",),
    "net_read": ("conn_reset", "stall", "slow_client"),
    "net_write": ("conn_reset", "partial_write", "stall"),
}


def _jax_runtime_error_base():
    import jax

    return jax.errors.JaxRuntimeError


class Preemption(RuntimeError):
    """Simulated mid-fit preemption (maintenance event / spot reclaim).

    Deliberately NOT a ``JaxRuntimeError``: retry loops must not swallow
    it as a transient device fault — ``recovery.fit_or_resume`` owns it
    (checkpoint what is done, resume from the artifact)."""


class InjectedIOError(OSError):
    """Simulated I/O failure in the native ingest layer (a flaky disk, a
    truncated network mount read). An ``OSError`` subclass — the exact
    class a real mid-read failure surfaces as — but deliberately NOT a
    ``FileNotFoundError``: a missing file is a permanent, user-visible
    condition the ingest ladder must re-raise, not degrade around."""


# The injected device error must be catchable exactly where real XLA
# faults are caught; subclassing at import time would force a jax import
# here, so the class is built lazily on first use.
_INJECTED_DEVICE_ERROR = None


def injected_device_error_class():
    global _INJECTED_DEVICE_ERROR
    if _INJECTED_DEVICE_ERROR is None:
        class InjectedDeviceError(_jax_runtime_error_base()):
            """Simulated ``XlaRuntimeError`` (device OOM / interconnect
            reset / preempted device) raised by the fault plan."""

        _INJECTED_DEVICE_ERROR = InjectedDeviceError
    return _INJECTED_DEVICE_ERROR


@dataclass
class FaultSpec:
    """One scheduled failure: ``kind`` at ``site``, firing on the listed
    1-based attempts, or per-attempt with probability ``p`` (seeded)."""

    site: str
    kind: str
    attempts: Optional[frozenset] = None   # None + p=None → {1}
    p: Optional[float] = None
    n: int = 1                             # device_drop count / nan leaves
    seed: Optional[int] = None             # overrides the plan seed

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r} "
                             f"(supported: {KINDS})")
        if self.attempts is None and self.p is None:
            self.attempts = frozenset({1})

    def fires(self, attempt: int, plan_seed: int) -> bool:
        if self.attempts is not None:
            return attempt in self.attempts
        # seeded Bernoulli: pure function of (seed, site, attempt) — no
        # global RNG state, so concurrent sites never perturb each other
        return _det_uniform(self._seed(plan_seed), self.site,
                            attempt) < float(self.p)

    def _seed(self, plan_seed: int) -> int:
        return plan_seed if self.seed is None else self.seed


def _det_uniform(seed: int, site: str, attempt: int) -> float:
    """Deterministic uniform in [0, 1): crc32-keyed — ``hash(str)`` is
    salted per process and would break run-to-run reproducibility."""
    key = zlib.crc32(f"{seed}:{site}:{attempt}".encode()) & 0xFFFFFFFF
    return key / 2.0 ** 32


def parse_spec(text: str) -> FaultSpec:
    parts = [p.strip() for p in text.strip().split(":") if p.strip()]
    if len(parts) < 2:
        raise ValueError(
            f"fault spec {text!r} must be site:kind[:attempts][:p=..]"
            "[:n=..][:seed=..]")
    site, kind = parts[0], parts[1].lower()
    attempts, p, n, seed = None, None, 1, None
    for part in parts[2:]:
        if part.startswith("p="):
            p = float(part[2:])
        elif part.startswith("n="):
            n = int(part[2:])
        elif part.startswith("seed="):
            seed = int(part[5:])
        else:
            attempts = frozenset(int(a) for a in part.split(",") if a)
    return FaultSpec(site, kind, attempts, p, n, seed)


def parse_plan(text: str, seed: int = 0) -> "FaultPlan":
    """Parse a plan string: specs separated by ``;`` (or newlines —
    commas stay free for attempt lists inside a spec)."""
    sep = ";" if ";" in text else "\n"
    specs = [parse_spec(s) for s in text.split(sep) if s.strip()]
    return FaultPlan(specs, seed=seed)


@dataclass
class FaultPlan:
    """Active failure schedule + per-(site, class) attempt counters + fire
    log. Attempt counters are keyed by failure *class* (``raise`` for
    device_error/preempt, ``nan``, ``drop``) so that co-located hooks —
    an ``inject`` and a ``corrupt`` guarding the same dispatch — never
    double-count one logical attempt."""

    specs: List[FaultSpec]
    seed: int = 0
    _counts: dict = field(default_factory=dict)
    _fired: list = field(default_factory=list)
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def _has(self, site: str, kinds: Sequence[str]) -> bool:
        return any(s.site == site and s.kind in kinds for s in self.specs)

    def _tick(self, site: str, cls: str) -> int:
        key = f"{site}#{cls}"
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + 1
            return self._counts[key]

    def _due(self, site: str, attempt: int, kinds: Sequence[str]):
        for spec in self.specs:
            if spec.site == site and spec.kind in kinds \
                    and spec.fires(attempt, self.seed):
                return spec
        return None

    def _record(self, spec: FaultSpec, attempt: int):
        with self._lock:
            self._fired.append((spec.site, spec.kind, attempt))
        from . import profiling

        profiling.counters.increment("faults.injected")
        profiling.counters.increment(f"faults.injected.{spec.site}")
        # Annotate the enclosing span: EXPLAIN ANALYZE copies every
        # ``recovery_*`` span attribute onto its operator node, so the
        # plan shows WHICH operator absorbed the fault (e.g. the
        # FusedStage whose flush span was live when this fired).
        try:
            from . import observability as _obs

            if _obs.TRACER.enabled:
                _obs.current_span().set(
                    recovery_fault=f"{spec.site}:{spec.kind}")
        except Exception:       # annotation must never mask the fault
            pass
        logger.warning("fault injected: site=%s kind=%s attempt=%d",
                       spec.site, spec.kind, attempt)

    # -- introspection (test assertions) -----------------------------------
    @property
    def fired(self) -> list:
        with self._lock:
            return list(self._fired)

    def attempts_at(self, site: str, cls: str = "raise") -> int:
        with self._lock:
            return self._counts.get(f"{site}#{cls}", 0)


# -- active-plan management (module global; None == chaos off) --------------
_PLAN: Optional[FaultPlan] = None
_ENV_CHECKED = False


def install_plan(plan: Optional[FaultPlan]) -> Optional[FaultPlan]:
    global _PLAN, _ENV_CHECKED
    _PLAN = plan
    _ENV_CHECKED = True  # an explicit install wins over the env
    return plan


def install_from_env(env: Optional[str] = None,
                     seed: int = 0) -> Optional[FaultPlan]:
    """(Re-)read the env spec; installs None when unset."""
    text = os.environ.get(ENV_VAR) if env is None else env
    return install_plan(parse_plan(text, seed=seed) if text else None)


def clear() -> None:
    install_plan(None)


def active() -> Optional[FaultPlan]:
    """The active plan — lazily picks up ``SPARKDQ4ML_FAULTS`` once so
    env-driven chaos works without a session."""
    global _ENV_CHECKED
    if not _ENV_CHECKED:
        _ENV_CHECKED = True
        if os.environ.get(ENV_VAR):
            install_from_env()
            _ENV_CHECKED = True
    return _PLAN


class inject_faults:
    """Context manager installing a plan for a scope (tests)::

        with inject_faults("gram_sharded:device_error:1", seed=42):
            model = lr.fit(frame)
    """

    def __init__(self, *specs, seed: int = 0):
        parsed = []
        for s in specs:
            parsed.append(s if isinstance(s, FaultSpec) else parse_spec(s))
        self.plan = FaultPlan(parsed, seed=seed)
        self._prev = None

    def __enter__(self) -> FaultPlan:
        self._prev = _PLAN
        install_plan(self.plan)
        return self.plan

    def __exit__(self, *exc):
        install_plan(self._prev)
        return False


# -- site hooks (the production instrumentation points) ---------------------
_RAISE_KINDS = ("device_error", "preempt", "io_error")


def inject(site: str) -> None:
    """Raise the scheduled failure for ``site``, if any. The per-site
    attempt counter ticks on every call that has a matching raise-class
    spec, so a retry loop naturally walks past an attempt-1-only fault on
    its second try. Raise classes: ``device_error`` →
    :class:`InjectedDeviceError` (a ``JaxRuntimeError``), ``preempt`` →
    :class:`Preemption`, ``io_error`` → :class:`InjectedIOError` (an
    ``OSError``, the native-ingest failure class)."""
    plan = active()
    if plan is None or not plan._has(site, _RAISE_KINDS):
        return
    attempt = plan._tick(site, "raise")
    spec = plan._due(site, attempt, _RAISE_KINDS)
    if spec is None:
        return
    plan._record(spec, attempt)
    if spec.kind == "preempt":
        raise Preemption(
            f"injected preemption at {site!r} (attempt {attempt})")
    if spec.kind == "io_error":
        raise InjectedIOError(
            f"injected I/O error at {site!r} (attempt {attempt})")
    raise injected_device_error_class()(
        f"injected device error at {site!r} (attempt {attempt})")


def fired(site: str, kind: str) -> bool:
    """Generic due-test hook for the non-raising fault kinds — the chaos
    switchpoints that alter a decision instead of throwing (a torn ingest
    chunk, a dying prefetch thread, an exhausted buffer pool, a tripped
    serving breaker, an admission-gate OOM). Each ``kind`` keeps its own
    per-site attempt counter, so co-located hooks of different kinds
    never steal each other's attempts. One ``is None`` check when no plan
    is installed — the same zero-cost contract as :func:`inject`."""
    plan = active()
    if plan is None or not plan._has(site, (kind,)):
        return False
    attempt = plan._tick(site, kind)
    spec = plan._due(site, attempt, (kind,))
    if spec is None:
        return False
    plan._record(spec, attempt)
    return True


def shrunk_budget(site: str) -> Optional[int]:
    """Device-byte budget override when an ``oom`` fault is due at
    ``site`` — "memory pressure as a schedulable fault" (arxiv
    2206.14148): the flush path treats the returned budget exactly like a
    conf-shrunken ``spark.audit.deviceBudget``, so the est-peak-over-
    budget → row-chunked degrade runs under test without touching real
    allocator state. The spec's ``n`` parameter carries the budget in
    bytes (``oom:oom:1:n=65536``); the default ``n=1`` is an always-over
    1-byte budget (maximum chunking). ``None`` = no fault due."""
    plan = active()
    if plan is None or not plan._has(site, ("oom",)):
        return None
    attempt = plan._tick(site, "oom")
    spec = plan._due(site, attempt, ("oom",))
    if spec is None:
        return None
    plan._record(spec, attempt)
    return max(1, int(spec.n))


def corrupt(site: str, tree):
    """Poison one float leaf element of ``tree`` with NaN when a ``nan``
    fault is due at ``site`` (seeded element choice); otherwise return
    ``tree`` unchanged."""
    plan = active()
    if plan is None or not plan._has(site, ("nan",)):
        return tree
    attempt = plan._tick(site, "nan")
    spec = plan._due(site, attempt, ("nan",))
    if spec is None:
        return tree
    plan._record(spec, attempt)
    return _poison(tree, spec._seed(plan.seed), site, attempt)


def _poison(tree, seed: int, site: str, attempt: int):
    """NaN one element of one inexact array leaf, chosen deterministically."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    targets = [i for i, leaf in enumerate(leaves)
               if hasattr(leaf, "dtype") and hasattr(leaf, "size")
               and np.issubdtype(np.asarray(leaf).dtype, np.inexact)
               and np.asarray(leaf).size > 0]
    if not targets:
        return tree
    u = _det_uniform(seed, site + "#leaf", attempt)
    li = targets[int(u * len(targets)) % len(targets)]
    leaf = leaves[li]
    size = int(np.asarray(leaf).size)
    ei = int(_det_uniform(seed, site + "#elem", attempt) * size) % size
    if isinstance(leaf, jax.Array):
        flat = jnp.ravel(leaf).at[ei].set(jnp.nan).reshape(leaf.shape)
    else:
        flat = np.array(leaf, copy=True)
        flat.reshape(-1)[ei] = np.nan
    leaves[li] = flat
    return jax.tree_util.tree_unflatten(treedef, leaves)


def degrade_mesh(site: str, mesh):
    """Drop ``n`` devices from ``mesh`` when a ``device_drop`` fault is due
    at ``site`` — the lost-worker scenario. Never drops below 1 device."""
    plan = active()
    if plan is None or mesh is None \
            or not plan._has(site, ("device_drop",)):
        return mesh
    attempt = plan._tick(site, "drop")
    spec = plan._due(site, attempt, ("device_drop",))
    if spec is None:
        return mesh
    plan._record(spec, attempt)
    devices = list(mesh.devices.flat)
    keep = max(1, len(devices) - spec.n)
    if keep == len(devices):
        return mesh
    from ..parallel.mesh import make_mesh

    logger.warning("fault plan dropped %d device(s): mesh %d -> %d",
                   len(devices) - keep, len(devices), keep)
    return make_mesh(devices=devices[:keep])
