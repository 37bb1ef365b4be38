"""Failure detection & recovery — the resilient-execution policy engine
(SURVEY.md §5 "Failure detection / elastic recovery").

The reference inherits Spark's recovery model — task retry, lineage
recomputation, checkpoint dirs — but configures none of it (``local[*]``,
no checkpoint dir, `DataQuality4MachineLearningApp.java:38-41`). The
TPU-native equivalents of those primitives:

* **Detection** — :func:`check_finite` inspects a result pytree for
  NaN/Inf (a diverged solver, a flaky interconnect transfer); the global
  NaN traps in ``utils.debug`` localize the producing op when needed.
  Device-side faults (OOM, interconnect resets, preempted devices)
  surface as ``XlaRuntimeError`` and are caught by the retry loop.
* **Deterministic re-execution (lineage)** — every fit in this framework
  is a pure function of (frame, params, seed), so a failed task re-runs
  identically; :func:`resilient_call` is the task-retry loop
  (``spark.task.maxFailures`` analogue) with exponential backoff +
  deterministic jitter (:class:`RetryPolicy`), per-attempt deadlines
  (:class:`DeadlineExceeded`), and a :class:`CircuitBreaker` that stops
  hammering a failing device path.
* **Graceful degradation** — :func:`resilient_call` walks a *fallback
  ladder*: when the primary path exhausts its retries (or its breaker is
  open) the next rung runs instead — e.g. sharded Gramian → single-device
  CPU Gramian (``parallel.distributed.compute_gram``), sharded packed fit
  → single-device fit → ``normal`` solver (``models.regression``).
* **Checkpointing** — :func:`fit_or_resume` persists the fitted stage via
  the models/base persistence layer and resumes from the artifact after a
  driver crash/preemption instead of refitting; with ``checkpoint_every``
  it checkpoints *mid-fit* every N solver iterations, so a preemption
  (real, or injected via ``utils.faults``) loses at most one segment.
* **Telemetry** — every retry, backoff, fallback, breaker trip, and
  resume lands in :data:`RECOVERY_LOG` as a structured
  :class:`RecoveryEvent` (mirrored into ``utils.profiling.counters`` and
  the ``sparkdq4ml_tpu.recovery`` logger), so recovery is observable,
  never silent. A clean run records zero events.

Fault injection for all of the above lives in :mod:`~sparkdq4ml_tpu.utils.faults`;
the chaos env vars, policy knobs, and the fallback ladder are documented in
README.md § "Failure model & fault injection".
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Callable, List, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np

logger = logging.getLogger("sparkdq4ml_tpu.recovery")


class FitFailure(RuntimeError):
    """A computation failed (non-finite result or device error) and did not
    recover within the configured retries/fallbacks."""


class DeadlineExceeded(RuntimeError):
    """An attempt ran past its per-attempt deadline. The in-flight device
    call cannot be cancelled (XLA dispatches are not interruptible); the
    retry loop stops *waiting* on it and moves on."""


class CircuitOpenError(FitFailure):
    """Every rung of the ladder was skipped because its breaker is open —
    nothing even ran. A :class:`FitFailure` subclass so callers guarding
    the generic failure path catch it too."""


# ---------------------------------------------------------------------------
# Telemetry: the structured recovery-event log
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RecoveryEvent:
    """One recovery decision, structured for assertions and dashboards."""

    site: str            # instrumented call site ("gram_sharded", "fit", …)
    action: str          # retry | fallback | recovered | exhausted |
    #                      circuit_open | circuit_skip | deadline |
    #                      preempted | resumed | checkpoint
    # wire sites (net_accept/net_read/net_write in serve/net.py, and the
    # client's net_client) add: conn_reset | partial_write | timeout |
    # hedge — one event per fault the network ladder absorbed
    attempt: int = 0     # 1-based attempt within the current rung
    rung: str = ""       # ladder rung label ("primary", "single_device", …)
    cause: str = ""      # exception repr / "non-finite" / ""
    backoff_s: float = 0.0
    detail: str = ""
    time_s: float = 0.0  # wall-clock timestamp (time.time)
    # Active-span correlation (None when tracing was off): the logfmt span
    # stream and the Chrome/Perfetto trace emit the same ids, so a retry
    # line here pins to the exact span it happened inside.
    trace_id: Optional[int] = None
    span_id: Optional[int] = None

    def as_kv(self) -> str:
        from .logging import format_kv

        return format_kv(
            site=self.site, action=self.action, attempt=self.attempt,
            rung=self.rung, cause=self.cause,
            backoff_s=round(self.backoff_s, 4), detail=self.detail,
            trace_id=self.trace_id, span_id=self.span_id)


class RecoveryLog:
    """Append-only structured event log + counter mirror. Thread-safe;
    bounded (drops oldest beyond ``maxlen``) so a hot retry loop can never
    grow memory without bound."""

    def __init__(self, maxlen: int = 10_000):
        self._events: List[RecoveryEvent] = []
        self._maxlen = maxlen
        self._lock = threading.Lock()

    def record(self, site: str, action: str, **kw) -> RecoveryEvent:
        if "trace_id" not in kw:
            from . import observability as _obs

            kw["trace_id"], kw["span_id"] = _obs.current_ids()
        ev = RecoveryEvent(site=site, action=action, time_s=time.time(), **kw)
        with self._lock:
            self._events.append(ev)
            if len(self._events) > self._maxlen:
                del self._events[: len(self._events) - self._maxlen]
        from . import profiling

        profiling.counters.increment(f"recovery.{action}")
        if site:
            # per-site mirror (recovery.retry.pipeline_flush, …): the
            # Prometheus scrape can attribute recovery activity to the
            # subsystem that absorbed it — cardinality bounded by the
            # FAULT_SITES registry, not by data
            profiling.counters.increment(f"recovery.{action}.{site}")
        level = (logging.INFO if action in ("resumed", "checkpoint",
                                            "recovered")
                 else logging.WARNING)
        logger.log(level, "recovery %s", ev.as_kv())
        return ev

    def events(self, site: Optional[str] = None,
               action: Optional[str] = None) -> List[RecoveryEvent]:
        with self._lock:
            evs = list(self._events)
        if site is not None:
            evs = [e for e in evs if e.site == site]
        if action is not None:
            evs = [e for e in evs if e.action == action]
        return evs

    def count(self, action: Optional[str] = None,
              site: Optional[str] = None) -> int:
        return len(self.events(site=site, action=action))

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


RECOVERY_LOG = RecoveryLog()


def recovery_events(site: Optional[str] = None,
                    action: Optional[str] = None) -> List[RecoveryEvent]:
    """The process-global structured recovery log (see :data:`RECOVERY_LOG`)."""
    return RECOVERY_LOG.events(site=site, action=action)


# ---------------------------------------------------------------------------
# Policy: backoff, deadlines, circuit breaker
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Retry-loop policy: attempts, exponential backoff with deterministic
    jitter, per-attempt deadline, and a total budget.

    Jitter is a pure function of (seed, site, attempt) — crc32-keyed, not
    ``random`` — so a failing run replays with identical sleeps (the same
    reproducibility rule as the fault schedule in ``utils.faults``).
    """

    max_attempts: int = 3
    backoff_base: float = 0.05     # s before the 2nd attempt
    backoff_factor: float = 2.0
    backoff_max: float = 5.0
    jitter: float = 0.1            # +[0, jitter) fraction of the backoff
    seed: int = 0
    attempt_deadline: Optional[float] = None   # s per attempt (thread-waited)
    total_deadline: Optional[float] = None     # s across all attempts/rungs
    sleep: Callable[[float], None] = time.sleep

    def __post_init__(self):
        if self.max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")

    def backoff(self, attempt: int, site: str = "") -> float:
        """Seconds to wait after failed ``attempt`` (1-based)."""
        if attempt >= self.max_attempts:
            return 0.0  # no sleep before a fallback/raise
        base = min(self.backoff_base * self.backoff_factor ** (attempt - 1),
                   self.backoff_max)
        if self.jitter <= 0.0 or base <= 0.0:
            return base
        from .faults import _det_uniform

        return base * (1.0 + self.jitter
                       * _det_uniform(self.seed, site, attempt))

    _CONF_KEYS = {
        "maxAttempts": ("max_attempts", int),
        "backoffBase": ("backoff_base", float),
        "backoffFactor": ("backoff_factor", float),
        "backoffMax": ("backoff_max", float),
        "jitter": ("jitter", float),
        "seed": ("seed", int),
        "attemptDeadline": ("attempt_deadline", float),
        "totalDeadline": ("total_deadline", float),
    }

    @classmethod
    def _conf_kwargs(cls, conf: Mapping, prefix: str) -> dict:
        kw = {}
        for conf_key, (attr, cast) in cls._CONF_KEYS.items():
            v = conf.get(prefix + conf_key)
            if v is not None:
                kw[attr] = cast(v)
        return kw

    @classmethod
    def from_conf(cls, conf: Optional[Mapping] = None,
                  prefix: str = "spark.recovery.", **overrides) -> "RetryPolicy":
        """Build from session conf / env-style string mappings, e.g.
        ``spark.recovery.maxAttempts``, ``.backoffBase``, ``.backoffMax``,
        ``.backoffFactor``, ``.jitter``, ``.seed``, ``.attemptDeadline``,
        ``.totalDeadline``. Unset keys keep the dataclass defaults."""
        kw = cls._conf_kwargs(conf or {}, prefix)
        kw.update(overrides)
        return cls(**kw)


def active_policy(site: str = "", **overrides) -> RetryPolicy:
    """The active session's retry policy: global ``spark.recovery.*``
    conf keys, with per-site ``spark.recovery.<site>.*`` keys layered on
    top (e.g. ``spark.recovery.gram_sharded.maxAttempts`` tunes only the
    sharded-Gramian ladder). Defaults when no session exists; lazy
    session lookup — recovery must stay importable without a session."""
    conf: Mapping = {}
    try:
        from ..session import TpuSession

        active = TpuSession.active()
        conf = active.conf if active is not None else {}
    except Exception:
        conf = {}
    kw = RetryPolicy._conf_kwargs(conf, "spark.recovery.")
    if site:
        kw.update(RetryPolicy._conf_kwargs(
            conf, f"spark.recovery.{site}."))
    kw.update(overrides)
    return RetryPolicy(**kw)


class CircuitBreaker:
    """Per-key consecutive-failure breaker: after ``failure_threshold``
    straight failures the key *opens* and calls are refused (the ladder
    skips straight to the next rung) until ``cooldown`` seconds pass, when
    one half-open trial is allowed; success closes the breaker."""

    def __init__(self, failure_threshold: int = 3, cooldown: float = 30.0,
                 clock: Callable[[], float] = time.monotonic):
        self.failure_threshold = int(failure_threshold)
        self.cooldown = float(cooldown)
        self._clock = clock
        self._state: dict = {}     # key -> [consecutive_failures, opened_at]
        self._lock = threading.Lock()

    def allow(self, key: str) -> bool:
        with self._lock:
            fails, opened = self._state.get(key, (0, None))
            if opened is None:
                return True
            if self._clock() - opened >= self.cooldown:
                return True    # half-open: one trial
            return False

    def is_open(self, key: str) -> bool:
        return not self.allow(key)

    def record_success(self, key: str) -> None:
        with self._lock:
            self._state.pop(key, None)

    def record_failure(self, key: str) -> bool:
        """Returns True when this failure OPENED the breaker."""
        with self._lock:
            fails, opened = self._state.get(key, (0, None))
            fails += 1
            just_opened = fails >= self.failure_threshold and opened is None
            if fails >= self.failure_threshold:
                opened = self._clock()
            self._state[key] = (fails, opened)
            return just_opened

    def trip(self, key: str) -> None:
        """Force the breaker OPEN for ``key`` now, as if
        ``failure_threshold`` consecutive failures just landed — the
        ``serve_admit:breaker_trip`` chaos hook. Recovery follows the
        normal path: the cooldown admits a half-open trial, and a success
        closes the key (``record_success``)."""
        with self._lock:
            self._state[key] = (self.failure_threshold, self._clock())

    def reset(self, key: Optional[str] = None) -> None:
        with self._lock:
            if key is None:
                self._state.clear()
            else:
                self._state.pop(key, None)

    def snapshot(self) -> dict:
        """Per-key breaker state for observability surfaces (the serving
        layer's ``QueryServer.stats()``): consecutive failure count and
        whether the key is currently refusing calls (``open`` goes False
        again once the cooldown admits a half-open trial)."""
        with self._lock:
            now = self._clock()
            return {
                key: {
                    "consecutive_failures": fails,
                    "open": (opened is not None
                             and now - opened < self.cooldown),
                }
                for key, (fails, opened) in self._state.items()
            }


#: Process-global breaker guarding device execution paths (sharded Gramian,
#: packed fit). Keys are site names; tests reset it via ``reset()``.
DEVICE_BREAKER = CircuitBreaker()


def _run_with_deadline(fn: Callable, seconds: Optional[float]):
    """Run ``fn()`` bounded by ``seconds``: the call runs in a DAEMON
    thread and :class:`DeadlineExceeded` is raised when it overruns. The
    worker cannot be cancelled (document over pretend: the dispatch keeps
    running), but the retry loop regains control — which for a wedged
    device call is the whole battle. Daemon, not a ThreadPoolExecutor:
    concurrent.futures joins its non-daemon workers at interpreter exit,
    so one wedged call would block process shutdown forever — the exact
    hang this deadline exists to escape."""
    if seconds is None:
        return fn()
    box: dict = {}

    def runner():
        try:
            box["value"] = fn()
        except BaseException as e:        # re-raised on the caller thread
            box["error"] = e

    t = threading.Thread(target=runner, daemon=True,
                         name="sparkdq4ml-deadline")
    t.start()
    t.join(seconds)
    if t.is_alive():
        raise DeadlineExceeded(
            f"attempt exceeded its {seconds:.3g} s deadline; the in-flight "
            "call may still be running")
    if "error" in box:
        raise box["error"]
    return box["value"]


# ---------------------------------------------------------------------------
# Detection
# ---------------------------------------------------------------------------

def check_finite(tree, _seen=None) -> bool:
    """True when every inexact array leaf in ``tree`` is fully finite.

    Works on device arrays, numpy arrays, fitted models (via their
    ``_persist_attrs`` when declared, else their instance ``__dict__`` —
    models with custom persistence must not silently pass), and arbitrary
    pytrees; non-numeric leaves pass. Cycles are guarded.
    """
    if _seen is None:
        _seen = set()
    if id(tree) in _seen:
        return True
    _seen.add(id(tree))

    attrs = getattr(tree, "_persist_attrs", None)
    if attrs is not None:
        return all(check_finite(getattr(tree, a, None), _seen)
                   for a in attrs)
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) == 1 and leaves[0] is tree \
            and not isinstance(tree, (jax.Array, np.ndarray, float,
                                      np.floating)) \
            and hasattr(tree, "__dict__"):
        # tree itself is one opaque leaf (a model object): scan its public
        # attributes directly
        return check_finite({k: v for k, v in vars(tree).items()
                             if not k.startswith("_")}, _seen)
    for leaf in leaves:
        if isinstance(leaf, (jax.Array, np.ndarray, float, np.floating)):
            arr = np.asarray(leaf)
            if np.issubdtype(arr.dtype, np.inexact) \
                    and not np.all(np.isfinite(arr)):
                return False
        elif hasattr(leaf, "__dict__") and id(leaf) not in _seen:
            # opaque object leaf (e.g. a model with custom save()): scan
            # its PUBLIC instance attributes instead of passing it blindly.
            # Private attrs are skipped — e.g. a model's _summary_source
            # frame legitimately carries NaN in masked slots.
            _seen.add(id(leaf))
            public = {k: v for k, v in vars(leaf).items()
                      if not k.startswith("_")}
            if not check_finite(public, _seen):
                return False
    return True


def result_validator() -> Optional[Callable]:
    """The NaN/Inf result validator for fit paths — :func:`check_finite`
    when detection is armed, else ``None``.

    Armed when a fault plan is installed (``utils.faults``; chaos tests
    must detect their own injected NaNs) or the active session opts in
    via ``spark.recovery.validate=on``. Off by default: a legitimately
    divergent fit (pathological data, zero valid rows) must keep
    returning its NaNs rather than silently refitting down the fallback
    ladder to *different* coefficients. Device errors always retry
    regardless — they never carry a legitimate result."""
    from . import faults as _faults

    if _faults.active() is not None:
        return check_finite
    try:
        from ..session import TpuSession

        s = TpuSession.active()
        from ..config import CONF_TRUE

        if s is not None and str(
                s.conf.get("spark.recovery.validate", "off")).lower() \
                in CONF_TRUE:
            return check_finite
    except Exception:
        pass
    return None


# ---------------------------------------------------------------------------
# The retry / fallback engine
# ---------------------------------------------------------------------------

def _retryable_errors() -> tuple:
    return (jax.errors.JaxRuntimeError, DeadlineExceeded)


def resilient_call(fn: Callable, *, site: str = "call",
                   policy: Optional[RetryPolicy] = None,
                   validate: Optional[Callable] = None,
                   fallbacks: Sequence[Tuple[str, Callable]] = (),
                   breaker: Optional[CircuitBreaker] = None,
                   on_failure: Optional[Callable] = None,
                   log: RecoveryLog = None):
    """Run ``fn()`` under the full resilience policy.

    The execution plan is a **ladder**: ``[("primary", fn)] + fallbacks``.
    Each rung gets up to ``policy.max_attempts`` attempts with exponential
    backoff + deterministic jitter between them; a rung whose breaker key
    (``site/rung``) is open is skipped outright (one ``circuit_skip``
    event), and when every rung fails the ladder raises
    :class:`FitFailure`. An attempt fails on a device error
    (``XlaRuntimeError``), a :class:`DeadlineExceeded`, or a result
    rejected by ``validate`` — the detection/lineage-replay loop.

    ``on_failure(attempt, error_or_none)`` runs after each failed attempt
    (cache clearing, re-seeding); when it returns a callable, that
    callable REPLACES the current rung's function for the remaining
    attempts — the downgrade hook (e.g. swap an ``owlqn`` solve for
    ``normal``).

    Every decision is recorded in ``log`` (default :data:`RECOVERY_LOG`);
    a clean first-attempt success records nothing.
    """
    from . import faults as _faults

    policy = policy or active_policy(site)
    log = log or RECOVERY_LOG
    started = time.monotonic()
    ladder = [("primary", fn)] + list(fallbacks)
    last_err: Optional[BaseException] = None
    last_cause = ""
    ran_any = False

    for rung_idx, (rung, call) in enumerate(ladder):
        key = f"{site}/{rung}"
        if breaker is not None and not breaker.allow(key):
            log.record(site, "circuit_skip", rung=rung,
                       detail="breaker open; skipping rung")
            continue
        ran_any = True
        if rung_idx > 0:
            log.record(site, "fallback", rung=rung, cause=last_cause,
                       detail=f"degrading to {rung!r}")
        for attempt in range(1, policy.max_attempts + 1):
            if policy.total_deadline is not None and \
                    time.monotonic() - started > policy.total_deadline:
                log.record(site, "deadline", rung=rung, attempt=attempt,
                           detail="total deadline exhausted")
                raise FitFailure(
                    f"{site}: total deadline of {policy.total_deadline:.3g}"
                    f" s exhausted after {attempt - 1} attempt(s) on rung "
                    f"{rung!r}") from last_err
            err: Optional[BaseException] = None
            try:
                # block_until_ready INSIDE the attempt: jax dispatch is
                # async, so a real device fault otherwise surfaces at the
                # caller's first host read — outside this ladder, past
                # the breaker, past every fallback. Syncing here also
                # makes attempt_deadline bound the actual device work,
                # not just the (instant) dispatch. Non-jax results pass
                # through untouched.
                out = _run_with_deadline(
                    lambda: jax.block_until_ready(call()),
                    policy.attempt_deadline)
            except _faults.Preemption:
                raise    # preemption is fit_or_resume's to handle
            except _retryable_errors() as e:
                err = e
            else:
                if validate is None or validate(out):
                    if breaker is not None:
                        breaker.record_success(key)
                    if attempt > 1 or rung_idx > 0:
                        log.record(site, "recovered", rung=rung,
                                   attempt=attempt)
                    return out
            last_err = err
            last_cause = (f"{type(err).__name__}: {err}" if err is not None
                          else "non-finite result")
            if breaker is not None and breaker.record_failure(key):
                log.record(site, "circuit_open", rung=rung, attempt=attempt,
                           cause=last_cause,
                           detail=f"breaker opened for {key!r}")
            wait = policy.backoff(attempt, site)
            log.record(site, "retry" if attempt < policy.max_attempts
                       else "exhausted", rung=rung, attempt=attempt,
                       cause=last_cause, backoff_s=wait)
            if on_failure is not None:
                downgraded = on_failure(attempt, err)
                if callable(downgraded):
                    call = downgraded
            if wait > 0.0:
                policy.sleep(wait)
    if not ran_any:
        raise CircuitOpenError(
            f"{site}: every rung's circuit breaker is open") from last_err
    raise FitFailure(
        f"{site}: failed after {len(ladder)} rung(s) x "
        f"{policy.max_attempts} attempt(s): {last_cause}") from last_err


def retry(fn: Callable, retries: int = 3,
          validate: Callable = check_finite,
          on_failure: Optional[Callable] = None):
    """Back-compat shim over :func:`resilient_call`: ``retries`` attempts,
    no backoff sleeps, no fallback ladder — the original task-retry loop
    (``spark.task.maxFailures`` analogue). ``on_failure(attempt, err)``
    runs between attempts; a callable return value downgrades ``fn``."""
    if retries < 1:
        raise ValueError("retries must be >= 1")
    policy = RetryPolicy(max_attempts=retries, backoff_base=0.0, jitter=0.0)
    try:
        return resilient_call(fn, site="retry", policy=policy,
                              validate=validate, on_failure=on_failure)
    except FitFailure as e:
        # preserve the historical message shape ("failed after N attempts")
        raise FitFailure(
            f"computation failed after {retries} attempts: "
            f"{e.__cause__ if e.__cause__ is not None else 'non-finite'}"
        ) from e.__cause__


# ---------------------------------------------------------------------------
# Checkpoint / resume (+ periodic mid-fit checkpointing)
# ---------------------------------------------------------------------------

def _has_stage(checkpoint_dir: str) -> bool:
    return os.path.exists(os.path.join(checkpoint_dir, "stage.json")) or \
        os.path.exists(os.path.join(checkpoint_dir, "metadata.json"))


def _atomic_save(model, checkpoint_dir: str,
                 progress: Optional[dict] = None) -> None:
    """Write to a sibling tmp dir, then one rename — a crash mid-save (the
    scenario this module exists for) must never leave a half-written dir
    that the resume branch would pick up. ``progress`` (the mid-fit
    checkpoint state) rides inside the same atomic rename."""
    import json
    import shutil

    from ..models.base import save_stage

    tmp = checkpoint_dir.rstrip("/\\") + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    save_stage(model, tmp)
    if progress is not None:
        with open(os.path.join(tmp, "progress.json"), "w") as f:
            json.dump(progress, f)
    shutil.rmtree(checkpoint_dir, ignore_errors=True)
    os.rename(tmp, checkpoint_dir)


def _read_progress(checkpoint_dir: str) -> Optional[dict]:
    import json

    try:
        with open(os.path.join(checkpoint_dir, "progress.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _fit_converged(model) -> Optional[bool]:
    """Convergence flag from the model's fit trajectory, when it has one."""
    src = getattr(model, "_summary_source", None)
    if src is None or len(src) < 2 or src[1] is None:
        return None
    converged = getattr(src[1], "converged", None)
    if converged is None:
        return None
    return bool(np.asarray(converged))


def fit_or_resume(estimator, frame, checkpoint_dir: str, mesh=None,
                  retries: int = 1, checkpoint_every: Optional[int] = None,
                  max_preemptions: int = 8):
    """Fit with a persistent checkpoint: if ``checkpoint_dir`` already holds
    a saved, *finished* stage, load and return it WITHOUT refitting
    (crash/preemption resume); otherwise fit (with retry semantics when
    ``retries > 1``), save atomically, and return the model.

    ``checkpoint_every=N`` enables **periodic mid-fit checkpointing** for
    iterative estimators (those with a ``max_iter`` param): the fit runs
    in segments of N iterations, each segment checkpointing its model +
    a ``progress.json`` cursor in one atomic rename. A crash or
    (injected) :class:`~sparkdq4ml_tpu.utils.faults.Preemption` between
    segments resumes from the cursor — at most one segment of work is
    lost. Segments re-run the data pass; for the Gramian-statistics
    solvers that pass is one masked matmul, so the dominant cost
    (tracing + compile) is paid once and cached. A simulated preemption
    is caught here (up to ``max_preemptions`` times), recorded in the
    recovery log, and turned into an immediate resume — the in-process
    equivalent of the restart-after-eviction path.
    """
    import inspect

    from ..models.base import load_stage
    from . import faults as _faults

    iterative = (checkpoint_every is not None
                 and getattr(estimator, "max_iter", None) is not None)
    if _has_stage(checkpoint_dir):
        progress = _read_progress(checkpoint_dir)
        finished = progress is None or progress.get("finished", True)
        if finished:
            logger.info("resuming fitted stage from %s", checkpoint_dir)
            RECOVERY_LOG.record("fit", "resumed",
                                detail=f"loaded stage from {checkpoint_dir}")
            return load_stage(checkpoint_dir)
        # The cursor marks the stage UNFINISHED — never hand it back as
        # the final model, even when this call didn't ask for segmented
        # fitting: continue from the cursor (iterative) or refit in full.
        if iterative:
            logger.info("resuming mid-fit from %s (%s/%s iterations)",
                        checkpoint_dir, progress.get("budget"),
                        progress.get("total"))
            RECOVERY_LOG.record(
                "fit", "resumed", detail=(
                    f"mid-fit cursor at {progress.get('budget')}"
                    f"/{progress.get('total')} iterations"))
        else:
            logger.info("checkpoint %s holds an UNFINISHED mid-fit "
                        "segment; refitting in full", checkpoint_dir)

    takes_mesh = "mesh" in inspect.signature(estimator.fit).parameters

    def do_fit(est):
        _faults.inject("fit")
        if takes_mesh:
            return est.fit(frame, mesh=mesh)
        return est.fit(frame)

    preemptions = 0
    while True:
        try:
            if iterative:
                return _fit_segments(estimator, checkpoint_dir, do_fit,
                                     retries, int(checkpoint_every))
            model = retry(lambda: do_fit(estimator), retries=retries)
            _atomic_save(model, checkpoint_dir)
            return model
        except _faults.Preemption as e:
            preemptions += 1
            RECOVERY_LOG.record("fit", "preempted", attempt=preemptions,
                                cause=str(e))
            if preemptions >= max_preemptions:
                raise FitFailure(
                    f"fit preempted {preemptions} times; giving up") from e
            if _has_stage(checkpoint_dir):
                progress = _read_progress(checkpoint_dir)
                if progress is None or progress.get("finished", True):
                    # a completed stage landed before the preemption —
                    # the restart path would just load it
                    return load_stage(checkpoint_dir)
            # else: loop — re-enter exactly like a restarted process would


def _fit_segments(estimator, checkpoint_dir: str, do_fit, retries: int,
                  every: int):
    """Segmented fit: grow the iteration budget ``every`` at a time,
    checkpointing after each segment. Re-fitting with a larger budget is
    deterministic lineage replay (a fit is a pure function of its
    inputs), so the final model is identical to a single uninterrupted
    fit that converged within the same budget."""
    import copy

    if every < 1:
        raise ValueError("checkpoint_every must be >= 1")
    total = int(estimator.max_iter)
    progress = _read_progress(checkpoint_dir) or {}
    done = int(progress.get("budget", 0)) if not progress.get(
        "finished", False) else 0
    model = None
    while True:
        budget = min(done + every, total)
        est = copy.copy(estimator)
        est.max_iter = budget
        model = retry(lambda: do_fit(est), retries=retries)
        converged = _fit_converged(model)
        finished = bool(converged) or budget >= total
        _atomic_save(model, checkpoint_dir, progress={
            "budget": budget, "total": total, "finished": finished})
        RECOVERY_LOG.record(
            "fit", "checkpoint",
            detail=f"segment at {budget}/{total} iterations"
                   + (" (finished)" if finished else ""))
        if finished:
            return model
        done = budget
