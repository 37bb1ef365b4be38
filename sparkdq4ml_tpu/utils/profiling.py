"""Tracing / profiling utilities (SURVEY.md §5 "Tracing / profiling").

The reference exposes nothing beyond post-hoc ``objectiveHistory`` prints
(`DataQuality4MachineLearningApp.java:133-136`). Here:

* :func:`block_until_ready` — honest timing helper (JAX dispatch is async;
  timings without a sync measure nothing),
* :data:`counters` — process-global named counters; the recovery layer
  (``utils.recovery.RECOVERY_LOG``) mirrors every retry/fallback/breaker
  event here as ``recovery.<action>``, so resilience activity shows up in
  the same place as performance telemetry; :func:`host_read` counts the
  blocking device->host reads and their bytes (every site reaches it
  through ``observability.host_reading``, which also times the read as a
  span while the tracer records),
* :func:`start_capture` / :func:`stop_capture` — managed ``jax.profiler``
  captures (the ``/profile/trace`` surface). While one runs the span
  tracer records by itself and writes its spans into the capture
  (``utils.observability``): per-phase wall-clock is a span's job now.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

import jax

logger = logging.getLogger("sparkdq4ml_tpu.profiling")


def block_until_ready(tree):
    return jax.block_until_ready(tree)


class Counters:
    """Thread-safe named monotonic counters (Spark-metrics analogue).

    The recovery subsystem increments ``recovery.retry``,
    ``recovery.fallback``, ``recovery.circuit_open``, … per structured
    event; anything else in the framework is free to add its own names.
    ``snapshot()`` returns a plain dict for reports/assertions."""

    def __init__(self):
        self._counts: dict[str, int] = {}
        self._lock = threading.Lock()

    def increment(self, name: str, by: int = 1) -> int:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + by
            return self._counts[name]

    def get(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def snapshot(self, prefix: str = "") -> dict:
        with self._lock:
            return {k: v for k, v in self._counts.items()
                    if k.startswith(prefix)}

    def clear(self, prefix: str = "") -> None:
        with self._lock:
            if not prefix:
                self._counts.clear()
            else:
                for k in [k for k in self._counts if k.startswith(prefix)]:
                    del self._counts[k]


#: Process-global counter registry (see :class:`Counters`).
counters = Counters()


def host_read(nbytes: int) -> None:
    """Count one blocking device->host read of ``nbytes`` (``host.reads``,
    ``host.read_bytes``). ``nbytes`` comes from the host copy's
    ``.nbytes`` or from static shapes — never from another device op.
    Beside ``frame.host_sync``, which keeps its meaning (counted frame
    boundary pulls); this pair counts every read, ``count()`` and the
    fit's validation-stats and result reads included, and says how large.
    A count, never a time: ``observability.host_reading`` wraps the
    blocking call, ends in this, and holds the duration as a span."""
    counters.increment("host.reads")
    counters.increment("host.read_bytes", int(nbytes))


# ---------------------------------------------------------------------------
# Managed jax-profiler captures (the /profile/trace surface)
# ---------------------------------------------------------------------------
#
# The on-demand capture the telemetry endpoint arms (serve/http.py
# ``/profile/trace?seconds=N``) needs a bounded, discoverable home:
# captures land under one base directory,
# named ``cap-<timestamp>-<label>`` so a capture is attributable to the
# plan/context that armed it, retention is bounded by
# ``spark.profiling.maxCaptures`` (oldest pruned), and the newest path
# is surfaced in ``/profile`` for the operator to pull into
# TensorBoard/Perfetto. One capture at a time per process (the jax
# profiler is a process-global singleton).

#: Hard ceiling on an armed capture's duration (seconds) — a typo'd
#: ``?seconds=`` must not leave the profiler running for an hour.
MAX_CAPTURE_S = 60.0

_CAPTURE_LOCK = threading.Lock()
_CAPTURE_ACTIVE: Optional[str] = None     # path of the running capture


def capture_base_dir() -> str:
    """Home of managed captures: ``SPARKDQ4ML_CAPTURE_DIR`` env
    override, else ``~/.cache/sparkdq4ml_tpu/captures``."""
    import os

    env = os.environ.get("SPARKDQ4ML_CAPTURE_DIR")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "sparkdq4ml_tpu", "captures")


def captures() -> list:
    """Managed capture directories, oldest first (timestamp-named, so
    lexicographic order IS age order)."""
    import os

    base = capture_base_dir()
    try:
        return sorted(
            os.path.join(base, d) for d in os.listdir(base)
            if d.startswith("cap-")
            and os.path.isdir(os.path.join(base, d)))
    except OSError:
        return []


def latest_capture() -> Optional[str]:
    """Newest managed capture path (``/profile`` surfaces it), or None."""
    caps = captures()
    return caps[-1] if caps else None


def prune_captures(keep: Optional[int] = None) -> int:
    """Drop the oldest managed captures past ``keep`` (default:
    ``spark.profiling.maxCaptures``); returns the pruned count.
    Best-effort — retention hygiene must never raise."""
    import shutil

    if keep is None:
        from ..config import config

        keep = int(config.profiling_max_captures)
    keep = max(int(keep), 1)
    pruned = 0
    for path in captures()[:-keep] if keep else captures():
        try:
            shutil.rmtree(path, ignore_errors=True)
            pruned += 1
        except OSError:
            pass
    return pruned


def capture_active() -> Optional[str]:
    with _CAPTURE_LOCK:
        return _CAPTURE_ACTIVE


def start_capture(seconds: float, label: str = "manual") -> str:
    """Arm one managed jax-profiler capture for ``seconds`` (clamped to
    :data:`MAX_CAPTURE_S`); a background timer stops it. Returns the
    capture path. Raises ``RuntimeError`` when a capture is already
    running — the profiler is process-global and two overlapping
    ``start_trace`` calls corrupt each other's sessions."""
    import os
    import re

    global _CAPTURE_ACTIVE
    seconds = min(max(float(seconds), 0.05), MAX_CAPTURE_S)
    safe = re.sub(r"[^A-Za-z0-9_.-]+", "_", str(label))[:48] or "manual"
    name = f"cap-{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}-{safe}"
    path = os.path.join(capture_base_dir(), name)
    with _CAPTURE_LOCK:
        if _CAPTURE_ACTIVE is not None:
            raise RuntimeError(
                f"a profiler capture is already running "
                f"({_CAPTURE_ACTIVE}); one capture at a time")
        os.makedirs(path, exist_ok=True)
        jax.profiler.start_trace(path)
        _CAPTURE_ACTIVE = path
    counters.increment("profiling.captures")

    def _stop(armed=path):
        time.sleep(seconds)
        # bound to the capture THIS timer armed: a manual stop_capture
        # followed by a fresh arm must not be truncated by the stale
        # timer of the capture that already ended
        stop_capture(expected=armed)

    threading.Thread(target=_stop, daemon=True,
                     name="sparkdq4ml-capture-timer").start()
    return path


def stop_capture(expected: Optional[str] = None) -> Optional[str]:
    """Stop the running capture (idempotent); prunes retention and
    returns the finished capture's path (None when nothing ran).
    ``expected`` stops only when that specific capture is still the
    active one (the timer-thread contract)."""
    global _CAPTURE_ACTIVE
    with _CAPTURE_LOCK:
        if expected is not None and _CAPTURE_ACTIVE != expected:
            return None
        path, _CAPTURE_ACTIVE = _CAPTURE_ACTIVE, None
        if path is None:
            return None
        try:
            jax.profiler.stop_trace()
        except Exception:
            logger.debug("profiler stop_trace failed", exc_info=True)
    prune_captures()
    return path
