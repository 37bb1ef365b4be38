"""Data-quality observatory — column profiles, violation rates, drift.

The paper's premise is data quality gating ML, yet the engine could
attribute every plan, byte, and request (statstore, cost observatory,
tracing) while staying blind to the *data* flowing through the DQ
rules: no violation rates, no column profiles, no drift signal. This
module closes that gap with three sketches that all obey the standing
hot-path contracts:

* **per-column profiles** (:class:`ColumnProfile`) — count, null/NaN
  count, min/max, Welford mean+M2, and a fixed-bucket histogram over a
  log-compressed domain. The flush hook dispatches ONE tiny device
  reduction per profiled column (``ops/compiler.run_pipeline``), keyed
  on the padded power-of-two bucket so sketch programs retrace like any
  other plan — never per raw row count. The raw moment vector is
  *decomposable* (arxiv 2112.09017 style): sharded frames compute
  per-shard partials merged by one ``psum``/``pmin``/``pmax`` inside a
  ``shard_map`` program, and host-side profiles merge exactly
  (Chan's parallel mean/M2 formula), so shard-merged and single-device
  profiles agree bucket-for-bucket.
* **per-rule violation accounting** — every registered DQ UDF column a
  flush materializes records ``[rows, passed]`` against the flush's
  INPUT mask (the DQ convention: output > 0 = pass, so the counts
  survive the fused ``WHERE rule > 0`` filter that would otherwise
  erase the failures). That is the path of a rule whose function is
  row-local, the reference app's two among them: such a call defers
  into the flush (``ops/udf.py`` says which do). A rule that stays eager
  — a whole-column or host-only function, or any rule with the pipeline
  off — records through the same queue from ``ops/expressions.UdfCall``,
  and there ``rows`` is every slot the rule saw, rows an earlier filter
  masked out included: the two denominators differ by exactly those
  rows. A deferred call inside a larger expression (``rule(x) > 0`` as
  a filter) has no column to tally and records nothing.
* **drift scoring** — PSI over the fixed-bucket histograms against a
  pinned baseline (``spark.dq.baselineMode``): past
  ``spark.dq.driftThreshold`` the breach sets the ``dq.drift.<col>``
  gauge, tags the current span for the tail sampler's keep-policy, and
  captures an incident bundle carrying the before/after profiles.

Deferred-drain contract (the statstore ``drain_sync`` pattern): the hot
path only *enqueues* already-dispatched device values; the single
batched, counted host pull (``dq.drain_sync``) happens on the cold
surfaces — ``report()`` / the ``/dq`` route / EXPLAIN ANALYZE — so a
flush pays zero counted host syncs. ``spark.dq.profile.enabled=false``
reduces every hook to one conf read (test-pinned raise-monkeypatch
style) and pins EXPLAIN byte-identical.

Chaos: the ``dq_profile`` fault site fires at the sketch-dispatch
boundary; ANY failure — injected or real — degrades that flush to
unprofiled (``dq.profile_failed`` + a structured recovery event),
never fails the flush or a telemetry surface. Profiles persist into
the statstore as versioned snapshots (optional field,
merge-don't-clobber, back-compatible) under ``dqprof|<column>`` keys.

CPU-sandbox caveat: sums accumulate in float32 on device (TPU-native);
the host-side merge algebra is float64. Sketches are profiles, not
ledgers — use the statstore for exact row accounting.
"""

from __future__ import annotations

import functools
import logging
import math
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import config
from . import observability as _obs
from .profiling import counters

logger = logging.getLogger("sparkdq4ml_tpu.dqprof")

#: Profile-document schema version — persisted snapshots carry it; a
#: version-skewed doc is ignored (absent baseline), never a crash.
PROFILE_VERSION = 1

#: Columns profiled per flush (name-sorted prefix) — bounds both the
#: per-flush dispatch count and the sketch cache population.
MAX_COLS = 16

#: Bound on not-yet-drained deferred sketch vectors (each one tiny
#: device array): past it the oldest observation drops and is counted,
#: never an unbounded device-buffer leak (statstore MAX_PENDING twin).
MAX_PENDING = 4096

#: Bound on cached sketch programs — one per (bucket, dtype, bins,
#: shards); power-of-two buckets keep the real population far below it.
MAX_PROGRAMS = 64

#: Histogram domain clip in transform space: t = sign(x)·log10(1+|x|)
#: clipped to ±TMAX covers |x| up to 1e12 before saturating into the
#: edge buckets. Fixed at module level so persisted histograms from
#: different sessions always merge bucket-for-bucket.
TMAX = 12.0

#: Leading raw-moment slots of a sketch vector, ahead of the histogram:
#: [count, nulls, sum, sumsq, min, max].
MOMENTS = 6

#: Histogram scatter-add row bound: buckets up to this size histogram
#: every row; past it a deterministic stride-sample (scaled back up by
#: the stride) caps the one super-linear op in the sketch so a profiled
#: flush stays as cheap as an unprofiled one at any bucket width. The
#: exact-count fields (count/nulls/min/max/moments) always see every
#: row.
HIST_SAMPLE = 4096

#: PSI smoothing pseudo-count per bucket — keeps an empty bucket from
#: blowing the log ratio up to infinity.
EPS = 1e-4

#: Violation-rate incident bar: a drain whose per-rule failure rate
#: (over that drain's rows alone) reaches this captures a bundle.
VIOLATION_SPIKE_RATE = 0.5
#: ... but only with at least this much evidence in the drain window.
SPIKE_MIN_ROWS = 8


class ColumnProfile:
    """One column's running profile sketch. The device side ships raw
    decomposable moments; this host-side form keeps Welford mean+M2 so
    :meth:`merge` (Chan's parallel formula) is exact and associative —
    per-shard partials, per-flush increments, and persisted snapshots
    all combine through the same algebra."""

    __slots__ = ("count", "nulls", "mean", "m2", "min", "max", "hist")

    def __init__(self, count=0, nulls=0, mean=0.0, m2=0.0,
                 min=None, max=None, hist=None):
        self.count = int(count)
        self.nulls = int(nulls)
        self.mean = float(mean)
        self.m2 = float(m2)
        self.min = None if min is None else float(min)
        self.max = None if max is None else float(max)
        self.hist = [int(c) for c in (hist or [])]

    @classmethod
    def from_raw(cls, raw) -> Optional["ColumnProfile"]:
        """Host profile from one drained device sketch vector
        (``[count, nulls, sum, sumsq, min, max, hist...]``). None for a
        malformed vector — a discarded observation, never a crash."""
        arr = np.asarray(raw, dtype=np.float64).ravel()
        if arr.size < MOMENTS:
            return None
        count = int(round(float(arr[0])))
        nulls = int(round(float(arr[1])))
        if count > 0:
            mean = float(arr[2]) / count
            # naive-moment M2: clamp the float32 cancellation floor
            m2 = max(float(arr[3]) - float(arr[2]) ** 2 / count, 0.0)
            mn, mx = float(arr[4]), float(arr[5])
        else:
            mean, m2, mn, mx = 0.0, 0.0, None, None
        hist = [int(round(float(c))) for c in arr[MOMENTS:]]
        return cls(count=count, nulls=nulls, mean=mean, m2=m2,
                   min=mn, max=mx, hist=hist)

    @property
    def variance(self) -> Optional[float]:
        """Sample variance (None below two observations)."""
        return self.m2 / (self.count - 1) if self.count > 1 else None

    def merge(self, other: "ColumnProfile") -> None:
        """Chan's parallel mean/M2 merge — exact and associative, the
        property that makes per-shard partials, per-flush increments,
        and persisted baselines one algebra (test-pinned)."""
        n1, n2 = self.count, other.count
        if n2 > 0:
            if n1 == 0:
                self.mean, self.m2 = other.mean, other.m2
            else:
                n = n1 + n2
                delta = other.mean - self.mean
                self.mean += delta * n2 / n
                self.m2 += other.m2 + delta * delta * n1 * n2 / n
            self.count = n1 + n2
        self.nulls += other.nulls
        for mine, theirs, pick in (("min", other.min, min),
                                   ("max", other.max, max)):
            cur = getattr(self, mine)
            if theirs is not None:
                setattr(self, mine,
                        theirs if cur is None else pick(cur, theirs))
        if len(self.hist) == len(other.hist):
            self.hist = [a + b for a, b in zip(self.hist, other.hist)]
        elif n2 > n1:
            # a histogramBins conf flip mid-history: buckets no longer
            # align, adopt the heavier side whole (profile, not ledger)
            self.hist = list(other.hist)

    def copy(self) -> "ColumnProfile":
        return ColumnProfile(count=self.count, nulls=self.nulls,
                             mean=self.mean, m2=self.m2, min=self.min,
                             max=self.max, hist=self.hist)

    def to_doc(self) -> dict:
        return {"version": PROFILE_VERSION, "count": self.count,
                "nulls": self.nulls, "mean": self.mean, "m2": self.m2,
                "min": self.min, "max": self.max,
                "hist": list(self.hist)}

    @classmethod
    def from_doc(cls, doc) -> Optional["ColumnProfile"]:
        """None on a version-skewed or malformed doc — a stale persisted
        snapshot degrades to "no baseline", never a crash."""
        if not isinstance(doc, dict) \
                or int(doc.get("version", 0)) != PROFILE_VERSION:
            return None
        try:
            return cls(count=doc.get("count", 0),
                       nulls=doc.get("nulls", 0),
                       mean=doc.get("mean", 0.0), m2=doc.get("m2", 0.0),
                       min=doc.get("min"), max=doc.get("max"),
                       hist=doc.get("hist"))
        except (TypeError, ValueError):
            return None

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"ColumnProfile(count={self.count}, nulls={self.nulls}, "
                f"mean={self.mean:g}, bins={len(self.hist)})")


def histogram_edges(bins: int) -> list:
    """The fixed bucket edges in DATA space (``bins + 1`` values):
    bucket ``i`` covers ``[edges[i], edges[i+1])`` of the inverse of
    the log-compressed transform. Deterministic per ``bins`` value —
    the property that makes histograms mergeable across flushes,
    shards, and sessions."""
    out = []
    for i in range(int(bins) + 1):
        t = -TMAX + (2.0 * TMAX) * i / int(bins)
        out.append(math.copysign(10.0 ** abs(t) - 1.0, t))
    return out


def drift_score(baseline: Optional[ColumnProfile],
                current: Optional[ColumnProfile]) -> Optional[float]:
    """Population-stability index over the fixed-bucket histograms —
    None when either side is empty or the bucketings don't align
    (a histogramBins flip mid-session)."""
    if baseline is None or current is None:
        return None
    if baseline.count <= 0 or current.count <= 0:
        return None
    if not baseline.hist or len(baseline.hist) != len(current.hist):
        return None
    te = float(sum(baseline.hist)) + EPS * len(baseline.hist)
    ta = float(sum(current.hist)) + EPS * len(current.hist)
    score = 0.0
    for e, a in zip(baseline.hist, current.hist):
        pe = (e + EPS) / te
        pa = (a + EPS) / ta
        score += (pa - pe) * math.log(pa / pe)
    return round(score, 6)


# ---------------------------------------------------------------------------
# Device sketch programs (bounded cache, ProgramHandle-enumerable)
# ---------------------------------------------------------------------------

def _sketch_body(bins: int):
    """The per-device sketch reduction: one 1-D float32 vector of raw
    decomposable moments ``[count, nulls, sum, sumsq, min, max]`` plus
    the ``bins``-bucket histogram. NaN counts as null and is excluded
    from every moment; the padded mask tail is invalid by construction
    so padding never pollutes a profile.

    The moment/min/max reductions run over EVERY row (fused elementwise
    passes — cheap at any size), but the histogram's scatter-add is the
    one super-linear-cost op in the sketch, so past ``HIST_SAMPLE``
    rows it runs over a deterministic stride-sample scaled back up by
    the stride: the bucket *shape* stays statistically faithful while
    the per-flush cost stays O(HIST_SAMPLE) — this is what keeps a
    profiled flush as fast as an unprofiled one on wide buckets."""
    def sketch(col, mask):
        x = col.astype(jnp.float32)
        nan = jnp.isnan(x)
        valid = jnp.logical_and(mask, jnp.logical_not(nan))
        vf = valid.astype(jnp.float32)
        count = jnp.sum(vf)
        nulls = jnp.sum(jnp.logical_and(mask, nan).astype(jnp.float32))
        xv = jnp.where(valid, x, jnp.float32(0.0))
        s1 = jnp.sum(xv)
        s2 = jnp.sum(xv * xv)
        big = jnp.float32(3.0e38)    # empty → +big/-big, None on drain
        mn = jnp.min(jnp.where(valid, x, big))
        mx = jnp.max(jnp.where(valid, x, -big))
        step = -(-col.shape[0] // HIST_SAMPLE)   # static at trace time
        xs, vs = (x, vf) if step <= 1 else (x[::step], vf[::step])
        t = jnp.sign(xs) * jnp.log10(jnp.float32(1.0) + jnp.abs(xs))
        t = jnp.clip(t, -TMAX, TMAX)
        idx = ((t + TMAX) * (bins / (2.0 * TMAX))).astype(jnp.int32)
        idx = jnp.clip(idx, 0, bins - 1)
        hist = jnp.zeros((bins,), jnp.float32).at[idx].add(
            vs * jnp.float32(step))
        return jnp.concatenate(
            [jnp.stack([count, nulls, s1, s2, mn, mx]), hist])
    return sketch


def _rule_body():
    """The per-rule accounting reduction: ``[rows, passed]`` over the
    flush's input mask. The DQ convention (reference app): a rule
    output > 0 is a pass — NaN compares False, so a NaN rule output
    counts as a violation."""
    def rule(col, mask):
        x = col.astype(jnp.float32)
        mf = mask.astype(jnp.float32)
        passed = jnp.sum(jnp.where(
            jnp.logical_and(mask, x > 0), jnp.float32(1.0),
            jnp.float32(0.0)))
        return jnp.stack([jnp.sum(mf), passed])
    return rule


def _sharded(body, mesh):
    """Per-shard partials + one collective merge: sums/histogram psum,
    min/max pmin/pmax — the decomposable-partial algebra, on device.
    Returns ``(guarded dispatch fn, un-counted trace body)`` — the
    dispatch side rides the process-wide collective guard (the XLA:CPU
    overlapping-collective deadlock class)."""
    from jax.sharding import PartitionSpec as _P

    from ..parallel.mesh import DATA_AXIS, serialize_collectives, shard_map

    def merged(col, mask):
        part = body(col, mask)
        head = jax.lax.psum(part[:4], DATA_AXIS)
        rest = part[4:]
        if rest.shape[0] >= 2:
            mn = jax.lax.pmin(rest[0], DATA_AXIS)
            mx = jax.lax.pmax(rest[1], DATA_AXIS)
            tail = jax.lax.psum(rest[2:], DATA_AXIS)
            return jnp.concatenate([head, mn[None], mx[None], tail])
        return head

    traced = shard_map(merged, mesh=mesh,
                       in_specs=(_P(DATA_AXIS), _P(DATA_AXIS)),
                       out_specs=_P())
    return serialize_collectives(jax.jit(traced), mesh), traced


def _sharded_rule(body, mesh):
    """Sharded ``[rows, passed]`` accounting; same ``(guarded fn,
    traced)`` contract as :func:`_sharded`."""
    from jax.sharding import PartitionSpec as _P

    from ..parallel.mesh import DATA_AXIS, serialize_collectives, shard_map

    def merged(col, mask):
        return jax.lax.psum(body(col, mask), DATA_AXIS)

    traced = shard_map(merged, mesh=mesh,
                       in_specs=(_P(DATA_AXIS), _P(DATA_AXIS)),
                       out_specs=_P())
    return serialize_collectives(jax.jit(traced), mesh), traced


#: (kind, bucket, dtype, bins, shards) → (dispatch fn, un-counted trace
#: body, abstract arg specs, mesh, guarded). Bounded FIFO (MAX_PROGRAMS).
_PROGRAMS: dict = {}
_PROG_LOCK = threading.Lock()


def _program_key(key) -> str:
    kind, b, dtype, bins, shards = key
    return f"dq{kind}|b{b}|{dtype}|bins{bins}|shards{shards}"


def _program(kind: str, b: int, dtype, shard):
    """The cached sketch/rule program at one structural key. Sharded
    frames get the psum-merged ``shard_map`` lowering, dispatched under
    the process-wide collective guard like every mesh-bearing program."""
    bins = max(int(config.dq_histogram_bins), 1) if kind == "sketch" \
        else 0
    devices = int(shard.devices) if shard is not None else 0
    key = (kind, int(b), str(jnp.dtype(dtype)), bins, devices)
    with _PROG_LOCK:
        entry = _PROGRAMS.get(key)
    if entry is not None:
        return entry
    raw = _sketch_body(bins) if kind == "sketch" else _rule_body()

    @functools.wraps(raw)       # the program keeps its name (jit_sketch)
    def body(col, mask):
        # the dq profile's device work, named in a trace's op metadata
        with _obs.scope("sketch"):
            return raw(col, mask)
    if shard is not None:
        wrap = _sharded if kind == "sketch" else _sharded_rule
        fn, traced = wrap(body, shard.mesh)
        mesh, guarded = shard.mesh, True
    else:
        traced = body
        fn = jax.jit(traced)
        mesh, guarded = None, None
    specs = (jax.ShapeDtypeStruct((int(b),), jnp.dtype(dtype)),
             jax.ShapeDtypeStruct((int(b),), jnp.bool_))
    entry = (fn, traced, specs, mesh, guarded)
    with _PROG_LOCK:
        if key not in _PROGRAMS and len(_PROGRAMS) >= MAX_PROGRAMS:
            _PROGRAMS.pop(next(iter(_PROGRAMS)))
            counters.increment("dq.program_evict")
        _PROGRAMS.setdefault(key, entry)
    return entry


def program_handles() -> list:
    """Registry callback (``observability.CACHES.register_programs``):
    one :class:`~.observability.ProgramHandle` per cached sketch/rule
    program, so dqaudit statically bounds sketch peak bytes the same
    way it bounds every other enumerable program. ``fn`` is the
    un-counted trace body."""
    with _PROG_LOCK:
        items = list(_PROGRAMS.items())
    return [_obs.ProgramHandle(
        "dqprof", _program_key(key), traced, args=specs,
        mesh=mesh, guarded=guarded)
        for key, (_, traced, specs, mesh, guarded) in items]


# ---------------------------------------------------------------------------
# Deferred observation queue + host-side state
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
#: ("col"|"rule", name, host_rows, device value) awaiting ONE batched
#: host pull — drained on the cold paths only (see drain()).
_PENDING: list = []
_PROFILES: dict = {}     # column -> ColumnProfile (cumulative)
_BASELINES: dict = {}    # column -> pinned ColumnProfile (drift ref)
_NO_BASELINE = object()  # pin attempted, mode yielded none — don't retry
_RULES: dict = {}        # rule -> {"evals", "rows", "violations"}
_DRIFT: dict = {}        # column -> latest PSI score


def enabled() -> bool:
    return bool(config.dq_profile_enabled)


def clear() -> None:
    """Drop every profile, baseline, rule tally, pending observation,
    and cached program (tests; conf flips)."""
    with _LOCK:
        _PENDING.clear()
        _PROFILES.clear()
        _BASELINES.clear()
        _RULES.clear()
        _DRIFT.clear()
    with _PROG_LOCK:
        _PROGRAMS.clear()


def _is_tracer(v) -> bool:
    try:
        return isinstance(v, jax.core.Tracer)
    except AttributeError:       # jax.core reshuffles across versions
        return "Tracer" in type(v).__name__


def _profilable(v, b: Optional[int]) -> bool:
    """Numeric 1-D device/host column at the expected bucket length —
    object (string) columns and shape surprises are skipped, never an
    error."""
    dt = getattr(v, "dtype", None)
    shape = getattr(v, "shape", None)
    if dt is None or shape is None or len(shape) != 1:
        return False
    if b is not None and int(shape[0]) != int(b):
        return False
    try:
        return np.dtype(dt).kind in "fiub"
    except TypeError:
        return False


def _enqueue(entries) -> None:
    dropped = 0
    with _LOCK:
        _PENDING.extend(entries)
        while len(_PENDING) > MAX_PENDING:
            _PENDING.pop(0)
            dropped += 1
    if dropped:
        counters.increment("dq.pending_dropped", dropped)


def observe_flush(changed, new_mask, bucket: int, shard=None,
                  rules=(), mask_in=None) -> None:
    """The flush hook (``ops/compiler.run_pipeline``, gated there on ONE
    ``spark.dq.profile.enabled`` read): dispatch one sketch reduction
    per profiled output column over the PADDED bucket arrays, plus one
    ``[rows, passed]`` reduction per registered-rule column (``rules``:
    ``(rule, values)`` pairs) against the flush's input mask, and
    enqueue the device results for a later batched drain — zero host
    syncs here. Each rule entry is one evaluation: ``dq.rule_evals``
    moves here as it does in :func:`record_eval`.

    Rides the ``dq_profile`` fault site: ANY failure — injected or
    real — degrades this flush to unprofiled with a counted, structured
    recovery event; the flush itself and every telemetry surface keep
    working."""
    if not enabled():
        return
    from . import faults as _faults

    try:
        _faults.inject("dq_profile")
        b = int(bucket)
        if b <= 0:
            return
        entries = []
        for name in sorted(changed):
            if len(entries) >= MAX_COLS:
                break
            v = changed[name]
            if not _profilable(v, b):
                continue
            fn = _program("sketch", b, v.dtype, shard)[0]
            entries.append(("col", str(name), 0, fn(v, new_mask)))
        evals = 0
        if mask_in is not None:
            for rule_name, v in rules:
                if not _profilable(v, b):
                    continue
                fn = _program("rule", b, v.dtype, shard)[0]
                entries.append(("rule", str(rule_name), 0,
                                fn(v, mask_in)))
                evals += 1
        if not entries:
            return
        counters.increment("dq.sketches", len(entries))
        if evals:
            counters.increment("dq.rule_evals", evals)
        _enqueue(entries)
    except Exception as e:
        counters.increment("dq.profile_failed")
        from .recovery import RECOVERY_LOG

        RECOVERY_LOG.record(
            "dq_profile", "fallback", rung="unprofiled",
            cause=f"{type(e).__name__}: {e}",
            detail="dq sketch dispatch degraded; this flush reports "
                   "no profile")
        logger.debug("dq sketch dispatch failed", exc_info=True)


def record_eval(rule: str, out) -> None:
    """Per-rule accounting for one EAGER UDF evaluation
    (``ops/expressions.UdfCall`` — gated there on ONE conf read). A
    trace-time call sees a tracer and returns immediately: compiled
    evaluations account through :func:`observe_flush` instead, so no
    evaluation is ever double-counted."""
    if not enabled():
        return
    try:
        if _is_tracer(out) or not _profilable(out, None):
            return
        rows = int(out.shape[0])
        if rows <= 0:
            return
        passed = jnp.sum(
            jnp.where(jnp.asarray(out) > 0, jnp.float32(1.0),
                      jnp.float32(0.0)))
        counters.increment("dq.rule_evals")
        _enqueue([("rule", str(rule), rows, passed)])
    except Exception:
        logger.debug("dq rule-eval hand-off failed", exc_info=True)


# ---------------------------------------------------------------------------
# Cold-path drain: profiles, baselines, drift, violation telemetry
# ---------------------------------------------------------------------------

def _record_statstore(col: str, prof: ColumnProfile) -> None:
    if not config.stats_enabled:
        return
    try:
        from . import statstore as _stats

        _stats.STORE.record_profile(f"dqprof|{col}", "dqprof",
                                    prof.to_doc())
    except Exception:
        logger.debug("dq-profile statstore hand-off failed",
                     exc_info=True)


def _adopted_baseline(col: str) -> Optional[ColumnProfile]:
    """A persisted snapshot loaded at session init may already carry
    this column's profile — the cross-session drift reference."""
    if not config.stats_enabled:
        return None
    try:
        from . import statstore as _stats

        doc = _stats.STORE.profile(f"dqprof|{col}")
    except Exception:
        return None
    return ColumnProfile.from_doc(doc) if doc else None


def _pin_baseline(col: str, prof: ColumnProfile):
    """The drift reference per ``spark.dq.baselineMode``: ``first``
    (default) adopts a persisted snapshot when one exists, else pins
    the first drained profile; ``persisted`` only ever adopts from the
    statstore; ``off`` disables drift scoring."""
    mode = str(config.dq_baseline_mode)
    if mode == "off":
        return _NO_BASELINE
    adopted = _adopted_baseline(col)
    if adopted is not None:
        baseline = adopted
    elif mode == "persisted":
        return _NO_BASELINE
    else:
        baseline = prof.copy()
    counters.increment("dq.baseline_pinned")
    return baseline


def _check_drift(col: str, prof: ColumnProfile) -> None:
    baseline = _BASELINES.get(col)
    if baseline is None:
        baseline = _BASELINES[col] = _pin_baseline(col, prof)
    if baseline is _NO_BASELINE:
        return
    score = drift_score(baseline, prof)
    if score is None:
        return
    with _LOCK:
        _DRIFT[col] = score
    _obs.METRICS.set_gauge(f"dq.drift.{col}", score)
    threshold = float(config.dq_drift_threshold)
    if score <= threshold:
        return
    counters.increment("dq.drift_breach")
    # tail-sampler keep-policy hand-off: a request tree whose spans saw
    # a drift breach is evidence worth retaining (observability.TailSampler)
    _obs.current_span().set(dq_drift=col)
    from . import incidents as _incidents

    _incidents.RECORDER.record(
        "dq_drift",
        detail=f"column {col!r} drift {score:g} > threshold "
               f"{threshold:g}",
        extra={"dq_drift": {"column": col, "score": score,
                            "threshold": threshold,
                            "baseline": baseline.to_doc(),
                            "current": prof.to_doc()}})


def _apply_rule(name: str, rows: int, passed: int, window: dict) -> None:
    with _LOCK:
        r = _RULES.setdefault(
            name, {"evals": 0, "rows": 0, "violations": 0})
        r["evals"] += 1
        r["rows"] += rows
        violations = max(rows - passed, 0)
        r["violations"] += violations
        total_rows, total_viol = r["rows"], r["violations"]
    w = window.setdefault(name, [0, 0])
    w[0] += rows
    w[1] += violations
    if violations:
        counters.increment(f"dq.violations.{name}", violations)
    rate = (total_viol / total_rows) if total_rows else 0.0
    _obs.METRICS.set_gauge(f"dq.violation_rate.{name}", round(rate, 6))


def _check_spikes(window: dict) -> None:
    """Violation-rate spike detection over THIS drain's evidence alone
    (a long healthy history must not mask a sudden failure wave)."""
    from . import incidents as _incidents

    for name, (rows, violations) in window.items():
        if rows < SPIKE_MIN_ROWS:
            continue
        rate = violations / rows
        if rate < VIOLATION_SPIKE_RATE:
            continue
        counters.increment("dq.violation_spike")
        _incidents.RECORDER.record(
            "dq_violations",
            detail=f"rule {name!r} violation rate {rate:.3f} over "
                   f"{rows} rows",
            extra={"dq_violations": {"rule": name, "rows": rows,
                                     "violations": violations,
                                     "rate": round(rate, 6)}})


def drain() -> None:
    """Pull every queued deferred observation in ONE batched
    ``device_get`` (cold paths only — report / the ``/dq`` route /
    EXPLAIN ANALYZE; counted ``dq.drain_sync``, never a silent sync),
    then fold the results into profiles, baselines, drift gauges, and
    per-rule violation telemetry."""
    with _LOCK:
        pending, _PENDING[:] = list(_PENDING), []
    if not pending:
        return
    try:
        values = jax.device_get([p[3] for p in pending])
        counters.increment("dq.drain_sync")
    except Exception:
        # a dead backend must not take a dq report down; the
        # observations are lost, the observatory stays coherent
        logger.debug("dq drain failed", exc_info=True)
        return
    touched: dict = {}
    window: dict = {}
    for (kind, name, rows, _), v in zip(pending, values):
        try:
            arr = np.asarray(v, dtype=np.float64).ravel()
            if kind == "col":
                prof = ColumnProfile.from_raw(arr)
                if prof is None:
                    continue
                with _LOCK:
                    cur = _PROFILES.get(name)
                    if cur is None:
                        cur = _PROFILES[name] = prof
                    else:
                        cur.merge(prof)
                touched[name] = cur
            else:
                if arr.size >= 2:       # flush path: [rows, passed]
                    total = int(round(arr[0]))
                    passed = int(round(arr[1]))
                else:                   # eager path: host rows + scalar
                    total = int(rows)
                    passed = int(round(float(arr.sum())))
                _apply_rule(name, total, passed, window)
        except Exception:
            logger.debug("dq observation discarded", exc_info=True)
    for col, prof in touched.items():
        try:
            _check_drift(col, prof)
            _record_statstore(col, prof)
        except Exception:
            logger.debug("dq drift/persist failed for %r", col,
                         exc_info=True)
    _check_spikes(window)


# ---------------------------------------------------------------------------
# Cold surfaces: report / EXPLAIN section
# ---------------------------------------------------------------------------

def report(top: Optional[int] = None, drain_first: bool = True) -> dict:
    """The observatory view (``session.dq_report()`` and the HTTP
    ``/dq`` route): one row per profiled column — sketch fields, drift
    score, pinned-baseline evidence — plus per-rule violation tallies.
    Cold surface: drains the deferred queue (``drain_first=False`` for
    re-entrant callers like the incident recorder)."""
    if not enabled():
        return {"enabled": False, "columns": [], "rules": [],
                "size": 0, "pending": 0}
    if drain_first:
        drain()
    with _LOCK:
        profiles = {k: v.copy() for k, v in _PROFILES.items()}
        baselines = dict(_BASELINES)
        rules = {k: dict(v) for k, v in _RULES.items()}
        drift = dict(_DRIFT)
        pending = len(_PENDING)
    columns = []
    for col in sorted(profiles):
        p = profiles[col]
        doc = p.to_doc()
        doc["column"] = col
        doc["variance"] = p.variance
        doc["drift"] = drift.get(col)
        base = baselines.get(col)
        doc["baseline_count"] = (base.count if isinstance(
            base, ColumnProfile) else None)
        columns.append(doc)
    if top is not None:
        columns = columns[:max(int(top), 0)]
    rule_rows = []
    for name in sorted(rules):
        r = rules[name]
        rate = (r["violations"] / r["rows"]) if r["rows"] else 0.0
        rule_rows.append({"rule": name, "evals": r["evals"],
                          "rows": r["rows"],
                          "violations": r["violations"],
                          "rate": round(rate, 6)})
    return {"enabled": True, "columns": columns, "rules": rule_rows,
            "size": len(profiles), "pending": pending,
            "bins": int(config.dq_histogram_bins),
            "drift_threshold": float(config.dq_drift_threshold),
            "baseline_mode": str(config.dq_baseline_mode)}


def rule_marks() -> Optional[dict]:
    """Pre-execution mark for EXPLAIN ANALYZE's rule-bearing detection:
    per-rule eval counts after a drain (cold surface — EXPLAIN owns
    the sync budget here). None when disabled."""
    if not enabled():
        return None
    drain()
    with _LOCK:
        return {name: r["evals"] for name, r in _RULES.items()}


def explain_lines(marks) -> list:
    """The ``== Data Quality ==`` EXPLAIN ANALYZE section — rendered
    only for rule-bearing queries (a registered DQ rule evaluated since
    ``marks``), so rule-free queries stay byte-identical. Cumulative
    observatory rows: the rule tallies and the profiled columns the
    session has accumulated."""
    if marks is None or not enabled():
        return []
    drain()
    with _LOCK:
        rules = {k: dict(v) for k, v in _RULES.items()}
        profiles = {k: v.copy() for k, v in _PROFILES.items()}
        drift = dict(_DRIFT)
    evaluated = [name for name in sorted(rules)
                 if rules[name]["evals"] > marks.get(name, 0)]
    if not evaluated:
        return []
    lines = ["== Data Quality =="]
    for name in sorted(rules):
        r = rules[name]
        rate = (r["violations"] / r["rows"]) if r["rows"] else 0.0
        lines.append(f"rule {name}: evals={r['evals']} "
                     f"rows={r['rows']} violations={r['violations']} "
                     f"rate={rate:.4f}")
    for col in sorted(profiles)[:8]:
        p = profiles[col]
        span = ("-" if p.min is None
                else f"[{p.min:g}, {p.max:g}]")
        d = drift.get(col)
        lines.append(f"column {col}: count={p.count} nulls={p.nulls} "
                     f"mean={p.mean:.4f} range={span} "
                     f"drift={'-' if d is None else format(d, 'g')}")
    return lines


# Program enumeration for the jaxpr auditor / cost observatory — the
# sketch cache is registry-enumerable like every other compiled-program
# cache (peak-byte bounding rides dqaudit's existing machinery).
def _register() -> None:
    _obs.CACHES.register_programs("dqprof", program_handles)


_register()
