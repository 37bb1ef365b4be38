"""Device-resident grouped execution: segment-reduction groupBy/sort/distinct.

``frame/aggregates.py`` documents the host boundary the seed design chose:
group discovery is data-dependent (dynamic shapes), so grouping, sorting,
and dedup all round-tripped device→host→device with numpy loops. This
module removes that boundary for the numeric surface, the same way the
pipeline compiler (``ops/compiler.py``) removed it for expression chains:

* **One jitted program per plan shape.** ``group_by(...).agg(...)`` lowers
  to a single XLA computation. Three lowerings share one calling convention:

  - the **dense** program (the common case: integer-valued keys whose
    packed range fits a bounded table) maps each row's key tuple straight
    to a dense lexicographic slot — NO row sort at all. Two tiers share
    the program and are chosen inside it from the traced key range: a
    range of at most 128 slots (one lane tile) reduces by blocked masked
    passes over the rows (``_tile_tables``: no n-sized operand per
    aggregate, float sums as a pairwise tree over block sums); a wider
    range by one ``jax.ops.segment_*`` scatter per aggregate member, block
    of rows by block (``_scatter_tables``; members are separate 1-D
    operands — stacked as ``(n, C)`` their minor dimension pads to 128
    lanes on the TPU). Table→group compaction is gather-based
    (``searchsorted`` over the presence prefix-sum), because gathers are
    fast on every backend while scatters are not.
  - the **sorted** program (arbitrary float keys, and any plan containing
    ``count_distinct``/``sum_distinct``, which need sorted-run counting)
    does an on-device lexicographic sort (``jax.lax.sort`` over null-flag/
    value key components with a row-index tiebreaker, exactly mirroring
    the host ``_group_plan`` lexsort) and reduces over the discovered
    segment boundaries.
  - the **ordered** program, where the dense one cannot hold the key range:
    one integer key over more rows than the exact threshold, stored in
    key order (a fact table grouped by its parent's key, TPC-H's ``GROUP
    BY l_orderkey``): its runs are its groups, reduced by segmented scans
    with no sort, gather or scatter, the result left in the input's slots
    under a mask. The order is read once a plan struct and size
    (``grouped.order``) and checked again inside every run; keys out of
    order take the sorted program (``grouped.order_miss``).

  The only dynamic quantity — the group count (plus the dense path's
  "did the range fit" verdict) — leaves the device as ONE scalar sync at
  the very end; outputs are computed at static length and sliced on the
  way out. A dense-range miss costs one extra sync (the verdict) before
  the sorted program runs. What surrounds a plan is one launch each, not
  one per column: the inputs' pad (``_padded``), the outputs' slice
  (``compiler._unpad_tree``), a sort's payload gather (``_take_tree``).

* **Plan-keyed jit cache.** Programs cache under a structural key (key
  dtypes, aggregate set with value-column slots, engine dtype tag) in a
  bounded LRU, with the same shape-bucketed row padding as the pipeline
  compiler (``bucket_size``/``pad_rows`` are imported from it), so repeated
  SQL ``GROUP BY`` queries and different-length CSV loads replay an
  already-compiled program: ``grouped.compile`` counts traces,
  ``grouped.hit`` counts replays, ``grouped.fallback`` counts host-path
  bailouts, ``grouped.dense_miss`` counts range-overflow reroutes.

* **Mask-weighted semantics identical to the host path.** Masked-out rows
  carry zero weight in every reduction; NaN keys form one null group that
  sorts first (Spark's NULLS FIRST, like the host ``_key_parts``); NaN
  values are skipped by aggregates (SQL semantics) with the same
  empty→NULL and n<2→NULL variance rules ``_np_agg`` implements.

``Frame.sort`` rides the same engine: on accelerators the permutation is
a pure-device ``lax.sort`` program; on XLA:CPU — whose sort lowers to a
scalar comparator loop ~5x slower than numpy's — the *plan* (the
permutation) comes from a host lexsort over just the key columns (one
batched pull) while the payload gather stays device-side ``jnp.take``,
the same "plan on host, materialize on device" split as ``Frame.join``.
``distinct``/``drop_duplicates`` use the sorted program's boundary
discovery and keep first-occurrence output order.

The compilable surface: numeric/bool 1-D key columns and the aggregate
family count/sum/avg/min/max/variance/stddev (sample + population),
first/last (with ignoreNulls), count_distinct, sum_distinct. Everything
else — string keys, host-object aggregates (``collect_list``,
``percentile_approx``, ``median``, the two-column family), grouped-map
UDFs — returns ``None`` here and the caller takes the legacy numpy path
unchanged. ``config.grouped_exec`` (session conf
``spark.groupedExec.enabled``, default on) gates the whole module; off
restores the exact seed behavior.

The module is deliberately numpy-free outside the marked host-fallback
region at the bottom (``scripts/check_segments_np.py`` enforces this):
everything between frame input and the final group-count sync must stay
on device, except the explicitly-host plans (string-payload gathers, the
CPU-backend sort permutation).
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..config import config, float_dtype, int_dtype
from ..utils import faults as _faults
from ..utils import observability as _obs
from ..utils.profiling import counters
from .compiler import (_unpad_tree, bucket_size, dtype_tag, pad_rows,
                       plan_namespace_tag, result_bucket)

logger = logging.getLogger("sparkdq4ml_tpu.ops.segments")

__all__ = [
    "DEVICE_AGG_FNS", "agg_lowerable", "try_device", "grouped_agg",
    "device_sort", "device_unique", "clear_cache", "cache_len",
]


def try_device(op: str, thunk):
    """THE fallback protocol for every device-path entry (grouped agg,
    sort, distinct, dropDuplicates): run ``thunk`` when grouped execution
    is enabled; an ineligible plan (``None``) or any internal failure
    yields ``None`` with a ``grouped.fallback`` increment, and the caller
    takes its legacy host path — the optimization layer must never
    change results. Centralized so the protocol (counter, logging,
    exception policy) lives in exactly one place.

    Executions serialize on ``_EXEC_LOCK`` — the grouped analogue of the
    pipeline compiler's flush lock: without it, two threads racing the
    same plan key would both trace (one compile wasted) and the
    compile-delta heuristic behind ``grouped.compile``/``grouped.hit``
    attribution would cross-label their counters and span verdicts.

    Degradation ladder (ISSUE 11): a DEVICE fault in the segment-reduce
    program — a real ``XlaRuntimeError`` at the group-count sync, or an
    injected ``grouped_flush`` fault — degrades THIS op one level to the
    host-numpy lowering, recorded as a ``recovery.fallback`` event (site
    ``grouped_flush``, rung ``host``) + ``grouped.fault_fallback``; the
    query lives. No fault plan installed = one ``is None`` check."""
    if not config.grouped_exec:
        return None
    try:
        with _EXEC_LOCK:
            _faults.inject("grouped_flush")
            out = thunk()
    except jax.errors.JaxRuntimeError as e:
        from ..utils.recovery import RECOVERY_LOG

        RECOVERY_LOG.record(
            "grouped_flush", "fallback", rung="host",
            cause=f"{type(e).__name__}: {e}",
            detail=f"device {op} degraded to the host-numpy lowering")
        counters.increment("grouped.fault_fallback")
        out = None
    except Exception as e:
        logger.debug("device %s fell back to host: %s", op, e)
        out = None
    if out is None:
        counters.increment("grouped.fallback")
    return out

def _record_grouped_stats(key: str, rows_in: int, rows_out: int,
                          wall_ms: float, compiles: int,
                          host_syncs: int,
                          card_key: Optional[str] = None) -> None:
    """Plan-stats observatory hand-off for the grouped engine: the group
    count is already host-known (the engine's one counted sync), so both
    the flush digest AND the rows-in→groups-out selectivity record
    directly — no deferred drain. ``card_key`` additionally records the
    observed OUTPUT CARDINALITY under a query-addressable name+dtype key
    (:func:`cardinality_history_key`) — the aggregate/distinct
    ``est_rows`` evidence ROADMAP item 4 named as headroom (only filters
    carried selectivity history before). Called only when
    ``spark.stats.enabled``; failures never take a flush down."""
    from ..utils import statstore as _stats

    try:
        _stats.STORE.record_flush(key, "grouped", wall_ms=wall_ms,
                                  compiled=compiles > 0,
                                  host_syncs=host_syncs)
        if rows_out >= 0:
            _stats.STORE.record_rows(key, "grouped", rows_in, rows_out)
            if card_key is not None:
                _stats.STORE.record_rows(card_key, "cardinality",
                                         rows_in, rows_out)
    except Exception:
        logger.debug("stats hand-off failed", exc_info=True)


def cardinality_history_key(op: str, names, arrs) -> Optional[str]:
    """Query-addressable output-cardinality key: ``op`` (``g`` group-by /
    ``d`` distinct) + the SORTED key column names with their device
    dtypes + the engine dtype tag. Name-addressed (unlike the structural
    plan keys) so EXPLAIN can rebuild the same key from a parsed query's
    GROUP BY / DISTINCT list against the catalog frame — zero execution.
    Like the filter-selectivity entries, cardinality is treated as a
    data property: the same key names/dtypes on two views share one
    entry (accepted estimation noise; the estimate is advisory). None
    when any column is missing or host-typed (those plans fall back and
    record nothing)."""
    parts = []
    for name, arr in sorted(zip(names, arrs), key=lambda p: p[0]):
        if arr is None or _is_host_col(arr):
            return None
        parts.append(f"{name}:{_col_kind_spec(arr)}")
    if not parts:
        return None
    return f"card|{dtype_tag()}|{op}|" + ",".join(parts)


# Aggregates this engine lowers to segment reductions. The names mirror
# frame.aggregates._AGGS (post `mean`→`avg` normalization).
DEVICE_AGG_FNS = frozenset({
    "count", "sum", "avg", "min", "max", "stddev", "variance",
    "stddev_pop", "var_pop", "first", "last", "count_distinct",
    "sum_distinct",
})

_DISTINCT_FNS = frozenset({"count_distinct", "sum_distinct"})


def agg_lowerable(agg) -> bool:
    """Structural eligibility of ONE AggExpr for this engine — shared by
    the executor (:func:`grouped_agg`) and the SQL plan-summary marker
    (``sql.parser``), so the ``SegmentedAggregate`` rendering can never
    drift from what actually lowers. Column dtypes are checked later at
    bind time; this is the fn-shape predicate only."""
    return (agg.fn in DEVICE_AGG_FNS and agg.column2 is None
            and agg.param is None)

# Dense-table ceiling: the packed key range must fit min(this, 2*bucket)
# slots or the plan reroutes to the sorted program. 2^17 keeps the table
# comfortably cache/VMEM-sized while covering the 100k-group regime.
_DENSE_MAX = 1 << 17
# Small-table tier of the dense lowering: a packed key range that fits one
# lane tile of slots reduces by blocked masked passes over the rows
# (_tile_tables) instead of an S-slot scatter — no n-sized operand per
# aggregate, whatever the row count.
_TILE = 128
_TILE_CHUNK = 8          # slots reduced per pass over the rows
_TILE_BLOCK = 1 << 21    # rows per block of a pass
_SCATTER_BLOCK = 1 << 22  # rows per block of the S-slot scatters


# ---------------------------------------------------------------------------
# Plan cache (same bounded-LRU discipline as ops/compiler.py)
# ---------------------------------------------------------------------------

_CACHE: "OrderedDict[str, object]" = OrderedDict()
#: Per-plan replay stats, keyed like _CACHE (observability.CACHES /
#: EXPLAIN ANALYZE per-program lines); mutated under _CACHE_LOCK only.
_PLAN_STATS: dict[str, dict] = {}
_CACHE_LOCK = threading.Lock()
# Serializes device-path executions (plan fetch → program call → counter
# attribution) across threads; see try_device. RLock: a thunk may itself
# re-enter try_device via a nested frame op.
_EXEC_LOCK = threading.RLock()


def clear_cache() -> None:
    """Drop every compiled grouped/sort/unique plan (tests; conf flips)."""
    with _CACHE_LOCK:
        _CACHE.clear()
        _PLAN_STATS.clear()
    with _ORDER_LOCK:
        _ORDER.clear()


def cache_len() -> int:
    with _CACHE_LOCK:
        return len(_CACHE)


def abstract_specs(tree):
    """Pytree of abstract call specs: array-like leaves (anything with
    ``shape``+``dtype``) become ``jax.ShapeDtypeStruct``; host scalars
    pass through. Shape/dtype metadata only — never a device read.
    Shared by every plan-cache producer that records an example calling
    convention for the program auditor (``observability.ProgramHandle``)."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") and hasattr(a, "dtype") else a, tree)


class _PlanEntry:
    """One cached grouped/sort/unique program: the counted jitted entry
    plus the UN-counted trace body and the abstract example calling
    convention recorded on first execution — the re-trace surface the
    program auditor enumerates (it must be able to ``make_jaxpr`` the
    plan without bumping ``grouped.compile`` or the replay stats)."""

    __slots__ = ("fn", "trace_body", "example", "shape_sigs", "mesh",
                 "key", "stats_key")

    def __init__(self, raw, mesh=None):
        def scoped(*args):
            # every operation of a grouped/sort/unique program carries
            # dq.grouped in its op metadata (the layer's name in a trace)
            with _obs.scope("grouped"):
                return raw(*args)

        self.trace_body = scoped
        self.mesh = mesh
        # full cache key (namespace-prefixed) — set by _cached_plan;
        # the cost observatory's join handle (flush spans carry it)
        self.key = ""
        # the statstore key this plan's flushes record under (grouped
        # aggregation keys stats by struct, "G|...", across the
        # dense/sorted lowerings) — the cost observatory joins wall
        # history through it; set at the execution sites, "" until the
        # plan has run under stats
        self.stats_key = ""

        def counted(*args):
            # Runs at trace time only → counts XLA compiles (the single
            # home of the increment the four program builders shared).
            counters.increment("grouped.compile")
            return scoped(*args)

        jitted = jax.jit(counted)
        if mesh is not None:
            # sharded programs (the cross-shard merge collective)
            # dispatch-to-completion under the process-wide collective
            # lock — the PR-6 overlapping-psum deadlock discipline
            from ..parallel.mesh import serialize_collectives

            jitted = serialize_collectives(jitted, mesh)
        self.fn = jitted
        self.example = None
        self.shape_sigs: set = set()

    def __call__(self, *args):
        if self.example is None:
            self.example = abstract_specs(args)
        # distinct shape signatures served → the retrace detector's
        # expected compile count (cheap: leaf-shape tuple, no tree_map
        # allocation; grouped dispatch already pays one host sync)
        self.shape_sigs.add(
            tuple(a.shape for a in jax.tree_util.tree_leaves(args)
                  if hasattr(a, "shape")))
        return self.fn(*args)


def _cached_plan(key: str, build, mesh=None):
    # Namespace prefix (ops/compiler.plan_namespace): empty in the shared
    # process-wide mode; the serving layer's isolated-cache mode salts it
    # per tenant so both plan-cache engines partition together.
    key = plan_namespace_tag() + key
    with _CACHE_LOCK:
        fn = _CACHE.get(key)
        if fn is not None:
            _CACHE.move_to_end(key)
            _PLAN_STATS.setdefault(key, {"hits": 0, "builds": 0})[
                "hits"] += 1
            return fn
    fn = _PlanEntry(build(), mesh=mesh)
    fn.key = key
    with _CACHE_LOCK:
        # Insert-if-absent (same rule as the pipeline cache): a build race
        # keeps the first inserted program so replay stats stay coherent.
        existing = _CACHE.get(key)
        if existing is not None:
            _CACHE.move_to_end(key)
            _PLAN_STATS.setdefault(key, {"hits": 0, "builds": 0})[
                "hits"] += 1
            return existing
        _CACHE[key] = fn
        _PLAN_STATS.setdefault(key, {"hits": 0, "builds": 0})["builds"] += 1
        while len(_CACHE) > int(config.pipeline_cache_size):
            evicted, _ = _CACHE.popitem(last=False)
            _PLAN_STATS.pop(evicted, None)
            counters.increment("grouped.evict")
    return fn


def cache_stats() -> dict:
    """Registry callback (observability.CACHES): size/capacity, the
    grouped.* counters, and one entry per cached program (with its
    stable ``program_key``)."""
    with _CACHE_LOCK:
        entries = [{"key": k[:160], "program_key": k, **dict(v)}
                   for k, v in _PLAN_STATS.items()]
        size = len(_CACHE)
    return {
        "kind": "plan-keyed jit cache (segment-reduction grouped exec)",
        "size": size,
        "capacity": int(config.pipeline_cache_size),
        "hits": counters.get("grouped.hit"),
        "misses": counters.get("grouped.compile"),
        "evictions": counters.get("grouped.evict"),
        "fallbacks": counters.get("grouped.fallback"),
        "dense_misses": counters.get("grouped.dense_miss"),
        "entries": entries,
    }


def _scale_rows(spec, factor: int):
    """Example specs with every array's row axis scaled — every plan in
    this cache pads all its inputs to one shared bucket, so this is "the
    same plan at a later shape bucket". Two factors (x2/x4) give the
    retrace detector a pair of FRESH traces to compare (jax may serve
    the recorded shape from a trace cache predating a config flip)."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            (s.shape[0] * factor,) + tuple(s.shape[1:]), s.dtype)
        if hasattr(s, "shape") and s.shape else s, spec)


def program_handles() -> list:
    """Registry callback (CACHES.register_programs): one traceable
    handle per cached grouped/sort/unique program that has executed."""
    with _CACHE_LOCK:
        items = list(_CACHE.items())
    out = []
    for key, entry in items:
        if entry.example is None:
            continue
        observed = None
        try:
            observed = int(entry.fn._cache_size())
        except Exception:
            pass
        meta = {"expected_traces": max(len(entry.shape_sigs), 1)}
        if observed is not None:
            meta["observed_traces"] = observed
        if entry.stats_key:
            # grouped flushes record wall history under the struct key
            # ("G|..."), not the per-lowering cache key — declare the
            # join handle so the cost observatory's report can find it
            meta["stats_key"] = entry.stats_key
        out.append(_obs.ProgramHandle(
            "grouped", key, entry.trace_body, args=entry.example,
            variants={"bucket": [(_scale_rows(entry.example, 2), {}),
                                 (_scale_rows(entry.example, 4), {})]},
            mesh=entry.mesh,
            guarded=True if entry.mesh is not None else None, meta=meta))
    return out


_obs.CACHES.register("grouped", cache_stats)
_obs.CACHES.register_programs("grouped", program_handles)


# ---------------------------------------------------------------------------
# Column classification (device-side metadata probes; no data movement)
# ---------------------------------------------------------------------------

def _is_host_col(arr) -> bool:
    # object-dtype numpy arrays are the engine's string/host columns; a
    # dtype comparison needs no numpy import (np.dtype('O') == object)
    return getattr(arr, "dtype", None) == object


def _key_kind(arr) -> Optional[str]:
    """Sort/group component kind for a 1-D device column: ``f`` float
    (null-flag + neutralized value, NaN = SQL NULL), ``b`` bool (cast to
    int8, numpy-lexsort parity), ``i`` other numeric. None = ineligible."""
    if _is_host_col(arr):
        return None
    a = jnp.asarray(arr)
    if a.ndim != 1:
        return None
    if jnp.issubdtype(a.dtype, jnp.floating):
        return "f"
    if a.dtype == jnp.bool_:
        return "b"
    if jnp.issubdtype(a.dtype, jnp.integer):
        return "i"
    return None


def _acc_dtype():
    """Float accumulator dtype: the widest the backend canonicalizes
    (float64 under x64 — matching the host path's float64 numpy compute —
    else float32)."""
    return jax.dtypes.canonicalize_dtype(jnp.float64)


def _col_kind_spec(arr) -> str:
    return str(jnp.asarray(arr).dtype)


def _key_components(arr, kind: str):
    """lax.sort operands for one group key, highest priority first — the
    device mirror of ``window._key_parts``: a not-null flag partitions
    nulls from values (flag False sorts first, so nulls lead — Spark's
    NULLS FIRST group order), and the value component is NaN-neutralized
    so the flag alone decides null placement."""
    a = jnp.asarray(arr)
    if kind == "b":
        a = a.astype(jnp.int8)
    if kind == "f":
        null = jnp.isnan(a)
        return [jnp.logical_not(null),
                jnp.where(null, jnp.zeros_like(a), a)]
    return [a]


def _sorted_neq(comps_sorted) -> jnp.ndarray:
    """Adjacent-row "key changed" flags over sorted key components (the
    device ``window._neq``; components are NaN-neutralized upstream)."""
    n = comps_sorted[0].shape[0]
    neq = jnp.zeros((n - 1,), jnp.bool_)
    for c in comps_sorted:
        neq = jnp.logical_or(neq, c[1:] != c[:-1])
    return neq


def _group_scaffold(keys, key_kinds, mask):
    """The shared on-device group-discovery core of the SORTED lowering:
    stable lexicographic sort with invalid rows pushed last, then segment
    ids + boundaries. Returns ``(perm, valid, seg, boundary, groups)``."""
    n = mask.shape[0]
    idx = lax.iota(jnp.int32, n)
    ops = [jnp.logical_not(mask)]
    for k, kind in zip(keys, key_kinds):
        ops.extend(_key_components(k, kind))
    ops.append(idx)
    sorted_ops = lax.sort(tuple(ops), num_keys=len(ops))
    perm = sorted_ops[-1]
    valid = jnp.logical_not(sorted_ops[0])
    if n > 1:
        neq = _sorted_neq(sorted_ops[1:-1])
        boundary = jnp.concatenate(
            [valid[:1], jnp.logical_and(valid[1:], neq)])
    else:
        boundary = valid
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    groups = jnp.sum(boundary.astype(jnp.int32))
    return perm, valid, seg, boundary, groups


# ---------------------------------------------------------------------------
# Dense lowering: pack integer-like keys into one lexicographic slot id
# ---------------------------------------------------------------------------

def _dense_slots(keys, key_kinds, valid, S: int, axis=None):
    """Per-row dense slot ids + the fit verdict.

    Each float key contributes a digit ``0`` for NULL (NaN) else
    ``k - lo + 1``, each int or bool key ``k - lo`` — ascending slot order
    IS the host lexsort's group order (key 1 major, nulls first). Returns
    ``(slots, ok, decoders, total)``:
    ``slots(keys)`` gives the per-row slot ids of any rows (the whole
    columns, or one block of them) from the layout fixed here,
    ``decoders`` rebuilds per-key group values from a slot index and
    ``total`` is the traced size of the packed range (what picks the tile
    tier inside the program).
    ``ok`` is a traced scalar: every float key integer-valued and the
    packed size within ``S``; when False the slot ids are garbage and the
    caller reroutes to the sorted program.

    With ``axis`` (the sharded lowering) the per-shard key extremes and
    fit verdict merge across shards (``pmin``/``pmax``), so every shard
    derives the SAME globally-consistent slot ids — the precondition for
    the cross-shard table merge."""
    acc = _acc_dtype()
    ok = jnp.asarray(True)
    sizes = []                       # traced digit counts, key order
    infos = []                       # (kind, lo_acc, dtype)
    for k, kind in zip(keys, key_kinds):
        a = jnp.asarray(k)
        af = (a.astype(jnp.int8) if kind == "b" else a).astype(acc)
        if kind == "f":
            nonnull = jnp.logical_and(valid, jnp.logical_not(jnp.isnan(af)))
            ok = jnp.logical_and(ok, jnp.all(jnp.where(
                nonnull, af == jnp.round(af), True)))
        else:
            nonnull = valid
        big = jnp.asarray(jnp.inf, acc)
        lo = jnp.min(jnp.where(nonnull, af, big))
        hi = jnp.max(jnp.where(nonnull, af, -big))
        if axis is not None:
            # global key range: ±inf identities of empty shards drop out
            lo = lax.pmin(lo, axis)
            hi = lax.pmax(hi, axis)
            any_nn = jnp.isfinite(lo)
        else:
            any_nn = jnp.any(nonnull)
        lo = jnp.where(any_nn, lo, jnp.zeros((), acc))
        hi = jnp.where(any_nn, hi, jnp.zeros((), acc) - 1)
        if kind == "f":
            size = hi - lo + 2       # +1 digit offset, +1 null slot
        else:
            # an int or bool key is never NULL: no digit is kept for it
            # (two flags of 3 and 2 values pack to 6 slots, not 12)
            size = jnp.maximum(hi - lo + 1, 1)
        sizes.append(size)
        infos.append((kind, lo, a.dtype))
        # digits are computed in the float accumulator: key magnitudes
        # past its exact-integer window (2^53 under x64, 2^24 without)
        # would round and alias distinct keys — reroute instead
        exact = jnp.asarray(2.0 ** (53 if acc == jnp.float64 else 24), acc)
        ok = jnp.logical_and(ok, jnp.abs(lo) < exact)
        ok = jnp.logical_and(ok, jnp.abs(hi) < exact)
    total = sizes[0]
    for s in sizes[1:]:
        total = total * s
    if axis is not None:
        # the integrality verdict is per-shard evidence; the slot ids are
        # only sound when EVERY shard's keys pass (range/size terms are
        # already global via the merged lo/hi)
        ok = lax.pmin(ok.astype(jnp.int32), axis) > 0
    ok = jnp.logical_and(ok, jnp.isfinite(total))
    ok = jnp.logical_and(ok, total <= S)

    stride = jnp.asarray(1.0, acc)
    # build strides minor→major (last key = fastest digit)
    strides = [None] * len(keys)
    for i in range(len(keys) - 1, -1, -1):
        strides[i] = stride
        stride = stride * sizes[i]
    safe = jnp.where(ok, jnp.asarray(1.0, acc), jnp.zeros((), acc))

    def slots(rows):
        slot = jnp.zeros(jnp.shape(rows[0]), jnp.int32)
        for (kind, lo, _dt), st, k in zip(infos, strides, rows):
            a = jnp.asarray(k)
            af = (a.astype(jnp.int8) if kind == "b" else a).astype(acc)
            if kind == "f":
                digit = jnp.where(jnp.isnan(af), jnp.zeros((), acc),
                                  af - lo + 1)
            else:
                digit = af - lo
            # ok=False ⇒ clamp contributions to 0 so the int32 cast
            # can't overflow into UB before the verdict reroutes the plan
            slot = slot + (digit * st * safe).astype(jnp.int32)
        return slot

    def make_decoder(kind, lo, dt, st, size):
        def decode(t_idx):
            tf = t_idx.astype(acc)
            digit = jnp.floor(tf / st) % size
            if kind == "f":
                return jnp.where(digit == 0, jnp.asarray(jnp.nan, acc),
                                 lo + digit - 1).astype(dt)
            val = lo + digit
            if kind == "b":
                return val.astype(jnp.int8).astype(dt)
            return val.astype(dt)
        return decode

    decoders = [make_decoder(kind, lo, dt, st, size)
                for (kind, lo, dt), st, size in zip(infos, strides, sizes)]
    return slots, ok, decoders, total


def _compact_index(present, S: int):
    """Gather-based table compaction: ``comp[j]`` = index of the j-th
    present slot. ``searchsorted`` over the presence prefix-sum is all
    gathers — fast on every backend, unlike an S-sized scatter."""
    cs = jnp.cumsum(present.astype(jnp.int32))
    return jnp.searchsorted(cs, lax.iota(jnp.int32, S) + 1, side="left")


# Per stack of the dense program: how two partials combine, how a member
# scatters into the S-slot table, and (``_stack_identity``) the value a row
# contributes when it is not in the slot being reduced.
_STACK_OPS = {"ai": jnp.add, "af": jnp.add, "mf": jnp.minimum,
              "mi": jnp.minimum, "xi": jnp.maximum}
_STACK_SCATTER = {"ai": jax.ops.segment_sum, "af": jax.ops.segment_sum,
                  "mf": jax.ops.segment_min, "mi": jax.ops.segment_min,
                  "xi": jax.ops.segment_max}
_STACK_MERGE = {"ai": lax.psum, "af": lax.psum, "mf": lax.pmin,
                "mi": lax.pmin, "xi": lax.pmax}


def _stack_identity(stack: str, dtype):
    if stack in ("ai", "af"):
        return jnp.zeros((), dtype)
    if stack == "mf":
        return jnp.asarray(jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if stack == "mi" else info.min, dtype)


def _contribution(member, rows):
    """A member's per-row operand: its value where its gate holds, its
    stack's identity on every other row (masked, NULL)."""
    stack, dtype, gate, value = member
    v = jnp.asarray(value(rows))
    if v.dtype == jnp.bool_:
        v = v.astype(jnp.int8)
    return jnp.where(gate(rows), v.astype(dtype),
                     _stack_identity(stack, dtype))


class _Rows:
    """The rows a reduction reads — the whole columns or one block of
    them: key columns, value columns, validity, and on demand the original
    row index (``idx``) and the slot ids those keys pack to (``seg``, from
    ``slots`` of ``_dense_slots``; invalid rows get ``drop``, a slot no
    table has)."""

    __slots__ = ("keys", "vals", "valid", "_slots", "_drop", "_start")

    def __init__(self, keys, vals, valid, slots, drop: int, start=0):
        self.keys, self.vals, self.valid = keys, vals, valid
        self._slots, self._drop, self._start = slots, drop, start

    @property
    def rows(self) -> int:
        return self.valid.shape[0]

    @property
    def idx(self):
        return self._start + lax.iota(jnp.int32, self.rows)

    @property
    def seg(self):
        return jnp.where(self.valid, self._slots(self.keys), self._drop)

    def block(self, start, size: int):
        """The ``size`` rows from ``start`` (traced or static)."""
        if size == self.rows:
            return self

        def cut(a):
            return lax.dynamic_slice(a, (start,), (size,))

        return _Rows(tuple(cut(k) for k in self.keys),
                     tuple(cut(v) for v in self.vals), cut(self.valid),
                     self._slots, self._drop, start)

    def fold(self, block: int, carry, step):
        """``step(carry, rows)`` over the whole blocks of ``block`` rows in
        turn (one loop), then over the shorter rest."""
        whole = self.rows // block
        if whole:
            carry = lax.fori_loop(
                0, whole, lambda i, c: step(c, self.block(i * block, block)),
                carry)
        if self.rows > whole * block:
            carry = step(carry, self.block(whole * block,
                                           self.rows - whole * block))
        return carry


def _tile_blocks(n: int) -> tuple[int, int]:
    """(rows per block, whole blocks >= 1) of the tile tier's row blocking:
    a block is a power of two of at most ``_TILE_BLOCK`` rows; the rows
    past the last whole block reduce as one short block of their own."""
    block = min(_TILE_BLOCK, 1 << (n.bit_length() - 1))
    return block, n // block


def _pairwise(x, op, identity):
    """Balanced-tree combine of the minor axis (padded to a power of two
    with ``identity``): the rounding error of a float sum grows with log2
    of the length, not with the length."""
    size = 1 << max(x.shape[-1] - 1, 0).bit_length()
    if size > x.shape[-1]:
        x = jnp.concatenate([x, jnp.full(
            x.shape[:-1] + (size - x.shape[-1],), identity, x.dtype)], -1)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = op(x[..., :h], x[..., h:])
    return x[..., 0]


def _tile_tables(plan, rows: _Rows, passes, tile: int, vary):
    """The tile tier's reduction: one ``(tile,)`` table per member of
    ``plan`` (``[(stack, dtype, gate, value)]``, see ``_contribution``),
    with no n-sized operand per aggregate.

    Rows are read in blocks of ``_TILE_BLOCK``. A pass reduces
    ``_TILE_CHUNK`` slots at once: every (slot, member) pair is one operand
    of ONE variadic reduce over the block, whose elementwise producer
    (``where(seg == slot, contribution, identity)``, from the block's slice
    of the program's own parameters) fuses into it — so a pass reads each
    input column once and writes one partial per (slot, member) and block,
    and nothing n-sized is ever built. The block partials then combine
    pairwise. A float sum is therefore a balanced tree over block sums of
    at most 2^21 terms — a few float32 ulps at 1.8e8 rows (1e-7 read on the
    chip), where one running total would drift; integer members are exact.
    ``passes`` (traced) is how many chunks the packed key range spans: a
    6-slot range costs one pass over the rows, a 128-slot range sixteen."""
    n = rows.rows
    block, blocks = _tile_blocks(n)
    whole = block * blocks
    stacks = [m[0] for m in plan]
    dtypes = [m[1] for m in plan]
    ident = [_stack_identity(st, dt) for st, dt in zip(stacks, dtypes)]
    ops = [_STACK_OPS[st] for st in stacks] * _TILE_CHUNK
    inits = tuple(ident) * _TILE_CHUNK
    # partials travel as one (chunk * members-of-the-kind,) vector per
    # (stack, dtype): a handful of small arrays a block, not one scalar
    # per operand
    kinds = list(dict.fromkeys(zip(stacks, dtypes)))
    of_kind = [[c for c, k in enumerate(zip(stacks, dtypes)) if k == kind]
               for kind in kinds]

    def combine(xs, ys):
        return tuple(op(x, y) for op, x, y in zip(ops, xs, ys))

    def partials(view, base):
        """The block's (chunk, member) partials, grouped by kind."""
        members = [_contribution(m, view) for m in plan]
        seg = view.seg
        operands = []
        for t in range(_TILE_CHUNK):
            hit = seg == base + t
            operands.extend(jnp.where(hit, m, e)
                            for m, e in zip(members, ident))
        part = lax.reduce(tuple(operands), inits, combine, (0,))
        return tuple(jnp.stack([part[t * len(plan) + c]
                                for t in range(_TILE_CHUNK) for c in cs])
                     for cs in of_kind)

    def one_pass(j, tables):
        base = j * _TILE_CHUNK
        _, per_block = lax.scan(
            lambda i, _: (i + 1, partials(rows.block(i * block, block),
                                          base)),
            jnp.zeros((), jnp.int32), None, length=blocks)
        got = [_pairwise(p.T, _STACK_OPS[st], _stack_identity(st, dt))
               for p, (st, dt) in zip(per_block, kinds)]
        if n > whole:
            rest = partials(rows.block(whole, n - whole), base)
            got = [_STACK_OPS[st](g, r)
                   for (st, _), g, r in zip(kinds, got, rest)]
        out = list(tables)
        for cs, g in zip(of_kind, got):
            g = g.reshape(_TILE_CHUNK, len(cs))
            for k, c in enumerate(cs):
                out[c] = lax.dynamic_update_slice(out[c], g[:, k], (base,))
        return tuple(out)

    empty = tuple(vary(jnp.full((tile,), e, e.dtype)) for e in ident)
    return lax.fori_loop(0, passes, one_pass, empty)


def _scatter_tables(plan, rows: _Rows, S: int, vary):
    """The scatter tier's reduction: one ``(S,)`` table per member of
    ``plan``, one scatter per member. Members are separate 1-D operands,
    built where they are reduced: stacked as ``(n, C)`` their minor
    dimension pads to 128 lanes on the TPU — 512 B a row, 61 GB at 1.2e8
    rows. Block by block too: a scatter cannot fuse its update's producer,
    so a member exists as a buffer while it scatters — of one block of
    rows, not of n."""
    def step(tables, view):
        seg = view.seg
        return tuple(_STACK_OPS[m[0]](t, _STACK_SCATTER[m[0]](
            _contribution(m, view), seg, num_segments=S))
            for m, t in zip(plan, tables))

    return rows.fold(_SCATTER_BLOCK, tuple(
        vary(jnp.full((S,), _stack_identity(st, dt), dt))
        for st, dt, _, _ in plan), step)


def _build_dense_agg_program(key_kinds, agg_ops, val_kinds, S: int,
                             axis=None, world: int = 1):
    """The sort-free grouped lowering (see module docstring): dense slot
    ids, one reduction per member, gather compaction. Two tiers share the
    program and are chosen inside it from the traced key range: the tile
    tier (``_tile_tables``) where the packed range fits ``_TILE`` slots,
    S-slot scatters above.

    Integer quantities — counts, integer sums, min/max over int columns,
    and the first/last row indices — reduce in INTEGER stacks: the float
    accumulator is float32 when x64 is off, and routing ints through it
    would silently round past 2^24 (host parity demands exact ints).

    With ``axis``/``world`` (the row-sharded lowering, arxiv 2112.09017
    reduction pattern) the SAME body runs per shard and the slot tables
    merge with ONE collective per stack — ``psum`` for the additive
    stacks (counts, sums — and with them the decomposable avg/variance
    (sum, count, Σ(v-μ)²) partials), ``pmin``/``pmax`` for the min/max
    stacks. ``first``/``last`` are not in the sharded surface (their
    row-index picks are shard-local); the caller gathers those plans."""
    acc = _acc_dtype()
    wide = jax.dtypes.canonicalize_dtype(jnp.int64)
    if axis is not None and any(fn in ("first", "last")
                                for fn, _, _ in agg_ops):
        raise AssertionError("first/last are not sharded-lowerable")
    def nonnull(rows, s_i):
        if val_kinds[s_i] != "f":
            return rows.valid
        return jnp.logical_and(
            rows.valid, jnp.logical_not(jnp.isnan(rows.vals[s_i])))

    def member_plan(n):
        """The members of the reduction, by domain (int/float) and
        combiner: ``{name: (stack, dtype, gate, value)}`` — a row
        contributes ``value`` where ``gate`` holds (``_contribution``).
        Counts and row indices are bounded by the STATIC n, so whenever n
        sits inside the accumulator's exact-integer window (2^53 / 2^24)
        they ride the float stacks exactly; the integer stacks exist for
        unbounded int VALUES (sums, min/max), which must never round."""
        plan: dict = {}

        def want(stack, name, dtype, gate, value):
            plan.setdefault(name, (stack, dtype, gate, value))

        def valid(r):
            return r.valid

        # counts/indices are bounded by the GLOBAL row count (n per shard
        # × world shards) — the exactness window must hold for the merged
        # totals, not just one shard's partials
        small_n = n * world < (1 << (53 if acc == jnp.float64 else 24))
        cstk, cdt = ("af", acc) if small_n else ("ai", wide)
        want(cstk, "present", cdt, valid, lambda r: 1)
        for fn, s_i, ig in agg_ops:
            if s_i < 0:
                continue
            is_float = val_kinds[s_i] == "f"

            def nn(r, s_i=s_i):
                return nonnull(r, s_i)

            def v(r, s_i=s_i):
                return r.vals[s_i]

            # every referenced slot carries its non-null count: the
            # empty→NULL rule (all-null float groups) needs it for
            # min/max/first/last too
            want(cstk, f"cnt{s_i}", cdt, nn, lambda r: 1)
            if fn in ("sum", "avg", "stddev", "variance", "stddev_pop",
                      "var_pop"):
                if is_float:
                    want("af", f"sum{s_i}", acc, nn, v)
                else:
                    want("ai", f"sum{s_i}", wide, valid, v)
            elif fn in ("min", "max"):
                if is_float:     # max rides the min stack via negation
                    want("mf", f"{fn}{s_i}", acc, nn,
                         v if fn == "min" else lambda r, v=v: -v(r))
                else:
                    want("mi" if fn == "min" else "xi", f"{fn}{s_i}", wide,
                         valid, v)
            elif fn in ("first", "last"):
                tag = "fst" if fn == "first" else "lst"
                gate = nn if ig else valid
                if small_n:      # last rides the min stack via negation
                    want("mf", f"{tag}{s_i}{ig}", acc, gate,
                         (lambda r: r.idx) if fn == "first"
                         else (lambda r: -r.idx))
                else:
                    want("mi" if fn == "first" else "xi",
                         f"{tag}{s_i}{ig}", wide, gate, lambda r: r.idx)
        return plan

    def program(keys, vals, mask):
        n = mask.shape[0]
        keys = tuple(jnp.asarray(k) for k in keys)
        vals = tuple(jnp.asarray(v) for v in vals)
        with _obs.scope("grouped.slots"):
            slots, ok, decoders, total = _dense_slots(
                keys, key_kinds, mask, S, axis)
            # the tile tier, chosen from the traced key range: a plan
            # whose verdict already failed takes it with no pass at all
            tiled = jnp.logical_and(ok, total <= min(_TILE, S))
            passes = jnp.where(
                tiled, jnp.ceil(total / _TILE_CHUNK), 0).astype(jnp.int32)
            use_tile = jnp.logical_or(tiled, jnp.logical_not(ok))

        rows = _Rows(keys, vals, mask, slots, S)     # invalid → dropped
        plan = member_plan(n)
        tile = min(_TILE, S)

        def vary(x):
            # inside shard_map a loop's carry is per-shard from its first
            # step
            return x if axis is None \
                else lax.pcast(x, (axis,), to="varying")

        def tier(size, reduce):
            """Rows to result columns of static length ``size``: the
            tables of ``size`` slots by ``reduce``, their cross-shard
            merge, the variance pass, compaction and the outputs. The two
            tiers differ in ``reduce`` and in ``size`` only — compacting a
            128-slot table costs nothing, compacting it padded to S slots
            cost 25 ms a job on the chip."""
            def reduce_plan(plan):
                """{name: (size,) table} of {name: member}."""
                with _obs.scope("grouped.reduce"):
                    tables = dict(zip(plan, reduce(list(plan.values()))))
                if axis is not None:
                    # THE cross-shard merge: one collective per populated
                    # stack (additive → psum, min → pmin, max → pmax);
                    # after it every shard holds the identical global slot
                    # tables and the rest of the program computes replicated
                    with _obs.scope("exchange"):
                        for st in _STACK_OPS:
                            names = [k for k, m in plan.items()
                                     if m[0] == st]
                            if names:
                                merged = _STACK_MERGE[st](jnp.stack(
                                    [tables[k] for k in names]), axis)
                                tables.update(zip(names, merged))
                return tables

            tables = reduce_plan(plan)
            table = tables.__getitem__

            present = table("present") > 0
            groups = jnp.sum(present.astype(jnp.int32))

            def fsum(s_i):
                s = table(f"sum{s_i}")
                return s if val_kinds[s_i] == "f" else s.astype(acc)

            # ---- variance family second pass (only when requested): the
            # same two-pass Σ(v-μ)² the host path computes; decomposable, so
            # the per-shard partials (μ already global from the merged
            # sum/count tables) psum into the global second moment
            need_var = [s_i for fn, s_i, _ in agg_ops
                        if fn in ("stddev", "variance", "stddev_pop",
                                  "var_pop")]
            if need_var:
                def squared_gap(r, s_i):
                    mu = fsum(s_i) / table(f"cnt{s_i}").astype(acc)
                    d = r.vals[s_i].astype(acc) - jnp.take(
                        mu, jnp.clip(r.seg, 0, size - 1))
                    return d * d

                ssd = reduce_plan({
                    s_i: ("af", acc, lambda r, s_i=s_i: nonnull(r, s_i),
                          lambda r, s_i=s_i: squared_gap(r, s_i))
                    for s_i in dict.fromkeys(need_var)})

            with _obs.scope("grouped.compact"):
                comp = _compact_index(present, size)
                key_outs = tuple(dec(comp) for dec in decoders)
            nan = jnp.asarray(jnp.nan, acc)

            agg_outs = []
            for fn, s_i, ig in agg_ops:
                if fn == "count" and s_i < 0:
                    agg_outs.append(jnp.take(table("present"), comp)
                                    .astype(int_dtype()))
                    continue
                vs = vals[s_i]
                cnt = jnp.take(table(f"cnt{s_i}"), comp)
                if fn == "count":
                    agg_outs.append(cnt.astype(int_dtype()))
                elif fn == "sum":
                    s = jnp.take(table(f"sum{s_i}"), comp)
                    if val_kinds[s_i] != "f":
                        agg_outs.append(s.astype(int_dtype()))
                    else:
                        agg_outs.append(jnp.where(cnt > 0, s, nan)
                                        .astype(vs.dtype))
                elif fn == "avg":
                    agg_outs.append((jnp.take(fsum(s_i), comp)
                                     / cnt.astype(acc)).astype(float_dtype()))
                elif fn in ("stddev", "variance", "stddev_pop", "var_pop"):
                    sd = jnp.take(ssd[s_i], comp)
                    cf = cnt.astype(acc)
                    if fn in ("stddev", "variance"):
                        var = jnp.where(cnt > 1,
                                        sd / jnp.maximum(cf - 1, 1), nan)
                    else:
                        var = jnp.where(cnt > 0, sd / jnp.maximum(cf, 1),
                                        nan)
                    out = var if fn in ("variance", "var_pop") \
                        else jnp.sqrt(var)
                    agg_outs.append(out.astype(float_dtype()))
                elif fn in ("min", "max"):
                    m = jnp.take(table(f"{fn}{s_i}"), comp)
                    if val_kinds[s_i] == "f":
                        if fn == "max":
                            m = -m
                        agg_outs.append(jnp.where(cnt > 0, m, nan)
                                        .astype(vs.dtype))
                    else:
                        agg_outs.append(m.astype(vs.dtype))
                elif fn in ("first", "last"):
                    tag = "fst" if fn == "first" else "lst"
                    pos = jnp.take(table(f"{tag}{s_i}{ig}"), comp)
                    if fn == "last" and plan[f"{tag}{s_i}{ig}"][0] == "mf":
                        pos = -pos         # small-n: last rode the min stack
                    pi = jnp.clip(pos, 0, n - 1).astype(jnp.int32)
                    picked = jnp.take(vs, pi)
                    if ig and val_kinds[s_i] == "f":
                        agg_outs.append(jnp.where(
                            cnt > 0, picked, jnp.asarray(jnp.nan, vs.dtype)))
                    else:
                        agg_outs.append(picked)
                else:  # pragma: no cover - distinct aggs never lower dense
                    raise AssertionError(fn)
            return key_outs, tuple(agg_outs), groups

        def padded(outs):
            """The tile tier's columns at the program's static length."""
            key_outs, agg_outs, groups = outs

            def grow(a):
                return jnp.concatenate([a, jnp.zeros((S - tile,), a.dtype)])

            if S == tile:
                return outs
            return (tuple(grow(a) for a in key_outs),
                    tuple(grow(a) for a in agg_outs), groups)

        key_outs, agg_outs, groups = lax.cond(
            use_tile,
            lambda: padded(tier(tile, lambda members: _tile_tables(
                members, rows, passes, tile, vary))),
            lambda: tier(S, lambda members: _scatter_tables(
                members, rows, S, vary)))
        return key_outs, agg_outs, groups, ok, tiled

    return lambda: program


def _build_sharded_dense_agg_program(mesh, key_kinds, agg_ops, val_kinds,
                                     S: int):
    """The row-sharded dense lowering: the dense program body runs per
    shard with globally-consistent slot ids, and the slot tables merge
    with one collective per stack (see ``_build_dense_agg_program``).
    Outputs are replicated — every shard computes the identical final
    tables, so the group-count/fit-verdict sync stays ONE host read."""
    from jax.sharding import PartitionSpec as _P

    from ..parallel.mesh import DATA_AXIS, shard_map

    def build():
        program = _build_dense_agg_program(
            key_kinds, agg_ops, val_kinds, S, axis=DATA_AXIS,
            world=int(mesh.devices.size))()
        pd = _P(DATA_AXIS)
        # dqlint: ok(collective-guard): dispatch routes through
        # _PlanEntry(mesh=...), which wraps the jitted entry in
        # serialize_collectives — see _cached_plan.
        return shard_map(program, mesh=mesh, in_specs=(pd, pd, pd),
                         out_specs=_P())

    return build


# ---------------------------------------------------------------------------
# Sharded distinct: hash-partition all-to-all exchange + local unique
# ---------------------------------------------------------------------------

def _mix_hash(h, arr, kind):
    """Fold one key column into the per-row shard hash. Null-safe and
    sign-of-zero-safe like the host ``parallel.shard.hash_partition``:
    NaN (the engine's NULL) folds to one hash class, ``-0.0`` onto
    ``0.0`` (they compare equal, so they must exchange together)."""
    a = jnp.asarray(arr)
    prime = jnp.uint32(0x01000193)
    if kind == "f":
        nulls = jnp.isnan(a)
        z = jnp.where(a == 0, jnp.zeros_like(a), a)
        z = jnp.where(nulls, jnp.zeros_like(a), z)
        if a.dtype.itemsize == 8:
            bits = lax.bitcast_convert_type(z, jnp.int64)
            c = (bits & 0xFFFFFFFF).astype(jnp.uint32) \
                ^ (bits >> 32).astype(jnp.uint32)
        else:
            c = lax.bitcast_convert_type(z, jnp.int32).astype(jnp.uint32)
        h = (h * prime) ^ c
        return (h * prime) ^ nulls.astype(jnp.uint32)
    return (h * prime) ^ a.astype(jnp.uint32)


def _build_sharded_unique_program(mesh, key_kinds):
    """Distinct over a row-sharded frame: every row hash-partitions by
    key to an owner shard, ONE static-shape ``all_to_all`` exchanges the
    (keys, global row index, validity) blocks — each (src, dst) block is
    a full shard bucket with a per-row validity mask, so the plan is
    static whatever the key skew — and each shard runs the local sorted
    unique over its hash class, emitting first-occurrence GLOBAL row
    indices. The host concatenates + sorts the per-shard candidate sets
    (ascending global index IS first-occurrence order) in the engine's
    one counted sync."""
    from jax.sharding import PartitionSpec as _P

    from ..parallel.mesh import DATA_AXIS, shard_map

    D = int(mesh.devices.size)

    def build():
        def program(keys, mask):
            b = mask.shape[0]                       # per-shard slots
            me = lax.axis_index(DATA_AXIS).astype(jnp.int32)
            gidx = me * b + lax.iota(jnp.int32, b)  # global slot index
            h = jnp.full((b,), 0x811C9DC5, jnp.uint32)
            for k, kind in zip(keys, key_kinds):
                h = _mix_hash(h, k, kind)
            t = (h % jnp.uint32(D)).astype(jnp.int32)

            def xchg(blocked):     # (D*b, …): block d → shard d
                # dqlint: ok(collective-guard): dispatch is guarded by
                # _PlanEntry(mesh=...) via serialize_collectives
                with _obs.scope("exchange"):
                    return lax.all_to_all(blocked, DATA_AXIS, split_axis=0,
                                          concat_axis=0, tiled=True)

            def rep(x):            # every destination gets the full rows
                return jnp.broadcast_to(
                    x[None], (D,) + x.shape).reshape((D * b,))

            dest = lax.iota(jnp.int32, D)[:, None]
            send_ok = jnp.logical_and(mask[None, :], t[None, :] == dest)
            rmask = xchg(send_ok.reshape(D * b))
            rkeys = [xchg(rep(jnp.asarray(k))) for k in keys]
            rgidx = xchg(rep(gidx))

            n2 = D * b             # received rows (sparse validity)
            perm, valid, seg, _boundary, groups = _group_scaffold(
                rkeys, key_kinds, rmask)
            sorted_g = jnp.take(rgidx, perm)
            big = jnp.asarray(n2, jnp.int32)        # > any global index
            first_g = jax.ops.segment_min(
                jnp.where(valid, sorted_g, big), seg, num_segments=n2)
            cand = lax.sort((first_g,), num_keys=1)[0]
            # dqlint: ok(collective-guard): dispatch is guarded by
            # _PlanEntry(mesh=...) via serialize_collectives
            total = lax.psum(groups, DATA_AXIS)
            return cand, groups[None], total

        pd = _P(DATA_AXIS)
        # dqlint: ok(collective-guard): dispatch routes through
        # _PlanEntry(mesh=...), which wraps the jitted entry in
        # serialize_collectives — see _cached_plan.
        return shard_map(program, mesh=mesh, in_specs=(pd, pd),
                         out_specs=(pd, pd, _P()))

    return build


# ---------------------------------------------------------------------------
# Sorted lowering (arbitrary keys; distinct aggregates)
# ---------------------------------------------------------------------------

def _distinct_runs(seg, v, eligible, n):
    """Sorted-run scaffolding for count/sum DISTINCT: re-sort (segment,
    value) among eligible rows (ineligible ⇒ segment id n, dropped by the
    out-of-range rule of ``segment_sum``), then flag the first row of
    every (segment, value) run."""
    seg_k = jnp.where(eligible, seg, n)
    val_k = jnp.where(eligible, v, jnp.zeros_like(v))
    s2, v2 = lax.sort((seg_k, val_k), num_keys=2)
    live = s2 < n
    if n > 1:
        change = jnp.logical_or(s2[1:] != s2[:-1], v2[1:] != v2[:-1])
        first = jnp.concatenate([live[:1], jnp.logical_and(live[1:], change)])
    else:
        first = live
    return s2, v2, first


def _build_sorted_agg_program(key_kinds, agg_ops, val_kinds):
    """The sorted grouped lowering. ``agg_ops``: tuple of ``(fn, slot,
    ignore_nulls)`` — ``slot`` indexes the deduplicated value-column
    tuple, -1 for ``count(*)``."""
    acc = _acc_dtype()

    def program(keys, vals, mask):
        n = mask.shape[0]
        idx = lax.iota(jnp.int32, n)
        perm, valid, seg, boundary, groups = _group_scaffold(
            keys, key_kinds, mask)
        w_int = valid.astype(jnp.int32)
        big = jnp.asarray(n, jnp.int32)

        # first sorted position of each group → original row of the
        # group's first (stable order) member; keys gather from there
        first_pos = jax.ops.segment_min(jnp.where(valid, idx, big), seg,
                                        num_segments=n)
        fp = jnp.clip(first_pos, 0, n - 1)
        orig_first = jnp.take(perm, fp)
        key_outs = tuple(jnp.take(jnp.asarray(k), orig_first) for k in keys)

        last_pos = jax.ops.segment_max(
            jnp.where(valid, idx, jnp.asarray(-1, jnp.int32)), seg,
            num_segments=n)
        lp = jnp.clip(last_pos, 0, n - 1)

        # per-slot sorted values + null masks, computed once and shared
        sorted_vals = {}
        nonnull = {}
        for s_i, v in enumerate(vals):
            vs = jnp.take(jnp.asarray(v), perm)
            sorted_vals[s_i] = vs
            if val_kinds[s_i] == "f":
                nonnull[s_i] = jnp.logical_and(
                    valid, jnp.logical_not(jnp.isnan(vs)))
            else:
                nonnull[s_i] = valid

        nan = jnp.asarray(jnp.nan, acc)

        def seg_sum(x):
            return jax.ops.segment_sum(x, seg, num_segments=n)

        def moments(s_i):
            nn = nonnull[s_i]
            vf = sorted_vals[s_i].astype(acc)
            wz = nn.astype(acc)
            cnt = seg_sum(wz)
            s = seg_sum(jnp.where(nn, vf, jnp.zeros_like(vf)))
            return nn, vf, wz, cnt, s

        agg_outs = []
        for fn, s_i, ignore_nulls in agg_ops:
            if fn == "count" and s_i < 0:                # count(*)
                agg_outs.append(seg_sum(w_int).astype(int_dtype()))
                continue
            nn = nonnull[s_i]
            vs = sorted_vals[s_i]
            if fn == "count":
                agg_outs.append(
                    seg_sum(nn.astype(jnp.int32)).astype(int_dtype()))
            elif fn in ("sum", "avg", "stddev", "variance", "stddev_pop",
                        "var_pop"):
                _, vf, _, cnt, s = moments(s_i)
                if fn == "sum":
                    if val_kinds[s_i] != "f":
                        # integer sums stay exact integers (host parity:
                        # numpy accumulates int64, the frame stores
                        # int_dtype); int columns have no nulls so the
                        # empty→NULL rule can never fire for them
                        wide = jax.dtypes.canonicalize_dtype(jnp.int64)
                        agg_outs.append(jax.ops.segment_sum(
                            jnp.where(valid, vs,
                                      jnp.zeros_like(vs)).astype(wide),
                            seg, num_segments=n).astype(int_dtype()))
                    else:
                        # numpy reductions preserve the column dtype
                        agg_outs.append(jnp.where(
                            cnt > 0, s, nan).astype(vs.dtype))
                elif fn == "avg":
                    # 0/0 → NaN reproduces the empty→NULL rule directly
                    agg_outs.append((s / cnt).astype(float_dtype()))
                else:
                    mu = s / cnt
                    d = jnp.where(nn, vf - jnp.take(mu, seg),
                                  jnp.zeros((), acc))
                    ss = seg_sum(d * d)
                    if fn in ("stddev", "variance"):     # sample, n>1
                        var = jnp.where(cnt > 1,
                                        ss / jnp.maximum(cnt - 1, 1), nan)
                    else:                                # population, n>0
                        var = jnp.where(cnt > 0, ss / jnp.maximum(cnt, 1),
                                        nan)
                    out = var if fn in ("variance", "var_pop") \
                        else jnp.sqrt(var)
                    agg_outs.append(out.astype(float_dtype()))
            elif fn in ("min", "max"):
                red = jax.ops.segment_min if fn == "min" \
                    else jax.ops.segment_max
                if val_kinds[s_i] == "f":
                    fill = jnp.asarray(
                        jnp.inf if fn == "min" else -jnp.inf, vs.dtype)
                    m = red(jnp.where(nn, vs, fill), seg, num_segments=n)
                    cnt = seg_sum(nn.astype(jnp.int32))
                    agg_outs.append(jnp.where(
                        cnt > 0, m, jnp.asarray(jnp.nan, vs.dtype)))
                else:
                    # int/bool columns carry no nulls: every discovered
                    # group has >= 1 contributing row, so the reduction
                    # identity of masked-out rows can never surface
                    vi = vs.astype(jnp.int32) if vs.dtype == jnp.bool_ \
                        else vs
                    info = jnp.iinfo(vi.dtype)
                    fill = jnp.asarray(
                        info.max if fn == "min" else info.min, vi.dtype)
                    m = red(jnp.where(valid, vi, fill), seg,
                            num_segments=n)
                    agg_outs.append(m.astype(vs.dtype))
            elif fn in ("first", "last"):
                if ignore_nulls:
                    pos = (jax.ops.segment_min(
                        jnp.where(nn, idx, big), seg, num_segments=n)
                        if fn == "first" else
                        jax.ops.segment_max(
                            jnp.where(nn, idx, jnp.asarray(-1, jnp.int32)),
                            seg, num_segments=n))
                    has = seg_sum(nn.astype(jnp.int32)) > 0
                    picked = jnp.take(vs, jnp.clip(pos, 0, n - 1))
                    if val_kinds[s_i] == "f":
                        agg_outs.append(jnp.where(
                            has, picked, jnp.asarray(jnp.nan, vs.dtype)))
                    else:
                        # int/bool columns have no nulls: has is always
                        # true for a discovered group
                        agg_outs.append(picked)
                else:
                    agg_outs.append(jnp.take(vs, fp if fn == "first"
                                             else lp))
            elif fn in ("count_distinct", "sum_distinct"):
                # run detection in the column's OWN dtype: the float
                # accumulator is float32 without x64, where distinct
                # large ints would alias before the comparison
                vn = vs.astype(jnp.int8) if vs.dtype == jnp.bool_ else vs
                s2, v2, firstrun = _distinct_runs(seg, vn, nn, n)
                sid = jnp.where(s2 < n, s2, jnp.zeros_like(s2))
                # rows pushed past the live region carry sid 0 but
                # firstrun False / zero weight: they contribute nothing
                if fn == "count_distinct":
                    cd = jax.ops.segment_sum(
                        firstrun.astype(jnp.int32), sid, num_segments=n)
                    agg_outs.append(cd.astype(int_dtype()))
                elif val_kinds[s_i] != "f":
                    wide = jax.dtypes.canonicalize_dtype(jnp.int64)
                    sd = jax.ops.segment_sum(
                        jnp.where(firstrun, v2,
                                  jnp.zeros_like(v2)).astype(wide),
                        sid, num_segments=n)
                    agg_outs.append(sd.astype(int_dtype()))
                else:
                    wrun = jnp.where(firstrun, jnp.ones((), acc),
                                     jnp.zeros((), acc))
                    sd = jax.ops.segment_sum(wrun * v2.astype(acc), sid,
                                             num_segments=n)
                    cd = jax.ops.segment_sum(
                        firstrun.astype(jnp.int32), sid, num_segments=n)
                    agg_outs.append(jnp.where(
                        cd > 0, sd, nan).astype(float_dtype()))
            else:  # pragma: no cover - guarded by the eligibility check
                raise AssertionError(fn)
        return key_outs, tuple(agg_outs), groups

    return lambda: program


# ---------------------------------------------------------------------------
# Ordered lowering (one integer key that arrives in order: runs, no sort)
# ---------------------------------------------------------------------------

#: aggregates the ordered lowering reduces by segmented scans
_RUN_FNS = frozenset({"count", "sum", "avg", "min", "max"})
_RUN_ROW = 128      # lanes of a row of the blocked segmented scan
_ORDER: dict = {}   # plan struct + rows -> the key column's order, last seen
_ORDER_LOCK = threading.Lock()


def _seg_scan(start, members):
    """Inclusive scans of ``members`` — ``(values, op, fill)`` with ``op``
    associative and ``fill`` its identity — that restart wherever
    ``start`` is set, with no sort, gather or scatter: the slots as rows
    of ``_RUN_ROW``, each row scanned by log steps of lane shifts, then the
    rows' totals scanned the same way (one level per 128x fewer slots) and
    carried into the rows that hold no start before a slot."""
    n = start.shape[0]
    rows = -(-n // _RUN_ROW)
    pad = rows * _RUN_ROW - n

    def grid(v, fill):
        return jnp.concatenate(
            [v, jnp.full((pad,), fill, v.dtype)]).reshape(rows, _RUN_ROW)

    def shift(v, s, fill):
        return jnp.concatenate(
            [jnp.full((rows, s), fill, v.dtype), v[:, :_RUN_ROW - s]], axis=1)

    f = grid(start, True)
    vs = [grid(v, fill) for v, _, fill in members]
    s = 1
    while s < _RUN_ROW:
        # (f, v) over (j - 2s, j] from its two halves: a start in the
        # nearer half cuts the farther one off
        vs = [jnp.where(f, v, op(shift(v, s, fill), v))
              for v, (_, op, fill) in zip(vs, members)]
        f = f | shift(f, s, False)
        s *= 2
    if rows > 1:
        totals = _seg_scan(f[:, -1], [(v[:, -1], op, fill) for v, (
            _, op, fill) in zip(vs, members)])
        # what runs into a row from the rows before it
        vs = [jnp.where(f, v, op(jnp.concatenate(
            [jnp.full((1,), fill, v.dtype), t[:-1]])[:, None], v))
            for v, t, (_, op, fill) in zip(vs, totals, members)]
    return [v.reshape(-1)[:n] for v in vs]


def _build_ordered_agg_program(agg_ops, val_kinds):
    """The ordered grouped lowering, for one integer key column that
    arrives in order (a fact table stored in its parent's key order): a
    group is a run of equal keys, every aggregate a segmented scan over
    the runs (:func:`_seg_scan`), read at the run's last slot — no sort,
    no gather, no scatter. The result keeps the input's slots under a mask
    (a run's last slot where the run holds a valid row), in key order, and
    the key column itself is its key (the program returns none); the
    program reports whether the keys were in order, and where they were
    not its result is not used. Same aggregate semantics as the sorted
    lowering: masked rows and NaN values vote nowhere, empty -> NULL."""
    acc = _acc_dtype()
    wide = jax.dtypes.canonicalize_dtype(jnp.int64)

    def program(keys, vals, mask):
        k = jnp.asarray(keys[0])
        change = k[1:] != k[:-1]
        one = jnp.ones((1,), jnp.bool_)
        start = jnp.concatenate([one, change])
        end = jnp.concatenate([change, one])
        members, at = [], {}

        def member(name, v, op, fill):
            if name not in at:
                at[name] = len(members)
                members.append((v, op, jnp.asarray(fill, v.dtype)))
            return at[name]

        # a run votes where it holds a valid row; counts are scanned only
        # where an aggregate reports one (a flag scan moves a byte a slot)
        rows_any = member("rows?", mask, jnp.logical_or, False)
        plan = []
        for fn, s_i, _ in agg_ops:
            if s_i < 0:                                   # count(*)
                plan.append((fn, s_i, member(
                    "rows", mask.astype(jnp.int32), jnp.add, 0), None))
                continue
            v = jnp.asarray(vals[s_i])
            floating = val_kinds[s_i] == "f"
            nn = jnp.logical_and(mask, jnp.logical_not(jnp.isnan(v))) \
                if floating else mask
            if fn in ("count", "avg"):
                cnt = member(f"nn{s_i}" if floating else "rows",
                             nn.astype(jnp.int32), jnp.add, 0)
            else:          # the empty -> NULL rule of a float column only
                cnt = member(f"nn{s_i}?", nn, jnp.logical_or, False) \
                    if floating else None
            if fn == "count":
                plan.append((fn, s_i, cnt, None))
            elif fn in ("sum", "avg"):
                if floating or fn == "avg":
                    x = jnp.where(nn, v.astype(acc), jnp.zeros((), acc))
                    plan.append((fn, s_i, cnt, member(
                        f"sum{s_i}", x, jnp.add, 0)))
                else:
                    x = jnp.where(mask, v, jnp.zeros_like(v)).astype(wide)
                    plan.append((fn, s_i, cnt, member(
                        f"isum{s_i}", x, jnp.add, 0)))
            else:                                         # min / max
                lo = fn == "min"
                vi = v.astype(jnp.int32) if v.dtype == jnp.bool_ else v
                if floating:
                    fill = jnp.inf if lo else -jnp.inf
                else:
                    info = jnp.iinfo(vi.dtype)
                    fill = info.max if lo else info.min
                x = jnp.where(nn, vi, jnp.asarray(fill, vi.dtype))
                plan.append((fn, s_i, cnt, member(
                    f"{fn}{s_i}", x, jnp.minimum if lo else jnp.maximum,
                    fill)))
        runs = _seg_scan(start, members)
        live = jnp.logical_and(end, runs[rows_any])
        nan = jnp.asarray(jnp.nan, acc)
        outs = []
        for fn, s_i, cnt, m in plan:
            if fn == "count":
                outs.append(runs[cnt].astype(int_dtype()))
                continue
            v = jnp.asarray(vals[s_i])
            r = runs[m]
            if fn == "avg":
                outs.append((r / runs[cnt]).astype(float_dtype()))
            elif val_kinds[s_i] != "f":
                outs.append(r.astype(int_dtype() if fn == "sum"
                                     else v.dtype))
            else:
                outs.append(jnp.where(runs[cnt], r, nan).astype(v.dtype))
        # the key column itself is the result's: no copy leaves the program
        return ((), tuple(outs), jnp.sum(live, dtype=jnp.int32), live,
                jnp.all(k[1:] >= k[:-1]))

    return lambda: program


# ---------------------------------------------------------------------------
# Grouped aggregation entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=1)
def _pad_tree(tree, b: int):
    return jax.tree_util.tree_map(
        lambda a: pad_rows(a, b, fresh=False), tree)


def _padded(tree, n: int, b: int):
    """The ``n``-row plan inputs of ``tree`` at ``b`` row slots in ONE
    dispatch (per-array eager pads cost three dispatches each); as they
    are when the bucket IS ``n`` — no copy of an n-row column."""
    if n == b:
        return jax.tree_util.tree_map(jnp.asarray, tree)
    return _pad_tree(tree, b)


def _read_verdict(tree, site: str = "grouped.verdict"):
    """THE blocking read of a grouped/sort plan: its few scalars (group
    count, fit verdict) in one counted frame-boundary sync."""
    counters.increment("frame.host_sync")
    with _obs.host_reading(site) as rd:
        host = jax.device_get(tree)
        rd.done(sum(a.nbytes for a in jax.tree_util.tree_leaves(host)))
    return host


def _run_plan(fn, args, before, sp):
    counters.increment("grouped.rows", int(args[-1].shape[0]))
    out = fn(*args)
    compiled = counters.get("grouped.compile") > before
    # plan_key: the cost-observatory join handle (attribute read, no
    # formatting — the noop contract holds on the disabled no-op span)
    sp.set(cache="compile" if compiled else "hit", plan_key=fn.key)
    if not compiled:
        counters.increment("grouped.hit")
    return out


def grouped_agg(frame, keys, agg_list):
    """Lower ``group_by(keys).agg(agg_list)`` to one device program.

    Returns the aggregated Frame — rows in lexicographic key order with
    the null group first, exactly like the host ``_group_plan`` path — or
    ``None`` when the plan is not device-lowerable (string keys,
    host-object aggregates, empty frame); the caller then takes the
    legacy numpy path and counts ``grouped.fallback``.

    The dense (sort-free) program runs first whenever the plan allows it;
    its fit verdict rides the same scalar sync as the group count, so the
    common case costs exactly ONE host sync. A range miss reroutes to the
    sorted program (one extra sync, ``grouped.dense_miss``).
    """
    from ..frame.frame import Frame

    data = frame._data                    # flush-on-read: pipeline settles
    mask = frame._mask
    n = frame.num_slots
    if n == 0:
        return None
    key_arrs, key_kinds = [], []
    for k in keys:
        arr = data.get(k)
        kind = _key_kind(arr) if arr is not None else None
        if kind is None:
            return None
        key_arrs.append(arr)
        key_kinds.append(kind)

    # value columns dedup into slots; aggregate ops reference slots so the
    # plan key stays structural (names never enter the key)
    slots: dict[str, int] = {}
    val_arrs: list = []
    val_kinds: list = []
    agg_ops = []
    for a in agg_list:
        if not agg_lowerable(a):
            return None
        if a.column is None:
            if a.fn != "count":
                return None
            agg_ops.append(("count", -1, False))
            continue
        arr = data.get(a.column)
        kind = _key_kind(arr) if arr is not None else None
        if kind is None:
            return None
        if a.column not in slots:
            slots[a.column] = len(val_arrs)
            val_arrs.append(arr)
            val_kinds.append(kind)
        agg_ops.append((a.fn, slots[a.column], bool(a.ignore_nulls)))

    struct = "|".join([
        dtype_tag(),
        ",".join(f"{k}:{_col_kind_spec(a)}"
                 for k, a in zip(key_kinds, key_arrs)),
        ",".join(f"{fn}@{s}{'!' if ig else ''}"
                 for fn, s, ig in agg_ops),
        ",".join(f"{k}:{_col_kind_spec(a)}"
                 for k, a in zip(val_kinds, val_arrs)),
    ])

    dense_ok = not any(fn in _DISTINCT_FNS for fn, _, _ in agg_ops)
    # Sharded lowering (frame rows laid out over the mesh): local
    # segment-reduce per shard + ONE cross-shard merge collective. The
    # surface is the dense program's decomposable aggregate set; plans
    # outside it (first/last — shard-local row picks — and the distinct
    # aggregates, which need a global sort) gather one level to the
    # single-device engine.
    shard = getattr(frame, "_shard", None)
    sharded = (shard is not None and dense_ok
               and not any(fn in ("first", "last")
                           for fn, _, _ in agg_ops))
    if shard is not None and not sharded:
        from ..parallel.shard import gather_arrays

        flat = gather_arrays(shard, jnp.asarray(mask, jnp.bool_),
                             *(list(key_arrs) + list(val_arrs)))
        mask = flat[0]
        key_arrs = list(flat[1:1 + len(key_arrs)])
        val_arrs = list(flat[1 + len(key_arrs):])
        shard = None

    b = n if sharded else bucket_size(n)
    args = keys_in, vals_in, mask_in = _padded(
        (tuple(key_arrs), tuple(val_arrs), jnp.asarray(mask, jnp.bool_)),
        n, b)

    S = min(_DENSE_MAX, max(2 * b, 16))
    # the ordered lowering: one integer key, aggregates of the run family,
    # and more rows than the exact threshold (the bucket IS n there: the
    # program takes the slots as they are, whose order it checks)
    run_ok = (not sharded and len(keys) == 1 and key_kinds[0] == "i"
              and b == n and n > int(config.pipeline_exact_threshold)
              and all(fn in _RUN_FNS for fn, _, _ in agg_ops))

    # Plan-stats observatory gate (ONE flag read; disabled = nothing
    # else) — the grouped engine records HOST-KNOWN group counts, so its
    # selectivity evidence needs no deferred drain.
    stats_on = config.stats_enabled
    t_stats = time.perf_counter() if stats_on else 0.0
    c_stats = counters.get("grouped.compile") if stats_on else 0
    syncs = 0
    stats_key = f"G|{shard.tag()}|{struct}" if sharded else f"G|{struct}"
    # Adaptive lowering choice (cost-based optimizer + statstore): a
    # struct whose dense attempts repeatedly overflowed the slot-table
    # range skips straight to the sorted program, saving the doomed
    # dense dispatch AND its extra host sync. Advisory history — the
    # sorted program is bit-identical to the miss-reroute it replaces,
    # and fresh data that would fit again just re-earns its dense path
    # after the history entry evicts.
    skip_dense = False
    if (dense_ok and not sharded and stats_on
            and config.optimizer_enabled):
        from ..utils import statstore as _stats_store

        try:
            if _stats_store.STORE.miss_count(f"GD{S}|{struct}") >= 2:
                skip_dense = True
                counters.increment("optimizer.dense_skip")
        except Exception:
            pass
    # Adaptive lowering re-plan (sql/adaptive.py): the recorded output-
    # cardinality history for THESE key columns estimates the group
    # count; more estimated groups than the dense table has slots means
    # the dense program MUST miss (g groups need g slots), so the
    # doomed dispatch and its extra host sync are skipped for this
    # query — live estimate evidence, where the miss-history skip above
    # needs two recorded failures first. Bit-identical: the sorted
    # program is exactly the reroute a dense miss would have taken.
    if (dense_ok and not sharded and not skip_dense and stats_on
            and config.aqe_enabled):
        from ..sql import adaptive as _aqe
        from ..utils import statstore as _stats_store

        est_g = None
        try:
            ckey = cardinality_history_key("g", keys, key_arrs)
            if ckey is not None:
                est_g = _stats_store.STORE.est_rows(ckey, n)
        except Exception:
            est_g = None
        if est_g is not None and est_g > S \
                and _aqe.guard("grouped-lowering"):
            skip_dense = True
            _aqe.record(
                "grouped-lowering",
                f"est {est_g} groups > dense range {S}; sorted "
                "program directly",
                est_before=S, est_after=est_g)
    with _obs.TRACER.span(
            "frame.grouped.flush", cat="frame", op="group_by",
            keys=len(keys), aggs=len(agg_list), rows=n, bucket=b) as sp:
        g = -1
        run_dense = dense_ok and not skip_dense
        if sharded:
            before = counters.get("grouped.compile")
            fn = _cached_plan(
                f"GDH{S}|{shard.tag()}|{struct}",
                _build_sharded_dense_agg_program(
                    shard.mesh, tuple(key_kinds), tuple(agg_ops),
                    tuple(val_kinds), S),
                mesh=shard.mesh)
            fn.stats_key = stats_key
            try:
                _faults.inject("shard_merge")
                key_outs, agg_outs, groups, fit, tiled = _run_plan(
                    fn, args, before, sp)
                # ONE host sync: fit verdict + group count together
                syncs += 1
                fit_h, g_h, tiled_h = _read_verdict((fit, groups, tiled))
            except jax.errors.JaxRuntimeError as e:
                # shard_merge ladder: a device fault in the sharded
                # merge gathers to single-device grouped execution —
                # the query keeps its device lowering, minus one rung
                from ..parallel.shard import gather_arrays
                from ..utils.recovery import RECOVERY_LOG

                RECOVERY_LOG.record(
                    "shard_merge", "fallback", rung="gather",
                    cause=f"{type(e).__name__}: {e}",
                    detail="sharded grouped merge degraded to "
                           "single-device execution")
                counters.increment("grouped.shard_gather")
                flat = gather_arrays(shard, mask_in,
                                     *(list(keys_in) + list(vals_in)))
                args = (tuple(flat[1:1 + len(keys_in)]),
                        tuple(flat[1 + len(keys_in):]), flat[0])
            else:
                if bool(fit_h):
                    g = int(g_h)
                    sp.set(groups=g, lowering="sharded-dense",
                           shards=shard.devices, tile=bool(tiled_h))
                    if config.costprof_enabled:
                        # exchange-volume accounting (device-cost
                        # observatory): the merge collective reduces the
                        # stacked S-slot tables — static shapes, so the
                        # aggregate payload is sized without any sync
                        from ..parallel.shard import record_exchange

                        record_exchange(
                            "psum",
                            S * max(len(agg_ops), 1)
                            * _acc_dtype().itemsize * shard.devices)
                else:
                    # global key range overflowed the dense table: the
                    # sorted program is single-device — gather (same S
                    # bound would miss again, skip the dense retry)
                    counters.increment("grouped.dense_miss")
                    from ..parallel.shard import gather_arrays

                    flat = gather_arrays(shard, mask_in,
                                         *(list(keys_in)
                                           + list(vals_in)))
                    args = (tuple(flat[1:1 + len(keys_in)]),
                            tuple(flat[1 + len(keys_in):]), flat[0])
                    run_dense = False
        if g < 0 and run_dense:
            before = counters.get("grouped.compile")
            fn = _cached_plan(f"GD{S}|{struct}", _build_dense_agg_program(
                tuple(key_kinds), tuple(agg_ops), tuple(val_kinds), S))
            fn.stats_key = stats_key
            key_outs, agg_outs, groups, fit, tiled = _run_plan(
                fn, args, before, sp)
            # ONE host sync: the fit verdict + group count together
            syncs += 1
            fit_h, g_h, tiled_h = _read_verdict((fit, groups, tiled))
            if bool(fit_h):
                g = int(g_h)
                if bool(tiled_h):
                    counters.increment("grouped.tile")
                    sp.set(groups=g, lowering="dense-tile",
                           blocks=-(-b // _tile_blocks(b)[0]))
                else:
                    sp.set(groups=g, lowering="dense")
            else:
                counters.increment("grouped.dense_miss")
                if stats_on:
                    # miss history feeds the optimizer's dense-skip
                    # decision above (same struct key, next query)
                    from ..utils import statstore as _stats_store

                    try:
                        _stats_store.STORE.record_miss(f"GD{S}|{struct}")
                    except Exception:
                        pass
        live = None
        if g < 0 and run_ok:
            # one integer key over more slots than the exact threshold:
            # where it arrives in order (read once a struct and size, and
            # checked again inside every run) its runs are its groups
            order_key = f"{struct}|{n}"
            with _ORDER_LOCK:
                ordered = _ORDER.get(order_key)
            if ordered is None:
                from .joins import _in_order

                syncs += 1
                ordered = bool(_read_verdict(_in_order(keys_in[0]),
                                             "grouped.order"))
                if not ordered:
                    counters.increment("grouped.order_miss")
            if ordered:
                before = counters.get("grouped.compile")
                fn = _cached_plan(f"GO|{struct}", _build_ordered_agg_program(
                    tuple(agg_ops), tuple(val_kinds)))
                fn.stats_key = stats_key
                key_outs, agg_outs, groups, live, held = _run_plan(
                    fn, args, before, sp)
                syncs += 1
                g_h, ordered = (int(x) for x in _read_verdict((groups, held)))
                if ordered:
                    g = g_h
                    counters.increment("grouped.ordered")
                    sp.set(groups=g, lowering="ordered")
                else:
                    counters.increment("grouped.order_miss")
            with _ORDER_LOCK:
                _ORDER[order_key] = bool(ordered)
        if g < 0:
            live = None
            before = counters.get("grouped.compile")
            fn = _cached_plan(f"GS|{struct}", _build_sorted_agg_program(
                tuple(key_kinds), tuple(agg_ops), tuple(val_kinds)))
            fn.stats_key = stats_key
            key_outs, agg_outs, groups = _run_plan(fn, args, before, sp)
            syncs += 1
            g = int(_read_verdict(groups))
            sp.set(groups=g, lowering="sorted")
    if stats_on:
        _record_grouped_stats(
            stats_key, n, g, (time.perf_counter() - t_stats) * 1e3,
            counters.get("grouped.compile") - c_stats, syncs,
            card_key=cardinality_history_key("g", keys, key_arrs))

    if live is not None:
        # the ordered lowering's groups stand at their runs' last slots,
        # under the key column they were read from
        out = dict(zip(keys, key_arrs))
        out.update((a.name, arr) for a, arr in zip(agg_list, agg_outs))
        return Frame(out, mask=None if g == n else live)

    # one slice program for all k+m outputs: it retraces per distinct
    # group count, as each eager ``arr[:g]`` would (the slice length is
    # static either way), and costs one dispatch instead of k+m
    # Past the exact-shape threshold the group count is data (4.6e5 orders
    # one day, 4.7e5 the next): such a result keeps a bucket of slots under
    # a mask, so that the sort or the join behind it is one program for
    # every count in the bucket instead of one compile a count.
    slots, out_mask = g, None
    if g > int(config.pipeline_exact_threshold):
        slots = min(result_bucket(g), int(key_outs[0].shape[0])
                    if key_outs else g)
        if slots > g:
            out_mask = jnp.arange(slots) < g
    key_outs, agg_outs = _unpad_tree((tuple(key_outs), tuple(agg_outs)),
                                     slots)
    out = dict(zip(keys, key_outs))
    out.update((a.name, arr) for a, arr in zip(agg_list, agg_outs))
    return Frame(out, mask=out_mask)


# ---------------------------------------------------------------------------
# Device sort (Frame.sort / SQL ORDER BY)
# ---------------------------------------------------------------------------

def _build_sort_program(key_specs):
    """``key_specs``: tuple of (kind, descending, nulls_first)."""

    def program(keys, mask):
        n = mask.shape[0]
        idx = lax.iota(jnp.int32, n)
        ops = [jnp.logical_not(mask)]
        for k, (kind, desc, nf) in zip(keys, key_specs):
            a = jnp.asarray(k)
            if kind == "b":
                a = a.astype(jnp.int8)
            if kind == "f":
                null = jnp.isnan(a)
                # flag False sorts first: nulls-first wants nulls=False
                ops.append(jnp.logical_not(null) if nf else null)
                a = jnp.where(null, jnp.zeros_like(a), a)
            ops.append(-a if desc else a)
        ops.append(idx)
        sorted_ops = lax.sort(tuple(ops), num_keys=len(ops))
        return sorted_ops[-1], jnp.sum(mask.astype(jnp.int32))

    return lambda: program


def device_sort(frame, names, ascending, nulls_first):
    """Device path for :meth:`Frame.sort`: numeric keys only, payload
    gathered with ``jnp.take`` so device columns never round-trip.

    On accelerators the permutation comes from one jitted ``lax.sort``
    program (one host sync: the valid-row count; none when the input is
    compact — a groupBy result — and its n rows are all valid). On XLA:CPU — whose
    variadic sort is a scalar comparator loop several times slower than
    numpy's — the permutation is planned host-side from one batched pull
    of just the key columns + mask (the ``Frame.join`` "plan on host,
    materialize on device" split; still one sync, and strictly less host
    traffic than the legacy full to_pydict round-trip). ``None`` = take
    the host path."""
    from ..frame.frame import Frame

    data = frame._data
    n = frame.num_slots
    if n == 0:
        return None
    key_arrs, specs = [], []
    for name, asc, nf in zip(names, ascending, nulls_first):
        arr = data.get(name)
        kind = _key_kind(arr) if arr is not None else None
        if kind is None:
            return None
        if nf is None:
            nf = asc                  # Spark default: asc→first, desc→last
        key_arrs.append(arr)
        specs.append((kind, not asc, bool(nf)))

    mask = frame._mask
    if jax.default_backend() == "cpu":
        counters.increment("frame.host_sync")
        take = _host_sort_plan(key_arrs, specs, mask)
        return Frame(_gather_columns(data, jnp.asarray(take), len(take),
                                     host_idx=take))

    if getattr(frame, "_shard", None) is not None:
        # A total sort has no shard-local lowering (the permutation is
        # global); gather the sort inputs one level and run the
        # single-device program — the output frame is compact and
        # single-device either way.
        from ..parallel.shard import gather_arrays

        flat = gather_arrays(frame._shard, jnp.asarray(mask, jnp.bool_),
                             *key_arrs)
        mask = flat[0]
        key_arrs = list(flat[1:])

    key = "|".join([
        dtype_tag(), "S",
        ",".join(f"{k}{'v' if d else '^'}{'n' if f else '_'}:"
                 f"{_col_kind_spec(a)}"
                 for a, (k, d, f) in zip(key_arrs, specs)),
    ])
    b = bucket_size(n)
    before = counters.get("grouped.compile")
    fn = _cached_plan(key, _build_sort_program(tuple(specs)))
    args = _padded((tuple(key_arrs), jnp.asarray(mask, jnp.bool_)), n, b)

    with _obs.TRACER.span(
            "frame.grouped.flush", cat="frame", op="sort",
            keys=len(names), rows=n, bucket=b) as sp:
        perm, nvalid = _run_plan(fn, args, before, sp)
        # a compact input (a groupBy result) has n valid rows: no read
        nv = (n if frame._every_slot_valid()
              else int(_read_verdict(nvalid)))
    return Frame(_gather_columns(data, perm, nv))


@functools.partial(jax.jit, static_argnums=2)
def _take_tree(cols, take, head: int):
    idx = take[:head]
    return tuple(jnp.take(c, idx, axis=0) for c in cols)


def _gather_columns(data, take_dev, head: int, host_idx=None):
    """Materialize every column at the first ``head`` entries of the
    device index vector ``take_dev``: the device columns in ONE dispatch
    (it retraces per distinct ``head``, as the eager slice and takes
    would). Host (string) columns need the indices host-side — one extra
    sync, only paid when such columns exist (or free when the caller
    already planned host-side)."""
    dev = [name for name, arr in data.items() if not _is_host_col(arr)]
    out = dict(zip(dev, _take_tree(
        tuple(jnp.asarray(data[name]) for name in dev), take_dev, head)))
    for name, arr in data.items():
        if _is_host_col(arr):
            if host_idx is None:
                host_idx = _read_verdict(take_dev[:head], "gather.index")
            out[name] = _host_gather(arr, host_idx)
    return {name: out[name] for name in data}


# ---------------------------------------------------------------------------
# Device distinct / dropDuplicates
# ---------------------------------------------------------------------------

def _build_unique_program(key_kinds):
    def program(keys, mask):
        n = mask.shape[0]
        perm, valid, seg, boundary, groups = _group_scaffold(
            keys, key_kinds, mask)
        big = jnp.asarray(n, jnp.int32)
        # stable sort ⇒ a group's first sorted member carries its minimum
        # original row index = the first occurrence; re-sorting those
        # indices restores first-occurrence output order (host parity)
        orig_first = jax.ops.segment_min(
            jnp.where(valid, perm, big), seg, num_segments=n)
        keep = lax.sort((orig_first,), num_keys=1)[0]
        return keep, groups

    return lambda: program


def device_unique(frame, key_names):
    """Device path for :meth:`Frame.distinct` (``key_names`` = all
    columns) and :meth:`Frame.drop_duplicates` (a subset): keep the first
    valid row per distinct key combination, in first-occurrence order.
    ``None`` = host path. NaN keys fold into one null group (the host
    behavior for scalar cells)."""
    from ..frame.frame import Frame

    data = frame._data
    n = frame.num_slots
    if n == 0:
        return None
    key_arrs, key_kinds = [], []
    for k in key_names:
        arr = data.get(k)
        if arr is None or _is_host_col(arr):
            return None
        a = jnp.asarray(arr)
        if a.ndim == 2:
            # vector cells group per component (distinct over an
            # assembled-features frame); NaN folds per component like the
            # scalar rule
            for j in range(a.shape[1]):
                comp = a[:, j]
                kind = _key_kind(comp)
                if kind is None:
                    return None
                key_arrs.append(comp)
                key_kinds.append(kind)
            continue
        kind = _key_kind(arr)
        if kind is None:
            return None
        key_arrs.append(arr)
        key_kinds.append(kind)

    mask = frame._mask
    card_key = (cardinality_history_key(
        "d", key_names, [data.get(k) for k in key_names])
        if config.stats_enabled else None)
    shard_store = getattr(frame, "_shard", None)
    if shard_store is not None:
        try:
            return _sharded_unique(frame, data, key_arrs, key_kinds,
                                   shard_store, card_key=card_key)
        except jax.errors.JaxRuntimeError as e:
            # shard_merge ladder: a device fault in the exchange program
            # gathers one level to the single-device unique below
            from ..parallel.shard import gather_arrays
            from ..utils.recovery import RECOVERY_LOG

            RECOVERY_LOG.record(
                "shard_merge", "fallback", rung="gather",
                cause=f"{type(e).__name__}: {e}",
                detail="sharded distinct degraded to single-device "
                       "execution")
            counters.increment("grouped.shard_gather")
            flat = gather_arrays(shard_store, jnp.asarray(mask, jnp.bool_),
                                 *key_arrs)
            mask = flat[0]
            key_arrs = list(flat[1:])

    key = "|".join([
        dtype_tag(), "U",
        ",".join(f"{k}:{_col_kind_spec(a)}"
                 for k, a in zip(key_kinds, key_arrs)),
    ])
    b = bucket_size(n)
    before = counters.get("grouped.compile")
    fn = _cached_plan(key, _build_unique_program(tuple(key_kinds)))
    fn.stats_key = key
    args = _padded((tuple(key_arrs), jnp.asarray(mask, jnp.bool_)), n, b)

    stats_on = config.stats_enabled
    t_stats = time.perf_counter() if stats_on else 0.0
    with _obs.TRACER.span(
            "frame.grouped.flush", cat="frame", op="distinct",
            keys=len(key_arrs), rows=n, bucket=b) as sp:
        keep, groups = _run_plan(fn, args, before, sp)
        g = int(_read_verdict(groups, "distinct.groups"))
        sp.set(groups=g)
    if stats_on:
        _record_grouped_stats(
            key, n, g, (time.perf_counter() - t_stats) * 1e3,
            counters.get("grouped.compile") - before, 1,
            card_key=card_key)
    return Frame(_gather_columns(data, keep, g))


# --- BEGIN HOST FALLBACK (numpy allowed: object-array gathers + the -------
# CPU-backend sort permutation plan; nothing here touches device compute)
import numpy as np  # noqa: E402  (scoped to the host-fallback region)


def _host_gather(arr, host_idx):
    return np.asarray(arr, dtype=object)[host_idx]


def _host_sort_plan(key_arrs, specs, mask):
    """XLA:CPU sort permutation: ONE batched pull of the key columns +
    mask, then the SAME lexsort component construction as the legacy
    ``Frame.sort`` host path (``frame.frame.lexsort_keys`` — one shared
    definition, so null placement and direction semantics cannot drift).
    Returns the original row indices of the valid rows in sorted order
    (host int array)."""
    from ..frame.frame import lexsort_keys

    # frame.host_sync is counted by the device-sort entry: its CPU branch
    # increments it immediately before planning here
    with _obs.host_reading("sort.keys") as rd:
        pulled = jax.device_get(tuple(key_arrs) + (mask,))
        rd.done(sum(np.asarray(a).nbytes for a in pulled))
    m = np.asarray(pulled[-1], bool)
    vi = np.nonzero(m)[0]
    arrays = [np.asarray(k)[vi] for k in pulled[:-1]]
    order = np.lexsort(lexsort_keys(
        arrays, [not d for _k, d, _f in specs],
        [f for _k, _d, f in specs]))
    return vi[order]


def _sharded_unique(frame, data, key_arrs, key_kinds, store,
                    card_key=None):
    """Sharded :func:`device_unique`: dispatch the hash-partition
    exchange program (one counted host sync pulls the per-shard
    first-occurrence candidate sets + counts in one batch), merge-sort
    the candidates host-side (ascending global index = first-occurrence
    order, exactly the single-device output order), and gather the kept
    rows on device. Raises ``JaxRuntimeError`` through to the caller's
    shard_merge ladder."""
    from ..frame.frame import Frame

    mesh = store.mesh
    D = int(mesh.devices.size)
    n = frame.num_slots
    key = "|".join([
        dtype_tag(), f"USH{D}",
        ",".join(f"{k}:{_col_kind_spec(a)}"
                 for k, a in zip(key_kinds, key_arrs)),
    ])
    before = counters.get("grouped.compile")
    fn = _cached_plan(key, _build_sharded_unique_program(
        mesh, tuple(key_kinds)), mesh=mesh)
    fn.stats_key = key
    keys_in = tuple(jnp.asarray(a) for a in key_arrs)
    mask_in = jnp.asarray(frame._mask, jnp.bool_)
    stats_on = config.stats_enabled
    t_stats = time.perf_counter() if stats_on else 0.0
    with _obs.TRACER.span(
            "frame.grouped.flush", cat="frame", op="distinct",
            keys=len(key_arrs), rows=n, bucket=store.bucket,
            shards=D) as sp:
        _faults.inject("shard_merge")
        cand, cnts, total = _run_plan(fn, (keys_in, mask_in), before, sp)
        cand_h, cnts_h, g = _read_verdict((cand, cnts, total),
                                          "distinct.candidates")
        g = int(g)
        sp.set(groups=g, lowering="sharded-exchange")
    if config.costprof_enabled:
        # exchange-volume accounting (device-cost observatory): the
        # hash-partition exchange ships FULL padded key blocks to every
        # owner shard — static shapes, sized without any sync
        from ..parallel.shard import record_exchange

        record_exchange(
            "all_to_all",
            sum(a.size * a.dtype.itemsize for a in keys_in) * D
            + mask_in.size * mask_in.dtype.itemsize * D)
    per = np.asarray(cand_h).reshape(D, -1)
    keep = np.sort(np.concatenate(
        [per[i, :int(cnts_h[i])] for i in range(D)])).astype(np.int64)
    if stats_on:
        _record_grouped_stats(
            key, n, g, (time.perf_counter() - t_stats) * 1e3,
            counters.get("grouped.compile") - before, 1,
            card_key=card_key)
    return Frame(_gather_columns(data, jnp.asarray(keep), len(keep),
                                 host_idx=keep))
# --- END HOST FALLBACK ----------------------------------------------------
