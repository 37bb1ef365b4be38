"""Columnar Frame: the framework's ``Dataset<Row>`` equivalent.

Design (SURVEY.md §7 step 1, TPU-first):

* a Frame is a dict of named columns — device arrays of shape ``(n,)`` (scalar
  columns) or ``(n, d)`` (vector columns, e.g. VectorAssembler output) — plus
  a boolean **validity mask** of shape ``(n,)``.
* ``filter`` ANDs into the mask instead of gathering rows, so every array keeps
  a static shape and everything downstream stays jit/XLA-friendly. All
  reductions (count, means, fit statistics) are mask-weighted; the golden DQ
  row counts (SURVEY.md §2.3: 40→34→24 etc.) are the regression tests that the
  mask never leaks.
* Spark's lazy DAG is deliberately **not** replicated: XLA's jit tracing and
  fusion provide the equivalent optimization, so eager column ops are the
  idiomatic design (SURVEY.md §7 preamble).

String columns are host-side numpy object arrays (TPUs do not hold strings);
numeric columns live in device memory.

Covers the Dataset API surface the reference app exercises:
``withColumnRenamed`` (`DataQuality4MachineLearningApp.java:58-59`),
``withColumn`` + ``callUDF`` (`:68-69,86-87`), ``show``/``printSchema``
(`:63,72-73,81-83,93-95,114-115`), temp views + SQL filtering (`:76-78,88-90`),
label-column copy (`:101`).
"""

from __future__ import annotations

import io
import logging
import threading
from typing import Iterable, Mapping, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..config import config, float_dtype, int_dtype
from ..ops.expressions import Col, Expr, spark_type_name
from ..utils.observability import (current_span, host_reading, op_span,
                                   span)
from ..utils.profiling import counters

logger = logging.getLogger("sparkdq4ml_tpu.frame")

# Pipeline flushes serialize PER FRAME (``Frame._lock``): frames were
# thread-safe-immutable before the lazy layer, and must stay observably
# so — but that is a per-object invariant, and a frame's flush touches
# only its own ``_data_store``/``_mask_store``/``_pending`` (stores are
# immutable snapshots; sibling frames replaying a shared prefix each
# publish their OWN result). A global flush lock would also serialize
# UNRELATED frames' flushes across serving workers — exactly the
# overlap the cross-request coalescer (serve/coalesce.py) exists to
# exploit: its batch leader holds its frame's lock through the hold
# window, and followers must be able to reach their own dispatches
# meanwhile. Inside a frame's lock, stores publish BEFORE _pending
# clears, so the unlocked fast-path check in the _data/_mask getters
# can never see "no pending" with stale stores. Concurrency of the
# device work itself needs no global lock: unsharded programs are
# single-device (thread-safe jit dispatch), and sharded flushes
# serialize on the collective lock (parallel/mesh.py) like every other
# multi-device program. _LOCK_FILL guards only the lazy per-frame lock
# creation — frames are minted on every op, so the hot construction
# paths must not pay an RLock allocation each.
_LOCK_FILL = threading.Lock()


def _is_device_error(e: BaseException) -> bool:
    """The retryable device-fault class of the flush ladder: exactly what
    a real XLA fault (OOM, interconnect reset) or an injected
    ``pipeline_flush:device_error`` surfaces as."""
    return isinstance(e, jax.errors.JaxRuntimeError)


ColumnLike = Union[Expr, jnp.ndarray, np.ndarray, Sequence]


def list_column(items) -> np.ndarray:
    """PUBLIC constructor for a ragged list column (token lists, item
    baskets): a 1-D object array with one list per row. ``np.asarray``
    would collapse equal-length lists into a 2-D array; explicit slot
    assignment keeps the ragged shape. Use with ``Frame({...: list_column(
    rows)})`` for any Tokenizer/Word2Vec/FPGrowth-style input."""
    arr = np.empty(len(items), dtype=object)
    for i, it in enumerate(items):
        arr[i] = it
    return arr


def _is_string_col(arr) -> bool:
    return isinstance(arr, np.ndarray) and arr.dtype == object


def lexsort_keys(arrays, ascending, nulls_first):
    """THE lexsort component construction for row ordering — shared by the
    host ``Frame.sort`` path and the grouped engine's CPU sort plan
    (``ops/segments._host_sort_plan``), so null placement and direction
    semantics cannot drift between them.

    ``arrays`` are per-key numpy arrays (original key order); returns the
    ``np.lexsort`` key list. Per key, appended last = higher priority:
    the null flag partitions each key before its values order within
    (False sorts first, so nulls-first wants nulls=False). Default null
    placement (``nulls_first[i] is None``) is Spark's: first ascending,
    last descending. NaN is the numeric null; None the string null;
    descending string keys are not supported (raises)."""
    keys = []
    for k, a, nf in zip(reversed(arrays), reversed(ascending),
                        reversed(nulls_first)):
        if nf is None:
            nf = a                 # Spark default: asc→first, desc→last
        k = np.asarray(k)
        if k.dtype == object:
            if not a:
                raise ValueError("descending sort on string columns is "
                                 "not supported")
            null_flag = np.asarray([x is None for x in k], bool)
            keys.append(np.asarray([x if x is not None else "" for x in k],
                                   dtype=object))
        else:
            if k.dtype == np.bool_:
                k = k.astype(np.int8)   # numpy forbids unary minus on bool
            null_flag = np.isnan(k) if np.issubdtype(
                k.dtype, np.floating) else np.zeros(len(k), bool)
            v = -k if not a else k
            # NaN would float to the end inside lexsort regardless of
            # the flag key; neutralize it so the flag alone decides
            keys.append(np.where(null_flag, 0.0, v)
                        if null_flag.any() else v)
        keys.append(~null_flag if nf else null_flag)
    return keys


def _vector_join_plan(lcols, rcols, li, ri, how, build_left=False):
    """The host's vectorized join *plan* for all-numeric keys — (lpairs,
    rpairs) row-index arrays, or None when ineligible (non-finite float
    keys, or integers float64 can't hold exactly; the dict plan in
    :meth:`Frame._host_join` then answers).

    Since the device plan (``ops/joins.py``) this is the plan of what stays
    on the host with numeric keys: ``right`` / ``outer`` joins, an integer
    key against a float one, sharded frames (per partition, through
    ``parallel/shard.partitioned_join_plan``) and frames with host (string)
    columns to gather. It works on the pulled keys of the valid rows.

    ``build_left`` (cost-based optimizer hint, inner joins only): sort
    the LEFT side instead of the right — the win when the left is the
    small side (the default plan's stable argsort runs over the right).
    Emission stays bit-identical: inner-join emission order IS the
    (left, right)-lexicographic pair order (left rows ascend, each with
    its right matches ascending), so the swapped plan's pairs
    re-canonicalize with one lexsort.

    Pure numpy: single-key joins use the float64 key values directly as
    sortable ids, multi-key joins assign integer group ids with ONE
    lexsort over the concatenated rows, then one stable argsort of the
    right ids + run-length-encoded binary-search group lookups — emitting
    pairs in exactly the dict plan's order (left rows in order, each with
    its right matches in right order; unmatched right rows appended in
    order for right/outer).
    """
    if build_left and how == "inner":
        swapped = _vector_join_plan(rcols, lcols, ri, li, "inner")
        if swapped is None:
            return None
        r_sw, l_sw = swapped          # swapped call: "left" = our right
        order = np.lexsort((r_sw, l_sw))   # primary: true left index
        return (l_sw[order].astype(np.int64),
                r_sw[order].astype(np.int64))

    def to64(c):
        c64 = c.astype(np.float64)
        if np.issubdtype(c.dtype, np.floating):
            return c64, bool(np.isfinite(c64).all())
        # integer keys: require an exact float64 round-trip (>2^53 ids lose
        # precision and could alias distinct keys)
        return c64, bool(np.array_equal(c64.astype(c.dtype), c))

    conv = [to64(c) for c in lcols + rcols]
    if not all(ok for _, ok in conv):
        return None
    k = len(lcols)
    nl = li.size

    if k == 1:
        # single key: the float64 values themselves are the sortable ids
        lid, rid = conv[0][0], conv[1][0]
    else:
        # multi-key: group ids via one lexsort over the concatenated rows
        # (np.unique(axis=0)'s void-view sort is ~5× slower than this)
        cols = [np.concatenate([conv[j][0], conv[k + j][0]])
                for j in range(k)]
        perm = np.lexsort(cols[::-1])
        newg = np.zeros(perm.size, bool)
        if perm.size:
            newg[0] = True
            for c in cols:
                cs = c[perm]
                newg[1:] |= cs[1:] != cs[:-1]
        inv = np.empty(perm.size, np.int64)
        inv[perm] = np.cumsum(newg) - 1
        lid, rid = inv[:nl], inv[nl:]
    order = np.argsort(rid, kind="stable")      # groups keep right order
    rid_sorted = rid[order]
    # run-length encode the sorted right keys: one binary search into the
    # distinct values + O(1) group offset/count lookups (two full
    # searchsorted calls over all rows would dominate the plan otherwise)
    if rid_sorted.size:
        bound = np.empty(rid_sorted.size, bool)
        bound[0] = True
        bound[1:] = rid_sorted[1:] != rid_sorted[:-1]
        gstart = np.nonzero(bound)[0]
        gvals = rid_sorted[gstart]
        gcnt = np.diff(np.append(gstart, rid_sorted.size))
        pos = np.minimum(np.searchsorted(gvals, lid), gvals.size - 1)
        hit = gvals[pos] == lid
        start = np.where(hit, gstart[pos], 0)
        counts = np.where(hit, gcnt[pos], 0)
    else:
        start = np.zeros(lid.size, np.int64)
        counts = np.zeros(lid.size, np.int64)

    if how == "left_semi":
        hit = counts > 0
        return li[hit], ri[order[start[hit]]]
    if how == "left_anti":
        miss = counts == 0
        return li[miss], np.full(int(miss.sum()), -1, np.int64)

    ecounts = counts
    if how in ("left", "outer"):                # unmatched left → one -1 row
        ecounts = np.maximum(counts, 1)
    total = int(ecounts.sum())
    lp = np.repeat(li, ecounts)
    group_first = np.cumsum(ecounts) - ecounts
    within = np.arange(total) - np.repeat(group_first, ecounts)
    flat = np.repeat(start, ecounts) + within
    if order.size:
        rp = ri[order[np.minimum(flat, order.size - 1)]]
    else:
        rp = np.full(total, -1, np.int64)
    if how in ("left", "outer"):
        rp = np.where(np.repeat(counts == 0, ecounts), -1, rp)

    if how in ("right", "outer"):               # append unmatched right rows
        lid_sorted = np.sort(lid)
        if lid_sorted.size:
            pos = np.searchsorted(lid_sorted, rid)
            matched = (pos < lid_sorted.size) & \
                (lid_sorted[np.minimum(pos, lid_sorted.size - 1)] == rid)
        else:
            matched = np.zeros(rid.size, bool)
        extra = ri[~matched]
        lp = np.concatenate([lp, np.full(extra.size, -1, np.int64)])
        rp = np.concatenate([rp, extra])
    return lp.astype(np.int64), rp.astype(np.int64)


def _as_column(values, n: Optional[int] = None):
    """Coerce raw values into a column array (device array, or host object array)."""
    if isinstance(values, np.ndarray) and values.dtype == object:
        arr = values
    elif isinstance(values, np.ndarray) and values.dtype.kind in ("U", "S"):
        # numpy unicode/bytes arrays are string columns: host object array
        arr = values.astype(object)
    elif isinstance(values, (jnp.ndarray, np.ndarray)):
        arr = jnp.asarray(values)
    else:
        values = list(values)
        if values and any(isinstance(v, str) for v in values):
            arr = np.asarray(values, dtype=object)
        else:
            np_arr = np.asarray(values)
            if np_arr.dtype == object:
                # e.g. [None, "a"] (null-first string groups) — host column
                arr = np_arr
            else:
                if np_arr.dtype == np.float64:
                    np_arr = np_arr.astype(np.dtype(float_dtype()))
                elif np_arr.dtype == np.int64:
                    np_arr = np_arr.astype(np.dtype(int_dtype()))
                arr = jnp.asarray(np_arr)
    if n is not None and arr.shape[0] != n:
        raise ValueError(f"column length {arr.shape[0]} != frame length {n}")
    return arr


def _pull(device: dict) -> dict:
    """ONE batched ``jax.device_get`` of ``device``: a counted frame host
    boundary, and a host read of the bytes that were on the device (host
    columns pass through ``device_get`` unread)."""
    counters.increment("frame.host_sync")
    with host_reading("frame.to_pydict") as rd:
        pulled = jax.device_get(device)
        rd.done(sum(pulled[k].nbytes for k, v in device.items()
                    if isinstance(v, jax.Array)))
    return pulled


def _pull_keys(frame, keys, site: str) -> list:
    """The key columns of ``frame`` as host arrays. The device ones come
    in one batch: a counted frame host boundary and one host read; string
    keys live on the host and are not read."""
    device = [k for k in keys if not _is_string_col(frame._data[k])]
    if not device:
        return [np.asarray(frame._column_values(k)) for k in keys]
    counters.increment("frame.host_sync")
    with host_reading(site) as rd:
        cols = [np.asarray(frame._column_values(k)) for k in keys]
        rd.done(sum(c.nbytes for k, c in zip(keys, cols) if k in device))
    return cols


class Frame:
    """Immutable columnar frame with a validity mask (see module docstring).

    Pipeline compiler (``ops/compiler.py``): consecutive *compilable*
    ``with_column``/``with_columns``/``filter`` calls do not dispatch one
    XLA computation each — they accumulate as pending steps
    (``_pending``) and materialize as ONE jitted program at the first
    read of ``_data``/``_mask`` (any action, aggregation, sort, join,
    fit, or host boundary). ``select`` fuses its own projection
    expressions into the same program. Externally frames stay immutable
    and eager-equivalent: the flush is a cache fill, semantics are
    bit-identical, and ``config.pipeline = False``
    (``spark.pipeline.enabled``) restores the exact per-op eager path.
    """

    _alias: Optional[str] = None  # set by .alias(); not inherited by _with
    _pending: tuple = ()          # deferred pipeline steps (see _defer)
    _flush_lock = None            # per-frame flush serializer (see _lock)
    _ones = None                  # the all-valid mask __init__ made, if any
    # Row-shard layout descriptor (parallel/shard.py ShardedStore), or
    # None for the single-device layout. A sharded frame's columns/mask
    # are global arrays padded to devices×bucket slots with a False mask
    # tail, laid out row-sharded over the mesh; masked-slot semantics
    # make every consumer correct unchanged, while the flush path lowers
    # pending steps as ONE shard_map program. Propagates through
    # _with/_defer (same layout); ops that rebuild a compact Frame
    # (sort, join, groupBy output, explode, union) return single-device
    # frames — re-shard at the next ingest/explicit shard_frame call.
    _shard = None

    # _data/_mask are flush-on-read properties so EVERY consumer — frame
    # methods, aggregates, models, tests poking internals — sees the
    # materialized state without knowing the pipeline layer exists.
    @property
    def _data(self) -> dict:
        if self._pending:
            self._flush()
        return self._data_store

    @_data.setter
    def _data(self, value: dict) -> None:
        self._data_store = value

    @property
    def _mask(self):
        if self._pending:
            self._flush()
        return self._mask_store

    @_mask.setter
    def _mask(self, value) -> None:
        self._mask_store = value

    def __init__(self, columns: Mapping[str, ColumnLike], mask=None):
        self._data: dict[str, object] = {}
        n = None
        for name, values in columns.items():
            arr = _as_column(values, n)
            n = arr.shape[0] if n is None else n
            self._data[name] = arr
        self._n = 0 if n is None else int(n)
        if mask is None:
            self._mask = self._ones = jnp.ones((self._n,), dtype=jnp.bool_)
        else:
            self._mask = jnp.asarray(mask, jnp.bool_)
            if self._mask.shape != (self._n,):
                raise ValueError("mask shape mismatch")

    # -- construction ------------------------------------------------------
    @classmethod
    def from_rows(cls, rows: Iterable[Sequence], names: Sequence[str]) -> "Frame":
        rows = list(rows)  # an exhausted iterator must still yield named cols
        cols = list(zip(*rows)) if rows else [[] for _ in names]
        return cls({name: list(vals) for name, vals in zip(names, cols)})

    def _every_slot_valid(self) -> bool:
        """True when the HOST knows no slot is masked: the frame still
        carries the all-ones mask it was constructed with (a compact
        result — groupBy, sort, join output). False says nothing."""
        return self._ones is not None and self._mask is self._ones

    def _with(self, data=None, mask=None) -> "Frame":
        f = Frame.__new__(Frame)
        f._data = dict(self._data if data is None else data)
        f._mask = self._mask if mask is None else mask
        f._ones = self._ones      # counts only while the mask is that one
        f._n = self._n
        f._shard = self._shard
        return f

    # -- pipeline compiler plumbing (ops/compiler.py) ----------------------
    def _lock(self):
        """This frame's flush serializer, created on first need (every
        frame op mints frames — the construction paths must not pay an
        RLock each). Reentrant: the flush ladder re-enters through eager
        replay on the same frame. Per-frame by design — see the
        _LOCK_FILL comment at module top."""
        lk = self._flush_lock
        if lk is None:
            with _LOCK_FILL:
                lk = self._flush_lock
                if lk is None:
                    lk = self._flush_lock = threading.RLock()
        return lk

    def _defer(self, step) -> "Frame":
        """New frame sharing this one's base columns/mask with ``step``
        appended to the pending pipeline. Flush never mutates a shared
        store in place, so sharing is safe; compilable steps are pure, so
        sibling frames replaying a shared prefix stay correct."""
        f = Frame.__new__(Frame)
        with self._lock():
            # consistent (stores, pending) snapshot: racing a concurrent
            # flush of this frame unlocked could pair the POST-flush
            # stores with the PRE-flush step list — the child would then
            # double-apply every step. The PARENT's lock is the right
            # one (it serializes this read against the parent's own
            # flush); the child lazily mints its own.
            f._data_store = self._data_store
            f._mask_store = self._mask_store
            f._pending = self._pending + (step,)
            f._shard = self._shard
        f._n = self._n
        return f

    def _pending_names(self) -> list[str]:
        names: list[str] = []
        for s in self._pending:
            if s[0] == "with_column":
                names.append(s[1])
            elif s[0] == "with_columns":
                names.extend(n for n, _ in s[1])
        return names

    def _pipe_schema(self):
        # lazy: only columns the checked expression references get a
        # dtype probe — deferral stays O(expr), not O(frame width)
        from ..ops.compiler import pending_schema

        return pending_schema(self._data_store, self._pending)

    def _can_defer(self, *exprs) -> bool:
        if not config.pipeline or self._n == 0:
            return False
        from ..ops.compiler import is_compilable

        schema = self._pipe_schema()
        return all(isinstance(e, Expr) and is_compilable(e, schema)
                   for e in exprs)

    def _flush(self) -> None:
        """Materialize the pending pipeline steps as one compiled program
        (or, on any compiler failure, by eager per-op replay — the
        optimization layer must never change results).

        ``_pending`` is cleared only AFTER a successful materialization:
        if even the eager replay raises (a genuinely bad expression), the
        exception propagates with the steps intact, so every subsequent
        read raises the same error instead of silently serving the
        pre-op frame state. Flushes serialize on this frame's own lock
        (``_lock`` — per frame, so UNRELATED frames' flushes overlap and
        the serving tier's coalescer can rendezvous them) and
        publish the new stores BEFORE clearing ``_pending`` — a reader
        racing the unlocked getter fast-path either re-enters here (and
        finds nothing left to do) or sees the fully flushed state; never
        stale stores, never a double-applied step.

        Degradation ladder (ISSUE 11): a DEVICE fault inside the fused
        dispatch — a real ``XlaRuntimeError``, or an injected
        ``pipeline_flush`` fault from ``utils.faults`` — routes through
        :meth:`_flush_ladder` (retry via the PR-1 recovery engine, then
        eager per-op replay, counted ``pipeline.fault_fallback``); steps
        stay in ``_pending`` until a rung succeeds, so a failed rung can
        never half-apply. With no fault plan installed the extra cost is
        one ``is None`` check (test-pinned)."""
        from ..ops.compiler import PipelineError, run_pipeline
        from ..utils import faults as _faults

        with self._lock():
            steps = self._pending
            if not steps:
                return
            try:
                new_data, new_mask, _ = run_pipeline(
                    self._data_store, self._mask_store, self._n, steps,
                    shard=self._shard)
                if _faults.active() is not None:   # chaos armed
                    # Surface async-dispatched device faults INSIDE this
                    # try while chaos is armed (jax dispatch is async; an
                    # unsynced fault would otherwise raise at a later
                    # host read, past the ladder, with _pending already
                    # cleared). The no-chaos path deliberately keeps the
                    # flush un-synced — one sync per flush would
                    # serialize the async pipeline; a real accelerator
                    # fault then surfaces at the consumer's first host
                    # read as a failed (never silently wrong) query, and
                    # the SERVING tier's requeue ladder still catches it
                    # there (JaxRuntimeError is its retryable class).
                    jax.block_until_ready((new_data, new_mask))
                    new_data, new_mask = self._chaos_validate(
                        steps, new_data, new_mask)
            except PipelineError as e:
                logger.debug("pipeline flush fell back to eager replay: %s",
                             e)
                new_data, new_mask = self._eager_replay(steps)
            except Exception as e:
                if not _is_device_error(e):
                    raise
                new_data, new_mask = self._flush_ladder(
                    steps, first_cause=e)
            self._data_store = new_data
            self._mask_store = new_mask
            self._pending = ()

    def _chaos_validate(self, steps, new_data, new_mask):
        """NaN-corruption arm of the ``pipeline_flush`` ladder — runs only
        under an installed fault plan with a ``nan`` spec at this site.
        The produced columns are corrupted through ``faults.corrupt`` (a
        flaky-transfer model) and checked with ``check_finite``; a
        detected poisoning re-runs the whole flush through the resilient
        ladder. The finiteness check is sound for the chaos suite's own
        workloads (PR-1 convention: chaos tests detect their own injected
        NaNs); workloads whose flush outputs legitimately carry NaN take
        the ladder's extra replays but keep their correct eager result."""
        from ..utils import faults as _faults

        plan = _faults.active()
        if plan is None or not plan._has("pipeline_flush", ("nan",)):
            return new_data, new_mask
        from ..utils import recovery as _rec

        new_data, changed = self._corrupt_changed(new_data)
        if _rec.check_finite(changed):
            return new_data, new_mask
        # rung "dispatch" = the pre-ladder flush attempt, distinct from
        # the ladder's own rung="primary" retry events (no double-log)
        _rec.RECOVERY_LOG.record("pipeline_flush", "retry", attempt=1,
                                 rung="dispatch",
                                 cause="non-finite result")
        return self._flush_ladder(steps)

    def _corrupt_changed(self, new_data):
        """The one corrupt-merge step of the nan arm, shared by the first
        flush (:meth:`_chaos_validate`) and the ladder's retries: corrupt
        the columns this flush PRODUCED (identity vs the pre-flush store)
        and merge any poisoning back. Returns ``(new_data, changed)`` —
        ``changed`` is the validation target."""
        from ..utils import faults as _faults

        changed = {k: v for k, v in new_data.items()
                   if v is not self._data_store.get(k)}
        poisoned = _faults.corrupt("pipeline_flush", changed)
        if poisoned is not changed:
            new_data = {**new_data, **poisoned}
            changed = poisoned
        return new_data, changed

    def _flush_ladder(self, steps, first_cause=None):
        """The ``pipeline_flush`` degradation ladder: retry the fused
        program under ``recovery.resilient_call`` (per-site
        ``spark.recovery.pipeline_flush.*`` policy), then degrade one
        level to eager per-op replay (``pipeline.fault_fallback``) — a
        fault costs one rung, never the query. Runs under this frame's
        flush lock (held by the caller), so chaos-path backoff sleeps
        briefly serialize THIS frame's other flushes — bounded by the
        retry policy; unrelated frames are unaffected."""
        from ..ops.compiler import PipelineError, run_pipeline
        from ..utils import faults as _faults
        from ..utils import recovery as _rec
        from ..utils.profiling import counters

        plan = _faults.active()
        nan_armed = plan is not None and plan._has("pipeline_flush",
                                                   ("nan",))
        shard_store = self._shard
        site = "pipeline_flush" if shard_store is None else "shard_flush"

        def fused():
            new_data, new_mask, _ = run_pipeline(
                self._data_store, self._mask_store, self._n, steps,
                shard=shard_store)
            if not nan_armed:
                return new_data, new_mask, None
            new_data, changed = self._corrupt_changed(new_data)
            return new_data, new_mask, changed

        degraded: list = []

        def gather():
            # shard_flush ladder rung 2 ("a device fault on one shard"):
            # re-place the columns single-device and replay the SAME
            # steps through the unsharded fused program; the frame drops
            # its sharded layout (the caller below) — a fault costs this
            # frame its distribution, never the query.
            from ..parallel.shard import gather_store

            counters.increment("pipeline.shard_gather")
            data, mask = gather_store(self)
            new_data, new_mask, _ = run_pipeline(data, mask, self._n,
                                                 steps)
            degraded.append(True)
            if not nan_armed:
                return new_data, new_mask, None
            new_data, changed = self._corrupt_changed(new_data)
            return new_data, new_mask, changed

        def eager():
            counters.increment("pipeline.fault_fallback")
            d, m = self._eager_replay(steps)
            return d, m, None

        validate = ((lambda out: out[2] is None
                     or _rec.check_finite(out[2]))
                    if nan_armed else None)
        if first_cause is not None:
            # the PRE-ladder dispatch that failed — rung "dispatch", so a
            # persistent fault's ladder retries (rung "primary") never
            # read as duplicates of this event
            _rec.RECOVERY_LOG.record(
                site, "retry", attempt=1, rung="dispatch",
                cause=f"{type(first_cause).__name__}: {first_cause}")
        fallbacks = ((("gather", gather),) if shard_store is not None
                     else ()) + (("eager", eager),)
        try:
            new_data, new_mask, _ = _rec.resilient_call(
                fused, site=site, validate=validate,
                fallbacks=fallbacks)
            if degraded:
                self._shard = None
            return new_data, new_mask
        except PipelineError:
            # structural compile failure inside the ladder: eager replay
            # is the answer on every path
            d, m, _ = eager()
            return d, m

    def _eager_replay(self, steps):
        """Apply pipeline steps through the eager code paths (fallback)."""
        f = self._with(data=self._data_store, mask=self._mask_store)
        for s in steps:
            if s[0] == "with_column":
                f = f._with_column_eager(s[1], s[2])
            elif s[0] == "with_columns":
                f = f._with_columns_eager(dict(s[1]))
            else:
                f = f._filter_eager(s[1])
        return f._data_store, f._mask_store

    # -- basic introspection ----------------------------------------------
    @property
    def columns(self) -> list[str]:
        if not self._pending:
            return list(self._data_store)
        # pending with_column targets are columns too — WITHOUT forcing a
        # flush (column-name introspection is not a materialization point)
        out = list(self._data_store)
        seen = set(out)
        for n in self._pending_names():
            if n not in seen:
                seen.add(n)
                out.append(n)
        return out

    @property
    def num_slots(self) -> int:
        """Physical row slots (including masked-out rows). Static under jit."""
        return self._n

    @property
    def mask(self) -> jnp.ndarray:
        return self._mask

    def dtypes(self) -> list[tuple[str, str]]:
        return [(name, spark_type_name(np.dtype(arr.dtype)) if not _is_string_col(arr)
                 else "string") for name, arr in self._data.items()]

    def schema_string(self) -> str:
        """``printSchema`` text, matching Spark's output shape."""
        out = io.StringIO()
        out.write("root\n")
        for name, arr in self._data.items():
            if _is_string_col(arr):
                tname = "string"
            elif arr.ndim == 2:
                tname = "vector"
            else:
                tname = spark_type_name(np.dtype(arr.dtype))
            out.write(f" |-- {name}: {tname} (nullable = true)\n")
        return out.getvalue()

    def print_schema(self) -> None:
        print(self.schema_string(), end="")

    printSchema = print_schema  # Spark-style alias

    # -- column access -----------------------------------------------------
    def _column_values(self, name: str):
        try:
            return self._data[name]
        except KeyError:
            raise KeyError(
                f"no column {name!r}; columns: {self.columns}") from None

    def col(self, name: str) -> Col:
        # raise early on unknown names, like Spark's analyzer — a name
        # check, not a value read, so a pending pipeline stays pending
        if name not in self.columns:
            raise KeyError(
                f"no column {name!r}; columns: {self.columns}")
        return Col(name)

    def __getitem__(self, name: str) -> Col:
        return self.col(name)

    def _eval(self, expr_or_values):
        if isinstance(expr_or_values, Expr):
            return expr_or_values.eval(self)
        if self._shard is not None:
            # raw columns sized to the TRUE row count (what a caller who
            # never heard of sharding naturally provides) pad + place
            # into the sharded layout; slot-length arrays pass through
            arr = _as_column(expr_or_values)
            if arr.shape[0] == self._shard.rows and \
                    self._shard.rows != self._n:
                from ..parallel.shard import place_column

                return place_column(arr, self._shard)
            if arr.shape[0] != self._n:
                raise ValueError(f"column length {arr.shape[0]} != frame "
                                 f"length {self._shard.rows} (sharded "
                                 f"slots {self._n})")
            return arr
        return _as_column(expr_or_values, self._n)

    # -- transformations (each returns a new Frame) ------------------------
    # Observability: the op_span decorator is a no-op (one flag read) until
    # spark.observability.enabled turns the tracer on; then each decorated
    # op records a span with rows in/out (static shapes — never a device
    # read, so the "no host syncs" hygiene of the fused paths holds).
    @op_span("frame.with_column")
    def with_column(self, name: str, values: ColumnLike) -> "Frame":
        """``withColumn`` — add or replace a column from an expression/array.

        A compilable expression defers into the fused pipeline (one XLA
        program per chain at the next materialization point) instead of
        dispatching its own computation; see the class docstring."""
        if isinstance(values, Expr) and self._can_defer(values):
            return self._defer(("with_column", name, values))
        return self._with_column_eager(name, values)

    def _with_column_eager(self, name: str, values: ColumnLike) -> "Frame":
        data = dict(self._data)
        data[name] = self._eval(values)
        return self._with(data=data)

    withColumn = with_column

    def with_column_renamed(self, old: str, new: str) -> "Frame":
        """``withColumnRenamed`` — no-op if ``old`` is absent (Spark semantics)."""
        if old not in self._data:
            return self
        data = {(new if k == old else k): v for k, v in self._data.items()}
        return self._with(data=data)

    withColumnRenamed = with_column_renamed

    def with_columns_renamed(self, mapping: Mapping[str, str]) -> "Frame":
        """Spark 3.4's ``withColumnsRenamed`` — batch rename; absent keys
        are no-ops (same semantics as the single-column form).

        A rename target that collides with a surviving column raises:
        Spark would produce duplicate column names, which this engine's
        dict-backed frame cannot represent — silently keeping one of the
        two (the old behavior) lost data with no error (ADVICE.md #4).
        Swaps (``{'a': 'b', 'b': 'a'}``) remain legal: the collision test
        only counts columns that keep their name."""
        renamed_away = {k for k, new in mapping.items()
                        if k in self._data and new != k}
        data: dict = {}
        for k, v in self._data.items():
            nk = mapping.get(k, k)
            if nk in data or (nk != k and nk in self._data
                              and nk not in renamed_away):
                raise ValueError(
                    f"withColumnsRenamed: rename target {nk!r} collides "
                    "with an existing column; the engine cannot hold "
                    "duplicate column names (rename or drop the other "
                    f"{nk!r} first)")
            data[nk] = v
        return self._with(data=data)

    withColumnsRenamed = with_columns_renamed

    def transform(self, func, *args, **kwargs) -> "Frame":
        """Spark's ``df.transform(fn)`` — chainable function application:
        ``df.transform(clean).transform(label)`` reads pipeline-style."""
        out = func(self, *args, **kwargs)
        if not isinstance(out, Frame):
            raise TypeError("transform function must return a Frame, got "
                            f"{type(out).__name__}")
        return out

    def unpivot(self, ids, values=None, variable_column_name: str = "variable",
                value_column_name: str = "value") -> "Frame":
        """Spark 3.4's ``unpivot``/``melt``: wide → long. ``ids`` stay as
        identifier columns; each of ``values`` (default: every non-id
        numeric column) contributes one output row per input row, tagged
        with its column name. Row-major like Spark: input row 0's value
        columns first, then row 1's. Host-side reshape at the boundary —
        the long result lands as fresh device columns."""
        ids = [ids] if isinstance(ids, str) else list(ids)
        if values is None:
            values = [c for c in self.columns if c not in ids]
        values = [values] if isinstance(values, str) else list(values)
        if not values:
            raise ValueError("unpivot requires at least one value column")
        for c in ids + values:
            if c not in self.columns:
                raise ValueError(f"unpivot column {c!r} is not a column")
        d = self.to_pydict()
        n = len(next(iter(d.values()))) if d else 0
        k = len(values)
        data: dict = {}
        for c in ids:
            col = np.asarray(d[c])
            data[c] = (np.repeat(col, k) if col.dtype != object
                       else np.asarray([x for x in col for _ in range(k)],
                                       dtype=object))
        data[variable_column_name] = np.asarray(values * n, dtype=object) \
            if n else np.asarray([], dtype=object)
        vals = np.column_stack(
            [np.asarray(d[c], np.float64) for c in values]) \
            if n else np.zeros((0, k))
        data[value_column_name] = vals.ravel()
        return Frame(data)

    melt = unpivot

    @op_span("frame.select")
    def select(self, *exprs: Union[str, Expr]) -> "Frame":
        from ..ops.expressions import Alias, Explode, JsonTuple

        # flatten list/tuple items so `select(df.colRegex("`x.*`"))` works
        flat = []
        for e in exprs:
            if isinstance(e, (list, tuple)):
                flat.extend(e)
            else:
                flat.append(e)
        exprs = tuple(flat)
        # Spark allows ONE generator (explode) per select: resolve the
        # scalar columns first, then expand rows at the host boundary.
        # Only a bare Explode or an Alias over one counts — any other
        # wrapper (Cast(Explode), arithmetic) falls through to eval(),
        # whose generator error explains the restriction.
        gens = [e for e in exprs if isinstance(e, Explode)
                or (isinstance(e, Alias) and isinstance(e.child, Explode))]
        if len(gens) > 1:
            raise ValueError("only one explode() per select (Spark rule)")
        # Fused select+filter: compilable projection expressions evaluate
        # inside ONE compiled program together with any pending
        # with_column/filter steps (the SQL SELECT-list + WHERE hot path).
        pre = self._precompute_select(exprs, gens)
        data: dict[str, object] = {}
        for e in exprs:
            if isinstance(e, str):
                if e == "*":
                    data.update(self._data)
                    continue
                e = Col(e)
            # identity, not `in`: Expr.__eq__ builds a BinOp (truthy), so
            # membership tests over Expr lists must never use ==
            if any(e is g for g in gens):
                continue
            if isinstance(e, JsonTuple):
                # multi-column generator: no row multiplication, so it
                # expands inline (c0…cN) unlike the explode family
                data.update(e.columns(self))
                continue
            if id(e) in pre:
                data[e.name] = pre[id(e)]
                continue
            data[e.name] = e.eval(self)
        if not gens:
            return self._with(data=data)
        g = gens[0]
        inner = g if isinstance(g, Explode) else g.child
        src_vals = inner.source_values(self)
        # a temp slot keeps an explicitly-selected source column (or one
        # pulled in via '*') in the output, like Spark
        tmp = "__explode_source__"
        while tmp in data:
            tmp += "_"
        return self._with(data={**data, tmp: src_vals}).explode(
            tmp, g.name, keep_nulls=inner.outer,
            position_col="pos" if inner.with_position else None)

    def _precompute_select(self, exprs, gens) -> dict:
        """Evaluate compilable select expressions (plus any pending
        pipeline steps) in one compiled program; returns ``{id(expr):
        array}`` for the loop in :meth:`select` to consume. Empty dict ⇒
        nothing fused (caller falls through to per-expression eval, which
        flushes pending steps on first `_data` read)."""
        if not config.pipeline or self._n == 0:
            return {}
        from ..ops.compiler import (PipelineError, is_compilable,
                                    run_pipeline)

        from ..ops.expressions import JsonTuple

        schema = self._pipe_schema()
        cand = [e for e in exprs
                if isinstance(e, Expr) and not isinstance(e, JsonTuple)
                and not any(e is g for g in gens)
                and not isinstance(e, Col)          # plain refs are free
                and is_compilable(e, schema)]
        # Fusing pays when a pending chain flushes anyway or when >= 2
        # expressions share one program; a lone expression on a clean
        # frame costs the same either way — keep it eager.
        if not cand or (not self._pending and len(cand) < 2):
            return {}
        extra = [(f"__sel_{i}", e) for i, e in enumerate(cand)]
        with self._lock():
            steps = self._pending
            try:
                new_data, new_mask, extras = run_pipeline(
                    self._data_store, self._mask_store, self._n, steps,
                    extra, shard=self._shard)
            except PipelineError as e:
                logger.debug("fused select fell back to eager: %s", e)
                return {}
            except Exception as e:
                if not _is_device_error(e):
                    raise
                # device fault in the fused select: defer to the eager
                # path (per-expression eval, whose first _data read
                # re-enters the _flush ladder if the fault persists)
                from ..utils.recovery import RECOVERY_LOG

                RECOVERY_LOG.record(
                    "pipeline_flush", "fallback", rung="select",
                    cause=f"{type(e).__name__}: {e}",
                    detail="fused select deferred to eager evaluation")
                return {}
            # stores BEFORE pending — same publish ordering as _flush
            self._data_store = new_data
            self._mask_store = new_mask
            self._pending = ()
        return {id(e): extras[f"__sel_{i}"] for i, e in enumerate(cand)}

    @op_span("frame.explode")
    def explode(self, column: str, output_col: str = None,
                keep_nulls: bool = False,
                position_col: str = None) -> "Frame":
        """Spark's ``explode``: one output row per element of a list cell.

        Row multiplication is inherently dynamic-shaped, so this is a host
        boundary like join/groupBy (the "gather at the boundary" rule):
        lengths gather once, scalar columns ``np.repeat``, and the result
        is a compact new Frame. Null/empty cells drop their row (Spark's
        ``explode``); ``keep_nulls=True`` gives ``explode_outer`` (one
        null-element row instead)."""
        arr = self._data.get(column)
        if arr is None:
            raise ValueError(f"no column {column!r}")
        if not _is_string_col(arr):
            raise ValueError("explode() expects an array column (e.g. "
                             "split() or collect_list() output)")
        from ..ops.expressions import _require_array_cells

        _require_array_cells(arr, "explode")  # a str cell would silently
        # produce zero rows otherwise (plain string columns are object
        # arrays too)
        out_name = output_col or column
        idx = np.nonzero(self._host_mask())[0]
        cells = np.asarray(arr, object)[idx]
        lens = np.asarray([
            (len(c) if isinstance(c, (list, tuple, np.ndarray)) else 0)
            if c is not None else 0 for c in cells], np.int64)
        if keep_nulls:
            rep = np.maximum(lens, 1)
        else:
            rep = lens
        src = np.repeat(idx, rep)
        values = []
        positions = []
        for c, ln in zip(cells, lens):
            if ln:
                values.extend(list(c))
                positions.extend(range(ln))
            elif keep_nulls:
                values.append(None)
                positions.append(None)     # posexplode_outer: null pos
        src_dev = jnp.asarray(src) if len(src) else None  # ONE transfer
        data: dict[str, object] = {}
        for name, col_arr in self._data.items():
            if name == column:
                continue
            if _is_string_col(col_arr):
                data[name] = np.asarray(col_arr, object)[src]
            else:
                data[name] = jnp.take(jnp.asarray(col_arr),
                                      src_dev, axis=0) \
                    if len(src) else jnp.asarray(col_arr)[:0]
        # element dtype from the NON-NULL values: numeric lists land on
        # device; strings (or an all-null result, which must not flip a
        # string column to float NaN) stay host
        non_null = [v for v in values if v is not None]
        if non_null and all(isinstance(v, (int, float, np.floating,
                                           np.integer)) for v in non_null):
            data[out_name] = jnp.asarray(np.asarray(
                [np.nan if v is None else float(v) for v in values],
                np.float64), float_dtype())
        else:
            out = np.empty(len(values), object)
            for i, v in enumerate(values):
                out[i] = v
            data[out_name] = out
        if position_col is not None:
            if position_col in data:
                raise ValueError(
                    f"position column {position_col!r} collides with an "
                    "existing output column")
            if any(p is None for p in positions):
                pos_arr = jnp.asarray(np.asarray(
                    [np.nan if p is None else float(p) for p in positions],
                    np.float64), float_dtype())
            else:
                pos_arr = jnp.asarray(np.asarray(positions, np.int32))
            # Spark's posexplode order is (pos, col): rebuild with the
            # position column right before the value column
            ordered: dict[str, object] = {}
            for k, v in data.items():
                if k == out_name:
                    ordered[position_col] = pos_arr
                ordered[k] = v
            data = ordered
        return Frame(data)

    def drop(self, *names: str) -> "Frame":
        data = {k: v for k, v in self._data.items() if k not in names}
        return self._with(data=data)

    @op_span("frame.filter")
    def filter(self, condition: Union[Expr, jnp.ndarray]) -> "Frame":
        """AND a predicate into the validity mask (static shapes preserved).

        SQL three-valued logic: a NULL predicate (NaN in this engine's
        float encoding — e.g. ``array_contains`` over a null cell) drops
        the row, exactly like Spark's WHERE. A bare ``NaN.astype(bool)``
        would be True and silently keep null rows.

        A compilable predicate defers into the fused pipeline — the mask
        AND lands inside the same compiled program as the column
        expressions it rides with."""
        if isinstance(condition, Expr) and self._can_defer(condition):
            return self._defer(("filter", condition))
        return self._filter_eager(condition)

    def _filter_eager(self, condition: Union[Expr, jnp.ndarray]) -> "Frame":
        from ..ops.expressions import predicate_keep_mask

        cond = condition.eval(self) if isinstance(condition, Expr) else jnp.asarray(condition)
        keep = predicate_keep_mask(cond)
        return self._with(mask=jnp.logical_and(self._mask, keep))

    where = filter

    def limit(self, n: int) -> "Frame":
        if self._shard is None and self._every_slot_valid():
            # a compact frame (a sort's or a GROUP BY's result): its first
            # n rows are its first n slots — a slice, so that what follows
            # (``to_pydict`` of ten ranked rows) reads n rows, not a mask
            # over every group
            if n >= self._n:
                return self
            return Frame({k: v[:max(n, 0)] for k, v in self._data.items()})
        keep = jnp.cumsum(self._mask.astype(jnp.int32)) <= n
        return self._with(mask=jnp.logical_and(self._mask, keep))

    def offset(self, n: int) -> "Frame":
        """Skip the first ``n`` valid rows (SQL OFFSET; Spark 3.4's
        ``df.offset``) — a mask update like ``limit``, no data movement."""
        keep = jnp.cumsum(self._mask.astype(jnp.int32)) > n
        return self._with(mask=jnp.logical_and(self._mask, keep))

    @op_span("frame.union")
    def union(self, other: "Frame") -> "Frame":
        if self.columns != other.columns:
            raise ValueError("union requires identical column lists")
        data = {}
        for name in self.columns:
            a, b = self._data[name], other._data[name]
            if _is_string_col(a) or _is_string_col(b):
                data[name] = np.concatenate([np.asarray(a, object), np.asarray(b, object)])
            else:
                data[name] = jnp.concatenate([jnp.asarray(a), jnp.asarray(b)])
        f = Frame(data)
        f._mask = jnp.concatenate([self._mask, other._mask])
        return f

    unionAll = union  # Spark 2.x alias (deprecated there, kept for parity)

    def union_by_name(self, other: "Frame",
                      allow_missing_columns: bool = False) -> "Frame":
        """``unionByName`` — union resolving columns by name, not position.
        With ``allow_missing_columns`` the asymmetric columns null-fill."""
        if allow_missing_columns:
            both = list(dict.fromkeys(self.columns + other.columns))

            def widen(frame):
                out = frame
                for name in both:
                    if name not in frame.columns:
                        ref_arr = (other if name in other.columns
                                   else self)._data[name]
                        if _is_string_col(ref_arr):
                            fill = np.full((frame.num_slots,), None,
                                           dtype=object)
                        else:
                            fill = jnp.full((frame.num_slots,), jnp.nan,
                                            float_dtype())
                        out = out.with_column(name, fill)
                return out.select(*both)

            return widen(self).union(widen(other))
        if set(self.columns) != set(other.columns):
            raise ValueError(
                f"unionByName: column sets differ {self.columns} vs "
                f"{other.columns}; pass allow_missing_columns=True")
        return self.union(other.select(*self.columns))

    unionByName = union_by_name

    _NULL_KEY = "\0__null__"  # NaN stand-in so null rows hash/compare equal

    def _keyed_rows(self):
        """One host gather → [(hashable null-safe key, row), ...]. NaN (the
        engine's null) maps to a sentinel so null rows match each other, as
        Spark's null-safe set ops do."""
        def norm(x):
            if isinstance(x, np.ndarray):                 # vector cell
                return tuple(norm(v) for v in x.tolist())
            if hasattr(x, "item"):
                x = x.item()
            if isinstance(x, float) and x != x:
                return Frame._NULL_KEY
            return x

        rows = self.collect()
        return [(tuple(norm(x) for x in r), r) for r in rows]

    def select_expr(self, *exprs: str) -> "Frame":
        """Spark's ``selectExpr``: SQL expression strings evaluated over
        this frame (same grammar as ``session.sql``'s select list — CAST,
        arithmetic, functions, aliases, ``*``), via a scratch catalog so
        no temp view leaks."""
        from ..sql.catalog import Catalog
        from ..sql.parser import execute

        cat = Catalog()
        cat.register("__this__", self)
        return execute(
            f"SELECT {', '.join(exprs)} FROM __this__", catalog=cat)

    selectExpr = select_expr

    def col_regex(self, pattern: str) -> list:
        """Spark's ``colRegex``: column expressions whose names match the
        (Java-style, backtick-quoted allowed) regex — pass the result
        straight to ``select`` (it flattens lists)."""
        import re as _re

        pat = pattern.strip()
        if pat.startswith("`") and pat.endswith("`"):
            pat = pat[1:-1]
        rx = _re.compile(pat)
        return [Col(c) for c in self.columns if rx.fullmatch(c)]

    colRegex = col_regex

    @property
    def schema(self) -> list[tuple[str, str]]:
        """``[(name, spark_type_name)]`` — the engine's schema form (the
        ``StructType`` analogue; same pairs as ``dtypes()``)."""
        return self.dtypes()

    @property
    def na(self) -> "_NAFunctions":
        """``df.na`` accessor (Spark ``DataFrameNaFunctions``):
        ``na.fill`` / ``na.drop`` / ``na.replace``."""
        return _NAFunctions(self)

    def intersect(self, other: "Frame") -> "Frame":
        """Distinct rows present in both frames (SQL INTERSECT, null-safe)."""
        if self.columns != other.columns:
            raise ValueError("intersect requires identical column lists")
        theirs = {k for k, _ in other._keyed_rows()}
        seen = set()
        rows = []
        for key, row in self._keyed_rows():
            if key in theirs and key not in seen:
                seen.add(key)
                rows.append(row)
        return Frame.from_rows(rows, self.columns)

    def except_all(self, other: "Frame") -> "Frame":
        """Rows of self not in other, preserving duplicates (EXCEPT ALL)."""
        if self.columns != other.columns:
            raise ValueError("exceptAll requires identical column lists")
        from collections import Counter

        budget = Counter(k for k, _ in other._keyed_rows())
        rows = []
        for key, row in self._keyed_rows():
            if budget[key] > 0:
                budget[key] -= 1
            else:
                rows.append(row)
        return Frame.from_rows(rows, self.columns)

    exceptAll = except_all

    def intersect_all(self, other: "Frame") -> "Frame":
        """Rows present in both frames, preserving duplicate counts
        (SQL INTERSECT ALL — each row appears min(count_self, count_other)
        times, null-safe like ``intersect``)."""
        if self.columns != other.columns:
            raise ValueError("intersectAll requires identical column lists")
        from collections import Counter

        budget = Counter(k for k, _ in other._keyed_rows())
        rows = []
        for key, row in self._keyed_rows():
            if budget[key] > 0:
                budget[key] -= 1
                rows.append(row)
        return Frame.from_rows(rows, self.columns)

    intersectAll = intersect_all

    def subtract(self, other: "Frame") -> "Frame":
        """Distinct rows of self not in other (SQL EXCEPT [DISTINCT])."""
        if self.columns != other.columns:
            raise ValueError("subtract requires identical column lists")
        theirs = {k for k, _ in other._keyed_rows()}
        seen = set()
        rows = []
        for key, row in self._keyed_rows():
            if key not in theirs and key not in seen:
                seen.add(key)
                rows.append(row)
        return Frame.from_rows(rows, self.columns)

    def replace(self, to_replace, value=None, subset=None) -> "Frame":
        """``df.replace`` — substitute exact values in [subset] columns.
        Accepts a scalar pair, a list + scalar, or a {old: new} dict."""
        if isinstance(to_replace, dict):
            mapping = to_replace
        elif isinstance(to_replace, (list, tuple)):
            if isinstance(value, (list, tuple)):  # PySpark list-to-list form
                if len(value) != len(to_replace):
                    raise ValueError(
                        f"replace: value list length {len(value)} != "
                        f"to_replace length {len(to_replace)}")
                mapping = dict(zip(to_replace, value))
            else:
                mapping = {v: value for v in to_replace}
        else:
            mapping = {to_replace: value}
        cols = subset if subset is not None else self.columns
        data = dict(self._data)
        for name in cols:
            arr = self._data[name]
            if _is_string_col(arr):
                str_map = {k: v for k, v in mapping.items()
                           if isinstance(k, str)}
                if str_map:
                    data[name] = np.asarray(
                        [str_map.get(x, x) for x in arr], dtype=object)
            else:
                num_map = {k: v for k, v in mapping.items()
                           if isinstance(k, (int, float))
                           and not isinstance(k, bool)}
                if num_map:
                    src = jnp.asarray(arr)  # converted ONCE; matches test
                    col = src               # against the original values
                    # replacing with None (null) or a float widens ints
                    if any(v is None or isinstance(v, float)
                           for v in num_map.values()) \
                            and not jnp.issubdtype(col.dtype, jnp.floating):
                        col = col.astype(float_dtype())
                    for old, new in num_map.items():
                        if new is None:
                            new = float("nan")
                        col = jnp.where(src == old,
                                        jnp.asarray(new, col.dtype), col)
                    data[name] = col
        return self._with(data=data)

    def with_columns(self, cols_map: Mapping[str, ColumnLike]) -> "Frame":
        """``withColumns`` — add/replace several columns at once. Every
        expression resolves against the *input* frame (Spark semantics), so
        a map that replaces a column and references it elsewhere sees the
        original values.

        When every expression is compilable the whole batch defers as ONE
        pipeline step — N expressions in one compiled program."""
        items = tuple(cols_map.items())
        if items and self._can_defer(*[v for _, v in items]):
            return self._defer(("with_columns", items))
        return self._with_columns_eager(cols_map)

    def _with_columns_eager(self, cols_map: Mapping[str, ColumnLike]) \
            -> "Frame":
        evaluated = {name: self._eval(values)
                     for name, values in cols_map.items()}
        data = dict(self._data)
        data.update(evaluated)
        return self._with(data=data)

    withColumns = with_columns

    def to_df(self, *names: str) -> "Frame":
        """``toDF`` — rename all columns positionally. Duplicate names are
        rejected (the columnar dict cannot represent them, unlike Spark)."""
        if len(names) != len(self.columns):
            raise ValueError(f"toDF expects {len(self.columns)} names, "
                             f"got {len(names)}")
        if len(set(names)) != len(names):
            raise ValueError(f"toDF names must be unique, got {list(names)}")
        data = {new: self._data[old]
                for new, old in zip(names, self.columns)}
        return self._with(data=data)

    toDF = to_df

    def summary(self, *stats: str) -> "Frame":
        """Spark's ``summary``: describe + percentiles. Default statistics:
        count, mean, stddev, min, 25%, 50%, 75%, max."""
        from .aggregates import AggExpr, global_agg

        if not stats:
            stats = ("count", "mean", "stddev", "min", "25%", "50%", "75%",
                     "max")
        cols = [name for name, arr in self._data.items()
                if not _is_string_col(arr) and arr.ndim == 1]
        data: dict[str, object] = {
            "summary": np.asarray(list(stats), dtype=object)}
        m = self._host_mask()
        plain = [s for s in stats if not s.endswith("%")]
        for c in cols:
            vals = np.asarray(self._data[c], np.float64)[m]
            vals = vals[~np.isnan(vals)]
            agg_row = {}
            if plain:  # one batched device reduction per column (cf describe)
                aggs = [AggExpr({"mean": "avg"}.get(s, s), c).alias(s)
                        for s in plain]
                d = global_agg(self, aggs).to_pydict()
                agg_row = {s: d[s][0] for s in plain}
            out = []
            for s in stats:
                if s.endswith("%"):
                    q = float(s[:-1]) / 100.0
                    out.append(str(np.quantile(vals, q)) if len(vals)
                               else "NaN")
                else:
                    out.append(str(agg_row[s]))
            data[c] = np.asarray(out, dtype=object)
        return Frame(data)

    def sample(self, fraction: float, seed: int = 0,
               with_replacement: bool = False) -> "Frame":
        """Row sample. Without replacement: Bernoulli mask — shapes stay
        static and the column arrays are shared. With replacement: Poisson
        counts per valid row (Spark's semantics; ``fraction`` is the
        expected copy count and may exceed 1), materialized by ONE gather
        into a NEW frame — this breaks mask/array sharing with the source
        and the result's row count is data-dependent."""
        if with_replacement:
            if fraction < 0.0:
                raise ValueError(f"fraction must be >= 0, got {fraction}")
            rng = np.random.default_rng(seed)
            counts = rng.poisson(fraction, self.num_slots)
            counts = np.where(self._host_mask(), counts, 0)
            idx = np.repeat(np.arange(self.num_slots), counts)
            data = {}
            for name, arr in self._data.items():
                if _is_string_col(arr):
                    data[name] = np.asarray(arr, object)[idx]
                else:
                    data[name] = jnp.take(jnp.asarray(arr),
                                          jnp.asarray(idx), axis=0)
            return Frame(data)
        if not 0.0 <= fraction <= 1.0:
            raise ValueError(f"fraction must be in [0, 1], got {fraction}")
        rng = np.random.default_rng(seed)
        keep = jnp.asarray(rng.random(self.num_slots) < fraction)
        return self._with(mask=jnp.logical_and(self._mask, keep))

    def random_split(self, weights: Sequence[float],
                     seed: int = 0) -> list["Frame"]:
        """Split rows into disjoint frames with the given relative weights —
        ``df.randomSplit([0.8, 0.2], seed)``, the MLlib train/test idiom.
        Each split shares the column arrays; only the masks differ."""
        w = np.asarray(weights, np.float64)
        if w.ndim != 1 or len(w) < 1 or np.any(w < 0) or w.sum() == 0:
            raise ValueError(f"invalid split weights {weights!r}")
        edges = np.cumsum(w / w.sum())
        rng = np.random.default_rng(seed)
        u = rng.random(self.num_slots)
        out = []
        lo = 0.0
        for hi in edges:
            pick = jnp.asarray((u >= lo) & (u < hi))
            out.append(self._with(mask=jnp.logical_and(self._mask, pick)))
            lo = hi
        return out

    randomSplit = random_split

    @op_span("frame.cache")
    def cache(self) -> "Frame":
        """Materialize and pin: flush any pending fused pipeline, then
        ``block_until_ready`` every device column and the validity mask.
        JAX dispatch is async — without the block, timing code around
        ``cache()`` would measure enqueue, not compute; this makes
        ``cache()`` an honest timing boundary
        (Spark parity: after ``cache().count()`` the data IS resident)."""
        arrs = [jnp.asarray(arr) for arr in self._data.values()
                if not _is_string_col(arr)]
        jax.block_until_ready(arrs + [self._mask])
        counters.increment("frame.cache")
        return self

    persist = cache

    def unpersist(self, blocking: bool = False) -> "Frame":
        return self

    def repartition(self, num_partitions: int, *cols) -> "Frame":
        """No-op for API parity: a device-mesh engine has no partition
        count — distribution happens at fit time via ``mesh=`` sharding,
        not by reshaping the frame."""
        return self

    def coalesce(self, num_partitions: int) -> "Frame":
        return self

    def hint(self, name: str, *parameters) -> "Frame":
        """No-op for API parity (broadcast/shuffle hints steer Spark's
        planner; XLA owns that choice here)."""
        return self

    def checkpoint(self, eager: bool = True) -> "Frame":
        """No-op for API parity: the frame IS materialized (eager engine);
        there is no lineage to truncate."""
        return self

    localCheckpoint = checkpoint
    local_checkpoint = checkpoint

    def alias(self, name: str) -> "Frame":
        """Record a frame alias (Spark ``alias``). Join disambiguation by
        alias-qualified columns is not supported — rename columns instead
        (``with_column_renamed``). Like Spark's (a plan-node property),
        the alias applies to THIS frame object; derived frames don't
        inherit it."""
        out = self._with()
        out._alias = name
        return out

    def explain(self, extended: bool = False, analyze: bool = False) -> None:
        """Describe the physical representation (the eager-engine analogue
        of Spark's plan dump): columns, dtypes, placement, mask stats.

        ``analyze=True`` additionally EXECUTES the frame's pending fused
        pipeline under a per-query stats collector and appends the
        measured flush profile — one line per recorded span (wall ms,
        rows, compile-vs-cache-hit verdict, host syncs, peak device
        bytes) plus the query-level counter deltas. An already-
        materialized frame reports an empty analyze section (nothing left
        to execute) — the informative call site is right after building a
        lazy op chain."""
        print(self.explain_string(extended=extended, analyze=analyze))

    def explain_string(self, extended: bool = False,
                       analyze: bool = False) -> str:
        """The text :meth:`explain` prints (testable surface)."""
        analyzed: list[str] = []
        if analyze:
            # run BEFORE the physical description below reads _data/_mask
            # (its count() would silently flush the pending steps outside
            # the measurement window)
            from ..config import config as _config
            from ..utils import observability as _obs
            from ..utils.logging import format_kv

            with _obs.query_stats(
                    sample_memory=_config.explain_memory) as qs:
                jax.block_until_ready(self._mask)   # flush + honest wait
            analyzed.append("== Analyzed ==")
            for s in qs.spans:
                attrs = {k: v for k, v in s.attrs.items() if v is not None}
                kv = format_kv(dur_ms=round((s.dur_us or 0) / 1e3, 3),
                               **attrs)
                analyzed.append(f"  {s.name}" + (f"  {kv}" if kv else ""))
            delta = qs.counter_delta()
            if delta:
                analyzed.append("  counters: " + format_kv(**delta))
            if not qs.spans:
                analyzed.append("  (nothing pending — frame already "
                                "materialized)")
        n_valid = self.count()
        lines = ["== Physical Frame =="]
        lines.append(f"row slots: {self.num_slots} (valid: {n_valid}, "
                     f"masked: {self.num_slots - n_valid})")
        if self._shard is not None:
            st = self._shard
            lines.append(
                f"layout: row-sharded over {st.devices} device(s), "
                f"{st.bucket} slot(s)/shard, rows/shard="
                f"{st.shard_counts()}")
        for name in self.columns:
            arr = self._data[name]
            kind = ("host/object" if _is_string_col(arr)
                    else f"device/{jnp.asarray(arr).dtype}")
            lines.append(f"  {name}: {kind}")
        if extended:
            devs = {getattr(d, "platform", "?")
                    for c in self._data.values() if hasattr(c, "devices")
                    for d in c.devices()}
            lines.append(f"devices: {sorted(devs) or ['host']}")
            lines.append("execution: eager columnar; filters are validity-"
                         "mask AND; XLA fuses expression chains under jit")
        return "\n".join(lines + analyzed)

    # -- actions -----------------------------------------------------------
    def count(self) -> int:
        """Number of valid (unmasked) rows."""
        # deliberately NOT a counted frame host boundary — the seed
        # contract, pinned by test_explain TestDisabledModeNoOp (count()
        # is the no-op-path probe there; counting it would make the probe
        # self-invalidating). It IS a blocking device->host read, so
        # host.reads / host.read_bytes count it and the host.read span
        # under frame.count shows how long the host waited.
        with span("frame.count", cat="action", rows_in=self._n) as s:
            total = jnp.sum(self._mask)
            with host_reading("frame.count") as rd:
                n = int(total)
                rd.done(total.dtype.itemsize)
            s.set(host_read_bytes=total.dtype.itemsize)
        return n

    def is_empty(self) -> bool:
        return self.count() == 0

    def _host_mask(self) -> np.ndarray:
        counters.increment("frame.host_sync")
        with host_reading("frame.mask") as rd:
            m = np.asarray(self._mask)
            rd.done(m.nbytes)
        return m

    @op_span("frame.to_pydict", cat="action")
    def to_pydict(self, limit: Optional[int] = None) -> dict[str, np.ndarray]:
        """Materialize valid rows on host (the gather happens here, once, at
        the host boundary — never inside the compute path).

        All device→host transfers batch into ONE ``jax.device_get`` of
        the column dict (mask included when no ``limit`` trims it first)
        instead of one sync per column; each batch counts as a
        ``frame.host_sync`` in ``profiling.counters``.

        ``limit`` gathers only the first N valid rows — ``take``/``show``
        use it so peeking at a large device-resident frame does not transfer
        the whole dataset.
        """
        if limit is not None:
            # the limit cut needs the mask on host BEFORE slicing columns:
            # one tiny mask sync, then one batched sync of the prefixes
            m = self._host_mask()
            keep = np.cumsum(m) <= limit
            m = m & keep
            upto = int(np.argmax(~keep)) if not keep.all() else len(m)
            m = m[:upto]
            device = {name: jnp.asarray(arr)[: len(m)]
                      for name, arr in self._data.items()
                      if not _is_string_col(arr)}
            pulled = _pull(device) if device else {}
        else:
            mask_key = "__mask__"
            while mask_key in self._data:       # paranoid name collision
                mask_key += "_"
            device = {name: arr for name, arr in self._data.items()
                      if not _is_string_col(arr)}
            device[mask_key] = self._mask
            pulled = _pull(device)              # ONE batched transfer
            m = np.asarray(pulled.pop(mask_key), bool)
        out = {}
        for name, arr in self._data.items():
            host = pulled[name] if name in pulled else arr[: len(m)]
            out[name] = np.asarray(host)[m]
        return out

    def collect(self, limit: Optional[int] = None) -> list[tuple]:
        d = self.to_pydict(limit)
        cols = [d[name] for name in self.columns]
        return [tuple(row) for row in zip(*cols)] if cols else []

    def take(self, n: int) -> list[tuple]:
        return self.collect(limit=n)

    def head(self, n: int = 1):
        rows = self.take(n)
        return rows if n != 1 else (rows[0] if rows else None)

    def first(self):
        return self.head(1)

    def tail(self, n: int) -> list[tuple]:
        """Last ``n`` valid rows (Spark ``tail``)."""
        rows = self.collect()
        return rows[-n:] if n > 0 else []

    def to_pandas(self):
        """Materialize as a pandas DataFrame (Spark ``toPandas``): string
        columns stay object dtype, numeric columns keep the engine's
        device dtypes, and vector columns (2D, e.g. an assembled
        ``features``) become per-row arrays in an object column — the
        shape Spark's toPandas gives vector UDTs."""
        import pandas as pd

        d = self.to_pydict()
        out = {}
        for k, v in d.items():
            arr = np.asarray(v) if not _is_string_col(v) else v
            if getattr(arr, "ndim", 1) > 1:
                col = np.empty(len(arr), dtype=object)
                for i in range(len(arr)):
                    col[i] = np.asarray(arr[i])
                arr = col
            out[k] = arr
        return pd.DataFrame(out, columns=self.columns)

    toPandas = to_pandas

    def to_json(self) -> list[str]:
        """One JSON object string per valid row (Spark ``toJSON``; a list,
        not an RDD — this engine has no lazy distributed collection).
        NaN/None become JSON null; numpy scalars coerce to Python."""
        import json
        import math

        def _coerce(v):
            if v is None:
                return None
            if isinstance(v, (np.floating, float)):
                f = float(v)
                return None if math.isnan(f) else f
            if isinstance(v, (np.integer, int)):
                return int(v)
            if isinstance(v, (np.bool_, bool)):
                return bool(v)
            if isinstance(v, np.ndarray):
                return [_coerce(x) for x in v.tolist()]
            return v

        cols = self.columns
        return [json.dumps({c: _coerce(v) for c, v in zip(cols, row)})
                for row in self.collect()]

    toJSON = to_json

    def foreach(self, f) -> None:
        """Apply ``f`` to every valid row host-side (Spark ``foreach`` —
        eager here, no executors)."""
        for row in self.collect():
            f(row)

    def foreach_partition(self, f) -> None:
        """Apply ``f`` to an iterator over all valid rows (Spark
        ``foreachPartition``; this engine is one partition)."""
        f(iter(self.collect()))

    foreachPartition = foreach_partition

    # -- display -----------------------------------------------------------
    def _format_cell(self, v, truncate: int) -> str:
        if isinstance(v, (np.floating, float)):
            if np.isnan(v):
                s = "NaN"
            elif isinstance(v, np.floating):
                # shortest round-trip repr at the column's own precision, so
                # float32 23.1 prints "23.1" (as Spark's double toString would)
                s = np.format_float_positional(v, unique=True, trim="0")
            else:
                s = repr(float(v))
        elif isinstance(v, (np.bool_, bool)):
            s = "true" if v else "false"
        elif isinstance(v, (np.integer, int)):
            s = str(int(v))
        elif isinstance(v, np.ndarray):  # vector cell, shown Spark-style: [40.0]
            s = "[" + ",".join(
                np.format_float_positional(x, unique=True, trim="0")
                if isinstance(x, np.floating) else str(x) for x in v) + "]"
        elif v is None:
            s = "null"
        else:
            s = str(v)
        if truncate > 0 and len(s) > truncate:
            s = s[: truncate - 3] + "..." if truncate > 3 else s[:truncate]
        return s

    def show_string(self, n: int = None, truncate: Union[bool, int] = True) -> str:
        """Spark-format ASCII table (right-aligned cells, +---+ borders,
        ``only showing top N rows`` footer)."""
        if n is None:
            n = config.default_show_rows
        tr = 20 if truncate is True else (0 if truncate is False else int(truncate))
        total = int(self._host_mask().sum())
        d = self.to_pydict(limit=n)  # gather only what is displayed
        names = self.columns
        rows = []
        shown = len(next(iter(d.values()))) if d else 0
        for i in range(shown):
            rows.append([self._format_cell(d[name][i], tr) for name in names])
        headers = [name if tr <= 0 or len(name) <= tr else name[: tr - 3] + "..."
                   for name in names]
        widths = [max([len(h)] + [len(r[j]) for r in rows]) for j, h in enumerate(headers)]
        sep = "+" + "+".join("-" * w for w in widths) + "+"
        out = [sep, "|" + "|".join(h.rjust(w) for h, w in zip(headers, widths)) + "|", sep]
        for r in rows:
            out.append("|" + "|".join(c.rjust(w) for c, w in zip(r, widths)) + "|")
        out.append(sep)
        text = "\n".join(out) + "\n"
        if total > n:
            text += f"only showing top {n} rows\n"
        return text

    def show(self, n: int = None, truncate: Union[bool, int] = True) -> None:
        print(self.show_string(n, truncate))

    def __repr__(self):
        fields = ", ".join(f"{name}: {t}" for name, t in self.dtypes())
        return f"Frame[{fields}]"

    # -- aggregation / reshaping ------------------------------------------
    def group_by(self, *keys: str):
        """``groupBy`` — returns a GroupedFrame with agg/count/avg/... ."""
        from .aggregates import GroupedFrame

        return GroupedFrame(self, list(keys))

    groupBy = group_by

    def map_in_pandas(self, func, schema):
        """Spark 3's ``mapInPandas(fn, schema)``: ``func`` receives an
        iterator of pandas DataFrame batches (one batch here — the frame
        is already fully resident) and yields output batches, concatenated
        and cast to the DDL ``schema``. Host-boundary escape hatch like
        ``applyInPandas``; the fused column path remains the fast lane."""
        import pandas as pd

        from .csv import parse_ddl_schema

        fields = parse_ddl_schema(schema) if isinstance(schema, str) \
            else list(schema)
        outs = [b for b in func(iter([self.to_pandas()]))]
        for b in outs:
            if not isinstance(b, pd.DataFrame):
                raise TypeError("mapInPandas function must yield pandas "
                                f"DataFrames, got {type(b).__name__}")
        names = [n for n, _ in fields]
        if outs:
            cat = pd.concat(outs, ignore_index=True)
            missing = [n for n in names if n not in cat.columns]
            if missing:
                raise ValueError(f"mapInPandas output is missing schema "
                                 f"columns {missing}")
            data = {n: cat[n].to_numpy() for n in names}
        else:
            data = {n: np.asarray([], np.float64) for n in names}
        out = Frame(data)
        for name, tname in fields:
            out = out.with_column(name, out.col(name).cast(tname))
        return out

    mapInPandas = map_in_pandas

    def rollup(self, *keys: str):
        """``rollup`` — hierarchical subtotals: every key prefix plus the
        grand total, absent keys null (Spark ROLLUP)."""
        from .aggregates import MultiGroupedFrame, rollup_levels

        return MultiGroupedFrame(self, list(keys), rollup_levels(list(keys)))

    def cube(self, *keys: str):
        """``cube`` — subtotals for EVERY key subset (Spark CUBE)."""
        from .aggregates import MultiGroupedFrame, cube_levels

        return MultiGroupedFrame(self, list(keys), cube_levels(list(keys)))

    @op_span("frame.agg")
    def agg(self, *aggs):
        """Global aggregates (no grouping): masked device reductions.
        Accepts AggExprs, bare fn names, or PySpark's dict form
        (``agg({'v': 'avg'})``)."""
        from .aggregates import (AggExpr, _dict_aggs, global_agg,
                                 materialize_agg_exprs)

        if len(aggs) == 1 and isinstance(aggs[0], dict):
            aggs = tuple(_dict_aggs(aggs[0]))
        agg_list = [a if isinstance(a, AggExpr) else AggExpr(a, None)
                    for a in aggs]
        frame, agg_list = materialize_agg_exprs(self, agg_list)
        return global_agg(frame, agg_list)

    @op_span("frame.sort")
    def sort(self, *cols, ascending=True) -> "Frame":
        """``orderBy`` — reorders valid rows (host argsort at the boundary),
        dropping masked slots (the result is compact). Columns may be
        names, ``Col``s, or ``col.asc()``/``col.desc()`` (+
        ``*_nulls_first/last``) sort markers — a marker's direction and
        null placement override ``ascending`` for that column. Default
        null placement is Spark's: nulls first ascending, last
        descending (NaN is the numeric null)."""
        from ..ops.expressions import SortOrder

        if not cols:
            raise ValueError("sort requires at least one column")
        asc = ([ascending] * len(cols) if isinstance(ascending, bool)
               else list(ascending))
        if len(asc) != len(cols):
            raise ValueError("ascending list must match columns")
        nulls_first: list = [None] * len(cols)
        resolved = []
        for i, c in enumerate(cols):
            if isinstance(c, SortOrder):
                name = c.name
                asc[i] = c.ascending
                nulls_first[i] = c.nulls_first
            elif isinstance(c, str):
                name = c
            else:
                name = c.name  # Col / aliased expr
            if name not in self.columns:
                raise ValueError(
                    f"sort key {name!r} is not a column of this frame "
                    "(sorting by a computed expression is not supported — "
                    "add it with with_column first)")
            resolved.append(name)
        cols = resolved
        if self.num_slots == 0:
            return self          # no row to order (an empty GROUP BY result)
        # Device path (ops/segments.py): numeric sort keys compute the
        # permutation on device (jax.lax.sort) and gather payload with
        # jnp.take — one host sync (the valid-row count) instead of the
        # full round trip. String keys / failures take the host lexsort.
        from ..ops import segments

        out = segments.try_device(
            "sort", lambda: segments.device_sort(self, cols, asc,
                                                 nulls_first))
        if out is not None:
            return out
        d = self.to_pydict()
        order = np.lexsort(lexsort_keys([d[c] for c in cols], asc,
                                        nulls_first))
        return Frame({name: (vals[order] if vals.dtype == object
                             else np.asarray(vals)[order])
                      for name, vals in d.items()})

    orderBy = sort
    # one partition: sorting "within partitions" IS a total sort here
    sortWithinPartitions = sort
    sort_within_partitions = sort
    order_by = sort

    @op_span("frame.distinct")
    def distinct(self) -> "Frame":
        """Unique valid rows (result compact, order of first occurrence).
        Null-safe like Spark: null rows equal each other, so duplicates
        with NaN/None cells collapse too. All-numeric frames dedup on
        device (ops/segments.py: one sort + boundary program, one host
        sync); any string column falls back to the host row walk."""
        from ..ops import segments

        out = segments.try_device(
            "distinct", lambda: segments.device_unique(self, self.columns))
        if out is not None:
            return out
        seen = set()
        out = []
        for key, r in self._keyed_rows():
            if key not in seen:
                seen.add(key)
                out.append(r)
        return Frame.from_rows(out, self.columns)

    @op_span("frame.drop_duplicates")
    def drop_duplicates(self, subset=None) -> "Frame":
        """Spark ``dropDuplicates``: with ``subset``, keep the FIRST valid
        row per distinct key combination (all columns retained); without,
        identical to :meth:`distinct`."""
        if subset is None:
            return self.distinct()
        if isinstance(subset, str):
            subset = [subset]
        for c in subset:
            if c not in self.columns:
                raise ValueError(f"dropDuplicates column {c!r} not found")
        # Numeric 1-D subset keys dedup on device (same kernel as
        # distinct); vector-cell keys stay host-side — the host path
        # treats NaN components of a vector cell as distinct (NaN != NaN
        # inside the tuple key) while scalar NaN keys fold, and the
        # device kernel implements only the scalar fold.
        if all(getattr(self._data.get(c), "ndim", 1) == 1
               for c in subset):
            from ..ops import segments

            out = segments.try_device(
                "drop_duplicates",
                lambda: segments.device_unique(self, subset))
            if out is not None:
                return out
        idx = np.nonzero(self._host_mask())[0]
        seen = set()
        keep = []
        keycols = _pull_keys(self, subset, "distinct.keys")

        def cell_key(cell):
            a = np.asarray(cell)
            if a.ndim:
                return tuple(a.ravel().tolist())
            x = a.item() if hasattr(a, "item") else cell
            # NaN = SQL NULL throughout this engine: null keys form ONE
            # group (NaN != NaN would keep every null-key duplicate)
            if isinstance(x, float) and x != x:
                return None
            return x

        for pos in idx:
            key = tuple(cell_key(k[pos]) for k in keycols)
            if key not in seen:
                seen.add(key)
                keep.append(pos)
        keep_idx = np.asarray(keep, np.int64)
        keep_dev = jnp.asarray(keep_idx)  # one host→device transfer, not
        data = {}                         # one per gathered column
        for name in self.columns:
            arr = self._data[name]
            if _is_string_col(arr):
                data[name] = np.asarray(arr, dtype=object)[keep_idx]
            else:
                data[name] = jnp.take(jnp.asarray(arr), keep_dev, axis=0)
        return Frame(data)

    dropDuplicates = drop_duplicates

    @op_span("frame.join")
    def join(self, other: "Frame", on, how: str = "inner",
             build: Optional[str] = None,
             est: Optional[tuple] = None) -> "Frame":
        """Relational join on key column(s).

        ``how``: ``inner`` | ``left`` | ``right`` | ``outer``/``full`` |
        ``left_semi`` | ``left_anti`` | ``cross``. ``on`` is a column name
        or a list of them; a key is a name both frames carry (Spark's
        ``USING``: the key column appears once in the result) or a pair
        ``(left name, right name)`` (SQL's ``ON a = b``: the right frame's
        column takes the left name in its column table — no copy — and an
        inner join's result carries the right name as a second name of the
        same column; a left/right/outer join keeps the right key as a
        column of its own, null where its row is missing). A non-key
        column name present on both sides keeps the left column and
        surfaces the right one as ``<name>_right`` (explicit, instead of
        Spark's ambiguous duplicate).

        Only valid (mask=True) rows take part. **Where the plan is made**
        follows from what the frames are: numeric keys of one kind on
        both sides (integers with integers, floats with floats), ``how``
        one of ``inner`` / ``left`` / ``left_semi`` / ``left_anti``,
        neither side sharded and every column to gather on the device ->
        the match plan, the pair order and the column gathers are one
        compiled program (``ops/joins.py``: a sort-merge over
        ``lax.sort``). Its build step brings both sides' keys into one
        order either by one sort of their concatenation or, where the
        probe side arrives in key order, by a merge: the build side
        sorted alone, the probe side in chunks sorted beside their share
        of it. Where an inner or semi join's build side is a few thousand
        keys against such a probe side, a lookup takes the merge's place:
        each sorted build key is searched into the probe keys and the
        pairs are laid out from the runs found, with nothing of the probe
        side's size sorted. Shapes (one key column, a probe side of many
        chunks and about twelve times the build side's slots, for the
        lookup far more) and the observed order of the
        input decide; no option does, and the result is the same bit for
        bit. The host reads one small array: the result's row count and,
        behind a merge or a lookup, whether its probe keys were in order
        and its chunks or slots had room — where the order or a merge's
        room did not hold (``join.merge_miss``) the join runs once more as
        the sort, and that signature's later runs start from what was
        learnt. The span
        says which ``build_step`` the result came from (``join.merge``
        and ``join.lookup`` count them). The result is a frame of
        a bucket of slots under a mask.
        String keys, an integer key against a float one, ``right`` /
        ``outer`` / ``cross``, a sharded side and host (string) columns to
        gather keep the **host plan**: masks and key columns are pulled,
        ``_vector_join_plan`` or the dict plan pairs the rows, and only
        the ``jnp.take`` gathers run on the device. Every join counts
        ``join.device`` or ``join.host`` and names its ``lowering`` in the
        ``frame.join`` span; every blocking read on either path counts
        ``host.reads`` / ``host.read_bytes``.

        Emission order is the same on both paths: the pairs in (left row,
        right row) order; unmatched right rows of right/outer joins
        appended in order. Unmatched slots fill with NaN (numeric, int
        promotes to float) or None (string).

        ``build="left"`` (cost-based optimizer hint, inner joins only):
        build from the LEFT side — the win when the left is the small
        side; without a hint the device plan builds from the side with
        fewer slots. The result is bit-identical either way.

        ``est=(left_rows, right_rows)`` (adaptive execution input,
        ``sql/adaptive.py``): the optimizer's pre-execution row
        estimates for the two sides. When ``spark.aqe.enabled`` and the
        OBSERVED valid-row counts (the host plan's pulled masks; on the
        device path two scalars read for this) drift past
        ``spark.aqe.driftFactor``, the build side re-decides mid-query
        and a small-enough observed build side skips the hash-partition
        shuffle. ``None`` (or AQE off) keeps the static plan.
        """
        how = how.lower().replace("fullouter", "outer").replace("full", "outer")
        valid = ("inner", "left", "right", "outer", "left_semi", "left_anti",
                 "cross")
        if how not in valid:
            raise ValueError(f"unknown join type {how!r}; expected one of {valid}")
        build_left = build == "left" and how == "inner"
        on = [on] if isinstance(on, str) else list(on or [])
        keys = [k if isinstance(k, str) else k[0] for k in on]
        pairs = [(k[0], k[1]) for k in on
                 if not isinstance(k, str) and k[0] != k[1]]
        if how != "cross" and not keys:
            raise ValueError("join requires `on` key column(s)")
        if how == "cross":
            keys, pairs = [], []
        for lname, rname in pairs:
            if rname not in other.columns:
                raise ValueError(f"join key {rname!r} must exist in the "
                                 "right frame")
            if lname in other.columns:
                raise ValueError(
                    f"join on {lname!r} = {rname!r}: {lname!r} is a shared "
                    "column name of both frames, and the right frame's "
                    "would be lost (rename or drop it first)")
        if pairs:
            # the right key takes the left name in the column table (host
            # dict only); outer types keep it as a column of its own too
            renames = dict((r, l) for l, r in pairs)
            keep_right_key = how in ("left", "right", "outer")
            table = {}
            for name, arr in other._data.items():
                table[renames.get(name, name)] = arr
                if name in renames and keep_right_key:
                    table[name] = arr
            other = other._with(data=table)
        for k in keys:
            if k not in self.columns or k not in other.columns:
                raise ValueError(f"join key {k!r} must exist in both frames")

        joined = self._device_join(other, keys, how, build_left, est)
        if joined is None:
            joined = self._host_join(other, keys, how, build_left, est)
        if how == "inner":
            for lname, rname in pairs:  # one column, two names
                out_name = rname + "_right" if rname in joined._data_store \
                    else rname
                joined._data_store[out_name] = joined._data_store[lname]
        return joined

    @staticmethod
    def _join_columns(keys, left_cols, right_cols, mask):
        """The result frame of a join from the two sides' gathered
        columns: the left side's, then the right side's without the keys,
        a name both sides carry suffixed ``_right``."""
        data = dict(left_cols)
        for name, col in right_cols.items():
            if name in keys:
                continue
            data[name + "_right" if name in data else name] = col
        return Frame(data, mask=mask)

    def _aqe_build_side(self, other, build_left, est, nl, nr):
        """Adaptive re-planning (sql/adaptive.py) from both sides' TRUE
        valid-row counts ``nl`` / ``nr``: when either drifted past
        spark.aqe.driftFactor from the optimizer's estimate, the build
        side re-decides from the observed counts, and an observed build
        side under spark.aqe.broadcastThreshold bytes skips the
        hash-partition shuffle entirely (the partitioned plan reproduces
        the unpartitioned emission order exactly, so skipping it is the
        identity transform). Returns ``(build_left, skip_shuffle)``."""
        from ..sql import adaptive as _aqe

        skip_shuffle = False
        left_est, right_est = est
        if _aqe.drift(left_est, nl) or _aqe.drift(right_est, nr):
            want_left = nl * _aqe.BUILD_RATIO <= nr
            if want_left != build_left and _aqe.guard("build-flip"):
                _aqe.record(
                    "build-flip",
                    f"join build={'left' if want_left else 'right'}"
                    f" (observed {nl} vs {nr} rows)",
                    est_before=(left_est if want_left else right_est),
                    est_after=(int(nl) if want_left else int(nr)))
                build_left = want_left
            store_hint = (self._shard if self._shard is not None
                          else other._shard)
            if store_hint is not None and \
                    max(nl, nr) >= int(config.shard_min_rows):
                b_rows = int(min(nl, nr))
                b_frame = self if nl <= nr else other
                b_bytes = b_rows * _aqe.row_nbytes(b_frame)
                if b_bytes <= int(config.aqe_broadcast_threshold) \
                        and _aqe.guard("broadcast"):
                    _aqe.record(
                        "broadcast",
                        "hash-partition Exchange skipped (observed"
                        f" build side {b_rows} rows ~{b_bytes} B "
                        "fits spark.aqe.broadcastThreshold)",
                        est_before=(left_est if nl <= nr else right_est),
                        est_after=b_rows)
                    skip_shuffle = True
        return build_left, skip_shuffle

    def _device_join(self, other, keys, how, build_left, est):
        """The join planned and gathered on the device (``ops/joins.py``),
        or None where the host plan has to take it (see :meth:`join`)."""
        from ..ops import joins as _joins

        if how not in _joins.DEVICE_HOWS or self._shard is not None \
                or other._shard is not None \
                or self.num_slots == 0 or other.num_slots == 0:
            return None
        ldata, rdata = self._data, other._data
        dtypes = []
        for k in keys:
            lk, rk = ldata[k], rdata[k]
            if _is_string_col(lk) or _is_string_col(rk):
                return None
            dt = _joins.key_dtype(lk, rk)
            if dt is None:
                return None
            dtypes.append(dt)
        # a semi or anti join gathers nothing of the right side
        right_names = [] if how in ("left_semi", "left_anti") \
            else [n for n in rdata if n not in keys]
        if any(_is_string_col(v) for v in list(ldata.values())
               + [rdata[n] for n in right_names]):
            return None
        lmask, rmask = self._mask, other._mask
        sp = current_span()
        if how == "inner":
            if est is not None and config.aqe_enabled:
                # the observed counts the adaptive hooks compare with the
                # optimizer's estimates: two scalars, not two masks
                counts = jnp.stack([jnp.sum(lmask, dtype=jnp.int32),
                                    jnp.sum(rmask, dtype=jnp.int32)])
                # two scalars where the host plan pulls both masks
                with host_reading("join.count") as rd:
                    nl, nr = (int(c) for c in np.asarray(counts))
                    rd.done(counts.nbytes)
                build_left, _ = self._aqe_build_side(
                    other, build_left, est, nl, nr)
            elif not build_left:
                # no hint: build from the side with fewer slots
                build_left = self.num_slots * 2 <= other.num_slots

        def distinct(names, data):
            """A column once, whatever names it has (an inner ``ON``
            join's key carries two)."""
            at, arrays = {}, []
            for name in names:
                if id(data[name]) not in at:
                    at[id(data[name])] = len(arrays)
                    arrays.append(data[name])
            return [at[id(data[name])] for name in names], arrays

        lat, larrays = distinct(list(ldata), ldata)
        rat, rarrays = distinct(right_names, rdata)
        lout, rout, mask, rows, missing = _joins.device_join(
            how, [ldata[k] for k in keys], lmask,
            [rdata[k] for k in keys], rmask, larrays, rarrays,
            build_left, tuple(dtypes))
        counters.increment("join.device")
        sp.set(how=how, lowering="device", keys=len(keys),
               rows_left=self.num_slots, rows_right=other.num_slots,
               build="left" if build_left else "right", rows_out=rows)
        if missing is not None:
            # a left join's unmatched rows: NaN where the right side is
            # missing (an integer column promotes to float)
            filled = []
            for col in rout:
                if not np.issubdtype(np.dtype(col.dtype), np.floating):
                    col = col.astype(float_dtype())
                filled.append(jnp.where(
                    missing[(...,) + (None,) * (col.ndim - 1)],
                    jnp.asarray(np.nan, col.dtype), col))
            rout = filled
        if rows == mask.shape[0]:
            mask = None                   # every slot holds a row
        return self._join_columns(
            keys, {name: lout[i] for name, i in zip(ldata, lat)},
            {name: rout[i] for name, i in zip(right_names, rat)}, mask)

    def _host_join(self, other, keys, how, build_left, est):
        """The join planned on the host: both masks and every key column
        are pulled, ``_vector_join_plan`` (numeric keys) or the dict plan
        (string keys) pairs the rows, and the columns are gathered on the
        device from the pair arrays. What :meth:`join` keeps here: string
        keys, ``right`` / ``outer`` / ``cross``, sharded frames."""
        counters.increment("join.host")
        current_span().set(how=how, lowering="host", keys=len(keys),
                           rows_left=self.num_slots,
                           rows_right=other.num_slots)
        li = np.nonzero(self._host_mask())[0]
        ri = np.nonzero(other._host_mask())[0]

        # Adaptive re-planning: the host plan already holds both sides'
        # TRUE valid-row counts — zero extra syncs. One conf read when AQE
        # is off; a cold estimate (est None) changes nothing.
        aqe_skip_shuffle = False
        if est is not None and how == "inner" and config.aqe_enabled:
            build_left, aqe_skip_shuffle = self._aqe_build_side(
                other, build_left, est, li.size, ri.size)

        if how == "cross":
            lpairs = np.repeat(li, len(ri))
            rpairs = np.tile(ri, len(li))
        elif ri.size == 0:
            # Empty group table (right side has zero valid rows): the
            # plan is fully determined without building one — inner /
            # right / semi match nothing, left / outer / anti keep every
            # left row (null-filled right columns via the -1 sentinel).
            # Guarding here keeps the searchsorted clamp in
            # _vector_join_plan (gvals.size - 1) unreachable at size 0.
            if how in ("inner", "right", "left_semi"):
                lpairs = np.empty(0, np.int64)
                rpairs = np.empty(0, np.int64)
            else:                       # left / outer / left_anti
                lpairs = li.astype(np.int64)
                rpairs = np.full(li.size, -1, np.int64)
        else:
            # key columns materialize ONCE; the vector plan and the dict
            # fallback share them (a plan bail-out must not re-read).
            # Each side's device-key pull counts as one host sync batch.
            lraw, rraw = [], []
            for fr, rows, raw in ((self, li, lraw), (other, ri, rraw)):
                cols = _pull_keys(fr, keys, "join.keys")
                raw.extend(c[rows] for c in cols)
            plan = None
            if all(not _is_string_col(self._data[k])
                   and not _is_string_col(other._data[k]) for k in keys):
                # Hash-partition shuffle lowering (sharded frames, above
                # the spark.shard.minRows host-fallback bound): the plan
                # computes per key-hash partition and merges back into
                # the exact unpartitioned emission order — the Exchange
                # EXPLAIN renders. Any partition bail-out (inexact keys)
                # falls through to the single plan below.
                store = self._shard if self._shard is not None \
                    else other._shard
                planner = ((lambda *a: _vector_join_plan(
                    *a, build_left=True)) if build_left
                    else _vector_join_plan)
                if store is not None and not aqe_skip_shuffle and \
                        max(li.size, ri.size) >= int(config.shard_min_rows):
                    from ..parallel.shard import partitioned_join_plan

                    plan = partitioned_join_plan(
                        planner, lraw, rraw, li, ri, how,
                        store.devices)
                if plan is None:
                    plan = planner(lraw, rraw, li, ri, how)
            if plan is not None:
                lpairs, rpairs = plan
            elif build_left:
                # hinted build-from-left dict plan (string keys): build
                # the table over the small left side, probe with the
                # right, and re-canonicalize to the default plan's
                # (left, right)-lexicographic inner emission order
                ltable: dict = {}
                lkeys = list(zip(*[c.tolist() for c in lraw]))
                for pos, kt in zip(li, lkeys):
                    ltable.setdefault(kt, []).append(pos)
                rkeys = list(zip(*[c.tolist() for c in rraw]))
                lp, rp = [], []
                for rpos, kt in zip(ri, rkeys):
                    for lpos in ltable.get(kt, ()):
                        lp.append(lpos)
                        rp.append(rpos)
                lpairs = np.asarray(lp, np.int64)
                rpairs = np.asarray(rp, np.int64)
                order = np.lexsort((rpairs, lpairs))
                lpairs, rpairs = lpairs[order], rpairs[order]
            else:
                rkeys = list(zip(*[c.tolist() for c in rraw]))
                table: dict = {}
                for pos, kt in zip(ri, rkeys):
                    table.setdefault(kt, []).append(pos)
                lkeys = list(zip(*[c.tolist() for c in lraw]))
                lp, rp = [], []
                matched_r = set()
                for pos, kt in zip(li, lkeys):
                    hits = table.get(kt)
                    if hits:
                        if how == "left_anti":
                            continue
                        if how == "left_semi":
                            lp.append(pos)
                            rp.append(hits[0])
                            continue
                        for rpos in hits:
                            lp.append(pos)
                            rp.append(rpos)
                            matched_r.add(rpos)
                    elif how in ("left", "outer", "left_anti"):
                        lp.append(pos)
                        rp.append(-1)
                if how in ("right", "outer"):
                    for pos in ri:
                        if pos not in matched_r:
                            lp.append(-1)
                            rp.append(pos)
                lpairs = np.asarray(lp, np.int64)
                rpairs = np.asarray(rp, np.int64)

        def gather(frame, idx, fill_missing):
            """Materialize frame columns at idx; idx == -1 ⇒ null fill."""
            missing = idx < 0
            safe = np.where(missing, 0, idx)
            safe_dev = jnp.asarray(safe)       # ONE host→device transfer
            miss_dev = (jnp.asarray(missing)   # shared across all columns
                        if fill_missing and missing.any() else None)
            out = {}
            if frame.num_slots == 0 and len(idx):
                # gathering from an EMPTY side (e.g. left join against an
                # empty right frame): every idx is -1; jnp.take from a
                # zero-length axis raises, so synthesize the null columns
                for name in frame.columns:
                    arr = frame._data[name]
                    if _is_string_col(arr):
                        out[name] = np.full(len(idx), None, dtype=object)
                    else:
                        a = jnp.asarray(arr)
                        out[name] = jnp.full((len(idx),) + a.shape[1:],
                                             jnp.nan, float_dtype())
                return out
            for name in frame.columns:
                arr = frame._data[name]
                if _is_string_col(arr):
                    col = arr[safe]
                    if fill_missing and missing.any():
                        col = col.copy()
                        col[missing] = None
                    out[name] = col
                else:
                    col = jnp.take(jnp.asarray(arr), safe_dev, axis=0)
                    if miss_dev is not None:
                        if not np.issubdtype(np.dtype(col.dtype), np.floating):
                            col = col.astype(float_dtype())
                        nan = jnp.asarray(np.nan, col.dtype)
                        col = jnp.where(
                            miss_dev[(...,) + (None,) * (col.ndim - 1)],
                            nan, col)
                    out[name] = col
            return out

        current_span().set(rows_out=int(lpairs.size))
        left_cols = gather(self, lpairs, how in ("right", "outer"))
        if how in ("left_semi", "left_anti"):
            return Frame(left_cols)
        right_cols = gather(other, rpairs, how in ("left", "outer", "left_anti"))
        data = left_cols
        if how in ("right", "outer") and lpairs.size and (lpairs < 0).any():
            # USING semantics: one key column, coalesced from the non-null
            # side (rows appended for unmatched right rows have lpairs == -1).
            miss = lpairs < 0
            for k in keys:
                lk, rk = data[k], right_cols[k]
                if _is_string_col(lk) or _is_string_col(rk):
                    data[k] = np.where(miss, np.asarray(rk, dtype=object),
                                       np.asarray(lk, dtype=object))
                else:
                    data[k] = jnp.where(jnp.asarray(miss),
                                        jnp.asarray(rk).astype(lk.dtype), lk)
        return self._join_columns(keys, data, right_cols, None)

    def cross_join(self, other: "Frame") -> "Frame":
        return self.join(other, on=None, how="cross")

    crossJoin = cross_join

    def dropna(self, how="any", thresh=None, subset=None) -> "Frame":
        """Mask out null rows (Spark ``dropna`` / ``na.drop`` signature:
        ``how`` "any"|"all", ``thresh`` = minimum non-null count which
        overrides ``how``, ``subset`` = columns considered). NaN (float) /
        None (string) count as null; stays static-shaped like ``filter``.
        A list first argument is accepted as a legacy positional
        ``subset``."""
        if isinstance(how, (list, tuple)):
            subset, how = list(how), "any"
        if how not in ("any", "all"):
            raise ValueError(f"how={how!r}; expected 'any' or 'all'")
        cols = subset if subset is not None else self.columns
        nonnull = jnp.zeros((self._n,), jnp.int32)
        for name in cols:
            arr = self._column_values(name)
            if _is_string_col(arr):
                ok = jnp.asarray([x is not None for x in arr])
            elif np.issubdtype(np.dtype(arr.dtype), np.floating):
                flat_nan = jnp.isnan(arr)
                if flat_nan.ndim > 1:
                    flat_nan = flat_nan.any(axis=tuple(range(1, flat_nan.ndim)))
                ok = jnp.logical_not(flat_nan)
            else:
                ok = jnp.ones((self._n,), jnp.bool_)  # ints have no null
            nonnull = nonnull + ok.astype(jnp.int32)
        if thresh is not None:
            keep = nonnull >= int(thresh)
        elif how == "all":
            keep = nonnull > 0
        else:
            keep = nonnull == len(cols)
        return self._with(mask=jnp.logical_and(self._mask, keep))

    def fillna(self, value, subset=None) -> "Frame":
        """Replace NaN/None with ``value`` in [subset] columns. A dict
        ``value`` maps column -> fill value per column (Spark's common
        ``na.fill({'col': 0.0})`` form; ``subset`` is ignored then, like
        Spark)."""
        if isinstance(value, dict):
            out = self
            for name, v in value.items():
                out = out.fillna(v, subset=[name])
            return out
        cols = subset if subset is not None else self.columns
        data = dict(self._data)
        for name in cols:
            arr = self._data[name]
            if _is_string_col(arr):
                if isinstance(value, str):
                    data[name] = np.asarray(
                        [value if x is None else x for x in arr], dtype=object)
            elif np.issubdtype(np.dtype(arr.dtype), np.floating) and \
                    isinstance(value, (int, float)):
                data[name] = jnp.where(jnp.isnan(arr),
                                       jnp.asarray(value, arr.dtype), arr)
        return self._with(data=data)

    def describe(self, *cols: str) -> "Frame":
        """Spark's ``describe``: count/mean/stddev/min/max summary rows.
        String columns describe like Spark's — non-null count and
        lexicographic min/max, with null mean/stddev cells."""
        from .aggregates import AggExpr, global_agg

        if not cols:
            cols = tuple(name for name, arr in self._data.items()
                         if arr.ndim == 1)
        stats = ["count", "mean", "stddev", "min", "max"]
        fns = [{"mean": "avg"}.get(s, s) for s in stats]
        data: dict[str, object] = {"summary": np.asarray(stats, dtype=object)}
        m = self._host_mask()
        for c in cols:
            arr = self._data[c]
            if _is_string_col(arr):
                vals = [x for x in np.asarray(arr, object)[m]
                        if x is not None]
                data[c] = np.asarray(
                    [str(len(vals)), None, None,
                     (min(vals) if vals else None),
                     (max(vals) if vals else None)], dtype=object)
                continue
            aggs = [AggExpr(fn, c).alias(fn) for fn in fns]
            row = global_agg(self, aggs).to_pydict()  # one sync per column
            data[c] = np.asarray([str(row[fn][0]) for fn in fns], dtype=object)
        return Frame(data)

    # -- statistics --------------------------------------------------------
    @property
    def stat(self):
        """``df.stat`` — corr/cov/approxQuantile/crosstab/freqItems
        (Spark's DataFrameStatFunctions)."""
        from .stat import FrameStatFunctions

        return FrameStatFunctions(self)

    def corr(self, col1: str, col2: str, method: str = "pearson") -> float:
        return self.stat.corr(col1, col2, method)

    def cov(self, col1: str, col2: str) -> float:
        return self.stat.cov(col1, col2)

    # -- writer ------------------------------------------------------------
    @property
    def write(self):
        """``df.write.format("csv").option("header", True).save(path)``."""
        from .writer import DataFrameWriter

        return DataFrameWriter(self)

    def to_csv(self, path: str, header: bool = False,
               delimiter: str = ",") -> None:
        from .writer import write_csv

        write_csv(self, path, header=header, delimiter=delimiter)

    # -- temp views --------------------------------------------------------
    def create_or_replace_temp_view(self, name: str) -> None:
        """Register this frame in the session catalog for SQL access
        (`DataQuality4MachineLearningApp.java:76,88`)."""
        from ..sql.catalog import default_catalog

        default_catalog().register(name, self)

    createOrReplaceTempView = create_or_replace_temp_view

    def create_temp_view(self, name: str) -> None:
        """``createTempView`` — like the or-replace form but raises if
        the name is taken (Spark's TempTableAlreadyExistsException)."""
        from ..sql.catalog import default_catalog

        cat = default_catalog()
        if cat.table_exists(name):
            raise ValueError(f"temp view {name!r} already exists "
                             "(use createOrReplaceTempView)")
        cat.register(name, self)

    createTempView = create_temp_view


class _NAFunctions:
    """``df.na`` accessor (Spark ``DataFrameNaFunctions``) — thin verbs
    over the frame's own null handling: ``fill`` -> ``fillna``,
    ``drop`` -> ``dropna``, ``replace`` -> ``replace``."""

    def __init__(self, frame: "Frame"):
        self._frame = frame

    def fill(self, value, subset=None) -> "Frame":
        return self._frame.fillna(value, subset=subset)

    def drop(self, how="any", thresh=None, subset=None) -> "Frame":
        return self._frame.dropna(how=how, thresh=thresh, subset=subset)

    def replace(self, to_replace, value=None, subset=None) -> "Frame":
        return self._frame.replace(to_replace, value=value, subset=subset)
