"""Equi-joins planned on the device (``Frame.join`` for numeric keys).

The match plan is a sort-merge over ``lax.sort``: on this engine's chip a
sort moves a row in about 3.5 ns where a gather or a scatter of one costs
6 to 27 ns and a binary search a gather a step, so the plan sorts where it
can and gathers only at the size of the result.

One compiled program per (join type, key dtypes, side sizes, result
bucket) does all of it:

``dq.join.build``   both sides' keys in ONE sort of their concatenation;
                    its last key, the tag, carries side, row and
                    validity, so that inside a key group the valid build
                    rows stand first in row order, then the valid probe
                    rows, then the masked ones
``dq.join.probe``   per key group, by four scans, where it starts and how
                    many valid build rows it has; the probe rows the join
                    type selects, compacted by one single-operand sort of
                    their positions
``dq.join.gather``  selected probe rows x their group's build rows laid
                    out in ``bucket`` slots, brought into (left, right)
                    order by one stable sort at result size, and every
                    output column gathered at that size

Keys are compared as the integers or floats they are (a float NaN matches
nothing, -0.0 equals 0.0); several keys are several sort operands — no
packing, no float detour. Memory is a constant number of n-row int32
operands for n = left + right rows, and no ``(n, k)`` operand.

The host reads one scalar a join: the size of the result, after the
program has run. The program is built for a result ``bucket`` remembered
per program signature from the join's last run (an estimate, like the
statstore's); a result that outgrew it runs once more at the size it
asked for. The result is a frame of ``bucket`` slots under a mask.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils import observability as _obs
from ..utils.profiling import counters, host_read
from .compiler import result_bucket

#: the join types this module plans; ``right``, ``outer`` and ``cross``
#: keep the host plan (``frame._vector_join_plan``)
DEVICE_HOWS = ("inner", "left", "left_semi", "left_anti")

_HIGH = np.uint32(1 << 31)
_INT_MAX = np.int32(np.iinfo(np.int32).max)

_LOCK = threading.Lock()
_PROGRAMS: dict = {}      # signature + bucket -> jitted program
_BUCKETS: dict = {}       # signature -> result bucket of the last run


def key_dtype(a, b):
    """The dtype two key columns are compared in, or None where the
    device plan does not take them: both integers (bools count) of one
    signedness, or both floats. An integer against a float would be
    compared in float32 here, which aliases integers over 2^24: the host
    plan's float64 keeps that pair."""
    if getattr(a, "ndim", 1) != 1 or getattr(b, "ndim", 1) != 1:
        return None
    da, db = (np.dtype(np.int32) if np.dtype(c.dtype).kind == "b"
              else np.dtype(c.dtype) for c in (a, b))
    if da.kind == db.kind and da.kind in "iuf":
        return np.promote_types(da, db)
    return None


_CHUNK = 1 << 15       # elements a row of the chunked compaction


def _compact(sel, n: int, bucket: int):
    """Positions of the first ``bucket`` selected elements of ``sel``, in
    order (slots past the last selected one hold any position): the
    compaction a sort-merge needs, without a scatter a row.

    Where the result is of the inputs' order of size, ONE single-operand
    sort of the positions (selected ones first). Where it is far smaller
    — a fact table probed by a filtered dimension — the sort runs inside
    chunks of 2^15 elements, a third of the full sort's passes, and the
    slots find their chunk by the chunks' counts: three more gathers at
    result size, which only a small result repays."""
    if bucket * 16 > n:
        pos = lax.iota(jnp.uint32, n)
        order = lax.sort(jnp.where(sel, pos, pos | _HIGH))
        m = min(bucket, n)
        at = (order[:m] & ~_HIGH).astype(jnp.int32)
        if m < bucket:
            at = jnp.concatenate([at, jnp.zeros((bucket - m,), jnp.int32)])
        return at
    chunks = -(-n // _CHUNK)
    grid = jnp.concatenate(
        [sel, jnp.zeros((chunks * _CHUNK - n,), jnp.bool_)]
    ).reshape(chunks, _CHUNK)
    local = lax.broadcasted_iota(jnp.uint32, (chunks, _CHUNK), 1)
    order = lax.sort(jnp.where(grid, local, local | _HIGH), dimension=1)
    held = jnp.sum(grid, axis=1, dtype=jnp.int32)
    offs = jnp.cumsum(held) - held
    slot = lax.iota(jnp.int32, bucket)
    # slot -> the chunk that holds its element
    chunk = lax.cummax(jnp.zeros((bucket,), jnp.int32).at[
        jnp.where(held > 0, offs, bucket)].max(
            lax.iota(jnp.int32, chunks), mode="drop"))
    flat = chunk * _CHUNK + slot - jnp.take(offs, chunk, mode="clip")
    inside = jnp.take(order.reshape(-1), flat, mode="clip") & ~_HIGH
    return jnp.minimum(chunk * _CHUNK + inside.astype(jnp.int32), n - 1)


def _build_program(how: str, dtypes: tuple, nb: int, npr: int, bucket: int,
                   probe_is_left: bool):
    """The jitted join: (build keys, build mask, probe keys, probe mask,
    build columns, probe columns) -> (left rows' columns, right rows'
    columns, slot mask, result size, missing-right flags or None)."""
    n = nb + npr
    k = len(dtypes)

    def canon(col, dt):
        col = col.astype(dt)
        if np.dtype(dt).kind == "f":
            col = col + jnp.zeros((), dt)          # -0.0 -> 0.0
        return col

    def program(bkeys, bmask, pkeys, pmask, bcols, pcols):
        counters.increment("join.compile")          # trace time only
        with _obs.scope("join"):
            bkeys = [canon(c, dt) for c, dt in zip(bkeys, dtypes)]
            pkeys = [canon(c, dt) for c, dt in zip(pkeys, dtypes)]
            # a NaN key needs no flag: NaN != NaN, so it is a key group of
            # its own and matches nothing (a left or anti join keeps it)
            bvalid, pvalid = bmask, pmask

            with _obs.scope("join.build"):
                # ONE sort of both sides' keys; the tag is the last key:
                # inside a key the valid build rows come first in row
                # order, then the valid probe rows, then the masked ones
                tag = lax.iota(jnp.uint32, n)
                valid = jnp.concatenate([bvalid, pvalid])
                tag = jnp.where(valid, tag, tag | _HIGH)
                keys = [jnp.concatenate([b, p])
                        for b, p in zip(bkeys, pkeys)]
                *ks, ts = lax.sort((*keys, tag), num_keys=k + 1)

            with _obs.scope("join.probe"):
                ok = ts < _HIGH
                row = (ts & ~_HIGH).astype(jnp.int32)
                is_b = ok & (row < nb)
                is_p = ok & (row >= nb)
                one = jnp.ones((1,), jnp.bool_)
                first = None
                for c in ks:
                    new = jnp.concatenate([one, c[1:] != c[:-1]])
                    first = new if first is None else first | new
                is_b32 = is_b.astype(jnp.int32)
                cb = jnp.cumsum(is_b32)
                # known to every probe row: where its key group starts
                # (the group's valid build rows stand there) and how many
                # valid build rows it has
                head = lax.cummax(
                    jnp.where(first, lax.iota(jnp.int32, n), 0))
                # (a probe row stands behind its group's build rows, so
                # the running count at it holds all of them)
                cnt = jnp.where(is_p, cb - lax.cummax(
                    jnp.where(first, cb - is_b32, 0)), 0)
                if how == "left_anti":
                    sel = is_p & (cnt == 0)
                elif how == "left":
                    sel = is_p
                else:
                    sel = is_p & (cnt > 0)
                expand = how in ("inner", "left")
                chosen = jnp.sum(sel, dtype=jnp.int32)
                size = chosen
                if expand:
                    each = jnp.maximum(cnt, 1) if how == "left" else cnt
                    size = jnp.sum(jnp.where(sel, each, 0),
                                   dtype=jnp.int32)
                at = _compact(sel, n, bucket)
                slot = lax.iota(jnp.int32, bucket)
                prow = jnp.take(row, at, mode="clip") - nb

            with _obs.scope("join.gather"):
                brow = missing = None
                if expand:
                    c = jnp.where(slot < chosen,
                                  jnp.take(cnt, at, mode="clip"), 0)
                    head_at = jnp.take(head, at, mode="clip")

                    def one_each(_):
                        # no selected probe row has two build rows (a
                        # foreign-key join): a slot a selected row
                        return (prow, jnp.take(row, head_at, mode="clip"),
                                c == 0)

                    def several(_):
                        # each selected probe row x its group's build
                        # rows, laid out over the slots
                        e = jnp.where(slot < chosen, jnp.maximum(c, 1), 0) \
                            if how == "left" else c
                        offs = jnp.cumsum(e) - e
                        marks = jnp.zeros((bucket,), jnp.int32).at[
                            jnp.where(e > 0, offs, bucket)].max(
                                slot, mode="drop")
                        src = lax.cummax(marks)
                        within = slot - jnp.take(offs, src, mode="clip")
                        return (jnp.take(prow, src, mode="clip"),
                                jnp.take(row, jnp.take(head_at, src,
                                                       mode="clip")
                                         + within, mode="clip"),
                                jnp.take(c, src, mode="clip") == 0)

                    prow, brow, missing = lax.cond(
                        jnp.max(c) <= 1, one_each, several, None)
                    if how != "left":
                        missing = None
                live = slot < size
                # (left, right) order: a left row's slots are one run with
                # its right rows ascending, so a stable sort by the left
                # row alone orders both; dead slots go last
                lrow, rrow = (prow, brow) if probe_is_left \
                    else (brow, prow)
                lrow = jnp.where(live, lrow, _INT_MAX)
                rest = [x for x in (rrow, missing) if x is not None]
                lrow, *rest = lax.sort((lrow, *rest), num_keys=1,
                                       is_stable=True)
                if rrow is not None:
                    rrow = rest[0]
                if missing is not None:
                    missing = rest[1]
                prow, brow = (lrow, rrow) if probe_is_left \
                    else (rrow, lrow)
                pout = [jnp.take(col, prow, axis=0, mode="clip")
                        for col in pcols]
                bout = [jnp.take(col, brow, axis=0, mode="clip")
                        for col in bcols]
            return pout, bout, live, size, missing

    return jax.jit(program)


def device_join(how: str, lkeys, lmask, rkeys, rmask, lcols, rcols,
                build_left: bool, dtypes: tuple):
    """Run the join on the device. ``lcols`` / ``rcols`` are the columns
    to gather from each side (none of the right for semi/anti). Returns
    ``(left columns, right columns, mask, rows, missing)``: the gathered
    columns at a bucket of slots, the slots' mask, the result's row count
    (the one scalar read) and, for a left join, the slots whose right
    side is missing."""
    nl, nr = int(lmask.shape[0]), int(rmask.shape[0])
    probe_is_left = not (how == "inner" and build_left)
    if probe_is_left:
        bkeys, bmask, pkeys, pmask, bcols, pcols = \
            rkeys, rmask, lkeys, lmask, rcols, lcols
    else:
        bkeys, bmask, pkeys, pmask, bcols, pcols = \
            lkeys, lmask, rkeys, rmask, lcols, rcols
    nb, npr = int(bmask.shape[0]), int(pmask.shape[0])
    sig = (how, tuple(str(d) for d in dtypes), nl, nr, probe_is_left,
           tuple((str(c.dtype), c.shape[1:]) for c in bcols),
           tuple((str(c.dtype), c.shape[1:]) for c in pcols),
           tuple(str(c.dtype) for c in list(bkeys) + list(pkeys)))
    with _LOCK:
        bucket = _BUCKETS.get(sig)
    if bucket is None:
        # first run of this join: a foreign-key join gives at most one
        # row a probe row
        bucket = result_bucket(npr if how != "inner" else min(nb, npr))
    while True:
        with _LOCK:
            fn = _PROGRAMS.get(sig + (bucket,))
            if fn is None:
                fn = _PROGRAMS[sig + (bucket,)] = _build_program(
                    how, dtypes, nb, npr, bucket, probe_is_left)
        before = counters.get("join.compile")
        pout, bout, live, size, missing = fn(
            list(bkeys), bmask, list(pkeys), pmask,
            list(bcols), list(pcols))
        if counters.get("join.compile") == before:
            counters.increment("join.hit")
        counters.increment("join.rows_probed", npr)
        # dqlint: ok(host-sync): THE read of a device join — one scalar,
        # the result's row count, a counted frame boundary like the
        # grouped verdict (``segments._read_verdict``)
        rows = int(size)
        counters.increment("frame.host_sync")
        host_read(size.dtype.itemsize)
        want = result_bucket(rows)
        with _LOCK:
            _BUCKETS[sig] = want
        if rows <= bucket:
            break
        bucket = want                     # outgrew its bucket: once more
    lout, rout = (pout, bout) if probe_is_left else (bout, pout)
    return lout, rout, live, rows, missing
