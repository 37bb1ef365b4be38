"""Equi-joins planned on the device (``Frame.join`` for numeric keys).

The match plan is a sort-merge over ``lax.sort``: on this engine's chip a
sort moves a row in about 3.5 ns where a gather or a scatter of one costs
6 to 27 ns and a binary search a gather a step, so the plan sorts where it
can and gathers only at the size of the result.

One compiled program per (join type, key dtypes, side sizes, result
bucket, build step) does all of it:

``dq.join.build``   both sides' keys in (key, tag) order; the tag carries
                    side, row and validity, so that inside a key group
                    the valid build rows stand first in row order, then
                    the valid probe rows. Two ways to that order. The
                    **sort**: ONE ``lax.sort`` of the two sides'
                    concatenation. The **merge**, for a probe side that
                    arrives in key order (a fact table stored in its
                    parent's key order): the build side sorted alone,
                    the probe side cut into chunks beside their share of
                    the sorted build rows, every chunk of 2^15 sorted by
                    itself (``_merge``) — on this chip a sort's cost
                    follows its length steeply. The **lookup**, for an
                    ordered probe side against a few thousand build
                    keys, takes neither: it sorts the build side alone
                    and searches each of its keys into the probe keys
                    (``_lookup``), so that nothing of probe size is
                    sorted, scanned or compacted
``dq.join.probe``   per key group, by scans, where it starts and how
                    many valid build rows it has — on one TPU device and
                    32-bit keys ONE pass of the Pallas kernel
                    ``join_probe_scan``, elsewhere XLA's ``cumsum`` and two
                    ``cummax`` (``scan_lowering``); the probe rows the join
                    type selects, compacted by one single-operand sort of
                    their positions. Behind the lookup: its candidate
                    pairs laid out in slots, the masked probe rows among
                    them dropped and the rest compacted at slot size
``dq.join.gather``  selected probe rows x their group's build rows laid
                    out in ``bucket`` slots, brought into (left, right)
                    order by one stable sort at result size, and every
                    output column gathered at that size

Keys are compared as the integers or floats they are (a float NaN matches
nothing, -0.0 equals 0.0); several keys are several sort operands — no
packing, no float detour. Memory is a constant number of n-row int32
operands for n = left + right rows, and no ``(n, k)`` operand.

The host reads one small array a join, after the program has run: the
size of the result and, behind a merge or a lookup, what it assumed —
whether the probe keys were in order, and the build slots the merge's
fullest chunk asked for or the lookup's candidate count. Shapes, the key
count and the observed order decide the build step; no option does. A
join of one key column whose probe side has eight chunks' worth of slots
and about twelve times the build side's is offered the merge, with a
``room`` of build slots a chunk taken from the shapes (``_first_room``);
before such a signature's first program is built the probe side's order
is read (one elementwise pass, one flag), so that no merge program is
built for a probe side that arrives unordered. Where the join's result
is of the build side's order of size (``inner``, the probe on either
side, and ``left_semi``) and the build side is so small that searching
each of its keys into the probe keys costs far less than a pass of the
chunked sort (``_takes_lookup``: build slots x ceil(log2(probe slots)) x
16 at most the probe slots), the lookup takes the merge's place; its
``room`` is the slots its candidate pairs are laid out in, at first the
result's bucket. Its one pass over the probe side is the order check
(``_in_order``) in the same program, whose flag and candidate count ride
the join's one read. An ordered build step that did not hold
(``join.merge_miss``: the merge's or the lookup's probe side out of
order, or a merge chunk over its room) runs once more as the sort, and
its signature remembers what it learnt: the sort for a probe side out of
order, the room the chunks asked for otherwise (``_ROOMS``); candidates
that outgrew the lookup's slots run once more in as many as they asked
for, remembered likewise. The program is likewise built for a result
``bucket`` remembered from the join's last run (an estimate, like the
statstore's); a result that outgrew it runs once more at the size it
asked for. So a signature's second run in a process is the program it
keeps. The result is a frame of ``bucket`` slots under a mask, bit for
bit the same under any build step.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils import observability as _obs
from ..utils.profiling import counters
from .compiler import result_bucket
from .segments import _read_verdict

#: the join types this module plans; ``right``, ``outer`` and ``cross``
#: keep the host plan (``frame._vector_join_plan``)
DEVICE_HOWS = ("inner", "left", "left_semi", "left_anti")

_HIGH = np.uint32(1 << 31)
_INT_MAX = np.int32(np.iinfo(np.int32).max)

_LOCK = threading.Lock()
_PROGRAMS: dict = {}      # signature + (bucket, room) -> jitted program
_BUCKETS: dict = {}       # signature -> result bucket of the last run
_ROOMS: dict = {}         # signature -> build slots a chunk of the merge
#                           (candidate slots of the lookup) since its last
#                           miss; 0: the build step sorts


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


_CHUNK = 1 << 15       # elements a row of the chunked sorts


def _room_for(need: int) -> int:
    """Build slots a chunk of the merge for chunks that ask for at most
    ``need``: an eighth more, in steps of a 256th of the row (whole lane
    tiles at the row's real length), or 0 — the build step sorts — where
    that is over an eighth of the row: the chunks' padding rides every
    later pass, and a program for 2.4e8 probe slots asked for 3.5 GB of
    scratch more than the sort's at a room of 5,760."""
    step = max(_CHUNK >> 8, 1)
    room = -(-(need + need // 8) // step) * step
    return max(room, step) if room * 8 <= _CHUNK else 0


def _first_room(k: int, nb: int, npr: int) -> int:
    """The room a signature's first run tries, from shapes alone, or 0
    where they rule the merge out: one key column, a probe side of eight
    chunks and a build side small beside it (about a twelfth). For a
    quarter more than the build slots an evenly spread build side puts
    into a chunk."""
    if k != 1 or npr < 8 * _CHUNK:
        return 0
    even = -(-nb * _CHUNK // npr)
    return _room_for(even + even // 4)


def _takes_lookup(how: str, k: int, nb: int, npr: int) -> bool:
    """Whether shapes that offer the merge offer the lookup in its place:
    a join whose result is of the build side's order of size (``inner``,
    ``left_semi``; a ``left`` or anti join gives the probe side's) and a
    build side whose binary searches into the probe keys, a gather a
    step, cost far less than one pass of the chunked sort over the probe
    side: ``nb * ceil(log2(npr)) * 16 <= npr``."""
    return how in ("inner", "left_semi") and _first_room(k, nb, npr) > 0 \
        and nb * (npr - 1).bit_length() * 16 <= npr


def _chunks(npr: int, room: int) -> int:
    """Chunks of the merge for ``npr`` probe slots, a multiple of eight:
    the scans behind it then see whole tiles (a program for 2.4e8 slots
    asked for 7.5 GB of scratch so, and 9.2 to 10.2 GB otherwise)."""
    return -(-npr // ((_CHUNK - room) * 8)) * 8


def _pairs(nb: int, npr: int, room: int) -> int:
    """Sorted (key, tag) pairs the build step gives and the rest of the
    program walks: the merge's chunks, or both sides."""
    return _chunks(npr, room) * _CHUNK if room else nb + npr


@jax.jit
def _in_order(keys):
    """Whether a key column is non-decreasing over all its slots, masked
    ones too (a NaN key: no)."""
    return jnp.all(keys[1:] >= keys[:-1])


def _sorted_build(bkey, bvalid):
    """The build side alone in (key, row) order, its valid rows first —
    a masked row or a NaN key carries the largest key and a tag over
    every valid one's — and how many are valid: ``(keys, tags, held)``."""
    nb = bkey.shape[0]
    floating = np.dtype(bkey.dtype).kind == "f"
    top = jnp.asarray(np.inf if floating else np.iinfo(bkey.dtype).max,
                      bkey.dtype)
    if floating:
        bvalid = bvalid & ~jnp.isnan(bkey)
    btag = lax.iota(jnp.uint32, nb)
    sk, st = lax.sort((jnp.where(bvalid, bkey, top),
                       jnp.where(bvalid, btag, btag | _HIGH)), num_keys=2)
    return sk, st, jnp.sum(bvalid, dtype=jnp.int32)


def _merge(bkey, bvalid, pkey, pvalid, room: int):
    """The build step for a probe side that arrives in key order: the
    pairs ``(ks, ts)`` the one sort of both sides gives (longer by the
    chunks' padding, masked), a flag that says the result may be used,
    and the build slots the fullest chunk asked for.

    Consecutive chunks of an ordered probe side are range partitions of
    the key space. The build side is sorted alone; chunk ``c`` of
    ``_CHUNK - room`` probe slots takes, into its ``room`` build slots,
    the valid build rows with ``lo[c] < key <= lo[c + 1]`` (``lo`` the
    chunks' first keys; chunk 0 from ``lo[0]`` itself, the last chunk up
    to the last probe key): a key's build rows stand in the chunk where
    its probe rows begin, in front of them, even where the group runs on
    into later chunks. Build rows no probe key reaches, masked ones and
    NaN keys match nothing in any device join type and are left out.
    Unused slots carry the chunk's first key and a masked tag, so they
    split no key group. Every chunk is then sorted by itself."""
    nb, npr = bkey.shape[0], pkey.shape[0]
    part = _CHUNK - room
    chunks = _chunks(npr, room)
    sk, st, held = _sorted_build(bkey, bvalid)

    ptag = lax.iota(jnp.uint32, npr) + np.uint32(nb)
    ptag = jnp.where(pvalid, ptag, ptag | _HIGH)
    tail = chunks * part - npr
    pk = jnp.concatenate(
        [pkey, jnp.broadcast_to(pkey[-1], (tail,))]).reshape(chunks, part)
    pt = jnp.concatenate(
        [ptag, jnp.full((tail,), ~np.uint32(0))]).reshape(chunks, part)
    lo = pk[:, 0]
    ordered = _in_order(pkey)

    def upto(keys, side):
        return jnp.minimum(jnp.searchsorted(sk, keys, side=side), held) \
            .astype(jnp.int32)

    edges = upto(lo[1:], "right")
    start = jnp.concatenate([upto(lo[:1], "left"), edges])
    count = jnp.concatenate([edges, upto(pkey[-1:], "right")]) - start
    # a chunk's build rows are one slice of the sorted build side
    sk, st = (jnp.concatenate([x, jnp.zeros((room,), x.dtype)])
              for x in (sk, st))
    bk, bt = jax.vmap(lambda at: tuple(
        lax.dynamic_slice(x, (at,), (room,)) for x in (sk, st)))(start)
    fits = lax.broadcasted_iota(jnp.int32, (chunks, room), 1) \
        < count[:, None]
    ks, ts = lax.sort(
        (jnp.concatenate([jnp.where(fits, bk, lo[:, None]), pk], axis=1),
         jnp.concatenate([jnp.where(fits, bt, ~np.uint32(0)), pt], axis=1)),
        dimension=1, num_keys=2)
    return ks.reshape(-1), ts.reshape(-1), ordered, jnp.max(count)


def _lookup(bkey, bvalid, pkey, expand: bool, room: int):
    """The build step for a few build keys against a probe side that
    arrives in key order: ``room`` candidate pairs ``(prow, brow, live)``
    in (probe row, build row) order, and how many candidates there are
    (over ``room``: the slots are too few, and the pairs incomplete).

    On an ordered probe side a key's probe rows are one run. The valid
    build rows are sorted alone in (key, row) order — masked rows and NaN
    keys match nothing in any device join type and are left out — and
    each key group's run ``[lo, hi)`` of probe slots found by two binary
    searches of its key. With ``expand`` (``inner``: every probe row
    pairs with each of its key's build rows) a group of ``m`` build rows
    gives ``(hi - lo) * m`` candidates, else ``hi - lo``; slot ``s``
    finds its group by a search over the groups' running totals and
    pairs probe row ``lo + s // m`` with the group's ``s % m``-th build
    row. Groups stand in key order, so the pairs come in probe row
    order. Masked probe slots are candidates too: the caller drops them."""
    nb = bkey.shape[0]
    sk, st, held = _sorted_build(bkey, bvalid)
    at = lax.iota(jnp.int32, nb)
    first = (at < held) & jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sk[1:] != sk[:-1]])
    # a group's build rows are [at, the next group's first row)
    after = jnp.concatenate([lax.cummin(jnp.where(first, at, held),
                                        reverse=True)[1:], held[None]])
    m = jnp.where(first, after - at, 0)
    lo, hi = (jnp.searchsorted(pkey, sk, side=side, method="scan")
              .astype(jnp.int32) for side in ("left", "right"))
    cand = jnp.where(first, hi - lo, 0)
    if expand:
        cand = cand * m
    ends = jnp.cumsum(cand, dtype=jnp.int32)
    slot = lax.iota(jnp.int32, room)
    group = jnp.minimum(jnp.searchsorted(ends, slot, side="right",
                                         method="scan"), nb - 1) \
        .astype(jnp.int32)
    within = slot - (jnp.take(ends, group) - jnp.take(cand, group))
    prow = jnp.take(lo, group)
    brow = None
    if expand:
        each = jnp.maximum(jnp.take(m, group), 1)
        prow = prow + within // each
        brow = (jnp.take(st, group + within % each, mode="clip")
                & ~_HIGH).astype(jnp.int32)
    else:
        prow = prow + within
    return prow, brow, slot < ends[-1], ends[-1]


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


#: slots a block of :func:`_sparse_compact` counts; a result slot per this
#: many slots at most
_SPARSE = 128


def _sparse_compact(sel, bucket: int):
    """:func:`_compact` for a selection far sparser than its slots: by the
    counts of blocks of ``_SPARSE`` and a binary search over their running
    totals, then a rank inside the one block each result slot falls in —
    one pass over ``sel``, no sort."""
    n = sel.shape[0]
    blocks = -(-n // _SPARSE)
    grid = jnp.concatenate(
        [sel, jnp.zeros((blocks * _SPARSE - n,), jnp.bool_)]
    ).reshape(blocks, _SPARSE)
    held = jnp.sum(grid, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(held)
    slot = lax.iota(jnp.int32, bucket)
    # the block that holds a slot's element: the first whose total passes it
    at = jnp.minimum(jnp.searchsorted(ends, slot, side="right"),
                     blocks - 1).astype(jnp.int32)
    rows = jnp.take(grid, at, axis=0)
    rank = jnp.cumsum(rows, axis=1, dtype=jnp.int32) \
        + (jnp.take(ends, at) - jnp.take(held, at))[:, None] - 1
    lane = jnp.argmax(rows & (rank == slot[:, None]), axis=1)
    return jnp.minimum(at * _SPARSE + lane.astype(jnp.int32), n - 1)


@functools.partial(jax.jit, static_argnums=2)
def _take_rows(cols, mask, bucket: int):
    n = mask.shape[0]
    at = _sparse_compact(mask, bucket) if bucket * _SPARSE <= n \
        else _compact(mask, n, bucket)
    return [jnp.take(c, at, axis=0, mode="clip") for c in cols]


def compact_rows(cols, mask):
    """The valid rows of ``cols`` in order, in ``result_bucket(rows)``
    slots under a mask: the build side of a join that a filter left
    sparse (a HAVING over a many-group result), so that the join sorts its
    rows and not its slots. One read, the row count; returns ``(columns,
    mask, rows)``, the columns as they were where the bucket saves no
    slot."""
    held = _read_verdict(jnp.sum(mask, dtype=jnp.int32), "join.build_rows")
    rows = int(held)
    bucket = result_bucket(rows)
    if bucket >= mask.shape[0]:
        return list(cols), mask, rows
    return (_take_rows(list(cols), mask, bucket),
            jnp.arange(bucket) < rows, rows)


#: pairs a grid step of ``join_probe_scan`` reads
SCAN_BLOCK = 1 << 18
#: rows of 128 pairs the kernel scans at once: a slab of 32 vregs, the
#: most whose place and count a 31-bit mark holds (2^15 pairs)
SCAN_ROWS = 256
_VREG = 8 * 128
# float32 bits: -0.0, the magnitude's mask, +inf (a NaN's magnitude is more)
_NEG_ZERO, _ABS, _INF = np.int32(-(1 << 31)), np.int32(0x7FFFFFFF), \
    np.int32(0x7F800000)


def scan_lowering(keys, dtypes, n: int) -> str:
    """Which lowering the probe's scans over ``n`` pairs take — from the
    backend and the operands, never from a conf key: ``"pallas"`` (the
    kernel ``join_probe_scan``) for keys compared in 32 bits on one TPU
    device, ``"xla"`` everywhere else (the CPU of the tests, a mesh, 64-bit
    keys) and for fewer pairs than a vreg holds (XLA tiles a 1-D operand
    of up to 512 elements otherwise than the kernel's blocks). Both give
    the same integers."""
    if jax.default_backend() != "tpu" or n < _VREG \
            or any(np.dtype(dt).itemsize != 4 for dt in dtypes):
        return "xla"
    for key in keys:
        sharding = getattr(key, "sharding", None)
        if sharding is None or len(sharding.device_set) != 1:
            return "xla"
    return "pallas"


def _scans_xla(ks, ts, nb: int):
    """(head, cnt) of the sorted pairs by XLA's scans: for every pair the
    position where its key group starts (the group's valid build rows
    stand there) and, for a valid probe row, how many valid build rows
    its group has (0 elsewhere)."""
    n = ts.shape[0]
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
    head = lax.cummax(jnp.where(first, lax.iota(jnp.int32, n), 0))
    # (a probe row stands behind its group's build rows, so the running
    # count at it holds all of them)
    cnt = jnp.where(is_p, cb - lax.cummax(
        jnp.where(first, cb - is_b32, 0)), 0)
    return head, cnt


def _scan_kernel(*refs, floats: tuple, nb: int, block: int, rows: int):
    """One grid step of ``join_probe_scan``: ``block`` pairs as slabs of
    ``rows`` x 128 consecutive ones (in row order), scanned in order. A
    slab's scans are log steps of lane rolls, then of row rolls over the
    rows' totals, each step over all of the slab's vregs at once (a roll's
    latency is paid once a slab, not once a vreg); what runs on from one
    slab to the next — the last key, the open group's start and its build
    rows so far — is carried, and kept in scratch from one grid step to
    the next. Keys are compared as their 32 bits (``floats`` says which
    are floats)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k = len(floats)
    key_refs, tag_ref = refs[:k], refs[k]
    head_ref, cnt_ref = refs[k + 1], refs[k + 2]
    last_refs = refs[k + 3:2 * k + 3]
    head_c_ref, run_c_ref = refs[2 * k + 3], refs[2 * k + 4]
    step = pl.program_id(0)
    size = rows * 128
    # constants as int32: a Python int would be an int64 under x64, which
    # Mosaic does not take
    bits_of_count = size.bit_length()   # place << them | count < 2^31
    shift, low = np.int32(bits_of_count), np.int32((1 << bits_of_count) - 1)
    nb, zero, none = np.int32(nb), np.int32(0), np.int32(-1)

    @pl.when(step == 0)
    def _():
        for ref in (*last_refs, head_c_ref, run_c_ref):
            ref[...] = jnp.zeros_like(ref)

    row = lax.broadcasted_iota(jnp.int32, (rows, 128), 0)
    lane = lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
    flat = row * 128 + lane

    def roll(v, by, axis):
        return pltpu.roll(v, np.int32(by), axis)     # int32 under x64 too

    def bits(v):
        v = v.reshape(rows, 128)
        return v if v.dtype == jnp.int32 else lax.bitcast_convert_type(
            v, jnp.int32)

    def lanes(v):
        """Each row's last lane, over the row."""
        return jnp.broadcast_to(v[:, 127:128], (rows, 128))

    def last(v):
        """The slab's last pair, over a row (Mosaic broadcasts one element
        along one axis at a time)."""
        return jnp.sum(jnp.where(row == rows - 1, lanes(v), zero), axis=0,
                       keepdims=True, dtype=jnp.int32)

    def over(c):
        return jnp.broadcast_to(c, (rows, 128))

    def scan(v, op, fill):
        """Inclusive scan of a slab in pair order."""
        s = 1
        while s < 128:
            v = op(v, jnp.where(lane >= s, roll(v, s, 1), fill))
            s *= 2
        # the rows before a row: their totals shifted by one, then a scan
        before = jnp.where(row >= 1, roll(lanes(v), 1, 0), fill)
        s = 1
        while s < rows:
            before = op(before, jnp.where(row >= s,
                                          roll(before, s, 0), fill))
            s *= 2
        return op(v, before)

    def slab(_, carry):
        # the slab's offset rides in the carry: int32 under x64 too, where
        # the loop's own index would not be
        start, lasts, head_c, run_c = carry
        at = pl.ds(pl.multiple_of(start, size), size)
        origin = step * np.int32(block) + start
        # keys as their bits, the tags as int32: a masked pair's is negative
        tag = bits(tag_ref[at])
        first = flat + origin == 0
        keys = []
        for ref, prev, floating in zip(key_refs, lasts, floats):
            c = bits(ref[at])
            if floating:
                c = jnp.where(c == _NEG_ZERO, zero, c)  # -0.0 is 0.0
            shifted = roll(c, 1, 1)            # lane l: l - 1
            before = jnp.where(lane >= 1, shifted, jnp.where(
                row >= 1, roll(shifted, 1, 0), over(prev)))
            first = first | (c != before)
            if floating:
                # a NaN key is a group of its own
                first = first | ((c & _ABS) > _INF)
            keys.append(c)
        b = ((tag >= 0) & (tag < nb)).astype(jnp.int32)
        cs = scan(b, jnp.add, zero)
        # the last group start at or before a pair: its place in the slab
        # and the build rows before it, one max scan of both (each grows
        # along the slab)
        mark = scan(jnp.where(first, (flat << shift) + cs - b, none),
                    jnp.maximum, none)
        opened = mark >= 0
        head = jnp.where(opened, origin + (mark >> shift), over(head_c))
        run = jnp.where(opened, cs - (mark & low), over(run_c) + cs)
        head_ref[at] = head.reshape(size)
        cnt_ref[at] = jnp.where(tag >= nb, run, zero).reshape(size)
        return (start + np.int32(size), [last(c) for c in keys],
                last(head), last(run))

    _, lasts, head_c, run_c = lax.fori_loop(
        0, block // size, slab, (np.int32(0), [ref[...] for ref in last_refs],
                                 head_c_ref[...], run_c_ref[...]))
    for ref, v in zip(last_refs, lasts):
        ref[...] = v
    head_c_ref[...] = head_c
    run_c_ref[...] = run_c


def _scans_pallas(ks, ts, nb: int, block: int = SCAN_BLOCK,
                  rows: int = SCAN_ROWS, interpret: bool = False):
    """:func:`_scans_xla` as ONE pass of the Pallas kernel
    ``join_probe_scan``: keys and tags read once, ``head`` and ``cnt``
    written once, the pairs in grid steps of ``block`` along a sequential
    axis. The last step may reach past the pairs' end: what it reads
    there comes after every pair it writes, and what it writes there is
    dropped — no operand is padded.

    Pallas is imported here and in the kernel, never at module level
    (rule 0: every cell imports this module)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = ts.shape[0]
    size = rows * 128
    assert rows % 8 == 0 and size <= 1 << 15, rows
    block = -(-min(block, n) // size) * size
    floats = tuple(np.dtype(c.dtype).kind == "f" for c in ks)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    return tuple(pl.pallas_call(
        functools.partial(_scan_kernel, floats=floats, nb=nb, block=block,
                          rows=rows),
        grid=(pl.cdiv(n, block),),
        in_specs=[spec] * (len(ks) + 1), out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((1, 128), jnp.int32)] * (len(ks) + 2),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="join_probe_scan")(*ks, ts))


def _build_step(how: str, k: int, nb: int, npr: int, room: int) -> str:
    """The build step a signature's program takes at ``room``: the sort at
    0, else the lookup where shapes offer it, else the merge."""
    if not room:
        return "sort"
    return "lookup" if _takes_lookup(how, k, nb, npr) else "merge"


def _build_program(how: str, dtypes: tuple, nb: int, npr: int, bucket: int,
                   probe_is_left: bool, room: int, scan: str):
    """The jitted join: (build keys, build mask, probe keys, probe mask,
    build columns, probe columns) -> (left rows' columns, right rows'
    columns, slot mask, verdict, missing-right flags or None). The
    verdict is what the host reads: the result size and, where the build
    step merges or looks up (``room`` > 0), whether the probe keys were in
    order and the room its fullest chunk asked for, or the candidates the
    lookup laid out."""
    k = len(dtypes)
    step = _build_step(how, k, nb, npr, room)
    # the pairs the sort and the merge walk; the lookup walks none, and
    # its room is its candidates' slots, not a chunk's build slots
    n = 0 if step == "lookup" else _pairs(nb, npr, room)

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

            def in_order_of_rows(prow, brow, missing, live):
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
                return pout, bout, missing

            if step == "lookup":
                with _obs.scope("join.build"):
                    # the build side sorted alone and its keys searched
                    # into the probe keys: the candidate pairs in (probe
                    # row, build row) order
                    prow, brow, fits, total = _lookup(
                        bkeys[0], bvalid, pkeys[0], how == "inner", room)
                    verdict = [_in_order(pkeys[0]).astype(jnp.int32),
                               total]
                with _obs.scope("join.probe"):
                    # the candidates on masked probe rows dropped, the
                    # rest compacted at slot size
                    keep = fits & jnp.take(pvalid, prow, mode="clip")
                    size = jnp.sum(keep, dtype=jnp.int32)
                    at = _compact(keep, room, bucket)
                    prow = jnp.take(prow, at, mode="clip")
                    if brow is not None:
                        brow = jnp.take(brow, at, mode="clip")
                with _obs.scope("join.gather"):
                    live = lax.iota(jnp.int32, bucket) < size
                    pout, bout, _ = in_order_of_rows(prow, brow, None, live)
                return pout, bout, live, jnp.stack([size, *verdict]), None

            with _obs.scope("join.build"):
                # both sides' keys in (key, tag) order; the tag is the
                # last key: inside a key the valid build rows come first
                # in row order, then the valid probe rows; masked ones
                # behind the valid ones they were sorted with
                if room:
                    *ks, ts, ordered, need = _merge(
                        bkeys[0], bvalid, pkeys[0], pvalid, room)
                    verdict = [ordered.astype(jnp.int32), need]
                else:
                    verdict = []
                    tag = lax.iota(jnp.uint32, n)
                    valid = jnp.concatenate([bvalid, pvalid])
                    tag = jnp.where(valid, tag, tag | _HIGH)
                    keys = [jnp.concatenate([b, p])
                            for b, p in zip(bkeys, pkeys)]
                    *ks, ts = lax.sort((*keys, tag), num_keys=k + 1)

            with _obs.scope("join.probe"):
                ok = ts < _HIGH
                row = (ts & ~_HIGH).astype(jnp.int32)
                is_p = ok & (row >= nb)
                # known to every probe row: where its key group starts
                # (the group's valid build rows stand there) and how many
                # valid build rows it has
                head, cnt = (_scans_pallas if scan == "pallas"
                             else _scans_xla)(ks, ts, nb)
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
                pout, bout, missing = in_order_of_rows(prow, brow, missing,
                                                       live)
            return pout, bout, live, jnp.stack([size, *verdict]), missing

    return jax.jit(program)


def device_join(how: str, lkeys, lmask, rkeys, rmask, lcols, rcols,
                build_left: bool, dtypes: tuple):
    """Run the join on the device. ``lcols`` / ``rcols`` are the columns
    to gather from each side (none of the right for semi/anti). Returns
    ``(left columns, right columns, mask, rows, missing)``: the gathered
    columns at a bucket of slots, the slots' mask, the result's row count
    and, for a left join, the slots whose right side is missing."""
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
        bucket, room = _BUCKETS.get(sig), _ROOMS.get(sig)
    if bucket is None:
        # first run of this join: a foreign-key join gives at most one
        # row a probe row, and a semi join of a key's probe rows against a
        # few build keys (an IN subquery's) about a row a build key
        bucket = result_bucket(min(nb, npr) if how in ("inner", "left_semi")
                               else npr)
    if room is None:
        # first run: where shapes offer the merge, the probe side's
        # order today (one elementwise pass, one flag read) decides
        # which program is built; the lookup lays its candidates out in
        # the result's bucket first
        room = _first_room(len(dtypes), nb, npr)
        if room and _takes_lookup(how, len(dtypes), nb, npr):
            room = bucket
        if room and not _read_verdict(_in_order(pkeys[0]), "join.order"):
            counters.increment("join.merge_miss")
            room = 0
        with _LOCK:
            _ROOMS[sig] = room
    while True:
        step = _build_step(how, len(dtypes), nb, npr, room)
        # (the lookup runs no probe scans)
        scan = None if step == "lookup" else scan_lowering(
            list(bkeys) + list(pkeys), dtypes, _pairs(nb, npr, room))
        with _LOCK:
            fn = _PROGRAMS.get(sig + (bucket, room, scan))
            if fn is None:
                fn = _PROGRAMS[sig + (bucket, room, scan)] = _build_program(
                    how, dtypes, nb, npr, bucket, probe_is_left, room, scan)
        before = counters.get("join.compile")
        pout, bout, live, verdict, missing = fn(
            list(bkeys), bmask, list(pkeys), pmask,
            list(bcols), list(pcols))
        if counters.get("join.compile") == before:
            counters.increment("join.hit")
        counters.increment("join.rows_probed", npr)
        # THE read of a device join, a counted frame boundary like the
        # grouped verdict: the result's row count and, behind a merge or a
        # lookup, what it assumed — one to three scalars in one array
        verdict = _read_verdict(verdict, "join.verdict")
        if step == "lookup" and verdict[1] and verdict[2] > room:
            # in order, but more candidates than slots: once more, with
            # slots for them all (the row count read is short of them)
            room = result_bucket(int(verdict[2]))
            with _LOCK:
                _ROOMS[sig] = room
            continue
        if room and not (verdict[1] and verdict[2] <= room):
            # out of order after all, or a chunk over its room: once more,
            # as a sort; the next run merges with the room this one asked
            # for
            counters.increment("join.merge_miss")
            with _LOCK:
                _ROOMS[sig] = _room_for(int(verdict[2])) if verdict[1] \
                    else 0
            room = 0
            continue
        rows = int(verdict[0])
        want = result_bucket(rows)
        with _LOCK:
            _BUCKETS[sig] = want
        if rows <= bucket:
            break
        bucket = want                     # outgrew its bucket: once more
    if scan == "pallas":
        counters.increment("join.scan_pallas")
    if step == "lookup":
        counters.increment("join.lookup")
        _obs.current_span().set(build_step="lookup", room=room)
    elif room:
        counters.increment("join.merge")
        _obs.current_span().set(build_step="merge", room=room,
                                probe_scan=scan)
    else:
        _obs.current_span().set(build_step="sort", probe_scan=scan)
    lout, rout = (pout, bout) if probe_is_left else (bout, pout)
    return lout, rout, live, rows, missing
