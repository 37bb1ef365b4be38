"""Clustering: TPU-native KMeans (MLlib ``org.apache.spark.ml.clustering``
equivalent — a capability upgrade; the reference app itself fits only
LinearRegression, `DataQuality4MachineLearningApp.java:120-126`, but its
MLlib dependency ships the clustering package and an estimator/model surface
identical to this one).

``KMeans`` has a **device entry** (as the tree fit has, ``models/tree.py``
``_TreeParams._prepare``): the frame's feature column and mask go to the
compiled programs as they lie, validation is a few scalars read through
``host_reading``, and no row leaves the chip.

* **Every pass over X is one function**, :func:`device_pass`: squared
  distances ``Σ_j (x_j − c_j)²`` in the column's own arithmetic (no
  ``‖x‖² − 2x·c + ‖c‖²``, which cancels, and no matmul, which a TPU runs
  in bfloat16 unless told otherwise), the nearest of up to a few dozen
  centres a row, and whichever of (per-row cost and index, total cost,
  rows a slot, coordinate sums a slot) the caller asks for. Nothing
  n-sized is written but the per-row vectors a caller asks for. On a TPU
  it is a Pallas kernel (``kmeans_pass``) that reads X in the layout the
  device holds an ``(n, d)`` column in — feature-major, ``X.T`` is a
  bitcast — with centres on sublanes and rows on lanes; elsewhere the
  same arithmetic in plain ``jax.numpy``.
* **k-means‖ on the device** (Spark 2.4 ``KMeans.initKMeansParallel``):
  :func:`_init_program`. The candidates' number is data: a static bucket
  a round, filled without a sort, an overflow counted.
* **Lloyd's loop is one program**: a ``lax.while_loop`` of passes carrying
  the (k, d) centres and their history; zero host round-trips an
  iteration — MLlib's per-iteration ``collectAsMap``/broadcast barrier
  disappears.
* **Distributed = psum.** Under a mesh the seeding runs as above (plain
  ``jax.numpy``, partitioned by the compiler) and the loop is
  ``_make_fit``'s: rows sharded on the data axis inside ``shard_map``, the
  per-iteration sufficient statistics reduced with ``jax.lax.psum`` over
  ICI — the ``treeAggregate`` replacement (SURVEY.md §3.3).
* **Masked rows never vote** and are never drawn as a centre; a NaN in a
  dropped slot reaches nothing. Empty clusters keep their previous centre
  (Spark keeps stale centres likewise).

``BisectingKMeans`` and ``GaussianMixture`` still pull X and the mask to
the host and seed with the host's greedy k-means++ (``_kmeans_pp_init``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config import float_dtype
from ..frame import Frame
from ..parallel.mesh import (DATA_AXIS, normalize_mesh,
                             serialize_collectives, shard_map)
from ..utils import observability as _obs
from .base import Estimator, Model, host_fetch, persistable


def _pad_and_shard(X, w, mesh, dt):
    """Zero-pad rows to the shard count and place (X, w) row-sharded —
    thin wrapper over the shared ``distributed.pad_and_shard_rows``."""
    from ..parallel.distributed import pad_and_shard_rows

    return pad_and_shard_rows(mesh, X, w)


def _lloyd_step(X, w, centers):
    """One Lloyd iteration's local sufficient statistics.

    Returns (per-cluster weighted coordinate sums, per-cluster weights,
    local weighted SSE) for masked rows X with weights w against the
    replicated (k, d) centers. All matmul-shaped for the MXU.
    """
    x_sq = jnp.sum(X * X, axis=1, keepdims=True)          # (n, 1)
    c_sq = jnp.sum(centers * centers, axis=1)             # (k,)
    d2 = x_sq - 2.0 * (X @ centers.T) + c_sq[None, :]     # (n, k) one matmul
    assign = jnp.argmin(d2, axis=1)                       # (n,)
    onehot = jax.nn.one_hot(assign, centers.shape[0],
                            dtype=X.dtype) * w[:, None]   # (n, k) masked
    sums = onehot.T @ X                                   # (k, d) MXU
    counts = jnp.sum(onehot, axis=0)                      # (k,)
    best = jnp.min(d2, axis=1)
    cost = jnp.sum(jnp.maximum(best, 0.0) * w)
    return sums, counts, cost


def _make_fit(mesh, k, max_iter, tol):
    """Build the jitted full KMeans fit: while_loop of psum'd Lloyd steps."""

    if mesh is None:
        def stats(X, w, centers):
            return _lloyd_step(X, w, centers)
    else:
        def local(X, w, centers):
            s, c, cost = _lloyd_step(X, w, centers)
            return (jax.lax.psum(s, DATA_AXIS), jax.lax.psum(c, DATA_AXIS),
                    jax.lax.psum(cost, DATA_AXIS))

        stats = shard_map(
            local, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P()),
            out_specs=(P(), P(), P()))

    def fit(X, w, centers0):
        def body(carry):
            centers, _, it, _ = carry
            sums, counts, cost = stats(X, w, centers)
            safe = jnp.maximum(counts, 1e-12)[:, None]
            new = jnp.where(counts[:, None] > 0, sums / safe, centers)
            shift = jnp.max(jnp.sum((new - centers) ** 2, axis=1))
            return (new, cost, it + 1, shift)

        def cond(carry):
            _, _, it, shift = carry
            return jnp.logical_and(it < max_iter, shift > tol * tol)

        init = (centers0, jnp.asarray(jnp.inf, X.dtype),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(jnp.inf, X.dtype))
        centers, cost, iters, _ = jax.lax.while_loop(cond, body, init)
        # one final stats pass so the reported cost matches the final centers
        _, counts, cost = stats(X, w, centers)
        return centers, cost, iters, counts

    return serialize_collectives(jax.jit(fit), mesh)


@functools.lru_cache(maxsize=None)
def _fit_cached(mesh, k, max_iter, tol):
    return _make_fit(mesh, k, max_iter, tol)


def _kmeans_pp_init(X, w, k, rng):
    """Greedy k-means++ seeding (host): first center uniform over valid
    rows, then each next center sampled ∝ current squared distance."""
    valid = np.flatnonzero(w > 0)
    if len(valid) < k:
        raise ValueError(f"k={k} exceeds the {len(valid)} valid rows")
    centers = [X[rng.choice(valid)]]
    d2 = None
    for _ in range(k - 1):
        diff = X[valid] - centers[-1]
        nd2 = np.sum(diff * diff, axis=1)
        d2 = nd2 if d2 is None else np.minimum(d2, nd2)
        total = d2.sum()
        if total <= 0:          # all remaining mass at existing centers
            extra = rng.choice(valid, size=k - len(centers), replace=False)
            centers.extend(X[i] for i in extra)
            break
        centers.append(X[valid[rng.choice(len(valid), p=d2 / total)]])
    return np.stack(centers[:k])


# ---------------------------------------------------------------------------
# KMeans' device entry: one pass function, k-means||, Lloyd's loop
# ---------------------------------------------------------------------------

#: The pass kernel's geometry: rows a grid step reads, rows an inner step
#: works on (centres on sublanes, rows on lanes), and the partial
#: accumulators a sum is spread over — a float32 sum is then 128 x 8 lanes
#: of block sums of 64 terms, combined pairwise at the end.
PASS_TILE = 8192
PASS_CHUNK = 512
PASS_PARTIALS = 8
#: Rows a block of :func:`_compact`'s two-level search.
COMPACT_BLOCK = 1024
#: Spark's ``LocalKMeans.kMeansPlusPlus`` iteration cap.
LOCAL_ITERATIONS = 30


class PassOut(NamedTuple):
    """What :func:`device_pass` returns; ``None`` where not asked for."""
    cost: jax.Array                 # () Σ over kept rows of the row's cost
    counts: Optional[jax.Array]     # (slots,) int32 kept rows a slot
    sums: Optional[jax.Array]       # (K, d) coordinate sums of kept rows
    row_cost: Optional[jax.Array]   # (1, row_slots) the row's cost
    row_idx: Optional[jax.Array]    # (1, row_slots) int32 the row's slot


def _round_up(x, m):
    return -(-x // m) * m


def pass_lowering(X, mesh=None):
    """Which lowering :func:`device_pass` takes — from the backend and the
    operand, never from a conf key: ``"pallas"`` for a float32 column on
    one TPU device, ``"xla"`` everywhere else (the CPU of the tests, a
    mesh, float64)."""
    if (jax.default_backend() == "tpu" and mesh is None
            and X.dtype == jnp.float32
            and len(X.sharding.device_set) == 1):
        return "pallas"
    return "xla"


def row_slots(n, lowering):
    """Length of the per-row vectors a pass reads and writes (weights,
    costs, indices): the kernel's grid covers whole tiles, plain
    ``jax.numpy`` needs no padding. X itself is never padded."""
    if lowering == "pallas":
        return _round_up(max(n, 1), PASS_TILE * PASS_PARTIALS)
    return n


def row_weights(mask, slots):
    """(1, slots) weights of the rows ``mask`` keeps: 1 a kept row, 0 a
    dropped one and past the table's end."""
    w = jnp.asarray(mask, jnp.bool_).astype(jnp.float32)
    return jnp.pad(w, (0, slots - w.shape[0]))[None, :]


def _pass_kernel(*refs, d, kp, prev, rows_out, sums, slots, base):
    """One tile of rows: distances of every row to ``kp`` centres, centres
    on sublanes and rows on lanes, a feature at a time; the nearest
    (ties: the lower slot); then whatever the caller asked for. Sums go to
    accumulators of this grid step first and to the output's once a step,
    so that a float32 total is a sum of block sums."""
    from jax.experimental import pallas as pl

    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    pc_ref, pi_ref = (next(it), next(it)) if prev else (None, None)
    cb_ref, pen_ref = next(it), next(it)
    cost_row, idx_row = (next(it), next(it)) if rows_out else (None, None)
    cost_out = next(it)
    cnt_out = next(it) if slots else None
    sum_out = next(it) if sums else None
    cost_loc = next(it)
    cnt_loc = next(it) if slots else None
    sum_loc = next(it) if sums else None
    accumulators = [(cost_out, cost_loc)]
    if slots:
        accumulators.append((cnt_out, cnt_loc))
    if sums:
        accumulators.append((sum_out, sum_loc))

    @pl.when(pl.program_id(1) == 0)
    def _():
        for out, _ in accumulators:
            out[...] = jnp.zeros_like(out)

    for _, loc in accumulators:
        loc[...] = jnp.zeros_like(loc)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (kp, PASS_CHUNK), 0)
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (slots, PASS_CHUNK), 0) \
        if slots else None

    def lanes(v):
        """(r, PASS_CHUNK) -> (r, 128): the chunk's lane groups added."""
        out = v[:, 0:128]
        for g in range(1, PASS_CHUNK // 128):
            out = out + v[:, g * 128:(g + 1) * 128]
        return out

    def chunk(_, start):
        # the offset rides in the carry: int32 under x64 too, where the
        # loop's own index would be int64 (which Mosaic does not take)
        at = pl.ds(pl.multiple_of(start, PASS_CHUNK), PASS_CHUNK)
        d2 = pen_ref[...]                       # 0, or +inf an unused slot
        for i in range(d):
            diff = jnp.broadcast_to(x_ref[i:i + 1, at],
                                    (kp, PASS_CHUNK)) - cb_ref[i]
            d2 = d2 + diff * diff
        best = jnp.min(d2, axis=0, keepdims=True)
        arg = jnp.min(jnp.where(d2 == best, iota_k, np.int32(kp)), axis=0,
                      keepdims=True)
        kept = w_ref[:, at] > 0
        if prev:
            before = pc_ref[:, at]
            closer = best < before
            best = jnp.where(closer, best, before)
            arg = jnp.where(closer, arg + np.int32(base), pi_ref[:, at])
        if rows_out:
            cost_row[:, at] = best
            idx_row[:, at] = arg
        cost_loc[...] += lanes(jnp.where(kept, best, jnp.zeros_like(best)))
        if slots:
            hit = jnp.logical_and(iota_s == arg, kept)
            cnt_loc[...] += lanes(hit.astype(jnp.int32))
        if sums:
            hit = jnp.logical_and(iota_k == arg, kept)
            for i in range(d):
                xb = jnp.broadcast_to(x_ref[i:i + 1, at], (kp, PASS_CHUNK))
                sum_loc[i] += lanes(jnp.where(hit, xb, jnp.zeros_like(xb)))
        return start + np.int32(PASS_CHUNK)

    jax.lax.fori_loop(0, PASS_TILE // PASS_CHUNK, chunk, np.int32(0))
    for out, loc in accumulators:
        out[0] += loc[...]


def _pass_pallas(xt, w, centres, ok, prev, base, rows_out, sums, slots,
                 interpret=False):
    """:func:`device_pass` as the Pallas kernel ``kmeans_pass``. ``xt`` is
    read in tiles of ``PASS_TILE`` rows; the last tile may reach past the
    table's end (whatever is read there has weight 0) and tiles wholly
    past it re-read the last one. The grid is (accumulator, tile).

    Pallas is imported here, by the one function that builds the kernel
    (rule 0: importing ``models`` loads no Pallas module)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d, n = xt.shape
    slots_w = w.shape[1]
    K = centres.shape[0]
    kp = _round_up(K, 8)
    sp = _round_up(slots, 8) if slots else 0
    steps = slots_w // (PASS_TILE * PASS_PARTIALS)
    if steps * PASS_TILE * PASS_PARTIALS != slots_w or slots_w < n:
        raise ValueError(f"row vectors of {slots_w} slots are not padded "
                         f"by row_slots for {n} rows")
    c = jnp.zeros((kp, d), jnp.float32).at[:K].set(
        centres.astype(jnp.float32))
    cb = jnp.broadcast_to(c.T[:, :, None], (d, kp, PASS_CHUNK))
    used = jnp.zeros((kp,), jnp.bool_).at[:K].set(ok)
    pen = jnp.broadcast_to(
        jnp.where(used, 0.0, jnp.inf).astype(jnp.float32)[:, None],
        (kp, PASS_CHUNK))
    steps32, zero = np.int32(steps), np.int32(0)  # int32 under x64 too
    last = np.int32(-(-n // PASS_TILE) - 1)

    def row_map(p, i):
        return zero, p * steps32 + i

    def x_map(p, i):
        return zero, jnp.minimum(p * steps32 + i, last)

    row_spec = pl.BlockSpec((1, PASS_TILE), row_map)
    in_specs = [pl.BlockSpec((d, PASS_TILE), x_map), row_spec]
    args = [xt, w]
    if prev is not None:
        in_specs += [row_spec, row_spec]
        args += list(prev)
    in_specs += [
        pl.BlockSpec((d, kp, PASS_CHUNK), lambda p, i: (zero, zero, zero)),
        pl.BlockSpec((kp, PASS_CHUNK), lambda p, i: (zero, zero))]
    args += [cb, pen]
    out_specs, out_shape, scratch = [], [], []
    if rows_out:
        out_specs += [row_spec, row_spec]
        out_shape += [jax.ShapeDtypeStruct((1, slots_w), jnp.float32),
                      jax.ShapeDtypeStruct((1, slots_w), jnp.int32)]

    def accumulator(shape, dtype):
        nd = len(shape)
        out_specs.append(pl.BlockSpec(
            (1,) + shape, lambda p, i: (p,) + (zero,) * nd))
        out_shape.append(jax.ShapeDtypeStruct((PASS_PARTIALS,) + shape,
                                              dtype))
        scratch.append(pltpu.VMEM(shape, dtype))

    accumulator((1, 128), jnp.float32)
    if slots:
        accumulator((sp, 128), jnp.int32)
    if sums:
        accumulator((d, kp, 128), jnp.float32)
    outs = list(pl.pallas_call(
        functools.partial(_pass_kernel, d=d, kp=kp, prev=prev is not None,
                          rows_out=rows_out, sums=sums, slots=sp,
                          base=base),
        grid=(PASS_PARTIALS, steps), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name="kmeans_pass")(*args))
    row_cost, row_idx = (outs.pop(0), outs.pop(0)) if rows_out \
        else (None, None)
    cost = jnp.sum(outs.pop(0))
    counts = jnp.sum(outs.pop(0), axis=(0, 2))[:slots] if slots else None
    total = jnp.sum(outs.pop(0), axis=(0, 3)).T[:K] if sums else None
    return PassOut(cost, counts, total, row_cost, row_idx)


def _pass_xla(xt, w, centres, ok, prev, base, rows_out, sums, slots):
    """:func:`device_pass` in plain ``jax.numpy``; the compiler fuses the
    (n, K, d) differences into their reductions."""
    X = xt.T
    kept = w[0] > 0
    d2 = jnp.sum((X[:, None, :] - centres[None].astype(X.dtype)) ** 2,
                 axis=2)
    d2 = jnp.where(ok[None, :], d2, jnp.inf)
    best = jnp.min(d2, axis=1)
    arg = jnp.argmin(d2, axis=1).astype(jnp.int32)
    if prev is not None:
        closer = best < prev[0][0]
        best = jnp.where(closer, best, prev[0][0])
        arg = jnp.where(closer, arg + base, prev[1][0])
    cost = jnp.sum(jnp.where(kept, best, 0.0))
    counts = total = None
    if slots:
        counts = jnp.sum(jnp.logical_and(
            arg[:, None] == jnp.arange(slots)[None, :], kept[:, None]),
            axis=0, dtype=jnp.int32)
    if sums:
        hit = jnp.logical_and(
            arg[:, None] == jnp.arange(centres.shape[0])[None, :],
            kept[:, None])
        total = jnp.sum(jnp.where(hit[:, :, None], X[:, None, :], 0.0),
                        axis=0)
    return PassOut(cost, counts, total,
                   best[None, :] if rows_out else None,
                   arg[None, :] if rows_out else None)


def device_pass(xt, w, centres, ok=None, prev=None, base=0, rows_out=False,
                sums=False, slots=0, lowering="xla"):
    """ONE pass over the rows — everything k-means‖, Lloyd's loop, the
    model's ``transform`` and its cost read of X goes through here.

    ``xt`` (d, n): the feature column transposed (a bitcast of how a TPU
    holds an ``(n, d)`` column); ``w`` (1, :func:`row_slots`): 1 a kept
    row, 0 elsewhere (:func:`row_weights`); ``centres`` (K, d), of which
    ``ok`` (K,) are in use. A row's cost is its least squared distance
    ``Σ_j (x_j − c_j)²`` to a centre in use, its index that centre's slot
    plus ``base`` (ties: the lower slot) — or, with ``prev`` = (row costs,
    row indices) of earlier passes, what it was before unless a centre
    here is strictly closer. Returns :class:`PassOut`: the kept rows'
    total cost always; ``rows_out``: the per-row vectors (every row's,
    kept or not; a dropped row's are unspecified); ``slots``: kept rows an
    index below ``slots``; ``sums``: kept rows' coordinate sums a centre
    here (``prev`` must be ``None``). Dropped rows and their NaNs reach
    no sum."""
    if ok is None:
        ok = jnp.ones((centres.shape[0],), jnp.bool_)
    if lowering == "pallas":
        return _pass_pallas(xt, w, centres, ok, prev, base, rows_out, sums,
                            slots)
    return _pass_xla(xt, w, centres, ok, prev, base, rows_out, sums, slots)


def init_bucket(k):
    """Slots a k-means‖ round's draws are compacted into. A round draws a
    sum of independent Bernoullis of mean at most 2k, so of variance at
    most 2k: six deviations over the mean, rounded up to the kernel's
    eight."""
    return _round_up(int(np.ceil(2 * k + 6 * np.sqrt(2 * k))), 8)


def _compact(chosen, bucket):
    """Indices of the first ``bucket`` set entries of ``chosen`` (1, m),
    in order, and how many are set — without a sort and without an
    m-sized scan: counts a block of ``COMPACT_BLOCK``, a cumulative sum
    over the blocks, then for every slot of the bucket its block (a binary
    search) and its place in that block. Slots past the count hold
    garbage."""
    flat = chosen[0]
    flat = jnp.pad(flat, (0, (-flat.shape[0]) % COMPACT_BLOCK))
    blocks = flat.reshape(-1, COMPACT_BLOCK)
    counts = jnp.sum(blocks, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    slot = jnp.arange(bucket, dtype=jnp.int32)
    blk = jnp.minimum(jnp.searchsorted(ends, slot, side="right"),
                      blocks.shape[0] - 1).astype(jnp.int32)
    within = slot - (ends[blk] - counts[blk])
    seen = jnp.cumsum(blocks[blk], axis=1, dtype=jnp.int32)
    pos = jnp.argmax(seen > within[:, None], axis=1).astype(jnp.int32)
    return blk * COMPACT_BLOCK + pos, ends[-1]


@jax.jit
def _validate(X, mask):
    """[kept rows, some kept row holds a NaN or an inf], int32."""
    with _obs.scope("fit.validate"):
        kept = jnp.asarray(mask, jnp.bool_)
        bad = jnp.any(jnp.logical_and(~jnp.isfinite(X), kept[:, None]))
        return jnp.stack([jnp.sum(kept, dtype=jnp.int32),
                          bad.astype(jnp.int32)])


@functools.lru_cache(maxsize=None)
def _init_program(k, steps, bucket, lowering):
    """k-means‖ (Spark 2.4 ``KMeans.initKMeansParallel``), jitted:
    ``(X, mask, key) -> (candidates, in use, weights, drawn a round)``.

    A first centre drawn uniformly from the kept rows; ``steps`` rounds of
    (a) every kept row's cost, the least squared distance to the
    candidates so far — as Spark, a round measures against the candidates
    of the round before only and keeps the minimum, here with the index of
    the nearest beside it; (b) every kept row drawn independently with
    probability ``min(1, 2 k cost / Σcost)``; (c) the drawn rows appended,
    ``bucket`` slots a round (:func:`_compact`; more draws than slots are
    reported and dropped); then a pass against the last round's draws that
    completes every row's nearest candidate and counts the kept rows a
    candidate (Spark's ``countByValue`` of ``findClosest``) — ``steps + 1``
    passes over X, each against the newest candidates only. The
    distribution is Spark's; the stream is ``jax.random``'s (Spark draws
    from a per-partition XORShift)."""
    def run(X, mask, key):
        n, d = X.shape
        slots_n = row_slots(n, lowering)
        total = 1 + steps * bucket
        xt = X.T
        keys = jax.random.split(key, steps + 1)
        with _obs.scope("kmeans.init.sample"):
            w = row_weights(mask, slots_n)
            u0 = jax.random.uniform(keys[0], (n,))
            first = jnp.argmax(jnp.where(mask, u0, -1.0))
            cands = jnp.zeros((total, d), X.dtype).at[0].set(X[first])
            ok = jnp.zeros((total,), jnp.bool_).at[0].set(True)
        prev, lo, hi, drawn = None, 0, 1, []
        for s in range(steps):
            with _obs.scope("kmeans.init.cost"):
                out = device_pass(xt, w, cands[lo:hi], ok[lo:hi], prev=prev,
                                  base=lo, rows_out=True, lowering=lowering)
            prev = (out.row_cost, out.row_idx)
            with _obs.scope("kmeans.init.sample"):
                # all mass at the candidates: 0 / 0 draws nothing
                p = (2.0 * k) * out.row_cost / out.cost
                u = jax.random.uniform(keys[s + 1], p.shape, p.dtype)
                chosen = jnp.logical_and(u < p, w > 0)
                idx, m = _compact(chosen, bucket)
                lo, hi = 1 + s * bucket, 1 + (s + 1) * bucket
                cands = cands.at[lo:hi].set(X[jnp.minimum(idx, n - 1)])
                ok = ok.at[lo:hi].set(jnp.arange(bucket) < m)
                drawn.append(m)
        with _obs.scope("kmeans.init.weigh"):
            out = device_pass(xt, w, cands[lo:hi], ok[lo:hi], prev=prev,
                              base=lo, slots=total, lowering=lowering)
        return cands, ok, out.counts, jnp.stack(drawn)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _random_program(k):
    """``init_mode="random"``: k distinct kept rows, uniformly — the k
    kept rows of the largest uniform draws, taken one ``argmax`` at a time
    (k short passes over a vector of draws; a ``top_k`` of 5e7 entries is
    a sort program, which a TPU compiles for half a minute)."""
    def run(X, mask, key):
        with _obs.scope("kmeans.init.sample"):
            u = jnp.where(mask, jax.random.uniform(key, (X.shape[0],)), -1.0)

            def draw(j, carry):
                u, idx = carry
                i = jnp.argmax(u).astype(jnp.int32)
                return u.at[i].set(-1.0), idx.at[j].set(i)

            _, idx = jax.lax.fori_loop(
                0, k, draw, (u, jnp.zeros((k,), jnp.int32)))
            return X[idx]

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _lloyd_program(max_iter, tol, lowering):
    """Lloyd's loop, jitted: ``(X, mask, centres0) -> (floats, sizes)``.
    An iteration is one pass (:func:`device_pass` with the sums); it stops
    when every centre moved at most ``tol`` (squared: ``tol²``) or at
    ``max_iter`` (MLlib's rule); an empty cluster keeps its centre. One
    more pass gives the sizes and the cost AT the final centres.
    ``floats``: the centres before the loop and after every iteration
    (``(max_iter + 1) k d``; NaN past the last), the iterations run, the
    cost; ``sizes`` (k,) int32."""
    def run(X, mask, centres0):
        k = centres0.shape[0]
        xt = X.T
        w = row_weights(mask, row_slots(X.shape[0], lowering))
        history = jnp.full((max_iter + 1,) + centres0.shape, jnp.nan,
                           X.dtype).at[0].set(centres0)

        def body(carry):
            centres, it, _, history = carry
            with _obs.scope("kmeans.assign"):
                out = device_pass(xt, w, centres, sums=True, slots=k,
                                  lowering=lowering)
            with _obs.scope("kmeans.update"):
                size = out.counts.astype(X.dtype)[:, None]
                new = jnp.where(size > 0, out.sums / jnp.maximum(size, 1.0),
                                centres)
                shift = jnp.max(jnp.sum((new - centres) ** 2, axis=1))
                history = jax.lax.dynamic_update_index_in_dim(
                    history, new, it + 1, 0)
            return new, it + 1, shift, history

        def cond(carry):
            _, it, shift, _ = carry
            return jnp.logical_and(it < max_iter, shift > tol * tol)

        centres, iters, _, history = jax.lax.while_loop(
            cond, body, (centres0.astype(X.dtype), jnp.asarray(0, jnp.int32),
                         jnp.asarray(jnp.inf, X.dtype), history))
        with _obs.scope("kmeans.assign"):
            out = device_pass(xt, w, centres, slots=k, lowering=lowering)
        floats = jnp.concatenate([
            history.ravel(), jnp.stack([iters.astype(X.dtype), out.cost])])
        return floats, out.counts

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _score_program(lowering, rows_out):
    """The model's pass, jitted: ``(X, mask, centres)`` -> every row's
    nearest centre as a float column (``transform``: one pass, only the
    prediction column written), or the kept rows' cost
    (``compute_cost``)."""
    def run(X, mask, centres):
        n = X.shape[0]
        with _obs.scope("kmeans.score"):
            w = row_weights(mask, row_slots(n, lowering))
            out = device_pass(X.T, w, centres.astype(X.dtype),
                              rows_out=rows_out, lowering=lowering)
            if not rows_out:
                return out.cost
            nearest = jnp.minimum(out.row_idx[0, :n], centres.shape[0] - 1)
            return nearest.astype(X.dtype)

    return jax.jit(run)


def _sq_dists(points, centres):
    return ((points[:, None, :] - centres[None]) ** 2).sum(axis=2)


def local_kmeans_pp(points, weights, k, rng, iterations=LOCAL_ITERATIONS):
    """Spark's ``LocalKMeans.kMeansPlusPlus`` over the candidates alone
    (a few dozen rows, float64 numpy, on the host as Spark runs it on the
    driver): a weighted k-means++ — the first centre drawn by weight, each
    next by weight x cost — then at most ``iterations`` weighted Lloyd
    steps, stopped when no candidate changes its centre; a centre left
    without candidates moves to a candidate drawn uniformly. The draws are
    numpy's, not ``java.util.Random``'s."""
    points = np.asarray(points, np.float64)
    weights = np.asarray(weights, np.float64)
    m = len(points)
    centres = np.empty((k, points.shape[1]))
    centres[0] = points[rng.choice(m, p=weights / weights.sum())]
    cost = _sq_dists(points, centres[:1])[:, 0]
    for i in range(1, k):
        mass = np.cumsum(weights * cost)
        j = min(int(np.searchsorted(mass, rng.random() * mass[-1],
                                    side="right")), m - 1)
        centres[i] = points[j]
        cost = np.minimum(cost, _sq_dists(points, centres[i:i + 1])[:, 0])
    assign = np.full(m, -1)
    for _ in range(iterations):
        nearest = _sq_dists(points, centres).argmin(axis=1)
        if np.array_equal(nearest, assign):
            break
        assign = nearest
        for j in range(k):
            mine = assign == j
            total = weights[mine].sum()
            centres[j] = (weights[mine, None] * points[mine]).sum(0) / total \
                if total > 0 else points[rng.integers(m)]
    return centres


@persistable
class KMeans(Estimator):
    """MLlib ``KMeans`` surface: ``setK/setMaxIter/setTol/setSeed/
    setInitMode/setInitSteps/setFeaturesCol/setPredictionCol`` +
    ``fit(frame[, mesh])``.

    ``init_mode="k-means||"`` (MLlib's default; ``"k-means++"`` is taken
    for it): k-means‖ on the device with ``init_steps`` rounds
    (:func:`_init_program`), its candidates reduced to k on the host by a
    weighted k-means++ over the candidates alone (:func:`local_kmeans_pp`,
    where Spark runs it on the driver); if they number k or fewer they ARE
    the centres, as in Spark — a model of fewer than k clusters.
    ``"random"``: k distinct kept rows, drawn on the device. Randomness is
    ``jax.random`` (and numpy on the host) keyed by ``seed``: the
    distributions are Spark's, the streams are not (Spark draws from a
    per-partition XORShift)."""

    _persist_attrs = ('k', 'max_iter', 'tol', 'seed', 'init_mode',
                      'init_steps', 'features_col', 'prediction_col')

    # class-level default: estimators persisted before this param existed
    init_steps = 2

    def __init__(self, k: int = 2, max_iter: int = 20, tol: float = 1e-4,
                 seed: int = 0, init_mode: str = "k-means||",
                 features_col: str = "features",
                 prediction_col: str = "prediction", init_steps: int = 2):
        if k < 1:
            raise ValueError("k must be >= 1")
        if init_mode not in ("k-means||", "k-means++", "random"):
            raise ValueError(f"init_mode={init_mode!r}")
        self.k = int(k)
        self.max_iter = int(max_iter)
        self.tol = float(tol)
        self.seed = int(seed)
        self.init_mode = init_mode
        self.features_col = features_col
        self.prediction_col = prediction_col
        self.set_init_steps(init_steps)

    def set_init_steps(self, v):
        if v < 1:
            raise ValueError("init_steps must be >= 1")
        self.init_steps = int(v)
        return self

    setInitSteps = set_init_steps

    def set_k(self, v):
        if v < 1:
            raise ValueError("k must be >= 1")
        self.k = int(v)
        return self

    setK = set_k

    def set_max_iter(self, v):
        self.max_iter = int(v)
        return self

    setMaxIter = set_max_iter

    def set_tol(self, v):
        self.tol = float(v)
        return self

    setTol = set_tol

    def set_seed(self, v):
        self.seed = int(v)
        return self

    setSeed = set_seed

    def set_init_mode(self, v):
        if v not in ("k-means||", "k-means++", "random"):
            raise ValueError(f"init_mode={v!r}")
        self.init_mode = v
        return self

    setInitMode = set_init_mode

    def set_features_col(self, v):
        self.features_col = v
        return self

    setFeaturesCol = set_features_col

    def set_prediction_col(self, v):
        self.prediction_col = v
        return self

    setPredictionCol = set_prediction_col

    def get_k(self):
        return self.k

    getK = get_k

    def fit(self, frame: Frame, mesh=None) -> "KMeansModel":
        """The device entry. Spans ``fit.kmeans`` > ``fit.prepare``
        (> ``fit.extract``, ``fit.validate``, ``fit.kmeans.init``) and
        ``fit.solve``; three reads, each a few scalars or a few KB:
        ``kmeans.validate``, ``kmeans.candidates``, ``kmeans.result``."""
        from ..utils.profiling import counters

        mesh = normalize_mesh(mesh)
        key = jax.random.PRNGKey(self.seed)
        parallel = self.init_mode != "random"
        with _obs.span("fit.kmeans", cat="fit") as root:
            with _obs.span("fit.prepare", cat="fit") as prep:
                with _obs.span("fit.extract", cat="fit"):
                    X, mask = _features(frame, self.features_col)
                n, d = int(X.shape[0]), int(X.shape[1])
                lowering = pass_lowering(X, mesh)
                prep.set(rows=n, features=d, lowering=lowering)
                # both programs are queued before the first read: the
                # device seeds while the host looks at the validation
                checked = _validate(X, mask)
                bucket = init_bucket(self.k)
                seeded = _init_program(self.k, self.init_steps, bucket,
                                       lowering)(X, mask, key) \
                    if parallel else _random_program(self.k)(X, mask, key)
                with _obs.span("fit.validate", cat="fit") as val:
                    checked = _read(checked, "kmeans.validate")
                    val.set(host_read_bytes=checked.nbytes)
                    rows, bad = (int(v) for v in checked)
                    if rows < self.k:
                        raise ValueError(
                            f"k={self.k} exceeds the {rows} valid rows")
                    if bad:
                        raise ValueError("KMeans: feature matrix has "
                                         "NaN/inf in valid rows")
                counters.increment("kmeans.fit_device")
                with _obs.span("fit.kmeans.init", cat="fit",
                               mode=self.init_mode) as init:
                    seeded = _read(seeded, "kmeans.candidates")
                    candidates = weights = None
                    if parallel:
                        candidates, centres0, weights = self._reduce(
                            seeded, bucket, init)
                    else:
                        centres0 = seeded
            passes = 1 + (self.init_steps + 1 if parallel else 0)
            with _obs.span("fit.solve", cat="fit") as solve:
                centres0 = np.asarray(centres0, X.dtype)
                if mesh is None:
                    floats, sizes = _read(
                        _lloyd_program(self.max_iter, self.tol, lowering)(
                            X, mask, centres0), "kmeans.result")
                    history = floats[:-2].reshape((-1,) + centres0.shape)
                    iters, cost = int(floats[-2]), float(floats[-1])
                    history = history[:iters + 1]
                    centres = history[-1]
                else:
                    # zero the dropped slots: 0-weighted statistics of the
                    # matmul form stay finite only then (0·NaN = NaN)
                    Xd, wd = _pad_and_shard(
                        jnp.where(mask[:, None], X, 0.0),
                        mask.astype(X.dtype), mesh, X.dtype)
                    centres, cost, iters, sizes = _read(
                        _fit_cached(mesh, len(centres0), self.max_iter,
                                    self.tol)(Xd, wd, jnp.asarray(centres0)),
                        "kmeans.result")
                    history, iters, cost = None, int(iters), float(cost)
                solve.set(iterations=iters)
            passes += iters + 1
            counters.increment("kmeans.iterations", iters)
            counters.increment("kmeans.data_passes", passes)
            root.set(rows=n, features=d, k=len(centres0), passes=passes)
        return KMeansModel(
            np.asarray(centres), self.features_col, self.prediction_col,
            cost, iters, np.asarray(sizes).astype(np.int64).tolist(),
            history=history, init_candidates=candidates,
            init_weights=weights)

    def _reduce(self, seeded, bucket, span):
        """k-means‖'s last step, on the host over the candidates alone:
        ``(candidates in use, k centres, the candidates' weights)``."""
        from ..utils.profiling import counters

        cands, ok, weights, drawn = seeded
        over = int(np.count_nonzero(drawn > bucket))
        if over:
            # more draws than the bucket holds: the rest were dropped.
            # The seeding stands, from fewer candidates than Spark's
            counters.increment("kmeans.init_overflow", over)
        candidates = np.asarray(cands[ok], np.float64)
        weights = np.asarray(weights[ok], np.int64)
        counters.increment("kmeans.init_candidates", len(candidates))
        span.set(candidates=len(candidates), overflow=over,
                 steps=self.init_steps)
        # Spark's ``distinct``: equal rows are one candidate
        points, inverse = np.unique(candidates, axis=0, return_inverse=True)
        mass = np.bincount(inverse.ravel(), weights, len(points))
        if len(points) <= self.k:
            return candidates, points, weights
        rng = np.random.default_rng(self.seed)
        return candidates, local_kmeans_pp(points, mass, self.k, rng), \
            weights


def _features(frame, features_col):
    """The feature column as the device holds it, 2-D, in the
    configuration's float type (no copy where it has it), and the mask."""
    X = jnp.asarray(frame._column_values(features_col), float_dtype())
    if X.ndim == 1:
        X = X[:, None]
    return X, jnp.asarray(frame.mask, jnp.bool_)


def _read(x, site: str):
    """One counted blocking read of a few small device arrays."""
    with _obs.host_reading(site) as rd:
        out = jax.device_get(x)
        rd.done(sum(a.nbytes for a in jax.tree_util.tree_leaves(out)))
    return out


@persistable
class KMeansModel(Model):
    """Fitted centers + the MLlib model surface: ``transform`` (nearest
    center as the prediction column: one pass over the feature column,
    only the prediction written), ``clusterCenters``, ``summary``
    (cluster sizes, training cost, iterations, and what the fit's device
    entry recorded: the centres before the loop and after every
    iteration, k-means‖'s candidates and their weights), ``predict``
    (host scalar path, like ``LinearRegressionModel.predict``)."""

    _persist_attrs = ('centers', 'features_col', 'prediction_col',
                      'training_cost', 'num_iters', 'cluster_sizes',
                      'history', 'init_candidates', 'init_weights')

    # class-level defaults: models persisted before these existed
    history = init_candidates = init_weights = None

    def __init__(self, centers, features_col, prediction_col,
                 training_cost=float("nan"), num_iters=0,
                 cluster_sizes=None, history=None, init_candidates=None,
                 init_weights=None):
        self.centers = np.asarray(centers)
        self.features_col = features_col
        self.prediction_col = prediction_col
        self.training_cost = training_cost
        self.num_iters = num_iters
        self.cluster_sizes = cluster_sizes or []
        self.history = history
        self.init_candidates = init_candidates
        self.init_weights = init_weights

    def cluster_centers(self):
        return [c for c in self.centers]

    clusterCenters = cluster_centers

    @property
    def k(self):
        return self.centers.shape[0]

    def _score(self, frame, rows_out):
        X, mask = _features(frame, self.features_col)
        return _score_program(pass_lowering(X), rows_out)(
            X, mask, self.centers.astype(X.dtype))

    def transform(self, frame: Frame) -> Frame:
        with _obs.span("model.transform", cat="model",
                       rows=int(frame.mask.shape[0])):
            return frame.with_column(self.prediction_col,
                                     self._score(frame, True))

    def predict(self, features) -> int:
        x = np.asarray(features, np.float64).reshape(1, -1)
        return int(_sq_dists(x, np.asarray(self.centers,
                                           np.float64)).argmin())

    def compute_cost(self, frame: Frame) -> float:
        """SSE to the nearest center over valid rows (MLlib 2.x
        ``computeCost``): one pass, one scalar read."""
        with _obs.span("model.compute_cost", cat="model",
                       rows=int(frame.mask.shape[0])):
            return float(host_fetch(self._score(frame, False)))

    computeCost = compute_cost

    @property
    def summary(self):
        return KMeansSummary(self)

    @property
    def has_summary(self):
        return True

    hasSummary = has_summary


class KMeansSummary:
    """MLlib ``KMeansSummary``: k, cluster sizes, training cost, iterations."""

    def __init__(self, model: KMeansModel):
        self._model = model

    @property
    def k(self):
        return self._model.k

    @property
    def cluster_sizes(self):
        return list(self._model.cluster_sizes)

    clusterSizes = cluster_sizes

    @property
    def training_cost(self):
        return self._model.training_cost

    trainingCost = training_cost

    @property
    def num_iter(self):
        return self._model.num_iters

    numIter = num_iter

    @property
    def history(self):
        """(iterations + 1, k, d): the centres before Lloyd's loop and
        after every iteration (``None`` for a fit on a mesh)."""
        return self._model.history

    @property
    def init_candidates(self):
        """k-means‖'s candidates (rows of the table) and, beside them, the
        kept rows nearest each; ``None`` for ``init_mode="random"``."""
        return self._model.init_candidates, self._model.init_weights


# ---------------------------------------------------------------------------
# GaussianMixture (MLlib org.apache.spark.ml.clustering.GaussianMixture)
# ---------------------------------------------------------------------------

def _gmm_log_prob(X, means, chols):
    """(n, k) log N(x | mean_j, cov_j) via per-component Cholesky solves.

    ``chols`` (k, d, d) lower Cholesky factors. vmapped over components:
    each solve is a batched triangular solve + reduction — all XLA-native,
    no per-row work.
    """
    d = X.shape[1]
    log2pi = jnp.log(2.0 * jnp.pi).astype(X.dtype)

    def one(mean, chol):
        diff = (X - mean[None, :]).T                       # (d, n)
        z = jax.scipy.linalg.solve_triangular(chol, diff, lower=True)
        maha = jnp.sum(z * z, axis=0)                      # (n,)
        logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)))
        return -0.5 * (d * log2pi + logdet + maha)

    return jax.vmap(one)(means, chols).T                   # (n, k)


def _gmm_estep(X, w, weights, means, chols):
    """Local E-step sufficient statistics for one shard.

    Returns (Nk (k,), Sk (k, d), Ck (k, d, d) raw scatter Σ r·x·xᵀ,
    weighted log-likelihood). Responsibilities never leave the device.
    """
    logp = _gmm_log_prob(X, means, chols) + jnp.log(weights)[None, :]
    lse = jax.nn.logsumexp(logp, axis=1)                   # (n,)
    resp = jnp.exp(logp - lse[:, None]) * w[:, None]       # masked (n, k)
    Nk = jnp.sum(resp, axis=0)
    Sk = resp.T @ X                                        # (k, d) MXU
    # per-component scatter: k MXU matmuls via vmap over the component axis
    Ck = jax.vmap(lambda r: (X * r[:, None]).T @ X)(resp.T)
    ll = jnp.sum(lse * w)
    return Nk, Sk, Ck, ll


def _make_gmm_fit(mesh, k, max_iter, tol, reg):
    if mesh is None:
        def stats(X, w, weights, means, chols):
            return _gmm_estep(X, w, weights, means, chols)
    else:
        def local(X, w, weights, means, chols):
            Nk, Sk, Ck, ll = _gmm_estep(X, w, weights, means, chols)
            return (jax.lax.psum(Nk, DATA_AXIS), jax.lax.psum(Sk, DATA_AXIS),
                    jax.lax.psum(Ck, DATA_AXIS), jax.lax.psum(ll, DATA_AXIS))

        stats = shard_map(
            local, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(), P(), P()),
            out_specs=(P(), P(), P(), P()))

    def chol_of(covs):
        d = covs.shape[-1]
        return jnp.linalg.cholesky(
            covs + reg * jnp.eye(d, dtype=covs.dtype)[None])

    def fit(X, w, n, weights0, means0, covs0):
        def body(carry):
            weights, means, covs, last_ll, it, _ = carry
            Nk, Sk, Ck, ll = stats(X, w, weights, means, chol_of(covs))
            safe = jnp.maximum(Nk, 1e-12)
            new_means = Sk / safe[:, None]
            new_covs = (Ck / safe[:, None, None]
                        - new_means[:, :, None] * new_means[:, None, :])
            new_weights = Nk / n
            return (new_weights, new_means, new_covs, ll, it + 1,
                    jnp.abs(ll - last_ll))

        def cond(carry):
            _, _, _, _, it, delta = carry
            return jnp.logical_and(it < max_iter, delta > tol)

        init = (weights0, means0, covs0,
                jnp.asarray(-jnp.inf, X.dtype), jnp.asarray(0, jnp.int32),
                jnp.asarray(jnp.inf, X.dtype))
        weights, means, covs, ll, iters, _ = jax.lax.while_loop(
            cond, body, init)
        return weights, means, covs, ll, iters

    return serialize_collectives(jax.jit(fit), mesh)


@functools.lru_cache(maxsize=None)
def _gmm_fit_cached(mesh, k, max_iter, tol, reg):
    return _make_gmm_fit(mesh, k, max_iter, tol, reg)


@persistable
class GaussianMixture(Estimator):
    """MLlib ``GaussianMixture``: full-covariance GMM fit by EM.

    TPU-first: the E-step is one fused (n, k) log-prob computation (batched
    triangular solves + an MXU matmul per component for the scatter); the
    whole EM loop runs inside one ``lax.while_loop`` with zero host
    round-trips, and under a mesh the (k + k·d + k·d²+1) sufficient
    statistics reduce with one fused psum — the ``treeAggregate`` analogue
    (SURVEY.md §3.3). MLlib dependency surface: `/root/reference/pom.xml:29-32`.
    """

    _persist_attrs = ('k', 'max_iter', 'tol', 'seed', 'reg',
                      'features_col', 'prediction_col', 'probability_col')

    def __init__(self, k: int = 2, max_iter: int = 100, tol: float = 0.01,
                 seed: int = 0, reg: float = 1e-6,
                 features_col: str = "features",
                 prediction_col: str = "prediction",
                 probability_col: str = "probability"):
        if k < 1:
            raise ValueError("k must be >= 1")
        self.k = int(k)
        self.max_iter = int(max_iter)
        self.tol = float(tol)
        self.seed = int(seed)
        self.reg = float(reg)
        self.features_col = features_col
        self.prediction_col = prediction_col
        self.probability_col = probability_col

    def set_k(self, v):
        if v < 1:
            raise ValueError("k must be >= 1")
        self.k = int(v)
        return self

    setK = set_k

    def set_max_iter(self, v):
        self.max_iter = int(v)
        return self

    setMaxIter = set_max_iter

    def set_tol(self, v):
        self.tol = float(v)
        return self

    setTol = set_tol

    def set_seed(self, v):
        self.seed = int(v)
        return self

    setSeed = set_seed

    def set_features_col(self, v):
        self.features_col = v
        return self

    setFeaturesCol = set_features_col

    def fit(self, frame: Frame, mesh=None) -> "GaussianMixtureModel":
        dt = np.dtype(float_dtype())
        X = np.asarray(frame._column_values(self.features_col), dt)
        if X.ndim == 1:
            X = X[:, None]
        w = np.asarray(frame.mask, dt)
        # masked slots may hold NaN (dropna/filter keep values in place);
        # zero them so 0-weighted statistics stay finite (0·NaN = NaN)
        X = np.where(w[:, None] > 0, X, 0.0)
        n_valid = float(w.sum())
        if n_valid < self.k:
            raise ValueError(f"k={self.k} exceeds the {int(n_valid)} valid rows")

        # init: k-means++ means, shared diagonal covariance of the data,
        # uniform weights (deterministic given seed)
        rng = np.random.default_rng(self.seed)
        means0 = _kmeans_pp_init(X, w, self.k, rng).astype(dt)
        mu = (w @ X) / n_valid
        var = (w @ (X * X)) / n_valid - mu * mu
        covs0 = np.tile(np.diag(np.maximum(var, 1e-6)).astype(dt),
                        (self.k, 1, 1))
        weights0 = np.full((self.k,), 1.0 / self.k, dt)

        mesh = normalize_mesh(mesh)
        Xd, wd = _pad_and_shard(X, w, mesh, dt)
        fit_fn = _gmm_fit_cached(mesh, self.k, self.max_iter, self.tol,
                                 self.reg)
        weights, means, covs, ll, iters = jax.block_until_ready(
            fit_fn(Xd, wd, jnp.asarray(n_valid, dt), jnp.asarray(weights0),
                   jnp.asarray(means0), jnp.asarray(covs0)))
        return GaussianMixtureModel(
            np.asarray(weights, np.float64), np.asarray(means, np.float64),
            np.asarray(covs, np.float64), self._params_dict(),
            log_likelihood=float(ll), num_iters=int(iters))

    def _params_dict(self):
        return {k: getattr(self, k) for k in (
            "k", "max_iter", "tol", "seed", "reg", "features_col",
            "prediction_col", "probability_col")}


@persistable
class GaussianMixtureModel(Model):
    """Fitted mixture: ``weights`` (k,), per-component ``gaussians``
    (mean, cov). ``transform`` appends probability (posterior vector) and
    prediction (argmax posterior) columns, like MLlib."""

    _persist_attrs = ('weights', 'means', 'covs', '_params',
                      'log_likelihood', 'num_iters')

    def __init__(self, weights, means, covs, params=None,
                 log_likelihood=float("nan"), num_iters=0):
        self.weights = np.asarray(weights)
        self.means = np.asarray(means)
        self.covs = np.asarray(covs)
        self._params = dict(params or {})
        self.log_likelihood = log_likelihood
        self.num_iters = num_iters

    @property
    def k(self):
        return int(self.weights.shape[0])

    getK = k

    @property
    def gaussians(self):
        return [{"mean": self.means[j], "cov": self.covs[j]}
                for j in range(self.k)]

    @property
    def gaussians_df(self) -> Frame:
        """MLlib's ``gaussiansDF``: one row per component."""
        return Frame({
            "mean": np.asarray([m for m in self.means], object),
            "cov": np.asarray([c for c in self.covs], object),
        })

    gaussiansDF = gaussians_df

    def _posterior(self, X):
        dt = X.dtype
        reg = self._params.get("reg", 1e-6)
        chols = jnp.linalg.cholesky(
            jnp.asarray(self.covs, dt)
            + reg * jnp.eye(self.covs.shape[-1], dtype=dt)[None])
        logp = _gmm_log_prob(X, jnp.asarray(self.means, dt), chols) \
            + jnp.log(jnp.asarray(self.weights, dt))[None, :]
        return jax.nn.softmax(logp, axis=1)

    def transform(self, frame: Frame) -> Frame:
        p = self._params
        X = jnp.asarray(frame._column_values(p.get("features_col",
                                                   "features")),
                        float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        post = self._posterior(X)
        pred = jnp.argmax(post, axis=1).astype(float_dtype())
        out = frame.with_column(p.get("probability_col", "probability"),
                                post)
        return out.with_column(p.get("prediction_col", "prediction"), pred)

    def predict(self, features) -> int:
        x = jnp.asarray(np.asarray(features, np.float64).reshape(1, -1),
                        float_dtype())
        return int(host_fetch(jnp.argmax(self._posterior(x), axis=1))[0])

    def predict_probability(self, features) -> np.ndarray:
        x = jnp.asarray(np.asarray(features, np.float64).reshape(1, -1),
                        float_dtype())
        return np.asarray(self._posterior(x))[0]

    predictProbability = predict_probability

    @property
    def summary(self):
        return GaussianMixtureSummary(self)

    @property
    def has_summary(self):
        return True

    hasSummary = has_summary


class GaussianMixtureSummary:
    """MLlib ``GaussianMixtureSummary``: logLikelihood + iterations."""

    def __init__(self, model: GaussianMixtureModel):
        self._model = model

    @property
    def log_likelihood(self):
        return self._model.log_likelihood

    logLikelihood = log_likelihood

    @property
    def num_iter(self):
        return self._model.num_iters

    numIter = num_iter

    @property
    def k(self):
        return self._model.k


# ---------------------------------------------------------------------------
# BisectingKMeans (MLlib org.apache.spark.ml.clustering.BisectingKMeans)
# ---------------------------------------------------------------------------

@persistable
class BisectingKMeans(Estimator):
    """MLlib ``BisectingKMeans``: divisive hierarchical clustering — start
    from one cluster, repeatedly bisect (larger clusters first, MLlib's
    priority order) with a 2-means run until there are ``k`` leaves.

    TPU-first: every bisection reuses the jitted masked 2-means program
    (``_fit_cached``) on the FULL row set with a per-cluster weight vector —
    subsetting by weights instead of gathers keeps one static shape for all
    splits, so the 2-means program compiles once and every split is a pure
    device dispatch. The split loop itself is host-side (≤ k−1 steps over a
    data-dependent tree — not a device hot loop). MLlib dependency surface:
    `/root/reference/pom.xml:29-32`.
    """

    _persist_attrs = ('k', 'max_iter', 'tol', 'seed',
                      'min_divisible_cluster_size', 'features_col',
                      'prediction_col')

    def __init__(self, k: int = 4, max_iter: int = 20, tol: float = 1e-4,
                 seed: int = 0, min_divisible_cluster_size: float = 1.0,
                 features_col: str = "features",
                 prediction_col: str = "prediction"):
        if k < 1:
            raise ValueError("k must be >= 1")
        self.k = int(k)
        self.max_iter = int(max_iter)
        self.tol = float(tol)
        self.seed = int(seed)
        self.min_divisible_cluster_size = float(min_divisible_cluster_size)
        self.features_col = features_col
        self.prediction_col = prediction_col

    def set_k(self, v):
        if v < 1:
            raise ValueError("k must be >= 1")
        self.k = int(v)
        return self

    setK = set_k

    def set_max_iter(self, v):
        self.max_iter = int(v)
        return self

    setMaxIter = set_max_iter

    def set_seed(self, v):
        self.seed = int(v)
        return self

    setSeed = set_seed

    def set_min_divisible_cluster_size(self, v):
        self.min_divisible_cluster_size = float(v)
        return self

    setMinDivisibleClusterSize = set_min_divisible_cluster_size

    def set_features_col(self, v):
        self.features_col = v
        return self

    setFeaturesCol = set_features_col

    def fit(self, frame: Frame, mesh=None) -> "BisectingKMeansModel":
        dt = np.dtype(float_dtype())
        X = np.asarray(frame._column_values(self.features_col), dt)
        if X.ndim == 1:
            X = X[:, None]
        w = np.asarray(frame.mask, dt)
        # masked slots may hold NaN (dropna/filter keep values in place);
        # zero them so 0-weighted statistics stay finite (0·NaN = NaN)
        X = np.where(w[:, None] > 0, X, 0.0)
        n_valid = int(w.sum())
        if n_valid < self.k:
            raise ValueError(f"k={self.k} exceeds the {n_valid} valid rows")
        rng = np.random.default_rng(self.seed)

        mesh = normalize_mesh(mesh)
        Xd, _ = _pad_and_shard(X, w, mesh, dt)
        if mesh is not None and Xd.shape[0] != X.shape[0]:
            # keep the host-side copies in the padded shape too, so the
            # per-split weight vectors built below line up with Xd
            pad_rows = Xd.shape[0] - X.shape[0]
            X = np.concatenate([X, np.zeros((pad_rows, X.shape[1]), dt)])
            w = np.concatenate([w, np.zeros((pad_rows,), dt)])
        two_means = _fit_cached(mesh, 2, self.max_iter, self.tol)

        # tree arrays: center per node, children (−1 = leaf)
        centers = [np.asarray((w @ X) / max(w.sum(), 1e-12))]
        left, right = [-1], [-1]
        assign = np.zeros(X.shape[0], np.int64)       # row → node id
        leaf_sizes = {0: n_valid}
        min_size = self.min_divisible_cluster_size
        if min_size <= 1.0:
            min_size = min_size * n_valid if min_size < 1.0 else 1.0
        undivisible: set[int] = set()

        while len(leaf_sizes) < self.k:
            divisible = [(sz, nid) for nid, sz in leaf_sizes.items()
                         if nid not in undivisible and sz >= max(min_size, 2)]
            if not divisible:
                break
            _, nid = max(divisible)                    # largest first
            sel = (assign == nid) & (w > 0)
            wc = np.where(sel, w, 0.0).astype(dt)
            try:
                c0 = _kmeans_pp_init(X, wc, 2, rng)
            except ValueError:
                undivisible.add(nid)
                continue
            if mesh is not None:
                wd = jax.device_put(wc, NamedSharding(mesh, P(DATA_AXIS)))
            else:
                wd = jnp.asarray(wc)
            c, _, _, counts = jax.block_until_ready(
                two_means(Xd, wd, jnp.asarray(c0)))
            counts = np.asarray(counts)
            if counts.min() < 1:                       # degenerate split
                undivisible.add(nid)
                continue
            c = np.asarray(c)
            # children assignment for this cluster's rows
            d2 = ((X[sel][:, None, :] - c[None, :, :]) ** 2).sum(-1)
            child = np.argmin(d2, axis=1)
            lid, rid = len(centers), len(centers) + 1
            centers.extend([c[0], c[1]])
            left.extend([-1, -1])
            right.extend([-1, -1])
            left[nid], right[nid] = lid, rid
            assign[np.flatnonzero(sel)] = np.where(child == 0, lid, rid)
            del leaf_sizes[nid]
            leaf_sizes[lid] = int((child == 0).sum())
            leaf_sizes[rid] = int((child == 1).sum())

        model = BisectingKMeansModel(
            np.stack(centers), np.asarray(left, np.int64),
            np.asarray(right, np.int64), self.features_col,
            self.prediction_col)
        # training cost: SSE of valid rows to their leaf center
        leaf_center = np.stack(centers)[assign]
        model.training_cost = float(
            np.sum(((X - leaf_center) ** 2).sum(-1) * w))
        model.cluster_sizes = [leaf_sizes[nid]
                               for nid in sorted(leaf_sizes)]
        return model


@persistable
class BisectingKMeansModel(Model):
    """Binary cluster tree: prediction walks root→leaf picking the nearer
    child center at each internal node (MLlib's traversal), vectorized —
    one gather + distance comparison per tree level."""

    _persist_attrs = ('node_centers', 'left', 'right', 'features_col',
                      'prediction_col', 'training_cost', 'cluster_sizes')

    def __init__(self, node_centers, left, right, features_col="features",
                 prediction_col="prediction", training_cost=float("nan"),
                 cluster_sizes=None):
        self.node_centers = np.asarray(node_centers)
        self.left = np.asarray(left, np.int64)
        self.right = np.asarray(right, np.int64)
        self.features_col = features_col
        self.prediction_col = prediction_col
        self.training_cost = training_cost
        self.cluster_sizes = list(cluster_sizes or [])
        self.num_iters = 0          # tree build has no single iteration count
        self._post_load()

    def _post_load(self):
        """Rebuild the leaf index (derived state) after load_stage."""
        self.left = np.asarray(self.left, np.int64)
        self.right = np.asarray(self.right, np.int64)
        self.node_centers = np.asarray(self.node_centers)
        if not hasattr(self, "num_iters"):
            self.num_iters = 0
        # leaf ids in stable order → cluster index 0..k−1
        self._leaves = np.flatnonzero(self.left < 0)
        self._leaf_index = np.full(len(self.left), -1, np.int64)
        self._leaf_index[self._leaves] = np.arange(len(self._leaves))
        # actual tree depth (descent steps needed), computed once from the
        # static child arrays — the predict loop runs exactly this many
        # rounds, not k−1
        depth = np.zeros(len(self.left), np.int64)
        for nid in range(len(self.left) - 1, -1, -1):   # children have
            if self.left[nid] >= 0:                     # larger ids
                depth[nid] = 1 + max(depth[self.left[nid]],
                                     depth[self.right[nid]])
        self._depth = int(depth[0]) if len(depth) else 0

    @property
    def k(self):
        return len(self._leaves)

    def cluster_centers(self):
        return [self.node_centers[i] for i in self._leaves]

    clusterCenters = cluster_centers

    def _predict_nodes(self, X):
        """(n,) leaf node id per row — root→leaf descent, ≤ depth steps."""
        C = jnp.asarray(self.node_centers, X.dtype)
        L = jnp.asarray(self.left)
        R = jnp.asarray(self.right)
        node = jnp.zeros(X.shape[0], jnp.int64)
        for _ in range(self._depth):
            l, r = L[node], R[node]
            is_leaf = l < 0
            dl = jnp.sum((X - C[jnp.maximum(l, 0)]) ** 2, axis=1)
            dr = jnp.sum((X - C[jnp.maximum(r, 0)]) ** 2, axis=1)
            nxt = jnp.where(dl <= dr, l, r)
            node = jnp.where(is_leaf, node, nxt)
        return node

    def transform(self, frame: Frame) -> Frame:
        X = jnp.asarray(frame._column_values(self.features_col),
                        float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        nodes = np.asarray(self._predict_nodes(X))
        pred = self._leaf_index[nodes].astype(np.dtype(float_dtype()))
        return frame.with_column(self.prediction_col, jnp.asarray(pred))

    def predict(self, features) -> int:
        x = jnp.asarray(np.asarray(features, np.float64).reshape(1, -1),
                        float_dtype())
        return int(self._leaf_index[int(np.asarray(self._predict_nodes(x))[0])])

    def compute_cost(self, frame: Frame) -> float:
        X = jnp.asarray(frame._column_values(self.features_col),
                        float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        w = frame.mask.astype(X.dtype)
        nodes = self._predict_nodes(X)
        C = jnp.asarray(self.node_centers, X.dtype)
        return float(host_fetch(jnp.sum(jnp.sum((X - C[nodes]) ** 2,
                                                axis=1) * w)))

    computeCost = compute_cost

    @property
    def summary(self):
        return KMeansSummary(self)

    @property
    def has_summary(self):
        return True

    hasSummary = has_summary


@persistable
class PowerIterationClustering(Estimator):
    """MLlib ``PowerIterationClustering`` (spark.ml 2.4,
    ``org.apache.spark.ml.clustering.PowerIterationClustering`` — part of
    the mllib dependency surface, `/root/reference/pom.xml:29-32`): cluster
    the nodes of a weighted similarity graph by power-iterating the
    degree-normalized affinity matrix to a 1-D pseudo-eigenvector
    embedding, then running k-means on the embedding (Lin & Cohen, the
    algorithm MLlib cites).

    TPU-first design: the affinity matrix is built DENSE ``(n, n)`` in HBM
    (PIC graphs are node-count-bounded — the embedding itself is (n,); a
    dense W turns every power step into one MXU matvec instead of mllib's
    per-edge aggregateMessages shuffle). The whole iteration runs inside
    one jit as a ``lax.scan`` carrying the embedding; under a mesh the
    rows of W are sharded and each step is ``local matvec →
    all_gather over ICI`` inside ``shard_map`` — the GraphX
    aggregateMessages/shuffle replacement. The final 1-D k-means reuses
    the mesh-aware :class:`KMeans`.

    API parity: ``assignClusters(dataset) -> Frame(id, cluster)`` with
    ``src``/``dst``/``weight`` columns; ``initMode`` ``"random"`` |
    ``"degree"``; ids are arbitrary integers (mapped to dense indices
    internally, reported back as the original ids, ascending).
    """

    _persist_attrs = ('k', 'max_iter', 'init_mode', 'src_col', 'dst_col',
                      'weight_col', 'seed')

    def __init__(self, k: int = 2, max_iter: int = 20,
                 init_mode: str = "random", src_col: str = "src",
                 dst_col: str = "dst", weight_col: str = "weight",
                 seed: int = 0):
        if k < 2:
            raise ValueError("k must be >= 2")
        if init_mode not in ("random", "degree"):
            raise ValueError(f"init_mode must be random or degree, "
                             f"got {init_mode!r}")
        self.k = int(k)
        self.max_iter = int(max_iter)
        self.init_mode = init_mode
        self.src_col = src_col
        self.dst_col = dst_col
        self.weight_col = weight_col
        self.seed = int(seed)

    def set_k(self, v):
        if v < 2:
            raise ValueError("k must be >= 2")
        self.k = int(v)
        return self

    setK = set_k

    def set_max_iter(self, v):
        self.max_iter = int(v)
        return self

    setMaxIter = set_max_iter

    def set_init_mode(self, v):
        if v not in ("random", "degree"):
            raise ValueError(f"init_mode must be random or degree, got {v!r}")
        self.init_mode = v
        return self

    setInitMode = set_init_mode

    def set_src_col(self, v):
        self.src_col = v
        return self

    setSrcCol = set_src_col

    def set_dst_col(self, v):
        self.dst_col = v
        return self

    setDstCol = set_dst_col

    def set_weight_col(self, v):
        self.weight_col = v
        return self

    setWeightCol = set_weight_col

    def set_seed(self, v):
        self.seed = int(v)
        return self

    setSeed = set_seed

    def assign_clusters(self, frame: Frame, mesh=None) -> Frame:
        dt = float_dtype()
        d = frame.to_pydict()
        src = np.asarray(d[self.src_col], np.int64)
        dst = np.asarray(d[self.dst_col], np.int64)
        if self.weight_col in frame.columns:
            w = np.asarray(d[self.weight_col], np.float64)
        else:
            w = np.ones(len(src), np.float64)
        if np.any(w < 0):
            raise ValueError("similarity weights must be nonnegative")
        ids = np.unique(np.concatenate([src, dst]))
        n = len(ids)
        if n < self.k:
            raise ValueError(f"k={self.k} exceeds node count {n}")
        si = np.searchsorted(ids, src)
        di = np.searchsorted(ids, dst)

        mesh = normalize_mesh(mesh)
        ndev = 1 if mesh is None else mesh.devices.size
        n_pad = n + ((-n) % ndev)

        # Dense symmetric affinity; mllib sums duplicate/bidirectional
        # entries the same way (aggregateMessages add). Self-loops add
        # once — the reverse scatter must not hit the diagonal again.
        w_dev = jnp.asarray(w, dt)
        W = jnp.zeros((n_pad, n_pad), dt)
        W = W.at[si, di].add(w_dev)
        W = W.at[di, si].add(jnp.where(jnp.asarray(si == di), 0.0, w_dev))

        deg = jnp.sum(W, axis=1)                          # (n_pad,)
        inv_deg = jnp.where(deg > 0, 1.0 / jnp.where(deg > 0, deg, 1.0), 0.0)
        vol = jnp.sum(deg)
        if self.init_mode == "degree":
            v0 = deg / jnp.where(vol > 0, vol, 1.0)
        else:
            key = jax.random.PRNGKey(self.seed)
            u = jax.random.uniform(key, (n_pad,), dt)
            u = jnp.where(jnp.arange(n_pad) < n, u, 0.0)
            v0 = u / jnp.maximum(jnp.sum(jnp.abs(u)), 1e-30)

        max_iter = self.max_iter

        if mesh is None:
            @jax.jit
            def power(Wm, v):
                def body(vc, _):
                    nv = inv_deg * (Wm @ vc)
                    nv = nv / jnp.maximum(jnp.sum(jnp.abs(nv)), 1e-30)
                    return nv, None
                v_out, _ = jax.lax.scan(body, v, None, length=max_iter)
                return v_out

            v = power(W, v0)
        else:
            # Row-sharded matvec: local rows → all_gather over ICI each
            # step; the scan (and therefore the whole loop) stays on
            # device inside the manual region.
            inv_deg_h = inv_deg

            @jax.jit
            @functools.partial(
                shard_map, mesh=mesh,
                in_specs=(P(DATA_AXIS), P(), P(DATA_AXIS)), out_specs=P(),
                check_vma=False)
            def power(Ws, v, inv_deg_s):
                def body(vc, _):
                    local = inv_deg_s * (Ws @ vc)          # (n_pad/ndev,)
                    nv = jax.lax.all_gather(local, DATA_AXIS, tiled=True)
                    nv = nv / jnp.maximum(jnp.sum(jnp.abs(nv)), 1e-30)
                    return nv, None
                v_out, _ = jax.lax.scan(body, v, None, length=max_iter)
                return v_out

            v = power(W, v0, inv_deg_h)

        emb = v[:n]
        km = KMeans(k=self.k, max_iter=30, seed=self.seed,
                    init_mode="k-means++", features_col="features",
                    prediction_col="cluster")
        emb_frame = Frame({"features": jnp.reshape(emb, (n, 1))})
        model = km.fit(emb_frame, mesh=mesh)
        out = model.transform(emb_frame)
        cluster = np.asarray(out._column_values("cluster"), np.int64)
        return Frame({"id": ids, "cluster": cluster})

    assignClusters = assign_clusters
