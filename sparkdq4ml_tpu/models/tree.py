"""Tree ensembles: DecisionTree / RandomForest / GBT (classifier+regressor)
— the MLlib ``org.apache.spark.ml`` tree family (shipped by the reference's
mllib dependency, pom.xml:29-32; the reference app itself fits only
LinearRegression, `DataQuality4MachineLearningApp.java:120-126`).

TPU-first design — this is NOT a port of MLlib's per-partition
``findBestSplits`` RPC machinery:

* **A fit stays on the device.** ``fit(frame)`` hands the compiled
  programs the frame's device columns and mask; the host reads the label
  statistics and one finite-features flag before the fit and the tree
  arrays after it (all through ``host.reads`` / ``host.read_bytes``), and
  nothing n-sized crosses in either direction. Every per-row array lives
  with the rows on the minor (lane) axis — bins ``(d, n)`` int8, statistics
  ``(s, n)``, node ids ``(n,)`` — so no ``(n, k)`` operand with a narrow
  ``k`` is ever tiled to 128 lanes.
* **Thresholds are order statistics.** Threshold ``k`` of a feature is the
  value at rank ``ceil(k * n_valid / max_bins)`` of its valid rows
  (duplicates merged, +inf padded): a data value, so the device's float32
  and a float64 reference agree to the last bit. It is selected, not
  sorted for: ranks are found by counting over the floats' integer image,
  a pass over the table a bit (:func:`device_edges`; a sort a feature
  where ``max_bins`` makes the counting dearer). Bins are
  ``sum_k [x > threshold_k]``, compared on the same image.
* **Histogram trees, level-wise.** A tree grows breadth-first; a level's
  per-(node, feature, bin) sufficient statistics are ONE contraction
  ``onehot(bins)ᵀ · (onehot(node) * statistics)`` — on a TPU a Pallas
  kernel on the MXU that builds both one-hot operands in VMEM from the
  int8 bins and never stores them (:func:`_mxu_histogram`); elsewhere a
  ``segment_sum`` a feature. Below the root the contraction takes one
  child of every split, the one of the smaller weight, and its sibling is
  the parent's histogram less it (:func:`_sibling_histograms`). Split
  scoring is a cumulative-sum scan over bins; leaf totals come from the
  last split level's left and right sums.
* **Static shapes, no per-row gather.** The tree is a dense heap array of
  2^(depth+1)−1 node slots; a level's descent reads, for each of its
  nodes, that node's feature row of the feature-major table (a dynamic
  slice of a contiguous row) and selects — the same chain for training
  (bins against the split's bin) and for scoring (values against the
  threshold). Leaf payloads are looked up by a chain of selects.
* **A forest is a leading axis.** RandomForest builds T trees in one
  program over per-tree Poisson bootstrap weights (drawn on the device) and
  per-node random feature masks. GBT runs one compiled round a boosting
  round — gradients, the tree, Newton leaves and ``F += step * leaf`` —
  with nothing read between rounds.
* **Masked rows never vote**: the row weight folds the frame's validity
  mask, the same rule as every other estimator here.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config import float_dtype
from ..frame import Frame
from ..parallel.mesh import (DATA_AXIS, normalize_mesh, replicated_sharding,
                             serialize_collectives, shard_map)
from ..utils import observability as _obs
from .base import (Estimator, Model, host_fetch, label_stats, persistable,
                   read_label_stats)

_NEG = -1e30


# ---------------------------------------------------------------------------
# thresholds and bins (the MLlib findSplits analogue)
# ---------------------------------------------------------------------------

def threshold_ranks(n_valid, max_bins, xp=np):
    """0-based positions, in a feature's sorted valid values, of its
    ``max_bins - 1`` thresholds: threshold ``k`` (1-based) sits at rank
    ``ceil(k * n_valid / max_bins)``. Written ``k*q + ceil(k*r / B)`` with
    ``n_valid = q*B + r`` so that int32 holds it for any row count."""
    k = xp.arange(1, max_bins, dtype=xp.int32)
    q, r = n_valid // max_bins, n_valid % max_bins
    return k * q + (k * r + max_bins - 1) // max_bins - 1


def bin_features(X: np.ndarray, mask: np.ndarray, max_bins: int):
    """The plain host version of :func:`device_edges` / :func:`device_bins`,
    which the tests hold the device one to.

    Returns (edges (d, max_bins-1) float64 — ascending, +inf padded on the
    right; binned (n, d) int32 in [0, max_bins)). Bin b holds values in
    (edges[b-1], edges[b]]; a split "at bin b" sends bins ≤ b left with
    threshold edges[b].
    """
    n, d = X.shape
    edges = np.full((d, max_bins - 1), np.inf, np.float64)
    valid = X[mask] if mask is not None else X
    if len(valid):
        ranks = threshold_ranks(len(valid), max_bins)
        for j in range(d):
            uniq = np.unique(np.sort(valid[:, j])[ranks])
            edges[j, :len(uniq)] = uniq
    binned = np.empty((n, d), np.int32)
    for j in range(d):
        binned[:, j] = np.sum(X[:, j, None] > edges[j][None, :], axis=1)
    return edges, binned


#: The most compares a value at which :func:`device_edges` selects its
#: thresholds by counting — ``max_bins - 1`` a pass, a pass a bit of the
#: column's dtype; past it it sorts. Read on one v5e at 1.1e7 x 28 float32
#: with 90.7 % of the rows voting (chip run, PR 36): the selection takes
#: 245.9 / 490.2 / 1,031.0 / 2,005.5 ms at ``max_bins`` 32 / 64 / 128 / 256,
#: 0.247 ms a compare, and the sort a feature 800.6 ms at 32 and at 256
#: alike: they cross at 3,240, ``max_bins`` 102 for float32.
EDGES_SELECT_MAX_COMPARES = 3200


def edges_lowering(max_bins, dtype):
    """``(how, passes)`` — how :func:`device_edges` finds its order
    statistics, from ``max_bins`` and the column's dtype, never from a conf
    key: ``("select", bits)``, a pass over the table a bit, where that
    costs less than a sort a feature
    (:data:`EDGES_SELECT_MAX_COMPARES`); else ``("sort", 0)``."""
    bits = jnp.finfo(dtype).bits
    if (max_bins - 1) * bits <= EDGES_SELECT_MAX_COMPARES:
        return "select", bits
    return "sort", 0


def _rank_keys(x, valid=None):
    """The order-preserving unsigned image of the floats ``x``: ``a < b``
    as floats exactly where ``key(a) < key(b)`` as integers, ``-0.0`` and
    ``0.0`` one key, and the largest key of all for every row ``valid``
    (n,) drops. All of it on the bits: a float compare flushes denormals
    (on a TPU and on XLA's CPU alike), and a masked slot may hold a NaN."""
    bits = jnp.finfo(x.dtype).bits
    uint = jnp.dtype(f"uint{bits}")
    top = uint.type(1 << (bits - 1))
    raw = jax.lax.bitcast_convert_type(x, uint)
    raw = jnp.where(raw == top, uint.type(0), raw)           # -0.0 -> 0.0
    key = jnp.where(raw >= top, ~raw, raw | top)
    return key if valid is None else jnp.where(valid, key, ~uint.type(0))


def _unrank_keys(key, dtype):
    """The floats whose :func:`_rank_keys` image ``key`` is."""
    top = key.dtype.type(1 << (jnp.finfo(dtype).bits - 1))
    return jax.lax.bitcast_convert_type(
        jnp.where(key >= top, key ^ top, ~key), dtype)


def _select_ranks(keys, ranks):
    """(d, r) the key at each 0-based position ``ranks`` (r,) of every
    sorted row of ``keys`` (d, n), without sorting: the largest ``v`` with
    at most ``rank`` keys under it, found bit by bit from the top. A pass
    counts, for every feature and rank at once, the keys under the
    candidate — ``r`` reductions over the one read of ``keys``."""
    uint = keys.dtype
    bits = uint.itemsize * 8

    def narrow(i, found):
        bit = jnp.left_shift(uint.type(1), (bits - 1 - i).astype(uint))
        cand = found | bit                                   # (d, r)
        under = jnp.stack(
            [jnp.sum(keys < cand[:, k][:, None], axis=1, dtype=jnp.int32)
             for k in range(ranks.shape[0])], axis=1)
        return jnp.where(under <= ranks[None, :], cand, found)

    return jax.lax.fori_loop(
        0, bits, narrow, jnp.zeros((keys.shape[0], ranks.shape[0]), uint))


def device_edges(Xt, valid, max_bins):
    """(d, max_bins-1) thresholds of the feature-major ``Xt`` (d, n) over
    the rows ``valid`` keeps: the values at :func:`threshold_ranks` of
    every feature's sorted valid values, duplicates merged to the left,
    +inf on the right (everywhere, where no row votes). The values are
    found on the floats' integer image (:func:`_rank_keys`), by
    :func:`edges_lowering`: an exact selection by counting
    (:func:`_select_ranks`), or a sort a feature (one at a time, so the
    scratch is one column's)."""
    with _obs.scope("tree.edges"):
        n_valid = jnp.sum(valid, dtype=jnp.int32)
        ranks = jnp.maximum(threshold_ranks(n_valid, max_bins, jnp), 0)
        if edges_lowering(max_bins, Xt.dtype)[0] == "select":
            picked = _select_ranks(_rank_keys(Xt, valid), ranks)
        else:
            picked = jax.lax.map(
                lambda col: jnp.sort(_rank_keys(col, valid))[ranks], Xt)
        # a rank past the last voting row reads a dropped row's key: +inf
        inf = _rank_keys(jnp.asarray(jnp.inf, Xt.dtype))
        picked = jnp.minimum(picked, inf)                    # (d, B-1)
        first = jnp.concatenate(
            [jnp.ones_like(picked[:, :1], bool),
             picked[:, 1:] != picked[:, :-1]], axis=1)
        slot = jnp.cumsum(first, axis=1) - 1                 # (d, B-1)
        # threshold k lands in slot[k]; a slot nobody lands in stays +inf
        at = slot[:, :, None] == jnp.arange(max_bins - 1)[None, None, :]
        return _unrank_keys(
            jnp.min(jnp.where(at & first[:, :, None], picked[:, :, None],
                              inf), axis=1), Xt.dtype)


def device_bins(Xt, edges, max_bins):
    """(d, n) bins ``sum_k [x > edges_k]`` of the feature-major ``Xt``, in
    the narrowest integer that holds ``max_bins``; compared on the integer
    image, so a denormal is not zero here either. (A NaN in a masked slot
    lands in the first or the last bin, by its sign.)"""
    with _obs.scope("tree.bin"):
        keys, cuts = _rank_keys(Xt), _rank_keys(edges)
        acc = jnp.zeros(Xt.shape, jnp.int32)
        for k in range(max_bins - 1):
            acc = acc + (keys > cuts[:, k][:, None])
        return acc.astype(jnp.int8 if max_bins <= 128 else jnp.int32)


# ---------------------------------------------------------------------------
# a level's histograms
# ---------------------------------------------------------------------------

#: Rows a grid step of the MXU histogram takes. The one-hot of a block is
#: (d * bins, HIST_BLOCK) bfloat16 in VMEM: 3.7 MB at 28 x 32 x 2048.
HIST_BLOCK = 2048
#: The widest one-hot (features x padded bins) the kernel holds in VMEM;
#: past it the scatter lowering runs.
HIST_MAX_ONEHOT = 4096


def _round_up(x, m):
    return -(-x // m) * m


def hist_lowering(d, max_bins, sharded=None):
    """Which lowering :func:`_level_histogram` takes — from the backend and
    the shapes, never from a conf key: ``"mxu"`` (the one-hot contraction)
    on a TPU, on one device, where the one-hot of a block fits VMEM;
    ``"scatter"`` (``segment_sum``) on every other backend, inside
    ``shard_map`` and for one-hots too wide — there a scatter is the
    fastest thing the backend has (the CPU of the tests) or the only one
    that traces."""
    if (jax.default_backend() == "tpu" and sharded is None
            and d * _round_up(max_bins, 16) <= HIST_MAX_ONEHOT):
        return "mxu"
    return "scatter"


def row_layout(rows):
    """(padded rows, rows a grid step, partial accumulators) for ``rows``
    row slots of one device. Padded rows pad to themselves, so the fit
    pads once and the kernel finds its grid from the padded length."""
    if rows <= HIST_BLOCK:
        padded = _round_up(max(rows, 1), 128)
        return padded, padded, 1
    if rows < 64 * HIST_BLOCK:
        return _round_up(rows, HIST_BLOCK), HIST_BLOCK, 1
    return _round_up(rows, 8 * HIST_BLOCK), HIST_BLOCK, 8


def split_bf16x3(x):
    """``x`` (float32) as three float32 arrays, each exactly representable
    in bfloat16, that sum to ``x`` exactly: 8 + 8 + 8 bits of mantissa, by
    truncation (bit masks, which no compiler pass may round away)."""
    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)

    hi = top(x)
    mid = top(x - hi)
    return hi, mid, x - hi - mid


def _hist_kernel(bins_ref, pos_ref, parts_ref, out_ref, onehot_ref, w_ref,
                 *, d, bp, m, r):
    """One block of rows of one tree: ``out += onehot(bins) · Wᵀ`` with
    ``W[node * r + j] = parts[j]`` where the row sits in ``node`` and 0
    elsewhere. Both operands are made here, in VMEM, and are exact in
    bfloat16; the MXU accumulates in float32."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = bins_ref[...].astype(jnp.int32)                   # (d, rows)
    rows = bins.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bp, rows), 0)
    for f in range(d):
        onehot_ref[f * bp:(f + 1) * bp, :] = (
            bins[f:f + 1, :] == iota).astype(jnp.bfloat16)
    pos = pos_ref[0]                                         # (1, rows)
    parts = parts_ref[0]                                     # (r, rows)
    for node in range(m):
        w_ref[node * r:(node + 1) * r, :] = jnp.where(
            pos == node, parts, jnp.zeros_like(parts)).astype(jnp.bfloat16)
    out_ref[0, 0] += jax.lax.dot_general(
        onehot_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _mxu_histogram(binned, node_pos, targets, n_nodes, B, interpret=False):
    """The one-hot contraction: (T, d, n_nodes, B, s) float32 sums.

    ``binned`` (d, n) int8, ``node_pos`` (T, n) int32, ``targets`` (T, s, n)
    float32, n padded by :func:`row_layout`. Each statistic rides as three
    bfloat16-exact parts (:func:`split_bf16x3`), every product with a 0/1
    is exact, and the sums are float32 — over a block on the MXU, over the
    blocks of one of ``partials`` accumulators in the kernel's output, over
    the accumulators here. The grid is (tree, accumulator, block); the
    one-hot of the bins is rebuilt for every tree of a forest.

    The statistic matrix has ``n_nodes * r`` columns, ``r`` = 3 s rounded
    up to 16, and the MXU takes 128 of them a pass at the same cost
    however many are filled (read on one v5e at 1.1e7 rows x 28 x 32, four
    statistics: section 5 of ``PERF.md``): 1 to 8 nodes of four statistics
    are one pass, 16 are two. :func:`build_trees` asks for half a level's
    nodes, so a depth-5 tree's widest level is one pass.

    Pallas is imported here, by the one function that builds the kernel:
    at module level every importer of ``models`` would pay for it (about
    a second), tree fit or not."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d, n = binned.shape
    T, s, _ = targets.shape
    padded, block, partials = row_layout(n)
    if padded != n:
        raise ValueError(f"rows {n} are not padded by row_layout")
    bp = _round_up(B, 16)
    r = _round_up(3 * s, 16)
    steps = np.int32(n // (block * partials))
    zero = np.int32(0)          # block indices stay int32 under x64 too
    parts = jnp.concatenate(
        split_bf16x3(targets.astype(jnp.float32))
        + (jnp.zeros((T, r - 3 * s, n), jnp.float32),), axis=1)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, d=d, bp=bp, m=n_nodes, r=r),
        grid=(T, partials, int(steps)),
        in_specs=[
            pl.BlockSpec((d, block), lambda t, p, i: (zero, p * steps + i)),
            pl.BlockSpec((1, 1, block),
                         lambda t, p, i: (t, zero, p * steps + i)),
            pl.BlockSpec((1, r, block),
                         lambda t, p, i: (t, zero, p * steps + i)),
        ],
        out_specs=pl.BlockSpec((1, 1, d * bp, n_nodes * r),
                               lambda t, p, i: (t, p, zero, zero)),
        out_shape=jax.ShapeDtypeStruct((T, partials, d * bp, n_nodes * r),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((d * bp, block), jnp.bfloat16),
                        pltpu.VMEM((n_nodes * r, block), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="tree_level_histogram",
    )(binned, node_pos[:, None, :], parts)
    hist = jnp.sum(out, axis=1).reshape(T, d, bp, n_nodes, r)
    hist = hist[:, :, :B, :, :3 * s].reshape(T, d, B, n_nodes, 3, s)
    # the small parts first: float32 keeps more of them that way
    hist = hist[..., 2, :] + hist[..., 1, :] + hist[..., 0, :]
    return hist.transpose(0, 1, 3, 2, 4)


def _scatter_histogram(binned, node_pos, targets, n_nodes, B):
    """One ``segment_sum`` a feature and a tree: a scatter-add of every row
    into its (node, bin) slot."""
    idx = node_pos[:, None, :] * B + binned[None].astype(jnp.int32)
    oob = node_pos >= n_nodes                                # (T, n)
    t = jnp.where(oob[:, None, :], 0, targets).transpose(0, 2, 1)

    def per_feature(idx_f, oob_t, t_t):
        return jax.ops.segment_sum(t_t, jnp.where(oob_t, 0, idx_f),
                                   num_segments=n_nodes * B)

    per_tree = jax.vmap(per_feature, in_axes=(0, None, None))
    hist = jax.vmap(per_tree)(idx, oob, t)                   # (T, d, m*B, s)
    return hist.reshape(hist.shape[:2] + (n_nodes, B, -1))


def _level_histogram(binned, node_pos, targets, n_nodes, B, psum_axis=None):
    """(T, d, n_nodes, B, s) sufficient statistics for one level.

    ``binned`` (d, n) bins; ``node_pos`` (T, n) int32 position of the row's
    node within the level (``n_nodes`` = parked/leaf rows — excluded);
    ``targets`` (T, s, n) already mask/bootstrap-weighted stat rows.

    ``psum_axis``: mesh axis name when rows are sharded — the local
    histograms reduce with ONE ``lax.psum`` over ICI, the exact analogue
    of MLlib's per-level ``aggregateByKey`` shuffle (`findBestSplits`,
    implied by the reference's mllib dep pom.xml:29-32).
    """
    with _obs.scope("tree.hist"):
        if hist_lowering(binned.shape[0], B, psum_axis) == "mxu":
            hist = _mxu_histogram(binned, node_pos, targets, n_nodes, B)
            hist = hist.astype(targets.dtype)
        else:
            hist = _scatter_histogram(binned, node_pos, targets, n_nodes, B)
        if psum_axis is not None:
            hist = jax.lax.psum(hist, psum_axis)
        return hist


def _impurity_sse(agg):
    """Variance-scaled impurity (SSE) from [w, wy, wy²] stats."""
    w = jnp.maximum(agg[..., 0], 1e-12)
    return agg[..., 2] - agg[..., 1] ** 2 / w


def _impurity_gini(agg):
    """Weighted gini from per-class counts: w·(1 − Σp²) = w − Σc²/w."""
    w = jnp.maximum(jnp.sum(agg, axis=-1), 1e-12)
    return w - jnp.sum(agg * agg, axis=-1) / w


def _impurity_entropy(agg):
    w = jnp.maximum(jnp.sum(agg, axis=-1), 1e-12)
    p = agg / w[..., None]
    return -w * jnp.sum(jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-12)),
                                  0.0), axis=-1)


_IMPURITY = {"variance": _impurity_sse, "gini": _impurity_gini,
             "entropy": _impurity_entropy}


def _node_weight(agg, impurity):
    """The row weight in a node's statistics ``agg`` (..., s): statistic 0
    for ``"variance"``, the sum of the class counts for gini and entropy."""
    return agg[..., 0] if impurity == "variance" else jnp.sum(agg, axis=-1)


def _find_splits(hist, edges, impurity, min_instances, min_info_gain,
                 feat_mask=None):
    """Best (feature, threshold, gain) per node from level histograms.

    hist (d, m, B, s); edges (d, B-1). Candidate split b sends bins ≤ b
    left (threshold edges[:, b]). Returns per-node best feature (int32),
    bin, threshold, whether it splits, its gain (−inf when no valid
    split), and the node's total, left and right stat sums (m, s) at that
    split: what the children hold.
    """
    imp_fn = _IMPURITY[impurity]
    left = jnp.cumsum(hist, axis=2)[:, :, :-1, :]            # (d, m, B-1, s)
    total = jnp.sum(hist, axis=2)                            # (d, m, s)
    right = total[:, :, None, :] - left
    gain = imp_fn(total)[:, :, None] - imp_fn(left) - imp_fn(right)

    ok = jnp.logical_and(_node_weight(left, impurity) >= min_instances,
                         _node_weight(right, impurity) >= min_instances)
    # +inf-padded edges mark bins beyond the feature's true quantiles
    real = jnp.isfinite(edges)[:, None, :]                   # (d, 1, B-1)
    ok = jnp.logical_and(ok, real)
    gain = jnp.where(ok, gain, _NEG)
    if feat_mask is not None:                                # (m, d) per node
        gain = jnp.where(feat_mask.T[:, :, None], gain, _NEG)

    d, m, bm1 = gain.shape
    flat = gain.transpose(1, 0, 2).reshape(m, d * bm1)       # (m, d*(B-1))
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    best_feat = (best // bm1).astype(jnp.int32)
    best_bin = (best % bm1).astype(jnp.int32)
    thr = edges[best_feat, best_bin]
    split = best_gain > jnp.maximum(min_info_gain, 1e-12)
    nodes = jnp.arange(m)
    # every feature's bins partition the same rows; feature 0's histogram
    # summed over bins is the exact node total
    return (best_feat, best_bin, thr, split, best_gain, total[0],
            left[best_feat, nodes, best_bin],
            right[best_feat, nodes, best_bin])


class TreeArrays(NamedTuple):
    """Dense heap tree: node i's children are 2i+1 / 2i+2."""
    feature: jnp.ndarray       # (N,) int32
    threshold: jnp.ndarray     # (N,)
    is_leaf: jnp.ndarray       # (N,) bool
    value: jnp.ndarray         # (N, v) leaf payload (mean or class counts)
    gain: jnp.ndarray          # (N,) split gain (0 for leaves)


def _descend_level(table, heap, base, feat, cut, split):
    """One level of descent without a gather a row: for each of the level's
    nodes ``base + p`` the node's row ``table[feat[p]]`` of the
    feature-major ``table`` (d, n) — a dynamic slice of one contiguous row
    — against ``cut[p]``; rows of a node that splits go to a child (≤ left),
    every other row keeps its heap id. ``table`` holds bins (training: the
    cut is the split's bin) or raw values (scoring: the threshold)."""
    pos = heap - base
    out = heap
    for p in range(feat.shape[0]):
        row = jax.lax.dynamic_index_in_dim(table, feat[p], 0,
                                           keepdims=False)
        child = jnp.where(row <= cut[p].astype(row.dtype),
                          2 * heap + 1, 2 * heap + 2)
        out = jnp.where(jnp.logical_and(pos == p, split[p]), child, out)
    return out


def heap_lookup(node, table):
    """(k, n) payloads ``table[node]`` of heap ids ``node`` (n,) from a
    small ``table`` (N, k), by a chain of selects instead of a gather."""
    out = jnp.zeros((table.shape[1],) + node.shape, table.dtype)
    for i in range(table.shape[0]):
        out = jnp.where(node[None, :] == i, table[i][:, None], out)
    return out


def _sibling_histograms(binned, heap, base, targets, parents, split, left,
                        right, impurity, psum_axis=None):
    """(T, d, m, B, s) histograms of the ``m`` nodes of a level below the
    root from ONE pass over ``m / 2`` of them: of every node of the level
    above — ``parents`` (T, d, m/2, B, s) its histograms, ``split``,
    ``left``, ``right`` (T, m/2[, s]) what :func:`_find_splits` made of
    them — the child of the smaller weight (ties: the left) goes through
    :func:`_level_histogram`, at its parent's position, and its sibling is
    the parent's histogram less it. The MXU pays by the 128 statistic
    columns, so half the nodes is half the passes from 16 nodes on.

    No row is dropped and nothing is approximated: every row's statistics
    are in every histogram of its path, pushed through the contraction or
    through its parent's, in float32 as before. The derived child is the
    larger one, at least half its parent, so it carries one subtraction of
    numbers of its own size; weights (integers under 2^24) subtract
    exactly. A parent that did not split has no rows below it: its derived
    child is zero, not the parent."""
    T, d, half, B, s = parents.shape
    with _obs.scope("tree.hist"):
        right_small = (_node_weight(right, impurity)
                       < _node_weight(left, impurity))       # (T, m/2)
        pos = heap - base                       # children of p: 2p, 2p + 1
        parent = pos >> 1
        picked = jax.vmap(heap_lookup)(
            parent, right_small.astype(jnp.int32)[:, :, None])[:, 0]
        # every other row is parked (slot m/2), as rows above the level are
        node_pos = jnp.where((pos >= 0) & ((pos & 1) == picked), parent,
                             half)
    small = _level_histogram(binned, node_pos, targets, half, B, psum_axis)
    with _obs.scope("tree.hist"):
        other = jnp.where(split[:, None, :, None, None], parents - small,
                          0.0)
        right_small = right_small[:, None, :, None, None]
        kids = jnp.stack([jnp.where(right_small, other, small),
                          jnp.where(right_small, small, other)], axis=3)
        return kids.reshape(T, d, 2 * half, B, s)


def build_trees(binned, edges, targets, max_depth, max_bins, impurity,
                min_instances, min_info_gain, feat_masks=None,
                psum_axis=None):
    """Level-wise histogram build of T trees at once (jit-compatible).

    ``binned`` (d, n) bins; ``targets`` (T, s, n): weighted stat rows
    ([w, wy, wy²], [w, wg, wg², wh] or class one-hots) of each tree;
    ``feat_masks`` optional (T, N, d) per-heap-node feature masks. Returns
    (stacked :class:`TreeArrays` with a leading T, the rows' final heap ids
    (T, n)). ``max_depth`` histogram passes a tree, and a pass holds half
    of its level's nodes: the root, then one child of every node of the
    level above (:func:`_sibling_histograms`), ``2^(max_depth - 1)`` nodes
    a tree in all; the children's totals are the split's left and right
    sums.

    ``psum_axis``: set inside ``shard_map`` when rows are sharded over a
    mesh axis. Each device histograms its row shard and the level stats
    psum over ICI; the (replicated) split decisions are then identical on
    every device, so each device descends only its own rows and the final
    tree arrays come out replicated — zero host syncs per level.
    """
    d, n = binned.shape
    T, s, _ = targets.shape
    N = 2 ** (max_depth + 1) - 1
    dt = targets.dtype

    feature = jnp.zeros((T, N), jnp.int32)
    threshold = jnp.zeros((T, N), dt)
    is_leaf = jnp.ones((T, N), bool)
    value = jnp.zeros((T, N, s), dt)
    gains = jnp.zeros((T, N), dt)
    heap = jnp.zeros((T, n), jnp.int32)        # heap node id per row

    def put(arr, update, at):
        return jax.lax.dynamic_update_slice_in_dim(arr, update.astype(
            arr.dtype), at, axis=1)

    for depth in range(max_depth):
        m = 2 ** depth
        base = m - 1                            # first heap id of this level
        if depth == 0:                          # every row sits in the root
            hist = _level_histogram(binned, heap, targets, m, max_bins,
                                    psum_axis)
        else:
            hist = _sibling_histograms(binned, heap, base, targets, hist,
                                       split, left, right, impurity,
                                       psum_axis)
        with _obs.scope("tree.split"):
            fm = None
            if feat_masks is not None:
                fm = jax.lax.dynamic_slice_in_dim(feat_masks, base, m, 1)
            find = functools.partial(
                _find_splits, edges=edges, impurity=impurity,
                min_instances=min_instances, min_info_gain=min_info_gain)
            if fm is None:
                found = jax.vmap(lambda h: find(h))(hist)
            else:
                found = jax.vmap(lambda h, f: find(h, feat_mask=f))(hist, fm)
            feat, split_bin, thr, split, gain, total, left, right = found
            if depth == 0:
                value = put(value, total, 0)
            feature = put(feature, feat, base)
            threshold = put(threshold, thr, base)
            is_leaf = put(is_leaf, jnp.logical_not(split), base)
            gains = put(gains, jnp.where(split, gain, 0.0), base)
            # children 2i+1 / 2i+2 of the level's nodes, interleaved; a
            # node that does not split leaves its children empty
            kids = jnp.where(split[:, :, None, None],
                             jnp.stack([left, right], axis=2), 0.0)
            value = put(value, kids.reshape(T, 2 * m, s), 2 * base + 1)
        with _obs.scope("tree.descend"):
            def descend(h, f, c, sp):
                return _descend_level(binned, h, base, f, c, sp)

            # one tree: no vmap, which would turn a node's row slice into
            # a gather of rows
            heap = descend(heap[0], feat[0], split_bin[0], split[0])[None] \
                if T == 1 else jax.vmap(descend)(heap, feat, split_bin,
                                                 split)

    return TreeArrays(feature, threshold, is_leaf, value, gains), heap


def predict_heap(X, feature, threshold, is_leaf, max_depth):
    """Heap descent of raw feature rows ``X`` (n, d): (n,) leaf heap ids,
    level by level through :func:`_descend_level` on the feature-major
    table (on a TPU an (n, d) array with a narrow d is stored so: the
    transpose moves nothing)."""
    return _descend(X.T, feature, threshold, is_leaf, max_depth)


def _descend(Xt, feature, threshold, is_leaf, max_depth):
    with _obs.scope("tree.descend"):
        node = jnp.zeros((Xt.shape[1],), jnp.int32)
        for depth in range(max_depth):
            m = 2 ** depth
            level = slice(m - 1, 2 * m - 1)
            node = _descend_level(Xt, node, m - 1, feature[level],
                                  threshold[level],
                                  jnp.logical_not(is_leaf[level]))
        return node


def score_cuts(feature, threshold, is_leaf, d):
    """What scoring bins by, from a model's host arrays: ``edges`` (d, K)
    the distinct thresholds of the ensemble's splits a feature (ascending,
    +inf padded, K a multiple of 8 so that a refit seldom changes the
    program's shapes) and ``cut`` (T, N) the place of every node's
    threshold in its feature's row: ``x <= threshold`` exactly where the
    number of ``edges`` under ``x`` is ``<= cut``."""
    feature, threshold = np.asarray(feature), np.asarray(threshold)
    split = np.logical_not(np.asarray(is_leaf))
    per = [np.unique(threshold[split & (feature == f)]) for f in range(d)]
    edges = np.full((d, _round_up(max(map(len, per), default=1) or 1, 8)),
                    np.inf, threshold.dtype)
    cut = np.zeros(feature.shape, np.int32)
    for f, uniq in enumerate(per):
        edges[f, :len(uniq)] = uniq
        here = split & (feature == f)
        cut[here] = np.searchsorted(uniq, threshold[here])
    return edges, cut


def forest_apply(X, edges, feature, cut, is_leaf, tables, max_depth):
    """``sum_t tables[t, leaf_t(row)]`` as (k, n): every tree's payload
    (``tables`` (T, N, k)) at the leaf its descent of ``X`` (n, d) ends
    in, summed over the trees — one tree at a time, so the working set is
    a handful of n-row vectors whatever T is.

    The rows are binned once by the ensemble's own thresholds
    (:func:`score_cuts`) and every tree descends the int8 bins, as a fit
    does: a node's row of a float32 table is a strided slice that fills an
    eighth of a vector register, its row of the bins is not. A NaN takes
    the last bin and so goes right at every node, as ``NaN <= t`` does."""
    with _obs.scope("tree.score"):
        Xt = X.T
        last = edges.shape[1]
        bins = jnp.where(jnp.isnan(Xt), last,
                         device_bins(Xt, edges, last + 1))

        def one(acc, tree):
            f, c, l, table = tree
            node = _descend(bins, f, c, l, max_depth)
            return acc + heap_lookup(node, table), None

        acc = jnp.zeros((tables.shape[2], X.shape[0]), tables.dtype)
        return jax.lax.scan(one, acc, (feature, cut, is_leaf, tables))[0]


def feature_importances(feature, gain, d: int) -> np.ndarray:
    """Gain-summed importances over all trees/nodes, normalized (MLlib),
    from the model's host copies of its tree arrays."""
    imp = np.zeros((d,), np.float64)
    np.add.at(imp, np.asarray(feature).reshape(-1),
              np.maximum(np.asarray(gain, np.float64).reshape(-1), 0.0))
    total = imp.sum()
    return imp / total if total > 0 else imp


# ---------------------------------------------------------------------------
# the device entry: what every tree estimator's fit does before it grows
# ---------------------------------------------------------------------------

@jax.jit
def _validate(X, y, mask):
    """``base.label_stats`` and two flags — some valid label, some valid
    feature value is NaN or infinite — reduced on the device."""
    stats = label_stats(y, mask)
    with _obs.scope("fit.validate"):
        bad = jnp.stack([
            jnp.any(jnp.where(mask, ~jnp.isfinite(y), False)),
            jnp.any(jnp.where(mask[:, None], ~jnp.isfinite(X), False))])
    return stats, bad


@functools.lru_cache(maxsize=None)
def _bin_program(max_bins, rows):
    """Jitted ``(X, y, mask, held) -> (edges, bins, y, w, w_held)`` with
    every per-row output padded to ``rows``: thresholds from the rows that
    vote (valid and not ``held`` out), int8 bins feature-major, the label
    zeroed where the mask drops the row (0 * NaN = NaN otherwise), and the
    0/1 weights of the voting and of the held-out rows."""
    def run(X, y, mask, held):
        dt = X.dtype
        pad = rows - X.shape[0]
        votes = mask if held is None else mask & ~held
        Xt = X.T
        edges = device_edges(Xt, votes, max_bins)
        bins = jnp.pad(device_bins(Xt, edges, max_bins), ((0, 0), (0, pad)))
        w_held = None if held is None \
            else jnp.pad((mask & held).astype(dt), (0, pad))
        return (edges, bins, jnp.pad(jnp.where(mask, y, 0), (0, pad)),
                jnp.pad(votes.astype(dt), (0, pad)), w_held)

    return jax.jit(run)


class _Prepared(NamedTuple):
    """A fit's inputs on the device, rows padded (:func:`row_layout`) and,
    under a mesh, sharded over the data axis."""
    binned: jax.Array          # (d, rows) bins
    edges: jax.Array           # (d, max_bins - 1)
    y: jax.Array               # (rows,)
    w: jax.Array               # (rows,) 1.0 where the row votes
    w_held: object             # (rows,) the validation rows, or None
    slots: int                 # the frame's row slots
    features: int
    label_max: float
    mesh: object


def _shard_rows(mesh, x):
    """``x`` with its last (row) axis sharded over the mesh's data axis."""
    return jax.device_put(x, NamedSharding(
        mesh, P(*([None] * (x.ndim - 1) + [DATA_AXIS]))))


def _read(x, site: str) -> np.ndarray:
    """One counted blocking read of a small device array."""
    with _obs.host_reading(site) as rd:
        out = np.asarray(x)
        rd.done(out.nbytes)
    return out


class _TreeParams:
    """Shared builder surface for the MLlib tree params, and the device
    entry every tree estimator's fit goes through."""

    def set_max_depth(self, v):
        self.max_depth = int(v)
        return self

    setMaxDepth = set_max_depth

    def set_max_bins(self, v):
        self.max_bins = int(v)
        return self

    setMaxBins = set_max_bins

    def set_min_instances_per_node(self, v):
        self.min_instances_per_node = int(v)
        return self

    setMinInstancesPerNode = set_min_instances_per_node

    def set_min_info_gain(self, v):
        self.min_info_gain = float(v)
        return self

    setMinInfoGain = set_min_info_gain

    def set_features_col(self, v):
        self.features_col = v
        return self

    setFeaturesCol = set_features_col

    def set_label_col(self, v):
        self.label_col = v
        return self

    setLabelCol = set_label_col

    def set_prediction_col(self, v):
        self.prediction_col = v
        return self

    setPredictionCol = set_prediction_col

    def set_seed(self, v):
        self.seed = int(v)
        return self

    setSeed = set_seed

    def _prepare(self, frame, mesh, labels, held_col=None):
        """``fit.prepare``: the frame's columns (``fit.extract``), their
        validation on the device and the read of its few scalars
        (``fit.validate``), thresholds and bins (``fit.tree.bin``, which
        says how the thresholds were found — ``edges`` = ``select`` |
        ``sort``, ``passes`` — and waits for the program, so it times the
        selection's passes, or the sorts, and the binning).
        ``labels``: ``"real"`` | ``"classes"`` | ``"binary"``."""
        from ..utils.profiling import counters
        from .regression import _extract_xy

        name = type(self).__name__
        mesh = normalize_mesh(mesh)
        with _obs.span("fit.prepare", cat="fit") as prep:
            with _obs.span("fit.extract", cat="fit"):
                X, y, mask = _extract_xy(frame, self.features_col,
                                         self.label_col)
                held = None
                if held_col is not None:
                    held = jnp.asarray(frame._column_values(held_col)) > 0
            slots, d = int(X.shape[0]), int(X.shape[1])
            prep.set(rows=slots, features=d)
            with _obs.span("fit.validate", cat="fit") as val:
                stats, bad = _validate(X, y, mask)
                stats = read_label_stats(stats)
                bad = _read(bad, "fit.finite_flags")
                val.set(host_read_bytes=stats.nbytes + bad.nbytes)
                if stats.rows == 0:
                    raise ValueError(f"{name}: no valid rows")
                if bad[0]:
                    raise ValueError(f"{name}: label column has NaN/inf in "
                                     "valid rows")
                if bad[1]:
                    raise ValueError(f"{name}: feature matrix has NaN/inf "
                                     "in valid rows")
                integral = not stats.label_bad and stats.label_min >= 0
                if labels == "classes" and not integral:
                    raise ValueError(
                        "labels must be nonnegative integers 0..k-1")
                if labels == "binary" and not (integral
                                               and stats.label_max <= 1):
                    raise ValueError(
                        "GBTClassifier requires binary 0/1 labels")
            shards = 1 if mesh is None else int(mesh.devices.size)
            rows = shards * row_layout(-(-slots // shards))[0]
            how, passes = edges_lowering(self.max_bins, X.dtype)
            with _obs.span("fit.tree.bin", cat="fit", rows=slots,
                           features=d, bins=self.max_bins,
                           lowering="device", edges=how, passes=passes):
                counters.increment("tree.fit_device")
                counters.increment("tree.edges_select" if how == "select"
                                   else "tree.edges_sort")
                out = _bin_program(self.max_bins, rows)(X, y, mask, held)
                # the span ends with its program: a wait that brings
                # nothing to the host, so timed and not counted as a read
                with _obs.host_reading("tree.bin"):
                    edges, binned, y, w, w_held = jax.block_until_ready(out)
            if mesh is not None:
                binned, y, w = (_shard_rows(mesh, a) for a in (binned, y, w))
                edges = jax.device_put(edges, replicated_sharding(mesh))
                if w_held is not None:
                    w_held = _shard_rows(mesh, w_held)
        return _Prepared(binned, edges, y, w, w_held, slots, d,
                         stats.label_max, mesh)

    def _count_growth(self, prep, trees):
        from ..utils.profiling import counters

        levels = trees * self.max_depth
        counters.increment("tree.rounds", trees)
        counters.increment("tree.levels", levels)
        counters.increment("tree.hist_rows", levels * prep.slots)
        # a level below the root pushes one child of every node above it
        # through the histogram and takes the sibling by subtraction
        pushed = trees * (2 ** self.max_depth // 2)
        counters.increment("tree.hist_nodes", pushed)
        counters.increment("tree.hist_derived", max(pushed - trees, 0))
        return levels


def _n_subset_features(strategy, d, is_classification, n_trees=1):
    """Spark's featureSubsetStrategy table: 'auto' = all for a single tree,
    sqrt(d) for classification forests, d/3 for regression forests; also
    accepts 'n' (an integer count) and '0.x' (a fraction)."""
    if strategy == "all":
        return d
    if strategy == "auto":
        if n_trees <= 1:
            return d
        return max(1, int(np.sqrt(d))) if is_classification \
            else max(1, d // 3)
    if strategy == "sqrt":
        return max(1, int(np.sqrt(d)))
    if strategy == "onethird":
        return max(1, d // 3)
    if strategy == "log2":
        return max(1, int(np.log2(d)))
    try:
        if isinstance(strategy, str) and strategy.isdigit():
            return min(d, max(1, int(strategy)))  # Spark's 'n' count form
        frac = float(strategy)
        if not 0.0 < frac <= 1.0:
            raise ValueError
        return max(1, int(round(frac * d)))
    except (TypeError, ValueError):
        raise ValueError(f"unknown featureSubsetStrategy {strategy!r}") \
            from None


def _pack_trees(trees: TreeArrays):
    """Stacked tree arrays (T, N, ...) as ONE (T, N, 4 + s) array in the
    statistics' dtype — what a fit reads at its end (a feature index is
    exact in float32)."""
    dt = trees.value.dtype
    return jnp.concatenate(
        [jnp.stack([trees.feature.astype(dt), trees.threshold,
                    trees.is_leaf.astype(dt), trees.gain], axis=2),
         trees.value], axis=2)


def _unpack_trees(packed: np.ndarray) -> TreeArrays:
    return TreeArrays(packed[:, :, 0].astype(np.int32), packed[:, :, 1],
                      packed[:, :, 2] > 0, packed[:, :, 4:], packed[:, :, 3])


@functools.lru_cache(maxsize=None)
def _forest_targets(n_trees, n_classes, slots):
    """Jitted ``(y, w, key, rate) -> (T, s, rows)`` weighted stat rows, made
    before any sharding and drawn for the frame's ``slots`` (not the padded
    rows) so that a sharded fit draws what a single-device one does. Regression (``n_classes`` 0): [w, wy, wy²]; classification:
    per-class weighted one-hots. A forest's trees each take a
    Poisson(rate) bootstrap weight a row (Spark's sampling model)."""
    def run(y, w, key, rate):
        boot = w[None, :]
        if n_trees > 1:
            draws = jax.random.poisson(key, rate, (n_trees, slots))
            boot = boot * jnp.pad(draws.astype(w.dtype),
                                  ((0, 0), (0, w.shape[0] - slots)))
        if n_classes:
            # masked slots hold label 0 (``_bin_program``) at weight 0
            stats = (y.astype(jnp.int32)[None, :]
                     == jnp.arange(n_classes)[:, None]).astype(w.dtype)
        else:
            stats = jnp.stack([jnp.ones_like(y), y, y * y])
        return boot[:, None, :] * stats[None, :, :]

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _forest_builder(max_depth, max_bins, impurity, min_instances,
                    min_info_gain, with_masks, mesh=None):
    """Jitted builder of T trees, cached per (hyperparameters, mesh) so
    repeated fits (cross-validation grids) reuse the compiled XLA program
    instead of re-tracing (cf glm._fit_cached). Returns the packed trees.

    With a mesh: ``shard_map`` over the data axis — per-shard descent,
    psum'd level histograms, replicated tree outputs."""

    def grow(binned, edges, targets, fm=None, axis=None):
        return _pack_trees(build_trees(
            binned, edges, targets, max_depth, max_bins, impurity,
            min_instances, min_info_gain, fm, psum_axis=axis)[0])

    if mesh is None:
        return jax.jit(grow)

    specs = (P(None, DATA_AXIS), P(), P(None, None, DATA_AXIS))
    if with_masks:
        fn = shard_map(lambda b, e, t, fm: grow(b, e, t, fm, DATA_AXIS),
                       mesh=mesh, in_specs=specs + (P(),), out_specs=P())
    else:
        fn = shard_map(lambda b, e, t: grow(b, e, t, None, DATA_AXIS),
                       mesh=mesh, in_specs=specs, out_specs=P())
    return serialize_collectives(jax.jit(fn), mesh)


def _fit_forest(prep: _Prepared, *, n_trees, max_depth, max_bins, impurity,
                min_instances, min_info_gain, n_classes, subsample, n_feat,
                seed) -> TreeArrays:
    """Build n_trees trees in one program; the host reads the packed tree
    arrays and nothing else. Under ``prep.mesh`` rows shard over the data
    axis and each level's histogram psums over ICI (see
    :func:`build_trees`); padded rows carry zero weight and never vote."""
    dt = np.dtype(float_dtype())
    mesh = prep.mesh
    targets = _forest_targets(n_trees, n_classes, prep.slots)(
        prep.y, prep.w, jax.random.key(seed), np.asarray(subsample, dt))
    args = (prep.binned, prep.edges,
            targets if mesh is None else _shard_rows(mesh, targets))
    if n_feat < prep.features:
        # per-node random feature subsets: (T, N, d) booleans, host-drawn
        rng = np.random.default_rng(seed)
        scores = rng.random(size=(n_trees, 2 ** (max_depth + 1) - 1,
                                  prep.features))
        kth = np.partition(scores, n_feat - 1, axis=2)[:, :, n_feat - 1]
        fm = scores <= kth[:, :, None]
        args += (fm if mesh is None
                 else jax.device_put(fm, replicated_sharding(mesh)),)
    fn = _forest_builder(max_depth, max_bins, impurity, min_instances,
                         min_info_gain, n_feat < prep.features, mesh)
    return _unpack_trees(_read(fn(*args), "tree.result"))


# ---------------------------------------------------------------------------
# scoring: one compiled program a model kind
# ---------------------------------------------------------------------------

def _ratio(num, den):
    return num / jnp.maximum(den, 1e-12)


@functools.lru_cache(maxsize=None)
def _score_program(kind, max_depth):
    """Jitted ``(X, edges, feature, cut, is_leaf, value, f0, step) -> ...``
    for a model ``kind``: the trees' payload tables from their node
    statistics, :func:`forest_apply`, and the model's output columns.

    * ``"mean"`` — regression trees: the equal-weight average of the
      per-tree leaf means wy / w (MLlib; NOT pooled leaf stats, which
      would weight trees by bootstrap count);
    * ``"votes"`` — classification trees: (rawPrediction, probability) —
      for one tree the leaf's class counts and their shares, for a forest
      the summed per-tree shares and their mean, so that
      argmax(rawPrediction) == argmax(probability) always holds;
    * ``"boosted"`` — GBT: ``f0 + step * sum_t`` of the Newton leaves
      Σg / Σh.
    """
    def run(X, edges, feature, cut, is_leaf, value, f0, step):
        trees = value.shape[0]
        if kind == "mean":
            tables = _ratio(value[:, :, 1], value[:, :, 0])[:, :, None]
        elif kind == "boosted":
            tables = _ratio(value[:, :, 1], value[:, :, 3])[:, :, None]
        else:
            share = _ratio(value, jnp.sum(value, axis=2, keepdims=True))
            tables = jnp.concatenate([value, share], axis=2) \
                if trees == 1 else share
        out = forest_apply(X, edges, feature, cut, is_leaf, tables,
                           max_depth)
        if kind == "mean":
            return out[0] / trees
        if kind == "boosted":
            return f0 + step * out[0]
        k = value.shape[2]
        if trees == 1:
            return out[:k].T, out[k:].T
        return out.T, out.T / trees

    return jax.jit(run)


class _TreeModelBase(Model):
    """Shared prediction over a stacked (T, N) heap forest."""

    _kind = "mean"
    #: The (d, max_bins - 1) thresholds the fit chose among, as it left
    #: them on the device (+inf padded; not persisted: None on a loaded
    #: model).
    split_candidates = None

    def _score(self, X):
        """The model's ``_score_program`` on raw feature rows ``X``."""
        dt = float_dtype()
        X = jnp.asarray(X, dt)
        if X.ndim == 1:
            X = X[:, None]
        edges, cut = score_cuts(self.feature, self.threshold.astype(dt),
                                self.is_leaf, X.shape[1])
        return _score_program(self._kind, self.max_depth)(
            X, edges, self.feature, cut, self.is_leaf,
            self.value.astype(dt),
            np.asarray(getattr(self, "f0", 0.0), dt),
            np.asarray(getattr(self, "step_size", 1.0), dt))

    def _leaf_values(self, X):
        """(T, n, s) leaf payloads for every tree."""
        Xd = jnp.asarray(X, float_dtype())
        if Xd.ndim == 1:
            Xd = Xd[:, None]

        def per_tree(feature, threshold, is_leaf, value):
            node = predict_heap(Xd, feature, threshold, is_leaf,
                                self.max_depth)
            return heap_lookup(node, value).T

        return jax.vmap(per_tree)(jnp.asarray(self.feature),
                                  jnp.asarray(self.threshold),
                                  jnp.asarray(self.is_leaf),
                                  jnp.asarray(self.value))

    @property
    def feature_importances(self):
        return feature_importances(self.feature, self.gain,
                                   self.num_features)

    featureImportances = feature_importances

    @property
    def num_features(self):
        return int(self._num_features)

    numFeatures = num_features

    def _frame_X(self, frame):
        """The frame's feature column as it lies on the device."""
        return frame._column_values(
            self._params.get("features_col", "features"))

    @staticmethod
    def _point(features):
        return np.asarray(features, np.float64).reshape(1, -1)


class _ForestEstimator(Estimator, _TreeParams):
    """The fit the four tree and forest estimators share: a single tree is
    a forest of one, without bootstrap or feature subsets."""

    _n_trees = 1
    _subsample = 1.0
    _feature_subset = "all"

    def fit(self, frame: Frame, mesh=None):
        """One root span a fit, ``fit.prepare`` and ``fit.solve`` under it
        (``solve``: the targets and the tree program dispatched, and the
        read of the packed tree arrays)."""
        with _obs.fit_span(self._span, _bin_program, _forest_builder,
                           trees=self._n_trees, max_depth=self.max_depth):
            prep = self._prepare(frame, mesh, self._labels)
            classes = int(prep.label_max) + 1 \
                if self._labels == "classes" else 0
            with _obs.span("fit.solve", cat="solver",
                           rounds=self._n_trees,
                           histogram=hist_lowering(
                               prep.features, self.max_bins,
                               prep.mesh)) as sv:
                trees = _fit_forest(
                    prep, n_trees=self._n_trees, max_depth=self.max_depth,
                    max_bins=self.max_bins, impurity=self._impurity,
                    min_instances=self.min_instances_per_node,
                    min_info_gain=self.min_info_gain, n_classes=classes,
                    subsample=self._subsample,
                    n_feat=_n_subset_features(
                        self._feature_subset, prep.features,
                        self._labels == "classes", self._n_trees),
                    seed=self.seed)
                sv.set(levels=self._count_growth(prep, self._n_trees))
            model = self._make_model(trees, prep.features, classes)
            model.split_candidates = prep.edges
            return model


@persistable
class DecisionTreeRegressor(_ForestEstimator):
    """MLlib ``DecisionTreeRegressor`` (variance impurity)."""

    _persist_attrs = ('max_depth', 'max_bins', 'min_instances_per_node',
                      'min_info_gain', 'features_col', 'label_col',
                      'prediction_col', 'seed')
    _span = "fit.decision_tree_regressor"
    _labels = "real"
    _impurity = "variance"

    def __init__(self, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 features_col: str = "features", label_col: str = "label",
                 prediction_col: str = "prediction", seed: int = 0):
        self.max_depth = int(max_depth)
        self.max_bins = int(max_bins)
        self.min_instances_per_node = int(min_instances_per_node)
        self.min_info_gain = float(min_info_gain)
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col
        self.seed = int(seed)

    def _make_model(self, trees, d, classes=0):
        return DecisionTreeRegressionModel(
            trees.feature, trees.threshold, trees.is_leaf, trees.value,
            trees.gain, d, self.max_depth,
            {"features_col": self.features_col,
             "prediction_col": self.prediction_col})


@persistable
class DecisionTreeRegressionModel(_TreeModelBase):
    _persist_attrs = ('feature', 'threshold', 'is_leaf', 'value', 'gain',
                      '_num_features', 'max_depth', '_params')

    def __init__(self, feature, threshold, is_leaf, value, gain,
                 num_features, max_depth, params=None):
        self.feature = np.asarray(feature)
        self.threshold = np.asarray(threshold)
        self.is_leaf = np.asarray(is_leaf)
        self.value = np.asarray(value)
        self.gain = np.asarray(gain)
        self._num_features = int(num_features)
        self.max_depth = int(max_depth)
        self._params = dict(params or {})

    def _predict_array(self, X):
        return self._score(X)

    def transform(self, frame: Frame) -> Frame:
        with _obs.span("model.transform", cat="model",
                       model=type(self).__name__, rows=frame.num_slots):
            pred = self._score(self._frame_X(frame))
            return frame.with_column(
                self._params.get("prediction_col", "prediction"),
                pred.astype(float_dtype()))

    def predict(self, features) -> float:
        return float(host_fetch(self._score(self._point(features)))[0])


@persistable
class RandomForestRegressor(DecisionTreeRegressor):
    """MLlib ``RandomForestRegressor``: Poisson bootstrap + per-node random
    feature subsets, all trees built in one program."""

    _persist_attrs = DecisionTreeRegressor._persist_attrs + (
        'num_trees', 'subsampling_rate', 'feature_subset_strategy')
    _span = "fit.random_forest_regressor"

    def __init__(self, num_trees: int = 20, subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto", **kw):
        super().__init__(**kw)
        self.num_trees = int(num_trees)
        self.subsampling_rate = float(subsampling_rate)
        self.feature_subset_strategy = feature_subset_strategy

    def set_num_trees(self, v):
        self.num_trees = int(v)
        return self

    setNumTrees = set_num_trees

    def set_subsampling_rate(self, v):
        self.subsampling_rate = float(v)
        return self

    setSubsamplingRate = set_subsampling_rate

    def set_feature_subset_strategy(self, v):
        self.feature_subset_strategy = v
        return self

    setFeatureSubsetStrategy = set_feature_subset_strategy

    @property
    def _n_trees(self):
        return self.num_trees

    @property
    def _subsample(self):
        return self.subsampling_rate

    @property
    def _feature_subset(self):
        return self.feature_subset_strategy

    def _make_model(self, trees, d, classes=0):
        return RandomForestRegressionModel(
            trees.feature, trees.threshold, trees.is_leaf, trees.value,
            trees.gain, d, self.max_depth,
            {"features_col": self.features_col,
             "prediction_col": self.prediction_col})


@persistable
class RandomForestRegressionModel(DecisionTreeRegressionModel):
    @property
    def num_trees(self):
        return int(np.asarray(self.feature).shape[0])

    getNumTrees = num_trees


@persistable
class DecisionTreeClassifier(_ForestEstimator):
    """MLlib ``DecisionTreeClassifier`` (gini default / entropy)."""

    _persist_attrs = ('max_depth', 'max_bins', 'min_instances_per_node',
                      'min_info_gain', 'impurity', 'features_col',
                      'label_col', 'prediction_col', 'probability_col',
                      'raw_prediction_col', 'seed')
    _span = "fit.decision_tree_classifier"
    _labels = "classes"

    def __init__(self, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 impurity: str = "gini", features_col: str = "features",
                 label_col: str = "label", prediction_col: str = "prediction",
                 probability_col: str = "probability",
                 raw_prediction_col: str = "rawPrediction", seed: int = 0):
        if impurity not in ("gini", "entropy"):
            raise ValueError(f"impurity={impurity!r} (gini|entropy)")
        self.max_depth = int(max_depth)
        self.max_bins = int(max_bins)
        self.min_instances_per_node = int(min_instances_per_node)
        self.min_info_gain = float(min_info_gain)
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col
        self.seed = int(seed)
        self.impurity = impurity
        self.probability_col = probability_col
        self.raw_prediction_col = raw_prediction_col

    def set_impurity(self, v):
        if v not in ("gini", "entropy"):
            raise ValueError(f"impurity={v!r}")
        self.impurity = v
        return self

    setImpurity = set_impurity

    @property
    def _impurity(self):
        return self.impurity

    def _params_for_model(self):
        return {"features_col": self.features_col,
                "prediction_col": self.prediction_col,
                "probability_col": self.probability_col,
                "raw_prediction_col": self.raw_prediction_col}

    def _make_model(self, trees, d, classes=0):
        return DecisionTreeClassificationModel(
            trees.feature, trees.threshold, trees.is_leaf, trees.value,
            trees.gain, d, self.max_depth, classes,
            self._params_for_model())


@persistable
class DecisionTreeClassificationModel(_TreeModelBase):
    _persist_attrs = ('feature', 'threshold', 'is_leaf', 'value', 'gain',
                      '_num_features', 'max_depth', 'num_classes', '_params')
    _kind = "votes"

    def __init__(self, feature, threshold, is_leaf, value, gain,
                 num_features, max_depth, num_classes, params=None):
        self.feature = np.asarray(feature)
        self.threshold = np.asarray(threshold)
        self.is_leaf = np.asarray(is_leaf)
        self.value = np.asarray(value)
        self.gain = np.asarray(gain)
        self._num_features = int(num_features)
        self.max_depth = int(max_depth)
        self.num_classes = int(num_classes)
        self._params = dict(params or {})

    numClasses = property(lambda self: self.num_classes)

    def _proba(self, X):
        return self._score(X)[1]

    def transform(self, frame: Frame) -> Frame:
        p = self._params
        with _obs.span("model.transform", cat="model",
                       model=type(self).__name__, rows=frame.num_slots):
            raw, prob = self._score(self._frame_X(frame))
            pred = jnp.argmax(prob, axis=1).astype(float_dtype())
            out = frame.with_column(
                p.get("raw_prediction_col", "rawPrediction"), raw)
            out = out.with_column(p.get("probability_col", "probability"),
                                  prob)
            return out.with_column(p.get("prediction_col", "prediction"),
                                   pred)

    def predict(self, features) -> float:
        return float(host_fetch(jnp.argmax(
            self._proba(self._point(features)), axis=1))[0])

    def predict_probability(self, features):
        return host_fetch(self._proba(self._point(features)))[0]

    predictProbability = predict_probability


@persistable
class RandomForestClassifier(DecisionTreeClassifier):
    """MLlib ``RandomForestClassifier``: bootstrap + sqrt feature subsets
    ("auto"), soft-vote probabilities."""

    _persist_attrs = DecisionTreeClassifier._persist_attrs + (
        'num_trees', 'subsampling_rate', 'feature_subset_strategy')
    _span = "fit.random_forest_classifier"

    def __init__(self, num_trees: int = 20, subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto", **kw):
        super().__init__(**kw)
        self.num_trees = int(num_trees)
        self.subsampling_rate = float(subsampling_rate)
        self.feature_subset_strategy = feature_subset_strategy

    set_num_trees = RandomForestRegressor.set_num_trees
    setNumTrees = set_num_trees
    set_subsampling_rate = RandomForestRegressor.set_subsampling_rate
    setSubsamplingRate = set_subsampling_rate
    set_feature_subset_strategy = \
        RandomForestRegressor.set_feature_subset_strategy
    setFeatureSubsetStrategy = set_feature_subset_strategy
    _n_trees = RandomForestRegressor._n_trees
    _subsample = RandomForestRegressor._subsample
    _feature_subset = RandomForestRegressor._feature_subset

    def _make_model(self, trees, d, classes=0):
        return RandomForestClassificationModel(
            trees.feature, trees.threshold, trees.is_leaf, trees.value,
            trees.gain, d, self.max_depth, classes,
            self._params_for_model())


@persistable
class RandomForestClassificationModel(DecisionTreeClassificationModel):
    @property
    def num_trees(self):
        return int(np.asarray(self.feature).shape[0])

    getNumTrees = num_trees


# ---------------------------------------------------------------------------
# Gradient-boosted trees: sequential Newton boosting over the same builder
# ---------------------------------------------------------------------------

def _boost_loss(loss, y, F, w):
    """Weighted mean loss of the scores ``F`` over the rows ``w`` keeps, as
    (numerator, denominator) so that a sharded caller can psum both."""
    if loss == "squared":
        per_row = (y - F) ** 2
    else:
        per_row = jnp.logaddexp(0.0, -jnp.where(y > 0.5, F, -F))
    return jnp.sum(w * per_row), jnp.sum(w)


@functools.lru_cache(maxsize=None)
def _gbt_programs(loss, max_depth, max_bins, min_instances, min_info_gain,
                  subsampled, validated, mesh=None):
    """``(start, boost)``, both jitted and cached per hyperparameters so
    every boosting round (and every refit) reuses one compiled program.

    ``start(y, w, w_held) -> (F, f0, held_loss)``: the base score ``f0``
    (the weighted label mean; for the logistic loss its log-odds) on every
    row. ``boost(binned, edges, y, w, w_held, F, key, hyper) -> (F, packed
    tree, held_loss)`` with ``hyper = [step, subsampling rate]``: one
    round on the device — the gradients ``g = y - sigmoid(F)``,
    ``h = max(p(1-p), 1e-12)`` (squared loss: ``g = y - F``, ``h = 1``),
    the statistics ``[w, wg, wg², wh]``, one tree on the variance of the
    gradient (Friedman), Newton leaves Σg / Σh, ``F += step * leaf`` from
    the rows' own descent, and the loss over the held-out rows where the
    fit validates. With a mesh, rows shard over the data axis exactly like
    :func:`_forest_builder` (psum'd level histograms)."""
    def total(v, axis):
        return v if axis is None else jax.lax.psum(v, axis)

    def held_loss(y, F, w_held, axis):
        if not validated:
            return jnp.zeros((), F.dtype)
        num, den = _boost_loss(loss, y, F, w_held)
        return total(num, axis) / jnp.maximum(total(den, axis), 1e-12)

    def start(y, w, w_held, axis=None):
        with _obs.scope("tree.gradient"):
            mean = total(jnp.sum(w * y), axis) \
                / jnp.maximum(total(jnp.sum(w), axis), 1e-12)
            if loss == "squared":
                f0 = mean
            else:  # logistic: F0 = log-odds of the weighted base rate
                p0 = jnp.clip(mean, 1e-6, 1 - 1e-6)
                f0 = jnp.log(p0 / (1 - p0))
            F = jnp.full(y.shape, f0, y.dtype)
            return F, f0, held_loss(y, F, w_held, axis)

    def boost(binned, edges, y, w, w_held, F, key, hyper, axis=None):
        with _obs.scope("tree.gradient"):
            if loss == "squared":
                g, h = y - F, jnp.ones_like(y)
            else:
                p = jax.nn.sigmoid(F)
                g, h = y - p, jnp.maximum(p * (1 - p), 1e-12)
            ww = w
            if subsampled:
                if axis is not None:
                    key = jax.random.fold_in(key, jax.lax.axis_index(axis))
                ww = w * (jax.random.uniform(key, w.shape) < hyper[1])
            targets = jnp.stack([ww, ww * g, ww * g * g, ww * h])[None]
        trees, heap = build_trees(binned, edges, targets, max_depth,
                                  max_bins, "variance", min_instances,
                                  min_info_gain, psum_axis=axis)
        with _obs.scope("tree.score"):
            leaf = _ratio(trees.value[0, :, 1], trees.value[0, :, 3])
            F = F + hyper[0] * heap_lookup(heap[0], leaf[:, None])[0]
            return F, _pack_trees(trees)[0], held_loss(y, F, w_held, axis)

    if mesh is None:
        return jax.jit(start), jax.jit(boost)

    rows = P(DATA_AXIS)
    held = rows if validated else None
    start_fn = shard_map(
        lambda y, w, wh: start(y, w, wh, DATA_AXIS), mesh=mesh,
        in_specs=(rows, rows, held), out_specs=(rows, P(), P()))
    boost_fn = shard_map(
        lambda b, e, y, w, wh, F, k, hy: boost(b, e, y, w, wh, F, k, hy,
                                               DATA_AXIS),
        mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P(), rows, rows, held, rows, P(),
                  P()),
        out_specs=(rows, P(), P()))
    return (serialize_collectives(jax.jit(start_fn), mesh),
            serialize_collectives(jax.jit(boost_fn), mesh))


@jax.jit
def _pack_ensemble(trees, f0):
    """Every round's packed tree and the base score as ONE flat array: the
    one read a boosted fit ends in."""
    return jnp.concatenate([jnp.stack(trees).reshape(-1), f0[None]])


def _gbt_fit(prep: _Prepared, *, loss, max_iter, step, max_depth, max_bins,
             min_instances, min_info_gain, subsample, seed,
             validation_tol=0.01):
    """Returns (F0, stacked TreeArrays, rounds run). One compiled round a
    boosting round (:func:`_gbt_programs`), dispatched back to back with
    nothing read in between; the fit ends in one read of the packed trees.

    ``prep.w_held``: held-out row weights (MLlib
    ``validationIndicatorCol``). The rounds then also reduce the loss over
    those rows and the host reads that one scalar a round; boosting stops
    once the relative improvement over the best loss so far drops below
    ``validation_tol``, and the returned ensemble is truncated at the best
    round."""
    dt = np.dtype(float_dtype())
    validated = prep.w_held is not None
    start, boost = _gbt_programs(loss, max_depth, max_bins, min_instances,
                                 min_info_gain, subsample < 1.0, validated,
                                 prep.mesh)
    hyper = np.asarray([step, subsample], dt)
    key = jax.random.key(seed)
    F, f0, held = start(prep.y, prep.w, prep.w_held)
    best_loss = float(_read(held, "tree.held_loss")) if validated else None
    best_k = 0
    trees = []
    for i in range(max_iter):
        F, tree, held = boost(prep.binned, prep.edges, prep.y, prep.w,
                              prep.w_held, F,
                              jax.random.fold_in(key, i)
                              if subsample < 1.0 else key, hyper)
        trees.append(tree)
        if validated:
            cur = float(_read(held, "tree.held_loss"))
            if cur < best_loss - validation_tol * max(abs(best_loss), 1e-12):
                best_loss = cur
                best_k = len(trees)
            else:
                break            # no meaningful improvement: stop boosting
    rounds = len(trees)
    if validated:
        # truncate at the best round; keep at least one tree (an ensemble
        # of zero trees has no stacked arrays and MLlib keeps one too)
        trees = trees[:max(best_k, 1)]
    flat = _read(_pack_ensemble(tuple(trees), f0), "tree.result")
    packed = flat[:-1].reshape((len(trees), 2 ** (max_depth + 1) - 1, -1))
    return float(flat[-1]), _unpack_trees(packed), rounds


class _GbtBase(Estimator, _TreeParams):
    # back-compat defaults for pre-validationIndicatorCol saves
    validation_indicator_col = None
    validation_tol = 0.01

    def __init__(self, max_iter: int = 20, step_size: float = 0.1,
                 max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 subsampling_rate: float = 1.0,
                 features_col: str = "features", label_col: str = "label",
                 prediction_col: str = "prediction", seed: int = 0,
                 validation_indicator_col=None, validation_tol: float = 0.01):
        self.max_iter = int(max_iter)
        self.step_size = float(step_size)
        self.max_depth = int(max_depth)
        self.max_bins = int(max_bins)
        self.min_instances_per_node = int(min_instances_per_node)
        self.min_info_gain = float(min_info_gain)
        self.subsampling_rate = float(subsampling_rate)
        self.features_col = features_col
        self.label_col = label_col
        self.prediction_col = prediction_col
        self.seed = int(seed)
        self.validation_indicator_col = validation_indicator_col
        self.validation_tol = float(validation_tol)

    def fit(self, frame: Frame, mesh=None):
        """One root span a fit; ``fit.solve`` is the rounds dispatched to
        the read of the packed tree arrays."""
        with _obs.fit_span(self._span, _bin_program, _gbt_programs,
                           max_iter=self.max_iter, max_depth=self.max_depth):
            prep = self._prepare(frame, mesh, self._labels,
                                 self.validation_indicator_col)
            with _obs.span("fit.solve", cat="solver",
                           histogram=hist_lowering(
                               prep.features, self.max_bins,
                               prep.mesh)) as sv:
                f0, trees, rounds = _gbt_fit(
                    prep, loss=self._loss, max_iter=self.max_iter,
                    step=self.step_size, max_depth=self.max_depth,
                    max_bins=self.max_bins,
                    min_instances=self.min_instances_per_node,
                    min_info_gain=self.min_info_gain,
                    subsample=self.subsampling_rate, seed=self.seed,
                    validation_tol=self.validation_tol)
                sv.set(rounds=rounds,
                       levels=self._count_growth(prep, rounds))
            model = self._model(
                trees.feature, trees.threshold, trees.is_leaf, trees.value,
                trees.gain, prep.features, self.max_depth, f0,
                self.step_size, self._params_for_model())
            model.split_candidates = prep.edges
            return model

    def _params_for_model(self):
        return {"features_col": self.features_col,
                "prediction_col": self.prediction_col}

    def set_max_iter(self, v):
        self.max_iter = int(v)
        return self

    setMaxIter = set_max_iter

    def set_validation_indicator_col(self, v):
        self.validation_indicator_col = v
        return self

    setValidationIndicatorCol = set_validation_indicator_col

    def set_validation_tol(self, v):
        self.validation_tol = float(v)
        return self

    setValidationTol = set_validation_tol

    def set_step_size(self, v):
        self.step_size = float(v)
        return self

    setStepSize = set_step_size

    def set_subsampling_rate(self, v):
        self.subsampling_rate = float(v)
        return self

    setSubsamplingRate = set_subsampling_rate


@persistable
class GBTRegressionModel(_TreeModelBase):
    _persist_attrs = ('feature', 'threshold', 'is_leaf', 'value', 'gain',
                      '_num_features', 'max_depth', 'f0', 'step_size',
                      '_params')
    _kind = "boosted"

    def __init__(self, feature, threshold, is_leaf, value, gain,
                 num_features, max_depth, f0, step_size, params=None):
        self.feature = np.asarray(feature)
        self.threshold = np.asarray(threshold)
        self.is_leaf = np.asarray(is_leaf)
        self.value = np.asarray(value)
        self.gain = np.asarray(gain)
        self._num_features = int(num_features)
        self.max_depth = int(max_depth)
        self.f0 = float(f0)
        self.step_size = float(step_size)
        self._params = dict(params or {})

    def transform(self, frame: Frame) -> Frame:
        with _obs.span("model.transform", cat="model",
                       model=type(self).__name__, rows=frame.num_slots):
            pred = self._score(self._frame_X(frame))
            return frame.with_column(
                self._params.get("prediction_col", "prediction"),
                pred.astype(float_dtype()))

    def predict(self, features) -> float:
        return float(host_fetch(self._score(self._point(features)))[0])

    @property
    def num_trees(self):
        return int(np.asarray(self.feature).shape[0])

    getNumTrees = num_trees


@persistable
class GBTRegressor(_GbtBase):
    """MLlib ``GBTRegressor`` (squared loss)."""

    _persist_attrs = ('max_iter', 'step_size', 'max_depth', 'max_bins',
                      'min_instances_per_node', 'min_info_gain',
                      'subsampling_rate', 'features_col', 'label_col',
                      'prediction_col', 'seed',
                      'validation_indicator_col', 'validation_tol')
    _span = "fit.gbt_regressor"
    _labels = "real"
    _loss = "squared"
    _model = GBTRegressionModel


@jax.jit
def _binary_columns(F):
    """(rawPrediction, probability, prediction) of the boosted scores."""
    with _obs.scope("tree.score"):
        prob1 = jax.nn.sigmoid(F)
        return (jnp.stack([-F, F], axis=1),
                jnp.stack([1.0 - prob1, prob1], axis=1),
                (F > 0).astype(F.dtype))


@persistable
class GBTClassificationModel(GBTRegressionModel):
    _persist_attrs = GBTRegressionModel._persist_attrs

    def transform(self, frame: Frame) -> Frame:
        p = self._params
        with _obs.span("model.transform", cat="model",
                       model=type(self).__name__, rows=frame.num_slots):
            raw, prob, pred = _binary_columns(
                self._score(self._frame_X(frame)))
            out = frame.with_column(
                p.get("raw_prediction_col", "rawPrediction"), raw)
            out = out.with_column(p.get("probability_col", "probability"),
                                  prob)
            return out.with_column(p.get("prediction_col", "prediction"),
                                   pred)

    def predict(self, features) -> float:
        return float(host_fetch(self._score(self._point(features)))[0] > 0)


@persistable
class GBTClassifier(_GbtBase):
    """MLlib ``GBTClassifier`` (binary, logistic loss, Newton leaves)."""

    _persist_attrs = GBTRegressor._persist_attrs + (
        'probability_col', 'raw_prediction_col')
    _span = "fit.gbt_classifier"
    _labels = "binary"
    _loss = "logistic"
    _model = GBTClassificationModel

    def __init__(self, probability_col: str = "probability",
                 raw_prediction_col: str = "rawPrediction", **kw):
        super().__init__(**kw)
        self.probability_col = probability_col
        self.raw_prediction_col = raw_prediction_col

    def _params_for_model(self):
        return {"features_col": self.features_col,
                "prediction_col": self.prediction_col,
                "probability_col": self.probability_col,
                "raw_prediction_col": self.raw_prediction_col}
