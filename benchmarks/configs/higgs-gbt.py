"""higgs-gbt: the table from the seed (``higgs-logistic``'s generator, loaded
and not copied), and the plain float64 reference of a boosted-tree fit.

The reference is numpy only: it imports nothing of the program and takes
nothing the program made except, in :func:`replay`, the trees it is asked to
check. Level-wise histogram boosting as the configuration's ``assumed``
states it: thresholds by ``np.partition`` at the stated ranks, bins by
comparison, a level's histograms by ``np.bincount``, the split that
maximises the fall of the variance-scaled impurity of the gradient (ties:
lowest feature, then lowest bin — ``argmax`` over (feature, bin) in that
order), Newton leaves, scores by vectorised descent. ``q`` rounds every
stored intermediate; the identity gives the float64 reference,
``refmath.round_bf16`` the lower-precision control.

A tree is a dict of heap arrays (node i's children are 2i+1 / 2i+2):
``feature`` (N,), ``threshold`` (N,), ``is_leaf`` (N,), ``value`` (N, 4) the
node's sums [w, g, g², h], ``gain`` (N,); an ensemble stacks them (T, N, ...).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

MIN_GAIN = 1e-12          # a split needs more than this (and min_info_gain)
WORKERS = 8               # threads of the per-column passes (GIL released)


def _shared():
    from benchmarks import harness

    return harness.load_module("configs", "higgs-logistic")


def column_names(cfg):
    return _shared().column_names(cfg)


def make_table(cfg, seed, rows=None):
    """{name: device column}: ``higgs-logistic``'s table for this seed."""
    return _shared().make_table(cfg, seed, rows)


def table_bytes(cfg, rows=None):
    """Bytes of the input columns one job reads (features + label)."""
    return _shared().table_bytes(cfg, rows)


def _identity(v):
    return v


def _per_column(fn, count):
    with ThreadPoolExecutor(WORKERS) as pool:
        return list(pool.map(fn, range(count)))


def thresholds(cols, keep, max_bins, q=None):
    """(d, max_bins - 1) float64: per column the values at the 1-based ranks
    ``ceil(k * n_valid / max_bins)`` of its kept rows, equal ones merged,
    +inf on the right."""
    q = q or _identity
    n_valid = int(np.count_nonzero(keep))
    edges = np.full((len(cols), max_bins - 1), np.inf)
    if n_valid == 0:
        return edges
    ranks = -(-np.arange(1, max_bins) * n_valid // max_bins) - 1

    def one(j):
        vals = np.asarray(q(cols[j][keep]), np.float64)
        return np.unique(np.partition(vals, ranks)[ranks])

    for j, uniq in enumerate(_per_column(one, len(cols))):
        edges[j, :len(uniq)] = uniq
    return edges


def bin_rows(cols, edges, q=None):
    """(d, n) uint8: how many of a column's thresholds each value exceeds
    (``searchsorted`` from the left counts the thresholds below it)."""
    q = q or _identity

    def one(j):
        return np.searchsorted(edges[j], np.asarray(q(cols[j]), np.float64),
                               side="left").astype(np.uint8)

    return np.stack(_per_column(one, len(cols)))


def base_score(y):
    """F0: the log-odds of the voting rows' base rate."""
    p0 = min(max(float(y.mean()), 1e-6), 1 - 1e-6)
    return float(np.log(p0 / (1 - p0)))


def gradients(y, F, q):
    """(g, g², h) of the logistic loss at the scores ``F``: p = sigmoid(F),
    g = y - p, h = max(p (1 - p), 1e-12)."""
    p = np.negative(F)
    np.exp(p, out=p)
    p += 1.0
    np.reciprocal(p, out=p)
    p = q(p)
    g = q(y - p)
    h = np.multiply(p, p)
    np.subtract(p, h, out=h)
    np.maximum(h, 1e-12, out=h)
    return g, q(g * g), q(h)


def level_histograms(bins, heap, base, m, weights, max_bins, q):
    """(d, m, max_bins, s): per feature the sums of each of ``weights`` (None
    = a count) over the rows of every (node, bin) of the level whose first
    heap id is ``base``; rows parked above the level fall in a slot of
    their own that is dropped."""
    pos = np.where(heap >= base, heap - np.uint8(base), m).astype(
        np.int64) * max_bins
    key = np.empty_like(pos)
    slots = (m + 1) * max_bins
    out = np.empty((bins.shape[0], slots, len(weights)))
    for f in range(bins.shape[0]):
        np.add(pos, bins[f], out=key)
        for s, w in enumerate(weights):
            out[f, :, s] = np.bincount(key, weights=w, minlength=slots)
    out = out[:, :m * max_bins]
    return q(out).reshape(bins.shape[0], m, max_bins, len(weights))


def impurity(agg):
    """Variance-scaled impurity (SSE) of [w, g, g²] sums."""
    return agg[..., 2] - agg[..., 1] ** 2 / np.maximum(agg[..., 0], 1e-12)


def split_gains(hist, edges, est, q):
    """(gains (m, d, B-1) with -inf where a candidate is not allowed, total
    (m, s), left and right (d, m, B-1, s)) of a level's histograms: candidate
    b of a feature sends bins <= b left."""
    left = q(np.cumsum(hist, axis=2)[:, :, :-1, :])
    total = hist.sum(axis=2)
    right = q(total[:, :, None, :] - left)
    gain = q(impurity(total)[:, :, None] - impurity(left) - impurity(right))
    ok = ((left[..., 0] >= est["min_instances_per_node"])
          & (right[..., 0] >= est["min_instances_per_node"])
          & np.isfinite(edges)[:, None, :])
    return (np.where(ok, gain, -np.inf).transpose(1, 0, 2), total[0], left,
            right)


def descend_level(bins, heap, base, feature, cut, split):
    """Heap ids (uint8: a depth up to 6) after one level: a row in node
    ``base + p`` that splits goes to a child — left (2i + 1) where its bin
    of ``feature[p]`` is <= ``cut[p]``, else right; every other row keeps
    its id."""
    m = len(feature)
    pos = heap - np.uint8(base)          # wraps past m for parked rows
    moves = pos < m
    np.minimum(pos, m - 1, out=pos)
    moves &= split[pos]
    row_bin = bins[feature.astype(np.uint8)[pos], np.arange(heap.shape[0])]
    child = heap * np.uint8(2) + np.uint8(2) \
        - (row_bin <= cut.astype(np.uint8)[pos])
    return np.where(moves, child, heap)


def split_bins(trees, edges):
    """(T, N) the bin each node's threshold stands for in ``edges``."""
    T, N = trees["feature"].shape
    out = np.zeros((T, N), np.int64)
    for t in range(T):
        for i in range(N):
            out[t, i] = np.searchsorted(edges[trees["feature"][t, i]],
                                        trees["threshold"][t, i],
                                        side="left")
    return out


def empty_trees(rounds, depth):
    N = 2 ** (depth + 1) - 1
    return {"feature": np.zeros((rounds, N), np.int64),
            "threshold": np.zeros((rounds, N)),
            "is_leaf": np.ones((rounds, N), bool),
            "value": np.zeros((rounds, N, 4)),
            "gain": np.zeros((rounds, N))}


def leaf_values(value):
    """Newton leaves sum g / sum h of node sums [w, g, g², h]."""
    return value[..., 1] / np.maximum(value[..., 3], 1e-12)


def grow(bins, edges, y, est, q=None, more=None):
    """The reference's own ensemble over the voting rows (``bins`` (d, rows),
    ``y``): (f0, trees, F, F_more) with ``F`` their final scores and
    ``F_more`` those of the further rows ``more`` (d, k), which are scored
    and never vote."""
    q = q or _identity
    rounds, depth = int(est["max_iter"]), int(est["max_depth"])
    B = int(est["max_bins"])
    need = max(float(est["min_info_gain"]), MIN_GAIN)
    trees = empty_trees(rounds, depth)
    f0 = q(base_score(y))
    F = np.full(y.shape, f0)
    F_more = None if more is None else np.full(more.shape[1], f0)
    for t in range(rounds):
        g, g2, h = gradients(y, F, q)
        heap = np.zeros(len(y), np.uint8)
        heap_more = None if more is None \
            else np.zeros(more.shape[1], np.uint8)
        for level in range(depth):
            m, base = 2 ** level, 2 ** level - 1
            hist = level_histograms(bins, heap, base, m, (None, g, g2, h),
                                    B, q)
            gains, total, left, right = split_gains(hist, edges, est, q)
            flat = gains.reshape(m, -1)
            best = np.argmax(flat, axis=1)
            feat, cut = best // (B - 1), best % (B - 1)
            top = flat[np.arange(m), best]
            split = top > need
            at = slice(base, base + m)
            if level == 0:
                trees["value"][t, 0] = total[0]
            trees["feature"][t, at] = feat
            trees["threshold"][t, at] = edges[feat, cut]
            trees["is_leaf"][t, at] = ~split
            trees["gain"][t, at] = np.where(split, top, 0.0)
            for p in np.flatnonzero(split):
                kids = 2 * (base + p) + 1
                trees["value"][t, kids] = left[feat[p], p, cut[p]]
                trees["value"][t, kids + 1] = right[feat[p], p, cut[p]]
            heap = descend_level(bins, heap, base, feat, cut, split)
            if more is not None:
                heap_more = descend_level(more, heap_more, base, feat, cut,
                                          split)
        leaf = q(leaf_values(trees["value"][t]))
        F = q(F + est["step_size"] * leaf[heap])
        if more is not None:
            F_more = q(F_more + est["step_size"] * leaf[heap_more])
    return float(f0), trees, F, F_more


def replay(bins, edges, y, trees, est, full=(), more=None):
    """The float64 reading of an ensemble somebody else grew, over the
    voting rows (``bins`` (d, rows), ``y``): along ITS structure (features
    and thresholds), with the reference's own scores carried from the
    reference's own leaves.

    Returns ``counts`` (T, N) the rows in every node, ``leaves`` (T, N) the
    Newton leaf of every node's rows (0 where empty), ``regret`` {tree: the
    largest (best gain - gain of the tree's split) / best gain over the
    nodes of the trees named in ``full``, both gains by the reference's own
    histograms of the node's rows}, ``F`` the final scores of the rows and
    ``F_more`` those of the further rows ``more`` (d, k)."""
    T, N = trees["feature"].shape
    depth = int(est["max_depth"])
    B = int(est["max_bins"])
    need = max(float(est["min_info_gain"]), MIN_GAIN)
    cuts = split_bins(trees, edges)
    counts = np.zeros((T, N))
    leaves = np.zeros((T, N))
    regret = {}
    f0 = base_score(y)
    F = np.full(y.shape, f0)
    F_more = None if more is None else np.full(more.shape[1], f0)
    for t in range(T):
        g, g2, h = gradients(y, F, _identity)
        heap = np.zeros(len(y), np.uint8)
        heap_more = None if more is None \
            else np.zeros(more.shape[1], np.uint8)
        worst = 0.0
        for level in range(depth):
            m, base = 2 ** level, 2 ** level - 1
            at = slice(base, base + m)
            feat, cut = trees["feature"][t, at], cuts[t, at]
            split = ~trees["is_leaf"][t, at]
            if t in full:
                hist = level_histograms(bins, heap, base, m, (None, g, g2),
                                        B, _identity)
                gains = split_gains(hist, edges, est, _identity)[0]
                for p in range(m):
                    best = float(gains[p].max())
                    if hist[0, p, :, 0].sum() == 0 or not (
                            split[p] or best > need):
                        continue         # empty, or a leaf on both sides
                    if not split[p]:
                        mine = 0.0       # the tree stopped where a split pays
                    elif cut[p] >= B - 1:
                        mine = -np.inf   # a threshold the reference lacks
                    else:
                        mine = float(gains[p, feat[p], cut[p]])
                    worst = max(worst, (best - mine) / max(best, need))
            heap = descend_level(bins, heap, base, feat, cut, split)
            if more is not None:
                heap_more = descend_level(more, heap_more, base, feat, cut,
                                          split)
        if t in full:
            regret[t] = worst
        # a row passes through every ancestor of its last node
        sums = [np.bincount(heap, weights=w, minlength=N)
                for w in (None, g, h)]
        for i in range(N - 1, 0, -1):
            for s in sums:
                s[(i - 1) // 2] += s[i]
        counts[t] = sums[0]
        leaves[t] = sums[1] / np.maximum(sums[2], 1e-12)
        F += np.take(est["step_size"] * leaves[t], heap)
        if more is not None:
            F_more += np.take(est["step_size"] * leaves[t], heap_more)
    return {"counts": counts, "leaves": leaves, "regret": regret, "F": F,
            "F_more": F_more}


def probabilities(F, q=None):
    return (q or _identity)(1.0 / (1.0 + np.exp(-F)))
