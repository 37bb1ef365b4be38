"""hibench-kmeans: the table from the seed, and the plain float64 reference
of MLlib's KMeans (k-means‖ seeding, Lloyd's loop).

The table is made on the device, a column a call (``make_table``), and is
pulled to the host once, after the window, for the reference. The
reference is numpy only: it imports nothing of the program and takes
nothing the program made except, in :func:`replay` and
:func:`candidate_check`, the centres and candidates it is asked to check.
Blocked over rows (a block is ``(d, BLOCK)`` float64, column-major like
the host copy), blocks spread over ``WORKERS`` threads (numpy releases the
GIL). ``q`` rounds every stored intermediate — a row's values, a centre,
a difference, its square, a distance, a total, a new centre; sums
accumulate unrounded, as a lower-precision program would accumulate in
float32 — ``None`` gives the float64 reference, ``refmath.round_bf16`` the
lower-precision control.

Departures from Spark 2.4, each where it is made: the draws are numpy's;
``kmeans_parallel`` measures a round against the newest candidates only
and keeps the minimum (as Spark does) and weighs by the nearest candidate
with ties to the earlier one (Spark: ``findClosest``, the same).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 16
WORKERS = 8
LOCAL_ITERATIONS = 30      # LocalKMeans.kMeansPlusPlus's cap


def column_names(cfg):
    return [f"f{j}" for j in range(cfg["features"])]


def make_table(cfg, seed, rows=None):
    """{name: device column}: 20 float32 columns, as the configuration's
    ``assumed.generator`` says."""
    import jax
    import jax.numpy as jnp

    n, d = int(rows or cfg["rows"]), int(cfg["features"])
    a = cfg["assumed"]
    c = int(cfg["generated_clusters"])
    lo, hi = a["sigma_range"]
    fixed = jnp.asarray(a["filter_centres"], jnp.float32)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    k_cluster, k_sigma, k_centre, k_rows = jax.random.split(key, 4)

    @jax.jit
    def column(key, centre, sigma, cluster):
        z = jax.random.normal(key, (n,), jnp.float32)
        return centre[cluster] + sigma[cluster] * z

    cluster = jax.random.randint(k_cluster, (n,), 0, c)
    sigma = jax.random.uniform(k_sigma, (c,), jnp.float32, lo, hi)
    cols = {}
    for j, name in enumerate(column_names(cfg)):
        kj = jax.random.fold_in(k_centre, j)
        if j in a["filter_columns"]:
            centre = jax.random.permutation(kj, fixed)
        else:
            centre = jax.random.uniform(kj, (c,), jnp.float32,
                                        -a["centre_box"], a["centre_box"])
        cols[name] = column(jax.random.fold_in(k_rows, j), centre, sigma,
                            cluster)
    return jax.block_until_ready(cols)


def table_bytes(cfg, rows=None):
    """Bytes of the input columns one job reads."""
    return int(rows or cfg["rows"]) * int(cfg["features"]) * 4


def _blocks(n):
    return [(lo, min(lo + BLOCK, n)) for lo in range(0, n, BLOCK)]


def _over_blocks(fn, n):
    with ThreadPoolExecutor(WORKERS) as pool:
        return list(pool.map(lambda b: fn(*b), _blocks(n)))


def _block(cols, lo, hi, q):
    """Rows lo..hi as a float64 (d, rows) block."""
    Xt = np.empty((len(cols), hi - lo))
    for j, col in enumerate(cols):
        Xt[j] = col[lo:hi] if q is None else q(col[lo:hi])
    return Xt


def sq_dists(Xt, centres, q=None):
    """(K, rows): ``Σ_j (x_j − c_j)²`` of every row of the block to every
    centre — the differences, never ``‖x‖² − 2x·c + ‖c‖²``."""
    out = np.empty((len(centres), Xt.shape[1]))
    for j, c in enumerate(centres):
        diff = Xt - c[:, None]
        if q is None:
            out[j] = np.einsum("ib,ib->b", diff, diff)
        else:
            diff = q(diff)
            out[j] = q(q(diff * diff).sum(axis=0))
    return out


def nearest(cols, kept, centres, q=None, sums=False):
    """One pass over the kept rows against ``centres`` (K, d): per block
    the row's nearest centre (ties: the lower index) and its squared
    distance; returns (sizes (K,) int, cost, coordinate sums (K, d) or
    None). Dropped rows are never looked at."""
    centres = np.asarray(centres, np.float64)
    if q is not None:
        centres = q(centres)
    K, d = centres.shape

    def one(lo, hi):
        keep = kept[lo:hi]
        Xt = _block(cols, lo, hi, q)[:, keep]
        d2 = sq_dists(Xt, centres, q)
        arg = d2.argmin(axis=0)
        size = np.bincount(arg, minlength=K)
        cost = d2[arg, np.arange(arg.shape[0])].sum()
        total = None
        if sums:
            onehot = (arg[None, :] == np.arange(K)[:, None]).astype(np.float64)
            total = onehot @ Xt.T
        return size, cost, total

    parts = _over_blocks(one, kept.shape[0])
    size = np.sum([p[0] for p in parts], axis=0)
    cost = float(np.sum([p[1] for p in parts]))
    total = np.sum([p[2] for p in parts], axis=0) if sums else None
    return size, (cost if q is None else float(q(cost))), total


def lloyd_step(cols, kept, centres, q=None):
    """One Lloyd iteration from ``centres``: (sums, sizes, cost AT
    ``centres``, next centres). An empty cluster keeps its centre."""
    centres = np.asarray(centres, np.float64)
    sizes, cost, sums = nearest(cols, kept, centres, q, sums=True)
    if q is not None:
        sums = q(sums)
    new = np.where(sizes[:, None] > 0,
                   sums / np.maximum(sizes, 1)[:, None], centres)
    return sums, sizes, cost, (new if q is None else q(new))


def stops_after(history, tol, max_iter):
    """MLlib's rule on a history of centres: the number of iterations after
    which the loop stops — the first whose every centre moved at most
    ``tol`` (squared: ``tol²``), else ``max_iter``."""
    for t in range(1, min(len(history), max_iter + 1)):
        shift = ((history[t] - history[t - 1]) ** 2).sum(axis=1).max()
        if shift <= tol * tol:
            return t
    return max_iter


def replay(cols, kept, history, q=None):
    """The program's own history held to the reference one step at a time:
    for every iteration t the gap between the program's centres t and ONE
    reference step from the program's centres t − 1 (so a float32 near-tie
    that moves a row costs that row, not every later number), relative to
    the largest coordinate; the cost at the centres before the loop; and
    the sizes and the cost at the last centres."""
    history = np.asarray(history, np.float64)
    gaps, costs = [], []
    for t in range(1, len(history)):
        _, _, cost, new = lloyd_step(cols, kept, history[t - 1], q)
        costs.append(cost)
        gaps.append(float(np.max(np.abs(new - history[t]))
                          / max(float(np.max(np.abs(new))), 1e-30)))
    sizes, cost, _ = nearest(cols, kept, history[-1], q)
    costs.append(cost)
    return {"gaps": gaps, "initial_cost": costs[0], "sizes": sizes,
            "cost": cost}


def candidate_check(cols, kept, candidates, weights):
    """(candidates that are no kept row, rows by which the weights differ):
    a candidate must BE a kept row — distance 0 exactly to its nearest
    kept row — and its weight the number of kept rows nearer to it than
    to any other candidate (ties: the earlier candidate)."""
    candidates = np.asarray(candidates, np.float64)
    m = len(candidates)

    def one(lo, hi):
        keep = kept[lo:hi]
        d2 = sq_dists(_block(cols, lo, hi, None)[:, keep], candidates)
        return np.bincount(d2.argmin(axis=0), minlength=m), \
            d2.min(axis=1, initial=np.inf)

    parts = _over_blocks(one, kept.shape[0])
    counts = np.sum([p[0] for p in parts], axis=0)
    closest = np.min([p[1] for p in parts], axis=0)
    return int(np.count_nonzero(closest != 0.0)), \
        float(np.abs(counts - np.asarray(weights, np.int64)).sum())


def local_kmeans_pp(points, weights, k, rng, q=None):
    """Spark's ``LocalKMeans.kMeansPlusPlus`` over the candidates: a
    weighted k-means++, then at most 30 weighted Lloyd steps, stopped when
    no candidate changes its centre; a centre left without candidates
    moves to a candidate drawn uniformly."""
    rq = q or (lambda v: v)
    points = np.asarray(points, np.float64)
    weights = np.asarray(weights, np.float64)
    m = len(points)
    centres = np.empty((k, points.shape[1]))
    centres[0] = points[rng.choice(m, p=weights / weights.sum())]
    cost = sq_dists(points.T, centres[:1], q)[0]
    for i in range(1, k):
        mass = np.cumsum(weights * cost)
        j = min(int(np.searchsorted(mass, rng.random() * mass[-1],
                                    side="right")), m - 1)
        centres[i] = points[j]
        cost = np.minimum(cost, sq_dists(points.T, centres[i:i + 1], q)[0])
    assign = np.full(m, -1)
    for _ in range(LOCAL_ITERATIONS):
        now = sq_dists(points.T, centres, q).argmin(axis=0)
        if np.array_equal(now, assign):
            break
        assign = now
        for j in range(k):
            mine = assign == j
            total = weights[mine].sum()
            centres[j] = rq((weights[mine, None] * points[mine]).sum(0)
                            / total) if total > 0 \
                else points[rng.integers(m)]
    return centres


def kmeans_parallel(cols, kept, k, steps, rng, q=None):
    """The reference's own k-means‖ (Spark 2.4 ``initKMeansParallel``):
    (candidates, weights, the k initial centres). A first centre drawn
    uniformly from the kept rows; ``steps`` rounds of every kept row's
    cost (the least squared distance to the candidates so far), each row
    drawn independently with probability ``min(1, 2 k cost / Σcost)``, the
    drawn rows appended; then the kept rows nearest each candidate, and the
    candidates reduced to k by :func:`local_kmeans_pp`. Holds one float64
    and one integer a kept row. Used for the band the program's seeding
    must stand in and for the lower-precision control — never for the
    centres a replay starts from."""
    rows = np.flatnonzero(kept)
    n = rows.shape[0]

    def take(idx):
        out = np.stack([col[idx] for col in cols], axis=1).astype(np.float64)
        return out if q is None else q(out)

    candidates = take(rows[rng.integers(n, size=1)])
    cost = np.full(n, np.inf)
    arg = np.zeros(n, np.int64)
    new, base = candidates, 0
    before = np.cumsum(kept) - kept      # kept rows ahead of each row

    def measure(new, base):
        def one(lo, hi):
            keep = kept[lo:hi]
            d2 = sq_dists(_block(cols, lo, hi, q)[:, keep], new, q)
            at = slice(before[lo], before[lo] + np.count_nonzero(keep))
            best, who = d2.min(axis=0), d2.argmin(axis=0)
            closer = best < cost[at]
            cost[at] = np.where(closer, best, cost[at])
            arg[at] = np.where(closer, who + base, arg[at])

        _over_blocks(one, kept.shape[0])

    for _ in range(steps):
        measure(new, base)
        p = 2.0 * k * cost / cost.sum()
        drawn = np.flatnonzero(rng.random(n) < p)
        base = len(candidates)
        new = take(rows[drawn])
        candidates = np.concatenate([candidates, new])
    if len(new):
        measure(new, base)
    weights = np.bincount(arg, minlength=len(candidates))
    points, inverse = np.unique(candidates, axis=0, return_inverse=True)
    mass = np.bincount(inverse.ravel(), weights, len(points))
    centres = points if len(points) <= k else \
        local_kmeans_pp(points, mass, k, rng, q)
    return candidates, weights, centres
