"""Job ``filter_kmeans_cost``: SQL range filter -> VectorAssembler ->
KMeans.fit -> model.transform / compute_cost -> a small result on the host
(HiBench's ``ml/kmeans``: ``DenseKMeans`` trains MLlib's KMeans and ends in
the model's cost).

    dq_sql  SELECT * FROM <view> WHERE <col> > <t> AND ...
    fit     VectorAssembler(all columns) -> KMeans(the configuration's
            estimator).fit: k-means‖ and Lloyd's loop on the device
    score   model.transform(frame) -> SELECT prediction, count(*) ...
            GROUP BY prediction ORDER BY prediction; model.compute_cost;
            to the host: the counts, the cost, and the model's centres,
            history, sizes, training cost, iterations and candidates

Traffic parameters (``params``): ``filters`` [[column, threshold], ...]
(kept where column > threshold; thresholds exactly representable in
float32), ``band_share`` and ``band_seeds`` (the seeded share of the kept
rows and the numpy seeds over which the reference runs its own k-means‖
for ``init_cost_ratio``).

A job that answers through a degraded path is an error, not a slow job:
``run`` raises if the fit did not take the device entry
(``kmeans.fit_device``), a seeding round overflowed its bucket
(``kmeans.init_overflow``) or a fallback counter moved, and the constructor
refuses a program whose KMeans has no device entry at all (it would pull
4 GB to the host, seed there and push it back: tens of seconds a job).

**The comparison replays the program's own history.** k-means is
discontinuous in its seeding — another draw, other centres — so
``compare`` hands the job's candidates and history to the reference
(``configs/hibench-kmeans.py``): the candidates must be kept rows with the
reference's weights, the initial centres k distinct points whose cost
stands in the band of the reference's own k-means‖, every iteration one
reference step from the centres before it (over every kept row, all
iterations: 6 passes and the candidates' one took under a minute at full
size), and the sizes and costs those of the last centres. A window's jobs
share table and seed and so draw the same candidates: a replay is kept by
the history it read.
"""

import hashlib

import numpy as np

SPANS = ("dq_sql", "fit", "score")
VIEW = "bench_table"
SCORED = "bench_scored"
DEGRADED = ("pipeline.fallback", "pipeline.fault_fallback",
            "pipeline.oom_chunked", "grouped.fallback",
            "grouped.fault_fallback", "kmeans.init_overflow")


def estimator_args(cfg):
    e = cfg["estimator"]
    return {k: e[k] for k in ("k", "max_iter", "tol", "seed", "init_mode",
                              "init_steps")}


class Job:
    def __init__(self, spark, cfg, cfg_mod, params, table):
        from sparkdq4ml_tpu.models import clustering

        if not hasattr(clustering, "device_pass"):
            raise RuntimeError(
                "this program's KMeans has no device entry "
                "(models/clustering.py pulls X to the host and seeds there)")
        self.spark, self.cfg, self.params = spark, cfg, params
        self.names = cfg_mod.column_names(cfg)
        self.frame = spark.create_data_frame(table)
        self.frame.create_or_replace_temp_view(VIEW)
        where = " AND ".join(f"{c} > {t!r}" for c, t in params["filters"])
        self.query = f"SELECT * FROM {VIEW} WHERE {where}"

    def rows_in(self):
        return int(self.frame.num_slots)

    def run(self, stage):
        """One job, from the table to the result on the host. ``stage``
        gives each span; its ``sync`` waits for a stage's output in a traced
        run only."""
        from sparkdq4ml_tpu.models import KMeans, VectorAssembler
        from sparkdq4ml_tpu.utils.profiling import counters

        before = [counters.get(k) for k in DEGRADED]
        fits = counters.get("kmeans.fit_device")
        k = int(self.cfg["estimator"]["k"])
        with stage("dq_sql") as sync:
            kept = self.spark.sql(self.query)
            sync(lambda: kept.mask)
        with stage("fit"):
            feats = VectorAssembler(self.names, "features").transform(kept)
            model = KMeans(**estimator_args(self.cfg)).fit(feats)
        with stage("score"):
            scored = model.transform(feats)
            scored.create_or_replace_temp_view(SCORED)
            groups = self.spark.sql(
                f"SELECT prediction, count(*) AS n FROM {SCORED} "
                f"GROUP BY prediction ORDER BY prediction").to_pydict()
            cost = model.compute_cost(feats)
        moved = [n for n, b in zip(DEGRADED, before) if counters.get(n) != b]
        if moved or counters.get("kmeans.fit_device") != fits + 1:
            raise RuntimeError("the k-means fit left the device entry or "
                               f"answered through a degraded path: {moved}")
        sizes = np.zeros(k, np.int64)
        for p, n in zip(groups["prediction"], groups["n"]):
            sizes[int(p)] = int(n)
        summary = model.summary
        candidates, weights = summary.init_candidates
        self.spark.catalog.drop(SCORED)
        return {
            "rows_kept": int(sizes.sum()),
            "score_sizes": sizes, "score_cost": float(cost),
            "history": np.asarray(summary.history, np.float64),
            "iterations": int(summary.num_iter),
            "sizes": np.asarray(summary.cluster_sizes, np.int64),
            "cost": float(summary.training_cost),
            "candidates": np.asarray(candidates, np.float64),
            "weights": np.asarray(weights, np.int64),
        }

    def close(self):
        self.spark.catalog.drop(VIEW)
        self.frame = None


def kmeans_least_bytes(cfg, cfg_mod, rows=None):
    """The least a fit must read from HBM in one job, whatever implements
    it: the feature columns once for every round of the seeding, once for
    the candidates' weights and once an iteration — passes no
    implementation can fuse or drop, each depending on the centres the one
    before produced. The final cost's pass is left out: a program may take
    it in the score."""
    e = cfg["estimator"]
    n, d = int(rows or cfg["rows"]), int(cfg["features"])
    return n * d * 4 * (e["init_steps"] + 1 + e["max_iter"])


def _kept(cfg_mod, cfg, params, host, q=None):
    cols = [host[n] for n in cfg_mod.column_names(cfg)]
    keep = np.ones(cols[0].shape[0], bool)
    for col, t in params["filters"]:
        keep &= (host[col] if q is None else q(host[col])) > t
    return cols, keep


def reference(cfg, cfg_mod, params, host, q=None):
    """What ``compare`` holds a job's result against: the host copy of the
    columns, the kept rows, and the band of the reference's own k-means‖ —
    the mean cost a kept row of its k initial centres, over
    ``band_seeds`` numpy seeds on a seeded ``band_share`` of the kept rows;
    the replay reads the history it is given. With ``q`` (the
    lower-precision control) the result instead, in the job's form: the
    reference's own seeding and Lloyd's loop with every stored
    intermediate rounded."""
    est = cfg["estimator"]
    cols, keep = _kept(cfg_mod, cfg, params, host, q)
    if q is not None:
        rng = np.random.default_rng(est["seed"])
        candidates, weights, centres = cfg_mod.kmeans_parallel(
            cols, keep, est["k"], est["init_steps"], rng, q)
        history = [centres]
        for _ in range(est["max_iter"]):
            history.append(cfg_mod.lloyd_step(cols, keep, history[-1], q)[3])
            if cfg_mod.stops_after(history, est["tol"],
                                   est["max_iter"]) < est["max_iter"]:
                break
        sizes, cost, _ = cfg_mod.nearest(cols, keep, history[-1], q)
        return {"rows_kept": int(keep.sum()), "score_sizes": sizes,
                "score_cost": cost, "history": np.asarray(history),
                "iterations": len(history) - 1, "sizes": sizes, "cost": cost,
                "candidates": candidates, "weights": weights}
    rows = np.flatnonzero(keep)
    pick = np.random.default_rng(0).random(rows.shape[0]) \
        < float(params["band_share"])
    sample = np.zeros_like(keep)
    sample[rows[pick]] = True
    band = []
    for s in range(int(params["band_seeds"])):
        centres = cfg_mod.kmeans_parallel(
            cols, sample, est["k"], est["init_steps"],
            np.random.default_rng(s))[2]
        band.append(cfg_mod.nearest(cols, sample, centres)[1]
                    / max(int(sample.sum()), 1))
    return {"rows_kept": int(keep.sum()), "cols": cols, "keep": keep,
            "estimator": est, "mod": cfg_mod, "replays": {},
            "band_cost": float(np.median(band))}


def replayed(got, want):
    """``configs/hibench-kmeans.py`` ``replay`` of the history in ``got``
    and ``candidate_check`` of its candidates; kept by their bytes, so that
    the jobs of a window, which draw the same candidates, are replayed
    once."""
    history = np.ascontiguousarray(got["history"], np.float64)
    candidates = np.ascontiguousarray(got["candidates"], np.float64)
    weights = np.ascontiguousarray(got["weights"], np.int64)
    digest = hashlib.sha1(history.tobytes() + candidates.tobytes()
                          + weights.tobytes()).hexdigest()
    if digest not in want["replays"]:
        mod = want["mod"]
        want["replays"][digest] = dict(
            mod.replay(want["cols"], want["keep"], history),
            candidates=mod.candidate_check(want["cols"], want["keep"],
                                           candidates, weights))
    return want["replays"][digest]


def compare(got, want):
    """{name: gap}: every number held to a limit of the cell.

    ``rows_kept_diff``; ``iterations_diff`` (the iterations reported
    against MLlib's stopping rule applied to the history, and against the
    history's length); ``candidates_not_rows`` and
    ``candidate_weights_diff`` (rows) from ``candidate_check``;
    ``init_distinct_diff`` (k less the distinct initial centres);
    ``init_cost_ratio`` (the cost of the initial centres a kept row over
    the reference's own k-means‖'s: an upper limit only); ``step_rel`` (the
    largest gap of ``replay`` over the iterations); ``sizes_diff`` (rows,
    the model's sizes against the replay's at the last centres) and
    ``cost_rel``; ``score_sizes_diff`` (the SQL counts against the same)
    and ``score_cost_rel``."""
    from benchmarks.refmath import mismatches, rel_gap

    est = want["estimator"]
    gaps = {"rows_kept_diff": mismatches([got["rows_kept"]],
                                         [want["rows_kept"]])}
    history = np.asarray(got["history"], np.float64)
    rest = ("iterations_diff", "candidates_not_rows",
            "candidate_weights_diff", "init_distinct_diff",
            "init_cost_ratio", "step_rel", "sizes_diff", "cost_rel",
            "score_sizes_diff", "score_cost_rel")
    if (history.ndim != 3 or history.shape[0] < 2
            or history.shape[1] != est["k"]
            or not np.all(np.isfinite(history))
            or np.asarray(got["candidates"]).ndim != 2):
        return dict(gaps, **{name: float("inf") for name in rest})
    ref = replayed(got, want)
    stops = want["mod"].stops_after(history, est["tol"], est["max_iter"])
    iterations = max(abs(got["iterations"] - stops),
                     abs(got["iterations"] - (history.shape[0] - 1)))
    not_rows, weights_diff = ref["candidates"]
    rows = max(want["rows_kept"], 1)
    gaps.update(
        iterations_diff=float(iterations),
        candidates_not_rows=float(not_rows),
        candidate_weights_diff=weights_diff,
        init_distinct_diff=float(
            est["k"] - len(np.unique(history[0], axis=0))),
        init_cost_ratio=(ref["initial_cost"] / rows) / want["band_cost"],
        step_rel=max(ref["gaps"]),
        sizes_diff=float(np.abs(np.asarray(got["sizes"], np.int64)
                                - ref["sizes"]).sum()),
        cost_rel=rel_gap([got["cost"]], [ref["cost"]]),
        score_sizes_diff=float(np.abs(
            np.asarray(got["score_sizes"], np.int64) - ref["sizes"]).sum()),
        score_cost_rel=rel_gap([got["score_cost"]], [ref["cost"]]))
    return gaps
