"""Job ``filter_gbt_score``: SQL range filter -> VectorAssembler ->
GBTClassifier.fit -> model.transform -> a small result on the host.

    dq_sql  SELECT * FROM <view> WHERE <col> > <t> AND ...
    fit     VectorAssembler(all feature columns) -> GBTClassifier(the
            configuration's estimator).fit: thresholds, bins and every
            boosting round on the device
    score   model.transform(frame) -> SELECT count(*),
            avg(element_at(probability, 2)), sum(prediction);
            model.transform(probe rows) -> their probabilities; the model's
            tree arrays and the thresholds it chose among, to the host

Traffic parameters (``params``): ``filters`` [[column, threshold], ...]
(kept where column > threshold; thresholds exactly representable in
float32, so that float64 and float32 agree on every row) and
``probe_rows`` (the first rows of the table, scored one by one).

A job that answers through a degraded path is an error, not a slow job:
``run`` raises if the fit did not take the device entry (``tree.fit_device``)
or a fallback counter moved, and the constructor refuses a program whose
tree fit has no device entry at all (it would pull 1.3 GB to the host and
scatter 3e8 rows a level: minutes a job, or no memory for it).

**The comparison replays the program's own trees.** Trees are
discontinuous — a float32 near-tie picks another split than float64 and
every later number differs — so ``compare`` hands the job's ensemble to the
reference (``configs/higgs-gbt.py`` ``replay``), which descends ITS
structure over every kept row in float64 with scores of its own: the rows
in each of the 63 nodes of every tree (exact), every tree's Newton leaves,
and the scores. The first and the last tree are replayed **in full**: for
each of their 31 split nodes the reference histograms the node's rows and
reads how far the tree's split stands under the best one
(``split_regret``). A window's jobs grow the same trees (same table, no
randomness at these defaults), so a replay is kept by the trees it read.
"""

import hashlib

import numpy as np

from benchmarks import harness

SPANS = ("dq_sql", "fit", "score")
SCORED = "bench_scored"
DEGRADED = ("pipeline.fallback", "pipeline.fault_fallback",
            "pipeline.oom_chunked")
TREE_KEYS = ("feature", "threshold", "is_leaf", "value", "gain")


def estimator_args(cfg):
    e = cfg["estimator"]
    return {k: e[k] for k in ("max_iter", "max_depth", "max_bins",
                              "step_size", "subsampling_rate",
                              "min_instances_per_node", "min_info_gain")}


class Job(harness.load_module("jobs", "filter_fit_score").Job):
    """``filter_fit_score``'s table, view, probe rows and filter statement;
    the fit and the score are this job's."""

    def __init__(self, spark, cfg, cfg_mod, params, table):
        from sparkdq4ml_tpu.models import tree

        if not hasattr(tree, "device_bins"):
            raise RuntimeError(
                "this program's tree fit has no device entry "
                "(models/tree.py bins and boosts on the host)")
        super().__init__(spark, cfg, cfg_mod, params, table)

    def run(self, stage):
        """One job, from the table to the result on the host. ``stage``
        gives each span; its ``sync`` waits for a stage's output in a traced
        run only."""
        from sparkdq4ml_tpu.models import GBTClassifier, VectorAssembler
        from sparkdq4ml_tpu.utils.profiling import counters

        before = [counters.get(k) for k in DEGRADED]
        fits = counters.get("tree.fit_device")
        with stage("dq_sql") as sync:
            kept = self.spark.sql(self.query)
            sync(lambda: kept.mask)
        with stage("fit"):
            feats = VectorAssembler(self.names, "features").transform(kept)
            model = GBTClassifier(**estimator_args(self.cfg)).fit(feats)
        with stage("score"):
            scored = model.transform(feats)
            scored.create_or_replace_temp_view(SCORED)
            agg = self.spark.sql(
                f"SELECT count(*) AS n, avg(element_at(probability, 2)) "
                f"AS mean_score, sum(prediction) AS positives "
                f"FROM {SCORED}").to_pydict()
            probe = model.transform(self.probe).to_pydict()["probability"]
        moved = [k for k, b in zip(DEGRADED, before) if counters.get(k) != b]
        if moved or counters.get("tree.fit_device") != fits + 1:
            raise RuntimeError("the tree fit left the device entry or "
                               f"answered through a degraded path: {moved}")
        result = {
            "rows_kept": int(agg["n"][0]),
            "edges": np.asarray(model.split_candidates, np.float64),
            "f0": float(model.f0),
            "mean_score": float(agg["mean_score"][0]),
            "positives": float(agg["positives"][0]),
            "probe_probability": np.asarray(probe, np.float64)[:, 1],
        }
        for key in TREE_KEYS:
            result[key] = np.asarray(getattr(model, key))
        self.spark.catalog.drop(SCORED)
        return result


def gbt_least_bytes(cfg, cfg_mod, rows=None):
    """The least a fit must read from HBM in one job, whatever implements
    it: the table's feature columns and the mask once for the thresholds
    and bins; then a level reads a row's 28 one-byte bins, its three
    float32 statistics (the weight is the mask) and its node id, 41 B at
    28 features; and a round reads its score, label and weight once for the
    gradients."""
    n, d = int(rows or cfg["rows"]), int(cfg["features"])
    e = cfg["estimator"]
    level = d + 3 * 4 + 1
    return n * (4 * d + 1) \
        + e["max_iter"] * n * (e["max_depth"] * level + 3 * 4)


def reference(cfg, cfg_mod, params, host, q=None):
    """What ``compare`` holds a job's result against: the kept rows' bins
    and labels in float64 numpy from the host copy of the table, with the
    thresholds and the row count — the replay reads the trees it is given.
    With ``q`` (the lower-precision control) the result instead, in the
    job's form: an ensemble the reference grew itself with every stored
    intermediate rounded."""
    rq = q or (lambda v: v)
    est = cfg["estimator"]
    cols = [host[n] for n in cfg_mod.column_names(cfg)]
    keep = np.ones(host["label"].shape[0], bool)
    for col, t in params["filters"]:
        keep &= rq(host[col]) > t
    edges = cfg_mod.thresholds(cols, keep, est["max_bins"], q)
    bins = cfg_mod.bin_rows(cols, edges, q)
    k = int(params["probe_rows"])
    want = {
        "rows_kept": int(keep.sum()), "edges": edges, "estimator": est,
        "bins": np.ascontiguousarray(bins[:, keep]),
        "y": host["label"][keep].astype(np.float64),
        "probe_bins": np.ascontiguousarray(bins[:, :k]),
        "mod": cfg_mod, "replays": {},
    }
    if q is None:
        return want
    f0, trees, F, F_probe = cfg_mod.grow(want["bins"], edges, want["y"],
                                         est, q, more=want["probe_bins"])
    p = cfg_mod.probabilities(F, q)
    return dict(trees, rows_kept=want["rows_kept"], edges=edges, f0=f0,
                mean_score=float(q(p.mean())),
                positives=float(np.count_nonzero(F > 0)),
                probe_probability=cfg_mod.probabilities(F_probe, q))


def replayed(got, want):
    """``configs/higgs-gbt.py`` ``replay`` of the ensemble in ``got``, the
    first and the last tree in full; kept by the trees' bytes, so that the
    jobs of a window, which grow the same trees, are replayed once."""
    trees = {k: np.asarray(got[k]) for k in TREE_KEYS}
    digest = hashlib.sha1(b"".join(
        np.ascontiguousarray(trees[k]).tobytes()
        for k in ("feature", "threshold", "is_leaf"))).hexdigest()
    if digest not in want["replays"]:
        rounds = trees["feature"].shape[0]
        want["replays"][digest] = want["mod"].replay(
            want["bins"], want["edges"], want["y"], trees,
            want["estimator"], full=(0, rounds - 1),
            more=want["probe_bins"])
    return want["replays"][digest]


def compare(got, want):
    """{name: gap}: every number held to a limit of the cell.

    ``rows_kept_diff``, ``edges_diff`` (thresholds of the 28 x 31 that
    differ) and ``node_rows_diff`` (nodes of the 20 x 63 whose row count,
    the program's ``w`` statistic, differs from the replay's) are exact;
    ``leaf_rel`` is the largest gap, over the trees, of a tree's Newton
    leaves against the replay's over the tree's largest leaf;
    ``split_regret`` the largest of the fully replayed trees'; the three
    score numbers are ``higgs_fit``'s, against the replayed ensemble's
    float64 scores."""
    from benchmarks.refmath import abs_gap, mismatches, rel_gap

    mod = want["mod"]
    gaps = {"rows_kept_diff": mismatches([got["rows_kept"]],
                                         [want["rows_kept"]]),
            "edges_diff": mismatches_float(got["edges"], want["edges"])}
    shape = (want["estimator"]["max_iter"],
             2 ** (want["estimator"]["max_depth"] + 1) - 1)
    if np.asarray(got["feature"]).shape != shape:
        inf = float("inf")
        return dict(gaps, node_rows_diff=inf, leaf_rel=inf, split_regret=inf,
                    mean_score_rel=inf, positives_rel=inf,
                    probe_prob_abs=inf)
    ref = replayed(got, want)
    value = np.asarray(got["value"], np.float64)
    reached = ref["counts"] > 0
    leaves = np.where(reached, mod.leaf_values(value), 0.0)
    p = mod.probabilities(ref["F"])
    gaps.update(
        node_rows_diff=float(np.count_nonzero(
            value[:, :, 0] != ref["counts"])),
        leaf_rel=max(rel_gap(mine, theirs)
                     for mine, theirs in zip(leaves, ref["leaves"])),
        split_regret=max(ref["regret"].values()),
        mean_score_rel=rel_gap([got["mean_score"]], [p.mean()]),
        positives_rel=rel_gap([got["positives"]],
                              [np.count_nonzero(ref["F"] > 0)]),
        probe_prob_abs=abs_gap(got["probe_probability"],
                               mod.probabilities(ref["F_more"])))
    return gaps


def mismatches_float(got, want):
    """How many entries of two float arrays differ (+inf equals +inf)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float(np.count_nonzero(got != want))
