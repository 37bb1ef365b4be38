"""Job ``filter_fit_score``: SQL range filter -> VectorAssembler ->
Estimator.fit -> model.transform -> a small result on the host.

    SELECT * FROM <view> WHERE <col> > <t> AND ...   (the DQ range filter)
    VectorAssembler(all feature columns) -> LogisticRegression.fit
    model.transform(frame) -> SELECT count(*), avg(probability),
    sum(prediction); model.transform(probe rows) -> their probabilities

Traffic parameters (``params``): ``filters`` [[column, threshold], ...]
(kept where column > threshold; thresholds exactly representable in
float32, so that float64 and float32 agree on every row) and
``probe_rows`` (the first rows of the table, scored one by one).
"""

import numpy as np

SPANS = ("dq_sql", "fit", "score")
VIEW = "bench_table"
SCORED = "bench_scored"


class Job:
    def __init__(self, spark, cfg, cfg_mod, params, table):
        from sparkdq4ml_tpu.models import VectorAssembler

        self.spark, self.cfg, self.params = spark, cfg, params
        self.names = cfg_mod.column_names(cfg)
        self.frame = spark.create_data_frame(table)
        self.frame.create_or_replace_temp_view(VIEW)
        k = int(params["probe_rows"])
        probe = spark.create_data_frame(
            {n: table[n][:k] for n in self.names})
        self.probe = VectorAssembler(self.names, "features").transform(probe)
        where = " AND ".join(f"{c} > {t!r}" for c, t in params["filters"])
        self.query = f"SELECT * FROM {VIEW} WHERE {where}"

    def rows_in(self):
        return int(self.frame.num_slots)

    def run(self, stage):
        """One job, from the table to the result on the host. ``stage``
        gives each span; its ``sync`` waits for a stage's output in a traced
        run only."""
        from sparkdq4ml_tpu.models import LogisticRegression, VectorAssembler

        e = self.cfg["estimator"]
        with stage("dq_sql") as sync:
            kept = self.spark.sql(self.query)
            sync(lambda: kept.mask)
        with stage("fit"):
            feats = VectorAssembler(self.names, "features").transform(kept)
            model = LogisticRegression(
                max_iter=e["max_iter"], reg_param=e["reg_param"],
                elastic_net_param=e["elastic_net_param"], tol=e["tol"],
                fit_intercept=e["fit_intercept"],
                standardization=e["standardization"],
                threshold=e["threshold"]).fit(feats)
        with stage("score"):
            scored = model.transform(feats)
            scored.create_or_replace_temp_view(SCORED)
            agg = self.spark.sql(
                f"SELECT count(*) AS n, avg(probability) AS mean_score, "
                f"sum(prediction) AS positives FROM {SCORED}").to_pydict()
            probe = model.transform(self.probe).to_pydict()["probability"]
        result = {
            "rows_kept": int(agg["n"][0]),
            "coefficients": np.asarray(model.coefficients, np.float64),
            "intercept": float(model.intercept),
            "mean_score": float(agg["mean_score"][0]),
            "positives": float(agg["positives"][0]),
            "probe_probability": np.asarray(probe, np.float64),
        }
        self.spark.catalog.drop(SCORED)
        return result

    def close(self):
        self.spark.catalog.drop(VIEW)
        self.frame = self.probe = None


def fit_least_bytes(cfg, result, counters_per_job):
    """The least the fit's algorithm can read from HBM in one job: one pass
    per reported solver iteration over the kept rows, each row its d
    features, its label and its weight in float32."""
    passes = counters_per_job.get("solver.iterations", 0)
    return passes * result["rows_kept"] * (int(cfg["features"]) + 2) * 4


def reference(cfg, cfg_mod, params, host, q=None):
    """The job's answers in float64 numpy from the host copy of the table
    (or, with ``q``, in the lower precision that ``q`` rounds to)."""
    rq = q or (lambda v: v)
    cols = [host[n] for n in cfg_mod.column_names(cfg)]
    keep = np.ones(host["label"].shape[0], bool)
    for col, t in params["filters"]:
        keep &= rq(host[col]) > t
    y = host["label"].astype(np.float64)
    coef, icpt = cfg_mod.logistic_mle(cols, y, keep, cfg, q)
    p = cfg_mod.scores(cols, coef, icpt, q)
    k = int(params["probe_rows"])
    return {
        "rows_kept": int(keep.sum()),
        "coefficients": coef, "intercept": icpt,
        "mean_score": float(rq(p[keep].mean())),
        "positives": float(np.count_nonzero(
            p[keep] > cfg["estimator"]["threshold"])),
        "probe_probability": p[:k].copy(),
    }


def compare(got, want):
    """{name: gap}: every number held to a limit of the cell."""
    from benchmarks.refmath import abs_gap, mismatches, rel_gap

    return {
        "rows_kept_diff": mismatches([got["rows_kept"]],
                                     [want["rows_kept"]]),
        "coef_rel": rel_gap(np.append(got["coefficients"], got["intercept"]),
                            np.append(want["coefficients"],
                                      want["intercept"])),
        "mean_score_rel": rel_gap([got["mean_score"]], [want["mean_score"]]),
        "positives_rel": rel_gap([got["positives"]], [want["positives"]]),
        "probe_prob_abs": abs_gap(got["probe_probability"],
                                  want["probe_probability"]),
    }
