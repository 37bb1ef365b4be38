"""Job ``dq_lasso``: the reference app's flow, from the table.

    rule 1 (minimumPriceRule) -> SQL filter -> cast -> rule 2
    (priceCorrelationRule) -> SQL filter          (chip_smoke.dq_phase, copied)
    VectorAssembler(["guest"]) -> Lasso fit -> predict(40 guests)
    model.transform -> SELECT count(*), avg((prediction - label)^2)
                                                  (the app's RMSE, and its rows)

No traffic parameters: the rules' thresholds and the estimator's settings
are the configuration's.
"""

import numpy as np

SPANS = ("dq_sql", "fit", "score")
SCORED = "bench_scored"


class Job:
    def __init__(self, spark, cfg, cfg_mod, params, table):
        import sparkdq4ml_tpu as dq

        self.spark, self.cfg = spark, cfg
        spark.udf.register("minimumPriceRule", dq.minimum_price_rule,
                           "double")
        spark.udf.register("priceCorrelationRule",
                           dq.price_correlation_rule, "double")
        self.frame = spark.create_data_frame(
            {"guest": table["guest"], "price": table["price"]})

    def rows_in(self):
        return int(self.frame.num_slots)

    def run(self, stage):
        import sparkdq4ml_tpu as dq
        from sparkdq4ml_tpu.models import (LinearRegression, VectorAssembler,
                                           Vectors)

        spark, e = self.spark, self.cfg["estimator"]
        with stage("dq_sql") as sync:
            df = self.frame.with_column(
                "price_no_min",
                dq.call_udf("minimumPriceRule", self.frame.col("price")))
            df.create_or_replace_temp_view("price")
            df = spark.sql("SELECT cast(guest as int) guest, price_no_min "
                           "AS price FROM price WHERE price_no_min > 0")
            rows_rule1 = df.count()
            df = df.with_column(
                "price_correct_correl",
                dq.call_udf("priceCorrelationRule", df.col("price"),
                            df.col("guest")))
            df.create_or_replace_temp_view("price")
            df = spark.sql("SELECT guest, price_correct_correl AS price "
                           "FROM price WHERE price_correct_correl > 0")
            rows_rule2 = df.count()
            sync(lambda: df.mask)
        with stage("fit"):
            df = df.with_column("label", df.col("price"))
            feats = (VectorAssembler().setInputCols(e["features"])
                     .setOutputCol("features").transform(df))
            model = (LinearRegression().setMaxIter(e["max_iter"])
                     .setRegParam(e["reg_param"])
                     .setElasticNetParam(e["elastic_net_param"]).fit(feats))
        with stage("score"):
            predicted = float(model.predict(
                Vectors.dense(self.cfg["predict_for_guests"])))
            scored = model.transform(feats)
            scored.create_or_replace_temp_view(SCORED)
            agg = spark.sql(
                "SELECT count(*) AS n, avg((prediction - label) * "
                f"(prediction - label)) AS mse FROM {SCORED}").to_pydict()
        result = {
            "rows_rule1": int(rows_rule1), "rows_rule2": int(rows_rule2),
            "coefficient": float(model.coefficients[0]),
            "intercept": float(model.intercept),
            "predicted": predicted,
            "rows_scored": int(agg["n"][0]),
            "rmse": float(np.sqrt(agg["mse"][0])),
        }
        for view in ("price", SCORED):
            spark.catalog.drop(view)
        return result

    def close(self):
        self.frame = None


def fit_least_bytes(cfg, result, counters_per_job):
    """The least the fit's algorithm can read from HBM in one job: ONE pass
    over the kept rows (the solver works on the (d+2)^2 moments, not on the
    data), each row its d features, its label and its weight in float32."""
    d = len(cfg["estimator"]["features"])
    return result["rows_rule2"] * (d + 2) * 4


def reference(cfg, cfg_mod, params, host, q=None):
    """The job's answers in float64 numpy from the host copy of the table
    (or, with ``q``, in the lower precision that ``q`` rounds to)."""
    rq = q or (lambda v: v)
    counts, value = cfg_mod.tabulate(host["guest"], host["price"])
    kept1, kept = cfg_mod.rules(cfg, counts, value, q)
    coef, icpt = cfg_mod.lasso(cfg, kept, value, q)
    return {
        "rows_rule1": int(kept1.sum()), "rows_rule2": int(kept.sum()),
        "coefficient": coef, "intercept": icpt,
        "predicted": float(rq(coef * cfg["predict_for_guests"] + icpt)),
        "rows_scored": int(kept.sum()),
        "rmse": cfg_mod.rmse(kept, value, coef, icpt, q),
    }


def compare(got, want):
    """{name: gap}: every number held to a limit of the cell. The
    prediction for 40 guests is not among them: the bfloat16 control reads
    it 2.9 times the program's largest, too near to set a limit between
    (PERF.md section 2); it is coef * 40 + intercept, which ``lasso_rel``
    holds."""
    from benchmarks.refmath import mismatches, rel_gap

    return {
        "rows_rule1_diff": mismatches([got["rows_rule1"]],
                                      [want["rows_rule1"]]),
        "rows_rule2_diff": mismatches([got["rows_rule2"]],
                                      [want["rows_rule2"]]),
        "lasso_rel": rel_gap([got["coefficient"], got["intercept"]],
                             [want["coefficient"], want["intercept"]]),
        "rows_scored_diff": mismatches([got["rows_scored"]],
                                       [want["rows_scored"]]),
        "rmse_rel": rel_gap([got["rmse"]], [want["rmse"]]),
    }
