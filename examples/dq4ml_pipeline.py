"""Port of the reference application
(`DataQuality4MachineLearningApp.java:28-155`) to the TPU-native framework —
same phases, same banners, same observable outputs: session init, UDF
registration, CSV load (bare-CR), two DQ rules + SQL cleanups, label column,
VectorAssembler, Lasso LinearRegression (maxIter=40, regParam=1,
elasticNetParam=1), transform/show, training summary, and the prediction for
40 guests.

Run:  python examples/dq4ml_pipeline.py [path/to/dataset.csv]
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import sparkdq4ml_tpu as dq
from sparkdq4ml_tpu.models import LinearRegression, Vectors, VectorAssembler
from sparkdq4ml_tpu.utils import configure_logging


def start(filename: str) -> None:
    # Session init (`App.java:38-41`): device discovery + mesh construction
    # replaces the driver JVM / executor pool. The span tracer is on, so
    # the run ends with the tree of what it did and how long each part took.
    spark = (dq.TpuSession.builder().app_name("DQ4ML").master("local[*]")
             .config("spark.observability.enabled", "true").get_or_create())

    # DQ Section (`App.java:44-95`)
    # ----------
    spark.udf.register("minimumPriceRule", dq.minimum_price_rule, "double")
    spark.udf.register("priceCorrelationRule", dq.price_correlation_rule, "double")

    df = (spark.read.format("csv")
          .option("inferSchema", "true").option("header", "false")
          .load(filename))

    df = df.with_column_renamed("_c0", "guest")
    df = df.with_column_renamed("_c1", "price")

    print("----")
    print("Load & Format")
    df.show()
    print("----")

    def dq_phase(d, show=False):
        d = d.with_column("price_no_min",
                          dq.call_udf("minimumPriceRule", d.col("price")))
        if show:
            print("----")
            print("1st DQ rule")
            d.print_schema()
            d.show(50)
            print("----")

        d.create_or_replace_temp_view("price")
        d = spark.sql("SELECT cast(guest as int) guest, price_no_min AS price "
                      "FROM price WHERE price_no_min > 0")
        if show:
            print("----")
            print("1st DQ rule - clean-up")
            d.print_schema()
            d.show(50)
            print("----")

        d = d.with_column("price_correct_correl",
                          dq.call_udf("priceCorrelationRule",
                                      d.col("price"), d.col("guest")))
        d.create_or_replace_temp_view("price")
        return spark.sql("SELECT guest, price_correct_correl AS price "
                         "FROM price WHERE price_correct_correl > 0")

    df = dq_phase(df, show=True)

    print("----")
    print("2nd DQ rule")
    df.show(50)
    print("----")

    # ML Section (`App.java:98-126`)
    # ----------
    df = df.with_column("label", df.col("price"))

    assembler = VectorAssembler().setInputCols(["guest"]).setOutputCol("features")
    df = assembler.transform(df)
    df.print_schema()
    df.show()

    lr = LinearRegression().setMaxIter(40).setRegParam(1).setElasticNetParam(1)

    model = lr.fit(df)

    model.transform(df).show()

    # Summary (`App.java:132-146`)
    trainingSummary = model.summary
    print("numIterations: " + str(trainingSummary.totalIterations))
    print("objectiveHistory: [" +
          ",".join(str(v) for v in trainingSummary.objectiveHistory) + "]")
    trainingSummary.residuals.show()
    print("RMSE: " + str(trainingSummary.rootMeanSquaredError))
    print("r2: " + str(trainingSummary.r2))

    print("Intersection: " + str(model.intercept))
    print("Regression parameter: " + str(model.getRegParam()))
    print("Tol: " + str(model.getTol()))

    # Prediction (`App.java:148-154`)
    feature = 40.0
    features = Vectors.dense(40.0)
    p = model.predict(features)
    print(f"Prediction for {feature} guests is {p}")

    # The program's own spans (the first run of each program includes its
    # XLA compile: the fit root says `compile=miss` then).
    print("span tree (ms):")
    print(spark.trace_report())

    # Pipeline-compiler telemetry (README § "Pipeline compiler & jit
    # cache"): steady-state reruns should show `compile` frozen while
    # `flush`/`hit` climb — cache reuse across the repeated DQ queries.
    from sparkdq4ml_tpu.utils.profiling import counters
    print("pipeline counters:", counters.snapshot("pipeline"))


if __name__ == "__main__":
    configure_logging()
    default = os.path.join(os.path.dirname(__file__), "..", "data",
                           "dataset-abstract.csv")
    start(sys.argv[1] if len(sys.argv) > 1 else default)
