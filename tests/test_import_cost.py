"""Importing the package loads no Pallas (standing rule 0, ROADMAP.md).

``jax.experimental.pallas`` takes about a second to import. A module that
``sparkdq4ml_tpu/models/__init__.py`` reaches and that imports it at module
level charges that second to every importer of ``models`` — the benchmark's
``table`` phase among them (PR 34 was refused for it: ``setup_s`` of
``higgs_fit``). A kernel's builder imports Pallas inside the function that
builds it, and a fit whose program holds no kernel loads none. Each case runs in a child, because this process's ``sys.modules``
holds whatever earlier tests loaded.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

IMPORTS = "import sparkdq4ml_tpu, sparkdq4ml_tpu.models"

FILTERED_FRAME = IMPORTS + """
from sparkdq4ml_tpu import TpuSession
spark = TpuSession.builder().app_name("import-cost").master("local[*]") \\
    .get_or_create()
frame = spark.create_data_frame({"a": [1.0, 2.0, 3.0], "b": [4.0, 5.0, 6.0]})
frame.create_or_replace_temp_view("t")
assert spark.sql("SELECT a, b FROM t WHERE a > 1.5").count() == 2
"""

TAIL = """
import sys
print("PALLAS=" + ",".join(sorted(
    m for m in sys.modules
    if m.startswith(("jax.experimental.pallas", "jax._src.pallas")))))
"""


DEVICE_JOIN = IMPORTS + """
import numpy as np
from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.utils.profiling import counters
left = Frame({"k": np.arange(3000, dtype=np.int32) % 700,
              "a": np.ones(3000, np.float32)})
right = Frame({"k": np.arange(700, dtype=np.int32),
               "b": np.ones(700, np.float32)})
assert left.join(right, "k", "inner").count() == 3000
assert counters.get("join.device") == 1
"""

# the reference app's flow (examples/dq4ml_pipeline.py) at tiny size: both
# rules, both SQL filters, the assembler and the Lasso fit
LINEAR_FIT = IMPORTS + """
import sparkdq4ml_tpu as dq
from sparkdq4ml_tpu.models import LinearRegression, VectorAssembler
spark = dq.TpuSession.builder().app_name("import-cost").master("local[*]") \\
    .get_or_create()
spark.udf.register("minimumPriceRule", dq.minimum_price_rule, "double")
spark.udf.register("priceCorrelationRule", dq.price_correlation_rule,
                   "double")
df = spark.create_data_frame({"guest": [2.0, 5.0, 9.0, 15.0, 20.0, 30.0],
                              "price": [10.0, 35.0, 95.0, 70.0, 90.0, 120.0]})
df = df.with_column("price_no_min",
                    dq.call_udf("minimumPriceRule", df.col("price")))
df.create_or_replace_temp_view("price")
df = spark.sql("SELECT cast(guest as int) guest, price_no_min AS price "
               "FROM price WHERE price_no_min > 0")
df = df.with_column("price_correct_correl",
                    dq.call_udf("priceCorrelationRule", df.col("price"),
                                df.col("guest")))
df.create_or_replace_temp_view("price")
df = spark.sql("SELECT guest, price_correct_correl AS price "
               "FROM price WHERE price_correct_correl > 0")
df = df.with_column("label", df.col("price"))
feats = VectorAssembler().setInputCols(["guest"]).setOutputCol("features") \\
    .transform(df)
model = LinearRegression().setMaxIter(40).setRegParam(1) \\
    .setElasticNetParam(1).fit(feats)
assert feats.count() == 4 and model.coefficients[0] > 0
"""

# the higgs_fit cell's fit at tiny size: a filter, the assembler over every
# feature column, LogisticRegression at the cell's estimator settings
LOGISTIC_FIT = IMPORTS + """
import numpy as np
from sparkdq4ml_tpu import TpuSession
from sparkdq4ml_tpu.models import LogisticRegression, VectorAssembler
spark = TpuSession.builder().app_name("import-cost").master("local[*]") \\
    .get_or_create()
rng = np.random.default_rng(0)
X = rng.normal(size=(256, 4)).astype(np.float32)
cols = {f"f{i}": X[:, i] for i in range(4)}
cols["label"] = (X @ [1.0, -2.0, 0.5, 0.0] > 0).astype(np.float32)
spark.create_data_frame(cols).create_or_replace_temp_view("higgs")
kept = spark.sql("SELECT * FROM higgs WHERE f3 > -3.0")
feats = VectorAssembler([f"f{i}" for i in range(4)], "features") \\
    .transform(kept)
model = LogisticRegression(max_iter=100, reg_param=0.0, elastic_net_param=0.0,
                           tol=1e-5, fit_intercept=True, standardization=True,
                           threshold=0.5).fit(feats)
assert model.coefficients[1] < 0
"""


@pytest.mark.parametrize("script", [IMPORTS, FILTERED_FRAME, DEVICE_JOIN,
                                    LINEAR_FIT, LOGISTIC_FIT],
                         ids=["import", "session_frame_filter",
                              "device_join", "linear_fit", "logistic_fit"])
def test_no_pallas_module_is_loaded(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")      # cwd is on sys.path
    proc = subprocess.run([sys.executable, "-c", script + TAIL], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    loaded = [line for line in proc.stdout.splitlines()
              if line.startswith("PALLAS=")]
    assert loaded == ["PALLAS="], loaded
