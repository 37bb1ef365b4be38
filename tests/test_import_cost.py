"""Importing the package loads no Pallas (standing rule 0, ROADMAP.md).

``jax.experimental.pallas`` takes about a second to import. A module that
``sparkdq4ml_tpu/models/__init__.py`` reaches and that imports it at module
level charges that second to every importer of ``models`` — the benchmark's
``table`` phase among them (PR 34 was refused for it: ``setup_s`` of
``higgs_fit``). A kernel's builder imports Pallas inside the function that
builds it. Each case runs in a child, because this process's ``sys.modules``
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


@pytest.mark.parametrize("script", [IMPORTS, FILTERED_FRAME, DEVICE_JOIN],
                         ids=["import", "session_frame_filter",
                              "device_join"])
def test_no_pallas_module_is_loaded(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")      # cwd is on sys.path
    proc = subprocess.run([sys.executable, "-c", script + TAIL], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    loaded = [line for line in proc.stdout.splitlines()
              if line.startswith("PALLAS=")]
    assert loaded == ["PALLAS="], loaded
