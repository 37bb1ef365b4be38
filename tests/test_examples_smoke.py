"""Example scripts run end-to-end (subprocess, CPU-pinned):
each example asserts its own results internally, so rc==0 + the final OK
banner is a real integration check, not a smoke-only pass."""

import os
import subprocess
import pytest
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, timeout: int = 240):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script)],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env)


def test_dq4ml_pipeline_end_to_end():
    """The flagship reference-app port: golden SURVEY §2.3 output."""
    proc = _run("dq4ml_pipeline.py")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-1500:])
    # float64 path prints 217.94357 / 2.8099; float32 drifts in the last
    # printed digits — accept the ±0.01-class neighborhood of the golden
    assert "Prediction for 40.0 guests is 217.9" in proc.stdout
    assert "RMSE: 2.80" in proc.stdout or "RMSE: 2.81" in proc.stdout


def test_ml_pipeline_tour_end_to_end():
    proc = _run("ml_pipeline_tour.py", timeout=420)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-1500:])
    assert "PrefixSpan" in proc.stdout


def test_distributed_fit_end_to_end():
    # the script self-appends the 8-virtual-device XLA flag when absent
    proc = _run("distributed_fit.py", timeout=420)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-1500:])
    assert "all sharded fits match their single-device fits" in proc.stdout


def test_sql_tour_end_to_end():
    proc = _run("sql_tour.py")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-1500:])
    assert "sql_tour OK" in proc.stdout
    assert "fluent dense_rank == SQL OVER dense_rank" in proc.stdout


def test_io_tour_end_to_end():
    pytest.importorskip("pandas")
    pytest.importorskip("pyarrow")
    proc = _run("io_tour.py")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-1500:])
    assert "io_tour OK" in proc.stdout
    assert "parquet: round-trip 1040 rows" in proc.stdout
    assert "applyInPandas: 1040 rows demeaned" in proc.stdout
