"""Test harness: distributed-without-a-cluster (SURVEY.md §4).

The reference's answer to "test distributed code on one machine" is
``master("local[*]")``; ours is an 8-fake-device CPU backend
(``xla_force_host_platform_device_count``) so the very same sharded
``psum`` code path runs in CI, and sharded fit can be asserted identical to
single-device fit.

Tests run in float64 (``jax_enable_x64``) so the golden tables from
SURVEY.md §2.3 can be asserted to ~1e-6; a dedicated test covers the float32
TPU-default precision envelope.
"""

import os

# Must happen before the first jax backend init.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# Compiles that happen BEFORE any test creates a TpuSession (most model
# tests never do) go to the same persistent cache a session would use:
# JAX_COMPILATION_CACHE_DIR when the environment names one, else the fixed
# in-checkout directory.
from sparkdq4ml_tpu.session import configure_compilation_cache  # noqa: E402

configure_compilation_cache()
# The suite's thousands of tiny repeated CPU compiles are exactly the case
# worth persisting (the AOT loader's stderr noise is captured here).
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import jax.numpy as jnp
import pytest

from sparkdq4ml_tpu.config import config

config.default_float_dtype = jnp.float64

DATA_DIR = os.path.join(os.path.dirname(__file__), "..", "data")


def dataset_path(name: str) -> str:
    return os.path.abspath(os.path.join(DATA_DIR, f"dataset-{name}.csv"))


@pytest.fixture(autouse=True)
def _clean_global_state():
    """Each test gets a fresh catalog/registry/session."""
    yield
    from sparkdq4ml_tpu import session as sess_mod
    from sparkdq4ml_tpu.ops import udf as udf_mod
    from sparkdq4ml_tpu.sql.catalog import default_catalog

    default_catalog().clear()
    udf_mod._DEFAULT = udf_mod.UDFRegistry()
    sess_mod._ACTIVE = None


@pytest.fixture
def session():
    from sparkdq4ml_tpu import TpuSession

    s = TpuSession.builder().app_name("test").master("local[*]").get_or_create()
    yield s
    s.stop()


def assert_devices(n: int = 8):
    assert len(jax.devices()) >= n, (
        f"test harness expected >= {n} fake CPU devices, got {jax.devices()}")


def run_dq_pipeline(session, path):
    """The reference app's DQ phase (`DataQuality4MachineLearningApp.java:46-95`),
    via the same call sequence: UDF registration, CSV load, rename, rule 1,
    SQL filter, rule 2, SQL filter."""
    import sparkdq4ml_tpu as dq

    dq.register_builtin_rules()
    df = (session.read.format("csv")
          .option("inferSchema", "true").option("header", "false")
          .load(path))
    df = df.with_column_renamed("_c0", "guest")
    df = df.with_column_renamed("_c1", "price")
    df = df.with_column("price_no_min", dq.call_udf("minimumPriceRule", dq.col("price")))
    df.create_or_replace_temp_view("price")
    df = session.sql("SELECT cast(guest as int) guest, price_no_min AS price "
                     "FROM price WHERE price_no_min > 0")
    df = df.with_column("price_correct_correl",
                        dq.call_udf("priceCorrelationRule", dq.col("price"), dq.col("guest")))
    df.create_or_replace_temp_view("price")
    df = session.sql("SELECT guest, price_correct_correl AS price "
                     "FROM price WHERE price_correct_correl > 0")
    return df


def prepare_features(df):
    """Label column + VectorAssembler (`App.java:101-113`)."""
    from sparkdq4ml_tpu.models import VectorAssembler

    df = df.with_column("label", df.col("price"))
    return VectorAssembler(["guest"], "features").transform(df)
