"""Multi-host bootstrap (``master("pod")``): config plumbing into
``jax.distributed.initialize`` (mocked), and a real 2-process CPU
integration run with a local coordinator asserting the mesh spans both
processes — the closest one-machine analogue of a TPU pod, mirroring how
the reference gets a multi-executor cluster from one JVM with
``master("local[*]")`` (`DataQuality4MachineLearningApp.java:40`).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from sparkdq4ml_tpu import TpuSession

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class TestPodBootstrapPlumbing:
    """Unit tests of TpuSession._init_distributed with a recording stub."""

    @pytest.fixture
    def record(self, monkeypatch):
        import jax

        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: calls.append(kw))
        return calls

    def test_local_master_does_not_initialize(self, record):
        s = TpuSession(master="local[2]")
        assert record == []
        s.stop()

    def test_pod_master_auto_bootstrap(self, record):
        # bare pod: coordinator/ranks come from the TPU metadata (no kwargs)
        s = TpuSession(master="pod")
        assert record == [{}]
        s.stop()

    def test_explicit_coordinator_conf_plumbed(self, record):
        s = TpuSession(master="pod", conf={
            "spark.distributed.coordinator": "10.0.0.1:8476",
            "spark.distributed.numProcesses": "4",
            "spark.distributed.processId": "2",
        })
        assert record == [{
            "coordinator_address": "10.0.0.1:8476",
            "num_processes": 4,
            "process_id": 2,
        }]
        s.stop()

    def test_coordinator_conf_without_pod_master_initializes(self, record):
        s = TpuSession(master="local[*]", conf={
            "spark.distributed.coordinator": "10.0.0.1:8476",
            "spark.distributed.numProcesses": "2",
            "spark.distributed.processId": "0",
        })
        assert len(record) == 1
        assert record[0]["coordinator_address"] == "10.0.0.1:8476"
        s.stop()

    def test_idempotent_when_client_exists(self, record, monkeypatch):
        from jax._src import distributed as _dist

        monkeypatch.setattr(_dist.global_state, "client", object(),
                            raising=False)
        s = TpuSession(master="pod")
        assert record == []
        s.stop()


_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, "@REPO@")
    import jax
    jax.config.update("jax_platforms", "cpu")
    from sparkdq4ml_tpu import TpuSession

    pid = int(sys.argv[1])
    s = (TpuSession.builder().app_name("podtest").master("pod")
         .config("spark.distributed.coordinator", "127.0.0.1:@PORT@")
         .config("spark.distributed.numProcesses", "2")
         .config("spark.distributed.processId", str(pid))
         .get_or_create())
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 2 * jax.local_device_count()
    assert s.mesh.devices.size == len(jax.devices())

    # the mesh spans both processes: a global psum over the pod mesh
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from sparkdq4ml_tpu.parallel.mesh import DATA_AXIS

    n_local = jax.local_device_count()
    total = len(jax.devices())
    local = np.full((n_local,), float(pid + 1), np.float32)
    garr = jax.make_array_from_single_device_arrays(
        (total,), NamedSharding(s.mesh, P(DATA_AXIS)),
        [jax.device_put(local[i:i+1], d)
         for i, d in enumerate(jax.local_devices())])
    tot = jax.jit(lambda x: jnp.sum(x))(garr)
    # process 0 contributes 1.0 per local device, process 1 contributes 2.0
    expect = 3.0 * n_local
    assert float(tot) == expect, (float(tot), expect)
    print(f"proc {pid} ok: devices={total} sum={float(tot)}")
""")


@pytest.mark.slow
def test_two_process_cpu_pod():
    """Real jax.distributed over two CPU processes and one coordinator."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)              # 1 local CPU device per process
    script = _WORKER.replace("@REPO@", REPO).replace("@PORT@", str(port))
    procs = [subprocess.Popen([sys.executable, "-c", script, str(i)],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for i in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert f"proc {i} ok" in out
