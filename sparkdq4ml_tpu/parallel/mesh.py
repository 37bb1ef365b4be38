"""Device mesh construction — the cluster-runtime init analogue.

Spark's ``master("local[*]")`` (`DataQuality4MachineLearningApp.java:40`)
spins up one in-process executor with task parallelism = host cores. The TPU
equivalent (SURVEY.md §3.1) is device discovery + a 1-D ``jax.sharding.Mesh``
over the chips; the data axis is named ``"data"`` because row-sharded data
parallelism is the reference stack's only parallelism strategy (SURVEY.md §5
"Parallelism strategies" — the model is two scalars; TP/PP/SP have nothing to
act on and are deliberately not invented).

Multi-host: ``jax.devices()`` already enumerates the global device set under
``jax.distributed``; the same 1-D mesh then spans hosts, and the psum in the
fit path rides ICI within a slice and DCN across slices — no framework code
changes (that is the point of SPMD).
"""

from __future__ import annotations

import contextlib
import functools
import re
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"

# ---------------------------------------------------------------------------
# Collective-dispatch serialization
# ---------------------------------------------------------------------------

#: Process-wide guard for executing multi-device collective programs.
#: XLA:CPU's intra-process collectives rendezvous participant threads per
#: (device set, op); when two executions of psum-bearing programs overlap
#: — exactly what a concurrent serving workload produces — the
#: participant threads of the two runs interleave and BOTH rendezvous
#: wait forever (observed live under 32 concurrent packed Lasso fits:
#: "This thread has been waiting for 5000ms ... waiting for all
#: participants"). Serializing dispatch-to-completion of multi-device
#: programs is the correctness fix; single-device programs (the common
#: serving hot path) never take the lock. RLock: a guarded program may be
#: invoked from inside another guarded region on the same thread (e.g. a
#: fallback rung re-dispatching).
_COLLECTIVE_LOCK = threading.RLock()


def _multi_device(mesh) -> bool:
    return mesh is not None and getattr(mesh, "devices", None) is not None \
        and mesh.devices.size > 1


@contextlib.contextmanager
def collective_guard(mesh=None):
    """Hold the process-wide collective lock while a multi-device program
    runs (no-op for ``None``/single-device meshes). Callers must keep the
    device work INSIDE the guard — jax dispatch is async, so block on the
    result before leaving the block (``serialize_collectives`` does both
    for jitted callables)."""
    if not _multi_device(mesh):
        yield
        return
    with _COLLECTIVE_LOCK:
        yield


def serialize_collectives(fn, mesh):
    """Wrap a jitted multi-device program so every call holds the
    collective lock for dispatch AND completion (``block_until_ready``
    inside the lock — releasing with the collective still in flight
    would re-create the interleave). Identity when the mesh is ``None``
    or single-device, so the wrapper costs nothing on the common path;
    under ``jax.jit`` tracing the block is a no-op on tracers and the
    lock is only held for the trace."""
    if not _multi_device(mesh):
        return fn

    @functools.wraps(fn)
    def locked(*args, **kwargs):
        with _COLLECTIVE_LOCK:
            return jax.block_until_ready(fn(*args, **kwargs))
    return locked


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` plus the build counter — every sharded program
    in the framework routes through here."""
    from ..utils.profiling import counters

    # One sharded-program BUILD (trace-time, not per-dispatch): the
    # collective-shape signal the observability layer surfaces as
    # ``parallel.shard_map_builds``.
    counters.increment("parallel.shard_map_builds")
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def parse_master(master: Optional[str]) -> Optional[int]:
    """Spark master string → device count (None = all available).

    ``local[*]``/``local``/``tpu``/None → all devices; ``local[N]`` → N.
    """
    if master is None:
        return None
    m = master.strip().lower()
    if m in ("local", "local[*]", "tpu", "tpu[*]", "*", "pod", "pod[*]"):
        return None
    match = re.fullmatch(r"(?:local|tpu|pod)\[(\d+)\]", m)
    if match:
        return int(match.group(1))
    raise ValueError(f"unsupported master string {master!r}")


def make_mesh(num_devices: Optional[int] = None,
              devices: Optional[Sequence] = None,
              axis_name: str = DATA_AXIS) -> Mesh:
    """Build a 1-D data-parallel mesh over the first ``num_devices`` devices."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices, only {len(devices)} present")
        devices = devices[:num_devices]
    from ..utils.observability import METRICS

    METRICS.set_gauge("mesh.devices", len(devices))
    return Mesh(np.asarray(devices), (axis_name,))


def normalize_mesh(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """Treat a trivial (≤1-device) mesh as no mesh — the shared guard every
    ``fit(frame, mesh=...)`` entry point applies before building a sharded
    program."""
    return None if mesh is None or mesh.devices.size <= 1 else mesh


def data_sharding(mesh: Mesh, axis_name: str = DATA_AXIS) -> NamedSharding:
    """Rows sharded over the data axis (leading-dim sharding)."""
    return NamedSharding(mesh, PartitionSpec(axis_name))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
