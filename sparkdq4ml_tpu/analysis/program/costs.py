"""AOT cost-analysis extraction — the device-cost observatory's sensor.

The audit tier (``detectors.py``) abstract-evaluates cached programs and
bounds their MEMORY; this module asks the compiler what each program
COSTS: ``jax.jit(trace_body).lower(*example).compile()`` produces an XLA
executable whose ``cost_analysis()`` reports FLOPs, transcendentals, and
bytes accessed, and whose ``memory_analysis()`` reports the generated
code's argument/output/temp footprint — the utilization lens of "Large
Scale Distributed Linear Algebra With TPUs" (arxiv 2112.09017), and the
profile ROADMAP item 1's EQuARX headroom note requires before a
quantized all-reduce can be justified.

Contract (mirrors the audit tier's):

* **zero device execution** — the program is lowered and compiled, never
  dispatched; nothing allocates on device, nothing runs;
* **zero counted host syncs** — no ``device_get``, no ``.item()``;
* **zero counted compiles** — extraction targets the producer's
  UN-counted ``trace_body`` (the ``ProgramHandle`` contract), so
  ``pipeline.compile``/``grouped.compile`` and the per-plan replay
  verdicts never move (test-pinned). The XLA compile is real host work —
  which is why extraction runs lazily on cold surfaces only and the
  result is cached per structural key (``utils/costprof.py``) and
  persisted into the statstore.

Collective traffic is accounted from the abstract trace, not the
executable (XLA:CPU's cost model does not itemize collectives): each
collective eqn's per-device operand bytes × the mesh device count = the
aggregate payload entering that collective across the mesh. A static
figure by construction — the shapes are in the jaxpr.

CPU-sandbox honesty: the FLOP/byte counts are the compiler's static
accounting and are chip-independent; *achieved* GFLOP/s / GB/s derived
from them (``utils/costprof.py``) divide by measured wall-clock, which
on the CPU sandbox reflects host dispatch, so those numbers are
structural there and meaningful on TPU captures.
"""

from __future__ import annotations

import time
from typing import Optional

from . import jaxpr_tools as JT

__all__ = ["extract", "collective_bytes"]

def _mesh_devices(handle) -> int:
    mesh = getattr(handle, "mesh", None)
    size = getattr(getattr(mesh, "devices", None), "size", None)
    return int(size) if size else 1


def collective_bytes(handle, closed=None) -> dict:
    """``{collective: aggregate_bytes}`` over the program's collective
    eqns — per-device operand bytes × mesh size, from the abstract trace
    (zero compiles beyond the caller's, zero device work)."""
    if closed is None:
        closed = JT.trace(handle.fn, handle.args, handle.kwargs)
    devices = _mesh_devices(handle)
    out: dict = {}
    for eqn in JT.iter_eqns(closed):
        name = JT.COLLECTIVE_FAMILY.get(eqn.primitive.name)
        if name is None:
            continue
        nb = sum(JT._nbytes(getattr(v, "aval", None))
                 for v in eqn.invars if not hasattr(v, "val"))
        out[name] = out.get(name, 0) + nb * devices
    return out


def extract(handle) -> Optional[dict]:
    """AOT-extract one cached program's cost profile; returns the raw
    document ``utils/costprof.CostProfile`` consumes, or None when the
    backend exposes no cost model. Raises on lowering/compile failure —
    the caller (``costprof._extract``) owns the degradation ladder."""
    import jax

    t0 = time.perf_counter()
    fn = handle.fn
    if handle.kwargs:
        kwargs = dict(handle.kwargs)

        def fn(*a, _inner=handle.fn, _kw=kwargs):
            return _inner(*a, **_kw)

    lowered = jax.jit(fn).lower(*handle.args)
    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    doc = {
        "flops": float(ca.get("flops", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "output_bytes": float(ca.get("bytes accessedout{}", 0.0)),
        "devices": _mesh_devices(handle),
    }
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        try:
            doc["argument_bytes"] = int(ma.argument_size_in_bytes)
            # the generated code's resident footprint past its inputs:
            # temps + outputs + the executable itself
            doc["peak_bytes"] = int(ma.temp_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.generated_code_size_in_bytes)
        except Exception:
            pass
    try:
        colls = collective_bytes(handle)
    except Exception:
        colls = {}
    if colls:
        doc["collectives"] = {k: int(v) for k, v in sorted(colls.items())}
    doc["extract_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
    return doc
