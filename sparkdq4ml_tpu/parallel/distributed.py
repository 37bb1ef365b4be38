"""Distributed statistics: row sharding + ICI collectives.

This is the ``treeAggregate``-over-netty replacement (SURVEY.md §3.3, §5
"Distributed communication backend"): rows are sharded over the mesh's
``data`` axis; each device computes its local augmented Gramian with one
masked matmul; ``jax.lax.psum`` reduces over ICI. Coefficient "broadcast" is
implicit in SPMD replication — the solver then runs identically on every
device on the replicated statistics, so there is no driver↔executor boundary
at all (zero host syncs per iteration vs. Spark's two).

Padding: row counts rarely divide the mesh size; rows are padded with
``mask=False`` slots, which the mask-weighted statistics ignore by
construction — the same mechanism that makes DQ filtering static-shaped
(SURVEY.md §7 "Masked-filter semantics").
"""

from __future__ import annotations

import functools
import logging
import threading
from collections import namedtuple
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.solvers import augmented_gram
from ..ops.segments import abstract_specs
from ..utils import observability as _obs
from .mesh import DATA_AXIS, serialize_collectives, shard_map

logger = logging.getLogger("sparkdq4ml_tpu.distributed")


# ---------------------------------------------------------------------------
# Enumerable jit-factory memo (the lru_cache replacement)
# ---------------------------------------------------------------------------

_CacheInfo = namedtuple("CacheInfo", ("hits", "misses", "maxsize",
                                      "currsize"))


class _RecordedProgram:
    """One memoized factory product: the guarded dispatch entry plus the
    raw trace body and the abstract example calling convention recorded
    on first execution. ``functools.lru_cache`` could report stats but
    never LIST its entries — which left the packed sharded fits with no
    re-trace surface for the program auditor (``observability.
    ProgramHandle``); this wrapper is that surface."""

    __slots__ = ("dispatch", "trace_body", "jit_fn", "mesh", "examples")

    def __init__(self, dispatch, trace_body, jit_fn, mesh):
        self.dispatch = dispatch
        self.trace_body = trace_body
        self.jit_fn = jit_fn
        self.mesh = mesh
        # entry name -> example: a fit program has two entries, the
        # frame's columns and a packed ``Z`` (see DesignColumns)
        self.examples: dict = {}

    def __call__(self, *args):
        # One dict probe per dispatch on the steady path — this wrapper
        # sits on the dispatch-lean fit hot loop, so recording happens
        # once an entry (shape/dtype metadata, no device read).
        entry = "columns" if isinstance(args[0], DesignColumns) else "packed"
        if entry not in self.examples:
            self.examples[entry] = abstract_specs(args)
        return self.dispatch(*args)


class _EnumerableFactory:
    """Memoizing decorator for the jit factories with the
    ``cache_info()``/``cache_clear()`` surface of ``functools.lru_cache``
    (the observability trace-probe and the tests use both) PLUS
    entry enumeration — ``entries()`` yields ``(key, product)`` pairs so
    the program auditor can re-trace every cached fit program without a
    private import. Builds serialize on one lock (factory builds are
    rare trace-time events; a double-build would strand replay stats)."""

    def __init__(self, builder):
        self._builder = builder
        self._entries: dict = {}
        self._hits = 0
        self._misses = 0
        self._lock = threading.Lock()
        functools.update_wrapper(self, builder)

    def __call__(self, *key):
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._hits += 1
                return hit
            self._misses += 1
            product = self._builder(*key)
            self._entries[key] = product
            return product

    def entries(self) -> list:
        with self._lock:
            return list(self._entries.items())

    def cache_info(self) -> _CacheInfo:
        with self._lock:
            return _CacheInfo(self._hits, self._misses, None,
                              len(self._entries))

    def cache_clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._hits = 0
            self._misses = 0


def pad_rows(X: np.ndarray, y: np.ndarray, mask: np.ndarray, multiple: int):
    """Pad the row dimension to a multiple of the shard count (mask=False)."""
    n = X.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return X, y, mask
    Xp = np.concatenate([X, np.zeros((rem, X.shape[1]), X.dtype)])
    yp = np.concatenate([y, np.zeros((rem,), y.dtype)])
    mp = np.concatenate([mask, np.zeros((rem,), bool)])
    return Xp, yp, mp


@jax.jit
def _gram_single(X, y, mask):
    return augmented_gram(X, y, mask)


@_EnumerableFactory
def _gram_sharded_fn(mesh: Mesh):
    """Build (once per mesh) the jitted sharded Gramian: local matmul + psum."""

    def local(X, y, mask):
        return jax.lax.psum(augmented_gram(X, y, mask), DATA_AXIS)

    sharded = shard_map(
        local, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P())
    jitted = jax.jit(sharded)
    return _RecordedProgram(serialize_collectives(jitted, mesh), sharded,
                            jitted, mesh)


def _resolve_solve_A(solver: str, max_iter: int, tol: float,
                     fit_intercept: bool, standardization: bool):
    """Solver-loop factory on the augmented Gramian ``A`` (shared by the
    packed and unpacked fused fit paths)."""
    from ..models.owlqn import owlqn_solve
    from ..models.solvers import fista_solve, normal_solve

    if solver == "normal":
        def solve_A(A, reg, alpha):
            return normal_solve(A, reg, alpha, fit_intercept=fit_intercept,
                                standardization=standardization)
    elif solver == "owlqn":
        def solve_A(A, reg, alpha):
            return owlqn_solve(A, reg, alpha, max_iter=max_iter, tol=tol,
                               fit_intercept=fit_intercept,
                               standardization=standardization)
    else:
        def solve_A(A, reg, alpha):
            return fista_solve(A, reg, alpha, max_iter=max_iter, tol=tol,
                               fit_intercept=fit_intercept,
                               standardization=standardization)
    return solve_A


class DesignColumns(NamedTuple):
    """What a fit reads of a frame, as ``_extract_xy`` returned it: the
    **columns entry** of the compiled fits. ``X`` ``(n, d)`` and ``y``
    ``(n,)`` in ``float_dtype()``, ``mask`` the frame's boolean mask,
    ``w`` the raw weight column or ``None``. A pytree, so a fit program
    sees which entry it was called through — these columns, or a packed
    ``Z`` (:func:`pack_design`) — from the form of its first argument."""

    X: jax.Array
    y: jax.Array
    mask: jax.Array
    w: Optional[jax.Array] = None


def row_scale(mask, w):
    """The per-row scale of a linear fit's design: the mask, and
    ``sqrt(w)`` where the fit is weighted (``ZᵀZ = Σ w·zzᵀ``; a masked
    slot's weight is never read: ``where`` first)."""
    if w is None:
        return mask
    return jnp.sqrt(jnp.where(mask, w, 0))


def _pack(xp, X, y, scale, last):
    """``[X, y, last]`` with row ``i`` scaled by ``scale[i]``; a row whose
    scale is 0 is written as zeros whatever it holds (NaN included)."""
    X = xp.asarray(X)
    if X.ndim == 1:
        X = X[:, None]
    y = xp.asarray(y, X.dtype)
    scale = xp.asarray(scale, X.dtype)[:, None]
    last = xp.ones_like(y) if last is None else xp.asarray(last, X.dtype)
    Z = xp.concatenate([X, y[:, None], last[:, None]], axis=1)
    return xp.where(scale != 0, Z, 0) * scale


_pack_program = jax.jit(functools.partial(_pack, jnp))


def _pack_eager(X, y, scale, last=None):
    from ..utils.profiling import counters

    counters.increment("fit.pack_eager")
    if any(isinstance(a, jax.Array) for a in (X, y, scale, last)):
        return _pack_program(X, y, scale, last)
    return _pack(np, X, y, scale, last)


def pack_design(X, y, mask):
    """Pack ``Z = [X, y, 1]·mask`` into ONE array: the **packed entry** of
    the compiled fits, for callers that hold a design matrix of their own
    (``chip_smoke.py``, the graft entry) and for the sharded path, which
    places ``Z`` row-sharded with one ``device_put``
    (:func:`place_packed`). A fit on one device does not come here: it
    hands the frame's columns to the program (:class:`DesignColumns`),
    which then never writes ``Z`` (ISSUE 29: the eager pack was 21 ms of
    a 93 ms ``catering_dq_lasso`` job on the chip, PERF.md section 6).

    The masked augmented Gramian only ever consumes ``Z`` (``A = ZᵀZ``,
    solvers.augmented_gram): the mask column *is* the masked ones-column,
    and all-zero rows contribute nothing to ``ZᵀZ``. ``mask`` may carry a
    per-row scale instead of 0/1 (the weighted linear fit passes
    ``sqrt(w)``).

    Device arrays are packed by ONE compiled program (one launch, ``Z``
    written once, no device→host read); host arrays in numpy. Counted as
    ``fit.pack_eager``.
    """
    return _pack_eager(X, y, mask)


def pack_design_weighted(X, y, mask, w):
    """Packed design for WEIGHTED fits: ``Z = [X·m, y·m, w·m]`` — the mask
    zeroes invalid rows (boolean, exactly like :func:`pack_design`) while
    the last column carries the real instance weights, so one buffer still
    ships everything the weighted logistic/softmax cores consume
    (``classification._unpack_zw``). One compiled program on device
    arrays, like :func:`pack_design`."""
    return _pack_eager(X, y, mask, w)


def place_packed(Z, mesh: Optional[Mesh]):
    """Pad packed rows to the shard count and device_put row-sharded.
    Zero padding rows are mask=0 rows by construction (see pack_design)."""
    if mesh is None or mesh.devices.size <= 1:
        return jnp.asarray(Z)
    xp = jnp if isinstance(Z, jax.Array) else np  # never read device→host
    Z = xp.asarray(Z)
    rem = (-Z.shape[0]) % mesh.devices.size
    if rem:
        Z = xp.concatenate([Z, xp.zeros((rem, Z.shape[1]), Z.dtype)])
    return jax.device_put(Z, NamedSharding(mesh, P(DATA_AXIS)))


@_EnumerableFactory
def fused_linear_fit_packed(mesh: Optional[Mesh], solver: str, max_iter: int,
                            tol: float, fit_intercept: bool,
                            standardization: bool):
    """The compiled linear fit — the hot path ``LinearRegression.fit``
    uses: one data pass (``A = ZᵀZ`` on the MXU, ``psum`` over
    ICI when sharded), then the solver loop on the replicated moments.

    Signature: ``fit(design, hyper) -> flat``. ``design`` is one of two
    entries, told apart by its form (a pytree the program sees; no option):

    * :class:`DesignColumns` — the frame's columns as ``_extract_xy``
      returned them (one device only). The program masks, scales and
      augments them under the scope ``dq.fit.pack`` as part of the
      Gramian's read; no ``(n, d+2)`` array is an argument of it.
    * a packed ``Z = pack_design(X, y, mask)`` (row-sharded over the mesh
      when there is one), for callers that hold one.

    Both run the same Gramian and the same solver. ``hyper = [regParam,
    elasticNetParam]`` as a device array, and ``flat`` is one buffer:
    ``[coef(d) | intercept | iterations | converged | objective_history]``
    (decode with :func:`unpack_fit_result`).
    """
    solve_A = _resolve_solve_A(solver, max_iter, tol, fit_intercept,
                               standardization)

    if mesh is None or mesh.devices.size <= 1:
        def gram(design):
            if isinstance(design, DesignColumns):
                # [X, y, 1] scaled row by row: a producer of the
                # contraction, which XLA fuses into it — Z is not written
                with _obs.scope("fit.pack"):
                    X, y, mask, w = design
                    design = _pack(jnp, X, y, row_scale(mask, w), None)
            return design.T @ design
    else:
        gram = shard_map(
            lambda Zs: jax.lax.psum(Zs.T @ Zs, DATA_AXIS),
            mesh=mesh, in_specs=(P(DATA_AXIS),), out_specs=P())

    def fit(design, hyper):
        # the two layers of the program, named in its op metadata: the
        # one data pass, then the solver on the (d+2)^2 moments
        with _obs.scope("fit.gram"):
            A = gram(design)
        with _obs.scope("fit.solve"):
            r = solve_A(A, hyper[0], hyper[1])
            dt = r.coefficients.dtype
            scalars = jnp.stack([r.intercept.astype(dt),
                                 r.iterations.astype(dt),
                                 r.converged.astype(dt)])
            return jnp.concatenate(
                [r.coefficients, scalars, r.objective_history.astype(dt)])

    # Multi-device programs serialize dispatch-to-completion on the
    # process-wide collective guard (mesh.serialize_collectives): two
    # overlapping psum executions interleave their participant threads on
    # XLA:CPU and deadlock — the exact workload a concurrent QueryServer
    # produces. Identity wrapper (zero cost) off-mesh.
    jitted = jax.jit(fit)
    return _RecordedProgram(serialize_collectives(jitted, mesh), fit,
                            jitted, mesh)


def _factory_program_key(name: str, key: tuple) -> str:
    """Stable program key for one factory entry: factory name + the memo
    key with the mesh summarized structurally (axis names + sizes, not
    device object reprs)."""
    parts = []
    for k in key:
        if isinstance(k, Mesh):
            axes = ",".join(f"{a}:{n}" for a, n in
                            zip(k.axis_names, k.devices.shape))
            parts.append(f"mesh({axes})")
        else:
            parts.append(repr(k))
    return f"{name}({', '.join(parts)})"


def fit_factory_cache_stats() -> dict:
    """Registry callback (observability.CACHES): memo introspection of
    the packed/sharded jit factories — the fit-path entries of
    ``session.cache_report()``. ``hits`` are factory replays (no new
    trace+compile); ``misses`` are cold builds."""
    out: dict = {"kind": "memoized jit factories (fused linear fit)"}
    for name, factory in (("fused_linear_fit_packed",
                           fused_linear_fit_packed),
                          ("gram_sharded", _gram_sharded_fn)):
        try:
            info = factory.cache_info()
            out[name] = {"size": info.currsize, "hits": info.hits,
                         "misses": info.misses,
                         "entries": [
                             {"program_key": _factory_program_key(name, k),
                              "called_through": sorted(rec.examples)}
                             for k, rec in factory.entries()]}
        except Exception as e:
            out[name] = {"error": str(e)}
    return out


def _fit_handle(name, key, rec, entry, example):
    """The handle of one entry (``packed`` or ``columns``) of one cached
    fit program, re-traceable at the calling convention it ran with."""
    # Scale only the ROW-indexed inputs (the widest leading dim
    # = the shared row count): hyperparameter vectors and other
    # small fixed-shape args keep their calling convention.
    # Two factors (x2/x4) give the retrace detector a pair of
    # FRESH traces — jax may serve the recorded shape from a
    # trace cache predating a config flip (the float dtype).
    leaves = [s for s in jax.tree_util.tree_leaves(example)
              if hasattr(s, "shape") and s.shape]
    rows = max((s.shape[0] for s in leaves), default=0)

    def scaled(factor):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                (s.shape[0] * factor,) + tuple(s.shape[1:]), s.dtype)
            if hasattr(s, "shape") and s.shape
            and s.shape[0] == rows else s, example)
    # NO expected/observed trace accounting here: the jit entry
    # legitimately retraces on input SHARDING layout (row-sharded
    # vs replicated placements of the same shapes — exactly what
    # the resilience fallback rungs produce), which the
    # shape-signature recorder cannot observe. The retrace
    # detector's variant re-trace still covers shape stability.
    program_key = _factory_program_key(name, key)
    if entry != "packed":
        program_key += f"[{entry}]"
    return _obs.ProgramHandle(
        "fit.factories", program_key, rec.trace_body, args=example,
        variants={"bucket": [(scaled(2), {}), (scaled(4), {})]},
        mesh=rec.mesh, guarded=True, meta={})


def fit_program_handles() -> list:
    """Registry callback (CACHES.register_programs): one traceable
    handle per cached fit program and entry that has executed (the packed
    ``Z`` entry under the factory's key, the columns entry under
    ``<key>[columns]``).
    ``guarded=True`` by construction — every product of these factories
    routes dispatch through ``mesh.serialize_collectives`` — so the
    collective-topology detector can cross-check the jaxpr's collectives
    against the mesh AND the guard wrapping in one place."""

    out = []
    for name, factory in (("fused_linear_fit_packed",
                           fused_linear_fit_packed),
                          ("gram_sharded", _gram_sharded_fn)):
        for key, rec in factory.entries():
            for entry, example in list(rec.examples.items()):
                out.append(_fit_handle(name, key, rec, entry, example))
    return out


def _register_cache_stats() -> None:

    _obs.CACHES.register("fit.factories", fit_factory_cache_stats)
    _obs.CACHES.register_programs("fit.factories", fit_program_handles)


_register_cache_stats()


def unpack_fit_result(flat, d: int):
    """Decode the packed fit output (host side) into a ``FitResult``: the
    one blocking read of the fit's result, counted as a host read."""
    from ..models.solvers import FitResult
    with _obs.host_reading("fit.result") as rd:
        flat = np.asarray(flat)
        rd.done(flat.nbytes)
    return FitResult(
        coefficients=flat[:d],
        intercept=flat[d],
        iterations=np.int32(flat[d + 1]),
        objective_history=flat[d + 3:],
        converged=bool(flat[d + 2]))


def _pre_sharded(a, mesh) -> bool:
    """True when ``a`` is a jax array ALREADY row-sharded over exactly
    ``mesh``'s device list — the sharded-frames fast path (ROADMAP item
    1 end-to-end leg): fit packing then consumes the frame's shard
    partials directly instead of gathering to host and re-sharding."""
    sh = getattr(a, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return False
    spec = tuple(sh.spec)
    if not spec or spec[0] != DATA_AXIS \
            or any(s is not None for s in spec[1:]):
        return False
    try:
        return [d.id for d in sh.mesh.devices.flat] \
            == [d.id for d in mesh.devices.flat]
    except Exception:
        return False


def pad_and_shard_rows(mesh: Optional[Mesh], *arrays):
    """Zero-pad every array's leading axis to the shard count and
    device_put them row-sharded; with no (or a trivial) mesh, pass through
    as plain device arrays. The generic variadic variant of
    ``place_sharded``, shared by the GLM/clustering fits — zero padding
    rows carry zero weight by construction in every masked statistic.

    Arrays that arrive ALREADY row-sharded over this mesh at a divisible
    row count (a sharded frame's columns) pass through untouched — no
    host gather, no re-placement."""
    if mesh is None or mesh.devices.size <= 1:
        return tuple(jnp.asarray(a) for a in arrays)
    if arrays[0].shape[0] % mesh.devices.size == 0 and \
            all(_pre_sharded(a, mesh) for a in arrays):
        from ..utils.profiling import counters

        counters.increment("shard.fit_passthrough")
        return tuple(arrays)
    rem = (-arrays[0].shape[0]) % mesh.devices.size
    shard = NamedSharding(mesh, P(DATA_AXIS))
    out = []
    for a in arrays:
        a = np.asarray(a)
        if rem:
            a = np.concatenate(
                [a, np.zeros((rem,) + a.shape[1:], a.dtype)])
        out.append(jax.device_put(a, shard))
    return tuple(out)


def place_sharded(X, y, mask, mesh: Optional[Mesh]):
    """Pad rows to the shard count and device_put with row sharding.
    Single-device/no-mesh inputs pass through as device arrays; inputs
    already row-sharded over this mesh (a sharded frame's columns) pass
    through without the host round trip."""
    if mesh is None or mesh.devices.size <= 1:
        return (jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask, jnp.bool_))
    if X.shape[0] % mesh.devices.size == 0 and \
            all(_pre_sharded(a, mesh) for a in (X, y, mask)):
        from ..utils.profiling import counters

        counters.increment("shard.fit_passthrough")
        return X, y, mask
    Xh, yh, mh = pad_rows(np.asarray(X), np.asarray(y), np.asarray(mask, bool),
                          mesh.devices.size)
    shard = NamedSharding(mesh, P(DATA_AXIS))
    return (jax.device_put(Xh, shard), jax.device_put(yh, shard),
            jax.device_put(mh, shard))


def _gram_single_cpu(Xh, yh, mh):
    """Single-device Gramian pinned to the host CPU backend — the last
    rung of the sharded-Gramian fallback ladder: when the mesh path is
    failing (lost device, stuck collective), the statistics still compute,
    just slower. Falls back to the default device when this process has
    no CPU backend (should not happen; jax always registers one)."""
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        return _gram_single(jnp.asarray(Xh), jnp.asarray(yh),
                            jnp.asarray(mh, jnp.bool_))
    with jax.default_device(cpu):
        return _gram_single(jax.device_put(Xh, cpu), jax.device_put(yh, cpu),
                            jax.device_put(np.asarray(mh, bool), cpu))


def compute_gram(X, y, mask, mesh: Optional[Mesh] = None):
    """Augmented Gramian ``A``, sharded over ``mesh`` when it has >1 device.

    Accepts host or device arrays; on the sharded path, inputs are placed with
    a row-sharded ``NamedSharding`` so each device holds only its shard (HBM
    never sees the replicated matrix).

    The sharded path runs under the resilience policy
    (``utils.recovery.resilient_call``): a device error — real
    ``XlaRuntimeError`` or one injected at the ``gram_sharded`` fault
    site — retries with backoff, trips the ``gram_sharded`` circuit
    breaker, and ultimately falls back to the single-device CPU Gramian
    with a logged warning instead of aborting the fit. Identical
    statistics either way (the psum and the single matmul compute the
    same ``A``); only throughput degrades.
    """
    if mesh is None or mesh.devices.size <= 1:
        return _gram_single(jnp.asarray(X), jnp.asarray(y),
                            jnp.asarray(mask, jnp.bool_))
    from ..utils import faults as _faults
    from ..utils import recovery as _recovery
    from ..utils.profiling import counters

    nshards = mesh.devices.size
    # Sharded-frame fast path: inputs already row-sharded over THIS mesh
    # consume the frame's shard partials directly — no host gather, no
    # re-placement (padded slots are mask=False rows, zero weight in A).
    pre = (getattr(X, "shape", (1,))[0] % nshards == 0
           and all(_pre_sharded(a, mesh) for a in (X, y, mask)))
    if pre:
        counters.increment("shard.fit_passthrough")
        Xp, yp, mp = X, y, mask
    else:
        Xp, yp, mp = pad_rows(np.asarray(X), np.asarray(y),
                              np.asarray(mask, bool), nshards)
    shard = NamedSharding(mesh, P(DATA_AXIS))

    def sharded():
        _faults.inject("gram_sharded")
        counters.increment("parallel.psum_dispatches")
        # Per-shard Gramian timing: with the tracer switched ON the span
        # blocks on the result so the duration covers the actual
        # collective, not just the async enqueue — an explicit-flag-only
        # sync, per the observability cost contract (off, and recording
        # for a profiler, add no device wait).
        with _obs.span("parallel.gram_shard", cat="parallel",
                       shards=nshards, rows=int(Xp.shape[0]),
                       rows_per_shard=int(Xp.shape[0]) // nshards,
                       device=mesh.devices.flat[0].platform) as s:
            Xd = Xp if pre else jax.device_put(Xp, shard)
            yd = yp if pre else jax.device_put(yp, shard)
            md = mp if pre else jax.device_put(mp, shard)
            A = _gram_sharded_fn(mesh)(Xd, yd, md)
            if _obs.TRACER.enabled:
                # the explicit flag only: a span recorded because a
                # profiler is on never adds a device wait
                jax.block_until_ready(A)
            return A

    def single_cpu():
        logger.warning(
            "sharded Gramian failed on %d devices; falling back to the "
            "single-device CPU path", nshards)
        # fault-path host pull: the ladder's last rung computes on host
        # CPU whatever the mesh state is
        return _gram_single_cpu(np.asarray(Xp), np.asarray(yp),
                                np.asarray(mp, bool))

    mark = _obs.recovery_mark()
    # np.shape reads metadata only — never a device pull
    n_rows, n_feats = (int(s) for s in np.shape(X)[:2])
    with _obs.span("parallel.gram", cat="parallel", shards=nshards,
                   rows=n_rows, features=n_feats) as s:
        A = _recovery.resilient_call(
            sharded, site="gram_sharded",
            policy=_recovery.active_policy("gram_sharded"),
            fallbacks=[("single_cpu", single_cpu)],
            breaker=_recovery.DEVICE_BREAKER)
        _obs.annotate_recovery(s, mark)
        return A
