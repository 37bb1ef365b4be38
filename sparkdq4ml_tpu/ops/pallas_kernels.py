"""Pallas TPU kernels for the framework's hot ops.

Two data-touching operations dominate the pipeline (SURVEY.md §3.2/§3.3):

1. **The masked augmented Gramian** ``A = ZᵀZ, Z = [X, y, 1]·mask`` — the
   single matmul that is the entire data pass of a linear/logistic fit (the
   ``treeAggregate`` analogue; ``models/solvers.py:augmented_gram``). The
   Pallas version tiles rows HBM→VMEM and accumulates the ``(d+2, d+2)``
   block on the MXU across the grid, so arbitrarily many rows stream through
   a fixed VMEM footprint — the XLA path must materialize the masked ``Z``
   in HBM first; here the mask-multiply fuses into the same VMEM pass.

2. **The DQ rule chain** (`MinimumPriceDataQualityService` +
   `PriceCorrelationDataQualityService` + the two SQL filters,
   `DataQuality4MachineLearningApp.java:68-95`) — four elementwise passes in
   the reference (two UDF columns, two WHERE filters), fused here into ONE
   row-tiled VPU pass emitting both rule columns and the combined keep-mask.
   The rule-layer entry point is ``ops/rules.py:dq_rules_fused``, which
   dispatches here when enabled and to the equivalent XLA expression
   otherwise.

Both kernels are optional fast paths selected via ``config.pallas``:
``"on"`` (compiled, TPU), ``"auto"`` (compiled when the backend is TPU),
``"interpret"`` (CPU tests/CI — same kernel code through the Pallas
interpreter), ``"off"`` (default — plain XLA, which already fuses these
well). Dispatch falls back to XLA inside ``shard_map`` or ``vmap`` traces:
Pallas state-discharge has no vma rules, and the pallas_call batching rule
would break the grid-step-0 accumulator init.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..config import config
from .rules import (BAD_ROW_SENTINEL, CORRELATION_MAX_GUESTS,
                    CORRELATION_MAX_PRICE, MIN_PRICE)

# Row-tile height for the Gramian kernel: multiples of the f32 sublane (8);
# 512 rows × up-to-128 padded lanes ≈ 256 KB/input block in VMEM — far under
# the ~16 MB budget, large enough to keep the MXU busy.
BLOCK_ROWS = 512
# Lane-tile width for the Gramian OUTPUT: Mosaic's scoped-VMEM scratch for
# the accumulator scales with the output block (measured ~16× its padded
# bytes on v5e — a full (514, 514) f32 block wants 21 MB against the 16 MB
# stack limit). Tiling the output columns keeps the scratch bounded for any
# d; at d+2 ≤ 128 the grid degenerates to the untiled layout.
BLOCK_COLS = 128
# Row tiles for the elementwise DQ kernel: (DQ_BLOCK_ROWS, 128) f32 blocks,
# 5 buffers live (2 in + 3 out) ≈ 1.3 MB of VMEM.
DQ_BLOCK_ROWS = 512


def use_pallas() -> bool:
    """True when the configured mode selects the Pallas path."""
    mode = getattr(config, "pallas", "off")
    if mode == "on":
        return True
    if mode == "interpret":
        return True
    if mode == "auto":
        return jax.default_backend() == "tpu"
    return False


def _interpret() -> bool:
    return getattr(config, "pallas", "off") == "interpret"


def _unsupported_trace(*operands) -> bool:
    """True when dispatching a Pallas kernel here would be incorrect:

    * inside ``shard_map`` (operands carry varying-mesh-axes; the Pallas
      state-discharge machinery has no vma rules), or
    * inside ``vmap`` (the pallas_call batching rule prepends the batch axis
      to the grid, so ``pl.program_id(0)`` would index the batch, breaking
      the grid-step-0 accumulator init).

    Callers fall back to the identical-semantics XLA expression.
    """
    from jax._src.interpreters import batching

    for op in operands:
        if isinstance(op, batching.BatchTracer):
            return True
        if jax.typeof(op).vma:
            return True
    return False


def dispatch_to_pallas(*operands) -> bool:
    """Single gate used by the XLA-level callers (solvers/rules)."""
    return use_pallas() and not _unsupported_trace(*operands)


# ---------------------------------------------------------------------------
# Masked augmented Gramian
# ---------------------------------------------------------------------------

def _gram_kernel(zl_ref, zr_ref, w_ref, out_ref):
    """One (col-tile, row-tile) step: out[:, j] += (Z·w)ᵀ Z[:, j] — the
    mask-multiply fused into the MXU pass. Row tiles are the INNER grid
    axis, so each output column block accumulates to completion before
    the next is touched."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    zw = zl_ref[:] * w_ref[:]  # broadcast (TILE, 1) mask over lanes
    # Contract the row (sublane) dimension: (TILE, D)ᵀ(TILE, Dt) → (D, Dt).
    out_ref[:] += jax.lax.dot_general(
        zw, zr_ref[:],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=out_ref.dtype,
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _masked_gram_call(Z, w, block_rows: int, interpret: bool):
    n, D = Z.shape
    bc = min(BLOCK_COLS, D)
    grid = (pl.cdiv(D, bc), pl.cdiv(n, block_rows))  # (cols OUTER, rows inner)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda j, i: (i, 0)),
            pl.BlockSpec((block_rows, bc), lambda j, i: (i, j)),
            pl.BlockSpec((block_rows, 1), lambda j, i: (i, 0)),
        ],
        # One output column block per outer step, revisited by every row
        # tile (accumulator); VMEM scratch scales with (D, bc), not (D, D).
        out_specs=pl.BlockSpec((D, bc), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((D, D), Z.dtype),
        interpret=interpret,
    )(Z, Z, w)


def masked_gram_pallas(X: jnp.ndarray, y: jnp.ndarray, mask: jnp.ndarray,
                       block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """Pallas equivalent of ``solvers.augmented_gram`` (same contract).

    ``A = ZᵀZ`` with ``Z = [X, y, 1]·mask``, shape ``(d+2, d+2)``. The mask
    enters once (Z·w against unweighted Z ⇒ ZᵀM Z for boolean M where
    w² = w); row padding added below carries zero weight.
    """
    D = X.shape[1] + 2
    n = X.shape[0]
    if n == 0:
        # A zero-step grid would never run the accumulator init.
        return jnp.zeros((D, D), X.dtype)
    w = mask.astype(X.dtype)
    ones = jnp.ones_like(y)
    Z = jnp.concatenate([X, y[:, None], ones[:, None]], axis=1)
    block = min(block_rows, max(8, -(-n // 8) * 8))
    pad = (-n) % block
    if pad:
        # Out-of-bounds block slots are undefined in Pallas; pad explicitly
        # with zero rows (zero weight ⇒ zero contribution to the Gramian).
        Z = jnp.concatenate([Z, jnp.zeros((pad, Z.shape[1]), Z.dtype)])
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])
    return _masked_gram_call(Z, w[:, None], block, _interpret())


def _packed_gram_kernel(zl_ref, zr_ref, out_ref):
    """One (col-tile, row-tile) step of the pre-masked design:
    out[:, j] += Zᵀ Z[:, j]."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] += jax.lax.dot_general(
        zl_ref[:], zr_ref[:],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=out_ref.dtype,
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _packed_gram_call(Z, block_rows: int, interpret: bool):
    n, D = Z.shape
    bc = min(BLOCK_COLS, D)
    grid = (pl.cdiv(D, bc), pl.cdiv(n, block_rows))
    return pl.pallas_call(
        _packed_gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda j, i: (i, 0)),
            pl.BlockSpec((block_rows, bc), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((D, bc), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((D, D), Z.dtype),
        interpret=interpret,
    )(Z, Z)


def packed_gram_pallas(Z: jnp.ndarray,
                       block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """Gramian of a pre-masked packed design ``Z = [X, y, 1]·mask``
    (``parallel/distributed.py:pack_design``): ``A = ZᵀZ``, rows streamed
    HBM→VMEM through a fixed footprint. Same contract as
    ``masked_gram_pallas`` with the mask-multiply already folded into ``Z``
    — one fewer input buffer."""
    n, D = Z.shape
    if n == 0:
        return jnp.zeros((D, D), Z.dtype)
    block = min(block_rows, max(8, -(-n // 8) * 8))
    pad = (-n) % block
    if pad:
        # Out-of-bounds block slots are undefined in Pallas; zero rows
        # contribute nothing to ZᵀZ.
        Z = jnp.concatenate([Z, jnp.zeros((pad, D), Z.dtype)])
    return _packed_gram_call(Z, block, _interpret())


# ---------------------------------------------------------------------------
# Fused DQ rule chain
# ---------------------------------------------------------------------------

def _dq_kernel(price_ref, guest_ref, pnm_ref, pcc_ref, keep_ref):
    """Fused DQ chain: both rule columns + combined keep mask, one VPU pass.

    Must match ``ops/rules.py`` exactly, including the null (NaN) asymmetry:
    ``minimum_price_rule`` propagates NaN; ``price_correlation_rule`` maps
    NaN in either input to the sentinel (the UDF2 null guard,
    `PriceCorrelationDataQualityUdf.java:12-14`).
    """
    price = price_ref[:]
    guest = guest_ref[:]
    sentinel = jnp.asarray(BAD_ROW_SENTINEL, price.dtype)
    pnm = jnp.where(price < MIN_PRICE, sentinel, price)
    bad2 = jnp.logical_and(guest < CORRELATION_MAX_GUESTS,
                           price > CORRELATION_MAX_PRICE)
    null2 = jnp.logical_or(jnp.isnan(price), jnp.isnan(guest))
    pcc = jnp.where(jnp.logical_or(bad2, null2), sentinel, price)
    pnm_ref[:] = pnm
    pcc_ref[:] = pcc
    # NaN pnm (null price) > 0 is False — the row drops, same as the SQL
    # WHERE in the reference chain.
    keep_ref[:] = jnp.logical_and(pnm > 0.0, pcc > 0.0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _dq_rules_call(price2d, guest2d, block_rows: int, interpret: bool):
    rows, lanes = price2d.shape
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        _dq_kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct(price2d.shape, price2d.dtype),
            jax.ShapeDtypeStruct(price2d.shape, price2d.dtype),
            jax.ShapeDtypeStruct(price2d.shape, jnp.bool_),
        ),
        interpret=interpret,
    )(price2d, guest2d)


def dq_rules_pallas(price: jnp.ndarray, guest: jnp.ndarray,
                    block_rows: int = DQ_BLOCK_ROWS):
    """Fused DQ pipeline: ``(price_no_min, price_correct_correl, keep)``.

    Semantically identical to applying ``minimum_price_rule``, filtering
    ``> 0``, then ``price_correlation_rule`` and filtering ``> 0`` (the
    reference's four-stage chain): because filtering is mask-composition,
    the two WHERE stages commute into one conjunction. Golden row counts
    (SURVEY.md §2.3: 40→24 / 27→20 / 1040→1024) are the regression tests.
    """
    dt = price.dtype if jnp.issubdtype(price.dtype, jnp.floating) else jnp.float32
    p = price.astype(dt)
    g = guest.astype(dt)
    n = p.shape[0]
    lanes = 128
    pad = (-n) % lanes
    if pad:
        # Padded slots: price=sentinel keeps them out of the keep-mask.
        p = jnp.concatenate([p, jnp.full((pad,), BAD_ROW_SENTINEL, dt)])
        g = jnp.concatenate([g, jnp.zeros((pad,), dt)])
    rows = p.shape[0] // lanes
    block = min(block_rows, max(8, -(-rows // 8) * 8))
    row_pad = (-rows) % block
    if row_pad:
        p = jnp.concatenate([p, jnp.full((row_pad * lanes,), BAD_ROW_SENTINEL, dt)])
        g = jnp.concatenate([g, jnp.zeros((row_pad * lanes,), dt)])
        rows += row_pad
    pnm, pcc, keep = _dq_rules_call(p.reshape(rows, lanes),
                                    g.reshape(rows, lanes), block, _interpret())
    return (pnm.reshape(-1)[:n], pcc.reshape(-1)[:n], keep.reshape(-1)[:n])
