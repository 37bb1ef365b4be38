"""Arithmetic shared by the plain references and the comparisons."""

import numpy as np


def round_bf16(v):
    """Round to the nearest bfloat16 (ties to even), returned as float64:
    what storing ``v`` in bfloat16 keeps. The lower-precision control's
    ``q``."""
    bits = np.asarray(v, np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                        & np.uint32(1))) & np.uint32(0xFFFF0000)
    out = bits.view(np.float32).astype(np.float64)
    return out if out.ndim else float(out)


def rel_gap(got, want):
    """Largest |got - want| over the largest |want|: one number for a
    vector of coefficients or aggregates (``chip_smoke.Smoke.approx``)."""
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def abs_gap(got, want):
    """Largest |got - want|."""
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)))


def mismatches(got, want):
    """How many entries of two integer vectors differ (inf if the shapes
    do): the number an exact comparison holds to 0."""
    got, want = np.asarray(got).ravel(), np.asarray(want).ravel()
    if got.shape != want.shape:
        return float("inf")
    return float(np.count_nonzero(got.astype(np.int64)
                                  != want.astype(np.int64)))
