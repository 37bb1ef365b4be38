"""Numeric-safety debug switches (SURVEY.md §5 "Race detection / sanitizers").

The reference stack has no sanitizers to mirror (no native code, no app-level
threads); the JAX-native equivalent is runtime NaN/Inf detection in compiled
programs — the numerics sanitizer for a pure-SPMD framework. Enable in test
or debugging sessions; it forces a device sync per op, so keep it out of
benchmarks.
"""

from __future__ import annotations

import jax


def enable_nan_checks(enable: bool = True) -> None:
    """Raise on any NaN produced inside jitted code (``jax_debug_nans``)."""
    jax.config.update("jax_debug_nans", enable)


def enable_inf_checks(enable: bool = True) -> None:
    jax.config.update("jax_debug_infs", enable)


class nan_checks:
    """Context manager: ``with nan_checks(): model = lr.fit(df)``."""

    def __init__(self, enable: bool = True):
        self.enable = enable
        self._saved = None

    def __enter__(self):
        self._saved = jax.config.jax_debug_nans
        jax.config.update("jax_debug_nans", self.enable)
        return self

    def __exit__(self, *exc):
        jax.config.update("jax_debug_nans", self._saved)
        return False
