"""Pure, vectorized data-quality rule functions.

The reference's one architectural idea (SURVEY.md §1) is the split between
pure rule logic (`dq/service/*.java`) and engine adapters (`dq/udf/*.java`).
This module is the service layer: plain jnp functions with zero framework
dependencies, testable outside any frame/session, exactly like the reference's
static service methods. The adapter step is just ``register_udf`` (see
``register_builtin_rules``), because vectorized fns plug straight into the
column engine — no per-row wrapper class is needed on TPU.

Null semantics use NaN as the null analogue and mirror the reference's
asymmetry (SURVEY.md §2.1):

* ``minimum_price_rule`` has *no* null guard — a NaN price propagates to the
  output (the analogue of `MinimumPriceDataQualityUdf.java:11-13`, which NPEs
  on a null ``Double``: garbage in, failure out).
* ``price_correlation_rule`` is null-safe: NaN in either input → ``-1.0``
  (mirrors the explicit guard at `PriceCorrelationDataQualityUdf.java:12-14`).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..config import float_dtype

# Threshold constants from the reference services.
MIN_PRICE = 20.0            # MinimumPriceDataQualityService.java:5
CORRELATION_MAX_GUESTS = 14  # PriceCorrelationDataQualityService.java:6
CORRELATION_MAX_PRICE = 90.0  # PriceCorrelationDataQualityService.java:6
BAD_ROW_SENTINEL = -1.0


def minimum_price_rule(price):
    """price < 20 → −1 else price (`MinimumPriceDataQualityService.java:7-13`).

    Vectorized: one fused ``jnp.where`` over the column. NaN propagates
    (NaN < 20 is False, so NaN is returned unchanged — the poison analogue of
    the reference UDF1's NPE on null).
    """
    price = jnp.asarray(price, float_dtype())
    return jnp.where(price < MIN_PRICE, jnp.asarray(BAD_ROW_SENTINEL, price.dtype), price)


def price_correlation_rule(price, guest):
    """guest < 14 AND price > 90 → −1 else price
    (`PriceCorrelationDataQualityService.java:5-10`), with the adapter's
    null guard folded in: NaN price/guest → −1.0
    (`PriceCorrelationDataQualityUdf.java:12-14`).
    """
    price = jnp.asarray(price, float_dtype())
    guest_f = jnp.asarray(guest, float_dtype())
    bad = jnp.logical_and(guest_f < CORRELATION_MAX_GUESTS, price > CORRELATION_MAX_PRICE)
    null = jnp.logical_or(jnp.isnan(price), jnp.isnan(guest_f))
    sentinel = jnp.asarray(BAD_ROW_SENTINEL, price.dtype)
    return jnp.where(jnp.logical_or(bad, null), sentinel, price)


def register_builtin_rules(registry=None) -> None:
    """Register both rules under the names the reference app uses
    (`DataQuality4MachineLearningApp.java:46-49`)."""
    from .udf import default_registry

    reg = registry if registry is not None else default_registry()
    reg.register("minimumPriceRule", minimum_price_rule, "double")
    reg.register("priceCorrelationRule", price_correlation_rule, "double")
