"""tpch-q1-lineitem: the table from the seed, and its plain float64 reference.

The seven columns of TPC-H's ``lineitem`` that query Q1 reads, drawn on the
device from the seed by dbgen's rules (the configuration's ``generator``):
quantity 1..50, extended price = quantity x a retail price in whole cents,
discount and tax in hundredths, ship date = order date + 1..121 days (int32
days since 1970-01-01), line status from the ship date and return flag from
the receipt date, both against dbgen's current date 1995-06-17. The flags are
int32 codes in ASCII order (``codes``), so that ascending codes are ascending
letters. The table is made in one jitted call and pulled to the host once,
after the window, for the reference.

The reference is numpy only and shares no code with the program: the date
filter, the two expressions, per-group sums by ``np.bincount`` over the
packed key, averages, counts, groups in key order. Rows are walked in
chunks, so that 1.8e8 rows never stand as several float64 columns at once;
the sums are float64 throughout. ``q`` rounds every stored intermediate: the
identity gives the float64 reference, ``refmath.round_bf16`` the
lower-precision control.
"""

import numpy as np

COLUMNS = ("l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
           "l_discount", "l_tax", "l_shipdate")
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
AVGS = ("avg_qty", "avg_price", "avg_disc")
CHUNK = 1 << 24


def make_table(cfg, seed, rows=None):
    """{column: device array}: the flags and the date int32, the rest
    float32."""
    import jax
    import jax.numpy as jnp

    n = int(rows or cfg["rows"])
    g = cfg["generator"]

    @jax.jit
    def generate(key):
        ks = jax.random.split(key, 8)

        def draw(k, lo, hi):
            return jax.random.randint(k, (n,), lo, hi + 1, jnp.int32)

        quantity = draw(ks[0], g["quantity_min"], g["quantity_max"])
        cents = quantity * draw(ks[1], g["retail_cents_min"],
                                g["retail_cents_max"])
        ship = (draw(ks[2], g["order_date_min"], g["order_date_max"])
                + draw(ks[3], g["ship_days_min"], g["ship_days_max"]))
        receipt = ship + draw(ks[4], g["receipt_days_min"],
                              g["receipt_days_max"])
        returned = 2 * draw(ks[5], 0, 1)              # R (2) or A (0)
        hundredth = jnp.float32(100.0)
        return {
            "l_returnflag": jnp.where(receipt <= g["current_date"],
                                      returned, 1),
            "l_linestatus": (ship > g["current_date"]).astype(jnp.int32),
            "l_quantity": quantity.astype(jnp.float32),
            "l_extendedprice": cents.astype(jnp.float32) / hundredth,
            "l_discount": draw(ks[6], 0, g["discount_max_hundredths"])
            .astype(jnp.float32) / hundredth,
            "l_tax": draw(ks[7], 0, g["tax_max_hundredths"])
            .astype(jnp.float32) / hundredth,
            "l_shipdate": ship,
        }

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.block_until_ready(generate(key))


def column_names(cfg):
    return list(COLUMNS)


def table_bytes(cfg, rows=None):
    """Bytes of the input columns one job reads: seven 4-byte columns."""
    return int(rows or cfg["rows"]) * len(COLUMNS) * 4


def cutoff(cfg, delta_days):
    """Q1's date bound: the end date less DELTA days, as days since 1970."""
    return int(cfg["query"]["end_date"]) - int(delta_days)


def kept(shipdate, bound):
    """The rows the WHERE clause keeps. Dates are whole numbers far inside
    every precision's exact range: no ``q``."""
    return shipdate <= bound


def expressions(price, discount, tax, q):
    """(l_extendedprice * (1 - l_discount), that * (1 + l_tax)), every
    stored intermediate through ``q``."""
    disc_price = q(price * q(1.0 - discount))
    return disc_price, q(disc_price * q(1.0 + tax))


def group_sums(host, bound, q, bins):
    """Per packed key (flag code x status codes + status code): the row
    count and the float64 sums of quantity, price, discounted price, charge
    and discount over the kept rows."""
    statuses = bins[1]
    width = bins[0] * bins[1]
    count = np.zeros(width, np.int64)
    sums = np.zeros((5, width), np.float64)
    n = host["l_shipdate"].shape[0]
    for lo in range(0, n, CHUNK):
        hi = min(lo + CHUNK, n)
        keep = kept(host["l_shipdate"][lo:hi], bound)
        packed = (host["l_returnflag"][lo:hi].astype(np.int64) * statuses
                  + host["l_linestatus"][lo:hi])[keep]
        quantity = q(host["l_quantity"][lo:hi][keep].astype(np.float64))
        price = q(host["l_extendedprice"][lo:hi][keep].astype(np.float64))
        discount = q(host["l_discount"][lo:hi][keep].astype(np.float64))
        tax = q(host["l_tax"][lo:hi][keep].astype(np.float64))
        disc_price, charge = expressions(price, discount, tax, q)
        count += np.bincount(packed, minlength=width)
        for k, column in enumerate((quantity, price, disc_price, charge,
                                    discount)):
            sums[k] += np.bincount(packed, weights=column, minlength=width)
    return count, sums


def q1(cfg, host, delta_days, q=None):
    """The published answer: one row per (flag, status) that has rows, in
    key order — the keys as letters, the four sums, the three averages and
    the count."""
    q = q or (lambda v: v)
    flags, statuses = cfg["codes"]["l_returnflag"], \
        cfg["codes"]["l_linestatus"]
    count, sums = group_sums(host, cutoff(cfg, delta_days), q,
                             (len(flags), len(statuses)))
    present = np.nonzero(count)[0]            # ascending packed key
    n = count[present].astype(np.float64)
    total = {name: q(sums[k][present]) for k, name in enumerate(SUMS)}
    answer = {
        "l_returnflag": [flags[p // len(statuses)] for p in present],
        "l_linestatus": [statuses[p % len(statuses)] for p in present],
        "count_order": count[present],
        "avg_qty": q(total["sum_qty"] / n),
        "avg_price": q(total["sum_base_price"] / n),
        "avg_disc": q(q(sums[4][present]) / n),
    }
    answer.update(total)
    return answer
