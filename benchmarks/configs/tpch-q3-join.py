"""tpch-q3-join: the three tables from the seed, and their plain float64
reference.

The ten columns of TPC-H's ``customer``, ``orders`` and ``lineitem`` that
query Q3 reads, made on the device from the seed by dbgen's rules (the
configuration's ``generator`` and ``assumed``): customer keys dense, a
market segment code each; orders in key order with dbgen's sparse keys
(8 used of every 32), a customer whose key is no multiple of 3, a date, a
ship priority of 0; lineitem in order-key order, 1 to 7 lines an order,
each with a price (quantity x retail price in whole cents), a discount and
a ship date 1 to 121 days after ITS ORDER's date. An order's date is a hash
of its index and the seed, so the lines compute it again instead of
gathering it; the line counts are a fixed multiset shuffled by the seed, so
every seed has the same three row counts. Three jitted calls; the tables
are pulled to the host once, after the window, for the reference.

The reference is numpy only and shares no code with the program: the three
filters, the customer join by a boolean lookup over the customer keys, the
order join by a slot lookup over the order-key range, revenue per order by
``np.bincount`` over chunks of lineitem (float64 sums), the groups ranked
by (-revenue, o_orderdate). ``q`` rounds every stored intermediate: the
identity gives the float64 reference, ``refmath.round_bf16`` the
lower-precision control.
"""

import numpy as np

TABLES = {
    "customer": ("c_custkey", "c_mktsegment"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
    "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                 "l_shipdate"),
}
CHUNK = 1 << 24
EXTRA = 6              # rows the reference ranks past the limit


def sizes(cfg, rows=None):
    """(customer, orders, lineitem) row counts; ``rows`` (tests) counts
    lineitem and the others follow in dbgen's ratios."""
    if rows is None:
        return (int(cfg["customer_rows"]), int(cfg["orders_rows"]),
                int(cfg["rows"]))
    g = cfg["generator"]
    orders = max(int(rows) // int(g["orders_per_lineitem_rows"]), 8)
    return (max(orders // int(g["customers_per_order_rows"]), 8), orders,
            int(rows))


def _mix(x):
    """murmur3's 32-bit finalizer over a uint32 array."""
    import jax.numpy as jnp

    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _order_date(index, salt, g):
    """An order's date from its index and the seed's salt: uniform over
    the published range (the modulo's bias is under 1e-6)."""
    import jax.numpy as jnp

    span = int(g["order_date_max"]) - int(g["order_date_min"]) + 1
    h = _mix(index.astype(jnp.uint32) * jnp.uint32(0x9E3779B1) + salt)
    return (h % jnp.uint32(span)).astype(jnp.int32) + int(g["order_date_min"])


def _order_key(index):
    return (index // 8) * 32 + index % 8 + 1


def line_counts(g, orders, lines):
    """The multiset of lines per order, before the seed shuffles it: the
    cycle lines_min..lines_max, then one more (or one fewer) for the first
    orders that have room, until the total is ``lines``."""
    import jax.numpy as jnp

    lo, hi = int(g["lines_min"]), int(g["lines_max"])
    base = jnp.arange(orders, dtype=jnp.int32) % (hi - lo + 1) + lo
    excess = lines - (orders // (hi - lo + 1)) * sum(range(lo, hi + 1)) \
        - sum(range(lo, lo + orders % (hi - lo + 1)))
    room = base < hi if excess >= 0 else base > lo
    bump = room & (jnp.cumsum(room, dtype=jnp.int32) <= abs(excess))
    return base + (1 if excess >= 0 else -1) * bump.astype(jnp.int32)


def make_table(cfg, seed, rows=None):
    """{table: {column: device array}}: keys, dates and codes int32,
    prices and discounts float32."""
    import jax
    import jax.numpy as jnp

    n_cust, n_orders, n_lines = sizes(cfg, rows)
    g = cfg["generator"]
    if not n_orders * int(g["lines_min"]) <= n_lines \
            <= n_orders * int(g["lines_max"]):
        raise ValueError(f"{n_lines} lines cannot be dealt to {n_orders} "
                         "orders")

    def draw(k, n, lo, hi):
        return jax.random.randint(k, (n,), lo, hi + 1, jnp.int32)

    @jax.jit
    def customer(key):
        return {"c_custkey": jnp.arange(1, n_cust + 1, dtype=jnp.int32),
                "c_mktsegment": draw(key, n_cust, 0, int(g["segments"]) - 1)}

    @jax.jit
    def orders(key, salt):
        index = jnp.arange(n_orders, dtype=jnp.int32)
        with_orders = n_cust - n_cust // 3     # keys that are no multiple of 3
        u = draw(key, n_orders, 0, with_orders - 1)
        return {"o_orderkey": _order_key(index),
                "o_custkey": u + u // 2 + 1,
                "o_orderdate": _order_date(index, salt, g),
                "o_shippriority": jnp.zeros((n_orders,), jnp.int32)}

    @jax.jit
    def lineitem(key, salt):
        ks = jax.random.split(key, 5)
        counts = jax.random.permutation(
            ks[0], line_counts(g, n_orders, n_lines))
        order = jnp.repeat(jnp.arange(n_orders, dtype=jnp.int32), counts,
                           total_repeat_length=n_lines)
        cents = draw(ks[1], n_lines, g["quantity_min"], g["quantity_max"]) \
            * draw(ks[2], n_lines, g["retail_cents_min"],
                   g["retail_cents_max"])
        hundredth = jnp.float32(100.0)
        return {
            "l_orderkey": _order_key(order),
            "l_extendedprice": cents.astype(jnp.float32) / hundredth,
            "l_discount": draw(ks[3], n_lines, 0,
                               g["discount_max_hundredths"])
            .astype(jnp.float32) / hundredth,
            "l_shipdate": _order_date(order, salt, g)
            + draw(ks[4], n_lines, g["ship_days_min"], g["ship_days_max"]),
        }

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    kc, ko, kl, ks = jax.random.split(key, 4)
    salt = jax.random.bits(ks, (), jnp.uint32)
    return jax.block_until_ready({"customer": customer(kc),
                                  "orders": orders(ko, salt),
                                  "lineitem": lineitem(kl, salt)})


def column_names(cfg):
    return {table: list(columns) for table, columns in TABLES.items()}


def table_bytes(cfg, rows=None):
    """Bytes of the input columns one job reads: ten 4-byte columns over
    their three tables."""
    return sum(n * len(TABLES[t]) * 4 for t, n in zip(
        ("customer", "orders", "lineitem"), sizes(cfg, rows)))


def revenue(price, discount, q):
    """l_extendedprice * (1 - l_discount), every stored intermediate
    through ``q``."""
    return q(price * q(1.0 - discount))


def q3(cfg, host, segment, date, limit, q=None):
    """The published answer and a few rows past it: per qualifying order
    its key, date, ship priority and revenue, ranked by (-revenue,
    o_orderdate), the first ``limit + EXTRA`` of them; with the counts of
    joined rows and of groups."""
    q = q or (lambda v: v)
    cust, orders, lines = host["customer"], host["orders"], host["lineitem"]
    in_segment = np.zeros(int(cust["c_custkey"].max()) + 1, bool)
    in_segment[cust["c_custkey"][cust["c_mktsegment"] == segment]] = True
    open_order = (orders["o_orderdate"] < date) \
        & in_segment[orders["o_custkey"]]
    slot = np.full(int(orders["o_orderkey"].max()) + 1, -1, np.int32)
    slot[orders["o_orderkey"][open_order]] = np.nonzero(open_order)[0]
    n_orders = orders["o_orderkey"].shape[0]
    total = np.zeros(n_orders, np.float64)
    joined = np.zeros(n_orders, np.int64)
    n = lines["l_orderkey"].shape[0]
    for lo in range(0, n, CHUNK):
        hi = min(lo + CHUNK, n)
        keep = lines["l_shipdate"][lo:hi] > date
        at = slot[lines["l_orderkey"][lo:hi][keep]]
        hit = at >= 0
        price = q(lines["l_extendedprice"][lo:hi][keep][hit]
                  .astype(np.float64))
        discount = q(lines["l_discount"][lo:hi][keep][hit]
                     .astype(np.float64))
        total += np.bincount(at[hit], weights=revenue(price, discount, q),
                             minlength=n_orders)
        joined += np.bincount(at[hit], minlength=n_orders)
    groups = np.nonzero(joined)[0]
    total = q(total[groups])
    ranked = groups[np.lexsort((orders["o_orderdate"][groups],
                                -total))][:limit + EXTRA]
    by_group = dict(zip(groups.tolist(), total.tolist()))
    return {
        "l_orderkey": orders["o_orderkey"][ranked].astype(np.int64),
        "o_orderdate": orders["o_orderdate"][ranked].astype(np.int64),
        "o_shippriority": orders["o_shippriority"][ranked].astype(np.int64),
        "revenue": np.asarray([by_group[g] for g in ranked.tolist()],
                              np.float64),
        "limit": int(limit),
        "groups": int(groups.size),
        "joined_rows": int(joined.sum()),
    }
