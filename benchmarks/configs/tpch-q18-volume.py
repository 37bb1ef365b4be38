"""tpch-q18-volume: the three tables from the seed, and their plain float64
reference.

The eight columns of TPC-H's ``customer``, ``orders`` and ``lineitem`` that
query Q18 reads, made on the device from the seed. Keys, dates, the line
counts and the quantities are ``tpch-q3-join``'s (its generator is loaded
from the file beside this one, not copied: the same seed gives the same
orders and the same quantities); beside them a customer's name is its key,
and an order's total price follows dbgen's rule over its own lines —
extended price x (1 + tax) x (1 - discount) — summed in float32 at set-up.

The reference is numpy only and shares no code with the program: per order
the sum of its lines' quantities by ``np.bincount`` over chunks of
lineitem (a slot lookup over the order-key range), the HAVING, the
qualifying orders' customers by a lookup over the customer keys, one group
an order (its key is unique, so the statement's five-key GROUP BY over the
joined lines is the order's own sum), ranked by (-o_totalprice,
o_orderdate). ``q`` rounds every stored intermediate: the identity gives
the float64 reference, ``refmath.round_bf16`` the lower-precision control.
"""

import importlib.util
import os

import numpy as np

TABLES = {
    "customer": ("c_custkey", "c_name"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"),
    "lineitem": ("l_orderkey", "l_quantity"),
}
CHUNK = 1 << 24
EXTRA = 6              # rows the reference ranks past the limit
RESULT = ("c_name", "c_custkey", "o_orderkey", "o_orderdate",
          "o_totalprice", "sum_qty")


def _q3():
    """``tpch-q3-join``'s generator module, from the file beside this one."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tpch-q3-join.py")
    spec = importlib.util.spec_from_file_location("benchmarks_q18_q3", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


Q3 = _q3()


def sizes(cfg, rows=None):
    """(customer, orders, lineitem) row counts, as ``tpch-q3-join``'s."""
    return Q3.sizes(cfg, rows)


def make_table(cfg, seed, rows=None):
    """{table: {column: device array}}: keys, dates and names int32,
    quantities and total prices float32."""
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
    def customer():
        key = jnp.arange(1, n_cust + 1, dtype=jnp.int32)
        return {"c_custkey": key, "c_name": key}

    @jax.jit
    def orders_and_lines(ko, kl, salt):
        index = jnp.arange(n_orders, dtype=jnp.int32)
        with_orders = n_cust - n_cust // 3     # keys that are no multiple of 3
        u = draw(ko, n_orders, 0, with_orders - 1)
        # tpch-q3-join's lineitem draws from its keys: counts, quantity,
        # retail price, discount; the tax from a key of its own
        ks = jax.random.split(kl, 5)
        counts = jax.random.permutation(
            ks[0], Q3.line_counts(g, n_orders, n_lines))
        order = jnp.repeat(index, counts, total_repeat_length=n_lines)
        quantity = draw(ks[1], n_lines, g["quantity_min"], g["quantity_max"])
        cents = quantity * draw(ks[2], n_lines, g["retail_cents_min"],
                                g["retail_cents_max"])
        hundredth = jnp.float32(100.0)
        discount = draw(ks[3], n_lines, 0, g["discount_max_hundredths"]) \
            .astype(jnp.float32) / hundredth
        tax = draw(jax.random.fold_in(kl, 5), n_lines, 0,
                   g["tax_max_hundredths"]) \
            .astype(jnp.float32) / hundredth
        charge = cents.astype(jnp.float32) / hundredth \
            * (jnp.float32(1.0) + tax) * (jnp.float32(1.0) - discount)
        total = jax.ops.segment_sum(charge, order, num_segments=n_orders,
                                    indices_are_sorted=True)
        return ({"o_orderkey": Q3._order_key(index),
                 "o_custkey": u + u // 2 + 1,
                 "o_orderdate": Q3._order_date(index, salt, g),
                 "o_totalprice": total},
                {"l_orderkey": Q3._order_key(order),
                 "l_quantity": quantity.astype(jnp.float32)})

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    _, ko, kl, ks = jax.random.split(key, 4)
    salt = jax.random.bits(ks, (), jnp.uint32)
    orders, lines = orders_and_lines(ko, kl, salt)
    return jax.block_until_ready({"customer": customer(), "orders": orders,
                                  "lineitem": lines})


def column_names(cfg):
    return {table: list(columns) for table, columns in TABLES.items()}


def table_bytes(cfg, rows=None):
    """Bytes of the input columns one job reads: eight 4-byte columns over
    their three tables."""
    return sum(n * len(TABLES[t]) * 4 for t, n in zip(
        ("customer", "orders", "lineitem"), sizes(cfg, rows)))


def q18(cfg, host, quantity, limit, q=None):
    """The published answer and a few rows past it: per qualifying order
    its customer's name and key, its key, date, total price and the sum of
    its lines' quantities, ranked by (-o_totalprice, o_orderdate), the
    first ``limit + EXTRA``; with the count of qualifying orders."""
    q = q or (lambda v: v)
    cust, orders, lines = host["customer"], host["orders"], host["lineitem"]
    okey = orders["o_orderkey"]
    slot = np.full(int(okey.max()) + 1, -1, np.int64)
    slot[okey] = np.arange(okey.shape[0])
    qty = np.zeros(okey.shape[0], np.float64)
    n = lines["l_orderkey"].shape[0]
    for lo in range(0, n, CHUNK):
        hi = min(lo + CHUNK, n)
        at = slot[lines["l_orderkey"][lo:hi]]
        hit = at >= 0
        qty += np.bincount(at[hit], weights=q(
            lines["l_quantity"][lo:hi][hit].astype(np.float64)),
            minlength=okey.shape[0])
    qty = q(qty)
    chosen = np.nonzero(qty > quantity)[0]       # HAVING, then the semi join
    name = np.full(int(cust["c_custkey"].max()) + 1, -1, np.int64)
    name[cust["c_custkey"]] = cust["c_name"]
    custkey = orders["o_custkey"][chosen].astype(np.int64)
    has = name[custkey] >= 0                     # the customer join
    chosen, custkey = chosen[has], custkey[has]
    price = q(orders["o_totalprice"][chosen].astype(np.float64))
    date = orders["o_orderdate"][chosen].astype(np.int64)
    rank = np.lexsort((date, -price))[:int(limit) + EXTRA]
    return {
        "c_name": name[custkey][rank],
        "c_custkey": custkey[rank],
        "o_orderkey": okey[chosen][rank].astype(np.int64),
        "o_orderdate": date[rank],
        "o_totalprice": price[rank],
        "sum_qty": qty[chosen][rank],
        "limit": int(limit),
        "qualifying": int(chosen.size),
    }
