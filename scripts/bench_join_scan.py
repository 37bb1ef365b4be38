"""The join's probe scans alone on the chip: the Pallas kernel
``join_probe_scan`` against XLA's ``cumsum`` and two ``cummax``
(``ops/joins.py``), at the two sizes ``tpch_q3_join`` runs them.

    python scripts/bench_join_scan.py [--repeats 10] [--out FILE]

Pairs are made on the device, in (key, tag) order as the join's build step
leaves them: the ``lineitem`` join's 7,624 x 32,768 merged pairs (an order's
four lines a key group, a valid build row in front of one order in ten,
46 % of the probe rows masked) and the customer join's 6.6e7 sorted ones
(eleven pairs a customer, one build row and ten orders; 80 % of the build
rows and 52 % of the probe rows masked). Every variant's ``head`` and
``cnt`` must equal XLA's bit for bit, here and on small cases with two
keys, float keys, NaN and signed zeros; then each is timed alone (host
clock around ``block_until_ready``, median of ``--repeats``). Prints one
JSON line; exits 1 on any difference. Needs a TPU: on another backend it
exits 2.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))
from sparkdq4ml_tpu.ops import joins as J  # noqa: E402

HIGH = np.uint32(1 << 31)


def cell_pairs(n, group, build_every, build_masked, probe_masked, seed):
    """``n`` pairs in key order: groups of ``group`` pairs, the first of a
    group a build row in one group of ``build_every``."""
    i = jax.lax.iota(jnp.int32, n)
    r = jax.random.uniform(jax.random.PRNGKey(seed), (n,))
    g = i // group
    build = (i % group == 0) & (g % build_every == 0)
    nb = -(-n // (group * build_every))
    tag = jnp.where(build, g // build_every, nb + i).astype(jnp.uint32)
    masked = jnp.where(build, r < build_masked, r < probe_masked)
    return [g], jnp.where(masked, tag | HIGH, tag), nb


def small_pairs(seed, n, k, floating):
    r = np.random.default_rng(seed)
    keys = [r.integers(0, 40, n).astype(np.int32) for _ in range(k)]
    if floating:
        keys = [x.astype(np.float32) - 20 for x in keys]
        keys[0][r.random(n) < 0.05] = np.nan
        keys[0][r.random(n) < 0.05] = -0.0
    tag = np.arange(n, dtype=np.uint32)
    tag = np.where(r.random(n) < 0.3, tag | HIGH, tag)
    order = np.lexsort([tag] + keys[::-1])
    return [jnp.asarray(x[order]) for x in keys], jnp.asarray(tag[order])


def timed(fn, args, repeats):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t) * 1e3)
    return statistics.median(times), min(times)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("no TPU: the kernel is timed on the chip only", file=sys.stderr)
        return 2
    out = {"device": jax.devices()[0].device_kind, "cases": {}, "small": []}
    bad = False
    # the kernel on the chip against XLA on the chip, small and odd cases
    for seed, n, k, floating in [(1, 5000, 1, False), (2, 70_001, 2, False),
                                 (3, 30_000, 1, True), (4, 1100, 2, True)]:
        ks, ts = small_pairs(seed, n, k, floating)
        nb = n // 3
        want = jax.jit(J._scans_xla, static_argnums=2)(ks, ts, nb)
        for block, rows in [(2048, 8), (J.SCAN_BLOCK, J.SCAN_ROWS)]:
            got = jax.jit(lambda a, b: J._scans_pallas(
                a, b, nb, block=block, rows=rows))(ks, ts)
            same = all(bool(jnp.array_equal(g, w)) for g, w in zip(got, want))
            out["small"].append([n, k, floating, block, rows, same])
            bad |= not same
    cases = {
        "lineitem_7624x32768": dict(n=7624 * 32768, group=4, build_every=10,
                                    build_masked=0.0, probe_masked=0.46),
        "customer_6.6e7": dict(n=66_000_000, group=11, build_every=1,
                               build_masked=0.8, probe_masked=0.52),
    }
    variants = [(J.SCAN_BLOCK, rows) for rows in (64, 128, J.SCAN_ROWS)]
    for name, spec in cases.items():
        ks, ts, nb = cell_pairs(seed=7, **spec)
        jax.block_until_ready(ts)
        xla = jax.jit(J._scans_xla, static_argnums=2)
        want = xla(ks, ts, nb)
        row = {"nb": nb, "xla_ms": timed(xla, (ks, ts, nb), args.repeats)}
        for block, rows in variants:
            fn = jax.jit(lambda a, b, block=block, rows=rows:
                         J._scans_pallas(a, b, nb, block=block, rows=rows))
            got = fn(ks, ts)
            same = all(bool(jnp.array_equal(g, w)) for g, w in zip(got, want))
            bad |= not same
            row[f"pallas_b{block}_r{rows}"] = [
                *timed(fn, (ks, ts), args.repeats), same]
        out["cases"][name] = row
        del ks, ts, want
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
