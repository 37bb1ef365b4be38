"""The GROUP BY of a whole fact table by the key it is stored in, alone on
the chip: ``ops/segments.py``'s ordered lowering (runs of the key, no sort)
against its sorted lowering, over ``tpch_q18_volume``'s ``lineitem``.

    python scripts/bench_grouped_ordered.py [--rows N] [--repeats 3] [--out FILE]

``lineitem`` is the benchmark's own (``benchmarks/configs/tpch-q18-volume.py``,
2.4e8 rows at scale factor 40 by default, seed 1): ``GROUP BY l_orderkey``
with ``sum(l_quantity)``, 6e7 groups. Each lowering runs once to compile,
then ``--repeats`` times, timed on the host's clock to its group count (the
reduction's one read); the peak of device memory is read after each. The
two results must be equal, key for key and sum for sum; the sorted lowering
may not fit beside the table, which the line then says. Prints one JSON
line; exits 1 where the results differ. Needs a TPU: elsewhere it exits 2
(``--cpu-ok`` and a small ``--rows`` run it on the CPU).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)


def _config():
    path = os.path.join(REPO, "benchmarks", "configs", "tpch-q18-volume")
    spec = importlib.util.spec_from_file_location("q18_config", path + ".py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    with open(path + ".json") as f:
        return module, json.load(f)


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=None)
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--cpu-ok", action="store_true")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu" and not args.cpu_ok:
        print("bench_grouped_ordered: no TPU", file=sys.stderr)
        return 2
    from sparkdq4ml_tpu import Frame
    from sparkdq4ml_tpu.frame.aggregates import AggExpr
    from sparkdq4ml_tpu.ops import segments
    from sparkdq4ml_tpu.utils.profiling import counters

    module, cfg = _config()
    lines = module.make_table(cfg, 1, args.rows)["lineitem"]
    frame = Frame(lines)
    device = jax.devices()[0]

    def group():
        # the engine's entry itself, not ``Frame.group_by``: a lowering that
        # does not fit raises here instead of falling to the host's numpy
        out = segments.grouped_agg(frame, ["l_orderkey"],
                                   [AggExpr("sum", "l_quantity")])
        return out, out.count()

    line = {"device": device.device_kind, "rows": frame.num_slots}
    results = {}
    for lowering in ("ordered", "sorted"):
        if lowering == "sorted":
            with segments._ORDER_LOCK:
                for key in segments._ORDER:
                    segments._ORDER[key] = False
        before = counters.get("grouped.ordered")
        try:
            out, groups = group()
            times = []
            for _ in range(args.repeats):
                t = time.perf_counter()
                out, groups = group()
                times.append(time.perf_counter() - t)
        except Exception as e:           # the sorted tier may not fit
            line[lowering] = {"error": f"{type(e).__name__}: {e}"[:400]}
            continue
        took = counters.get("grouped.ordered") > before
        stats = device.memory_stats() or {}
        line[lowering] = {"ms": 1e3 * statistics.median(times),
                          "groups": groups, "took_ordered": took,
                          "peak_bytes": stats.get("peak_bytes_in_use")}
        host = out.to_pydict()
        results[lowering] = (np.asarray(host["l_orderkey"]),
                             np.asarray(host["sum(l_quantity)"]))
        del out
    same = None
    if len(results) == 2:
        (k1, s1), (k2, s2) = results.values()
        same = bool(np.array_equal(k1, k2) and np.array_equal(s1, s2))
    line["equal"] = same
    text = json.dumps(line)
    print(text)
    if args.out:
        with open(args.out, "a") as f:
            f.write(text + "\n")
    return 1 if same is False else 0


if __name__ == "__main__":
    sys.exit(main())
