"""The GROUP BY of a whole fact table by the key it is stored in, alone on
the chip: ``ops/segments.py``'s ordered lowering (runs of the key, no sort)
under both its scan lowerings — the Pallas kernel ``grouped_run_scan`` and
XLA's segmented scans — over ``tpch_q18_volume``'s ``lineitem``.

    python scripts/bench_grouped_ordered.py [--rows N] [--repeats 3]
        [--unroll 2,4,8] [--sorted] [--out FILE]

``lineitem`` is the benchmark's own (``benchmarks/configs/tpch-q18-volume.py``,
2.4e8 rows at scale factor 40 by default, seed 1): ``GROUP BY l_orderkey``
with ``sum(l_quantity)``, 6e7 groups. Each lowering runs once to compile,
then ``--repeats`` times, timed on the host's clock to its group count (the
reduction's one read); the peak of device memory is read after each.
``--sorted`` adds the sorted lowering, which may not fit beside the table
(the line then says so). The results must be equal, key for key and sum for
sum. ``--unroll`` also times the kernel alone (``_run_scan_pallas``, host
clock around ``block_until_ready``) at each number of vregs a loop turn
scans, and checks each against the engine's result. Prints one JSON line;
exits 1 where results differ. Needs a TPU: elsewhere it exits 2
(``--cpu-ok`` and a small ``--rows`` run it on the CPU, where the kernel
runs through the Pallas interpreter).
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
    parser.add_argument("--unroll", default="")
    parser.add_argument("--sorted", action="store_true")
    parser.add_argument("--cpu-ok", action="store_true")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not args.cpu_ok:
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
    choose = segments.run_scan_lowering

    def group():
        # the engine's entry itself, not ``Frame.group_by``: a lowering that
        # does not fit raises here instead of falling to the host's numpy
        out = segments.grouped_agg(frame, ["l_orderkey"],
                                   [AggExpr("sum", "l_quantity")])
        return out, out.count()

    line = {"device": device.device_kind, "rows": frame.num_slots}
    results = {}
    lowerings = ["pallas", "xla"] + (["sorted"] if args.sorted else [])
    for lowering in lowerings:
        # the engine chooses from what it observes; here each is steered
        segments.run_scan_lowering = (
            (lambda *a: "xla") if lowering == "xla" else
            (lambda *a: "pallas") if not on_tpu else choose)
        if lowering == "sorted":
            with segments._ORDER_LOCK:
                for key in segments._ORDER:
                    segments._ORDER[key] = False
        if not on_tpu and lowering == "pallas":
            _interpret(segments)
        before = counters.snapshot()
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
        moved = {k: v - before.get(k, 0)
                 for k, v in counters.snapshot().items()
                 if k in ("grouped.ordered", "grouped.run_pallas")}
        stats = device.memory_stats() or {}
        line[lowering] = {"ms": 1e3 * statistics.median(times),
                          "groups": groups, "counters": moved,
                          "peak_bytes": stats.get("peak_bytes_in_use")}
        host = out.to_pydict()
        results[lowering] = (np.asarray(host["l_orderkey"]),
                             np.asarray(host["sum(l_quantity)"]))
        del out
    segments.run_scan_lowering = choose
    same = None
    if len(results) > 1:
        (k1, s1), *rest = results.values()
        same = all(np.array_equal(k1, k) and np.array_equal(s1, s)
                   for k, s in rest)
    if args.unroll and "pallas" in results:
        same = _kernel_alone(args, segments, frame, results["pallas"],
                             line, on_tpu) and same is not False
    line["equal"] = same
    text = json.dumps(line)
    print(text)
    if args.out:
        with open(args.out, "a") as f:
            f.write(text + "\n")
    return 1 if same is False else 0


def _interpret(segments):
    import functools

    segments._run_scan_pallas = functools.partial(
        segments._run_scan_pallas, interpret=True)


def _kernel_alone(args, segments, frame, want, line, on_tpu):
    """The kernel alone at each ``--unroll``, against the engine's
    result: (key, sum) at the live slots."""
    import jax
    import jax.numpy as jnp

    key = frame._data["l_orderkey"]
    qty = frame._data["l_quantity"]
    mask = jnp.asarray(frame._mask, jnp.bool_)
    ops, kinds = (("sum", 0, False),), ("f",)
    same = True
    for unroll in (int(u) for u in args.unroll.split(",")):
        fn = jax.jit(lambda k, m, v, unroll=unroll: segments._run_scan_pallas(
            ops, kinds, k, m, [v], unroll=unroll, interpret=not on_tpu))
        got = fn(key, mask, qty)
        jax.block_until_ready(got)
        times = []
        for _ in range(args.repeats):
            t = time.perf_counter()
            jax.block_until_ready(fn(key, mask, qty))
            times.append((time.perf_counter() - t) * 1e3)
        outs, live, groups, held = got
        live = np.asarray(live)
        ok = (bool(held) and np.array_equal(np.asarray(key)[live], want[0])
              and np.array_equal(np.asarray(outs[0])[live], want[1])
              and int(groups) == len(want[0]))
        line[f"kernel_unroll{unroll}"] = {
            "ms": statistics.median(times), "min_ms": min(times),
            "equal": ok}
        same &= ok
    return same


if __name__ == "__main__":
    sys.exit(main())
