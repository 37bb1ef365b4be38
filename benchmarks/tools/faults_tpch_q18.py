"""Faults planted under ``tpch_q18_volume``'s timed path, by name, like
``tools/faults_tpch_q3.py``: ``FAULTS[cell][name](job)`` breaks the program
for one run and leaves ``job._undo`` to mend it.
``benchmarks/tests/test_tpch_q18.py`` drives each through
``harness.execute(..., tamper=...)`` and sees ``correct`` come out false;
``tests/test_benchmark_cells.py`` drives the first, at a size where the
published threshold keeps no order: so the first is one whose answer is
never empty. ``python3 benchmarks/tools/faults_tpch_q18.py --seed <n>`` runs
each at the cell's size on the chip. Not used by the benchmark's own runs.
"""


def _patched(module, name, wrap):
    """``wrap(original)`` takes the place of ``module.name`` for a run."""
    def tamper(job):
        import importlib

        mod = importlib.import_module(module)
        original = getattr(mod, name)
        setattr(mod, name, wrap(original))
        job._undo = lambda: setattr(mod, name, original)
    return tamper


def _having(change):
    """The HAVING predicate the executor builds, changed by ``change``
    (applied to a top-level ``sum(...) > x`` only)."""
    def wrap(original):
        from sparkdq4ml_tpu.ops import expressions as E

        def rewrite(expr, extra_aggs):
            out = original(expr, extra_aggs)
            if isinstance(out, E.BinOp) and out.op == ">" \
                    and isinstance(out.left, E.Col) \
                    and out.left.name.startswith("sum("):
                return change(E, out)
            return out
        return rewrite
    return _patched("sparkdq4ml_tpu.sql.parser", "_rewrite_having", wrap)


#: the HAVING lost: ``p OR NOT p`` keeps every group, so every order joins
lost_having = _having(lambda E, p: E.BinOp("|", p, E.UnaryOp("!", p)))
#: the HAVING compares with ``>=``: orders whose lines sum to exactly the
#: threshold join too
having_ge = _having(lambda E, p: E.BinOp(">=", p.left, p.right))


def skipped_block(job):
    """The grouped reduction skips a block of ``lineitem``: a GROUP BY over
    a frame as long as ``lineitem`` sees its second quarter of rows masked
    out, so the orders stored there sum no quantity and none qualifies."""
    def wrap(original):
        import jax.numpy as jnp

        def grouped_agg(frame, keys, agg_list):
            n = frame.num_slots
            if n == lines:
                i = jnp.arange(n)
                frame = frame.filter((i < n // 4) | (i >= n // 2))
            return original(frame, keys, agg_list)
        return grouped_agg

    lines = int(job.frames["lineitem"].num_slots)
    _patched("sparkdq4ml_tpu.ops.segments", "grouped_agg", wrap)(job)


def dropped_build_keys(job):
    """The semi join drops build keys: the build side, compacted to its
    valid rows, loses one in every eight, so those orders do not join."""
    def wrap(original):
        import jax.numpy as jnp

        def compact_rows(cols, mask):
            cols, mask, rows = original(cols, mask)
            return cols, mask & (jnp.arange(mask.shape[0]) % 8 != 0), rows
        return compact_rows

    _patched("sparkdq4ml_tpu.ops.joins", "compact_rows", wrap)(job)


FAULTS = {
    "tpch_q18_volume": {
        "lost_having": lost_having,
        "skipped_block": skipped_block,
        "having_ge": having_ge,
        "dropped_build_keys": dropped_build_keys,
    },
}


def main(argv=None):
    """python3 benchmarks/tools/faults_tpch_q18.py --seed <n> [--fault
    <name>[,<name>...]] [--seconds 4] [--rows <n>] [--cpu-ok]: each fault
    (or those named) through ``harness.execute`` at the cell's size, one
    JSON line a fault with ``correct`` and the numbers compared. Needs the
    chip, like a run. At full size ``lost_having`` joins every order: the
    joined frame is 2.4e8 rows of seven columns, more than the chip holds
    beside the tables, so name the others there."""
    import argparse
    import json
    import os
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, repo_root)
    from benchmarks import harness

    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--fault", default=None)
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--rows", type=int, default=None)
    parser.add_argument("--cpu-ok", action="store_true")
    args = parser.parse_args(argv)
    table = FAULTS["tpch_q18_volume"]
    names = args.fault.split(",") if args.fault else list(table)
    for k, name in enumerate(names):
        undo = []

        def tamper(job):
            table[name](job)
            undo.append(getattr(job, "_undo", lambda: None))

        try:
            line = harness.execute("tpch_q18_volume", args.seed + k,
                                   args.seconds, 0, repo_root,
                                   rows=args.rows,
                                   require_tpu=not args.cpu_ok,
                                   tamper=tamper)
        finally:
            for u in undo:
                u()
        print(json.dumps({"fault": name, "seed": args.seed + k,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
