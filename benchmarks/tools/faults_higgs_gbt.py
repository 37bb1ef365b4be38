"""Faults planted under ``higgs_gbt``'s timed path, by name, like
``tools/faults_tpch_q3.py``: ``FAULTS[cell][name](job)`` breaks the program
for one run and leaves ``job._undo`` to mend it.
``benchmarks/tests/test_higgs_gbt.py`` drives each through
``harness.execute(..., tamper=...)`` and sees ``correct`` come out false;
``tests/test_benchmark_cells.py`` drives the first. Not used by the
benchmark's own runs.
"""


def _patched(name, wrap):
    """``wrap(original)`` takes the place of ``models.tree.<name>``."""
    def tamper(job):
        from sparkdq4ml_tpu.models import tree

        original = getattr(tree, name)
        setattr(tree, name, wrap(original))
        clear_programs(tree)
        job._undo = lambda: (setattr(tree, name, original),
                             clear_programs(tree))
    return tamper


def clear_programs(tree):
    """The compiled fits close over the functions a fault replaces."""
    for cached in (tree._bin_program, tree._gbt_programs,
                   tree._forest_builder):
        cached.cache_clear()


def half_histogram(original):
    """Half of the rows left out of the histogram pass: every level's
    statistics see the first half of the row slots only."""
    def histogram(binned, node_pos, targets, n_nodes, B, psum_axis=None):
        import jax.numpy as jnp

        n = binned.shape[1]
        first = jnp.arange(n) < n // 2
        return original(binned, jnp.where(first[None, :], node_pos, n_nodes),
                        targets, n_nodes, B, psum_axis)
    return histogram


def shifted_bin(job):
    """A bin off by one for one feature — the one the labels lean on most
    (the largest ``beta`` of the configuration: a feature no tree splits
    on would hide the fault, as ``x0`` does at full size): its values above
    their lowest threshold land one bin too high."""
    import numpy as np

    feature = int(np.argmax(np.abs(job.cfg["assumed"]["beta"])))

    def wrap(original):
        def bins(Xt, edges, max_bins):
            import jax.numpy as jnp

            out = original(Xt, edges, max_bins)
            row = jnp.minimum(out[feature] + (out[feature] > 0),
                              max_bins - 1)
            return out.at[feature].set(row.astype(out.dtype))
        return bins

    _patched("device_bins", wrap)(job)


def altered_leaves(job):
    """A leaf 1 % off: every node's gradient sum, as the job reads it from
    the model, stands 1 % high."""
    import numpy as np

    run = job.run

    def altered(stages):
        result = run(stages)
        value = np.array(result["value"], np.float64)
        value[:, :, 1] *= 1.01
        result["value"] = value
        return result

    job.run = altered


FAULTS = {
    "higgs_gbt": {
        "half_histogram": _patched("_level_histogram", half_histogram),
        "shifted_bin": shifted_bin,
        "altered_leaves": altered_leaves,
    },
}


def main(argv=None):
    """python3 benchmarks/tools/faults_higgs_gbt.py --seed <n> [--fault <name>]
    [--seconds 4] [--rows <n>] [--cpu-ok]: each fault (or the one named)
    through ``harness.execute`` at the cell's size, one JSON line a fault
    with ``correct`` and the numbers compared. Needs the chip, like a run."""
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
    table = FAULTS["higgs_gbt"]
    for k, name in enumerate([args.fault] if args.fault else sorted(table)):
        undo = []

        def tamper(job):
            table[name](job)
            undo.append(getattr(job, "_undo", lambda: None))

        try:
            line = harness.execute("higgs_gbt", args.seed + k, args.seconds,
                                   0, repo_root, rows=args.rows,
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
