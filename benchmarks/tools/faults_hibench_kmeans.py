"""Faults planted under ``hibench_kmeans``'s timed path, by name, like
``tools/faults_higgs_gbt.py``: ``FAULTS[cell][name](job)`` breaks the
program for one run and leaves ``job._undo`` to mend it.
``benchmarks/tests/test_hibench_kmeans.py`` drives each through
``harness.execute(..., tamper=...)`` and sees ``correct`` come out false;
``tests/test_benchmark_cells.py`` drives the first. ``VARIANTS`` holds what
is no fault on every backend: ``default_precision`` runs Lloyd's distances
as ``‖x‖² − 2x·c + ‖c‖²`` with the matmul at the backend's default
precision — bfloat16 operands on a TPU, exact on the CPU of the tests. Not
used by the benchmark's own runs.
"""


def clear_programs(clustering):
    """The compiled programs close over the functions a fault replaces
    (a program a fault has replaced is no cache)."""
    for cached in (clustering._init_program, clustering._lloyd_program,
                   clustering._score_program):
        getattr(cached, "cache_clear", lambda: None)()


def _patched(name, wrap):
    """``wrap(original)`` takes the place of ``models.clustering.<name>``."""
    def tamper(job):
        from sparkdq4ml_tpu.models import clustering

        original = getattr(clustering, name)
        setattr(clustering, name, wrap(original))
        clear_programs(clustering)
        job._undo = lambda: (setattr(clustering, name, original),
                             clear_programs(clustering))
    return tamper


def half_pass(original):
    """A Lloyd pass leaves out half of the rows: where the coordinate sums
    are asked for, the second half of the row slots weighs nothing."""
    def device_pass(xt, w, centres, ok=None, prev=None, base=0,
                    rows_out=False, sums=False, slots=0, lowering="xla"):
        import jax.numpy as jnp

        if sums:
            first = jnp.arange(w.shape[1]) < xt.shape[1] // 2
            w = jnp.where(first[None, :], w, 0.0)
        return original(xt, w, centres, ok, prev, base, rows_out, sums,
                        slots, lowering)
    return device_pass


def dropped_rows_vote(job):
    """The filter's mask is ignored by the fit: every row slot votes."""
    from sparkdq4ml_tpu.models import clustering

    fit, features = clustering.KMeans.fit, clustering._features

    def every_row(frame, features_col):
        import jax.numpy as jnp

        X, mask = features(frame, features_col)
        return X, jnp.ones_like(mask)

    def unmasked_fit(self, frame, mesh=None):
        clustering._features = every_row
        try:
            return fit(self, frame, mesh)
        finally:
            clustering._features = features

    clustering.KMeans.fit = unmasked_fit
    job._undo = lambda: setattr(clustering.KMeans, "fit", fit)


def one_step_short(original):
    """The history is one iteration longer than what ran: the loop runs
    ``max_iter - 1`` iterations and reports its last centres twice."""
    def lloyd_program(max_iter, tol, lowering):
        import jax.numpy as jnp

        shorter = original(max_iter - 1, tol, lowering)

        def run(X, mask, centres0):
            floats, sizes = shorter(X, mask, centres0)
            per = centres0.size
            history, tail = floats[:-2], floats[-2:]
            iters = tail[0].astype(jnp.int32)
            last = jnp.take(history.reshape(-1, per), iters, axis=0)
            return jnp.concatenate([history, last,
                                    jnp.stack([tail[0] + 1, tail[1]])]), \
                sizes
        return run
    return lloyd_program


def foreign_candidate(original):
    """A candidate that is a dropped row: the first dropped row takes the
    place of the seeding's second candidate."""
    def init_program(k, steps, bucket, lowering):
        import jax.numpy as jnp

        program = original(k, steps, bucket, lowering)

        def run(X, mask, key):
            cands, ok, weights, drawn = program(X, mask, key)
            dropped = jnp.argmax(jnp.logical_not(mask))
            return cands.at[1].set(X[dropped]), ok, weights, drawn
        return run
    return init_program


def default_precision(original):
    """Lloyd's distances as ``‖x‖² − 2 x·c + ‖c‖²``, the product a matmul
    at the backend's default precision, in blocks of rows (the last block
    overlaps the one before it and counts its own rows only)."""
    block = 1 << 20

    def device_pass(xt, w, centres, ok=None, prev=None, base=0,
                    rows_out=False, sums=False, slots=0, lowering="xla"):
        import jax
        import jax.numpy as jnp

        from sparkdq4ml_tpu.models import clustering

        if not sums:
            return original(xt, w, centres, ok, prev, base, rows_out, sums,
                            slots, lowering)
        d, n = xt.shape
        K = centres.shape[0]
        size = min(block, n)
        c_sq = jnp.sum(centres * centres, axis=1)

        def one(i, carry):
            cost, counts, total = carry
            start = jnp.minimum(i * size, n - size)
            x = jax.lax.dynamic_slice(xt, (0, start), (d, size))
            wb = jax.lax.dynamic_slice(w, (0, start), (1, size))[0]
            own = jnp.logical_and(start + jnp.arange(size) >= i * size,
                                  wb > 0)
            d2 = jnp.sum(x * x, axis=0)[:, None] - 2.0 * (x.T @ centres.T) \
                + c_sq[None, :]
            arg = jnp.argmin(d2, axis=1)
            hit = jnp.logical_and(arg[:, None] == jnp.arange(K)[None, :],
                                  own[:, None])
            return (cost + jnp.sum(jnp.where(own, jnp.min(d2, axis=1), 0.0)),
                    counts + jnp.sum(hit, axis=0, dtype=jnp.int32),
                    total + jnp.where(hit, 1.0, 0.0).T
                    @ jnp.where(own[None, :], x, 0.0).T)

        cost, counts, total = jax.lax.fori_loop(
            0, -(-n // size), one,
            (jnp.zeros((), xt.dtype), jnp.zeros((K,), jnp.int32),
             jnp.zeros((K, d), xt.dtype)))
        return clustering.PassOut(
            cost, counts[:slots] if slots else None, total, None, None)
    return device_pass


FAULTS = {
    "hibench_kmeans": {
        "half_pass": _patched("device_pass", half_pass),
        "dropped_rows_vote": dropped_rows_vote,
        "one_step_short": _patched("_lloyd_program", one_step_short),
        "foreign_candidate": _patched("_init_program", foreign_candidate),
    },
}
VARIANTS = {
    "hibench_kmeans": {
        "default_precision": _patched("device_pass", default_precision),
    },
}


def main(argv=None):
    """python3 benchmarks/tools/faults_hibench_kmeans.py --seed <n>
    [--fault <name>] [--seconds 4] [--rows <n>] [--cpu-ok]: each fault (or
    the one named, a variant too) through ``harness.execute`` at the cell's
    size, one JSON line a fault with ``correct`` and the numbers compared.
    Needs the chip, like a run."""
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
    table = dict(FAULTS["hibench_kmeans"], **VARIANTS["hibench_kmeans"])
    names = [args.fault] if args.fault else list(FAULTS["hibench_kmeans"])
    for k, name in enumerate(names):
        undo = []

        def tamper(job):
            table[name](job)
            undo.append(getattr(job, "_undo", lambda: None))

        try:
            line = harness.execute("hibench_kmeans", args.seed + k,
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
