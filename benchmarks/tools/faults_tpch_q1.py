"""Faults planted under ``tpch_q1_sf30``'s timed path, by name, like
``tools/faults.py`` (whose table is keyed by cell and is not edited):
``FAULTS[name](job)`` breaks the program or the job's answers for one run
and leaves ``job._undo`` to mend it. ``benchmarks/tests/test_tpch_q1.py``
drives each through ``harness.execute(..., tamper=...)`` and sees ``correct``
come out false; ``--fault`` on the chip is read with a few lines around
``execute`` (PERF.md section 2). Not used by the benchmark's own runs.
"""

import numpy as np


def half_reduce(job):
    """Half of the rows left out of the grouped reduction: the grouped
    entry point sees the second half of the frame's rows masked out, so
    every group's count and sums are of the first half only."""
    import jax.numpy as jnp

    from sparkdq4ml_tpu.ops import segments

    original = segments.grouped_agg

    def grouped_agg(frame, keys, agg_list):
        keep = jnp.arange(frame.num_slots) < frame.num_slots // 2
        return original(frame.filter(keep), keys, agg_list)

    segments.grouped_agg = grouped_agg
    job._undo = lambda: setattr(segments, "grouped_agg", original)


def altered_answer(key):
    """An answer altered where it is produced: 1 % off."""
    def tamper(job):
        run = job.run

        def altered(stages):
            result = run(stages)
            result[key] = np.asarray(result[key]) * 1.01
            return result

        job.run = altered
    return tamper


FAULTS = {
    "tpch_q1_sf30": {
        "half_reduce": half_reduce,
        "altered_sum": altered_answer("sum_charge"),
        "altered_avg": altered_answer("avg_disc"),
    },
}
