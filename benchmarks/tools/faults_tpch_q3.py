"""Faults planted under ``tpch_q3_join``'s timed path, by name, like
``tools/faults_tpch_q1.py``: ``FAULTS[cell][name](job)`` breaks the program
or the job's answers for one run and leaves ``job._undo`` to mend it.
``benchmarks/tests/test_tpch_q3.py`` drives each through
``harness.execute(..., tamper=...)`` and sees ``correct`` come out false;
``tests/test_benchmark_cells.py`` drives the first. Not used by the
benchmark's own runs.
"""

from benchmarks.tools.faults_tpch_q1 import altered_answer


def half_probe(job):
    """Half of the probe rows left out of the ``lineitem`` join: the join
    whose right side is as long as ``lineitem`` sees the second half of
    that side's rows masked out, so the orders stored there lose their
    lines."""
    import jax.numpy as jnp

    from sparkdq4ml_tpu.frame.frame import Frame

    original = Frame.join
    lines = int(job.frames["lineitem"].num_slots)

    def join(self, other, on, how="inner", **kw):
        if other.num_slots == lines:
            other = other.filter(jnp.arange(lines) < lines // 2)
        return original(self, other, on, how, **kw)

    Frame.join = join
    job._undo = lambda: setattr(Frame, "join", original)


def lost_filter(job):
    """The ``customer`` join's filter lost: every customer carries the
    statement's segment, so the join keeps the orders of all of them."""
    import jax.numpy as jnp

    frame = job.frames["customer"]
    code = int(job.query.split("c_mktsegment = ")[1].split()[0])
    broken = frame.with_column(
        "c_mktsegment", jnp.full((frame.num_slots,), code, jnp.int32))
    broken.create_or_replace_temp_view("customer")
    job._undo = lambda: frame.create_or_replace_temp_view("customer")


FAULTS = {
    "tpch_q3_join": {
        "half_probe": half_probe,
        "lost_filter": lost_filter,
        "altered_revenue": altered_answer("revenue"),
    },
}
