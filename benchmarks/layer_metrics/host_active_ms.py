"""Median over the profiled jobs of ``done - submit`` less the union of the
job's ``host.read`` spans (host clock, read from inside): the host's own
time in a job — Python, parse and plan, dispatch, the hooks of the dq
profile, garbage — during which the chip runs what was queued, or starves.
It is the ceiling of the idle time the host can cause: read it beside
``device_idle_share`` x the traced job. Where it stands far over the idle
time the host overlaps with the chip; where the two are close, every
millisecond of host code is a millisecond of the job.

In a traced run it also holds the one wait that lies outside the program:
the benchmark's ``sync`` at the end of a stage (``jax.block_until_ready``
on the stage's output, so that the device's work for a stage ends inside
its span). After ``dq_sql`` in the HIGGS cells that is at most
``dq_sql_ms`` (1.6 ms); after ``q1`` / ``q3`` it is what the statement left
queued behind its last read. The stages that end in a read of their own
(``fit``, ``score``) leave nothing to wait for."""

from benchmarks import host_split


def read(run):
    return host_split.median_ms(run, "active_s")
