"""Median over the profiled jobs of the job's summed ``sql.subquery.in``
spans (host clock, read from inside: each from the subquery's execution to
the join it became — or, on the literal path, to its values on the host —
so it holds the subquery's GROUP BY and HAVING, the compaction of the
groups that pass and the semi join, ended by the join's read of its row
count). Per job, not per span. None where the program records no such span
(a program without the rewrite)."""

from benchmarks import program_spans


def read(run):
    return program_spans.per_job_ms(run, ("sql.subquery.in",))
