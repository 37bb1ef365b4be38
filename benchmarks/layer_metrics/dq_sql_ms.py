"""Median per job of the benchmark's span around the DQ rules, the SQL
filters and their counts (host clock; in a traced run the span ends when the
stage's output is ready)."""


def read(run):
    spans = [j["spans"]["dq_sql"] for j in run["jobs"] if "dq_sql" in j["spans"]]
    return 1e3 * run["median"](spans) if spans else None
