"""Loop kind ``closed``: each client sends its next job when the last one
has answered. One client today (``clients: 1``): jobs back to back.

A loop kind is a file here, found by the ``loop`` key of the traffic file.
``run`` drives ``submit(index, due)`` until ``seconds`` have passed and
returns one record per job; an ``open`` loop (fixed rate, latency from the
due time) is another file with the same ``run``.
"""


def run(traffic, seconds, clock, submit, after_job):
    """Records [{"due", "submit", "done", "ok", "result", ...}] of every job
    started inside the window, and the window's (start, end): the end is
    when the last job answered, so every job and all of its time count."""
    if int(traffic.get("clients", 1)) != 1:
        raise NotImplementedError("closed loop: one client only")
    records = []
    start = clock()
    while True:
        t = clock()
        if t - start >= seconds:
            break
        record = {"due": t, "submit": t}
        record.update(submit(len(records)))
        record["done"] = clock()
        records.append(record)
        after_job(record)
    return records, start, clock()
