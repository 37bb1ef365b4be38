"""From a profiler trace to busy time, idle share, device time inside a
named host span, the top device operations and the idle gaps.

Two steps, so that the arithmetic can be pinned on a hand-built event list:
``read_profile`` turns an ``.xplane.pb`` into plain (name, start_s, end_s)
tuples per device and for the benchmark's host annotations; ``reduce`` does
the rest and touches no profiler type.

Planes (looked at by hand on a v5e trace, PERF.md section 3): a device is a
plane named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO operation (``XLA Modules`` holds one per program and ``Steps``
the step markers: both overlap the operations and are not counted). The
benchmark's ``jax.profiler.TraceAnnotation`` spans are events named
``bench.<span>`` on the host plane's thread lines, on the same clock.
"""

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
JOB_SPAN = "job"


def read_profile(path):
    """{"devices": {plane: [(name, start_s, end_s)]}, "spans": [...],
    "planes": {plane: {line: events}}, "modules": [(program, seconds)]}
    from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, planes, modules = {}, [], {}, {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            count = 0
            for ev in line.events:
                count += 1
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                if is_device and line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).append(
                        (ev.name, start, end))
                elif is_device and line.name == MODULES_LINE:
                    modules[ev.name] = modules.get(ev.name, 0.0) + end - start
                elif not is_device and ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name[len(SPAN_PREFIX):], start, end))
            lines[line.name] = lines.get(line.name, 0) + count
    return {"devices": devices, "spans": spans, "planes": planes,
            "modules": sorted(modules.items(), key=lambda kv: -kv[1])}


def short_name(op):
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: the trace names
    an operation by its whole HLO line."""
    return op.split(" = ", 1)[0].lstrip("%")[:120]


def self_seconds(events, lo, hi):
    """{short name: seconds} inside [lo, hi], each operation less the
    operations that ran inside it: a ``while`` or ``conditional`` holds its
    body's operations as events of their own, and would count them twice."""
    out, stack = {}, []

    def close(item):
        name, s, e, inside = item
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[name] = out.get(name, 0.0) + (e - s) - inside
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += max(0.0, min(e, stack[-1][2], hi) - max(s, lo))
        stack.append([short_name(name), s, e, 0.0])
    while stack:
        close(stack.pop())
    return {k: v for k, v in out.items() if v > 0.0}


def union(intervals):
    """Merged, sorted, non-overlapping [(start, end)] of ``intervals``."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clipped(intervals, lo, hi):
    """Seconds of the merged ``intervals`` that lie inside [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def reduce(devices, spans, top=10):
    """The traced window is from the first ``job`` span's start to the last
    one's end. Returns None where the trace holds no job span or no device
    operation inside the window.

    busy_s            union of device-op intervals in the window, averaged
                      over the devices that ran any
    window_s          length of the traced window
    idle_share        1 - busy_s / window_s
    span_device_s     {span: [device-busy seconds inside each occurrence]}
    span_host_s       {span: [length of each occurrence]}
    device_ops        [[name, seconds]] the operations that took most time,
                      each without the operations nested in it
    idle_gaps         [[span, seconds]] idle time of the first device by the
                      span the host was in (``job``: inside a job, between
                      its stages; ``between_jobs``: outside every job)
    """
    jobs = sorted((s, e) for name, s, e in spans if name == JOB_SPAN)
    if not jobs:
        return None
    lo, hi = jobs[0][0], max(e for _, e in jobs)
    busy, per_op, first_busy = [], {}, None
    for plane in sorted(devices):
        merged = union((s, e) for _, s, e in devices[plane]
                       if e > lo and s < hi)
        if not merged:
            continue
        busy.append(clipped(merged, lo, hi))
        if first_busy is None:
            first_busy = merged
        for name, seconds in self_seconds(devices[plane], lo, hi).items():
            per_op[name] = per_op.get(name, 0.0) + seconds
    if not busy:
        return None
    window_s = hi - lo
    busy_s = sum(busy) / len(busy)
    inner = sorted(((s, e, name) for name, s, e in spans
                    if name != JOB_SPAN and e > lo and s < hi))
    span_device_s, span_host_s = {}, {}
    for s, e, name in inner:
        span_device_s.setdefault(name, []).append(clipped(first_busy, s, e))
        span_host_s.setdefault(name, []).append(e - s)

    # idle time by what the host was doing: the stage spans lie side by
    # side inside a job span (they do not nest), so each one's idle time is
    # its length less the busy time inside it; the rest of a job is
    # ``job`` (between its stages) and the rest of the window
    # ``between_jobs``
    gaps = {}
    for s, e, name in inner:
        s, e = max(s, lo), min(e, hi)
        gaps[name] = gaps.get(name, 0.0) + (e - s) - clipped(first_busy, s, e)
    in_jobs = sum((min(e, hi) - s) - clipped(first_busy, s, min(e, hi))
                  for s, e in jobs)
    total = window_s - clipped(first_busy, lo, hi)
    gaps[JOB_SPAN] = in_jobs - sum(gaps.values())
    gaps["between_jobs"] = total - in_jobs
    gaps = {k: v for k, v in gaps.items() if v > 1e-9}
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "span_device_s": span_device_s, "span_host_s": span_host_s,
        "device_ops": [[k, v] for k, v in ranked],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        "devices": len(busy), "jobs": len(jobs),
    }
