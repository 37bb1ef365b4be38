#!/usr/bin/env python3
"""Where one cell's time goes by the program's own names. Needs the chip.

    python3 benchmarks/tools/scopes.py --workload <cell> [--seed 11] \
        [--seconds 4] [--out chiprun_out/scopes_<cell>]
    python3 benchmarks/tools/scopes.py --xplane <kept .xplane.pb>

Runs the cell's job back to back for ``--seconds`` under a profiler capture
of its own (the harness's options: python tracer off, host tracer 2, every
stage synced, ``bench.job`` / ``bench.<stage>`` annotations), keeps the
``.xplane.pb`` under ``--out`` and prints, per job:

* device-busy ms by named scope (``dq.<layer>`` in an operation's op
  metadata — the ``tf_op`` stat of its XEventMetadata, which
  ``jax.profiler.ProfileData`` does not expose, so the file is read from its
  wire format here: ``jax.named_scope`` inside the compiled programs), each
  operation without what is nested in it; ``(none)`` is what carries no
  scope;
* device-busy ms by program (the ``XLA Modules`` line), with the scopes its
  operations carry — the one-operation programs (``jit_multiply``, ...)
  show here whether a scope opened on the host around an eager call reaches
  them;
* device-idle ms by the innermost host span the main thread was in: the
  program's ``dq.<span>`` annotations and the benchmark's ``bench.<stage>``;
* device-busy ms by the innermost host span that dispatched the program
  (the k-th ``PjitFunction(<name>)`` of the main thread is the k-th run of
  ``jit_<name>`` on the device): the layer of an eager one-operation
  program, which no scope reaches;
* the program's spans from its in-memory tracer (count, median duration and
  self time per job) and the counters that moved, per job;
* two excerpts: the ``dq.*`` events under the first ``bench.fit``, and the
  device operations of the largest program with their scopes.

It is the prototype of what a ``benchmark`` PR moves into
``trace_reduce.read_profile`` (PERF.md section 7): nothing here feeds a
metric of ``BENCHMARK.json``.
"""

import argparse
import bisect
import gc
import glob
import json
import os
import re
import statistics
import sys
import time

SCOPE = re.compile(r"(?<![A-Za-z0-9_])dq\.[A-Za-z0-9_.]+")
DQ, BENCH = "dq.", "bench."
DISPATCH = re.compile(r"^PjitFunction\((.+)\)$")    # one per jitted call


def scope_of(op_name):
    """The innermost ``dq.<layer>`` of an operation's op metadata
    (``jit(fit)/jit(main)/while/body/dq.fit.newton.hessian/dot_general``
    -> ``dq.fit.newton.hessian``); None where it carries none."""
    found = SCOPE.findall(op_name or "")
    return found[-1].rstrip(".") if found else None


# -- the .xplane.pb, read from its wire format -------------------------------
# ``jax.profiler.ProfileData`` gives an event's own stats only; an
# operation's op metadata (the ``tf_op`` stat, where a named scope lands)
# hangs on the plane's XEventMetadata, which it does not expose. So the file
# is read here directly: protobuf's wire format, and of tsl's xplane.proto
# the few field numbers named below (XSpace.planes 1; XPlane name 2, lines
# 3, event_metadata 4, stat_metadata 5; XLine name 2, timestamp_ns 3,
# events 4; XEvent metadata_id 1, offset_ps 2, duration_ps 3, stats 4;
# XEventMetadata id 1, name 2, stats 5; XStatMetadata id 1, name 2; XStat
# metadata_id 1, uint64 3, int64 4, str 5, ref 7).

def varint(buf, i):
    """(value, next index) of the varint that starts at ``buf[i]``."""
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def fields(buf):
    """(field number, value) of one message: ints for varints and fixed
    widths, ``memoryview`` for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = varint(buf, i)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, i = varint(buf, i)
        elif kind == 2:
            size, i = varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value = int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield number, value


def text(view):
    return bytes(view).decode("utf-8", "replace")


def map_entry(view):
    """The value message of one map<int64, Message> entry."""
    for number, value in fields(view):
        if number == 2:
            return value
    return b""


def stats_of(view_list, stat_names):
    """{stat name: value} of a message's XStat fields."""
    out = {}
    for view in view_list:
        key = value = None
        for number, v in fields(view):
            if number == 1:
                key = v
            elif number in (3, 4):
                value = v
            elif number == 5:
                value = text(v)
            elif number == 7:
                value = stat_names.get(v, v)
        out[stat_names.get(key, key)] = value
    return out


def read(path, dump_stats=0):
    """Plain tuples out of one ``.xplane.pb``: device operations
    (plane, name, start_s, end_s, scope), programs (plane, name, start_s,
    end_s) and host annotations (line, name, start_s, end_s, stats), all on
    the capture's one clock."""
    from benchmarks import trace_reduce

    with open(path, "rb") as f:
        space = memoryview(f.read())
    ops, modules, host, seen = [], [], [], []
    for number, plane in fields(space):
        if number != 1:
            continue
        name, lines, event_md, stat_names = "", [], {}, {}
        for number, value in fields(plane):
            if number == 2:
                name = text(value)
            elif number == 3:
                lines.append(value)
            elif number == 4:
                event_md_raw = map_entry(value)
                md = {"name": "", "stats": []}
                for k, v in fields(event_md_raw):
                    if k == 1:
                        md["id"] = v
                    elif k == 2:
                        md["name"] = text(v)
                    elif k == 5:
                        md["stats"].append(v)
                event_md[md.get("id", 0)] = md
            elif number == 5:
                sid = sname = None
                for k, v in fields(map_entry(value)):
                    if k == 1:
                        sid = v
                    elif k == 2:
                        sname = text(v)
                stat_names[sid] = sname
        device = name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)
        if not device and name != "/host:CPU":
            continue
        for line in lines:
            line_name, base_ns, events = "", 0, []
            for number, value in fields(line):
                if number == 2:
                    line_name = text(value)
                elif number == 3:
                    base_ns = value
                elif number == 4:
                    events.append(value)
            if device and line_name not in (trace_reduce.OPS_LINE,
                                            trace_reduce.MODULES_LINE):
                continue
            for event in events:
                md_id = offset_ps = duration_ps = 0
                own = []
                for number, value in fields(event):
                    if number == 1:
                        md_id = value
                    elif number == 2:
                        offset_ps = value
                    elif number == 3:
                        duration_ps = value
                    elif number == 4:
                        own.append(value)
                md = event_md.get(md_id, {"name": "", "stats": []})
                start = base_ns * 1e-9 + offset_ps * 1e-12
                end = start + duration_ps * 1e-12
                if not device:
                    if md["name"].startswith((DQ, BENCH, "PjitFunction(")):
                        host.append((line_name, md["name"], start, end,
                                     stats_of(own, stat_names)))
                elif line_name == trace_reduce.MODULES_LINE:
                    modules.append((name, md["name"], start, end))
                else:
                    if "tf_op" not in md:      # decode once per operation
                        md["tf_op"] = stats_of(md["stats"],
                                               stat_names).get("tf_op", "")
                    if len(seen) < dump_stats and md["tf_op"]:
                        seen.append({"name": md["name"][:160],
                                     "tf_op": md["tf_op"]})
                    ops.append((name, md["name"], start, end,
                                scope_of(md["tf_op"])))
    return ops, modules, host, seen


class Busy:
    """Merged busy intervals with prefix sums: seconds busy inside [lo, hi]
    by two bisections."""

    def __init__(self, intervals):
        from benchmarks import trace_reduce

        self.merged = trace_reduce.union(intervals)
        self.starts = [s for s, _ in self.merged]
        self.upto = [0.0]
        for s, e in self.merged:
            self.upto.append(self.upto[-1] + e - s)

    def before(self, t):
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        s, e = self.merged[i - 1]
        return self.upto[i - 1] + min(t, e) - s

    def inside(self, lo, hi):
        return max(0.0, self.before(hi) - self.before(lo))


def idle_by_innermost(host, busy, lo, hi):
    """{span: idle seconds}: the window cut at every start and end of a
    host span; each piece goes to the span that started last among those
    open over it (spans of one thread nest), ``(outside)`` where none is."""
    edges = sorted({lo, hi} | {t for _, _, s, e, _ in host for t in (s, e)
                               if lo < t < hi})
    spans = sorted(host, key=lambda h: (h[2], -h[3]))
    out, stack, k = {}, [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(spans) and spans[k][2] <= a:
            stack.append(spans[k])
            k += 1
        while stack and stack[-1][3] <= a:
            stack.pop()
        # a span that closed under a still-open later one cannot happen on
        # one thread; drop stale entries anywhere to be safe
        stack = [h for h in stack if h[3] > a]
        name = stack[-1][1] if stack else "(outside)"
        out[name] = out.get(name, 0.0) + (b - a) - busy.inside(a, b)
    return out


def busy_by_dispatcher(on_main, dev_ops, modules, lo, hi):
    """{span: device-busy seconds} by the innermost host span that
    DISPATCHED the program: the device runs programs in the order the host
    sent them, so the k-th ``PjitFunction(<name>)`` event of the main
    thread inside the window is the k-th run of ``jit_<name>`` on the
    device, whenever that ran. This is what tells the eager one-operation
    programs apart (a rule's ``jit_multiply`` from the pack's), which carry
    no scope. Returns (by span, seconds that found no partner)."""
    from benchmarks import trace_reduce

    spans = sorted((h for h in on_main if not DISPATCH.match(h[1])),
                   key=lambda h: (h[2], -h[3]))
    calls = sorted((h for h in on_main if DISPATCH.match(h[1])
                    and lo <= h[2] < hi), key=lambda h: h[2])
    sent, stack, k = {}, [], 0          # program -> [dispatching span]
    for call in calls:
        while k < len(spans) and spans[k][2] <= call[2]:
            stack.append(spans[k])
            k += 1
        stack = [h for h in stack if h[3] > call[2]]
        sent.setdefault("jit_" + DISPATCH.match(call[1]).group(1),
                        []).append(stack[-1][1] if stack else "(outside)")
    ops = sorted(dev_ops, key=lambda o: o[2])
    starts = [o[2] for o in ops]
    out, unmatched, ran = {}, 0.0, {}
    for m in sorted((m for m in modules if lo <= m[2] < hi),
                    key=lambda m: m[2]):
        i, j = bisect.bisect_left(starts, m[2]), bisect.bisect_left(
            starts, m[3])
        busy = trace_reduce.clipped(trace_reduce.union(
            (o[2], o[3]) for o in ops[i:j]), m[2], m[3])
        name = program_name(m[1])
        nth = ran[name] = ran.get(name, -1) + 1
        if len(sent.get(name, ())) > nth:
            span = sent[name][nth]
            out[span] = out.get(span, 0.0) + busy
        else:
            unmatched += busy
    return out, unmatched


def program_name(module):
    """``jit_fit(1234567)`` -> ``jit_fit``."""
    return re.sub(r"\(\d+\)$", "", module)


def by_program(ops, modules):
    """{program: {"busy_s", "runs", "scopes": {scope: seconds}}}: an
    operation belongs to the program whose interval holds its start."""
    from benchmarks import trace_reduce

    out = {}
    for plane in sorted({m[0] for m in modules}):
        mods = sorted((m for m in modules if m[0] == plane),
                      key=lambda m: m[2])
        starts = [m[2] for m in mods]
        held = [[] for _ in mods]
        for _, name, s, e, scope in (o for o in ops if o[0] == plane):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][3]:
                held[i].append((scope or "(none)", s, e))
        for m, events in zip(mods, held):
            entry = out.setdefault(program_name(m[1]),
                                   {"busy_s": 0.0, "runs": 0, "scopes": {}})
            entry["runs"] += 1
            entry["busy_s"] += trace_reduce.clipped(
                trace_reduce.union((s, e) for _, s, e in events), m[2], m[3])
            for scope, sec in trace_reduce.self_seconds(
                    events, m[2], m[3]).items():
                entry["scopes"][scope] = entry["scopes"].get(scope, 0.0) + sec
    return out


def table(title, rows, jobs, top=24):
    print(f"\n{title} (ms a job over {jobs} jobs)")
    for name, sec in sorted(rows.items(), key=lambda kv: -kv[1])[:top]:
        print(f"  {1e3 * sec / jobs:10.3f}  {name}")


def run_cell(args, repo_root, out_dir):
    """The cell's jobs under one capture. Returns (path of the .xplane.pb
    or None, the program's spans, one record per job, counters a job,
    device, the job file's stage names), or None without the chip."""
    import jax

    from benchmarks import harness, program_spans

    spec = harness.load_cell(args.workload, repo_root)
    cfg, cfg_mod, traffic = spec["cfg"], spec["cfg_mod"], spec["traffic"]
    try:
        _, device = harness.device_info(1, not args.cpu_ok)
    except harness.NoChip as e:
        print(f"scopes: {e}", file=sys.stderr)
        return None
    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.utils.profiling import counters

    master = "tpu[*]" if device["platform"] == "tpu" else "local[*]"
    spark = (dq.TpuSession.builder().app_name("scopes").master(master)
             .get_or_create())
    made = cfg_mod.make_table(cfg, args.seed, args.rows)
    job = spec["job_mod"].Job(spark, cfg, cfg_mod, traffic["params"], made)
    for _ in range(harness.WARMUP_JOBS):
        job.run(harness.Stages(True))
        gc.collect()
    gc.freeze()

    tracer = harness.Tracer(out_dir, args.seconds)
    before = counters.snapshot()
    tracer.start()
    t0, records = time.perf_counter(), []
    while time.perf_counter() - t0 < args.seconds:
        record = {"submit": time.perf_counter()}
        stages = harness.Stages(True)
        with jax.profiler.TraceAnnotation("bench.job"):
            job.run(stages)
        record["done"] = time.perf_counter()
        record["spans"] = stages.seconds
        records.append(record)
        gc.collect()
    tracer.maybe_stop(force=True)
    moved = harness.counter_delta(counters.snapshot(), before)
    spans = program_spans.recorded()
    job.close()
    spark.stop()
    files = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return (files[-1] if files else None, spans, records,
            {k: v / len(records) for k, v in moved.items()}, device,
            spec["job_mod"].SPANS)


def report_capture(path, dump_stats, report):
    """Everything read from the kept ``.xplane.pb``."""
    from benchmarks import trace_reduce

    ops, modules, host, seen = read(path, dump_stats)
    for item in seen:
        print("device operation and its op metadata:", json.dumps(item))
    jobs = sorted((s, e) for _, name, s, e, _ in host
                  if name == BENCH + trace_reduce.JOB_SPAN)
    if not jobs or not ops:
        print(f"scopes: {len(jobs)} bench.job events, {len(ops)} device "
              f"operations: nothing to reduce (no device plane on the CPU)")
        return
    lo, hi = jobs[0][0], max(e for _, e in jobs)
    n = len(jobs)
    first = sorted({o[0] for o in ops})[0]
    dev_ops = [o for o in ops if o[0] == first]
    dev_modules = [m for m in modules if m[0] == first]
    busy = Busy((s, e) for _, _, s, e, _ in dev_ops if e > lo and s < hi)
    print(f"\n{n} jobs in {hi - lo:.4f} s; device busy "
          f"{busy.inside(lo, hi):.4f} s = "
          f"{1e3 * busy.inside(lo, hi) / n:.3f} ms a job; idle share "
          f"{100 * (1 - busy.inside(lo, hi) / (hi - lo)):.2f} %")
    scoped = trace_reduce.self_seconds(
        [(scope or "(none)", s, e) for _, _, s, e, scope in dev_ops],
        lo, hi)
    table("device busy by named scope", scoped, n)
    programs = by_program(dev_ops, dev_modules)
    print(f"\ndevice busy by program (ms a job over {n} jobs; runs a "
          "job; scopes its operations carry)")
    for name, p in sorted(programs.items(),
                          key=lambda kv: -kv[1]["busy_s"])[:24]:
        carried = ", ".join(
            f"{k} {1e3 * v / n:.2f}" for k, v in
            sorted(p["scopes"].items(), key=lambda kv: -kv[1])[:6])
        print(f"  {1e3 * p['busy_s'] / n:10.3f}  {name}  "
              f"x{p['runs'] / n:.1f}  [{carried}]")
    main_line = next(line for line, name, *_ in host
                     if name == BENCH + trace_reduce.JOB_SPAN)
    on_main = [h for h in host if h[0] == main_line]
    annotations = [h for h in on_main if not DISPATCH.match(h[1])]
    idle = idle_by_innermost(annotations, busy, lo, hi)
    table("device idle by the innermost host span", idle, n)
    sent, unmatched = busy_by_dispatcher(on_main, dev_ops, dev_modules,
                                         lo, hi)
    table("device busy by the innermost host span that dispatched the "
          f"program (found no partner: {1e3 * unmatched / n:.3f})", sent, n)
    report.update({
        "window_s": hi - lo, "busy_s": busy.inside(lo, hi),
        "traced_jobs": n, "by_scope_s": scoped, "by_program": programs,
        "idle_by_span_s": idle, "busy_by_dispatcher_s": sent})
    fits = sorted((h for h in annotations if h[1] == BENCH + "fit"),
                  key=lambda h: h[2])
    if fits:
        _, _, fs, fe, _ = fits[0]
        print(f"\nexcerpt: host line {main_line!r}, first bench.fit "
              f"[0, {1e3 * (fe - fs):.3f}] ms and the dq.* events in it")
        for _, name, s, e, stats in sorted(
                (h for h in annotations if h[1].startswith(DQ)
                 and fs <= h[2] and h[3] <= fe), key=lambda h: h[2]):
            print(f"  {1e3 * (s - fs):9.3f} +{1e3 * (e - s):9.3f} ms  "
                  f"{name}  {json.dumps(stats)}")
    largest = max(programs, key=lambda k: programs[k]["busy_s"])
    mod = next(m for m in dev_modules
               if program_name(m[1]) == largest and m[2] >= lo)
    print(f"\nexcerpt: device operations of one run of {largest}")
    inside = [o for o in dev_ops if mod[2] <= o[2] < mod[3]]
    for _, name, s, e, scope in sorted(inside,
                                       key=lambda o: o[2] - o[3])[:14]:
        print(f"  {1e3 * (e - s):9.3f} ms  "
              f"{trace_reduce.short_name(name)[:44]:44s}  "
              f"{scope or '(none)'}")


def report_spans(spans, records, stage_names, report):
    """The program's spans from its in-memory tracer, and the benchmark's
    stages, per job."""
    from benchmarks import program_spans

    n = len(records)
    print(f"\nthe program's spans, from its tracer ({len(spans)} spans over "
          f"{n} jobs): count a job, median ms, median self ms")
    report["spans"] = {}
    for name, found in sorted(
            program_spans.by_name(spans).items(),
            key=lambda kv: -sum(s["dur_s"] for s in kv[1])):
        row = {"per_job": len(found) / n,
               "median_ms": 1e3 * statistics.median(
                   s["dur_s"] for s in found),
               "self_ms": 1e3 * statistics.median(
                   s["self_s"] for s in found)}
        report["spans"][name] = row
        print(f"  {row['per_job']:5.1f}  {row['median_ms']:10.3f}  "
              f"{row['self_ms']:10.3f}  {name}")
    report["stages_ms"] = {}
    for stage in stage_names:
        report["stages_ms"][stage] = 1e3 * statistics.median(
            r["spans"].get(stage, 0.0) for r in records)
        print(f"bench.{stage}: median {report['stages_ms'][stage]:.3f} ms")
    report["job_ms"] = 1e3 * statistics.median(
        r["done"] - r["submit"] for r in records)
    print(f"job: median {report['job_ms']:.3f} ms")
    print("counters a job:", json.dumps(report["counters_per_job"]))


def main(argv=None):
    here = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(os.path.dirname(here))
    sys.path.insert(0, repo_root)
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload")
    parser.add_argument("--xplane", help="reduce a kept capture again "
                        "and run nothing (needs no chip)")
    parser.add_argument("--seed", type=int, default=11)
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--out", default=None)
    parser.add_argument("--rows", type=int, default=None)
    parser.add_argument("--cpu-ok", action="store_true")
    parser.add_argument("--dump-stats", type=int, default=3)
    args = parser.parse_args(argv)
    if args.xplane:
        report_capture(args.xplane, args.dump_stats, {})
        return 0
    if not args.workload:
        parser.error("--workload or --xplane")
    out_dir = args.out or os.path.join(repo_root, "chiprun_out",
                                       "scopes_" + args.workload)
    os.makedirs(out_dir, exist_ok=True)
    ran = run_cell(args, repo_root, out_dir)
    if ran is None:
        return 2
    path, spans, records, moved, device, stage_names = ran
    if path is None:
        print("scopes: the capture left no .xplane.pb", file=sys.stderr)
        return 1
    print(f"capture kept: {os.path.relpath(path, repo_root)} "
          f"({os.path.getsize(path)} bytes); device {device}")
    report = {"workload": args.workload, "device": device,
              "jobs": len(records), "counters_per_job": moved}
    report_capture(path, args.dump_stats, report)
    report_spans(spans, records, stage_names, report)
    with open(os.path.join(out_dir, "scopes.json"), "w") as f:
        json.dump(report, f, indent=1, default=float)
    return 0


if __name__ == "__main__":
    sys.exit(main())
