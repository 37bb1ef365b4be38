"""The runner: one run of one cell, driven by ``BENCHMARK.json`` and the
files it names. Names no cell, configuration, job or metric.

    BENCHMARK.json  workloads[name] -> config, traffic, chips
                    configs[name].file -> the configuration's JSON; its
                    plain reference and generator are the .py beside it
    traffic/<traffic>.json -> job, loop kind, parameters, limits
    jobs/<job>.py          -> Job (prepare, run one job), reference, compare
    loops/<loop>.py        -> the window's loop
    end_to_end/<metric>.py, layer_metrics/<metric>.py -> read(run) -> number

A run: set-up (chip claimed, session, table from the seed on the device,
warm-up jobs) -> the window (jobs for ``seconds``) -> peak memory read ->
table pulled to the host, program state freed -> float64 reference -> every
job's answers compared -> one JSON line.
"""

import contextlib
import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DEGRADED_COUNTERS = (
    "pipeline.fault_fallback", "pipeline.fallback", "pipeline.oom_chunked",
    "pipeline.shard_gather", "dq.profile_failed", "dq.pending_dropped",
    "grouped.fault_fallback", "grouped.fallback")
COMPILE_COUNTERS = ("pipeline.compile", "grouped.compile")
WARMUP_JOBS = 2        # the first compiles (or loads), the second runs warm
TRACE_SECONDS = 4.0    # of the window's start that a traced run profiles


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def load_module(kind, name, root=HERE):
    """The module ``<root>/<kind>/<name>.py``, found by name."""
    path = os.path.join(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}".replace("-", "_").replace(".", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload, repo_root):
    """Everything one cell is made of, from the files that name it."""
    bench = load_json(os.path.join(repo_root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg_path = os.path.join(repo_root, entry["file"])
    bench_root = os.path.dirname(os.path.dirname(cfg_path))
    traffic = load_json(os.path.join(bench_root, "traffic",
                                     cell["traffic"] + ".json"))

    def reports(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell, "root": bench_root,
        "cfg": load_json(cfg_path),
        "cfg_mod": load_module("configs", cell["config"], bench_root),
        "traffic": traffic,
        "job_mod": load_module("jobs", traffic["job"], bench_root),
        "loop_mod": load_module("loops", traffic["loop"], bench_root),
        "peaks": load_json(os.path.join(bench_root, "peaks.json")),
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def device_info(chips, require_tpu):
    import jax

    devices = jax.devices()
    first = devices[0]
    if require_tpu and (first.platform != "tpu" or len(devices) < chips):
        raise NoChip(f"platform {first.platform!r}, kind "
                     f"{first.device_kind!r}, {len(devices)} device(s); the "
                     f"cell asks for {chips} TPU chip(s)")
    return devices, {"platform": first.platform, "kind": first.device_kind,
                     "count": len(devices)}


def peaks_for(peaks, kind):
    if kind not in peaks:
        raise KeyError(f"no published peaks for device_kind {kind!r} in "
                       "peaks.json: add it with its source")
    return peaks[kind]


def memory_stats(devices):
    """(bytes in use, peak bytes) on the fullest device; (0, 0) where the
    backend reports none (the CPU of the tests)."""
    in_use = peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        in_use = max(in_use, int(stats.get("bytes_in_use", 0)))
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return in_use, peak


class Stages:
    """The benchmark's own spans around its calls into the program. Host
    clock always; in a traced run each span is also written into the
    profiler's trace and ``sync`` waits for the stage's output, so that the
    device's work for a stage ends inside its span."""

    def __init__(self, traced):
        self.traced = traced
        self.seconds = {}

    @contextlib.contextmanager
    def __call__(self, name):
        import jax

        def sync(output):
            if self.traced:
                jax.block_until_ready(output())

        t0 = time.perf_counter()
        if self.traced:
            with jax.profiler.TraceAnnotation("bench." + name):
                yield sync
        else:
            yield sync
        self.seconds[name] = (self.seconds.get(name, 0.0)
                              + time.perf_counter() - t0)


class Tracer:
    """The profiler over the first ``seconds`` of the window."""

    def __init__(self, directory, seconds):
        self.directory, self.seconds = directory, seconds
        self.started = self.stopped = None

    def start(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.started = time.perf_counter()

    def maybe_stop(self, force=False):
        import jax

        if self.started is None or self.stopped is not None:
            return
        if force or time.perf_counter() - self.started >= self.seconds:
            jax.profiler.stop_trace()
            self.stopped = time.perf_counter()

    def reduced(self):
        from benchmarks import trace_reduce

        files = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not files:
            return None, {}, []
        raw = trace_reduce.read_profile(files[-1])
        shutil.rmtree(self.directory, ignore_errors=True)
        return (trace_reduce.reduce(raw["devices"], raw["spans"]),
                raw["planes"], raw["modules"])


def counter_delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def percentile(values, share):
    """Nearest-rank percentile: the smallest value with at least ``share``
    of the sample at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(share * len(ordered)) - 1)]


def execute(workload, seed, seconds, trace, repo_root, t0=None,
            require_tpu=True, rows=None, scratch=None, tamper=None):
    """One run of ``workload``; returns the result line as a dict.

    ``rows`` (tests only) runs the same code on a smaller table;
    ``tamper(job)`` (tests only) breaks the timed path underneath before
    the run. Raises :class:`NoChip` before any result where the chips are
    not there."""
    t0 = time.perf_counter() if t0 is None else t0
    spec = load_cell(workload, repo_root)
    cfg, cfg_mod, traffic = spec["cfg"], spec["cfg_mod"], spec["traffic"]
    job_mod = spec["job_mod"]

    import jax

    devices, device = device_info(int(spec["cell"]["chips"]), require_tpu)
    devices = devices[:int(spec["cell"]["chips"])]
    phases = {"chip_claimed": time.perf_counter() - t0}

    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.utils.profiling import counters

    programs = {"built": 0}

    def on_event(name, **kw):
        if name in ("/jax/compilation_cache/cache_hits",
                    "/jax/compilation_cache/cache_misses"):
            programs["built"] += 1

    jax.monitoring.register_event_listener(on_event)
    master = "tpu[*]" if device["platform"] == "tpu" else "local[*]"
    spark = (dq.TpuSession.builder().app_name("benchmarks")
             .master(master).get_or_create())
    log(f"[setup] {workload} seed={seed} device={device} compile cache "
        f"{jax.config.jax_compilation_cache_dir}")

    phases["session"] = time.perf_counter() - t0
    table = cfg_mod.make_table(cfg, seed, rows)
    job = job_mod.Job(spark, cfg, cfg_mod, traffic["params"], table)
    phases["table"] = time.perf_counter() - t0
    if tamper is not None:
        tamper(job)
    rows_in = job.rows_in()
    traced = bool(trace)
    for _ in range(WARMUP_JOBS):
        job.run(Stages(traced))
        gc.collect()
    # everything alive now stays alive for the run: keep it out of the
    # collector's sight, so that the collection between jobs looks only at
    # what the jobs made (a full pass over jax's own objects took 70 ms)
    gc.freeze()
    degraded0 = {k: counters.get(k) for k in DEGRADED_COUNTERS}
    # set-up is everything from the start of the process to the window:
    # interpreter, ``import jax``, the claim of the chip (9 to 17.5 s, the
    # platform's), then what the repo controls: importing the program, the
    # session, the table, the warm-up jobs. ``setup_after_claim_s`` reports
    # the second part alone, per layer.
    setup_s = phases["window"] = time.perf_counter() - t0
    log(f"[setup] {setup_s:.3f} s since process start ("
        + ", ".join(f"{k} {v:.3f}" for k, v in phases.items())
        + f"); {setup_s - phases['chip_claimed']:.3f} s of it after the chip "
        f"was claimed; programs built or loaded in set-up: "
        f"{programs['built']}; bytes in use {memory_stats(devices)[0]}")

    # ---- the window ------------------------------------------------------
    tracer = None
    if traced:
        tracer = Tracer(os.path.join(scratch or os.path.join(
            repo_root, ".bench_scratch"), f"trace_{workload}"),
            TRACE_SECONDS)
    state = {"counters": counters.snapshot(), "built": programs["built"],
             "gc_s": 0.0}

    def submit(index):
        stages = Stages(traced)
        try:
            if traced:
                with jax.profiler.TraceAnnotation("bench.job"):
                    result = job.run(stages)
            else:
                result = job.run(stages)
            return {"ok": True, "result": result, "spans": stages.seconds}
        except Exception as e:     # a failed job is counted, not fatal
            log(f"[job {index}] FAILED: {type(e).__name__}: {e}")
            return {"ok": False, "result": None, "spans": stages.seconds}

    def after_job(record):
        if tracer is not None:
            tracer.maybe_stop()
        # a job's frames, views and model are dropped when ``submit``
        # returns; their reference cycles hold device buffers until collected
        t = time.perf_counter()
        gc.collect()
        state["gc_s"] += time.perf_counter() - t
        now = counters.snapshot()
        record["counters"] = counter_delta(now, state["counters"])
        state["counters"] = now
        record["bytes_in_use"] = memory_stats(devices)[0]

    if tracer is not None:
        tracer.start()
    records, start, end = spec["loop_mod"].run(
        traffic, float(seconds), time.perf_counter, submit, after_job)
    if tracer is not None:
        tracer.maybe_stop(force=True)
    window_s = end - start
    built_in_window = programs["built"] - state["built"]
    in_use, peak = memory_stats(devices)
    device["memory_peak_bytes"] = peak
    degraded = {k: counters.get(k) - v for k, v in degraded0.items()
                if counters.get(k) != v}

    # ---- the window has closed: free the program's state, then compare ---
    host = jax.device_get(table)
    job.close()
    del table, job
    spark.stop()
    gc.collect()
    t_ref = time.perf_counter()
    want = job_mod.reference(cfg, cfg_mod, traffic["params"], host)
    reference_s = time.perf_counter() - t_ref
    del host
    limits = traffic["limits"]
    worst = {}
    done = [r for r in records if r["ok"]]
    for r in done:
        for name, gap in job_mod.compare(r["result"], want).items():
            worst[name] = max(worst.get(name, 0.0), float(gap))
    worst["jobs_failed"] = float(len(records) - len(done))
    worst["degraded_paths"] = float(sum(degraded.values()))
    checks = {}
    for name, gap in worst.items():
        limit = 0.0 if name in ("jobs_failed", "degraded_paths") \
            else float(limits[name])
        checks[name] = {"value": gap, "limit": limit}
    correct = bool(done) and all(
        c["value"] <= c["limit"] for c in checks.values())

    # ---- metrics ---------------------------------------------------------
    per_job = [r["counters"] for r in done]
    run = {
        "workload": workload, "cfg": cfg, "cfg_mod": cfg_mod,
        "traffic": traffic, "job_mod": job_mod, "rows": rows,
        "peaks": peaks_for(spec["peaks"], device["kind"])
        if device["platform"] == "tpu" else None,
        "rows_in": rows_in, "setup_s": setup_s, "setup_phases": phases,
        "window_s": window_s,
        "jobs": done, "trace": None, "median": statistics.median,
        "percentile": percentile,
    }
    planes, modules = {}, []
    if tracer is not None:
        run["trace"], planes, modules = tracer.reduced()
    kind = "layer_metrics" if traced else "end_to_end"
    metrics = {}
    for m in spec["per_layer" if traced else "end_to_end"]:
        value = load_module(kind, m["name"], spec["root"]).read(run) \
            if done else None
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # ---- report: everything but the result line goes to stderr -----------
    same = all(c == per_job[0] for c in per_job) if per_job else False
    in_use_series = [r["bytes_in_use"] for r in records]
    log(f"[window] {len(records)} jobs in {window_s:.3f} s; gc between "
        f"jobs {state['gc_s']:.3f} s; programs built or loaded inside the "
        f"window: {built_in_window}"
        + (" (WARNING: something compiled in the window)"
           if built_in_window else ""))
    log(f"[window] counters per job "
        f"{'alike' if same else 'DIFFER'}: "
        f"{per_job[0] if per_job else {}}"
        + ("" if same else f" ... {per_job[-1]}"))
    compiles = sum(c.get(k, 0) for c in per_job for k in COMPILE_COUNTERS)
    log(f"[window] program compiles by the counters: {compiles}; degraded "
        f"paths: {degraded or 0}")
    if in_use_series:
        log(f"[memory] bytes in use after job 1 / last job: "
            f"{in_use_series[0]} / {in_use_series[-1]} "
            f"(max {max(in_use_series)}); peak {peak}")
    for name in getattr(job_mod, "SPANS", ()):
        spans = [r["spans"].get(name, 0.0) for r in done]
        if spans:
            log(f"[stage] {name}: median {statistics.median(spans) * 1e3:.3f} ms"
                + ("" if traced else " (no sync: enqueue time only)"))
    if run["trace"] is not None:
        log(f"[trace] planes and lines: {json.dumps(planes)}")
        log("[trace] programs by device seconds: " + json.dumps(
            [[trace_name[:80], round(sec, 5)]
             for trace_name, sec in modules[:12]]))
        log(f"[trace] {run['trace']['jobs']} traced jobs, busy "
            f"{run['trace']['busy_s']:.4f} s of "
            f"{run['trace']['window_s']:.4f} s")
    log(f"[reference] {reference_s:.3f} s")
    for name, c in checks.items():
        log(f"[check] {name} {c['value']:.6g} limit {c['limit']:.6g}"
            + ("" if c["value"] <= c["limit"] else "  <-- FAILS"))
    line = {"correct": correct, "attempted": len(records),
            "failed": len(records) - len(done), "metrics": metrics,
            "device": device}
    if run["trace"] is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
    line["window"] = {"jobs": len(records), "window_s": window_s,
                      "programs_built": built_in_window,
                      "reference_s": reference_s, "seed": seed,
                      "setup_phases": phases}
    line["checks"] = checks
    return line
