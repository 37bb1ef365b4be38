"""Benchmark harness (BASELINE.md / BASELINE.json target).

Covers the five BASELINE.json configs plus a synthetic scale sweep:

(a/b) LinearRegression Lasso fit on dataset-full.csv (the headline metric:
      maxIter=40, regParam=1, elasticNetParam=1; single-chip mesh = config a,
      the same packed psum path sharded = config b, exercised in CI and the
      multichip dryrun),
(c)   elastic-net general path (FISTA, regParam=0.3, elasticNetParam=0.5),
(d)   LogisticRegression on the DQ-filtered rows (per-iteration-psum loop),
      plus a 1e6×16 scale variant (d_scale) where barrier elimination —
      not solver iteration counts — dominates,
(e)   CrossValidator grid (regParam × elasticNetParam, grid-parallel cell
      sharding) vs sklearn GridSearchCV(refit=True) — timed as the fused
      device-complete CV program (fold Gramians → every cell solved →
      winner selected → best model refit, one dispatch, no host reads;
      the same program CrossValidator.fit runs, which then adds exactly
      one host read to materialize the packed result),
(dq)  the DQ phase itself (`App.java:52-95`): CSV parse throughput
      (native C++ tokenizer vs pure-Python) on a ~1e6-row synthetic file,
      and the fused rules+filter pass (XLA, on device) vs vectorized numpy,
(ingest) streaming native CSV ingest (native/csvparse.cpp): scalar vs
      SIMD vs SIMD+chunk-parallel-threads vs the full streaming pipeline
      (bounded chunks + prefetch overlapping parse with device transfer),
      end-to-end through read_csv at 1e5/1e6/1e7 rows, bit-parity
      asserted and the golden DQ+Lasso numbers driven through the
      streaming reader,
(serving) closed-loop multi-tenant serving (serve/): 32 concurrent
      clients driving the headline DQ+Lasso query through the QueryServer,
      sustained QPS + p50/p99 latency, shared plan/jit cache on vs off,
      cross-tenant program-reuse pin, golden numbers asserted per query,
      plus a real-socket arm (serve/net.py + the resilient client, frame
      and HTTP framings mixed) whose QPS/latency delta vs the in-process
      arm prices the wire overhead,
(sweep) the masked-Gramian data pass at n ∈ {1e5, 1e6, 1e7} × d ∈ {16, 128,
      512} (HBM-bounded subset), XLA vs compiled Pallas, with on-device
      numerics assertions — the MXU/HBM throughput story behind every fit.
      On TPU each cell also reports its roofline fractions: ``hbm_frac``
      (achieved GB/s ÷ chip HBM peak) and ``mfu`` (achieved FLOP/s ÷ chip
      bf16 matmul peak; f32 cells use the same denominator, so their mfu
      is a conservative lower bound).

Baselines are **measured CPU** stand-ins (sklearn / numpy, documented per
config): the reference publishes no numbers (SURVEY.md §6) and no JVM is
available, so sklearn-CPU — a C-optimized solver without Spark's RPC
barriers — is a strictly faster proxy than the Spark stack it stands in
for. ``vs_baseline`` = baseline_seconds / device_seconds.

Prints exactly ONE JSON line on stdout (driver contract); the per-config
results, sweep table, and pallas-vs-XLA table ride inside it. Per-config
lines are echoed to stderr for human reading.

Measurement hygiene: every device op is timed by ``make_median_time`` —
a host clock around work that ends in ``block_until_ready`` (which blocks on
the chip; ``chip_smoke.py`` stage E checks it against the matmul peak). Data
for the sweep is generated ON DEVICE (jax.random) so multi-GB operands are
never staged through host memory.
"""

import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

GOLDEN_RMSE_FULL = 1.805140  # SURVEY.md §2.3, dataset-full Lasso
# BENCH_SMOKE=1: tiny sweep + few reps, for CI validation of the harness
# itself on CPU (real numbers come from the TPU run).
SMOKE = os.environ.get("BENCH_SMOKE") == "1"
REPS = 3 if SMOKE else 30
SWEEP_REPS = 2 if SMOKE else 5
# (rows, features) — sizes chosen to fit v5e HBM (16 GB) with headroom;
# the 1e7×128 / 1e7×512 cells would be 5–20 GB and are deliberately absent
# (documented cap, not silent truncation).
SWEEP_SHAPES = [(100_000, 16), (100_000, 128)] if SMOKE else \
    [(100_000, 16), (1_000_000, 16), (10_000_000, 16),
     (100_000, 128), (1_000_000, 128), (1_000_000, 512)]
CPU_SWEEP_SHAPES = {(100_000, 16), (1_000_000, 16), (100_000, 128)}

# Public per-chip peaks (vendor spec sheets), keyed by device_kind prefix:
# (HBM GB/s, bf16 dense matmul TFLOP/s). Drives the hbm_frac / mfu roofline
# fractions of TPU runs; a kind that is not in the table is an error.
ROOFLINE = {
    "TPU v4": (1228.0, 275.0),
    "TPU v5 lite": (819.0, 197.0),    # v5e
    "TPU v5e": (819.0, 197.0),
    "TPU v5p": (2765.0, 459.0),
    "TPU v6 lite": (1640.0, 918.0),   # v6e / Trillium
    "TPU v6e": (1640.0, 918.0),
}


def roofline_for(device_kind: str):
    for prefix, peaks in ROOFLINE.items():
        if device_kind.startswith(prefix):
            return peaks
    raise KeyError(
        f"no published peaks for device_kind {device_kind!r}; add it to "
        "ROOFLINE with its source before reporting roofline fractions")


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def make_median_time(jax):
    """Timing loop: each rep blocks on ITS OWN ``fn()`` result — blocking on
    a stale array measures only async dispatch enqueue (µs), not the
    computation. Opaque (non-pytree) results pass through block_until_ready
    untouched, which is correct for the synchronous CPU baselines."""
    def median_time(fn, reps):
        jax.block_until_ready(fn())   # warm: compile cached after
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        return statistics.median(times)
    return median_time


def bench_frame_pipeline(median_time, n_rows: int):
    """(frame_pipeline) The fused expression-pipeline compiler
    (ops/compiler.py) vs the per-op eager path on a 20-op
    with_column/filter chain: the ISSUE-3 acceptance metric. One chain
    execution dispatches ONE compiled XLA program when fused vs 20
    interpreter-dispatched computations when eager; compile counters
    prove the plan-keyed cache reuses (0 recompiles once warm)."""
    import jax
    import numpy as np

    from sparkdq4ml_tpu.config import config
    from sparkdq4ml_tpu.frame.frame import Frame
    from sparkdq4ml_tpu.ops import compiler
    from sparkdq4ml_tpu.ops import expressions as E
    from sparkdq4ml_tpu.utils.profiling import counters

    base = Frame({"v": np.arange(n_rows, dtype=np.float64) / n_rows})

    def chain(f):
        for i in range(10):
            f = f.with_column(f"c{i}", E.col("v") * float(i + 1) + 0.5)
            f = f.filter(E.col(f"c{i}") > float(-1 - i))
        return f

    def run():
        out = chain(base)
        # flush + honest sync on EVERY produced column and the mask
        # (syncing just the mask would let async column slices escape the
        # clock); a device wait, never a host read
        jax.block_until_ready(list(out._data.values()) + [out._mask])
        return out

    compiler.clear_cache()
    counters.clear("pipeline")
    run()                                   # cold: trace + compile
    compiles_cold = counters.get("pipeline.compile")
    t_fused = median_time(run, REPS)
    compiles_steady = counters.get("pipeline.compile") - compiles_cold
    flushes = counters.get("pipeline.flush")
    hits = counters.get("pipeline.hit")
    prev_pipeline = config.pipeline
    config.pipeline = False
    try:
        run()                               # warm eager's own jit caches
        t_eager = median_time(run, REPS)
    finally:
        config.pipeline = prev_pipeline
    n_ops = 20
    return {
        "config": "frame_pipeline",
        "rows": n_rows,
        "chain_ops": n_ops,
        "fused_ms": round(t_fused * 1e3, 3),
        "eager_ms": round(t_eager * 1e3, 3),
        "fused_ops_per_s": round(n_ops / t_fused, 1),
        "eager_ops_per_s": round(n_ops / t_eager, 1),
        "speedup": round(t_eager / t_fused, 2),
        "compiles_cold": compiles_cold,
        "compiles_steady": compiles_steady,   # 0 ⇒ plan cache reuse
        "cache_hits": hits,
        "flushes": flushes,
    }


def bench_grouped_ops(median_time):
    """(grouped_ops) Device-resident grouped execution (ops/segments.py)
    vs the legacy host numpy path: groupBy().agg() across a rows × groups
    grid, plus sort and distinct — the ISSUE-4 acceptance surface. The
    device path is ONE jitted sort + segment-reduce program whose only
    host sync is the group count; the host path loops Python over groups.
    Compile counters prove the plan-keyed cache replays warm
    (compiles_steady=0 across repeated queries)."""
    import jax
    import numpy as np

    from sparkdq4ml_tpu.config import config
    from sparkdq4ml_tpu.frame import aggregates as A
    from sparkdq4ml_tpu.frame.frame import Frame
    from sparkdq4ml_tpu.ops import segments
    from sparkdq4ml_tpu.utils.profiling import counters

    if SMOKE:
        rows_sweep, groups_sweep = [100_000], [8, 1024]
    else:
        rows_sweep = [100_000, 1_000_000, 10_000_000]
        groups_sweep = [8, 1024, 100_000]
    # grouped ops run 10-10000x longer per call than the sub-ms fit
    # configs, so the global REPS=30 would push this section past the
    # bench lock window: 3 device reps / 1 host rep give a stable median
    # (the host path is a Python loop over groups; one rep keeps the
    # 1e7x100k cell from dominating wall-clock), and the sort/distinct
    # sweeps stop at 1e6 rows (logged, not silently dropped) — the 1e7
    # distinct host walk alone is ~a minute per rep.
    dev_reps = REPS if SMOKE else 3
    host_reps = REPS if SMOKE else 1
    out = []
    prev = config.grouped_exec
    for n_rows in rows_sweep:
        for n_groups in groups_sweep:
            if n_groups * 4 > n_rows:
                continue
            rng = np.random.default_rng(42)
            frame = Frame({
                "k": rng.integers(0, n_groups, n_rows).astype(np.float64),
                "v": rng.normal(size=n_rows),
            }).cache()
            aggs = [A.count(), A.sum("v"), A.avg("v"), A.min("v"),
                    A.max("v")]
            # honest GB/s denominators: agg and sort stream both float64
            # columns (k + v = 16 B/row); distinct runs on select("k")
            # and touches only the 8-byte key column
            op_bytes = {"agg": n_rows * 16, "sort": n_rows * 16,
                        "distinct": n_rows * 8}

            def run_agg():
                res = frame.group_by("k").agg(*aggs)
                jax.block_until_ready(
                    [c for c in res._data.values()
                     if getattr(c, "dtype", None) != object])

            def run_sort():
                res = frame.sort("v")
                jax.block_until_ready(list(res._data.values()))

            def run_distinct():
                res = frame.select("k").distinct()
                jax.block_until_ready(list(res._data.values()))

            ops = [("agg", run_agg)]
            if n_rows <= 1_000_000:
                ops += [("sort", run_sort), ("distinct", run_distinct)]
            elif n_groups == groups_sweep[0]:
                log(json.dumps({"config": "grouped_ops", "rows": n_rows,
                                "note": "sort/distinct capped at 1e6 rows"
                                        " (host walk ~minutes beyond)"}))
            row = {"config": "grouped_ops", "rows": n_rows,
                   "groups": n_groups}
            try:
                config.grouped_exec = True
                segments.clear_cache()
                counters.clear("grouped")
                for name, fn in ops:
                    before = counters.get("grouped.compile")
                    fn()                         # cold: trace + compile
                    cold = counters.get("grouped.compile") - before
                    t_dev = median_time(fn, dev_reps)
                    steady = counters.get("grouped.compile") - before - cold
                    config.grouped_exec = False
                    try:
                        fn()                     # warm host-path caches
                        t_host = median_time(fn, host_reps)
                    finally:
                        config.grouped_exec = True
                    row[f"{name}_device_ms"] = round(t_dev * 1e3, 3)
                    row[f"{name}_host_ms"] = round(t_host * 1e3, 3)
                    row[f"{name}_speedup"] = round(t_host / t_dev, 2)
                    row[f"{name}_device_gbps"] = round(
                        op_bytes[name] / t_dev / 1e9, 3)
                    row[f"{name}_compiles_cold"] = cold
                    row[f"{name}_compiles_steady"] = steady
            finally:
                config.grouped_exec = prev
            out.append(row)
            log(json.dumps(row))
    return out


def bench_ingest(median_time, session):
    """(ingest) Streaming native CSV ingest (native/csvparse.cpp +
    frame/native_csv.py) — the ISSUE-7 acceptance surface. Four arms per
    row count, all END-TO-END through ``read_csv`` (bytes on disk →
    device-ready Frame columns):

      scalar          one-shot parse, SIMD off, 1 thread — the floor
      simd            one-shot, runtime-dispatched SIMD tier, 1 thread
      simd_threads    one-shot, SIMD + chunk-parallel parse threads
      stream          the full pipeline: bounded chunks, SIMD + threads,
                      prefetch queue overlapping parse with host→device
                      transfer

    Streaming output is asserted bit-identical to the scalar one-shot arm
    (dtype + value parity per column) before any time is reported, and
    the golden DQ pipeline (dataset-abstract, count 24, RMSE 2.8099) is
    driven through the streaming reader with a chunk size small enough to
    actually stream. ``parse_frac`` reports parse wall ÷ (parse + fused
    DQ rules) — the "parse no longer dominates" row. CPU-backend caveat
    (ROADMAP standing constraint): SIMD wins are chip-dependent — on
    hosts where AVX is emulated/throttled the honest verdict can be ~1×,
    so parity + counter structure is the CPU assertion and the GB/s rows
    are the TPU-capture measurement."""
    import tempfile

    import jax
    import numpy as np

    from sparkdq4ml_tpu.config import config
    from sparkdq4ml_tpu.frame import native_csv
    from sparkdq4ml_tpu.frame.csv import read_csv
    from sparkdq4ml_tpu.ops.rules import dq_rules_fused
    from sparkdq4ml_tpu.utils.profiling import counters

    if not native_csv.available():
        log(json.dumps({"config": "ingest",
                        "note": "libdqcsv.so missing or pre-streaming ABI; "
                                "section skipped"}))
        return []

    rows_sweep = [100_000] if SMOKE else [100_000, 1_000_000, 10_000_000]
    reps = REPS if SMOKE else 3
    saved = (config.ingest_streaming, config.ingest_threads,
             config.ingest_chunk_bytes, config.ingest_prefetch,
             config.ingest_simd)
    out = []
    try:
        for n_rows in rows_sweep:
            fd, path = tempfile.mkstemp(prefix=f"ingest_bench_{n_rows}_",
                                        suffix=".csv")
            rng = np.random.default_rng(13)
            g = rng.integers(1, 40, n_rows)
            p = np.round(rng.uniform(1.0, 120.0, n_rows), 2)
            with os.fdopen(fd, "w") as f:
                f.write("\n".join(f"{a},{b}" for a, b in zip(g, p)))
                f.write("\n")
            nbytes = os.path.getsize(path)

            def set_arm(streaming, chunk, threads, simd, prefetch=2):
                config.ingest_streaming = streaming
                config.ingest_chunk_bytes = chunk
                config.ingest_threads = threads
                config.ingest_simd = simd
                config.ingest_prefetch = prefetch

            def parse():
                f = read_csv(path, engine="native")
                jax.block_until_ready([
                    c for c in f._data.values()
                    if getattr(c, "dtype", None) != object])
                return f

            whole = nbytes + 1  # one-shot: chunk bound beyond the file
            # stream arm: ~4+ chunks at every sweep size (a chunk bound
            # past the file would silently degrade to one-shot)
            stream_chunk = max(min(8 << 20, nbytes // 4), 1 << 16)
            arms = [
                ("scalar", (True, whole, 1, "off")),
                ("simd", (True, whole, 1, "auto")),
                ("simd_threads", (True, whole, 0, "auto")),
                ("stream", (True, stream_chunk, 0, "auto")),
            ]
            # bit parity BEFORE timing: stream (many chunks) == scalar
            set_arm(True, whole, 1, "off")
            ref = parse()
            set_arm(True, max(nbytes // 8, 1 << 16), 0, "auto")
            streamed = parse()
            for c in ref.columns:
                a, b = np.asarray(ref._data[c]), np.asarray(streamed._data[c])
                if a.dtype != b.dtype or not np.array_equal(
                        a, b, equal_nan=True):
                    log(f"ERROR: ingest bench: stream vs one-shot parity "
                        f"broke on column {c} at {n_rows} rows")
                    return out
            row = {"config": "ingest", "rows": n_rows,
                   "bytes": nbytes, "parity": "bit-identical",
                   "simd_verdict": native_csv.simd_level("auto")}
            t_by_arm = {}
            for name, (streaming, chunk, threads, simd) in arms:
                set_arm(streaming, chunk, threads, simd)
                if name == "stream":
                    # warmup doubles as the exact per-read chunk count
                    # (counters would otherwise accumulate across reps)
                    counters.clear("ingest")
                    parse()
                    row["stream_chunks"] = counters.get("ingest.chunks")
                else:
                    parse()  # page-cache + buffer-pool warmup
                t = median_time(parse, reps)
                t_by_arm[name] = t
                row[f"{name}_ms"] = round(t * 1e3, 2)
                row[f"{name}_gbps"] = round(nbytes / t / 1e9, 3)
            row["pipeline_vs_scalar"] = round(
                t_by_arm["scalar"] / min(t_by_arm["stream"],
                                         t_by_arm["simd_threads"]), 2)
            # parse share of the ingest→DQ wall: the fused rules pass on
            # the columns the stream just delivered
            set_arm(True, stream_chunk, 0, "auto")
            frame = parse()
            price = frame._data["_c1"]
            guest = frame._data["_c0"]

            def rules():
                jax.block_until_ready(dq_rules_fused(price, guest))

            rules()  # compile outside the clock
            t_rules = median_time(rules, reps)
            t_parse = t_by_arm["stream"]
            row["dq_rules_ms"] = round(t_rules * 1e3, 3)
            row["parse_frac"] = round(t_parse / (t_parse + t_rules), 4)
            out.append(row)
            log(json.dumps(row))
            try:
                os.remove(path)
            except OSError:
                pass

        # golden numbers THROUGH the streaming reader: the headline DQ +
        # Lasso pipeline on dataset-abstract with the chunk size forced
        # below the file size, so the 320-byte file genuinely streams
        config.ingest_streaming = True
        config.ingest_chunk_bytes = 64
        config.ingest_simd = "auto"
        config.ingest_threads = 0
        config.ingest_prefetch = 2
        counters.clear("ingest")
        import sparkdq4ml_tpu as dq
        from sparkdq4ml_tpu.models import LinearRegression, VectorAssembler

        dq.register_builtin_rules()
        df = (session.read.format("csv").option("inferSchema", "true")
              .load(os.path.join(REPO, "data", "dataset-abstract.csv")))
        df = (df.with_column_renamed("_c0", "guest")
                .with_column_renamed("_c1", "price"))
        df = df.with_column("price_no_min",
                            dq.call_udf("minimumPriceRule", dq.col("price")))
        df.create_or_replace_temp_view("price")
        df = session.sql("SELECT cast(guest as int) guest, price_no_min AS "
                         "price FROM price WHERE price_no_min > 0")
        df = df.with_column(
            "price_correct_correl",
            dq.call_udf("priceCorrelationRule", dq.col("price"),
                        dq.col("guest")))
        df.create_or_replace_temp_view("price")
        df = session.sql("SELECT guest, price_correct_correl AS price "
                         "FROM price WHERE price_correct_correl > 0")
        count = df.count()
        df = df.with_column("label", df.col("price"))
        df = VectorAssembler(["guest"], "features").transform(df)
        model = LinearRegression(max_iter=40, reg_param=1.0,
                                 elastic_net_param=1.0).fit(df)
        rmse = float(model.summary.root_mean_squared_error)
        golden = {"config": "ingest_golden", "dq_count": count,
                  "rmse": round(rmse, 4),
                  "streamed_chunks": counters.get("ingest.chunks"),
                  "golden_ok": bool(count == 24
                                    and abs(rmse - 2.809940) < 0.01)}
        if not golden["golden_ok"]:
            log("ERROR: ingest bench: golden numbers through the streaming "
                f"reader were count={count} rmse={rmse:.4f}, expected "
                "24 / 2.8099")
        out.append(golden)
        log(json.dumps(golden))
    finally:
        (config.ingest_streaming, config.ingest_threads,
         config.ingest_chunk_bytes, config.ingest_prefetch,
         config.ingest_simd) = saved
    return out


def bench_serving(session, data_path: str):
    """(serving) Closed-loop multi-tenant serving bench — the ISSUE-6
    acceptance metric. N concurrent clients (one logical tenant each)
    drive the headline DQ+Lasso query through the QueryServer in a
    closed loop (submit → wait → submit), giving sustained QPS and
    p50/p99 end-to-end latency, with the shared plan/jit cache ON vs
    OFF (per-tenant cache namespaces — what serving would cost if every
    tenant compiled its own plans). ``cross_tenant_new_compiles`` pins
    the reuse claim: with sharing on, the SECOND tenant's first query
    replays the first tenant's compiled programs with zero new pipeline/
    grouped compiles (cache_report diff). Every served query must return
    the golden numbers (count=24, RMSE 2.8099 ± 1%) or the bench exits
    1 — concurrency must never change results.

    The ``coalesced`` arm (ISSUE-18) repeats the shared-cache closed
    loop with cross-request plan coalescing ON: identical-plan flushes
    from concurrent clients rendezvous inside the hold window and run
    as ONE stacked (vmapped) device dispatch. ``cross_request_dispatches``
    is the batched-dispatch count (must sit well below ``queries`` —
    otherwise nothing coalesced) and ``batch_size_hist`` is the padded
    member-bucket histogram from the batched-plan cache."""
    import threading

    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.models import LinearRegression, VectorAssembler
    from sparkdq4ml_tpu.ops import compiler, segments
    from sparkdq4ml_tpu.serve import QueryServer, TenantQuota
    from sparkdq4ml_tpu.utils.profiling import counters

    clients = 8 if SMOKE else 32
    per_client = 2 if SMOKE else 6
    workers = 8
    golden_rmse = 2.809940          # SURVEY.md §2.3, dataset-abstract

    def job(ctx):
        df = (ctx.read.format("csv").option("inferSchema", "true")
              .option("header", "false").load(data_path))
        df = df.with_column_renamed("_c0", "guest") \
               .with_column_renamed("_c1", "price")
        df = df.with_column("price_no_min",
                            dq.call_udf("minimumPriceRule", dq.col("price")))
        ctx.register_view("price", df)
        df = ctx.sql("SELECT cast(guest as int) guest, price_no_min AS "
                     "price FROM price WHERE price_no_min > 0")
        df = df.with_column(
            "price_correct_correl",
            dq.call_udf("priceCorrelationRule", dq.col("price"),
                        dq.col("guest")))
        ctx.register_view("price", df)
        df = ctx.sql("SELECT guest, price_correct_correl AS price "
                     "FROM price WHERE price_correct_correl > 0")
        df = df.with_column("label", df.col("price"))
        df = VectorAssembler(["guest"], "features").transform(df)
        model = LinearRegression(max_iter=40, reg_param=1.0,
                                 elastic_net_param=1.0).fit(df)
        return {"count": df.count(),
                "rmse": float(model.summary.root_mean_squared_error)}

    def plan_compiles(report):
        # pipeline + grouped "misses" ARE the plan-compile counters; the
        # solver/fit factories are tenant-independent in both modes and
        # deliberately excluded from the reuse pin
        return sum(int(report.get(k, {}).get("misses", 0))
                   for k in ("pipeline", "grouped"))

    def run_arm(shared: bool, coalesce: bool = False):
        compiler.clear_cache()
        segments.clear_cache()
        server = QueryServer(
            session, workers=workers, max_queue=4 * clients,
            default_quota=TenantQuota(max_in_flight=2,
                                      max_queued=per_client + 2),
            shared_plan_cache=shared, coalesce=coalesce,
            coalesce_max_delay_ms=5.0, coalesce_max_batch=8,
            coalesce_min_queue_depth=2).start()
        # Cold warm-up on tenant-00, then the cross-tenant pin: does
        # tenant-01's FIRST query need any new compiled plan?
        r0 = server.submit(job, tenant="tenant-00").result()
        rep0 = plan_compiles(server.cache_report())
        r1 = server.submit(job, tenant="tenant-01").result()
        cross_new = plan_compiles(server.cache_report()) - rep0
        if coalesce:
            # untimed concurrent burst: rendezvous real batches so the
            # vmapped (plan, member-bucket) programs compile BEFORE the
            # timed loop — the arm measures steady-state coalesced QPS,
            # same warm-plan footing the uncoalesced arms get from r0/r1
            for _ in range(2):
                warm_threads = [
                    threading.Thread(target=lambda i=i: server.submit(
                        job, tenant=f"tenant-{i:02d}").result())
                    for i in range(clients)]
                for t in warm_threads:
                    t.start()
                for t in warm_threads:
                    t.join()

        co0 = counters.get("serve.coalesce.dispatches")
        co0_members = counters.get("serve.coalesce.batched")
        results: list = []
        res_lock = threading.Lock()

        def client(i: int):
            tenant = f"tenant-{i:02d}"
            out = [server.submit(job, tenant=tenant).result()
                   for _ in range(per_client)]
            with res_lock:
                results.extend(out)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        # batched-plan cache state BEFORE stop/clear: one row per
        # (plan, member bucket), hits+compiles = dispatches through it
        hist: dict = {}
        for e in compiler.coalesce_cache_stats()["entries"]:
            k = f"x{e['batch']}"
            hist[k] = (hist.get(k, 0) + int(e["hits"])
                       + int(e["compiles"]))
        server.stop()
        ok = [r for r in results if r.ok]
        golden_ok = all(
            r.ok                           # short-circuits: a failed
            and r.value["count"] == 24     # warm-up has value=None
            and abs(r.value["rmse"] - golden_rmse) / golden_rmse < 0.01
            for r in ok + [r0, r1])
        lats = sorted(r.e2e_ms for r in ok)

        def pct(p):
            return (round(lats[min(len(lats) - 1,
                                   int(p * (len(lats) - 1)))], 2)
                    if lats else None)

        arm = {
            "queries": len(results), "completed": len(ok),
            "qps": round(len(ok) / wall, 2), "wall_s": round(wall, 3),
            "p50_ms": pct(0.50), "p99_ms": pct(0.99),
            "cross_tenant_new_compiles": cross_new,
            "golden_ok": bool(golden_ok and r0.ok and r1.ok
                              and len(ok) == len(results)),
        }
        if coalesce:
            arm["cross_request_dispatches"] = (
                counters.get("serve.coalesce.dispatches") - co0)
            arm["coalesced_members"] = (
                counters.get("serve.coalesce.batched") - co0_members)
            arm["batch_size_hist"] = hist
        return arm

    def run_socket_arm(tracing: bool = False):
        # Same closed-loop workload through REAL sockets (serve/net.py):
        # half the clients speak the length-prefixed frame protocol,
        # half HTTP/1.1 chunked streaming, all via the resilient client.
        # Latencies are CLIENT-side wall time per logical call, so the
        # delta vs the in-process arm IS the wire + framing overhead.
        # ``tracing=True`` runs the identical workload with distributed
        # tracing ON (context propagation, span trees, tail sampling) —
        # the enabled-vs-disabled QPS pair is the tracing-overhead arm.
        from sparkdq4ml_tpu.serve import NetServer, ResilientClient
        from sparkdq4ml_tpu.utils import observability as _obs

        compiler.clear_cache()
        segments.clear_cache()
        was_tracing = _obs.TRACER.enabled
        if tracing:
            _obs.enable()
        else:
            _obs.disable()
        server = QueryServer(
            session, workers=workers, max_queue=4 * clients,
            default_quota=TenantQuota(max_in_flight=2,
                                      max_queued=per_client + 2),
            shared_plan_cache=True).start()
        net = NetServer(server, host="127.0.0.1", port=0).start()
        net.register_job("headline", job)
        warm = ResilientClient("127.0.0.1", net.port, transport="frame")
        r0 = warm.call_job("headline", tenant="tenant-00",
                           deadline_s=300.0)
        warm.close()

        results: list = []
        lats: list = []
        res_lock = threading.Lock()

        def wire_client(i: int):
            tenant = f"tenant-{i:02d}"
            wire = ResilientClient(
                "127.0.0.1", net.port,
                transport="frame" if i % 2 else "http", tenant=tenant)
            out, took = [], []
            try:
                for _ in range(per_client):
                    t_call = time.perf_counter()
                    out.append(wire.call_job("headline", tenant=tenant,
                                             deadline_s=300.0))
                    took.append((time.perf_counter() - t_call) * 1e3)
            finally:
                wire.close()
            with res_lock:
                results.extend(out)
                lats.extend(took)

        threads = [threading.Thread(target=wire_client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        net.stop()
        server.stop()
        if was_tracing:
            _obs.enable()
        else:
            _obs.disable()
        ok = [r for r in results if r.ok]
        golden_ok = all(
            r.ok
            and r.value["count"] == 24
            and abs(r.value["rmse"] - golden_rmse) / golden_rmse < 0.01
            for r in ok + [r0])
        lat_sorted = sorted(lats)

        def pct(p):
            return (round(lat_sorted[min(len(lat_sorted) - 1,
                                         int(p * (len(lat_sorted) - 1)))],
                          2) if lat_sorted else None)

        return {
            "queries": len(results), "completed": len(ok),
            "qps": round(len(ok) / wall, 2), "wall_s": round(wall, 3),
            "p50_ms": pct(0.50), "p99_ms": pct(0.99),
            "golden_ok": bool(golden_ok and r0.ok
                              and len(ok) == len(results)),
        }

    shared = run_arm(True)
    isolated = run_arm(False)
    coalesced = run_arm(True, coalesce=True)
    socket_arm = run_socket_arm()
    # (tracing overhead) the same socket workload with distributed
    # tracing ON, then OFF again: tracing_enabled_qps is what the span
    # pipeline costs live; the disabled repeat vs the baseline socket
    # arm pins the one-flag-read contract — with tracing off the wire
    # path is byte-identical, so the ratio must sit at ~1.0 (gated by
    # eye + the test-suite no-op pin, not the regress gate: run-to-run
    # QPS noise swamps a one-branch delta)
    traced_arm = run_socket_arm(tracing=True)
    untraced_arm = run_socket_arm(tracing=False)
    # drop the tenant-namespaced plans the isolated arm salted in
    compiler.clear_cache()
    segments.clear_cache()
    arms = {"shared": shared, "isolated": isolated,
            "coalesced": coalesced, "socket": socket_arm,
            "traced": traced_arm, "untraced": untraced_arm}
    failed = [name for name, arm in arms.items()
              if not arm["golden_ok"]]
    if failed:
        log("ERROR: serving bench: a served query missed the golden "
            "numbers (count 24 / RMSE 2.8099) or failed outright in "
            f"arm(s): {', '.join(failed)}")
        sys.exit(1)
    row = {
        "config": "serving", "clients": clients,
        "queries_per_client": per_client, "workers": workers,
        "shared_cache": shared, "isolated_cache": isolated,
        "coalesced": coalesced,
        "socket": socket_arm,
        "shared_vs_isolated_qps": round(
            shared["qps"] / isolated["qps"], 2)
        if isolated["qps"] else None,
        "coalesced_vs_uncoalesced_qps": round(
            coalesced["qps"] / shared["qps"], 2)
        if shared["qps"] else None,
        "socket_vs_inproc_qps": round(
            socket_arm["qps"] / shared["qps"], 2)
        if shared["qps"] else None,
        "tracing_enabled_qps": traced_arm["qps"],
        "tracing_disabled_qps": untraced_arm["qps"],
        "tracing_disabled_overhead": round(
            socket_arm["qps"] / untraced_arm["qps"], 3)
        if untraced_arm["qps"] else None,
    }
    log(json.dumps(row))
    return row


_SHARD_WORKER = r'''
import json, os, sys, time
n, d, golden = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={max(d, 1)}"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import sparkdq4ml_tpu as dq
from sparkdq4ml_tpu.frame.frame import Frame
from sparkdq4ml_tpu.utils.profiling import counters
import sparkdq4ml_tpu.ops.expressions as E
from sparkdq4ml_tpu.parallel import shard as shard_mod

sess = (dq.TpuSession.builder().app_name("bench-shard").master("local[*]")
        .config("spark.shard.enabled", "true" if d > 1 else "false")
        .config("spark.shard.minRows", "8" if golden else "1024")
        .get_or_create())

if golden:
    # headline DQ+Lasso golden workload, sharding per arm: parity is a
    # RESULT property, not a layout property
    dq.register_builtin_rules()
    df = (sess.read.format("csv").option("inferSchema", "true")
          .load(sys.argv[4]))
    df = df.with_column_renamed("_c0", "guest") \
           .with_column_renamed("_c1", "price")
    df = df.with_column("price_no_min",
                        dq.call_udf("minimumPriceRule", dq.col("price")))
    df.create_or_replace_temp_view("price")
    df = sess.sql("SELECT cast(guest as int) guest, price_no_min AS price "
                  "FROM price WHERE price_no_min > 0")
    df = df.with_column("price_correct_correl",
                        dq.call_udf("priceCorrelationRule",
                                    dq.col("price"), dq.col("guest")))
    df.create_or_replace_temp_view("price")
    df = sess.sql("SELECT guest, price_correct_correl AS price "
                  "FROM price WHERE price_correct_correl > 0")
    df = df.with_column("label", df.col("price"))
    from sparkdq4ml_tpu.models import LinearRegression, VectorAssembler
    df = VectorAssembler(["guest"], "features").transform(df)
    model = LinearRegression(max_iter=40, reg_param=1.0,
                             elastic_net_param=1.0).fit(df)
    print(json.dumps({
        "devices": d, "count": df.count(),
        "rmse": float(model.summary.root_mean_squared_error),
        "sharded": df._shard is not None}))
    sys.exit(0)

rng = np.random.default_rng(7)
f = Frame({"v": rng.normal(size=n),
           "k": rng.integers(0, 1024, n).astype(np.float64),
           "w": rng.normal(size=n)})
if d > 1:
    f = shard_mod.maybe_shard_frame(f)

def chain(fr):
    for i in range(10):
        fr = fr.with_column(f"c{i}", E.col("v") * float(i + 1) + 0.5)
        fr = fr.filter(E.col(f"c{i}") > float(-1 - i))
    return fr

def flush():
    out = chain(f)
    jax.block_until_ready(list(out._data.values()) + [out._mask])
    return out

def med(fn, reps=3):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]

out = flush()                                  # warm: trace + compile
compiles0 = counters.get("pipeline.compile")
pipe_ms = med(flush) * 1e3
steady = counters.get("pipeline.compile") - compiles0
m = np.asarray(out._mask)
ck_pipe = float(np.asarray(jnp.asarray(out._data["c9"]))[m].sum())

def grp():
    return f.group_by("k").agg({"v": "sum", "w": "avg"}).to_pydict()

gp = grp()                                     # warm
ck_group = [float(np.sum(gp["sum(v)"])), float(np.sum(gp["avg(w)"])),
            int(len(gp["k"]))]
group_ms = med(grp) * 1e3

rsz = max(n // 10, 16)
r = Frame({"k": rng.integers(0, 1024, rsz).astype(np.float64),
           "z": rng.normal(size=rsz)})
if d > 1:
    r = shard_mod.maybe_shard_frame(r)

def jn():
    return int(f.join(r, "k", "inner").num_slots)

jrows = jn()                                   # warm
join_ms = med(jn) * 1e3
print(json.dumps({
    "rows": n, "devices": d, "pipeline_ms": round(pipe_ms, 3),
    "groupby_ms": round(group_ms, 3), "join_ms": round(join_ms, 3),
    "compiles_steady": steady, "ck_pipe": ck_pipe, "ck_group": ck_group,
    "join_rows": jrows, "sharded": f._shard is not None}))
'''


def bench_sharded(log):
    """(sharded) Row-sharded frame execution (parallel/shard.py +
    the shard_map pipeline/grouped lowerings) across forced host device
    counts: the 20-op fused chain, GROUP BY (sum/avg), and an inner join
    at each row count × 1/2/4/8 devices, each arm an isolated subprocess
    (device count is a process-level XLA flag). The children are pinned
    to the CPU backend before they import jax (``run_arm`` sets
    ``JAX_PLATFORMS=cpu``, the worker repeats it), so they never ask for
    the chip this process may hold. Parity-asserted — the
    d>1 arms must reproduce the 1-device checksums (pipeline and join
    exact; the grouped merge collective at 1e-5 relative, the
    engine-default float32's reduction-order ULP envelope) — and
    golden-pinned via the headline DQ+Lasso workload with sharding on.
    CPU-sandbox honesty: forced host devices share the same cores, so
    these rows prove structure and scaling SHAPE (plus steady-state
    zero-recompile), not wall-clock wins — speedup columns are captured
    for TPU runs where the shards are real chips."""
    import subprocess
    import sys

    try:
        rows_list = [int(x) for x in os.environ.get(
            "BENCH_SHARD_ROWS", "1000000,10000000").split(",") if x]
    except ValueError:
        rows_list = [1_000_000, 10_000_000]
    devs = [1, 2, 4, 8]
    section = {"pipeline": [], "groupby": [], "join": [],
               "parity_ok": True, "parity_failures": []}

    def run_arm(n, d, golden=False, data=""):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _SHARD_WORKER, str(n), str(d),
                 "1" if golden else "0", data],
                cwd=REPO, env=env, capture_output=True, text=True,
                timeout=1800)
        except subprocess.SubprocessError as e:
            log(f"sharded arm n={n} d={d} failed: {e}")
            return None
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                return json.loads(line)
            except ValueError:
                continue
        log(f"sharded arm n={n} d={d} produced no JSON "
            f"(rc={proc.returncode}): {proc.stderr[-400:]}")
        return None

    for n in rows_list:
        base = None
        for d in devs:
            row = run_arm(n, d)
            if row is None:
                continue
            if d == 1:
                base = row
            else:
                ok = base is not None and (
                    row["ck_pipe"] == base["ck_pipe"]
                    and row["join_rows"] == base["join_rows"]
                    and row["ck_group"][2] == base["ck_group"][2]
                    # grouped float aggregates merge cross-shard partials
                    # — reduction order differs, so the engine-default
                    # float32 checksums compare at ULP-order tolerance
                    # (pipeline/join checksums stay EXACT-equality)
                    and all(abs(a - b) <= 1e-5 * max(abs(a), abs(b), 1.0)
                            for a, b in zip(row["ck_group"][:2],
                                            base["ck_group"][:2])))
                if not ok:
                    section["parity_ok"] = False
                    section["parity_failures"].append(
                        {"rows": n, "devices": d})
            for kind in ("pipeline", "groupby", "join"):
                entry = {
                    "config": f"{kind}_r{n}_d{d}",
                    "rows": n, "devices": d,
                    f"{kind}_ms": row[f"{kind}_ms"],
                }
                if base is not None and d > 1:
                    entry["speedup_vs_1dev"] = round(
                        base[f"{kind}_ms"] / row[f"{kind}_ms"], 3) \
                        if row[f"{kind}_ms"] else None
                if kind == "pipeline":
                    entry["compiles_steady"] = row["compiles_steady"]
                section[kind].append(entry)
            log(json.dumps({"config": "sharded", "rows": n, "devices": d,
                            **{k: row[k] for k in ("pipeline_ms",
                                                   "groupby_ms",
                                                   "join_ms")}}))
    gold = run_arm(0, 8, golden=True,
                   data=os.path.join(REPO, "data", "dataset-abstract.csv"))
    if gold is not None:
        section["golden"] = gold
        section["golden_ok"] = (
            gold.get("count") == 24
            and abs(gold.get("rmse", 0.0) - 2.809940) / 2.809940 < 0.01)
        if not section["golden_ok"]:
            log(f"sharded golden MISMATCH: {gold}")
    return section


def bench_optimizer(session, log):
    """(optimizer) Cost-based plan optimizer (sql/optimizer.py): the
    pushdown / join-order / boundary arms, each timed with the optimizer
    OFF (the literal parse shape) vs ON, parity-asserted (exact column
    equality for the order-preserving level-1 rewrites; sorted-row
    equality for the level-2 join reorder, where SQL imposes no order),
    and golden-pinned via the headline DQ+Lasso workload run under BOTH
    settings (count 24 / RMSE 2.8099 each).

    CPU-sandbox honesty: the pushdown/join-order wins here come from the
    host-side join planning (fewer rows into the hash plan, the small
    side sorted), which is chip-independent; the boundary arm's win is
    avoided XLA recompiles, also host-side. TPU captures inherit the
    same structure."""
    import time as _time

    import jax
    import numpy as np

    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.config import config
    from sparkdq4ml_tpu.frame.frame import Frame
    from sparkdq4ml_tpu.ops import compiler as _compiler
    from sparkdq4ml_tpu.utils.profiling import counters

    n = 100_000 if SMOKE else 1_000_000
    reps = 3 if SMOKE else 7
    rng = np.random.default_rng(11)
    section = {"parity_ok": True, "parity_failures": [], "rows": n}
    saved = (config.optimizer_enabled, config.optimizer_level)

    def med(fn):
        ts = []
        for _ in range(reps):
            t0 = _time.perf_counter()
            fn()
            ts.append(_time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    big = Frame({"k": rng.integers(0, 4096, n).astype(np.float64),
                 "v": rng.normal(size=n),
                 **{f"x{i}": rng.normal(size=n) for i in range(6)}})
    mid = Frame({"k": np.arange(4096).astype(np.float64),
                 "u": rng.normal(size=4096)})
    small = Frame({"k": np.arange(64).astype(np.float64),
                   "w": rng.normal(size=64)})
    big.create_or_replace_temp_view("opt_big")
    mid.create_or_replace_temp_view("opt_mid")
    small.create_or_replace_temp_view("opt_small")

    def run(sql):
        out = session.sql(sql)
        jax.block_until_ready(out._mask)
        return out

    def sql_arm(name, sql, level=1, order_insensitive=False):
        config.optimizer_level = level
        config.optimizer_enabled = False
        ref = run(sql).to_pydict()          # warm plans off-arm
        t_off = med(lambda: run(sql)) * 1e3
        config.optimizer_enabled = True
        got = run(sql).to_pydict()          # warm plans + history on-arm
        t_on = med(lambda: run(sql)) * 1e3
        ok = sorted(ref) == sorted(got)
        if ok:
            for c in ref:
                a = np.asarray(ref[c], dtype=np.float64)
                b = np.asarray(got[c], dtype=np.float64)
                if order_insensitive:
                    a, b = np.sort(a), np.sort(b)
                ok = ok and a.shape == b.shape and bool(np.array_equal(a, b))
        if not ok:
            section["parity_ok"] = False
            section["parity_failures"].append(name)
        entry = {"config": f"optimizer_{name}",
                 "off_ms": round(t_off, 3), "on_ms": round(t_on, 3),
                 "speedup": round(t_off / t_on, 3) if t_on else None,
                 "rows_out": len(next(iter(ref.values()))) if ref else 0}
        section[name] = entry
        log(json.dumps(entry))
        return entry

    try:
        # (pushdown) selective WHERE past a join: the join's host-side
        # plan sees only surviving rows, and pruning drops the x0..x5
        # payload columns from the per-column join gathers
        sql_arm("pushdown",
                "SELECT k, v, u FROM opt_big JOIN opt_mid USING (k) "
                "WHERE v < -1.35")
        # (join_order) build-side selection: the 64-row side is the
        # LEFT relation, so the literal plan sorts the 1e6-row side;
        # the hint builds from the small side, bit-identical emission
        sql_arm("build_side",
                "SELECT k, w, v FROM opt_small JOIN opt_big USING (k)")
        # (join_order, level 2) reordering proper: the literal order
        # joins the 4096-row table first and carries every big row
        # through both plans; smallest-estimate-first joins the 64-row
        # table first and shrinks the intermediate 64x
        sql_arm("join_order",
                "SELECT v, u, w FROM opt_big JOIN opt_mid USING (k) "
                "JOIN opt_small USING (k) WHERE v < 0",
                level=2, order_insensitive=True)

        # (boundary) fused-stage boundary placement, level 2: V fresh
        # 12-step chains sharing a warm 6-step prefix. OFF compiles V
        # mega-programs; ON splits at the warm boundary (prefix replays,
        # only the 6-step tail compiles). Cold-compile wall-clock, one
        # pass per arm over a fresh plan cache.
        nv = 2 if SMOKE else 4
        fbase = Frame({"v": rng.normal(size=4096),
                       **{f"y{i}": rng.normal(size=4096)
                          for i in range(nv)}})

        def prefix(f):
            for i in range(6):
                f = f.with_column(f"p{i}", dq.col("v") * float(i + 1) + 0.5)
            return f

        def variant(f, j):
            f = prefix(f)
            for i in range(6):
                f = f.with_column(
                    f"t{i}", dq.col(f"y{j}") * dq.col(f"p{i}")
                    + dq.col(f"y{j}"))
            return f

        def flush(f):
            jax.block_until_ready(f._mask)
            return f

        def boundary_pass(enabled):
            _compiler.clear_cache()
            config.optimizer_enabled = True
            config.optimizer_level = 2 if enabled else 1
            flush(prefix(fbase))        # warm the prefix plan + history
            t0 = _time.perf_counter()
            outs = [flush(variant(fbase, j)) for j in range(nv)]
            dt = (_time.perf_counter() - t0) * 1e3
            return dt, outs[0]._data["t5"]

        t_b_off, ref_col = boundary_pass(False)
        splits0 = counters.get("optimizer.split")
        t_b_on, got_col = boundary_pass(True)
        splits = counters.get("optimizer.split") - splits0
        if not np.array_equal(np.asarray(ref_col), np.asarray(got_col)):
            section["parity_ok"] = False
            section["parity_failures"].append("boundary")
        entry = {"config": "optimizer_boundary", "variants": nv,
                 "off_ms": round(t_b_off, 3), "on_ms": round(t_b_on, 3),
                 "speedup": round(t_b_off / t_b_on, 3) if t_b_on else None,
                 "splits": splits}
        section["boundary"] = entry
        log(json.dumps(entry))

        # golden pin: the headline DQ+Lasso numbers under BOTH settings
        def golden_arm(enabled):
            config.optimizer_enabled = enabled
            config.optimizer_level = 2 if enabled else 1
            dq.register_builtin_rules()
            df = (session.read.format("csv")
                  .option("inferSchema", "true")
                  .load(os.path.join(REPO, "data",
                                     "dataset-abstract.csv")))
            df = (df.with_column_renamed("_c0", "guest")
                    .with_column_renamed("_c1", "price"))
            df = df.with_column(
                "price_no_min",
                dq.call_udf("minimumPriceRule", dq.col("price")))
            df.create_or_replace_temp_view("price")
            df = session.sql(
                "SELECT cast(guest as int) guest, price_no_min AS price "
                "FROM price WHERE price_no_min > 0")
            df = df.with_column(
                "price_correct_correl",
                dq.call_udf("priceCorrelationRule", dq.col("price"),
                            dq.col("guest")))
            df.create_or_replace_temp_view("price")
            df = session.sql(
                "SELECT guest, price_correct_correl AS price "
                "FROM price WHERE price_correct_correl > 0")
            count = df.count()
            from sparkdq4ml_tpu.models import (LinearRegression,
                                               VectorAssembler)

            df = df.with_column("label", df.col("price"))
            df = VectorAssembler(["guest"], "features").transform(df)
            model = LinearRegression(max_iter=40, reg_param=1.0,
                                     elastic_net_param=1.0).fit(df)
            return count, float(model.summary.root_mean_squared_error)

        c_off, r_off = golden_arm(False)
        c_on, r_on = golden_arm(True)
        golden = {"config": "optimizer_golden",
                  "count_off": c_off, "count_on": c_on,
                  "rmse_off": round(r_off, 4), "rmse_on": round(r_on, 4),
                  "golden_ok": bool(
                      c_off == 24 and c_on == 24 and r_off == r_on
                      and abs(r_on - 2.809940) < 0.01)}
        section["golden"] = golden
        if not golden["golden_ok"]:
            log(f"ERROR: optimizer bench golden MISMATCH: {golden}")
        log(json.dumps(golden))
    finally:
        config.optimizer_enabled, config.optimizer_level = saved
        for v in ("opt_big", "opt_mid", "opt_small"):
            try:
                session.sql(f"DROP VIEW IF EXISTS {v}")
            except Exception:
                pass
    return section


def bench_costprof(session, log):
    """(costprof) Device-cost observatory (utils/costprof.py +
    analysis/program/costs.py): AOT extraction latency per plan class
    (one lower+compile per cached program, amortized by the per-key
    cache + statstore persistence), report-render cost once warm, and
    the overhead-when-disabled pin — with spark.costprof.enabled=false
    the hot path pays one flag read, so the disabled-vs-never-loaded
    flush delta must be ~0 (reported as a ratio, gated by eye + the
    test-suite pin, not the regress gate: sub-ms deltas are noise).

    Chip-independence: extraction cost is host-side XLA compile time;
    the extracted flop/byte figures are the compiler's static
    accounting. Only the ACHIEVED gflops/gbps joins need real silicon."""
    import time as _time

    import jax
    import numpy as np

    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.config import config
    from sparkdq4ml_tpu.frame.frame import Frame
    from sparkdq4ml_tpu.utils import costprof
    from sparkdq4ml_tpu.utils import observability as _obs

    n = 100_000 if SMOKE else 1_000_000
    rng = np.random.default_rng(23)
    section = {"rows": n}
    saved = config.costprof_enabled

    def flush(f):
        jax.block_until_ready(f._mask)
        return f

    def chain(f):
        for i in range(8):
            f = f.with_column(f"c{i}", dq.col("v") * float(i + 1) + 0.25)
        return f.filter(dq.col("c7") > 0)

    frame = Frame({"v": rng.normal(size=n),
                   "k": rng.integers(0, 64, n).astype(np.float64)})
    try:
        # populate the caches the extractor will sweep: a fused
        # pipeline plan + a grouped plan
        from sparkdq4ml_tpu.frame import aggregates as A

        flush(chain(frame))
        frame.group_by("k").agg(A.sum("v"))

        # (overhead-when-disabled) steady-state flush wall with the
        # observatory off vs on — the hot path carries no costprof
        # hook, so this pins the one-flag-read contract at ~1.0
        def steady_flush():
            t0 = _time.perf_counter()
            flush(chain(frame))
            return (_time.perf_counter() - t0) * 1e3

        steady_flush()                      # warm
        config.costprof_enabled = False
        off = sorted(steady_flush() for _ in range(5))[2]
        config.costprof_enabled = True
        on = sorted(steady_flush() for _ in range(5))[2]
        section["disabled_flush_ms"] = round(off, 3)
        section["enabled_flush_ms"] = round(on, 3)
        section["disabled_overhead"] = round(on / off, 3) if off else None

        # (extraction latency per plan class) fresh profile cache; one
        # timed extract_all sweep, split per producer cache
        costprof.clear()
        handles, _errors = _obs.CACHES.programs()
        by_cache: dict = {}
        for h in handles:
            t0 = _time.perf_counter()
            prof = costprof.profile_for(h.program_key)
            dt = (_time.perf_counter() - t0) * 1e3
            row = by_cache.setdefault(
                h.cache, {"programs": 0, "profiled": 0,
                          "extract_ms": 0.0})
            row["programs"] += 1
            if prof is not None:
                row["profiled"] += 1
                row["extract_ms"] += dt
        for cache, row in sorted(by_cache.items()):
            row["extract_ms"] = round(row["extract_ms"], 3)
            entry = {"config": f"costprof_extract_{cache}", **row}
            log(json.dumps(entry))
        section["extract"] = by_cache

        # (report render) warm-cache fleet report cost
        t0 = _time.perf_counter()
        doc = costprof.report()
        section["report_ms"] = round((_time.perf_counter() - t0) * 1e3, 3)
        section["profiles"] = doc["size"]
        section["pending"] = doc["pending"]
        log(json.dumps({"config": "costprof_report",
                        "report_ms": section["report_ms"],
                        "profiles": section["profiles"],
                        "disabled_overhead": section["disabled_overhead"]}))
    finally:
        config.costprof_enabled = saved
    return section


def bench_dqprof(session, log):
    """(dqprof) Data-quality observatory (utils/dqprof.py): steady-state
    flush throughput with profiling ON (deferred sketch dispatch, zero
    host syncs) vs OFF, the overhead-when-disabled pin — with
    spark.dq.profile.enabled=false the hot path pays one flag read, so
    the disabled-vs-never-loaded flush delta must be ~1.0 (reported as
    a ratio, gated by eye + the test-suite pin, not the regress gate:
    sub-ms deltas are noise) — plus the cold drain + report-render
    cost once sketches have accumulated.

    Chip-independence: sketch reductions are tiny device programs; the
    profiled-vs-unprofiled ratio is the structural figure, the absolute
    walls are sandbox-dependent."""
    import time as _time

    import jax
    import numpy as np

    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.config import config
    from sparkdq4ml_tpu.frame.frame import Frame
    from sparkdq4ml_tpu.utils import dqprof

    n = 100_000 if SMOKE else 1_000_000
    rng = np.random.default_rng(29)
    section = {"rows": n}
    saved = config.dq_profile_enabled

    def flush(f):
        jax.block_until_ready(f._mask)
        return f

    def chain(f):
        for i in range(8):
            f = f.with_column(f"c{i}", dq.col("v") * float(i + 1) + 0.25)
        return f.filter(dq.col("c7") > 0)

    frame = Frame({"v": rng.normal(size=n)})

    def steady_flush():
        t0 = _time.perf_counter()
        flush(chain(frame))
        return (_time.perf_counter() - t0) * 1e3

    try:
        # warm both plan variants (hook on/off traces the same fused
        # program — the sketch programs are separate dispatches)
        config.dq_profile_enabled = True
        steady_flush()
        config.dq_profile_enabled = False
        steady_flush()

        # (overhead-when-disabled) the one-flag-read contract at ~1.0:
        # two interleaved disabled batches must agree (the per-flush
        # conf read neither accumulates nor drifts — the structural
        # zero-work pin is the raise-monkeypatch in tests/test_dqprof),
        # then profiled-vs-unprofiled prices the deferred sketch
        # dispatches themselves
        off_a = sorted(steady_flush() for _ in range(5))[2]
        off_b = sorted(steady_flush() for _ in range(5))[2]
        config.dq_profile_enabled = True
        dqprof.clear()
        on = sorted(steady_flush() for _ in range(5))[2]
        off = min(off_a, off_b)
        section["disabled_flush_ms"] = round(off, 3)
        section["profiled_flush_ms"] = round(on, 3)
        section["disabled_overhead"] = (round(off_b / off_a, 3)
                                        if off_a else None)
        section["profiled_overhead"] = round(on / off, 3) if off else None

        # (cold drain + report render) pull the accumulated deferred
        # sketches in the module's one batched counted sync, then the
        # warm report
        t0 = _time.perf_counter()
        doc = dqprof.report()
        section["report_ms"] = round((_time.perf_counter() - t0) * 1e3, 3)
        section["columns"] = doc["size"]
        section["pending"] = doc["pending"]
        log(json.dumps({"config": "dqprof_report",
                        "report_ms": section["report_ms"],
                        "columns": section["columns"],
                        "profiled_overhead": section["profiled_overhead"],
                        "disabled_flush_ms": section["disabled_flush_ms"],
                        "profiled_flush_ms": section["profiled_flush_ms"]}))
    finally:
        config.dq_profile_enabled = saved
    return section


def bench_aqe(session, log):
    """(aqe) Adaptive query execution (sql/adaptive.py): the two drift
    workloads, each run with AQE OFF (static plan to the end) vs ON,
    bit-parity asserted, replans counted from the ``aqe.replans``
    counters, and the headline ``adaptive_vs_static`` speedup reported
    per arm.

    * ``skewed_join`` — a hash-partitioned join plan whose probe side
      piles ~half its rows onto ONE key-hash partition; adaptive
      execution splits the skewed partition into balanced probe chunks
      (``spark.aqe.skewFactor``), merging back bit-identically.
    * ``misestimated_filter`` — a WHERE whose recorded selectivity says
      ~0.5% of rows survive into a GROUP BY; adaptive execution compacts
      the survivors into the observed power-of-two bucket
      (``spark.aqe.driftFactor``) so the grouped stage runs with far
      fewer padded slots.

    CPU-sandbox honesty: the structural claims (split happened, fewer
    padded slots, bit-parity) hold on any chip and are asserted here;
    the wall-clock speedup is real on device backends where padded
    slots cost device time, while on CPU the numbers are reported but
    gated only structurally."""
    import time as _time

    import jax
    import numpy as np

    from sparkdq4ml_tpu.config import config
    from sparkdq4ml_tpu.frame.frame import Frame, _vector_join_plan
    from sparkdq4ml_tpu.ops.compiler import bucket_size
    from sparkdq4ml_tpu.parallel.shard import partitioned_join_plan
    from sparkdq4ml_tpu.utils import statstore as _statstore
    from sparkdq4ml_tpu.utils.profiling import counters

    n = 50_000 if SMOKE else 400_000
    reps = 3 if SMOKE else 7
    rng = np.random.default_rng(23)
    section = {"parity_ok": True, "parity_failures": [], "rows": n}
    saved = (config.aqe_enabled, config.aqe_drift_factor,
             config.aqe_skew_factor)

    def med(fn):
        ts = []
        for _ in range(reps):
            t0 = _time.perf_counter()
            fn()
            ts.append(_time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    try:
        # (skewed_join) synthetic 4-way exchange, probe side ~60% on one
        # key (continuous-float keys — integer-valued doubles would all
        # hash into one partition and degenerate the exchange): static
        # plans the whole skewed partition in one searchsorted pass over
        # its build side; adaptive splits it into balanced chunks
        parts = 4
        config.aqe_skew_factor = 2.0
        rk = rng.random(1024) * 100.0
        lk = np.where(rng.random(n) < 0.6, rk[7],
                      rk[rng.integers(0, 1024, n)])
        li = np.arange(n, dtype=np.int64)
        ri = np.arange(rk.size, dtype=np.int64)

        def plan_join():
            return partitioned_join_plan(
                _vector_join_plan, [lk], [rk], li, ri, "inner", parts)

        config.aqe_enabled = False
        ref = plan_join()
        t_off = med(plan_join) * 1e3
        config.aqe_enabled = True
        r0 = counters.get("aqe.replans.skew-split")
        got = plan_join()
        splits = counters.get("aqe.replans.skew-split") - r0
        t_on = med(plan_join) * 1e3
        ok = (ref is not None and got is not None
              and np.array_equal(ref[0], got[0])
              and np.array_equal(ref[1], got[1]))
        if not ok or splits < 1:
            section["parity_ok"] = False
            section["parity_failures"].append("skewed_join")
        entry = {"config": "aqe_skewed_join",
                 "off_ms": round(t_off, 3), "on_ms": round(t_on, 3),
                 "adaptive_vs_static_speedup": (round(t_off / t_on, 3)
                                                if t_on else None),
                 "replans": int(splits),
                 "pairs": 0 if ref is None else int(ref[0].size)}
        section["skewed_join"] = entry
        log(json.dumps(entry))

        # (misestimated_filter) ~0.5% selectivity into a GROUP BY: the
        # first (history-seeding) run records the true selectivity; with
        # AQE on, the second run's re-bucket hook compacts the survivors
        # before the grouped stage
        Frame({"k": rng.integers(0, 64, n).astype(np.float64),
               "v": rng.normal(size=n)}).create_or_replace_temp_view(
            "aqe_mis")
        sql = ("SELECT k, sum(v) AS s FROM aqe_mis "
               "WHERE v > 2.575 GROUP BY k")

        def run():
            out = session.sql(sql)
            jax.block_until_ready(out._mask)
            return out

        config.aqe_enabled = False
        ref = run().to_pydict()             # seeds selectivity history
        _statstore.STORE.drain_pending()
        t_off = med(run) * 1e3
        config.aqe_enabled = True
        r0 = counters.get("aqe.replans.re-bucket")
        got = run().to_pydict()
        rebuckets = counters.get("aqe.replans.re-bucket") - r0
        t_on = med(run) * 1e3
        ok = sorted(ref) == sorted(got)
        if ok:
            for c in ref:
                a = np.sort(np.asarray(ref[c], dtype=np.float64))
                b = np.sort(np.asarray(got[c], dtype=np.float64))
                ok = ok and a.shape == b.shape \
                    and bool(np.array_equal(a, b))
        if not ok or rebuckets < 1:
            section["parity_ok"] = False
            section["parity_failures"].append("misestimated_filter")
        entry = {"config": "aqe_misestimated_filter",
                 "off_ms": round(t_off, 3), "on_ms": round(t_on, 3),
                 "adaptive_vs_static_speedup": (round(t_off / t_on, 3)
                                                if t_on else None),
                 "replans": int(rebuckets),
                 "slots_static": bucket_size(n),
                 "rows_out": len(next(iter(ref.values()))) if ref else 0}
        section["misestimated_filter"] = entry
        log(json.dumps(entry))
        section["replans"] = int(splits + rebuckets)
        if not section["parity_ok"]:
            log("ERROR: aqe bench parity/structural FAILURES: "
                f"{section['parity_failures']}")
    finally:
        (config.aqe_enabled, config.aqe_drift_factor,
         config.aqe_skew_factor) = saved
        try:
            session.sql("DROP VIEW IF EXISTS aqe_mis")
        except Exception:
            pass
    return section


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.config import config
    from sparkdq4ml_tpu.models import VectorAssembler
    from sparkdq4ml_tpu.models.classification import fused_logistic_fit_packed
    from sparkdq4ml_tpu.ops import pallas_kernels
    from sparkdq4ml_tpu.parallel.distributed import (fused_linear_fit_packed,
                                                     pack_design, place_packed,
                                                     unpack_fit_result)

    path = os.path.join(REPO, "data", "dataset-full.csv")
    session = dq.TpuSession.builder().app_name("bench").master("local[*]").get_or_create()
    log(f"devices: {jax.devices()}")
    backend = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    is_tpu = backend == "tpu"
    roof = roofline_for(device_kind) if is_tpu else None

    # ---- build the DQ-cleaned frame (no host reads of device arrays) ----
    dq.register_builtin_rules()
    df = (session.read.format("csv").option("inferSchema", "true")
          .option("header", "false").load(path))
    df = df.with_column_renamed("_c0", "guest").with_column_renamed("_c1", "price")
    df = df.with_column("price_no_min", dq.call_udf("minimumPriceRule", dq.col("price")))
    df.create_or_replace_temp_view("price")
    df = session.sql("SELECT cast(guest as int) guest, price_no_min AS price "
                     "FROM price WHERE price_no_min > 0")
    df = df.with_column("price_correct_correl",
                        dq.call_udf("priceCorrelationRule", dq.col("price"), dq.col("guest")))
    df.create_or_replace_temp_view("price")
    df = session.sql("SELECT guest, price_correct_correl AS price "
                     "FROM price WHERE price_correct_correl > 0")
    df = df.with_column("label", df.col("price"))
    df = VectorAssembler(["guest"], "features").transform(df)

    X = jnp.asarray(df._column_values("features"))
    y = jnp.asarray(df._column_values("label"))
    mask = df.mask
    mesh = None if session.mesh.devices.size <= 1 else session.mesh
    Zd = place_packed(pack_design(X, y, mask), mesh)

    # =====================================================================
    # PHASE 1 — every device timing loop
    # =====================================================================

    median_time = make_median_time(jax)

    def timed(op, args, reps=REPS):
        return median_time(lambda: op(*args), reps)

    # (a) headline: Lasso fit, one packed dispatch
    fit_a = fused_linear_fit_packed(mesh, "fista", 40, 1e-6, True, True)
    hyper_a = jnp.asarray([1.0, 1.0], Zd.dtype)
    result_a = jax.block_until_ready(fit_a(Zd, hyper_a))
    t_a = timed(fit_a, (Zd, hyper_a))

    # (c) elastic-net general path (FISTA, mixed penalty, 100 iters)
    fit_c = fused_linear_fit_packed(mesh, "fista", 100, 1e-6, True, True)
    hyper_c = jnp.asarray([0.3, 0.5], Zd.dtype)
    t_c = timed(fit_c, (Zd, hyper_c))

    # (d) logistic on DQ rows: per-iteration psum loop. hyper has no L1
    # part, so the production router (LogisticRegression.fit) picks the
    # damped-Newton solver — bench the same program users get.
    yb = (y > jnp.median(y)).astype(Zd.dtype)   # device-side label build
    Zb = place_packed(pack_design(X, yb, mask), mesh)
    fit_d = fused_logistic_fit_packed(mesh, 100, 1e-6, True, True,
                                      solver="newton")
    hyper_d = jnp.asarray([0.01, 0.0], Zd.dtype)
    result_d = jax.block_until_ready(fit_d(Zb, hyper_d))  # iters read later
    t_d = timed(fit_d, (Zb, hyper_d))

    # (d_scale) logistic at 1e6×16: the regime config (d) cannot show on
    # 1024 rows — here the fused on-device loop (zero host barriers, MXU
    # matmuls) is measured against sklearn lbfgs on the same shape.
    n_ds, d_ds = (100_000, 16) if SMOKE else (1_000_000, 16)
    Xds = jax.random.normal(jax.random.PRNGKey(7), (n_ds, d_ds), jnp.float32)
    w_true = jax.random.normal(jax.random.PRNGKey(8), (d_ds,), jnp.float32)
    noise = 0.5 * jax.random.normal(jax.random.PRNGKey(9), (n_ds,),
                                    jnp.float32)
    yds = (Xds @ w_true + noise > 0).astype(jnp.float32)
    Zds = jax.block_until_ready(place_packed(
        pack_design(Xds, yds, jnp.ones((n_ds,), jnp.float32)), mesh))
    del Xds, yds, noise
    fit_ds = fused_logistic_fit_packed(mesh, 100, 1e-6, True, True,
                                       solver="newton")
    result_ds = jax.block_until_ready(fit_ds(Zds, hyper_d))  # iters read later
    t_ds = timed(fit_ds, (Zds, hyper_d), max(3, REPS // 6))

    # (dq) the fused rules+filter pass — the reference's UDF hot loop
    # (`App.java:68-95`) as ONE elementwise device pass
    from sparkdq4ml_tpu.ops.rules import dq_rules_fused

    n_dq = 100_000 if SMOKE else 1_000_000
    price_dq = jax.random.uniform(jax.random.PRNGKey(3), (n_dq,),
                                  jnp.float32, 1.0, 120.0)
    guest_dq = jax.random.randint(jax.random.PRNGKey(4), (n_dq,),
                                  1, 40).astype(jnp.float32)
    fused_rules_fn = jax.jit(dq_rules_fused)
    t_rules = timed(fused_rules_fn, (price_dq, guest_dq))

    # (e) CrossValidator grid: the fused device-complete CV program
    from sparkdq4ml_tpu.models import LinearRegression
    from sparkdq4ml_tpu.models.evaluation import RegressionEvaluator
    from sparkdq4ml_tpu.models.tuning import (ParamGridBuilder,
                                              cv_device_program)

    grid_reg, grid_en, folds = [0.1, 0.5, 1.0], [0.0, 0.5, 1.0], 3
    grid = (ParamGridBuilder().add_grid("reg_param", grid_reg)
            .add_grid("elastic_net_param", grid_en).build())
    cv_prog, cv_args, _, _ = cv_device_program(
        df, LinearRegression(max_iter=40, tol=1e-6), grid, "rmse", folds,
        7, mesh, RegressionEvaluator("rmse").is_larger_better())
    t_e = timed(cv_prog, tuple(cv_args))

    # (sweep) masked-Gramian pass: XLA vs compiled Pallas, data on device
    @jax.jit
    def xla_gram(Z):
        return Z.T @ Z

    # bf16-STORED variant: rows live in HBM at half the bytes and the MXU
    # is bf16-native; accumulation stays f32 (preferred_element_type)
    @jax.jit
    def xla_gram_bf16(Zh):
        return jax.lax.dot_general(
            Zh, Zh, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    sweep_rows = []        # timings (host floats, no device reads)
    pallas_diffs = []      # on-device |A_p - A_x| max scalars, read later
    pallas_mode = "on" if is_tpu else "interpret"
    for (n, d) in SWEEP_SHAPES:
        key = jax.random.PRNGKey(n + d)
        Z = jax.random.normal(key, (n, d + 2), jnp.float32)
        Z = jax.block_until_ready(Z)
        gb = n * (d + 2) * 4 / 1e9

        t_x = timed(xla_gram, (Z,), SWEEP_REPS)

        # bf16-stored Gramian is gated to TPU captures (VERDICT r4 item 6):
        # the variant exists for the MXU (bf16-native) + halved HBM bytes;
        # on CPU it measures only a conversion penalty (r4: 0.29–0.81×),
        # which read as a defect rather than a chip-only optimization.
        t_h = None
        if is_tpu:
            Zh = jax.block_until_ready(Z.astype(jnp.bfloat16))
            t_h = timed(xla_gram_bf16, (Zh,), SWEEP_REPS)
            gb_h = n * (d + 2) * 2 / 1e9
            del Zh

        t_p = None
        best_block = None
        pallas_err = None
        # Off-TPU the Pallas interpreter executes element-by-element — the
        # numerics cross-check at full sweep sizes would run for hours, so
        # it only runs compiled (TPU) or on the SMOKE shapes.
        if is_tpu or SMOKE:
            config.pallas = pallas_mode
            try:
                A_p = pallas_kernels.packed_gram_pallas(Z)
                if is_tpu:
                    # Pre-pad rows to a multiple of every autotune block so
                    # the in-call pad branch (a full concatenate) never
                    # executes INSIDE the timing chain; zero rows add
                    # nothing to ZᵀZ and <4% to the traffic.
                    pal_pad = (-n) % 4096
                    Zp = jnp.concatenate(
                        [Z, jnp.zeros((pal_pad, d + 2), Z.dtype)]) \
                        if pal_pad else Z
                    Zp = jax.block_until_ready(Zp)
                    # Row-tile autotune: bigger tiles amortize grid/DMA
                    # overhead. Candidates whose input block would blow
                    # VMEM at this width are skipped up front (the full-D
                    # left operand double-buffers at block_rows × padded
                    # lanes), and a candidate that still fails on-chip
                    # only voids itself, not the cell.
                    lanes_pad = -((d + 2) // -128) * 128
                    for blk in (512, 1024, 2048, 4096):
                        if blk > n or blk * lanes_pad * 4 * 3 > 8 << 20:
                            continue

                        def pal_op(Zi, _blk=blk):
                            return pallas_kernels.packed_gram_pallas(
                                Zi, block_rows=_blk)

                        try:
                            t_b = timed(pal_op, (Zp,), SWEEP_REPS)
                        except Exception as e:  # noqa: BLE001
                            log(f"pallas block {blk} @ ({n},{d}) failed: "
                                f"{type(e).__name__}: {str(e)[:120]}")
                            continue
                        if t_b is not None and (t_p is None or t_b < t_p):
                            t_p, best_block = t_b, blk
                    del Zp
                A_x = xla_gram(Z)
                scale = jnp.maximum(jnp.max(jnp.abs(A_x)), 1.0)
                pallas_diffs.append(
                    ((n, d), jnp.max(jnp.abs(A_p - A_x)) / scale))
            except Exception as e:  # noqa: BLE001 - one bad cell must not
                # kill a whole TPU capture; the cell reports the error and
                # the sweep continues.
                t_p, best_block = None, None
                pallas_err = f"{type(e).__name__}: {str(e)[:300]}"
                log(f"pallas cell ({n},{d}) failed: {pallas_err}")
            finally:
                config.pallas = "off"

        sweep_rows.append({
            "rows": n, "features": d,
            "xla_ms": round(t_x * 1e3, 3) if t_x else None,
            "xla_gbps": round(gb / t_x, 1) if t_x else None,
            "bf16_ms": round(t_h * 1e3, 3) if t_h else None,
            "bf16_gbps": round(gb_h / t_h, 1) if t_h else None,
            "bf16_rows_speedup": round(t_x / t_h, 2) if t_x and t_h else None,
            "pallas_ms": round(t_p * 1e3, 3) if t_p else None,
            "pallas_gbps": round(gb / t_p, 1) if t_p else None,
            "pallas_block": best_block,
            **({"pallas_error": pallas_err} if pallas_err else {}),
        })
        del Z

    # =====================================================================
    # PHASE 2 — host reads, CPU baselines, assertions
    # =====================================================================
    n_rows = df.count()
    log(f"DQ-clean rows: {n_rows} (expect 1024)")
    result = unpack_fit_result(result_a, 1)
    coef = float(result.coefficients[0])
    intercept = float(result.intercept)
    d_host = df.to_pydict()
    yv = d_host["label"].astype(np.float64)
    xv = d_host["guest"].astype(np.float64)
    rmse = float(np.sqrt(np.mean((yv - (coef * xv + intercept)) ** 2)))
    drift = abs(rmse - GOLDEN_RMSE_FULL) / GOLDEN_RMSE_FULL
    log(f"fit: coef={coef:.6f} intercept={intercept:.6f} rmse={rmse:.6f} "
        f"drift={drift*100:.4f}% (budget 1%)")
    if drift > 0.01:
        log("ERROR: RMSE drift exceeds the 1% acceptance budget")
        sys.exit(1)

    # pallas numerics: assert before reporting any pallas number
    for (shape, diff_dev) in pallas_diffs:
        diff = float(diff_dev)
        log(f"pallas-vs-xla rel diff @ {shape}: {diff:.2e}")
        if not diff < 5e-5:
            log(f"ERROR: pallas Gramian diverges from XLA at {shape}")
            sys.exit(1)

    # CPU baselines --------------------------------------------------------
    # sklearn is a strictly faster Spark-CPU proxy; without it, a pure-numpy
    # ISTA stands in for (a) and c/d report no baseline rather than dying
    # (the driver contract — one JSON line — must survive a missing dep).
    Xh = xv.reshape(-1, 1)
    sx, sy = Xh.std(ddof=1), yv.std(ddof=1)
    Xs = (Xh - Xh.mean()) / sx
    ys = (yv - yv.mean()) / sy
    yb_h = (yv > np.median(yv)).astype(np.float64)

    try:
        from sklearn.linear_model import (ElasticNet, Lasso,
                                          LogisticRegression as SkLogit)
        have_sklearn = True
    except ImportError:
        have_sklearn = False

    sk_iters_d = None
    sk_iters_ds = None
    t_ds_cpu = None
    if have_sklearn:
        base_a = "sklearn Lasso(cd) maxIter=40"
        t_a_cpu = median_time(
            lambda: Lasso(alpha=1.0 / sy, max_iter=40, tol=1e-6).fit(Xs, ys),
            REPS)
        t_c_cpu = median_time(
            lambda: ElasticNet(alpha=0.3 / sy, l1_ratio=0.5, max_iter=100,
                               tol=1e-6).fit(Xs, ys), REPS)
        t_d_cpu = median_time(
            lambda: SkLogit(C=100.0, max_iter=100, tol=1e-6).fit(Xs, yb_h),
            REPS)
        sk_iters_d = int(np.ravel(SkLogit(C=100.0, max_iter=100, tol=1e-6)
                                  .fit(Xs, yb_h).n_iter_)[0])

        # d_scale baseline: same shape/regime, independent draw (the
        # comparison is solver-vs-solver on the task family, not bitwise)
        rng_ds = np.random.default_rng(11)
        Xh_ds = rng_ds.standard_normal((n_ds, d_ds)).astype(np.float64)
        wh = rng_ds.standard_normal(d_ds)
        yh_ds = (Xh_ds @ wh + 0.5 * rng_ds.standard_normal(n_ds) > 0
                 ).astype(np.float64)
        est_ds = SkLogit(C=100.0, max_iter=100, tol=1e-6)
        t_ds_cpu = median_time(lambda: est_ds.fit(Xh_ds, yh_ds), 3)
        # n_iter_ read off the last timed fit — a dedicated fourth fit
        # would add a full t_ds_cpu to every capture for one integer
        sk_iters_ds = int(np.ravel(est_ds.n_iter_)[0])
        del Xh_ds
    else:
        base_a = "numpy ISTA maxIter=40"

        def ista():
            w = 0.0
            h = float(Xs[:, 0] @ Xs[:, 0]) / len(ys)
            c0 = float(Xs[:, 0] @ ys) / len(ys)
            lam = 1.0 / sy
            for _ in range(40):
                g = h * w - c0
                w = np.sign(w - g / h) * max(abs(w - g / h) - lam / h, 0.0)

        t_a_cpu = median_time(ista, REPS)
        t_c_cpu = t_d_cpu = None

    # CPU gram GB/s context for the sweep's smaller cells
    for row in sweep_rows:
        shape = (row["rows"], row["features"])
        if shape in CPU_SWEEP_SHAPES:
            rng = np.random.default_rng(0)
            Zc = rng.standard_normal((shape[0], shape[1] + 2),
                                     dtype=np.float32)
            t_cpu = median_time(lambda: Zc.T @ Zc, SWEEP_REPS)
            row["cpu_gbps"] = round(
                shape[0] * (shape[1] + 2) * 4 / 1e9 / t_cpu, 1)

    # (dq) numpy baseline for the fused rules pass — the vectorized-host
    # equivalent of the reference's per-row UDF chain
    rng_dq = np.random.default_rng(12)
    ph = rng_dq.uniform(1.0, 120.0, n_dq).astype(np.float32)
    gh = rng_dq.integers(1, 40, n_dq).astype(np.float32)

    def np_rules():
        pnm = np.where(ph < 20, -1.0, ph)
        pcc = np.where((gh < 14) & (ph > 90), -1.0, ph)
        return pnm, pcc, (pnm > 0) & (pcc > 0)

    t_rules_cpu = median_time(np_rules, REPS)
    # bytes touched: 2 f32 inputs read + 2 f32 outputs + 1 bool written
    rules_bytes = n_dq * (4 * 4 + 1)

    # (dq) CSV parse throughput: native C++ tokenizer vs pure-Python vs
    # pandas on a synthetic (guest,price) file at DQ-bench scale
    import tempfile

    n_csv = 100_000 if SMOKE else 1_000_000
    # unique per run: a fixed name would let concurrent benches race on
    # write/parse/remove
    csv_fd, csv_path = tempfile.mkstemp(prefix=f"dq_bench_{n_csv}_",
                                        suffix=".csv")
    rng_csv = np.random.default_rng(13)
    guests_csv = rng_csv.integers(1, 40, n_csv)
    prices_csv = np.round(rng_csv.uniform(1.0, 120.0, n_csv), 2)
    with os.fdopen(csv_fd, "w") as f:
        f.write("\n".join(f"{g},{p}" for g, p in
                          zip(guests_csv, prices_csv)))
        f.write("\n")
    csv_bytes = os.path.getsize(csv_path)

    from sparkdq4ml_tpu.frame import native_csv
    from sparkdq4ml_tpu.frame.csv import read_csv

    t_parse_native = None
    if native_csv.available():
        t_parse_native = median_time(
            lambda: read_csv(csv_path, engine="native"), 3)
    # the pure-python engine is O(seconds) at 1e6 rows, and a host parser
    # has no compile cache to warm: ONE direct timed run, no warmup rep
    t0 = time.perf_counter()
    read_csv(csv_path, engine="python")
    t_parse_py = time.perf_counter() - t0
    t_parse_pandas = None
    try:
        import pandas as pd

        t_parse_pandas = median_time(
            lambda: pd.read_csv(csv_path, header=None), 3)
    except ImportError:
        pass
    try:
        os.remove(csv_path)   # ~15 MB of /tmp litter otherwise
    except OSError:
        pass

    # (frame_pipeline) fused expression-pipeline compiler vs eager per-op
    # dispatch on a 20-op frame chain (the dispatch overhead being
    # eliminated is host-side on every backend)
    n_fp = 100_000 if SMOKE else 1_000_000
    frame_pipeline = bench_frame_pipeline(median_time, n_fp)

    # (grouped_ops) device-resident groupBy/sort/distinct vs the host
    # numpy path (ops/segments.py) across a rows × groups grid
    grouped_ops = bench_grouped_ops(median_time)

    # (ingest) streaming native CSV parse: scalar vs SIMD vs SIMD+threads
    # vs the full prefetch pipeline, bit-parity + golden-pinned
    ingest = bench_ingest(median_time, session)

    # (serving) closed-loop multi-tenant QPS/p99 on the headline DQ+Lasso
    # query (serve/), shared plan cache on vs off, golden-pinned
    serving = bench_serving(session,
                            os.path.join(REPO, "data",
                                         "dataset-abstract.csv"))

    if SMOKE and "BENCH_SHARD_ROWS" not in os.environ:
        os.environ["BENCH_SHARD_ROWS"] = "100000"
    sharded = bench_sharded(log)

    # (optimizer) cost-based plan rewrites: pushdown / join-order /
    # boundary arms, off-vs-on, parity-asserted, golden-pinned
    optimizer_sec = bench_optimizer(session, log)

    # (costprof) device-cost observatory: extraction latency per plan
    # class, report-render cost, overhead-when-disabled pinned ~0
    costprof_sec = bench_costprof(session, log)

    # (dqprof) data-quality observatory: profiled-vs-unprofiled flush
    # throughput, overhead-when-disabled pinned ~1.0, cold drain cost
    dqprof_sec = bench_dqprof(session, log)

    # (aqe) adaptive execution: skewed-join + misestimated-filter arms,
    # off-vs-on, bit-parity + structural assertions, replans counted
    aqe_sec = bench_aqe(session, log)

    # (e) baseline: sklearn GridSearchCV, same 3x3 grid / folds / family,
    # refit=True to match the in-program best-model refit
    t_e_cpu = None
    if have_sklearn:
        from sklearn.model_selection import GridSearchCV

        def cpu_grid():
            GridSearchCV(ElasticNet(max_iter=40, tol=1e-6),
                         {"alpha": [r / sy for r in grid_reg],
                          "l1_ratio": grid_en},
                         cv=folds, scoring="neg_root_mean_squared_error",
                         n_jobs=1, refit=True).fit(Xs, ys)

        t_e_cpu = median_time(cpu_grid, REPS)

    # =====================================================================
    # PHASE 3 — report
    # =====================================================================
    def cfg(name, t_dev, baseline_name, t_cpu, **extra):
        out = {"config": name,
               "device_ms": round(t_dev * 1e3, 4) if t_dev else None,
               "baseline": baseline_name if t_cpu else "unavailable",
               "baseline_ms": round(t_cpu * 1e3, 4) if t_cpu else None,
               "vs_baseline": round(t_cpu / t_dev, 2)
               if t_cpu and t_dev else None}
        out.update({k: v for k, v in extra.items() if v is not None})
        return out

    # Config (d) has never cleared 10× on 1024 rows and the reason is
    # structural, not a bug: report it instead of hiding it.
    iters_d = int(unpack_fit_result(np.asarray(result_d), 1).iterations)
    sk_clause = (f"vs sklearn lbfgs converging in {sk_iters_d} iterations"
                 if sk_iters_d is not None else
                 "(no sklearn baseline available)")
    analysis_d = (
        f"device runs {iters_d} damped-Newton iterations inside one fused "
        f"dispatch {sk_clause} on 1024 rows; at this size wall-clock is "
        f"bounded by per-dispatch overhead, not FLOPs — see "
        f"d_scale_logistic for the regime where the fused loop wins")

    # d_scale: close the argument with iteration-level numbers (VERDICT r4
    # item 3). CPU-vs-CPU the honest finding is parity: XLA-CPU's fused
    # damped-Newton and sklearn's lbfgs both converge in a handful of
    # iterations at 1e6×16 and both are memory-bound on the same host, so
    # neither side has a structural edge. The fused loop's claimed win —
    # zero per-iteration host barriers (vs treeAggregate, SURVEY §3.3) and
    # MXU matmuls — only materializes on the chip.
    iters_ds = int(unpack_fit_result(np.asarray(result_ds), d_ds).iterations)
    dev_ms_it = t_ds * 1e3 / max(iters_ds, 1) if t_ds else None
    if t_ds_cpu is not None and sk_iters_ds is not None:
        cpu_ms_it = t_ds_cpu * 1e3 / max(sk_iters_ds, 1)
        ds_cpu_clause = (f"sklearn lbfgs: {sk_iters_ds} iterations × "
                         f"{cpu_ms_it:.1f} ms/iter")
    else:
        ds_cpu_clause = "no sklearn baseline available"
    dev_it_clause = (f"{dev_ms_it:.1f} ms/iter" if dev_ms_it is not None
                     else "unmeasured ms/iter")
    if is_tpu:
        analysis_ds = (
            f"on-chip capture: fused damped-Newton runs {iters_ds} "
            f"iterations × {dev_it_clause} in one dispatch "
            f"(zero host barriers) vs {ds_cpu_clause} on the host CPU")
    else:
        analysis_ds = (
            f"CPU-vs-CPU this is parity, not a win: XLA-CPU fused Newton "
            f"({iters_ds} iterations × {dev_it_clause}, one "
            f"dispatch) vs {ds_cpu_clause}; both are memory-bound on the "
            f"same cores. The fused loop's claimed advantage — eliminating "
            f"the per-iteration host barrier (treeAggregate analogue, "
            f"SURVEY §3.3) and MXU-resident matmuls — requires the chip; "
            f"no on-chip number exists in this capture")

    configs = [
        cfg("a_linear_lasso_dataset_full", t_a, base_a, t_a_cpu),
        cfg("c_elasticnet_fista_path", t_c,
            "sklearn ElasticNet(cd) maxIter=100", t_c_cpu),
        cfg("d_logistic_dq_rows", t_d,
            "sklearn LogisticRegression(lbfgs) maxIter=100", t_d_cpu,
            analysis=analysis_d),
        cfg(f"d_scale_logistic_{n_ds}x{d_ds}", t_ds,
            f"sklearn LogisticRegression(lbfgs) {n_ds}x{d_ds}", t_ds_cpu,
            analysis=analysis_ds, device_iterations=iters_ds,
            device_ms_per_iter=round(dev_ms_it, 2)
            if dev_ms_it is not None else None,
            baseline_iterations=sk_iters_ds,
            baseline_ms_per_iter=round(t_ds_cpu * 1e3 / max(sk_iters_ds, 1),
                                       2)
            if t_ds_cpu is not None and sk_iters_ds else None),
        cfg("e_crossvalidator_grid", t_e,
            f"sklearn GridSearchCV(ElasticNet) {len(grid)}x{folds} refit",
            t_e_cpu),
        cfg(f"dq_rules_fused_{n_dq}", t_rules,
            f"numpy vectorized rules {n_dq}", t_rules_cpu,
            device_gbps=round(rules_bytes / t_rules / 1e9, 2)
            if t_rules else None,
            baseline_gbps=round(rules_bytes / t_rules_cpu / 1e9, 2),
            # The ~12 MB working set fits VMEM, so chained iterations
            # run on-chip-resident — device_gbps above the 819 GB/s HBM
            # roofline is expected and means VMEM-resident throughput,
            # not HBM streaming (see top-level timing_note).
            analysis=(
                "operands (~12 MB) stay VMEM-resident across chained "
                "iterations; device_gbps above the HBM roofline reports "
                "on-chip throughput, not HBM streaming — see timing_note")
            if is_tpu else None),
    ]
    parse_cfg = {
        "config": f"dq_parse_csv_{n_csv}",
        "file_mb": round(csv_bytes / 1e6, 1),
        "native_ms": round(t_parse_native * 1e3, 1) if t_parse_native
        else None,
        "native_gbps": round(csv_bytes / t_parse_native / 1e9, 3)
        if t_parse_native else None,
        "python_ms": round(t_parse_py * 1e3, 1),
        "python_gbps": round(csv_bytes / t_parse_py / 1e9, 3),
        "pandas_ms": round(t_parse_pandas * 1e3, 1) if t_parse_pandas
        else None,
        "pandas_gbps": round(csv_bytes / t_parse_pandas / 1e9, 3)
        if t_parse_pandas else None,
        "native_vs_python": round(t_parse_py / t_parse_native, 2)
        if t_parse_native else None,
        # The VERDICT-r4 cycle budget: where the single-core ns/byte goes.
        # Stage costs measured with a C-level stage harness on this host
        # class (1-core Xeon 2.1 GHz). The parse is bitmap-first: phase A
        # classifies every structural byte (AVX2 compare+movemask, ~24
        # GB/s) into a bitmap that also yields the record count; phase B
        # walks set bits, so each field's ADDRESS comes from the bitmap
        # instead of the previous field's parsed length — the ~20-cycle
        # per-field convert chains (Lemire SWAR digits, exact /10^frac)
        # are independent work the OoO core overlaps. Direct column-major
        # store; integral int32 flags are free for bare-digit fields (a
        # frac==0 word parse is integral by construction). No staging
        # vector, no transpose, no libm calls.
        "analysis": (
            f"{t_parse_native * 1e9 / csv_bytes:.2f} ns/byte end-to-end "
            "(python wrapper incl. one astype copy per column); C stage "
            "budget at ~4.4-byte fields: quote memchr ~0.07 ns/B, "
            "structural bitmap ~0.05, bitmap walk + field converts + "
            "column store ~2.2 — the per-field exact-divide (10^frac) "
            "and store/flag dispatch are the binding cost now that "
            "converts overlap; the next step-change needs batched "
            "multi-field SIMD conversion (AVX-512 class)")
        if t_parse_native else None,
    }
    configs.append(parse_cfg)

    # Roofline fractions (TPU only): achieved ÷ chip peak per sweep cell.
    # mfu uses the bf16 matmul peak as denominator for the f32 cells too,
    # making their mfu a conservative lower bound (stated in the README).
    if roof is not None:
        hbm_peak, tflops_peak = roof
        for row in sweep_rows:
            n_r, d_r = row["rows"], row["features"]
            flops = 2.0 * n_r * (d_r + 2) ** 2
            if row["xla_ms"]:               # None/0 = unmeasurable cell
                row["hbm_frac"] = round(row["xla_gbps"] / hbm_peak, 4)
                row["mfu"] = round(
                    flops / (row["xla_ms"] / 1e3) / (tflops_peak * 1e12), 4)
            if row["bf16_ms"]:
                row["bf16_hbm_frac"] = round(row["bf16_gbps"] / hbm_peak, 4)
                row["bf16_mfu"] = round(
                    flops / (row["bf16_ms"] / 1e3) / (tflops_peak * 1e12), 4)
            if row.get("pallas_gbps"):
                row["pallas_hbm_frac"] = round(
                    row["pallas_gbps"] / hbm_peak, 4)

    for c in configs:
        log(json.dumps(c))
    # frame_pipeline lives ONLY under its top-level key (the README
    # contract) — appending it to configs too would double-count it for
    # tooling that aggregates config rows; the stderr echo is for humans
    log(json.dumps(frame_pipeline))
    for row in sweep_rows:
        log(json.dumps(row))

    print(json.dumps({
        "metric": "linear_regression_fit_wallclock_dataset_full",
        "value": round(t_a * 1e3, 4) if t_a else None,
        "unit": "ms",
        "vs_baseline": round(t_a_cpu / t_a, 3) if t_a else None,
        "configs": configs,
        "frame_pipeline": frame_pipeline,
        "grouped_ops": grouped_ops,
        "ingest": ingest,
        "serving": serving,
        "sharded": sharded,
        "optimizer": optimizer_sec,
        "costprof": costprof_sec,
        "dqprof": dqprof_sec,
        "aqe": aqe_sec,
        "sweep": sweep_rows,
        "pallas_max_rel_diff": max((float(d) for _, d in pallas_diffs),
                                   default=None),
        "backend": backend,
        "device_kind": device_kind,
        "bf16_gated": None if is_tpu else (
            "bf16-stored Gramian gated to TPU captures: no MXU on this "
            "backend, the variant would measure only a conversion penalty"),
        "roofline": {"hbm_gbps": roof[0], "bf16_tflops": roof[1]}
        if roof else None,
    }))


if __name__ == "__main__":
    main()
