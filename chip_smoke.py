#!/usr/bin/env python3
"""The quickest proof that the DQ -> fit -> serve path still starts on the chip.

    python3 chip_smoke.py

One process, one session, the entry points a user would call
(``TpuSession.builder()``, ``session.read``, ``dq.call_udf``,
``session.sql``, the estimators, ``session.serve`` and
``serve.ResilientClient``), every result checked against a float64 numpy
reference computed here. It exits non-zero on the first failed check, and
the command line always demands a TPU: without one it prints no result and
exits 2 — a CPU run is never reported as a pass. Every line it prints names
``jax.devices()[0].platform``, ``device_kind`` and the device count; the
last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Stages (``run``): A the reference app on ``data/dataset-full.csv``; B the
same path on a seed-generated 4,000,000-row CSV (streaming native ingest,
both rules, both SQL filters, GROUP BY, ORDER BY, Lasso fit) plus a
1,000,000 x 16 logistic fit, with the flushes run twice; C eight requests
through the socket front end; E nothing degraded on the way (device
placement, recovery log, fallback counters, dq profile,
``block_until_ready``, the compile cache); F, on more than one device, the
sharded frame path and the sharded gradient. There is no stage D: the
kernels it compiled are gone, and the other stages keep their letters.

``tests/test_chip_smoke.py`` imports ``run`` and drives it at tiny sizes
with the platform it expects there.
"""

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

GOLDEN_FULL = {"count": 1024, "rmse": 1.805140, "predict40": 219.11}
GOLDEN_ABSTRACT = {"count": 24, "rmse": 2.809940}

#: Counters that must not move during a run: each one is a path that
#: finished the job some degraded way and would otherwise exit 0.
DEGRADED_COUNTERS = (
    "pipeline.fault_fallback", "pipeline.fallback", "pipeline.oom_chunked",
    "pipeline.shard_gather", "ingest.fault_fallback",
    "ingest.python_fallback", "dq.profile_failed", "dq.pending_dropped",
    "grouped.fault_fallback", "grouped.fallback")


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


class Smoke:
    """Device identity + the report/check helpers every stage uses."""

    def __init__(self, expect_platform: str):
        import jax

        devices = jax.devices()
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}
        self.expect = expect_platform
        self.stage = "start"
        self.t0 = time.perf_counter()
        self.check(self.device["platform"] == expect_platform,
                   f"expected platform {expect_platform!r}")

    def report(self, **facts) -> None:
        print(json.dumps({"stage": self.stage,
                          "t_s": round(time.perf_counter() - self.t0, 1),
                          **facts, "device": self.device}), flush=True)

    def check(self, cond, what: str) -> None:
        if not cond:
            raise SmokeFailure(
                f"stage {self.stage}: {what} [device {self.device}]")

    def approx(self, got, want, rtol: float, what: str) -> float:
        """Largest error of ``got`` against ``want`` relative to the
        largest reference magnitude; fails above ``rtol``."""
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        self.check(got.shape == want.shape,
                   f"{what}: shape {got.shape} != {want.shape}")
        self.check(bool(np.all(np.isfinite(got))), f"{what}: not finite")
        err = float(np.max(np.abs(got - want))
                    / max(float(np.max(np.abs(want))), 1e-30))
        self.check(err <= rtol, f"{what}: {got.tolist()} vs reference "
                   f"{want.tolist()} (rel err {err:.3g} > {rtol})")
        return err

    def on_expected_platform(self, arr, what: str) -> None:
        platforms = {d.platform for d in arr.devices()}
        self.check(platforms == {self.expect},
                   f"{what} lives on {sorted(platforms)}")


# ---------------------------------------------------------------------------
# The reference app's call sequence (examples/dq4ml_pipeline.py)
# ---------------------------------------------------------------------------

def load_csv(spark, path):
    df = (spark.read.format("csv")
          .option("inferSchema", "true").option("header", "false")
          .load(path))
    df = df.with_column_renamed("_c0", "guest")
    return df.with_column_renamed("_c1", "price")


def dq_phase(spark, df):
    import sparkdq4ml_tpu as dq

    df = df.with_column("price_no_min",
                        dq.call_udf("minimumPriceRule", df.col("price")))
    df.create_or_replace_temp_view("price")
    df = spark.sql("SELECT cast(guest as int) guest, price_no_min AS price "
                   "FROM price WHERE price_no_min > 0")
    df = df.with_column("price_correct_correl",
                        dq.call_udf("priceCorrelationRule",
                                    df.col("price"), df.col("guest")))
    df.create_or_replace_temp_view("price")
    return spark.sql("SELECT guest, price_correct_correl AS price "
                     "FROM price WHERE price_correct_correl > 0")


def assemble(df):
    from sparkdq4ml_tpu.models import VectorAssembler

    df = df.with_column("label", df.col("price"))
    return (VectorAssembler().setInputCols(["guest"])
            .setOutputCol("features").transform(df))


def lasso():
    from sparkdq4ml_tpu.models import LinearRegression

    return (LinearRegression().setMaxIter(40).setRegParam(1)
            .setElasticNetParam(1))


def peak_bf16_flops_per_s(device_kind: str) -> float:
    """The chip's published bf16 peak, from the benchmark's table of peaks;
    a kind the table does not hold raises."""
    from benchmarks import harness

    peaks = harness.load_json(os.path.join(REPO, "benchmarks", "peaks.json"))
    return harness.peaks_for(peaks, device_kind)["bf16_flops_per_s"]


# ---------------------------------------------------------------------------
# float64 references
# ---------------------------------------------------------------------------

def rules_reference(guest, price):
    """Both DQ rules + both filters in float64 numpy: the keep mask and,
    per rule, the (rows seen, violations) tallies the dq profile may
    report. The profile counts against a flush's INPUT mask: when both
    rules fuse into one flush the second rule also sees — and rejects —
    the rows the first one marked."""
    from sparkdq4ml_tpu.ops.rules import (CORRELATION_MAX_GUESTS,
                                          CORRELATION_MAX_PRICE, MIN_PRICE)

    n = int(price.size)
    bad1 = price < MIN_PRICE
    keep1 = ~bad1
    bad2 = keep1 & (guest < CORRELATION_MAX_GUESTS) \
        & (price > CORRELATION_MAX_PRICE)
    n1, n2 = int(bad1.sum()), int(bad2.sum())
    return keep1 & ~bad2, {
        "minimumPriceRule": {(n, n1)},
        "priceCorrelationRule": {(n, n1 + n2), (n - n1, n2)}}


def lasso_reference(x, y, reg: float = 1.0):
    """Closed form of the one-feature MLlib Lasso (standardized space,
    sample standard deviations, ``regParam / std_y`` as the L1 weight)."""
    n = x.size
    mx, my = x.mean(), y.mean()
    sx, sy = x.std(ddof=1), y.std(ddof=1)
    b = ((x - mx) * (y - my)).sum() / (n * sx * sy)
    g = (n - 1.0) / n
    w = np.sign(b) * max(abs(b) - reg / sy, 0.0) / g
    coef = w * sy / sx
    return coef, my - coef * mx


def logistic_reference(X, y, iters: int = 25):
    """Unpenalized logistic MLE by Newton's method in float64."""
    Xa = np.concatenate([X.astype(np.float64),
                         np.ones((X.shape[0], 1))], axis=1)
    w = np.zeros(Xa.shape[1])
    for _ in range(iters):
        p = 1.0 / (1.0 + np.exp(-(Xa @ w)))
        step = np.linalg.solve((Xa * (p * (1 - p))[:, None]).T @ Xa,
                               Xa.T @ (p - y))
        w -= step
        if np.max(np.abs(step)) < 1e-12:
            break
    return w[:-1], w[-1]


def check_group_counts(ctx: "Smoke", grouped: dict, g_ref, what: str):
    """``grouped`` (a ``guest``/``n`` pydict) holds exactly the reference's
    keys and counts; returns the row order that sorts it by key."""
    order = np.argsort(np.asarray(grouped["guest"]))
    keys = np.unique(g_ref).astype(int)
    ctx.check(np.array_equal(np.asarray(grouped["guest"])[order], keys),
              f"{what} keys differ from the reference")
    ctx.check(np.array_equal(np.asarray(grouped["n"])[order],
                             np.bincount(g_ref.astype(int))[keys]),
              f"{what} counts differ from the reference")
    return order, keys


def write_catering_csv(path: str, rows: int, seed: int):
    """The reference's 2-column catering shape at scale: ``guest`` in
    1..40, ``price`` about 5.2/guest with noise, two decimals — so both
    rules reject some rows. Returns the float64 columns as written."""
    rng = np.random.default_rng(seed)
    guest = rng.integers(1, 41, rows)
    price = np.round(np.maximum(
        5.2 * guest + 12.0 + rng.normal(0.0, 8.0, rows), 1.0), 2)
    with open(path, "w") as f:
        for lo in range(0, rows, 500_000):
            g = guest[lo:lo + 500_000].tolist()
            p = price[lo:lo + 500_000].tolist()
            f.write("".join(f"{a},{b:.2f}\n" for a, b in zip(g, p)))
    return guest.astype(np.float64), price


# ---------------------------------------------------------------------------
# Stages
# ---------------------------------------------------------------------------

def stage_a_reference_app(ctx: Smoke, spark) -> None:
    from sparkdq4ml_tpu.models import Vectors

    ctx.stage = "A:reference-app"
    df = load_csv(spark, os.path.join(REPO, "data", "dataset-full.csv"))
    df = assemble(dq_phase(spark, df))
    model = lasso().fit(df)
    scored = model.transform(df)
    count = df.count()
    rmse = float(model.summary.rootMeanSquaredError)
    predict40 = float(model.predict(Vectors.dense(40.0)))
    ctx.check(count == GOLDEN_FULL["count"] == scored.count(),
              f"count {count}, want {GOLDEN_FULL['count']}")
    ctx.approx(rmse, GOLDEN_FULL["rmse"], 0.01, "RMSE")
    ctx.approx(predict40, GOLDEN_FULL["predict40"], 0.01,
              "prediction for 40 guests")
    ctx.report(count=count, rmse=round(rmse, 6),
               predict40=round(predict40, 4),
               coef=float(model.coefficients[0]),
               intercept=float(model.intercept))


def stage_b_chip_width(ctx: Smoke, spark, workdir: str, rows: int,
                       logit_rows: int, logit_cols: int, seed: int) -> dict:
    from sparkdq4ml_tpu.models import LogisticRegression, VectorAssembler
    from sparkdq4ml_tpu.utils.profiling import counters

    ctx.stage = "B:chip-width"
    csv_path = os.path.join(workdir, "catering.csv")
    guest, price = write_catering_csv(csv_path, rows, seed)
    keep, rule_ref = rules_reference(guest, price)
    g_ref, p_ref, kept = guest[keep], price[keep], int(keep.sum())
    ctx.report(csv_mb=round(os.path.getsize(csv_path) / 1e6, 1),
               rows=rows, keep=kept)

    chunks0 = counters.get("ingest.chunks")
    streamed0 = counters.get("ingest.streamed")
    rules0 = {r["rule"]: r for r in spark.dq_report()["rules"]}
    loaded = load_csv(spark, csv_path)
    ctx.check(counters.get("ingest.streamed") == streamed0 + 1,
              "the read did not go through the streaming native engine")
    chunks = counters.get("ingest.chunks") - chunks0
    ctx.check(chunks >= 4, f"only {chunks} streaming chunk(s)")
    ctx.check(loaded.count() == rows, f"loaded {loaded.count()} rows")

    def flushes():
        df = dq_phase(spark, loaded)
        df.create_or_replace_temp_view("clean")
        grouped = spark.sql(
            "SELECT guest, count(*) AS n, avg(price) AS avg_price, "
            "max(price) AS max_price FROM clean GROUP BY guest")
        ordered = spark.sql(
            "SELECT guest, price FROM clean ORDER BY price DESC, guest")
        return df, grouped.to_pydict(), ordered

    df, grouped, ordered = flushes()
    ctx.check(df.count() == kept,
              f"{df.count()} rows after DQ, reference {kept}")

    # the dq profile saw both rules of THIS flush, with exact tallies
    for r in spark.dq_report()["rules"]:
        if r["rule"] in rule_ref:
            before = rules0.get(r["rule"], {"rows": 0, "violations": 0})
            got = (r["rows"] - before["rows"],
                   r["violations"] - before["violations"])
            ctx.check(got in rule_ref[r["rule"]],
                      f"dq_report {r['rule']} (rows, violations) {got}, "
                      f"reference {sorted(rule_ref[r['rule']])}")
            del rule_ref[r["rule"]]
    ctx.check(not rule_ref, f"dq_report lacks rule rows: {sorted(rule_ref)}")

    order, keys = check_group_counts(ctx, grouped, g_ref, "GROUP BY")
    avg_ref = [p_ref[g_ref == k].mean() for k in keys]
    max_ref = [p_ref[g_ref == k].max() for k in keys]
    avg_err = ctx.approx(np.asarray(grouped["avg_price"])[order], avg_ref,
                        1e-4, "GROUP BY avg(price)")
    ctx.approx(np.asarray(grouped["max_price"])[order], max_ref, 1e-6,
              "GROUP BY max(price)")

    ctx.check(ordered.count() == kept, "ORDER BY lost rows")
    top = ordered.to_pydict(limit=2000)
    ref_order = np.lexsort((g_ref, -p_ref))[:2000]
    ctx.approx(top["price"], p_ref[ref_order], 1e-6, "ORDER BY price DESC")
    ctx.check(np.array_equal(np.asarray(top["guest"]), g_ref[ref_order]),
              "ORDER BY tie-break on guest differs from the reference")

    compiles = {k: counters.get(k)
                for k in ("pipeline.compile", "grouped.compile")}
    df2, grouped2, ordered2 = flushes()
    again = {k: counters.get(k) - v for k, v in compiles.items()}
    ctx.check(not any(again.values()),
              f"the second pass of the same flushes compiled again: {again}")
    ctx.check(df2.count() == df.count()
              and ordered2.count() == ordered.count()
              and np.array_equal(grouped2["n"], grouped["n"]),
              "the second pass gave different results")

    features = assemble(df)
    model = lasso().fit(features)
    coef_ref, icpt_ref = lasso_reference(g_ref, p_ref)
    lasso_err = ctx.approx([model.coefficients[0], model.intercept],
                          [coef_ref, icpt_ref], 1e-3, "Lasso (coef, icpt)")

    rng = np.random.default_rng(seed + 1)
    X = rng.normal(size=(logit_rows, logit_cols)).astype(np.float32)
    w_true = rng.normal(size=logit_cols) / np.sqrt(logit_cols)
    y = (rng.uniform(size=logit_rows)
         < 1.0 / (1.0 + np.exp(-(X @ w_true + 0.3)))).astype(np.float32)
    names = [f"x{j}" for j in range(logit_cols)]
    wide = spark.create_data_frame(
        {**{n: X[:, j] for j, n in enumerate(names)}, "label": y})
    wide = VectorAssembler(names, "features").transform(wide)
    logit = LogisticRegression(max_iter=100).fit(wide)
    w_ref, b_ref = logistic_reference(X, y)
    logit_err = ctx.approx(
        np.append(np.asarray(logit.coefficients).ravel(), logit.intercept),
        np.append(w_ref, b_ref), 1e-3, "logistic (coef, icpt)")

    ctx.report(chunks=chunks, rows_after_dq=kept,
               lasso_coef=float(model.coefficients[0]),
               lasso_intercept=float(model.intercept),
               lasso_rel_err=float(f"{lasso_err:.3g}"),
               logistic_rel_err=float(f"{logit_err:.3g}"),
               groupby_avg_rel_err=float(f"{avg_err:.3g}"),
               second_pass_compiles=again)
    return {"features": features, "model": model, "csv_path": csv_path,
            "kept": kept, "g_ref": g_ref}


def stage_c_serve(ctx: Smoke, spark) -> None:
    import socket

    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.models import LinearRegression, VectorAssembler
    from sparkdq4ml_tpu.serve import ResilientClient

    ctx.stage = "C:serve"
    data_path = os.path.join(REPO, "data", "dataset-abstract.csv")

    def job(q):     # the reference app's flow as one served job
        df = (q.read.format("csv").option("inferSchema", "true")
              .option("header", "false").load(data_path))
        df = df.with_column_renamed("_c0", "guest") \
               .with_column_renamed("_c1", "price")
        df = df.with_column("price_no_min",
                            dq.call_udf("minimumPriceRule", dq.col("price")))
        q.register_view("price", df)
        df = q.sql("SELECT cast(guest as int) guest, price_no_min AS "
                   "price FROM price WHERE price_no_min > 0")
        df = df.with_column(
            "price_correct_correl",
            dq.call_udf("priceCorrelationRule", dq.col("price"),
                        dq.col("guest")))
        q.register_view("price", df)
        df = q.sql("SELECT guest, price_correct_correl AS price "
                   "FROM price WHERE price_correct_correl > 0")
        df = df.with_column("label", df.col("price"))
        df = VectorAssembler(["guest"], "features").transform(df)
        model = LinearRegression(max_iter=40, reg_param=1.0,
                                 elastic_net_param=1.0).fit(df)
        return {"count": df.count(),
                "rmse": float(model.summary.root_mean_squared_error)}

    server = spark.serve(workers=2)
    ctx.check(server.net is not None, "the socket front end did not start")
    port = server.net.port
    server.net.register_job("headline", job)
    answered = 0
    for transport in ("frame", "http"):
        with ResilientClient("127.0.0.1", port, transport=transport,
                             tenant=f"smoke-{transport}") as client:
            for _ in range(4):
                res = client.call_job("headline", deadline_s=600.0)
                ctx.check(res.ok, f"{transport} request: {res.status} "
                          f"{res.reason} {res.error} {res.detail}")
                ctx.check(res.value["count"] == GOLDEN_ABSTRACT["count"],
                          f"served count {res.value['count']}")
                ctx.approx(res.value["rmse"], GOLDEN_ABSTRACT["rmse"], 0.01,
                          f"served RMSE ({transport})")
                answered += 1
            health = client.healthz()
            ctx.check(health["http_code"] == 200, f"/healthz {health}")
    server.stop(drain=True)
    ctx.check(not server.running, "the server still runs after stop()")
    with socket.socket() as probe:
        probe.settimeout(2.0)
        ctx.check(probe.connect_ex(("127.0.0.1", port)) != 0,
                  "the listener still accepts after stop()")
    ctx.report(answered=answered, framings=["frame", "http"],
               healthz=200, drained=True)


def stage_e_nothing_degraded(ctx: Smoke, spark, b: dict, marks: dict,
                             matmul_n: int) -> None:
    import jax
    import jax.numpy as jnp

    from sparkdq4ml_tpu import session as session_mod
    from sparkdq4ml_tpu.frame import native_csv
    from sparkdq4ml_tpu.models.regression import _extract_xy
    from sparkdq4ml_tpu.parallel.distributed import (
        fused_linear_fit_packed, pack_design, place_packed,
        unpack_fit_result)
    from sparkdq4ml_tpu.utils.profiling import counters

    ctx.stage = "E:nothing-degraded"
    # placement: frame columns, the packed design, the fitted coefficients
    # (the same three steps LinearRegression.fit takes, held on device)
    features = b["features"]
    for name in ("guest", "price", "features"):
        ctx.on_expected_platform(jnp.asarray(features._column_values(name)),
                                 f"frame column {name!r}")
    ctx.on_expected_platform(features.mask, "frame mask")
    mesh = None if spark.mesh.devices.size <= 1 else spark.mesh
    X, y, mask = _extract_xy(features, "features", "label")
    Zd = place_packed(pack_design(X, y, mask), mesh)
    ctx.on_expected_platform(Zd, "packed design")
    ctx.check(len(Zd.devices()) == ctx.device["count"],
              f"packed design sits on {len(Zd.devices())} device(s)")
    fit = fused_linear_fit_packed(mesh, "fista", 40, 1e-6, True, True)
    flat = jax.block_until_ready(fit(Zd, jnp.asarray([1.0, 1.0], Zd.dtype)))
    ctx.on_expected_platform(flat, "fitted coefficients")
    held = unpack_fit_result(flat, 1)
    ctx.approx([held.coefficients[0], held.intercept],
              [b["model"].coefficients[0], b["model"].intercept], 1e-6,
              "device-held fit vs LinearRegression.fit")

    # no recovery event, no fallback counter
    events = len(spark.recovery_log) - marks["recovery_events"]
    ctx.check(events == 0, f"RECOVERY_LOG gained {events} event(s): "
              f"{[str(e) for e in spark.recovery_log.events()[-3:]]}")
    moved = {k: counters.get(k) - marks["counters"][k]
             for k in DEGRADED_COUNTERS}
    ctx.check(not any(moved.values()), f"degraded paths ran: {moved}")
    simd = native_csv.simd_level()
    ctx.check(simd != "unavailable", "native CSV engine unavailable")
    rules_rows = {r["rule"]: r["rows"] for r in spark.dq_report()["rules"]}
    ctx.check(all(rules_rows.get(r, 0) > 0 for r in
                  ("minimumPriceRule", "priceCorrelationRule")),
              f"dq_report rule rows: {rules_rows}")
    ctx.report(recovery_events=0, degraded_counters=0, simd=simd,
               ingest_streamed=counters.get("ingest.streamed")
               - marks["ingest_streamed"], dq_rule_rows=rules_rows)

    # block_until_ready blocks: one large bf16 matmul cannot finish faster
    # than its FLOPs over the chip's peak (benchmarks/peaks.json)
    a = jnp.ones((matmul_n, matmul_n), jnp.bfloat16)
    matmul = jax.jit(lambda u, v: u @ v)
    jax.block_until_ready(matmul(a, a))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(matmul(a, a))
        times.append(time.perf_counter() - t0)
    flops = 2.0 * matmul_n ** 3
    facts = {"matmul_n": matmul_n, "min_ms": round(min(times) * 1e3, 3)}
    if ctx.expect == "tpu":
        floor_s = flops / peak_bf16_flops_per_s(ctx.device["kind"])
        ctx.check(min(times) >= floor_s,
                  f"a {matmul_n}^3 bf16 matmul 'finished' in "
                  f"{min(times) * 1e3:.3f} ms, below the {floor_s * 1e3:.3f}"
                  " ms its FLOPs need at peak: block_until_ready does not "
                  "block")
        facts.update(peak_floor_ms=round(floor_s * 1e3, 3),
                     achieved_tflops=round(flops / min(times) / 1e12, 1),
                     block_until_ready_blocks=True)
    ctx.report(**facts)

    # the compile cache in effect is the one the rule names, and it fills
    want_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or session_mod.COMPILE_CACHE_DIR)
    ctx.check(jax.config.jax_compilation_cache_dir == want_dir,
              f"compile cache dir {jax.config.jax_compilation_cache_dir!r},"
              f" want {want_dir!r}")
    entries = len(os.listdir(want_dir))
    # (XLA:CPU persists only what passes JAX's stock one-second threshold,
    # which nothing here does: an empty directory is no finding there)
    ctx.check(entries >= marks["cache_entries"]
              and (entries > 0 or ctx.expect == "cpu"),
              f"compile cache {want_dir} holds {entries} entries "
              f"({marks['cache_entries']} at start)")
    ctx.report(compile_cache_dir=want_dir, entries=entries,
               gained=entries - marks["cache_entries"],
               cache_hits=marks["cache_events"]["hits"],
               cache_misses=marks["cache_events"]["misses"])


def stage_f_several_devices(ctx: Smoke, spark, b: dict,
                            shard_min_rows: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.models.mlp import _mlp_forward
    from sparkdq4ml_tpu.models.solvers import psum_value_and_grad
    from sparkdq4ml_tpu.parallel.mesh import DATA_AXIS, shard_map

    ctx.stage = "F:several-devices"
    n_dev = ctx.device["count"]
    ctx.check(spark.mesh.devices.size == n_dev,
              f"session mesh has {spark.mesh.devices.size} devices")
    ctx.check(len({d.id for d in spark.mesh.devices.flat}) == n_dev,
              "session mesh repeats a device")

    # one flush + GROUP BY + DISTINCT over a row-sharded frame
    (dq.TpuSession.builder().config("spark.shard.enabled", "true")
     .config("spark.shard.minRows", shard_min_rows).get_or_create())
    try:
        loaded = load_csv(spark, b["csv_path"])
        ctx.check(loaded._shard is not None, "the read did not land sharded")
        df = dq_phase(spark, loaded)
        ctx.check(df.count() == b["kept"],
                  f"sharded flush kept {df.count()} rows")
        df.create_or_replace_temp_view("clean_sharded")
        grouped = spark.sql("SELECT guest, count(*) AS n FROM clean_sharded "
                            "GROUP BY guest").to_pydict()
        distinct = spark.sql(
            "SELECT DISTINCT guest FROM clean_sharded").to_pydict()
    finally:
        (dq.TpuSession.builder().config("spark.shard.enabled", "false")
         .get_or_create())
    _, keys = check_group_counts(ctx, grouped, b["g_ref"],
                                 "sharded GROUP BY")
    ctx.check(np.array_equal(np.sort(np.asarray(distinct["guest"])), keys),
              "sharded DISTINCT differs from the reference")

    # the sharded MLP gradient is the single-device gradient: small
    # integers keep every partial sum exact in float32, so equality holds
    # whatever order the interconnect reduces in
    rows = 8 * n_dev
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.integers(-2, 3, (rows, 4)), jnp.float32)
    Y = jnp.asarray(rng.integers(-2, 3, (rows, 3)), jnp.float32)
    params = ((jnp.asarray(rng.integers(-1, 2, (4, 3)), jnp.float32),
               jnp.zeros((3,), jnp.float32)),)

    def grad_of(Xs, Ys, axis):
        return psum_value_and_grad(
            lambda p: jnp.sum(_mlp_forward(p, Xs) * Ys), axis)(params)

    single = grad_of(X, Y, None)
    sharded = jax.jit(shard_map(
        lambda Xs, Ys: grad_of(Xs, Ys, DATA_AXIS), mesh=spark.mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=P()))(X, Y)
    for got, want in zip(jax.tree_util.tree_leaves(sharded),
                         jax.tree_util.tree_leaves(single)):
        ctx.check(bool(jnp.array_equal(got, want)),
                  f"sharded gradient {np.asarray(got).tolist()} != "
                  f"single-device {np.asarray(want).tolist()}")
    ctx.report(mesh_devices=n_dev, sharded_rows_after_dq=b["kept"],
               groups=int(keys.size), sharded_gradient_equal=True)


# ---------------------------------------------------------------------------

def run(expect_platform: str, rows: int = 4_000_000,
        logit_rows: int = 1_000_000, logit_cols: int = 16,
        ingest_chunk_bytes: int = 8 << 20, shard_min_rows: int = 65536,
        matmul_n: int = 8192,
        seed: int = 20260926) -> dict:
    """Drive every stage once; raise :class:`SmokeFailure` on the first
    check that does not hold. Returns the device identity."""
    import jax

    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu import session as session_mod
    from sparkdq4ml_tpu.utils.profiling import counters

    ctx = Smoke(expect_platform)
    cache_events = {"hits": 0, "misses": 0}

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or session_mod.COMPILE_CACHE_DIR)
    marks = {
        "cache_entries": (len(os.listdir(cache_dir))
                          if os.path.isdir(cache_dir) else 0),
        "cache_events": cache_events,
        "counters": {k: counters.get(k) for k in DEGRADED_COUNTERS},
        "ingest_streamed": counters.get("ingest.streamed"),
    }
    master = "tpu[*]" if expect_platform == "tpu" else "local[*]"
    spark = (dq.TpuSession.builder().app_name("chip-smoke").master(master)
             .config("spark.serve.net.enabled", "true")
             .config("spark.serve.net.port", 0)
             .config("spark.ingest.chunkBytes", ingest_chunk_bytes)
             .get_or_create())
    marks["recovery_events"] = len(spark.recovery_log)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        spark.udf.register("minimumPriceRule", dq.minimum_price_rule,
                           "double")
        spark.udf.register("priceCorrelationRule",
                           dq.price_correlation_rule, "double")
        ctx.report(master=master, mesh_devices=int(spark.mesh.devices.size),
                   jax=jax.__version__)
        stage_a_reference_app(ctx, spark)
        b = stage_b_chip_width(ctx, spark, workdir, rows, logit_rows,
                               logit_cols, seed)
        stage_c_serve(ctx, spark)
        stage_e_nothing_degraded(ctx, spark, b, marks, matmul_n)
        if ctx.device["count"] > 1:
            stage_f_several_devices(ctx, spark, b, shard_min_rows)
    finally:
        spark.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    ctx.stage = "done"
    ctx.report(ok=True)
    return ctx.device


def main() -> int:
    import jax

    first = jax.devices()[0]
    if first.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {first.platform!r}, "
              f"kind {first.device_kind!r}, {len(jax.devices())} device(s));"
              " no result", file=sys.stderr)
        return 2
    try:
        device = run("tpu")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
