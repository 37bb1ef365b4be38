"""Concurrent query-serving layer (serve/) — multi-tenant sessions,
shared plan cache, admission control, deadlines, SLO metrics, and the
engine-wide thread-safety audit (ISSUE 6).

Covers: tenant catalog isolation, golden results under 32-way
concurrency (count=24 / RMSE 2.80994), the cross-tenant plan-cache reuse
pin (second tenant's identical query = 0 new compiles), the isolated-
cache control mode, every admission gate (global queue, per-tenant
quota, memory, breaker shedding), structured deadline errors that never
hang, per-tenant metric isolation + the Prometheus scrape, concurrent
``query_stats`` collectors at server scale, the 16-thread jit-cache
hammer, and the thread-safe session singleton.
"""

import threading
import time

import numpy as np
import pytest

import sparkdq4ml_tpu as dq
from conftest import dataset_path
from sparkdq4ml_tpu.frame import aggregates as A
from sparkdq4ml_tpu.frame.frame import Frame
from sparkdq4ml_tpu.ops import compiler, segments
from sparkdq4ml_tpu.ops import expressions as E
from sparkdq4ml_tpu.serve import (QueryDeadlineExceeded, QueryRefused,
                                  QueryServer, TenantQuota)
from sparkdq4ml_tpu.utils import observability as obs
from sparkdq4ml_tpu.utils.profiling import counters

pytestmark = pytest.mark.serve

GOLDEN_COUNT = 24
GOLDEN_RMSE = 2.809940


def headline_job(path):
    """The reference app's DQ+Lasso flow (the headline query) as a
    tenant-scoped server job: same call sequence as
    ``conftest.run_dq_pipeline`` + fit, but temp views live in the
    tenant's own catalog."""
    from sparkdq4ml_tpu.models import LinearRegression, VectorAssembler

    def job(ctx):
        dq.register_builtin_rules()
        df = (ctx.read.format("csv").option("inferSchema", "true")
              .option("header", "false").load(path))
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
    return job


def _plan_compiles(report):
    return sum(int(report.get(k, {}).get("misses", 0))
               for k in ("pipeline", "grouped"))


def _plan_hits(report):
    return sum(int(report.get(k, {}).get("hits", 0))
               for k in ("pipeline", "grouped"))


# ---------------------------------------------------------------------------
# Basics: submission surface, tenant isolation, lifecycle
# ---------------------------------------------------------------------------

class TestBasics:
    def test_sql_string_and_callable_jobs(self, session):
        with QueryServer(session, workers=2) as srv:
            f = Frame({"x": np.arange(10.0)})
            srv.context("a").register_view("t", f)
            res = srv.submit("SELECT x FROM t WHERE x > 6",
                             tenant="a").result()
            assert res.ok and res.status == "ok"
            assert res.value.count() == 3
            assert res.queue_ms is not None and res.e2e_ms is not None

            res2 = srv.submit(lambda ctx: 41 + 1, tenant="a").result()
            assert res2.value == 42
            assert res2.value_or_raise() == 42

    def test_tenant_view_isolation(self, session):
        """Two tenants both own a view named ``t`` — no collision (the
        multi-tenant property the process-default catalog cannot give)."""
        with QueryServer(session, workers=2) as srv:
            srv.context("a").register_view("t", Frame({"x": np.arange(3.0)}))
            srv.context("b").register_view("t", Frame({"x": np.arange(7.0)}))
            ra = srv.submit("SELECT count(*) c FROM t", tenant="a").result()
            rb = srv.submit("SELECT count(*) c FROM t", tenant="b").result()
            assert int(np.asarray(ra.value.to_pydict()["c"])[0]) == 3
            assert int(np.asarray(rb.value.to_pydict()["c"])[0]) == 7

    def test_execution_error_is_structured(self, session):
        with QueryServer(session, workers=1) as srv:
            def boom(ctx):
                raise ValueError("tenant bug")
            res = srv.submit(boom, tenant="a").result()
            assert res.status == "error"
            assert "ValueError" in res.error and "tenant bug" in res.error
            with pytest.raises(Exception, match="tenant bug"):
                res.value_or_raise()

    def test_submit_requires_running_server(self, session):
        srv = QueryServer(session, workers=1)
        with pytest.raises(RuntimeError, match="not running"):
            srv.submit(lambda ctx: 1)
        srv.start()
        try:
            assert srv.submit(lambda ctx: 1).result().ok
        finally:
            srv.stop()
        with pytest.raises(RuntimeError, match="not running"):
            srv.submit(lambda ctx: 1)

    def test_stop_drain_false_rejects_queued(self, session):
        srv = QueryServer(session, workers=1).start()
        started, release = threading.Event(), threading.Event()

        def blocker(ctx):
            started.set()
            release.wait(5)
            return "done"

        f0 = srv.submit(blocker, tenant="a")
        assert started.wait(5)
        f1 = srv.submit(lambda ctx: 1, tenant="a")   # queued behind blocker
        rej0 = counters.get("serve.reject.shutdown")
        t = threading.Thread(target=srv.stop, kwargs={"drain": False})
        t.start()
        r1 = f1.result(timeout=5)
        assert r1.status == "rejected" and r1.reason == "shutdown"
        release.set()
        t.join(5)
        assert f0.result(timeout=5).ok       # in-flight still finished
        # refusals are observable, never silent — shutdown included
        assert counters.get("serve.reject.shutdown") == rej0 + 1
        assert obs.METRICS.get_gauge("serve.workers") == 0

    def test_session_serve_accessor_and_stop(self, session):
        srv = session.serve(workers=2)
        assert srv.running
        assert session.serve() is srv        # same running server back
        assert srv.submit(lambda ctx: 7).result().value == 7
        session.stop()
        assert not srv.running

    def test_restart_after_timed_out_stop_keeps_pool_size(self, session):
        """A worker wedged in a device call past stop()'s join timeout
        rejoins the pool on restart: start() spawns only the difference
        (regression: a full new set ran the pool oversized with threads
        no later stop() ever joined, and the workers gauge lied)."""
        srv = QueryServer(session, workers=2).start()
        started, release = threading.Event(), threading.Event()
        try:
            def blocker(ctx):
                started.set()
                release.wait(10)
                return "done"

            fut = srv.submit(blocker, tenant="a")
            assert started.wait(5)
            srv.stop(timeout=0.5)                # straggler left behind
            assert obs.METRICS.get_gauge("serve.workers") == 1
            srv.start()                          # spawns exactly one more
            assert len(srv._threads) == 2
            assert obs.METRICS.get_gauge("serve.workers") == 2
            release.set()
            assert fut.result(timeout=5).value == "done"
            assert srv.submit(lambda ctx: 1, tenant="a").result(
                timeout=5).ok
        finally:
            release.set()
            srv.stop(timeout=5)


# ---------------------------------------------------------------------------
# Golden results under concurrency + shared plan cache
# ---------------------------------------------------------------------------

class TestConcurrentGolden:
    def test_32_tenants_all_get_golden_numbers(self, session):
        """The acceptance pin: 32 concurrent clients, one tenant each,
        all running the headline DQ+Lasso query — every result must be
        count=24 / RMSE 2.80994 (concurrency must never change
        results)."""
        job = headline_job(dataset_path("abstract"))
        with QueryServer(session, workers=8, max_queue=128) as srv:
            futs = [srv.submit(job, tenant=f"tenant-{i:02d}")
                    for i in range(32)]
            results = [f.result(timeout=300) for f in futs]
        assert all(r.ok for r in results), \
            [r.error for r in results if not r.ok]
        for r in results:
            assert r.value["count"] == GOLDEN_COUNT
            assert r.value["rmse"] == pytest.approx(GOLDEN_RMSE, abs=1e-4)

    def test_cross_tenant_plan_reuse_zero_new_compiles(self, session):
        """The shared-cache pin: tenant B's FIRST query replays tenant
        A's compiled programs — the cache_report diff shows zero new
        pipeline/grouped compiles and at least one fresh hit."""
        job = headline_job(dataset_path("abstract"))
        compiler.clear_cache()
        segments.clear_cache()
        with QueryServer(session, workers=2) as srv:
            assert srv.shared_plan_cache
            r_a = srv.submit(job, tenant="alpha").result()
            assert r_a.ok and r_a.value["count"] == GOLDEN_COUNT
            rep0 = srv.cache_report()
            r_b = srv.submit(job, tenant="beta").result()
            rep1 = srv.cache_report()
        assert r_b.ok and r_b.value["count"] == GOLDEN_COUNT
        assert _plan_compiles(rep1) - _plan_compiles(rep0) == 0
        assert _plan_hits(rep1) > _plan_hits(rep0)

    def test_isolated_cache_mode_compiles_per_tenant(self, session):
        """shared_plan_cache=False partitions the plan caches by tenant
        (the bench's control arm): tenant B's first query does NOT reuse
        tenant A's programs."""
        job = headline_job(dataset_path("abstract"))
        compiler.clear_cache()
        segments.clear_cache()
        try:
            with QueryServer(session, workers=2,
                             shared_plan_cache=False) as srv:
                r_a = srv.submit(job, tenant="alpha").result()
                rep0 = srv.cache_report()
                r_b = srv.submit(job, tenant="beta").result()
                rep1 = srv.cache_report()
            assert r_a.ok and r_b.ok
            assert _plan_compiles(rep1) - _plan_compiles(rep0) > 0
            # same tenant again: its namespaced plans replay
            with QueryServer(session, workers=2,
                             shared_plan_cache=False) as srv:
                rep2 = srv.cache_report()
                r_a2 = srv.submit(job, tenant="alpha").result()
                rep3 = srv.cache_report()
            assert r_a2.ok
            assert _plan_compiles(rep3) - _plan_compiles(rep2) == 0
        finally:
            compiler.clear_cache()   # drop the tenant-salted entries
            segments.clear_cache()

    def test_lazy_frame_value_materializes_in_tenant_namespace(self,
                                                               session):
        """A callable job returning a LAZY Frame (pending fused-pipeline
        steps) must flush inside the serve scope: left lazy, the
        client's first read would flush on the client thread — outside
        the tenant's plan namespace, silently un-partitioning the
        isolated-cache mode (regression: confirmed escape)."""
        compiler.clear_cache()
        try:
            def lazy_job(ctx):
                f = Frame({"v": np.arange(48.0)})
                return f.with_column("c", E.col("v") * 3.0) \
                        .filter(E.col("c") > 6.0)        # NOT materialized

            with QueryServer(session, workers=1,
                             shared_plan_cache=False) as srv:
                res = srv.submit(lazy_job, tenant="nsq").result()
            assert res.ok
            # the worker flushed it: nothing pending, and the plan landed
            # under the tenant namespace (a fresh read compiles nothing)
            assert not res.value._pending
            assert res.value.count() == 45
            report = compiler.cache_stats()
            assert report["size"] == 1
            assert "ns:'nsq'" in report["entries"][0]["key"]
        finally:
            compiler.clear_cache()

    def test_plan_namespace_scopes_keys(self):
        compiler.clear_cache()

        def chain():
            f = Frame({"v": np.arange(32.0)})
            f = f.with_column("c", E.col("v") * 2.0) \
                 .filter(E.col("c") > 3.0)
            return f.count()

        try:
            with compiler.plan_namespace("t1"):
                assert chain() == 30
            assert compiler.cache_len() == 1
            with compiler.plan_namespace("t2"):
                assert chain() == 30
            assert compiler.cache_len() == 2    # t2 compiled its own
            chain()                             # shared (empty) namespace
            assert compiler.cache_len() == 3
            with compiler.plan_namespace("t1"):
                assert chain() == 30
            assert compiler.cache_len() == 3    # t1 replayed
        finally:
            compiler.clear_cache()


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------

class TestAdmission:
    def _blocking_server(self, session, **kw):
        srv = QueryServer(session, **kw).start()
        started, release = threading.Event(), threading.Event()

        def blocker(ctx):
            started.set()
            release.wait(10)
            return "done"

        fut = srv.submit(blocker, tenant="a")
        assert started.wait(5)
        return srv, fut, release

    def test_queue_bounds_global_and_per_tenant(self, session):
        srv, fut, release = self._blocking_server(
            session, workers=1, max_queue=2,
            default_quota=TenantQuota(max_in_flight=1, max_queued=1))
        try:
            f1 = srv.submit(lambda ctx: 1, tenant="a")   # a queued: 1
            r2 = srv.submit(lambda ctx: 1, tenant="a").result()
            assert r2.status == "rejected"
            assert r2.reason == "tenant_queue_full"
            f3 = srv.submit(lambda ctx: 1, tenant="b")   # global queued: 2
            r4 = srv.submit(lambda ctx: 1, tenant="c").result()
            assert r4.status == "rejected" and r4.reason == "queue_full"
            with pytest.raises(QueryRefused, match="queue"):
                r4.value_or_raise()
            release.set()
            assert fut.result(timeout=10).ok
            assert f1.result(timeout=10).ok
            assert f3.result(timeout=10).ok
        finally:
            release.set()
            srv.stop()

    def test_refused_submissions_allocate_no_tenant_state(self, session):
        """Refused work must not grow per-tenant state: a flood of
        rejected submissions under unique tenant names leaves _tenants
        (and the scheduler's round-robin scan) untouched."""
        srv, fut, release = self._blocking_server(
            session, workers=1, max_queue=1)
        try:
            srv.submit(lambda ctx: 1, tenant="a")   # fills max_queue=1
            for i in range(20):
                r = srv.submit(lambda ctx: 1, tenant=f"ghost{i}").result()
                assert r.status == "rejected" and r.reason == "queue_full"
            tenants = srv.stats()["tenants"]
            assert not any(t.startswith("ghost") for t in tenants)
        finally:
            release.set()
            srv.stop()

    def test_admitted_flood_reaps_idle_stateless_tenants(self, session):
        """The admitted-flood sibling of the refused-flood pin: one
        trivial admitted query per unique tenant name must not grow the
        tenant table (and the round-robin scan) past the reap threshold.
        Tenants with durable state — registered views, custom quota, an
        exposed context — survive the sweep."""
        from sparkdq4ml_tpu.serve import server as srv_mod

        old = srv_mod.TENANT_REAP_THRESHOLD
        srv_mod.TENANT_REAP_THRESHOLD = 8
        try:
            with QueryServer(session, workers=2) as srv:
                srv.context("keeper").register_view(
                    "t", Frame({"x": np.arange(3.0)}))
                srv.set_quota("vip", TenantQuota(max_in_flight=1,
                                                 max_queued=2))
                for i in range(50):
                    assert srv.submit(lambda ctx: i,
                                      tenant=f"fly{i}").result().ok
                tenants = srv.stats()["tenants"]
                assert len(tenants) <= 8 + 1   # threshold + the newest
                assert "keeper" in tenants and "vip" in tenants
                # reaped names come back transparently
                assert srv.submit(lambda ctx: 1, tenant="fly0").result().ok
        finally:
            srv_mod.TENANT_REAP_THRESHOLD = old

    def test_reap_clears_breaker_state(self, session):
        """The breaker entry is tenant bookkeeping: reaping the tenant
        but leaving its ``CircuitBreaker._state`` key behind would grow
        one dict entry per failed-once tenant forever — the exact
        admitted-flood leak the sweep exists to bound."""
        from sparkdq4ml_tpu.serve import server as srv_mod

        old = srv_mod.TENANT_REAP_THRESHOLD
        srv_mod.TENANT_REAP_THRESHOLD = 8
        try:
            with QueryServer(session, workers=2) as srv:
                def boom(ctx):
                    raise ValueError("nope")

                for i in range(30):
                    r = srv.submit(boom, tenant=f"fail{i}").result()
                    assert r.status == "error"
                assert srv.submit(lambda ctx: 1, tenant="last").result().ok
                stale = [k for k in srv.breaker.snapshot()
                         if k.startswith("serve/fail")]
                # reaped tenants took their breaker entry with them (the
                # +2 slack: the newest tenant plus one whose worker is
                # still between _finish and the in_flight decrement)
                assert len(stale) <= srv_mod.TENANT_REAP_THRESHOLD + 2
        finally:
            srv_mod.TENANT_REAP_THRESHOLD = old

    def test_memory_gate_structured_rejection(self, session):
        with QueryServer(session, workers=1,
                         memory_limit_bytes=1) as srv:
            res = srv.submit(lambda ctx: 1, tenant="big",
                             est_bytes=1 << 30).result()
            assert res.status == "rejected" and res.reason == "memory"
            assert "B exceeds" in res.detail
            # no estimate declared -> the gate stays advisory and admits
            assert srv.submit(lambda ctx: 2, tenant="big").result().ok
        assert counters.get("serve.reject.memory") >= 1

    def test_would_fit_census(self):
        from sparkdq4ml_tpu.utils import meminfo

        fits, live = meminfo.would_fit(1, 1 << 62)
        assert fits and live >= 0
        fits, _ = meminfo.would_fit(1 << 62, 1)
        assert not fits
        assert meminfo.headroom(1) in (0, 1)

    def test_breaker_sheds_then_recovers(self, session):
        with QueryServer(session, workers=1, breaker_threshold=2,
                         breaker_cooldown=0.2) as srv:
            def boom(ctx):
                raise RuntimeError("down")
            for _ in range(2):
                assert srv.submit(boom, tenant="c").result().status == "error"
            shed = srv.submit(lambda ctx: 1, tenant="c").result()
            assert shed.status == "shed" and shed.reason == "breaker_open"
            # healthy tenants are unaffected by c's breaker
            assert srv.submit(lambda ctx: 1, tenant="d").result().ok
            snap = srv.breaker.snapshot()
            assert snap["serve/c"]["open"] is True
            time.sleep(0.25)                     # cooldown -> half-open
            ok = srv.submit(lambda ctx: 1, tenant="c").result()
            assert ok.ok
            assert srv.breaker.snapshot().get("serve/c") is None

    def test_stats_snapshot_shape(self, session):
        with QueryServer(session, workers=2) as srv:
            srv.submit(lambda ctx: 1, tenant="a").result()
            st = srv.stats()
        assert st["workers"] == 2 and st["shared_plan_cache"] is True
        assert st["tenants"]["a"]["max_in_flight"] == 4
        assert "serve.admit" in st["counters"]


# ---------------------------------------------------------------------------
# Deadlines: structured, prompt, never a hang
# ---------------------------------------------------------------------------

class TestDeadlines:
    def test_exec_overrun_returns_structured_error_promptly(self, session):
        # Determinism on the fault-throttled CI host (PR-7 flake note): no
        # wall-clock margin — the job blocks on an Event we control, so
        # "returned promptly, not hung" is proven by the result arriving
        # WHILE the job is still provably running (the event is unset),
        # not by a scheduler-sensitive elapsed-time bound.
        release = threading.Event()
        with QueryServer(session, workers=1) as srv:
            fut = srv.submit(lambda ctx: release.wait(30) or "late",
                             tenant="a", deadline_s=0.15)
            t0 = time.perf_counter()
            res = fut.result()
            waited = time.perf_counter() - t0
            assert not release.is_set()          # job still held: no hang
            # generous monotonic bound (0.15 s deadline, 30 s job hold):
            # catches a regression that waits for worker completion
            # without being schedulable-noise-sensitive
            assert waited < 10.0
            assert res.status == "deadline_exceeded"
            assert res.where in ("exec", "wait")
            assert res.value is None             # late value is discarded
            with pytest.raises(QueryDeadlineExceeded):
                res.value_or_raise()
            release.set()                        # let the worker drain
        assert counters.get("serve.deadline_exceeded") >= 1

    def test_queue_overrun_never_executes(self, session):
        with QueryServer(session, workers=1) as srv:
            started, release = threading.Event(), threading.Event()

            def blocker(ctx):
                started.set()
                release.wait(5)

            ran = []
            srv.submit(blocker, tenant="a")
            assert started.wait(5)
            late0 = counters.get("serve.late_result")
            fut = srv.submit(lambda ctx: ran.append(1), tenant="a",
                             deadline_s=0.1)
            res = fut.result()
            assert res.status == "deadline_exceeded"
            assert res.where in ("queue", "wait")
            release.set()
            time.sleep(0.1)
            assert ran == []                     # the work never ran
            # and NOT a "late result": nothing executed, so nothing was
            # discarded (regression: the worker's losing queue-deadline
            # resolution used to inflate serve.late_result)
            assert counters.get("serve.late_result") == late0

    def test_deadline_overruns_land_in_e2e_histogram(self, session):
        """e2e is the client-experienced latency: a deadline overrun
        resolved from the queue pop or the waiter lands in
        ``serve.e2e_ms`` exactly once (regression: those paths were
        silently skipped while exec-path overruns recorded, so a
        scrape-derived p99 read healthy under queue saturation — the
        regime deadlines exist for)."""
        obs.METRICS.clear()
        with QueryServer(session, workers=1) as srv:
            started, release = threading.Event(), threading.Event()

            def blocker(ctx):
                started.set()
                release.wait(5)

            srv.submit(blocker, tenant="a")
            assert started.wait(5)
            res = srv.submit(lambda ctx: 1, tenant="a",
                             deadline_s=0.1).result()
            assert res.status == "deadline_exceeded"
            # the overrun is IN (blocker still running: count is exactly 1)
            assert obs.METRICS.snapshot()["serve.e2e_ms"]["count"] == 1
            release.set()
        # stop() drained: blocker completed (+1), and the worker's
        # losing pop of the already-resolved job must NOT re-observe
        assert obs.METRICS.snapshot()["serve.e2e_ms"]["count"] == 2

    def test_default_deadline_from_conf(self, session):
        srv = QueryServer.from_conf(
            session, {"spark.serve.defaultDeadline": "0.05",
                      "spark.serve.workers": "1"})
        assert srv.default_deadline_s == pytest.approx(0.05)
        srv.start()
        try:
            res = srv.submit(lambda ctx: time.sleep(0.6), tenant="a").result()
            assert res.status == "deadline_exceeded"
        finally:
            srv.stop(timeout=2)

    def test_no_deadline_result_timeout_raises(self, session):
        with QueryServer(session, workers=1) as srv:
            started, release = threading.Event(), threading.Event()

            def blocker(ctx):
                started.set()
                release.wait(5)
                return "ok"

            fut = srv.submit(blocker, tenant="a")
            assert started.wait(5)
            with pytest.raises(TimeoutError):
                fut.result(timeout=0.1)
            release.set()
            assert fut.result(timeout=5).value == "ok"


# ---------------------------------------------------------------------------
# SLO observability: metrics, per-tenant isolation, Prometheus
# ---------------------------------------------------------------------------

class TestObservability:
    def test_per_tenant_latency_isolation(self, session):
        obs.METRICS.clear()
        with QueryServer(session, workers=2) as srv:
            for _ in range(3):
                srv.submit(lambda ctx: 1, tenant="iso_ta").result()
            srv.submit(lambda ctx: 1, tenant="iso_tb").result()
        snap = obs.METRICS.snapshot()
        assert snap["serve.e2e_ms.iso_ta"]["count"] == 3
        assert snap["serve.e2e_ms.iso_tb"]["count"] == 1
        assert snap["serve.e2e_ms"]["count"] >= 4
        assert snap["serve.queue_ms"]["count"] >= 4
        assert snap["serve.exec_ms"]["count"] >= 4

    def test_single_scrape_covers_engine_and_server(self, session):
        """session.metrics()/metrics_text() merge the server-scope
        series: one scrape covers engine + server, with HELP lines."""
        with QueryServer(session, workers=1) as srv:
            srv.submit(lambda ctx: Frame({"x": np.arange(4.0)}).count(),
                       tenant="a").result()
        m = session.metrics()
        assert m.get("serve.admit", 0) >= 1
        assert m.get("serve.complete", 0) >= 1
        assert isinstance(m.get("serve.e2e_ms"), dict)
        text = session.metrics_text()
        # HELP text comes from the METRIC_NAMES registry (ISSUE 12)
        assert "# HELP sparkdq4ml_serve_admit serve.admit - queries " \
            "admitted" in text
        assert "# TYPE sparkdq4ml_serve_e2e_ms histogram" in text
        assert "sparkdq4ml_serve_queue_depth" in text
        assert "sparkdq4ml_serve_in_flight" in text

    def test_collect_stats_attaches_query_collector(self, session):
        was_enabled = obs.TRACER.enabled
        with QueryServer(session, workers=1) as srv:
            def job(ctx):
                f = Frame({"x": np.arange(8.0)})
                f = f.with_column("y", E.col("x") + 1.0)
                return f.count()
            res = srv.submit(job, tenant="a", collect_stats=True).result()
        assert res.ok and res.value == 8
        assert res.stats is not None
        assert res.stats.spans                       # per-query span stream
        assert any("with_column" in s.name or "pipeline" in s.name
                   for s in res.stats.spans)
        assert obs.TRACER.enabled == was_enabled     # restored after

    def test_tenant_series_cardinality_cap(self, session):
        from sparkdq4ml_tpu.serve import server as server_mod

        obs.METRICS.clear()
        old = server_mod.MAX_TENANT_SERIES
        server_mod.MAX_TENANT_SERIES = 2
        try:
            with QueryServer(session, workers=1) as srv:
                for name in ("cap_a", "cap_b", "cap_c"):
                    srv.submit(lambda ctx: 1, tenant=name).result()
        finally:
            server_mod.MAX_TENANT_SERIES = old
        snap = obs.METRICS.snapshot()
        assert "serve.e2e_ms.cap_a" in snap
        assert "serve.e2e_ms.cap_b" in snap
        assert "serve.e2e_ms.cap_c" not in snap      # over the cap
        assert snap["serve.e2e_ms"]["count"] == 3    # aggregate keeps all


# ---------------------------------------------------------------------------
# Satellite: concurrent query_stats collectors at server scale
# ---------------------------------------------------------------------------

class TestConcurrentQueryStats:
    def test_eight_threads_staggered_enter_exit(self):
        """8 threads × staggered query_stats windows: each collector sees
        only its own thread's spans, and the LAST collector out restores
        the prior (disabled) tracing state — the PR-5 refcounted restore
        at serving scale."""
        assert not obs.TRACER.enabled
        errors, streams = [], {}

        def worker(i):
            try:
                time.sleep(0.01 * (i % 4))           # staggered enter
                with obs.query_stats(sample_memory=False) as qs:
                    f = Frame({"x": np.arange(16.0) + i})
                    f = f.with_column("y", E.col("x") * 2.0)
                    f.count()
                    time.sleep(0.01 * ((i + 2) % 4))  # staggered exit
                streams[i] = (threading.get_ident(), list(qs.spans))
            except Exception as e:                   # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errors
        assert len(streams) == 8
        for i, (tid, spans) in streams.items():
            assert spans, f"collector {i} saw no spans"
            assert all(s.tid == tid for s in spans)  # thread-scoped
        assert not obs.TRACER.enabled                # restore held

    def test_server_collect_stats_under_concurrency(self, session):
        with QueryServer(session, workers=4) as srv:
            def job(ctx):
                f = Frame({"x": np.arange(8.0)})
                return f.with_column("y", E.col("x") + 1.0).count()
            futs = [srv.submit(job, tenant=f"qs{i}", collect_stats=True)
                    for i in range(8)]
            results = [f.result(timeout=60) for f in futs]
        assert all(r.ok and r.value == 8 for r in results)
        assert all(r.stats is not None and r.stats.spans for r in results)
        assert not obs.TRACER.enabled


# ---------------------------------------------------------------------------
# Satellite: the 16-thread jit-cache hammer
# ---------------------------------------------------------------------------

class TestHammer:
    def test_sixteen_threads_mixed_queries_no_lost_updates(self, session):
        """16 threads × mixed pipeline/grouped/sort queries while a
        scraper thread iterates CACHES.report(), prometheus_text(), and
        metrics_snapshot(): no RuntimeError (dict changed during
        iteration), no lost per-plan stat updates — after the storm,
        sum(per-entry hits+compiles) over the pipeline cache equals the
        flush counter exactly."""
        compiler.clear_cache()
        segments.clear_cache()
        counters.clear("pipeline")
        counters.clear("grouped")
        errors: list = []
        stop_scrape = threading.Event()
        ITERS, THREADS = 6, 16

        def scraper():
            while not stop_scrape.is_set():
                try:
                    obs.cache_report()
                    obs.prometheus_text()
                    obs.metrics_snapshot()
                except Exception as e:               # noqa: BLE001
                    errors.append(f"scraper: {e!r}")
                    return

        def worker(i):
            try:
                rng = np.random.default_rng(i)
                for it in range(ITERS):
                    # pipeline chain: 4 plan shapes shared across threads
                    # (i % 4) -> heavy cross-thread hit/evict traffic.
                    # Bounded uniform data: every row must survive the
                    # filter so the count pins row preservation.
                    f = Frame({"v": rng.uniform(0.0, 1.0, 64)})
                    f = f.with_column(f"c{i % 4}",
                                      E.col("v") * float(it + 1) + 0.5)
                    f = f.filter(E.col(f"c{i % 4}") > -10.0)
                    assert f.count() == 64
                    # grouped aggregation (device segment-reduce path)
                    g = Frame({"k": (np.arange(64) % 4).astype(np.float64),
                               "v": rng.normal(size=64)})
                    out = g.group_by("k").agg(A.sum("v"))
                    assert out.count() == 4
                    # device distinct
                    d = Frame({"k": (np.arange(32) % 8).astype(np.float64)})
                    assert d.distinct().count() == 8
            except Exception as e:                   # noqa: BLE001
                errors.append(f"worker {i}: {e!r}")

        scr = threading.Thread(target=scraper)
        scr.start()
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        stop_scrape.set()
        scr.join(30)
        assert errors == []
        # no lost updates: every flush landed on exactly one cached
        # plan's hit/compile tally (no fallbacks, no evictions)
        assert counters.get("pipeline.fallback") == 0
        assert counters.get("pipeline.evict") == 0
        stats = compiler.cache_stats()
        entry_sum = sum(e["hits"] + e["compiles"] for e in stats["entries"])
        assert entry_sum == counters.get("pipeline.flush")
        assert counters.get("pipeline.flush") == THREADS * ITERS
        gstats = segments.cache_stats()
        g_entry_sum = sum(e["hits"] + e["builds"]
                          for e in gstats["entries"])
        assert g_entry_sum >= THREADS * ITERS * 2    # agg + distinct plans
        assert counters.get("grouped.fallback") == 0


# ---------------------------------------------------------------------------
# Satellite: thread-safe session singleton
# ---------------------------------------------------------------------------

class TestSessionThreadSafety:
    def test_get_or_create_race_yields_one_session(self):
        from sparkdq4ml_tpu import session as sess_mod

        assert sess_mod._ACTIVE is None
        out, errors = [], []
        barrier = threading.Barrier(16)

        def racer():
            try:
                barrier.wait(10)
                s = dq.TpuSession.builder().app_name("race") \
                    .master("local[*]").get_or_create()
                out.append(s)
            except Exception as e:                   # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=racer) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        try:
            assert errors == []
            assert len(out) == 16
            assert len({id(s) for s in out}) == 1    # ONE session object
            assert dq.TpuSession.active() is out[0]
        finally:
            if out:
                out[0].stop()

    def test_stop_vs_inflight_conf_restore(self):
        """A session that changed pipeline conf restores it exactly once
        even when stop() races a concurrent builder re-init — the
        _CONF_LOCK pin."""
        from sparkdq4ml_tpu.config import config

        default_pipeline = config.pipeline
        s = dq.TpuSession.builder().app_name("restore") \
            .config("spark.pipeline.enabled", "false").get_or_create()
        assert config.pipeline is False

        def reinit():
            dq.TpuSession.builder() \
                .config("spark.pipeline.enabled", "false").get_or_create()

        t = threading.Thread(target=reinit)
        t.start()
        s.stop()
        t.join(30)
        # whichever order the race resolved, a final stop of the active
        # session (if the re-init re-created state) must land back at
        # the process default
        active = dq.TpuSession.active()
        if active is not None:
            active.stop()
        assert config.pipeline == default_pipeline


# ---------------------------------------------------------------------------
# Disabled mode / no-op contract
# ---------------------------------------------------------------------------

class TestDisabledMode:
    def test_conf_disables_session_serve(self):
        from sparkdq4ml_tpu.config import config

        s = dq.TpuSession.builder().app_name("noserve") \
            .config("spark.serve.enabled", "false").get_or_create()
        try:
            assert config.serve_enabled is False
            with pytest.raises(RuntimeError, match="disabled"):
                s.serve()
        finally:
            s.stop()
        assert config.serve_enabled is True          # session-scoped restore

    def test_conf_accepts_no_spelling(self):
        """``spark.serve.enabled=no`` disables serving — the session conf
        parser accepts the same boolean spellings as the serve layer's
        own ``_CONF_BOOL_FALSE`` (regression: "no" was silently ignored
        and the server started anyway)."""
        from sparkdq4ml_tpu.config import config

        s = dq.TpuSession.builder().app_name("noserve2") \
            .config("spark.serve.enabled", "no").get_or_create()
        try:
            assert config.serve_enabled is False
            with pytest.raises(RuntimeError, match="disabled"):
                s.serve()
        finally:
            s.stop()
        assert config.serve_enabled is True

    def test_unstarted_layer_records_nothing(self, session):
        counters.clear("serve.")
        obs.METRICS.clear()
        f = Frame({"x": np.arange(16.0)})
        f = f.with_column("y", E.col("x") * 2.0)
        assert f.count() == 16
        session.sql("SELECT 1 AS one")
        assert counters.snapshot("serve.") == {}
        assert not any(k.startswith("serve.")
                       for k in obs.METRICS.snapshot())
