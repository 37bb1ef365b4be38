"""Chaos-complete engine suite (ISSUE 11).

Every post-PR-1 subsystem now carries seeded fault sites and a
degradation ladder through the PR-1 recovery engine: the fused pipeline
flush (retry → eager replay, NaN detection, OOM → row-chunked), the
grouped segment-reduce program (device → host lowering), the native
streaming ingest (io error / torn chunk / dead prefetch producer / pool
exhaustion → python engine or chunked body, pooled buffers always
returned), and the QueryServer (worker fault → deadline-aware requeue,
admission breaker trips + census-OOM rejections). Plus: cross-thread
fault determinism (the ``_det_uniform`` pure-function contract from 16
concurrent serve workers), the trip → shed → half-open → closed breaker
lifecycle, recovery telemetry (per-site ``recovery.*`` counters in the
Prometheus scrape, ``recovery_fault`` span annotation in EXPLAIN
ANALYZE), the no-fault-plan hot-path overhead pins, and the
``scripts/chaos_soak.py`` smoke (≥ 5 seeds over the concurrent serving
workload; the full ``--seeds 50`` arm is slow-marked).
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

import jax

from conftest import dataset_path
from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame import native_csv
from sparkdq4ml_tpu.frame.csv import read_csv
from sparkdq4ml_tpu.serve import QueryServer
from sparkdq4ml_tpu.utils import faults, profiling, recovery
from sparkdq4ml_tpu.utils.recovery import RECOVERY_LOG

pytestmark = pytest.mark.chaos

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SOAK = os.path.join(REPO, "scripts", "chaos_soak.py")


@pytest.fixture(autouse=True)
def _clean_chaos_state():
    """Chaos state is process-global: scrub the plan, the event log, the
    device breaker, and the chaos-relevant counters around every test."""
    faults.clear()
    RECOVERY_LOG.clear()
    recovery.DEVICE_BREAKER.reset()
    profiling.counters.clear("recovery.")
    profiling.counters.clear("faults.")
    yield
    faults.clear()
    RECOVERY_LOG.clear()
    recovery.DEVICE_BREAKER.reset()
    profiling.counters.clear("recovery.")
    profiling.counters.clear("faults.")


def _eq(a: dict, b: dict) -> None:
    assert list(a) == list(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def _chain(n=64):
    f = Frame({"x": np.arange(float(n)), "y": np.arange(float(n)) * 2})
    return f.with_column("z", f["x"] * 2 + 1).filter(f["x"] > 10)


REF = _chain().to_pydict()


# ---------------------------------------------------------------------------
# fault-plan mechanics of the new sites/kinds
# ---------------------------------------------------------------------------

class TestNewFaultKinds:
    def test_fault_sites_registry_covers_all_hooked_sites(self):
        assert set(faults.FAULT_SITES) >= {
            "pipeline_flush", "grouped_flush", "ingest_native",
            "serve_exec", "serve_admit", "oom",
            "gram_sharded", "fit_packed", "solver", "fit", "mesh"}
        for kinds in faults.FAULT_SITES.values():
            assert set(kinds) <= set(faults.KINDS)

    def test_inject_io_error_raises_oserror_not_filenotfound(self):
        with faults.inject_faults("ingest_native:io_error:1"):
            with pytest.raises(OSError) as ei:
                faults.inject("ingest_native")
            assert not isinstance(ei.value, FileNotFoundError)

    def test_fired_ticks_per_kind_independently(self):
        with faults.inject_faults("ingest_native:torn_chunk:1",
                                  "ingest_native:pool_exhaust:2") as plan:
            assert faults.fired("ingest_native", "torn_chunk")
            assert not faults.fired("ingest_native", "pool_exhaust")
            assert faults.fired("ingest_native", "pool_exhaust")
        assert set(plan.fired) == {
            ("ingest_native", "torn_chunk", 1),
            ("ingest_native", "pool_exhaust", 2)}

    def test_fired_is_noop_without_plan(self):
        assert faults.fired("serve_admit", "breaker_trip") is False

    def test_shrunk_budget_carries_spec_n(self):
        with faults.inject_faults("oom:oom:1:n=4096"):
            assert faults.shrunk_budget("oom") == 4096
            assert faults.shrunk_budget("oom") is None   # attempt 2
        assert faults.shrunk_budget("oom") is None       # no plan

    def test_injected_fault_counts(self):
        with faults.inject_faults("serve_admit:breaker_trip:1"):
            faults.fired("serve_admit", "breaker_trip")
        assert profiling.counters.get("faults.injected") >= 1
        assert profiling.counters.get("faults.injected.serve_admit") >= 1


# ---------------------------------------------------------------------------
# cross-thread determinism (the _det_uniform pure-function contract)
# ---------------------------------------------------------------------------

class TestCrossThreadDeterminism:
    def test_det_uniform_pure_across_16_threads(self):
        grid = [(s, site, a) for s in (0, 7) for site in ("a", "serve_exec")
                for a in range(1, 40)]
        ref = {g: faults._det_uniform(*g) for g in grid}
        errs: list = []

        def worker():
            for g, want in ref.items():
                if faults._det_uniform(*g) != want:
                    errs.append(g)

        threads = [threading.Thread(target=worker) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs

    def _serve_fire_run(self, seed, jobs):
        """Drive ``jobs`` trivial queries through 16 workers under a
        p-spec at serve_exec; returns (fired list, total attempts)."""
        spec = faults.parse_spec("serve_exec:device_error:p=0.3")
        plan = faults.install_plan(faults.FaultPlan([spec], seed=seed))
        # threshold high enough that shedding never perturbs the run
        srv = QueryServer(workers=16, max_queue=4 * jobs,
                          breaker_threshold=10 ** 6).start()
        try:
            futs = [srv.submit(lambda ctx: 1, tenant=f"t{i % 4}")
                    for i in range(jobs)]
            for f in futs:
                f.result(timeout=60)
        finally:
            srv.stop()
            faults.clear()
        return sorted(plan.fired), plan.attempts_at("serve_exec")

    def test_16_worker_fire_set_matches_pure_function(self):
        """The per-site fire set from 16 concurrent serve workers is
        exactly the pure function of (seed, site, attempt) — thread
        interleaving cannot perturb which attempts fire."""
        fired, attempts = self._serve_fire_run(seed=11, jobs=32)
        expect = sorted(
            ("serve_exec", "device_error", a)
            for a in range(1, attempts + 1)
            if faults._det_uniform(11, "serve_exec", a) < 0.3)
        assert fired == expect
        # and a second concurrent run agrees on the common attempt range
        fired2, attempts2 = self._serve_fire_run(seed=11, jobs=32)
        k = min(attempts, attempts2)
        assert [f for f in fired if f[2] <= k] == \
            [f for f in fired2 if f[2] <= k]


# ---------------------------------------------------------------------------
# pipeline_flush: retry -> eager ladder, NaN detection, select path
# ---------------------------------------------------------------------------

class TestPipelineFlushLadder:
    def test_device_error_retries_and_recovers(self):
        with faults.inject_faults("pipeline_flush:device_error:1",
                                  seed=3) as plan:
            _eq(_chain().to_pydict(), REF)
        assert plan.fired == [("pipeline_flush", "device_error", 1)]
        assert RECOVERY_LOG.count("retry", site="pipeline_flush") >= 1
        # recovered on the retry — the eager rung never ran
        assert profiling.counters.get("pipeline.fault_fallback") == 0 or \
            not RECOVERY_LOG.events(site="pipeline_flush",
                                    action="fallback")

    def test_persistent_device_error_degrades_to_eager(self):
        before = profiling.counters.get("pipeline.fault_fallback")
        with faults.inject_faults(
                "pipeline_flush:device_error:1,2,3,4,5,6,7,8", seed=3):
            _eq(_chain().to_pydict(), REF)
        assert profiling.counters.get("pipeline.fault_fallback") \
            == before + 1
        acts = {e.action for e in RECOVERY_LOG.events(
            site="pipeline_flush")}
        assert {"retry", "exhausted", "fallback"} <= acts
        ev = RECOVERY_LOG.events(site="pipeline_flush",
                                 action="fallback")[-1]
        assert ev.rung == "eager"

    def test_pending_steps_survive_failed_rungs(self):
        """A failed fused attempt must not half-apply: the frame's
        pending steps stay queued until a rung succeeds, so the eventual
        result is exactly the eager result."""
        f = Frame({"x": np.arange(64.0)})
        g = f.with_column("a", f["x"] + 1)
        g = g.with_column("b", g["a"] * 3)
        g = g.filter(g["x"] > 5)
        assert len(g._pending) == 3
        with faults.inject_faults("pipeline_flush:device_error:1,2,3,4,5",
                                  seed=9):
            out = g.to_pydict()
        assert g._pending == ()
        h = Frame({"x": np.arange(64.0)})
        h = h.with_column("a", h["x"] + 1)
        h = h.with_column("b", h["a"] * 3).filter(h["x"] > 5)
        _eq(out, h.to_pydict())

    def test_nan_corruption_detected_and_replayed(self):
        with faults.inject_faults("pipeline_flush:nan:1", seed=3) as plan:
            out = _chain().to_pydict()
        _eq(out, REF)
        assert plan.fired == [("pipeline_flush", "nan", 1)]
        assert any(e.cause == "non-finite result"
                   for e in RECOVERY_LOG.events(site="pipeline_flush"))

    def test_fused_select_device_error_falls_back_correct(self):
        f = Frame({"x": np.arange(64.0), "y": np.arange(64.0) * 3})
        ref = f.select((f["x"] * 2).alias("a"),
                       (f["y"] + 1).alias("b")).to_pydict()
        with faults.inject_faults("pipeline_flush:device_error:1", seed=5):
            g = Frame({"x": np.arange(64.0), "y": np.arange(64.0) * 3})
            out = g.select((g["x"] * 2).alias("a"),
                           (g["y"] + 1).alias("b")).to_pydict()
        _eq(out, ref)

    def test_no_fault_plan_hot_path_never_touches_recovery(self,
                                                           monkeypatch):
        """The no-fault-plan overhead contract: one ``is None`` check —
        the ladder, the corrupt hook, and the event log are never even
        called."""
        def boom(*a, **kw):
            raise AssertionError("recovery machinery on the clean path")

        monkeypatch.setattr(recovery, "resilient_call", boom)
        monkeypatch.setattr(faults, "corrupt", boom)
        monkeypatch.setattr(faults, "fired", boom)
        _eq(_chain().to_pydict(), REF)
        assert len(RECOVERY_LOG) == 0


# ---------------------------------------------------------------------------
# oom: est-peak-over-budget -> row-chunked execution
# ---------------------------------------------------------------------------

class TestOomChunkedExecution:
    def _big_chain(self, n=4096):
        f = Frame({"x": np.arange(float(n)), "y": np.arange(float(n)) * 2})
        return f.with_column("z", f["x"] * 2 + 1).filter(f["x"] > 10)

    def test_injected_oom_chunks_and_matches(self):
        ref = self._big_chain().to_pydict()
        before = profiling.counters.get("pipeline.oom_chunked")
        with faults.inject_faults("oom:oom:1:n=64", seed=3) as plan:
            out = self._big_chain().to_pydict()
        _eq(out, ref)
        assert plan.fired == [("oom", "oom", 1)]
        assert profiling.counters.get("pipeline.oom_chunked") == before + 1
        ev = RECOVERY_LOG.events(site="oom", action="fallback")
        assert ev and ev[-1].rung == "chunked"

    def test_oom_fault_is_one_shot(self):
        with faults.inject_faults("oom:oom:1:n=64", seed=3):
            before = profiling.counters.get("pipeline.oom_chunked")
            _eq(self._big_chain().to_pydict(),
                self._big_chain().to_pydict())   # 2 flushes, 1 fault
            assert profiling.counters.get("pipeline.oom_chunked") \
                == before + 1

    def test_conf_budget_triggers_chunked(self):
        ref = self._big_chain().to_pydict()
        before = profiling.counters.get("pipeline.oom_chunked")
        config.audit_device_budget = 2048
        try:
            out = self._big_chain().to_pydict()
        finally:
            config.audit_device_budget = 0
        _eq(out, ref)
        assert profiling.counters.get("pipeline.oom_chunked") > before

    def test_no_budget_no_chunking(self):
        before = profiling.counters.get("pipeline.oom_chunked")
        self._big_chain().to_pydict()
        assert profiling.counters.get("pipeline.oom_chunked") == before


# ---------------------------------------------------------------------------
# grouped_flush: device -> host lowering
# ---------------------------------------------------------------------------

class TestGroupedFlushLadder:
    def _agg(self):
        f = Frame({"k": np.array([1, 1, 2, 2, 3]),
                   "v": np.array([1.0, 2, 3, 4, 5])})
        return f.group_by("k").agg({"v": "sum"}).to_pydict()

    def test_device_error_degrades_to_host(self):
        ref = self._agg()
        before = profiling.counters.get("grouped.fault_fallback")
        with faults.inject_faults("grouped_flush:device_error:1",
                                  seed=3) as plan:
            out = self._agg()
        _eq(out, ref)
        assert plan.fired == [("grouped_flush", "device_error", 1)]
        assert profiling.counters.get("grouped.fault_fallback") \
            == before + 1
        ev = RECOVERY_LOG.events(site="grouped_flush", action="fallback")
        assert ev and ev[-1].rung == "host"

    def test_sort_degrades_too(self):
        f = Frame({"k": np.array([3.5, 1.25, 2.75, 0.5])})
        ref = f.sort("k").to_pydict()
        with faults.inject_faults("grouped_flush:device_error:1", seed=3):
            g = Frame({"k": np.array([3.5, 1.25, 2.75, 0.5])})
            out = g.sort("k").to_pydict()
        _eq(out, ref)


# ---------------------------------------------------------------------------
# ingest_native: io error / torn chunk / thread death / pool exhaustion
# ---------------------------------------------------------------------------

def _write_csv(tmp_path, name="big.csv", rows=4000):
    p = str(tmp_path / name)
    with open(p, "w") as f:
        for i in range(rows):
            f.write(f"{i},{i * 2},{i / 4}\n")
    return p


needs_stream = pytest.mark.skipif(
    not native_csv.available(),
    reason="native streaming library not built")


@pytest.fixture
def small_chunks():
    saved = config.ingest_chunk_bytes
    config.ingest_chunk_bytes = 4096
    yield
    config.ingest_chunk_bytes = saved


class TestIngestChaos:
    @needs_stream
    @pytest.mark.parametrize("kind,rung", [
        ("io_error", "python"), ("torn_chunk", "python"),
        ("thread_death", "python"), ("pool_exhaust", "chunked")])
    def test_fault_degrades_with_identical_data(self, tmp_path,
                                                small_chunks, kind, rung):
        path = _write_csv(tmp_path)
        ref = read_csv(path).to_pydict()
        before = profiling.counters.get("ingest.fault_fallback")
        with faults.inject_faults(f"ingest_native:{kind}:1",
                                  seed=1) as plan:
            out = read_csv(path).to_pydict()
        _eq(out, ref)
        assert plan.fired == [("ingest_native", kind, 1)]
        assert profiling.counters.get("ingest.fault_fallback") \
            == before + 1
        ev = RECOVERY_LOG.events(site="ingest_native", action="fallback")
        assert ev and ev[-1].rung == rung

    @needs_stream
    def test_missing_file_still_raises_filenotfound(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            read_csv(str(tmp_path / "nope.csv"))

    @needs_stream
    @pytest.mark.parametrize("kind,exc", [
        ("io_error", OSError),
        ("torn_chunk", native_csv.NativeIngestError),
        ("thread_death", native_csv.NativeIngestError)])
    def test_explicit_native_engine_never_degrades(self, tmp_path,
                                                   small_chunks, kind,
                                                   exc):
        path = _write_csv(tmp_path)
        with faults.inject_faults(f"ingest_native:{kind}:1", seed=1):
            with pytest.raises(exc):
                read_csv(path, engine="native")

    def test_producer_exception_propagates_not_hangs(self):
        """A dying prefetch producer surfaces as NativeIngestError at the
        consumer instead of leaving it blocked on the bounded queue."""
        calls = []

        def next_chunk():
            if calls:
                raise RuntimeError("producer boom")
            calls.append(1)
            return 5, "payload"

        saved = config.ingest_prefetch
        config.ingest_prefetch = 2
        try:
            it = native_csv._prefetch_iter(next_chunk)
            assert next(it) == (5, "payload")
            t0 = time.monotonic()
            with pytest.raises(native_csv.NativeIngestError) as ei:
                next(it)
            assert time.monotonic() - t0 < 30.0
            assert isinstance(ei.value.__cause__, RuntimeError)
        finally:
            config.ingest_prefetch = saved

    @needs_stream
    def test_pool_buffers_returned_on_parse_failure(self, tmp_path,
                                                    small_chunks,
                                                    monkeypatch):
        """The pooled bind-mode buffers return to the pool on a
        mid-stream parse failure (the old code leaked them on every
        non-success exit). No column is handed to the engine on this
        path, so the buffers pool on every backend."""
        path = str(tmp_path / "bad.csv")
        with open(path, "w") as f:
            for i in range(2500):
                f.write(f"{i},{i * 2}\n")
            f.write("oops,text\n")
            for i in range(2500):
                f.write(f"{i},{i * 2}\n")
        with native_csv._POOL_LOCK:
            saved_pool = list(native_csv._POOL)
            native_csv._POOL.clear()
        try:
            frame = read_csv(path)   # python engine takes over
            assert "_c0" in frame.columns
            with native_csv._POOL_LOCK:
                assert len(native_csv._POOL) == 1
        finally:
            with native_csv._POOL_LOCK:
                native_csv._POOL.clear()
                native_csv._POOL.extend(saved_pool)


# ---------------------------------------------------------------------------
# serve: worker requeue ladder + admission chaos + breaker lifecycle
# ---------------------------------------------------------------------------

class TestServeChaos:
    def _server(self, **kw):
        kw.setdefault("workers", 4)
        kw.setdefault("breaker_threshold", 3)
        kw.setdefault("breaker_cooldown", 0.4)
        return QueryServer(**kw).start()

    def test_worker_fault_requeues_then_succeeds(self):
        srv = self._server()
        try:
            before = profiling.counters.get("serve.requeue")
            with faults.inject_faults("serve_exec:device_error:1", seed=2):
                r = srv.submit(lambda ctx: 41 + 1,
                               tenant="t0").result(timeout=60)
            assert r.ok and r.value == 42
            assert profiling.counters.get("serve.requeue") == before + 1
            ev = RECOVERY_LOG.events(site="serve_exec", action="retry")
            assert ev and ev[-1].rung == "requeue"
        finally:
            srv.stop()

    def test_persistent_fault_exhausts_to_structured_error(self):
        srv = self._server()
        try:
            with faults.inject_faults(
                    "serve_exec:device_error:1,2,3,4,5,6,7,8", seed=2):
                r = srv.submit(lambda ctx: 1,
                               tenant="t1").result(timeout=60)
            assert r.status == "error"
            assert "InjectedDeviceError" in r.error
            assert RECOVERY_LOG.events(site="serve_exec",
                                       action="exhausted")
        finally:
            srv.stop()

    def test_requeue_is_deadline_aware(self):
        """A faulted job whose deadline already passed fails instead of
        requeuing — and its result() stays bounded either way."""
        srv = self._server()
        try:
            release = threading.Event()
            before = profiling.counters.get("serve.requeue")
            with faults.inject_faults(
                    "serve_exec:device_error:1,2,3,4,5,6,7,8", seed=2):
                fut = srv.submit(
                    lambda ctx: release.wait(5) or 1, tenant="t2",
                    deadline_s=0.2)
                r = fut.result(timeout=30)
            release.set()
            assert r.status in ("deadline_exceeded", "error")
            # never an unbounded requeue loop
            assert profiling.counters.get("serve.requeue") - before <= 3
        finally:
            srv.stop()

    def test_tenant_bug_fails_fast_no_requeue(self):
        srv = self._server()
        try:
            before = profiling.counters.get("serve.requeue")

            def bad(ctx):
                raise ValueError("tenant bug")

            r = srv.submit(bad, tenant="t3").result(timeout=60)
            assert r.status == "error" and "ValueError" in r.error
            assert profiling.counters.get("serve.requeue") == before
        finally:
            srv.stop()

    def test_breaker_trip_shed_halfopen_closed_lifecycle(self):
        srv = self._server()
        try:
            key = srv.admission.breaker_key("t4")
            with faults.inject_faults("serve_admit:breaker_trip:1",
                                      seed=2):
                r = srv.submit(lambda ctx: 1,
                               tenant="t4").result(timeout=60)
                assert r.status == "shed" and r.reason == "breaker_open"
                assert srv.breaker.snapshot()[key]["open"]
                r2 = srv.submit(lambda ctx: 1,
                                tenant="t4").result(timeout=60)
                assert r2.status == "shed"
                time.sleep(0.5)
                assert srv.breaker.allow(key)    # half-open
                r3 = srv.submit(lambda ctx: 7,
                                tenant="t4").result(timeout=60)
                assert r3.ok and r3.value == 7
                assert key not in srv.breaker.snapshot()   # closed
        finally:
            srv.stop()

    def test_admission_oom_fault_rejects_memory(self):
        srv = self._server()
        try:
            before = profiling.counters.get("serve.reject.memory")
            with faults.inject_faults("serve_admit:oom:1", seed=2):
                r = srv.submit(lambda ctx: 1,
                               tenant="t5").result(timeout=60)
            assert r.status == "rejected" and r.reason == "memory"
            assert profiling.counters.get("serve.reject.memory") \
                == before + 1
        finally:
            srv.stop()

    def test_no_plan_submit_never_consults_fired(self, monkeypatch):
        def boom(*a, **kw):
            raise AssertionError("fired() on the clean submit path")

        monkeypatch.setattr(faults, "fired", boom)
        srv = self._server()
        try:
            r = srv.submit(lambda ctx: 1, tenant="t6").result(timeout=60)
            assert r.ok
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# recovery telemetry: per-site counters, Prometheus HELP, span annotation
# ---------------------------------------------------------------------------

class TestRecoveryTelemetry:
    def test_per_site_counters_mirror_events(self):
        RECOVERY_LOG.record("pipeline_flush", "retry", attempt=1)
        RECOVERY_LOG.record("pipeline_flush", "retry", attempt=2)
        RECOVERY_LOG.record("grouped_flush", "fallback", rung="host")
        snap = profiling.counters.snapshot("recovery.")
        assert snap["recovery.retry"] == 2
        assert snap["recovery.retry.pipeline_flush"] == 2
        assert snap["recovery.fallback.grouped_flush"] == 1

    def test_prometheus_scrape_carries_per_site_series_with_help(self):
        from sparkdq4ml_tpu.utils import observability as obs

        RECOVERY_LOG.record("serve_exec", "retry", attempt=1)
        text = obs.prometheus_text()
        assert "sparkdq4ml_recovery_retry_serve_exec" in text
        assert ("# HELP sparkdq4ml_recovery_retry_serve_exec "
                "recovery.retry.serve_exec") in text

    def test_explain_analyze_shows_absorbing_operator(self, session):
        from sparkdq4ml_tpu.utils import observability as obs

        f = Frame({"a": np.arange(64.0)})
        f.create_or_replace_temp_view("t_chaos_xp")
        try:
            with faults.inject_faults("pipeline_flush:device_error:1",
                                      seed=4):
                out = session.sql("EXPLAIN ANALYZE SELECT a, a*2 AS d "
                                  "FROM t_chaos_xp WHERE a > 3")
            text = str(out.to_pydict()["plan"][0])
        finally:
            # the ANALYZE pass records spans into the process-global
            # buffer; leaving them behind breaks buffer-positional
            # assertions in suites that run right after this one
            obs.TRACER.clear()
        line = next(ln for ln in text.splitlines()
                    if "recovery_fault" in ln)
        assert "pipeline_flush:device_error" in line
        assert "FusedStage" in line or "Filter" in line


# ---------------------------------------------------------------------------
# conf vocabulary: spark.chaos.* session-scoped
# ---------------------------------------------------------------------------

class TestChaosConf:
    def test_chaos_conf_session_scoped(self):
        import sparkdq4ml_tpu as dq

        assert config.chaos_seeds == 5 and config.chaos_soak_s == 0.0
        s = dq.TpuSession.builder().app_name("chaos-conf").master(
            "local[*]").config("spark.chaos.seed", "9").config(
            "spark.chaos.seeds", "11").config(
            "spark.chaos.soakSeconds", "2.5").get_or_create()
        try:
            assert config.chaos_seed == 9
            assert config.chaos_seeds == 11
            assert config.chaos_soak_s == 2.5
        finally:
            s.stop()
        assert config.chaos_seed == 0
        assert config.chaos_seeds == 5
        assert config.chaos_soak_s == 0.0


# ---------------------------------------------------------------------------
# the soak harness: tier-1 smoke + slow full arm
# ---------------------------------------------------------------------------

def _load_soak():
    import importlib.util

    spec = importlib.util.spec_from_file_location("chaos_soak", SOAK)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestChaosSoak:
    def test_schedule_is_pure_function_of_seed(self):
        soak = _load_soak()
        assert soak.build_schedule(5) == soak.build_schedule(5)
        assert soak.build_schedule(5) != soak.build_schedule(6)
        for s in range(10):
            faults.parse_plan(soak.build_schedule(s), seed=s)   # parses

    def test_soak_smoke_five_seeds(self):
        """The tier-1 smoke of the headline gate: ≥ 5 seeded random
        fault schedules over the concurrent serving workload — zero
        hangs, golden results on every success, coherent counters,
        breaker recovery on the tripped seeds."""
        soak = _load_soak()
        summary = soak.run_soak(seeds=5, clients=3, queries=1, workers=4)
        assert summary["ok"], summary["per_seed"]
        assert summary["seeds"] == 5
        assert summary["completed"] > 0
        assert summary["faults_fired"] > 0
        # seeds 0 and 3 schedule a breaker trip; recovery must be seen
        assert summary["breakers_tripped"] >= 1
        assert summary["breakers_recovered"] == summary["breakers_probed"]

    @pytest.mark.slow
    def test_soak_full_fifty_seeds_32_clients(self):
        """The full acceptance arm: ``--seeds 50`` over the 32-client
        serving workload (slow; also runnable as
        ``python scripts/chaos_soak.py --seeds 50``)."""
        soak = _load_soak()
        summary = soak.run_soak(seeds=50, clients=32, queries=1,
                                workers=8)
        assert summary["ok"], summary["failed_seeds"]
        assert summary["faults_fired"] > 0
        assert summary["breakers_recovered"] == summary["breakers_probed"]
