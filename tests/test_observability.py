"""Observability subsystem: spans, metrics, exporters, satellites (tier-1).

Covers the PR-2 tentpole (``utils.observability``) AND the telemetry seeds
PR 1 left untested: ``Counters`` under threads, ``snapshot``/``clear``
prefix semantics — plus the
acceptance criterion: the headline Lasso fit (dataset-full.csv, maxIter=40)
with ``spark.observability.enabled=true`` produces a valid nested Chrome
trace, one merged metrics registry (solver + ``recovery.*``), and a
Prometheus text dump that round-trips; with observability disabled, the
instrumented paths add zero host syncs and allocate no span objects.
"""

import json
import logging
import math
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdq4ml_tpu.utils import observability as obs
from sparkdq4ml_tpu.utils import profiling
from sparkdq4ml_tpu.utils.logging import configure_logging, format_kv
from sparkdq4ml_tpu.utils.profiling import Counters

from conftest import dataset_path, prepare_features, run_dq_pipeline

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with the tracer off and buffers empty."""
    obs.disable()
    obs.reset()
    profiling.counters.clear()
    yield
    obs.disable()
    obs.reset()
    profiling.counters.clear()


# ---------------------------------------------------------------------------
# PR-1 telemetry seeds (previously untested)
# ---------------------------------------------------------------------------


class TestCountersSeed:
    def test_concurrent_increments_are_lossless(self):
        c = Counters()
        n_threads, per_thread = 8, 500

        def worker():
            for _ in range(per_thread):
                c.increment("hot")
                c.increment("cold", by=2)

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.get("hot") == n_threads * per_thread
        assert c.get("cold") == 2 * n_threads * per_thread

    def test_snapshot_prefix_filters(self):
        c = Counters()
        c.increment("recovery.retry")
        c.increment("recovery.fallback", by=3)
        c.increment("solver.fits")
        snap = c.snapshot("recovery.")
        assert snap == {"recovery.retry": 1, "recovery.fallback": 3}
        assert c.snapshot() == {"recovery.retry": 1, "recovery.fallback": 3,
                                "solver.fits": 1}
        # snapshot is a copy, not a view
        snap["recovery.retry"] = 99
        assert c.get("recovery.retry") == 1

    def test_clear_prefix_leaves_the_rest(self):
        c = Counters()
        c.increment("a.x")
        c.increment("a.y")
        c.increment("b.z")
        c.clear("a.")
        assert c.snapshot() == {"b.z": 1}
        c.clear()
        assert c.snapshot() == {}


# ---------------------------------------------------------------------------
# Satellites: format_kv zeros, configure_logging force
# ---------------------------------------------------------------------------


class TestFormatKvZeros:
    def test_meaningful_zeros_survive(self):
        line = format_kv(retries=0, duration_ms=0.0, site="s")
        assert "retries=0" in line
        assert "duration_ms=0.0" in line

    def test_none_and_empty_string_still_elided(self):
        assert format_kv(a=None, b="", c=1) == "c=1"

    def test_quoting_unchanged(self):
        assert format_kv(msg="two words") == 'msg="two words"'

    def test_false_survives(self):
        # False is a value, not an absence (bool is an int subclass — the
        # old zero-ish elision dropped it too)
        assert "ok=False" in format_kv(ok=False)


class TestConfigureLoggingForce:
    def _with_root_handler(self):
        root = logging.getLogger()
        sentinel = logging.NullHandler()
        saved = list(root.handlers)
        return root, sentinel, saved

    def test_default_appends_when_handlers_exist(self):
        root, sentinel, saved = self._with_root_handler()
        try:
            root.addHandler(sentinel)
            configure_logging()
            assert sentinel in root.handlers  # caplog-style handler survives
            assert len(root.handlers) >= 2
        finally:
            root.handlers = saved

    def test_force_replaces(self):
        root, sentinel, saved = self._with_root_handler()
        try:
            root.addHandler(sentinel)
            configure_logging(force=True)
            assert sentinel not in root.handlers
            assert len(root.handlers) == 1
        finally:
            root.handlers = saved

    def test_repeated_calls_are_idempotent(self):
        root, sentinel, saved = self._with_root_handler()
        try:
            root.addHandler(sentinel)
            configure_logging()
            configure_logging()
            configure_logging()
            ours = [h for h in root.handlers
                    if getattr(h, "_sparkdq4ml", False)]
            assert len(ours) == 1           # no duplicate log lines
            assert sentinel in root.handlers
        finally:
            root.handlers = saved


# ---------------------------------------------------------------------------
# Tracer: spans, nesting, disabled-mode no-op
# ---------------------------------------------------------------------------


class TestSpans:
    def test_nesting_via_contextvar(self):
        obs.enable()
        with obs.span("outer", cat="t") as o:
            with obs.span("inner", cat="t") as i:
                pass
        spans = {s.name: s for s in obs.TRACER.spans()}
        assert spans["inner"].parent_id == spans["outer"].sid
        assert spans["outer"].parent_id is None
        assert o.dur_us >= i.dur_us

    def test_attributes_and_error_capture(self):
        obs.enable()
        with pytest.raises(ValueError):
            with obs.span("boom", cat="t", a=1) as s:
                s.set(b=2)
                raise ValueError("x")
        (sp,) = obs.TRACER.spans()
        assert sp.attrs["a"] == 1 and sp.attrs["b"] == 2
        assert sp.attrs["error"] == "ValueError"

    def test_begin_end_long_lived_span(self):
        obs.enable()
        root = obs.TRACER.begin("root", cat="session")
        with obs.span("child", cat="t"):
            pass
        assert any(s.dur_us is None for s in obs.TRACER.spans())  # still open
        obs.TRACER.end(root)
        spans = {s.name: s for s in obs.TRACER.spans()}
        assert spans["child"].parent_id == spans["root"].sid
        assert spans["root"].dur_us is not None

    def test_begun_root_survives_enclosing_span_exit(self):
        # begin() inside a `with span` must keep parenting AFTER that
        # span exits (the contextvar reset would otherwise orphan every
        # later span) — the ambient-root fallback.
        obs.enable()
        with obs.span("startup", cat="t"):
            root = obs.TRACER.begin("root", cat="session")
        with obs.span("later", cat="t"):
            pass
        obs.TRACER.end(root)
        spans = {s.name: s for s in obs.TRACER.spans()}
        assert spans["later"].parent_id == spans["root"].sid

    def test_worker_thread_spans_nest_under_begun_root(self):
        obs.enable()
        root = obs.TRACER.begin("root", cat="session")
        seen = {}

        def worker():
            with obs.span("in_thread", cat="t") as s:
                seen["parent"] = s.parent_id

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        obs.TRACER.end(root)
        assert seen["parent"] == root.sid

    def test_buffer_is_bounded(self):
        obs.enable(max_spans=5)
        for i in range(12):
            with obs.span(f"s{i}", cat="t"):
                pass
        assert len(obs.TRACER.spans()) == 5

    def test_span_durations_feed_histograms(self):
        obs.enable()
        with obs.span("x", cat="mycat"):
            pass
        snap = obs.METRICS.snapshot()
        assert snap["span_ms.mycat"]["count"] == 1

    def test_threads_get_independent_parents(self):
        obs.enable()
        seen = {}

        def worker():
            with obs.span("in_thread", cat="t") as s:
                seen["parent"] = s.parent_id

        with obs.span("main_span", cat="t"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        # a fresh thread starts a fresh context: no cross-thread parent
        assert seen["parent"] is None


class TestDisabledNoOp:
    def test_span_returns_shared_singleton(self):
        a = obs.span("x", cat="t")
        b = obs.TRACER.span("y")
        assert a is b is obs._NOOP          # no allocation, one flag check
        assert obs.current_span() is obs._NOOP
        with a as s:
            s.set(anything=1)               # all methods are no-ops
        assert obs.TRACER.spans() == []

    def test_frame_ops_record_nothing_and_never_sync(self, monkeypatch):
        from sparkdq4ml_tpu.frame.frame import Frame

        syncs = []
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda t: syncs.append(1) or t)
        f = Frame({"a": [1.0, 2.0, 3.0], "b": [4.0, 5.0, 6.0]})
        g = f.with_column("c", f["a"] + f["b"]).filter(f["a"] > 1).select(
            "a", "c")
        assert g.columns == ["a", "c"]
        assert obs.TRACER.spans() == []
        assert syncs == []                  # zero additional host syncs

    def test_disabled_fit_adds_no_spans_or_syncs(self, monkeypatch):
        from sparkdq4ml_tpu.frame.frame import Frame
        from sparkdq4ml_tpu.models.regression import LinearRegression

        f = Frame({"features": np.arange(8.0)[:, None],
                   "label": 2.0 * np.arange(8.0) + 1.0})
        syncs = []
        orig = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda t: syncs.append(1) or orig(t))
        m = LinearRegression(max_iter=5).fit(f, mesh=None)
        assert np.isfinite(m.coefficients).all()
        assert obs.TRACER.spans() == []
        # Exactly ONE sync, and it predates this subsystem: the recovery
        # validator blocks inside the attempt (utils/recovery.py,
        # resilient_call) so non-finite results are caught while retries
        # can still help. Observability-disabled mode adds zero on top.
        assert len(syncs) == 1


# ---------------------------------------------------------------------------
# Metrics registry: gauges + histograms
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_gauge_set_get(self):
        obs.METRICS.set_gauge("g", 3.5)
        assert obs.METRICS.get_gauge("g") == 3.5
        obs.METRICS.set_gauge("g", 1.0)     # gauges move both ways
        assert obs.METRICS.snapshot()["g"] == 1.0

    def test_histogram_fixed_buckets_cumulative(self):
        h = obs.Histogram("h", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 50.0, 500.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["buckets"][1.0] == 1
        assert snap["buckets"][10.0] == 2
        assert snap["buckets"][100.0] == 3
        assert snap["buckets"][float("inf")] == 4
        assert snap["count"] == 4
        assert snap["sum"] == pytest.approx(555.5)

    def test_boundary_value_lands_in_its_bucket(self):
        h = obs.Histogram("h", buckets=(1.0, 10.0))
        h.observe(10.0)                     # le semantics: 10.0 ≤ 10.0
        assert h.snapshot()["buckets"][10.0] == 1
        assert h.snapshot()["buckets"][1.0] == 0

    def test_registry_histogram_get_or_create(self):
        h1 = obs.METRICS.histogram("lat")
        h2 = obs.METRICS.histogram("lat")
        assert h1 is h2

    def test_merged_snapshot_spans_all_three_kinds(self):
        profiling.counters.increment("solver.fits")
        obs.METRICS.set_gauge("mesh.devices", 8)
        obs.METRICS.observe("lat_ms", 3.0)
        snap = obs.metrics_snapshot()
        assert snap["solver.fits"] == 1
        assert snap["mesh.devices"] == 8.0
        assert snap["lat_ms"]["count"] == 1


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------


def _parse_prometheus(text: str) -> dict:
    """Minimal Prometheus text-format parser: {name or name{labels}: value}.
    Raises on any malformed line — the round-trip assertion."""
    out = {}
    line_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                            r"(counter|gauge|histogram)"
                            r"|HELP [a-zA-Z_:][a-zA-Z0-9_:]* \S.*)$",
                            line), line
            continue
        m = line_re.match(line)
        assert m, f"malformed Prometheus line: {line!r}"
        val = float(m.group(3)) if m.group(3) != "+Inf" else math.inf
        out[m.group(1) + (m.group(2) or "")] = val
    return out


class TestExporters:
    def test_chrome_trace_shape(self):
        obs.enable()
        with obs.span("parent", cat="sql", q=1):
            with obs.span("child", cat="frame"):
                pass
        doc = obs.chrome_trace()
        all_events = doc["traceEvents"]
        # span events; counter ("C") resource tracks ride alongside
        events = [e for e in all_events if e["ph"] == "X"]
        assert {e["name"] for e in events} == {"parent", "child"}
        for e in all_events:
            assert e["ph"] in ("X", "C")
        for e in events:
            assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
            assert e["dur"] >= 1
        child = next(e for e in events if e["name"] == "child")
        parent = next(e for e in events if e["name"] == "parent")
        assert child["args"]["parent_id"] == parent["args"]["span_id"]
        # time containment (same thread ⇒ chrome nests by ts/dur)
        assert parent["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1
        json.dumps(doc)                      # serializable

    def test_dump_chrome_trace_roundtrip(self, tmp_path):
        obs.enable()
        with obs.span("a", cat="t"):
            pass
        p = obs.dump_chrome_trace(str(tmp_path / "trace.json"))
        doc = json.load(open(p))
        assert doc["traceEvents"][0]["name"] == "a"

    def test_trace_report_tree(self):
        obs.enable()
        with obs.span("outer", cat="t"):
            with obs.span("inner", cat="t", rows=3):
                pass
        rep = obs.trace_report()
        lines = rep.splitlines()
        assert lines[0].startswith("outer")
        assert lines[1].startswith("  inner")
        assert "rows=3" in lines[1]

    def test_prometheus_roundtrip(self):
        profiling.counters.increment("recovery.retry", by=2)
        obs.METRICS.set_gauge("mesh.devices", 8)
        obs.METRICS.observe("lat_ms", 7.0, buckets=(5.0, 50.0))
        parsed = _parse_prometheus(obs.prometheus_text())
        assert parsed["sparkdq4ml_recovery_retry"] == 2
        assert parsed["sparkdq4ml_mesh_devices"] == 8
        assert parsed['sparkdq4ml_lat_ms_bucket{le="5"}'] == 0
        assert parsed['sparkdq4ml_lat_ms_bucket{le="50"}'] == 1
        assert parsed['sparkdq4ml_lat_ms_bucket{le="+Inf"}'] == 1
        assert parsed["sparkdq4ml_lat_ms_count"] == 1
        assert parsed["sparkdq4ml_lat_ms_sum"] == 7.0

    def test_logfmt_span_lines(self, caplog):
        obs.enable(log_spans=True)
        with caplog.at_level(logging.DEBUG,
                             logger="sparkdq4ml_tpu.observability"):
            with obs.span("op", cat="frame", rows=4):
                pass
        line = next(r.getMessage() for r in caplog.records
                    if "name=op" in r.getMessage())
        assert "cat=frame" in line and "rows=4" in line
        assert "dur_ms=" in line


# ---------------------------------------------------------------------------
# Wiring: SQL plan spans, parallel gram, session surface
# ---------------------------------------------------------------------------


class TestSqlSpans:
    def test_query_span_carries_text_plan_and_rows(self, session):
        from sparkdq4ml_tpu.frame.frame import Frame

        obs.enable()
        Frame({"a": [1.0, 2.0, 3.0]}).create_or_replace_temp_view("t")
        out = session.sql("SELECT a FROM t WHERE a > 1 ORDER BY a LIMIT 5")
        assert out.count() == 2
        sql_spans = [s for s in obs.TRACER.spans() if s.name == "sql.query"]
        assert len(sql_spans) == 1
        s = sql_spans[0]
        assert "SELECT a FROM t" in s.attrs["query"]
        # Project+Filter print as one FusedStage when the pipeline
        # compiler is on (the default) — the stage boundary marker; the
        # ORDER BY prints as DeviceSort under grouped execution (PR 4)
        assert s.attrs["plan"] == (
            "Limit[5] <- DeviceSort[1] <- FusedStage(Project[1] <- Filter) "
            "<- Scan[t]")
        assert s.attrs["rows_out"] == out.num_slots
        # frame ops executed by the query nest under it, below its
        # sql.execute child (sql.parse and sql.optimize are its siblings)
        by_sid = {c.sid: c for c in obs.TRACER.spans()}
        execute = [c for c in by_sid.values()
                   if c.name == "sql.execute" and c.parent_id == s.sid]
        assert len(execute) == 1
        frame_children = [c for c in by_sid.values() if c.cat == "frame"
                          and c.parent_id == execute[0].sid]
        assert frame_children

    def test_ddl_spans(self, session):
        from sparkdq4ml_tpu.frame.frame import Frame

        obs.enable()
        Frame({"a": [1.0]}).create_or_replace_temp_view("src")
        session.sql("CREATE OR REPLACE TEMP VIEW v AS SELECT a FROM src")
        session.sql("DROP VIEW v")
        plans = [s.attrs.get("plan") for s in obs.TRACER.spans()
                 if s.name == "sql.query"]
        assert "CreateView[v]" in plans
        assert "DropView[v]" in plans


class TestParallelSpans:
    def test_sharded_gram_span_and_counters(self, session):
        from sparkdq4ml_tpu.parallel.distributed import compute_gram

        obs.enable()
        n0 = profiling.counters.get("parallel.psum_dispatches")
        X = np.arange(16.0).reshape(8, 2)
        y = np.arange(8.0)
        mask = np.ones(8, bool)
        A = compute_gram(X, y, mask, mesh=session.mesh)
        assert np.asarray(A).shape == (4, 4)
        assert profiling.counters.get("parallel.psum_dispatches") == n0 + 1
        spans = {s.name: s for s in obs.TRACER.spans()}
        outer, inner = spans["parallel.gram"], spans["parallel.gram_shard"]
        assert inner.parent_id == outer.sid
        assert outer.attrs["shards"] == session.num_devices
        assert inner.attrs["rows_per_shard"] == 8 // session.num_devices
        assert inner.attrs["device"] == "cpu"

    def test_mesh_gauge_set(self, session):
        assert obs.METRICS.get_gauge("mesh.devices") == session.num_devices


class TestSessionSurface:
    CONF = {"spark.compilation.cache": "off"}

    def _session(self, **conf):
        from sparkdq4ml_tpu import TpuSession

        b = TpuSession.builder().app_name("obs").master("local[*]")
        for k, v in {**self.CONF, **conf}.items():
            b = b.config(k, v)
        return b.get_or_create()

    def test_conf_enables_and_opens_root_span(self):
        s = self._session(**{"spark.observability.enabled": "true"})
        try:
            assert obs.enabled()
            roots = [sp for sp in obs.TRACER.spans() if sp.name == "session"]
            assert len(roots) == 1
            assert roots[0].attrs["app"] == "obs"
            assert roots[0].attrs["devices"] == s.num_devices
            assert roots[0].dur_us is None          # still open
        finally:
            s.stop()
        assert not obs.enabled()                    # session-scoped opt-in
        roots = [sp for sp in obs.TRACER.spans() if sp.name == "session"]
        assert roots[0].dur_us is not None          # closed by stop()

    def test_env_var_enables(self, monkeypatch):
        monkeypatch.setenv(obs.ENV_VAR, "1")
        s = self._session()
        try:
            assert obs.enabled()
        finally:
            s.stop()

    def test_env_off_spellings_do_not_enable(self, monkeypatch):
        for off in ("off", "False", "no", "0"):
            monkeypatch.setenv(obs.ENV_VAR, off)
            s = self._session()
            try:
                assert not obs.enabled(), off
            finally:
                s.stop()

    def test_conf_off_beats_env(self, monkeypatch):
        monkeypatch.setenv(obs.ENV_VAR, "1")
        s = self._session(**{"spark.observability.enabled": "false"})
        try:
            assert not obs.enabled()
        finally:
            s.stop()

    def test_default_is_disabled(self):
        s = self._session()
        try:
            assert not obs.enabled()
            assert s.trace_report() == ""
        finally:
            s.stop()

    def test_metrics_and_text_surface(self):
        s = self._session()
        try:
            profiling.counters.increment("solver.fits")
            assert s.metrics()["solver.fits"] == 1
            assert "sparkdq4ml_solver_fits 1" in s.metrics_text()
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# Acceptance: headline Lasso fit, end to end
# ---------------------------------------------------------------------------


class TestHeadlineAcceptance:
    def test_lasso_fit_full_observability(self, tmp_path):
        from sparkdq4ml_tpu import TpuSession
        from sparkdq4ml_tpu.models.regression import LinearRegression
        from sparkdq4ml_tpu.utils.recovery import RECOVERY_LOG

        RECOVERY_LOG.record("obs_test", "retry")  # recovery.* pre-seeded
        session = (TpuSession.builder().app_name("headline")
                   .master("local[*]")
                   .config("spark.compilation.cache", "off")
                   .config("spark.observability.enabled", "true")
                   .get_or_create())
        try:
            df = run_dq_pipeline(session, dataset_path("full"))
            df = prepare_features(df)
            lr = (LinearRegression().setMaxIter(40).setRegParam(0.01)
                  .setElasticNetParam(1.0))
            model = lr.fit(df)
            assert np.isfinite(model.coefficients).all()

            # (a) valid Chrome trace with nested session/query/fit/solver
            path = session.dump_trace(str(tmp_path / "lasso_trace.json"))
            doc = json.load(open(path))
            events = doc["traceEvents"]
            by_name = {}
            for e in events:
                by_name.setdefault(e["name"], []).append(e)
            assert "session" in by_name
            assert "sql.query" in by_name
            assert "fit.linear_regression" in by_name
            assert "fit.solve" in by_name
            root_id = by_name["session"][0]["args"]["span_id"]
            assert all(e["args"]["parent_id"] == root_id
                       for e in by_name["sql.query"])
            fit = by_name["fit.linear_regression"][0]
            assert fit["args"]["parent_id"] == root_id
            solve = by_name["fit.solve"][0]
            assert solve["args"]["parent_id"] == fit["args"]["span_id"]
            assert fit["args"]["solver"] == "fista"       # L1 ⇒ proximal
            assert fit["args"]["compile"] in ("miss", "hit")
            assert fit["args"]["iterations"] >= 1
            assert math.isfinite(fit["args"]["objective_final"])
            q = by_name["sql.query"][0]["args"]
            assert "plan" in q and "Scan[price]" in q["plan"]

            # (b) one merged registry: solver counters AND recovery.*
            met = session.metrics()
            assert met["solver.fits"] >= 1
            assert met["solver.iterations"] >= 1
            assert met["recovery.retry"] >= 1
            assert met["mesh.devices"] == session.num_devices
            assert met["span_ms.fit"]["count"] >= 1

            # (c) Prometheus text round-trips through the parser
            parsed = _parse_prometheus(session.metrics_text())
            assert parsed["sparkdq4ml_solver_fits"] >= 1
            assert parsed["sparkdq4ml_recovery_retry"] >= 1
            buckets = [k for k in parsed
                       if k.startswith("sparkdq4ml_span_ms_fit_bucket")]
            assert buckets
            # cumulative monotone buckets
            vals = [parsed[k] for k in sorted(
                buckets, key=lambda k: math.inf if "+Inf" in k
                else float(k.split('le="')[1].rstrip('"}')))]
            assert vals == sorted(vals)
        finally:
            session.stop()


# ---------------------------------------------------------------------------
# CI/tooling satellite: logger-namespace lint
# ---------------------------------------------------------------------------


class TestLoggerNamespaceLint:
    REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    SCRIPT = os.path.join(REPO, "scripts", "check_logger_ns.py")

    def test_framework_is_clean(self):
        proc = subprocess.run([sys.executable, self.SCRIPT, self.REPO],
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_lint_catches_offender(self, tmp_path):
        pkg = tmp_path / "sparkdq4ml_tpu"
        pkg.mkdir()
        (pkg / "bad.py").write_text(
            'import logging\nlog = logging.getLogger("rogue.ns")\n')
        (pkg / "wrapped.py").write_text(
            'import logging\nlog = logging.getLogger(\n'
            '    "rogue.wrapped")\n')
        (pkg / "aliased.py").write_text(
            'from logging import getLogger\nlog = getLogger("rogue")\n')
        (pkg / "good.py").write_text(
            'import logging\n'
            'a = logging.getLogger("sparkdq4ml_tpu.good")\n'
            'b = logging.getLogger(__name__)\n'
            'c = logging.getLogger("jax")  # logger-ns: ok\n'
            '"""docstring mentioning logging.getLogger("rogue") is text"""\n'
            '# comment: logging.getLogger("rogue") never executes\n'
            'mylogging = logging\n'
            's = "logging.getLogger(\'rogue\')"\n')
        proc = subprocess.run([sys.executable, self.SCRIPT, str(tmp_path)],
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 1
        assert "bad.py:2" in proc.stdout
        assert "wrapped.py:2" in proc.stdout       # line-wrapped call caught
        assert "aliased.py:1" in proc.stdout       # bare-name import caught
        assert "good.py" not in proc.stdout
