"""EXPLAIN / EXPLAIN ANALYZE + memory/cache introspection (tier-1).

PR-5 tentpole: per-operator runtime plan profiles (``sql/parser.py`` plan
tree + ``observability.query_stats``), device-memory accounting
(``utils.meminfo``), unified jit-cache introspection
(``observability.CACHES``), plus the satellites: trace-buffer overflow
accounting, stable trace/span ids across exporters, and the host-sync
audit (window/stat/evaluation).
"""

import logging
import re

import jax.numpy as jnp
import numpy as np
import pytest

import sparkdq4ml_tpu as dq
from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame.frame import Frame
from sparkdq4ml_tpu.sql import parser as sqlparser
from sparkdq4ml_tpu.utils import meminfo, observability as obs, profiling

from conftest import dataset_path, prepare_features, run_dq_pipeline

pytestmark = pytest.mark.explain

HEADLINE_DQ = ("SELECT cast(guest as int) guest, price_no_min AS price "
               "FROM price WHERE price_no_min > 0")

#: The acceptance schema: every operator node of an ANALYZE'd plan
#: carries all of these (measured or explicit "-").
NODE_FIELDS = ("rows_in=", "rows_out=", "wall_ms=", "compile=",
               "host_syncs=", "peak_mem=")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    profiling.counters.clear()
    yield
    obs.disable()
    obs.reset()
    profiling.counters.clear()


def _views(session):
    Frame({"a": [1.0, 2.0, 3.0, 4.0], "k": [1, 1, 2, 2]}
          ).create_or_replace_temp_view("t")
    Frame({"k": [1, 2], "b": [10.0, 20.0]}).create_or_replace_temp_view("u")


def _plan_text(frame) -> str:
    return str(frame.to_pydict()["plan"][0])


def _node_lines(text: str) -> list[str]:
    """The operator lines of a rendered ANALYZE plan."""
    lines = text.splitlines()
    start = lines.index("== Analyzed Plan ==") + 1
    end = lines.index("== Query Stats ==")
    return lines[start:end]


# ---------------------------------------------------------------------------
# Plan-node tree
# ---------------------------------------------------------------------------


class TestPlanTree:
    def test_main_chain_matches_plan_summary(self):
        q = sqlparser.parse("SELECT a FROM t WHERE a > 1 ORDER BY a LIMIT 5")
        tree = sqlparser.plan_tree(q)
        chain = " <- ".join(n.label for n in tree.main_chain())
        assert chain == sqlparser.plan_summary(q)
        assert chain == ("Limit[5] <- DeviceSort[1] <- "
                         "FusedStage(Project[1] <- Filter) <- Scan[t]")

    def test_join_nodes_carry_right_scan_child(self):
        q = sqlparser.parse("SELECT t.a FROM t JOIN u USING (k)")
        tree = sqlparser.plan_tree(q)
        joins = [n for n in tree.walk() if n.op == "Join"]
        assert len(joins) == 1
        assert joins[0].children[1].label == "Scan[u]"

    def test_render_indents_children(self):
        q = sqlparser.parse("SELECT a FROM t WHERE a > 1 LIMIT 2")
        text = sqlparser.plan_tree(q).render()
        lines = text.splitlines()
        assert lines[0] == "Limit[2]"
        assert lines[1].startswith("+- ")
        assert lines[-1].strip().endswith("Scan[t]")

    def test_stats_empty_without_analyze(self):
        q = sqlparser.parse("SELECT a FROM t")
        assert all(n.stats == {} for n in sqlparser.plan_tree(q).walk())


# ---------------------------------------------------------------------------
# EXPLAIN — render only, zero execution
# ---------------------------------------------------------------------------


class TestExplain:
    def test_returns_one_row_plan_frame(self, session):
        _views(session)
        out = session.sql("EXPLAIN SELECT a FROM t WHERE a > 1")
        text = _plan_text(out)
        assert text.startswith("== Physical Plan ==")
        assert "FusedStage(Project[1] <- Filter)" in text
        assert "Scan[t]" in text

    def test_explain_is_case_insensitive(self, session):
        _views(session)
        text = _plan_text(session.sql("explain select a from t"))
        assert "Scan[t]" in text

    def test_no_execution_zero_compiles(self, session):
        _views(session)
        before = profiling.counters.snapshot()
        session.sql("EXPLAIN SELECT a, a * 2 AS b FROM t WHERE a > 1 "
                    "ORDER BY a")
        after = profiling.counters.snapshot()
        for key in ("pipeline.flush", "pipeline.compile", "grouped.compile",
                    "frame.host_sync"):
            assert after.get(key, 0) == before.get(key, 0), key

    def test_explain_leaves_tracer_disabled(self, session):
        _views(session)
        session.sql("EXPLAIN SELECT a FROM t")
        assert not obs.TRACER.enabled

    def test_explain_ddl_forms(self, session):
        _views(session)
        text = _plan_text(session.sql(
            "EXPLAIN CREATE OR REPLACE TEMP VIEW v AS SELECT a FROM t"))
        assert "CreateView[v]" in text
        assert "Scan[t]" in text
        # the view was NOT created (EXPLAIN never executes)
        with pytest.raises(KeyError):
            session.table("v")
        text = _plan_text(session.sql("EXPLAIN DROP VIEW t"))
        assert "DropView[t]" in text
        session.table("t")            # still registered

    def test_explain_grouped_markers_follow_conf(self, session):
        _views(session)
        q = "EXPLAIN SELECT k, count(*) c FROM t GROUP BY k ORDER BY k"
        assert "SegmentedAggregate[groupBy:1]" in _plan_text(session.sql(q))
        assert "DeviceSort[1]" in _plan_text(session.sql(q))
        config.grouped_exec = False
        try:
            text = _plan_text(session.sql(q))
            assert "Aggregate[groupBy:1]" in text
            assert "SegmentedAggregate" not in text
            assert "Sort[1]" in text and "DeviceSort" not in text
        finally:
            config.grouped_exec = True


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE — measured per-operator stats
# ---------------------------------------------------------------------------


class TestExplainAnalyze:
    def test_headline_dq_query_every_node_annotated(self, session):
        dq.register_builtin_rules()
        df = (session.read.format("csv").option("inferSchema", "true")
              .load(dataset_path("abstract")))
        df = df.with_column_renamed("_c0", "guest")
        df = df.with_column_renamed("_c1", "price")
        df = df.with_column("price_no_min",
                            dq.call_udf("minimumPriceRule", dq.col("price")))
        df.create_or_replace_temp_view("price")
        text = _plan_text(session.sql("EXPLAIN ANALYZE " + HEADLINE_DQ))
        nodes = _node_lines(text)
        assert len(nodes) >= 2          # Project/Filter stage(s) + Scan
        for line in nodes:
            for field in NODE_FIELDS:
                assert field in line, (field, line)
        assert "== Query Stats ==" in text
        assert "wall_ms=" in text and "rows_out=" in text

    def test_repeat_flips_compile_to_hit(self, session):
        _views(session)
        q = ("EXPLAIN ANALYZE SELECT k, count(*) c, avg(a) m FROM t "
             "WHERE a > 0 GROUP BY k ORDER BY k")
        first = _plan_text(session.sql(q))
        agg_line = next(ln for ln in _node_lines(first)
                        if "SegmentedAggregate" in ln)
        assert "compile=compile" in agg_line
        second = _plan_text(session.sql(q))
        agg_line = next(ln for ln in _node_lines(second)
                        if "SegmentedAggregate" in ln)
        assert "compile=hit" in agg_line
        assert "lowering=" in agg_line

    def test_group_by_rows_in_out(self, session):
        _views(session)
        text = _plan_text(session.sql(
            "EXPLAIN ANALYZE SELECT k, count(*) c FROM t GROUP BY k"))
        agg_line = next(ln for ln in _node_lines(text)
                        if "SegmentedAggregate" in ln)
        assert "rows_in=4" in agg_line and "rows_out=2" in agg_line

    def test_join_node_counts_host_syncs(self, session):
        _views(session)
        text = _plan_text(session.sql(
            "EXPLAIN ANALYZE SELECT t.a, u.b FROM t JOIN u USING (k) "
            "WHERE a > 1"))
        join_line = next(ln for ln in _node_lines(text) if "Join[" in ln)
        m = re.search(r"host_syncs=(\d+)", join_line)
        assert m and int(m.group(1)) >= 1   # join's planning pulls count
        assert "Scan[u]" in text

    def test_cache_section_lists_touched_programs(self, session):
        _views(session)
        text = _plan_text(session.sql(
            "EXPLAIN ANALYZE SELECT a FROM t WHERE a > 1"))
        assert "== Caches ==" in text
        assert "pipeline:" in text
        assert "program " in text

    def test_caches_section_gated_by_conf(self, session):
        _views(session)
        config.explain_caches = False
        try:
            text = _plan_text(session.sql(
                "EXPLAIN ANALYZE SELECT a FROM t WHERE a > 1"))
            assert "== Caches ==" not in text
        finally:
            config.explain_caches = True

    def test_memory_sampling_gated_by_conf(self, session):
        _views(session)
        config.explain_memory = False
        try:
            text = _plan_text(session.sql(
                "EXPLAIN ANALYZE SELECT a FROM t WHERE a > 1"))
            assert "live_bytes=" not in text
            assert all("peak_mem=-" in ln for ln in _node_lines(text))
        finally:
            config.explain_memory = True
        text = _plan_text(session.sql(
            "EXPLAIN ANALYZE SELECT a FROM t WHERE a > 1"))
        assert "live_bytes=" in text
        assert any(re.search(r"peak_mem=\d", ln)
                   for ln in _node_lines(text))

    def test_pipeline_off_unfused_plan_still_annotates(self, session):
        _views(session)
        config.pipeline = False
        try:
            text = _plan_text(session.sql(
                "EXPLAIN ANALYZE SELECT a FROM t WHERE a > 1"))
            assert "FusedStage" not in text
            nodes = _node_lines(text)
            assert any("Filter" in ln for ln in nodes)
            for line in nodes:
                for field in NODE_FIELDS:
                    assert field in line
        finally:
            config.pipeline = True

    def test_grouped_off_still_annotates(self, session):
        _views(session)
        config.grouped_exec = False
        try:
            text = _plan_text(session.sql(
                "EXPLAIN ANALYZE SELECT k, count(*) c FROM t GROUP BY k "
                "ORDER BY k"))
            assert "Aggregate[groupBy:1]" in text
            assert "SegmentedAggregate" not in text
            for line in _node_lines(text):
                for field in NODE_FIELDS:
                    assert field in line
        finally:
            config.grouped_exec = True

    def test_where_and_having_filters_not_swapped(self, session):
        """Attribution follows EXECUTION order: the WHERE filter's span
        (rows_in = full table) must land on the Filter node, the HAVING
        filter's span (rows_in = group count) on the Having node — a
        root-first walk used to swap them."""
        _views(session)
        text = _plan_text(session.sql(
            "EXPLAIN ANALYZE SELECT k, sum(a) s FROM t WHERE a > 0 "
            "GROUP BY k HAVING sum(a) > 1"))
        nodes = _node_lines(text)
        filter_line = next(ln for ln in nodes
                           if re.search(r"\bFilter\b", ln)
                           and "FusedStage" not in ln)
        having_line = next(ln for ln in nodes if "Having" in ln)
        assert "rows_in=4" in filter_line     # the source table's slots
        assert "rows_in=2" in having_line     # the two groups

    def test_derived_table_spans_stay_in_subquery(self, session):
        """A derived table's plan renders as a child of its Scan and
        consumes its own spans — the outer Filter must be annotated with
        the OUTER filter's rows, not the subquery's."""
        _views(session)
        text = _plan_text(session.sql(
            "EXPLAIN ANALYZE SELECT a FROM "
            "(SELECT a FROM t WHERE a > 0) sub WHERE a < 4"))
        nodes = _node_lines(text)
        assert any("Scan[(subquery)]" in ln for ln in nodes)
        # the subquery's own FusedStage/Filter renders nested under it
        scan_i = next(i for i, ln in enumerate(nodes)
                      if "Scan[(subquery)]" in ln)
        assert any("Filter" in ln for ln in nodes[scan_i + 1:])
        # outer and inner stages both annotated with the source's slots
        stage_lines = [ln for ln in nodes
                       if "FusedStage" in ln or re.search(r"\bFilter\b",
                                                          ln)]
        assert len(stage_lines) == 2
        for ln in stage_lines:
            assert "rows_in=4" in ln

    def test_cte_subtrees_render_and_annotate(self, session):
        _views(session)
        text = _plan_text(session.sql(
            "EXPLAIN ANALYZE WITH big AS (SELECT a FROM t WHERE a > 1) "
            "SELECT a FROM big WHERE a < 4"))
        nodes = _node_lines(text)
        assert nodes[0].startswith("With[1]")
        assert any("Scan[big]" in ln for ln in nodes)
        assert any("Scan[t]" in ln for ln in nodes)

    def test_analyze_leaves_tracer_state(self, session):
        _views(session)
        session.sql("EXPLAIN ANALYZE SELECT a FROM t WHERE a > 1")
        assert not obs.TRACER.enabled
        assert not obs.TRACER.mem_sample

    def test_golden_numbers_with_analyze_on(self, session):
        """Acceptance: the example-app goldens are unchanged when the
        queries also run under EXPLAIN ANALYZE (observability on)."""
        from sparkdq4ml_tpu.models import LinearRegression

        obs.enable()
        df = run_dq_pipeline(session, dataset_path("abstract"))
        # the same two queries, analyzed (executes them again under the
        # per-query collector)
        for q in ("SELECT guest, price_correct_correl AS price "
                  "FROM price WHERE price_correct_correl > 0",):
            text = _plan_text(session.sql("EXPLAIN ANALYZE " + q))
            for line in _node_lines(text):
                for field in NODE_FIELDS:
                    assert field in line
        assert df.count() == 24
        df = prepare_features(df)
        model = (LinearRegression().setMaxIter(40).setRegParam(1)
                 .setElasticNetParam(1)).fit(df)
        assert model.summary.root_mean_squared_error == pytest.approx(
            2.809940, abs=1e-4)


# ---------------------------------------------------------------------------
# Frame.explain(analyze=...)
# ---------------------------------------------------------------------------


class TestFrameExplainAnalyze:
    def test_pending_pipeline_profile(self):
        f = (Frame({"x": [1.0, 2.0, 3.0]})
             .with_column("y", dq.col("x") * 2)
             .filter(dq.col("y") > 2))
        text = f.explain_string(analyze=True)
        assert "== Analyzed ==" in text
        assert "frame.pipeline.flush" in text
        assert "cache=" in text
        assert "counters:" in text and "pipeline.flush=1" in text
        assert "== Physical Frame ==" in text

    def test_materialized_frame_reports_nothing_pending(self):
        f = Frame({"x": [1.0, 2.0]})
        f.count()
        text = f.explain_string(analyze=True)
        assert "nothing pending" in text

    def test_plain_explain_unchanged(self, capsys):
        Frame({"x": [1.0, 2.0]}).explain()
        out = capsys.readouterr().out
        assert out.startswith("== Physical Frame ==")
        assert "== Analyzed ==" not in out


# ---------------------------------------------------------------------------
# Memory + cache reports (session surface)
# ---------------------------------------------------------------------------


class TestMemoryReport:
    def test_report_shape_and_census(self, session):
        f = Frame({"x": np.arange(1024, dtype=np.float64)})
        f.count()
        rep = session.memory_report(top=3)
        for key in ("backend", "live_bytes", "peak_bytes", "live_arrays",
                    "by_dtype", "largest", "devices"):
            assert key in rep
        assert rep["live_bytes"] >= 1024 * 8
        assert rep["peak_bytes"] >= rep["live_bytes"]
        assert len(rep["largest"]) <= 3
        assert rep["largest"][0]["bytes"] >= 1024 * 8

    def test_estimated_bytes_is_static(self):
        est = meminfo.estimated_bytes(
            {"a": jnp.zeros((16, 4)), "b": np.zeros(8, np.int32)})
        assert est == 16 * 4 * jnp.zeros((1,)).dtype.itemsize + 8 * 4

    def test_sample_updates_gauges_and_peak(self):
        meminfo.reset_peak()
        keep = jnp.arange(4096.0)     # noqa: F841 - held live on purpose
        b = meminfo.sample()
        assert b > 0
        assert obs.METRICS.get_gauge("mem.live_bytes") == b
        assert meminfo.peak_bytes() >= b


class TestCacheReport:
    def test_all_producers_registered(self, session):
        rep = session.cache_report()
        for name in ("pipeline", "grouped", "solver", "fit.factories"):
            assert name in rep, rep.keys()

    def test_pipeline_entries_track_hits_and_buckets(self, session):
        from sparkdq4ml_tpu.ops import compiler

        compiler.clear_cache()
        f = Frame({"x": [1.0, 2.0, 3.0]}).filter(dq.col("x") > 1)
        f.count()
        g = Frame({"x": [4.0, 5.0, 6.0]}).filter(dq.col("x") > 2)
        g.count()
        entry = session.cache_report()["pipeline"]["entries"][0]
        assert entry["compiles"] == 1
        assert entry["hits"] == 1
        assert sum(entry["buckets"].values()) == 2

    def test_grouped_entries_track_builds(self, session):
        from sparkdq4ml_tpu.frame.aggregates import AggExpr
        from sparkdq4ml_tpu.ops import segments

        segments.clear_cache()
        f = Frame({"k": [1, 1, 2], "v": [1.0, 2.0, 3.0]})
        f.group_by("k").agg(AggExpr("sum", "v")).count()
        f.group_by("k").agg(AggExpr("sum", "v")).count()
        rep = session.cache_report()["grouped"]
        assert rep["size"] >= 1
        assert any(e["builds"] == 1 and e["hits"] >= 1
                   for e in rep["entries"])


# ---------------------------------------------------------------------------
# Satellite: trace-buffer overflow accounting
# ---------------------------------------------------------------------------


class TestDroppedSpans:
    def test_overflow_counts_and_reports(self):
        obs.enable(max_spans=5)
        for i in range(12):
            with obs.span(f"s{i}", cat="t"):
                pass
        assert obs.TRACER.dropped == 7
        assert profiling.counters.get("trace.dropped_spans") == 7
        assert len(obs.TRACER.spans()) == 5
        assert "dropped=7 spans" in obs.trace_report()
        doc = obs.chrome_trace()
        assert doc["otherData"]["dropped_spans"] == 7

    def test_no_overflow_no_field(self):
        obs.enable(max_spans=100)
        with obs.span("only", cat="t"):
            pass
        assert "dropped=" not in obs.trace_report()
        assert obs.chrome_trace()["otherData"]["dropped_spans"] == 0

    def test_reset_clears_dropped(self):
        obs.enable(max_spans=2)
        for i in range(5):
            with obs.span(f"s{i}", cat="t"):
                pass
        assert obs.TRACER.dropped > 0
        obs.reset()
        assert obs.TRACER.dropped == 0


# ---------------------------------------------------------------------------
# Satellite: stable ids across exporters + Prometheus HELP
# ---------------------------------------------------------------------------


class TestExporterIds:
    def test_logfmt_and_chrome_share_ids(self, caplog):
        obs.enable(log_spans=True)
        with caplog.at_level(logging.DEBUG,
                             logger="sparkdq4ml_tpu.observability"):
            with obs.span("outer", cat="t"):
                with obs.span("inner", cat="t"):
                    pass
        line = next(r.getMessage() for r in caplog.records
                    if "name=inner" in r.getMessage())
        trace_id = int(re.search(r"trace_id=(\d+)", line).group(1))
        span_id = int(re.search(r"span_id=(\d+)", line).group(1))
        ev = next(e for e in obs.chrome_trace()["traceEvents"]
                  if e["name"] == "inner")
        assert ev["args"]["trace_id"] == trace_id
        assert ev["args"]["span_id"] == span_id
        outer = next(e for e in obs.chrome_trace()["traceEvents"]
                     if e["name"] == "outer")
        # one trace: both spans share the root's id
        assert outer["args"]["trace_id"] == trace_id
        assert outer["args"]["span_id"] == trace_id

    def test_recovery_events_carry_ids(self):
        from sparkdq4ml_tpu.utils.recovery import RECOVERY_LOG

        RECOVERY_LOG.clear()
        obs.enable()
        with obs.span("fit", cat="fit") as s:
            RECOVERY_LOG.record("test_site", "retry", attempt=1)
        ev = RECOVERY_LOG.events(site="test_site")[-1]
        assert ev.trace_id == s.trace_id
        assert ev.span_id == s.sid
        assert f"span_id={s.sid}" in ev.as_kv()

    def test_recovery_ids_none_when_disabled(self):
        from sparkdq4ml_tpu.utils.recovery import RECOVERY_LOG

        RECOVERY_LOG.clear()
        RECOVERY_LOG.record("test_site", "retry")
        ev = RECOVERY_LOG.events(site="test_site")[-1]
        assert ev.trace_id is None and ev.span_id is None

    def test_prometheus_help_and_sanitization(self):
        profiling.counters.increment("pipeline.hit", by=3)
        obs.METRICS.set_gauge("mem.live_bytes", 42)
        text = obs.prometheus_text()
        lines = text.splitlines()
        i = lines.index("# TYPE sparkdq4ml_pipeline_hit counter")
        assert lines[i - 1].startswith(
            "# HELP sparkdq4ml_pipeline_hit pipeline.hit - ")
        assert "sparkdq4ml_mem_live_bytes 42" in text
        # every TYPE line is preceded by a HELP line for the same metric
        for j, ln in enumerate(lines):
            if ln.startswith("# TYPE "):
                name = ln.split()[2]
                assert lines[j - 1].startswith(f"# HELP {name} ")


# ---------------------------------------------------------------------------
# Satellite: host-sync audit (window / stat / evaluation)
# ---------------------------------------------------------------------------


class TestHostSyncAudit:
    def _frame(self):
        f = Frame({"g": [1, 1, 2, 2], "v": [1.0, 3.0, 2.0, 4.0]})
        f.count()                      # materialize outside the window
        return f

    def test_window_eval_counts_one_sync(self):
        from sparkdq4ml_tpu.frame.window import Window, row_number

        f = self._frame()
        w = Window.partition_by("g").order_by("v")
        profiling.counters.clear("frame.host_sync")
        f.with_column("rn", row_number().over(w))._data  # force eval
        assert profiling.counters.get("frame.host_sync") == 1

    def test_stat_corr_cov_count_one_each(self):
        f = self._frame()
        profiling.counters.clear("frame.host_sync")
        f.stat.corr("g", "v")
        assert profiling.counters.get("frame.host_sync") == 1
        f.stat.cov("g", "v")
        assert profiling.counters.get("frame.host_sync") == 2

    def test_stat_approx_quantile_counts_one(self):
        f = self._frame()
        profiling.counters.clear("frame.host_sync")
        f.stat.approx_quantile("v", [0.5])
        assert profiling.counters.get("frame.host_sync") == 1

    def test_stat_sample_by_counts_one_for_device_column(self):
        f = self._frame()
        profiling.counters.clear("frame.host_sync")
        f.stat.sample_by("g", {1: 1.0, 2: 0.0}, seed=1)
        assert profiling.counters.get("frame.host_sync") == 1

    def test_evaluation_device_inputs_counted(self):
        from sparkdq4ml_tpu.models.evaluation import area_under_roc

        labels = jnp.asarray([0.0, 1.0, 1.0, 0.0])
        scores = jnp.asarray([0.1, 0.8, 0.7, 0.3])
        profiling.counters.clear("frame.host_sync")
        auc = area_under_roc(labels, scores)
        assert auc == pytest.approx(1.0)
        assert profiling.counters.get("frame.host_sync") == 1

    def test_evaluation_host_inputs_free(self):
        from sparkdq4ml_tpu.models.evaluation import area_under_roc

        labels = np.asarray([0.0, 1.0, 1.0, 0.0])
        scores = np.asarray([0.1, 0.8, 0.7, 0.3])
        profiling.counters.clear("frame.host_sync")
        area_under_roc(labels, scores)
        assert profiling.counters.get("frame.host_sync") == 0


# ---------------------------------------------------------------------------
# Disabled-mode no-op pinning for the new collectors
# ---------------------------------------------------------------------------


class TestDisabledModeNoOp:
    def test_default_query_records_nothing_new(self, session):
        _views(session)
        assert not obs.TRACER.enabled
        before = profiling.counters.get("frame.host_sync")
        out = session.sql("SELECT a FROM t WHERE a > 1")
        out.count()
        assert obs.TRACER.spans() == []
        assert obs.TRACER.mem_sample is False
        assert obs.METRICS.snapshot().get("mem.live_bytes") is None
        assert profiling.counters.get("trace.dropped_spans") == 0
        # the default path added zero host syncs (count() is a device
        # reduction + scalar pull the engine does NOT count as a frame
        # host boundary — unchanged from the seed contract)
        assert profiling.counters.get("frame.host_sync") == before

    def test_query_stats_restores_disabled_state(self):
        assert not obs.TRACER.enabled
        with obs.query_stats(sample_memory=True) as qs:
            assert obs.TRACER.enabled
            assert obs.TRACER.mem_sample
            with obs.span("inside", cat="t"):
                pass
        assert not obs.TRACER.enabled
        assert not obs.TRACER.mem_sample
        assert [s.name for s in qs.spans] == ["inside"]
        assert qs.counter_delta().get("nonexistent") is None

    def test_query_stats_nested_in_enabled_session(self):
        obs.enable()
        with obs.query_stats(sample_memory=False):
            pass
        assert obs.TRACER.enabled     # outer enablement preserved

    def test_concurrent_collectors_are_thread_scoped(self):
        """Two threads' collectors must not pollute each other, and the
        first to exit must not disable tracing under the second."""
        import threading

        results = {}
        gate_a_in = threading.Event()
        gate_a_out = threading.Event()

        def slow_query():
            with obs.query_stats(sample_memory=False) as qs:
                gate_a_in.set()
                gate_a_out.wait(timeout=10)   # outlive the fast query
                with obs.span("slow.op", cat="t"):
                    pass
                results["slow_enabled_mid"] = obs.TRACER.enabled
            results["slow"] = [s.name for s in qs.spans]

        def fast_query():
            gate_a_in.wait(timeout=10)
            with obs.query_stats(sample_memory=False) as qs:
                with obs.span("fast.op", cat="t"):
                    pass
            results["fast"] = [s.name for s in qs.spans]
            gate_a_out.set()

        ta = threading.Thread(target=slow_query)
        tb = threading.Thread(target=fast_query)
        ta.start(); tb.start()
        ta.join(timeout=20); tb.join(timeout=20)
        assert results["fast"] == ["fast.op"]
        assert results["slow"] == ["slow.op"]     # no cross-pollution
        assert results["slow_enabled_mid"] is True  # fast exit ≠ disable
        assert not obs.TRACER.enabled             # last one out restores
