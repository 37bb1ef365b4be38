"""Device-cost observatory suite (ISSUE 15, tier-1, ``costprof`` marker).

Tentpole coverage: the AOT cost extractor (``analysis/program/costs.py``
— flops/bytes monotone in rows, per-collective bytes scaling with the
mesh, zero counted compiles/syncs during extraction), the per-key
profile cache + statstore persistence (``utils/costprof.py``), EXPLAIN
ANALYZE cost columns on the headline DQ+Lasso workload with goldens
unchanged, roofline verdict sanity (memory-bound elementwise chain vs
compute-bound Gramian, sync/host arms), the shard-skew gauge and
exchange-volume counters, the ``/profile`` + ``/profile/trace`` HTTP
routes with managed-capture retention, the ``cost_profile`` fault-site
degradation ladder, the ``program-handle`` dqlint rule, and the
disabled-mode pins (``spark.costprof.enabled=false`` = one flag read,
byte-identical pre-observatory EXPLAIN output).
"""

from __future__ import annotations

import json
import os
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

import sparkdq4ml_tpu as dq
from sparkdq4ml_tpu.analysis.program import costs as prog_costs
from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame.frame import Frame
from sparkdq4ml_tpu.serve import TelemetryServer
from sparkdq4ml_tpu.utils import costprof, faults
from sparkdq4ml_tpu.utils import observability as obs
from sparkdq4ml_tpu.utils import profiling, statstore
from sparkdq4ml_tpu.utils.observability import ProgramHandle
from sparkdq4ml_tpu.utils.recovery import RECOVERY_LOG

from conftest import dataset_path, prepare_features, run_dq_pipeline

pytestmark = pytest.mark.costprof


@pytest.fixture(autouse=True)
def _clean_costprof_state():
    """Profile cache, statstore, chaos plan, and conf are process-global."""
    costprof.clear()
    statstore.STORE.clear()
    faults.clear()
    RECOVERY_LOG.clear()
    profiling.counters.clear("costprof.")
    profiling.counters.clear("shard.exchange_bytes")
    saved = (config.costprof_enabled, config.costprof_ridge,
             config.profiling_max_captures, config.stats_enabled)
    yield
    obs.disable()
    (config.costprof_enabled, config.costprof_ridge,
     config.profiling_max_captures, config.stats_enabled) = saved
    costprof.clear()
    statstore.STORE.clear()
    faults.clear()
    RECOVERY_LOG.clear()


def _get(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def _elementwise_handle(n: int, steps: int = 6,
                        key: str = "ew") -> ProgramHandle:
    """A memory-bound chain: O(1) flops per byte moved."""
    def body(x):
        for i in range(steps):
            x = x * 1.5 + float(i)
        return x

    spec = jax.ShapeDtypeStruct((n,), np.float32)
    return ProgramHandle("test", f"{key}|n={n}", body, args=(spec,))


def _gram_handle(n: int, d: int, key: str = "gram") -> ProgramHandle:
    """A compute-bound Gramian: O(d) flops per byte at n >> d."""
    def body(x):
        return x.T @ x

    spec = jax.ShapeDtypeStruct((n, d), np.float32)
    return ProgramHandle("test", f"{key}|{n}x{d}", body, args=(spec,))


def _psum_handle(devices: int, n: int = 1024) -> ProgramHandle:
    from jax.sharding import PartitionSpec as P

    from sparkdq4ml_tpu.parallel.mesh import (DATA_AXIS, make_mesh,
                                              shard_map)

    mesh = make_mesh(devices=jax.devices()[:devices])

    def local(x):
        return jax.lax.psum(x.sum(), DATA_AXIS)

    fn = shard_map(local, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P())
    spec = jax.ShapeDtypeStruct((n,), np.float32)
    return ProgramHandle("test", f"psum|{devices}", fn, args=(spec,),
                         mesh=mesh, guarded=True)


# ---------------------------------------------------------------------------
# Extractor unit pins
# ---------------------------------------------------------------------------


class TestExtractor:
    def test_profile_fields_present(self):
        doc = prog_costs.extract(_elementwise_handle(4096))
        assert doc is not None
        assert doc["flops"] > 0
        assert doc["bytes_accessed"] > 0
        assert doc["output_bytes"] > 0
        assert doc["devices"] == 1
        assert doc["extract_ms"] >= 0

    def test_flops_and_bytes_monotone_in_rows(self):
        small = prog_costs.extract(_elementwise_handle(1024))
        big = prog_costs.extract(_elementwise_handle(8192))
        assert big["flops"] > small["flops"]
        assert big["bytes_accessed"] > small["bytes_accessed"]
        assert big["output_bytes"] > small["output_bytes"]

    def test_transcendentals_counted(self):
        def body(x):
            return jax.numpy.exp(x)

        h = ProgramHandle("test", "exp", body,
                          args=(jax.ShapeDtypeStruct((512,), np.float32),))
        doc = prog_costs.extract(h)
        assert doc["transcendentals"] >= 512

    @pytest.mark.skipif(len(jax.devices()) < 8,
                        reason="needs 8 forced host devices")
    def test_collective_bytes_match_mesh_size(self):
        d4 = prog_costs.collective_bytes(_psum_handle(4))
        d8 = prog_costs.collective_bytes(_psum_handle(8))
        assert "psum" in d4 and "psum" in d8
        # a scalar psum's aggregate payload is itemsize x devices
        assert d8["psum"] == 2 * d4["psum"]
        doc = prog_costs.extract(_psum_handle(8))
        assert doc["collectives"]["psum"] == d8["psum"]
        assert doc["devices"] == 8

    def test_extraction_counts_no_compiles_no_syncs(self):
        """The acceptance pin: extraction performs zero counted host
        syncs and zero counted compiles — it targets the UN-counted
        trace bodies, and nothing executes on device."""
        session = dq.TpuSession.builder().app_name(
            "costprof-pin").master("local[*]").get_or_create()
        try:
            f = Frame({"v": np.arange(512, dtype=np.float64)})
            f.create_or_replace_temp_view("cp_pin")
            session.sql("SELECT v * 2 AS w FROM cp_pin WHERE v > 10") \
                .count()
            session.sql("SELECT v, count(*) c FROM cp_pin GROUP BY v") \
                .count()
            costprof.clear()
            before = {k: profiling.counters.get(k) for k in (
                "frame.host_sync", "pipeline.compile", "pipeline.hit",
                "grouped.compile", "grouped.hit", "stats.drain_sync")}
            out = costprof.extract_all(budget=100)
            assert any(v["profile"] is not None for v in out.values())
            for k, v in before.items():
                assert profiling.counters.get(k) == v, k
        finally:
            session.stop()


# ---------------------------------------------------------------------------
# Roofline verdicts + achieved throughput
# ---------------------------------------------------------------------------


class TestRoofline:
    def test_elementwise_chain_is_memory_bound(self):
        prof = costprof.CostProfile.from_doc(
            prog_costs.extract(_elementwise_handle(65536)))
        assert prof.intensity < config.costprof_ridge
        assert costprof.roofline(prof) == "memory"

    def test_gramian_is_compute_bound(self):
        prof = costprof.CostProfile.from_doc(
            prog_costs.extract(_gram_handle(4096, 64)))
        assert prof.intensity >= config.costprof_ridge
        assert costprof.roofline(prof) == "compute"

    def test_ridge_conf_moves_the_verdict(self):
        prof = costprof.CostProfile.from_doc(
            prog_costs.extract(_gram_handle(4096, 64)))
        config.costprof_ridge = 1e9
        assert costprof.roofline(prof) == "memory"

    def test_sync_bound_tiny_program_with_sync(self):
        prof = costprof.CostProfile(flops=10.0, bytes_accessed=64.0)
        assert costprof.roofline(prof, host_syncs=1) == "sync"
        assert costprof.roofline(prof, host_syncs=0) == "memory"

    def test_host_verdict_without_profile(self):
        assert costprof.roofline(None) == "host"

    def test_achieved_throughput(self):
        prof = costprof.CostProfile(flops=2e9, bytes_accessed=1e9)
        gflops, gbps = costprof.achieved(prof, wall_ms=1000.0)
        assert gflops == pytest.approx(2.0)
        assert gbps == pytest.approx(1.0)
        assert costprof.achieved(prof, None) == (None, None)
        assert costprof.achieved(None, 5.0) == (None, None)


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE cost columns (headline workload, goldens pinned)
# ---------------------------------------------------------------------------


#: The second headline DQ filter — the view ``run_dq_pipeline`` leaves
#: registered holds the second-stage frame, so this is the statement an
#: EXPLAIN ANALYZE can replay against it.
HEADLINE_DQ2 = ("SELECT guest, price_correct_correl AS price "
                "FROM price WHERE price_correct_correl > 0")


class TestExplainCostColumns:
    def test_headline_analyze_renders_cost_columns_goldens_unchanged(
            self, session):
        df = run_dq_pipeline(session, dataset_path("abstract"))
        assert df.count() == 24                       # golden
        plan = session.sql("EXPLAIN ANALYZE " + HEADLINE_DQ2) \
            .to_pydict()["plan"][0]
        assert "est_flops=" in plan
        assert "est_bytes=" in plan
        assert "gflops=" in plan and "gbps=" in plan
        assert "bound=" in plan
        # the fused stage ran a device program: a real verdict, not "-"
        fused = next(ln for ln in plan.splitlines()
                     if ln.startswith("FusedStage"))
        assert "bound=memory" in fused or "bound=compute" in fused \
            or "bound=sync" in fused
        assert "est_flops=-" not in fused
        # golden model numbers stay exact with the observatory on
        from sparkdq4ml_tpu.models import LinearRegression

        model = LinearRegression(max_iter=40, reg_param=1.0,
                                 elastic_net_param=1.0).fit(
            prepare_features(df))
        assert float(model.summary.root_mean_squared_error) == \
            pytest.approx(2.809940, rel=1e-3)

    def test_grouped_node_gets_cost_columns(self, session):
        f = Frame({"k": (np.arange(2048) % 8).astype(np.float64),
                   "v": np.arange(2048, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_g")
        session.sql("SELECT k, sum(v) s FROM cp_g GROUP BY k").count()
        plan = session.sql(
            "EXPLAIN ANALYZE SELECT k, sum(v) s FROM cp_g GROUP BY k") \
            .to_pydict()["plan"][0]
        seg = next(ln for ln in plan.splitlines()
                   if ln.lstrip("+- ").startswith("SegmentedAggregate"))
        assert "est_flops=" in seg and "bound=" in seg
        assert "est_flops=-" not in seg

    def test_disabled_mode_restores_pre_observatory_output(
            self, session, monkeypatch):
        f = Frame({"v": np.arange(256, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_off")
        sql = "EXPLAIN ANALYZE SELECT v * 3 AS w FROM cp_off WHERE v > 5"
        session.sql(sql)                    # warm plans either way
        config.costprof_enabled = False
        # one-flag-read pin: with the observatory off, none of its
        # machinery may run at all
        monkeypatch.setattr(costprof, "profile_for", _raise_hook)
        monkeypatch.setattr(costprof, "report", _raise_hook)
        plan = session.sql(sql).to_pydict()["plan"][0]
        for key in ("est_flops", "est_bytes", "gflops", "gbps", "bound="):
            assert key not in plan
        config.costprof_enabled = True
        plan_on = session.sql(sql).to_pydict()["plan"][0]
        assert "bound=" in plan_on          # flag flips it back on


def _raise_hook(*a, **kw):
    raise AssertionError("costprof hook ran in disabled mode")


# ---------------------------------------------------------------------------
# Cardinality history (satellite: aggregates no longer estimate blind)
# ---------------------------------------------------------------------------


class TestCardinalityHistory:
    def test_group_by_est_rows_from_history(self, session):
        f = Frame({"k": (np.arange(4096) % 16).astype(np.float64),
                   "v": np.arange(4096, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_card")
        sql = "SELECT k, count(*) c FROM cp_card GROUP BY k"
        cold = session.sql("EXPLAIN " + sql).to_pydict()["plan"][0]
        agg_cold = next(ln for ln in cold.splitlines()
                        if ln.startswith(("SegmentedAggregate",
                                          "Aggregate")))
        assert "est_rows=-" in agg_cold     # blind before history
        session.sql(sql).count()            # record the cardinality
        warm = session.sql("EXPLAIN " + sql).to_pydict()["plan"][0]
        agg_warm = next(ln for ln in warm.splitlines()
                        if ln.startswith(("SegmentedAggregate",
                                          "Aggregate")))
        assert "est_rows=16" in agg_warm

    def test_distinct_est_rows_from_history(self, session):
        f = Frame({"k": (np.arange(2048) % 32).astype(np.float64)})
        f.create_or_replace_temp_view("cp_dcard")
        sql = "SELECT DISTINCT k FROM cp_dcard"
        session.sql(sql).count()
        plan = session.sql("EXPLAIN " + sql).to_pydict()["plan"][0]
        dist = next(ln for ln in plan.splitlines()
                    if ln.startswith("Distinct"))
        assert "est_rows=32" in dist

    def test_cardinality_key_is_order_insensitive(self):
        from sparkdq4ml_tpu.ops import segments

        a = np.arange(8, dtype=np.float32)
        b = np.arange(8, dtype=np.int32)
        k1 = segments.cardinality_history_key("g", ["x", "y"], [a, b])
        k2 = segments.cardinality_history_key("g", ["y", "x"], [b, a])
        assert k1 == k2
        assert segments.cardinality_history_key(
            "g", ["x"], [np.array(["s"], dtype=object)]) is None


# ---------------------------------------------------------------------------
# Profile cache + statstore persistence
# ---------------------------------------------------------------------------


class TestPersistence:
    def test_statstore_roundtrip_of_cost_profiles(self, tmp_path):
        doc = {"flops": 123.0, "bytes_accessed": 456.0,
               "output_bytes": 7.0, "devices": 2,
               "collectives": {"psum": 64}, "peak_bytes": 2048}
        statstore.STORE.record_cost("K1", "cost:test", doc)
        path = str(tmp_path / "stats.jsonl")
        assert statstore.STORE.save(path)
        fresh = statstore.StatStore()
        assert fresh.load(path) >= 1
        got = fresh.cost("K1")
        assert got is not None
        assert got["flops"] == 123.0
        assert got["collectives"] == {"psum": 64}

    def test_cost_survives_winner_merge(self):
        with_cost = statstore.KeyStats("K", "pipeline")
        with_cost.cost = {"flops": 5.0}
        heavier = statstore.KeyStats("K", "pipeline")
        heavier.flushes = 50                 # more evidence, no cost
        target: dict = {}
        statstore.StatStore._merge_into(target, [with_cost])
        statstore.StatStore._merge_into(target, [heavier])
        assert target["K"].cost == {"flops": 5.0}
        # and the reverse order keeps it too
        target2: dict = {}
        statstore.StatStore._merge_into(target2, [heavier])
        statstore.StatStore._merge_into(target2, [with_cost])
        assert target2["K"].cost == {"flops": 5.0}

    def test_profile_for_adopts_persisted_doc_without_extraction(
            self, monkeypatch):
        statstore.STORE.record_cost(
            "PK", "cost:test", {"flops": 9.0, "bytes_accessed": 90.0})
        monkeypatch.setattr(costprof, "_extract", _raise_hook)
        prof = costprof.profile_for("PK")
        assert prof is not None and prof.flops == 9.0

    def test_bytes_bound_folds_cost_peak(self):
        s = statstore.StatStore()
        s.record_flush("K", "pipeline", est_bytes=100)
        assert s.bytes_bound("K") == 100
        s.record_cost("K", "cost:test", {"peak_bytes": 5000})
        assert s.bytes_bound("K") == 5000

    def test_extraction_records_into_statstore(self, session):
        f = Frame({"v": np.arange(512, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_rec")
        session.sql("SELECT v + 1 AS w FROM cp_rec WHERE v > 3").count()
        out = costprof.extract_all(budget=100)
        keys = [k for k, v in out.items()
                if v["cache"] == "pipeline" and v["profile"] is not None]
        assert keys
        assert statstore.STORE.cost(keys[0]) is not None


# ---------------------------------------------------------------------------
# Fault-site ladder
# ---------------------------------------------------------------------------


class TestFaultLadder:
    def test_cost_profile_site_registered(self):
        assert "cost_profile" in faults.FAULT_SITES

    def test_injected_fault_degrades_to_unprofiled(self, session):
        f = Frame({"v": np.arange(512, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_fault")
        session.sql("SELECT v - 1 AS w FROM cp_fault WHERE v > 2").count()
        handles, _ = obs.CACHES.programs()
        key = next(h.program_key for h in handles
                   if h.cache == "pipeline")
        statstore.STORE.clear()              # no persisted shortcut
        before = profiling.counters.get("costprof.failed")
        with faults.inject_faults("cost_profile:device_error:1"):
            assert costprof.profile_for(key) is None
        assert profiling.counters.get("costprof.failed") == before + 1
        events = [e for e in RECOVERY_LOG.events()
                  if e.site == "cost_profile"]
        assert events and events[-1].action == "fallback"
        # the failure is cached: no re-extraction storm per scrape
        assert costprof.profile_for(key) is None
        # a fresh cache re-earns the profile once chaos stops
        costprof.clear()
        assert costprof.profile_for(key) is not None

    def test_report_survives_extraction_faults(self, session):
        f = Frame({"v": np.arange(512, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_fsurv")
        session.sql("SELECT v * 4 AS w FROM cp_fsurv WHERE v > 1").count()
        statstore.STORE.clear()
        with faults.inject_faults("cost_profile:device_error:p=1.0"):
            doc = costprof.report()
        assert doc["enabled"] is True
        assert all(r["flops"] is None for r in doc["entries"])


# ---------------------------------------------------------------------------
# Shard skew + exchange volume
# ---------------------------------------------------------------------------


class TestShardCost:
    def test_skew_gauge_under_forced_imbalance(self):
        from sparkdq4ml_tpu.parallel import shard

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 forced host devices")
        from sparkdq4ml_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(devices=jax.devices()[:8])
        balanced = shard.ShardedStore(mesh, rows=4096, bucket=512)
        shard.record_skew(balanced)
        assert obs.METRICS.get_gauge("shard.skew") == pytest.approx(1.0)
        lopsided = shard.ShardedStore(mesh, rows=513, bucket=512)
        shard.record_skew(lopsided)
        # worst shard holds 512 of 513 rows: ~8x the mean
        assert obs.METRICS.get_gauge("shard.skew") == pytest.approx(
            512 / (513 / 8), rel=1e-3)

    def test_exchange_counter_families(self):
        from sparkdq4ml_tpu.parallel.shard import record_exchange

        base = profiling.counters.get("shard.exchange_bytes")
        record_exchange("gather", 1000)
        record_exchange("psum", 24)
        assert profiling.counters.get("shard.exchange_bytes") \
            == base + 1024
        assert profiling.counters.get("shard.exchange_bytes.gather") \
            >= 1000
        assert profiling.counters.get("shard.exchange_bytes.psum") >= 24

    def test_exchange_disabled_is_noop(self):
        from sparkdq4ml_tpu.parallel.shard import record_exchange

        config.costprof_enabled = False
        base = profiling.counters.get("shard.exchange_bytes")
        record_exchange("gather", 4096)
        assert profiling.counters.get("shard.exchange_bytes") == base

    def test_metric_families_registered(self):
        assert "shard.skew" in obs.METRIC_NAMES
        assert "shard.exchange_bytes" in obs.METRIC_NAMES
        assert "shard.exchange_bytes." in obs.METRIC_NAME_PREFIXES
        assert "costprof." in obs.METRIC_NAME_PREFIXES
        assert "costprof.extracted" in obs.METRIC_NAMES
        assert "costprof.failed" in obs.METRIC_NAMES


# ---------------------------------------------------------------------------
# HTTP routes
# ---------------------------------------------------------------------------


class TestProfileRoutes:
    def test_profile_route_schema(self, session):
        f = Frame({"v": np.arange(1024, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_http")
        session.sql("SELECT v / 2 AS w FROM cp_http WHERE v > 7").count()
        with TelemetryServer(port=0) as ts:
            code, body = _get(
                f"http://127.0.0.1:{ts.port}/profile?top=4")
            assert code == 200
            doc = json.loads(body)
            for key in ("enabled", "entries", "size", "pending",
                        "capture", "skew", "exchange_bytes",
                        "ridge_flops_per_byte"):
                assert key in doc, key
            assert doc["enabled"] is True
            assert doc["entries"]
            row = doc["entries"][0]
            for key in ("key", "cache", "flops", "bytes", "gflops",
                        "gbps", "bound", "device_time_share",
                        "collectives"):
                assert key in row, key

    def test_profile_route_disabled_pin(self, monkeypatch):
        config.costprof_enabled = False
        monkeypatch.setattr(costprof, "report", _raise_hook)
        with TelemetryServer(port=0) as ts:
            code, body = _get(f"http://127.0.0.1:{ts.port}/profile")
        assert code == 200
        assert json.loads(body) == {"enabled": False, "entries": []}

    def test_profile_trace_arms_and_rejects_concurrent(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("SPARKDQ4ML_CAPTURE_DIR", str(tmp_path))
        with TelemetryServer(port=0) as ts:
            base = f"http://127.0.0.1:{ts.port}"
            code, body = _get(base + "/profile/trace?seconds=5&label=t1")
            assert code == 200
            doc = json.loads(body)
            assert doc["armed"] is True
            assert os.path.isdir(doc["path"])
            assert "-t1" in doc["path"]
            # one capture at a time: the second arm answers 409
            try:
                _get(base + "/profile/trace?seconds=1")
                raise AssertionError("expected 409")
            except urllib.error.HTTPError as e:
                assert e.code == 409
            finally:
                profiling.stop_capture()
            # /profile surfaces the newest capture path
            code, body = _get(base + "/profile")
            assert json.loads(body)["capture"] == doc["path"]

    def test_capture_retention_bounded(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SPARKDQ4ML_CAPTURE_DIR", str(tmp_path))
        config.profiling_max_captures = 2
        for i in range(5):
            os.makedirs(tmp_path / f"cap-2026010{i}-000000-1-x")
        assert profiling.prune_captures() == 3
        assert len(profiling.captures()) == 2
        # newest survive
        assert profiling.latest_capture().endswith("cap-20260104-000000-1-x")


# ---------------------------------------------------------------------------
# session.profile_report + disabled-mode pins
# ---------------------------------------------------------------------------


class TestProfileReport:
    def test_report_rows_join_statstore(self, session):
        f = Frame({"v": np.arange(2048, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_rep")
        sql = "SELECT v * 2 AS w FROM cp_rep WHERE v > 100"
        for _ in range(3):
            session.sql(sql).count()
        doc = session.profile_report()
        for _ in range(32):              # budgeted extraction refills
            if not doc["pending"]:
                break
            doc = session.profile_report()
        assert doc["enabled"] is True and doc["size"] >= 1
        assert not doc["pending"]
        # the one plan with recorded wall mass ranks first by share
        row = doc["entries"][0]
        assert row["cache"] == "pipeline"
        assert row["device_time_share"] == pytest.approx(1.0)
        assert row["bound"] in ("compute", "memory", "sync")
        assert row["flushes"] >= 3
        assert row["wall_ms_p50"] is not None
        assert row["gflops"] is not None and row["gbps"] is not None
        shares = [r["device_time_share"] for r in doc["entries"]
                  if r["device_time_share"] is not None]
        assert shares == sorted(shares, reverse=True)

    def test_grouped_rows_join_wall_history(self, session):
        """Review regression: grouped flushes record statstore history
        under the struct key ('G|...'), not the per-lowering cache key —
        the report must join through the producer-declared stats_key or
        every grouped plan reads flushes=0 / throughput None."""
        f = Frame({"k": (np.arange(2048) % 8).astype(np.float64),
                   "v": np.arange(2048, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_gjoin")
        sql = "SELECT k, sum(v) s FROM cp_gjoin GROUP BY k"
        for _ in range(3):
            session.sql(sql).count()
        doc = costprof.report(budget=100)
        grouped = [r for r in doc["entries"]
                   if r["cache"] == "grouped" and r["flushes"] >= 3]
        assert grouped, doc["entries"]
        assert grouped[0]["wall_ms_p50"] is not None
        assert grouped[0]["gflops"] is not None

    def test_pending_rows_are_not_verdicted_host(self, session):
        """Review regression: a budget-exhausted (pending) or degraded
        entry is still a device program — its bound must render null,
        never the roofline's 'host' verdict."""
        f = Frame({"v": np.arange(512, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_pend")
        for _ in range(2):
            session.sql("SELECT v * 9 AS w FROM cp_pend WHERE v > 4") \
                .count()
        doc = costprof.report(budget=0)
        assert doc["pending"] >= 1
        for r in doc["entries"]:
            if r["pending"]:
                assert r["bound"] is None

    def test_capture_timer_bound_to_its_own_capture(
            self, tmp_path, monkeypatch):
        """Review regression: a stale stop timer from an earlier capture
        must not truncate a newer one."""
        import time

        monkeypatch.setenv("SPARKDQ4ML_CAPTURE_DIR", str(tmp_path))
        path_a = profiling.start_capture(0.1, label="a")
        assert profiling.stop_capture() == path_a     # manual stop
        path_b = profiling.start_capture(60, label="b")
        try:
            # a's timer (and an explicit stale-expected stop) are no-ops
            assert profiling.stop_capture(expected=path_a) is None
            time.sleep(0.3)
            assert profiling.capture_active() == path_b
        finally:
            assert profiling.stop_capture() == path_b

    def test_report_refuses_when_disabled(self, session, monkeypatch):
        config.costprof_enabled = False
        monkeypatch.setattr(costprof, "report", _raise_hook)
        doc = session.profile_report()
        assert doc == {"enabled": False, "entries": [], "size": 0,
                       "pending": 0}

    def test_extraction_budget_leaves_pending(self, session):
        f = Frame({"v": np.arange(256, dtype=np.float64)})
        f.create_or_replace_temp_view("cp_bud")
        session.sql("SELECT v + 2 AS a FROM cp_bud WHERE v > 1").count()
        session.sql("SELECT v, max(v) m FROM cp_bud GROUP BY v").count()
        out = costprof.extract_all(budget=0)
        assert out and all(v["pending"] for v in out.values()
                           if v["profile"] is None)
        out2 = costprof.extract_all(budget=100)
        assert any(v["profile"] is not None for v in out2.values())

    def test_costprof_conf_keys_session_scoped(self):
        s = (dq.TpuSession.builder().app_name("cp-conf")
             .master("local[*]")
             .config("spark.costprof.enabled", "false")
             .config("spark.costprof.ridge", "32.5")
             .config("spark.profiling.maxCaptures", "7")
             .get_or_create())
        try:
            assert config.costprof_enabled is False
            assert config.costprof_ridge == 32.5
            assert config.profiling_max_captures == 7
        finally:
            s.stop()
        assert config.costprof_enabled is True     # restored


# ---------------------------------------------------------------------------
# dqlint program-handle rule
# ---------------------------------------------------------------------------


class TestProgramHandleRule:
    @staticmethod
    def _run(text: str):
        from sparkdq4ml_tpu.analysis.core import SourceFile
        from sparkdq4ml_tpu.analysis.rules.program_handles import (
            ProgramHandleRule)

        src = SourceFile("x.py", "sparkdq4ml_tpu/x.py", text=text)
        rule = ProgramHandleRule()
        return [f for f in rule.visit(src) if f is not None]

    def test_register_without_programs_flagged(self):
        findings = self._run(
            "CACHES.register('mycache', stats_fn)\n")
        assert findings and "register_programs" in findings[0].message

    def test_register_with_programs_sanctioned(self):
        findings = self._run(
            "CACHES.register('mycache', stats_fn)\n"
            "CACHES.register_programs('mycache', programs_fn)\n")
        assert not findings

    def test_unrelated_registry_ignored(self):
        findings = self._run("router.register('x', handler)\n")
        assert not findings

    def test_counted_fn_entry_flagged(self):
        findings = self._run(
            "h = ProgramHandle('c', 'k', entry.fn, args=())\n")
        assert findings and "COUNTED" in findings[0].message

    def test_trace_body_sanctioned(self):
        findings = self._run(
            "h = ProgramHandle('c', 'k', entry.trace_body, args=())\n")
        assert not findings

    def test_missing_fn_flagged(self):
        findings = self._run("h = ProgramHandle('c', 'k')\n")
        assert findings and "untraceable" in findings[0].message

    def test_rule_in_catalog(self):
        from sparkdq4ml_tpu.analysis.rules import ALL_RULES, get_rules

        names = [c.name for c in ALL_RULES]
        assert "program-handle" in names
        assert get_rules(["program-handle"])
