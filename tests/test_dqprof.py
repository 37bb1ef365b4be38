"""Data-quality observatory suite (tier-1, ``dqprof`` marker).

Tentpole coverage: the column-profile sketch algebra
(``utils/dqprof.py`` — raw-moment decode, Welford/Chan merge
associativity, fixed histogram bucket edges, null/NaN arms, empty-column
sentinels), decomposable shard-merge parity vs single-device, the
zero-added-sync contract (deferred sketches, one counted cold-path
drain) and the disabled-mode raise-monkeypatch pins, statstore baseline
persistence (round-trip + winner-merge keeps profiles), the drift
scorer's threshold flip (gauge + incident bundle + tail-sampler
keep-reason), per-rule violation accounting on the eager UDF path,
the ``dq_profile`` fault-site degradation ladder, the ``/dq`` HTTP
route schema + disabled pin, and the ``== Data Quality ==`` EXPLAIN
ANALYZE section with the headline goldens (24 rows / RMSE 2.8099)
unchanged.
"""

from __future__ import annotations

import json
import types
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

import sparkdq4ml_tpu as dq
from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame.frame import Frame
from sparkdq4ml_tpu.serve import TelemetryServer
from sparkdq4ml_tpu.utils import dqprof, faults, incidents
from sparkdq4ml_tpu.utils import observability as obs
from sparkdq4ml_tpu.utils import profiling, statstore
from sparkdq4ml_tpu.utils.recovery import RECOVERY_LOG

from conftest import dataset_path, prepare_features, run_dq_pipeline

pytestmark = pytest.mark.dqprof


@pytest.fixture(autouse=True)
def _clean_dqprof_state():
    """Profiles, statstore, chaos plan, recorder, and conf are
    process-global."""
    dqprof.clear()
    statstore.STORE.clear()
    faults.clear()
    RECOVERY_LOG.clear()
    profiling.counters.clear("dq.")
    obs.METRICS.clear()
    incidents.RECORDER.reset()
    incidents.RECORDER.configure(enabled=False, directory="",
                                 max_bundles=32, cooldown_s=5.0,
                                 slo_burn_threshold=8.0)
    saved = (config.dq_profile_enabled, config.dq_histogram_bins,
             config.dq_drift_threshold, config.dq_baseline_mode,
             config.stats_enabled)
    yield
    obs.disable()
    (config.dq_profile_enabled, config.dq_histogram_bins,
     config.dq_drift_threshold, config.dq_baseline_mode,
     config.stats_enabled) = saved
    dqprof.clear()
    statstore.STORE.clear()
    faults.clear()
    RECOVERY_LOG.clear()
    incidents.RECORDER.reset()
    incidents.RECORDER.configure(enabled=False, directory="",
                                 max_bundles=32, cooldown_s=5.0,
                                 slo_burn_threshold=8.0)


def _get(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def _profile_of(values, bins: int = 32,
                mask=None) -> dqprof.ColumnProfile:
    """One drained single-device sketch of ``values``."""
    x = np.asarray(values, dtype=np.float64)
    m = (np.ones(x.shape, bool) if mask is None
         else np.asarray(mask, bool))
    raw = jax.device_get(dqprof._sketch_body(bins)(
        jax.numpy.asarray(x), jax.numpy.asarray(m)))
    prof = dqprof.ColumnProfile.from_raw(raw)
    assert prof is not None
    return prof


def _flush_chain(frame) -> int:
    """A fused 3-column arithmetic chain + filter, forced to execute."""
    f = frame
    for i in range(3):
        f = f.with_column(f"c{i}", dq.col("v") * float(i + 1) + 0.5)
    f = f.filter(dq.col("c2") > 0)
    return int(f.count())


# ---------------------------------------------------------------------------
# Sketch units: raw-moment decode, merge algebra, histogram, NaN arms
# ---------------------------------------------------------------------------


class TestSketchUnits:
    def test_device_sketch_matches_numpy(self):
        vals = np.linspace(-50.0, 200.0, 400)
        p = _profile_of(vals)
        assert p.count == 400 and p.nulls == 0
        assert p.mean == pytest.approx(vals.mean(), rel=1e-5)
        assert p.variance == pytest.approx(vals.var(ddof=1), rel=1e-4)
        assert p.min == pytest.approx(vals.min())
        assert p.max == pytest.approx(vals.max())
        assert sum(p.hist) == 400 and len(p.hist) == 32

    def test_welford_merge_associative(self):
        rng = np.random.default_rng(11)
        a, b, c = (rng.normal(loc=m, scale=3.0, size=257)
                   for m in (0.0, 5.0, -2.0))
        pa, pb, pc = (_profile_of(v) for v in (a, b, c))
        left = pa.copy()
        left.merge(pb)
        left.merge(pc)                       # (a + b) + c
        right = pb.copy()
        right.merge(pc)
        merged = pa.copy()
        merged.merge(right)                  # a + (b + c)
        whole = np.concatenate([a, b, c])
        for p in (left, merged):
            assert p.count == whole.size
            assert p.mean == pytest.approx(whole.mean(), rel=1e-5)
            assert p.variance == pytest.approx(whole.var(ddof=1),
                                               rel=1e-4)
            assert p.min == pytest.approx(whole.min())
            assert p.max == pytest.approx(whole.max())
        assert left.mean == pytest.approx(merged.mean, rel=1e-9)
        assert left.m2 == pytest.approx(merged.m2, rel=1e-8)
        assert left.hist == merged.hist

    def test_histogram_edges_fixed_and_monotone(self):
        edges = dqprof.histogram_edges(32)
        assert len(edges) == 33
        assert all(b > a for a, b in zip(edges, edges[1:]))
        # symmetric log-compressed domain: edge k mirrors edge -k,
        # zero sits exactly on the middle edge
        assert edges[0] == pytest.approx(-edges[-1])
        assert edges[16] == pytest.approx(0.0, abs=1e-9)
        # deterministic: the merge contract across sessions
        assert dqprof.histogram_edges(32) == edges

    def test_histogram_buckets_match_edges(self):
        # values chosen in bucket interiors: the f32 device transform
        # and the f64 host edges must not disagree at a boundary
        vals = np.array([-1234.5, -3.0, -0.5, 0.5, 3.0, 7777.0])
        p = _profile_of(vals, bins=16)
        edges = np.asarray(dqprof.histogram_edges(16))
        expect, _ = np.histogram(vals, bins=edges)
        assert sum(p.hist) == vals.size
        assert p.hist == [int(c) for c in expect]

    def test_null_nan_arms(self):
        vals = np.array([1.0, np.nan, 3.0, np.nan, 5.0, 7.0])
        mask = np.array([True, True, True, False, False, True])
        p = _profile_of(vals, mask=mask)
        # one NaN under the mask counts as a null; the masked-out NaN
        # and the masked-out 5.0 count as nothing at all
        assert p.nulls == 1
        assert p.count == 3
        assert p.mean == pytest.approx(np.mean([1.0, 3.0, 7.0]))
        assert p.min == pytest.approx(1.0)
        assert p.max == pytest.approx(7.0)
        assert sum(p.hist) == 3

    def test_empty_column_sentinels(self):
        p = _profile_of(np.arange(8.0), mask=np.zeros(8, bool))
        assert p.count == 0 and p.nulls == 0
        assert p.min is None and p.max is None
        assert p.variance is None
        assert sum(p.hist) == 0

    def test_profile_doc_roundtrip_and_version_gate(self):
        p = _profile_of(np.arange(64.0))
        doc = p.to_doc()
        assert doc["version"] == dqprof.PROFILE_VERSION
        back = dqprof.ColumnProfile.from_doc(doc)
        assert back is not None
        assert back.to_doc() == doc
        skewed = dict(doc, version=dqprof.PROFILE_VERSION + 1)
        assert dqprof.ColumnProfile.from_doc(skewed) is None
        assert dqprof.ColumnProfile.from_doc("nope") is None


# ---------------------------------------------------------------------------
# Decomposable shard merge: per-shard partials + psum/pmin/pmax
# ---------------------------------------------------------------------------


class TestShardMerge:
    @pytest.mark.skipif(len(jax.devices()) < 4,
                        reason="needs 4 forced host devices")
    def test_sharded_sketch_parity_vs_single_device(self):
        from sparkdq4ml_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(devices=jax.devices()[:4])
        shard = types.SimpleNamespace(mesh=mesh, devices=4)
        rng = np.random.default_rng(7)
        vals = jax.numpy.asarray(rng.normal(scale=20.0, size=1024))
        mask = jax.numpy.asarray(rng.random(1024) > 0.2)
        single_fn = dqprof._program("sketch", 1024, vals.dtype, None)[0]
        sharded_fn = dqprof._program("sketch", 1024, vals.dtype,
                                     shard)[0]
        single = dqprof.ColumnProfile.from_raw(
            jax.device_get(single_fn(vals, mask)))
        merged = dqprof.ColumnProfile.from_raw(
            jax.device_get(sharded_fn(vals, mask)))
        # count/nulls/min/max/histogram are exact under any partition;
        # the f32 moment sums agree to summation-order rounding
        assert merged.count == single.count
        assert merged.nulls == single.nulls
        assert merged.min == pytest.approx(single.min)
        assert merged.max == pytest.approx(single.max)
        assert merged.hist == single.hist
        assert merged.mean == pytest.approx(single.mean, rel=1e-5)
        assert merged.m2 == pytest.approx(single.m2, rel=1e-4)

    @pytest.mark.skipif(len(jax.devices()) < 4,
                        reason="needs 4 forced host devices")
    def test_sharded_rule_counts_exact(self):
        from sparkdq4ml_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(devices=jax.devices()[:4])
        shard = types.SimpleNamespace(mesh=mesh, devices=4)
        vals = jax.numpy.asarray(
            np.where(np.arange(512) % 3 == 0, -1.0, 2.0))
        mask = jax.numpy.asarray(np.ones(512, bool))
        fn = dqprof._program("rule", 512, vals.dtype, shard)[0]
        total, passed = (int(round(float(v)))
                         for v in jax.device_get(fn(vals, mask)))
        assert total == 512
        assert passed == int(np.sum(np.arange(512) % 3 != 0))

    def test_host_merge_of_chunked_profiles_matches_whole(self):
        rng = np.random.default_rng(3)
        whole = rng.normal(size=900)
        merged = _profile_of(whole[:300])
        merged.merge(_profile_of(whole[300:600]))
        merged.merge(_profile_of(whole[600:]))
        direct = _profile_of(whole)
        assert merged.count == direct.count
        assert merged.hist == direct.hist
        assert merged.mean == pytest.approx(direct.mean, rel=1e-5)
        assert merged.variance == pytest.approx(direct.variance,
                                                rel=1e-4)


# ---------------------------------------------------------------------------
# Hot-path contract: deferred sketches, zero added syncs, disabled pins
# ---------------------------------------------------------------------------


class TestHotPathPins:
    def test_enabled_flush_adds_no_syncs_and_defers_drain(self, session):
        frame = Frame({"v": np.arange(2048, dtype=np.float64)})
        watched = ("frame.host_sync", "pipeline.compile",
                   "stats.drain_sync", "dq.drain_sync")

        def deltas():
            before = {k: profiling.counters.get(k) for k in watched}
            _flush_chain(frame)
            return {k: profiling.counters.get(k) - before[k]
                    for k in watched}

        config.dq_profile_enabled = False
        _flush_chain(frame)                  # warm the fused plan
        off = deltas()
        config.dq_profile_enabled = True
        _flush_chain(frame)                  # warm the sketch programs
        dqprof.clear()
        on = deltas()
        # the profiled flush costs the SAME number of host syncs and
        # pipeline compiles as the unprofiled one — sketches are
        # deferred device reductions, not synced reads
        assert on == off
        with dqprof._LOCK:
            assert len(dqprof._PENDING) > 0
        # the one counted sync happens at the cold report, not before
        base = profiling.counters.get("dq.drain_sync")
        rep = dqprof.report()
        assert rep["size"] > 0
        assert profiling.counters.get("dq.drain_sync") == base + 1

    def test_disabled_mode_never_touches_dqprof(self, session,
                                                monkeypatch):
        frame = Frame({"v": np.arange(512, dtype=np.float64)})
        config.dq_profile_enabled = True
        _flush_chain(frame)                  # warm while enabled
        config.dq_profile_enabled = False

        def _raise(*a, **kw):
            raise AssertionError("dqprof hook ran in disabled mode")

        monkeypatch.setattr(dqprof, "observe_flush", _raise)
        monkeypatch.setattr(dqprof, "record_eval", _raise)
        monkeypatch.setattr(dqprof, "drain", _raise)
        assert _flush_chain(frame) > 0
        # eager UDF path too: a registered rule evaluates, no hook runs
        dq.register_builtin_rules()
        f2 = Frame({"price": np.arange(32, dtype=np.float64) + 20.0})
        f2 = f2.with_column("pnm", dq.call_udf("minimumPriceRule",
                                               dq.col("price")))
        assert int(f2.count()) == 32

    def test_disabled_report_refuses(self, monkeypatch):
        config.dq_profile_enabled = False

        def _raise(*a, **kw):
            raise AssertionError("drain ran in disabled mode")

        monkeypatch.setattr(dqprof, "drain", _raise)
        assert dqprof.report() == {"enabled": False, "columns": [],
                                   "rules": [], "size": 0, "pending": 0}
        assert dqprof.rule_marks() is None
        assert dqprof.explain_lines(None) == []

    def test_pending_bound_drops_oldest_and_counts(self):
        config.dq_profile_enabled = True
        v = jax.numpy.float32(1.0)
        dqprof._enqueue([("rule", f"r{i}", 1, v)
                         for i in range(dqprof.MAX_PENDING + 5)])
        with dqprof._LOCK:
            assert len(dqprof._PENDING) == dqprof.MAX_PENDING
        assert profiling.counters.get("dq.pending_dropped") == 5

    def test_program_handles_registered(self, session):
        frame = Frame({"v": np.arange(256, dtype=np.float64)})
        config.dq_profile_enabled = True
        _flush_chain(frame)
        handles, errors = obs.CACHES.programs()
        assert "dqprof" not in errors
        mine = [h for h in handles if h.cache == "dqprof"]
        assert mine, "sketch programs must be registry-enumerable"
        assert all(h.program_key.startswith("dq") for h in mine)


# ---------------------------------------------------------------------------
# Statstore baselines: round-trip + winner-merge keeps profiles
# ---------------------------------------------------------------------------


class TestStatstoreBaselines:
    def test_record_profile_roundtrip(self, tmp_path):
        doc = _profile_of(np.arange(100.0)).to_doc()
        statstore.STORE.record_profile("dqprof|price", "dqprof", doc)
        path = str(tmp_path / "stats.jsonl")
        assert statstore.STORE.save(path)
        fresh = statstore.StatStore()
        assert fresh.load(path) >= 1
        assert fresh.profile("dqprof|price") == doc

    def test_profile_survives_winner_merge(self):
        with_prof = statstore.KeyStats("K", "dqprof")
        with_prof.profile = {"version": 1, "count": 9}
        heavier = statstore.KeyStats("K", "dqprof")
        heavier.flushes = 50                 # more evidence, no profile
        target: dict = {}
        statstore.StatStore._merge_into(target, [with_prof])
        statstore.StatStore._merge_into(target, [heavier])
        assert target["K"].profile == {"version": 1, "count": 9}
        target2: dict = {}
        statstore.StatStore._merge_into(target2, [heavier])
        statstore.StatStore._merge_into(target2, [with_prof])
        assert target2["K"].profile == {"version": 1, "count": 9}

    def test_pre_dq_docs_load_without_profile(self):
        # a persisted doc from before the observatory has no "profile"
        # field — loading must not invent one, saving must not emit one
        doc = statstore.KeyStats("old", "x").to_doc()
        doc.pop("profile", None)
        ks = statstore.KeyStats.from_doc(doc)
        assert ks.profile is None
        assert "profile" not in ks.to_doc()

    def test_drain_persists_and_adopts_baseline(self, session):
        config.dq_profile_enabled = True
        config.stats_enabled = True
        frame = Frame({"v": np.arange(128, dtype=np.float64)})
        _flush_chain(frame)
        rep = dqprof.report()
        cols = [c["column"] for c in rep["columns"]]
        assert cols
        persisted = statstore.STORE.profile(f"dqprof|{cols[0]}")
        assert persisted is not None
        assert persisted["version"] == dqprof.PROFILE_VERSION
        # a fresh observatory adopts the persisted snapshot as baseline
        # instead of re-learning one ("first" mode, snapshot present)
        dqprof.clear()
        before = profiling.counters.get("dq.baseline_pinned")
        _flush_chain(frame)
        rep2 = dqprof.report()
        row = next(c for c in rep2["columns"]
                   if c["column"] == cols[0])
        assert row["baseline_count"] == persisted["count"]
        assert profiling.counters.get("dq.baseline_pinned") > before

    def test_baseline_mode_off_disables_drift(self, session):
        config.dq_profile_enabled = True
        config.dq_baseline_mode = "off"
        frame = Frame({"v": np.arange(128, dtype=np.float64)})
        _flush_chain(frame)
        rep = dqprof.report()
        assert rep["columns"]
        assert all(c["drift"] is None for c in rep["columns"])
        assert profiling.counters.get("dq.baseline_pinned") == 0


# ---------------------------------------------------------------------------
# Drift: threshold flip → gauge + incident bundle + tail keep-reason
# ---------------------------------------------------------------------------


class TestDrift:
    def test_psi_zero_on_identical_and_positive_on_shift(self):
        base = _profile_of(np.random.default_rng(1).normal(size=500))
        assert dqprof.drift_score(base, base) == pytest.approx(0.0)
        shifted = _profile_of(
            np.random.default_rng(1).normal(size=500) * 100.0 + 500.0)
        score = dqprof.drift_score(base, shifted)
        assert score is not None and score > 1.0
        assert dqprof.drift_score(None, base) is None
        assert dqprof.drift_score(base, dqprof.ColumnProfile()) is None

    def test_threshold_flip_sets_gauge_incident_and_tail_keep(
            self, session):
        config.dq_profile_enabled = True
        config.dq_drift_threshold = 0.25
        obs.enable()
        obs.TAIL.configure(ring_size=8, retained_size=8)
        incidents.RECORDER.configure(enabled=True, cooldown_s=0.0)
        frame = Frame({"v": np.arange(256, dtype=np.float64)})
        _flush_chain(frame)
        dqprof.report()                       # pins the baseline
        assert profiling.counters.get("dq.drift_breach") == 0
        shifted = Frame(
            {"v": np.arange(256, dtype=np.float64) * 500.0 + 1e4})
        ctx = obs.TraceContext.mint()
        with obs.request_span("serve.query", ctx, tenant="t"):
            _flush_chain(shifted)
            rep = dqprof.report()             # drains inside the span
        drifted = [c for c in rep["columns"]
                   if c["drift"] is not None
                   and c["drift"] > config.dq_drift_threshold]
        assert drifted, "distribution shift must score past threshold"
        col = drifted[0]["column"]
        assert obs.METRICS.get_gauge(f"dq.drift.{col}") == \
            pytest.approx(drifted[0]["drift"])
        assert profiling.counters.get("dq.drift_breach") >= 1
        # the incident bundle carries the before/after profiles
        bundles = [b for b in incidents.RECORDER.list()
                   if b["trigger"] == "dq_drift"]
        assert bundles
        bundle = incidents.RECORDER.get(bundles[-1]["id"])
        assert bundle["dq_drift"]["column"] in [c["column"]
                                                for c in drifted]
        assert bundle["dq_drift"]["score"] > 0.25
        assert bundle["dq_drift"]["baseline"]["count"] > 0
        assert bundle["dq_drift"]["current"]["count"] > 0
        assert bundle["dq"]["enabled"] is True
        # the span annotation promotes the tree in the tail sampler
        obs.TAIL.finish_request(ctx, status="ok", reason="",
                                e2e_ms=1.0, breaker_opened=False,
                                slo_ms=None)
        doc = obs.TAIL.lookup(ctx.trace_id)[0]
        assert doc["kept"] and "dq_drift" in doc["keep_reasons"]

    def test_no_breach_below_threshold(self, session):
        config.dq_profile_enabled = True
        config.dq_drift_threshold = 0.25
        frame = Frame({"v": np.arange(256, dtype=np.float64)})
        _flush_chain(frame)
        dqprof.report()
        _flush_chain(frame)                   # identical distribution
        rep = dqprof.report()
        assert profiling.counters.get("dq.drift_breach") == 0
        assert all((c["drift"] or 0.0) <= 0.25 for c in rep["columns"])


# ---------------------------------------------------------------------------
# Rule violation accounting (eager UDF path + report + spike incident)
# ---------------------------------------------------------------------------


class TestRuleAccounting:
    @pytest.mark.parametrize("path", ["in_flush", "eager_pipeline_off"])
    def test_udf_evals_accounted(self, session, path):
        config.dq_profile_enabled = True
        dq.register_builtin_rules()
        price = np.where(np.arange(40) % 4 == 0, 5.0, 50.0)
        f = Frame({"price": price.astype(np.float64)})
        saved = config.pipeline
        config.pipeline = path == "in_flush"
        try:
            f = f.with_column("pnm", dq.call_udf("minimumPriceRule",
                                                 dq.col("price")))
            assert bool(f._pending) == (path == "in_flush")
            f.count()
        finally:
            config.pipeline = saved
        rep = dqprof.report()
        row = next(r for r in rep["rules"]
                   if r["rule"] == "minimumPriceRule")
        # the eager fallback may evaluate the column more than once;
        # the tallies scale together and the RATE stays exact
        evals = row["evals"]
        assert evals >= 1
        assert row["rows"] == 40 * evals
        assert row["violations"] == 10 * evals
        assert row["rate"] == pytest.approx(0.25)
        assert profiling.counters.get(
            "dq.violations.minimumPriceRule") == 10 * evals
        assert obs.METRICS.get_gauge(
            "dq.violation_rate.minimumPriceRule") == pytest.approx(0.25)

    @pytest.mark.parametrize("path", ["in_flush", "eager_pipeline_off",
                                      "eager_whole_column"])
    def test_rule_tally_behind_an_earlier_filter(self, session, path):
        """What each path counts a rule's rows against. In a flush: the
        flush's INPUT mask — the rows an earlier, already materialised
        filter dropped are out, and the fused ``WHERE rule > 0`` behind
        the rule does not eat the violations. Eagerly: every slot the
        rule saw, masked ones included. The violations the two report
        differ by exactly the masked rows' — the rate of a rule over
        valid rows is the flush's."""
        from sparkdq4ml_tpu.ops import expressions as E
        from sparkdq4ml_tpu.ops.udf import UDFRegistry

        config.dq_profile_enabled = True
        reg = UDFRegistry()
        if path == "eager_whole_column":
            # looks at every row (adds 0 * the column's maximum): not
            # row-local, so it stays eager with the pipeline on
            reg.register("floor", lambda x: jax.numpy.where(
                x < 20.0, -1.0, x) + 0.0 * jax.numpy.max(x), "double")
        else:
            reg.register("floor", lambda x: jax.numpy.where(
                x < 20.0, -1.0, x), "double")
        price = np.where(np.arange(40) % 4 == 0, 5.0, 50.0)
        guest = np.arange(40)
        saved = config.pipeline
        config.pipeline = path != "eager_pipeline_off"
        before = {k: profiling.counters.get(k) for k in (
            "dq.rule_evals", "dq.rule_in_flush", "dq.rule_eager",
            "pipeline.fallback")}
        try:
            f = Frame({"price": price, "guest": guest})
            f = f.filter(E.col("guest") >= 20)      # 20 rows stay
            assert f.count() == 20                  # ... materialised
            g = f.with_column("r", E.UdfCall("floor", [E.col("price")],
                                             reg))
            assert bool(g._pending) == (path == "in_flush")
            assert g.filter(E.col("r") > 0).count() == 15
        finally:
            config.pipeline = saved
        moved = {k: profiling.counters.get(k) - v
                 for k, v in before.items()}
        row = next(r for r in dqprof.report()["rules"]
                   if r["rule"] == "floor")
        if path == "in_flush":
            assert (row["evals"], row["rows"], row["violations"]) == \
                (1, 20, 5)
            assert moved == {"dq.rule_evals": 1, "dq.rule_in_flush": 1,
                             "dq.rule_eager": 0, "pipeline.fallback": 0}
        else:
            assert (row["evals"], row["rows"], row["violations"]) == \
                (1, 40, 10)
            assert moved == {"dq.rule_evals": 1, "dq.rule_in_flush": 0,
                             "dq.rule_eager": 1, "pipeline.fallback": 0}

    def test_rule_in_a_projection_is_tallied_nested_is_not(self, session):
        """A rule that IS a column of the flush — a with_column or a
        SELECT item — has its tally; one inside a larger expression has
        no column to count (its values exist inside the program only):
        it is counted as run in the flush and tallies nothing."""
        config.dq_profile_enabled = True
        dq.register_builtin_rules()
        price = np.where(np.arange(40) % 4 == 0, 5.0, 50.0)
        Frame({"price": price, "guest": np.arange(40.0)}
              ).create_or_replace_temp_view("t")
        before = profiling.counters.get("dq.rule_in_flush")
        out = session.sql("SELECT minimumPriceRule(price) AS p, guest + 1 "
                          "AS g FROM t WHERE guest >= 20")
        assert out.count() == 20
        assert profiling.counters.get("dq.rule_in_flush") == before + 1
        row = next(r for r in dqprof.report()["rules"]
                   if r["rule"] == "minimumPriceRule")
        assert (row["evals"], row["rows"], row["violations"]) == (1, 40, 10)
        nested = session.sql("SELECT guest FROM t WHERE "
                             "minimumPriceRule(price) > 0")
        assert nested.count() == 30
        assert profiling.counters.get("dq.rule_in_flush") == before + 2
        row = next(r for r in dqprof.report()["rules"]
                   if r["rule"] == "minimumPriceRule")
        assert row["evals"] == 1                     # nothing added

    def test_rule_rows_recorded_when_the_flush_donates(self, session,
                                                       monkeypatch):
        # On an accelerator the fused flush donates input buffers — a
        # branch no CPU test ran. The reference app's rule-bearing path
        # must come out the same with donation on: same rows, exact rule
        # tallies, no swallowed profile failure. (The profile hook is
        # handed the flush's INPUT mask after the dispatch, which is why
        # the mask is never among the donated buffers.)
        from sparkdq4ml_tpu.ops import compiler

        config.dq_profile_enabled = True
        monkeypatch.setattr(compiler, "_donates", lambda: True)
        compiler.clear_cache()
        try:
            df = run_dq_pipeline(session, dataset_path("abstract"))
            assert df.count() == 24
            assert compiler._CACHE and all(
                p.donates for p in compiler._CACHE.values())
        finally:
            compiler.clear_cache()
        rules = {r["rule"]: r for r in dqprof.report()["rules"]}
        assert (rules["minimumPriceRule"]["rows"],
                rules["minimumPriceRule"]["violations"]) == (40, 6)
        assert rules["priceCorrelationRule"]["rows"] > 0
        assert profiling.counters.get("dq.profile_failed") == 0
        assert RECOVERY_LOG.count(site="dq_profile") == 0

    def test_violation_spike_captures_incident(self, session):
        config.dq_profile_enabled = True
        obs.enable()
        incidents.RECORDER.configure(enabled=True, cooldown_s=0.0)
        dq.register_builtin_rules()
        bad = Frame({"price": np.full(32, 1.0)})   # all under the floor
        bad = bad.with_column("pnm", dq.call_udf("minimumPriceRule",
                                                 dq.col("price")))
        bad.count()
        before = profiling.counters.get("dq.violation_spike")
        dqprof.report()
        assert profiling.counters.get("dq.violation_spike") == before + 1
        bundles = [b for b in incidents.RECORDER.list()
                   if b["trigger"] == "dq_violations"]
        assert bundles
        bundle = incidents.RECORDER.get(bundles[-1]["id"])
        assert bundle["dq_violations"]["rule"] == "minimumPriceRule"
        assert bundle["dq_violations"]["rate"] == pytest.approx(1.0)

    def test_trace_time_evals_not_enqueued(self, session):
        config.dq_profile_enabled = True
        with dqprof._LOCK:
            n0 = len(dqprof._PENDING)

        def traced(x):
            # a tracer inside a jit body must never enqueue — the
            # compiled replay would double-count every execution
            dqprof.record_eval("someRule", x)
            return x

        jax.block_until_ready(jax.jit(traced)(jax.numpy.arange(4.0)))
        with dqprof._LOCK:
            assert len(dqprof._PENDING) == n0


# ---------------------------------------------------------------------------
# Fault ladder: dq_profile degrades the flush to unprofiled, never down
# ---------------------------------------------------------------------------


class TestFaultLadder:
    def test_dq_profile_site_registered(self):
        assert "dq_profile" in faults.FAULT_SITES
        assert "device_error" in faults.FAULT_SITES["dq_profile"]

    def test_injected_fault_degrades_to_unprofiled(self, session):
        config.dq_profile_enabled = True
        frame = Frame({"v": np.arange(512, dtype=np.float64)})
        _flush_chain(frame)                   # warm plans + sketches
        dqprof.clear()
        RECOVERY_LOG.clear()
        before = profiling.counters.get("dq.profile_failed")
        with faults.inject_faults("dq_profile:device_error:p=1.0"):
            assert _flush_chain(frame) > 0    # the flush itself survives
        assert profiling.counters.get("dq.profile_failed") > before
        events = RECOVERY_LOG.events(site="dq_profile")
        assert events and events[-1].action == "fallback"
        assert events[-1].rung == "unprofiled"
        # degraded flushes contributed nothing; the observatory is
        # coherent, not corrupt — and chaos ending resumes profiling
        assert dqprof.report()["size"] == 0
        _flush_chain(frame)
        assert dqprof.report()["size"] > 0

    def test_report_survives_faults(self, session):
        config.dq_profile_enabled = True
        frame = Frame({"v": np.arange(128, dtype=np.float64)})
        with faults.inject_faults("dq_profile:device_error:p=1.0"):
            _flush_chain(frame)
            rep = dqprof.report()
        assert rep["enabled"] is True
        assert isinstance(rep["columns"], list)


# ---------------------------------------------------------------------------
# /dq HTTP route
# ---------------------------------------------------------------------------


class TestDqRoute:
    def test_dq_route_schema(self, session):
        config.dq_profile_enabled = True
        dq.register_builtin_rules()
        f = Frame({"price": np.arange(64, dtype=np.float64) + 20.0})
        f = f.with_column("pnm", dq.call_udf("minimumPriceRule",
                                             dq.col("price")))
        f.count()
        _flush_chain(Frame({"v": np.arange(128, dtype=np.float64)}))
        with TelemetryServer(port=0) as ts:
            code, body = _get(f"http://127.0.0.1:{ts.port}/dq?top=4")
        assert code == 200
        doc = json.loads(body)
        for key in ("enabled", "columns", "rules", "size", "pending",
                    "bins", "drift_threshold", "baseline_mode"):
            assert key in doc, key
        assert doc["enabled"] is True
        assert doc["rules"] and doc["columns"]
        col = doc["columns"][0]
        for key in ("column", "count", "nulls", "mean", "min", "max",
                    "hist", "drift", "baseline_count", "version"):
            assert key in col, key
        rule = doc["rules"][0]
        for key in ("rule", "evals", "rows", "violations", "rate"):
            assert key in rule, key

    def test_dq_route_disabled_pin(self, monkeypatch):
        config.dq_profile_enabled = False

        def _raise(*a, **kw):
            raise AssertionError("dq report ran in disabled mode")

        monkeypatch.setattr(dqprof, "report", _raise)
        with TelemetryServer(port=0) as ts:
            code, body = _get(f"http://127.0.0.1:{ts.port}/dq")
        assert code == 200
        assert json.loads(body) == {"enabled": False, "columns": [],
                                    "rules": []}

    def test_dq_route_in_404_listing(self):
        with TelemetryServer(port=0) as ts:
            with pytest.raises(urllib.error.HTTPError) as exc:
                _get(f"http://127.0.0.1:{ts.port}/nope")
            assert exc.value.code == 404
            routes = json.loads(exc.value.read().decode())["routes"]
            assert "/dq" in routes


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE section + headline goldens
# ---------------------------------------------------------------------------


#: A rule-bearing replay against the view ``run_dq_pipeline`` leaves
#: registered — the UDF call sits IN the statement so execution
#: re-evaluates the rule (a materialized view column would not).
HEADLINE_RULE_SQL = (
    "SELECT guest, priceCorrelationRule(price, guest) AS pcc "
    "FROM price WHERE priceCorrelationRule(price, guest) > 0")


class TestExplainSection:
    def test_headline_analyze_renders_dq_section_goldens_unchanged(
            self, session):
        config.dq_profile_enabled = True
        df = run_dq_pipeline(session, dataset_path("abstract"))
        assert df.count() == 24                       # golden
        plan = session.sql("EXPLAIN ANALYZE " + HEADLINE_RULE_SQL) \
            .to_pydict()["plan"][0]
        assert "== Data Quality ==" in plan
        assert "rule priceCorrelationRule:" in plan
        assert "violations=" in plan and "rate=" in plan
        # golden model numbers stay exact with the observatory on
        from sparkdq4ml_tpu.models import LinearRegression

        model = LinearRegression(max_iter=40, reg_param=1.0,
                                 elastic_net_param=1.0).fit(
            prepare_features(df))
        assert float(model.summary.root_mean_squared_error) == \
            pytest.approx(2.809940, rel=1e-3)

    def test_rule_free_analyze_has_no_section(self, session):
        config.dq_profile_enabled = True
        f = Frame({"v": np.arange(256, dtype=np.float64)})
        f.create_or_replace_temp_view("dqp_plain")
        plan = session.sql(
            "EXPLAIN ANALYZE SELECT v * 2 AS w FROM dqp_plain "
            "WHERE v > 5").to_pydict()["plan"][0]
        assert "== Data Quality ==" not in plan

    def test_disabled_mode_pins_analyze_byte_identical(
            self, session, monkeypatch):
        dq.register_builtin_rules()
        f = Frame({"price": np.arange(64, dtype=np.float64) + 20.0})
        f.create_or_replace_temp_view("dqp_off")
        sql = ("EXPLAIN ANALYZE SELECT minimumPriceRule(price) AS p "
               "FROM dqp_off WHERE minimumPriceRule(price) > 0")
        config.dq_profile_enabled = True
        session.sql(sql)                      # warm plans either way
        config.dq_profile_enabled = False

        def _raise(*a, **kw):
            raise AssertionError("dq EXPLAIN hook ran in disabled mode")

        monkeypatch.setattr(dqprof, "rule_marks", _raise)
        monkeypatch.setattr(dqprof, "explain_lines", _raise)
        plan_off = session.sql(sql).to_pydict()["plan"][0]
        assert "== Data Quality ==" not in plan_off
        monkeypatch.undo()
        config.dq_profile_enabled = True
        plan_on = session.sql(sql).to_pydict()["plan"][0]
        assert "== Data Quality ==" in plan_on    # flag flips it back

    def test_plain_explain_untouched(self, session):
        config.dq_profile_enabled = True
        f = Frame({"v": np.arange(64, dtype=np.float64)})
        f.create_or_replace_temp_view("dqp_ex")
        plan = session.sql(
            "EXPLAIN SELECT v FROM dqp_ex").to_pydict()["plan"][0]
        assert "Data Quality" not in plan
