"""Fault-injection + resilient-execution suite (ISSUE 1 tentpole).

Every injected failure class is triggered deterministically and recovered
from, with assertions on the structured recovery-event log
(``utils.recovery.RECOVERY_LOG``): device errors retry with backoff, NaN
results are detected and replayed, mid-fit preemption resumes from the
checkpoint cursor, a failing sharded Gramian degrades to the single-device
CPU path, and a failing iterative solver degrades to the closed-form one.
A clean run records zero events — resilience must be free when nothing
fails.
"""

import time

import numpy as np
import pytest

import jax

from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.models import LinearRegression, VectorAssembler
from sparkdq4ml_tpu.parallel.distributed import compute_gram
from sparkdq4ml_tpu.parallel.mesh import make_mesh
from sparkdq4ml_tpu.utils import faults, profiling, recovery
from sparkdq4ml_tpu.utils.recovery import (RECOVERY_LOG, CircuitBreaker,
                                           DeadlineExceeded, FitFailure,
                                           RetryPolicy, resilient_call)

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """Chaos state is process-global: scrub the plan, the event log, the
    device breaker, and the counters around every test."""
    faults.clear()
    RECOVERY_LOG.clear()
    recovery.DEVICE_BREAKER.reset()
    profiling.counters.clear("recovery.")
    yield
    faults.clear()
    RECOVERY_LOG.clear()
    recovery.DEVICE_BREAKER.reset()
    profiling.counters.clear("recovery.")


def _frame(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=n)
    cols = {"x": x, "label": 3 * x + 1 + 0.01 * rng.normal(size=n)}
    return VectorAssembler(["x"], "features").transform(Frame(cols))


# ---------------------------------------------------------------------------
# The schedule itself: determinism
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_parse_spec_forms(self):
        s = faults.parse_spec("gram_sharded:device_error:1,3")
        assert s.site == "gram_sharded" and s.kind == "device_error"
        assert s.attempts == frozenset({1, 3})
        s = faults.parse_spec("fit:preempt:p=0.5:seed=7")
        assert s.p == 0.5 and s.seed == 7 and s.attempts is None
        s = faults.parse_spec("mesh:device_drop:n=2")
        assert s.n == 2 and s.attempts == frozenset({1})

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError, match="site:kind"):
            faults.parse_spec("lonesite")
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.parse_spec("site:explode")

    def test_attempt_schedule_fires_exactly_when_listed(self):
        with faults.inject_faults("s:device_error:2") as plan:
            faults.inject("s")                      # attempt 1: clean
            with pytest.raises(jax.errors.JaxRuntimeError):
                faults.inject("s")                  # attempt 2: fires
            faults.inject("s")                      # attempt 3: clean
        assert plan.fired == [("s", "device_error", 2)]

    def test_probability_schedule_is_deterministic(self):
        def run():
            hits = []
            with faults.inject_faults("s:device_error:p=0.5", seed=11):
                for i in range(20):
                    try:
                        faults.inject("s")
                        hits.append(0)
                    except jax.errors.JaxRuntimeError:
                        hits.append(1)
            return hits

        a, b = run(), run()
        assert a == b            # same seed → identical failure sequence
        assert 0 < sum(a) < 20   # and it's actually probabilistic

    def test_env_driven_install(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, "s:device_error:1")
        plan = faults.install_from_env()
        assert plan is not None and plan.specs[0].site == "s"
        monkeypatch.delenv(faults.ENV_VAR)
        assert faults.install_from_env() is None

    def test_nan_corruption_is_deterministic(self):
        tree = {"a": np.zeros(8), "b": np.ones(3)}

        def run():
            with faults.inject_faults("s:nan:1", seed=3):
                return faults.corrupt("s", {k: v.copy()
                                            for k, v in tree.items()})

        out1, out2 = run(), run()
        n1 = [np.isnan(out1[k]) for k in ("a", "b")]
        n2 = [np.isnan(out2[k]) for k in ("a", "b")]
        assert sum(int(m.sum()) for m in n1) == 1      # exactly one NaN
        assert all((x == y).all() for x, y in zip(n1, n2))  # same slot

    def test_no_plan_hooks_are_noops(self):
        faults.inject("anything")
        t = {"a": np.ones(2)}
        assert faults.corrupt("anything", t) is t
        mesh = make_mesh()
        assert faults.degrade_mesh("anything", mesh) is mesh


# ---------------------------------------------------------------------------
# Policy engine: backoff, deadlines, breaker
# ---------------------------------------------------------------------------

class TestRetryPolicy:
    def test_backoff_grows_and_caps(self):
        p = RetryPolicy(max_attempts=10, backoff_base=0.1, backoff_factor=2.0,
                        backoff_max=0.5, jitter=0.0)
        waits = [p.backoff(a) for a in range(1, 6)]
        assert waits[:3] == [0.1, 0.2, 0.4]
        assert waits[3] == waits[4] == 0.5              # capped

    def test_jitter_is_deterministic_per_seed(self):
        p = RetryPolicy(max_attempts=5, backoff_base=0.1, jitter=0.5, seed=9)
        assert p.backoff(2, "site") == p.backoff(2, "site")
        assert p.backoff(2, "site") != p.backoff(2, "other-site")
        base = RetryPolicy(max_attempts=5, backoff_base=0.1, jitter=0.0)
        assert base.backoff(2) <= p.backoff(2, "site") <= base.backoff(2) * 1.5

    def test_no_sleep_after_final_attempt(self):
        p = RetryPolicy(max_attempts=3, backoff_base=0.1, jitter=0.0)
        assert p.backoff(3) == 0.0

    def test_from_conf(self):
        p = RetryPolicy.from_conf({
            "spark.recovery.maxAttempts": "5",
            "spark.recovery.backoffBase": "0.2",
            "spark.recovery.attemptDeadline": "1.5",
            "spark.recovery.jitter": "0",
        })
        assert (p.max_attempts, p.backoff_base, p.attempt_deadline,
                p.jitter) == (5, 0.2, 1.5, 0.0)
        assert p.backoff_factor == 2.0   # untouched keys keep defaults

    def test_retries_with_backoff_records_sleeps(self):
        sleeps = []
        p = RetryPolicy(max_attempts=3, backoff_base=0.01, jitter=0.2,
                        seed=4, sleep=sleeps.append)
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise jax.errors.JaxRuntimeError("boom")
            return "ok"

        assert resilient_call(flaky, site="s", policy=p) == "ok"
        assert calls["n"] == 3
        assert sleeps == [p.backoff(1, "s"), p.backoff(2, "s")]
        evs = RECOVERY_LOG.events(site="s", action="retry")
        assert [e.attempt for e in evs] == [1, 2]
        assert [e.backoff_s for e in evs] == sleeps   # backoff in the log

    def test_attempt_deadline(self):
        p = RetryPolicy(max_attempts=2, backoff_base=0.0, jitter=0.0,
                        attempt_deadline=0.05)
        with pytest.raises(FitFailure):
            resilient_call(lambda: time.sleep(0.4), site="dl", policy=p)
        evs = RECOVERY_LOG.events(site="dl")
        assert all("DeadlineExceeded" in e.cause for e in evs
                   if e.action in ("retry", "exhausted"))

    def test_total_deadline_stops_retrying(self):
        clockbox = {"t": 0.0}
        p = RetryPolicy(max_attempts=100, backoff_base=0.0, jitter=0.0,
                        total_deadline=0.2, sleep=lambda s: None)

        def fail():
            time.sleep(0.15)
            raise jax.errors.JaxRuntimeError("down")

        t0 = time.monotonic()
        with pytest.raises(FitFailure, match="total deadline"):
            resilient_call(fail, site="td", policy=p)
        assert time.monotonic() - t0 < 5.0   # nowhere near 100 attempts
        del clockbox

    def test_deadline_exceeded_is_its_own_type(self):
        with pytest.raises(DeadlineExceeded):
            recovery._run_with_deadline(lambda: time.sleep(0.3), 0.02)

    def test_deadline_worker_is_daemon(self):
        """An abandoned (wedged) attempt must not block interpreter exit:
        the deadline worker is a daemon thread, never a pool worker that
        concurrent.futures would join at shutdown."""
        import threading

        with pytest.raises(DeadlineExceeded):
            recovery._run_with_deadline(lambda: time.sleep(1.0), 0.02)
        stuck = [t for t in threading.enumerate()
                 if t.name == "sparkdq4ml-deadline" and t.is_alive()]
        assert stuck and all(t.daemon for t in stuck)

    def test_per_site_policy_overrides(self):
        from sparkdq4ml_tpu.session import TpuSession

        s = TpuSession(conf={"spark.compilation.cache": "off",
                             "spark.recovery.maxAttempts": "5",
                             "spark.recovery.gram_sharded.maxAttempts": "2"})
        import sparkdq4ml_tpu.session as sess_mod

        prev = sess_mod._ACTIVE
        sess_mod._ACTIVE = s
        try:
            assert recovery.active_policy("fit_packed").max_attempts == 5
            assert recovery.active_policy("gram_sharded").max_attempts == 2
        finally:
            sess_mod._ACTIVE = prev


class TestCircuitBreaker:
    def test_opens_after_threshold_and_cools_down(self):
        clock = {"t": 0.0}
        b = CircuitBreaker(failure_threshold=2, cooldown=10.0,
                           clock=lambda: clock["t"])
        assert b.allow("k")
        assert not b.record_failure("k")
        assert b.record_failure("k")          # this one OPENS it
        assert not b.allow("k")
        clock["t"] = 11.0
        assert b.allow("k")                   # half-open trial
        b.record_success("k")
        assert b.allow("k")

    def test_open_breaker_skips_rung(self):
        b = CircuitBreaker(failure_threshold=1, cooldown=1e9)
        b.record_failure("s/primary")
        p = RetryPolicy(max_attempts=1, backoff_base=0.0, jitter=0.0)
        out = resilient_call(lambda: 1 / 0, site="s", policy=p, breaker=b,
                             fallbacks=[("plan_b", lambda: "fell back")])
        assert out == "fell back"
        assert RECOVERY_LOG.count(action="circuit_skip", site="s") == 1
        # primary never ran: 1/0 would have raised ZeroDivisionError
        # (not retryable) straight through

    def test_all_rungs_open_raises_circuit_open(self):
        b = CircuitBreaker(failure_threshold=1, cooldown=1e9)
        b.record_failure("s/primary")
        p = RetryPolicy(max_attempts=1, backoff_base=0.0, jitter=0.0)
        with pytest.raises(recovery.CircuitOpenError):
            resilient_call(lambda: "never runs", site="s", policy=p,
                           breaker=b)


# ---------------------------------------------------------------------------
# End-to-end failure classes (the acceptance matrix)
# ---------------------------------------------------------------------------

class TestDeviceErrorRecovery:
    def test_fit_retries_through_injected_device_error(self):
        f = _frame()
        with faults.inject_faults("fit_packed:device_error:1") as plan:
            model = LinearRegression(max_iter=10).fit(f)
        assert plan.fired == [("fit_packed", "device_error", 1)]
        assert model.coefficients[0] == pytest.approx(3.0, abs=0.05)
        retries = RECOVERY_LOG.events(site="fit_packed", action="retry")
        assert len(retries) == 1 and retries[0].attempt == 1
        assert retries[0].backoff_s > 0.0           # backoff was applied
        assert "InjectedDeviceError" in retries[0].cause
        assert RECOVERY_LOG.count(action="recovered", site="fit_packed") == 1
        assert profiling.counters.get("recovery.retry") == 1

    def test_persistent_device_error_exhausts_then_raises(self):
        f = _frame()
        # fails every attempt on every rung: primary + solver downgrade
        with faults.inject_faults("fit_packed:device_error:p=1.0"):
            with pytest.raises(FitFailure):
                LinearRegression(max_iter=10, solver="fista").fit(f)
        assert RECOVERY_LOG.count(action="exhausted") == 2
        falls = RECOVERY_LOG.events(site="fit_packed", action="fallback")
        assert [e.rung for e in falls] == ["solver_normal"]


class TestNanRecovery:
    def test_fit_detects_and_replays_nan_result(self):
        f = _frame()
        with faults.inject_faults("solver:nan:1") as plan:
            model = LinearRegression(max_iter=10).fit(f)
        assert plan.fired == [("solver", "nan", 1)]
        assert np.isfinite(model.coefficients).all()
        retries = RECOVERY_LOG.events(site="fit_packed", action="retry")
        assert len(retries) == 1 and retries[0].cause == "non-finite result"
        assert RECOVERY_LOG.count(action="recovered") == 1

    def test_persistent_nan_downgrades_solver(self):
        f = _frame()
        # fista requested; every fista attempt poisoned → the ladder's
        # last rung (closed-form normal solve, L2-only penalty) recovers
        with faults.inject_faults("solver:nan:1,2,3"):
            model = LinearRegression(max_iter=20, reg_param=0.1,
                                     solver="fista").fit(f)
        assert np.isfinite(model.coefficients).all()
        falls = RECOVERY_LOG.events(site="fit_packed", action="fallback")
        assert [e.rung for e in falls] == ["solver_normal"]
        rec = RECOVERY_LOG.events(site="fit_packed", action="recovered")
        assert len(rec) == 1 and rec[0].rung == "solver_normal"

    def test_l1_penalty_has_no_solver_downgrade(self):
        from sparkdq4ml_tpu.models.solvers import downgrade_solver

        assert downgrade_solver("fista", 0.1, 0.5) is None
        assert downgrade_solver("owlqn", 0.1, 0.0) == "normal"
        assert downgrade_solver("normal", 0.0, 0.0) is None


class TestShardedGramianFallback:
    def test_falls_back_to_single_device_cpu(self):
        rng = np.random.default_rng(1)
        X = rng.normal(size=(40, 3))
        y = rng.normal(size=40)
        mask = np.ones(40, bool)
        mesh = make_mesh()
        assert mesh.devices.size > 1     # conftest forces 8 CPU devices
        expected = np.asarray(compute_gram(X, y, mask))
        # the sharded path fails all 3 attempts → single-CPU rung serves
        with faults.inject_faults("gram_sharded:device_error:1,2,3"):
            got = np.asarray(compute_gram(X, y, mask, mesh=mesh))
        np.testing.assert_allclose(got, expected, rtol=1e-9)
        assert [e.attempt for e in RECOVERY_LOG.events(
            site="gram_sharded", action="retry")] == [1, 2]
        assert RECOVERY_LOG.count(action="exhausted",
                                  site="gram_sharded") == 1
        falls = RECOVERY_LOG.events(site="gram_sharded", action="fallback")
        assert [e.rung for e in falls] == ["single_cpu"]
        assert RECOVERY_LOG.count(action="circuit_open",
                                  site="gram_sharded") == 1

    def test_transient_error_recovers_without_fallback(self):
        rng = np.random.default_rng(2)
        X = rng.normal(size=(24, 2))
        y = rng.normal(size=24)
        mask = np.ones(24, bool)
        mesh = make_mesh()
        with faults.inject_faults("gram_sharded:device_error:1"):
            got = np.asarray(compute_gram(X, y, mask, mesh=mesh))
        np.testing.assert_allclose(
            got, np.asarray(compute_gram(X, y, mask)), rtol=1e-9)
        assert RECOVERY_LOG.count(action="fallback") == 0
        assert RECOVERY_LOG.count(action="recovered",
                                  site="gram_sharded") == 1


class TestPreemption:
    def test_mid_fit_preemption_resumes_from_cursor(self, tmp_path):
        f = _frame()
        est = LinearRegression(max_iter=40, reg_param=0.1,
                               elastic_net_param=0.5, tol=0.0)
        ck = str(tmp_path / "ck")
        # tol=0 never converges early → 4 segments of 10; the 3rd fit
        # call is preempted mid-run and must resume from the 20/40 cursor
        with faults.inject_faults("fit:preempt:3") as plan:
            model = recovery.fit_or_resume(est, f, ck, checkpoint_every=10)
        assert plan.fired == [("fit", "preempt", 3)]
        assert RECOVERY_LOG.count(action="preempted", site="fit") == 1
        ckpts = [e.detail for e in RECOVERY_LOG.events(site="fit",
                                                       action="checkpoint")]
        assert any("20/40" in d for d in ckpts)
        assert "finished" in ckpts[-1]
        # deterministic lineage replay: identical to an uninterrupted fit
        straight = LinearRegression(max_iter=40, reg_param=0.1,
                                    elastic_net_param=0.5, tol=0.0).fit(f)
        np.testing.assert_allclose(model.coefficients,
                                   straight.coefficients, rtol=1e-12)

    def test_finished_checkpoint_resumes_without_refit(self, tmp_path):
        f = _frame()
        ck = str(tmp_path / "ck")
        est = LinearRegression(max_iter=10)
        m1 = recovery.fit_or_resume(est, f, ck, checkpoint_every=5)
        RECOVERY_LOG.clear()
        calls = {"n": 0}

        class Counting(LinearRegression):
            def fit(self, frame, mesh=None):
                calls["n"] += 1
                return super().fit(frame, mesh=mesh)

        m2 = recovery.fit_or_resume(Counting(max_iter=10), f, ck,
                                    checkpoint_every=5)
        assert calls["n"] == 0
        assert RECOVERY_LOG.count(action="resumed") == 1
        np.testing.assert_allclose(m1.coefficients, m2.coefficients)

    def test_unfinished_cursor_never_returned_as_final(self, tmp_path):
        """A stage whose progress.json says finished=false must not be
        handed back as the final model — even by a later call that
        doesn't ask for segmented fitting (it refits in full)."""
        f = _frame()
        ck = str(tmp_path / "ck")
        est = LinearRegression(max_iter=40, reg_param=0.1,
                               elastic_net_param=0.5, tol=0.0)
        # simulate a kill after the first segment: fit 10/40 and rewrite
        # the cursor as unfinished
        seg = LinearRegression(max_iter=10, reg_param=0.1,
                               elastic_net_param=0.5, tol=0.0).fit(f)
        recovery._atomic_save(seg, ck, progress={
            "budget": 10, "total": 40, "finished": False})
        m = recovery.fit_or_resume(est, f, ck)      # no checkpoint_every
        straight = LinearRegression(max_iter=40, reg_param=0.1,
                                    elastic_net_param=0.5, tol=0.0).fit(f)
        np.testing.assert_allclose(m.coefficients, straight.coefficients,
                                   rtol=1e-12)

    def test_runaway_preemption_gives_up(self, tmp_path):
        f = _frame()
        with faults.inject_faults("fit:preempt:p=1.0"):
            with pytest.raises(FitFailure, match="preempted"):
                recovery.fit_or_resume(LinearRegression(max_iter=5), f,
                                       str(tmp_path / "ck"),
                                       max_preemptions=3)
        assert RECOVERY_LOG.count(action="preempted") == 3


class TestDeviceDrop:
    def test_mesh_degrades_by_n_devices(self):
        mesh = make_mesh()
        n = mesh.devices.size
        with faults.inject_faults("mesh:device_drop:n=2") as plan:
            smaller = faults.degrade_mesh("mesh", mesh)
        assert smaller.devices.size == max(1, n - 2)
        assert plan.fired == [("mesh", "device_drop", 1)]

    def test_session_mesh_shrinks_under_plan(self):
        from sparkdq4ml_tpu.session import TpuSession

        full = make_mesh().devices.size
        s = TpuSession(conf={"spark.faults": "mesh:device_drop:n=1",
                             "spark.compilation.cache": "off"})
        try:
            assert s.mesh.devices.size == max(1, full - 1)
        finally:
            faults.clear()

    def test_conf_installed_plan_cleared_on_stop(self):
        """Chaos is session-scoped: a conf-installed plan must not leak
        into later, chaos-free sessions after stop()."""
        from sparkdq4ml_tpu.session import TpuSession

        s = TpuSession(conf={"spark.faults": "solver:device_error:1,2,3",
                             "spark.compilation.cache": "off"})
        assert faults.active() is not None
        s.stop()
        assert faults.active() is None

    def test_get_or_create_installs_late_fault_conf(self):
        from sparkdq4ml_tpu import session as sess_mod
        from sparkdq4ml_tpu.session import TpuSession

        prev = sess_mod._ACTIVE
        sess_mod._ACTIVE = None
        try:
            s = TpuSession.builder() \
                .config("spark.compilation.cache", "off").get_or_create()
            assert faults.active() is None
            TpuSession.builder() \
                .config("spark.faults", "solver:device_error:1") \
                .get_or_create()
            assert faults.active() is not None
            s.stop()
            assert faults.active() is None
        finally:
            sess_mod._ACTIVE = prev

    def test_fit_still_correct_on_degraded_mesh(self):
        f = _frame()
        mesh = make_mesh()
        with faults.inject_faults("mesh:device_drop:n=6"):
            degraded = faults.degrade_mesh("mesh", mesh)
        model = LinearRegression(max_iter=10).fit(f, mesh=degraded)
        assert model.coefficients[0] == pytest.approx(3.0, abs=0.05)
        assert len(RECOVERY_LOG) == 0   # degraded ≠ failing: no recovery


# ---------------------------------------------------------------------------
# The zero-overhead guarantee
# ---------------------------------------------------------------------------

class TestCleanRunIsSilent:
    def test_no_faults_no_events(self):
        f = _frame()
        model = LinearRegression(max_iter=10).fit(f)
        rng = np.random.default_rng(0)
        X = rng.normal(size=(16, 2))
        compute_gram(X, rng.normal(size=16), np.ones(16, bool),
                     mesh=make_mesh())
        assert np.isfinite(model.coefficients).all()
        assert len(RECOVERY_LOG) == 0
        assert profiling.counters.snapshot("recovery.") == {}

    def test_clean_fit_or_resume_records_only_lifecycle(self, tmp_path):
        f = _frame()
        recovery.fit_or_resume(LinearRegression(max_iter=5), f,
                               str(tmp_path / "ck"))
        assert RECOVERY_LOG.count(action="retry") == 0
        assert RECOVERY_LOG.count(action="fallback") == 0
        assert RECOVERY_LOG.count(action="preempted") == 0


class TestTelemetrySurface:
    def test_event_kv_rendering(self):
        ev = RECOVERY_LOG.record("s", "retry", attempt=2, rung="primary",
                                 cause="boom boom", backoff_s=0.25)
        line = ev.as_kv()
        assert "site=s" in line and "attempt=2" in line
        assert 'cause="boom boom"' in line and "backoff_s=0.25" in line

    def test_counters_mirror_actions(self):
        RECOVERY_LOG.record("s", "retry")
        RECOVERY_LOG.record("s", "fallback")
        RECOVERY_LOG.record("s", "fallback")
        snap = profiling.counters.snapshot("recovery.")
        assert snap["recovery.retry"] == 1
        assert snap["recovery.fallback"] == 2

    def test_session_exposes_the_log(self):
        from sparkdq4ml_tpu.session import TpuSession

        s = TpuSession(conf={"spark.compilation.cache": "off"})
        assert s.recovery_log is RECOVERY_LOG

    def test_log_is_bounded(self):
        log = recovery.RecoveryLog(maxlen=5)
        for i in range(12):
            log.record("s", "retry", attempt=i)
        assert len(log) == 5
        assert [e.attempt for e in log.events()] == list(range(7, 12))
