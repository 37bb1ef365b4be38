"""The tracer's bridge to the jax profiler (tier-1).

One gate that follows the profiler (``Tracer.recording``), spans written
into a capture as ``dq.<name>`` annotations on the device's clock, the
layer-boundary spans both benchmark cells cross, ``jax.named_scope`` inside
the compiled programs, and the ``host.reads`` / ``host.read_bytes``
counters. Everything here runs on the CPU backend: it pins names, parents,
counts and metadata — no timing is taken from it.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdq4ml_tpu.utils import observability as obs
from sparkdq4ml_tpu.utils import profiling

pytestmark = pytest.mark.obs

ROWS = 512


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


class capture:
    """A short real profiler session with the benchmark harness's options
    (python tracer off, host tracer 2)."""

    def __init__(self, directory):
        self.directory = str(directory)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    def host_events(self, prefix=("dq.", "bench.")):
        """[(name, start_ns, end_ns, stats)] of the capture's host plane."""
        from jax.profiler import ProfileData

        path = glob.glob(os.path.join(self.directory, "plugins", "profile",
                                      "*", "*.xplane.pb"))[0]
        out = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
        return out


# ---------------------------------------------------------------------------
# 1. One gate: the explicit flag, or a profiler session
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("flag", [False, True])
@pytest.mark.parametrize("session", [False, True])
def test_gate_has_four_states(flag, session, tmp_path):
    if flag:
        obs.enable()
    if session:
        with capture(tmp_path):
            assert obs.profiler_active()
            assert obs.TRACER.recording
            assert obs.TRACER.enabled is flag       # two separate facts
            with obs.span("inside", cat="t"):
                pass
    else:
        assert not obs.profiler_active()
        assert obs.TRACER.recording is flag
        with obs.span("inside", cat="t"):
            pass
    recorded = [s.name for s in obs.TRACER.spans()]
    assert recorded == (["inside"] if flag or session else [])
    assert not obs.profiler_active()                # the session is over
    assert obs.TRACER.recording is flag
    assert obs.enabled() is flag


def test_gate_follows_a_managed_capture(tmp_path, monkeypatch):
    """The operator's /profile/trace route (profiling.start_capture) turns
    the tracer on for as long as the capture runs, and no longer."""
    monkeypatch.setenv("SPARKDQ4ML_CAPTURE_DIR", str(tmp_path))
    assert not obs.TRACER.recording
    path = profiling.start_capture(30.0, label="bridge")
    try:
        assert obs.TRACER.recording and not obs.TRACER.enabled
        with obs.span("while.capturing", cat="t"):
            pass
    finally:
        assert profiling.stop_capture() == path
    assert not obs.TRACER.recording
    with obs.span("after", cat="t"):
        pass
    assert [s.name for s in obs.TRACER.spans()] == ["while.capturing"]


def test_off_sites_return_the_shared_noop():
    assert obs.span("x", cat="t") is obs._NOOP
    assert obs.TRACER.span("x") is obs._NOOP
    assert obs.TRACER.begin("x") is obs._NOOP
    assert obs.current_span() is obs._NOOP
    assert obs.current_ids() == (None, None)
    obs.emit_span("late", dur_ms=1.0)
    with obs.fit_span("fit.x", max_iter=1) as s:
        assert s is obs._NOOP
    assert obs.TRACER.spans() == []


def test_query_stats_restores_the_flag_under_a_session(tmp_path):
    """query_stats saves and restores the explicit flag only; a profiler
    session running meanwhile neither leaks into it nor is stopped."""
    with capture(tmp_path):
        assert not obs.TRACER.enabled
        with obs.query_stats(sample_memory=False) as qs:
            assert obs.TRACER.enabled
            with obs.span("q", cat="t"):
                pass
        assert not obs.TRACER.enabled
        assert obs.TRACER.recording                 # the session goes on
        assert [s.name for s in qs.spans] == ["q"]


def test_session_stop_leaves_profiler_driven_recording_alone(tmp_path):
    from sparkdq4ml_tpu import TpuSession

    with capture(tmp_path):
        s = (TpuSession.builder().app_name("bridge").master("local[*]")
             .get_or_create())
        assert not obs.TRACER.enabled               # no conf, no env
        s.stop()
        assert not obs.TRACER.enabled
        assert obs.TRACER.recording
        with obs.span("after.stop", cat="t"):
            pass
    assert [x.name for x in obs.TRACER.spans()][-1] == "after.stop"


# ---------------------------------------------------------------------------
# 2. Spans on the capture's host line, with sid / parent, nested
# ---------------------------------------------------------------------------


def test_spans_become_nested_annotations_in_the_capture(tmp_path):
    with capture(tmp_path) as cap:
        with jax.profiler.TraceAnnotation("bench.fit"):
            with obs.span("fit.prepare", cat="fit") as outer:
                with obs.span("fit.pack", cat="fit") as inner:
                    jnp.ones((64,)).block_until_ready()
            obs.emit_span("serve.queue", dur_ms=5.0)    # back-dated
            root = obs.TRACER.begin("session", cat="session")
            obs.TRACER.end(root)
    events = {name: (start, end, stats)
              for name, start, end, stats in cap.host_events()}
    assert set(events) == {"bench.fit", "dq.fit.prepare", "dq.fit.pack"}
    bench, prep, pack = (events["bench.fit"], events["dq.fit.prepare"],
                         events["dq.fit.pack"])
    assert bench[0] <= prep[0] <= pack[0] <= pack[1] <= prep[1] <= bench[1]
    assert prep[2] == {"sid": outer.sid}            # a root: no parent
    assert pack[2] == {"sid": inner.sid, "parent": outer.sid}
    # the tracer's buffer holds all four; only with-style spans are written
    assert {s.name for s in obs.TRACER.spans()} == {
        "fit.prepare", "fit.pack", "serve.queue", "session"}


def test_annotation_is_closed_when_the_span_raises(tmp_path):
    with capture(tmp_path) as cap:
        with pytest.raises(ValueError):
            with obs.span("boom", cat="t"):
                raise ValueError("x")
        with obs.span("next", cat="t"):
            pass
    names = [name for name, *_ in cap.host_events()]
    assert sorted(names) == ["dq.boom", "dq.next"]
    spans = {s.name: s for s in obs.TRACER.spans()}
    assert spans["boom"].attrs["error"] == "ValueError"
    assert spans["next"].parent_id is None          # the stack unwound


def test_no_annotation_without_a_session():
    obs.enable()
    with obs.span("flag.only", cat="t") as s:
        assert s._annotation is None
    assert s.start_s > 0.0 and s.dur_us is not None


# ---------------------------------------------------------------------------
# 3. Profiler-driven spans cost no census, no device wait, no host read
# ---------------------------------------------------------------------------


def test_no_census_on_span_completion_in_profiler_driven_mode(
        tmp_path, monkeypatch):
    from sparkdq4ml_tpu.utils import meminfo

    def census():
        raise AssertionError("live-array census on the span path")

    monkeypatch.setattr(meminfo, "live_bytes", census)
    monkeypatch.setattr(jax, "live_arrays", census)
    with capture(tmp_path):
        for _ in range(3):
            with obs.span("op", cat="frame"):
                pass
    assert len(obs.TRACER.spans()) == 3
    assert obs.TRACER.counter_samples() == []
    assert obs.METRICS.snapshot().get("mem.live_bytes") is None


def test_explicit_flag_still_samples_the_counter_tracks():
    obs.enable()
    with obs.span("op", cat="frame"):
        pass
    samples = obs.TRACER.counter_samples()
    assert samples and "mem.live_bytes" in samples[0][1]


def test_sharded_gram_blocks_under_the_explicit_flag_only(
        tmp_path, monkeypatch):
    """The one site that blocks for honest timing adds its device wait
    under the explicit flag; recording for a profiler adds none."""
    from sparkdq4ml_tpu.parallel.distributed import compute_gram
    from sparkdq4ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 3))
    y = rng.normal(size=(64,))
    mask = np.ones((64,), bool)
    compute_gram(X, y, mask, mesh)                  # compile outside
    waits = []
    orig = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda t: waits.append(1) or orig(t))

    def waits_of(run):
        del waits[:]
        run()
        return len(waits)

    def under_capture():
        with capture(tmp_path):
            compute_gram(X, y, mask, mesh)

    off = waits_of(lambda: compute_gram(X, y, mask, mesh))
    assert obs.TRACER.spans() == []
    assert waits_of(under_capture) == off
    assert "parallel.gram_shard" in [s.name for s in obs.TRACER.spans()]
    obs.enable()
    assert waits_of(lambda: compute_gram(X, y, mask, mesh)) == off + 1


# ---------------------------------------------------------------------------
# 4. The layer-boundary spans of both cells' flows, with their parents
# ---------------------------------------------------------------------------


def _tree(spans):
    by_sid = {s.sid: s for s in spans}
    return [(s.name, by_sid[s.parent_id].name if s.parent_id in by_sid
             else None, s) for s in spans]


def _catering_flow(session):
    """The reference app's flow (benchmarks/jobs/dq_lasso.py), small."""
    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.models import (LinearRegression, VectorAssembler,
                                       Vectors)

    session.udf.register("minimumPriceRule", dq.minimum_price_rule, "double")
    session.udf.register("priceCorrelationRule", dq.price_correlation_rule,
                         "double")
    rng = np.random.default_rng(3)
    guest = rng.integers(14, 39, ROWS).astype(np.int32)
    price = (guest * 5.0 + 12.0 + rng.normal(0, 2, ROWS)).astype(np.float32)
    frame = session.create_data_frame({"guest": jnp.asarray(guest),
                                       "price": jnp.asarray(price)})
    df = frame.with_column(
        "price_no_min", dq.call_udf("minimumPriceRule", frame.col("price")))
    df.create_or_replace_temp_view("price")
    df = session.sql("SELECT cast(guest as int) guest, price_no_min AS "
                     "price FROM price WHERE price_no_min > 0")
    df.count()
    df = df.with_column(
        "price_correct_correl",
        dq.call_udf("priceCorrelationRule", df.col("price"),
                    df.col("guest")))
    df.create_or_replace_temp_view("price")
    df = session.sql("SELECT guest, price_correct_correl AS price FROM "
                     "price WHERE price_correct_correl > 0")
    df.count()
    df = df.with_column("label", df.col("price"))
    feats = (VectorAssembler().setInputCols(["guest"])
             .setOutputCol("features").transform(df))
    model = (LinearRegression().setMaxIter(40).setRegParam(1.0)
             .setElasticNetParam(1.0).fit(feats))
    model.predict(Vectors.dense(40.0))
    scored = model.transform(feats)
    scored.create_or_replace_temp_view("scored")
    session.sql("SELECT count(*) AS n, avg((prediction - label) * "
                "(prediction - label)) AS mse FROM scored").to_pydict()


def _higgs_flow(session):
    """The filter -> assemble -> logistic fit -> score flow
    (benchmarks/jobs/filter_fit_score.py), small."""
    from sparkdq4ml_tpu.models import LogisticRegression, VectorAssembler

    rng = np.random.default_rng(5)
    names = [f"x{i}" for i in range(4)]
    cols = {n: rng.normal(size=ROWS).astype(np.float32) for n in names}
    logit = cols["x0"] - 0.5 * cols["x1"] + 0.25
    label = (rng.random(ROWS) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    frame = session.create_data_frame(
        {**{n: jnp.asarray(v) for n, v in cols.items()},
         "label": jnp.asarray(label)})
    frame.create_or_replace_temp_view("t")
    kept = session.sql("SELECT * FROM t WHERE x0 > -1.5 AND x3 > -1.5")
    feats = VectorAssembler(names, "features").transform(kept)
    model = LogisticRegression(max_iter=50, tol=1e-6).fit(feats)
    scored = model.transform(feats)
    scored.create_or_replace_temp_view("scored")
    session.sql("SELECT count(*) AS n, avg(probability) AS m FROM "
                "scored").to_pydict()
    model.predict(np.zeros(4))


def _q3_flow(session):
    """Join, GROUP BY, ORDER BY (benchmarks/jobs/tpch_q3.py), small."""
    rng = np.random.default_rng(7)
    orders = session.create_data_frame(
        {"o_key": jnp.arange(64, dtype=jnp.int32),
         "o_pri": jnp.asarray(rng.integers(0, 4, 64).astype(np.int32))})
    items = session.create_data_frame(
        {"l_key": jnp.asarray(np.sort(rng.integers(0, 64, ROWS))
                              .astype(np.int32)),
         "l_price": jnp.asarray(rng.random(ROWS).astype(np.float32))})
    orders.create_or_replace_temp_view("orders")
    items.create_or_replace_temp_view("lineitem")
    session.sql("SELECT o_pri, sum(l_price) AS revenue FROM orders JOIN "
                "lineitem ON o_key = l_key WHERE l_price > 0.1 GROUP BY "
                "o_pri ORDER BY revenue DESC").to_pydict()


FLOWS = {"catering": _catering_flow, "higgs": _higgs_flow, "q3": _q3_flow}
_FLOW_SPANS = {}


@pytest.fixture
def flow_spans(request, tmp_path):
    """The spans of one job of a cell's flow, recorded once per flow —
    because a profiler session is on, nothing else — and shared by the
    cases below."""
    flow = request.param
    if flow not in _FLOW_SPANS:
        from sparkdq4ml_tpu import TpuSession

        session = (TpuSession.builder().app_name("bridge")
                   .master("local[*]").get_or_create())
        try:
            FLOWS[flow](session)                     # warm: compiles
            obs.TRACER.clear()
            profiling.counters.clear()
            with capture(tmp_path):
                FLOWS[flow](session)
            _FLOW_SPANS[flow] = (_tree(obs.TRACER.spans()),
                                 profiling.counters.snapshot())
        finally:
            session.stop()
    return _FLOW_SPANS[flow]


# (flow, span, how many a job, its parent)
SPAN_TABLE = [
    ("catering", "dq.rule", 2, "frame.pipeline.flush"),
    ("catering", "frame.count", 2, None),
    ("catering", "sql.parse", 3, "sql.query"),
    ("catering", "sql.optimize", 3, "sql.query"),
    ("catering", "sql.execute", 3, "sql.query"),
    ("catering", "feature.assemble", 1, None),
    ("catering", "fit.linear_regression", 1, None),
    ("catering", "fit.prepare", 1, "fit.linear_regression"),
    ("catering", "fit.extract", 1, "fit.prepare"),
    ("catering", "fit.pack", 1, "fit.prepare"),
    ("catering", "fit.solve", 1, "fit.linear_regression"),
    ("catering", "model.predict", 1, None),
    ("catering", "model.transform", 1, None),
    ("higgs", "sql.parse", 2, "sql.query"),
    ("higgs", "sql.optimize", 2, "sql.query"),
    ("higgs", "sql.execute", 2, "sql.query"),
    ("higgs", "feature.assemble", 1, None),
    ("higgs", "fit.logistic_regression", 1, None),
    ("higgs", "fit.prepare", 1, "fit.logistic_regression"),
    ("higgs", "fit.extract", 1, "fit.prepare"),
    ("higgs", "fit.validate", 1, "fit.prepare"),
    ("higgs", "fit.pack", 1, "fit.prepare"),
    ("higgs", "fit.solve", 1, "fit.logistic_regression"),
    ("higgs", "model.transform", 1, None),
    ("higgs", "model.predict", 1, None),
]


@pytest.mark.parametrize(
    "flow_spans,name,count,parent",
    [pytest.param(f, n, c, p, id=f"{f}-{n}") for f, n, c, p in SPAN_TABLE],
    indirect=["flow_spans"])
def test_layer_boundary_span(flow_spans, name, count, parent):
    tree, _ = flow_spans
    found = [(n, p, s) for n, p, s in tree if n == name]
    assert len(found) == count, [n for n, _, _ in tree]
    assert {p for _, p, _ in found} == {parent}


@pytest.mark.parametrize("flow_spans", ["catering", "higgs"],
                         indirect=True)
def test_prepare_and_solve_cover_the_fit_root(flow_spans):
    tree, _ = flow_spans
    root = next(s for n, _, s in tree if n in ("fit.linear_regression",
                                               "fit.logistic_regression"))
    kids = [s for _, _, s in tree if s.parent_id == root.sid]
    assert [k.name for k in kids] == ["fit.prepare", "fit.solve"]
    covered = sum(k.dur_us for k in kids)
    self_us = root.dur_us - covered
    # the children lie inside the root, one after the other, and what they
    # leave is the root's self time: the probes, the ladder, the model
    assert 0 <= self_us < root.dur_us
    for k in kids:
        assert root.start_s <= k.start_s
        assert k.start_s + k.dur_us * 1e-6 <= \
            root.start_s + root.dur_us * 1e-6 + 1e-5
    assert kids[0].start_s + kids[0].dur_us * 1e-6 <= kids[1].start_s + 1e-5
    assert root.attrs["iterations"] >= 1 and "compile" in root.attrs
    solve = kids[1]
    assert solve.attrs["iterations"] == root.attrs["iterations"]
    assert solve.attrs["converged"] == root.attrs["converged"]


@pytest.mark.parametrize("flow_spans", ["catering", "higgs"],
                         indirect=True)
def test_span_attributes_carry_the_counts(flow_spans):
    tree, _ = flow_spans
    attrs = {}
    for n, _, s in tree:
        attrs.setdefault(n, s.attrs)
    assert attrs["feature.assemble"]["columns"] >= 1
    assert attrs["feature.assemble"]["width"] == \
        attrs["feature.assemble"]["columns"]
    assert attrs["fit.prepare"]["rows"] == ROWS
    assert attrs["model.transform"]["rows"] == ROWS
    assert attrs["sql.optimize"]["rewrites"] >= 0
    if "dq.rule" in attrs:
        # both rules are row-local, so each runs inside the flush of its
        # SQL statement: the span lies under that flush's
        assert attrs["dq.rule"] == {"rule": "minimumPriceRule",
                                    "rows": ROWS, "lowering": "in-flush"}
        assert attrs["frame.count"]["host_read_bytes"] in (4, 8)
    if "fit.validate" in attrs:
        # the stats vector of base.label_stats, whatever the row count:
        # 5 scalars, float64 here (conftest), float32 on the chip
        assert attrs["fit.validate"]["host_read_bytes"] == 5 * 8


@pytest.mark.parametrize("flow_spans", ["catering", "higgs"],
                         indirect=True)
def test_rule_evals_and_rule_spans_count_alike(flow_spans):
    tree, counters = flow_spans
    rules = sum(1 for n, _, _ in tree if n == "dq.rule")
    assert counters.get("dq.rule_evals", 0) == rules
    # ... and every one of them ran inside a flush's program
    assert counters.get("dq.rule_in_flush", 0) == rules
    assert counters.get("dq.rule_eager", 0) == 0
    assert counters.get("pipeline.fallback", 0) == 0
    assert counters["host.reads"] >= 3
    assert counters["host.read_bytes"] > 0
    # a profiler session changes no counter a job reads: the fit root's
    # compile verdict mirrors into jit.trace_* under the explicit flag only
    assert not [k for k in counters if k.startswith("jit.trace_")]


# ---------------------------------------------------------------------------
# 5. host.reads / host.read_bytes move by known amounts
# ---------------------------------------------------------------------------


def _moved(before):
    now = profiling.counters.snapshot()
    return {k: now.get(k, 0) - before.get(k, 0)
            for k in ("host.reads", "host.read_bytes", "frame.host_sync")}


def test_count_is_one_small_read_and_no_host_sync():
    from sparkdq4ml_tpu.frame.frame import Frame

    f = Frame({"a": jnp.arange(1000.0)})
    f.count()                                       # compile outside
    before = profiling.counters.snapshot()
    assert f.count() == 1000
    moved = _moved(before)
    assert moved["host.reads"] == 1
    assert moved["host.read_bytes"] == jnp.sum(f.mask).dtype.itemsize
    assert moved["frame.host_sync"] == 0            # the pinned contract
    assert obs.TRACER.spans() == []                 # off: no span


def test_to_pydict_counts_its_one_batched_pull():
    from sparkdq4ml_tpu.frame.frame import Frame

    f = Frame({"a": jnp.arange(100, dtype=jnp.float32),
               "b": jnp.arange(100, dtype=jnp.int32)})
    before = profiling.counters.snapshot()
    f.to_pydict()
    moved = _moved(before)
    assert moved["host.reads"] == 1 and moved["frame.host_sync"] == 1
    assert moved["host.read_bytes"] == 100 * 4 + 100 * 4 + 100   # + mask


@pytest.mark.parametrize("n", [300, 30_000])
def test_logistic_fit_reads_the_stats_vector_and_the_result(n):
    """Two counted reads a fit, and their bytes do not depend on n: the
    labels are validated on the device (base.label_stats)."""
    from sparkdq4ml_tpu.frame.frame import Frame
    from sparkdq4ml_tpu.models import LogisticRegression

    d, iters = 3, 20
    rng = np.random.default_rng(1)
    X = rng.normal(size=(n, d))
    y = (rng.random(n) < 0.5).astype(np.float64)
    f = Frame({"features": jnp.asarray(X), "label": jnp.asarray(y)})
    before = profiling.counters.snapshot()
    LogisticRegression(max_iter=iters).fit(f, mesh=None)
    moved = _moved(before)
    item = jnp.asarray(y).dtype.itemsize
    stats = 5 * item                        # rows, min, max, two flags
    flat = (d + 3 + iters + 1) * item       # coef, 3 scalars, history
    assert moved["host.reads"] == 2         # stats vector, packed result
    assert moved["host.read_bytes"] == stats + flat
    assert moved["frame.host_sync"] == 0


def test_linear_fit_reads_its_result_only():
    from sparkdq4ml_tpu.frame.frame import Frame
    from sparkdq4ml_tpu.models import LinearRegression

    n, iters = 64, 5
    f = Frame({"features": jnp.arange(float(n))[:, None],
               "label": 2.0 * jnp.arange(float(n)) + 1.0})
    before = profiling.counters.snapshot()
    LinearRegression(max_iter=iters, reg_param=0.1,
                     elastic_net_param=1.0).fit(f, mesh=None)
    moved = _moved(before)
    assert moved["host.reads"] == 1
    item = jnp.arange(1.0).dtype.itemsize
    assert moved["host.read_bytes"] == (1 + 3 + iters + 1) * item
    assert moved["frame.host_sync"] == 0


def test_host_read_counters_are_declared():
    assert obs.METRIC_NAMES["host.reads"][0] == "counter"
    assert obs.METRIC_NAMES["host.read_bytes"][0] == "counter"
    profiling.host_read(10)
    profiling.host_read(5)
    assert profiling.counters.get("host.reads") == 2
    assert profiling.counters.get("host.read_bytes") == 15
    assert "sparkdq4ml_host_read_bytes 15" in obs.prometheus_text()


# ---------------------------------------------------------------------------
# 5b. host_reading: the wrapper every blocking read runs inside
# ---------------------------------------------------------------------------


def _read_cases():
    """{case: (call, reads, bytes)}: the sites a small frame, fit, grouped
    plan and join reach, with what ``host_read`` counted there before the
    wrapper (read off the parent commit on these inputs)."""
    from sparkdq4ml_tpu.frame import aggregates as A
    from sparkdq4ml_tpu.frame.frame import Frame
    from sparkdq4ml_tpu.models import LinearRegression, LogisticRegression

    rng = np.random.default_rng(0)
    n = 1000
    f = Frame({"k": jnp.asarray(rng.integers(0, 7, n).astype(np.int32)),
               "v": jnp.asarray(rng.normal(size=n).astype(np.float32))})
    g = Frame({"k": jnp.arange(7, dtype=jnp.int32),
               "w": jnp.arange(7, dtype=jnp.float32)})
    fit = Frame({
        "features": jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32)),
        "label": jnp.asarray((rng.random(n) < 0.5).astype(np.float32))})
    count = jnp.sum(f.mask).dtype.itemsize
    item = jnp.arange(1.0).dtype.itemsize       # float64 here (conftest)
    return {
        "frame.count": (f.count, 1, count),
        "frame.mask": (f._host_mask, 1, n),
        "frame.to_pydict": (f.to_pydict, 1, 4 * n + 4 * n + n),
        # the mask, then the five-row prefixes
        "frame.to_pydict-limit": (lambda: f.to_pydict(limit=5), 2,
                                  n + 5 * 8),
        "grouped.verdict": (lambda: f.group_by("k").agg(A.sum("v")), 1, 10),
        "sort.keys": (lambda: f.filter(f.col("v") > 0).sort("v"), 1,
                      4 * n + n),
        "join.verdict": (lambda: f.join(g, ["k"], "inner"), 1, 4),
        "agg.verdict": (lambda: f.agg(A.min("v")), 1, 4),
        # the stats vector and the packed result: coef, 3 scalars, history
        "fit.label_stats": (
            lambda: LogisticRegression(max_iter=20).fit(fit, mesh=None), 2,
            5 * item + (3 + 3 + 20 + 1) * item),
        "fit.result": (
            lambda: LinearRegression(max_iter=5, reg_param=0.1,
                                     elastic_net_param=1.0)
            .fit(fit, mesh=None), 1, (3 + 3 + 5 + 1) * item),
    }


READ_CASES = ("frame.count", "frame.mask", "frame.to_pydict",
              "frame.to_pydict-limit", "grouped.verdict", "sort.keys",
              "join.verdict", "agg.verdict", "fit.label_stats", "fit.result")


@pytest.mark.parametrize("case", READ_CASES)
def test_off_a_read_counts_as_before_and_makes_no_span(case, monkeypatch):
    call, reads, nbytes = _read_cases()[case]
    call()                                          # compile outside

    def no_span(*a, **kw):
        raise AssertionError("a Span was made with the tracer off")

    monkeypatch.setattr(obs.Span, "__init__", no_span)
    before = profiling.counters.snapshot()
    call()
    moved = _moved(before)
    assert (moved["host.reads"], moved["host.read_bytes"]) == (reads, nbytes)
    assert obs.TRACER.spans() == []


def test_off_the_wrapper_is_one_shared_object_that_only_counts():
    a, b = obs.host_reading("frame.count"), obs.host_reading("fit.result")
    assert a is b and not isinstance(a, obs.Span)
    with a as rd:
        rd.done(12)
    assert profiling.counters.get("host.reads") == 1
    assert profiling.counters.get("host.read_bytes") == 12
    assert obs.TRACER.spans() == []


@pytest.mark.parametrize("case", READ_CASES)
def test_recording_the_same_read_is_a_span_with_its_site_and_bytes(case):
    call, reads, nbytes = _read_cases()[case]
    call()
    obs.enable()
    before = profiling.counters.snapshot()
    call()
    obs.disable()
    moved = _moved(before)
    found = [s for s in obs.TRACER.spans() if s.name == "host.read"]
    # the counters do not know that anyone recorded; a span a read
    assert (moved["host.reads"], moved["host.read_bytes"]) == (reads, nbytes)
    assert len(found) == reads
    assert sum(s.attrs["bytes"] for s in found) == nbytes
    assert case.split("-")[0] in {s.attrs["site"] for s in found}
    assert all(s.cat == "host" and s.dur_us is not None for s in found)


# (flow, the span that holds the read, the read's site)
READ_PARENTS = [
    ("catering", "frame.count", "frame.count"),
    ("catering", "fit.solve", "fit.result"),
    ("catering", "frame.to_pydict", "frame.to_pydict"),
    ("higgs", "fit.validate", "fit.label_stats"),
    ("higgs", "fit.solve", "fit.result"),
    ("higgs", "frame.to_pydict", "frame.to_pydict"),
    ("q3", "frame.grouped.flush", "grouped.verdict"),
    ("q3", "frame.join", "join.verdict"),
    ("q3", "frame.to_pydict", "frame.to_pydict"),
]


@pytest.mark.parametrize(
    "flow_spans,parent,site",
    [pytest.param(f, p, s, id=f"{f}-{p}") for f, p, s in READ_PARENTS],
    indirect=["flow_spans"])
def test_a_boundary_span_holds_its_read_and_its_self_time_leaves_it_out(
        flow_spans, parent, site):
    tree, _ = flow_spans
    reads = [s for n, p, s in tree
             if n == "host.read" and p == parent and s.attrs["site"] == site]
    assert reads, [(n, p, s.attrs.get("site")) for n, p, s in tree
                   if n == "host.read"]
    read = reads[0]
    assert read.attrs["bytes"] > 0 and read.cat == "host"
    holder = next(s for _, _, s in tree if s.sid == read.parent_id)
    lo, hi = holder.start_s, holder.start_s + holder.dur_us * 1e-6
    assert lo <= read.start_s and \
        read.start_s + read.dur_us * 1e-6 <= hi + 1e-5
    # the holder's self time as the benchmark computes it: its length less
    # what its children cover, the read among them
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import program_spans

    timed = program_spans.with_self_time(
        [{"name": s.name, "sid": s.sid, "parent": s.parent_id,
          "start_s": s.start_s, "dur_s": s.dur_us * 1e-6}
         for _, _, s in tree])
    self_s = next(t["self_s"] for t in timed if t["sid"] == holder.sid)
    assert -1e-5 <= self_s <= (holder.dur_us - read.dur_us) * 1e-6 + 1e-5


@pytest.mark.parametrize("flow_spans", ["catering", "higgs", "q3"],
                         indirect=True)
def test_a_job_has_as_many_read_spans_as_counted_reads(flow_spans):
    tree, counters = flow_spans
    reads = [s for n, _, s in tree if n == "host.read"]
    assert len(reads) == counters["host.reads"] > 0
    assert sum(s.attrs["bytes"] for s in reads) == counters["host.read_bytes"]


def test_the_read_span_is_an_annotation_under_its_parents(tmp_path):
    from sparkdq4ml_tpu.frame.frame import Frame

    f = Frame({"a": jnp.arange(100.0)})
    f.count()
    with capture(tmp_path) as cap:
        f.count()
    events = {name: stats for name, _, _, stats in cap.host_events()}
    count = next(s for s in obs.TRACER.spans() if s.name == "frame.count")
    read = next(s for s in obs.TRACER.spans() if s.name == "host.read")
    assert events["dq.host.read"]["sid"] == read.sid
    assert events["dq.host.read"]["parent"] == count.sid == read.parent_id


SECOND_GROUP = ("stat.corr", "stat.cov", "stat.quantile", "stat.strata",
                "window.mask", "evaluation.pair", "distinct.groups",
                "distinct.keys")


@pytest.mark.parametrize("site", SECOND_GROUP)
def test_a_pull_that_counted_host_sync_alone_is_a_read_now(site):
    """The stat, window, evaluation and distinct pulls: ``host.reads``
    moves by one where ``frame.host_sync`` moves by one (a window moves
    once more for each device column of its plan)."""
    from sparkdq4ml_tpu.frame.frame import Frame
    from sparkdq4ml_tpu.frame.window import Window, row_number
    from sparkdq4ml_tpu.models import evaluation

    n = 64
    rng = np.random.default_rng(2)
    f = Frame({"k": jnp.asarray(rng.integers(0, 5, n).astype(np.int32)),
               "v": jnp.asarray(rng.normal(size=n).astype(np.float32))})
    strings = Frame({"s": np.asarray(["a", "b"] * 8, object),
                     "k": jnp.arange(16, dtype=jnp.int32) // 2})
    calls = {
        "stat.corr": lambda: f.stat.corr("k", "v"),
        "stat.cov": lambda: f.stat.cov("k", "v"),
        "stat.quantile": lambda: f.stat.approx_quantile("v", [0.5]),
        "stat.strata": lambda: f.stat.sample_by("k", {1: 0.5}),
        "window.mask": lambda: f.with_column(
            "r", row_number().over(Window.partition_by("k").order_by("v"))
        ).count(),
        "evaluation.pair": lambda: evaluation.threshold_sweep(
            (f._data["k"] > 2).astype(jnp.float32), f._data["v"]),
        "distinct.groups": lambda: f.select("k").distinct(),
        # a string key sends dropDuplicates to the host plan: the mask,
        # then the one device key column
        "distinct.keys": lambda: strings.drop_duplicates(["s", "k"]),
    }
    calls[site]()
    obs.enable()
    before = profiling.counters.snapshot()
    calls[site]()
    obs.disable()
    moved = _moved(before)
    sites = [s.attrs["site"] for s in obs.TRACER.spans()
             if s.name == "host.read"]
    assert site in sites
    assert moved["host.reads"] == len(sites) >= 1
    assert moved["host.read_bytes"] > 0
    if site.startswith(("stat.", "evaluation.", "distinct.groups")):
        assert moved["host.reads"] == 1 == moved["frame.host_sync"]


# ---------------------------------------------------------------------------
# 6. Named scopes inside the compiled programs: metadata, nothing else
# ---------------------------------------------------------------------------


def _lowered(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    return lowered.as_text(debug_info=True), lowered.as_text()


def test_scope_is_metadata_only():
    def plain(x):
        return (x * 2.0).sum()

    def scoped(x):
        with obs.scope("fit.gram"):
            return (x * 2.0).sum()

    x = jnp.ones((8, 4))
    with_names, bare = _lowered(scoped, x)
    assert "dq.fit.gram" in with_names and "dq.fit.gram" not in bare
    assert bare.replace("jit_scoped", "jit_plain") == _lowered(plain, x)[1]
    hlo = jax.jit(scoped).lower(x).compile().as_text()
    assert 'op_name="jit(scoped)/dq.fit.gram/' in hlo


def test_linear_fit_program_carries_gram_and_solve_scopes():
    from sparkdq4ml_tpu.parallel.distributed import fused_linear_fit_packed

    program = fused_linear_fit_packed(None, "fista", 5, 1e-6, True, True)
    Z = jnp.ones((32, 3))
    hyper = jnp.asarray([1.0, 1.0])
    text, _ = _lowered(program.trace_body, Z, hyper)
    assert "dq.fit.gram/dot_general" in text
    assert "dq.fit.solve/" in text


def test_logistic_newton_program_carries_its_six_scopes():
    from sparkdq4ml_tpu.models.classification import (
        fused_logistic_fit_packed)

    fit = fused_logistic_fit_packed(None, 5, 1e-6, True, True,
                                    solver="newton")
    text = fit.lower(jnp.ones((32, 5)), jnp.asarray([0.0, 0.0])).as_text(
        debug_info=True)
    for scope in ("fit.pack", "fit.newton.margin", "fit.newton.gradient",
                  "fit.newton.hessian", "fit.newton.line_search",
                  "fit.solve"):
        assert f"dq.{scope}/" in text, scope


def test_logistic_fista_program_names_its_data_pass():
    from sparkdq4ml_tpu.models.classification import (
        fused_logistic_fit_packed)

    fit = fused_logistic_fit_packed(None, 5, 1e-6, True, True,
                                    solver="fista")
    text = fit.lower(jnp.ones((32, 5)), jnp.asarray([0.1, 0.5])).as_text(
        debug_info=True)
    assert "dq.fit.pack/" in text and "dq.fit.fista.loss_grad/" in text


def test_flush_and_sketch_programs_carry_their_scopes(session):
    from sparkdq4ml_tpu.ops import compiler
    from sparkdq4ml_tpu.utils import dqprof

    compiler.clear_cache()
    frame = session.create_data_frame({"a": jnp.arange(100.0),
                                       "b": jnp.arange(100.0)})
    frame.create_or_replace_temp_view("t")
    session.sql("SELECT a + b AS c FROM t WHERE a > 3").count()
    flushes = [h for h in compiler.program_handles()]
    assert flushes, "the query built no flush program"
    h = flushes[0]
    text = jax.jit(h.fn).lower(*h.args, **h.kwargs).as_text(debug_info=True)
    assert "dq.flush/" in text
    sketches = dqprof.program_handles()
    assert sketches, "the flush dispatched no dq sketch"
    s = sketches[0]
    text = jax.jit(s.fn).lower(*s.args, **s.kwargs).as_text(debug_info=True)
    assert "dq.sketch/" in text


def test_grouped_program_carries_its_scope(session):
    from sparkdq4ml_tpu.ops import segments

    frame = session.create_data_frame(
        {"k": jnp.asarray(np.arange(64) % 4, jnp.int32),
         "v": jnp.arange(64.0)})
    frame.create_or_replace_temp_view("g")
    session.sql("SELECT k, sum(v) AS s FROM g GROUP BY k").to_pydict()
    handles = segments.program_handles()
    assert handles, "the GROUP BY built no grouped program"
    h = handles[0]
    text = jax.jit(h.fn).lower(*h.args, **h.kwargs).as_text(debug_info=True)
    assert "dq.grouped/" in text


# ---------------------------------------------------------------------------
# 7. fit_span: one root, several factories, one verdict
# ---------------------------------------------------------------------------


def test_fit_span_reports_a_miss_when_any_factory_traced():
    import functools

    @functools.lru_cache(maxsize=None)
    def cold(k):
        return k

    @functools.lru_cache(maxsize=None)
    def warm(k):
        return k

    warm(1)
    obs.enable()
    with obs.fit_span("fit.x", cold, warm, max_iter=3) as s:
        warm(1)
        cold(2)
    assert s.attrs["compile"] == "miss" and s.attrs["max_iter"] == 3
    with obs.fit_span("fit.x", cold, warm) as s2:
        warm(1)
        cold(2)
    assert s2.attrs["compile"] == "hit"
    with obs.fit_span("fit.x") as s3:
        pass
    assert s3.attrs["compile"] == "unknown"
    assert [x.cat for x in obs.TRACER.spans()] == ["fit"] * 3


def test_removed_timing_helpers_are_gone():
    import sparkdq4ml_tpu.utils as utils

    for name in ("PhaseTimer", "timed", "trace"):
        assert not hasattr(profiling, name)
        assert not hasattr(utils, name)
    assert callable(profiling.start_capture)        # the operator's route
