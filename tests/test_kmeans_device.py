"""KMeans' device entry (models/clustering.py): the fit against the
benchmark's plain reference (benchmarks/configs/hibench-kmeans.py) replayed
from the program's own history; dropped rows and their NaNs never vote and
are never drawn; k-means‖'s candidates are kept rows with the reference's
weights and its rounds draw what a sum of Bernoullis allows; the stopping
rule, the empty cluster, the bucket's overflow; the reads, spans, scopes
and counters; the Pallas kernel through the interpreter.

CPU, seeded, small sizes (the suite's float64; the kernel in float32).
Nothing here asserts a time.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.models import KMeans, KMeansModel, VectorAssembler
from sparkdq4ml_tpu.models import clustering as C
from sparkdq4ml_tpu.utils.profiling import counters

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402

REFERENCE = harness.load_module("configs", "hibench-kmeans")
WATCHED = ("host.reads", "host.read_bytes", "frame.host_sync",
           "kmeans.fit_device", "kmeans.iterations", "kmeans.data_passes",
           "kmeans.init_candidates", "kmeans.init_overflow")


def _blobs(n, d=4, clusters=5, seed=0, spread=0.3, dropped=0.0, nan=False):
    """(frame, columns as the reference takes them, kept): ``clusters``
    well-separated normal blobs; a share of the rows dropped by a filter,
    their slots holding NaN if asked."""
    rng = np.random.default_rng(seed)
    centres = rng.uniform(-10, 10, size=(clusters, d))
    which = rng.integers(clusters, size=n)
    X = (centres[which] + spread * rng.normal(size=(n, d))).astype(np.float32)
    keep = rng.random(n) >= dropped
    if nan:
        X[~keep] = np.nan
    names = [f"f{j}" for j in range(d)]
    frame = VectorAssembler(names, "features").transform(
        Frame({name: X[:, j] for j, name in enumerate(names)}))
    if dropped:
        frame = frame.filter(np.asarray(keep))
    return frame, [X[:, j] for j in range(d)], keep


def _delta(before):
    return {k: counters.get(k) - v for k, v in before.items()}


# ---------------------------------------------------------------------------
# the fit against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("init_mode", ["k-means||", "random"])
@pytest.mark.parametrize("seed", range(4))
def test_every_iteration_is_one_reference_step_from_the_one_before(
        seed, init_mode):
    frame, cols, keep = _blobs(3000, seed=seed, dropped=0.2, nan=True)
    model = KMeans(k=5, max_iter=6, seed=seed, init_mode=init_mode).fit(frame)
    summary = model.summary
    history = np.asarray(summary.history)
    assert history.shape == (summary.num_iter + 1, 5, 4)
    assert np.array_equal(history[-1], np.asarray(model.centers))
    ref = REFERENCE.replay(cols, keep, history)
    assert max(ref["gaps"]) < 1e-9
    assert list(ref["sizes"]) == list(summary.cluster_sizes)
    assert sum(summary.cluster_sizes) == int(keep.sum())
    assert summary.training_cost == pytest.approx(ref["cost"], rel=1e-5)
    assert model.compute_cost(frame) == pytest.approx(ref["cost"], rel=1e-5)


@pytest.mark.parametrize("seed", range(3))
def test_candidates_are_kept_rows_with_the_reference_weights(seed):
    frame, cols, keep = _blobs(2500, seed=10 + seed, dropped=0.3, nan=True)
    summary = KMeans(k=6, seed=seed).fit(frame).summary
    candidates, weights = summary.init_candidates
    assert len(candidates) == len(weights) > 6
    assert REFERENCE.candidate_check(cols, keep, candidates, weights) \
        == (0, 0.0)
    assert int(np.sum(weights)) == int(keep.sum())
    # the k initial centres are k distinct points
    assert len(np.unique(np.asarray(summary.history)[0], axis=0)) == 6


@pytest.mark.parametrize("seed", range(3))
def test_random_mode_returns_k_distinct_kept_rows(seed):
    frame, cols, keep = _blobs(800, seed=20 + seed, dropped=0.5, nan=True)
    model = KMeans(k=7, max_iter=0, seed=seed, init_mode="random").fit(frame)
    centres = np.asarray(model.centers)
    assert len(np.unique(centres, axis=0)) == 7
    assert REFERENCE.candidate_check(
        cols, keep, centres, np.zeros(7, np.int64))[0] == 0
    assert model.summary.init_candidates == (None, None)
    assert model.summary.num_iter == 0


def test_dropped_rows_pull_no_centre():
    frame, cols, keep = _blobs(600, seed=3, dropped=0.25)
    far = np.stack(cols, axis=1)
    far[~keep] = 1e6        # finite, dropped, and far from everything
    names = [f"f{j}" for j in range(4)]
    frame = VectorAssembler(names, "features").transform(
        Frame({n: far[:, j] for j, n in enumerate(names)})).filter(
        np.asarray(keep))
    model = KMeans(k=5, seed=1).fit(frame)
    assert np.abs(np.asarray(model.centers)).max() < 100.0
    assert np.abs(np.asarray(model.summary.init_candidates[0])).max() < 100.0


def test_a_nan_in_a_kept_row_is_refused_and_too_few_rows_too():
    frame, _, _ = _blobs(50, seed=4)
    bad = Frame({"x": [1.0, np.nan, 3.0, 4.0]})
    bad = VectorAssembler(["x"], "features").transform(bad)
    with pytest.raises(ValueError, match="NaN/inf"):
        KMeans(k=2).fit(bad)
    with pytest.raises(ValueError, match="exceeds"):
        KMeans(k=51).fit(frame)
    with pytest.raises(ValueError, match="init_steps"):
        KMeans(k=2, init_steps=0)
    assert KMeans(k=2).setInitSteps(5).init_steps == 5


def test_fewer_distinct_candidates_than_k_are_the_centres():
    rows = np.repeat(np.asarray([[0.0, 0.0], [5.0, 5.0], [9.0, 0.0]]), 10,
                     axis=0)
    frame = VectorAssembler(["x", "y"], "features").transform(
        Frame({"x": rows[:, 0], "y": rows[:, 1]}))
    model = KMeans(k=5, seed=0).fit(frame)
    assert model.k == 3 and sorted(model.summary.cluster_sizes) == [10] * 3


# ---------------------------------------------------------------------------
# k-means||'s draws
# ---------------------------------------------------------------------------

def test_a_round_draws_2k_rows_and_the_seeding_stands_in_the_reference_band():
    k, n, seeds = 10, 2000, 200
    frame, cols, keep = _blobs(n, d=6, clusters=5, seed=30, spread=1.0,
                               dropped=0.1)
    X, mask = C._features(frame, "features")
    program = C._init_program(k, 2, C.init_bucket(k), "xla")
    drawn = np.asarray([np.asarray(program(X, mask, jax.random.PRNGKey(s))[3])
                        for s in range(seeds)])
    # a sum of independent Bernoullis of mean at most 2k and variance at
    # most 2k: the mean over 200 seeds within four of ITS deviations
    for r in range(2):
        assert 2 * k - 4 * np.sqrt(2 * k / seeds) - 1.0 \
            <= drawn[:, r].mean() <= 2 * k + 4 * np.sqrt(2 * k / seeds)
    assert drawn.max() <= C.init_bucket(k)
    rows = int(keep.sum())
    mine = []
    for s in range(40):
        history = KMeans(k=k, max_iter=0, seed=s).fit(frame).summary.history
        mine.append(REFERENCE.nearest(cols, keep, history[0])[1] / rows)
    theirs = [REFERENCE.nearest(cols, keep, REFERENCE.kmeans_parallel(
        cols, keep, k, 2, np.random.default_rng(s))[2])[1] / rows
        for s in range(40)]
    assert 0.8 < np.median(mine) / np.median(theirs) < 1.25
    assert max(mine) < 2.0 * max(theirs)


def test_a_bucket_overflow_is_counted(monkeypatch):
    frame, cols, keep = _blobs(1500, seed=5)
    monkeypatch.setattr(C, "init_bucket", lambda k: 8)
    before = {k: counters.get(k) for k in WATCHED}
    model = KMeans(k=10, seed=2).fit(frame)
    moved = _delta(before)
    assert moved["kmeans.init_overflow"] >= 1
    candidates, weights = model.summary.init_candidates
    assert len(candidates) <= 1 + 2 * 8
    assert REFERENCE.candidate_check(cols, keep, candidates, weights) \
        == (0, 0.0)


@pytest.mark.parametrize("n", [100, 1024, 5000])
def test_compaction_finds_the_set_entries_in_order(n):
    rng = np.random.default_rng(n)
    chosen = rng.random(n) < 12 / n
    idx, count = C._compact(jnp.asarray(chosen)[None, :], 16)
    want = np.flatnonzero(chosen)
    assert int(count) == len(want)
    m = min(len(want), 16)
    assert list(np.asarray(idx)[:m]) == list(want[:m])


# ---------------------------------------------------------------------------
# Lloyd's loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("max_iter,tol", [(2, 1e-4), (30, 1e-4), (30, 0.5)])
def test_the_loop_stops_where_the_reference_stops(max_iter, tol):
    frame, cols, keep = _blobs(2000, clusters=3, seed=6, spread=1.5)
    summary = KMeans(k=6, max_iter=max_iter, tol=tol, seed=3).fit(
        frame).summary
    history = np.asarray(summary.history)
    stops = REFERENCE.stops_after(history, tol, max_iter)
    assert summary.num_iter == stops == len(history) - 1
    if max_iter == 2:
        assert stops == 2
    else:
        assert stops < 30
    # the reference, run on from the program's start, stops there too
    theirs = [history[0]]
    for _ in range(max_iter):
        theirs.append(REFERENCE.lloyd_step(cols, keep, theirs[-1])[3])
        if REFERENCE.stops_after(theirs, tol, max_iter) < max_iter:
            break
    assert len(theirs) - 1 == stops


def test_an_empty_cluster_keeps_its_centre():
    frame, cols, keep = _blobs(500, clusters=2, seed=7)
    X, mask = C._features(frame, "features")
    start = np.stack([X[0], X[1], np.full(4, 1e3)]).astype(X.dtype)
    floats, sizes = C._lloyd_program(3, 1e-4, "xla")(X, mask, start)
    history = np.asarray(floats[:-2]).reshape(4, 3, 4)
    iters = int(floats[-2])
    assert np.array_equal(history[iters][2], np.full(4, 1e3))
    assert int(sizes[2]) == 0 and int(np.sum(sizes)) == 500
    _, ref_sizes, _, new = REFERENCE.lloyd_step(cols, keep, start)
    assert np.array_equal(new[2], np.full(4, 1e3)) and ref_sizes[2] == 0


# ---------------------------------------------------------------------------
# the pass itself
# ---------------------------------------------------------------------------

def _pass_case(n=700, d=5, K=11, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(dtype) * 3
    keep = rng.random(n) < 0.8
    X[~keep] = np.nan
    centres = X[keep][:K].copy()
    ok = np.arange(K) != 4
    return X, keep, centres, ok


@pytest.mark.parametrize("with_prev", [False, True])
def test_the_pass_in_plain_numpy_terms(with_prev):
    X, keep, centres, ok = _pass_case()
    w = C.row_weights(jnp.asarray(keep), X.shape[0])
    d2 = ((X[:, None, :] - centres[None]) ** 2).sum(-1)
    d2[:, ~ok] = np.inf
    best, arg = d2.min(1), d2.argmin(1)
    prev = None
    if with_prev:
        before = np.where(np.arange(len(X)) % 2 == 0, 0.5, np.inf)
        prev = (jnp.asarray(before)[None, :],
                jnp.full((1, len(X)), 3, jnp.int32))
        closer = best < before
        best = np.where(closer, best, before)
        arg = np.where(closer, arg + 20, 3)
    out = C.device_pass(jnp.asarray(X).T, w, jnp.asarray(centres),
                        jnp.asarray(ok), prev=prev, base=20, rows_out=True,
                        sums=not with_prev, slots=40)
    assert np.allclose(np.asarray(out.row_cost)[0][keep], best[keep])
    assert np.array_equal(np.asarray(out.row_idx)[0][keep], arg[keep])
    assert float(out.cost) == pytest.approx(best[keep].sum())
    assert np.array_equal(np.asarray(out.counts),
                          np.bincount(arg[keep], minlength=40))
    if not with_prev:
        want = np.stack([X[keep][arg[keep] == j].sum(0) for j in range(11)])
        assert np.allclose(np.asarray(out.sums), want)


@pytest.mark.parametrize("mode", ["lloyd", "rows", "weigh"])
def test_the_kernel_through_the_interpreter_equals_the_plain_pass(mode):
    X, keep, centres, ok = _pass_case(n=9000, d=6, K=11, seed=2,
                                      dtype=np.float32)
    slots_n = C.row_slots(len(X), "pallas")
    assert slots_n == C.PASS_TILE * C.PASS_PARTIALS
    xt = jnp.asarray(X).T
    args = dict(prev=None, base=0, rows_out=False, sums=False, slots=0)
    if mode == "lloyd":
        args.update(sums=True, slots=11)
    else:
        first = C._pass_xla(xt, C.row_weights(keep, len(X)),
                            jnp.asarray(centres[:1]), jnp.ones(1, bool),
                            None, 0, True, False, 0)
        args.update(base=1, rows_out=mode == "rows",
                    slots=12 if mode == "weigh" else 0)
        # a round measures against the NEW candidates only
        centres, ok = centres[1:], ok[1:]
    def prev_for(slots):
        if mode == "lloyd":
            return None
        pad = slots - len(X)
        return (jnp.pad(first.row_cost, ((0, 0), (0, pad)),
                        constant_values=jnp.inf),
                jnp.pad(first.row_idx, ((0, 0), (0, pad))))
    want = C._pass_xla(xt, C.row_weights(keep, len(X)), jnp.asarray(centres),
                       jnp.asarray(ok), **dict(args, prev=prev_for(len(X))))
    got = C._pass_pallas(xt, C.row_weights(keep, slots_n),
                         jnp.asarray(centres), jnp.asarray(ok),
                         **dict(args, prev=prev_for(slots_n)),
                         interpret=True)
    assert float(got.cost) == pytest.approx(float(want.cost), rel=1e-5)
    if args["slots"]:
        assert np.array_equal(np.asarray(got.counts), np.asarray(want.counts))
    if args["sums"]:
        assert np.allclose(np.asarray(got.sums), np.asarray(want.sums),
                           rtol=1e-5, atol=1e-3)
    if args["rows_out"]:
        assert np.array_equal(np.asarray(got.row_idx)[0, :len(X)][keep],
                              np.asarray(want.row_idx)[0][keep])
        assert np.allclose(np.asarray(got.row_cost)[0, :len(X)][keep],
                           np.asarray(want.row_cost)[0][keep], rtol=1e-5)


def test_lowering_follows_backend_and_operand(monkeypatch):
    X = jnp.zeros((16, 3), jnp.float32)
    assert C.pass_lowering(X) == "xla"          # the CPU of the tests
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert C.pass_lowering(X) == "pallas"
    assert C.pass_lowering(X.astype(jnp.float64)) == "xla"
    assert C.pass_lowering(X, mesh=object()) == "xla"
    assert C.row_slots(5, "pallas") == C.PASS_TILE * C.PASS_PARTIALS
    assert C.row_slots(5, "xla") == 5
    assert C.init_bucket(10) == 48 and C.init_bucket(2) % 8 == 0


# ---------------------------------------------------------------------------
# the model's passes
# ---------------------------------------------------------------------------

def test_transform_is_the_nearest_centre_and_writes_one_column():
    frame, cols, keep = _blobs(900, seed=8, dropped=0.2, nan=True)
    model = KMeans(k=5, seed=1).fit(frame)
    out = model.transform(frame)
    assert out.columns == frame.columns + ["prediction"]
    X = np.stack(cols, axis=1).astype(np.float64)[keep]
    want = ((X[:, None, :] - np.asarray(model.centers, np.float64)[None])
            ** 2).sum(-1).argmin(1)
    got = np.asarray(out.to_pydict()["prediction"])
    assert np.array_equal(got, want)
    assert model.predict(X[3]) == want[3]


def test_a_model_keeps_its_history_through_save_and_load(tmp_path):
    frame, _, _ = _blobs(400, seed=9)
    model = KMeans(k=4, seed=1).fit(frame)
    model.save(str(tmp_path / "m"))
    loaded = KMeansModel.load(str(tmp_path / "m"))
    assert np.array_equal(np.asarray(loaded.summary.history),
                          np.asarray(model.summary.history))
    assert np.array_equal(np.asarray(loaded.centers),
                          np.asarray(model.centers))
    assert KMeans.load is not None and loaded.summary.num_iter \
        == model.summary.num_iter


def test_a_fit_on_a_mesh_seeds_on_the_device_and_keeps_the_psum_loop():
    from sparkdq4ml_tpu.parallel.mesh import make_mesh

    frame, cols, keep = _blobs(999, seed=11, dropped=0.1, nan=True)
    before = {k: counters.get(k) for k in WATCHED}
    model = KMeans(k=5, seed=1).fit(frame, mesh=make_mesh(8))
    assert _delta(before)["kmeans.fit_device"] == 1
    single = KMeans(k=5, seed=1).fit(frame)
    assert model.summary.history is None
    candidates, weights = model.summary.init_candidates
    assert REFERENCE.candidate_check(cols, keep, candidates, weights) \
        == (0, 0.0)
    assert np.allclose(np.sort(np.asarray(model.centers), axis=0),
                       np.sort(np.asarray(single.centers), axis=0), atol=1e-3)


# ---------------------------------------------------------------------------
# reads, spans, scopes, counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("init_mode", ["k-means||", "random"])
def test_a_fit_reads_three_small_things_and_counts_its_passes(init_mode):
    k, d, max_iter = 5, 4, 7
    frame, _, _ = _blobs(4000, seed=12, dropped=0.2)
    est = KMeans(k=k, max_iter=max_iter, seed=2, init_mode=init_mode)
    est.fit(frame)
    before = {n: counters.get(n) for n in WATCHED}
    summary = est.fit(frame).summary
    moved = _delta(before)
    assert moved["kmeans.fit_device"] == 1
    assert moved["kmeans.iterations"] == summary.num_iter
    seeding = 3 if init_mode == "k-means||" else 0
    assert moved["kmeans.data_passes"] == 1 + seeding + summary.num_iter + 1
    assert moved["host.reads"] == 3 and moved["frame.host_sync"] == 0
    candidates = summary.init_candidates[0]
    drawn = 0 if candidates is None else len(candidates)
    assert moved["kmeans.init_candidates"] == drawn
    # validation, the seeding's bucket (or the k rows), the history: no
    # n-sized array comes to the host — 4,000 rows of 4 would be 128 KB
    bucket = 1 + 2 * C.init_bucket(k)
    bound = 8 + bucket * (d * 8 + 1 + 4) + 2 * 4 \
        + (k * d * (max_iter + 1) + 2) * 8 + k * 4
    assert 0 < moved["host.read_bytes"] <= bound < 4000 * d * 8 // 4


def test_spans_and_reads_of_a_kmeans_fit():
    from sparkdq4ml_tpu.utils import observability as obs

    frame, _, _ = _blobs(500, seed=13)
    KMeans(k=3, seed=1).fit(frame)
    obs.enable()
    try:
        obs.TRACER.clear()
        model = KMeans(k=3, seed=1).fit(frame)
        model.transform(frame)
        spans = obs.TRACER.spans()
    finally:
        obs.disable()
        obs.TRACER.clear()
    by_name = {s.name: s for s in spans}
    by_sid = {s.sid: s.name for s in spans}
    for name, parent in (("fit.prepare", "fit.kmeans"),
                         ("fit.extract", "fit.prepare"),
                         ("fit.validate", "fit.prepare"),
                         ("fit.kmeans.init", "fit.prepare"),
                         ("fit.solve", "fit.kmeans")):
        assert by_sid[by_name[name].parent_id] == parent, name
    assert "model.transform" in by_name
    assert by_name["fit.prepare"].attrs["lowering"] == "xla"
    assert by_name["fit.kmeans.init"].attrs["steps"] == 2
    assert by_name["fit.kmeans.init"].attrs["overflow"] == 0
    assert by_name["fit.solve"].attrs["iterations"] == model.summary.num_iter
    assert by_name["fit.validate"].attrs["host_read_bytes"] == 8
    reads = [(by_sid[s.parent_id], s.attrs["site"]) for s in spans
             if s.name == "host.read"]
    assert reads == [("fit.validate", "kmeans.validate"),
                     ("fit.kmeans.init", "kmeans.candidates"),
                     ("fit.solve", "kmeans.result")]


def test_the_programs_carry_their_scopes():
    X = jnp.zeros((64, 3))
    mask = jnp.ones((64,), bool)
    init = C._init_program(2, 2, C.init_bucket(2), "xla").lower(
        X, mask, jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("dq.kmeans.init.cost", "dq.kmeans.init.sample",
                  "dq.kmeans.init.weigh"):
        assert scope in init, scope
    loop = C._lloyd_program(2, 1e-4, "xla").lower(
        X, mask, X[:2]).as_text(debug_info=True)
    assert "dq.kmeans.assign" in loop and "dq.kmeans.update" in loop
    score = C._score_program("xla", True).lower(
        X, mask, X[:2]).as_text(debug_info=True)
    assert "dq.kmeans.score" in score
    assert "dq.fit.validate" in C._validate.lower(X, mask).as_text(
        debug_info=True)
