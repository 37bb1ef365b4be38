"""The tree family's device entry (models/tree.py): thresholds and bins made
on the device equal the plain host version to the bit; a level's histogram
— the MXU one-hot contraction through the Pallas interpreter, the scatter,
the psum'd sharded form — equals float64 numpy; a whole ``GBTClassifier``
fit equals the benchmark's plain reference (benchmarks/configs/higgs-gbt.py)
tree for tree; a fit reads a few KB and builds no program the second time;
the descent without gathers equals a gather a row.

CPU, seeded, small sizes. Nothing here asserts a time.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_devices
from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.models import (DecisionTreeRegressor, GBTClassifier,
                                   RandomForestClassifier, VectorAssembler)
from sparkdq4ml_tpu.models import tree as T
from sparkdq4ml_tpu.utils.profiling import counters

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402

REFERENCE = harness.load_module("configs", "higgs-gbt")
ESTIMATOR = harness.load_json(os.path.join(
    REPO, "benchmarks", "configs", "higgs-gbt.json"))["estimator"]


# ---------------------------------------------------------------------------
# thresholds and bins
# ---------------------------------------------------------------------------

def _columns(case, n=700, d=4, seed=3):
    """(X float32, mask, held): the rows ``mask`` keeps are valid, and those
    of them that ``held`` (None: none) marks do not vote."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    mask = np.ones(n, bool)
    held = None
    tiny = np.finfo(np.float32).smallest_subnormal
    if case == "masked_rows":
        mask = rng.random(n) > 0.3
    elif case == "nan_in_masked_slots":
        mask = rng.random(n) > 0.3
        X[~mask] = np.nan
    elif case == "constant_column":
        X[:, 1] = 2.5
    elif case == "heavy_duplicates":
        X[:, 0] = rng.integers(0, 5, size=n)
        X[:, 2] = np.where(rng.random(n) < 0.9, 1.0, X[:, 2])
    elif case == "fewer_values_than_bins":
        X[:, 3] = rng.integers(0, 7, size=n)
    elif case == "two_valid_rows":
        mask[:] = False
        mask[[5, 77]] = True
    elif case == "negatives_only":
        X[:, 0] = -np.abs(X[:, 0]) - 1.0
        X[:, 2] = -np.exp(8 * X[:, 2])
    elif case == "signed_zeros":
        X[:, 0] = np.where(rng.random(n) < 0.5, -0.0, 0.0)
        X[:, 1] = np.where(rng.random(n) < 0.6,
                           np.where(rng.random(n) < 0.5, -0.0, 0.0), X[:, 1])
    elif case == "denormals":
        # a float compare reads every one of these as zero
        X[:, 0] = rng.integers(-40, 40, size=n) * tiny
        X[:, 3] = np.where(rng.random(n) < 0.5, X[:, 3],
                           rng.integers(-9, 9, size=n) * tiny)
    elif case == "finfo_extremes":
        big = np.finfo(np.float32).max
        X[:, 1] = np.where(rng.random(n) < 0.5, big, -big)
        X[:, 2] = np.where(rng.random(n) < 0.2, big, X[:, 2])
        X[:, 3] = np.where(rng.random(n) < 0.2, -big, X[:, 3])
    elif case == "one_voting_row":
        mask = rng.random(n) > 0.3
        X[~mask] = np.nan
        held = mask.copy()
        held[np.flatnonzero(mask)[11]] = False
    elif case == "no_voting_row":
        mask = rng.random(n) > 0.3
        held = mask.copy()
    return X, mask, held


# 127 compares a pass are past ``EDGES_SELECT_MAX_COMPARES`` for float32:
# 128 bins take the sort, 32 and 8 the selection
@pytest.mark.parametrize("max_bins", [32, 8, 128])
@pytest.mark.parametrize("case", [
    "dense", "masked_rows", "nan_in_masked_slots", "constant_column",
    "heavy_duplicates", "fewer_values_than_bins", "two_valid_rows",
    "negatives_only", "signed_zeros", "denormals", "finfo_extremes",
    "one_voting_row", "no_voting_row"])
def test_device_thresholds_and_bins_equal_the_host_version(case, max_bins):
    X, mask, held = _columns(case)
    assert T.edges_lowering(max_bins, X.dtype) == (
        ("sort", 0) if max_bins == 128 else ("select", 32))
    n = X.shape[0]
    votes = mask if held is None else mask & ~held
    rows = T.row_layout(n)[0]
    edges, binned, _, w, w_held = T._bin_program(max_bins, rows)(
        jnp.asarray(X), jnp.zeros(n, jnp.float32), jnp.asarray(mask),
        None if held is None else jnp.asarray(held))
    # every valid row is binned by the voting rows' thresholds
    want_edges, want_bins = T.bin_features(X.astype(np.float64), votes,
                                           max_bins)
    if not votes.any():
        assert np.isposinf(want_edges).all()
    np.testing.assert_array_equal(np.asarray(edges, np.float64), want_edges)
    got = np.asarray(binned)
    assert got.dtype == np.int8 and got.shape == (X.shape[1], rows)
    np.testing.assert_array_equal(got[:, :n][:, mask], want_bins.T[:, mask])
    assert not got[:, n:].any()
    np.testing.assert_array_equal(np.asarray(w)[:n], votes.astype(np.float32))
    if held is not None:
        np.testing.assert_array_equal(np.asarray(w_held)[:n],
                                      (mask & held).astype(np.float32))
    # the benchmark's plain reference states the same rule
    ref = REFERENCE.thresholds(list(X.T), votes, max_bins)
    np.testing.assert_array_equal(ref, want_edges)
    np.testing.assert_array_equal(
        REFERENCE.bin_rows(list(X.T), ref)[:, mask], want_bins.T[:, mask])


def _host_keys(x):
    """``tree._rank_keys`` in numpy: the floats' order as unsigned ints."""
    uint = np.dtype(f"uint{x.dtype.itemsize * 8}")
    top = uint.type(1 << (x.dtype.itemsize * 8 - 1))
    raw = x.view(uint)
    raw = np.where(raw == top, uint.type(0), raw)
    return np.where(raw >= top, ~raw, raw | top)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_selection_and_sort_agree_bit_for_bit_on_random_bit_patterns(
        dtype, monkeypatch):
    """Every bit pattern but the NaNs — denormals, infinities, both zeros,
    the extremes — in columns of which some rows do not vote: the counting
    passes select what a sort of the integer image reads at the ranks."""
    rng = np.random.default_rng(17)
    d, n, max_bins = 6, 5000, 32
    uint = np.dtype(f"uint{np.dtype(dtype).itemsize * 8}")
    raw = rng.integers(0, np.iinfo(uint).max, size=(d, n), dtype=uint,
                       endpoint=True)
    raw[0, :40] = raw[0, 40:80]                          # some duplicates
    raw[1, ::7] = uint.type(1 << (uint.itemsize * 8 - 1))  # -0.0
    raw[1, 1::7] = 0
    Xt = raw.view(dtype)
    Xt = np.where(np.isnan(Xt), dtype(1.5), Xt)
    valid = rng.random(n) > 0.25
    Xt[:, ~valid] = np.nan
    got = {}
    for how, limit in (("select", 10 ** 9), ("sort", 0)):
        monkeypatch.setattr(T, "EDGES_SELECT_MAX_COMPARES", limit)
        assert T.edges_lowering(max_bins, dtype)[0] == how
        got[how] = np.asarray(jax.jit(
            lambda x, v: T.device_edges(x, v, max_bins))(
            jnp.asarray(Xt), jnp.asarray(valid)))
        assert got[how].dtype == np.dtype(dtype)
    np.testing.assert_array_equal(got["select"].view(uint),
                                  got["sort"].view(uint))
    ranks = T.threshold_ranks(int(valid.sum()), max_bins)
    for j in range(d):
        keys = np.unique(np.sort(_host_keys(Xt[j, valid]))[ranks])
        np.testing.assert_array_equal(
            _host_keys(got["select"][j, :len(keys)]), keys)
        assert np.isposinf(got["select"][j, len(keys):]).all()


def test_threshold_ranks_hold_for_row_counts_past_int32_products():
    # 31 * 2e9 overflows int32; the split form does not
    n = np.int64(2_000_000_011)
    want = -(-np.arange(1, 32, dtype=np.int64) * n // 32) - 1
    got = T.threshold_ranks(np.int32(n), 32)
    np.testing.assert_array_equal(got.astype(np.int64), want)


# ---------------------------------------------------------------------------
# a level's histogram
# ---------------------------------------------------------------------------

def _level_case(level, n=1500, d=5, B=32, trees=2, seed=11):
    rng = np.random.default_rng(seed + level)
    m = 2 ** level
    rows = T.row_layout(n)[0]
    binned = np.zeros((d, rows), np.int8)
    binned[:, :n] = rng.integers(0, B, size=(d, n))
    # slot m: parked rows; masked rows carry zero statistics
    pos = np.full((trees, rows), m, np.int32)
    pos[:, :n] = rng.integers(0, m + 1, size=(trees, n))
    stats = np.zeros((trees, 4, rows), np.float32)
    stats[:, :, :n] = rng.normal(size=(trees, 4, n))
    stats[:, :, :n] *= rng.random(n) > 0.2
    return binned, pos, stats, m, B, _numpy_histogram(binned, pos, stats,
                                                      m, B)


def _numpy_histogram(binned, pos, stats, m, B):
    want = np.zeros((pos.shape[0], binned.shape[0], m, B, stats.shape[1]))
    for t in range(pos.shape[0]):
        live = pos[t] < m
        for f in range(binned.shape[0]):
            np.add.at(want[t, f], (pos[t][live], binned[f][live]),
                      stats[t][:, live].T.astype(np.float64))
    return want


@pytest.mark.parametrize("level", range(5))
def test_mxu_histogram_equals_segment_sum_and_float64(level):
    binned, pos, stats, m, B, want = _level_case(level)
    args = (jnp.asarray(binned), jnp.asarray(pos), jnp.asarray(stats))
    mxu = np.asarray(T._mxu_histogram(*args, m, B, interpret=True))
    scatter = np.asarray(T._scatter_histogram(*args, m, B))
    scale = np.abs(want).max()
    assert mxu.shape == scatter.shape == want.shape
    # three bfloat16-exact parts and float32 sums: float32's own error
    assert np.abs(mxu - want).max() <= 2e-6 * scale
    assert np.abs(scatter - want).max() <= 2e-5 * scale


def test_mxu_histogram_counts_are_exact_integers():
    binned, pos, stats, m, B, _ = _level_case(3, n=5000)
    stats[:, 0] = (stats[:, 0] != 0)           # the weight statistic: 0 / 1
    got = np.asarray(T._mxu_histogram(
        jnp.asarray(binned), jnp.asarray(pos), jnp.asarray(stats), m, B,
        interpret=True))[..., 0]
    want = np.zeros_like(got)
    for t in range(pos.shape[0]):
        live = (pos[t] < m) & (stats[t, 0] > 0)
        for f in range(binned.shape[0]):
            np.add.at(want[t, f], (pos[t][live], binned[f][live]), 1.0)
    np.testing.assert_array_equal(got, want)


def test_split_bf16x3_parts_are_exact():
    x = jnp.asarray(np.random.default_rng(0).normal(size=4096) * 1e3,
                    jnp.float32)
    parts = T.split_bf16x3(x)
    for part in parts:
        np.testing.assert_array_equal(
            np.asarray(part.astype(jnp.bfloat16).astype(jnp.float32)),
            np.asarray(part))
    np.testing.assert_array_equal(
        np.asarray(parts[0] + parts[1] + parts[2]), np.asarray(x))


@pytest.mark.parametrize("level", [0, 2, 4])
def test_sharded_histogram_is_the_single_device_one(level):
    from jax.sharding import PartitionSpec as P

    from sparkdq4ml_tpu.parallel.mesh import (DATA_AXIS, make_mesh,
                                              shard_map)

    assert_devices(8)
    binned, pos, stats, m, B, _ = _level_case(level, n=2048)
    stats = np.round(stats * 8)                # exact in any order
    args = (jnp.asarray(binned), jnp.asarray(pos),
            jnp.asarray(stats, jnp.float64))
    single = T._level_histogram(*args, m, B)
    sharded = jax.jit(shard_map(
        lambda b, p, t: T._level_histogram(b, p, t, m, B, DATA_AXIS),
        mesh=make_mesh(8),
        in_specs=(P(None, DATA_AXIS), P(None, DATA_AXIS),
                  P(None, None, DATA_AXIS)),
        out_specs=P()))(*args)
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(single))
    np.testing.assert_array_equal(
        np.asarray(single), _numpy_histogram(binned, pos, stats, m, B))


def test_lowering_follows_backend_and_shapes(monkeypatch):
    assert T.hist_lowering(28, 32) == "scatter"          # the CPU of the tests
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert T.hist_lowering(28, 32) == "mxu"
    assert T.hist_lowering(28, 32, sharded="data") == "scatter"
    assert T.hist_lowering(600, 32) == "scatter"         # one-hot too wide
    for rows in (1, 127, 2048, 2049, 131_071, 131_072, 11_000_000):
        padded = T.row_layout(rows)[0]
        again, block, partials = T.row_layout(padded)
        # the fit pads once; the kernel finds its grid from the padded rows
        assert again == padded >= rows and padded % (block * partials) == 0


# ---------------------------------------------------------------------------
# a level below the root: one child of every split, its sibling by subtraction
# ---------------------------------------------------------------------------

def _growth_case(case, n=4096, d=6, seed=23):
    """(binned, edges, targets, feat_masks, kwargs of ``build_trees``) at
    32 bins."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    edges, bins = T.bin_features(X, None, 32)
    binned = np.ascontiguousarray(bins.T).astype(np.int8)
    w = (rng.random(n) > 0.2).astype(np.float32)         # masked rows: 0
    fm, kw = None, dict(max_depth=5, impurity="variance", min_instances=1)
    if case == "gini_forest":
        trees, classes, kw = 3, 3, dict(max_depth=4, impurity="gini",
                                        min_instances=1)
        label = rng.integers(0, classes, size=n)
        label[X[:, 1] > 0.3] = 0
        boot = rng.poisson(1.0, size=(trees, n)).astype(np.float32) * w
        targets = boot[:, None, :] * (
            label[None, :] == np.arange(classes)[:, None])[None]
        fm = rng.random((trees, 2 ** 5 - 1, d)) < 0.5
        fm[:, :, 0] = True                               # never an empty set
    else:
        # the boosted statistics [w, wg, wg^2, wh] of a first round
        y = (rng.random(n) < 1 / (1 + np.exp(-X[:, 0] - X[:, 2] ** 2)))
        p = 1 / (1 + np.exp(-0.4 * X[:, 1]))
        g, h = y - p, p * (1 - p)
        targets = np.stack([w, w * g, w * g * g, w * h])[None]
        if case == "some_nodes_do_not_split":
            kw["min_instances"] = n // 9
    return (jnp.asarray(binned), jnp.asarray(edges, jnp.float32),
            jnp.asarray(targets, jnp.float32), fm, kw)


def _asked_nodes(monkeypatch):
    """The ``n_nodes`` of every ``_level_histogram`` call traced from here
    on."""
    direct, asked = T._level_histogram, []

    def counted(binned, node_pos, targets, n_nodes, B, psum_axis=None):
        asked.append(n_nodes)
        return direct(binned, node_pos, targets, n_nodes, B, psum_axis)

    monkeypatch.setattr(T, "_level_histogram", counted)
    return asked


@pytest.mark.parametrize("case", ["boosted_depth_5", "gini_forest",
                                  "some_nodes_do_not_split", "shard_map"])
def test_a_level_histograms_one_child_and_subtracts_its_sibling(
        case, monkeypatch):
    """What ``build_trees`` hands ``_find_splits`` at every level below the
    root (``_sibling_histograms``' result, as it stands) against
    ``_level_histogram`` over all ``m`` nodes of the same heap: the weight
    statistic bit for bit, the rest to float32's rounding, and zeros under
    a node that did not split."""
    from jax.sharding import PartitionSpec as P

    from sparkdq4ml_tpu.parallel.mesh import (DATA_AXIS, make_mesh,
                                              shard_map)

    binned, edges, targets, fm, kw = _growth_case(case)
    derive, direct = T._sibling_histograms, T._level_histogram
    seen = []

    def spy(binned, heap, base, targets, parents, *rest):
        got = derive(binned, heap, base, targets, parents, *rest)
        m = base + 1
        want = direct(binned, jnp.where(heap >= base, heap - base, m),
                      targets, m, parents.shape[3], rest[-1])
        seen.append((got, want))
        return got

    monkeypatch.setattr(T, "_sibling_histograms", spy)
    asked = _asked_nodes(monkeypatch)          # the spy's own call apart

    def grow(b, e, t, axis=None):
        trees, _ = T.build_trees(b, e, t, kw["max_depth"], 32,
                                 kw["impurity"], kw["min_instances"], 0.0,
                                 fm, psum_axis=axis)
        return trees.is_leaf, list(seen)

    if case == "shard_map":
        assert_devices(8)
        run = jax.jit(shard_map(
            lambda b, e, t: grow(b, e, t, DATA_AXIS), mesh=make_mesh(8),
            in_specs=(P(None, DATA_AXIS), P(), P(None, None, DATA_AXIS)),
            out_specs=P()))
    else:
        run = jax.jit(grow)
    is_leaf, levels = run(binned, edges, targets)
    depth = kw["max_depth"]
    assert asked == [1] + [2 ** k for k in range(depth - 1)]
    assert len(levels) == depth - 1
    is_leaf = np.asarray(is_leaf)
    for k, (got, want) in enumerate(levels, start=1):
        got, want = np.asarray(got), np.asarray(want)
        m = 2 ** k
        assert got.dtype == want.dtype == np.float32
        assert got.shape == want.shape == (
            targets.shape[0], binned.shape[0], m, 32, targets.shape[1])
        np.testing.assert_array_equal(
            np.asarray(T._node_weight(got, kw["impurity"])),
            np.asarray(T._node_weight(want, kw["impurity"])))
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
        # children of a node that did not split hold nothing
        under_leaf = np.repeat(is_leaf[:, m // 2 - 1:m - 1], 2, axis=1)
        assert not got.transpose(0, 2, 1, 3, 4)[under_leaf].any()
    if case == "some_nodes_do_not_split":
        stopped = is_leaf[:, :2 ** (depth - 1) - 1]
        assert stopped[:, 1:].any() and not stopped.all()


def test_a_depth_5_fit_asks_the_histogram_for_1_1_2_4_8_nodes(monkeypatch):
    frame, _, _, _ = _higgs_like(3000, 5, 4)
    asked = _asked_nodes(monkeypatch)
    T._gbt_programs.cache_clear()              # the round is traced anew
    try:
        GBTClassifier(max_iter=3, max_depth=5).fit(frame)
    finally:
        T._gbt_programs.cache_clear()
    # one compiled round, whatever the number of rounds
    assert asked == [1, 1, 2, 4, 8]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("nodes,columns", [(8, 128), (16, 256)])
def test_mxu_histogram_compiles_for_the_chip_at_the_cell_size(
        one_chip, nodes, columns):
    """The TPU's compiler takes the kernel at 11M rows x 28 features x 32
    bins (Mosaic refuses what the interpreter lets through): 8 nodes, the
    widest pass of the cell's depth-5 fit — a statistic matrix of 128
    columns, one pass of the MXU — and 16, which a depth-6 fit runs."""
    from jax.experimental.compilation_cache import compilation_cache

    rows = T.row_layout(11_000_000)[0]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            lambda b, p, t: T._mxu_histogram(b, p, t, nodes, 32)).lower(
            shape((28, rows), jnp.int8), shape((1, rows), jnp.int32),
            shape((1, 4, rows), jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert " scatter(" not in text and " gather(" not in text
    # (tree, accumulators, 28 x 32 one-hot rows, the statistic columns)
    assert f"f32[1,8,896,{columns}]" in text


@pytest.mark.parametrize("mode", ["lloyd", "seeding_round", "weigh"])
def test_kmeans_pass_compiles_for_the_chip_at_the_cell_size(one_chip, mode):
    """k-means' pass kernel (``models/clustering.py``; here because one
    file holds every compile for the described chip: its fixture owns the
    TPU's library) at ``hibench_kmeans``' 5e7 rows x 20 features: Lloyd's
    pass at k = 10, a seeding round against a 48-slot bucket with the
    earlier round's costs beside it, the weighing pass. The feature column
    arrives ``(n, 20)``; its transpose must be a bitcast (no copy of 4 GB)
    and nothing n-sized may be built beside the row vectors asked for."""
    from jax.experimental.compilation_cache import compilation_cache

    from sparkdq4ml_tpu.models import clustering as C

    n, d, k = 50_000_000, 20, 10
    slots = C.row_slots(n, "pallas")
    bucket = C.init_bucket(k)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    X, w = shape((n, d), jnp.float32), shape((1, slots), jnp.float32)
    rows = (shape((1, slots), jnp.float32), shape((1, slots), jnp.int32))
    if mode == "lloyd":
        fn = lambda X, w, c: C.device_pass(  # noqa: E731
            X.T, w, c, sums=True, slots=k, lowering="pallas")
        args = (X, w, shape((k, d), jnp.float32))
    else:
        fn = lambda X, w, c, pc, pi: C.device_pass(  # noqa: E731
            X.T, w, c, prev=(pc, pi), base=1 + bucket,
            rows_out=mode == "seeding_round",
            slots=1 + 2 * bucket if mode == "weigh" else 0,
            lowering="pallas")
        args = (X, w, shape((bucket, d), jnp.float32)) + rows
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kmeans_pass" in text
    # the 4 GB operand reaches the kernel as a bitcast of the column
    assert "f32[20,50000000]{1,0:T(8,128)} bitcast(" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("n, dtype, keys", [
    (7_624 * 32_768, jnp.int32, 1),       # the lineitem join's merged pairs
    (66_000_000, jnp.int32, 1),           # the customer join's sorted pairs
    (66_000_017, jnp.float32, 2),         # no step multiple, float keys
    (1_024, jnp.uint32, 1)])              # the fewest the lowering hands it
def test_join_probe_scan_compiles_for_the_chip_at_the_cell_size(
        one_chip, n, dtype, keys):
    """The join's probe-scan kernel (``ops/joins.py``; here because this
    file holds every compile for the described chip) at ``tpch_q3_join``'s
    two sizes: its 1-D operands are read where they lie — no copy, no
    padded operand, not a byte of temporaries — and the ragged last step
    is the kernel's own."""
    from jax.experimental.compilation_cache import compilation_cache

    from sparkdq4ml_tpu.ops import joins as J

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(lambda ks, ts: J._scans_pallas(ks, ts, n // 10)) \
            .lower([shape((n,), dtype)] * keys,
                   shape((n,), jnp.uint32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "join_probe_scan" in text
    assert " copy(" not in text and " pad(" not in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes == 0
    # head and cnt, each tiled to whole 1,024-element tiles
    assert memory.output_size_in_bytes <= 2 * 4 * (n + 1024) + 1024


@pytest.mark.parametrize("n, plan", [
    (240_048_600, "q18"),                 # the cell's lineitem
    (66_000_017, "family"),               # no step multiple, every member
    (1_024, "q18")])                      # the fewest the lowering hands it
def test_grouped_run_scan_compiles_for_the_chip_at_the_cell_size(
        one_chip, n, plan):
    """The ordered GROUP BY's kernel (``ops/segments.py``; here because this
    file holds every compile for the described chip) in the chip's 32-bit
    mode: ``tpch_q18_volume``'s ``sum(l_quantity)`` over 2.4e8 lines, and
    the whole aggregate family over a float, an int and a bool column. No
    operand is padded or concatenated; the only temporaries are the int32
    forms of the bools that Mosaic takes and gives (the mask in, the live
    mask out, a bool column and a bool column's max)."""
    from jax.experimental.compilation_cache import compilation_cache

    from sparkdq4ml_tpu.config import config
    from sparkdq4ml_tpu.ops import segments as S

    if plan == "q18":
        ops, kinds, dts = (("sum", 0, False),), ("f",), (jnp.float32,)
    else:
        ops = (("count", -1, False), ("sum", 0, False), ("avg", 0, False),
               ("min", 0, False), ("max", 1, False), ("sum", 1, False),
               ("count", 0, False), ("max", 2, False))
        kinds, dts = ("f", "i", "b"), (jnp.float32, jnp.int32, jnp.bool_)

    def shape(dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)

    saved = (config.default_float_dtype, config.default_int_dtype)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        config.default_float_dtype, config.default_int_dtype = \
            jnp.float32, jnp.int32
        with jax.enable_x64(False):
            compiled = jax.jit(S._build_ordered_agg_program(
                ops, kinds, "pallas")()).lower(
                (shape(jnp.int32),), tuple(shape(dt) for dt in dts),
                shape(jnp.bool_)).compile()
    finally:
        config.default_float_dtype, config.default_int_dtype = saved
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_run_scan" in text
    assert " pad(" not in text and " concatenate(" not in text
    memory = compiled.memory_analysis()
    # the mask and the live mask; the family's bool column and its max
    bools = 4 if plan == "family" else 2
    assert memory.temp_size_in_bytes <= bools * (4 * n + (1 << 18))


# ---------------------------------------------------------------------------
# a whole fit against the plain reference
# ---------------------------------------------------------------------------

def _higgs_like(n, d, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 0] = np.exp(0.55 * X[:, 0])
    beta = rng.normal(size=d) * 0.6
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ beta)))).astype(np.float32)
    keep = X[:, 0] > 0.6
    cols = {f"x{j}": X[:, j] for j in range(d)}
    cols["label"] = y
    names = [f"x{j}" for j in range(d)]
    frame = VectorAssembler(names, "features").transform(Frame(cols))
    return frame.filter(np.asarray(keep)), X, y, keep


@pytest.mark.parametrize("n,d", [(3000, 5), (6000, 9)])
@pytest.mark.parametrize("seed", range(6))
def test_gbt_fit_equals_the_plain_reference_tree_for_tree(seed, n, d):
    frame, X, y, keep = _higgs_like(n, d, seed)
    # leaves of 100 rows and more: in a small node two features part the
    # rows alike (an exact tie) and the last bit of a sum picks between them
    est = dict(ESTIMATOR, max_iter=5, max_depth=3,
               min_instances_per_node=100)
    model = GBTClassifier(
        max_iter=est["max_iter"], max_depth=est["max_depth"],
        max_bins=est["max_bins"], step_size=est["step_size"],
        min_instances_per_node=100).fit(frame)
    cols = list(X.T)
    edges = REFERENCE.thresholds(cols, keep, est["max_bins"])
    bins = REFERENCE.bin_rows(cols, edges)
    f0, trees, F, _ = REFERENCE.grow(
        np.ascontiguousarray(bins[:, keep]), edges,
        y[keep].astype(np.float64), est)
    np.testing.assert_array_equal(np.asarray(model.split_candidates), edges)
    assert model.f0 == pytest.approx(f0, rel=1e-12)
    split = ~trees["is_leaf"]
    np.testing.assert_array_equal(model.is_leaf, trees["is_leaf"])
    np.testing.assert_array_equal(model.feature[split],
                                  trees["feature"][split])
    np.testing.assert_array_equal(model.threshold[split],
                                  trees["threshold"][split])
    np.testing.assert_array_equal(model.value[:, :, 0],
                                  trees["value"][:, :, 0])
    np.testing.assert_allclose(REFERENCE.leaf_values(model.value),
                               REFERENCE.leaf_values(trees["value"]),
                               rtol=1e-5, atol=1e-9)
    prob = np.stack(model.transform(frame).to_pydict()["probability"])[:, 1]
    np.testing.assert_allclose(prob, REFERENCE.probabilities(F), atol=1e-5)


def test_replay_of_the_program_trees_reads_no_gap():
    """The cell's comparison on a small table: the reference's replay of
    the program's own ensemble finds its rows, leaves and splits."""
    frame, X, y, keep = _higgs_like(2000, 6, 21)
    est = dict(ESTIMATOR, max_iter=4, max_depth=3)
    model = GBTClassifier(max_iter=4, max_depth=3).fit(frame)
    cols = list(X.T)
    edges = REFERENCE.thresholds(cols, keep, 32)
    bins = REFERENCE.bin_rows(cols, edges)
    trees = {k: np.asarray(getattr(model, k)) for k in
             ("feature", "threshold", "is_leaf", "value", "gain")}
    ref = REFERENCE.replay(np.ascontiguousarray(bins[:, keep]), edges,
                           y[keep].astype(np.float64), trees, est,
                           full=(0, 3))
    np.testing.assert_array_equal(model.value[:, :, 0], ref["counts"])
    reached = ref["counts"] > 0
    np.testing.assert_allclose(
        np.where(reached, REFERENCE.leaf_values(model.value), 0.0),
        ref["leaves"], rtol=1e-9, atol=1e-12)
    assert max(ref["regret"].values()) <= 1e-9


# ---------------------------------------------------------------------------
# what a fit reads, counts and builds
# ---------------------------------------------------------------------------

def _delta(before):
    return {k: counters.get(k) - v for k, v in before.items()}


WATCHED = ("host.reads", "host.read_bytes", "tree.fit_device",
           "tree.rounds", "tree.levels", "tree.hist_rows", "tree.hist_nodes",
           "tree.hist_derived", "frame.host_sync")


@pytest.mark.parametrize("make,trees", [
    (lambda: GBTClassifier(max_iter=10, max_depth=5), 10),
    (lambda: RandomForestClassifier(num_trees=4, max_depth=3, seed=1), 4),
    (lambda: DecisionTreeRegressor(max_depth=4), 1),
], ids=["gbt", "forest", "tree"])
def test_a_fit_reads_a_few_kilobytes_through_the_device_entry(make, trees):
    frame, _, _, _ = _higgs_like(3000, 8, 5)
    est = make()
    before = {k: counters.get(k) for k in WATCHED}
    est.fit(frame)
    moved = _delta(before)
    assert moved["tree.fit_device"] == 1
    assert moved["tree.rounds"] == trees
    assert moved["tree.levels"] == trees * est.max_depth
    assert moved["tree.hist_rows"] == trees * est.max_depth * 3000
    # the root and one child of every split; their siblings by subtraction
    assert moved["tree.hist_nodes"] == trees * 2 ** (est.max_depth - 1)
    assert moved["tree.hist_derived"] == trees * (
        2 ** (est.max_depth - 1) - 1)
    # label statistics, the two flags, the packed trees: three reads
    assert moved["host.reads"] == 3 and moved["frame.host_sync"] == 0
    assert 0 < moved["host.read_bytes"] < 64 * 1024


def test_a_second_fit_of_the_same_signature_builds_no_program():
    frame, _, _, _ = _higgs_like(900, 4, 8)
    other, _, _, _ = _higgs_like(900, 4, 9)
    GBTClassifier(max_iter=3, max_depth=3).fit(frame)
    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: events.append(name))
    model = GBTClassifier(max_iter=3, max_depth=3).fit(other)
    model.transform(other)                      # the first transform traces
    events.clear()
    GBTClassifier(max_iter=3, max_depth=3).fit(frame).transform(frame)
    again = [e for e in events if "compile" in e or "trace" in e]
    assert not again, again


def test_spans_of_a_tree_fit():
    from sparkdq4ml_tpu.utils import observability as obs

    frame, _, _, _ = _higgs_like(500, 4, 2)
    obs.enable()
    try:
        obs.TRACER.clear()
        GBTClassifier(max_iter=2, max_depth=3).fit(frame)
        spans = {s.name: s for s in obs.TRACER.spans()}
    finally:
        obs.disable()
    for name in ("fit.gbt_classifier", "fit.prepare", "fit.extract",
                 "fit.validate", "fit.tree.bin", "fit.solve"):
        assert name in spans, sorted(spans)
    assert spans["fit.tree.bin"].attrs["lowering"] == "device"
    assert spans["fit.tree.bin"].attrs["bins"] == 32
    assert spans["fit.solve"].attrs["rounds"] == 2
    assert spans["fit.solve"].attrs["levels"] == 6
    assert spans["fit.solve"].attrs["histogram"] == "scatter"
    assert spans["fit.validate"].attrs["host_read_bytes"] > 0


def test_the_reads_of_a_tree_fit_and_the_wait_of_its_binning():
    """Three counted reads, each a ``host.read`` span with its bytes, and
    the binning's wait for its program: timed the same way, no bytes, not
    counted."""
    from sparkdq4ml_tpu.utils import observability as obs

    frame, _, _, _ = _higgs_like(500, 4, 2)
    GBTClassifier(max_iter=2, max_depth=3).fit(frame)
    before = {k: counters.get(k) for k in WATCHED}
    obs.enable()
    try:
        obs.TRACER.clear()
        GBTClassifier(max_iter=2, max_depth=3).fit(frame)
        spans = obs.TRACER.spans()
    finally:
        obs.disable()
        obs.TRACER.clear()
    moved = _delta(before)
    by_sid = {s.sid: s.name for s in spans}
    reads = [(by_sid[s.parent_id], s.attrs["site"], s.attrs.get("bytes"))
             for s in spans if s.name == "host.read"]
    assert [r[:2] for r in reads] == [
        ("fit.validate", "fit.label_stats"),
        ("fit.validate", "fit.finite_flags"),
        ("fit.tree.bin", "tree.bin"), ("fit.solve", "tree.result")]
    assert reads[2][2] is None
    counted = [r[2] for r in reads if r[2] is not None]
    assert moved["host.reads"] == len(counted) == 3
    assert moved["host.read_bytes"] == sum(counted)


@pytest.mark.parametrize("max_bins,how", [(32, "select"), (8, "select"),
                                          (128, "sort")])
def test_the_bin_span_and_a_counter_say_how_the_thresholds_were_found(
        max_bins, how):
    from sparkdq4ml_tpu.utils import observability as obs

    frame, _, _, _ = _higgs_like(600, 4, 12)
    dtype = frame._column_values("features").dtype
    bits = jnp.finfo(dtype).bits
    assert T.edges_lowering(max_bins, dtype) == (
        how, bits if how == "select" else 0)
    watched = ("tree.edges_select", "tree.edges_sort", "tree.fit_device")
    before = {k: counters.get(k) for k in watched}
    obs.enable()
    try:
        obs.TRACER.clear()
        DecisionTreeRegressor(max_depth=2, max_bins=max_bins).fit(frame)
        spans = {s.name: s for s in obs.TRACER.spans()}
    finally:
        obs.disable()
    assert spans["fit.tree.bin"].attrs["edges"] == how
    assert spans["fit.tree.bin"].attrs["passes"] == (
        bits if how == "select" else 0)
    moved = _delta(before)
    assert moved["tree.fit_device"] == 1
    assert moved["tree.edges_" + how] == 1
    assert moved["tree.edges_select"] + moved["tree.edges_sort"] == 1


# ---------------------------------------------------------------------------
# descent and scoring without a gather a row
# ---------------------------------------------------------------------------

def _gather_descent(X, feature, threshold, is_leaf, depth):
    node = np.zeros(X.shape[0], np.int64)
    for _ in range(depth):
        go_left = X[np.arange(X.shape[0]), feature[node]] <= threshold[node]
        node = np.where(is_leaf[node], node, 2 * node + 2 - go_left)
    return node


@pytest.mark.parametrize("depth", [1, 3, 5])
def test_select_chain_descent_equals_a_gather_a_row(depth):
    rng = np.random.default_rng(depth)
    N, d, n = 2 ** (depth + 1) - 1, 6, 800
    X = rng.normal(size=(n, d))
    feature = rng.integers(0, d, size=N)
    threshold = rng.normal(size=N) * 0.5
    is_leaf = rng.random(N) < 0.25
    want = _gather_descent(X, feature, threshold, is_leaf, depth)
    got = T.predict_heap(jnp.asarray(X), jnp.asarray(feature),
                         jnp.asarray(threshold), jnp.asarray(is_leaf), depth)
    np.testing.assert_array_equal(np.asarray(got), want)
    table = rng.normal(size=(N, 3))
    np.testing.assert_array_equal(
        np.asarray(T.heap_lookup(got, jnp.asarray(table))), table[want].T)


def test_forest_apply_sums_every_tree_leaf_payload():
    rng = np.random.default_rng(4)
    trees, depth, d, n = 5, 3, 4, 300
    N = 2 ** (depth + 1) - 1
    X = rng.normal(size=(n, d))
    feature = rng.integers(0, d, size=(trees, N))
    threshold = rng.normal(size=(trees, N)) * 0.5
    is_leaf = rng.random((trees, N)) < 0.2
    tables = rng.normal(size=(trees, N, 2))
    want = sum(tables[t][_gather_descent(X, feature[t], threshold[t],
                                         is_leaf[t], depth)]
               for t in range(trees))
    X[7, 2] = np.nan                       # NaN <= t is false: goes right
    want = sum(tables[t][_gather_descent(X, feature[t], threshold[t],
                                         is_leaf[t], depth)]
               for t in range(trees))
    edges, cut = T.score_cuts(feature, threshold, is_leaf, d)
    assert edges.shape[1] % 8 == 0
    got = T.forest_apply(jnp.asarray(X), jnp.asarray(edges),
                         jnp.asarray(feature), jnp.asarray(cut),
                         jnp.asarray(is_leaf), jnp.asarray(tables), depth)
    np.testing.assert_allclose(np.asarray(got).T, want, rtol=1e-12)


def test_element_at_reads_a_device_vector_column():
    import sparkdq4ml_tpu as dq

    frame, _, _, _ = _higgs_like(400, 3, 6)
    scored = GBTClassifier(max_iter=2, max_depth=2).fit(frame) \
        .transform(frame)
    spark = dq.TpuSession.builder().app_name("t").master("local[*]") \
        .get_or_create()
    scored.create_or_replace_temp_view("scored")
    got = spark.sql("SELECT avg(element_at(probability, 2)) AS p1, "
                    "avg(element_at(probability, -2)) AS p0 "
                    "FROM scored").to_pydict()
    prob = np.stack(scored.to_pydict()["probability"])
    assert got["p1"][0] == pytest.approx(prob[:, 1].mean(), rel=1e-9)
    assert got["p0"][0] == pytest.approx(prob[:, 0].mean(), rel=1e-9)
