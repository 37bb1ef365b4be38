"""The ordered GROUP BY's reduction as one Pallas pass (``ops/segments.py``,
the kernel ``grouped_run_scan``), run by the Pallas interpreter against the
XLA segmented scans it replaces on the chip (``_seg_scan``): every member
kind of the plan (add, or, min, max over int32, float32 and bool), runs
that cross a lane, a row, a vreg, a loop turn and a grid step, one run over
the whole input, a ragged last step, masked rows, NaN values and the empty
-> NULL rule, keys out of order. Integers and whole-number floats match bit
for bit; random floats within a relative tolerance (the kernel adds a run's
rows in another order).

The chip runs the engine in 32-bit mode, so these tests do: x64 off and the
engine's float and int dtypes 32-bit, restored after each test. CPU, small
sizes; nothing here asserts a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame.aggregates import AggExpr
from sparkdq4ml_tpu.ops import segments as S
from sparkdq4ml_tpu.utils.profiling import counters

# the kernel's grid step here: two turns of two vregs (8 x 128 slots each)
BLOCK, UNROLL = 4096, 2

# the whole family over a float, an int and a bool column
AGG_OPS = (("count", -1, False), ("count", 0, False), ("sum", 0, False),
           ("avg", 0, False), ("min", 0, False), ("max", 0, False),
           ("count", 1, False), ("sum", 1, False), ("avg", 1, False),
           ("min", 1, False), ("max", 1, False), ("sum", 2, False),
           ("min", 2, False), ("max", 2, False))
KINDS = ("f", "i", "b")


@pytest.fixture
def chip_dtypes():
    """The engine in the chip's 32-bit mode for one test."""
    saved = (config.default_float_dtype, config.default_int_dtype)
    jax.config.update("jax_enable_x64", False)
    config.default_float_dtype, config.default_int_dtype = \
        jnp.float32, jnp.int32
    try:
        yield
    finally:
        config.default_float_dtype, config.default_int_dtype = saved
        jax.config.update("jax_enable_x64", True)


def ordered_columns(seed, n, groups, masked=0.1, nan=0.02, dead=0,
                    random_floats=False, unsigned=False, swap=False):
    """Keys in order (``groups`` distinct, uneven runs), a mask, and a
    float, an int and a bool column; ``dead`` runs wholly masked and as
    many wholly NaN; ``swap`` puts two keys out of order."""
    r = np.random.default_rng(seed)
    if unsigned:
        pool = np.sort(r.choice(np.arange(2 ** 31 - 50, 2 ** 31 + 5000,
                                          dtype=np.int64), groups,
                                replace=False)).astype(np.uint32)
    else:
        pool = np.sort(r.choice(np.arange(-40 * groups, 40 * groups),
                                groups, replace=False)).astype(np.int32)
    k = np.sort(r.choice(pool, n))
    mask = r.random(n) >= masked
    f = (r.random(n) * 1e3 if random_floats
         else r.integers(1, 51, n)).astype(np.float32)
    f[r.random(n) < nan] = np.nan
    i = r.integers(-1000, 1000, n).astype(np.int32)
    b = r.random(n) < 0.5
    runs = np.unique(k)
    for key in r.choice(runs, min(dead, runs.size), replace=False):
        mask[k == key] = False
    for key in r.choice(runs, min(dead, runs.size), replace=False):
        f[k == key] = np.nan
    if swap:
        a = n // 3
        k[a], k[2 * a] = k[2 * a], k[a]
    return k, mask, [f, i, b]


CASES = {
    "short_runs_over_lanes": dict(n=5_000, groups=2_000),
    "runs_over_rows": dict(n=9_000, groups=40),
    "runs_over_vregs_turns_and_steps": dict(n=13_000, groups=4),
    "one_run_over_the_whole_input": dict(n=3 * BLOCK + 700, groups=1),
    "no_step_multiple": dict(n=3 * BLOCK + 517, groups=300),
    "under_one_turn": dict(n=1_500, groups=200),
    "masked_rows_and_empty_groups": dict(n=8_000, groups=500, masked=0.6,
                                         dead=40),
    "nan_values_and_null_groups": dict(n=8_000, groups=500, nan=0.5,
                                       dead=40),
    "keys_out_of_order": dict(n=6_000, groups=700, swap=True),
    "unsigned_keys_past_two_to_the_31": dict(n=7_000, groups=900,
                                             unsigned=True),
    "random_floats": dict(n=9_000, groups=60, random_floats=True),
}


def both(k, mask, vals, agg_ops=AGG_OPS, kinds=KINDS):
    """(XLA's program, the kernel's) results of one plan."""
    args = ((jnp.asarray(k),), tuple(jnp.asarray(v) for v in vals),
            jnp.asarray(mask))
    want = jax.jit(S._build_ordered_agg_program(agg_ops, kinds, "xla")())(
        *args)
    kernel = functools.partial(S._run_scan_pallas, block=BLOCK,
                               unroll=UNROLL, interpret=True)
    got = jax.jit(lambda k, v, m: kernel(agg_ops, kinds, k[0], m, list(v)))(
        *args)
    return want, got


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_run_kernel_gives_the_xla_scans(chip_dtypes, case):
    spec = CASES[case]
    k, mask, vals = ordered_columns(len(case), **spec)
    (_, w_outs, w_groups, w_live, w_held), (outs, live, groups, held) = \
        both(k, mask, vals)
    assert bool(held) == bool(w_held) == (not spec.get("swap", False))
    assert int(groups) == int(w_groups) > 0
    live, w_live = np.asarray(live), np.asarray(w_live)
    assert live.dtype == w_live.dtype == np.bool_
    assert np.array_equal(live, w_live)
    for (fn, s_i, _), w, g in zip(AGG_OPS, w_outs, outs):
        assert g.dtype == w.dtype and g.shape == w.shape == k.shape
        w, g = np.asarray(w)[live], np.asarray(g)[live]
        if spec.get("random_floats") and s_i == 0 and fn in ("sum", "avg"):
            assert np.array_equal(np.isnan(g), np.isnan(w))
            assert np.allclose(g, w, rtol=1e-5, atol=0, equal_nan=True)
        else:
            assert np.array_equal(g, w, equal_nan=w.dtype.kind == "f"), \
                (fn, s_i)
    if case == "one_run_over_the_whole_input":
        assert int(groups) == 1 and live[-1]
    if spec.get("dead"):
        # a wholly masked run is no group; a wholly NaN one reads NULL
        nulls = np.isnan(np.asarray(outs[2])[live])
        assert 0 < nulls.sum() < live.sum()
        assert int(groups) < np.unique(k).size


def test_a_one_column_sum_like_q18s_is_bit_for_bit(chip_dtypes):
    """Q18's own plan (``sum(l_quantity)``: the flags and one float sum)
    on runs of one to seven lines."""
    r = np.random.default_rng(18)
    k = np.repeat(np.arange(1, 3_001, dtype=np.int32) * 4,
                  r.integers(1, 8, 3_000))
    q = r.integers(1, 51, k.size).astype(np.float32)
    (_, w_outs, w_groups, w_live, _), (outs, live, groups, held) = both(
        k, np.ones(k.size, bool), [q], (("sum", 0, False),), ("f",))
    assert bool(held) and int(groups) == int(w_groups) == 3_000
    assert np.array_equal(np.asarray(live), np.asarray(w_live))
    sums = np.asarray(outs[0])[np.asarray(live)]
    assert np.array_equal(sums, np.asarray(w_outs[0])[np.asarray(w_live)])
    assert np.array_equal(sums, np.bincount(k // 4 - 1, weights=q))


def test_the_run_scan_lowering_follows_backend_dtype_and_devices(
        monkeypatch):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkdq4ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

    i32 = jnp.arange(4096, dtype=jnp.int32)
    u32 = i32.astype(jnp.uint32)
    i64 = i32.astype(jnp.int64)          # the tests run under x64
    assert i64.dtype == jnp.int64
    flags32 = (np.dtype(bool), np.dtype(np.int32), np.dtype(np.float32))
    f64 = (np.dtype(bool), np.dtype(np.float64))
    # the CPU of the tests
    assert S.run_scan_lowering([i32], flags32, 8192) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert S.run_scan_lowering([i32], flags32, 8192) == "pallas"
    assert S.run_scan_lowering([u32], flags32, 8192) == "pallas"
    # 64-bit members or keys under x64
    assert S.run_scan_lowering([i32], f64, 8192) == "xla"
    assert S.run_scan_lowering([i64], flags32, 8192) == "xla"
    assert S.run_scan_lowering([i32.astype(jnp.int16)], flags32, 8192) \
        == "xla"
    # fewer slots than a vreg holds
    assert S.run_scan_lowering([i32], flags32, 1023) == "xla"
    assert S.run_scan_lowering([i32], flags32, 1024) == "pallas"
    # a key spread over a mesh
    spread = jax.device_put(i32, NamedSharding(make_mesh(), P(DATA_AXIS)))
    assert len(spread.sharding.device_set) > 1
    assert S.run_scan_lowering([spread], flags32, 8192) == "xla"
    # the members the engine hands it: Q18's plan in both modes
    q18 = ((("sum", 0, False),), ("f",), [i32.astype(jnp.float32)])
    assert [dt for _, dt in S._plan_specs(*q18)[0]] == [
        np.dtype(bool), np.dtype(bool), np.dtype(np.float64)]
    with jax.enable_x64(False):
        assert [dt for _, dt in S._plan_specs(*q18)[0]] == [
            np.dtype(bool), np.dtype(bool), np.dtype(np.float32)]


def _group(k, mask, vals, spans=None):
    from sparkdq4ml_tpu.utils import observability as obs

    names = ("grouped.ordered", "grouped.run_pallas", "grouped.order_miss",
             "grouped.fallback")
    before = {c: counters.get(c) for c in names}
    frame = Frame({"k": k, "f": vals[0], "i": vals[1]}, mask=mask)
    aggs = [AggExpr("sum", "f"), AggExpr("count", None),
            AggExpr("min", "i"), AggExpr("max", "f"), AggExpr("avg", "i")]
    with obs.query_stats() as qs:
        got = frame.group_by("k").agg(*aggs).to_pydict()
    if spans is not None:
        spans.extend(s for s in qs.spans if s.name == "frame.grouped.flush")
    return got, {c: counters.get(c) - b for c, b in before.items()}


@pytest.mark.parametrize("case", ["in_order",
                                  "a_settled_order_meets_keys_out_of_order"])
def test_the_engine_on_the_kernel_is_the_engine_on_xla_scans(
        chip_dtypes, monkeypatch, case):
    """Through ``Frame.group_by``: a whole table of one integer key past the
    exact threshold takes the ordered lowering; steered to the kernel (the
    Pallas interpreter), it gives what XLA's scans give, counts
    ``grouped.run_pallas`` once and says ``scan="pallas"`` on its span.
    Where the order read for an earlier table of the same plan and size
    meets keys out of order, the kernel's order check sends the plan to
    the sorted program."""
    n = 150_001
    unordered = case != "in_order"
    settled = ordered_columns(5, n, 40_000)
    data = ordered_columns(6, n, 40_000, swap=True) if unordered \
        else settled

    def run(spans):
        with S._ORDER_LOCK:
            S._ORDER.clear()
        if unordered:
            _group(*settled)                  # the order is read: in order
        return _group(*data, spans)

    want_spans, got_spans, taken = [], [], []
    want, by_xla = run(want_spans)
    real = S.run_scan_lowering

    def pallas(*args):
        taken.append(real(*args))
        return "pallas"

    monkeypatch.setattr(S, "run_scan_lowering", pallas)
    monkeypatch.setattr(S, "_run_scan_pallas", functools.partial(
        S._run_scan_pallas, block=1 << 14, unroll=4, interpret=True))
    got, by_kernel = run(got_spans)
    expect = {"grouped.ordered": int(not unordered),
              "grouped.run_pallas": 0,
              "grouped.order_miss": int(unordered), "grouped.fallback": 0}
    assert by_xla == expect
    assert by_kernel == dict(expect, **{"grouped.run_pallas": 1})
    # the CPU's own choice was XLA's scans
    assert taken and set(taken) == {"xla"}
    if unordered:
        assert want_spans[-1].attrs["lowering"] == "sorted"
        assert got_spans[-1].attrs["lowering"] == "sorted"
    else:
        assert want_spans[-1].attrs["lowering"] == "ordered"
        assert want_spans[-1].attrs["scan"] == "xla"
        assert got_spans[-1].attrs["scan"] == "pallas"
    assert want.keys() == got.keys()
    for name in want:
        assert np.array_equal(np.asarray(got[name]), np.asarray(want[name]),
                              equal_nan=True), name
