"""The join planned on the device (``ops/joins.py``) against the host plan
(``frame._vector_join_plan``), case by case; which joins take which path;
the SQL forms that reach it (comma FROM, ``ON a = b`` over two names); and
TPC-H Q3 through ``spark.sql`` against its configuration's float64
reference. On the CPU backend at small sizes: nothing here is a time.
"""

import os
import sys

import numpy as np
import pytest

from sparkdq4ml_tpu import Frame
from sparkdq4ml_tpu.utils.profiling import counters

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

HOWS = ("inner", "left", "left_semi", "left_anti")


def frame(seed, n, key_range, value, keys=("k",), valid=0.8, base=0):
    r = np.random.default_rng(seed)
    data = {k: (base + r.integers(0, key_range, n)).astype(np.int32)
            for k in keys}
    data[value] = r.normal(size=n).astype(np.float32)
    mask = r.random(n) < valid if valid < 1.0 else None
    return Frame(data, mask=mask)


def unique_keys(seed, n, value, base=0):
    r = np.random.default_rng(seed)
    return Frame({"k": (base + r.permutation(n)).astype(np.int32),
                  value: r.normal(size=n).astype(np.float32)})


BIG = 1 << 25      # int32 keys that float32 cannot tell apart


def sides(case):
    if case == "one_key_duplicates_on_both":
        return frame(1, 60, 12, "a"), frame(2, 50, 12, "b"), ["k"]
    if case == "one_key_duplicates_on_the_left":
        return (frame(3, 80, 20, "a", valid=1.0), unique_keys(4, 20, "b"),
                ["k"])
    if case == "one_key_duplicates_on_the_right":
        return unique_keys(5, 20, "a"), frame(6, 80, 20, "b"), ["k"]
    if case == "two_keys":
        return (frame(7, 70, 4, "a", keys=("k", "j")),
                frame(8, 60, 4, "b", keys=("k", "j")), ["k", "j"])
    if case == "masked_rows_on_the_left_only":
        return (frame(9, 40, 10, "a", valid=0.5),
                frame(10, 30, 10, "b", valid=1.0), ["k"])
    if case == "masked_rows_on_the_right_only":
        return (frame(11, 40, 10, "a", valid=1.0),
                frame(12, 30, 10, "b", valid=0.5), ["k"])
    if case == "an_empty_left_side":
        return (frame(13, 16, 5, "a", valid=0.0), frame(14, 12, 5, "b"),
                ["k"])
    if case == "an_empty_right_side":
        return (frame(15, 16, 5, "a"), frame(16, 12, 5, "b", valid=0.0),
                ["k"])
    if case == "no_match_at_all":
        return (frame(17, 30, 10, "a"), frame(18, 30, 10, "b", base=100),
                ["k"])
    if case == "int32_keys_over_2_24_that_differ_in_the_last_bit":
        # 2^25 + {0..7}: float32 holds one of every four of them
        return (frame(19, 64, 8, "a", base=BIG),
                frame(20, 48, 8, "b", base=BIG), ["k"])
    if case == "float_keys_with_nan_and_signed_zero":
        left = Frame({"k": np.asarray([0.0, -0.0, np.nan, 1.5, 2.5, 1.5],
                                      np.float32),
                      "a": np.arange(6, dtype=np.float32)})
        right = Frame({"k": np.asarray([-0.0, np.nan, 1.5, 1.5, 3.5],
                                       np.float32),
                       "b": np.arange(5, dtype=np.float32)})
        return left, right, ["k"]
    raise KeyError(case)


CASES = (
    "one_key_duplicates_on_both", "one_key_duplicates_on_the_left",
    "one_key_duplicates_on_the_right", "two_keys",
    "masked_rows_on_the_left_only", "masked_rows_on_the_right_only",
    "an_empty_left_side", "an_empty_right_side", "no_match_at_all",
    "int32_keys_over_2_24_that_differ_in_the_last_bit",
    "float_keys_with_nan_and_signed_zero",
)


def moved(before):
    now = counters.snapshot()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


def same_rows(got, want):
    """Equal columns, row for row: emission order is part of the answer."""
    g, w = got.to_pydict(), want.to_pydict()
    assert list(g) == list(w)
    for name in g:
        assert g[name].dtype.kind == w[name].dtype.kind, name
        assert np.array_equal(g[name], w[name], equal_nan=True), name


@pytest.mark.parametrize("build", [None, "left"])
@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("case", CASES)
def test_device_join_equals_the_host_plan(case, how, build):
    left, right, keys = sides(case)
    # (with NaN keys the host's vector plan declines and its dict plan
    # answers: no NaN matches there either)
    want = left._host_join(right, keys, how,
                           build == "left" and how == "inner", None)
    before = counters.snapshot()
    got = left.join(right, keys, how, build=build)
    delta = moved(before)
    same_rows(got, want)
    assert delta.get("join.device") == 1 and "join.host" not in delta
    # a scalar a run: the result's size (twice where the first bucket was
    # outgrown); no mask, no key column
    assert delta["host.read_bytes"] < 100 and delta["host.reads"] <= 2
    assert delta["join.rows_probed"] >= min(left.num_slots,
                                            right.num_slots)



# ---------------------------------------------------------------------------
# The build step: the merge (a probe side in key order) against the sort
# ---------------------------------------------------------------------------

@pytest.fixture
def joins(monkeypatch):
    """``ops/joins`` with rows of 128 elements, so that a few thousand
    slots are many chunks, and with nothing remembered."""
    from sparkdq4ml_tpu.ops import joins as mod

    monkeypatch.setattr(mod, "_CHUNK", 128)
    for name in ("_PROGRAMS", "_BUCKETS", "_ROOMS"):
        monkeypatch.setattr(mod, name, {})
    return mod


NPR, NB = 2_000, 150


def ordered_keys(r, distinct=700, run=1):
    """``NPR`` non-decreasing int32 keys, multiples of 3 (so that build
    keys fit between them), in runs of about ``run``."""
    return np.sort(r.integers(0, distinct, -(-NPR // run)).repeat(run)[:NPR]
                   .astype(np.int32) * 3)


def spread(r, pk, every, rows=NB):
    """``rows`` build keys, every ``every``-th distinct probe key from
    the lowest, in random row order: a chunk's share of them is even."""
    return r.permutation(np.resize(np.unique(pk)[::every], rows))


def merge_sides(case, joins):
    """(probe frame, build frame, the build step the probe side gets)."""
    r = np.random.default_rng(len(case))
    pk = ordered_keys(r)
    bk = spread(r, pk, 4)              # a foreign key: no key twice
    assert len(np.unique(bk)) == NB
    pmask = bmask = None
    step = "merge"
    if case == "duplicate_build_keys":
        bk = spread(r, pk, 9)          # two and three rows a key
        assert len(np.unique(bk)) < NB / 2
    elif case == "key_groups_over_chunk_borders":
        # runs of 45 under chunks of under 128 probe slots: most chunks
        # begin inside a group, and one group is longer than three chunks
        pk = ordered_keys(r, run=45)
        pk[600:1_000] = pk[600]
        keys = np.unique(pk)
        bk = r.permutation(np.concatenate(
            [keys, keys[::2], [pk[600]] * 2,
             -1 - np.arange(NB - 2 - len(keys) - len(keys[::2]))]))
    elif case == "masked_rows_on_both_sides":
        pmask, bmask = r.random(NPR) < 0.6, r.random(NB) < 0.7
        part = joins._CHUNK - joins._first_room(1, NB, NPR)
        pmask[::part] = False          # every chunk's first slot is masked
        pmask[-1] = False
    elif case == "build_keys_below_above_and_between":
        bk = r.permutation(np.concatenate(
            [r.integers(-50, 0, 30), pk.max() + 1 + r.integers(0, 50, 30),
             spread(r, pk, 15, 45) + 1,                    # between
             spread(r, pk, 15, 45)]))
    elif case == "an_empty_build_side_after_its_mask":
        bmask = np.zeros(NB, bool)
    elif case == "float_keys_with_signed_zeros_and_a_build_nan":
        pk = pk.astype(np.float32) - 300.0
        at = np.searchsorted(pk, 0.0)
        pk[at:at + 4] = [-0.0, 0.0, -0.0, 0.0]
        bk = np.concatenate([spread(r, pk, 4, NB - 4),
                             [0.0, -0.0, np.nan, np.inf]])
    elif case == "a_nan_among_the_probe_keys":
        pk = pk.astype(np.float32)
        pk[1234] = np.nan              # no order holds: the sort answers
        bk = np.concatenate([bk[:-1], [np.nan]])
        step = "miss"
    elif case != "foreign_keys_drawn_from_the_probe":
        raise KeyError(case)
    assert len(bk) == NB
    probe = Frame({"k": pk, "a": r.normal(size=NPR).astype(np.float32)},
                  mask=pmask)
    build = Frame({"k": bk.astype(pk.dtype),
                   "b": np.arange(NB, dtype=np.float32)}, mask=bmask)
    return probe, build, step


MERGE_CASES = (
    "foreign_keys_drawn_from_the_probe", "duplicate_build_keys",
    "key_groups_over_chunk_borders", "masked_rows_on_both_sides",
    "build_keys_below_above_and_between",
    "an_empty_build_side_after_its_mask",
    "float_keys_with_signed_zeros_and_a_build_nan",
    "a_nan_among_the_probe_keys",
)


def run_join(joins, left, right, how, sort_only=False):
    """One join with nothing remembered: (result frame, counters moved)."""
    for table in (joins._PROGRAMS, joins._BUCKETS, joins._ROOMS):
        table.clear()
    first = joins._first_room
    if sort_only:
        joins._first_room = lambda *shapes: 0
    try:
        before = counters.snapshot()
        out = left.join(right, "k", how)
        return out, moved(before)
    finally:
        joins._first_room = first


@pytest.mark.parametrize("how, probe_is", [
    ("inner", "left"), ("inner", "right"), ("left", "left"),
    ("left_semi", "left"), ("left_anti", "left")])
@pytest.mark.parametrize("case", MERGE_CASES)
def test_the_merge_gives_the_sorts_join_bit_for_bit(joins, case, how,
                                                     probe_is):
    probe, build, step = merge_sides(case, joins)
    # (an inner join builds from the side with fewer slots)
    left, right = (probe, build) if probe_is == "left" else (build, probe)
    want, by_sort = run_join(joins, left, right, how, sort_only=True)
    got, by_merge = run_join(joins, left, right, how)
    assert "join.merge" not in by_sort and "join.merge_miss" not in by_sort
    assert by_merge.get("join.merge", 0) == (step == "merge")
    assert by_merge.get("join.merge_miss", 0) == (step == "miss")
    assert by_merge["join.device"] == 1 and "join.host" not in by_merge
    # what the merge reports rides the join's one read; a signature's
    # first run reads the probe side's order before it builds a program
    assert by_merge["host.reads"] == by_sort["host.reads"] + 1
    assert by_merge["join.compile"] == by_sort["join.compile"]
    same_rows(got, want)
    # the same slots, not only the same rows
    assert got.num_slots == want.num_slots
    assert np.array_equal(np.asarray(got._mask), np.asarray(want._mask))
    if case == "foreign_keys_drawn_from_the_probe" and how != "left_anti":
        assert got.count() >= NB                       # it joined something


def spans_of(fn):
    from sparkdq4ml_tpu.utils import observability as obs

    obs.reset()
    obs.enable()
    try:
        fn()
    finally:
        obs.disable()
    found = [s for s in obs.TRACER.spans() if s.name == "frame.join"]
    obs.reset()
    return found


def test_an_ordered_probe_counts_a_merge_and_its_span_says_so(joins):
    probe, build, _ = merge_sides("foreign_keys_drawn_from_the_probe", joins)
    before = counters.snapshot()
    (span,) = spans_of(lambda: probe.join(build, "k", "left"))
    delta = moved(before)
    assert delta["join.merge"] == 1 and "join.merge_miss" not in delta
    # the order flag before the first program; then size, order, fullest
    # chunk in the join's one read
    assert delta["join.compile"] == 1 and delta["host.reads"] == 2
    assert delta["host.read_bytes"] == 1 + 12
    assert span.attrs["build_step"] == "merge"
    assert span.attrs["room"] == joins._first_room(1, NB, NPR) > 0
    # settled: the second run builds nothing and reads once
    before = counters.snapshot()
    probe.join(build, "k", "left")
    delta = moved(before)
    assert delta["join.hit"] == 1 and "join.compile" not in delta
    assert delta["join.merge"] == 1 and delta["host.reads"] == 1
    assert delta["host.read_bytes"] == 12


def test_an_unordered_probe_misses_once_and_then_sorts(joins):
    probe, build, _ = merge_sides("foreign_keys_drawn_from_the_probe", joins)
    d = probe.to_pydict()
    shuffled = Frame({"k": d["k"][::-1].copy(), "a": d["a"][::-1].copy()})
    want = shuffled._host_join(build, ["k"], "left", False, None)
    before = counters.snapshot()
    got = []
    (span,) = spans_of(lambda: got.append(shuffled.join(build, "k", "left")))
    delta = moved(before)
    assert delta["join.merge_miss"] == 1 and "join.merge" not in delta
    # no merge program is built for it: the order flag, then the sort
    assert delta["join.compile"] == 1 and delta["host.reads"] == 2
    assert delta["host.read_bytes"] == 1 + 4
    assert span.attrs["build_step"] == "sort" and "room" not in span.attrs
    same_rows(got[0], want)
    # the signature keeps the sort: no miss, no program, one 4-byte read
    before = counters.snapshot()
    again = shuffled.join(build, "k", "left")
    delta = moved(before)
    same_rows(again, want)
    assert "join.merge_miss" not in delta and "join.merge" not in delta
    assert delta["join.hit"] == 1 and "join.compile" not in delta
    assert delta["host.reads"] == 1 and delta["host.read_bytes"] == 4


def test_a_settled_merge_that_meets_an_unordered_probe_sorts_it(joins):
    probe, build, _ = merge_sides("foreign_keys_drawn_from_the_probe", joins)
    probe.join(build, "k", "left")                 # settles on the merge
    d = probe.to_pydict()
    at = np.random.default_rng(7).permutation(NPR)
    shuffled = Frame({"k": d["k"][at], "a": d["a"][at]})
    want = shuffled._host_join(build, ["k"], "left", False, None)
    before = counters.snapshot()
    got = shuffled.join(build, "k", "left")
    delta = moved(before)
    same_rows(got, want)
    # the merge program itself found it, in its one read; then the sort
    assert delta["join.merge_miss"] == 1 and "join.merge" not in delta
    assert delta["join.compile"] == 1 and delta["join.hit"] == 1
    assert delta["host.reads"] == 2 and delta["host.read_bytes"] == 12 + 4
    before = counters.snapshot()
    same = shuffled.join(build, "k", "left")
    delta = moved(before)
    same_rows(same, want)
    assert "join.merge_miss" not in delta and delta["join.hit"] == 1
    assert delta["host.reads"] == 1 and delta["host.read_bytes"] == 4


@pytest.mark.parametrize("crowd, then", [(14, "merge"), (40, "sort")])
def test_a_chunk_over_its_room_runs_once_more_and_the_next_run_fits(
        joins, crowd, then):
    """``crowd`` build rows of one key between two probe keys, over the
    first room of 13: 14 ask for a room the row has, 40 for over an
    eighth of it (the signature keeps the sort)."""
    r = np.random.default_rng(5)
    pk = ordered_keys(r)
    # (the others: a key every 40 distinct ones, at most two a chunk)
    bk = np.concatenate([[pk[1000] + 1] * crowd, np.unique(pk)[::40][:10],
                         -1 - np.arange(NB - 10 - crowd)]).astype(np.int32)
    assert len(np.unique(bk)) == NB - crowd + 1    # a left join of NPR rows
    probe = Frame({"k": pk, "a": np.arange(NPR, dtype=np.float32)})
    build = Frame({"k": bk, "b": np.arange(NB, dtype=np.float32)})
    assert 0 < joins._first_room(1, NB, NPR) < crowd
    want = probe._host_join(build, ["k"], "left", False, None)

    def run():
        got = []
        before = counters.snapshot()
        (span,) = spans_of(lambda: got.append(probe.join(build, "k", "left")))
        delta = moved(before)
        same_rows(got[0], want)
        return delta, span.attrs

    delta, attrs = run()
    assert delta["join.merge_miss"] == 1 and "join.merge" not in delta
    assert delta["join.compile"] == 2 and attrs["build_step"] == "sort"
    (room,) = joins._ROOMS.values()
    assert (crowd <= room <= joins._CHUNK // 8) if then == "merge" \
        else room == 0
    # the next run fits (or sorts) at once; the one after builds nothing
    delta, attrs = run()
    assert "join.merge_miss" not in delta
    assert delta.get("join.merge", 0) == (then == "merge")
    assert delta.get("join.compile", 0) == (then == "merge")
    assert attrs["build_step"] == then and attrs.get("room", 0) == room
    delta, attrs = run()
    assert delta["join.hit"] == 1 and "join.compile" not in delta
    assert "join.merge_miss" not in delta and attrs["build_step"] == then


@pytest.mark.parametrize("shapes, why", [
    ((2, 150, 2_000), "two key columns"),
    ((1, 50, 1_000), "a probe side of under eight chunks"),
    ((1, 300, 2_000), "a build side over a twelfth of the probe side")])
def test_shapes_that_rule_the_merge_out_pay_nothing(joins, shapes, why):
    assert joins._first_room(*shapes) == 0, why
    assert joins._first_room(1, 150, 2_000) > 0
    # and the join of such shapes reads the one scalar of a sort
    k, nb, npr = shapes
    keys = ["k", "j"][:k]
    probe = Frame({name: np.arange(npr, dtype=np.int32) for name in keys})
    build = Frame({**{name: np.arange(nb, dtype=np.int32) for name in keys},
                   "b": np.arange(nb, dtype=np.float32)})
    before = counters.snapshot()
    (span,) = spans_of(lambda: probe.join(build, keys, "left"))
    delta = moved(before)
    assert span.attrs["build_step"] == "sort"
    assert delta["host.reads"] == 1 and delta["host.read_bytes"] == 4
    assert "join.merge" not in delta and "join.merge_miss" not in delta


@pytest.mark.parametrize("need, room", [
    (0, 128), (100, 128), (114, 128), (115, 256), (865, 1024), (911, 1024),
    (912, 1152), (3_641, 4_096), (3_642, 0)])
def test_room_is_an_eighth_over_the_need_in_steps_of_128(need, room):
    from sparkdq4ml_tpu.ops import joins as mod

    assert mod._CHUNK == 1 << 15
    assert mod._room_for(need) == room


def test_the_first_room_at_the_benchmarks_shapes():
    from sparkdq4ml_tpu.ops import joins as mod

    # tpch_q3_join: the customer join's result against lineitem (859 build
    # slots a chunk if spread evenly), and customer against orders (a
    # tenth of them: too many)
    assert mod._first_room(1, 6_291_456, 240_048_600) == 1_280
    assert mod._first_room(1, 6_000_000, 60_000_000) == 0
    # Q3 of tests/test_benchmark_cells.py: under eight chunks
    assert mod._first_room(1, 20_000, 80_000) == 0


def test_the_lookup_at_the_benchmarks_shapes():
    from sparkdq4ml_tpu.ops import joins as mod
    from sparkdq4ml_tpu.ops.compiler import result_bucket

    orders = result_bucket(2_545)            # the orders Q18's HAVING keeps
    assert orders == 2_560
    # tpch_q18_volume: the semi join of orders against the kept keys, the
    # customer join's result against lineitem (probe on the right), and
    # customer probed against the orders that passed
    assert mod._takes_lookup("left_semi", 1, orders, 60_000_000)
    assert mod._takes_lookup("inner", 1, orders, 240_048_600)
    assert mod._takes_lookup("inner", 1, orders, 6_000_000)
    for nb, npr in ((orders, 60_000_000), (orders, 240_048_600),
                    (orders, 6_000_000)):
        assert mod._build_step("inner", 1, nb, npr,
                               mod._first_room(1, nb, npr)) == "lookup"
        # a left or anti join's result is the probe side's size
        assert mod._build_step("left", 1, nb, npr,
                               mod._first_room(1, nb, npr)) == "merge"
    # tpch_q3_join: the customer join's result against lineitem keeps the
    # merge, customer against orders the sort
    assert not mod._takes_lookup("inner", 1, 6_291_456, 240_048_600)
    assert mod._build_step("inner", 1, 6_291_456, 240_048_600,
                           1_280) == "merge"
    assert not mod._takes_lookup("inner", 1, 6_000_000, 60_000_000)
    assert mod._build_step("inner", 1, 6_000_000, 60_000_000,
                           mod._first_room(1, 6_000_000, 60_000_000)) \
        == "sort"
    # the merge's own tests: NB against NPR at rows of 128, and 2e4
    # against 3e5 at rows of 2^15
    assert not mod._takes_lookup("inner", 1, NB, NPR)
    assert not mod._takes_lookup("inner", 1, 20_000, 300_000)


@pytest.mark.parametrize("how", ["inner", "left_anti"])
def test_the_merge_at_its_real_row_length(how):
    """Rows of 2^15, as on the chip: 3e5 ordered probe slots (four lines
    an order, masked ones among them) against 2e4 of their orders."""
    from sparkdq4ml_tpu.ops import joins as mod

    r = np.random.default_rng(15)
    orders = 75_000
    pk = np.repeat(np.arange(orders, dtype=np.int32) * 4 + 1, 4)
    bk = r.permutation(orders)[:20_000].astype(np.int32) * 4 + 1
    probe = Frame({"k": pk, "a": np.arange(len(pk), dtype=np.float32)},
                  mask=r.random(len(pk)) < 0.54)
    build = Frame({"k": bk, "b": np.arange(len(bk), dtype=np.float32)},
                  mask=r.random(len(bk)) < 0.9)
    room = mod._first_room(1, len(bk), len(pk))
    assert room % 128 == 0 and 0 < room <= 1 << 12
    want = probe._host_join(build, ["k"], how, False, None)
    before = counters.snapshot()
    (span,) = spans_of(lambda: same_rows(probe.join(build, "k", how), want))
    delta = moved(before)
    assert delta["join.merge"] == 1 and "join.merge_miss" not in delta
    assert span.attrs["build_step"] == "merge" and span.attrs["room"] == room


@pytest.mark.parametrize("seed", range(6))
def test_random_tables_join_alike_under_both_build_steps(joins, seed):
    """Whatever the spread of the build keys — the merge holds, or a
    chunk outgrows its room and the sort answers."""
    r = np.random.default_rng(seed)
    npr = int(r.integers(600, 3_000))
    nb = int(r.integers(1, npr // 16 + 1))
    span = int(r.integers(5, 2_000))
    probe = Frame({"k": np.sort(r.integers(0, span, npr)).astype(np.int32),
                   "a": r.normal(size=npr).astype(np.float32)},
                  mask=r.random(npr) < 0.7)
    build = Frame({"k": r.integers(-20, span + 20, nb).astype(np.int32),
                   "b": r.normal(size=nb).astype(np.float32)},
                  mask=r.random(nb) < 0.8)
    tried = 0
    for how in HOWS:
        for left, right in ((probe, build), (build, probe)):
            want, _ = run_join(joins, left, right, how, sort_only=True)
            got, delta = run_join(joins, left, right, how)
            same_rows(got, want)
            tried += delta.get("join.merge", 0) \
                + delta.get("join.merge_miss", 0)
    # every join whose probe side was the ordered one tried the merge
    assert tried >= 4


# ---------------------------------------------------------------------------
# The lookup: a few build keys searched into a probe side in key order
# ---------------------------------------------------------------------------

#: probe and build slots at which rows of 128 offer the lookup
LNPR, LNB = 24_000, 64


def lookup_sides(case):
    """(probe frame, build frame, the build step the probe side gets) at
    ``LNPR`` x ``LNB``: about three probe rows a key, multiples of 3."""
    r = np.random.default_rng(len(case))
    pk = np.sort(r.integers(0, 8_000, LNPR)).astype(np.int32) * 3
    keys = np.unique(pk)
    bk = r.permutation(keys[::100][:LNB])        # a foreign key
    pmask = bmask = None
    step = "lookup"
    if case == "duplicate_build_keys":
        bk = r.permutation(np.resize(keys[::300], LNB))
        assert len(np.unique(bk)) < LNB / 2
    elif case == "a_long_probe_run_against_duplicate_build_keys":
        # 3,000 probe rows of one key x three build rows: more candidates
        # than the first slots hold
        pk[5_000:8_000] = pk[5_000]
        bk = np.concatenate([bk[:-3], [pk[5_000]] * 3])
    elif case == "masked_rows_on_both_sides":
        pmask, bmask = r.random(LNPR) < 0.6, r.random(LNB) < 0.7
        pmask[0] = pmask[-1] = False
    elif case == "build_keys_below_above_and_between":
        bk = r.permutation(np.concatenate(
            [r.integers(-50, 0, 16), pk.max() + 1 + r.integers(0, 50, 16),
             keys[::200][:16] + 1, keys[::200][:16]]))
    elif case == "an_empty_build_side_after_its_mask":
        bmask = np.zeros(LNB, bool)
    elif case == "float_keys_with_signed_zeros_and_a_build_nan":
        pk = pk.astype(np.float32) - 3_000.0
        at = np.searchsorted(pk, 0.0)
        pk[at:at + 4] = [-0.0, 0.0, -0.0, 0.0]
        bk = np.concatenate([bk[:-4].astype(np.float32) - 3_000.0,
                             [0.0, -0.0, np.nan, np.inf]])
    elif case == "a_nan_among_the_probe_keys":
        pk = pk.astype(np.float32)
        pk[12_345] = np.nan            # no order holds: the sort answers
        bk = np.concatenate([bk[:-1], [np.nan]])
        step = "miss"
    elif case != "foreign_keys_drawn_from_the_probe":
        raise KeyError(case)
    assert len(bk) == LNB
    probe = Frame({"k": pk, "a": r.normal(size=LNPR).astype(np.float32)},
                  mask=pmask)
    build = Frame({"k": bk.astype(pk.dtype),
                   "b": np.arange(LNB, dtype=np.float32)}, mask=bmask)
    return probe, build, step


LOOKUP_CASES = (
    "foreign_keys_drawn_from_the_probe", "duplicate_build_keys",
    "a_long_probe_run_against_duplicate_build_keys",
    "masked_rows_on_both_sides", "build_keys_below_above_and_between",
    "an_empty_build_side_after_its_mask",
    "float_keys_with_signed_zeros_and_a_build_nan",
    "a_nan_among_the_probe_keys",
)


@pytest.mark.parametrize("how, probe_is", [
    ("inner", "left"), ("inner", "right"), ("left_semi", "left")])
@pytest.mark.parametrize("case", LOOKUP_CASES)
def test_the_lookup_gives_the_sorts_join_bit_for_bit(joins, case, how,
                                                      probe_is):
    probe, build, step = lookup_sides(case)
    assert joins._takes_lookup(how, 1, LNB, LNPR)
    # (an inner join builds from the side with fewer slots)
    left, right = (probe, build) if probe_is == "left" else (build, probe)
    want, by_sort = run_join(joins, left, right, how, sort_only=True)
    got, by_lookup = run_join(joins, left, right, how)
    assert "join.lookup" not in by_sort
    assert by_lookup.get("join.lookup", 0) == (step == "lookup")
    assert by_lookup.get("join.merge_miss", 0) == (step == "miss")
    assert "join.merge" not in by_lookup
    assert by_lookup["join.device"] == 1 and "join.host" not in by_lookup
    same_rows(got, want)
    # the same slots, not only the same rows
    assert got.num_slots == want.num_slots
    assert np.array_equal(np.asarray(got._mask), np.asarray(want._mask))
    if case in ("foreign_keys_drawn_from_the_probe",
                "a_long_probe_run_against_duplicate_build_keys"):
        assert got.count() >= LNB                     # it joined something


def unique_probe(r, n=LNPR):
    """``n`` probe rows, one a key, in key order, and ``LNB`` build rows
    whose keys each meet one of them: the result fits the first bucket."""
    pk = np.arange(n, dtype=np.int32) * 3
    probe = Frame({"k": pk, "a": r.normal(size=n).astype(np.float32)})
    build = Frame({"k": r.permutation(pk)[:LNB],
                   "b": np.arange(LNB, dtype=np.float32)})
    return probe, build


@pytest.mark.parametrize("how", ["inner", "left_semi"])
def test_an_ordered_probe_against_few_keys_counts_a_lookup(joins, how):
    probe, build = unique_probe(np.random.default_rng(21))
    want = probe._host_join(build, ["k"], how, False, None)
    got = []
    before = counters.snapshot()
    (span,) = spans_of(lambda: got.append(probe.join(build, "k", how)))
    delta = moved(before)
    same_rows(got[0], want)
    assert delta["join.lookup"] == 1 and "join.merge" not in delta
    assert "join.merge_miss" not in delta and "join.scan_pallas" not in delta
    assert span.attrs["build_step"] == "lookup"
    (room,) = joins._ROOMS.values()
    assert span.attrs["room"] == room > 0
    # the order flag before the first program; then size, order and the
    # candidate count in the join's one read
    assert delta["join.compile"] == 1 and delta["host.reads"] == 2
    assert delta["host.read_bytes"] == 1 + 12
    # settled: the second run builds nothing and reads once
    before = counters.snapshot()
    again = probe.join(build, "k", how)
    delta = moved(before)
    same_rows(again, want)
    assert delta["join.hit"] == 1 and "join.compile" not in delta
    assert delta["join.lookup"] == 1 and delta["host.reads"] == 1
    assert delta["host.read_bytes"] == 12


def test_a_settled_lookup_that_meets_an_unordered_probe_sorts_it(joins):
    probe, build = unique_probe(np.random.default_rng(22))
    probe.join(build, "k", "inner")                # settles on the lookup
    d = probe.to_pydict()
    at = np.random.default_rng(7).permutation(LNPR)
    shuffled = Frame({"k": d["k"][at], "a": d["a"][at]})
    want = shuffled._host_join(build, ["k"], "inner", False, None)
    before = counters.snapshot()
    got = []
    (span,) = spans_of(lambda: got.append(
        shuffled.join(build, "k", "inner")))
    delta = moved(before)
    same_rows(got[0], want)
    # the lookup program itself found it, in its one read; then the sort
    assert delta["join.merge_miss"] == 1 and "join.lookup" not in delta
    assert delta["join.compile"] == 1 and delta["join.hit"] == 1
    assert delta["host.reads"] == 2 and delta["host.read_bytes"] == 12 + 4
    assert span.attrs["build_step"] == "sort"
    before = counters.snapshot()
    again = shuffled.join(build, "k", "inner")
    delta = moved(before)
    same_rows(again, want)
    assert "join.merge_miss" not in delta and "join.lookup" not in delta
    assert delta["join.hit"] == 1 and "join.compile" not in delta
    assert delta["host.reads"] == 1 and delta["host.read_bytes"] == 4


def test_candidates_over_their_slots_run_once_more_and_then_fit(joins):
    probe, build, _ = lookup_sides(
        "a_long_probe_run_against_duplicate_build_keys")
    want = probe._host_join(build, ["k"], "inner", False, None)
    before = counters.snapshot()
    same_rows(probe.join(build, "k", "inner"), want)
    delta = moved(before)
    # the lookup at the first bucket's slots, again at the candidates'
    # (no miss: the order held), again at the result's bucket
    assert delta["join.lookup"] == 1 and "join.merge_miss" not in delta
    assert delta["join.compile"] == 3
    (room,), (bucket,) = joins._ROOMS.values(), joins._BUCKETS.values()
    assert room >= bucket >= 9_000 > LNB
    before = counters.snapshot()
    again = probe.join(build, "k", "inner")
    delta = moved(before)
    same_rows(again, want)
    assert delta["join.hit"] == 1 and "join.compile" not in delta
    assert delta["join.lookup"] == 1 and delta["host.reads"] == 1


#: (probe slots, build keys, probe rows a key) at which the lookup's room
#: is one whole row of the chunked sorts: the first bucket of the build
#: keys, or the bucket of the candidates a rerun asks for
WHOLE_ROW = {
    (128, "first_bucket"): (40_000, 120, 1),
    (128, "rerun"): (40_000, 32, 4),
    (1 << 15, "first_bucket"): (12_000_000, 30_000, 1),
    (1 << 15, "rerun"): (4_000_000, 8_192, 4),
}


@pytest.mark.parametrize("chunk, how, probe_is", [
    (128, "inner", "left"), (128, "inner", "right"),
    (128, "left_semi", "left"),
    (1 << 15, "inner", "left"), (1 << 15, "left_semi", "left")])
@pytest.mark.parametrize("room_from", ["first_bucket", "rerun"])
def test_a_lookup_room_of_a_whole_row_gives_the_sorts_join(
        monkeypatch, joins, room_from, chunk, how, probe_is):
    # the room is the lookup's slots, not a chunk's build slots: a room
    # of a whole row builds no chunks of the merge
    monkeypatch.setattr(joins, "_CHUNK", chunk)
    npr, nb, run = WHOLE_ROW[chunk, room_from]
    assert joins._takes_lookup(how, 1, nb, npr)
    r = np.random.default_rng(nb)
    pk = (np.arange(npr, dtype=np.int32) // run) * 3
    probe = Frame({"k": pk})
    build = Frame({"k": r.choice(pk[::run], nb, replace=False),
                   "b": np.arange(nb, dtype=np.float32)})
    left, right = (probe, build) if probe_is == "left" else (build, probe)
    want, _ = run_join(joins, left, right, how, sort_only=True)
    got, by_lookup = run_join(joins, left, right, how)
    assert by_lookup["join.lookup"] == 1
    assert "join.merge_miss" not in by_lookup
    (room,) = joins._ROOMS.values()
    assert room == chunk
    # the first bucket held the candidates, or they ran once more in a
    # row's slots and the result once more in its own bucket
    assert by_lookup["join.compile"] == (1 if room_from == "first_bucket"
                                         else 3)
    same_rows(got, want)
    assert got.num_slots == want.num_slots
    assert np.array_equal(np.asarray(got._mask), np.asarray(want._mask))
    assert got.count() == nb * run          # every key meets its run


@pytest.mark.parametrize("how", ["left", "left_anti"])
def test_left_and_anti_joins_never_take_the_lookup(joins, how):
    probe, build, _ = lookup_sides("foreign_keys_drawn_from_the_probe")
    assert not joins._takes_lookup(how, 1, LNB, LNPR)
    assert joins._takes_lookup("inner", 1, LNB, LNPR)
    want = probe._host_join(build, ["k"], how, False, None)
    got = []
    before = counters.snapshot()
    (span,) = spans_of(lambda: got.append(probe.join(build, "k", how)))
    delta = moved(before)
    same_rows(got[0], want)
    assert "join.lookup" not in delta and delta["join.merge"] == 1
    assert span.attrs["build_step"] == "merge"


def test_emission_order_is_left_then_right_row_order():
    left = Frame({"k": np.asarray([2, 1, 2, 3], np.int32),
                  "a": np.asarray([0., 1., 2., 3.], np.float32)})
    right = Frame({"k": np.asarray([2, 3, 2, 1, 2], np.int32),
                   "b": np.asarray([0., 1., 2., 3., 4.], np.float32)})
    for build in (None, "left"):
        d = left.join(right, "k", "inner", build=build).to_pydict()
        assert d["a"].tolist() == [0., 0., 0., 1., 2., 2., 2., 3.]
        assert d["b"].tolist() == [0., 2., 4., 3., 0., 2., 4., 1.]


def test_a_second_run_of_one_join_builds_nothing_and_reads_one_scalar():
    left, right, keys = sides("one_key_duplicates_on_both")
    left.join(right, keys, "inner")
    before = counters.snapshot()
    left.join(right, keys, "inner")
    delta = moved(before)
    assert delta.get("join.hit") == 1 and "join.compile" not in delta
    assert delta["host.reads"] == 1 and delta["host.read_bytes"] <= 8


@pytest.mark.parametrize("how, keys", [
    ("right", "numeric"), ("outer", "numeric"), ("inner", "string"),
    ("left", "string"), ("inner", "integer_against_float"),
    ("cross", "none")])
def test_what_stays_on_the_host_answers_there_and_says_so(how, keys):
    if keys == "string":
        left = Frame({"k": ["a", "b", "c", "b"], "x": [1., 2., 3., 4.]})
        right = Frame({"k": ["b", "c", "d"], "y": [5., 6., 7.]})
    elif keys == "integer_against_float":
        left = Frame({"k": np.asarray([1, 2, 3, 2], np.int32),
                      "x": [1., 2., 3., 4.]})
        right = Frame({"k": np.asarray([2., 3., 4.], np.float32),
                       "y": [5., 6., 7.]})
    else:
        left = Frame({"k": [1., 2., 3., 2.], "x": [1., 2., 3., 4.]})
        right = Frame({"k": [2., 3., 4.], "y": [5., 6., 7.]})
    before = counters.snapshot()
    out = left.join(right, None if how == "cross" else "k", how)
    delta = moved(before)
    assert delta.get("join.host") == 1 and "join.device" not in delta
    # the host plan's mask and key pulls are counted reads now
    assert delta["host.reads"] >= 2
    d = out.to_pydict()
    if how == "cross":
        assert len(d["x"]) == 12
    elif how == "right":
        assert sorted(d["y"].tolist()) == [5., 5., 6., 7.]
        assert np.isnan(d["x"]).sum() == 1
    elif how == "outer":
        assert len(d["x"]) == 5 and np.isnan(d["y"]).sum() == 1
    elif how == "left":
        assert d["x"].tolist() == [1., 2., 3., 4.]
        assert np.isnan(d["y"]).sum() == 1
    else:
        assert d["x"].tolist() == [2., 3., 4.]
        assert d["y"].tolist() == [5., 6., 5.]


def test_a_key_pair_joins_two_names_and_keeps_both():
    orders = Frame({"o_key": np.asarray([10, 11, 12], np.int32),
                    "o_cust": np.asarray([1, 3, 1], np.int32)})
    cust = Frame({"c_key": np.asarray([1, 2], np.int32),
                  "c_seg": np.asarray([7, 8], np.int32)})
    inner = orders.join(cust, [("o_cust", "c_key")], "inner")
    d = inner.to_pydict()
    assert inner.columns == ["o_key", "o_cust", "c_seg", "c_key"]
    assert d["o_key"].tolist() == [10, 12]
    assert d["c_key"].tolist() == d["o_cust"].tolist() == [1, 1]
    left = orders.join(cust, [("o_cust", "c_key")], "left").to_pydict()
    assert left["o_cust"].tolist() == [1, 3, 1]
    assert np.isnan(left["c_key"][1]) and left["c_key"][0] == 1
    semi = orders.join(cust, [("o_cust", "c_key")], "left_anti")
    assert semi.to_pydict()["o_key"].tolist() == [11]
    with pytest.raises(ValueError, match="shared column name"):
        orders.join(cust.with_column_renamed("c_seg", "o_cust"),
                    [("o_cust", "c_key")], "inner")


# ---------------------------------------------------------------------------
# SQL: the comma FROM list and ON over two names plan as USING does
# ---------------------------------------------------------------------------

@pytest.fixture
def tables(session):
    r = np.random.default_rng(0)
    cust = Frame({"c_key": np.arange(1, 41, dtype=np.int32),
                  "c_seg": r.integers(0, 3, 40).astype(np.int32)})
    orders = Frame({"o_key": np.arange(100, 300, dtype=np.int32),
                    "o_cust": r.integers(1, 61, 200).astype(np.int32),
                    "o_total": r.normal(size=200).astype(np.float32)})
    same = orders.with_column_renamed("o_cust", "c_key")
    cust.create_or_replace_temp_view("cust")
    orders.create_or_replace_temp_view("orders")
    same.create_or_replace_temp_view("orders_using")
    return cust, orders


FORMS = {
    "comma_from": "SELECT o_key, c_seg FROM cust, orders "
                  "WHERE c_key = o_cust AND c_seg = 1 AND o_total > 0",
    "on_two_names": "SELECT o_key, c_seg FROM cust JOIN orders "
                    "ON c_key = o_cust WHERE c_seg = 1 AND o_total > 0",
    "on_turned_and_qualified": "SELECT o_key, c_seg FROM cust c JOIN orders o "
                               "ON o.o_cust = c.c_key "
                               "WHERE c.c_seg = 1 AND o.o_total > 0",
}
USING = ("SELECT o_key, c_seg FROM cust JOIN orders_using USING (c_key) "
         "WHERE c_seg = 1 AND o_total > 0")


def plan_of(session, sql):
    text = str(session.sql("EXPLAIN " + sql).to_pydict()["plan"][0])
    return text.replace("orders_using", "orders")


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_new_forms_give_the_plan_and_the_rows_using_gives(
        session, tables, form):
    want = session.sql(USING).to_pydict()
    before = counters.snapshot()
    got = session.sql(FORMS[form]).to_pydict()
    assert moved(before).get("join.device") == 1
    assert got["o_key"].tolist() == want["o_key"].tolist()
    assert got["c_seg"].tolist() == want["c_seg"].tolist()
    physical, rewrites = plan_of(session, FORMS[form]) \
        .split("== Before Optimization ==")[0].split("== Rewrites ==")
    want_physical, want_rewrites = plan_of(session, USING) \
        .split("== Before Optimization ==")[0].split("== Rewrites ==")
    # the same tree: one inner join, both filters pushed under it
    assert "Join[inner" in physical and rewrites.count("pushdown") == 2

    def shape(text):
        return [ln.split("(est")[0].rstrip() for ln in text.splitlines()]

    assert shape(physical) == shape(want_physical)


def test_a_comma_list_without_an_equality_is_a_cross_join(session, tables):
    out = session.sql("SELECT o_key FROM cust, orders WHERE c_seg = 9")
    assert out.count() == 0
    assert session.sql("SELECT count(*) AS n FROM cust, orders") \
        .to_pydict()["n"].tolist() == [40 * 200]


@pytest.mark.parametrize("on", [
    "cust.c_key = cust.c_seg",         # both of one side
    "c_key < o_cust",                  # no equality
    "c_key = nowhere"])                # a name neither side has
def test_an_on_that_is_no_equality_of_one_column_a_side_keeps_its_error(
        session, tables, on):
    with pytest.raises(ValueError):
        session.sql(f"SELECT o_key FROM cust JOIN orders ON {on}")


def test_on_without_the_optimizer_settles_its_keys_at_execution(
        session, tables):
    from sparkdq4ml_tpu.config import config

    want = session.sql(USING).to_pydict()["o_key"].tolist()
    config.optimizer_enabled = False
    try:
        for form in sorted(FORMS):
            assert session.sql(FORMS[form]).to_pydict()["o_key"].tolist() \
                == want
    finally:
        config.optimizer_enabled = True


# ---------------------------------------------------------------------------
# TPC-H Q3 as published, against the configuration's float64 reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def q3_cell():
    from benchmarks import harness

    return harness.load_cell("tpch_q3_join", REPO)


@pytest.mark.parametrize("seed", [3, 2_600_000_011])
def test_q3_through_spark_sql_equals_its_float64_reference(
        session, q3_cell, seed):
    import jax

    cfg, mod, job_mod = q3_cell["cfg"], q3_cell["cfg_mod"], \
        q3_cell["job_mod"]
    params = q3_cell["traffic"]["params"]
    table = mod.make_table(cfg, seed, 24_000)      # 6,000 orders
    job = job_mod.Job(session, cfg, mod, params, table)
    try:
        plan = str(session.sql("EXPLAIN " + job.query)
                   .to_pydict()["plan"][0])
        physical, rest = plan.split("== Rewrites ==")
        # two joins over three filtered scans, the first join innermost
        assert physical.count("Join[inner") == 2
        assert physical.count("Filter") == 3
        assert physical.index("Scan[customer]") \
            < physical.index("Scan[orders]") \
            < physical.index("Scan[lineitem]")
        assert rest.count("pushdown") == 3
        before = counters.snapshot()
        rows = session.sql(job.query)
        got = {k: np.asarray(v) for k, v in rows.to_pydict().items()}
        delta = moved(before)
    finally:
        job.close()
    assert delta.get("join.device") == 2 and "join.host" not in delta
    assert "grouped.fallback" not in delta
    want = job_mod.reference(cfg, mod, params, jax.device_get(table))
    assert want["groups"] > 10 and len(got["revenue"]) == 10
    gaps = job_mod.compare(got, want)
    assert gaps["rows_diff"] == 0
    assert gaps["revenue_rel"] < q3_cell["traffic"]["limits"]["revenue_rel"]
    assert np.all(np.diff(got["revenue"]) <= 0)


def test_limit_over_a_compact_frame_is_a_slice():
    f = Frame({"a": np.arange(100, dtype=np.float32)}).sort("a")
    cut = f.limit(10)
    assert cut.num_slots == 10
    assert cut.to_pydict()["a"].tolist() == list(range(10))
    masked = Frame({"a": np.arange(8, dtype=np.float32)},
                   mask=np.arange(8) % 2 == 0).limit(2)
    assert masked.num_slots == 8
    assert masked.to_pydict()["a"].tolist() == [0.0, 2.0]


def test_a_many_group_result_keeps_a_bucket_of_slots_under_a_mask():
    n = 300_000
    f = Frame({"k": np.arange(n, dtype=np.int32) // 2,
               "v": np.ones(n, np.float32)})
    out = f.group_by("k").agg({"v": "sum"})
    assert out.num_slots >= n // 2 and out.count() == n // 2
    top = out.sort("k", ascending=False).limit(3).to_pydict()
    assert top["k"].tolist() == [n // 2 - 1, n // 2 - 2, n // 2 - 3]


@pytest.mark.parametrize("share", [0.0, 0.0005, 0.002])
def test_the_chunked_compaction_finds_what_the_full_sort_finds(share):
    """A small result over a large input compacts inside chunks
    (``ops/joins._compact``): the same positions as the one sort gives."""
    import jax.numpy as jnp

    from sparkdq4ml_tpu.ops import joins

    n, bucket = 3 * joins._CHUNK + 1234, 512
    sel = np.random.default_rng(4).random(n) < share
    sel[-1] = share > 0                       # the ragged last chunk holds one
    want = np.nonzero(sel)[0]
    assert len(want) <= bucket and bucket * 16 <= n
    got = np.asarray(joins._compact(jnp.asarray(sel), n, bucket))
    assert np.array_equal(got[:len(want)], want)
    assert got.min() >= 0 and got.max() < n


# ---------------------------------------------------------------------------
# The probe's scans: the Pallas kernel (through the interpreter) against
# XLA's cumsum and two cummax, integer for integer
# ---------------------------------------------------------------------------

HIGH = np.uint32(1 << 31)
# the kernel's grid step in these tests: two slabs of 8 x 128 pairs
SCAN_ROWS, SCAN_BLOCK = 8, 2048


def sorted_pairs(seed, n, nb, keys=1, groups=60, masked=0.2,
                 floating=False, run=0):
    """``n`` (key, tag) pairs in (key, tag) order, as the join's build step
    leaves them: tags under ``nb`` are build rows, a masked pair's tag has
    its high bit set; ``run`` pairs share one key (a group that long)."""
    r = np.random.default_rng(seed)
    cols = [r.integers(0, groups, n).astype(np.int32) for _ in range(keys)]
    if run:
        cols[0][r.permutation(n)[:run]] = groups
    if floating:
        cols = [c.astype(np.float32) - groups // 2 for c in cols]
        cols[0][r.random(n) < 0.05] = np.nan
        # zeros of both signs, one group: the program adds 0.0 to a float
        # key, which leaves none negative; the kernel holds that too
        zero = r.random(n) < 0.1
        cols[0][zero] = np.where(r.random(zero.sum()) < 0.5, -0.0, 0.0)
    tag = np.arange(n, dtype=np.uint32)
    tag = np.where(r.random(n) < masked, tag | HIGH, tag)
    order = np.lexsort([tag] + cols[::-1])
    return [c[order] for c in cols], tag[order]


def merge_pairs(monkeypatch):
    """The merge's chunked pairs (``_merge``: 2^15-pair rows here cut to
    128), flattened as the program holds them."""
    import jax.numpy as jnp

    from sparkdq4ml_tpu.ops import joins

    monkeypatch.setattr(joins, "_CHUNK", 128)
    r = np.random.default_rng(9)
    pk = ordered_keys(r, run=3)
    bk = spread(r, pk, 4)
    room = joins._first_room(1, NB, NPR)
    ks, ts, ordered, need = joins._merge(
        jnp.asarray(bk), jnp.asarray(r.random(NB) < 0.8), jnp.asarray(pk),
        jnp.asarray(r.random(NPR) < 0.7), room)
    assert bool(ordered) and int(need) <= room
    assert ks.shape[0] == joins._chunks(NPR, room) * 128
    return [np.asarray(ks)], np.asarray(ts), NB


SCAN_CASES = {
    "one_int_key": dict(n=5_000, nb=700),
    "two_int_keys": dict(n=5_000, nb=1_500, keys=2, groups=9),
    "float_keys_nan_and_both_zeros": dict(n=4_000, nb=1_000, floating=True),
    "two_float_keys": dict(n=3_000, nb=900, keys=2, groups=6,
                           floating=True),
    "masked_rows": dict(n=6_000, nb=2_000, masked=0.5),
    "groups_over_step_borders": dict(n=7_000, nb=300, groups=4),
    "a_group_over_three_steps": dict(n=9_000, nb=4_000, run=6_500),
    "only_build_rows": dict(n=5_000, nb=5_000),
    "only_probe_rows": dict(n=5_000, nb=0),
    "under_one_step": dict(n=700, nb=200),
    "no_step_multiple": dict(n=3 * SCAN_BLOCK + 517, nb=2_000),
    "merge_chunked_pairs": None,
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_the_scan_kernel_gives_the_xla_scans_integer_for_integer(
        monkeypatch, case):
    import jax.numpy as jnp

    from sparkdq4ml_tpu.ops import joins

    spec = SCAN_CASES[case]
    if spec is None:
        ks, ts, nb = merge_pairs(monkeypatch)
    else:
        ks, ts = sorted_pairs(len(case), **spec)
        nb = spec["nb"]
    ks, ts = [jnp.asarray(c) for c in ks], jnp.asarray(ts)
    want = joins._scans_xla(ks, ts, nb)
    got = joins._scans_pallas(ks, ts, nb, block=SCAN_BLOCK, rows=SCAN_ROWS,
                              interpret=True)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.int32 and g.shape == w.shape
        assert np.array_equal(np.asarray(g), np.asarray(w))
    if case == "a_group_over_three_steps":
        head = np.asarray(want[0])
        assert np.bincount(head).max() >= 2 * SCAN_BLOCK + 1
    if case != "only_probe_rows" and spec is not None and spec["nb"] < \
            spec["n"]:
        assert np.asarray(want[1]).max() > 0          # something matched


def take_the_kernel(monkeypatch, joins):
    """Steer ``joins`` to the kernel, run by the Pallas interpreter with
    grid steps of two slabs of 8 x 128 pairs; returns the list of what
    :func:`scan_lowering` would have chosen."""
    import functools

    real, taken = joins.scan_lowering, []

    def pallas(*args):
        taken.append(real(*args))
        return "pallas"

    monkeypatch.setattr(joins, "scan_lowering", pallas)
    monkeypatch.setattr(joins, "_scans_pallas", functools.partial(
        joins._scans_pallas, block=SCAN_BLOCK, rows=SCAN_ROWS,
        interpret=True))
    return taken


@pytest.mark.parametrize("step", ["sort", "merge"])
@pytest.mark.parametrize("how", HOWS)
def test_a_join_with_the_kernel_is_the_join_with_xla_scans(
        monkeypatch, joins, how, step):
    probe, build, _ = merge_sides("key_groups_over_chunk_borders", joins)
    want, by_xla = run_join(joins, probe, build, how,
                            sort_only=step == "sort")
    taken = take_the_kernel(monkeypatch, joins)
    got, by_kernel = run_join(joins, probe, build, how,
                              sort_only=step == "sort")
    # the CPU's own choice was XLA's scans
    assert taken and set(taken) == {"xla"}
    assert by_kernel.get("join.merge", 0) == (step == "merge") \
        == by_xla.get("join.merge", 0)
    assert by_kernel["join.scan_pallas"] == 1
    assert "join.scan_pallas" not in by_xla
    same_rows(got, want)
    assert got.num_slots == want.num_slots
    assert np.array_equal(np.asarray(got._mask), np.asarray(want._mask))
    if how != "left_anti":
        assert got.count() > 0


def test_the_join_span_says_which_scans_ran(monkeypatch, joins):
    probe, build, _ = merge_sides("foreign_keys_drawn_from_the_probe", joins)
    (span,) = spans_of(lambda: probe.join(build, "k", "inner"))
    assert span.attrs["probe_scan"] == "xla"
    take_the_kernel(monkeypatch, joins)
    before = counters.snapshot()
    (span,) = spans_of(lambda: probe.join(build, "k", "inner"))
    delta = moved(before)
    assert span.attrs["probe_scan"] == "pallas"
    assert span.attrs["build_step"] == "merge"
    assert delta["join.scan_pallas"] == 1 and delta["join.compile"] == 1


def test_the_scan_lowering_follows_backend_dtype_and_devices(monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkdq4ml_tpu.ops import joins
    from sparkdq4ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

    i32 = jnp.arange(4096, dtype=jnp.int32)
    f32 = i32.astype(jnp.float32)
    i64 = i32.astype(jnp.int64)          # the tests run under x64
    assert i64.dtype == jnp.int64
    d32, f, d64 = np.dtype(np.int32), np.dtype(np.float32), \
        np.dtype(np.int64)
    # the CPU of the tests
    assert joins.scan_lowering([i32, i32], (d32,), 8192) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert joins.scan_lowering([i32, i32], (d32,), 8192) == "pallas"
    assert joins.scan_lowering([f32, f32], (f,), 8192) == "pallas"
    assert joins.scan_lowering([i32, f32, i32, f32], (d32, f), 8192) \
        == "pallas"
    # 64-bit keys under x64
    assert joins.scan_lowering([i64, i64], (d64,), 8192) == "xla"
    assert joins.scan_lowering([i32, i64, i32, i64], (d32, d64), 8192) \
        == "xla"
    # fewer pairs than a vreg holds
    assert joins.scan_lowering([i32, i32], (d32,), 1023) == "xla"
    assert joins.scan_lowering([i32, i32], (d32,), 1024) == "pallas"
    # a key spread over a mesh
    mesh = make_mesh()
    spread_key = jax.device_put(i32, NamedSharding(mesh, P(DATA_AXIS)))
    assert len(spread_key.sharding.device_set) > 1
    assert joins.scan_lowering([spread_key, i32], (d32,), 8192) == "xla"
