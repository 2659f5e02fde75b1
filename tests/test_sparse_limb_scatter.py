"""A sparse plan's slot tables in 32 bits (PR 42), read from prefix sums (PR 44).

Under `accum_policy()` = "chunked32" (the chip) `planner.sparse_grouped_tables`
keeps, after its sort, ONE row-length scatter (each slot's first row) and reads
the keys off the sorted key there, every count as the slot's row range (or a
prefix sum of the aggregate's own mask) and every sum of an integer input as
8-bit limbs' int32 prefix sums at the range's two ends
(`ops.limb_prefix_table`), met in int64 at table size: what it returns is what
it returned (int64 keys with `SPARSE_EMPTY_KEY`, int64 counts, f64 sums); PR 42
had scattered them as int32 limb tables (`scan.traced.sparse_limb_scatter`
still says a sum rode limbs).  Float sums, min / max and the sketch family
keep their 64-bit scatters.  On the CPU the engine takes "wide", so here the
policy is steered as tests/test_sparse_drill_exact.py steers it, and every
case is held to a numpy group-by at difference 0 and to the "wide" policy's
tables (the 64-bit scatters the chip ran before PR 42) field by field.
"""
import jax
import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.ops import segmented
from pinot_tpu.query import planner
from pinot_tpu.query.engine import QueryEngine
from pinot_tpu.query.functions import get_agg_function
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils.metrics import METRICS
from tests.test_ssb_templates_chip_path import chip_path  # noqa: F401  (chunked32 for this module's traces)

LIMB = "scan.traced.sparse_limb_scatter"
PREFIX = "scan.traced.sparse_prefix_sums"
GROUPS = 3_000  # the key space (num_groups): every key is below it


def _rows(seed, n, values, vrange=None, groups=GROUPS):
    """n rows: a key in [0, groups), a filter that passes ~90 %, an
    aggregation mask that drops a few more (a nullable input)."""
    rng = np.random.default_rng(seed)
    key = rng.integers(0, groups, n).astype(np.int64)
    tmask = rng.random(n) < 0.9
    mask = tmask & (rng.random(n) < 0.97)
    return {"key": key, "tmask": tmask, "mask": mask, "vals": values(rng, n, key), "vrange": vrange}


def _hot_sums(rng, n, key):
    """int32 values near the type's top: group 7 (~200 rows) passes 2^31,
    group 1,500 (thousands of rows at 2^31 - 1) passes 2^40."""
    v = rng.integers(-5, 1 << 20, n).astype(np.int32)
    key[rng.permutation(n)[:4_000]] = 1_500
    v[key == 1_500] = np.iinfo(np.int32).max
    v[key == 7] = np.iinfo(np.int32).max - 3
    return v


CASES = {
    # (a) a bare int32 column with stats: two 12-bit limbs, no sign table
    "int32_column_with_stats": dict(
        n=40_000, values=lambda rng, n, key: rng.integers(0, 60_000, n).astype(np.int32), vrange=(0, 59_999), limbs=True),
    # (b) an int32 expression (no stats): negatives, three limbs and the negatives' count
    "int32_expression_negative": dict(
        n=40_000, values=lambda rng, n, key: (rng.integers(0, 1 << 24, n) - rng.integers(0, 1 << 25, n)).astype(np.int32),
        limbs=True),
    # (c) an int64 column past 2^40, both signs: signed-magnitude limbs
    "int64_column_past_2_40": dict(
        n=40_000, values=lambda rng, n, key: rng.integers(-(1 << 44), 1 << 44, n).astype(np.int64),
        vrange=(-(1 << 44), (1 << 44) - 1), limbs=True),
    # (d) sums past 2^31 and past 2^40, over more than one 2^19-row chunk
    "sums_past_2_31_and_2_40": dict(n=600_000, values=_hot_sums, limbs=True),
    # (e) a float column: the parent's f64 scatter
    "float_column": dict(
        n=40_000, values=lambda rng, n, key: np.round(rng.random(n) * 1000.0, 3), limbs=False),
}


def _plan_tables(key, tmask, aggs, num_slots, order_spec=None, groups=GROUPS, vranges=()):
    """`aggs` = [(function name, values, mask or None for the filter's own)] as ONE plan through
    sparse_grouped_tables under jit -> (uniq, [tables an aggregate], how far LIMB moved, how far PREFIX moved)."""
    fns = [get_agg_function(name) for name, _, _ in aggs]

    def kernel(key, tmask, vals, masks):
        inputs = [(v, tmask if m is None else m) for v, m in zip(vals, masks)]
        return planner.sparse_grouped_tables(fns, inputs, tmask, key, num_slots, order_spec, num_groups=groups, vranges=vranges)

    before = METRICS.counter(LIMB).value, METRICS.counter(PREFIX).value
    uniq, parts = jax.jit(kernel)(key, tmask, [v for _, v, _ in aggs], [m for _, _, m in aggs])
    moved = METRICS.counter(LIMB).value - before[0], METRICS.counter(PREFIX).value - before[1]
    return np.asarray(uniq), [{f: np.asarray(t) for f, t in p.items()} for p in parts], *moved


def _tables(rows, num_slots, order_spec=None, fn_name="sum", groups=GROUPS):
    """One aggregate with a mask of its own over `rows` -> (uniq, its tables, how far LIMB moved at trace time)."""
    uniq, (part,), moved, _ = _plan_tables(rows["key"], rows["tmask"], [(fn_name, rows["vals"], rows["mask"])], num_slots,
                                           order_spec, groups, [rows["vrange"]])
    return uniq, part, moved


def _numpy_groups(rows):
    """{key: (count, exact sum)} over the filtered rows, the sum in Python integers (floats: math.fsum-free, in order)."""
    out = {}
    vals = rows["vals"]
    exact = np.issubdtype(vals.dtype, np.integer)
    for k in np.unique(rows["key"][rows["tmask"]]).tolist():
        at = (rows["key"] == k) & rows["mask"]
        out[k] = (int(at.sum()), sum(vals[at].tolist()) if exact else float(vals[at].sum()))
    return out


def _as_groups(uniq, part):
    live = uniq != planner.SPARSE_EMPTY_KEY
    return {int(k): (int(c), s) for k, c, s in zip(uniq[live], part["count"][live], part["sum"][live])}


@pytest.mark.parametrize("name", sorted(CASES))
def test_limb_tables_equal_a_numpy_group_by_and_the_wide_policy(name, chip_path, monkeypatch):
    case = CASES[name]
    rows = _rows(42, case["n"], case["values"], case.get("vrange"))
    uniq, part, moved = _tables(rows, GROUPS)
    # what the function returns is what it returned
    assert (uniq.dtype, part["count"].dtype, part["sum"].dtype) == (np.int64, np.int64, np.float64)
    assert moved == (1 if case["limbs"] else 0)
    want = _numpy_groups(rows)
    got = _as_groups(uniq, part)
    assert got.keys() == want.keys()
    if case["limbs"]:
        assert max(abs(s) for _, s in want.values()) < 1 << 53  # f64 holds every sum: the comparison is exact
        assert all(got[k][0] == c and int(got[k][1]) == s and got[k][1] == s for k, (c, s) in want.items())
    if name == "sums_past_2_31_and_2_40":
        assert want[7][1] > 1 << 31 and want[1_500][1] > 1 << 40
        # a group's rows straddle the first chunk boundary of the sorted order
        skey = np.sort(np.where(rows["tmask"], rows["key"], np.iinfo(np.int64).max))
        assert skey[segmented._WIDE_CHUNK - 1] == skey[segmented._WIDE_CHUNK]
    # the 64-bit scatters: the same tables, slot by slot and bit by bit
    monkeypatch.setattr(ops, "accum_policy", lambda: "wide")
    w_uniq, w_part, w_moved = _tables(rows, GROUPS)
    assert w_moved == 0
    assert np.array_equal(uniq, w_uniq) and all(np.array_equal(part[f], w_part[f]) for f in ("count", "sum"))


@pytest.mark.parametrize("ordered", [False, True], ids=["lowest_keys", "order_by_sum_desc"])
def test_the_trim_is_the_parents_and_the_overflow_slot_is_dropped(ordered, chip_path, monkeypatch):
    """(f) fewer slots than groups: the rows of the groups that lose their slot land in the overflow slot, which
    is sliced off; which groups keep a slot is the parent's choice (lowest packed keys, or ORDER BY SUM(..) DESC)."""
    rows = _rows(43, 40_000, CASES["int32_expression_negative"]["values"])
    slots = 500
    order_spec = (0, "sum", False) if ordered else None
    uniq, part, moved = _tables(rows, slots, order_spec)
    assert moved == 1 and uniq.shape == part["count"].shape == part["sum"].shape == (slots,)
    want = _numpy_groups(rows)
    assert len(want) > slots
    if ordered:
        keep = sorted(want, key=lambda k: (-want[k][1], k))[:slots]
    else:
        keep = sorted(want)[:slots]
    got = _as_groups(uniq, part)
    assert sorted(got) == sorted(keep)
    assert all(got[k] == (want[k][0], float(want[k][1])) for k in keep)
    monkeypatch.setattr(ops, "accum_policy", lambda: "wide")
    w_uniq, w_part, _ = _tables(rows, slots, order_spec)
    assert np.array_equal(uniq, w_uniq) and all(np.array_equal(part[f], w_part[f]) for f in ("count", "sum"))


@pytest.mark.parametrize("fn_name,fields", [("min", ("min", "count")), ("variance", ("count", "sum", "sumsq"))])
def test_min_max_and_sumsq_keep_the_parents_scatter(fn_name, fields, chip_path, monkeypatch):
    """A min / max table and a sum of squares are not limb tables: the same f64 tables as under "wide"; the
    count beside them (and the variance's integer sum) take the int32 form and still equal the parent's."""
    rows = _rows(44, 20_000, CASES["int32_expression_negative"]["values"])
    uniq, part, moved = _tables(rows, GROUPS, fn_name=fn_name)
    assert sorted(part) == sorted(fields) and moved == (1 if "sum" in fields else 0)
    monkeypatch.setattr(ops, "accum_policy", lambda: "wide")
    w_uniq, w_part, _ = _tables(rows, GROUPS, fn_name=fn_name)
    assert np.array_equal(uniq, w_uniq)
    assert all(part[f].dtype == w_part[f].dtype and np.array_equal(part[f], w_part[f]) for f in fields)


def test_a_key_space_past_2_31_keeps_its_int64_keys(chip_path):
    """Past 2^31 the rows sort by their int64 keys and the slots' keys are gathered as int64, the counts and
    sums still read from int32 prefix sums."""
    rows = _rows(45, 20_000, CASES["int32_column_with_stats"]["values"], (0, 59_999), groups=5_000)
    rows["key"] = rows["key"] + ((1 << 31) - 10) * (rows["key"] % 2)
    uniq, part, moved = _tables(rows, 20_000, groups=(1 << 31) + 5_000)
    assert moved == 1 and _as_groups(uniq, part) == {k: (c, float(s)) for k, (c, s) in _numpy_groups(rows).items()}
    assert uniq[uniq != planner.SPARSE_EMPTY_KEY].max() > 1 << 31


def test_the_mv_explode_rides_the_limb_tables(chip_path):
    """(g) a multi-value GROUP BY dimension under a sparse plan: n * max_len exploded rows through the same
    function, each element of the array one logical row."""
    rng = np.random.default_rng(46)
    n, tags_all = 4_000, [f"t{i:02d}" for i in range(40)]
    tags = [list(rng.choice(tags_all, size=int(rng.integers(0, 4)), replace=False)) for _ in range(n)]
    data = {"city": rng.choice(["sf", "nyc", "la"], n).astype(object), "tags": tags,
            "v": rng.integers(-(1 << 20), 1 << 30, n)}
    schema = Schema("mv", [FieldSpec("city", DataType.STRING), FieldSpec("tags", DataType.STRING, single_value=False),
                           FieldSpec("v", DataType.LONG, role=FieldRole.METRIC)])
    eng = QueryEngine()
    eng.register_table(schema)
    eng.add_segment("mv", build_segment(schema, data, "s0"))
    before = METRICS.counter(LIMB).value
    res = eng.query("SET maxDenseGroups = 2; SELECT tags, city, COUNT(*), SUM(v) FROM mv WHERE v <> 17 "
                    "GROUP BY tags, city ORDER BY tags, city LIMIT 1000")
    assert METRICS.counter(LIMB).value == before + 1
    want = {}
    for ts, c, v in zip(tags, data["city"], data["v"].tolist()):
        for t in ts:
            cnt, s = want.get((t, c), (0, 0))
            want[(t, c)] = (cnt + 1, s + v) if v != 17 else (cnt, s)
    assert [(r[0], r[1], int(r[2]), int(r[3])) for r in res.rows] == [(t, c, *want[(t, c)]) for t, c in sorted(want)]
    assert max(s for _, s in want.values()) > 1 << 31


# ---------------------------------------------------------------------------
# PR 44: counts and integer sums read from prefix sums over the sorted rows
# ---------------------------------------------------------------------------
# After the sort a group's rows are contiguous, so under "chunked32" ONE
# row-length scatter records each slot's first row and a count / an integer
# sum is the difference of an int32 prefix sum (one an 8-bit limb:
# segmented.prefix_limb_bits) at the slot's two bounds.  Every case below is
# a whole plan, held to a numpy group-by at difference 0 and to the "wide"
# policy's 64-bit scatters field by field and bit by bit.
I32 = np.iinfo(np.int32)
_INT_EXPR = CASES["int32_expression_negative"]["values"]


def _numpy_plan(key, tmask, aggs):
    """{key: [{field: value} an aggregate]} over the filtered rows: counts, exact integer sums (Python
    integers), float sums in row order, minima."""
    out = {}
    for k in np.unique(key[tmask]).tolist():
        row = []
        for name, vals, mask in aggs:
            at = (key == k) & (tmask if mask is None else mask)
            fields = {"count": int(at.sum())}
            if name == "sum":
                fields["sum"] = sum(vals[at].tolist()) if np.issubdtype(vals.dtype, np.integer) else float(vals[at].sum())
            elif name == "min":
                fields["min"] = float(vals[at].min()) if at.any() else np.inf
            row.append(fields)
        out[k] = row
    return out


def _assert_plan(key, tmask, aggs, num_slots, keep, monkeypatch, order_spec=None, groups=GROUPS, limb=1):
    """The plan's tables under "chunked32" hold exactly the groups `keep` of the numpy group-by, integers at
    difference 0; PREFIX moved; the "wide" policy's tables are the same bit for bit and PREFIX stays."""
    uniq, parts, limb_moved, prefix_moved = _plan_tables(key, tmask, aggs, num_slots, order_spec, groups)
    assert (limb_moved, prefix_moved) == (limb, 1)
    assert uniq.shape == (num_slots,) and all(t.shape == (num_slots,) for p in parts for t in p.values())
    want = _numpy_plan(key, tmask, aggs)
    live = uniq != planner.SPARSE_EMPTY_KEY
    assert sorted(uniq[live].tolist()) == sorted(keep(want)) and len(set(uniq[live].tolist())) == int(live.sum())
    for slot in np.flatnonzero(live).tolist():
        for (name, vals, _), part, ref in zip(aggs, parts, want[int(uniq[slot])]):
            assert part["count"].dtype == np.int64 and int(part["count"][slot]) == ref["count"]
            if name == "sum" and np.issubdtype(vals.dtype, np.integer):
                assert abs(ref["sum"]) < 1 << 53 and part["sum"].dtype == np.float64
                assert part["sum"][slot] == ref["sum"] and int(part["sum"][slot]) == ref["sum"]
            elif name == "sum":
                assert part["sum"][slot] == pytest.approx(ref["sum"], rel=1e-12, abs=1e-9)
            elif name == "min":
                assert part["min"][slot] == ref["min"]
    # a slot without a group is empty in every table
    for (name, _, _), part in zip(aggs, parts):
        assert not part["count"][~live].any() and (name != "sum" or not part["sum"][~live].any())
    monkeypatch.setattr(ops, "accum_policy", lambda: "wide")
    w_uniq, w_parts, w_limb, w_prefix = _plan_tables(key, tmask, aggs, num_slots, order_spec, groups)
    assert (w_limb, w_prefix) == (0, 0)
    assert np.array_equal(uniq, w_uniq)
    for part, w_part in zip(parts, w_parts):
        assert sorted(part) == sorted(w_part)
        assert all(part[f].dtype == w_part[f].dtype and np.array_equal(part[f], w_part[f]) for f in part)
    return uniq, parts, want


def _every(want):
    return list(want)


def _drawn(seed, n, selectivity, groups=GROUPS):
    rng = np.random.default_rng(seed)
    return rng, rng.integers(0, groups, n).astype(np.int64), rng.random(n) < selectivity


@pytest.mark.parametrize("selectivity", [1e-4, 0.5, 0.0, 1.0], ids=["1e-4", "half", "no_row_passes", "every_row_passes"])
def test_prefix_form_at_the_filters_selectivities(selectivity, chip_path, monkeypatch):
    """SUM of an int32 expression under the filter's own mask (`mask is tmask`: the count is the row range's
    length).  No row passing: every slot empty.  Every row passing: no filtered row sorts last, the last group
    ends on the last row and the key space's top key is among the groups."""
    n = 200_000
    rng, key, tmask = _drawn(50, n, selectivity)
    key[-1] = key[7] = GROUPS - 1
    vals = _INT_EXPR(rng, n, key)
    uniq, parts, want = _assert_plan(key, tmask, [("sum", vals, None)], GROUPS, _every, monkeypatch)
    assert len(want) == {1e-4: len(np.unique(key[tmask])), 0.5: GROUPS, 0.0: 0, 1.0: GROUPS}[selectivity]
    if selectivity == 1.0:
        assert uniq[GROUPS - 1] == GROUPS - 1 and parts[0]["count"].sum() == n
    if selectivity == 0.0:
        assert (uniq == planner.SPARSE_EMPTY_KEY).all()


def test_prefix_form_drops_the_overflow_slot_and_the_lowest_keys_win(chip_path, monkeypatch):
    """More groups than slots, no ORDER BY on an aggregate: the first num_slots groups by packed key keep a slot,
    the last of them ends where the first trimmed group starts, and the trimmed groups' rows are nobody's."""
    rng, key, tmask = _drawn(51, 40_000, 0.9)
    slots = 500
    uniq, parts, want = _assert_plan(key, tmask, [("sum", _INT_EXPR(rng, 40_000, key), None)], slots,
                                     lambda want: sorted(want)[:slots], monkeypatch)
    assert len(want) > slots and (uniq != planner.SPARSE_EMPTY_KEY).all() and (np.diff(uniq) > 0).all()


@pytest.mark.parametrize("value", [I32.max, I32.min, -1], ids=["int32_max", "int32_min", "minus_one"])
def test_one_group_of_2_20_plus_1_rows_at_the_limbs_extremes(value, chip_path, monkeypatch):
    """The wrap probe: ONE group holds every row, each limb at its largest value (int32 max: 255, 255, 255, 127;
    int32 min and -1: the negatives' column at -1 a row, whose prefix is negative all the way).  A 12-bit limb
    would sum to 4,095 x (2^20 + 1) > 2^31 here; prefix_limb_bits keeps (2^bits - 1) x rows under 2^31."""
    n = (1 << 20) + 1
    assert ((1 << segmented._WIDE_LIMB_BITS) - 1) * n > 1 << 31 > ((1 << segmented.prefix_limb_bits(n)) - 1) * n
    key, tmask = np.full(n, 1_234, np.int64), np.ones(n, bool)
    uniq, parts, want = _assert_plan(key, tmask, [("sum", np.full(n, value, np.int32), None)], 16, _every, monkeypatch)
    assert uniq[0] == 1_234 and parts[0]["count"][0] == n and int(parts[0]["sum"][0]) == value * n


@pytest.mark.parametrize("magnitude,groups", [(44, GROUPS), (50, 39_000)], ids=["past_2_44", "past_2_48_few_rows_a_group"])
def test_prefix_form_of_an_int64_sum_input(magnitude, groups, chip_path, monkeypatch):
    """An int64 input past int32 ("int64_sum": signed-magnitude limbs, the sign riding each limb, so a
    prefix runs below zero and back).  With |v| in [2^48, 2^50) a few rows a group keep the sums below 2^53."""
    n = 40_000
    rng, key, tmask = _drawn(52, n, 0.9, groups=groups)
    lowest = 1 << 48 if magnitude == 50 else 0
    vals = rng.integers(lowest, 1 << magnitude, n).astype(np.int64) * (rng.integers(0, 2, n) * 2 - 1)
    _assert_plan(key, tmask, [("sum", vals, None)], groups, _every, monkeypatch, groups=groups)


def test_prefix_form_under_an_aggregates_own_filter(chip_path, monkeypatch):
    """`SUM(v) FILTER (WHERE ..)` beside a plain SUM: the aggregate's mask is not the filter's, so its count
    is a prefix sum of the permuted mask, not the range's length, and a group none of whose rows pass it
    keeps its slot with count 0."""
    n = 40_000
    rng, key, tmask = _drawn(53, n, 0.9)
    vals = _INT_EXPR(rng, n, key)
    own = tmask & (rng.random(n) < 0.3) & (key % 5 != 0)
    uniq, parts, want = _assert_plan(key, tmask, [("sum", vals, own), ("sum", vals, None)], GROUPS, _every, monkeypatch)
    live = uniq != planner.SPARSE_EMPTY_KEY
    assert (parts[0]["count"][live & (uniq % 5 == 0)] == 0).all() and (parts[1]["count"][live] > 0).all()
    assert (parts[0]["count"] < parts[1]["count"])[live].any()


def _rank(field, agg, descending):
    return lambda want: sorted(want, key=lambda k: ((-1 if descending else 1) * want[k][agg][field], k))


@pytest.mark.parametrize("kind", ["sum", "count", "min"])
def test_prefix_form_under_the_order_by_aware_trim(kind, chip_path, monkeypatch):
    """Fewer slots than groups and ORDER BY an aggregate: slots are ranks, not row order, so a slot's end is
    its group's next start (`nxt`) and two neighbouring slots' ranges do not adjoin."""
    n, slots = 40_000, 400
    rng, key, tmask = _drawn(54, n, 0.9)
    vals = _INT_EXPR(rng, n, key)
    aggs, order_spec, keep = {
        "sum": ([("sum", vals, None)], (0, "sum", False), _rank("sum", 0, True)),
        "count": ([("count", vals, None), ("sum", vals, None)], (0, "count", False), _rank("count", 0, True)),
        "min": ([("sum", vals, None), ("min", vals, None)], (1, "min", True), _rank("min", 1, False)),
    }[kind]
    uniq, parts, want = _assert_plan(key, tmask, aggs, slots, lambda want: keep(want)[:slots], monkeypatch, order_spec)
    assert len(want) > slots and uniq.tolist() == keep(want)[:slots]  # the slots ARE the ranks


def test_a_float_sum_and_a_min_keep_their_scatters_beside_an_integer_sum(chip_path, monkeypatch):
    """One plan: SUM(int) (prefix sums), SUM(float) and MIN(int) (their f64 scatters on `slot`, as they were),
    every count the prefix form's.  The kept scatters still write the right slots."""
    n = 40_000
    rng, key, tmask = _drawn(55, n, 0.9)
    vals = _INT_EXPR(rng, n, key)
    floats = np.round(rng.random(n) * 1000.0, 3)
    _assert_plan(key, tmask, [("sum", vals, None), ("sum", floats, None), ("min", vals, None)], 500,
                 lambda want: sorted(want)[:500], monkeypatch)


def test_a_plan_of_floats_alone_still_counts_by_prefix(chip_path, monkeypatch):
    """No integer sum: LIMB stays, PREFIX moves (the counts)."""
    rng, key, tmask = _drawn(56, 20_000, 0.9)
    _assert_plan(key, tmask, [("sum", np.round(rng.random(20_000) * 10.0, 2), None)], GROUPS, _every, monkeypatch, limb=0)


@pytest.mark.parametrize("rows,bits", [(1, 8), (1_500_000, 8), (8_421_504, 8), (8_421_505, 7), (1 << 24, 7),
                                       (1 << 27, 4), ((1 << 31) - 1, 1)])
def test_prefix_limb_bits_keeps_a_whole_segment_of_one_group_inside_int32(rows, bits):
    assert segmented.prefix_limb_bits(rows) == bits
    assert ((1 << bits) - 1) * rows < 1 << 31 and (bits == 8 or ((1 << (bits + 1)) - 1) * rows >= 1 << 31)


def test_prefix_limb_bits_refuses_rows_past_int32():
    with pytest.raises(ValueError):
        segmented.prefix_limb_bits(1 << 31)


@pytest.mark.parametrize("bits", [3, 7])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_narrower_limbs_of_a_longer_segment_give_the_same_tables(dtype, bits, chip_path, monkeypatch):
    """A segment past 2^23 rows takes limbs under 8 bits; the width is steered here (a 2^24-row sort is not a
    tier-1 case) and the tables are the 8-bit ones: 7 and 3 bits do not divide 32, so a limb straddles an
    int64's halves."""
    monkeypatch.setattr(segmented, "prefix_limb_bits", lambda rows: bits)
    n = 20_000
    rng, key, tmask = _drawn(57, n, 0.9)
    vals = rng.integers(I32.min, I32.max, n).astype(dtype) if dtype is np.int32 else rng.integers(-(1 << 50), 1 << 50, n)
    _assert_plan(key, tmask, [("sum", vals, None)], GROUPS, _every, monkeypatch)


@pytest.mark.parametrize("rows", [0, 1])
def test_prefix_form_of_an_empty_and_a_one_row_segment(rows, chip_path, monkeypatch):
    """No row at all (an empty segment: every gather reads the sentinel appended past the last row) and one."""
    key, tmask = np.full(rows, 9, np.int64), np.ones(rows, bool)
    uniq, parts, _ = _assert_plan(key, tmask, [("sum", np.full(rows, -7, np.int32), None)], 4, _every, monkeypatch)
    assert uniq.tolist() == [9] * rows + [planner.SPARSE_EMPTY_KEY] * (4 - rows) and parts[0]["sum"].tolist() == [-7.0] * rows + [0.0] * (4 - rows)
