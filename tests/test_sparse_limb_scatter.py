"""A sparse plan's slot tables in 32 bits (PR 42).

Under `accum_policy()` = "chunked32" (the chip) `planner.sparse_grouped_tables`
scatters, after its sort, the keys of a key space under 2^31 as ONE int32
table, every count as an int32 table and every sum of an integer input as
12-bit limbs into int32 tables over 2^19-row chunks
(`ops.limb_scatter_table`, the wide group table's form), and widens them at
table size: what it returns is what it returned (int64 keys with
`SPARSE_EMPTY_KEY`, int64 counts, f64 sums).  Float sums, min / max and the
sketch family keep their 64-bit scatters.  On the CPU the engine takes
"wide", so here the policy is steered as tests/test_sparse_drill_exact.py
steers it, and every case is held to a numpy group-by at difference 0 and to
the "wide" policy's tables (the 64-bit scatters the parent ran on the chip
too; the keys of a key space under 2^31 are one int32 table under either
policy) field by field.
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


def _tables(rows, num_slots, order_spec=None, fn_name="sum", groups=GROUPS):
    """sparse_grouped_tables under jit -> (uniq, partials) as numpy, and how far LIMB moved at trace time."""
    fn = get_agg_function(fn_name)

    def kernel(vals, mask, tmask, key):
        return planner.sparse_grouped_tables(
            [fn], [(vals, mask)], tmask, key, num_slots, order_spec, num_groups=groups, vranges=[rows["vrange"]])

    before = METRICS.counter(LIMB).value
    uniq, (part,) = jax.jit(kernel)(rows["vals"], rows["mask"], rows["tmask"], rows["key"])
    return np.asarray(uniq), {f: np.asarray(t) for f, t in part.items()}, METRICS.counter(LIMB).value - before


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


def test_a_key_space_past_2_31_keeps_its_int64_key_scatter(chip_path):
    """The int32 key table is for the branch that sorts int32 keys; past it the keys are the parent's int64
    `.at[].set`, the counts and sums still limb tables."""
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
