"""A table whose segments hold UNEQUAL rows (PR 50): pushed a time bucket at
a time, a segment holds what arrived in its bucket.  The rows a kernel is
compiled for come from the table (segment/table_shape.py TableShape.rows):
the resident columns are padded to them, the true count rides the parameters
(planner.ROWS_KEY) and masks every filter, as a star-tree level's always has.

The table here: five segments of five row counts (70,001 / 66,000 / 69,000,
a short tail of 40,000 and one of 700, under a row tile), each a slice of the
calendar sorted by `d`, served through `Broker.query`.  Every plan kind is
held to plain numpy over the same rows, and `numDocsScanned` / `totalDocs` to
the TRUE rows; the sketches to the same rows cut EQUALLY (an unpadded table:
registers and bins merge exactly, so the cut must not show) and to the exact
answer within their error.  Then what the benchmark's `correct` cannot see:
the programs a query shape compiles are the table's row buckets, not its
segments; a group launch holds members of different rows; a table of equal
rows compiles the very program text the seed compiled; and a row mask
dropped on purpose turns COUNT(*) red.
"""
import hashlib

import jax
import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.query import planner
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.table_shape import TableShape, row_bucket
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
from pinot_tpu.utils.metrics import METRICS

COUNTS = [70_001, 66_000, 69_000, 40_000, 700]
DAYS_A_SEGMENT = 30
SCHEMA = Schema(
    "t",
    [
        FieldSpec("d", DataType.INT, role=FieldRole.DIMENSION),
        FieldSpec("k", DataType.INT, role=FieldRole.DIMENSION),
        FieldSpec("c", DataType.INT, role=FieldRole.DIMENSION),
        FieldSpec("b", DataType.INT, role=FieldRole.DIMENSION),
        FieldSpec("v", DataType.INT, role=FieldRole.METRIC),
        FieldSpec("w", DataType.LONG, role=FieldRole.METRIC),
    ],
)
PLAIN = {"sortedColumn": "d", "noDictionaryColumns": ["w"]}
STAR = dict(PLAIN, starTreeIndexConfigs=[{
    "dimensionsSplitOrder": ["k"], "functionColumnPairs": ["SUM__v", "COUNT__*"], "maxLeafRecords": 1,
}])


def _blocks(counts, seed=50):
    rng = np.random.default_rng(seed)
    out = []
    for i, n in enumerate(counts):
        out.append({
            "d": np.sort(rng.integers(i * DAYS_A_SEGMENT, (i + 1) * DAYS_A_SEGMENT, n)).astype(np.int32),
            "k": rng.integers(0, 50, n).astype(np.int32),
            "c": rng.integers(0, 250, n).astype(np.int32),
            "b": rng.integers(0, 1000, n).astype(np.int32),
            "v": rng.integers(1, 1000, n).astype(np.int32),
            "w": rng.integers(-50_000, 10**6, n).astype(np.int64),  # every segment holds both signs: one limb plan
        })
    return out


def _serve(blocks, table_config, name="t"):
    schema = Schema(name, SCHEMA.fields)
    tcfg = TableConfig(name, indexing=IndexingConfig.from_dict(table_config))
    coord, server = Coordinator(replication=1), ServerInstance(f"server_{name}")
    coord.register_server(server)
    coord.add_table(schema, tcfg)
    for i, block in enumerate(blocks):
        coord.add_segment(name, build_segment(schema, block, f"seg{i}", table_config=tcfg))
    return Broker(coord), server


def _whole(blocks):
    return {name: np.concatenate([b[name] for b in blocks]) for name in blocks[0]}


@pytest.fixture(scope="module")
def table():
    """(broker, server, the blocks, the rows as one table) of the unequal table."""
    planner.plan_cache_clear()
    blocks = _blocks(COUNTS)
    broker, server = _serve(blocks, PLAIN)
    yield broker, server, blocks, _whole(blocks)
    planner.plan_cache_clear()


@pytest.fixture(scope="module")
def equal_cut(table):
    """The same rows cut every 35,100 rows: no pad, no mask, but for the tail."""
    _, _, _, whole = table
    size = len(whole["d"]) // 7  # 245,701 = 7 x 35,100 + 1: seven segments of the same rows and a tail of one
    edges = [i * size for i in range(8)] + [len(whole["d"])]
    blocks = [{name: v[a:b] for name, v in whole.items()} for a, b in zip(edges[:-1], edges[1:])]
    return _serve(blocks, PLAIN, name="t_equal")


def _answer(broker, sql, segments=len(COUNTS)):
    got = broker.query(sql)
    t = got.to_dict()
    assert not t["exceptions"] and not t["partialResult"], t
    assert t["numSegmentsQueried"] == segments
    t["traceInfo"] = got.stats.trace
    return t


def _rows(t):
    return [tuple(r) for r in t["resultTable"]["rows"]]


def _spans(node, name):
    if node["name"] == name or node["name"].startswith(name + ":"):
        yield node
    for c in node.get("children", ()):
        yield from _spans(c, name)


def test_the_table_states_one_bound_and_a_far_smaller_segment_keeps_its_own_bucket(table):
    _, server, _, _ = table
    shape = server.shapes["t"]
    rows = {name: shape.rows(seg) for name, seg in server.segments["t"].items()}
    bound = row_bucket(max(COUNTS))
    assert rows == {"seg0": bound, "seg1": bound, "seg2": bound, "seg3": bound, "seg4": row_bucket(700)}
    assert shape.row_buckets() == 2
    assert server.metrics.gauge("server.rowBuckets.t").value == 2


def test_a_table_cut_every_n_rows_pads_its_tail_alone():
    shape = TableShape()

    class Seg:
        columns = {}

        def __init__(self, name, rows):
            self.name, self.num_docs = name, rows

    segs = [Seg(f"s{i}", 50_000) for i in range(3)] + [Seg("tail", 41_234), Seg("empty", 0)]
    for seg in segs:
        shape.add(seg)
    assert [shape.rows(seg) for seg in segs] == [50_000, 50_000, 50_000, 50_000, 0]
    assert shape.rows(Seg("stranger", 7)) == 7  # a segment the table does not hold keeps its own count
    shape.remove("tail")
    assert shape.row_buckets() == 1 and shape.rows(segs[0]) == 50_000


# -- every plan kind against numpy over the same rows --------------------------------------------------------
def _scalar_sum(w):
    m = (w["k"] < 20) & (w["c"] >= 100)
    return "SELECT SUM(v) FROM t WHERE k < 20 AND c >= 100", [(float(w["v"][m].sum()),)]


def _count_star(w):
    return "SELECT COUNT(*) FROM t", [(len(w["d"]),)]


def _count_filtered(w):
    return "SELECT COUNT(*) FROM t WHERE v > 500", [(int((w["v"] > 500).sum()),)]


def _min_max(w):
    m = w["k"] == 7
    return "SELECT MIN(w), MAX(w), MIN(v), MAX(v) FROM t WHERE k = 7", [
        (float(w["w"][m].min()), float(w["w"][m].max()), float(w["v"][m].min()), float(w["v"][m].max()))
    ]


def _min_max_unfiltered(w):
    return "SELECT MIN(w), MAX(w) FROM t", [(float(w["w"].min()), float(w["w"].max()))]


def _dense_group_by(w):
    want = [(k, int((w["k"] == k).sum()), float(w["v"][w["k"] == k].sum())) for k in range(50)]
    return "SELECT k, COUNT(*), SUM(v) FROM t GROUP BY k ORDER BY k LIMIT 100", want


def _wide_scatter(w):
    key = w["c"].astype(np.int64) * 1000 + w["b"]
    sums = np.bincount(key, weights=w["v"], minlength=250_000)
    counts = np.bincount(key, minlength=250_000)
    top = np.lexsort((np.arange(250_000), -sums))[:20]
    return (
        "SELECT c, b, SUM(v), COUNT(*) FROM t GROUP BY c, b ORDER BY SUM(v) DESC, c, b LIMIT 20",
        [(int(g // 1000), int(g % 1000), float(sums[g]), int(counts[g])) for g in top],
    )


def _sparse_sort(w):
    key = (w["c"].astype(np.int64) * 1000 + w["b"]) * 50 + w["k"]
    uniq, inverse = np.unique(key, return_inverse=True)
    sums = np.bincount(inverse, weights=w["v"])
    top = np.lexsort((uniq, -sums))[:20]
    return (
        "SET maxDenseGroups = 1000; SET numGroupsLimit = 1000000; "
        "SELECT c, b, k, SUM(v) FROM t GROUP BY c, b, k ORDER BY SUM(v) DESC, c, b, k LIMIT 20",
        [(int(g // 50000), int(g // 50 % 1000), int(g % 50), float(s)) for g, s in zip(uniq[top], sums[top])],
    )


def _selection(w):
    m = w["k"] == 7
    order = np.lexsort((-w["v"][m], -w["d"][m]))[:5]
    return (
        "SELECT d, v FROM t WHERE k = 7 ORDER BY d DESC, v DESC LIMIT 5",
        [(int(d), int(v)) for d, v in zip(w["d"][m][order], w["v"][m][order])],
    )


def _doc_range(w):
    m = (w["d"] >= 25) & (w["d"] <= 70)
    return "SELECT COUNT(*), SUM(v) FROM t WHERE d BETWEEN 25 AND 70", [(int(m.sum()), float(w["v"][m].sum()))]


def _in_lookup(w):
    m = np.isin(w["b"], [3, 141, 592, 653, 999]) & np.isin(w["k"], [1, 2, 3])
    return (
        "SELECT COUNT(*), SUM(v) FROM t WHERE b IN (3, 141, 592, 653, 999) AND k IN (1, 2, 3)",
        [(int(m.sum()), float(w["v"][m].sum()))],
    )


CASES = {
    "scalar_sum": _scalar_sum, "count_star": _count_star, "count_filtered": _count_filtered, "min_max": _min_max,
    "min_max_unfiltered": _min_max_unfiltered, "dense_group_by": _dense_group_by, "wide_scatter": _wide_scatter,
    "sparse_sort": _sparse_sort, "selection_order_by_limit": _selection, "doc_range_on_the_sorted_column": _doc_range,
    "in_through_the_code_lookup": _in_lookup,
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_plan_kind_over_unequal_rows_equals_numpy_and_counts_the_true_rows(table, case):
    broker, _, blocks, whole = table
    sql, want = CASES[case](whole)
    masked = METRICS.counter("scan.traced.rowmasked").value
    t = _answer(broker, "SET trace = true; " + sql)
    assert _rows(t) == want, (sql, _rows(t)[:3], want[:3])
    assert t["totalDocs"] == sum(COUNTS)
    scanned = [len(b["d"]) for b in blocks]
    if case == "doc_range_on_the_sorted_column":  # the pruner drops the segments whose days lie outside
        scanned = [len(b["d"]) for b in blocks if b["d"].max() >= 25 and b["d"].min() <= 70]
        assert sum(n["attrs"]["docRangeSegments"] for n in _spans(t["traceInfo"], "dispatch")) == len(scanned)
    assert t["numDocsScanned"] == sum(scanned)
    # the programs of this shape mask by the bound row count, and every launched segment was padded
    assert METRICS.counter("scan.traced.rowmasked").value > masked
    (dispatch,) = _spans(t["traceInfo"], "dispatch")
    assert dispatch["attrs"]["rowBuckets"] <= 2
    assert dispatch["attrs"]["rowsPadded"] > 0
    assert sum(n["attrs"]["docs"] for n in _spans(t["traceInfo"], "collect")) == sum(scanned)


@pytest.mark.parametrize("agg, exact, tolerance", [
    ("DISTINCTCOUNTHLL(b)", lambda w: len(np.unique(w["b"])), 0.08),
    ("PERCENTILETDIGEST(v, 50)", lambda w: float(np.percentile(w["v"], 50)), 0.02),
    ("PERCENTILETDIGEST(v, 95)", lambda w: float(np.percentile(w["v"], 95)), 0.02),
])
def test_a_sketch_over_unequal_rows_equals_the_equal_cut_and_nears_the_exact_answer(table, equal_cut, agg, exact, tolerance):
    broker, _, _, whole = table
    equal, equal_server = equal_cut
    shape = equal_server.shapes["t_equal"]
    assert sorted({shape.rows(seg) for seg in equal_server.segments["t_equal"].values()}) == [1024, 35_100]
    sql = f"SELECT k, {agg} FROM {{}} WHERE c < 200 GROUP BY k ORDER BY k LIMIT 100"
    got = _rows(_answer(broker, sql.format("t")))
    assert got == _rows(_answer(equal, sql.format("t_equal"), segments=len(equal_server.segments["t_equal"])))
    assert len(got) == 50
    for k, value in got:
        m = (whole["k"] == k) & (whole["c"] < 200)
        want = exact({name: v[m] for name, v in whole.items()})
        assert abs(float(value) - want) <= tolerance * max(abs(want), 1.0), (agg, k, value, want)


def test_valid_docs_on_one_segment_mask_its_rows_and_the_padding(table):
    _, _, blocks, _ = table
    broker, server = _serve(blocks, PLAIN, name="t_upsert")
    seg = server.segments["t_upsert"]["seg2"]
    valid = np.random.default_rng(5).random(seg.num_docs) < 0.6
    seg.valid_docs = valid
    live = [np.ones(len(b["d"]), bool) for b in blocks]
    live[2] = valid
    keep = np.concatenate(live)
    whole = _whole(blocks)
    for sql, want in [
        ("SELECT COUNT(*), SUM(v) FROM t_upsert", (int(keep.sum()), float(whole["v"][keep].sum()))),
        ("SELECT COUNT(*), MAX(w) FROM t_upsert WHERE k < 10",
         (int((keep & (whole["k"] < 10)).sum()), float(whole["w"][keep & (whole["k"] < 10)].max()))),
    ]:
        t = _answer(broker, sql)
        assert _rows(t) == [want], sql
        assert t["totalDocs"] == sum(COUNTS) and t["numDocsScanned"] == sum(COUNTS)


def test_a_star_tree_level_over_unequal_parents_equals_numpy(table):
    _, _, blocks, whole = table
    broker, server = _serve(blocks, STAR, name="t_star")
    assert all(seg.indexes.get("startree") for seg in server.segments["t_star"].values())
    t = _answer(broker, "SET trace = true; SELECT k, SUM(v), COUNT(*) FROM t_star WHERE k >= 10 GROUP BY k ORDER BY k LIMIT 100")
    assert _rows(t) == [
        (k, float(whole["v"][whole["k"] == k].sum()), int((whole["k"] == k).sum())) for k in range(10, 50)
    ]
    (dispatch,) = _spans(t["traceInfo"], "dispatch")
    assert dispatch["attrs"]["starSegments"] == len(COUNTS)  # every segment's level answered, under the one rule
    assert t["totalDocs"] == sum(COUNTS) and 0 < t["numDocsScanned"] < sum(COUNTS)
    # the same table's scan path, unequal rows under the tree's segments too
    t = _answer(broker, "SELECT COUNT(*), MIN(w) FROM t_star WHERE b < 500")
    assert _rows(t) == [(int((whole["b"] < 500).sum()), float(whole["w"][whole["b"] < 500].min()))]
    assert t["numDocsScanned"] == sum(COUNTS)


# -- what the benchmark's `correct` cannot see ----------------------------------------------------------------
def test_a_query_shape_compiles_a_program_a_row_bucket_and_launches_its_segments_in_groups(table):
    broker, server, _, whole = table
    compiles = METRICS.counter("compile.sse.compiles").value
    t = _answer(broker, "SET trace = true; SELECT SUM(w), COUNT(*) FROM t WHERE c BETWEEN 10 AND 19 AND k > 40")
    m = (whole["c"] >= 10) & (whole["c"] <= 19) & (whole["k"] > 40)
    assert _rows(t) == [(float(whole["w"][m].sum()), int(m.sum()))]
    assert METRICS.counter("compile.sse.compiles").value - compiles <= server.shapes["t"].row_buckets() == 2
    widths = sorted(n["attrs"]["width"] for n in _spans(t["traceInfo"], "launch_enqueue"))
    assert widths == [1, 4], widths  # the four segments of the table's bound ride ONE call, whatever their rows
    # and a second literal set compiles nothing
    compiles = METRICS.counter("compile.sse.compiles").value
    _answer(broker, "SELECT SUM(w), COUNT(*) FROM t WHERE c BETWEEN 100 AND 101 AND k > 3")
    assert METRICS.counter("compile.sse.compiles").value == compiles


# sha256 of the StableHLO text the SEED (6f2b75a) lowers these two shapes to over a segment of 50,000 rows
# (measured on the seed's checkout with this very function, jax as below)
_SEED_TEXT = {
    "jax": "0.9.0",
    "q1": "f34739336cc3a76b7bcea5d7d24bf31ef7e4d293762c163cd908e30369c7b12e",
    "q2": "bba0ffbc27779c936020cfa73db7a27c0fc90ff85bae3cebfa30d72cea54dd9f",
}
_EQUAL_SHAPES = {
    "q1": "SELECT SUM(v) FROM t_same WHERE d BETWEEN 3 AND 40 AND k < 25 AND c BETWEEN 10 AND 30",
    "q2": "SELECT k, SUM(v) FROM t_same WHERE c < 100 AND b BETWEEN 100 AND 107 GROUP BY k ORDER BY k LIMIT 100",
}


def program_text(shape: str):
    """(the text of the program a table of three segments of 50,000 rows compiles for one of _EQUAL_SHAPES, its plan)."""
    planner.plan_cache_clear()
    broker, server = _serve(_blocks([50_000] * 3, seed=7), PLAIN, name="t_same")
    ctx = parse_query(_EQUAL_SHAPES[shape])
    seg = server.segments["t_same"]["seg1"]
    plan = planner.QueryPlanning(ctx, server.shapes.get("t_same")).plan(seg)
    kwargs = {"rows": plan.rows} if hasattr(plan, "rows") else {}
    cols = seg.to_device(
        columns=plan.needed_columns, packed_codes=True, dict_rows=plan.dict_sizes, value_columns=plan.value_columns,
        **kwargs,
    )
    text = plan.fn.lower(cols, plan.params).as_text()
    planner.plan_cache_clear()
    return text, plan


@pytest.mark.parametrize("shape", list(_EQUAL_SHAPES))
def test_a_table_of_equal_rows_compiles_the_program_text_the_seed_compiled(shape):
    masked = METRICS.counter("scan.traced.rowmasked").value
    text, plan = program_text(shape)
    assert METRICS.counter("scan.traced.rowmasked").value == masked  # no mask
    assert plan.rows == 50_000 and all(key != planner.ROWS_KEY for key, _, _ in plan.param_layout)  # no pad
    if jax.__version__ != _SEED_TEXT["jax"]:
        pytest.skip(f"the seed's text was recorded under jax {_SEED_TEXT['jax']}")
    assert hashlib.sha256(text.encode()).hexdigest() == _SEED_TEXT[shape]


def test_a_dropped_row_mask_turns_count_star_red(table, monkeypatch):
    """The benchmark's templates are SUMs: a padded row adds 0 and joins a
    group real rows hold, so its limit-0 comparison cannot see a dropped
    mask.  COUNT(*) can."""
    broker, _, _, whole = table
    import jax.numpy as jnp

    planner.plan_cache_clear()
    monkeypatch.setattr(planner, "_row_mask", lambda rows, counted: jnp.ones((rows,), bool))
    try:
        count = _rows(_answer(broker, "SELECT COUNT(*) FROM t WHERE v >= 0"))[0][0]
    finally:
        planner.plan_cache_clear()  # the unmasked programs leave with the patch
    # the padding was counted, every row of it (a padded row holds code 0, a real value): red
    assert count == 4 * row_bucket(max(COUNTS)) + row_bucket(700) > len(whole["d"])


def test_a_segment_of_more_rows_moves_the_bound_and_the_table_is_staged_again():
    """The bound is the table's state: a table cut every 5,000 rows (its tail
    padded alone), then a segment of 8,000 joins it: every segment is staged
    again at the new bound at its next launch, and every answer stays numpy's."""
    planner.plan_cache_clear()
    blocks = _blocks([5_000, 5_000, 4_500], seed=9)
    broker, server = _serve(blocks, PLAIN, name="t_moving")
    shape, segs = server.shapes["t_moving"], server.segments["t_moving"]

    def held():
        whole = _whole(blocks)
        for sql, want in [
            ("SELECT COUNT(*), SUM(v), MAX(w) FROM t_moving WHERE k < 30",
             (int((whole["k"] < 30).sum()), float(whole["v"][whole["k"] < 30].sum()), float(whole["w"][whole["k"] < 30].max()))),
            ("SELECT COUNT(*) FROM t_moving", (len(whole["d"]),)),
        ]:
            t = _answer(broker, sql, segments=len(blocks))
            assert _rows(t) == [want] and t["totalDocs"] == t["numDocsScanned"] == len(whole["d"])

    held()
    assert [shape.rows(s) for s in segs.values()] == [5_000, 5_000, 5_000]
    cached = {name: {k: v for k, v in seg._device_cache[None].items()} for name, seg in segs.items()}
    assert cached["seg2"]["#rows"] == 5_000 and "#rows" not in cached["seg0"]
    version = shape.version
    blocks.append(_blocks([1, 1, 1, 8_000], seed=9)[3])
    tcfg = TableConfig("t_moving", indexing=IndexingConfig.from_dict(PLAIN))
    broker.coordinator.add_segment(
        "t_moving", build_segment(Schema("t_moving", SCHEMA.fields), blocks[3], "seg3", table_config=tcfg))
    assert shape.version > version and {shape.rows(s) for s in segs.values()} == {row_bucket(8_000)}
    held()
    assert all(seg._device_cache[None]["#rows"] == row_bucket(8_000) for seg in segs.values())
    planner.plan_cache_clear()
