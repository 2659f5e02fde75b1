"""A plan's table-by-code lookups (PR 48, ops/code_lookup.py), beside
tests/test_segments_built_apart.py.

Three segments built apart (the dictionaries of `d` differ in size and in
values) behind a broker: a `count_in`-shaped and a `filtered_query`-shaped
query equal numpy over the rows; their lowered programs hold no gather, a
predicate's table longer than the threshold still does; the plan-cache
key and the kernel are one across the segments; and the `dispatch` span says
how many lookups were contracted, how many gathered and how many read a
column staging handed out decoded (PR 49: a 32-bit dictionary past the
threshold read by value), a multi-value column's table predicate among the
gathered.
"""
import numpy as np
import pytest

import jax

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.ops.code_lookup import CONTRACTED, GATHERED, RESIDENT
from pinot_tpu.ops.segmented import _CONTRACT_MAX_TABLE
from pinot_tpu.query import planner
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query

SEGMENTS = 3
ROWS = _CONTRACT_MAX_TABLE + 1_500  # `big` holds a value a row: a dictionary past the threshold
SCHEMA = Schema("t", [
    FieldSpec("d", DataType.INT),
    FieldSpec("big", DataType.INT),
    FieldSpec("tags", DataType.INT, single_value=False),
    FieldSpec("v", DataType.INT, role=FieldRole.METRIC),
])

COUNT_IN = "SELECT COUNT(*) FROM t WHERE d IN (3, 5, 8, 13, 21, 400, 405, 1000)"
FILTERED = ("SELECT SUM(d) FILTER(WHERE d > 40 AND d < 5000), MAX(d) FILTER(WHERE d > 40 AND d < 5000) "
            "FROM t WHERE v > 5 AND v < 900")
OVER_BIG = "SELECT SUM(big) FROM t WHERE big IN (7, 8, 9, 100000) AND v > 5"
SUM_BIG = "SELECT SUM(big), MAX(big + v) FROM t WHERE v > 5"
MV_IN = "SELECT COUNT(*) FROM t WHERE tags IN (1, 3)"


def _block(i):
    rng = np.random.default_rng([48, i])
    # EXP-shaped like the benchmark's: every segment draws a dictionary of its own
    d = np.minimum(rng.exponential(150 + 60 * i, ROWS), 1800).astype(np.int32) * (1 + i % 2)
    return {
        "d": d,
        "big": rng.permutation(ROWS).astype(np.int32) + i,
        "tags": [list(rng.integers(0, 6, size=int(k))) for k in rng.integers(0, 3, ROWS)],
        "v": rng.integers(0, 1000, ROWS).astype(np.int32),
    }


@pytest.fixture(scope="module")
def table():
    planner.plan_cache_clear()
    coord, server = Coordinator(replication=1), ServerInstance("server0")
    coord.register_server(server)
    coord.add_table(SCHEMA)
    blocks = [_block(i) for i in range(SEGMENTS)]
    for i, block in enumerate(blocks):
        coord.add_segment("t", build_segment(SCHEMA, block, f"seg{i}"))
    yield Broker(coord), server, blocks
    planner.plan_cache_clear()


def _spans(node, name):
    if node["name"] == name:
        yield node
    for c in node.get("children", ()):
        yield from _spans(c, name)


def _rows(result):
    t = result.to_dict()
    assert not t["exceptions"] and not t["partialResult"] and t["numSegmentsQueried"] == SEGMENTS, t
    return t["resultTable"]["rows"]


def _plans(server, sql):
    planning = planner.QueryPlanning(parse_query(sql), server.shapes["t"])
    return [(seg, planning.plan(seg)) for seg in server.segments["t"].values()]


def _lowered(server, sql):
    seg, plan = _plans(server, sql)[0]
    cols = seg.to_device(
        columns=plan.needed_columns, packed_codes=True, dict_rows=plan.dict_sizes, value_columns=plan.value_columns
    )
    return plan.fn.lower(cols, {k: jax.device_put(v) for k, v in plan.params.items()}).as_text()


def test_the_dictionaries_differ(table):
    _, server, _ = table
    segs = list(server.segments["t"].values())
    assert len({s.column("d").cardinality for s in segs}) == SEGMENTS
    assert all(s.column("big").cardinality == ROWS > _CONTRACT_MAX_TABLE for s in segs)
    assert max(s.column("d").cardinality for s in segs) < _CONTRACT_MAX_TABLE


def test_count_in_equals_numpy(table):
    broker, _, blocks = table
    want = sum(int(np.isin(b["d"], [3, 5, 8, 13, 21, 400, 405, 1000]).sum()) for b in blocks)
    assert want > 0 and _rows(broker.query(COUNT_IN)) == [[want]]


def test_filtered_aggregates_equal_numpy(table):
    broker, _, blocks = table
    picked = [b["d"][(b["v"] > 5) & (b["v"] < 900) & (b["d"] > 40) & (b["d"] < 5000)].astype(np.int64) for b in blocks]
    ((total, largest),) = _rows(broker.query(FILTERED))
    assert total == sum(int(p.sum()) for p in picked) and largest == max(int(p.max()) for p in picked)


def test_a_long_dictionary_still_answers_right(table):
    broker, _, blocks = table
    want = sum(int(b["big"][np.isin(b["big"], [7, 8, 9, 100000]) & (b["v"] > 5)].astype(np.int64).sum()) for b in blocks)
    assert want > 0 and _rows(broker.query(OVER_BIG)) == [[want]]


@pytest.mark.parametrize("sql,gathers", [
    (COUNT_IN, 0), (FILTERED, 0),
    (OVER_BIG, 1),  # the IN's bool table past the threshold; SUM(big) reads the decoded column
    (SUM_BIG, 0), (MV_IN, 1),
], ids=["count_in", "filtered", "over_big", "sum_big", "multi_value"])
def test_which_lowered_programs_hold_a_gather(table, sql, gathers):
    _, server, _ = table
    assert _lowered(server, sql).count('"stablehlo.gather"(') == gathers


@pytest.mark.parametrize("sql,want", [
    (OVER_BIG, {"big"}),  # the predicate reads its codes from the same entry
    (SUM_BIG, {"big"}),
    (FILTERED, set()),  # d's dictionary is in the contraction's range
    ("SELECT COUNT(big) FROM t WHERE v > 5", set()),  # COUNT reads the null mask
    ("SELECT SUM(v) FROM t WHERE big > 5", set()),  # read by code alone
], ids=["over_big", "sum_big", "filtered", "count", "predicate_only"])
def test_the_plan_says_which_columns_it_reads_decoded(table, sql, want):
    _, server, _ = table
    planning = planner.QueryPlanning(parse_query(sql), server.shapes["t"])
    for seg, plan in _plans(server, sql):
        assert plan.value_columns == want == planning.value_columns(seg)
        cols = seg.to_device(columns=plan.needed_columns, packed_codes=True, value_columns=plan.value_columns)
        for name in want:
            assert {"codes", "dict", "values"} <= set(cols[name])


def test_the_look_ahead_asks_no_segment_where_no_dictionary_it_reads_is_long(table, monkeypatch):
    """QueryPlanning.value_columns decides from the table's shape, once a
    query: where no dictionary the query reads by value is compiled past the
    contraction's range, no segment's memo is touched (the hit path of every
    cell but the sketch's)."""
    _, server, _ = table
    shape = server.shapes["t"]
    assert 0 < shape.longest(("d",)) <= _CONTRACT_MAX_TABLE < shape.longest(("big",)) == shape.longest(("d", "big"))
    assert shape.longest(("v", "no_such_column")) == 0  # a raw column has no lane
    segs = list(server.segments["t"].values())
    for sql, asks in ((FILTERED, False), (SUM_BIG, True), ("SELECT COUNT(*) FROM t WHERE big > 5", False)):
        planning = planner.QueryPlanning(parse_query(sql), shape)
        asked = []
        real = planning._key
        monkeypatch.setattr(planning, "_key", lambda *a, real=real, asked=asked: asked.append(1) or real(*a))
        assert all(bool(planning.value_columns(seg)) == asks for seg in segs)
        assert bool(asked) == asks, sql


@pytest.mark.parametrize("sql", [COUNT_IN, FILTERED], ids=["count_in", "filtered"])
def test_one_program_a_shape_across_the_segments(table, sql):
    _, server, _ = table
    plans = [plan for _, plan in _plans(server, sql)]
    assert len({plan.cache_key for plan in plans}) == 1
    assert all(plan.fn is plans[0].fn and plan.lookups is plans[0].lookups for plan in plans)
    assert any(plan.table_shaped for plan in plans)  # compiled for the table's bound, not for a dictionary of their own


@pytest.mark.parametrize("sql,contracted,gathered,resident", [
    (COUNT_IN, 1, 0, 0),  # the IN's bool table
    (FILTERED, 2, 0, 0),  # SUM's and MAX's read of d's dictionary (one column: XLA merges them)
    (OVER_BIG, 0, 1, 1),  # a bool table past the threshold, and a 32-bit dictionary past it read decoded
    (SUM_BIG, 0, 0, 2),  # two reads of the decoded column
    (MV_IN, 0, 1, 0),  # [rows, k] codes keep the gather
    ("SELECT SUM(v) FROM t WHERE v > 5", 0, 0, 0),  # raw columns: no lookup
], ids=["count_in", "filtered", "over_big", "sum_big", "multi_value", "raw"])
def test_the_dispatch_span_counts_the_lookups_by_form(table, sql, contracted, gathered, resident):
    broker, server, _ = table
    counters = [server.metrics.counter(f"server.{form}Lookups") for form in ("contracted", "resident")]
    for again in range(2):  # the first answer traces the program, the second finds it made
        before = [c.value for c in counters]
        (dispatch,) = _spans(broker.query("SET trace = true; " + sql).stats.trace, "dispatch")
        attrs = dispatch["attrs"]
        assert (attrs["contractedLookups"], attrs["gatheredLookups"], attrs["residentLookups"]) == (
            SEGMENTS * contracted, SEGMENTS * gathered, SEGMENTS * resident), again
        assert [c.value - b for c, b in zip(counters, before)] == [SEGMENTS * contracted, SEGMENTS * resident]
    assert _plans(server, sql)[0][1].lookups == {CONTRACTED: contracted, GATHERED: gathered, RESIDENT: resident}


def test_sums_over_the_decoded_column_equal_numpy(table):
    broker, _, blocks = table
    picked = [b["v"] > 5 for b in blocks]
    want = [sum(int(b["big"][p].astype(np.int64).sum()) for b, p in zip(blocks, picked)),
            max(int((b["big"][p].astype(np.int64) + b["v"][p]).max()) for b, p in zip(blocks, picked))]
    assert _rows(broker.query(SUM_BIG)) == [want]
