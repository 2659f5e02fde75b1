"""A table ingested in time order under Pinot's documented `sortedColumn` +
`invertedIndexColumns` (PR 47): every segment a slice of the calendar with
dictionaries of its own, sorted by `lo_orderdate`, its filter dimensions
inverted-indexed.

The table is the benchmark's `ssb_flat_sf10_bydate` at toy size: 8 segments
of 5,000 rows from its generator under its `table_config`, served through
`Broker.query`.  SSB's ten templates that a scalar or a dense plan serves
(Q1.1-Q1.3, Q2.1-Q2.3, Q3.1, Q4.1, Q4.2 and, wide but quick at this size,
Q3.2; the wide Q3.3 / Q3.4 and the sparse Q4.3 are left to
tests/test_sparse_drill_exact.py: they add compile time and no date logic)
and `rev_by_day` are held, at 20 drawn literal sets each and at the edges of
the calendar, to the benchmark's plain reference over the same blocks AND to
the same rows shuffled over a table with an empty `table_config`, at limit 0;
the pruner's count is held to a count over the blocks, a plan-cache hit binds
(no rebuild), and no program compiles after a shape's first answer whatever
count of segments survives.  The pruner alone is held to a brute-force "does
any row match" over OR / IN / RANGE / NOT shapes, and a boundary segment
wrongly dropped is seen by the comparison.
"""
import os
import sys

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.query import planner
from pinot_tpu.query.filter import bitmap_serves
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
from pinot_tpu.utils.metrics import METRICS

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
SEGMENTS, SEGMENT_ROWS, SEED, DRAWS = 8, 5_000, 47, 20
TEMPLATES = ["q1_1", "q1_2", "q1_3", "q2_1", "q2_2", "q2_3", "q3_1", "q3_2", "q4_1", "q4_2", "rev_by_day"]


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own files: configuration, generator, templates, renderer, reference."""
    sys.path.insert(0, BENCH)
    try:
        from lib import plugins, templates
        from lib.references import filter_group_sum

        cfg = plugins.load_json("configs", "ssb_flat_sf10_bydate")
        cfg = dict(cfg, rows=SEGMENTS * SEGMENT_ROWS, segment_rows=SEGMENT_ROWS)
        gen = plugins.load_module("datagen", cfg["datagen"])
        queries = dict(plugins.load_json("queries", "ssb_flat")["templates"])
        queries.update(plugins.load_json("queries", cfg["query_set"])["templates"])
    finally:
        sys.path.remove(BENCH)
    return cfg, gen, queries, templates, filter_group_sum


def _serve(cfg, blocks, table_config):
    schema = Schema(
        cfg["table"],
        [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]]) for c in cfg["columns"]],
    )
    tcfg = TableConfig(cfg["table"], indexing=IndexingConfig.from_dict(table_config))
    coord = Coordinator(replication=1)
    server = ServerInstance("server0")
    coord.register_server(server)
    coord.add_table(schema, tcfg)
    for i, block in enumerate(blocks):
        cols = {c["name"]: block[c["name"]].astype(np.int32) for c in cfg["columns"]}
        coord.add_segment(cfg["table"], build_segment(schema, cols, f"seg{i}", table_config=tcfg))
    return Broker(coord), server


@pytest.fixture(scope="module")
def tables(bench):
    """(the time-ordered, indexed table's broker and server; the same rows
    shuffled over a table with an empty table_config; the ordered blocks)."""
    cfg, gen, queries, templates, _ = bench
    planner.plan_cache_clear()
    blocks = [gen.make_segment(cfg, SEED, i, SEGMENT_ROWS) for i in range(SEGMENTS)]
    ordered, server = _serve(cfg, blocks, cfg["table_config"])
    order = np.random.default_rng(SEED).permutation(SEGMENTS * SEGMENT_ROWS)
    whole = {name: np.concatenate([b[name] for b in blocks])[order] for name in blocks[0]}
    shuffled = [{n: v[i * SEGMENT_ROWS : (i + 1) * SEGMENT_ROWS] for n, v in whole.items()} for i in range(SEGMENTS)]
    plain, plain_server = _serve(cfg, shuffled, {"invertedIndexColumns": [], "rangeIndexColumns": []})
    # the first answer of every shape, at the published literals: what a deployment's warm-up sends
    _PRUNED_BEFORE.clear()
    for name in TEMPLATES:
        for broker in (ordered, plain):
            first = broker.query(templates.render(queries[name], dict(queries[name]["ssb"])))
        if first.stats.num_segments_pruned:
            _PRUNED_BEFORE.add(name)
    yield ordered, server, plain, plain_server, blocks
    planner.plan_cache_clear()


def _table(result):
    t = result.to_dict()
    assert not t["exceptions"] and not t["partialResult"], t
    assert t["numSegmentsQueried"] == SEGMENTS  # a pruned segment counts as queried
    return t["resultTable"]["dataSchema"]["columnNames"], t["resultTable"]["rows"]


def _expected_pruned(reference, spec, params, blocks):
    """Segments in which some top-level term of WHERE matches no row."""
    return sum(
        any(not reference._mask(b[t[0]], t[1], [params[p] for p in t[2:]]).any() for t in spec["where"])
        for b in blocks
    )


def _spans(node, name):
    if node["name"] == name or node["name"].startswith(name + ":"):
        yield node
    for c in node.get("children", ()):
        yield from _spans(c, name)


# the shapes whose first PRUNING query has been answered: that query has the server make every program
# a later one can need (executor.warm_widths), so it may compile; at toy size a brand is missing from a
# segment now and then, so a template that never prunes at 1.5M rows a segment may here, at some draw
_PRUNED_BEFORE = set()


def _held(bench, tables, sql, spec, params, warm=True, shape=None):
    """One query on both tables against the reference and each other; on the
    ordered table traced: the pruner's count, no rebuild, no compile."""
    _, _, _, _, reference = bench
    ordered, _, plain, _, blocks = tables
    want = reference.answer(spec, params, blocks)
    rebuilds = METRICS.counter("compile.sse.rebuilds").value
    got = ordered.query("SET trace = true; " + sql)
    columns, rows = _table(got)
    equal, numbers = reference.compare(spec, columns, rows, want)
    assert equal and numbers["limit"] == 0, (sql, numbers)
    plain_columns, plain_rows = _table(plain.query(sql))
    assert plain_columns == columns and plain_rows == rows, sql
    assert got.stats.num_segments_pruned == _expected_pruned(reference, spec, params, blocks), sql
    first_to_prune = bool(got.stats.num_segments_pruned) and shape not in _PRUNED_BEFORE
    if first_to_prune:
        _PRUNED_BEFORE.add(shape)
    if warm and not first_to_prune:
        assert METRICS.counter("compile.sse.rebuilds").value == rebuilds, sql
        firsts = [s for s in _spans(got.stats.trace, "launch_enqueue") if s.get("attrs", {}).get("firstLaunch")]
        assert not firsts and not list(_spans(got.stats.trace, "width_warm")), (sql, firsts)
    return got


def _cases():
    return [(name, k) for name in TEMPLATES for k in range(DRAWS)]


@pytest.mark.parametrize("name,draw", _cases(), ids=[f"{n}-{k}" for n, k in _cases()])
def test_template_equals_the_reference_and_the_unordered_table(name, draw, bench, tables):
    _, _, queries, templates, _ = bench
    rng = np.random.default_rng([SEED, TEMPLATES.index(name), draw])
    params = templates.draw_params(queries[name], rng)
    _held(bench, tables, templates.render(queries[name], params), queries[name]["reference"], params, shape=name)


def _edges(blocks):
    """(id, template or None, sql, reference spec, params): the calendar's edges."""
    first, last = int(blocks[3]["lo_orderdate"][0]), int(blocks[3]["lo_orderdate"][-1])
    starts = next(i for i, b in enumerate(blocks) if b["d_year"][0] != b["d_year"][-1])  # a year starts inside it
    year = int(blocks[starts]["d_year"][-1])
    revenue = ["col", "lo_revenue"]
    day = "SELECT SUM(lo_revenue) FROM lineorder_flat WHERE lo_orderdate = {d}"
    span = "SELECT SUM(lo_revenue) FROM lineorder_flat WHERE lo_orderdate BETWEEN {a} AND {b}"
    by_week = ("SELECT d_weeknuminyear, SUM(lo_revenue) FROM lineorder_flat WHERE d_year = {y} AND d_weeknuminyear = {w} "
               "GROUP BY d_weeknuminyear ORDER BY d_weeknuminyear LIMIT 100")
    one = {"where": [["lo_orderdate", "eq", "d"]], "group_by": [], "sum": revenue, "order_by": []}
    rng = {"where": [["lo_orderdate", "between", "a", "b"]], "group_by": [], "sum": revenue, "order_by": []}
    week = {"where": [["d_year", "eq", "y"], ["d_weeknuminyear", "eq", "w"]], "group_by": ["d_weeknuminyear"],
            "sum": revenue, "order_by": [["d_weeknuminyear", "asc"]]}
    return [
        ("a_segments_first_day", None, day, one, {"d": first}),
        ("a_segments_last_day", None, day, one, {"d": last}),
        ("a_segments_whole_span", None, span, rng, {"a": first, "b": last}),
        ("a_span_over_two_segments", None, span, rng, {"a": int(blocks[2]["lo_orderdate"][-1]), "b": first}),
        ("the_year_that_starts_inside_a_segment", "q1_1", None, None, {"year": year, "dlo": 1, "dhi": 3, "qty": 25}),
        ("its_first_week", "q1_3", None, None, {"week": 1, "year": year, "dlo": 0, "dhi": 2, "qlo": 1, "qhi": 50}),
        ("week_53_of_the_year_before", None, by_week, week, {"y": year - 1, "w": 53}),
        ("both_years_of_that_segment", "q4_2", None, None, {"region": 1, "ya": year - 1, "yb": year, "ma": 0, "mb": 1}),
        ("a_day_no_segment_holds", None, day, one, {"d": 20050101}),
        ("a_year_no_segment_holds", "q1_1", None, None, {"year": 2005, "dlo": 1, "dhi": 3, "qty": 25}),
    ]


@pytest.mark.parametrize("edge", range(10))
def test_the_calendars_edges(edge, bench, tables):
    _, _, queries, templates, _ = bench
    blocks = tables[4]
    _, name, sql, spec, params = _edges(blocks)[edge]
    if name is not None:
        sql, spec = templates.render(queries[name], params), queries[name]["reference"]
    else:
        sql = sql.format(**params)
        _held(bench, tables, sql, spec, params, warm=False, shape=sql)  # an edge's own shape: its first answer may compile
    got = _held(bench, tables, sql, spec, params, shape=name or sql)
    if "no_segment_holds" in _edges(blocks)[edge][0]:
        # every segment pruned: nothing launched, and the answer is the unindexed table's (held above)
        assert got.stats.num_segments_pruned == SEGMENTS and got.stats.num_docs_scanned == 0


def test_a_hit_binds_a_doc_range_and_the_index_decision_is_reported(bench, tables):
    """The spans and counters of PR 47 on one traced Q1.1 and Q4.2."""
    _, _, queries, templates, _ = bench
    ordered, server, plain, _, blocks = tables
    pruned = server.metrics.counter("server.segmentsPruned").value
    binds = METRICS.counter("compile.sse.binds").value
    got = ordered.query("SET trace = true; " + templates.render(queries["q1_1"], dict(queries["q1_1"]["ssb"])))
    (dispatch,) = _spans(got.stats.trace, "dispatch")
    (prune,) = _spans(dispatch, "prune")
    survivors = SEGMENTS - got.stats.num_segments_pruned
    assert prune["attrs"] == {"segments": SEGMENTS, "pruned": SEGMENTS - survivors} and 0 < survivors < SEGMENTS
    attrs = dispatch["attrs"]
    assert attrs["docRangeSegments"] == survivors  # d_year is sorted in every segment: two int32 a segment
    assert attrs["indexServedPredicates"] == 0 and attrs["indexScannedPredicates"] == 2 * survivors
    assert METRICS.counter("compile.sse.binds").value == binds + survivors
    assert server.metrics.counter("server.segmentsPruned").value == pruned + SEGMENTS - survivors
    shipped = [s["attrs"]["paramBytes"] for s in _spans(dispatch, "launch_enqueue")]
    assert shipped and 0 < sum(shipped) < 64 * survivors  # six int32 a member, no row-length operand
    plans = [s["attrs"] for s in _spans(dispatch, "launch_plan")]
    assert len(plans) == survivors and all(p["cache"] == "hit" and p["bind"] == "recipe" for p in plans)
    # Q4.2's OR of two years prunes like an IN
    got = ordered.query("SET trace = true; " + templates.render(queries["q4_2"], dict(queries["q4_2"]["ssb"])))
    assert 0 < got.stats.num_segments_pruned < SEGMENTS
    # the plain table: nothing sorted, nothing indexed, nothing pruned, and the same answers (held above)
    got = plain.query("SET trace = true; " + templates.render(queries["q1_1"], dict(queries["q1_1"]["ssb"])))
    (dispatch,) = _spans(got.stats.trace, "dispatch")
    attrs = dispatch["attrs"]
    assert (attrs["docRangeSegments"], attrs["indexServedPredicates"], attrs["indexScannedPredicates"]) == (0, 0, 0)
    assert got.stats.num_segments_pruned == 0


def test_the_index_is_built_kept_and_not_consulted(bench, tables):
    """`invertedIndexColumns` builds the bitmaps and the planner, from costs,
    scans the packed codes: an answer never depends on the index."""
    cfg = bench[0]
    server = tables[1]
    seg = server.get_segment(cfg["table"], "seg0")
    inverted = cfg["table_config"]["invertedIndexColumns"]
    assert sorted(seg.indexes["inverted"]) == sorted(inverted)
    for name in inverted:
        col, idx = seg.column(name), seg.indexes["inverted"][name]
        assert not bitmap_serves(seg, col)
        codes = np.asarray(col.codes)
        for c in range(col.cardinality):  # the bitmaps are right all the same
            bits = np.unpackbits(idx.bitmaps[c].view(np.uint8), bitorder="little")[: seg.num_docs].astype(bool)
            assert np.array_equal(bits, codes == c), (name, c)
    ctx = parse_query("SELECT COUNT(*) FROM lineorder_flat WHERE lo_quantity < 25 AND s_region IN (1, 2)")
    plan = planner.QueryPlanning(ctx, server.shapes[cfg["table"]]).plan(seg)
    assert plan.index_uses == [] and sorted(plan.index_scans) == [("lo_quantity", "inverted"), ("s_region", "inverted")]
    assert plan.recipe is not None and all(v.nbytes < 256 for v in plan.params.values())
    # the sorted index: dictId -> first doc, kept from the build
    day = seg.column("lo_orderdate")
    assert day.stats.is_sorted and day.sorted_first_docs is not None
    assert np.array_equal(day.first_docs(), np.searchsorted(day.codes, np.arange(day.cardinality + 1)))


def test_the_plain_table_is_planned_as_before(bench, tables):
    """An empty `table_config` over unordered rows: no index use, no index
    scan, no doc range, every hit bound by the recipe's range / table kinds
    alone: the served path of every other cell."""
    cfg, _, queries, templates, _ = bench
    plain_server = tables[3]
    seg = plain_server.get_segment(cfg["table"], "seg0")
    assert not any(seg.indexes.get(kind) for kind in ("inverted", "range"))
    for name in TEMPLATES:
        ctx = parse_query(templates.render(queries[name], dict(queries[name]["ssb"])))
        plan = planner.QueryPlanning(ctx, plain_server.shapes[cfg["table"]]).plan(seg)
        assert plan.cache_hit and plan.bind == "recipe" and not plan.index_uses and not plan.index_scans, name
        assert {b[0] for b in planner._PLAN_CACHE.get(plan.cache_key).recipe.binders} <= {"range", "table"}, name


WHERES = [
    ("d_year = 1993", lambda b: b["d_year"] == 1993),
    ("d_year = 1993 OR d_year = 1994", lambda b: (b["d_year"] == 1993) | (b["d_year"] == 1994)),
    ("(d_year = 1993 OR d_year = 1996) AND lo_quantity < 25", lambda b: ((b["d_year"] == 1993) | (b["d_year"] == 1996)) & (b["lo_quantity"] < 25)),
    ("d_year IN (1992, 1998)", lambda b: (b["d_year"] == 1992) | (b["d_year"] == 1998)),
    ("d_year = 1993 OR d_yearmonthnum = 199512", lambda b: (b["d_year"] == 1993) | (b["d_yearmonthnum"] == 199512)),
    ("d_year = 1993 OR lo_quantity = 7", lambda b: (b["d_year"] == 1993) | (b["lo_quantity"] == 7)),
    ("d_year = 1993 OR (d_year = 1995 AND d_weeknuminyear = 60)", lambda b: (b["d_year"] == 1993) | ((b["d_year"] == 1995) & (b["d_weeknuminyear"] == 60))),
    ("lo_orderdate BETWEEN 19940301 AND 19940820", lambda b: (b["lo_orderdate"] >= 19940301) & (b["lo_orderdate"] <= 19940820)),
    ("lo_orderdate > 19970101", lambda b: b["lo_orderdate"] > 19970101),
    ("lo_orderdate < 19930215 OR lo_orderdate >= 19980701", lambda b: (b["lo_orderdate"] < 19930215) | (b["lo_orderdate"] >= 19980701)),
    ("NOT d_year = 1993", lambda b: b["d_year"] != 1993),
    ("NOT (d_year = 1993 OR d_year = 1994)", lambda b: ~((b["d_year"] == 1993) | (b["d_year"] == 1994))),
    ("d_year != 1992", lambda b: b["d_year"] != 1992),
    ("d_year NOT IN (1992, 1993)", lambda b: ~((b["d_year"] == 1992) | (b["d_year"] == 1993))),
    ("d_weeknuminyear = 53", lambda b: b["d_weeknuminyear"] == 53),
    ("d_year = 1995 AND d_weeknuminyear = 53", lambda b: (b["d_year"] == 1995) & (b["d_weeknuminyear"] == 53)),
    ("d_year = 2005 OR d_year = 2006", lambda b: np.zeros(len(b["d_year"]), bool)),
]


@pytest.mark.parametrize("where,mask", WHERES, ids=[w for w, _ in WHERES])
def test_the_pruner_never_drops_a_segment_that_matches(where, mask, bench, tables):
    cfg = bench[0]
    _, server, _, _, blocks = tables
    ctx = parse_query(f"SELECT COUNT(*) FROM lineorder_flat WHERE {where}")
    planning = planner.QueryPlanning(ctx, server.shapes[cfg["table"]])
    segments = [server.get_segment(cfg["table"], f"seg{i}") for i in range(SEGMENTS)]
    verdicts = [planning.prunes(seg) for seg in segments]
    # the whole list at once, over the columns' bounds and distinct dictionaries as arrays: the same verdicts
    at_once = planner.QueryPlanning(ctx, server.shapes[cfg["table"]]).prune_many(segments, planner.SegmentBounds(segments))
    assert at_once == verdicts, (where, at_once, verdicts)
    matches = [bool(mask(b).any()) for b in blocks]
    assert not any(v and m for v, m in zip(verdicts, matches)), (where, verdicts, matches)
    if "NOT " not in where and "lo_quantity = 7" not in where:
        # a shape the pruner reads through: it drops every segment no VALUE of which matches a conjunct
        assert verdicts == [not m for m in matches] or "AND" in where, (where, verdicts, matches)
        assert any(verdicts)
    # every verdict was resolved once a distinct dictionary, not once a segment
    assert len(planning._verdicts) <= sum(
        len({planner._dictionary_identity(server.get_segment(cfg["table"], f"seg{i}"), c) for i in range(SEGMENTS)})
        for c in planning.predicate_cols
    )


def test_a_boundary_segment_wrongly_dropped_changes_the_compared_answer(bench, tables, monkeypatch):
    """The check can see the fault: a pruner that drops the segment in which
    the year starts loses that year's first rows, and the reference says so."""
    _, _, queries, templates, reference = bench
    ordered, _, _, _, blocks = tables
    boundary = next(i for i, b in enumerate(blocks) if b["d_year"][0] != b["d_year"][-1])
    params = {"year": int(blocks[boundary]["d_year"][-1]), "dlo": 0, "dhi": 10, "qty": 51}
    spec, sql = queries["q1_1"]["reference"], templates.render(queries["q1_1"], params)
    want = reference.answer(spec, params, blocks)
    assert reference.compare(spec, *_table(ordered.query(sql)), want)[0]
    sound = planner.QueryPlanning.prune_many

    def faulty(self, segments, bounds=None):
        return [seg.name == f"seg{boundary}" or pruned for seg, pruned in zip(segments, sound(self, segments, bounds))]

    monkeypatch.setattr(planner.QueryPlanning, "prune_many", faulty)
    equal, numbers = reference.compare(spec, *_table(ordered.query(sql)), want)
    assert not equal and numbers["abs_diff"] > 0


def test_the_pruner_at_once_equals_one_by_one_on_raw_string_and_bloom_columns():
    """The list-at-once pruner (SegmentBounds) against the per-segment one,
    and both against the rows, where a column is raw, a STRING dictionary,
    bloom-filtered, or in a segment without rows."""
    schema = Schema("mix", [
        FieldSpec("r", DataType.INT), FieldSpec("s", DataType.STRING), FieldSpec("d", DataType.INT),
        FieldSpec("v", DataType.INT, role=FieldRole.METRIC),
    ])
    cfg = TableConfig("mix", indexing=IndexingConfig(no_dictionary_columns=["r"], bloom_filter_columns=["r"]))
    rng = np.random.default_rng(7)
    blocks, segments = [], []
    for i, n in enumerate((400, 400, 0, 400, 400)):
        block = {
            "r": (rng.integers(0, 50, n) * 2 + 100 * i).astype(np.int32),  # even values of [100 i, 100 i + 98]
            "s": np.asarray([f"k{i}{j}" for j in rng.integers(0, 5, n)], dtype=object),
            "d": rng.integers(10 * i, 10 * i + 12, n).astype(np.int32),
            "v": rng.integers(0, 9, n).astype(np.int32),
        }
        blocks.append(block)
        segments.append(build_segment(schema, block, f"m{i}", table_config=cfg))
    wheres = [
        ("r = 104", lambda b: b["r"] == 104), ("r = 105", lambda b: b["r"] == 105),  # in the bounds, not in the bloom
        ("r > 250", lambda b: b["r"] > 250), ("r BETWEEN 90 AND 101", lambda b: (b["r"] >= 90) & (b["r"] <= 101)),
        ("r IN (7, 304)", lambda b: (b["r"] == 7) | (b["r"] == 304)), ("r != 104", lambda b: b["r"] != 104),
        ("s = 'k13'", lambda b: b["s"] == "k13"), ("s IN ('k00', 'k44')", lambda b: (b["s"] == "k00") | (b["s"] == "k44")),
        ("s > 'k3'", lambda b: b["s"] > "k3"), ("s LIKE 'k4%'", lambda b: np.asarray([x.startswith("k4") for x in b["s"]], bool)),
        ("d = 11", lambda b: b["d"] == 11), ("d = 11 OR r = 304", lambda b: (b["d"] == 11) | (b["r"] == 304)),
        ("d > 35 AND s = 'k40'", lambda b: (b["d"] > 35) & (b["s"] == "k40")), ("d = 9999999999999", lambda b: b["d"] < 0),
        ("NOT (d = 11)", lambda b: b["d"] != 11), ("v = 3", lambda b: b["v"] == 3),
    ]
    bounds = planner.SegmentBounds(segments)
    pruned_some = 0
    for where, mask in wheres:
        ctx = parse_query(f"SELECT COUNT(*) FROM mix WHERE {where}")
        one_by_one = [planner.QueryPlanning(ctx).prunes(seg) for seg in segments]
        at_once = planner.QueryPlanning(ctx).prune_many(segments, bounds)
        assert at_once == one_by_one, (where, at_once, one_by_one)
        matches = [bool(len(b["r"]) and np.asarray(mask(b)).any()) for b in blocks]
        assert not any(p and m for p, m in zip(at_once, matches)), (where, at_once, matches)
        assert at_once[2]  # the segment without rows
        pruned_some += sum(at_once) > 1
    assert pruned_some >= 10
    assert planner.QueryPlanning(parse_query("SELECT COUNT(*) FROM mix")).prune_many(segments, bounds) == [False] * 5
