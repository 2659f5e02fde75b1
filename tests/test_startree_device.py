"""A star-tree level is a table the device holds, planned, cached, grouped,
launched and collected like a segment (PR 37).

SSB's roll-up panels (Q2.1, Q2.2, Q2.3, Q3.1 of `benchmarks/queries/ssb_flat.json`)
over the benchmark's own generator and the two trees of
`benchmarks/configs/ssb_flat_sf10_startree.json`, at a small size on the CPU:
the tree's answer, the scan's (`SET useStarTree=false`) and the plain numpy
reference's are one answer, at limit 0; so are COUNT(*), MIN, MAX and AVG over
a table whose tree stores their pairs.  Through the chip's arithmetic
(`chunked32`, the scan interpreted) a level's int64 sums pass 2^31, and 2^24 a
slot, and stay exact.  The levels of a table's segments hold different numbers
of rows and ride ONE compiled program and one group launch (the true row
count is a bound parameter; the bucket's identity rows add nothing); a
plan-cache hit on a level binds.  What the tree may not serve takes the scan:
a segment with `valid_docs`, an aggregate over an expression (Q4.1), a
segment without a tree beside ones with.
"""
import os
import sys

import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.admission import ResourceBudget
from pinot_tpu.indexes.startree import StarTreeIndex
from pinot_tpu.segment.table_shape import row_bucket as level_bucket
from pinot_tpu.ops import segmented
from pinot_tpu.query import planner
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.residency import ResidencyManager
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
from pinot_tpu.utils.metrics import METRICS

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
ROLLUPS = ["q2_1", "q2_2", "q2_3", "q3_1"]
SEGMENTS, SEGMENT_ROWS, SEED, DRAWS = 4, 12_000, 37, 3


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own files: configuration, generator, query set, renderer, reference."""
    sys.path.insert(0, BENCH)
    try:
        from lib import plugins, templates
        from lib.references import filter_group_sum

        cfg = plugins.load_json("configs", "ssb_flat_sf10_startree")
        gen = plugins.load_module("datagen", cfg["datagen"])
        queries = plugins.load_json("queries", cfg["query_set"])["templates"]
    finally:
        sys.path.remove(BENCH)
    return cfg, gen, queries, templates, filter_group_sum


def _cluster(schema, table_configs, blocks, wide):
    """(broker, server, segments): one server, one segment a block, built
    under its own table config (None: no index)."""
    coord = Coordinator(replication=1)
    server = ServerInstance("server0")
    coord.register_server(server)
    coord.add_table(schema, table_configs[0] or TableConfig(schema.name))
    segments = []
    for i, (block, tcfg) in enumerate(zip(blocks, table_configs)):
        cols = {f.name: block[f.name].astype(wide[f.data_type]) for f in schema.fields}
        segments.append(build_segment(schema, cols, f"seg{i}", table_config=tcfg))
        coord.add_segment(schema.name, segments[-1])
    return Broker(coord), server, segments


def _ssb(bench, with_tree):
    cfg, gen, _, _, _ = bench
    schema = Schema(
        cfg["table"],
        [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]]) for c in cfg["columns"]],
    )
    tcfg = TableConfig(cfg["table"], indexing=IndexingConfig.from_dict(cfg["table_config"]))
    blocks = [gen.make_segment(cfg, SEED, i, SEGMENT_ROWS) for i in range(SEGMENTS)]
    broker, server, segments = _cluster(
        schema, [tcfg if t else None for t in with_tree], blocks, {DataType.INT: np.int32, DataType.LONG: np.int64}
    )
    return broker, server, segments, blocks


@pytest.fixture(scope="module")
def ssb(bench):
    """Four segments of the generator's rows, both trees on each."""
    return _ssb(bench, [True] * SEGMENTS)


def _spans(node, out=None):
    out = {} if out is None else out
    out.setdefault(node["name"].split(":", 1)[0], []).append(node)
    for c in node.get("children", []):
        _spans(c, out)
    return out


def _draws(template, templates):
    rng = np.random.default_rng([SEED, 5])
    return [dict(template["ssb"])] + [templates.draw_params(template, rng) for _ in range(DRAWS)]


# ---------------------------------------------------------------------------
# (1) tree == scan == the plain reference, limit 0
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ROLLUPS)
def test_rollup_tree_equals_scan_equals_the_plain_reference(name, bench, ssb):
    _, _, queries, templates, reference = bench
    broker, _, segments, blocks = ssb
    template = queries[name]
    for params in _draws(template, templates):
        sql = templates.render(template, params)
        tree, scan = broker.query(sql), broker.query("SET useStarTree=false; " + sql)
        assert "startree" in {k for _, k in tree.stats.filter_index_uses}, (name, tree.stats.filter_index_uses)
        assert "startree" not in {k for _, k in scan.stats.filter_index_uses}
        assert tree.stats.num_docs_scanned < scan.stats.num_docs_scanned == SEGMENTS * SEGMENT_ROWS
        assert tree.stats.num_segments_processed == scan.stats.num_segments_processed == SEGMENTS
        assert tree.rows == scan.rows, (name, params)
        want = reference.answer(template["reference"], params, blocks)
        for got in (tree, scan):
            ok, numbers = reference.compare(template["reference"], got.columns, got.rows, want)
            assert ok, (name, params, numbers)
            assert numbers["max_abs_diff"] == numbers["missing"] == numbers["extra"] == numbers["out_of_order"] == 0


def test_level_selection_reads_the_levels_the_configuration_reckons(bench, ssb):
    """Q2.x from level 4 of the first tree, Q3.1 from level 5 of the second;
    `docsScanned` is the levels' true rows, not their buckets."""
    _, _, queries, templates, _ = bench
    broker, _, segments, _ = ssb
    for name, tree, k in [("q2_1", "st0", 4), ("q2_2", "st0", 4), ("q2_3", "st0", 4), ("q3_1", "st1", 5)]:
        got = broker.query(templates.render(queries[name], queries[name]["ssb"]))
        rows = [seg.indexes["startree"][tree].levels[k].num_rows for seg in segments]
        assert got.stats.num_docs_scanned == sum(rows), name
        assert len(set(rows)) > 1 or name == "q3_1"  # the brand level's rows differ a segment at this size


# ---------------------------------------------------------------------------
# (2) COUNT(*), MIN, MAX, AVG over a tree that stores their pairs
# ---------------------------------------------------------------------------
PAIRS_SCHEMA = Schema("pairs", [
    FieldSpec("a", DataType.INT), FieldSpec("b", DataType.INT), FieldSpec("c", DataType.INT),
    FieldSpec("v", DataType.INT, role=FieldRole.METRIC), FieldSpec("w", DataType.LONG, role=FieldRole.METRIC),
])
PAIRS_TREE = {"dimensionsSplitOrder": ["a", "b"],
              "functionColumnPairs": ["COUNT__*", "SUM__v", "MIN__v", "MAX__v", "AVG__w", "SUM__w"]}
PAIRS_ROWS = 20_000


def _pairs_block(i, rows=PAIRS_ROWS):
    rng = np.random.default_rng([SEED, 11, i])
    return {
        "a": rng.integers(0, 5, rows).astype(np.int32), "b": rng.integers(0, 7, rows).astype(np.int32),
        "c": rng.integers(0, 3, rows).astype(np.int32),
        "v": rng.integers(1, 10**7, rows).astype(np.int32),  # SSB's revenue magnitudes
        "w": rng.integers(-(10**9), 10**9, rows),
    }


@pytest.fixture(scope="module")
def pairs():
    tcfg = TableConfig("pairs", indexing=IndexingConfig(star_tree_index_configs=[PAIRS_TREE]))
    blocks = [_pairs_block(i) for i in range(4)]
    broker, server, segments = _cluster(PAIRS_SCHEMA, [tcfg] * 4, blocks, {DataType.INT: np.int32, DataType.LONG: np.int64})
    return broker, server, segments, {k: np.concatenate([b[k] for b in blocks]) for k in blocks[0]}


def _numpy_groups(data, mask, value):
    """{(a, b): value(rows of the group)} over the masked rows, plain numpy."""
    out = {}
    for a in range(5):
        for b in range(7):
            rows = mask & (data["a"] == a) & (data["b"] == b)
            if rows.any():
                out[(a, b)] = value(rows)
    return out


@pytest.mark.parametrize("agg, value", [
    ("COUNT(*)", lambda d, r: int(r.sum())),
    ("MIN(v)", lambda d, r: int(d["v"][r].min())),
    ("MAX(v)", lambda d, r: int(d["v"][r].max())),
    ("AVG(w)", lambda d, r: int(d["w"][r].sum()) / int(r.sum())),
    ("SUM(w)", lambda d, r: int(d["w"][r].sum())),
])
def test_other_aggregates_tree_equals_scan_equals_numpy(agg, value, pairs):
    broker, _, _, data = pairs
    sql = f"SELECT a, b, {agg} FROM pairs WHERE b <= 4 AND a <> 2 GROUP BY a, b LIMIT 100"
    tree, scan = broker.query(sql), broker.query("SET useStarTree=false; " + sql)
    assert "startree" in {k for _, k in tree.stats.filter_index_uses}
    assert tree.stats.num_docs_scanned <= 4 * 35 < scan.stats.num_docs_scanned
    want = _numpy_groups(data, (data["b"] <= 4) & (data["a"] != 2), lambda r: value(data, r))
    for got in (tree, scan):
        assert {(r[0], r[1]): r[2] for r in got.rows} == want, agg


def test_ungrouped_aggregates_read_the_one_row_level(pairs):
    broker, _, _, data = pairs
    got = broker.query("SELECT COUNT(*), SUM(v), MIN(v), MAX(v), AVG(w) FROM pairs")
    assert got.stats.num_docs_scanned == 4  # level 0: one pre-aggregated row a segment
    assert list(got.rows[0]) == [len(data["v"]), int(data["v"].astype(np.int64).sum()), int(data["v"].min()),
                                 int(data["v"].max()), int(data["w"].sum()) / len(data["w"])]


# ---------------------------------------------------------------------------
# (3) the chip's arithmetic: level sums past 2^31, and past 2^24 a slot
# ---------------------------------------------------------------------------
@pytest.fixture()
def chip_arithmetic():
    """accum_policy() = "chunked32" and the scan interpreted, for one test's plans."""
    mp = pytest.MonkeyPatch()
    mp.setenv("PINOT_TPU_SCAN_BACKEND", "interpret")
    ops.scan_backend.cache_clear()
    mp.setattr(ops, "accum_policy", lambda: "chunked32")
    mp.setattr(segmented, "accum_policy", lambda: "chunked32")
    planner.plan_cache_clear()
    yield
    mp.undo()
    ops.scan_backend.cache_clear()
    planner.plan_cache_clear()


@pytest.mark.parametrize("sql, level, past", [
    # level 2: 35 combinations of ~570 rows, a sum ~2.9e9: past 2^31 a level ROW, so the column rides int64 limbs
    ("SELECT a, b, SUM(v), COUNT(*) FROM pairs WHERE b <= 4 GROUP BY a, b LIMIT 100", 2, 1 << 31),
    # level 1: 5 combinations of 4,000 rows, ~2e10 a row
    ("SELECT a, SUM(v), COUNT(*) FROM pairs WHERE a >= 1 GROUP BY a LIMIT 100", 1, 1 << 33),
    # a scalar sum over level 2's rows
    ("SELECT SUM(v), COUNT(*) FROM pairs WHERE b = 3", 2, 1 << 31),
])
def test_level_sums_past_32_bits_are_exact_under_chunked32(sql, level, past, pairs, chip_arithmetic):
    broker, server, segments, data = pairs
    sums = segments[0].indexes["startree"]["st0"].levels[level].fields[("v", "sum")]
    assert sums.dtype == np.int64 and sums.max() > past > 1 << 24
    kernel = METRICS.counter("scan.traced.startree").value
    tree, scan = broker.query(sql), broker.query("SET useStarTree=false; " + sql)
    assert METRICS.counter("scan.traced.startree").value > kernel  # a program was traced over a level
    assert "startree" in {k for _, k in tree.stats.filter_index_uses}
    ctx = parse_query(sql)
    group = [g.op for g in ctx.group_by]
    mask = {2: data["b"] <= 4, 1: data["a"] >= 1}[level] if group else data["b"] == 3
    v = data["v"].astype(np.int64)
    if group:
        keys = np.stack([data[g] for g in group], axis=1)[mask]
        uniq, inv = np.unique(keys, axis=0, return_inverse=True)
        inv = inv.reshape(-1)
        want = sorted(tuple(int(x) for x in k) + (int(v[mask][inv == i].sum()), int((inv == i).sum()))
                      for i, k in enumerate(uniq))
    else:
        want = [(int(v[mask].sum()), int(mask.sum()))]
    for got in (tree, scan):
        assert sorted(tuple(int(x) for x in r) for r in got.rows) == want


def test_rollups_under_chunked32_equal_the_reference(bench, ssb, chip_arithmetic):
    """The cell's four tree-served templates through the chip's arithmetic and
    the interpreted kernel, at SSB's literals: difference 0."""
    _, _, queries, templates, reference = bench
    broker, _, _, blocks = ssb
    for name in ROLLUPS:
        template = queries[name]
        got = broker.query(templates.render(template, template["ssb"]))
        assert "startree" in {k for _, k in got.stats.filter_index_uses}
        ok, numbers = reference.compare(template["reference"], got.columns, got.rows,
                                        reference.answer(template["reference"], template["ssb"], blocks))
        assert ok and numbers["max_abs_diff"] == 0, (name, numbers)


# ---------------------------------------------------------------------------
# (4) one program, one group launch, levels of unequal rows; a hit binds
# ---------------------------------------------------------------------------
def test_levels_of_unequal_rows_ride_one_program_and_one_launch(bench, ssb):
    _, _, queries, templates, _ = bench
    _, server, segments, blocks = ssb
    planner.plan_cache_clear()
    sql = templates.render(queries["q2_1"], queries["q2_1"]["ssb"])
    compiles, groups = METRICS.counter("compile.sse.compiles").value, METRICS.counter("compile.group.programs").value
    results, stats = server.execute(parse_query("SET trace = true; " + sql), [s.name for s in segments])
    spans = _spans(stats.trace)
    rows = [n["attrs"]["levelRows"] for n in spans["launch"]]
    assert rows == [s.indexes["startree"]["st0"].levels[4].num_rows for s in segments] and len(set(rows)) > 1
    assert {level_bucket(r) for r in rows} == {16384}  # one shape: the bucket
    assert [(n["attrs"]["segments"], n["attrs"]["width"]) for n in spans["launch_enqueue"]] == [(SEGMENTS, SEGMENTS)]
    dispatch = spans["dispatch"][0]["attrs"]
    assert {k: v for k, v in dispatch.items() if k != "loopMs"} == {
        "launches": 1, "starSegments": SEGMENTS, "combinedSegments": SEGMENTS,  # the levels' tables fold into one on the chip
        "tableShapedSegments": 0,  # a level is bucketed by its own rule, not by the table's shape
        "docRangeSegments": 0, "indexServedPredicates": 0, "indexScannedPredicates": 0,  # nothing sorted, nothing indexed
        "contractedLookups": 0, "gatheredLookups": 0, "residentLookups": 0,  # no table read at a row's code (ops/code_lookup.py)
        "compactedScatters": 0,  # a level's table is the one-hot kernel's: no row-priced scatter to compact (PR 51)
        "rowBuckets": 1, "rowsPadded": sum(16384 - r for r in rows),  # the one rule of padded rows (PR 50): the levels' too
    }
    assert stats.trace["attrs"]["docsScanned"] == stats.num_docs_scanned == sum(rows)
    assert all("cpuMs" in n["attrs"] and n["attrs"]["kernelBytes"] > 0 for n in spans["launch"])
    assert [(n["attrs"]["star"], n["attrs"]["level"]) for n in spans["launch_plan"]] == [("st0", 4)] * SEGMENTS
    # one program for the four levels (one miss, three hits) and one group program of their width
    assert METRICS.counter("compile.sse.compiles").value == compiles + 1
    assert METRICS.counter("compile.group.programs").value == groups + 1
    assert [n["attrs"]["cache"] for n in spans["launch_plan"]] == ["miss", "hit", "hit", "hit"]
    # the identity rows add nothing: every row of the table is counted once
    count = server.execute(parse_query("SELECT COUNT(*), SUM(lo_revenue) FROM lineorder_flat"), [s.name for s in segments])[0]
    assert sum(int(r.partials[0]["count"]) for r in count) == SEGMENTS * SEGMENT_ROWS
    assert sum(int(r.partials[1]["sum"]) for r in count) == sum(int(b["lo_revenue"].astype(np.int64).sum()) for b in blocks)
    assert server.metrics.counter("server.starTreeSegments").value >= 2 * SEGMENTS
    assert server.metrics.counter("server.starTreeLevelRows").value >= sum(rows)


def test_a_plan_cache_hit_on_a_level_binds(bench, ssb):
    _, _, queries, templates, _ = bench
    _, server, segments, _ = ssb
    template = queries["q3_1"]
    names = [s.name for s in segments]
    server.execute(parse_query(templates.render(template, template["ssb"])), names)
    binds, rebuilds = METRICS.counter("compile.sse.binds").value, METRICS.counter("compile.sse.rebuilds").value
    other = dict(template["ssb"], region=(template["ssb"]["region"] + 1) % 5)
    _, stats = server.execute(parse_query("SET trace = true; " + templates.render(template, other)), names)
    plans = _spans(stats.trace)["launch_plan"]
    assert [(n["attrs"]["cache"], n["attrs"]["bind"], n["attrs"]["star"]) for n in plans] == [("hit", "recipe", "st1")] * SEGMENTS
    assert METRICS.counter("compile.sse.binds").value == binds + SEGMENTS
    assert METRICS.counter("compile.sse.rebuilds").value == rebuilds


# ---------------------------------------------------------------------------
# (5) what the tree may not serve takes the scan
# ---------------------------------------------------------------------------
def test_an_expression_aggregate_takes_the_scan(bench, ssb):
    """Q4.1: SUM(lo_revenue - lo_supplycost) is no function-column pair."""
    _, _, queries, templates, reference = bench
    broker, server, segments, blocks = ssb
    template = queries["q4_1"]
    sql = templates.render(template, template["ssb"])
    assert planner.QueryPlanning(parse_query(sql)).source(segments[0])[0] is segments[0]
    got = broker.query(sql)
    assert "startree" not in {k for _, k in got.stats.filter_index_uses}
    assert got.stats.num_docs_scanned == SEGMENTS * SEGMENT_ROWS
    ok, numbers = reference.compare(template["reference"], got.columns, got.rows,
                                    reference.answer(template["reference"], template["ssb"], blocks))
    assert ok, numbers
    _, stats = server.execute(parse_query("SET trace = true; " + sql), [s.name for s in segments])
    spans = _spans(stats.trace)
    assert spans["dispatch"][0]["attrs"]["starSegments"] == 0
    assert all("cpuMs" in n["attrs"] and "levelRows" not in n["attrs"] for n in spans["launch"])


def test_a_segment_with_valid_docs_takes_the_scan():
    tcfg = TableConfig("pairs", indexing=IndexingConfig(star_tree_index_configs=[PAIRS_TREE]))
    blocks = [_pairs_block(i, rows=3_000) for i in range(2)]
    broker, _, segments = _cluster(PAIRS_SCHEMA, [tcfg] * 2, blocks, {DataType.INT: np.int32, DataType.LONG: np.int64})
    segments[1].valid_docs = np.random.default_rng(3).random(3_000) < 0.5
    sql = "SELECT a, COUNT(*), SUM(v) FROM pairs GROUP BY a LIMIT 10"
    planning = planner.QueryPlanning(parse_query(sql))
    assert planning.source(segments[0])[0].level_rows == 5 and planning.source(segments[1])[0] is segments[1]
    got = broker.query(sql)
    keep = [np.ones(3_000, bool), segments[1].valid_docs]
    want = {a: (sum(int((k & (b["a"] == a)).sum()) for b, k in zip(blocks, keep)),
                sum(int(b["v"][k & (b["a"] == a)].astype(np.int64).sum()) for b, k in zip(blocks, keep))) for a in range(5)}
    assert {r[0]: (r[1], r[2]) for r in got.rows} == want
    assert got.stats.num_docs_scanned == 5 + 3_000


def test_a_mixed_table_merges_in_one_key_space(bench):
    """Two segments with the trees, two without: tree answers and scan
    answers meet at the reduce under the same dictionaries."""
    _, _, queries, templates, reference = bench
    broker, server, segments, blocks = _ssb(bench, [True, False, True, False])
    for name in ROLLUPS:
        template = queries[name]
        got = broker.query("SET trace = true; " + templates.render(template, template["ssb"]))
        assert _spans(got.stats.trace)["dispatch"][0]["attrs"]["starSegments"] == 2
        ok, numbers = reference.compare(template["reference"], got.columns, got.rows,
                                        reference.answer(template["reference"], template["ssb"], blocks))
        assert ok, (name, numbers)


# ---------------------------------------------------------------------------
# (6) the build, the bucket, the staging
# ---------------------------------------------------------------------------
def test_build_on_a_packed_key_gives_the_levels_of_the_matrix_form():
    """StarTreeIndex.build (one mixed-radix int64 key, 1-D uniques) against
    np.unique(matrix, axis=0) a level, which it replaced."""
    block = _pairs_block(0, rows=5_000)
    block["a"] = block["a"] - 2  # a raw dimension with negative values
    seg = build_segment(PAIRS_SCHEMA, block, "s", table_config=TableConfig(
        "pairs", indexing=IndexingConfig(no_dictionary_columns=["a"])))
    order = ["c", "a", "b"]
    tree = StarTreeIndex.build(seg.columns, seg.num_docs, order, ["COUNT__*", "SUM__v", "MIN__v", "MAX__w", "SUM__w"])
    mat = np.stack([np.asarray(seg.column(d).codes if seg.column(d).codes is not None else seg.column(d).values,
                               dtype=np.int64) for d in order], axis=1)
    for k in range(len(order) + 1):
        combos, inv = np.unique(mat[:, :k], axis=0, return_inverse=True)
        inv = inv.reshape(-1)
        level = tree.levels[k]
        assert level.num_rows == len(combos) and list(level.dims) == order[:k]
        for i, d in enumerate(order[:k]):
            assert np.array_equal(level.dims[d], combos[:, i]), (k, d)
        count = np.zeros(len(combos), np.int64)
        np.add.at(count, inv, 1)
        assert np.array_equal(level.fields[("*", "count")], count) and level.fields[("*", "count")].dtype == np.int64
        for col, kind, ufunc, start in [("v", "sum", np.add, 0), ("w", "sum", np.add, 0),
                                        ("v", "min", np.minimum, np.inf), ("w", "max", np.maximum, -np.inf)]:
            want = np.full(len(combos), start, dtype=np.int64 if kind == "sum" else np.float64)
            ufunc.at(want, inv, block[col].astype(want.dtype))
            assert np.array_equal(level.fields[(col, kind)], want), (k, col, kind)


@pytest.mark.parametrize("rows, bucket", [
    (1, 1024), (1024, 1024), (1025, 2048), (4375, 8192), (32768, 32768), (35000, 65536), (65537, 98304),
])
def test_level_bucket(rows, bucket):
    assert level_bucket(rows) == bucket


def test_to_device_stages_the_levels_and_an_empty_list_is_no_column(pairs):
    _, _, segments, _ = pairs
    seg = segments[0]
    assert seg.to_device(columns=[]) == {}  # was "every column" until PR 37
    whole = seg.to_device()
    assert set(whole) == set(seg.columns) | {"*startree"}
    assert set(whole["*startree"]) == {f"seg0/st0.L{k}" for k in range(3)}
    level = seg.indexes["startree"]["st0"].levels[2]
    table = level.table(seg, "st0")
    staged = whole["*startree"][table.name]
    assert set(staged) == {"a", "b", "*:count", "v:sum", "v:min", "v:max", "w:sum"}
    assert staged["v:sum"]["values"].shape == (1024,) and table.level_rows == level.num_rows == 35
    # the bucket's rows past the true count are identities
    assert int(np.asarray(staged["*:count"]["values"])[35:].sum()) == 0 and np.isposinf(np.asarray(staged["v:min"]["values"])[35:]).all()
    assert table.column("a").dictionary is seg.column("a").dictionary  # the parent's key space


def test_levels_are_residency_groups_of_their_own():
    tcfg = TableConfig("pairs", indexing=IndexingConfig(star_tree_index_configs=[PAIRS_TREE]))
    seg = build_segment(PAIRS_SCHEMA, _pairs_block(9, rows=2_000), "fresh", table_config=tcfg)
    res = ResidencyManager(ResourceBudget(1 << 30), name="residency.t37")
    seg.to_device(residency=res)
    tables = seg.star_tables()
    gauge = METRICS.gauge("residency.t37.starTreeBytes")
    assert len(tables) == 3 and gauge.value > 0
    assert gauge.value == sum(t._entry_bytes(c, False) for t in tables for c in t.columns.values())  # codes, values, numeric dictionaries
    held = res.resident_bytes
    assert res.evict(tables[0].device_group(None)) and res.state_of(seg.device_group(None)) == "resident"
    assert 0 < gauge.value < held and res.resident_bytes < held
    for t in tables[1:]:
        res.evict(t.device_group(None))
    assert gauge.value == 0 and res.resident_bytes > 0
    res.evict(seg.device_group(None))
