"""A table whose segments were built apart: every segment has dictionaries
of its own, and a query shape still compiles ONE kernel and rides the group
ladder (PR 41).

The table is the benchmark's `pinot_perf_ssqe_exp001_50seg` at toy size: 9
segments of ~20,000 rows from its generator (EXP(0.001) values, so the nine
dictionaries of INT_COL have nine sizes), served with the chip's arithmetic
(the kernel interpreted, `chunked32` accumulation: steered as
tests/test_ssb_templates_chip_path.py steers them) behind the front door.
The cell's four templates are held to the benchmark's plain reference
(`lib/references/filter_group_aggs.py`) at limit 0, at the file's literals and
at seeded draws; the compiles and launches are counted; a table whose segments
agree is planned as it always was; a segment added with a dictionary past the
bound, and one dropped, still answer right; and the spans and counters say
what happened.
"""
import json
import os
import sys
import urllib.request

import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.rest import QueryServer
from pinot_tpu.ops import segmented
from pinot_tpu.query import planner
from pinot_tpu.query.shape import column_info_from
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.table_shape import TableShape, _rounded_up
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
from pinot_tpu.utils.metrics import METRICS

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
TEMPLATES = ["filtered_query", "count_in", "group_low_high", "sum_query"]
SEGMENTS, SEGMENT_ROWS, SEED, DRAWS = 9, 20_000, 41, 20
WIDE = {"INT": np.int32, "LONG": np.int64}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own files: configuration, generator, query set, renderer, reference."""
    sys.path.insert(0, BENCH)
    try:
        from lib import plugins, templates
        from lib.references import filter_group_aggs

        cfg = plugins.load_json("configs", "pinot_perf_ssqe_exp001_50seg")
        gen = plugins.load_module("datagen", cfg["datagen"])
        queries = plugins.load_json("queries", cfg["query_set"])["templates"]
    finally:
        sys.path.remove(BENCH)
    return cfg, gen, queries, templates, filter_group_aggs


@pytest.fixture(scope="module")
def chip_path():
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


def _schema(cfg):
    schema = Schema(
        cfg["table"],
        [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]]) for c in cfg["columns"]],
    )
    return schema, TableConfig(cfg["table"], indexing=IndexingConfig.from_dict(cfg["table_config"]))


def _segment(cfg, gen, index, rows=SEGMENT_ROWS):
    schema, tcfg = _schema(cfg)
    block = gen.make_segment(cfg, SEED, index, rows)
    cols = {c["name"]: block[c["name"]].astype(WIDE[c["type"]]) for c in cfg["columns"]}
    return block, build_segment(schema, cols, f"seg{index}", table_config=tcfg)


@pytest.fixture(scope="module")
def table(bench, chip_path):
    """(coordinator, server, front door, the blocks by segment name)."""
    cfg, gen, _, _, _ = bench
    schema, tcfg = _schema(cfg)
    coord = Coordinator(replication=1)
    server = ServerInstance("server0")
    coord.register_server(server)
    coord.add_table(schema, tcfg)
    blocks = {}
    for i in range(SEGMENTS):
        block, seg = _segment(cfg, gen, i)
        blocks[seg.name] = block
        coord.add_segment(cfg["table"], seg)
    front = QueryServer(Broker(coord)).start()
    yield coord, server, front, blocks
    front.stop()


def _ask(front, sql, traced=False):
    body = json.dumps({"sql": ("SET trace = true; " if traced else "") + sql}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{front.port}/query/sql", data=body, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        answer = json.loads(r.read().decode("utf-8"))
    assert not answer.get("exceptions") and not answer.get("partialResult"), answer
    return answer


def _held(bench, answer, name, params, blocks):
    """The served answer against the plain reference over `blocks`, at limit 0."""
    _, _, queries, _, reference = bench
    spec = queries[name]["reference"]
    table = answer["resultTable"]
    want = reference.answer(spec, params, list(blocks))
    equal, numbers = reference.compare(spec, table["dataSchema"]["columnNames"], table["rows"], want)
    assert equal and numbers["limit"] == 0, (name, params, numbers)
    return want


def _cases():
    """(template, draw): draw 0 is the file's literals; a template without a
    literal has that one case, every request of it being one string."""
    out = []
    for name in TEMPLATES:
        out.extend((name, k) for k in range(1 + (DRAWS if name in ("filtered_query", "count_in") else 0)))
    return out


def test_the_dictionaries_differ_and_ride_one_lane(table):
    _, server, _, _ = table
    segs = list(server.segments["MyTable"].values())
    for column in ("INT_COL", "NO_INDEX_INT_COL", "NO_INDEX_STRING_COL"):
        sizes = [s.column(column).cardinality for s in segs]
        assert len(set(sizes)) > SEGMENTS // 2, (column, sizes)  # built apart: hardly two dictionaries of one size
        assert {s.column(column).code_bits for s in segs} == {16}
        bound = server.shapes["MyTable"].bound(column, segs[0].column(column))
        assert bound == _rounded_up(max(sizes)) and max(sizes) <= bound < 1.13 * max(sizes)
    low = segs[0].column("LOW_CARDINALITY_STRING_COL")
    assert server.shapes["MyTable"].bound("LOW_CARDINALITY_STRING_COL", low) == low.cardinality == 10  # they agree: no bound


@pytest.mark.parametrize("name,draw", _cases())
def test_template_equals_the_plain_reference(name, draw, bench, table):
    _, _, queries, templates, _ = bench
    _, _, front, blocks = table
    template = queries[name]
    params = dict(template["ssb"])
    if draw:
        rng = np.random.default_rng([SEED, TEMPLATES.index(name)])
        for _ in range(draw):
            params = templates.draw_params(template, rng)
    answer = _ask(front, templates.render(template, params))
    assert answer["numSegmentsQueried"] == SEGMENTS
    want = _held(bench, answer, name, params, blocks.values())
    if name == "group_low_high":
        assert len(want["rows"]) == 10 and want["groups"] > 10_000  # Pinot's default LIMIT over a wide key space
    elif name != "sum_query":
        assert all(v for v in want["aggs"]), "the literals select no row: the case shows nothing"


def test_only_the_whole_merged_table_can_tell_a_merge_by_value_from_one_by_code(bench, table):
    """The cell's `group_low_high` keeps Pinot's default 10 rows, keys (0,0)..(0,9), and down there every
    segment's dictionary code IS its value: the benchmark's two controls on the `merge` guarantee
    (`lib/controls_aggs.py`: tables met by code; one segment's dictionary tail lost) pass the check on it.
    The query set's probe `group_low_high_whole` brings every group of every table back: the program's
    answer equals the reference there too, and both controls are called not correct."""
    cfg, _, queries, templates, reference = bench
    from lib import controls_aggs  # the benchmark's package is imported already (the `bench` fixture)

    _, _, front, by_name = table
    blocks, controls = list(by_name.values()), controls_aggs.controls_for(cfg)
    passes = {}
    for name in ("group_low_high", "group_low_high_whole"):
        spec, params = queries[name]["reference"], dict(queries[name]["ssb"])
        answer = _ask(front, templates.render(queries[name], params))
        want = _held(bench, answer, name, params, blocks)
        served = answer["resultTable"]
        for control, fn in controls.items():
            other = fn(reference, spec, params, blocks)
            passes[name, control] = reference.compare(spec, served["dataSchema"]["columnNames"], served["rows"], other)[0]
    assert len(served["rows"]) == want["groups"] > SEGMENT_ROWS  # the whole table came back: more groups than a segment has rows
    assert passes == {("group_low_high", "merged_by_code"): True, ("group_low_high", "tail_dropped"): True,
                      ("group_low_high_whole", "merged_by_code"): False, ("group_low_high_whole", "tail_dropped"): False}
    assert controls["merged_by_code"](reference, queries["sum_query"]["reference"], {}, blocks) is None  # a raw column: nothing to break


@pytest.mark.parametrize("name", TEMPLATES)
def test_one_kernel_a_template_and_the_ladder(name, bench, table):
    """Whatever each segment's dictionary holds: ONE compile a query shape
    (and one group program: 9 segments = 8 + 1), then none for other
    literals, and 2 jitted calls a query."""
    _, _, queries, templates, _ = bench
    _, server, front, _ = table
    template = queries[name]
    planner.plan_cache_clear()
    compiles, programs = METRICS.counter("compile.sse.compiles"), METRICS.counter("compile.group.programs")
    c0, p0 = compiles.value, programs.value
    _ask(front, templates.render(template, template["ssb"]))
    assert (compiles.value - c0, programs.value - p0) == (1, 1)
    launches = server.metrics.counter("server.launches")
    l0 = launches.value
    rng = np.random.default_rng(7)
    for _ in range(3):
        _ask(front, templates.render(template, templates.draw_params(template, rng)))
    assert (compiles.value - c0, programs.value - p0) == (1, 1)  # other literals, FILTER's too: no compile
    assert launches.value - l0 == 3 * 2


def test_the_spans_and_counters_say_what_happened(bench, table):
    _, _, queries, templates, _ = bench
    _, server, front, _ = table

    def spans(tree, name, out=None):
        out = [] if out is None else out
        if tree["name"] == name or tree["name"].startswith(name + ":"):
            out.append(tree)
        for c in tree.get("children", ()):
            spans(c, name, out)
        return out

    shaped = server.metrics.counter("server.tableShapedSegments")
    by_value = METRICS.counter("broker.tablesMergedByValue")
    s0, v0 = shaped.value, by_value.value
    group = _ask(front, templates.render(queries["group_low_high"], {}), traced=True)["trace"]
    plans = spans(group, "launch_plan")
    # NO_INDEX_STRING_COL's bound is the largest dictionary rounded up: every segment's own is smaller
    assert [n["attrs"]["shape"] for n in plans] == ["table"] * SEGMENTS
    (dispatch,) = spans(group, "dispatch")
    assert dispatch["attrs"]["tableShapedSegments"] == SEGMENTS and dispatch["attrs"]["combinedSegments"] == 0
    (reduce,) = spans(group, "reduce")
    assert reduce["attrs"]["tablesByValue"] == SEGMENTS  # nine key spaces: merged by value
    assert sum(n["attrs"]["tables"] for n in spans(group, "table_decode")) == SEGMENTS
    assert (shaped.value - s0, by_value.value - v0) == (SEGMENTS, SEGMENTS)

    raw = _ask(front, templates.render(queries["sum_query"], {}), traced=True)["trace"]
    assert [n["attrs"]["shape"] for n in spans(raw, "launch_plan")] == ["segment"] * SEGMENTS  # a raw column: no dictionary
    assert spans(raw, "dispatch")[0]["attrs"]["tableShapedSegments"] == 0
    assert "tablesByValue" not in spans(raw, "reduce")[0]["attrs"]  # a scalar aggregation merges no table
    assert (shaped.value - s0, by_value.value - v0) == (SEGMENTS, SEGMENTS)


def test_a_segment_with_a_larger_dictionary_joins_and_one_leaves(bench, table):
    """The bound is the table's and moves with it: a segment whose
    dictionaries pass it is added (every kernel is compiled anew, once, and
    the other segments' device dictionaries are handed out at the new size),
    then the largest is dropped (back under the old bound); every answer
    equals the reference over the rows then served."""
    cfg, gen, queries, templates, _ = bench
    coord, server, front, blocks = table
    shape = server.shapes["MyTable"]
    some = next(iter(server.segments["MyTable"].values()))
    before = shape.bound("INT_COL", some.column("INT_COL"))
    block, big = _segment(cfg, gen, SEGMENTS, rows=4 * SEGMENT_ROWS)
    assert big.column("INT_COL").cardinality > before
    coord.add_segment("MyTable", big)
    after = shape.bound("INT_COL", some.column("INT_COL"))
    assert after == _rounded_up(big.column("INT_COL").cardinality) > before
    served = dict(blocks, **{big.name: block})
    try:
        for name in TEMPLATES:
            answer = _ask(front, templates.render(queries[name], queries[name]["ssb"]))
            assert answer["numSegmentsQueried"] == SEGMENTS + 1
            _held(bench, answer, name, queries[name]["ssb"], served.values())
        cols = some.to_device(device=server.device, columns=["INT_COL"], packed_codes=True)
        assert cols["INT_COL"]["dict"].shape == (after,)  # the cached array is the one the last launch took
    finally:
        meta = coord.tables["MyTable"]
        for s in meta.ideal.pop(big.name):  # what Coordinator.run_retention does to a segment past its window
            coord.servers[s].drop_segment("MyTable", big.name)
        meta.segment_meta.pop(big.name, None)
        coord._bump_version()
    assert shape.bound("INT_COL", some.column("INT_COL")) == before
    for name in TEMPLATES:
        answer = _ask(front, templates.render(queries[name], queries[name]["ssb"]))
        assert answer["numSegmentsQueried"] == SEGMENTS
        _held(bench, answer, name, queries[name]["ssb"], blocks.values())


def test_a_table_whose_segments_agree_is_planned_as_before():
    """Where every segment holds the same dictionaries the bound IS the
    cardinality: the plan-cache key, the kernel and the group program are
    what a planner without a table's shape makes."""
    schema = Schema("agree", [FieldSpec("d", DataType.INT), FieldSpec("v", DataType.INT, role=FieldRole.METRIC)])
    server = ServerInstance("server0")
    rng = np.random.default_rng(3)
    segs = []
    for i in range(3):
        d = np.concatenate([np.arange(700), rng.integers(0, 700, 2300)]).astype(np.int32)
        seg = build_segment(schema, {"d": d, "v": rng.integers(0, 1000, 3000).astype(np.int32)}, f"a{i}")
        server.add_segment("agree", seg)
        segs.append(seg)
    ctx = parse_query("SELECT d, SUM(v) FROM agree WHERE d IN (3, 5, 8) GROUP BY d LIMIT 1000")
    planner.plan_cache_clear()
    try:
        alone, shaped = planner.QueryPlanning(ctx), planner.QueryPlanning(ctx, server.shapes["agree"])
        assert len({alone.key(s) for s in segs} | {shaped.key(s) for s in segs}) == 1
        plans = [alone.plan(segs[0])] + [shaped.plan(s) for s in segs]
        assert all(p.fn is plans[0].fn and not p.table_shaped and p.dict_sizes == {"d": 700} for p in plans)
        assert all(p.num_groups == 700 and p.param_layout == plans[0].param_layout for p in plans)
        assert planner.grouped_plan(plans[1], 2).fn is planner.grouped_plan(plans[0], 2).fn
        # an empty table_config over unordered rows: no index consulted, none scanned past, no doc range,
        # and the recipe's kinds are the code scan's (PR 47 added `docrange` and `index_scans` beside them)
        assert all(not p.index_uses and not p.index_scans for p in plans)
        assert [b[0] for b in planner._PLAN_CACHE.get(plans[0].cache_key).recipe.binders] == ["table"]
        assert not any(server.get_segment("agree", f"a{i}").column("d").stats.is_sorted for i in range(3))
    finally:
        planner.plan_cache_clear()


def test_the_shape_follows_the_segments_added_and_dropped():
    schema = Schema("t", [FieldSpec("d", DataType.INT), FieldSpec("m", DataType.INT, role=FieldRole.METRIC)])

    def seg(name, n):
        return build_segment(schema, {"d": np.arange(n, dtype=np.int32), "m": np.arange(n, dtype=np.int32)}, name)

    a, b, c = seg("a", 300), seg("b", 300), seg("c", 330)
    shape = TableShape()
    shape.add(a)
    shape.add(b)
    v = shape.version
    assert shape.bound("d", a.column("d")) == 300  # they agree: the cardinality itself
    shape.add(c)
    assert shape.bound("d", a.column("d")) == shape.bound("d", c.column("d")) == _rounded_up(330) == 352
    assert shape.version > v
    v = shape.version
    shape.add(seg("e", 340))  # under the rounded bound: nobody's kernel changes
    assert shape.version == v and shape.bound("d", a.column("d")) == 352
    shape.remove("c")
    assert shape.bound("d", a.column("d")) == 352  # 340 is still there
    shape.add(seg("e", 300))  # replaced in place by one of the others' size: the old one leaves, they agree again
    assert shape.bound("d", a.column("d")) == 300 and shape.version > v
    small = seg("s", 200)  # another lane (8 bits): a kernel of its own anyway, and no bound from the 16-bit ones
    assert small.column("d").code_bits != a.column("d").code_bits
    assert shape.bound("d", small.column("d")) == 200
    assert shape.bound("m", a.column("m")) == a.column("m").cardinality  # a raw column: the table has no say
    assert [_rounded_up(n) for n in (1, 7, 16, 17, 673, 7940, 8192, 8193)] == [1, 7, 16, 18, 704, 8192, 8192, 9216]


def test_a_filter_clauses_literals_are_parameters_of_the_shape():
    """`SUM(x) FILTER(WHERE d > 5)` and `... d > 7` are one query shape, as
    two WHERE literals are: the FilterCompiler compiles both clauses into one
    params pytree.  Without the column's shape the literal stays in the key."""
    schema = Schema("t", [FieldSpec("d", DataType.INT), FieldSpec("x", DataType.INT, role=FieldRole.METRIC)])
    seg = build_segment(schema, {"d": np.arange(50, dtype=np.int32) % 9, "x": np.arange(50, dtype=np.int32)}, "s0")
    info = column_info_from(seg)
    q = "SELECT SUM(x) FILTER(WHERE d > {0} AND d < 8), COUNT(*) FROM t WHERE d > {1}"
    fps = {parse_query(q.format(a, b)).shape_fingerprint(info) for a, b in ((1, 0), (5, 0), (5, 2))}
    assert len(fps) == 1
    assert parse_query(q.format(1, 0)).shape_fingerprint() != parse_query(q.format(5, 0)).shape_fingerprint()
    other = "SELECT SUM(x) FILTER(WHERE d > 1 AND d < 8), COUNT(*) FROM t WHERE d IN (1, 2)"
    assert parse_query(other).shape_fingerprint(info) not in fps
    planner.plan_cache_clear()
    try:
        got = {}
        for a in (1, 5):
            ctx = parse_query(q.format(a, 0))
            plan = planner.plan_segment(ctx, seg)
            got[a] = plan
        assert got[5].cache_hit and got[5].fn is got[1].fn and got[5].bind == "recipe"
        assert any(not np.array_equal(got[1].params[k], got[5].params[k]) for k in got[1].params)
    finally:
        planner.plan_cache_clear()


def _upsert_merge(tables):
    """The by-value merge as a loop: {key tuple: [count, min]} in first-seen order."""
    out = {}
    for keys, count, low in tables:
        for i in range(len(count)):
            k = tuple(col[i] for col in keys)
            have = out.get(k)
            out[k] = [count[i], low[i]] if have is None else [have[0] + count[i], min(have[1], low[i])]
    return out


@pytest.mark.parametrize(
    "kind", ["small ints", "wide ints", "strings", "a null among ints", "one dimension", "int16 range past the dtype"]
)
def test_the_by_value_merge_equals_an_upsert_loop(kind):
    """`reduce._hash_merge` over tables whose key spaces differ: integer
    dimensions of a small range are coded by their values and the merged
    table kept dense (no sort over millions of keys), anything else goes
    through `np.unique` as before; either way the groups, their first-seen
    order and every combined field are the upsert loop's."""
    from pinot_tpu.query import reduce as reduce_mod
    from pinot_tpu.query.result import GroupBySegmentResult

    rng = np.random.default_rng(len(kind))
    tables = []
    for t in range(7):
        n = int(rng.integers(1, 400))
        a = rng.integers(-50, 50, n)
        if kind == "int16 range past the dtype":  # 60,001 values: `keys - lo` does not fit int16
            a = rng.choice(np.asarray([-30000, -7, 0, 7, 30000]), n)
        b = rng.integers(0, 30, n) * (10**12 if kind == "wide ints" else 1)
        keep = np.unique(np.stack([a, b], axis=1), axis=0, return_index=True)[1]  # a table holds a key once
        a, b = a[np.sort(keep)].astype(np.int32 if t % 2 else np.int64), b[np.sort(keep)]
        if kind == "int16 range past the dtype":
            a = a.astype(np.int16)
        if kind == "strings":
            b = np.asarray([f"v{x}" for x in b], dtype=object)
        if kind == "a null among ints":
            b = b.astype(object)
            b[0] = None
        keys = [a] if kind == "one dimension" else [a, b]
        if kind == "one dimension":
            keys = [np.unique(a)]
        m = len(keys[0])
        tables.append((keys, rng.integers(1, 9, m), rng.integers(-99, 99, m).astype(np.float64)))
    want = _upsert_merge(tables)

    class Fn:
        pairwise_merge = False

    results = [GroupBySegmentResult(keys=k, partials=[{"count": c}, {"min": lo}], dense=None) for k, c, lo in tables]
    if kind == "a null among ints":  # None does not sort against ints: np.unique refuses, the upsert loop answers
        assert reduce_mod._hash_merge_vectorized(results, [Fn(), Fn()]) is None
        return
    keys, partials = reduce_mod._hash_merge(results, [Fn(), Fn()])
    got = {tuple(col[i] for col in keys): [partials[0]["count"][i], partials[1]["min"][i]] for i in range(len(keys[0]))}
    assert list(got) == list(want)  # first-seen order too
    assert got == want
