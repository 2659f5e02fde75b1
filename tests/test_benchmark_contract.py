"""What the benchmark reads from inside the program is still there.

Most per-layer metrics of `benchmarks/layer_metrics/*.json` are computed from
names the program chose: spans and their attrs in a traced answer
(`"source": "program_span"`), counters and timers of its registries
(`"program_counter"`).  A PR that renames or drops one leaves `null` in the
driver's ledger, and every later benchmark PR is refused for it.  So each
such file is a case here: on a small two-server replicated cluster behind
the front door, a traced group-by and a traced scalar sum must produce every
span and attr the file names, and the registries must hold every counter,
counter family and timer it names.

The metrics of the layer `wide and sparse group-by` (PR 31) read what only a
group table past the one-hot kernel produces, and the device-trace ones among
them pick their templates by a trace-time counter (`served_by_counter(s)`):
they are cases of their own, on a table whose key space is wide, served once
through the wide scatter under the chip's accumulation policy and once
through the sparse sort.

The metrics of the layer `star-tree` (PR 37) read what only a table with a
star-tree produces (`starSegments`, `levelRows`, the trace-time counter
`scan.traced.startree`, the build timer, the residency gauge): cases of their
own again, on a table with a tree, served one query the tree answers and one
it may not.

The metrics of PR 39 (the front door's waits from accept() on, CPU beside
wall on the group-level stages, the server's loop) are cases of the first
kind: each names an attr or a timer, and the traced answers came through the
door, so the attr is looked for and not the span alone.

The metrics of PR 52 (the interpreter watch, utils/interpreter.py: the lock's
waiters and holders, the collector's pauses, CPU beside wall at the front door,
the jitted call's operands) are cases of the first kind again: the traced
answers started the watch, so its timers ticked, its counters are held (the
ones that count what may never happen at 0) and the door read its thread's
CPU clock.  `INVENTORY`'s case goes the other way: every span, attr, counter
and timer those served queries LEFT has a reader named in PERF.md section 3.

The files are read as data: nothing of `benchmarks/lib` is imported, and no
number is checked, only presence.  The last two tests alone run the
benchmark's own readers: PR 36 was refused because one of them found nothing
in a cell it is given (`launch_cpu_ms`), so every per-layer metric `load_cell`
gives the star-tree cell, and a scan cell, must return a value over a traced
window of that cell's own traffic at toy size.
"""
import glob
import json
import os
import threading
import urllib.request

import jax
import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.rest import QueryServer
from pinot_tpu.ops import segmented
from pinot_tpu.query import planner
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils.interpreter import WATCH
from pinot_tpu.utils.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the keys by which a metric's file names something of the program
SPAN_KEYS = ("spans", "span", "numerator", "denominator")
REGISTRY_KEYS = ("counter", "counters", "over", "prefix", "timer")


DRILL_LAYER = "wide and sparse group-by"
STAR_LAYER = "star-tree"
# counters that count what should not happen: the registries must HOLD them
# (a reducer that finds no such counter reports nothing), at 0 after QUERIES,
# whose every plan-cache hit binds its parameters by the entry's recipe
SOUND_AT_ZERO = {"plan_rebuilds_in_window"}
# counters of what may or may not happen while QUERIES run (a hold of the interpreter lock past 50 ms, a full
# collection): the registry must HOLD them, moved or not
HELD_WHATEVER_MOVED = {"interpreter_holds_in_window", "gc_full_collections_in_window"}
# PR 52's, all among SPECS
INTERPRETER_SPECS = {
    "interpreter_wait_ms", "interpreter_wait_over_20ms_share", "interpreter_cpu_share", "interpreter_embedder_share",
    "interpreter_holds_in_window", "gc_pause_ms", "gc_full_collections_in_window", "frontdoor_door_cpu_ms",
    "frontdoor_engine_cpu_ms", "frontdoor_serialize_cpu_ms", "launch_operands_per_query",
}
# read what only a group-by produces (BENCHMARK.json lists their cells): looked for in QUERIES' group-by alone
GROUP_BY_ONLY = {"table_decode_cpu_ms", "tables_decoded_per_query", "tables_merged_by_value_per_query",
                 "sketch_table_bytes_per_query"}
# read what only a sketch aggregation produces (PR 43: the estimator step's span): looked for in QUERIES' sketch alone
SKETCH_ONLY = {"sketch_final_cpu_ms"}
# PR 39's, all among SPECS
DOOR_SPECS = {
    "frontdoor_accept_wait_ms", "frontdoor_accept_wait_p99_ms", "frontdoor_head_ms", "frontdoor_read_ms",
    "frontdoor_write_ms", "frontdoor_accept_loop_ms", "frontdoor_door_ms", "frontdoor_before_accept_ms",
    "frontdoor_engine_ms", "launch_enqueue_cpu_ms", "collect_cpu_ms", "reduce_cpu_ms", "table_decode_cpu_ms",
    "dispatch_loop_ms",
}


def _program_metrics():
    """(the files that read spans, counters or timers; every file of DRILL_LAYER; every file of STAR_LAYER)"""
    specs, drill, star = {}, {}, {}
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmarks", "layer_metrics", "*.json"))):
        with open(path, encoding="utf-8") as f:
            spec = json.load(f)
        if spec.get("layer") == DRILL_LAYER:
            drill[spec["name"]] = spec
        elif spec.get("layer") == STAR_LAYER:
            star[spec["name"]] = spec
        elif spec.get("source") in ("program_span", "program_counter"):
            specs[spec["name"]] = spec
    return specs, drill, star


SPECS, DRILL_SPECS, STAR_SPECS = _program_metrics()
N_SERVERS = 2
QUERIES = (
    "SELECT region, SUM(rev) FROM contract WHERE qty < 40 GROUP BY region ORDER BY region",
    "SELECT SUM(rev) FROM contract WHERE qty BETWEEN 5 AND 30",
    "SELECT region, DISTINCTCOUNTHLL(qty), PERCENTILETDIGEST(rev, 95) FROM contract WHERE qty < 40 GROUP BY region ORDER BY region",
)


def _named(tree, name):
    """Spans called `name`, or `name:<suffix>` (launch:seg3, round:0), as the
    benchmark's reducers match them."""
    out = [tree] if tree["name"] == name or tree["name"].startswith(name + ":") else []
    for c in tree.get("children", ()):
        out.extend(_named(c, name))
    return out


def _ask_traced(front, sql):
    body = json.dumps({"sql": "SET trace = true; " + sql}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{front.port}/query/sql", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        answer = json.loads(r.read().decode("utf-8"))
    assert answer["trace"] and not answer.get("exceptions"), answer
    return answer["trace"]


@pytest.fixture(scope="module")
def served():
    """(span trees of the traced answers, the registries as the benchmark
    exports them: the process-wide one plus each server's own)."""
    schema = Schema(
        "contract",
        [
            FieldSpec("region", DataType.INT),
            FieldSpec("qty", DataType.INT),
            FieldSpec("rev", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    coord = Coordinator(replication=2)
    servers = [ServerInstance(f"server{i}", device=d) for i, d in enumerate(jax.devices()[:N_SERVERS])]
    for s in servers:
        coord.register_server(s)
    coord.add_table(schema, TableConfig(name="contract"))
    rng = np.random.default_rng(28)
    for i in range(4):
        block = {
            "region": rng.integers(0, 5, 300).astype(np.int32),
            "qty": rng.integers(1, 51, 300).astype(np.int32),
            "rev": rng.integers(1, 10**6, 300),
        }
        coord.add_segment("contract", build_segment(schema, block, f"seg{i}"))
    METRICS.reset()  # what is found below, these queries put there,
    planner.plan_cache_clear()  # their compiles included
    front = QueryServer(Broker(coord)).start()
    trees = []
    try:
        for sql in QUERIES:
            trees.append(_ask_traced(front, sql))
        # the front door updates rest.*Ms after the client has its answer
        settled = threading.Event()
        for _ in range(500):
            if METRICS.snapshot()["timers"].get("rest.writeMs", {"count": 0})["count"] >= len(QUERIES):
                break
            settled.wait(0.01)
    finally:
        front.stop()
        WATCH.stop()  # the traced answers started it; a reset registry must not tick on into the next module
    counters, timers = {}, {}
    for snap in [METRICS.snapshot()] + [s.metrics.snapshot() for s in servers]:
        for k, v in snap["counters"].items():
            counters[k] = counters.get(k, 0) + v
        for k, t in snap["timers"].items():
            timers[k] = timers.get(k, 0) + t["count"]
    return trees, counters, timers


def test_there_are_program_metrics_to_guard():
    assert SPECS, "no program_span / program_counter file under benchmarks/layer_metrics: did they move?"


@pytest.mark.parametrize("name", sorted(SPECS))
def test_program_still_says_what_the_metric_reads(name, served):
    spec = SPECS[name]
    trees, counters, timers = served
    named_keys = [k for k in SPAN_KEYS + REGISTRY_KEYS if k in spec]
    assert named_keys, f"{name}: names nothing this test knows how to look for: {sorted(spec)}"
    answers = [(sql, tree) for sql, tree in zip(QUERIES, trees)
               if (name not in GROUP_BY_ONLY or "GROUP BY" in sql) and (name not in SKETCH_ONLY or "HLL" in sql)]

    span_names = []
    for key in SPAN_KEYS:
        value = spec.get(key, [])
        span_names.extend([value] if isinstance(value, str) else value)
    for span in span_names:
        for sql, tree in answers:
            assert _named(tree, span), f"{name}: no span {span!r} in the traced answer of: {sql}"
    if "attr" in spec:
        for sql, tree in answers:
            values = [n.get("attrs", {}).get(spec["attr"]) for n in _named(tree, spec["span"])]
            assert values and all(isinstance(v, (int, float)) for v in values), (
                f"{name}: attr {spec['attr']!r} of span {spec['span']!r} in the answer of: {sql}: {values}"
            )

    if "counter" in spec:
        if name in SOUND_AT_ZERO:
            assert counters.get(spec["counter"]) == 0, f"{name}: counter {spec['counter']!r} missing, or moved"
        elif name in HELD_WHATEVER_MOVED:
            assert spec["counter"] in counters, f"{name}: counter {spec['counter']!r}"
        else:
            assert counters.get(spec["counter"], 0) > 0, f"{name}: counter {spec['counter']!r}"
    for counter in list(spec.get("counters", ())) + list(spec.get("over", ())):  # a sum of several, a ratio's
        assert counter in counters, f"{name}: counter {counter!r}"  # denominator: each is held, moved or not
    if "over" in spec:
        assert sum(counters[c] for c in spec["over"]) > 0, f"{name}: the denominator {spec['over']} stood still"
    if "prefix" in spec:
        family = {k: v for k, v in counters.items() if k.startswith(spec["prefix"])}
        # replication 2 over two servers: the balanced selector routes to both
        assert len(family) == N_SERVERS and all(v > 0 for v in family.values()), (name, family)
    if "timer" in spec:
        assert timers.get(spec["timer"], 0) > 0, f"{name}: timer {spec['timer']!r}"


def test_plan_rebuilds_counts_the_hits_that_planned_again(served):
    """`plan_rebuilds_in_window` reads `compile.sse.rebuilds`: it stands still
    while hits bind by their entry's recipe (`compile.sse.binds` moves instead:
    QUERIES' segments of one signature share an entry) and moves by one a hit
    whose entry has none, here a raw column's predicate."""
    spec = SPECS["plan_rebuilds_in_window"]
    assert spec["reducer"] == "counter_delta" and spec["counter"] == "compile.sse.rebuilds"
    _, counters, _ = served
    assert counters["compile.sse.binds"] > 0 and counters["compile.sse.rebuilds"] == 0
    schema = Schema("raw_t", [FieldSpec("k", DataType.INT), FieldSpec("v", DataType.LONG, role=FieldRole.METRIC)])
    block = {"k": np.arange(50, dtype=np.int32) % 5, "v": np.arange(50)}
    seg = build_segment(schema, block, "raw0")
    from pinot_tpu.sql.parser import parse_query

    before = METRICS.counter("compile.sse.rebuilds").value
    try:
        for bound in (10, 20, 30):
            plan = planner.plan_segment(parse_query(f"SELECT COUNT(*) FROM raw_t WHERE v < {bound}"), seg)
        assert plan.cache_hit and plan.bind == "rebuild"
        assert METRICS.counter("compile.sse.rebuilds").value == before + 2
    finally:
        planner.plan_cache_clear()


def test_launches_per_query_counts_the_jitted_calls(served):
    """`launches_per_query` reads `device_wait.launches`: the number of
    jitted calls a server made for the query, one a GROUP of segments, which
    is the number of `launch_enqueue` spans under that server's root (and
    `dispatch.launches`); the segments are the `launch:<segment>` spans."""
    spec = SPECS["launches_per_query"]
    assert (spec["span"], spec["attr"]) == ("device_wait", "launches")
    trees, _, _ = served
    for sql, tree in zip(QUERIES, trees):
        roots = [n for n in _named(tree, "server") if "server" in n.get("attrs", {})]
        assert len(roots) == N_SERVERS, sql
        for root in roots:
            (wait,), (dispatch,) = _named(root, "device_wait"), _named(root, "dispatch")
            enqueues = _named(root, "launch_enqueue")
            assert wait["attrs"]["launches"] == dispatch["attrs"]["launches"] == len(enqueues) == 1, sql
            # two of the four segments a server, in one call and one fetch
            assert len(_named(root, "launch")) == enqueues[0]["attrs"]["segments"] == 2
            assert [n["attrs"]["segments"] for n in _named(root, "collect")] == [2]


def test_the_interpreter_watch_has_its_metrics():
    assert INTERPRETER_SPECS <= set(SPECS)
    layers = {SPECS[n]["layer"] for n in INTERPRETER_SPECS}
    assert layers == {"interpreter lock", "front door", "per-server execute"}
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in INTERPRETER_SPECS:  # every cell is given them: no `workloads` list
        assert "workloads" not in listed[name] and listed[name]["moves"] == "latency_p50_ms", name


def test_the_served_queries_were_watched(served):
    """The traced answers of QUERIES started the watch: it ticked, it read
    the threads' clocks by class, and the door read its own thread's."""
    _, counters, timers = served
    assert timers["runtime.interpreterWaitMs"] == counters["runtime.interpreterWait.ticks"] > 0
    assert counters["runtime.watchedMs"] > 0 and counters["runtime.cpuMs.watch"] > 0
    assert counters["runtime.cpuMs.handler"] > 0 and timers["rest.doorCpuMs"] >= len(QUERIES) - 1
    assert timers["rest.doorCpuMs"] == timers["rest.engineCpuMs"] == timers["rest.serializeCpuMs"]
    assert counters["runtime.gc.gen1"] + counters["runtime.gc.gen2"] == timers["runtime.gcPauseMs"] > 0


def test_launch_operands_counts_what_the_jitted_call_is_handed(served):
    """`operands` on `launch_enqueue`: a resident entry a column a member,
    the stacked parameter buffers (`paramArrays` on `launch_ship`), the
    carried (presence, partials) of a combining call."""
    trees, _, _ = served
    for sql, tree in zip(QUERIES, trees):
        for root in [n for n in _named(tree, "server") if "server" in n.get("attrs", {})]:
            (enqueue,) = _named(root, "launch_enqueue")
            ships = _named(root, "launch_ship")
            params = {n["attrs"]["paramArrays"] for n in ships}
            assert len(params) == 1 and len(ships) == enqueue["attrs"]["segments"] == 2
            operands = enqueue["attrs"]["operands"]
            # at least an entry a column a member and the parameter buffers; the exact count is held against the
            # call's own arguments in tests/test_span_cpu.py
            assert isinstance(operands, int) and operands >= 2 + params.pop(), sql


# what a served query leaves and PERF.md section 3 does not name with its reader is removed or documented:
# names that stand there under a family's stem (`residency.<server>.hits`, `.launchShipMs`)
INVENTORY_STEMS = {
    "broker.routedSegments.": "broker.routedSegments.<server>", "residency.server": "residency.<server>",
    "server.launch": "server.launchPlanMs", "server.collectMs": ".collectMs", "server.compileMs": ".compileMs",
    "runtime.cpuMs.": "runtime.cpuMs.<class>", "runtime.interpreterWait.": "runtime.interpreterWait.ticks",
    "runtime.gc.": "runtime.gc.gen1",
}


def test_every_name_a_served_query_leaves_has_a_reader_in_perf_md(served):
    trees, counters, timers = served
    with open(os.path.join(ROOT, "PERF.md"), encoding="utf-8") as f:
        layers = f.read().split("## 3. Layers", 1)[1].split("## 4. Cells", 1)[0]
    names = set(counters) | set(timers)

    def walk(node):
        names.add(node["name"].split(":", 1)[0])
        names.update(node.get("attrs", {}))
        for child in node.get("children", ()):
            walk(child)

    for tree in trees:
        walk(tree)
    unread = []
    for name in sorted(names):
        stem = next((doc for stem, doc in INVENTORY_STEMS.items() if name.startswith(stem)), name)
        if f"`{stem}`" not in layers and f"`{stem}" not in layers and f"{stem}`" not in layers:
            unread.append(name)
    assert not unread, f"left by a served query, with no reader named in PERF.md section 3: {unread}"


# ---------------------------------------------------------------------------
# the layer `wide and sparse group-by` (PR 31)
# ---------------------------------------------------------------------------
DRILL_QUERIES = {  # 120 x 120 = 14,400 slots: past the one-hot kernel's 8,192
    "wide": "SELECT a, b, SUM(rev) FROM drill WHERE qty < 40 GROUP BY a, b LIMIT 100000",
    "sparse": "SET maxDenseGroups = 8192; SELECT a, b, SUM(rev) FROM drill WHERE qty < 40 GROUP BY a, b LIMIT 100000",
    # PR 43: 120 slots x 4,096 registers and x 2,048 bins, scattered (`scan.traced.sketch_scatter`)
    "sketch": "SELECT a, DISTINCTCOUNTHLL(b), PERCENTILETDIGEST(rev, 95) FROM drill WHERE qty < 40 GROUP BY a LIMIT 100000",
}


@pytest.fixture(scope="module")
def drill_served():
    """({wide, sparse}: span tree of the traced answer, the counters) of one
    server with two segments, under the chip's accumulation policy."""
    mp = pytest.MonkeyPatch()
    mp.setattr(ops, "accum_policy", lambda: "chunked32")
    mp.setattr(segmented, "accum_policy", lambda: "chunked32")
    schema = Schema(
        "drill",
        [
            FieldSpec("a", DataType.INT),
            FieldSpec("b", DataType.INT),
            FieldSpec("qty", DataType.INT),
            FieldSpec("rev", DataType.INT, role=FieldRole.METRIC),
        ],
    )
    coord = Coordinator(replication=1)
    server = ServerInstance("server0")
    coord.register_server(server)
    coord.add_table(schema, TableConfig(name="drill"))
    rng = np.random.default_rng(31)
    for i in range(2):
        block = {
            "a": rng.permutation(np.arange(600) % 120).astype(np.int32),
            "b": rng.permutation(np.arange(600) % 120).astype(np.int32),
            "qty": rng.integers(1, 51, 600).astype(np.int32),
            "rev": rng.integers(1, 10**7, 600).astype(np.int32),
        }
        coord.add_segment("drill", build_segment(schema, block, f"seg{i}"))
    METRICS.reset()
    planner.plan_cache_clear()
    front = QueryServer(Broker(coord)).start()
    try:
        trees = {name: _ask_traced(front, sql) for name, sql in DRILL_QUERIES.items()}
    finally:
        front.stop()
        mp.undo()
        planner.plan_cache_clear()
    counters = dict(METRICS.snapshot()["counters"])
    for k, v in server.metrics.snapshot()["counters"].items():
        counters[k] = counters.get(k, 0) + v
    return trees, counters


def test_the_drill_layer_has_its_metrics():
    assert {"wide_table_ms", "sparse_sort_ms", "sparse_roofline", "sparse_table_bytes_per_query",
            "sparse_decode_ms", "compacted_scatters_per_query", "compact_sort_ms"} <= set(DRILL_SPECS)


@pytest.mark.parametrize("name", sorted(DRILL_SPECS))
def test_program_still_says_what_a_drill_metric_reads(name, drill_served):
    spec = DRILL_SPECS[name]
    trees, counters = drill_served
    named = [k for k in SPAN_KEYS + ("served_by_counter", "served_by_counters") if k in spec]
    assert named, f"{name}: names nothing this test knows how to look for: {sorted(spec)}"
    span_names = []
    for key in SPAN_KEYS:
        value = spec.get(key, [])
        span_names.extend([value] if isinstance(value, str) else value)
    for span in span_names:
        for how, tree in trees.items():
            assert _named(tree, span), f"{name}: no span {span!r} in the traced {how} group-by"
    if "attr" in spec:
        for how, tree in trees.items():
            values = [n.get("attrs", {}).get(spec["attr"]) for n in _named(tree, spec["span"])]
            assert values and all(isinstance(v, (int, float)) for v in values), (name, how, values)
    for counter in [spec.get("served_by_counter")] + spec.get("served_by_counters", []):
        if counter is not None:
            assert counters.get(counter, 0) > 0, f"{name}: trace-time counter {counter!r}"


def test_table_decode_says_what_came_back(drill_served):
    """`table_decode` (inside `collect`): `tableBytes` of the fetched tables,
    `tables` decoded (PR 40: the two segments' dense tables come back as the
    ONE the chip folded them into, the sparse ones one a segment), `keySpace`
    slots a table has, `groups` the decoded tables held (the merged table's,
    or summed over the segments'); and the always-on `server.sparseGroups`
    counts the sparse plan's alone."""
    trees, counters = drill_served
    groups = {}
    for how in ("wide", "sparse"):
        tree = trees[how]
        (collect,) = _named(tree, "collect")
        (decode,) = _named(collect, "table_decode")
        attrs = decode["attrs"]
        assert attrs["kind"] == {"wide": "groupby_dense", "sparse": "groupby_sparse"}[how]
        assert attrs["keySpace"] == 120 * 120 and attrs["tableBytes"] >= 2 * 8 * attrs["keySpace"]
        assert attrs["tables"] == {"wide": 1, "sparse": 2}[how]
        # one dense table of `keySpace` slots came back, not one a segment (presence + SUM's sum and count, 8 B each)
        assert how == "sparse" or attrs["tableBytes"] == 3 * 8 * attrs["keySpace"]
        groups[how] = attrs["groups"]
    assert 0 < groups["wide"] <= groups["sparse"] <= 2 * groups["wide"]  # a group that two segments hold counts once merged
    assert counters["server.sparseGroups"] == groups["sparse"]
    assert counters["scan.traced.wide_scatter"] >= 1 and counters["scan.traced.sparse_sort"] >= 1


def test_the_drill_configuration_shares_sf1s_table():
    """`ssb_flat_sf1_drill` is `ssb_flat_sf1`'s table on purpose: the same
    generator, schema, query set and rows, so the same seed gives the same
    rows and every line of staging and of the reference is shared."""
    def load(name):
        with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json"), encoding="utf-8") as f:
            return json.load(f)

    drill, sf1 = load("ssb_flat_sf1_drill"), load("ssb_flat_sf1")
    for key in ("columns", "datagen", "query_set", "rows", "segment_rows", "packed_codes", "table", "table_config", "hierarchy"):
        assert drill[key] == sf1[key], key
    assert (drill["servers"], drill["chips"], drill["replication"]) == (1, 1, 1)
    assert set(drill["reduced"]) == set(sf1["reduced"]) | {"scale_factor", "templates"}
    assert set(drill["reduced"]) == set(drill["reduced_why"])
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == "ssb_flat_sf1_drill"]
    assert entry["source"] == drill["source"] and entry["reduced"] == drill["reduced"]


# ---------------------------------------------------------------------------
# the layer `star-tree` (PR 37)
# ---------------------------------------------------------------------------
STAR_QUERIES = {  # the tree serves the first; the second sums an expression, which Pinot's rules send to the scan
    "tree": "SELECT region, SUM(rev), COUNT(*) FROM star WHERE qty < 40 GROUP BY region ORDER BY region",
    "scan": "SELECT region, SUM(rev - qty) FROM star WHERE qty < 40 GROUP BY region ORDER BY region",
}


@pytest.fixture(scope="module")
def star_served():
    """({tree, scan}: span tree of the traced answer, counters, timers, gauges)
    of one server with two segments that each have a star-tree."""
    from pinot_tpu.spi.config import IndexingConfig

    schema = Schema(
        "star",
        [
            FieldSpec("region", DataType.INT),
            FieldSpec("qty", DataType.INT),
            FieldSpec("rev", DataType.INT, role=FieldRole.METRIC),
        ],
    )
    tcfg = TableConfig(name="star", indexing=IndexingConfig(star_tree_index_configs=[
        {"dimensionsSplitOrder": ["region", "qty"], "functionColumnPairs": ["SUM__rev", "COUNT__*"]}]))
    coord = Coordinator(replication=1)
    server = ServerInstance("server0")
    coord.register_server(server)
    coord.add_table(schema, tcfg)
    METRICS.reset()
    planner.plan_cache_clear()
    rng = np.random.default_rng(37)
    for i in range(2):
        block = {
            "region": rng.integers(0, 5, 3000).astype(np.int32),
            "qty": rng.integers(1, 51, 3000).astype(np.int32),
            "rev": rng.integers(1, 10**7, 3000).astype(np.int32),
        }
        seg = build_segment(schema, block, f"seg{i}", table_config=tcfg)
        coord.add_segment("star", seg)
        seg.to_device(device=server.device, residency=server.residency)  # as the benchmark's set-up stages it
    front = QueryServer(Broker(coord)).start()
    try:
        trees = {name: _ask_traced(front, sql) for name, sql in STAR_QUERIES.items()}
    finally:
        front.stop()
        planner.plan_cache_clear()
    snaps = [METRICS.snapshot(), server.metrics.snapshot()]
    counters, timers, gauges = {}, {}, {}
    for snap in snaps:
        for k, v in snap["counters"].items():
            counters[k] = counters.get(k, 0) + v
        for k, t in snap["timers"].items():
            timers[k] = timers.get(k, 0) + t["count"]
        gauges.update(snap["gauges"])
    return trees, counters, timers, gauges


def test_the_star_tree_layer_has_its_metrics():
    assert set(STAR_SPECS) == {"startree_segments_per_query", "startree_level_rows_per_query", "startree_roofline",
                               "startree_build_s", "startree_resident_bytes"}


@pytest.mark.parametrize("name", sorted(STAR_SPECS))
def test_program_still_says_what_a_star_tree_metric_reads(name, star_served):
    import re

    spec = STAR_SPECS[name]
    trees, counters, timers, gauges = star_served
    named = [k for k in ("span", "served_by_counter", "timer", "pattern") if k in spec]
    assert named, f"{name}: names nothing this test knows how to look for: {sorted(spec)}"
    if "span" in spec:
        values = {how: [n.get("attrs", {}).get(spec["attr"]) for n in _named(tree, spec["span"])] for how, tree in trees.items()}
        assert values["tree"] and all(isinstance(v, (int, float)) for v in values["tree"]), (name, values)
        if spec["attr"] == "levelRows":  # of the launches that read a level, and of no other
            assert all(v > 0 for v in values["tree"]) and values["scan"] == [None, None], values
        else:  # of every query: the scan-served one says 0
            assert values == {"tree": [2], "scan": [0]}, values
    if "served_by_counter" in spec:
        assert counters.get(spec["served_by_counter"], 0) > 0, f"{name}: trace-time counter {spec['served_by_counter']!r}"
    if "timer" in spec:
        assert timers.get(spec["timer"], 0) == 2, f"{name}: timer {spec['timer']!r}: one update a tree a segment"
    if "pattern" in spec:
        found = {k: v for k, v in gauges.items() if re.fullmatch(spec["pattern"], k)}
        assert list(found) == ["residency.server0.starTreeBytes"] and found["residency.server0.starTreeBytes"] > 0, (name, gauges)


def test_a_star_tree_launch_is_a_launch_like_any_other(star_served):
    """What the star-tree layer adds to the spans the older metrics read:
    `launch_plan` says `star` and `level` beside `cache`, `launch:<segment>`
    keeps `cpuMs` and `kernelBytes` (PR 36 was refused for a cell without a
    single `cpuMs`), the root's `docsScanned` is the levels' rows, and the
    always-on counters count segments and rows."""
    trees, counters, _, _ = star_served
    tree = trees["tree"]
    plans = _named(tree, "launch_plan")
    assert [(n["attrs"]["star"], n["attrs"]["level"]) for n in plans] == [("st0", 2), ("st0", 2)]
    assert all(n["attrs"]["cache"] in ("hit", "miss") for n in plans)
    launches = [n for n in _named(tree, "launch") if n["name"].startswith("launch:")]
    assert len(launches) == 2 and all(n["attrs"]["cpuMs"] >= 0 and n["attrs"]["kernelBytes"] > 0 for n in launches)
    (root,) = [n for n in _named(tree, "server") if "server" in n.get("attrs", {})]
    rows = sum(n["attrs"]["levelRows"] for n in launches)
    assert root["attrs"]["docsScanned"] == rows < 2 * 3000
    assert _named(tree, "device_wait")[0]["attrs"]["launches"] == 1  # the two levels rode one call
    assert counters["server.starTreeSegments"] == 2 and counters["server.starTreeLevelRows"] == rows
    assert all("star" not in n["attrs"] for n in _named(trees["scan"], "launch_plan"))


def test_the_star_tree_configuration_shares_sf10s_table():
    """`ssb_flat_sf10_startree` is `ssb_flat_sf10`'s table, row for row, plus
    the two trees: the same generator, schema, query set and rows, so cell 1
    beside its cell IS "the same queries without the tree"."""
    def load(name):
        with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json"), encoding="utf-8") as f:
            return json.load(f)

    star, sf10 = load("ssb_flat_sf10_startree"), load("ssb_flat_sf10")
    for key in ("columns", "datagen", "query_set", "rows", "segment_rows", "packed_codes", "table", "hierarchy",
                "servers", "chips", "replication", "scale_factor"):
        assert star[key] == sf10[key], key
    trees = star["table_config"].pop("starTreeIndexConfigs")
    assert sf10["table_config"].pop("starTreeIndexConfigs") == [] and star["table_config"] == sf10["table_config"]
    assert [t["dimensionsSplitOrder"] for t in trees] == [
        ["s_region", "d_year", "p_category", "p_brand1"], ["c_region", "s_region", "d_year", "c_nation", "s_nation"]]
    assert all(t["functionColumnPairs"] == ["SUM__lo_revenue", "COUNT__*"] and t["maxLeafRecords"] == 10000 for t in trees)
    assert set(star["reduced"]) == set(sf10["reduced"]) | {"templates"} == set(star["reduced_why"])
    assert set(sf10["guarantees"]) < set(star["guarantees"])
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == "ssb_flat_sf10_startree"]
    assert entry["source"] == star["source"] and entry["reduced"] == star["reduced"]
    with open(os.path.join(ROOT, "benchmarks", "traffic", "rollup_closed.json"), encoding="utf-8") as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["templates"], mix["sample_checked"], mix["rolling_start_s"]) == (
        "closed", 4, ["q2_1", "q2_2", "q2_3", "q3_1", "q4_1"], 40, 3.0)


def _toy_window(workload, rows, seed, seconds=1.5, events=None):
    """The benchmark's own readers over a traced window of a cell's own
    traffic, through its own set-up, warm-up and load generator, at toy size
    on the CPU: {metric: value} of every per-layer metric `load_cell` gives
    the cell, the cell, its requests.  Only the device's trace is made by
    hand (this process has no chip): busy seconds, the share of each
    template in the traced span and, where a cell's metrics read device
    events by name, `events` ({name: (count, seconds)}) in the chip's form."""
    import sys

    bench_dir = os.path.join(ROOT, "benchmarks")
    sys.path.insert(0, bench_dir)
    try:
        from lib import cluster as cluster_mod
        from lib import harness, loadgen

        cell = harness.load_cell(workload)
        config = dict(cell["config"], rows=rows, segment_rows=rows // 4)
        planner.plan_cache_clear()
        cl = cluster_mod.Cluster(config, seed, jax.devices()[:1], build_threads=2)
        try:
            moved = {}
            harness.warm_up(cl.url, cell, True, counters=cl.counters, moved=moved)
            before = cl.counters()
            window = loadgen.run(cl.url, cell["mix"], cell["query_set"], seed, seconds, traced=True)
            reqs = window["requests"]
            assert {r.template for r in reqs} == set(cell["mix"]["templates"]) and all(r.spans for r in reqs)
            weights = {t: float(sum(r.template == t for r in reqs)) for t in cell["mix"]["templates"]}
            ctx = {
                "window_requests": reqs, "faults": {}, "window_s": window["window_s"], "failed_latency_s": 120.0,
                "timers": dict(cl.timers, setup_s=1.0, warm_up_s=1.0), "requests": reqs, "counters_before": before,
                "counters_after": cl.counters(), "warm_moved": moved, "config": config, "query_set": cell["query_set"],
                "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "name": "TPU v5e"},
                "device_trace": {"busy_s": 1.0, "window_s": seconds, "events": events or {}, "chips": 1,
                                 "queries_in_trace": float(len(reqs)), "template_weights": weights},
            }
            values = {m["name"]: harness.metric_value("layer_metrics", m["name"], ctx) for m in cell["per_layer"]}
            # the untraced line's: they read the requests' clocks and the set-up timer alone
            assert all(harness.metric_value("end_to_end", m["name"], ctx) is not None for m in cell["end_to_end"])
        finally:
            cl.close()
            planner.plan_cache_clear()
    finally:
        sys.path.remove(bench_dir)
    return values, cell, reqs, weights


def test_every_metric_of_the_star_tree_cell_has_a_reader_that_returns_a_value():
    values, _, reqs, weights = _toy_window("ssb_sf10_startree.rollup_closed", 48_000, 37)
    assert set(STAR_SPECS) | {"launch_cpu_ms", "launches_per_query", "compiles_in_window", "plan_rebuilds_in_window"} <= set(values)
    assert DOOR_SPECS <= set(values)  # its group-bys decode tables: table_decode_cpu_ms lists the cell
    assert not [name for name, v in values.items() if v is None], values
    assert values["startree_segments_per_query"] == pytest.approx(4 * sum(weights[t] for t in weights if t != "q4_1") / len(reqs))
    assert 0.0 < values["startree_roofline"] < 100.0 and values["startree_resident_bytes"] > 0
    assert values["compiles_in_window"] == values["plan_rebuilds_in_window"] == 0.0


@pytest.fixture(scope="module")
def q1_window():
    return _toy_window("ssb_sf10.q1_closed", 40_000, 39)


def test_the_door_and_cpu_metrics_read_a_value_on_a_scan_cells_traffic(q1_window):
    """PR 39's metrics carry no `workloads` list (but `table_decode_cpu_ms`), so
    every cell is given them: on Q1's traffic, which decodes no table, each
    returns a value and `table_decode_cpu_ms` is not asked for; and what they
    return hangs together: the door's time holds its parts, a stage's CPU is at
    most its wall, the server's loop is part of `dispatch`."""
    values, cell, reqs, _ = q1_window
    assert DOOR_SPECS - {"table_decode_cpu_ms"} <= set(values) and "table_decode_cpu_ms" not in values
    assert not [name for name, v in values.items() if v is None], values
    assert all(values[name] >= 0.0 for name in DOOR_SPECS & set(values)), values
    assert values["frontdoor_door_ms"] >= (values["frontdoor_accept_wait_ms"] + values["frontdoor_head_ms"]
                                           + values["frontdoor_read_ms"] + values["frontdoor_engine_ms"]
                                           + values["frontdoor_serialize_ms"] + values["frontdoor_write_ms"]) - 0.01
    assert values["frontdoor_accept_wait_p99_ms"] >= values["frontdoor_accept_wait_ms"] / 2
    for stage in ("launch_enqueue", "collect", "reduce"):
        assert values[stage + "_cpu_ms"] <= values[stage + "_ms"] + 1.0, stage
    assert values["dispatch_loop_ms"] <= values["dispatch_ms"]
    # one update of rest.doorMs an answered request: the two means of frontdoor_before_accept_ms are over the same requests
    sent = np.mean([r.done - r.sent for r in reqs]) * 1000.0
    assert values["frontdoor_before_accept_ms"] == pytest.approx(sent - values["frontdoor_door_ms"])


@pytest.mark.parametrize("name", sorted(INTERPRETER_SPECS))
def test_an_interpreter_metric_reads_a_value_on_a_scan_cells_traffic(name, q1_window):
    """PR 52's eleven carry no `workloads` list: every cell is given them,
    and each returns a value over a traced window (the traced warm-up
    started the watch; the window's own traced queries kept it)."""
    values = q1_window[0]
    assert values[name] is not None and values[name] >= 0.0, (name, values[name])
    if name.endswith("_share"):
        assert values[name] <= (8.0 if name == "interpreter_cpu_share" else 1.0)  # cores here; a share of a whole
    if name == "launch_operands_per_query":
        assert values[name] >= values["launches_per_query"] * 2


def test_what_the_interpreter_metrics_read_hangs_together(q1_window):
    values = q1_window[0]
    assert values["frontdoor_engine_cpu_ms"] + values["frontdoor_serialize_cpu_ms"] <= values["frontdoor_door_cpu_ms"] + 0.01
    assert values["frontdoor_door_cpu_ms"] <= values["frontdoor_door_ms"] + 1.0
    assert values["frontdoor_engine_cpu_ms"] <= values["frontdoor_engine_ms"] + 1.0
    assert values["interpreter_cpu_share"] > 0.0 and values["interpreter_embedder_share"] > 0.0  # the clients are this process's


def test_every_metric_of_the_built_apart_cell_has_a_reader_that_returns_a_value(monkeypatch):
    """`ssqe_exp001_50seg.aggs_closed` (PR 41): every per-layer metric `load_cell` gives the cell returns a value
    over a traced window of its own traffic at toy size (4 segments of 10,000 rows, dictionaries of 4 sizes), and
    every end-to-end metric over the same window untraced-wise (they read the requests' clocks alone); and what
    the new names say hangs together: one kernel a template, three templates in four table-shaped."""
    # the three ops that pace the cell, as the v5e compiler names them (an AOT compile of the cell's programs:
    # `predicate/gather`, `value_transform/gather`, `wide_scatter/scatter-add`), at the toy window's 10,000 rows a
    # segment; a group key's vector (kLoop) and a table of another size are there to be left out
    events = {
        "%fusion.2 = pred[10000]{0:T(1024)(128)(4,1)S(1)} fusion(%copy-done, %pad_clamp_fusion), kind=kCustom, calls=%f": (8, 0.30),
        "%fusion.6 = s32[10000]{0:T(1024)S(1)} fusion(%copy-done, %pad_clamp_fusion), kind=kCustom, calls=%fused_c": (8, 0.20),
        "%fusion.19 = s32[81920]{0:T(1024)S(1)} fusion(%fusion.1, %broadcast.55, %constant.7), kind=kCustom, calls=%f": (8, 0.10),
        "%fusion.1 = s32[10000]{0:T(1024)S(1)} fusion(%bitcast.5, %bitcast.4), kind=kLoop, calls=%fused_computation.4": (8, 5.0),
        "%fusion.7 = s32[70001]{0:T(1024)S(1)} fusion(%fusion.1, %broadcast.55, %constant.7), kind=kCustom, calls=%f": (8, 5.0),
    }
    # the chip's accumulation: a group table past the one-hot kernel's slots is scattered (`scan.traced.wide_scatter`)
    monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
    values, cell, reqs, weights = _toy_window("ssqe_exp001_50seg.aggs_closed", 40_000, 41, events=events)
    new = {"table_shaped_segments_per_query", "tables_decoded_per_query", "tables_merged_by_value_per_query",
           "warm_up_compiles_per_template", "warm_up_s", "ssqe_roofline",
           "in_table_gather_ms", "dict_decode_gather_ms", "table_shaped_scatter_ms"}
    assert new | {"launches_per_query", "compiles_in_window", "table_decode_cpu_ms", "combined_segments_per_query"} <= set(values)
    assert DOOR_SPECS <= set(values)
    assert not [name for name, v in values.items() if v is None], values
    assert values["compiles_in_window"] == values["plan_rebuilds_in_window"] == 0.0
    # a kernel and its group program (4 segments: one call of width 4) a template, whatever each dictionary holds
    assert values["warm_up_compiles_per_template"] == 2.0 and values["launches_per_query"] == 1.0
    shaped = sum(weights[t] for t in weights if t != "sum_query") / len(reqs)
    assert 3 * shaped <= values["table_shaped_segments_per_query"] <= 4 * shaped  # the segment that holds a bound may be its own shape
    assert values["tables_decoded_per_query"] == values["tables_merged_by_value_per_query"] == 4.0  # group_low_high's
    assert values["combined_segments_per_query"] == 0.0 and 0.0 < values["ssqe_roofline"] < 100.0
    # PR 51: the one table past the one-hot kernel's slots is group_low_high's, which has no predicate: no compaction
    assert values["compacted_scatters_per_query"] == 0.0
    # each op's time over ITS template's traced queries, nothing of the kLoop vector or the other table
    assert values["in_table_gather_ms"] == pytest.approx(300.0 / weights["count_in"])
    assert values["dict_decode_gather_ms"] == pytest.approx(200.0 / weights["filtered_query"])
    assert values["table_shaped_scatter_ms"] == pytest.approx(100.0 / weights["group_low_high"])
    assert {m["name"] for m in cell["end_to_end"]} == {"throughput_qps", "latency_p50_ms", "latency_p95_ms", "setup_s"}


def test_every_metric_of_the_sketch_cell_has_a_reader_that_returns_a_value(monkeypatch):
    """`ssb_sf10_sketch.sketch_closed` (PR 43): every per-layer metric `load_cell` gives the cell returns a value
    over a traced window of its own traffic at toy size (4 segments of 10,000 rows, four `lo_custkey` dictionaries),
    and what the new names say hangs together: one kernel and one group program a template whatever each dictionary
    holds, ONE table of 175 x 4,096 registers (or 175 x 2,048 bins) back for the four segments."""
    # the ops that pace the cell, as the v5e compiler names them (an AOT compile of the cell's programs:
    # `value_transform/gather`, `sketch_scatter/scatter-max`, `sketch_scatter/scatter-add`); a group key's vector
    # (kLoop) and a table that is no multiple of 175 slots are there to be left out
    events = {
        "%fusion = s32[10000]{0:T(1024)S(1)} fusion(%copy-done, %pad_clamp_fusion), kind=kCustom, calls=%fused_comput": (8, 0.20),
        "%fusion.1 = s32[716800]{0:T(1024)S(1)} fusion(%get-tuple-element.20, %get-tuple-element.16, %constant.25), kind=kCustom, calls=%f": (8, 0.30),
        "%fusion.5 = s32[358400]{0:T(1024)S(1)} fusion(%fusion.7, %param_0.64, %param_1.63), kind=kCustom, calls=%f": (4, 0.10),
        "%fusion.3 = s32[10000]{0:T(1024)S(1)} fusion(%bitcast.5, %bitcast.4), kind=kLoop, calls=%fused_computation.4": (8, 5.0),
        "%fusion.9 = s32[70001]{0:T(1024)S(1)} fusion(%fusion.1, %broadcast.55, %constant.7), kind=kCustom, calls=%f": (8, 5.0),
        # PR 51: the compaction's one-operand sort; Q4.3's kind, three operands by two keys, is there to be left out
        "%sort.1 = s32[10000]{0:T(1024)S(1)} sort(%fusion.11), dimensions={0}, to_apply=%region_5.9, metadata={op_name=": (8, 0.60),
        "%sort.7 = (s32[10000]{0:T(1024)S(1)}, s32[10000]{0:T(1024)S(1)}, s32[10000]{0:T(1024)S(1)}) sort(%fusion.3, %iota.1": (8, 5.0),
    }
    monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
    values, cell, reqs, weights = _toy_window("ssb_sf10_sketch.sketch_closed", 40_000, 43, events=events)
    new = {"sketch_scatter_ms", "sketch_hash_ms", "sketch_roofline", "sketch_table_bytes_per_query", "sketch_final_cpu_ms",
           "compacted_scatters_per_query", "compact_sort_ms"}
    assert new | {"launches_per_query", "compiles_in_window", "table_decode_cpu_ms", "combined_segments_per_query",
                  "tables_decoded_per_query", "warm_up_compiles_per_template", "warm_up_s",
                  "contracted_lookups_per_query", "resident_lookups_per_query"} <= set(values)
    assert DOOR_SPECS <= set(values)
    assert not [name for name, v in values.items() if v is None], values
    assert values["compiles_in_window"] == values["plan_rebuilds_in_window"] == 0.0
    # PR 49's metric is on the traced line (its file is a data file beside PR 48's: the same reducer, the next attr
    # of the same span); a toy table's lo_custkey dictionary is in the contraction's range, so the two HLL templates'
    # reads of it are contracted here, one a segment, and none is resident: at the timed size it is the other way round
    assert SPECS["resident_lookups_per_query"]["attr"] == "residentLookups"
    assert SPECS["resident_lookups_per_query"]["reducer"] == SPECS["contracted_lookups_per_query"]["reducer"]
    assert values["resident_lookups_per_query"] == 0.0
    assert values["contracted_lookups_per_query"] == pytest.approx(4 * (len(reqs) - weights["p95_rev_year_nation"]) / len(reqs))
    # a kernel and its combining group program (4 segments: one call of width 4) a template
    assert values["warm_up_compiles_per_template"] == 2.0 and values["launches_per_query"] == 1.0
    assert values["combined_segments_per_query"] == 4.0 and values["tables_decoded_per_query"] == 1.0
    assert values["sketch_table_bytes_per_query"] == 175 * 4096 * 4 == 175 * 2048 * 8  # int32 registers, int64 bins
    assert values["sketch_final_cpu_ms"] > 0.0 and 0.0 < values["sketch_roofline"] < 100.0
    # the scatters over every traced query (all three templates scatter), the gather over the HLL templates' alone
    assert values["sketch_scatter_ms"] == pytest.approx(400.0 / len(reqs))
    assert values["sketch_hash_ms"] == pytest.approx(200.0 / (len(reqs) - weights["p95_rev_year_nation"]))
    # PR 51: every template has a WHERE and one mask, so each segment's program carries one compaction, and the
    # one-operand sorts are read over every traced query (`scan.traced.compact_scatter` moved in all three warm-ups)
    assert values["compacted_scatters_per_query"] == 4.0 and values["compact_sort_ms"] == pytest.approx(600.0 / len(reqs))
    assert {m["name"] for m in cell["end_to_end"]} == {"throughput_qps", "latency_p50_ms", "latency_p95_ms", "setup_s"}


@pytest.mark.parametrize("case", ["sort_in_trace", "no_sort_in_trace", "attr_on_span", "no_attr_on_span", "counter_never_moved"])
def test_the_compactions_two_metrics_read_a_value_or_nothing(case):
    """PR 51's two data files over reducers the benchmark had: `compact_sort_ms` reads the one-operand int32
    sorts of a trace over the queries whose warm-up moved `scan.traced.compact_scatter`, and nothing where no such
    event ran (every mask passed more than the crossover) or no plan carries the compaction (the parent);
    `compacted_scatters_per_query` the `dispatch` span's attr, and nothing on a program without it."""
    import sys
    from types import SimpleNamespace

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        from lib import harness
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmarks"))
    events = {
        "%sort.4 = s32[1500000]{0:T(1024)S(1)} sort(%broadcast_select_fusion), dimensions={0}, to_apply=%region_6.9": (16, 0.032),
        "%sort.9 = (s32[1500000]{0:T(1024)S(1)}, s32[1500000]{0:T(1024)S(1)}, s32[1500000]{0:T(1024)S(1)}) sort(%f": (8, 0.017),
        "%fusion.63 = s32[437500]{0:T(1024)S(1)} fusion(%get-tuple-element.346, %bitcast.37, %fusion.62), kind=kCustom": (16, 0.004),
    }
    if case == "no_sort_in_trace":
        events = {k: v for k, v in events.items() if not k.startswith("%sort.4 ")}
    moved = {"q3_2": {"scan.traced.compact_scatter": 1.0, "scan.traced.wide_scatter": 1.0},
             "q3_3": {"scan.traced.compact_scatter": 3.0}, "q4_3": {"scan.traced.sparse_sort": 1.0}}
    if case == "counter_never_moved":
        moved = {t: {k: v for k, v in m.items() if "compact" not in k} for t, m in moved.items()}
    attrs = {"launches": 1, "residentLookups": 0}
    if case != "no_attr_on_span":
        attrs["compactedScatters"] = 4
    tree = {"name": "query", "children": [{"name": "server:s0", "children": [{"name": "dispatch", "attrs": attrs}]}]}
    ctx = {
        "requests": [SimpleNamespace(spans=tree), SimpleNamespace(spans=tree), SimpleNamespace(spans=None)],
        "warm_moved": moved,
        "device_trace": {"events": events, "template_weights": {"q3_2": 2.0, "q3_3": 2.0, "q4_3": 2.0}},
    }
    sort_ms = harness.metric_value("layer_metrics", "compact_sort_ms", ctx)
    per_query = harness.metric_value("layer_metrics", "compacted_scatters_per_query", ctx)
    assert sort_ms == (pytest.approx(32.0 / 4.0) if case in ("sort_in_trace", "attr_on_span", "no_attr_on_span") else None)
    assert per_query == (None if case == "no_attr_on_span" else 4.0)


def test_every_metric_of_the_time_ordered_cell_has_a_reader_that_returns_a_value():
    """`ssb_sf10_bydate.dashboard_closed` (PR 47): every per-layer metric `load_cell` gives the cell, the list-less
    ones too, returns a value over a traced window of its own traffic at toy size (4 segments of 10,000 rows, each
    a quarter of the calendar, sorted by `lo_orderdate`, six columns inverted), and every end-to-end metric; and what
    the new names say hangs together: the pruner's count is the calendar's, every hit binds, nothing compiles in the
    window whatever count survives, and no launch carries a row-length operand."""
    import sys

    values, cell, reqs, weights = _toy_window("ssb_sf10_bydate.dashboard_closed", 40_000, 47)
    new = {"segments_pruned_per_query", "prune_ms", "doc_range_segments_per_query", "launch_param_bytes_per_query",
           "bydate_roofline"}
    listed = {"compiles_in_window", "combined_segments_per_query", "table_decode_cpu_ms", "tables_decoded_per_query",
              "table_shaped_segments_per_query", "tables_merged_by_value_per_query", "warm_up_compiles_per_template",
              "warm_up_s"}
    assert new | listed | {"launches_per_query", "plan_rebuilds_in_window"} <= set(values)
    assert "scan_kernel_ms" not in values and "scan_roofline" not in values  # their count is every row of the table
    assert DOOR_SPECS <= set(values)
    assert not [name for name, v in values.items() if v is None], values
    assert values["compiles_in_window"] == values["plan_rebuilds_in_window"] == 0.0
    # the pruner's count, from the requests' parameters and the generator's calendar (lib/prunecount.py)
    bench_dir = os.path.join(ROOT, "benchmarks")
    sys.path.insert(0, bench_dir)
    try:
        from lib import prunecount
        from lib.datagen import ssb_flat_bydate

        cal = ssb_flat_bydate.calendar()
        config = dict(cell["config"], rows=40_000, segment_rows=10_000)
        pruned = []
        for r in reqs:
            ref = cell["query_set"]["templates"][r.template]["reference"]
            dated = [dict(ref, where=[t]) for t in ref["where"] if t[0] in cal]
            shares = [prunecount.segment_shares(config, prunecount.matching_days(one, r.params, cal)) for one in dated]
            pruned.append(sum(any(s[i] == 0.0 for s in shares) for i in range(4)))
    finally:
        sys.path.remove(bench_dir)
    assert values["segments_pruned_per_query"] == pytest.approx(np.mean(pruned)) and np.mean(pruned) > 1.0
    assert values["doc_range_segments_per_query"] > 0.0 and values["prune_ms"] > 0.0
    assert 0.0 < values["launch_param_bytes_per_query"] < 4096.0
    assert 0.0 < values["bydate_roofline"] < 100.0
    # not throughput_qps: the driver holds a new cell's spread to the PARENT's median, a ninth of the change's here (PERF.md section 7)
    assert {m["name"] for m in cell["end_to_end"]} == {"latency_p50_ms", "latency_p95_ms", "setup_s"}


def test_every_metric_of_the_month_cut_cell_has_a_reader_that_returns_a_value():
    """`ssb_sf10_bymonth.dashboard_closed` (PR 50): every per-layer metric `load_cell` gives the cell, the list-less
    ones too, returns a value over a traced window of its own traffic at toy size (4 segments of 21 whole months each,
    ~10,000 rows and no two alike, sorted by `lo_orderdate`), and every end-to-end metric; and what the new names say
    hangs together: ONE compiled row count serves the four segments, every launched segment is padded to it and
    masked (`scan.traced.rowmasked`), the pruner's count is the calendar's, nothing compiles or plans again in the
    window, and the roofline's least bytes are the TRUE rows' (lib/monthcount.py)."""
    import sys

    masked = METRICS.counter("scan.traced.rowmasked").value
    values, cell, reqs, weights = _toy_window("ssb_sf10_bymonth.dashboard_closed", 40_000, 50)
    new = {"row_buckets_per_query", "padded_rows_per_query", "bymonth_roofline"}
    listed = {"compiles_in_window", "warm_up_compiles_per_template", "warm_up_s", "segments_pruned_per_query", "prune_ms",
              "doc_range_segments_per_query", "launch_param_bytes_per_query", "combined_segments_per_query",
              "table_shaped_segments_per_query", "tables_decoded_per_query", "tables_merged_by_value_per_query",
              "table_decode_cpu_ms"}
    assert new | listed | {"launches_per_query", "plan_rebuilds_in_window"} <= set(values)
    assert "bydate_roofline" not in values and "scan_roofline" not in values  # their counts are another cut's
    assert DOOR_SPECS <= set(values)
    assert not [name for name, v in values.items() if v is None], values
    assert values["compiles_in_window"] == values["plan_rebuilds_in_window"] == 0.0
    assert METRICS.counter("scan.traced.rowmasked").value > masked  # the warm-up traced programs that mask by a bound row count
    bench_dir = os.path.join(ROOT, "benchmarks")
    sys.path.insert(0, bench_dir)
    try:
        from lib import monthcount, prunecount
        from lib.datagen import ssb_flat_bymonth

        cal = ssb_flat_bymonth.calendar()
        config = dict(cell["config"], rows=40_000, segment_rows=10_000)
        counts = ssb_flat_bymonth.segment_row_counts(config)
        assert sum(counts) == 40_000 and len(set(counts)) == 4
        pruned, padded = [], []
        for r in reqs:
            ref = cell["query_set"]["templates"][r.template]["reference"]
            dated = [dict(ref, where=[t]) for t in ref["where"] if t[0] in cal]
            shares = [monthcount.segment_shares(config, prunecount.matching_days(one, r.params, cal)) for one in dated]
            gone = [any(s[i] == 0.0 for s in shares) for i in range(4)]
            pruned.append(sum(gone))
            padded.append(sum(16_384 - n for n, out in zip(counts, gone) if not out))
    finally:
        sys.path.remove(bench_dir)
    assert values["row_buckets_per_query"] == 1.0  # four row counts, one program: the table's bound, 16,384 rows
    assert values["padded_rows_per_query"] == pytest.approx(np.mean(padded))
    assert values["segments_pruned_per_query"] == pytest.approx(np.mean(pruned)) and np.mean(pruned) > 1.0
    assert values["launches_per_query"] == 1.0  # whatever survives the pruner rides one call
    assert values["doc_range_segments_per_query"] > 0.0 and values["prune_ms"] > 0.0
    assert 0.0 < values["bymonth_roofline"] < 100.0
    # not throughput_qps: as for the time-ordered cell (PERF.md section 7)
    assert {m["name"] for m in cell["end_to_end"]} == {"latency_p50_ms", "latency_p95_ms", "setup_s"}


def test_the_month_cut_configuration_is_the_time_ordered_one_in_another_cut():
    def load(name):
        with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json"), encoding="utf-8") as f:
            return json.load(f)

    month, bydate = load("ssb_flat_sf10_bymonth"), load("ssb_flat_sf10_bydate")
    changed = {"name", "source", "stands_for", "datagen", "segment_rows", "cut_seed", "columns", "guarantees", "assumed",
               "memory"}
    assert {k for k in set(month) | set(bydate) if month.get(k) != bydate.get(k)} == changed
    assert (month["datagen"], month["rows"], month["segment_rows"], -(-month["rows"] // month["segment_rows"])) == (
        "ssb_flat_bymonth", 60_000_000, 714_286, 84)
    # the columns are the sibling's but for what lo_orderdate's says of the cut
    assert [dict(c, distribution=None) for c in month["columns"]] == [dict(c, distribution=None) for c in bydate["columns"]]
    assert [c["name"] for c in month["columns"] if c not in bydate["columns"]] == ["lo_orderdate"]
    assert set(month["guarantees"]) == set(bydate["guarantees"]) | {"rows_free"}
    assert "numSegmentsQueried = 84" in month["guarantees"]["complete"]
    assert set(month["assumed"]) == set(bydate["assumed"]) | {"cut_seed"}
    assert month["assumed"]["order_dates"] == bydate["assumed"]["order_dates"]
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == "ssb_flat_sf10_bymonth"]
    assert entry["source"] == month["source"] and entry["reduced"] == month["reduced"] and len(entry["source"]) <= 200
    (cell,) = [w for w in bench["workloads"] if w["config"] == "ssb_flat_sf10_bymonth"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == ("ssb_sf10_bymonth.dashboard_closed", "bydate_closed", 1)
    assert len(bench["workloads"]) == 10 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
