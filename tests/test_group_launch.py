"""One launch a server, not one a segment (query/executor.py QueryLaunches).

A query's segments whose plans share one compiled kernel ride ONE jitted
call (planner.grouped_plan: the members' columns joined on the device, the
kernel scanned over them) with their parameter buffers stacked on the host,
and come back in ONE fetch; the per-segment decode and everything above it
are unchanged.  Where the members are dense group-bys over ONE key space
whose fields combine by name, the program folds their tables into ONE before
the fetch (the server's combine, on the chip: section 7 below) and the group
has one result.  These tests hold the answers bit-equal to the per-segment
launch's (with the kernel interpreted and 32-bit accumulation, the chip's
path, as tests/test_ssb_templates_chip_path.py steers it; a dense and a
SPARSE group-by among them), the grouping of a
mixed scan list, the widths' ladder, the first launch of a group program on
each device, `ServerInstance.warm`, cancellation between groups, and an
upsert segment in a group.
"""
import dataclasses

import jax
import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.cluster.admission import QueryKilledError, ResourceBudget
from pinot_tpu.cluster.server import ServerInstance
from pinot_tpu.ops import segmented
from pinot_tpu.query import executor, planner, reduce
from pinot_tpu.query.functions import combine_field
from pinot_tpu.query.result import DenseGroupData, ExecutionStats, GroupBySegmentResult
from pinot_tpu.query.safety import Deadline, QueryTimeoutError
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.residency import ResidencyManager
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
from pinot_tpu.utils.metrics import METRICS, Trace

N = 3000
POOL = ["ams", "ber", "cph", "dub", "edi", "fra", "gva", "hel", "ist"]
PER_SEGMENT = 6  # cities a segment holds: one cardinality, so one plan; another six a segment
SCHEMA = Schema(
    "t",
    [
        FieldSpec("year", DataType.INT),
        FieldSpec("qty", DataType.INT),
        FieldSpec("city", DataType.STRING),
        FieldSpec("rev", DataType.LONG, role=FieldRole.METRIC),
    ],
)


def _block(i: int, rows: int = N):
    rng = np.random.default_rng(100 + i)
    cities = [POOL[(i + k) % len(POOL)] for k in range(PER_SEGMENT)]  # segment i lacks POOL[i-3 .. i-1]
    city = rng.choice(cities, rows).astype(object)
    city[:PER_SEGMENT] = cities  # every one of them present
    return {
        "year": rng.integers(1992, 1999, rows).astype(np.int32),
        "qty": rng.integers(1, 51, rows).astype(np.int32),
        "city": city,
        "rev": rng.integers(1, 10**7, rows),
    }


BLOCKS = [_block(i) for i in range(5)]

# name -> (SQL, its rows' mask over a block, GROUP BY column)
QUERIES = {
    "aggregation": (
        "SELECT COUNT(*), SUM(rev) FROM t WHERE city IN ('cph', 'dub', 'edi') AND qty < 30",
        lambda b: np.isin(b["city"], ["cph", "dub", "edi"]) & (b["qty"] < 30), None,
    ),
    "groupby_dense": (
        "SELECT city, year, COUNT(*), SUM(rev) FROM t WHERE city <> 'edi' AND qty BETWEEN 5 AND 40 GROUP BY city, year",
        lambda b: (b["city"] != "edi") & (b["qty"] >= 5) & (b["qty"] <= 40), ("city", "year"),
    ),
    # the same table past maxDenseGroups: sort + slot scatter (planner.sparse_grouped_tables), which PR 29
    # left untested in a group on the chip's arithmetic
    "groupby_sparse": (
        "SET maxDenseGroups = 16; SELECT city, year, COUNT(*), SUM(rev) FROM t WHERE city <> 'edi' AND qty BETWEEN 5 AND 40 "
        "GROUP BY city, year",
        lambda b: (b["city"] != "edi") & (b["qty"] >= 5) & (b["qty"] <= 40), ("city", "year"),
    ),
}


def _reference(block, mask, group_cols):
    if group_cols is None:
        return int(mask.sum()), int(block["rev"][mask].sum())
    out = {}
    for key, rev in zip(zip(*(block[c][mask] for c in group_cols)), block["rev"][mask]):
        count, total = out.get(key, (0, 0))
        out[key] = (count + 1, total + int(rev))
    return out


def _answer(res):
    count, total = res.partials
    if not hasattr(res, "keys"):
        return int(count["count"]), int(total["sum"])
    return {
        tuple(k): (int(c), int(s)) for k, c, s in zip(zip(*res.keys), count["count"], total["sum"])
    }


def _same(a, b):
    """Two segment results, field for field and bit for bit."""
    if dataclasses.is_dataclass(a):
        return type(a) is type(b) and all(
            _same(getattr(a, f.name), getattr(b, f.name)) for f in dataclasses.fields(a)
        )
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_same(x, y) for x, y in zip(a, b))
    if isinstance(a, (np.ndarray, np.generic, jax.Array)):
        a, b = np.asarray(a), np.asarray(b)
        return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(a, b)
    return a == b


def _fold(results):
    """Per-segment dense group-by results over one key space as the ONE a
    combined group ships: the reduce's aligned merge (identity, then every
    member in order, field by field), every present group kept."""
    d0 = results[0].dense
    presence = np.zeros_like(d0.presence)
    partials = [{f: np.full_like(a, reduce._ident_like(f, a)) for f, a in p.items()} for p in d0.partials]
    for r in results:
        assert r.dense.key_space == d0.key_space
        presence = presence + r.dense.presence
        for mine, theirs in zip(partials, r.dense.partials):
            for f in mine:
                mine[f] = combine_field(f, mine[f], np.asarray(theirs[f]))
    present = np.nonzero(presence > 0)[0]
    return GroupBySegmentResult(
        keys=planner.decode_packed_keys(d0.group_dims, present),
        partials=[{f: a[present] for f, a in p.items()} for p in partials],
        dense=DenseGroupData(presence, partials, d0.key_space, d0.group_dims),
    )


def _same_result(a, b):
    """_same, but for a dense result's `group_dims` (the planner's objects,
    one list a plan: told apart by what they decode to, which `key_space` says)."""
    strip = lambda r: dataclasses.replace(r, dense=dataclasses.replace(r.dense, group_dims=[]))
    return _same(strip(a), strip(b))


def _spans(node, out=None):
    out = {} if out is None else out
    out.setdefault(node["name"], []).append(node)
    for c in node.get("children", []):
        _spans(c, out)
    return out


def _group_programs():
    return METRICS.snapshot()["counters"].get("compile.group.programs", 0)


@pytest.fixture(scope="module")
def chip_path():
    """scan_backend() = "interpret", accum_policy() = "chunked32" for this
    module's plans; the plan cache does not key on the accumulation policy,
    so it is emptied on the way in and on the way out."""
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


@pytest.fixture(scope="module")
def segments():
    return [build_segment(SCHEMA, b, f"seg{i}") for i, b in enumerate(BLOCKS)]


def _launch_all(ctx, segs, **kw):
    launches = executor.QueryLaunches(ctx, **kw)
    for seg in segs:
        launches.add(seg)
    launches.flush()
    return launches


# ---------------------------------------------------------------------------
# (1) the answers: grouped against width 1, bit for bit, on the chip's path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", list(QUERIES))
def test_grouped_launch_equals_the_per_segment_launch_bit_for_bit(name, chip_path, segments):
    sql, mask_of, group_cols = QUERIES[name]
    ctx = parse_query(sql)
    kernel = METRICS.counter("scan.traced.interpret").value
    one_by_one = [executor.execute_segment(ctx, seg)[0] for seg in segments]

    trace = Trace(True)
    launches = _launch_all(ctx, segments, trace=trace)
    assert (launches.calls, launches.grouped_segments) == (2, 4)  # 5 = 4 + 1
    (wide, lone), _, _ = zip(*launches._states)
    plans = wide[3]
    assert all(p.kind == name and p.fn is plans[0].fn for p in plans)
    # per-segment dictionaries: the members' parameters really differ, so the stack carries five
    assert len({tuple(np.concatenate([np.ravel(v) for v in p.params.values()]).tolist()) for p in plans}) > 1
    grouped = [res for res, _ in launches.collect()]

    assert all(_same(a, b) for a, b in zip(grouped, one_by_one))
    assert [_answer(r) for r in grouped] == [_reference(b, mask_of(b), group_cols) for b in BLOCKS]
    if name == "groupby_dense":  # the kernel's, interpreted (traced once: the group program maps the same jitted kernel)
        assert METRICS.counter("scan.traced.interpret").value == kernel + 1
    if name == "groupby_sparse":  # sorted, not scattered by key; traced once as well
        assert METRICS.counter("scan.traced.sparse_sort").value == 1
    enqueues = _spans(trace.finish())["launch_enqueue"]
    assert [(e["attrs"]["segments"], e["attrs"]["width"]) for e in enqueues] == [(4, 4), (1, 1)]


# ---------------------------------------------------------------------------
# (2) a mixed scan list
# ---------------------------------------------------------------------------
def test_mixed_scan_list_forms_the_right_groups_and_the_same_rows(segments):
    """Seven named segments: four of one plan around one of another row
    count, one whose star-tree's level answers and one an upsert segment (a plan of
    its own: it reads `__valid__`); a second query's literal prunes three."""
    star_cfg = TableConfig(name="t", indexing=IndexingConfig(star_tree_index_configs=[{
        "dimensionsSplitOrder": ["city", "year"], "functionColumnPairs": ["COUNT__*", "SUM__rev"],
        "maxLeafRecords": 100}]))
    other = build_segment(SCHEMA, _block(1, rows=N + 500), "other_rows")
    star = build_segment(SCHEMA, BLOCKS[2], "star", table_config=star_cfg)
    upsert = build_segment(SCHEMA, BLOCKS[0], "upsert")
    upsert.valid_docs = np.random.default_rng(3).random(N) < 0.6
    pruned = build_segment(SCHEMA, _block(2), "no_ber")  # POOL[2..7]: no 'ber'; nor has seg4 (POOL[4..8] + 'ams')
    scan = [segments[0], other, star, segments[1], upsert, pruned, segments[4]]
    # a NOT, which the pruner never reads through (an OR of EQs it prunes like an IN since PR 47)
    sql = "SELECT year, COUNT(*), SUM(rev) FROM t WHERE NOT (city != 'ber' AND city != 'ams') GROUP BY year"
    sql_pruning = "SELECT year, COUNT(*), SUM(rev) FROM t WHERE city = 'ber' GROUP BY year"

    server = ServerInstance("s")
    for seg in scan:
        server.add_segment("t", seg)
    names = [seg.name for seg in scan]

    ctx = parse_query("SET trace = true; " + sql)
    results, stats = server.execute(ctx, names)
    assert (stats.num_segments_queried, stats.num_segments_pruned, stats.num_segments_processed) == (7, 0, 7)
    spans = _spans(stats.trace)
    plans = spans["launch_plan"]
    assert len(plans) == 7 and all(n["attrs"]["cache"] in ("hit", "miss") for n in plans)
    # the star-tree's level is planned like a segment (PR 37: it was `cache` = "startree", answered on the host)
    level = star.indexes["startree"]["st0"].levels[2]
    assert [(n["attrs"]["star"], n["attrs"]["level"]) for n in plans if "star" in n["attrs"]] == [("st0", 2)]
    assert [n["attrs"].get("levelRows") for n in spans["launch:star"]] == [level.num_rows]
    assert all("cpuMs" in n["attrs"] for name, nodes in spans.items() if name.startswith("launch:") for n in nodes)
    assert spans["dispatch"][0]["attrs"]["starSegments"] == 1
    # seg0, other_rows, seg1, no_ber, seg4 share a kernel (PR 50: a segment of another row count is padded to
    # the table's rows and masked, segment/table_shape.py; it was a kernel and a launch of its own): 4 + 1 of the
    # ladder; upsert and the star-tree's level are alone
    assert spans["dispatch"][0]["attrs"]["rowBuckets"] == 2  # the table's rows, and the level's bucket
    assert sorted(n["attrs"]["segments"] for n in spans["launch_enqueue"]) == [1, 1, 1, 4]
    assert spans["device_wait"][0]["attrs"]["launches"] == spans["dispatch"][0]["attrs"]["launches"] == 4
    assert sum(n["attrs"]["segments"] for n in spans["collect"]) == 7
    assert sum(n["attrs"]["docs"] for n in spans["collect"]) == 6 * N + 500 + level.num_rows
    # the same rows, in the order the segments were named
    untraced = parse_query(sql)
    alone = [executor.execute_segment(untraced, seg)[0] for seg in scan]
    # the first four that share a kernel share their dictionaries of `year` too: the chip combined their tables,
    # the ONE result stands at the first one's place; seg4, the ladder's 1, is a launch and a result of its own
    assert spans["dispatch"][0]["attrs"]["combinedSegments"] == 4
    assert server.metrics.snapshot()["counters"]["server.combinedSegments"] == 4
    assert [r is None for r in results] == [False, True, False, True, False, True, False]
    assert _same_result(results[0], _fold([alone[i] for i in (0, 1, 3, 5)]))
    assert all(_same(results[i], alone[i]) for i in (2, 4, 6))
    decodes = {n["attrs"]["tables"]: n["attrs"]["groups"] for n in spans["table_decode"]}
    assert sorted(n["attrs"]["tables"] for n in spans["table_decode"]) == [1, 1, 1, 1] and decodes[1] == 7

    results, stats = server.execute(parse_query(sql_pruning), names)
    assert (stats.num_segments_queried, stats.num_segments_pruned, stats.num_segments_processed) == (7, 3, 4)
    kept = [seg for seg in scan if seg.name not in ("star", "no_ber", "seg4")]  # BLOCKS[2] is POOL[2..7] too
    alone = [executor.execute_segment(parse_query(sql_pruning), seg)[0] for seg in kept]  # seg0, other_rows, seg1, upsert
    assert [r is None for r in results] == [False, True, False, False]  # 2 + 1 of the ladder: a lone member folds nothing
    assert _same_result(results[0], _fold(alone[:2])) and _same(results[2], alone[2]) and _same(results[3], alone[3])


# ---------------------------------------------------------------------------
# (3) widths come from a ladder
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n, cap, want", [
    (40, 8, [8, 8, 8, 8, 8]), (37, 8, [8, 8, 8, 8, 4, 1]), (5, 8, [4, 1]), (4, 8, [4]), (7, 2, [2, 2, 2, 1]),
    (3, 1, [1, 1, 1]), (0, 8, []), (1, 8, [1]), (40, 32, [32, 8]), (63, 32, [32, 16, 8, 4, 2, 1]),
])
def test_ladder(n, cap, want):
    assert executor._ladder(n, cap) == want


@pytest.mark.parametrize("member_bytes, cache_bytes, want", [
    (12e6, None, 8),  # an SF10 segment's 8 B/row: the widest
    (12e6, 8 << 30, 8),  # the default cache holds it too
    (200e6, None, 4),  # 1 GiB of joined columns holds five: four
    (2e9, None, 1),
    (12e6, 64e6, 1),  # a cache of five such segments: a quarter of it holds one
    (12e6, 200e6, 4),
    (0.0, None, 8),  # a kernel that reads no column
])
def test_group_cap_follows_the_bytes(member_bytes, cache_bytes, want):
    residency = None if cache_bytes is None else ResidencyManager(ResourceBudget(int(cache_bytes)))
    assert executor.group_cap(member_bytes, residency) == want


def test_pruning_moves_the_member_count_and_compiles_nothing_beyond_the_ladder(segments):
    planner.plan_cache_clear()
    server = ServerInstance("s")
    for seg in segments:
        server.add_segment("t", seg)
    names = [seg.name for seg in segments]

    def ask(city):
        before = (_group_programs(), METRICS.snapshot()["counters"].get("compile.sse.compiles", 0),
                  server.metrics.snapshot()["counters"].get("server.launches", 0),
                  server.metrics.snapshot()["counters"].get("server.groupedSegments", 0))
        _, stats = server.execute(parse_query(f"SELECT COUNT(*), SUM(rev) FROM t WHERE city = '{city}' AND qty > 3"), names)
        after = (_group_programs(), METRICS.snapshot()["counters"].get("compile.sse.compiles", 0),
                 server.metrics.snapshot()["counters"]["server.launches"],
                 server.metrics.snapshot()["counters"]["server.groupedSegments"])
        return stats, tuple(a - b for a, b in zip(after, before))

    # 'fra' = POOL[5] is in every segment: five members, 4 + 1; one plan, one group program (x4)
    stats, moved = ask("fra")
    assert stats.num_segments_pruned == 0 and moved == (1, 1, 2, 4) and stats.compile_ms > 0
    # 'edi' = POOL[4] is in all five too: other literal, nothing new
    stats, moved = ask("edi")
    assert stats.num_segments_pruned == 0 and moved == (0, 0, 2, 4) and stats.compile_ms == 0.0
    # 'gva' = POOL[6] is missing from seg0 (POOL[0..5]): four members, the x4 program again; and the shape's
    # first query that PRUNES has the server make the ladder's other program (x2) before it returns (PR 47:
    # executor.warm_widths; the plan's own program ran as the lone member above)
    warmups = METRICS.counter("compile.sse.widthWarmups").value
    stats, moved = ask("gva")
    assert stats.num_segments_pruned == 1 and moved == (1, 0, 1, 4) and stats.compile_ms == 0.0
    assert METRICS.counter("compile.sse.widthWarmups").value == warmups + 1
    # 'ams' = POOL[0] is in seg0 and seg4 alone: x2, the ladder's next, made ahead of need: nothing compiles
    stats, moved = ask("ams")
    assert stats.num_segments_pruned == 3 and moved == (0, 0, 1, 2) and stats.compile_ms == 0.0
    assert METRICS.counter("compile.sse.widthWarmups").value == warmups + 1
    (entry,) = [p for p, _, _ in planner._PLAN_CACHE._entries.values()]
    assert sorted(entry.widened) == [(2, False), (4, False)] and max(entry.widened)[0] <= executor.MAX_GROUP_WIDTH


def test_a_server_paging_a_small_cache_launches_at_the_width_its_window_holds():
    """Tiered residency with a cache that holds three and a half of the five
    segments' columns (whole 2^15-row blocks, so the packed lanes carry no
    padding and the plan's bytes are the staged bytes): a quarter of it
    holds no second segment, so every launch is of width 1, and segment k+1
    is still prefetched behind k."""
    rows = 1 << 15
    blocks = [_block(i, rows) for i in range(5)]
    segs = [build_segment(SCHEMA, b, f"big{i}") for i, b in enumerate(blocks)]
    ctx = parse_query("SELECT COUNT(*), SUM(rev) FROM t WHERE qty < 30")
    plan = planner.plan_segment(ctx, segs[0])
    staged = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
        segs[0].to_device(columns=plan.needed_columns, packed_codes=True)))
    segs[0].evict_device(None)
    assert plan.scan_bytes <= staged <= 1.1 * plan.scan_bytes
    residency = ResidencyManager(ResourceBudget(int(3.5 * staged)), name="residency.small")
    assert executor.group_cap(plan.scan_bytes, residency) == 1
    server = ServerInstance("s", residency=residency)
    for seg in segs:
        server.add_segment("t", seg)
    prefetched, real = [], residency.submit
    residency.submit = lambda fn, *a, **kw: prefetched.append(kw["prefetch"]) or real(fn, *a, **kw)
    try:
        results, stats = server.execute(ctx, [seg.name for seg in segs])
        snap = residency.snapshot()
    finally:
        residency.shutdown()
    assert server.metrics.snapshot()["counters"]["server.launches"] == 5
    assert server.metrics.snapshot()["counters"].get("server.groupedSegments", 0) == 0
    # handed to the staging thread behind each segment but the last (whether it or the launch
    # stages a segment first is a race), and the table did page through the cache
    assert prefetched == [True] * 4 and snap["evictions"] >= 1
    assert [_answer(r) for r in results] == [_reference(b, b["qty"] < 30, None) for b in blocks]


def test_nothing_is_handed_to_the_staging_thread_for_columns_the_device_holds():
    """A cache that holds the table: the first query stages segment k+1
    behind k, as above; from then on every column it reads is resident and
    the server hands the staging thread NOTHING (PR 38: forty tasks and
    forty wake-ups a query were the largest waste on a Q1 query's host path
    once its device time fell).  A query that reads another column stages
    again."""
    blocks = [_block(i) for i in range(5)]
    residency = ResidencyManager(ResourceBudget(1 << 30), name="residency.roomy")
    server = ServerInstance("s", residency=residency)
    for i, b in enumerate(blocks):
        server.add_segment("t", build_segment(SCHEMA, b, f"roomy{i}"))
    names = [f"roomy{i}" for i in range(5)]
    handed, real = [], residency.submit
    residency.submit = lambda fn, *a, **kw: handed.append(tuple(kw["columns"])) or real(fn, *a, **kw)
    ctx = parse_query("SELECT COUNT(*), SUM(rev) FROM t WHERE qty < 30")
    try:
        first, _ = server.execute(ctx, names)
        assert len(handed) == 4  # behind each segment but the last
        del handed[:]
        again, _ = server.execute(ctx, names)
        assert handed == []
        other, _ = server.execute(parse_query("SELECT COUNT(*), SUM(rev) FROM t WHERE year = 1994"), names)
        assert len(handed) == 4 and all("year" in cols for cols in handed)
    finally:
        residency.shutdown()
    want = [_reference(b, b["qty"] < 30, None) for b in blocks]
    assert [_answer(r) for r in first] == want and [_answer(r) for r in again] == want
    assert [_answer(r) for r in other] == [_reference(b, b["year"] == 1994, None) for b in blocks]


# ---------------------------------------------------------------------------
# (4) first launch: per (group program, device); `warm` compiles what the served call runs
# ---------------------------------------------------------------------------
def test_first_launch_is_per_group_program_and_device(segments):
    planner.plan_cache_clear()
    ctx = parse_query("SELECT year, COUNT(*), SUM(rev) FROM t WHERE qty < 20 GROUP BY year")
    d0, d1 = jax.devices()[1], jax.devices()[2]
    four = segments[:4]
    for device, want_first in [(d0, True), (d0, False), (d1, True), (d1, False), (d0, False)]:
        trace, hooked = Trace(True), []
        launches = _launch_all(ctx, four, device=device, trace=trace, on_first_launch=lambda: hooked.append(1))
        answers = launches.collect()
        (enqueue,) = _spans(trace.finish())["launch_enqueue"]
        assert enqueue["attrs"]["width"] == 4
        assert enqueue["attrs"].get("firstLaunch", False) == want_first, (device, enqueue)
        assert ("compileMs" in enqueue["attrs"]) == want_first
        assert hooked == ([1] if want_first else [])  # called before the compile, and only then
        # the compile lands on the group's first member
        assert [s.compile_ms > 0 for _, s in answers] == [want_first, False, False, False]
        assert all(leaf.devices() == {device} for leaf in jax.tree_util.tree_leaves(launches.outputs()))
    (entry,) = [p for p, _, _ in planner._PLAN_CACHE._entries.values()]
    assert entry.launched_on == {}  # the plan's own program never ran: the group program keeps its own record
    assert set(entry.widened[4, True].launched_on) == {d0, d1}  # a dense group-by over one key space: the combining program
    # a lone segment is the width-1 case: the plan's own program, its own first launch
    (_, stats) = executor.collect_segment(executor.launch_segment(ctx, segments[4], device=d0))
    assert stats.compile_ms > 0 and set(entry.launched_on) == {d0}


def test_warm_compiles_the_program_the_served_call_then_finds_warm(segments):
    planner.plan_cache_clear()
    server = ServerInstance("peer", device=jax.devices()[4])
    for seg in segments:
        server.add_segment("t", seg)
    names = [seg.name for seg in segments]
    sql = "SELECT COUNT(*), SUM(rev) FROM t WHERE year > 1994 AND qty > 3"
    server.warm(parse_query(sql), names)
    snap = server.metrics.snapshot()
    assert snap["timers"]["server.compileMs"]["count"] == 1 and "server.queries" not in snap["counters"]
    hooked = []
    _, stats = server.execute(parse_query("SET trace = true; " + sql), names, on_first_launch=lambda: hooked.append(1))
    enqueues = _spans(stats.trace)["launch_enqueue"]
    assert [e["attrs"]["width"] for e in enqueues] == [4, 1]
    assert not hooked and stats.compile_ms == 0.0 and not any("firstLaunch" in e["attrs"] for e in enqueues)
    assert server.metrics.snapshot()["timers"]["server.compileMs"]["count"] == 1


# ---------------------------------------------------------------------------
# (5) a deadline or a kill between groups abandons the rest
# ---------------------------------------------------------------------------
class _ExpiresAfter(Deadline):
    """A deadline that expires after it has been asked `n` times."""

    def __init__(self, n):
        super().__init__(1e9)
        self.left = n

    def expired(self):
        self.left -= 1
        return self.left < 0


@pytest.mark.parametrize("how", ["deadline", "kill"])
@pytest.mark.parametrize("asked, where, fetched, abandoned", [
    (3, "planning_the_fourth_segment", 0, 0),
    (6, "before_the_second_call", 0, 1),
    (7, "before_the_first_fetch", 0, 2),
    (11, "before_the_second_fetch", 1, 1),
])
def test_deadline_or_kill_between_groups_abandons_the_rest(how, asked, where, fetched, abandoned, segments):
    """Five segments, 4 + 1.  The probe is asked before each segment is
    planned (asks 1-5), before each jitted call (6, 7), before each fetch
    (8, 12) and before each member's decode after a group's first (9-11);
    it says no on the ask after `asked`."""
    server = ServerInstance("s")
    for seg in segments:
        server.add_segment("t", seg)
    names = [seg.name for seg in segments]
    ctx = parse_query("SELECT COUNT(*), SUM(rev) FROM t WHERE qty < 25")
    server.execute(ctx, names)  # warm
    fetches = []
    real = jax.device_get
    launched0 = server.metrics.snapshot()["counters"]["server.launches"]
    cancelled0 = METRICS.snapshot()["counters"].get("server.launchesCancelled", 0)
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "device_get", lambda x: fetches.append(1) or real(x))
    try:
        if how == "deadline":
            with pytest.raises(QueryTimeoutError, match=f"{abandoned} pending"):
                server.execute(ctx, names, deadline=_ExpiresAfter(asked))
        else:
            probes = iter([None] * asked + ["watchdog"])
            with pytest.raises(QueryKilledError, match=f"{abandoned} pending"):
                server.execute(ctx, names, cancel=lambda: next(probes))
    finally:
        mp.undo()
    assert len(fetches) == fetched  # abandoning is never collecting: no further fetch, no sync
    assert METRICS.snapshot()["counters"].get("server.launchesCancelled", 0) - cancelled0 == abandoned
    assert server.metrics.snapshot()["counters"]["server.launches"] == launched0  # counted when a query answers


def test_a_kill_before_a_members_decode_abandons_the_groups_left(segments):
    server = ServerInstance("s")
    for seg in segments:
        server.add_segment("t", seg)
    ctx = parse_query("SELECT COUNT(*), SUM(rev) FROM t WHERE qty < 25")
    decoded = []
    real = executor._decode_host
    mp = pytest.MonkeyPatch()
    mp.setattr(executor, "_decode_host", lambda *a: decoded.append(a[1].name) or real(*a))
    probes = iter([None] * 9 + ["watchdog"])  # 7 to launch, the first fetch, the second member's decode
    try:
        with pytest.raises(QueryKilledError, match="2 pending launch"):  # the group being decoded and the lone one
            server.execute(ctx, [seg.name for seg in segments], cancel=lambda: next(probes, None))
    finally:
        mp.undo()
    assert decoded == ["seg0", "seg1"]


# ---------------------------------------------------------------------------
# (6) an upsert segment in a group
# ---------------------------------------------------------------------------
def test_upsert_segments_ride_one_call_each_with_its_own_valid_mask():
    rng = np.random.default_rng(9)
    segs, valids = [], []
    for i, block in enumerate(BLOCKS[:4]):
        seg = build_segment(SCHEMA, block, f"up{i}")
        seg.valid_docs = rng.random(N) < 0.3 + 0.15 * i
        valids.append(seg.valid_docs.copy())
        segs.append(seg)
    ctx = parse_query("SELECT COUNT(*), SUM(rev) FROM t WHERE qty >= 10")
    launches = _launch_all(ctx, segs)
    assert (launches.calls, launches.grouped_segments) == (1, 4)
    ((state, _, _),) = launches._states
    assert all(planner.VALID_KEY in p.params for p in state[3])
    got = [_answer(res) for res, _ in launches.collect()]
    assert got == [_reference(b, v & (b["qty"] >= 10), None) for b, v in zip(BLOCKS, valids)]
    # an invalidation between queries applies without a new program
    segs[2].valid_docs[:] = False
    programs = _group_programs()
    got = [_answer(res) for res, _ in _launch_all(ctx, segs).collect()]
    assert got[2] == (0, 0) and _group_programs() == programs


def test_selection_masks_stack_too(segments):
    """A selection's kernel returns bool[num_docs] a segment: it stacks like
    any other output, and the host-side gather is each member's own."""
    ctx = parse_query("SELECT city, rev FROM t WHERE qty = 7 AND year = 1995 LIMIT 100000")
    launches = _launch_all(ctx, segments)
    assert launches.calls == 2
    grouped = [res for res, _ in launches.collect()]
    assert all(_same(a, executor.execute_segment(ctx, seg)[0]) for a, seg in zip(grouped, segments))
    assert [len(r.arrays["rev"]) for r in grouped] == [int(((b["qty"] == 7) & (b["year"] == 1995)).sum()) for b in BLOCKS]


# ---------------------------------------------------------------------------
# (7) the server's combine, on the chip: a group of dense group-bys over ONE key space ships ONE table
# ---------------------------------------------------------------------------
SHARED = Schema(
    "u",
    [
        FieldSpec("year", DataType.INT),
        FieldSpec("shop", DataType.INT),
        FieldSpec("item", DataType.INT),
        FieldSpec("qty", DataType.INT),
        FieldSpec("rev", DataType.LONG, role=FieldRole.METRIC),
    ],
)
SHOPS = ITEMS = 100  # 7 x 100 x 100 slots: past the one-hot kernel's 8,192, a wide table under chunked32


def _shared_block(i: int, rows: int = N):
    """Every segment holds every year, shop and item: one dictionary a column across segments, so
    one key space."""
    rng = np.random.default_rng(700 + i)
    block = {
        "year": rng.integers(1992, 1999, rows).astype(np.int32),
        "shop": rng.integers(0, SHOPS, rows).astype(np.int32),
        "item": rng.integers(0, ITEMS, rows).astype(np.int32),
        "qty": rng.integers(1, 51, rows).astype(np.int32),
        "rev": rng.integers(1, 10**7, rows),
    }
    block["year"][:7] = np.arange(1992, 1999)
    block["shop"][:SHOPS] = np.arange(SHOPS)
    block["item"][:ITEMS] = np.arange(ITEMS)
    return block


@pytest.fixture(scope="module")
def shared_segments():
    return [build_segment(SHARED, _shared_block(i), f"sh{i}") for i in range(13)]


DENSE_SQL = "SELECT year, COUNT(*), SUM(rev), MIN(qty), MAX(qty) FROM u WHERE qty BETWEEN 3 AND 44 GROUP BY year"


def _collect_combined(ctx, segs, widths, **kw):
    """`segs` launched as calls of `widths`, every call of more than one
    member folding into ONE table between them (a later call takes the
    earlier one's table on the device): [(a result, the per-segment results
    of the members it stands for)], the combined one first, and the launches."""
    launches = _launch_all(ctx, segs, **kw)
    together = sum(w for w in widths if w > 1)
    assert launches.calls == len(widths) and launches.combined_segments == together
    assert [(len(slots), calls) for _, slots, calls in launches._states] == (
        [(together, sum(1 for w in widths if w > 1))] + [(1, 1)] * widths.count(1)
    )
    fetches, real = [], jax.device_get
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "device_get", lambda x: fetches.append(1) or real(x))
    try:
        answers = launches.collect()
    finally:
        mp.undo()
    assert len(fetches) == 1 + widths.count(1) and launches.uncollected == 0
    alone = [executor.execute_segment(ctx, seg)[0] for seg in segs]
    assert [res is None for res, _ in answers] == [False] + [True] * (together - 1) + [False] * widths.count(1)
    # every member's stats are kept: what was scanned is per segment and still sums
    assert [st.num_docs_scanned for _, st in answers] == [seg.num_docs for seg in segs]
    return [(answers[0][0], alone[:together])] + [(answers[i][0], [alone[i]]) for i in range(together, len(segs))], launches


@pytest.mark.parametrize("n, widths", [(2, [2]), (4, [4]), (8, [8]), (13, [8, 4, 1])])
def test_a_combined_group_is_the_fold_of_its_members_bit_for_bit(n, widths, chip_path, shared_segments):
    """count / int SUM / MIN / MAX on the chip's arithmetic (kernel
    interpreted, 32-bit accumulation): the ONE table a group ships equals the
    host's fold of its members' tables, field for field and bit for bit."""
    ctx = parse_query(DENSE_SQL)
    kernel = METRICS.counter("scan.traced.interpret").value
    programs = _group_programs()
    groups, launches = _collect_combined(ctx, shared_segments[:n], widths)
    for got, members in groups:
        assert _same_result(got, _fold(members) if len(members) > 1 else members[0])
    # the kernel's body is traced ONCE however many programs scan it (the carry's types come from the
    # jitted kernel's cached trace), and a width has ONE program
    assert METRICS.counter("scan.traced.interpret").value - kernel <= 1
    assert _group_programs() - programs <= sum(1 for w in widths if w > 1)
    merged = _fold([m for _, members in groups for m in members])
    want = {}
    for b in (_shared_block(i) for i in range(n)):
        m = (b["qty"] >= 3) & (b["qty"] <= 44)
        for y in range(1992, 1999):
            sel = m & (b["year"] == y)
            c, s, lo, hi = want.get(y, (0, 0, 99, 0))
            want[y] = (c + int(sel.sum()), s + int(b["rev"][sel].sum()), min(lo, int(b["qty"][sel].min())), max(hi, int(b["qty"][sel].max())))
    count, total, low, high = merged.partials
    got = {int(y): (int(c), int(s), int(lo), int(hi)) for y, c, s, lo, hi in
           zip(merged.keys[0], count["count"], total["sum"], low["min"], high["max"])}
    assert got == want


def test_a_wide_table_combines(chip_path, shared_segments):
    """70,000 slots: past the one-hot kernel, the wide scatter's table
    (ops/segmented._wide_group_tables under chunked32) folds like any other."""
    ctx = parse_query("SELECT year, shop, item, COUNT(*), SUM(rev) FROM u WHERE qty < 30 GROUP BY year, shop, item LIMIT 100000")
    wide = METRICS.counter("scan.traced.wide_scatter").value
    ((got, members),), launches = _collect_combined(ctx, shared_segments[:4], [4])
    plan = launches._states[0][0][3][0]
    assert plan.kind == "groupby_dense" and plan.num_groups == 7 * SHOPS * ITEMS > 8192
    assert METRICS.counter("scan.traced.wide_scatter").value > wide
    assert got.dense.presence.shape == (plan.num_groups,)
    assert _same_result(got, _fold(members))


def test_a_star_tree_level_group_combines_and_restores_once(shared_segments):
    from pinot_tpu.query.startree import StarRewrite

    cfg = TableConfig(name="u", indexing=IndexingConfig(star_tree_index_configs=[{
        "dimensionsSplitOrder": ["year", "shop"], "functionColumnPairs": ["COUNT__*", "SUM__rev"],
        "maxLeafRecords": 100}]))
    segs = [build_segment(SHARED, _shared_block(i), f"st{i}", table_config=cfg) for i in range(4)]
    ctx = parse_query("SELECT year, COUNT(*), SUM(rev) FROM u WHERE shop < 60 GROUP BY year")
    alone = [executor.execute_segment(ctx, seg)[0] for seg in segs]
    restored, real = [], StarRewrite.restore
    mp = pytest.MonkeyPatch()
    mp.setattr(StarRewrite, "restore", lambda self, res: restored.append(1) or real(self, res))
    try:
        launches = _launch_all(ctx, segs)
        assert (launches.calls, launches.star_segments, launches.combined_segments) == (1, 4, 4)
        answers = launches.collect()
    finally:
        mp.undo()
    assert restored == [1]
    assert [res is None for res, _ in answers] == [False, True, True, True]
    assert _same_result(answers[0][0], _fold(alone))
    # the same numbers as the scan's, by the tree's own rows
    scan = parse_query("SET useStarTree = false; SELECT year, COUNT(*), SUM(rev) FROM u WHERE shop < 60 GROUP BY year")
    scanned = [executor.execute_segment(scan, seg)[0] for seg in segs]
    assert _answer(answers[0][0]) == _answer(_fold(scanned))


def test_upsert_segments_combine_each_under_its_own_valid_mask(shared_segments):
    rng = np.random.default_rng(11)
    segs, valids = [], []
    for i in range(4):
        seg = build_segment(SHARED, _shared_block(i), f"uv{i}")
        seg.valid_docs = rng.random(N) < 0.3 + 0.15 * i
        valids.append(seg.valid_docs.copy())
        segs.append(seg)
    ctx = parse_query("SELECT year, COUNT(*), SUM(rev) FROM u WHERE qty >= 10 GROUP BY year")
    ((got, members),), launches = _collect_combined(ctx, segs, [4])
    assert all(planner.VALID_KEY in p.params for p in launches._states[0][0][3])
    assert _same_result(got, _fold(members))
    want = {}
    for i, v in enumerate(valids):
        b = _shared_block(i)
        for (y,), (c, s) in _reference(b, v & (b["qty"] >= 10), ("year",)).items():
            want[(y,)] = (want.get((y,), (0, 0))[0] + c, want.get((y,), (0, 0))[1] + s)
    assert _answer(got) == want


@pytest.mark.parametrize("n", [4, 5])  # ONE combined result: the reduce's `len(results) == 1`; 4 + 1: its aligned merge
@pytest.mark.parametrize("sql", [
    "SELECT shop, COUNT(*), SUM(rev) FROM u WHERE qty < 40 GROUP BY shop HAVING SUM(rev) > 470000000 ORDER BY SUM(rev) DESC LIMIT 7",
    "SELECT shop, year, MAX(qty), COUNT(*) FROM u GROUP BY shop, year HAVING COUNT(*) > 20 ORDER BY COUNT(*) DESC, shop, year LIMIT 9",
    "SET numGroupsLimit = 5; SELECT shop, SUM(rev) FROM u WHERE qty < 40 GROUP BY shop ORDER BY SUM(rev) DESC LIMIT 50",
    "SET numGroupsLimit = 5; SELECT shop, COUNT(*) FROM u GROUP BY shop LIMIT 200",
])
def test_the_reduced_answer_is_the_aligned_merges_whichever_branch_reduces_it(sql, n, shared_segments):
    """ORDER BY + LIMIT + HAVING, and numGroupsLimit below the merged group
    count: a combined group's table holds every group its members' tables
    held, as the aligned merge of those tables does (it never trimmed), so
    the rows and `numGroups` are the per-segment launch's."""
    ctx = parse_query(sql)
    segs = shared_segments[:n]
    launches = _launch_all(ctx, segs)
    assert launches.combined_segments == 4
    grouped = [res for res, _ in launches.collect()]
    assert sum(r is not None for r in grouped) == n - 3
    alone = [executor.execute_segment(ctx, seg)[0] for seg in segs]
    assert all(r.dense is not None for r in alone)
    got = reduce.reduce_results(ctx, grouped, ExecutionStats())
    want = reduce.reduce_results(ctx, alone, ExecutionStats())  # five tables over one key space: the aligned path
    assert got.rows == want.rows and len(got.rows) > 0
    assert got.stats.num_groups == want.stats.num_groups > (5 if "numGroupsLimit" in sql else 0)


def _parents_group_program(base, width):
    """planner.grouped_plan's program as the parent of this PR built it, word
    for word: what every group that does not combine must still run."""
    import jax.numpy as jnp

    kernel = base.fn

    def group(cols, packed):
        leaves, treedefs = zip(*(jax.tree_util.tree_flatten(c) for c in cols))
        with jax.named_scope("group_stack"):
            takes = [planner._join(xs) for xs in zip(*leaves)]

        def member(_, at):
            i, params = at
            mine = jax.tree_util.tree_unflatten(treedefs[0], [take(i) for take in takes])
            return (), kernel(mine, params)

        members = (jnp.arange(width, dtype=jnp.int32), packed)
        return jax.lax.scan(member, (), members, length=width)[1]

    group.__name__ = group.__qualname__ = f"{base.kind}_{base.cache_key[2]}_x{width}"
    return jax.jit(group)


def _group_args(plans, segs):
    cols = tuple(seg.to_device(columns=p.needed_columns, packed_codes=True) for p, seg in zip(plans, segs))
    return cols, {k: np.stack([p.params[k] for p in plans]) for k in plans[0].params}


FALL_BACKS = {
    "different_dictionaries": (QUERIES["groupby_dense"][0], "groupby_dense"),
    "pairwise_merge": ("SELECT year, LASTWITHTIME(rev, qty, 'LONG') FROM t GROUP BY year", "groupby_dense"),
    # an own-scatter function whose cells are a segment's own codes (HLL's registers and a
    # percentile's bins hash or bin VALUES and do combine: tests/test_sketch_served.py)
    "sketch": ("SELECT year, DISTINCTCOUNT(qty) FROM t GROUP BY year", "groupby_dense"),
    "sparse": (QUERIES["groupby_sparse"][0], "groupby_sparse"),
    "scalar_aggregation": (QUERIES["aggregation"][0], "aggregation"),
    "selection": ("SELECT city, rev FROM t WHERE qty = 7 LIMIT 100000", "selection"),
}


@pytest.mark.parametrize("name", list(FALL_BACKS))
def test_what_does_not_combine_keeps_the_parents_program(name, segments):
    """Members with different dictionaries, a pairwise merge, a code-indexed sketch, a
    sparse plan, a scalar aggregation, a selection: the stacked program, its
    text the parent's (so the persistent cache's entries still load: the
    Q1.x cell's), a result a member, `combinedSegments` 0."""
    sql, kind = FALL_BACKS[name]
    ctx = parse_query("SET trace = true; " + sql)
    server = ServerInstance("s")
    four = segments[:4]
    for seg in four:
        server.add_segment("t", seg)
    results, stats = server.execute(ctx, [seg.name for seg in four])
    assert all(r is not None for r in results)
    assert _spans(stats.trace)["dispatch"][0]["attrs"]["combinedSegments"] == 0
    assert server.metrics.snapshot()["counters"]["server.combinedSegments"] == 0
    plans = [planner.plan_segment(ctx, seg) for seg in four]
    base = plans[0]
    assert base.kind == kind and all(p.fn is base.fn for p in plans)
    assert (4, False) in base.widened and (4, True) not in base.widened
    args = _group_args(plans, four)
    ours = planner.grouped_plan(base, 4).fn.lower(*args).as_text()
    assert ours == _parents_group_program(base, 4).lower(*args).as_text()
    # every output keeps its leading member axis
    assert all(leaf.shape[0] == 4 for leaf in jax.tree_util.tree_leaves(jax.eval_shape(planner.grouped_plan(base, 4).fn, *args)))


def test_a_combining_program_returns_one_table_and_no_member_axis(shared_segments):
    """The HLO guard's other half: a dense group-by's combining program has
    no [width, slots] output (nor a stacked table anywhere in its text),
    where the stacked program of the same plan has one a field."""
    ctx = parse_query(DENSE_SQL)
    eight = shared_segments[:8]
    plans = [planner.plan_segment(ctx, seg) for seg in eight]
    base = plans[0]
    assert planner.combines(base) and len({executor._key_space_id(p) for p in plans}) == 1
    args = _group_args(plans, eight)
    slots = base.num_groups
    combined = planner.grouped_plan(base, 8, True)
    assert combined is not planner.grouped_plan(base, 8) and combined is planner.grouped_plan(base, 8, True)
    assert combined.fn.__name__.endswith("_x8_combined")
    # what a query's first call folds into: presence, COUNT's count; SUM's, MIN's and MAX's field each beside its count
    start = planner.identity_tables(combined, base.fn, (args[0][0], base.params), None)
    assert start is planner.identity_tables(combined, base.fn, (args[0][0], base.params), None)  # made once
    presence, partials = start
    assert not presence.any() and [sorted(p) for p in partials] == [["count"], ["count", "sum"], ["count", "min"], ["count", "max"]]
    assert float(partials[2]["min"][0]) == np.inf and float(partials[3]["max"][0]) == -np.inf and not partials[1]["sum"].any()
    shapes = jax.tree_util.tree_leaves(jax.eval_shape(combined.fn, *args, start))
    assert len(shapes) == 1 + 1 + 2 + 2 + 2 and {s.shape for s in shapes} == {(slots,)}
    stacked = f"tensor<8x{slots}x"
    assert stacked not in combined.fn.lower(*args, start).as_text()
    assert stacked in planner.grouped_plan(base, 8).fn.lower(*args).as_text()


@pytest.mark.parametrize("how", ["deadline", "kill"])
@pytest.mark.parametrize("asked, where, fetched, abandoned", [
    (8, "before_the_first_call", 0, 0),
    (14, "before_the_second_call", 0, 1),
    (16, "before_the_one_fetch_of_the_combined_calls", 0, 3),
    (17, "before_the_lone_segments_fetch", 1, 1),
])
def test_deadline_or_kill_between_combining_calls_abandons_the_rest(how, asked, where, fetched, abandoned, shared_segments):
    """Thirteen segments of one key space, 8 + 4 + 1: the probe is asked
    before each segment is planned (asks 1-8, 10-14; the eighth fills a
    group, which launches at once: ask 9), before the calls the flush makes
    (15, 16), and before each FETCH (17: the two combining calls' one table;
    18: the lone segment's); the combined table has one decode, so nothing
    is asked between members.  What is abandoned is counted in CALLS."""
    server = ServerInstance("s")
    for seg in shared_segments:
        server.add_segment("u", seg)
    names = [seg.name for seg in shared_segments]
    ctx = parse_query(DENSE_SQL)
    server.execute(ctx, names)  # warm
    fetches, real = [], jax.device_get
    cancelled0 = METRICS.snapshot()["counters"].get("server.launchesCancelled", 0)
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "device_get", lambda x: fetches.append(1) or real(x))
    try:
        if how == "deadline":
            with pytest.raises(QueryTimeoutError, match=f"{abandoned} pending"):
                server.execute(ctx, names, deadline=_ExpiresAfter(asked))
        else:
            probes = iter([None] * asked + ["watchdog"])
            with pytest.raises(QueryKilledError, match=f"{abandoned} pending"):
                server.execute(ctx, names, cancel=lambda: next(probes))
    finally:
        mp.undo()
    assert len(fetches) == fetched
    assert METRICS.snapshot()["counters"].get("server.launchesCancelled", 0) - cancelled0 == abandoned
