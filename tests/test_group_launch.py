"""One launch a server, not one a segment (query/executor.py QueryLaunches).

A query's segments whose plans share one compiled kernel ride ONE jitted
call (planner.grouped_plan: the members' columns joined on the device, the
kernel scanned over them) with their parameter buffers stacked on the host,
and come back in ONE fetch; the per-segment decode and everything above it
are unchanged.  These tests hold the answers bit-equal to the per-segment
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
from pinot_tpu.query import executor, planner
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
    (wide, lone), _ = zip(*launches._states)
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
    sql = "SELECT year, COUNT(*), SUM(rev) FROM t WHERE city = 'ber' OR city = 'ams' GROUP BY year"
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
    # seg0, seg1, no_ber, seg4 share a kernel; other_rows, upsert and the star-tree's level are alone
    assert sorted(n["attrs"]["segments"] for n in spans["launch_enqueue"]) == [1, 1, 1, 4]
    assert spans["device_wait"][0]["attrs"]["launches"] == spans["dispatch"][0]["attrs"]["launches"] == 4
    assert sum(n["attrs"]["segments"] for n in spans["collect"]) == 7
    assert sum(n["attrs"]["docs"] for n in spans["collect"]) == 6 * N + 500 + level.num_rows
    # the same rows, in the order the segments were named
    untraced = parse_query(sql)
    assert all(_same(got, executor.execute_segment(untraced, seg)[0]) for got, seg in zip(results, scan))

    results, stats = server.execute(parse_query(sql_pruning), names)
    assert (stats.num_segments_queried, stats.num_segments_pruned, stats.num_segments_processed) == (7, 3, 4)
    kept = [seg for seg in scan if seg.name not in ("star", "no_ber", "seg4")]  # BLOCKS[2] is POOL[2..7] too
    assert all(_same(got, executor.execute_segment(parse_query(sql_pruning), seg)[0]) for got, seg in zip(results, kept))


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
    # 'gva' = POOL[6] is missing from seg0 (POOL[0..5]): four members, the x4 program again
    stats, moved = ask("gva")
    assert stats.num_segments_pruned == 1 and moved == (0, 0, 1, 4) and stats.compile_ms == 0.0
    # 'ams' = POOL[0] is in seg0 and seg4 alone: x2, the ladder's next, and that is all a plan can add
    stats, moved = ask("ams")
    assert stats.num_segments_pruned == 3 and moved == (1, 0, 1, 2) and stats.compile_ms > 0
    (entry,) = [p for p, _, _ in planner._PLAN_CACHE._entries.values()]
    assert sorted(entry.widened) == [2, 4] and max(entry.widened) <= executor.MAX_GROUP_WIDTH


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
    assert set(entry.widened[4].launched_on) == {d0, d1}
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
    ((state, _),) = launches._states
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
