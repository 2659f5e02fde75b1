"""Segment execution: prune -> plan -> kernel launch -> segment result.

Reference parity: ServerQueryExecutorV1Impl.executeInternal
(pinot-core/.../query/executor/ServerQueryExecutorV1Impl.java:161,316) —
acquire segments, server-side pruning (SegmentPrunerService, value/bloom
pruners), per-segment plan execution — and the per-segment hot loop of
SURVEY.md 3.1.

Re-design: "execution" is one jitted kernel call per GROUP of a query's
segments that share a compiled kernel (planner.py; QueryLaunches here);
this module owns the host-side halves: pruning from metadata before any
launch, the grouping, and the post-kernel decode per segment (dense group
table -> present keys, the sparse-groupby host fallback, selection row
gather)."""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.ops.code_lookup import CONTRACTED, GATHERED, RESIDENT
from pinot_tpu.query import planner
from pinot_tpu.utils.metrics import Trace
from pinot_tpu.query.functions import combine_field
from pinot_tpu.query.ir import Expr, QueryContext
from pinot_tpu.query.transform import eval_expr_host
from pinot_tpu.query.result import (
    AggSegmentResult,
    DenseGroupData,
    ExecutionStats,
    GroupBySegmentResult,
    SelectionSegmentResult,
)
from pinot_tpu.segment.segment import ImmutableSegment


# ---------------------------------------------------------------------------
# Pruning (SegmentPrunerService analog — entirely host-side, metadata only)
# ---------------------------------------------------------------------------
def prune_segment(ctx: QueryContext, segment: ImmutableSegment, planning: Optional[planner.QueryPlanning] = None) -> bool:
    """True if the segment provably matches no rows (value/bloom pruner):
    planner.QueryPlanning.prunes.  `planning` is the query's, where the
    caller asks about more than one segment: a predicate's verdict is then
    resolved once a query per distinct dictionary, and a surviving segment's
    plan binds with the same resolution."""
    return (planning if planning is not None else planner.QueryPlanning(ctx)).prunes(segment)


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------
# A group program is compiled for a power-of-two number of members, so a plan
# has at most log2(MAX_GROUP_WIDTH) programs a form (stacked, combining)
# beside its own however a query's pruning moves its member count: 40
# segments launch as 5 x 8, 37 as 4 x 8 + 4 + 1.  A table whose queries all
# scan every segment meets the same widths in every query and compiles them
# at its first; where the pruner leaves another count a query (a table cut by
# time), the first query of a shape that prunes has the server make the
# narrower programs, the other form and the shape's other kernels (the
# segments it pruned may hold another signature) before it returns
# (warm_widths), so no later query compiles.  Eight, not more, by
# measurement on the chip (PERF.md, PR 29): a
# server's 40 segments then need ONE group program a plan where 32 + 8 need
# two, and a program's cost is its set-up (compile: ~1 s at 8 and 2.6-4.2 s
# at 32 for a dense group-by, 6-7 s either way for Q1; trace, lower and load
# from a warm cache: ~0.7 s), which every process pays for every query shape;
# 32 + 8 answered 5-6 % more queries a second and took 2.4-2.6 x the parent's
# cold warm-up and 12-17 % more of a warm start's set-up.
MAX_GROUP_WIDTH = 8
# The group program joins its members' columns into one array a column on the
# device before it scans the kernel over them (planner._join): a temporary of
# `SegmentPlan.scan_bytes` a member, held to 1/16 of a v5e's 16 GB.  A plan
# whose segment is larger takes narrower groups.
GROUP_STACK_BYTES = 1 << 30


def group_cap(member_bytes: float, residency=None) -> int:
    """The widest group (a power of two) of members that each read
    `member_bytes` of resident columns: MAX_GROUP_WIDTH, held to
    GROUP_STACK_BYTES of joined columns, and, where HBM is a cache
    (segment/residency.py), to a quarter of that cache's budget, because a
    group's members must be resident all at once, beside the segment
    prefetching behind them and what other queries hold: a server paging a
    table larger than its cache launches at the width its window holds,
    down to 1."""
    room = GROUP_STACK_BYTES
    if residency is not None:
        room = min(room, residency.budget.budget_bytes // 4)
    fits = max(1, int(room // max(member_bytes, 1.0)))
    return min(MAX_GROUP_WIDTH, 1 << (fits.bit_length() - 1))


@dataclass
class _Member:
    """One planned segment of a query, its columns resident: what a launch
    takes a group of.  `table` is what the plan reads: the segment, or the
    star-tree level that serves the query for it (indexes/startree.py
    LevelSegment), then with `rewrite` set (query/startree.py StarRewrite:
    the plan is the rewritten query's, `rewrite.ctx`)."""

    table: ImmutableSegment
    plan: planner.SegmentPlan
    cols: Dict
    stats: ExecutionStats
    rewrite: Optional[object] = None


def _plan_member(ctx, segment, device, residency, trace, planning=None) -> _Member:
    """A launch's per-segment stages, as spans of `trace`: launch_plan (the
    star-tree's level selection + the plan cache: dictionary look-ups are
    per segment; `planning` is the query's planner.QueryPlanning where the
    caller plans more than one segment, so the query's half is derived once;
    attr `cache` = hit / miss, on a hit `bind` = recipe / rebuild, `shape` =
    table where the plan's kernel was compiled for the dictionary sizes the
    table's segments share and this segment's own is smaller, else segment
    (planner.compiled_dict_sizes), and where
    a star-tree level is what was planned `star` = the tree's name and
    `level`) and launch_ship (the plan's columns looked up in, or staged
    into, the device's cache, nothing else: no device array is made for a
    parameter; attr paramArrays counts the host buffers that carry them, one
    per dtype, planner.pack_params).  Returns the _Member to launch."""
    if planning is None:
        planning = planner.QueryPlanning(ctx)
    with trace.span("launch_plan", segment=segment.name) as psp:
        table, asked = planning.source(segment)
        plan = asked.plan(table)
        if psp is not None:
            if plan.cache_hit:
                psp.annotate(cache="hit", bind=plan.bind)
            else:
                psp.annotate(cache="miss")
            psp.annotate(shape="table" if plan.table_shaped else "segment")
            if table is not segment:
                psp.annotate(star=table.tree, level=table.level)

    stats = ExecutionStats(
        num_segments_queried=1,
        num_segments_processed=1,
        num_docs_scanned=segment.num_docs if table is segment else table.level_rows,
        total_docs=segment.num_docs,
    )
    stats.filter_index_uses = tuple(plan.index_uses)
    if table is not segment:
        stats.add_index_uses([(table.prefix, "startree")])
    stats.kernel_bytes = plan.scan_bytes
    with trace.span("launch_ship", segment=segment.name, params=len(plan.param_layout)) as ssp:
        cols = table.to_device(
            device=device, columns=plan.needed_columns, packed_codes=True,
            residency=residency, dict_rows=plan.dict_sizes, value_columns=plan.value_columns, rows=plan.rows,
        )
        if ssp is not None:
            ssp.annotate(paramArrays=len(plan.params))
    return _Member(table, plan, cols, stats, asked.rewrite)


def _combines_as(members: List[_Member]) -> Optional[Tuple]:
    """What a group of `members` must share with another for the chip to
    fold the dense group tables of both into one, or None where it folds
    nothing: the compiled kernel and ONE key space (the group columns'
    dictionaries, by fingerprint), for a plan whose fields combine by name
    (planner.combines) and more than one member."""
    base = members[0].plan
    if len(members) == 1 or not planner.combines(base):
        return None
    spaces = {_key_space_id(m.plan) for m in members}
    return (id(base.fn), spaces.pop()) if len(spaces) == 1 else None


def _launch_group(ctx, members: List[_Member], device, trace, on_first_launch=None, combined=None, before=None):
    """ONE jitted call for `members`, whose plans share one compiled kernel
    (`plan.fn` is one object) and differ in their parameters' values: the
    launch's one trip into the runtime (span launch_enqueue, attrs
    `segments` = members, `width` = what the program was compiled for).  A
    lone member calls `plan.fn(cols, params)` itself; N call the plan's
    program of that width (planner.grouped_plan) with the members' resident
    column pytrees as a tuple, nothing re-staged, and their packed parameter
    buffers stacked on the host to [N, n], riding the call as a lone
    member's ride theirs.  With `combined` (the caller's _combines_as of the
    members: dense group-bys over ONE key space whose fields combine by
    name) the program folds their tables into ONE before the fetch: the
    server's combine, on the chip.  `before` is then the state of the
    query's earlier groups that combine as the same, if any: this call
    folds into THEIR table (a device array; nothing is fetched) and the
    state returned holds both calls' members and the one table.
    Returns the pending state collect_group takes:
    the outputs carry a leading member axis when N > 1 and not combined; the
    query is the one
    the plans were made from, so the rewritten one where the members are
    star-tree levels (all of a group are: they share a kernel), and the
    state's last items are then the rewrite that restores their answers, and
    what the members combine as (None: a result a member)."""
    rewrite = members[0].rewrite
    if rewrite is not None:
        ctx = rewrite.ctx
    base = members[0].plan
    width = len(members)
    if width == 1:
        program, args = base, (members[0].cols, base.params)
    else:
        program = planner.grouped_plan(base, width, combined is not None)
        args = (
            tuple(m.cols for m in members),
            {k: np.stack([m.plan.params[k] for m in members]) for k in base.params},
        )
        if combined is not None:
            args += (
                before[4] if before is not None
                else planner.identity_tables(program, base.fn, (members[0].cols, base.params), device),
            )
    counted = {}
    if trace.enabled:
        import jax

        # the array leaves the jitted call is handed, which its dispatch walks every call: a resident entry a
        # column a member, the stacked parameter buffers, the carried tables of a combining call
        counted["operands"] = (
            sum(len(entry) for m in members for entry in m.cols.values()) + len(args[1])
            + (len(jax.tree_util.tree_leaves(args[2])) if combined is not None else 0)
        )
    out, members[0].stats.compile_ms = _enqueue(
        trace, program, args, device, on_first_launch,
        segments=width, width=width, kind=base.kind, backend=base.cache_key[2],
        # the host operands the call ships: the members' packed parameters
        paramBytes=sum(int(v.nbytes) for v in args[1].values()), **counted,
    )
    tables, plans, stats = [m.table for m in members], [m.plan for m in members], [m.stats for m in members]
    if combined is not None and before is not None:
        tables, plans, stats = before[2] + tables, before[3] + plans, before[5] + stats
    return ("pending", ctx, tables, plans, out, stats, rewrite, combined)


def launch_segment(
    ctx: QueryContext, segment: ImmutableSegment, device=None, residency=None,
    trace: Optional[Trace] = None, on_first_launch=None,
):
    """Phase 1 of pipelined execution: plan, ship inputs, and DISPATCH the
    segment kernel (jax dispatch is asynchronous — the call returns as soon
    as the work is enqueued).  Returns an opaque pending state for
    collect_segment.  The group launch's width-1 case: the stages are
    _plan_member's and _launch_group's.

    `on_first_launch` (zero-arg) is called just before the jitted call when
    that call will compile, i.e. the program has not run on this device yet:
    the broker uses it to start the same compile on the table's other
    servers' devices while this one runs."""
    trace = trace if trace is not None else Trace()
    member = _plan_member(ctx, segment, device, residency, trace)
    return _launch_group(ctx, [member], device, trace, on_first_launch)


def _ladder(n: int, cap: int) -> List[int]:
    """`n` members as widths a group program is compiled for: the largest
    power of two that fits under `cap`, then the rest the same way."""
    widths = []
    while n:
        w = 1 << (min(n, cap).bit_length() - 1)
        widths.append(w)
        n -= w
    return widths


class QueryLaunches:
    """One query's launches on one device: one jitted call a GROUP of its
    segments, not one a segment, and one fetch a group.

    `add` plans a segment (span `launch:<segment>` over its launch_plan and
    launch_ship) and files it with the segments whose plans resolved to the
    same compiled kernel.  A group that has reached its cap launches at once,
    so the device works while the host plans the rest (the pipeline
    SURVEY.md 2.5 asks for: a per-segment launch had it between segments,
    a group launch has it between groups); `flush` launches what is left,
    in widths from the ladder.  The cap is what the code can see, and no
    option (group_cap): the bytes the plan's columns take against what the
    program's joined columns may take and, under tiered residency, against what the
    cache can hold at once (the caller still prefetches segment k+1 while k
    is planned).  A plan of another kernel and a lone segment are groups of
    their own.  A segment whose star-tree serves the query is a member like
    any other: what it plans, ships and launches is the tree's level
    (planner.QueryPlanning.source), the levels of a table's segments share
    a kernel and ride one call a group, and the span says so (`levelRows`:
    the level's true rows, beside `cpuMs` and `kernelBytes`).

    `check` (zero-arg, raises to abandon the query: the deadline, a kill) is
    called before each segment is planned, before each jitted call, and
    before each member's decode: abandoning means never collecting (jax
    dispatch is async; nothing syncs back).  `collect` gives the answers in
    the order the segments were added."""

    def __init__(self, ctx: QueryContext, device=None, residency=None,
                 trace: Optional[Trace] = None, on_first_launch=None, check=None,
                 planning: Optional[planner.QueryPlanning] = None):
        self.ctx = ctx
        self.device = device
        self.residency = residency
        self.trace = trace if trace is not None else Trace()
        self.on_first_launch = on_first_launch
        self.check = check if check is not None else (lambda: None)
        self.calls = 0  # jitted calls made
        self.grouped_segments = 0  # segments that shared a call
        self.kernel_bytes = 0.0
        self.uncollected = 0  # launched groups not yet fetched
        self.sparse_groups = 0  # groups the collected sparse tables held, summed over segments
        self.star_segments = 0  # segments a star-tree level answered for
        self.star_level_rows = 0  # the true rows of those levels
        self.combined_segments = 0  # segments whose dense tables the chip folded into their group's one
        self.table_shaped_segments = 0  # segments whose kernel was compiled for the table's shape, not their own
        self.contracted_lookups = 0  # table-by-code lookups of the launched segments' programs read by a one-hot contraction
        self.gathered_lookups = 0  # and by a gather a row (ops/code_lookup.py)
        self.resident_lookups = 0  # and not at all: the dictionary column came decoded from staging (SegmentPlan.value_columns)
        self.compacted_scatters = 0  # compactions in the launched segments' programs: a filtered mask's passing rows sorted first (ops.mask_facts)
        self.row_buckets: set = set()  # the row counts the launched segments' kernels were compiled for
        self.rows_padded = 0  # those counts less the segments' true rows, summed: rows scanned and masked
        self.doc_range_segments = 0  # segments whose plan answers a sorted column's predicate with a doc range
        self.index_served = 0  # predicates answered from a range / inverted index's bitmaps
        self.index_scanned = 0  # predicates on a column with such an index that scanned its codes (filter.bitmap_serves)
        self.shape_fp: Optional[str] = None  # the first planned segment's shape fingerprint: the query shape's name
        self._added = 0
        # the query's half of its plans, derived once (the caller's, where it
        # already asked it for the columns the query reads)
        self.planning = planning if planning is not None else planner.QueryPlanning(ctx)
        self._open: Dict[int, List[Tuple[int, _Member]]] = {}  # id(plan.fn) -> (slot, member)
        self._states: List[Tuple[Tuple, List[int], int]] = []  # (state, its members' slots, its calls), in launch order
        self._combining: Dict[Tuple, int] = {}  # what a state's members combine as -> its place in _states

    def add(self, segment: ImmutableSegment) -> None:
        self.check()
        slot, self._added = self._added, self._added + 1
        with self.trace.span(f"launch:{segment.name}", cpu=True, segment=segment.name) as lsp:
            member = _plan_member(
                self.ctx, segment, self.device, self.residency, self.trace, self.planning
            )
        plan = member.plan
        self.kernel_bytes += plan.scan_bytes
        self.table_shaped_segments += plan.table_shaped
        self.row_buckets.add(plan.rows)
        self.rows_padded += plan.rows - planner.true_rows(member.table)
        if plan.index_uses:
            kinds = [kind for _, kind in plan.index_uses]
            self.doc_range_segments += "sorted" in kinds
            self.index_served += sum(kind in ("range", "inverted") for kind in kinds)
        self.index_scanned += len(plan.index_scans)
        if self.shape_fp is None:
            self.shape_fp = plan.cache_key[0]
        if lsp is not None:
            # beside the span's cpuMs (the rest of its wall time is waiting:
            # interpreter lock, a lock); kernelBytes is EXPLAIN ANALYZE's Bytes
            lsp.annotate(kernelBytes=member.plan.scan_bytes)
        if member.rewrite is not None:
            self.star_segments += 1
            self.star_level_rows += member.table.level_rows
            if lsp is not None:
                lsp.annotate(levelRows=member.table.level_rows)
        group = self._open.setdefault(id(member.plan.fn), [])
        group.append((slot, member))
        if len(group) >= group_cap(member.plan.scan_bytes, self.residency):
            del self._open[id(member.plan.fn)]
            self._launch(group)

    def flush(self) -> None:
        for group in self._open.values():
            at = 0
            for width in _ladder(len(group), group_cap(group[0][1].plan.scan_bytes, self.residency)):
                self._launch(group[at : at + width])
                at += width
        self._open = {}

    def _launch(self, group: List[Tuple[int, _Member]]) -> None:
        self.check()
        members, slots = [m for _, m in group], [slot for slot, _ in group]
        combined = _combines_as(members)
        # the query's earlier groups of this kernel and key space: this one folds into their table
        at = self._combining.get(combined)
        state = _launch_group(
            self.ctx, members, self.device, self.trace, self.on_first_launch,
            combined, None if at is None else self._states[at][0],
        )
        if at is not None:
            self._states[at] = (state, self._states[at][1] + slots, self._states[at][2] + 1)
        else:
            self._states.append((state, slots, 1))
            if combined is not None:
                self._combining[combined] = len(self._states) - 1
        self.calls += 1
        self.uncollected += 1
        if len(group) > 1:
            self.grouped_segments += len(group)
        if combined is not None:
            self.combined_segments += len(group)
        lookups = members[0].plan.lookups  # the kernel's, traced by now: every member runs it
        self.contracted_lookups += len(group) * lookups.get(CONTRACTED, 0)
        self.gathered_lookups += len(group) * lookups.get(GATHERED, 0)
        self.resident_lookups += len(group) * lookups.get(RESIDENT, 0)
        self.compacted_scatters += len(group) * members[0].plan.mask_facts.compactions

    def outputs(self) -> list:
        """Device outputs of every launched group: what a tracing caller
        fences on with ONE jax.block_until_ready, to split device compute
        time from host dispatch (never per launch: a fence in the loop would
        serialize the pipeline, lint W002)."""
        return [state[4] for state, _, _ in self._states]

    def collect(self) -> List[Tuple]:
        """(segment result, ExecutionStats) of every added segment, in the
        order added: one `collect` span and one fetch a group.  The groups
        whose tables the chip combined have ONE fetch and ONE result between
        them, at their first member's place, and None at the others'; every
        member keeps its stats."""
        answers: List = [None] * self._added
        for state, slots, calls in self._states:
            self.check()
            with self.trace.span("collect", cpu=True, segments=len(slots)) as csp:
                for slot, answer in zip(slots, collect_group(state, self.check, self.trace)):
                    answers[slot] = answer
                if state[3][0].kind == "groupby_sparse":
                    self.sparse_groups += sum(answers[slot][1].num_groups for slot in slots)
            self.uncollected -= calls
            if csp is not None:
                csp.annotate(docs=sum(answers[slot][1].num_docs_scanned for slot in slots))
        return answers


_WARM_THREADS = 4  # first launches made at once ahead of need: a compile releases the interpreter lock


def warm_widths(ctx: QueryContext, segments: List[ImmutableSegment], device=None, residency=None,
                planning: Optional[planner.QueryPlanning] = None) -> int:
    """Make ready, for `device`, every program a query of `ctx`'s SHAPE can
    launch over `segments` (ALL of the table's segments the server was asked
    about, the pruned ones too) whatever its literals leave after pruning:
    each kernel the segments' signatures resolve to (a pruned segment may
    hold another: a column sorted in one segment and not in the next), alone
    and in the widths of the ladder its segments can fill, in the stacked
    form and, where the plan's tables combine, the combining one (the form
    follows the members' key spaces: _combines_as).  A program already
    launched on `device` is left alone; each of the others runs once over
    real members, its answer dropped, so the jitted call's own cache holds
    it (an AOT compile would be paid again at the first call).  The
    parameters are `ctx`'s own: a pruned segment binds to an empty range.
    Returns the programs made (counter compile.sse.widthWarmups).  Called
    by the server once a query shape, at the first query of it that prunes
    (ServerInstance.execute), before that query returns: a table whose
    queries scan every segment never comes here."""
    from collections import Counter
    from concurrent.futures import ThreadPoolExecutor

    from pinot_tpu.utils.metrics import METRICS

    planning = planning if planning is not None else planner.QueryPlanning(ctx)
    by_kernel: Dict[int, List[Tuple[ImmutableSegment, planner.SegmentPlan]]] = {}
    for segment in segments:
        table, asked = planning.source(segment)
        plan = asked.plan(table)
        by_kernel.setdefault(id(plan.fn), []).append((segment, plan))
    tasks = []  # (segments to launch as one group, whether their tables combine)
    for group in by_kernel.values():
        base = group[0][1]
        cap = min(group_cap(base.scan_bytes, residency), len(group))
        # the segments of the commonest key space combine (where the plan's tables combine at all); a group
        # of several key spaces, or of tables that do not combine, is stacked
        spaces = Counter(_key_space_id(plan) for _, plan in group) if planner.combines(base) else Counter()
        commonest = spaces.most_common(1)[0][0] if spaces else None
        same = [seg for seg, plan in group if spaces and _key_space_id(plan) == commonest]
        mixed = len(spaces) != 1
        if device not in base.launched_on:
            tasks.append(([group[0][0]], False))
        width = 2
        while width <= cap:
            if mixed and device not in planner.grouped_plan(base, width, False).launched_on:
                tasks.append(([seg for seg, _ in group[:width]], False))
            if len(same) >= width and device not in planner.grouped_plan(base, width, True).launched_on:
                tasks.append((same[:width], True))
            width *= 2

    def first_launch(task) -> None:
        some, combine = task
        trace = Trace()
        members = [_plan_member(ctx, seg, device, residency, trace, planning) for seg in some]
        _launch_group(ctx, members, device, trace, None, _combines_as(members) if combine else None)

    if tasks:
        with ThreadPoolExecutor(max_workers=min(_WARM_THREADS, len(tasks)), thread_name_prefix="warm-width") as pool:
            list(pool.map(first_launch, tasks))
        METRICS.counter("compile.sse.widthWarmups").inc(len(tasks))
    return len(tasks)


def _enqueue(trace, plan, args, device, on_first_launch=None, **attrs):
    """A launch's jitted call, `plan.fn(*args)`, inside its `launch_enqueue`
    span (`attrs` are the span's), and the one place that knows a first
    launch.  Where `device` is not in `plan.launched_on` the call will trace
    and compile before it enqueues, so `on_first_launch` is called first,
    the call's wall time is recorded there as the compile time (an AOT
    compile would pay it a second time) and the span says `firstLaunch` /
    `compileMs`.  The span ends with its child launch_release (an empty
    block: the launch holds no device array of its own, so there is nothing
    to drop).  Returns the asynchronously dispatched output
    (device_get happens at collect) and the compile ms this call paid, 0.0
    on a warm launch."""
    launched_on = plan.launched_on
    first = device not in launched_on
    if first and on_first_launch is not None:
        on_first_launch()
    compile_ms = 0.0
    with trace.span("launch_enqueue", cpu=True, **attrs) as esp:
        t0 = time.perf_counter()
        with _placed_on(device):
            out = plan.fn(*args)
        if first:
            compile_ms = launched_on[device] = (time.perf_counter() - t0) * 1000.0
            if esp is not None:
                esp.annotate(firstLaunch=True, compileMs=round(compile_ms, 3))
        with trace.span("launch_release"):
            pass  # nothing of the launch's own to drop
    return out, compile_ms


def _placed_on(device):
    """Where a jitted call runs when no committed argument says.  Parameters
    are host numpy (uncommitted), and the kernel may read no column at all
    (COUNT(*) over an upsert segment reads `__valid__` only; unused
    arguments pin nothing), so the caller's device is made the call's
    default: thread-local, no transfer.  Every call of a `plan.fn` goes
    through here (_enqueue), so one plan sees one argument form and one
    trace context."""
    import jax

    return jax.default_device(device) if device is not None else contextlib.nullcontext()


def collect_group(state, check=None, trace: Optional[Trace] = None):
    """Phase 2: ONE jax.device_get for the group's outputs (the fence, and
    the launch's one trip back), then the host-side decode a member on its
    slice of the leading member axis, or ONE decode where the chip combined
    the members' tables (_launch_group): that result is the first member's
    and the others' is None.  Returns [(result, stats)] a member,
    and calls `check` (QueryLaunches) before each decode after the first.
    A group-by's decodes sit in a span `table_decode` of `trace`: the
    fetched tables' bytes (`tableBytes`), of which the vector fields' (a
    sketch's [groups, m] registers or bins: `sketchBytes`, 0 for scalar
    fields), the tables decoded (`tables`), the slots of a table (`keySpace`:
    the dense key space, or the sparse table's fixed size) and the groups
    the decoded tables held (`groups`)."""
    import jax

    _, ctx, segments, plans, out, stats_list, rewrite, combined = state
    host = jax.device_get(out)
    answers = []
    plan = plans[0]
    one = combined is not None  # ONE table came back for all the members
    table = trace is not None and plan.kind.startswith("groupby")
    with trace.span("table_decode", cpu=True, kind=plan.kind) if table else contextlib.nullcontext() as tsp:
        for i, (segment, member_plan, stats) in enumerate(zip(segments, plans, stats_list)):
            if one and i:
                answers.append((None, stats))
                continue
            if i and check is not None:
                check()
            member = host if one or len(segments) == 1 else jax.tree_util.tree_map(lambda a: a[i], host)
            answer = _decode_host(ctx, segment, member_plan, member, stats, not one)
            if rewrite is not None:  # a star-tree level's answer, under the query's own aggregations
                answer = (rewrite.restore(answer[0]), stats)
            answers.append(answer)
        if tsp is not None:
            slots = np.ndim(host[0])  # the presence / key table's axes: a field with more holds a vector a slot
            tsp.annotate(
                tableBytes=sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(host)),
                sketchBytes=sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(host[1]) if np.ndim(a) > slots),
                tables=1 if one else len(segments),
                keySpace=plan.num_groups if plan.kind == "groupby_dense" else min(plan.num_groups, ctx.num_groups_limit),
                groups=sum(stats.num_groups for stats in stats_list),
            )
    return answers


def collect_segment(state):
    """Phase 2 of launch_segment: block on the kernel's outputs and finish
    host-side."""
    (answer,) = collect_group(state)
    return answer


def _decode_host(ctx, segment, plan, host, stats, trim=True):
    """Host-side decode of one member's (already fetched) kernel outputs.
    `trim` False: a dense table that is already several segments' combine
    keeps every group, as the reduce's aligned merge of those segments'
    tables does (numGroupsLimit bounds what ONE segment tracks)."""
    if plan.kind == "aggregation":
        partials = [fn.host_partial(p) for fn, p in zip(plan.aggs, host)]
        return AggSegmentResult(partials=partials), stats

    if plan.kind == "groupby_dense":
        presence, partials = host
        dense = DenseGroupData(
            presence=presence,
            partials=partials,
            key_space=_key_space_id(plan),
            group_dims=plan.group_dims,
        )
        keys, sliced = _dense_to_present(
            plan, presence, partials, ctx.num_groups_limit if trim else None,
            order_trim=planner.order_by_agg_index(ctx),
        )
        stats.num_groups = len(keys[0]) if keys else 0
        return GroupBySegmentResult(keys=keys, partials=sliced, dense=dense), stats

    if plan.kind == "groupby_sparse":
        uniq, partials = host
        res = sparse_tables_to_result(
            plan.group_dims, plan.aggs, uniq, partials, ctx.num_groups_limit,
            order_trim=planner.order_by_agg_index(ctx),
        )
        stats.num_groups = len(res.keys[0]) if res.keys else 0
        return res, stats

    # selection
    tmask = np.asarray(host)
    return _gather_selection(ctx, plan, segment, tmask), stats


def execute_segment(ctx: QueryContext, segment: ImmutableSegment, device=None):
    """Run one query on one segment; returns (SegmentResult, ExecutionStats)."""
    return collect_segment(launch_segment(ctx, segment, device=device))


def _key_space_id(plan) -> Tuple:
    parts = []
    for gd in plan.group_dims:
        if gd.kind == "dict":
            # the stride too: a table's bound may pass the dictionary (planner.compiled_dict_sizes)
            parts.append(("dict", gd.name, gd.dictionary.fingerprint(), gd.null_code, gd.cardinality))
        else:
            parts.append(("rawint", gd.name, gd.base, gd.cardinality))
    return tuple(parts)


def _order_trim_select(aggs, partials_for, candidates_key, order_trim, limit):
    """Indices (into the candidate set) surviving an ORDER BY-aware trim:
    rank by the order aggregation's FINAL value (NaN last), tie-break by
    packed key — the TableResizer comparator analog.  Returns None when the
    order value is not rankable (object finals), signalling the caller to
    fall back to the deterministic lowest-key trim."""
    idx, asc = order_trim
    try:
        vals = np.asarray(aggs[idx].final(partials_for(idx)))
    except Exception:
        return None
    if vals.dtype == object or not np.issubdtype(vals.dtype, np.number):
        return None
    k = vals.astype(np.float64)
    if not asc:
        k = -k
    k = np.where(np.isnan(k), np.inf, k)
    sel = np.lexsort((candidates_key, k))[:limit]
    sel.sort()
    return sel


def _dense_to_present(
    plan, presence: np.ndarray, partials, num_groups_limit: Optional[int] = None,
    order_trim: Optional[Tuple[int, bool]] = None,
) -> Tuple[List[np.ndarray], List[Dict]]:
    """Dense table -> (decoded keys, partials) for present groups only.

    num_groups_limit caps TRACKED groups (the numGroupsLimit safety valve,
    InstancePlanMakerImplV2.java:100-120).  With an ORDER BY over an
    aggregate, the trim ranks groups by the comparator (TableResizer.java
    analog); otherwise lowest packed keys win (deterministic)."""
    present = np.nonzero(presence > 0)[0]
    if num_groups_limit is not None and len(present) > num_groups_limit:
        sel = None
        if order_trim is not None:
            sel = _order_trim_select(
                plan.aggs,
                lambda i: {f: np.asarray(a)[present] for f, a in partials[i].items()},
                present,
                order_trim,
                num_groups_limit,
            )
        present = present[sel] if sel is not None else present[:num_groups_limit]
    keys = planner.decode_packed_keys(plan.group_dims, present)
    sliced = [{f: np.asarray(arr)[present] for f, arr in p.items()} for p in partials]
    return keys, sliced


def sparse_tables_to_result(
    group_dims, aggs, uniq, partials, num_groups_limit: int,
    order_trim: Optional[Tuple[int, bool]] = None,
    assume_unique: bool = False,
) -> GroupBySegmentResult:
    """Decode fixed-size sparse group tables (planner.sparse_grouped_tables)
    into a GroupBySegmentResult, merging slots that share a key.

    Handles both the single-kernel shape ([K] tables, keys already unique)
    and the multi-device shape ([ndev*K] concatenated per-device tables,
    where the same key may appear on several devices — the IndexedTable
    merge the reference runs in CombineOperator).  Only table-sized arrays
    are touched; nothing here is row-length.

    assume_unique: the caller already merged duplicate keys (the device-side
    ops.merge_sparse_tables path) — keys are unique, ascending, and any
    order-aware trim has been applied; this just drops empty padding slots
    and decodes, no unique/fold pass."""
    uniq = np.asarray(uniq).reshape(-1)
    present = uniq != planner.SPARSE_EMPTY_KEY
    if assume_unique:
        u = uniq[present]
        if len(u) > num_groups_limit:  # defensive; device merge already trims
            present = present & (np.cumsum(present) <= num_groups_limit)
            u = u[:num_groups_limit]
        out = [
            {f: np.asarray(arr)[present] for f, arr in p.items()} for p in partials
        ]
        keys = planner.decode_packed_keys(group_dims, u)
        return GroupBySegmentResult(keys=keys, partials=out, dense=None)
    keys_flat = uniq[present]
    u, inverse = np.unique(keys_flat, return_inverse=True)
    if len(u) > num_groups_limit and order_trim is None:
        # numGroupsLimit safety valve (InstancePlanMakerImplV2.java:100-120):
        # lowest packed keys win — deterministic, documented trim.  With an
        # ORDER BY comparator the trim instead happens AFTER the fold below,
        # over fully merged per-group partials (TableResizer analog).
        keep = inverse < num_groups_limit
        u = u[:num_groups_limit]
        inverse = inverse[keep]
    else:
        keep = None
    n_groups = len(u)

    # Padded per-group row matrix: mat[g] lists the slot rows carrying key g
    # (-1 padding).  Duplicate keys only arise on the multi-device shape, so
    # the fold depth is <= ndev; one vectorized combine per fold level merges
    # every group at once — scalar fields, vector fields (present/hll/hist
    # [slots, W]) and pairwise-coupled partials (KMV, (t, v)) all ride it.
    counts = np.bincount(inverse, minlength=n_groups) if len(inverse) else np.zeros(n_groups, np.int64)
    maxc = int(counts.max(initial=1))
    order = np.argsort(inverse, kind="stable")
    starts = np.zeros(n_groups, dtype=np.int64)
    np.cumsum(counts[:-1], out=starts[1:] if n_groups > 1 else starts[:0])
    mat = np.full((n_groups, maxc), -1, dtype=np.int64)
    if len(order):
        col = np.arange(len(order)) - starts[inverse[order]]
        mat[inverse[order], col] = order

    first = np.maximum(mat[:, 0], 0)
    out: List[Dict[str, np.ndarray]] = []
    for fn, p in zip(aggs, partials):
        rows: Dict[str, np.ndarray] = {}
        for fname, arr in p.items():
            a = np.asarray(arr)
            a = a[present] if keep is None else a[present][keep]
            rows[fname] = a
        acc = {f: a[first] for f, a in rows.items()}
        for j in range(1, maxc):
            validj = mat[:, j] >= 0
            if not validj.any():
                break
            idx = np.maximum(mat[:, j], 0)
            other = {f: a[idx] for f, a in rows.items()}
            if getattr(fn, "pairwise_merge", False):
                merged = fn.merge(acc, other)
            else:
                merged = {f: combine_field(f, acc[f], other[f]) for f in acc}
            for f in acc:
                v = validj.reshape((-1,) + (1,) * (acc[f].ndim - 1))
                acc[f] = np.where(v, merged[f], acc[f])
        out.append(acc)

    if order_trim is not None and n_groups > num_groups_limit:
        sel = _order_trim_select(aggs, lambda i: out[i], u, order_trim, num_groups_limit)
        if sel is None:
            sel = np.arange(num_groups_limit)  # u is sorted: lowest keys
        u = u[sel]
        out = [{f: a[sel] for f, a in p.items()} for p in out]
    keys = planner.decode_packed_keys(group_dims, u)
    return GroupBySegmentResult(keys=keys, partials=out, dense=None)


def _gather_selection(ctx: QueryContext, plan, segment: ImmutableSegment, tmask: np.ndarray) -> SelectionSegmentResult:
    """Host-side row gather for selection queries, with per-segment trim
    (SelectionOnly / SelectionOrderBy operator analog)."""
    from pinot_tpu.query.ir import WindowSpec

    docids = np.nonzero(tmask)[0]
    # window functions rank/aggregate over ALL matched rows, and UNNEST
    # drops empty-MV rows AFTER gathering — per-segment trim would change
    # results for both, so it is disabled (bounded by a valve)
    has_unnest = any(
        isinstance(s, Expr) and s.kind.name == "CALL" and s.op == "unnest" for s in ctx.select_list
    )
    if ctx.windows or has_unnest:
        cap = int(ctx.options.get("maxWindowRows", 1_000_000))
        if len(docids) > cap:
            raise ValueError(f"window/unnest query matched {len(docids)} rows > maxWindowRows={cap}")
        want = len(docids)
    else:
        want = ctx.offset + ctx.limit
    if ctx.order_by:
        if len(docids) > want:
            # Per-segment trim: WITHIN one segment dict codes are sort ranks
            # (sorted dictionary), so a numeric lexsort on codes/values is a
            # correct local top-k regardless of type.  Expression keys
            # evaluate host-side over the matched rows (O(matched)).
            # lexsort's primary key is the LAST array; push
            # (value, null_rank) per order-by expr in reverse significance.
            lex_keys: List[np.ndarray] = []
            for ob in reversed(ctx.order_by):
                if ob.expr.is_column:
                    value_key, null_rank = _local_order_key(
                        segment, ob.expr.op, docids, ob.ascending, ob.nulls_last
                    )
                else:
                    value_key, null_rank = _expr_order_key(
                        segment, ob.expr, docids, ob.ascending, ob.nulls_last
                    )
                lex_keys.append(value_key)
                if null_rank is not None:
                    lex_keys.append(null_rank)
            order = np.lexsort(tuple(lex_keys))[:want]
            docids = docids[order]
    else:
        docids = docids[:want]
    arrays: Dict[str, np.ndarray] = {}

    def _decoded(name: str) -> np.ndarray:
        c = segment.column(name)
        vals = c.decoded()[docids]
        if c.nulls is not None and ctx.null_handling:
            vals = np.asarray(vals, dtype=object)
            vals[c.nulls[docids]] = None
        return vals

    def _value_array(e) -> np.ndarray:
        return _decoded(e.op) if e.is_column else eval_expr_host(e, segment, docids)

    out_keys: List[str] = []
    items = plan.select_exprs or [planner.Expr.col(n) for n in plan.select_columns]
    # window keys are indexed by position in ctx.select_list (what reduce
    # enumerates), NOT the *-expanded items index
    win_positions = iter(i for i, s in enumerate(ctx.select_list) if isinstance(s, WindowSpec))
    for i, e in enumerate(items):
        if isinstance(e, WindowSpec):
            # placeholder output slot (reduce overwrites after the global
            # merge) + the window's input arrays keyed by expr fingerprint
            key = f"__win{next(win_positions)}"
            out_keys.append(key)
            arrays[key] = np.zeros(len(docids))
            for ie in list(e.partition_by) + [o.expr for o in e.order_by] + ([e.expr] if e.expr else []):
                wkey = f"__wx_{ie.fingerprint()}"
                if wkey not in arrays:
                    arrays[wkey] = _value_array(ie)
            continue
        if e.is_column:
            out_keys.append(e.op)
            arrays[e.op] = _decoded(e.op)
            continue
        if e.kind.name == "CALL" and e.op == "unnest":
            key = f"__sel{i}"
            out_keys.append(key)
            arrays[key] = np.zeros(len(docids), dtype=object)  # filled by the explode below
            continue
        # expression select item: host evaluation over the gathered rows only
        # (O(limit), TransformOperator-on-selection analog)
        key = f"__sel{i}"
        out_keys.append(key)
        vals = eval_expr_host(e, segment, docids)
        nmask = None
        if ctx.null_handling:
            for cname in e.columns():
                cn = segment.column(cname).nulls
                if cn is not None:
                    m = cn[docids]
                    nmask = m if nmask is None else (nmask | m)
        if nmask is not None and nmask.any():
            vals = np.asarray(vals, dtype=object)
            vals[nmask] = None
        arrays[key] = vals
    # Cross-segment merge needs real VALUES for order columns (codes are
    # segment-local); reduce.py re-sorts the concatenated trimmed rows.
    for i, ob in enumerate(ctx.order_by):
        arrays[f"__ord{i}"] = _value_array(ob.expr)
    cols = out_keys + [f"__ord{i}" for i in range(len(ctx.order_by))]
    cols += sorted(k for k in arrays if k.startswith("__wx_"))

    # UNNEST(mvcol): explode each gathered row once per element (the MSE
    # UnnestOperator analog on the selection path; zero-length rows drop)
    unnest_keys = [
        (k, e)
        for k, e in zip(out_keys, items)
        if isinstance(e, planner.Expr) and e.kind.name == "CALL" and e.op == "unnest"
    ]
    if unnest_keys:
        if len(unnest_keys) > 1:
            raise NotImplementedError("one UNNEST per query")
        ukey, uexpr = unnest_keys[0]
        if not (len(uexpr.args) == 1 and uexpr.args[0].is_column):
            raise NotImplementedError("UNNEST takes a bare multi-value column")
        c = segment.column(uexpr.args[0].op)
        if c.mv_lengths is None:
            raise ValueError(f"UNNEST requires a multi-value column ({uexpr.args[0].op})")
        reps = c.mv_lengths[docids].astype(np.int64)
        idx = np.repeat(np.arange(len(docids)), reps)
        elems = np.concatenate(
            [list(t) for t in c.decoded()[docids] if len(t)] or [np.array([], dtype=object)]
        )
        new_arrays: Dict[str, np.ndarray] = {}
        for k in cols:
            if k == ukey:
                new_arrays[k] = np.asarray(elems, dtype=object)
            else:
                new_arrays[k] = np.asarray(arrays[k], dtype=object)[idx]
        arrays = new_arrays
    return SelectionSegmentResult(columns=cols, arrays=arrays)


def order_key_arrays(
    codes: Optional[np.ndarray],
    values: Optional[np.ndarray],
    nulls: Optional[np.ndarray],
    docids: np.ndarray,
    ascending: bool,
    nulls_last: bool,
):
    """(value_key, null_rank) lexsort keys for ORDER BY, keeping integer
    dtypes intact (no float64 cast: LONG values above 2^53 must not collide).
    Shared by the per-segment selection trim and the distributed gather
    (codes are sort ranks within their dictionary's key space)."""
    if codes is not None:
        key = np.asarray(codes)[docids].astype(np.int64)
    else:
        key = np.asarray(values)[docids]
    if not ascending:
        key = -key.astype(np.int64) if np.issubdtype(key.dtype, np.integer) else -key.astype(np.float64)
    null_rank = None
    if nulls is not None:
        nullm = np.asarray(nulls)[docids]
        null_rank = np.where(nullm, np.int8(1 if nulls_last else -1), np.int8(0))
        key = np.where(nullm, key.dtype.type(0), key)
    return key, null_rank


def _expr_order_key(
    segment: ImmutableSegment, expr, docids: np.ndarray, ascending: bool, nulls_last: bool
):
    """(lexsort key, null_rank) for an ORDER BY expression: host evaluation
    over matched rows; a row is NULL when any input column is null there
    (SQL null propagation), ranked by NULLS FIRST/LAST — not by whatever
    placeholder value the expression computed (review-caught)."""
    vals = eval_expr_host(expr, segment, docids)
    nullm = None
    for cname in expr.columns():
        cn = segment.column(cname).nulls
        if cn is not None:
            m = cn[docids]
            nullm = m if nullm is None else (nullm | m)
    a = np.asarray(vals)
    if a.dtype == object:
        none_m = np.array([v is None for v in a], dtype=bool)
        if none_m.any():
            nullm = none_m if nullm is None else (nullm | none_m)
            a = a.copy()
            a[none_m] = 0
        try:
            a = a.astype(np.float64)
        except (ValueError, TypeError):
            pass
    if np.issubdtype(a.dtype, np.number):
        key = a.astype(np.float64)
        key = key if ascending else -key
    else:
        _, inv = np.unique(a.astype(str), return_inverse=True)
        key = inv if ascending else -inv
    null_rank = None
    if nullm is not None and nullm.any():
        null_rank = np.where(nullm, np.int8(1 if nulls_last else -1), np.int8(0))
        key = np.where(nullm, 0, key)
    return key, null_rank


def _local_order_key(segment: ImmutableSegment, col: str, docids: np.ndarray, ascending: bool, nulls_last: bool):
    c = segment.column(col)
    return order_key_arrays(c.codes, c.values, c.nulls, docids, ascending, nulls_last)
