"""Server instance: owns segments, executes per-segment query work.

Reference parity: pinot-server ServerInstance (.../starter/ServerInstance.java
:69-177) + HelixInstanceDataManager / BaseTableDataManager — the process that
holds segment data and runs the single-stage executor over its local
segments when the broker scatters a query.

Re-design: segments stay the same ImmutableSegment objects (in one process
the "download from deep store" step is a reference share / mmap re-open);
execution reuses the SSE executor with its device pytree cache, so each
logical server keeps its own HBM-resident working set.

Fault surface: the broker hands each scatter call a Deadline (its remaining
budget, optionally capped by serverTimeoutMs) — the launch/collect loop
checks it between kernels, and on expiry abandons still-pending launches
(cooperative cancellation: JAX dispatch is async, so "cancel" means never
collecting — no device_get, no host sync) before raising QueryTimeoutError.
An attached cluster.faults.FaultPlan can fail/delay the call or hide
segments, driving the broker's failover paths deterministically.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

from pinot_tpu.cluster.admission import QueryKilledError, ResourceBudget
from pinot_tpu.query import executor
from pinot_tpu.query.ir import QueryContext
from pinot_tpu.query.result import ExecutionStats
from pinot_tpu.query.safety import Deadline, QueryTimeoutError, estimate_segment_bytes
from pinot_tpu.segment.segment import ImmutableSegment
from pinot_tpu.segment.table_shape import TableShape
from pinot_tpu.utils.metrics import METRICS, MetricsRegistry

# span name -> this server's timer: one update a query, the sum over its segments
_STAGE_TIMERS = {
    "launch_plan": "server.launchPlanMs",
    "launch_ship": "server.launchShipMs",
    "launch_enqueue": "server.launchEnqueueMs",
    "launch_release": "server.launchReleaseMs",  # inside launch_enqueue
    "collect": "server.collectMs",
}


def _staging_depth() -> int:
    """Scatter staging window: how many consecutive segments must be
    jointly resident while the scan pages through the HBM cache.  Routed
    through the autopilot KnobRegistry (PINOT_TPU_STAGING_DEPTH initial,
    default 2 = current segment + the one prefetching behind it)."""
    from pinot_tpu.cluster import autopilot

    return max(1, int(autopilot.knobs().get("staging_depth")))


def _segment_bytes(segment: ImmutableSegment) -> int:
    """Host-array bytes of one segment (codes/values/null masks/MV lengths)
    — the per-table residency the segmentBytes gauge tracks."""
    total = 0
    for c in segment.columns.values():
        for arr in (c.codes, c.values, c.nulls, c.mv_lengths):
            if arr is not None:
                total += arr.nbytes
    return total


class ServerInstance:
    def __init__(
        self, name: str, device=None, fault_plan=None, budget=None, data_dir=None,
        residency=None,
    ):
        self.name = name
        self.device = device
        # table -> {segment name -> segment}
        self.segments: Dict[str, Dict[str, ImmutableSegment]] = {}
        # table -> what its segments HERE share: the dictionary sizes their
        # kernels are compiled for (segment/table_shape.py), kept by
        # add_segment / drop_segment
        self.shapes: Dict[str, TableShape] = {}
        # (table, query shape) whose programs were made ahead of need (_warm_widths)
        self._width_warmed: set = set()
        # table -> (the segment names a query named, their columns' min / max as arrays): _bounds_of
        self._bounds: Dict[str, tuple] = {}
        self._width_warm_lock = threading.Lock()
        # cluster.faults.FaultPlan hook (None in production)
        self.fault_plan = fault_plan
        # HBM reservation ledger (cluster.admission.ResourceBudget): every
        # scatter call reserves its working-set estimate before launching so
        # concurrent queries can't jointly overcommit device memory.  None
        # disables tracking; the coordinator attaches one at registration.
        self.budget: Optional[ResourceBudget] = budget
        # tiered storage (segment/residency.py): when attached, HBM is a
        # byte-budgeted CACHE over the segments' host arrays — scatter
        # calls reserve only the pipeline window (not the full working
        # set), segment columns page through the residency budget with
        # cost-aware eviction, and the next segment's columns prefetch on
        # the staging thread while the current kernel runs.  None keeps
        # the legacy pin-everything path.  The coordinator attaches one
        # at registration (PINOT_TPU_HBM_CACHE_BYTES=0 disables).
        self.residency = residency
        # local segment cache dir for deep-store restores (tempdir fallback)
        self.data_dir = data_dir
        # process-death simulation: True between crash() and boot() — every
        # execute fails like a dead TCP peer until the coordinator restarts
        # and reconciles this server
        self.crashed = False
        # per-SERVER metric registry (ServerMetrics analog): the broker
        # federates these into one labeled cluster exposition
        # (utils.metrics.federate_prometheus) — the process-global METRICS
        # keeps its role as this process's aggregate view
        self.metrics = MetricsRegistry()

    # -- crash / restart (process-death simulation) -----------------------
    def crash(self) -> None:
        """Simulate process death: all in-memory/HBM segment state is lost
        (gauges zero out with it) and calls fail until boot()."""
        for table in list(self.segments):
            for seg_name in list(self.segments[table]):
                self.drop_segment(table, seg_name)
        self.segments = {}
        self.crashed = True
        METRICS.counter("server.crashes").inc()

    def boot(self) -> None:
        """Come back up EMPTY — recovery is the coordinator reconciling this
        server against ideal state (restart_server), not a local replay."""
        self.crashed = False

    def restore_segment(self, table: str, seg_name: str, deep_store) -> ImmutableSegment:
        """Re-materialize one committed segment from the deep store: download
        to the local cache dir, CRC-verify, load, pin (restart recovery and
        rebalance both land here)."""
        import tempfile

        if self.data_dir is None:
            self.data_dir = tempfile.mkdtemp(prefix=f"pinot-server-{self.name}-")
        local_dir = os.path.join(self.data_dir, table)
        segment = deep_store.fetch_segment(table, seg_name, local_dir)
        self.add_segment(table, segment)
        return segment

    # -- data manager ----------------------------------------------------
    def add_segment(self, table: str, segment: ImmutableSegment) -> None:
        self.segments.setdefault(table, {})[segment.name] = segment
        # one of this name held before (replaced in place) leaves the shape as this one joins
        shape = self.shapes.setdefault(table, TableShape())
        shape.add(segment)
        # what its resident columns are padded to on this device where no plan says (to_device)
        segment.table_shapes[self.device] = shape
        self.metrics.gauge(f"server.rowBuckets.{table}").set(shape.row_buckets())
        with self._width_warm_lock:
            self._width_warmed.clear()  # a new segment may bring a kernel of its own, or fill a wider group
        self._bounds.pop(table, None)
        # device-residency gauge: segment host arrays mirror what the
        # executor's pytree cache pins in HBM for this table
        METRICS.gauge(f"server.segmentBytes.{table}").add(_segment_bytes(segment))
        self.metrics.gauge(f"server.segmentBytes.{table}").add(_segment_bytes(segment))

    def drop_segment(self, table: str, seg_name: str) -> None:
        seg = self.segments.get(table, {}).pop(seg_name, None)
        self._bounds.pop(table, None)
        if seg is not None:
            self.shapes[table].remove(seg_name)  # add_segment made it
            seg.table_shapes.pop(self.device, None)
            self.metrics.gauge(f"server.rowBuckets.{table}").set(self.shapes[table].row_buckets())
            for held in [seg, *seg.star_tables(made_only=True)]:  # its star-tree levels are groups of their own
                if self.residency is not None:
                    # uncharge the cache budget AND drop the device entry;
                    # the evict callback clears raw + #packed flavors together
                    self.residency.evict(held.device_group(self.device))
                # idempotent with the residency evict; also clears legacy pins
                held.evict_device(self.device)
            METRICS.gauge(f"server.segmentBytes.{table}").add(-_segment_bytes(seg))
            self.metrics.gauge(f"server.segmentBytes.{table}").add(-_segment_bytes(seg))

    def get_segment(self, table: str, seg_name: str) -> Optional[ImmutableSegment]:
        return self.segments.get(table, {}).get(seg_name)

    def segment_names(self, table: str) -> List[str]:
        return list(self.segments.get(table, {}))

    # -- query execution (InstanceRequestHandler analog) ------------------
    def execute(
        self,
        ctx: QueryContext,
        seg_names: List[str],
        table_schema=None,
        deadline: Optional[Deadline] = None,
        cancel=None,
        source: str = "broker",
        query_id: Optional[str] = None,
        on_first_launch=None,
    ):
        """Run one query over the named LOCAL segments; returns
        (segment results, stats) — the DataTable the reference ships back.

        `on_first_launch`: zero-arg hook, called when a launch is about to
        compile its program for this server's device (executor._enqueue).

        `cancel`: optional zero-arg probe (the broker watchdog's closure)
        returning a kill reason or None — checked between kernels alongside
        the deadline, so a killed query abandons its pending launches the
        same cooperative way a timed-out one does.  When `self.budget` is
        set, the working-set estimate for the named segments is reserved
        before any launch and released on exit (success, timeout, or kill) —
        a ReservationError here means this server is at capacity and the
        broker should fail the segments over to another replica.

        Tracing (ctx option `trace`): builds a per-server span subtree —
        dispatch (attrs starSegments: the segments a star-tree level answered
        for, combinedSegments: the segments whose dense group tables the chip
        folded into their group's ONE before the fetch (executor._launch_group:
        such a group has one result, at its first segment's place in the
        results, and None at the others'), tableShapedSegments: the segments
        whose plan's kernel was compiled for the dictionary sizes the table's
        segments here share and not their own (`self.shapes`;
        planner.compiled_dict_sizes), contractedLookups / gatheredLookups /
        residentLookups: the table-by-code lookups in the launched segments'
        programs, by the form each was compiled with (ops/code_lookup.py; a
        resident one reads the column staging decoded), compactedScatters: the
        compactions in those programs, one a filtered mask whose row-priced
        scatters take the passing rows alone (ops/segmented.py), rowBuckets: the
        distinct row counts the launched segments' kernels were compiled for
        (planner.compiled_rows: the table's, where its segments hold unequal
        rows), rowsPadded: those counts less the segments' true rows, summed:
        the rows scanned and masked, loopMs: its time
        outside its child
        spans; per segment a
        launch:<segment> span over the executor's
        launch_plan / launch_ship, and per GROUP of segments that share a
        compiled kernel one launch_enqueue, ending in launch_release:
        executor.QueryLaunches; launch_ship is the resident columns' lookup
        alone, the members' parameters ride the jitted call inside
        launch_enqueue as host numpy and run on self.device), device_wait
        (ONE block_until_ready over every pending output: the device-compute
        share the async dispatch hides; `launches` = jitted calls), then one
        collect span a group — annotated with segments/docs/backend and any
        fault-plan events, and ships it back via stats.trace for the broker
        to graft.  Traced or not, every span is a profiler annotation
        carrying `query_id` (the broker's), and the stages' sums go once a
        query into this server's timers (_STAGE_TIMERS)."""
        from pinot_tpu.query.planner import QueryPlanning
        from pinot_tpu.utils.metrics import Trace

        if self.crashed:
            from pinot_tpu.cluster.faults import ServerFaultError

            # a dead process looks like a transport error to the broker —
            # exactly the signal that drives its failover/breaker paths
            raise ServerFaultError(f"server {self.name} is down (crashed)")
        trace = Trace(
            bool(ctx.options.get("trace", False)), root=f"server:{self.name}", query_id=query_id
        )
        # the query's half of planning, derived once for every segment below:
        # the columns it reads here, its plans in QueryLaunches
        planning = QueryPlanning(ctx, self.shapes.get(ctx.table))
        ticket = None
        if self.budget is not None:
            # working-set estimate for the batch, reserved all-or-nothing
            # BEFORE any kernel launches (host-side arithmetic only — no
            # device values touched, so the warm path stays sync-free)
            est = []
            for name in seg_names:
                seg = self.get_segment(ctx.table, name)
                if seg is not None:
                    # what the query reads for it: its columns, or a star-tree level's
                    table, asked = planning.source(seg)
                    est.append(estimate_segment_bytes(asked.ctx, table, asked.needed_columns(table)))
            if self.residency is not None:
                # tiered storage: HBM is a cache, so a scatter only needs
                # its PIPELINE WINDOW resident at once (current segment +
                # the one prefetching behind it) — the residency manager
                # pages the rest through the budget as the scan advances.
                # Working sets that exceed free-but-not-total budget park
                # as a staged fetch instead of 503ing; a window that
                # exceeds the whole budget cannot fit even transiently
                # and still raises ReservationError.  The window width is
                # the autopilot staging_depth knob (read per decision), or
                # the widest group the segments launch in, whose members
                # are resident all at once (executor.group_cap).
                win = max(_staging_depth(), executor.group_cap(max(est, default=0), self.residency))
                need = max(
                    (sum(est[i : i + win]) for i in range(len(est))), default=0
                )
                ticket = self.budget.reserve_or_wait(
                    need, what=f"scatter to server {self.name}", deadline=deadline
                )
            else:
                ticket = self.budget.reserve(
                    sum(est), what=f"scatter to server {self.name}"
                )
        try:
            plan = self.fault_plan
            if plan is not None:
                fault_n0 = len(plan.log)
                # may sleep, flap liveness, or raise; `source` lets one-way
                # partition rules drop only this caller's direction
                plan.on_execute(self.name, source=source)
                if trace.enabled and len(plan.log) > fault_n0:
                    trace.annotate(faults=[k for (_, _, k, _) in plan.log[fault_n0:]])
            stats = ExecutionStats()
            launches = executor.QueryLaunches(
                ctx, device=self.device, residency=self.residency, trace=trace,
                on_first_launch=on_first_launch, planning=planning,
                check=lambda: self._check_budget(deadline, cancelled=launches.uncollected, cancel=cancel),
            )
            with trace.span("dispatch") as dsp:
                # host-side pre-filter FIRST: dictionary/range/bloom metadata
                # prunes cold segments before any planning or staging, so a
                # pruned segment never enters the host->device copy stream
                # (span `prune`: every named segment looked up and asked;
                # a pruned one still counts as queried)
                scan, named = [], []
                with trace.span("prune") as psp:
                    for name in seg_names:
                        seg = self.get_segment(ctx.table, name)
                        if seg is not None and plan is not None and plan.segment_dropped(self.name, ctx.table, name):
                            seg = None
                        if seg is None:
                            raise KeyError(f"server {self.name} does not serve {ctx.table}/{name}")
                        stats.num_segments_queried += 1
                        stats.total_docs += seg.num_docs
                        if table_schema is not None:
                            seg.ensure_columns(table_schema, planning.needed_columns(seg))
                        named.append(seg)
                    bounds = self._bounds_of(ctx.table, seg_names, named)
                    for seg, pruned in zip(named, planning.prune_many(named, bounds)):
                        if pruned:
                            stats.num_segments_pruned += 1
                        else:
                            scan.append(seg)
                if psp is not None:
                    psp.annotate(segments=len(named), pruned=stats.num_segments_pruned)
                for k, seg in enumerate(scan):
                    if self.residency is not None and k + 1 < len(scan):
                        # double-buffer: stage segment k+1's columns on the
                        # residency staging thread while k dispatches/runs
                        # (a server with tiered residency launches at width 1).
                        # Columns the device already holds have nothing to
                        # stage: no task, no wake-up of the staging thread
                        # (a table that fits its cache: every segment of
                        # every query after the first).  The flavors asked
                        # about are the plan's: its decoded columns too
                        nxt, asked = planning.source(scan[k + 1])
                        ahead = asked.needed_columns(nxt)
                        by_value = asked.value_columns(nxt)
                        if not nxt.resident(self.device, ahead, packed_codes=True, value_columns=by_value):
                            self.residency.submit(
                                nxt.to_device,
                                device=self.device,
                                columns=ahead,
                                packed_codes=True,
                                residency=self.residency,
                                prefetch=True,
                                value_columns=by_value,
                                rows=asked.rows(nxt),
                            )
                    # pipelined: a full group dispatches async while the
                    # host plans the next, then drain (executor.QueryLaunches)
                    launches.add(seg)
                launches.flush()
            if dsp is not None:
                # loopMs: the span's time outside its children, which is this
                # loop itself a segment (prune, residency check, budget check)
                dsp.annotate(
                    launches=launches.calls, starSegments=launches.star_segments,
                    combinedSegments=launches.combined_segments,
                    tableShapedSegments=launches.table_shaped_segments,
                    contractedLookups=launches.contracted_lookups,
                    gatheredLookups=launches.gathered_lookups,
                    residentLookups=launches.resident_lookups,
                    compactedScatters=launches.compacted_scatters,
                    rowBuckets=len(launches.row_buckets), rowsPadded=launches.rows_padded,
                    docRangeSegments=launches.doc_range_segments,
                    indexServedPredicates=launches.index_served,
                    indexScannedPredicates=launches.index_scanned,
                    loopMs=round(dsp.duration_ms - sum(c.duration_ms for c in dsp.children), 3),
                )
            if trace.enabled:
                # device/host time split: ONE fence over every pending output
                # (trace-only — the untraced path lets collect's device_get be
                # the fence so cancellation stays responsive between collects)
                import jax

                with trace.span("device_wait", launches=launches.calls) as wsp:
                    jax.block_until_ready(launches.outputs())
                if wsp is not None:
                    wsp.annotate(kernelBytes=launches.kernel_bytes)
            results = []
            for res, seg_stats in launches.collect():
                stats.num_segments_processed += 1
                stats.num_docs_scanned += seg_stats.num_docs_scanned
                stats.add_index_uses(seg_stats.filter_index_uses)
                stats.add_kernel_cost(seg_stats)
                results.append(res)
            if stats.num_segments_pruned and launches.shape_fp is not None:
                self._warm_widths(ctx, named, launches, trace)
            # server-local series the broker federates into the cluster view
            self.metrics.counter("server.segmentsPruned").inc(stats.num_segments_pruned)
            self.metrics.counter("server.queries").inc()
            self.metrics.counter("server.docsScanned").inc(stats.num_docs_scanned)
            self.metrics.counter("server.launches").inc(launches.calls)
            self.metrics.counter("server.groupedSegments").inc(launches.grouped_segments)
            self.metrics.counter("server.sparseGroups").inc(launches.sparse_groups)
            self.metrics.counter("server.combinedSegments").inc(launches.combined_segments)
            self.metrics.counter("server.tableShapedSegments").inc(launches.table_shaped_segments)
            self.metrics.counter("server.contractedLookups").inc(launches.contracted_lookups)
            self.metrics.counter("server.residentLookups").inc(launches.resident_lookups)
            if launches.star_segments:
                self.metrics.counter("server.starTreeSegments").inc(launches.star_segments)
                self.metrics.counter("server.starTreeLevelRows").inc(launches.star_level_rows)
            trace.flush(self.metrics, _STAGE_TIMERS)
            stats.stage_ns = [(self.name, trace.totals_ns)]  # by reference: only a slow request's log entry reads it
            if stats.compile_ms > 0:
                self.metrics.timer("server.compileMs").update(stats.compile_ms)
            if trace.enabled:
                from pinot_tpu import ops

                trace.annotate(
                    server=self.name,
                    segments=len(seg_names),
                    segmentsPruned=stats.num_segments_pruned,
                    docsScanned=stats.num_docs_scanned,
                    backend=ops.scan_backend(),
                )
                stats.trace = trace.finish()
            return results, stats
        finally:
            if ticket is not None:
                self.budget.release(ticket)

    def _bounds_of(self, table: str, seg_names: List[str], segments: List):
        """The columns' min / max over the segments a query names, as arrays
        (planner.SegmentBounds): kept a table for the list the last query
        named (a table's queries name the same list until the routing
        changes) and dropped when a segment joins or leaves."""
        kept = self._bounds.get(table)
        if kept is None or kept[0] != seg_names:
            from pinot_tpu.query.planner import SegmentBounds

            kept = self._bounds[table] = (list(seg_names), SegmentBounds(segments))
        return kept[1]

    def _warm_widths(self, ctx: QueryContext, asked: List, launches, trace) -> None:
        """The first query of a shape that PRUNES here (its answer already
        collected) makes every program a later query of the shape can need
        ready for this server's device: the pruner leaves another member
        count a query, so other widths of the group ladder, the other form,
        and the kernels of the segments it dropped (executor.warm_widths,
        span `width_warm`, attr `programs`).  Once a (table, query shape): a
        table whose queries scan every segment never comes here."""
        shape = (ctx.table, launches.shape_fp)
        with self._width_warm_lock:
            if shape in self._width_warmed:
                return
            self._width_warmed.add(shape)
        with trace.span("width_warm", segments=len(asked)) as wsp:
            made = executor.warm_widths(
                ctx, asked, device=self.device, residency=self.residency, planning=launches.planning
            )
        if wsp is not None:
            wsp.annotate(programs=made)

    def warm(self, ctx: QueryContext, seg_names: List[str], table_schema=None) -> None:
        """Compile, for this server's device, the programs `ctx` runs over the
        named local segments, by running it once as `execute` would (the same
        groups, so the same widths) and dropping the answer.  Not a served
        call: no fault plan, no budget, no query counters; only the compile
        is recorded (`server.compileMs`), as a served first launch's is.  The
        broker calls it on a table's other servers while one of them compiles
        the same programs (Broker._scatter)."""
        from pinot_tpu.query.planner import QueryPlanning

        launches = executor.QueryLaunches(
            ctx, device=self.device, residency=self.residency,
            planning=QueryPlanning(ctx, self.shapes.get(ctx.table)),
        )
        for name in seg_names:
            seg = self.get_segment(ctx.table, name)
            if seg is None:
                continue
            if table_schema is not None:
                seg.ensure_columns(table_schema, launches.planning.needed_columns(seg))
            if not executor.prune_segment(ctx, seg, launches.planning):
                launches.add(seg)
        launches.flush()
        compile_ms = sum(seg_stats.compile_ms for _, seg_stats in launches.collect())
        if compile_ms > 0:
            self.metrics.timer("server.compileMs").update(compile_ms)

    def _check_budget(
        self, deadline: Optional[Deadline], cancelled: int, cancel=None
    ) -> None:
        """Between-kernel deadline + kill probe.  On expiry or kill the
        still-pending launches are abandoned uncollected (their references
        die with this frame — the async dispatches finish on device but
        never sync back)."""
        if cancel is not None:
            reason = cancel()
            if reason:
                if cancelled:
                    METRICS.counter("server.launchesCancelled").inc(cancelled)
                METRICS.counter("server.queriesKilled").inc()
                raise QueryKilledError(
                    f"server {self.name}: query killed ({reason}); "
                    f"{cancelled} pending launch(es) abandoned",
                    reason=reason,
                )
        if deadline is not None and deadline.expired():
            if cancelled:
                METRICS.counter("server.launchesCancelled").inc(cancelled)
            raise QueryTimeoutError(
                f"server {self.name} ran out of query budget "
                f"(timeoutMs={deadline.timeout_ms:g}); "
                f"{cancelled} pending launch(es) abandoned"
            )
