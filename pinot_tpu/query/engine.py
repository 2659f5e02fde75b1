"""In-process query engine: table registry + execute = broker+server in one.

Reference parity: this is the BaseQueriesTest topology (SURVEY.md 4.2) as a
production object — real planner + executor + reduce, no cluster required.
The cluster layer (cluster/) wraps the same engine behind broker/server
roles; the distributed combine (parallel/) slots in between execute and
reduce.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from pinot_tpu.query import executor, reduce as reduce_mod
from pinot_tpu.query.ir import Expr, QueryContext
from pinot_tpu.query.result import ExecutionStats, ResultTable
from pinot_tpu.segment.segment import ImmutableSegment
from pinot_tpu.spi.config import TableConfig
from pinot_tpu.spi.schema import Schema
from pinot_tpu.utils import perf


@dataclass
class TableState:
    schema: Schema
    config: TableConfig
    segments: List[ImmutableSegment] = field(default_factory=list)
    # realtime tables: RealtimeTableDataManager owning sealed + consuming
    # segments (realtime/manager.py); None for offline tables
    realtime: Optional[object] = None

    def query_segments(self) -> List[ImmutableSegment]:
        """The segment list a query against this table scans: offline
        segments plus the realtime view (sealed + consuming snapshots)."""
        segs = list(self.segments)
        if self.realtime is not None:
            segs.extend(self.realtime.query_segments())
        return segs


class QueryEngine:
    def __init__(self, memory_budget_bytes: int = 8 << 30, secondary_slots: int = 2) -> None:
        from pinot_tpu.query.safety import MemoryAccountant, WorkloadScheduler
        from pinot_tpu.utils.slowlog import SlowQueryLog

        self.tables: Dict[str, TableState] = {}
        self.accountant = MemoryAccountant(memory_budget_bytes)
        self.scheduler = WorkloadScheduler(secondary_slots)
        self._qid_seq = itertools.count(1)
        self.slow_queries = SlowQueryLog()

    # -- table registry (controller-lite) -------------------------------
    def register_table(self, schema: Schema, config: Optional[TableConfig] = None) -> None:
        cfg = config or TableConfig(name=schema.name)
        self.tables[cfg.name] = TableState(schema=schema, config=cfg)

    def add_segment(self, table: str, segment: ImmutableSegment) -> None:
        self.tables[table].segments.append(segment)

    def table(self, name: str) -> TableState:
        if name not in self.tables:
            raise KeyError(f"table {name!r} not registered (have {list(self.tables)})")
        return self.tables[name]

    # -- execution -------------------------------------------------------
    def execute(self, ctx: QueryContext, device=None) -> ResultTable:
        from pinot_tpu.spi.env import apply_env_defaults

        apply_env_defaults(ctx.options)
        if ctx.options.get("__explain__"):
            # explain never executes anything — not subqueries, not set-op
            # components (review-caught: per-component explains would union)
            return self._explain(ctx, self.table(ctx.table).query_segments())
        if ctx.options.get("__analyze__"):
            return self._explain_analyze(ctx, device=device)
        resolve_subqueries(ctx, lambda c: self.execute(c, device=device))
        if ctx.set_ops:
            return apply_set_ops(ctx, lambda c: self.execute(c, device=device))
        if ctx.joins:
            raise NotImplementedError(
                "JOIN queries require the distributed engine "
                "(parallel.DistributedEngine routes them to mse.MultiStageEngine); "
                "the single-node QueryEngine serves single-table queries only"
            )
        from pinot_tpu.query.safety import Deadline, estimate_segment_bytes
        from pinot_tpu.utils.metrics import METRICS, Trace

        t0 = time.perf_counter()
        deadline = Deadline.from_ctx(ctx)
        req_id = f"engine_{next(self._qid_seq)}"
        trace = Trace(bool(ctx.options.get("trace", False)), query_id=req_id)
        METRICS.counter("queries").inc()
        state = self.table(ctx.table)
        # schema-aware static validation before any per-segment planning:
        # malformed plans fail here with a structured PlanCheckError
        from pinot_tpu.analysis.plan_check import check_plan

        check_plan(ctx, state.schema)
        segments = state.query_segments()
        self._inject_global_ranges(ctx, state, segments)
        # admission: charge the estimated device bytes up front (safety.py),
        # counting only the columns the query actually ships
        from pinot_tpu.query.planner import _needed_columns

        est = sum(
            estimate_segment_bytes(ctx, seg, _needed_columns(ctx, seg)) for seg in segments
        )
        # workload tier gate first (BinaryWorkloadScheduler): secondary
        # queries wait for a slot before charging memory
        release_slot = self.scheduler.acquire(ctx, deadline)
        try:
            qid = self.accountant.acquire(est)
        except BaseException:
            release_slot()
            raise
        stats = ExecutionStats()
        results = []
        try:
            # pipelined execution: dispatch the segments' kernels (async, one
            # call a group of segments that share a program:
            # executor.QueryLaunches), then drain
            from pinot_tpu.query.planner import _needed_columns

            launches = executor.QueryLaunches(
                ctx, device=device, trace=trace,
                check=lambda: deadline.check(f"query on {ctx.table}"),
            )
            for seg in segments:
                stats.num_segments_queried += 1
                stats.total_docs += seg.num_docs
                # schema evolution: older segments synthesize virtual
                # default columns for schema-added fields; SELECT * covers
                # the FULL table schema (review-caught: per-segment schemas
                # would drop/crash on added columns)
                needed = _needed_columns(ctx, seg)
                if any(isinstance(s, Expr) and s.is_column and s.op == "*" for s in ctx.select_list):
                    needed = list(dict.fromkeys(list(needed) + state.schema.column_names))
                seg.ensure_columns(state.schema, needed)
                if executor.prune_segment(ctx, seg, launches.planning):
                    stats.num_segments_pruned += 1
                    continue
                launches.add(seg)
            launches.flush()
            if trace.enabled:
                # device/host time split: ONE fence over every pending output
                # (trace-only — the untraced path lets collect's device_get
                # fence so deadline checks stay responsive between collects)
                import jax

                with trace.span("device_wait", launches=launches.calls) as wsp:
                    jax.block_until_ready(launches.outputs())
                if wsp is not None:
                    wsp.annotate(kernelBytes=launches.kernel_bytes)
            for res, seg_stats in launches.collect():
                stats.num_segments_processed += 1
                stats.num_docs_scanned += seg_stats.num_docs_scanned
                stats.add_index_uses(seg_stats.filter_index_uses)
                stats.add_kernel_cost(seg_stats)
                results.append(res)
            deadline.check(f"query on {ctx.table}")
            with trace.span("reduce", cpu=True):
                out = reduce_mod.reduce_results(ctx, results, stats)
        except Exception:
            METRICS.counter("queryExceptions").inc()
            raise
        finally:
            self.accountant.release(qid)
            release_slot()
        out.stats.time_ms = (time.perf_counter() - t0) * 1000
        out.stats.query_id = req_id
        out.stats.trace = trace.finish()
        METRICS.histogram("queryLatency").update(out.stats.time_ms)
        METRICS.counter("docsScanned").inc(stats.num_docs_scanned)
        from pinot_tpu.query.shape import shape_digest

        perf.SHAPE_STATS.record(
            ctx.table,
            shape_digest(ctx.shape_fingerprint()),
            rows=out.stats.num_docs_scanned,
            time_ms=out.stats.time_ms,
            kernel_bytes=out.stats.kernel_bytes,
            compile_ms=out.stats.compile_ms,
            cache_hit=out.stats.compile_ms == 0.0,
        )
        return out

    def _explain_analyze(self, ctx: QueryContext, device=None) -> ResultTable:
        """EXPLAIN ANALYZE: run the query with tracing forced, then join the
        static operator tree with the measured span tree (query.analyze)."""
        from pinot_tpu.query.analyze import analyze_result

        ctx.options.pop("__analyze__", None)
        ctx.options["trace"] = True
        for _op, _all, rhs in ctx.set_ops:
            rhs.options.pop("__analyze__", None)
            rhs.options["trace"] = True
        executed = self.execute(ctx, device=device)
        return analyze_result(
            self._explain(ctx, self.table(ctx.table).query_segments()), executed
        )

    def _explain(self, ctx: QueryContext, segments) -> ResultTable:
        """EXPLAIN PLAN FOR: per-shape operator tree rows (Pinot's explain
        table: Operator / Operator_Id / Parent_Id)."""
        from pinot_tpu.query import planner as planner_mod

        rows = [("BROKER_REDUCE(" + ("sort/limit" if ctx.order_by else "limit") + ")", 1, 0)]
        if not segments:
            return ResultTable(columns=["Operator", "Operator_Id", "Parent_Id"], rows=rows, stats=ExecutionStats())
        plan = planner_mod.plan_segment(ctx, segments[0])
        oid = 2
        rows.append((f"COMBINE_{plan.kind.upper()}", oid, 1))
        parent = oid
        oid += 1
        if plan.kind == "aggregation":
            rows.append((f"AGGREGATE({', '.join(str(a) for a in ctx.aggregations)})", oid, parent))
        elif plan.kind.startswith("groupby"):
            rows.append(
                (
                    f"GROUP_BY(keys: {', '.join(str(g) for g in ctx.group_by)}; "
                    f"{'dense' if plan.kind == 'groupby_dense' else 'sparse'} table {plan.num_groups})",
                    oid,
                    parent,
                )
            )
        else:
            rows.append((f"SELECT(columns: {', '.join(plan.select_columns)})", oid, parent))
        parent = oid
        oid += 1
        rows.append((f"PROJECT({', '.join(plan.needed_columns)})", oid, parent))
        parent = oid
        oid += 1
        if plan.index_uses:
            uses = ", ".join(f"{c}:{k}" for c, k in plan.index_uses)
            rows.append((f"FILTER_INDEX({uses})", oid, parent))
        elif ctx.filter is not None:
            rows.append((f"FILTER_SCAN({ctx.filter.fingerprint()[:80]})", oid, parent))
        else:
            rows.append(("FILTER_MATCH_ALL", oid, parent))
        return ResultTable(columns=["Operator", "Operator_Id", "Parent_Id"], rows=rows, stats=ExecutionStats())

    def attach_realtime(self, table: str, manager) -> None:
        """Bind a RealtimeTableDataManager to a registered table."""
        self.table(table).realtime = manager

    @staticmethod
    def _inject_global_ranges(ctx: QueryContext, state: TableState, segments=None) -> None:
        """Table-global facts per sketch-aggregated column, injected as ctx
        options so every segment binds identically:
          __range__<col>  - global [min, max]: histogram bin edges must be
                            the same everywhere for partials to add
          __dictfp__<col> - dictionary-fingerprint consensus; "MIXED" tells
                            column_binding the code space is NOT shared, so
                            code-indexed partials must not merge"""
        from pinot_tpu.query.functions import for_spec

        if segments is None:
            segments = state.query_segments()
        for spec in ctx.aggregations:
            if spec.expr is None or not spec.expr.is_column:
                continue
            if not for_spec(spec).needs_binding:
                continue
            col = spec.expr.op
            rkey, fkey = f"__range__{col}", f"__dictfp__{col}"
            if rkey in ctx.options and fkey in ctx.options:
                continue
            mins, maxs = [], []
            fps = set()
            dict_values = None
            for seg in segments:
                if col not in seg.columns:
                    continue
                c = seg.column(col)
                fps.add(c.dictionary.fingerprint() if c.has_dictionary else None)
                if c.has_dictionary and dict_values is None:
                    dict_values = c.dictionary.values
                if c.stats.min_value is not None and not c.data_type.is_string_like:
                    mins.append(c.stats.min_value)
                    maxs.append(c.stats.max_value)
            if mins:
                ctx.options.setdefault(rkey, (min(mins), max(maxs)))
            if fps:
                only = next(iter(fps)) if len(fps) == 1 else None
                ctx.options.setdefault(fkey, "MIXED" if len(fps) > 1 else (only or ""))
                if len(fps) == 1 and dict_values is not None:
                    # shared key space: reduce-time decode (bind_reduce) may
                    # need the dictionary values themselves
                    ctx.options.setdefault(f"__dictvals__{col}", dict_values)

    def query(self, sql: str, device=None) -> ResultTable:
        """SQL front door (CalciteSqlParser analog lives in sql/); finished
        requests land in the slow-query ring (utils/slowlog.py)."""
        from pinot_tpu.sql.parser import parse_query

        ctx = parse_query(sql)
        if ctx.options.get("__explain__"):
            return self.execute(ctx, device=device)  # plan-only: not served
        fp = ctx.fingerprint()
        try:
            out = self.execute(ctx, device=device)
        except Exception as e:
            self.slow_queries.record(sql, fp, None, error=f"{type(e).__name__}: {e}")
            raise
        self.slow_queries.record(sql, fp, out)
        return out

    def sql(self, statement: str, device=None) -> ResultTable:
        """DDL + DML front door (the pinot-sql-ddl controller resource)."""
        from pinot_tpu.sql.ddl import is_ddl, parse_ddl, show_create_table

        if not is_ddl(statement):
            return self.query(statement, device=device)
        stmt = parse_ddl(statement)
        if stmt.kind == "create_table":
            self.register_table(stmt.schema, stmt.config)
            return ResultTable(columns=["status"], rows=[(f"created {stmt.table}",)], stats=ExecutionStats())
        if stmt.kind == "drop_table":
            if stmt.table not in self.tables:
                raise KeyError(f"table {stmt.table!r} not found")
            del self.tables[stmt.table]
            return ResultTable(columns=["status"], rows=[(f"dropped {stmt.table}",)], stats=ExecutionStats())
        if stmt.kind == "show_tables":
            return ResultTable(
                columns=["tableName"], rows=[(n,) for n in sorted(self.tables)], stats=ExecutionStats()
            )
        state = self.table(stmt.table)
        return ResultTable(
            columns=["createTable"],
            rows=[(show_create_table(state.schema, state.config),)],
            stats=ExecutionStats(),
        )


# ---------------------------------------------------------------------------
# Engine-agnostic rewrites (shared by QueryEngine / Broker / Distributed)
# ---------------------------------------------------------------------------
def resolve_subqueries(ctx: QueryContext, exec_fn) -> None:
    """IN (SELECT ...) semi-joins: run the subquery, substitute its first
    output column as the IN value set (the reference's IdSet/semi-join
    rewrite in the Calcite planner).  An unspecified subquery LIMIT bumps to
    the semi-join valve instead of Pinot's cosmetic default 10."""
    from pinot_tpu.query.ir import FilterNode, FilterOp, Predicate, Subquery

    def rewrite(node):
        if node is None:
            return None
        if node.op is FilterOp.PRED:
            p = node.predicate
            if p is not None and p.values and isinstance(p.values[0], Subquery):
                sub = p.values[0].ctx
                if not sub.options.get("__hasExplicitLimit__", False):
                    sub.limit = int(ctx.options.get("inSubqueryLimit", 1_000_000))
                res = exec_fn(sub)
                vals = tuple(sorted({r[0] for r in res.rows if r[0] is not None}))
                return FilterNode.pred(
                    Predicate(p.ptype, p.lhs, values=vals)
                    if vals
                    else Predicate(p.ptype, p.lhs, values=("\x00__nomatch__",))
                )
            return node
        children = tuple(rewrite(c) for c in node.children)
        return FilterNode(node.op, children=children, predicate=node.predicate)

    ctx.filter = rewrite(ctx.filter)
    if ctx.having is not None:
        ctx.having = rewrite(ctx.having)


def apply_set_ops(ctx: QueryContext, exec_fn) -> ResultTable:
    """UNION [ALL] / INTERSECT / EXCEPT over component results (the MSE
    SetOperator analog, executed at the broker-reduce level)."""
    ops = ctx.set_ops
    ctx.set_ops = []
    try:
        base = exec_fn(ctx)
        rows = list(base.rows)
        for op, all_flag, rhs_ctx in ops:
            rhs = exec_fn(rhs_ctx)
            if rhs.columns and base.columns and len(rhs.columns) != len(base.columns):
                raise ValueError(
                    f"set operation arity mismatch: {len(base.columns)} vs {len(rhs.columns)} columns"
                )
            if op == "union" and all_flag:
                rows = rows + list(rhs.rows)
            elif op == "union":
                seen = set()
                out = []
                for r in rows + list(rhs.rows):
                    if r not in seen:
                        seen.add(r)
                        out.append(r)
                rows = out
            elif op == "intersect":
                rset = set(rhs.rows)
                seen = set()
                rows = [r for r in rows if r in rset and not (r in seen or seen.add(r))]
            else:  # except
                rset = set(rhs.rows)
                seen = set()
                rows = [r for r in rows if r not in rset and not (r in seen or seen.add(r))]
        return ResultTable(columns=base.columns, rows=rows, stats=base.stats)
    finally:
        ctx.set_ops = ops
