"""Distributed query engine: one shard_map kernel per query over the mesh.

Reference parity: the whole distributed SSE path in one construct —
QueryRouter.submitQuery scatter (pinot-core/.../transport/QueryRouter.java:77)
+ BaseCombineOperator worker pool (.../combine/BaseCombineOperator.java:202)
+ BrokerReduceService merge (.../query/reduce/BrokerReduceService.java:65).

Re-design (SURVEY.md section 7 "Combine = collective"): there is no transport.
Segments live stacked+sharded in HBM across the mesh (stacked.py); a query
compiles to ONE shard_map kernel that filters/aggregates its local shard rows
and merges partials IN-GRAPH with lax.psum/pmin/pmax over the data axes.  The
host sees already-combined results; the remaining broker work (HAVING, ORDER
BY, LIMIT, formatting) reuses query/reduce.py verbatim.

The mesh may be the legacy 1-D SEG_AXIS mesh or the 2-D
(REPLICA_AXIS, SHARD_AXIS) mesh (parallel/mesh.py).  On 2-D, table rows
shard jointly over BOTH axes (capacity mode) and the combine is
HIERARCHICAL: reduce over SHARD_AXIS (ICI) first — collapsing each replica
row to one partial table — then once over REPLICA_AXIS, the only reduction
that crosses host/DCN boundaries on a multi-host pod, so cross-host bytes
scale with partial-table size rather than raw rows.  The QPS deployment of
the same mesh is ReplicatedEngine below: one 1-D sub-engine per replica
row, each a full data copy, queries routed to rows round-robin.

DataTable/Netty have no analog here by design: the wire format between
"servers" (shards) is an XLA collective over ICI/DCN (SURVEY.md 2.6).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from pinot_tpu import ops
from pinot_tpu.parallel import mesh as mesh_mod
from pinot_tpu.query import executor as sse_executor
from pinot_tpu.query import reduce as reduce_mod
from pinot_tpu.query import planner as planner_mod
from pinot_tpu.query.filter import FilterCompiler
from pinot_tpu.query.functions import FIELD_COMBINE, get_agg_function
from pinot_tpu.query.ir import AggregationSpec, Expr, QueryContext
from pinot_tpu.query.planner import GroupDim, _group_dim
from pinot_tpu.query.result import (
    AggSegmentResult,
    DenseGroupData,
    ExecutionStats,
    GroupBySegmentResult,
    ResultTable,
    SelectionSegmentResult,
)
from pinot_tpu.query.transform import as_row_array, eval_expr
from pinot_tpu.segment import packing
from pinot_tpu.utils import perf
from pinot_tpu.utils.metrics import METRICS


def _psum_field(name: str, x, axes):
    """Combine one partial field across the data axes, innermost axis
    (ICI) first — on the 2-D mesh the REPLICA_AXIS step is the only one
    that crosses host/DCN boundaries and it moves partial-table bytes.
    Float sums take the order-canonical path (mesh.psum_ordered): integer
    adds and min/max are exact under any association, but float partials
    must reduce in one fixed global order or 2x4 and 8x1 drift by ulps."""
    op = FIELD_COMBINE[name]
    if op == "add":
        if jnp.issubdtype(x.dtype, jnp.floating):
            return mesh_mod.psum_ordered(x, axes)
        return mesh_mod.psum_hierarchical(x, axes)
    if op == "min":
        return mesh_mod.pmin_hierarchical(x, axes)
    return mesh_mod.pmax_hierarchical(x, axes)


def flatten_cols(cols):
    """[S, D, ...] shard-local row arrays -> flat [S*D, ...] views.
    MV code matrices keep their trailing element axis."""
    out = {}
    for name, entry in cols.items():
        e = {}
        for k, v in entry.items():
            if k in ("codes", "codes_packed", "values", "nulls", "lengths"):
                e[k] = v.reshape((-1,) + v.shape[2:])
            else:
                e[k] = v
        out[name] = e
    return out


def make_agg_inputs(agg_specs, aggs, agg_filter_fns, view, table_like, null_handling):
    """Per-aggregation (values, mask) input builder usable inside kernels.

    Shared by the distributed SSE combine kernel and the MSE join kernels —
    the projection/transform step of the hot loop (ProjectionOperator /
    TransformOperator analog) specialised to one plan."""

    def _agg_inputs(cols, params, base_mask):
        out = []
        for spec, fn, ffn in zip(agg_specs, aggs, agg_filter_fns):
            mask = base_mask
            if ffn is not None:
                ft, _ = ffn(cols, params)
                mask = mask & ft
            if getattr(fn, "mv_input", False):
                out.append(planner_mod.mv_agg_input(spec, fn, view, cols, mask))
                continue
            if spec.expr is None:
                vals = mask
            elif fn.needs_codes:
                vals, mask = planner_mod.agg_input_codes(spec, fn, view, cols, mask, null_handling)
            elif fn.name == "count" and spec.expr.is_column:
                vals = mask
                c = table_like.column(spec.expr.op)
                if c.nulls is not None and null_handling:
                    mask = mask & ~cols[spec.expr.op]["nulls"]
            else:
                vals, nulls = eval_expr(spec.expr, view, cols)
                vals = as_row_array(vals, mask.shape)
                if nulls is not None and null_handling:
                    mask = mask & ~nulls
            if fn.needs_extra_exprs:
                extras = []
                for ex in spec.extra_exprs:
                    ev, en = eval_expr(ex, view, cols)
                    extras.append(as_row_array(ev, mask.shape))
                    if en is not None and null_handling:
                        mask = mask & ~en
                vals = (vals, *extras)
            out.append((vals, mask))
        return out

    return _agg_inputs


class _ShardView:
    """Compile-time segment facade over a StackedTable: FilterCompiler and
    transform tracing only consult metadata (dictionaries, nulls, dtypes) and
    num_docs for match-all shapes — here num_docs is the per-device flat row
    count for ONE launch (local shards x batch docs).

    When axis/ndev are given, FilterCompiler compiles SHARD-AWARE index
    paths: bitmap params stored full as [ndev, L, D//32] and sliced per
    macro-batch by the engine, doc ranges compare against global flat doc
    ids via `docs_fn` (query/filter.py)."""

    def __init__(
        self,
        stacked,
        local_rows: int,
        axis: Optional[str] = None,
        ndev: int = 0,
        docs_fn: Optional[Callable] = None,
        bitmap_layout: Optional[Tuple[int, int, int]] = None,
    ):
        self._stacked = stacked
        self.num_docs = local_rows
        self.schema = stacked.schema
        self.total_docs = stacked.num_docs
        self.indexes = getattr(stacked, "indexes", {})
        self.shard_info = (axis, ndev, local_rows) if axis is not None else None
        self.docs_fn = docs_fn
        self.bitmap_layout = bitmap_layout

    def column(self, name: str):
        return self._stacked.column(name)


@dataclass
class _DistPlan:
    kind: str  # aggregation | groupby_dense | groupby_sparse | selection
    fn: Callable  # jitted shard_map kernel(cols, params)
    params: Dict[str, Any]
    needed_columns: List[str]
    aggs: List[Any]
    group_dims: List[GroupDim]
    num_groups: int
    select_columns: List[str]
    # param keys sharded on the device axis (index bitmap word slices)
    row_sharded_params: frozenset = frozenset()
    # (column, index kind) per index-accelerated filter predicate
    index_uses: Tuple = ()
    # macro-batch launch schedule: each launch covers doc columns
    # [off, off+batch_docs) of the [S, D] arrays; `fresh` marks the first
    # not-yet-covered within-batch column (tail overlap masking)
    batch_docs: int = 0
    batch_offsets: Tuple[Tuple[int, int], ...] = ((0, 0),)
    # jitted device-side cross-launch merge for the sparse group-by path
    # (ops.merge_sparse_tables); None falls back to the host numpy merge
    sparse_merge_fn: Optional[Callable] = None
    # bytes ONE launch must read (utils/perf.scan_bytes_per_row x the rows a
    # launch covers; every macro-batch shares the shape), counted when the
    # plan-cache entry is built
    scan_bytes: float = 0.0
    # wall ms of the program's first call (trace + compile), empty until it
    # ran: the list is the plan-cache entry's, shared with the plan every
    # hit builds
    first_call_ms: List[float] = field(default_factory=list)


class DistributedEngine:
    """Executes queries over a StackedTable sharded on a device mesh."""

    def __init__(
        self,
        mesh=None,
        axis: str = mesh_mod.SEG_AXIS,
        launch_bytes: Optional[int] = None,
        pipeline_depth: Optional[int] = None,
        hbm_cache_bytes: Optional[int] = None,
        residency=None,
    ):
        import os

        if mesh is None:
            mesh = mesh_mod.default_mesh(axis)
        from pinot_tpu.query.planner import _plan_cache_entries
        from pinot_tpu.utils.cache import LruCache

        self.mesh = mesh
        # data-placement axes, outermost first: ("seg",) on the legacy 1-D
        # mesh, (REPLICA_AXIS, SHARD_AXIS) on the 2-D mesh.  `self.axis` is
        # what flows into PartitionSpecs and collectives — a bare name for
        # 1-D, the axes tuple for 2-D (both spellings every jax collective
        # accepts); hierarchical combines walk `self.axes` innermost-first.
        self.axes: Tuple[str, ...] = mesh_mod.data_axes(mesh)
        self.axis = self.axes[0] if len(self.axes) == 1 else self.axes
        self.tables: Dict[str, Any] = {}  # name -> StackedTable
        # plan-cache bytes charge the process host ledger the admission
        # controller tracks (runtime import: admission is cluster-layer)
        from pinot_tpu.cluster.admission import process_host_budget

        self._plan_cache = LruCache(
            max_entries=_plan_cache_entries(), name="compile.dist", budget=process_host_budget()
        )
        # shape fp + hit/miss of the most recent _plan call (trace/EXPLAIN
        # ANALYZE annotation; the engine plans one query at a time)
        self._last_shape_fp: str = ""
        self._last_plan_cache_hit = False
        # per-device bytes one launch may capture (macro-batching threshold);
        # ~2GB leaves the while-loop capture copy well under HBM headroom
        self.launch_bytes = (
            launch_bytes
            if launch_bytes is not None
            else int(os.environ.get("PINOT_TPU_LAUNCH_BYTES", str(2 << 30)))
        )
        # max in-flight macro-batch launches: 2 = double-buffering (dispatch
        # batch k+1 while batch k computes, hiding the host dispatch gap the
        # r5 timing_pairs spread exposed); 1 = the old fully-serialized loop.
        # Each in-flight launch holds a capture copy of its batch inputs, so
        # resident HBM scales with depth — _batching sizes batches against
        # launch_bytes, keeping depth * batch_bytes bounded.  None routes
        # through the autopilot KnobRegistry per launch (env var = initial
        # value + ceiling); an explicit ctor value or direct assignment pins.
        self._pipeline_depth_override: Optional[int] = (
            None if pipeline_depth is None else int(pipeline_depth)
        )
        # tiered segment storage (segment/residency.py): HBM is a byte-
        # budgeted cache over the host arrays.  The staging stream copies
        # batch k+1's slices while batch k computes — the generalization of
        # pipeline_depth from "launch next kernel" to "stage next segment".
        # PINOT_TPU_HBM_CACHE_BYTES sizes the cache (0 disables tiering and
        # restores the legacy pin-everything path).
        from pinot_tpu.segment.residency import default_residency

        if residency is not None:
            # caller-owned manager (ReplicatedEngine splits one HBM budget
            # into per-mesh-row managers so staging/eviction stays row-local)
            self.residency = residency
        elif hbm_cache_bytes is not None and hbm_cache_bytes > 0:
            from pinot_tpu.cluster.admission import ResourceBudget

            self.residency = default_residency(
                budget=ResourceBudget(hbm_cache_bytes, gauge="residency.reservedBytes")
            )
        elif hbm_cache_bytes is not None:
            self.residency = None
        else:
            self.residency = default_residency()

    @property
    def pipeline_depth(self) -> int:
        """In-flight launch depth, read per launch loop (KnobRegistry-backed
        unless pinned by the ctor or a direct assignment)."""
        if self._pipeline_depth_override is not None:
            return self._pipeline_depth_override
        # runtime import: autopilot is cluster-layer, engine is parallel-layer
        from pinot_tpu.cluster import autopilot

        return int(autopilot.knobs().get("pipeline_depth"))

    @pipeline_depth.setter
    def pipeline_depth(self, value: int) -> None:
        self._pipeline_depth_override = int(value)

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    def register_table(self, name: str, stacked) -> None:
        if stacked.num_shards % self.num_devices:
            raise ValueError(
                f"num_shards={stacked.num_shards} not divisible by mesh size {self.num_devices}"
            )
        self.tables[name] = stacked
        # HBM residency gauge: stacked host arrays mirror what to_device
        # pins across the mesh for this table
        nbytes = 0
        for c in stacked.columns.values():
            for arr in (c.codes, c.values, c.nulls, c.mv_lengths):
                if arr is not None:
                    nbytes += arr.nbytes
        METRICS.gauge(f"hbm.pinnedBytes.{name}").set(float(nbytes))
        # drop stale self-join facades of a re-registered table (mse/plan.py
        # resolve registers them as '{name}@{alias}')
        for k in [k for k in self.tables if k.startswith(name + "@")]:
            del self.tables[k]

    def _mse(self):
        """Join queries route to the multi-stage engine over the same mesh
        and table registry (MultiStageBrokerRequestHandler delegation analog)."""
        if not hasattr(self, "_mse_engine"):
            from pinot_tpu.mse.engine import MultiStageEngine

            self._mse_engine = MultiStageEngine(self.mesh, self.axis, tables=self.tables)
        return self._mse_engine

    # ------------------------------------------------------------------
    def query(self, sql: str) -> ResultTable:
        from pinot_tpu.sql.parser import parse_query

        return self.execute(parse_query(sql))

    def execute(self, ctx: QueryContext) -> ResultTable:
        import time

        if ctx.joins:
            return self._mse().execute(ctx)
        from pinot_tpu.utils.metrics import Trace

        t0 = time.perf_counter()
        trace = Trace(bool(ctx.options.get("trace", False)))
        stacked = self.tables[ctx.table]
        self._inject_sketch_info(ctx, stacked)
        stats = ExecutionStats(
            num_segments_queried=stacked.num_shards,
            num_segments_processed=stacked.num_shards,
            num_docs_scanned=stacked.num_docs,
            total_docs=stacked.num_docs,
        )
        with trace.span("plan") as psp:
            plan = self._plan(ctx, stacked)
            if psp is not None:
                from pinot_tpu.query.shape import shape_digest

                psp.annotate(
                    shapeFp=shape_digest(self._last_shape_fp),
                    planCache="hit" if self._last_plan_cache_hit else "miss",
                )
        stats.add_index_uses(plan.index_uses)
        with trace.span("run"):
            result = self._run(ctx, plan, stacked, stats, trace)
        with trace.span("reduce"):
            out = reduce_mod.reduce_results(ctx, [result], stats)
        t = trace.finish()
        if t is not None:
            out.stats.trace = t
        out.stats.time_ms = (time.perf_counter() - t0) * 1000
        METRICS.counter("dist.queries").inc()
        METRICS.histogram("dist.queryLatency").update(out.stats.time_ms)
        from pinot_tpu.query.shape import shape_digest

        perf.SHAPE_STATS.record(
            ctx.table,
            shape_digest(self._last_shape_fp),
            rows=out.stats.num_docs_scanned,
            time_ms=out.stats.time_ms,
            kernel_bytes=out.stats.kernel_bytes,
            compile_ms=out.stats.compile_ms,
            cache_hit=self._last_plan_cache_hit,
        )
        return out

    @staticmethod
    def _inject_sketch_info(ctx: QueryContext, stacked) -> None:
        """Stacked tables are aligned by construction (one dictionary per
        column); publish that plus global ranges for sketch bindings."""
        from pinot_tpu.query.functions import for_spec

        for spec in ctx.aggregations:
            if spec.expr is None or not spec.expr.is_column:
                continue
            if not for_spec(spec).needs_binding:
                continue
            col = spec.expr.op
            c = stacked.column(col)
            ctx.options.setdefault(
                f"__dictfp__{col}", c.dictionary.fingerprint() if c.has_dictionary else ""
            )
            if c.has_dictionary:
                ctx.options.setdefault(f"__dictvals__{col}", c.dictionary.values)
            if c.stats.min_value is not None and not c.data_type.is_string_like:
                ctx.options.setdefault(f"__range__{col}", (c.stats.min_value, c.stats.max_value))

    # ------------------------------------------------------------------
    def _plan(self, ctx: QueryContext, stacked) -> _DistPlan:
        from pinot_tpu.analysis.compile_audit import DIST_AUDIT
        from pinot_tpu.analysis.plan_check import check_plan_cached
        from pinot_tpu.query.shape import column_info_from, params_structure

        check_plan_cached(ctx)
        batch_docs, batch_offsets = self._batching(ctx, stacked)
        # Keyed on the SHAPE fingerprint: predicate literals canonicalize to
        # parameter slots (query/shape.py), so 20 distinct-literal variants of
        # one query share this entry and only rebind params below.
        key = (
            ctx.shape_fingerprint(column_info_from(stacked)),
            stacked.signature(), self.axis, self.num_devices, batch_docs,
            ops.scan_backend(),  # pallas/xla plans trace different kernels
        )
        self._last_shape_fp = key[0]
        cached = self._plan_cache.get(key)
        if cached is not None:
            # Rebind this query's literals into a fresh plan that reuses the
            # cached compiled kernel (and device merge fn).  The structure
            # check guards against an audit miss: a jitted fn silently
            # retraces on a different params pytree, so a mismatch is a
            # compile and must be counted (and cached) as one.
            plan = self._build_plan(
                ctx, stacked, batch_docs, batch_offsets,
                compiled_fn=cached.fn, compiled_merge_fn=cached.sparse_merge_fn,
            )
            if (
                params_structure(plan.params) == params_structure(cached.params)
                and plan.row_sharded_params == cached.row_sharded_params
            ):
                plan.scan_bytes = cached.scan_bytes
                plan.first_call_ms = cached.first_call_ms
                DIST_AUDIT.record_hit(key[0])
                self._last_plan_cache_hit = True
                return plan
        DIST_AUDIT.record_compile(key[0])
        self._last_plan_cache_hit = False
        plan = self._build_plan(ctx, stacked, batch_docs, batch_offsets)
        plan.scan_bytes = stacked.num_shards * batch_docs * perf.scan_bytes_per_row(
            (stacked.column(n) for n in plan.needed_columns),
            bitmap_params=len(plan.row_sharded_params),
        )
        self._plan_cache.put(key, plan)
        return plan

    def _batching(self, ctx: QueryContext, stacked) -> Tuple[int, Tuple[Tuple[int, int], ...]]:
        """Macro-batch launch schedule (round 5, VERDICT r4 #2).

        XLA materializes one copy of every while-loop-captured buffer, so a
        single launch's resident HBM is ~2x its input bytes — at 1B rows
        that alone exceeds a v5e chip.  Splitting the doc axis into B
        host-level launches caps the copy at one batch's bytes; the combine
        across launches is group-table-sized (never row-length).  Batch
        width is 32-aligned so index bitmap words slice cleanly (and whole
        lane blocks where the table's shards are); a ragged
        tail re-launches the last full-width window with already-covered
        rows masked via the `fresh` offset (same trick as
        ops/segmented._fused_scan_inchunk)."""
        D = stacked.docs_per_shard
        L = stacked.num_shards // self.num_devices
        # Per-doc bytes over the WHOLE table, not the query's needed columns:
        # batch width must be a pure function of the table so every query
        # shares one doc slicing — per-query widths would cache duplicate
        # on-device slices of the same column (review-caught: at 1B rows the
        # second slicing is the OOM the batching exists to prevent).  Narrow
        # queries over-batch slightly; launch overhead is microseconds.
        bytes_per_doc = 0.0
        for c in stacked.columns.values():
            if c.codes is not None:
                width = c.codes.shape[2] if c.codes.ndim == 3 else 1
                if getattr(c, "code_bits", None) and c.packed is not None:
                    # packed forward index ships the uint32 lane words
                    bytes_per_doc += c.code_bits / 8.0 * width
                else:
                    bytes_per_doc += c.codes.dtype.itemsize * width
            if c.values is not None:
                bytes_per_doc += c.values.dtype.itemsize
            if c.nulls is not None:
                bytes_per_doc += 1
            if c.mv_lengths is not None:
                bytes_per_doc += c.mv_lengths.dtype.itemsize
        per_dev = int(max(1.0, bytes_per_doc) * L * D)
        n_batches = max(1, -(-per_dev // self.launch_bytes))
        if n_batches == 1 or D < 64:
            return D, ((0, 0),)
        # a table of whole lane blocks a shard (StackedTable.build) is cut at
        # block bounds, so every batch's dictionary columns ship bit-packed
        align = packing.BLOCK_ROWS if D % packing.BLOCK_ROWS == 0 else 32
        batch_docs = min(D, -(-(-(-D // n_batches)) // align) * align)
        offsets = []
        off = 0
        while off + batch_docs <= D:
            offsets.append((off, 0))
            off += batch_docs
        if off < D:
            tail = D - batch_docs
            offsets.append((tail, off - tail))
        return batch_docs, tuple(offsets)

    def _build_plan(
        self,
        ctx: QueryContext,
        stacked,
        batch_docs: int,
        batch_offsets: Tuple[Tuple[int, int], ...],
        compiled_fn: Optional[Callable] = None,
        compiled_merge_fn: Optional[Callable] = None,
    ) -> _DistPlan:
        axis = self.axis
        ndev = self.num_devices
        local_shards = stacked.num_shards // ndev
        D_full = stacked.docs_per_shard
        local_rows = local_shards * batch_docs
        L = local_shards
        Db = batch_docs
        has_padding = stacked.num_docs < stacked.num_shards * D_full
        use_fresh = any(fresh for _, fresh in batch_offsets)

        def docs_fn(params):
            """Global flat doc ids for this device's rows in this launch."""
            base = lax.axis_index(axis).astype(jnp.int32) * np.int32(L * D_full)
            off = params["__boff__"].astype(jnp.int32)
            return (
                base
                + off
                + jnp.arange(L, dtype=jnp.int32)[:, None] * np.int32(D_full)
                + jnp.arange(Db, dtype=jnp.int32)[None, :]
            ).reshape(-1)

        def _valid_mask(params):
            m = None
            if has_padding:
                m = docs_fn(params) < np.int32(stacked.num_docs)
            if use_fresh:
                f = jnp.tile(jnp.arange(Db, dtype=jnp.int32) >= params["__fresh__"], L)
                m = f if m is None else m & f
            return m

        assert D_full % 32 == 0, "docs_per_shard must be 32-aligned (StackedTable.build)"
        view = _ShardView(
            stacked, local_rows, axis=axis, ndev=ndev,
            docs_fn=docs_fn, bitmap_layout=(ndev, L, D_full // 32),
        )

        fc = FilterCompiler(view, ctx.null_handling)
        filter_fn = fc.compile(ctx.filter)
        # set when the WHOLE filter resolved to one plain index bitmap: the
        # fused Pallas scan can then consume the packed words directly and
        # the row-length bool mask never exists in HBM (capture before the
        # per-agg FILTER compiles below reuse the compiler)
        word_key = fc.sole_bitmap_param
        scan_be = ops.scan_backend()  # plan-time backend decision (cache-keyed)
        agg_specs = list(ctx.aggregations)
        aggs = planner_mod.bind_aggs(agg_specs, stacked, ctx)
        agg_filter_fns = [fc.compile(s.filter) if s.filter is not None else None for s in agg_specs]

        if ctx.is_aggregate and not ctx.group_by:
            kind = "aggregation"
            group_dims: List[GroupDim] = []
            num_groups = 0
        elif ctx.group_by:
            group_dims = [_group_dim(g, view, ctx.null_handling) for g in ctx.group_by]
            num_groups = 1
            for gd in group_dims:
                num_groups *= max(1, gd.cardinality)
            kind = "groupby_dense" if num_groups <= ctx.max_dense_groups else "groupby_sparse"
        else:
            kind = "selection"
            group_dims = []
            num_groups = 0

        planner_mod.guard_sparse_vector_fields(kind, aggs)
        if any(gd.mv for gd in group_dims):
            raise NotImplementedError("MV GROUP BY (explode) is not yet supported on the distributed stacked path")
        if kind in ("aggregation", "groupby_dense") and any(fn.pairwise_merge for fn in aggs):
            # the sparse path merges per-device tables HOST-side (pairwise
            # fn.merge in sparse_tables_to_result), so only the in-graph
            # psum-combined paths exclude coupled partials
            raise NotImplementedError(
                "pairwise-merge aggregations (FIRST/LAST_WITH_TIME, DISTINCTCOUNTTHETA) "
                "cannot ride the in-graph psum combine; run them on the single-node engine"
            )

        null_handling = ctx.null_handling
        # Bit-packed forward indexes: to_device(packed_codes=True) ships
        # uint32 lane words under "codes_packed" instead of the unpacked
        # codes; every kernel sees an overlay that adds trace-level unpacked
        # "codes" (XLA dedups/DCEs; the Pallas fused path additionally gets
        # the raw words via key_packed and unpacks in-register).  A slice
        # ships packed only as whole lane blocks a shard, so a device's
        # shards laid end to end are whole blocks too.
        packed_meta: Dict[str, int] = {
            name: int(c.code_bits)
            for name, c in stacked.columns.items()
            if getattr(c, "code_bits", None)
            and getattr(c, "packed", None) is not None
        }

        def _flat(cols, _rows=local_rows):
            out = flatten_cols(cols)
            for name, bits in packed_meta.items():
                e = out.get(name)
                if e is not None and "codes_packed" in e and "codes" not in e:
                    e = dict(e)
                    e["codes"] = packing.unpack_codes_jnp(
                        e["codes_packed"], bits, _rows
                    )
                    out[name] = e
            return out
        _agg_inputs = make_agg_inputs(agg_specs, aggs, agg_filter_fns, view, stacked, null_handling)

        def _group_key(cols):
            if len(group_dims) == 1 and group_dims[0].kind == "dict":
                return cols[group_dims[0].name]["codes"]  # cast per chunk in ops
            key = None
            for gd in group_dims:
                code = gd.device_code(cols, view, jnp.int32)
                key = code if key is None else key * np.int32(gd.cardinality) + code
            return key

        def _key_packed(cols):
            """(words, bits) for the group key when its bit-packed forward
            index shipped — lets the Pallas scan skip the unpacked codes."""
            if len(group_dims) != 1 or group_dims[0].kind != "dict":
                return None
            bits = packed_meta.get(group_dims[0].name)
            e = cols.get(group_dims[0].name)
            if not bits or e is None or "codes_packed" not in e:
                return None
            return (e["codes_packed"], bits)

        sparse_merge_fn = None  # set by the groupby_sparse branch when eligible

        if kind == "aggregation":

            def shard_kernel(cols, params):
                cols = _flat(cols)
                tmask, _ = filter_fn(cols, params)
                vm = _valid_mask(params)
                if vm is not None:
                    tmask = tmask & vm
                partials = [fn.partial(v, m) for fn, (v, m) in zip(aggs, _agg_inputs(cols, params, tmask))]
                return [
                    {f: _psum_field(f, x, axis) for f, x in p.items()} for p in partials
                ]

            out_specs = P()

        elif kind == "groupby_dense":
            vranges = planner_mod.agg_vranges(agg_specs, stacked)
            # Word fusion: when the whole filter is one plain index bitmap
            # and every aggregation is fully fusable (count/sum/sumsq field
            # kinds only — scatter and sketch paths never see packed words),
            # hand the PACKED words straight to the fused scan; the Pallas
            # kernel unpacks them in-register, so the filter costs 1 bit of
            # HBM per row instead of an unpacked bool byte.
            fuse_words = (
                scan_be in ("pallas", "interpret")
                and word_key is not None
                and all(fn.field_kinds is not None for fn in aggs)
                and all(
                    k in ("count", "sum", "sumsq")
                    for fn in aggs
                    for k in fn.field_kinds.values()
                )
            )

            if fuse_words:

                def shard_kernel(cols, params):
                    cols = _flat(cols)
                    vm = _valid_mask(params)
                    tmask = vm if vm is not None else jnp.ones((local_rows,), bool)
                    key = _group_key(cols)
                    inputs = _agg_inputs(cols, params, tmask)
                    presence, partials = planner_mod.grouped_partials(
                        aggs, inputs, tmask, key, num_groups, vranges,
                        backend=scan_be,
                        mask_words=params[word_key].reshape(-1),
                        key_packed=_key_packed(cols),
                    )
                    presence = mesh_mod.psum_hierarchical(presence, axis)
                    partials = [
                        {f: _psum_field(f, x, axis) for f, x in p.items()} for p in partials
                    ]
                    return presence, partials

            else:

                def shard_kernel(cols, params):
                    cols = _flat(cols)
                    tmask, _ = filter_fn(cols, params)
                    vm = _valid_mask(params)
                    if vm is not None:
                        tmask = tmask & vm
                    key = _group_key(cols)
                    inputs = _agg_inputs(cols, params, tmask)
                    presence, partials = planner_mod.grouped_partials(
                        aggs, inputs, tmask, key, num_groups, vranges,
                        backend=scan_be, key_packed=_key_packed(cols),
                    )
                    presence = mesh_mod.psum_hierarchical(presence, axis)
                    partials = [
                        {f: _psum_field(f, x, axis) for f, x in p.items()} for p in partials
                    ]
                    return presence, partials

            out_specs = P()

        elif kind == "groupby_sparse":
            # Per-device sort+scatter into fixed [numGroupsLimit] tables
            # (planner_mod.sparse_grouped_tables); only [ndev*K] tables cross
            # PCIe — never row-length arrays.  Cross-device key merge happens
            # host-side in sparse_tables_to_result (IndexedTable combine).
            if num_groups >= (1 << 62):
                raise NotImplementedError("composite group key exceeds 62 bits")
            num_slots = min(ctx.num_groups_limit, num_groups)
            # per-device ORDER BY-aware trim: each device keeps its LOCAL
            # top-num_slots groups by the comparator (groups split across
            # devices rank by local partials — the same accuracy valve as
            # the reference's server-side numGroupsLimit trim)
            order_spec = planner_mod.kernel_order_spec(ctx, aggs)
            vranges = planner_mod.agg_vranges(agg_specs, stacked)

            def shard_kernel(cols, params):
                cols = _flat(cols)
                tmask, _ = filter_fn(cols, params)
                vm = _valid_mask(params)
                if vm is not None:
                    tmask = tmask & vm
                key = planner_mod.packed_key64(cols, group_dims, view)
                inputs = _agg_inputs(cols, params, tmask)
                return planner_mod.sparse_grouped_tables(
                    aggs, inputs, tmask, key, num_slots, order_spec,
                    num_groups=num_groups, vranges=vranges,
                )

            out_specs = P(self.axis)

            # Device-side cross-launch merge (ops.merge_sparse_tables): the
            # stacked [B*ndev*K] per-launch tables combine in-graph and only
            # the FINAL [num_slots] tables cross PCIe — replacing the host
            # numpy fold of sparse_tables_to_result.  Eligible when every
            # aggregation merges field-wise (field_kinds set, no pairwise
            # merge) and any ORDER BY-aware trim is expressible on device
            # (kernel_order_spec); otherwise the host merge remains.
            sparse_merge_fn = None
            merge_ok = all(
                fn.field_kinds is not None and not fn.pairwise_merge for fn in aggs
            )
            morder = None
            if merge_ok and planner_mod.order_by_agg_index(ctx) is not None:
                if order_spec is None:
                    merge_ok = False  # host ranks via fn.final; not derivable here
                else:
                    morder = order_spec  # (agg index, order FIELD name, asc)
            if merge_ok:
                field_ops = [
                    {f: FIELD_COMBINE[f] for f in fn.fields} for fn in aggs
                ]

                def _merge(uniq_list, parts_list):
                    uniq = jnp.concatenate([u.reshape(-1) for u in uniq_list])
                    parts = [
                        {
                            f: jnp.concatenate([p[i][f].reshape(-1) for p in parts_list])
                            for f in field_ops[i]
                        }
                        for i in range(len(field_ops))
                    ]
                    return ops.merge_sparse_tables(
                        uniq, parts, num_slots, field_ops, order_spec=morder,
                        may_trim=num_slots < num_groups,
                    )

                sparse_merge_fn = (
                    compiled_merge_fn if compiled_merge_fn is not None else jax.jit(_merge)
                )

        else:  # selection

            def shard_kernel(cols, params):
                cols = _flat(cols)
                tmask, _ = filter_fn(cols, params)
                vm = _valid_mask(params)
                if vm is not None:
                    tmask = tmask & vm
                return tmask

            out_specs = P(self.axis)

        # in_specs matching the pytrees: row arrays shard on the leading axis,
        # dictionaries and params replicate.
        def _col_specs(cols):
            out = {}
            for name, entry in cols.items():
                out[name] = {
                    k: (
                        P(axis, *([None] * (v.ndim - 1)))
                        if k in ("codes", "codes_packed", "values", "nulls", "lengths")
                        else P()
                    )
                    for k, v in entry.items()
                }
            return out

        select_columns: List[str] = []
        if kind == "selection":
            for s in ctx.select_list:
                if isinstance(s, Expr) and s.is_column:
                    if s.op == "*":
                        select_columns.extend(stacked.schema.column_names)
                    else:
                        select_columns.append(s.op)
                else:
                    raise NotImplementedError(f"selection expression {s} not yet supported")

        mesh = self.mesh
        # launch-schedule params: batch doc offset + fresh floor (tail
        # overlap masking); always present so every batch shares one pytree
        fc.params["__boff__"] = np.int32(0)
        fc.params["__fresh__"] = np.int32(0)
        row_sharded = frozenset(fc.row_sharded_params)

        def run(cols, params):
            kern = jax.shard_map(
                shard_kernel,
                mesh=mesh,
                in_specs=(
                    _col_specs(cols),
                    {k: (P(axis, None) if k in row_sharded else P()) for k in params},
                ),
                out_specs=out_specs,
                check_vma=False,
            )
            return kern(cols, params)

        # On a shape-cache hit the caller passes the already-jitted kernel:
        # this rebuild only re-derives params/metadata, never re-traces.
        fn = compiled_fn if compiled_fn is not None else jax.jit(run)

        needed = sse_executor_needed_columns(ctx, stacked)
        # index-resolved filter columns never ship to device (the bitmap/doc
        # range already answered them) — same pruning as the SSE planner
        keep = planner_mod._non_filter_columns(ctx, view) | fc.used_columns
        if kind == "selection":
            keep |= set(select_columns) | {o.expr.op for o in ctx.order_by if o.expr.is_column}
        needed = [c for c in needed if c in keep]
        return _DistPlan(
            kind=kind,
            fn=fn,
            params=fc.params,
            needed_columns=needed,
            aggs=aggs,
            group_dims=group_dims,
            num_groups=num_groups,
            select_columns=select_columns,
            row_sharded_params=frozenset(fc.row_sharded_params),
            index_uses=tuple(fc.index_uses),
            batch_docs=batch_docs,
            batch_offsets=tuple(batch_offsets),
            sparse_merge_fn=sparse_merge_fn,
        )

    # ------------------------------------------------------------------
    def batch_params(self, plan: _DistPlan, off: int, fresh: int) -> Dict[str, Any]:
        """Host-side params for the launch covering docs [off, off+batch_docs):
        schedule scalars set, row-sharded bitmap words sliced on the doc axis."""
        p = dict(plan.params)
        p["__boff__"] = np.int32(off)
        p["__fresh__"] = np.int32(fresh)
        wlo, whi = off // 32, (off + plan.batch_docs) // 32
        for k in plan.row_sharded_params:
            w = plan.params[k]  # [ndev, L, D//32]
            p[k] = np.ascontiguousarray(w[:, :, wlo:whi]).reshape(w.shape[0], -1)
        return p

    def _shared_params(self, plan: _DistPlan):
        """Batch-invariant params stage ONCE per query: only the launch-
        schedule scalars (__boff__/__fresh__) and the doc-sliced row-sharded
        bitmap words differ between launches, so the shared device_put cost
        does not scale with the launch count."""
        repl = NamedSharding(self.mesh, P())
        shard = NamedSharding(self.mesh, P(self.axis, None))
        shared = {
            k: jax.device_put(v, repl)
            for k, v in plan.params.items()
            if k not in plan.row_sharded_params and k not in ("__boff__", "__fresh__")
        }
        return shared, repl, shard

    def _stage_batch(
        self, plan: _DistPlan, stacked, j: int, shared, repl, shard, prefetch: bool = False
    ) -> Tuple[Dict, Dict]:
        """Stage macro-batch j's device inputs: the table slice rides the
        residency cache (budgeted, evictable), per-batch params ship fresh.
        Runs on the residency staging stream when called with prefetch."""
        off, fresh = plan.batch_offsets[j]
        cols, _ = stacked.to_device(
            self.mesh, self.axis, plan.needed_columns,
            doc_slice=(off, off + plan.batch_docs), with_valid=False,
            packed_codes=True, residency=self.residency, prefetch=prefetch,
        )
        params = dict(shared)
        for k, v in self.batch_params(plan, off, fresh).items():
            if k in shared:
                continue
            params[k] = jax.device_put(v, shard if k in plan.row_sharded_params else repl)
        return cols, params

    def device_batches(self, plan: _DistPlan, stacked) -> List[Tuple[Dict, Dict]]:
        """Device-placed (cols, params) per macro-batch launch, the whole
        list at once (the AOT compile tests; _run itself
        stages lazily through the prefetch stream)."""
        shared, repl, shard = self._shared_params(plan)
        return [
            self._stage_batch(plan, stacked, j, shared, repl, shard)
            for j in range(len(plan.batch_offsets))
        ]

    @staticmethod
    def _combine_partials(parts_list):
        """Fold per-batch partials (list over batches of list-of-field-dicts)
        with the same add/min/max semantics as the in-graph psum combine
        (functions.combine_field — the one FIELD_COMBINE dispatch)."""
        from pinot_tpu.query.functions import combine_field

        out = parts_list[0]
        for nxt in parts_list[1:]:
            out = [
                {f: combine_field(f, p[f], q[f]) for f in p}
                for p, q in zip(out, nxt)
            ]
        return out

    def _drain(self, out, keep_device: bool):
        """Completion fence for one in-flight launch.  keep_device leaves the
        output tables on device (the sparse merge consumes them in-graph) and
        fences on a single table-sized leaf instead of copying everything —
        one small device_get, not a per-launch block_until_ready."""
        if keep_device:
            jax.device_get(jax.tree_util.tree_leaves(out)[0])
            return out
        return jax.device_get(out)

    def _run(self, ctx, plan: _DistPlan, stacked, stats: ExecutionStats, trace=None):
        from pinot_tpu.utils.metrics import Trace

        if trace is None:
            trace = Trace(False)
        # Launches are PIPELINED up to pipeline_depth in flight (default 2 =
        # double-buffering): batch k+1 dispatches while batch k computes,
        # hiding the host-side dispatch gap between launches.  Each
        # in-flight execution holds a capture copy of its batch inputs, so
        # resident HBM is bounded by depth * batch bytes (depth=1 restores
        # the old fully-serialized loop).  The fence is a device_get of the
        # oldest launch's output — never a per-launch block_until_ready.
        depth = max(1, int(self.pipeline_depth))
        # graceful degradation: under process-wide memory pressure (broker
        # admission controller, cluster/admission.py) the pipeline sheds
        # in-flight launches — one fewer capture copy resident in HBM per
        # pressure level past 1, down to a fully serialized loop
        from pinot_tpu.cluster.admission import current_pressure_level, pipeline_depth_under_pressure

        pressure = current_pressure_level()
        if pressure:
            depth = pipeline_depth_under_pressure(depth, pressure)
            trace.annotate(pressure=pressure)
        # device merge consumes sparse outputs in-graph: keep them on device
        keep_device = plan.kind == "groupby_sparse" and plan.sparse_merge_fn is not None
        batch_outs = []
        pending: List[Any] = []
        n_batches = len(plan.batch_offsets)
        # Staging pipeline: with a residency manager attached, batch j+1's
        # host->device copies run on the residency staging thread while
        # batch j computes — the "stage next segment" generalization of the
        # launch pipeline below.  Without one (tiering disabled) staging is
        # inline, restoring the legacy pin-everything behaviour.  The single
        # staging worker keeps copies FIFO, so consuming j never waits
        # behind a copy issued for j+1.
        shared, repl, shard = self._shared_params(plan)
        use_stream = self.residency is not None and n_batches > 1
        staged: Dict[int, Any] = {}

        def _ensure(j: int, prefetch: bool) -> None:
            if j >= n_batches or j in staged:
                return
            if use_stream:
                staged[j] = self.residency.submit(
                    self._stage_batch, plan, stacked, j, shared, repl, shard, prefetch
                )
            else:
                staged[j] = self._stage_batch(plan, stacked, j, shared, repl, shard)

        def _consume(j: int) -> Tuple[Dict, Dict]:
            item = staged.pop(j)
            if not use_stream:
                return item
            if item.done():
                METRICS.counter("engine.prefetchHits").inc()
                return item.result()
            # the copy stream is behind the compute stream: timed stall
            tw0 = time.perf_counter()
            out = item.result()
            METRICS.counter("engine.stagingStalls").inc()
            METRICS.histogram("residency.stagingStallMs").update(
                (time.perf_counter() - tw0) * 1000.0
            )
            return out

        with trace.span("launches") as lsp:
            _ensure(0, False)
            for i in range(n_batches):
                for j in range(i + 1, min(i + 1 + depth, n_batches)):
                    _ensure(j, True)
                with trace.span(f"stage:{i}"):
                    cols, params = _consume(i)
                first_call = i == 0 and not plan.first_call_ms
                td0 = time.perf_counter()
                with trace.span(f"dispatch:{i}"):
                    pending.append(plan.fn(cols, params))
                if first_call:
                    # the first jit dispatch pays trace+compile; its wall
                    # time is the compile cost this query actually paid
                    compile_ms = (time.perf_counter() - td0) * 1000.0
                    plan.first_call_ms.append(compile_ms)
                    stats.compile_ms += compile_ms
                if len(pending) >= depth:
                    with trace.span("drain"):
                        batch_outs.append(self._drain(pending.pop(0), keep_device))
            while pending:
                with trace.span("drain"):
                    batch_outs.append(self._drain(pending.pop(0), keep_device))
            total_bytes = plan.scan_bytes * len(plan.batch_offsets)
            stats.kernel_bytes += total_bytes
            if lsp is not None:
                lsp.annotate(
                    batches=len(plan.batch_offsets),
                    pipelineDepth=depth,
                    backend=ops.scan_backend(),
                    kernelBytes=total_bytes,
                )

        if plan.kind == "aggregation":
            partials = self._combine_partials(batch_outs)
            return AggSegmentResult(partials=partials)

        if plan.kind == "groupby_dense":
            presence = np.asarray(batch_outs[0][0])
            for p, _ in batch_outs[1:]:
                presence = presence + np.asarray(p)
            partials = self._combine_partials([p for _, p in batch_outs])
            dense = DenseGroupData(
                presence=presence,
                partials=partials,
                key_space=tuple(
                    ("dict", gd.name, gd.dictionary.fingerprint(), gd.null_code)
                    if gd.kind == "dict"
                    else ("rawint", gd.name, gd.base, gd.cardinality)
                    for gd in plan.group_dims
                ),
                group_dims=plan.group_dims,
            )
            shim = SimpleNamespace(group_dims=plan.group_dims, aggs=plan.aggs)
            keys, sliced = sse_executor._dense_to_present(
                shim, presence, partials, ctx.num_groups_limit,
                order_trim=planner_mod.order_by_agg_index(ctx),
            )
            stats.num_groups = len(keys[0]) if keys else 0
            return GroupBySegmentResult(keys=keys, partials=sliced, dense=dense)

        if plan.kind == "groupby_sparse":
            if plan.sparse_merge_fn is not None:
                # device merge: the [B*ndev*K] stacked tables combine
                # in-graph (ops.merge_sparse_tables, order-aware trim
                # included) and only the final [num_slots] tables come home
                with trace.span("sparse_merge:device"):
                    merged = plan.sparse_merge_fn(
                        [u for u, _ in batch_outs], [p for _, p in batch_outs]
                    )
                    uniq, partials = jax.device_get(merged)
                res = sse_executor.sparse_tables_to_result(
                    plan.group_dims, plan.aggs, np.asarray(uniq), partials,
                    ctx.num_groups_limit, order_trim=None, assume_unique=True,
                )
                stats.num_groups = len(res.keys[0]) if res.keys else 0
                return res
            # host fallback (pairwise-merge partials or an ORDER BY rank the
            # device cannot derive): batches concatenate like extra devices
            # and sparse_tables_to_result folds duplicate keys on host
            with trace.span("sparse_merge:host"):
                uniq = np.concatenate([np.asarray(u).reshape(-1) for u, _ in batch_outs])
                partials = [
                    {
                        f: np.concatenate([np.asarray(p[i][f]) for _, p in batch_outs])
                        for f in batch_outs[0][1][i]
                    }
                    for i in range(len(batch_outs[0][1]))
                ]
                res = sse_executor.sparse_tables_to_result(
                    plan.group_dims, plan.aggs, uniq, partials, ctx.num_groups_limit,
                    order_trim=planner_mod.order_by_agg_index(ctx),
                )
            stats.num_groups = len(res.keys[0]) if res.keys else 0
            return res

        # selection: reassemble the [S, D] mask from the per-batch doc slices
        # (only the fresh part of a ragged tail writes back)
        S, D = stacked.num_shards, stacked.docs_per_shard
        if plan.batch_offsets == ((0, 0),) and plan.batch_docs == D:
            tmask = np.asarray(batch_outs[0])
        else:
            tmask = np.zeros((S, D), dtype=bool)
            for (off, fresh), out in zip(plan.batch_offsets, batch_outs):
                m = np.asarray(out).reshape(S, plan.batch_docs)
                tmask[:, off + fresh : off + plan.batch_docs] = m[:, fresh:]
        return self._gather_selection(ctx, plan, stacked, tmask)

    def _gather_selection(self, ctx, plan: _DistPlan, stacked, tmask: np.ndarray) -> SelectionSegmentResult:
        docids = np.nonzero(tmask.reshape(-1))[0]
        want = ctx.offset + ctx.limit
        if ctx.order_by:
            for ob in ctx.order_by:
                if not ob.expr.is_column:
                    raise NotImplementedError("selection ORDER BY supports bare columns only")
            if len(docids) > want:
                # Codes are GLOBAL sort ranks here (one shared dictionary), so
                # a numeric lexsort on codes is a correct global top-k.
                lex_keys: List[np.ndarray] = []
                for ob in reversed(ctx.order_by):
                    c = stacked.column(ob.expr.op)
                    key, null_rank = sse_executor.order_key_arrays(
                        c.codes.reshape(-1) if c.codes is not None else None,
                        c.values.reshape(-1) if c.values is not None else None,
                        c.nulls.reshape(-1) if c.nulls is not None else None,
                        docids, ob.ascending, ob.nulls_last,
                    )
                    lex_keys.append(key)
                    if null_rank is not None:
                        lex_keys.append(null_rank)
                order = np.lexsort(tuple(lex_keys))[:want]
                docids = docids[order]
        else:
            docids = docids[:want]

        arrays: Dict[str, np.ndarray] = {}

        def _decoded(name: str) -> np.ndarray:
            c = stacked.column(name)
            vals = stacked.decoded_rows(name, docids)
            if c.nulls is not None and ctx.null_handling:
                vals = np.asarray(vals, dtype=object)
                vals[c.nulls.reshape(-1)[docids]] = None
            return vals

        for name in plan.select_columns:
            arrays[name] = _decoded(name)
        for i, ob in enumerate(ctx.order_by):
            arrays[f"__ord{i}"] = _decoded(ob.expr.op)
        cols_out = plan.select_columns + [f"__ord{i}" for i in range(len(ctx.order_by))]
        return SelectionSegmentResult(columns=cols_out, arrays=arrays)


def sse_executor_needed_columns(ctx: QueryContext, stacked) -> List[str]:
    """Column set the kernel touches (planner._needed_columns against the
    stacked facade)."""
    from pinot_tpu.query.planner import _needed_columns

    view = SimpleNamespace(schema=stacked.schema)
    return _needed_columns(ctx, view)


class ReplicatedEngine:
    """QPS deployment of the 2-D mesh: one 1-D DistributedEngine per
    replica row, each holding a FULL copy of every registered table on its
    own disjoint device set (replica-group serving, SURVEY.md 2.5).

    Routing: a query goes whole to one replica row, rows rotate
    round-robin — concurrent load spreads across rows so sustained QPS
    scales with R.

    Placement is CoordinatorHandle-driven when a coordinator is attached:
    `mesh_placement(R)` maps journaled replica groups onto mesh rows, so
    rebalance and leader failover move the routing view and the mesh
    placement together — a row whose replica group has no live server is
    skipped by the round-robin until it recovers.

    Each row gets its OWN residency manager with an even share of the HBM
    cache budget (segment/residency.row_residency): staging and eviction
    are row-local, so one row's working set never evicts another's."""

    def __init__(
        self,
        mesh=None,
        num_replicas: int = 2,
        hbm_cache_bytes: Optional[int] = None,
        coordinator=None,
        **engine_kwargs,
    ):
        import threading

        if mesh is None:
            mesh = mesh_mod.make_mesh2d(num_replicas)
        self.mesh = mesh
        rows = mesh_mod.replica_rows(mesh)
        from pinot_tpu.segment.residency import row_residency

        self.engines: List[DistributedEngine] = []
        for r, row_mesh in enumerate(rows):
            res = row_residency(len(rows), r, total_bytes=hbm_cache_bytes)
            self.engines.append(
                DistributedEngine(
                    row_mesh,
                    axis=row_mesh.axis_names[0],
                    residency=res,
                    hbm_cache_bytes=0 if res is None else None,
                    **engine_kwargs,
                )
            )
        self.coordinator = coordinator
        self._rr = 0
        self._rr_lock = threading.Lock()
        # One dispatch lock per row: a row's collectives must never
        # interleave with another in-flight program on the SAME device set
        # (XLA's CPU collective rendezvous deadlocks when two programs'
        # participants mix).  Concurrency comes from having R rows — each
        # row is one serving pipeline — not from racing a row's mesh.
        self._row_locks = [threading.Lock() for _ in self.engines]

    @property
    def num_replicas(self) -> int:
        return len(self.engines)

    def register_table(self, name: str, stacked) -> None:
        for eng in self.engines:
            eng.register_table(name, stacked)

    def _live_rows(self) -> List[int]:
        """Rows eligible for routing: all of them standalone; with a
        coordinator attached, only rows whose mapped replica group still
        has a live server (failover parks a dead row out of the rotation
        exactly as the broker's routing view drops its servers)."""
        all_rows = list(range(len(self.engines)))
        if self.coordinator is None:
            return all_rows
        placement = self.coordinator.mesh_placement(len(self.engines))
        live = [r for r in all_rows if placement.get(r)]
        return live or all_rows

    def _next_row(self) -> int:
        rows = self._live_rows()
        with self._rr_lock:
            self._rr += 1
            return rows[self._rr % len(rows)]

    def query(self, sql: str) -> ResultTable:
        row = self._next_row()
        with self._row_locks[row]:
            return self.engines[row].query(sql)

    def execute(self, ctx: QueryContext) -> ResultTable:
        row = self._next_row()
        with self._row_locks[row]:
            return self.engines[row].execute(ctx)
