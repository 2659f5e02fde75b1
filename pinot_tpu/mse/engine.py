"""Multi-stage engine: star joins as one fused shard_map program.

Reference parity: the MSE runtime path — QueryDispatcher.submitAndReduce
(pinot-query-runtime/.../service/dispatch/QueryDispatcher.java:189-211)
shipping plan fragments to workers, LeafOperator scanning segments,
HashJoinOperator build/probe (.../runtime/operator/HashJoinOperator.java),
Hash/BroadcastExchange mailboxes, AggregateOperator, and the broker-side
final reduce.

Re-design (SURVEY.md 2.6, section 7): there are no fragments-over-gRPC.  All
participating tables are resident sharded over ONE mesh, so the whole
multi-stage plan — leaf filters on every table, the exchange, the join
build/probe, and the aggregation — traces into a single jitted shard_map
kernel whose stage boundaries are XLA collectives:

  leaf:      per-device filter masks on fact + dimension shards
  exchange:  BROADCAST (lax.all_gather of the filtered build side) or
             HASH (bucketize + lax.all_to_all of both sides)
  join:      sorted-build + searchsorted probe (mse/join.py)
  aggregate: the existing fused dense group-table kernels + psum combine

Scope: star joins — FROM fact JOIN dim ON fact.fk = dim.pk — INNER/LEFT,
aggregation or group-by on fact and/or dim attributes; build sides may have
NON-unique keys up to a bounded multiplicity (range_join expansion,
joinMaxDup, broadcast strategy, at most one such join per query).
Snowflake chains (fact→dim→dim) and join-output selection of dimension
attributes are supported.  Cross-table predicates (WHERE mixing columns of
both sides outside the ON clause) raise JoinPlanError/NotImplementedError.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from pinot_tpu.mse import exchange as ex
from pinot_tpu.mse.join import KEY_SENTINEL, lookup_join, range_join
from pinot_tpu.mse.plan import JoinPlanError, ResolvedQuery, resolve
from pinot_tpu.parallel import mesh as mesh_mod
from pinot_tpu.parallel.engine import (
    _psum_field,
    _ShardView,
    flatten_cols,
    make_agg_inputs,
)
from pinot_tpu.query import executor as sse_executor
from pinot_tpu.query import planner as planner_mod
from pinot_tpu.query import reduce as reduce_mod
from pinot_tpu.query.filter import FilterCompiler
from pinot_tpu.query.ir import Expr, QueryContext
from pinot_tpu.query.planner import GroupDim
from pinot_tpu.query.result import (
    AggSegmentResult,
    DenseGroupData,
    ExecutionStats,
    GroupBySegmentResult,
    ResultTable,
    SelectionSegmentResult,
)
from pinot_tpu.spi.schema import DataType
from pinot_tpu.utils.perf import scan_bytes_per_row
from types import SimpleNamespace

_INT_KEY_TYPES = (DataType.INT, DataType.LONG, DataType.TIMESTAMP, DataType.BOOLEAN)


def _order_pretrim(order_by, ord_cols, want: int, is_str: List[bool]):
    """Vectorized top-`want` row indices consistent with reduce._sorted_order
    (asc/desc + nulls placement, stable ties).  `is_str` comes from the
    DECLARED column types — numeric-LOOKING strings must rank
    lexicographically like the final Python `<` comparator, never
    numerically (review-caught).  Returns None when a column's values defy
    coding (caller falls back to the full sort).  int64 order values round
    through float64 here (>2^53 ties may trim the 'wrong' equal-ranked row —
    a row set the comparator deems equal)."""
    n = len(ord_cols[0])
    keys = []
    for ob, vals, s in zip(reversed(order_by), reversed(ord_cols), reversed(is_str)):
        a = np.asarray(vals, dtype=object)
        isnull = np.array([v is None for v in a], dtype=bool)
        body = a[~isnull]
        k = np.empty(n, dtype=np.float64)
        try:
            if s:
                # unique over the RAW objects: python `<` ordering (str AND
                # bytes alike) must match the final comparator —
                # astype(str) would rank bytes by their repr (review-caught)
                _, inv = np.unique(body, return_inverse=True)
                num = inv.astype(np.float64)
            else:
                num = body.astype(np.float64)
            k[~isnull] = num if ob.ascending else -num
        except (ValueError, TypeError):
            return None
        k[isnull] = -np.inf if not ob.nulls_last else np.inf
        keys.append(k)
    return np.lexsort(tuple(keys))[:want]


def _max_multiplicity(dim_st, dcol) -> int:
    """Max repeats of one key in the build column (flat order = input order,
    padding at the tail)."""
    arr = dcol.codes if dcol.has_dictionary else dcol.values
    flat = np.asarray(arr).reshape(-1)[: dim_st.num_docs]
    if dcol.has_dictionary:
        counts = np.bincount(flat.astype(np.int64), minlength=dcol.dictionary.cardinality)
    else:
        _, counts = np.unique(flat, return_counts=True)
    return int(counts.max()) if len(counts) else 1


@dataclass
class _JoinPlan:
    """Compile-time recipe for one join stage."""

    dim_table: str
    join_type: str
    fact_key: str
    dim_key: str
    build_key_fn: Callable  # (dim_cols) -> int64 keys
    probe_key_fn: Callable  # (fact_cols, params) -> int64 keys (fact probes)
    attrs: List[str]  # dim columns gathered through the join
    # max build-key multiplicity (1 = unique PK join; >1 = bounded M:N
    # expansion via range_join — see mse/join.py)
    max_dup: int = 1
    # snowflake chain (probe key owned by an earlier-joined dim): index of
    # the parent join whose gathered value array supplies the probe keys
    parent: Optional[int] = None
    # parent columns gathered as int64 VALUES for child probes (chains)
    val_attrs: List[str] = None
    # child-side translate param key (string chain keys: parent dict code ->
    # child build key space)
    trans_key: Optional[str] = None


@dataclass
class _MsePlan:
    kind: str  # "aggregation" | "groupby_dense"
    fn: Callable
    params: Dict[str, Any]
    fact_needed: List[str]
    dim_needed: Dict[str, List[str]]
    aggs: List[Any]
    group_dims: List[GroupDim]
    num_groups: int
    strategy: str  # "broadcast" | "shuffle"
    rq: ResolvedQuery
    # namespace -> param keys sharded on the device axis (index bitmaps)
    sharded_by_ns: Dict[str, frozenset] = None
    index_uses: Tuple = ()
    # selection kind: output columns + per-join (table, join_type) in topo
    # order + the M:N expansion join index (host-side row assembly)
    select_columns: List[str] = None
    joins_info: List[Tuple[str, str]] = None
    dup_idx: Optional[int] = None
    # bytes the fact-side scan must read (utils/perf.scan_bytes_per_row x
    # the fact table's rows; dim tables are broadcast-small by strategy),
    # counted when the plan-cache entry is built
    scan_bytes: float = 0.0
    # wall ms of the program's first call (trace + compile), empty until it
    # ran: the list is the plan-cache entry's, shared with the plan every
    # hit builds
    first_call_ms: List[float] = field(default_factory=list)
    # shuffle bucket slack this plan's kernel was TRACED with (cap_f bakes
    # into the program, so slack is part of the plan-cache key); the
    # overflow back-pressure loop doubles it and re-plans
    slack: float = 2.0


class ExchangeOverflowError(RuntimeError):
    """A hash exchange dropped rows (bucket capacity exceeded).  Carries the
    slack the failing plan ran with so the engine's back-pressure loop can
    re-plan with a doubled slack (execute's retry — the TPU analog of
    mailbox back-pressure)."""

    def __init__(self, overflow: int, slack: float):
        self.overflow = int(overflow)
        self.slack = float(slack)
        super().__init__(
            f"hash exchange dropped {self.overflow} rows at shuffleSlack="
            f"{self.slack} (bucket capacity exceeded)"
        )


class MultiStageEngine:
    """Join-capable engine over StackedTables sharing one mesh (1-D seg or
    2-D replica x shard — parallel/mesh.data_axes; on 2-D, exchanges span
    the axes tuple and combines reduce hierarchically, shard/ICI first)."""

    def __init__(self, mesh=None, axis="seg", tables: Optional[Dict[str, Any]] = None):
        if mesh is None:
            from pinot_tpu.parallel.mesh import default_mesh

            mesh = default_mesh(axis if isinstance(axis, str) else axis[0])
        from pinot_tpu.parallel import mesh as mesh_mod
        from pinot_tpu.query.planner import _plan_cache_entries
        from pinot_tpu.utils.cache import LruCache

        self.mesh = mesh
        self.axes = mesh_mod.data_axes(mesh)
        self.axis = self.axes[0] if len(self.axes) == 1 else self.axes
        self.tables: Dict[str, Any] = tables if tables is not None else {}
        # plan-cache bytes charge the process host ledger the admission
        # controller tracks (runtime import: admission is cluster-layer)
        from pinot_tpu.cluster.admission import process_host_budget

        self._plan_cache = LruCache(
            max_entries=_plan_cache_entries(), name="compile.mse", budget=process_host_budget()
        )

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    def register_table(self, name: str, stacked) -> None:
        if stacked.num_shards % self.num_devices:
            raise ValueError(
                f"num_shards={stacked.num_shards} not divisible by mesh size {self.num_devices}"
            )
        self.tables[name] = stacked
        for k in [k for k in self.tables if k.startswith(name + "@")]:
            del self.tables[k]

    def query(self, sql: str) -> ResultTable:
        from pinot_tpu.sql.parser import parse_query

        return self.execute(parse_query(sql))

    # ------------------------------------------------------------------
    def execute(self, ctx: QueryContext) -> ResultTable:
        t0 = time.perf_counter()
        # Overflow back-pressure loop: a shuffle plan whose fixed-capacity
        # exchange buckets dropped rows re-plans with a DOUBLED slack
        # (bounded by _backoff_slack) and re-runs.  Results are exact after
        # the retry — dropped rows never fold into partials because the
        # host checks the psum'd overflow counter before consuming output.
        slack_override: Optional[float] = None
        while True:
            plan = self._plan(ctx, slack=slack_override)
            rq = plan.rq
            fact_st = self.tables[rq.fact]
            stats = ExecutionStats(
                num_segments_queried=fact_st.num_shards,
                num_segments_processed=fact_st.num_shards,
                num_docs_scanned=fact_st.num_docs
                + sum(self.tables[j.table].num_docs for j in rq.joins),
                total_docs=fact_st.num_docs,
            )
            fact_cols, fact_valid = fact_st.to_device(self.mesh, self.axis, plan.fact_needed)
            dim_cols, dim_valids = [], []
            for j in rq.joins:
                st = self.tables[j.table]
                c, v = st.to_device(self.mesh, self.axis, plan.dim_needed[j.table])
                dim_cols.append(c)
                dim_valids.append(v)
            stats.add_index_uses(plan.index_uses)
            rep = NamedSharding(self.mesh, P())
            row = NamedSharding(self.mesh, P(self.axis, None))
            params = {}
            for k, v in plan.params.items():
                if isinstance(v, dict):
                    ns = (plan.sharded_by_ns or {}).get(k, frozenset())
                    params[k] = {
                        k2: jax.device_put(v2, row if k2 in ns else rep) for k2, v2 in v.items()
                    }
                else:
                    params[k] = jax.device_put(v, rep)
            try:
                result = self._run(rq.ctx, plan, fact_cols, fact_valid, dim_cols, dim_valids, params, stats)
                break
            except ExchangeOverflowError as e:
                slack_override = self._backoff_slack(rq.ctx, e)
        out = reduce_mod.reduce_results(rq.ctx, [result], stats)
        out.stats.time_ms = (time.perf_counter() - t0) * 1000
        from pinot_tpu.query.shape import shape_digest
        from pinot_tpu.utils import perf

        perf.SHAPE_STATS.record(
            rq.fact,
            shape_digest(getattr(self, "_last_shape_fp", "")),
            rows=out.stats.num_docs_scanned,
            time_ms=out.stats.time_ms,
            kernel_bytes=out.stats.kernel_bytes,
            compile_ms=out.stats.compile_ms,
            cache_hit=getattr(self, "_last_plan_cache_hit", None),
        )
        return out

    # ------------------------------------------------------------------
    def _backoff_slack(self, ctx: QueryContext, err: ExchangeOverflowError) -> float:
        """Back-pressure response to a bucket overflow: double the slack,
        bounded by shuffleSlackCap (default ndev^2 — at that slack every
        bucket can hold the whole global row set, so a further overflow is
        impossible and anything still failing is a bug, not skew)."""
        ndev = self.num_devices
        cap = float(ctx.options.get("shuffleSlackCap", float(ndev * ndev)))
        if err.slack >= cap:
            raise RuntimeError(
                f"hash exchange still dropped {err.overflow} rows at "
                f"shuffleSlack={err.slack} (cap {cap}); raise the "
                "shuffleSlackCap query option if the key skew is expected"
            ) from err
        from pinot_tpu.utils.metrics import METRICS

        METRICS.counter("mse.exchangeOverflowRetries").inc()
        return min(err.slack * 2.0, cap)

    def _plan(self, ctx: QueryContext, slack: Optional[float] = None) -> _MsePlan:
        from pinot_tpu.analysis.compile_audit import MSE_AUDIT
        from pinot_tpu.query.shape import column_info_from, params_structure

        rq = resolve(ctx, self.tables)
        strategy = self._strategy(ctx, rq)
        if slack is None:
            slack = float(ctx.options.get("shuffleSlack", 2.0))
        if strategy != "shuffle":
            slack = 0.0  # broadcast plans never bucketize: one cache entry

        def _info(name: str):
            # column shapes resolve through the owning table (join queries
            # span several); unknown columns bake their literals into the key
            t = getattr(rq, "owner", {}).get(name)
            if t is None or t not in self.tables:
                return None
            return column_info_from(self.tables[t])(name)

        key = (
            rq.ctx.shape_fingerprint(_info),
            tuple(self.tables[t].signature() for t in [rq.fact] + [j.table for j in rq.joins]),
            strategy,
            self.axis,
            self.num_devices,
            # slack bakes into the traced kernel as the bucket capacity, so
            # a retry at doubled slack MUST miss here — reusing the old
            # kernel would silently re-drop the same rows
            slack,
        )
        cached = self._plan_cache.get(key)
        if cached is not None:
            # rebind literals into a fresh plan around the cached jitted
            # kernel; a params-structure mismatch means the shape audit was
            # wrong for this query — count it as the compile it would be
            plan = self._build_plan(rq, strategy, slack, compiled_fn=cached.fn)
            if (
                params_structure(plan.params) == params_structure(cached.params)
                and plan.sharded_by_ns == cached.sharded_by_ns
            ):
                plan.scan_bytes = cached.scan_bytes
                plan.first_call_ms = cached.first_call_ms
                MSE_AUDIT.record_hit(key[0])
                self._last_plan_cache_hit = True
                self._last_shape_fp = key[0]
                return plan
        MSE_AUDIT.record_compile(key[0])
        self._last_plan_cache_hit = False
        self._last_shape_fp = key[0]
        plan = self._build_plan(rq, strategy, slack)
        fact_st = self.tables[rq.fact]
        plan.scan_bytes = fact_st.num_docs * scan_bytes_per_row(
            fact_st.column(n) for n in plan.fact_needed
        )
        self._plan_cache.put(key, plan)
        return plan

    def _strategy(self, ctx: QueryContext, rq: ResolvedQuery) -> str:
        opt = ctx.options.get("joinStrategy")
        if opt is not None and opt not in ("broadcast", "shuffle"):
            raise ValueError(
                f"unknown joinStrategy {opt!r} (expected 'broadcast' or 'shuffle')"
            )
        if opt == "shuffle" and len(rq.joins) > 1:
            raise NotImplementedError(
                "hash-shuffle joins partition fact rows by one key; multi-join "
                "queries must use the broadcast strategy"
            )
        is_selection = not ctx.is_aggregate and not ctx.group_by
        chained = any(j.probe_owner and j.probe_owner != rq.fact for j in rq.joins)
        if chained or is_selection:
            # snowflake chains probe through gathered parent rows; selection
            # maps build rows back to host doc ids — both need every build
            # side replicated (broadcast)
            if opt == "shuffle":
                raise NotImplementedError(
                    "snowflake chains and join-output selection require the "
                    "broadcast strategy (build rows must be globally addressable)"
                )
            return "broadcast"
        # many-to-many build sides need the broadcast expansion path
        def _dup(j) -> bool:
            dcol = self.tables[j.table].column(j.dim_key)
            distinct = dcol.dictionary.cardinality if dcol.has_dictionary else dcol.stats.cardinality
            return distinct < self.tables[j.table].num_docs

        if any(_dup(j) for j in rq.joins):
            if opt == "shuffle":
                raise NotImplementedError(
                    "many-to-many joins ride the broadcast expansion; joinStrategy='shuffle' "
                    "requires unique build keys"
                )
            return "broadcast"
        if opt in ("broadcast", "shuffle"):
            return str(opt)
        if len(rq.joins) > 1:
            return "broadcast"
        # broadcast when every build side is small enough to replicate
        threshold = int(ctx.options.get("broadcastJoinRowThreshold", 1 << 22))
        if all(self.tables[j.table].num_docs <= threshold for j in rq.joins):
            return "broadcast"
        return "shuffle"

    # ------------------------------------------------------------------
    def _key_plan(self, idx: int, rq: ResolvedQuery, params: Dict[str, Any]) -> _JoinPlan:
        j = rq.joins[idx]
        probe_owner = j.probe_owner or rq.fact
        probe_st = self.tables[probe_owner]
        dim_st = self.tables[j.table]
        fcol = probe_st.column(j.fact_key)
        dcol = dim_st.column(j.dim_key)
        is_chain = probe_owner != rq.fact
        parent = (
            next(i for i, rj in enumerate(rq.joins[:idx]) if rj.table == probe_owner)
            if is_chain
            else None
        )

        distinct = dcol.dictionary.cardinality if dcol.has_dictionary else dcol.stats.cardinality
        max_dup = 1
        if distinct < dim_st.num_docs:
            # many-to-many: bound the expansion by the true max multiplicity
            # (host-side, unfiltered — a safe static upper bound)
            max_dup = _max_multiplicity(dim_st, dcol)
            cap = int(rq.ctx.options.get("joinMaxDup", 64))
            if max_dup > cap:
                raise NotImplementedError(
                    f"join build side {j.table}.{j.dim_key} has keys repeated up to "
                    f"{max_dup}x; the static expansion is capped at joinMaxDup={cap} "
                    "(raise the option or pre-aggregate the build side)"
                )

        fname, dname = j.fact_key, j.dim_key
        trans_key = None
        probe_key = None
        string_like = dcol.data_type.is_string_like or fcol.data_type.is_string_like
        if string_like:
            if not (dcol.has_dictionary and fcol.has_dictionary):
                raise NotImplementedError("string join keys require dictionaries on both sides")
            dvals, fvals = dcol.dictionary.values, fcol.dictionary.values
            pos = np.searchsorted(dvals, fvals)
            posc = np.clip(pos, 0, max(0, len(dvals) - 1))
            ok = (dvals[posc] == fvals) if len(dvals) else np.zeros(len(fvals), bool)
            trans = np.where(ok, posc, np.iinfo(np.int64).max).astype(np.int64)
            tkey = f"join{idx}.trans"
            params[tkey] = trans
            trans_key = tkey

            def build_key(dcols, _d=dname):
                return dcols[_d]["codes"].astype(jnp.int64)

            if not is_chain:

                def probe_key(fcols, p, _f=fname, _t=tkey):
                    return p[_t][fcols[_f]["codes"].astype(jnp.int32)]

        elif dcol.data_type in _INT_KEY_TYPES and fcol.data_type in _INT_KEY_TYPES:

            def _int_key(cols, name, col):
                if col.has_dictionary:
                    return cols[name]["dict"][cols[name]["codes"].astype(jnp.int32)].astype(jnp.int64)
                return cols[name]["values"].astype(jnp.int64)

            def build_key(dcols, _d=dname, _c=dcol):
                return _int_key(dcols, _d, _c)

            if not is_chain:

                def probe_key(fcols, p, _f=fname, _c=fcol):
                    return _int_key(fcols, _f, _c)

        else:
            raise NotImplementedError(
                f"join keys must be integer or string typed "
                f"(got {fcol.data_type.value} = {dcol.data_type.value})"
            )

        # null join keys never match (SQL equi-join semantics); chain probe
        # nulls are folded in at the parent's value gather instead
        if probe_key is not None and fcol.nulls is not None:
            inner_probe = probe_key

            def probe_key(fcols, p, _f=fname, _inner=inner_probe):
                k = _inner(fcols, p)
                return jnp.where(fcols[_f]["nulls"], KEY_SENTINEL, k)

        if dcol.nulls is not None:
            inner_build = build_key

            def build_key(dcols, _d=dname, _inner=inner_build):
                k = _inner(dcols)
                return jnp.where(dcols[_d]["nulls"], KEY_SENTINEL, k)

        return _JoinPlan(
            j.table, j.join_type, fname, dname, build_key, probe_key,
            attrs=[], max_dup=max_dup, parent=parent, val_attrs=[], trans_key=trans_key,
        )

    def _dim_group_dim(
        self, expr: Expr, table: str, left_join: bool, null_handling: bool
    ) -> Tuple[GroupDim, int]:
        """Returns (GroupDim, placeholder_code): placeholder_code >= 0 marks
        the dictionary code of the SQL-NULL placeholder when a LEFT JOIN
        forces the null slot to live PAST the dictionary — the kernel remaps
        placeholder-coded rows onto the no-match slot so the NULL group does
        not split in two."""
        c = self.tables[table].column(expr.op)
        if c.has_dictionary:
            card = c.dictionary.cardinality
            null_code = -1
            if c.nulls is not None and null_handling:
                nc = c.dictionary.index_of(c.data_type.null_placeholder)
                if nc >= 0:
                    null_code = nc
            if left_join:
                placeholder = null_code  # may be -1 (no nulls stored)
                null_code = card
                card += 1
                return (
                    GroupDim(expr, c.name, "dict", card, dictionary=c.dictionary, null_code=null_code),
                    placeholder,
                )
            return (
                GroupDim(expr, c.name, "dict", card, dictionary=c.dictionary, null_code=null_code),
                -1,
            )
        if c.data_type in _INT_KEY_TYPES and c.stats.min_value is not None:
            lo, hi = int(c.stats.min_value), int(c.stats.max_value)
            rng = hi - lo + 1
            if rng <= planner_mod.MAX_DENSE_RAW_INT_RANGE:
                card, null_code = (rng + 1, rng) if left_join else (rng, -1)
                return GroupDim(expr, c.name, "rawint", card, base=lo, null_code=null_code), -1
        raise NotImplementedError(f"group-by on dimension column {expr.op} (type/range unsupported)")

    # ------------------------------------------------------------------
    def _build_plan(
        self,
        rq: ResolvedQuery,
        strategy: str,
        slack: float,
        compiled_fn: Optional[Callable] = None,
    ) -> _MsePlan:
        ctx = rq.ctx
        axis = self.axis
        ndev = self.num_devices
        fact_st = self.tables[rq.fact]
        local_rows = (fact_st.num_shards // ndev) * fact_st.docs_per_shard
        fact_view = _ShardView(fact_st, local_rows, axis=axis, ndev=ndev)
        null_handling = ctx.null_handling

        params: Dict[str, Any] = {}
        sharded_by_ns: Dict[str, frozenset] = {}
        index_uses: List[Tuple[str, str]] = []
        fc_fact = FilterCompiler(fact_view, null_handling)
        fact_filter_fn = fc_fact.compile(rq.fact_filter)
        params["fact"] = fc_fact.params

        join_plans: List[_JoinPlan] = []
        dim_filter_fns: List[Callable] = []
        dim_views: List[Any] = []
        dim_used_columns: List[set] = []
        for i, rj in enumerate(rq.joins):
            dim_st = self.tables[rj.table]
            d_local = (dim_st.num_shards // ndev) * dim_st.docs_per_shard
            dview = _ShardView(dim_st, d_local, axis=axis, ndev=ndev)
            dim_views.append(dview)
            fc = FilterCompiler(dview, null_handling)
            dim_filter_fns.append(fc.compile(rq.dim_filters[rj.table]))
            params[f"dimf{i}"] = fc.params
            sharded_by_ns[f"dimf{i}"] = frozenset(fc.row_sharded_params)
            index_uses.extend(fc.index_uses)
            dim_used_columns.append(set(fc.used_columns))
            join_plans.append(self._key_plan(i, rq, params))

        # -- snowflake chains: parents gather probe-key VALUES -------------
        for i, jp in enumerate(join_plans):
            if jp.parent is not None:
                pjp = join_plans[jp.parent]
                if pjp.max_dup > 1:
                    raise NotImplementedError(
                        f"snowflake chain through many-to-many join {pjp.dim_table!r} "
                        "is unsupported (pre-aggregate the M:N build side)"
                    )
                if jp.fact_key not in pjp.val_attrs:
                    pjp.val_attrs.append(jp.fact_key)
                if jp.max_dup > 1:
                    raise NotImplementedError(
                        "a many-to-many build side must join to the fact table directly"
                    )

        # -- aggregations (fact-side inputs only) ------------------------
        agg_specs = list(ctx.aggregations)
        for s in agg_specs:
            for col in ([] if s.expr is None else s.expr.columns()) + (
                s.filter.columns() if s.filter is not None else []
            ):
                if col != "*" and rq.owner[col] != rq.fact:
                    raise NotImplementedError(
                        f"aggregation input {col!r} belongs to joined table "
                        f"{rq.owner[col]!r}; only fact-table measures are supported"
                    )
        aggs = planner_mod.bind_aggs(agg_specs, fact_st, ctx)
        agg_filter_fns = [
            fc_fact.compile(s.filter) if s.filter is not None else None for s in agg_specs
        ]
        agg_inputs_fn = make_agg_inputs(
            agg_specs, aggs, agg_filter_fns, fact_view, fact_st, null_handling
        )
        sharded_by_ns["fact"] = frozenset(fc_fact.row_sharded_params)
        index_uses.extend(fc_fact.index_uses)

        # -- group dimensions --------------------------------------------
        group_dims: List[GroupDim] = []
        dim_of_group: List[Optional[int]] = []  # join index or None (fact)
        group_placeholder: List[int] = []  # LEFT-JOIN placeholder remap code
        for g in ctx.group_by:
            if not g.is_column:
                raise NotImplementedError(f"group-by on expression {g} not yet supported")
            t = rq.owner[g.op]
            if t == rq.fact:
                group_dims.append(planner_mod._group_dim(g, fact_view, null_handling))
                dim_of_group.append(None)
                group_placeholder.append(-1)
            else:
                ji = next(i for i, jp in enumerate(join_plans) if jp.dim_table == t)
                left = join_plans[ji].join_type == "left"
                gd, placeholder = self._dim_group_dim(g, t, left, null_handling)
                group_dims.append(gd)
                dim_of_group.append(ji)
                group_placeholder.append(placeholder)
                if g.op not in join_plans[ji].attrs:
                    join_plans[ji].attrs.append(g.op)

        select_columns: List[str] = []
        if ctx.is_aggregate and not ctx.group_by:
            kind = "aggregation"
            num_groups = 0
        elif ctx.group_by:
            kind = "groupby_dense"
            num_groups = 1
            for gd in group_dims:
                num_groups *= max(1, gd.cardinality)
            if num_groups > ctx.max_dense_groups:
                raise NotImplementedError(
                    f"join group-by key space {num_groups} exceeds maxDenseGroups "
                    f"({ctx.max_dense_groups}); high-cardinality join group-by is unsupported"
                )
        else:
            # join-output selection (round 5, VERDICT r4 #7): return joined
            # ROWS — the kernel produces the match mask + build-row indices,
            # the host gathers/decodes columns through them
            # (HashJoinOperator + LookupJoinOperator output semantics)
            kind = "selection"
            num_groups = 0
            for s in ctx.select_list:
                if not (isinstance(s, Expr) and s.is_column):
                    raise NotImplementedError(
                        f"join selection supports bare columns only (got {s})"
                    )
                if s.op == "*":
                    raise NotImplementedError("SELECT * over joins is unsupported; list columns")
                select_columns.append(s.op)
            for ob in ctx.order_by:
                if not ob.expr.is_column:
                    raise NotImplementedError("join selection ORDER BY supports bare columns only")

        planner_mod.guard_sparse_vector_fields(kind, aggs)
        if any(fn.pairwise_merge for fn in aggs):
            raise NotImplementedError(
                "pairwise-merge aggregations cannot ride the in-graph psum combine"
            )
        vranges = planner_mod.agg_vranges(agg_specs, fact_st)

        # -- needed columns ----------------------------------------------
        fact_needed: List[str] = []

        def need_fact(cols):
            for c in cols:
                if c != "*" and c not in fact_needed:
                    fact_needed.append(c)

        # filter-scanned columns come from the compiler's used set — columns
        # whose predicates resolved through an index never ship to device
        need_fact(sorted(fc_fact.used_columns))
        for s in agg_specs:
            if s.expr is not None:
                need_fact(s.expr.columns())
        for jp in join_plans:
            if jp.parent is None:  # chain probes read the PARENT DIM's rows
                need_fact([jp.fact_key])
        for g, di in zip(ctx.group_by, dim_of_group):
            if di is None:
                need_fact([g.op])
        dim_needed: Dict[str, List[str]] = {}
        for i, (jp, dview) in enumerate(zip(join_plans, dim_views)):
            cols = [jp.dim_key] + list(jp.attrs)
            cols += [a for a in jp.val_attrs if a not in cols]
            cols += [c for c in sorted(dim_used_columns[i]) if c not in cols]
            dim_needed[jp.dim_table] = cols

        # -- dim attr array access (codes for dict, raw values otherwise) --
        # Raw values stay in their source dtype until the base subtraction:
        # casting first would wrap values beyond int32 (the code AFTER the
        # subtraction always fits — cardinality <= MAX_DENSE_RAW_INT_RANGE).
        def attr_array(dcols, table: str, name: str):
            c = self.tables[table].column(name)
            if c.has_dictionary:
                return dcols[name]["codes"].astype(jnp.int32)
            return dcols[name]["values"]

        def val_array(dcols, table: str, name: str):
            """int64 probe-key VALUES of a parent-dim column for snowflake
            chains: dict codes for string keys (children translate), decoded
            values for ints; stored nulls become the never-match sentinel."""
            c = self.tables[table].column(name)
            if c.data_type.is_string_like:
                v = dcols[name]["codes"].astype(jnp.int64)
            elif c.has_dictionary:
                v = dcols[name]["dict"][dcols[name]["codes"].astype(jnp.int32)].astype(jnp.int64)
            else:
                v = dcols[name]["values"].astype(jnp.int64)
            if c.nulls is not None:
                v = jnp.where(dcols[name]["nulls"], KEY_SENTINEL, v)
            return v

        def group_code(gd: GroupDim, arr):
            if gd.kind == "rawint":
                return (arr - np.asarray(gd.base, dtype=arr.dtype)).astype(jnp.int32)
            return arr

        def fact_group_code(gd: GroupDim, fcols):
            if gd.kind == "dict":
                return fcols[gd.name]["codes"].astype(jnp.int32)
            v = fcols[gd.name]["values"]
            return (v - np.asarray(gd.base, dtype=v.dtype)).astype(jnp.int32)

        # bounded M:N expansion (at most one non-unique build side)
        dup_idxs = [i for i, jp in enumerate(join_plans) if jp.max_dup > 1]
        if len(dup_idxs) > 1:
            raise NotImplementedError(
                "at most one join may have a many-to-many build side "
                f"(got {len(dup_idxs)}); pre-aggregate the other build sides"
            )
        dup_idx = dup_idxs[0] if dup_idxs else None
        if dup_idx is not None and strategy != "broadcast":
            raise NotImplementedError("many-to-many joins require the broadcast strategy")

        # ------------------------------------------------------------------
        def shard_kernel(fact_cols, fact_valid, dim_cols_list, dim_valids, params):
            fcols = flatten_cols(fact_cols)
            fmask, _ = fact_filter_fn(fcols, params["fact"])
            fmask = fmask & fact_valid.reshape(-1)
            overflow = jnp.int32(0)

            # leaf + exchange + probe per join (topological order: snowflake
            # parents run before their children)
            gathered: Dict[Tuple[int, str], Any] = {}
            gathered_vals: Dict[Tuple[int, str], Any] = {}  # chain probe keys
            matches: List[Any] = []
            brows: List[Any] = []

            if strategy == "broadcast":
                probe_cols = fcols
                probe_mask = fmask
                for i, jp in enumerate(join_plans):
                    dcols = flatten_cols(dim_cols_list[i])
                    dmask, _ = dim_filter_fns[i](dcols, params[f"dimf{i}"])
                    dmask = dmask & dim_valids[i].reshape(-1)
                    side = {"key": jp.build_key_fn(dcols), "ok": dmask}
                    for a in jp.attrs:
                        side[a] = attr_array(dcols, jp.dim_table, a)
                    for a in jp.val_attrs:
                        side["__val__" + a] = val_array(dcols, jp.dim_table, a)
                    g = ex.broadcast_rows(side, axis)
                    if jp.parent is None:
                        pk = jp.probe_key_fn(fcols, params)
                    else:
                        # chain probe: the parent's gathered value per fact row
                        pv = gathered_vals[(jp.parent, jp.fact_key)]
                        if jp.trans_key is not None:
                            t = params[jp.trans_key]
                            idx = jnp.clip(pv, 0, t.shape[0] - 1).astype(jnp.int32)
                            pk = jnp.where(pv == KEY_SENTINEL, KEY_SENTINEL, t[idx])
                        else:
                            pk = pv
                    if i == dup_idx:
                        # bounded M:N: [P, max_dup] expansion; validity folds
                        # into exp_mask below, not the 1-D probe_mask
                        brow, match = range_join(g["key"], g["ok"], pk, jp.max_dup)
                        matches.append(match)
                    else:
                        brow, match = lookup_join(g["key"], g["ok"], pk)
                        matches.append(match)
                        if jp.join_type == "inner":
                            probe_mask = probe_mask & match
                    brows.append(brow)
                    for a in jp.attrs:
                        gathered[(i, a)] = g[a][brow]
                    for a in jp.val_attrs:
                        gathered_vals[(i, a)] = jnp.where(
                            match, g["__val__" + a][brow], KEY_SENTINEL
                        )
            else:  # hash shuffle
                # fact payload: key per join, group codes, agg inputs
                payload: Dict[str, Any] = {}
                for i, jp in enumerate(join_plans):
                    payload[f"k{i}"] = jp.probe_key_fn(fcols, params)
                for gi, (gd, di) in enumerate(zip(group_dims, dim_of_group)):
                    if di is None:
                        payload[f"g{gi}"] = fact_group_code(gd, fcols)
                inputs = agg_inputs_fn(fcols, params["fact"], fmask)
                for ai, (v, m) in enumerate(inputs):
                    payload[f"av{ai}"] = jnp.broadcast_to(v, fmask.shape)
                    payload[f"am{ai}"] = m
                # partition fact rows by the join key's hash (single join
                # only — enforced in _strategy)
                dest = ex.hash_dest(payload["k0"], ndev)
                cap_f = max(1, int(-(-local_rows // ndev) * slack))
                recv, rvalid, ovf = ex.hash_repartition(payload, dest, fmask, ndev, cap_f, axis)
                overflow = overflow + ovf
                probe_cols = recv
                probe_mask = rvalid

                for i, jp in enumerate(join_plans):
                    dcols = flatten_cols(dim_cols_list[i])
                    dmask, _ = dim_filter_fns[i](dcols, params[f"dimf{i}"])
                    dmask = dmask & dim_valids[i].reshape(-1)
                    dkey = jp.build_key_fn(dcols)
                    side = {"key": dkey}
                    for a in jp.attrs:
                        side[a] = attr_array(dcols, jp.dim_table, a)
                    d_local = dkey.shape[0]
                    cap_d = max(1, int(-(-d_local // ndev) * slack))
                    drecv, dvalid_r, dovf = ex.hash_repartition(
                        side, ex.hash_dest(dkey, ndev), dmask, ndev, cap_d, axis
                    )
                    overflow = overflow + dovf
                    brow, match = lookup_join(drecv["key"], dvalid_r, recv[f"k{i}"])
                    matches.append(match)
                    if jp.join_type == "inner":
                        probe_mask = probe_mask & match
                    for a in jp.attrs:
                        gathered[(i, a)] = drecv[a][brow]

            # -- M:N expansion mask ([P, D] slot validity) -----------------
            exp_mask = None
            if dup_idx is not None:
                D = join_plans[dup_idx].max_dup
                m2 = matches[dup_idx]
                if join_plans[dup_idx].join_type == "left":
                    # LEFT with zero matches: one surviving slot (0) carrying
                    # the null dim code
                    nomatch = ~jnp.any(m2, axis=1)
                    slot0 = jnp.arange(D) == 0
                    m2 = m2 | (nomatch[:, None] & slot0[None, :])
                exp_mask = probe_mask[:, None] & m2

            def _expand_rows(v):
                """[P] row array -> flat [P*D] under the expansion."""
                return jnp.broadcast_to(v[:, None], exp_mask.shape).reshape(-1)

            # -- selection: ship match mask + build-row indices only --------
            if kind == "selection":
                out = {"mask": probe_mask}
                for i in range(len(join_plans)):
                    out[f"brow{i}"] = brows[i].astype(jnp.int32)
                    out[f"match{i}"] = matches[i]
                if exp_mask is not None:
                    out["exp"] = exp_mask
                return out, overflow

            # -- aggregate ------------------------------------------------
            if strategy == "broadcast":
                inputs = agg_inputs_fn(fcols, params["fact"], probe_mask)
            else:
                inputs = [
                    (probe_cols[f"av{ai}"], probe_cols[f"am{ai}"] & probe_mask)
                    for ai in range(len(agg_specs))
                ]
            if exp_mask is not None:
                flat_exp = exp_mask.reshape(-1)
                inputs = [
                    (
                        _expand_rows(jnp.broadcast_to(v, probe_mask.shape)),
                        _expand_rows(m) & flat_exp,
                    )
                    for v, m in inputs
                ]
                tmask = flat_exp
            else:
                tmask = probe_mask

            if kind == "aggregation":
                partials = [fn.partial(v, m) for fn, (v, m) in zip(aggs, inputs)]
                partials = [
                    {f: _psum_field(f, x, axis) for f, x in p.items()} for p in partials
                ]
                return partials, overflow

            # group key assembly
            key = None
            for gi, (gd, di) in enumerate(zip(group_dims, dim_of_group)):
                if di is None:
                    if strategy == "broadcast":
                        code = fact_group_code(gd, fcols)
                    else:
                        code = probe_cols[f"g{gi}"]
                    if exp_mask is not None:
                        code = _expand_rows(code)
                else:
                    code = group_code(gd, gathered[(di, gd.expr.op)])
                    match = matches[di]
                    if join_plans[di].join_type == "left":
                        code = jnp.where(match, code, jnp.int32(gd.null_code))
                        # stored-NULL placeholder joins the no-match NULL slot
                        ph = group_placeholder[gi]
                        if ph >= 0:
                            code = jnp.where(code == jnp.int32(ph), jnp.int32(gd.null_code), code)
                    else:
                        code = jnp.where(match, code, jnp.int32(0))
                    if exp_mask is not None:
                        code = code.reshape(-1) if di == dup_idx else _expand_rows(code)
                code = jnp.clip(code, 0, gd.cardinality - 1)
                key = code if key is None else key * jnp.int32(gd.cardinality) + code
            presence, partials = planner_mod.grouped_partials(
                aggs, inputs, tmask, key, num_groups, vranges
            )
            presence = mesh_mod.psum_hierarchical(presence, axis)
            partials = [
                {f: _psum_field(f, x, axis) for f, x in p.items()} for p in partials
            ]
            return (presence, partials), overflow

        # -- specs ----------------------------------------------------------
        def _col_specs(cols):
            out = {}
            for name, entry in cols.items():
                out[name] = {
                    k: (P(axis, None) if k in ("codes", "values", "nulls") else P())
                    for k in entry
                }
            return out

        mesh = self.mesh

        def _param_specs(params):
            out = {}
            for k, v in params.items():
                if isinstance(v, dict):
                    ns = sharded_by_ns.get(k, frozenset())
                    out[k] = {k2: (P(axis, None) if k2 in ns else P()) for k2 in v}
                else:
                    out[k] = P()
            return out

        if kind == "selection":
            sel_specs = {"mask": P(axis)}
            for i in range(len(join_plans)):
                two_d = i == dup_idx
                sel_specs[f"brow{i}"] = P(axis, None) if two_d else P(axis)
                sel_specs[f"match{i}"] = P(axis, None) if two_d else P(axis)
            if dup_idx is not None:
                sel_specs["exp"] = P(axis, None)
            out_spec = (sel_specs, P())
        else:
            out_spec = (P(), P())

        def run(fact_cols, fact_valid, dim_cols_list, dim_valids, params):
            kern = jax.shard_map(
                shard_kernel,
                mesh=mesh,
                in_specs=(
                    _col_specs(fact_cols),
                    P(axis, None),
                    tuple(_col_specs(c) for c in dim_cols_list),
                    tuple(P(axis, None) for _ in dim_valids),
                    _param_specs(params),
                ),
                out_specs=out_spec,
                check_vma=False,
            )
            return kern(fact_cols, fact_valid, tuple(dim_cols_list), tuple(dim_valids), params)

        fn = compiled_fn if compiled_fn is not None else jax.jit(run)
        return _MsePlan(
            kind=kind,
            fn=fn,
            params=params,
            fact_needed=fact_needed,
            dim_needed=dim_needed,
            aggs=aggs,
            group_dims=group_dims,
            num_groups=num_groups,
            strategy=strategy,
            rq=rq,
            sharded_by_ns=sharded_by_ns,
            index_uses=tuple(index_uses),
            select_columns=select_columns,
            joins_info=[(jp.dim_table, jp.join_type) for jp in join_plans],
            dup_idx=dup_idx,
            slack=slack,
        )

    # ------------------------------------------------------------------
    def _run(self, ctx, plan: _MsePlan, fact_cols, fact_valid, dim_cols, dim_valids, params, stats):
        first_call = not plan.first_call_ms
        td0 = time.perf_counter()
        out, overflow = plan.fn(fact_cols, fact_valid, dim_cols, dim_valids, params)
        if first_call:
            # the first jit dispatch pays trace+compile; its wall time is the
            # compile cost this query actually paid
            compile_ms = (time.perf_counter() - td0) * 1000.0
            plan.first_call_ms.append(compile_ms)
            stats.compile_ms += compile_ms
        stats.kernel_bytes += plan.scan_bytes
        overflow = int(jax.device_get(overflow))
        if overflow:
            # execute()'s back-pressure loop catches this, doubles the slack
            # (bounded by shuffleSlackCap) and re-plans + re-runs
            raise ExchangeOverflowError(overflow, plan.slack)
        if plan.kind == "aggregation":
            return AggSegmentResult(partials=jax.device_get(out))
        if plan.kind == "selection":
            return self._gather_join_selection(ctx, plan, jax.device_get(out))
        presence, partials = jax.device_get(out)
        presence = np.asarray(presence)
        shim = SimpleNamespace(group_dims=plan.group_dims, aggs=plan.aggs)
        dense = DenseGroupData(
            presence=presence,
            partials=partials,
            key_space=sse_executor._key_space_id(shim),
            group_dims=plan.group_dims,
        )
        keys, sliced = sse_executor._dense_to_present(
            shim, presence, partials, ctx.num_groups_limit,
            order_trim=planner_mod.order_by_agg_index(ctx),
        )
        stats.num_groups = len(keys[0]) if keys else 0
        return GroupBySegmentResult(keys=keys, partials=sliced, dense=dense)

    # ------------------------------------------------------------------
    def _gather_join_selection(self, ctx, plan: _MsePlan, sel):
        """Join-output selection rows (HashJoinOperator output semantics):
        the kernel shipped [rows] match masks + build-row indices (global dim
        flat order — broadcast gathers in mesh order); columns decode host-
        side through them.  LEFT no-match rows yield SQL NULL dim values."""
        rq = plan.rq
        fact_st = self.tables[rq.fact]
        mask = np.asarray(sel["mask"]).reshape(-1)
        exp = np.asarray(sel["exp"]) if "exp" in sel else None
        if exp is not None:
            frow, slot = np.nonzero(exp)
        else:
            frow = np.nonzero(mask)[0]
            slot = None
        want = ctx.offset + ctx.limit

        def col_out(name: str, rows: np.ndarray, slots) -> np.ndarray:
            t = rq.owner[name]
            if t == rq.fact:
                c = fact_st.column(name)
                vals = fact_st.decoded_rows(name, rows)
                if c.nulls is not None and ctx.null_handling:
                    vals = np.asarray(vals, dtype=object)
                    vals[c.nulls.reshape(-1)[rows]] = None
                return vals
            ji = next(i for i, (tb, _) in enumerate(plan.joins_info) if tb == t)
            st = self.tables[t]
            if ji == plan.dup_idx:
                br = np.asarray(sel[f"brow{ji}"])[rows, slots]
                mt = np.asarray(sel[f"match{ji}"])[rows, slots]
            else:
                br = np.asarray(sel[f"brow{ji}"])[rows]
                mt = np.asarray(sel[f"match{ji}"])[rows]
            total = st.num_shards * st.docs_per_shard
            safe = np.clip(br, 0, max(0, total - 1))
            c = st.column(name)
            vals = np.asarray(st.decoded_rows(name, safe), dtype=object)
            if c.nulls is not None and ctx.null_handling:
                vals[c.nulls.reshape(-1)[safe]] = None
            vals[~mt] = None  # LEFT no-match: SQL NULL (inner rows always match)
            return vals

        if not ctx.order_by and len(frow) > want:
            frow = frow[:want]
            slot = slot[:want] if slot is not None else None
        elif ctx.order_by and len(frow) > want:
            # top-`want` pre-trim under the same comparator the reduce sort
            # applies — without it every matching row materializes host-side
            # as object arrays for a LIMIT-sized answer (review-caught)
            def _col_type(name: str):
                t = rq.owner[name]
                st = fact_st if t == rq.fact else self.tables[t]
                return st.column(name).data_type

            ord_cols = [col_out(ob.expr.op, frow, slot) for ob in ctx.order_by]
            is_str = [_col_type(ob.expr.op).is_string_like for ob in ctx.order_by]
            keep = _order_pretrim(ctx.order_by, ord_cols, want, is_str)
            if keep is not None:
                frow = frow[keep]
                slot = slot[keep] if slot is not None else None

        arrays: Dict[str, np.ndarray] = {}
        for name in plan.select_columns:
            arrays[name] = col_out(name, frow, slot)
        for i, ob in enumerate(ctx.order_by):
            arrays[f"__ord{i}"] = col_out(ob.expr.op, frow, slot)
        cols_out = plan.select_columns + [f"__ord{i}" for i in range(len(ctx.order_by))]
        return SelectionSegmentResult(columns=cols_out, arrays=arrays)
