"""Broker reduce: merge per-segment results, HAVING/ORDER BY/LIMIT, format.

Reference parity: BrokerReduceService.reduceOnDataTable
(pinot-core/.../query/reduce/BrokerReduceService.java:65) and its per-shape
reducers (GroupByDataTableReducer, AggregationDataTableReducer,
SelectionDataTableReducer) + PostAggregationHandler/HAVING handling.

Re-design: partials arrive as numpy arrays, not serialized DataTables.  The
group-by merge has two paths:
  * ALIGNED DENSE: when every segment produced a dense group table over the
    SAME key space (shared dictionary fingerprints — always true for stacked/
    aligned tables, M2), merging is pure elementwise array combination; this
    is the shape that becomes a psum over ICI in the distributed engine.
  * GENERIC: decoded-key hash merge (GroupByDataTableReducer's IndexedTable
    analog) for heterogeneous segments.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pinot_tpu.query.functions import FIELD_COMBINE, combine_field, field_identity, for_spec
from pinot_tpu.query.ir import (
    AggregationSpec,
    Expr,
    FilterNode,
    FilterOp,
    OrderByExpr,
    PredicateType,
    QueryContext,
)
from pinot_tpu.query.result import (
    AggSegmentResult,
    ExecutionStats,
    GroupBySegmentResult,
    ResultTable,
    SelectionSegmentResult,
)


def reduce_results(ctx: QueryContext, results: List[Any], stats: ExecutionStats, trace=None) -> ResultTable:
    """`trace` (the broker's): a group-by whose aggregations hold vector
    fields says what their estimator step cost (span `sketch_final`)."""
    if ctx.is_aggregate and not ctx.group_by:
        return _reduce_aggregation(ctx, results, stats)
    if ctx.group_by:
        return _reduce_groupby(ctx, results, stats, trace)
    return _reduce_selection(ctx, results, stats)


# ---------------------------------------------------------------------------
# Aggregation-only
# ---------------------------------------------------------------------------
def _reduce_aggregation(ctx: QueryContext, results: List[AggSegmentResult], stats: ExecutionStats) -> ResultTable:
    aggs = [for_spec(a).bind_reduce(ctx, a) for a in ctx.aggregations]
    merged: Optional[List[Dict[str, np.ndarray]]] = None
    for r in results:
        if merged is None:
            merged = [dict(p) for p in r.partials]
        else:
            merged = [fn.merge(m, p) for fn, m, p in zip(aggs, merged, r.partials)]
    # finals for every aggregation (selected + hidden extras), then resolve
    # select items — post-aggregation arithmetic evaluates over the env
    specs = list(ctx.aggregations)
    env: Dict[str, Any] = {}
    for i, (spec, fn) in enumerate(zip(specs, aggs)):
        if merged is None:
            val = 0 if fn.name == "count" else None  # all segments pruned
        else:
            val = fn.final(merged[i])
            if not isinstance(val, (list, tuple)):
                val = _scalar(val)
        cell = np.empty(1, dtype=object)  # explicit: np.asarray would
        cell[0] = np.nan if val is None else val  # 2D-ify a list value
        _register_agg_env(env, spec, cell)
    row = []
    for s in ctx.select_list:
        if isinstance(s, AggregationSpec):
            v = env[s.fingerprint()][0]
        else:
            v = _eval_env_expr(s, env, 1)[0]
        row.append(_scalar(v) if not isinstance(v, (str, bytes, list, tuple, type(None))) else v)
    return ResultTable(columns=ctx.column_names_out(), rows=[tuple(row)], stats=stats)


def _scalar(v):
    v = np.asarray(v)
    x = v.item() if v.ndim == 0 else v
    if isinstance(x, float) and (math.isnan(x) or math.isinf(x)):
        return None
    return x


def _register_agg_env(env: Dict[str, Any], spec: AggregationSpec, finals) -> None:
    """Register one aggregation's final array under every fingerprint form
    HAVING/ORDER BY/post-aggregation may reference it by: the spec itself,
    the plain call `sum(v)` (literal args re-attached), and explicit
    `count(*)`.  Shared by the scalar and group-by reducers."""
    env[spec.fingerprint()] = finals
    if spec.filter is None:
        args = list(spec.expr and [spec.expr] or []) + [Expr.lit(a) for a in spec.literal_args]
        env.setdefault(Expr.call(spec.function, *args).fingerprint(), finals)
        if spec.expr is None and not spec.literal_args:
            env.setdefault(Expr.call(spec.function, Expr.col("*")).fingerprint(), finals)


# ---------------------------------------------------------------------------
# Group-by
# ---------------------------------------------------------------------------
def _finals(aggs, partials, trace) -> List[np.ndarray]:
    """Every aggregation's final column from its merged partials.  The
    vector-field functions' step (HLL's estimator over [groups, m]
    registers, a percentile's walk of each group's bins) sits in a span
    `sketch_final` of `trace`: the groups, the cells read, its CPU time."""

    def final(i: int) -> np.ndarray:
        return np.atleast_1d(np.asarray(aggs[i].final(partials[i])))

    out = {i: final(i) for i, fn in enumerate(aggs) if not fn.vector_fields}
    if len(out) < len(aggs):
        vectors = [a for i, p in enumerate(partials) if i not in out for a in p.values() if np.ndim(a) > 1]
        span = trace.span(
            "sketch_final", cpu=True, groups=len(vectors[0]) if vectors else 0, cells=sum(int(np.size(a)) for a in vectors),
        ) if trace is not None else contextlib.nullcontext()
        with span:
            out.update((i, final(i)) for i in range(len(aggs)) if i not in out)
    return [out[i] for i in range(len(aggs))]


def _reduce_groupby(
    ctx: QueryContext, results: List[GroupBySegmentResult], stats: ExecutionStats, trace=None
) -> ResultTable:
    aggs = [for_spec(a).bind_reduce(ctx, a) for a in ctx.aggregations]
    results = [r for r in results if r is not None]
    if not results:
        return ResultTable(columns=ctx.column_names_out(), rows=[], stats=stats)

    # -- aligned dense fast path ---------------------------------------
    key_spaces = {r.dense.key_space for r in results if r.dense is not None}
    if len(results) > 1 and len(key_spaces) == 1 and all(r.dense is not None for r in results):
        d0 = results[0].dense
        presence = np.zeros_like(d0.presence)
        merged_partials = [
            {f: np.full_like(arr, _ident_like(f, arr)) for f, arr in p.items()}
            if not fn.pairwise_merge
            else None
            for fn, p in zip(aggs, d0.partials)
        ]
        for r in results:
            presence = presence + r.dense.presence
            for ai, (fn, p) in enumerate(zip(aggs, r.dense.partials)):
                if fn.pairwise_merge:
                    # coupled fields (LASTWITHTIME's (t, v)): elementwise
                    # fn.merge over the whole dense table, not per-field
                    cur = merged_partials[ai]
                    merged_partials[ai] = p if cur is None else fn.merge(cur, p)
                    continue
                mp = merged_partials[ai]
                for f in mp:
                    mp[f] = combine_field(f, mp[f], np.asarray(p[f]))
        present = np.nonzero(presence > 0)[0]
        keys = _decode_dense_keys(d0.group_dims, present)
        partials = [{f: arr[present] for f, arr in p.items()} for p in merged_partials]
    elif len(results) == 1:
        keys, partials = results[0].keys, results[0].partials
    else:
        keys, partials = _hash_merge(results, aggs)
        stats.tables_merged_by_value = len(results)

    stats.num_groups = len(keys[0]) if keys else 0
    finals = _finals(aggs, partials, trace)

    # fingerprint -> column array, for select/having/order resolution
    env: Dict[str, np.ndarray] = {}
    for g, k in zip(ctx.group_by, keys):
        env[g.fingerprint()] = k
    for spec, f in zip(ctx.aggregations, finals):
        _register_agg_env(env, spec, f)
    # select aliases: ORDER BY/HAVING may reference any select item by alias
    # (covers filtered/literal-arg aggregations the call forms above can't)
    for s, alias in zip(ctx.select_list, ctx.select_aliases):
        if alias:
            fp = s.fingerprint()
            if fp in env:
                env.setdefault(Expr.col(alias).fingerprint(), env[fp])

    # HAVING
    n = len(keys[0]) if keys else 0
    if ctx.having is not None and n:
        mask = _eval_host_filter(ctx.having, env, n)
        keys = [k[mask] for k in keys]
        finals = [f[mask] for f in finals]
        env = {k: v[mask] for k, v in env.items()}
        n = int(mask.sum())

    # output columns in select order (post-aggregation arithmetic resolves
    # against the env of final arrays)
    out_cols: List[np.ndarray] = []
    for s in ctx.select_list:
        out_cols.append(_eval_env_expr(s, env, n) if isinstance(s, Expr) else env[s.fingerprint()])

    if ctx.gapfill is not None:
        rows = _apply_gapfill(ctx, _rows_from_columns(out_cols, n))
        if ctx.order_by:
            rows = _order_rows_by_select(ctx, rows)
        rows = rows[ctx.offset: ctx.offset + ctx.limit]
    else:
        # order the groups as arrays and make rows of the ones kept only: a
        # merged table of 100,000 groups under LIMIT 10 is 10 tuples, not 100,000
        keep = _ordered_slice(ctx, env, n)
        rows = _rows_from_columns([np.asarray(c)[keep] for c in out_cols], len(keep))
    return ResultTable(columns=ctx.column_names_out(), rows=rows, stats=stats)


def _gapfill_select_pos(ctx, e) -> int:
    """Resolve a GAPFILL argument expression to its select-list position
    (by fingerprint, then by alias name)."""
    fps = [s.fingerprint() for s in ctx.select_list]
    fp = e.fingerprint()
    if fp in fps:
        return fps.index(fp)
    if e.is_column and e.op in ctx.select_aliases:
        return ctx.select_aliases.index(e.op)
    # plain-call form of a selected aggregation: FILL(SUM(v), ...)
    for i, s in enumerate(ctx.select_list):
        if isinstance(s, AggregationSpec) and s.filter is None:
            args = ([s.expr] if s.expr is not None else []) + [Expr.lit(a) for a in s.literal_args]
            if Expr.call(s.function, *args).fingerprint() == fp:
                return i
            if s.expr is None and not s.literal_args and (
                Expr.call(s.function, Expr.col("*")).fingerprint() == fp
            ):
                return i
    raise ValueError(f"GAPFILL references {e}, which is not in the select list")


def _apply_gapfill(ctx, rows: List[tuple]) -> List[tuple]:
    """Time-bucket gap filling over reduced group-by rows — the
    GapfillProcessor contract (pinot-core/.../core/query/reduce/
    GapfillProcessor.java): emit every bucket in [start, end) stepping by
    step for every observed TIMESERIESON key combination; missing cells
    fill per FILL mode (FILL_PREVIOUS_VALUE carries the series' last seen
    value; default NULL).  Buckets outside the range are dropped."""
    gf = ctx.gapfill
    tpos = _gapfill_select_pos(ctx, gf.time_expr)
    spos = [_gapfill_select_pos(ctx, s) for s in gf.series]
    fill_modes = {_gapfill_select_pos(ctx, t): mode for t, mode in gf.fills}
    ncol = len(ctx.select_list)
    cell: Dict[tuple, tuple] = {}
    series_seen: List[tuple] = []
    sset = set()
    for r in rows:
        b = r[tpos]
        if b is None:
            continue
        b = int(b)
        sk = tuple(r[i] for i in spos)
        if sk not in sset:
            sset.add(sk)
            series_seen.append(sk)
        if gf.start <= b < gf.end and (b - gf.start) % gf.step == 0:
            cell[(b, sk)] = r
    if not series_seen:
        series_seen = [()] if not spos else []
    # FILL_DEFAULT_VALUE fills the column's TYPE default (0 for numeric, ""
    # for strings — GapfillUtils.getDefaultValue), inferred from observed
    # values; columns without a FILL spec stay NULL
    defaults: Dict[int, Any] = {}
    for i, mode in fill_modes.items():
        if mode != "FILL_DEFAULT_VALUE":
            continue
        defaults[i] = 0
        for r in rows:
            if r[i] is not None:
                defaults[i] = "" if isinstance(r[i], str) else 0
                break
    prev: Dict[tuple, Dict[int, Any]] = {sk: {} for sk in series_seen}
    out: List[tuple] = []
    for b in range(gf.start, gf.end, gf.step):
        for sk in series_seen:
            r = cell.get((b, sk))
            if r is not None:
                out.append(tuple(b if i == tpos else v for i, v in enumerate(r)))
                for i in range(ncol):
                    prev[sk][i] = r[i]
            else:
                vals = []
                for i in range(ncol):
                    if i == tpos:
                        vals.append(b)
                    elif i in spos:
                        vals.append(sk[spos.index(i)])
                    elif fill_modes.get(i) == "FILL_PREVIOUS_VALUE":
                        vals.append(prev[sk].get(i))
                    elif i in defaults:
                        vals.append(defaults[i])
                    else:
                        vals.append(None)
                out.append(tuple(vals))
    return out


def _order_rows_by_select(ctx, rows: List[tuple]) -> List[tuple]:
    """ORDER BY over already-materialized rows (post-gapfill): each order
    expression must resolve to a select-list position."""
    ord_vals = []
    for ob in ctx.order_by:
        p = _gapfill_select_pos(ctx, ob.expr)
        ord_vals.append(np.asarray([r[p] for r in rows], dtype=object))
    order = _sorted_order(ctx.order_by, ord_vals, len(rows))
    return [rows[i] for i in order]


def _ident_like(field: str, arr: np.ndarray):
    if field == "count":
        return 0
    ident = field_identity(field)
    if np.issubdtype(np.asarray(arr).dtype, np.integer):
        # +-inf identities don't exist for int fields (presence bitmaps, HLL
        # registers, histograms); use the dtype extremes / zero instead
        info = np.iinfo(np.asarray(arr).dtype)
        return {0.0: 0, float("inf"): info.max, float("-inf"): 0}[ident]
    return ident


def _decode_dense_keys(group_dims, present: np.ndarray) -> List[np.ndarray]:
    from pinot_tpu.query.planner import decode_packed_keys

    return decode_packed_keys(group_dims, present)


def _hash_merge(results: List[GroupBySegmentResult], aggs) -> Tuple[List[np.ndarray], List[Dict[str, np.ndarray]]]:
    """Generic keyed merge (IndexedTable upsert analog).

    Fast path: key tuples encode to dense int codes (np.unique per dim) and
    every partial field combines with ONE ufunc scatter (the FIELD_COMBINE
    name contract the dense/psum merges already rely on) — no per-row Python
    upsert.  First-seen key order is preserved.  Pairwise-merge aggregations
    (coupled fields) and incomparable mixed-type keys fall back to the loop."""
    if all(
        not fn.pairwise_merge and all(f in FIELD_COMBINE for f in results[0].partials[ai])
        for ai, fn in enumerate(aggs)
    ):
        merged = _hash_merge_vectorized(results, aggs)
        if merged is not None:
            return merged
    table: Dict[tuple, List[Dict[str, Any]]] = {}
    for r in results:
        n = len(r.keys[0]) if r.keys else 0
        for i in range(n):
            key = tuple(k[i] for k in r.keys)
            partial = [{f: arr[i] for f, arr in p.items()} for p in r.partials]
            cur = table.get(key)
            if cur is None:
                table[key] = partial
            else:
                table[key] = [fn.merge(a, b) for fn, a, b in zip(aggs, cur, partial)]
    keys_out: List[np.ndarray] = []
    ndims = len(results[0].keys)
    all_keys = list(table.keys())
    for d in range(ndims):
        keys_out.append(np.asarray([k[d] for k in all_keys], dtype=object))
    partials_out: List[Dict[str, np.ndarray]] = []
    for ai, fn in enumerate(aggs):
        fields = results[0].partials[ai].keys()
        partials_out.append({f: np.asarray([table[k][ai][f] for k in all_keys]) for f in fields})
    return keys_out, partials_out


def _scatter_init(shape, dtype, op: str):
    """Identity-filled accumulator for one ufunc-scatter combine; every group
    has at least one row, so the identity never reaches the output."""
    if op == "add":
        return np.zeros(shape, dtype=dtype)
    if np.issubdtype(dtype, np.floating):
        fill = np.inf if op == "min" else -np.inf
    elif dtype == np.bool_:
        fill = op == "min"
    else:
        info = np.iinfo(dtype)
        fill = info.max if op == "min" else info.min
    return np.full(shape, fill, dtype=dtype)


# the by-value merge codes an integer dimension by its values, and keeps the
# merged table dense, while the range / the key space has at most this many
# slots (8 B a slot of scratch); past it, np.unique's sort as before
_DENSE_MERGE_SPACE = 1 << 24


def _hash_merge_vectorized(results: List[GroupBySegmentResult], aggs):
    """Returns (keys, partials) in first-seen key order, or None when the
    keys defy np.unique coding (caller falls back to the upsert loop)."""
    ndims = len(results[0].keys)
    total = sum(len(r.keys[0]) if r.keys else 0 for r in results)
    if total == 0 or ndims == 0:
        return None
    cat_keys = []
    for d in range(ndims):
        arrs = [np.asarray(r.keys[d]) for r in results]
        # a dimension whose every table decoded to integers (an INT
        # dictionary's values) keeps its dtype; anything else (strings, a
        # None among the values, mixed kinds) rides as objects, as before
        kinds = {a.dtype.kind for a in arrs}
        if not (len(kinds) == 1 and kinds <= {"i", "u"}):
            arrs = [a.astype(object) for a in arrs]
        cat_keys.append(np.concatenate(arrs))
    cards, invs = [], []
    for d in range(ndims):
        keys = cat_keys[d]
        lo, hi = (int(keys.min()), int(keys.max())) if keys.dtype != object else (0, _DENSE_MERGE_SPACE)
        if hi - lo < _DENSE_MERGE_SPACE:
            # integers of a small range code themselves: no sort.  np.unique
            # over the 4M keys of 50 tables of ~79,000 groups is a second a
            # dimension, and the tables of segments built apart meet here
            cards.append(hi - lo + 1)
            # in int64: a narrow dtype's range can pass the dtype (int16 keys of -30000..30000)
            invs.append(np.subtract(keys, lo, dtype=np.int64) if lo else keys)
            continue
        try:
            uniq, inv = np.unique(keys, return_inverse=True)
        except TypeError:
            return None
        cards.append(max(1, len(uniq)))
        invs.append(inv.reshape(-1))
    space = 1
    for c in cards:
        space *= c
    if space >= (1 << 62):  # packed composite code must fit int64
        return None
    codes = invs[0].astype(np.int64)  # a copy: the passes below are in place, over millions of keys
    for card, inv in zip(cards[1:], invs[1:]):
        codes *= np.int64(card)
        codes += inv
    if space <= _DENSE_MERGE_SPACE:
        # a key space that fits a table: first positions by one scatter-min
        first_at = np.full(space, total, dtype=np.int64)
        np.minimum.at(first_at, codes, np.arange(total, dtype=np.int64))
        uniq_codes = np.flatnonzero(first_at < total)
        first_pos = first_at[uniq_codes]
        order = np.argsort(first_pos, kind="stable")  # sorted-unique -> first-seen
        slot = np.empty(space, dtype=np.int64)
        slot[uniq_codes[order]] = np.arange(len(uniq_codes))
        g = slot[codes]  # row -> output slot
    else:
        uniq_codes, first_pos, inv = np.unique(codes, return_index=True, return_inverse=True)
        order = np.argsort(first_pos, kind="stable")  # sorted-unique -> first-seen
        rank = np.empty(len(uniq_codes), dtype=np.int64)
        rank[order] = np.arange(len(uniq_codes))
        g = rank[inv.reshape(-1)]  # row -> output slot
    k = len(uniq_codes)
    keys_out = [cat_keys[d][first_pos[order]] for d in range(ndims)]
    partials_out: List[Dict[str, np.ndarray]] = []
    for ai in range(len(aggs)):
        out: Dict[str, np.ndarray] = {}
        for f in results[0].partials[ai]:
            arr = np.concatenate(
                [np.atleast_1d(np.asarray(r.partials[ai][f])) for r in results]
            )
            if arr.dtype == object:
                return None  # non-numeric partials: upsert loop path
            op = FIELD_COMBINE[f]
            acc = _scatter_init((k,) + arr.shape[1:], arr.dtype, op)
            if op == "add":
                np.add.at(acc, g, arr)
            elif op == "min":
                np.minimum.at(acc, g, arr)
            else:
                np.maximum.at(acc, g, arr)
            out[f] = acc
        partials_out.append(out)
    return keys_out, partials_out


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------
def _reduce_selection(ctx: QueryContext, results: List[SelectionSegmentResult], stats: ExecutionStats) -> ResultTable:
    results = [r for r in results if r is not None]
    out_names = ctx.column_names_out()
    if not results:
        return ResultTable(columns=out_names, rows=[], stats=stats)
    cols = results[0].columns
    if "*" in out_names:
        # SELECT *: label with the actual gathered columns so dataSchema
        # matches the row arity (window inputs/order keys are internal)
        out_names = [c for c in cols if not (c.startswith("__ord") or c.startswith("__wx_"))]
    arrays = {
        c: np.concatenate([np.asarray(r.arrays[c], dtype=object) for r in results])
        if len(results) > 1
        else np.asarray(results[0].arrays[c], dtype=object)
        for c in cols
    }
    n = len(next(iter(arrays.values()))) if arrays else 0
    # window functions: computed HERE, over the globally merged row set
    # (WindowAggregateOperator analog; whole-partition frames)
    if ctx.windows:
        from pinot_tpu.query.ir import WindowSpec

        for i, s in enumerate(ctx.select_list):
            if isinstance(s, WindowSpec):
                arrays[f"__win{i}"] = _compute_window(s, arrays, n)
    select_cols = [c for c in cols if not (c.startswith("__ord") or c.startswith("__wx_"))]
    rows = _rows_from_columns([arrays[c] for c in select_cols], n)
    if ctx.order_by:
        ord_vals = [arrays[f"__ord{i}"] for i in range(len(ctx.order_by))]
        order = _sorted_order(ctx.order_by, ord_vals, n)
        rows = [rows[i] for i in order]
    rows = rows[ctx.offset: ctx.offset + ctx.limit]
    return ResultTable(columns=out_names, rows=rows, stats=stats)


def _win_lex_key(vals, asc: bool) -> Tuple[np.ndarray, bool]:
    """(sortable float key, is_numeric) for one OVER(ORDER BY) expression:
    numeric values rank numerically, genuine strings by sorted-unique codes.
    Descending flips sign, so 'preceding' is always toward SMALLER keys —
    which makes signed RANGE offsets direction-agnostic.  RANGE offset
    frames are only legal over a numeric key (the caller checks the flag)."""
    a = np.asarray(vals)
    if a.dtype == object:
        try:
            a = a.astype(np.float64)
        except (ValueError, TypeError):
            pass
    if np.issubdtype(a.dtype, np.number):
        a = a.astype(np.float64)
        return (a if asc else -a), True
    _, inv = np.unique(a.astype(str), return_inverse=True)
    inv = inv.astype(np.float64)
    return (inv if asc else -inv), False


_WIN_AGG_FNS = ("sum", "avg", "count", "min", "max", "bool_and", "bool_or")


def _compute_window(spec, arrays: Dict[str, np.ndarray], n: int) -> np.ndarray:
    """One window function over the merged result rows.

    Reference parity: WindowAggregateOperator + the window/value family
    (pinot-query-runtime/.../runtime/operator/window/value/
    LagValueWindowFunction.java, LeadValueWindowFunction.java,
    FirstValueWindowFunction.java, LastValueWindowFunction.java,
    range/NtileWindowFunction.java) with ROWS/RANGE frames per
    WindowFrame.java.

    Partition ids hash the partition-key tuples; within each partition rows
    order by the OVER(ORDER BY ...) keys (stable).  Every frame shape
    reduces to per-row inclusive-exclusive bounds [ws, we) in sorted space;
    sums/counts then resolve via prefix sums, min/max via prefix/suffix
    accumulation (unbounded edge) or per-row slices (bounded frames)."""
    pid = np.zeros(n, dtype=np.int64)
    if spec.partition_by:
        pkeys = [np.asarray(arrays[f"__wx_{p.fingerprint()}"]) for p in spec.partition_by]
        seen: Dict[tuple, int] = {}
        for i in range(n):
            key = tuple(k[i] for k in pkeys)
            pid[i] = seen.setdefault(key, len(seen))
    fn = spec.function
    keyed = [_win_lex_key(arrays[f"__wx_{o.expr.fingerprint()}"], o.ascending) for o in spec.order_by]
    lex = [k for k, _ in keyed]
    lex_numeric = [num for _, num in keyed]
    order = np.lexsort(tuple(reversed([pid] + lex)))
    spid = pid[order]
    idx = np.arange(n)
    starts = np.ones(n, dtype=bool)
    if n > 1:
        starts[1:] = spid[1:] != spid[:-1]
    # partition bounds per sorted row: [start_idx, end_idx)
    ps = idx[starts]
    pe = np.append(ps[1:], n)
    pnum = np.cumsum(starts) - 1
    start_idx = ps[pnum] if n else idx
    end_idx = pe[pnum] if n else idx
    pos0 = idx - start_idx
    plen = end_idx - start_idx
    # peer groups: rows with equal ORDER BY keys (frame CURRENT ROW in RANGE
    # mode, and rank/dense_rank steps)
    peer_flags = starts.copy()
    if lex and n > 1:
        diff = np.zeros(n - 1, dtype=bool)
        for k in lex:
            a = np.asarray(k)[order]
            diff |= ~((a[1:] == a[:-1]) | (np.isnan(a[1:]) & np.isnan(a[:-1])))
        peer_flags[1:] |= diff
    pps = idx[peer_flags]
    ppe = np.append(pps[1:], n)
    ppnum = np.cumsum(peer_flags) - 1
    peer_start = pps[ppnum] if n else idx
    peer_end = ppe[ppnum] if n else idx

    def unsort(sorted_vals, dtype):
        out = np.empty(n, dtype=dtype)
        out[order] = sorted_vals
        return out

    # -- ranking functions (frames do not apply) ------------------------
    if fn in ("row_number", "rank", "dense_rank", "ntile"):
        if fn == "row_number":
            r = pos0 + 1
        elif fn == "rank":
            r = peer_start - start_idx + 1
        elif fn == "dense_rank":
            dc = np.cumsum(peer_flags)
            r = dc - (dc[start_idx] - 1)
        else:  # NTILE(t): first (plen % t) buckets get one extra row
            t = int(spec.literal_args[0])
            q, rem = plen // t, plen % t
            cut = rem * (q + 1)
            r = np.where(
                pos0 < cut,
                pos0 // np.maximum(q + 1, 1),
                rem + (pos0 - cut) // np.maximum(q, 1),
            ) + 1
        return unsort(r.astype(np.int64), np.int64)

    sval = None
    if spec.expr is not None:
        sval = np.asarray(arrays[f"__wx_{spec.expr.fingerprint()}"], dtype=object)[order]

    # -- offset value functions (frames do not apply) -------------------
    if fn in ("lag", "lead"):
        off = int(spec.literal_args[0]) if spec.literal_args else 1
        default = spec.literal_args[1] if len(spec.literal_args) > 1 else None
        src = idx - off if fn == "lag" else idx + off
        valid = (src >= start_idx) & (src < end_idx)
        srcc = np.clip(src, 0, max(n - 1, 0))
        return unsort(np.where(valid, sval[srcc], default), object)

    # -- frame resolution: [ws, we) per sorted row ----------------------
    mode, lo, hi = spec.frame, spec.frame_lo, spec.frame_hi
    if mode == "rows_cumulative":
        mode, lo, hi = "rows", None, 0
    elif mode == "range_all":
        if spec.order_by:
            # SQL default frame with ORDER BY: RANGE UNBOUNDED PRECEDING ..
            # CURRENT ROW (cumulative by peer groups)
            mode, lo, hi = "range", None, 0
        else:
            mode, lo, hi = "rows", None, None  # whole partition
    if mode == "rows":
        ws = start_idx if lo is None else np.maximum(start_idx, idx + int(lo))
        we = end_idx if hi is None else np.minimum(end_idx, idx + int(hi) + 1)
    else:  # range
        if not lex:
            ws, we = start_idx, end_idx
        elif lo in (None, 0) and hi in (None, 0):
            ws = start_idx if lo is None else peer_start
            we = end_idx if hi is None else peer_end
        else:
            if len(lex) != 1:
                raise ValueError("RANGE frame with offsets requires exactly one ORDER BY key")
            if not lex_numeric[0]:
                raise ValueError("RANGE frame with offsets requires a NUMERIC ORDER BY key")
            sk = np.asarray(lex[0], np.float64)[order]
            ws = np.empty(n, dtype=np.int64)
            we = np.empty(n, dtype=np.int64)
            for s, e in zip(ps, pe):  # per partition: vectorized searchsorted
                seg = sk[s:e]
                if lo is None:
                    ws[s:e] = s
                elif lo == 0:
                    ws[s:e] = peer_start[s:e]
                else:
                    ws[s:e] = s + np.searchsorted(seg, seg + float(lo), side="left")
                if hi is None:
                    we[s:e] = e
                elif hi == 0:
                    we[s:e] = peer_end[s:e]
                else:
                    we[s:e] = s + np.searchsorted(seg, seg + float(hi), side="right")
    wsc = np.minimum(ws, we)  # empty frames collapse to zero-width slices

    if fn == "count" and spec.expr is None:  # COUNT(*): frame row count
        return unsort(np.maximum(we - ws, 0).astype(np.int64), np.int64)
    if sval is None:
        raise ValueError(f"window {fn} needs an argument")

    if fn in ("first_value", "last_value"):
        valid = we > ws
        pos = np.clip(np.where(fn == "first_value", wsc, we - 1), 0, max(n - 1, 0))
        return unsort(np.where(valid, sval[pos], None), object)

    # -- numeric frame aggregates ---------------------------------------
    v = np.array([np.nan if x is None else float(x) for x in sval], dtype=np.float64)
    if fn in ("bool_and", "bool_or"):
        v = np.where(np.isnan(v), np.nan, (v != 0).astype(np.float64))
    notnan = ~np.isnan(v)
    cn = np.concatenate([[0], np.cumsum(notnan.astype(np.int64))])
    m = cn[we] - cn[wsc]  # non-null rows in frame
    if fn == "count":
        return unsort(m.astype(np.int64), np.int64)
    if fn in ("sum", "avg"):
        cs = np.concatenate([[0.0], np.cumsum(np.where(notnan, v, 0.0))])
        tot = cs[we] - cs[wsc]
        out_sorted = np.where(m > 0, tot, np.nan)
        if fn == "avg":
            out_sorted = out_sorted / np.maximum(m, 1)
        return unsort(out_sorted, np.float64)
    # min/max family: prefix/suffix accumulation when one edge is the
    # partition bound, per-row slices for doubly-bounded frames
    is_min = fn in ("min", "bool_and")
    acc_op = np.fmin if is_min else np.fmax  # fmin/fmax ignore NaN
    lo_unbounded = bool(np.all(wsc == start_idx))
    hi_unbounded = bool(np.all(we == end_idx))
    out_sorted = np.full(n, np.nan)
    if lo_unbounded:
        pref = np.empty(n, dtype=np.float64)
        for i in range(n):
            pref[i] = v[i] if starts[i] else acc_op(pref[i - 1], v[i])
        sel = we > wsc
        out_sorted[sel] = pref[we[sel] - 1]
    elif hi_unbounded:
        suf = np.empty(n, dtype=np.float64)
        for i in range(n - 1, -1, -1):
            last = (i == n - 1) or starts[i + 1]
            suf[i] = v[i] if last else acc_op(suf[i + 1], v[i])
        sel = we > wsc
        out_sorted[sel] = suf[wsc[sel]]
    else:
        for i in range(n):
            if we[i] > wsc[i] and m[i] > 0:
                seg = v[wsc[i]: we[i]]
                out_sorted[i] = np.nanmin(seg) if is_min else np.nanmax(seg)
    out_sorted = np.where(m > 0, out_sorted, np.nan)
    return unsort(out_sorted, np.float64)


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------
def _rows_from_columns(cols: Sequence[np.ndarray], n: int) -> List[tuple]:
    rows = []
    for i in range(n):
        rows.append(
            tuple(
                _scalar(c[i]) if not isinstance(c[i], (str, bytes, list, tuple, type(None))) else c[i]
                for c in cols
            )
        )
    return rows


def _order_codes(order_by: List[OrderByExpr], ord_vals: List[np.ndarray], n: int):
    """Vectorized rank keys for _sorted_order's lexsort fast path: each
    column codes to float ranks via np.unique over the RAW objects (python
    `<` ordering, so strings and numbers alike match the comparator), nulls
    to +-inf per nulls placement.  Returns None when a column defies
    total-order coding (mixed incomparable types, NaN) — the caller falls
    back to the Python comparator."""
    keys = []
    for ob, vals in zip(reversed(order_by), reversed(ord_vals)):
        if isinstance(vals, np.ndarray) and vals.dtype.kind in "iu" and (
            not vals.size or max(abs(int(vals.min())), abs(int(vals.max()))) < (1 << 53)
        ):
            num = vals.astype(np.float64)  # exact, so the floats order as the integers do
            keys.append(num if ob.ascending else -num)
            continue
        a = np.asarray(vals, dtype=object)
        isnull = np.fromiter((v is None for v in a), dtype=bool, count=len(a))
        body = a[~isnull]
        k = np.empty(n, dtype=np.float64)
        if body.size:
            if any(isinstance(v, (float, np.floating)) and math.isnan(v) for v in body):
                return None
            try:
                _, inv = np.unique(body, return_inverse=True)
            except TypeError:
                return None
            num = inv.reshape(-1).astype(np.float64)
            k[~isnull] = num if ob.ascending else -num
        k[isnull] = np.inf if ob.nulls_last else -np.inf
        keys.append(k)
    return keys


def _sorted_order(order_by: List[OrderByExpr], ord_vals: List[np.ndarray], n: int) -> List[int]:
    """Stable index sort honoring asc/desc + nulls placement, robust to
    mixed/None/object values (python comparison semantics)."""
    if n > 1:
        keys = _order_codes(order_by, ord_vals, n)
        if keys is not None:
            # np.lexsort is stable, so equal-ranked rows keep their original
            # order — the same i - j tiebreak the comparator applies
            return list(np.lexsort(tuple(keys)))

    def cmp(i: int, j: int) -> int:
        for ob, vals in zip(order_by, ord_vals):
            a, b = vals[i], vals[j]
            if a is None or b is None:
                if a is None and b is None:
                    continue
                null_first = not ob.nulls_last
                if a is None:
                    return -1 if null_first else 1
                return 1 if null_first else -1
            if a == b:
                continue
            less = a < b
            if ob.ascending:
                return -1 if less else 1
            return 1 if less else -1
        return i - j  # stable tiebreak

    return sorted(range(n), key=functools.cmp_to_key(cmp))


def _ordered_slice(ctx: QueryContext, env: Dict[str, np.ndarray], n: int) -> np.ndarray:
    """The indices of the groups a query keeps, in its ORDER BY's order, cut
    to OFFSET / LIMIT."""
    order = np.arange(n)
    if ctx.order_by:
        ord_vals = []
        for ob in ctx.order_by:
            try:
                vals = _eval_env_expr(ob.expr, env, n)
            except ValueError:
                raise ValueError(
                    f"ORDER BY {ob.expr} must be a select/group/aggregation expression"
                ) from None
            vals = np.asarray(vals)
            if vals.dtype.kind not in "iu":  # integers have no null and no NaN: they order as they are
                vals = np.asarray([_scalar(v) if not isinstance(v, (str, bytes, type(None))) else v for v in vals], dtype=object)
            ord_vals.append(vals)
        order = np.asarray(_sorted_order(ctx.order_by, ord_vals, n), dtype=np.int64)
    return order[ctx.offset: ctx.offset + ctx.limit]


_ENV_BINOPS = {
    "plus": np.add,
    "add": np.add,
    "minus": np.subtract,
    "sub": np.subtract,
    "times": np.multiply,
    "mult": np.multiply,
    "mod": np.mod,
    "pow": np.power,
}
_ENV_UNARY = {
    "abs": np.abs,
    "neg": np.negative,
    "sqrt": np.sqrt,
    "ln": np.log,
    "log": np.log,
    "log2": np.log2,
    "log10": np.log10,
    "exp": np.exp,
    "floor": np.floor,
    "ceil": np.ceil,
    "ceiling": np.ceil,
    "round": np.round,
}


def _eval_env_expr(e, env: Dict[str, np.ndarray], n: int) -> np.ndarray:
    """POST-AGGREGATION expression evaluation over final arrays — the
    reference's post-aggregation gap-filling (PostAggregationFunction):
    SELECT SUM(a)/COUNT(*), HAVING SUM(v)*2 > x, ORDER BY SUM(a)-SUM(b).
    Resolution: fingerprint in env (group keys, aggregation finals, aliases)
    else arithmetic over recursively evaluated args."""
    fp = e.fingerprint()
    if fp in env:
        return np.asarray(env[fp])
    if e.is_literal:
        return np.full(n, e.value)
    if e.kind is not None and e.kind.name == "CALL":
        op = e.op
        if op in _ENV_BINOPS and len(e.args) == 2:
            a = np.asarray(_eval_env_expr(e.args[0], env, n), dtype=np.float64)
            b = np.asarray(_eval_env_expr(e.args[1], env, n), dtype=np.float64)
            return _ENV_BINOPS[op](a, b)
        if op in ("divide", "div") and len(e.args) == 2:
            a = np.asarray(_eval_env_expr(e.args[0], env, n), dtype=np.float64)
            b = np.asarray(_eval_env_expr(e.args[1], env, n), dtype=np.float64)
            with np.errstate(divide="ignore", invalid="ignore"):
                return a / b
        if op in _ENV_UNARY and len(e.args) == 1:
            return _ENV_UNARY[op](np.asarray(_eval_env_expr(e.args[0], env, n), dtype=np.float64))
    raise ValueError(f"select item {e} is neither a group key nor an aggregation")


def _eval_host_filter(node: FilterNode, env: Dict[str, np.ndarray], n: int) -> np.ndarray:
    """HAVING evaluation over final (already-aggregated) columns."""
    if node.op is FilterOp.AND:
        m = np.ones(n, dtype=bool)
        for c in node.children:
            m &= _eval_host_filter(c, env, n)
        return m
    if node.op is FilterOp.OR:
        m = np.zeros(n, dtype=bool)
        for c in node.children:
            m |= _eval_host_filter(c, env, n)
        return m
    if node.op is FilterOp.NOT:
        return ~_eval_host_filter(node.children[0], env, n)
    p = node.predicate
    try:
        vals = _eval_env_expr(p.lhs, env, n)
    except ValueError:
        raise ValueError(f"HAVING references {p.lhs}, which is not in the select/group list") from None

    def isnull(v) -> bool:
        # NULL aggregates arrive as np.nan here (converted to None only at
        # _scalar); SQL 3VL: any comparison with NULL excludes the group.
        return v is None or (isinstance(v, (float, np.floating)) and math.isnan(v))

    if p.ptype is PredicateType.EQ:
        return np.asarray([not isnull(v) and v == p.values[0] for v in vals], dtype=bool)
    if p.ptype is PredicateType.NEQ:
        return np.asarray([not isnull(v) and v != p.values[0] for v in vals], dtype=bool)
    if p.ptype in (PredicateType.IN, PredicateType.NOT_IN):
        s = set(p.values)
        if p.ptype is PredicateType.IN:
            return np.asarray([not isnull(v) and v in s for v in vals], dtype=bool)
        return np.asarray([not isnull(v) and v not in s for v in vals], dtype=bool)
    if p.ptype is PredicateType.RANGE:
        m = np.ones(n, dtype=bool)
        for i, v in enumerate(vals):
            if isnull(v):
                m[i] = False
                continue
            if p.lower is not None and not (v >= p.lower if p.lower_inclusive else v > p.lower):
                m[i] = False
            if p.upper is not None and not (v <= p.upper if p.upper_inclusive else v < p.upper):
                m[i] = False
        return m
    raise ValueError(f"HAVING predicate {p.ptype} unsupported")
