"""Star-tree query routing: level selection and the query's rewrite onto a
level's fields.

Reference parity: Pinot injects the star-tree when a group-by's filter and
group columns fall inside the tree's dimension split order and every
aggregation has a matching function-column pair
(AggregationPlanNode.buildAggregationInfoWithStarTree,
pinot-core/.../core/plan/AggregationPlanNode.java:109;
StarTreeFilterOperator traversal, .../core/startree/operator/
StarTreeFilterOperator.java:90,218; StarTreeAggregationExecutor /
StarTreeGroupByExecutor, .../core/startree/executor/).

Re-design (see indexes/startree.py): tree traversal becomes level selection —
pick the smallest prefix level covering the query's dimension set
(`pick_level`, O(1) on the host) — and the level is a table like any other
(LevelSegment: the parent's dictionaries, the fields as metric columns,
resident on the device).  What is left to do here is to say the query in the
level's columns (`StarRewrite`: COUNT(*) becomes the sum of `*:count`, SUM(x)
the sum of `x:sum`, MIN / MAX those of `x:min` / `x:max`, AVG both), so that
the ORDINARY plan answers it — plan cache, group launch, one fetch a group,
the decode (planner.QueryPlanning.source, executor.QueryLaunches) — and to put
the answer's partials back under the names the query's own aggregations
merge by (`StarRewrite.restore`).  Rows scanned = collapsed level rows, the
docs-scanned win the reference gets from skipping to aggregated docs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.indexes.startree import field_column
from pinot_tpu.query.functions import for_spec
from pinot_tpu.query.ir import AggregationSpec, Expr, QueryContext
from pinot_tpu.query.result import AggSegmentResult, DenseGroupData, GroupBySegmentResult

# what answers a field kind over a level's rows, and the field of that answer
_FIELD_AGG = {"count": "sum", "sum": "sum", "sumsq": "sum", "min": "min", "max": "max"}


def star_enabled(ctx: QueryContext) -> bool:
    """The query option `useStarTree` (upstream's; default true)."""
    opt = ctx.options.get("useStarTree", True)
    return bool(opt) and not (isinstance(opt, str) and opt.lower() in ("false", "0"))


def star_need(ctx: QueryContext) -> Optional[Tuple[frozenset, Tuple[Tuple[str, str], ...]]]:
    """What `ctx` asks of a tree, by upstream's rules: (the dimensions its
    filters and GROUP BY name, (function, column) of each aggregation), or
    None where no tree can serve it: a join, no aggregation, a GROUP BY
    expression, an aggregation over an expression.  The query's half of the
    decision, made once a query (planner.QueryPlanning)."""
    if ctx.joins or not ctx.is_aggregate:
        return None
    dims = set()
    for g in ctx.group_by:
        if not g.is_column:
            return None
        dims.add(g.op)
    if ctx.filter:
        dims.update(ctx.filter.columns())
    pairs = []
    for spec in ctx.aggregations:
        if spec.expr is not None and not spec.expr.is_column:
            return None
        if spec.filter is not None:
            dims.update(spec.filter.columns())
        pairs.append((spec.function, spec.expr.op if spec.expr is not None else "*"))
    if "*" in dims:
        return None
    return frozenset(dims), tuple(pairs)


def pick_level(need, segment) -> Optional[Tuple[str, object, int]]:
    """(tree name, StarTreeIndex, level k) of the tree of `segment` that
    answers `need` (star_need) from the fewest rows, or None.  Upsert
    segments never use one: pre-aggregated levels cannot honor per-row
    validDocIds (the reference likewise excludes star-trees from upsert
    tables)."""
    trees = segment.indexes.get("startree")
    if not trees or need is None or segment.valid_docs is not None:
        return None
    dims, pairs = need
    best = None
    for name, st in trees.items():
        k = st.level_for(dims)
        if k is None:
            continue
        if best is not None and st.levels[k].num_rows >= best[1].levels[best[2]].num_rows:
            continue
        # star count fields assume null-free metrics
        if all(
            st.has_fields(func, col) and (col == "*" or segment.column(col).nulls is None)
            for func, col in pairs
        ):
            best = (name, st, k)
    return best


class StarRewrite:
    """`ctx` said in a level's columns.  Every field of every aggregation
    becomes one aggregation over the level's field column (shared where two
    aggregations read the same field): `self.ctx` is what a level's plan is
    made from, `restore` puts its answer under the query's own aggregations.
    It depends on the query alone, not on the tree or the level: one a
    query.

    The rewritten query keeps the filters, the GROUP BY and the options; it
    drops HAVING and ORDER BY, which the reduce applies to the merged
    groups.  (A level that holds more groups than numGroupsLimit is trimmed
    by lowest key, as every path trims without an ORDER BY it can rank by.)"""

    def __init__(self, ctx: QueryContext):
        specs: List[AggregationSpec] = []
        at: Dict[Tuple, int] = {}
        # per aggregation of ctx: ((its field, index into specs, that answer's field, counts?), ...)
        self.fields: List[Tuple[Tuple[str, int, str, bool], ...]] = []
        for spec in ctx.aggregations:
            col = spec.expr.op if spec.expr is not None else "*"
            mine = []
            for fname, kind in for_spec(spec).field_kinds.items():
                source = field_column("*" if kind == "count" else col, kind)
                key = (_FIELD_AGG[kind], source, spec.filter.fingerprint() if spec.filter else None)
                if key not in at:
                    at[key] = len(specs)
                    specs.append(AggregationSpec(_FIELD_AGG[kind], Expr.col(source), filter=spec.filter))
                mine.append((fname, at[key], _FIELD_AGG[kind], kind == "count"))
            self.fields.append(tuple(mine))
        self.ctx = dataclasses.replace(
            ctx, select_list=list(specs), select_aliases=[None] * len(specs),
            extra_aggregations=[], having=None, order_by=[],
        )

    def _partials(self, partials: List[Dict]) -> List[Dict]:
        out = []
        for mine in self.fields:
            p = {}
            for fname, i, field, counts in mine:
                v = partials[i][field]
                # a count is a sum of the level's counts: whole, as COUNT's is
                p[fname] = np.rint(np.asarray(v)).astype(np.int64) if counts else v
            out.append(p)
        return out

    def restore(self, result):
        """The level's answer as the scan's: same keys, the partials under
        the query's own aggregations and field names."""
        if isinstance(result, AggSegmentResult):
            return AggSegmentResult(partials=self._partials(result.partials))
        dense = result.dense
        if dense is not None:
            dense = DenseGroupData(
                presence=dense.presence, partials=self._partials(dense.partials),
                key_space=dense.key_space, group_dims=dense.group_dims,
            )
        return GroupBySegmentResult(keys=result.keys, partials=self._partials(result.partials), dense=dense)
