"""Per-segment plan maker + jit kernel compiler — the SSE hot path.

Reference parity: InstancePlanMakerImplV2.makeSegmentPlanNode
(pinot-core/.../core/plan/maker/InstancePlanMakerImplV2.java:347-362) picking
Aggregation/GroupBy/Selection plans per query shape, plus the operator chain
it builds (FilterPlanNode -> DocIdSet -> Projection -> Transform ->
Aggregation/GroupBy operators, SURVEY.md 3.1 hot loop).

Re-design (SURVEY.md section 7 "Query plan = traced function"): instead of an
interpreted operator tree pulling 10k-doc blocks, the whole
filter->project->aggregate chain for one query shape is traced into ONE
jax.jit kernel over whole columns; XLA fuses it. Compiled kernels are cached
by (query fingerprint, segment signature) — the plan-cache analog — so a
table of uniformly-shaped segments compiles once.

Group-by: dictId-packed keys (DictionaryBasedGroupKeyGenerator analog,
.../groupby/DictionaryBasedGroupKeyGenerator.java:68): the composite key is
codes raveled over dimension cardinalities; when the cardinality product fits
numGroupsLimit the result is a DENSE group table filled by segment_sum /
scatter-min-max (result-holder analog). Overflow falls back to a vectorized
host groupby (executor.py) — the IndexedTable-with-trim analog, to be
replaced by a Pallas hash table.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu import ops
from pinot_tpu.ops.code_lookup import RESIDENT, lookup_form, lookup_tally
from pinot_tpu.query.filter import _DICT_RESOLVED, FilterCompiler, dict_predicate_codes, sorted_doc_range
from pinot_tpu.query.functions import (
    FIELD_COMBINE,
    AggFunction,
    field_identity,
    for_spec,
    get_agg_function,
)
from pinot_tpu.query.ir import AggregationSpec, Expr, FilterOp, PredicateType, QueryContext
from pinot_tpu.query.shape import column_info_from, params_structure
from pinot_tpu.query.startree import StarRewrite, pick_level, star_enabled, star_need
from pinot_tpu.query.transform import as_row_array, eval_expr, value_leaves
from pinot_tpu.segment.segment import ImmutableSegment
from pinot_tpu.spi.schema import DataType
from pinot_tpu.utils.metrics import METRICS
from pinot_tpu.utils.perf import scan_bytes_per_row

MAX_DENSE_RAW_INT_RANGE = 1 << 20  # raw ints join the dense keyspace when (max-min+1) is small


@dataclass
class GroupDim:
    """How one group-by dimension maps into the dense key space.

    kinds:
      dict    - dictionary codes of a column
      rawint  - integer column values shifted by base
      expr    - integer-valued device expression shifted by base (range
                bounded statically by scalar.expr_int_range)
      derived - dict column remapped through a host-computed derived
                dictionary (string functions: code -> remap[code], decode via
                derived_values) — Pinot's expression group-by over strings
    """

    expr: Expr
    name: str
    kind: str  # "dict" | "rawint" | "expr" | "derived"
    cardinality: int
    dictionary: Optional[Any] = None  # Dictionary for kind=dict
    base: int = 0  # min value for kind=rawint/expr
    null_code: int = -1  # code representing SQL NULL (placeholder), -1 if none
    derived_values: Optional[np.ndarray] = None  # kind=derived decode table
    remap: Optional[np.ndarray] = None  # kind=derived code remap (int32)
    # multi-value dimension: rows EXPLODE — each element contributes a row
    # (Pinot's MV group-by semantics); kernels expand [n] -> [n, max_len]
    mv: bool = False

    def decode(self, codes: np.ndarray) -> np.ndarray:
        if self.kind == "dict":
            # null_code may be an extra slot past the dictionary (LEFT JOIN
            # no-match rows, mse/engine.py) — clip before the gather
            card = self.dictionary.cardinality
            vals = self.dictionary.get_values(np.minimum(np.asarray(codes), card - 1))
        elif self.kind == "derived":
            vals = self.derived_values[np.minimum(np.asarray(codes), len(self.derived_values) - 1)]
        else:
            vals = codes.astype(np.int64) + self.base
        if self.null_code >= 0:
            vals = np.asarray(vals, dtype=object)
            vals[np.asarray(codes) == self.null_code] = None
        return vals

    def device_code(self, cols, segment, dtype=None):
        """Traced per-row dimension code (the group-key contribution)."""
        from pinot_tpu.query.transform import eval_expr

        dtype = dtype or jnp.int32
        if self.kind == "dict":
            return cols[self.name]["codes"].astype(dtype)
        if self.kind == "rawint":
            v = cols[self.name]["values"]
            # subtract in storage dtype (np scalar: no x64 promotion)
            return (v - np.asarray(self.base, dtype=v.dtype)).astype(dtype)
        if self.kind == "derived":
            return jnp.asarray(self.remap)[cols[self.name]["codes"].astype(jnp.int32)].astype(dtype)
        v, _ = eval_expr(self.expr, segment, cols)
        return (v.astype(jnp.int64) - np.int64(self.base)).astype(dtype)


def group_strides(group_dims: List["GroupDim"]) -> List[int]:
    """Strides of the packed composite group key (most-significant-first, the
    layout _group_key produces).  Single source of truth for key packing —
    dense decode, sparse host groupby and reduce all unravel through here."""
    strides: List[int] = []
    acc = 1
    for gd in reversed(group_dims):
        strides.append(acc)
        acc *= gd.cardinality
    return list(reversed(strides))


def decode_packed_keys(group_dims: List["GroupDim"], packed: np.ndarray) -> List[np.ndarray]:
    """Packed composite keys -> per-dimension decoded value arrays."""
    packed = np.asarray(packed)
    return [
        gd.decode(((packed // stride) % gd.cardinality).astype(np.int64))
        for gd, stride in zip(group_dims, group_strides(group_dims))
    ]


@dataclass
class SegmentPlan:
    kind: str  # "aggregation" | "groupby_dense" | "groupby_sparse" | "selection"
    fn: Callable  # jitted kernel(cols, params)
    # what fn takes: the query's parameters packed into one host numpy
    # buffer per dtype (pack_params), so a launch's call carries 1-3 small
    # arrays however many predicates the query has
    params: Dict[str, np.ndarray]
    needed_columns: List[str]
    # (key, dtype, shape) of every parameter the kernel reads, sorted: the
    # packed buffers' layout, and what a plan-cache hit must match
    param_layout: Tuple = ()
    aggs: List[AggFunction] = field(default_factory=list)
    group_dims: List[GroupDim] = field(default_factory=list)
    num_groups: int = 0
    select_columns: List[str] = field(default_factory=list)
    # selection output items in order (columns AND expressions)
    select_exprs: List[Expr] = field(default_factory=list)
    # (column, index kind) per index-accelerated filter predicate
    index_uses: List[Tuple[str, str]] = field(default_factory=list)
    # (column, index kind) per filter predicate whose column has a range /
    # inverted index and whose codes the plan scans all the same: the
    # planner's decision from costs (filter.bitmap_serves)
    index_scans: List[Tuple[str, str]] = field(default_factory=list)
    # bytes the scan must read: the segment's TRUE rows x the stored bytes per
    # row of needed_columns (utils/perf.scan_bytes_per_row: `scan_row_bytes`,
    # counted once when the plan-cache entry is built); every launch reports it
    scan_bytes: float = 0.0
    scan_row_bytes: float = 0.0
    # device -> wall ms of this program's first call there (trace + compile:
    # a jitted program compiles anew for every device it first runs on, and
    # the persistent cache keys an entry by its device too).  The dict is the
    # plan-cache entry's, created with it and shared by reference with the
    # plan every hit builds, so "has not run on this device yet" is one fact
    # however many queries race the first launch
    launched_on: Dict[Any, float] = field(default_factory=dict)
    # (width, combine) -> this plan's group program (grouped_plan): the
    # plan-cache entry's too, shared by reference like launched_on, so a
    # program is built once a width and form and leaves the process with its
    # entry
    widened: Dict[Tuple[int, bool], "SegmentPlan"] = field(default_factory=dict)
    # a combining group program's: device -> the tables its first call of a
    # query folds into (identity_tables), made once a device
    identity: Dict[Any, Any] = field(default_factory=dict)
    # plan-cache key (shape fp, segment signature, backend): a launch's span
    # and a group program's name read the backend from it
    cache_key: Optional[Tuple] = None
    # whether plan_segment took the compiled fn from the plan cache (the
    # `cache` attr of the launch_plan span)
    cache_hit: bool = False
    # how a hit came by its parameters (the `bind` attr of the launch_plan
    # span): "recipe" = the entry's ParamRecipe evaluated against this
    # segment's dictionaries, "rebuild" = _build_plan run again
    bind: Optional[str] = None
    # the plan-cache entry's: how its parameters are made from (segment,
    # literals), None where some predicate has no such recipe
    recipe: Optional["ParamRecipe"] = None
    # {dictionary column: the dictionary size the kernel was compiled for}
    # (compiled_dict_sizes): the rows of the column's device dictionary the
    # launch hands it (ImmutableSegment.to_device `dict_rows`)
    dict_sizes: Dict[str, int] = field(default_factory=dict)
    # this segment's own dictionary is smaller than one of `dict_sizes`: the
    # kernel was compiled for the table's shape, not the segment's (the
    # `shape` attr of the launch_plan span)
    table_shaped: bool = False
    # form -> the table-by-code lookups of this plan's program compiled in
    # that form (ops/code_lookup.py: "contracted" / "gathered" / "resident"),
    # written when the kernel's body is FIRST traced, which a program's first
    # call does before it returns (a later trace over another staging's pytree
    # leaves it: the served launch's count stands); the plan-cache entry's,
    # shared by reference like launched_on
    lookups: Dict[str, int] = field(default_factory=dict)
    # what the kernel said of its row masks where it was first traced and what
    # the row-priced scatters answered (ops.mask_facts: `filtered`, the plan's
    # static fact that a predicate narrows them; `compactions`, the
    # compactions its program carries, one a distinct mask); the plan-cache
    # entry's, written and shared like `lookups`
    mask_facts: ops.MaskFacts = field(default_factory=lambda: ops.MaskFacts(False))
    # the dictionary columns the kernel reads BY VALUE where a gather would be
    # row-priced (code_lookup's RESIDENT form; _value_columns): what the
    # launch asks staging to hand out decoded beside their codes
    # (ImmutableSegment.to_device `value_columns`).  The same for every
    # segment of the plan-cache key: the compiled sizes and the dtypes are in it
    value_columns: frozenset = frozenset()
    # the rows the kernel was compiled for (compiled_rows), the same for every
    # segment of the plan-cache key: what the launch asks staging to pad the
    # segment's resident columns to (ImmutableSegment.to_device `rows`)
    rows: int = 0


# A member's run in a group program's joined column starts on a multiple of
# this many elements: whole tiles of the chip's 1-D layout for every dtype
_MEMBER_ALIGN = 1 << 12


def _join(xs):
    """The members' arrays `xs` of one column leaf (one shape) as ONE device
    array, and the function `i -> member i's array` a traced loop reads it
    with.  A leaf of at least _MEMBER_ALIGN elements is flattened, padded to
    whole runs and concatenated end to end, so member i is a contiguous,
    tile-aligned dynamic slice.  NOT jnp.stack: XLA lays a stacked [S, n]
    out with the member axis in its (8, 128) tiles, the members interleaved
    sublane by sublane, and reading one back costs a strided pass (on the
    chip +23-29 % device time a query, +4-6 % this way: PERF.md, PR 29).
    Smaller leaves are stacked: nothing to lay out."""
    x0 = xs[0]
    if x0.size < _MEMBER_ALIGN:
        stacked = jnp.stack(xs)
        return lambda i: stacked[i]
    run = -(-x0.size // _MEMBER_ALIGN) * _MEMBER_ALIGN
    joined = jnp.concatenate([jnp.pad(x.reshape(-1), (0, run - x0.size)) for x in xs])
    return lambda i: jax.lax.dynamic_slice(joined, (i * run,), (x0.size,)).reshape(x0.shape)


def combines(plan: SegmentPlan) -> bool:
    """Whether the tables of `plan`'s kernel fold into one elementwise: a
    dense group-by whose every aggregation says its fields' kinds, or is an
    own-scatter function that says its fields meet by name whatever segment
    made them (`fold_by_field`: HLL's registers, a histogram's bins, [groups,
    m] tables), so each field combines by its NAME (FIELD_COMBINE: add / min /
    max).  Any other own-scatter function (`field_kinds` None: its cells may
    be indexed by a segment's own codes) and a pairwise merge (coupled
    fields: LASTWITHTIME's (t, v)) do not."""
    return plan.kind == "groupby_dense" and all(
        (fn.field_kinds is not None or fn.fold_by_field)
        and not fn.pairwise_merge
        and all(f in FIELD_COMBINE for f in fn.fields)
        for fn in plan.aggs
    )


def _identity_tables(shapes):
    """The dense group-by's (presence, partials) that changes nothing when a
    member's tables fold into it: zeros, and a min / max field's identity
    (+-inf for the float fields of ops.group_min / group_max and a
    histogram's range, the type's own bound for an integer field: HLL's
    registers are int32).  Host arrays: made once a program and device, and
    an eager device op a field would be a small compile of its own in every
    process."""
    presence, partials = shapes

    def identity(f, like):
        if FIELD_COMBINE[f] != "add" and np.issubdtype(like.dtype, np.integer):
            bound = np.iinfo(like.dtype)
            return bound.max if FIELD_COMBINE[f] == "min" else bound.min
        return field_identity(f)

    return (
        np.zeros(presence.shape, presence.dtype),
        [{f: np.full(like.shape, identity(f, like), like.dtype) for f, like in p.items()} for p in partials],
    )


_COMBINE = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _fold_tables(acc, tables):
    """A member's dense (presence, partials) combined into `acc`, field by
    field: reduce._reduce_groupby's aligned merge, on the device."""
    presence, partials = acc
    more, theirs = tables
    return (
        presence + more,
        [{f: _COMBINE[FIELD_COMBINE[f]](a[f], b[f]) for f in a} for a, b in zip(partials, theirs)],
    )


def identity_tables(program: SegmentPlan, kernel: Callable, member_args: Tuple, device):
    """What the first call of a query's combining group `program` folds
    into: the dense (presence, partials) of `kernel(*member_args)` at every
    field's identity, on `device`.  Made once a (program, device): the
    kernel's output types come from the jitted kernel's own cached trace."""
    start = program.identity.get(device)
    if start is None:
        start = jax.device_put(_identity_tables(jax.eval_shape(kernel, *member_args)), device)
        start = program.identity.setdefault(device, start)
    return start


def grouped_plan(base: SegmentPlan, width: int, combine: bool = False) -> SegmentPlan:
    """`base`'s group program for `width` members: `fn(cols, packed)` takes
    the members' column pytrees as a tuple and their parameter buffers
    stacked to [width, n], joins each column's members into one array on the
    device (_join; the copy: the members' stored bytes read and written once
    more) and scans `base.fn` over the members, so the kernel's body is
    compiled ONCE whatever the width and every output comes back with a
    leading member axis.  With `combine` (the caller's to say: `combines(base)`
    and the members share ONE key space) it is `fn(cols, packed, tables)`:
    the scan CARRIES dense group tables, the members' folded into `tables`
    in member order, and ONE table comes back: the server's combine, on the
    chip.  `tables` is identity_tables for a query's first such call and the
    call before's output after it, so a query's groups of one key space
    leave ONE table however many calls they ride.  The
    arithmetic is the per-segment kernel's.  A
    program of its own, with a first-launch record of its own, kept on the
    plan-cache entry (`widened`)."""
    grouped = base.widened.get((width, combine))
    if grouped is None:
        kernel = base.fn

        def group(cols, packed, tables=()):
            leaves, treedefs = zip(*(jax.tree_util.tree_flatten(c) for c in cols))
            with jax.named_scope("group_stack"):
                takes = [_join(xs) for xs in zip(*leaves)]

            def member(acc, at):
                i, params = at
                mine = jax.tree_util.tree_unflatten(treedefs[0], [take(i) for take in takes])
                out = kernel(mine, params)
                if not combine:
                    return acc, out
                with jax.named_scope("group_combine"):
                    return _fold_tables(acc, out), ()

            members = (jnp.arange(width, dtype=jnp.int32), packed)
            return jax.lax.scan(member, tables, members, length=width)[0 if combine else 1]

        group.__name__ = group.__qualname__ = (
            f"{base.kind}_{base.cache_key[2]}_x{width}" + ("_combined" if combine else "")
        )
        # a program, not a query's plan: it keeps none of `base`'s parameter buffers
        mine = replace(base, fn=jax.jit(group), params={}, launched_on={}, widened={}, identity={})
        grouped = base.widened.setdefault((width, combine), mine)  # a racing query's wins
        if grouped is mine:
            METRICS.counter("compile.group.programs").inc()
    return grouped


# Upsert validDocIds ride beside the packed buffers, not in them: the mask
# is the segment's state (bool[num_docs], shared by every member of a batched
# launch), not a literal of the query.
VALID_KEY = "__valid__"
# A table whose rows on the device are padded past its true count
# (`true_rows`: a star-tree level's table, padded to a bucket so that the same
# level of every segment has one shape, indexes/startree.py LevelSegment; a
# segment of a table whose segments hold unequal rows, padded to the table's
# rows, segment/table_shape.py and ImmutableSegment.padded_to): the true row
# count is an int32 parameter of every plan over it, packed with the query's
# own, and rows from it on are masked out of every filter.
ROWS_KEY = "__rows__"


def pack_params(params: Dict[str, Any], layout: Tuple) -> Dict[str, np.ndarray]:
    """The FilterCompiler's parameters (numpy scalars and small tables, one
    per key) as one flat host buffer per dtype, in `layout`'s order.  Every
    argument of a jitted call is a transfer of its own, and on a busy host
    each costs a trip through the runtime: a Q1 query's six int32 bounds
    travel as one int32[6]."""
    groups: Dict[str, List[Any]] = {}
    for key, dtype, _ in layout:
        if key != VALID_KEY:
            groups.setdefault(dtype, []).append(params[key])
    packed = {
        dtype: np.concatenate([np.ravel(v) for v in vals]) for dtype, vals in groups.items()
    }
    if VALID_KEY in params:
        packed[VALID_KEY] = params[VALID_KEY]
    return packed


def unpack_params(packed: Dict[str, Any], layout: Tuple) -> Dict[str, Any]:
    """pack_params undone: the dict the kernel reads.  Runs inside the jitted
    program (static slices of the traced buffers); works on the host
    buffers too."""
    out: Dict[str, Any] = {}
    offsets: Dict[str, int] = {}
    for key, dtype, shape in layout:
        if key == VALID_KEY:
            out[key] = packed[key]
            continue
        at = offsets.get(dtype, 0)
        size = math.prod(shape)
        offsets[dtype] = at + size
        out[key] = packed[dtype][at] if shape == () else packed[dtype][at : at + size].reshape(shape)
    return out


# jit cache: (query SHAPE fingerprint, segment signature, backend) -> plan.
# Shape-keyed (query/shape.py): literals ride the params pytree, so distinct
# literals of one query shape share a single traced program.  Bounded LRU —
# an unbounded plan cache under shape churn (many distinct query shapes) is
# a slow memory leak; eviction only drops OUR reference, XLA's own
# executable cache keeps the compiled artifact reusable.
_PLAN_CACHE_ENTRIES = 512  # override: PINOT_TPU_PLAN_CACHE_ENTRIES


def _plan_cache_entries() -> int:
    import os

    return int(os.environ.get("PINOT_TPU_PLAN_CACHE_ENTRIES", _PLAN_CACHE_ENTRIES))


from pinot_tpu.utils.cache import LruCache  # noqa: E402  (after np/jax imports)

_PLAN_CACHE: LruCache = LruCache(max_entries=_plan_cache_entries(), name="compile.sse")


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()


def attach_plan_cache_budget(budget) -> None:
    """Charge the SSE plan cache's byte accounting to a shared host ledger
    (cluster.admission.ResourceBudget) — the broker attaches its admission
    budget here so cached plans + cached results + in-flight working sets
    all bound against ONE budget.  Clears the cache on first attach so every
    resident entry is charged exactly once; idempotent for the same ledger
    (repeat broker constructions must not cold the cache)."""
    if _PLAN_CACHE.budget is budget:
        return
    _PLAN_CACHE.clear()
    _PLAN_CACHE.budget = budget


def plan_cache_size() -> int:
    return len(_PLAN_CACHE)


def _sig_value(v):
    return v.item() if isinstance(v, np.generic) else v


_NO_VALUE_COLUMNS: frozenset = frozenset()


def _value_reads(ctx: QueryContext) -> Tuple[Tuple, List[AggregationSpec]]:
    """What `ctx` alone says of the columns its kernel reads BY VALUE
    (transform.column_values), as _build_plan's _agg_inputs reaches them,
    hashable: ((the columns an aggregation's expressions reach through
    eval_expr's COLUMN case, ...), ((function, column, the options its
    binding reads), ...) of the aggregations over a bare column whose
    function agg_input_codes feeds: codes or values, which is its binding's
    to say a segment); and those aggregations."""
    leaves: List[str] = []
    coded: List[AggregationSpec] = []
    for spec in ctx.aggregations:
        fn = for_spec(spec)
        if spec.expr is None or getattr(fn, "mv_input", False):
            continue
        exprs = list(spec.extra_exprs) if fn.needs_extra_exprs else []
        if fn.needs_codes:
            if spec.expr.is_column:
                coded.append(spec)
        elif not (fn.name == "count" and spec.expr.is_column):
            exprs.append(spec.expr)
        for e in exprs:
            leaves.extend(value_leaves(e))
    if not leaves and not coded:
        return _NO_VALUE_READS

    def option(key):
        v = ctx.options.get(key)
        return tuple(v) if isinstance(v, list) else v

    return (
        tuple(sorted(set(leaves))),
        tuple([
            (spec.function, spec.expr.op, option(f"__range__{spec.expr.op}"), option(f"__dictfp__{spec.expr.op}"))
            for spec in coded
        ]),
    ), coded


_NO_VALUE_READS: Tuple[Tuple, List[AggregationSpec]] = (((), ()), [])


def _value_columns(ctx: QueryContext, segment, dict_sizes: Dict[str, int], reads: Tuple) -> frozenset:
    """The columns of `segment` the query's kernel reads by value in
    code_lookup's RESIDENT form: the rule's (ops/code_lookup.lookup_form) of
    the dictionary's COMPILED length (`dict_sizes`), its device dtype and the
    codes' rank; `reads` is _value_reads(ctx)."""
    (leaves, _), specs = reads
    out = [name for name in leaves if _resident_form(segment.columns.get(name), dict_sizes)]
    for spec in specs:
        name = spec.expr.op
        if name in out or not _resident_form(segment.columns.get(name), dict_sizes):
            continue
        fn = for_spec(spec)
        if fn.needs_binding:
            fn = fn.bind_column(column_binding(spec, segment, ctx))
        if getattr(fn, "input_kind", "codes") != "codes":
            out.append(name)
    return frozenset(out) if out else _NO_VALUE_COLUMNS


def _resident_form(c, dict_sizes: Dict[str, int]) -> bool:
    """Whether column `c`'s dictionary, at its compiled length, is one
    lookup_form would read decoded (1-D codes: a single-value column)."""
    if c is None or not c.has_dictionary or c.is_multi_value or c.data_type.is_string_like:
        return False
    size = dict_sizes.get(c.name, 0)
    # by its length alone first (the widest dtype the form takes): nearly every
    # dictionary stops here, before its values are touched for their dtype
    if lookup_form(size, np.int32) != RESIDENT:
        return False
    return lookup_form(size, c.dictionary.device_values().dtype) == RESIDENT


def compiled_dict_sizes(segment, needed: List[str], exact_cols: frozenset, shape) -> Dict[str, int]:
    """{dictionary column: the dictionary size a kernel over `segment`'s
    `needed` columns is compiled for}: the bound the table's segments share
    (segment/table_shape.py, `shape`: the server's, None where the caller has
    none), the segment's own cardinality for a column of `exact_cols` (its
    dictionary's VALUES are baked into the kernel), for a star-tree level
    (padded and bucketed by its own rule) and without a shape."""
    shared = shape is not None and getattr(segment, "level_rows", None) is None
    sizes = {}
    for name in needed:
        c = segment.column(name)
        if c.has_dictionary:
            sizes[name] = shape.bound(name, c) if shared and name not in exact_cols else c.cardinality
    return sizes


def compiled_rows(segment, shape) -> int:
    """The rows a kernel over `segment` is compiled for, and its resident
    columns hold: what its table's segments share (segment/table_shape.py,
    `shape`: the server's, None where the caller has none), its own count
    where they agree or without a shape; a star-tree level's table states its
    bucket itself."""
    if shape is None or getattr(segment, "true_rows", None) is not None:
        return segment.num_docs
    return shape.rows(segment)


def _segment_signature(
    segment: ImmutableSegment, needed: List[str], sketch_cols: frozenset = frozenset(),
    group_cols: frozenset = frozenset(), dict_sizes: Optional[Dict[str, int]] = None,
    rows: Optional[int] = None,
) -> Tuple:
    """`dict_sizes` (compiled_dict_sizes) is what the kernel bakes of each
    dictionary column's size; None: every column's own cardinality.  `rows`
    (compiled_rows) is the row count it bakes; None: the segment's own."""
    if rows is None:
        rows = segment.num_docs
    sig = [rows, segment.valid_docs is not None]
    if getattr(segment, "true_rows", None) is not None or rows != segment.num_docs:
        sig.append(ROWS_KEY)  # padded rows: the kernel masks by the bound row count
    for name in sorted(needed):
        c = segment.column(name)
        # MV columns: the padded width is a static kernel shape, and the
        # vector predicate bakes the index dim — both join the key.
        mv_width = None
        if getattr(c, "mv_lengths", None) is not None:
            arr = c.codes if c.codes is not None else c.values
            mv_width = int(arr.shape[1]) if arr is not None and arr.ndim == 2 else None
        # Raw GROUP BY columns include min/max: the kernel bakes rawint
        # group-dim base/cardinality in statically, so they are part of the
        # cache key.  A raw column that is only aggregated or filtered bakes
        # nothing but its limb plan (column_limb_sig below) — keying it on
        # min/max would compile one kernel per segment.
        raw_range = None
        if name in group_cols and not c.has_dictionary and c.data_type.is_numeric:
            raw_range = (
                (_sig_value(c.stats.min_value), _sig_value(c.stats.max_value)) if c.stats.num_docs else (0, 0)
            )
        # Sketch-bound columns bake DICTIONARY-DERIVED constants (a string
        # column's HLL hash tables, a presence domain, histogram edges taken
        # from the segment's own stats) into the compiled kernel as closure
        # constants — the exact dictionary must be part of the cache key or
        # a same-shaped segment silently reuses another segment's tables.
        # `sketch_cols` holds only the columns that do (baked_columns): a
        # sketch that hashes a numeric column's VALUES on the device, or
        # bins by the table's injected range, bakes nothing of a segment.
        sketch_extra = None
        if name in sketch_cols:
            sketch_extra = (
                c.dictionary.fingerprint() if c.has_dictionary else None,
                _sig_value(c.stats.min_value),
                _sig_value(c.stats.max_value),
            )
        sig.append(
            (
                name,
                (c.cardinality if dict_sizes is None else dict_sizes[name]) if c.has_dictionary else -1,
                str(c.codes.dtype if c.codes is not None else c.values.dtype),
                # packed lane width: packed and unpacked segments trace
                # different kernels (word inputs vs code inputs)
                getattr(c, "code_bits", None),
                c.nulls is not None,
                raw_range,
                sketch_extra,
                column_limb_sig(c),
                c.stats.is_sorted,
                mv_width,
                tuple(
                    sorted(
                        k
                        for k, by_col in getattr(segment, "indexes", {}).items()
                        if name in by_col
                    )
                ),
            )
        )
    return tuple(sig)


def _sketch_binds(ctx: QueryContext):
    """(column, what the function's bind_column takes from a segment:
    AggFunction.binds) of every column-bound sketch aggregation."""
    for spec in ctx.aggregations:
        if spec.expr is not None and spec.expr.is_column:
            fn = for_spec(spec)
            if fn.needs_binding:
                yield spec.expr.op, fn.binds


def sketch_bound_columns(ctx: QueryContext) -> frozenset:
    """Columns whose sketch bindings bake per-segment constants into kernels
    whatever the segment holds.  Not among them: a column under a VALUE-hash
    sketch alone (value_hashed_columns), and one whose function takes the
    column's range alone where the engine injected the TABLE's
    (`__range__<col>`, which the shape fingerprint holds among the options):
    every segment then binds alike."""
    return frozenset(
        col for col, binds in _sketch_binds(ctx)
        if binds != "value_hash" and (binds != "range" or f"__range__{col}" not in ctx.options)
    )


def value_hashed_columns(ctx: QueryContext) -> frozenset:
    """Columns under a VALUE-hash sketch: they bake a segment's dictionary
    only where it is a string's (baked_columns)."""
    return frozenset(col for col, binds in _sketch_binds(ctx) if binds == "value_hash")


def baked_columns(segment, bound: frozenset, hashed: frozenset) -> frozenset:
    """The columns whose dictionary VALUES `segment`'s kernel bakes: `bound`,
    and of `hashed` (value_hashed_columns) the string columns: their values
    never reach the device, so sketches.DistinctCountHLLFunction hashes the
    dictionary on the host into tables the kernel closes over.  A numeric
    column is hashed on the device by value and bakes nothing."""
    strings = [
        name for name in hashed
        for c in [segment.columns.get(name)]
        if c is not None and c.has_dictionary and c.data_type.is_string_like
    ]
    return bound | frozenset(strings) if strings else bound


def const_bound_columns(ctx: QueryContext) -> frozenset:
    """Columns whose DICTIONARY VALUES are baked into compiled kernels as
    closure constants: any column under a dictionary-domain function call
    (derived arrays, transform.py) or an expression group-by (derived remap
    / expr ranges).  Their dictionary fingerprint must join the plan-cache
    signature or a same-shaped segment would reuse another segment's
    constants (same hazard as sketch bindings)."""
    from pinot_tpu.query import scalar

    out = set()

    def visit(e: Expr) -> None:
        if e is None:
            return
        if e.kind.name == "CALL":
            if e.op in scalar.DICT_FNS:
                out.update(e.columns())
            for a in e.args:
                visit(a)

    def visit_filter(node) -> None:
        if node is None:
            return
        if node.predicate is not None:
            visit(node.predicate.lhs)
        for ch in node.children:
            visit_filter(ch)

    for g in ctx.group_by:
        if not g.is_column:
            out.update(g.columns())  # expr dims bake ranges/remaps
    for spec in list(ctx.aggregations):
        if spec.expr is not None:
            visit(spec.expr)
        if spec.filter is not None:
            visit_filter(spec.filter)
    visit_filter(ctx.filter)
    return frozenset(out)


def guard_sparse_vector_fields(kind: str, aggs: List[AggFunction]) -> None:
    """Pre-trace check for the sparse group path.  Round 5: vector-field
    sketches (DISTINCTCOUNT/HLL/PERCENTILE/MODE/theta/...) now ride the
    sparse kernel through their own partial_grouped over slot ids
    (sparse_grouped_tables), matching the reference's high-cardinality
    group-by with any aggregation (DefaultGroupByExecutor.java:51 + object
    result holders).  Only genuinely un-groupable forms raise early with a
    pointed message instead of failing mid-trace."""
    if kind != "groupby_sparse":
        return
    from pinot_tpu.query.sketches import DistinctCountValueSetFunction

    for fn in aggs:
        base = getattr(fn, "base", fn)  # MV wrappers delegate
        if isinstance(base, DistinctCountValueSetFunction):
            raise NotImplementedError(
                "exact grouped DISTINCTCOUNT requires a shared dictionary across "
                "segments; these segments' dictionaries differ — use DISTINCTCOUNTHLL"
            )
        if getattr(fn, "subfilter_args", False):
            raise NotImplementedError(
                "theta sub-filter set expressions do not support GROUP BY"
            )


def _all_column_names(segment) -> List[str]:
    """All queryable columns, INCLUDING schema-evolution virtuals the
    segment's own (older) schema does not list."""
    cols = getattr(segment, "columns", None)
    if isinstance(cols, dict):
        return list(cols)
    return segment.schema.column_names


def _referenced_columns(ctx: QueryContext) -> Tuple[List[str], List[str], set]:
    """What `ctx` alone says about the columns a query reads: (the column
    references of WHERE, GROUP BY and the select list, in that order and
    with repeats; those of ORDER BY and HAVING; the aliases of selected
    aggregations).  _needed_columns makes a segment's list of them."""
    cols: List[str] = []
    if ctx.filter:
        cols.extend(ctx.filter.columns())
    for g in ctx.group_by:
        cols.extend(g.columns())
    from pinot_tpu.query.ir import WindowSpec

    for s in list(ctx.select_list) + list(ctx.extra_aggregations):
        if isinstance(s, AggregationSpec):
            if s.expr is not None:
                cols.extend(s.expr.columns())
            for ex in s.extra_exprs:
                cols.extend(ex.columns())
            if s.filter:
                cols.extend(s.filter.columns())
            fn_ = for_spec(s)
            if getattr(fn_, "subfilter_args", False):
                for node in fn_.filter_nodes:
                    cols.extend(node.columns())
        elif isinstance(s, WindowSpec):
            if s.expr is not None:
                cols.extend(s.expr.columns())
            for p in s.partition_by:
                cols.extend(p.columns())
            for o in s.order_by:
                cols.extend(o.expr.columns())
        else:
            cols.extend(s.columns())
    # "*" here can only come from count(*) inside an ORDER BY/HAVING call —
    # it needs no column loads (unlike SELECT *).
    late: List[str] = []
    for o in ctx.order_by:
        late.extend(c for c in o.expr.columns() if c != "*")
    if ctx.having:
        late.extend(c for c in ctx.having.columns() if c != "*")
    agg_aliases = {
        a
        for s, a in zip(ctx.select_list, ctx.select_aliases)
        if a and isinstance(s, AggregationSpec)
    }
    return cols, late, agg_aliases


def _needed_columns(
    ctx: QueryContext, segment: ImmutableSegment, referenced: Optional[Tuple] = None
) -> List[str]:
    """The columns of `segment` the query reads, each once, in the order
    first referenced.  `referenced` is _referenced_columns(ctx) where the
    caller already has it."""
    cols, late, agg_aliases = referenced if referenced is not None else _referenced_columns(ctx)
    # ORDER BY/HAVING references to AGGREGATION aliases are resolved by
    # reduce against final arrays, not segment columns — skip them unless a
    # physical column shadows the alias.
    if agg_aliases:
        alias_only = agg_aliases - set(segment.schema.column_names)
        late = [c for c in late if c not in alias_only]
    seen, out = set(), []
    for c in [*cols, *late]:
        if c == "*":
            for name in _all_column_names(segment):
                if name not in seen:
                    seen.add(name)
                    out.append(name)
            continue
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def _non_filter_columns(ctx: QueryContext, segment) -> set:
    """Columns the kernel needs independent of WHERE / FILTER clauses."""
    import dataclasses as dc

    def strip(s):
        if isinstance(s, AggregationSpec) and s.filter is not None:
            return dc.replace(s, filter=None)
        return s

    ctx2 = dc.replace(
        ctx,
        filter=None,
        select_list=[strip(s) for s in ctx.select_list],
        extra_aggregations=[strip(s) for s in ctx.extra_aggregations],
    )
    return set(_needed_columns(ctx2, segment))


def _group_dim(
    expr: Expr, segment: ImmutableSegment, null_handling: bool, dict_sizes: Optional[Dict[str, int]] = None
) -> GroupDim:
    """`dict_sizes` (compiled_dict_sizes): a dictionary dimension's stride in
    the key space is the size its kernel was compiled for, which may pass
    the segment's own dictionary; the decode reads the segment's own."""
    from pinot_tpu.query import scalar

    if expr.is_column:
        c = segment.column(expr.op)
        if getattr(c, "is_multi_value", False):
            if c.dictionary is None:
                raise NotImplementedError(f"GROUP BY on raw MV column {c.name} (vector columns are not groupable)")
            return GroupDim(
                expr, c.name, "dict", c.dictionary.cardinality, dictionary=c.dictionary, mv=True
            )
        null_code = -1
        if c.has_dictionary:
            if c.nulls is not None and null_handling:
                nc = c.dictionary.index_of(c.data_type.null_placeholder)
                if nc >= 0:
                    null_code = nc
            size = c.dictionary.cardinality if dict_sizes is None else dict_sizes[c.name]
            return GroupDim(expr, c.name, "dict", size, dictionary=c.dictionary, null_code=null_code)
        if c.data_type in (DataType.INT, DataType.LONG, DataType.TIMESTAMP, DataType.BOOLEAN):
            lo, hi = int(c.stats.min_value), int(c.stats.max_value)
            rng = hi - lo + 1
            return GroupDim(expr, c.name, "rawint", rng, base=lo)
        raise NotImplementedError(f"group-by on raw {c.data_type.value} column {c.name} is not groupable")
    # GROUP BY <expression> (ExpressionContext function analog):
    # string-valued dictionary function -> derived dictionary dimension
    if scalar.is_dict_fn_expr(expr) and scalar.string_result(expr):
        col = next(a for a in expr.args if not a.is_literal).op
        c = segment.column(col)
        if c.has_dictionary:
            derived = scalar.derived_for(expr, c.dictionary)
            uniq, remap = np.unique(derived, return_inverse=True)
            return GroupDim(
                expr,
                col,
                "derived",
                len(uniq),
                derived_values=uniq,
                remap=remap.astype(np.int32),
            )
    # integer-valued device expression -> statically bounded expr dimension
    # (GROUP BY DATETRUNC('day', ts) — the archetypal OLAP bucketing)
    rng = scalar.expr_int_range(expr, segment)
    if rng is not None:
        lo, hi = rng
        return GroupDim(expr, str(expr), "expr", hi - lo + 1, base=lo)
    raise NotImplementedError(
        f"group-by expression {expr} is not supported: its integer range cannot be "
        "bounded from column stats and it is not a dictionary string function"
    )


def column_binding(spec, segment, ctx: Optional[QueryContext] = None):
    """Per-column constants for sketch aggregations (query/sketches.py).

    Alignment resolution: engine-injected options carry the table-global
    value range ("__range__<col>") and dictionary-fingerprint consensus
    ("__dictfp__<col>", "MIXED" when segments disagree).  A dict column whose
    key space is NOT shared across segments must not merge code-indexed
    partials — numeric columns downgrade to a value-range ("rawint") binding,
    everything else to "raw" (hash-based sketches only)."""
    from pinot_tpu.query.sketches import ColumnBinding

    e = spec.expr
    if e is None or not e.is_column:
        raise NotImplementedError(f"{spec.function} requires a bare column argument")
    c = segment.column(e.op)
    mn, mx = c.stats.min_value, c.stats.max_value
    aligned = True
    if ctx is not None:
        rng = ctx.options.get(f"__range__{e.op}")
        if rng is not None:
            mn, mx = rng
        aligned = ctx.options.get(f"__dictfp__{e.op}", "") != "MIXED"
    dict_values = c.dictionary.values if c.has_dictionary else None
    numeric = not c.data_type.is_string_like
    wide = numeric and c.data_type.np_dtype.itemsize == 8
    if c.has_dictionary and aligned:
        return ColumnBinding(
            "dict", domain=c.dictionary.cardinality, dict_values=dict_values,
            numeric=numeric, wide=wide, min_value=mn, max_value=mx,
        )
    if c.data_type in (DataType.INT, DataType.LONG, DataType.TIMESTAMP, DataType.BOOLEAN) and mn is not None:
        rng_width = int(mx) - int(mn) + 1
        if rng_width <= MAX_DENSE_RAW_INT_RANGE:
            return ColumnBinding("rawint", domain=rng_width, base=int(mn), wide=wide, min_value=mn, max_value=mx)
    # dict_values still flow through: value-based host hashing (HLL) stays
    # correct across misaligned dictionaries
    return ColumnBinding("raw", dict_values=dict_values, numeric=numeric, wide=wide, min_value=mn, max_value=mx)


def bind_aggs(agg_specs, segment, ctx: QueryContext):
    """Specialize + column-bind the aggregation functions for one plan."""
    out = []
    for spec in agg_specs:
        fn = for_spec(spec)
        if fn.needs_binding:
            fn = fn.bind_column(column_binding(spec, segment, ctx))
        out.append(fn)
    return out


def mv_agg_input(spec, fn, segment, cols, mask):
    """(values, mask) for an MV aggregation: padded [rows, max_len] element
    matrix + combined row-filter x length mask."""
    if spec.expr is None or not spec.expr.is_column:
        raise ValueError(f"{spec.function} requires a multi-value column argument")
    c = segment.column(spec.expr.op)
    if not getattr(c, "is_multi_value", False):
        raise ValueError(f"{spec.function} requires a multi-value column; {spec.expr.op} is single-value")
    entry = cols[spec.expr.op]
    codes = entry["codes"].astype(jnp.int32)
    pad = jnp.arange(codes.shape[1], dtype=jnp.int32)[None, :] < entry["lengths"][:, None].astype(jnp.int32)
    m2 = mask[:, None] & pad
    if fn.needs_codes:
        return codes, m2
    if fn.base.name == "count":
        return m2, m2
    if c.data_type.is_string_like:
        raise ValueError(f"{spec.function} needs numeric elements; {spec.expr.op} is {c.data_type.value}")
    vals = entry["dict"][jnp.minimum(codes, np.int32(c.dictionary.cardinality - 1))]
    return vals, m2


def agg_input_codes(spec, fn, segment, cols, mask, null_handling: bool):
    """Kernel-side input for needs_codes aggregations, dispatched on the
    bound function's input_kind:
      codes         - dictionary codes (shared key space / per-segment hash
                      tables index by them)
      values_offset - decoded numeric values minus the binding's base (a
                      table-global int range, aligned by construction)
      values_hash   - raw numeric values, hashed on device (full bit
                      pattern; see sketches._device_hash_values)"""
    import jax.numpy as jnp

    from pinot_tpu.query.transform import column_values

    name = spec.expr.op
    c = segment.column(name)
    entry = cols[name]
    if c.nulls is not None and null_handling:
        mask = mask & ~entry["nulls"]
    kind = getattr(fn, "input_kind", "codes")
    if kind == "codes":
        if not c.has_dictionary:
            raise ValueError(f"{spec.function} bound to codes but column {name} has no dictionary")
        return entry["codes"].astype(jnp.int32), mask
    vals, _ = column_values(name, segment, cols)
    if kind == "values_offset":
        return (vals - np.asarray(fn.base, dtype=vals.dtype)).astype(jnp.int32), mask
    return vals, mask  # values_hash


def column_limb_sig(c) -> Optional[Tuple[int, bool]]:
    """Limb-decomposition plan implied by an int column's stats — part of the
    kernel cache key because grouped_partials bakes it into the trace."""
    if c.data_type in (DataType.INT, DataType.LONG, DataType.TIMESTAMP, DataType.BOOLEAN):
        s = c.stats
        if s.num_docs and s.min_value is not None:
            if int(s.min_value) < -(1 << 31) or int(s.max_value) >= (1 << 31):
                # past int32 the fused scan takes signed-magnitude int64 limbs
                return ("int64", ops.sum_limb_plan64(s.min_value, s.max_value))
            return ops.sum_limb_plan(s.min_value, s.max_value)
    return None


def agg_vranges(agg_specs, table_like) -> List[Optional[Tuple[int, int]]]:
    """Per-aggregation (min, max) column stats when the input is a bare int
    column — lets the fused scan drop statically-zero limbs."""
    out: List[Optional[Tuple[int, int]]] = []
    for spec in agg_specs:
        rng = None
        e = spec.expr
        if e is not None and e.is_column and e.op != "*":
            try:
                c = table_like.column(e.op)
            except KeyError:
                c = None
            if c is not None and c.data_type in (
                DataType.INT, DataType.LONG, DataType.TIMESTAMP, DataType.BOOLEAN
            ):
                s = c.stats
                if s.num_docs and s.min_value is not None:
                    rng = (int(s.min_value), int(s.max_value))
        out.append(rng)
    return out


def grouped_partials(aggs, inputs, tmask, key, num_groups: int, vranges,
                     backend=None, mask_words=None, key_packed=None):
    """Presence table + per-agg grouped partial dicts for the dense path.

    All additive fields (presence, counts, sums, sums of squares) across ALL
    aggregations share ONE fused one-hot-matmul scan
    (ops.fused_group_tables) — one (A, B) one-hot pair per chunk instead of
    one per table, the single biggest kernel-time win of round 2.  min/max
    fields scatter (no matmul semiring); sketch functions (field_kinds None)
    run their own partial_grouped.

    backend tags the plan-time scan backend (ops.scan_backend()) so eligible
    entry sets route to the Pallas fused kernel.  mask_words optionally
    carries the filter as PACKED uint32 bitmap words instead of folded into
    tmask/input masks — the Pallas scan unpacks them in-register.
    key_packed optionally carries the group-key column's bit-packed forward
    index as (words, code_bits) so the Pallas scan streams packed key bytes
    and lane-unpacks in-register; `key` must still be the (trace-level
    unpacked) codes for every non-Pallas consumer.  Scatter and sketch
    paths never see packed words, so they are defensively unpacked here
    whenever any aggregation needs a non-fusable field."""
    if mask_words is not None:
        fuse_ok = all(fn.field_kinds is not None for fn in aggs) and all(
            k in ("count", "sum", "sumsq")
            for fn in aggs
            for k in fn.field_kinds.values()
        )
        if not fuse_ok:
            row_mask = ops.unpack_bitmap_words(mask_words, tmask.shape[0])
            tmask = tmask & row_mask
            inputs = [(v, m & row_mask) for v, m in inputs]
            mask_words = None
    entries: List[Tuple] = []
    slot_of: Dict[Tuple, int] = {}

    def entry_slot(kind, values, mask, limb_plan=None) -> int:
        k = (kind, id(values) if values is not None else None, id(mask), limb_plan)
        idx = slot_of.get(k)
        if idx is None:
            idx = len(entries)
            entries.append((kind, values, mask, limb_plan))
            slot_of[k] = idx
        return idx

    presence_idx = entry_slot("count", None, tmask)
    requests: List[Tuple[str, Optional[Dict]]] = []
    for i, (fn, (vals, mask)) in enumerate(zip(aggs, inputs)):
        if fn.field_kinds is None:
            requests.append(("own", None))
            continue
        fmap: Dict[str, Tuple[str, Optional[int]]] = {}
        for field, kind in fn.field_kinds.items():
            if kind == "count":
                fmap[field] = ("fused", entry_slot("count", None, mask))
            elif kind == "sum":
                ent = ops.int_sum_entry(vals, vranges[i] if i < len(vranges) else None)
                if ent is not None:
                    fmap[field] = ("fused", entry_slot(ent[0], ent[1], mask, ent[2]))
                else:
                    fmap[field] = ("fused", entry_slot("f32_sum", vals, mask))
            elif kind == "sumsq":
                fmap[field] = ("fused", entry_slot("f32_sumsq", vals, mask))
            else:
                fmap[field] = (kind, None)  # min/max: scatter below
        requests.append(("fields", fmap))

    tables = ops.fused_group_tables(
        entries, key, num_groups, backend=backend, mask_words=mask_words,
        codes_packed=key_packed,
    )

    def _as_table(idx):
        t = tables[idx]
        if entries[idx][0] == "count":
            return t.astype(jnp.int64)
        return t

    presence = _as_table(presence_idx)
    partials: List[Dict] = []
    for (tag, fmap), fn, (vals, mask) in zip(requests, aggs, inputs):
        if tag == "own":
            partials.append(fn.partial_grouped(vals, mask, key, num_groups))
            continue
        p: Dict[str, Any] = {}
        for field, (k2, idx) in fmap.items():
            if k2 == "fused":
                p[field] = _as_table(idx)
            elif k2 == "min":
                p[field] = ops.group_min(vals, mask, key, num_groups)
            else:
                p[field] = ops.group_max(vals, mask, key, num_groups)
        partials.append(p)
    return presence, partials


# sentinel packed key for rows filtered out / slots never written; all real
# packed keys are >= 0, so int64 max never collides
SPARSE_EMPTY_KEY = np.int64(np.iinfo(np.int64).max)


def order_by_agg_index(ctx: QueryContext) -> Optional[Tuple[int, bool]]:
    """Map the FIRST ORDER BY expression to an index into ctx.aggregations
    (by alias or by call shape).  The trim paths use it to rank groups by
    the ORDER BY comparator before dropping any — the TableResizer analog
    (pinot-core/.../core/data/table/TableResizer.java) replacing the
    round-4 lowest-packed-key trim that could drop the true top groups of
    a `GROUP BY hi_card ORDER BY SUM(x) DESC LIMIT k` query."""
    if not ctx.order_by:
        return None
    ob = ctx.order_by[0]
    e = ob.expr
    specs = list(ctx.aggregations)
    if e.is_column:
        # alias of a select aggregation
        for s, a in zip(ctx.select_list, ctx.select_aliases):
            if a == e.op and isinstance(s, AggregationSpec):
                fp = s.fingerprint()
                for i, sp in enumerate(specs):
                    if sp.fingerprint() == fp:
                        return i, ob.ascending
        return None
    if e.kind.name != "CALL":
        return None
    for i, sp in enumerate(specs):
        if sp.filter is not None or sp.extra_exprs or sp.literal_args:
            continue
        if e.op.lower() != sp.function.lower():
            continue
        if sp.expr is None:
            if not e.args or (len(e.args) == 1 and e.args[0].is_column and e.args[0].op == "*"):
                return i, ob.ascending
        elif len(e.args) == 1 and e.args[0].fingerprint() == sp.expr.fingerprint():
            return i, ob.ascending
    return None


def kernel_order_spec(ctx: QueryContext, aggs: List[AggFunction]) -> Optional[Tuple[int, str, bool]]:
    """(agg index, contribution mode, ascending) when the first ORDER BY key
    is an aggregate whose per-group order value the sparse kernel can derive
    in one pass: additive sum/count via a segment cumsum, min/max via a
    secondary sort key.  None falls back to the lowest-packed-key trim."""
    hit = order_by_agg_index(ctx)
    if hit is None:
        return None
    i, asc = hit
    fn = aggs[i]
    mode = {"sum": "sum", "count": "count", "min": "min", "max": "max"}.get(fn.name)
    if mode is None or getattr(fn, "mv_input", False) or getattr(fn, "needs_extra_exprs", False):
        return None
    return i, mode, asc


def packed_key64(cols, group_dims, segment) -> jnp.ndarray:
    """Ravel per-dim codes into one int64 key (device side).  The planner
    guards the key space to < 2^62 before choosing the sparse path."""
    key = None
    for gd in group_dims:
        code = gd.device_code(cols, segment, jnp.int64)
        key = code if key is None else key * np.int64(gd.cardinality) + code
    return key


def sparse_grouped_tables(aggs, inputs, tmask, key, num_slots: int, order_spec=None,
                          num_groups: Optional[int] = None, vranges=()):
    """Device-side high-cardinality group-by: sort, then FIXED-size tables
    read off the sorted rows (the IndexedTable analog with numGroupsLimit
    trim built into the kernel).

    Replaces the round-1/2 host fallback that device_get the mask, codes and
    every agg input for ALL rows (tens of GB over PCIe at 1B rows).  Now the
    kernel returns [num_slots]-sized tables only:

      sort rows by packed key (filtered rows get SPARSE_EMPTY_KEY, sorting
      last) -> group starts where the sorted key changes -> running group
      index = cumsum(starts) -> rows beyond num_slots groups fall in a
      dropped overflow slot.  Sorted keys make the trim deterministic (lowest
      keys win — the documented analog of Pinot's first-arrival trim).

    After the sort a group's rows are contiguous, so a slot is a row range
    [lo, hi).  ONE row-length scatter records each slot's first row (a
    scatter-min of the row index by slot); its end is the next slot's first
    row where slots rise with the rows, and under the ORDER BY-aware trim
    (slots are ranks) the next group start after it (`nxt`), either cut at
    the first filtered row.  The keys are the sorted key gathered at the
    slots' first rows: a table-size gather, no scatter.

    Accumulation dtypes mirror the host reduce contracts: counts int64,
    sums/sumsq float64 (exact for int sums < 2^53 — the reference likewise
    accumulates long sums in double), min/max float64.  What the tables
    cost is their row-length scatters: the chip takes ~9 ns a row for an
    int32 one whatever it writes and wherever (13-14 ms a 1.5M-row
    segment) and ~85 for a 64-bit one (a pair of 32-bit halves), while a
    prefix sum streams (~1 ms) and a table-size gather is ~1.5 ms.  So
    under accum_policy() "chunked32" a count is hi - lo (an aggregate with
    a mask of its own: the difference of the permuted mask's prefix sum)
    and the sum of an integer input is, an 8-bit limb, the difference of
    that limb's int32 prefix sum at hi and lo, limbs met in int64 at table
    size (ops.limb_prefix_table; exact, ops/segmented.py says why;
    `vranges`, from agg_vranges, shrinks a bare column's limb count by its
    stats): SSB Q4.3's key, count and sum cost 404-416 ms a segment as
    three 64-bit scatters, 89 as six int32 ones (PERF.md, PR 42) and 33 as
    one scatter and five prefix sums (PR 44), the same tables bit for bit.
    Float sums, sumsq, min / max and the sketch family keep their 64-bit
    scatters on the slot: a float prefix difference rounds differently, and
    an f32 table would be a lower precision than this path states.  The
    "wide" policy (CPU) keeps the 64-bit scatters for counts and sums too.

    num_groups, when given, is the static size of the key space (every key
    is < num_groups).  It buys two things the TPU cares about — a 64-bit or
    multi-key row-length sort is minutes of compile there and emulated at
    run time: a key space under 2^31 sorts as int32, and a key space that
    fits the slots (num_slots >= num_groups) can never trim, so the ORDER
    BY-aware ranking sort is skipped.

    Returns (uniq_keys[num_slots] int64 with SPARSE_EMPTY_KEY padding,
             [{field: table[num_slots]}] per agg)."""
    from jax import lax

    from pinot_tpu.utils.metrics import METRICS

    METRICS.counter("scan.traced.sparse_sort").inc()  # trace time: this plan's table is sorted, not scattered by key
    with jax.named_scope("sparse_sort"):
        n = tmask.shape[0]
        if num_groups is not None and num_slots >= num_groups:
            order_spec = None
        i32_max = np.iinfo(np.int32).max  # the int32 twin of SPARSE_EMPTY_KEY
        if num_groups is not None and num_groups < i32_max:
            krow = jnp.where(tmask, key.astype(jnp.int32), i32_max)
        else:
            krow = jnp.where(tmask, key, SPARSE_EMPTY_KEY)
        iota = jnp.arange(n, dtype=jnp.int32)
        if order_spec is not None and order_spec[1] in ("min", "max"):
            # min/max order value rides the row sort as a secondary key: after
            # sorting by (key, ±value) the group's extremum sits at its start row
            oi, omode, _ = order_spec
            ov_raw, om = inputs[oi]
            ovr = ov_raw.astype(jnp.float64)
            ovr = ovr if omode == "min" else -ovr
            ovr = jnp.where(om, ovr, jnp.inf)
            skey, sov, perm = lax.sort((krow, ovr, iota), num_keys=2)
        else:
            sov = None
            skey, perm = lax.sort((krow, iota), num_keys=1)
        # a filtered row sorted as the sentinel and every real key is below it:
        # tmask[perm] without the row-length gather (~13 ms a 1.5M-row segment on the chip)
        no_key = i32_max if skey.dtype == jnp.int32 else SPARSE_EMPTY_KEY
        smask = skey != no_key
        prev = jnp.concatenate([jnp.full((1,), -1, skey.dtype), skey[:-1]])
        is_start = smask & (skey != prev)
        seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1
        if order_spec is None:
            # slot num_slots = overflow/invalid bin, sliced off before returning;
            # first-num_slots-groups-by-packed-key trim (deterministic)
            slot = jnp.where(smask & (seg < num_slots), seg, num_slots)
        else:
            # ORDER BY-aware trim (TableResizer analog): compute each group's
            # order value in-row-space, rank groups by (order value, packed key)
            # on device, and give slots to the top num_slots groups only.
            oi, omode, asc = order_spec
            # each row's next group start (smallest start index > i; n past the last): the end, in
            # row space, of the group that starts at i
            starts_at = jnp.where(is_start, iota, np.int32(n))
            nxt = jnp.concatenate([lax.cummin(starts_at[::-1])[::-1][1:], jnp.full((1,), n, jnp.int32)])
            if sov is not None:
                empty = jnp.isinf(sov)  # no agg-mask rows in the group: NULL
                group_ov = sov  # valid at start rows: the group's min / -max
                group_ov = group_ov if asc else -group_ov
                # sov carries -v for max, so one more flip restores the sign
                if omode == "max":
                    group_ov = -group_ov
                # NULL (empty) and NaN order values rank LAST in every direction
                # (matching the host-side _order_trim_select NaN handling); clamp
                # keeps them FINITE so the finite check below still marks the
                # group rankable instead of dropping it (review-caught).  An
                # all-NaN group's start-row sov is NaN (NaN sorts last), which
                # would otherwise survive clip as NaN and drop the group.
                group_ov = jnp.clip(
                    jnp.where(empty | jnp.isnan(group_ov), jnp.inf, group_ov), -1e300, 1e300
                )
            else:
                ov_raw, om = inputs[oi]
                isn = None
                if omode == "count":
                    c = om.astype(jnp.float64)
                else:
                    v = ov_raw if getattr(ov_raw, "ndim", 0) else jnp.broadcast_to(ov_raw, (n,))
                    cv = v.astype(jnp.float64)
                    # NaN rows are excluded from the cumsum (one NaN would poison
                    # the prefix sums of every later-keyed group) and tracked per
                    # group instead; NaN-sum groups rank last like the host path
                    isn = jnp.isnan(cv)
                    c = jnp.where(om & ~isn, cv, 0.0)
                cp = c[perm]
                s0 = jnp.concatenate([jnp.zeros((1,), jnp.float64), jnp.cumsum(cp)])
                total = s0[nxt] - s0[iota]  # valid at start rows
                group_ov = total if asc else -total
                if omode == "sum":
                    # SUM over zero agg-mask rows is SQL NULL, not 0: count the
                    # mask the same way and send empty groups to rank-last
                    mp = om.astype(jnp.float64)[perm]
                    m0 = jnp.concatenate([jnp.zeros((1,), jnp.float64), jnp.cumsum(mp)])
                    np_ = (isn & om).astype(jnp.float64)[perm]
                    n0 = jnp.concatenate([jnp.zeros((1,), jnp.float64), jnp.cumsum(np_)])
                    # rank-last when the group saw a NaN value, when the prefix
                    # sums overflowed to inf (inf - inf = NaN), or when no
                    # agg-mask rows contributed (SQL NULL)
                    bad = ((n0[nxt] - n0[iota]) > 0) | jnp.isnan(group_ov)
                    group_ov = jnp.clip(
                        jnp.where(bad | ((m0[nxt] - m0[iota]) <= 0), jnp.inf, group_ov),
                        -1e300, 1e300,
                    )
            ovkey = jnp.where(is_start, group_ov, jnp.inf)
            sovk, sskey, sseg = lax.sort((ovkey, skey, seg), num_keys=2)
            rank = jnp.minimum(iota, np.int32(num_slots))
            ranks = (
                jnp.full((n + 1,), num_slots, dtype=jnp.int32)
                .at[jnp.where(jnp.isfinite(sovk), sseg, np.int32(n))]
                .set(rank, mode="drop")
            )
            gslot = ranks[jnp.minimum(seg, np.int32(n))]
            slot = jnp.where(smask & (gslot < num_slots), gslot, num_slots)
    # the chip's form: counts and integer sums read from int32 prefix sums over the sorted rows (docstring)
    limbs = ops.accum_policy() == "chunked32"
    with jax.named_scope("sparse_scatter"):
        # the ONE row-length scatter every plan keeps: each slot's first row.  A group's rows are
        # contiguous after the sort, so a slot's table entry is a function of its row range
        # [lo, hi) alone; the overflow slot's first row (the first group past num_slots, else the
        # first filtered row) ends the last slot's range where slots rise with the rows
        n_valid = jnp.sum(smask, dtype=jnp.int32)  # the filtered rows sorted last: rows [n_valid, n)
        first = jnp.minimum(jnp.full((num_slots + 1,), n, jnp.int32).at[slot].min(iota), n_valid)
        lo = first[:num_slots]  # n_valid where the slot has no group
        if order_spec is None:
            hi, bounds = first[1:], (first,)  # slot s + 1 holds the next group: the ranges adjoin
        else:
            # slots are ranks: the end is the next group start after lo (row n has none after it)
            hi = jnp.minimum(jnp.concatenate([nxt, jnp.full((1,), n, jnp.int32)])[lo], n_valid)
            bounds = (lo, hi)
        # the keys: the sorted key at each slot's first row; row n_valid is a filtered row's sentinel, or the one appended
        uniq = jnp.concatenate([skey, jnp.full((1,), no_key, skey.dtype)])[lo]
        uniq = jnp.where(uniq == no_key, SPARSE_EMPTY_KEY, uniq.astype(jnp.int64))
        partials = []
        limb_sums = prefix_sums = False
        for i, (fn, (vals, mask)) in enumerate(zip(aggs, inputs)):
            m = smask if mask is tmask else mask[perm]

            def _perm(x):
                x = x if getattr(x, "ndim", 0) else jnp.broadcast_to(x, (n,))
                return x[perm]

            if fn.field_kinds is None:
                # sketch / own-scatter family (HLL registers, presence bitmaps,
                # histograms, KMV, (t, v) pairs, MV wrappers): the slot array IS
                # a dense group-key space of num_slots+1 ids, so the function's
                # own partial_grouped scatters per-slot vector fields directly;
                # the overflow slot is sliced off like the scalar tables.
                v = tuple(_perm(x) for x in vals) if isinstance(vals, tuple) else _perm(vals)
                own = fn.partial_grouped(v, m, slot, num_slots + 1)
                partials.append({f: t[:num_slots] for f, t in own.items()})
                continue
            v = _perm(vals)
            ent = ops.int_sum_entry(v, vranges[i] if i < len(vranges) else None) if limbs else None
            p: Dict[str, Any] = {}
            for fname in fn.fields:
                comb = FIELD_COMBINE[fname]
                if comb == "add":
                    if fname == "count" and limbs:
                        # the filter's own mask passes every row of a group: its count is the range's length
                        acc = (hi - lo if mask is tmask else ops.prefix_group_sums(m.astype(jnp.int32), *bounds)).astype(jnp.int64)
                        prefix_sums = True
                    elif fname == "count":
                        acc = jnp.zeros((num_slots + 1,), jnp.int64).at[slot].add(m.astype(jnp.int64))
                    elif fname == "sum" and ent is not None:
                        kind, lv, lp = ent
                        acc = ops.limb_prefix_table(kind, lv, m, lp, *bounds).astype(jnp.float64)
                        limb_sums = prefix_sums = True
                    else:
                        w = v.astype(jnp.float64)
                        if fname == "sumsq":
                            w = w * w
                        acc = jnp.zeros((num_slots + 1,), jnp.float64).at[slot].add(jnp.where(m, w, 0.0))
                else:
                    ident = field_identity(fname)
                    masked = jnp.where(m, v.astype(jnp.float64), ident)
                    base = jnp.full((num_slots + 1,), ident, jnp.float64)
                    acc = base.at[slot].min(masked) if comb == "min" else base.at[slot].max(masked)
                p[fname] = acc[:num_slots]
            partials.append(p)
    if limb_sums:
        # trace time: this plan's integer sums rode int32 limbs (its counts are
        # int32 under chunked32 whatever it sums)
        METRICS.counter("scan.traced.sparse_limb_scatter").inc()
    if prefix_sums:
        # trace time: this plan's counts / integer sums were read from prefix sums
        # at the slots' row ranges, not scattered
        METRICS.counter("scan.traced.sparse_prefix_sums").inc()
    return uniq, partials


@dataclass(frozen=True)
class ParamRecipe:
    """How a plan-cache entry's packed parameters are made from (segment,
    literals) without planning again.  Recorded when _build_plan compiles
    the entry; keyed, like the entry, by the query's SHAPE, so it holds no
    literal and no dictionary: a hit walks the CURRENT query's predicates in
    the order the FilterCompiler compiled them and resolves each against
    THIS segment's dictionary (filter.dict_predicate_codes, the function the
    compiler itself calls), writing straight into buffers of
    `param_layout`'s packing."""

    # one a compiled predicate, in compile order:
    # (kind, ptype, column, multi-value, ((dtype, offset, shape), ...))
    binders: Tuple[Tuple, ...]
    # (dtype, length) of each packed buffer, in pack_params' order
    buffers: Tuple[Tuple[str, int], ...]
    valid_docs: Optional[int]  # VALID_KEY rides beside the buffers: the rows it holds (the compiled count)
    rows_at: Optional[int] = None  # where ROWS_KEY sits in the int32 buffer (a plan over padded rows)


def _param_recipe(binders: Optional[List[Tuple]], layout: Tuple) -> Optional[ParamRecipe]:
    """The recipe for a plan whose FilterCompiler recorded `binders` and
    whose parameters pack as `layout`, or None where a predicate cannot be
    bound or a parameter is nobody's."""
    if binders is None:
        return None
    where: Dict[str, Tuple] = {}
    lengths: Dict[str, int] = {}
    for key, dtype, shape in layout:
        if key != VALID_KEY:
            at = lengths.get(dtype, 0)
            where[key] = (dtype, at, shape)
            lengths[dtype] = at + math.prod(shape)
    rows_at = where.pop(ROWS_KEY, None)
    out = []
    for kind, ptype, column, mv, keys in binders:
        two_ints = [("int32", ()), ("int32", ())]
        want = {"none": [], "range": two_ints, "docrange": two_ints, "table": [("bool", None)]}[kind]
        slots = [where.pop(k, None) for k in keys]
        if len(slots) != len(want) or any(
            s is None or s[0] != dtype or (shape is not None and s[2] != shape)
            for s, (dtype, shape) in zip(slots, want)
        ):
            return None
        out.append((kind, ptype, column, mv, tuple(slots)))
    if where:
        return None
    return ParamRecipe(
        tuple(out), tuple(lengths.items()),
        next((shape[0] for key, _, shape in layout if key == VALID_KEY), None),
        None if rows_at is None else rows_at[1],
    )


def _bind_params(
    recipe: ParamRecipe, predicates: List, segment, same_dict: Tuple, lookups: Dict
) -> Optional[Dict[str, np.ndarray]]:
    """`recipe` evaluated for `predicates` (the current query's, in compile
    order) against `segment`: the packed buffers _build_plan would make,
    byte for byte, or None where the recipe does not fit (the caller
    rebuilds).  `lookups` lives for one query and `same_dict[i]` says which
    dictionary predicate i meets in this segment (_dictionary_identity):
    segments whose dictionaries have the same content share one resolution
    of a predicate."""
    if len(predicates) != len(recipe.binders):
        return None
    packed = {dtype: np.empty(n, dtype) for dtype, n in recipe.buffers}
    columns = segment.columns  # the signature was made of these: each is there
    for at, ((kind, ptype, column, mv, slots), p) in enumerate(zip(recipe.binders, predicates)):
        if p.ptype is not ptype or p.lhs.op != column:
            return None
        if kind == "none":
            continue
        memo = (at, same_dict[at])
        codes = lookups.get(memo)
        if codes is None:
            codes = lookups[memo] = dict_predicate_codes(p, columns[column].dictionary)
        if kind == "range":
            (_, lo_at, _), (_, hi_at, _) = slots
            ints = packed["int32"]
            ints[lo_at], ints[hi_at] = codes[0], codes[1]
        elif kind == "docrange":
            # the signature says the column is sorted: its docs of those codes
            (_, lo_at, _), (_, hi_at, _) = slots
            ints = packed["int32"]
            ints[lo_at], ints[hi_at] = sorted_doc_range(columns[column], codes[0], codes[1])
        else:
            ((_, table_at, shape),) = slots
            table = codes[2]
            # the slot has the size the kernel was compiled for (the table's
            # bound, compiled_dict_sizes): this segment's dictionary fills
            # its head, and no code reaches the rest (a multi-value column's
            # is its own size and the padding code's slot)
            if table.shape[0] + mv > shape[0] or (mv and table.shape[0] + mv != shape[0]):
                return None
            packed["bool"][table_at : table_at + table.shape[0]] = table
            packed["bool"][table_at + table.shape[0] : table_at + shape[0]] = False
    if recipe.valid_docs is not None:
        if segment.valid_docs is None:
            return None
        packed[VALID_KEY] = _valid_mask(segment, recipe.valid_docs)
    if recipe.rows_at is not None:
        packed["int32"][recipe.rows_at] = true_rows(segment)
    return packed


def true_rows(table) -> int:
    """The rows of `table` that count: all of a segment's, and what a table
    of padded rows says (a star-tree level's table, a segment's padded view)."""
    n = table.true_rows
    return table.num_docs if n is None else n


def _row_mask(rows: int, counted):
    """bool[rows]: the rows before the bound true count `counted`."""
    return jnp.arange(rows, dtype=jnp.int32) < counted


def _valid_mask(segment, rows: int) -> np.ndarray:
    """`segment`'s validDocIds as the bool[rows] a kernel compiled for `rows`
    rows takes: a padded row is no valid doc."""
    valid = np.asarray(segment.valid_docs, dtype=bool)
    if len(valid) == rows:
        return valid
    out = np.zeros(rows, dtype=bool)
    out[: len(valid)] = valid
    return out


# A dictionary's content hash is read once and kept (Dictionary.fingerprint),
# but reading it walks every value: past this many, two segments' look-ups
# are shared only where they hold the very same Dictionary
_SHARED_LOOKUP_MAX_CARDINALITY = 1 << 16


def _dictionary_identity(segment, column: Optional[str]):
    """What tells the dictionaries two segments hold for `column` apart, for
    sharing a predicate's resolution between them within one query."""
    if column is None or column not in segment.columns:
        return None
    d = segment.columns[column].dictionary
    if d is None:
        return None
    return d.fingerprint() if d.cardinality <= _SHARED_LOOKUP_MAX_CARDINALITY else id(d)


class _SegmentMemo:
    """The segment's half of planning, kept on the segment: its columns'
    shapes (shape.ColumnShape), its signatures, its dictionaries' identities
    and its group dimensions do not change between queries.  What CAN change
    is in `state`, and a memo whose state is not the segment's is thrown
    away: `valid_docs` appearing (upserts) and `indexes` gaining a column
    (FilterCompiler._cache_index builds text / json indexes lazily), both of
    which _segment_signature reads."""

    __slots__ = ("state", "halves", "group_dims")
    MAX_ENTRIES = 128  # query shapes a segment remembers; past it, start over

    def __init__(self, state):
        self.state = state
        # (predicate columns, needed columns, bound columns, value-hashed
        # columns, group columns,
        # the table shape asked and its version, the by-value reads:
        # _value_reads' key) ->
        # (the predicate columns' ColumnShapes, _segment_signature, the
        # predicate columns' _dictionary_identity, compiled_dict_sizes,
        # whether one of those passes the segment's own dictionary, the
        # group columns' of those sizes, value_columns, compiled_rows)
        self.halves: Dict[Tuple, Tuple] = {}
        # (GROUP BY fingerprint, null handling, the group columns' compiled
        # sizes) -> [GroupDim]: the decode's view of this segment's dictionaries
        self.group_dims: Dict[Tuple, List[GroupDim]] = {}


def _segment_memo(segment) -> _SegmentMemo:
    indexes = getattr(segment, "indexes", None)
    state = (
        segment.valid_docs is not None,
        tuple([(kind, tuple(by_col)) for kind, by_col in indexes.items()]) if indexes else (),
    )
    memo = getattr(segment, "_plan_memo", None)
    if memo is None or memo.state != state:
        memo = _SegmentMemo(state)
        try:
            segment._plan_memo = memo
        except AttributeError:  # a segment-like view without room for it: no memo
            pass
    return memo


def _prune_tree(node, at: Optional[List[int]] = None):
    """`node` (a FilterNode) as ("pred", i) | ("and" | "or" | "not",
    [children]), i the predicate's place in FilterNode.predicates()' order
    (QueryPlanning.predicates: WHERE's come first); None without a filter."""
    if node is None:
        return None
    at = [0] if at is None else at
    if node.op is FilterOp.PRED:
        at[0] += 1
        return ("pred", at[0] - 1)
    return (node.op.name.lower(), [_prune_tree(c, at) for c in node.children])


def _unsat_by_stats(p, c, segment) -> bool:
    """Whether no row of column `c` can satisfy predicate `p`, from its min
    / max (EQ / IN / RANGE: a few compares, no look-up; a table cut by time
    drops most of its segments here) and, for EQ, its bloom filter."""
    s = c.stats
    lo, hi = s.min_value, s.max_value
    pt = p.ptype
    try:
        if lo is not None and pt in (PredicateType.EQ, PredicateType.IN):
            if all(v < lo or v > hi for v in p.values):
                return True
        elif lo is not None and pt is PredicateType.RANGE:
            if p.lower is not None and (hi < p.lower or (hi == p.lower and not p.lower_inclusive)):
                return True
            if p.upper is not None and (lo > p.upper or (lo == p.upper and not p.upper_inclusive)):
                return True
    except TypeError:  # a literal of another type than the column's: the exact paths decide
        pass
    if pt is PredicateType.EQ:
        bloom = segment.indexes.get("bloom", {}).get(p.lhs.op)
        return bloom is not None and not bloom.might_contain(p.values[0])
    return False


class SegmentBounds:
    """What a query's pruner asks of a fixed list of segments, column by
    column, as arrays over the list: the columns' min / max, and which
    DISTINCT dictionary each segment holds.  They let the pruner answer for
    the whole list in a few numpy operations a predicate and one dictionary
    look-up a distinct dictionary (QueryPlanning.prune_many), not a Python
    loop a segment a predicate.  A server keeps one a table for the segment
    list its queries name (ServerInstance._bounds_of) and drops it when a
    segment joins or leaves; a column is read at its first use."""

    def __init__(self, segments: List) -> None:
        self.segments = list(segments)
        self.empty = np.asarray([seg.num_docs == 0 for seg in self.segments], bool)
        self._columns: Dict[str, Optional[Tuple[np.ndarray, np.ndarray]]] = {}
        self._dictionaries: Dict[str, Optional[Tuple]] = {}
        self._blooms: Dict[str, bool] = {}

    def of(self, column: str) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(min, max) of `column` a segment, or None where it is not numeric
        in every segment (or an empty segment has no bounds for it)."""
        if column not in self._columns:
            got = None
            try:
                stats = [seg.columns[column].stats for seg in self.segments]
                lo, hi = np.asarray([s.min_value for s in stats]), np.asarray([s.max_value for s in stats])
                if lo.dtype.kind in "iuf" and hi.dtype.kind in "iuf":
                    wide = np.float64 if "f" in (lo.dtype.kind, hi.dtype.kind) else np.int64
                    got = (lo.astype(wide), hi.astype(wide))
            except (KeyError, TypeError, ValueError, OverflowError):
                pass
            self._columns[column] = got
        return self._columns[column]

    def dictionaries(self, column: str) -> Optional[Tuple]:
        """(which distinct dictionary each segment holds for `column`, int a
        segment; the distinct dictionaries' _dictionary_identity; one
        Dictionary of each), or None where some segment has no dictionary
        for it: a table drawn by one generator has ONE, a table cut by time
        one a segment for its date attributes."""
        if column not in self._dictionaries:
            got = None
            identities = [_dictionary_identity(seg, column) for seg in self.segments]
            if identities and all(i is not None for i in identities):
                place: Dict[Any, int] = {}
                which, distinct = [], []
                for seg, identity in zip(self.segments, identities):
                    if identity not in place:
                        place[identity] = len(distinct)
                        distinct.append(seg.columns[column].dictionary)
                    which.append(place[identity])
                got = (np.asarray(which), list(place), distinct)
            self._dictionaries[column] = got
        return self._dictionaries[column]

    def all_raw(self, column: str) -> bool:
        """No segment holds a dictionary for `column` (a raw column, or one no segment has)."""
        return all(getattr(seg.columns.get(column), "dictionary", None) is None for seg in self.segments)

    def has_bloom(self, column: str) -> bool:
        if column not in self._blooms:
            self._blooms[column] = any(
                (getattr(seg, "indexes", None) or {}).get("bloom", {}).get(column) is not None for seg in self.segments
            )
        return self._blooms[column]


def _is_number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


class QueryPlanning:
    """The query's half of planning, made once a query a server
    (executor.QueryLaunches) and asked for a plan a segment.

    Everything plan_segment used to derive again for every segment from
    `ctx` alone is derived here once: the static plan check, the columns the
    query reads, the columns whose dictionaries are baked into kernels, the
    group-by columns, the predicates in compile order, and the shape
    fingerprint per distinct set of filter-column shapes.  The segment's
    half is memoised on the segment (_SegmentMemo).  A plan-cache hit then
    BINDS its parameters through the entry's ParamRecipe; an entry without
    one, or a recipe that does not fit, rebuilds through _build_plan as
    before.

    Where a segment has a star-tree that serves the query (`source`), what
    is planned is the tree's level, a table of its own, under the query
    rewritten onto the level's fields (query/startree.py StarRewrite): that
    query has a QueryPlanning of its own, made at the first such segment,
    and everything above holds for it and the levels."""

    def __init__(self, ctx: QueryContext, shape=None):
        self.ctx = ctx
        # what the table's segments on this server share (segment/table_shape.py
        # TableShape: the dictionary sizes kernels are compiled for), None
        # where the caller plans a segment on its own
        self.shape = shape
        # what the query asks of a star-tree (startree.star_need), None where
        # none may serve it; the rewritten query's planning, once a segment
        # has a tree that does; on THAT planning, the rewrite it plans
        self._star_need = star_need(ctx) if star_enabled(ctx) else None
        self._star: Optional[QueryPlanning] = None
        self.rewrite: Optional[StarRewrite] = None
        self._sources: Dict[int, Tuple] = {}  # id(segment) -> source(segment)
        self._checked = False  # check_plan_cached(ctx) has passed: at the first plan
        self.bound_cols = sketch_bound_columns(ctx) | const_bound_columns(ctx)
        self.hashed_cols = value_hashed_columns(ctx) - self.bound_cols
        self.group_cols = frozenset(c for g in ctx.group_by for c in g.columns())
        # the query's predicates in the order _build_plan compiles them:
        # WHERE's, then each aggregation's FILTER clause's
        self.predicates: List = list(ctx.filter.predicates()) if ctx.filter else []
        for spec in ctx.aggregations:
            if spec.filter is not None:
                self.predicates.extend(spec.filter.predicates())
        # the columns a segment is asked about: their shapes
        # (shape.audit_predicate, WHERE's alone; the rest ride along) and
        # their dictionaries
        self.predicate_cols = tuple(p.lhs.op if p.lhs.is_column else None for p in self.predicates)
        self._group_by = ("|".join(g.fingerprint() for g in ctx.group_by), ctx.null_handling)
        self._referenced = _referenced_columns(ctx)
        self._value_reads = _value_reads(ctx)
        # the columns those reads name, and whether one of them can be compiled
        # in the RESIDENT form on this table: value_columns' short way out
        (leaves, coded), _ = self._value_reads
        self._value_names = tuple(set(leaves) | {key[1] for key in coded})
        self._may_be_resident = False
        self._resident_under: Optional[Tuple] = None  # the table shape and version it was decided under
        self._needed: Optional[List[str]] = None  # where no segment changes it
        self._half_key: Optional[Tuple] = None
        self._half_under: Optional[Tuple] = None  # the table shape and version _half_key was made under
        self._shape_fps: Dict[Tuple, str] = {}
        self._lookups: Dict[Tuple, Tuple] = {}
        # WHERE as (op, children | the predicate's place in `predicates`), and
        # each dictionary predicate's verdict per distinct dictionary (prunes)
        self._prune_tree = _prune_tree(ctx.filter)
        self._verdicts: Dict[Tuple, bool] = {}

    def prunes(self, segment) -> bool:
        """True where `segment` provably holds no row WHERE selects, from its
        metadata alone (upstream's SegmentPrunerService: the value and bloom
        pruners), before anything is planned, staged or bound for it.  The
        filter tree is read whole: a conjunction is unsatisfiable where one
        child is, a disjunction where every child is (so Q4.2's `d_year =
        1997 OR d_year = 1998` prunes like an IN), a NOT never.  A predicate
        on a dictionary column is unsatisfiable where no entry of the
        segment's dictionary matches (filter.dict_predicate_codes: exact for
        EQ / IN / RANGE / NEQ / NOT IN / LIKE), and that verdict is resolved
        ONCE a query per distinct dictionary (_dictionary_identity): the
        resolution is the very one the plan's recipe binds with
        (`_lookups`), so a segment that survives pays for it once.  Before
        any look-up a column's min / max say no for most segments of a table
        cut by time (_unsat_by_stats: a few compares); a raw column has
        those and its bloom filter alone."""
        tree = self._prune_tree
        if tree is None:
            return False
        if segment.num_docs == 0:
            return True
        return self._unsat(tree, segment)

    def prune_many(self, segments: List, bounds: Optional[SegmentBounds] = None) -> List[bool]:
        """`prunes` of each of `segments`.  With `bounds` (the SegmentBounds
        of that very list) the filter tree is answered for the whole list at
        once (`_maybe`): a column's min / max rule segments out in a few
        numpy compares, and a dictionary predicate's verdict is looked up
        once a DISTINCT dictionary of the segments still standing and spread
        over them; only where the tree holds something the arrays cannot
        settle (a bloom filter, bounds that are not numbers) are the
        segments left asked one by one."""
        tree = self._prune_tree
        if tree is None:
            return [False] * len(segments)
        try:
            got = self._maybe(tree, bounds) if bounds is not None else None
        except OverflowError:  # a literal past int64: the per-segment path decides
            got = None
        if got is None:
            return [self.prunes(seg) for seg in segments]
        maybe, settled = got
        maybe = maybe & ~bounds.empty
        if settled:
            return (~maybe).tolist()
        return [not possible or self.prunes(seg) for possible, seg in zip(maybe.tolist(), segments)]

    def _maybe(self, node, bounds: SegmentBounds) -> Tuple[np.ndarray, bool]:
        """(bool a segment of `bounds`: False where no row of the segment can
        satisfy `node`; whether that is all `prunes` could say of `node`, so
        that a True needs no second look)."""
        op, arg = node
        n = len(bounds.segments)
        if op == "not":
            return np.ones(n, bool), True
        if op != "pred":
            parts = [self._maybe(c, bounds) for c in arg]
            fold = np.logical_and if op == "and" else np.logical_or
            return fold.reduce([m for m, _ in parts]), all(settled for _, settled in parts)
        p = self.predicates[arg]
        if not p.lhs.is_column:
            return np.ones(n, bool), True
        column, pt = p.lhs.op, p.ptype
        maybe, by_bounds = np.ones(n, bool), False
        got = bounds.of(column)
        if got is not None:
            lo, hi = got
            if pt in (PredicateType.EQ, PredicateType.IN) and all(_is_number(v) for v in p.values):
                maybe, by_bounds = np.logical_or.reduce([(lo <= v) & (v <= hi) for v in p.values]), True
            elif pt is PredicateType.RANGE and all(v is None or _is_number(v) for v in (p.lower, p.upper)):
                by_bounds = True
                if p.lower is not None:
                    maybe &= (hi >= p.lower) if p.lower_inclusive else (hi > p.lower)
                if p.upper is not None:
                    maybe &= (lo <= p.upper) if p.upper_inclusive else (lo < p.upper)
        held = bounds.dictionaries(column) if pt in _DICT_RESOLVED else None
        if held is None:
            # a raw column (or one some segment lacks): its bounds are all prunes has, but for a bloom filter
            if pt in _DICT_RESOLVED and not bounds.all_raw(column):
                return maybe, False  # some segments hold a dictionary for it, some none: each is asked
            raw = pt in (PredicateType.EQ, PredicateType.IN, PredicateType.RANGE)
            return maybe, not raw or (by_bounds and not (pt is PredicateType.EQ and bounds.has_bloom(column)))
        which, identities, dictionaries = held
        unsat = np.zeros(len(identities), bool)
        for k in np.unique(which[maybe]).tolist():  # the distinct dictionaries of the segments still standing
            unsat[k] = self._no_entry_matches(arg, identities[k], dictionaries[k])
        settled = not (pt is PredicateType.EQ and bounds.has_bloom(column))
        return maybe & ~unsat[which], settled

    def _unsat(self, node, segment) -> bool:
        op, arg = node
        if op == "and":
            return any(self._unsat(c, segment) for c in arg)
        if op == "or":
            return all(self._unsat(c, segment) for c in arg)
        if op == "not":
            return False
        p = self.predicates[arg]
        if not p.lhs.is_column:
            return False
        c = segment.columns.get(p.lhs.op)
        if c is None:
            return False
        if _unsat_by_stats(p, c, segment):
            return True
        if c.dictionary is not None and p.ptype in _DICT_RESOLVED:
            return self._no_entry_matches(arg, _dictionary_identity(segment, p.lhs.op), c.dictionary)
        return False

    def _no_entry_matches(self, at: int, identity, dictionary) -> bool:
        """Whether no entry of `dictionary` (told apart by `identity`:
        _dictionary_identity) satisfies predicate `at`: resolved once a query
        per distinct dictionary, by the resolution the recipe binds with."""
        memo = (at, identity)
        verdict = self._verdicts.get(memo)
        if verdict is None:
            codes = self._lookups.get(memo)
            if codes is None:
                codes = self._lookups[memo] = dict_predicate_codes(self.predicates[at], dictionary)
            lo, hi, table = codes
            verdict = self._verdicts[memo] = (hi <= lo) if table is None else not bool(table.any())
        return verdict

    def source(self, segment) -> Tuple[Any, "QueryPlanning"]:
        """(the table the query reads for `segment`, the planning to ask for
        its columns and its plan): the star-tree level that serves the query
        and the rewritten query's planning (its `rewrite` set), else
        (segment, self).  A table without a tree returns at once."""
        if self._star_need is None or not getattr(segment, "indexes", {}).get("startree"):
            return segment, self
        got = self._sources.get(id(segment))
        if got is None:
            pick = pick_level(self._star_need, segment)
            if pick is None:
                got = (segment, self)
            else:
                if self._star is None:
                    rewrite = StarRewrite(self.ctx)
                    self._star = QueryPlanning(rewrite.ctx, self.shape)
                    self._star.rewrite = rewrite
                name, tree, k = pick
                got = (tree.levels[k].table(segment, name), self._star)
            self._sources[id(segment)] = got
        return got

    def needed_columns(self, segment) -> List[str]:
        """_needed_columns(ctx, segment).  The segment enters it in two
        places only, a `*` (expanded to the segment's own columns) and an
        aggregation alias in ORDER BY / HAVING (a column only where the
        segment's schema has one of that name): a query with neither reads
        the same columns of every segment."""
        if self._needed is not None:
            return self._needed
        needed = _needed_columns(self.ctx, segment, self._referenced)
        cols, _, agg_aliases = self._referenced
        if "*" not in cols and not agg_aliases:
            self._needed = needed
        return needed

    def key(self, segment) -> Tuple:
        """The plan-cache key of (ctx, segment): what plan_segment has always
        keyed on, `(ctx.shape_fingerprint(column_info_from(segment)),
        _segment_signature(...), ops.scan_backend())`, each half derived
        once."""
        return self._key(segment, self.needed_columns(segment), _segment_memo(segment))[0]

    def value_columns(self, segment) -> frozenset:
        """The plan's `value_columns` for `segment` before there is a plan:
        what a staging ahead of need (the server's look-ahead) passes
        to_device / resident beside needed_columns.  Nothing is asked of the
        segment where no dictionary the query reads by value is compiled past
        the contraction's range on this table (decided once a query and
        version of the table's shape); else its memo holds the answer."""
        names = self._value_names
        if not names:
            return _NO_VALUE_COLUMNS
        shape = self.shape
        if shape is None or getattr(segment, "level_rows", None) is not None:  # its own sizes: compiled_dict_sizes
            longest = max((c.cardinality for c in map(segment.columns.get, names) if c is not None), default=0)
            may = lookup_form(longest, np.int32) == RESIDENT
        else:
            under = (id(shape), shape.version)
            if under != self._resident_under:
                self._may_be_resident = lookup_form(shape.longest(names), np.int32) == RESIDENT
                self._resident_under = under
            may = self._may_be_resident
        if not may:
            return _NO_VALUE_COLUMNS
        return self._key(segment, self.needed_columns(segment), _segment_memo(segment))[5]

    def rows(self, segment) -> int:
        """The plan's `rows` for `segment` before there is a plan: what a
        staging ahead of need passes to_device / resident."""
        return compiled_rows(segment, self.shape)

    def _key(
        self, segment, needed: List[str], memo: _SegmentMemo
    ) -> Tuple[Tuple, Tuple, Dict[str, int], bool, Tuple, frozenset, int]:
        """(the key, the predicate columns' _dictionary_identity, the
        dictionary sizes the key's kernel is compiled for, whether one of
        them passes this segment's own dictionary, the group columns' of
        those sizes, the columns staging hands out decoded: _value_columns,
        the rows the key's kernel is compiled for: compiled_rows)."""
        shape = self.shape
        under = None if shape is None else (id(shape), shape.version)
        half_key = self._half_key if needed is self._needed and under == self._half_under else None
        if half_key is None:
            half_key = (
                self.predicate_cols, tuple(needed), self.bound_cols, self.hashed_cols, self.group_cols, under,
                self._value_reads[0],
            )
            if needed is self._needed:
                self._half_key, self._half_under = half_key, under
        half = memo.halves.get(half_key)
        if half is None:
            info = column_info_from(segment)
            baked = baked_columns(segment, self.bound_cols, self.hashed_cols)
            sizes = compiled_dict_sizes(segment, needed, baked, shape)
            rows = compiled_rows(segment, shape)
            half = (
                tuple([info(c) for c in self.predicate_cols if c is not None]),
                _segment_signature(segment, needed, baked, self.group_cols, sizes, rows),
                tuple([_dictionary_identity(segment, c) for c in self.predicate_cols]),
                sizes,
                any(size > segment.column(name).cardinality for name, size in sizes.items()),
                tuple([sizes.get(c) for c in self.group_cols]),
                _value_columns(self.ctx, segment, sizes, self._value_reads),
                rows,
            )
            if len(memo.halves) >= memo.MAX_ENTRIES:
                memo.halves.clear()
            memo.halves[half_key] = half
        shapes, signature, same_dict, sizes, table_shaped, group_sizes, by_value, rows = half
        fp = self._shape_fps.get(shapes)
        if fp is None:
            fp = self._shape_fps[shapes] = self.ctx.shape_fingerprint(column_info_from(segment))
        # pallas/xla plans trace different kernels
        return (fp, signature, ops.scan_backend()), same_dict, sizes, table_shaped, group_sizes, by_value, rows

    def _bound(
        self, cached: SegmentPlan, segment, same_dict: Tuple, memo: _SegmentMemo, sizes: Dict[str, int],
        group_sizes: Tuple,
    ) -> Optional[SegmentPlan]:
        """The hit's plan by the entry's recipe: the entry itself with THIS
        query's parameters and THIS segment's group dimensions (the decode
        reads their dictionaries).  Everything else of a plan is the same for
        every (query, segment) of the key: the kernel, the layout, the
        columns, the aggregation functions, the key space."""
        params = _bind_params(cached.recipe, self.predicates, segment, same_dict, self._lookups)
        if params is None:
            return None
        # dataclasses.replace reads every field through getattr and runs
        # __init__: ~30 calls where this runs for every segment of every query
        plan = object.__new__(SegmentPlan)
        plan.__dict__.update(cached.__dict__)
        plan.params = params
        plan.scan_bytes = segment.num_docs * cached.scan_row_bytes
        ctx = self.ctx
        if ctx.group_by:
            # the strides are the compiled sizes', the values this segment's dictionaries'
            dims_key = (*self._group_by, group_sizes)
            dims = memo.group_dims.get(dims_key)
            if dims is None:
                dims = [_group_dim(g, segment, ctx.null_handling, sizes) for g in ctx.group_by]
                if len(memo.group_dims) >= memo.MAX_ENTRIES:
                    memo.group_dims.clear()
                memo.group_dims[dims_key] = dims
            plan.group_dims = dims
        return plan

    def plan(self, segment: ImmutableSegment) -> SegmentPlan:
        from pinot_tpu.analysis.compile_audit import SSE_AUDIT

        ctx = self.ctx
        if not self._checked:
            from pinot_tpu.analysis.plan_check import check_plan_cached

            # static IR validation before anything traces: malformed plans
            # raise structured PlanCheckError here instead of a tracer error
            # inside jit
            check_plan_cached(ctx)
            self._checked = True
        needed = self._needed if self._needed is not None else self.needed_columns(segment)
        memo = _segment_memo(segment)
        key, same_dict, sizes, table_shaped, group_sizes, by_value, rows = self._key(segment, needed, memo)
        cached = _PLAN_CACHE.get(key)
        if cached is not None:
            plan = (
                self._bound(cached, segment, same_dict, memo, sizes, group_sizes)
                if cached.recipe is not None else None
            )
            bind = "recipe"
            if plan is None:
                # no recipe, or one that does not fit: the parameters are
                # rebuilt and the compiled fn reused.  The structure check is
                # the safety net under the shape audit — a mismatch would
                # silently retrace, so it counts (and compiles) as a miss
                # instead.
                bind = "rebuild"
                plan = _build_plan(
                    ctx, segment.padded_to(rows), needed, compiled_fn=cached.fn, dict_sizes=sizes,
                    value_columns=by_value,
                )
                if plan.param_layout == cached.param_layout:
                    plan.scan_row_bytes = cached.scan_row_bytes
                    plan.scan_bytes = segment.num_docs * plan.scan_row_bytes
                    plan.launched_on = cached.launched_on
                    plan.widened = cached.widened
                    plan.lookups = cached.lookups
                    plan.mask_facts = cached.mask_facts
                else:
                    plan = None
            if plan is not None:
                plan.cache_key = key
                plan.cache_hit = True
                plan.bind = bind
                plan.table_shaped = table_shaped
                METRICS.counter("compile.sse.binds" if bind == "recipe" else "compile.sse.rebuilds").inc()
                SSE_AUDIT.record_hit(key[0])
                return plan
        SSE_AUDIT.record_compile(key[0])
        # a process that has an entry has both counters, moved or not
        METRICS.counter("compile.sse.binds"), METRICS.counter("compile.sse.rebuilds")
        plan = _build_plan(
            ctx, segment.padded_to(rows), needed, compiled_fn=None, dict_sizes=sizes, value_columns=by_value
        )
        plan.scan_row_bytes = scan_bytes_per_row(segment.column(n) for n in plan.needed_columns)
        plan.scan_bytes = segment.num_docs * plan.scan_row_bytes
        plan.table_shaped = table_shaped
        plan.cache_key = key
        _PLAN_CACHE.put(key, plan)
        return plan


def plan_segment(ctx: QueryContext, segment: ImmutableSegment) -> SegmentPlan:
    """The plan of one query over one segment.  A caller with many segments
    makes ONE QueryPlanning and asks it a plan a segment
    (executor.QueryLaunches): the query's half is then derived once."""
    return QueryPlanning(ctx).plan(segment)


def _build_plan(
    ctx: QueryContext,
    segment: ImmutableSegment,
    needed: List[str],
    compiled_fn: Optional[Callable],
    dict_sizes: Optional[Dict[str, int]] = None,
    value_columns: frozenset = _NO_VALUE_COLUMNS,
) -> SegmentPlan:
    """`dict_sizes` (compiled_dict_sizes): what the kernel bakes of each
    dictionary column's size, the key of the plan cache says the same; None:
    the segment's own cardinalities.  `value_columns` (_value_columns): the
    plan's, for the launch to pass to staging; the kernel reads
    whatever entry it is handed (transform.column_values)."""
    null_handling = ctx.null_handling
    if dict_sizes is None:
        dict_sizes = compiled_dict_sizes(segment, needed, frozenset(), None)
    fc = FilterCompiler(segment, null_handling, dict_sizes)
    filter_fn = fc.compile(ctx.filter)

    # Upsert validDocIds: rows replaced by a newer row elsewhere are ANDed
    # out of EVERY filter (the reference's validDocIds bitmap in
    # FilterPlanNode).  The mask ships as a param so invalidations between
    # queries apply without recompiling; presence is part of the plan-cache
    # signature (_segment_signature) since the kernel must consume it.
    if segment.valid_docs is not None:
        fc.params[VALID_KEY] = _valid_mask(segment, segment.num_docs)
        base_filter_fn = filter_fn

        def filter_fn(cols, params):
            t, nl = base_filter_fn(cols, params)
            v = params[VALID_KEY]
            return t & v, (nl & v if nl is not None else None)

    # Padded rows (a star-tree level's table, a segment's view at its table's
    # rows: ImmutableSegment.true_rows): the rows past the true count are
    # masked out of every filter like replaced rows above.  ONE rule for both.
    counted_rows = getattr(segment, "true_rows", None)
    if counted_rows is not None:
        fc.params[ROWS_KEY] = np.int32(counted_rows)
        whole_table_filter_fn = filter_fn
        star_level = getattr(segment, "level_rows", None) is not None

        def filter_fn(cols, params):
            # trace time: this plan's program masks by a bound row count, and
            # reads a star-tree level where it does (beside the backend's own
            # scan.traced.* counter)
            METRICS.counter("scan.traced.rowmasked").inc()
            if star_level:
                METRICS.counter("scan.traced.startree").inc()
            t, nl = whole_table_filter_fn(cols, params)
            v = _row_mask(segment.num_docs, params[ROWS_KEY])
            return t & v, (nl & v if nl is not None else None)

    # Device-trace names (HLO op_name metadata only; nothing computes
    # differently).  The scopes open inside the functions, which run at trace
    # time only: this builder runs for every segment of every query.
    unscoped_filter_fn = filter_fn

    def filter_fn(cols, params):
        with jax.named_scope("predicate"):
            return unscoped_filter_fn(cols, params)

    agg_specs = list(ctx.aggregations)
    aggs = bind_aggs(agg_specs, segment, ctx)

    # per-aggregation FILTER(WHERE ...) clauses
    agg_filter_fns: List[Optional[Callable]] = []
    for spec in agg_specs:
        agg_filter_fns.append(fc.compile(spec.filter) if spec.filter is not None else None)

    # theta sub-filter strings ('dim=''a''' literals) compile through the
    # same FilterCompiler; the kernel feeds one mask per sub-filter
    agg_subfilter_fns: List[Optional[List[Callable]]] = []
    for fn_ in aggs:
        if getattr(fn_, "subfilter_args", False):
            agg_subfilter_fns.append([fc.compile(node) for node in fn_.filter_nodes])
        else:
            agg_subfilter_fns.append(None)

    # Columns touched ONLY by index-resolved predicates never ship to device
    # (the index row already answered them) — the byte-savings half of the
    # BitmapBasedFilterOperator redesign.
    keep = _non_filter_columns(ctx, segment) | fc.used_columns
    needed = [c for c in needed if c in keep]

    # Bit-packed forward indexes (segment/packing.py): columns the executor
    # may ship as uint32 lane words ("codes_packed" entries).  The kernel
    # overlays a trace-time vectorized-shift unpack so every existing
    # reader sees "codes" unchanged; XLA dedups the single unpack across
    # readers and DCEs it when the Pallas path consumes the words directly.
    packed_meta: Dict[str, int] = {}
    for name in needed:
        c = segment.column(name)
        bits = getattr(c, "code_bits", None)
        if bits and getattr(c, "packed", None) is not None:
            packed_meta[name] = int(bits)
    num_docs = segment.num_docs

    def _overlay_unpacked(cols):
        from pinot_tpu.segment import packing

        out = dict(cols)
        with jax.named_scope("lane_unpack"):
            for name, bits in packed_meta.items():
                e = out.get(name)
                if e is not None and "codes_packed" in e and "codes" not in e:
                    e = dict(e)
                    e["codes"] = packing.unpack_codes_jnp(e["codes_packed"], bits, num_docs)
                    out[name] = e
        return out

    if ctx.is_aggregate and not ctx.group_by:
        kind = "aggregation"
        group_dims: List[GroupDim] = []
        num_groups = 0
    elif ctx.group_by:
        group_dims = [_group_dim(g, segment, null_handling, dict_sizes) for g in ctx.group_by]
        num_groups = 1
        for gd in group_dims:
            num_groups *= max(1, gd.cardinality)
        kind = "groupby_dense" if num_groups <= ctx.max_dense_groups else "groupby_sparse"
    else:
        kind = "selection"
        group_dims = []
        num_groups = 0

    guard_sparse_vector_fields(kind, aggs)

    def _agg_inputs(cols, params, base_mask):
        """Per-aggregation (values, mask) with null + FILTER handling."""
        with jax.named_scope("value_transform"):
            out = []
            for spec, fn, ffn, sfns in zip(agg_specs, aggs, agg_filter_fns, agg_subfilter_fns):
                mask = base_mask
                if ffn is not None:
                    ft, _ = ffn(cols, params)
                    mask = mask & ft
                if getattr(fn, "mv_input", False):
                    out.append(mv_agg_input(spec, fn, segment, cols, mask))
                    continue
                if spec.expr is None:
                    vals = mask  # COUNT(*): values unused
                elif fn.needs_codes:
                    vals, mask = agg_input_codes(spec, fn, segment, cols, mask, null_handling)
                elif fn.name == "count" and spec.expr.is_column:
                    # COUNT(col) needs only the null mask — works on strings too.
                    vals = mask
                    c = segment.column(spec.expr.op)
                    if c.nulls is not None and null_handling:
                        mask = mask & ~cols[spec.expr.op]["nulls"]
                else:
                    vals, nulls = eval_expr(spec.expr, segment, cols)
                    vals = as_row_array(vals, mask.shape)
                    if nulls is not None and null_handling:
                        mask = mask & ~nulls
                if fn.needs_extra_exprs:
                    extras = []
                    for ex in spec.extra_exprs:
                        ev, en = eval_expr(ex, segment, cols)
                        extras.append(as_row_array(ev, mask.shape))
                        if en is not None and null_handling:
                            mask = mask & ~en
                    vals = (vals, *extras)
                if sfns:
                    vals = (vals, *[mask & sf(cols, params)[0] for sf in sfns])
                out.append((vals, mask))
            return out

    def _group_key(cols, params):
        if len(group_dims) == 1 and group_dims[0].kind == "dict":
            # storage-dtype passthrough: the group kernels cast per chunk
            return cols[group_dims[0].name]["codes"]
        key = None
        with jax.named_scope("group_key"):
            for gd in group_dims:
                code = gd.device_code(cols, segment, jnp.int32)
                key = code if key is None else key * np.int32(gd.cardinality) + code
        return key

    def _key_packed(cols):
        """(words, code_bits) when the single dict group key shipped packed
        AND the Pallas backend can lane-unpack it in-register; else None."""
        if scan_be not in ("pallas", "interpret") or len(group_dims) != 1:
            return None
        gd = group_dims[0]
        if gd.kind != "dict" or gd.mv:
            return None
        bits = packed_meta.get(gd.name)
        if not bits:
            return None
        e = cols.get(gd.name)
        if e is None or "codes_packed" not in e:
            return None
        return (e["codes_packed"], bits)

    if kind == "aggregation":

        def kernel(cols, params):
            tmask, _ = filter_fn(cols, params)
            inputs = _agg_inputs(cols, params, tmask)
            with jax.named_scope("aggregate"):
                return [fn.partial(vals, mask) for fn, (vals, mask) in zip(aggs, inputs)]

    mv_dims = [i for i, gd in enumerate(group_dims) if gd.mv]
    if len(mv_dims) > 1:
        raise NotImplementedError("at most one multi-value GROUP BY dimension (explode) per query")
    if mv_dims and any(
        getattr(fn_, "mv_input", False) or getattr(fn_, "needs_extra_exprs", False) for fn_ in aggs
    ):
        raise NotImplementedError(
            "MV/tuple-input aggregations (SUMMV..., FIRST/LASTWITHTIME) cannot combine "
            "with an MV GROUP BY dimension"
        )
    mv_i = mv_dims[0] if mv_dims else None

    def _mv_explode(cols, params, tmask, key_dtype):
        """MV group-by explode: [n] -> flattened [n*max_len] key/mask/inputs
        (each element of the MV dimension contributes one logical row —
        Pinot's MV group-by semantics)."""
        gd_mv = group_dims[mv_i]
        entry = cols[gd_mv.name]
        codes2 = entry["codes"].astype(jnp.int32)
        pad = jnp.arange(codes2.shape[1], dtype=jnp.int32)[None, :] < entry["lengths"][:, None].astype(jnp.int32)
        t2 = tmask[:, None] & pad
        shape2 = t2.shape
        key = None
        for i2, gd in enumerate(group_dims):
            if i2 == mv_i:
                code = jnp.minimum(codes2, np.asarray(gd.cardinality - 1, dtype=key_dtype)).astype(key_dtype)
            else:
                code = jnp.broadcast_to(
                    gd.device_code(cols, segment, key_dtype)[:, None], shape2
                )
            key = code if key is None else key * np.asarray(gd.cardinality, dtype=key_dtype) + code
        inputs = _agg_inputs(cols, params, tmask)
        flat_inputs = [
            (
                jnp.broadcast_to(jnp.broadcast_to(v, tmask.shape)[:, None], shape2).reshape(-1),
                (m[:, None] & t2).reshape(-1),
            )
            for v, m in inputs
        ]
        return key.reshape(-1), t2.reshape(-1), flat_inputs

    scan_be = ops.scan_backend()  # plan-time backend decision (cache-keyed)

    if kind == "groupby_dense" and mv_i is not None:
        vranges = agg_vranges(agg_specs, segment)

        def kernel(cols, params):
            tmask, _ = filter_fn(cols, params)
            key, t_f, inputs = _mv_explode(cols, params, tmask, jnp.int32)
            return grouped_partials(aggs, inputs, t_f, key, num_groups, vranges,
                                    backend=scan_be)

    elif kind == "groupby_dense":
        vranges = agg_vranges(agg_specs, segment)

        def kernel(cols, params):
            tmask, _ = filter_fn(cols, params)
            key = _group_key(cols, params)
            inputs = _agg_inputs(cols, params, tmask)
            return grouped_partials(aggs, inputs, tmask, key, num_groups, vranges,
                                    backend=scan_be, key_packed=_key_packed(cols))

    elif kind == "groupby_sparse":
        # Device-side sort+scatter into fixed [numGroupsLimit] tables — no
        # row-length arrays ever leave the device (sparse_grouped_tables).
        if num_groups >= (1 << 62):
            raise NotImplementedError("composite group key exceeds 62 bits")
        num_slots = min(ctx.num_groups_limit, num_groups)
        order_spec = kernel_order_spec(ctx, aggs)
        vranges = agg_vranges(agg_specs, segment)

        if mv_i is not None:

            def kernel(cols, params):
                tmask, _ = filter_fn(cols, params)
                key, t_f, inputs = _mv_explode(cols, params, tmask, jnp.int64)
                return sparse_grouped_tables(aggs, inputs, t_f, key, num_slots, order_spec,
                                             num_groups=num_groups, vranges=vranges)

        else:

            def kernel(cols, params):
                tmask, _ = filter_fn(cols, params)
                key = packed_key64(cols, group_dims, segment)
                inputs = _agg_inputs(cols, params, tmask)
                return sparse_grouped_tables(aggs, inputs, tmask, key, num_slots, order_spec,
                                             num_groups=num_groups, vranges=vranges)

    elif kind == "selection":

        def kernel(cols, params):
            tmask, _ = filter_fn(cols, params)
            return tmask

    if packed_meta:
        base_kernel = kernel

        def kernel(cols, params):
            return base_kernel(_overlay_unpacked(cols), params)

    # the plan.fn boundary: the call carries the packed buffers, the program
    # slices them back into the dict the kernel reads
    param_layout = params_structure(fc.params)
    dict_kernel = kernel

    lookups: Dict[str, int] = {}
    # A mask that holds more than the padding: a WHERE, an aggregation's
    # FILTER, replaced rows.  Without one a row-priced scatter keeps every row
    # and compiles no compaction (ops/segmented.py); the sparse plan's slot
    # tables are read off its own sort and are not asked.
    mask_facts = ops.MaskFacts(
        kind in ("aggregation", "groupby_dense")
        and (ctx.filter is not None or segment.valid_docs is not None or any(spec.filter is not None for spec in agg_specs))
    )

    def kernel(cols, packed):
        # trace time: the table-by-code lookups of this program, by the form
        # each was compiled with (ops/code_lookup.py), and the compactions of
        # its row-priced scatters
        with lookup_tally() as seen, ops.mask_facts(mask_facts.filtered) as traced:
            out = dict_kernel(cols, unpack_params(packed, param_layout))
        if not lookups:  # the first trace's, the served launch's: a later trace over another staging's pytree leaves it
            lookups.update(seen)
            mask_facts.compactions = traced.compactions
        return out

    # the jitted program is named by what it is (module `jit_<kind>_<backend>`
    # in a device trace), not `kernel`
    kernel.__name__ = kernel.__qualname__ = f"{kind}_{scan_be}"
    fn = compiled_fn if compiled_fn is not None else jax.jit(kernel)

    select_columns = []
    select_exprs: List[Any] = []
    if kind == "selection":
        from pinot_tpu.query.ir import WindowSpec

        for s in ctx.select_list:
            if isinstance(s, WindowSpec):
                select_exprs.append(s)  # computed at reduce over merged rows
                continue
            if not isinstance(s, Expr):
                raise NotImplementedError(f"unsupported selection item {s}")
            if s.is_column and s.op == "*":
                select_exprs.extend(Expr.col(n) for n in _all_column_names(segment))
            else:
                select_exprs.append(s)
        select_columns = [e.op for e in select_exprs if isinstance(e, Expr) and e.is_column]
    elif ctx.windows:
        raise NotImplementedError("window functions apply to selection queries only")

    return SegmentPlan(
        kind=kind,
        fn=fn,
        params=pack_params(fc.params, param_layout),
        needed_columns=needed,
        param_layout=param_layout,
        aggs=aggs,
        group_dims=group_dims,
        num_groups=num_groups,
        select_columns=select_columns,
        select_exprs=select_exprs,
        index_uses=list(fc.index_uses),
        index_scans=list(fc.index_scans),
        # an entry's alone: a rebuilt hit has the entry's.  A theta
        # sub-filter's predicates come out of a function's arguments, not
        # the query's filter trees: no recipe walks them
        recipe=(
            _param_recipe(fc.binders, param_layout)
            if compiled_fn is None and not any(agg_subfilter_fns)
            else None
        ),
        dict_sizes={name: dict_sizes[name] for name in needed if name in dict_sizes},
        lookups=lookups,
        mask_facts=mask_facts,
        value_columns=value_columns,
        rows=segment.num_docs,
    )
