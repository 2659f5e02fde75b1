"""Filter compilation: predicate tree -> device mask computation.

Reference parity: pinot-core's filter operators + predicate evaluators
(BaseFilterOperator subclasses, .../operator/filter/; dictionary-based
evaluators in .../operator/filter/predicate/).  The key Pinot trick is kept
and tensorized:

  * Dictionary-based evaluation: predicates on dict-encoded columns are
    resolved AGAINST THE SORTED DICTIONARY host-side, then evaluated on the
    code array on device as either
      - a closed-form code-range compare (EQ/RANGE -> lo <= code < hi), or
      - a boolean lookup table over the dictionary space, gathered by code
        (IN/NOT_IN/REGEXP/LIKE -> table[codes]); O(rows) regardless of the
        predicate's value-set size, and it makes regex a device-side tensor
        op because the regex only ever ran over the dictionary.
  * Raw columns use direct vectorized value compares (ScanBasedFilterOperator
    analog — except a TPU scan IS the vector unit's native mode).
  * AND/OR/NOT are mask algebra with SQL three-valued-logic null tracking:
    each node yields (true_mask, null_mask); rows are selected iff truly true.

Per-segment dictionaries mean per-segment constants: the jitted kernel takes
them via a params pytree so equal-shaped segments share one compiled kernel.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from pinot_tpu.ops.code_lookup import code_lookup
from pinot_tpu.query.ir import FilterNode, FilterOp, Predicate, PredicateType
from pinot_tpu.query.transform import eval_expr, _or_masks
from pinot_tpu.segment.segment import ImmutableSegment

# (true_mask, null_mask|None)
MaskPair = Tuple[jnp.ndarray, Optional[jnp.ndarray]]
Params = Dict[str, np.ndarray]


def _match_values(p, values: np.ndarray) -> np.ndarray:
    """Evaluate a predicate over a (derived) value array -> bool table.
    Used by derived-string predicates, where codes are NOT sort ranks of the
    derived values, so everything is a table lookup (no code ranges)."""
    pt = p.ptype
    if pt is PredicateType.EQ:
        return np.array([v == p.values[0] for v in values], dtype=bool)
    if pt is PredicateType.NEQ:
        return np.array([v != p.values[0] for v in values], dtype=bool)
    if pt in (PredicateType.IN, PredicateType.NOT_IN):
        s = set(p.values)
        t = np.array([v in s for v in values], dtype=bool)
        return ~t if pt is PredicateType.NOT_IN else t
    if pt is PredicateType.RANGE:
        t = np.ones(len(values), dtype=bool)
        if p.lower is not None:
            t &= np.array(
                [(v >= p.lower if p.lower_inclusive else v > p.lower) for v in values], dtype=bool
            )
        if p.upper is not None:
            t &= np.array(
                [(v <= p.upper if p.upper_inclusive else v < p.upper) for v in values], dtype=bool
            )
        return t
    if pt in (PredicateType.REGEXP_LIKE, PredicateType.LIKE):
        pat = p.values[0]
        rx = re.compile(pat if pt is PredicateType.REGEXP_LIKE else like_to_regex(pat))
        return np.array([rx.search(str(v)) is not None for v in values], dtype=bool)
    raise ValueError(f"predicate {pt} not supported on derived string values")


def like_to_regex(pattern: str) -> str:
    """SQL LIKE -> anchored regex (Pinot LikeToRegexpLikePatternConverter)."""
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return "^" + "".join(out) + "$"


def dict_predicate_codes(p: Predicate, d) -> Tuple[Optional[int], Optional[int], Optional[np.ndarray]]:
    """A predicate's literals resolved against ONE sorted dictionary `d`:
    `(lo_code, hi_code, None)` for EQ / RANGE (the codes `lo <= code < hi`
    match), `(None, None, table)` for NEQ / IN / NOT_IN / REGEXP_LIKE / LIKE
    (a bool per dictionary entry).  A pure function of (dictionary,
    literals): the compiler calls it when a plan is built, and a plan-cache
    hit calls it again to bind the same predicate's parameters for another
    segment or other literals (planner.ParamRecipe), so the two cannot
    differ.  Raises ValueError for a predicate kind it does not resolve
    (TEXT_MATCH / JSON_MATCH read an index, not the dictionary alone)."""
    pt = p.ptype
    if pt is PredicateType.EQ:
        i = d.index_of(p.values[0])
        return (i, i + 1, None) if i >= 0 else (0, 0, None)
    if pt is PredicateType.RANGE:
        values = d.values
        lo_code, hi_code = 0, d.cardinality
        # raw literals into searchsorted: numpy's cross-dtype compare keeps
        # 2.5 between 2 and 3 on an INT dictionary (no truncation).
        if p.lower is not None:
            lo_code = int(np.searchsorted(values, p.lower, side="left" if p.lower_inclusive else "right"))
        if p.upper is not None:
            hi_code = int(np.searchsorted(values, p.upper, side="right" if p.upper_inclusive else "left"))
        return lo_code, hi_code, None
    card = d.cardinality
    if pt is PredicateType.NEQ:
        i = d.index_of(p.values[0])
        table = np.ones(card, dtype=bool)
        if i >= 0:
            table[i] = False
        return None, None, table
    if pt in (PredicateType.IN, PredicateType.NOT_IN):
        table = np.zeros(card, dtype=bool)
        for v in p.values:
            i = d.index_of(v)
            if i >= 0:
                table[i] = True
        return None, None, (~table if pt is PredicateType.NOT_IN else table)
    if pt in (PredicateType.REGEXP_LIKE, PredicateType.LIKE):
        pat = p.values[0]
        rx = re.compile(pat if pt is PredicateType.REGEXP_LIKE else like_to_regex(pat))
        # regex over the dictionary, not the rows — card evaluations total.
        table = np.fromiter((rx.search(str(v)) is not None for v in d.values), dtype=bool, count=card)
        return None, None, table
    raise ValueError(f"predicate {pt} is not resolved by a dictionary alone")


# the predicate kinds dict_predicate_codes resolves
_DICT_RESOLVED = frozenset({
    PredicateType.EQ, PredicateType.NEQ, PredicateType.RANGE, PredicateType.IN,
    PredicateType.NOT_IN, PredicateType.REGEXP_LIKE, PredicateType.LIKE,
})

# IN/NOT_IN/regex tables resolve through the inverted index only up to this
# many bitmap-row ORs (past it a code scan reads less)
_INV_MAX_ROWS = 256

# What each side of the index decision moves, bytes a second: the chip reads
# its own memory (a v5e's HBM, benchmarks/peaks.json), a launch's host
# operands cross the host link
_HBM_BYTES_PER_S = 819e9
_HOST_LINK_BYTES_PER_S = 16e9


def bitmap_serves(table_like, col) -> bool:
    """Whether a predicate on dictionary column `col` of `table_like` is
    answered from the column's inverted / range index (doc bitmaps ORed on
    the host, rows / 8 bytes shipped with the launch) rather than by a scan
    of its codes: the planner's decision, from what each costs, for a
    segment whose columns are resident on the device.  The scan reads
    rows x code_bits / 8 bytes of HBM; the bitmap, at its best (ONE row, no
    OR), crosses the host link with rows / 8 bytes, a launch a segment a
    query.  So the index wins only where code_bits passes the ratio of the
    two speeds (~51): never for a dictionary column, whose codes ride in
    4 to 32 bits.  A bitmap RESIDENT on the device and gathered there would
    win by bytes where fewer rows are ORed than the lane has bits, by at most
    (code_bits - 1) / 8 bytes a row: 1.6 us of a 1.5M-row segment's 8-bit
    column, under a thousandth of the launch that carries it, for 19 MB of
    HBM a segment; it is not built (PERF.md, PR 47).  The index is still
    built, stored and reported; `index_scans` says which predicates had one
    and scanned.  The decision hangs on the segment's shape alone (rows, lane
    width), not on a literal, so a predicate on an indexed column is a
    parameter slot of the shape fingerprint (query/shape.py) and binds on a
    plan-cache hit.  The stacked engines' tables (parallel/, mse/: sharded
    code matrices whose index words the engine slices per launch) are not
    costed here and keep the index path."""
    if not isinstance(table_like, ImmutableSegment):
        return True
    codes = getattr(col, "codes", None)
    bits = getattr(col, "code_bits", None) or (codes.dtype.itemsize * 8 if codes is not None else 32)
    rows = table_like.num_docs
    return rows / 8 / _HOST_LINK_BYTES_PER_S < rows * bits / 8 / _HBM_BYTES_PER_S


def sorted_doc_range(col, lo_code: int, hi_code: int) -> Tuple[int, int]:
    """The docs [d0, d1) of a SORTED column that hold codes [lo_code,
    hi_code): two reads of the column's dictId -> first-doc table
    (ColumnData.first_docs), whatever the segment's rows."""
    first = col.first_docs()
    d0 = int(first[lo_code])
    return d0, (int(first[hi_code]) if hi_code > lo_code else d0)


class FilterCompiler:
    """Compiles one filter tree against one segment.

    Produces (a) a params dict of per-segment device constants and (b) an
    eval closure usable inside jit.  Param keys follow traversal order, so
    segments with the same query shape produce structurally identical params
    pytrees -> one jit cache entry per (query, segment-signature).

    Index acceleration (round 2 — BitmapBasedFilterOperator /
    SortedIndexBasedFilterOperator analogs,
    pinot-core/.../operator/filter/BitmapBasedFilterOperator.java:29):
      * sorted column + code-range predicate -> contiguous doc range, two
        int params, ZERO row reads on device;
      * range index + code-range predicate -> prefix[hi] & ~prefix[lo]
        resolved host-side from the mmap'd index (n/8 bytes), shipped as a
        packed-words param and bit-unpacked on device;
      * inverted index + small dictId set -> OR of bitmap rows, same.
    The device never rescans the code array for such predicates, and if a
    column is touched ONLY by index-resolved predicates its codes are never
    shipped to HBM at all (planner prunes via `used_columns`).
    `index_uses` records (column, kind) per accelerated predicate for
    ExecutionStats."""

    def __init__(
        self, segment: ImmutableSegment, null_handling: bool = True,
        dict_sizes: Optional[Dict[str, int]] = None,
    ):
        self.segment = segment
        self.null_handling = null_handling
        # {dictionary column: the dictionary size the kernel is compiled for}
        # (planner.compiled_dict_sizes): a code table has that many slots,
        # this segment's dictionary filling its head; None: the segment's own
        self.dict_sizes = dict_sizes or {}
        self.params: Params = {}
        self._counter = 0
        # columns whose device entries the compiled closures will read
        self.used_columns = set()
        # (column, "sorted"|"range"|"inverted") per index-accelerated predicate
        self.index_uses: List[Tuple[str, str]] = []
        # (column, "range"|"inverted") per predicate whose column has such an
        # index and whose codes are scanned all the same (bitmap_serves)
        self.index_scans: List[Tuple[str, str]] = []
        # Sharded compilation target (_ShardView): (axis_name, ndev,
        # local_rows) — bitmap params split on the leading device axis and
        # doc ranges compare against GLOBAL flat doc ids (parallel/engine.py)
        self.shard_info: Optional[Tuple[str, int, int]] = getattr(segment, "shard_info", None)
        # Macro-batch launches (parallel/engine.py): per-device global doc
        # ids come from a params-dependent closure (the batch offset is a
        # param), and bitmap words are stored FULL as [ndev, L, D//32] so
        # the engine can slice the doc axis per launch.
        self.docs_fn = getattr(segment, "docs_fn", None)
        self.bitmap_layout: Optional[Tuple[int, int, int]] = getattr(segment, "bitmap_layout", None)
        # param keys whose leading axis is the device axis (in_spec P(axis))
        self.row_sharded_params: set = set()
        # bitmap param keys that are PLAIN (not negated, no null guard) —
        # candidates for staying packed through a fused Pallas scan
        self._plain_bitmaps: set = set()
        # set when the ROOT filter is exactly one plain bitmap predicate:
        # the engine can then skip the unpack entirely and hand the packed
        # words to the fused scan (pallas_scan word-slicing)
        self.sole_bitmap_param: Optional[str] = None
        self._root_compiled = False
        # How each compiled predicate's parameters are made, in compile
        # order: (kind, ptype, column, multi-value, the param keys it
        # wrote), kind "none" (no parameter), "range" (lo, hi codes),
        # "table" (a bool per dictionary entry), both dict_predicate_codes
        # of (the segment's dictionary, the literals), or "docrange" (a
        # sorted column's [d0, d1): sorted_doc_range of those codes).  None once a
        # predicate was compiled whose parameters are not such a pure
        # function, or whose path an index may choose differently for
        # another segment or literal: a plan-cache hit then rebuilds
        # (planner.ParamRecipe).
        self.binders: Optional[List[Tuple]] = []
        self._bindable: Optional[Tuple] = None

    def _key(self, suffix: str) -> str:
        k = f"f{self._counter}.{suffix}"
        self._counter += 1
        return k

    def _col_index(self, kind: str, name: str):
        idx = getattr(self.segment, "indexes", None)
        if not idx:
            return None
        return idx.get(kind, {}).get(name)

    def _cache_index(self, kind: str, name: str, idx) -> None:
        """Cache a lazily-built (text/json) index on the segment so repeated
        queries pay the cardinality-sized build once."""
        store = getattr(self.segment, "indexes", None)
        if isinstance(store, dict):
            store.setdefault(kind, {})[name] = idx

    # ------------------------------------------------------------------
    def compile(self, node: Optional[FilterNode]) -> Callable[[Dict, Dict], MaskPair]:
        is_root = not self._root_compiled
        self._root_compiled = True
        if node is None:
            n = self.segment.num_docs

            def match_all(cols, params):
                return jnp.ones((n,), dtype=bool), None

            return match_all
        before_keys = set(self.params)
        fn = self._compile_node(node)
        if is_root and node.op is FilterOp.PRED:
            new_keys = set(self.params) - before_keys
            if len(new_keys) == 1 and next(iter(new_keys)) in self._plain_bitmaps:
                self.sole_bitmap_param = next(iter(new_keys))
        return fn

    def _compile_node(self, node: FilterNode) -> Callable[[Dict, Dict], MaskPair]:
        if node.op is FilterOp.PRED:
            return self._compile_predicate(node.predicate)
        children = [self._compile_node(c) for c in node.children]
        if node.op is FilterOp.AND:

            def eval_and(cols, params):
                t, nl = children[0](cols, params)
                for c in children[1:]:
                    t2, n2 = c(cols, params)
                    # null = at least one null, no false (3VL)
                    if nl is None and n2 is None:
                        t = t & t2
                        continue
                    f1 = ~t & (jnp.zeros_like(t) if nl is None else ~nl)
                    f2 = ~t2 & (jnp.zeros_like(t2) if n2 is None else ~n2)
                    nl = (_or_masks(nl, n2)) & ~f1 & ~f2
                    t = t & t2
                return t, nl

            return eval_and
        if node.op is FilterOp.OR:

            def eval_or(cols, params):
                t, nl = children[0](cols, params)
                for c in children[1:]:
                    t2, n2 = c(cols, params)
                    t = t | t2
                    nl = _or_masks(nl, n2)
                if nl is not None:
                    nl = nl & ~t
                return t, nl

            return eval_or
        if node.op is FilterOp.NOT:

            def eval_not(cols, params):
                t, nl = children[0](cols, params)
                if nl is None:
                    return ~t, None
                return ~t & ~nl, nl

            return eval_not
        raise ValueError(f"unknown filter op {node.op}")

    # ------------------------------------------------------------------
    def _compile_predicate(self, p: Predicate) -> Callable[[Dict, Dict], MaskPair]:
        first_key = len(self.params)
        self._bindable = None  # the branch that can be bound says how
        fn = self._compile_one(p)
        if self.binders is not None:
            if self._bindable is None:
                self.binders = None
            else:
                self.binders.append((*self._bindable, tuple(list(self.params)[first_key:])))
        return fn

    def _compile_one(self, p: Predicate) -> Callable[[Dict, Dict], MaskPair]:
        seg = self.segment
        # IS_NULL / IS_NOT_NULL act on the column's null vector directly.
        if p.ptype in (PredicateType.IS_NULL, PredicateType.IS_NOT_NULL):
            if not p.lhs.is_column:
                raise ValueError("IS [NOT] NULL requires a bare column")
            col = seg.column(p.lhs.op)
            want_null = p.ptype is PredicateType.IS_NULL
            has_nulls = col.nulls is not None and self.null_handling
            if has_nulls:
                self.used_columns.add(p.lhs.op)
            n = seg.num_docs
            self._bindable = ("none", p.ptype, p.lhs.op, False)

            def eval_null(cols, params, _want=want_null, _has=has_nulls, _name=p.lhs.op):
                if not _has:
                    return (jnp.zeros((n,), bool) if _want else jnp.ones((n,), bool)), None
                nulls = cols[_name]["nulls"]
                return (nulls if _want else ~nulls), None

            return eval_null

        if p.ptype is PredicateType.VECTOR_SIMILARITY:
            return self._compile_vector_predicate(p)
        if p.lhs.is_column and seg.column(p.lhs.op).has_dictionary:
            return self._compile_dict_predicate(p)
        from pinot_tpu.query import scalar

        if (
            scalar.is_dict_fn_expr(p.lhs)
            and scalar.string_result(p.lhs)
        ):
            return self._compile_derived_string_predicate(p)
        return self._compile_value_predicate(p)

    def _compile_vector_predicate(self, p: Predicate) -> Callable[[Dict, Dict], MaskPair]:
        """VECTOR_SIMILARITY(col, queryVec, topK): one MXU matvec over the
        HBM-resident embedding matrix + lax.top_k — exact cosine top-k (the
        reference's HNSW is approximate; brute-force is the TPU-idiomatic
        trade, indexes/vector.py).  Ties at the kth score may admit extras."""
        import jax

        from pinot_tpu.indexes.vector import parse_query_vector

        if not p.lhs.is_column:
            raise ValueError("VECTOR_SIMILARITY requires a bare vector column")
        name = p.lhs.op
        vidx = self._col_index("vector", name)
        if vidx is None:
            raise ValueError(
                f"VECTOR_SIMILARITY requires a vector index on {name} (tableIndexConfig.vectorIndexColumns)"
            )
        q = vidx.normalize_query(parse_query_vector(p.values[0]))
        k = int(p.values[1]) if len(p.values) > 1 else 10
        key = self._key("qvec")
        self.params[key] = q
        self.used_columns.add(name)
        self.index_uses.append((name, "vector"))
        dim = vidx.dim

        def eval_vec(cols, params, _key=key, _name=name, _k=k, _dim=dim):
            m = cols[_name]["values"][:, :_dim].astype(jnp.float32)
            norms = jnp.sqrt(jnp.sum(m * m, axis=1))
            scores = (m @ params[_key]) / jnp.where(norms == 0, 1.0, norms)
            scores = jnp.where(norms == 0, -jnp.inf, scores)
            kk = min(_k, scores.shape[0])
            thresh = jax.lax.top_k(scores, kk)[0][-1]
            return scores >= thresh, None

        return eval_vec

    def _compile_derived_string_predicate(self, p: Predicate) -> Callable[[Dict, Dict], MaskPair]:
        """Predicate over a string function of a dict column — e.g.
        WHERE UPPER(city) = 'SF'.  The function evaluates over the
        DICTIONARY'S VALUES (cardinality work, host-side), the predicate over
        the derived values yields a code table, and the device work is the
        same table[codes] lookup as any dictionary predicate."""
        from pinot_tpu.query import scalar

        name = next(a for a in p.lhs.args if not a.is_literal).op
        col = self.segment.column(name)
        if not col.has_dictionary:
            raise ValueError(f"{p.lhs.op} predicate requires dictionary column, {name} is raw")
        derived = scalar.derived_for(p.lhs, col.dictionary)
        table = _match_values(p, derived)
        has_nulls = col.nulls is not None and self.null_handling
        key = self._key("dtable")
        self.params[key] = table
        self.used_columns.add(name)

        def eval_table(cols, params, _key=key, _name=name, _has=has_nulls):
            codes = cols[_name]["codes"].astype(jnp.int32)
            t = code_lookup(params[_key], codes)
            nulls = cols[_name].get("nulls") if _has else None
            if nulls is not None:
                t = t & ~nulls
            return t, nulls

        return eval_table

    # -- dictionary-based ------------------------------------------------
    def _compile_dict_predicate(self, p: Predicate) -> Callable[[Dict, Dict], MaskPair]:
        name = p.lhs.op
        col = self.segment.column(name)
        d = col.dictionary
        values = d.values
        pt = p.ptype
        # Multi-value columns: predicates match a row when ANY element
        # matches (the reference's per-value MV predicate semantics).  The
        # padded code matrix evaluates elementwise, then any(axis=1); the
        # padding code (== cardinality) must stay no-match, so code tables
        # get an explicit False pad slot — including after NEQ/NOT_IN
        # negation — and code ranges can never reach it (hi <= card).
        is_mv = getattr(col, "is_multi_value", False)

        lo_code = hi_code = None
        table: Optional[np.ndarray] = None

        if pt in _DICT_RESOLVED:
            lo_code, hi_code, table = dict_predicate_codes(p, d)
        elif pt is PredicateType.TEXT_MATCH:
            from pinot_tpu.indexes.text import TextIndex

            idx = self._col_index("text", name)
            if idx is None:
                idx = TextIndex.build(values)  # lazy: cardinality work, cached below
                self._cache_index("text", name, idx)
            else:
                self.index_uses.append((name, "text"))
            table = idx.match(str(p.values[0]))
        elif pt is PredicateType.JSON_MATCH:
            from pinot_tpu.indexes.jsonidx import JsonIndex

            idx = self._col_index("json", name)
            if idx is None:
                idx = JsonIndex.build(values)
                self._cache_index("json", name, idx)
            else:
                self.index_uses.append((name, "json"))
            table = idx.match(str(p.values[0]))
        else:
            raise ValueError(f"predicate {pt} not supported on dictionary column {name}")

        has_nulls = col.nulls is not None and self.null_handling

        # -- index-accelerated paths (no code scan) ----------------------
        if not is_mv:
            accel = self._try_index_paths(name, col, lo_code, hi_code, table, has_nulls, pt)
            if accel is not None:
                return accel

        # What follows scans the codes with parameters that are
        # dict_predicate_codes of this segment's dictionary.  Where the
        # column's inverted index may serve (bitmap_serves: a stacked
        # engine's table) the choice between bitmap and scan hangs on how
        # many codes the literals select in THIS dictionary, so another
        # segment of the same signature may take the other path.
        if pt in _DICT_RESOLVED and (
            is_mv or self._col_index("inverted", name) is None or not bitmap_serves(self.segment, col)
        ):
            self._bindable = ("range" if table is None else "table", pt, name, is_mv)

        if table is not None:
            tail = self.dict_sizes.get(name, 0) - len(table)
            if is_mv:
                table = np.append(table, False)  # padding code slot
            elif tail > 0:  # the table's bound: no code of this segment reaches the tail
                table = np.append(table, np.zeros(tail, bool))
            key = self._key("table")
            self.params[key] = table
            self.used_columns.add(name)

            def eval_table(cols, params, _key=key, _name=name, _has=has_nulls):
                codes = cols[_name]["codes"].astype(jnp.int32)
                t = code_lookup(params[_key], codes)
                if t.ndim == 2:
                    t = jnp.any(t, axis=1)
                nulls = cols[_name].get("nulls") if _has else None
                if nulls is not None:
                    t = t & ~nulls
                return t, nulls

            return eval_table

        lo_key = self._key("lo")
        hi_key = self._key("hi")
        self.params[lo_key] = np.int32(lo_code)
        self.params[hi_key] = np.int32(hi_code)
        self.used_columns.add(name)

        def eval_range(cols, params, _lo=lo_key, _hi=hi_key, _name=name, _has=has_nulls):
            codes = cols[_name]["codes"].astype(jnp.int32)
            t = (codes >= params[_lo]) & (codes < params[_hi])
            if t.ndim == 2:
                t = jnp.any(t, axis=1)
            nulls = cols[_name].get("nulls") if _has else None
            if nulls is not None:
                t = t & ~nulls
            return t, nulls

        return eval_range

    # -- index-accelerated predicate compilation -------------------------
    def _null_guard(self, name: str, has_nulls: bool):
        if has_nulls:
            self.used_columns.add(name)

    def _emit_doc_range(self, name: str, d0: int, d1: int, has_nulls: bool):
        n = self.segment.num_docs
        lo_key = self._key("d0")
        hi_key = self._key("d1")
        self.params[lo_key] = np.int32(d0)
        self.params[hi_key] = np.int32(d1)
        self._null_guard(name, has_nulls)
        self.index_uses.append((name, "sorted"))
        shard_info = self.shard_info
        docs_fn = self.docs_fn

        def eval_docrange(cols, params, _lo=lo_key, _hi=hi_key, _name=name, _has=has_nulls):
            if docs_fn is not None:
                docs = docs_fn(params)
            elif shard_info is not None:
                axis, _, local_rows = shard_info
                from jax import lax

                base = lax.axis_index(axis).astype(jnp.int32) * jnp.int32(local_rows)
                docs = base + jnp.arange(local_rows, dtype=jnp.int32)
            else:
                docs = jnp.arange(n, dtype=jnp.int32)
            t = (docs >= params[_lo]) & (docs < params[_hi])
            nulls = cols[_name].get("nulls") if _has else None
            if nulls is not None:
                t = t & ~nulls
            return t, nulls

        return eval_docrange

    def _emit_bitmap(self, name: str, words: np.ndarray, kind: str, has_nulls: bool, negate: bool):
        n = self.segment.num_docs
        key = self._key("bits")
        words = np.ascontiguousarray(words, dtype=np.uint32)
        if self.bitmap_layout is not None:
            # macro-batch engine: store FULL words as [ndev, L, D//32]; the
            # engine slices the doc axis per launch to [ndev, L*Db//32]
            # (parallel/engine.py _batch_params)
            assert words.size == int(np.prod(self.bitmap_layout)), (words.size, self.bitmap_layout)
            words = words.reshape(self.bitmap_layout)
            self.row_sharded_params.add(key)
        elif self.shard_info is not None:
            # split words on the device axis: each device ships + unpacks
            # ONLY its slice (local_rows is 32-aligned by construction)
            _, ndev, local_rows = self.shard_info
            assert local_rows % 32 == 0 and words.size == ndev * (local_rows // 32), (
                words.size, ndev, local_rows,
            )
            words = words.reshape(ndev, local_rows // 32)
            self.row_sharded_params.add(key)
        self.params[key] = words
        if not negate and not has_nulls:
            self._plain_bitmaps.add(key)
        self._null_guard(name, has_nulls)
        self.index_uses.append((name, kind))

        def eval_bitmap(cols, params, _key=key, _name=name, _has=has_nulls, _neg=negate):
            w = params[_key].reshape(-1)
            bits = ((w[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)) != 0
            t = bits.reshape(-1)[:n]
            if _neg:
                t = ~t
            nulls = cols[_name].get("nulls") if _has else None
            if nulls is not None:
                t = t & ~nulls
            return t, nulls

        return eval_bitmap

    def _consults_index(self, name, col) -> bool:
        """Whether `name`'s range / inverted index answers its predicates
        (bitmap_serves); where the column has one and scans, says so."""
        if bitmap_serves(self.segment, col):
            return True
        for kind in ("range", "inverted"):
            if self._col_index(kind, name) is not None:
                self.index_scans.append((name, kind))
                break
        return False

    def _try_index_paths(self, name, col, lo_code, hi_code, table, has_nulls, pt=None):
        """Sorted doc-range > range-index > inverted-index, else None (scan)."""
        if lo_code is not None:  # code-range predicate (EQ / RANGE)
            if col.stats.is_sorted and col.codes is not None:
                codes_arr = np.asarray(col.codes)
                if codes_arr.ndim == 1 and hasattr(col, "first_docs"):
                    # the sorted index: two reads of the dictId -> first-doc
                    # table, and on a plan-cache hit the recipe does the same
                    self._bindable = ("docrange", pt, name, False)
                    return self._emit_doc_range(name, *sorted_doc_range(col, lo_code, hi_code), has_nulls)
                if codes_arr.ndim == 2:
                    # stacked [S, D]: flat row-major order IS the build input
                    # order (padding all at the tail) — slice it off so
                    # searchsorted sees the sorted run; doc ranges are in
                    # GLOBAL flat coordinates (see _emit_doc_range)
                    total = getattr(self.segment, "total_docs", None)
                    if total is None:
                        return self._try_bitmap_range(name, col, lo_code, hi_code, has_nulls)
                    codes_arr = codes_arr.reshape(-1)[:total]
                d0 = int(np.searchsorted(codes_arr, lo_code, side="left"))
                d1 = int(np.searchsorted(codes_arr, hi_code, side="left")) if hi_code > lo_code else d0
                return self._emit_doc_range(name, d0, d1, has_nulls)
            return self._try_bitmap_range(name, col, lo_code, hi_code, has_nulls)
        # table predicate (IN / NOT_IN / NEQ / regex / LIKE)
        inv = self._col_index("inverted", name)
        if inv is None or not self._consults_index(name, col):
            return None
        pos = np.nonzero(table)[0]
        neg_ids = np.nonzero(~table)[0]
        if len(pos) <= _INV_MAX_ROWS:
            words = inv.doc_bitmap(pos) if len(pos) else np.zeros(inv.num_words, np.uint32)
            return self._emit_bitmap(name, words, "inverted", has_nulls, False)
        if len(neg_ids) <= _INV_MAX_ROWS:
            words = inv.doc_bitmap(neg_ids) if len(neg_ids) else np.zeros(inv.num_words, np.uint32)
            return self._emit_bitmap(name, words, "inverted", has_nulls, True)
        return None

    def _try_bitmap_range(self, name, col, lo_code, hi_code, has_nulls):
        """Range-index / inverted-index resolution for a code-range predicate."""
        if not self._consults_index(name, col):
            return None
        rng_idx = self._col_index("range", name)
        if rng_idx is not None:
            return self._emit_bitmap(
                name, rng_idx.range_bitmap(lo_code, hi_code), "range", has_nulls, False
            )
        inv = self._col_index("inverted", name)
        if inv is not None and (hi_code - lo_code) <= _INV_MAX_ROWS:
            ids = np.arange(lo_code, hi_code, dtype=np.int64)
            words = inv.doc_bitmap(ids) if len(ids) else np.zeros(inv.num_words, np.uint32)
            return self._emit_bitmap(name, words, "inverted", has_nulls, False)
        return None

    # -- raw-value -------------------------------------------------------
    def _compile_value_predicate(self, p: Predicate) -> Callable[[Dict, Dict], MaskPair]:
        seg = self.segment
        pt = p.ptype
        if pt in (PredicateType.REGEXP_LIKE, PredicateType.LIKE, PredicateType.TEXT_MATCH, PredicateType.JSON_MATCH):
            raise ValueError(f"{pt.value} requires a dictionary-encoded column (lhs={p.lhs})")
        null_handling = self.null_handling
        self.used_columns.update(c for c in p.lhs.columns() if c != "*")

        if pt in (PredicateType.IN, PredicateType.NOT_IN):
            from pinot_tpu.query.shape import bucket_size

            key = self._key("set")
            vals_arr = np.asarray(sorted(p.values))
            # numeric lists: normalize dtype (a value-dependent downcast
            # would make the traced program depend on the literals) and pad
            # to the bucketed size class with identity fill — repeating a
            # member never changes isin semantics, and distinct list
            # lengths within one bucket share a single compile
            # (shape-fingerprint contract, query/shape.py).
            if np.issubdtype(vals_arr.dtype, np.integer):
                vals_arr = vals_arr.astype(np.int64)
            elif np.issubdtype(vals_arr.dtype, np.floating):
                vals_arr = vals_arr.astype(np.float64)
            if vals_arr.dtype.kind in "iuf" and len(vals_arr):
                b = bucket_size(len(vals_arr))
                if b > len(vals_arr):
                    fill = np.full(b - len(vals_arr), vals_arr[0], vals_arr.dtype)
                    vals_arr = np.concatenate([vals_arr, fill])
            self.params[key] = vals_arr

            def eval_in(cols, params, _key=key, _neg=(pt is PredicateType.NOT_IN)):
                vals, nulls = eval_expr(p.lhs, seg, cols)
                t = jnp.isin(vals, params[_key])
                if _neg:
                    t = ~t
                if nulls is not None and null_handling:
                    t = t & ~nulls
                    return t, nulls
                return t, None

            return eval_in

        # raw EQ/NEQ/RANGE: numeric literals ship as scalar params, so
        # distinct literals replay one traced program (the shape-
        # fingerprint contract).  Which bounds exist and their inclusivity
        # stay trace-time structure — exactly what query/shape.py keeps in
        # the slot.  Non-numeric literals remain trace-time constants (the
        # audit keeps those predicates literal-keyed).
        def _num_param(suffix: str, v):
            if not isinstance(v, (bool, int, float)):
                return None
            key = self._key(suffix)
            if isinstance(v, bool):
                self.params[key] = np.bool_(v)
            elif isinstance(v, int):
                self.params[key] = np.int64(v)
            else:
                self.params[key] = np.float64(v)
            return key

        eq_key = lo_key = hi_key = None
        if pt in (PredicateType.EQ, PredicateType.NEQ):
            eq_key = _num_param("cmp", p.values[0])
        elif pt is PredicateType.RANGE:
            if p.lower is not None:
                lo_key = _num_param("lo", p.lower)
            if p.upper is not None:
                hi_key = _num_param("hi", p.upper)

        def eval_cmp(cols, params):
            vals, nulls = eval_expr(p.lhs, seg, cols)
            if pt is PredicateType.EQ:
                t = vals == (params[eq_key] if eq_key is not None else p.values[0])
            elif pt is PredicateType.NEQ:
                t = vals != (params[eq_key] if eq_key is not None else p.values[0])
            elif pt is PredicateType.RANGE:
                t = jnp.ones_like(vals, dtype=bool)
                if p.lower is not None:
                    lo = params[lo_key] if lo_key is not None else p.lower
                    t = t & (vals >= lo if p.lower_inclusive else vals > lo)
                if p.upper is not None:
                    hi = params[hi_key] if hi_key is not None else p.upper
                    t = t & (vals <= hi if p.upper_inclusive else vals < hi)
            else:
                raise ValueError(f"predicate {pt} unsupported on raw values")
            if nulls is not None and null_handling:
                t = t & ~nulls
                return t, nulls
            return t, None

        return eval_cmp
