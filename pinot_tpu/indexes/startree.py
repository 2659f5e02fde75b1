"""Star-tree index: pre-aggregated prefix-level tables, resident on the device.

Reference parity: Pinot's StarTreeV2 — a materialized tree over a dimension
split order where star (*) nodes pre-aggregate over the remaining dimensions,
letting group-by queries answer from aggregated records instead of scanning
raw rows (pinot-segment-spi/.../spi/index/startree/StarTreeV2.java, builder
pinot-segment-local/.../startree/v2/builder/OffHeapSingleTreeBuilder.java,
runtime pinot-core/.../core/startree/operator/StarTreeFilterOperator.java:90,
traversal :218, StarTreeAggregationExecutor/StarTreeGroupByExecutor).

TPU re-design — the tree becomes a LADDER OF COLLAPSED TABLES. A pointer
tree with star-node traversal is a branchy, dynamic-shape structure XLA cannot
compile; but its *content* is equivalent to: for every prefix of the split
order, the table of distinct prefix combos with metrics pre-aggregated over
all other columns.  So we materialize exactly that — for each prefix length
k, a small columnar table ("level") of the distinct (d1..dk) combos with
pre-aggregated partial FIELDS (count/sum/sumsq/min/max per metric).  A query
whose filter+group-by columns all fall in the first k dims answers from
level k.  Star-node traversal becomes *level selection*, a host-side O(1)
decision (query/startree.py pick_level).

A level is answered by the ORDINARY plan: `StarLevel.table(parent)` is the
level as a segment of its own (LevelSegment): its dimension columns carry the
PARENT segment's dictionaries over the level's codes (so star results and
raw-scan results from other segments merge in one key space at reduce time),
its fields are metric columns named `<column>:<kind>` (`*:count`,
`lo_revenue:sum`), and its rows are padded to a bucket (table_shape.row_bucket) so that
the same level of every segment of a table has ONE shape and shares ONE
compiled program; the true row count is the plan's bound parameter
(planner.ROWS_KEY).  The parent's `to_device()` stages its levels with it,
each a residency group of its own.

Pinot's functionColumnPairs config maps 1:1; maxLeafRecords is accepted but
moot here (every "leaf" is one aggregated row); instead `min_collapse`
skips building when the finest level barely collapses the data.
"""
from __future__ import annotations

import threading
from dataclasses import replace
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.query.functions import get_agg_function
from pinot_tpu.segment.segment import ColumnData, ImmutableSegment
from pinot_tpu.segment.stats import ColumnStats
from pinot_tpu.segment.table_shape import row_bucket
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema

# A level's rows on the device are padded to a bucket (table_shape.row_bucket,
# the rule a table's segments of unequal rows are padded by).  The padding
# is identity rows, masked out by the bound row count.
# a dense key space up to this many slots is counted (bincount), not sorted
_DENSE_KEY_SPACE = 1 << 24


def field_column(col: str, kind: str) -> str:
    """The level table's column that holds field `kind` of metric `col`."""
    return f"{col}:{kind}"


def scatter_combine(kind: str, inverse: np.ndarray, vals: np.ndarray, n_groups: int) -> np.ndarray:
    """One (count|sum|sumsq|min|max) scatter-aggregate into n_groups slots —
    the single combine rule shared by the finest-level build and the
    coarser-level rollup.  Additive integer kinds accumulate exactly in
    int64 (through bincount's f64 where every partial sum stays under 2^53,
    else np.add.at); float kinds use bincount; min/max use ufunc scatter.
    `vals` is taken as-is (callers square before passing sumsq of raw rows;
    partials re-combine without squaring)."""
    vals = np.asarray(vals)
    if kind in ("count", "sum", "sumsq"):
        if np.issubdtype(vals.dtype, np.integer) and kind != "sumsq":
            bound = int(np.abs(vals).max(initial=0)) * max(1, len(vals))
            if bound < (1 << 53):
                return np.bincount(inverse, weights=vals, minlength=n_groups).astype(np.int64)
            acc = np.zeros(n_groups, dtype=np.int64)
            np.add.at(acc, inverse, vals.astype(np.int64, copy=False))
            return acc
        return np.bincount(inverse, weights=vals.astype(np.float64, copy=False), minlength=n_groups)
    if kind == "min":
        acc = np.full(n_groups, np.inf)
        np.minimum.at(acc, inverse, vals.astype(np.float64, copy=False))
        return acc
    if kind == "max":
        acc = np.full(n_groups, -np.inf)
        np.maximum.at(acc, inverse, vals.astype(np.float64, copy=False))
        return acc
    raise ValueError(f"unknown star-tree field kind {kind!r}")


def _parse_pairs(pairs: List[Any]) -> List[Tuple[str, str]]:
    """functionColumnPairs: "SUM__lo_revenue" strings or [func, col] lists."""
    out = []
    for p in pairs:
        if isinstance(p, str):
            func, _, col = p.partition("__")
        else:
            func, col = p
        out.append((func.lower(), col))
    return out


def _distinct(key: np.ndarray, space: int) -> Tuple[np.ndarray, np.ndarray]:
    """np.unique(key, return_inverse=True) for keys in [0, space): counted
    where the key space is small enough to hold, sorted otherwise."""
    if space <= _DENSE_KEY_SPACE:
        present = np.bincount(key, minlength=space) > 0
        rank = np.cumsum(present) - 1
        return np.nonzero(present)[0], rank[key]
    return np.unique(key, return_inverse=True)


class StarTreeIndex:
    KIND = "startree"

    def __init__(
        self,
        split_order: List[str],
        pairs: List[Tuple[str, str]],
        levels: Dict[int, "StarLevel"],
        total_docs: int,
    ):
        self.split_order = list(split_order)
        self.pairs = [(f.lower(), c) for f, c in pairs]
        self.levels = levels
        self.total_docs = total_docs
        # (col, kind) set actually stored (derived from level 0's fields)
        any_level = next(iter(levels.values()))
        self.stored: frozenset = frozenset(any_level.fields)

    # ------------------------------------------------------------------
    @staticmethod
    def build(
        columns: Dict[str, Any],
        num_docs: int,
        split_order: List[str],
        function_column_pairs: List[Any],
        min_collapse: float = 1.1,
    ) -> Optional["StarTreeIndex"]:
        """Build the level ladder from a segment's columns.

        Returns None (tree not worth it / not buildable) when: a dim or
        metric column has nulls, a metric is non-numeric, or the finest
        level collapses rows by less than `min_collapse`x.

        The rows' dimension codes are packed into ONE mixed-radix int64 key
        (first dimension most significant), so a level's distinct
        combinations are a one-dimensional unique of `key // stride` and a
        coarser level is the next-finer one's keys divided down: the levels
        np.unique(matrix, axis=0) gives, without sorting rows of a matrix."""
        pairs = _parse_pairs(function_column_pairs)
        if not num_docs:
            return None

        # per dimension: parent dict codes, or raw ints as-is
        dim_cols = []
        for d in split_order:
            c = columns.get(d)
            if c is None or c.nulls is not None or c.mv_lengths is not None:
                return None
            if c.codes is not None:
                dim_cols.append(np.asarray(c.codes))
            elif c.values is not None and np.issubdtype(np.asarray(c.values).dtype, np.integer):
                dim_cols.append(np.asarray(c.values))
            else:
                return None

        # metric field columns to aggregate: (col, kind) -> source values
        need: Dict[Tuple[str, str], np.ndarray] = {}
        for func, col in pairs:
            if col == "*":
                continue
            c = columns.get(col)
            if c is None or c.nulls is not None:
                return None
            vals = np.asarray(c.decoded())
            if not np.issubdtype(vals.dtype, np.number):
                return None
            fn = get_agg_function(func)
            if fn.field_kinds is None:
                return None  # sketch family: not pre-aggregable as scalars
            for kind in fn.field_kinds.values():
                if kind == "count":
                    continue
                need[(col, kind)] = vals

        # the packed key: digit i = dim i's value less its minimum, radix its range
        lows = [int(a.min()) for a in dim_cols]
        radix = [int(a.max()) - lo + 1 for a, lo in zip(dim_cols, lows)]
        K = len(split_order)
        strides = [1] * (K + 1)  # strides[k]: what a key is divided by to keep its first k digits
        for k in range(K - 1, -1, -1):
            strides[k] = strides[k + 1] * radix[k]
        if strides[0] >= (1 << 62):
            return None  # the combinations do not fit one int64 key: the scan serves the table
        key = np.zeros(num_docs, dtype=np.int64)
        for a, lo, stride in zip(dim_cols, lows, strides[1:]):
            key += (a.astype(np.int64) - lo) * stride
        finest, inverse = _distinct(key, strides[0])
        if len(finest) * min_collapse > num_docs:
            return None  # barely collapses: scanning raw rows is as cheap

        def dims_of(keys: np.ndarray, k: int) -> Dict[str, np.ndarray]:
            """The first k dimensions' values of level k's keys (`keys` hold k digits)."""
            return {
                d: (keys // (strides[i + 1] // strides[k])) % radix[i] + lows[i]
                for i, d in enumerate(split_order[:k])
            }

        # finest level: aggregate raw rows into the distinct-combo table
        n_g = len(finest)
        fields: Dict[Tuple[str, str], np.ndarray] = {}
        fields[("*", "count")] = np.bincount(inverse, minlength=n_g).astype(np.int64)
        for (col, kind), vals in need.items():
            src = vals.astype(np.float64) ** 2 if kind == "sumsq" else vals
            fields[(col, kind)] = scatter_combine(kind, inverse, src, n_g)

        levels: Dict[int, StarLevel] = {K: StarLevel(n_g, dims_of(finest, K), fields)}
        # coarser levels: aggregate the next-finer level (adds add, mins min)
        cur = finest  # level k + 1's keys, ascending
        for k in range(K - 1, -1, -1):
            combos, inv2 = np.unique(cur // radix[k], return_inverse=True)
            f2 = {
                field: scatter_combine(field[1], inv2, arr, len(combos))
                for field, arr in levels[k + 1].fields.items()
            }
            levels[k] = StarLevel(len(combos), dims_of(combos, k), f2)
            cur = combos
        return StarTreeIndex(split_order, pairs, levels, num_docs)

    # -- persistence (store.py region protocol) -------------------------
    def to_regions(self, prefix: str) -> List[Tuple[str, np.ndarray]]:
        regions = []
        for k, lvl in self.levels.items():
            for d, arr in lvl.dims.items():
                regions.append((f"{prefix}.L{k}.d.{d}", arr))
            for (col, kind), arr in lvl.fields.items():
                regions.append((f"{prefix}.L{k}.f.{col}:{kind}", arr))
        return regions

    def meta(self) -> Dict[str, Any]:
        return {
            "splitOrder": self.split_order,
            "pairs": [[f, c] for f, c in self.pairs],
            "levels": {str(k): lvl.num_rows for k, lvl in self.levels.items()},
            "fields": [[c, k] for c, k in sorted(self.stored)],
            "totalDocs": self.total_docs,
        }

    @staticmethod
    def from_regions(meta: Dict[str, Any], regions, prefix: str) -> "StarTreeIndex":
        split_order = meta["splitOrder"]
        levels: Dict[int, StarLevel] = {}
        for ks, nrows in meta["levels"].items():
            k = int(ks)
            dims = {
                d: np.asarray(regions[f"{prefix}.L{k}.d.{d}"]) for d in split_order[:k]
            }
            fields = {
                (c, kd): np.asarray(regions[f"{prefix}.L{k}.f.{c}:{kd}"])
                for c, kd in meta["fields"]
            }
            levels[k] = StarLevel(num_rows=nrows, dims=dims, fields=fields)
        return StarTreeIndex(
            split_order, [tuple(p) for p in meta["pairs"]], levels, meta["totalDocs"]
        )

    # -- query-time API --------------------------------------------------
    def level_for(self, dims_used) -> Optional[int]:
        """Smallest prefix length covering dims_used, or None."""
        k = found = 0
        for i, d in enumerate(self.split_order):
            if d in dims_used:
                k = i + 1
                found += 1
        return k if found == len(dims_used) else None

    def has_fields(self, func: str, col: str) -> bool:
        fn = get_agg_function(func)
        if fn.field_kinds is None or fn.needs_binding:
            return False
        for kind in fn.field_kinds.values():
            key = ("*", "count") if kind == "count" else (col, kind)
            if key not in self.stored:
                return False
        return True


class StarLevel:
    """One collapsed table: distinct prefix combos + aggregated fields, as
    host arrays of its true rows (what the segment file holds), and as the
    table a plan reads (`table`)."""

    def __init__(
        self,
        num_rows: int,
        dims: Dict[str, np.ndarray],
        fields: Dict[Tuple[str, str], np.ndarray],
    ):
        self.num_rows = num_rows
        self.dims = dims
        self.fields = fields
        self.k = len(dims)  # the prefix length
        self.made: Optional[LevelSegment] = None  # `table`'s, once asked for
        self._lock = threading.Lock()

    def table(self, parent: ImmutableSegment, tree: str) -> "LevelSegment":
        """This level as a segment under `parent`'s dictionaries, made once."""
        with self._lock:
            if self.made is None:
                self.made = LevelSegment(parent, tree, self)
            return self.made


def _envelope(arr: np.ndarray) -> Tuple[int, int]:
    """(min, max) bounds of an integer field column, widened to what its
    limb plan can tell apart (ops.sum_limb_plan: whole bytes, int32's sign
    bit), so that the same level of a table's segments, whose sums differ,
    states ONE range and shares one compiled program."""
    lo, hi = (int(arr.min()), int(arr.max())) if len(arr) else (0, 0)
    m = max(abs(lo), abs(hi))
    if m < (1 << 31):
        top = (1 << 31) - 1  # narrows to int32
        for bits in (8, 16, 24):
            if m < (1 << bits):
                top = (1 << bits) - 1
                break
    else:
        top = (1 << (8 * (-(-m.bit_length() // 8)))) - 1
    return (0 if lo >= 0 else -top - 1), top


class LevelSegment(ImmutableSegment):
    """One star-tree level as a table of its own, padded to row_bucket
    rows: dimension columns under the parent's dictionaries (or its raw
    ints), one metric column a field (LONG for integer counts and sums,
    DOUBLE for the rest).  The padding rows hold code 0 and the fields'
    identities (count 0, sum 0, min +inf, max -inf); `level_rows` is the
    true row count, which every plan over a level binds as a parameter and
    masks by (planner.ROWS_KEY)."""

    def __init__(self, parent: ImmutableSegment, tree: str, level: StarLevel):
        n = level.num_rows
        bucket = row_bucket(n)
        specs: List[FieldSpec] = []
        columns: Dict[str, ColumnData] = {}
        for name, arr in level.dims.items():
            pc = parent.column(name)
            # the parent's statistics: a raw-int dimension's key space (its
            # base and range) is then the scan's; a level holds every value
            # of a dimension its parent holds
            stats = replace(pc.stats, num_docs=bucket, is_sorted=False, partition_id=None, num_partitions=None)
            if pc.has_dictionary:
                codes = np.zeros(bucket, dtype=pc.codes.dtype)
                codes[:n] = arr
                columns[name] = ColumnData(name, pc.data_type, pc.dictionary, codes, None, None, stats)
            else:
                vals = np.full(bucket, pc.stats.min_value, dtype=pc.values.dtype)
                vals[:n] = arr
                columns[name] = ColumnData(name, pc.data_type, None, None, vals, None, stats)
            specs.append(parent.schema.field(name))
        for (col, kind), arr in level.fields.items():
            name = field_column(col, kind)
            if np.issubdtype(arr.dtype, np.integer):
                data_type, lo_hi = DataType.LONG, _envelope(arr)
                vals = np.zeros(bucket, dtype=np.int64)
            else:
                data_type = DataType.DOUBLE
                lo_hi = (float(arr.min()), float(arr.max())) if n else (None, None)
                vals = np.full(bucket, {"min": np.inf, "max": -np.inf}.get(kind, 0.0))
            vals[:n] = arr
            stats = ColumnStats(
                name=name, data_type=data_type, num_docs=bucket, cardinality=n,
                min_value=lo_hi[0], max_value=lo_hi[1], has_dictionary=False,
            )
            columns[name] = ColumnData(name, data_type, None, None, vals, None, stats)
            specs.append(FieldSpec(name, data_type, role=FieldRole.METRIC))
        super().__init__(
            name=f"{parent.name}/{tree}.L{level.k}",
            table_name=parent.table_name,
            schema=Schema(parent.schema.name, specs),
            columns=columns,
            num_docs=bucket,
        )
        self.level_rows = self.true_rows = n
        self.tree = tree
        self.level = level.k
        self.prefix = "/".join(level.dims) or "*"  # what EXPLAIN's index uses name the level by

    def device_group(self, device=None):
        """A residency group of its own kind: the manager tells a level's
        bytes from a segment's by it (gauge starTreeBytes)."""
        return ("star", id(self), device)
