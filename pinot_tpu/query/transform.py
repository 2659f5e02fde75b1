"""Expression evaluation on device columns.

Reference parity: pinot-core's 76 vectorized transform-function classes +
TransformOperator (.../operator/transform/).  Re-design: expressions are
evaluated by tracing — each Expr node becomes jnp ops inside the segment
kernel closure, and XLA fuses the whole expression into the surrounding
filter/aggregate kernel (no per-block operator objects, no intermediate
buffers unless XLA wants them).

Null propagation is SQL-style: a row's expression value is null if any input
column value is null (tracked as a parallel bool mask; None when statically
known null-free).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from pinot_tpu.ops.code_lookup import RESIDENT, code_lookup, tally
from pinot_tpu.query import scalar
from pinot_tpu.query.ir import Expr, ExprKind
from pinot_tpu.segment.segment import ImmutableSegment

# value, null-mask (None = no nulls possible)
EvalResult = Tuple[jnp.ndarray, Optional[jnp.ndarray]]


def _or_masks(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a | b


_BINARY = {
    "plus": jnp.add,
    "add": jnp.add,
    "minus": jnp.subtract,
    "sub": jnp.subtract,
    "times": jnp.multiply,
    "mult": jnp.multiply,
    "mod": jnp.mod,
    "pow": jnp.power,
}

_UNARY = {
    "abs": jnp.abs,
    "neg": jnp.negative,
    "floor": jnp.floor,
    "ceiling": jnp.ceil,
    "ceil": jnp.ceil,
    "exp": jnp.exp,
    "ln": jnp.log,
    "log": jnp.log,  # Pinot's LOG is natural log
    "log2": jnp.log2,
    "log10": jnp.log10,
    "sqrt": jnp.sqrt,
    "sign": jnp.sign,
}


# see column_values: the bytes a materialized unpack may hold (2^24 rows)
_DECODE_BARRIER_MAX_BYTES = 1 << 26


def column_values(name: str, segment: ImmutableSegment, cols: Dict) -> EvalResult:
    """Numeric values of a column from the device pytree (dictionary gather
    for dict-encoded numerics — the ProjectionOperator/DataFetcher analog;
    no gather where staging handed the dictionary column out decoded: the
    plan's value_columns, code_lookup's RESIDENT form)."""
    c = segment.column(name)
    entry = cols[name]
    if c.data_type.is_string_like:
        raise ValueError(
            f"column {name!r} is {c.data_type.value}; string values never materialize on device "
            "(use it in predicates/group-by, which operate on dict codes)"
        )
    if "values" in entry:
        vals = entry["values"]
        if c.has_dictionary:
            tally(RESIDENT)
    else:
        codes = entry["codes"].astype(jnp.int32)
        bits = getattr(c, "code_bits", None)
        if "codes_packed" in entry and bits and codes.ndim == 1:
            # `codes` is the trace-level lane unpack of the packed words.  A
            # 1-D gather whose indices are that unpack FUSED IN compiles
            # pathologically on XLA's TPU backend (AOT for a described v5e,
            # 1.5M rows of interleaved lanes, PR 22: 44 s at 4-bit lanes, 104
            # s at 8, 241 s at 16; ~1 s behind a barrier).  Materializing
            # the unpack writes the int32 codes once, 4 B a row (6 MB for a
            # 1.5M-row segment; the interleaved layout's [words, lanes] view
            # was held tile-padded at 512 B a word, 384 MB) — fine for a
            # segment, so only segment-sized decodes take the barrier.
            if codes.shape[0] * 4 <= _DECODE_BARRIER_MAX_BYTES:
                codes = jax.lax.optimization_barrier(codes)
        vals = code_lookup(entry["dict"], codes)
    nulls = entry.get("nulls")
    return vals, nulls


def value_leaves(expr: Expr) -> List[str]:
    """The column references of `expr` that eval_expr reads BY VALUE (its
    COLUMN case: column_values), with repeats, from the expression alone.
    The cases are eval_expr's, in its order; a call it does not descend into
    through itself (a CASE, a condition, a dictionary-domain function, an
    array length: readers of codes, nulls or lengths) gives none, so a
    column under one is not taken for a by-value read."""
    if expr.kind is ExprKind.COLUMN:
        return [expr.op]
    if expr.kind is ExprKind.LITERAL:
        return []
    op, args = expr.op, expr.args
    if (op in _BINARY and len(args) == 2) or op in ("divide", "div") or (op in _UNARY and len(args) == 1):
        descend = args
    elif op == "cast" and len(args) == 2 and args[1].is_literal:
        descend = args[:1]
    elif op in ("arraylength", "cardinality", "case") or op.startswith("__"):
        descend = ()
    elif (op in ("least", "greatest") and args) or op in scalar.DEVICE_MULTI_FNS:
        descend = args
    elif op in scalar.DEVICE_FNS:
        descend = [a for a in args if not a.is_literal]
    else:
        descend = ()
    return [name for a in descend for name in value_leaves(a)]


def eval_expr(expr: Expr, segment: ImmutableSegment, cols: Dict) -> EvalResult:
    """Trace an expression into jnp ops over the segment's device columns
    (value_leaves above says which columns that reads by value: keep the
    two in step)."""
    if expr.kind is ExprKind.COLUMN:
        return column_values(expr.op, segment, cols)
    if expr.kind is ExprKind.LITERAL:
        # Python scalars stay weak-typed: arithmetic keeps the column's dtype
        # (jnp.asarray would mint an int64/f64 under x64 and force emulated
        # 64-bit elementwise ops on TPU).
        return expr.value, None
    op = expr.op
    if op in _BINARY and len(expr.args) == 2:
        (a, na) = eval_expr(expr.args[0], segment, cols)
        (b, nb) = eval_expr(expr.args[1], segment, cols)
        return _BINARY[op](a, b), _or_masks(na, nb)
    if op in ("divide", "div"):
        (a, na) = eval_expr(expr.args[0], segment, cols)
        (b, nb) = eval_expr(expr.args[1], segment, cols)
        # SQL divide: always double (Pinot DivisionTransformFunction)
        return astype(a, jnp.float64) / astype(b, jnp.float64), _or_masks(na, nb)
    if op in _UNARY and len(expr.args) == 1:
        (a, na) = eval_expr(expr.args[0], segment, cols)
        return _UNARY[op](a), na
    if op == "cast" and len(expr.args) == 2 and expr.args[1].is_literal:
        (a, na) = eval_expr(expr.args[0], segment, cols)
        target = str(expr.args[1].value).upper()
        dt = {"INT": jnp.int32, "LONG": jnp.int64, "FLOAT": jnp.float32, "DOUBLE": jnp.float64}.get(target)
        if dt is None:
            raise ValueError(f"unsupported CAST target {target}")
        return astype(a, dt), na
    if op in ("arraylength", "cardinality") and len(expr.args) == 1 and expr.args[0].is_column:
        entry = cols[expr.args[0].op]
        if "lengths" not in entry:
            raise ValueError(f"{op} requires a multi-value column ({expr.args[0].op} is single-value)")
        return entry["lengths"].astype(jnp.int32), None
    if op == "case":
        return _eval_case(expr, segment, cols)
    if op in ("__and", "__or", "__not", "__eq", "__in", "__ge", "__gt", "__le", "__lt", "__isnull"):
        return _eval_bool(expr, segment, cols), None
    if op in ("least", "greatest") and expr.args:
        vals, nulls = zip(*(eval_expr(a, segment, cols) for a in expr.args))
        acc, nl = vals[0], nulls[0]
        for v, n in zip(vals[1:], nulls[1:]):
            acc = jnp.minimum(acc, v) if op == "least" else jnp.maximum(acc, v)
            nl = _or_masks(nl, n)
        return acc, nl
    if op in scalar.DEVICE_MULTI_FNS:
        # positional: every arg evaluates (literals stay scalars)
        vals, nulls = [], None
        for a in expr.args:
            if a.is_literal:
                vals.append(a.value)
            else:
                v, nv = eval_expr(a, segment, cols)
                vals.append(v)
                nulls = _or_masks(nulls, nv)
        return scalar.DEVICE_MULTI_FNS[op](*vals), nulls
    if op in scalar.DEVICE_FNS:
        # one traced operand + literal parameters, in SQL order
        # (DATETRUNC('day', ts) / ROUND(x, 2) / TIMECONVERT(t, 'SECONDS', 'DAYS'))
        traced = [a for a in expr.args if not a.is_literal]
        lits = [a.value for a in expr.args if a.is_literal]
        if len(traced) != 1:
            raise ValueError(f"{op} expects exactly one column/expression argument, got {expr}")
        v, nv = eval_expr(traced[0], segment, cols)
        return scalar.DEVICE_FNS[op](v if hasattr(v, "astype") else jnp.asarray(v), *lits), nv
    if scalar.is_dict_fn_expr(expr):
        # dictionary-domain function: host-evaluate over the dictionary's
        # VALUES (cardinality-sized) and gather derived[codes] on device.
        col = next(a for a in expr.args if not a.is_literal).op
        c = segment.column(col)
        if not c.has_dictionary:
            raise ValueError(f"{op} requires a dictionary-encoded column ({col} is raw)")
        if scalar.string_result(expr):
            raise ValueError(
                f"string-valued {op}(...) never materializes on device; use it in "
                "predicates, GROUP BY, or the select list (host paths)"
            )
        derived = scalar.derived_for(expr, c.dictionary)
        entry = cols[col]
        vals = code_lookup(jnp.asarray(derived), entry["codes"].astype(jnp.int32))
        return vals, entry.get("nulls")
    raise ValueError(f"unsupported transform function {op!r} in {expr}")


def _eval_bool_host(expr: Expr, segment: ImmutableSegment, docids: np.ndarray) -> np.ndarray:
    """Host (numpy) twin of _eval_bool for selection-path CASE."""
    op = expr.op
    if op == "__and":
        out = None
        for a in expr.args:
            b = _eval_bool_host(a, segment, docids)
            out = b if out is None else out & b
        return out
    if op == "__or":
        out = None
        for a in expr.args:
            b = _eval_bool_host(a, segment, docids)
            out = b if out is None else out | b
        return out
    if op == "__not":
        return ~_eval_bool_host(expr.args[0], segment, docids)
    lhs = expr.args[0]
    lits = [a.value for a in expr.args[1:]]
    if op == "__isnull":
        if lhs.is_column and segment.column(lhs.op).nulls is not None:
            return segment.column(lhs.op).nulls[docids]
        return np.zeros(len(docids), dtype=bool)
    v = eval_expr_host(lhs, segment, docids)
    if op == "__eq":
        return np.asarray([x == lits[0] for x in v], dtype=bool)
    if op == "__in":
        s = set(lits)
        return np.asarray([x in s for x in v], dtype=bool)
    v = np.asarray(v, dtype=np.float64)
    if op == "__ge":
        return v >= lits[0]
    if op == "__gt":
        return v > lits[0]
    if op == "__le":
        return v <= lits[0]
    return v < lits[0]


def _eval_bool(expr: Expr, segment: ImmutableSegment, cols: Dict):
    """CASE condition ops -> traced bool row mask (CaseTransformFunction's
    WHEN evaluation).  String equality/IN resolve against the dictionary
    (code compares); numerics compare values directly."""
    op = expr.op
    if op == "__and":
        out = None
        for a in expr.args:
            b = _eval_bool(a, segment, cols)
            out = b if out is None else out & b
        return out
    if op == "__or":
        out = None
        for a in expr.args:
            b = _eval_bool(a, segment, cols)
            out = b if out is None else out | b
        return out
    if op == "__not":
        return ~_eval_bool(expr.args[0], segment, cols)
    lhs = expr.args[0]
    lits = [a.value for a in expr.args[1:]]
    if op == "__isnull":
        entry = cols.get(lhs.op, {}) if lhs.is_column else {}
        if "nulls" in entry:
            return entry["nulls"]
        n = segment.num_docs
        return jnp.zeros((n,), dtype=bool)
    # string column comparisons resolve to dictionary codes
    if lhs.is_column and segment.column(lhs.op).data_type.is_string_like:
        c = segment.column(lhs.op)
        codes = cols[lhs.op]["codes"].astype(jnp.int32)
        ids = [c.dictionary.index_of(v) for v in lits]
        if op == "__eq":
            return codes == np.int32(ids[0])
        if op == "__in":
            valid = np.asarray([i for i in ids if i >= 0], dtype=np.int32)
            return jnp.isin(codes, valid) if len(valid) else jnp.zeros(codes.shape, bool)
        raise ValueError(f"CASE condition {op} not supported on string column {lhs.op}")
    v, _ = eval_expr(lhs, segment, cols)
    if op == "__eq":
        return v == lits[0]
    if op == "__in":
        return jnp.isin(v, jnp.asarray(lits))
    if op == "__ge":
        return v >= lits[0]
    if op == "__gt":
        return v > lits[0]
    if op == "__le":
        return v <= lits[0]
    return v < lits[0]


def _eval_case(expr: Expr, segment: ImmutableSegment, cols: Dict) -> EvalResult:
    """CASE WHEN ... THEN ... ELSE ... END: reverse-fold of jnp.where.
    An omitted ELSE yields SQL NULL via the null mask."""
    args = list(expr.args)
    else_e = args[-1]
    else_null = else_e.is_literal and else_e.value is None
    if else_null:
        out, en = jnp.float64(0.0), None  # implicit ELSE NULL
    else:
        out, en = eval_expr(else_e, segment, cols)
    evaluated = [
        (_eval_bool(c, segment, cols), *eval_expr(t, segment, cols))
        for c, t in zip(args[:-1:2], args[1::2])
    ]
    # reverse-fold values AND null masks together: a row's nullness is the
    # CHOSEN branch's nullness, not the OR of all branches (review-caught)
    if else_null or en is not None or any(tn is not None for _, _, tn in evaluated):
        nulls = en if en is not None else jnp.full((segment.num_docs,), else_null, dtype=bool)
    else:
        nulls = None
    for cond, tv, tn in reversed(evaluated):
        out = jnp.where(cond, tv, out)
        if nulls is not None:
            branch_null = tn if tn is not None else False
            nulls = jnp.where(cond, branch_null, nulls)
    return out, nulls


def eval_expr_host(expr: Expr, segment: ImmutableSegment, docids: np.ndarray) -> np.ndarray:
    """Host-side expression evaluation over a SELECTED row subset (selection
    queries gather at most offset+limit rows, so O(rows-out) host work).
    Shares DEVICE_FNS via eager jnp; string-valued dictionary functions
    evaluate over the dictionary and gather by code."""
    if expr.kind is ExprKind.COLUMN:
        return segment.column(expr.op).decoded()[docids]
    if expr.kind is ExprKind.LITERAL:
        return np.full(len(docids), expr.value)
    if expr.op in ("arraylength", "cardinality") and len(expr.args) == 1 and expr.args[0].is_column:
        c = segment.column(expr.args[0].op)
        if c.mv_lengths is None:
            raise ValueError(f"{expr.op} requires a multi-value column")
        return c.mv_lengths[docids].astype(np.int64)
    if expr.op == "case":
        args = list(expr.args)
        else_e = args[-1]
        pairs = list(zip(args[:-1:2], args[1::2]))
        if else_e.is_literal and else_e.value is None:
            out = np.full(len(docids), None, dtype=object)
        else:
            out = np.asarray(eval_expr_host(else_e, segment, docids), dtype=object)
        for cond_e, then_e in reversed(pairs):
            cond = _eval_bool_host(cond_e, segment, docids)
            tv = np.asarray(eval_expr_host(then_e, segment, docids), dtype=object)
            out = np.where(cond, tv, out)
        return out
    if scalar.is_dict_fn_expr(expr):
        col = next(a for a in expr.args if not a.is_literal).op
        c = segment.column(col)
        if c.has_dictionary:
            derived = scalar.derived_for(expr, c.dictionary)
            return derived[np.asarray(c.codes, dtype=np.int64)[docids]]
    op = expr.op
    if op in _BINARY and len(expr.args) == 2:
        a = eval_expr_host(expr.args[0], segment, docids)
        b = eval_expr_host(expr.args[1], segment, docids)
        return np.asarray(_BINARY[op](jnp.asarray(a), jnp.asarray(b)))
    if op in ("divide", "div"):
        a = eval_expr_host(expr.args[0], segment, docids).astype(np.float64)
        b = eval_expr_host(expr.args[1], segment, docids).astype(np.float64)
        return a / b
    if op in _UNARY and len(expr.args) == 1:
        return np.asarray(_UNARY[op](jnp.asarray(eval_expr_host(expr.args[0], segment, docids))))
    if op in scalar.DEVICE_MULTI_FNS:
        vals = [
            a.value if a.is_literal else jnp.asarray(eval_expr_host(a, segment, docids).astype(np.float64))
            for a in expr.args
        ]
        return np.asarray(scalar.DEVICE_MULTI_FNS[op](*vals))
    if op in scalar.DEVICE_FNS:
        traced = [a for a in expr.args if not a.is_literal]
        lits = [a.value for a in expr.args if a.is_literal]
        if len(traced) == 1:
            v = eval_expr_host(traced[0], segment, docids)
            return np.asarray(scalar.DEVICE_FNS[op](jnp.asarray(v), *lits))
    if op == "todatetime" and len(expr.args) in (2, 3) and expr.args[1].is_literal:
        v = eval_expr_host(expr.args[0], segment, docids)
        tz = expr.args[2].value if len(expr.args) == 3 and expr.args[2].is_literal else None
        return scalar.to_datetime(v, expr.args[1].value, tz)
    if op == "cast" and len(expr.args) == 2 and expr.args[1].is_literal:
        v = eval_expr_host(expr.args[0], segment, docids)
        target = str(expr.args[1].value).upper()
        npdt = {"INT": np.int32, "LONG": np.int64, "FLOAT": np.float32, "DOUBLE": np.float64, "STRING": None}.get(
            target, np.float64
        )
        return v.astype(str) if npdt is None else v.astype(npdt)
    raise ValueError(f"unsupported selection expression {op!r} in {expr}")


def astype(vals, dt):
    """dtype cast that also accepts the weak-typed python scalars LITERAL
    nodes produce (the single normalization point for literal operands)."""
    if hasattr(vals, "astype"):
        return vals.astype(dt)
    return jnp.asarray(vals, dtype=dt)


def as_row_array(vals, shape):
    """Broadcast a weak-typed literal to a row-shaped array; pass arrays
    through (shared by planner/engine aggregation-input plumbing)."""
    if hasattr(vals, "astype"):
        return vals
    return jnp.full(shape, float(vals), dtype=jnp.float64)
