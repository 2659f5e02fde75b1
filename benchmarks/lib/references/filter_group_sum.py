"""Plain numpy reference: WHERE (a conjunction of column tests) -> GROUP BY
-> SUM of an integer expression, exact.  Knows nothing of pinot_tpu.

spec: {"where": [[column, op, param...]], "group_by": [columns],
       "sum": ["col", c] | ["mul", a, b] | ["sub", a, b],
       "order_by": [[column | "sum", "asc" | "desc"]]}
A test's operands are names of the request's parameters.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

_HALF = 20


def _mask(col: np.ndarray, op: str, vals: Sequence[int]) -> np.ndarray:
    if op == "eq":
        return col == vals[0]
    if op == "lt":
        return col < vals[0]
    if op == "between":
        return (col >= vals[0]) & (col <= vals[1])
    if op == "in":
        m = col == vals[0]
        for v in vals[1:]:
            m |= col == v
        return m
    raise ValueError(f"unknown test {op!r}")


def _value(expr: List[Any], cols: Dict[str, np.ndarray], sel: np.ndarray) -> np.ndarray:
    kind = expr[0]
    a = cols[expr[1]][sel].astype(np.int64)
    if kind == "col":
        return a
    b = cols[expr[2]][sel].astype(np.int64)
    if kind == "mul":
        return a * b
    if kind == "sub":
        return a - b
    raise ValueError(f"unknown expression {kind!r}")


def _exact_group_sums(keys: np.ndarray, values: np.ndarray, size: int) -> np.ndarray:
    """int64 group sums through two float64 bincounts over 2^20 halves: each
    half-sum stays far under 2^53, so the result is exact.  Values >= 0."""
    lo = np.bincount(keys, weights=(values & ((1 << _HALF) - 1)).astype(np.float64), minlength=size)
    hi = np.bincount(keys, weights=(values >> _HALF).astype(np.float64), minlength=size)
    return lo.astype(np.int64) + (hi.astype(np.int64) << _HALF)


def partial(spec: Dict[str, Any], params: Dict[str, int], cols: Dict[str, np.ndarray]):
    """One block of rows -> a mergeable partial: an int for a scalar sum,
    {group key tuple: [sum, count]} for a group-by."""
    m = None
    for test in spec["where"]:
        t = _mask(cols[test[0]], test[1], [params[p] for p in test[2:]])
        m = t if m is None else m & t
    sel = np.flatnonzero(m)
    vals = _value(spec["sum"], cols, sel)
    if not spec["group_by"]:
        return int(vals.sum()), int(len(sel))
    if vals.size and int(vals.min()) < 0:
        raise ValueError("reference sums non-negative values only")
    gcols = [cols[g][sel].astype(np.int64) for g in spec["group_by"]]
    los = [int(g.min()) if g.size else 0 for g in gcols]
    spans = [int(g.max()) - lo + 1 if g.size else 1 for g, lo in zip(gcols, los)]
    key = np.zeros(len(sel), np.int64)
    for g, lo, span in zip(gcols, los, spans):
        key = key * span + (g - lo)
    size = int(np.prod(spans))
    sums = _exact_group_sums(key, vals, size)
    counts = np.bincount(key, minlength=size)
    out: Dict[Tuple[int, ...], List[int]] = {}
    for k in np.flatnonzero(counts):
        rest, parts = int(k), []
        for lo, span in zip(reversed(los), reversed(spans)):
            parts.append(rest % span + lo)
            rest //= span
        out[tuple(reversed(parts))] = [int(sums[k]), int(counts[k])]
    return out


def merge(partials: List[Any], spec: Dict[str, Any]):
    """Partials of all blocks -> the answer: {"scalar": int, "matched": n} or
    {"groups": {key: sum}}."""
    if not spec["group_by"]:
        return {"scalar": sum(p[0] for p in partials), "matched": sum(p[1] for p in partials)}
    groups: Dict[Tuple[int, ...], int] = {}
    for p in partials:
        for k, (s, _) in p.items():
            groups[k] = groups.get(k, 0) + s
    return {"groups": groups}


def answer(spec, params, blocks) -> Dict[str, Any]:
    return merge([partial(spec, params, b) for b in blocks], spec)


def _as_int(x: Any) -> int:
    """A served integer aggregate may arrive as 123 or 123.0, never 123.4 and
    never beyond 2^53 as a float (which could not be exact)."""
    if isinstance(x, bool) or not isinstance(x, (int, float)):
        raise ValueError(f"not a number: {x!r}")
    if isinstance(x, float):
        if not x.is_integer() or abs(x) >= 2.0**53:
            raise ValueError(f"not an exact integer: {x!r}")
    return int(x)


def compare(spec, columns: List[str], rows: List[List[Any]], ref: Dict[str, Any]) -> Tuple[bool, Dict[str, Any]]:
    """(equal, numbers) of one served result against the reference answer.
    `numbers` holds each quantity compared beside its limit (all exact: 0)."""
    try:
        if "scalar" in ref:
            got = _as_int(rows[0][0]) if ref["matched"] or rows[0][0] is not None else 0
            diff = abs(got - ref["scalar"])
            return diff == 0 and len(rows) == 1, {"abs_diff": diff, "limit": 0, "rows": len(rows)}
        gb = spec["group_by"]
        # result columns are named by the SQL; the aggregate is the one column
        # that is no group column
        gi = [columns.index(g) for g in gb]
        (ai,) = [i for i in range(len(columns)) if i not in gi]
        got = {tuple(_as_int(r[i]) for i in gi): _as_int(r[ai]) for r in rows}
    except (ValueError, IndexError, TypeError) as e:
        return False, {"error": str(e), "limit": 0}
    want = ref["groups"]
    missing = len(set(want) - set(got))
    extra = len(set(got) - set(want)) + (len(rows) - len(got))
    wrong = sum(1 for k, v in got.items() if k in want and want[k] != v)
    worst = max((abs(v - want[k]) for k, v in got.items() if k in want), default=0)
    # order: the served rows' sort keys must run in the order the query asks
    disorder = 0
    if spec["order_by"]:
        def sort_key(r):
            out = []
            for what, direction in spec["order_by"]:
                v = _as_int(r[ai]) if what == "sum" else _as_int(r[columns.index(what)])
                out.append(v if direction == "asc" else -v)
            return tuple(out)
        keys = [sort_key(r) for r in rows]
        disorder = sum(1 for a, b in zip(keys, keys[1:]) if a > b)
    ok = not (missing or extra or wrong or disorder)
    return ok, {"groups": len(want), "missing": missing, "extra": extra, "wrong_sums": wrong,
                "max_abs_diff": worst, "out_of_order": disorder, "limit": 0}
