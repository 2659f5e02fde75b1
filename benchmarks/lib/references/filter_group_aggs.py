"""Plain numpy reference: WHERE (a conjunction of column tests) -> optional
GROUP BY -> COUNT / SUM / MIN / MAX, each with an optional FILTER (a
conjunction of its own) -> ORDER BY -> LIMIT.  Integers, exact.  Knows
nothing of pinot_tpu.

spec: {"where": [[column, op, operand...]],
       "aggs": [{"fn": "count" | "sum" | "min" | "max", "col": column | null,
                 "filter": [[column, op, operand...]]}],
       "group_by": [columns], "order_by": [[column, "asc" | "desc"]],
       "limit": n | null}
ops: "gt", "lt", "eq", "in".  An operand is the name of one of the request's
parameters, or an integer written in the file.  Aggregates are served in the
order of `aggs`, after the group columns are taken out by name.

Its own copy of the semantics: a group is present where a row passes WHERE,
whatever the aggregates' FILTERs let through (such an aggregate is null
there, a COUNT 0); blocks are aggregated apart and merged by VALUE.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

# a block's group sums ride float64 bincounts: exact while rows x |value| < 2^53
_MAX_ROWS, _MAX_ABS = 1 << 21, 1 << 31


def _operand(x: Any, params: Dict[str, int]) -> int:
    return int(params[x]) if isinstance(x, str) else int(x)


def _mask(col: np.ndarray, op: str, vals: Sequence[int]) -> np.ndarray:
    if op == "gt":
        return col > vals[0]
    if op == "lt":
        return col < vals[0]
    if op == "eq":
        return col == vals[0]
    if op == "in":
        return np.isin(col, np.asarray(vals, np.int64))
    raise ValueError(f"unknown test {op!r}")


def _conjunction(tests, params, cols, rows: int) -> np.ndarray:
    m = np.ones(rows, bool)
    for test in tests or ():
        m &= _mask(cols[test[0]], test[1], [_operand(x, params) for x in test[2:]])
    return m


def _combine(fn: str, a: Optional[int], b: Optional[int]) -> Optional[int]:
    if a is None or b is None:
        return b if a is None else a
    return a + b if fn in ("count", "sum") else (min(a, b) if fn == "min" else max(a, b))


def _scalar(fn: str, values: Optional[np.ndarray], m: np.ndarray) -> Optional[int]:
    n = int(m.sum())
    if fn == "count":
        return n
    if not n:
        return None
    v = values[m].astype(np.int64)
    return int(v.sum() if fn == "sum" else v.min() if fn == "min" else v.max())


def _grouped(fn: str, values: Optional[np.ndarray], m: np.ndarray, key: np.ndarray, size: int):
    """(per-slot value, per-slot rows that passed `m`) over the dense block key."""
    passed = np.bincount(key[m], minlength=size)
    if fn == "count":
        return passed, passed
    v = values[m].astype(np.int64)
    if fn == "sum":
        if len(m) > _MAX_ROWS or (v.size and int(np.abs(v).max()) >= _MAX_ABS):
            raise ValueError("reference sums blocks of at most 2^21 rows of values under 2^31")
        return np.bincount(key[m], weights=v.astype(np.float64), minlength=size).astype(np.int64), passed
    out = np.full(size, np.iinfo(np.int64).max if fn == "min" else np.iinfo(np.int64).min)
    (np.minimum if fn == "min" else np.maximum).at(out, key[m], v)
    return out, passed


def _pack(keys: np.ndarray, los: Sequence[int], spans: Sequence[int]) -> np.ndarray:
    """Rows of group keys [g, n] -> one int64 a row, most significant first."""
    out = np.zeros(len(keys), np.int64)
    for i, (lo, span) in enumerate(zip(los, spans)):
        out = out * span + (keys[:, i] - lo)
    return out


def partial(spec: Dict[str, Any], params: Dict[str, int], cols: Dict[str, np.ndarray]):
    """One block of rows -> a mergeable partial: [value | None] an aggregate,
    or, for a group-by, (the present groups' keys [g, n], their aggregates
    [g, a], which of those are no null [g, a])."""
    rows = len(next(iter(cols.values())))
    where = _conjunction(spec["where"], params, cols, rows)
    masks = [where & _conjunction(a.get("filter"), params, cols, rows) if a.get("filter") else where
             for a in spec["aggs"]]
    values = [cols[a["col"]] if a.get("col") else None for a in spec["aggs"]]
    if not spec["group_by"]:
        return [_scalar(a["fn"], v, m) for a, v, m in zip(spec["aggs"], values, masks)]
    gcols = np.stack([cols[g].astype(np.int64) for g in spec["group_by"]], axis=1)
    los = gcols.min(axis=0) if rows else np.zeros(gcols.shape[1], np.int64)
    spans = gcols.max(axis=0) - los + 1 if rows else np.ones(gcols.shape[1], np.int64)
    key = _pack(gcols, los, spans)
    size = int(np.prod(spans))
    present = np.flatnonzero(np.bincount(key[where], minlength=size))
    tables = [_grouped(a["fn"], v, m, key, size) for a, v, m in zip(spec["aggs"], values, masks)]
    parts, rest = [], present.copy()
    for lo, span in zip(los[::-1], spans[::-1]):
        parts.append(rest % span + lo)
        rest //= span
    keys = np.stack(parts[::-1], axis=1)
    vals = np.stack([table[present] for table, _ in tables], axis=1)
    has = np.stack([(passed[present] > 0) | (a["fn"] == "count") for a, (_, passed) in zip(spec["aggs"], tables)], axis=1)
    return keys, vals, has


def merge(partials: List[Any], spec: Dict[str, Any]):
    """Partials of all blocks -> the answer: {"aggs": [...]} or {"rows":
    [[group key..., aggregate...]]} in ORDER BY's order, cut at LIMIT.
    Groups of different blocks meet by their VALUES."""
    fns = [a["fn"] for a in spec["aggs"]]
    if not spec["group_by"]:
        out: List[Optional[int]] = [None] * len(fns)
        for p in partials:
            out = [_combine(fn, a, b) for fn, a, b in zip(fns, out, p)]
        return {"aggs": out}
    keys = np.concatenate([p[0] for p in partials])
    vals = np.concatenate([p[1] for p in partials])
    has = np.concatenate([p[2] for p in partials])
    if not len(keys):
        return {"rows": [], "groups": 0}
    los = keys.min(axis=0)
    packed, at = np.unique(_pack(keys, los, keys.max(axis=0) - los + 1), return_inverse=True)
    first = np.full(len(packed), len(keys), np.int64)
    np.minimum.at(first, at, np.arange(len(keys)))
    merged, merged_has = [], []
    for i, fn in enumerate(fns):
        h, v = has[:, i], vals[:, i]
        some = np.bincount(at[h], minlength=len(packed)) > 0
        if fn in ("count", "sum"):
            out = np.zeros(len(packed), np.int64)
            np.add.at(out, at[h], v[h])
        else:
            out = np.full(len(packed), np.iinfo(np.int64).max if fn == "min" else np.iinfo(np.int64).min)
            (np.minimum if fn == "min" else np.maximum).at(out, at[h], v[h])
        merged.append(out)
        merged_has.append(some)
    group_keys = keys[first]
    gb = spec["group_by"]
    by = [gb.index(c) for c, _ in spec["order_by"]] + list(range(len(gb)))
    sign = [1 if d == "asc" else -1 for _, d in spec["order_by"]] + [1] * len(gb)
    order = np.lexsort([s * group_keys[:, i] for i, s in zip(by, sign)][::-1])
    if spec.get("limit") is not None:
        order = order[: int(spec["limit"])]
    rows = [
        [int(k) for k in group_keys[g]] + [int(m[g]) if h[g] else None for m, h in zip(merged, merged_has)]
        for g in order
    ]
    return {"rows": rows, "groups": len(packed)}


def answer(spec, params, blocks) -> Dict[str, Any]:
    return merge([partial(spec, params, b) for b in blocks], spec)


def _as_int(x: Any) -> Optional[int]:
    """A served integer aggregate may arrive as 123 or 123.0, never 123.4 and
    never beyond 2^53 as a float (which could not be exact); null stays null."""
    if x is None:
        return None
    if isinstance(x, bool) or not isinstance(x, (int, float)):
        raise ValueError(f"not a number: {x!r}")
    if isinstance(x, float) and (not x.is_integer() or abs(x) >= 2.0**53):
        raise ValueError(f"not an exact integer: {x!r}")
    return int(x)


def _same(fn: str, got: Optional[int], want: Optional[int]) -> bool:
    """Null where nothing passed; a SUM of nothing may also be served as 0."""
    return got == want or (want is None and fn == "sum" and got == 0)


def compare(spec, columns: List[str], rows: List[List[Any]], ref: Dict[str, Any]) -> Tuple[bool, Dict[str, Any]]:
    """(equal, numbers) of one served result against the reference answer.
    `numbers` holds each quantity compared beside its limit (all exact: 0)."""
    fns = [a["fn"] for a in spec["aggs"]]
    try:
        if "aggs" in ref:
            got = [_as_int(x) for x in rows[0]]
            wrong = sum(1 for fn, g, w in zip(fns, got, ref["aggs"]) if not _same(fn, g, w)) + abs(len(got) - len(fns))
            worst = max((abs(g - w) for g, w in zip(got, ref["aggs"]) if g is not None and w is not None), default=0)
            return wrong == 0 and len(rows) == 1, {"wrong_aggs": wrong, "max_abs_diff": worst, "rows": len(rows), "limit": 0}
        gi = [columns.index(g) for g in spec["group_by"]]
        ai = [i for i in range(len(columns)) if i not in gi]
        got_rows = [[_as_int(r[i]) for i in gi] + [_as_int(r[i]) for i in ai] for r in rows]
    except (ValueError, IndexError, TypeError) as e:
        return False, {"error": str(e), "limit": 0}
    want_rows, n = ref["rows"], len(spec["group_by"])
    got_keys, want_keys = [tuple(r[:n]) for r in got_rows], [tuple(r[:n]) for r in want_rows]
    missing = len(set(want_keys) - set(got_keys))
    extra = len(set(got_keys) - set(want_keys)) + (len(got_keys) - len(set(got_keys)))
    want_by_key = {tuple(r[:n]): r[n:] for r in want_rows}
    wrong, worst = 0, 0
    for r in got_rows:
        want = want_by_key.get(tuple(r[:n]))
        if want is None:
            continue
        if len(r) - n != len(fns) or any(not _same(fn, g, w) for fn, g, w in zip(fns, r[n:], want)):
            wrong += 1
        worst = max([worst] + [abs(g - w) for g, w in zip(r[n:], want) if g is not None and w is not None])
    # the rows' order is part of the answer: ORDER BY names every group column here, so it is total
    disorder = sum(1 for a, b in zip(got_keys, want_keys) if a != b) if not (missing or extra) else 0
    ok = not (missing or extra or wrong or disorder)
    return ok, {"groups": ref["groups"], "rows": len(rows), "missing": missing, "extra": extra, "wrong_aggs": wrong,
                "max_abs_diff": worst, "out_of_order": disorder, "limit": 0}
