"""Plain numpy reference: WHERE (a conjunction of column tests) -> GROUP BY ->
per group an approximate distinct count, an approximate percentile or a SUM ->
ORDER BY the group columns.  float64 and int64, no kernels, no batching.
Knows nothing of pinot_tpu.

spec: {"where": [[column, "eq" | "in", operand...]], "group_by": [columns],
       "aggs": [{"fn": "hll", "col": column, "log2m": 12}
                | {"fn": "percentile", "col": column, "rank": 95, "bins": 2048}
                | {"fn": "sum", "col": column}],
       "order_by": [[column, "asc"]]}
An operand is the name of one of the request's parameters, or an integer
written in the file.  Aggregates are served in the order of `aggs`, after the
group columns are taken out by name.

`answer` gives, a group and aggregate:
 (a) the EXACT value: the distinct count (numpy's unique over every block's
     rows), the percentile by nearest rank (the value of rank ceil(p x n / 100)
     among the group's n sorted values), the sum;
 (b) the configuration's stated sketch built plainly from the rows
     (configs/ssb_flat_sf10_sketch.json, guarantees.sketch): for "hll" its own
     hash, its own registers a block, blocks merged by max, its own
     estimator; for "percentile" its own histogram over the table's [min,
     max], a value's bin found in float32 as the configuration states it.

`compare` holds a served answer to:
 - the group columns and every SUM: equal, limit 0;
 - an HLL count: EQUAL to (b), limit 0 (a row lost, counted twice, or counted
   into another group moves a register; so does another hash, another log2m
   or another estimator), and within HLL_SIGMAS x 1.04 / sqrt(m) of (a) in
   every group (5 standard errors of HyperLogLog's own law: 8.125 % at m =
   4,096; a sketch of fewer registers or of a narrower hash has a wider law
   and fails it somewhere among 175 groups), or within HLL_SMALL of it where
   that is more: under about 100 values a group the error is a handful of
   bucket collisions, Poisson's law and not HyperLogLog's, and one collision
   among 10 values is already 10 %.  Only a toy table has such groups: at
   the configuration's size the smallest group holds about 11,900 customers
   and the relative limit (967 there) alone decides;
 - a percentile: EQUAL to (b) to float64's last digits (PCT_SKETCH_REL: the
   two walk the same counts by the same formula; a row lost, a value binned
   elsewhere than float32 arithmetic bins it, fewer bins or another range
   move it), and within ONE BIN WIDTH of (a), (max - min) / bins over the
   TABLE's min and max of the column.  Why that bound: the configuration
   answers a percentile from an equi-width histogram of `bins` bins over the
   table's [min, max], interpolated in the bin that holds the rank; the value
   of that rank lies in the same bin, so the two are at most one width
   apart.  PCT_WIDTHS is 1.001 and not 1: the bin of a value is found in
   float32, so a value within 2048 x 2^-23 < 0.001 of a width of an edge may
   be counted next door, and the served value then lies up to that much past
   the width.  A histogram of fewer bins, or one over another range, can
   pass THIS bound by luck (large groups interpolate well); it does not pass
   the comparison with (b).
Its numbers hold the largest relative HLL error and the largest percentile
error, in bin widths, that it found.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

HLL_SIGMAS = 5.0
HLL_SMALL = 8  # see above: what a count of under ~100 may differ by
PCT_WIDTHS = 1.001
PCT_SKETCH_REL = 1e-9


def _operand(x: Any, params: Dict[str, int]) -> int:
    return int(params[x]) if isinstance(x, str) else int(x)


def _where(tests, params, cols, rows: int) -> np.ndarray:
    m = np.ones(rows, bool)
    for test in tests:
        vals = [_operand(x, params) for x in test[2:]]
        if test[1] == "eq":
            m &= cols[test[0]] == vals[0]
        elif test[1] == "in":
            m &= np.isin(cols[test[0]], np.asarray(vals, np.int64))
        else:
            raise ValueError(f"unknown test {test[1]!r}")
    return m


# -- the configuration's HyperLogLog, plainly ---------------------------------
def hash32(values: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer (fmix32) of an INT value's two's-complement
    32-bit word, in uint64 arithmetic cut back to 32 bits after every
    multiply."""
    mask = np.uint64(0xFFFFFFFF)
    h = values.astype(np.int64).view(np.uint64) & mask
    h ^= h >> np.uint64(16)
    h = (h * np.uint64(0x85EBCA6B)) & mask
    h ^= h >> np.uint64(13)
    h = (h * np.uint64(0xC2B2AE35)) & mask
    h ^= h >> np.uint64(16)
    return h


def bucket_rho(values: np.ndarray, log2m: int) -> Tuple[np.ndarray, np.ndarray]:
    """bucket = the hash's low log2m bits; rho = the place of the first 1 bit
    among the other 32 - log2m, counted from their top, and 32 - log2m + 1
    where they are all 0."""
    h = hash32(values)
    bucket = (h & np.uint64((1 << log2m) - 1)).astype(np.int64)
    w = h >> np.uint64(log2m)
    bit_length = np.zeros(len(w), np.int64)
    nz = w > 0
    bit_length[nz] = np.floor(np.log2(w[nz].astype(np.float64))).astype(np.int64) + 1  # w < 2^20: a double holds it
    return bucket, 32 - log2m + 1 - bit_length


def registers(values: np.ndarray, group: np.ndarray, groups: int, log2m: int) -> np.ndarray:
    """int64[groups, m]: a register holds the largest rho of the rows of its
    (group, bucket), 0 where there is none."""
    m = 1 << log2m
    bucket, rho = bucket_rho(values, log2m)
    # the largest rho a cell: sort (cell, rho) as one integer, keep each cell's last
    packed = np.unique((group * m + bucket) * 64 + rho)
    cell = packed >> 6
    last = np.append(cell[1:] != cell[:-1], True)
    regs = np.zeros(groups * m, np.int64)
    regs[cell[last]] = packed[last] & 63
    return regs.reshape(groups, m)


def estimate(regs: np.ndarray) -> np.ndarray:
    """HyperLogLog's estimator (Flajolet et al. 2007) a row of registers:
    alpha_m x m^2 / sum(2^-register), alpha_m = 0.7213 / (1 + 1.079 / m);
    where that is at most 2.5 m and a register is 0, linear counting,
    m x ln(m / zeros); rounded to the nearest integer (half to even).  No
    large-range correction: a 32-bit hash and counts far under 2^32 / 30."""
    regs = np.asarray(regs, np.float64)
    m = regs.shape[-1]
    raw = 0.7213 / (1 + 1.079 / m) * m * m / np.sum(np.exp2(-regs), axis=-1)
    zeros = np.sum(regs == 0, axis=-1)
    linear = m * np.log(m / np.maximum(zeros, 1))
    return np.rint(np.where((raw <= 2.5 * m) & (zeros > 0), linear, raw)).astype(np.int64)


# -- the configuration's percentile sketch, plainly ------------------------------
def histogram_percentile(values: np.ndarray, lo: float, hi: float, bins: int, rank: float) -> float:
    """The stated sketch: an equi-width histogram of `bins` bins over [lo,
    hi], a value's bin floor((v - lo) x scale) in float32 with the scale
    bins / (hi - lo) rounded to float32 once, the bin that holds the rank
    found by a running count, the value interpolated in it (float64)."""
    width = (hi - lo) / bins
    scale = np.float32(bins / (hi - lo))
    b = np.clip(np.floor((values.astype(np.float32) - np.float32(lo)) * scale).astype(np.int64), 0, bins - 1)
    hist = np.bincount(b, minlength=bins).astype(np.float64)
    target = rank / 100.0 * hist.sum()
    cum = np.cumsum(hist)
    idx = min(int(np.searchsorted(cum, target, side="left")), bins - 1)
    before = cum[idx - 1] if idx else 0.0
    return lo + width * (idx + ((target - before) / hist[idx] if hist[idx] else 0.0))


# -- the answer --------------------------------------------------------------
def _group_index(cols, group_by: Sequence[str], mask: np.ndarray, spans: Sequence[Tuple[int, int]]) -> np.ndarray:
    key = np.zeros(int(mask.sum()), np.int64)
    for name, (lo, span) in zip(group_by, spans):
        key = key * span + (cols[name][mask].astype(np.int64) - lo)
    return key


def answer(spec: Dict[str, Any], params: Dict[str, int], blocks, log2m_less: int = 0, bins_divisor: int = 1) -> Dict[str, Any]:
    """{"rows": [[group key..., {"exact", "sketch"} an aggregate...]], "width":
    {aggregate index: the percentile's bin width}, "groups"}.  `log2m_less`
    and `bins_divisor` build the stated sketches at a lower precision (the
    controls'): with them "sketch" is what a narrower sketch answers."""
    gb = spec["group_by"]
    los = [min(int(b[g].min()) for b in blocks) for g in gb]
    spans = [(lo, max(int(b[g].max()) for b in blocks) - lo + 1) for g, lo in zip(gb, los)]
    size = int(np.prod([s for _, s in spans]))
    present = np.zeros(size, bool)
    per_agg: List[Dict[str, Any]] = []
    for a in spec["aggs"]:
        if a["fn"] == "hll":
            per_agg.append({"pairs": [], "regs": np.zeros((size, 1 << (int(a["log2m"]) - log2m_less)), np.int64)})
        elif a["fn"] == "percentile":
            per_agg.append({"keys": [], "values": [],
                            "lo": min(int(b[a["col"]].min()) for b in blocks),
                            "hi": max(int(b[a["col"]].max()) for b in blocks)})
        elif a["fn"] == "sum":
            per_agg.append({"sum": np.zeros(size, np.int64)})
        else:
            raise ValueError(f"unknown aggregate {a['fn']!r}")
    for cols in blocks:
        rows = len(next(iter(cols.values())))
        mask = _where(spec["where"], params, cols, rows)
        key = _group_index(cols, gb, mask, spans)
        present[np.unique(key)] = True
        for a, acc in zip(spec["aggs"], per_agg):
            v = cols[a["col"]][mask].astype(np.int64)
            if a["fn"] == "hll":
                span = int(v.max()) + 1 if v.size else 1
                acc["pairs"].append((np.unique(key * span + v), span))  # a block's distinct (group, value)
                np.maximum(acc["regs"], registers(v, key, size, int(a["log2m"]) - log2m_less), out=acc["regs"])
            elif a["fn"] == "percentile":
                acc["keys"].append(key)
                acc["values"].append(v)
            else:
                acc["sum"] += np.bincount(key, weights=v.astype(np.float64), minlength=size).astype(np.int64)
    finals: List[Dict[str, np.ndarray]] = []
    widths: Dict[int, float] = {}
    for i, (a, acc) in enumerate(zip(spec["aggs"], per_agg)):
        if a["fn"] == "hll":
            span = max(s for _, s in acc["pairs"])
            pairs = np.unique(np.concatenate([(p // s) * span + p % s for p, s in acc["pairs"]]))
            finals.append({"exact": np.bincount(pairs // span, minlength=size), "sketch": estimate(acc["regs"])})
        elif a["fn"] == "percentile":
            keys, values = np.concatenate(acc["keys"]), np.concatenate(acc["values"])
            order = np.lexsort((values, keys))
            keys, values = keys[order], values[order]
            n = np.bincount(keys, minlength=size)
            start = np.cumsum(n) - n
            rank = -(-int(a["rank"]) * n // 100)  # nearest rank: ceil(p x n / 100), 1-based
            exact = np.where(n > 0, values[np.minimum(start + np.maximum(rank, 1) - 1, len(values) - 1)], 0)
            bins = int(a["bins"]) // bins_divisor
            lo, hi = float(acc["lo"]), float(max(acc["hi"], acc["lo"] + 1))
            sketch = np.asarray([histogram_percentile(values[s : s + k], lo, hi, bins, float(a["rank"])) if k else 0.0
                                 for s, k in zip(start, n)])
            finals.append({"exact": exact, "sketch": sketch})
            widths[i] = (hi - lo) / int(a["bins"])  # the configuration's bound, whatever a control builds
        else:
            finals.append({"exact": acc["sum"], "sketch": acc["sum"]})
    out = []
    for slot in np.flatnonzero(present):  # ascending packed key = ORDER BY the group columns, ascending
        rest, key = int(slot), []
        for lo, span in spans[::-1]:
            key.append(rest % span + lo)
            rest //= span
        out.append(key[::-1] + [{"exact": f["exact"][slot].item(), "sketch": f["sketch"][slot].item()} for f in finals])
    return {"rows": out, "width": widths, "groups": len(out)}


def served_from(ref: Dict[str, Any], spec: Dict[str, Any]) -> Tuple[List[str], List[List[Any]]]:
    """(columns, rows) as a server would send `ref`'s SKETCH values: what a
    control hands to `compare` in a served answer's place."""
    columns = list(spec["group_by"]) + [f"{a['fn']}({a['col']})" for a in spec["aggs"]]
    n = len(spec["group_by"])
    return columns, [r[:n] + [x["sketch"] for x in r[n:]] for r in ref["rows"]]


def compare(spec, columns: List[str], rows: List[List[Any]], ref: Dict[str, Any]) -> Tuple[bool, Dict[str, Any]]:
    """(correct, numbers) of one served result against the reference answer;
    `numbers` holds each quantity compared beside its limit."""
    gb, aggs = spec["group_by"], spec["aggs"]
    n = len(gb)
    hll_limit = max([HLL_SIGMAS * 1.04 / math.sqrt(1 << int(a["log2m"])) for a in aggs if a["fn"] == "hll"], default=None)
    try:
        gi = [columns.index(g) for g in gb]
        ai = [i for i in range(len(columns)) if i not in gi]
        if len(ai) != len(aggs):
            raise ValueError(f"{len(ai)} aggregate columns served, {len(aggs)} asked")
        got = {}
        for r in rows:
            key = tuple(int(r[i]) for i in gi)
            if any(float(r[i]) != key[j] for j, i in enumerate(gi)) or key in got:
                raise ValueError(f"group key {[r[i] for i in gi]!r} is no integer or comes twice")
            got[key] = [r[i] for i in ai]
        got_order = [tuple(int(r[i]) for i in gi) for r in rows]
    except (ValueError, IndexError, TypeError) as e:
        return False, {"error": str(e), "limit": 0}
    want = {tuple(r[:n]): r[n:] for r in ref["rows"]}
    want_order = [tuple(r[:n]) for r in ref["rows"]]
    missing, extra = len(set(want) - set(got)), len(set(got) - set(want))
    sum_diff = hll_diff = hll_far = 0
    hll_rel = pct_widths = pct_vs_sketch = 0.0
    wrong = 0
    for key, served in got.items():
        if key not in want:
            continue
        for i, (a, x, w) in enumerate(zip(aggs, served, want[key])):
            try:
                if x is None or isinstance(x, bool) or not math.isfinite(float(x)):
                    raise ValueError
                if a["fn"] == "percentile":
                    pct_widths = max(pct_widths, abs(float(x) - w["exact"]) / ref["width"][i])
                    pct_vs_sketch = max(pct_vs_sketch, abs(float(x) - w["sketch"]) / max(abs(w["sketch"]), 1.0))
                    continue
                if float(x) != int(x):
                    raise ValueError
                if a["fn"] == "sum":
                    sum_diff = max(sum_diff, abs(int(x) - w["exact"]))
                else:
                    hll_diff = max(hll_diff, abs(int(x) - w["sketch"]))
                    off = abs(int(x) - w["exact"])
                    hll_rel = max(hll_rel, off / max(w["exact"], 1))
                    hll_far += off > max(hll_limit * w["exact"], HLL_SMALL)
            except (ValueError, TypeError):
                wrong += 1
    disorder = sum(1 for a, b in zip(got_order, want_order) if a != b) if not (missing or extra) else 0
    ok = not (missing or extra or wrong or disorder or sum_diff or hll_diff or hll_far) and (
        pct_widths <= PCT_WIDTHS and pct_vs_sketch <= PCT_SKETCH_REL)
    numbers = {"groups": ref["groups"], "rows": len(rows), "missing": missing, "extra": extra, "not_numbers": wrong,
               "out_of_order": disorder, "limit": 0}
    if any(a["fn"] == "sum" for a in aggs):
        numbers.update(sum_max_abs_diff=sum_diff)
    if hll_limit is not None:
        numbers.update(hll_vs_sketch_max_abs_diff=hll_diff, hll_vs_exact_max_rel_err=hll_rel, hll_rel_err_limit=hll_limit,
                       hll_small_count_limit=HLL_SMALL, hll_beyond_both_limits=hll_far,
                       exact_count_min=min(w[i]["exact"] for w in want.values() for i, a in enumerate(aggs) if a["fn"] == "hll"),
                       exact_count_max=max(w[i]["exact"] for w in want.values() for i, a in enumerate(aggs) if a["fn"] == "hll"))
    if ref["width"]:
        numbers.update(pct_vs_exact_max_bin_widths=pct_widths, pct_bin_widths_limit=PCT_WIDTHS,
                       pct_bin_width=next(iter(ref["width"].values())), pct_vs_sketch_max_rel_diff=pct_vs_sketch,
                       pct_vs_sketch_limit=PCT_SKETCH_REL)
    return ok, numbers
