"""What a query NEEDS from the chip over a table cut by time: the bytes of
the columns it names over ONLY the rows whose day satisfies its date terms,
counted from the request's parameters, the configuration and the generator's
calendar (lib/datagen/ssb_flat_bydate.py) — not from the program's pruner,
its plans or its spans.

Rows: segment i holds the calendar's slice [i x 2556 / n, (i + 1) x 2556 / n)
with its rows uniform over it, so the rows of a segment whose day satisfies
the date terms are, in expectation, the segment's rows x the measure of the
satisfying days inside the slice over the slice's width (a boundary day
counts by the part of it inside).  A template without a date term needs every
row.  Bytes: a row's named columns at the width they have on the device
(lib/opcount.py): a date attribute rides in the lane of the values ITS
SEGMENT holds (a segment's dictionary is its own: ~64 order days, one or two
years), the least a store of per-segment dictionaries can hold it in, every
other column in its configured lane; plus the group table written once, 8
bytes a slot.  Operations as in lib/opcount.py, per counted row.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from lib import opcount
from lib.datagen import ssb_flat_bydate as gen
from lib.references import filter_group_sum


def matching_days(ref: Dict[str, Any], params: Dict[str, int], cal: Dict[str, np.ndarray]) -> np.ndarray:
    """bool[DAYS]: the days that satisfy every date term of the template."""
    mask = np.ones(gen.DAYS, bool)
    for test in ref["where"]:
        if test[0] in cal:
            mask &= filter_group_sum._mask(cal[test[0]], test[1], [params[p] for p in test[2:]])
    return mask


def slice_days(config: Dict[str, Any], index: int) -> np.ndarray:
    """The days segment `index`'s slice of the calendar touches."""
    lo, hi = gen.segment_slice(config, index)
    return np.arange(int(np.floor(lo)), min(int(np.ceil(hi)), gen.DAYS))


def segment_shares(config: Dict[str, Any], mask: np.ndarray) -> List[float]:
    """A segment: the share of its rows whose day is in `mask` (expected)."""
    out = []
    for i in range(gen.num_segments(config)):
        lo, hi = gen.segment_slice(config, i)
        days = slice_days(config, i)
        inside = np.minimum(days + 1.0, hi) - np.maximum(days, lo)  # the part of each day the slice holds
        out.append(float((inside * mask[days]).sum() / (hi - lo)))
    return out


def segment_date_widths(config: Dict[str, Any], index: int, cal: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Bytes a row of each date attribute in segment `index`: the lane of
    the distinct values its slice holds."""
    days = slice_days(config, index)
    return {name: opcount.lane_bits(int(np.unique(per_day[days]).size)) / 8.0 for name, per_day in cal.items()}


def query_needs(config: Dict[str, Any], template: Dict[str, Any], params: Dict[str, int]) -> Dict[str, float]:
    """opcount.query_needs for ONE request over the time-cut table."""
    ref = template["reference"]
    cal = gen.calendar()
    named = {t[0] for t in ref["where"]} | set(ref["group_by"]) | set(ref["sum"][1:])
    widths = opcount.column_bytes_per_row(config)
    packed = bool(config.get("packed_codes"))
    rows = int(config["rows"])
    seg_rows = int(config["segment_rows"])
    ops_per_row = len(ref["where"]) + 2 * len(ref["group_by"]) + (len(ref["sum"]) - 1) + 1
    total_rows = total_bytes = 0.0
    for i, share in enumerate(segment_shares(config, matching_days(ref, params, cal))):
        if share <= 0.0:
            continue
        mine = dict(widths, **segment_date_widths(config, i, cal)) if packed else widths
        n = share * min(seg_rows, rows - i * seg_rows)
        total_rows += n
        total_bytes += n * sum(mine[c] for c in named)
    return {
        "bytes": total_bytes + 8.0 * float(template.get("group_space", 1)),
        "ops": total_rows * ops_per_row,
        "rows": total_rows,
    }
