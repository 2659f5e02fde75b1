"""What a query NEEDS from the chip over a table cut by the calendar, a
segment a run of whole months and no two segments of the same rows
(lib/datagen/ssb_flat_bymonth.py): the bytes of the columns it names over
ONLY the TRUE rows whose day satisfies its date terms, counted as
lib/prunecount.py counts them, from the request's parameters, the
configuration and the generator's calendar and row counts — not from the
program's pruner, its plans, its spans or the rows it padded its segments to:
a padded row is no needed work, so padding reads as a lower share.

Rows: a segment's days are uniform over its months' days, so the rows whose
day satisfies the date terms are, in expectation, the segment's OWN rows
(`segment_row_counts`) x the satisfying days among its days over its days.
A template without a date term needs every row.  Bytes and operations as in
lib/prunecount.py: a date attribute in the lane of the values ITS SEGMENT
holds, every other column in its configured lane, + 8 B a slot.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from lib import opcount, prunecount
from lib.datagen import ssb_flat_bymonth as gen


def segment_shares(config: Dict[str, Any], mask: np.ndarray) -> List[float]:
    """A segment: the share of its rows whose day is in `mask` (expected)."""
    out = []
    for i in range(gen.num_segments(config)):
        first, last = gen.segment_days(config, i)
        out.append(float(mask[first:last].sum()) / (last - first))
    return out


def segment_date_widths(config: Dict[str, Any], index: int, cal: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Bytes a row of each date attribute in segment `index`: the lane of
    the distinct values its days hold."""
    first, last = gen.segment_days(config, index)
    return {name: opcount.lane_bits(int(np.unique(per_day[first:last]).size)) / 8.0 for name, per_day in cal.items()}


def query_needs(config: Dict[str, Any], template: Dict[str, Any], params: Dict[str, int]) -> Dict[str, float]:
    """opcount.query_needs for ONE request over the month-cut table."""
    ref = template["reference"]
    cal = gen.calendar()
    named = {t[0] for t in ref["where"]} | set(ref["group_by"]) | set(ref["sum"][1:])
    widths = opcount.column_bytes_per_row(config)
    packed = bool(config.get("packed_codes"))
    counts = gen.segment_row_counts(config)
    ops_per_row = len(ref["where"]) + 2 * len(ref["group_by"]) + (len(ref["sum"]) - 1) + 1
    total_rows = total_bytes = 0.0
    for i, share in enumerate(segment_shares(config, prunecount.matching_days(ref, params, cal))):
        if share <= 0.0:
            continue
        mine = dict(widths, **segment_date_widths(config, i, cal)) if packed else widths
        n = share * counts[i]
        total_rows += n
        total_bytes += n * sum(mine[c] for c in named)
    return {
        "bytes": total_bytes + 8.0 * float(template.get("group_space", 1)),
        "ops": total_rows * ops_per_row,
        "rows": total_rows,
    }
