"""Star Schema Benchmark, flat form, CUT BY THE CALENDAR: `ssb_flat_bydate`'s
19 columns, the table pushed a time bucket at a time, so a segment holds what
arrived in its bucket and no two segments hold the same rows.

Segment `i` of `n` (`n` = the configuration's rows / segment_rows, rounded
up) holds the calendar's months [i x 84 // n, (i + 1) x 84 // n) WHOLE: one
month each at n = 84, 21 months each in the rehearsal's n = 4 (the same
table in small).  A row's day is uniform over the date dimension's 2,556
days, as in `ssb_flat_bydate`, so the rows a segment holds are a multinomial
draw of the table's rows over the segments' days (`segment_row_counts`): 28,
29, 30 and 31-day months give ~657k, ~681k, ~704k and ~728k rows at SF10,
+- ~0.8k, and no two of the 84 agree.  The counts are drawn from the
configuration's `cut_seed`, NOT from the run's seed: the cut is the
deployment's, the same in every run, as `segment_rows` is in every other
configuration.  The run's seed draws every value of every row: the day
within the segment's days (uniform; rows in day order), every other column
as `ssb_flat` draws it from (seed, segment index); the date attributes follow
from the day (`ssb_flat_bydate.calendar`).  Knows nothing of pinot_tpu.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Dict, List, Tuple

import numpy as np

from lib.datagen import ssb_flat, ssb_flat_bydate

DAYS = ssb_flat.DAYS
MONTHS = 84
calendar = ssb_flat_bydate.calendar
num_segments = ssb_flat_bydate.num_segments


@lru_cache(maxsize=1)
def month_starts() -> np.ndarray:
    """int[MONTHS + 1]: the first day of each month of the calendar, and DAYS."""
    month_of_day = calendar()["d_yearmonth"].astype(np.int64)
    return np.r_[np.flatnonzero(np.r_[True, month_of_day[1:] != month_of_day[:-1]]), DAYS]


def segment_days(config: Dict, index: int) -> Tuple[int, int]:
    """[first day, last day + 1) of segment `index`: its months, whole."""
    n = num_segments(config)
    starts = month_starts()
    return int(starts[index * MONTHS // n]), int(starts[(index + 1) * MONTHS // n])


@lru_cache(maxsize=8)
def _row_counts(rows: int, n: int, cut_seed: int) -> Tuple[int, ...]:
    starts = month_starts()
    days = np.asarray([starts[(i + 1) * MONTHS // n] - starts[i * MONTHS // n] for i in range(n)], np.float64)
    counts = np.random.default_rng([int(cut_seed), n]).multinomial(rows, days / days.sum())
    assert int(counts.sum()) == rows
    return tuple(int(c) for c in counts)


def segment_row_counts(config: Dict) -> List[int]:
    """The rows of every segment: one multinomial draw of the table's rows
    over the segments' days, from the configuration's `cut_seed`."""
    counts = _row_counts(int(config["rows"]), num_segments(config), int(config["cut_seed"]))
    assert sum(counts) == int(config["rows"])
    return list(counts)


def make_segment(config: Dict, seed: int, index: int, rows: int) -> Dict[str, np.ndarray]:
    """The columns of segment `index`, narrow host dtypes, rows in day order.
    The segment's OWN row count (segment_row_counts): the harness's `rows`
    (its hint `min(segment_rows, ...)`, the table's mean) is not read."""
    rows = segment_row_counts(config)[index]
    rng = np.random.default_rng([int(seed), int(index)])
    nation_region = np.asarray(config["hierarchy"]["nation_region"], np.int8)
    cal = calendar()

    first, last = segment_days(config, index)
    day = rng.integers(first, last, rows, dtype=np.int32)
    day.sort()  # the other columns are drawn independently of the day: sorting it sorts the table
    c_city = rng.integers(0, 250, rows, dtype=np.int16)
    s_city = rng.integers(0, 250, rows, dtype=np.int16)
    brand = rng.integers(0, 1000, rows, dtype=np.int16)
    quantity = rng.integers(1, 51, rows, dtype=np.int8)
    discount = rng.integers(0, 11, rows, dtype=np.int8)
    price = rng.integers(90_000, 200_001, rows, dtype=np.int32)  # cents, p_retailprice's range

    extended = quantity.astype(np.int32) * price
    c_nation = (c_city // 10).astype(np.int8)
    s_nation = (s_city // 10).astype(np.int8)
    category = (brand // 40).astype(np.int8)
    block = {
        "lo_quantity": quantity,
        "lo_discount": discount,
        "lo_extendedprice": extended,
        "lo_revenue": (extended.astype(np.int64) * (100 - discount) // 100).astype(np.int32),
        "lo_supplycost": (price * 6 // 10).astype(np.int32),
        "c_city": c_city,
        "c_nation": c_nation,
        "c_region": nation_region[c_nation],
        "s_city": s_city,
        "s_nation": s_nation,
        "s_region": nation_region[s_nation],
        "p_mfgr": (category // 5).astype(np.int8),
        "p_category": category,
        "p_brand1": brand,
    }
    block.update({name: per_day[day] for name, per_day in cal.items()})
    return block
