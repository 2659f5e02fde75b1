"""Star Schema Benchmark, flat form, INGESTED IN TIME ORDER: `ssb_flat`'s 18
columns and `lo_orderdate`, the table sorted by order date and cut every
`segment_rows` rows.

`ssb_flat` draws a row's day uniformly over the whole calendar in every
segment; no table in the field looks like that.  Here segment `i` of `n`
(`n` = the configuration's rows / segment_rows, rounded up) draws its days
uniformly over the calendar's slice [i x 2556 / n, (i + 1) x 2556 / n) (a
real-valued point of the slice, floored: a boundary day is shared by the two
segments in proportion) and its rows are in day order, as a batch job over
time-sorted input leaves them.  Over the whole table a day still holds
rows / 2556 rows in expectation, as in `ssb_flat`.  Every other column is
`ssb_flat`'s own draw (the same ranges, from (seed, segment index)); the date
attributes follow from the day.  `lo_orderdate` is the day as yyyymmdd.
Knows nothing of pinot_tpu.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from lib.datagen import ssb_flat

DAYS = ssb_flat.DAYS


def calendar() -> Dict[str, np.ndarray]:
    """The date attributes of every day of the date dimension, by column
    name (what `make_segment` reads them from, and lib/prunecount.py)."""
    year, month, week = ssb_flat._calendar()
    y = year.astype(np.int32)
    first = np.r_[True, (y[1:] != y[:-1]) | (month[1:] != month[:-1])]  # a month's first day
    starts = np.flatnonzero(first)
    dom = np.arange(DAYS) - starts[np.cumsum(first) - 1] + 1
    return {
        "d_year": year,
        "d_yearmonthnum": y * 100 + month,
        "d_yearmonth": ((y - ssb_flat.FIRST_YEAR) * 12 + (month - 1)).astype(np.int8),
        "d_weeknuminyear": week,
        "lo_orderdate": (y * 10000 + month.astype(np.int32) * 100 + dom).astype(np.int32),
    }


def num_segments(config: Dict) -> int:
    return -(-int(config["rows"]) // int(config["segment_rows"]))


def segment_slice(config: Dict, index: int) -> Tuple[float, float]:
    """[lo, hi) of segment `index` on the calendar's day axis (real-valued)."""
    n = num_segments(config)
    return index * DAYS / n, (index + 1) * DAYS / n


def make_segment(config: Dict, seed: int, index: int, rows: int) -> Dict[str, np.ndarray]:
    """The columns of segment `index`, narrow host dtypes, rows in day order."""
    rng = np.random.default_rng([int(seed), int(index)])
    nation_region = np.asarray(config["hierarchy"]["nation_region"], np.int8)
    cal = calendar()

    lo, hi = segment_slice(config, index)
    day = np.minimum((lo + rng.random(rows) * (hi - lo)).astype(np.int32), DAYS - 1)
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
