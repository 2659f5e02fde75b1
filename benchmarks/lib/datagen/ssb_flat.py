"""Star Schema Benchmark, flat (pre-joined) form: `lineorder` with the
attributes of `date`, `customer`, `supplier` and `part` that the flight reads.

Rows are made segment by segment from (seed, segment index), so a segment can
be built while the next is drawn and the same seed always gives the same
table.  Keys are uniform, as dbgen's are.  Dimension attributes are integer
codes that keep SSB's hierarchy: a city determines its nation and the nation
its region; a brand determines its category and the category its
manufacturer; a day determines year, month and week.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

FIRST_YEAR, YEARS = 1992, 7
DAYS = 2556  # 1992-01-01 .. 1998-12-30, dbgen's date dimension


def _calendar():
    """Per-day lookup tables for the date attributes."""
    year = np.empty(DAYS, np.int16)
    month = np.empty(DAYS, np.int8)
    week = np.empty(DAYS, np.int8)
    d = 0
    for y in range(FIRST_YEAR, FIRST_YEAR + YEARS):
        leap = y % 4 == 0
        doy = 0
        for m, n in enumerate((31, 29 if leap else 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31)):
            for _ in range(n):
                if d < DAYS:
                    year[d], month[d], week[d] = y, m + 1, doy // 7 + 1
                d += 1
                doy += 1
    return year, month, week


def make_segment(config: Dict, seed: int, index: int, rows: int) -> Dict[str, np.ndarray]:
    """The columns of segment `index`, narrow host dtypes (the reference
    reads these arrays; the builder widens them)."""
    rng = np.random.default_rng([int(seed), int(index)])
    nation_region = np.asarray(config["hierarchy"]["nation_region"], np.int8)
    year, month, week = _calendar()

    day = rng.integers(0, DAYS, rows, dtype=np.int32)
    c_city = rng.integers(0, 250, rows, dtype=np.int16)
    s_city = rng.integers(0, 250, rows, dtype=np.int16)
    brand = rng.integers(0, 1000, rows, dtype=np.int16)
    quantity = rng.integers(1, 51, rows, dtype=np.int8)
    discount = rng.integers(0, 11, rows, dtype=np.int8)
    price = rng.integers(90_000, 200_001, rows, dtype=np.int32)  # cents, p_retailprice's range

    y = year[day]
    m = month[day]
    extended = quantity.astype(np.int32) * price
    c_nation = (c_city // 10).astype(np.int8)
    s_nation = (s_city // 10).astype(np.int8)
    category = (brand // 40).astype(np.int8)
    return {
        "lo_quantity": quantity,
        "lo_discount": discount,
        "lo_extendedprice": extended,
        "lo_revenue": (extended.astype(np.int64) * (100 - discount) // 100).astype(np.int32),
        "lo_supplycost": (price * 6 // 10).astype(np.int32),
        "d_year": y,
        "d_yearmonthnum": y.astype(np.int32) * 100 + m,
        "d_yearmonth": ((y - FIRST_YEAR) * 12 + (m - 1)).astype(np.int8),
        "d_weeknuminyear": week[day],
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
