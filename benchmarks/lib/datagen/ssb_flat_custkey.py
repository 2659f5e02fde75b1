"""Star Schema Benchmark, flat form, with the customer KEY: `ssb_flat`'s 18
columns and `lo_custkey`, and a customer that determines its city.

SSB's `customer` table has 30,000 x SF rows in which `c_custkey` determines
`c_city`, `c_nation` and `c_region`; dbgen draws an order's customer
uniformly.  So a row's `lo_custkey` is drawn uniformly over the
configuration's `customers`, its `c_city` is read from a table of that many
customers whose cities are drawn uniformly from the seed (one table a seed,
the same for every segment), and `c_nation` / `c_region` follow from the city
as in `ssb_flat`.  Every other column is `ssb_flat`'s own draw, segment by
segment from (seed, segment index).  A segment of 1.5M rows over 300,000
customers lacks about 300,000 x e^-5 ~ 2,000 of them, other ones in every
segment: no two segments share a `lo_custkey` dictionary.  Knows nothing of
pinot_tpu.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from lib.datagen import ssb_flat

_CUSTOMER_STREAM = 0xC057  # what tells this generator's own draws from ssb_flat's


def customer_cities(config: Dict, seed: int) -> np.ndarray:
    """c_city of every customer: the seed's `customer` table."""
    rng = np.random.default_rng([int(seed), _CUSTOMER_STREAM])
    return rng.integers(0, 250, int(config["customers"]), dtype=np.int16)


def make_segment(config: Dict, seed: int, index: int, rows: int) -> Dict[str, np.ndarray]:
    """The columns of segment `index`, narrow host dtypes (the reference
    reads these arrays; the builder widens them)."""
    block = ssb_flat.make_segment(config, seed, index, rows)
    rng = np.random.default_rng([int(seed), int(index), _CUSTOMER_STREAM])
    custkey = rng.integers(0, int(config["customers"]), rows, dtype=np.int32)
    nation_region = np.asarray(config["hierarchy"]["nation_region"], np.int8)
    c_city = customer_cities(config, seed)[custkey]
    c_nation = (c_city // 10).astype(np.int8)
    block.update(lo_custkey=custkey, c_city=c_city, c_nation=c_nation, c_region=nation_region[c_nation])
    return block
