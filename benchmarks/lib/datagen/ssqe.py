"""The table of upstream's `pinot-perf` `BenchmarkQueriesSSQE`: `MyTable`,
every segment drawn apart, so every segment has dictionaries of its own.

A value is `(long)(-ln(U) / lambda)` (the benchmark's `_scenario`, EXP(lambda):
the configuration's `exp_lambda`), one supplier feeding `INT_COL`,
`NO_INDEX_INT_COL`, `RAW_INT_COL` and the string key of a row in turn.  The
two STRING dimensions ride as their integer codes (the configuration's
`dimension_encoding`): `NO_INDEX_STRING_COL` is the EXP draw that looks the
row's UUID up, `LOW_CARDINALITY_STRING_COL` the k of "value" + k.  Rows are
made segment by segment from (seed, segment index); numpy's generator stands
in for `java.util.Random` (the configuration's `assumed`).  Knows nothing of
pinot_tpu.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

LOW_CARDINALITY = 10
TSTMP_STEP_MS = 1200 * 1000


def make_segment(config: Dict, seed: int, index: int, rows: int) -> Dict[str, np.ndarray]:
    """The columns of segment `index`, narrow host dtypes (the reference
    reads these arrays; the builder widens them)."""
    rng = np.random.default_rng([int(seed), int(index)])
    # a row's four draws are consecutive, as the one supplier hands them out
    draws = (rng.standard_exponential((rows, 4)) / float(config["exp_lambda"])).astype(np.int32)
    ordinal = np.arange(rows, dtype=np.int32)  # the row's place in ITS segment
    return {
        "SORTED_COL": ordinal,
        "INT_COL": draws[:, 0].copy(),
        "NO_INDEX_INT_COL": draws[:, 1].copy(),
        "RAW_INT_COL": draws[:, 2].copy(),
        "NO_INDEX_STRING_COL": draws[:, 3].copy(),
        "LOW_CARDINALITY_STRING_COL": (ordinal % LOW_CARDINALITY).astype(np.int8),
        "TSTMP_COL": ordinal.astype(np.int64) * TSTMP_STEP_MS,
    }
