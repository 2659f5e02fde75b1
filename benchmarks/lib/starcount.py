"""What a query NEEDS from the chip where a star-tree serves it: the rows of
the tree's level, counted from the configuration and the generator's own rows
— not from the program's index, its plan or its spans.

Pinot's rule (docs, Star-Tree Index): a tree serves a query whose filter and
group-by columns are all among the tree's `dimensionsSplitOrder` and whose
every aggregation is one of its `functionColumnPairs`; it then reads one
pre-aggregated record a distinct combination of the dimensions down to the
last one the query names (the prefix of the split order).  Of the trees that
serve a template the one with the fewest such combinations is counted.  The
combinations are counted in one segment of the generator's rows (a packed key
and numpy's unique) and taken for every segment: keys are uniform and drawn
the same way whatever the seed, and at 1.5M rows a segment every combination
of both prefixes of `ssb_flat_sf10_startree` occurs (35,000 and 4,375), so
the count is the deployment's for every seed.

Bytes: a level's row holds the prefix's dimensions, at the width the column
has on the device (lib/opcount.py), and 8 bytes a pre-aggregated field the
query sums (a level's sums pass 32 bits); the group table is written once
(8 bytes a slot), as in lib/opcount.py.  Operations: as there, per level row.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from lib import opcount, plugins

PROBE_SEED = 0  # see above: the count does not depend on it
FIELD_BYTES = 8.0


def serving_prefix(config: Dict[str, Any], template: Dict[str, Any]) -> Optional[List[str]]:
    """The dimensions of the level that serves `template`, or None where no
    tree of the configuration does (an expression, a column outside every
    split order, a pair no tree has)."""
    ref = template["reference"]
    if ref["sum"][0] != "col":
        return None
    named = {t[0] for t in ref["where"]} | set(ref["group_by"])
    best = None
    for tree in config.get("table_config", {}).get("starTreeIndexConfigs", []):
        order = list(tree["dimensionsSplitOrder"])
        if not named <= set(order) or f"SUM__{ref['sum'][1]}" not in tree["functionColumnPairs"]:
            continue
        prefix = order[: max(order.index(c) for c in named) + 1] if named else []
        if best is None or len(prefix) < len(best):
            best = prefix
    return best


def combinations_a_segment(config: Dict[str, Any], prefix: List[str]) -> int:
    """Distinct combinations of `prefix` in one segment of the generator's rows."""
    gen = plugins.load_module("datagen", config["datagen"])
    n = min(int(config["segment_rows"]), int(config["rows"]))
    block = gen.make_segment(config, PROBE_SEED, 0, n)
    key = np.zeros(n, np.int64)
    for name in prefix:
        col = block[name].astype(np.int64)
        key = key * (int(col.max()) + 1) + col
    return int(np.unique(key).size)


def query_needs(config: Dict[str, Any], template: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """opcount.query_needs for a tree-served template: over the level's rows
    of every segment, not the table's; None where no tree serves it."""
    prefix = serving_prefix(config, template)
    if prefix is None:
        return None
    ref = template["reference"]
    segments = -(-int(config["rows"]) // int(config["segment_rows"]))
    rows = float(segments * combinations_a_segment(config, prefix))
    widths = opcount.column_bytes_per_row(config)
    named = {t[0] for t in ref["where"]} | set(ref["group_by"])
    bytes_per_row = sum(widths[c] for c in named) + FIELD_BYTES * (len(ref["sum"]) - 1)
    ops_per_row = len(ref["where"]) + 2 * len(ref["group_by"]) + (len(ref["sum"]) - 1) + 1
    return {
        "bytes": rows * bytes_per_row + 8.0 * float(template.get("group_space", 1)),
        "ops": rows * ops_per_row,
        "bytes_per_row": bytes_per_row,
        "rows": rows,
    }
