"""What a query of the reference kind `filter_group_sketch` NEEDS from the
chip: the bytes it has to read and write and the operations it has to do,
counted from the configuration and the template, as lib/opcount.py counts the
kind `filter_group_sum` and lib/aggcount.py the kind `filter_group_aggs`: not
from the program's plan, and the same whatever implements the query.

Bytes: every row of every column the query names (WHERE, GROUP BY, each
aggregate), read once at the width the column is stored in on the device (a
column that declares a `cardinality` rides in the packed lane of that
cardinality, 32 bits past 2^16: lib/opcount.lane_bits; any other at its
type's width), plus each aggregate's table written once: a HyperLogLog
register is one byte (it holds at most 32 - log2m + 1), a histogram's bin a
4-byte count, a SUM's slot 8 bytes, a slot of the key space its 8 bytes of
presence as in lib/opcount.py.  A dictionary's values (300,000 x 4 B a
segment here, read once by 1.5M codes) are not counted: the least a query
needs is the values of its rows, however they are stored.
Operations, a row: one test per WHERE term, one multiply-add per group column
to form the key, and per aggregate: HLL, the hash's 8 (three shift-xors, two
multiplies), 3 to cut bucket and rho, 1 to form the cell, 1 max; a
percentile, 3 to find the bin (subtract, multiply, floor), 1 to form the
cell, 1 add; a SUM, 1 add.
"""
from __future__ import annotations

from typing import Any, Dict, Set

from lib import aggcount

_CELL_BYTES = {"hll": 1.0, "percentile": 4.0, "sum": 8.0}
_ROW_OPS = {"hll": 13.0, "percentile": 5.0, "sum": 1.0}


def named_columns(ref: Dict[str, Any]) -> Set[str]:
    return {t[0] for t in ref["where"]} | set(ref["group_by"]) | {a["col"] for a in ref["aggs"]}


def table_cells(agg: Dict[str, Any], group_space: int) -> float:
    """Cells of one aggregate's table over `group_space` slots."""
    width = 1 << int(agg["log2m"]) if agg["fn"] == "hll" else int(agg["bins"]) if agg["fn"] == "percentile" else 1
    return float(group_space) * width


def query_needs(config: Dict[str, Any], template: Dict[str, Any]) -> Dict[str, float]:
    ref = template["reference"]
    widths = aggcount.column_bytes_per_row(config)
    rows = float(config["rows"])
    slots = int(template.get("group_space", 1))
    bytes_per_row = sum(widths[c] for c in named_columns(ref))
    table_bytes = 8.0 * slots + sum(_CELL_BYTES[a["fn"]] * table_cells(a, slots) for a in ref["aggs"])
    ops_per_row = len(ref["where"]) + 2 * len(ref["group_by"]) + sum(_ROW_OPS[a["fn"]] for a in ref["aggs"])
    return {
        "bytes": rows * bytes_per_row + table_bytes,
        "ops": rows * ops_per_row,
        "bytes_per_row": bytes_per_row,
        "table_bytes": table_bytes,
    }
