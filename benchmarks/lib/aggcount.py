"""What a query of the reference kind `filter_group_aggs` NEEDS from the
chip: the bytes it has to read and the operations it has to do, counted from
the configuration and the template, as lib/opcount.py counts the kind
`filter_group_sum`: not from the program's plan, and the same whatever
implements the query.

Bytes: every row of every column the query names (WHERE, each aggregate and
its FILTER, GROUP BY), at the width the column has on the device: a column
that declares a `cardinality` rides in the packed lane of that cardinality
(lib/opcount.lane_bits; its dictionary's values, a few KB a segment, are not
counted), any other at its type's width (INT 4 bytes, LONG 8); plus the
group table written once (8 bytes a slot of the template's `group_space`).
Operations: per row one test per WHERE term and per FILTER term, one
multiply-add per group column to form the key, and one accumulate per
aggregate.
"""
from __future__ import annotations

from typing import Any, Dict, Set

from lib import opcount

_TYPE_BYTES = {"INT": 4.0, "LONG": 8.0}


def column_bytes_per_row(config: Dict[str, Any]) -> Dict[str, float]:
    out = {}
    for c in config["columns"]:
        if "cardinality" in c and config.get("packed_codes"):
            out[c["name"]] = opcount.lane_bits(int(c["cardinality"])) / 8.0
        else:
            out[c["name"]] = _TYPE_BYTES[c["type"]]
    return out


def named_columns(ref: Dict[str, Any]) -> Set[str]:
    named = {t[0] for t in ref["where"]} | set(ref["group_by"])
    for a in ref["aggs"]:
        if a.get("col"):
            named.add(a["col"])
        named |= {t[0] for t in a.get("filter") or ()}
    return named


def query_needs(config: Dict[str, Any], template: Dict[str, Any]) -> Dict[str, float]:
    ref = template["reference"]
    widths = column_bytes_per_row(config)
    rows = float(config["rows"])
    bytes_per_row = sum(widths[c] for c in named_columns(ref))
    tests = len(ref["where"]) + sum(len(a.get("filter") or ()) for a in ref["aggs"])
    ops_per_row = tests + 2 * len(ref["group_by"]) + len(ref["aggs"])
    return {
        "bytes": rows * bytes_per_row + 8.0 * float(template.get("group_space", 1)),
        "ops": rows * ops_per_row,
        "bytes_per_row": bytes_per_row,
    }
