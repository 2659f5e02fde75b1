"""What a query shape NEEDS from the chip: the bytes it has to read and the
operations it has to do, counted from the configuration and the template —
not from the program's plan, its cost model or XLA's cost_analysis().

Bytes: every row of every column the query names, at the width the column
has on the device (a dictionary column rides in 4/8/16-bit lanes by its
cardinality, a raw INT metric in 4 bytes), plus the group table written once
(8 bytes a slot).  Operations: per row one test per WHERE term, one
multiply-add per group column to form the key, the value expression and one
accumulate.  The least time is the larger of bytes / peak bytes/s and
operations / peak op/s; for every SSB template that is the bytes.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple


def lane_bits(cardinality: int) -> int:
    """The packed lane a dictionary column of this cardinality rides in."""
    for bits in (4, 8, 16):
        if cardinality <= (1 << bits):
            return bits
    return 32


def column_bytes_per_row(config: Dict[str, Any]) -> Dict[str, float]:
    out = {}
    for c in config["columns"]:
        if c["role"] == "METRIC" or "cardinality" not in c:
            out[c["name"]] = 4.0
        else:
            out[c["name"]] = lane_bits(int(c["cardinality"])) / 8.0 if config.get("packed_codes") else 4.0
    return out


def query_needs(config: Dict[str, Any], template: Dict[str, Any]) -> Dict[str, float]:
    ref = template["reference"]
    named = {t[0] for t in ref["where"]} | set(ref["group_by"]) | set(ref["sum"][1:])
    widths = column_bytes_per_row(config)
    rows = float(config["rows"])
    groups = float(template.get("group_space", 1))
    ops_per_row = len(ref["where"]) + 2 * len(ref["group_by"]) + (len(ref["sum"]) - 1) + 1
    return {
        "bytes": rows * sum(widths[c] for c in named) + 8.0 * groups,
        "ops": rows * ops_per_row,
        "bytes_per_row": sum(widths[c] for c in named),
    }


def least_seconds(needs: Dict[str, float], peak: Dict[str, Any]) -> Tuple[float, str]:
    """(least time on this device, which peak bounds it)."""
    t_mem = needs["bytes"] / float(peak["hbm_bytes_per_s"])
    t_ops = needs["ops"] / float(peak["flops_per_s"])
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "flops")
