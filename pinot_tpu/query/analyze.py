"""EXPLAIN ANALYZE: join the static operator tree with measured execution.

Reference parity: Pinot 1.1's `EXPLAIN ANALYZE` (multi-stage) returns the
operator tree annotated with actual stats instead of the planned shape.
Re-design: the query executes normally with tracing forced; the static
EXPLAIN rows (engine._explain) join against the finished span tree by
stage, and the full span tree is appended below the operator rows so
per-server / per-launch timing is visible in the same table.

Stage attribution is approximate by construction — the engine pipelines
launches, so "AGGREGATE time" is the sum of its launch/dispatch spans, not
an exclusive wall-clock slice.  The TRACE rows underneath are the ground
truth; the operator-row ms are the navigation aid.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from pinot_tpu.query.result import ResultTable

ANALYZE_COLUMNS = [
    "Operator",
    "Operator_Id",
    "Parent_Id",
    "Actual_Ms",
    "Rows",
    # bytes the stage's launches had to read (SegmentPlan.scan_bytes)
    "Bytes",
]

# span names carrying the per-launch kernelBytes attr (SSE/server `launch:*`
# spans, the dist engine's `launches` section)
_SCAN_COST_SPANS = ("launch", "launches")

# operator-name prefix -> trace span names whose ms sum to that stage
# (a span matches a candidate by exact name or "<candidate>:" prefix)
_STAGE_SPANS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("BROKER_REDUCE", ("reduce",)),
    ("COMBINE", ("collect", "device_wait", "sparse_merge", "scatter", "realtime")),
    ("AGGREGATE", ("launch", "dispatch", "run", "launches")),
    ("GROUP_BY", ("launch", "dispatch", "run", "launches")),
    ("SELECT", ("launch", "dispatch", "run", "launches")),
    ("PROJECT", ()),
    ("FILTER", ()),
)


def _span_ms_index(trace: Optional[Dict[str, Any]]) -> Dict[str, float]:
    """Total ms per span name over the whole tree (grafted subtrees
    included); names like 'launch:seg_3' also accumulate under 'launch'."""
    out: Dict[str, float] = {}

    def walk(node: Optional[Dict[str, Any]]) -> None:
        if not node:
            return
        name = node.get("name", "")
        ms = float(node.get("ms", 0.0))
        out[name] = out.get(name, 0.0) + ms
        base = name.split(":", 1)[0]
        if base != name:
            out[base] = out.get(base, 0.0) + ms
        for c in node.get("children", ()):
            walk(c)

    walk(trace)
    return out


def _stage_ms(op_name: str, index: Dict[str, float]) -> Optional[float]:
    for prefix, candidates in _STAGE_SPANS:
        if not op_name.startswith(prefix):
            continue
        vals = [index[c] for c in candidates if c in index]
        return round(sum(vals), 3) if vals else None
    return None


def _stage_rows(op_name: str, executed: ResultTable) -> Optional[int]:
    s = executed.stats
    if op_name.startswith("BROKER_REDUCE") or op_name.startswith("SELECT"):
        return len(executed.rows)
    if op_name.startswith(("COMBINE", "AGGREGATE", "GROUP_BY")):
        return s.num_groups if s.num_groups else len(executed.rows)
    if op_name.startswith(("PROJECT", "FILTER")):
        return s.num_docs_scanned
    return None


def _attr_summary(attrs: Dict[str, Any]) -> str:
    parts = [f"{k}={v}" for k, v in attrs.items() if not isinstance(v, (dict, list))]
    return ", ".join(parts)


def _span_bytes_index(trace: Optional[Dict[str, Any]]) -> Dict[str, float]:
    """Per-span-base-name sums of the kernelBytes attr — the cost twin of
    _span_ms_index."""
    bytes_by: Dict[str, float] = {}

    def walk(node: Optional[Dict[str, Any]]) -> None:
        if not node:
            return
        v = node.get("attrs", {}).get("kernelBytes")
        if isinstance(v, (int, float)):
            base = node.get("name", "").split(":", 1)[0]
            bytes_by[base] = bytes_by.get(base, 0.0) + float(v)
        for c in node.get("children", ()):
            walk(c)

    walk(trace)
    return bytes_by


def _stage_bytes(
    op_name: str, executed: ResultTable, bytes_by: Dict[str, float]
) -> Optional[float]:
    """Bytes for one operator row: the scan stage carries its launch spans'
    sum; the root BROKER_REDUCE row the query total from ExecutionStats."""
    if op_name.startswith("BROKER_REDUCE"):
        return executed.stats.kernel_bytes or None
    if op_name.startswith(("AGGREGATE", "GROUP_BY", "SELECT")):
        return sum(bytes_by.get(c, 0.0) for c in _SCAN_COST_SPANS) or None
    return None


def analyze_result(static: ResultTable, executed: ResultTable) -> ResultTable:
    """Static EXPLAIN rows + Actual_Ms/Rows + per-operator Bytes, followed
    by the measured span tree as TRACE(...) rows parented under the operator
    root."""
    index = _span_ms_index(executed.stats.trace)
    bytes_by = _span_bytes_index(executed.stats.trace)
    rows: List[tuple] = []
    for op_name, oid, parent in static.rows:
        rows.append(
            (
                op_name, oid, parent, _stage_ms(op_name, index),
                _stage_rows(op_name, executed), _stage_bytes(op_name, executed, bytes_by),
            )
        )
    next_id = max((r[1] for r in static.rows), default=0) + 1

    def add_span(node: Dict[str, Any], parent_id: int) -> None:
        nonlocal next_id
        oid = next_id
        next_id += 1
        attrs = node.get("attrs", {})
        label = f"TRACE({node.get('name', '?')})"
        summary = _attr_summary(attrs)
        if summary:
            label += f" [{summary}]"
        docs = attrs.get("docs", attrs.get("docsScanned"))
        rows.append(
            (
                label,
                oid,
                parent_id,
                round(float(node.get("ms", 0.0)), 3),
                docs,
                attrs.get("kernelBytes"),
            )
        )
        for c in node.get("children", ()):
            add_span(c, oid)

    if executed.stats.trace:
        add_span(executed.stats.trace, 0)
    return ResultTable(columns=list(ANALYZE_COLUMNS), rows=rows, stats=executed.stats)
