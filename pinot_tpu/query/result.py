"""Result containers flowing segment -> combine -> broker reduce.

Reference parity: per-segment result blocks + the DataTable payload
(IntermediateResultsBlock / DataTableImplV4, SURVEY.md 2.2).  Re-design:
results stay columnar numpy end-to-end; "serialization" only exists at the
client boundary (JSON), since combine happens via collectives/arrays, not
sockets.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


@dataclass
class ExecutionStats:
    """Per-query execution statistics (ExecutionStatistics /
    BrokerResponse stats analog)."""

    num_segments_queried: int = 0
    num_segments_pruned: int = 0
    num_segments_processed: int = 0
    num_docs_scanned: int = 0
    total_docs: int = 0
    num_groups: int = 0
    # group tables the reduce merged by VALUE (a hash merge over decoded
    # keys) because their key spaces differ; 0 where they aligned or one came
    tables_merged_by_value: int = 0
    time_ms: float = 0.0
    # scatter-gather fault surface (BrokerResponse partialResult /
    # processingExceptions / numServersQueried|Responded analog): a query
    # that lost segments but was allowed to degrade carries
    # partial_result=True plus one exception entry per absorbed failure
    num_servers_queried: int = 0
    num_servers_responded: int = 0
    partial_result: bool = False
    exceptions: List[Dict[str, Any]] = field(default_factory=list)
    # (column, "sorted"|"range"|"inverted") per index-accelerated predicate —
    # proof that the filter read bitmap/doc-range rows instead of scanning
    # codes (BitmapBasedFilterOperator analog; see query/filter.py)
    filter_index_uses: Tuple = ()
    # span tree dict when the query ran with trace=true (utils/metrics.Trace)
    trace: Optional[dict] = None
    # broker/engine-minted request id (RequestContext requestId analog)
    query_id: Optional[str] = None
    # bytes the launches of this query had to read (SegmentPlan.scan_bytes,
    # summed over every launch), and the trace + compile wall time THIS
    # query paid (0 where every program had run on its device before)
    kernel_bytes: float = 0.0
    compile_ms: float = 0.0
    # tail-tolerance surface (hedged scatter + brownout router, r15): how
    # many scatter calls hedged a backup, which server won the last hedged
    # call, how long the cancelled loser ran (best-effort: the loser thread
    # stamps it when its cooperative kill lands), and any brownout
    # transitions ("enter:server" / "exit:server") observed this query
    hedged: int = 0
    hedge_winner: Optional[str] = None
    hedge_cancelled_ms: float = 0.0
    brownout_events: List[str] = field(default_factory=list)
    # [(source, {stage name: summed ns})]: the per-stage totals of every Trace
    # that served the query (each server's, the broker's), by reference; and
    # the query's slow-log entry, which copies them if the request turns out
    # slow at the front door (utils/slowlog.SlowQueryLog.door)
    stage_ns: Optional[List[Tuple[str, Dict[str, int]]]] = None
    slow_entry: Optional[dict] = None

    def merge(self, other: "ExecutionStats") -> None:
        self.num_segments_queried += other.num_segments_queried
        self.num_segments_pruned += other.num_segments_pruned
        self.num_segments_processed += other.num_segments_processed
        self.num_docs_scanned += other.num_docs_scanned
        self.total_docs += other.total_docs
        self.num_groups = max(self.num_groups, other.num_groups)
        self.num_servers_queried += other.num_servers_queried
        self.num_servers_responded += other.num_servers_responded
        self.partial_result = self.partial_result or other.partial_result
        self.exceptions.extend(other.exceptions)
        self.add_index_uses(other.filter_index_uses)
        self.query_id = self.query_id or other.query_id
        self.hedged += other.hedged
        self.hedge_winner = other.hedge_winner or self.hedge_winner
        self.hedge_cancelled_ms += other.hedge_cancelled_ms
        self.brownout_events.extend(other.brownout_events)
        self.add_kernel_cost(other)

    def add_kernel_cost(self, other: "ExecutionStats") -> None:
        """Accumulate just the launch-cost slice of `other` (used by the
        broker's scatter path, which merges the rest field-by-field)."""
        self.kernel_bytes += other.kernel_bytes
        self.compile_ms += other.compile_ms

    def add_index_uses(self, uses: Tuple) -> None:
        """Order-preserving dedup-union into filter_index_uses."""
        if uses:
            self.filter_index_uses = tuple(
                dict.fromkeys(self.filter_index_uses + tuple(uses))
            )


@dataclass
class AggSegmentResult:
    """Scalar aggregation partials: one Partial (dict of np scalars) per agg."""

    partials: List[Dict[str, np.ndarray]]


@dataclass
class GroupBySegmentResult:
    """Columnar group-by partials.

    keys: one np array per group dimension (decoded values; dtype=object when
    the dimension can hold None).  partials[i][field] is aligned with keys.
    dense_meta carries (num_groups, dim cardinalities, decode tables id) when
    the result came off the dense kernel with its FULL key space intact —
    enabling the aligned array merge fast path in reduce.py."""

    keys: List[np.ndarray]
    partials: List[Dict[str, np.ndarray]]
    dense: Optional["DenseGroupData"] = None


@dataclass
class DenseGroupData:
    """Full dense group table straight from the device kernel (before
    presence filtering) — kept when segments share a key space so the combine
    is pure array addition (the psum-shaped path)."""

    presence: np.ndarray  # int32[num_groups]
    partials: List[Dict[str, np.ndarray]]  # field arrays [num_groups]
    key_space: Tuple  # hashable id of the decode tables (see reduce.py)
    group_dims: List[Any] = field(default_factory=list)  # planner.GroupDim (decode)


@dataclass
class SelectionSegmentResult:
    columns: List[str]  # gathered columns (select + order-by needs)
    arrays: Dict[str, np.ndarray]


SegmentResult = Any  # union of the three above


@dataclass
class ResultTable:
    """Final client-facing result (BrokerResponse resultTable analog)."""

    columns: List[str]
    rows: List[tuple]
    stats: ExecutionStats = field(default_factory=ExecutionStats)

    def to_dict(self) -> Dict[str, Any]:
        def _py(v):
            if isinstance(v, np.generic):
                return v.item()
            if isinstance(v, bytes):
                return v.decode("latin-1")
            return v

        return {
            "resultTable": {
                "dataSchema": {"columnNames": self.columns},
                "rows": [[_py(v) for v in r] for r in self.rows],
            },
            "numSegmentsQueried": self.stats.num_segments_queried,
            "numSegmentsPruned": self.stats.num_segments_pruned,
            "numSegmentsProcessed": self.stats.num_segments_processed,
            "numDocsScanned": self.stats.num_docs_scanned,
            "totalDocs": self.stats.total_docs,
            "timeUsedMs": self.stats.time_ms,
            "numServersQueried": self.stats.num_servers_queried,
            "numServersResponded": self.stats.num_servers_responded,
            "partialResult": self.stats.partial_result,
            "exceptions": list(self.stats.exceptions),
        }
