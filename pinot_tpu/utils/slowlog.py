"""Broker-side slow-query log: bounded ring buffer of recent queries.

Reference parity: Pinot's broker query log (BaseSingleStageBrokerRequestHandler
logs requestId/SQL/timing per request, rate-limited) + the druid-style
/debug surface.  Re-design: an in-memory deque the REST layer serves at
`GET /debug/queries` (newest first) and the CLI prints via `slow-queries`;
queries over `slow_ms` additionally keep their full span tree, so the tail
that matters arrives with its own flame graph attached.  A request that came
through the HTTP front door is judged there a second time (`door`): by its
time from `accept()` to its last byte, which holds the waits the engine's
`timeMs` starts after; a slow one, traced or not, keeps the front door's
times (`door`) and what each stage summed to on the broker and on every
server that answered (`stagesMs`).

Entries are plain dicts (JSON-ready); SQL text is stored verbatim but
NEVER used as a metric/span name (repo_lint W007 guards that class), and
the plan fingerprint is stored as a short digest — full fingerprints embed
literal values.
"""
from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from pinot_tpu.utils.interpreter import WATCH
from pinot_tpu.utils.metrics import METRICS


def _fp_digest(fingerprint: str) -> str:
    return hashlib.sha1(fingerprint.encode("utf-8", "replace")).hexdigest()[:12]


class SlowQueryLog:
    """Ring buffer of the last `capacity` queries; `snapshot()` is newest
    first.  `slow_ms` gates trace retention (and the slowQueries counter),
    not admission — every query lands in the ring so /debug/queries doubles
    as a recent-query log."""

    def __init__(self, capacity: Optional[int] = None, slow_ms: Optional[float] = None):
        if capacity is None:
            capacity = int(os.environ.get("PINOT_TPU_SLOW_LOG_CAPACITY", "128"))
        if slow_ms is None:
            slow_ms = float(os.environ.get("PINOT_TPU_SLOW_QUERY_MS", "250"))
        self.capacity = max(1, capacity)
        self.slow_ms = slow_ms
        self._entries: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()

    def record(
        self,
        sql: str,
        fingerprint: str,
        result=None,
        query_id: Optional[str] = None,
        error: Optional[str] = None,
        shape_fingerprint: Optional[str] = None,
    ) -> Dict[str, Any]:
        """Log one finished (or failed) query; returns the entry dict."""
        stats = getattr(result, "stats", None)
        time_ms = float(stats.time_ms) if stats is not None else 0.0
        entry: Dict[str, Any] = {
            # epoch stamp for display only — never used in elapsed math (W005)
            "timestamp": time.time(),
            "queryId": query_id if query_id is not None else (stats.query_id if stats else None),
            "sql": sql,
            "planFingerprint": _fp_digest(fingerprint),
            # literal-canonical shape digest: every member of a parameterized
            # plan-cache family shares this value (query/shape.py)
            "shapeFingerprint": _fp_digest(shape_fingerprint)
            if shape_fingerprint is not None
            else None,
            # "hit" | "miss" when the broker result cache was consulted
            "resultCache": getattr(stats, "result_cache", None) if stats else None,
            "timeMs": round(time_ms, 3),
            "rows": len(result.rows) if result is not None else 0,
            "numDocsScanned": stats.num_docs_scanned if stats else 0,
            "numSegmentsProcessed": stats.num_segments_processed if stats else 0,
            "partialResult": bool(stats.partial_result) if stats else False,
            "numExceptions": len(stats.exceptions) if stats else 0,
        }
        # the bytes the query's scans had to read, the compile time THIS
        # query paid and its row rate: whether the device or the
        # compile/dispatch path made a slow query slow
        if stats is not None and getattr(stats, "kernel_bytes", 0):
            entry["kernelBytes"] = round(stats.kernel_bytes, 1)
            entry["compileMs"] = round(stats.compile_ms, 3)
            if time_ms > 0:
                entry["rowsPerSec"] = round(stats.num_docs_scanned / (time_ms / 1000.0), 1)
        if error is not None:
            entry["error"] = error
        # watchdog kill record: a killed-but-partial query carries its
        # QUERY_KILLED exception entry (query id, reason, server) — surface
        # it top-level so /debug/queries and the CLI show kills at a glance
        if stats is not None:
            for exc in stats.exceptions:
                if isinstance(exc, dict) and exc.get("errorCode") == "QUERY_KILLED":
                    entry["kill"] = exc
                    break
        # tail-tolerance decisions (r15): hedged scatter calls and brownout
        # transitions surface top-level, so /debug/queries and EXPLAIN
        # ANALYZE show WHY a tail query came back fast (or didn't)
        if stats is not None and getattr(stats, "hedged", 0):
            entry["hedge"] = {
                "hedged": stats.hedged,
                "winner": stats.hedge_winner,
                "cancelledMs": round(stats.hedge_cancelled_ms, 3),
            }
        if stats is not None and getattr(stats, "brownout_events", None):
            entry["brownout"] = list(stats.brownout_events)
        if self._kept_as_slow(entry):
            self._slow(entry, stats)
        if stats is not None:
            stats.slow_entry = entry  # for the front door: door()
        with self._lock:
            self._entries.append(entry)
        return entry

    def _kept_as_slow(self, entry: Dict[str, Any]) -> bool:
        return entry["timeMs"] >= self.slow_ms or "error" in entry or "kill" in entry

    @staticmethod
    def _slow(entry: Dict[str, Any], stats) -> None:
        METRICS.counter("broker.slowQueries").inc()
        if stats is not None and stats.trace is not None:
            entry["trace"] = stats.trace

    def door(self, stats, door: Dict[str, float], accept_ns: Optional[int] = None) -> None:
        """The front door's verdict on a request it has just answered:
        `door` holds its times there in ms (`doorMs`: accept() to the last
        byte written).  Slow by `doorMs`, the request's entry keeps them and
        `stagesMs`: per source (`broker`, each server) what every stage
        summed to over the query, `launch:<segment>` folded into `launch`;
        and, where the interpreter watch ran and `accept_ns` says when the
        request began on the tracer's clock, `heldBy`: the holds of the
        interpreter lock that overlap its life (utils/interpreter.py: holder,
        thread, frame, lateMs), so the record that says WHERE the request
        waited says for WHOM.  A fast request's entry is left as record()
        made it."""
        entry = stats.slow_entry
        if entry is None or door["doorMs"] < self.slow_ms:
            return
        stages: Dict[str, Dict[str, float]] = {}  # source -> stage -> summed ns
        for source, totals in stats.stage_ns or ():
            into = stages.setdefault(source, {})
            for name, ns in totals.items():
                stem = name.split(":", 1)[0]
                into[stem] = into.get(stem, 0.0) + ns
        with self._lock:  # snapshot() may be reading the entry
            if not self._kept_as_slow(entry):
                self._slow(entry, stats)  # record() had let it pass
            entry["door"] = {k: round(v, 3) for k, v in door.items()}
            entry["stagesMs"] = {s: {k: round(ns / 1e6, 3) for k, ns in d.items()} for s, d in stages.items()}
            if accept_ns is not None:
                WATCH.held_by(entry, accept_ns, accept_ns + int(door["doorMs"] * 1e6))

    def snapshot(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        with self._lock:
            out = [dict(e) for e in self._entries]
        out.reverse()  # newest first
        return out[:limit] if limit else out

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)
