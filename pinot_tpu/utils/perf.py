"""What the program keeps of its own speed: the bytes a scan must read, and a
rolling window of served-query statistics per (table, query shape).

- scan_bytes_per_row(): a COUNT, not a model: the stored bytes per row of
  the columns a plan reads (the same principle as the benchmark's
  lib/opcount.py).  A plan multiplies it by its rows once, when it is built,
  and every launch reports that number (ExecutionStats.kernel_bytes, the
  `kernelBytes` span attr, EXPLAIN ANALYZE's Bytes, the slow-query log).

- ShapeStats: rolling windows of rows/s, bytes/s, latency, compile ms,
  plan-cache outcome and QPS keyed (table, shape digest).  Exported as
  bounded-name gauges (`perf.{table}.*`) and the `GET /debug/perf` /
  `cli perf` views; the residency manager ranks evictions by its bytes/s
  and the autopilot reads its tail latency and QPS.

How fast the program is on the chip is measured from outside, by the
benchmark (BENCHMARK.json, benchmarks/, PERF.md).
"""
from __future__ import annotations

import collections
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, Optional, Tuple

from pinot_tpu.utils.metrics import METRICS


def scan_bytes_per_row(columns, bitmap_params: int = 0) -> float:
    """Bytes a scan must read per row: each needed column at its stored
    width — bit-packed dict columns at `code_bits / 8` (the uint32 lane words
    are what actually stream; see segment/packing.py), unpacked dict codes at
    code dtype width, raw columns at value width — null bitmaps at 1
    byte/row, plus one uint32 per 32 rows per row-sharded index-bitmap
    parameter."""
    bpr = 0.0
    for c in columns:
        arr = c.codes if getattr(c, "codes", None) is not None else c.values
        if arr is not None:
            bits = getattr(c, "code_bits", None)
            if bits and getattr(c, "packed", None) is not None:
                bpr += bits / 8.0  # MV columns never pack, so no width factor
            else:
                bpr += arr.dtype.itemsize
        if getattr(c, "nulls", None) is not None:
            bpr += 1
    return bpr + bitmap_params * 4.0 / 32.0


# ---------------------------------------------------------------------------
# per-table / per-shape stats window
# ---------------------------------------------------------------------------


@dataclass
class _ShapeEntry:
    window: int
    queries: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    compile_ms_total: float = 0.0
    rows_per_sec: Deque[float] = field(default_factory=collections.deque)
    bytes_per_sec: Deque[float] = field(default_factory=collections.deque)
    latency_ms: Deque[float] = field(default_factory=collections.deque)
    arrivals: Deque[float] = field(default_factory=collections.deque)

    def push(self, dq: Deque[float], v: float) -> None:
        dq.append(v)
        while len(dq) > self.window:
            dq.popleft()


def _win_stats(dq: Deque[float]) -> Dict[str, float]:
    if not dq:
        return {"last": 0.0, "mean": 0.0, "max": 0.0, "p99": 0.0}
    vals = list(dq)
    ordered = sorted(vals)
    p99 = ordered[min(len(ordered) - 1, int(round(0.99 * (len(ordered) - 1))))]
    return {
        "last": round(vals[-1], 3),
        "mean": round(sum(vals) / len(vals), 3),
        "max": round(max(vals), 3),
        # windowed tail: the autopilot's primary feedback signal
        "p99": round(p99, 3),
    }


def _window_qps(arrivals: Deque[float]) -> float:
    """Arrival rate over the rolling window: (n-1) queries per elapsed span.
    Span-based rather than per-second bucketing so short test bursts still
    read as a meaningful rate."""
    if len(arrivals) < 2:
        return 0.0
    span = arrivals[-1] - arrivals[0]
    return (len(arrivals) - 1) / span if span > 0 else 0.0


class ShapeStats:
    """Rolling perf windows keyed (table, shape digest).

    Gauges are per-table only (`perf.{table}.rowsPerSec` etc. — table names
    are a bounded set, same precedent as `server.segmentBytes.{table}`);
    shape digests stay inside the snapshot payload so metric-name
    cardinality never tracks query shapes."""

    def __init__(self, window: int = 128) -> None:
        self.window = window
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, str], _ShapeEntry] = {}

    def record(
        self,
        table: str,
        shape_fp: str,
        *,
        rows: float,
        time_ms: float,
        kernel_bytes: float = 0.0,
        compile_ms: float = 0.0,
        cache_hit: Optional[bool] = None,
    ) -> None:
        if not table:
            table = "_unknown"
        rows_ps = rows / (time_ms / 1000.0) if time_ms > 0 else 0.0
        bytes_ps = kernel_bytes / (time_ms / 1000.0) if time_ms > 0 else 0.0
        now = time.monotonic()
        with self._lock:
            key = (table, shape_fp or "")
            e = self._entries.get(key)
            if e is None:
                e = self._entries[key] = _ShapeEntry(window=self.window)
            e.queries += 1
            if cache_hit is True:
                e.cache_hits += 1
            elif cache_hit is False:
                e.cache_misses += 1
            e.compile_ms_total += compile_ms
            e.push(e.rows_per_sec, rows_ps)
            e.push(e.bytes_per_sec, bytes_ps)
            e.push(e.latency_ms, time_ms)
            e.push(e.arrivals, now)
            table_arrivals = [
                t for (tb, _), en in self._entries.items() if tb == table for t in en.arrivals
            ]
        # gauge export outside the window's lock (gauge ops take their own)
        table_arrivals.sort()
        qps_dq: Deque[float] = collections.deque(table_arrivals[-self.window :])
        g = METRICS.gauge
        g(f"perf.{table}.rowsPerSec").set(rows_ps)
        g(f"perf.{table}.bytesPerSec").set(bytes_ps)
        g(f"perf.{table}.qps").set(_window_qps(qps_dq))
        if compile_ms > 0:
            g(f"perf.{table}.lastCompileMs").set(compile_ms)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            items = list(self._entries.items())
        tables: Dict[str, Any] = {}
        for (table, fp), e in items:
            t = tables.setdefault(table, {"queries": 0, "qps": 0.0, "shapes": {}})
            t["queries"] += e.queries
            hitseen = e.cache_hits + e.cache_misses
            t["shapes"][fp or "-"] = {
                "queries": e.queries,
                "qps": round(_window_qps(e.arrivals), 3),
                "rowsPerSec": _win_stats(e.rows_per_sec),
                "bytesPerSec": _win_stats(e.bytes_per_sec),
                "latencyMs": _win_stats(e.latency_ms),
                "compileMsTotal": round(e.compile_ms_total, 3),
                "planCacheHitRate": round(e.cache_hits / hitseen, 3) if hitseen else None,
            }
        for table, t in tables.items():
            arrivals = sorted(
                ts
                for (tb, _), e in items
                if tb == table
                for ts in e.arrivals
            )
            t["qps"] = round(_window_qps(collections.deque(arrivals[-self.window :])), 3)
        return {"window": self.window, "tables": tables}

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()


SHAPE_STATS = ShapeStats()
