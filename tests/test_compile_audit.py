"""Recompilation guard (pinot_tpu.analysis.compile_audit): repeated
identical queries must hit the plan cache — the compile counter stays flat
while the hit counter climbs; a storming fingerprint warns (or raises in
strict mode)."""
import warnings

import numpy as np
import pytest

from pinot_tpu.analysis.compile_audit import (
    SSE_AUDIT,
    CompileAudit,
    RecompilationStormError,
)
from pinot_tpu.query import planner
from pinot_tpu.query.engine import QueryEngine
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils.metrics import METRICS


def _counter(name):
    return METRICS.snapshot()["counters"].get(name, 0)


@pytest.fixture()
def eng():
    rng = np.random.default_rng(3)
    schema = Schema(
        "t",
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.INT, role=FieldRole.METRIC),
        ],
    )
    e = QueryEngine()
    e.register_table(schema)
    data = {
        "city": rng.choice(["sf", "nyc"], 1000).astype(object),
        "v": rng.integers(0, 100, 1000).astype(np.int32),
    }
    e.add_segment("t", build_segment(schema, data, "s0"))
    return e


def test_repeated_query_compiles_once(eng):
    planner.plan_cache_clear()
    SSE_AUDIT.reset()
    METRICS.reset()
    sql = "SELECT city, SUM(v) FROM t GROUP BY city"
    eng.sql(sql)
    compiles_after_first = _counter("compile.sse.compiles")
    assert compiles_after_first >= 1
    for _ in range(5):
        eng.sql(sql)
    assert _counter("compile.sse.compiles") == compiles_after_first
    assert _counter("compile.sse.hits") >= 5
    # per-fingerprint view agrees: every fingerprint compiled exactly once
    assert all(n == 1 for n in SSE_AUDIT.counts().values())


def test_distinct_shapes_compile_separately(eng):
    planner.plan_cache_clear()
    SSE_AUDIT.reset()
    METRICS.reset()
    eng.sql("SELECT COUNT(*) FROM t")
    n1 = _counter("compile.sse.compiles")
    eng.sql("SELECT SUM(v) FROM t")
    n2 = _counter("compile.sse.compiles")
    assert n2 > n1


def test_storm_warns_then_raises_in_strict_mode():
    audit = CompileAudit("fixture", threshold=3, strict=False)
    for _ in range(3):
        audit.record_compile("fp")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        audit.record_compile("fp")
    assert any("recompilation storm" in str(x.message) for x in w)
    assert _counter("compile.fixture.storms") >= 1

    strict = CompileAudit("fixture2", threshold=1, strict=True)
    strict.record_compile("fp")
    with pytest.raises(RecompilationStormError):
        strict.record_compile("fp")


def test_reset_clears_counts():
    audit = CompileAudit("fixture3", threshold=10)
    audit.record_compile("a")
    assert audit.compile_count("a") == 1
    audit.reset()
    assert audit.compile_count("a") == 0 and audit.counts() == {}


def test_same_shaped_segments_share_one_plan_for_a_raw_metric():
    """Segments of equal shape whose RAW metric column differs only in its
    min/max share one compiled plan when the column is aggregated or
    filtered (nothing of min/max is baked beyond the limb plan); as a GROUP
    BY dimension its range is baked, so each range keeps its own plan."""
    rng = np.random.default_rng(5)
    schema = Schema(
        "m",
        [
            FieldSpec("k", DataType.INT),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    e = QueryEngine()
    e.register_table(schema)
    for i in range(4):
        data = {
            "k": np.arange(500, dtype=np.int32) % 7,
            "v": (1000 + i + rng.integers(0, 50_000, 500)).astype(np.int64),
        }
        e.add_segment("m", build_segment(schema, data, f"s{i}"))
    planner.plan_cache_clear()
    SSE_AUDIT.reset()
    e.sql("SELECT k, SUM(v) FROM m WHERE v > 5 GROUP BY k")
    assert SSE_AUDIT.summary()["compiles_total"] == 1
    SSE_AUDIT.reset()
    e.sql("SELECT v, COUNT(*) FROM m GROUP BY v LIMIT 5")
    assert SSE_AUDIT.summary()["compiles_total"] == 4
