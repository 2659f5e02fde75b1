"""Safety + observability tests: timeouts, admission control, metrics,
trace spans, EXPLAIN PLAN.
"""
import numpy as np
import pytest

from pinot_tpu.query.engine import QueryEngine
from pinot_tpu.query.safety import AdmissionError, Deadline, QueryTimeoutError
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils.metrics import METRICS, Trace


def _schema():
    return Schema(
        "t",
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
        ],
    )


def _engine(budget=8 << 30, n=5000, segments=3):
    rng = np.random.default_rng(61)
    eng = QueryEngine(memory_budget_bytes=budget)
    cfg = TableConfig(name="t", indexing=IndexingConfig(inverted_index_columns=["city"]))
    eng.register_table(_schema(), cfg)
    for i in range(segments):
        data = {"city": rng.choice(["sf", "nyc"], n).astype(object), "v": rng.integers(0, 100, n)}
        eng.add_segment("t", build_segment(_schema(), data, f"s{i}", table_config=cfg))
    return eng


class TestTimeout:
    def test_expired_deadline_raises(self):
        eng = _engine()
        with pytest.raises(QueryTimeoutError, match="timeoutMs"):
            eng.query("SET timeoutMs = 0.000001; SELECT city, COUNT(*) FROM t GROUP BY city")

    def test_generous_deadline_passes(self):
        eng = _engine()
        res = eng.query("SET timeoutMs = 60000; SELECT COUNT(*) FROM t")
        assert res.rows[0][0] == 15000

    def test_deadline_helper(self):
        d = Deadline(None)
        d.check()  # no timeout: never raises
        d2 = Deadline(0.0000001)
        import time

        time.sleep(0.001)
        with pytest.raises(QueryTimeoutError):
            d2.check()


class TestAdmission:
    def test_oversized_query_rejected_upfront(self):
        eng = _engine(budget=1000)  # 1 KB budget: nothing real fits
        with pytest.raises(AdmissionError, match="device memory"):
            eng.query("SELECT SUM(v) FROM t")

    def test_budget_released_after_queries(self):
        eng = _engine()
        for _ in range(3):
            eng.query("SELECT COUNT(*) FROM t")
        assert eng.accountant.in_use == 0

    def test_release_on_failure(self):
        eng = _engine()
        with pytest.raises(Exception):
            eng.query("SELECT nonexistent_column FROM t")
        assert eng.accountant.in_use == 0


class TestMetricsAndTrace:
    def test_metrics_accumulate(self):
        METRICS.reset()
        eng = _engine()
        eng.query("SELECT COUNT(*) FROM t")
        eng.query("SELECT city, SUM(v) FROM t GROUP BY city")
        snap = METRICS.snapshot()
        assert snap["counters"]["queries"] == 2
        assert snap["counters"]["docsScanned"] == 30000
        assert snap["histograms"]["queryLatency"]["count"] == 2
        assert snap["histograms"]["queryLatency"]["maxMs"] > 0
        assert snap["histograms"]["queryLatency"]["p99Ms"] > 0

    def test_trace_spans(self):
        eng = _engine()
        res = eng.query("SET trace = true; SELECT city, COUNT(*) FROM t GROUP BY city")
        tr = res.stats.trace
        assert tr is not None and tr["name"] == "query"
        names = [c["name"] for c in tr["children"]]
        assert "reduce" in names
        assert sum(1 for n in names if n.startswith("launch:")) == 3
        # three segments of one plan: two jitted calls (2 + 1) and two fetches
        assert sum(1 for n in names if n == "launch_enqueue") == 2
        assert sum(1 for n in names if n == "collect") == 2
        assert all(c["ms"] >= 0 for c in tr["children"])

    def test_trace_off_by_default(self):
        eng = _engine()
        res = eng.query("SELECT COUNT(*) FROM t")
        assert res.stats.trace is None


class TestExplain:
    def test_explain_groupby_with_index(self):
        eng = _engine()
        res = eng.query("EXPLAIN PLAN FOR SELECT city, SUM(v) FROM t WHERE city = 'sf' GROUP BY city")
        assert res.columns == ["Operator", "Operator_Id", "Parent_Id"]
        ops = [r[0] for r in res.rows]
        assert any(o.startswith("BROKER_REDUCE") for o in ops)
        assert any(o.startswith("GROUP_BY") for o in ops)
        assert any("FILTER" in o for o in ops)
        # parent ids form a chain rooted at 0
        ids = {r[1] for r in res.rows}
        assert all(r[2] in ids | {0} for r in res.rows)

    def test_explain_runs_nothing(self):
        METRICS.reset()
        eng = _engine()
        eng.query("EXPLAIN PLAN FOR SELECT COUNT(*) FROM t")
        assert METRICS.snapshot()["counters"].get("docsScanned", 0) == 0


class TestEnvConfigLayering:
    def test_env_option_applies_and_query_overrides(self, monkeypatch):
        from pinot_tpu.spi.env import env_options

        monkeypatch.setenv("PINOT_TPU_OPT_numGroupsLimit", "7")
        monkeypatch.setenv("PINOT_TPU_OPT_enableNullHandling", "false")
        opts = env_options()
        assert opts["numGroupsLimit"] == 7 and opts["enableNullHandling"] is False
        eng = _engine(n=500, segments=1)
        # env default caps the group count...
        res = eng.query("SELECT v, COUNT(*) FROM t GROUP BY v LIMIT 1000")
        assert len(res.rows) <= 7
        # ...but an explicit per-query SET wins over the env layer
        res2 = eng.query("SET numGroupsLimit = 1000; SELECT v, COUNT(*) FROM t GROUP BY v LIMIT 1000")
        assert len(res2.rows) > 7


class TestWorkloadScheduler:
    """BinaryWorkloadScheduler analog: secondary workload isolation."""

    def test_primary_never_queued(self):
        from pinot_tpu.query.ir import QueryContext
        from pinot_tpu.query.safety import WorkloadScheduler

        ws = WorkloadScheduler(secondary_slots=1)
        ctx = QueryContext(table="t", select_list=[])
        rels = [ws.acquire(ctx) for _ in range(10)]  # primary: unbounded
        for r in rels:
            r()

    def test_secondary_bounded(self):
        from pinot_tpu.query.ir import QueryContext
        from pinot_tpu.query.safety import AdmissionError, Deadline, WorkloadScheduler

        ws = WorkloadScheduler(secondary_slots=2)
        ctx = QueryContext(table="t", select_list=[], options={"isSecondaryWorkload": "true"})
        d = Deadline(50.0)  # 50ms: don't block the test
        r1 = ws.acquire(ctx, d)
        r2 = ws.acquire(ctx, d)
        with pytest.raises(AdmissionError):
            ws.acquire(ctx, Deadline(50.0))
        r1()
        r3 = ws.acquire(ctx, Deadline(50.0))  # freed slot admits again
        r3(); r2()

    def test_engine_option_roundtrip(self):
        import numpy as np

        from pinot_tpu.query.engine import QueryEngine
        from pinot_tpu.segment.builder import build_segment
        from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema

        schema = Schema("w", [FieldSpec("v", DataType.INT, role=FieldRole.METRIC)])
        eng = QueryEngine(secondary_slots=1)
        eng.register_table(schema)
        eng.add_segment("w", build_segment(schema, {"v": np.arange(100, dtype=np.int32)}, "s0"))
        r = eng.query("SET isSecondaryWorkload = true; SELECT COUNT(*) FROM w")
        assert int(r.rows[0][0]) == 100
