"""What the program keeps of its own speed (utils/perf.py): the bytes a scan
must read, counted once a plan; the per-table/per-shape stats window; cluster
metric federation, /debug/perf and `cli perf`; the slow-query log's fields."""
import json
import urllib.request

import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.rest import QueryServer
from pinot_tpu.query import planner
from pinot_tpu.query.engine import QueryEngine
from pinot_tpu.query.result import ExecutionStats
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils import perf
from pinot_tpu.utils.metrics import METRICS, MetricsRegistry, federate_prometheus, merge_registry_snapshots
from pinot_tpu.utils.slowlog import SlowQueryLog


def _schema(table="t"):
    return Schema(
        table,
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
        ],
    )


def _data(n, seed):
    rng = np.random.default_rng(seed)
    return {
        "city": rng.choice(["sf", "nyc", "la"], n).astype(object),
        "v": rng.integers(0, 100, n),
    }


def _spans(node, out=None):
    """A span tree as {base name: [node, ...]} (launch:seg0 under launch)."""
    out = {} if out is None else out
    out.setdefault(node["name"].split(":", 1)[0], []).append(node)
    for c in node.get("children", []):
        _spans(c, out)
    return out


def _engine(table="t", n_segments=2, rows=150):
    eng = QueryEngine()
    eng.register_table(_schema(table))
    for i in range(n_segments):
        eng.add_segment(table, build_segment(_schema(table), _data(rows, 100 + i), f"seg{i}"))
    return eng


class _FakeCol:
    def __init__(self, codes=None, values=None, nulls=None):
        self.codes = codes
        self.values = values
        self.nulls = nulls


class TestScanBytes:
    def test_bytes_per_row_uses_stored_widths(self):
        cols = [
            _FakeCol(codes=np.zeros(4, np.int8)),  # dict codes at code width
            _FakeCol(values=np.zeros(4, np.int64), nulls=np.zeros(4, bool)),
        ]
        bpr = perf.scan_bytes_per_row(cols, bitmap_params=1)
        assert bpr == pytest.approx(1 + 8 + 1 + 4 / 32)


# ---------------------------------------------------------------------------
# engine integration: cost on stats, EXPLAIN ANALYZE, cached reuse
# ---------------------------------------------------------------------------
class TestEngineCostIntegration:
    def test_stats_carry_the_scan_bytes_of_every_launch(self):
        eng = _engine(table="perfcost")
        out = eng.query("SELECT city, SUM(v) FROM perfcost GROUP BY city")
        # two segments of 150 rows; `city` packs three values into 4-bit
        # lanes (0.5 B/row), `v` < 100 is stored at one byte
        want = 0
        for seg in eng.tables["perfcost"].query_segments():
            want += seg.num_docs * perf.scan_bytes_per_row(seg.column(c) for c in ("city", "v"))
        assert out.stats.kernel_bytes == pytest.approx(want) and want > 0

    def test_bytes_counted_once_and_no_plan_lowered_on_a_cold_launch(self, monkeypatch):
        """The number rides the plan-cache entry, and a cold launch calls
        the plan's jitted function and nothing else of it: `.lower()`, which
        the retired cost model ran before every first launch, would trace
        the program a second time."""
        import jax

        lowered = []
        real_jit = jax.jit

        def counting_jit(fn, *a, **kw):
            jitted = real_jit(fn, *a, **kw)

            class Jitted:
                def __call__(self, *args, **kwargs):
                    return jitted(*args, **kwargs)

                def lower(self, *args, **kwargs):
                    lowered.append(fn)
                    return jitted.lower(*args, **kwargs)

            return Jitted()

        monkeypatch.setattr(jax, "jit", counting_jit)
        planner.plan_cache_clear()
        eng = _engine(table="perfreuse")
        sql = "SELECT city, SUM(v) FROM perfreuse GROUP BY city"
        first = eng.query(sql).stats
        second = eng.query(sql).stats
        assert first.compile_ms > 0  # cold: the first call's wall time
        assert second.compile_ms == 0.0
        assert second.kernel_bytes == pytest.approx(first.kernel_bytes) and first.kernel_bytes > 0
        assert lowered == []

    def test_explain_analyze_interpret_pallas_shows_bytes(self, monkeypatch):
        # a Pallas-backed group-by scan on CPU tier-1 (interpret mode)
        # surfaces per-operator Bytes, and none of the retired columns
        monkeypatch.setenv("PINOT_TPU_SCAN_BACKEND", "interpret")
        ops.scan_backend.cache_clear()
        try:
            eng = _engine(table="perfinterp", rows=170)
            res = eng.query(
                "EXPLAIN ANALYZE SELECT city, SUM(v) FROM perfinterp GROUP BY city"
            )
            assert res.columns == [
                "Operator", "Operator_Id", "Parent_Id", "Actual_Ms", "Rows", "Bytes",
            ]
            assert all(len(r) == len(res.columns) for r in res.rows)
            gb = [r for r in res.rows if str(r[0]).startswith(("GROUP_BY", "AGGREGATE"))]
            assert gb, res.rows
            assert gb[0][5] > 0  # Bytes
            trace_launch = [r for r in res.rows if str(r[0]).startswith("TRACE(launch:")]
            assert trace_launch and all(r[5] > 0 for r in trace_launch)  # span-level kernelBytes
            assert sum(r[5] for r in trace_launch) == pytest.approx(gb[0][5])
            # and the spans say the bytes and nothing else of a cost
            spans = _spans(res.stats.trace)
            assert all(set(n["attrs"]) == {"segment", "cpuMs", "kernelBytes"} for n in spans["launch"])
            assert [set(n["attrs"]) for n in spans["device_wait"]] == [{"launches", "kernelBytes"}]
        finally:
            ops.scan_backend.cache_clear()


# ---------------------------------------------------------------------------
# stats window
# ---------------------------------------------------------------------------
class TestShapeStats:
    def test_record_snapshot_and_gauges(self):
        led = perf.ShapeStats(window=4)
        for i in range(6):  # overflow the window: deques stay bounded
            led.record(
                "t", "abc123", rows=1000, time_ms=10.0, kernel_bytes=8000.0,
                compile_ms=5.0 if i == 0 else 0.0, cache_hit=i > 0,
            )
        snap = led.snapshot()
        sh = snap["tables"]["t"]["shapes"]["abc123"]
        assert snap["tables"]["t"]["queries"] == 6
        assert sh["rowsPerSec"]["last"] == pytest.approx(100000.0)
        assert sh["planCacheHitRate"] == pytest.approx(5 / 6, abs=1e-3)
        assert sh["compileMsTotal"] == pytest.approx(5.0)
        assert sh["bytesPerSec"]["last"] == pytest.approx(800000.0)
        assert set(sh) == {
            "queries", "qps", "rowsPerSec", "bytesPerSec", "latencyMs", "compileMsTotal",
            "planCacheHitRate",
        }
        assert sh["qps"] >= 0

    def test_global_window_exports_table_gauges(self):
        perf.SHAPE_STATS.record("gt", "fp", rows=100, time_ms=5.0, kernel_bytes=400.0)
        snap = METRICS.snapshot()
        assert snap["gauges"]["perf.gt.rowsPerSec"] == pytest.approx(20000.0)
        assert snap["gauges"]["perf.gt.bytesPerSec"] == pytest.approx(80000.0)
        assert {k for k in snap["gauges"] if k.startswith("perf.gt.")} == {
            "perf.gt.rowsPerSec", "perf.gt.bytesPerSec", "perf.gt.qps",
        }

    def test_sse_query_lands_in_global_window(self):
        eng = _engine(table="perfledger")
        eng.query("SELECT COUNT(*) FROM perfledger")
        snap = perf.SHAPE_STATS.snapshot()
        assert "perfledger" in snap["tables"]
        t = snap["tables"]["perfledger"]
        assert t["queries"] >= 1
        (shape,) = list(t["shapes"].values())[:1]
        assert shape["rowsPerSec"]["last"] > 0


# ---------------------------------------------------------------------------
# cluster metric federation
# ---------------------------------------------------------------------------
def _cluster(n_servers=2, n_segments=4, rows=150):
    coord = Coordinator(replication=2)
    for i in range(n_servers):
        coord.register_server(ServerInstance(f"server{i}"))
    coord.add_table(_schema(), TableConfig(name="t"))
    for i in range(n_segments):
        coord.add_segment("t", build_segment(_schema(), _data(rows, 100 + i), f"seg{i}"))
    return coord


class TestFederation:
    def test_merge_semantics(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("queries").inc(3)
        b.counter("queries").inc(4)
        a.gauge("level").set(1.0)
        b.gauge("level").set(2.0)
        a.timer("lat").update(10.0)
        b.timer("lat").update(30.0)
        a.histogram("h").update(1.0)
        b.histogram("h").update(1.0)
        merged = merge_registry_snapshots({"s0": a, "s1": b})
        assert merged["counters"]["queries"] == 7  # SUM
        assert merged["gauges"]["level"] == 2.0  # LAST (lexicographic s1)
        assert merged["timers"]["lat"]["count"] == 2
        assert merged["timers"]["lat"]["maxMs"] == 30.0  # MAX
        assert merged["histograms"]["h"]["count"] == 2  # bucket-wise SUM
        assert sum(merged["histograms"]["h"]["counts"]) == 2

    def test_federate_prometheus_labels_sources(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("server.queries").inc(2)
        b.counter("server.queries").inc(5)
        text = federate_prometheus({"s0": a, "s1": b})
        assert 'pinot_server_queries_total{server="s0"} 2' in text
        assert 'pinot_server_queries_total{server="s1"} 5' in text
        assert "pinot_cluster_server_queries_total 7" in text

    def test_broker_federates_server_registries(self):
        coord = _cluster()
        broker = Broker(coord)
        for _ in range(3):
            broker.query("SELECT city, COUNT(*) FROM t GROUP BY city")
        regs = broker.federated_registries()
        assert set(regs) == {"server0", "server1"}
        text = broker.federated_prometheus()
        assert 'server="server0"' in text and 'server="server1"' in text
        assert "pinot_cluster_server_queries_total" in text
        snap = broker.federated_snapshot()
        per_server = sum(
            r["counters"].get("server.queries", 0) for r in snap["perServer"].values()
        )
        assert snap["cluster"]["counters"]["server.queries"] == per_server > 0

    def test_rest_metrics_endpoint_serves_federation(self):
        coord = _cluster()
        broker = Broker(coord)
        broker.query("SELECT COUNT(*) FROM t")
        srv = QueryServer(broker).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(base + "/metrics?format=prometheus") as r:
                text = r.read().decode()
            assert 'server="server0"' in text and "pinot_cluster_" in text
            with urllib.request.urlopen(base + "/debug/perf") as r:
                payload = json.loads(r.read().decode())
            assert "tables" in payload and "t" in payload["tables"]
            assert "caches" in payload
        finally:
            srv.stop()

    def test_debug_perf_route_on_plain_engine(self):
        srv = QueryServer(_engine(table="perfroute")).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(
                base + "/query", data=json.dumps({"sql": "SELECT COUNT(*) FROM perfroute"}).encode()
            ) as r:
                r.read()
            with urllib.request.urlopen(base + "/debug/perf") as r:
                payload = json.loads(r.read().decode())
            assert "tables" in payload
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# slow log perf fields
# ---------------------------------------------------------------------------
class TestSlowLogPerfFields:
    def test_entry_carries_bytes_compile_time_and_row_rate(self):
        class R:
            stats = ExecutionStats()
            rows = [(1,)]

        R.stats.time_ms = 10.0
        R.stats.num_docs_scanned = 1000
        R.stats.kernel_bytes = 8.0e6
        R.stats.compile_ms = 3.0
        log = SlowQueryLog(capacity=4, slow_ms=1e9)
        entry = log.record("SELECT 1", "fp", result=R())
        assert entry["kernelBytes"] == 8.0e6
        assert entry["compileMs"] == 3.0
        assert entry["rowsPerSec"] == pytest.approx(100000.0)
        lean = set(log.record("SELECT 1", "fp", result=type("R0", (), {"stats": ExecutionStats(), "rows": []})()))
        assert set(entry) - lean == {"kernelBytes", "compileMs", "rowsPerSec"}

    def test_entry_without_cost_stays_lean(self):
        class R:
            stats = ExecutionStats()
            rows = []

        log = SlowQueryLog(capacity=4, slow_ms=1e9)
        entry = log.record("SELECT 1", "fp", result=R())
        assert "kernelBytes" not in entry


# ---------------------------------------------------------------------------
# cli perf: the stats-window view, and no gate
# ---------------------------------------------------------------------------
class TestCliPerf:
    def test_prints_the_served_shapes(self, capsys):
        from pinot_tpu.tools.cli import main

        eng = _engine(table="perfcli")
        srv = QueryServer(eng).start()
        try:
            eng.query("SELECT city, SUM(v) FROM perfcli GROUP BY city")
            assert main(["perf", "--url", f"http://127.0.0.1:{srv.port}"]) == 0
        finally:
            srv.stop()
        out = capsys.readouterr().out
        assert "table perfcli: 1 quer" in out
        assert "bytes/s last=" in out and "roofline" not in out

    @pytest.mark.parametrize("flag", ["--check", "--history", "--baseline", "--threshold"])
    def test_the_gate_options_are_argparse_errors(self, flag, capsys):
        from pinot_tpu.tools.cli import main

        with pytest.raises(SystemExit) as e:
            main(["perf", flag] + ([] if flag == "--check" else ["x"]))
        assert e.value.code == 2
        assert "unrecognized arguments" in capsys.readouterr().err
