"""Performance observatory (round 6): kernel cost capture + fallbacks,
roofline accounting, the per-table/per-shape perf ledger, cluster metric
federation, /debug/perf, and the bench-history regression gate."""
import json
import urllib.request

import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.rest import QueryServer
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


# ---------------------------------------------------------------------------
# capture_cost fallbacks
# ---------------------------------------------------------------------------
class TestCaptureCost:
    def test_auto_on_cpu_is_analytic_without_lowering(self):
        # auto mode on a CPU backend must not even touch fn (no extra
        # trace+lower on the tier-1 serving path)
        analytic = perf.analytic_cost(100, 8.0)
        got = perf.capture_cost(None, (), analytic)
        assert got is analytic and got.source == "analytic"

    def test_forced_xla_reads_cost_analysis_on_cpu(self):
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda x: (x * x).sum())
        x = jnp.arange(1024, dtype=jnp.float32)
        analytic = perf.analytic_cost(1024, 4.0)
        got = perf.capture_cost(fn, (x,), analytic, force="xla")
        # CPU XLA reports cost_analysis (probed); if a backend ever stops,
        # the guarded fallback hands back the analytic estimate instead
        assert got.source in ("xla", "analytic")
        assert got.bytes_accessed > 0
        if got.source == "xla":
            assert got.flops > 0 and got.lower_ms > 0

    def test_lowering_failure_falls_back_to_analytic(self):
        class Exploding:
            def lower(self, *a):
                raise RuntimeError("backend without cost analysis")

        analytic = perf.analytic_cost(10, 4.0)
        got = perf.capture_cost(Exploding(), (1,), analytic, force="xla")
        assert got is analytic and got.source == "analytic"

    def test_missing_bytes_key_falls_back_but_keeps_lower_ms(self):
        class NoBytes:
            def lower(self, *a):
                return self

            def cost_analysis(self):
                return {"flops": 42.0}  # no 'bytes accessed' -> unusable

        analytic = perf.analytic_cost(10, 4.0)
        got = perf.capture_cost(NoBytes(), (1,), analytic, force="xla")
        assert got.source == "analytic" and got.lower_ms > 0

    def test_env_override_forces_analytic(self, monkeypatch):
        monkeypatch.setenv("PINOT_TPU_COST_SOURCE", "analytic")
        analytic = perf.analytic_cost(10, 4.0)
        got = perf.capture_cost(None, (), analytic)
        assert got is analytic

    def test_combine_sources(self):
        assert perf.combine_sources(None, "xla") == "xla"
        assert perf.combine_sources("xla", "xla") == "xla"
        assert perf.combine_sources("xla", "analytic") == "mixed"
        assert perf.combine_sources("analytic", None) == "analytic"


class TestAnalyticModel:
    def test_bytes_per_row_uses_stored_widths(self):
        cols = [
            _FakeCol(codes=np.zeros(4, np.int8)),  # dict codes at code width
            _FakeCol(values=np.zeros(4, np.int64), nulls=np.zeros(4, bool)),
        ]
        bpr = perf.analytic_bytes_per_row(cols, bitmap_params=1)
        assert bpr == pytest.approx(1 + 8 + 1 + 4 / 32)

    def test_groupby_flops_follow_one_hot_matmul(self):
        from pinot_tpu.ops.pallas_scan import matmul_flops_per_row

        c = perf.analytic_cost(1000, 8.0, kind="groupby", num_groups=50, num_entries=2)
        assert c.flops == pytest.approx(1000 * matmul_flops_per_row(50, 2))
        assert c.bytes_accessed == pytest.approx(8000.0)
        assert c.output_bytes > 0

    def test_aggregation_and_selection_kinds(self):
        agg = perf.analytic_cost(100, 4.0, kind="aggregation", num_entries=3)
        sel = perf.analytic_cost(100, 4.0, kind="selection")
        assert agg.flops == pytest.approx(600.0)
        assert sel.flops == pytest.approx(100.0)


class TestRoofline:
    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv("PINOT_TPU_PEAK_HBM_BPS", "1e9")
        perf.peak_hbm_bytes_per_sec.cache_clear()
        try:
            assert perf.peak_hbm_bytes_per_sec() == 1e9
            # 5e8 bytes in 1s = 50% of a 1e9 peak
            assert perf.roofline_pct(5e8, 1.0) == pytest.approx(50.0)
        finally:
            perf.peak_hbm_bytes_per_sec.cache_clear()

    def test_unmeasurable_is_none(self):
        assert perf.roofline_pct(0.0, 1.0) is None
        assert perf.roofline_pct(100.0, 0.0) is None

    def test_unknown_tpu_kind_is_an_error(self, monkeypatch):
        # a TPU the peaks table does not know gets no borrowed peak
        import jax

        class _Dev:
            device_kind = "TPU v9 imaginary"

        monkeypatch.delenv("PINOT_TPU_PEAK_HBM_BPS", raising=False)
        monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
        perf.peak_hbm_bytes_per_sec.cache_clear()
        try:
            with pytest.raises(ValueError, match="no peak HBM bandwidth on record"):
                perf.peak_hbm_bytes_per_sec()
        finally:
            perf.peak_hbm_bytes_per_sec.cache_clear()

    def test_cpu_fallback_peak_is_positive(self, monkeypatch):
        monkeypatch.delenv("PINOT_TPU_PEAK_HBM_BPS", raising=False)
        perf.peak_hbm_bytes_per_sec.cache_clear()
        try:
            assert perf.peak_hbm_bytes_per_sec() > 0
        finally:
            perf.peak_hbm_bytes_per_sec.cache_clear()


# ---------------------------------------------------------------------------
# engine integration: cost on stats, EXPLAIN ANALYZE, cached reuse
# ---------------------------------------------------------------------------
class TestEngineCostIntegration:
    def test_stats_carry_kernel_cost(self):
        eng = _engine(table="perfcost")
        out = eng.query("SELECT city, SUM(v) FROM perfcost GROUP BY city")
        s = out.stats
        assert s.kernel_bytes > 0 and s.kernel_flops > 0
        assert s.kernel_cost_source in ("analytic", "xla", "mixed")

    def test_cost_captured_once_not_relowered_on_hits(self):
        eng = _engine(table="perfreuse")
        sql = "SELECT city, SUM(v) FROM perfreuse GROUP BY city"
        first = eng.query(sql).stats
        second = eng.query(sql).stats
        # cold: compile wall time recorded; warm: plan-cache hit copies the
        # captured cost without re-lowering, and pays no compile
        assert first.compile_ms > 0
        assert second.compile_ms == 0.0
        assert second.kernel_bytes == pytest.approx(first.kernel_bytes)
        assert second.kernel_cost_source == first.kernel_cost_source

    def test_explain_analyze_interpret_pallas_shows_cost_columns(self, monkeypatch):
        # the acceptance shape: a Pallas-backed group-by scan on CPU tier-1
        # (interpret mode) surfaces per-operator Bytes/Flops/Roofline_Pct
        # through the analytic fallback
        monkeypatch.setenv("PINOT_TPU_SCAN_BACKEND", "interpret")
        ops.scan_backend.cache_clear()
        # a peak this low keeps every measured roofline % far above the
        # two-decimal rounding, however slow the interpreted kernel runs
        monkeypatch.setenv("PINOT_TPU_PEAK_HBM_BPS", "1e3")
        perf.peak_hbm_bytes_per_sec.cache_clear()
        try:
            eng = _engine(table="perfinterp", rows=170)
            res = eng.query(
                "EXPLAIN ANALYZE SELECT city, SUM(v) FROM perfinterp GROUP BY city"
            )
            assert res.columns == [
                "Operator", "Operator_Id", "Parent_Id", "Actual_Ms", "Rows",
                "Bytes", "Flops", "Roofline_Pct",
            ]
            gb = [r for r in res.rows if str(r[0]).startswith(("GROUP_BY", "AGGREGATE"))]
            assert gb, res.rows
            op = gb[0]
            assert op[5] > 0 and op[6] > 0  # Bytes, Flops
            assert op[7] is None or op[7] > 0  # Roofline_Pct when fence measured
            # roofline must be measured somewhere in the plan: the fence-
            # owning COMBINE row or a TRACE(device_wait) span carries it
            roofs = [r[7] for r in res.rows if r[7] is not None]
            assert roofs and all(v > 0 for v in roofs)
            trace_launch = [r for r in res.rows if str(r[0]).startswith("TRACE(launch")]
            assert any(r[5] for r in trace_launch)  # span-level kernelBytes
        finally:
            ops.scan_backend.cache_clear()
            perf.peak_hbm_bytes_per_sec.cache_clear()


# ---------------------------------------------------------------------------
# perf ledger
# ---------------------------------------------------------------------------
class TestPerfLedger:
    def test_record_snapshot_and_gauges(self):
        led = perf.PerfLedger(window=4)
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
        assert sh["rooflinePct"]["last"] > 0
        assert sh["qps"] >= 0

    def test_global_ledger_exports_table_gauges(self):
        perf.PERF_LEDGER.record("gt", "fp", rows=100, time_ms=5.0, kernel_bytes=400.0)
        snap = METRICS.snapshot()
        assert snap["gauges"]["perf.gt.rowsPerSec"] == pytest.approx(20000.0)
        assert "perf.gt.bytesPerSec" in snap["gauges"]

    def test_sse_query_lands_in_global_ledger(self):
        eng = _engine(table="perfledger")
        eng.query("SELECT COUNT(*) FROM perfledger")
        snap = perf.PERF_LEDGER.snapshot()
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
    def test_entry_carries_kernel_cost_and_roofline(self):
        class R:
            stats = ExecutionStats()
            rows = [(1,)]

        R.stats.time_ms = 10.0
        R.stats.num_docs_scanned = 1000
        R.stats.kernel_bytes = 8.0e6
        R.stats.kernel_flops = 2.0e6
        R.stats.kernel_cost_source = "analytic"
        R.stats.compile_ms = 3.0
        R.stats.device_ms = 8.0
        log = SlowQueryLog(capacity=4, slow_ms=1e9)
        entry = log.record("SELECT 1", "fp", result=R())
        assert entry["kernelBytes"] == 8.0e6
        assert entry["costSource"] == "analytic"
        assert entry["rooflinePct"] > 0
        assert entry["rowsPerSec"] == pytest.approx(100000.0)

    def test_entry_without_cost_stays_lean(self):
        class R:
            stats = ExecutionStats()
            rows = []

        log = SlowQueryLog(capacity=4, slow_ms=1e9)
        entry = log.record("SELECT 1", "fp", result=R())
        assert "kernelBytes" not in entry


# ---------------------------------------------------------------------------
# bench-history regression gate
# ---------------------------------------------------------------------------
def _rec(scale=1.0, backend="xla", rows=1000, rv=0.02):
    return {
        "schema": 1,
        "bench": "ssb_groupby",
        "backend": backend,
        "rows": rows,
        "metrics": {
            "kernel_rows_per_sec": 1e6 * scale,
            "e2e_rows_per_sec": 5e5 * scale,
            "warm_p50_rows_per_sec": 8e5 * scale,
            "effective_bytes_per_sec": 9e6 * scale,
        },
        "noise": {"run_variance": rv},
    }


class TestRegressionGate:
    def test_identical_records_pass(self):
        v = perf.check_regression(_rec(), _rec())
        assert v["ok"] and len(v["checks"]) == 4

    def test_twenty_percent_drop_always_fails(self):
        # the acceptance bar: a true >=20% throughput regression trips the
        # gate regardless of measured noise
        v = perf.check_regression(_rec(scale=0.80), _rec(), threshold=None)
        assert not v["ok"] and v["reasons"]
        v_noisy = perf.check_regression(_rec(scale=0.80, rv=10.0), _rec(rv=10.0))
        assert not v_noisy["ok"]  # allowance clamps below 20%

    def test_small_drop_within_noise_passes(self):
        assert perf.check_regression(_rec(scale=0.90), _rec())["ok"]

    def test_incomparable_records_fail(self):
        v = perf.check_regression(_rec(backend="interpret"), _rec())
        assert not v["ok"] and any("incomparable" in r for r in v["reasons"])

    def test_empty_comparison_fails(self):
        v = perf.check_regression({"metrics": {}}, {"metrics": {}})
        assert not v["ok"] and "no gated metrics" in v["reasons"][0]

    def test_allowance_clamps(self):
        assert perf.regression_allowance(_rec(rv=0.0)) == pytest.approx(0.15)
        assert perf.regression_allowance(_rec(rv=1.0)) == pytest.approx(0.19)

    def test_history_roundtrip_skips_corrupt_lines(self, tmp_path):
        p = tmp_path / "hist.jsonl"
        perf.append_bench_history(str(p), _rec())
        p.write_text(p.read_text() + "{torn line\n")
        perf.append_bench_history(str(p), _rec(scale=1.1))
        hist = perf.load_bench_history(str(p))
        assert len(hist) == 2
        assert hist[-1]["metrics"]["kernel_rows_per_sec"] == pytest.approx(1.1e6)

    def test_bench_record_distills_report(self):
        report = {
            "value": 123.0,
            "value_e2e": 45.0,
            "run_variance": 0.07,
            "rows": 10,
            "backend": "xla",
            "effective_bytes_per_sec": 999.0,
            "distinct_literal_sweep": {"warm_p50_rows_per_sec": 77.0},
            "plan_cache": {"hit_rate": 0.9},
            "roofline": {"device_kind": "cpu", "kernel_roofline_pct": 1.5,
                         "cost_bytes_per_sec": 1000.0},
        }
        rec = perf.bench_record(report)
        assert rec["metrics"]["kernel_rows_per_sec"] == 123.0
        assert rec["metrics"]["warm_p50_rows_per_sec"] == 77.0
        assert rec["metrics"]["roofline_pct"] == 1.5
        assert rec["noise"]["run_variance"] == 0.07

    def test_cli_perf_check_exits_nonzero_on_synthetic_regression(self, tmp_path, capsys):
        from pinot_tpu.tools.cli import main

        hist = tmp_path / "bench_history.jsonl"
        base = tmp_path / "BENCH_BASELINE.json"
        base.write_text(json.dumps(_rec()))
        perf.append_bench_history(str(hist), _rec(scale=0.75))  # injected -25%
        rc = main(["perf", "--check", "--history", str(hist), "--baseline", str(base)])
        assert rc == 1
        out = capsys.readouterr().out
        assert "FAIL" in out

    def test_cli_perf_check_passes_on_healthy_run(self, tmp_path, capsys):
        from pinot_tpu.tools.cli import main

        hist = tmp_path / "bench_history.jsonl"
        base = tmp_path / "BENCH_BASELINE.json"
        base.write_text(json.dumps(_rec()))
        perf.append_bench_history(str(hist), _rec(scale=1.02))
        rc = main(["perf", "--check", "--history", str(hist), "--baseline", str(base)])
        assert rc == 0

    def test_cli_perf_check_fails_on_missing_history(self, tmp_path):
        from pinot_tpu.tools.cli import main

        base = tmp_path / "BENCH_BASELINE.json"
        base.write_text(json.dumps(_rec()))
        rc = main([
            "perf", "--check",
            "--history", str(tmp_path / "nope.jsonl"),
            "--baseline", str(base),
        ])
        assert rc == 1


@pytest.mark.slow
def test_repo_bench_baseline_gate_passes():
    """The committed bench history vs the pinned baseline must pass the
    gate — this is the regression check CI runs after a real bench run."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hist = os.path.join(root, "bench_history.jsonl")
    base = os.path.join(root, "BENCH_BASELINE.json")
    if not (os.path.exists(hist) and os.path.exists(base)):
        pytest.skip("no committed bench artifacts")
    latest = perf.load_bench_history(hist)[-1]
    with open(base, "r", encoding="utf-8") as f:
        baseline = json.load(f)
    verdict = perf.check_regression(latest, baseline)
    assert verdict["ok"], verdict["reasons"]
