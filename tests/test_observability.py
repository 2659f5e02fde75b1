"""Observability surface: trace trees under injected faults, latency
histograms, Prometheus exposition, the slow-query log and EXPLAIN ANALYZE.

The trace assertions pin the PR's acceptance shape: one span tree per query
with the broker scatter, per-round failover, per-server execute (grafted
server subtree with dispatch/device_wait/collect) all visible, durations
non-zero where work happened.
"""
import json
import threading
import urllib.request

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, FaultPlan, ServerInstance
from pinot_tpu.cluster.rest import QueryServer
from pinot_tpu.query.engine import QueryEngine
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils.metrics import METRICS, Histogram, MetricsRegistry
from pinot_tpu.utils.slowlog import SlowQueryLog


def _schema():
    return Schema(
        "t",
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


def _engine(n_segments=3, rows=200):
    eng = QueryEngine()
    eng.register_table(_schema())
    for i in range(n_segments):
        eng.add_segment("t", build_segment(_schema(), _data(rows, 100 + i), f"seg{i}"))
    return eng


def _cluster(n_servers=2, replication=2, n_segments=4, rows=200):
    coord = Coordinator(replication=replication)
    for i in range(n_servers):
        coord.register_server(ServerInstance(f"server{i}"))
    coord.add_table(_schema(), TableConfig(name="t"))
    for i in range(n_segments):
        coord.add_segment("t", build_segment(_schema(), _data(rows, 100 + i), f"seg{i}"))
    return coord


def _spans(node, out=None):
    """Flatten a span tree into {name: [node, ...]}."""
    if out is None:
        out = {}
    out.setdefault(node["name"], []).append(node)
    for c in node.get("children", []):
        _spans(c, out)
    return out


# ---------------------------------------------------------------------------
# Histogram + registry
# ---------------------------------------------------------------------------
class TestHistogram:
    def test_quantiles_bracket_the_data(self):
        h = Histogram()
        for ms in range(1, 101):  # 1..100 ms, ~uniform
            h.update(float(ms))
        s = h._snap()
        assert s["count"] == 100
        assert s["minMs"] == 1.0 and s["maxMs"] == 100.0
        # log-bucketed interpolation: a few percent of bucket width
        assert 30 <= s["p50Ms"] <= 70
        assert 75 <= s["p95Ms"] <= 100
        assert s["p95Ms"] <= s["p99Ms"] <= 100

    def test_buckets_are_cumulative_and_end_at_inf(self):
        h = Histogram()
        for ms in (0.05, 1.0, 10.0, 1e9):  # below first bound + overflow
            h.update(ms)
        b = h.buckets()
        assert b[-1][0] == float("inf") and b[-1][1] == 4
        counts = [c for _, c in b]
        assert counts == sorted(counts), "bucket counts must be cumulative"

    def test_empty_histogram_snapshots_zeros(self):
        s = Histogram()._snap()
        assert s == {
            "count": 0, "meanMs": 0.0, "maxMs": 0.0, "minMs": 0.0,
            "p50Ms": 0.0, "p95Ms": 0.0, "p99Ms": 0.0,
        }

    def test_concurrent_updates_are_exact(self):
        reg = MetricsRegistry()
        n, threads = 2000, 8

        def work():
            for i in range(n):
                reg.counter("c").inc()
                reg.histogram("h").update(float(i % 50))
                reg.gauge("g").add(1.0)

        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        snap = reg.snapshot()
        assert snap["counters"]["c"] == n * threads
        assert snap["histograms"]["h"]["count"] == n * threads
        assert snap["gauges"]["g"] == float(n * threads)

    def test_snapshot_during_concurrent_registration(self):
        reg = MetricsRegistry()
        stop = threading.Event()
        errors = []

        def register():
            i = 0
            while not stop.is_set():
                reg.counter(f"series{i % 500}").inc()
                i += 1

        def snap():
            try:
                for _ in range(200):
                    reg.snapshot()
                    reg.to_prometheus()
            except Exception as e:  # pragma: no cover - the failure under test
                errors.append(e)

        reg_t = threading.Thread(target=register)
        snap_t = threading.Thread(target=snap)
        reg_t.start()
        snap_t.start()
        snap_t.join()
        stop.set()
        reg_t.join()
        assert errors == []


class TestPrometheusExposition:
    def test_counter_gauge_histogram_render(self):
        reg = MetricsRegistry()
        reg.counter("broker.queries").inc(3)
        reg.gauge("broker.openBreakers").set(1)
        reg.timer("plan").update(2.0)
        for ms in (0.5, 5.0, 500.0):
            reg.histogram("queryLatency").update(ms)
        text = reg.to_prometheus()
        lines = text.splitlines()
        assert "pinot_broker_queries_total 3" in lines
        assert "pinot_broker_openBreakers 1" in lines
        assert "# TYPE pinot_queryLatency_ms histogram" in lines
        assert 'pinot_queryLatency_ms_bucket{le="+Inf"} 3' in lines
        assert "pinot_queryLatency_ms_count 3" in lines
        assert any(l.startswith("pinot_queryLatency_ms_sum ") for l in lines)
        assert "pinot_plan_ms_count 1" in lines
        # bucket series are monotone non-decreasing
        cums = [
            int(l.rsplit(" ", 1)[1])
            for l in lines
            if l.startswith("pinot_queryLatency_ms_bucket")
        ]
        assert cums == sorted(cums)

    def test_names_are_sanitized(self):
        reg = MetricsRegistry()
        reg.counter("server.segmentBytes.my-table").inc()
        assert "pinot_server_segmentBytes_my_table_total 1" in reg.to_prometheus()


# ---------------------------------------------------------------------------
# Trace propagation
# ---------------------------------------------------------------------------
class TestEngineTrace:
    def test_device_host_split_spans(self):
        eng = _engine()
        res = eng.query("SET trace = true; SELECT city, COUNT(*) FROM t GROUP BY city")
        spans = _spans(res.stats.trace)
        assert res.stats.query_id and res.stats.query_id.startswith("engine_")
        assert spans["query"][0]["attrs"]["queryId"] == res.stats.query_id
        # three segments of one plan ride two jitted calls (widths 2 + 1 of
        # the ladder) and come back in two fetches
        dw = spans["device_wait"][0]
        assert dw["attrs"]["launches"] == 2
        assert len([n for n in spans if n.startswith("launch:")]) == 3
        assert [n["attrs"]["width"] for n in spans["launch_enqueue"]] == [2, 1]
        assert [n["attrs"]["segments"] for n in spans["collect"]] == [2, 1]

    def test_untraced_query_has_no_id_overhead_fields(self):
        eng = _engine()
        res = eng.query("SELECT COUNT(*) FROM t")
        assert res.stats.trace is None
        assert res.stats.query_id is not None  # id minted regardless


class TestBrokerFaultTrace:
    def test_single_tree_with_failover_rounds(self):
        """One server killed mid-scatter: the finished trace is ONE tree
        holding the broker scatter, both rounds, the failed server_execute
        (error + breaker state) and each surviving server's grafted subtree
        with dispatch/device_wait/collect spans."""
        coord = _cluster()
        FaultPlan(seed=7).fail_server("server0", on_call=1).attach(coord)
        broker = Broker(coord)
        broker._sleep = lambda s: None
        res = broker.query("SET trace = true; SELECT city, COUNT(*), SUM(v) FROM t GROUP BY city")
        tr = res.stats.trace
        assert tr["name"] == "query"
        assert tr["attrs"]["queryId"] == res.stats.query_id
        spans = _spans(tr)
        assert "scatter" in spans and "round:0" in spans and "round:1" in spans
        execs = spans["server_execute"]
        failed = [s for s in execs if "error" in s.get("attrs", {})]
        assert len(failed) == 1
        assert failed[0]["attrs"]["server"] == "server0"
        assert "breaker" in failed[0]["attrs"]
        # surviving calls graft the server-built subtree under themselves
        ok = [s for s in execs if "error" not in s.get("attrs", {})]
        assert ok, "at least one server call must succeed"
        for s in ok:
            sub = [c for c in s.get("children", []) if c["name"].startswith("server:")]
            assert len(sub) == 1
            sub_spans = _spans(sub[0])
            assert "dispatch" in sub_spans
            assert "device_wait" in sub_spans
            assert "collect" in sub_spans
            assert sub[0]["attrs"]["backend"]
            assert sub[0]["ms"] > 0
        assert spans["dispatch"][0]["ms"] > 0
        assert tr["ms"] > 0

    def test_breaker_and_inflight_gauges_published(self):
        coord = _cluster()
        FaultPlan(seed=7).fail_server("server0", on_call=1).attach(coord)
        broker = Broker(coord)
        broker._sleep = lambda s: None
        broker.query("SELECT COUNT(*) FROM t")
        snap = METRICS.snapshot()
        assert "broker.openBreakers" in snap["gauges"]
        assert "broker.breakerOpen.server0" in snap["gauges"]
        assert snap["gauges"]["broker.inFlightScatters"] == 0.0
        assert snap["histograms"]["broker.queryLatency"]["count"] == 1
        assert snap["gauges"]["server.segmentBytes.t"] > 0


# ---------------------------------------------------------------------------
# Slow-query log
# ---------------------------------------------------------------------------
class TestSlowQueryLog:
    def test_ring_evicts_oldest(self):
        log = SlowQueryLog(capacity=4, slow_ms=1e12)
        for i in range(10):
            log.record(f"SELECT {i}", f"fp{i}")
        snap = log.snapshot()
        assert len(log) == 4 and len(snap) == 4
        assert [e["sql"] for e in snap] == ["SELECT 9", "SELECT 8", "SELECT 7", "SELECT 6"]

    def test_trace_kept_only_over_threshold(self):
        log = SlowQueryLog(capacity=8, slow_ms=50.0)

        class R:
            rows = [(1,)]

            class stats:
                time_ms = 0.0
                query_id = "q"
                num_docs_scanned = 1
                num_segments_processed = 1
                partial_result = False
                exceptions = []
                trace = {"name": "query", "ms": 1.0}

        R.stats.time_ms = 10.0
        fast = log.record("SELECT 1", "fp", R)
        R.stats.time_ms = 90.0
        slow = log.record("SELECT 2", "fp", R)
        assert "trace" not in fast and slow["trace"]["name"] == "query"

    def test_errors_are_logged_and_counted(self):
        eng = _engine()
        with pytest.raises(Exception):
            eng.query("SELECT nope FROM t")
        e = eng.slow_queries.snapshot(1)[0]
        assert "error" in e and "nope" in e["sql"]
        assert METRICS.snapshot()["counters"]["broker.slowQueries"] >= 1

    def test_engine_records_every_query_newest_first(self):
        eng = _engine()
        eng.query("SELECT COUNT(*) FROM t")
        eng.query("SELECT SUM(v) FROM t")
        snap = eng.slow_queries.snapshot()
        assert len(snap) == 2
        assert "SUM" in snap[0]["sql"]  # newest first
        assert snap[0]["queryId"].startswith("engine_")
        assert snap[0]["rows"] == 1 and snap[0]["numDocsScanned"] > 0


# ---------------------------------------------------------------------------
# REST + CLI surface
# ---------------------------------------------------------------------------
class TestRestSurface:
    @pytest.fixture()
    def server(self):
        srv = QueryServer(_engine()).start()
        yield srv
        srv.stop()

    def _get(self, srv, path):
        with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}{path}") as r:
            return r.headers.get("Content-Type", ""), r.read().decode("utf-8")

    def _post(self, srv, sql):
        body = json.dumps({"sql": sql}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/query/sql", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as r:
            return json.loads(r.read().decode("utf-8"))

    def test_prometheus_format_and_json_default(self, server):
        self._post(server, "SELECT COUNT(*) FROM t")
        ctype, text = self._get(server, "/metrics?format=prometheus")
        assert ctype.startswith("text/plain")
        assert "pinot_queries_total" in text
        assert 'pinot_queryLatency_ms_bucket{le="+Inf"} 1' in text
        ctype, body = self._get(server, "/metrics")
        assert ctype.startswith("application/json")
        snap = json.loads(body)
        assert "counters" in snap and "histograms" in snap

    def test_debug_queries_and_request_id(self, server):
        resp = self._post(server, "SELECT COUNT(*) FROM t")
        assert resp["requestId"].startswith("engine_")
        _, body = self._get(server, "/debug/queries?limit=5")
        entries = json.loads(body)["queries"]
        assert entries and entries[0]["queryId"] == resp["requestId"]

    def test_cli_slow_queries(self, server, capsys):
        from pinot_tpu.tools.cli import main

        self._post(server, "SELECT city, COUNT(*) FROM t GROUP BY city")
        rc = main(["slow-queries", "--url", f"http://127.0.0.1:{server.port}", "--limit", "3"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "GROUP BY city" in out and "qid=engine_" in out
        rc = main(["slow-queries", "--url", f"http://127.0.0.1:{server.port}", "--json"])
        assert rc == 0
        assert json.loads(capsys.readouterr().out)


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE
# ---------------------------------------------------------------------------
class TestExplainAnalyze:
    def test_engine_operator_rows_join_measured_ms(self):
        eng = _engine()
        res = eng.query("EXPLAIN ANALYZE SELECT city, SUM(v) FROM t WHERE city = 'sf' GROUP BY city")
        assert res.columns == [
            "Operator", "Operator_Id", "Parent_Id", "Actual_Ms", "Rows", "Bytes",
        ]
        by_op = {r[0].split("(")[0]: r for r in res.rows if not r[0].startswith("TRACE")}
        assert by_op["BROKER_REDUCE"][3] is not None and by_op["BROKER_REDUCE"][3] >= 0
        assert by_op["GROUP_BY"][3] is not None and by_op["GROUP_BY"][3] > 0
        assert by_op["FILTER_SCAN"][4] == res.stats.num_docs_scanned
        trace_rows = [r for r in res.rows if r[0].startswith("TRACE")]
        assert trace_rows, "measured span tree must follow the operator rows"
        assert trace_rows[0][2] == 0  # trace root parented at the table root
        assert any("device_wait" in r[0] for r in trace_rows)
        # ids are unique and parents resolve
        ids = [r[1] for r in res.rows]
        assert len(ids) == len(set(ids))
        assert all(r[2] in set(ids) | {0} for r in res.rows)

    def test_broker_explain_analyze_executes_with_trace(self):
        broker = Broker(_cluster())
        res = broker.query("EXPLAIN ANALYZE SELECT COUNT(*) FROM t")
        assert res.columns[3] == "Actual_Ms"
        trace_rows = [r for r in res.rows if r[0].startswith("TRACE")]
        assert any("server_execute" in r[0] for r in trace_rows)
        assert any("scatter" in r[0] for r in trace_rows)
        assert res.stats.num_docs_scanned > 0  # it really executed

    def test_explain_plan_for_still_static(self):
        eng = _engine()
        res = eng.query("EXPLAIN PLAN FOR SELECT COUNT(*) FROM t")
        assert res.columns == ["Operator", "Operator_Id", "Parent_Id"]
        assert METRICS.snapshot()["counters"].get("docsScanned", 0) == 0

    def test_explain_garbage_still_fails(self):
        from pinot_tpu.sql.parser import SqlParseError

        eng = _engine()
        with pytest.raises(SqlParseError):
            eng.query("EXPLAIN NONSENSE SELECT COUNT(*) FROM t")


# ---------------------------------------------------------------------------
# Stages: span starts and CPU time, the launch split, timers, annotations
# ---------------------------------------------------------------------------
GROUP_SQL = "SELECT city, COUNT(*), SUM(v) FROM t WHERE v > 5 GROUP BY city ORDER BY city"
STAGE_TIMERS = {
    "server": ["server.launchPlanMs", "server.launchShipMs", "server.launchEnqueueMs", "server.launchReleaseMs",
               "server.collectMs"],
    "process": ["broker.parseMs", "broker.reduceMs", "rest.readMs", "rest.serializeMs", "rest.writeMs"],
}


def _placed(node, base_ns=None, thread=None, out=None, parent=None):
    """Every span as (node, start_ns, end_ns, thread, parent) on the
    process's clock: a root (`t0Ns`) re-bases its subtree."""
    if out is None:
        out = []
    if "t0Ns" in node:
        base_ns, thread = node["t0Ns"] - node["startMs"] * 1e6, node["thread"]
    start = base_ns + node["startMs"] * 1e6
    me = (node, start, start + node["ms"] * 1e6, thread, parent)
    out.append(me)
    for c in node.get("children", []):
        _placed(c, base_ns, thread, out, me)
    return out


class TestStages:
    def _post(self, srv, sql):
        body = json.dumps({"sql": sql}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/query/sql", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as r:
            return json.loads(r.read().decode("utf-8"))

    def test_to_dict_keeps_the_old_keys_and_adds_start_cpu_and_root_clock(self):
        from pinot_tpu.utils.metrics import Trace

        t = Trace(True, query_id="q_1")
        with t.span("outer", cpu=True, segments=2):
            with t.span("inner"):
                sum(range(20000))
        d = t.finish()
        outer = d["children"][0]
        inner = outer["children"][0]
        assert {"name", "ms", "attrs", "children"} <= set(outer)  # as before
        # a span that measured its CPU time says so among its attrs too: where the benchmark's readers look
        assert (outer["name"], outer["attrs"], inner["name"]) == ("outer", {"segments": 2, "cpuMs": outer["cpuMs"]}, "inner")
        assert "attrs" not in inner and "children" not in inner  # still left out when empty
        for node in (d, outer, inner):
            assert node["startMs"] >= 0.0
        for node in (d, outer):  # roots, and spans opened with cpu=True
            assert 0.0 < node["cpuMs"] <= node["ms"] + 1.0  # the loop ran on this thread
        assert "cpuMs" not in inner  # the CPU clock is a system call: only where asked for
        assert isinstance(d["t0Ns"], int) and d["thread"] == threading.current_thread().name
        assert "t0Ns" not in outer and "thread" not in outer  # roots only
        assert d["startMs"] == 0.0 and outer["startMs"] <= inner["startMs"]

    def test_children_lie_inside_parents_and_siblings_do_not_overlap(self):
        coord = _cluster(n_servers=2, replication=1, n_segments=4)
        res = Broker(coord).query("SET trace = true; " + GROUP_SQL)
        placed = _placed(res.stats.trace)
        assert len([p for p in placed if "t0Ns" in p[0]]) == 3  # query + two grafted server roots
        slack = 2_000.0  # ns: startMs and ms are rounded to the microsecond
        by_parent = {}
        for node, start, end, thread, parent in placed:
            if parent is None:
                continue
            assert parent[1] - slack <= start and end <= parent[2] + slack, (node["name"], parent[0]["name"])
            if thread == parent[3]:
                by_parent.setdefault(id(parent[0]), []).append((start, end, node["name"]))
        for sibs in by_parent.values():
            sibs.sort()
            for (_, end_a, a), (start_b, _, b) in zip(sibs, sibs[1:]):
                assert end_a <= start_b + slack, (a, b)

    def test_launch_split_under_every_segment_miss_then_hit(self):
        from pinot_tpu.query.planner import plan_cache_clear

        coord = _cluster(n_servers=1, replication=1, n_segments=2)
        broker = Broker(coord)
        plan_cache_clear()  # process-wide: an earlier test may have compiled this shape
        res = broker.query("SET trace = true; " + GROUP_SQL)
        launches = [n for name, ns in _spans(res.stats.trace).items() if name.startswith("launch:") for n in ns]
        assert sorted(n["name"] for n in launches) == ["launch:seg0", "launch:seg1"]
        caches = {}
        for n in launches:
            kids = n["children"]
            assert [k["name"] for k in kids] == ["launch_plan", "launch_ship"]
            assert all(k["attrs"]["segment"] == n["attrs"]["segment"] for k in kids)
            assert n["attrs"]["cpuMs"] == n["cpuMs"] and n["attrs"]["kernelBytes"] > 0
            plan, ship = kids
            caches[n["name"]] = plan["attrs"]["cache"]
            assert ship["attrs"]["params"] >= 0
            assert sum(k["ms"] for k in kids) <= n["ms"] + 0.01
        assert caches == {"launch:seg0": "miss", "launch:seg1": "hit"}
        # the two segments share one compiled kernel: ONE jitted call, a
        # sibling of their launch:<segment> spans under dispatch, and ONE fetch
        (dispatch,) = _spans(res.stats.trace)["dispatch"]
        # (and of the pruner's pass over the query's segments, PR 47)
        assert [k["name"] for k in dispatch["children"]] == ["prune", "launch:seg0", "launch:seg1", "launch_enqueue"]
        assert dispatch["children"][0]["attrs"] == {"segments": 2, "pruned": 0}
        enqueue = dispatch["children"][3]
        assert enqueue["attrs"]["kind"] == "groupby_dense" and enqueue["attrs"]["backend"]
        assert enqueue["attrs"]["segments"] == enqueue["attrs"]["width"] == dispatch["attrs"]["launches"] * 2 == 2
        assert [k["name"] for k in enqueue["children"]] == ["launch_release"]  # its arguments dropped
        assert enqueue["attrs"]["firstLaunch"] and enqueue["attrs"]["compileMs"] > 0  # the group program's
        (collect,) = _spans(res.stats.trace)["collect"]
        assert collect["attrs"]["segments"] == 2 and collect["attrs"]["docs"] > 0
        # warm: the same program, no first launch
        res = broker.query("SET trace = true; " + GROUP_SQL)
        (enqueue,) = _spans(res.stats.trace)["launch_enqueue"]
        assert "firstLaunch" not in enqueue["attrs"] and enqueue["attrs"]["width"] == 2

    def test_untraced_query_moves_launches_and_every_stage_timer_once(self):
        coord = _cluster(n_servers=1, replication=1, n_segments=3)
        server = coord.servers["server0"]
        front = QueryServer(Broker(coord)).start()
        try:
            def counts():
                s, p = server.metrics.snapshot(), METRICS.snapshot()
                return (s["counters"].get("server.launches", 0),
                        {k: s["timers"].get(k, {"count": 0})["count"] for k in STAGE_TIMERS["server"]},
                        {k: p["timers"].get(k, {"count": 0})["count"] for k in STAGE_TIMERS["process"]})

            def after_answer_of(sql, before):
                """The answer, and the counts once the front door has finished
                with the request: it updates rest.readMs / rest.writeMs after
                the client has its answer."""
                resp = self._post(front, sql)
                pause = threading.Event()
                for _ in range(500):
                    after = counts()
                    if after[2]["rest.writeMs"] > before[2]["rest.writeMs"]:
                        break
                    pause.wait(0.01)
                return resp, after

            # compiles; the counts below are of the second query
            _, before = after_answer_of(GROUP_SQL, counts())
            resp, after = after_answer_of(GROUP_SQL, before)
            assert resp["trace"] is None
        finally:
            front.stop()
        assert after[0] - before[0] == 2  # server.launches counts jitted calls: three segments ride 2 + 1
        assert {k: after[1][k] - before[1][k] for k in after[1]} == dict.fromkeys(STAGE_TIMERS["server"], 1)
        assert {k: after[2][k] - before[2][k] for k in after[2]} == dict.fromkeys(STAGE_TIMERS["process"], 1)
        assert "server.kernelBytes" not in server.metrics.snapshot()["counters"]  # removed: nothing read it

    def test_traced_answer_carries_front_door_attrs_and_equals_the_untraced(self):
        coord = _cluster(n_servers=2, replication=1, n_segments=4)
        front = QueryServer(Broker(coord)).start()
        try:
            plain = self._post(front, GROUP_SQL)
            traced = self._post(front, "SET trace = true; " + GROUP_SQL)
        finally:
            front.stop()
        assert plain["trace"] is None and traced["trace"]["name"] == "query"
        assert traced["resultTable"] == plain["resultTable"] and len(plain["resultTable"]["rows"]) == 3
        for key in ("numDocsScanned", "numSegmentsQueried", "numSegmentsProcessed", "totalDocs"):
            assert traced[key] == plain[key]
        attrs = traced["trace"]["attrs"]
        assert attrs["parseMs"] > 0 and attrs["httpReadMs"] > 0
        roots = [n for ns in _spans(traced["trace"]).values() for n in ns if n["name"].startswith("server:")]
        assert [r["attrs"]["queryId"] for r in roots] == [traced["requestId"]] * 2  # the broker's id travels

    def test_query_inside_a_profiler_session_leaves_annotations_in_the_host_plane(self, tmp_path):
        """The sandbox's profiler records on the CPU, so this reads a real
        trace.  Its own time limit: the session runs on a side thread that
        is given 120 s."""
        import glob

        import jax
        from jax.profiler import ProfileData

        coord = _cluster(n_servers=1, replication=1, n_segments=2)
        broker = Broker(coord)
        broker.query(GROUP_SQL)  # compile outside the session
        got = {}

        def session():
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)
            try:
                got["res"] = broker.query(GROUP_SQL)  # untraced: annotations need no SET trace
            finally:
                jax.profiler.stop_trace()

        worker = threading.Thread(target=session, daemon=True)
        worker.start()
        worker.join(120.0)
        assert not worker.is_alive(), "the profiler session did not end inside its time limit"
        assert got["res"].stats.trace is None
        paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        assert paths, "the profiler wrote no trace"
        events = [
            (e.name, dict(e.stats))
            for plane in ProfileData.from_file(paths[0]).planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
        ]
        names = {n for n, _ in events}
        for want in ("sql_parse", "plan", "prune", "scatter", "round:0", "server_execute", "dispatch",
                     "launch:seg0", "launch:seg1", "launch_plan", "launch_ship", "launch_enqueue",
                     "launch_release", "collect", "reduce"):
            assert want in names, want
        assert "device_wait" not in names  # the fence is the traced path's alone
        qid = got["res"].stats.query_id
        launches = [st for n, st in events if n.startswith("launch:")]
        assert [st["query_id"] for st in launches] == [qid, qid]
        assert sorted(st["segment"] for st in launches) == ["seg0", "seg1"]
        parts = [st for n, st in events if n in ("launch_plan", "launch_ship", "launch_enqueue")]
        # plan and ship a segment, ONE jitted call for the two
        assert len(parts) == 5 and {st["query_id"] for st in parts} == {qid}
        assert [st["segments"] for n, st in events if n == "launch_enqueue"] == [2]
