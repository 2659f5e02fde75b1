"""HTTP query endpoint: the broker REST surface.

Reference parity: Pinot's broker query REST (POST /query/sql handled by
BaseSingleStageBrokerRequestHandler) + cursor endpoints + /health and
/metrics (JSON, or Prometheus text with ?format=prometheus) and the
/debug/queries slow-query surface.  Re-design: stdlib http.server on a daemon thread serving an
in-process QueryEngine or cluster Broker — the data plane stays in-process
(SURVEY.md §2.6); this surface exists for clients/tools parity.

Response shape follows BrokerResponse: {"resultTable": {"dataSchema":
{"columnNames": [...]}, "rows": [...]}, "numDocsScanned": ..., ...}.
"""
from __future__ import annotations

import json
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

import numpy as np

from pinot_tpu.query.cursors import ResponseStore
from pinot_tpu.query.result import ResultTable
from pinot_tpu.utils.interpreter import ACCEPT_LOOP_THREAD, WATCH
from pinot_tpu.utils.metrics import METRICS, annotate_root, mark, now_ns, stage


def _jsonable(v):
    if isinstance(v, np.generic):
        v = v.item()
    if isinstance(v, float) and (v != v or v in (float("inf"), float("-inf"))):
        return None
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    if isinstance(v, (tuple, list)):
        return [_jsonable(x) for x in v]
    return v


def broker_response(result: ResultTable) -> Dict[str, Any]:
    s = result.stats
    return {
        "resultTable": {
            "dataSchema": {"columnNames": list(result.columns)},
            "rows": [[_jsonable(v) for v in row] for row in result.rows],
        },
        "numRowsResultSet": len(result.rows),
        "numDocsScanned": s.num_docs_scanned,
        "numSegmentsQueried": s.num_segments_queried,
        "numSegmentsPruned": s.num_segments_pruned,
        "numSegmentsProcessed": s.num_segments_processed,
        "totalDocs": s.total_docs,
        "timeUsedMs": round(s.time_ms, 3),
        "requestId": s.query_id,
        "trace": s.trace,
        # fault surface (BrokerResponse partialResult / processingExceptions)
        "partialResult": bool(s.partial_result),
        "exceptions": list(s.exceptions),
        "numServersQueried": s.num_servers_queried,
        "numServersResponded": s.num_servers_responded,
    }


class _StampedServer(ThreadingHTTPServer):
    """The accept loop, with its two stamps.  The loop is the front door's one
    serial resource: every request waits for its turn of it, so what a turn
    takes (accept() back to the handler's thread made and started) is a
    timer of its own, updated on the loop's thread."""

    def get_request(self):
        request, address = super().get_request()
        # the handler finds the stamp where it finds the socket's peer: the
        # last item of its client_address
        return request, (*address, now_ns())

    def process_request(self, request, client_address):
        super().process_request(request, client_address)
        METRICS.timer("rest.acceptLoopMs").update((now_ns() - client_address[-1]) / 1e6)


class QueryServer:
    """Serves one engine-like object (anything with .sql or .query)."""

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 0):
        self.engine = engine
        self.cursors = ResponseStore()
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # quiet
                pass

            def setup(self):
                # the handler thread's first line: `http_head` runs from here
                # until the request line and the headers are parsed, and what
                # came before, on another thread, is the accept wait
                self.head = stage("http_head").__enter__()
                self.accept_ns = self.client_address[-1]
                mark("http_accepted", accept_wait_us=(self.head.t0_ns - self.accept_ns) // 1000)
                super().setup()

            def parse_request(self):
                ok = super().parse_request()
                self.head.__exit__(None, None, None)
                return ok

            def _send(self, code: int, payload: Dict[str, Any]) -> None:
                self._send_body(code, json.dumps(payload).encode("utf-8"))

            def _send_body(self, code: int, body: bytes, content_type: str = "application/json") -> None:
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                try:
                    url = urllib.parse.urlsplit(self.path)
                    qs = urllib.parse.parse_qs(url.query)
                    if url.path == "/health":
                        self._send(200, {"status": "OK"})
                    elif url.path == "/metrics":
                        # a broker engine federates its servers' registries
                        # into one labeled cluster exposition; plain engines
                        # fall back to this process's registry
                        fed = getattr(outer.engine, "federated_prometheus", None)
                        if qs.get("format", [""])[0] == "prometheus":
                            self._send_body(
                                200,
                                (fed() if fed is not None else METRICS.to_prometheus()).encode("utf-8"),
                                "text/plain; version=0.0.4; charset=utf-8",
                            )
                        else:
                            snap = METRICS.snapshot()
                            fed_json = getattr(outer.engine, "federated_snapshot", None)
                            if fed_json is not None:
                                snap["servers"] = fed_json()
                            self._send(200, snap)
                    elif url.path == "/debug/queries":
                        slow = getattr(outer.engine, "slow_queries", None)
                        if slow is None:
                            self._send(404, {"error": "engine has no slow-query log"})
                            return
                        limit = int(qs.get("limit", ["0"])[0]) or None
                        self._send(200, {"queries": slow.snapshot(limit)})
                    elif url.path == "/debug/interpreter":
                        # the interpreter lock's waiters, holders and the collector's pauses
                        # (utils/interpreter.py); ?watch=N turns the watch on for N seconds
                        if "watch" in qs:
                            WATCH.renew(float(qs["watch"][0]))
                        self._send(200, WATCH.snapshot())
                    elif url.path == "/debug/admission":
                        gov = getattr(outer.engine, "governor", None)
                        if gov is None:
                            self._send(404, {"error": "engine has no resource governor"})
                            return
                        self._send(200, gov.snapshot())
                    elif url.path == "/debug/perf":
                        # per-table/per-shape stats window (utils/perf.py):
                        # rolling rows/s, bytes/s, compile ms,
                        # plan-cache outcomes, QPS — the `cli perf` source
                        snap_fn = getattr(outer.engine, "perf_snapshot", None)
                        if snap_fn is not None:
                            self._send(200, snap_fn())
                        else:
                            from pinot_tpu.utils.perf import SHAPE_STATS

                            self._send(200, SHAPE_STATS.snapshot())
                    elif url.path == "/debug/autopilot":
                        # SLO autopilot view: knob values vs clamp bounds,
                        # last N controller decisions with triggering signal,
                        # per-table SLO state (cluster/autopilot.py)
                        snap_fn = getattr(outer.engine, "autopilot_snapshot", None)
                        if snap_fn is None:
                            self._send(404, {"error": "engine has no autopilot view"})
                            return
                        self._send(200, snap_fn())
                    elif url.path == "/debug/election":
                        # coordinator HA view: current leader + per-candidate
                        # lease/epoch/role state (cluster/election.py)
                        snap_fn = getattr(outer.engine, "election_snapshot", None)
                        if snap_fn is None:
                            self._send(404, {"error": "engine has no election view"})
                            return
                        self._send(200, snap_fn())
                    elif url.path.startswith("/cursors/"):
                        parts = url.path.strip("/").split("/")
                        cid = parts[1]
                        page = int(parts[2]) if len(parts) > 2 else 0
                        self._send(200, outer.cursors.fetch(cid, page))
                    else:
                        self._send(404, {"error": f"unknown path {self.path}"})
                except KeyError as e:
                    self._send(404, {"error": str(e)})
                except Exception as e:  # noqa: BLE001 - boundary
                    self._send(500, {"error": f"{type(e).__name__}: {e}"})

            def do_POST(self):
                """The front door's stages, each a profiler annotation and
                (for a query that is answered) one timer update: `http_head`
                (setup to the parsed headers), then read + decode the body,
                the engine call, build + serialise the answer, write it.
                Before them the accept wait (accept() on the loop's thread to
                setup on this one), and over all of them `doorMs`, accept()
                to the last byte written; what a request waited before
                accept() returned it (the kernel's listen queue, while the
                loop's thread waited for the interpreter) no clock of the
                program sees.  A traced answer carries what came
                before its root span on it (acceptT0Ns on t0Ns' clock,
                acceptWaitMs, headMs, httpReadMs); serialise and write cannot
                ride the payload they produce, and reach, like the rest, the
                slow-query log's entry of a request that was slow here.
                While the interpreter watch runs (a traced query started it,
                or `/debug/interpreter?watch=N`), and only then, the thread's
                CPU clock is read at the stamps around the engine call and the
                serialising and at the end: `doorCpuMs` (the thread's whole
                life: its clock starts at 0), `engineCpuMs`, `serializeCpuMs`,
                timers beside their walls and the handler's share of the
                interpreter's CPU (`runtime.cpuMs.handler`)."""
                head, accept_ns = self.head, self.accept_ns
                wait_ms = (head.t0_ns - accept_ns) / 1e6
                watched = WATCH.running
                try:
                    with stage("http_read") as read:
                        n = int(self.headers.get("Content-Length", 0))
                        req = json.loads(self.rfile.read(n) or b"{}")
                    if self.path not in ("/query/sql", "/query"):
                        self._send(404, {"error": f"unknown path {self.path}"})
                        return
                    sql = req.get("sql", "")
                    run = getattr(outer.engine, "sql", None) or outer.engine.query
                    cpu0 = time.thread_time() if watched else 0.0
                    with stage("http_engine") as eng:
                        result = run(sql)
                    cpu1 = time.thread_time() if watched else 0.0
                    if result.stats.trace is not None:
                        annotate_root(
                            result.stats.trace, acceptT0Ns=accept_ns, acceptWaitMs=round(wait_ms, 3),
                            headMs=round(head.ms, 3), httpReadMs=round(read.ms, 3),
                        )
                    with stage("http_serialize") as ser:
                        payload = broker_response(result)
                        if req.get("useCursor"):
                            cid = outer.cursors.register(result, int(req.get("pageSize", 1000)))
                            payload["cursorId"] = cid
                            payload["resultTable"]["rows"] = payload["resultTable"]["rows"][
                                : int(req.get("pageSize", 1000))
                            ]
                        body = json.dumps(payload).encode("utf-8")
                    cpu2 = time.thread_time() if watched else 0.0
                    with stage("http_write") as write:
                        self._send_body(200, body)
                    door = {
                        "acceptWaitMs": wait_ms, "headMs": head.ms, "readMs": read.ms, "engineMs": eng.ms,
                        "serializeMs": ser.ms, "writeMs": write.ms, "doorMs": (now_ns() - accept_ns) / 1e6,
                    }
                    for name, ms in door.items():  # rest.acceptWaitMs ... rest.doorMs
                        METRICS.timer("rest." + name).update(ms)
                    if watched:
                        door_cpu = time.thread_time() * 1000.0
                        METRICS.timer("rest.doorCpuMs").update(door_cpu)
                        METRICS.timer("rest.engineCpuMs").update((cpu1 - cpu0) * 1000.0)
                        METRICS.timer("rest.serializeCpuMs").update((cpu2 - cpu1) * 1000.0)
                        METRICS.counter("runtime.cpuMs.handler").inc(door_cpu)
                    slow = getattr(outer.engine, "slow_queries", None)
                    if slow is not None:
                        slow.door(result.stats, door, accept_ns)
                except Exception as e:  # noqa: BLE001 - boundary
                    from pinot_tpu.analysis.plan_check import PlanCheckError
                    from pinot_tpu.cluster.admission import (
                        QueryKilledError,
                        ReservationError,
                        TooManyRequestsError,
                    )
                    from pinot_tpu.cluster.broker import (
                        NoReplicaAvailableError,
                        QuotaExceededError,
                        ScatterGatherError,
                    )
                    from pinot_tpu.cluster.election import NotLeaderError
                    from pinot_tpu.query.safety import AdmissionError, QueryTimeoutError

                    if isinstance(e, NotLeaderError):
                        # control-plane leadership moved and the bounded
                        # failover park expired: retryable 503 — the standby
                        # finishes taking over and the next attempt serves
                        self._send(503, {"error": str(e), "errorCode": "NOT_LEADER"})
                    elif isinstance(e, QuotaExceededError):
                        # the reference's 429 QUERY_QUOTA_EXCEEDED contract:
                        # throttled clients must be able to back off
                        self._send(429, {"error": str(e), "errorCode": "QUERY_QUOTA_EXCEEDED"})
                    elif isinstance(e, TooManyRequestsError):
                        # admission shed: over the cost-rate budget, rejected
                        # up front with the minted query id for correlation
                        self._send(
                            429,
                            {
                                "error": str(e),
                                "errorCode": "TOO_MANY_REQUESTS_ERROR",
                                "requestId": e.query_id,
                            },
                        )
                    elif isinstance(e, QueryKilledError):
                        # watchdog killed it mid-flight and the query did not
                        # allow partial results: retryable 503 with the reason
                        self._send(
                            503,
                            {
                                "error": str(e),
                                "errorCode": "QUERY_KILLED",
                                "requestId": e.query_id,
                                "reason": e.reason,
                            },
                        )
                    elif isinstance(e, ReservationError):
                        # HBM/host reservation refused: the tier is at
                        # capacity RIGHT NOW — retryable as queries drain.
                        # Checked before the AdmissionError base class below.
                        self._send(
                            503,
                            {
                                "error": str(e),
                                "errorCode": "SERVER_OUT_OF_CAPACITY",
                                "requestId": e.query_id,
                            },
                        )
                    elif isinstance(e, QueryTimeoutError):
                        # deadline blew anywhere in the scatter: 408, the
                        # reference's EXECUTION_TIMEOUT_ERROR contract
                        self._send(408, {"error": str(e), "errorCode": "EXECUTION_TIMEOUT_ERROR"})
                    elif isinstance(e, AdmissionError):
                        # resource admission refused up-front: retryable 503
                        self._send(
                            503,
                            {"error": str(e), "errorCode": "SERVER_RESOURCE_LIMIT_EXCEEDED"},
                        )
                    elif isinstance(e, ScatterGatherError):
                        # every replica of some segment failed and the query
                        # did not allow partial results
                        self._send(
                            500,
                            {
                                "error": str(e),
                                "errorCode": "SERVER_SCATTER_ERROR",
                                "exceptions": e.exceptions,
                            },
                        )
                    elif isinstance(e, NoReplicaAvailableError):
                        # a segment lost every live replica: retryable 503
                        # (capacity may come back), distinct from scatter
                        # failures so clients can tell "down" from "flaky"
                        self._send(503, {"error": str(e), "errorCode": "NO_REPLICA_AVAILABLE"})
                    elif isinstance(e, PlanCheckError):
                        # statically-rejected plan: a 400 with the machine
                        # code, never a tracer traceback (analysis/plan_check)
                        self._send(400, e.to_dict())
                    else:
                        self._send(500, {"error": f"{type(e).__name__}: {e}"})

        self._httpd = _StampedServer((host, port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "QueryServer":
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True, name=ACCEPT_LOOP_THREAD)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


class PinotClient:
    """Minimal python client over the REST surface (pinot-java-client /
    pinotdb analog)."""

    def __init__(self, url: str):
        self.url = url.rstrip("/")

    def execute(self, sql: str, **kw) -> Dict[str, Any]:
        import urllib.request

        body = json.dumps({"sql": sql, **kw}).encode("utf-8")
        req = urllib.request.Request(
            self.url + "/query/sql", data=body, headers={"Content-Type": "application/json"}
        )
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read().decode("utf-8"))

    def fetch_cursor(self, cursor_id: str, page: int) -> Dict[str, Any]:
        import urllib.request

        with urllib.request.urlopen(f"{self.url}/cursors/{cursor_id}/{page}") as resp:
            return json.loads(resp.read().decode("utf-8"))
