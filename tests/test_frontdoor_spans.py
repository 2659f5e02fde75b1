"""Where a request waits at the front door (cluster/rest.py), by the program's
own stamps: accept() on the accept loop's thread, `process_request` back, the
handler thread's first line (`setup`), the parsed headers, the four stages
that were there, the last byte written.

Over a real QueryServer on loopback.  What is held: a traced answer carries
the waits before its root span on that span; the always-on timers move once a
request, traced or not; a wait put on purpose before the handler's thread
starts or before the client sends shows up under the right name and under
no other; the stages are profiler annotations through
`Stage`'s own path; and a request that was slow AT THE DOOR keeps its own
record in the slow-query log, traced or not.
"""
import glob
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster import rest
from pinot_tpu.cluster.rest import QueryServer
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils import metrics
from pinot_tpu.utils.metrics import METRICS

SQL = "SELECT region, SUM(rev) FROM door GROUP BY region ORDER BY region"
DOOR_TIMERS = ("rest.acceptLoopMs", "rest.acceptWaitMs", "rest.headMs", "rest.engineMs", "rest.doorMs")
STAGES = ("acceptWaitMs", "headMs", "readMs", "engineMs", "serializeMs", "writeMs")
NAP_S = 0.3


@pytest.fixture(scope="module")
def broker():
    schema = Schema(
        "door",
        [FieldSpec("region", DataType.INT), FieldSpec("rev", DataType.LONG, role=FieldRole.METRIC)],
    )
    coord = Coordinator(replication=1)
    coord.register_server(ServerInstance("server0"))
    coord.add_table(schema, TableConfig(name="door"))
    rng = np.random.default_rng(39)
    for i in range(3):
        block = {"region": rng.integers(0, 4, 200).astype(np.int32), "rev": rng.integers(1, 10**6, 200)}
        coord.add_segment("door", build_segment(schema, block, f"seg{i}"))
    b = Broker(coord)
    b.query(SQL)  # compile outside every case
    b.query("SET trace = true; " + SQL)
    return b


@pytest.fixture()
def front(broker):
    broker.slow_queries._entries.clear()
    srv = QueryServer(broker).start()
    yield srv
    srv.stop()


def _post(front, sql=SQL):
    body = json.dumps({"sql": sql}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{front.port}/query/sql", data=body, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read().decode("utf-8"))


def _get(front, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{front.port}{path}", timeout=60) as r:
        return json.loads(r.read().decode("utf-8"))


def _counts(names=DOOR_TIMERS):
    timers = METRICS.snapshot()["timers"]
    return {n: timers.get(n, {"count": 0})["count"] for n in names}


def _settled(counts_before, by=1, names=("rest.doorMs",)):
    """The handler updates its timers after the client has its answer."""
    for _ in range(500):
        now = _counts(names)
        if all(now[n] >= counts_before[n] + by for n in names):
            return
        time.sleep(0.01)
    raise AssertionError(f"timers did not move: {counts_before} -> {_counts(names)}")


def _last_entry(front, before):
    _settled(before)
    return _get(front, "/debug/queries?limit=1")["queries"][0]


def test_traced_root_carries_what_came_before_it(front):
    root = _post(front, "SET trace = true; " + SQL)["trace"]
    attrs = root["attrs"]
    for key in ("acceptWaitMs", "headMs", "httpReadMs", "parseMs"):
        assert isinstance(attrs[key], (int, float)) and attrs[key] >= 0.0, key
    assert attrs["acceptWaitMs"] > 0.0 and attrs["headMs"] > 0.0
    # one clock: accept() came before the root span opened, by at least the stages between them
    assert isinstance(attrs["acceptT0Ns"], int) and attrs["acceptT0Ns"] <= root["t0Ns"]
    before_root_ms = (root["t0Ns"] - attrs["acceptT0Ns"]) / 1e6
    assert before_root_ms >= attrs["acceptWaitMs"] + attrs["headMs"] + attrs["httpReadMs"] + attrs["parseMs"] - 0.01


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_door_timers_move_once_a_request(front, traced):
    before = _counts()
    for i in range(3):
        _post(front, ("SET trace = true; " if traced else "") + SQL)
        _settled(before, by=i + 1, names=DOOR_TIMERS)
        assert _counts() == {n: c + i + 1 for n, c in before.items()}


def test_the_accept_loop_times_every_connection_and_the_door_only_answers(front):
    before = _counts()
    assert _get(front, "/health") == {"status": "OK"}
    with pytest.raises(urllib.error.HTTPError):
        _post(front, "SELECT nope FROM nowhere")
    _settled(before, by=2, names=("rest.acceptLoopMs",))
    after = _counts()
    assert after["rest.acceptLoopMs"] == before["rest.acceptLoopMs"] + 2
    assert {n: after[n] for n in DOOR_TIMERS[1:]} == {n: before[n] for n in DOOR_TIMERS[1:]}


def test_door_ms_covers_its_named_stages(front, broker, monkeypatch):
    monkeypatch.setattr(broker.slow_queries, "slow_ms", 0.0)  # every request keeps its record
    before = _counts()
    _post(front)
    door = _last_entry(front, before)["door"]
    assert set(STAGES) | {"doorMs"} == set(door)
    named = sum(door[k] for k in STAGES)
    assert door["doorMs"] >= named - 0.01  # rounded to the microsecond, seven times
    assert door["doorMs"] - named < 50.0  # what lies between two stages is a few lines
    assert door["engineMs"] >= _get(front, "/debug/queries?limit=1")["queries"][0]["timeMs"] - 0.01


def test_a_thread_that_starts_late_is_accept_wait_and_accept_loop_time(front, monkeypatch):
    real = rest.ThreadingHTTPServer.process_request

    def late(self, request, client_address):
        time.sleep(NAP_S)  # before the handler's thread is made and started
        real(self, request, client_address)

    monkeypatch.setattr(rest.ThreadingHTTPServer, "process_request", late)
    loop = METRICS.timer("rest.acceptLoopMs")
    before, total_before = _counts(), loop.total_ms
    attrs = _post(front, "SET trace = true; " + SQL)["trace"]["attrs"]
    assert attrs["acceptWaitMs"] >= NAP_S * 1000.0
    assert attrs["headMs"] < NAP_S * 500.0 and attrs["httpReadMs"] < NAP_S * 500.0
    _settled(before, names=DOOR_TIMERS)
    assert loop.total_ms - total_before >= NAP_S * 1000.0
    assert METRICS.timer("rest.doorMs").max_ms >= NAP_S * 1000.0


@pytest.mark.parametrize("late_part", ["everything", "body"])
def test_a_client_that_sends_late_is_not_accept_wait(front, late_part):
    """The connection is accepted at once; the wait for the client's bytes is
    the head's (no request line yet) or the read's (headers there, no body)."""
    body = json.dumps({"sql": "SET trace = true; " + SQL}).encode()
    head = (f"POST /query/sql HTTP/1.0\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    with socket.create_connection(("127.0.0.1", front.port), timeout=60) as s:
        if late_part == "everything":
            time.sleep(NAP_S)
            s.sendall(head + body)
        else:
            s.sendall(head)
            time.sleep(NAP_S)
            s.sendall(body)
        raw = b""
        while chunk := s.recv(65536):
            raw += chunk
    attrs = json.loads(raw.split(b"\r\n\r\n", 1)[1])["trace"]["attrs"]
    slow, fast = ("headMs", "httpReadMs") if late_part == "everything" else ("httpReadMs", "headMs")
    assert attrs[slow] >= NAP_S * 1000.0 - 20.0
    assert attrs[fast] < NAP_S * 500.0 and attrs["acceptWaitMs"] < NAP_S * 500.0


def test_head_and_accepted_go_through_the_stage_annotation_path(front, monkeypatch):
    """With a profiler session recording (here: said to be), every stage of the
    door is a TraceAnnotation made by Stage._annotate, `http_accepted` an
    instant one that carries the wait a thread-crossing span cannot."""
    seen = []

    class Recorder:
        def __init__(self, name, **meta):
            self.name, self.meta = name, meta

        def __enter__(self):
            seen.append((self.name, self.meta, threading.current_thread().name))

        def __exit__(self, *exc):
            seen.append((self.name, "exit", threading.current_thread().name))

    monkeypatch.setattr(metrics, "TraceAnnotation", Recorder)
    monkeypatch.setattr(metrics, "_profiling", lambda: True)
    before = _counts()
    _post(front)
    _settled(before)
    door = [(n, m) for n, m, _ in seen if n.startswith("http_")]
    opened = [n for n, m in door if m != "exit"]
    assert opened == ["http_head", "http_accepted", "http_read", "http_engine", "http_serialize", "http_write"]
    # the instant lies inside http_head, which closes before http_read opens
    assert [n for n, m in door][:5] == ["http_head", "http_accepted", "http_accepted", "http_head", "http_read"]
    (accepted,) = [m for n, m in door if n == "http_accepted" and m != "exit"]
    assert accepted["accept_wait_us"] >= 0
    assert len({t for n, _, t in seen if n.startswith("http_")}) == 1  # all on the handler's thread


def test_head_and_accepted_stand_in_a_real_traces_host_plane(front, tmp_path):
    import jax
    from jax.profiler import ProfileData

    _post(front)
    got = {}

    def session():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            before = _counts()
            got["answer"] = _post(front)
            _settled(before)  # the client has its answer before http_write closes
        finally:
            jax.profiler.stop_trace()

    worker = threading.Thread(target=session, daemon=True)
    worker.start()
    worker.join(120.0)
    assert not worker.is_alive(), "the profiler session did not end inside its time limit"
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert paths, "the profiler wrote no trace"
    events = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("http_"):
                        events[e.name] = (line.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
    assert {"http_head", "http_accepted", "http_read", "http_engine", "http_serialize", "http_write"} <= set(events)
    assert len({line for line, _, _, _ in events.values()}) == 1  # one thread's line
    head, accepted, read = events["http_head"], events["http_accepted"], events["http_read"]
    assert head[1] <= accepted[1] and accepted[2] <= head[2] <= read[1]
    assert int(accepted[3]["accept_wait_us"]) >= 0


# ---------------------------------------------------------------------------
# the slow request's own record
# ---------------------------------------------------------------------------
def test_a_request_slow_at_the_door_keeps_its_record_and_a_fast_one_does_not(front, broker, monkeypatch):
    """Untraced both.  The engine's own time is a few ms in either; the slow
    one waited for its handler's thread, which only `doorMs` sees."""
    assert broker.slow_queries.slow_ms == 250.0
    slow_before = METRICS.counter("broker.slowQueries").value
    before = _counts()
    _post(front)
    fast = _last_entry(front, before)
    assert "door" not in fast and "stagesMs" not in fast and "trace" not in fast
    assert METRICS.counter("broker.slowQueries").value == slow_before

    real = rest.ThreadingHTTPServer.process_request
    monkeypatch.setattr(
        rest.ThreadingHTTPServer, "process_request",
        lambda self, request, address: (time.sleep(NAP_S), real(self, request, address)),
    )
    before = _counts()
    _post(front)
    slow = _last_entry(front, before)
    assert slow["timeMs"] < 250.0 <= slow["door"]["doorMs"]  # judged on doorMs
    assert slow["door"]["acceptWaitMs"] >= NAP_S * 1000.0 and slow["door"]["acceptWaitMs"] > slow["door"]["engineMs"]
    assert METRICS.counter("broker.slowQueries").value == slow_before + 1
    stages = slow["stagesMs"]
    assert set(stages) == {"broker", "server0"}
    assert {"sql_parse", "plan", "prune", "scatter", "server_execute", "reduce"} <= set(stages["broker"])
    # launch:<segment> folded by the name before `:`; an untraced query has no device_wait
    assert {"dispatch", "launch", "launch_plan", "launch_ship", "launch_enqueue", "collect", "table_decode"} <= set(stages["server0"])
    assert not [k for s in stages.values() for k in s if ":" in k] and "device_wait" not in stages["server0"]
    assert stages["server0"]["launch"] >= stages["server0"]["launch_plan"] + stages["server0"]["launch_ship"] - 0.01
    assert stages["broker"]["scatter"] <= slow["door"]["engineMs"]


def test_a_traced_slow_request_keeps_its_tree_beside_its_record(front, broker, monkeypatch):
    monkeypatch.setattr(broker.slow_queries, "slow_ms", 0.0)
    before = _counts()
    answer = _post(front, "SET trace = true; " + SQL)
    entry = _last_entry(front, before)
    assert entry["queryId"] == answer["requestId"] and entry["trace"]["name"] == "query"
    assert entry["door"]["acceptWaitMs"] == answer["trace"]["attrs"]["acceptWaitMs"]
    assert entry["stagesMs"]["server0"]["device_wait"] >= 0.0  # the traced path's fence


def test_a_query_that_did_not_come_through_the_door_has_no_door(broker, monkeypatch):
    monkeypatch.setattr(broker.slow_queries, "slow_ms", 0.0)
    out = broker.query(SQL)
    entry = broker.slow_queries.snapshot(1)[0]
    assert entry["queryId"] == out.stats.query_id and "door" not in entry and "stagesMs" not in entry
    assert out.stats.slow_entry["queryId"] == entry["queryId"] and [s for s, _ in out.stats.stage_ns] == ["server0", "broker", "broker"]


def test_cli_slow_queries_prints_where_a_slow_request_waited(front, broker, monkeypatch, capsys):
    from pinot_tpu.tools import cli

    monkeypatch.setattr(broker.slow_queries, "slow_ms", 0.0)
    before = _counts()
    _post(front)
    _settled(before)
    assert cli.main(["slow-queries", "--url", f"http://127.0.0.1:{front.port}", "--limit", "1"]) == 0
    out = capsys.readouterr().out
    assert "door:" in out and "acceptWaitMs=" in out and "doorMs=" in out
    assert "server0:" in out and "launch_enqueue=" in out and "broker:" in out and "reduce=" in out


def test_concurrent_requests_each_leave_one_record_while_the_log_is_read(front, broker, monkeypatch):
    """More clients than cores, a short switch interval, and a reader that
    serialises the log all the while: door() writes into entries that
    snapshot() hands out, so a snapshot copies under the log's lock."""
    import sys

    monkeypatch.setattr(broker.slow_queries, "slow_ms", 0.0)
    monkeypatch.setattr(broker.slow_queries, "_entries", type(broker.slow_queries._entries)(maxlen=4096))
    clients, each = 16, 6
    before = _counts()
    stop, faults = threading.Event(), []

    def read():
        while not stop.is_set():
            try:
                json.dumps(broker.slow_queries.snapshot())
            except Exception as e:  # noqa: BLE001 - the invariant under test
                faults.append(repr(e))

    def ask():
        try:
            for _ in range(each):
                _post(front)
        except Exception as e:  # noqa: BLE001
            faults.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read, daemon=True)
        reader.start()
        workers = [threading.Thread(target=ask, daemon=True) for _ in range(clients)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(120.0)
        assert not [t for t in workers if t.is_alive()]
        _settled(before, by=clients * each, names=DOOR_TIMERS)
        stop.set()
        reader.join(30.0)
        assert not reader.is_alive()
    finally:
        stop.set()
        sys.setswitchinterval(interval)
    assert not faults, faults[:3]
    assert _counts() == {n: c + clients * each for n, c in before.items()}  # no update lost, none made twice
    entries = broker.slow_queries.snapshot()
    assert len(entries) == clients * each and len({e["queryId"] for e in entries}) == clients * each
    assert all(e["door"]["doorMs"] >= e["door"]["engineMs"] and set(e["stagesMs"]) == {"broker", "server0"} for e in entries)
