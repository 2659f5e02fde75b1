"""The interpreter watch (utils/interpreter.py): who waits for the interpreter
lock, who held it, and what the collector's pauses cost.

What is held: the watch costs nothing until a query is traced (no thread, no
clock read), a traced query starts it, it ends itself after the last one and
a later one starts it again; a C call that never yields the lock leaves ONE
hold record that names its thread's class and a frame of this file, with the
process's CPU in the gap about the gap; a thread that sleeps leaves none; a
full collection is timed, counted and named in the hold it caused, a young
one is not; a request slow at the door that waited across a hold says for
whom (`heldBy`); a hold and a collection stand in a recording profiler's host
plane; `GET /debug/interpreter` answers with and without `watch=`.

Every case has a time limit of its own and sleeps 10 ms at a time.
"""
import gc
import glob
import json
import signal
import threading
import time
import urllib.request

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.rest import QueryServer
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils import interpreter, metrics
from pinot_tpu.utils.interpreter import WATCH
from pinot_tpu.utils.metrics import METRICS, Trace

SQL = "SELECT region, SUM(rev) FROM watched GROUP BY region ORDER BY region"
LIMIT_S = 120.0
HOG_N = 12_000_000  # sum(range(N)): one C call, ~0.1-0.3 s with the lock and never a yield


@pytest.fixture(autouse=True)
def _time_limit_and_no_watch_left():
    def over(signum, frame):
        raise TimeoutError(f"the case ran past its {LIMIT_S:.0f} s")

    WATCH.stop()
    WATCH._holds.clear()
    old = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, LIMIT_S)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, old)
    WATCH.stop()


@pytest.fixture(scope="module")
def broker():
    schema = Schema(
        "watched",
        [FieldSpec("region", DataType.INT), FieldSpec("rev", DataType.LONG, role=FieldRole.METRIC)],
    )
    coord = Coordinator(replication=1)
    coord.register_server(ServerInstance("server0"))
    coord.add_table(schema, TableConfig(name="watched"))
    rng = np.random.default_rng(52)
    for i in range(3):
        block = {"region": rng.integers(0, 4, 200).astype(np.int32), "rev": rng.integers(1, 10**6, 200)}
        coord.add_segment("watched", build_segment(schema, block, f"seg{i}"))
    b = Broker(coord)
    b.query(SQL)  # compile outside every case
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


def _until(cond, what, seconds=20.0):
    for _ in range(int(seconds / 0.01)):
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"not within {seconds} s: {what}")


def _watch_thread():
    return [t for t in threading.enumerate() if t.name == interpreter.WATCH_THREAD]


_released = threading.Event()


def _hog():
    """One C call that never yields the lock; then, like a handler in
    mid-request, the thread lives on until the case has read its record."""
    sum(range(HOG_N))
    _released.wait(30.0)


def _holds_of(thread_name):
    return [h for h in WATCH.snapshot()["holds"] if h.get("thread") == thread_name]


def _counter(name):
    return METRICS.snapshot()["counters"].get(name, 0)


def _provoke_hold(name="hog", target=_hog):
    _released.clear()
    held = threading.Thread(target=target, name=name)
    time.sleep(0.05)  # a few quiet ticks first: the watch has its base sample
    held.start()
    try:
        _until(lambda: _holds_of(name), f"a hold that names {name}")
    except AssertionError as e:
        raise AssertionError(f"{e}; the watch says: {json.dumps(WATCH.snapshot())}") from None
    finally:
        _released.set()
        held.join()


# ---------------------------------------------------------------------------
# when it runs
# ---------------------------------------------------------------------------
def test_no_watch_until_a_query_is_traced(broker):
    assert not WATCH.running and not _watch_thread()
    assert broker.query(SQL).stats.trace is None
    Trace(enabled=False)
    assert not WATCH.running and not _watch_thread()
    assert broker.query("SET trace = true; " + SQL).stats.trace is not None
    assert WATCH.running and len(_watch_thread()) == 1


def test_every_traced_query_renews_one_thread(broker):
    for _ in range(3):
        broker.query("SET trace = true; " + SQL)
    assert len(_watch_thread()) == 1  # broker and server each made a Trace: one watch
    (thread,) = _watch_thread()
    assert thread.daemon and interpreter.thread_class(thread.name) == "watch"


def test_the_watch_ends_itself_after_the_last_traced_query_and_a_later_one_starts_it_again(broker, monkeypatch):
    monkeypatch.setattr(interpreter, "LINGER_S", 0.3)
    broker.query("SET trace = true; " + SQL)
    (first,) = _watch_thread()
    time.sleep(0.15)
    broker.query("SET trace = true; " + SQL)  # renewed: it outlives the first query's 0.3 s
    time.sleep(0.2)
    assert _watch_thread() == [first]
    _until(lambda: not _watch_thread() and not WATCH.running, "the watch's thread gone after its linger", 5.0)
    ticks = _counter("runtime.interpreterWait.ticks")
    assert ticks > 10
    time.sleep(0.1)
    assert _counter("runtime.interpreterWait.ticks") == ticks  # nothing ticks on
    broker.query("SET trace = true; " + SQL)
    (second,) = _watch_thread()
    assert second is not first and WATCH.running


def test_the_ticks_feed_the_registry(broker):
    broker.query("SET trace = true; " + SQL)
    _until(lambda: _counter("runtime.interpreterWait.ticks") >= 30, "thirty ticks")
    snap = METRICS.snapshot()
    wait = snap["timers"]["runtime.interpreterWaitMs"]
    assert wait["count"] >= 30 and 0.0 <= wait["meanMs"] < 50.0
    counters = snap["counters"]
    assert counters["runtime.watchedMs"] >= 30 * interpreter.TICK_S * 1000.0 * 0.9
    for name in ("over5ms", "over20ms", "over100ms"):
        assert counters["runtime.interpreterWait." + name] <= counters["runtime.interpreterWait.ticks"]
    for cls in interpreter.CLASSES:
        assert counters["runtime.cpuMs." + cls] >= 0.0
    assert counters["runtime.cpuMs.watch"] > 0.0  # its own thread's clock answered: the per-thread clock works here
    assert "runtime.interpreterHolds" in counters


# ---------------------------------------------------------------------------
# holders
# ---------------------------------------------------------------------------
def test_a_c_call_that_never_yields_the_lock_leaves_one_hold_that_names_it(broker):
    broker.query("SET trace = true; " + SQL)
    holds_before = _counter("runtime.interpreterHolds")
    _provoke_hold()
    (hold,) = _holds_of("hog")  # ONE record: the watch woke once, when the call ended
    # busy: the process computed through at least half the gap; on a host with more runnable threads than
    # cores the holder itself stands in the run queue and the same hold reads idle_holder
    assert hold["holder"] == "embedder" and hold["kind"] in ("busy_holder", "idle_holder")
    assert hold["frame"].startswith("test_interpreter_watch:_hog:") or hold["frame"].startswith("tests.test_interpreter_watch:_hog:")
    assert hold["lateMs"] > interpreter.HOLD_MS
    assert hold["processCpuMs"] >= hold["lateMs"] / 5  # something computed through the gap
    assert hold["holderCpuMs"] >= hold["lateMs"] / 5 and hold["cpuMsByClass"]["embedder"] >= hold["holderCpuMs"]
    assert hold["atNs"] < metrics.now_ns() and "gc" not in hold
    assert _counter("runtime.interpreterHolds") >= holds_before + 1
    assert _counter("runtime.interpreterWait.over20ms") >= 1
    assert METRICS.snapshot()["timers"]["runtime.interpreterWaitMs"]["maxMs"] >= hold["lateMs"] - 0.01


def test_a_thread_that_sleeps_leaves_no_hold(broker):
    broker.query("SET trace = true; " + SQL)
    sleeper = threading.Thread(target=lambda: [time.sleep(0.01) for _ in range(30)], name="sleeper")
    sleeper.start()
    sleeper.join()
    time.sleep(0.05)
    assert not _holds_of("sleeper")
    assert not [h for h in WATCH.snapshot()["holds"] if h["kind"] == "busy_holder"]


@pytest.mark.parametrize("name,cls", [
    ("Thread-7 (process_request_thread)", "handler"), ("hedge-primary-server0", "handler"), ("warm-server1", "handler"),
    ("accept-loop", "accept_loop"), ("residency.server0-stage_0", "staging"), ("interpreter-watch", "watch"),
    ("MainThread", "embedder"), ("client3", "embedder"), ("heartbeat", "embedder"),
])
def test_a_thread_is_classed_by_what_made_it(name, cls):
    assert interpreter.thread_class(name) == cls and cls in interpreter.CLASSES


def test_the_front_doors_threads_carry_the_names_the_classes_read(front):
    started = threading.Event()
    release = threading.Event()
    seen = []

    class Engine:
        def query(self, sql):
            seen.append(threading.current_thread().name)
            started.set()
            release.wait(10.0)
            raise ValueError("no answer needed")

    srv = QueryServer(Engine()).start()
    try:
        client = threading.Thread(target=lambda: pytest.raises(Exception, _post, srv), daemon=True)
        client.start()
        assert started.wait(10.0)
        names = {t.name: interpreter.thread_class(t.name) for t in threading.enumerate()}
        assert names[seen[0]] == "handler" and names[interpreter.ACCEPT_LOOP_THREAD] == "accept_loop"
        release.set()
        client.join(10.0)
    finally:
        release.set()
        srv.stop()


def test_the_per_thread_clock_is_the_threads_own():
    """The clock id made from a native id is the one pthread_getcpuclockid
    gives for a live thread, and a thread that is gone is None or a number,
    never a fault."""
    own = interpreter._thread_cpu_ns(threading.get_native_id())
    assert own is not None and abs(own - time.thread_time_ns()) < 50_000_000
    assert ((~threading.get_native_id()) << 3) | 6 == time.pthread_getcpuclockid(threading.get_ident())
    gone = threading.Thread(target=lambda: None)
    gone.start()
    tid = gone.native_id
    gone.join()
    assert interpreter._thread_cpu_ns(tid) is None or interpreter._thread_cpu_ns(tid) >= 0
    assert interpreter._thread_cpu_ns(2**22 + 12345) is None  # past pid_max's default: no such thread


# ---------------------------------------------------------------------------
# the collector
# ---------------------------------------------------------------------------
class _Node:
    pass


def _garbage(pairs):
    keep = []
    for _ in range(pairs):
        a, b = _Node(), _Node()
        a.other, b.other = b, a
        keep.append(a)
    return keep


def test_a_full_collection_is_timed_and_counted_and_a_young_one_is_not():
    gc.collect()
    METRICS.reset()
    junk = _garbage(20_000)
    del junk
    collected = gc.collect()
    snap = METRICS.snapshot()
    assert snap["counters"]["runtime.gc.gen2"] == 1 and snap["counters"]["runtime.gc.collected"] >= collected >= 40_000
    pause = snap["timers"]["runtime.gcPauseMs"]
    assert pause["count"] >= 1 and pause["maxMs"] > 0.0
    assert interpreter.last_gc["generation"] == 2 and interpreter.last_gc["ms"] == pytest.approx(pause["maxMs"], abs=0.01)
    assert interpreter.last_gc["atNs"] < metrics.now_ns()
    METRICS.reset()
    gc.collect(0)
    snap = METRICS.snapshot()
    assert "runtime.gcPauseMs" not in snap["timers"] and not [k for k in snap["counters"] if k.startswith("runtime.gc.")]
    gc.collect(1)
    assert METRICS.snapshot()["counters"]["runtime.gc.gen1"] == 1


def test_a_collection_that_falls_inside_the_registrys_lock_takes_no_lock():
    """The callback runs wherever a collection falls, a `with` of the
    registry's lock included (`METRICS.reset()` freeing enough to collect):
    it adds to plain totals, and the registry publishes them when read."""
    METRICS.reset()
    with METRICS._lock:  # what reset() and a first registration hold
        interpreter._on_gc_start("start", {"generation": 2})
        interpreter._on_gc_stop("stop", {"generation": 2, "collected": 7, "uncollectable": 0})
        timer = METRICS._timers.get("runtime.gcPauseMs")
        assert timer is None  # nothing registered, nothing locked
    snap = METRICS.snapshot()
    assert snap["timers"]["runtime.gcPauseMs"]["count"] == 1
    assert snap["counters"]["runtime.gc.gen2"] == 1 and snap["counters"]["runtime.gc.collected"] == 7
    assert METRICS.snapshot()["timers"]["runtime.gcPauseMs"]["count"] == 1  # published once


def test_the_collectors_callback_is_registered_once_and_starts_no_watch():
    # the stop stamp before every other callback's `stop` work, the start stamp after their `start` work: what
    # lies between is the collection alone (JAX's own callback gives the lock away in both phases)
    assert gc.callbacks[0] is interpreter._on_gc_stop and gc.callbacks.count(interpreter._on_gc_stop) == 1
    assert gc.callbacks.count(interpreter._on_gc_start) == 1
    assert gc.callbacks.index(interpreter._on_gc_start) > gc.callbacks.index(interpreter._on_gc_stop)
    gc.collect()
    assert not WATCH.running and not _watch_thread()


def test_a_provoked_full_collection_is_named_in_the_hold_it_caused(broker):
    gc.collect()
    junk = _garbage(300_000)  # old enough to survive to generation 2 while it is built; a full collection frees it
    broker.query("SET trace = true; " + SQL)
    del junk

    def collect():
        gc.collect()
        _released.wait(30.0)

    _provoke_hold("collector", collect)
    hold = _holds_of("collector")[-1]
    assert hold["gc"]["generation"] == 2 and hold["gc"]["ms"] > 0.0
    assert hold["kind"] in ("busy_holder", "idle_holder") and hold["lateMs"] > interpreter.HOLD_MS
    assert METRICS.snapshot()["timers"]["runtime.gcPauseMs"]["maxMs"] >= interpreter.HOLD_MS * 0.8


# ---------------------------------------------------------------------------
# the front door
# ---------------------------------------------------------------------------
def test_an_unwatched_request_reads_no_cpu_clock_and_a_watched_one_reads_it_four_times(front, monkeypatch):
    reads = []
    real = time.thread_time

    def counting():
        reads.append(threading.current_thread().name)
        return real()

    monkeypatch.setattr(time, "thread_time", counting)
    before = METRICS.snapshot()["timers"].get("rest.doorMs", {"count": 0})["count"]
    _post(front)
    _until(lambda: METRICS.snapshot()["timers"].get("rest.doorMs", {"count": 0})["count"] > before, "the door's timers")
    assert not reads and not _watch_thread()
    assert "rest.doorCpuMs" not in METRICS.snapshot()["timers"]
    _get(front, "/debug/interpreter?watch=5")
    assert WATCH.running
    _post(front)
    _until(lambda: "rest.doorCpuMs" in METRICS.snapshot()["timers"], "the door's CPU timers")
    handler_reads = [r for r in reads if "process_request_thread" in r]
    assert len(handler_reads) == 4  # around the engine call, after serialising, at the end
    snap = METRICS.snapshot()
    door, engine, ser = (snap["timers"][f"rest.{n}CpuMs"] for n in ("door", "engine", "serialize"))
    assert door["count"] == engine["count"] == ser["count"] == 1
    assert 0.0 <= ser["meanMs"] and 0.0 < engine["meanMs"] <= door["meanMs"] <= snap["timers"]["rest.doorMs"]["maxMs"] + 1.0
    assert snap["counters"]["runtime.cpuMs.handler"] == pytest.approx(door["meanMs"])


def test_debug_interpreter_answers_with_and_without_watch(front):
    idle = _get(front, "/debug/interpreter")
    assert idle["watching"] is False and idle["holds"] == [] and not _watch_thread()
    assert idle["tickMs"] == interpreter.TICK_S * 1000.0 and idle["holdMs"] == interpreter.HOLD_MS
    on = _get(front, "/debug/interpreter?watch=0.5")
    assert on["watching"] is True and 0.0 < on["watchLeftS"] <= 0.5 and len(_watch_thread()) == 1
    _until(lambda: _get(front, "/debug/interpreter")["counters"].get("runtime.interpreterWait.ticks", 0) >= 5, "ticks")
    seen = _get(front, "/debug/interpreter")
    assert "runtime.interpreterWaitMs" in seen["timers"] and "runtime.watchedMs" in seen["counters"]
    _until(lambda: not _watch_thread(), "the watch gone after its half second", 5.0)
    assert _get(front, "/debug/interpreter")["watching"] is False


def test_a_hold_is_named_in_debug_interpreter(front):
    _get(front, "/debug/interpreter?watch=10")
    _provoke_hold()
    holds = [h for h in _get(front, "/debug/interpreter")["holds"] if h.get("thread") == "hog"]
    assert len(holds) == 1 and holds[0]["holder"] == "embedder" and "_hog:" in holds[0]["frame"]


def test_a_door_slow_request_that_waited_across_a_hold_says_who_held_it(broker, monkeypatch):
    """The hold starts inside the request's engine call, so the request's
    life contains it whatever the scheduler does."""
    monkeypatch.setattr(broker.slow_queries, "slow_ms", 40.0)
    broker.slow_queries._entries.clear()

    class Engine:
        slow_queries = broker.slow_queries

        def query(self, sql):
            _released.clear()
            hog = threading.Thread(target=_hog, name="hog", daemon=True)
            hog.start()
            time.sleep(0.01)  # hands the lock over: the call below waits for it
            return broker.query(sql)

    srv = QueryServer(Engine()).start()
    try:
        _get(srv, "/debug/interpreter?watch=10")
        time.sleep(0.05)
        _post(srv)
        _until(lambda: _holds_of("hog"), "the hold's record")
        _until(lambda: "heldBy" in _get(srv, "/debug/queries?limit=1")["queries"][0], "heldBy in the slow entry")
        entry = _get(srv, "/debug/queries?limit=1")["queries"][0]
        (hold,) = [h for h in entry["heldBy"] if h.get("thread") == "hog"]
        _released.set()
        assert hold["holder"] == "embedder" and "_hog:" in hold["frame"] and hold["lateMs"] > interpreter.HOLD_MS
        assert entry["door"]["doorMs"] >= hold["lateMs"] and entry["door"]["engineMs"] >= hold["lateMs"]
        # a fast request's entry says nothing of the kind
        monkeypatch.setattr(broker.slow_queries, "slow_ms", 60_000.0)
        fast = QueryServer(broker).start()
        try:
            _post(fast)
            time.sleep(0.05)
            assert "heldBy" not in _get(fast, "/debug/queries?limit=1")["queries"][0]
        finally:
            fast.stop()
    finally:
        _released.set()
        srv.stop()


def test_a_hold_recorded_after_the_requests_last_byte_still_reaches_its_entry():
    """door() may run before the watch's late wake has written its record:
    the entry waits in the watch's short list of recent slow requests."""
    WATCH.renew(5.0)
    entry = {}
    t0 = metrics.now_ns()
    WATCH.held_by(entry, t0, t0 + 60_000_000_000)  # a life that holds whatever comes in the next minute
    assert "heldBy" not in entry
    _provoke_hold()
    _until(lambda: "heldBy" in entry, "the late record copied into the waiting entry")
    assert entry["heldBy"][0]["thread"] == "hog"
    other = {}
    WATCH.held_by(other, t0 - 10_000_000_000, t0 - 9_000_000_000)  # a life long before the hold
    assert "heldBy" not in other


# ---------------------------------------------------------------------------
# the profiler's host plane
# ---------------------------------------------------------------------------
def test_a_hold_and_a_collection_are_annotations_while_a_profiler_records(broker, monkeypatch):
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
    monkeypatch.setattr(interpreter, "_profiling", lambda: True)
    broker.query("SET trace = true; " + SQL)
    _provoke_hold()
    gc.collect()
    (hold,) = [m for n, m, _ in seen if n == "interpreter_hold" and m != "exit" and "_hog:" in m["frame"]]
    assert hold["holder"] == "embedder" and hold["late_us"] > interpreter.HOLD_MS * 1000
    assert [t for n, m, t in seen if n == "interpreter_hold"][0] == interpreter.WATCH_THREAD
    pauses = [(m, t) for n, m, t in seen if n == "gc_pause"]
    opened = [m for m, _ in pauses if m != "exit"]
    assert {"generation": 2} in opened and len(pauses) == 2 * len(opened)  # each an interval: entered and left


def test_a_hold_stands_in_a_real_traces_host_plane(broker, tmp_path):
    import jax
    from jax.profiler import ProfileData

    def session():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            broker.query("SET trace = true; " + SQL)
            _provoke_hold()
            gc.collect()
        finally:
            jax.profiler.stop_trace()

    worker = threading.Thread(target=session, daemon=True)
    worker.start()
    worker.join(100.0)
    assert not worker.is_alive(), "the profiler session did not end inside its time limit"
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert paths, "the profiler wrote no trace"
    events = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("interpreter_hold", "gc_pause"):
                        events.setdefault(e.name, []).append(dict(e.stats))
    assert any("_hog:" in str(s.get("frame")) and int(s["late_us"]) > 50_000 for s in events.get("interpreter_hold", []))
    assert any(int(s["generation"]) == 2 for s in events.get("gc_pause", []))
