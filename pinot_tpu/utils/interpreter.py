"""The interpreter watch: how long a ready thread waits for the interpreter
lock, whose CPU held it, and what the collector's pauses cost, on the spans'
clock (`metrics.now_ns` = perf_counter_ns, the clock of a root's `t0Ns` and
of the front door's `acceptT0Ns`).

Every host stage of a served query queues for ONE lock.  A span says where
a thread waited (`ms` less `cpuMs`), not for whom; this module reads the lock
itself and feeds the sinks the tracer has: the process registry `METRICS`
(`runtime.*`), the profiler's host plane (`mark("interpreter_hold")`, a
`gc_pause` Stage) and a bounded ring beside the slow-query log
(`GET /debug/interpreter`, `heldBy` in a slow request's entry).

It costs nothing while no query is traced: no thread, no clock read.  The
first traced query (`Trace(enabled=True)`) starts the watch's one thread,
every traced query renews it, and it ends itself LINGER_S after the last
(`GET /debug/interpreter?watch=N` holds it for N seconds).  The collector's
two callbacks alone are always on: they return at once for generation 0.

The thread sleeps TICK_S on a lock's timed acquire (what `Event.wait` comes
down to, without its Python) and stamps the clock at each wake: the wake's
lateness is what any thread of the process that became ready at that instant
would have waited to run Python.  Bytecode yields the lock every switch
interval (5 ms), so a wait past ~5 ms x the threads in flight says a C call
or the collector held it.  Every SAMPLE_S, and at once after a late tick, it
reads the CPU clock of every live Python thread (Linux's per-thread CPU
clock, addressed by the thread's native id as `pthread_getcpuclockid` does
it: no system call releases the lock, and a thread that is gone is an
OSError, not a dangling pthread_t) and its own run-queue wait
(`/proc/self/task/<tid>/schedstat`: the OS's scheduling told apart from the
lock).  A tick later than HOLD_MS leaves a record in the ring.
"""
from __future__ import annotations

import collections
import gc
import os
import sys
import threading
import time
from typing import Any, Deque, Dict, Optional, Tuple

from pinot_tpu.utils.metrics import METRICS, Stage, _profiling, mark, now_ns

TICK_S = 0.010  # 100 wake-ups a second: its cost on the chip's host is in PERF.md section 6, PR 52
SAMPLE_S = 0.2  # the threads' CPU clocks: ~1 us a thread here, a system call (~6 us) on the TPU host
LINGER_S = 3.0  # the watch outlives the last traced query by this much
HOLD_MS = 50.0  # a tick later than this leaves a record
RING = 256  # hold records kept
MAX_WATCH_S = 3600.0  # the most `?watch=N` holds it for
WATCH_THREAD = "interpreter-watch"
ACCEPT_LOOP_THREAD = "accept-loop"  # cluster/rest.py names its serve_forever thread so
_HANDLER_THREAD = "process_request_thread"  # socketserver's name for a per-request thread: "Thread-N (<target>)"
CLASSES = ("handler", "accept_loop", "staging", "watch", "embedder")
_CODE_ROOTS = (os.sep + "pinot_tpu" + os.sep, os.sep + "benchmarks" + os.sep)
_LIBRARY_ROOTS = (os.path.dirname(os.__file__) + os.sep, "<")  # the standard library; `<frozen ...>`, `<string>`
# counters of what may never happen: the registry holds them at 0 while the watch runs, so a reader of a delta
# finds "none" and not "no such counter"
_HELD_AT_ZERO = (
    "runtime.interpreterWait.over5ms", "runtime.interpreterWait.over20ms", "runtime.interpreterWait.over100ms",
    "runtime.interpreterHolds", "runtime.gc.gen1", "runtime.gc.gen2", "runtime.gc.collected",
)


def thread_class(name: str) -> str:
    """What made a thread: `handler` (the front door's per-request threads,
    and the broker's hedge and warm-up threads, which do a request's work),
    `accept_loop`, `staging` (a residency's one thread), `watch`, and
    `embedder`: every other Python thread, the process's main thread and, in
    a benchmark run, the load generator's clients and its heartbeat."""
    if _HANDLER_THREAD in name or name.startswith(("hedge-", "warm-")):
        return "handler"
    if name == ACCEPT_LOOP_THREAD:
        return "accept_loop"
    if "-stage_" in name:
        return "staging"
    if name == WATCH_THREAD:
        return "watch"
    return "embedder"


def _thread_cpu_ns(native_id: int) -> Optional[int]:
    """CPU ns of the thread with this native id, None where it is gone (or
    the platform has no such clock).  The clock id is the kernel's
    MAKE_THREAD_CPUCLOCK(tid, CPUCLOCK_SCHED), what glibc's
    pthread_getcpuclockid returns; time.clock_gettime_ns holds the lock."""
    try:
        return time.clock_gettime_ns(((~native_id) << 3) | 6)
    except (OSError, OverflowError, AttributeError):
        return None


def _frame_of(frame) -> Optional[str]:
    """`module:function:line` of the innermost frame under pinot_tpu/ or
    benchmarks/; else of the innermost frame of the embedder's own code
    (outside the standard library and site-packages); else of the innermost."""
    ours = own = None
    f = frame
    while f is not None and ours is None:
        path = f.f_code.co_filename
        if any(root in path for root in _CODE_ROOTS):
            ours = f
        elif own is None and not path.startswith(_LIBRARY_ROOTS) and "site-packages" not in path:
            own = f
        f = f.f_back
    pick = ours or own or frame
    if pick is None:
        return None
    module = pick.f_globals.get("__name__") or os.path.basename(pick.f_code.co_filename)
    return f"{module}:{pick.f_code.co_name}:{pick.f_lineno}"


# -- the collector ------------------------------------------------------------
# The two callbacks run wherever a collection falls: inside a `with` of any lock, the registry's own included (a
# `METRICS.reset()` whose `clear()` frees enough to collect deadlocked on it).  So they take NO lock: they add to
# these totals, and `flush_gc` (the registry calls it before it is read or reset, the watch at each sample)
# publishes what they moved by.
_gc_totals = [0, 0.0, 0.0, 0, 0, 0]  # pauses, their ms, the longest since the last flush, gen1, gen2, collected
_gc_published = list(_gc_totals)
_gc_flush_lock = threading.Lock()
_gc_open: Optional[Tuple[int, int, Optional[Stage]]] = None  # (start ns, generation, the host plane's Stage) of the collection running
last_gc: Optional[Dict[str, Any]] = None  # the last generation-1/2 pause: atNs, ms, generation


def _on_gc_start(phase: str, info: Dict[str, int]) -> None:
    """LAST of gc.callbacks: a generation-1 or -2 collection is about to run
    (generation 0 returns at once), every other callback's `start` work
    done (JAX's own frees its deferred references there and may give the
    lock away): stamp the clock, open a `gc_pause` Stage where a profiler
    records."""
    global _gc_open
    generation = info["generation"]
    if generation == 0 or phase != "start":
        return
    ann = Stage(None, "gc_pause", {"generation": generation}).__enter__() if _profiling() else None
    _gc_open = (now_ns(), generation, ann)


def _on_gc_stop(phase: str, info: Dict[str, int]) -> None:
    """FIRST of gc.callbacks: the collection has just ended, before any
    other callback's `stop` work: between the two stamps the thread that
    triggered the collection stalled, in no span, and the others stood
    still for as much of it as kept the lock (a collected object's
    destructor may give it away: a hold's `lateMs` says how long EVERY
    thread stood).  Timed and counted (`runtime.gcPauseMs`,
    `runtime.gc.*` once flushed), closed in the profiler's host plane, kept
    as `last_gc` for a hold's record."""
    global _gc_open, last_gc
    if phase != "stop" or _gc_open is None:
        return
    end = now_ns()
    t0, generation, ann = _gc_open
    _gc_open = None
    if ann is not None:
        ann.__exit__(None, None, None)
    ms = (end - t0) / 1e6
    totals = _gc_totals
    totals[0] += 1
    totals[1] += ms
    if ms > totals[2]:
        totals[2] = ms
    totals[3 if generation == 1 else 4] += 1
    totals[5] += info.get("collected", 0)
    last_gc = {"atNs": t0, "ms": round(ms, 3), "generation": generation}


def flush_gc() -> None:
    """Publish what the collector's totals moved by since the last flush:
    timer `runtime.gcPauseMs`, counters `runtime.gc.gen1`, `.gen2`,
    `.collected`.  Never called from the callbacks."""
    with _gc_flush_lock:
        now = list(_gc_totals)
        pauses = now[0] - _gc_published[0]
        if pauses > 0:
            _gc_totals[2] = 0.0
            METRICS.timer("runtime.gcPauseMs").merge(pauses, now[1] - _gc_published[1], now[2])
            for at, name in ((3, "gen1"), (4, "gen2"), (5, "collected")):
                if now[at] != _gc_published[at]:
                    METRICS.counter("runtime.gc." + name).inc(now[at] - _gc_published[at])
            _gc_published[:] = now


gc.callbacks.insert(0, _on_gc_stop)
gc.callbacks.append(_on_gc_start)


# -- the watch ----------------------------------------------------------------
class InterpreterWatch:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._until_ns = 0
        self._stop: Optional[threading.Lock] = None
        self._holds: Deque[Dict[str, Any]] = collections.deque(maxlen=RING)
        # door-slow requests of the last moments: a hold recorded after a request's last byte still finds it
        self._slow: Deque[Tuple[Dict[str, Any], int, int]] = collections.deque(maxlen=32)
        self._cpu: Dict[int, int] = {}  # native id -> CPU ns at the last sample
        self._sampled_ns = 0
        self._schedstat: Optional[int] = None  # fd of the watch thread's own schedstat
        self._run_queue_ns = 0

    # -- life ---------------------------------------------------------------
    @property
    def running(self) -> bool:
        """Read by the front door once a request: a plain attribute read, no
        lock (a request that straddles the watch's start or end reads its
        CPU clock or does not: either is sound)."""
        return self._thread is not None  # pinot-lint: disable=W010

    def renew(self, seconds: Optional[float] = None) -> None:
        """Watch for `seconds` more from now (None, a traced query:
        LINGER_S), starting the thread where there is none."""
        seconds = LINGER_S if seconds is None else min(max(seconds, 0.0), MAX_WATCH_S)
        until = now_ns() + int(seconds * 1e9)
        with self._lock:
            if until > self._until_ns:
                self._until_ns = until
            if self._thread is None:
                self._stop = threading.Lock()  # held while the thread runs: its timed acquire is the tick
                self._stop.acquire()
                self._thread = threading.Thread(target=self._run, args=(self._stop,), name=WATCH_THREAD, daemon=True)
                self._thread.start()

    def stop(self) -> None:
        """End the watch now and wait for its thread (tests; a process that
        exits needs no call: the thread is a daemon)."""
        with self._lock:
            thread, stop = self._thread, self._stop
            self._until_ns = 0
        if thread is not None:
            try:
                stop.release()  # the tick's timed acquire returns at once
            except RuntimeError:
                pass  # released already: another stop()
            thread.join()

    # -- the thread -----------------------------------------------------------
    def _run(self, stop: threading.Lock) -> None:
        tick_ns = int(TICK_S * 1e9)
        sample_ns = int(SAMPLE_S * 1e9)
        try:  # this thread's own run-queue wait; the fd is this run's alone
            self._schedstat = os.open(f"/proc/self/task/{threading.get_native_id()}/schedstat", os.O_RDONLY)
            self._run_queue_ns = self._run_queue()
        except (OSError, ValueError, IndexError):
            self._schedstat = None
        schedstat = self._schedstat  # a watch started as this one ends opens its own
        self._cpu = {}
        self._sample(now_ns(), baseline=True)
        woke, cpu_last = now_ns(), time.process_time_ns()
        cpu_at = woke  # when cpu_last was read
        while True:
            slept = now_ns()
            stopped = stop.acquire(timeout=TICK_S)
            now, cpu_now = now_ns(), time.process_time_ns()
            late_ms = max(0.0, (now - slept - tick_ns) / 1e6)
            METRICS.timer("runtime.interpreterWaitMs").update(late_ms)
            METRICS.counter("runtime.interpreterWait.ticks").inc()
            METRICS.counter("runtime.watchedMs").inc((now - woke) / 1e6)
            woke = now
            if late_ms > 5.0:
                METRICS.counter("runtime.interpreterWait.over5ms").inc()
                if late_ms > 20.0:
                    METRICS.counter("runtime.interpreterWait.over20ms").inc()
                    if late_ms > 100.0:
                        METRICS.counter("runtime.interpreterWait.over100ms").inc()
            if late_ms > HOLD_MS:
                self._record_hold(slept + tick_ns, now, late_ms, (cpu_now - cpu_last) / 1e6, (now - cpu_at) / 1e6)
                cpu_now, now = time.process_time_ns(), now_ns()  # the record's own CPU is not the next gap's
            elif now - self._sampled_ns >= sample_ns:
                self._sample(now)
            cpu_last, cpu_at = cpu_now, now
            with self._lock:
                if stopped or now >= self._until_ns:  # no traced query renewed it
                    self._thread = None
                    break
        if schedstat is not None:
            os.close(schedstat)

    def _run_queue(self) -> int:
        """ns this thread stood runnable without a CPU since it began."""
        return int(os.pread(self._schedstat, 64, 0).split()[1])

    def _sample(self, now: int, baseline: bool = False) -> Dict[int, Tuple[str, str, float]]:
        """Read every live Python thread's CPU clock; what each used since
        the last sample goes to its class's counter (a per-request handler
        thread adds its own whole life when it ends, cluster/rest.py: it is
        too short-lived to sample, and is read here only to name a holder).
        A thread first seen is new since the last sample and counts whole,
        except at the watch's first sample, which only sets the base.
        Returns {ident: (name, class, CPU ms since the last sample)}."""
        by_class = dict.fromkeys(CLASSES, 0.0)
        seen: Dict[int, int] = {}
        moved: Dict[int, Tuple[str, str, float]] = {}
        for t in threading.enumerate():
            tid = t.native_id
            cpu = _thread_cpu_ns(tid) if tid is not None else None
            if cpu is None:
                continue
            seen[tid] = cpu
            ms = (cpu - self._cpu.get(tid, cpu if baseline else 0)) / 1e6
            cls = thread_class(t.name)
            moved[t.ident] = (t.name, cls, ms)
            if _HANDLER_THREAD not in t.name:  # it reports itself
                by_class[cls] += ms
        self._cpu = seen
        self._sampled_ns = now
        for cls, ms in by_class.items():
            METRICS.counter("runtime.cpuMs." + cls).inc(ms)
        flush_gc()
        for name in _HELD_AT_ZERO:
            METRICS.counter(name)
        if self._schedstat is not None:
            run_queue = self._run_queue()
            METRICS.counter("runtime.runQueueMs").inc((run_queue - self._run_queue_ns) / 1e6)
            self._run_queue_ns = run_queue
        return moved

    def _record_hold(self, due_ns: int, now: int, late_ms: float, process_cpu_ms: float, cpu_over_ms: float) -> None:
        """This wake came `late_ms` after it was due at `due_ns`: say who
        had the interpreter meanwhile, as far as the clocks tell.
        `process_cpu_ms` is the process's CPU over the `cpu_over_ms` before
        this wake: the tick and its lateness, and whatever this thread itself
        stood still between two ticks (seen once on the chip: 24 s)."""
        run_queue_before = self._run_queue_ns
        since_ms = (now - self._sampled_ns) / 1e6
        moved = self._sample(now)
        run_queue_ms = (self._run_queue_ns - run_queue_before) / 1e6
        by_class = dict.fromkeys(CLASSES, 0.0)
        for _, cls, ms in moved.values():
            by_class[cls] += ms
        record: Dict[str, Any] = {
            "atNs": due_ns, "lateMs": round(late_ms, 3), "processCpuMs": round(process_cpu_ms, 3),
            "processCpuOverMs": round(cpu_over_ms, 3), "runQueueMs": round(run_queue_ms, 3),
            # busy: the process computed through at least half of that time, so a thread had the lock and worked;
            # else the watch stood in the OS's run queue (where the host tells), or the lock's holder slept with it
            # (or the whole process was not scheduled)
            "kind": "busy_holder" if process_cpu_ms >= cpu_over_ms / 2 else
                    "os_run_queue" if run_queue_ms >= late_ms / 2 else "idle_holder",
            "cpuSinceMs": round(since_ms, 3),  # the threads' CPU below is since the last sample, this long ago
            "cpuMsByClass": {c: round(ms, 3) for c, ms in by_class.items()},
        }
        top = max(moved, key=lambda ident: moved[ident][2], default=None)
        if top is not None and moved[top][2] > 0.0:
            name, cls, ms = moved[top]
            record["holder"] = cls
            record["thread"] = name
            record["holderCpuMs"] = round(ms, 3)
            record["frame"] = _frame_of(sys._current_frames().get(top))
        running, gc_pause = _gc_open, last_gc
        if running is not None:
            # the collector's own Python (another callback, a finalizer) let this thread in before its `stop`
            record["gc"] = {"atNs": running[0], "ms": round((now - running[0]) / 1e6, 3), "generation": running[1],
                            "running": True}
        elif gc_pause is not None and gc_pause["atNs"] < now and gc_pause["atNs"] + gc_pause["ms"] * 1e6 > due_ns:
            record["gc"] = gc_pause
        METRICS.counter("runtime.interpreterHolds").inc()
        mark("interpreter_hold", late_us=int(late_ms * 1000), holder=record.get("holder", record["kind"]),
             frame=record.get("frame") or "")
        with self._lock:
            self._holds.append(record)
            for entry, t0, t1 in self._slow:
                if _overlaps(record, t0, t1):
                    entry.setdefault("heldBy", []).append(_brief(record))

    # -- readers --------------------------------------------------------------
    def held_by(self, entry: Dict[str, Any], t0_ns: int, t1_ns: int) -> None:
        """The slow-query log's: `entry` is a request slow at the door that
        lived [t0_ns, t1_ns] on this clock; the holds that overlap its life
        go into it as `heldBy` (holder, thread, frame, lateMs, atNs, kind),
        those the watch has yet to record included."""
        if not self.running:
            return
        with self._lock:
            found = [_brief(h) for h in self._holds if _overlaps(h, t0_ns, t1_ns)]
            if found:
                entry["heldBy"] = found
            self._slow.append((entry, t0_ns, t1_ns))

    def snapshot(self) -> Dict[str, Any]:
        """`GET /debug/interpreter`: the ring, newest first, and the
        registry's `runtime.*` beside the front door's CPU timers."""
        snap = METRICS.snapshot()
        with self._lock:
            holds = list(self._holds)
            left_s = max(0.0, (self._until_ns - now_ns()) / 1e9) if self._thread is not None else 0.0
        holds.reverse()
        return {
            "watching": left_s > 0.0, "watchLeftS": round(left_s, 3), "tickMs": TICK_S * 1000.0, "holdMs": HOLD_MS,
            "holds": holds, "lastGc": last_gc,
            "counters": {k: v for k, v in snap["counters"].items() if k.startswith("runtime.")},
            "timers": {k: v for k, v in snap["timers"].items() if k.startswith("runtime.") or k.endswith("CpuMs")},
        }


def _overlaps(hold: Dict[str, Any], t0_ns: int, t1_ns: int) -> bool:
    return hold["atNs"] < t1_ns and hold["atNs"] + hold["lateMs"] * 1e6 > t0_ns


def _brief(hold: Dict[str, Any]) -> Dict[str, Any]:
    return {k: hold[k] for k in ("atNs", "lateMs", "kind", "holder", "thread", "frame", "gc") if k in hold}


WATCH = InterpreterWatch()
