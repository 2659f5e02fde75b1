"""From the profiler's trace to numbers: device busy time, per-event device
time, idle gaps named by what the host was doing.

jax.profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`;
`ProfileData.from_file` reads it with nothing but JAX.  A device plane is
`/device:TPU:<n>`; its `XLA Ops` line holds one event per operation that ran
on that chip, with a start and a duration in nanoseconds.  Busy time is the
union of those intervals (averaged over the chips used); the rest of the
traced span is idle.

The program's spans carry durations but no start times, and they are not in
the profiler's trace (that is for the `tracing` issue).  To name an idle gap
the spans of each traced answer are laid out on the benchmark's clock between
the request's send and its answer: children one after another from their
parent's start, the parent's own time after them.  That is exact to within
the front door's share of a request, which is what `frontdoor_ms` measures.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SYNC = "bench_clock_sync"
HEAD_SHARE = 0.6  # of a request's time outside its root span, the share before it (connect, parse)

Interval = Tuple[float, float]
_SHAPE = re.compile(r"\w+\[[\d,]*\]")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def short(name: str) -> str:
    """An HLO line of the trace (`%copy.1 = s32[750000,2]{1,0:T(8,128)}
    copy(...)`) as `copy.1 copy s32[750000,2]`: enough to tell the programs'
    operations apart, short enough for the ledger."""
    head, sep, rest = name.partition(" = ")
    shape, opcode = _SHAPE.search(rest), _OPCODE.search(rest)
    if not sep or not shape or not opcode:
        return name[:80]
    return f"{head.lstrip('%')} {opcode.group(1)} {shape.group(0)}"


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def union_seconds(intervals_ns: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merge(intervals_ns)) * 1e-9


def gaps(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What [lo, hi] has left when the intervals are taken out."""
    out, at = [], lo
    for a, b in merge(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


class _Overlap:
    """Total length of a fixed set of disjoint intervals inside a queried one."""

    def __init__(self, disjoint: Sequence[Interval]):
        self.starts = [a for a, _ in disjoint]
        self.ends = [b for _, b in disjoint]
        self.cum = [0.0]
        for a, b in disjoint:
            self.cum.append(self.cum[-1] + (b - a))

    def _upto(self, x: float) -> float:
        i = bisect.bisect_right(self.starts, x)
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(x, self.ends[i - 1]) - self.starts[i - 1]

    def inside(self, a: float, b: float) -> float:
        return max(0.0, self._upto(b) - self._upto(a))


def read_planes(path: str) -> Dict[str, Any]:
    """{device: {chip: [(start_ns, end_ns, name)]}, sync_ns: start of the
    benchmark's clock-sync annotation on the trace's clock, or None}."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):  # the recorded trace of selftest.py is kept compressed
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    device: Dict[str, List[Tuple[float, float, str]]] = {}
    sync = None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (float(e.start_ns), float(e.start_ns + e.duration_ns), e.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC:
                        sync = float(e.start_ns)
    return {"device": device, "sync_ns": sync}


def reduce_planes(planes: Dict[str, Any], lo_ns: Optional[float] = None, hi_ns: Optional[float] = None) -> Dict[str, Any]:
    """Busy seconds (mean over chips), per-event (count, seconds), the idle
    gaps of the first chip, over [lo_ns, hi_ns] (default: first to last op)."""
    chips = sorted(planes["device"])
    if not chips:
        return {"busy_s": 0.0, "span_s": 0.0, "events": {}, "idle": [], "chips": 0, "lo_ns": lo_ns, "hi_ns": hi_ns}
    every = [ev for c in chips for ev in planes["device"][c]]
    lo = min(a for a, _, _ in every) if lo_ns is None else lo_ns
    hi = max(b for _, b, _ in every) if hi_ns is None else hi_ns
    events: Dict[str, List[float]] = {}
    busy = 0.0
    for c in chips:
        clipped = [(max(a, lo), min(b, hi)) for a, b, _ in planes["device"][c] if b > lo and a < hi]
        busy += union_seconds(clipped)
        for a, b, name in planes["device"][c]:
            if b > lo and a < hi:
                acc = events.setdefault(name, [0, 0.0])
                acc[0] += 1
                acc[1] += (min(b, hi) - max(a, lo)) * 1e-9
    first = [(a, b) for a, b, _ in planes["device"][chips[0]]]
    return {"busy_s": busy / len(chips), "span_s": (hi - lo) * 1e-9, "events": {k: tuple(v) for k, v in events.items()},
            "idle": gaps(first, lo, hi), "chips": len(chips), "lo_ns": lo, "hi_ns": hi}


def reduce_file(path: str) -> Dict[str, Any]:
    return reduce_planes(read_planes(path))


def lay_out(tree: Dict[str, Any], sent: float, done: float) -> List[Tuple[str, float, float]]:
    """Leaf spans of one traced answer as (name, start, end) on the
    benchmark's clock (seconds)."""
    root_s = float(tree["ms"]) / 1000.0
    outside = max(0.0, (done - sent) - root_s)
    start = sent + HEAD_SHARE * outside
    out = [("front door", sent, start), ("front door", min(done, start + root_s), done)]

    def place(node: Dict[str, Any], at: float) -> None:
        name = node["name"].split(":", 1)[0]
        end = at + float(node["ms"]) / 1000.0
        for c in node.get("children", ()):
            place(c, at)
            at += float(c["ms"]) / 1000.0
        if end > at:  # the node's own time, after its children
            out.append((name, at, end))

    place(tree, start)
    return out


def name_idle(idle_s: Sequence[Interval], requests, lo_s: float, hi_s: float) -> List[List[Any]]:
    """Idle seconds by what the host was doing: the leaf span open in each
    traced request, 'between requests' where none was open.  Where several
    requests are open at once their spans share the gap in proportion."""
    idle = _Overlap(merge(idle_s))
    total = idle.inside(lo_s, hi_s)
    open_any = merge([(r.sent, r.done) for r in requests if r.done > lo_s and r.sent < hi_s])
    with_request = sum(idle.inside(max(a, lo_s), min(b, hi_s)) for a, b in open_any)
    by_name: Dict[str, float] = {}
    for r in requests:
        if r.spans and r.done > lo_s and r.sent < hi_s:
            for name, a, b in lay_out(r.spans, r.sent, r.done):
                by_name[name] = by_name.get(name, 0.0) + idle.inside(max(a, lo_s), min(b, hi_s))
    scale = with_request / sum(by_name.values()) if sum(by_name.values()) > 0 else 0.0
    named = [[k, v * scale] for k, v in by_name.items() if v > 0]
    named.append(["between requests", max(0.0, total - with_request)])
    return sorted(named, key=lambda kv: -kv[1])[:10]


class Recorder:
    """Traces a few seconds of the window from a side thread, so that the
    load generator runs as in any other run."""

    def __init__(self, directory: str, start_after_s: float, seconds: float):
        self.directory, self.start_after_s, self.seconds = directory, start_after_s, seconds
        self.thread: Optional[threading.Thread] = None
        self.marks: Dict[str, float] = {}
        shutil.rmtree(directory, ignore_errors=True)

    def arm(self) -> None:
        self.thread = threading.Thread(target=self._record, name="device-trace")
        self.thread.start()

    def _record(self) -> None:
        import jax

        time.sleep(self.start_after_s)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the Python tracer slows every call of the host path it would measure
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.marks["sync"] = time.perf_counter()
        with jax.profiler.TraceAnnotation(SYNC):
            pass
        self.marks["lo"] = time.perf_counter()
        time.sleep(self.seconds)
        self.marks["hi"] = time.perf_counter()
        jax.profiler.stop_trace()

    def finish(self, window: Dict[str, Any], keep: Optional[str] = None) -> Dict[str, Any]:
        self.thread.join()
        paths = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"), recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        if keep:
            os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
            shutil.copyfile(paths[0], keep)
        planes = read_planes(paths[0])
        if planes["sync_ns"] is None:
            raise RuntimeError("the clock-sync annotation is not in the trace")
        # trace clock (ns) -> the window's clock (s from its start)
        to_window = lambda ns: (ns - planes["sync_ns"]) * 1e-9 + (self.marks["sync"] - window["t0"])  # noqa: E731
        to_trace = lambda s: (s - (self.marks["sync"] - window["t0"])) * 1e9 + planes["sync_ns"]  # noqa: E731
        lo_s, hi_s = self.marks["lo"] - window["t0"], self.marks["hi"] - window["t0"]
        red = reduce_planes(planes, to_trace(lo_s), to_trace(hi_s))
        reqs = [r for r in window["requests"] if r.done > lo_s and r.sent < hi_s and r.done > r.sent]
        weights: Dict[str, float] = {}
        for r in reqs:
            share = (min(r.done, hi_s) - max(r.sent, lo_s)) / (r.done - r.sent)
            weights[r.template] = weights.get(r.template, 0.0) + share
        top = sorted(red["events"].items(), key=lambda kv: -kv[1][1])[:10]
        idle_s = [(to_window(a), to_window(b)) for a, b in red["idle"]]
        shutil.rmtree(self.directory, ignore_errors=True)
        return {
            "busy_s": red["busy_s"], "window_s": red["span_s"], "events": red["events"], "chips": red["chips"],
            "queries_in_trace": sum(weights.values()), "template_weights": weights,
            "breakdown": {"device_ops": [[short(name), sec] for name, (_, sec) in top],
                          "idle_gaps": name_idle(idle_s, window["requests"], lo_s, hi_s)},
        }
