#!/usr/bin/env python3
"""What the profiler's trace holds beside the `XLA Ops` lines the metrics
read (a tool; no metric reads it): one traced run of a cell, then

    python benchmarks/tools/host_plane.py --workload ssb_sf10.groupby_closed \
        --seed 7 --seconds 51 --keep-trace chiprun_out/trace/cell1.xplane.pb

1. the run itself is `lib/harness.py`'s, with `--trace 1 --keep-trace`: its
   lines and its result line are printed as in any run;
2. `host_plane`: the program's stages found as annotations in the trace's
   host plane (the program marks every span as a
   `jax.profiler.TraceAnnotation` of the span's name, with `query_id` and
   `segment`);
3. `launch_split`: over the window's traced answers, the `launch:<segment>`
   spans' wall and CPU time and the share their three stages cover;
   `clock`: each `launch:<segment>` annotation mapped to the benchmark's
   clock through `bench_clock_sync`, less the same span's own start (`t0Ns`
   of its server root + `startMs`) in the traced answer: how far the two
   clocks disagree;
4. `idle_from_annotations`: the device's idle seconds inside the traced span
   named by the stage that was open on the host (read from the trace, both
   sides on one clock), beside the result's `breakdown.idle_gaps`, which lays
   the spans out from durations;
5. `device_names`: device time by kernel name and by XLA module.

To see what an existing file holds: `--file <x.xplane.pb>` prints 2, 5 and
`device_scopes`: device time by the `jax.named_scope` of each operation (its
`tf_op` statistic), which needs the xplane protobuf and so is not read in
the process that holds the chip.
Pointing `tracered.name_idle` at real starts is a later benchmark PR's.
"""
import argparse
import collections
import json
import os
import re
import statistics
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import harness, tracered  # noqa: E402
from lib.reducers import spans as span_walk  # noqa: E402

# the stages the program annotates (PERF.md section 3), by the name before `:`
STAGES = ("http_read", "http_engine", "sql_parse", "plan", "prune", "scatter", "round", "server_execute",
          "dispatch", "launch", "launch_plan", "launch_ship", "launch_enqueue", "launch_release", "device_wait",
          "collect",
          "reduce", "http_serialize", "http_write")
_SCOPE = re.compile(r"^jit\(([^)]*)\)/(?:jit\([^)]*\)/)*([^/]+)")


def emit(phase: str, **fields) -> None:
    print(json.dumps(dict(phase=phase, **fields), default=str), flush=True)


def stem(name: str) -> str:
    return name.split(":", 1)[0]


def read(path: str):
    """([(thread line, name, start_ns, end_ns, stats)] of the program's
    stages, [(name, start_ns, end_ns, stats)] of the first chip's XLA Ops,
    {module name: seconds}, sync_ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, ops, modules, sync = [], [], collections.Counter(), None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name == tracered.SYNC:
                        sync = float(e.start_ns)
                    elif stem(e.name) in STAGES:
                        host.append((i, e.name, float(e.start_ns), float(e.start_ns + e.duration_ns), dict(e.stats)))
        elif plane.name.startswith(tracered.DEVICE_PLANE) and not ops:
            for line in plane.lines:
                if line.name == tracered.OPS_LINE:
                    ops = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns), dict(e.stats))
                           for e in line.events]
                elif line.name == "XLA Modules":
                    for e in line.events:
                        modules[re.sub(r"\(\d+\)$", "", e.name)] += e.duration_ns * 1e-9
    return host, ops, modules, sync


def own_intervals(host):
    """(stage, start, end) with every annotation's children taken out of it,
    thread by thread: what the thread itself was in at each moment."""
    out = []
    by_line = collections.defaultdict(list)
    for line, name, a, b, _ in host:
        by_line[line].append((a, -b, name))
    for events in by_line.values():
        stack = []  # (name, end, cursor)
        for a, neg_b, name in sorted(events):
            b = -neg_b
            while stack and stack[-1][1] <= a:
                top = stack.pop()
                if top[1] > top[2]:
                    out.append((stem(top[0]), top[2], top[1]))
            if stack:
                top = stack[-1]
                if a > top[2]:
                    out.append((stem(top[0]), top[2], a))
                stack[-1] = (top[0], top[1], max(top[2], b))
            stack.append((name, b, a))
        while stack:
            top = stack.pop()
            if top[1] > top[2]:
                out.append((stem(top[0]), top[2], top[1]))
    return out


def host_plane(host) -> None:
    count = collections.Counter(stem(n) for _, n, _, _, _ in host)
    secs = collections.Counter()
    for _, n, a, b, _ in host:
        secs[stem(n)] += (b - a) * 1e-9
    emit("host_plane", annotations={k: {"n": count[k], "seconds": secs[k]} for k in STAGES if count[k]},
         missing=[k for k in STAGES if not count[k]],
         with_query_id=sum(1 for *_, st in host if "query_id" in st),
         threads=len({line for line, *_ in host}))


def clock(host, sync_ns: float, sync_s: float, requests) -> None:
    """Annotation start on the process's clock (through the sync mark) less
    the span's own start, for every `launch:<segment>` of the trace."""
    span_ns = {}
    for r in requests:
        if not r.spans:
            continue
        qid = r.spans.get("attrs", {}).get("queryId")
        for root in span_walk.named(r.spans, "server"):
            for n in span_walk.named(root, "launch"):
                span_ns[(qid, n["name"])] = (root["t0Ns"] + n["startMs"] * 1e6, n["ms"])
    diffs, lens = [], []
    for _, name, a, b, st in host:
        hit = span_ns.get((st.get("query_id"), name)) if stem(name) == "launch" else None
        if hit is not None:
            diffs.append(((a - sync_ns) + sync_s * 1e9 - hit[0]) * 1e-6)
            lens.append((b - a) * 1e-6 - hit[1])
    if not diffs:
        emit("clock", matched=0)
        return
    mag = sorted(abs(d) for d in diffs)
    emit("clock", matched=len(diffs), annotation_less_span_ms={
        "median": statistics.median(diffs), "min": min(diffs), "max": max(diffs),
        "abs_p50": mag[len(mag) // 2], "abs_p95": mag[int(len(mag) * 0.95)], "abs_max": mag[-1],
        "within_0.5ms": sum(1 for m in mag if m <= 0.5) / len(mag)},
        duration_less_span_ms={"median": statistics.median(lens), "abs_max": max(abs(x) for x in lens)})


def launch_split(requests) -> None:
    """Over the window's traced answers: the `launch:<segment>` spans' wall
    and CPU time and what their three stages cover of it."""
    wall = cpu = 0.0
    parts, nested, cpus, gaps, hits = (collections.Counter() for _ in range(5))
    n = queries = 0
    for r in requests:
        if not r.spans:
            continue
        queries += 1
        for span in span_walk.named(r.spans, "launch"):
            n += 1
            wall += span["ms"]
            cpu += span.get("cpuMs", 0.0)
            at, where = span["startMs"], "launch"
            for c in span.get("children", ()):
                parts[c["name"]] += c["ms"]
                if "cpuMs" in c:  # only spans the program opened with cpu=True carry it
                    cpus[c["name"]] += c["cpuMs"]
                for inner in c.get("children", ()):  # launch_release, inside launch_enqueue
                    nested[inner["name"]] += inner["ms"]
                    if "cpuMs" in inner:
                        cpus[inner["name"]] += inner["cpuMs"]
                gaps[f"{where}..{c['name']}"] += c["startMs"] - at  # launch time that no stage covers
                at, where = c["startMs"] + c["ms"], c["name"]
                if c["name"] == "launch_plan":
                    hits[c.get("attrs", {}).get("cache")] += 1
            gaps[f"{where}..end"] += span["startMs"] + span["ms"] - at
    if n:
        emit("launch_split", queries=queries, launches=n, launch_wall_ms_per_query=wall / queries,
             launch_cpu_ms_per_query=cpu / queries, stages_ms_per_query={k: v / queries for k, v in parts.items()},
             of_which_ms_per_query={k: v / queries for k, v in nested.items()},
             stages_cpu_ms_per_query={k: v / queries for k, v in cpus.items()},  # wall less this: waiting
             stages_share_of_launch=sum(parts.values()) / wall,
             uncovered_ms_per_query={k: v / queries for k, v in gaps.items()}, plan_cache=dict(hits))


def idle_from_annotations(host, ops, lo_ns: float, hi_ns: float, laid_out) -> None:
    idle = tracered.gaps([(a, b) for _, a, b, _ in ops], lo_ns, hi_ns)
    inside = tracered._Overlap(tracered.merge(idle))
    total = inside.inside(lo_ns, hi_ns)
    own = [(n, max(a, lo_ns), min(b, hi_ns)) for n, a, b in own_intervals(host) if b > lo_ns and a < hi_ns]
    covered = sum(inside.inside(a, b) for a, b in tracered.merge([(a, b) for _, a, b in own]))
    by_name = collections.Counter()
    for n, a, b in own:
        by_name[n] += inside.inside(a, b)
    scale = covered / sum(by_name.values()) if sum(by_name.values()) > 0 else 0.0
    named = sorted(([k, v * scale * 1e-9] for k, v in by_name.items() if v > 0), key=lambda kv: -kv[1])
    named.append(["no stage open", max(0.0, total - covered) * 1e-9])
    emit("idle_from_annotations", idle_s=total * 1e-9, traced_s=(hi_ns - lo_ns) * 1e-9, by_stage=named,
         laid_out_from_durations=laid_out)


def device_names(ops, modules) -> None:
    kernels = collections.Counter()
    for name, a, b, _ in ops:
        if "custom-call(" in name:
            kernels[tracered.short(name)] += (b - a) * 1e-9
    emit("device_names", kernels=kernels.most_common(12), modules=modules.most_common(12))


def device_scopes(path: str) -> None:
    """Device time of the first chip's `XLA Ops` by the `jax.named_scope` of
    each operation.  The scope is in the `tf_op` statistic of the event's
    METADATA (`jit(<module>)/<scope>/...`), which `ProfileData` does not
    expose: this reads the file with the xplane protobuf that TensorFlow
    ships, and says so where that cannot be imported."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:
        emit("device_scopes", unread=f"no xplane protobuf here: {e}")
        return
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    plane = next((p for p in space.planes if p.name.startswith(tracered.DEVICE_PLANE)), None)
    if plane is None:
        emit("device_scopes", unread="no device plane")
        return
    stat_id = {v.name: k for k, v in plane.stat_metadata.items()}
    by_scope, by_source, total = collections.Counter(), collections.Counter(), 0.0
    for line in plane.lines:
        if line.name != tracered.OPS_LINE:
            continue
        for ev in line.events:
            stats = {s.metadata_id: s for s in plane.event_metadata[ev.metadata_id].stats}
            op = stats.get(stat_id.get("tf_op"))
            m = _SCOPE.match(op.str_value) if op is not None else None
            sec = ev.duration_ps * 1e-12
            total += sec
            by_scope[f"{m.group(1)}/{m.group(2)}" if m else "(no op_name)"] += sec
            src = stats.get(stat_id.get("source"))
            if src is not None:
                by_source[src.str_value.split("/pinot_tpu/")[-1]] += sec
    emit("device_scopes", seconds=total, by_scope=by_scope.most_common(24), by_source_line=by_source.most_common(12))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--file", help="an .xplane.pb kept by an earlier run: print what it holds and stop")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--keep-trace", default=os.path.join(harness.REPO, "chiprun_out", "host_plane.xplane.pb"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.file:
        host, ops, modules, _ = read(args.file)
        host_plane(host)
        device_names(ops, modules)
        device_scopes(args.file)
        return 0
    if not args.workload:
        ap.error("--workload or --file")

    # the harness's own traced run; the recorder's marks and the window's answers are kept on the way
    kept = {}
    finish = tracered.Recorder.finish

    def finish_and_keep(self, window, keep=None):
        out = finish(self, window, keep=keep)
        kept.update(marks=dict(self.marks), window=window, breakdown=out["breakdown"])
        return out

    tracered.Recorder.finish = finish_and_keep
    argv = ["--workload", args.workload, "--seed", str(args.seed), "--seconds", str(args.seconds),
            "--trace", "1", "--keep-trace", args.keep_trace] + (["--rehearse"] if args.rehearse else [])
    rc = harness.main(argv, t_start=T0)
    if rc != 0 or not kept:
        return rc or 1
    host, ops, modules, sync_ns = read(args.keep_trace)
    marks, window = kept["marks"], kept["window"]
    host_plane(host)
    launch_split(window["requests"])
    clock(host, sync_ns, marks["sync"], window["requests"])
    to_trace = lambda s: (s - marks["sync"]) * 1e9 + sync_ns  # noqa: E731
    idle_from_annotations(host, ops, to_trace(marks["lo"]), to_trace(marks["hi"]), kept["breakdown"]["idle_gaps"])
    device_names(ops, modules)
    return 0


if __name__ == "__main__":
    sys.exit(main())
