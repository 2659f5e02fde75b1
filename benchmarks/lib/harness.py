"""One run of one cell: set-up from the seed, warm-up of the cell's own query
shapes, a measured window, the check, and the result line.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, JAX imported once, no child process.  The program runs with its
defaults: the only thing it is told is where the compile cache lives.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from lib import check, loadgen, plugins

REPO = os.path.dirname(plugins.ROOT)
BUILD_THREADS = 8  # host threads that draw and build segments during set-up
TRACE_SECONDS = 3.0  # the device trace covers this much of a --trace 1 window
FAILED_LATENCY_S = loadgen.REQUEST_TIMEOUT_S  # a failed request misses every latency limit


class Refusal(Exception):
    """The run cannot be a measurement (no TPU, unknown device, bad cell):
    non-zero exit and no result line."""


def emit(phase: str, rehearse: bool = False, **fields: Any) -> None:
    line: Dict[str, Any] = {"phase": phase}
    if rehearse:
        line["rehearsal"] = True
    line.update(fields)
    print(json.dumps(line, default=str), flush=True)


def load_cell(workload: str) -> Dict[str, Any]:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refusal(f"no workload {workload!r} in BENCHMARK.json (have: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(REPO, cfg_entry["file"])) as f:
        config = json.load(f)
    mix = plugins.load_json("traffic", cell["traffic"])
    query_set = plugins.load_json("queries", config["query_set"])

    def applies(m: Dict[str, Any]) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return {
        "bench": bench, "cell": cell, "config": config, "mix": mix, "query_set": query_set,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def find_devices(chips: int, rehearse: bool):
    """The chips this cell asks for, or a Refusal.  The peaks table must know
    the device: a roofline against a guessed peak is not a measurement."""
    import jax

    devs = jax.devices()
    with open(os.path.join(plugins.ROOT, "peaks.json")) as f:
        peaks = json.load(f)
    if rehearse:
        return devs[:chips], {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0, "name": "rehearsal"}
    if devs[0].platform != "tpu":
        raise Refusal(f"the benchmark needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refusal(f"the cell asks for {chips} chip(s); JAX found {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise Refusal(f"device kind {kind!r} is not in benchmarks/peaks.json")
    return devs[:chips], peaks[kind]


def cell_templates(mix: Dict[str, Any]) -> List[str]:
    return list(mix["templates"]) if "templates" in mix else list(mix["weights"])


def warm_up(url: str, cell: Dict[str, Any], traced: bool, counters=None,
            moved: Optional[Dict[str, Dict[str, float]]] = None) -> List[loadgen.Request]:
    """Every query shape the window will send, at SSB's published literals:
    twice each, so the second answer comes from the compiled plan.  Returns
    the second answers, which the check compares in full.  With `counters`
    (a function that reads the program's counters), `moved` is filled with
    how far each counter moved while each template was warmed: which kernel
    a template's plan got is read there (`scan.traced.*`), not assumed."""
    out = []
    for i, name in enumerate(cell_templates(cell["mix"])):
        template = cell["query_set"]["templates"][name]
        before = counters() if counters else {}
        for _ in range(2):
            req = loadgen.Request(-1 - i, -1, name, dict(template["ssb"]), 0.0)
            loadgen.send(url, req, template, traced, time.perf_counter())
        out.append(req)
        if counters and moved is not None:
            moved[name] = {k: v - before.get(k, 0.0) for k, v in counters().items() if v != before.get(k, 0.0)}
    return out


class Heartbeat:
    """A thread that only sleeps 50 ms at a time through the window (20 wake-ups
    a second: it must not disturb the interpreter it watches).  Where a
    run stalls (`longest_quiet`), its longest gap says whether the whole
    process stood still, and the CPU seconds the process used inside that
    gap say whether it was busy (something held the interpreter) or not
    scheduled at all (the host)."""

    def __init__(self) -> None:
        self.stop = threading.Event()
        self.longest = {"gap_ms": 0.0, "cpu_s_in_gap": 0.0, "at_s": 0.0}
        self.thread = threading.Thread(target=self._beat, name="heartbeat")

    def _beat(self) -> None:
        t0 = last = time.perf_counter()
        cpu = time.process_time()
        while not self.stop.wait(0.05):
            now, cpu_now = time.perf_counter(), time.process_time()
            if (now - last) * 1000.0 > self.longest["gap_ms"]:
                self.longest = {"gap_ms": (now - last) * 1000.0, "cpu_s_in_gap": cpu_now - cpu, "at_s": last - t0}
            last, cpu = now, cpu_now

    def __enter__(self) -> "Heartbeat":
        self.thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop.set()
        self.thread.join()


def percentile(values: np.ndarray, q: float) -> float:
    return float(np.quantile(values, q)) if values.size else float("nan")


def metric_value(family: str, name: str, ctx: Dict[str, Any]) -> Optional[float]:
    """One metric, by its own file `<family>/<name>.json` and the reducer it
    names; None where the reader finds nothing to read."""
    spec = plugins.load_json(family, name)
    return plugins.load_module("reducers", spec["reducer"]).reduce(spec, ctx)


def run_cell(args: argparse.Namespace, t_start: float) -> Dict[str, Any]:
    """The whole run; returns the result line's object."""
    rehearse = bool(args.rehearse)
    cell = load_cell(args.workload)
    config, mix, query_set = cell["config"], cell["mix"], cell["query_set"]
    if rehearse:
        # the sandbox rehearsal: CPU, the scan interpreted, a table of toy size
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PINOT_TPU_SCAN_BACKEND"] = "interpret"
        config = dict(config, rows=int(args.rehearse_rows), segment_rows=max(1, int(args.rehearse_rows) // 4))
        cell["config"] = config
    if not os.path.isdir(os.path.join(REPO, "pinot_tpu")):
        raise Refusal("no program here: pinot_tpu/ is missing beside benchmarks/")
    sys.path.insert(0, REPO)

    import jax

    from lib import cluster as cluster_mod  # imports the program

    devices, peak = find_devices(int(cell["cell"]["chips"]), rehearse)

    emit("device", rehearse, platform=devices[0].platform, kind=devices[0].device_kind,
         count=len(jax.devices()), compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
         or jax.config.jax_compilation_cache_dir, import_s=round(time.perf_counter() - t_start, 3))

    traced = bool(args.trace)
    cl = cluster_mod.Cluster(config, args.seed, devices, build_threads=BUILD_THREADS)
    try:
        emit("load", rehearse, rows=config["rows"], segments=cl.num_segments, bytes_staged=cl.bytes_staged,
             bytes_per_row=cl.bytes_staged / config["rows"], **{k: round(v, 3) for k, v in cl.timers.items()})
        t_w = time.perf_counter()
        counters_cold = cl.counters()
        warm_moved: Dict[str, Dict[str, float]] = {}
        warm = warm_up(cl.url, cell, traced, counters=cl.counters, moved=warm_moved)
        if float(mix.get("rolling_start_s", 0)) > 0:
            # the mix's own traffic for a few seconds, unmeasured: threads, sockets and queues as in the window
            loadgen.run(cl.url, mix, query_set, args.seed ^ 0x5EED, float(mix["rolling_start_s"]), traced=traced)
        counters_0 = cl.counters()
        cl.timers["warm_up_s"] = time.perf_counter() - t_w
        emit("warm_up", rehearse, seconds=round(cl.timers["warm_up_s"], 3),
             compiles=counters_0.get("compile.sse.compiles", 0) - counters_cold.get("compile.sse.compiles", 0),
             scans_traced={n: {k.rsplit(".", 1)[1]: v for k, v in m.items() if k.startswith("scan.traced.")}
                           for n, m in warm_moved.items()})

        device_trace = None
        tracer = None
        if traced:
            from lib import tracered

            tracer = tracered.Recorder(os.path.join(plugins.ROOT, ".trace", f"{args.workload}.{args.seed}"),
                                       start_after_s=min(2.0, args.seconds / 4.0),
                                       seconds=min(TRACE_SECONDS, args.seconds / 2.0))
        setup_s = time.perf_counter() - t_start
        if tracer is not None:
            tracer.arm()
        with Heartbeat() as heart:
            window = loadgen.run(cl.url, mix, query_set, args.seed, args.seconds, traced=traced)
        if tracer is not None:
            device_trace = tracer.finish(window, keep=args.keep_trace)
        counters_1 = cl.counters()
        mem = devices[0].memory_stats() or {}
        peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)

        # -- the check: after the window, outside set-up -----------------------
        t_c = time.perf_counter()
        reqs = window["requests"]
        faults: Dict[int, str] = {}  # request index -> why; warm-up answers have negative indices
        for r in warm + reqs:
            why = check.envelope_fault(r, cl.num_segments)
            if why is not None:
                faults[r.index] = why
        sample = check.pick_sample(reqs, int(mix["sample_checked"]), args.seed)
        compared = []
        for r in warm + sample:
            why = faults.get(r.index)
            ok, numbers = (False, {"error": why, "limit": 0}) if why else check.compare(r, query_set, cl.blocks)
            compared.append(ok)
            if not ok:
                faults[r.index] = f"differs from the reference: {numbers}"
            emit("compared", rehearse, request=r.index, equal=ok, **numbers)
        check_s = time.perf_counter() - t_c
        failed = len([i for i in faults if i >= 0])
        correct = not faults and bool(compared) and all(compared) and len(reqs) > 0
        emit("check", rehearse, correct=correct, answers_checked_envelope=len(reqs),
             answers_compared_in_full=len(compared), warm_up_answers=len(warm), seconds=round(check_s, 3),
             faults=[f"{i}: {w}" for i, w in list(faults.items())[:5]])

        ctx = {
            "window_requests": reqs, "faults": faults, "window_s": window["window_s"],
            "failed_latency_s": FAILED_LATENCY_S, "timers": dict(cl.timers, setup_s=setup_s),
            "requests": [r for r in reqs if r.index not in faults],  # traced answers whose spans are read
            "counters_before": counters_0, "counters_after": counters_1, "warm_moved": warm_moved,
            "device_trace": device_trace, "config": config, "query_set": query_set, "peak": peak,
        }
        values = {m["name"]: metric_value("end_to_end", m["name"], ctx) for m in cell["end_to_end"]}
        lat_ms = np.asarray([r.latency_s for r in reqs]) * 1000.0
        done = np.sort(np.asarray([0.0] + [r.done for r in reqs if r.done > 0.0]))
        quiet = int(np.argmax(np.diff(done))) if done.size > 1 else 0  # the longest stretch in which no answer came
        emit("window", rehearse, seconds=window["window_s"], offered=window["offered"], latency_samples=len(reqs),
             generator_late_ms=window["late_ms"], plan=window["plan"], reconnects=window["reconnects"],
             compiles=counters_1.get("compile.sse.compiles", 0.0) - counters_0.get("compile.sse.compiles", 0.0),
             latency_p99_ms=percentile(lat_ms, 0.99),  # printed only: no cell reports it (PERF.md section 2)
             longest_quiet={"ms": float(done[quiet + 1] - done[quiet]) * 1000.0, "at_s": float(done[quiet])}
             if done.size > 1 else None,  # a stall shows here, not only in the tail
             heartbeat=heart.longest,
             per_template={n: {"n": len(v), "p50_ms": round(percentile(np.asarray(v), 0.5) * 1000, 3)}
                           for n in cell_templates(mix)
                           for v in [[r.latency_s for r in reqs if r.template == n]]},
             **values)

        metrics: Dict[str, Dict[str, Any]] = {}
        for m in cell["per_layer"] if traced else cell["end_to_end"]:
            value = values[m["name"]] if not traced else metric_value("layer_metrics", m["name"], ctx)
            if value is not None:  # a reader that finds nothing reports nothing
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak_bytes,
                  "memory_in_use_bytes": int(mem.get("bytes_in_use", 0))}
        result = {"correct": correct, "attempted": len(reqs), "failed": failed, "metrics": metrics, "device": device}
        if device_trace is not None:
            device.update(busy_s=device_trace["busy_s"], window_s=device_trace["window_s"])
            result["breakdown"] = device_trace["breakdown"]
    finally:
        cl.close()
    return result


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, help="with --trace 1: also copy the .xplane.pb to this file")
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal on the CPU at a toy size; marks every line, prints no result line")
    ap.add_argument("--rehearse-rows", type=int, default=40_000)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args, t_start)
    except Refusal as e:
        print(f"benchmark refused: {e}", file=sys.stderr)
        return 2
    if args.rehearse:  # never the result line: a rehearsal is not a measurement
        emit("rehearsal_result", True, **result)
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0
