#!/usr/bin/env python3
"""The controls at a replicated cell's own size, on the chips the cell asks
for (not part of any run of the benchmark; tools/control.py takes one chip
and one server).  For each seed one set-up, then

1. a short window at the cell's own load: the program's answers compared
   with the reference (the limit is 0), and the same answers' references
   computed by each control of lib/controls_replicated.py (float32 sums, one
   segment missing, one segment counted twice);
2. the same window with the broker made to route one segment to both of its
   replicas (`route_one_segment_twice`): every answer's envelope has to
   fault (`numSegmentsQueried` one too many) and every compared answer's
   sums have to differ.

    python benchmarks/tools/control_replicated.py --workload ssb_sf20_4srv.groupby_closed \
        --seeds 2147483659,2147483693 --seconds 8

Also prints how the window's segments fell on the servers
(`broker.routedSegments.*`), whether a server was weighted away as a gray
failure (`broker.serversBrownedOut`), and how many first launches of a
program on a chip (`timer:server.compileMs:count`) fell in the warm-up and
in the window.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import check, controls_replicated, harness, loadgen  # noqa: E402


def _diff(numbers):
    """One number for 'how far off': the absolute difference of the sums, or
    the count of groups missing, extra or wrong when groups differ."""
    if "abs_diff" in numbers:
        return numbers["abs_diff"]
    return max(numbers.get("max_abs_diff", 0), numbers.get("missing", 0) + numbers.get("extra", 0))


def _moved(before, after, prefix):
    return {k[len(prefix):]: v - before.get(k, 0.0) for k, v in sorted(after.items())
            if k.startswith(prefix) and v != before.get(k, 0.0)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-answers", type=int, default=6,
                    help="answers per window on which each control is computed (the float32 one is slow)")
    ap.add_argument("--rehearse", action="store_true", help="sandbox: CPU, toy size, as run.py --rehearse")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    config, mix, query_set = cell["config"], cell["mix"], cell["query_set"]
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PINOT_TPU_SCAN_BACKEND"] = "interpret"
        config = dict(config, rows=40_000, segment_rows=10_000)
    sys.path.insert(0, harness.REPO)
    from lib import cluster as cluster_mod
    from pinot_tpu.cluster.broker import Broker

    devices, _ = harness.find_devices(int(cell["cell"]["chips"]), rehearse=args.rehearse)
    sound_route = Broker._route
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cl = cluster_mod.Cluster(config, seed, devices, build_threads=harness.BUILD_THREADS)
        try:
            warm = harness.warm_up(cl.url, cell, traced=False)
            c0 = cl.counters()
            w = loadgen.run(cl.url, mix, query_set, seed, args.seconds)
            c1 = cl.counters()
            reqs = w["requests"]
            compiled = "timer:server.compileMs:count"
            faults = [f for f in (check.envelope_fault(r, cl.num_segments) for r in warm + reqs) if f]
            sample = warm + check.pick_sample(reqs, int(mix["sample_checked"]), seed)
            sound = [check.compare(r, query_set, cl.blocks) for r in sample]
            line = {"workload": args.workload, "seed": seed, "answers": len(reqs), "envelope_faults": len(faults),
                    "compared": len(sample), "program_correct": all(ok for ok, _ in sound) and not faults,
                    "program_max_diff": max(_diff(n) for _, n in sound), "limit": 0,
                    "routed_segments": _moved(c0, c1, "broker.routedSegments."),
                    "servers_browned_out": c1.get("broker.serversBrownedOut", 0.0),
                    # a program compiles on each chip's first launch of it: all of them in the warm-up, none after
                    "first_launches": {"in_warm_up": c0.get(compiled, 0.0),
                                       "in_window": c1.get(compiled, 0.0) - c0.get(compiled, 0.0)}}
            for cname, fn in controls_replicated.CONTROLS.items():
                got = [check.compare(r, query_set, cl.blocks, answer_fn=fn) for r in sample[: args.control_answers]]
                line[cname] = {"called_correct": sum(1 for ok, _ in got if ok), "of": len(got),
                               "min_diff": min(_diff(n) for _, n in got), "max_diff": max(_diff(n) for _, n in got)}
            harness.emit("control", **line)

            Broker._route = controls_replicated.route_one_segment_twice(sound_route)
            try:
                w = loadgen.run(cl.url, mix, query_set, seed + 1, args.seconds)
            finally:
                Broker._route = sound_route
            reqs = w["requests"]
            faults = [check.envelope_fault(r, cl.num_segments) for r in reqs]
            got = [check.compare(r, query_set, cl.blocks)
                   for r in check.pick_sample(reqs, int(mix["sample_checked"]), seed + 1)]
            harness.emit("control_served_twice", workload=args.workload, seed=seed, answers=len(reqs),
                         envelope_called_correct=sum(1 for f in faults if f is None),
                         envelope_says=sorted({f for f in faults if f})[:3],
                         compared=len(got), sums_called_correct=sum(1 for ok, _ in got if ok),
                         min_diff=min(_diff(n) for _, n in got), max_diff=max(_diff(n) for _, n in got))
        finally:
            cl.close()
        harness.emit("control_seed_done", seed=seed, seconds=round(time.perf_counter() - t0, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
