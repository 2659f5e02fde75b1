#!/usr/bin/env python3
"""The knee of an open-loop cell, found once (not part of any run of the
benchmark): one process, one set-up, a short window at each rate.

    python benchmarks/tools/sweep.py --workload ssb_sf1.mixed_open --seed 7 \
        --seconds 12 --rates 20,40,60,80,100,120

The knee is the highest swept rate, below the first that is not sustained,
at which at least 99 % of the offered queries complete inside the window and
the second half's median latency is at most 1.5 x the first half's.  The cell's traffic file then stores 0.8 x the
knee as a number; PERF.md keeps this table.
"""
import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from lib import check, harness, loadgen  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, harness.REPO)
    from lib import cluster as cluster_mod

    devices, _ = harness.find_devices(int(cell["cell"]["chips"]), rehearse=False)
    cl = cluster_mod.Cluster(cell["config"], args.seed, devices)
    try:
        harness.warm_up(cl.url, cell, traced=False)
        harness.emit("sweep_setup", seconds=round(time.perf_counter() - T0, 2))
        knee, passed_it = None, False
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            w = loadgen.run_open(cl.url, cell["mix"], cell["query_set"], args.seed + i, args.seconds, rate_qps=rate)
            reqs = w["requests"]
            bad = sum(1 for r in reqs if check.envelope_fault(r, cl.num_segments))
            inside = sum(1 for r in reqs if r.done and r.done <= args.seconds and r.error is None)
            lat = np.asarray([r.latency_s for r in reqs]) * 1000
            half = len(reqs) // 2
            m1, m2 = float(np.median(lat[:half])), float(np.median(lat[half:]))
            ok = inside >= 0.99 * len(reqs) and m2 <= 1.5 * m1 and not bad
            passed_it = passed_it or not ok  # rates go up: a rate above the first unsustained one is no knee
            if ok and not passed_it:
                knee = rate
            harness.emit("sweep", rate_qps=rate, offered=len(reqs), completed_in_window=inside, faults=bad,
                         p50_ms=float(np.median(lat)), p95_ms=float(np.quantile(lat, .95)),
                         p99_ms=float(np.quantile(lat, .99)), first_half_p50_ms=m1, second_half_p50_ms=m2,
                         generator_late_ms=w["late_ms"], sustained=ok)
        harness.emit("knee", rate_qps=knee, cell_rate_qps=None if knee is None else 0.8 * knee)
    finally:
        cl.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
