#!/usr/bin/env python3
"""One cold and one warm reading of SSB's four high-cardinality group-bys
(Q3.2, Q3.3, Q3.4, Q4.3: group spaces of 437,500 and 1,750,000, past the
planner's dense tables) on the chip.  Evidence for PERF.md, not a cell: one
reading each, host clock over HTTP.

    python benchmarks/tools/sparse_reading.py --config ssb_flat_sf1 --seed 7
"""
import argparse
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import check, harness, loadgen, plugins  # noqa: E402

SPARSE = ["q3_2", "q3_3", "q3_4", "q4_3"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ssb_flat_sf1")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--templates", default=",".join(SPARSE))
    args = ap.parse_args()
    config = plugins.load_json("configs", args.config)
    qs = plugins.load_json("queries", config["query_set"])
    sys.path.insert(0, harness.REPO)
    from lib import cluster as cluster_mod

    devices, _ = harness.find_devices(1, rehearse=False)
    cl = cluster_mod.Cluster(config, args.seed, devices)
    harness.emit("sparse_setup", config=args.config, rows=config["rows"], seconds=round(time.perf_counter() - T0, 1),
                 **{k: round(v, 2) for k, v in cl.timers.items()})
    try:
        for name in args.templates.split(","):
            t = qs["templates"][name]
            before = cl.counters()
            times, oks, rows = [], [], 0
            for _ in range(2):  # cold (trace + compile + run), then warm
                req = loadgen.Request(0, -1, name, dict(t["ssb"]), 0.0)
                loadgen.send(cl.url, req, t, False, time.perf_counter())
                times.append(round(req.done - req.sent, 3))
                fault = check.envelope_fault(req, cl.num_segments)
                ok, numbers = (False, {"fault": fault}) if fault else check.compare(req, qs, cl.blocks)
                oks.append(ok)
                if not ok:
                    harness.emit("sparse_differs", **numbers)
                rows = len(req.rows)
            after = cl.counters()
            moved = {k: after[k] - before.get(k, 0) for k in after
                     if after[k] != before.get(k, 0) and (k.startswith(("scan.traced", "compile.sse")) or "compileMs" in k)}
            harness.emit("sparse", template=name, group_space=t["group_space"], cold_s=times[0], warm_s=times[1],
                         equal_to_reference=oks, rows=rows, us_per_row_warm=times[1] * 1e6 / config["rows"], moved=moved)
    finally:
        cl.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
