#!/usr/bin/env python3
"""SSB's four roll-up panels (Q2.1, Q2.2, Q2.3, Q3.1) at SSB's literals over a
table with star-trees, each asked of the trees and, with Pinot's query option
`useStarTree=false`, of the scan, in ONE process over the same resident table:
a cold and a warm reading of each (host clock over HTTP), whether the two
answers are the same rows, and whether each equals the plain reference.
Evidence for PERF.md, not a cell: one reading each.

    python benchmarks/tools/startree_reading.py --config ssb_flat_sf10_startree --seed 7
"""
import argparse
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import check, harness, loadgen, plugins  # noqa: E402

ROLLUPS = ["q2_1", "q2_2", "q2_3", "q3_1"]
NO_TREE = "SET useStarTree=false; "


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ssb_flat_sf10_startree")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--templates", default=",".join(ROLLUPS))
    ap.add_argument("--rehearse-rows", type=int, default=0, help="sandbox: the CPU, a table of this many rows")
    args = ap.parse_args()
    config = plugins.load_json("configs", args.config)
    if args.rehearse_rows:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = dict(config, rows=args.rehearse_rows, segment_rows=max(1, args.rehearse_rows // 4))
    qs = plugins.load_json("queries", config["query_set"])
    sys.path.insert(0, harness.REPO)
    from lib import cluster as cluster_mod

    devices, _ = harness.find_devices(1, rehearse=bool(args.rehearse_rows))
    cl = cluster_mod.Cluster(config, args.seed, devices)
    harness.emit("startree_setup", config=args.config, rows=config["rows"], bytes_staged=cl.bytes_staged,
                 seconds=round(time.perf_counter() - T0, 1), **{k: round(v, 2) for k, v in cl.timers.items()})
    try:
        for name in args.templates.split(","):
            t = qs["templates"][name]
            line = {}
            answers = {}
            for how, template in (("tree", t), ("scan", dict(t, sql=NO_TREE + t["sql"]))):
                before = cl.counters()
                times, oks = [], []
                for _ in range(2):  # cold (trace + compile + run), then warm
                    req = loadgen.Request(0, -1, name, dict(t["ssb"]), 0.0)
                    loadgen.send(cl.url, req, template, False, time.perf_counter())
                    times.append(round(req.done - req.sent, 4))
                    fault = check.envelope_fault(req, cl.num_segments)
                    ok, numbers = (False, {"fault": fault}) if fault else check.compare(req, qs, cl.blocks)
                    oks.append(ok)
                    if not ok:
                        harness.emit("startree_differs", how=how, **numbers)
                after = cl.counters()
                answers[how] = req.rows
                line[how] = {"cold_s": times[0], "warm_s": times[1], "equal_to_reference": oks,
                             "docs_scanned": req.meta.get("numDocsScanned"),
                             "moved": {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)
                                       and k.startswith(("scan.traced", "compile.sse.compiles", "server.starTree", "server.launches"))}}
            harness.emit("startree", template=name, rows=len(answers["tree"]), same_rows=answers["tree"] == answers["scan"], **line)
    finally:
        cl.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
