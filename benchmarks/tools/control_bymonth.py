#!/usr/bin/env python3
"""The probe that `correct` cannot make in the cells whose templates are all
SUMs (`ssb_sf10_bymonth.dashboard_closed`, `ssb_sf10_bydate.dashboard_closed`):
a padded row whose mask is forgotten adds 0 to a sum and joins a group that
real rows already hold, so the limit-0 comparison is blind to a dropped row
mask.  Here, for each template's WHERE at its published literals and at
`--draws` drawn ones, through the front door at the timed size:

    COUNT(*), MIN(lo_revenue), MAX(lo_revenue)        (and, for the group-bys,
    the group count: the template's GROUP BY with COUNT(*) and a LIMIT past it)

against numpy over the generator's own blocks (`cl.blocks`), difference 0.
Evidence for PERF.md, not a cell: no timing is reported.

    python benchmarks/tools/control_bymonth.py --config ssb_flat_sf10_bymonth --seed 2147483999
    ... --rehearse     here, on the CPU: 4 segments of ~10,000 rows
"""
import argparse
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from lib import harness, loadgen, plugins, templates  # noqa: E402
from lib.references import filter_group_sum  # noqa: E402


def _where(sql: str) -> str:
    """The template's WHERE clause, its parameters still to render."""
    tail = sql.split(" WHERE ", 1)[1]
    for stop in (" GROUP BY ", " ORDER BY ", " LIMIT "):
        tail = tail.split(stop, 1)[0]
    return tail


def _ask(url: str, sql: str, segments: int):
    """The answer's body, whole: HTTP 200, no exception, every segment."""
    out = loadgen.post(url, sql)
    body = out["body"]
    assert out["status"] == 200 and not body.get("exceptions") and not body.get("partialResult"), (sql, out)
    assert body["numSegmentsQueried"] == segments, (sql, body["numSegmentsQueried"])
    return body


def _mask(ref, params, block):
    mask = np.ones(len(block["lo_revenue"]), bool)
    for test in ref["where"]:
        mask &= filter_group_sum._mask(block[test[0]], test[1], [params[p] for p in test[2:]])
    return mask


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ssb_flat_sf10_bymonth")
    ap.add_argument("--seed", type=int, default=2147483999)
    ap.add_argument("--draws", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    config = plugins.load_json("configs", args.config)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PINOT_TPU_SCAN_BACKEND"] = "interpret"
        config = dict(config, rows=40_000, segment_rows=10_000)
    qs = plugins.load_json("queries", config["query_set"])
    table = qs["table"]
    sys.path.insert(0, harness.REPO)
    from lib import cluster as cluster_mod

    devices, _ = harness.find_devices(1, rehearse=args.rehearse)
    cl = cluster_mod.Cluster(config, args.seed, devices, build_threads=harness.BUILD_THREADS)
    rows_of = [len(b["lo_revenue"]) for b in cl.blocks]
    harness.emit("probe_setup", args.rehearse, config=args.config, seed=args.seed, segments=cl.num_segments,
                 rows=sum(rows_of), distinct_row_counts=len(set(rows_of)), bytes_staged=cl.bytes_staged,
                 seconds=round(time.perf_counter() - T0, 1))
    rng = np.random.default_rng([args.seed, 50])
    worst = 0
    try:
        for name, tpl in qs["templates"].items():
            ref = tpl["reference"]
            for k, params in enumerate([dict(tpl["ssb"])] + [templates.draw_params(tpl, rng) for _ in range(args.draws)]):
                where = _where(tpl["sql"]).format(**params)
                masks = [_mask(ref, params, b) for b in cl.blocks]
                revenue = np.concatenate([b["lo_revenue"][m] for b, m in zip(cl.blocks, masks)])
                want = [int(revenue.size), int(revenue.min()) if revenue.size else None,
                        int(revenue.max()) if revenue.size else None]
                got = _ask(cl.url, f"SELECT COUNT(*), MIN(lo_revenue), MAX(lo_revenue) FROM {table} WHERE {where}",
                           cl.num_segments)
                row = got["resultTable"]["rows"][0]
                have = [int(row[0])] + [None if not revenue.size else int(float(v)) for v in row[1:]]
                diff = [abs(h - w) for h, w in zip(have, want) if w is not None]
                line = {"template": name, "literals": "published" if k == 0 else "drawn", "params": params,
                        "count_min_max": have, "reference": want, "difference": max(diff, default=0),
                        "total_docs": got["totalDocs"], "docs_scanned": got["numDocsScanned"]}
                if got["totalDocs"] != sum(rows_of):
                    line["difference"] = max(line["difference"], abs(got["totalDocs"] - sum(rows_of)))
                if ref["group_by"]:
                    keys = np.unique(np.stack(
                        [np.concatenate([b[c][m] for b, m in zip(cl.blocks, masks)]) for c in ref["group_by"]]), axis=1)
                    cols = ", ".join(ref["group_by"])
                    groups = _ask(
                        cl.url, f"SELECT {cols}, COUNT(*) FROM {table} WHERE {where} GROUP BY {cols} LIMIT {keys.shape[1] + 1000}",
                        cl.num_segments)
                    served = groups["resultTable"]["rows"]
                    line.update(groups=len(served), reference_groups=int(keys.shape[1]),
                                group_rows=sum(int(r[-1]) for r in served))
                    line["difference"] = max(line["difference"], abs(len(served) - keys.shape[1]),
                                             abs(line["group_rows"] - want[0]))
                worst = max(worst, line["difference"])
                harness.emit("probe", args.rehearse, **line)
    finally:
        cl.close()
    harness.emit("probe_result", args.rehearse, config=args.config, seed=args.seed, largest_difference=worst)
    return 0 if worst == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
