#!/usr/bin/env python3
"""The controls at the cells' own sizes, on the chip (not part of any run of
the benchmark): for each seed one set-up, then for each named cell of that
configuration a short window at the cell's own load, the program's answers
compared with the reference, and the same answers' references computed by
each control of lib/controls.py.

    python benchmarks/tools/control.py --workloads ssb_sf10.groupby_closed,ssb_sf10.q1_closed \
        --seeds 2147483659,2147483693,2147483713 --seconds 8

Per seed and cell it prints the largest difference a sound answer showed
(the limit is 0: integers are exact) and the smallest difference each control
showed over the compared answers, and whether the check called it correct.
"""
import argparse
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import check, controls, harness, loadgen  # noqa: E402


def _diff(numbers):
    """One number for 'how far off': the absolute difference of the sums, or
    the count of groups missing, extra or wrong when groups differ."""
    if "abs_diff" in numbers:
        return numbers["abs_diff"]
    return max(numbers.get("max_abs_diff", 0), numbers.get("missing", 0) + numbers.get("extra", 0))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-answers", type=int, default=6,
                    help="answers per window on which each control is computed (the float32 one is slow)")
    args = ap.parse_args()
    cells = [harness.load_cell(w) for w in args.workloads.split(",")]
    if len({c["cell"]["config"] for c in cells}) != 1:
        raise SystemExit("the cells of one call share a configuration (one set-up per seed)")
    sys.path.insert(0, harness.REPO)
    from lib import cluster as cluster_mod

    devices, _ = harness.find_devices(1, rehearse=False)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cl = cluster_mod.Cluster(cells[0]["config"], seed, devices)
        try:
            for cell in cells:
                warm = harness.warm_up(cl.url, cell, traced=False)
                w = loadgen.run(cl.url, cell["mix"], cell["query_set"], seed, args.seconds)
                reqs = w["requests"]
                faults = [f for f in (check.envelope_fault(r, cl.num_segments) for r in reqs) if f]
                sample = warm + check.pick_sample(reqs, int(cell["mix"]["sample_checked"]), seed)
                sound = [check.compare(r, cell["query_set"], cl.blocks) for r in sample]
                line = {"workload": cell["cell"]["name"], "seed": seed, "answers": len(reqs),
                        "envelope_faults": len(faults), "compared": len(sample),
                        "program_correct": all(ok for ok, _ in sound) and not faults,
                        "program_max_diff": max(_diff(n) for _, n in sound), "limit": 0}
                for cname, fn in controls.CONTROLS.items():
                    got = [check.compare(r, cell["query_set"], cl.blocks, answer_fn=fn)
                           for r in sample[: args.control_answers]]
                    line[cname] = {"called_correct": sum(1 for ok, _ in got if ok), "of": len(got),
                                   "min_diff": min(_diff(n) for _, n in got),
                                   "max_diff": max(_diff(n) for _, n in got)}
                harness.emit("control", **line)
        finally:
            cl.close()
        harness.emit("control_seed_done", seed=seed, seconds=round(time.perf_counter() - t0, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
