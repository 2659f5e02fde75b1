#!/usr/bin/env python3
"""The controls of a `filter_group_aggs` cell at its own size, on the chip
(not part of any run of the benchmark; tools/control.py is its twin for the
kind `filter_group_sum`): for each seed one set-up, the cell's warm-up, a
short window at the cell's own load, then per TEMPLATE the program's answers
compared with the reference and the same answers' references computed by
each control of lib/controls_aggs.py; and, once a seed, the query set's
probe of the WHOLE merged table (`--whole`, a template of no traffic mix),
compared and controlled the same way.

    python benchmarks/tools/control_aggs.py --workload ssqe_exp001_50seg.aggs_closed \
        --seeds 2147483659,2147483693 --seconds 8

A control the check calls correct on every answer of a template is a
guarantee that template cannot see: the line says so per template, which is
what PERF.md section 2 reports.
"""
import argparse
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import check, controls_aggs, harness, loadgen  # noqa: E402


def _verdicts(reqs, query_set, blocks, control_of=None):
    """The check's verdict on each answer against the reference, or against
    what `control_of(request)` computes in its place; `of` counts the answers
    the control applies to (it gives None where it has nothing to break)."""
    got = []
    for r in reqs:
        ref = control_of(r) if control_of else None
        if control_of and ref is None:
            continue
        got.append(check.compare(r, query_set, blocks, answer_fn=(lambda *_, ref=ref: ref) if control_of else None))
    off = [n.get("missing", 0) + n.get("extra", 0) + n.get("wrong_aggs", 0) + n.get("out_of_order", 0) for _, n in got]
    return {"called_correct": sum(1 for ok, _ in got if ok), "of": len(got),
            "differing_min": min(off, default=None), "differing_max": max(off, default=None),
            "max_abs_diff": max((n.get("max_abs_diff", 0) for _, n in got), default=None)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-answers", type=int, default=4, help="answers a template on which each control is computed")
    ap.add_argument("--whole", default="group_low_high_whole", help="the query set's probe of the whole merged table")
    ap.add_argument("--rehearse", action="store_true", help="here, on the CPU, at a toy size")
    ap.add_argument("--rehearse-rows", type=int, default=40_000)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    config, qs = cell["config"], cell["query_set"]
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = dict(config, rows=args.rehearse_rows, segment_rows=max(1, args.rehearse_rows // 4))
    sys.path.insert(0, harness.REPO)
    from lib import plugins
    from lib import cluster as cluster_mod

    devices, _ = harness.find_devices(1, rehearse=args.rehearse)
    fns = controls_aggs.controls_for(config)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cl = cluster_mod.Cluster(config, seed, devices)
        try:
            def control(fn):
                def of_request(request):
                    spec = qs["templates"][request.template]["reference"]
                    return fn(plugins.load_module("references", spec["kind"]), spec, request.params, cl.blocks)
                return of_request

            warm = harness.warm_up(cl.url, cell, traced=False)
            w = loadgen.run(cl.url, cell["mix"], qs, seed, args.seconds)
            faults = [f for f in (check.envelope_fault(r, cl.num_segments) for r in warm + w["requests"]) if f]
            sample = warm + check.pick_sample(w["requests"], int(cell["mix"]["sample_checked"]), seed)
            probe = loadgen.Request(-100, -1, args.whole, dict(qs["templates"][args.whole]["ssb"]), 0.0)
            loadgen.send(cl.url, probe, qs["templates"][args.whole], False, time.perf_counter())
            why = check.envelope_fault(probe, cl.num_segments)
            if why:
                faults.append(f"{args.whole}: {why}")
            for name in harness.cell_templates(cell["mix"]) + ([] if why else [args.whole]):
                reqs = [probe] if name == args.whole else [r for r in sample if r.template == name]
                line = {"workload": cell["cell"]["name"], "seed": seed, "template": name, "in_the_mix": name != args.whole,
                        "rows_an_answer": len(reqs[0].rows) if reqs else 0, "limit": 0,
                        "program": _verdicts(reqs, qs, cl.blocks)}
                distinct = list({str(sorted(r.params.items())): r for r in reqs}.values())  # a literal-free template is one string
                for cname, fn in fns.items():
                    line[cname] = _verdicts(distinct[: args.control_answers], qs, cl.blocks, control(fn))
                harness.emit("control_aggs", args.rehearse, **line)
        finally:
            cl.close()
        harness.emit("control_seed_done", args.rehearse, seed=seed, answers=len(w["requests"]), envelope_faults=faults[:5],
                     seconds=round(time.perf_counter() - t0, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
