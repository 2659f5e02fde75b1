#!/usr/bin/env python3
"""The readings the limits of the reference kind `filter_group_sketch` rest
on, from the generator's rows alone: no program, no chip.

    python3 benchmarks/tools/control_sketch.py --config ssb_flat_sf10_sketch --seeds 7 [--rows 240000]

For every template of the configuration's query set at its published
literals, over the configuration's table drawn from each seed: what the
STATED sketches (lib/references/filter_group_sketch.py: HyperLogLog at the
file's log2m, the equi-width histogram at its bins) read against the exact
answer, and what the same sketches read one step of precision lower (a
register fewer in log2m, half the bins), handed to `compare` in a served
answer's place.  The stated sketch must be called correct and each lower one
not.  A line a (seed, template), JSON.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import plugins  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="ssb_flat_sf10_sketch")
    ap.add_argument("--seeds", type=int, nargs="+", default=[7])
    ap.add_argument("--rows", type=int, default=None, help="a smaller table than the configuration's (4 segments)")
    args = ap.parse_args()
    config = plugins.load_json("configs", args.config)
    if args.rows is not None:
        config = dict(config, rows=args.rows, segment_rows=max(1, args.rows // 4))
    gen = plugins.load_module("datagen", config["datagen"])
    queries = plugins.load_json("queries", config["query_set"])["templates"]
    rows, seg_rows = int(config["rows"]), int(config["segment_rows"])
    failed = 0
    for seed in args.seeds:
        t0 = time.perf_counter()
        blocks = [gen.make_segment(config, seed, i, min(seg_rows, rows - i * seg_rows)) for i in range(-(-rows // seg_rows))]
        drawn_s = time.perf_counter() - t0
        for name, template in queries.items():
            spec = template["reference"]
            ref_mod = plugins.load_module("references", spec["kind"])
            t0 = time.perf_counter()
            want = ref_mod.answer(spec, template["ssb"], blocks)
            answer_s = time.perf_counter() - t0
            line = {"seed": seed, "template": name, "rows": rows, "drawn_s": round(drawn_s, 1), "answer_s": round(answer_s, 1)}
            ok, numbers = ref_mod.compare(spec, *ref_mod.served_from(want, spec), want)
            line["stated"] = dict(numbers, correct=ok)
            lower = {"log2m_less": 1} if any(a["fn"] == "hll" for a in spec["aggs"]) else {"bins_divisor": 2}
            low = ref_mod.answer(spec, template["ssb"], blocks, **lower)
            low_ok, low_numbers = ref_mod.compare(spec, *ref_mod.served_from(low, spec), want)
            line["lower"] = dict(low_numbers, correct=low_ok, **lower)
            failed += (not ok) + low_ok
            print(json.dumps(line), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
