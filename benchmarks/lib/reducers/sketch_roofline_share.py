"""The share of its roofline at which the device ran the traced queries of a
cell whose templates are of the reference kind `filter_group_sketch`: the
least time the chip could take for them (lib/sketchcount.py, from the
configuration and the templates; peaks from peaks.json) over the device's
busy time in the traced span.  agg_roofline_share's principle with another
count: everything the device did is in the busy time and every traced
template is counted, so the share cannot pass 100 % while each least time is
a lower bound.  None without a device trace."""
import json

from lib import opcount, sketchcount


def reduce(spec, ctx):
    dt = ctx["device_trace"]
    if not dt or dt["busy_s"] <= 0.0 or not dt["template_weights"]:
        return None
    least_s, counted = 0.0, {}
    for template, weight in dt["template_weights"].items():
        needs = sketchcount.query_needs(ctx["config"], ctx["query_set"]["templates"][template])
        t, bound = opcount.least_seconds(needs, ctx["peak"])
        least_s += weight * t
        counted[template] = {"queries": round(weight, 3), "least_ms": t * 1000.0, "bound_by": bound,
                             "bytes_per_row": needs["bytes_per_row"], "table_bytes": needs["table_bytes"]}
    print(json.dumps({"phase": "roofline", "metric": spec["name"], "least_s": least_s, "busy_s": dt["busy_s"],
                      "peak": ctx["peak"]["name"], "queries_counted": counted}), flush=True)
    return 100.0 * least_s / dt["busy_s"]
