"""The share of its roofline at which the device ran the traced queries of a
cell whose table is cut by the calendar into segments of unequal rows: the
least time the chip could take for them (lib/monthcount.py: the named columns
over ONLY the TRUE rows whose day satisfies a request's date terms, every row
for a template without one, + 8 B a slot; peaks.json) over the device's busy
time in the traced span.  A template's least time is the mean over the
window's sound requests of that template (their own parameters), weighted by
the queries of it that the traced span held.  Everything the device did is in
the busy time, the rows a segment was padded with among it, and no padded row
is needed work: padding reads as a lower share, a program that prunes nothing
reads every row and reads lower still, and the share cannot pass 100 % while
the least time is a lower bound.  None where the span held no request."""
import json

from lib import monthcount, opcount


def reduce(spec, ctx):
    dt = ctx["device_trace"]
    if not dt or dt["busy_s"] <= 0.0:
        return None
    least_s, counted = 0.0, {}
    for template, weight in dt["template_weights"].items():
        tpl = ctx["query_set"]["templates"][template]
        mine = [r for r in ctx["requests"] if r.template == template]
        if not mine:
            continue
        needs = [monthcount.query_needs(ctx["config"], tpl, r.params) for r in mine]
        t = sum(opcount.least_seconds(n, ctx["peak"])[0] for n in needs) / len(needs)
        least_s += weight * t
        counted[template] = {"queries": round(weight, 3), "rows": sum(n["rows"] for n in needs) / len(needs),
                             "least_ms": t * 1000.0}
    if not counted:
        return None
    print(json.dumps({"phase": "roofline", "metric": spec["name"], "least_s": least_s, "busy_s": dt["busy_s"],
                      "peak": ctx["peak"]["name"], "queries_counted": counted}), flush=True)
    return 100.0 * least_s / dt["busy_s"]
