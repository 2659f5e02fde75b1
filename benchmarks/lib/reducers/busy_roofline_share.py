"""The share of its roofline at which the device ran the traced queries of
the templates that `served_by_counters` name: the least time the chip could
take for them (lib/opcount.py, from the configuration and the templates, the
group table's 8 bytes a slot included; peaks from peaks.json) over the
device's busy time in the traced span.  Where roofline_share divides by one
kernel's events, this divides by everything the device did, so it cannot
pass 100 % while the least time is a lower bound; a traced template that
moved none of the counters adds to the busy time and not to the least time,
so the share is then an underestimate (the `roofline` line names them).
None where no served template ran or the program has none of the counters."""
import json

from lib import opcount


def reduce(spec, ctx):
    dt = ctx["device_trace"]
    if not dt or dt["busy_s"] <= 0.0:
        return None
    served = {t: w for t, w in dt["template_weights"].items()
              if any(ctx["warm_moved"].get(t, {}).get(c, 0.0) > 0.0 for c in spec["served_by_counters"])}
    if not served:
        return None
    least_s, bounds = 0.0, {}
    for template, weight in served.items():
        needs = opcount.query_needs(ctx["config"], ctx["query_set"]["templates"][template])
        t, bound = opcount.least_seconds(needs, ctx["peak"])
        least_s += weight * t
        bounds[bound] = bounds.get(bound, 0.0) + weight
    print(json.dumps({"phase": "roofline", "metric": spec["name"], "least_s": least_s, "busy_s": dt["busy_s"],
                      "bound_by": max(bounds, key=bounds.get), "peak": ctx["peak"]["name"],
                      "queries_counted": {t: round(w, 3) for t, w in sorted(served.items())},
                      "queries_not_counted": {t: round(w, 3) for t, w in sorted(dt["template_weights"].items())
                                              if t not in served}}), flush=True)
    return 100.0 * least_s / dt["busy_s"]
