"""A kernel's share of its roofline: the least time the chip could take for
the queries that ran the kernel inside the traced span (lib/opcount.py, from
the configuration and the templates; peaks from peaks.json) over the device
time of the kernel's events.  Only templates whose plans run the kernel are
counted (device_events.served_queries): a query the kernel never sees adds
nothing to the bytes.  Prints which peak bounds it and what it counted."""
import json

from lib import opcount
from lib.reducers import device_events


def reduce(spec, ctx):
    dt = ctx["device_trace"]
    if not dt:
        return None
    kernel_s = device_events.matched_seconds(dt, spec["pattern"])
    served = device_events.served_queries(spec, ctx)
    if kernel_s <= 0.0 or not served:
        return None
    least_s, bounds = 0.0, {}
    for template, weight in served.items():
        needs = opcount.query_needs(ctx["config"], ctx["query_set"]["templates"][template])
        t, bound = opcount.least_seconds(needs, ctx["peak"])
        least_s += weight * t
        bounds[bound] = bounds.get(bound, 0.0) + weight
    print(json.dumps({"phase": "roofline", "metric": spec["name"], "least_s": least_s, "kernel_s": kernel_s,
                      "bound_by": max(bounds, key=bounds.get), "peak": ctx["peak"]["name"],
                      "queries_counted": {t: round(w, 3) for t, w in sorted(served.items())},
                      "queries_not_counted": {t: round(w, 3) for t, w in sorted(dt["template_weights"].items())
                                              if t not in served}}), flush=True)
    return 100.0 * least_s / kernel_s
