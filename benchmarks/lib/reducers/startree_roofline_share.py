"""The share of its roofline at which the device ran the traced queries of a
cell whose table has star-trees: the least time the chip could take for them
over the device's busy time in the traced span.  As busy_roofline_share, but
a template that a tree served (its plan was traced over a level:
`served_by_counter` moved while it was warmed) is counted at the LEVEL's rows
(lib/starcount.py: from the configuration and the generator's rows), a
template the scan served at the table's (lib/opcount.py); a template
starcount finds no tree for is counted at the table's rows whatever the
counter says.  Everything the device did is in the busy time, so the share
cannot pass 100 % while each least time is a lower bound.  None where no
traced template's plan was traced over a level (a program before PR 37, a
table without trees): the layer is not there to be measured."""
import json

from lib import opcount, starcount


def reduce(spec, ctx):
    dt = ctx["device_trace"]
    if not dt or dt["busy_s"] <= 0.0:
        return None
    star = {t for t in dt["template_weights"]
            if ctx["warm_moved"].get(t, {}).get(spec["served_by_counter"], 0.0) > 0.0}
    if not star:
        return None
    least_s, counted = 0.0, {}
    for template, weight in dt["template_weights"].items():
        tpl = ctx["query_set"]["templates"][template]
        needs = starcount.query_needs(ctx["config"], tpl) if template in star else None
        how = "level" if needs is not None else "table"
        if needs is None:
            needs = opcount.query_needs(ctx["config"], tpl)
        t, _ = opcount.least_seconds(needs, ctx["peak"])
        least_s += weight * t
        counted[template] = {"queries": round(weight, 3), "rows": how, "least_ms": t * 1000.0}
    print(json.dumps({"phase": "roofline", "metric": spec["name"], "least_s": least_s, "busy_s": dt["busy_s"],
                      "peak": ctx["peak"]["name"], "queries_counted": counted}), flush=True)
    return 100.0 * least_s / dt["busy_s"]
