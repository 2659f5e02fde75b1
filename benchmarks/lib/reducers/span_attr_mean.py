"""Mean, over traced answers, of an attribute summed over the named spans."""
from lib.reducers import spans


def reduce(spec, ctx):
    per_query = []
    for r in ctx["requests"]:
        if r.spans:
            vals = [n.get("attrs", {}).get(spec["attr"]) for n in spans.named(r.spans, spec["span"])]
            vals = [float(v) for v in vals if v is not None]
            if vals:
                per_query.append(sum(vals))
    return sum(per_query) / len(per_query) if per_query else None
