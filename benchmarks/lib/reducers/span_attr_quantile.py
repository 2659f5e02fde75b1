"""A quantile (`q` in the metric's file), over traced answers, of an attribute
summed over the named spans of an answer: where a mean hides a slow class of
a few per cent of the answers.  None where no traced answer has the attr."""
import numpy as np

from lib.reducers import spans


def reduce(spec, ctx):
    per_query = []
    for r in ctx["requests"]:
        if r.spans:
            vals = [n.get("attrs", {}).get(spec["attr"]) for n in spans.named(r.spans, spec["span"])]
            vals = [float(v) for v in vals if v is not None]
            if vals:
                per_query.append(sum(vals))
    return float(np.quantile(np.asarray(per_query), float(spec["q"]))) if per_query else None
