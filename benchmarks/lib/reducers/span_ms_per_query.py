"""Mean, over traced answers, of the time in the named spans (whole spans,
their children included)."""
from lib.reducers import spans


def reduce(spec, ctx):
    per_query = [
        sum(float(n["ms"]) for name in spec["spans"] for n in spans.named(r.spans, name))
        for r in ctx["requests"] if r.spans
    ]
    return sum(per_query) / len(per_query) if per_query else None
