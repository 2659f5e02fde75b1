"""Mean, over traced answers, of the client's latency less the named span:
the time a query spends outside that span (HTTP, parse, plan, reduce,
serialise), by the benchmark's own clock and the program's span."""
from lib.reducers import spans


def reduce(spec, ctx):
    per_query = []
    for r in ctx["requests"]:
        if r.spans:
            inside = sum(float(n["ms"]) for n in spans.named(r.spans, spec["span"]))
            per_query.append((r.done - r.sent) * 1000.0 - inside)
    return sum(per_query) / len(per_query) if per_query else None
