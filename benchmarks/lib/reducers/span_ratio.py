"""Mean, over traced answers, of the time in the `numerator` spans over the
time in the `denominator` spans of the same answer (whole spans, children
included).  An answer without either kind, or whose denominator took no
time, is left out; None where no answer is left."""
from lib.reducers.span_ms_per_query_if_any import named_ms


def reduce(spec, ctx):
    per_query = []
    for r in ctx["requests"]:
        if r.spans:
            num, den = named_ms(r.spans, spec["numerator"]), named_ms(r.spans, spec["denominator"])
            if num is not None and den:
                per_query.append(num / den)
    return sum(per_query) / len(per_query) if per_query else None
