"""As span_ms_per_query, for a span that a program may not have: mean, over
the traced answers that hold at least one of the named spans, of the time in
them; None where no answer holds one (span_ms_per_query reads 0.0 there,
which a reader cannot tell from a span that took no time)."""
from lib.reducers import spans


def named_ms(tree, names):
    """Summed time of the spans of `tree` called one of `names` (whole
    spans, children included), or None where it holds none."""
    found = [float(n["ms"]) for name in names for n in spans.named(tree, name)]
    return sum(found) if found else None


def reduce(spec, ctx):
    per_query = [ms for ms in (named_ms(r.spans, spec["spans"]) for r in ctx["requests"] if r.spans) if ms is not None]
    return sum(per_query) / len(per_query) if per_query else None
