"""How far the `counters` moved over the window, summed, over how far the
`over` counters moved (each a name or a list of names): a share of ticks, of
a clock, of one class among several.  None where the program lacks one of
the counters or the denominator stood still."""


def _moved(names, ctx):
    names = [names] if isinstance(names, str) else list(names)
    before, after = ctx["counters_before"], ctx["counters_after"]
    if not names or any(n not in after for n in names):
        return None
    return sum(float(after[n] - before.get(n, 0.0)) for n in names)


def reduce(spec, ctx):
    num, den = _moved(spec["counters"], ctx), _moved(spec["over"], ctx)
    if num is None or not den:
        return None
    return num / den
