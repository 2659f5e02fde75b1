"""How far one of the program's counters moved over the window."""


def reduce(spec, ctx):
    name = spec["counter"]
    if name not in ctx["counters_after"] and name not in ctx["counters_before"]:
        return None
    return float(ctx["counters_after"].get(name, 0.0) - ctx["counters_before"].get(name, 0.0))
