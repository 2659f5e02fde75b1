"""How far the named counters of the program moved while ONE template was
warmed (the harness's `warm_moved`: each template sent twice at the file's
literals, its counters read before and after), summed over `counters`, mean
over the cell's templates.  A counter that did not move while a template was
warmed counts 0 for it.  None where the program has none of the counters."""


def reduce(spec, ctx):
    names = spec["counters"]
    if not any(n in ctx["counters_after"] for n in names) or not ctx["warm_moved"]:
        return None
    per_template = [sum(float(moved.get(n, 0.0)) for n in names) for moved in ctx["warm_moved"].values()]
    return sum(per_template) / len(per_template)
