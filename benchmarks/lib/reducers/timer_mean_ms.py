"""Mean of one of the program's timers over the window, in milliseconds: how
far its total moved over how far its count moved (`timer:<name>:total_ms` and
`:count`, as lib/cluster.py exports every timer).  None where the program has
no such timer or it was not updated inside the window."""


def reduce(spec, ctx):
    count, total = f"timer:{spec['timer']}:count", f"timer:{spec['timer']}:total_ms"
    before, after = ctx["counters_before"], ctx["counters_after"]
    if count not in after:
        return None
    n = after[count] - before.get(count, 0.0)
    return (after[total] - before.get(total, 0.0)) / n if n > 0 else None
