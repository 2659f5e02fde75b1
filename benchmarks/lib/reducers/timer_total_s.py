"""The total of one of the program's timers when the window ended, in
seconds (`timer:<name>:total_ms`, as lib/cluster.py exports every timer): for
a timer that only set-up moves.  None where the program has no such timer."""


def reduce(spec, ctx):
    total = ctx["counters_after"].get(f"timer:{spec['timer']}:total_ms")
    return None if total is None else float(total) / 1000.0
