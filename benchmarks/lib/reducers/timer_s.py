"""One of the benchmark's own set-up timers, in seconds."""


def reduce(spec, ctx):
    v = ctx["timers"].get(spec["timer"])
    return None if v is None else float(v)
