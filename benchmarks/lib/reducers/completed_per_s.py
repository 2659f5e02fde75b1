"""Sound answers completed inside the window, per second of the window."""


def reduce(spec, ctx):
    inside = sum(1 for r in ctx["window_requests"] if r.index not in ctx["faults"] and r.done <= ctx["window_s"])
    return inside / ctx["window_s"]
