"""Device busy time per query that ran inside the traced span."""


def reduce(spec, ctx):
    dt = ctx["device_trace"]
    if not dt or not dt["queries_in_trace"] or dt["busy_s"] <= 0.0:
        return None
    return dt["busy_s"] * 1000.0 / dt["queries_in_trace"]
