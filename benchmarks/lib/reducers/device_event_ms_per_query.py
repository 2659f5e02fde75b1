"""Device time of the events whose names match `pattern`, from the profiler's
trace, per query that ran inside the traced span of the window and whose
plan runs those events (see device_events.served_queries)."""
from lib.reducers import device_events


def reduce(spec, ctx):
    dt = ctx["device_trace"]
    if not dt:
        return None
    queries = sum(device_events.served_queries(spec, ctx).values())
    total_s = device_events.matched_seconds(dt, spec["pattern"])
    return total_s * 1000.0 / queries if total_s > 0.0 and queries > 0.0 else None
