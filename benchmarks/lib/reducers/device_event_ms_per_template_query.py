"""Device time of the events whose names match `pattern`, from the profiler's
trace, per traced query of the templates the metric's file names
(`templates`): the readers that divide by `served_by_counter` need a counter
of the program that moved for those templates alone, and a predicate's or a
dictionary's gather moves none.  `{segment_rows}` in the pattern stands for
the configuration's rows a segment: a gather is told from a scatter into a
group table by the length of what it writes.  None where the trace holds no
such event or no such query (a program whose kernels are of another shape)."""
from lib.reducers import device_events


def reduce(spec, ctx):
    dt = ctx["device_trace"]
    if not dt:
        return None
    queries = sum(dt["template_weights"].get(t, 0.0) for t in spec["templates"])
    pattern = spec["pattern"].replace("{segment_rows}", str(int(ctx["config"]["segment_rows"])))
    total_s = device_events.matched_seconds(dt, pattern)
    return total_s * 1000.0 / queries if total_s > 0.0 and queries > 0.0 else None
