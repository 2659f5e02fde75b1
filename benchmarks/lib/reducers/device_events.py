"""Device time of the trace's events whose names match a pattern, and the
traced queries that those events belong to."""
import re


def matched_seconds(device_trace, pattern: str) -> float:
    pat = re.compile(pattern)
    return sum(sec for name, (_, sec) in device_trace["events"].items() if pat.search(name))


def served_queries(spec, ctx):
    """{template: queries inside the traced span} for the templates whose
    plans run the matched events.  Where the metric's file names a
    `served_by_counter`, those are the templates during whose warm-up that
    counter of the program moved (`scan.traced.pallas`: the plan's scan got
    the Pallas kernel); a template that runs no such kernel brings neither
    queries nor bytes.  Without the key every traced template counts."""
    weights = ctx["device_trace"]["template_weights"]
    counter = spec.get("served_by_counter")
    if counter is None:
        return dict(weights)
    return {t: w for t, w in weights.items() if ctx["warm_moved"].get(t, {}).get(counter, 0.0) > 0.0}
