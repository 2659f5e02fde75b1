"""A quantile of the client's latency over every request of the window, in
milliseconds.  A request that failed (error, time-out, partial answer, or an
answer that differs from the reference) enters as the request time-out."""
import numpy as np


def reduce(spec, ctx):
    lat = [ctx["failed_latency_s"] if r.index in ctx["faults"] else r.latency_s for r in ctx["window_requests"]]
    return float(np.quantile(np.asarray(lat), float(spec["q"]))) * 1000.0 if lat else None
