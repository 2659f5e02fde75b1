"""Mean of the client's time from send to answer (`done - sent`, by the
benchmark's clock: NOT from the due time, which in an open loop holds the
generator's own queue) over the window's answered requests, less the mean of
one of the program's timers over the window (`timer:<name>:total_ms` and
`:count`, as lib/cluster.py exports every timer), in milliseconds: the part of
a request's time the program's timer does not cover.  A difference of two
means over the same requests only where the timer moved once for every
answered request.  None where the program has no such timer, where it did not
move inside the window, or where no request was answered."""


def reduce(spec, ctx):
    count, total = f"timer:{spec['timer']}:count", f"timer:{spec['timer']}:total_ms"
    before, after = ctx["counters_before"], ctx["counters_after"]
    if count not in after:
        return None
    n = after[count] - before.get(count, 0.0)
    answered = [r for r in ctx["window_requests"] if r.status == 200 and r.done > r.sent]
    if n <= 0 or not answered:
        return None
    client_ms = sum(r.done - r.sent for r in answered) * 1000.0 / len(answered)
    return client_ms - (after[total] - before.get(total, 0.0)) / n
