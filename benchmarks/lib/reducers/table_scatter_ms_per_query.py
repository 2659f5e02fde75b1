"""Device time of the scatters into a group table of the served templates'
key space, per traced query of those templates (device_events.served_queries).

XLA's TPU compiler names a scatter itself (`%fusion.N = ... kind=kCustom`: a
`jax.named_scope` reaches the HLO metadata, which the trace's event names do
not carry), so the events are told apart by what they write: a flat 32-bit
table of m x `group_space` slots, `group_space` being a served template's in
the query set and m the number of row chunks the program keeps apart
(ops/segmented.py `_chunked_scatter`: one table a count, one a 12-bit limb
of a sum).  A gather or a scatter of another size (the sparse plan's slot
tables, a row-length permutation) does not match."""
import re

from lib.reducers import device_events

_SCATTER = re.compile(r"^%[\w.\-]+ = [suf]32\[(\d+)\]\S* fusion\(.*kind=kCustom")


def reduce(spec, ctx):
    dt = ctx["device_trace"]
    if not dt:
        return None
    served = device_events.served_queries(spec, ctx)
    spaces = {int(ctx["query_set"]["templates"][t]["group_space"]) for t in served}
    total_s = 0.0
    for name, (_, sec) in dt["events"].items():
        m = _SCATTER.match(name)
        if m and any(int(m.group(1)) % g == 0 for g in spaces):
            total_s += sec
    queries = sum(served.values())
    return total_s * 1000.0 / queries if total_s > 0.0 and queries > 0.0 else None
