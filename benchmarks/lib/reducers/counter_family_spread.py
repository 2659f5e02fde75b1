"""How unevenly a family of the program's counters (`prefix` + member name)
moved over the window: the largest delta over the mean delta.  The mean is
taken over the number the configuration states under the key `members`
names (its `servers`), so a member that got nothing, and so may have no
counter at all, still counts: 1.0 = even.  None where the program has no
counter of the family, or none of them moved."""


def reduce(spec, ctx):
    before, after = ctx["counters_before"], ctx["counters_after"]
    deltas = [v - before.get(k, 0.0) for k, v in after.items() if k.startswith(spec["prefix"])]
    total = sum(deltas)
    if not deltas or total <= 0.0:
        return None
    members = max(len(deltas), int(ctx["config"].get(spec["members"], 0)))
    return max(deltas) * members / total
