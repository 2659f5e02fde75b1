"""The sum, when the window ended, of the program's gauges whose name matches
`pattern` (one a server: `residency.<server>.starTreeBytes`).  lib/cluster.py
exports counters and timers only, so this reads the program's process-wide
registry itself: the one reducer that imports the program.  None where the
program has no such gauge."""
import re


def reduce(spec, ctx):
    try:
        from pinot_tpu.utils.metrics import METRICS
    except ImportError:
        return None
    found = [v for k, v in METRICS.snapshot().get("gauges", {}).items() if re.fullmatch(spec["pattern"], k)]
    return float(sum(found)) if found else None
