"""The control proper to a replicated deployment: one segment answered by
BOTH of its replicas.  Two forms, and the check has to call each of them not
correct:

one_segment_twice   the reference put in the program's place (as the controls
                    of lib/controls.py are): the same sums with the last
                    segment's rows counted twice
route_one_segment_twice
                    the program itself made to do it: a wrapper for the
                    broker's `_route` that sends the first routed segment to
                    its other replica as well, so that the served answer
                    carries the doubled rows and `numSegmentsQueried` is one
                    more than the table has
"""
from __future__ import annotations

from typing import Any, Dict

from lib import controls


def one_segment_twice(mod, spec, params, blocks) -> Dict[str, Any]:
    return mod.answer(spec, params, list(blocks) + [blocks[-1]])


CONTROLS = dict(controls.CONTROLS, one_segment_twice=one_segment_twice)


def route_one_segment_twice(route):
    """`route` is the broker's own `_route` (unbound); the result routes as
    it does and then gives the first segment to another live replica too."""

    def both(self, table, seg_names, *args, **kwargs):
        out = route(self, table, seg_names, *args, **kwargs)
        assign = out[0] if isinstance(out, tuple) else out
        seg = seg_names[0]
        holders = [s for s, segs in assign.items() if seg in segs]
        others = sorted(self.coordinator.external_view(table).get(seg, set()) - set(holders))
        if holders and others:
            assign.setdefault(others[0], []).append(seg)
        return out

    return both
