"""What decides `correct`: every answer's envelope, and a seeded sample of
answers compared in full with the plain reference."""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from lib import plugins
from lib.loadgen import Request


def envelope_fault(req: Request, segments: int) -> Optional[str]:
    """Why this answer breaks the configuration's guarantees, or None: it
    came back, whole, from every segment."""
    if req.error is not None or req.status != 200:
        return f"error: {req.error or req.status}"
    m = req.meta
    if m.get("partialResult"):
        return "partial result"
    if m.get("exceptions"):
        return f"exceptions: {m['exceptions']}"
    if m.get("numSegmentsQueried") != segments:
        return f"numSegmentsQueried {m.get('numSegmentsQueried')} != {segments}"
    if m.get("numServersQueried") != m.get("numServersResponded"):
        return "a server did not respond"
    if not req.rows:
        return "no rows"
    return None


def pick_sample(reqs: List[Request], k: int, seed: int) -> List[Request]:
    """k answered requests drawn from the seed, the one with the most rows
    among them, and at least one of every template when k allows."""
    good = [r for r in reqs if r.status == 200 and r.error is None]
    if not good:
        return []
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    chosen = {max(good, key=lambda r: len(r.rows)).index}
    by_t: Dict[str, List[Request]] = {}
    for r in good:
        by_t.setdefault(r.template, []).append(r)
    for name in sorted(by_t):
        if len(chosen) < k:
            chosen.add(by_t[name][int(rng.integers(0, len(by_t[name])))].index)
    rest = [r.index for r in good if r.index not in chosen]
    take = min(max(0, k - len(chosen)), len(rest))
    if take:
        chosen.update(int(i) for i in rng.choice(rest, size=take, replace=False))
    return [r for r in good if r.index in chosen]


def compare(req: Request, query_set: Dict[str, Any], blocks, answer_fn=None) -> Tuple[bool, Dict[str, Any]]:
    """One served answer against the reference over the same rows.
    `answer_fn(module, spec, params, blocks)` stands in for the reference's
    own `answer` when a control computes it another way."""
    spec = query_set["templates"][req.template]["reference"]
    mod = plugins.load_module("references", spec["kind"])
    ref = mod.answer(spec, req.params, blocks) if answer_fn is None else answer_fn(mod, spec, req.params, blocks)
    ok, numbers = mod.compare(spec, req.columns, req.rows, ref)
    return ok, dict(numbers, template=req.template, params=req.params)
