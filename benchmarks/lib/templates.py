"""Query templates: drawing a request's parameters and rendering its SQL."""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def draw_params(template: Dict[str, Any], rng: np.random.Generator) -> Dict[str, int]:
    """Parameters in file order; a `linear` one reads those before it."""
    out: Dict[str, int] = {}
    for name, dom in template["params"].items():
        if "int" in dom:
            lo, hi = dom["int"]
            out[name] = int(rng.integers(lo, hi + 1))
        elif "linear" in dom:
            out[name] = int(dom.get("const", 0)) + sum(int(w) * out[p] for p, w in dom["linear"])
        else:
            raise ValueError(f"parameter {name}: unknown domain {dom!r}")
    return out


def render(template: Dict[str, Any], params: Dict[str, int], traced: bool = False) -> str:
    sql = template["sql"].format(**params)
    # `trace` is an option of the query, so a traced request is the same
    # request with its span tree returned in the response
    return ("SET trace = true; " + sql) if traced else sql
