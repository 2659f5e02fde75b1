"""Controls: the reference put in the program's place with one guarantee of
the configuration broken.  The check has to call each of them not correct.

float32_sums   the exact integer sums accumulated in float32 instead, the
               precision a later PR would be tempted by (the configuration
               states exact integers, so the limit on every difference is 0)
one_segment_missing
               a partial result: the same sums over all segments but the last
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def float32_sums(mod, spec, params, blocks) -> Dict[str, Any]:
    exact = mod.answer(spec, params, blocks)
    if "scalar" in exact:
        total = np.float32(0.0)
        for b in blocks:
            total = np.float32(total + _scalar_f32(mod, spec, params, b))
        return {"scalar": int(total), "matched": exact["matched"]}
    out: Dict[Any, np.float32] = {}
    for b in blocks:
        for k, v in _groups_f32(mod, spec, params, b).items():
            out[k] = np.float32(out.get(k, np.float32(0.0)) + v)
    return {"groups": {k: int(v) for k, v in out.items()}}


def _selected(mod, spec, params, cols):
    m = None
    for test in spec["where"]:
        t = mod._mask(cols[test[0]], test[1], [params[p] for p in test[2:]])
        m = t if m is None else m & t
    sel = np.flatnonzero(m)
    return sel, mod._value(spec["sum"], cols, sel)


def _scalar_f32(mod, spec, params, cols):
    _, vals = _selected(mod, spec, params, cols)
    # a running float32 sum, as an accumulator on the device would keep it
    return np.cumsum(vals.astype(np.float32), dtype=np.float32)[-1] if len(vals) else np.float32(0.0)


def _groups_f32(mod, spec, params, cols):
    sel, vals = _selected(mod, spec, params, cols)
    keys = np.stack([cols[g][sel].astype(np.int64) for g in spec["group_by"]], axis=1)
    uniq, inv = np.unique(keys, axis=0, return_inverse=True)
    sums = np.zeros(len(uniq), np.float32)
    np.add.at(sums, inv.reshape(-1), vals.astype(np.float32))
    return {tuple(int(x) for x in k): s for k, s in zip(uniq, sums)}


def one_segment_missing(mod, spec, params, blocks) -> Dict[str, Any]:
    return mod.answer(spec, params, blocks[:-1])


CONTROLS = {"float32_sums": float32_sums, "one_segment_missing": one_segment_missing}
