"""Controls for the reference kind `filter_group_aggs`: the reference put in
the program's place with the configuration's `merge` guarantee broken (a
group-by's tables meet by VALUE: segments' dictionaries differ, so a code
means another value in every segment).  The check has to call each of them
not correct on some answer; where it calls one correct, that answer cannot
see the guarantee (lib/controls.py's two need `spec["sum"]` and serve the
kind `filter_group_sum` alone).

merged_by_code  every dictionary column a query names is served as its CODE in
                each block's own dictionary (its rank among the block's sorted
                distinct values): predicates and aggregates read codes as if
                they were values, a group-by's tables meet slot by slot, and
                the merged keys are decoded through the FIRST block's
                dictionary (a code past its end has no value and is dropped):
                a fold of the tables on the chip without a remap, or with an
                identity one
tail_dropped    the last block loses the rows whose value of such a column lies
                in the top sixteenth of that block's own dictionary: a decode
                that stops short of a segment's own cardinality, or reads the
                slots between it and the table's bound wrong

`controls_for(config)` binds them to the configuration's dictionary columns;
a control returns None where a query names no such column (nothing to break).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

_DICTIONARIES: Dict[int, Any] = {}  # id(column array) -> (the array, its sorted distinct values): drawn once a column


def _dictionary(col: np.ndarray) -> np.ndarray:
    held = _DICTIONARIES.get(id(col))
    if held is None or held[0] is not col:
        held = _DICTIONARIES[id(col)] = (col, np.unique(col))
    return held[1]


def _named(spec: Dict[str, Any], dict_columns) -> List[str]:
    """The dictionary columns a query reads, in the order it names them."""
    tests = list(spec["where"]) + [t for a in spec["aggs"] for t in a.get("filter") or ()]
    cols = list(spec["group_by"]) + [t[0] for t in tests] + [a["col"] for a in spec["aggs"] if a.get("col")]
    return [c for c in dict.fromkeys(cols) if c in dict_columns]


def merged_by_code(mod, spec, params, blocks, dict_columns) -> Optional[Dict[str, Any]]:
    cols = _named(spec, dict_columns)
    if not cols:
        return None
    coded = [dict(b, **{c: np.searchsorted(_dictionary(b[c]), b[c]).astype(np.int64) for c in cols}) for b in blocks]
    out = mod.answer(spec, params, coded)
    if "rows" not in out:
        return out
    first = [_dictionary(blocks[0][g]) if g in cols else None for g in spec["group_by"]]
    rows = []
    for row in out["rows"]:
        if all(d is None or row[i] < len(d) for i, d in enumerate(first)):
            rows.append([k if d is None else int(d[k]) for k, d in zip(row, first)] + row[len(first):])
    return dict(out, rows=rows)


def tail_dropped(mod, spec, params, blocks, dict_columns) -> Optional[Dict[str, Any]]:
    cols = _named(spec, dict_columns)
    if not cols:
        return None
    last = blocks[-1]
    keep = np.ones(len(next(iter(last.values()))), bool)
    for c in cols:
        d = _dictionary(last[c])
        keep &= last[c] < d[len(d) - max(1, len(d) // 16)]
    return mod.answer(spec, params, list(blocks[:-1]) + [{k: v[keep] for k, v in last.items()}])


def controls_for(config: Dict[str, Any]) -> Dict[str, Callable]:
    """{name: fn(mod, spec, params, blocks)}, the form check.compare's `answer_fn` takes."""
    raw = set(config["table_config"].get("noDictionaryColumns") or ())
    dict_columns = {c["name"] for c in config["columns"]} - raw

    def bound(fn):
        return lambda mod, spec, params, blocks: fn(mod, spec, params, blocks, dict_columns)

    return {"merged_by_code": bound(merged_by_code), "tail_dropped": bound(tail_dropped)}
