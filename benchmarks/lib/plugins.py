"""Files found by name.  A generator, a reference kind or a reducer is a
module `lib/<family>/<name>.py`; a configuration, a query set, a traffic mix
or a per-layer metric is `<family>/<name>.json` beside `lib/`.  A later PR
adds files and edits none."""
from __future__ import annotations

import importlib
import json
import os
import re
from typing import Any, Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name) or ".." in name:
        raise ValueError(f"not a name: {name!r}")
    return name


def load_json(family: str, name: str) -> Dict[str, Any]:
    path = os.path.join(ROOT, family, _checked(name) + ".json")
    with open(path) as f:
        return json.load(f)


def load_module(family: str, name: str):
    return importlib.import_module(f"lib.{_checked(family)}.{_checked(name)}")
