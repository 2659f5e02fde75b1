"""Walking the program's span trees (the `trace` of a traced answer)."""
from __future__ import annotations

from typing import Any, Dict, Iterator


def walk(node: Dict[str, Any]) -> Iterator[Dict[str, Any]]:
    yield node
    for c in node.get("children", ()):
        yield from walk(c)


def named(tree: Dict[str, Any], name: str) -> Iterator[Dict[str, Any]]:
    """Spans called `name`, or `name:<suffix>` (launch:seg3, round:0)."""
    for n in walk(tree):
        if n["name"] == name or n["name"].startswith(name + ":"):
            yield n
