"""What the segments of one table on one server share: the shape a query's
kernel is compiled for.

A compiled kernel bakes a dictionary column's size into its strides, its
group table and its look-up tables (query/planner.py).  Segments drawn by one
generator hold the same dictionaries, so one kernel serves a table; segments
that were built apart (an ingested table) hold a dictionary of another size
each, and a kernel a segment is a compile a segment a query shape and a
launch a segment a query.  So the size a kernel bakes comes from the TABLE:
for the segments whose column rides in one lane (the same packed width and
code dtype: other lanes are other kernels anyway) it is a bound they all fit
under.  Where every such segment agrees the bound IS their cardinality, and
the table's programs are what they were; where they differ it is the largest,
rounded up so that the next segment, whose dictionary is a little larger
still, is no new kernel for every other.  A segment's own dictionary enters
through parameters (predicate tables and code ranges) and the decode.

ServerInstance keeps one TableShape a table and tells it of every segment
added and dropped; a query's planning (planner.QueryPlanning) asks it.
"""
from __future__ import annotations

import threading
from collections import Counter
from typing import Dict, List, Optional, Tuple


def _rounded_up(n: int) -> int:
    """`n` up to a multiple of 1/16 of the power of two it fits under: 7,940
    -> 8,192, 673 -> 704.  At most 1/8 more slots than the largest segment
    needs, and never past that power of two, so never past the lane."""
    step = max(1, (1 << max(n - 1, 0).bit_length()) >> 4)
    return -(-n // step) * step


def column_lane(c) -> Optional[Tuple]:
    """What tells the kernels over two segments' column `c` apart whatever
    their dictionaries hold, or None where the kernel bakes more of the
    dictionary than its size (a raw or multi-value column: no shared shape)."""
    if c.dictionary is None or c.mv_lengths is not None or c.codes is None:
        return None
    return (c.code_bits, str(c.codes.dtype))


class TableShape:
    """The dictionary sizes of one table's segments on one server, by column
    and lane, and the bound each lane's kernels are compiled for."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sizes: Dict[Tuple, Counter] = {}  # (column, lane) -> {cardinality: segments}
        self._counted: Dict[str, List[Tuple[Tuple, int]]] = {}  # segment name -> what it added to _sizes
        self._bounds: Dict[Tuple, int] = {}
        # moves whenever a bound does: what a segment's memoised signatures
        # were made under (planner._SegmentMemo)
        self.version = 0

    def _uncount(self, name: str) -> List[Tuple]:
        """Forget what segment `name` added; the (column, lane)s it touched."""
        counted = self._counted.pop(name, [])
        for key, size in counted:
            sizes = self._sizes[key]
            sizes[size] -= 1
            if sizes[size] <= 0:
                del sizes[size]
        return [key for key, _ in counted]

    def _rebound(self, keys) -> None:
        for key in keys:
            sizes = self._sizes.get(key)
            bound = 0 if not sizes else (next(iter(sizes)) if len(sizes) == 1 else _rounded_up(max(sizes)))
            if bound != self._bounds.get(key, 0):
                self._bounds[key] = bound
                self.version += 1

    def add(self, segment) -> None:
        """Count `segment`'s dictionaries; one of its name counted before
        (replaced in place) leaves first."""
        counted = []
        for name, c in segment.columns.items():
            lane = column_lane(c)
            if lane is not None:
                counted.append(((name, lane), c.dictionary.cardinality))
        with self._lock:
            touched = self._uncount(segment.name)
            self._counted[segment.name] = counted
            for key, size in counted:
                self._sizes.setdefault(key, Counter())[size] += 1
            self._rebound(touched + [key for key, _ in counted])

    def remove(self, name: str) -> None:
        with self._lock:
            self._rebound(self._uncount(name))

    def longest(self, names) -> int:
        """The largest bound of any lane of the columns `names`: whether a
        kernel over some segment of the table can be compiled for a
        dictionary past a given length, without asking a segment."""
        with self._lock:
            return max((size for (name, _), size in self._bounds.items() if name in names), default=0)

    def bound(self, name: str, c) -> int:
        """The dictionary size a kernel over column `c` (called `name`) of
        one of this table's segments is compiled for; `c`'s own where the
        table has no say (column_lane) or does not know the segment's lane."""
        lane = column_lane(c)
        own = c.cardinality
        if lane is None:
            return own
        with self._lock:
            return max(own, self._bounds.get((name, lane), own))
