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

A kernel bakes its segment's ROWS too: every `arange`, the lane unpack and
every array's shape.  Segments cut every n rows hold the same rows; a table
cut by time (a segment a month) holds another count in each, and so would a
kernel a segment.  So the rows a kernel is compiled for, and the resident
columns are padded to, come from the table as well (`TableShape.rows`): the
count itself where the segments agree (no pad, no mask: the programs are
what they were), else a bound they fit under; a segment of fewer rows binds
its true count as a parameter and masks the rest out of every filter
(planner.ROWS_KEY), the rule a star-tree level's table has always had
(row_bucket: indexes/startree.py LevelSegment).

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


# Rows padded on the device come in buckets: a power of two from _MIN_BUCKET
# up to the dense scan kernel's row tile (= packing.BLOCK_ROWS: a packed
# column's words are whole blocks of it), whole tiles past it (35,000 rows ->
# 65,536, 4,375 -> 8,192, one grand total -> 1,024; 728,961 -> 753,664).
_MIN_BUCKET = 1 << 10
_ROW_TILE = 1 << 15


def row_bucket(num_rows: int) -> int:
    if num_rows > _ROW_TILE:
        return -(-num_rows // _ROW_TILE) * _ROW_TILE
    return max(_MIN_BUCKET, 1 << max(0, num_rows - 1).bit_length())


def _rows_bound(counts: Counter) -> int:
    """The rows a table's kernels are compiled for, from {rows: segments}:
    the largest count itself where several segments hold it or it is the
    only one (a table cut every n rows: its tail, shorter, is the only
    segment padded and masked), else its bucket (a table cut by time: the
    next month, a little larger, fits under it)."""
    if not counts:
        return 0
    most = max(counts)
    return most if len(counts) == 1 or counts[most] > 1 else row_bucket(most)


def _padded_rows(own: int, bound: int) -> int:
    """What a segment of `own` rows is padded to under a table's `bound`: the
    bound, or its own bucket where that is under half of it (a segment far
    smaller than the rest is not padded to their size); itself where it
    fills the bound."""
    if own >= bound:
        return own
    bucket = row_bucket(own)
    return bound if 2 * bucket > bound else bucket


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
        self._rows: Counter = Counter()  # {rows: segments}, the empty ones left out
        self._rows_counted: Dict[str, int] = {}  # segment name -> its rows
        self._rows_bound = 0
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

    def _recount_rows(self, name: str, rows: int) -> None:
        """Segment `name` now holds `rows` rows (0: it left, or is empty)."""
        was = self._rows_counted.pop(name, 0)
        if was:
            self._rows[was] -= 1
            if self._rows[was] <= 0:
                del self._rows[was]
        if rows:
            self._rows_counted[name] = rows
            self._rows[rows] += 1
        bound = _rows_bound(self._rows)
        if bound != self._rows_bound:
            self._rows_bound = bound
            self.version += 1

    def add(self, segment) -> None:
        """Count `segment`'s dictionaries and rows; one of its name counted
        before (replaced in place) leaves first."""
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
            self._recount_rows(segment.name, int(segment.num_docs))

    def remove(self, name: str) -> None:
        with self._lock:
            self._rebound(self._uncount(name))
            self._recount_rows(name, 0)

    def rows(self, segment) -> int:
        """The rows a kernel over `segment` is compiled for and its resident
        columns are padded to (_padded_rows under the table's bound); its own
        count where the table does not know it, and for an empty segment."""
        own = int(segment.num_docs)
        with self._lock:
            known = self._rows_counted.get(segment.name) == own
            return _padded_rows(own, self._rows_bound) if known else own

    def row_buckets(self) -> int:
        """The distinct row counts this table's kernels are compiled for."""
        with self._lock:
            return len({_padded_rows(own, self._rows_bound) for own in self._rows})

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
