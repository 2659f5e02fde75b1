"""Immutable segment: the unit of storage, distribution and query.

Reference parity: pinot-segment-spi IndexSegment/ImmutableSegment and
pinot-segment-local ImmutableSegmentImpl + ImmutableSegmentLoader.load
(ImmutableSegmentLoader.java:91) — a named, immutable, columnar slice of a
table with per-column metadata, dictionaries, forward storage and optional
extra indexes.

TPU re-design (SURVEY.md section 7 "Segment = pytree of device arrays"):
  * Host side: zero-copy mmaps over columns.bin (store.py).
  * Device side: `to_device()` pins a plain-dict pytree of jnp arrays in HBM —
    {col: {"codes": u8/u16/u32[n]} | {"values": dtype[n]}, plus "dict" for
    numeric dictionaries and "nulls" for null masks}; a dictionary column a
    plan reads by value through a row-priced gather is handed out DECODED as
    well (`value_columns`: its "values", made once).  Static facts
    (num_docs, cardinalities, stats) stay host-side for pruning and for
    building closed-form predicate constants, so jitted kernels see only
    dense arrays and static shapes.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from pinot_tpu.segment import packing, store
from pinot_tpu.segment.dictionary import Dictionary, min_code_dtype
from pinot_tpu.segment.stats import ColumnStats
from pinot_tpu.spi.schema import DataType, Schema

# Bumped when build-time encoding changes shape (v2: bit-packed forward
# indexes; v3: their lanes block-planar, stamped per column by
# packing.LAYOUT_KEY).  Segments carry it in meta; absent/v1 segments have no
# `codeBits` column attribute and load through the raw path unchanged; a v2
# segment's interleaved lane words are re-packed once at load.
BUILDER_VERSION = 3


@dataclass
class ColumnData:
    """One column inside a segment (DataSource analog: forward index +
    dictionary + null vector handles)."""

    name: str
    data_type: DataType
    dictionary: Optional[Dictionary]  # None => raw storage
    codes: Optional[np.ndarray]  # uint8/16/32[num_docs] (SV) or [num_docs, max_len] (MV)
    values: Optional[np.ndarray]  # raw storage (numeric) when no dictionary
    nulls: Optional[np.ndarray]  # bool[num_docs] true=null, None if no nulls
    stats: ColumnStats
    # multi-value columns: per-row element counts; codes beyond a row's
    # length hold the padding code (== cardinality)
    mv_lengths: Optional[np.ndarray] = None
    # bit-packed forward index (segment/packing.py): `packed` holds codes in
    # `code_bits`-wide lanes inside uint32 words.  `codes` stays materialized
    # host-side (index builds, sorted searchsorted, decode); `packed` is what
    # save() persists and to_device(packed_codes=True) ships.  None on raw,
    # MV, and wide (>16-bit) columns.
    code_bits: Optional[int] = None
    packed: Optional[np.ndarray] = None
    # a SORTED dictionary column's dictId -> first doc, cardinality + 1
    # entries, the last one num_docs (upstream's SortedIndexReader): the
    # docs of codes [lo, hi) are [first_docs[lo], first_docs[hi]).  Made by
    # the builder; a loaded segment makes it at first use (first_docs()).
    sorted_first_docs: Optional[np.ndarray] = None

    def first_docs(self) -> np.ndarray:
        """The sorted column's dictId -> first-doc table (see the field)."""
        if self.sorted_first_docs is None:
            assert self.stats.is_sorted and self.codes is not None and self.codes.ndim == 1, self.name
            self.sorted_first_docs = np.searchsorted(
                self.codes, np.arange(self.cardinality + 1), side="left"
            ).astype(np.int32)
        return self.sorted_first_docs

    @property
    def has_dictionary(self) -> bool:
        return self.dictionary is not None

    @property
    def is_multi_value(self) -> bool:
        return self.mv_lengths is not None

    @property
    def cardinality(self) -> int:
        return self.dictionary.cardinality if self.dictionary else self.stats.cardinality

    def value_at(self, doc: int):
        """Point read of one value (upsert merge reads) — O(1), no full
        column materialization."""
        if self.mv_lengths is not None:
            ln = int(self.mv_lengths[doc])
            if self.dictionary is not None:
                return tuple(self.dictionary.get_values(self.codes[doc, :ln]))
            return tuple(self.values[doc, :ln].tolist())
        if self.nulls is not None and self.nulls[doc]:
            return None
        if self.dictionary is not None:
            v = self.dictionary.get_values(np.asarray([self.codes[doc]]))[0]
        else:
            v = self.values[doc]
        return v.item() if isinstance(v, np.generic) else v

    def decoded(self) -> np.ndarray:
        """Materialize raw values host-side (tests/golden comparisons).
        MV columns decode to an object array of tuples."""
        if self.mv_lengths is not None:
            out = np.empty(len(self.mv_lengths), dtype=object)
            for i, ln in enumerate(self.mv_lengths):
                if self.dictionary is not None:
                    out[i] = tuple(self.dictionary.get_values(self.codes[i, :ln]))
                else:
                    out[i] = tuple(self.values[i, :ln].tolist())
            return out
        if self.dictionary is not None:
            return self.dictionary.get_values(self.codes)
        return self.values


# the device cache's key that says what rows its entries are padded to
# (absent: the segment's own count); no column's entry is keyed so
_ROWS_TAG = "#rows"


def _pad_rows(arr: np.ndarray, rows: int) -> np.ndarray:
    """`arr` (row-length along its first axis) with `rows` rows: the last
    row repeated, so that whatever a kernel derives from a padded row it
    derives from a real one's values (a raw group key stays in its range);
    zeros for a matrix (a multi-value column's padding codes ride with a
    length of 0; a vector of zeros has no similarity) and where there is no
    row to repeat.  Every padded row is masked out of every filter
    (planner.ROWS_KEY); a null or a length pads to none."""
    more = rows - arr.shape[0]
    if more <= 0:
        return arr
    if arr.ndim == 1 and arr.shape[0] and arr.dtype != bool and arr.dtype.kind in "iuf":
        fill = np.full(more, arr[-1], arr.dtype)
    else:
        fill = np.zeros((more,) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, fill])


class ImmutableSegment:
    """Loaded immutable segment with optional device residency."""

    # the true row count of a star-tree level's table (indexes/startree.py
    # LevelSegment), whose rows are padded to a bucket; None: every row counts
    level_rows: Optional[int] = None
    # the rows that count, of a table whose `num_docs` is what its kernel is
    # compiled for and its resident columns are padded to: a star-tree level's
    # table (= level_rows) and a segment's view at its table's rows (padded_to);
    # None: every row of `num_docs` counts.  Every plan over such a table binds
    # it as a parameter and masks by it (planner.ROWS_KEY)
    true_rows: Optional[int] = None

    def __init__(
        self,
        name: str,
        table_name: str,
        schema: Schema,
        columns: Dict[str, ColumnData],
        num_docs: int,
        indexes: Optional[Dict[str, Dict[str, Any]]] = None,
        creation_time_ms: int = 0,
        time_range: Optional[tuple] = None,
    ):
        self.name = name
        self.table_name = table_name
        self.schema = schema
        self.columns = columns
        self.num_docs = num_docs
        # indexes[kind][column] -> index object (indexes/ package), e.g.
        # indexes["inverted"]["color"] -> BitmapInvertedIndex
        self.indexes: Dict[str, Dict[str, Any]] = indexes or {}
        self.creation_time_ms = creation_time_ms
        self.time_range = time_range  # (min, max) of the table's time column
        # upsert hooks: validDocIds bitmask (bool[num_docs], False = replaced
        # by a newer row elsewhere) and the build-time sort permutation
        # (new position -> input row) used to remap it at seal time
        self.valid_docs: Optional[np.ndarray] = None
        self.sort_order: Optional[np.ndarray] = None
        self._device_cache: Dict[str, Any] = {}
        # device -> the TableShape of the table this segment is served from
        # there (ServerInstance.add_segment): the rows its resident columns
        # are padded to where no caller says (to_device `rows`)
        self.table_shapes: Dict[Any, Any] = {}
        self._padded_views: Dict[int, "ImmutableSegment"] = {}
        # the segment's half of its plan-cache keys (query/planner.py
        # _SegmentMemo): column shapes, signatures and group dimensions do
        # not change between queries; thrown away when valid_docs or an
        # index appears
        self._plan_memo = None
        # guards _device_cache reads/publishes under tiered residency
        # (segment/residency.py); NEVER held across a device copy — owners
        # stage with no lock held, then publish in one critical section so a
        # query racing an eviction re-checks instead of mixing tiers
        self._device_lock = threading.Lock()
        # durable home of this segment on local disk (set by save/load):
        # the deep store uploads from here without a redundant re-serialize
        self.source_dir: Optional[str] = None

    # ------------------------------------------------------------------
    def column(self, name: str) -> ColumnData:
        try:
            return self.columns[name]
        except KeyError:
            raise KeyError(f"segment {self.name} has no column {name!r}") from None

    def ensure_columns(self, table_schema, names) -> None:
        """Schema evolution: synthesize virtual columns for fields the TABLE
        schema has but this (older) segment lacks.  Old rows read as SQL
        NULL (null mask all-set over the type placeholder) — a documented
        delta from Pinot's defaultColumnHandler, whose legacy semantics
        return the default VALUE; with this engine's SQL-standard null
        handling, placeholder values leaking into SUM/MIN would corrupt
        aggregates (review-caught)."""
        from pinot_tpu.segment.dictionary import Dictionary
        from pinot_tpu.segment.stats import collect_stats

        for name in names:
            if name in self.columns or name not in table_schema:
                continue
            f = table_schema.field(name)
            if not f.single_value:
                raise NotImplementedError(f"virtual default for MV column {name} is unsupported")
            default = f.data_type.null_placeholder
            n = self.num_docs
            nulls = np.ones(n, dtype=bool)
            if f.data_type.is_string_like:
                dictionary, _ = Dictionary.build(f.data_type, np.asarray([default], dtype=object))
                codes = np.zeros(n, dtype=np.uint8)
                stats = collect_stats(name, f.data_type, np.asarray([default], dtype=object), None, 1, True)
                stats.num_docs = n
                self.columns[name] = ColumnData(name, f.data_type, dictionary, codes, None, nulls, stats)
            else:
                arr = np.broadcast_to(f.data_type.np_dtype.type(default), (n,))
                stats = collect_stats(name, f.data_type, np.asarray([default]), None, 1, False)
                stats.num_docs = n
                self.columns[name] = ColumnData(name, f.data_type, None, None, arr, nulls, stats)

    @property
    def column_names(self) -> List[str]:
        return list(self.columns)

    def padded_to(self, rows: int) -> "ImmutableSegment":
        """This segment as the table a kernel compiled for `rows` rows reads:
        itself where `rows` is its own count, else a view of it (the same
        columns, indexes and caches) whose `num_docs` is `rows` and whose
        `true_rows` is this segment's count, as a star-tree level's table
        states its bucket and its true rows.  What is traced against the view
        takes every shape from `rows`; the host arrays keep their true length
        and staging pads them (to_device `rows`)."""
        if rows == self.num_docs:
            return self
        view = self._padded_views.get(rows)
        if view is None:
            import copy

            view = copy.copy(self)
            view.num_docs, view.true_rows = rows, self.num_docs
            if len(self._padded_views) >= 4:  # the table's bound moved: the old views go
                self._padded_views.clear()
            self._padded_views[rows] = view
        return view

    def staged_rows(self, device=None) -> int:
        """The rows this segment's resident columns hold on `device` where no
        plan says: its table's there (segment/table_shape.py), else its own."""
        shape = self.table_shapes.get(device)
        return self.num_docs if shape is None else shape.rows(self)

    def star_tables(self, made_only: bool = False) -> List["ImmutableSegment"]:
        """Every level of every star-tree of this segment, as the tables the
        plans read (indexes/startree.py LevelSegment); with `made_only`,
        those a plan or a staging has already asked for."""
        tables = [
            level.made if made_only else level.table(self, name)
            for name, tree in self.indexes.get("startree", {}).items()
            for level in tree.levels.values()
        ]
        return [t for t in tables if t is not None]

    # -- device residency ----------------------------------------------
    def device_group(self, device=None):
        """Residency cache-group key: ALL flavors (raw, #packed and #values)
        of this segment on one device live and die as a unit."""
        return ("seg", id(self), device)

    @staticmethod
    def _entry_bytes(c: ColumnData, use_packed: bool, decoded: bool = False, rows: Optional[int] = None) -> int:
        """Host-side estimate of the device bytes one cache entry pins;
        `decoded`: the #values entry of a dictionary column (_stage_entry);
        `rows`: what the row-length arrays are padded to (None: as they are)."""

        def padded(arr) -> int:
            return arr.nbytes if rows is None or not arr.shape[0] else arr.nbytes // arr.shape[0] * rows

        if decoded:
            return (c.codes.shape[0] if rows is None else rows) * c.dictionary.device_values().dtype.itemsize
        n = 0
        if use_packed:
            n += c.packed.nbytes if rows is None else 4 * packing.packed_words(rows, c.code_bits)
        elif c.codes is not None:
            n += padded(c.codes)
        if c.codes is not None and c.dictionary is not None:
            dvals = c.dictionary.device_values()
            if dvals is not None:
                n += dvals.nbytes
        for arr in (c.values, c.nulls, c.mv_lengths):
            if arr is not None:
                n += padded(arr)
        return n

    def _plan_missing(self, device, cols, packed_codes, value_columns=None, rows: Optional[int] = None):
        """(missing [(cname, key, use_packed, decoded)], bytes) the cache
        lacks.  `value_columns` (to_device): a column of it has a #values
        entry beside its code entry.  `rows` (to_device): what the entries'
        rows are padded to (None: the segment's own count, no pad); None
        comes back where the cache holds entries padded to ANOTHER count
        (the table's bound moved: to_device drops them, _drop_stale)."""
        need = []
        nbytes = 0
        pad = None if rows is None or rows == self.num_docs else rows
        with self._device_lock:
            cache = self._device_cache.get(device, {})
            if rows is not None and cache.get(_ROWS_TAG, self.num_docs) != rows and cache:
                return None
            for cname in cols:
                c = self.columns[cname]
                if value_columns and cname in value_columns:
                    key = f"{cname}#values"
                    if key not in cache:
                        need.append((cname, key, False, True))
                        nbytes += self._entry_bytes(c, False, decoded=True, rows=pad)
                use_packed = bool(packed_codes and c.packed is not None)
                key = f"{cname}#packed" if use_packed else cname
                if key in cache:
                    continue
                need.append((cname, key, use_packed, False))
                nbytes += self._entry_bytes(c, use_packed, rows=pad)
        return need, nbytes

    def resident(self, device, columns: List[str], packed_codes: bool = False, value_columns=None) -> bool:
        """Whether every entry to_device would hand out for `columns` is in
        the device cache now, at the rows its table states: there is nothing
        to stage ahead of need."""
        missing = self._plan_missing(device, columns, packed_codes, value_columns, self.staged_rows(device))
        return missing is not None and not missing[0]

    def _drop_stale(self, device, residency=None) -> None:
        """Drop what the device cache holds at another row count than a
        launch asks for (the table's bound moved: a segment of more rows
        joined it): every flavor together, uncharged, to be staged again."""
        if residency is not None:
            residency.evict(self.device_group(device))
        self.evict_device(device)

    def _publish(self, device, staged: Dict[str, Any], rows: int, first_wins: bool) -> None:
        """`staged` entries into the device cache, which says what rows they
        are padded to where that is not the segment's own count."""
        with self._device_lock:
            cache = self._device_cache.setdefault(device, {})
            if rows != self.num_docs:
                cache[_ROWS_TAG] = rows
            if first_wins:
                for key, entry in staged.items():
                    cache.setdefault(key, entry)
            else:
                cache.update(staged)

    def _stage_entry(
        self, c: ColumnData, use_packed: bool, device, decoded: bool = False, rows: Optional[int] = None
    ) -> Dict[str, Any]:
        """One column's host->device copy (NO locks held — this runs on the
        staging stream or a staging owner, never under _device_lock).
        `decoded`: the #values entry of a single-value dictionary column, its
        values at its rows' codes (a take on the host, once a stage): what a
        kernel reads where it would have gathered.  `rows`: every row-length
        array is padded to that many rows (_pad_rows; packed words to whole
        blocks of zeros), the kernel's compiled count; None: as they are."""
        import jax

        def put(arr):
            arr = np.asarray(arr)
            return jax.device_put(arr if rows is None else _pad_rows(arr, rows), device)

        entry: Dict[str, Any] = {}
        if decoded:
            entry["values"] = put(c.dictionary.device_values()[np.asarray(c.codes)])
            return entry
        if use_packed:
            words = np.asarray(c.packed)
            if rows is not None:
                more = packing.packed_words(rows, c.code_bits) - words.shape[-1]
                words = np.concatenate([words, np.zeros(words.shape[:-1] + (more,), words.dtype)], axis=-1)
            entry["codes_packed"] = jax.device_put(words, device)
        elif c.codes is not None:
            entry["codes"] = put(c.codes)
        if c.codes is not None:
            dvals = c.dictionary.device_values() if c.dictionary else None
            if dvals is not None:
                entry["dict"] = jax.device_put(dvals, device)
        if c.values is not None:
            entry["values"] = put(c.values)
        if c.nulls is not None:
            entry["nulls"] = put(c.nulls)
        if c.mv_lengths is not None:
            entry["lengths"] = put(c.mv_lengths)
        return entry

    def _assemble(self, device, cols, packed_codes, value_columns=None, rows: Optional[int] = None) -> Optional[Dict[str, Any]]:
        """Read the pytree out of the cache in ONE critical section; None if
        any needed entry vanished (a racing eviction) or the cache holds
        another row count than `rows` — the caller re-stages
        the whole group, so it can never observe a half-evicted segment.  A
        column of `value_columns` comes out as its code entry joined with
        its #values entry."""
        with self._device_lock:
            cache = self._device_cache.get(device, {})
            if rows is not None and cache.get(_ROWS_TAG, self.num_docs) != rows:
                return None
            out: Dict[str, Any] = {}
            for cname in cols:
                c = self.columns[cname]
                decoded = None
                if value_columns and cname in value_columns:
                    decoded = cache.get(f"{cname}#values")
                    if decoded is None:
                        return None
                use_packed = bool(packed_codes and c.packed is not None)
                key = f"{cname}#packed" if use_packed else cname
                if key not in cache:
                    return None
                out[cname] = cache[key] if decoded is None else {**cache[key], **decoded}
            return out

    def evict_device(self, device=None) -> None:
        """Atomic flavor invalidation: the entire per-device cache region —
        raw, #packed, #values, dict, null entries together — drops in one critical
        section (residency eviction callback; satellite fix r17)."""
        with self._device_lock:
            self._device_cache.pop(device, None)

    def to_device(
        self,
        device=None,
        columns: Optional[List[str]] = None,
        packed_codes: bool = False,
        residency=None,
        prefetch: bool = False,
        query_id: Optional[str] = None,
        dict_rows: Optional[Dict[str, int]] = None,
        value_columns=None,
        rows: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Pin column arrays into device memory; returns the segment pytree.

        `rows` (a plan's `rows`: what its kernel was compiled for): every
        row-length array is handed out padded to that many rows (a packed
        column's words to whole blocks); None: what the segment's table on
        `device` states (staged_rows: the table's bound where its segments
        hold unequal rows, segment/table_shape.py), else its own count, no
        pad.  The padding is made on the host once a stage; the device cache
        holds ONE row count, and entries at another are dropped and staged
        again (the table's bound moved).

        `value_columns` (a set of columns, a plan's `value_columns`): each
        named single-value dictionary column is handed out DECODED as well,
        "values" as a raw column's beside its codes and dictionary, from a
        cache entry of its own (`<col>#values`, made once a segment and
        device, charged and evicted like any flavor).

        `dict_rows` ({column: rows}, a plan's `dict_sizes`): the device
        dictionary ("dict") of each named column is handed out with that
        many rows, the segment's own values at its head (_with_dict_rows).

        The pytree is cached — segments are immutable so repeated queries hit
        HBM-resident arrays.  With `residency` (segment/residency.py) HBM is
        a byte-budgeted CACHE over the host arrays: staging charges the
        residency budget (evicting cost-ranked victims to make room), at most
        one thread copies while the rest park on the group's event, and a
        mid-stage failure unwinds the charge (crash-harness covered).
        `prefetch=True` marks the stage as issued ahead of need for the
        prefetch-hit accounting.  Without `residency` this is the legacy
        pin-everything path.

        packed_codes=True ships bit-packed columns as uint32 lane words under
        entry key "codes_packed" instead of widened "codes" — opt-in because
        only plan kernels that unpack at trace time (or route the words to
        the Pallas lane-unpack) can consume it; direct `cols[n]["codes"]`
        readers keep the default.  Packed entries cache under a distinct
        key so the two shapes never alias.

        `columns=None` is the whole segment: every column, and every level
        of its star-trees (each a table and a residency group of its own,
        under key "*startree" -> its name); an empty list is no column."""
        if dict_rows:
            out = self.to_device(
                device, columns, packed_codes, residency, prefetch, query_id, value_columns=value_columns, rows=rows
            )
            return self._with_dict_rows(device, out, dict_rows, packed_codes)
        cols = list(self.columns) if columns is None else columns
        if columns is None and self.indexes.get("startree"):
            out = self.to_device(device, cols, packed_codes, residency, prefetch, query_id, rows=rows)
            out["*startree"] = {
                t.name: t.to_device(device, None, packed_codes, residency, prefetch, query_id)
                for t in self.star_tables()
            }
            return out
        if rows is None:
            rows = self.staged_rows(device)
        pad = None if rows == self.num_docs else rows
        if residency is None:
            # legacy pin-everything path: no budget, no eviction — but the
            # copy still happens with no lock held, and the publish races
            # resolve first-wins through setdefault
            while True:
                missing = self._plan_missing(device, cols, packed_codes, value_columns, rows)
                if missing is None:
                    self._drop_stale(device)
                    continue
                staged = {
                    key: self._stage_entry(self.columns[cname], up, device, decoded, pad)
                    for cname, key, up, decoded in missing[0]
                }
                self._publish(device, staged, rows, first_wins=True)
                out = self._assemble(device, cols, packed_codes, value_columns, rows)
                if out is not None:  # None: released between publish and read
                    return out

        from pinot_tpu.segment import residency as res_mod
        from pinot_tpu.utils.crashpoints import crash_point

        group = self.device_group(device)
        while True:
            missing = self._plan_missing(device, cols, packed_codes, value_columns, rows)
            if missing is None:
                self._drop_stale(device, residency)
                continue
            missing = missing[0]
            st, entry = residency.begin_stage(
                group, self.table_name, lambda: self.evict_device(device), prefetch=prefetch
            )
            if st == res_mod.WAIT:
                residency.wait(entry)
                continue
            if st == res_mod.HIT:
                if not missing:
                    out = self._assemble(device, cols, packed_codes, value_columns, rows)
                    if out is not None:
                        return out
                    continue  # evicted between plan and read: re-stage
                # resident but lacking columns/flavors this query needs:
                # claim the group for incremental staging
                st2, entry2 = residency.begin_grow(group)
                if st2 == res_mod.WAIT:
                    residency.wait(entry2)
                    continue
                if st2 == res_mod.RETRY:
                    continue
            # OWN: charge, copy (no locks held), publish, commit
            try:
                again = self._plan_missing(device, cols, packed_codes, value_columns, rows)
                if again is None:  # entries at another row count were published meanwhile: start over
                    residency.abort_stage(group)
                    continue
                missing, nbytes = again
                residency.charge(group, nbytes, query_id=query_id)
                crash_point("segment.stage.after_charge")
                staged = {
                    key: self._stage_entry(self.columns[cname], up, device, decoded, pad)
                    for cname, key, up, decoded in missing
                }
                crash_point("segment.stage.after_copy")
                self._publish(device, staged, rows, first_wins=False)
            except BaseException:
                residency.abort_stage(group)
                raise
            residency.finish_stage(group)
            out = self._assemble(device, cols, packed_codes, value_columns, rows)
            if out is not None:
                return out

    def _with_dict_rows(self, device, out: Dict[str, Any], dict_rows: Dict[str, int], packed_codes: bool):
        """`out` (to_device's pytree) with every column of `dict_rows` whose
        device dictionary has another number of rows handed a dictionary of
        that many: the kernel a plan compiled for the TABLE's shape
        (segment/table_shape.py) takes a dictionary array of the table's
        bound, whatever this segment's holds.  The tail repeats the last
        value; no code reaches it.  The new array takes the old one's place
        in the device cache, so it is made once a change of the bound (a
        segment added to or dropped from the table); its few KB are not
        charged to a residency budget."""
        import jax

        for cname, rows in dict_rows.items():
            entry = out.get(cname)
            have = None if entry is None else entry.get("dict")
            if have is None or have.shape[0] == rows:
                continue
            c = self.columns[cname]
            dvals = c.dictionary.device_values()
            if rows > len(dvals):
                last = dvals[-1] if len(dvals) else 0
                dvals = np.concatenate([dvals, np.full(rows - len(dvals), last, dvals.dtype)])
            padded = jax.device_put(dvals[:rows], device)
            key = f"{cname}#packed" if packed_codes and c.packed is not None else cname
            with self._device_lock:
                cache = self._device_cache.get(device)
                if cache is not None and key in cache:
                    # the cache's own entry: `entry` may be it joined with the column's #values
                    cache[key] = dict(cache[key], dict=padded)
            out[cname] = dict(entry, dict=padded)
        return out

    def release_device(self) -> None:
        with self._device_lock:
            self._device_cache.clear()

    # -- persistence ----------------------------------------------------
    def save(self, path: str) -> None:
        regions = []
        col_meta = []
        for c in self.columns.values():
            if c.dictionary is not None:
                regions.extend(c.dictionary.to_regions(c.name))
                # packed columns persist the lane words (block-planar, the
                # layout stamped below); codes are rematerialized at load
                # via packing.unpack_codes
                regions.append((f"{c.name}.fwd", c.packed if c.packed is not None else c.codes))
            else:
                regions.append((f"{c.name}.fwd", c.values))
            if c.nulls is not None:
                regions.append((f"{c.name}.nulls", np.packbits(c.nulls)))
            if c.mv_lengths is not None:
                regions.append((f"{c.name}.mvlen", c.mv_lengths))
            cm = {
                "stats": c.stats.to_dict(),
                "hasNulls": c.nulls is not None,
                "isMV": c.mv_lengths is not None,
            }
            if c.packed is not None:
                cm["codeBits"] = int(c.code_bits)
                cm[packing.LAYOUT_KEY] = packing.BLOCK_ROWS
            col_meta.append(cm)
        for kind, by_col in self.indexes.items():
            for cname, idx in by_col.items():
                regions.extend(idx.to_regions(f"{cname}.{kind}"))
        meta = {
            "segmentName": self.name,
            "tableName": self.table_name,
            "numDocs": self.num_docs,
            "builderVersion": BUILDER_VERSION,
            "schema": self.schema.to_dict(),
            "columns": col_meta,
            "indexes": {kind: {c: idx.meta() for c, idx in by_col.items()} for kind, by_col in self.indexes.items()},
            "creationTimeMs": self.creation_time_ms,
            "timeRange": [v.item() if isinstance(v, np.generic) else v for v in self.time_range]
            if self.time_range
            else None,
        }
        store.write_segment(path, meta, regions)
        self.source_dir = path

    @staticmethod
    def load(path: str, verify: bool = False) -> "ImmutableSegment":
        """mmap-load (ImmutableSegmentLoader.load analog — ReadMode.mmap).

        verify=True checks columns.bin against the committed size + CRC32
        first (SegmentCorruptError on mismatch) — the deep-store download
        and server restart-recovery paths load verified."""
        from pinot_tpu.indexes import load_index  # local import; avoids cycle

        meta, regions = store.read_segment(path, verify=verify)
        schema = Schema.from_dict(meta["schema"])
        num_docs = meta["numDocs"]
        columns: Dict[str, ColumnData] = {}
        for cm in meta["columns"]:
            stats = ColumnStats.from_dict(cm["stats"])
            name = stats.name
            dt = stats.data_type
            nulls = None
            if cm.get("hasNulls"):
                nulls = np.unpackbits(np.asarray(regions[f"{name}.nulls"]), count=num_docs).astype(bool)
            if stats.has_dictionary:
                dictionary = Dictionary.from_regions(dt, regions, name)
                fwd = regions[f"{name}.fwd"]
                mv_lengths = regions[f"{name}.mvlen"] if cm.get("isMV") else None
                bits = cm.get("codeBits")  # absent on pre-v2 segments: raw path
                packed = None
                codes = fwd
                if bits and bits < 32:
                    code_dtype = min_code_dtype(dictionary.cardinality)
                    block_rows = cm.get(packing.LAYOUT_KEY)
                    if block_rows is None:
                        # written before the layout stamp: interleaved lanes,
                        # re-packed once here so every reader sees one layout
                        codes = packing.unpack_interleaved(fwd, bits, num_docs, dtype=code_dtype)
                        packed = packing.pack_codes(codes, bits)
                    elif block_rows != packing.BLOCK_ROWS:
                        raise ValueError(
                            f"segment {path!r} column {name!r}: lane blocks of {block_rows} "
                            f"rows, this build reads {packing.BLOCK_ROWS}"
                        )
                    else:
                        packed = np.asarray(fwd)
                        codes = packing.unpack_codes(packed, bits, num_docs, dtype=code_dtype)
                columns[name] = ColumnData(
                    name, dt, dictionary, codes, None, nulls, stats,
                    mv_lengths=mv_lengths, code_bits=bits, packed=packed,
                )
            else:
                mv_lengths = regions[f"{name}.mvlen"] if cm.get("isMV") else None
                columns[name] = ColumnData(
                    name, dt, None, None, regions[f"{name}.fwd"], nulls, stats, mv_lengths=mv_lengths
                )
        indexes: Dict[str, Dict[str, Any]] = {}
        for kind, by_col in meta.get("indexes", {}).items():
            for cname, idx_meta in by_col.items():
                idx = load_index(kind, idx_meta, regions, f"{cname}.{kind}")
                indexes.setdefault(kind, {})[cname] = idx
        # text indexes evaluate phrase queries over the ORIGINAL values —
        # rehydrate them from the column dictionary (not persisted twice)
        for cname, idx in indexes.get("text", {}).items():
            if cname in columns and columns[cname].dictionary is not None:
                idx.values = columns[cname].dictionary.values
        seg = ImmutableSegment(
            name=meta["segmentName"],
            table_name=meta["tableName"],
            schema=schema,
            columns=columns,
            num_docs=num_docs,
            indexes=indexes,
            creation_time_ms=meta.get("creationTimeMs", 0),
            time_range=tuple(meta["timeRange"]) if meta.get("timeRange") else None,
        )
        seg.source_dir = path
        return seg
