"""Stacked (sharded) table: N segments as one leading-axis device array set.

Reference parity: Pinot's intra-server segment parallelism + scatter-gather
(BaseCombineOperator.java:202-218 runs numTasks worker threads over the
segment list; QueryRouter fans out one request per server).  SURVEY.md 2.5
maps both onto ONE TPU-native construct: segments stacked on a leading axis,
sharded over a jax.sharding.Mesh, with the per-segment combine becoming an
in-graph psum over ICI (SURVEY.md section 7 "Combine = collective").

The load-bearing alignment trick: all shards share ONE dictionary per column
(the key space is global), so per-shard dense group tables are element-wise
addable — the combine is literally `lax.psum`.  Pinot pays a keyed hash merge
(IndexedTable) for the same step because its per-segment dictionaries differ.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.segment import packing
from pinot_tpu.segment.dictionary import Dictionary, min_code_dtype
from pinot_tpu.segment.segment import ColumnData, ImmutableSegment
from pinot_tpu.segment.stats import ColumnStats
from pinot_tpu.spi.schema import DataType, FieldRole, Schema


@dataclass
class StackedColumn:
    """Host-side stacked column: row arrays are [num_shards, docs_per_shard]."""

    name: str
    data_type: DataType
    dictionary: Optional[Dictionary]  # GLOBAL dictionary (shared key space)
    codes: Optional[np.ndarray]  # [S, D] unsigned codes (MV: [S, D, max_len])
    values: Optional[np.ndarray]  # [S, D] raw numerics otherwise
    nulls: Optional[np.ndarray]  # [S, D] bool, None if no nulls
    stats: ColumnStats
    # multi-value: [S, D] per-row element counts; padded cells hold the
    # padding code (== cardinality), mirroring segment/builder MV layout
    mv_lengths: Optional[np.ndarray] = None
    # bit-packed forward index (segment/packing.py layout): codes in
    # `code_bits`-wide lanes inside uint32 words, [S, D * code_bits / 32].
    # Only where D is whole lane blocks, so no block straddles a shard
    # boundary.  None otherwise (such a table ships its codes unpacked),
    # when the cardinality needs >16 bits (stored unpacked) or the column
    # is MV.
    code_bits: Optional[int] = None
    packed: Optional[np.ndarray] = None

    @property
    def is_multi_value(self) -> bool:
        return self.mv_lengths is not None

    @property
    def has_dictionary(self) -> bool:
        return self.dictionary is not None

    @property
    def cardinality(self) -> int:
        return self.dictionary.cardinality if self.dictionary else self.stats.cardinality


def _stack_mv_column(f, raw, n: int, num_shards: int, D: int) -> "StackedColumn":
    """MV column -> [S, D, max_len] padded code matrix + [S, D] lengths
    (distributed twin of segment/builder._build_mv_column)."""
    from pinot_tpu.segment.builder import _build_mv_column

    col = _build_mv_column(f, np.asarray([tuple(v) if v is not None else () for v in raw], dtype=object), n)
    total = num_shards * D
    max_len = col.codes.shape[1]
    codes = np.full((total, max_len), col.dictionary.cardinality, dtype=col.codes.dtype)
    codes[:n] = col.codes
    lengths = np.zeros(total, dtype=np.int32)
    lengths[:n] = col.mv_lengths
    return StackedColumn(
        f.name,
        f.data_type,
        col.dictionary,
        codes.reshape(num_shards, D, max_len),
        None,
        None,
        col.stats,
        mv_lengths=lengths.reshape(num_shards, D),
    )


_BUILD_COUNTER = 0


class StackedTable:
    """A table resident as stacked columns, ready to shard over a device mesh.

    Padding: shards are padded to equal docs_per_shard; `valid[s, d]` marks
    real rows.  Every kernel ANDs `valid` into its filter mask, so padded rows
    are invisible — the static-shape answer to ragged segment sizes
    (SURVEY.md section 7 "Hard parts: dynamic shapes")."""

    def __init__(
        self,
        schema: Schema,
        columns: Dict[str, StackedColumn],
        valid: np.ndarray,  # [S, D] bool
        num_docs: int,
        indexes: Optional[Dict[str, Dict[str, Any]]] = None,
    ):
        self.schema = schema
        self.columns = columns
        self.valid = valid
        self.num_docs = num_docs
        self.num_shards, self.docs_per_shard = valid.shape
        # {"inverted"|"range": {column: index}} over the FLAT PADDED doc
        # space (num_shards * docs_per_shard rows) — docs_per_shard is
        # 32-aligned so per-device bitmap word slices stay word-aligned
        # (query/filter.py shard-aware params)
        self.indexes: Dict[str, Dict[str, Any]] = indexes or {}
        self._device_cache: Dict[Any, Any] = {}
        # guards _device_cache/_group_keys (shared by aliased_view facades);
        # NEVER held across a device copy — staging owners copy lock-free
        # and publish in one critical section
        self._device_lock = threading.Lock()
        # residency cache-group -> the cache keys it charged: one doc-slice
        # of the table is the eviction unit, and ALL its flavors (raw,
        # #packed, valid words, dictionaries it staged) drop together
        self._group_keys: Dict[Any, set] = {}
        # Per-instance nonce in signature(): compiled plans bake ROW-DATA
        # dependent params (sorted doc ranges, index bitmap words), which
        # dictionary fingerprints alone cannot distinguish — two tables with
        # identical shapes/dictionaries but different row content must never
        # share cached plans.
        global _BUILD_COUNTER
        _BUILD_COUNTER += 1
        self._build_nonce = _BUILD_COUNTER

    # -- facade used by FilterCompiler / planner at compile time ---------
    def column(self, name: str) -> StackedColumn:
        try:
            return self.columns[name]
        except KeyError:
            raise KeyError(f"stacked table has no column {name!r}") from None

    @property
    def column_names(self) -> List[str]:
        return list(self.columns)

    def signature(self) -> Tuple:
        """Kernel cache key component: shapes + dictionary fingerprints +
        stats-derived limb plans (baked into fused group-by kernels)."""
        from pinot_tpu.query.planner import column_limb_sig

        parts: List[Tuple] = [(self.num_shards, self.docs_per_shard, self._build_nonce)]
        for name, c in sorted(self.columns.items()):
            parts.append(
                (
                    name,
                    c.dictionary.fingerprint() if c.dictionary else None,
                    str((c.codes if c.codes is not None else c.values).dtype),
                    c.code_bits,  # packed vs unpacked trace different kernels
                    c.nulls is not None,
                    column_limb_sig(c),
                    c.stats.is_sorted,
                    tuple(sorted(k for k, by_col in self.indexes.items() if name in by_col)),
                )
            )
        return tuple(parts)

    # ------------------------------------------------------------------
    @staticmethod
    def build(
        schema: Schema,
        data: Dict[str, np.ndarray],
        num_shards: int,
        no_dictionary_columns: Tuple[str, ...] = (),
        table_config=None,
    ) -> "StackedTable":
        """Build from column-major data, row-partitioned into num_shards.

        table_config.indexing drives index construction (inverted/range
        bitmaps over the flat padded doc space, data pre-sorted when
        sorted_column is declared) — the distributed counterpart of
        segment/builder.py's index creation (SegmentColumnarIndexCreator
        analog), so the shard_map filter kernels can ride bitmap/doc-range
        params instead of code scans."""
        from pinot_tpu.indexes.inverted import InvertedIndex, RangeEncodedIndex
        from pinot_tpu.segment.builder import MAX_BITMAP_INDEX_CARDINALITY, _extract_nulls
        from pinot_tpu.segment.stats import collect_stats

        idx_cfg = table_config.indexing if table_config is not None else None

        names = schema.column_names
        n = len(data[names[0]]) if names else 0
        # 32-align docs_per_shard: per-device row counts stay multiples of 32
        # so index bitmap words split cleanly across devices.  Dictionary
        # columns ship bit-packed only from shards of whole lane blocks, so
        # a shard is rounded up to them where that pads it by at most 1/16
        D = -(-n // num_shards)  # ceil
        D = -(-D // 32) * 32
        whole = -(-D // packing.BLOCK_ROWS) * packing.BLOCK_ROWS
        if whole - D <= D // 16:
            D = whole
        total = num_shards * D

        # sorted column: physically sort rows (the sorted "index" IS the
        # order, SortedIndexReader analog)
        if idx_cfg is not None and idx_cfg.sorted_column and idx_cfg.sorted_column in data and n > 1:
            order = np.argsort(np.asarray(data[idx_cfg.sorted_column]), kind="stable")
            if not np.array_equal(order, np.arange(n)):
                data = {k: np.asarray(v)[order] for k, v in data.items()}

        valid = np.zeros(total, dtype=bool)
        valid[:n] = True

        columns: Dict[str, StackedColumn] = {}
        indexes: Dict[str, Dict[str, Any]] = {}
        for f in schema.fields:
            if not f.single_value:
                columns[f.name] = _stack_mv_column(f, data[f.name], n, num_shards, D)
                continue
            arr, nmask = _extract_nulls(f, data[f.name])
            no_dict_cfg = tuple(idx_cfg.no_dictionary_columns) if idx_cfg is not None else ()
            use_dict = f.data_type.is_string_like or (
                f.name not in no_dictionary_columns
                and f.name not in no_dict_cfg
                and f.role in (FieldRole.DIMENSION, FieldRole.DATE_TIME)
            )
            padded_nulls = None
            if nmask is not None:
                padded_nulls = np.zeros(total, dtype=bool)
                padded_nulls[:n] = nmask
                padded_nulls = padded_nulls.reshape(num_shards, D)
            if use_dict:
                dictionary, codes32 = Dictionary.build(f.data_type, arr)
                codes = np.zeros(total, dtype=min_code_dtype(dictionary.cardinality))
                codes[:n] = codes32.astype(codes.dtype)
                stats = collect_stats(f.name, f.data_type, arr, nmask, dictionary.cardinality, True)
                bits = packing.lane_bits(dictionary.cardinality)
                # a shard of whole lane blocks packs on its own axis, and a
                # device's shards laid end to end are still whole blocks
                packed = (
                    packing.pack_codes(codes.reshape(num_shards, D), bits)
                    if bits < 32 and D % packing.BLOCK_ROWS == 0
                    else None
                )
                columns[f.name] = StackedColumn(
                    f.name,
                    f.data_type,
                    dictionary,
                    codes.reshape(num_shards, D),
                    None,
                    padded_nulls,
                    stats,
                    code_bits=bits if packed is not None else None,
                    packed=packed,
                )
                card = dictionary.cardinality
                if idx_cfg is not None and card <= MAX_BITMAP_INDEX_CARDINALITY:
                    # padded rows carry code 0 and DO enter the bitmaps;
                    # every kernel ANDs the valid mask, so they stay invisible
                    if f.name in idx_cfg.inverted_index_columns:
                        indexes.setdefault("inverted", {})[f.name] = InvertedIndex.build(
                            codes.astype(np.int64), card, total
                        )
                    if f.name in idx_cfg.range_index_columns:
                        indexes.setdefault("range", {})[f.name] = RangeEncodedIndex.build(
                            codes.astype(np.int64), card, total
                        )
            else:
                from pinot_tpu.segment.builder import narrow_ints

                card = int(len(np.unique(arr)))
                stats = collect_stats(f.name, f.data_type, arr, nmask, card, False)
                arr = narrow_ints(arr, nmask)
                vals = np.zeros(total, dtype=arr.dtype)
                vals[:n] = arr
                columns[f.name] = StackedColumn(
                    f.name, f.data_type, None, None, vals.reshape(num_shards, D), padded_nulls, stats
                )
        return StackedTable(schema, columns, valid.reshape(num_shards, D), n, indexes=indexes)

    @staticmethod
    def from_segments(
        segments: List[ImmutableSegment],
        num_shards: Optional[int] = None,
        table_config=None,
    ) -> "StackedTable":
        """Re-align N immutable segments onto a shared key space.

        Dictionary union + code remap per segment (the price Pinot pays per
        query in IndexedTable merges is paid once here at load time), then
        stack with padding.  num_shards defaults to len(segments); if given,
        segments are concatenated then re-split (e.g. 40 segments -> 8 shards
        on a v5e-8)."""
        if not segments:
            raise ValueError("no segments")
        schema = segments[0].schema
        names = schema.column_names
        # Upsert segments COMPACT at stack time: rows masked out of
        # validDocIds (replaced by newer rows elsewhere) are dropped here, so
        # the distributed engine needs no per-row valid mask at query time —
        # the load-time analog of the reference's UpsertCompaction minion task.
        keeps = [
            np.nonzero(seg.valid_docs)[0] if seg.valid_docs is not None else None
            for seg in segments
        ]
        # Re-decode per segment and concatenate; dictionary union via rebuild.
        data: Dict[str, np.ndarray] = {}
        null_cols: Dict[str, Optional[np.ndarray]] = {}
        for name in names:
            parts = []
            nparts = []
            any_nulls = False
            for seg, keep in zip(segments, keeps):
                c = seg.column(name)
                vals = np.asarray(c.decoded())
                nm = np.asarray(c.nulls) if c.nulls is not None else np.zeros(seg.num_docs, dtype=bool)
                if keep is not None:
                    vals = vals[keep]
                    nm = nm[keep]
                parts.append(vals)
                if c.nulls is not None:
                    any_nulls = True
                nparts.append(nm)
            data[name] = np.concatenate(parts)
            null_cols[name] = np.concatenate(nparts) if any_nulls else None
        S = num_shards or len(segments)
        # respect nullability via object arrays where needed — on a COPY of
        # the schema (mutating the caller's shared schema was round-2 weak #4)
        if any(null_cols[n] is not None and not schema.field(n).nullable for n in names):
            import dataclasses

            schema = Schema(
                name=schema.name,
                fields=[
                    dataclasses.replace(
                        f, nullable=f.nullable or null_cols[f.name] is not None
                    )
                    for f in schema.fields
                ],
                primary_key_columns=list(schema.primary_key_columns),
            )
        merged = {}
        for name in names:
            arr = data[name]
            if null_cols[name] is not None:
                arr = np.asarray(arr, dtype=object)
                arr[null_cols[name]] = None
            merged[name] = arr
        no_dict = tuple(
            f.name for f in schema.fields if not segments[0].column(f.name).has_dictionary
        )
        return StackedTable.build(
            schema, merged, S, no_dictionary_columns=no_dict, table_config=table_config
        )

    # -- device residency ----------------------------------------------
    def _use_packed(self, c: StackedColumn, sl, packed_codes: bool) -> bool:
        # a doc slice ships packed only as whole lane blocks (the engine's
        # _batching cuts a packed table's batches there); any other slice
        # ships the unpacked codes
        return bool(
            packed_codes
            and c.packed is not None
            and sl[0] % packing.BLOCK_ROWS == 0
            and sl[1] % packing.BLOCK_ROWS == 0
        )

    @staticmethod
    def _col_key(c: StackedColumn, sl, use_packed: bool):
        # cache by BACKING-ARRAY identity, not name: self-join facades
        # (aliased_view) rename columns but share the numpy storage —
        # identity keys mean one HBM copy serves every alias
        arr_id = id(c.codes if c.codes is not None else c.values)
        return (arr_id, sl, "#packed") if use_packed else (arr_id, sl)

    def device_group(self, mesh, sl) -> Tuple:
        """Residency cache-group key: ONE doc-slice of this table on one
        mesh.  Slices evict independently (a 4x-budget working set must be
        able to rotate through the cache), but all flavors of a slice drop
        as a unit."""
        return ("stacked", id(self), id(mesh), sl)

    def _plan_missing(self, mesh, cols, sl, packed_codes, with_valid):
        """(missing column specs, valid missing?, bytes to charge)."""
        span = sl[1] - sl[0]
        need = []
        nbytes = 0
        need_valid = False
        with self._device_lock:
            cache = self._device_cache.get(id(mesh), {})
            for cname in cols:
                c = self.columns[cname]
                use_packed = self._use_packed(c, sl, packed_codes)
                ck = self._col_key(c, sl, use_packed)
                if ck in cache:
                    continue
                dkey = cached_dict = None
                if c.codes is not None and c.dictionary is not None:
                    dvals = c.dictionary.device_values()
                    if dvals is not None:
                        dkey = (id(c.dictionary), "dict")
                        cached_dict = cache.get(dkey)
                        if cached_dict is None:
                            nbytes += dvals.nbytes
                        else:
                            dkey = None  # already staged (and charged) once
                if use_packed:
                    f = 32 // c.code_bits
                    nbytes += c.packed[:, sl[0] // f : sl[1] // f].nbytes
                elif c.codes is not None:
                    nbytes += c.codes[:, sl[0] : sl[1]].nbytes
                for arr in (c.values, c.nulls, c.mv_lengths):
                    if arr is not None:
                        nbytes += arr.itemsize * arr.shape[0] * span * (
                            int(np.prod(arr.shape[2:])) if arr.ndim > 2 else 1
                        )
                need.append((cname, ck, use_packed, dkey, cached_dict))
            if with_valid:
                vk = (id(self.valid), sl)
                if vk not in cache:
                    need_valid = True
                    nbytes += self.valid[:, sl[0] : sl[1]].nbytes
        return need, need_valid, nbytes

    def _stage_slice(self, need, need_valid, sl, row_sharding, rep_sharding):
        """Host->device copies for one slice's missing entries (NO locks
        held — this is the staging-stream body)."""
        import jax

        def _rows(a: np.ndarray) -> np.ndarray:
            if sl == (0, self.docs_per_shard):
                return a
            return np.ascontiguousarray(a[:, sl[0] : sl[1]])

        staged: Dict[Any, Any] = {}
        for cname, ck, use_packed, dkey, cached_dict in need:
            c = self.columns[cname]
            entry: Dict[str, Any] = {}
            if use_packed:
                f = 32 // c.code_bits
                w = c.packed[:, sl[0] // f : sl[1] // f]
                entry["codes_packed"] = jax.device_put(
                    np.ascontiguousarray(w), row_sharding
                )
            if c.codes is not None:
                if not use_packed:
                    entry["codes"] = jax.device_put(_rows(c.codes), row_sharding)
                if dkey is not None:
                    dvals = c.dictionary.device_values()
                    dput = jax.device_put(dvals, rep_sharding)
                    staged[dkey] = dput
                    entry["dict"] = dput
                elif cached_dict is not None:
                    entry["dict"] = cached_dict
            if c.values is not None:
                entry["values"] = jax.device_put(_rows(c.values), row_sharding)
            if c.nulls is not None:
                entry["nulls"] = jax.device_put(_rows(c.nulls), row_sharding)
            if c.mv_lengths is not None:
                entry["lengths"] = jax.device_put(_rows(c.mv_lengths), row_sharding)
            staged[ck] = entry
        if need_valid:
            staged[(id(self.valid), sl)] = jax.device_put(_rows(self.valid), row_sharding)
        return staged

    def _publish(self, mesh, group, staged) -> None:
        """First-wins publish + group-key registration in ONE critical
        section, so eviction can drop exactly this group's flavors."""
        with self._device_lock:
            cache = self._device_cache.setdefault(id(mesh), {})
            for k, v in staged.items():
                cache.setdefault(k, v)
            self._group_keys.setdefault(group, set()).update(staged.keys())

    def _assemble(self, mesh, cols, sl, packed_codes, with_valid):
        """Read the slice pytree in ONE critical section; None if a racing
        eviction removed any needed entry — callers re-stage the whole
        group, never observing a half-evicted slice."""
        with self._device_lock:
            cache = self._device_cache.get(id(mesh), {})
            out: Dict[str, Dict[str, Any]] = {}
            for cname in cols:
                c = self.columns[cname]
                ck = self._col_key(c, sl, self._use_packed(c, sl, packed_codes))
                if ck not in cache:
                    return None
                out[cname] = cache[ck]
            if not with_valid:
                # distributed-engine path: validity is computed IN-KERNEL
                # from static num_docs (padding is always trailing in the
                # global flat doc space by construction) — at 1B rows the
                # [S, D] bool buffer plus its while-loop capture copy is
                # ~2GB of HBM for a mask the kernel derives from an iota
                # compare.
                return out, None
            vk = (id(self.valid), sl)
            if vk not in cache:
                return None
            return out, cache[vk]

    def evict_slice(self, mesh, sl) -> None:
        """Atomic flavor invalidation for one slice group: every cache key
        the group charged — raw, #packed, valid, dictionaries it staged —
        drops in one critical section (residency eviction callback)."""
        group = self.device_group(mesh, sl)
        with self._device_lock:
            keys = self._group_keys.pop(group, set())
            cache = self._device_cache.get(id(mesh), {})
            for k in keys:
                cache.pop(k, None)

    def to_device(
        self,
        mesh=None,
        axis="seg",
        columns: Optional[List[str]] = None,
        doc_slice: Optional[Tuple[int, int]] = None,
        with_valid: bool = True,
        packed_codes: bool = False,
        residency=None,
        prefetch: bool = False,
        query_id: Optional[str] = None,
    ):
        """Shard row arrays over the mesh axis; dictionaries replicate.

        Returns (cols_pytree, valid) of jax arrays with NamedSharding — the
        input side of the shard_map combine kernel (parallel/engine.py).

        doc_slice=(lo, hi) ships only columns [:, lo:hi] of the [S, D] row
        arrays — the macro-batch launch path (parallel/engine.py batching):
        at 1B rows a single launch's while-loop capture copy alone exceeds
        HBM, so the engine slices the doc axis into batches and combines
        the table-sized partials across launches.

        With `residency` (segment/residency.py) the device cache is a
        byte-budgeted tier over the host arrays: each doc-slice is a cache
        group that charges the residency budget before copying (evicting
        cost-ranked victim slices to make room), at most one thread stages
        a group while the rest park on its event, and `prefetch=True` marks
        a stage issued ahead of need (the engine's double-buffered copy
        stream) for the prefetch-hit accounting."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if mesh is None:
            from pinot_tpu.parallel.mesh import default_mesh

            mesh = default_mesh(axis)
        # `axis` may be one mesh axis name or the 2-D (replica, shard)
        # axes tuple: a tuple shards the leading [S, ...] dim jointly over
        # both axes (capacity mode — parallel/mesh.data_axes)
        row_sharding = NamedSharding(mesh, P(axis, None))
        rep_sharding = NamedSharding(mesh, P())
        cols = columns or list(self.columns)
        sl = doc_slice if doc_slice is not None else (0, self.docs_per_shard)
        group = self.device_group(mesh, sl)

        if residency is None:
            # legacy pin-everything path: no budget, no eviction
            while True:
                need, need_valid, _ = self._plan_missing(
                    mesh, cols, sl, packed_codes, with_valid
                )
                if need or need_valid:
                    staged = self._stage_slice(need, need_valid, sl, row_sharding, rep_sharding)
                    self._publish(mesh, group, staged)
                out = self._assemble(mesh, cols, sl, packed_codes, with_valid)
                if out is not None:
                    return out

        from pinot_tpu.segment import residency as res_mod
        from pinot_tpu.utils.crashpoints import crash_point

        while True:
            need, need_valid, _ = self._plan_missing(mesh, cols, sl, packed_codes, with_valid)
            st, entry = residency.begin_stage(
                group,
                self.schema.name,
                lambda: self.evict_slice(mesh, sl),
                prefetch=prefetch,
            )
            if st == res_mod.WAIT:
                residency.wait(entry)
                continue
            if st == res_mod.HIT:
                if not need and not need_valid:
                    out = self._assemble(mesh, cols, sl, packed_codes, with_valid)
                    if out is not None:
                        return out
                    continue  # evicted between plan and read: re-stage
                st2, entry2 = residency.begin_grow(group)
                if st2 == res_mod.WAIT:
                    residency.wait(entry2)
                    continue
                if st2 == res_mod.RETRY:
                    continue
            # OWN: charge, copy (no locks held), publish, commit
            try:
                need, need_valid, nbytes = self._plan_missing(
                    mesh, cols, sl, packed_codes, with_valid
                )
                residency.charge(group, nbytes, query_id=query_id)
                crash_point("segment.stage.after_charge")
                staged = self._stage_slice(need, need_valid, sl, row_sharding, rep_sharding)
                crash_point("segment.stage.after_copy")
                self._publish(mesh, group, staged)
            except BaseException:
                residency.abort_stage(group)
                raise
            residency.finish_stage(group)
            out = self._assemble(mesh, cols, sl, packed_codes, with_valid)
            if out is not None:
                return out

    def release_device(self) -> None:
        # in-place: self-join facades (aliased_view) share this dict by
        # reference — rebinding would leave their references pinning HBM
        with self._device_lock:
            self._device_cache.clear()
            self._group_keys.clear()

    # -- self-join facades ----------------------------------------------
    def aliased_view(self, alias: str) -> "StackedTable":
        """A facade of this table for SELF-JOINS: columns renamed to
        '{alias}${col}' so one query can reference two instances without
        name collisions (the reference resolves this in Calcite's scope
        binding; here it is a table-level rename).  Storage is SHARED — the
        facade's StackedColumn objects reference the same numpy arrays, and
        to_device's array-identity cache keys mean one HBM copy serves
        every alias."""
        import dataclasses as _dc

        from pinot_tpu.spi.schema import Schema as _Schema

        cols = {
            f"{alias}${n}": _dc.replace(c, name=f"{alias}${n}") for n, c in self.columns.items()
        }
        schema = _Schema(
            name=f"{self.schema.name}@{alias}",
            fields=[_dc.replace(f, name=f"{alias}${f.name}") for f in self.schema.fields],
            primary_key_columns=[f"{alias}${c}" for c in self.schema.primary_key_columns],
        )
        idx = {
            kind: {f"{alias}${n}": v for n, v in by_col.items()}
            for kind, by_col in self.indexes.items()
        }
        t = StackedTable(schema, cols, self.valid, self.num_docs, indexes=idx)
        t._device_lock = self._device_lock
        with self._device_lock:
            t._device_cache = self._device_cache
            t._group_keys = self._group_keys
        return t

    # -- host decode (selection gather) ---------------------------------
    def decoded_flat(self, name: str) -> np.ndarray:
        """Row-major decoded values (padding rows included; mask with valid)."""
        c = self.columns[name]
        if c.dictionary is not None:
            return c.dictionary.get_values(c.codes.reshape(-1))
        return c.values.reshape(-1)

    def decoded_rows(self, name: str, rows: np.ndarray) -> np.ndarray:
        """Decoded values for SPECIFIC flat doc ids — O(len(rows)) host work,
        never a full-column decode (selection gathers read a LIMIT-sized
        handful out of potentially 1B rows)."""
        c = self.columns[name]
        if c.dictionary is not None:
            return c.dictionary.get_values(c.codes.reshape(-1)[rows])
        return c.values.reshape(-1)[rows]
