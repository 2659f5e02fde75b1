"""Segment builder: rows -> immutable columnar segment.

Reference parity: pinot-segment-local SegmentIndexCreationDriverImpl.build
(SegmentIndexCreationDriverImpl.java:248) — stats pass, dictionary build,
per-column index creation, single-file packing — and SegmentColumnarIndexCreator.

Re-design: Pinot streams rows twice through per-row creators; here every phase
is a vectorized numpy pass over whole columns (np.unique fuses the stats pass
with dictionary build), and the output is written once via store.write_segment.

Encoding policy (delta from the reference, TPU-motivated):
  * STRING/BYTES/JSON: always dictionary-encoded — device sees int codes only.
  * Numeric DIMENSION / DATE_TIME: dictionary-encoded (sorted dict makes range
    predicates closed-form code compares) unless listed in
    no_dictionary_columns.
  * METRIC: raw storage by default (aggregation reads values directly; a
    dictionary gather would waste an HBM round-trip).  Pinot dict-encodes
    metrics by default; raw is the TPU-right default and Pinot supports the
    same via noDictionaryColumns.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from pinot_tpu.indexes.bloom import BloomFilter
from pinot_tpu.indexes.inverted import InvertedIndex, RangeEncodedIndex
from pinot_tpu.segment import packing
from pinot_tpu.segment.dictionary import Dictionary, min_code_dtype
from pinot_tpu.segment.segment import ColumnData, ImmutableSegment
from pinot_tpu.segment.stats import ColumnStats, collect_stats
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, Schema
from pinot_tpu.utils.hashing import partition_of

# Above this cardinality, bitmap indexes stop paying for themselves vs a
# vectorized code scan (see indexes/inverted.py docstring).
MAX_BITMAP_INDEX_CARDINALITY = 1 << 16

ColumnInput = Union[np.ndarray, Sequence[Any]]


def _extract_nulls(field, raw: ColumnInput) -> (np.ndarray, Optional[np.ndarray]):
    """Split out a null mask and substitute typed placeholders."""
    dt = field.data_type
    arr = np.asarray(raw, dtype=object) if not isinstance(raw, np.ndarray) or raw.dtype == object else raw
    null_mask = None
    if arr.dtype == object:
        null_mask = np.array([v is None or (isinstance(v, float) and np.isnan(v)) for v in arr], dtype=bool)
        if null_mask.any():
            arr = arr.copy()
            arr[null_mask] = dt.null_placeholder
        else:
            null_mask = None
        if not dt.is_string_like:
            arr = arr.astype(dt.np_dtype)
    else:
        if np.issubdtype(arr.dtype, np.floating):
            nan = np.isnan(arr)
            if nan.any():
                null_mask = nan
                arr = np.where(nan, dt.np_dtype.type(dt.null_placeholder), arr)
        if not dt.is_string_like:
            arr = arr.astype(dt.np_dtype, copy=False)
    if dt.is_string_like and arr.dtype != object:
        arr = arr.astype(object)
    if null_mask is not None and not field.nullable:
        raise ValueError(f"nulls in non-nullable column {field.name}")
    return arr, null_mask


def narrow_ints(arr: np.ndarray, nmask: Optional[np.ndarray]) -> np.ndarray:
    """Store 64-bit integer columns as int32 when the value range fits.

    TPUs have no 64-bit ALU (emulated, ~50x slower) — narrowing at build time
    makes scans/compares native-speed and halves HBM traffic.  The logical
    type stays LONG; only storage narrows.  Columns with nulls keep their
    dtype (the null placeholder is int64-min)."""
    if (
        nmask is None
        and np.issubdtype(arr.dtype, np.integer)
        and arr.dtype.itemsize > 4
        and len(arr)
        and np.iinfo(np.int32).min <= arr.min()
        and arr.max() <= np.iinfo(np.int32).max
    ):
        return arr.astype(np.int32)
    return arr


def build_segment(
    schema: Schema,
    data: Dict[str, ColumnInput],
    segment_name: str,
    table_config: Optional[TableConfig] = None,
    output_dir: Optional[str] = None,
) -> ImmutableSegment:
    """Build an immutable segment from column-major data.

    If output_dir is given, also persists it (driver's handlePostCreation)."""
    cfg = table_config or TableConfig(name=schema.name)
    idx_cfg: IndexingConfig = cfg.indexing
    names = schema.column_names
    missing = [n for n in names if n not in data]
    if missing:
        raise ValueError(f"missing columns in input data: {missing}")
    lengths = {n: len(data[n]) for n in names}
    if len(set(lengths.values())) > 1:
        raise ValueError(f"ragged column lengths: {lengths}")
    num_docs = lengths[names[0]] if names else 0

    # Extract nulls + typed arrays first (record-transformer analog).
    # Multi-value fields keep their raw list-of-lists shape here; they build
    # through the dedicated MV path below (null -> empty array, like the
    # reference's default MV null handling).
    arrays: Dict[str, np.ndarray] = {}
    nulls: Dict[str, Optional[np.ndarray]] = {}
    for f in schema.fields:
        if not f.single_value:
            arrays[f.name] = np.asarray(
                [tuple(v) if v is not None else () for v in data[f.name]], dtype=object
            )
            nulls[f.name] = None
            continue
        arrays[f.name], nulls[f.name] = _extract_nulls(f, data[f.name])

    # Sort by the configured sorted column (Pinot keeps segments sorted when
    # declared; gives contiguous docId ranges for predicates on that column).
    sort_order = None  # new position -> input row (upsert validDocIds remap)
    if idx_cfg.sorted_column and idx_cfg.sorted_column in arrays and num_docs > 1:
        order = np.argsort(arrays[idx_cfg.sorted_column], kind="stable")
        if not np.array_equal(order, np.arange(num_docs)):
            sort_order = order
            for n in names:
                arrays[n] = np.asarray(arrays[n])[order]
                if nulls[n] is not None:
                    nulls[n] = nulls[n][order]

    columns: Dict[str, ColumnData] = {}
    indexes: Dict[str, Dict[str, Any]] = {}
    for f in schema.fields:
        arr, nmask = arrays[f.name], nulls[f.name]
        if not f.single_value:
            if f.name in idx_cfg.vector_index_columns:
                col, vidx = _build_vector_column(f, arr, num_docs)
                columns[f.name] = col
                indexes.setdefault("vector", {})[f.name] = vidx
                continue
            columns[f.name] = _build_mv_column(f, arr, num_docs)
            continue
        use_dict = _wants_dictionary(f, idx_cfg)
        if use_dict:
            dictionary, codes32 = Dictionary.build(f.data_type, arr)
            codes = codes32.astype(min_code_dtype(dictionary.cardinality))
            stats = collect_stats(f.name, f.data_type, arr, nmask, dictionary.cardinality, True)
            # bit-pack the forward index when the cardinality fits a 4/8/16
            # bit lane (segment/packing.py); codes stay materialized for
            # host-side consumers (index builds, sorted searchsorted, decode)
            bits = packing.lane_bits(dictionary.cardinality)
            columns[f.name] = ColumnData(
                f.name, f.data_type, dictionary, codes, None, nmask, stats,
                code_bits=bits if bits < 32 else None,
                packed=packing.pack_codes(codes, bits) if bits < 32 else None,
            )
            if stats.is_sorted and num_docs:
                columns[f.name].first_docs()  # the sorted index: dictId -> first doc, kept from the build
            card = dictionary.cardinality
            if f.name in idx_cfg.inverted_index_columns:
                if card <= MAX_BITMAP_INDEX_CARDINALITY:
                    indexes.setdefault("inverted", {})[f.name] = InvertedIndex.build(codes32, card, num_docs)
                else:
                    # high cardinality: sparse compressed postings, O(docs)
                    # total storage (indexes/inverted.py CompressedInvertedIndex)
                    from pinot_tpu.indexes.inverted import CompressedInvertedIndex

                    indexes.setdefault("inverted", {})[f.name] = CompressedInvertedIndex.build(
                        codes32, card, num_docs
                    )
            if f.name in idx_cfg.range_index_columns and card <= MAX_BITMAP_INDEX_CARDINALITY:
                indexes.setdefault("range", {})[f.name] = RangeEncodedIndex.build(codes32, card, num_docs)
            if f.name in idx_cfg.json_index_columns:
                from pinot_tpu.indexes.jsonidx import JsonIndex

                indexes.setdefault("json", {})[f.name] = JsonIndex.build(dictionary.values)
            if f.name in idx_cfg.text_index_columns:
                from pinot_tpu.indexes.text import TextIndex

                indexes.setdefault("text", {})[f.name] = TextIndex.build(dictionary.values)
        else:
            if f.data_type.is_string_like:
                raise ValueError(f"string column {f.name} requires a dictionary")
            card = int(len(np.unique(arr)))
            stats = collect_stats(f.name, f.data_type, arr, nmask, card, False)
            columns[f.name] = ColumnData(f.name, f.data_type, None, None, narrow_ints(arr, nmask), nmask, stats)
        if f.name in idx_cfg.bloom_filter_columns:
            uniq = columns[f.name].dictionary.values if use_dict else np.unique(arr)
            indexes.setdefault("bloom", {})[f.name] = BloomFilter.build(list(uniq))

    # star-tree indexes: pre-aggregated prefix-level tensors (indexes/startree.py)
    for i, st_cfg in enumerate(idx_cfg.star_tree_index_configs):
        from pinot_tpu.indexes.startree import StarTreeIndex
        from pinot_tpu.utils.metrics import METRICS

        t0 = time.perf_counter()
        st = StarTreeIndex.build(
            columns,
            num_docs,
            st_cfg.get("dimensionsSplitOrder", []),
            st_cfg.get("functionColumnPairs", []),
            min_collapse=float(st_cfg.get("minCollapse", 1.1)),
        )
        METRICS.timer("segment.starTreeBuildMs").update((time.perf_counter() - t0) * 1000.0)
        if st is not None:
            indexes.setdefault("startree", {})[f"st{i}"] = st

    # partition metadata for partition-pinned routing
    if cfg.partition_column and cfg.partition_column in columns and cfg.num_partitions:
        col = columns[cfg.partition_column]
        vals = col.decoded()
        pids = np.unique([partition_of(v, cfg.num_partitions) for v in vals.tolist()])
        if len(pids) == 1:
            col.stats.partition_id = int(pids[0])
            col.stats.num_partitions = cfg.num_partitions

    time_range = None
    tc = cfg.segments.time_column
    if tc and tc in columns:
        s = columns[tc].stats
        time_range = (s.min_value, s.max_value)

    seg = ImmutableSegment(
        name=segment_name,
        table_name=cfg.name,
        schema=schema,
        columns=columns,
        num_docs=num_docs,
        indexes=indexes,
        creation_time_ms=int(time.time() * 1000),
        time_range=time_range,
    )
    seg.sort_order = sort_order
    if output_dir is not None:
        seg.save(output_dir)
    return seg


def _build_mv_column(f, lists: np.ndarray, num_docs: int) -> ColumnData:
    """Multi-value column: dictionary over the FLATTENED values + a padded
    [num_docs, max_len] code matrix with per-row lengths.

    Reference parity: FixedBitMVForwardIndexReader (pinot-segment-local/...
    readers/forward/FixedBitMVForwardIndexReader.java) stores var-length
    code runs; the TPU layout is fixed-width padded — a dense matrix the
    kernels scan with a length mask (static shapes, no row offsets).
    Padding cells hold code == cardinality (one past the dictionary), which
    every predicate table/range treats as no-match."""
    flat: list = []
    lengths = np.empty(num_docs, dtype=np.int32)
    for i, row in enumerate(lists):
        lengths[i] = len(row)
        flat.extend(row)
    flat_arr = np.asarray(flat, dtype=object if f.data_type.is_string_like else f.data_type.np_dtype)
    if flat_arr.dtype == object and not f.data_type.is_string_like:
        flat_arr = flat_arr.astype(f.data_type.np_dtype)
    dictionary, flat_codes = Dictionary.build(f.data_type, flat_arr)
    card = dictionary.cardinality
    max_len = max(1, int(lengths.max()) if num_docs else 1)
    code_dt = min_code_dtype(card + 1)  # +1: the padding code
    codes2d = np.full((num_docs, max_len), card, dtype=code_dt)
    pos = 0
    for i in range(num_docs):
        ln = lengths[i]
        codes2d[i, :ln] = flat_codes[pos : pos + ln]
        pos += ln
    stats = collect_stats(f.name, f.data_type, flat_arr, None, card, True)
    stats.num_docs = num_docs  # rows, not elements
    return ColumnData(f.name, f.data_type, dictionary, codes2d, None, None, stats, mv_lengths=lengths)


def _build_vector_column(f, lists: np.ndarray, num_docs: int):
    """Embedding column: raw padded [n, dim] float32 matrix (no dictionary)
    + a VectorIndex of the row-normalized matrix (indexes/vector.py)."""
    from pinot_tpu.indexes.vector import VectorIndex

    lengths = np.array([len(r) for r in lists], dtype=np.int32)
    max_len = max(1, int(lengths.max()) if num_docs else 1)
    mat = np.zeros((num_docs, max_len), dtype=np.float32)
    for i, row in enumerate(lists):
        mat[i, : len(row)] = np.asarray(row, dtype=np.float32)
    flat = mat[np.arange(max_len)[None, :] < lengths[:, None]]
    stats = collect_stats(f.name, f.data_type, flat.astype(np.float64), None, 0, False)
    stats.num_docs = num_docs
    col = ColumnData(f.name, f.data_type, None, None, mat, None, stats, mv_lengths=lengths)
    return col, VectorIndex.build(mat, lengths)


def _wants_dictionary(f, idx_cfg: IndexingConfig) -> bool:
    if f.data_type.is_string_like:
        return True
    if f.name in idx_cfg.no_dictionary_columns:
        return False
    return f.role in (FieldRole.DIMENSION, FieldRole.DATE_TIME)
