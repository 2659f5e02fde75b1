"""Sketch & distinct aggregations: DISTINCTCOUNT, DISTINCTCOUNTHLL, PERCENTILE.

Reference parity: pinot-core's sketch family —
DistinctCountAggregationFunction (exact, value sets),
DistinctCountHLLAggregationFunction (HyperLogLog registers),
PercentileEst/TDigest/KLL (quantile sketches)
(pinot-core/.../query/aggregation/function/, SURVEY.md 2.2 "Aggregation
functions": 106 classes, DISTINCTCOUNT(HLL/...)/PERCENTILE(Est/TDigest/KLL)).

TPU re-design — all three become FIXED-SIZE TENSOR partials whose combine is
elementwise, so they ride the same dense-group-table + psum machinery as SUM:

  * DISTINCTCOUNT (exact): a presence table over the column's code domain
    (dictionary ids, or range-offset raw ints).  partial field "present"
    [.., domain] int32 0/1, combine = max (set union).  final = row-sum.
    Pinot keeps hash sets per group; a bounded-domain bitmap is the exact
    tensor equivalent (same idea as its RoaringBitmap-based
    DistinctCountBitmapAggregationFunction).
  * DISTINCTCOUNTHLL: classic HLL registers [.., m], int32 from the scatter
    on (ops.sketch_max_table: an int32 scatter-max on the chip and on the
    CPU, never a float); combine = max (HLL union is register-wise max —
    exactly FIELD_COMBINE's "max").  A NUMERIC column is hashed ON THE
    DEVICE, by VALUE: a raw column's values as they are, a dictionary
    column's decoded through its device dictionary (one gather a row), then
    murmur3's 32-bit finalizer over the value's 32-bit words
    (_device_hash_values: one word an INT, two a LONG or a DOUBLE, whatever
    width a segment stores them in), bucket = the hash's low log2m bits, rho
    = the leading zeros of the rest + 1 (lax.clz: no float).  It is the hash
    a raw column has always had, and a dictionary column whose segments'
    dictionaries differ.  The kernel bakes nothing of a segment's dictionary, so a table
    whose segments were built apart is ONE kernel a query shape
    (segment/table_shape.py) and every segment's registers meet by max.  A
    STRING column's values never reach the device: its dictionary is hashed
    on the host (blake2b a value, _hll_host_tables) into per-code bucket /
    rho tables the kernel bakes, so its kernel is its segment's own
    (planner._segment_signature).
  * PERCENTILE (and the Est/TDigest/KLL names): an equi-width histogram
    sketch over [lo, hi], the TABLE's range where the engine or the broker
    injected one (`__range__<col>`), else the segment's stats; partial
    "hist" [.., B] additive (ops.sketch_count_table: exact integer counts) +
    "lo"/"hi" scalar fields (min/max combine) to keep merges
    self-describing.  final interpolates within the hit bin.  Accuracy is
    (hi-lo)/B — with B=2048 that is tighter than Pinot's default TDigest
    compression for most distributions, and the partial is mergeable across
    segments by plain addition (a psum over ICI).

HLL's and PERCENTILE's fields meet by NAME (FIELD_COMBINE), so a server's
group program folds its members' [groups, m] tables into one on the chip as
it folds scalar fields (`fold_by_field`, planner.combines).

Binding: these functions need per-column constants (domain width, hash
tables, bin ranges).  `get_agg_function` returns unbound singletons whose
merge/final are shape-agnostic (reduce side); the planner calls
`with_args(literal_args)` then `bind_column(info)` to get the kernel-side
instance (see planner._bind_aggs).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import numpy as np

from pinot_tpu import ops
from pinot_tpu.query.functions import AggFunction, register

# Grouped sketch tables (presence bitmaps, HLL registers, histograms) are
# capped at this many cells (groups x per-group width) — the
# numGroupsLimit-style memory valve.  Also guarantees the flattened
# keys*width+offset index stays far below int32 overflow (silent
# FILL_OR_DROP row loss otherwise).
MAX_PRESENCE_CELLS = 1 << 26

# Pinot's DistinctCountHLL default is log2m=8 for the plain HLL type
# (CommonConstants.Helix.DEFAULT_HYPERLOGLOG_LOG2M); we default to 12 —
# 1.04 / sqrt(4096) = 1.6 % standard error vs 6.5 % — because the register
# table is a device tensor here: int32[groups, 4096], filled by ONE int32
# scatter-max a segment from a 32-bit hash of the value (20 bits left for
# rho: registers <= 21).  Documented accuracy delta; pass an explicit log2m
# literal arg for parity.
_DEFAULT_LOG2M = 12
_DEFAULT_PERCENTILE_BINS = 2048


def _check_cell_budget(fn_name: str, num_groups: int, width: int) -> None:
    cells = num_groups * width
    if cells > MAX_PRESENCE_CELLS:
        raise NotImplementedError(
            f"{fn_name} grouped table {num_groups}x{width} = {cells} cells exceeds "
            f"{MAX_PRESENCE_CELLS}; lower group-key cardinality, numGroupsLimit, "
            "or the sketch width (log2m / bins)"
        )


@dataclass(frozen=True)
class ColumnBinding:
    """What the planner knows about the aggregated column at plan time.

    kind is already alignment-resolved by planner.column_binding:
      "dict"   - dictionary codes are a SHARED key space across all segments
                 of the query (single segment, stacked table, or verified
                 equal fingerprints) — code-indexed partials merge directly.
      "rawint" - bounded int value range (table-global); partials index by
                 (value - base), aligned by construction.
      "raw"    - unbounded/float values; only hash-based sketches apply.
    """

    kind: str  # "dict" | "rawint" | "raw"
    domain: int = 0  # dictionary cardinality / int range width
    base: int = 0  # min value for rawint code normalization
    # host-side dictionary values (numeric np array or object array) for
    # hash precomputation; None for raw columns
    dict_values: Optional[np.ndarray] = None
    # the column's values reach the device (a string's never do: only its
    # codes), so a value-hash sketch can hash them there
    numeric: bool = True
    # a LONG / TIMESTAMP / DOUBLE column: its values are 64-bit ones, though
    # a segment whose range fits may store them in 32
    wide: bool = False
    # column stats for histogram ranges
    min_value: Any = None
    max_value: Any = None


# ---------------------------------------------------------------------------
# Exact DISTINCTCOUNT
# ---------------------------------------------------------------------------
class DistinctCountFunction(AggFunction):
    """Exact distinct count over a bounded code domain.

    needs_codes: the planner feeds dictionary codes (or range-offset ints)
    instead of values — the domain is what presence is tracked over."""

    name = "distinctcount"
    needs_codes = True
    needs_binding = True
    vector_fields = True
    fields = ("present",)

    # how the planner feeds rows: "codes" (shared-key-space dictionary) or
    # "values_offset" (decoded value - base over a table-global int range)
    input_kind = "codes"

    def __init__(self, domain: int = 0, base: int = 0, input_kind: str = "codes"):
        self.domain = domain
        self.base = base
        self.input_kind = input_kind

    def bind_column(self, info: ColumnBinding) -> "AggFunction":
        if info.kind == "dict":
            # codes only merge across segments when the key space is shared —
            # planner.column_binding already downgraded kind otherwise
            return DistinctCountFunction(domain=info.domain, input_kind="codes")
        if info.kind == "rawint":
            return DistinctCountFunction(domain=info.domain, base=info.base, input_kind="values_offset")
        if info.dict_values is not None:
            # misaligned per-segment dictionaries: exact count still works by
            # unioning DECODED value sets at reduce (the reference's
            # DistinctCountAggregationFunction value-set semantics); device
            # work stays a local presence bitmap, host decodes present codes
            return DistinctCountValueSetFunction(info.dict_values)
        raise NotImplementedError(
            "exact DISTINCTCOUNT needs a dictionary or a bounded int range; "
            "this column has neither (unbounded/float raw values) — use "
            "DISTINCTCOUNTHLL"
        )

    # codes arrive as the "values" argument
    def partial(self, codes, mask):
        import jax.numpy as jnp

        present = ops.group_count(mask, codes, self.domain) > 0
        return {"present": present.astype(jnp.int32)}

    def partial_grouped(self, codes, mask, keys, num_groups):
        import jax.numpy as jnp

        _check_cell_budget(self.name, num_groups, self.domain)
        cells = num_groups * self.domain
        flat = keys * np.int32(self.domain) + codes
        present = ops.group_count(mask, flat, cells) > 0
        return {"present": present.astype(jnp.int32).reshape(num_groups, self.domain)}

    def merge(self, a, b):
        # the unbound registry singleton merges BOTH partial forms: presence
        # bitmaps (aligned code spaces) and host value sets (fallback below)
        if "valueset" in a:
            return {"valueset": a["valueset"] | b["valueset"]}
        return {"present": np.maximum(a["present"], b["present"])}

    def final(self, p):
        if "valueset" in p:
            return len(p["valueset"])
        return np.asarray(p["present"]).sum(axis=-1)

    def final_dtype(self):
        return np.dtype(np.int64)


class DistinctCountValueSetFunction(AggFunction):
    """Exact distinct count across segments with DIFFERENT dictionaries.

    Device partial: presence bitmap over the segment's LOCAL dictionary.
    host_partial decodes present codes into a frozenset; reduce unions sets
    (reference DistinctCountAggregationFunction's value-set merge).  Grouped
    form is unsupported (per-group sets defeat the tensor contract) — use
    DISTINCTCOUNTHLL for grouped heterogeneous-dictionary counts."""

    name = "distinctcount"
    needs_codes = True
    needs_binding = True
    vector_fields = True
    fields = ("present",)
    input_kind = "codes"

    def __init__(self, dict_values):
        self._values = np.asarray(dict_values, dtype=object)
        self.domain = len(self._values)

    def partial(self, codes, mask):
        import jax.numpy as jnp

        present = ops.group_count(mask, codes, self.domain) > 0
        return {"present": present.astype(jnp.int32)}

    def partial_grouped(self, codes, mask, keys, num_groups):
        raise NotImplementedError(
            "exact grouped DISTINCTCOUNT requires a shared dictionary across "
            "segments; these segments' dictionaries differ — use DISTINCTCOUNTHLL"
        )

    def host_partial(self, p):
        present = np.asarray(p["present"]) > 0
        return {"valueset": frozenset(self._values[present].tolist())}

    def merge(self, a, b):
        return {"valueset": a["valueset"] | b["valueset"]}

    def final(self, p):
        return len(p["valueset"])

    def final_dtype(self):
        return np.dtype(np.int64)


# ---------------------------------------------------------------------------
# DISTINCTCOUNTHLL
# ---------------------------------------------------------------------------
def _hll_host_tables(values: np.ndarray, log2m: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-dictionary-id (bucket, rho) of a STRING / BYTES dictionary from a
    64-bit host hash of each value's bytes (blake2b): card hashes total — the
    dictionary trick: device rows only gather.  A numeric dictionary has no
    host tables: its values are hashed on the device (bind_column)."""
    import hashlib

    m = 1 << log2m
    nbits = 64 - log2m
    buckets = np.empty(len(values), dtype=np.int32)
    rhos = np.empty(len(values), dtype=np.int32)
    for i, v in enumerate(values):
        b = v if isinstance(v, bytes) else str(v).encode("utf-8")
        h = int.from_bytes(hashlib.blake2b(b, digest_size=8).digest(), "little")
        buckets[i] = h & (m - 1)
        w = h >> log2m
        rhos[i] = (nbits - w.bit_length()) + 1 if w else nbits + 1
    return buckets, rhos


def _device_hash32(x):
    """murmur3 finalizer on uint32 lanes (device-side, 32-bit ops only)."""
    import jax.numpy as jnp

    h = x.astype(jnp.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    return h


def _device_hash_values(v, seed=np.uint32(0)):
    """Hash arbitrary-width numeric values with 32-bit ops only.

    8-byte types split into two 32-bit words so (nearly) the full bit
    pattern participates — a plain int32 cast truncates and collides values
    2^32 apart (review-caught).  TPU's X64 rewriter cannot lower 64-bit
    bitcast-convert, so the split is arithmetic: LONGs shift/mask; DOUBLEs
    take the float32 head + float32 residual (~48 mantissa bits; doubles
    closer than that collide, which is within HLL's approximation budget).

    `seed` XORs into the input lanes before finalizing, yielding an
    INDEPENDENT hash stream per seed — the 62-bit sketch hashes combine two
    differently-seeded streams of the full value instead of deriving the low
    word from the high one (ADVICE r5: hash32(h1^c) carries only h1's 32
    bits of entropy)."""
    import jax.numpy as jnp
    from jax import lax

    seed = np.uint32(seed)
    if v.dtype.itemsize == 8:
        if jnp.issubdtype(v.dtype, jnp.floating):
            head = v.astype(jnp.float32)
            resid = (v - head.astype(jnp.float64)).astype(jnp.float32)
            w0 = lax.bitcast_convert_type(head, jnp.uint32)
            w1 = lax.bitcast_convert_type(resid, jnp.uint32)
        else:
            w0 = (v & np.int64(0xFFFFFFFF)).astype(jnp.uint32)
            w1 = ((v >> np.int64(32)) & np.int64(0xFFFFFFFF)).astype(jnp.uint32)
        return _device_hash32((w0 ^ seed) ^ _device_hash32(w1 ^ seed))
    if jnp.issubdtype(v.dtype, jnp.floating):
        return _device_hash32(lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32) ^ seed)
    return _device_hash32(v.astype(jnp.int32).astype(jnp.uint32) ^ seed)


# second-stream seed for the 62-bit KMV hashes (any odd constant works; this
# is the golden-ratio word the old derived construction reused as an XOR)
_H2_SEED = np.uint32(0x9E3779B9)


def _device_hash62(values):
    """Positive-int64 62-bit hash: two independently seeded 32-bit streams,
    h1 -> bits 31..61, h2 -> bits 0..30 (int64 sort order == unsigned order).
    Shared by the theta/tuple KMV sketches."""
    import jax.numpy as jnp

    h1 = _device_hash_values(values)
    h2 = _device_hash_values(values, seed=_H2_SEED)
    return ((h1 & np.uint32(0x7FFFFFFF)).astype(jnp.int64) << np.int64(31)) | (
        h2 >> np.uint32(1)
    ).astype(jnp.int64)


class DistinctCountHLLFunction(AggFunction):
    """HyperLogLog distinct count: registers [.., m], combine = max."""

    name = "distinctcounthll"
    needs_codes = True
    needs_binding = True
    vector_fields = True
    fields = ("hll",)
    fold_by_field = True  # registers hash VALUES: every segment's meet by max
    binds = "value_hash"

    input_kind = "codes"

    def __init__(self, log2m: int = _DEFAULT_LOG2M, bucket_table=None, rho_table=None, device_hash=False, wide=False):
        self.log2m = int(log2m)
        self.wide = wide  # device_hash: the column's values are 64-bit ones, whatever width a segment stores
        self.m = 1 << self.log2m
        self.bucket_table = bucket_table  # np.int32[card] for dict columns
        self.rho_table = rho_table
        self.device_hash = device_hash  # raw path: hash values on device
        self.input_kind = "values_hash" if device_hash else "codes"

    def with_args(self, literal_args):
        if literal_args:
            return DistinctCountHLLFunction(log2m=int(literal_args[0]))
        return self

    def bind_column(self, info: ColumnBinding) -> "DistinctCountHLLFunction":
        if info.dict_values is not None and not info.numeric:
            # a string's values never reach the device: the dictionary is
            # hashed on the host, by value (registers align across segments
            # whose dictionaries differ), and the kernel bakes the tables
            b, r = _hll_host_tables(info.dict_values, self.log2m)
            return DistinctCountHLLFunction(self.log2m, bucket_table=b, rho_table=r)
        # a numeric column, raw or dictionary-encoded: the planner feeds the
        # decoded values and the kernel bakes nothing of the segment
        return DistinctCountHLLFunction(self.log2m, device_hash=True, wide=info.wide)

    def _bucket_rho(self, values_or_codes):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("sketch_hash"):
            if self.device_hash:
                # bucket = the hash's low log2m bits; rho = the place of the
                # first 1 bit of the rest, counted from their top, and one
                # past them where there is none: the leading zeros of
                # (hash >> log2m) less log2m - 1, integers all the way
                v = values_or_codes
                if self.wide and v.dtype.itemsize < 8 and jnp.issubdtype(v.dtype, jnp.integer):
                    # a LONG column stored narrowed (its range fits 32 bits in
                    # THIS segment): hashed as the 64-bit value it is, so that
                    # a segment that stores it whole holds the same registers
                    v = v.astype(jnp.int64)
                h = _device_hash_values(v)
                bucket = (h & np.uint32(self.m - 1)).astype(jnp.int32)
                rho = jax.lax.clz(h >> np.uint32(self.log2m)).astype(jnp.int32) - np.int32(self.log2m - 1)
                return bucket, rho
            bucket = jnp.asarray(self.bucket_table)[values_or_codes]
            rho = jnp.asarray(self.rho_table)[values_or_codes]
            return bucket, rho

    @property
    def _rho_bits(self) -> int:
        """rho < 2^bits, from the hash's width alone: at most its bits past
        the bucket's, and one (_bucket_rho; _hll_host_tables hashes 64)."""
        return ((32 if self.device_hash else 64) - self.log2m + 1).bit_length()

    def partial(self, codes, mask):
        bucket, rho = self._bucket_rho(codes)
        return {"hll": ops.sketch_max_table(rho, mask, bucket, self.m, value_bits=self._rho_bits)}

    def partial_grouped(self, codes, mask, keys, num_groups):
        _check_cell_budget(self.name, num_groups, self.m)
        bucket, rho = self._bucket_rho(codes)
        flat = keys * np.int32(self.m) + bucket
        regs = ops.sketch_max_table(rho, mask, flat, num_groups * self.m, value_bits=self._rho_bits)
        return {"hll": regs.reshape(num_groups, self.m)}

    def merge(self, a, b):
        return {"hll": np.maximum(a["hll"], b["hll"])}

    def final(self, p):
        regs = np.asarray(p["hll"], dtype=np.float64)
        m = regs.shape[-1]
        alpha = 0.7213 / (1 + 1.079 / m)
        est = alpha * m * m / np.sum(np.exp2(-regs), axis=-1)
        zeros = np.sum(regs == 0, axis=-1)
        # small-range correction (linear counting)
        with np.errstate(divide="ignore"):
            lc = m * np.log(np.where(zeros > 0, m / np.maximum(zeros, 1), 1.0))
        est = np.where((est <= 2.5 * m) & (zeros > 0), lc, est)
        return np.rint(est).astype(np.int64)

    def final_dtype(self):
        return np.dtype(np.int64)


# ---------------------------------------------------------------------------
# PERCENTILE (histogram sketch)
# ---------------------------------------------------------------------------
class PercentileFunction(AggFunction):
    """Equi-width histogram percentile: partial = ("hist" add, "lo" min,
    "hi" max).  The engine injects a table-global [lo, hi] via bind_column so
    all segments share bin edges (mergeable by addition)."""

    name = "percentile"
    needs_binding = True
    vector_fields = True
    fields = ("hist", "lo", "hi")
    fold_by_field = True  # one kernel = one [lo, hi]: its members' bins add
    binds = "range"

    def __init__(self, rank: float = 50.0, lo: float = 0.0, hi: float = 1.0, bins: int = _DEFAULT_PERCENTILE_BINS):
        self.rank = float(rank)
        self.lo = float(lo)
        self.hi = float(hi)
        self.bins = int(bins)

    def with_args(self, literal_args):
        if literal_args:
            return PercentileFunction(rank=float(literal_args[0]), lo=self.lo, hi=self.hi, bins=self.bins)
        return self

    def bind_column(self, info: ColumnBinding) -> "PercentileFunction":
        lo = float(info.min_value) if info.min_value is not None else 0.0
        hi = float(info.max_value) if info.max_value is not None else 1.0
        if hi <= lo:
            hi = lo + 1.0
        return PercentileFunction(self.rank, lo, hi, self.bins)

    def _bin(self, values):
        import jax.numpy as jnp

        v = values.astype(jnp.float32)
        scale = np.float32(self.bins / (self.hi - self.lo))
        b = jnp.floor((v - np.float32(self.lo)) * scale).astype(jnp.int32)
        return jnp.clip(b, 0, self.bins - 1)

    def _range_fields(self, template):
        import jax.numpy as jnp

        lo = jnp.full(template, self.lo, dtype=jnp.float32)
        hi = jnp.full(template, self.hi, dtype=jnp.float32)
        return lo, hi

    def partial(self, values, mask):
        b = self._bin(values)
        hist = ops.sketch_count_table(mask, b, self.bins)
        lo, hi = self._range_fields(())
        return {"hist": hist, "lo": lo, "hi": hi}

    def partial_grouped(self, values, mask, keys, num_groups):
        _check_cell_budget(self.name, num_groups, self.bins)
        b = self._bin(values)
        flat = keys * np.int32(self.bins) + b
        hist = ops.sketch_count_table(mask, flat, num_groups * self.bins).reshape(num_groups, self.bins)
        lo, hi = self._range_fields((num_groups,))
        return {"hist": hist, "lo": lo, "hi": hi}

    def merge(self, a, b):
        # bin edges are injected table-globally (engine _inject_sketch_info);
        # summing histograms with mismatched edges would silently skew the
        # percentile, so mismatch is an error, not a merge
        if not (np.allclose(a["lo"], b["lo"]) and np.allclose(a["hi"], b["hi"])):
            raise ValueError(
                "percentile histograms have mismatched bin edges "
                f"([{a['lo']}, {a['hi']}] vs [{b['lo']}, {b['hi']}]) — partials "
                "were built without a shared table-global range"
            )
        return {
            "hist": a["hist"] + b["hist"],
            "lo": np.minimum(a["lo"], b["lo"]),
            "hi": np.maximum(a["hi"], b["hi"]),
        }

    def final(self, p):
        hist = np.atleast_2d(np.asarray(p["hist"], dtype=np.float64))
        lo = np.atleast_1d(np.asarray(p["lo"], dtype=np.float64))
        hi = np.atleast_1d(np.asarray(p["hi"], dtype=np.float64))
        n_groups, bins = hist.shape
        out = np.full(n_groups, np.nan)
        width = (hi - lo) / bins
        for g in range(n_groups):
            total = hist[g].sum()
            if total == 0:
                continue
            target = self.rank / 100.0 * total
            cum = np.cumsum(hist[g])
            idx = int(np.searchsorted(cum, target, side="left"))
            idx = min(idx, bins - 1)
            prev = cum[idx - 1] if idx > 0 else 0.0
            in_bin = hist[g][idx]
            frac = (target - prev) / in_bin if in_bin > 0 else 0.0
            out[g] = lo[g] + width[g] * (idx + frac)
        scalar = np.asarray(p["hist"]).ndim == 1
        return out[0] if scalar else out


# The Est/TDigest names resolve to the same mergeable histogram sketch;
# accuracy contract is (hi-lo)/bins instead of the reference's per-sketch
# bounds (documented delta — the partials remain mergeable across segments
# and psum-combinable across chips, which the reference's sketches are not).
# PERCENTILEKLL lives in aggs_extra.py as a log-bucketed sketch with a
# relative-error bound on unbounded/skewed ranges.
class PercentileEstFunction(PercentileFunction):
    name = "percentileest"


class PercentileTDigestFunction(PercentileFunction):
    name = "percentiletdigest"


for _cls in (
    DistinctCountFunction,
    DistinctCountHLLFunction,
    PercentileFunction,
    PercentileEstFunction,
    PercentileTDigestFunction,
):
    register(_cls())

# Pinot alias: exact distinct count over partitioned segments
from pinot_tpu.query.functions import _REGISTRY  # noqa: E402

_REGISTRY["segmentpartitioneddistinctcount"] = _REGISTRY["distinctcount"]
_REGISTRY["distinctcountbitmap"] = _REGISTRY["distinctcount"]
