"""Segmented (grouped) and masked reductions — the aggregation hot path.

Reference parity: the per-row accumulate loops of DefaultGroupByExecutor +
the typed result holders (pinot-core/.../query/aggregation/groupby/
DefaultGroupByExecutor.java:192, result holders in the same package).  Pinot
accumulates into on-heap double[]/long[] arrays indexed by group id; the TPU
form maps the same computation onto the MXU.

TPU-native design (measured on v5e; numbers for 16M rows x 2406 groups):
  * TPUs have no 64-bit ALU: under jax_enable_x64, f64/i64 arithmetic is
    software-emulated (~50x slower on big arrays) and jax.ops.segment_sum
    promotes its scatter indices to int64 (1.75s vs 110us for a raw
    int32-index lax.scatter_add).  Nothing here ever touches 64-bit types on
    the row axis.
  * XLA lowers scatter to a serialized loop on TPU: even an f32 scatter-add
    group-by runs at ~0.15 Grows/s.  The MXU answer is the TWO-LEVEL ONE-HOT
    MATMUL: split code = hi*64 + lo, build two narrow one-hot matrices
    (n x H and n x 64 — n*(H+64) VPU compares instead of n*G), then
    (A * v)^T @ B accumulates the whole [H, 64] group table as one matmul.
    ~11 Grows/s in f32.
  * Exact integer sums at MXU speed: decompose values into 8-bit limbs —
    every limb (< 256) is exact in bfloat16, every per-chunk dot accumulates
    < 2^24 in the MXU's f32 accumulator, so each limb matmul is EXACT.  The
    per-chunk [limb, H, 64] tables are recombined in (emulated) f64, which is
    cheap at table size.  Negative int32 values ride a fifth limb: the
    two's-complement reinterpretation plus a -2^32 * count(v<0) correction.
    3.7-2.7 Grows/s, error == 0.  (Pinot's double accumulators round above
    2^53; this path doesn't round at all for int32 inputs.)
  * Float sums use the single-f32 matmul (~1e-5 worst-case relative error;
    float-float "double-single" limbs are a planned upgrade).
  * Group tables wider than _MATMUL_MAX_GROUPS scatter (_wide_group_tables):
    integers as 12-bit limbs into int32 tables over 2^19-row chunks, exact;
    min/max always use scatter (no matmul semiring).
  * On CPU (tests, golden comparisons) the "wide" policy scatters directly
    in f64/i64 — bit-exact vs sqlite — still with int32 indices.

All functions take a boolean mask (filter + null handling folded in by the
caller) and return f64 (i64 for counts) outputs; outputs are group-table
sized, so the final widening costs nothing.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Rows per chunk for the matmul path: limb sums stay < 2^24 (255 * 65536),
# i.e. exact in the MXU's f32 accumulator.
_CHUNK = 1 << 16
# Lane width of the two-level decomposition (code = hi * _W + lo).
_W = 64
# Above this group count the one-hot matrices stop paying for themselves.
_MATMUL_MAX_GROUPS = 8192
# A table read at a row's dictionary code, `table[codes]`, is read by a
# one-hot contraction (ops/code_lookup.py) where it has this many entries, by
# a gather outside.  Measured on the chip, one 1.5M-row segment, kernel alone
# (PERF.md section 5, PR 48): up to 64 entries the TPU compiler turns the
# gather into compares and selects, priced by the table and not by the row
# (under the 0.4 ms a call costs; the contraction 0.4 ms for a bool table, 1.2
# for an int32 one); from 65 on the gather is 10.8-12.1 ms a segment whatever
# the size, the contraction 0.4 / 1.2 ms up to 16,384 entries and linear in
# the table past that: 8.2 ms at 131,072 int32 entries, 12.3 at 196,608.  The
# upper end is that crossover (~172,000) rounded down to a power of two; a
# bool table's lies past 327,680 (5.2 ms there) and is not told apart.
_CONTRACT_MIN_TABLE = 65
_CONTRACT_MAX_TABLE = 1 << 17

_POS_INF32 = np.float32(np.inf)
_NEG_INF32 = np.float32(-np.inf)


@functools.lru_cache(maxsize=None)
def accum_policy() -> str:
    """"wide" (native 64-bit, CPU) or "chunked32" (32-bit kernels + small
    f64 combines, TPU and any backend without 64-bit ALUs)."""
    return "wide" if jax.default_backend() == "cpu" else "chunked32"


@functools.lru_cache(maxsize=None)
def _i64_low_half_index() -> int:
    """Which minor index of bitcast_convert_type(i64 -> u32) holds the LOW
    32 bits (XLA leaves the order to the backend; probe once per process)."""
    with jax.ensure_compile_time_eval():  # callable from inside a jit trace
        halves = np.asarray(
            jax.lax.bitcast_convert_type(jnp.asarray([1], jnp.int64), jnp.uint32)
        )
    return 0 if halves[0, 0] == 1 else 1


def _i32(codes):
    return codes.astype(jnp.int32)


def unpack_bitmap_words(words, n: int):
    """[n // 32] uint32 packed filter words -> [n] bool row mask (bit r of
    word w covers row 32 * w + r — the query/filter.eval_bitmap layout).
    The XLA-path materialization of the mask the Pallas scan keeps packed."""
    bits = ((words[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)) != 0
    return bits.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# Scatter primitives (explicit int32 indices)
# ---------------------------------------------------------------------------
def _scatter_add(target, idx_i32, updates):
    dnums = lax.ScatterDimensionNumbers(
        update_window_dims=(), inserted_window_dims=(0,), scatter_dims_to_operand_dims=(0,)
    )
    return lax.scatter_add(
        target, idx_i32[:, None], updates, dnums,
        indices_are_sorted=False, unique_indices=False,
        mode=lax.GatherScatterMode.FILL_OR_DROP,
    )


def _scatter_extreme(target, idx_i32, updates, *, is_min: bool):
    dnums = lax.ScatterDimensionNumbers(
        update_window_dims=(), inserted_window_dims=(0,), scatter_dims_to_operand_dims=(0,)
    )
    op = lax.scatter_min if is_min else lax.scatter_max
    return op(
        target, idx_i32[:, None], updates, dnums,
        indices_are_sorted=False, unique_indices=False,
        mode=lax.GatherScatterMode.FILL_OR_DROP,
    )


# ---------------------------------------------------------------------------
# Compaction: a row-priced scatter paid by the rows that PASS the filter
# ---------------------------------------------------------------------------
# The chip prices a 32-bit scatter by the ROW (7-9 ns, 10-14 ms a 1.5M-row
# segment, whatever the table's size) and gives a row the filter rejected a
# 0 to add.  A sort is not row-priced, so where a mask carries a predicate
# the passing rows are sorted first (ONE one-operand int32 sort a mask,
# rejected rows a sentinel that sorts last) and a loop of ceil(count / C)
# trips scatters C sorted entries a trip: the passing prefix alone.  What is
# sorted is what the scatter needs of a row in one word: the PAYLOAD where
# it packs (a sketch's cell << bits | value, a histogram's cell: the trip
# unpacks, no gather), else the ROW NUMBER (a wide table's codes and values
# ride one mask: the trip gathers them at C rows, a gather being row-priced
# too).  Integer max and integer add are order-free: the tables are the plain
# scatter's bit for bit at every passing share.
#
# Which form a call gets is read from its input alone.  A plan with no
# predicate says so where its kernel is traced (mask_facts: its masks are
# padding masks) and compiles no compaction: the plain scatter, its text
# unchanged.  A plan with one counts its mask on the device and takes the
# compaction under lax.cond where at most _COMPACT_MAX_SHARE_* of the rows
# pass, so a filter that passes nearly everything pays one count and the
# plain scatter.  accum_policy() "wide" (the CPU: native 64-bit scatters, no
# row price) keeps the plain form.
#
# Measured on the chip, kernel alone, one 1.5M-row segment, median of 7
# calls (PERF.md section 6, PR 51).  A row dropped by an out-of-range index
# is NOT free: 11.04 ms against the plain scatter's 11.07 at every share.
# The one-operand int32 sort 1.92 ms (3.09 stable).  PAYLOAD sorted (716,800
# registers, scatter-max; 358,400 bins, scatter-add, the same to 0.1 ms):
# 1.7 ms where no row passes, 2.8-2.9 at a share of 0.08, 4.5-4.6 at 0.2,
# 8.4-8.7 at 0.5, 10.4-10.5 at 0.65, 12.4-12.8 at 0.8 against the plain
# 11.0: linear, 1.9 + 13.2 x share, crossing at 0.69; the share below keeps
# a margin.  ROW NUMBERS sorted: a wide table's count and two limb tables
# (two gathers and three scatters a trip) 2.4-2.5 ms at 1/625, 6.6 at 0.08,
# 13.0 at 0.2, 28.9 at 0.5 against the plain 31.5 (crossing at 0.54); ONE
# table behind two gathers (a sketch whose value does not pack) 4.5 at
# 0.08, 8.7 at 0.2, 14.3 at 0.35 against 11.0, crossing at 0.26: the share
# below is that narrowest case's, every other mask's tables cross later.
# The chunk: a trip costs nothing that could be read (183 trips of 2^13 and
# 46 of 2^15 at a share of 1: 55.5 and 55.4 ms), and a lone trip is paid
# whole, so a small chunk wins where few rows pass (row numbers at 1/625:
# 2.3 / 2.4 / 2.8 / 3.9 ms at 2^12 / 2^13 / 2^14 / 2^15; 8.5 at 2^16) and
# loses nothing where many do (13.3 / 13.0 / 13.2 / 14.3 at 0.2).
_COMPACT_CHUNK = 1 << 13
_COMPACT_MAX_SHARE_PAYLOAD = 0.6
_COMPACT_MAX_SHARE_ROWS = 0.25
_I32_MAX = np.int32(np.iinfo(np.int32).max)


class MaskFacts:
    """What a plan's kernel says, at trace time, of the masks it hands to
    the scatters of this module (mask_facts), and what they answer."""

    def __init__(self, filtered: bool):
        self.filtered = filtered  # a predicate narrowed the masks: more than the padding mask
        self.compactions = 0  # compactions traced inside the block: one a distinct mask


_facts = threading.local()


@contextlib.contextmanager
def mask_facts(filtered: bool) -> Iterator[MaskFacts]:
    """What a plan's kernel wraps its body in (trace time only): `filtered`
    is the planner's static fact that the row masks carry a predicate.
    Outside any block a mask is taken as unfiltered: the plain scatter."""
    outer = getattr(_facts, "open", None)
    facts = _facts.open = MaskFacts(filtered)
    try:
        yield facts
    finally:
        _facts.open = outer


def _compacts() -> bool:
    """Whether the scatter being traced compiles the compaction."""
    facts = getattr(_facts, "open", None)
    return facts is not None and facts.filtered and accum_policy() != "wide"


def _compact_scatter(mask, keys, sentinel, init, scatter, plain, max_share: float):
    """The tables of a scatter of the mask-true rows: the compaction
    described above over `init()` (zeros), or `plain()` (every row
    scattered, a rejected one adding nothing) where more than `max_share`
    of the rows pass.

    keys: int32[n], sorted with `sentinel` (past every passing key) in the
    rejected rows' place.  scatter(tables, part, valid, pos) -> tables puts
    one chunk of the sorted keys: `part` int32[C], `valid` the entries that
    are passing rows' and not yet put, `pos` their places in the sorted
    order (a chunk's index where tables are kept a chunk of rows)."""
    from pinot_tpu.utils.metrics import METRICS

    facts = getattr(_facts, "open", None)
    if facts is not None:
        if not facts.compactions:
            METRICS.counter("scan.traced.compact_scatter").inc()  # trace time: this plan's program carries the compaction
        facts.compactions += 1
    n = mask.shape[0]
    if n == 0:
        return plain()
    chunk = min(_COMPACT_CHUNK, n)
    count = jnp.sum(mask, dtype=jnp.int32)

    def compacted():
        with jax.named_scope("compact_sort"):
            first = lax.sort(jnp.where(mask, keys, sentinel), is_stable=False)
        lane = lax.iota(jnp.int32, chunk)

        def trip(i, tables):
            # the last chunk is cut from n - C: its head, put a trip before, drops out
            begin = i * np.int32(chunk)
            start = jnp.minimum(begin, np.int32(n - chunk))
            pos = start + lane
            part = lax.dynamic_slice_in_dim(first, start, chunk)
            return scatter(tables, part, (pos >= begin) & (pos < count), pos)

        trips = (count + np.int32(chunk - 1)) // np.int32(chunk)
        return lax.fori_loop(np.int32(0), trips, trip, init())

    return lax.cond(count <= np.int32(max_share * n), compacted, plain)


# ---------------------------------------------------------------------------
# Two-level one-hot matmul core (chunked32 group path)
# ---------------------------------------------------------------------------
def _pad_to_chunks(*arrays):
    """Pad row arrays to a multiple of _CHUNK (padding rows carry mask=False
    via the first array being the already-masked values/False mask)."""
    n = arrays[0].shape[0]
    rem = n % _CHUNK
    if rem == 0:
        return arrays
    pad = _CHUNK - rem
    return tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) for a in arrays
    )


def _matmul_group_table(weighted_limbs, scales, codes, num_groups: int):
    """Core: sum of scales[l] * sum_rows(limb_l[row] * onehot(code)) tables.

    weighted_limbs: [n, L] bf16 (each limb value exact in bf16, masked rows 0)
    scales: f64[L] recombination factors
    Returns f64[num_groups]."""
    H = -(-num_groups // _W)
    n = weighted_limbs.shape[0]
    weighted_limbs, codes = _pad_to_chunks(weighted_limbs, _i32(codes))
    L = weighted_limbs.shape[1] if weighted_limbs.ndim == 2 else 1
    v_r = weighted_limbs.reshape(-1, _CHUNK, L)
    k_r = codes.reshape(-1, _CHUNK)
    scales = jnp.asarray(scales, jnp.float64)

    def body(acc, xs):
        li, ki = xs
        hi = ki // np.int32(_W)
        lo = ki % np.int32(_W)
        A = jax.nn.one_hot(hi, H, dtype=jnp.bfloat16)  # [C, H]
        B = jax.nn.one_hot(lo, _W, dtype=jnp.bfloat16)  # [C, W]
        S = jnp.einsum("cl,ch,cw->lhw", li, A, B, preferred_element_type=jnp.float32)
        tot = (S.astype(jnp.float64) * scales[:, None, None]).sum(0)
        return acc + tot, None

    acc, _ = lax.scan(body, jnp.zeros((H, _W), jnp.float64), (v_r, k_r))
    return acc.reshape(-1)[:num_groups]


def _matmul_group_sum_f32(values_f32, codes, num_groups: int):
    """Float path: single f32 matmul per chunk (~1e-5 relative error)."""
    H = -(-num_groups // _W)
    values_f32, codes = _pad_to_chunks(values_f32, _i32(codes))
    v_r = values_f32.reshape(-1, _CHUNK)
    k_r = codes.reshape(-1, _CHUNK)

    def body(acc, xs):
        vi, ki = xs
        hi = ki // np.int32(_W)
        lo = ki % np.int32(_W)
        A = jax.nn.one_hot(hi, H, dtype=jnp.float32)
        B = jax.nn.one_hot(lo, _W, dtype=jnp.float32)
        S = jnp.einsum("ch,cw->hw", A * vi[:, None], B, preferred_element_type=jnp.float32)
        return acc + S.astype(jnp.float64), None

    acc, _ = lax.scan(body, jnp.zeros((H, _W), jnp.float64), (v_r, k_r))
    return acc.reshape(-1)[:num_groups]


# ---------------------------------------------------------------------------
# Fused multi-aggregate group tables (the dense group-by hot path)
# ---------------------------------------------------------------------------
# A group-by query computes MANY additive group tables over the SAME key
# column: the presence table, each SUM's value limbs and null-aware count,
# AVG's pair, VARIANCE's triple...  Computing each through its own
# _matmul_group_table scan rebuilds the one-hot matrices per table — measured
# 3x slower end-to-end than one fused scan sharing one (A, B) pair per chunk
# (v5e, 134M rows x 2406 groups: 213ms separate vs ~60ms fused).  Layout
# note: W=64 with the lhw einsum is the measured sweet spot; W=128/256 and
# int8 MXU variants all regress (see round-2 bench notes).

def sum_limb_plan(vmin, vmax) -> Tuple[int, bool]:
    """(n_limbs, signed) for the exact two's-complement 8-bit limb
    decomposition of ints known to lie in [vmin, vmax].  Column stats shrink
    the default int32 plan (4 limbs + sign) down to as little as one limb —
    each dropped limb removes a whole matmul from every chunk."""
    if vmin is None or vmax is None:
        return 4, True
    vmin, vmax = int(vmin), int(vmax)
    if vmin < -(1 << 31) or vmax > (1 << 31) - 1:
        return 4, True  # caller guarantees int32 storage; defensive
    for k in (1, 2, 3, 4):
        if vmin >= 0 and vmax < (1 << (8 * k)):
            return k, False
        if -(1 << (8 * k - 1)) <= vmin and vmax < (1 << (8 * k - 1)):
            return k, True
    return 4, vmin < 0


def sum_limb_plan64(vmin, vmax) -> int:
    """Limb count for the SIGNED-MAGNITUDE 8-bit decomposition of int64
    values in [vmin, vmax] (the "int64_sum" fused kind).  Unlike the int32
    two's-complement plan there is no sign-correction limb — the sign rides
    each limb — so the count is just ceil(bits(max |v|) / 8)."""
    if vmin is None or vmax is None:
        return 8
    m = max(abs(int(vmin)), abs(int(vmax)))
    for k in range(1, 8):
        if m < (1 << (8 * k)):
            return k
    return 8


def int_sum_entry(values, vrange):
    """(kind, values, limb_plan) for the exact limb sum of an integer row
    array whose column stats, where it is a bare column, say it lies in
    `vrange` = (min, max): "int_sum" with sum_limb_plan for int32 and
    narrower (an int64 column that stats prove inside int32 is narrowed),
    "int64_sum" with sum_limb_plan64 past it; without stats the storage
    type's full width.  None where the values are not integers."""
    if not jnp.issubdtype(values.dtype, jnp.integer):
        return None
    if values.dtype.itemsize > 4 and vrange is not None and -(1 << 31) <= vrange[0] and vrange[1] < (1 << 31):
        values = values.astype(jnp.int32)  # stats prove int32 narrowing safe
    if values.dtype.itemsize <= 4:
        return "int_sum", values, sum_limb_plan(*vrange) if vrange is not None else (4, True)
    # wide-range int64: signed-magnitude limb decomposition, bit-exact
    # while sum(|v|) < 2^53 — the reference's double-accumulate contract
    # (SumAggregationFunction)
    return "int64_sum", values, sum_limb_plan64(*vrange) if vrange is not None else 8


def _int64_magnitude_halves(values, mask):
    """int64 values (masked rows 0) -> (low, high) uint32 halves of |v| and
    the row's sign as int32 +-1, every op 32-bit: the column is bitcast to
    uint32 halves and |v| computed with a one-bit carry (~v + 1 carries iff
    lo == 0)."""
    vm = jnp.where(mask, values, jnp.int64(0))
    halves = lax.bitcast_convert_type(vm, jnp.uint32)  # [n, 2]
    lo_ix = _i64_low_half_index()
    lo = halves[..., lo_ix]
    hi = halves[..., 1 - lo_ix]
    neg = hi >= np.uint32(1 << 31)
    alo = jnp.where(neg, ~lo + np.uint32(1), lo)
    ahi = jnp.where(neg, ~hi + (lo == np.uint32(0)).astype(jnp.uint32), hi)
    return alo, ahi, jnp.where(neg, np.int32(-1), np.int32(1))


def _int64_signed_limbs(values, mask, n_limbs: int, dt):
    """Signed-magnitude 8-bit limb columns + scales for int64 values.

    Two's-complement limbs would recombine through a -2^64 * negcount
    correction whose f64 cancellation is catastrophic (a column of -1s
    yields n*(2^64 - 1) - n*2^64, which rounds to 0 long before 2^53);
    sign-magnitude limbs keep every recombine partial sum bounded by
    sum(|v| mod 2^(8k)) <= sum(|v|), so the ascending-scale f64 recombine
    is BIT-exact while sum(|v|) < 2^53 — the reference's double-accumulate
    contract (SumAggregationFunction.java).  Every row-axis op is 32-bit
    (_int64_magnitude_halves), and each limb (<= 255, exact in bf16) is
    signed by the row's sign."""
    alo, ahi, sgn = _int64_magnitude_halves(values, mask)
    cols, scales = [], []
    for k in range(n_limbs):
        h = alo if k < 4 else ahi
        limb = ((h >> np.uint32(8 * (k % 4))) & np.uint32(0xFF)).astype(jnp.int32)
        cols.append((limb * sgn).astype(dt))
        scales.append(float(1 << (8 * k)))
    return cols, scales


# entry kinds understood by fused_group_tables
FUSED_KINDS = ("count", "int_sum", "int64_sum", "f32_sum", "f32_sumsq")


def _fused_wide_tables(entries, codes, num_groups: int):
    """Wide (native-f64) policy: ONE windowed scatter-add for ALL entries.

    Every FUSED_KIND is additive, so the whole group-by reduces to scattering
    [n, E] f64 update rows into a [num_groups, E] table — one serialized
    scatter loop instead of E of them.  Measured on the CPU bench (4M rows,
    2406 groups, 2 entries): 2 per-entry scatters = 375ms, one windowed
    scatter = 297ms/entry-pair — the difference between 11.7M and 14M rows/s.
    Counts ride as mask-valued f64 columns (exact integers below 2^53, the
    fused-table contract callers already cast from)."""
    codes = _i32(codes)
    cols = []
    for kind, values, mask, _ in entries:
        if kind == "count":
            cols.append(mask.astype(jnp.float64))
        elif kind == "f32_sumsq":
            v = values.astype(jnp.float64)
            cols.append(jnp.where(mask, v * v, 0.0))
        else:
            cols.append(jnp.where(mask, values.astype(jnp.float64), 0.0))
    upd = jnp.stack(cols, axis=1)  # [n, E]
    dnums = lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0,), scatter_dims_to_operand_dims=(0,)
    )
    table = lax.scatter_add(
        jnp.zeros((num_groups, len(entries)), jnp.float64), codes[:, None], upd, dnums,
        indices_are_sorted=False, unique_indices=False,
        mode=lax.GatherScatterMode.FILL_OR_DROP,
    )
    return [table[:, e] for e in range(len(entries))]


# row-length limb stacks past this size extract in-chunk instead of
# materializing [n, L] in HBM (see fused_group_tables)
_FUSED_STACK_BYTES = 1 << 31


def _entry_width(kind, limb_plan) -> int:
    """Limb-column count _entry_limbs will produce for this entry."""
    if kind == "count":
        return 1
    if kind == "int_sum":
        n_limbs, signed = limb_plan if limb_plan is not None else (4, True)
        return n_limbs + (1 if signed else 0)
    if kind == "int64_sum":
        return limb_plan if limb_plan is not None else 8
    return 1


def _fused_scan_inchunk(entries, codes, num_groups, dt, H):
    """fused_group_tables' loop with PER-CHUNK limb extraction and
    dynamic_slice reads straight out of the ORIGINAL flat arrays.

    No [n, L] limb stack, no pad copy, no scan-operand reshape copies —
    every one of those materialized gigabytes of HLO temps at 1B rows
    (three HBM-OOM post-mortems of the 1B bench).  The tail chunk slices
    from n - CHUNK with already-covered head rows masked off, so unaligned
    row counts need no padding."""
    n = codes.shape[0]
    operands = []
    for kind, values, mask, limb_plan in entries:
        v = values if values is not None else mask
        operands.append((v, mask))
    slices = []
    L = 0
    for kind, _, _, limb_plan in entries:
        w = _entry_width(kind, limb_plan)
        slices.append((L, None))  # scales captured at trace time below
        L += w

    num_chunks = max(1, -(-n // _CHUNK))
    scale_box = []
    iota = jnp.arange(_CHUNK, dtype=jnp.int32)

    def body(i, acc):
        start = jnp.minimum(i * _CHUNK, np.int32(max(0, n - _CHUNK)))
        # rows already covered by the previous chunk (tail overlap) drop out
        fresh = (start + iota) >= i * _CHUNK
        ki = _i32(lax.dynamic_slice_in_dim(codes, start, _CHUNK))
        cols = []
        for ei, (kind, _, _, limb_plan) in enumerate(entries):
            v, m = operands[ei]
            vi = lax.dynamic_slice_in_dim(v, start, _CHUNK)
            mi = lax.dynamic_slice_in_dim(m, start, _CHUNK) & fresh
            ecols, scales = _entry_limbs(kind, vi, mi, limb_plan, dt)
            if len(scale_box) == ei:  # python-level capture at trace time
                scale_box.append(scales)
            cols.extend(ecols)
        li = jnp.stack(cols, axis=1)
        hi = ki // np.int32(_W)
        lo = ki % np.int32(_W)
        A = jax.nn.one_hot(hi, H, dtype=dt)
        B = jax.nn.one_hot(lo, _W, dtype=dt)
        S = jnp.einsum("cl,ch,cw->lhw", li, A, B, preferred_element_type=jnp.float32)
        return acc + S.astype(jnp.float64)

    if n < _CHUNK:
        # single undersized chunk: fall back to padded one-shot
        ops_p = _pad_to_chunks(*[a for pair in operands for a in pair], codes)
        *ent_ops, codes_p = ops_p
        cols = []
        for ei, (kind, _, _, limb_plan) in enumerate(entries):
            ecols, scales = _entry_limbs(kind, ent_ops[2 * ei], ent_ops[2 * ei + 1], limb_plan, dt)
            if len(scale_box) == ei:
                scale_box.append(scales)
            cols.extend(ecols)
        li = jnp.stack(cols, axis=1)
        ki = _i32(codes_p)
        A = jax.nn.one_hot(ki // np.int32(_W), H, dtype=dt)
        B = jax.nn.one_hot(ki % np.int32(_W), _W, dtype=dt)
        acc = jnp.einsum("cl,ch,cw->lhw", li, A, B, preferred_element_type=jnp.float32).astype(jnp.float64)
    else:
        acc = lax.fori_loop(0, num_chunks, body, jnp.zeros((L, H, _W), jnp.float64))
    flat = acc.reshape(L, H * _W)[:, :num_groups]
    slices = [(start, scale_box[ei]) for ei, (start, _) in enumerate(slices)]
    return flat, slices


def _entry_limbs(kind, values, mask, limb_plan, dt):
    """-> (list of [n] limb columns in dtype dt, list of f64 scales)."""
    if kind == "count":
        return [mask.astype(dt)], [1.0]
    if kind == "int_sum":
        n_limbs, signed = limb_plan if limb_plan is not None else (4, True)
        vm = jnp.where(mask, values, np.int32(0)).astype(jnp.int32)
        u = vm.astype(jnp.uint32)
        cols = [((u >> np.uint32(8 * i)) & np.uint32(0xFF)).astype(dt) for i in range(n_limbs)]
        scales = [float(1 << (8 * i)) for i in range(n_limbs)]
        if signed:
            cols.append((vm < 0).astype(dt))
            scales.append(-float(1 << (8 * n_limbs)))
        return cols, scales
    if kind == "int64_sum":
        return _int64_signed_limbs(values, mask, limb_plan if limb_plan is not None else 8, dt)
    if kind == "f32_sum":
        return [jnp.where(mask, values.astype(jnp.float32), np.float32(0.0))], [1.0]
    v = values.astype(jnp.float32)
    return [jnp.where(mask, v * v, np.float32(0.0))], [1.0]


def fused_group_tables(
    entries, codes, num_groups: int, backend=None, mask_words=None, codes_packed=None
):
    """Compute many additive group tables in ONE chunked one-hot-matmul scan.

    entries: list of (kind, values, mask, limb_plan); kind in FUSED_KINDS,
    limb_plan = sum_limb_plan(...) for int_sum (None -> full int32 plan).
    Returns a list of f64[num_groups] tables in entry order ("count" entries
    are exact integer-valued f64; callers cast).

    backend: plan-time scan-backend tag ("pallas" | "interpret" | "xla" |
    None).  "pallas"/"interpret" dispatch Pallas-eligible entry sets (exact
    integer kinds, narrow-enough table — pallas_scan.pallas_supported) to
    the fused single-HBM-pass kernel; everything else stays here.
    mask_words: optional packed uint32 filter bitmap ([n // 32], the
    range-index word-slice layout) ANDed into every entry mask — the Pallas
    kernel unpacks it in-register; the XLA path unpacks it once up front.
    codes_packed: optional (words, code_bits) — the bit-packed forward index
    of the SAME key column (segment/packing.py lanes).  The Pallas kernel
    reads the words and lane-unpacks in-register; non-Pallas paths keep
    using `codes` (the caller's trace-level unpack, which XLA dedups/DCEs),
    so `codes` must always be provided.

    Exactness: int_sum limbs (< 256) and count flags are exact in bf16; each
    per-chunk MXU dot accumulates < 2^24 in f32 (exact); cross-chunk
    accumulation is f64.  f32_sum/f32_sumsq share the scan by promoting the
    one-hot matrices to f32 (int limbs stay exact there too)."""
    from pinot_tpu.utils.metrics import METRICS

    if backend in ("pallas", "interpret"):
        from pinot_tpu.ops import pallas_scan  # lazy: keeps import DAG flat

        if pallas_scan.pallas_supported(entries, num_groups):
            # trace-time record of which kernel THIS plan's dense scan got:
            # the plan-time backend tag alone cannot say whether the entry
            # set was eligible
            METRICS.counter(f"scan.traced.{backend}").inc()
            return pallas_scan.fused_group_tables_pallas(
                entries, codes, num_groups,
                mask_words=mask_words,
                codes_packed=codes_packed,
                interpret=(backend == "interpret"),
            )
    METRICS.counter("scan.traced.xla").inc()
    with jax.named_scope("xla_scan"):
        return _fused_group_tables_xla(entries, codes, num_groups, mask_words, codes_packed)


def _fused_group_tables_xla(entries, codes, num_groups: int, mask_words, codes_packed):
    """The XLA scan of fused_group_tables (everything the Pallas kernel
    declined or the backend does not have)."""
    if mask_words is not None:
        # declined the Pallas path (wide table, float kinds, CPU policy):
        # fall back to one explicit unpack shared by every entry
        # (once a distinct mask: the entries that shared one still do)
        row_mask = unpack_bitmap_words(mask_words, codes.shape[0])
        anded = {}
        for _, _, m, _ in entries:
            if id(m) not in anded:
                anded[id(m)] = m & row_mask
        entries = [(k, v, anded[id(m)], lp) for k, v, m, lp in entries]
    if accum_policy() == "wide":
        return _fused_wide_tables(entries, codes, num_groups)
    if num_groups > _MATMUL_MAX_GROUPS:
        return _wide_group_tables(entries, codes, num_groups)

    use_f32 = any(k in ("f32_sum", "f32_sumsq") for k, _, _, _ in entries)
    dt = jnp.float32 if use_f32 else jnp.bfloat16
    H = -(-num_groups // _W)

    # Estimate the [n, L] stacked-limb footprint; past the budget, limbs
    # extract INSIDE the scan body from the raw (values, mask) chunks —
    # VMEM-resident, ~25% slower per chunk but it removes the multi-GB HBM
    # intermediate that OOMed the 1B-row bench.  Dead-bytes rule: even under
    # the budget, when the widened stack would out-weigh the RAW inputs
    # (e.g. an int8 dict column fanning out to L bf16 limb columns) the
    # in-chunk form wins — it streams the narrow storage bytes instead of
    # writing back a wider copy of them.
    n_rows = codes.shape[0]
    L = sum(_entry_width(kind, limb_plan) for kind, _, _, limb_plan in entries)
    stack_bytes = n_rows * L * jnp.dtype(dt).itemsize
    # dead-byte rule: a bit-packed key streams code_bits/8 bytes per row —
    # the trace-level unpacked view never touches HBM at full width
    key_bytes = codes_packed[1] / 8.0 if codes_packed is not None else codes.dtype.itemsize
    raw_ids = {id(codes): key_bytes}
    for _, values, mask, _ in entries:
        if values is not None:
            raw_ids[id(values)] = values.dtype.itemsize
        raw_ids[id(mask)] = mask.dtype.itemsize
    raw_bytes = n_rows * sum(raw_ids.values())
    if stack_bytes > _FUSED_STACK_BYTES or (
        stack_bytes > raw_bytes and n_rows >= 4 * _CHUNK
    ):
        flat, slices = _fused_scan_inchunk(entries, codes, num_groups, dt, H)
    else:
        cols = []
        slices = []  # per entry: (start, scales)
        for kind, values, mask, limb_plan in entries:
            ecols, scales = _entry_limbs(kind, values, mask, limb_plan, dt)
            slices.append((len(cols), scales))
            cols.extend(ecols)

        stacked = jnp.stack(cols, axis=1)  # [n, L]
        # codes keep their storage dtype; the body casts one chunk at a time
        # (a full-array i32 cast is a multi-GB HBM temp at 1B rows)
        stacked, codes = _pad_to_chunks(stacked, codes)
        v_r = stacked.reshape(-1, _CHUNK, L)
        k_r = codes.reshape(-1, _CHUNK)

        def body(acc, xs):
            li, ki = xs
            ki = _i32(ki)
            hi = ki // np.int32(_W)
            lo = ki % np.int32(_W)
            A = jax.nn.one_hot(hi, H, dtype=dt)  # [C, H]
            B = jax.nn.one_hot(lo, _W, dtype=dt)  # [C, W]
            S = jnp.einsum("cl,ch,cw->lhw", li, A, B, preferred_element_type=jnp.float32)
            return acc + S.astype(jnp.float64), None

        acc, _ = lax.scan(body, jnp.zeros((L, H, _W), jnp.float64), (v_r, k_r))
        flat = acc.reshape(L, H * _W)[:, :num_groups]

    out = []
    for start, scales in slices:
        t = flat[start] * scales[0] if scales[0] != 1.0 else flat[start]
        for j, s in enumerate(scales[1:], start=1):
            t = t + flat[start + j] * s
        out.append(t)
    return out


# ---------------------------------------------------------------------------
# Wide group tables (chunked32, num_groups > _MATMUL_MAX_GROUPS)
# ---------------------------------------------------------------------------
# Past _MATMUL_MAX_GROUPS the one-hot matrices stop paying and rows scatter.
# The chip scatters 32-bit words at 7-9 ns a row and 64-bit ones (emulated:
# a pair of 32-bit halves) at 85-94, and an f32 table loses a unit as soon as
# a slot passes 2^24 (SSB Q3.2 on the chip, PERF.md PR 31).  So an integer rides as
# 12-bit limbs, each scattered into an int32 table of its own over chunks of
# 2^19 rows: a chunk's limb sum is at most 2^19 * (2^12 - 1) < 2^31, exact;
# chunks and limbs recombine in int64 at table size, exact while the group's
# sum is an int64; the table leaves as f64 like every fused table, exact
# below 2^53.  The limb count comes from the column's min/max at plan time
# (sum_limb_plan / sum_limb_plan64); without stats it is the storage type's
# full width, never fewer.  Floats have no exact sum: they keep an f32
# table over 2^16-row chunks and an f64 combine (relative error of a chunk's
# accumulation <= 2^16 * 2^-24, as the matmul path's).
#
# A sparse plan (planner.sparse_grouped_tables) has SORTED its rows, so a
# group is a row range and its limb sum the difference of the limb's prefix
# sum at the range's two ends (prefix_group_sums, limb_prefix_table): no
# scatter a limb, one prefix sum (~1 ms a 1.5M-row segment against a
# scatter's 13) and one table-size gather.  Exactness, for every input:
# with limbs of prefix_limb_bits(rows) bits (8 up to 2^23 rows) the sum of
# ANY set of a segment's rows of one limb column is at most (2^bits - 1) *
# rows < 2^31 in magnitude (a signed-magnitude limb and the negatives'
# column are -1 at least), so no prefix and no group sum leaves int32,
# were one group the whole segment at the column's extreme values; the
# prefix is kept in uint32, where a negative one is its residue mod 2^32,
# and the difference of two residues read as int32 IS the group's sum.
# The bound comes from the static row count alone.  Limbs meet in int64 at
# table size like the scatters' tables, to the same value mod 2^64.
_WIDE_LIMB_BITS = 12
_WIDE_CHUNK = 1 << 19


def _chunked_scatter(col, codes, num_groups: int, chunk_rows: int):
    """[n] 32-bit updates -> [k, num_groups] per-chunk tables of the same
    dtype, k = ceil(n / chunk_rows): ONE scatter into a flat k * num_groups
    table, the chunk folded into the index."""
    n = codes.shape[0]
    k = max(1, -(-n // chunk_rows))
    idx = codes if k == 1 else (lax.iota(jnp.int32, n) // np.int32(chunk_rows)) * np.int32(num_groups) + codes
    return _scatter_add(jnp.zeros((k * num_groups,), col.dtype), idx, col).reshape(k, num_groups)


def _wide_int_limbs(kind, values, mask, limb_plan, limb_bits: int = _WIDE_LIMB_BITS):
    """-> [(int32 limb column, bit shift)] of an integer entry: its table is
    the sum over limbs of limb_table << shift.  int32 and narrower: the
    two's complement in 8 * n_limbs bits cut into limbs of `limb_bits` bits
    (12 for a wide table), plus, where the plan is signed, minus one a
    negative row at shift 8 * n_limbs.  int64: signed-magnitude limbs
    (_int64_signed_limbs' reasoning), the sign riding each limb."""
    low = np.uint32((1 << limb_bits) - 1)
    if kind == "int_sum":
        n_limbs, signed = limb_plan if limb_plan is not None else (4, True)
        bits = 8 * n_limbs
        vm = jnp.where(mask, values, np.int32(0)).astype(jnp.int32)
        u = vm.astype(jnp.uint32)
        if bits < 32:
            u = u & np.uint32((1 << bits) - 1)
        out = [(((u >> np.uint32(s)) & low).astype(jnp.int32), s) for s in range(0, bits, limb_bits)]
        if signed:
            out.append((-(vm < 0).astype(jnp.int32), bits))
        return out
    alo, ahi, sgn = _int64_magnitude_halves(values, mask)
    out = []
    for s in range(0, 8 * (limb_plan if limb_plan is not None else 8), limb_bits):
        if s >= 32:
            w = ahi >> np.uint32(s - 32)
        elif s + limb_bits <= 32:
            w = alo >> np.uint32(s)
        else:  # the limb that straddles the halves
            w = (alo >> np.uint32(s)) | (ahi << np.uint32(32 - s))
        out.append(((w & low).astype(jnp.int32) * sgn, s))
    return out


def limb_scatter_table(kind, values, mask, limb_plan, codes, num_groups: int):
    """The exact table of ONE "count" / "int_sum" / "int64_sum" entry by
    int32 scatters alone, the form described above: int32[num_groups] for a
    count (a sum of ones: no chunk can overflow), int64 for a sum (one
    table a 12-bit limb over 2^19-row chunks, chunks and limbs met at table
    size).  `codes` are int32 in [0, num_groups).  indices_are_sorted buys
    nothing on the chip where they are (a sparse plan's slots after its
    sort: 14.02 against 14.05 ms a 1.5M-row scatter, PERF.md PR 42)."""
    if kind == "count":
        return _chunked_scatter(mask.astype(jnp.int32), codes, num_groups, codes.shape[0])[0]
    return sum(
        _chunked_scatter(limb, codes, num_groups, _WIDE_CHUNK).astype(jnp.int64).sum(axis=0) << np.int64(shift)
        for limb, shift in _wide_int_limbs(kind, values, mask, limb_plan)
    )


def prefix_limb_bits(rows: int) -> int:
    """The limb width of a sorted-row prefix table (the block above) over
    `rows` rows: the widest, at most 8 bits, with (2^bits - 1) * rows < 2^31,
    so that no group, were it every row at the limb's largest value, sums
    past an int32.  8 up to 2^23 rows (_SCALAR_PIECE's bound), 7 up to
    2^24, ...; the static shape decides, no data does."""
    bits = min(8, (((1 << 31) - 1) // max(rows, 1) + 1).bit_length() - 1)
    if bits < 1:
        raise ValueError(f"{rows} rows cannot be indexed in int32")
    return bits


def prefix_group_sums(col, lo, hi=None):
    """int32[groups] sums of the int32 row column `col` over each group's
    CONTIGUOUS rows [lo[g], hi[g]), read from ONE prefix sum: no row-length
    scatter.  `hi` None: the groups adjoin, `lo` has groups + 1 bounds and
    one table-size gather serves both ends.  The prefix is kept in uint32,
    where a negative one (a signed limb's) is its residue mod 2^32; the
    difference of two residues, read as int32, is the group's sum while
    that lies in int32 (the caller's bound: prefix_limb_bits)."""
    c = jnp.concatenate([jnp.zeros((1,), jnp.uint32), jnp.cumsum(col.astype(jnp.uint32), dtype=jnp.uint32)])
    at = lo if hi is None else jnp.concatenate([lo, hi])
    g = c[at]  # the sum of the rows before each bound
    k = lo.shape[0] - 1 if hi is None else lo.shape[0]
    return lax.bitcast_convert_type(g[at.shape[0] - k :] - g[:k], jnp.int32)


def limb_prefix_table(kind, values, mask, limb_plan, lo, hi=None):
    """limb_scatter_table's int64 table of an "int_sum" / "int64_sum" entry
    over SORTED rows whose groups are the row ranges [lo, hi)
    (prefix_group_sums): one int32 prefix sum a limb of prefix_limb_bits
    bits, limbs met in int64 at table size.  Bit for bit the scatter's
    table: both are the group's exact sum mod 2^64."""
    bits = prefix_limb_bits(mask.shape[0])
    return sum(
        prefix_group_sums(limb, lo, hi).astype(jnp.int64) << np.int64(shift)
        for limb, shift in _wide_int_limbs(kind, values, mask, limb_plan, bits)
    )


_LIMB_KINDS = ("count", "int_sum", "int64_sum")


def _wide_limb_shifts(kind, limb_plan):
    """The bit shifts of an integer entry's int32 tables, in _wide_int_limbs'
    order (a count: its one table): the plan's alone, static."""
    if kind == "count":
        return [0]
    if kind == "int_sum":
        n_limbs, signed = limb_plan if limb_plan is not None else (4, True)
        return list(range(0, 8 * n_limbs, _WIDE_LIMB_BITS)) + ([8 * n_limbs] if signed else [])
    return list(range(0, 8 * (limb_plan if limb_plan is not None else 8), _WIDE_LIMB_BITS))


def _compacted_limb_tables(entries, codes, num_groups: int):
    """{entry index: limb_scatter_table's table, as int64} of the integer
    entries, by ONE compaction a distinct mask (_compact_scatter, the row
    numbers sorted): the entries that share a mask (a Q3.x's count and its
    limb tables all carry the WHERE mask) share its sort and its trips, a
    trip gathering the codes and each entry's values at C rows.  The int32
    bound is limb_scatter_table's: a limb table holds at most 2^19 rows'
    worth, the compacted POSITION taking the row number's place in the chunk
    index, so the same k tables a limb meet in int64 at table size."""
    n = codes.shape[0]
    by_mask = {}
    for i, (kind, _, mask, _) in enumerate(entries):
        if kind in _LIMB_KINDS:
            by_mask.setdefault(id(mask), []).append(i)
    out = {}
    for members in by_mask.values():
        mask = entries[members[0]][2]
        # every int32 table of the members, in order: (entry, bit shift, rows a chunk)
        specs = [
            (i, shift, n if entries[i][0] == "count" else _WIDE_CHUNK)
            for i in members
            for shift in _wide_limb_shifts(entries[i][0], entries[i][3])
        ]

        def columns(passing, take):
            cols = []
            for i in members:
                kind, values, _, limb_plan = entries[i]
                if kind == "count":
                    cols.append(passing.astype(jnp.int32))
                else:
                    cols.extend(limb for limb, _ in _wide_int_limbs(kind, take(values), passing, limb_plan))
            return cols

        def plain():
            return tuple(
                _chunked_scatter(col, codes, num_groups, per).reshape(-1)
                for col, (_, _, per) in zip(columns(mask, lambda v: v), specs)
            )

        def init():
            return tuple(jnp.zeros((max(1, -(-n // per)) * num_groups,), jnp.int32) for _, _, per in specs)

        def gathered(tables, rows, valid, pos):
            rows = jnp.minimum(rows, np.int32(n - 1))
            at = codes[rows]
            chunked = (pos // np.int32(_WIDE_CHUNK)) * np.int32(num_groups) + at
            return tuple(
                _scatter_add(table, jnp.where(valid, at if per >= n else chunked, np.int32(table.shape[0])), col)
                for table, col, (_, _, per) in zip(tables, columns(valid, lambda v: v[rows]), specs)
            )

        tables = _compact_scatter(mask, lax.iota(jnp.int32, n), np.int32(n), init, gathered, plain, _COMPACT_MAX_SHARE_ROWS)
        for (i, shift, _), table in zip(specs, tables):
            limb = table.reshape(-1, num_groups).astype(jnp.int64).sum(axis=0) << np.int64(shift)
            out[i] = out[i] + limb if i in out else limb
    return out


def _wide_group_tables(entries, codes, num_groups: int):
    """fused_group_tables for a table past _MATMUL_MAX_GROUPS under
    chunked32: one int32 (or, for floats, f32) scatter a limb column, the
    form described above; the integer entries of a filtered mask by its
    compaction.  Float entries keep the plain scatter: moving rows between
    chunks would change a float sum's last bits.  Returns f64[num_groups]
    tables in entry order."""
    from pinot_tpu.utils.metrics import METRICS

    METRICS.counter("scan.traced.wide_scatter").inc()  # trace time: which form this plan's table got
    codes = _i32(codes)
    out = []
    with jax.named_scope("wide_scatter"):
        compacted = _compacted_limb_tables(entries, codes, num_groups) if _compacts() else {}
        for i, (kind, values, mask, limb_plan) in enumerate(entries):
            if i in compacted:
                out.append(compacted[i].astype(jnp.float64))
            elif kind in _LIMB_KINDS:
                out.append(limb_scatter_table(kind, values, mask, limb_plan, codes, num_groups).astype(jnp.float64))
            else:
                v = values.astype(jnp.float32)
                v = jnp.where(mask, v * v if kind == "f32_sumsq" else v, np.float32(0.0))
                t = _chunked_scatter(v, codes, num_groups, _CHUNK)
                out.append(t.astype(jnp.float64).sum(axis=0))
    return out


# ---------------------------------------------------------------------------
# Grouped reductions
# ---------------------------------------------------------------------------
def group_sum(values, mask, codes, num_groups: int):
    """f64[num_groups] sum of values where mask, by group code."""
    codes = _i32(codes)
    is_int = jnp.issubdtype(values.dtype, jnp.integer)
    if accum_policy() == "wide":
        v = jnp.where(mask, values.astype(jnp.float64), 0.0)
        return _scatter_add(jnp.zeros((num_groups,), jnp.float64), codes, v)
    if num_groups > _MATMUL_MAX_GROUPS:
        kind = "f32_sum" if not is_int else "int_sum" if values.dtype.itemsize <= 4 else "int64_sum"
        return _wide_group_tables([(kind, values, mask, None)], codes, num_groups)[0]
    if is_int and values.dtype.itemsize <= 4:
        # exact limb path (int32 and narrower)
        vm = jnp.where(mask, values, np.int32(0)).astype(jnp.int32)
        u = vm.astype(jnp.uint32)
        limbs = [((u >> np.uint32(8 * i)) & np.uint32(0xFF)).astype(jnp.bfloat16) for i in range(4)]
        limbs.append((vm < 0).astype(jnp.bfloat16))  # two's-complement correction
        stacked = jnp.stack(limbs, axis=1)
        scales = [float(1 << (8 * i)) for i in range(4)] + [-float(1 << 32)]
        return _matmul_group_table(stacked, scales, codes, num_groups)
    if is_int:
        # exact signed-magnitude limb path for int64 (see _int64_signed_limbs)
        cols, scales = _int64_signed_limbs(values, mask, 8, jnp.bfloat16)
        return _matmul_group_table(jnp.stack(cols, axis=1), scales, codes, num_groups)
    v = jnp.where(mask, values.astype(jnp.float32), np.float32(0.0))
    return _matmul_group_sum_f32(v, codes, num_groups)


def group_sum_sq(values, mask, codes, num_groups: int):
    if accum_policy() == "wide":
        v = values.astype(jnp.float64)
        return group_sum(v * v, mask, codes, num_groups)
    v = values.astype(jnp.float32)
    return group_sum(v * v, mask, codes, num_groups)


def group_count(mask, codes, num_groups: int):
    """i64[num_groups] count of mask-true rows by group code."""
    codes = _i32(codes)
    if accum_policy() == "wide":
        return _scatter_add(jnp.zeros((num_groups,), jnp.int64), codes, mask.astype(jnp.int64))
    if num_groups > _MATMUL_MAX_GROUPS:
        return _wide_group_tables([("count", None, mask, None)], codes, num_groups)[0].astype(jnp.int64)
    # single-limb matmul: per-chunk counts <= _CHUNK, exact in f32
    stacked = mask.astype(jnp.bfloat16)[:, None]
    return _matmul_group_table(stacked, [1.0], codes, num_groups).astype(jnp.int64)


def group_min(values, mask, codes, num_groups: int):
    """f64[num_groups]; +inf where a group matched no rows.

    chunked32 note: f32 scatter (values round to f32; exact below 2^24).
    Scatter is the slow path on TPU — acceptable because min/max group-bys
    are rare vs sum/count; a Pallas tiled kernel is the planned upgrade."""
    codes = _i32(codes)
    if accum_policy() == "wide":
        v = jnp.where(mask, values.astype(jnp.float64), jnp.float64(np.inf))
        return _scatter_extreme(jnp.full((num_groups,), np.float64(np.inf)), codes, v, is_min=True)
    v = jnp.where(mask, values.astype(jnp.float32), _POS_INF32)
    out = _scatter_extreme(jnp.full((num_groups,), _POS_INF32), codes, v, is_min=True)
    return out.astype(jnp.float64)


def group_max(values, mask, codes, num_groups: int):
    """f64[num_groups]; -inf where a group matched no rows.  chunked32: an
    f32 scatter, as group_min: exact for integers below 2^24, rounded past
    it.  A sketch's registers do not ride it (sketch_max_table)."""
    codes = _i32(codes)
    if accum_policy() == "wide":
        v = jnp.where(mask, values.astype(jnp.float64), jnp.float64(-np.inf))
        return _scatter_extreme(jnp.full((num_groups,), np.float64(-np.inf)), codes, v, is_min=False)
    v = jnp.where(mask, values.astype(jnp.float32), _NEG_INF32)
    out = _scatter_extreme(jnp.full((num_groups,), _NEG_INF32), codes, v, is_min=False)
    return out.astype(jnp.float64)


# ---------------------------------------------------------------------------
# Sketch tables: [groups x width] cells of small integers (query/sketches.py)
# ---------------------------------------------------------------------------
def _sketch_scatter_traced() -> None:
    from pinot_tpu.utils.metrics import METRICS

    METRICS.counter("scan.traced.sketch_scatter").inc()  # trace time: this plan's sketch table took the scatter form


def sketch_max_table(values, mask, cells, num_cells: int, value_bits=None):
    """int32[num_cells]: the largest of `values` (int32, >= 0: HyperLogLog's
    rho) over the mask-true rows of each cell, 0 where a cell has none.  ONE
    int32 scatter-max under either policy: a register is a small integer and
    never rides a float.  `value_bits`: the caller's static bound, values <
    2^value_bits; where cell << value_bits | value fits an int32 a filtered
    mask's compaction sorts that payload, else the row numbers."""
    _sketch_scatter_traced()
    with jax.named_scope("sketch_scatter"):

        def zeros():
            return jnp.zeros((num_cells,), jnp.int32)

        def plain():
            v = jnp.where(mask, values.astype(jnp.int32), np.int32(0))
            return _scatter_extreme(zeros(), _i32(cells), v, is_min=False)

        if not _compacts():
            return plain()
        cells = _i32(cells)
        values = values.astype(jnp.int32)
        if value_bits is not None and num_cells << value_bits <= _I32_MAX:
            low = np.int32((1 << value_bits) - 1)

            def unpacked(table, part, valid, pos):
                at = jnp.where(valid, part >> np.int32(value_bits), np.int32(num_cells))
                return _scatter_extreme(table, at, part & low, is_min=False)

            payload = (cells << np.int32(value_bits)) | values
            return _compact_scatter(mask, payload, _I32_MAX, zeros, unpacked, plain, _COMPACT_MAX_SHARE_PAYLOAD)
        n = mask.shape[0]

        def gathered(table, rows, valid, pos):
            rows = jnp.minimum(rows, np.int32(n - 1))
            at = jnp.where(valid, cells[rows], np.int32(num_cells))
            return _scatter_extreme(table, at, values[rows], is_min=False)

        return _compact_scatter(mask, lax.iota(jnp.int32, n), np.int32(n), zeros, gathered, plain, _COMPACT_MAX_SHARE_ROWS)


def sketch_count_table(mask, cells, num_cells: int):
    """int64[num_cells]: the mask-true rows of each cell (a histogram's
    bins).  Exact integers: a table the one-hot matmul holds
    (_MATMUL_MAX_GROUPS) is group_count's; past it ONE scatter-add, int32
    under chunked32 (a cell of one call holds at most its rows, far under
    2^31) and widened at table size, so that tables can meet by addition
    across any number of segments.  A filtered mask's compaction sorts the
    cells themselves."""
    if num_cells <= _MATMUL_MAX_GROUPS:
        return group_count(mask, cells, num_cells)
    _sketch_scatter_traced()
    with jax.named_scope("sketch_scatter"):
        dt = jnp.int64 if accum_policy() == "wide" else jnp.int32

        def zeros():
            return jnp.zeros((num_cells,), dt)

        def plain():
            return _scatter_add(zeros(), _i32(cells), mask.astype(dt))

        if not _compacts():
            return plain().astype(jnp.int64)

        def counted(table, part, valid, pos):
            return _scatter_add(table, jnp.where(valid, part, np.int32(num_cells)), jnp.ones(part.shape, dt))

        return _compact_scatter(mask, _i32(cells), _I32_MAX, zeros, counted, plain, _COMPACT_MAX_SHARE_PAYLOAD).astype(jnp.int64)


# ---------------------------------------------------------------------------
# Masked scalar reductions (aggregation without group-by)
# ---------------------------------------------------------------------------
def masked_count(mask):
    """i64 scalar count (reduce in i32, widen the scalar)."""
    if accum_policy() == "wide":
        return jnp.sum(mask, dtype=jnp.int64)
    return jnp.sum(mask, dtype=jnp.int32).astype(jnp.int64)


# Rows one whole-column int32 reduction of an 8-bit limb may cover:
# 255 * 2^23 < 2^31.  A segment is one piece; a longer column is cut at
# static bounds and the pieces' sums meet in int64.
_SCALAR_PIECE = 1 << 23


def _scalar_limb_sums(limbs):
    """One exact i64 scalar a limb column (int32[n], |x| <= 255).  Each limb
    is reduced WHOLE, in int32, where it is produced: no limb is stacked,
    padded or reshaped, so the limbs of a value column are the outputs of
    one reduction fusion over the column's operands, and only L scalars a
    piece reach the recombine."""
    n = limbs[0].shape[0]
    sums = []
    for limb in limbs:
        total = jnp.int64(0)
        for start in range(0, max(n, 1), _SCALAR_PIECE):
            total = total + jnp.sum(limb[start : start + _SCALAR_PIECE], dtype=jnp.int32).astype(jnp.int64)
        sums.append(total)
    return sums


def masked_sum(values, mask):
    """f64 scalar masked sum.

    chunked32: integer inputs ride exact 8-bit limbs, each summed in int32
    (_scalar_limb_sums): Pinot's double accumulator below 2^53.  int32 and
    narrower: the two's complement's low bytes unsigned and its top byte
    SIGNED (an arithmetic shift), so no sign-correction column; |sum| <
    rows * 2^31 cannot reach 2^63, so the limb sums meet in int64 and are
    converted once.  int64: signed-magnitude limbs, whose sum CAN pass 2^63
    (four rows of 2^62), so their sums meet in f64, scales ascending
    (_int64_signed_limbs' reasoning: exact below 2^53, a double's rounding
    past it, never a wrap).  Floats use XLA's f32 tree reduction with an
    f64 chunk combine (~2^-24 relative error per chunk)."""
    if accum_policy() == "wide":
        return jnp.sum(jnp.where(mask, values.astype(jnp.float64), 0.0))
    if jnp.issubdtype(values.dtype, jnp.integer):
        from pinot_tpu.utils.metrics import METRICS

        METRICS.counter("scan.traced.scalar_limbs").inc()  # trace time: one a value column summed in limbs
        with jax.named_scope("scalar_sum"):
            if values.dtype.itemsize <= 4:
                vm = jnp.where(mask, values, np.int32(0)).astype(jnp.int32)
                top = 8 * (values.dtype.itemsize - 1)
                limbs = [(vm >> np.int32(s)) & np.int32(0xFF) for s in range(0, top, 8)]
                limbs.append(vm >> np.int32(top))
                total = jnp.int64(0)
                for k, s in enumerate(_scalar_limb_sums(limbs)):
                    total = total + (s << np.int64(8 * k))
                return total.astype(jnp.float64)
            cols, scales = _int64_signed_limbs(values, mask, 8, jnp.int32)
            total = jnp.float64(0.0)
            for s, scale in zip(_scalar_limb_sums(cols), scales):
                total = total + s.astype(jnp.float64) * np.float64(scale)
            return total
    v = jnp.where(mask, values.astype(jnp.float32), np.float32(0.0))
    # two-stage: f32 chunk sums (vectorized reduce), f64 combine of the
    # small vector — bounds error without the scatter.
    (v,) = _pad_to_chunks(v)
    return v.reshape(-1, _CHUNK).sum(axis=1).astype(jnp.float64).sum()


def masked_sum_sq(values, mask):
    if accum_policy() == "wide":
        v = values.astype(jnp.float64)
        return masked_sum(v * v, mask)
    v = values.astype(jnp.float32)
    return masked_sum(v * v, mask)


def masked_min(values, mask):
    """f64 scalar; +inf when nothing matched."""
    if accum_policy() == "wide":
        return jnp.min(jnp.where(mask, values.astype(jnp.float64), jnp.float64(np.inf)))
    return jnp.min(jnp.where(mask, values.astype(jnp.float32), _POS_INF32)).astype(jnp.float64)


def masked_max(values, mask):
    if accum_policy() == "wide":
        return jnp.max(jnp.where(mask, values.astype(jnp.float64), jnp.float64(-np.inf)))
    return jnp.max(jnp.where(mask, values.astype(jnp.float32), _NEG_INF32)).astype(jnp.float64)
