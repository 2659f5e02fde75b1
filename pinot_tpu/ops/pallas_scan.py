"""Pallas fused filter→group-by scan: one pass over HBM per macro-batch.

The XLA path (ops/segmented.py) streams each macro-batch several times —
bitmap words unpack into a row-length bool mask, limbs stack into an [n, L]
matrix (or re-slice per chunk), and the one-hot matmuls read it all back.
This module fuses the whole row pipeline into ONE Pallas grid over row
tiles, so each input byte is read exactly once:

  tile load:   dict codes in STORAGE dtype (int8 stays int8 in HBM),
               range-index prefix-bitmap WORDS ([T/32] uint32, unpacked
               in-register), optional predicate codes
  tile math:   all in int32 with rows along LANES ((1, C) row vectors):
               dictionary-code range predicate, 8-bit-limb extraction
               (two's-complement int32 / signed-magnitude int64 halves),
               group-major two-level one-hot (A [Hp, C], B [W, C]) pair
               shared by every limb; the narrower of the two carries the
               limbs, its L weighted copies stacked into one [L * S, C]
               operand, and ONE bf16 X.Y^T MXU matmul a chunk meets the
               stack with the other one-hot for all L limb columns at once
               (operands are integers <= 255: exact)
  tile store:  int32 accumulation into a VMEM-resident block as wide as
               that matmul's result ([L * S, F] or [F, L * S]), revisited
               across the tiles of one "super-segment"

Exactness contract (matches segmented.fused_group_tables bit-for-bit on
integer kinds): every limb is < 256 so each per-chunk f32 dot accumulates
< 255 * _TILE_CHUNK < 2^24 (exact); chunks add into int32 where one
super-segment covers <= 2^23 rows so |sum| <= 255 * 2^23 < 2^31 (exact); the per-super
int32 tables recombine OUTSIDE the kernel in f64 with the limb scales —
TPU Pallas has no f64, and the recombine is table-sized anyway.  Float
kinds (f32_sum/f32_sumsq) are NOT eligible: f32 accumulation over 2^23-row
supers would lose vs the XLA path's per-chunk f64 combine, so the plan-time
dispatch keeps floats on the XLA path (pallas_supported).

Backend selection is a PLAN-TIME decision (scan_backend): "pallas" on TPU,
"xla" elsewhere, overridable with PINOT_TPU_SCAN_BACKEND=pallas|xla|
interpret — "interpret" runs this same kernel through the Pallas
interpreter so tier-1 exercises it under JAX_PLATFORMS=cpu.

Also here: merge_sparse_tables, the device-side cross-launch merge for the
sparse group-by path (fixed-slot tables merged in-graph; see the function
docstring) — jnp-only, so it runs on every backend.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pinot_tpu.ops import segmented as _seg
from pinot_tpu.segment import packing

from jax.experimental import pallas as pl

# Rows per grid step.  XLA tiles a 1-D 32-bit HBM array by 1024 elements and
# a kernel block must be whole tiles, so the narrowest packed operand — the
# range-index bitmap, 32 rows per word — sets the floor: 1024 words = 2^15
# rows.  It is also one block of a bit-packed forward index, so a packed
# key's tile holds each lane's rows as one run of whole sublane rows.
_TILE = packing.BLOCK_ROWS
# Rows per in-kernel chunk: the [Hp, chunk] / [W, chunk] one-hot working set
# (Hp <= 128 sublanes) stays a few MB under the 16MB VMEM budget, and the
# stack of weighted one-hots is never built there (Mosaic folds the select
# into the MXU's masked operands).  2048 measured 8-10 % slower (PR 45).
_TILE_CHUNK = 4096
# Grid steps per int32 accumulator "super-segment": 256 * 2^15 = 2^23 rows,
# so a per-limb super sum is <= 255 * 2^23 < 2^31 - 1 (int32 exact).
_SUPER_TILES = 256

_W = _seg._W  # two-level decomposition lane width (code = hi * 64 + lo)
_W_SHIFT = _W.bit_length() - 1  # _W is a power of two: hi = code >> shift

# Pallas-eligible fused entry kinds: exact integer accumulation only (see
# module docstring for why floats stay on the XLA path).
PALLAS_KINDS = ("count", "int_sum", "int64_sum")

# same sentinel as query/planner.SPARSE_EMPTY_KEY (ops cannot import the
# query layer); all real packed keys are >= 0 so int64 max never collides
SPARSE_EMPTY_KEY = np.int64(np.iinfo(np.int64).max)


SCAN_BACKENDS = ("pallas", "xla", "interpret")


@functools.lru_cache(maxsize=None)
def scan_backend() -> str:
    """Plan-time scan-backend selector, part of every plan-cache key.

    "pallas" on a real TPU backend, "xla" everywhere else.  Env override
    PINOT_TPU_SCAN_BACKEND in {pallas, xla, interpret}: "interpret" routes
    plans through this kernel under the Pallas interpreter (CPU tests, the
    bench smoke gate).  A forced value that cannot be honoured — an unknown
    name, or "pallas" where the backend is not a TPU (Mosaic compiles for
    nothing else) — raises instead of degrading to another backend.
    lru_cached like accum_policy — tests that flip the env var must
    scan_backend.cache_clear()."""
    forced = os.environ.get("PINOT_TPU_SCAN_BACKEND", "").strip().lower()
    on_tpu = jax.default_backend() == "tpu"
    if not forced:
        return "pallas" if on_tpu else "xla"
    if forced not in SCAN_BACKENDS:
        raise ValueError(
            f"PINOT_TPU_SCAN_BACKEND={forced!r}: expected one of {SCAN_BACKENDS}"
        )
    if forced == "pallas" and not on_tpu:
        raise RuntimeError(
            "PINOT_TPU_SCAN_BACKEND=pallas needs a TPU backend, found "
            f"{jax.default_backend()!r}; use 'interpret' to run the kernel off the chip"
        )
    return forced


def pallas_supported(entries, num_groups: int) -> bool:
    """Can fused_group_tables_pallas compute these entries exactly?

    Integer-exact kinds only, group table narrow enough for the one-hot
    matmul (the same _MATMUL_MAX_GROUPS ceiling as the XLA matmul path)."""
    if num_groups < 1 or num_groups > _seg._MATMUL_MAX_GROUPS:
        return False
    for kind, values, _mask, _lp in entries:
        if kind not in PALLAS_KINDS:
            return False
        if kind == "int_sum" and not (
            jnp.issubdtype(values.dtype, jnp.integer) and values.dtype.itemsize <= 4
        ):
            return False
        if kind == "int64_sum" and values.dtype != jnp.int64:
            return False
    return True


def _stack_streams(rows: int, fixed_rows: int) -> bool:
    """Is the stack of `rows` weighted one-hot rows the chunk matmul's LEFT
    operand, against the `fixed_rows` (<= 128) of the shared one-hot?

    The MXU holds 128 rows of a matmul's right operand a pass and streams
    the whole left operand through each; a streamed row costs about three
    times a held one on a v5e (its f32 results are popped and added on the
    VPU: the MXU keeps no sum across the chunk's 128-row slices).  So the
    side that makes fewer row-passes streams: the stack where it is small
    against the shared one-hot (a lone COUNT over a wide table), the shared
    one-hot against ceil(rows / 128) held tiles of the stack otherwise.
    Measured on the chip over the benchmark's plans, PERF.md section 6
    (PR 45): each side wins or ties where this picks it."""
    return rows <= -(-rows // 128) * fixed_rows


def _lane_unpack(w, bits: int):
    """In-register unpack of INTERLEAVED lanes: a (1, rows * bits // 32) row
    of int32 words -> a (1, rows) row of int32 lanes, lane l of word i
    covering row i * (32 // bits) + l.

    That is the range-index bitmap's layout (bits=1, one bool per lane:
    query/filter.eval_bitmap); a bit-packed forward index is block-planar
    and needs no lane shuffle (_scan_chunk's key_row).  Each word is
    repeated along the lane axis and shifted against a lane iota, so the
    array stays 2-D throughout (Mosaic refuses the rank-changing reshape a
    [words, lanes] -> [rows] unpack needs) — the packed word tile is the
    only HBM read and the widened lanes never leave registers/VMEM."""
    f = 32 // bits
    x = jnp.repeat(w, f, axis=1)
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = (lane & np.int32(f - 1)) * np.int32(bits)
    return lax.shift_right_logical(x, shift) & np.int32((1 << bits) - 1)


def _as_i32_words(words):
    """uint32 packed words -> the same bits as int32: the TPU kernel does
    all of its integer math in int32 (Mosaic has no uint32 -> f32 cast)."""
    return lax.bitcast_convert_type(words.astype(jnp.uint32), jnp.int32)


def fused_group_tables_pallas(
    entries,
    codes,
    num_groups: int,
    *,
    mask_words=None,
    code_pred: Optional[Tuple[Any, int, int]] = None,
    codes_packed: Optional[Tuple[Any, int]] = None,
    interpret: bool = False,
):
    """Pallas twin of segmented.fused_group_tables for integer kinds.

    entries: list of (kind, values, mask, limb_plan) with kind in
    PALLAS_KINDS.  mask_words: optional packed uint32 filter bitmap
    ([n // 32], bit r of word w covers row 32*w + r — the range-index
    word-slice layout of query/filter.eval_bitmap) ANDed into every entry
    mask IN-REGISTER, so the row-length bool mask never exists in HBM.
    code_pred: optional (codes_array, lo, hi) dictionary-code range
    predicate, likewise fused.  codes_packed: optional (words, code_bits)
    bit-packed forward index of the key column (segment/packing.py: whole
    blocks, so the words already cover the padded row count); the kernel
    streams the uint32 word tiles, one block a tile, and shifts each
    chunk's codes out of its lane in-register, so the key's HBM traffic is
    its PACKED byte count.  Returns
    f64[num_groups] tables in entry order, bit-identical to the XLA path
    (both are exact integer sums).

    Rows are padded to a _TILE multiple when needed (padding carries
    mask=False, so padded rows contribute exactly nothing); 32-aligned
    macro-batch widths make the engine's hot path pad-free."""
    n = int(codes.shape[0])
    if mask_words is not None and n % 32:
        raise ValueError("mask_words requires a 32-aligned row count")
    if not pallas_supported(entries, num_groups):
        raise ValueError("entries not eligible for the Pallas fused scan")

    T = _TILE
    n_tiles = max(1, -(-n // T))
    n_super = -(-n_tiles // _SUPER_TILES)
    H = -(-num_groups // _W)
    Hp = -(-H // 8) * 8  # pad the sublane dim for TPU tiling

    # TPU Pallas is a 32-bit world and the package runs with jax_enable_x64
    # on: every scalar the kernel or an index map touches is an explicit
    # np.int32 (a Python int would trace as a weak int64 that Mosaic cannot
    # convert), and the block index maps stay in int32 end to end.
    def _tile_map(i):
        return (i,)

    def _word_tile_map(i):
        return (i, np.int32(0))

    def _super_map(i):
        z = np.int32(0)
        return (lax.div(i, np.int32(_SUPER_TILES)), z, z)

    # inputs[k] covers rows_per[k] rows per element: 1 for a row-length
    # operand, 32 // bits for packed words
    inputs: List[Any] = []
    rows_per: List[int] = []
    key_bits = None
    if codes_packed is not None:
        kw, key_bits = codes_packed
        key_bits = int(key_bits)
        if int(kw.shape[0]) != packing.packed_words(n, key_bits):
            raise ValueError("codes_packed must be the whole blocks of its rows")
        inputs.append(_as_i32_words(kw))
        rows_per.append(32 // key_bits)
    else:
        inputs.append(codes)
        rows_per.append(1)
    ix_of: Dict[int, int] = {}

    @jax.named_scope("scan_operands")
    def _operand(arr) -> int:
        k = id(arr)
        if k not in ix_of:
            # bool refs are not a VMEM type: masks ride as int8 (the cast
            # fuses into the XLA producer of the mask)
            inputs.append(arr.astype(jnp.int8) if arr.dtype == jnp.bool_ else arr)
            rows_per.append(1)
            ix_of[k] = len(inputs) - 1
        return ix_of[k]

    words_ix = None
    if mask_words is not None:
        inputs.append(_as_i32_words(mask_words))
        rows_per.append(32)
        words_ix = len(inputs) - 1
    pred_plan = None
    if code_pred is not None:
        pc, plo, phi = code_pred
        pred_plan = (_operand(pc), np.int32(plo), np.int32(phi))

    halves_of: Dict[int, Tuple[Any, Any]] = {}

    @jax.named_scope("scan_operands")
    def _halves(arr):
        """int32 (lo, hi) halves of an int64 column, split OUTSIDE the
        kernel — TPU Pallas has no 64-bit row ops; the bitcast is a cheap
        elementwise pass and the kernel reads the halves once."""
        k = id(arr)
        if k not in halves_of:
            h = lax.bitcast_convert_type(arr, jnp.int32)
            lo_ix = _seg._i64_low_half_index()
            halves_of[k] = (h[..., lo_ix], h[..., 1 - lo_ix])
        return halves_of[k]

    plans: List[Tuple] = []  # (kind, mask_ix, value_ixs, limb_plan, col0)
    scales_per_entry: List[List[float]] = []
    col = 0
    for kind, values, mask, limb_plan in entries:
        m_ix = _operand(mask)
        if kind == "count":
            plans.append(("count", m_ix, (), None, col))
            scales = [1.0]
            col += 1
        elif kind == "int_sum":
            n_limbs, signed = limb_plan if limb_plan is not None else (4, True)
            plans.append(("int_sum", m_ix, (_operand(values),), (n_limbs, signed), col))
            scales = [float(1 << (8 * i)) for i in range(n_limbs)]
            if signed:
                scales.append(-float(1 << (8 * n_limbs)))
            col += n_limbs + (1 if signed else 0)
        else:  # int64_sum: signed-magnitude limbs (see segmented._int64_signed_limbs)
            nl = limb_plan if limb_plan is not None else 8
            lo_arr, hi_arr = _halves(values)
            plans.append(("int64_sum", m_ix, (_operand(lo_arr), _operand(hi_arr)), nl, col))
            scales = [float(1 << (8 * i)) for i in range(nl)]
            col += nl
        scales_per_entry.append(scales)
    L = col
    # the limb weights ride the narrower one-hot (S sublanes a column), the
    # other one (F sublanes) meets all L columns as it is; (L, Hp) alone say
    # which and how the two are laid into the chunk's one matmul
    weigh_a = Hp <= _W
    S, F = (Hp, _W) if weigh_a else (_W, Hp)
    C = _TILE_CHUNK
    stack_lhs = _stack_streams(L * S, F)

    with jax.named_scope("scan_operands"):
        if n % T:
            # padding carries mask=False / zero words, so it contributes nothing
            # (a packed key's words are whole blocks already: nothing to add)
            inputs = [
                jnp.pad(a, (0, n_tiles * T // f - a.shape[0])) for a, f in zip(inputs, rows_per)
            ]
        # packed words ride as [words / 128, 128]: for a 32-bit array that is the
        # same bytes as the 1-D HBM tiling, and it makes the words of chunk c
        # whole sublane rows (a 1-D block can only be sliced by 1024s)
        inputs = [a if f == 1 else a.reshape(-1, 128) for a, f in zip(inputs, rows_per)]
    in_specs = [
        pl.BlockSpec((T,), _tile_map)
        if f == 1
        else pl.BlockSpec((T // f // 128, 128), _word_tile_map)
        for f in rows_per
    ]

    i32 = jnp.int32
    zero, one, byte = np.int32(0), np.int32(1), np.int32(0xFF)
    super_tiles = np.int32(_SUPER_TILES)

    def scan_kernel(*refs):
        out_ref = refs[-1]
        i = pl.program_id(0)

        @pl.when(lax.rem(i, super_tiles) == zero)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        def body(c, carry):
            _scan_chunk(refs, out_ref, c)
            return carry

        # jnp (not np/Python) bounds: a concrete trip count would lower to a
        # scan whose counter is int64 under x64
        lax.fori_loop(jnp.int32(0), jnp.int32(T // C), body, jnp.int32(0))

    def _scan_chunk(refs, out_ref, c):
        """Chunk c of the tile: the tile is as wide as the widest packed
        operand's HBM tiling demands, the chunk as narrow as the [Hp, C]
        one-hot working set needs to stay in VMEM."""

        def row(ix):
            # a [C] slice of the block as a (1, C) int32 row: ROWS RUN ALONG
            # LANES, so per-row values broadcast over the one-hot sublanes
            # for free and the contraction is the MXU-native A.B^T form
            start = pl.multiple_of(c * np.int32(C), C)
            return refs[ix][pl.ds(start, C)].astype(i32)[None, :]

        def word_rows(ix, first, r: int):
            # r whole sublane rows of the [.., 128] word block, laid end to
            # end as a (1, r * 128) row
            w = refs[ix][pl.ds(first, r), :]
            return jnp.concatenate([w[k:k + 1, :] for k in range(r)], axis=1)

        def key_row(bits: int):
            # block-planar lanes (segment/packing.py): the tile is one
            # block, lane l of its words holds rows [l, l + 1) * T / f, so
            # the chunk's C codes sit in ONE lane of C whole words: a shift
            # of C / 128 sublane rows, no shuffle along lanes
            per = np.int32(T * bits // 32 // C)  # chunks per lane run
            lane, part = lax.div(c, per), lax.rem(c, per)
            w = word_rows(0, part * np.int32(C // 128), C // 128)
            shifted = lax.shift_right_logical(w, lane * np.int32(bits))
            return shifted & np.int32((1 << bits) - 1)

        with jax.named_scope("lane_unpack"):
            ki = key_row(key_bits) if key_bits is not None else row(0)
            base = None
            if words_ix is not None:
                # the chunk's C / 32 bitmap words, interleaved lanes
                r = C // 32 // 128
                base = _lane_unpack(word_rows(words_ix, c * np.int32(r), r), 1) != zero
        if pred_plan is not None:
            with jax.named_scope("predicate"):
                p_ix, plo, phi = pred_plan
                pc = row(p_ix)
                pm = (pc >= plo) & (pc < phi)
                base = pm if base is None else base & pm

        # one (A, B) one-hot pair shared by EVERY limb column of the chunk —
        # the same sharing that makes the fused XLA scan 3x faster than
        # per-table scans, now also sharing the single HBM read.  Both are
        # group-major ([Hp, C] / [W, C]); codes are >= 0 so shift/mask is
        # the hi/lo split.
        a_hot = lax.broadcasted_iota(i32, (Hp, C), 0) == (ki >> np.int32(_W_SHIFT))
        b_hot = lax.broadcasted_iota(i32, (_W, C), 0) == (ki & np.int32(_W - 1))
        # the per-row limb weights ride whichever one-hot is narrower; every
        # operand value is an integer of magnitude <= 255, exact in bf16
        hot, other = (a_hot, b_hot) if weigh_a else (b_hot, a_hot)
        fixed = other.astype(jnp.float32).astype(jnp.bfloat16)

        # value transform: each entry's masked values as 8-bit limb rows,
        # in column order
        limb_rows = []
        with jax.named_scope("value_transform"):
            for kind, m_ix, v_ixs, lp, _col0 in plans:
                m = row(m_ix) != zero
                if base is not None:
                    m = m & base
                if kind == "count":
                    limb_rows.append(jnp.where(m, one, zero))
                elif kind == "int_sum":
                    n_limbs, signed = lp
                    vm = jnp.where(m, row(v_ixs[0]), zero)
                    # arithmetic shift then mask == the two's-complement byte
                    limb_rows += [(vm >> np.int32(8 * k)) & byte for k in range(n_limbs)]
                    if signed:
                        limb_rows.append(jnp.where(vm < zero, one, zero))
                else:  # int64_sum: signed-magnitude limbs of the (lo, hi) halves
                    lo_h = row(v_ixs[0])
                    hi_h = row(v_ixs[1])
                    neg = hi_h < zero
                    alo = jnp.where(neg, -lo_h, lo_h)  # wrapping: ~lo + 1
                    ahi = jnp.where(neg, ~hi_h + jnp.where(lo_h == zero, one, zero), hi_h)
                    sgn = jnp.where(m, jnp.where(neg, np.int32(-1), one), zero)
                    limb_rows += [
                        (((alo if k < 4 else ahi) >> np.int32(8 * (k % 4))) & byte) * sgn
                        for k in range(lp)
                    ]

        # ONE contraction for all of the plan's limb columns: their weighted
        # one-hots laid one under the other ([L * S, C]) meet the shared
        # one-hot in a single MXU matmul over the chunk's rows
        with jax.named_scope("onehot_accumulate"):
            stacked = jnp.concatenate(
                [jnp.where(hot, w.astype(jnp.float32), np.float32(0)) for w in limb_rows],
                axis=0,
            ).astype(jnp.bfloat16)
            lhs, rhs = (stacked, fixed) if stack_lhs else (fixed, stacked)
            s = lax.dot_general(
                lhs, rhs, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            out_ref[0] = out_ref[0] + s.astype(i32)

    # The name is the HLO instruction's and so the device trace's: the dense
    # one-hot scan, its limb columns and its table height in sublanes.  It
    # keeps the `kernel` prefix the benchmark's scan_kernel_ms pattern reads.
    out_block = (L * S, F) if stack_lhs else (F, L * S)
    out = pl.pallas_call(
        scan_kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1,) + out_block, _super_map),
        out_shape=jax.ShapeDtypeStruct((n_super,) + out_block, jnp.int32),
        interpret=bool(interpret),
        name=f"kernel_dense_onehot_l{L}_h{Hp}",
    )(*inputs)

    # cross-super recombine in f64 (table-sized): every per-super value is
    # an exact integer < 2^31, every partial sum stays < 2^53 under the
    # same contract as the XLA path's per-chunk f64 combine
    with jax.named_scope("recombine_f64"):
        acc = out.astype(jnp.float64).sum(axis=0)
        # -> [L, S, F], column-major as the kernel stacked it; a group is
        # hi * _W + lo, hi along the A one-hot's sublanes
        acc = acc.reshape(L, S, F) if stack_lhs else acc.reshape(F, L, S).transpose(1, 2, 0)
        if not weigh_a:
            acc = acc.transpose(0, 2, 1)
        flat = acc.reshape(L, Hp * _W)[:, :num_groups]
        tables = []
        for (kind, _m, _v, _lp, col0), scales in zip(plans, scales_per_entry):
            t = flat[col0] if scales[0] == 1.0 else flat[col0] * scales[0]
            for j, s in enumerate(scales[1:], start=1):
                t = t + flat[col0 + j] * s
            tables.append(t)
    return tables


# ---------------------------------------------------------------------------
# Device-side sparse group-by cross-launch merge
# ---------------------------------------------------------------------------
def merge_sparse_tables(
    uniq,
    partials: Sequence[Dict[str, Any]],
    num_slots: int,
    field_ops: Sequence[Dict[str, str]],
    order_spec: Optional[Tuple[int, str, bool]] = None,
    may_trim: bool = True,
):
    """Merge stacked fixed-slot sparse group tables ON DEVICE: replaces the
    host numpy fold of sparse_tables_to_result for
    the macro-batched path, so cross-launch combining is part of the graph
    and only FINAL [num_slots] tables ever cross PCIe.

    uniq: [M] int64 packed keys (SPARSE_EMPTY_KEY padding), the
    concatenation of every launch's per-device [K] key tables (M = B*ndev*K).
    partials: per-agg {field: [M]} stacked the same way.  field_ops: per-agg
    {field: "add"|"min"|"max"} (functions.FIELD_COMBINE, passed in because
    ops cannot import the query layer).  order_spec: (agg index, order
    FIELD name, ascending) when an ORDER BY-aware trim applies — the
    device analog of executor._order_trim_select: rank by the merged order
    value (empty/NaN groups last), tie-break by packed key, keep the top
    num_slots, and emit survivors in ascending key order so downstream
    decode matches the host merge byte-for-byte.  may_trim=False is the
    caller's static promise that the distinct keys always fit num_slots
    (slots cover the whole key space): the merged groups already sit in
    ascending key order, so the ranking and compaction sorts — 64-bit
    multi-key sorts, minutes of TPU compile each — are skipped.

    The merge is sort-based over the SAME fixed-slot contract as the
    per-launch kernel (sort keys -> segment starts -> running group id ->
    scatter-combine), not a literal probed hash table: table-sized lax.sort
    is TPU-native and exact, where open-addressing probe loops serialize.
    Everything here is [M]-sized (never row-length)."""
    with jax.named_scope("merge_sparse_tables"):
        return _merge_sparse_tables(uniq, partials, num_slots, field_ops, order_spec, may_trim)


def _merge_sparse_tables(uniq, partials, num_slots, field_ops, order_spec, may_trim):
    M = int(uniq.shape[0])
    uniq = uniq.astype(jnp.int64).reshape(-1)
    iota = jnp.arange(M, dtype=jnp.int32)
    skey, perm = lax.sort((uniq, iota), num_keys=1)
    valid = skey != SPARSE_EMPTY_KEY
    prev = jnp.concatenate([jnp.full((1,), np.int64(-1), skey.dtype), skey[:-1]])
    is_start = valid & (skey != prev)
    seg_id = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    # empty slots fold into an overflow slot M (sliced off): add-fields
    # carry 0 there, min/max carry their identity, so it absorbs harmlessly
    slot = jnp.where(valid, seg_id, np.int32(M))

    merged: List[Dict[str, Any]] = []
    for fops, p in zip(field_ops, partials):
        q: Dict[str, Any] = {}
        for fname, comb in fops.items():
            x = p[fname].reshape(-1)[perm]
            if comb == "add":
                q[fname] = jnp.zeros((M + 1,), x.dtype).at[slot].add(x)
            elif comb == "min":
                base = jnp.full((M + 1,), jnp.asarray(np.inf, x.dtype))
                q[fname] = base.at[slot].min(x)
            else:
                base = jnp.full((M + 1,), jnp.asarray(-np.inf, x.dtype))
                q[fname] = base.at[slot].max(x)
        merged.append(q)

    gslot = jnp.where(is_start, seg_id, np.int32(M))
    gkey = (
        jnp.full((M + 1,), SPARSE_EMPTY_KEY, jnp.int64)
        .at[gslot]
        .set(jnp.where(is_start, skey, SPARSE_EMPTY_KEY))
    )
    if not may_trim:
        return gkey[:num_slots], [{f: t[:num_slots] for f, t in q.items()} for q in merged]
    phantom = gkey == SPARSE_EMPTY_KEY  # slots past the last real group
    if order_spec is None:
        # lowest packed keys win — the deterministic numGroupsLimit trim
        ovk = jnp.where(phantom, jnp.inf, 0.0)
    else:
        oi, field, asc = order_spec
        ov = merged[oi][field].astype(jnp.float64)
        cnt = merged[oi].get("count")
        if cnt is not None:
            # SUM/MIN/MAX over zero agg-mask rows is SQL NULL: rank last,
            # mirroring AggFunction.final's count>0 guard on the host
            ov = jnp.where(cnt.astype(jnp.float64) > 0, ov, jnp.nan)
        ovk = ov if asc else -ov
        ovk = jnp.where(jnp.isnan(ovk) | phantom, jnp.inf, ovk)
    slots = jnp.arange(M + 1, dtype=jnp.int32)
    _, _, ranked = lax.sort((ovk, gkey, slots), num_keys=2)
    selmask = jnp.zeros((M + 1,), bool).at[ranked[:num_slots]].set(True)
    outkey = jnp.where(selmask & ~phantom, gkey, SPARSE_EMPTY_KEY)
    okey, operm = lax.sort((outkey, slots), num_keys=1)
    out = [{f: t[operm][:num_slots] for f, t in q.items()} for q in merged]
    return okey[:num_slots], out
