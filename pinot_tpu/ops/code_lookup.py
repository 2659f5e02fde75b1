"""table[codes] for a SMALL table, as a one-hot contraction on the MXU.

The chip charges an index by row 7-9 ns a row whatever the table's size
(11.7-12.9 ms a 1.5M-row segment, PERF.md PR 41-43): a gather's cost is the
rows', not the table's.  A table indexed by a dictionary code is small, so
the same values come out of two steps that index nothing.  For a table of
T = H x 128 entries and a chunk of C rows, hi = code >> 7, lo = code & 127:

  1. picked[128, C] = table2d^T[128, H] . onehot(hi)[H, C]   (the MXU)
     every row gets the 128 entries of ITS table row: one nonzero term a
     sum, so the f32 accumulate is exact;
  2. out[C] = sum over sublanes of where(iota128 == lo, picked, 0)
     the entry of its lane; rows stay on lanes, the result is lane-dense.

Operands are integers of magnitude <= 255, exact in bf16 (the contract of
ops/pallas_scan.py).  A bool table is one such limb; a 32-bit table (INT,
and FLOAT through its bit pattern) is four byte limbs stacked into ONE
[4 x 128, H] operand and recombined with shifts after step 2, so the result
is table[codes] bit for bit, NaN and -0.0 included.  The limb count is the
table's DTYPE's, never its values': segments built apart keep one program.

The contraction's work grows with T (2 x T flop a row a limb), the gather's
does not, and a gather of up to 64 entries is no gather on the chip (the
compiler turns it into selects): tables outside segmented._CONTRACT_MIN_TABLE
.. _CONTRACT_MAX_TABLE, 64-bit tables and a multi-value column's [rows, k]
codes keep `table[codes]`.

Past _CONTRACT_MAX_TABLE neither form is cheap, and a 32-bit table that long
needs no index at all: its codes take more than 16 bits, so they ride as
int32 (segment/packing.py LANE_WIDTHS stop at 16), 4 B a row, and the decoded
values are exactly as wide.  That is the third form, RESIDENT: staging hands
the column out decoded, made once a segment and device
(ImmutableSegment.to_device `value_columns`), and the kernel streams it.
lookup_form names it; it is staging's to give and the plan's to ask for
(planner.QueryPlanning.value_columns), so a table of that range that reaches
code_lookup all the same (the stacked engine's, a dictionary function's
derived table) is gathered.

Which form a lookup was traced with is tallied (lookup_tally) for the plan
that traced it: the `dispatch` span's contractedLookups / gatheredLookups /
residentLookups.

Forms: the Pallas kernel on the chip (scan_backend() "pallas"; "interpret"
runs it through the interpreter), the plain jnp form elsewhere.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from pinot_tpu.ops.segmented import _CONTRACT_MAX_TABLE, _CONTRACT_MIN_TABLE
from pinot_tpu.segment import packing

_LANES = 128  # entries a table row: code = hi * 128 + lo
_LANE_SHIFT = _LANES.bit_length() - 1
_TILE = packing.BLOCK_ROWS  # rows a grid step (pallas_scan._TILE)
# Rows a chunk, in the kernel and in the jnp form: the [4 x 128, C] f32 result
# of step 1 is 4 MB at 2048, inside the kernel's 16 MB of VMEM with the
# one-hot beside it; a one-limb table's is 2 MB at 4096.
_CHUNK = {1: 4096, 4: 2048}
# Table rows (hi values) a matmul of step 1 contracts: a longer table's
# one-hot is built and met a block at a time
_K_BLOCK = 256

CONTRACTED, GATHERED, RESIDENT = "contracted", "gathered", "resident"

_tally = threading.local()


@contextlib.contextmanager
def lookup_tally() -> Iterator[Dict[str, int]]:
    """Counts, by form, the lookups traced on this thread inside the block
    (code_lookup's calls, and the columns read decoded: tally): what a plan's
    kernel wraps its body in (trace time only)."""
    seen = {CONTRACTED: 0, GATHERED: 0, RESIDENT: 0}
    outer = getattr(_tally, "seen", None)
    _tally.seen = seen
    try:
        yield seen
    finally:
        _tally.seen = outer


def tally(form: str) -> None:
    """One lookup of `form` for the tally open on this thread, if any."""
    seen = getattr(_tally, "seen", None)
    if seen is not None:
        seen[form] += 1


def _limbs_of(dtype) -> Optional[int]:
    """Byte limbs a table of `dtype` rides, None where it keeps the gather."""
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return 1
    if dtype in (np.dtype(np.int32), np.dtype(np.float32)):
        return 4
    return None


def lookup_form(table_len: int, dtype, codes_ndim: int = 1) -> str:
    """The best form of `table[codes]` for a table of these static
    properties (its COMPILED length): RESIDENT is the decoded column's,
    which staging gives where a plan asks; code_lookup has the other two."""
    limbs = _limbs_of(dtype)
    if codes_ndim != 1 or limbs is None or table_len < _CONTRACT_MIN_TABLE:
        return GATHERED
    if table_len <= _CONTRACT_MAX_TABLE:
        return CONTRACTED
    return RESIDENT if limbs == 4 else GATHERED


def code_lookup(table, codes):
    """`table[codes]`, bit for bit, for in-bounds int32 `codes` (a
    dictionary's): contracted where lookup_form says so, gathered else."""
    form = lookup_form(int(table.shape[0]), table.dtype, codes.ndim)
    if form != CONTRACTED:
        tally(GATHERED)  # a table of RESIDENT's range that was not staged decoded is indexed
        return table[codes]
    tally(CONTRACTED)
    from pinot_tpu import ops  # the package's name for it: what the planner reads and tests steer

    with jax.named_scope("code_lookup"):
        backend = ops.scan_backend()
        operand = _limb_operand(table)
        codes = jnp.minimum(codes, np.int32(table.shape[0] - 1))  # the gather's clamp
        if backend == "xla":
            bits = _lookup_jnp(operand, codes)
        else:
            bits = _lookup_pallas(operand, codes, interpret=backend == "interpret")
        if table.dtype == jnp.bool_:
            return bits != 0
        return lax.bitcast_convert_type(bits, table.dtype)


def _limb_operand(table):
    """The table as step 1's left operand, bf16 [limbs x 128, Hp]: row
    l * 128 + lo, column hi = byte l of table[hi * 128 + lo]; Hp = the
    table's rows of 128, padded to whole sublane tiles and, past one, to
    whole blocks of _K_BLOCK (zeros: no code reaches them).  Table-sized
    work, inside the program."""
    t = int(table.shape[0])
    whole = _K_BLOCK if t > _K_BLOCK * _LANES else 16
    hp = -(-t // (_LANES * whole)) * whole
    bits = table.astype(jnp.int32) if table.dtype == jnp.bool_ else lax.bitcast_convert_type(table, jnp.int32)
    t2 = jnp.pad(bits, (0, hp * _LANES - t)).reshape(hp, _LANES).T  # [lo, hi]
    stacked = jnp.concatenate(
        [(t2 >> np.int32(8 * k)) & np.int32(0xFF) for k in range(_limbs_of(table.dtype))], axis=0
    )
    return stacked.astype(jnp.float32).astype(jnp.bfloat16)


def _contract_chunk(operand, k):
    """Steps 1 and 2 for a (1, C) row of codes `k`; `operand` is
    _limb_operand's array or the kernel's ref of it.  -> (1, C) int32, the
    table entry's bits."""
    i32 = jnp.int32
    c = k.shape[1]
    limbs, hp = operand.shape[0] // _LANES, operand.shape[1]
    hi = k >> np.int32(_LANE_SHIFT)
    picked = None
    for k0 in range(0, hp, _K_BLOCK):
        kb = min(_K_BLOCK, hp - k0)
        hot = lax.broadcasted_iota(i32, (kb, c), 0) == (hi - np.int32(k0))
        part = lax.dot_general(
            operand[:, k0:k0 + kb], hot.astype(jnp.float32).astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        picked = part if picked is None else picked + part
    lane = lax.broadcasted_iota(i32, (_LANES, c), 0) == (k & np.int32(_LANES - 1))
    out = None
    for l in range(limbs):
        mine = jnp.where(lane, picked[l * _LANES:(l + 1) * _LANES, :], np.float32(0))
        byte = jnp.sum(mine, axis=0, keepdims=True).astype(i32)
        out = byte if out is None else out | (byte << np.int32(8 * l))
    return out


def _lookup_jnp(operand, codes):
    """The plain jnp form: a chunk's [limbs x 128, C] result at a time."""
    n = int(codes.shape[0])
    c = _CHUNK[operand.shape[0] // _LANES] * 8
    if n <= c:
        return _contract_chunk(operand, codes[None, :])[0]
    chunks = jnp.pad(codes, (0, -n % c)).reshape(-1, 1, c)
    return lax.map(lambda k: _contract_chunk(operand, k), chunks).reshape(-1)[:n]


def _lookup_pallas(operand, codes, interpret: bool = False):
    """The two steps in one Pallas grid over row tiles: a tile's codes are
    read once, its entries written once, the [limbs x 128, C] result of step
    1 never leaves VMEM."""
    n = int(codes.shape[0])
    limbs, hp = operand.shape[0] // _LANES, operand.shape[1]
    T, C = _TILE, _CHUNK[limbs]
    n_tiles = max(1, -(-n // T))
    if n % T:
        codes = jnp.pad(codes, (0, n_tiles * T - n))

    chunk = np.int32(C)  # an explicit int32: a Python int would trace as a weak int64 under x64

    def lookup_kernel(codes_ref, operand_ref, out_ref):
        def body(c, carry):
            start = pl.multiple_of(c * chunk, C)
            k = codes_ref[pl.ds(start, C)][None, :]
            out_ref[pl.ds(start, C)] = _contract_chunk(operand_ref, k)[0]
            return carry

        # jnp bounds: a Python trip count's counter would be int64 under x64
        lax.fori_loop(jnp.int32(0), jnp.int32(T // C), body, jnp.int32(0))

    out = pl.pallas_call(
        lookup_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((T,), lambda i: (i,)),
            pl.BlockSpec(operand.shape, lambda i: (np.int32(0), np.int32(0))),
        ],
        out_specs=pl.BlockSpec((T,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * T,), jnp.int32),
        interpret=bool(interpret),
        # the HLO instruction's name, and so the device trace's: byte limbs
        # and the table's rows of 128
        name=f"kernel_code_lookup_l{limbs}_h{hp}",
    )(codes, operand)
    return out[:n]
