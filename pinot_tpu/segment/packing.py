"""Bit-packed forward indexes: dictionary codes in 4/8/16-bit lanes.

Codes for a dictionary column with cardinality C need only
ceil(log2(C)) bits each; storing them at int32 (or even uint8/16)
width wastes HBM bandwidth on the scan hot path.  This module packs
codes into little-endian lanes inside uint32 words, in ONE layout,
block-planar:

    factor f = 32 // bits          lanes per word
    B = BLOCK_ROWS                 rows per block, B // f words per block
    block b, word j, lane l        covers row b * B + l * (B // f) + j
    code  = (word >> (bits * l)) & ((1 << bits) - 1)

so lane l of a block's words is a contiguous RUN of B // f rows, and the
unpack is f shifted copies of a block's words laid one after another: no
array with a minor dimension of f ever exists.  (The interleaved layout
this replaced, row = w * f + l, unpacks through a [words, f] view, and an
array whose minor dimension is 2, 4 or 8 is held by the TPU padded to 128
lanes: 384 MB written and read back for a 3 MB column.)  B = 2^15 is the
Pallas scan's row tile, so one kernel tile is one block, and the shortest
lane run (f = 8: 4,096 words) is whole (8, 128) tiles of 32-bit words: the
trace-time unpack views a block as [f, B // f // 128, 128] and XLA's
reshape back to row order is a bitcast.  The tail block is zero-padded
(zero is always a valid in-range lane, and consumers mask rows >= n), so
the word count is always a whole number of blocks: packed_words(n, bits).

Range-index bitmap words (bits=1: bit r of word w covers row 32*w + r)
are NOT this layout; they keep their own (query/filter.eval_bitmap,
ops/pallas_scan._lane_unpack).

Only power-of-two lane widths that divide 32 are used (4/8/16); a
column whose cardinality needs >16 bits stays unpacked (32 means "no
packing").  Multi-value columns stay unpacked too: their padding code
equals the cardinality, which may not fit the lane width chosen from
cardinality alone.

On disk a packed column's metadata carries LAYOUT_KEY = BLOCK_ROWS beside
`codeBits`.  Segments written before the stamp existed hold the
interleaved layout; unpack_interleaved reads them, once, at load
(segment.py), and nothing else in the tree knows that layout.
"""
from __future__ import annotations

import numpy as np

LANE_WIDTHS = (4, 8, 16)

# Rows per block.  A power of two; a multiple of 8 * 128 * 8 so that every
# lane run is whole (8, 128) tiles of 32-bit words at every lane width.
BLOCK_ROWS = 1 << 15

# column-metadata key that stamps the layout (value: BLOCK_ROWS)
LAYOUT_KEY = "codeBlockRows"


def lane_bits(cardinality: int) -> int:
    """Narrowest supported lane width for a dictionary of this size.

    Returns 32 when the column does not benefit (codes would need more
    than 16 bits), meaning "store unpacked".
    """
    for bits in LANE_WIDTHS:
        if cardinality <= (1 << bits):
            return bits
    return 32


def _factor(bits: int) -> int:
    if bits not in LANE_WIDTHS:
        raise ValueError(f"unsupported lane width: {bits}")
    return 32 // bits


def packed_words(n: int, bits: int) -> int:
    """Words that hold n rows: whole blocks, the tail block zero-padded."""
    return -(-int(n) // BLOCK_ROWS) * (BLOCK_ROWS // _factor(bits))


def _blocks(words_len: int, factor: int) -> int:
    run = BLOCK_ROWS // factor
    if words_len % run:
        raise ValueError(
            f"{words_len} packed words are not whole blocks of {run}: "
            "not the block-planar layout (see packing.unpack_interleaved)"
        )
    return words_len // run


def pack_codes(codes: np.ndarray, bits: int) -> np.ndarray:
    """Pack int codes into uint32 words along the LAST axis, `32 // bits`
    lanes per word, block-planar (module docstring).  [..., n] codes give
    [..., packed_words(n, bits)] words."""
    factor = _factor(bits)
    lead, n = codes.shape[:-1], int(codes.shape[-1])
    nb = -(-n // BLOCK_ROWS)
    lanes = np.zeros(lead + (nb * BLOCK_ROWS,), dtype=np.uint32)
    lanes[..., :n] = codes
    lanes = lanes.reshape(lead + (nb, factor, BLOCK_ROWS // factor))
    shifts = (np.arange(factor, dtype=np.uint32) * np.uint32(bits))[:, None]
    words = np.bitwise_or.reduce(lanes << shifts, axis=-2)
    return words.reshape(lead + (-1,)).astype(np.uint32, copy=False)


def _unpack(xp, words, bits: int, n: int):
    """The one unpack, for numpy and jax.numpy alike.  A block's lane run is
    viewed as [run // 128, 128], whole (8, 128) tiles, so the widened
    [.., blocks, f, run // 128, 128] array is the row-ordered result already:
    for the TPU the reshapes either side are bitcasts and the codes are
    written once, at 4 B a row."""
    factor = _factor(bits)
    lead = words.shape[:-1]
    nb = _blocks(int(words.shape[-1]), factor)
    w = xp.asarray(words, dtype=xp.uint32).reshape(lead + (nb, 1, BLOCK_ROWS // factor // 128, 128))
    shifts = (xp.arange(factor, dtype=xp.uint32) * xp.uint32(bits))[:, None, None]
    lanes = (w >> shifts) & xp.uint32((1 << bits) - 1)
    return lanes.reshape(lead + (nb * BLOCK_ROWS,))[..., :n]


def unpack_codes(words: np.ndarray, bits: int, n: int, dtype=np.uint32) -> np.ndarray:
    """Numpy inverse of pack_codes: the first n rows of the last axis."""
    return _unpack(np, words, bits, n).astype(dtype, copy=False)


def unpack_codes_jnp(words, bits: int, n: int, dtype=None):
    """Trace-time unpack with vectorized shifts (CPU/XLA fallback path).

    Unpacks along the LAST axis (1-D segment codes or [shards, words]
    stacked layouts alike).  `bits` and `n` (rows kept of the last axis)
    must be static; `words` may be a traced uint32 array.  Returns int32 by
    default — the width device readers expect from `.astype(jnp.int32)`
    anyway.

    Counts `scan.traced.lane_unpack`, once a column: under jit that is at
    trace time only, so a served window in which it moves has retraced.
    (It counts what was traced: XLA drops an unpack no reader uses, as when
    the Pallas scan takes a single key's words itself.)
    """
    import jax.numpy as jnp

    from pinot_tpu.utils.metrics import METRICS

    METRICS.counter("scan.traced.lane_unpack").inc()
    return _unpack(jnp, words, bits, n).astype(jnp.int32 if dtype is None else dtype)


def unpack_interleaved(words: np.ndarray, bits: int, n: int, dtype=np.uint32) -> np.ndarray:
    """Reader of the layout segments were written in before LAYOUT_KEY
    (lane l of word w covers row w * f + l).  For segment load alone."""
    factor = _factor(bits)
    shifts = (np.arange(factor, dtype=np.uint32) * np.uint32(bits))[None, :]
    lanes = (words.astype(np.uint32, copy=False)[:, None] >> shifts) & np.uint32((1 << bits) - 1)
    return lanes.reshape(-1)[:n].astype(dtype, copy=False)
