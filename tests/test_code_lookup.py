"""ops/code_lookup.py: `table[codes]` read through a one-hot contraction.

The helper equals the gather bit for bit for bool, int32 and float32 tables
(negatives, the int32 bounds, NaN, -0.0, inf), at lengths around a table row
(128 entries), at both ends of the contracted range and one outside each (the
gather's side), for codes at both ends and from 4- / 8- / 16-bit packed lanes
(a 4-bit column's table is under the range: the helper leaves it the gather); in
the plain jnp form (what the CPU runs) and through the Pallas kernel under
the interpreter (what the chip runs).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu import ops
from pinot_tpu.ops import code_lookup as cl
from pinot_tpu.ops.segmented import _CONTRACT_MAX_TABLE, _CONTRACT_MIN_TABLE
from pinot_tpu.segment import packing

FORMS = {"jnp": "xla", "interpret": "interpret"}
LENGTHS = sorted({1, 5, _CONTRACT_MIN_TABLE - 1, _CONTRACT_MIN_TABLE, 127, 128, 129, 8192,
                  _CONTRACT_MAX_TABLE, _CONTRACT_MAX_TABLE + 1})
ROWS = 5_000


@pytest.fixture(params=list(FORMS))
def form(request, monkeypatch):
    monkeypatch.setenv("PINOT_TPU_SCAN_BACKEND", FORMS[request.param])
    ops.scan_backend.cache_clear()
    yield request.param
    monkeypatch.undo()
    ops.scan_backend.cache_clear()


def _table(dtype, n, rng):
    if dtype == "bool":
        t = rng.random(n) < 0.5
        t[[0, -1]] = [True, False] if n > 1 else [True]
        return t
    if dtype == "int32":
        t = rng.integers(-(2**31), 2**31 - 1, n, dtype=np.int64).astype(np.int32)
        edge = [np.iinfo(np.int32).min, -1, 0, 255, 256, np.iinfo(np.int32).max]
    else:
        t = rng.standard_normal(n).astype(np.float32)
        edge = [np.nan, -0.0, np.inf, -np.inf, np.float32(1e-45), 0.0]
    # the last entry first: the longest table has them all, a table of one the maximum / zero
    for at, v in zip([n - 1, 0, n // 2, n // 3, n // 5, n // 7], reversed(edge)):
        t[at] = v
    return t


def _bits(a):
    a = np.asarray(a)
    return a if a.dtype == np.bool_ else a.view(np.int32)


def _looked_up(table, codes):
    with cl.lookup_tally() as seen:  # a jit of its own: the form is read where the body is traced
        out = jax.jit(lambda t, c: cl.code_lookup(t, c))(jnp.asarray(table), jnp.asarray(codes))
    return np.asarray(out), seen


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("dtype", ["bool", "int32", "float32"])
def test_equals_the_gather_bit_for_bit(form, dtype, length):
    rng = np.random.default_rng([length, len(dtype)])
    table = _table(dtype, length, rng)
    codes = rng.integers(0, length, ROWS).astype(np.int32)
    codes[:3] = [0, length - 1, length // 2]
    got, seen = _looked_up(table, codes)
    assert got.dtype == table.dtype
    assert np.array_equal(_bits(got), _bits(table[codes]))
    contracted = _CONTRACT_MIN_TABLE <= length <= _CONTRACT_MAX_TABLE
    # past the range a 32-bit table's best form is the decoded column, which is staging's to give
    # (PR 49): one that reaches the helper all the same is gathered, and counted so
    beyond = cl.GATHERED if dtype == "bool" or length < _CONTRACT_MIN_TABLE else cl.RESIDENT
    assert cl.lookup_form(length, table.dtype) == (cl.CONTRACTED if contracted else beyond)
    assert seen == {cl.CONTRACTED: int(contracted), cl.GATHERED: int(not contracted), cl.RESIDENT: 0}


@pytest.mark.parametrize("bits", [4, 8, 16])
@pytest.mark.parametrize("dtype", ["bool", "int32"])
def test_codes_from_packed_lanes(form, dtype, bits):
    """The served path's codes: the lane unpack of a bit-packed forward
    index, more rows than a kernel tile and not a whole number of them."""
    rows = packing.BLOCK_ROWS + 4_321
    length = min(1 << bits, 8192) - 3
    rng = np.random.default_rng([bits, len(dtype)])
    table = _table(dtype, length, rng)
    codes = rng.integers(0, length, rows).astype(np.uint32)
    codes[[0, -1]] = [length - 1, 0]
    words = jnp.asarray(packing.pack_codes(codes, bits))

    @jax.jit
    def served(table, words):
        return cl.code_lookup(table, packing.unpack_codes_jnp(words, bits, rows))

    got = np.asarray(served(jnp.asarray(table), words))
    assert np.array_equal(_bits(got), _bits(table[codes]))


@pytest.mark.parametrize("table,codes_ndim,why", [
    (np.zeros(100, np.int64), 1, "a 64-bit table"),
    (np.zeros(100, np.float64), 1, "a 64-bit table"),
    (np.zeros(100, bool), 2, "a multi-value column's [rows, k] codes"),
    (np.zeros(11, np.int32), 1, "a table the chip's compiler turns into selects (SSB's lo_discount)"),
    (np.zeros(_CONTRACT_MAX_TABLE + 1, bool), 1, "a bool table past the range: no column of its width to stage"),
    (np.zeros(_CONTRACT_MAX_TABLE + 1, np.int64), 1, "a 64-bit table past the range: 8 B a row decoded"),
    (np.zeros(_CONTRACT_MAX_TABLE + 1, np.int32), 2, "a multi-value column's codes over a long 32-bit table"),
])
def test_what_keeps_the_gather(table, codes_ndim, why):
    assert cl.lookup_form(len(table), table.dtype, codes_ndim) == cl.GATHERED, why
    codes = np.arange(12, dtype=np.int32).reshape((12,) if codes_ndim == 1 else (6, 2))
    got, seen = _looked_up(table + 1 if table.dtype != np.bool_ else ~table, codes)
    assert got.shape == codes.shape and got.all() and seen[cl.GATHERED] == 1


def test_a_tally_counts_its_own_block_alone():
    table, codes = jnp.zeros(100, bool), jnp.zeros(4, jnp.int32)
    with cl.lookup_tally() as outer:
        cl.code_lookup(table, codes)
        with cl.lookup_tally() as inner:
            cl.code_lookup(jnp.zeros(100, jnp.int64), codes)
        cl.code_lookup(table, codes)
        cl.tally(cl.RESIDENT)  # what transform.column_values says of a column it took decoded
    assert inner == {cl.CONTRACTED: 0, cl.GATHERED: 1, cl.RESIDENT: 0}
    assert outer == {cl.CONTRACTED: 2, cl.GATHERED: 0, cl.RESIDENT: 1}
    cl.code_lookup(table, codes)  # no tally open: counted nowhere, no error
    cl.tally(cl.RESIDENT)


@pytest.mark.parametrize("length,dtype,ndim,form", [
    (_CONTRACT_MAX_TABLE, np.int32, 1, cl.CONTRACTED),
    (_CONTRACT_MAX_TABLE + 1, np.int32, 1, cl.RESIDENT),
    (_CONTRACT_MAX_TABLE + 1, np.float32, 1, cl.RESIDENT),
    (327_680, np.int32, 1, cl.RESIDENT),  # lo_custkey's compiled length at SF10
    (_CONTRACT_MAX_TABLE + 1, np.bool_, 1, cl.GATHERED),
    (_CONTRACT_MAX_TABLE + 1, np.int64, 1, cl.GATHERED),
    (_CONTRACT_MAX_TABLE + 1, np.float64, 1, cl.GATHERED),
    (_CONTRACT_MAX_TABLE + 1, np.int32, 2, cl.GATHERED),
    (_CONTRACT_MIN_TABLE - 1, np.int32, 1, cl.GATHERED),
    (_CONTRACT_MIN_TABLE, np.float32, 1, cl.CONTRACTED),
])
def test_the_rule_is_of_the_length_the_dtype_and_the_rank(length, dtype, ndim, form):
    assert cl.lookup_form(length, dtype, ndim) == form
