"""The chip's scalar SUM (`ops.masked_sum` under `accum_policy() = "chunked32"`)
at difference 0 from Python integers.

The reduction sums 8-bit limbs WHOLE in int32 (255 * 2^23 < 2^31): no chunk
axis, no pad, no stack.  The limb sums of an int32 or narrower column meet in
int64; an int64 column's meet in f64, because its sum can pass 2^63.  What can
go wrong is a limb's sign, a narrow dtype's top byte, a column past one piece,
an empty column, a LONG sum past int64: each is a case here, steered as tests/test_ssb_templates_chip_path.py steers the
policy (it asks `jax.default_backend()`, which says cpu here).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.ops import segmented
from pinot_tpu.utils.metrics import METRICS

CHUNK = segmented._CHUNK
SEGMENT = 1_500_000  # a served segment: 22 chunks of the old form and a tail
DTYPES = {"int8": np.int8, "int16": np.int16, "int32": np.int32, "int64": np.int64}
ROWS = [0, 1, CHUNK - 1, CHUNK, CHUNK + 1, SEGMENT]
PATTERNS = ["int32_min", "int32_max", "minus_one", "mixed", "ssb"]


@pytest.fixture(autouse=True)
def chunked32(monkeypatch):
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")


def _column(pattern, dtype, n, rng):
    """`pattern` in `dtype`: the int32 extremes are the dtype's own where it is
    narrower (an int64 column holds int32's: sum(|v|) stays below 2^53)."""
    info = np.iinfo(dtype)
    lo, hi = max(info.min, -(1 << 31)), min(info.max, (1 << 31) - 1)
    if pattern == "int32_min":
        return np.full(n, lo, dtype)
    if pattern == "int32_max":
        return np.full(n, hi, dtype)
    if pattern == "minus_one":
        return np.full(n, -1, dtype)
    if pattern == "mixed":
        if dtype is np.int64:  # past int32 both ways
            lo, hi = -(1 << 32), (1 << 32) - 1
        return rng.integers(lo, hi, n, dtype=dtype, endpoint=True)
    # SSB's own: lo_quantity in an int8, a supply cost in an int16, lo_extendedprice * lo_discount wider
    if dtype is np.int8:
        return rng.integers(1, 51, n, dtype=dtype)
    if dtype is np.int16:
        return rng.integers(1, 20_001, n, dtype=dtype)
    return (rng.integers(1, 51, n) * rng.integers(90_000, 200_001, n) // 100 * rng.integers(0, 11, n)).astype(dtype)


def _exact(vals, mask):
    return int(vals[mask].astype(object).sum()) if mask.any() else 0


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_masked_sum_is_exact(dtype, pattern, rows):
    rng = np.random.default_rng(38)
    vals = _column(pattern, DTYPES[dtype], rows, rng)
    mask = rng.random(rows) < 0.7
    got = jax.jit(ops.masked_sum)(jnp.asarray(vals), jnp.asarray(mask))
    assert got.dtype == jnp.float64 and got.shape == ()
    assert int(got) == _exact(vals, mask)


@pytest.mark.parametrize("mask_kind", ["none", "all", "sparse"])
@pytest.mark.parametrize("pattern", ["int32_min", "mixed"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_masks(dtype, pattern, mask_kind):
    rng = np.random.default_rng(39)
    n = CHUNK + 1
    vals = _column(pattern, DTYPES[dtype], n, rng)
    mask = {"none": np.zeros(n, bool), "all": np.ones(n, bool), "sparse": rng.random(n) < 0.001}[mask_kind]
    assert int(ops.masked_sum(jnp.asarray(vals), jnp.asarray(mask))) == _exact(vals, mask)


@pytest.mark.parametrize("pattern", ["int32_min", "int32_max", "mixed"])
@pytest.mark.parametrize("dtype", ["int16", "int32", "int64"])
def test_a_column_past_one_piece_is_cut_at_static_bounds(monkeypatch, dtype, pattern):
    """Three whole pieces and a tail, with the piece shrunk so the case is small."""
    monkeypatch.setattr(segmented, "_SCALAR_PIECE", 1 << 12)
    rng = np.random.default_rng(40)
    n = 3 * (1 << 12) + 17
    vals = _column(pattern, DTYPES[dtype], n, rng)
    mask = rng.random(n) < 0.9
    assert int(jax.jit(ops.masked_sum)(jnp.asarray(vals), jnp.asarray(mask))) == _exact(vals, mask)


@pytest.mark.parametrize("pattern", ["int32_min", "int32_max"])
def test_a_whole_piece_of_extreme_rows_fits_int32(pattern):
    """The bound the piece rests on, at its real size: 2^23 rows whose every
    limb is at its largest magnitude, and one row more (a second piece).  The
    total is past 2^53: exact in int64, rounded once on its way to f64."""
    n = segmented._SCALAR_PIECE + 1
    vals = _column(pattern, np.int32, n, None)
    got = jax.jit(ops.masked_sum)(jnp.asarray(vals), jnp.ones(n, bool))
    assert float(got) == float(n * int(vals[0]))


def _long_column(pattern, n, rng):
    i64 = np.iinfo(np.int64)
    if pattern == "int64_min":
        return np.full(n, i64.min, np.int64)
    if pattern == "int64_max":
        return np.full(n, i64.max, np.int64)
    if pattern == "min_and_max":
        return np.where(np.arange(n) % 3 == 0, i64.min, i64.max).astype(np.int64)
    if pattern == "near_2p62":
        return rng.integers((1 << 62) - (1 << 40), 1 << 62, n, dtype=np.int64, endpoint=True)
    if pattern == "near_2p62_both_signs":
        return rng.integers((1 << 62) - (1 << 40), 1 << 62, n, dtype=np.int64) * rng.choice(np.array([-1, 1]), n)
    # nanosecond timestamps of 2024: six of them are past 2^63
    return rng.integers(1_704_067_200 * 10**9, 1_735_689_600 * 10**9, n, dtype=np.int64)


@pytest.mark.parametrize("rows", [6, 1000, CHUNK + 1])
@pytest.mark.parametrize(
    "pattern", ["int64_min", "int64_max", "min_and_max", "near_2p62", "near_2p62_both_signs", "timestamps_ns"]
)
def test_a_long_sum_past_int64_rounds_as_a_double_and_never_wraps(pattern, rows):
    """Past 2^53 the answer is a double's, as Pinot's accumulator's and the
    `wide` policy's: the limb sums meet in f64 (each S_k * 2^(8k) exact, eight
    additions whose partial sums are bounded by sum(|v|)), so the answer is
    within a few ulps of sum(|v|) of the exact integer and never the value
    mod 2^64 (a recombine in int64 read 0 for four rows of 2^62)."""
    rng = np.random.default_rng(42)
    vals = _long_column(pattern, rows, rng)
    mask = np.ones(rows, bool) if rows <= 6 else rng.random(rows) < 0.8
    got = float(jax.jit(ops.masked_sum)(jnp.asarray(vals), jnp.asarray(mask)))
    exact = _exact(vals, mask)
    magnitude = sum(abs(int(v)) for v in vals[mask])
    assert magnitude >= 1 << 63  # every case is past what an int64 recombine holds
    assert abs(got - float(exact)) <= float(magnitude) * 2.0**-50, (got, float(exact))


def test_four_rows_of_two_to_the_62_sum_to_two_to_the_64():
    """The reviewer's case of PR 38, exact because every term is a power of two."""
    vals = jnp.asarray(np.full(4, 1 << 62, np.int64))
    assert float(jax.jit(ops.masked_sum)(vals, jnp.ones(4, bool))) == 2.0**64
    assert float(jax.jit(ops.masked_sum)(-vals, jnp.ones(4, bool))) == -(2.0**64)


@pytest.mark.parametrize("rows", [1, 1000, SEGMENT])
def test_a_long_sum_below_two_to_the_53_is_exact(rows):
    """int64 values as wide as the bound allows for the row count."""
    rng = np.random.default_rng(43)
    top = (1 << 52) // rows
    vals = rng.integers(-top, top, rows, dtype=np.int64, endpoint=True)
    mask = rng.random(rows) < 0.9 if rows > 1 else np.ones(1, bool)
    assert int(jax.jit(ops.masked_sum)(jnp.asarray(vals), jnp.asarray(mask))) == _exact(vals, mask)


@pytest.mark.parametrize("rows", [0, 1, CHUNK + 1, SEGMENT])
@pytest.mark.parametrize("dtype", ["int8", "int16", "int32"])
def test_masked_sum_sq_of_small_integers_is_exact(dtype, rows):
    """`masked_sum_sq` squares in f32 and takes the FLOAT branch (f32 chunk
    sums, f64 combine), which this PR leaves as it is: exact while a chunk's
    sum of squares stays below 2^24, so |v| <= 15 here."""
    rng = np.random.default_rng(41)
    vals = rng.integers(-15, 16, rows).astype(DTYPES[dtype])
    mask = rng.random(rows) < 0.7
    got = jax.jit(ops.masked_sum_sq)(jnp.asarray(vals), jnp.asarray(mask))
    assert int(got) == _exact(vals.astype(np.int64) ** 2, mask)


@pytest.mark.parametrize("dtype,limbs", [("int8", 1), ("int16", 2), ("int32", 4), ("int64", 8)])
def test_one_count_a_summed_column_at_trace_time_and_one_reduce_a_limb(dtype, limbs):
    """`scan.traced.scalar_limbs` moves once a value column, when the program is
    traced and not when it runs again; the program reduces one int32 scalar a
    limb (a limb a byte of the dtype) under the scope `scalar_sum`, and holds
    no array of two dimensions."""
    import re

    counter = METRICS.counter("scan.traced.scalar_limbs")
    vals = jnp.asarray(np.arange(-500, 500).astype(DTYPES[dtype]))
    mask = jnp.asarray(np.arange(1000) % 3 == 0)
    fn = jax.jit(lambda v, m: ops.masked_sum(v, m))  # a function of its own: no other case's trace is in its cache
    before = counter.value
    text = fn.lower(vals, mask).as_text(debug_info=True)
    assert counter.value == before + 1
    fn(vals, mask)
    traced = counter.value
    fn(vals, mask)  # warm: nothing retraced
    assert counter.value == traced
    reduces = re.findall(r"stablehlo\.reduce\([^\n]*: \(tensor<1000xi32>, tensor<i32>\) -> tensor<i32>", text)
    assert len(reduces) == limbs, text
    assert "scalar_sum" in text
    two_d = set(re.findall(r"tensor<((?:\d+x){2,})\w+>", text))
    # int64's own, and not this reduction's: the column bitcast to its uint32 halves
    assert two_d <= ({"1000x2x", "1000x1x"} if dtype == "int64" else set()), two_d


def test_float_sums_and_the_wide_policy_are_what_they_were(monkeypatch):
    """The float branch moves no limb counter; `wide` (the CPU's) is one f64 sum."""
    counter = METRICS.counter("scan.traced.scalar_limbs")
    before = counter.value
    v = jnp.asarray(np.linspace(-1, 1, 1001, dtype=np.float32))
    m = jnp.ones(1001, bool)
    assert abs(float(ops.masked_sum(v, m))) < 1e-4
    monkeypatch.setattr(segmented, "accum_policy", lambda: "wide")
    ints = jnp.asarray(np.full(10, -(1 << 31), np.int32))
    assert int(ops.masked_sum(ints, jnp.ones(10, bool))) == -10 * (1 << 31)
    assert counter.value == before
