"""A row-priced scatter paid by the rows that pass the filter (PR 51):
ops/segmented.py's compaction (one one-operand sort a mask, then a scatter
over the passing prefix alone) against the plain scatter of every row.

Integer max and integer add are order-free, so registers, bins, counts and
limb tables must be the plain form's BIT FOR BIT at every passing share, from
no row to all.  The compaction is traced where a plan's kernel says its masks
carry a predicate (`ops.mask_facts`), under the chip's arithmetic
(`accum_policy() == "chunked32"`, steered here as tests/test_sketch_served.py
steers it); which branch of its `lax.cond` runs is the mask's own count.
"""
import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.ops import segmented
from pinot_tpu.query import planner
from pinot_tpu.segment import table_shape
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query

ROWS = 150_001  # not a multiple of the chunk: the last trip is cut from n - C
CHUNK = 1 << 13  # 19 trips where every row passes
REGISTERS, BINS, SLOTS = 716_800, 358_400, 437_500  # cell 8's two tables, cell 5's


@pytest.fixture(autouse=True)
def chip_arithmetic(monkeypatch):
    monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
    monkeypatch.setattr(segmented, "_COMPACT_CHUNK", CHUNK)


def _registers(mask, cells, values):
    return segmented.sketch_max_table(values & 31, mask, cells, REGISTERS, value_bits=5)


def _registers_by_row(mask, cells, values):  # a value that does not pack: the row numbers are sorted
    return segmented.sketch_max_table(values & 0xFFFFF, mask, cells, REGISTERS)


def _bins(mask, cells, values):
    return segmented.sketch_count_table(mask, cells % BINS, BINS)


def _wide(entry):
    def tables(mask, cells, values):
        return segmented._wide_group_tables([entry(mask, values)], cells % SLOTS, SLOTS)[0]

    return tables


FORMS = {
    "registers": _registers,
    "registers_by_row": _registers_by_row,
    "bins": _bins,
    "wide_count": _wide(lambda m, v: ("count", None, m, None)),
    "wide_int_sum": _wide(lambda m, v: ("int_sum", v - (1 << 20), m, segmented.sum_limb_plan(-(1 << 20), 1 << 20))),  # negative values, a limb plan
    "wide_int64_sum": _wide(lambda m, v: ("int64_sum", (v.astype(jnp.int64) - (1 << 20)) << 21, m, segmented.sum_limb_plan64(-(1 << 41), 1 << 41))),
}


@contextlib.contextmanager
def _crossover(always: bool):
    """The module's two crossovers as they are, or at 1.0: the compaction whatever passes.  Module constants are
    read where a body is traced."""
    mp = pytest.MonkeyPatch()
    if always:
        mp.setattr(segmented, "_COMPACT_MAX_SHARE_PAYLOAD", 1.0)
        mp.setattr(segmented, "_COMPACT_MAX_SHARE_ROWS", 1.0)
    try:
        yield
    finally:
        mp.undo()


@functools.lru_cache(maxsize=None)
def _program(form: str, filtered: bool, always: bool):
    """The jitted form, its masks said to carry a predicate or not."""

    def body(mask, cells, values):
        with ops.mask_facts(filtered), _crossover(always):
            return FORMS[form](mask, cells, values)

    return jax.jit(body)


def _run(form, filtered, always, *args):
    return np.asarray(_program(form, filtered, always)(*args))


def _rows(rows=ROWS, seed=51):
    rng = np.random.default_rng(seed)
    return rng.random(rows), rng.integers(0, REGISTERS, rows).astype(np.int32), rng.integers(0, 1 << 21, rows).astype(np.int32)


def _mask_of(share, u):
    if share == "one_row":
        mask = np.zeros(u.shape, bool)
        mask[len(u) // 3] = True
        return mask
    return u < share


SHARES = [0.0, "one_row", 1e-4, 0.08, 0.2, 0.7, 1.0]


@pytest.mark.parametrize("share", SHARES, ids=[str(s) for s in SHARES])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_compacted_table_is_the_plain_one_bit_for_bit(form, share):
    """The compaction taken whatever the share (a cond that always compacts) against the plain scatter."""
    u, cells, values = _rows()
    mask = _mask_of(share, u)
    plain = _run(form, False, True, mask, cells, values)
    compacted = _run(form, True, True, mask, cells, values)
    assert compacted.dtype == plain.dtype and np.array_equal(compacted, plain)
    assert (plain != 0).any() == bool(mask.any())


@pytest.mark.parametrize("side", ["at", "past"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_both_sides_of_the_crossover_hold_the_same_table(form, side):
    """The cond as it is served: a mask that passes exactly the flavour's share of the rows compacts, one row
    more scatters every row; the table is the plain one on either side."""
    u, cells, values = _rows()
    share = segmented._COMPACT_MAX_SHARE_PAYLOAD if form in ("registers", "bins") else segmented._COMPACT_MAX_SHARE_ROWS
    passing = int(np.int32(share * ROWS)) + (side == "past")
    mask = np.zeros(ROWS, bool)
    mask[np.argsort(u)[:passing]] = True
    taken = str(jax.make_jaxpr(_program(form, True, False))(mask, cells, values))
    assert taken.count("cond[") == 1 and "sort[" in taken
    plain = _run(form, False, False, mask, cells, values)
    assert np.array_equal(_run(form, True, False, mask, cells, values), plain)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_every_row_in_one_cell(form):
    u, cells, values = _rows()
    cells = np.full_like(cells, 4_242)
    mask = u < 0.3
    plain = _run(form, False, True, mask, cells, values)
    assert np.array_equal(_run(form, True, True, mask, cells, values), plain)
    assert np.count_nonzero(plain) == 1


@pytest.mark.parametrize("rows", [CHUNK, CHUNK - 1, 3 * CHUNK, 2 * CHUNK + 1, 7, 0])
def test_row_counts_at_and_off_the_chunks_edges(rows):
    """A whole number of chunks, one row short, one row over, fewer rows than a chunk, and an empty segment."""
    u, cells, values = _rows(rows, seed=rows)
    for share in (0.5, 1.0):
        mask = u < share
        for form in ("registers", "wide_int_sum"):
            plain = _run(form, False, True, mask, cells, values)  # traced anew a row count: the chunk is cut from the static length
            assert np.array_equal(_run(form, True, True, mask, cells, values), plain), (form, share)


def _entries(mask, other, values):
    """A Q3.x's entries: the presence count and a SUM's limb tables on the WHERE mask, a FILTERed COUNT on its own."""
    return [("count", None, mask, None), ("int_sum", values, mask, (3, False)), ("count", None, other, None),
            ("int64_sum", values.astype(jnp.int64) << 20, mask, 6)]


def _lowered(filtered, mask_words=False):
    def body(mask, other, cells, values, words):
        with ops.mask_facts(filtered) as facts:
            if mask_words:
                out = segmented._fused_group_tables_xla(_entries(mask, other, values), cells, SLOTS, words, None)
            else:
                out = segmented._wide_group_tables(_entries(mask, other, values), cells, SLOTS)
        body.compactions = facts.compactions
        return out

    n = 1 << 15
    args = [jax.ShapeDtypeStruct((n,), jnp.bool_)] * 2 + [jax.ShapeDtypeStruct((n,), jnp.int32)] * 2
    text = jax.jit(body).lower(*args, jax.ShapeDtypeStruct((n // 32,), jnp.uint32)).as_text()
    return text, body.compactions


@pytest.mark.parametrize("mask_words", [False, True], ids=["masks", "packed_words_anded_in"])
def test_entries_that_share_a_mask_share_one_sort(mask_words):
    """Three entries on the WHERE mask and one on another: two compactions, two sorts, whether the masks come
    as they are or with a packed filter bitmap ANDed into each (once a distinct mask)."""
    text, compactions = _lowered(True, mask_words)
    assert compactions == 2 and text.count("stablehlo.sort") == 2 and text.count("stablehlo.while") == 2


def test_a_plan_with_no_predicate_lowers_to_the_plain_forms_text():
    """No sort, no loop, no cond: the text outside any `mask_facts` block, which is the parent's."""
    def bare(mask, other, cells, values):
        return segmented._wide_group_tables(_entries(mask, other, values), cells, SLOTS)

    n = 1 << 15
    args = [jax.ShapeDtypeStruct((n,), jnp.bool_)] * 2 + [jax.ShapeDtypeStruct((n,), jnp.int32)] * 2
    outside = jax.jit(bare).lower(*args).as_text()
    text, compactions = _lowered(False)
    assert compactions == 0 and "stablehlo.sort" not in text and "stablehlo.case" not in text and "stablehlo.if" not in text
    strip = lambda t: "\n".join(line.split(" loc(")[0] for line in t.splitlines() if not line.startswith("#loc"))  # noqa: E731
    assert strip(text).replace("jit_body", "jit_bare") == strip(outside)


@pytest.mark.parametrize("form", ["registers", "registers_by_row", "bins", "wide_tables"])
def test_the_cpus_policy_keeps_the_plain_form(form, monkeypatch):
    monkeypatch.setattr(ops, "accum_policy", lambda: "wide")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "wide")

    def body(mask, cells, values):
        with ops.mask_facts(True) as facts:
            if form == "wide_tables":  # the entry point: a wide-policy table is _fused_wide_tables', native f64
                out = segmented.fused_group_tables(_entries(mask, mask, values), cells % SLOTS, SLOTS)
            else:
                out = FORMS[form](mask, cells, values)
        body.compactions = facts.compactions
        return out

    n = 1 << 12
    args = [jax.ShapeDtypeStruct((n,), jnp.bool_)] + [jax.ShapeDtypeStruct((n,), jnp.int32)] * 2
    assert "stablehlo.sort" not in jax.jit(body).lower(*args).as_text() and body.compactions == 0


# ---------------------------------------------------------------------------
# through the planner: which plans carry the compaction
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def segment():
    rows = 40_000
    rng = np.random.default_rng(5)
    schema = Schema("t", [
        FieldSpec("city_a", DataType.INT), FieldSpec("city_b", DataType.INT), FieldSpec("region", DataType.INT),
        FieldSpec("cust", DataType.INT), FieldSpec("rev", DataType.INT, role=FieldRole.METRIC),
    ])
    return build_segment(schema, {
        "city_a": rng.integers(0, 250, rows).astype(np.int32), "city_b": rng.integers(0, 250, rows).astype(np.int32),
        "region": rng.integers(0, 5, rows).astype(np.int32), "cust": rng.integers(0, 9_000, rows).astype(np.int32),
        "rev": rng.integers(-90_000, 10_000_000, rows).astype(np.int32),
    }, "seg0")


QUERIES = {
    # (sql, compactions its program carries): a 62,500-slot table past _MATMUL_MAX_GROUPS; 250 x 4,096 registers
    "wide_filtered": ("SELECT city_a, city_b, SUM(rev), COUNT(*) FROM t WHERE region = 2 GROUP BY city_a, city_b LIMIT 100000", 1),
    "wide_unfiltered": ("SELECT city_a, city_b, SUM(rev), COUNT(*) FROM t GROUP BY city_a, city_b LIMIT 100000", 0),
    "wide_agg_filter": ("SELECT city_a, city_b, SUM(rev) FILTER (WHERE region = 2), COUNT(*) FROM t GROUP BY city_a, city_b LIMIT 100000", 2),
    "hll_filtered": ("SELECT city_a, DISTINCTCOUNTHLL(cust, 12) FROM t WHERE region < 2 GROUP BY city_a LIMIT 1000", 1),
    "hll_unfiltered": ("SELECT city_a, DISTINCTCOUNTHLL(cust, 12) FROM t GROUP BY city_a LIMIT 1000", 0),
    "hll_ungrouped": ("SELECT DISTINCTCOUNTHLL(cust, 12), PERCENTILETDIGEST(rev, 95) FROM t WHERE region = 1", 1),
    "dense_filtered": ("SELECT region, SUM(rev) FROM t WHERE city_a < 9 GROUP BY region LIMIT 10", 0),  # the one-hot kernel's: no row-priced scatter
}


def _plan_and_result(segment, sql, shape=None):
    planner.plan_cache_clear()
    try:
        plan = planner.QueryPlanning(parse_query(sql), shape).plan(segment)
        cols = segment.to_device(columns=plan.needed_columns, packed_codes=True, value_columns=plan.value_columns, rows=plan.rows)
        out = jax.tree_util.tree_map(np.asarray, plan.fn(cols, plan.params))
        return plan, out
    finally:
        planner.plan_cache_clear()


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_a_plans_static_facts_say_whether_its_program_compacts(name, segment, monkeypatch):
    """A predicate anywhere in the plan (WHERE, an aggregation's FILTER) and a row-priced scatter: the
    compaction, counted on the plan; no predicate, or no such scatter: none.  The answer is the CPU policy's."""
    sql, compactions = QUERIES[name]
    plan, got = _plan_and_result(segment, sql)
    assert plan.mask_facts.compactions == compactions
    assert plan.mask_facts.filtered == ("unfiltered" not in name)
    monkeypatch.setattr(ops, "accum_policy", lambda: "wide")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "wide")
    wide_plan, want = _plan_and_result(segment, sql)
    assert wide_plan.mask_facts.compactions == 0
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("name", ["wide_filtered", "hll_filtered", "wide_unfiltered"])
def test_a_padded_segments_rows_past_its_count_are_never_scattered(name, segment):
    """The segment's view at a table's row bound (ImmutableSegment.padded_to): the padding mask is ANDed into
    the WHERE mask, and alone it is no predicate."""
    class Longer:  # a second segment of the table, a little longer: the table's rows are its bucket
        name, columns, num_docs = "longer", {}, segment.num_docs + 1

    shape = table_shape.TableShape()
    shape.add(segment)
    shape.add(Longer)
    sql, compactions = QUERIES[name]
    _, want = _plan_and_result(segment, sql)
    plan, got = _plan_and_result(segment, sql, shape)
    assert plan.rows == table_shape.row_bucket(Longer.num_docs) > segment.num_docs and plan.mask_facts.compactions == compactions
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.array_equal(a, b)
