"""SSB's drill-down queries (Q3.2, Q3.3, Q3.4, Q4.3) through the chip's
arithmetic, exact.

The four templates of `benchmarks/queries/ssb_flat.json` that group by
(c_city, s_city, d_year) = 437,500 slots and (d_year, s_city, p_brand1) =
1,750,000 take two paths no dense template takes: the first three plan as
`groupby_dense` with a table past the one-hot kernel's 8,192 slots, which
under `chunked32` is ops/segmented.py `_wide_group_tables` (12-bit limbs
scattered into int32 tables); Q4.3 plans as `groupby_sparse`
(planner.sparse_grouped_tables: sort + slot scatter).  On the CPU the engine
takes `accum_policy()` = "wide", so tier-1 never ran what the chip runs
(ROADMAP C2), and the chip served Q3.2 with two sums off by one (PERF.md,
PR 31).  Here they run with 32-bit accumulation and the scan interpreted
(steered as tests/test_ssb_templates_chip_path.py steers them), at SSB's
published literals and at eight seeded draws each, launched a segment at a
time and as ONE group program of width 4, over a four-segment table whose
revenue keeps the schema's magnitudes: every segment holds, for each
template's published literals, one group of 1,000 rows whose sum passes
2^31, so an f32 or an int32 accumulator fails (the last test shows both
failing).  Compared with the benchmark's plain numpy reference at
difference 0: keys, missing and extra groups, every sum, the order.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.ops import segmented
from pinot_tpu.query import executor, planner
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils.metrics import METRICS
from tests.test_ssb_templates_chip_path import chip_path  # noqa: F401  (chunked32 + the scan interpreted, for this module's plans)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
TEMPLATES = ["q3_2", "q3_3", "q3_4", "q4_3"]
FORM = {"q3_2": "wide_scatter", "q3_3": "wide_scatter", "q3_4": "wide_scatter", "q4_3": "sparse_sort"}
SEGMENTS, SEGMENT_ROWS, HOT_ROWS = 4, 20_000, 1_000
SEED, DRAWS = 31, 8
# one hot group a template, inside its published literals: column -> value
HOT = [
    {"c_city": 231, "s_city": 235, "d_year": 1995, "d_yearmonth": 40},  # Q3.2: both in UNITED STATES
    {"c_city": 221, "s_city": 225, "d_year": 1996, "d_yearmonth": 50},  # Q3.3: UNITED KI1, UNITED KI5
    {"c_city": 225, "s_city": 221, "d_year": 1997, "d_yearmonth": 71},  # Q3.4: the same cities, Dec1997
    {"c_city": 13, "s_city": 233, "d_year": 1998, "d_yearmonth": 75, "p_brand1": 130},  # Q4.3: AMERICA, MFGR#14
]


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own files: generator, query set, renderer, reference."""
    sys.path.insert(0, BENCH)
    try:
        from lib import plugins, templates
        from lib.references import filter_group_sum

        cfg = plugins.load_json("configs", "ssb_flat_sf1_drill")
        gen = plugins.load_module("datagen", cfg["datagen"])
        queries = plugins.load_json("queries", cfg["query_set"])["templates"]
    finally:
        sys.path.remove(BENCH)
    return cfg, gen, queries, templates, filter_group_sum


def _concentrate(block, cfg, rng):
    """The generator's uniform rows, with HOT_ROWS of them moved into each
    hot group (at drawn positions, the hierarchy kept): at this size uniform
    keys put a row or two in a group, and the accumulation is never asked
    for more than one value."""
    region = np.asarray(cfg["hierarchy"]["nation_region"])
    n = len(block["c_city"])
    at = rng.permutation(n)[: HOT_ROWS * len(HOT)].reshape(len(HOT), HOT_ROWS)
    for rows, hot in zip(at, HOT):
        for col, v in hot.items():
            block[col][rows] = v
        block["d_yearmonthnum"][rows] = hot["d_year"] * 100 + hot["d_yearmonth"] % 12 + 1
    for side in "cs":
        block[f"{side}_nation"] = (block[f"{side}_city"] // 10).astype(block[f"{side}_nation"].dtype)
        block[f"{side}_region"] = region[block[f"{side}_nation"]].astype(block[f"{side}_region"].dtype)
    block["p_category"] = (block["p_brand1"] // 40).astype(block["p_category"].dtype)
    block["p_mfgr"] = (block["p_category"] // 5).astype(block["p_mfgr"].dtype)
    return block


@pytest.fixture(scope="module")
def table(bench, chip_path):
    cfg, gen, _, _, _ = bench
    schema = Schema(
        cfg["table"],
        [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]]) for c in cfg["columns"]],
    )
    tcfg = TableConfig(cfg["table"], indexing=IndexingConfig.from_dict(cfg["table_config"]))
    coord = Coordinator(replication=1)
    server = ServerInstance("server0")
    coord.register_server(server)
    coord.add_table(schema, tcfg)
    wide = {"INT": np.int32, "LONG": np.int64}
    blocks = []
    for i in range(SEGMENTS):
        block = _concentrate(gen.make_segment(cfg, SEED, i, SEGMENT_ROWS), cfg, np.random.default_rng([SEED, 99, i]))
        blocks.append(block)
        cols = {c["name"]: block[c["name"]].astype(wide[c["type"]]) for c in cfg["columns"]}
        coord.add_segment(cfg["table"], build_segment(schema, cols, f"seg{i}", table_config=tcfg))
    return Broker(coord), server, blocks


def _params(bench, name):
    """The published literals, then DRAWS seeded draws from the template's domain."""
    _, _, queries, templates, _ = bench
    rng = np.random.default_rng([SEED, TEMPLATES.index(name)])
    return [dict(queries[name]["ssb"])] + [templates.draw_params(queries[name], rng) for _ in range(DRAWS)]


def _compare(bench, table, name, params):
    _, _, queries, templates, reference = bench
    broker, _, blocks = table
    spec = queries[name]["reference"]
    got = broker.query(templates.render(queries[name], params))
    assert not got.stats.partial_result and got.stats.num_segments_processed == len(blocks)
    want = reference.answer(spec, params, blocks)
    equal, numbers = reference.compare(spec, list(got.columns), [list(r) for r in got.rows], want)
    return equal, numbers, want


@pytest.mark.parametrize("width", [1, SEGMENTS], ids=["a_segment_a_launch", "one_group_program"])
@pytest.mark.parametrize("draw", range(1 + DRAWS), ids=["ssb"] + [f"draw{i}" for i in range(DRAWS)])
@pytest.mark.parametrize("name", TEMPLATES)
def test_drill_down_equals_the_plain_reference(name, draw, width, bench, table, monkeypatch):
    monkeypatch.setattr(executor, "MAX_GROUP_WIDTH", width)
    _, server, _ = table
    params = _params(bench, name)[draw]
    before = server.metrics.snapshot()["counters"]
    equal, numbers, want = _compare(bench, table, name, params)
    assert equal, numbers
    moved = {k: v - before.get(k, 0) for k, v in server.metrics.snapshot()["counters"].items()}
    assert moved["server.launches"] == SEGMENTS // width
    assert moved["server.groupedSegments"] == (SEGMENTS if width > 1 else 0)
    if draw == 0:
        # the published literals hold the hot group: a sum no 32-bit accumulator holds, in every segment
        assert max(want["groups"].values()) > SEGMENTS * (1 << 31)
        # groups a sparse table held, summed over the segments; a wide dense table is not one
        held = moved["server.sparseGroups"]
        assert held == 0 if FORM[name] == "wide_scatter" else len(want["groups"]) <= held <= SEGMENTS * len(want["groups"])


@pytest.mark.parametrize("name", TEMPLATES)
def test_the_trace_time_counter_names_the_form_a_template_got(name, bench, table):
    """`scan.traced.wide_scatter` for the 437,500-slot tables, `scan.traced.sparse_sort`
    for Q4.3's 1,750,000 slots, and beside it `scan.traced.sparse_limb_scatter` (PR 42:
    its integer sum rides int32 limbs after the sort) and `scan.traced.sparse_prefix_sums` (PR 44: its
    count and sum are read from prefix sums at the slots' row ranges): what the benchmark's warm-up line prints.
    A wide table's plan has a WHERE, so its scatters take the passing rows alone (PR 51: `scan.traced.compact_scatter`,
    once a plan: its count and its limb tables share the mask's one compaction)."""
    planner.plan_cache_clear()
    equal, numbers, _ = _compare(bench, table, name, _params(bench, name)[0])
    assert equal, numbers
    moved = {k.rsplit(".", 1)[1] for k, v in METRICS.snapshot()["counters"].items()
             if k.startswith("scan.traced.") and v and not k.endswith((".lane_unpack", ".xla"))}
    assert moved == {FORM[name]} | ({"sparse_limb_scatter", "sparse_prefix_sums"} if FORM[name] == "sparse_sort" else {"compact_scatter"})


def _f32_tables(entries, codes, num_groups):
    """The accumulation this PR removed: an f32 table over 2^16-row chunks."""
    out = []
    for kind, values, mask, _ in entries:
        v = mask.astype(jnp.float32) if kind == "count" else jnp.where(mask, values.astype(jnp.float32), 0.0)
        out.append(segmented._chunked_scatter(v, codes.astype(jnp.int32), num_groups, 1 << 16)
                   .astype(jnp.float64).sum(axis=0))
    return out


def _int32_tables(entries, codes, num_groups):
    """One int32 table a sum: wraps past 2^31."""
    out = []
    for kind, values, mask, _ in entries:
        v = mask.astype(jnp.int32) if kind == "count" else jnp.where(mask, values.astype(jnp.int32), 0)
        out.append(segmented._chunked_scatter(v, codes.astype(jnp.int32), num_groups, codes.shape[0])[0]
                   .astype(jnp.float64))
    return out


@pytest.mark.parametrize("control", [_f32_tables, _int32_tables], ids=["f32_accumulator", "int32_accumulator"])
@pytest.mark.parametrize("name", ["q3_2", "q3_3", "q3_4"])
def test_control_a_32_bit_accumulator_fails_the_same_comparison(name, control, bench, table, monkeypatch):
    monkeypatch.setattr(segmented, "_wide_group_tables", control)
    planner.plan_cache_clear()
    try:
        equal, numbers, _ = _compare(bench, table, name, _params(bench, name)[0])
    finally:
        planner.plan_cache_clear()
    assert not equal and numbers["wrong_sums"] >= 1 and numbers["missing"] == numbers["extra"] == 0, numbers


WIDE_INPUTS = {  # name -> (low, high, dtype, kind, limb plan as the planner would give it from min/max, or None)
    "revenue_two_limbs": (0, 10_000_001, np.int32, "int_sum", (3, False)),
    "int32_full_range": (-(2**31), 2**31 - 1, np.int32, "int_sum", (4, True)),
    "int32_no_stats": (-(2**31), 2**31 - 1, np.int32, "int_sum", None),
    "int8_signed": (-120, 120, np.int8, "int_sum", (1, True)),
    "int16_signed": (-30_000, 30_000, np.int16, "int_sum", (2, True)),
    "int64_five_limbs": (-(1 << 39), 1 << 39, np.int64, "int64_sum", 5),
    "int64_no_stats": (-(1 << 40), 1 << 40, np.int64, "int64_sum", None),
}


@pytest.mark.parametrize("name", sorted(WIDE_INPUTS))
def test_wide_table_is_exact_for_every_integer_input(name, chip_path):
    """ops/segmented.py `_wide_group_tables` over three 2^19-row chunks:
    signed and unsigned limb plans, the full-width plan where there are no
    stats, int64's signed-magnitude limbs, and the count beside them."""
    low, high, dtype, kind, limb_plan = WIDE_INPUTS[name]
    rng = np.random.default_rng([SEED, sorted(WIDE_INPUTS).index(name)])
    n, groups = (1 << 20) + 12_345, 20_000
    codes = rng.integers(0, groups, n).astype(np.int32)
    mask = rng.random(n) < 0.8
    values = rng.integers(low, high, n).astype(dtype)
    want = np.zeros(groups, np.int64)
    np.add.at(want, codes[mask], values[mask].astype(np.int64))
    count, total = segmented.fused_group_tables(
        [("count", None, jnp.asarray(mask), None), (kind, jnp.asarray(values), jnp.asarray(mask), limb_plan)],
        jnp.asarray(codes), groups,
    )
    assert METRICS.counter("scan.traced.wide_scatter").value == 1
    np.testing.assert_array_equal(np.asarray(total).astype(np.int64), want)
    np.testing.assert_array_equal(np.asarray(count).astype(np.int64), np.bincount(codes[mask], minlength=groups))
    one = segmented.group_sum(jnp.asarray(values), jnp.asarray(mask), jnp.asarray(codes), groups)
    np.testing.assert_array_equal(np.asarray(one).astype(np.int64), want)
