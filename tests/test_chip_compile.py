"""AOT compiles of the chip's own program, for a DESCRIBED TPU v5e.

Tier-1 runs the Pallas scan in interpret mode on the CPU, which cannot show
what the chip's compiler refuses (tiling, VMEM, 64-bit scalars in a kernel,
unsupported casts and shape casts).  The TPU compiler is installed in the
sandbox and compiles for a chip that is described, not attached — so these
cases compile, at the widths the served queries produce and with
jax_enable_x64 ON as the package runs, every Pallas variant the planner can
select, the device-side sparse merge, and one DistributedEngine dense
group-by step under the chip's arithmetic policy.  Nothing executes: a pass
here is not a chip run.

The topology is described inside a module-scoped fixture (never at import,
in a skipif or in parametrize arguments): only the worker that runs this
file loads the TPU library.  No child process; the persistent compile cache
is off around the compiles (an entry written for a described chip cannot be
read back without one).
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from pinot_tpu import ops
from pinot_tpu.ops import pallas_scan, segmented

ROWS = 1 << 23  # one int32 super-segment: 256 tiles of 2^15 rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _case(kinds, groups, words=False, packed=None, pred=False,
          code_dt=jnp.int32, val_dt=jnp.int32, limbs=(4, True)):
    return dict(kinds=kinds, groups=groups, words=words, packed=packed, pred=pred,
                code_dt=code_dt, val_dt=val_dt, limbs=limbs)


# G = 2406 (lo_orderdate) and 11 (lo_discount) are the served queries' tables;
# 8192 is the widest table the planner hands to the kernel
SCAN_CASES = {
    "count_g11": _case(["count"], 11),
    "count_g11_words": _case(["count"], 11, words=True),
    "int_sum_g11": _case(["int_sum"], 11),
    "int_sum_g11_words": _case(["int_sum"], 11, words=True),
    "int64_sum_g2406": _case(["int64_sum"], 2406),
    "int64_sum_g2406_words": _case(["int64_sum"], 2406, words=True),
    "packed4": _case(["count"], 11, packed=4),
    "packed8": _case(["count"], 50, packed=8),
    "packed16": _case(["count"], 2406, packed=16),
    "code_pred": _case(["count"], 11, pred=True),
    "headline_a": _case(["count", "int64_sum"], 2406, words=True, packed=16),
    "agg_bound_c": _case(["count", "int_sum", "int64_sum"], 2406, packed=16),
    "widest_table": _case(["count", "int64_sum"], 8192, words=True),
    "storage_dtypes": _case(["count", "int_sum"], 50, code_dt=jnp.uint8, val_dt=jnp.int16,
                            limbs=(2, True)),
    "unsigned_limbs": _case(["int_sum"], 2406, code_dt=jnp.uint16, val_dt=jnp.uint8,
                            limbs=(1, False)),
    # the stacked operand (PR 45) at the (limb columns, table sublanes) the
    # benchmark's cells run: cell 1's four, the star-tree levels' two, and a
    # 16-column plan (one matmul of a [1024, C] stack against [112, C])
    "l4_h112": _case(["count", ("int_sum", (3, False))], 7000, words=True),
    "l6_h72": _case(["count", ("int_sum", (4, True))], 4375, words=True),
    "l4_h72": _case(["count", ("int_sum", (3, False))], 4375, words=True),
    "l6_h8": _case(["count", ("int_sum", (4, True))], 175, words=True),
    "l6_h112": _case(["count", ("int_sum", (4, True))], 7000),
    "l7_h72": _case(["count", ("int64_sum", 6)], 4375),
    "l16_h112": _case(["count", ("int_sum", (1, True)), ("int_sum", (4, True)), "int64_sum"], 7000),
    "l16_h8": _case(["count", ("int_sum", (1, True)), ("int_sum", (4, True)), "int64_sum"], 300),
}


@pytest.mark.parametrize("name", sorted(SCAN_CASES))
def test_pallas_scan_compiles_for_v5e(one_chip, no_compile_cache, name):
    case = SCAN_CASES[name]
    assert jax.config.jax_enable_x64  # as the package runs

    def shape(n, dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)

    packed = case["packed"]

    def scan(codes, mask, v32, v64, mask_words, key_words):
        entries = []
        for k in case["kinds"]:
            # a kind alone takes the case's limb plan, a pair brings its own
            k, lp = k if isinstance(k, tuple) else (k, {"int_sum": case["limbs"], "int64_sum": 8}.get(k))
            entries.append((k, {"count": None, "int_sum": v32, "int64_sum": v64}[k], mask, lp))
        assert pallas_scan.pallas_supported(entries, case["groups"])
        return pallas_scan.fused_group_tables_pallas(
            entries, codes, case["groups"],
            mask_words=mask_words if case["words"] else None,
            codes_packed=(key_words, packed) if packed else None,
            code_pred=(codes, 3, 9) if case["pred"] else None,
        )

    compiled = jax.jit(scan).lower(
        shape(ROWS, case["code_dt"]), shape(ROWS, jnp.bool_), shape(ROWS, case["val_dt"]),
        shape(ROWS, jnp.int64), shape(ROWS // 32, jnp.uint32),
        shape(ROWS * (packed or 32) // 32, jnp.uint32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel is in the program
    if re.fullmatch(r"l\d+_h\d+", name):  # under the name the benchmark's device trace reads
        assert f"%kernel_dense_onehot_{name}" in text


# table[codes] as a one-hot contraction (ops/code_lookup.py, PR 48) over one
# served segment whose rows are no whole number of tiles: cell 7's IN table
# and INT_COL's dictionary (compiled at 8,192 entries), a FLOAT dictionary,
# and both ends of the contracted range (the longest table's one-hot is met
# a block of 256 table rows at a time)
LOOKUP_CASES = {
    "bool_8192": (jnp.bool_, 8192, "l1_h64"),
    "int32_8192": (jnp.int32, 8192, "l4_h64"),
    "float32_8192": (jnp.float32, 8192, "l4_h64"),
    "int32_min": (jnp.int32, segmented._CONTRACT_MIN_TABLE, "l4_h16"),
    "int32_max": (jnp.int32, segmented._CONTRACT_MAX_TABLE, f"l4_h{segmented._CONTRACT_MAX_TABLE // 128}"),
}


@pytest.mark.parametrize("name", sorted(LOOKUP_CASES))
def test_code_lookup_compiles_for_v5e(one_chip, no_compile_cache, monkeypatch, name):
    from pinot_tpu.ops import code_lookup

    dtype, entries, kernel = LOOKUP_CASES[name]
    monkeypatch.setattr(ops, "scan_backend", lambda: "pallas")
    rows = 1_500_000
    compiled = jax.jit(code_lookup.code_lookup).lower(
        jax.ShapeDtypeStruct((entries,), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    assert f"%kernel_code_lookup_{kernel}" in text  # under the name a device trace lists
    assert "kind=kCustom" not in text  # and no gather a row beside it


# SSB Q3.2-Q3.4's table (437,500 slots) over one served segment: past the
# one-hot kernel, so under chunked32 the fused tables are _wide_group_tables'
WIDE_CASES = {
    "q3_revenue": [("count", None), ("int_sum", (3, False))],  # lo_revenue < 2^24: two 12-bit limbs
    "int32_any": [("int_sum", None)],  # no stats: three limbs and the negatives' count
    "int64_and_float": [("int64_sum", None), ("f32_sum", None), ("f32_sumsq", None)],
}


@pytest.mark.parametrize("name", sorted(WIDE_CASES))
def test_wide_group_tables_compile_for_v5e(one_chip, no_compile_cache, monkeypatch, name):
    """Every integer table is int32 scatters (one a 12-bit limb, the chip's
    fast scatter), never a 64-bit or an f32 one; floats keep an f32 table."""
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
    n, groups = 1_500_000, 437_500

    def shape(dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)

    def tables(codes, mask, v32, v64, vf):
        values = {"count": None, "int_sum": v32, "int64_sum": v64, "f32_sum": vf, "f32_sumsq": vf}
        return segmented.fused_group_tables([(k, values[k], mask, lp) for k, lp in WIDE_CASES[name]], codes, groups)

    text = jax.jit(tables).lower(
        shape(jnp.int32), shape(jnp.bool_), shape(jnp.int32), shape(jnp.int64), shape(jnp.float32)
    ).compile().as_text()
    # the entry computation's instructions (a fused computation's root repeats each as `ROOT %fusion...`)
    scatters = re.findall(r"^\s*%[\w.\-]+ = (\(?\w+)\[\d+\]\S* fusion\([^\n]*kind=kCustom[^\n]*scatter-add", text, re.M)
    want = {"q3_revenue": ["s32"] * 3, "int32_any": ["s32"] * 4, "int64_and_float": ["s32"] * 6 + ["f32"] * 2}[name]
    assert sorted(scatters) == sorted(want), scatters


# the smoke's sparse query (d) tracks its whole key space (no trim).  The
# default numGroupsLimit path ranks and trims through an (f64, int64) two-key
# sort that XLA's TPU backend compiles for ~265 s at this size — marked slow
# so tier-1 stays well inside its time limit; run it with `-m slow`.
@pytest.mark.parametrize(
    "slots,tables,may_trim",
    [
        (8192, 4, False),
        pytest.param(8192, 4, True, marks=pytest.mark.slow),
        (1_323_300, 1, False),
    ],
    ids=["4x8192", "4x8192_trim", "smoke_d_1chip"],
)
def test_merge_sparse_tables_compiles_for_v5e(one_chip, no_compile_cache, slots, tables, may_trim):
    m = slots * tables

    def shape(dt):
        return jax.ShapeDtypeStruct((m,), dt, sharding=one_chip)

    def merge(uniq, sums, counts):
        return pallas_scan.merge_sparse_tables(
            uniq, [{"sum": sums, "count": counts}], slots,
            [{"sum": "add", "count": "add"}], order_spec=(0, "sum", False),
            may_trim=may_trim,
        )

    jax.jit(merge).lower(shape(jnp.int64), shape(jnp.float64), shape(jnp.int64)).compile()


def test_sparse_group_tables_compile_for_v5e(one_chip, no_compile_cache):
    """The per-segment sparse kernel of the smoke's query (d): one 1.5M-row
    segment, 1,323,300 possible groups, every group tracked."""
    from pinot_tpu.query import planner
    from pinot_tpu.query.functions import get_agg_function

    n, groups = 1_500_000, 1_323_300
    sum_fn = get_agg_function("sum")

    def shape(dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)

    def kernel(vals, mask, key):
        return planner.sparse_grouped_tables(
            [sum_fn], [(vals, mask)], mask, key, groups, (0, "sum", False), num_groups=groups
        )

    jax.jit(kernel).lower(shape(jnp.int64), shape(jnp.bool_), shape(jnp.int64)).compile()


def test_sparse_slot_tables_keep_one_row_length_scatter_for_v5e(one_chip, no_compile_cache, monkeypatch):
    """SSB Q4.3's per-segment sparse kernel under the chip's policy (PR 44): 1.5M rows, 100,000 slots of a
    1,750,000-key space, an int32 expression summed.  After the sort ONE row-length scatter is left, the
    slots' first rows (`s32[100001]`, the shape `sparse_limb_scatter_ms` reads); the keys, the count and the
    sum are table-size gathers, of the sorted key and of five uint32 prefix sums (four 8-bit limbs and the
    negatives' count), which that metric's `s32` pattern does not take for scatters.  None is a tuple of
    32-bit halves (the emulated 64-bit scatter, ~14 times the time a row)."""
    from pinot_tpu.query import planner
    from pinot_tpu.query.functions import get_agg_function

    monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
    n, slots, groups = 1_500_000, 100_000, 1_750_000
    sum_fn = get_agg_function("sum")

    def shape(dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)

    def kernel(vals, mask, key):
        return planner.sparse_grouped_tables([sum_fn], [(vals, mask)], mask, key, slots, None, num_groups=groups)

    text = jax.jit(kernel).lower(shape(jnp.int32), shape(jnp.bool_), shape(jnp.int64)).compile().as_text()
    # the entry computation's custom fusions: (what it writes, the op it was traced as)
    custom = re.findall(r"^\s*%[\w.\-]+ = (\(?\w+\[\d+\])\S* fusion\([^\n]*kind=kCustom[^\n]*op_name=\"[^\"]*/([\w\-]+)\"", text, re.M)
    assert [out for out, op in custom if op.startswith("scatter")] == ["s32[100001]"], custom
    gathers = sorted(out for out, op in custom if op == "gather")
    assert gathers == ["s32[100000]", "s32[1500000]"] + ["u32[100001]"] * 5, custom
    assert len(custom) == 1 + len(gathers) and not any(out.startswith("(") for out, _ in custom), custom
    # the benchmark's reader of the slot tables' scatters (benchmarks/layer_metrics/sparse_limb_scatter_ms.json) finds that one
    assert len(re.findall(r"^\s*%fusion[\w.\-]* = s32\[([1-9])0000\1\]\S* fusion\(.*kind=kCustom", text, re.M)) == 1


def test_engine_dense_groupby_step_compiles_for_v5e(topo, no_compile_cache, monkeypatch):
    """One single-device DistributedEngine step of the headline query — word-
    fused filter, 16-bit packed key, int64 limbs — traced as the chip traces
    it: scan_backend()="pallas", accum_policy()="chunked32" (both ask
    jax.default_backend(), which says cpu here, so the test steers them)."""
    from pinot_tpu.parallel import mesh as mesh_mod
    from pinot_tpu.parallel.engine import DistributedEngine
    from pinot_tpu.parallel.stacked import StackedTable
    from pinot_tpu.spi.config import IndexingConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    monkeypatch.setattr(ops, "scan_backend", lambda: "pallas")
    monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")

    rng = np.random.default_rng(0)
    schema = Schema(
        "lineorder",
        [
            FieldSpec("lo_orderdate", DataType.INT),
            FieldSpec("lo_quantity", DataType.INT),
            FieldSpec("lo_revenue", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    data = {
        "lo_orderdate": (19920101 + rng.integers(0, 2406, ROWS)).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, ROWS).astype(np.int32),
        "lo_revenue": rng.integers(100, 1_000_000, ROWS).astype(np.int64),
    }
    cfg = TableConfig("lineorder", indexing=IndexingConfig(range_index_columns=["lo_quantity"]))
    stacked = StackedTable.build(schema, data, 1, table_config=cfg)
    ctx = parse_query(
        "SELECT lo_orderdate, SUM(lo_revenue) FROM lineorder "
        "WHERE lo_quantity < 25 GROUP BY lo_orderdate LIMIT 2500"
    )

    # concrete inputs staged on a CPU device give the step's pytree, shapes
    # and partition specs; the described chip cannot hold an array
    host = DistributedEngine(mesh=mesh_mod.default_mesh(num_devices=1), hbm_cache_bytes=0)
    host.register_table("lineorder", stacked)
    [(cols, params)] = host.device_batches(host._plan(ctx, stacked), stacked)

    chip_mesh = Mesh(np.asarray(topo.devices[:1]), (mesh_mod.SEG_AXIS,))
    chip = DistributedEngine(mesh=chip_mesh, hbm_cache_bytes=0)
    chip.register_table("lineorder", stacked)
    plan = chip._plan(ctx, stacked)
    assert plan.kind == "groupby_dense" and plan.row_sharded_params  # word-fused filter

    def described(x):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(chip_mesh, x.sharding.spec)
        )

    compiled = plan.fn.lower(*jax.tree_util.tree_map(described, (cols, params))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("combine", [False, True])
@pytest.mark.parametrize("width", [4, 8])
def test_group_program_compiles_its_kernel_once_for_v5e(one_chip, no_compile_cache, monkeypatch, width, combine):
    """The served path's group launch (planner.grouped_plan): `width`
    members' packed columns joined end to end and the per-segment kernel
    scanned over them.  Whatever the width the program holds ONE Mosaic
    kernel, in the body of one while loop, so a wide group compiles about as
    long as a lone segment; and no column is a [width, rows] array, whose
    member axis the chip would tile (planner._join).  With `combine` (what
    the cells' dense group-bys launch since PR 40: segments of one key
    space) the loop carries the tables and no [width, slots] table exists."""
    from pinot_tpu.query import planner
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    monkeypatch.setattr(ops, "scan_backend", lambda: "pallas")
    monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
    planner.plan_cache_clear()

    rows = (1 << 17) + 4321  # whole kernel tiles and a tail
    rng = np.random.default_rng(0)
    schema = Schema(
        "lineorder",
        [
            FieldSpec("d_year", DataType.INT),
            FieldSpec("p_brand", DataType.INT),
            FieldSpec("lo_quantity", DataType.INT),
            FieldSpec("lo_revenue", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    seg = build_segment(schema, {
        "d_year": rng.integers(1992, 1999, rows).astype(np.int32),
        "p_brand": rng.integers(0, 1000, rows).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, rows).astype(np.int32),
        "lo_revenue": rng.integers(100, 1_000_000, rows),
    }, "seg0")
    ctx = parse_query(
        "SELECT d_year, p_brand, SUM(lo_revenue) FROM lineorder WHERE lo_quantity < 25 "
        "GROUP BY d_year, p_brand LIMIT 10000"
    )
    try:
        plan = planner.plan_segment(ctx, seg)
        assert plan.kind == "groupby_dense" and plan.cache_key[2] == "pallas"
        cols = seg.to_device(columns=plan.needed_columns, packed_codes=True)  # on the CPU: shapes only
        assert any("codes_packed" in entry for entry in cols.values())

        def described(x, lead=()):
            return jax.ShapeDtypeStruct(lead + x.shape, x.dtype, sharding=one_chip)

        members = tuple(jax.tree_util.tree_map(described, cols) for _ in range(width))
        stacked = {k: described(v, (width,)) for k, v in plan.params.items()}
        assert planner.combines(plan)
        args = (members, stacked)
        if combine:  # the table the members fold into: the kernel's own output types
            args += (jax.tree_util.tree_map(described, jax.eval_shape(plan.fn, members[0], plan.params)),)
        text = planner.grouped_plan(plan, width, combine).fn.lower(*args).compile().as_text()
    finally:
        planner.plan_cache_clear()
    assert text.count("tpu_custom_call") == 1 and text.count(" while(") == 1
    assert f"groupby_dense_pallas_x{width}" + ("_combined" if combine else "") in text
    assert not re.search(rf"\[{width},\d{{5,}}\]", text)  # the stacked outputs are [width, 7000 slots]
    assert (f"[{width},7000]" in text) == (not combine)


def test_q1_group_program_reduces_its_limbs_in_one_fusion_for_v5e(one_chip, no_compile_cache, monkeypatch):
    """SSB Q1.1's width-8 group program over served segments of 1.5M rows, under
    the chip's arithmetic: the exact scalar SUM (ops.masked_sum, PR 38) is ONE
    multi-output reduction fusion, an int32 scalar a limb, in the body of the
    one while loop; no f32[rows, 5] limb stack, no [rows, 1] column, no pad or
    reshape of a row-length operand on the way to it."""
    from pinot_tpu.query import planner
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    monkeypatch.setattr(ops, "scan_backend", lambda: "pallas")
    monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
    planner.plan_cache_clear()

    rows = 1_500_000
    rng = np.random.default_rng(38)
    schema = Schema("lineorder_flat", [
        FieldSpec("d_year", DataType.INT), FieldSpec("lo_discount", DataType.INT), FieldSpec("lo_quantity", DataType.INT),
        FieldSpec("lo_extendedprice", DataType.INT, role=FieldRole.METRIC),
    ])
    seg = build_segment(schema, {
        "d_year": rng.integers(1992, 1999, rows).astype(np.int32),
        "lo_discount": rng.integers(0, 11, rows).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, rows).astype(np.int32),
        "lo_extendedprice": rng.integers(90_000, 10_000_000, rows).astype(np.int32),
    }, "seg0")
    ctx = parse_query("SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder_flat "
                      "WHERE d_year = 1993 AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25")
    try:
        plan = planner.plan_segment(ctx, seg)
        assert plan.kind == "aggregation"
        cols = seg.to_device(columns=plan.needed_columns, packed_codes=True)  # on the CPU: shapes only
        assert any("codes_packed" in entry for entry in cols.values())

        def described(x, lead=()):
            return jax.ShapeDtypeStruct(lead + x.shape, x.dtype, sharding=one_chip)

        members = tuple(jax.tree_util.tree_map(described, cols) for _ in range(8))
        stacked = {k: described(v, (8,)) for k, v in plan.params.items()}
        text = planner.grouped_plan(plan, 8).fn.lower(members, stacked).compile().as_text()
    finally:
        planner.plan_cache_clear()
    assert "aggregation_pallas_x8" in text and text.count(" while(") == 1
    assert not re.search(rf"\[(?:{rows}|\d{{6,}}),[1-9]\d?\]", text)  # no [rows, L] with a small L, padded or not
    assert not re.search(r"f32\[\d+,65536,\d\]", text)
    sums = [line.split(" fusion(")[0] for line in text.splitlines() if " fusion(" in line and "scalar_sum/reduce_sum" in line]
    assert len(sums) == 1 and sums[0].count("s32[]") == 4, sums  # the four limbs of the product's int32, reduced together


def test_long_scalar_sum_compiles_for_v5e_with_its_limbs_met_in_f64(one_chip, no_compile_cache, monkeypatch):
    """SUM over a LONG column of a served segment's length under `chunked32`:
    eight signed-magnitude limbs reduced together as int32 scalars, met in f64
    (a sum past 2^63 rounds, it does not wrap: PR 38's review), and no
    row-length array of eight columns."""
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
    rows = 1_500_000
    args = (jax.ShapeDtypeStruct((rows,), jnp.int64, sharding=one_chip), jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip))
    text = jax.jit(ops.masked_sum).lower(*args).compile().as_text()
    assert not re.search(rf"\[{rows},[3-9]\]", text)  # [rows, 2] is the column's own uint32 halves
    sums = [line.split(" fusion(")[0] for line in text.splitlines() if " fusion(" in line and "scalar_sum/reduce_sum" in line]
    assert sum(head.count("s32[]") for head in sums) == 8, sums


@pytest.mark.parametrize("query", ["q2_1", "q3_1"])
def test_star_tree_level_group_program_compiles_for_v5e(one_chip, no_compile_cache, monkeypatch, query):
    """The star-tree cell's tree-served program (PR 37): the plan of SSB Q2.1
    over level 4 of the brand tree (a 65,536-row bucket, 7,000 slots) and of
    Q3.1 over level 5 of the nation tree (8,192 rows, 4,375 slots), as the
    width-8 group program 40 segments launch (the combining one since PR 40:
    the levels of a table's segments share their dictionaries): int64 field
    columns narrowed by their stated range, the bound row count, ONE Mosaic
    kernel in one loop, one table of `slots` out."""
    from pinot_tpu.query import planner
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.config import IndexingConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    monkeypatch.setattr(ops, "scan_backend", lambda: "pallas")
    monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
    planner.plan_cache_clear()

    rows = 400_000  # enough uniform rows to fill both levels' combinations
    rng = np.random.default_rng(37)
    region = np.arange(25) % 5
    dims = ["s_region", "d_year", "p_category", "p_brand1", "c_region", "c_nation", "s_nation"]
    schema = Schema("lineorder_flat", [FieldSpec(d, DataType.INT) for d in dims]
                    + [FieldSpec("lo_revenue", DataType.INT, role=FieldRole.METRIC)])
    brand, c_nation, s_nation = rng.integers(0, 1000, rows), rng.integers(0, 25, rows), rng.integers(0, 25, rows)
    block = {
        "s_region": region[s_nation], "d_year": rng.integers(1992, 1999, rows), "p_category": brand // 40,
        "p_brand1": brand, "c_region": region[c_nation], "c_nation": c_nation, "s_nation": s_nation,
        "lo_revenue": rng.integers(90_000, 10_000_000, rows),
    }
    tcfg = TableConfig("lineorder_flat", indexing=IndexingConfig(star_tree_index_configs=[
        {"dimensionsSplitOrder": ["s_region", "d_year", "p_category", "p_brand1"], "functionColumnPairs": ["SUM__lo_revenue", "COUNT__*"]},
        {"dimensionsSplitOrder": ["c_region", "s_region", "d_year", "c_nation", "s_nation"], "functionColumnPairs": ["SUM__lo_revenue", "COUNT__*"]},
    ]))
    seg = build_segment(schema, {k: v.astype(np.int32) for k, v in block.items()}, "seg0", table_config=tcfg)
    sql, bucket, slots = {
        "q2_1": ("SELECT SUM(lo_revenue), d_year, p_brand1 FROM lineorder_flat WHERE p_category = 1 AND s_region = 1 "
                 "GROUP BY d_year, p_brand1 LIMIT 10000", 65_536, 7_000),
        "q3_1": ("SELECT c_nation, s_nation, d_year, SUM(lo_revenue) FROM lineorder_flat WHERE c_region = 2 AND s_region = 2 "
                 "AND d_year >= 1992 AND d_year <= 1997 GROUP BY c_nation, s_nation, d_year LIMIT 100000", 8_192, 4_375),
    }[query]
    try:
        table, asked = planner.QueryPlanning(parse_query(sql)).source(seg)
        assert table.num_docs == bucket and table.level_rows < bucket and asked.rewrite is not None
        plan = asked.plan(table)
        assert plan.kind == "groupby_dense" and plan.cache_key[2] == "pallas" and plan.num_groups == slots
        cols = table.to_device(columns=plan.needed_columns, packed_codes=True)  # on the CPU: shapes only

        def described(x, lead=()):
            return jax.ShapeDtypeStruct(lead + x.shape, x.dtype, sharding=one_chip)

        members = tuple(jax.tree_util.tree_map(described, cols) for _ in range(8))
        stacked = {k: described(v, (8,)) for k, v in plan.params.items()}
        assert planner.combines(plan)
        tables = jax.tree_util.tree_map(described, jax.eval_shape(plan.fn, members[0], plan.params))
        text = planner.grouped_plan(plan, 8, True).fn.lower(members, stacked, tables).compile().as_text()
    finally:
        planner.plan_cache_clear()
    assert text.count("tpu_custom_call") == 1 and text.count(" while(") == 1
    assert "groupby_dense_pallas_x8_combined" in text and f"[8,{slots}]" not in text


@pytest.mark.parametrize("decoded", [True, False], ids=["decoded", "indexed"])
def test_hll_template_streams_the_decoded_column_for_v5e(one_chip, no_compile_cache, monkeypatch, decoded):
    """The sketch cell's HLL template over one 1.5M-row segment whose
    `lo_custkey` dictionary is SF10's (~298,000 of 300,000 keys: past
    segmented._CONTRACT_MAX_TABLE), for a described v5e.  Staged as the plan
    asks (PR 49: `value_columns`, the column decoded) the program has no
    row-length gather and no dictionary operand: the hash streams an
    s32[1500000] parameter into the registers' scatter.  Staged as the parent
    staged it (codes and dictionary), the same plan's kernel gathers: the
    control, and what a caller without the flavour still gets."""
    from pinot_tpu.ops.code_lookup import GATHERED, RESIDENT
    from pinot_tpu.query import planner
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    monkeypatch.setattr(ops, "scan_backend", lambda: "pallas")
    monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
    planner.plan_cache_clear()

    rows = 1_500_000
    rng = np.random.default_rng(49)
    schema = Schema("lineorder_flat", [
        FieldSpec("d_year", DataType.INT), FieldSpec("c_nation", DataType.INT), FieldSpec("s_region", DataType.INT),
        FieldSpec("lo_custkey", DataType.INT), FieldSpec("lo_revenue", DataType.INT, role=FieldRole.METRIC),
    ])
    seg = build_segment(schema, {
        "d_year": rng.integers(1992, 1999, rows).astype(np.int32),
        "c_nation": rng.integers(0, 25, rows).astype(np.int32),
        "s_region": rng.integers(0, 5, rows).astype(np.int32),
        "lo_custkey": rng.integers(1, 300_001, rows).astype(np.int32),
        "lo_revenue": rng.integers(90_000, 10_000_000, rows).astype(np.int32),
    }, "seg0")
    keys = seg.column("lo_custkey").cardinality
    assert segmented._CONTRACT_MAX_TABLE < keys < 300_000
    ctx = parse_query("SELECT d_year, c_nation, DISTINCTCOUNTHLL(lo_custkey, 12) FROM lineorder_flat "
                      "WHERE s_region = 2 GROUP BY d_year, c_nation ORDER BY d_year, c_nation LIMIT 10000")
    try:
        plan = planner.plan_segment(ctx, seg)
        assert plan.kind == "groupby_dense" and plan.value_columns == {"lo_custkey"}
        cols = seg.to_device(  # on the CPU: shapes only
            columns=plan.needed_columns, packed_codes=True, value_columns=plan.value_columns if decoded else None)
        assert sorted(cols["lo_custkey"]) == ["codes", "dict"] + ["values"] * decoded  # the program takes what it reads

        def described(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        text = plan.fn.lower(jax.tree_util.tree_map(described, cols), plan.params).compile().as_text()
        assert plan.lookups[RESIDENT if decoded else GATHERED] == 1
    finally:
        planner.plan_cache_clear()
    gathers = [line for line in text.splitlines() if "value_transform/gather" in line or " gather(" in line]
    assert bool(gathers) == (not decoded), gathers[:2]
    assert (f"s32[{keys}]" in text) == (not decoded)  # the dictionary operand
    # the registers' scatter-max is there either way, fed by the hash of a row-length int32
    assert "sketch_scatter" in text and f"s32[{rows}]" in text and "s32[716800]" in text


def _layer_metric(name):
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks", "layer_metrics", name + ".json")) as f:
        return json.load(f)


COMPACTED_CASES = {
    # (sql, the flat tables its scatters write: a multiple of the template's group space)
    "hll_cust_year_nation": (
        "SELECT d_year, c_nation, DISTINCTCOUNTHLL(lo_custkey, 12) FROM lineorder_flat WHERE s_region = 2 "
        "GROUP BY d_year, c_nation ORDER BY d_year, c_nation LIMIT 10000", 175, {716_800}),
    "q3_2": (
        "SELECT c_city, s_city, d_year, SUM(lo_revenue) FROM lineorder_flat WHERE c_nation = 24 AND s_nation = 24 "
        "AND d_year >= 1992 AND d_year <= 1997 GROUP BY c_city, s_city, d_year ORDER BY d_year ASC, SUM(lo_revenue) DESC "
        "LIMIT 100000", 437_500, {437_500, 1_312_500}),
}


@pytest.mark.parametrize("name", sorted(COMPACTED_CASES))
def test_a_filtered_plans_scatters_take_a_sorted_prefix_for_v5e(one_chip, no_compile_cache, monkeypatch, name):
    """Cell 8's HLL template and cell 5's Q3.2 over one 1.5M-row segment, for a
    described v5e (PR 51): the program sorts ONE int32 operand a mask (the
    registers' payload; the row numbers that the count and the two limb
    tables share), under an event name `compact_sort_ms` matches, and the
    scatters still write the flat tables `table_scatter_ms_per_query` tells
    by their size (`sketch_scatter_ms`, `wide_table_ms`)."""
    from pinot_tpu.query import planner
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    monkeypatch.setattr(ops, "scan_backend", lambda: "pallas")
    monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
    monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
    planner.plan_cache_clear()

    rows = 1_500_000
    rng = np.random.default_rng(51)
    domains = {"d_year": (1992, 1999), "c_nation": (0, 25), "s_nation": (0, 25), "s_region": (0, 5), "c_city": (0, 250),
               "s_city": (0, 250), "lo_custkey": (1, 100_001)}
    schema = Schema("lineorder_flat", [FieldSpec(d, DataType.INT) for d in domains]
                    + [FieldSpec("lo_revenue", DataType.INT, role=FieldRole.METRIC)])
    block = {d: rng.integers(lo, hi, rows).astype(np.int32) for d, (lo, hi) in domains.items()}
    block["lo_revenue"] = rng.integers(90_000, 10_000_000, rows).astype(np.int32)
    seg = build_segment(schema, block, "seg0")
    sql, group_space, tables = COMPACTED_CASES[name]
    try:
        plan = planner.plan_segment(parse_query(sql), seg)
        assert plan.kind == "groupby_dense" and plan.num_groups == group_space
        cols = seg.to_device(columns=plan.needed_columns, packed_codes=True, value_columns=plan.value_columns)  # on the CPU: shapes only

        def described(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        text = plan.fn.lower(jax.tree_util.tree_map(described, cols), plan.params).compile().as_text()
        assert plan.mask_facts.filtered and plan.mask_facts.compactions == 1
    finally:
        planner.plan_cache_clear()
    # a device trace names an event by its instruction: `%name = shape op(operands...`
    instructions = [line.strip() for line in text.splitlines() if re.match(r"\s*(ROOT )?%[\w.\-]+ = ", line)]
    sort = re.compile(_layer_metric("compact_sort_ms")["pattern"])
    sorts = [line for line in instructions if sort.search(line.removeprefix("ROOT "))]
    assert len(sorts) == 1 and f"s32[{rows}]" in sorts[0], sorts
    assert not [line for line in instructions if " sort(" in line and line not in sorts]
    scatter = re.compile(r"^%[\w.\-]+ = [suf]32\[(\d+)\]\S* fusion\(.*kind=kCustom")  # benchmarks/lib/reducers/table_scatter_ms_per_query.py
    written = {int(m.group(1)) for m in map(scatter.match, instructions) if m and int(m.group(1)) % group_space == 0}
    assert written == tables, written
    assert text.count(" while(") == 1 and " conditional(" in text  # the trips over the passing prefix, under the cond
