"""Sketch aggregates on the served path (PR 43): the cell
`ssb_sf10_sketch.sketch_closed`'s three templates through `Broker.query` over
segments whose `lo_custkey` dictionaries all differ.

Under the chip's arithmetic (the kernel interpreted, 32-bit accumulation,
steered as tests/test_ssb_templates_chip_path.py steers it) and under the
CPU's own ("wide"), each template's answer is held to the benchmark's plain
reference at the configuration's limits: the HLL count EQUAL to the
reference's own sketch and within HyperLogLog's law of the exact count, the
percentile within a bin width of the exact one, the SUM exact.  One compiled
kernel a query shape whatever each segment's dictionary holds; the group
program folds the members' [groups, m] tables on the device into the table
the host's merge would give; the bin of a value on a bin's edge is the same
under both policies.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.ops import segmented
from pinot_tpu.query import executor, planner, sketches
from pinot_tpu.query.functions import FIELD_COMBINE, combine_field
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
from pinot_tpu.utils.metrics import METRICS

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
TEMPLATES = ["hll_cust_year_nation", "p95_rev_year_nation", "hll_cust_sum_year_category"]
SEGMENTS, SEGMENT_ROWS, CUSTOMERS, SEED = 6, 30_000, 8_000, 43


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own files: configuration, generator, query set, renderer, reference."""
    sys.path.insert(0, BENCH)
    try:
        from lib import plugins, templates
        from lib.references import filter_group_sketch

        cfg = dict(plugins.load_json("configs", "ssb_flat_sf10_sketch"), customers=CUSTOMERS)
        gen = plugins.load_module("datagen", cfg["datagen"])
        queries = plugins.load_json("queries", cfg["query_set"])["templates"]
    finally:
        sys.path.remove(BENCH)
    return cfg, gen, queries, templates, filter_group_sketch


@pytest.fixture(scope="module")
def blocks(bench):
    cfg, gen, _, _, _ = bench
    return [gen.make_segment(cfg, SEED, i, SEGMENT_ROWS) for i in range(SEGMENTS)]


@pytest.fixture(scope="module")
def segments(bench, blocks):
    cfg = bench[0]
    schema = Schema(
        cfg["table"],
        [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]]) for c in cfg["columns"]],
    )
    tcfg = TableConfig(cfg["table"], indexing=IndexingConfig.from_dict(cfg["table_config"]))
    segs = [
        build_segment(schema, {c["name"]: b[c["name"]].astype(np.int32) for c in cfg["columns"]}, f"seg{i}", table_config=tcfg)
        for i, b in enumerate(blocks)
    ]
    prints = {s.column("lo_custkey").dictionary.fingerprint() for s in segs}
    assert len(prints) == SEGMENTS, "the case needs a lo_custkey dictionary a segment"
    return schema, tcfg, segs


def _policy(mp, policy):
    """The chip's arithmetic ("chunked32": 32-bit accumulation, the dense kernel interpreted) or the CPU's own
    ("wide"); the plan cache does not key on the accumulation policy, so it is emptied on the way in and out."""
    if policy == "chunked32":
        mp.setenv("PINOT_TPU_SCAN_BACKEND", "interpret")
        mp.setattr(ops, "accum_policy", lambda: "chunked32")
        mp.setattr(segmented, "accum_policy", lambda: "chunked32")
    ops.scan_backend.cache_clear()
    planner.plan_cache_clear()


@pytest.fixture(params=["chunked32", "wide"])
def served(request, bench, segments):
    """(policy, broker, server) over the six segments on one server."""
    schema, tcfg, segs = segments
    mp = pytest.MonkeyPatch()
    _policy(mp, request.param)
    coord = Coordinator(replication=1)
    server = ServerInstance("server0")
    coord.register_server(server)
    coord.add_table(schema, tcfg)
    for seg in segs:
        coord.add_segment(bench[0]["table"], seg)
    yield request.param, Broker(coord), server
    mp.undo()
    ops.scan_backend.cache_clear()
    planner.plan_cache_clear()


@pytest.mark.parametrize("name", TEMPLATES)
def test_template_meets_the_plain_reference_at_the_configurations_limits(name, bench, blocks, served):
    _, _, queries, templates, reference = bench
    _, broker, _ = served
    template = queries[name]
    spec = template["reference"]
    assert spec["kind"] == "filter_group_sketch"
    got = broker.query(templates.render(template, template["ssb"]))
    assert not got.stats.partial_result and got.stats.num_segments_processed == SEGMENTS
    want = reference.answer(spec, template["ssb"], blocks)
    equal, numbers = reference.compare(spec, list(got.columns), [list(r) for r in got.rows], want)
    assert equal, numbers
    assert want["groups"] == 175 == len(got.rows)
    if "hll_vs_sketch_max_abs_diff" in numbers:
        # counts past the small-count allowance: HyperLogLog's own law is what held them
        assert numbers["exact_count_min"] > 8 / numbers["hll_rel_err_limit"] and numbers["hll_vs_sketch_max_abs_diff"] == 0
        # the reference's sketch at a register fewer is another answer: the limit-0 comparison sees it
        low = reference.answer(spec, template["ssb"], blocks, log2m_less=1)
        assert not reference.compare(spec, *reference.served_from(low, spec), want)[0]
    else:
        coarse = reference.answer(spec, template["ssb"], blocks, bins_divisor=2)
        assert not reference.compare(spec, *reference.served_from(coarse, spec), want)[0]


def test_one_kernel_a_query_shape_whatever_each_dictionary_holds(bench, served):
    """Six segments, six `lo_custkey` dictionaries: a template compiles ONE kernel (and the ladder's two group
    programs, widths 4 and 2), and a warm query makes two jitted calls, binds six plans and compiles nothing."""
    _, _, queries, templates, _ = bench
    _, broker, server = served
    for name in TEMPLATES:
        sql = templates.render(queries[name], queries[name]["ssb"])
        compiles, programs = METRICS.counter("compile.sse.compiles").value, METRICS.counter("compile.group.programs").value
        broker.query(sql)
        assert METRICS.counter("compile.sse.compiles").value == compiles + 1, name
        assert METRICS.counter("compile.group.programs").value == programs + 2, name
        launches = server.metrics.counter("server.launches").value
        binds = METRICS.counter("compile.sse.binds").value
        broker.query(sql.replace("= 1 ", "= 3 ").replace("= 2 ", "= 3 "))  # another region: the same shape
        assert METRICS.counter("compile.sse.compiles").value == compiles + 1
        assert METRICS.counter("compile.group.programs").value == programs + 2
        assert server.metrics.counter("server.launches").value == launches + 2
        assert METRICS.counter("compile.sse.binds").value == binds + SEGMENTS
        assert server.metrics.counter("server.combinedSegments").value >= SEGMENTS


@pytest.mark.parametrize("name", TEMPLATES)
def test_the_folded_table_is_the_hosts_merge_of_the_segments_tables(name, bench, segments, served):
    """The group program's ONE table (registers met by max, bins by addition, on the device) against the
    per-segment kernels' tables merged on the host, field by field, cell by cell."""
    _, _, queries, templates, _ = bench
    _, _, segs = segments
    _, broker, server = served
    ctx = parse_query("SET trace = true; " + templates.render(queries[name], queries[name]["ssb"]))
    broker._inject_global_ranges(ctx, bench[0]["table"])  # the table's [min, max]: what every segment bins by
    results, stats = server.execute(ctx, [s.name for s in segs])
    (folded,) = [r for r in results if r is not None]
    decode = [n for n in _spans(stats.trace, "table_decode")]
    assert [d["attrs"]["tables"] for d in decode] == [1]
    planning = planner.QueryPlanning(ctx, server.shapes[bench[0]["table"]])
    plans = [planning.plan(s) for s in segs]
    assert planner.combines(plans[0]) and all(p.fn is plans[0].fn for p in plans)
    presence, merged = None, None
    for plan, seg in zip(plans, segs):
        cols = seg.to_device(columns=plan.needed_columns, packed_codes=True, dict_rows=plan.dict_sizes)
        mine, partials = jax.device_get(plan.fn(cols, plan.params))
        if merged is None:
            presence, merged = mine, [dict(p) for p in partials]
        else:
            presence = presence + mine
            merged = [{f: combine_field(f, m[f], p[f]) for f in m} for m, p in zip(merged, partials)]
    assert np.array_equal(folded.dense.presence, presence)
    vectors = 0
    for ours, theirs in zip(folded.dense.partials, merged):
        assert set(ours) == set(theirs) <= set(FIELD_COMBINE)
        for f in ours:
            assert np.asarray(ours[f]).dtype == np.asarray(theirs[f]).dtype and np.array_equal(ours[f], theirs[f]), f
            vectors += int(np.ndim(ours[f]) == 2) * np.asarray(ours[f]).nbytes
    assert decode[0]["attrs"]["sketchBytes"] == vectors == 175 * 4096 * 4  # int32 registers or int64 bins


def _spans(tree, name):
    out = [tree] if tree["name"] == name else []
    for c in tree.get("children", ()):
        out.extend(_spans(c, name))
    return out


@pytest.mark.parametrize("policy", ["chunked32", "wide"])
def test_a_value_on_a_bins_edge_lands_in_the_same_bin_under_both_policies(policy, monkeypatch):
    """The percentile's bin of lo_revenue's boundary values: every integer next to a bin's edge over the cell's
    own range, through the jitted histogram, against float32 arithmetic done plainly in numpy (subtract, one
    multiply by the float32 scale, floor): the multiply is where a chip and a CPU could part."""
    _policy(monkeypatch, policy)
    lo, hi, bins = 81_000, 9_999_000, 2048  # lo_revenue over SF10's table, about: every value is under 2^24
    fn = sketches.PercentileFunction(95.0, lo, hi, bins)
    edges = lo + (hi - lo) * np.arange(bins + 1) / bins
    values = np.unique(np.clip(np.concatenate([np.floor(edges) + d for d in (-1, 0, 1, 2)]), lo, hi)).astype(np.int32)
    scale = np.float32(bins / (float(hi) - float(lo)))
    want = np.clip(np.floor((values.astype(np.float32) - np.float32(lo)) * scale).astype(np.int64), 0, bins - 1)
    got = np.asarray(jax.jit(fn._bin)(jnp.asarray(values)))
    assert np.array_equal(got, want)
    # float32 parts from the real quotient by the bin next door at most, and only within 2048 x 2^-23 of a
    # width of an edge (some of these values are: the reference's limit of 1.001 widths has that much room)
    real = (values.astype(np.float64) - lo) * bins / (hi - lo)
    off = got != np.clip(np.floor(real), 0, bins - 1)
    assert np.abs(got - np.clip(np.floor(real), 0, bins - 1)).max() <= 1 and 0 < off.mean() < 0.2
    assert np.abs(real[off] - np.rint(real[off])).max() < bins * 2.0**-23
    # the table the kernel scatters: 3 groups x 2,048 bins, exact counts, the masked rows nowhere
    keys = (np.arange(len(values)) % 3).astype(np.int32)
    mask = np.arange(len(values)) % 5 != 0
    table = jax.jit(lambda v, m, k: fn.partial_grouped(v, m, k, 3))(jnp.asarray(values), jnp.asarray(mask), jnp.asarray(keys))
    hist = np.zeros((3, bins), np.int64)
    np.add.at(hist, (keys[mask], want[mask]), 1)
    assert np.asarray(table["hist"]).dtype == np.int64 and np.array_equal(table["hist"], hist)
    planner.plan_cache_clear()


def test_registers_are_small_integers_all_the_way():
    """A register is an int32 from the scatter on (no float holds it), 0 where a cell saw no row, and the device's
    hash, bucket and rho are the reference's (murmur3's finalizer, low 12 bits, leading zeros of the rest + 1)."""
    sys.path.insert(0, BENCH)
    try:
        from lib.references import filter_group_sketch as reference
    finally:
        sys.path.remove(BENCH)
    rng = np.random.default_rng(43)
    values = np.concatenate([rng.integers(-(2**31), 2**31, 50_000), np.arange(300_000), [0, -1, 2**31 - 1, -(2**31)]]).astype(np.int32)
    fn = sketches.DistinctCountHLLFunction(12, device_hash=True)
    bucket, rho = jax.jit(fn._bucket_rho)(jnp.asarray(values))
    want_bucket, want_rho = reference.bucket_rho(values, 12)
    assert np.array_equal(bucket, want_bucket) and np.array_equal(rho, want_rho)
    assert int(np.max(rho)) <= 21 and int(np.min(rho)) >= 1
    keys = (np.arange(len(values)) % 7).astype(np.int32)
    mask = np.arange(len(values)) % 3 != 0
    regs = jax.jit(lambda v, m, k: fn.partial_grouped(v, m, k, 7))(jnp.asarray(values), jnp.asarray(mask), jnp.asarray(keys))["hll"]
    assert regs.dtype == jnp.int32 and regs.shape == (7, 4096)
    assert np.array_equal(regs, reference.registers(values[mask], keys[mask].astype(np.int64), 7, 12))


def test_a_long_column_hashes_alike_whatever_width_a_segment_stores_it_in():
    """A LONG column's dictionary rides the device narrowed to int32 where a segment's range fits: its values are
    hashed as the 64-bit ones they are, so that segment's registers meet another's that stores them whole."""
    schema = Schema("t", [FieldSpec("k", DataType.INT), FieldSpec("v", DataType.LONG)])
    small = np.arange(1, 2001, dtype=np.int64) * 1_000
    narrow = build_segment(schema, {"k": np.zeros(2000, np.int32), "v": small}, "narrow")
    whole = build_segment(schema, {"k": np.zeros(2001, np.int32), "v": np.append(small, np.int64(1) << 40)}, "whole")
    assert narrow.column("v").dictionary.device_values().dtype == np.int32
    assert whole.column("v").dictionary.device_values().dtype == np.int64
    ctx = parse_query("SELECT k, DISTINCTCOUNTHLL(v, 12) FROM t GROUP BY k")
    regs = []
    for seg in (narrow, whole):
        result, _ = executor.collect_segment(executor.launch_segment(ctx, seg))
        regs.append(np.asarray(result.dense.partials[0]["hll"]))
    # the one value only `whole` holds moves at most its own register
    assert (regs[0] != regs[1]).sum() <= 1 and (regs[0] > 0).sum() > 1500
    planner.plan_cache_clear()
