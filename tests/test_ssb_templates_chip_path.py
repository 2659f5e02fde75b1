"""The benchmark cells' own query templates through the chip's code path.

On the CPU the engine takes `scan_backend()` = "xla" and `accum_policy()` =
"wide"; the chip takes "pallas" and "chunked32", so tier-1 would otherwise
never run what the cells run.  Here the nine dense templates of
`benchmarks/queries/ssb_flat.json` (Q1.1-Q1.3, Q2.1-Q2.3, Q3.1, Q4.1, Q4.2) at
SSB's published literals run over a two-segment table from the benchmark's
generator with the kernel interpreted and 32-bit accumulation (steered as
tests/test_chip_compile.py steers them: both ask `jax.default_backend()`,
which says cpu here), and are compared at difference 0 with the benchmark's
plain numpy reference.  The four drill-down templates (Q3.2-Q3.4, Q4.3: the
wide group table and the sparse sort) have tests/test_sparse_drill_exact.py.
"""
import os
import sys

import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.ops import segmented
from pinot_tpu.query import planner
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils.metrics import METRICS

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
TEMPLATES = ["q1_1", "q1_2", "q1_3", "q2_1", "q2_2", "q2_3", "q3_1", "q4_1", "q4_2"]
SEGMENT_ROWS = (1 << 15) + 4321  # a whole kernel tile and a tail
SEED = 28


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own files: generator, query set, renderer, reference."""
    sys.path.insert(0, BENCH)
    try:
        from lib import plugins, templates
        from lib.references import filter_group_sum

        cfg = plugins.load_json("configs", "ssb_flat_sf1")
        gen = plugins.load_module("datagen", cfg["datagen"])
        queries = plugins.load_json("queries", cfg["query_set"])["templates"]
    finally:
        sys.path.remove(BENCH)
    return cfg, gen, queries, templates, filter_group_sum


@pytest.fixture(scope="module")
def chip_path():
    """scan_backend() = "interpret", accum_policy() = "chunked32" for this
    module's plans; the plan cache does not key on the accumulation policy,
    so it is emptied on the way in and on the way out."""
    mp = pytest.MonkeyPatch()
    mp.setenv("PINOT_TPU_SCAN_BACKEND", "interpret")
    ops.scan_backend.cache_clear()
    mp.setattr(ops, "accum_policy", lambda: "chunked32")
    mp.setattr(segmented, "accum_policy", lambda: "chunked32")
    planner.plan_cache_clear()
    yield
    mp.undo()
    ops.scan_backend.cache_clear()
    planner.plan_cache_clear()


@pytest.fixture(scope="module")
def table(bench, chip_path):
    cfg, gen, _, _, _ = bench
    schema = Schema(
        cfg["table"],
        [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]]) for c in cfg["columns"]],
    )
    tcfg = TableConfig(cfg["table"], indexing=IndexingConfig.from_dict(cfg["table_config"]))
    coord = Coordinator(replication=1)
    coord.register_server(ServerInstance("server0"))
    coord.add_table(schema, tcfg)
    wide = {"INT": np.int32, "LONG": np.int64}
    blocks = []
    for i in range(2):
        block = gen.make_segment(cfg, SEED, i, SEGMENT_ROWS)
        blocks.append(block)
        cols = {c["name"]: block[c["name"]].astype(wide[c["type"]]) for c in cfg["columns"]}
        coord.add_segment(cfg["table"], build_segment(schema, cols, f"seg{i}", table_config=tcfg))
    return Broker(coord), blocks


@pytest.mark.parametrize("name", TEMPLATES)
def test_template_equals_the_plain_reference(name, bench, table):
    _, _, queries, templates, reference = bench
    broker, blocks = table
    template = queries[name]
    spec = template["reference"]
    assert spec["kind"] == "filter_group_sum"
    kernel = METRICS.counter("scan.traced.interpret").value
    got = broker.query(templates.render(template, template["ssb"]))
    assert not got.stats.partial_result and got.stats.num_segments_processed == len(blocks)
    want = reference.answer(spec, template["ssb"], blocks)
    equal, numbers = reference.compare(spec, list(got.columns), [list(r) for r in got.rows], want)
    assert equal, numbers
    if spec["group_by"]:
        assert want["groups"], "the published literals select no row of this table: the case shows nothing"
        # a dense group-by is served by the kernel, interpreted; Q1's scalar sum by XLA
        assert METRICS.counter("scan.traced.interpret").value > kernel
    else:
        assert want["matched"] > 0
