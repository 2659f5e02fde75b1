"""`sparse_limb_scatter_ms` (PR 42), a data file alone: the device time of the
int32 scatters a sparse plan's slot tables are built by since that PR, per
traced query whose plan sorts.  The events are the v5e compiler's own names,
read from an AOT compile of `planner.sparse_grouped_tables` at Q4.3's shape
(1.5M rows a segment, 100,000 slots + the overflow slot, a key space of
1,750,000, an int32 expression summed) for a described v5e: the first case
reads lines copied from that compile, in the long form the profiler's trace
gives them; the last compiles it again where the sandbox can describe the
chip, and the metric's pattern finds 1 key + 1 count + 4 limb tables under
`chunked32` and the keys alone beside two 64-bit tuples under "wide".  Run by hand:
`python -m pytest benchmarks/tests -q`.
"""
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import harness, plugins  # noqa: E402

NAME = "sparse_limb_scatter_ms"
CELL = "ssb_sf1_drill.drill_closed"
T = "{0:T(1024)S(1)}"
ROWS = f"s32[1500000]{T}"
LIMB_EVENTS = {  # name -> (count, seconds): a Q4.3 group program's body, four members a traced query
    f"%fusion.2 = s32[100001]{T} fusion({ROWS} %compare_select_fusion, {ROWS} %copy-done.3, s32[]{{:T(128)}} %constant.40), kind=kCustom, calls=%fused_computation.75": (4, 0.040),
    f"%fusion.3 = s32[100001]{T} fusion({ROWS} %get-tuple-element.77, {ROWS} %get-tuple-element.82, s32[]{{:T(128)}} %constant.45), kind=kCustom, calls=%fused_computation.72": (4, 0.041),
    f"%fusion.4 = s32[300003]{T} fusion({ROWS} %get-tuple-element.76, {ROWS} %get-tuple-element.78, s32[]{{:T(128)}} %constant.45), kind=kCustom, calls=%fused_computation.44": (4, 0.042),
    f"%fusion.5 = s32[300003]{T} fusion({ROWS} %get-tuple-element.76, {ROWS} %get-tuple-element.80, s32[]{{:T(128)}} %constant.45), kind=kCustom, calls=%fused_computation.42": (4, 0.043),
    f"%fusion.6 = s32[300003]{T} fusion({ROWS} %get-tuple-element.76, {ROWS} %get-tuple-element.79, s32[]{{:T(128)}} %constant.45), kind=kCustom, calls=%fused_computation.40": (4, 0.044),
    f"%fusion.7 = s32[300003]{T} fusion({ROWS} %get-tuple-element.76, {ROWS} %get-tuple-element.81, s32[]{{:T(128)}} %constant.45), kind=kCustom, calls=%fused_computation.38": (4, 0.045),
}
OTHER_EVENTS = {  # the same programs' other events, and the cell's wide table: none is a slot table
    f"%sort = (s32[1500000]{{0:T(1024)}}, {ROWS}) sort({ROWS} %convert_select_fusion, {ROWS} %iota.5), dimensions={{0}}, is_stable=true, to_apply=%region_0.2": (4, 0.010),
    f"%fusion = pred[1500000]{{0:T(1024)(128)(4,1)}} fusion(pred[1500000]{{0:T(1024)(128)(4,1)S(1)}} %copy-done.2, s32[1500160]{T} %pad_clamp_fusion), kind=kCustom, calls=%fused_computation": (4, 0.050),
    f"%fusion.1 = {ROWS} fusion({ROWS} %copy-done, s32[1500160]{T} %pad_clamp_fusion), kind=kCustom, calls=%fused_computation.1": (4, 0.040),
    f"%fusion.53 = s32[437500]{T} fusion({ROWS} %get-tuple-element.639, {ROWS} %convert_element_type.108, s32[]{{:T(128)}} %constant.582..sunk), kind=kCustom, calls=%fused_computation.72.clone.clone": (8, 0.080),
    f"%fusion.54 = s32[1312500]{T} fusion({ROWS} %get-tuple-element.652, {ROWS} %and_convert_fusion.5, s32[]{{:T(128)}} %constant.582..sunk), kind=kCustom, calls=%fused_computation.38.clone.clone": (8, 0.082),
    f"%add_reduce_fusion.2 = s32[100001]{T} fusion(s32[3,100001]{{1,0:T(4,128)S(1)}} %bitcast.9), kind=kLoop, calls=%fused_computation.9": (4, 0.0007),
    "%while.5 = (u32[]{:T(128)}, u32[4,100000]{1,0:T(4,128)}) while((u32[]{:T(128)}, u32[4,100000]{1,0:T(4,128)}) %tuple.1), condition=%cond, body=%body": (1, 0.30),
}
PARENT_EVENTS = {  # the parent's three 64-bit scatters (benchmarks/tests/test_drill_metrics.py): tuples of 32-bit halves
    f"%fusion.29 = (u32[100001]{T}, u32[100001]{T}) fusion(u32[100001]{T} %broadcast_in_dim.140, u32[100001]{T} %broadcast_in_dim.141, {ROWS} %get-tuple-element.659, u32[1500000]{T} %get-tuple-element.660, u32[1500000]{T} %get-tuple-element.661), kind=kCustom, calls=%fused_computation.2.clone.clone": (4, 0.480),
    f"%fusion.32 = (f32[100001]{T}, f32[100001]{T}) fusion(f32[100001]{T} %broadcast_in_dim.147, f32[100001]{T} %broadcast_in_dim.147.clone, {ROWS} %copy-done.3, f32[1500000]{T} %get-tuple-element.668, f32[1500000]{T} %get-tuple-element.669), kind=kCustom, calls=%fused_computation.4.clone.clone": (4, 0.570),
}
WEIGHTS = {"q3_2": 1.5, "q3_3": 0.5, "q4_3": 1.25}
MOVED = {"q3_2": {"scan.traced.xla": 1.0, "scan.traced.wide_scatter": 1.0}, "q3_3": {"scan.traced.xla": 1.0, "scan.traced.wide_scatter": 1.0},
         "q4_3": {"scan.traced.sparse_sort": 1.0, "scan.traced.sparse_limb_scatter": 1.0}}


def _ctx(events, moved=MOVED):
    return {"device_trace": {"events": events, "template_weights": WEIGHTS, "busy_s": 1.0, "queries_in_trace": 3.25}, "warm_moved": moved}


def test_the_six_slot_tables_are_read_per_traced_q4_3_and_nothing_else_is():
    spec = plugins.load_json("layer_metrics", NAME)
    assert (spec["name"], spec["layer"], spec["unit"], spec["moves"], spec["source"]) == (
        NAME, "wide and sparse group-by", "ms", "latency_p50_ms", "device_trace")
    assert spec["reducer"] == "device_event_ms_per_query" and spec["served_by_counter"] == "scan.traced.sparse_sort"
    want = sum(sec for _, sec in LIMB_EVENTS.values()) * 1000.0 / WEIGHTS["q4_3"]
    assert harness.metric_value("layer_metrics", NAME, _ctx({**LIMB_EVENTS, **OTHER_EVENTS})) == pytest.approx(want)
    # the 64-bit form beside it (a float sum keeps it) is sparse_scatter_ms's, not this metric's
    assert harness.metric_value("layer_metrics", NAME, _ctx({**LIMB_EVENTS, **OTHER_EVENTS, **PARENT_EVENTS})) == pytest.approx(want)
    assert harness.metric_value("layer_metrics", "sparse_scatter_ms", _ctx({**LIMB_EVENTS, **OTHER_EVENTS})) is None


def test_the_parent_program_and_an_untraced_run_report_nothing():
    """The parent writes tuples of 32-bit halves: the reader finds no event, returns None and does not raise."""
    assert harness.metric_value("layer_metrics", NAME, _ctx({**PARENT_EVENTS, **OTHER_EVENTS})) is None
    no_sort = {"q3_2": MOVED["q3_2"], "q3_3": MOVED["q3_3"], "q4_3": {"scan.traced.lane_unpack": 6.0}}
    assert harness.metric_value("layer_metrics", NAME, _ctx({**LIMB_EVENTS, **OTHER_EVENTS}, no_sort)) is None
    assert harness.metric_value("layer_metrics", NAME, {"device_trace": None, "warm_moved": MOVED}) is None


def test_benchmark_json_lists_it_for_the_drill_cell_alone_at_the_end():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, spec = bench["per_layer"][-1], plugins.load_json("layer_metrics", NAME)
    assert entry == {"name": NAME, "unit": spec["unit"], "better": "lower", "source": spec["source"], "layer": spec["layer"],
                     "moves": spec["moves"], "workloads": [CELL]}
    assert NAME in {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
    assert NAME not in {m["name"] for m in harness.load_cell("ssqe_exp001_50seg.aggs_closed")["per_layer"]}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, harness.REPO)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        return SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("policy,tables,tuples", [("chunked32", ["s32[100001]"] * 2 + ["s32[300003]"] * 4, 0), ("wide", ["s32[100001]"], 2)])
def test_the_pattern_finds_the_v5e_compilers_own_names(one_chip, monkeypatch, policy, tables, tuples):
    """An AOT compile of Q4.3's sparse kernel for a described v5e (~30 s): the instructions the metric's pattern
    matches are the slot tables' scatters, all of them and nothing else.  With the 64-bit accumulation forced
    ("wide": what a float sum keeps on the chip) the count and the sum are `sparse_scatter_ms`'s tuples again
    and only the keys, which scatter as the int32 they were sorted as, are this metric's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from pinot_tpu import ops
    from pinot_tpu.query import planner
    from pinot_tpu.query.functions import get_agg_function

    monkeypatch.setattr(ops, "accum_policy", lambda: policy)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # an entry written for a described chip cannot be read back
    cc.reset_cache()
    try:
        n, slots, groups = 1_500_000, 100_000, 1_750_000
        shape = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)  # noqa: E731
        fn = get_agg_function("sum")
        text = jax.jit(lambda v, m, k: planner.sparse_grouped_tables([fn], [(v, m)], m, k, slots, None, num_groups=groups)).lower(
            shape(jnp.int32), shape(jnp.bool_), shape(jnp.int64)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    pat = re.compile(plugins.load_json("layer_metrics", NAME)["pattern"])
    entry = [ln.strip() for ln in text.splitlines() if re.match(r"\s*%[\w.\-]+ = ", ln)]  # `ROOT %...` lines repeat a fused computation's
    hits = [ln for ln in entry if pat.search(ln)]
    assert sorted(re.match(r"%\S+ = (s32\[\d+\])", ln).group(1) for ln in hits) == sorted(tables)
    assert all("scatter" in ln.split("op_name=")[1].split('"')[1] for ln in hits)
    old = re.compile(plugins.load_json("layer_metrics", "sparse_scatter_ms")["pattern"])
    assert len([ln for ln in entry if old.search(ln)]) == tuples
