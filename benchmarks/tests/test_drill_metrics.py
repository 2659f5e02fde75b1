"""The per-layer metrics of the layer `wide and sparse group-by` (PR 31):
each metric's file loads, names a reducer that exists, and reads the expected
number from device events, span trees and warm-up counters written by hand;
a program that lacks the counters and the span reports nothing.  The device
events are lines of the two programs as the TPU's compiler names them
(compiled for a described v5e: Q3.2's wide scatter, Q4.3's sort and 64-bit
slot scatters; run by hand: `python -m pytest benchmarks/tests -q`).
"""
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import harness, opcount, plugins  # noqa: E402

CELL = "ssb_sf1_drill.drill_closed"
LAYER = "wide and sparse group-by"
T = "{0:T(1024)S(1)}"
EVENTS = {  # name -> (count, seconds) over the traced span
    # Q3.2 / Q3.3: one int32 table a count (437,500) and one a limb over three row chunks (1,312,500)
    f"%fusion.53 = s32[437500]{T} fusion(s32[1500000]{T} %get-tuple-element.639, s32[1500000]{T} %convert_element_type.108, s32[]{{:T(128)}} %constant.582..sunk), kind=kCustom, calls=%fused_computation.72.clone.clone": (8, 0.080),
    f"%fusion.54 = s32[1312500]{T} fusion(s32[1500000]{T} %get-tuple-element.652, s32[1500000]{T} %and_convert_fusion.5, s32[]{{:T(128)}} %constant.582..sunk), kind=kCustom, calls=%fused_computation.38.clone.clone": (8, 0.082),
    f"%fusion.55 = s32[1312500]{T} fusion(s32[1500000]{T} %get-tuple-element.652, s32[1500000]{T} %get-tuple-element.656, s32[]{{:T(128)}} %constant.582..sunk), kind=kCustom, calls=%fused_computation.40.clone.clone": (8, 0.083),
    # Q4.3: the sort, a gather and a row-length permutation (neither is a table), three 64-bit slot scatters
    f"%sort.25 = (s32[1500000]{{0:T(1024)}}, s32[1500000]{T}) sort(s32[1500000]{T} %get-tuple-element.657, s32[1500000]{T} %iota.47), dimensions={{0}}, is_stable=true, to_apply=%region_1.6": (4, 0.010),
    f"%fusion.27 = pred[1500000]{{0:T(1024)(128)(4,1)S(1)}} fusion(pred[1500000]{{0:T(1024)(128)(4,1)S(1)}} %get-tuple-element.656, s32[1500160]{T} %pad_clamp_fusion.2), kind=kCustom, calls=%fused_computation.clone.clone": (4, 0.050),
    f"%fusion.31 = s32[1500000]{T} fusion(s32[1500000]{T} %dynamic-slice_subtract_fusion.2, s32[1500160]{T} %custom-call.11), kind=kCustom, calls=%fused_computation.1.clone.clone": (4, 0.040),
    f"%fusion.29 = (u32[100001]{T}, u32[100001]{T}) fusion(u32[100001]{T} %broadcast_in_dim.140, u32[100001]{T} %broadcast_in_dim.141, s32[1500000]{T} %get-tuple-element.659, u32[1500000]{T} %get-tuple-element.660, u32[1500000]{T} %get-tuple-element.661), kind=kCustom, calls=%fused_computation.2.clone.clone": (4, 0.480),
    f"%fusion.30 = (u32[100001]{T}, u32[100001]{T}) fusion(u32[100001]{T} %broadcast_in_dim.144, u32[100001]{T} %broadcast_in_dim.144.clone, s32[1500000]{T} %copy-done.2, u32[1500000]{{0:T(1024)}} %get-tuple-element.665, u32[1500000]{T} %broadcast.389), kind=kCustom, calls=%fused_computation.3.clone.clone": (4, 0.530),
    f"%fusion.32 = (f32[100001]{T}, f32[100001]{T}) fusion(f32[100001]{T} %broadcast_in_dim.147, f32[100001]{T} %broadcast_in_dim.147.clone, s32[1500000]{T} %copy-done.3, f32[1500000]{T} %get-tuple-element.668, f32[1500000]{T} %get-tuple-element.669), kind=kCustom, calls=%fused_computation.4.clone.clone": (4, 0.570),
    # the group programs' loops span their bodies' events: never matched, or a query's time would count twice
    "%while.5 = (u32[]{:T(128)}, u32[4,100000]{1,0:T(4,128)}) while((u32[]{:T(128)}, u32[4,100000]{1,0:T(4,128)}) %tuple.1), condition=%cond, body=%body": (1, 1.69),
    f"%add_reduce_fusion.4 = (f32[437500]{T}, f32[437500]{T}) fusion(f32[3,437500]{{1,0:T(4,128)S(1)}} %reshape.191), kind=kLoop, calls=%fused_computation.8.clone.clone": (8, 0.0007),
}
WEIGHTS = {"q3_2": 1.5, "q3_3": 0.5, "q4_3": 1.0}
MOVED = {"q3_2": {"scan.traced.xla": 1.0, "scan.traced.wide_scatter": 1.0, "scan.traced.lane_unpack": 5.0},
         "q3_3": {"scan.traced.xla": 1.0, "scan.traced.wide_scatter": 1.0}, "q4_3": {"scan.traced.sparse_sort": 1.0}}
BUSY_S = 2.5


def _answer(decodes):
    """One traced answer: a `table_decode` a launch, inside its `collect`."""
    collects = [{"name": "collect", "ms": ms + 5.0, "startMs": 1.0, "attrs": {"segments": 4}, "children": [
        {"name": "table_decode", "ms": ms, "startMs": 4.0, "attrs": {"kind": "groupby_dense", "tableBytes": nbytes, "keySpace": 437500, "groups": 600}}]}
        for ms, nbytes in decodes]
    return {"name": "query", "ms": 100.0, "startMs": 0.0, "children": [
        {"name": "server:server0", "ms": 90.0, "startMs": 1.0, "children": collects}]}


@pytest.fixture()
def ctx():
    config = plugins.load_json("configs", "ssb_flat_sf1_drill")
    return {
        "requests": [SimpleNamespace(spans=_answer([(3.0, 42_000_000)])), SimpleNamespace(spans=_answer([(1.0, 9_600_000), (2.0, 400_000)])),
                     SimpleNamespace(spans=None)],
        "device_trace": {"events": EVENTS, "template_weights": WEIGHTS, "busy_s": BUSY_S, "queries_in_trace": 3.0},
        "warm_moved": MOVED, "config": config, "query_set": plugins.load_json("queries", config["query_set"]),
        "peak": {"name": "TPU v5e", "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


def _least_s(ctx):
    return sum(w * opcount.least_seconds(opcount.query_needs(ctx["config"], ctx["query_set"]["templates"][t]), ctx["peak"])[0]
               for t, w in WEIGHTS.items())


EXPECTED = {
    "wide_table_ms": lambda ctx: (0.080 + 0.082 + 0.083) * 1000.0 / 2.0,  # per Q3.2 / Q3.3 query
    "sparse_sort_ms": lambda ctx: 0.010 * 1000.0 / 1.0,  # per Q4.3 query
    "sparse_scatter_ms": lambda ctx: (0.480 + 0.530 + 0.570) * 1000.0 / 1.0,
    "sparse_roofline": lambda ctx: 100.0 * _least_s(ctx) / BUSY_S,
    "sparse_table_bytes_per_query": lambda ctx: (42_000_000 + 10_000_000) / 2,
    "sparse_decode_ms": lambda ctx: (3.0 + 3.0) / 2,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_file_reads_the_expected_number(ctx, name):
    spec = plugins.load_json("layer_metrics", name)
    assert spec["name"] == name and spec["layer"] == LAYER and spec["moves"] == "latency_p50_ms"
    assert os.path.isfile(os.path.join(HERE, "lib", "reducers", spec["reducer"] + ".py"))
    assert harness.metric_value("layer_metrics", name, ctx) == pytest.approx(EXPECTED[name](ctx))


def test_the_roofline_share_counts_the_key_space_and_stays_under_100(ctx):
    """opcount's bytes hold 8 B a slot of the key space, whatever builds the
    table: 3.5 MB of Q3.2's 22.5 MB, 14 MB of Q4.3's 41 MB; the share divides
    by everything the device did, so a lower bound cannot pass it."""
    needs = opcount.query_needs(ctx["config"], ctx["query_set"]["templates"]["q4_3"])
    assert needs["bytes"] == 6_000_000 * needs["bytes_per_row"] + 8.0 * 1_750_000
    got = harness.metric_value("layer_metrics", "sparse_roofline", ctx)
    assert 0.0 < got < 100.0
    least = _least_s(ctx)
    assert harness.metric_value("layer_metrics", "sparse_roofline", dict(ctx, device_trace=dict(ctx["device_trace"], busy_s=least))) == pytest.approx(100.0)


def test_benchmark_json_lists_them_for_the_drill_cell_alone():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        spec, entry = plugins.load_json("layer_metrics", name), listed[name]
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["source"], entry["unit"], entry["moves"]) == (
            spec["layer"], spec["source"], spec["unit"], spec["moves"])
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ssb_flat_sf1_drill", "drill_closed", 1)
    mix = plugins.load_json("traffic", cell["traffic"])
    assert (mix["loop"], mix["clients"], mix["templates"], mix["sample_checked"], mix["rolling_start_s"]) == (
        "closed", 1, ["q3_2", "q3_3", "q4_3"], 40, 3.0) and "rate_qps" not in mix


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_parent_program_reports_nothing(ctx, name):
    """The parent commit runs the same templates, but moves neither counter
    while they warm and has no `table_decode` span: each reader finds nothing
    and the line leaves the metric out."""
    for r in ctx["requests"]:
        if r.spans:
            for c in r.spans["children"][0]["children"]:
                c["children"] = []
    bare = dict(ctx, warm_moved={"q3_2": {"scan.traced.xla": 1.0}, "q3_3": {"scan.traced.xla": 1.0}, "q4_3": {"scan.traced.lane_unpack": 6.0}})
    assert harness.metric_value("layer_metrics", name, bare) is None


def test_a_table_of_another_key_space_is_not_counted(ctx):
    """A dense plan's 4,375-slot table never scatters, and a scatter whose
    table is no multiple of the served templates' key space is not theirs."""
    events = dict(EVENTS)
    events[f"%fusion.9 = s32[500000]{T} fusion(s32[1500000]{T} %a, s32[1500000]{T} %b, s32[]{{:T(128)}} %c), kind=kCustom, calls=%f"] = (8, 9.0)
    got = harness.metric_value("layer_metrics", "wide_table_ms", dict(ctx, device_trace=dict(ctx["device_trace"], events=events)))
    assert got == pytest.approx(EXPECTED["wide_table_ms"](ctx))
