"""The per-layer metrics that read the program's launch and front-door spans
and timers (PR 24): each metric's file loads, names a reducer that exists,
and reads the expected number from a span tree and a pair of counter
snapshots written by hand (run by hand: `python -m pytest benchmarks/tests -q`).
"""
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import harness, plugins  # noqa: E402


def _launch(seg, start, plan, ship, enqueue, cpu):
    """A `launch:<segment>` span as cluster/server.py renders it."""
    kids, at = [], start
    for name, ms in (("launch_plan", plan), ("launch_ship", ship), ("launch_enqueue", enqueue)):
        kids.append({"name": name, "ms": ms, "startMs": at, "cpuMs": ms, "attrs": {"segment": seg}})
        at += ms
    kids[-1]["children"] = [{"name": "launch_release", "ms": enqueue / 4, "startMs": at - enqueue / 4,
                             "cpuMs": 0.01, "attrs": {"segment": seg}}]
    return {"name": f"launch:{seg}", "ms": plan + ship + enqueue + 0.5, "startMs": start, "cpuMs": cpu,
            "attrs": {"segment": seg, "cpuMs": cpu}, "children": kids}


def _answer(scale):
    """One traced answer's tree: two segments on one server."""
    launches = [_launch("seg0", 1.0, 2.0 * scale, 0.5, 1.0, 3.0 * scale),
                _launch("seg1", 6.0, 1.0 * scale, 0.25, 0.75, 1.5 * scale)]
    server = {"name": "server:server0", "ms": 20.0, "startMs": 0.0, "cpuMs": 9.0, "t0Ns": 5_000_000, "thread": "t",
              "children": [{"name": "dispatch", "ms": 10.0, "startMs": 0.5, "cpuMs": 8.0,
                            "attrs": {"launches": 2}, "children": launches},
                           {"name": "device_wait", "ms": 4.0, "startMs": 10.5, "cpuMs": 0.1, "attrs": {"launches": 2}},
                           {"name": "collect", "ms": 1.5 * scale, "startMs": 14.5, "cpuMs": 1.0},
                           {"name": "collect", "ms": 0.5 * scale, "startMs": 16.5, "cpuMs": 0.4}]}
    return {"name": "query", "ms": 30.0, "startMs": 0.0, "cpuMs": 12.0, "t0Ns": 4_000_000, "thread": "t",
            "attrs": {"queryId": "b_1", "parseMs": 0.4 * scale, "httpReadMs": 0.2},
            "children": [{"name": "plan", "ms": 0.3, "startMs": 0.1, "cpuMs": 0.3},
                         {"name": "scatter", "ms": 25.0, "startMs": 1.0, "cpuMs": 10.0, "children": [
                             {"name": "round:0", "ms": 24.0, "startMs": 1.1, "cpuMs": 10.0, "children": [
                                 {"name": "server_execute", "ms": 23.0, "startMs": 1.2, "cpuMs": 10.0,
                                  "children": [server]}]}]},
                         {"name": "reduce", "ms": 0.8 * scale, "startMs": 27.0, "cpuMs": 0.8}]}


@pytest.fixture()
def ctx():
    reqs = [SimpleNamespace(spans=_answer(1.0)), SimpleNamespace(spans=_answer(3.0)), SimpleNamespace(spans=None)]
    return {"requests": reqs,
            "counters_before": {"timer:rest.serializeMs:count": 10.0, "timer:rest.serializeMs:total_ms": 5.0},
            "counters_after": {"timer:rest.serializeMs:count": 14.0, "timer:rest.serializeMs:total_ms": 8.0}}


# mean over the two traced answers (scales 1 and 3) of the per-query sum
EXPECTED = {
    "launch_plan_ms": (3.0 + 9.0) / 2,
    "launch_ship_ms": 0.75,
    "launch_enqueue_ms": 1.75,
    "launch_release_ms": 1.75 / 4,
    "launch_cpu_ms": (4.5 + 13.5) / 2,
    "collect_ms": (2.0 + 6.0) / 2,
    "reduce_ms": (0.8 + 2.4) / 2,
    "frontdoor_parse_ms": (0.4 + 1.2) / 2,
    "frontdoor_serialize_ms": 3.0 / 4.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_file_reads_the_expected_number(ctx, name):
    spec = plugins.load_json("layer_metrics", name)
    assert spec["name"] == name and spec["unit"] == "ms" and spec["moves"] == "latency_p50_ms"
    assert os.path.isfile(os.path.join(HERE, "lib", "reducers", spec["reducer"] + ".py"))
    assert harness.metric_value("layer_metrics", name, ctx) == pytest.approx(EXPECTED[name])


def test_benchmark_json_lists_each_metric_under_its_files_layer_and_source():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in EXPECTED:
        spec = plugins.load_json("layer_metrics", name)
        entry = listed[name]
        assert "workloads" not in entry  # every cell reports them
        assert (entry["layer"], entry["source"], entry["unit"], entry["moves"]) == (
            spec["layer"], spec["source"], spec["unit"], spec["moves"])


@pytest.mark.parametrize("name", sorted(n for n in EXPECTED if n != "frontdoor_serialize_ms"))
def test_untraced_answers_report_nothing(name):
    empty = {"requests": [SimpleNamespace(spans=None)], "counters_before": {}, "counters_after": {}}
    assert harness.metric_value("layer_metrics", name, empty) is None


@pytest.mark.parametrize("name", ["launch_cpu_ms", "frontdoor_parse_ms"])
def test_a_program_without_the_attr_reports_nothing(ctx, name):
    """The parent commit's spans carry neither `cpuMs` nor `parseMs`."""
    def strip(node):
        node.get("attrs", {}).pop("cpuMs", None)
        node.get("attrs", {}).pop("parseMs", None)
        for c in node.get("children", ()):
            strip(c)
    for r in ctx["requests"]:
        if r.spans:
            strip(r.spans)
    assert harness.metric_value("layer_metrics", name, ctx) is None


def test_timer_mean_ms_is_none_without_the_timer_or_without_updates(ctx):
    spec = plugins.load_json("layer_metrics", "frontdoor_serialize_ms")
    reducer = plugins.load_module("reducers", "timer_mean_ms")
    absent = dict(ctx, counters_before={}, counters_after={"compile.sse.compiles": 3.0})
    assert reducer.reduce(spec, absent) is None
    still = dict(ctx, counters_after=dict(ctx["counters_before"]))
    assert reducer.reduce(spec, still) is None
    first = dict(ctx, counters_before={})  # the timer appeared inside the window
    assert reducer.reduce(spec, first) == pytest.approx(8.0 / 14.0)
