"""The per-layer metrics of the layer `broker scatter` (PR 26): each metric's
file loads, names a reducer that exists, reads the expected number from a
span tree and a pair of counter snapshots written by hand, and reports
nothing from a program that lacks the span or the counters (run by hand:
`python -m pytest benchmarks/tests -q`).
"""
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import harness, plugins  # noqa: E402

CELL = "ssb_sf20_4srv.groupby_closed"


def _answer(first_ms, second_ms, scatter_ms, rounds=1):
    """One traced answer as cluster/broker.py renders a two-server scatter."""
    calls = [{"name": "server_execute", "ms": ms, "startMs": 1.0, "attrs": {"server": f"server{i}", "replicaGroup": i % 2}}
             for i, ms in enumerate((first_ms, second_ms))]
    route = {"name": "route", "ms": 0.5, "startMs": 0.5,
             "attrs": {"selector": "balanced", "segments": 80, "servers": 2, "maxPerServer": 40, "minReplicas": 2}}
    rnds = [{"name": f"round:{n}", "ms": scatter_ms / rounds, "startMs": 0.4, "children": [dict(route)] + (calls if n == 0 else [])}
            for n in range(rounds)]
    return {"name": "query", "ms": scatter_ms + 5.0, "startMs": 0.0, "children": [
        {"name": "scatter", "ms": scatter_ms, "startMs": 0.3, "attrs": {"segments": 80, "servers": 2, "rounds": rounds},
         "children": rnds},
        {"name": "reduce", "ms": 4.0, "startMs": scatter_ms + 0.5}]}


@pytest.fixture()
def ctx():
    reqs = [SimpleNamespace(spans=_answer(100.0, 98.0, 200.0)), SimpleNamespace(spans=_answer(60.0, 60.0, 100.0, rounds=2)),
            SimpleNamespace(spans=None)]
    before = {f"broker.routedSegments.server{i}": 1000.0 for i in range(4)}
    after = {"broker.routedSegments.server0": 1400.0, "broker.routedSegments.server1": 1200.0,
             "broker.routedSegments.server2": 1200.0, "broker.routedSegments.server3": 1400.0}
    return {"requests": reqs, "counters_before": before, "counters_after": after, "config": {"servers": 4}}


EXPECTED = {
    "servers_per_query": (2 + 4) / 2,  # the second answer routed twice: a failover round
    "route_ms": (0.5 + 1.0) / 2,
    "scatter_ms": (200.0 + 100.0) / 2,
    "scatter_serial_share": (198.0 / 200.0 + 120.0 / 100.0) / 2,
    "replica_balance": 400.0 / 300.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_file_reads_the_expected_number(ctx, name):
    spec = plugins.load_json("layer_metrics", name)
    assert spec["name"] == name and spec["layer"] == "broker scatter" and spec["moves"] == "latency_p50_ms"
    assert os.path.isfile(os.path.join(HERE, "lib", "reducers", spec["reducer"] + ".py"))
    assert harness.metric_value("layer_metrics", name, ctx) == pytest.approx(EXPECTED[name])


def test_benchmark_json_lists_them_for_the_four_server_cell_alone():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in EXPECTED:
        spec, entry = plugins.load_json("layer_metrics", name), listed[name]
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["source"], entry["unit"], entry["moves"]) == (
            spec["layer"], spec["source"], spec["unit"], spec["moves"])


@pytest.mark.parametrize("name", ["servers_per_query", "route_ms", "replica_balance"])
def test_a_program_without_the_span_or_the_counters_reports_nothing(ctx, name):
    """The parent commit has `scatter` and `server_execute`, but no `route` span and no routing counters."""
    def strip(node):
        node["children"] = [c for c in node.get("children", ()) if c["name"] != "route"]
        for c in node["children"]:
            strip(c)
    for r in ctx["requests"]:
        if r.spans:
            strip(r.spans)
    bare = dict(ctx, counters_before={"compile.sse.compiles": 9.0}, counters_after={"compile.sse.compiles": 9.0})
    assert harness.metric_value("layer_metrics", name, bare) is None


def test_a_server_that_got_nothing_shows_in_the_balance(ctx):
    after = dict(ctx["counters_after"])
    del after["broker.routedSegments.server2"]  # never routed to: the program has no counter for it
    before = {k: v for k, v in ctx["counters_before"].items() if k in after}
    got = harness.metric_value("layer_metrics", "replica_balance", dict(ctx, counters_before=before, counters_after=after))
    assert got == pytest.approx(400.0 * 4 / 1000.0) and got >= 4.0 / 3.0
