"""The per-layer metrics of PR 39 (the front door's waits from accept() on, CPU
beside wall on the group-level stages, the server's per-segment loop): each
metric's file loads, names a reducer that exists, and reads the expected
number from span trees, client times and counter snapshots written by hand;
the two new reducers return None where the program has no such attr or timer
(as the parent of PR 39 has not), and the quantile is numpy's
(run by hand: `python -m pytest benchmarks/tests -q`).
"""
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import harness, plugins  # noqa: E402
from lib.reducers import client_mean_minus_timer_ms, span_attr_quantile  # noqa: E402

FOUR_CELLS = ["ssb_sf10.groupby_closed", "ssb_sf20_4srv.groupby_closed", "ssb_sf1_drill.drill_closed",
              "ssb_sf10_startree.rollup_closed"]


def _answer(wait, decode=True):
    """One traced answer's tree as PR 39's program renders it: one server,
    two groups of segments."""
    def group(at, scale):
        kids = [{"name": "table_decode", "ms": 1.0 * scale, "startMs": at + 0.5, "cpuMs": 0.75 * scale,
                 "attrs": {"kind": "groupby_dense", "cpuMs": 0.75 * scale}}] if decode else []
        return {"name": "collect", "ms": 2.0 * scale, "startMs": at, "cpuMs": 1.25 * scale,
                "attrs": {"segments": 8, "cpuMs": 1.25 * scale}, "children": kids}

    enqueues = [{"name": "launch_enqueue", "ms": 4.0, "startMs": 2.0 + 5 * i, "cpuMs": 0.5,
                 "attrs": {"segments": 8, "width": 8, "cpuMs": 0.5}} for i in range(2)]
    server = {"name": "server:server0", "ms": 30.0, "startMs": 0.0, "cpuMs": 9.0, "t0Ns": 5_000_000, "thread": "t",
              "attrs": {"server": "server0", "cpuMs": 9.0},
              "children": [{"name": "dispatch", "ms": 14.0, "startMs": 0.5, "attrs": {"launches": 2, "loopMs": 6.0},
                            "children": enqueues},
                           {"name": "device_wait", "ms": 4.0, "startMs": 14.5, "attrs": {"launches": 2}},
                           group(19.0, 1.0), group(22.0, 2.0)]}
    return {"name": "query", "ms": 40.0, "startMs": 0.0, "cpuMs": 12.0, "t0Ns": 4_000_000, "thread": "t",
            "attrs": {"queryId": "b_1", "cpuMs": 12.0, "parseMs": 0.4, "httpReadMs": 0.2, "acceptT0Ns": 3_000_000,
                      "acceptWaitMs": wait, "headMs": wait / 4},
            "children": [{"name": "scatter", "ms": 31.0, "startMs": 1.0, "children": [server]},
                         {"name": "reduce", "ms": 3.0, "startMs": 33.0, "cpuMs": 1.0, "attrs": {"cpuMs": 1.0}}]}


def _request(i, sent, done, spans=None, status=200, due=None):
    return SimpleNamespace(index=i, sent=sent, done=done, due=sent if due is None else due, status=status, spans=spans)


WAITS = [0.5, 1.0, 1.5, 2.0, 100.0]  # one slow answer in five


@pytest.fixture()
def ctx():
    reqs = [_request(i, 1.0 * i, 1.0 * i + 0.050 + 0.001 * w, _answer(w)) for i, w in enumerate(WAITS)]
    reqs.append(_request(5, 5.0, 5.040, spans=None))  # answered, its trace not read (as an answer with a fault's is not)
    failed = _request(6, 6.0, 126.0, status=500)  # never updated rest.doorMs: not among the answered
    before = {"timer:rest.doorMs:count": 100.0, "timer:rest.doorMs:total_ms": 4000.0}
    after = {"timer:rest.doorMs:count": 106.0, "timer:rest.doorMs:total_ms": 4000.0 + 6 * 45.0}
    for name, mean in (("readMs", 0.25), ("writeMs", 0.5), ("acceptLoopMs", 2.0), ("engineMs", 40.0)):
        before[f"timer:rest.{name}:count"], before[f"timer:rest.{name}:total_ms"] = 10.0, 7.0
        after[f"timer:rest.{name}:count"], after[f"timer:rest.{name}:total_ms"] = 16.0, 7.0 + 6 * mean
    return {"requests": [r for r in reqs if r.spans], "window_requests": reqs + [failed],
            "counters_before": before, "counters_after": after}


CLIENT_MS = (5 * 50.0 + sum(WAITS) + 40.0) / 6  # mean of done - sent over the six answered requests
EXPECTED = {
    "frontdoor_accept_wait_ms": sum(WAITS) / 5,
    "frontdoor_accept_wait_p99_ms": float(np.quantile(WAITS, 0.99)),
    "frontdoor_head_ms": sum(WAITS) / 20,
    "frontdoor_read_ms": 0.25,
    "frontdoor_write_ms": 0.5,
    "frontdoor_accept_loop_ms": 2.0,
    "frontdoor_door_ms": 45.0,
    "frontdoor_engine_ms": 40.0,
    "frontdoor_before_accept_ms": CLIENT_MS - 45.0,
    "launch_enqueue_cpu_ms": 1.0,
    "collect_cpu_ms": 1.25 + 2.5,
    "table_decode_cpu_ms": 0.75 + 1.5,
    "reduce_cpu_ms": 1.0,
    "dispatch_loop_ms": 6.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_file_reads_the_expected_number(ctx, name):
    spec = plugins.load_json("layer_metrics", name)
    assert spec["name"] == name and spec["unit"] == "ms" and spec["moves"] == "latency_p50_ms"
    assert os.path.isfile(os.path.join(HERE, "lib", "reducers", spec["reducer"] + ".py"))
    assert harness.metric_value("layer_metrics", name, ctx) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_benchmark_json_lists_the_metric_as_its_file_says(name):
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    spec = plugins.load_json("layer_metrics", name)
    assert {k: entry[k] for k in ("unit", "source", "layer", "moves")} == {k: spec[k] for k in ("unit", "source", "layer", "moves")}
    assert entry["better"] == "lower" and entry["layer"] in ("front door", "per-server execute")
    # every cell reports latency_p50_ms, so a metric without a list is given to every cell: Q1 decodes no table
    assert entry.get("workloads") == (FOUR_CELLS if name == "table_decode_cpu_ms" else None)
    assert harness.load_cell("ssb_sf10.q1_closed")["per_layer"].count(entry) == (0 if name == "table_decode_cpu_ms" else 1)


@pytest.mark.parametrize("name", [n for n in sorted(EXPECTED) if n != "frontdoor_read_ms" and n != "frontdoor_write_ms"])
def test_a_program_before_pr_39_reports_nothing_and_does_not_raise(name):
    """The parent has the spans and not the attrs, `rest.readMs` / `rest.writeMs`
    / `rest.serializeMs` and no other timer of the door: the driver runs it
    under these files, and a reader there returns None."""
    def strip(node):
        attrs = {k: v for k, v in node.get("attrs", {}).items()
                 if k not in ("acceptWaitMs", "headMs", "acceptT0Ns", "loopMs")
                 and not (k == "cpuMs" and not node["name"].startswith("launch:"))}
        out = dict(node, attrs=attrs)
        if "children" in node:
            out["children"] = [strip(c) for c in node["children"]]
        return out

    reqs = [_request(i, 1.0 * i, 1.0 * i + 0.05, strip(_answer(w))) for i, w in enumerate(WAITS)]
    old = {"timer:rest.readMs:count": 5.0, "timer:rest.readMs:total_ms": 1.0}
    ctx = {"requests": reqs, "window_requests": reqs, "counters_before": {}, "counters_after": old}
    assert harness.metric_value("layer_metrics", name, ctx) is None


def test_the_quantile_is_numpys_over_the_per_query_sums():
    rng = np.random.default_rng(39)
    waits = rng.gamma(2.0, 3.0, size=257)
    reqs = [_request(i, 0.0, 1.0, _answer(float(w))) for i, w in enumerate(waits)]
    for q in (0.5, 0.95, 0.99, 1.0):
        spec = {"span": "query", "attr": "acceptWaitMs", "q": q}
        assert span_attr_quantile.reduce(spec, {"requests": reqs}) == pytest.approx(float(np.quantile(waits, q)))
    # an attr on several spans of one answer is summed first, as span_attr_mean does
    spec = {"span": "collect", "attr": "cpuMs", "q": 0.5}
    assert span_attr_quantile.reduce(spec, {"requests": reqs}) == pytest.approx(3.75)
    assert span_attr_quantile.reduce(dict(spec, attr="nope"), {"requests": reqs}) is None
    assert span_attr_quantile.reduce(spec, {"requests": [_request(0, 0.0, 1.0, None)]}) is None


def test_before_accept_is_timed_from_the_send_and_not_from_the_due_time(ctx):
    """An open loop times a request from when it was due; a request the
    generator sent late has not waited at the door for that long."""
    spec = plugins.load_json("layer_metrics", "frontdoor_before_accept_ms")
    for r in ctx["window_requests"]:
        r.due = r.sent - 10.0
    assert client_mean_minus_timer_ms.reduce(spec, ctx) == pytest.approx(CLIENT_MS - 45.0)


def test_before_accept_has_nothing_to_say_without_both_of_its_means(ctx):
    spec = plugins.load_json("layer_metrics", "frontdoor_before_accept_ms")
    still = dict(ctx, counters_after=dict(ctx["counters_after"], **{"timer:rest.doorMs:count": 100.0}))
    assert client_mean_minus_timer_ms.reduce(spec, still) is None  # the timer did not move inside the window
    nobody = dict(ctx, window_requests=[r for r in ctx["window_requests"] if r.status != 200])
    assert client_mean_minus_timer_ms.reduce(spec, nobody) is None  # no request was answered
