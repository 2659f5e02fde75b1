"""The per-layer metrics of PR 52 (the interpreter lock's waiters and holders,
the collector's pauses, CPU beside wall at the front door, the jitted call's
operands): each metric's file loads, names a reducer that exists, is listed in
BENCHMARK.json for every cell, and reads the expected number from counter
snapshots and span trees written by hand; the new reducer `counter_ratio`
takes its numerator and denominator as a name or a list and returns None on a
missing counter or a still denominator, so a program without the watch (the
parent of PR 52) or a window without it (`--trace 0`) reports nothing and
raises nothing (run by hand: `python -m pytest benchmarks/tests -q`).
"""
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import harness, plugins  # noqa: E402
from lib.reducers import counter_ratio  # noqa: E402

CLASSES = ["handler", "accept_loop", "staging", "watch", "embedder"]
NEW = {
    "interpreter_wait_ms": "interpreter lock", "interpreter_wait_over_20ms_share": "interpreter lock",
    "interpreter_cpu_share": "interpreter lock", "interpreter_embedder_share": "interpreter lock",
    "interpreter_holds_in_window": "interpreter lock", "gc_pause_ms": "interpreter lock",
    "gc_full_collections_in_window": "interpreter lock", "frontdoor_door_cpu_ms": "front door",
    "frontdoor_engine_cpu_ms": "front door", "frontdoor_serialize_cpu_ms": "front door",
    "launch_operands_per_query": "per-server execute",
}


def _timer(snap, name, count, total):
    snap[f"timer:{name}:count"], snap[f"timer:{name}:total_ms"] = float(count), float(total)


def _snapshots():
    """The program's registry before and after a 51 s traced window, as
    lib/cluster.py exports it: 5,000 ticks, 40 of them past 20 ms."""
    before = {"runtime.interpreterWait.ticks": 100.0, "runtime.interpreterWait.over20ms": 2.0, "runtime.watchedMs": 1000.0,
              "runtime.interpreterHolds": 1.0, "runtime.gc.gen2": 3.0, "runtime.gc.gen1": 30.0}
    after = {"runtime.interpreterWait.ticks": 5100.0, "runtime.interpreterWait.over20ms": 42.0, "runtime.watchedMs": 52000.0,
             "runtime.interpreterHolds": 4.0, "runtime.gc.gen2": 5.0, "runtime.gc.gen1": 90.0}
    cpu_before = {"handler": 500.0, "accept_loop": 20.0, "staging": 0.0, "watch": 5.0, "embedder": 300.0}
    cpu_after = {"handler": 30500.0, "accept_loop": 1020.0, "staging": 0.0, "watch": 260.0, "embedder": 13045.0}
    for cls in CLASSES:
        before[f"runtime.cpuMs.{cls}"], after[f"runtime.cpuMs.{cls}"] = cpu_before[cls], cpu_after[cls]
    _timer(before, "runtime.interpreterWaitMs", 100, 50.0)
    _timer(after, "runtime.interpreterWaitMs", 5100, 12_550.0)
    _timer(before, "runtime.gcPauseMs", 33, 400.0)
    _timer(after, "runtime.gcPauseMs", 95, 1_640.0)
    for name, (n0, t0, n1, t1) in {"rest.doorCpuMs": (10, 80.0, 760, 6_830.0), "rest.engineCpuMs": (10, 70.0, 760, 5_320.0),
                                   "rest.serializeCpuMs": (10, 5.0, 760, 380.0)}.items():
        _timer(before, name, n0, t0)
        _timer(after, name, n1, t1)
    return before, after


def _answer(operands):
    enqueues = [{"name": "launch_enqueue", "ms": 4.0, "startMs": 2.0 + 5 * i, "cpuMs": 0.5,
                 "attrs": {"segments": 8, "width": 8, "cpuMs": 0.5, "operands": n}} for i, n in enumerate(operands)]
    return {"name": "query", "ms": 40.0, "startMs": 0.0, "children": [
        {"name": "server:server0", "ms": 30.0, "startMs": 1.0, "children": [
            {"name": "dispatch", "ms": 14.0, "startMs": 0.5, "children": enqueues}]}]}


@pytest.fixture()
def ctx():
    before, after = _snapshots()
    reqs = [SimpleNamespace(index=i, spans=_answer(ops)) for i, ops in enumerate([[33, 33, 33, 33, 33], [33, 33, 33, 33, 35]])]
    return {"counters_before": before, "counters_after": after, "requests": reqs, "window_requests": reqs, "faults": {}}


EXPECTED = {
    "interpreter_wait_ms": 12_500.0 / 5_000,
    "interpreter_wait_over_20ms_share": 40.0 / 5_000,
    "interpreter_cpu_share": (30_000.0 + 1_000.0 + 0.0 + 255.0 + 12_745.0) / 51_000.0,
    "interpreter_embedder_share": 12_745.0 / 44_000.0,
    "interpreter_holds_in_window": 3.0,
    "gc_pause_ms": 1_240.0 / 62,
    "gc_full_collections_in_window": 2.0,
    "frontdoor_door_cpu_ms": 6_750.0 / 750,
    "frontdoor_engine_cpu_ms": 5_250.0 / 750,
    "frontdoor_serialize_cpu_ms": 375.0 / 750,
    "launch_operands_per_query": (165 + 167) / 2,
}


def test_the_eleven_are_the_eleven():
    assert set(EXPECTED) == set(NEW) and len(NEW) == 11


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_file_names_a_reducer_that_exists_and_benchmark_json_lists_it_for_every_cell(name):
    spec = plugins.load_json("layer_metrics", name)
    assert spec["name"] == name and spec["layer"] == NEW[name] and spec["moves"] == "latency_p50_ms"
    assert callable(plugins.load_module("reducers", spec["reducer"]).reduce)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (listed,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert set(listed) == {"name", "unit", "better", "source", "layer", "moves"}  # no `workloads`: every cell
    assert (listed["layer"], listed["unit"], listed["source"], listed["moves"]) == (
        spec["layer"], spec["unit"], spec["source"], "latency_p50_ms")
    assert listed["source"] == ("program_span" if name == "launch_operands_per_query" else "program_counter")
    for cell in ("ssb_sf10.q1_closed", "ssb_sf1.mixed_open", "ssb_sf20_4srv.groupby_closed", "ssb_sf10_sketch.sketch_closed"):
        assert name in [m["name"] for m in harness.load_cell(cell)["per_layer"]]
    # appended: the accepted metrics keep their places
    assert [m["name"] for m in bench["per_layer"]].index(name) >= 78


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_reader_reads_the_expected_number(name, ctx):
    assert harness.metric_value("layer_metrics", name, ctx) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_watch_reports_nothing_and_raises_nothing(name, ctx):
    """The parent's registry and spans: no `runtime.*`, no `rest.*CpuMs`, no `operands`."""
    for snap in (ctx["counters_before"], ctx["counters_after"]):
        for key in [k for k in snap if "runtime." in k or "CpuMs" in k]:
            del snap[key]
    for r in ctx["requests"]:
        for enqueue in r.spans["children"][0]["children"][0]["children"]:
            del enqueue["attrs"]["operands"]
    assert harness.metric_value("layer_metrics", name, ctx) is None


@pytest.mark.parametrize("name", sorted(set(NEW) - {"launch_operands_per_query", "interpreter_holds_in_window",
                                                    "gc_full_collections_in_window"}))
def test_an_unwatched_window_reports_nothing(name, ctx):
    """`--trace 0`, or the watch stubbed out: the counters are there (an
    earlier traced query made them) and stand still."""
    ctx["counters_after"] = dict(ctx["counters_before"])
    assert harness.metric_value("layer_metrics", name, ctx) is None


def test_the_two_counts_read_zero_where_nothing_happened(ctx):
    ctx["counters_after"] = dict(ctx["counters_before"])
    assert harness.metric_value("layer_metrics", "interpreter_holds_in_window", ctx) == 0.0
    assert harness.metric_value("layer_metrics", "gc_full_collections_in_window", ctx) == 0.0


# -- counter_ratio -----------------------------------------------------------
@pytest.mark.parametrize("counters,over,expected", [
    ("a", "b", 30.0 / 60.0),
    (["a"], ["b"], 30.0 / 60.0),
    (["a", "c"], "b", (30.0 + 6.0) / 60.0),
    ("a", ["b", "c"], 30.0 / 66.0),
    (["a", "b", "c"], ["a", "b", "c"], 1.0),
    ("new", "b", 7.0 / 60.0),  # a counter the window made: it moved from 0
])
def test_counter_ratio_takes_a_name_or_a_list(counters, over, expected):
    ctx = {"counters_before": {"a": 10.0, "b": 40.0, "c": 4.0}, "counters_after": {"a": 40.0, "b": 100.0, "c": 10.0, "new": 7.0}}
    assert counter_ratio.reduce({"counters": counters, "over": over}, ctx) == pytest.approx(expected)


@pytest.mark.parametrize("counters,over,why", [
    ("missing", "b", "no such numerator"),
    (["a", "missing"], "b", "one of the numerator's counters is missing"),
    ("a", "missing", "no such denominator"),
    ("a", ["b", "missing"], "one of the denominator's counters is missing"),
    ("a", "still", "the denominator stood still"),
    ("a", ["still", "still2"], "every counter of the denominator stood still"),
    ([], "b", "an empty numerator"),
])
def test_counter_ratio_returns_none(counters, over, why):
    ctx = {"counters_before": {"a": 10.0, "b": 40.0, "still": 5.0, "still2": 0.0},
           "counters_after": {"a": 40.0, "b": 100.0, "still": 5.0, "still2": 0.0}}
    assert counter_ratio.reduce({"counters": counters, "over": over}, ctx) is None, why


def test_counter_ratio_reads_zero_where_the_numerator_stood_still():
    ctx = {"counters_before": {"a": 10.0, "b": 40.0}, "counters_after": {"a": 10.0, "b": 100.0}}
    assert counter_ratio.reduce({"counters": "a", "over": "b"}, ctx) == 0.0
