"""What the benchmark reads from inside the program is still there.

Most per-layer metrics of `benchmarks/layer_metrics/*.json` are computed from
names the program chose: spans and their attrs in a traced answer
(`"source": "program_span"`), counters and timers of its registries
(`"program_counter"`).  A PR that renames or drops one leaves `null` in the
driver's ledger, and every later benchmark PR is refused for it.  So each
such file is a case here: on a small two-server replicated cluster behind
the front door, a traced group-by and a traced scalar sum must produce every
span and attr the file names, and the registries must hold every counter,
counter family and timer it names.

The files are read as data: nothing of `benchmarks/lib` is imported, and no
number is checked, only presence.
"""
import glob
import json
import os
import threading
import urllib.request

import jax
import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.rest import QueryServer
from pinot_tpu.query import planner
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the keys by which a metric's file names something of the program
SPAN_KEYS = ("spans", "span", "numerator", "denominator")
REGISTRY_KEYS = ("counter", "prefix", "timer")


def _program_metrics():
    specs = {}
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmarks", "layer_metrics", "*.json"))):
        with open(path, encoding="utf-8") as f:
            spec = json.load(f)
        if spec.get("source") in ("program_span", "program_counter"):
            specs[spec["name"]] = spec
    return specs


SPECS = _program_metrics()
N_SERVERS = 2
QUERIES = (
    "SELECT region, SUM(rev) FROM contract WHERE qty < 40 GROUP BY region ORDER BY region",
    "SELECT SUM(rev) FROM contract WHERE qty BETWEEN 5 AND 30",
)


def _named(tree, name):
    """Spans called `name`, or `name:<suffix>` (launch:seg3, round:0), as the
    benchmark's reducers match them."""
    out = [tree] if tree["name"] == name or tree["name"].startswith(name + ":") else []
    for c in tree.get("children", ()):
        out.extend(_named(c, name))
    return out


@pytest.fixture(scope="module")
def served():
    """(span trees of the traced answers, the registries as the benchmark
    exports them: the process-wide one plus each server's own)."""
    schema = Schema(
        "contract",
        [
            FieldSpec("region", DataType.INT),
            FieldSpec("qty", DataType.INT),
            FieldSpec("rev", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    coord = Coordinator(replication=2)
    servers = [ServerInstance(f"server{i}", device=d) for i, d in enumerate(jax.devices()[:N_SERVERS])]
    for s in servers:
        coord.register_server(s)
    coord.add_table(schema, TableConfig(name="contract"))
    rng = np.random.default_rng(28)
    for i in range(4):
        block = {
            "region": rng.integers(0, 5, 300).astype(np.int32),
            "qty": rng.integers(1, 51, 300).astype(np.int32),
            "rev": rng.integers(1, 10**6, 300),
        }
        coord.add_segment("contract", build_segment(schema, block, f"seg{i}"))
    METRICS.reset()  # what is found below, these queries put there,
    planner.plan_cache_clear()  # their compiles included
    front = QueryServer(Broker(coord)).start()
    trees = []
    try:
        for sql in QUERIES:
            body = json.dumps({"sql": "SET trace = true; " + sql}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{front.port}/query/sql", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                answer = json.loads(r.read().decode("utf-8"))
            assert answer["trace"] and not answer.get("exceptions"), answer
            trees.append(answer["trace"])
        # the front door updates rest.*Ms after the client has its answer
        settled = threading.Event()
        for _ in range(500):
            if METRICS.snapshot()["timers"].get("rest.writeMs", {"count": 0})["count"] >= len(QUERIES):
                break
            settled.wait(0.01)
    finally:
        front.stop()
    counters, timers = {}, {}
    for snap in [METRICS.snapshot()] + [s.metrics.snapshot() for s in servers]:
        for k, v in snap["counters"].items():
            counters[k] = counters.get(k, 0) + v
        for k, t in snap["timers"].items():
            timers[k] = timers.get(k, 0) + t["count"]
    return trees, counters, timers


def test_there_are_program_metrics_to_guard():
    assert SPECS, "no program_span / program_counter file under benchmarks/layer_metrics: did they move?"


@pytest.mark.parametrize("name", sorted(SPECS))
def test_program_still_says_what_the_metric_reads(name, served):
    spec = SPECS[name]
    trees, counters, timers = served
    named_keys = [k for k in SPAN_KEYS + REGISTRY_KEYS if k in spec]
    assert named_keys, f"{name}: names nothing this test knows how to look for: {sorted(spec)}"

    span_names = []
    for key in SPAN_KEYS:
        value = spec.get(key, [])
        span_names.extend([value] if isinstance(value, str) else value)
    for span in span_names:
        for sql, tree in zip(QUERIES, trees):
            assert _named(tree, span), f"{name}: no span {span!r} in the traced answer of: {sql}"
    if "attr" in spec:
        for sql, tree in zip(QUERIES, trees):
            values = [n.get("attrs", {}).get(spec["attr"]) for n in _named(tree, spec["span"])]
            assert values and all(isinstance(v, (int, float)) for v in values), (
                f"{name}: attr {spec['attr']!r} of span {spec['span']!r} in the answer of: {sql}: {values}"
            )

    if "counter" in spec:
        assert counters.get(spec["counter"], 0) > 0, f"{name}: counter {spec['counter']!r}"
    if "prefix" in spec:
        family = {k: v for k, v in counters.items() if k.startswith(spec["prefix"])}
        # replication 2 over two servers: the balanced selector routes to both
        assert len(family) == N_SERVERS and all(v > 0 for v in family.values()), (name, family)
    if "timer" in spec:
        assert timers.get(spec["timer"], 0) > 0, f"{name}: timer {spec['timer']!r}"


def test_launches_per_query_counts_the_jitted_calls(served):
    """`launches_per_query` reads `device_wait.launches`: the number of
    jitted calls a server made for the query, one a GROUP of segments, which
    is the number of `launch_enqueue` spans under that server's root (and
    `dispatch.launches`); the segments are the `launch:<segment>` spans."""
    spec = SPECS["launches_per_query"]
    assert (spec["span"], spec["attr"]) == ("device_wait", "launches")
    trees, _, _ = served
    for sql, tree in zip(QUERIES, trees):
        roots = [n for n in _named(tree, "server") if "server" in n.get("attrs", {})]
        assert len(roots) == N_SERVERS, sql
        for root in roots:
            (wait,), (dispatch,) = _named(root, "device_wait"), _named(root, "dispatch")
            enqueues = _named(root, "launch_enqueue")
            assert wait["attrs"]["launches"] == dispatch["attrs"]["launches"] == len(enqueues) == 1, sql
            # two of the four segments a server, in one call and one fetch
            assert len(_named(root, "launch")) == enqueues[0]["attrs"]["segments"] == 2
            assert [n["attrs"]["segments"] for n in _named(root, "collect")] == [2]
