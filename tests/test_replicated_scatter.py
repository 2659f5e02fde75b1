"""Four servers behind one broker at replication 2 (the deployment of
benchmarks/configs/ssb_flat_sf20_4srv.json at a toy size): 8 segments of the
SSB flat generator's columns, built and served exactly as the benchmark's
cell builds and serves them (benchmarks/lib/cluster.py: seeded columns ->
build_segment -> Coordinator(replication=2) -> four ServerInstances, each on
a device of its own -> to_device -> Broker -> QueryServer over HTTP), and
held to the benchmark's plain numpy reference at limit 0.

What a replicated table promises and no one-server test can hold: every
segment resident on exactly two servers of two replica groups, every query
reading each segment from exactly one of them, answers whole and exact with
one server down (and a second, of the other pair), a failure and no silent
partial when both replicas of a segment are gone — and the broker's `route`
span and routing counters saying what the routing did.
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)

from lib import check, loadgen, plugins, templates  # noqa: E402
from lib.reducers import spans  # noqa: E402

SEGMENTS, SEGMENT_ROWS, SERVERS = 8, 3_000, 4
ROWS = SEGMENTS * SEGMENT_ROWS
SEED = 2**31 + 26
GROUP_BYS = plugins.load_json("traffic", "groupby_closed")["templates"]
LITERALS = ["ssb", "drawn_a", "drawn_b"]


@pytest.fixture(scope="module")
def deployment():
    import jax

    from lib import cluster as cluster_mod

    config = dict(plugins.load_json("configs", "ssb_flat_sf20_4srv"), rows=ROWS, segment_rows=SEGMENT_ROWS)
    cl = cluster_mod.Cluster(config, SEED, jax.devices()[:SERVERS], build_threads=2)
    try:
        yield cl, plugins.load_json("queries", config["query_set"])
    finally:
        cl.close()


@pytest.fixture()
def all_up(deployment):
    """Every server live and every breaker closed, before and after."""
    cl, _ = deployment

    def heal():
        for s in cl.servers:
            cl.coordinator.mark_up(s.name)
            cl.broker.health.reset(s.name)

    heal()
    yield
    heal()


def ask(cl, query_set, name, params=None, traced=False, index=0):
    template = query_set["templates"][name]
    req = loadgen.Request(index, 0, name, dict(params or template["ssb"]), 0.0)
    loadgen.send(cl.url, req, template, traced, time.perf_counter())
    return req


def literals_of(template, which):
    if which == "ssb":
        return dict(template["ssb"])
    return templates.draw_params(template, np.random.default_rng([SEED, LITERALS.index(which)]))


def served_by(tree):
    """{server: [segments it launched]} of one traced answer."""
    out = {}
    for root in spans.named(tree, "server"):
        if "server" in root.get("attrs", {}):  # the server's own root, not `server_execute`
            out.setdefault(root["attrs"]["server"], []).extend(
                n["attrs"]["segment"] for n in spans.named(root, "launch"))
    return out


def assert_whole_and_exact(cl, query_set, req):
    assert check.envelope_fault(req, SEGMENTS) is None, req.error or req.meta
    # every segment once, less whole segments a server pruned by its column ranges: no copy read twice
    assert req.meta["numDocsScanned"] <= ROWS and req.meta["numDocsScanned"] % SEGMENT_ROWS == 0, req.meta
    ok, numbers = check.compare(req, query_set, cl.blocks)
    assert ok, numbers


@pytest.mark.parametrize("which", LITERALS)
@pytest.mark.parametrize("name", GROUP_BYS)
def test_answer_equals_the_reference(deployment, all_up, name, which):
    """Group keys, missing and extra groups, every sum and the sort order, limit 0."""
    cl, query_set = deployment
    req = ask(cl, query_set, name, literals_of(query_set["templates"][name], which))
    assert_whole_and_exact(cl, query_set, req)
    assert req.meta["numServersQueried"] == req.meta["numServersResponded"] == 2


def test_every_segment_is_resident_on_two_servers_of_two_replica_groups(deployment):
    cl, _ = deployment
    table = cl.config["table"]
    holders = {f"seg{i}": [s.name for s in cl.servers if f"seg{i}" in s.segment_names(table)]
               for i in range(SEGMENTS)}
    assert all(len(h) == 2 for h in holders.values()), holders
    groups = cl.coordinator.replica_group
    assert all(len({groups[s] for s in h}) == 2 for h in holders.values()), (holders, groups)
    assert [len(s.segment_names(table)) for s in cl.servers] == [SEGMENTS * 2 // SERVERS] * SERVERS
    assert cl.coordinator.tables[table].ideal == {seg: set(h) for seg, h in holders.items()}
    assert len({s.device for s in cl.servers}) == SERVERS  # a device each


def test_each_segment_is_served_once_and_two_queries_use_all_four_servers(deployment, all_up):
    cl, query_set = deployment
    seen = set()
    for i in range(2):
        req = ask(cl, query_set, "q2_1", traced=True, index=i)
        assert_whole_and_exact(cl, query_set, req)
        by_server = served_by(req.spans)
        launched = sorted(seg for segs in by_server.values() for seg in segs)
        assert launched == sorted(f"seg{i}" for i in range(SEGMENTS)), by_server
        assert sorted(len(v) for v in by_server.values()) == [SEGMENTS // 2] * 2
        seen |= set(by_server)
    assert seen == {s.name for s in cl.servers}


@pytest.mark.parametrize("down, answers", [
    (["server0"], True),
    (["server0", "server2"], True),  # one of each pair: every segment keeps a replica
    (["server0", "server1"], False),  # both replicas of the even segments
], ids=["one_down", "one_of_each_pair_down", "both_replicas_down"])
def test_servers_down(deployment, all_up, down, answers):
    """Replication 2's guarantee: whole and exact while every segment keeps
    one replica; a failure, not a silent partial, when one does not."""
    cl, query_set = deployment
    for name in down:
        cl.coordinator.mark_down(name)
    for i, name in enumerate(GROUP_BYS[:3]):
        req = ask(cl, query_set, name, traced=True, index=i)
        if answers:
            assert_whole_and_exact(cl, query_set, req)
            assert not set(served_by(req.spans)) & set(down)
        else:
            assert req.status != 200 and not req.rows, (req.status, req.meta)
            assert check.envelope_fault(req, SEGMENTS) is not None


def test_route_span_and_counters_read_what_the_routing_did(deployment, all_up):
    from pinot_tpu.utils.metrics import METRICS

    cl, query_set = deployment
    before = cl.counters()
    req = ask(cl, query_set, "q4_1", traced=True)
    moved = {k: v - before.get(k, 0.0) for k, v in cl.counters().items() if v != before.get(k, 0.0)}
    (route,) = spans.named(req.spans, "route")
    assert route["attrs"] == {"selector": "balanced", "segments": SEGMENTS, "servers": 2,
                              "maxPerServer": SEGMENTS // 2, "minReplicas": 2}
    (scatter,) = spans.named(req.spans, "scatter")
    assert scatter["attrs"]["servers"] == 2 and scatter["attrs"]["rounds"] == 1
    (rnd,) = spans.named(req.spans, "round")
    assert [c["name"] for c in rnd["children"]] == ["route", "server_execute", "server_execute"]
    groups = cl.coordinator.replica_group
    for n in spans.named(req.spans, "server_execute"):
        assert n["attrs"]["replicaGroup"] == groups[n["attrs"]["server"]]
    routed = {k.rsplit(".", 1)[1]: v for k, v in moved.items() if k.startswith("broker.routedSegments.")}
    assert routed == {s: SEGMENTS // 2 for s in served_by(req.spans)}
    assert moved["broker.scatter.serverCalls"] == 2
    assert "broker.scatter.serverCalls" in METRICS.snapshot()["counters"]  # the process-wide registry


def test_a_failover_round_shows_in_the_spans(deployment, all_up):
    """A server that fails under the query: its segments go to their other
    replicas in a second round, and the spans say so."""
    from pinot_tpu.cluster.faults import FaultPlan

    cl, query_set = deployment
    victim = cl.servers[1]
    victim.fault_plan = FaultPlan(seed=1).fail_server(victim.name, on_call=1)
    try:
        reqs = [ask(cl, query_set, "q3_1", traced=True, index=i) for i in range(2)]
    finally:
        victim.fault_plan = None
    hit = [r for r in reqs if len(list(spans.named(r.spans, "round"))) == 2]
    assert hit, "neither query was routed to the victim"
    for r in reqs:  # whole and exact all the same; the failed call stays on record in the answer
        assert r.meta["numSegmentsQueried"] == SEGMENTS and not r.meta["partialResult"]
        assert check.compare(r, query_set, cl.blocks)[0]
    assert [e["server"] for e in hit[0].meta["exceptions"]] == [victim.name]
    (scatter,) = spans.named(hit[0].spans, "scatter")
    assert scatter["attrs"]["rounds"] == 2 and scatter["attrs"]["servers"] == 2
    first, second = spans.named(hit[0].spans, "route")
    assert (first["attrs"]["servers"], second["attrs"]["servers"]) == (2, 1)
    assert second["attrs"]["segments"] == SEGMENTS // 2 and second["attrs"]["minReplicas"] == 1


def test_explain_analyze_prints_the_routing_attrs(deployment, all_up):
    cl, query_set = deployment
    t = query_set["templates"]["q4_2"]
    labels = [r[0] for r in cl.broker.query("EXPLAIN ANALYZE " + templates.render(t, t["ssb"])).rows]
    (route,) = [x for x in labels if x.startswith("TRACE(route)")]
    assert route == f"TRACE(route) [selector=balanced, segments={SEGMENTS}, servers=2, maxPerServer=4, minReplicas=2]"
    (scatter,) = [x for x in labels if x.startswith("TRACE(scatter)")]
    assert "servers=2" in scatter and "rounds=1" in scatter
    assert sum("replicaGroup=" in x for x in labels if x.startswith("TRACE(server_execute)")) == 2


def test_a_new_query_shape_compiles_on_every_chip_at_once_and_says_so(deployment, all_up):
    """A jitted program compiles once for each device it runs on.  Q1.1 has
    not run on this deployment: the first server its first query calls is
    about to compile, so the broker has the other three compile the same
    program at the same time (`ServerInstance.warm`), and the second and
    third query compile nowhere.  Every compile is on record
    (`firstLaunch`/`compileMs` on the served launch, `server.compileMs` for
    all four), so that "no compile inside the window" can be read there."""
    cl, query_set = deployment
    fed = []
    real = cl.broker.health.note_latency
    cl.broker.health.note_latency = lambda server, ms: fed.append((server, ms)) or real(server, ms)
    try:
        first_launches, moved = [], []
        for i in range(3):
            before = cl.counters()
            req = ask(cl, query_set, "q1_1", traced=True, index=i)
            assert check.envelope_fault(req, SEGMENTS) is None and check.compare(req, query_set, cl.blocks)[0]
            moved.append({k: v - before.get(k, 0.0) for k, v in cl.counters().items() if v != before.get(k, 0.0)})
            first_launches.append(sorted(
                sum(1 for n in spans.named(root, "launch_enqueue") if n["attrs"].get("firstLaunch"))
                for root in spans.named(req.spans, "server") if "server" in root.get("attrs", {})))
            if i == 0:
                # the gray-failure detector is fed the call's time less its compile
                (call,) = [c for c in spans.named(req.spans, "server_execute")
                           if any(n["attrs"].get("firstLaunch") for n in spans.named(c, "launch_enqueue"))]
                compile_ms = sum(n["attrs"].get("compileMs", 0.0) for n in spans.named(call, "launch_enqueue"))
                (latency,) = [ms for server, ms in fed if server == call["attrs"]["server"]]
                assert compile_ms > 0 and latency < call["ms"] - 0.9 * compile_ms
    finally:
        cl.broker.health.note_latency = real
    assert [m.get("timer:server.compileMs:count", 0) for m in moved] == [SERVERS, 0, 0]
    assert [m.get("broker.peerWarmups", 0) for m in moved] == [SERVERS - 1, 0, 0]
    assert not any("broker.peerWarmupFailures" in m for m in moved)
    assert first_launches == [[0, 1], [0, 0], [0, 0]]  # the served call that compiled; its peers compiled in `warm`
    assert not [t for t in threading.enumerate() if t.name.startswith("warm-")]
