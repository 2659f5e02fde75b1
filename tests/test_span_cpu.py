"""CPU beside wall on the host's group-level stages, on the traced path only.

`launch_enqueue`, `collect`, `table_decode` (query/executor.py) and the
broker's `reduce` are opened with `cpu=True`: a traced answer's span says
`cpuMs` (time.thread_time over the span) beside `ms`, top level and among its
attrs, where the benchmark's `span_attr_mean` looks; wall less CPU is the
stage's wait for the interpreter lock, a lock or the device.  `dispatch`
says `loopMs`: its time outside its child spans, the server's per-segment
loop.  An UNTRACED query builds no Span and so never reads the CPU clock,
which is a system call (6 us on the TPU host).
"""
import time

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils import metrics

GROUP_SQL = "SELECT region, SUM(rev) FROM cpu_t WHERE qty < 40 GROUP BY region ORDER BY region"
SCALAR_SQL = "SELECT SUM(rev) FROM cpu_t WHERE qty < 40"
SEGMENTS = 4
CPU_STAGES = ("launch_enqueue", "collect", "table_decode", "reduce")
CLOCK_SLACK_MS = 1.0  # two clocks, read one after the other


@pytest.fixture(scope="module")
def broker():
    schema = Schema(
        "cpu_t",
        [
            FieldSpec("region", DataType.INT),
            FieldSpec("qty", DataType.INT),
            FieldSpec("rev", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    coord = Coordinator(replication=1)
    coord.register_server(ServerInstance("server0"))
    coord.add_table(schema, TableConfig(name="cpu_t"))
    rng = np.random.default_rng(39)
    for i in range(SEGMENTS):
        block = {
            # every value in every segment: one dictionary shape, so one kernel and one group launch
            "region": rng.permutation(np.arange(400) % 5).astype(np.int32),
            "qty": rng.permutation(np.arange(400) % 50 + 1).astype(np.int32),
            "rev": rng.integers(1, 10**6, 400),
        }
        coord.add_segment("cpu_t", build_segment(schema, block, f"seg{i}"))
    b = Broker(coord)
    for sql in (GROUP_SQL, SCALAR_SQL):  # compile outside every case
        b.query(sql)
    return b


@pytest.fixture(scope="module")
def tree(broker):
    return broker.query("SET trace = true; " + GROUP_SQL).stats.trace


def _named(node, name):
    out = [node] if node["name"].split(":", 1)[0] == name else []
    for c in node.get("children", ()):
        out.extend(_named(c, name))
    return out


@pytest.mark.parametrize("stage", CPU_STAGES)
def test_group_level_stage_says_its_cpu_beside_its_wall(tree, stage):
    spans = _named(tree, stage)
    assert spans, f"no {stage} span in a traced group-by"
    for sp in spans:
        assert sp["attrs"]["cpuMs"] == sp["cpuMs"]  # top level as every cpu=True span, and where the readers look
        assert 0.0 <= sp["cpuMs"] <= sp["ms"] + CLOCK_SLACK_MS, sp


def test_a_scalar_sum_decodes_no_table(broker):
    scalar = broker.query("SET trace = true; " + SCALAR_SQL).stats.trace
    assert not _named(scalar, "table_decode")
    assert all("cpuMs" in sp["attrs"] for name in ("launch_enqueue", "collect", "reduce") for sp in _named(scalar, name))


def test_table_decode_cpu_is_part_of_collects(tree):
    (collect,) = _named(tree, "collect")
    (decode,) = _named(collect, "table_decode")
    assert decode["cpuMs"] <= collect["cpuMs"] + 0.01


def test_the_stages_no_one_asked_stay_off_the_cpu_clock(tree):
    for name in ("launch_plan", "launch_ship", "launch_release", "dispatch", "plan", "prune", "scatter", "route"):
        spans = _named(tree, name)
        assert spans and not [sp for sp in spans if "cpuMs" in sp or "cpuMs" in sp.get("attrs", {})], name


def test_dispatch_loop_ms_is_its_time_less_its_childrens(tree):
    (dispatch,) = _named(tree, "dispatch")
    kids = dispatch["children"]
    assert sorted({k["name"].split(":", 1)[0] for k in kids}) == ["launch", "launch_enqueue", "prune"]
    assert len(kids) == SEGMENTS + 2  # the pruner's pass (PR 47), four segments planned and shipped, one jitted call
    loop = dispatch["ms"] - sum(k["ms"] for k in kids)
    assert dispatch["attrs"]["loopMs"] == pytest.approx(loop, abs=0.001 * (len(kids) + 2))  # each rounded to the us
    assert 0.0 <= dispatch["attrs"]["loopMs"] < dispatch["ms"]


def test_a_slow_loop_is_loop_time_and_no_childs(broker, monkeypatch):
    """What the server's loop does between two launches has no span: it is
    `loopMs`, and no child's `ms`.  (The pruner's pass has had a span of its
    own since PR 47, so the loop is slowed where it checks the residency.)"""
    from pinot_tpu.cluster import server as server_mod

    real = server_mod.executor.QueryLaunches.add

    def slow_add(self, seg):
        real(self, seg)
        time.sleep(0.02)  # after the segment's own spans closed: the loop's time

    monkeypatch.setattr(server_mod.executor.QueryLaunches, "add", slow_add)
    slow = broker.query("SET trace = true; " + GROUP_SQL).stats.trace
    (dispatch,) = _named(slow, "dispatch")
    assert dispatch["attrs"]["loopMs"] >= SEGMENTS * 20.0
    assert sum(k["ms"] for k in dispatch["children"]) < dispatch["ms"] - SEGMENTS * 20.0 + 1.0


def test_a_slow_pruner_is_the_prune_spans_time(broker, monkeypatch):
    """The pruner's pass over a query's segments is the `prune` span under
    `dispatch` (PR 47: `segments`, `pruned`), not the loop's."""
    from pinot_tpu.query import planner

    real = planner.QueryPlanning.prune_many

    def slow_prune(self, segs, bounds=None):
        time.sleep(0.02 * len(segs))
        return real(self, segs, bounds)

    monkeypatch.setattr(planner.QueryPlanning, "prune_many", slow_prune)
    slow = broker.query("SET trace = true; " + GROUP_SQL).stats.trace
    (dispatch,) = _named(slow, "dispatch")
    (prune,) = _named(dispatch, "prune")
    assert prune["ms"] >= SEGMENTS * 20.0 and prune["attrs"] == {"segments": SEGMENTS, "pruned": 0}
    assert dispatch["attrs"]["loopMs"] < SEGMENTS * 20.0


@pytest.mark.parametrize("sql", [GROUP_SQL, SCALAR_SQL], ids=["group_by", "scalar"])
def test_an_untraced_query_never_reads_the_cpu_clock(broker, monkeypatch, sql):
    reads = []
    real = time.thread_time

    def counting():
        reads.append(1)
        return real()

    monkeypatch.setattr(metrics.time, "thread_time", counting)
    out = broker.query(sql)
    assert out.stats.trace is None and out.rows and not reads
    traced = broker.query("SET trace = true; " + sql)
    spans = [n for name in CPU_STAGES + ("launch", "query", "server") for n in _named(traced.stats.trace, name)]
    assert len(reads) == 2 * len(spans)  # once at entry, once at exit, of every span that asked and no other



@pytest.mark.parametrize("sql", [GROUP_SQL, SCALAR_SQL], ids=["group_by", "scalar"])
def test_launch_enqueue_says_how_many_arrays_the_jitted_call_is_handed(broker, monkeypatch, sql):
    """`operands` (PR 52): the array leaves of the call's arguments, counted
    from the members' column entries and the parameter buffers without
    flattening them; a combining call also carries its tables.  An untraced
    query counts nothing."""
    import jax

    from pinot_tpu.query import executor

    handed = []
    real = executor._enqueue

    def counting(trace, plan, args, device, on_first_launch=None, **attrs):
        handed.append((len(jax.tree_util.tree_leaves(args)), attrs))
        return real(trace, plan, args, device, on_first_launch, **attrs)

    monkeypatch.setattr(executor, "_enqueue", counting)
    assert broker.query(sql).rows
    assert handed and all("operands" not in attrs for _, attrs in handed)
    del handed[:]
    traced = broker.query("SET trace = true; " + sql).stats.trace
    spans = _named(traced, "launch_enqueue")
    assert [sp["attrs"]["operands"] for sp in spans] == [leaves for leaves, _ in handed]
    assert all(leaves >= SEGMENTS + 1 for leaves, _ in handed)  # four members' columns and their parameters


def test_the_front_door_reads_no_cpu_clock_until_the_watch_runs(broker, monkeypatch):
    """The handler reads `time.thread_time` only while the interpreter watch
    runs (utils/interpreter.py): an untraced, unwatched request through the
    front door reads it 0 times, handler included, and starts no thread."""
    import json
    import threading
    import urllib.request

    from pinot_tpu.cluster.rest import QueryServer
    from pinot_tpu.utils.interpreter import WATCH, WATCH_THREAD

    WATCH.stop()
    reads = []
    real = time.thread_time

    def counting():
        reads.append(threading.current_thread().name)
        return real()

    monkeypatch.setattr(metrics.time, "thread_time", counting)
    front = QueryServer(broker).start()

    def post(sql):
        req = urllib.request.Request(f"http://127.0.0.1:{front.port}/query/sql", data=json.dumps({"sql": sql}).encode(),
                                     headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read().decode("utf-8"))

    try:
        for sql in (GROUP_SQL, SCALAR_SQL):
            assert post(sql)["resultTable"]["rows"]
        assert not reads and not [t for t in threading.enumerate() if t.name == WATCH_THREAD]
        assert post("SET trace = true; " + GROUP_SQL)["trace"]  # starts the watch
        assert WATCH.running and reads
        del reads[:]
        assert post(GROUP_SQL)["resultTable"]["rows"]  # untraced, watched: the door's four reads and no span's
        for _ in range(500):
            if len(reads) >= 4:
                break
            time.sleep(0.01)
        assert len(reads) == 4 and all("process_request_thread" in name for name in reads)
    finally:
        front.stop()
        WATCH.stop()
