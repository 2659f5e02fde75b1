"""Concurrent serving tier (round 12): cross-query vmap batching.

Same-shape in-flight queries coalesce in the broker's MicroBatcher and
execute as ONE vmapped plan launch; results must be bit-exact vs the
sequential path, per-member stats must SUM to one unbatched run (never
N duplicated copies), and batch-member kills must leave siblings exact.

Determinism: every test injects a fake clock (``broker.batch_clock`` /
``MicroBatcher(clock=...)``) and drives flushes with ``drain_batches()`` /
``pump(now)`` — no real sleeps anywhere.
"""
import numpy as np
import pytest

from pinot_tpu.analysis.compile_audit import SSE_AUDIT
from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.admission import QueryKilledError
from pinot_tpu.cluster.batcher import MicroBatcher
from pinot_tpu.query import executor as sse_executor
from pinot_tpu.query.safety import Deadline, QueryTimeoutError
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import SegmentsConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
from pinot_tpu.utils.metrics import METRICS


def _schema():
    return Schema(
        "t",
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
            FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
        ],
    )


def _data(n, seed, t0=1_700_000_000_000):
    rng = np.random.default_rng(seed)
    return {
        "city": rng.choice(["sf", "nyc", "la"], n).astype(object),
        "v": rng.integers(0, 100, n),
        "ts": t0 + rng.integers(0, 86_400_000, n).astype(np.int64),
    }


def _cluster(n_servers=2, replication=2, n_segments=4, rows=200):
    coord = Coordinator(replication=replication)
    for i in range(n_servers):
        coord.register_server(ServerInstance(f"server{i}"))
    coord.add_table(_schema(), TableConfig(name="t", segments=SegmentsConfig(time_column="ts")))
    for i in range(n_segments):
        coord.add_segment("t", build_segment(_schema(), _data(rows, seed=100 + i), f"seg{i}"))
    return coord


def _broker(coord):
    b = Broker(coord)
    b.batch_clock = lambda: 0.0  # deterministic: groups flush only on drain
    return b


SAME_SHAPE = [
    f"SELECT city, COUNT(*), SUM(v) FROM t WHERE v < {40 + i} GROUP BY city ORDER BY city"
    for i in range(5)
]


class TestBitExactness:
    def test_batched_equals_sequential(self):
        coord = _cluster()
        broker = _broker(coord)
        futs = [broker.submit(q) for q in SAME_SHAPE]
        assert broker.drain_batches() >= 1
        batched = [f.result() for f in futs]
        sequential = [broker.query(q) for q in SAME_SHAPE]
        for b, s in zip(batched, sequential):
            assert b.rows == s.rows
        assert METRICS.counter("broker.batches").value >= 1

    def test_query_many_wrapper(self):
        coord = _cluster()
        broker = _broker(coord)
        outs = broker.query_many(SAME_SHAPE)
        for out, q in zip(outs, SAME_SHAPE):
            assert out.rows == broker.query(q).rows


class TestStatsAttribution:
    def test_member_stats_sum_to_one_unbatched_run(self):
        """The regression the issue demands: summing batched member stats
        reproduces ONE unbatched execution — docs exactly, kernel
        bytes to float tolerance — never N duplicated copies."""
        coord = _cluster()
        broker = _broker(coord)
        futs = [broker.submit(q) for q in SAME_SHAPE]
        broker.drain_batches()
        batched = [f.result() for f in futs]

        unbatched = broker.query(SAME_SHAPE[0])
        n = len(SAME_SHAPE)
        assert sum(b.stats.num_docs_scanned for b in batched) == unbatched.stats.num_docs_scanned
        assert unbatched.stats.kernel_bytes > 0
        assert sum(b.stats.kernel_bytes for b in batched) == pytest.approx(
            unbatched.stats.kernel_bytes, rel=1e-6
        )
        # total_docs reports table size per member (not a cost — undivided)
        for b in batched:
            assert b.stats.total_docs == unbatched.stats.total_docs
        # per-member docs differ by at most 1 (the divmod remainder)
        docs = [b.stats.num_docs_scanned for b in batched]
        assert max(docs) - min(docs) <= 1


class TestCompileBudget:
    def test_at_most_two_compiles_per_shape(self):
        """One base compile (per-segment plan cache) + one vmapped compile
        (batch fn cache) per shape — the acceptance criterion's <=2."""
        coord = _cluster()
        broker = _broker(coord)
        broker.query(SAME_SHAPE[0])  # warm the base plan
        SSE_AUDIT.reset()
        sse_executor.BATCH_AUDIT.reset()
        futs = [broker.submit(q) for q in SAME_SHAPE]
        broker.drain_batches()
        for f in futs:
            f.result()
        base = SSE_AUDIT.summary()
        batch = sse_executor.BATCH_AUDIT.snapshot()
        assert base["compiles_total"] == 0  # base plan already cached
        assert batch["compiles"] <= 1  # exactly one vmapped trace per width
        # second wave of the same shape: zero compiles anywhere
        futs = [broker.submit(q) for q in SAME_SHAPE]
        broker.drain_batches()
        for f in futs:
            f.result()
        assert SSE_AUDIT.summary()["compiles_total"] == 0
        assert sse_executor.BATCH_AUDIT.snapshot()["compiles"] == batch["compiles"]


class TestMixedShapes:
    def test_mixed_shape_storm_never_cross_coalesces(self):
        """Distinct shapes (different group key / aggregate structure) form
        distinct batch groups; every result stays correct."""
        coord = _cluster()
        broker = _broker(coord)
        shapes = [
            "SELECT city, COUNT(*) FROM t WHERE v < 30 GROUP BY city ORDER BY city",
            "SELECT COUNT(*), MAX(v) FROM t WHERE v > 10",
            "SELECT city, SUM(v) FROM t GROUP BY city ORDER BY city LIMIT 2",
        ]
        storm = [q for q in shapes for _ in range(3)]
        b0 = METRICS.counter("broker.batches").value
        futs = [broker.submit(q) for q in storm]
        broker.drain_batches()
        outs = [f.result() for f in futs]
        for out, q in zip(outs, storm):
            assert out.rows == broker.query(q).rows
        # one batch per distinct shape, not one mega-batch
        assert METRICS.counter("broker.batches").value - b0 == len(shapes)

    def test_literal_variants_do_coalesce(self):
        """Same shape, different literals: ONE batch group (the whole point
        of canonicalizing literals into parameter slots)."""
        coord = _cluster()
        broker = _broker(coord)
        b0 = METRICS.counter("broker.batches").value
        futs = [broker.submit(q) for q in SAME_SHAPE]
        broker.drain_batches()
        for f in futs:
            f.result()
        assert METRICS.counter("broker.batches").value - b0 == 1


class TestMemberIsolation:
    def test_killed_member_detaches_siblings_exact(self):
        """server.execute_batch: one member's kill probe fires mid-batch —
        its error records, every sibling's result is bit-exact."""
        coord = _cluster(n_servers=1, replication=1)
        server = coord.servers["server0"]
        seg_names = sorted(coord.external_view("t").keys())
        ctxs = [parse_query(q) for q in SAME_SHAPE]
        kill_idx = 2
        cancels = [
            (lambda: "killed by test") if i == kill_idx else (lambda: None)
            for i in range(len(ctxs))
        ]
        results, stats, errors, _ = server.execute_batch(
            ctxs, seg_names, table_schema=coord.tables["t"].schema, cancels=cancels
        )
        assert isinstance(errors[kill_idx], QueryKilledError)
        for i, q in enumerate(SAME_SHAPE):
            if i == kill_idx:
                continue
            assert errors[i] is None
            ref_res, _ = server.execute(parse_query(q), seg_names,
                                        table_schema=coord.tables["t"].schema)
            from pinot_tpu.query.reduce import reduce_results
            from pinot_tpu.query.result import ExecutionStats

            got = reduce_results(parse_query(q), results[i], ExecutionStats())
            want = reduce_results(parse_query(q), ref_res, ExecutionStats())
            assert got.rows == want.rows

    def test_expired_member_detaches_siblings_exact(self):
        coord = _cluster(n_servers=1, replication=1)
        server = coord.servers["server0"]
        seg_names = sorted(coord.external_view("t").keys())
        ctxs = [parse_query(q) for q in SAME_SHAPE[:3]]
        deadlines = [None, Deadline(0.0), None]  # member 1 born expired
        results, stats, errors, _ = server.execute_batch(
            ctxs, seg_names, table_schema=coord.tables["t"].schema, deadlines=deadlines
        )
        assert isinstance(errors[1], QueryTimeoutError)
        assert errors[0] is None and errors[2] is None
        from pinot_tpu.query.reduce import reduce_results
        from pinot_tpu.query.result import ExecutionStats

        for i in (0, 2):
            ref_res, _ = server.execute(parse_query(SAME_SHAPE[i]), seg_names,
                                        table_schema=coord.tables["t"].schema)
            got = reduce_results(parse_query(SAME_SHAPE[i]), results[i], ExecutionStats())
            want = reduce_results(parse_query(SAME_SHAPE[i]), ref_res, ExecutionStats())
            assert got.rows == want.rows


class TestMicroBatcher:
    def test_bounded_wait_expiry_flushes_singleton(self):
        ran = []
        mb = MicroBatcher(lambda entries: ran.append(len(entries)) or [
            e.future.set_result(e.payload) for e in entries
        ], wait_ms=5, max_batch=8, clock=lambda: 0.0)
        fut = mb.submit("k", "q0")
        assert mb.pump(now=0.004) == 0  # window not yet expired
        assert not fut.done()
        assert mb.pump(now=0.0051) == 1  # expiry flushes the singleton
        assert fut.result() == "q0" and ran == [1]

    def test_full_group_flushes_inline_without_clock(self):
        ran = []
        mb = MicroBatcher(lambda entries: ran.append(len(entries)) or [
            e.future.set_result(i) for i, e in enumerate(entries)
        ], wait_ms=5, max_batch=3, clock=lambda: 0.0)
        futs = [mb.submit("k", f"q{i}") for i in range(3)]
        assert ran == [3]  # flushed at max_batch, no pump needed
        assert [f.result() for f in futs] == [0, 1, 2]
        assert mb.pending() == 0

    def test_keys_never_mix(self):
        groups = []
        mb = MicroBatcher(lambda entries: groups.append([e.payload for e in entries]) or [
            e.future.set_result(None) for e in entries
        ], wait_ms=5, max_batch=8, clock=lambda: 0.0)
        mb.submit("a", "a0"), mb.submit("b", "b0"), mb.submit("a", "a1")
        assert mb.flush() == 2
        assert sorted(map(sorted, groups)) == [["a0", "a1"], ["b0"]]

    def test_wait_zero_bypasses_coalescing(self):
        ran = []
        mb = MicroBatcher(lambda entries: ran.append(len(entries)) or [
            e.future.set_result(None) for e in entries
        ], wait_ms=0, max_batch=8, clock=lambda: 0.0)
        mb.submit("k", "q0"), mb.submit("k", "q1")
        assert ran == [1, 1]  # each ran inline as a singleton

    def test_runner_crash_fails_futures_not_process(self):
        def boom(entries):
            raise RuntimeError("runner died")

        mb = MicroBatcher(boom, wait_ms=5, max_batch=8, clock=lambda: 0.0)
        fut = mb.submit("k", "q0")
        mb.flush()
        with pytest.raises(RuntimeError, match="runner died"):
            fut.result()


class TestBypasses:
    def test_non_batchable_shapes_run_synchronously(self):
        """EXPLAIN and set-op queries bypass the batcher entirely but still
        return completed futures."""
        coord = _cluster()
        broker = _broker(coord)
        fut = broker.submit("EXPLAIN PLAN FOR SELECT city, COUNT(*) FROM t GROUP BY city")
        assert fut.done()  # never queued
        sub = (
            "SELECT city, COUNT(*) FROM t GROUP BY city "
            "UNION ALL SELECT city, COUNT(*) FROM t GROUP BY city"
        )
        fut2 = broker.submit(sub)
        assert fut2.done()
        assert broker.drain_batches() == 0

    def test_parse_error_returns_failed_future(self):
        coord = _cluster()
        broker = _broker(coord)
        fut = broker.submit("SELECT FROM WHERE")
        assert fut.done()
        with pytest.raises(Exception):
            fut.result()
