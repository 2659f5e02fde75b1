"""Broker query quota + adaptive replica selection + new minion tasks
(VERDICT r4 missing #9/#10, weak #11).

Reference model: HelixExternalViewBasedQueryQuotaManager (per-table QPS),
pinot-broker adaptiveserverselector (latency/in-flight biased routing),
UpsertCompactionTaskExecutor, RefreshSegmentTaskExecutor.
"""
import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.broker import AdaptiveServerStats, QueryQuotaManager, QuotaExceededError
from pinot_tpu.cluster.minion import MinionTaskManager
from pinot_tpu.realtime import InMemoryStream, RealtimeTableDataManager
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import (
    IndexingConfig,
    SegmentsConfig,
    StreamConfig,
    TableConfig,
    UpsertConfig,
)
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema


def _schema():
    return Schema(
        "t",
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
            FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
        ],
    )


def _data(n, seed=1):
    rng = np.random.default_rng(seed)
    return {
        "city": rng.choice(["sf", "nyc"], n).astype(object),
        "v": rng.integers(0, 100, n),
        "ts": 1_700_000_000_000 + rng.integers(0, 1000, n).astype(np.int64),
    }


class TestQueryQuota:
    def test_quota_token_bucket(self):
        q = QueryQuotaManager()
        for i in range(3):
            q.check("t", 3.0, now=100.0 + i * 0.001)  # burst capacity = qps
        with pytest.raises(QuotaExceededError):
            q.check("t", 3.0, now=100.01)
        # tokens refill at 3/s: ~0.4s later one query fits again
        q.check("t", 3.0, now=100.5)

    def test_broker_enforces_table_quota(self):
        coord = Coordinator(replication=1)
        coord.register_server(ServerInstance("s0"))
        cfg = TableConfig(
            name="t", segments=SegmentsConfig(time_column="ts"), max_queries_per_second=2.0
        )
        coord.add_table(_schema(), cfg)
        coord.add_segment("t", build_segment(_schema(), _data(100), "s", table_config=cfg))
        broker = Broker(coord)
        # frozen clock: query duration (JAX compiles!) must not refill tokens
        broker.quota.clock = lambda: 1000.0
        broker.query("SELECT COUNT(*) FROM t")
        broker.query("SELECT COUNT(*) FROM t")
        with pytest.raises(QuotaExceededError):
            broker.query("SELECT COUNT(*) FROM t")
        # advancing the clock refills
        broker.quota.clock = lambda: 1000.6
        broker.query("SELECT COUNT(*) FROM t")

    def test_quota_charges_once_per_request(self):
        """Set-op operands / subqueries must not double-charge the quota
        (review-caught: UNION ALL on a qps=1 table could never succeed)."""
        coord = Coordinator(replication=1)
        coord.register_server(ServerInstance("s0"))
        cfg = TableConfig(
            name="t", segments=SegmentsConfig(time_column="ts"), max_queries_per_second=1.0
        )
        coord.add_table(_schema(), cfg)
        coord.add_segment("t", build_segment(_schema(), _data(100), "s", table_config=cfg))
        broker = Broker(coord)
        broker.quota.clock = lambda: 50.0
        r = broker.query(
            "SELECT COUNT(*) FROM t UNION ALL SELECT COUNT(*) FROM t"
        )
        assert len(r.rows) == 2  # one request, one token

    def test_zero_quota_is_unlimited(self):
        q = QueryQuotaManager()
        for i in range(100):
            q.check("t", 0.0, now=50.0)

    def test_fractional_quota(self):
        """q=0.5 means one query per 2 seconds (review-caught: a 1s sliding
        window admitted ceil(q))."""
        q = QueryQuotaManager()
        q.check("t", 0.5, now=100.0)
        with pytest.raises(QuotaExceededError):
            q.check("t", 0.5, now=101.0)  # only 1s elapsed: 0.5 tokens
        q.check("t", 0.5, now=102.1)  # 2.1s since success: ~1.05 tokens

    def test_fractional_quota_refill_via_injectable_clock(self):
        """Same contract through the clock the broker path uses (no `now=`):
        q=0.5 admits exactly one query per 2-second window."""
        clk = [100.0]
        q = QueryQuotaManager()
        q.clock = lambda: clk[0]
        q.check("t", 0.5)
        admitted = 1
        for _ in range(40):  # walk 10s in 0.25s steps
            clk[0] += 0.25
            try:
                q.check("t", 0.5)
                admitted += 1
            except QuotaExceededError:
                pass
        assert admitted == 1 + 5  # one per 2s over the 10s walk


class TestAdaptiveSelection:
    def test_scores_prefer_fast_idle_servers(self):
        st = AdaptiveServerStats()
        st.begin("slow"); st.end("slow", 100.0)
        st.begin("fast"); st.end("fast", 5.0)
        assert st.score("fast") < st.score("slow")
        # in-flight load inflates the score
        st.begin("fast")
        st.begin("fast")
        assert st.score("fast") == 5.0 * 3

    def test_adaptive_routing_avoids_slow_replica(self):
        coord = Coordinator(replication=2)
        for i in range(2):
            coord.register_server(ServerInstance(f"server{i}"))
        cfg = TableConfig(name="t", segments=SegmentsConfig(time_column="ts"))
        coord.add_table(_schema(), cfg)
        for i in range(4):
            coord.add_segment("t", build_segment(_schema(), _data(50, seed=i), f"s{i}", table_config=cfg))
        broker = Broker(coord, selector="adaptive")
        # feed stats: server0 is 100x slower
        broker.server_stats.end("server0", 0)  # init entries
        broker.server_stats.ewma_ms["server0"] = 500.0
        broker.server_stats.ewma_ms["server1"] = 2.0
        assign = broker._route("t", [f"s{i}" for i in range(4)])
        # every segment replicated on both servers -> all go to the fast one
        assert set(assign) == {"server1"}
        # queries still work end-to-end and refresh the stats
        r = broker.query("SELECT COUNT(*) FROM t")
        assert int(r.rows[0][0]) == 200
        assert broker.server_stats.ewma_ms["server1"] != 2.0  # updated


class TestAdaptiveStatsConcurrency:
    def test_begin_end_under_concurrent_threads(self):
        """begin/end are read-modify-writes: unlocked, two begins could both
        read in_flight=0 (count lost -> later end drives it negative) and
        EWMA decay updates could vanish.  Hammer one shared server from many
        threads and verify the invariants hold."""
        import threading

        st = AdaptiveServerStats()
        n_threads, n_iter = 8, 500
        errors = []

        def worker(tid):
            try:
                for i in range(n_iter):
                    st.begin("shared")
                    st.end("shared", float((tid * n_iter + i) % 37) + 1.0)
                    # per-thread server: its EWMA entry must never be lost
                    st.begin(f"srv{tid}")
                    st.end(f"srv{tid}", 10.0 * (tid + 1))
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        # every begin was paired with an end: in-flight settles at exactly 0
        assert st.in_flight["shared"] == 0
        assert all(st.in_flight[f"srv{t}"] == 0 for t in range(n_threads))
        # no lost dict updates: every per-thread server kept its EWMA (each
        # thread always reports the same latency, so EWMA == that latency)
        for t in range(n_threads):
            assert st.ewma_ms[f"srv{t}"] == pytest.approx(10.0 * (t + 1))
        assert st.ewma_ms["shared"] > 0.0

    def test_punish_inflates_score(self):
        st = AdaptiveServerStats()
        st.begin("s"); st.end("s", 4.0)
        before = st.score("s")
        st.punish("s")
        assert st.score("s") >= max(2 * before, 50.0)
        # repeated punishment keeps compounding (flaky stays deprioritized)
        st.punish("s")
        assert st.ewma_ms["s"] == pytest.approx(100.0)


class TestUpsertCompaction:
    def test_compaction_drops_invalidated_rows(self, tmp_path):
        schema = Schema(
            "o",
            [
                FieldSpec("oid", DataType.STRING),
                FieldSpec("amount", DataType.DOUBLE, role=FieldRole.METRIC),
                FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
            ],
            primary_key_columns=["oid"],
        )
        cfg = TableConfig(
            "o",
            segments=SegmentsConfig(time_column="ts"),
            stream=StreamConfig(stream_type="memory", max_rows_per_segment=10),
            upsert=UpsertConfig(mode="FULL", comparison_column="ts"),
        )
        stream = InMemoryStream(1)
        mgr = RealtimeTableDataManager(schema, cfg, str(tmp_path / "t"), stream=stream)
        # 30 rows over 5 keys: each key updated 6x -> sealed segments carry
        # mostly-invalidated rows
        rows = [
            {"oid": f"k{i % 5}", "amount": float(i), "ts": 1000 + i} for i in range(30)
        ]
        stream.publish_many(rows, partition=0)
        mgr.consume_all()
        sealed_before = [s for segs in mgr.sealed.values() for s in segs]
        assert sealed_before and any(
            s.valid_docs is not None and not np.asarray(s.valid_docs).all() for s in sealed_before
        )
        from pinot_tpu.query.engine import QueryEngine

        eng = QueryEngine()
        eng.register_table(schema, cfg)
        eng.attach_realtime("o", mgr)
        before = eng.query("SELECT oid, amount FROM o ORDER BY oid LIMIT 10").rows

        coord = Coordinator(replication=1)
        report = MinionTaskManager(coord).upsert_compact("o", realtime_manager=mgr)
        assert report["compacted"] and report["rowsDropped"] > 0
        for segs in mgr.sealed.values():
            for s in segs:
                assert np.asarray(s.valid_docs).all()  # fully compacted
        after = eng.query("SELECT oid, amount FROM o ORDER BY oid LIMIT 10").rows
        assert before == after
        # further upserts still resolve correctly against remapped locations
        stream.publish({"oid": "k0", "amount": 999.0, "ts": 99999}, partition=0)
        mgr.consume_all()
        r = eng.query("SELECT amount FROM o WHERE oid = 'k0' LIMIT 2")
        assert len(r.rows) == 1 and float(r.rows[0][0]) == 999.0


class TestUpsertCompactionTombstones:
    def test_compaction_with_delete_tombstones(self, tmp_path):
        """A compacted-away tombstone row must not leave its pk_map location
        pointing into the shorter segment (review-caught: a later upsert
        would mask out a DIFFERENT key's live row)."""
        schema = Schema(
            "o",
            [
                FieldSpec("oid", DataType.STRING),
                FieldSpec("amount", DataType.DOUBLE, role=FieldRole.METRIC),
                FieldSpec("deleted", DataType.BOOLEAN),
                FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
            ],
            primary_key_columns=["oid"],
        )
        cfg = TableConfig(
            "o",
            segments=SegmentsConfig(time_column="ts"),
            stream=StreamConfig(stream_type="memory", max_rows_per_segment=8),
            upsert=UpsertConfig(
                mode="FULL", comparison_column="ts", delete_record_column="deleted"
            ),
        )
        stream = InMemoryStream(1)
        mgr = RealtimeTableDataManager(schema, cfg, str(tmp_path / "t"), stream=stream)
        rows = [
            {"oid": f"k{i % 4}", "amount": float(i), "deleted": False, "ts": 100 + i}
            for i in range(7)
        ]
        # tombstone k1 inside the first sealed segment (8 rows/seal)
        rows.append({"oid": "k1", "amount": 0.0, "deleted": True, "ts": 200})
        rows += [
            {"oid": f"k{i % 4}", "amount": 50.0 + i, "deleted": False, "ts": 300 + i}
            for i in range(4)
        ]
        stream.publish_many(rows, partition=0)
        mgr.consume_all()
        coord = Coordinator(replication=1)
        MinionTaskManager(coord).upsert_compact("o", realtime_manager=mgr, invalid_threshold=0.01)
        # tombstone entry is marked compacted-away, not a stale index
        assert mgr.upsert.pk_map[("k1",)].doc == -1 or not mgr.upsert.pk_map[("k1",)].deleted
        from pinot_tpu.query.engine import QueryEngine

        eng = QueryEngine()
        eng.register_table(schema, cfg)
        eng.attach_realtime("o", mgr)
        # a NEWER row revives k1; other keys keep exactly one live row each
        stream.publish({"oid": "k1", "amount": 77.0, "deleted": False, "ts": 999}, partition=0)
        mgr.consume_all()
        res = eng.query("SELECT oid, amount FROM o ORDER BY oid LIMIT 10")
        got = {a: float(b) for a, b in res.rows}
        # latest per key: k0 ts=300 amount=50, k1 revived at ts=999,
        # k2 ts=302 amount=52, k3 ts=303 amount=53
        assert got == {"k0": 50.0, "k1": 77.0, "k2": 52.0, "k3": 53.0}, got


class TestRefreshSegment:
    def test_refresh_picks_up_new_index_config(self):
        coord = Coordinator(replication=1)
        coord.register_server(ServerInstance("s0"))
        cfg = TableConfig(name="t", segments=SegmentsConfig(time_column="ts"))
        coord.add_table(_schema(), cfg)
        coord.add_segment("t", build_segment(_schema(), _data(500), "seg0", table_config=cfg))
        broker = Broker(coord)
        before = broker.query("SELECT city, COUNT(*), SUM(v) FROM t GROUP BY city ORDER BY city").rows
        # config change: add an inverted index, then refresh
        meta = coord.tables["t"]
        meta.config.indexing = IndexingConfig(inverted_index_columns=["city"])
        report = MinionTaskManager(coord).run("RefreshSegmentTask", "t")
        assert report["refreshed"] == ["seg0"]
        after = broker.query("SELECT city, COUNT(*), SUM(v) FROM t GROUP BY city ORDER BY city").rows
        assert before == after
        r = broker.query("SELECT COUNT(*) FROM t WHERE city = 'sf'")
        # the refreshed segment has the index; a resident segment's plan scans the codes and says so (PR 47)
        refreshed = next(iter(coord.servers.values())).get_segment("t", "seg0")
        assert "city" in refreshed.indexes["inverted"] and not r.stats.filter_index_uses
        from pinot_tpu.query import planner
        from pinot_tpu.sql.parser import parse_query

        plan = planner.plan_segment(parse_query("SELECT COUNT(*) FROM t WHERE city = 'sf'"), refreshed)
        assert plan.index_scans == [("city", "inverted")]
