"""Realtime parity (VERDICT r4 #10): MV columns in consuming segments,
snapshot-time index builds, upsert metadataTTL, consistent deletes,
APPEND/UNION partial strategies.

Reference model: MutableSegmentImpl.java:638 (every mutable index type),
ConcurrentMapPartitionUpsertMetadataManager.java:49 (metadataTTL, deletes),
PartialUpsertHandler APPEND/UNION.
"""
import numpy as np
import pytest

from pinot_tpu.query.engine import QueryEngine
from pinot_tpu.realtime import InMemoryStream, RealtimeTableDataManager
from pinot_tpu.spi.config import (
    IndexingConfig,
    SegmentsConfig,
    StreamConfig,
    TableConfig,
    UpsertConfig,
)
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema


def _mgr(schema, cfg, path, n_part=1):
    stream = InMemoryStream(n_part)
    return RealtimeTableDataManager(schema, cfg, str(path), stream=stream), stream


def _engine(schema, cfg, mgr):
    eng = QueryEngine()
    eng.register_table(schema, cfg)
    eng.attach_realtime(schema.name, mgr)
    return eng


class TestRealtimeMV:
    def _schema(self):
        return Schema(
            "events",
            [
                FieldSpec("eid", DataType.INT),
                FieldSpec("tags", DataType.STRING, single_value=False),
                FieldSpec("vals", DataType.INT, single_value=False),
                FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
            ],
        )

    def test_mv_ingest_and_query(self, tmp_path):
        schema = self._schema()
        cfg = TableConfig(
            "events",
            segments=SegmentsConfig(time_column="ts"),
            stream=StreamConfig(stream_type="memory", max_rows_per_segment=10),
        )
        mgr, stream = _mgr(schema, cfg, tmp_path / "t")
        eng = _engine(schema, cfg, mgr)
        rows = [
            {
                "eid": i,
                "tags": ["red", "blue"] if i % 2 == 0 else ["green"],
                "vals": [i, i * 10],
                "ts": 1_700_000_000_000 + i,
            }
            for i in range(25)
        ]
        stream.publish_many(rows, partition=0)
        mgr.consume_all()
        # spans 2 sealed + 1 consuming segment
        r = eng.query("SELECT COUNT(*) FROM events WHERE tags = 'red'")
        assert int(r.rows[0][0]) == 13  # even eids
        r2 = eng.query("SELECT SUMMV(vals) FROM events WHERE eid < 3")
        assert float(r2.rows[0][0]) == sum(i + i * 10 for i in range(3))
        # empty-MV row: missing tags ingests as empty, matches nothing
        stream.publish({"eid": 99, "tags": None, "vals": [1], "ts": 1_700_000_100_000}, partition=0)
        mgr.consume_all()
        r3 = eng.query("SELECT COUNT(*) FROM events WHERE tags = 'red'")
        assert int(r3.rows[0][0]) == 13

    def test_mv_value_at_point_read(self, tmp_path):
        schema = self._schema()
        cfg = TableConfig(
            "events",
            segments=SegmentsConfig(time_column="ts"),
            stream=StreamConfig(stream_type="memory", max_rows_per_segment=100),
        )
        mgr, stream = _mgr(schema, cfg, tmp_path / "t")
        stream.publish({"eid": 1, "tags": ["a", "b"], "vals": [7], "ts": 1}, partition=0)
        mgr.consume_all()
        m = next(iter(mgr.managers.values())).mutable
        assert m.value_at("tags", 0) == ("a", "b")
        assert m.value_at("vals", 0) == (7,)


class TestSnapshotIndexes:
    def test_consuming_snapshot_builds_configured_indexes(self, tmp_path):
        schema = Schema(
            "logs",
            [
                FieldSpec("level", DataType.STRING),
                FieldSpec("msg", DataType.STRING),
                FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
            ],
        )
        cfg = TableConfig(
            "logs",
            indexing=IndexingConfig(
                inverted_index_columns=["level"], text_index_columns=["msg"]
            ),
            segments=SegmentsConfig(time_column="ts"),
            stream=StreamConfig(stream_type="memory", max_rows_per_segment=1000),
        )
        mgr, stream = _mgr(schema, cfg, tmp_path / "t")
        eng = _engine(schema, cfg, mgr)
        rows = [
            {"level": ["info", "warn", "error"][i % 3], "msg": f"request {i} failed fast" if i % 3 == 2 else f"request {i} ok", "ts": i}
            for i in range(60)
        ]
        stream.publish_many(rows, partition=0)
        mgr.consume_all()
        r = eng.query("SELECT COUNT(*) FROM logs WHERE level = 'error'")
        assert int(r.rows[0][0]) == 20
        # the CONSUMING snapshot has its inverted index; a resident segment's codes scan cheaper than a
        # bitmap ships, so the planner scans them and says so (PR 47: filter.bitmap_serves)
        snapshot = mgr.query_segments()[-1]
        assert "level" in snapshot.indexes["inverted"]
        from pinot_tpu.query import planner
        from pinot_tpu.sql.parser import parse_query

        plan = planner.plan_segment(parse_query("SELECT COUNT(*) FROM logs WHERE level = 'error'"), snapshot)
        assert ("level", "inverted") in plan.index_scans and not r.stats.filter_index_uses
        r2 = eng.query("SELECT COUNT(*) FROM logs WHERE TEXT_MATCH(msg, 'failed')")
        assert int(r2.rows[0][0]) == 20
        assert ("msg", "text") in r2.stats.filter_index_uses


def _upsert_schema():
    return Schema(
        "orders",
        [
            FieldSpec("oid", DataType.STRING),
            FieldSpec("amount", DataType.DOUBLE, role=FieldRole.METRIC),
            FieldSpec("deleted", DataType.BOOLEAN),
            FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
        ],
        primary_key_columns=["oid"],
    )


class TestUpsertTTLAndDelete:
    def _cfg(self, **up):
        return TableConfig(
            "orders",
            segments=SegmentsConfig(time_column="ts"),
            stream=StreamConfig(stream_type="memory", max_rows_per_segment=1000),
            upsert=UpsertConfig(mode="FULL", comparison_column="ts", **up),
        )

    def test_consistent_delete_hides_rows(self, tmp_path):
        cfg = self._cfg(delete_record_column="deleted")
        mgr, stream = _mgr(_upsert_schema(), cfg, tmp_path / "t")
        eng = _engine(_upsert_schema(), cfg, mgr)
        stream.publish({"oid": "a", "amount": 10.0, "deleted": False, "ts": 1}, partition=0)
        stream.publish({"oid": "b", "amount": 20.0, "deleted": False, "ts": 2}, partition=0)
        stream.publish({"oid": "a", "amount": 0.0, "deleted": True, "ts": 3}, partition=0)
        mgr.consume_all()
        r = eng.query("SELECT COUNT(*), SUM(amount) FROM orders")
        assert int(r.rows[0][0]) == 1 and float(r.rows[0][1]) == 20.0
        # older out-of-order arrival cannot resurrect the deleted key
        stream.publish({"oid": "a", "amount": 99.0, "deleted": False, "ts": 2}, partition=0)
        mgr.consume_all()
        r2 = eng.query("SELECT COUNT(*) FROM orders")
        assert int(r2.rows[0][0]) == 1
        # NEWER arrival revives the key
        stream.publish({"oid": "a", "amount": 55.0, "deleted": False, "ts": 9}, partition=0)
        mgr.consume_all()
        r3 = eng.query("SELECT COUNT(*), SUM(amount) FROM orders")
        assert int(r3.rows[0][0]) == 2 and float(r3.rows[0][1]) == 75.0

    def test_metadata_ttl_expires_tracking(self, tmp_path):
        cfg = self._cfg(metadata_ttl=100.0)
        mgr, stream = _mgr(_upsert_schema(), cfg, tmp_path / "t")
        um = mgr.upsert
        stream.publish({"oid": "old", "amount": 1.0, "deleted": False, "ts": 10}, partition=0)
        stream.publish({"oid": "new", "amount": 2.0, "deleted": False, "ts": 500}, partition=0)
        mgr.consume_all()
        assert ("old",) in um.pk_map
        um.expire_ttl_keys()
        # ts=10 trails the 500 watermark by more than metadataTTL=100
        assert ("old",) not in um.pk_map
        assert ("new",) in um.pk_map
        # the expired key's ROW stays visible (tracking ends, data stays)
        eng = _engine(_upsert_schema(), cfg, mgr)
        assert int(eng.query("SELECT COUNT(*) FROM orders").rows[0][0]) == 2


class TestPartialMVStrategies:
    def test_append_and_union(self, tmp_path):
        schema = Schema(
            "carts",
            [
                FieldSpec("cid", DataType.STRING),
                FieldSpec("items", DataType.STRING, single_value=False),
                FieldSpec("seen", DataType.STRING, single_value=False),
                FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
            ],
            primary_key_columns=["cid"],
        )
        cfg = TableConfig(
            "carts",
            segments=SegmentsConfig(time_column="ts"),
            stream=StreamConfig(stream_type="memory", max_rows_per_segment=1000),
            upsert=UpsertConfig(
                mode="PARTIAL",
                comparison_column="ts",
                partial_upsert_strategies={"items": "APPEND", "seen": "UNION"},
            ),
        )
        mgr, stream = _mgr(schema, cfg, tmp_path / "t")
        stream.publish({"cid": "c1", "items": ["x"], "seen": ["x"], "ts": 1}, partition=0)
        stream.publish({"cid": "c1", "items": ["y"], "seen": ["x", "z"], "ts": 2}, partition=0)
        mgr.consume_all()
        m = next(iter(mgr.managers.values())).mutable
        # winning row is doc 1 (merged)
        assert m.value_at("items", 1) == ("x", "y")
        assert m.value_at("seen", 1) == ("x", "z")
