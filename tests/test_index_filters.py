"""Round-2: index-accelerated filtering (BitmapBasedFilterOperator /
SortedIndexBasedFilterOperator analogs).  Every query runs against two
identical tables — one fully indexed, one bare — and must return identical
rows; the indexed plan must report index use and must NOT ship the
filter-only column to the device."""
import numpy as np
import pytest

from pinot_tpu.query import planner
from pinot_tpu.query.engine import QueryEngine
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query

N = 6000
CITIES = ["sf", "nyc", "chi", "la", "sea", "pdx", "atx"]


def _schema(name):
    return Schema(
        name,
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("year", DataType.INT),
            FieldSpec("day", DataType.INT),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
        ],
    )


@pytest.fixture(scope="module")
def env():
    rng = np.random.default_rng(21)
    data = {
        "city": rng.choice(CITIES, N).astype(object),
        "year": rng.integers(2000, 2020, N).astype(np.int32),
        "day": rng.integers(0, 366, N).astype(np.int32),
        "v": rng.integers(0, 100_000, N),
    }
    engine = QueryEngine()

    plain_schema = _schema("plain")
    engine.register_table(plain_schema, TableConfig("plain"))
    engine.add_segment("plain", build_segment(plain_schema, dict(data), "p0"))

    idx_schema = _schema("indexed")
    cfg = TableConfig(
        "indexed",
        indexing=IndexingConfig(
            inverted_index_columns=["city"],
            range_index_columns=["year"],
            sorted_column="day",
        ),
    )
    engine.register_table(idx_schema, cfg)
    engine.add_segment("indexed", build_segment(idx_schema, dict(data), "i0", table_config=cfg))
    return engine


QUERIES = [
    ("SELECT COUNT(*), SUM(v) FROM {t} WHERE city = 'sf'", ("city", "inverted")),
    ("SELECT COUNT(*), SUM(v) FROM {t} WHERE city IN ('sf', 'nyc', 'la')", ("city", "inverted")),
    ("SELECT COUNT(*), SUM(v) FROM {t} WHERE city != 'chi'", ("city", "inverted")),
    ("SELECT COUNT(*), SUM(v) FROM {t} WHERE year > 2010", ("year", "range")),
    ("SELECT COUNT(*), SUM(v) FROM {t} WHERE year BETWEEN 2005 AND 2012", ("year", "range")),
    ("SELECT COUNT(*), SUM(v) FROM {t} WHERE day < 100", ("day", "sorted")),
    ("SELECT COUNT(*), SUM(v) FROM {t} WHERE day = 250", ("day", "sorted")),
    (
        "SELECT year, COUNT(*) FROM {t} WHERE city = 'sf' AND day >= 180 "
        "GROUP BY year ORDER BY year LIMIT 25",
        ("city", "inverted"),
    ),
    ("SELECT city FROM {t} WHERE year = 2001 AND day > 350 ORDER BY city LIMIT 5", ("year", "range")),
]


@pytest.mark.parametrize("sql_tpl,expected_use", QUERIES)
def test_indexed_matches_scan(env, sql_tpl, expected_use):
    """A segment resident on the device: the sorted column's doc range is
    taken; a range / inverted index is built and kept, and the planner, from
    costs (filter.bitmap_serves), scans the codes: the plan says which
    predicates had an index and scanned (PR 47).  The answer is the plain
    table's either way."""
    got_plain = env.query(sql_tpl.format(t="plain"))
    got_idx = env.query(sql_tpl.format(t="indexed"))
    assert got_idx.rows == got_plain.rows
    assert not got_plain.stats.filter_index_uses
    if expected_use[1] == "sorted":
        assert expected_use in got_idx.stats.filter_index_uses
    else:
        seg = env.tables["indexed"].segments[0]
        plan = planner.plan_segment(parse_query(sql_tpl.format(t="indexed")), seg)
        assert expected_use in plan.index_scans and expected_use not in plan.index_uses
        assert expected_use[0] in seg.indexes[expected_use[1]]
        assert expected_use not in got_idx.stats.filter_index_uses


def test_indexed_filter_column_scans_its_codes_and_ships_no_bitmap(env):
    """An EQ predicate on an inverted-indexed column of a resident segment
    scans the column's codes: no row-length bitmap rides the launch."""
    ctx = parse_query("SELECT SUM(v) FROM indexed WHERE city = 'sf'")
    seg = env.tables["indexed"].segments[0]
    plan = planner.plan_segment(ctx, seg)
    assert plan.index_scans == [("city", "inverted")] and not plan.index_uses
    assert "city" in plan.needed_columns and "v" in plan.needed_columns
    params = planner.unpack_params(plan.params, plan.param_layout)  # what the kernel reads
    assert not [k for k in params if k.endswith(".bits")]
    assert {k: v.shape for k, v in plan.params.items()} == {"int32": (2,)}  # the code range, packed
    assert plan.recipe is not None  # and a plan-cache hit binds it


def test_sorted_range_zero_reads(env):
    """A sorted-column range predicate compiles to two int params (doc
    range) — no column data and no bitmap shipped."""
    ctx = parse_query("SELECT COUNT(*) FROM indexed WHERE day < 50")
    seg = env.tables["indexed"].segments[0]
    plan = planner.plan_segment(ctx, seg)
    assert ("day", "sorted") in plan.index_uses
    assert "day" not in plan.needed_columns
    assert all(shape == () for _, _, shape in plan.param_layout)
    assert {k: v.shape for k, v in plan.params.items()} == {"int32": (2,)}  # the doc range, packed


def test_index_nulls_respected():
    """3VL: index-resolved predicates still exclude NULL rows."""
    schema = Schema(
        "nt",
        [
            FieldSpec("c", DataType.STRING, nullable=True),
            FieldSpec("v", DataType.INT, role=FieldRole.METRIC),
        ],
    )
    cfg = TableConfig("nt", indexing=IndexingConfig(inverted_index_columns=["c"]))
    e = QueryEngine()
    e.register_table(schema, cfg)
    data = {
        "c": np.array(["a", None, "b", "a", None, "b", "a"], dtype=object),
        "v": np.arange(7, dtype=np.int32),
    }
    e.add_segment("nt", build_segment(schema, data, "n0", table_config=cfg))
    r = e.query("SELECT COUNT(*) FROM nt WHERE c != 'a'")
    assert r.rows[0][0] == 2  # b rows only; NULLs excluded by 3VL
    plan = planner.plan_segment(parse_query("SELECT COUNT(*) FROM nt WHERE c != 'a'"), e.tables["nt"].segments[0])
    assert plan.index_scans == [("c", "inverted")]  # the index is there; the codes are scanned (PR 47)


# ---------------------------------------------------------------------------
# Distributed path (round 3): StackedTable carries indexes; the shard_map
# kernels ride shard-sliced bitmap words / global doc ranges instead of
# code scans, and index-only columns never ship to device.
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dist_env():
    from pinot_tpu.parallel.engine import DistributedEngine
    from pinot_tpu.parallel.stacked import StackedTable

    rng = np.random.default_rng(22)
    data = {
        "city": rng.choice(CITIES, N).astype(object),
        "year": rng.integers(2000, 2020, N).astype(np.int32),
        "day": np.sort(rng.integers(0, 366, N).astype(np.int32)),
        "v": rng.integers(0, 100_000, N),
    }
    cfg = TableConfig(
        "indexed",
        indexing=IndexingConfig(
            inverted_index_columns=["city"],
            range_index_columns=["year"],
            sorted_column="day",
        ),
    )
    eng = DistributedEngine()
    eng.register_table(
        "indexed",
        StackedTable.build(_schema("indexed"), dict(data), eng.num_devices, table_config=cfg),
    )
    eng.register_table("plain", StackedTable.build(_schema("plain"), dict(data), eng.num_devices))
    return eng


@pytest.mark.parametrize("sql_tpl,expected_use", QUERIES)
def test_distributed_indexed_matches_scan(dist_env, sql_tpl, expected_use):
    got_plain = dist_env.query(sql_tpl.format(t="plain"))
    got_idx = dist_env.query(sql_tpl.format(t="indexed"))
    assert got_idx.rows == got_plain.rows
    assert expected_use in got_idx.stats.filter_index_uses
    # the plain table has no configured indexes, but its physically-sorted
    # `day` column still legitimately takes the sorted doc-range path
    assert all(kind == "sorted" for _, kind in got_plain.stats.filter_index_uses)


def test_distributed_bitmap_params_shard_sliced(dist_env):
    """The distributed EQ plan ships [ndev, words] bitmap slices, not codes."""
    ctx = parse_query("SELECT SUM(v) FROM indexed WHERE city = 'sf'")
    stacked = dist_env.tables["indexed"]
    plan = dist_env._plan(ctx, stacked)
    assert ("city", "inverted") in plan.index_uses
    assert "city" not in plan.needed_columns
    bits = [plan.params[k] for k in plan.row_sharded_params]
    assert len(bits) == 1
    ndev = dist_env.num_devices
    L = stacked.num_shards // ndev
    # stored full as [ndev, L, D//32]; launch params slice the doc axis
    assert bits[0].shape == (ndev, L, stacked.docs_per_shard // 32)
    key = next(iter(plan.row_sharded_params))
    launch = dist_env.batch_params(plan, 0, 0)
    assert launch[key].shape == (ndev, L * plan.batch_docs // 32)


def test_distributed_sorted_doc_range(dist_env):
    """Sorted-column predicates over the stacked table: global doc-range
    params, no bitmap, no column shipment."""
    ctx = parse_query("SELECT COUNT(*) FROM indexed WHERE day < 50")
    stacked = dist_env.tables["indexed"]
    plan = dist_env._plan(ctx, stacked)
    assert ("day", "sorted") in plan.index_uses
    assert "day" not in plan.needed_columns
    assert not plan.row_sharded_params
    assert all(np.asarray(v).size <= 1 for v in plan.params.values())


def test_mse_join_with_indexed_fact_filter(dist_env):
    """Join queries pick up fact-side index acceleration too."""
    from pinot_tpu.parallel.stacked import StackedTable as _ST

    dim = {
        "y": np.arange(2000, 2020, dtype=np.int32),
        "decade": (np.arange(2000, 2020) // 10).astype(np.int32),
    }
    dschema = Schema("years", [FieldSpec("y", DataType.INT), FieldSpec("decade", DataType.INT)])
    dist_env.register_table("years", _ST.build(dschema, dim, dist_env.num_devices))
    res = dist_env.query(
        "SELECT decade, COUNT(*) FROM indexed JOIN years ON year = y "
        "WHERE city = 'sf' GROUP BY decade ORDER BY decade LIMIT 10"
    )
    assert ("city", "inverted") in res.stats.filter_index_uses
    plain = dist_env.query(
        "SELECT city, COUNT(*) FROM indexed WHERE city = 'sf' GROUP BY city"
    )
    assert sum(int(r[1]) for r in res.rows) == int(plain.rows[0][1])
