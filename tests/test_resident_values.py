"""A dictionary column read BY VALUE past the contraction's range is resident
decoded (PR 49): `SegmentPlan.value_columns`, `ImmutableSegment.to_device`'s
`<col>#values` flavour, `transform.column_values`' third form.

Four segments built apart behind a broker, `key` (INT) and `price` (FLOAT)
under dictionaries of more than segmented._CONTRACT_MAX_TABLE entries, no two
alike; one segment's `key` has nulls, one segment has upsert's `valid_docs`.
Every query is answered twice, by the parent's program (the rule held to the
gather: `planner.lookup_form` patched) and by this one, and the answers are
equal bit for bit; exact aggregates equal numpy over the rows as well.  The
lowered programs hold no gather, and no dictionary operand where nothing
else reads the table; a query that filters or groups on the column too reads
both from one entry; segments whose dictionaries differ share ONE kernel; and
the server's look-ahead hands the staging thread the flavour the plan will
ask for, once.
"""
import numpy as np
import pytest

import jax

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.admission import ResourceBudget
from pinot_tpu.ops import code_lookup
from pinot_tpu.ops.code_lookup import CONTRACTED, GATHERED, RESIDENT
from pinot_tpu.ops.segmented import _CONTRACT_MAX_TABLE
from pinot_tpu.query import planner
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.residency import ResidencyManager
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query

SEGMENTS, ROWS = 4, 140_000
NULLS_IN, UPSERTS_IN = 1, 2  # the segment whose `key` has nulls; the one with valid_docs
SCHEMA = Schema("t", [
    FieldSpec("key", DataType.INT, nullable=True),
    FieldSpec("price", DataType.FLOAT),
    FieldSpec("grp", DataType.INT),
    FieldSpec("v", DataType.INT, role=FieldRole.METRIC),
])

QUERIES = {
    "hll_grouped": "SELECT grp, DISTINCTCOUNTHLL(key, 12) FROM t WHERE v < 800 GROUP BY grp ORDER BY grp LIMIT 100",
    "hll_scalar": "SELECT DISTINCTCOUNTHLL(key) FROM t",
    "hll_and_sum": "SELECT grp, DISTINCTCOUNTHLL(key, 10), SUM(v) FROM t WHERE v > 100 GROUP BY grp ORDER BY grp LIMIT 100",
    "distinctcount": "SELECT DISTINCTCOUNT(key) FROM t WHERE v < 50",
    "sum_max": "SELECT SUM(key), MAX(key), MIN(key + v), COUNT(key) FROM t WHERE v < 900",
    "sum_grouped": "SELECT grp, SUM(key), AVG(key) FROM t GROUP BY grp ORDER BY grp LIMIT 100",
    "float_values": "SELECT SUM(price), MIN(price), MAX(price * 2) FROM t WHERE v >= 10",
    "filters_on_it": "SELECT SUM(key), COUNT(*) FROM t WHERE key > 1000000 AND v < 500",
    "in_on_it": "SELECT MAX(key) FROM t WHERE key IN (5, 17, 1000003, 2000001) OR v = 7",
    "groups_on_it": "SELECT key, SUM(key), COUNT(*) FROM t WHERE v < 2 GROUP BY key ORDER BY key LIMIT 25",
    "case_on_it": "SELECT SUM(CASE WHEN key > 2000000 THEN key ELSE 0 END), SUM(key) FROM t WHERE v < 300",
}
# the columns read decoded, the same for every segment
VALUE_COLUMNS = {
    "hll_grouped": {"key"}, "hll_scalar": {"key"}, "hll_and_sum": {"key"},
    "distinctcount": set(),  # bound to the shared int range or to the codes: never the RESIDENT form's to say here
    "sum_max": {"key"}, "sum_grouped": {"key"}, "float_values": {"price"},
    "filters_on_it": {"key"}, "in_on_it": {"key"}, "groups_on_it": {"key"},
    "case_on_it": {"key"},  # SUM(key)'s: the CASE's own reads are not taken for by-value ones
}


def _block(i):
    rng = np.random.default_rng([49, i])
    # drawn with replacement from a pool of its own: ~137,000-139,000 distinct keys a segment
    key = rng.integers(0, 4_000_000 + 1_500_000 * i, ROWS).astype(np.int32)
    price = (rng.integers(0, 3_000_000 + 500_000 * i, ROWS) / 8).astype(np.float32)
    block = {
        "key": key, "price": price,
        "grp": rng.integers(0, 9, ROWS).astype(np.int32),
        "v": rng.integers(0, 1000, ROWS).astype(np.int32),
    }
    if i == NULLS_IN:
        holes = rng.random(ROWS) < 0.05
        block["key"] = np.where(holes, None, key.astype(object))
        block["key_nulls"] = holes
    return block


@pytest.fixture(scope="module")
def blocks():
    return [_block(i) for i in range(SEGMENTS)]


def _cluster(blocks, residency=None):
    coord, server = Coordinator(replication=1), ServerInstance("server0", residency=residency)
    coord.register_server(server)
    coord.add_table(SCHEMA)
    valid = np.random.default_rng(490).random(ROWS) < 0.7
    for i, block in enumerate(blocks):
        seg = build_segment(SCHEMA, {k: v for k, v in block.items() if k in SCHEMA.column_names}, f"seg{i}")
        if i == UPSERTS_IN:
            seg.valid_docs = valid.copy()
        coord.add_segment("t", seg)
    return Broker(coord), server, valid


def _rows(result):
    t = result.to_dict()
    assert not t["exceptions"] and not t["partialResult"] and t["numSegmentsQueried"] == SEGMENTS, t
    return t["resultTable"]["rows"]


@pytest.fixture(scope="module")
def gathered(blocks):
    """Every query's rows under the parent's rule: nothing is RESIDENT, so the
    plans ask for no decoded column and column_values gathers."""
    planner.plan_cache_clear()
    real = code_lookup.lookup_form
    planner.lookup_form = lambda *a, **kw: GATHERED if real(*a, **kw) == RESIDENT else real(*a, **kw)
    try:
        broker, server, _ = _cluster(blocks)
        rows = {name: _rows(broker.query(sql)) for name, sql in QUERIES.items()}
        for name, sql in QUERIES.items():
            assert all(not plan.value_columns and not plan.lookups[RESIDENT] for _, plan in _plans(broker, server, sql)), name
    finally:
        planner.lookup_form = real
        planner.plan_cache_clear()
    return rows


@pytest.fixture(scope="module")
def table(blocks, gathered):
    planner.plan_cache_clear()
    yield _cluster(blocks)
    planner.plan_cache_clear()


def _ctx(broker, sql):
    ctx = parse_query(sql)
    broker._inject_global_ranges(ctx, "t")  # what a sketch binds to: part of the served query's shape
    return ctx


def _plans(broker, server, sql):
    planning = planner.QueryPlanning(_ctx(broker, sql), server.shapes["t"])
    return [(seg, planning.plan(seg)) for seg in server.segments["t"].values()]


def _staged(seg, plan):
    return seg.to_device(
        columns=plan.needed_columns, packed_codes=True, dict_rows=plan.dict_sizes, value_columns=plan.value_columns
    )


def _spans(node, name):
    if node["name"] == name:
        yield node
    for c in node.get("children", ()):
        yield from _spans(c, name)


def test_the_dictionaries_are_past_the_range_and_differ(table):
    _, server, _ = table
    segs = list(server.segments["t"].values())
    for name in ("key", "price"):
        sizes = [s.column(name).cardinality for s in segs]
        assert min(sizes) > _CONTRACT_MAX_TABLE and len(set(sizes)) == SEGMENTS, (name, sizes)
        assert all(s.column(name).packed is None and s.column(name).codes.dtype.itemsize == 4 for s in segs)
    assert segs[NULLS_IN].column("key").nulls.sum() > 0 and segs[UPSERTS_IN].valid_docs is not None


@pytest.mark.parametrize("name", list(QUERIES))
def test_equals_the_gathers_answer_bit_for_bit(table, gathered, name):
    broker, server, _ = table
    first = _rows(broker.query(QUERIES[name]))
    assert first == gathered[name] and len(first) > 0
    assert _rows(broker.query(QUERIES[name])) == first  # from the resident flavour, staged by the first
    plans = [plan for _, plan in _plans(broker, server, QUERIES[name])]
    assert all(plan.value_columns == VALUE_COLUMNS[name] for plan in plans)
    assert all((plan.lookups[RESIDENT] > 0) == bool(VALUE_COLUMNS[name]) for plan in plans)


def test_exact_aggregates_equal_numpy(table, blocks):
    broker, _, valid = table
    keys, vs, live = [], [], []
    for i, b in enumerate(blocks):
        nulls = b.get("key_nulls", np.zeros(ROWS, bool))
        keys.append(np.where(nulls, 0, b["key"]).astype(np.int64))
        vs.append(b["v"].astype(np.int64))
        live.append((valid if i == UPSERTS_IN else np.ones(ROWS, bool)) & ~nulls)
    key, v, live = np.concatenate(keys), np.concatenate(vs), np.concatenate(live)
    pick = live & (v < 900)
    ((total, largest, least, count),) = _rows(broker.query(QUERIES["sum_max"]))
    assert (total, largest, least, count) == (
        int(key[pick].sum()), int(key[pick].max()), int((key + v)[pick].min()), int(pick.sum()))
    ((distinct,),) = _rows(broker.query(QUERIES["distinctcount"]))
    assert distinct == len(np.unique(key[live & (v < 50)]))
    ((estimate,),) = _rows(broker.query(QUERIES["hll_scalar"]))
    exact = len(np.unique(key[live]))
    assert abs(estimate - exact) <= 4 * 1.04 / np.sqrt(1 << 12) * exact  # HyperLogLog's law, four sigma at the default log2m


@pytest.mark.parametrize("name,gathers,sized", [
    ("hll_grouped", 0, False), ("hll_scalar", 0, False), ("sum_max", 0, False), ("float_values", 0, False),
    ("in_on_it", 1, True),  # the IN's bool table, a bool past the range: the gather's still
    ("groups_on_it", 0, True),  # a group table of the dictionary's compiled length
])
def test_the_lowered_program_reads_the_column_and_gathers_nothing(table, name, gathers, sized):
    broker, server, _ = table
    seg, plan = _plans(broker, server, QUERIES[name])[0]
    cols = _staged(seg, plan)
    text = plan.fn.lower(cols, {k: jax.device_put(v) for k, v in plan.params.items()}).as_text()
    assert text.count('"stablehlo.gather"(') == gathers
    for column in plan.value_columns:
        assert sorted(cols[column]) == ["codes", "dict", "values"]
        if not sized:  # no dictionary operand (unread, so dropped from the program): nothing of the table's bound enters it
            assert f"tensor<{plan.dict_sizes[column]}x" not in text


@pytest.mark.parametrize("name", ["hll_grouped", "sum_grouped", "float_values", "filters_on_it"])
def test_one_kernel_for_segments_whose_dictionaries_differ(table, name):
    broker, server, _ = table
    plans = {seg.name: plan for seg, plan in _plans(broker, server, QUERIES[name])}
    alike = [plans["seg0"], plans["seg3"]]  # the two without nulls or valid_docs: other signatures are other kernels
    assert alike[0].cache_key == alike[1].cache_key and alike[0].fn is alike[1].fn
    assert alike[0].value_columns == alike[1].value_columns
    # nulls in a column it reads, or valid_docs, are another signature and so another kernel; nothing else is
    assert len({id(plan.fn) for plan in plans.values()}) == len({plan.cache_key for plan in plans.values()}) <= 3


@pytest.mark.parametrize("name,contracted,gathered_,resident", [
    ("hll_grouped", 0, 0, 1), ("sum_max", 0, 0, 3), ("float_values", 0, 0, 3),
    ("in_on_it", 0, 1, 1), ("hll_and_sum", 0, 0, 1),
])
def test_the_dispatch_span_counts_the_resident_reads(table, name, contracted, gathered_, resident):
    broker, server, _ = table
    counter = server.metrics.counter("server.residentLookups")
    before = counter.value
    (dispatch,) = _spans(broker.query("SET trace = true; " + QUERIES[name]).stats.trace, "dispatch")
    attrs = dispatch["attrs"]
    assert (attrs["contractedLookups"], attrs["gatheredLookups"], attrs["residentLookups"]) == (
        SEGMENTS * contracted, SEGMENTS * gathered_, SEGMENTS * resident)
    assert counter.value - before == SEGMENTS * resident
    assert _plans(broker, server, QUERIES[name])[0][1].lookups == {
        CONTRACTED: contracted, GATHERED: gathered_, RESIDENT: resident}


def test_a_plan_without_the_flavour_still_gathers_the_same_values(table):
    """The kernel reads what it is handed: a caller that stages without
    `value_columns` (the stacked engine's way, a consuming path) gets the
    dictionary and the gather, and the same sums."""
    broker, server, _ = table
    seg, plan = _plans(broker, server, "SELECT SUM(key), MAX(key) FROM t")[0]
    decoded = plan.fn(_staged(seg, plan), plan.params)
    plain = seg.to_device(columns=plan.needed_columns, packed_codes=True, dict_rows=plan.dict_sizes)
    assert sorted(plain["key"]) == ["codes", "dict"]
    counted = dict(plan.lookups)
    indexed = plan.fn(plain, plan.params)
    assert plan.lookups == counted and counted[RESIDENT] == 2  # the first trace's, the served launch's: the retrace leaves it
    for a, b in zip(jax.tree_util.tree_leaves(decoded), jax.tree_util.tree_leaves(indexed)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_look_ahead_stages_the_flavour_once(blocks):
    """PR 38's case with the flavour: the first query stages segment k+1's
    decoded column behind k; from then on `resident()` is true for what the
    plan will ask, and the server hands the staging thread nothing."""
    planner.plan_cache_clear()
    residency = ResidencyManager(ResourceBudget(1 << 30), name="residency.values")
    broker, server, _ = _cluster(blocks, residency)
    names = [f"seg{i}" for i in range(SEGMENTS)]
    handed, real = [], residency.submit
    residency.submit = lambda fn, *a, **kw: handed.append((tuple(kw["columns"]), kw["value_columns"])) or real(fn, *a, **kw)
    ctx = _ctx(broker, QUERIES["hll_grouped"])
    try:
        first, _ = server.execute(ctx, names)
        assert handed == [(("v", "grp", "key"), {"key"})] * (SEGMENTS - 1)
        del handed[:]
        planning = planner.QueryPlanning(ctx, server.shapes["t"])
        for seg in server.segments["t"].values():
            by_value = planning.value_columns(seg)
            assert seg.resident(server.device, planning.needed_columns(seg), True, by_value)
            cache = seg._device_cache[server.device]
            assert {"key", "key#values"} <= set(cache)  # the decoded column beside its codes
        charged = residency.resident_bytes
        again, _ = server.execute(ctx, names)
        assert handed == [] and residency.resident_bytes == charged
        # a query that reads the codes too finds both where the first left them
        server.execute(_ctx(broker, QUERIES["filters_on_it"]), names)
        assert handed == [] and residency.resident_bytes == charged
    finally:
        residency.shutdown()
        planner.plan_cache_clear()
    for a, b in zip(first, again):
        assert (a is None) == (b is None)  # a group's ONE folded table sits at its first member's place
        if a is not None:
            assert all(np.array_equal(x["hll"], y["hll"]) for x, y in zip(a.partials, b.partials))


# -- which columns the plan SAYS it reads by value, against the ones its kernel's trace reads ------------------
# (planner._value_reads / transform.value_leaves restate what _agg_inputs and eval_expr do: this holds them together)

READERS = {
    "column": "SELECT SUM(a), AVG(b) FROM r",
    "binary": "SELECT SUM(a + b), MIN(a - 3), MAX(a * b) FROM r",
    "divide": "SELECT SUM(a / b) FROM r",
    "unary": "SELECT SUM(ABS(a)), MAX(SQRT(f)) FROM r",
    "cast": "SELECT SUM(CAST(a AS DOUBLE)) FROM r",
    "least_greatest": "SELECT MAX(LEAST(a, b)), MIN(GREATEST(a, 7)) FROM r",
    "device_multi_fn": "SELECT SUM(POWER(a, 2)) FROM r",
    "device_fn": "SELECT MAX(ROUND(f, 1)) FROM r",
    "dict_fn": "SELECT SUM(LENGTH(s)) FROM r",
    "array_length": "SELECT SUM(ARRAYLENGTH(mv)) FROM r",
    "case": "SELECT SUM(CASE WHEN a > 5 THEN b ELSE 0 END) FROM r",
    "counts": "SELECT COUNT(a), COUNT(*) FROM r",
    "wide_dictionary": "SELECT SUM(ts), MAX(ts) FROM r",
    "raw_metric": "SELECT SUM(v), MIN(v + a) FROM r",
    "distinctcount": "SELECT DISTINCTCOUNT(a) FROM r",
    "distinctcount_by_range": "SET __dictfp__a = 'MIXED'; SELECT DISTINCTCOUNT(a) FROM r",
    "hll": "SELECT DISTINCTCOUNTHLL(a), DISTINCTCOUNTHLL(s) FROM r",
    "percentile": "SELECT PERCENTILE(a, 50), PERCENTILETDIGEST(f, 90) FROM r",
    "mode": "SELECT MODE(a) FROM r",
    "two_expressions": "SELECT COVAR_POP(a, b) FROM r",
    "with_time": "SELECT LASTWITHTIME(a, ts, 'INT') FROM r",
    "multi_value": "SELECT SUMMV(mv), COUNTMV(mv) FROM r",
    "code_readers": "SELECT a, SUM(b) FROM r WHERE f > 3 AND a IN (1, 2, 3) GROUP BY a LIMIT 10",
    "filter_clause": "SELECT SUM(a) FILTER (WHERE b > 3), COUNT(*) FROM r",
}
# eval_expr cases value_leaves does not descend into: the plan asks for less than the trace reads, and gathers
ASKS_LESS = {"case"}


@pytest.fixture(scope="module")
def readers_segment():
    rows = 2048
    rng = np.random.default_rng(4949)
    schema = Schema("r", [
        FieldSpec("a", DataType.INT), FieldSpec("b", DataType.INT), FieldSpec("f", DataType.FLOAT),
        FieldSpec("ts", DataType.LONG), FieldSpec("s", DataType.STRING),
        FieldSpec("mv", DataType.INT, single_value=False), FieldSpec("v", DataType.INT, role=FieldRole.METRIC),
    ])
    return build_segment(schema, {
        "a": rng.integers(1, 90, rows).astype(np.int32), "b": rng.integers(1, 70, rows).astype(np.int32),
        "f": (rng.integers(0, 500, rows) / 4).astype(np.float32),
        "ts": rng.integers(1 << 40, (1 << 40) + 300, rows).astype(np.int64),
        "s": np.array([f"k{i % 37}" for i in rng.integers(0, 1000, rows)], dtype=object),
        "mv": [list(rng.integers(0, 20, int(n))) for n in rng.integers(1, 4, rows)],
        "v": rng.integers(0, 1000, rows).astype(np.int32),
    }, "r0")


@pytest.mark.parametrize("name", list(READERS))
def test_the_plan_asks_for_the_columns_its_trace_reads_by_value(readers_segment, monkeypatch, name):
    """With the rule held to RESIDENT for every 32-bit single-value
    dictionary (so a toy table's count), `plan.value_columns` is the set of
    such columns `transform.column_values` is called for while the plan's
    kernel is traced: never more (nothing is staged decoded for no reader),
    and all of them outside ASKS_LESS."""
    from pinot_tpu.query import transform

    seg = readers_segment
    read, real = [], transform.column_values
    monkeypatch.setattr(transform, "column_values", lambda n, *a, **kw: read.append(n) or real(n, *a, **kw))
    monkeypatch.setattr(
        planner, "lookup_form",
        lambda size, dtype, ndim=1: RESIDENT if ndim == 1 and np.dtype(dtype).kind in "if" and np.dtype(dtype).itemsize == 4 else GATHERED,
    )
    planner.plan_cache_clear()
    try:
        plan = planner.plan_segment(parse_query(READERS[name]), seg)
        cols = seg.to_device(
            columns=plan.needed_columns, packed_codes=True, dict_rows=plan.dict_sizes, value_columns=plan.value_columns)
        jax.eval_shape(plan.fn, cols, plan.params)
    finally:
        planner.plan_cache_clear()

    def decodable(c):
        return (c.has_dictionary and not c.is_multi_value and not c.data_type.is_string_like
                and c.dictionary.device_values().dtype.itemsize == 4)

    traced = {n for n in read if decodable(seg.column(n))}
    assert plan.value_columns <= traced, (plan.value_columns, read)
    assert (plan.value_columns == traced) == (name not in ASKS_LESS), (plan.value_columns, read)
    assert plan.lookups[RESIDENT] == sum(n in plan.value_columns for n in read)
