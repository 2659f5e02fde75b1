"""Several clients at once on the served path (Broker.query -> _scatter ->
ServerInstance.execute -> executor.QueryLaunches -> planner.grouped_plan).

Every closed-loop cell of the benchmark is four clients on one broker, and a
query's launches carry a device-resident table from call to call
(QueryLaunches._combining).  These tests hold what that needs, at toy sizes
and with no assertion on a wall clock: eight threads sending eight literal
variants of one template get each the answer its own SQL gets alone and a
numpy reference's, for every kind of plan the served path has; a storm of
literal variants compiles nothing the template's first query did not; mixed
shapes answer each by its own plan; a query killed or timed out between its
group calls abandons its pending calls and leaves its concurrent siblings
exact; and each concurrent answer's stats are its own.

(Cross-query coalescing is not offered: a query's segments are coalesced
into group calls inside QueryLaunches, and queries overlap on the device
through async dispatch.)
"""
import itertools
import threading

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.admission import QueryKilledError
from pinot_tpu.query import executor, planner
from pinot_tpu.query.safety import Deadline, QueryTimeoutError
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
from pinot_tpu.utils.metrics import METRICS

CLIENTS = 8
ROWS = 1000
YEARS = np.arange(1990, 2000, dtype=np.int32)
CITIES = ["ams", "ber", "cph", "dub", "edi"]
POOL = CITIES + ["fra", "gva", "hel", "ist"]  # table d: six of these a segment, another six each
SHOPS = ITEMS = 100  # GROUP BY shop, item: 10,000 slots, past the one-hot kernel's 8,192
CUSTOMERS = 500
WAIT_S = 60.0  # a gate's patience: a missed gate fails its assert, no test times anything


def _schema(name):
    return Schema(
        name,
        [
            FieldSpec("year", DataType.INT),
            FieldSpec("qty", DataType.INT),
            FieldSpec("shop", DataType.INT),
            FieldSpec("item", DataType.INT),
            FieldSpec("cust", DataType.INT),
            FieldSpec("city", DataType.STRING),
            FieldSpec("rev", DataType.LONG, role=FieldRole.METRIC),
        ],
    )


def _block(i, cities=CITIES, all_customers=True):
    """Segment i's rows.  Every value of every dimension is in every block, so
    a table's segments share their dictionaries (one key space, one kernel),
    but for what the caller varies: `cities`, and with `all_customers` off
    whichever of the 500 the draw happened to take."""
    rng = np.random.default_rng(700 + i)
    b = {
        "year": rng.choice(YEARS, ROWS),
        "qty": rng.integers(1, 51, ROWS).astype(np.int32),
        "shop": rng.integers(0, SHOPS, ROWS).astype(np.int32),
        "item": rng.integers(0, ITEMS, ROWS).astype(np.int32),
        "cust": rng.integers(0, CUSTOMERS, ROWS).astype(np.int32),
        "city": rng.choice(cities, ROWS).astype(object),
        "rev": rng.integers(1, 10**7, ROWS),
    }
    b["year"][: len(YEARS)] = YEARS
    b["qty"][:50] = np.arange(1, 51)
    b["shop"][:SHOPS] = np.arange(SHOPS)
    b["item"][:ITEMS] = np.arange(ITEMS)[::-1]
    b["city"][: len(cities)] = cities
    if all_customers:
        b["cust"][:CUSTOMERS] = np.arange(CUSTOMERS)
    return b


def _concat(blocks):
    return {k: np.concatenate([b[k] for b in blocks]) for k in blocks[0]}


class _Table:
    """A table's blocks, what numpy reads of them (`rows`, and `valid`: the
    upsert table's validDocIds) and its segments."""

    def __init__(self, name, blocks, valid=None, table_config=None):
        self.name = name
        self.schema = _schema(name)
        self.config = table_config or TableConfig(name)
        self.rows = _concat(blocks)
        self.valid = np.ones(len(self.rows["rev"]), bool) if valid is None else np.concatenate(valid)
        self.segments = []
        for i, b in enumerate(blocks):
            seg = build_segment(self.schema, b, f"{name}{i}", table_config=table_config)
            if valid is not None:
                seg.valid_docs = valid[i].copy()
            self.segments.append(seg)


@pytest.fixture(scope="module")
def tables():
    t_blocks = [_block(i) for i in range(12)]  # 8 + 4: two group calls a query, the second folds into the first's table
    star = TableConfig(
        "s",
        indexing=IndexingConfig(star_tree_index_configs=[{
            "dimensionsSplitOrder": ["year", "city"],
            "functionColumnPairs": ["SUM__rev", "COUNT__*"],
            "maxLeafRecords": 10000,
        }]),
    )
    out = {
        "t": _Table("t", t_blocks),
        # validDocIds: a mask of its own a segment
        "u": _Table("u", t_blocks[:5], valid=[np.random.default_rng(40 + i).random(ROWS) < 0.6 for i in range(5)]),
        "s": _Table("s", t_blocks[:5], table_config=star),
        # dictionaries of its own a segment: six of nine cities, whichever customers were drawn
        "d": _Table("d", [
            _block(20 + i, [POOL[(i + k) % len(POOL)] for k in range(6)], all_customers=False) for i in range(5)
        ]),
    }
    assert len({seg.column("city").dictionary.fingerprint() for seg in out["t"].segments}) == 1
    assert len({seg.column("city").dictionary.fingerprint() for seg in out["d"].segments}) == 5
    assert len({seg.column("cust").dictionary.fingerprint() for seg in out["d"].segments}) == 5
    return out


@pytest.fixture(scope="module")
def cluster(tables):
    """(broker, server): one server holding every table, behind one broker."""
    coord = Coordinator(replication=1)
    server = ServerInstance("server0")
    coord.register_server(server)
    for table in tables.values():
        coord.add_table(table.schema, table.config)
        for seg in table.segments:
            coord.add_segment(table.name, seg)
    return Broker(coord), server


# ---------------------------------------------------------------------------
# the kinds: a template, eight literals, the same in numpy
# ---------------------------------------------------------------------------
def _grouped(r, mask, cols, aggs):
    """r's rows under `mask` grouped by `cols`: a row [*key, *aggregates] a group, keys in order."""
    vals = {c: r[c][mask] for c in ("rev", "cust")}
    index = {}
    for at, key in enumerate(zip(*(r[c][mask].tolist() for c in cols))):
        index.setdefault(key, []).append(at)
    return [list(key) + [fn(vals, np.asarray(index[key])) for fn in aggs] for key in sorted(index)]


_COUNT = lambda vals, at: len(at)
_SUM = lambda vals, at: int(vals["rev"][at].sum())
_MIN = lambda vals, at: int(vals["rev"][at].min())
_MAX = lambda vals, at: int(vals["rev"][at].max())
_DISTINCT = lambda vals, at: len(set(vals["cust"][at].tolist()))

CITY_PAIRS = list(itertools.combinations(CITIES, 2))[:CLIENTS]
QTYS = [12, 17, 23, 28, 31, 36, 42, 47]


class _Kind:
    def __init__(self, table, sql, literals, reference, plan_kind, approx=None, engaged=None, trace_odd=False):
        self.table, self.sql, self.literals, self.reference = table, sql, literals, reference
        self.plan_kind = plan_kind  # of the plan the segments' own scan takes
        self.approx = approx  # column of the answer held to a relative error, not equality (a sketch)
        self.engaged = engaged  # the server's counter that says the kind's mechanism ran
        self.trace_odd = trace_odd  # every other client asks for a trace

    def render(self, i):
        sql = self.sql.format(self.literals[i])
        return "SET trace = true; " + sql if self.trace_odd and i % 2 else sql


KINDS = {
    "aggregation": _Kind(
        "t", "SELECT COUNT(*), SUM(rev) FROM t WHERE year = 1994 AND qty < {}", QTYS,
        lambda t, q: [[int(m.sum()), int(t.rows["rev"][m].sum())] for m in [(t.rows["year"] == 1994) & (t.rows["qty"] < q)]],
        "aggregation",
    ),
    "groupby_dense": _Kind(  # one key space: the combining group program, two calls folding into one table
        "t", "SELECT city, COUNT(*), SUM(rev) FROM t WHERE qty < {} GROUP BY city ORDER BY city LIMIT 100", QTYS,
        lambda t, q: _grouped(t.rows, t.rows["qty"] < q, ["city"], [_COUNT, _SUM]),
        "groupby_dense", engaged="server.combinedSegments",
    ),
    "groupby_dense_traced": _Kind(  # traced beside untraced: one shape (query/shape.py leaves `trace` out)
        "t", "SELECT year, COUNT(*), SUM(rev) FROM t WHERE qty < {} GROUP BY year ORDER BY year LIMIT 100", QTYS,
        lambda t, q: _grouped(t.rows, t.rows["qty"] < q, ["year"], [_COUNT, _SUM]),
        "groupby_dense", engaged="server.combinedSegments", trace_odd=True,
    ),
    "groupby_wide": _Kind(  # 10,000 slots: the wide group table
        "t", "SELECT shop, item, COUNT(*), SUM(rev) FROM t WHERE qty < {} GROUP BY shop, item ORDER BY shop, item LIMIT 20000",
        QTYS, lambda t, q: _grouped(t.rows, t.rows["qty"] < q, ["shop", "item"], [_COUNT, _SUM]),
        "groupby_dense", engaged="server.combinedSegments",
    ),
    "groupby_minmax": _Kind(
        "t", "SELECT year, MIN(rev), MAX(rev) FROM t WHERE qty = {} GROUP BY year ORDER BY year LIMIT 100", QTYS,
        lambda t, q: _grouped(t.rows, t.rows["qty"] == q, ["year"], [_MIN, _MAX]),
        "groupby_dense",
    ),
    "groupby_sparse": _Kind(  # past maxDenseGroups: sort + slot tables, a result a segment
        "t", "SET maxDenseGroups = 16; SELECT city, year, COUNT(*), SUM(rev) FROM t WHERE qty < {} "
        "GROUP BY city, year ORDER BY city, year LIMIT 1000", QTYS,
        lambda t, q: _grouped(t.rows, t.rows["qty"] < q, ["city", "year"], [_COUNT, _SUM]),
        "groupby_sparse", engaged="server.sparseGroups",
    ),
    "selection": _Kind(
        "t", "SELECT rev, qty, city FROM t WHERE year = 1995 AND qty = {} ORDER BY rev LIMIT 1000", QTYS,
        lambda t, q: sorted(
            [int(v), int(q), c] for v, c, m in zip(t.rows["rev"], t.rows["city"], (t.rows["year"] == 1995) & (t.rows["qty"] == q)) if m
        ),
        "selection",
    ),
    "upsert": _Kind(  # `__valid__`: a bool[rows] parameter a member
        "u", "SELECT city, COUNT(*), SUM(rev) FROM u WHERE qty < {} GROUP BY city ORDER BY city LIMIT 100", QTYS,
        lambda t, q: _grouped(t.rows, t.valid & (t.rows["qty"] < q), ["city"], [_COUNT, _SUM]),
        "groupby_dense", engaged="server.combinedSegments",
    ),
    "in_table": _Kind(  # an IN list on a dictionary column: a bool[cardinality] table parameter
        "t", "SELECT year, COUNT(*), SUM(rev) FROM t WHERE city IN ({}) GROUP BY year ORDER BY year LIMIT 100",
        [", ".join(f"'{c}'" for c in pair) for pair in CITY_PAIRS],
        lambda t, lst: _grouped(t.rows, np.isin(t.rows["city"], [c.strip(" '") for c in lst.split(",")]), ["year"], [_COUNT, _SUM]),
        "groupby_dense", engaged="server.combinedSegments",
    ),
    "startree": _Kind(  # a star-tree level answers for the segment
        "s", "SELECT city, SUM(rev), COUNT(*) FROM s WHERE year = {} GROUP BY city ORDER BY city LIMIT 100", YEARS[:CLIENTS].tolist(),
        lambda t, y: _grouped(t.rows, t.rows["year"] == y, ["city"], [_SUM, _COUNT]),
        "groupby_dense", engaged="server.starTreeSegments",
    ),
    "hll": _Kind(  # [groups, m] register tables, folded on the device
        "t", "SELECT year, DISTINCTCOUNTHLL(cust), COUNT(*) FROM t WHERE qty < {} GROUP BY year ORDER BY year LIMIT 100", QTYS,
        lambda t, q: _grouped(t.rows, t.rows["qty"] < q, ["year"], [_DISTINCT, _COUNT]),
        "groupby_dense", approx=1, engaged="server.combinedSegments",
    ),
    "table_shape": _Kind(  # dictionaries of its own a segment: one kernel, a table a segment, merged by value
        "d", "SELECT city, COUNT(*), SUM(rev) FROM d WHERE qty < {} GROUP BY city ORDER BY city LIMIT 100", QTYS,
        lambda t, q: _grouped(t.rows, t.rows["qty"] < q, ["city"], [_COUNT, _SUM]),
        "groupby_dense", engaged="server.groupedSegments",
    ),
    "hll_table_shape": _Kind(  # the sketch over a column whose dictionaries differ: hashed by value on the device
        "d", "SELECT year, DISTINCTCOUNTHLL(cust), COUNT(*) FROM d WHERE qty < {} GROUP BY year ORDER BY year LIMIT 100", QTYS,
        lambda t, q: _grouped(t.rows, t.rows["qty"] < q, ["year"], [_DISTINCT, _COUNT]),
        "groupby_dense", approx=1, engaged="server.combinedSegments",
    ),
}


def _plain(rows):
    """An answer's rows as plain Python values (numpy scalars and integral floats as ints)."""
    out = []
    for row in rows:
        plain = []
        for v in row:
            if isinstance(v, (np.generic,)):
                v = v.item()
            if isinstance(v, float) and v == int(v):
                v = int(v)
            plain.append(v)
        out.append(plain)
    return out


def _meets(kind, rows, want):
    """`rows` against the numpy reference: equal, but for a sketch's column, within 5 % (HyperLogLog at
    log2m 12 over at most 500 values is in its linear-counting range)."""
    rows = _plain(rows)
    if kind.approx is None:
        assert rows == want
        return
    assert len(rows) == len(want)
    for got, ref in zip(rows, want):
        assert [v for k, v in enumerate(got) if k != kind.approx] == [v for k, v in enumerate(ref) if k != kind.approx]
        assert abs(got[kind.approx] - ref[kind.approx]) <= 0.05 * ref[kind.approx], (got, ref)


def _clients(n, work):
    """`work(i)` from n threads released together: what each returned, or
    the exception it raised (an AssertionError too), in order."""
    outs = [None] * n
    start = threading.Barrier(n)

    def client(i):
        try:
            start.wait(WAIT_S)
            outs[i] = work(i)
        except Exception as e:  # noqa: BLE001 -- the caller compares it
            outs[i] = e

    threads = [threading.Thread(target=client, args=(i,), name=f"client-{i}", daemon=True) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(4 * WAIT_S)
    assert not [t.name for t in threads if t.is_alive()], "a client did not come back"
    return outs


def _storm(broker, sqls):
    """Every SQL from a thread of its own: the answers (or the exceptions), in the order given."""
    return _clients(len(sqls), lambda i: broker.query(sqls[i]))


def _counters():
    return dict(METRICS.snapshot()["counters"])


# ---------------------------------------------------------------------------
# (1) concurrent == alone == numpy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", list(KINDS))
def test_concurrent_same_shape_equals_sequential(name, tables, cluster):
    kind, (broker, server) = KINDS[name], cluster
    table = tables[kind.table]
    sqls = [kind.render(i) for i in range(CLIENTS)]
    wants = [kind.reference(table, lit) for lit in kind.literals]
    assert len({str(w) for w in wants}) == CLIENTS  # the literals tell the answers apart
    own = planner.plan_segment(parse_query(sqls[0]), table.segments[0])
    assert own.kind == kind.plan_kind
    if name == "groupby_wide":
        assert own.num_groups == SHOPS * ITEMS > 8192

    alone = [broker.query(sql) for sql in sqls]  # (the shape's first query compiles: a cold storm is section 6)
    before = server.metrics.snapshot()["counters"].get(kind.engaged, 0)
    outs = _storm(broker, sqls)
    for out, want in zip(outs, wants):
        assert not isinstance(out, Exception), out
        _meets(kind, out.rows, want)
        assert not out.stats.partial_result and not out.stats.exceptions
    if kind.engaged is not None:  # the mechanism the kind is named for ran, in every client's query
        moved = server.metrics.snapshot()["counters"].get(kind.engaged, 0) - before
        assert moved >= CLIENTS, (kind.engaged, moved)
    if kind.trace_odd:
        assert [out.stats.trace is not None for out in outs] == [bool(i % 2) for i in range(CLIENTS)]
    assert [_plain(a.rows) for a in alone] == [_plain(o.rows) for o in outs]  # a sketch's estimate too, bit for bit


# ---------------------------------------------------------------------------
# (2) a storm of literal variants compiles what ONE query of the kind compiles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", list(KINDS))
def test_concurrent_literal_variants_compile_each_program_once(name, tables, cluster):
    kind, (broker, _) = KINDS[name], cluster
    table = tables[kind.table]
    planner.plan_cache_clear()
    first = broker.query(kind.render(0))  # ONE query of the kind, cold: its kernel, its group programs
    _meets(kind, first.rows, kind.reference(table, kind.literals[0]))
    one = _counters()
    assert one.get("compile.sse.compiles", 0) >= 1 and first.stats.compile_ms > 0

    outs = _storm(broker, [kind.render(i) for i in range(CLIENTS)])
    after = _counters()
    for out, lit in zip(outs, kind.literals):
        assert not isinstance(out, Exception), out
        _meets(kind, out.rows, kind.reference(table, lit))
        assert out.stats.compile_ms == 0.0  # no call of the storm was a first launch
    for key in ("compile.sse.compiles", "compile.group.programs"):
        assert after.get(key, 0) == one.get(key, 0), key
    assert after.get("compile.sse.rebuilds", 0) == 0  # every hit bound its parameters by the entry's recipe
    assert after["compile.sse.binds"] - one["compile.sse.binds"] >= CLIENTS


# ---------------------------------------------------------------------------
# (3) mixed shapes, interleaved
# ---------------------------------------------------------------------------
def test_mixed_shape_storm_answers_each_by_its_own_plan(tables, cluster):
    """Eight clients, each walking all the kinds from a different start with
    a literal of its own: at any moment the server plans, launches and
    collects several shapes at once."""
    broker, _ = cluster
    names = list(KINDS)

    def client(i):
        failures = []
        for step in range(len(names)):
            name = names[(i + step) % len(names)]
            kind = KINDS[name]
            try:
                out = broker.query(kind.render(i))
                _meets(kind, out.rows, kind.reference(tables[kind.table], kind.literals[i]))
            except Exception as e:  # noqa: BLE001 -- AssertionError included: reported below
                failures.append((i, name, repr(e)[:400]))
        return failures

    assert _clients(CLIENTS, client) == [[]] * CLIENTS
    assert _counters()["broker.queries"] == CLIENTS * len(names)


# ---------------------------------------------------------------------------
# (4) one query dies between its group calls; its siblings do not notice
# ---------------------------------------------------------------------------
class _ExpiresWhen(Deadline):
    """A deadline that has expired once `evt` is set; the broker hands it to
    the server as it is (`bounded` of an unbounded deadline is itself)."""

    __slots__ = ("evt",)

    def __init__(self, evt):
        super().__init__(1e9)
        self.evt = evt

    def expired(self):
        return self.evt.is_set()

    def remaining_ms(self):
        return None


@pytest.mark.parametrize("how", ["killed", "expired"])
def test_ended_query_leaves_concurrent_siblings_exact(how, tables, cluster, monkeypatch):
    """Four combining group-bys of one shape at once over t's twelve segments
    (8 + 4: two calls a query).  Each client stops after its FIRST call until
    all four have made theirs, so four device-resident tables are pending
    side by side; then the victim is killed by the watchdog (or its deadline
    expires) before its second call.  Its one pending call is abandoned and
    it fails as a kill (a timeout) does; the three siblings fold their second
    call into their own table and answer exactly; the victim's SQL run again
    answers exactly too (the identity tables a first combining call starts
    from are shared by a device's queries, and nobody folded into them)."""
    kind, (broker, _) = KINDS["groupby_dense"], cluster
    table = tables["t"]
    sqls = [kind.render(i) for i in range(4)]
    wants = [kind.reference(table, lit) for lit in kind.literals[:4]]
    for sql, want in zip(sqls, wants):  # warm, and right alone
        _meets(kind, broker.query(sql).rows, want)
    victim = parse_query(sqls[2]).filter.fingerprint()
    cancelled0 = _counters().get("server.launchesCancelled", 0)

    first_calls = threading.Semaphore(0)
    all_called, ended = threading.Event(), threading.Event()
    victim_qids, calls_of = [], {}
    gov = broker.governor
    real_admit, real_launch = gov.admit, executor._launch_group

    def admit(qid, ctx, cost, deadline=None):
        if ctx.filter.fingerprint() == victim:
            victim_qids.append(qid)
        return real_admit(qid, ctx, cost, deadline)

    def launch_group(ctx, members, *a, **kw):
        state = real_launch(ctx, members, *a, **kw)
        fp = ctx.filter.fingerprint()
        calls_of[fp] = calls_of.get(fp, 0) + 1
        if calls_of[fp] == 1:
            first_calls.release()
            assert all_called.wait(WAIT_S)
            if fp == victim:
                if how == "killed":
                    assert gov.watchdog.kill(victim_qids[0], "killed by test")
                ended.set()
            else:
                assert ended.wait(WAIT_S)
        return state

    monkeypatch.setattr(gov, "admit", admit)
    monkeypatch.setattr(executor, "_launch_group", launch_group)
    if how == "expired":
        real_from_ctx = Deadline.from_ctx
        monkeypatch.setattr(
            Deadline, "from_ctx",
            staticmethod(lambda ctx: _ExpiresWhen(ended) if ctx.filter.fingerprint() == victim else real_from_ctx(ctx)),
        )

    def gate():
        for _ in range(4):
            assert first_calls.acquire(timeout=WAIT_S)
        all_called.set()

    gatekeeper = threading.Thread(target=gate, daemon=True)
    gatekeeper.start()
    outs = _storm(broker, sqls)
    gatekeeper.join(WAIT_S)
    monkeypatch.undo()

    assert all_called.is_set() and ended.is_set()
    if how == "killed":
        assert isinstance(outs[2], QueryKilledError) and "1 pending launch" in str(outs[2]), outs[2]
    else:
        assert isinstance(outs[2], QueryTimeoutError), outs[2]
    assert calls_of.pop(victim) == 1  # it never made its second call
    assert set(calls_of.values()) == {2}  # the siblings made both of theirs
    assert _counters().get("server.launchesCancelled", 0) - cancelled0 == 1
    for i in (0, 1, 3):
        assert not isinstance(outs[i], Exception), outs[i]
        _meets(kind, outs[i].rows, wants[i])
        assert outs[i].stats.num_docs_scanned == len(table.rows["rev"])
    _meets(kind, broker.query(sqls[2]).rows, wants[2])


# ---------------------------------------------------------------------------
# (5) stats
# ---------------------------------------------------------------------------
STATS_CASES = {
    # the literal moves what the server prunes: a city a segment's dictionary lacks (table d: six of nine a segment)
    "pruned_by_literal": ("d", "SELECT COUNT(*), SUM(rev) FROM d WHERE city = '{}'", POOL[:CLIENTS]),
    "groupby_dense": ("t", KINDS["groupby_dense"].sql, QTYS),
    "startree": ("s", KINDS["startree"].sql, KINDS["startree"].literals),  # docs scanned: the level's rows
    "selection": ("t", KINDS["selection"].sql, QTYS),
}


@pytest.mark.parametrize("name", list(STATS_CASES))
def test_stats_of_concurrent_queries_are_each_ones_own(name, tables, cluster):
    broker, _ = cluster
    table, sql, literals = STATS_CASES[name]
    sqls = [sql.format(lit) for lit in literals]
    alone = [broker.query(s) for s in sqls]
    outs = _storm(broker, sqls)

    def stats(out):
        s = out.stats
        return (
            s.num_docs_scanned, s.num_segments_processed, s.num_segments_queried, s.num_segments_pruned,
            s.total_docs, s.num_servers_queried, s.num_servers_responded,
        )

    for out, ref in zip(outs, alone):
        assert not isinstance(out, Exception), out
        assert stats(out) == stats(ref) and _plain(out.rows) == _plain(ref.rows)
        assert out.stats.num_segments_queried == len(tables[table].segments)
    if name == "pruned_by_literal":
        holds = [sum(lit in seg.column("city").dictionary.values for seg in tables["d"].segments) for lit in literals]
        assert [o.stats.num_segments_processed for o in outs] == holds and len(set(holds)) > 1
        assert [o.stats.num_docs_scanned for o in outs] == [h * ROWS for h in holds]
    if name == "startree":
        assert all(o.stats.num_docs_scanned < len(tables["s"].rows["rev"]) for o in outs)


# ---------------------------------------------------------------------------
# (6) a cold shape met by every client at once
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["groupby_dense", "aggregation", "groupby_sparse"])
def test_a_cold_shape_met_by_every_client_at_once_answers_exactly(name, tables, cluster):
    """No warm-up: the plan cache is empty and eight clients miss it together
    (the entry is not single-flight: several may compile, one entry stays,
    and a group program built twice keeps the first: planner.grouped_plan)."""
    kind, (broker, _) = KINDS[name], cluster
    planner.plan_cache_clear()
    outs = _storm(broker, [kind.render(i) for i in range(CLIENTS)])
    for out, lit in zip(outs, kind.literals):
        assert not isinstance(out, Exception), out
        _meets(kind, out.rows, kind.reference(tables[kind.table], lit))
    assert _counters().get("compile.sse.compiles", 0) >= 1
    again = _storm(broker, [kind.render(i) for i in range(CLIENTS)])
    assert [_plain(a.rows) for a in again] == [_plain(o.rows) for o in outs]
    assert all(a.stats.compile_ms == 0.0 for a in again)
