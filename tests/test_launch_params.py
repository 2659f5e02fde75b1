"""A launch's parameters ride the jitted call (query/executor.py).

A warm launch makes one trip into the runtime that carries data: the call
of the plan's jitted kernel.  The plan's parameters (numpy scalars and
small tables, the FilterCompiler's own) are packed into one host buffer per
dtype (planner.pack_params) and are the call's arguments as they are, so a
launch makes no `jax.device_put` and holds no device array to drop.  These
tests hold that, the answers (exact integers, against numpy), the one
argument form a compiled plan sees, and the placement on the server's
device, which committed columns used to share with device-put parameters.
"""
import jax
import numpy as np
import pytest

from pinot_tpu.cluster.server import ServerInstance
from pinot_tpu.query import executor, planner
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
from pinot_tpu.utils.metrics import METRICS, Trace

N = 6000
CITIES = ["ams", "ber", "cph", "dub", "edi"]


def _schema():
    return Schema(
        "t",
        [
            FieldSpec("year", DataType.INT),
            FieldSpec("disc", DataType.INT),
            FieldSpec("qty", DataType.INT),
            FieldSpec("city", DataType.STRING),
            FieldSpec("rev", DataType.LONG, role=FieldRole.METRIC),
        ],
    )


def _data(seed=11):
    rng = np.random.default_rng(seed)
    return {
        "year": rng.integers(1992, 1999, N).astype(np.int32),
        "disc": rng.integers(0, 11, N).astype(np.int32),
        "qty": rng.integers(1, 51, N).astype(np.int32),
        "city": rng.choice(CITIES, N).astype(object),
        "rev": rng.integers(1, 10**7, N),
    }


DATA = _data()
VALID = np.random.default_rng(5).random(N) < 0.7  # the upsert segment's validDocIds


def _segment(upsert: bool, name="s0", rows=slice(None)):
    seg = build_segment(_schema(), {k: v[rows] for k, v in DATA.items()}, name)
    if upsert:
        seg.valid_docs = VALID[rows].copy()
    return seg


def _halves(upsert: bool):
    """DATA's rows as two segments: one plan (every column's few values are in
    both halves), so a query's two members ride ONE group call."""
    return [_segment(upsert, "h0", slice(0, N // 2)), _segment(upsert, "h1", slice(N // 2, N))]


def _launch_halves(ctx, segs, trace=None, device=None):
    """`ctx` over `segs` as a server launches it (QueryLaunches), dispatched
    and not collected."""
    launches = executor.QueryLaunches(ctx, device=device, trace=trace)
    for seg in segs:
        launches.add(seg)
    launches.flush()
    return launches


def _merged(launches):
    """The members' answers as one: a combined group ships ONE table (None at
    the other members' places), anything else a result a member."""
    answers = [_answer(res) for res, _ in launches.collect() if res is not None]
    if isinstance(answers[0], tuple):
        return tuple(sum(x) for x in zip(*answers))
    out = {}
    for a in answers:
        for k, (c, t) in a.items():
            c0, t0 = out.get(k, (0, 0))
            out[k] = (c0 + c, t0 + t)
    return out


def _grouped(mask, col):
    keys = np.unique(DATA[col][mask])
    return {k: (int((mask & (DATA[col] == k)).sum()), int(DATA["rev"][mask & (DATA[col] == k)].sum())) for k in keys}


# name -> (kind, upsert segment?, SQL over two sets of literals, the same in numpy, GROUP BY column)
CASES = {
    "aggregation": (  # Q1-shaped: three range predicates, six scalar parameters
        "aggregation", False,
        "SELECT COUNT(*), SUM(rev) FROM t WHERE year = {0} AND disc BETWEEN {1} AND {2} AND qty < {3}",
        [(1993, 1, 3, 25), (1996, 4, 6, 35)],
        lambda y, lo, hi, q: (DATA["year"] == y) & (DATA["disc"] >= lo) & (DATA["disc"] <= hi) & (DATA["qty"] < q),
        None,
    ),
    "groupby_dense": (
        "groupby_dense", False,
        "SELECT year, COUNT(*), SUM(rev) FROM t WHERE qty BETWEEN {0} AND {1} GROUP BY year",
        [(10, 30), (5, 45)],
        lambda lo, hi: (DATA["qty"] >= lo) & (DATA["qty"] <= hi),
        "year",
    ),
    "in_table": (  # an IN list on a dictionary column: a bool[cardinality] table
        "groupby_dense", False,
        "SELECT city, COUNT(*), SUM(rev) FROM t WHERE city IN ({0}) GROUP BY city",
        [("'ams', 'cph'",), ("'ber', 'dub', 'edi'",)],
        lambda lst: np.isin(DATA["city"], [c.strip(" '") for c in lst.split(",")]),
        "city",
    ),
    "upsert": (  # `__valid__`, a bool[num_docs] parameter, beside two scalars
        "aggregation", True,
        "SELECT COUNT(*), SUM(rev) FROM t WHERE year >= {0} AND disc < {1}",
        [(1994, 7), (1997, 3)],
        lambda y, d: VALID & (DATA["year"] >= y) & (DATA["disc"] < d),
        None,
    ),
}


def _case(name):
    kind, upsert, sql, literals, mask_of, group_col = CASES[name]
    ctxs = [parse_query(sql.format(*lit)) for lit in literals]
    wants = []
    for lit in literals:
        m = mask_of(*lit)
        wants.append(_grouped(m, group_col) if group_col else (int(m.sum()), int(DATA["rev"][m].sum())))
    return kind, _segment(upsert), ctxs, wants


def _answer(res):
    """A segment result as plain integers: (count, sum), or {key: (count, sum)}."""
    if hasattr(res, "keys"):
        count, total = res.partials
        return {
            k: (int(c), int(s)) for k, c, s in zip(res.keys[0], count["count"], total["sum"])
        }
    count, total = res.partials
    return int(count["count"]), int(total["sum"])


def _spans(node, out=None):
    out = {} if out is None else out
    out.setdefault(node["name"], []).append(node)
    for c in node.get("children", []):
        _spans(c, out)
    return out


@pytest.fixture
def device_puts(monkeypatch):
    """Counts every `jax.device_put` (the executor's and the segment's)."""
    calls = []
    real = jax.device_put

    def counting(x, *a, **kw):
        calls.append(type(x).__name__)
        return real(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", counting)
    return calls


# the host buffers each case's call carries (name -> dtype, shape), and the parameters packed in them
CARRIED = {
    "aggregation": ({"int32": ("int32", (6,))}, 6),
    "groupby_dense": ({"int32": ("int32", (2,))}, 2),
    "in_table": ({"bool": ("bool", (len(CITIES),))}, 1),
    "upsert": ({"int32": ("int32", (4,)), "__valid__": ("bool", (N,))}, 5),
}


def _check_carried(name, plan, rows=N):
    """The call's arguments: one host numpy buffer per dtype, the valid mask
    (the segment's `rows`) beside them."""
    carried, n_params = CARRIED[name]
    carried = {k: (d, (rows,) if k == "__valid__" else sh) for k, (d, sh) in carried.items()}
    assert {k: (v.dtype.name, v.shape) for k, v in plan.params.items()} == carried
    assert all(type(v) is np.ndarray for v in plan.params.values())
    assert len(plan.param_layout) == n_params
    raw = planner.unpack_params(plan.params, plan.param_layout)  # what the kernel reads
    assert sorted(raw) == [k for k, _, _ in plan.param_layout]
    assert all(raw[k].dtype.name == d and raw[k].shape == sh for k, d, sh in plan.param_layout)


def _check_trace(trace, plan):
    spans = _spans(trace.finish())
    (ship,), (enqueue,) = spans["launch_ship"], spans["launch_enqueue"]
    assert ship["attrs"]["paramArrays"] == len(plan.params) <= 3  # host buffers; no device array made
    assert ship["attrs"]["params"] == len(plan.param_layout)
    assert [c["name"] for c in enqueue["children"]] == ["launch_release"]  # kept, by name and nesting
    assert "attrs" not in spans["launch_plan"][0] or spans["launch_plan"][0]["attrs"]["cache"] == "hit"


@pytest.mark.parametrize("name", list(CASES))
def test_warm_launch_ships_no_parameter_array(name, device_puts):
    kind, seg, (ctx, _), (want, _) = _case(name)
    res, _ = executor.execute_segment(ctx, seg)  # compiles, stages the columns
    assert _answer(res) == want
    staged = len(device_puts)
    assert staged > 0  # the columns went through jax.device_put, so the count sees them

    trace = Trace(True)
    st = executor.launch_segment(ctx, seg, trace=trace)
    assert len(device_puts) == staged  # resident columns, host parameters: no transfer but the call's
    (plan,) = st[3]  # a launch's state holds its members' plans: here the one
    assert plan.kind == kind and plan.cache_hit
    _check_carried(name, plan)
    res, _ = executor.collect_segment(st)
    assert _answer(res) == want
    _check_trace(trace, plan)


@pytest.mark.parametrize("name", list(CASES))
def test_warm_group_launch_ships_no_parameter_array(name, device_puts):
    """The width-2 group call of a server's launch: the members' parameter
    buffers are stacked on the host ([2, n] numpy) and ride the ONE jitted
    call; the columns are the resident ones, joined inside the program."""
    kind, _, ctxs, wants = _case(name)
    segs = _halves(CASES[name][1])
    assert _merged(_launch_halves(ctxs[0], segs)) == wants[0]  # compiles, stages the columns
    staged = len(device_puts)
    assert staged > 0

    for ctx, want in zip(ctxs, wants):  # warm: the same literals, then others of the shape
        trace = Trace(True)
        launches = _launch_halves(ctx, segs, trace)
        assert len(device_puts) == staged  # no transfer but the call's own
        assert launches.calls == 1 and launches.grouped_segments == 2
        ((state, _, _),) = launches._states
        assert [p.kind for p in state[3]] == [kind, kind] and all(p.cache_hit for p in state[3])
        for plan in state[3]:
            _check_carried(name, plan, rows=N // 2)
        assert _merged(launches) == want
        spans = _spans(trace.finish())
        (enqueue,) = spans["launch_enqueue"]
        assert enqueue["attrs"]["width"] == enqueue["attrs"]["segments"] == 2
        assert [c["name"] for c in enqueue["children"]] == ["launch_release"]
        assert [sp["attrs"]["paramArrays"] for sp in spans["launch_ship"]] == [len(state[3][0].params)] * 2
    assert len(device_puts) == staged


@pytest.mark.parametrize("name", list(CASES))
def test_one_compiled_plan_sees_one_argument_form(name):
    """Committed device arrays and host numpy are different signatures to
    jit: a caller that still device-put its parameters would compile the
    plan a second time.  launch_segment, execute_segment and the group
    launch (which traces the plan's kernel inside its own program) all run
    one plan here; it is compiled once, and other literals of the same shape
    add nothing."""
    kind, seg, ctxs, wants = _case(name)
    planner.plan_cache_clear()
    METRICS.reset()

    def counter(key):
        return METRICS.snapshot()["counters"].get(key, 0)

    st = executor.launch_segment(ctxs[0], seg)  # cold
    fn = st[3][0].fn
    assert _answer(executor.collect_segment(st)[0]) == wants[0]
    assert fn._cache_size() == 1 and counter("compile.sse.compiles") == 1
    assert _answer(executor.execute_segment(ctxs[0], seg)[0]) == wants[0]
    assert fn._cache_size() == 1

    # the second form: two members of that plan in ONE call (the same segment twice)
    launches = _launch_halves(ctxs[0], [seg, seg])
    ((state, _, _),) = launches._states
    assert launches.calls == 1 and all(p.fn is fn for p in state[3])
    double = _merged(launches)
    assert double == (
        {k: (2 * c, 2 * t) for k, (c, t) in wants[0].items()} if isinstance(wants[0], dict)
        else tuple(2 * x for x in wants[0])
    )
    assert counter("compile.group.programs") == 1  # a program of its own, once
    assert fn._cache_size() == 1 and counter("compile.sse.compiles") == 1  # and the plan's kernel compiled no second time

    assert _answer(executor.execute_segment(ctxs[1], seg)[0]) == wants[1]  # same shape, other literals
    _launch_halves(ctxs[1], [seg, seg]).collect()
    assert fn._cache_size() == 1 and counter("compile.sse.compiles") == 1
    assert counter("compile.group.programs") == 1 and counter("compile.sse.rebuilds") == 0


# ---------------------------------------------------------------------------
# first launch: a fact of (program, device), kept on the plan-cache entry
# ---------------------------------------------------------------------------
def test_first_launch_is_per_plan_and_device():
    """Two of tier-1's eight devices: the program compiles once on each, the
    span says so there and only there, and the record is the plan-cache
    entry's, so the plan a cache hit builds knows what the first one did.
    (A group program's first launch: tests/test_group_launch.py.)"""
    _, seg, ctxs, _ = _case("groupby_dense")
    planner.plan_cache_clear()
    d0, d1 = jax.devices()[1], jax.devices()[2]
    seen = []
    for device, want_first in [(d0, True), (d0, False), (d1, True), (d1, False), (d0, False)]:
        trace = Trace(True)
        hooked = []
        st = executor.launch_segment(ctxs[0], seg, device=device, trace=trace, on_first_launch=lambda: hooked.append(1))
        _, stats = executor.collect_segment(st)
        plan, compile_ms, kernel_bytes = st[3][0], stats.compile_ms, stats.kernel_bytes
        (enqueue,) = _spans(trace.finish())["launch_enqueue"]
        assert enqueue["attrs"].get("firstLaunch", False) == want_first, (device, enqueue)
        assert (compile_ms > 0) == want_first and ("compileMs" in enqueue["attrs"]) == want_first
        assert hooked == ([1] if want_first else [])  # called before the compile, and only then
        assert kernel_bytes == pytest.approx(plan.scan_bytes) and plan.scan_bytes > 0
        seen.append(plan)
    record = seen[0].launched_on
    assert all(p.launched_on is record for p in seen)  # one record, shared by reference
    assert seen[0] is not seen[1] and seen[1].cache_hit
    assert set(record) == {d0, d1} and all(ms > 0 for ms in record.values())


def test_a_hit_that_races_the_first_launch_shares_its_record():
    """Two plans of one cache entry built BEFORE either launched (two queries
    of a cold shape in flight at once): the second launch is not a first."""
    _, seg, ctxs, _ = _case("aggregation")
    planner.plan_cache_clear()
    first, second = planner.plan_segment(ctxs[0], seg), planner.plan_segment(ctxs[1], seg)
    assert not first.cache_hit and second.cache_hit and second.launched_on is first.launched_on == {}
    assert second.scan_bytes == first.scan_bytes > 0
    (_, s1), (_, s2) = executor.execute_segment(ctxs[0], seg), executor.execute_segment(ctxs[1], seg)
    assert s1.compile_ms > 0 and s2.compile_ms == 0.0
    assert list(first.launched_on) == [None]  # the default device, as the callers name it


# ---------------------------------------------------------------------------
# placement: tier-1 has 8 CPU devices (conftest.py)
# ---------------------------------------------------------------------------
PLACEMENT = {
    # name -> (SQL, upsert segment?, columns the plan needs, parameters)
    "with_columns": ("SELECT year, SUM(rev) FROM t WHERE qty < 20 GROUP BY year", False, True, 2),
    "no_column_no_parameter": ("SELECT COUNT(*) FROM t", False, False, 0),
    "no_column_valid_mask_only": ("SELECT COUNT(*) FROM t", True, False, 1),
    # its column is staged for the host-side gather; the kernel reads `__valid__` alone
    "selection_reads_no_column": ("SELECT rev FROM t LIMIT 5", True, True, 1),
}


@pytest.mark.parametrize("name", list(PLACEMENT))
def test_server_on_device_3_answers_from_device_3(name, monkeypatch):
    sql, upsert, has_columns, n_params = PLACEMENT[name]
    dev = jax.devices()[3]
    assert dev != jax.devices()[0]
    server = ServerInstance("server3", device=dev)
    server.add_segment("t", _segment(upsert, "s3"))

    states = []
    real = executor._launch_group
    monkeypatch.setattr(
        executor, "_launch_group", lambda *a, **kw: states.append(real(*a, **kw)) or states[-1]
    )
    for _ in range(2):  # cold, then warm
        results, stats = server.execute(parse_query(sql), ["s3"])
        assert stats.num_segments_processed == 1
    assert len(states) == 2
    for st in states:
        (plan,) = st[3]
        assert bool(plan.needed_columns) == has_columns and len(plan.param_layout) == n_params
        leaves = jax.tree_util.tree_leaves(st[4])
        assert leaves and all(leaf.devices() == {dev} for leaf in leaves)
    if name.startswith("no_column"):
        (res,) = results
        assert int(res.partials[0]["count"]) == (int(VALID.sum()) if upsert else N)


def test_pack_and_unpack_are_inverse_and_group_by_dtype():
    from pinot_tpu.query.shape import params_structure

    rng = np.random.default_rng(2)
    raw = {
        "f0.lo": np.int32(3),
        "f0.hi": np.int32(9),
        "f1.table": rng.random(5) < 0.5,
        "f2.eq": np.float64(2.5),
        "f3.vals": rng.integers(-(2**40), 2**40, 4),
        "f4.bits": rng.integers(0, 2**32, (2, 3), dtype=np.uint32),  # keeps its shape
        "f5.eq": np.int64(-(2**53) - 1),  # no detour through a float
        "__valid__": rng.random(7) < 0.5,
    }
    layout = params_structure(raw)
    packed = planner.pack_params(raw, layout)
    assert {k: (v.dtype.name, v.shape) for k, v in packed.items()} == {
        "int32": ("int32", (2,)), "bool": ("bool", (5,)), "float64": ("float64", (1,)),
        "int64": ("int64", (5,)), "uint32": ("uint32", (6,)), "__valid__": ("bool", (7,)),
    }
    assert packed["__valid__"] is raw["__valid__"]  # the segment's mask, not a copy
    for unpack in (planner.unpack_params, jax.jit(planner.unpack_params, static_argnums=1)):
        back = unpack(packed, layout)
        assert sorted(back) == sorted(raw)
        for k, v in raw.items():
            assert back[k].dtype == np.asarray(v).dtype and back[k].shape == np.shape(v)
            assert np.array_equal(np.asarray(back[k]), v)
    assert planner.pack_params({}, ()) == {} and planner.unpack_params({}, ()) == {}
