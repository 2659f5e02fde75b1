"""A plan-cache hit binds its parameters; it does not plan again.

`planner.QueryPlanning` derives a query's half of planning once, keeps a
segment's half on the segment (`planner._SegmentMemo`), and on a plan-cache
hit evaluates the entry's `ParamRecipe` against THIS segment's dictionaries
instead of running `_build_plan`.  What is held here, on the CPU:

  * the bound plan is the rebuilt plan: for all 13 templates of
    `benchmarks/queries/ssb_flat.json`, at SSB's literals and at three drawn
    sets, over segments whose dictionaries DIFFER (another dictionary of the
    same signature, so one cache entry serves both; fewer distinct values,
    so another entry; an upsert segment), the packed parameters are equal
    byte for byte and everything else of the plan, and the decoded answers,
    equal what a forced rebuild gives;
  * the recipe holds no literal of the query that compiled the entry;
  * four threads binding against one entry agree;
  * the plan-cache key is, value for value, the old derivation's, and the
    memo on the segment goes when `valid_docs` or a lazily built index
    appears;
  * predicates with a recipe bind (EQ, RANGE, NEQ, IN, NOT IN, LIKE,
    REGEXP_LIKE, IS NULL, a multi-value IN) and those without rebuild
    (TEXT_MATCH, a raw column, an inverted index), and the counters and the
    span attr say which happened;
  * a hit costs at most 150 Python calls a segment (call COUNTS under
    cProfile: no clock is read anywhere in this file).
"""
import cProfile
import dataclasses
import os
import pstats
import sys
import threading

import jax
import numpy as np
import pytest

from pinot_tpu import ops
from pinot_tpu.analysis.plan_check import check_plan_cached
from pinot_tpu.query import executor, planner
from pinot_tpu.query.shape import column_info_from
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
from pinot_tpu.utils.metrics import METRICS, Trace

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
TEMPLATES = ["q1_1", "q1_2", "q1_3", "q2_1", "q2_2", "q2_3", "q3_1", "q3_2", "q3_3", "q3_4", "q4_1", "q4_2", "q4_3"]
LITERALS = ["ssb", "drawn0", "drawn1", "drawn2"]
ROWS = 2500
SEED = 32
# filtered columns and the SSB literal each loses in the segment `other_dict`
# (replaced by a value no segment has, so the cardinality stays)
SWAPPED = {"d_year": (1993, 1991), "s_region": (1, 7), "lo_discount": (2, 11), "p_category": (1, 25)}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own files: configuration, generator, query set, renderer."""
    sys.path.insert(0, BENCH)
    try:
        from lib import plugins, templates

        cfg = plugins.load_json("configs", "ssb_flat_sf1")
        gen = plugins.load_module("datagen", cfg["datagen"])
        queries = plugins.load_json("queries", cfg["query_set"])["templates"]
    finally:
        sys.path.remove(BENCH)
    return cfg, gen, queries, templates


@pytest.fixture(scope="module")
def segments(bench):
    """Four segments whose dictionaries differ.  `base` and `other_dict`
    have the same rows but for SWAPPED's values: the same signature, so one
    plan-cache entry, and another dictionary in four filtered columns, one
    without SSB's literal.  `fewer` has no row of s_region 1 or d_year 1993:
    fewer distinct values, another signature.  `upsert` has `valid_docs`."""
    cfg, gen, _, _ = bench
    schema = Schema(
        cfg["table"],
        [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]]) for c in cfg["columns"]],
    )
    tcfg = TableConfig(cfg["table"], indexing=IndexingConfig.from_dict(cfg["table_config"]))

    def build(name, block):
        return build_segment(schema, {k: v.astype(np.int32) for k, v in block.items()}, name, table_config=tcfg)

    base = gen.make_segment(cfg, SEED, 0, ROWS)
    other = {k: v.copy() for k, v in base.items()}
    for col, (old, new) in SWAPPED.items():
        assert (other[col] == old).any() and not (other[col] == new).any()
        other[col][other[col] == old] = new
    second = gen.make_segment(cfg, SEED, 1, ROWS)
    keep = (second["s_region"] != 1) & (second["d_year"] != 1993)
    upsert = build("upsert", gen.make_segment(cfg, SEED, 2, ROWS))
    upsert.valid_docs = np.random.default_rng(SEED).random(ROWS) < 0.6
    planner.plan_cache_clear()
    yield [build("base", base), build("other_dict", other), build("fewer", {k: v[keep] for k, v in second.items()}), upsert]
    planner.plan_cache_clear()


def _ctx(bench, name, literals):
    _, _, queries, templates = bench
    t = queries[name]
    if literals == "ssb":
        return parse_query(templates.render(t, t["ssb"]))
    rng = np.random.default_rng([SEED, TEMPLATES.index(name), int(literals[-1])])
    return parse_query(templates.render(t, templates.draw_params(t, rng)))


@pytest.fixture(scope="module")
def warm(bench, segments):
    """Every template's entries compiled by a query whose literals no case
    below uses: whatever a case binds, the recipe did not get from it."""
    _, _, queries, templates = bench
    for name in TEMPLATES:
        rng = np.random.default_rng([SEED, TEMPLATES.index(name), 99])
        ctx = parse_query(templates.render(queries[name], templates.draw_params(queries[name], rng)))
        hits = [planner.plan_segment(ctx, seg).cache_hit for seg in segments]
        assert hits == [False, True, False, False]  # `other_dict` is served by `base`'s entry


def _old_key(ctx, segment):
    """The plan-cache key as plan_segment derived it before the halves were
    memoised, line for line."""
    needed = planner._needed_columns(ctx, segment)
    return (
        ctx.shape_fingerprint(column_info_from(segment)),
        planner._segment_signature(
            segment, needed, planner.sketch_bound_columns(ctx) | planner.const_bound_columns(ctx),
            group_cols=frozenset(c for g in ctx.group_by for c in g.columns()),
        ),
        ops.scan_backend(),
    )


def _rebuilt(ctx, segment, fn):
    """What a hit was before the recipe: _build_plan over the compiled fn."""
    return planner._build_plan(ctx, segment, planner._needed_columns(ctx, segment), compiled_fn=fn)


def _same(a, b):
    """Field for field and bit for bit."""
    if dataclasses.is_dataclass(a):
        return type(a) is type(b) and all(
            _same(getattr(a, f.name), getattr(b, f.name)) for f in dataclasses.fields(a)
        )
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_same(x, y) for x, y in zip(a, b))
    if isinstance(a, (np.ndarray, np.generic, jax.Array)):
        a, b = np.asarray(a), np.asarray(b)
        return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(a, b)
    return a == b


def _same_plan(bound, rebuilt):
    """The bound plan is the rebuilt one: parameters byte for byte."""
    assert list(bound.params) == list(rebuilt.params)
    for k, v in bound.params.items():
        w = rebuilt.params[k]
        assert (v.dtype, v.shape, v.tobytes()) == (w.dtype, w.shape, w.tobytes()), k
    for field in ("kind", "param_layout", "needed_columns", "num_groups", "select_columns", "index_uses"):
        assert getattr(bound, field) == getattr(rebuilt, field), field
    assert _same(bound.group_dims, rebuilt.group_dims)
    assert [type(a) for a in bound.aggs] == [type(a) for a in rebuilt.aggs]
    return True


def _launch(ctx, segs, trace=None):
    launches = executor.QueryLaunches(ctx, trace=trace)
    for seg in segs:
        launches.add(seg)
    launches.flush()
    return [res for res, _ in launches.collect()]


def _spans(node, name, out=None):
    out = [] if out is None else out
    if node["name"] == name:
        out.append(node)
    for c in node.get("children", []):
        _spans(c, name, out)
    return out


def _counters():
    got = METRICS.snapshot()["counters"]
    return {k: got.get(f"compile.sse.{k}", 0) for k in ("binds", "rebuilds", "hits", "compiles")}


# ---------------------------------------------------------------------------
# (1) the bound plan is the rebuilt plan, and so are the answers
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("literals", LITERALS)
@pytest.mark.parametrize("name", TEMPLATES)
def test_bound_plan_equals_a_forced_rebuild(name, literals, bench, segments, warm, monkeypatch):
    ctx = _ctx(bench, name, literals)
    planning = planner.QueryPlanning(ctx)
    before = _counters()
    for seg in segments:
        assert planning.key(seg) == _old_key(ctx, seg)
        plan = planning.plan(seg)
        assert plan.cache_hit and plan.bind == "recipe", (seg.name, plan.bind)
        assert _same_plan(plan, _rebuilt(ctx, seg, plan.fn))
    base, other, fewer, upsert = (planning.plan(seg) for seg in segments)
    # one entry serves two dictionaries, and the parameters are each segment's own
    assert base.fn is other.fn and base.cache_key == other.cache_key
    assert fewer.fn is not base.fn and planner.VALID_KEY in upsert.params
    moved = {k: v - before[k] for k, v in _counters().items()}
    assert (moved["binds"], moved["rebuilds"], moved["compiles"]) == (2 * len(segments), 0, 0)

    bound = _launch(ctx, segments)
    monkeypatch.setattr(planner, "_bind_params", lambda *a: None)  # every hit rebuilds
    before = _counters()
    rebuilt = _launch(ctx, segments)
    moved = {k: v - before[k] for k, v in _counters().items()}
    assert (moved["binds"], moved["rebuilds"], moved["compiles"]) == (0, len(segments), 0)
    assert all(_same(a, b) for a, b in zip(bound, rebuilt))


def test_ssb_literal_absent_from_one_dictionary_binds_the_empty_range(bench, segments, warm):
    """Q1.1 at SSB's `d_year = 1993`: `other_dict` has no 1993, `base` has."""
    ctx = _ctx(bench, "q1_1", "ssb")
    planning = planner.QueryPlanning(ctx)
    base, other = planning.plan(segments[0]), planning.plan(segments[1])
    layout = {key: i for i, (key, _, _) in enumerate(base.param_layout)}
    lo, hi = base.params["int32"][layout["f0.lo"]], base.params["int32"][layout["f1.hi"]]
    assert (lo, hi) == (1, 2)  # 1992, [1993], 1994...
    assert (other.params["int32"][layout["f0.lo"]], other.params["int32"][layout["f1.hi"]]) == (0, 0)
    got = _launch(ctx, segments[:2])
    assert got[0].partials[0]["sum"] > 0 and not got[1].partials[0]["sum"]


def test_a_recipe_holds_no_literal_and_no_dictionary(bench, segments, warm):
    for name in TEMPLATES:
        plan = planner.plan_segment(_ctx(bench, name, "ssb"), segments[0])
        recipe = planner._PLAN_CACHE.get(plan.cache_key).recipe
        assert recipe is not None and plan.recipe is recipe
        for kind, ptype, column, mv, slots in recipe.binders:
            assert kind in ("range", "table", "none", "docrange") and isinstance(column, str) and mv is False
            assert all(isinstance(x, (str, int, tuple)) for slot in slots for x in slot)


def test_four_threads_binding_one_entry_agree(bench, segments, warm):
    """Each thread binds its own literals against the entries the others
    use, many times over; every plan is what a rebuild gives, serially."""
    ctxs = [_ctx(bench, "q4_2", lit) for lit in LITERALS]
    want = [[_rebuilt(ctx, seg, planner.plan_segment(ctx, seg).fn) for seg in segments] for ctx in ctxs]
    wrong, start = [], threading.Barrier(len(ctxs))

    def run(i):
        try:
            start.wait()
            for _ in range(25):
                planning = planner.QueryPlanning(ctxs[i])
                for seg, rebuilt in zip(segments, want[i]):
                    plan = planning.plan(seg)
                    assert plan.bind == "recipe" and _same_plan(plan, rebuilt)
        except BaseException as e:  # noqa: BLE001: reported by the main thread
            wrong.append((i, repr(e)))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(ctxs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not wrong


def test_counters_and_span_attr_say_bind_or_rebuild(bench, segments, warm, monkeypatch):
    ctx = _ctx(bench, "q2_1", "drawn1")
    before = _counters()
    trace = Trace(True)
    _launch(ctx, segments, trace=trace)
    plans = _spans(trace.finish(), "launch_plan")
    assert [(p["attrs"]["cache"], p["attrs"]["bind"]) for p in plans] == [("hit", "recipe")] * len(segments)
    moved = {k: v - before[k] for k, v in _counters().items()}
    assert (moved["binds"], moved["rebuilds"]) == (len(segments), 0) and moved["hits"] >= len(segments)

    monkeypatch.setattr(planner, "_bind_params", lambda *a: None)
    trace = Trace(True)
    _launch(ctx, segments, trace=trace)
    plans = _spans(trace.finish(), "launch_plan")
    assert [(p["attrs"]["cache"], p["attrs"]["bind"]) for p in plans] == [("hit", "rebuild")] * len(segments)
    assert {k: v - before[k] for k, v in _counters().items()}["rebuilds"] == len(segments)

    planner.plan_cache_clear()
    try:
        trace = Trace(True)
        _launch(ctx, segments[:1], trace=trace)
        (miss,) = _spans(trace.finish(), "launch_plan")
        assert miss["attrs"]["cache"] == "miss" and "bind" not in miss["attrs"]
    finally:
        planner.plan_cache_clear()  # `warm` is module-scoped: the cases after this one compile their own


# ---------------------------------------------------------------------------
# (2) the segment's memo is the function called fresh, and goes when it must
# ---------------------------------------------------------------------------
def test_valid_docs_set_after_the_first_query_changes_the_key_as_a_fresh_signature_would(bench):
    cfg, gen, _, _ = bench
    schema = Schema(
        cfg["table"],
        [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]]) for c in cfg["columns"]],
    )
    block = gen.make_segment(cfg, SEED, 5, 600)
    seg = build_segment(schema, {k: v.astype(np.int32) for k, v in block.items()}, "late_upsert")
    ctx = _ctx(bench, "q1_1", "ssb")
    planning = planner.QueryPlanning(ctx)
    first = planning.key(seg)
    assert first == _old_key(ctx, seg) and planning.key(seg) == first
    memo = seg._plan_memo
    seg.valid_docs = np.arange(600) % 3 > 0
    second = planning.key(seg)  # the same query, mid-flight, and a new one
    assert second == _old_key(ctx, seg) == planner.QueryPlanning(ctx).key(seg) and second != first
    assert seg._plan_memo is not memo and second[1][1] is True and first[1][1] is False
    try:
        plan = planner.plan_segment(ctx, seg)
        again = planner.plan_segment(_ctx(bench, "q1_1", "drawn0"), seg)
        assert not plan.cache_hit and again.bind == "recipe" and planner.VALID_KEY in again.params
        assert again.params[planner.VALID_KEY] is seg.valid_docs  # shared: later invalidations apply
    finally:
        planner.plan_cache_clear()


NOTES = np.array(["red fox", "blue fox", "red hen", "grey owl", "blue jay", "red kite"], dtype=object)
TAGS = [["a", "b"], ["b"], ["c", "a"], ["d"], ["a"], ["b", "c", "d"]]


def _text_table(inverted=()):
    schema = Schema(
        "notes",
        [
            FieldSpec("note", DataType.STRING),
            FieldSpec("city", DataType.STRING),
            FieldSpec("qty", DataType.INT, nullable=True),
            FieldSpec("tags", DataType.STRING, single_value=False),
            FieldSpec("rev", DataType.LONG, role=FieldRole.METRIC),
            FieldSpec("raw", DataType.INT),
        ],
    )
    tcfg = TableConfig(
        "notes", indexing=IndexingConfig(no_dictionary_columns=["raw"], inverted_index_columns=list(inverted))
    )

    def block(seed, n=240):
        rng = np.random.default_rng(seed)
        cities = np.array(["ams", "ber", "cph", "dub", "edi"], dtype=object)[(seed % 2):]
        at = rng.integers(0, len(NOTES), n)
        return {
            "note": NOTES[at],
            "city": cities[rng.integers(0, len(cities) - 1 + (seed % 2), n) % len(cities)],
            "qty": np.where(rng.random(n) < 0.1, None, rng.integers(1, 50, n)).astype(object),
            "tags": [TAGS[i] for i in at],
            "rev": rng.integers(1, 10**6, n),
            "raw": rng.integers(0, 1000, n).astype(np.int32),
        }

    return [build_segment(schema, block(s), f"notes{s}", table_config=tcfg) for s in (1, 2, 3)]


def test_a_lazily_built_index_changes_the_key_as_a_fresh_signature_would():
    seg = _text_table()[0]
    eq = parse_query("SELECT COUNT(*) FROM notes WHERE note = 'red fox'")
    text = parse_query("SELECT COUNT(*) FROM notes WHERE TEXT_MATCH(note, 'fox')")
    planning = planner.QueryPlanning(eq)
    first = planning.key(seg)
    assert first == _old_key(eq, seg) and "text" not in seg.indexes
    try:
        planner.plan_segment(text, seg)  # FilterCompiler._cache_index: segment.indexes gains a kind
        assert "note" in seg.indexes["text"]
        second = planning.key(seg)
        assert second == _old_key(eq, seg) == planner.QueryPlanning(eq).key(seg) and second != first
        # a second kind on the same column, and the same kind on another: each seen
        seg.indexes.setdefault("json", {})["note"] = object()
        third = planning.key(seg)
        assert third == _old_key(eq, seg) and third != second
        seg.indexes["text"]["city"] = seg.indexes["text"]["note"]
        by_city = parse_query("SELECT COUNT(*) FROM notes WHERE city = 'ber'")
        assert planner.QueryPlanning(by_city).key(seg) == _old_key(by_city, seg)
    finally:
        planner.plan_cache_clear()


# ---------------------------------------------------------------------------
# (3) which predicates bind and which rebuild
# ---------------------------------------------------------------------------
BINDS = {
    "in": ("SELECT COUNT(*), SUM(rev) FROM notes WHERE city IN ('ber', 'cph', 'xyz')",
           "SELECT COUNT(*), SUM(rev) FROM notes WHERE city IN ('ams', 'edi', 'dub')"),
    "not_in": ("SELECT COUNT(*), SUM(rev) FROM notes WHERE city NOT IN ('ber', 'cph')",
               "SELECT COUNT(*), SUM(rev) FROM notes WHERE city NOT IN ('dub', 'xyz')"),
    "neq": ("SELECT city, SUM(rev) FROM notes WHERE note <> 'red hen' GROUP BY city",
            "SELECT city, SUM(rev) FROM notes WHERE note <> 'blue jay' GROUP BY city"),
    "like": ("SELECT COUNT(*) FROM notes WHERE note LIKE 'red%'",
             "SELECT COUNT(*) FROM notes WHERE note LIKE '%fox'"),
    "regexp": ("SELECT COUNT(*) FROM notes WHERE REGEXP_LIKE(note, '^blue')",
               "SELECT COUNT(*) FROM notes WHERE REGEXP_LIKE(note, 'o[wx]')"),
    "is_null_and_range": ("SELECT COUNT(*) FROM notes WHERE qty IS NOT NULL AND qty BETWEEN 5 AND 20",
                          "SELECT COUNT(*) FROM notes WHERE qty IS NOT NULL AND qty BETWEEN 30 AND 31"),
    "multi_value_in": ("SELECT COUNT(*) FROM notes WHERE tags IN ('a', 'zzz')",
                       "SELECT COUNT(*) FROM notes WHERE tags IN ('c', 'd')"),
    # a FILTER clause's literals are part of the select list's fingerprint, so of the key: its predicate is
    # walked, after WHERE's, and resolved against each segment's dictionary
    "filtered_aggregation": ("SELECT SUM(rev) FILTER (WHERE city = 'ber'), COUNT(*) FROM notes WHERE qty < 40",
                             "SELECT SUM(rev) FILTER (WHERE city = 'ber'), COUNT(*) FROM notes WHERE qty < 12"),
}
REBUILDS = {
    # the literal of a TEXT_MATCH is part of the key (shape.audit_predicate: traced-structure)
    "text_match": ("SELECT COUNT(*) FROM notes WHERE TEXT_MATCH(note, 'fox') AND city <> 'ber'",
                   "SELECT COUNT(*) FROM notes WHERE TEXT_MATCH(note, 'fox') AND city <> 'cph'"),
    "raw_column": ("SELECT COUNT(*) FROM notes WHERE raw < 500 AND city = 'ber'",
                   "SELECT COUNT(*) FROM notes WHERE raw < 100 AND city = 'cph'"),
}


def _bind_case(sqls, segs, how):
    """`sqls[0]` compiles the entries, `sqls[1]` (other literals) hits them:
    `how` it came by its parameters, and that they and the answers are a
    rebuild's."""
    planner.plan_cache_clear()
    try:
        first, second = (parse_query(s) for s in sqls)
        _launch(first, segs)
        _launch(first, segs)  # a lazily built index changed the signature: the entries that stay
        before = _counters()
        planning = planner.QueryPlanning(second)
        for seg in segs:
            assert planning.key(seg) == _old_key(second, seg)
            plan = planning.plan(seg)
            assert plan.cache_hit and plan.bind == how, (seg.name, plan.bind)
            assert _same_plan(plan, _rebuilt(second, seg, plan.fn))
        moved = {k: v - before[k] for k, v in _counters().items()}
        assert moved["compiles"] == 0 and moved["binds" if how == "recipe" else "rebuilds"] == len(segs)
        got = _launch(second, segs)
        planner.plan_cache_clear()
        assert all(_same(a, b) for a, b in zip(got, _launch(second, segs)))  # each compiled for these literals
    finally:
        planner.plan_cache_clear()


@pytest.mark.parametrize("case", sorted(BINDS))
def test_a_predicate_with_a_recipe_binds(case):
    _bind_case(BINDS[case], _text_table(), "recipe")


@pytest.mark.parametrize("case", sorted(REBUILDS))
def test_a_predicate_without_a_recipe_rebuilds_and_says_so(case):
    _bind_case(REBUILDS[case], _text_table(), "rebuild")


def test_an_inverted_index_binds():
    """An inverted index on a resident segment's column is not consulted (PR
    47: the planner's decision from costs, filter.bitmap_serves, which hangs
    on the segment's shape and not on a literal), so the predicate is a code
    scan's and a hit binds it; it rebuilt while the choice between bitmap and
    scan hung on the literals and the dictionary."""
    _bind_case(
        ("SELECT COUNT(*) FROM notes WHERE city = 'ber'", "SELECT COUNT(*) FROM notes WHERE city = 'ber'"),
        _text_table(inverted=["city"]), "recipe",
    )


# ---------------------------------------------------------------------------
# (4) what a hit costs: a count of calls, not a time
# ---------------------------------------------------------------------------
MAX_CALLS_A_SEGMENT = 150  # ~700 before this mechanism (Q1.1; ~1,400 for Q4.2)


@pytest.mark.parametrize("name", TEMPLATES)
def test_a_hit_makes_at_most_150_python_calls_a_segment(name, bench):
    """Eight segments of one signature, one query: its half of planning made
    once, then a plan a segment, as executor.QueryLaunches does it."""
    cfg, gen, _, _ = bench
    schema = Schema(
        cfg["table"],
        [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]]) for c in cfg["columns"]],
    )
    block = gen.make_segment(cfg, SEED, 0, 400)
    segs = [build_segment(schema, {k: v.astype(np.int32) for k, v in block.items()}, f"s{i}") for i in range(8)]
    planner.plan_cache_clear()
    try:
        warm_up = planner.QueryPlanning(_ctx(bench, name, "ssb"))
        for seg in segs:
            warm_up.plan(seg)
        ctx = _ctx(bench, name, "drawn0")
        # the static plan check runs once a query TEXT a process (an LRU of its own): whether an
        # earlier test has run this text must not move the count below, which is the planner's
        check_plan_cached(ctx)
        profile = cProfile.Profile()
        profile.enable()
        planning = planner.QueryPlanning(ctx)
        plans = [planning.plan(seg) for seg in segs]
        profile.disable()
        assert all(p.bind == "recipe" for p in plans)
        calls = pstats.Stats(profile).total_calls / len(segs)
        assert calls <= MAX_CALLS_A_SEGMENT, calls
    finally:
        planner.plan_cache_clear()
