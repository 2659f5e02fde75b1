"""What PR 41 adds to the benchmark: the configuration
`pinot_perf_ssqe_exp001_50seg`, its cell, the plain reference
`filter_group_aggs`, the count of its needs (lib/aggcount.py), three
reducers and the controls on its `merge` guarantee (lib/controls_aggs.py).  The reference is held to a brute-force loop over 1,000 rows (and
merges blocks by VALUE); the generator draws every segment apart; the count
reads columns at their device widths; the roofline share reads 100 % exactly
when the device was busy for the least time and cannot pass it; the warm-up
reducer reports nothing on a program without the counters.  Run by hand:
`python -m pytest benchmarks/tests -q`.
"""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import aggcount, controls_aggs, harness, opcount, plugins, templates  # noqa: E402
from lib.reducers import agg_roofline_share, device_event_ms_per_template_query, warm_moved_mean  # noqa: E402
from lib.references import filter_group_aggs as ref  # noqa: E402

CELL = "ssqe_exp001_50seg.aggs_closed"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "name": "TPU v5e"}
NEW = ["table_shaped_segments_per_query", "tables_decoded_per_query", "tables_merged_by_value_per_query",
       "warm_up_compiles_per_template", "warm_up_s", "ssqe_roofline",
       "in_table_gather_ms", "dict_decode_gather_ms", "table_shaped_scatter_ms"]


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


def _blocks(cell, rows=250, n=4, seed=2**31 + 11):
    gen = plugins.load_module("datagen", cell["config"]["datagen"])
    return [gen.make_segment(cell["config"], seed, i, rows) for i in range(n)]


def _brute(spec, params, blocks):
    """The same semantics, a row at a time over all the rows at once."""
    val = lambda x: params[x] if isinstance(x, str) else x  # noqa: E731

    def passes(row, tests):
        for col, op, *xs in tests or ():
            v, xs = row[col], [val(x) for x in xs]
            if not {"gt": v > xs[0], "lt": v < xs[0], "eq": v == xs[0], "in": v in xs}[op]:
                return False
        return True

    names = list(blocks[0])
    rows = [dict(zip(names, vals)) for b in blocks for vals in zip(*[b[n].tolist() for n in names])]
    groups = {}
    for row in rows:
        if not passes(row, spec["where"]):
            continue
        acc = groups.setdefault(tuple(row[g] for g in spec["group_by"]), [None] * len(spec["aggs"]))
        for i, a in enumerate(spec["aggs"]):
            if a["fn"] == "count":
                acc[i] = (acc[i] or 0) + (1 if passes(row, a.get("filter")) else 0)
            elif passes(row, a.get("filter")):
                v = row[a["col"]]
                acc[i] = v if acc[i] is None else {"sum": acc[i] + v, "min": min(acc[i], v), "max": max(acc[i], v)}[a["fn"]]
    if not spec["group_by"]:
        return {"aggs": groups.get((), [0 if a["fn"] == "count" else None for a in spec["aggs"]])}
    gb = spec["group_by"]
    order = sorted(groups, key=lambda k: tuple((1 if d == "asc" else -1) * k[gb.index(c)] for c, d in spec["order_by"]) + k)
    if spec.get("limit") is not None:
        order = order[: spec["limit"]]
    return {"rows": [list(k) + groups[k] for k in order], "groups": len(groups)}


def test_the_generator_draws_every_segment_apart(cell):
    cfg = cell["config"]
    a, b = _blocks(cell, rows=20_000, n=3), _blocks(cell, rows=20_000, n=3)
    assert all(np.array_equal(x[k], y[k]) for x, y in zip(a, b) for k in x)  # the same seed, the same table
    assert set(a[0]) == {c["name"] for c in cfg["columns"]}
    sizes = [len(np.unique(blk["INT_COL"])) for blk in a]
    assert len(set(sizes)) == 3 and all(3000 < s < 5000 for s in sizes)  # EXP(0.001): dictionaries of another size each
    assert not np.array_equal(np.unique(a[0]["INT_COL"]), np.unique(a[1]["INT_COL"]))
    for blk in a:
        assert np.array_equal(blk["SORTED_COL"], np.arange(20_000)) and np.array_equal(blk["TSTMP_COL"], np.arange(20_000) * 1_200_000)
        assert np.array_equal(blk["LOW_CARDINALITY_STRING_COL"], np.arange(20_000) % 10)
        for name in ("INT_COL", "NO_INDEX_INT_COL", "RAW_INT_COL", "NO_INDEX_STRING_COL"):
            assert 900 < blk[name].mean() < 1100 and blk[name].min() >= 0  # mean 1 / lambda - 1/2
    assert not np.array_equal(a[0]["INT_COL"], a[0]["NO_INDEX_INT_COL"])  # the supplier's draws in turn, not one draw four times


@pytest.mark.parametrize("name", ["filtered_query", "count_in", "group_low_high", "sum_query"])
def test_the_reference_equals_a_brute_force_loop(name, cell):
    template = cell["query_set"]["templates"][name]
    spec, blocks = template["reference"], _blocks(cell)
    rng = np.random.default_rng(5)
    for params in [dict(template["ssb"])] + [templates.draw_params(template, rng) for _ in range(5)]:
        want = _brute(spec, params, blocks)
        got = ref.answer(spec, params, blocks)
        assert {k: got[k] for k in want} == want, (name, params)
        if "rows" in want:
            cols = spec["group_by"] + ["count(*)"]
            assert ref.compare(spec, cols, want["rows"], got)[0]
            assert not ref.compare(spec, cols, want["rows"][::-1], got)[0]  # the order is part of the answer
            assert not ref.compare(spec, cols, want["rows"][:-1], got)[0]
            off = [list(r) for r in want["rows"]]
            off[3][-1] += 1
            assert not ref.compare(spec, cols, off, got)[0]
        else:
            served = [[float(v) if v is not None else None for v in want["aggs"]]]  # an integer may arrive as 123.0
            assert ref.compare(spec, [], served, got) == (True, {"wrong_aggs": 0, "max_abs_diff": 0, "rows": 1, "limit": 0})
            served[0][0] += 1
            assert not ref.compare(spec, [], served, got)[0]


def test_the_reference_merges_blocks_by_value_and_knows_nulls():
    spec = {"where": [["a", "gt", "lo"]], "group_by": ["g"], "order_by": [["g", "desc"]], "limit": None,
            "aggs": [{"fn": "count", "col": None}, {"fn": "min", "col": "a", "filter": [["a", "lt", 5]]},
                     {"fn": "sum", "col": "a", "filter": [["g", "eq", 7]]}]}
    blocks = [{"g": np.array([7, 7, 9]), "a": np.array([1, 6, 3])}, {"g": np.array([9, 7, 4]), "a": np.array([8, 2, 0])}]
    got = ref.answer(spec, {"lo": 0}, blocks)
    # g = 4 passes no WHERE; g = 9: min over [3] (8 is no < 5), no row of g = 7 so its SUM is null
    assert got == {"rows": [[9, 2, 3, None], [7, 3, 1, 9]], "groups": 2}
    assert ref.answer(dict(spec, group_by=[], order_by=[]), {"lo": 100}, blocks) == {"aggs": [0, None, None]}
    assert ref.compare(dict(spec, group_by=[], order_by=[]), [], [[0, None, 0]], {"aggs": [0, None, None]})[0]  # a SUM of nothing may be 0


def _served(spec, answer):
    """An exact answer as the front door would serve it: (columns, rows)."""
    if "aggs" in answer:
        return [], [answer["aggs"]]
    return list(spec["group_by"]) + ["count(*)"], answer["rows"]


@pytest.mark.parametrize("name,sees", [("group_low_high_whole", True), ("filtered_query", True),
                                       ("group_low_high", False), ("count_in", False)])
def test_the_merge_controls_and_the_answers_that_can_see_them(name, sees, cell):
    """lib/controls_aggs.py breaks the configuration's `merge` guarantee two ways (tables met by CODE; a
    segment's dictionary tail lost).  The whole merged table and the FILTERed SUM / MAX see both; the cell's
    own group-by (10 rows, keys (0,0)..(0,9)) and its IN list (values of at most 1,010) sit where every
    segment's code IS its value and its tail is far away, and see neither: tools/control_aggs.py reports the
    same at the timed size."""
    blocks = _blocks(cell, rows=20_000, n=4)
    t = cell["query_set"]["templates"][name]
    spec, params = t["reference"], t["ssb"]
    cols, rows = _served(spec, ref.answer(spec, params, blocks))
    assert ref.compare(spec, cols, rows, ref.answer(spec, params, blocks))[0]
    for control, fn in controls_aggs.controls_for(cell["config"]).items():
        assert ref.compare(spec, cols, rows, fn(ref, spec, params, blocks))[0] == (not sees), control


def test_a_merge_control_has_nothing_to_break_on_a_raw_column(cell):
    blocks = _blocks(cell, rows=2_000, n=2)
    spec = cell["query_set"]["templates"]["sum_query"]["reference"]
    assert all(fn(ref, spec, {}, blocks) is None for fn in controls_aggs.controls_for(cell["config"]).values())
    mixed = dict(spec, where=[["INT_COL", "gt", 500]])  # a dictionary column beside it: now there is
    assert all(fn(ref, mixed, {}, blocks)["aggs"] != ref.answer(mixed, {}, blocks)["aggs"]
               for fn in controls_aggs.controls_for(cell["config"]).values())


def test_the_count_reads_columns_at_their_device_widths(cell):
    cfg, tpl = cell["config"], cell["query_set"]["templates"]
    widths = aggcount.column_bytes_per_row(cfg)
    assert widths == {"SORTED_COL": 4.0, "INT_COL": 2.0, "NO_INDEX_INT_COL": 2.0, "RAW_INT_COL": 4.0,
                      "NO_INDEX_STRING_COL": 2.0, "LOW_CARDINALITY_STRING_COL": 0.5, "TSTMP_COL": 8.0}
    rows = 75_000_000
    needs = {n: aggcount.query_needs(cfg, tpl[n]) for n in tpl}
    assert needs["filtered_query"] == {"bytes": rows * 4.0 + 8.0, "ops": rows * (2 + 4 + 2), "bytes_per_row": 4.0}
    assert needs["count_in"] == {"bytes": rows * 2.0 + 8.0, "ops": rows * 2, "bytes_per_row": 2.0}
    assert needs["group_low_high"] == {"bytes": rows * 2.5 + 8.0 * 81920, "ops": rows * 5, "bytes_per_row": 2.5}
    assert needs["sum_query"] == {"bytes": rows * 4.0 + 8.0, "ops": rows * 1, "bytes_per_row": 4.0}
    assert all(opcount.least_seconds(n, PEAK)[1] == "hbm" for n in needs.values())


def test_the_roofline_share_cannot_pass_100(cell):
    weights = {"filtered_query": 3.0, "count_in": 3.0, "group_low_high": 2.5, "sum_query": 3.5}
    least = sum(w * opcount.least_seconds(aggcount.query_needs(cell["config"], cell["query_set"]["templates"][t]), PEAK)[0]
                for t, w in weights.items())
    spec = plugins.load_json("layer_metrics", "ssqe_roofline")

    def share(busy_s, w=weights):
        return agg_roofline_share.reduce(spec, {"config": cell["config"], "query_set": cell["query_set"], "peak": PEAK,
                                                "device_trace": {"busy_s": busy_s, "template_weights": w}})

    assert share(least) == pytest.approx(100.0) and share(4 * least) == pytest.approx(25.0)
    assert share(0.0) is None and share(1.0, {}) is None
    assert agg_roofline_share.reduce(spec, {"device_trace": None}) is None


def test_warm_up_compiles_are_a_mean_over_the_templates():
    spec = plugins.load_json("layer_metrics", "warm_up_compiles_per_template")
    assert spec["counters"] == ["compile.sse.compiles", "compile.group.programs"]
    moved = {"a": {"compile.sse.compiles": 40.0, "compile.group.programs": 6.0, "scan.traced.xla": 40.0},
             "b": {"compile.sse.compiles": 1.0, "compile.group.programs": 2.0}, "c": {"compile.sse.binds": 50.0}}
    held = {"compile.sse.compiles": 41.0}
    assert warm_moved_mean.reduce(spec, {"warm_moved": moved, "counters_after": held}) == pytest.approx(49.0 / 3)
    assert warm_moved_mean.reduce(spec, {"warm_moved": moved, "counters_after": {}}) is None  # a program without the counters
    assert warm_moved_mean.reduce(spec, {"warm_moved": {}, "counters_after": held}) is None


def test_a_gather_is_read_per_query_of_its_own_template_and_not_at_all_where_there_is_none(cell):
    """`in_table_gather_ms` / `dict_decode_gather_ms`: the v5e compiler's own names (an AOT compile of the cell's
    programs), told by what they write: one flat vector of a segment's rows from a `kCustom` fusion."""
    rows = cell["config"]["segment_rows"]
    events = {
        f"%fusion.2 = pred[{rows}]{{0:T(1024)(128)(4,1)S(1)}} fusion(%copy-done, %pad_clamp_fusion), kind=kCustom, calls=%f": (350, 1.4),
        f"%fusion.6 = s32[{rows}]{{0:T(1024)S(1)}} fusion(%copy-done, %pad_clamp_fusion), kind=kCustom, calls=%fused": (350, 0.5),
        f"%fusion.1 = s32[{rows}]{{0:T(1024)S(1)}} fusion(%bitcast.5, %bitcast.4), kind=kLoop, calls=%fused_computation.4": (350, 9.0),
        "%fusion.19 = s32[81920]{0:T(1024)S(1)} fusion(%fusion.1, %broadcast.55, %constant.7), kind=kCustom, calls=%f": (350, 1.0),
        f"%select_reduce_fusion = (f32[], pred[{rows}]{{0:T(1024)}}, pred[{rows}]{{0:T(1024)}}) fusion(%fusion), kind=kLoop": (350, 9.0),
    }
    ctx = {"config": cell["config"], "device_trace": {"events": events, "template_weights": {"count_in": 2.0, "filtered_query": 1.25, "sum_query": 4.0}}}
    gather, decode = (plugins.load_json("layer_metrics", n) for n in ("in_table_gather_ms", "dict_decode_gather_ms"))
    assert device_event_ms_per_template_query.reduce(gather, ctx) == pytest.approx(1400.0 / 2.0)
    assert device_event_ms_per_template_query.reduce(decode, ctx) == pytest.approx(500.0 / 1.25)
    other = dict(ctx, config=dict(cell["config"], segment_rows=rows // 2))  # kernels of another shape: nothing to read
    assert device_event_ms_per_template_query.reduce(gather, other) is None
    none_traced = dict(ctx, device_trace=dict(ctx["device_trace"], template_weights={"sum_query": 4.0}))
    assert device_event_ms_per_template_query.reduce(gather, none_traced) is None
    assert device_event_ms_per_template_query.reduce(gather, dict(ctx, device_trace=None)) is None  # an untraced run


def test_the_cell_and_its_metrics_are_what_issue_41_names(cell):
    assert (cell["cell"]["config"], cell["cell"]["traffic"], cell["cell"]["chips"]) == (
        "pinot_perf_ssqe_exp001_50seg", "ssqe_aggs_closed", 1)
    mix = cell["mix"]
    assert (mix["loop"], mix["clients"], mix["templates"], mix["sample_checked"], mix["rolling_start_s"]) == (
        "closed", 4, ["filtered_query", "count_in", "group_low_high", "sum_query"], 40, 3.0)
    cfg = cell["config"]
    assert (cfg["rows"], cfg["segment_rows"], cfg["servers"], cfg["replication"], cfg["exp_lambda"]) == (75_000_000, 1_500_000, 1, 1, 0.001)
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {"columns", "dimension_encoding", "indexes", "templates"}
    assert {"source", "stands_for", "assumed", "guarantees"} <= set(cfg) and cfg["guarantees"]["result_cache"].startswith("off")
    per_layer = {m["name"]: m for m in cell["per_layer"]}
    for name in NEW:
        spec = plugins.load_json("layer_metrics", name)
        m = per_layer[name]
        assert (spec["layer"], spec["unit"], spec["moves"], spec["source"]) == (m["layer"], m["unit"], m["moves"], m["source"])
        assert CELL in m["workloads"] and hasattr(plugins.load_module("reducers", spec["reducer"]), "reduce")
    listed = {"compiles_in_window", "table_decode_cpu_ms", "combined_segments_per_query"}
    assert listed <= set(per_layer) and all(per_layer[n]["workloads"][-1] == CELL for n in listed)
    assert {m["name"] for m in cell["end_to_end"]} == {"throughput_qps", "latency_p50_ms", "latency_p95_ms", "setup_s"}
    # the dense kernel's, the scatter's and the star-tree's metrics do not list the cell
    assert not {"scan_roofline", "scan_kernel_ms", "startree_roofline", "servers_per_query"} & set(per_layer)
    for t in cell["query_set"]["templates"].values():  # the file's own literals name every parameter
        assert set(t["ssb"]) == set(t["params"]) and templates.draw_params(t, np.random.default_rng(1)).keys() == t["ssb"].keys()
        for _ in range(50):
            p = templates.draw_params(t, np.random.default_rng())
            assert all(v >= 0 for v in p.values()) and p.get("lo", 0) <= 1000 and p.get("nlo", 0) <= 50 and p.get("v8", 0) <= 1010
