"""The time-ordered table and its metrics (PR 47): the generator
(lib/datagen/ssb_flat_bydate.py) cuts the calendar into equal slices, a
segment a slice, rows in day order, every other column `ssb_flat`'s draw and
the date attributes those of the day; the count of what a request NEEDS
(lib/prunecount.py) reads the rows whose day satisfies the request's date
terms from the calendar and agrees with a count over the generator's blocks;
the share of the roofline (lib/reducers/bydate_roofline_share.py) reads 100 %
exactly when the device was busy for the least time, less whenever it was
busy longer, and the CONTROL, the same requests counted at every row of the
table (lib/opcount.py: right for the cells that prune nothing), reads several
times higher; the four span readers of the cell read what recorded spans
hold and nothing from a program without the names.  Run by hand:
`python -m pytest benchmarks/tests -q`.
"""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import harness, loadgen, opcount, plugins, prunecount, templates  # noqa: E402
from lib.datagen import ssb_flat, ssb_flat_bydate  # noqa: E402
from lib.references import filter_group_sum  # noqa: E402

CELL = "ssb_sf10_bydate.dashboard_closed"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "name": "TPU v5e"}
NEW = ["segments_pruned_per_query", "prune_ms", "doc_range_segments_per_query", "launch_param_bytes_per_query",
       "bydate_roofline"]


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


def _small(cell, segments=40, rows=4_000):
    return dict(cell["config"], rows=segments * rows, segment_rows=rows)


def _blocks(config, seed):
    n = ssb_flat_bydate.num_segments(config)
    return [ssb_flat_bydate.make_segment(config, seed, i, int(config["segment_rows"])) for i in range(n)]


def test_the_cell_is_what_issue_47_names(cell):
    assert (cell["cell"]["config"], cell["cell"]["traffic"], cell["cell"]["chips"]) == ("ssb_flat_sf10_bydate", "bydate_closed", 1)
    cfg, mix = cell["config"], cell["mix"]
    assert (cfg["rows"], cfg["segment_rows"], cfg["servers"], cfg["replication"], cfg["packed_codes"]) == (60_000_000, 1_500_000, 1, 1, True)
    assert cfg["table_config"]["sortedColumn"] == "lo_orderdate"
    assert cfg["table_config"]["invertedIndexColumns"] == ["lo_discount", "lo_quantity", "c_region", "s_region", "p_mfgr", "p_category"]
    sf10 = plugins.load_json("configs", "ssb_flat_sf10")
    assert cfg["columns"][:-1] == [dict(c, distribution=mine["distribution"]) for c, mine in zip(sf10["columns"], cfg["columns"])]
    assert (cfg["columns"][-1]["name"], cfg["columns"][-1]["cardinality"]) == ("lo_orderdate", 2556)
    assert {k: cfg["guarantees"][k] for k in sf10["guarantees"] if k != "complete"} == {
        k: v for k, v in sf10["guarantees"].items() if k != "complete"}
    assert (mix["loop"], mix["clients"], mix["templates"], mix["sample_checked"], mix["rolling_start_s"]) == (
        "closed", 4, ["q1_1", "q1_2", "q1_3", "q4_2", "rev_by_day"], 40, 3.0)
    flight = plugins.load_json("queries", "ssb_flat")["templates"]
    assert all(cell["query_set"]["templates"][t] == flight[t] for t in ("q1_1", "q1_2", "q1_3", "q4_2"))
    assert {m["name"] for m in cell["end_to_end"]} == {"latency_p50_ms", "latency_p95_ms", "setup_s"}  # PERF.md section 7
    per_layer = {m["name"]: m for m in cell["per_layer"]}
    assert set(NEW) <= set(per_layer) and all(per_layer[n]["workloads"] == [CELL] for n in NEW)
    assert "scan_kernel_ms" not in per_layer and "scan_roofline" not in per_layer and "sparse_scatter_ms" not in per_layer
    for name in NEW:
        spec = plugins.load_json("layer_metrics", name)
        assert (spec["name"], spec["layer"], spec["unit"], spec["moves"], spec["source"]) == tuple(
            per_layer[name][k] for k in ("name", "layer", "unit", "moves", "source"))
        assert hasattr(plugins.load_module("reducers", spec["reducer"]), "reduce")


def test_a_segment_is_a_slice_of_the_calendar_in_day_order(cell):
    config = _small(cell)
    cal = ssb_flat_bydate.calendar()
    assert len(np.unique(cal["lo_orderdate"])) == ssb_flat.DAYS and np.all(np.diff(cal["lo_orderdate"]) > 0)
    assert (cal["lo_orderdate"][0], cal["lo_orderdate"][59], cal["lo_orderdate"][-1]) == (19920101, 19920229, 19981230)
    blocks = _blocks(config, 2**31 + 11)
    again = _blocks(config, 2**31 + 11)
    assert all(np.array_equal(a[k], b[k]) for a, b in zip(blocks, again) for k in a)
    assert set(blocks[0]) == {c["name"] for c in config["columns"]}
    day_of = {int(d): i for i, d in enumerate(cal["lo_orderdate"])}
    for i, b in enumerate(blocks):
        lo, hi = ssb_flat_bydate.segment_slice(config, i)
        days = np.asarray([day_of[int(d)] for d in b["lo_orderdate"]])
        assert np.all(np.diff(days) >= 0)  # the table's order: a segment is sorted by day
        assert days.min() >= int(np.floor(lo)) and days.max() <= min(int(np.ceil(hi)) - 1, ssb_flat.DAYS - 1)
        for name, per_day in cal.items():  # a day determines its attributes
            assert np.array_equal(b[name], per_day[days]), name
        # the other columns are ssb_flat's ranges and hierarchy
        assert np.array_equal(b["c_nation"], b["c_city"] // 10) and np.array_equal(b["p_category"], b["p_brand1"] // 40)
        assert b["lo_quantity"].min() >= 1 and b["lo_quantity"].max() <= 50 and b["lo_discount"].max() <= 10
    # a boundary day is shared by its two segments; over the table a day holds rows / 2556 rows
    assert int(blocks[0]["lo_orderdate"][-1]) == int(blocks[1]["lo_orderdate"][0])
    whole = np.concatenate([b["lo_orderdate"] for b in blocks])
    per_day = np.unique(whole, return_counts=True)[1]
    assert len(per_day) == ssb_flat.DAYS and abs(per_day.mean() - config["rows"] / ssb_flat.DAYS) < 1e-9
    assert per_day.std() < 3 * np.sqrt(per_day.mean())  # uniform over the calendar, as ssb_flat's days are
    # the rehearsal's four segments are the same table in small
    four = dict(config, rows=16_000, segment_rows=4_000)
    assert [ssb_flat_bydate.segment_slice(four, i) for i in range(4)] == [(i * 639.0, (i + 1) * 639.0) for i in range(4)]


def test_the_survivors_are_the_issues_counts(cell):
    """Segments left after pruning, from the calendar over each template's
    domain at 40 segments: 6.57, 1.44, 1.27, 12.33 and 40."""
    cal = ssb_flat_bydate.calendar()
    config = cell["config"]

    def left(ref, params):
        terms = [dict(ref, where=[t]) for t in ref["where"] if t[0] in cal]  # each term prunes apart
        shares = [prunecount.segment_shares(config, prunecount.matching_days(one, params, cal)) for one in terms]
        return sum(all(s[i] > 0.0 for s in shares) for i in range(40))

    tpl = cell["query_set"]["templates"]
    q11 = [left(tpl["q1_1"]["reference"], {"year": y, "dlo": 1, "dhi": 3, "qty": 25}) for y in range(1992, 1999)]
    q12 = [left(tpl["q1_2"]["reference"], {"ym": y * 100 + m, "dlo": 1, "dhi": 3, "qlo": 1, "qhi": 10})
           for y in range(1992, 1999) for m in range(1, 13)]
    q13 = [left(tpl["q1_3"]["reference"], {"week": w, "year": y, "dlo": 1, "dhi": 3, "qlo": 1, "qhi": 10})
           for y in range(1992, 1999) for w in range(1, 53)]
    q42 = [left(tpl["q4_2"]["reference"], {"region": 1, "ya": y, "yb": y + 1, "ma": 0, "mb": 1}) for y in range(1992, 1998)]
    assert (min(q11), max(q11), round(np.mean(q11), 2)) == (6, 7, 6.57)
    assert (min(q12), max(q12), round(np.mean(q12), 2)) == (1, 2, 1.44)
    assert (min(q13), max(q13), round(np.mean(q13), 2)) == (1, 3, 1.27)
    assert (min(q42), max(q42), round(np.mean(q42), 2)) == (12, 13, 12.33)
    assert left(tpl["rev_by_day"]["reference"], {"region": 1, "cat": 1}) == 40
    assert left(tpl["q1_2"]["reference"], dict(tpl["q1_2"]["ssb"])) == 1 and left(tpl["q1_3"]["reference"], dict(tpl["q1_3"]["ssb"])) == 3


@pytest.mark.parametrize("name", ["q1_1", "q1_2", "q1_3", "q4_2", "rev_by_day"])
def test_the_count_agrees_with_a_count_over_the_blocks(cell, name):
    """prunecount's rows (expected, from the calendar) against the rows of the
    generator's blocks whose day satisfies the request's date terms."""
    config = _small(cell, rows=20_000)
    blocks = _blocks(config, 2**31 + 29)
    cal = ssb_flat_bydate.calendar()
    tpl = cell["query_set"]["templates"][name]
    ref = tpl["reference"]
    rng = np.random.default_rng(5)
    for params in [dict(tpl["ssb"])] + [templates.draw_params(tpl, rng) for _ in range(6)]:
        needs = prunecount.query_needs(config, tpl, params)
        counted = 0
        for b in blocks:
            mask = np.ones(len(b["lo_orderdate"]), bool)
            for t in ref["where"]:
                if t[0] in cal:
                    mask &= filter_group_sum._mask(b[t[0]], t[1], [params[p] for p in t[2:]])
            counted += int(mask.sum())
        assert counted > 0
        assert abs(needs["rows"] - counted) <= 4 * np.sqrt(counted) + 1, (name, params, needs["rows"], counted)
        widths = opcount.column_bytes_per_row(config)
        named = {t[0] for t in ref["where"]} | set(ref["group_by"]) | set(ref["sum"][1:])
        # a date attribute rides in its segment's own lane: never wider than the configured one
        assert needs["bytes"] <= needs["rows"] * sum(widths[c] for c in named) + 8.0 * tpl["group_space"] + 1e-6
        assert needs["bytes"] >= needs["rows"] * sum(min(widths[c], 0.5) if c in cal else widths[c] for c in named)
    if name == "rev_by_day":
        assert needs["rows"] == config["rows"]  # no date term: every row


def _requests(cell, per_template=6, seed=9):
    rng = np.random.default_rng(seed)
    reqs = []
    for name in cell["mix"]["templates"]:
        tpl = cell["query_set"]["templates"][name]
        for _ in range(per_template):
            reqs.append(loadgen.Request(len(reqs), 0, name, templates.draw_params(tpl, rng), 0.0))
    return reqs


def test_the_share_is_100_at_the_least_time_and_the_tables_rows_read_far_higher(cell, capsys):
    reducer = plugins.load_module("reducers", "bydate_roofline_share")
    spec = plugins.load_json("layer_metrics", "bydate_roofline")
    reqs = _requests(cell)
    weights = {"q1_1": 3.0, "q1_2": 2.5, "q1_3": 2.5, "q4_2": 3.0, "rev_by_day": 2.0}

    def ctx(busy_s):
        return {"config": cell["config"], "query_set": cell["query_set"], "peak": PEAK, "requests": reqs,
                "device_trace": {"busy_s": busy_s, "window_s": 3.0, "template_weights": weights,
                                 "queries_in_trace": sum(weights.values())}}

    def least(needs_of):
        total = 0.0
        for name, w in weights.items():
            mine = [r for r in reqs if r.template == name]
            total += w * np.mean([opcount.least_seconds(needs_of(name, r), PEAK)[0] for r in mine])
        return total

    tpl = cell["query_set"]["templates"]
    pruned = least(lambda name, r: prunecount.query_needs(cell["config"], tpl[name], r.params))
    whole = least(lambda name, r: opcount.query_needs(cell["config"], tpl[name]))
    assert reducer.reduce(spec, ctx(pruned)) == pytest.approx(100.0)
    assert reducer.reduce(spec, ctx(3 * pruned)) == pytest.approx(100.0 / 3)
    assert '"phase": "roofline"' in capsys.readouterr().out
    assert reducer.reduce(spec, ctx(0.0)) is None and reducer.reduce(spec, dict(ctx(1.0), device_trace=None)) is None
    # the control: every row of the table for every request, as scan_roofline's count would have it
    assert 2.0 < whole / pruned < 7.0
    q11 = [r for r in reqs if r.template == "q1_1"][0]
    a_year = prunecount.query_needs(cell["config"], tpl["q1_1"], q11.params)["rows"]
    assert 60_000_000 / 7.1 < a_year < 60_000_000 / 6.9


def test_the_span_readers_read_recorded_spans_and_nothing_from_a_program_without_the_names(cell):
    def tree(pruned, doc_ranges, param_bytes, prune_ms):
        attrs = {} if doc_ranges is None else {"docRangeSegments": doc_ranges}
        calls = [{"name": "launch_enqueue", "ms": 1.0, "attrs": {} if param_bytes is None else {"paramBytes": b}}
                 for b in (param_bytes or [0])]
        kids = ([] if prune_ms is None else [{"name": "prune", "ms": prune_ms, "attrs": {"segments": 40, "pruned": pruned}}]) + calls
        server = {"name": "server:server0", "ms": 9.0, "attrs": {"segmentsPruned": pruned},
                  "children": [{"name": "dispatch", "ms": 5.0, "attrs": attrs, "children": kids}]}
        return {"name": "query", "ms": 10.0, "attrs": {}, "children": [
            {"name": "prune", "ms": 0.25, "attrs": {"table": "lineorder_flat"}},
            {"name": "scatter", "ms": 9.5, "attrs": {}, "children": [{"name": "server_execute", "ms": 9.2, "children": [server]}]}]}

    def value(name, trees):
        reqs = [loadgen.Request(i, 0, "q1_1", {}, 0.0, spans=t) for i, t in enumerate(trees)]
        return harness.metric_value("layer_metrics", name, {"requests": reqs})

    change = [tree(33, 7, [96, 48, 24], 0.5), tree(0, 0, [384] * 5, 0.75)]
    assert value("segments_pruned_per_query", change) == pytest.approx(16.5)
    assert value("doc_range_segments_per_query", change) == pytest.approx(3.5)
    assert value("launch_param_bytes_per_query", change) == pytest.approx((168 + 1920) / 2)
    assert value("prune_ms", change) == pytest.approx((0.75 + 1.0) / 2)  # the broker's span and the server's
    parent = [tree(33, None, None, None), tree(0, None, None, None)]
    assert value("segments_pruned_per_query", parent) == pytest.approx(16.5)  # the parent's root has the attr
    assert value("doc_range_segments_per_query", parent) is None and value("launch_param_bytes_per_query", parent) is None
    assert value("prune_ms", parent) == pytest.approx(0.25)  # the broker's alone
