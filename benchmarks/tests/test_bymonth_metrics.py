"""The month-cut table and its metrics (PR 50): the generator
(lib/datagen/ssb_flat_bymonth.py) gives segment i of n the calendar's months
[i x 84 // n, (i + 1) x 84 // n) whole, rows in day order, and its OWN row
count: a multinomial draw of the table's rows over the segments' days from
the configuration's `cut_seed`, so the counts add up, differ, and repeat
whatever the run's seed; the count of what a request NEEDS (lib/monthcount.py)
reads the TRUE rows whose day satisfies the request's date terms and agrees
with a brute-force count over the generator's blocks; the share of the
roofline (lib/reducers/bymonth_roofline_share.py) reads 100 % exactly when
the device was busy for the least time and less when it was busy longer (a
padded row is busy time and no needed work); the two span readers of the cell
read what recorded spans hold and nothing from a program without the names.
Run by hand: `python -m pytest benchmarks/tests -q`.
"""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import harness, loadgen, monthcount, opcount, plugins, templates  # noqa: E402
from lib.datagen import ssb_flat_bydate, ssb_flat_bymonth  # noqa: E402
from lib.references import filter_group_sum  # noqa: E402

CELL = "ssb_sf10_bymonth.dashboard_closed"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "name": "TPU v5e"}
NEW = ["row_buckets_per_query", "padded_rows_per_query", "bymonth_roofline"]


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


def _small(cell, segments=84, rows=2_000):
    return dict(cell["config"], rows=segments * rows, segment_rows=rows)


def _blocks(config, seed):
    n = ssb_flat_bymonth.num_segments(config)
    return [ssb_flat_bymonth.make_segment(config, seed, i, int(config["segment_rows"])) for i in range(n)]


def test_the_cell_is_what_issue_50_names(cell):
    assert (cell["cell"]["config"], cell["cell"]["traffic"], cell["cell"]["chips"]) == ("ssb_flat_sf10_bymonth", "bydate_closed", 1)
    cfg = cell["config"]
    assert (cfg["rows"], cfg["segment_rows"], ssb_flat_bymonth.num_segments(cfg), cfg["datagen"]) == (
        60_000_000, 714_286, 84, "ssb_flat_bymonth")
    assert cell["mix"] == plugins.load_json("traffic", "bydate_closed")
    assert cell["query_set"] == plugins.load_json("queries", "ssb_flat_bydate")
    assert {m["name"] for m in cell["end_to_end"]} == {"latency_p50_ms", "latency_p95_ms", "setup_s"}  # PERF.md section 7
    per_layer = {m["name"]: m for m in cell["per_layer"]}
    assert set(NEW) <= set(per_layer) and all(per_layer[n]["workloads"] == [CELL] for n in NEW)
    assert "bydate_roofline" not in per_layer and "scan_roofline" not in per_layer
    for name in NEW:
        spec = plugins.load_json("layer_metrics", name)
        assert (spec["name"], spec["layer"], spec["unit"], spec["moves"], spec["source"]) == tuple(
            per_layer[name][k] for k in ("name", "layer", "unit", "moves", "source"))
        assert hasattr(plugins.load_module("reducers", spec["reducer"]), "reduce")


def test_the_counts_add_up_differ_and_repeat_across_seeds(cell):
    cfg = cell["config"]
    counts = ssb_flat_bymonth.segment_row_counts(cfg)
    assert len(counts) == 84 and sum(counts) == 60_000_000 and len(set(counts)) == 84  # no two alike
    assert 650_000 < min(counts) < 665_000 and 720_000 < max(counts) < 735_000
    days = np.diff(ssb_flat_bymonth.month_starts())
    assert sorted(set(days.tolist())) == [28, 29, 30, 31] and days.sum() == ssb_flat_bymonth.DAYS
    for d in (28, 29, 30, 31):  # ~657k, ~681k, ~704k, ~728k, +- ~0.8k
        mine = np.asarray([c for c, n in zip(counts, days) if n == d])
        assert abs(mine.mean() - 60_000_000 * d / 2556) < 1_500 and mine.std() < 2_000
    assert counts == ssb_flat_bymonth.segment_row_counts(dict(cfg))  # the cut is the configuration's
    assert counts != ssb_flat_bymonth.segment_row_counts(dict(cfg, cut_seed=cfg["cut_seed"] + 1))
    small = _small(cell)
    a, b = _blocks(small, 3), _blocks(small, 2147483999)
    assert [len(x["lo_orderdate"]) for x in a] == [len(x["lo_orderdate"]) for x in b] == ssb_flat_bymonth.segment_row_counts(small)
    assert any((x["lo_revenue"] != y["lo_revenue"]).any() for x, y in zip(a, b))  # the run's seed draws the values
    # the rehearsal's n = 4: 21 months each, the same table in small
    four = dict(cfg, rows=40_000, segment_rows=10_000)
    assert [ssb_flat_bymonth.segment_days(four, i) for i in range(4)] == [
        (int(ssb_flat_bymonth.month_starts()[21 * i]), int(ssb_flat_bymonth.month_starts()[21 * (i + 1)])) for i in range(4)]
    assert sum(ssb_flat_bymonth.segment_row_counts(four)) == 40_000


def test_a_segment_is_its_months_whole_in_day_order(cell):
    config = _small(cell)
    cal = ssb_flat_bydate.calendar()
    blocks = _blocks(config, 11)
    assert ssb_flat_bymonth.make_segment(config, 11, 5, 1)["lo_orderdate"].shape == blocks[5]["lo_orderdate"].shape  # the hint is not read
    for i, b in enumerate(blocks):
        assert (np.diff(b["lo_orderdate"]) >= 0).all()
        assert np.unique(b["d_yearmonth"]).tolist() == [i]  # a month each at n = 84
        day = np.searchsorted(cal["lo_orderdate"], b["lo_orderdate"])
        for name in ("d_year", "d_yearmonthnum", "d_weeknuminyear"):
            assert (b[name] == cal[name][day]).all()
    whole = np.concatenate([b["lo_orderdate"] for b in blocks])
    assert (np.diff(whole) >= 0).all() and np.unique(whole).size == ssb_flat_bymonth.DAYS


@pytest.mark.parametrize("name", ["q1_1", "q1_2", "q1_3", "q4_2", "rev_by_day"])
def test_the_count_agrees_with_a_brute_force_count_over_the_blocks(cell, name):
    config = _small(cell, rows=4_000)
    blocks = _blocks(config, 5)
    tpl = cell["query_set"]["templates"][name]
    ref = tpl["reference"]
    cal = ssb_flat_bydate.calendar()
    rng = np.random.default_rng(50)
    widths = opcount.column_bytes_per_row(config)
    named = {t[0] for t in ref["where"]} | set(ref["group_by"]) | set(ref["sum"][1:])
    for params in [dict(tpl["ssb"])] + [templates.draw_params(tpl, rng) for _ in range(5)]:
        needs = monthcount.query_needs(config, tpl, params)
        rows = byts = 0.0
        for b in blocks:
            mask = np.ones(len(b["lo_orderdate"]), bool)
            for test in ref["where"]:
                if test[0] in cal:
                    mask &= filter_group_sum._mask(b[test[0]], test[1], [params[p] for p in test[2:]])
            if not mask.any():
                continue
            mine = dict(widths, **{c: opcount.lane_bits(int(np.unique(b[c]).size)) / 8.0 for c in cal})
            rows += float(mask.sum())
            byts += float(mask.sum()) * sum(mine[c] for c in named)
        # the count is an expectation over the days of a segment: a week's rows of a 4,000-row month spread ~3 %
        assert needs["rows"] == pytest.approx(rows, rel=0.08, abs=200), (params, needs["rows"], rows)
        assert needs["bytes"] - 8.0 * tpl.get("group_space", 1) == pytest.approx(byts, rel=0.08, abs=2_000)
        assert needs["rows"] <= config["rows"]


def _ctx(cell, config, busy_for):
    """A recorded window of one request a template, the device busy `busy_for(least seconds)`."""
    reqs, least = [], 0.0
    for i, name in enumerate(cell["mix"]["templates"]):
        tpl = cell["query_set"]["templates"][name]
        reqs.append(loadgen.Request(i, 0, name, dict(tpl["ssb"]), 0.0))
        least += opcount.least_seconds(monthcount.query_needs(config, tpl, dict(tpl["ssb"])), PEAK)[0]
    return {
        "requests": reqs, "config": config, "query_set": cell["query_set"], "peak": PEAK,
        "device_trace": {"busy_s": busy_for(least), "template_weights": {r.template: 1.0 for r in reqs}},
    }


def test_the_share_is_100_at_the_least_time_and_padding_reads_lower(cell, capsys):
    spec = plugins.load_json("layer_metrics", "bymonth_roofline")
    reducer = plugins.load_module("reducers", spec["reducer"])
    config = cell["config"]
    assert reducer.reduce(spec, _ctx(cell, config, lambda least: least)) == pytest.approx(100.0)
    # a program that scans the padded rows too (one bound of 753,664 rows a segment) is busy 5.5 % longer at best
    counts = ssb_flat_bymonth.segment_row_counts(config)
    padded = reducer.reduce(spec, _ctx(cell, config, lambda least: least * 753_664 * 84 / sum(counts)))
    assert 94.0 < padded < 95.5
    assert reducer.reduce(spec, dict(_ctx(cell, config, lambda least: least), device_trace=None)) is None
    assert reducer.reduce(spec, _ctx(cell, config, lambda least: 0.0)) is None
    assert '"phase": "roofline"' in capsys.readouterr().out
    # what is counted is the TRUE rows: Q1.1's year is 12 of the 84 segments' own counts
    year = monthcount.query_needs(config, cell["query_set"]["templates"]["q1_1"], {"year": 1993, "dlo": 1, "dhi": 3, "qty": 25})
    assert year["rows"] == sum(counts[12:24])
    assert monthcount.query_needs(config, cell["query_set"]["templates"]["rev_by_day"],
                                  dict(cell["query_set"]["templates"]["rev_by_day"]["ssb"]))["rows"] == 60_000_000


def test_the_span_readers_read_recorded_spans_and_nothing_from_a_program_without_the_names(cell):
    def request(attrs):
        r = loadgen.Request(0, 0, "q1_1", {}, 0.0)
        r.spans = {"name": "query", "children": [{"name": "server:server0", "children": [
            {"name": "dispatch", "attrs": attrs, "children": []}]}]}
        return r

    buckets = plugins.load_json("layer_metrics", "row_buckets_per_query")
    padded = plugins.load_json("layer_metrics", "padded_rows_per_query")
    reduce = plugins.load_module("reducers", buckets["reducer"]).reduce
    ctx = {"requests": [request({"rowBuckets": 1, "rowsPadded": 300_000}), request({"rowBuckets": 3, "rowsPadded": 100_000})]}
    assert reduce(buckets, ctx) == 2.0 and reduce(padded, ctx) == 200_000.0
    parent = {"requests": [request({"launches": 12}), request({"launches": 1})]}  # a program before PR 50
    assert reduce(buckets, parent) is None and reduce(padded, parent) is None
