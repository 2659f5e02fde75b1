"""What PR 43 adds to the benchmark: the configuration `ssb_flat_sf10_sketch`,
its cell, the generator `ssb_flat_custkey`, the plain reference
`filter_group_sketch`, the count of its needs (lib/sketchcount.py) and the
reducer `sketch_roofline_share`.  The reference is held to a brute-force loop
over a few hundred rows (Python sets, sorted lists, one register at a time);
its own sketches one step of precision lower are called not correct; a
customer determines its city and no two segments share a `lo_custkey`
dictionary; the count reads columns at their stored widths; the roofline
share reads 100 % exactly when the device was busy for the least time.  Run
by hand: `python -m pytest benchmarks/tests -q`.
"""
import math
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import harness, opcount, plugins, sketchcount  # noqa: E402
from lib.reducers import sketch_roofline_share  # noqa: E402
from lib.references import filter_group_sketch as ref  # noqa: E402

CELL = "ssb_sf10_sketch.sketch_closed"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "name": "TPU v5e"}
NEW = ["sketch_scatter_ms", "sketch_hash_ms", "sketch_roofline", "sketch_table_bytes_per_query", "sketch_final_cpu_ms"]


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


def _blocks(cell, rows=400, n=3, seed=2**31 + 43, customers=60):
    config = dict(cell["config"], customers=customers)
    gen = plugins.load_module("datagen", config["datagen"])
    return [gen.make_segment(config, seed, i, rows) for i in range(n)]


def test_the_cell_is_what_issue_43_names(cell):
    assert cell["cell"] == {"name": CELL, "config": "ssb_flat_sf10_sketch", "traffic": "sketch_closed", "chips": 1,
                            "why": cell["cell"]["why"]} and len(cell["cell"]["why"]) <= 200
    config, mix = cell["config"], cell["mix"]
    assert (config["rows"], config["segment_rows"], config["customers"], config["scale_factor"]) == (60_000_000, 1_500_000, 300_000, 10)
    assert [c["name"] for c in config["columns"]][-1] == "lo_custkey" and len(config["columns"]) == 19
    assert (mix["loop"], mix["clients"], mix["sample_checked"], mix["rolling_start_s"]) == ("closed", 2, 12, 3.0)
    assert mix["templates"] == ["hll_cust_year_nation", "p95_rev_year_nation", "hll_cust_sum_year_category"]
    assert {m["name"] for m in cell["end_to_end"]} == {"throughput_qps", "latency_p50_ms", "latency_p95_ms", "setup_s"}
    assert set(NEW) <= {m["name"] for m in cell["per_layer"]}
    sketch = config["guarantees"]["sketch"]
    assert sketch["DISTINCTCOUNTHLL"]["log2m"] == 12 and sketch["PERCENTILETDIGEST"]["bins"] == 2048


def test_a_customer_determines_its_city_and_every_segment_has_its_own_customers(cell):
    config = cell["config"]
    gen = plugins.load_module("datagen", config["datagen"])
    blocks = [gen.make_segment(config, 7, i, 20_000) for i in range(3)]
    city = gen.customer_cities(config, 7)
    region = np.asarray(config["hierarchy"]["nation_region"])
    for b in blocks:
        assert np.array_equal(b["c_city"], city[b["lo_custkey"]]) and np.array_equal(b["c_nation"], b["c_city"] // 10)
        assert np.array_equal(b["c_region"], region[b["c_nation"]]) and b["lo_custkey"].max() < config["customers"]
    assert len({tuple(np.unique(b["lo_custkey"])[:50]) for b in blocks}) == 3
    again = gen.make_segment(config, 7, 1, 20_000)
    assert all(np.array_equal(again[k], blocks[1][k]) for k in again)


def _fmix32(w):
    h = w & 0xFFFFFFFF
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    return h ^ (h >> 16)


def _brute(spec, params, blocks, table_range):
    """{group key: [(exact, sketch) an aggregate]} by Python loops over the rows."""
    rows = [dict(zip(b, vals)) for b in blocks for vals in zip(*(b[k].tolist() for k in b))]
    groups = {}
    for r in rows:
        if all((r[t[0]] == params[t[2]]) if t[1] == "eq" else (r[t[0]] in [params[x] for x in t[2:]]) for t in spec["where"]):
            groups.setdefault(tuple(r[g] for g in spec["group_by"]), []).append(r)
    out = {}
    for key, members in groups.items():
        cells = []
        for a in spec["aggs"]:
            vals = [m[a["col"]] for m in members]
            if a["fn"] == "sum":
                cells.append((sum(vals), sum(vals)))
            elif a["fn"] == "hll":
                m = 1 << a["log2m"]
                regs = [0] * m
                for v in set(vals):
                    h = _fmix32(v)
                    w = h >> a["log2m"]
                    regs[h & (m - 1)] = max(regs[h & (m - 1)], 32 - a["log2m"] + 1 - w.bit_length())
                zeros = regs.count(0)
                raw = 0.7213 / (1 + 1.079 / m) * m * m / sum(2.0 ** -r for r in regs)
                est = m * math.log(m / zeros) if raw <= 2.5 * m and zeros else raw
                cells.append((len(set(vals)), int(np.rint(est))))
            else:
                ordered = sorted(vals)
                exact = ordered[-(-a["rank"] * len(ordered) // 100) - 1]
                lo, hi = table_range[a["col"]]
                cells.append((exact, ref.histogram_percentile(np.asarray(vals), float(lo), float(hi), a["bins"], float(a["rank"]))))
        out[key] = cells
    return out


@pytest.mark.parametrize("name", ["hll_cust_year_nation", "p95_rev_year_nation", "hll_cust_sum_year_category"])
def test_the_reference_equals_a_brute_force_loop_and_calls_a_narrower_sketch_wrong(cell, name):
    template = cell["query_set"]["templates"][name]
    spec, params = template["reference"], template["ssb"]
    blocks = _blocks(cell)
    got = ref.answer(spec, params, blocks)
    table_range = {c: (min(int(b[c].min()) for b in blocks), max(int(b[c].max()) for b in blocks)) for c in ("lo_revenue",)}
    want = _brute(spec, params, blocks, table_range)
    n = len(spec["group_by"])
    assert got["groups"] == len(want) > 20 and [tuple(r[:n]) for r in got["rows"]] == sorted(want)
    for r in got["rows"]:
        for cell_, (exact, sketch) in zip(r[n:], want[tuple(r[:n])]):
            assert cell_["exact"] == exact and cell_["sketch"] == pytest.approx(sketch, rel=1e-12)
    columns, served = ref.served_from(got, spec)
    ok, numbers = ref.compare(spec, columns, served, got)
    assert ok and numbers["missing"] == numbers["extra"] == numbers["out_of_order"] == 0
    # a row lost from one group, a group lost, the rows out of order: each is seen
    assert not ref.compare(spec, columns, served[1:], got)[0]
    assert not ref.compare(spec, columns, served[::-1], got)[0]
    # one step of precision lower, over a table large enough for the sketches to differ
    blocks = _blocks(cell, rows=20_000, n=2, customers=4_000)
    full = ref.answer(spec, params, blocks)
    lower = {"log2m_less": 1} if "hll" in name else {"bins_divisor": 2}
    assert ref.compare(spec, *ref.served_from(full, spec), full)[0]
    assert not ref.compare(spec, *ref.served_from(ref.answer(spec, params, blocks, **lower), spec), full)[0]


def test_the_count_reads_columns_at_their_stored_widths_and_tables_once(cell):
    config, templates = cell["config"], cell["query_set"]["templates"]
    rows = 60_000_000
    hll = sketchcount.query_needs(config, templates["hll_cust_year_nation"])
    # s_region 4 bits, d_year 4, c_nation 8, lo_custkey 32 (300,000 values pass 16 bits); 175 x 4,096 one-byte registers
    assert hll["bytes_per_row"] == 0.5 + 0.5 + 1.0 + 4.0 and hll["table_bytes"] == 8 * 175 + 175 * 4096
    assert hll["bytes"] == rows * 6.0 + hll["table_bytes"] and hll["ops"] == rows * (1 + 4 + 13)
    pct = sketchcount.query_needs(config, templates["p95_rev_year_nation"])
    assert pct["bytes_per_row"] == 0.5 + 0.5 + 0.5 + 1.0 + 4.0 and pct["table_bytes"] == 8 * 175 + 4 * 175 * 2048
    both = sketchcount.query_needs(config, templates["hll_cust_sum_year_category"])
    assert both["bytes_per_row"] == 0.5 + 0.5 + 1.0 + 4.0 + 4.0 and both["ops"] == rows * (1 + 4 + 13 + 1)
    assert opcount.least_seconds(hll, PEAK)[1] == "hbm"


def test_the_roofline_share_is_100_when_the_device_was_busy_for_the_least_time(cell, capsys):
    weights = {"hll_cust_year_nation": 1.0, "p95_rev_year_nation": 0.5}
    least = sum(w * opcount.least_seconds(sketchcount.query_needs(cell["config"], cell["query_set"]["templates"][t]), PEAK)[0]
                for t, w in weights.items())
    ctx = {"config": cell["config"], "query_set": cell["query_set"], "peak": PEAK,
           "device_trace": {"busy_s": least, "template_weights": weights}}
    assert sketch_roofline_share.reduce({"name": "sketch_roofline"}, ctx) == pytest.approx(100.0)
    ctx["device_trace"]["busy_s"] = 4 * least
    assert sketch_roofline_share.reduce({"name": "sketch_roofline"}, ctx) == pytest.approx(25.0)
    assert sketch_roofline_share.reduce({"name": "sketch_roofline"}, dict(ctx, device_trace=None)) is None
    assert '"phase": "roofline"' in capsys.readouterr().out
