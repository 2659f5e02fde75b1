#!/usr/bin/env python3
"""Sandbox self-test of the yardstick (CPU; no speed number comes from here).

  python benchmarks/selftest.py          generator, reference vs sqlite, trace
                                         reduction, refusals, BENCHMARK.json
  python benchmarks/selftest.py --aot    also compile every timed template's
                                         per-segment plan for a described
                                         v5e:2x2 at 1.5M rows (minutes)
"""
import json
import os
import sqlite3
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from lib import controls, plugins, templates  # noqa: E402

DONE = []


def need(cond, what):
    if not cond:
        raise SystemExit(f"selftest FAILED: {what}")
    DONE.append(what)


def small_config(name="ssb_flat_sf1", rows=10_000):
    cfg = plugins.load_json("configs", name)
    return dict(cfg, rows=rows, segment_rows=rows // 4)


def blocks_of(cfg, seed):
    gen = plugins.load_module("datagen", cfg["datagen"])
    n = -(-cfg["rows"] // cfg["segment_rows"])
    return [gen.make_segment(cfg, seed, i, min(cfg["segment_rows"], cfg["rows"] - i * cfg["segment_rows"]))
            for i in range(n)]


def test_generator():
    cfg = small_config()
    a, b, c = blocks_of(cfg, 2**31 + 7), blocks_of(cfg, 2**31 + 7), blocks_of(cfg, 8)
    need(all(np.array_equal(x[k], y[k]) for x, y in zip(a, b) for k in x), "generator: same seed, same table")
    need(any(not np.array_equal(x[k], y[k]) for x, y in zip(a, c) for k in x), "generator: another seed, another table")
    need(not np.array_equal(a[0]["c_city"], a[1]["c_city"]), "generator: segments differ")
    t = {k: np.concatenate([blk[k] for blk in a]) for k in a[0]}
    nr = np.asarray(cfg["hierarchy"]["nation_region"])
    need(np.array_equal(t["c_nation"], t["c_city"] // 10) and np.array_equal(t["c_region"], nr[t["c_nation"]])
         and np.array_equal(t["s_region"], nr[t["s_city"] // 10]), "hierarchy: city -> nation -> region")
    need(np.array_equal(t["p_category"], t["p_brand1"] // 40) and np.array_equal(t["p_mfgr"], t["p_category"] // 5),
         "hierarchy: brand -> category -> manufacturer")
    need(np.array_equal(t["d_yearmonthnum"] // 100, t["d_year"])
         and np.array_equal(t["d_yearmonth"], (t["d_year"] - 1992) * 12 + t["d_yearmonthnum"] % 100 - 1),
         "hierarchy: day -> month -> year")
    declared = {c["name"]: c for c in cfg["columns"]}
    need(set(declared) == set(t), "generator makes exactly the configuration's columns")
    big = blocks_of(small_config(rows=400_000), 1)
    tb = {k: np.concatenate([blk[k] for blk in big]) for k in big[0]}
    need(all(len(np.unique(tb[n])) == c["cardinality"] for n, c in declared.items() if "cardinality" in c),
         "every dimension reaches its declared cardinality")


def test_reference_against_sqlite():
    cfg = small_config()
    blocks = blocks_of(cfg, 5)
    qs = plugins.load_json("queries", cfg["query_set"])
    names = [c["name"] for c in cfg["columns"]]
    db = sqlite3.connect(":memory:")
    db.execute(f"CREATE TABLE {qs['table']} ({', '.join(n + ' INTEGER' for n in names)})")
    for blk in blocks:
        db.executemany(f"INSERT INTO {qs['table']} VALUES ({', '.join('?' * len(names))})",
                       zip(*[blk[n].tolist() for n in names]))
    rng = np.random.default_rng(17)
    caught = {k: 0 for k in controls.CONTROLS}
    for name, t in qs["templates"].items():
        mod = plugins.load_module("references", t["reference"]["kind"])
        for params in [t["ssb"]] + [templates.draw_params(t, rng) for _ in range(3)]:
            cur = db.execute(templates.render(t, params))
            cols = [d[0] for d in cur.description]
            rows = [list(r) for r in cur.fetchall()]
            # sqlite names an aggregate by its text; the program by its own
            # rule; compare() needs only the group columns' names
            ref = mod.answer(t["reference"], params, blocks)
            if "scalar" in ref and rows[0][0] is None:
                rows = [[0]]
            ok, numbers = mod.compare(t["reference"], cols, rows, ref)
            need(ok, f"reference == sqlite: {name} {params} {numbers}")
            for cname, fn in controls.CONTROLS.items():
                bad, _ = mod.compare(t["reference"], cols, rows, fn(mod, t["reference"], params, blocks))
                caught[cname] += (not bad)
    need(all(v > 0 for v in caught.values()), f"each control differs from sqlite somewhere: {caught}")


def test_params_keep_their_domain():
    qs = plugins.load_json("queries", "ssb_flat")
    rng = np.random.default_rng(3)
    for name, t in qs["templates"].items():
        need(templates.draw_params(t, np.random.default_rng(1)) == templates.draw_params(t, np.random.default_rng(1)),
             f"{name}: parameters repeat with the seed")
        for _ in range(200):
            p = templates.draw_params(t, rng)
            for k in ("dhi", "qhi", "bhi", "cb", "mb", "yb", "yhi"):
                if k in p:
                    need(p[k] <= {"dhi": 10, "qhi": 50, "bhi": 999, "cb": 249, "mb": 4, "yb": 1998, "yhi": 1998}[k],
                         f"{name}: {k} stays inside its column's domain")
            if "blo" in p:
                need(p["blo"] // 40 == p["bhi"] // 40, f"{name}: the brand range stays inside one category")
            if "ca" in p:
                need(p["ca"] // 10 == p["cb"] // 10 and p["ca"] != p["cb"], f"{name}: two cities of one nation")
        need(set(t["ssb"]) == set(t["params"]), f"{name}: published literals name every parameter")


def test_schedule():
    from lib import loadgen

    mix = plugins.load_json("traffic", "mixed_open")
    a, b = loadgen.plan_open(mix, 1, 20.0), loadgen.plan_open(mix, 2**31 + 5, 20.0)
    need(len(a) == len(b), "open loop: every seed offers the same number of requests")
    count = lambda plan: sorted((n, sum(1 for s in plan if s["template"] == n)) for n in mix["weights"])  # noqa: E731
    need(count(a) == count(b), "open loop: every seed offers the same count of each template")
    need(a == b, "open loop: every seed offers the same schedule")
    need(abs(len(a) / 20.0 - mix["rate_qps"]) < 0.2 * mix["rate_qps"], "open loop: the offered rate is the mix's")
    c = loadgen.plan_closed(plugins.load_json("traffic", "groupby_closed"), 5)
    need(len(set(c["offsets"])) == c["clients"], "closed loop: clients start on different templates")


def test_reconnect():
    """A connection the kernel dropped is made again, once, and counted; a second drop is a failed request."""
    import time

    from lib import loadgen

    t = plugins.load_json("queries", "ssb_flat")["templates"]["q1_1"]
    answer = {"status": 200, "body": {"resultTable": {"dataSchema": {"columnNames": ["s"]}, "rows": [[1]]}}}
    real, calls = loadgen.post, []

    def flaky(url, sql, drops):
        calls.append(sql)
        if len(calls) <= drops:
            raise ConnectionResetError(104, "Connection reset by peer")
        return answer

    try:
        for drops, want in ((1, (200, None, 1)), (2, (0, "ConnectionResetError", 1))):
            calls.clear()
            loadgen.post = lambda url, sql: flaky(url, sql, drops)  # noqa: B023
            req = loadgen.Request(0, 0, "q1_1", dict(t["ssb"]), 0.0)
            loadgen.send("nowhere", req, t, False, time.perf_counter())
            got = (req.status, req.error and req.error.split(":")[0], req.reconnects)
            need(got == want and len(calls) == 2, f"reconnect once after {drops} drop(s): {got}")
    finally:
        loadgen.post = real


def test_refusals():
    import subprocess

    from lib import harness

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(HERE, "run.py"), "--workload", "ssb_sf1.mixed_open",
                        "--seed", "1", "--seconds", "1", "--trace", "0"], capture_output=True, text=True, env=env,
                       cwd=harness.REPO)
    need(p.returncode != 0 and '"correct"' not in p.stdout, "no TPU: non-zero exit, no result line")
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v9 imaginary"

        def memory_stats(self):
            return {}

    real = jax.devices
    jax.devices = lambda *a: [Fake()]
    try:
        try:
            harness.find_devices(1, rehearse=False)
            refused = False
        except harness.Refusal as e:
            refused = "peaks.json" in str(e)
    finally:
        jax.devices = real
    need(refused, "a device kind that peaks.json lacks is refused")
    try:
        harness.load_cell("no_such_cell")
        refused = False
    except harness.Refusal:
        refused = True
    need(refused, "an unknown workload is refused")


def test_benchmark_json():
    from lib import harness

    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the contract's static rules, so that a later PR's entry is refused here and not by the driver
    import re
    import subprocess

    name_ok = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$").match
    unit_ok = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$").match
    line_ok = lambda t: 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t  # noqa: E731
    need(set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
         "BENCHMARK.json has exactly the contract's keys")
    need(isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51, "run_seconds is a whole number <= 51")
    for c in bench["configs"]:
        need(set(c) == {"name", "source", "file", "reduced", "why"} and name_ok(c["name"]) and line_ok(c["source"])
             and line_ok(c["why"]) and all(name_ok(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
             and c["file"].startswith(tuple(p + "/" for p in bench["paths"])), f"config entry {c['name']}")
        with open(os.path.join(harness.REPO, c["file"])) as f:
            held = json.load(f)
        need(held["source"] == c["source"] and held["reduced"] == c["reduced"], f"config file of {c['name']} agrees")
    need(len({c["source"] for c in bench["configs"]}) == len(bench["configs"]), "configurations' sources differ")
    for w in bench["workloads"]:
        need(set(w) == {"name", "config", "traffic", "chips", "why"} and name_ok(w["name"]) and name_ok(w["traffic"])
             and w["chips"] in (1, 4) and line_ok(w["why"]), f"workload entry {w['name']}")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        need(set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"} and name_ok(m["name"])
             and unit_ok(m["unit"]) and m["better"] in ("lower", "higher") and 0.01 <= m["bound"] <= 0.25
             and m["source"] in ("host_clock", "device_trace") and set(m.get("workloads", cells)) <= cells,
             f"end-to-end entry {m['name']}")
    for m in bench["per_layer"]:
        need(set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"} and name_ok(m["name"])
             and unit_ok(m["unit"]) and m["better"] in ("lower", "higher") and line_ok(m["layer"])
             and m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
             and set(m.get("workloads", cells)) <= cells, f"per-layer entry {m['name']}")
        mover = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        need(set(m.get("workloads", cells)) <= set(mover.get("workloads", cells)),
             f"{m['name']}: every cell that reports it reports {m['moves']}")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    need(len(names) == len(set(names)), "no two metrics share a name")
    tracked = subprocess.run(["git", "ls-files", "-co", "--exclude-standard", "benchmarks"], cwd=harness.REPO,
                             capture_output=True, text=True).stdout.split()
    need(all(re.match(r"^[A-Za-z0-9_.\-/]+$", f) for f in tracked), "files under paths are named from a name's characters")
    with open(os.path.join(harness.REPO, "BENCHMARK.json"), "rb") as f:
        need(len(f.read()) <= 64 * 1024, "BENCHMARK.json is at most 64 KiB")

    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        spec = plugins.load_json("end_to_end", m["name"])
        need(spec["unit"] == m["unit"] and spec["source"] == m["source"],
             f"end-to-end metric {m['name']}: BENCHMARK.json and its file agree")
        plugins.load_module("reducers", spec["reducer"])
    layers = set()
    for m in bench["per_layer"]:
        spec = plugins.load_json("layer_metrics", m["name"])
        need(m["moves"] in e2e and spec["moves"] == m["moves"] and spec["layer"] == m["layer"]
             and spec["unit"] == m["unit"] and spec["source"] == m["source"],
             f"per-layer metric {m['name']}: BENCHMARK.json and its file agree")
        plugins.load_module("reducers", spec["reducer"])
        layers.add(m["layer"])
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        names = harness.cell_templates(cell["mix"])
        need(all(n in cell["query_set"]["templates"] for n in names), f"cell {w['name']}: its templates exist")
        need(any(m["name"] == "setup_s" for m in cell["end_to_end"]) and len(cell["end_to_end"]) >= 2
             and len(cell["per_layer"]) >= 1, f"cell {w['name']}: reports setup_s, another end-to-end metric, a layer")


def test_trace_reduction():
    from lib import tracered

    path = os.path.join(HERE, "testdata", "served_v5e.xplane.pb.gz")
    with open(os.path.join(HERE, "testdata", "served_v5e.expected.json")) as f:
        want = json.load(f)
    got = tracered.reduce_file(path)  # the profiler rounds to nanoseconds, the recorded reading keeps picoseconds
    need(abs(got["busy_s"] - want["busy_s"]) < 5e-6 and abs(got["span_s"] - want["span_s"]) < 5e-6
         and sum(c for c, _ in got["events"].values()) == want["n_events"],
         "trace reduction: busy union and span of the recorded trace")
    need(got["busy_s"] <= got["span_s"], "busy time cannot pass the traced span")
    need({k: v[0] for k, v in got["events"].items()} == {k: v[0] for k, v in want["events"].items()},
         "trace reduction: per-event counts of the recorded trace")
    # the union is a union: two overlapping intervals count once
    need(abs(tracered.union_seconds([(0, 10), (5, 20), (30, 40)]) - 30e-9) < 1e-15, "interval union")
    need(tracered.gaps([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30), (40, 50)], "idle gaps")


def test_kernel_reducers():
    """A kernel's metrics count only the queries whose plan got the kernel."""
    from lib import opcount
    from lib.reducers import device_event_ms_per_query, roofline_share

    cfg = plugins.load_json("configs", "ssb_flat_sf1")
    qs = plugins.load_json("queries", cfg["query_set"])
    peak = {"name": "test", "hbm_bytes_per_s": 1e9, "flops_per_s": 1e15}
    kernel = "%kernel.1 = s32[1,4,112,64]{3,2,1,0} custom-call(s32[1]{0} %p)"
    ctx = {
        "config": cfg, "query_set": qs, "peak": peak,
        "warm_moved": {"q1_1": {"scan.traced.xla": 1.0}, "q2_1": {"scan.traced.pallas": 1.0}},
        "device_trace": {"events": {kernel: (4, 0.5), "%copy.1 = s32[8]{0} copy(s32[8]{0} %p)": (4, 0.25)},
                         "template_weights": {"q1_1": 6.0, "q2_1": 2.0}, "queries_in_trace": 8.0},
    }
    spec = dict(plugins.load_json("layer_metrics", "scan_roofline"), name="x")  # the pattern the cells use
    need(device_event_ms_per_query.reduce(spec, ctx) == 250.0, "kernel ms per query: divided by the kernel's queries only")
    want = 100.0 * 2.0 * opcount.query_needs(cfg, qs["templates"]["q2_1"])["bytes"] / 1e9 / 0.5
    need(abs(roofline_share.reduce(spec, ctx) - want) < 1e-9, "roofline share: bytes of the kernel's queries only")
    del spec["served_by_counter"]
    need(device_event_ms_per_query.reduce(spec, ctx) == 62.5, "without the key every traced query counts")
    ctx["warm_moved"] = {}
    spec["served_by_counter"] = "scan.traced.pallas"
    need(roofline_share.reduce(spec, ctx) is None, "no template got the kernel: nothing to read")


def aot_compile():
    """Every timed template's per-segment plan, compiled for a described
    v5e at 1.5M rows with the chip's own choices (Pallas scan, chunked32)."""
    import time

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    from lib import harness

    sys.path.insert(0, harness.REPO)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import pinot_tpu  # noqa: F401
    from pinot_tpu import ops
    from pinot_tpu.ops import segmented
    from pinot_tpu.query import planner
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.config import IndexingConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    # the planner asks jax.default_backend(), which says cpu here
    ops.scan_backend = lambda: "pallas"
    ops.accum_policy = segmented.accum_policy = lambda: "chunked32"
    cfg = plugins.load_json("configs", "ssb_flat_sf10")
    qs = plugins.load_json("queries", cfg["query_set"])
    schema = Schema(cfg["table"], [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]])
                                   for c in cfg["columns"]])
    tcfg = TableConfig(cfg["table"], indexing=IndexingConfig.from_dict(cfg["table_config"]))
    block = plugins.load_module("datagen", cfg["datagen"]).make_segment(cfg, 1, 0, cfg["segment_rows"])
    seg = build_segment(schema, {k: v.astype(np.int32) for k, v in block.items()}, "seg0", table_config=tcfg)
    timed = sorted({n for f in os.listdir(os.path.join(HERE, "traffic"))
                    for n in harness.cell_templates(plugins.load_json("traffic", f[:-5]))})
    for name in timed:
        t = qs["templates"][name]
        plan = planner.plan_segment(parse_query(templates.render(t, t["ssb"])), seg)
        cols = seg.to_device(device=jax.devices()[0], columns=plan.needed_columns, packed_codes=True)
        params = {k: jax.device_put(v) for k, v in plan.params.items()}
        described = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), (cols, params))
        t0 = time.time()
        text = plan.fn.lower(*described).compile().as_text()
        print(json.dumps({"aot": name, "kind": plan.kind, "kernel": "pallas" if "tpu_custom_call" in text else "xla",
                          "compile_s": round(time.time() - t0, 1)}), flush=True)
        DONE.append(f"AOT compile for v5e: {name}")


if __name__ == "__main__":
    test_generator()
    test_params_keep_their_domain()
    test_reference_against_sqlite()
    test_schedule()
    test_benchmark_json()
    test_trace_reduction()
    test_kernel_reducers()
    test_reconnect()
    test_refusals()
    if "--aot" in sys.argv:
        aot_compile()
    print(json.dumps({"selftest": "passed", "checks": len(DONE)}))
