"""The check has been shown to fail on the replicated cell's own control
(run by hand: `python -m pytest benchmarks/tests -q`; the benchmark's own
runs never run this).

1. One segment counted twice — the reference in the program's place
   (lib/controls_replicated.py) — comes out not correct on every template of
   the cell's mix, and an answer that says it read one segment more than the
   table has faults the envelope.
2. A whole rehearsed run of `ssb_sf20_4srv.groupby_closed` comes out
   `correct: true`; the same run with the broker made to route one segment
   to both of its replicas comes out `correct: false`: every answer faults
   the envelope (an answer that does is not compared further; that the sums
   differ too is case 1 and, at the cell's own size on the chip,
   tools/control_replicated.py).  The run is a child process: `find_devices` hands a rehearsal
   `jax.devices()[:chips]`, and the CPU shows four devices only to a process
   started with XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""
import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import check, controls_replicated, harness, loadgen, plugins, templates  # noqa: E402

CELL = "ssb_sf20_4srv.groupby_closed"
ROWS = 400_000


@pytest.fixture(scope="module")
def table():
    cfg = dict(plugins.load_json("configs", "ssb_flat_sf20_4srv"), rows=ROWS, segment_rows=ROWS // 8)
    gen = plugins.load_module("datagen", cfg["datagen"])
    return cfg, [gen.make_segment(cfg, 2**31 + 26, i, ROWS // 8) for i in range(8)]


def _served(spec, answer):
    """A group-by answer laid out as the front door would return it."""
    cols = list(spec["group_by"]) + ["sum"]
    rows = [list(k) + [v] for k, v in answer["groups"].items()]
    sign = {"asc": 1, "desc": -1}
    return cols, sorted(rows, key=lambda r: tuple(sign[d] * r[cols.index(what)] for what, d in spec["order_by"]))


@pytest.mark.parametrize("name", harness.cell_templates(plugins.load_json("traffic", "groupby_closed")))
def test_one_segment_counted_twice_is_not_correct(table, name):
    cfg, blocks = table
    t = plugins.load_json("queries", cfg["query_set"])["templates"][name]
    spec = t["reference"]
    mod = plugins.load_module("references", spec["kind"])
    params = templates.draw_params(t, np.random.default_rng(26))
    exact = mod.answer(spec, params, blocks)
    assert mod.compare(spec, *_served(spec, exact), exact)[0], "the reference agrees with itself"
    twice = controls_replicated.one_segment_twice(mod, spec, params, blocks)
    ok, numbers = mod.compare(spec, *_served(spec, twice), exact)
    assert not ok and numbers["wrong_sums"] > 0, numbers


def test_an_answer_from_one_segment_too_many_faults_the_envelope():
    req = loadgen.Request(0, 0, "q2_1", {}, 0.0, status=200, rows=[[1]], meta={
        "partialResult": False, "exceptions": [], "numSegmentsQueried": 80,
        "numServersQueried": 2, "numServersResponded": 2})
    assert check.envelope_fault(req, 80) is None
    req.meta["numSegmentsQueried"] = 81
    assert check.envelope_fault(req, 80) == "numSegmentsQueried 81 != 80"


def _child(serve_twice: bool) -> int:
    """One rehearsed run of the cell in this process; prints its verdict."""
    if serve_twice:
        sys.path.insert(0, harness.REPO)
        from pinot_tpu.cluster.broker import Broker

        Broker._route = controls_replicated.route_one_segment_twice(Broker._route)
    args = argparse.Namespace(workload=CELL, seed=2**31 + 3, seconds=2.0, trace=0, rehearse=True,
                              rehearse_rows=40_000, keep_trace=None)
    result = harness.run_cell(args, time.perf_counter())
    print(json.dumps({"child": {k: result[k] for k in ("correct", "attempted", "failed")}}), flush=True)
    return 0


@pytest.mark.parametrize("serve_twice", [False, True], ids=["sound", "one_segment_served_twice"])
def test_rehearsed_run_of_the_cell(serve_twice):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PINOT_TPU_SCAN_BACKEND="interpret",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.abspath(__file__), "--child", str(int(serve_twice))],
                       capture_output=True, text=True, env=env, cwd=harness.REPO, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    verdict = lines[-1]["child"]
    faults = next(x for x in lines if x.get("phase") == "check")["faults"]
    assert verdict["attempted"] > 0
    if serve_twice:
        assert verdict["correct"] is False and verdict["failed"] == verdict["attempted"]
        assert all("numSegmentsQueried 5 != 4" in f for f in faults) and faults
        assert not any(x["equal"] for x in lines if x.get("phase") == "compared")
    else:
        assert verdict["correct"] is True and verdict["failed"] == 0 and not faults


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", type=int, required=True)
    sys.exit(_child(bool(ap.parse_args().child)))
