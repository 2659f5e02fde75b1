"""The check has been shown to fail (run by hand: `python -m pytest
benchmarks/tests -q`; the benchmark's own runs never run this).

1. Each control — the reference in the program's place with one guarantee
   broken (lib/controls.py) — comes out not correct, at a size a test run
   can hold.  On the chip, at the cells' own sizes, tools/control.py reads
   the same numbers; PERF.md has them.
2. A whole run with the chip look-up skipped (--rehearse) and the timed path
   broken underneath — one served sum altered where the answer is produced,
   or one segment's partial dropped — comes out `correct: false`; the same
   run unbroken comes out `correct: true`.
"""
import argparse
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import controls, harness, plugins, templates  # noqa: E402

ROWS = 400_000


@pytest.fixture(scope="module")
def table():
    cfg = dict(plugins.load_json("configs", "ssb_flat_sf1"), rows=ROWS, segment_rows=ROWS // 4)
    gen = plugins.load_module("datagen", cfg["datagen"])
    return cfg, [gen.make_segment(cfg, 2**31 + 11, i, ROWS // 4) for i in range(4)]


def _rows(spec, answer):
    """An answer laid out as the front door would return it."""
    if "scalar" in answer:
        return ["sum"], [[answer["scalar"]]]
    cols = list(spec["group_by"]) + ["sum"]
    rows = [list(k) + [v] for k, v in answer["groups"].items()]

    def key(r):
        out = []
        for what, direction in spec["order_by"]:
            v = r[cols.index(what)]
            out.append(v if direction == "asc" else -v)
        return tuple(out)

    return cols, sorted(rows, key=key)


@pytest.mark.parametrize("control", sorted(controls.CONTROLS))
@pytest.mark.parametrize("mix", ["groupby_closed", "q1_closed", "mixed_open"])
def test_control_is_not_correct(table, control, mix):
    cfg, blocks = table
    qs = plugins.load_json("queries", cfg["query_set"])
    rng = np.random.default_rng(3)
    verdicts = []
    for name in harness.cell_templates(plugins.load_json("traffic", mix)):
        t = qs["templates"][name]
        spec = t["reference"]
        mod = plugins.load_module("references", spec["kind"])
        params = templates.draw_params(t, rng)
        exact = mod.answer(spec, params, blocks)
        cols, rows = _rows(spec, exact)
        assert mod.compare(spec, cols, rows, exact)[0], "the reference agrees with itself"
        cols, rows = _rows(spec, controls.CONTROLS[control](mod, spec, params, blocks))
        verdicts.append(mod.compare(spec, cols, rows, exact)[0])
    assert not any(verdicts), f"{control} passed the check on {mix}: {verdicts}"


def _run(monkeypatch, workload, break_with=None):
    args = argparse.Namespace(workload=workload, seed=2**31 + 3, seconds=2.0, trace=0, rehearse=True,
                              rehearse_rows=20_000)
    if break_with is not None:
        sys.path.insert(0, harness.REPO)
        from pinot_tpu.cluster import rest

        real = rest.broker_response

        def broken(result):
            return break_with(real(result))

        monkeypatch.setattr(rest, "broker_response", broken)
    return harness.run_cell(args, time.perf_counter())


def _alter_one_sum(payload):
    rows = payload["resultTable"]["rows"]
    if rows and rows[-1][-1] is not None:
        rows[-1][-1] = rows[-1][-1] + 1  # off by one in one group: a float32 sum would be further
    return payload


def _drop_a_segment(payload):
    payload["numSegmentsQueried"] -= 1  # the envelope of a partial answer
    return payload


def test_unbroken_run_is_correct(monkeypatch):
    result = _run(monkeypatch, "ssb_sf10.q1_closed")
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("fault", [_alter_one_sum, _drop_a_segment], ids=["one_sum_altered", "partial_answer"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    result = _run(monkeypatch, "ssb_sf10.q1_closed", break_with=fault)
    assert result["correct"] is False
