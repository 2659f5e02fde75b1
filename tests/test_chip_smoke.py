"""chip_smoke.py rehearsed in-process on the CPU (interpret-mode scan).

The real run needs a TPU and is made through the chip tool; here the same
phases run at a size that spans several segments, every phase line must say
`match: true`, a rehearsal can never print the final `ok` line, and a wrong
reference must turn into a non-zero exit."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


def _run(capsys, argv):
    rc = chip_smoke.main(argv)
    out = capsys.readouterr().out
    return rc, [json.loads(line) for line in out.splitlines() if line.strip()]


@pytest.mark.parametrize(
    "argv,phases,queries",
    [
        (
            ["--rows", "20000", "--segment-rows", "7000"],
            {"device", "reference", "kernels", "load", "served", "stacked", "done"},
            {"served": list("abcde"), "stacked": list("abcd")},
        ),
        (
            ["--chips", "4", "--rows", "8000", "--segment-rows", "1500"],
            {"device", "reference", "mesh_1x4", "load", "four_servers", "done"},
            {"mesh_1x4": list("acd"), "four_servers": ["a"]},
        ),
    ],
    ids=["one_chip", "four_chips"],
)
def test_rehearsal_passes_every_phase(capsys, argv, phases, queries):
    rc, lines = _run(capsys, ["--rehearse"] + argv)
    assert rc == 0
    assert lines and all(line.get("rehearsal") is True for line in lines)
    assert not any("ok" in line for line in lines)  # never the final ok line
    assert {line["phase"] for line in lines} == phases | {"rows"}
    checked = [line for line in lines if "match" in line]
    assert checked and all(line["match"] is True for line in checked)
    for phase, qids in queries.items():
        got = [line for line in lines if line["phase"] == phase and "query" in line]
        assert [line["query"] for line in got] == qids
        assert all(line["backend"] == line["expected_backend"] for line in got)
    # the Pallas kernel (interpreted here) really ran the headline query
    assert any(line.get("query") == "a" and line["backend"] == "interpret" for line in lines)
    load = next(line for line in lines if line["phase"] == "load")
    assert load["segments"] >= 2 and load["devices_holding_segments"] == load["servers"]


def test_wrong_reference_is_a_nonzero_exit(capsys, monkeypatch):
    real = chip_smoke.reference_answers

    def wrong(data):
        ref = real(data)
        first = next(iter(ref["a"]))
        ref["a"][first] += 1
        return ref

    monkeypatch.setattr(chip_smoke, "reference_answers", wrong)
    rc, lines = _run(capsys, ["--rehearse", "--rows", "8000", "--segment-rows", "3000"])
    assert rc != 0
    assert [line["match"] for line in lines if "match" in line][-1] is False
    assert not any("ok" in line or line["phase"] == "done" for line in lines)


def test_real_run_refuses_the_cpu(capsys):
    rc, lines = _run(capsys, [])
    assert rc != 0 and lines == []
