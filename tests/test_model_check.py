"""Tier-1 gate for the deterministic-schedule concurrency model checker
(analysis/scheduler.py + analysis/model_check.py).

Three properties, each a hard gate:

  * CLEAN: every registered protocol model explores its seeded schedule
    budget without a failure, inside a wall-clock budget (the checker is
    a pre-merge tool, not an overnight one).
  * MUTATION COVERAGE: every broken twin (a protocol subclass with one
    surgically reintroduced bug) is CAUGHT within the same budget — the
    checker's invariants actually discriminate, they aren't tautologies.
  * REPLAY DETERMINISM: a captured failing trace re-runs bit-identically
    — same failure kind, detail, step index, and schedule — across
    repeated replays and through a JSON round-trip.  "Capture once,
    replay forever" is the debugging contract.

The suite runs under the real tier-1 flags (-p no:randomly among them);
determinism here is by construction (seeded RNG + forced schedules +
fake clock), not by test-ordering luck.
"""
import json
import time

import pytest

from pinot_tpu.analysis import model_check
from pinot_tpu.analysis.models import PROTOCOLS
from pinot_tpu.utils import threads

ALL_MUTATIONS = [
    (name, mut)
    for name, cls in sorted(PROTOCOLS.items())
    for mut in getattr(cls, "MUTATIONS", ())
]


def test_clean_models_pass_within_budget():
    t0 = time.monotonic()
    report = model_check.check_all(seed=0, max_schedules=25, mutations=True)
    elapsed = time.monotonic() - t0
    assert report["ok"] is True, json.dumps(report, indent=2)
    assert set(report["protocols"]) == set(PROTOCOLS)
    for name, entry in report["protocols"].items():
        assert entry["failure"] is None, f"{name}: {entry['failure']}"
        assert entry["invariants"], f"{name} registered no invariants"
    assert elapsed < 10.0, f"mc gate took {elapsed:.1f}s (budget 10s)"


@pytest.mark.parametrize("protocol,mutation", ALL_MUTATIONS)
def test_every_mutation_is_caught(protocol, mutation):
    res = model_check.explore(
        PROTOCOLS[protocol], max_schedules=25, seed=0, mutation=mutation
    )
    assert res["failure"] is not None, (
        f"broken twin {protocol}[{mutation}] survived the schedule budget — "
        "the invariants are not discriminating"
    )


@pytest.mark.parametrize("protocol,mutation", ALL_MUTATIONS)
def test_failing_trace_replays_bit_identically(protocol, mutation):
    trace = model_check.explore(
        PROTOCOLS[protocol], max_schedules=25, seed=0, mutation=mutation
    )
    want = trace["failure"]
    assert want is not None
    for _ in range(2):
        got = model_check.replay(trace)
        assert got is not None, "forced replay lost the failure"
        for key in ("kind", "detail", "step", "schedule"):
            assert got[key] == want[key], (
                f"replay diverged on {key}: {got[key]!r} != {want[key]!r}"
            )


def test_trace_survives_json_round_trip(tmp_path):
    trace = model_check.explore(
        PROTOCOLS["lease"], max_schedules=25, seed=0, mutation="skip_fence"
    )
    path = str(tmp_path / "trace.json")
    model_check.save_trace(trace, path)
    loaded = model_check.load_trace(path)
    assert loaded == json.loads(json.dumps(trace))  # JSON-clean, no lossy types
    got = model_check.replay(loaded)
    assert got["detail"] == trace["failure"]["detail"]
    assert got["schedule"] == trace["failure"]["schedule"]


def test_same_seed_same_exploration():
    a = model_check.explore(PROTOCOLS["admission"], max_schedules=6, seed=3,
                            mutation="if_not_while")
    b = model_check.explore(PROTOCOLS["admission"], max_schedules=6, seed=3,
                            mutation="if_not_while")
    assert a == b  # schedulesExplored AND the full failure record
    c = model_check.explore(PROTOCOLS["admission"], max_schedules=6, seed=4,
                            mutation="if_not_while")
    # a different seed may catch on a different schedule — what must hold
    # is that it still catches within budget
    assert c["failure"] is not None


def test_cli_mc_gate(capsys):
    import pinot_tpu.tools.cli as cli

    rc = cli.main(["mc", "--mutations"])
    out = capsys.readouterr()
    assert rc == 0, out.out + out.err
    assert "all gates green" in out.err
    assert "MISSED" not in out.out and "FAIL " not in out.out


def test_cli_mc_capture_then_replay(tmp_path, capsys):
    import pinot_tpu.tools.cli as cli

    path = str(tmp_path / "trace.json")
    rc = cli.main(["mc", "--mutations", "--protocols", "lease", "--save-trace", path])
    capsys.readouterr()
    assert rc == 0
    rc = cli.main(["mc", "--replay", path])
    out = capsys.readouterr()
    assert rc == 0, out.out + out.err
    assert "reproduced lease[skip_fence]" in out.out


def test_provider_restored_after_schedules():
    model_check.run_schedule(PROTOCOLS["admission"], seed=1)
    assert threads.provider() is threads._DEFAULT
    # and real primitives work immediately after a checker run
    ev = threads.Event()
    ev.set()
    assert ev.wait(timeout=0.1)
