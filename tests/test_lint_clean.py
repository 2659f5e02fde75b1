"""Tier-1 CI gate: `pinot_tpu lint` must exit clean on the shipped tree.

The gate now covers the full pipeline — per-file rules plus the
interprocedural race detector and sync auditor with the committed
baseline — and budgets its wall time so the analysis can't quietly grow
past what a pre-merge check can afford.  Kept as its own tiny module so
the gate shows up as named tests in the standard tier-1 run (ROADMAP
command unchanged)."""
import json
import time

import pinot_tpu.tools.cli as cli


def test_cli_lint_exits_zero(capsys):
    rc = cli.main(["lint"])
    out = capsys.readouterr()
    assert rc == 0, out.out + out.err
    assert "0 finding(s)" in out.err


def test_interprocedural_gate_clean_and_under_budget():
    from pinot_tpu.analysis.engine import run_project

    t0 = time.monotonic()
    report = run_project()
    elapsed = time.monotonic() - t0
    assert report.findings == [], "\n".join(str(f) for f in report.findings)
    assert report.stale_baseline == [], report.stale_baseline
    assert report.baselined > 0  # the committed baseline is live, not decorative
    # budget tracks tree growth: ~8s on an idle machine at r18 (the r10
    # original was 10s over a tree half this size); the gate is against
    # pathological blowup, not linear growth
    assert elapsed < 20.0, f"analysis gate took {elapsed:.1f}s (budget 20s)"


def test_cli_lint_json_report(capsys):
    rc = cli.main(["lint", "--json"])
    out = capsys.readouterr()
    assert rc == 0
    payload = json.loads(out.out)
    assert payload["count"] == 0 and payload["findings"] == []
    assert payload["staleBaseline"] == []
    assert payload["baselined"] > 0
    # the model-check sweep rides along in the one machine-readable gate
    mc = payload["modelCheck"]
    assert mc["ok"] is True
    assert set(mc["protocols"]) == {"admission", "knobs", "lease", "residency"}
    for entry in mc["protocols"].values():
        assert entry["failure"] is None


def test_cli_lint_flags_bad_path(tmp_path, capsys):
    bad = tmp_path / "cluster" / "racy.py"
    bad.parent.mkdir()
    bad.write_text(
        "class C:\n"
        "    def bump(self):\n"
        "        self._n += 1\n"
    )
    rc = cli.main(["lint", str(bad), "--explain"])
    out = capsys.readouterr()
    assert rc == 1
    assert "W004" in out.out and "1 finding(s)" in out.err
