"""SLO autopilot: KnobRegistry + feedback controller (ISSUE 18).

Everything here runs on a fake clock and a fake ledger — tick() is the
whole control law, driven directly.  Covers: clamped/typed knob writes
with the hard static-ceiling invariant, live env-default fallthrough
(autopilot off == pre-registry behavior bit-exact), hysteresis holds,
anti-windup skips, cooldown after every ladder walk, the
breach -> degrade -> recover round trip retracing the ladder, the
oscillation bound, and the satellite-1 regression: a registry write
takes effect on the next decision without rebuilding any consumer."""
import pytest

from pinot_tpu.cluster.autopilot import (
    Autopilot,
    KnobRegistry,
    LADDER,
    autopilot_enabled,
    knobs,
)


class FakeLedger:
    """Minimal ShapeStats stand-in: per-table (p99_ms, qps)."""

    def __init__(self):
        self.tables = {}

    def set(self, table, p99, qps=10.0):
        self.tables[table] = (p99, qps)

    def snapshot(self):
        return {
            "tables": {
                t: {
                    "qps": q,
                    "shapes": {"s": {"latencyMs": {"p99": p, "max": p}}},
                }
                for t, (p, q) in self.tables.items()
            }
        }


def make_pilot(slo_ms=100.0, registry=None):
    sim = [0.0]
    reg = registry if registry is not None else KnobRegistry()
    led = FakeLedger()
    ap = Autopilot(
        registry=reg, ledger=led, clock=lambda: sim[0], tick_s=1.0, slo_ms=slo_ms
    )
    return ap, reg, led, sim


def drive(ap, led, sim, p99, n=1, table="t"):
    """Set the signal, advance the fake clock, tick n times."""
    out = []
    for _ in range(n):
        if p99 is None:
            led.tables.pop(table, None)
        else:
            led.set(table, p99)
        sim[0] += ap.tick_s
        out.append(ap.tick())
    return out


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestKnobRegistry:
    def test_env_default_read_live(self, monkeypatch):
        """No override => the env var is consulted at decision time, so a
        monkeypatched env (and the autopilot-off path) behaves exactly
        like the pre-registry construction-time read."""
        reg = KnobRegistry()
        assert reg.get("hedge_budget_pct") == 10.0
        monkeypatch.setenv("PINOT_TPU_HEDGE_BUDGET_PCT", "5.5")
        assert reg.get("hedge_budget_pct") == 5.5

    def test_hard_ceiling_invariant(self):
        """Setters can NEVER exceed the static env-derived clamp bounds."""
        reg = KnobRegistry()
        for name in reg.names():
            lo, hi = reg.bounds(name)
            assert reg.set(name, hi + 1e9) <= hi
            assert reg.set(name, lo - 1e9) >= lo

    def test_integer_knobs_round(self):
        reg = KnobRegistry()
        assert reg.set("pipeline_depth", 1.4) == 1.0
        assert reg.set("degrade_level", 2.6) == 3.0

    def test_set_many_one_atomic_tick(self):
        reg = KnobRegistry()
        applied = reg.set_many({"hedge_delay_mult": 2.0, "hedge_budget_pct": 5.0})
        assert applied == {"hedge_delay_mult": 2.0, "hedge_budget_pct": 5.0}
        view = reg.view()
        assert view["hedge_delay_mult"] == 2.0
        assert view["hedge_budget_pct"] == 5.0

    def test_snapshot_marks_overrides_and_reset_clears(self):
        reg = KnobRegistry()
        reg.set("hedge_budget_pct", 4.0)
        snap = reg.snapshot()["knobs"]
        assert snap["hedge_budget_pct"]["overridden"] is True
        assert snap["pipeline_depth"]["overridden"] is False
        reg.reset()
        assert reg.snapshot()["knobs"]["hedge_budget_pct"]["overridden"] is False
        assert reg.get("hedge_budget_pct") == 10.0

    def test_splits_normalized_copy(self):
        reg = KnobRegistry()
        reg.set_splits({"a": 0.75, "b": 0.25})
        s = reg.splits()
        assert s == {"a": 0.75, "b": 0.25}
        s["a"] = 99.0  # caller mutation must not leak in
        assert reg.splits()["a"] == 0.75

    def test_enabled_toggle(self, monkeypatch):
        monkeypatch.delenv("PINOT_TPU_AUTOPILOT", raising=False)
        assert autopilot_enabled() is False
        monkeypatch.setenv("PINOT_TPU_AUTOPILOT", "1")
        assert autopilot_enabled() is True


# ---------------------------------------------------------------------------
# control law
# ---------------------------------------------------------------------------


class TestControlLaw:
    def test_idle_without_traffic(self):
        ap, reg, led, sim = make_pilot()
        (d,) = drive(ap, led, sim, None)
        assert d["action"] == "idle"
        assert reg.view() == {n: reg.initial(n) for n in reg.names()}

    def test_hysteresis_band_holds(self):
        """p99 between recover_ratio*slo and slo: no move, ever."""
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        for d in drive(ap, led, sim, 85.0, n=10):
            assert d["action"] == "hold"
        assert not reg.snapshot()["knobs"]["hedge_budget_pct"]["overridden"]

    def test_breach_needs_sustained_evidence(self):
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        d1, d2 = drive(ap, led, sim, 300.0, n=2)
        assert d1["action"] == "breach-pending"
        assert d2["action"] == "degrade"
        # first ladder rung: shed hedges, multiplicative decrease
        assert d2["knob"] == "hedge_budget_pct"
        assert d2["to"] == pytest.approx(5.0)

    def test_one_breach_then_health_resets_streak(self):
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        drive(ap, led, sim, 300.0)  # breach-pending
        drive(ap, led, sim, 85.0)  # in band: evidence resets
        (d,) = drive(ap, led, sim, 300.0)
        assert d["action"] == "breach-pending"  # streak restarted, no move

    def test_anti_windup_skips_saturated_knob(self):
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        reg.set("hedge_budget_pct", 0.0)  # pinned at lo: saturated
        _, d = drive(ap, led, sim, 300.0, n=2)
        assert d["action"] == "degrade"
        assert d["knob"] == "pipeline_depth"  # next rung, not the pinned one

    def test_ladder_walk_sets_cooldown(self):
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        # saturate every rung before degrade_level (admission inert: env 0)
        reg.set_many(
            {
                "hedge_budget_pct": 0.0,
                "pipeline_depth": 1,
                "staging_depth": 1,
            }
        )
        _, d = drive(ap, led, sim, 300.0, n=2)
        assert d["action"] == "degrade"
        assert d["knob"] == "degrade_level"
        assert reg.get("degrade_level") == 1.0
        for d in drive(ap, led, sim, 300.0, n=ap.cooldown_ticks):
            assert d["action"] == "cooldown"
        assert ap.snapshot()["ladderWalks"] == 1

    def test_fully_saturated_reports_not_moves(self):
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        reg.set_many(
            {
                "hedge_budget_pct": 0.0,
                "pipeline_depth": 1,
                "staging_depth": 1,
                "degrade_level": 3,
            }
        )
        _, d = drive(ap, led, sim, 300.0, n=2)
        assert d["action"] == "saturated"
        assert reg.get("degrade_level") == 3.0  # nothing pushed past a clamp

    def test_breach_degrade_recover_round_trip(self):
        """Sustained breach walks down the ladder; sustained health climbs
        back the SAME path until every knob sits at its env initial."""
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        initials = {n: reg.initial(n) for n in reg.names()}
        moves = [d for d in drive(ap, led, sim, 400.0, n=4) if "knob" in d]
        assert [m["knob"] for m in moves if m["action"] == "degrade"] == [
            "hedge_budget_pct",
            "hedge_budget_pct",
        ]
        assert reg.get("hedge_budget_pct") == pytest.approx(2.5)
        # now healthy: recovery retraces (additive increase) to initial
        recovered = False
        for d in drive(ap, led, sim, 20.0, n=60):
            if d["action"] == "recover":
                assert d["knob"] == "hedge_budget_pct"
                assert d["to"] > d["from"]
            if d["action"] == "recovered":
                recovered = True
                break
        assert recovered
        assert reg.view() == initials
        assert not reg.snapshot()["knobs"]["hedge_budget_pct"]["overridden"] or (
            reg.get("hedge_budget_pct") == initials["hedge_budget_pct"]
        )

    def test_oscillation_bound_caps_changes_per_window(self):
        """At most max_changes_per_window knob moves per change_window
        ticks, no matter how hard the signal whipsaws."""
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        decisions = drive(ap, led, sim, 400.0, n=3 * ap.change_window)
        move_ticks = [
            d["tick"] for d in decisions if d["action"] in ("degrade", "recover")
        ]
        assert any(d["action"] == "capped" for d in decisions)
        for t in move_ticks:
            in_window = [m for m in move_ticks if t - ap.change_window < m <= t]
            assert len(in_window) <= ap.max_changes_per_window
        assert ap.snapshot()["knobChanges"] == len(move_ticks)

    def test_disabled_when_slo_nonpositive(self):
        ap, reg, led, sim = make_pilot(slo_ms=0.0)
        (d,) = drive(ap, led, sim, 400.0)
        assert d["action"] == "disabled"

    def test_splits_follow_traffic_share(self):
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        led.set("hot", 50.0, qps=30.0)
        led.set("cold", 50.0, qps=10.0)
        sim[0] += 1.0
        ap.tick()
        s = reg.splits()
        assert s["hot"] == pytest.approx(0.75)
        assert s["cold"] == pytest.approx(0.25)

    def test_single_tenant_keeps_no_splits(self):
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        drive(ap, led, sim, 50.0, n=3)
        assert reg.splits() == {}

    def test_snapshot_surface(self):
        ap, reg, led, sim = make_pilot(slo_ms=100.0)
        drive(ap, led, sim, 300.0, n=2)
        snap = ap.snapshot()
        assert snap["enabled"] is True
        assert snap["ticks"] == 2
        assert snap["changeBound"] == {"windowTicks": 16, "maxChanges": 4}
        assert snap["decisions"][-1]["action"] == "degrade"
        assert snap["tables"]["t"]["state"] == "breach"
        assert set(LADDER) <= set(snap["knobs"])

    def test_telemetry_failure_holds_not_dies(self):
        class BrokenLedger:
            def snapshot(self):
                raise RuntimeError("ledger down")

        sim = [0.0]
        ap = Autopilot(
            registry=KnobRegistry(),
            ledger=BrokenLedger(),
            clock=lambda: sim[0],
            slo_ms=100.0,
        )
        d = ap.tick()
        assert d["action"] == "idle"  # degraded to no-signal, loop survives

    def test_sensing_backoff_policy(self):
        # steady ticks stretch the cadence geometrically up to the cap;
        # saturated counts as steady (nothing to move until load eases)
        b = 1
        for expect in (2, 4, 8, 8):
            b = Autopilot._next_backoff(b, "hold")
            assert b == expect
        assert Autopilot._next_backoff(8, "saturated") == 8
        assert Autopilot._next_backoff(8, "idle") == 8
        # any evidence, move, or cooldown snaps straight back to tick_s
        for action in ("breach-pending", "degrade", "recover-pending",
                       "recover", "capped", "cooldown"):
            assert Autopilot._next_backoff(8, action) == 1


# ---------------------------------------------------------------------------
# satellite 1: a registry write reaches every consumer on the NEXT decision
# ---------------------------------------------------------------------------


class TestLiveKnobConsumers:
    def test_hedge_controller_live(self):
        from pinot_tpu.cluster.broker import HedgeController

        hc = HedgeController()
        assert hc.budget_pct == 10.0
        assert hc.quantile_mult == 1.0
        knobs().set_many({"hedge_budget_pct": 4.0, "hedge_delay_mult": 2.0})
        assert hc.budget_pct == 4.0
        assert hc.quantile_mult == 2.0
        hc.budget_pct = 60.0  # bench/test idiom still pins
        assert hc.budget_pct == 60.0

    def test_engine_pipeline_depth_live(self):
        from pinot_tpu.parallel.engine import DistributedEngine

        eng = object.__new__(DistributedEngine)  # property only, no mesh
        eng._pipeline_depth_override = None
        assert eng.pipeline_depth == 2
        knobs().set("pipeline_depth", 1)
        assert eng.pipeline_depth == 1
        eng.pipeline_depth = 2
        knobs().set("pipeline_depth", 1)
        assert eng.pipeline_depth == 2  # explicit assignment pins

    def test_server_staging_depth_live(self):
        from pinot_tpu.cluster.server import _staging_depth

        assert _staging_depth() == 2
        knobs().set("staging_depth", 1)
        assert _staging_depth() == 1

    def test_admission_rate_live(self, monkeypatch):
        from pinot_tpu.cluster.admission import AdmissionController

        monkeypatch.setenv("PINOT_TPU_ADMISSION_RATE", "100")
        adm = AdmissionController(
            rate_units_per_s=100.0, burst_units=10.0, knob="admission_rate"
        )
        assert adm.snapshot()["rate"] == 100.0
        knobs().set("admission_rate", 40.0)
        assert adm.snapshot()["rate"] == 40.0
        assert adm.snapshot()["staticRate"] == 100.0
        # registry clamp: the controller cannot raise the rate above env
        knobs().set("admission_rate", 500.0)
        assert adm.snapshot()["rate"] == 100.0

    def test_degradation_floor_live(self):
        from pinot_tpu.cluster.admission import DegradationController

        dc = DegradationController()
        assert dc.update(0.0) == 0
        knobs().set("degrade_level", 2)
        assert dc.update(0.0) == 2  # floor holds with zero occupancy
        assert dc.update(0.999) >= 2  # occupancy can push higher, not lower


# ---------------------------------------------------------------------------
# satellite 2: observability surface — GET /debug/autopilot + cli autopilot
# ---------------------------------------------------------------------------


def _small_cluster():
    import numpy as np

    from pinot_tpu.cluster.coordinator import Coordinator
    from pinot_tpu.cluster.server import ServerInstance
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.config import SegmentsConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema

    schema = Schema(
        "t",
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
            FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
        ],
    )
    coord = Coordinator(replication=1)
    coord.register_server(ServerInstance("server0"))
    coord.add_table(
        schema, TableConfig(name="t", segments=SegmentsConfig(time_column="ts"))
    )
    rng = np.random.default_rng(3)
    coord.add_segment(
        "t",
        build_segment(
            schema,
            {
                "city": rng.choice(["sf", "nyc"], 64).astype(object),
                "v": rng.integers(0, 100, 64),
                "ts": 1_700_000_000_000 + rng.integers(0, 1_000_000, 64).astype("int64"),
            },
            "s0",
        ),
    )
    return coord


class TestObservability:
    def _get(self, port, path):
        import json
        import urllib.request

        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as resp:
            return resp.status, json.loads(resp.read().decode())

    def test_debug_autopilot_detached(self):
        """Without an attached controller the endpoint still serves the
        registry view (enabled: false) — knob values vs clamp bounds."""
        from pinot_tpu.cluster.broker import Broker
        from pinot_tpu.cluster.rest import QueryServer

        broker = Broker(_small_cluster())
        srv = QueryServer(broker).start()
        try:
            code, payload = self._get(srv.port, "/debug/autopilot")
            assert code == 200
            assert payload["enabled"] is False
            k = payload["knobs"]["hedge_budget_pct"]
            assert {"value", "initial", "lo", "hi", "overridden"} <= set(k)
        finally:
            srv.stop()

    def test_debug_autopilot_attached(self):
        from pinot_tpu.cluster.broker import Broker
        from pinot_tpu.cluster.rest import QueryServer

        broker = Broker(_small_cluster())
        broker.attach_autopilot()  # not started: tick() driven manually
        broker.autopilot.tick()
        srv = QueryServer(broker).start()
        try:
            code, payload = self._get(srv.port, "/debug/autopilot")
            assert code == 200
            assert payload["enabled"] is True
            assert payload["ticks"] == 1
            assert payload["decisions"][-1]["action"] in ("idle", "hold")
            assert payload["changeBound"]["maxChanges"] == 4
        finally:
            srv.stop()
            broker.attach_autopilot(controller=None)  # detach leaves no thread

    def test_cli_autopilot_renders(self, capsys):
        from pinot_tpu.cluster.broker import Broker
        from pinot_tpu.cluster.rest import QueryServer
        from pinot_tpu.tools.cli import main as cli_main

        broker = Broker(_small_cluster())
        broker.attach_autopilot()
        broker.autopilot.tick()
        knobs().set("hedge_budget_pct", 4.0)
        srv = QueryServer(broker).start()
        try:
            rc = cli_main(["autopilot", "--url", f"http://127.0.0.1:{srv.port}"])
            assert rc == 0
            out = capsys.readouterr().out
            assert "autopilot : ON" in out
            assert "hedge_budget_pct" in out and "*" in out  # override marker
            rc = cli_main(
                ["autopilot", "--url", f"http://127.0.0.1:{srv.port}", "--json"]
            )
            assert rc == 0
            import json

            payload = json.loads(capsys.readouterr().out)
            assert payload["knobs"]["hedge_budget_pct"]["value"] == 4.0
        finally:
            srv.stop()

    def test_knob_gauges_published(self):
        from pinot_tpu.utils.metrics import METRICS

        knobs().set("hedge_budget_pct", 4.0)
        assert METRICS.gauge("autopilot.knob.hedge_budget_pct").value == 4.0

    def test_autopilot_env_toggle_attaches(self, monkeypatch):
        from pinot_tpu.cluster.broker import Broker

        monkeypatch.setenv("PINOT_TPU_AUTOPILOT", "1")
        broker = Broker(_small_cluster())
        try:
            assert broker.autopilot is not None
            assert broker.autopilot_snapshot()["enabled"] is True
        finally:
            broker.autopilot.stop()

    def test_autopilot_off_by_default(self, monkeypatch):
        monkeypatch.delenv("PINOT_TPU_AUTOPILOT", raising=False)
        from pinot_tpu.cluster.broker import Broker

        broker = Broker(_small_cluster())
        assert broker.autopilot is None
        assert broker.autopilot_snapshot()["enabled"] is False
