"""Tail-tolerant serving tests (round 15): hedged scatter, gray-failure
(brownout) detection, and the enriched network fault model.

Determinism: fault draws are keyed on (seed, server, call#) so logs are
bit-identical across runs and thread interleavings; jitter rules with
sigma=0 sleep EXACTLY base_ms; brownout/breaker clocks are injected.  The
one real-time test (TestTailAcceptance) uses latency magnitudes chosen so
scheduler noise of several ms cannot flip the asserted ratios.
"""
import statistics
import threading

import numpy as np
import pytest

from pinot_tpu.cluster import (
    Broker,
    Coordinator,
    FaultPlan,
    HedgeController,
    ServerFaultError,
    ServerHealth,
    ServerInstance,
)
from pinot_tpu.cluster.admission import AdmissionController, QueryKilledError
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import SegmentsConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils.metrics import METRICS


def _schema():
    return Schema(
        "t",
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
            FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
        ],
    )


def _data(n, seed, t0=1_700_000_000_000):
    rng = np.random.default_rng(seed)
    return {
        "city": rng.choice(["sf", "nyc", "la"], n).astype(object),
        "v": rng.integers(0, 100, n),
        "ts": t0 + rng.integers(0, 86_400_000, n).astype(np.int64),
    }


def _cluster(n_servers=2, replication=2, n_segments=4, rows=300):
    coord = Coordinator(replication=replication)
    for i in range(n_servers):
        coord.register_server(ServerInstance(f"server{i}"))
    coord.add_table(_schema(), TableConfig(name="t", segments=SegmentsConfig(time_column="ts")))
    for i in range(n_segments):
        coord.add_segment("t", build_segment(_schema(), _data(rows, seed=100 + i), f"seg{i}"))
    return coord


SQL = "SELECT city, COUNT(*), SUM(v) FROM t GROUP BY city ORDER BY city"


def _hedged(sql, delay_ms=5, budget_pct=100):
    return (
        f"SET hedge = true; SET hedgeDelayMs = {delay_ms}; "
        f"SET hedgeBudgetPct = {budget_pct}; " + sql
    )


def _fake_sleep(plan):
    """Replace plan.sleep with a recorder: clock-free fault tests."""
    slept = []
    plan.sleep = slept.append
    return slept


# ---------------------------------------------------------------------------
# enriched fault model
# ---------------------------------------------------------------------------
class TestFaultModelDeterminism:
    def test_jitter_log_bit_identical_across_runs(self):
        """Same seed -> identical draws, logs, and sleeps; the draw is keyed
        on (seed, server, call#) so thread interleaving can't change it."""
        logs, sleeps = [], []
        for _ in range(2):
            plan = FaultPlan(seed=42).jitter("server0", base_ms=10.0, sigma=0.7)
            s = _fake_sleep(plan)
            for _ in range(20):
                plan.on_execute("server0")
            logs.append(list(plan.log))
            sleeps.append(list(s))
        assert logs[0] == logs[1]
        assert sleeps[0] == sleeps[1]
        # sigma > 0 actually varies the draws (not a constant)
        details = [d for (_, _, kind, d) in logs[0] if kind == "jitter"]
        assert len(set(details)) > 1

    def test_jitter_seed_changes_draws(self):
        def draws(seed):
            plan = FaultPlan(seed=seed).jitter("server0", base_ms=10.0, sigma=0.7)
            _fake_sleep(plan)
            for _ in range(8):
                plan.on_execute("server0")
            return [d for (_, _, k, d) in plan.log if k == "jitter"]

        assert draws(1) != draws(2)

    def test_jitter_sigma_zero_is_exact_and_cap_clamps(self):
        plan = FaultPlan(seed=0).jitter("server0", base_ms=7.0, sigma=0.0)
        plan.jitter("server1", base_ms=100.0, sigma=0.0, cap_ms=9.0)
        s = _fake_sleep(plan)
        plan.on_execute("server0")
        plan.on_execute("server1")
        assert s == [0.007, 0.009]  # lognormvariate(0, 0) == 1.0; cap clamps

    def test_slow_ramp_monotone_then_capped(self):
        plan = FaultPlan(seed=0).slow_ramp("server0", ms_per_call=5.0, cap_ms=12.0)
        _fake_sleep(plan)
        for _ in range(4):
            plan.on_execute("server0")
        assert [d for (_, _, _, d) in plan.log] == [5.0, 10.0, 12.0, 12.0]

    def test_gray_flap_alternates_slow_and_clean(self):
        """period=2: calls 1-2 slow, 3-4 clean (no log entry, no sleep),
        5-6 slow again — the flapping gray failure brownout must chase."""
        plan = FaultPlan(seed=0).gray_flap("server0", slow_ms=8.0, period=2)
        s = _fake_sleep(plan)
        for _ in range(6):
            plan.on_execute("server0")
        assert [n for (_, n, _, _) in plan.log] == [1, 2, 5, 6]
        assert s == [0.008] * 4


class TestOneWayPartition:
    def test_direction_matters(self):
        """broker->server0 drops; server1->server0 (peer traffic) and
        broker->server1 are untouched."""
        plan = FaultPlan(seed=0).partition("broker", "server0")
        _fake_sleep(plan)
        with pytest.raises(ServerFaultError, match="broker->server0"):
            plan.on_execute("server0", source="broker")
        plan.on_execute("server0", source="server1")  # reverse-ish path: fine
        plan.on_execute("server1", source="broker")  # other server: fine

    def test_broker_fails_over_around_one_way_partition(self):
        coord = _cluster()
        clean = Broker(_cluster()).query(SQL)
        plan = FaultPlan(seed=3).partition("broker", "server0").attach(coord)
        _fake_sleep(plan)
        broker = Broker(coord)
        out = broker.query(SQL)
        assert out.rows == clean.rows
        assert any(k == "partition" for (_, _, k, _) in plan.log)


# ---------------------------------------------------------------------------
# hedge delay derivation (HedgeController unit)
# ---------------------------------------------------------------------------
class TestHedgeDelayDerivation:
    def test_delay_is_peer_p95_not_own_window(self):
        """A chronically slow primary must not inflate its own trigger: the
        delay comes from PEER windows only."""
        hc = HedgeController()
        hc.env_delay_ms = None
        hc.min_samples = 8
        for i in range(10):
            hc.observe("t", "slow", 500.0)  # primary's own window: ignored
            hc.observe("t", "fast", float(i + 1))  # peer p95 == 10.0
        assert hc.delay_ms("t", "slow") == pytest.approx(10.0)
        # for the FAST primary the slow peer sets the trigger
        assert hc.delay_ms("t", "fast") == pytest.approx(500.0)

    def test_cold_start_returns_none(self):
        hc = HedgeController()
        hc.env_delay_ms = None
        hc.min_samples = 8
        for _ in range(7):  # one short of min_samples
            hc.observe("t", "peer", 5.0)
        assert hc.delay_ms("t", "primary") is None

    def test_option_and_env_override_order(self):
        hc = HedgeController()
        hc.env_delay_ms = 7.5
        assert hc.delay_ms("t", "p") == 7.5  # env beats derivation
        assert hc.delay_ms("t", "p", {"hedgeDelayMs": 3}) == 3.0  # option beats env

    def test_budget_counter(self):
        hc = HedgeController()
        hc.budget_pct = 50.0
        for _ in range(4):
            hc.note_primary()
        assert hc.try_fire()  # 1 hedge / 4 primaries = 25%
        assert hc.try_fire()  # 50%: exactly at budget
        assert not hc.try_fire()  # 75% would exceed
        hc.unfire()
        assert hc.try_fire()


# ---------------------------------------------------------------------------
# hedged scatter (broker level)
# ---------------------------------------------------------------------------
class TestHedgedScatter:
    def _slow_cluster(self, slow_ms=60.0):
        coord = _cluster()
        FaultPlan(seed=7).jitter("server0", base_ms=slow_ms, sigma=0.0).attach(coord)
        return coord

    @staticmethod
    def _warm(broker, **hedge_kw):
        """Compile the SET-prefixed hedged shape once (different literal) so
        the measured query races sleeps, not a cold compile."""
        broker.query(
            _hedged("SELECT city, COUNT(*) FROM t WHERE v < 1 GROUP BY city", **hedge_kw)
        )

    def test_hedge_fires_backup_wins_loser_cancelled(self):
        clean = Broker(_cluster()).query(_hedged(SQL))
        broker = Broker(self._slow_cluster())
        self._warm(broker)
        out = broker.query(_hedged(SQL))
        assert out.rows == clean.rows
        assert out.stats.hedged >= 1
        assert out.stats.hedge_winner == "server1"
        assert METRICS.counter("broker.hedgesLaunched").value >= 1
        assert METRICS.counter("broker.hedgeWins").value >= 1
        assert broker.hedge_drain() == 0  # no leaked launches
        # every loser settled exactly once: cooperatively cancelled, or it
        # finished too late and was booked as hedge waste — never punished
        launched = METRICS.counter("broker.hedgesLaunched").value
        settled = (
            METRICS.timer("broker.hedgeCancelMs").count
            + METRICS.timer("broker.hedgeWastedMs").count
        )
        assert settled == launched

    def test_loser_cancel_is_not_a_failure(self):
        """Cooperative hedge cancel must not punish the loser: breaker stays
        closed, no quarantine, no scatter-failure accounting — exactly once
        means exactly zero here."""
        broker = Broker(self._slow_cluster())
        self._warm(broker)
        broker.query(_hedged(SQL))
        assert broker.hedge_drain() == 0
        assert broker.health.state("server0") == "closed"
        assert METRICS.counter("broker.scatterServerFailures").value == 0
        assert METRICS.counter("broker.serversQuarantined").value == 0

    def test_slowlog_surfaces_hedge_annotations(self):
        broker = Broker(self._slow_cluster())
        self._warm(broker)
        broker.query(_hedged(SQL))
        broker.hedge_drain()
        entry = broker.slow_queries.snapshot()[0]
        assert entry["hedge"]["hedged"] >= 1
        assert entry["hedge"]["winner"] == "server1"
        assert entry["hedge"]["cancelledMs"] >= 0.0

    def test_budget_zero_denies_hedge(self):
        clean = Broker(_cluster()).query(SQL)
        broker = Broker(self._slow_cluster(slow_ms=20.0))
        self._warm(broker, budget_pct=0)
        out = broker.query(_hedged(SQL, budget_pct=0))
        assert out.rows == clean.rows
        assert METRICS.counter("broker.hedgesLaunched").value == 0
        assert METRICS.counter("broker.hedgesDenied").value >= 1

    def test_disabled_by_default_no_threads(self):
        broker = Broker(self._slow_cluster(slow_ms=5.0))
        out = broker.query(SQL)
        assert out.stats.hedged == 0
        assert METRICS.counter("broker.hedgesLaunched").value == 0
        assert not broker._hedge_threads

    def test_no_spare_replica_runs_inline(self):
        """replication=1: no replica covers the primary's segments, so the
        call runs inline even with hedging enabled (no threads, no denial)."""
        coord = _cluster(replication=1)
        broker = Broker(coord)
        out = broker.query(_hedged(SQL))
        assert out.stats.hedged == 0
        assert METRICS.counter("broker.hedgesLaunched").value == 0
        assert not broker._hedge_threads

    def test_admission_sheds_hedges_before_primaries(self):
        """With the token bucket nearly drained, the primary's admission
        succeeds but the hedge's non-blocking charge fails: the hedge is the
        first thing shed, and the query still completes."""
        from pinot_tpu.cluster.admission import estimate_query_cost
        from pinot_tpu.sql.parser import parse_query

        clean = Broker(_cluster()).query(SQL)
        coord = self._slow_cluster(slow_ms=40.0)
        broker = Broker(coord)
        ctx = parse_query(SQL)
        cost = estimate_query_cost(ctx, coord.tables["t"].segment_meta.values()).units
        adm = AdmissionController(
            rate_units_per_s=1e-9, burst_units=cost + 0.5, max_queue=0
        )
        adm.clock = lambda: 0.0  # pinned: the bucket never refills
        broker.governor.admission = adm
        out = broker.query(_hedged(SQL))
        assert out.rows == clean.rows  # primary admitted and served
        assert METRICS.counter("broker.hedgesLaunched").value == 0
        assert METRICS.counter("broker.hedgesDenied").value >= 1
        assert broker.hedge_drain() == 0

    def test_try_charge_is_nonblocking_token_bucket(self):
        adm = AdmissionController(rate_units_per_s=1.0, burst_units=2.0, max_queue=4)
        now = [0.0]
        adm.clock = lambda: now[0]
        assert adm.try_charge(1.0)
        assert adm.try_charge(1.0)
        assert not adm.try_charge(1.0)  # bucket empty: refuse, never queue
        now[0] = 1.0  # one unit refilled
        assert adm.try_charge(1.0)
        assert not adm.try_charge(1.0)
        # permissive default (rate<=0) always grants
        assert AdmissionController().try_charge(1.0)


# ---------------------------------------------------------------------------
# brownout (gray-failure) detection
# ---------------------------------------------------------------------------
class TestBrownout:
    def _browned_health(self):
        h = ServerHealth(cooldown_s=30.0)
        now = [0.0]
        h.clock = lambda: now[0]
        for _ in range(8):
            h.note_latency("server1", 1.0)
        transitions = [h.note_latency("server0", 30.0) for _ in range(8)]
        return h, now, transitions

    def test_latency_outlier_enters_brownout(self):
        h, _, transitions = self._browned_health()
        assert transitions[-1] == "enter"
        assert transitions[:-1] == [None] * 7  # below min_samples: no verdict
        assert h.in_brownout("server0")
        assert h.brownout_deprioritized("server0")
        assert h.state("server0") == "brownout"
        assert h.available("server0")  # weighted away, never quarantined
        assert not h.in_brownout("server1")
        assert METRICS.counter("broker.serversBrownedOut").value == 1
        assert METRICS.gauge("broker.brownouts").value == 1.0

    def test_sub_floor_latencies_never_brown(self):
        """Microsecond-scale medians stay below brownout_min_ms: a 10x ratio
        on tiny absolute numbers must not shift routing."""
        h = ServerHealth()
        for _ in range(10):
            h.note_latency("server0", 1.0)  # 10x of 0.1 but under the 2ms floor
            h.note_latency("server1", 0.1)
        assert not h.in_brownout("server0")

    def test_breaker_and_brownout_are_independent(self):
        h, _, _ = self._browned_health()
        # breaker trips on top of the brownout; brownout state unmoved
        for _ in range(3):
            h.record_failure("server0")
        assert h.state("server0") == "open"
        assert h.in_brownout("server0")
        # breaker recovery does NOT clear the brownout
        h.record_success("server0")
        assert h.state("server0") == "brownout"
        assert h.in_brownout("server0")
        # and latency feeding never moved the breaker
        assert h.state("server1") == "closed"

    def test_recovery_probe_cycle(self):
        h, now, _ = self._browned_health()
        # inside the cooldown: deprioritized
        now[0] = 29.0
        assert h.brownout_deprioritized("server0")
        # cooldown elapsed: deprioritization lifts (probe window opens)
        # but the server is still marked browned until probes come back fast
        now[0] = 31.0
        assert not h.brownout_deprioritized("server0")
        assert h.in_brownout("server0")
        # a still-slow probe re-stamps the cooldown (failed probe)
        h.note_latency("server0", 30.0)
        assert h.brownout_deprioritized("server0")
        # probe traffic comes back at peer speed: flush the window fast...
        for _ in range(12):
            h.note_latency("server0", 1.0)
        assert h.in_brownout("server0")  # re-stamped cooldown still running
        # ...and once the re-stamped cooldown elapses, the next fast
        # evaluation clears the brownout
        now[0] = 62.0
        assert h.note_latency("server0", 1.0) == "exit"
        assert not h.in_brownout("server0")
        assert h.state("server0") == "closed"
        assert METRICS.counter("broker.brownoutRecoveries").value == 1

    def test_router_weights_away_browned_replica(self):
        coord = _cluster()
        broker = Broker(coord)
        for _ in range(8):
            broker.health.note_latency("server1", 1.0)
            broker.health.note_latency("server0", 30.0)
        assert broker.health.brownout_deprioritized("server0")
        assign = broker._route("t", ["seg0", "seg1", "seg2", "seg3"])
        assert set(assign) == {"server1"}
        # availability wins when EVERY candidate is browned
        for _ in range(32):
            broker.health.note_latency("server1", 31.0)
        if broker.health.in_brownout("server1"):
            assign = broker._route("t", ["seg0", "seg1"])
            assert assign  # still routes somewhere rather than failing


# ---------------------------------------------------------------------------
# concurrent clients ride the hedge path
# ---------------------------------------------------------------------------
class TestConcurrentHedging:
    def test_concurrent_hedged_queries_exact_and_losers_reclaimed(self):
        """Four clients at once on one broker, each query hedged away from
        the slow replica: every answer its own literal's, every loser
        settled exactly once, none punished."""
        sqls = [
            f"SELECT city, COUNT(*), SUM(v) FROM t WHERE v < {40 + i} "
            "GROUP BY city ORDER BY city"
            for i in range(4)
        ]
        clean = Broker(_cluster())
        expected = [clean.query(q) for q in sqls]
        assert len({str(e.rows) for e in expected}) == 4  # the literals tell the answers apart

        coord = _cluster()
        FaultPlan(seed=7).jitter("server0", base_ms=50.0, sigma=0.0).attach(coord)
        broker = Broker(coord)
        # warm the shape so the hedge races sleeps, not a compile
        broker.query(_hedged(sqls[0]))
        broker.hedge_drain()
        METRICS.reset()
        outs = [None] * len(sqls)
        start = threading.Barrier(len(sqls))

        def client(i):
            try:
                start.wait(60.0)
                outs[i] = broker.query(_hedged(sqls[i]))
            except Exception as e:  # noqa: BLE001 -- compared below
                outs[i] = e

        clients = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(len(sqls))]
        for t in clients:
            t.start()
        for t in clients:
            t.join(120.0)
        assert not [t for t in clients if t.is_alive()]
        for out, exp in zip(outs, expected):
            assert not isinstance(out, Exception), out
            assert out.rows == exp.rows
        launched = METRICS.counter("broker.hedgesLaunched").value
        assert launched >= 1
        assert broker.hedge_drain() == 0
        # every loser reclaimed (cancelled through its probe, or finished
        # too late and booked as waste) and none punished
        settled = (
            METRICS.timer("broker.hedgeCancelMs").count
            + METRICS.timer("broker.hedgeWastedMs").count
        )
        assert settled == launched
        assert METRICS.counter("broker.scatterServerFailures").value == 0
        assert broker.health.state("server0") == "closed"
        assert sum(o.stats.hedged for o in outs) >= 1


# ---------------------------------------------------------------------------
# acceptance: one replica at 10x latency
# ---------------------------------------------------------------------------
class TestTailAcceptance:
    def test_hedged_p99_within_3x_fault_free_unhedged_beyond_8x(self):
        """The ISSUE's headline numbers: with one replica at 10x latency
        under a seeded fault plan, hedging clips the tail to <=3x the
        fault-free p99 while the unhedged tail blows past 8x.  The fault is
        calibrated off the MEASURED fault-free p99 (slow = 10x p99), which
        makes the 8x bound structural — every unhedged query serially waits
        out a sleep that is itself 10x the baseline tail — and leaves the
        3x bound a ~2x margin over scheduler noise."""
        import time as _time

        base_ms, n = 10.0, 8

        def leg(slow_ms, hedge, delay_ms=None):
            coord = _cluster(rows=150)
            plan = FaultPlan(seed=13).jitter("server1", base_ms=base_ms, sigma=0.0)
            plan.jitter("server0", base_ms=slow_ms or base_ms, sigma=0.0)
            plan.attach(coord)
            broker = Broker(coord)
            # warm with the SAME parameterized shape as the measured queries
            # — including the SET prefix, which is part of the fingerprint —
            # so a different literal keeps the result cache cold while the
            # plan/compile caches are hot
            warm = "SELECT city, COUNT(*), SUM(v) FROM t WHERE v < 59 GROUP BY city ORDER BY city"
            broker.query(_hedged(warm, delay_ms=delay_ms) if hedge else warm)
            ts = []
            for i in range(n):
                sql = f"SELECT city, COUNT(*), SUM(v) FROM t WHERE v < {60 + i} GROUP BY city ORDER BY city"
                if hedge:
                    sql = _hedged(sql, delay_ms=delay_ms)
                t0 = _time.perf_counter()
                broker.query(sql)
                ts.append((_time.perf_counter() - t0) * 1000)
            return broker, float(np.percentile(ts, 99))

        _, ff_p99 = leg(slow_ms=None, hedge=False)
        slow_ms = 10.0 * ff_p99  # "one replica at 10x latency"
        _, un_p99 = leg(slow_ms, hedge=False)
        # hedge trigger at ~half the baseline tail: past every healthy reply
        broker, hd_p99 = leg(slow_ms, hedge=True, delay_ms=round(0.5 * ff_p99, 3))

        assert un_p99 >= 8.0 * ff_p99, (un_p99, ff_p99)
        assert hd_p99 <= 3.0 * ff_p99, (hd_p99, ff_p99)
        # budget respected: hedges never exceed 100% of primary launches
        snap = broker.hedge.snapshot()
        assert 1 <= snap["hedges"] <= snap["primaries"]
        # every loser reclaimed, nothing leaked, punish exactly zero times
        assert broker.hedge_drain(timeout_s=10.0) == 0
        launched = METRICS.counter("broker.hedgesLaunched").value
        settled = (
            METRICS.timer("broker.hedgeCancelMs").count
            + METRICS.timer("broker.hedgeWastedMs").count
        )
        assert launched >= n  # the slow replica's half of every query hedged
        assert settled == launched  # one loser per engaged pair, all reclaimed
        assert METRICS.counter("broker.scatterServerFailures").value == 0
        assert broker.health.state("server0") == "closed"
