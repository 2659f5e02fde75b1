"""Overload-safe serving tests (round 11): cost-based token-bucket
admission, HBM/host byte reservations, the runaway-query watchdog, graceful
degradation, and their REST / breaker / cache interactions.

Determinism: admission tests inject the bucket clock (the simulated arrival
schedule IS the clock, host speed is irrelevant), watchdog tests inject a
counting clock, and in the overload acceptance sweep offered load is
simulated and outcomes are exact counts.
"""
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Coordinator, ServerInstance
from pinot_tpu.cluster.admission import (
    AdmissionController,
    DegradationController,
    QueryCost,
    QueryKilledError,
    QueryWatchdog,
    ReservationError,
    ResourceBudget,
    ResourceGovernor,
    TooManyRequestsError,
    estimate_query_cost,
    pipeline_depth_under_pressure,
)
from pinot_tpu.query.safety import AdmissionError
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.spi.config import SegmentsConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query
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


def _cluster(n_servers=2, replication=2, n_segments=4, rows=200, server_budget=None):
    """Deterministic small cluster; `server_budget` bytes installs an
    explicit HBM reservation ledger per server (None = coordinator default)."""
    coord = Coordinator(replication=replication)
    for i in range(n_servers):
        budget = (
            ResourceBudget(server_budget, gauge=f"server.reservedBytes.server{i}")
            if server_budget is not None
            else None
        )
        coord.register_server(ServerInstance(f"server{i}", budget=budget))
    coord.add_table(_schema(), TableConfig(name="t", segments=SegmentsConfig(time_column="ts")))
    for i in range(n_segments):
        coord.add_segment("t", build_segment(_schema(), _data(rows, seed=100 + i), f"seg{i}"))
    return coord


def _governor(rate=0.0, burst=None, max_queue=8, host_bytes=1 << 30,
              runaway_ms=0.0, kill_at=0.0):
    return ResourceGovernor(
        admission=AdmissionController(
            rate_units_per_s=rate, burst_units=burst, max_queue=max_queue
        ),
        host_budget=ResourceBudget(host_bytes, gauge="admission.hostReservedBytes"),
        watchdog=QueryWatchdog(runaway_ms=runaway_ms, pressure_kill_at=kill_at),
        degrade=DegradationController(),
    )


def _sql(i=0):
    # distinct literal per call: misses the result cache, shares ONE
    # parameterized plan shape (literals ride as device args)
    return (
        "SELECT city, COUNT(*), SUM(v) FROM t "
        f"WHERE v < {50 + i % 40} GROUP BY city ORDER BY city"
    )


# ---------------------------------------------------------------------------
# admission controller units (injected clock — no sleeps, no luck)
# ---------------------------------------------------------------------------
class TestAdmissionController:
    def test_disabled_by_default(self):
        adm = AdmissionController()  # rate 0 = off
        for _ in range(100):
            adm.admit("q", units=50.0)
        assert adm.snapshot()["rate"] == 0.0

    def test_token_bucket_charges_and_refills_on_injected_clock(self):
        sim = [0.0]
        adm = AdmissionController(rate_units_per_s=2.0, burst_units=2.0, max_queue=0)
        adm.clock = lambda: sim[0]
        adm.admit("q1")  # burst 2.0 -> 1.0
        adm.admit("q2")  # -> 0.0
        with pytest.raises(TooManyRequestsError) as ei:
            adm.admit("q3")
        assert ei.value.query_id == "q3"
        sim[0] += 1.0  # repays 2 units
        adm.admit("q4")
        adm.admit("q5")
        assert METRICS.counter("admission.shed").value == 1
        assert METRICS.counter("admission.admitted").value == 4

    def test_oversized_query_is_clamped_to_burst_not_starved(self):
        sim = [0.0]
        adm = AdmissionController(rate_units_per_s=1.0, burst_units=4.0, max_queue=0)
        adm.clock = lambda: sim[0]
        adm.admit("huge", units=1e9)  # min(units, burst): servable, drains bucket
        with pytest.raises(TooManyRequestsError):
            adm.admit("next")

    def test_queue_full_sheds_immediately(self):
        sim = [0.0]
        adm = AdmissionController(rate_units_per_s=1.0, burst_units=1.0, max_queue=0)
        adm.clock = lambda: sim[0]
        adm.admit("q1")
        with pytest.raises(TooManyRequestsError, match="queue full"):
            adm.admit("q2")
        assert adm.snapshot()["waiting"] == 0

    def test_wait_budget_exhaustion_sheds(self):
        sim = [0.0]
        adm = AdmissionController(
            rate_units_per_s=1.0, burst_units=1.0, max_queue=4, max_wait_ms=0.0
        )
        adm.clock = lambda: sim[0]
        adm.admit("q1")
        with pytest.raises(TooManyRequestsError, match="without a token"):
            adm.admit("q2")
        assert adm.snapshot()["waiting"] == 0  # bounded queue drained

    def test_low_priority_sheds_before_queueing(self):
        sim = [0.0]
        adm = AdmissionController(rate_units_per_s=1.0, burst_units=1.0, max_queue=8)
        adm.clock = lambda: sim[0]
        adm.admit("q1")
        with pytest.raises(TooManyRequestsError, match="low-priority"):
            adm.admit("q2", priority=-1)

    def test_waiter_admitted_when_tokens_refill(self):
        # real clock: rate 200 units/s repays one unit in ~5 ms — the waiter
        # parks on the condition and wakes within the 500 ms wait budget
        adm = AdmissionController(rate_units_per_s=200.0, burst_units=1.0, max_queue=8)
        adm.admit("q1")
        adm.admit("q2")  # waits ~5 ms, then admitted
        assert METRICS.counter("admission.admittedAfterWait").value >= 1


# ---------------------------------------------------------------------------
# byte-reservation ledger units
# ---------------------------------------------------------------------------
class TestResourceBudget:
    def test_reserve_release_and_peak(self):
        b = ResourceBudget(1000, gauge="test.reservedBytes")
        t1 = b.reserve(400)
        t2 = b.reserve(500)
        assert b.in_use == 900 and b.peak == 900
        assert METRICS.gauge("test.reservedBytes").value == 900.0
        assert b.release(t1) == 400
        assert b.in_use == 500
        b.release(t2)
        assert b.in_use == 0 and b.peak == 900
        assert METRICS.gauge("test.reservedBytes").value == 0.0

    def test_overcommit_raises_and_leaves_ledger_intact(self):
        b = ResourceBudget(1000)
        b.reserve(900)
        with pytest.raises(ReservationError) as ei:
            b.reserve(200, what="query working set", query_id="qx")
        assert ei.value.query_id == "qx"
        assert isinstance(ei.value, AdmissionError)  # REST 503 family
        assert b.in_use == 900 and b.snapshot()["reservations"] == 1

    def test_cache_charges_share_the_same_ledger(self):
        b = ResourceBudget(1000)
        assert b.try_charge(600)
        with pytest.raises(ReservationError):
            b.reserve(500)  # queries see cache-held bytes
        assert not b.try_charge(600)  # and caches see reservations
        b.uncharge(600)
        b.uncharge(999)  # clamps at zero, never negative
        assert b.in_use == 0

    def test_release_is_idempotent_per_ticket(self):
        b = ResourceBudget(100)
        t = b.reserve(40)
        assert b.release(t) == 40
        assert b.release(t) == 0
        assert b.in_use == 0


# ---------------------------------------------------------------------------
# cost estimation
# ---------------------------------------------------------------------------
class TestCostEstimation:
    def test_cost_scales_with_segment_stats_and_group_by(self):
        coord = _cluster()
        metas = coord.tables["t"].segment_meta.values()
        scan = estimate_query_cost(parse_query("SELECT COUNT(*) FROM t"), metas)
        grouped = estimate_query_cost(parse_query(_sql()), metas)
        assert scan.rows == 4 * 200
        assert scan.hbm_bytes > 0  # coordinator metadata carries segment bytes
        assert scan.group_cardinality == 0
        assert grouped.group_cardinality > 0
        assert grouped.units > scan.units >= 1.0
        assert grouped.host_bytes > scan.host_bytes


# ---------------------------------------------------------------------------
# deterministic overload acceptance: 3x offered load sheds, never crashes
# ---------------------------------------------------------------------------
class TestOverloadAcceptance:
    def test_3x_offered_load_sheds_structured_and_keeps_admitted_latency(self):
        """The mechanism, on the injected clock: which arrivals the bucket
        admits is arithmetic (no host speed in it), a shed is structured and
        costs the servers nothing (no launch, no reservation), and every
        admitted query runs whole under its budgets.  What it no longer
        compares is two wall-clock p99s of ~30 requests each: that ratio
        measured the host's scheduling, and was the one red test of the
        driver's runs of PR 31, 36, 37 and 40 (ROADMAP C9)."""
        host_budget_bytes = 1 << 30
        server_budget_bytes = 64 << 20
        coord = _cluster(server_budget=server_budget_bytes)
        broker = Broker(coord)
        for i in range(3):
            broker.query(_sql(i))  # warm: parse/plan/compile

        capacity_qps = 100.0  # what the bucket is told the cluster sustains: any rate does, the clock is simulated
        unit_cost = estimate_query_cost(
            parse_query(_sql()), coord.tables["t"].segment_meta.values()
        ).units
        gov = _governor(
            rate=capacity_qps * unit_cost,
            burst=2 * unit_cost,
            max_queue=0,
            host_bytes=host_budget_bytes,
        )
        sim = [0.0]
        gov.admission.clock = lambda: sim[0]
        broker.governor = gov

        def served():
            return sum(s.metrics.counter("server.queries").value for s in coord.servers.values())

        offered_qps = 3.0 * capacity_qps
        shed0, admitted0 = METRICS.counter("admission.shed").value, METRICS.counter("admission.admitted").value
        admitted, shed_ids, outcomes, served_by_sheds = 0, [], [], 0
        for i in range(90):
            sim[0] += 1.0 / offered_qps
            before = served()
            try:
                out = broker.query(_sql(i))
            except TooManyRequestsError as e:
                shed_ids.append(e.query_id)
                outcomes.append(False)
                served_by_sheds += served() - before
            else:
                admitted += 1
                outcomes.append(True)
                # an admitted query is answered whole: every segment, no degradation
                assert not out.stats.partial_result and out.stats.num_segments_processed == 4

        # sheds happened, were structured, and carried the minted query id
        assert shed_ids and all(qid for qid in shed_ids) and len(set(shed_ids)) == len(shed_ids)
        # the bucket's own arithmetic on the simulated arrivals: the burst's 2 units first, then a third of a unit
        # back an arrival, so one arrival in three is admitted (a rounding of the refill may make a gap of three)
        assert outcomes[:2] == [True, True]
        at = [i for i, ok in enumerate(outcomes) if ok]
        assert all(3 <= b - a <= 4 for a, b in zip(at[2:], at[3:])) and at[-1] >= 90 - 4
        assert 90 // 3 <= admitted <= 90 // 3 + 2 + 2  # ~1/3 admitted at 3x offered load (plus the burst)
        assert METRICS.counter("admission.shed").value - shed0 == len(shed_ids)
        assert METRICS.counter("admission.admitted").value - admitted0 == admitted
        # a shed query reaches no server: the admitted ones are not charged for the traffic that was turned away
        assert served_by_sheds == 0
        # reservations never exceeded any budget (gauge-backed high-water)
        assert 0 < gov.host_budget.peak <= host_budget_bytes
        for name in ("server0", "server1"):
            srv = coord.servers[name]
            assert 0 < srv.budget.peak <= server_budget_bytes
            assert METRICS.gauge(f"server.reservedBytes.{name}").value == 0.0
        assert METRICS.gauge("admission.hostReservedBytes").value == 0.0
        # nothing queued unboundedly, nothing leaked
        snap = gov.snapshot()
        assert snap["admission"]["waiting"] == 0
        assert snap["hostBudget"]["inUseBytes"] == 0
        assert snap["watchdog"]["activeQueries"] == 0


# ---------------------------------------------------------------------------
# runaway-query watchdog
# ---------------------------------------------------------------------------
class TestWatchdog:
    def test_lazy_runaway_kill_on_injected_clock(self):
        wd = QueryWatchdog(runaway_ms=100.0)
        tick = [0.0]
        wd.clock = lambda: tick[0]
        wd.register("q1", reserved_bytes=123, priority=0)
        assert wd.kill_reason("q1") is None  # within budget
        tick[0] = 0.2  # 200 ms elapsed > 100 ms ceiling
        reason = wd.kill_reason("q1")
        assert reason and "runaway" in reason
        rec = wd.kill_log[-1]
        assert rec.query_id == "q1" and rec.reserved_bytes == 123
        assert rec.elapsed_ms == pytest.approx(200.0)
        wd.deregister("q1")
        assert wd.snapshot()["activeQueries"] == 0

    def test_explicit_kill_and_unknown_query(self):
        wd = QueryWatchdog()
        wd.register("q1")
        assert wd.kill("q1", "operator request")
        assert not wd.kill("q1", "twice")  # already dead
        assert not wd.kill("ghost", "never registered")
        assert wd.kill_reason("q1") == "operator request"

    def test_pressure_patrol_prefers_low_priority_then_largest(self):
        wd = QueryWatchdog(pressure_kill_at=0.9)
        wd.register("big", reserved_bytes=1 << 20, priority=0)
        wd.register("small-low", reserved_bytes=1 << 10, priority=-1)
        assert wd.patrol(0.5) is None  # below threshold
        rec = wd.patrol(0.95)
        assert rec is not None and rec.query_id == "small-low"
        rec2 = wd.patrol(0.95)  # next victim: the remaining query
        assert rec2 is not None and rec2.query_id == "big"

    def test_cluster_kill_releases_resources_and_returns_partial(self):
        coord = _cluster()
        broker = Broker(coord)
        broker.query(_sql())  # warm
        # isolated governor: the env default shares the process host budget
        # with the plan caches, whose resident bytes are not this query's
        gov = _governor()
        broker.governor = gov
        # maxRuntimeMs=0.001: the first between-kernel probe is already past
        # the ceiling — a deterministic mid-flight kill without sleeps
        out = broker.query(
            "SET trace = true; SET allowPartialResults = true; "
            "SET maxRuntimeMs = 0.001; " + _sql()
        )
        assert out.stats.partial_result is True
        kills = [e for e in out.stats.exceptions if e.get("errorCode") == "QUERY_KILLED"]
        assert kills and "runaway" in kills[0]["reason"]
        # reservation released, watchdog drained, kill record retained
        assert gov.host_budget.in_use == 0
        assert gov.watchdog.snapshot()["activeQueries"] == 0
        assert any(r.query_id == out.stats.query_id for r in gov.watchdog.kill_log)
        # kill record in the slow log entry (top-level "kill" field)
        entry = broker.slow_queries.snapshot(limit=1)[0]
        assert entry["kill"]["errorCode"] == "QUERY_KILLED"
        assert entry["queryId"] == out.stats.query_id
        # ... and in the trace tree as a span annotation
        def spans_with_kill(node):
            found = []
            if isinstance(node, dict):
                if "killed" in node.get("attrs", {}):
                    found.append(node)
                for c in node.get("children", []):
                    found.extend(spans_with_kill(c))
            return found
        assert spans_with_kill(out.stats.trace)

    def test_cluster_kill_without_partial_raises_structured(self):
        coord = _cluster()
        broker = Broker(coord)
        broker.query(_sql())  # warm
        broker.governor = _governor()  # isolated ledger (see partial test)
        with pytest.raises(QueryKilledError) as ei:
            broker.query("SET maxRuntimeMs = 0.001; " + _sql())
        assert ei.value.query_id is not None
        assert broker.governor.host_budget.in_use == 0
        assert METRICS.counter("broker.queriesKilled").value >= 1


# ---------------------------------------------------------------------------
# breaker x admission isolation
# ---------------------------------------------------------------------------
class TestBreakerAdmissionIsolation:
    def test_shed_query_never_touches_breaker_or_stats(self, monkeypatch):
        coord = _cluster()
        broker = Broker(coord)
        broker.query(_sql())  # warm
        punished = []
        monkeypatch.setattr(
            broker.server_stats, "punish",
            lambda server, **kw: punished.append(server),
        )
        gov = _governor(rate=1.0, burst=1e-9, max_queue=0)
        sim = [0.0]
        gov.admission.clock = lambda: sim[0]
        gov.admission.admit("drain")  # consume the initial burst
        broker.governor = gov  # frozen clock: every query from here sheds
        for _ in range(5):
            with pytest.raises(TooManyRequestsError):
                broker.query(_sql())
        assert punished == []
        for name in coord.servers:
            assert broker.health.consecutive_failures(name) == 0
            assert broker.health.state(name) == "closed"

    def test_capacity_rejection_fails_over_without_punish_or_breaker(self, monkeypatch):
        coord = _cluster(server_budget=64 << 20)
        baseline = Broker(coord).query(_sql()).rows
        # server0's HBM ledger is committed to a phantom tenant: every
        # reserve() there fails, segments must fail over to server1
        coord.servers["server0"].budget = ResourceBudget(16)
        broker = Broker(coord)
        broker._sleep = lambda s: None
        punished = []
        monkeypatch.setattr(
            broker.server_stats, "punish",
            lambda server, **kw: punished.append(server),
        )
        out = broker.query(_sql())
        assert out.rows == baseline  # failover absorbed the capacity fault
        assert punished == []
        assert broker.health.consecutive_failures("server0") == 0
        assert broker.health.state("server0") == "closed"
        codes = {e["errorCode"] for e in out.stats.exceptions}
        assert "SERVER_OUT_OF_CAPACITY" in codes
        assert METRICS.counter("broker.scatterCapacityRejections").value >= 1

    def test_every_replica_out_of_capacity_is_structured_not_scatter_error(self):
        coord = _cluster(server_budget=64 << 20)
        for s in coord.servers.values():
            s.budget = ResourceBudget(16)
        broker = Broker(coord)
        broker._sleep = lambda s: None
        with pytest.raises(ReservationError) as ei:
            broker.query(_sql())
        assert ei.value.query_id is not None
        for name in coord.servers:
            assert broker.health.state(name) == "closed"

    def test_killed_query_punishes_exactly_once(self, monkeypatch):
        coord = _cluster()
        broker = Broker(coord)
        broker.query(_sql())  # warm
        punished = []
        monkeypatch.setattr(
            broker.server_stats, "punish",
            lambda server, **kw: punished.append(server),
        )
        out = broker.query(
            "SET allowPartialResults = true; SET maxRuntimeMs = 0.001; " + _sql()
        )
        assert out.stats.partial_result is True
        assert len(punished) == 1  # exactly once, not per retry round
        for name in coord.servers:
            assert broker.health.consecutive_failures(name) == 0

    def test_concurrent_mixed_outcomes_leave_ledgers_clean(self):
        coord = _cluster()
        broker = Broker(coord)
        broker.query(_sql())  # warm
        unit_cost = estimate_query_cost(
            parse_query(_sql()), coord.tables["t"].segment_meta.values()
        ).units
        gov = _governor(rate=1.0, burst=8.0 * unit_cost, max_queue=0)
        sim = [0.0]
        gov.admission.clock = lambda: sim[0]
        broker.governor = gov  # 8 queries' worth of tokens: half of 16 shed
        outcomes = {"ok": 0, "shed": 0, "other": 0}
        olock = threading.Lock()

        def worker(i):
            try:
                broker.query(_sql(i))
            except TooManyRequestsError:
                with olock:
                    outcomes["shed"] += 1
            except Exception:
                with olock:
                    outcomes["other"] += 1
            else:
                with olock:
                    outcomes["ok"] += 1

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert outcomes["other"] == 0
        assert outcomes["ok"] + outcomes["shed"] == 16
        assert outcomes["ok"] >= 8 and outcomes["shed"] >= 1
        assert gov.host_budget.in_use == 0
        assert gov.snapshot()["admission"]["waiting"] == 0
        assert gov.snapshot()["watchdog"]["activeQueries"] == 0
        for name in coord.servers:
            assert broker.health.consecutive_failures(name) == 0


# ---------------------------------------------------------------------------
# graceful degradation
# ---------------------------------------------------------------------------
class TestDegradation:
    def test_pressure_ladder_levels_and_flags(self):
        d = DegradationController()
        assert d.update(0.5) == 0 and d.result_cache_enabled()
        assert d.update(0.70) == 1
        assert not d.result_cache_enabled() and d.shed_low_priority()
        assert d.update(0.85) == 2
        assert d.update(0.95) == 3
        assert METRICS.gauge("admission.pressureLevel").value == 3.0
        assert d.update(0.1) == 0  # pressure release restores everything

    def test_pipeline_depth_shrinks_then_serializes(self):
        assert pipeline_depth_under_pressure(4, 0) == 4
        assert pipeline_depth_under_pressure(4, 1) == 4
        assert pipeline_depth_under_pressure(4, 2) == 3
        assert pipeline_depth_under_pressure(4, 3) == 1  # fully serialized
        assert pipeline_depth_under_pressure(1, 2) == 1  # floor

    def test_low_priority_shed_under_host_pressure(self):
        gov = _governor(host_bytes=1000)
        gov.host_budget.reserve(800)  # occupancy 0.8 -> level 1
        ctx = parse_query("SET isSecondaryWorkload = true; SELECT COUNT(*) FROM t")
        cost = QueryCost(rows=10, hbm_bytes=10, group_cardinality=0, host_bytes=10)
        with pytest.raises(TooManyRequestsError, match="low-priority"):
            gov.admit("q-low", ctx, cost)
        # a normal-priority query still gets through at level 1
        grant = gov.admit("q-norm", parse_query("SELECT COUNT(*) FROM t"), cost)
        grant.close()
        assert gov.host_budget.in_use == 800  # only the phantom reservation

    def test_result_cache_bypassed_under_pressure(self):
        coord = _cluster()
        broker = Broker(coord)
        sql = "SET useResultCache = true; " + _sql()
        broker.query(sql)  # populate
        assert broker.query(sql).stats.result_cache == "hit"
        # real pressure, not a poked level: admit() recomputes the level
        # from occupancy on every query, so only a held reservation sticks
        gov = _governor(host_bytes=32 << 20)
        broker.governor = gov
        # 75% reserved -> level 1, with headroom left for the query's own
        # ~3 MB working-set reservation (bypass, not rejection)
        ticket = gov.host_budget.reserve(int(0.75 * (32 << 20)))
        # bypassed = the cache was never consulted, so no hit/miss at all
        assert getattr(broker.query(sql).stats, "result_cache", None) is None
        gov.host_budget.release(ticket)  # pressure drains -> cache resumes
        assert broker.query(sql).stats.result_cache == "hit"


# ---------------------------------------------------------------------------
# cache byte-accounting against the shared host budget
# ---------------------------------------------------------------------------
class TestCacheBudgetUnification:
    def test_lru_cache_charges_and_releases_budget(self):
        from pinot_tpu.utils.cache import LruCache

        budget = ResourceBudget(10_000)
        c = LruCache(max_entries=64, name="test.cache", budget=budget)
        c.put("a", np.zeros(500, dtype=np.int8))  # ~500 bytes + overhead
        assert budget.in_use > 0
        held = budget.in_use
        c.put("b", np.zeros(500, dtype=np.int8))
        assert budget.in_use > held
        c.invalidate("a")
        c.invalidate("b")
        assert budget.in_use == 0

    def test_full_budget_forces_eviction_not_growth(self):
        from pinot_tpu.utils.cache import LruCache

        budget = ResourceBudget(10_000)
        budget.reserve(9_000)  # queries hold most of the ledger
        c = LruCache(max_entries=64, name="test.cache", budget=budget)
        for i in range(10):
            c.put(f"k{i}", np.zeros(400, dtype=np.int8))
        # the cache never pushed the ledger past its budget: it evicted
        assert budget.peak <= 10_000
        assert len(c) < 10
        c.clear()
        assert budget.in_use == 9_000  # only the query reservation remains

    def test_entry_too_big_for_remaining_budget_is_dropped(self):
        from pinot_tpu.utils.cache import LruCache

        budget = ResourceBudget(1_000)
        budget.reserve(900)
        c = LruCache(max_entries=64, name="test.cache", budget=budget)
        c.put("big", np.zeros(5_000, dtype=np.int8))
        assert c.get("big") is None and len(c) == 0
        assert budget.in_use == 900

    def test_broker_result_cache_rides_the_governor_host_budget(self):
        coord = _cluster()
        broker = Broker(coord)
        host = broker.governor.host_budget
        assert broker.result_cache.budget is host
        before = host.in_use
        broker.query("SET useResultCache = true; " + _sql())
        assert host.in_use > before  # cached rows are ledgered bytes
        broker.result_cache.clear()
        assert host.in_use == before

    def test_plan_cache_attached_to_process_budget(self):
        from pinot_tpu.query.planner import _PLAN_CACHE

        coord = _cluster()
        broker = Broker(coord)
        assert _PLAN_CACHE.budget is broker.governor.host_budget


# ---------------------------------------------------------------------------
# REST surface parity
# ---------------------------------------------------------------------------
class TestRestOverloadSurface:
    def _post(self, port, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/query/sql",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req) as resp:
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    def _get(self, port, path):
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as resp:
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    def test_shed_maps_to_429_with_request_id(self):
        from pinot_tpu.cluster.rest import QueryServer

        broker = Broker(_cluster())
        gov = _governor(rate=1.0, burst=1e-9, max_queue=0)
        sim = [0.0]
        gov.admission.clock = lambda: sim[0]
        gov.admission.admit("drain")  # consume the initial burst
        broker.governor = gov  # frozen clock: the POST below sheds
        srv = QueryServer(broker).start()
        try:
            code, payload = self._post(srv.port, {"sql": _sql()})
            assert code == 429
            assert payload["errorCode"] == "TOO_MANY_REQUESTS_ERROR"
            assert payload["requestId"]
        finally:
            srv.stop()

    def test_capacity_maps_to_503_out_of_capacity(self):
        from pinot_tpu.cluster.rest import QueryServer

        coord = _cluster()
        for s in coord.servers.values():
            s.budget = ResourceBudget(16)
        broker = Broker(coord)
        broker._sleep = lambda s: None
        srv = QueryServer(broker).start()
        try:
            code, payload = self._post(srv.port, {"sql": _sql()})
            assert code == 503
            assert payload["errorCode"] == "SERVER_OUT_OF_CAPACITY"
            assert payload["requestId"]
        finally:
            srv.stop()

    def test_kill_maps_to_503_query_killed_with_reason(self):
        from pinot_tpu.cluster.rest import QueryServer

        broker = Broker(_cluster())
        broker.query(_sql())  # warm so the killed run reaches the probe fast
        srv = QueryServer(broker).start()
        try:
            code, payload = self._post(
                srv.port, {"sql": "SET maxRuntimeMs = 0.001; " + _sql()}
            )
            assert code == 503
            assert payload["errorCode"] == "QUERY_KILLED"
            assert payload["requestId"]
            assert "runaway" in payload["reason"]
        finally:
            srv.stop()

    def test_killed_partial_carries_exception_detail_at_200(self):
        from pinot_tpu.cluster.rest import QueryServer

        broker = Broker(_cluster())
        broker.query(_sql())  # warm
        srv = QueryServer(broker).start()
        try:
            code, payload = self._post(
                srv.port,
                {"sql": "SET allowPartialResults = true; SET maxRuntimeMs = 0.001; " + _sql()},
            )
            assert code == 200
            assert payload["partialResult"] is True
            assert any(
                e.get("errorCode") == "QUERY_KILLED" for e in payload["exceptions"]
            )
        finally:
            srv.stop()

    def test_debug_admission_snapshot(self):
        from pinot_tpu.cluster.rest import QueryServer

        broker = Broker(_cluster())
        srv = QueryServer(broker).start()
        try:
            code, payload = self._get(srv.port, "/debug/admission")
            assert code == 200
            assert set(payload) >= {"pressureLevel", "admission", "hostBudget", "watchdog"}
            assert payload["hostBudget"]["budgetBytes"] > 0
        finally:
            srv.stop()
