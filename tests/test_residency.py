"""Tiered segment storage (round 14, segment/residency.py).

HBM is a byte-budgeted cache over host RAM: these tests drive the residency
state machine (host-only -> staging -> resident -> evicting) under
concurrency, kill a stage mid-flight through the r12 crash harness and
assert the budget ledger never leaks, race queries against evictions to
prove a group's raw and #packed flavors drop atomically (a reader can
never observe half a segment), check the prefetch-hit accounting parity of
the engine's double-buffered staging stream, and pin the staged-fetch
admission semantics (ReservationError only when the working set cannot fit
even transiently).
"""
import itertools
import threading
import time

import numpy as np
import pytest

from pinot_tpu.cluster.admission import ReservationError, ResourceBudget
from pinot_tpu.parallel.engine import DistributedEngine
from pinot_tpu.parallel.stacked import StackedTable
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.residency import (
    EVICTING,
    HIT,
    HOST_ONLY,
    OWN,
    RESIDENT,
    STAGING,
    WAIT,
    ResidencyManager,
)
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.utils import crashpoints
from pinot_tpu.utils.metrics import METRICS
from pinot_tpu.utils.perf import ShapeStats

_seq = itertools.count()


def _mgr(budget_bytes, ledger=None):
    """Fresh manager with a unique metrics namespace (global registry)."""
    return ResidencyManager(
        ResourceBudget(budget_bytes), name=f"res.t{next(_seq)}", ledger=ledger
    )


def _segment(name="segres", n=4096):
    schema = Schema(
        name,
        [
            FieldSpec("g", DataType.INT),
            FieldSpec("v", DataType.INT, role=FieldRole.METRIC),
        ],
    )
    rng = np.random.default_rng(3)
    return build_segment(
        schema,
        {
            "g": rng.integers(0, 16, n).astype(np.int32),
            "v": rng.integers(0, 100, n).astype(np.int32),
        },
        "s0",
    )


# ---------------------------------------------------------------------------
# state machine
# ---------------------------------------------------------------------------


class TestStateMachine:
    def test_own_stage_commit_hit_evict(self):
        res = _mgr(1_000)
        evicted = []
        g = ("seg", 1, None)
        st, e = res.begin_stage(g, "t", lambda: evicted.append(g))
        assert st == OWN and res.state_of(g) == STAGING
        res.charge(g, 400)
        res.finish_stage(g)
        assert res.state_of(g) == RESIDENT
        assert res.resident_bytes == 400 == res.budget.in_use
        st2, _ = res.begin_stage(g, "t", lambda: None)
        assert st2 == HIT
        assert res.evict(g)
        assert evicted == [g]
        assert res.state_of(g) == HOST_ONLY
        assert res.resident_bytes == 0 == res.budget.in_use

    def test_waiters_park_then_hit_after_commit(self):
        res = _mgr(1_000)
        g = ("seg", 2, None)
        st, _ = res.begin_stage(g, "t", lambda: None)
        assert st == OWN
        statuses = []

        def waiter():
            s, entry = res.begin_stage(g, "t", lambda: None)
            statuses.append(s)
            if s == WAIT:
                assert res.wait(entry, timeout_s=5.0)

        threads = [threading.Thread(target=waiter) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)  # let waiters park on the STAGING entry
        res.charge(g, 100)
        res.finish_stage(g)
        for t in threads:
            t.join()
        assert statuses == [WAIT] * 4
        assert res.state_of(g) == RESIDENT

    def test_abort_of_fresh_stage_removes_entry_and_uncharges(self):
        res = _mgr(1_000)
        g = ("seg", 3, None)
        res.begin_stage(g, "t", lambda: None)
        res.charge(g, 300)
        assert res.budget.in_use == 300
        res.abort_stage(g)
        assert res.state_of(g) == HOST_ONLY
        assert res.budget.in_use == 0 and res.resident_bytes == 0

    def test_abort_of_grow_reverts_to_resident(self):
        res = _mgr(1_000)
        g = ("seg", 4, None)
        res.begin_stage(g, "t", lambda: None)
        res.charge(g, 200)
        res.finish_stage(g)
        st, _ = res.begin_grow(g)
        assert st == OWN and res.state_of(g) == STAGING
        res.charge(g, 150)
        res.abort_stage(g)
        # the committed 200 bytes survive; only the grow's 150 unwind
        assert res.state_of(g) == RESIDENT
        assert res.resident_bytes == 200 == res.budget.in_use


# ---------------------------------------------------------------------------
# cost-aware eviction
# ---------------------------------------------------------------------------


class TestCostRankedEviction:
    def test_cold_table_evicted_before_hot_despite_recency(self):
        ledger = ShapeStats()
        # hot table: high bytes/s in the r13 ledger -> expensive to refetch
        ledger.record("hotT", "fp", rows=1e6, time_ms=10.0, kernel_bytes=1e9)
        res = _mgr(1_000, ledger=ledger)
        evicted = []
        a, b, c = ("a", None), ("b", None), ("c", None)
        res.begin_stage(a, "coldT", lambda: evicted.append("a"))
        res.charge(a, 400)
        res.finish_stage(a)
        res.begin_stage(b, "hotT", lambda: evicted.append("b"))
        res.charge(b, 400)
        res.finish_stage(b)
        res.touch(a)  # pure LRU would now pick b; the heat signal must win
        res.begin_stage(c, "t3", lambda: evicted.append("c"))
        res.charge(c, 400)
        res.finish_stage(c)
        assert evicted == ["a"]
        assert res.state_of(a) == HOST_ONLY and res.state_of(b) == RESIDENT

    def test_lru_fallback_without_ledger_signal(self):
        res = _mgr(1_000)
        evicted = []
        a, b, c = ("a", None), ("b", None), ("c", None)
        for g, nm in ((a, "a"), (b, "b")):
            res.begin_stage(g, "t", lambda nm=nm: evicted.append(nm))
            res.charge(g, 400)
            res.finish_stage(g)
        res.touch(a)  # b is now least-recent
        res.begin_stage(c, "t", lambda: evicted.append("c"))
        res.charge(c, 400)
        res.finish_stage(c)
        assert evicted == ["b"]

    def test_unfittable_charge_raises_and_unwinds(self):
        res = _mgr(100)
        g = ("seg", 9, None)
        res.begin_stage(g, "t", lambda: None)
        with pytest.raises(ReservationError):
            res.charge(g, 200)
        res.abort_stage(g)
        assert res.state_of(g) == HOST_ONLY
        assert res.budget.in_use == 0 and res.resident_bytes == 0


# ---------------------------------------------------------------------------
# mid-stage kill (r12 crash harness): no budget leak
# ---------------------------------------------------------------------------


class TestMidStageCrash:
    @pytest.fixture(autouse=True)
    def _clean_points(self):
        crashpoints.reset()
        yield
        crashpoints.reset()

    @pytest.mark.parametrize(
        "point", ["segment.stage.after_charge", "segment.stage.after_copy"]
    )
    def test_killed_stage_leaves_no_ledger_leak_and_retries_clean(self, point):
        seg = _segment()
        res = _mgr(10 << 20)
        crashpoints.arm(point)
        with pytest.raises(crashpoints.InjectedCrash):
            seg.to_device(residency=res)
        g = seg.device_group(None)
        assert res.state_of(g) == HOST_ONLY
        assert res.budget.in_use == 0 and res.resident_bytes == 0
        # the point disarmed on firing: the post-restart retry commits
        cols = seg.to_device(residency=res)
        assert set(cols) == set(seg.column_names)
        assert res.state_of(g) == RESIDENT
        assert res.budget.in_use == res.resident_bytes > 0

    def test_killed_grow_keeps_committed_bytes(self):
        seg = _segment()
        res = _mgr(10 << 20)
        seg.to_device(columns=["g"], residency=res)
        committed = res.resident_bytes
        assert committed > 0
        crashpoints.arm("segment.stage.after_copy")
        with pytest.raises(crashpoints.InjectedCrash):
            seg.to_device(columns=["g", "v"], residency=res)
        assert res.state_of(seg.device_group(None)) == RESIDENT
        assert res.budget.in_use == res.resident_bytes == committed
        cols = seg.to_device(columns=["g", "v"], residency=res)
        assert set(cols) == {"g", "v"}
        assert res.budget.in_use == res.resident_bytes > committed


# ---------------------------------------------------------------------------
# atomic flavor eviction: a reader never mixes tiers
# ---------------------------------------------------------------------------


class TestAtomicFlavorEviction:
    def test_concurrent_readers_race_eviction_without_mixing(self):
        """Readers alternate raw and #packed requests while an evictor
        drops the group; every assembled pytree must be complete for the
        requested flavor (assemble returns None on a half-evicted cache and
        the reader re-stages — satellite fix r17)."""
        seg = _segment(n=8192)
        res = _mgr(10 << 20)
        stop = threading.Event()
        errors = []

        def reader(packed):
            try:
                for _ in range(30):
                    cols = seg.to_device(packed_codes=packed, residency=res)
                    if set(cols) != set(seg.column_names):
                        errors.append(f"partial pytree: {sorted(cols)}")
            except Exception as exc:  # pragma: no cover - failure detail
                errors.append(repr(exc))

        def evictor():
            while not stop.is_set():
                res.evict(seg.device_group(None))
                time.sleep(0.001)

        threads = [threading.Thread(target=reader, args=(p,)) for p in (False, True)]
        ev = threading.Thread(target=evictor)
        ev.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        ev.join()
        assert errors == []
        # ledger is exact after the dust settles: committed == charged
        assert res.budget.in_use == res.resident_bytes

    def test_single_owner_stages_group_once(self):
        seg = _segment()
        res = _mgr(10 << 20)
        miss0 = METRICS.counter(f"{res.name}.misses").value
        barrier = threading.Barrier(6)
        outs = []
        lock = threading.Lock()

        def worker():
            barrier.wait()
            cols = seg.to_device(residency=res)
            with lock:
                outs.append(set(cols))

        threads = [threading.Thread(target=worker) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(o == set(seg.column_names) for o in outs)
        # one miss -> one staging owner; everyone else waited or hit
        assert METRICS.counter(f"{res.name}.misses").value - miss0 == 1


    @pytest.mark.parametrize("codes_first", [False, True], ids=["both_at_once", "codes_resident_before"])
    def test_the_decoded_flavour_is_charged_evicted_and_restaged(self, codes_first):
        """`<col>#values` (PR 49: a dictionary column handed out decoded) is
        a flavour like #packed: _entry_bytes charges what it pins, the
        group's eviction drops it with the rest, a re-stage makes it again;
        it rides in one entry with the column's codes, nulls and dictionary,
        staged with them or added to a group that held them already."""
        seg = _segment(n=8192)
        c = seg.column("g")
        c.nulls = np.random.default_rng(5).random(8192) < 0.1
        res = _mgr(10 << 20)
        want = c.dictionary.device_values()[c.codes]
        decoded = 8192 * 4
        assert seg._entry_bytes(c, False, decoded=True) == decoded
        rest = seg._entry_bytes(seg.column("v"), False) + seg._entry_bytes(c, True)
        for again in range(2):
            if codes_first:
                plain = seg.to_device(columns=["g", "v"], packed_codes=True, residency=res)
                assert sorted(plain["g"]) == ["codes_packed", "dict", "nulls"] and res.resident_bytes == rest
                assert seg.resident(None, ["g", "v"], True)
            assert not seg.resident(None, ["g", "v"], True, {"g"})
            cols = seg.to_device(columns=["g", "v"], packed_codes=True, residency=res, value_columns=frozenset({"g"}))
            assert seg.resident(None, ["g", "v"], True, {"g"})
            assert sorted(cols["g"]) == ["codes_packed", "dict", "nulls", "values"]
            assert np.array_equal(np.asarray(cols["g"]["values"]), want)
            assert np.array_equal(np.asarray(cols["g"]["nulls"]), c.nulls)
            assert sorted(seg._device_cache[None]) == ["g#packed", "g#values", "v"]
            assert res.resident_bytes == res.budget.in_use == decoded + rest
            # the code flavour alone is another request: it finds its entry, and leaves #values where it is
            plain = seg.to_device(columns=["g"], packed_codes=True, residency=res)
            assert sorted(plain["g"]) == ["codes_packed", "dict", "nulls"]
            assert res.resident_bytes == decoded + rest
            res.evict(seg.device_group(None))
            assert None not in seg._device_cache and res.resident_bytes == res.budget.in_use == 0


# ---------------------------------------------------------------------------
# staged-fetch admission (reserve_or_wait)
# ---------------------------------------------------------------------------


class TestStagedFetchAdmission:
    def test_rejects_immediately_when_unfittable_even_transiently(self):
        b = ResourceBudget(100)
        with pytest.raises(ReservationError, match="even\\s+transiently"):
            b.reserve_or_wait(150, max_wait_ms=5_000)

    def test_parks_until_release_then_admits(self):
        b = ResourceBudget(100)
        t = b.reserve(80)
        served0 = METRICS.counter("admission.stagedFetchServed").value

        def releaser():
            time.sleep(0.05)
            b.release(t)

        th = threading.Thread(target=releaser)
        th.start()
        ticket = b.reserve_or_wait(50, max_wait_ms=5_000)
        th.join()
        assert b.in_use == 50
        assert METRICS.counter("admission.stagedFetchServed").value == served0 + 1
        b.release(ticket)

    def test_times_out_to_out_of_capacity(self):
        b = ResourceBudget(100)
        b.reserve(80)
        t0 = METRICS.counter("admission.stagedFetchTimeouts").value
        with pytest.raises(ReservationError, match="staged wait"):
            b.reserve_or_wait(50, max_wait_ms=40)
        assert METRICS.counter("admission.stagedFetchTimeouts").value == t0 + 1


# ---------------------------------------------------------------------------
# engine integration: tiered vs pinned bit-exactness + prefetch accounting
# ---------------------------------------------------------------------------

N = 64 * 1024  # with launch_bytes=8000 the doc axis splits into ~5 batches


@pytest.fixture(scope="module")
def tiered_pair():
    schema = Schema(
        "t",
        [
            FieldSpec("d", DataType.INT),
            FieldSpec("v", DataType.INT, role=FieldRole.METRIC),
        ],
    )
    rng = np.random.default_rng(5)
    data = {
        "d": rng.integers(0, 64, N).astype(np.int32),
        "v": rng.integers(-50, 50, N).astype(np.int32),
    }

    def build(cache_bytes):
        eng = DistributedEngine(launch_bytes=8_000, hbm_cache_bytes=cache_bytes)
        eng.register_table("t", StackedTable.build(schema, dict(data), eng.num_devices))
        return eng

    # cache ~= 1/3 of the working set: every query cycles through eviction
    tiered, ref = build(128_000), build(0)
    yield tiered, ref
    tiered.residency.shutdown()


QUERIES = [
    "SELECT COUNT(*), SUM(v), MIN(v), MAX(v) FROM t",
    "SELECT COUNT(*), SUM(v) FROM t WHERE d < 32",
    "SELECT d, COUNT(*), SUM(v) FROM t GROUP BY d ORDER BY d LIMIT 70",
]


class TestTieredEngine:
    def test_over_budget_working_set_is_bit_exact(self, tiered_pair):
        tiered, ref = tiered_pair
        ev0 = METRICS.counter("residency.evictions").value
        for q in QUERIES:
            assert tiered.query(q).rows == ref.query(q).rows
        assert METRICS.counter("residency.evictions").value > ev0

    def test_queries_racing_manager_evictions_stay_exact(self, tiered_pair):
        tiered, ref = tiered_pair
        q = QUERIES[2]
        expect = ref.query(q).rows
        stop = threading.Event()

        def evictor():
            while not stop.is_set():
                tiered.residency.evict_matching(lambda g: True)
                time.sleep(0.002)

        th = threading.Thread(target=evictor)
        th.start()
        try:
            for _ in range(6):
                assert tiered.query(q).rows == expect
        finally:
            stop.set()
            th.join()

    def test_prefetch_hit_accounting_parity(self, tiered_pair):
        """Every streamed macro-batch is consumed exactly once as either a
        prefetch hit or a staging stall — identical reruns see identical
        hit+stall deltas (the sweep's hit-rate denominator is exact)."""
        tiered, _ = tiered_pair
        q = QUERIES[1]
        tiered.query(q)  # warm compile

        def delta():
            h0 = METRICS.counter("engine.prefetchHits").value
            s0 = METRICS.counter("engine.stagingStalls").value
            tiered.query(q)
            return (
                METRICS.counter("engine.prefetchHits").value - h0,
                METRICS.counter("engine.stagingStalls").value - s0,
            )

        h1, s1 = delta()
        h2, s2 = delta()
        assert h1 + s1 == h2 + s2 > 1
