"""Headline bench: SSB-style group-by scan rate on real TPU hardware.

Config 2 of BASELINE.json: lineorder `WHERE lo_quantity < 25 GROUP BY
lo_orderdate SUM(lo_revenue)` — filter + dense group-by aggregation, the
reference's hot path (BenchmarkQueriesSSQE shape).  The filter column
carries a RANGE INDEX (round 3): the compiled kernel reads prefix-bitmap
word slices instead of scanning codes, and `filter_index_uses` in the
output proves the indexed path ran.  Prints ONE JSON line.

Two timings are reported (round-3 methodology fix — both recorded so rounds
stay comparable):

  value / value_marginal  — MARGINAL per-query kernel time: K queries run
      inside one program (lax.fori_loop whose body depends on the loop index
      so XLA cannot hoist it); median slope over >=3 interleaved
      (t_1, t_K) pairs, each the min of 3 runs (round-5 hardening: a
      single contended pair understated r4 by 21x).  The
      estimate is cross-checked against the subtraction-free amortized
      floor n*K/min(t_K); >25% disagreement triggers re-measurement, and
      the reported value is max(median slope, amortized floor) with the
      pair spread in `run_variance`.  Excludes input transfer and the
      host reduce tail (group-table-sized, row-count independent):
      columns stay pinned in HBM between queries.
  value_e2e — full DistributedEngine.execute() wall clock (parse reuse,
      kernel, device_get, broker reduce), min of 3 after warm-up: the
      query latency a library caller sees.

vs_baseline: the reference publishes no absolute numbers (BASELINE.md).
The denominator is the ASSUMED 5e8 rows/s whole-server Java scan rate
(kept constant across rounds for comparability).  To bracket the
assumption, `cpu_proxy_rows_per_sec` measures a single-core numpy
scan-aggregate of the same query in-image (extrapolated from a 8M-row
sample); BASELINE.md records the provenance of both.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

JAVA_SERVER_ROWS_PER_SEC = 5e8  # assumed reference throughput (see docstring)
N_ROWS = int(os.environ.get("BENCH_ROWS", 1 << 27))  # 134M default; 1<<30 for the 1B run
K_ITERS = 8


def _cpu_proxy(sample_rows: int = 1 << 23) -> float:
    """Single-core numpy scan-aggregate proxy for the Java-server denominator:
    same query shape (mask + filtered segmented sum) on a smaller sample."""
    rng = np.random.default_rng(7)
    od = rng.integers(0, 2406, sample_rows).astype(np.int32)
    qty = rng.integers(1, 51, sample_rows).astype(np.int8)
    rev = rng.integers(100, 1_000_000, sample_rows).astype(np.int64)
    t0 = time.perf_counter()
    mask = qty < 25
    np.bincount(od[mask], weights=rev[mask], minlength=2406)
    dt = time.perf_counter() - t0
    return sample_rows / dt


def _overload_bench() -> dict:
    """Offered-load sweep through the broker's admission controller (round-11
    overload governance): estimate single-stream capacity on a small broker
    cluster, then offer 0.5x / 1x / 3x that rate with the token bucket
    clocked by the *simulated* arrival times (deterministic: admission
    depends only on the arrival schedule, not host speed).  Reports
    admitted/shed/killed counts and the admitted-query p99 — the tracked
    proof that 3x overload sheds with structured 429s instead of queueing
    unboundedly or crashing."""
    from pinot_tpu.cluster.admission import (
        AdmissionController,
        QueryKilledError,
        ReservationError,
        TooManyRequestsError,
        estimate_query_cost,
    )
    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.coordinator import Coordinator
    from pinot_tpu.cluster.server import ServerInstance
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.config import SegmentsConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    schema = Schema(
        "t",
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
            FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
        ],
    )
    coord = Coordinator(replication=2)
    for i in range(2):
        coord.register_server(ServerInstance(f"server{i}"))
    coord.add_table(schema, TableConfig(name="t", segments=SegmentsConfig(time_column="ts")))
    rng = np.random.default_rng(11)
    rows = int(os.environ.get("BENCH_OVERLOAD_ROWS", 50_000))
    for i in range(4):
        coord.add_segment(
            "t",
            build_segment(
                schema,
                {
                    "city": rng.choice(["sf", "nyc", "la"], rows).astype(object),
                    "v": rng.integers(0, 100, rows),
                    "ts": 1_700_000_000_000 + rng.integers(0, 86_400_000, rows).astype(np.int64),
                },
                f"seg{i}",
            ),
        )
    broker = Broker(coord)

    # distinct literal per query: misses the result cache every time (full
    # scatter path) while the parameterized plan cache stays warm
    def sql_at(i: int) -> str:
        return (
            "SELECT city, COUNT(*), SUM(v) FROM t "
            f"WHERE v < {50 + i % 40} GROUP BY city ORDER BY city"
        )

    broker.query(sql_at(0))  # warm: parse, plan, compile

    # ---- uncontended baseline (governor at env defaults: admission off) --
    n_base = 40
    base_ts = []
    for i in range(n_base):
        t0 = time.perf_counter()
        broker.query(sql_at(i))
        base_ts.append((time.perf_counter() - t0) * 1000)
    uncontended_p99 = float(np.percentile(base_ts, 99))
    capacity_qps = 1000.0 / float(np.median(base_ts))

    ctx = parse_query(sql_at(0))
    unit_cost = estimate_query_cost(ctx, coord.tables["t"].segment_meta.values()).units

    sweep = []
    for mult in (0.5, 1.0, 3.0):
        # fresh bucket per load point, clocked by the simulated arrival
        # schedule; max_queue=0 = admit-or-shed (the sim clock never
        # advances inside a wait, so queueing would never drain)
        sim = [0.0]
        adm = AdmissionController(
            rate_units_per_s=capacity_qps * unit_cost,
            burst_units=2 * unit_cost,
            max_queue=0,
        )
        adm.clock = lambda: sim[0]
        broker.governor.admission = adm
        offered_qps = mult * capacity_qps
        admitted = shed = killed = 0
        admitted_ms = []
        for i in range(120):
            sim[0] += 1.0 / offered_qps  # next arrival
            t0 = time.perf_counter()
            try:
                broker.query(sql_at(i))
            except TooManyRequestsError:
                shed += 1
            except (QueryKilledError, ReservationError):
                killed += 1
            else:
                admitted += 1
                admitted_ms.append((time.perf_counter() - t0) * 1000)
        sweep.append(
            {
                "offered_x": mult,
                "offered_qps": round(offered_qps, 1),
                "admitted": admitted,
                "shed": shed,
                "killed": killed,
                "admitted_p99_ms": (
                    round(float(np.percentile(admitted_ms, 99)), 3) if admitted_ms else None
                ),
            }
        )
    broker.governor.admission = AdmissionController()  # back to permissive
    return {
        "uncontended_p99_ms": round(uncontended_p99, 3),
        "capacity_qps_est": round(capacity_qps, 1),
        "sweep": sweep,
    }


def _tail_latency_bench() -> dict:
    """Tail-latency section (round-15 tail tolerance): one of two replicas
    degraded to ~10x latency by a seeded FaultPlan jitter rule, measured
    three ways — fault-free baseline, degraded without hedging, degraded
    with hedged scatter (delay derived from the healthy peer's observed
    p95).  Brownout deprioritization is disabled for the sweep so it
    isolates hedging from routing-away; the brownout path has its own
    tests.  Reports p50/p99 per leg plus the hedge rate and wasted-work %
    — `hedged_p99_ms` is a lower-is-better metric in the `cli perf
    --check` regression gate."""
    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.coordinator import Coordinator
    from pinot_tpu.cluster.faults import FaultPlan
    from pinot_tpu.cluster.server import ServerInstance
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.config import SegmentsConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.utils.metrics import METRICS

    rows = int(os.environ.get("BENCH_TAIL_ROWS", 5_000))
    n_meas = int(os.environ.get("BENCH_TAIL_QUERIES", 60))
    slow_mult = float(os.environ.get("BENCH_TAIL_SLOW_MULT", "10"))

    def make_cluster():
        schema = Schema(
            "t",
            [
                FieldSpec("city", DataType.STRING),
                FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
                FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
            ],
        )
        coord = Coordinator(replication=2)
        for i in range(2):
            coord.register_server(ServerInstance(f"server{i}"))
        coord.add_table(schema, TableConfig(name="t", segments=SegmentsConfig(time_column="ts")))
        rng = np.random.default_rng(11)
        for i in range(4):
            coord.add_segment(
                "t",
                build_segment(
                    schema,
                    {
                        "city": rng.choice(["sf", "nyc", "la"], rows).astype(object),
                        "v": rng.integers(0, 100, rows),
                        "ts": 1_700_000_000_000
                        + rng.integers(0, 86_400_000, rows).astype(np.int64),
                    },
                    f"seg{i}",
                ),
            )
        return coord, Broker(coord)

    def sql_at(i: int) -> str:
        # distinct literal per query: misses the result cache every time
        return (
            "SELECT city, COUNT(*), SUM(v) FROM t "
            f"WHERE v < {50 + i % 40} GROUP BY city ORDER BY city"
        )

    hedge_counters = ("hedgesLaunched", "hedgeWins", "hedgesCancelled", "hedgesDenied")

    def run_leg(slow_ms: float, hedge: bool) -> dict:
        coord, broker = make_cluster()
        if slow_ms > 0:
            # balanced round-robin routing sends 2 of 4 segments to each
            # server, so every query's scatter includes the slow replica
            FaultPlan(seed=17).jitter("server0", base_ms=slow_ms, sigma=0.5).attach(coord)
        broker.health.brownout_factor = float("inf")  # isolate hedging
        hc = broker.hedge
        hc.enabled_default = hedge
        hc.budget_pct = 60.0  # 2-server scatter: 1 hedge per query = 50% of launches
        c0 = {k: METRICS.counter(f"broker.{k}").value for k in hedge_counters}
        w0 = (
            METRICS.timer("broker.hedgeWastedMs").total_ms
            + METRICS.timer("broker.hedgeCancelMs").total_ms
        )
        leg_t0 = time.perf_counter()
        broker.query(sql_at(0))  # warm: parse, plan, compile
        # fill the per-(table, server) latency windows so the hedge delay is
        # derived from observed peer quantiles rather than an env override
        for i in range(hc.min_samples + 2):
            broker.query(sql_at(i))
        ts = []
        for i in range(n_meas):
            t0 = time.perf_counter()
            broker.query(sql_at(100 + i))
            ts.append((time.perf_counter() - t0) * 1000)
        leaked = broker.hedge_drain()
        leg_wall_ms = (time.perf_counter() - leg_t0) * 1000
        counts = {k: METRICS.counter(f"broker.{k}").value - c0[k] for k in hedge_counters}
        wasted_ms = (
            METRICS.timer("broker.hedgeWastedMs").total_ms
            + METRICS.timer("broker.hedgeCancelMs").total_ms
            - w0
        )
        snap = hc.snapshot()
        return {
            "p50_ms": round(float(np.percentile(ts, 50)), 3),
            "p99_ms": round(float(np.percentile(ts, 99)), 3),
            "hedge_rate": round(snap["hedges"] / max(1, snap["primaries"]), 4),
            # share of all compute-ms (wall + discarded attempt time) that
            # losing attempts burned before cooperative cancel reclaimed them
            "wasted_work_pct": round(100.0 * wasted_ms / max(1e-9, wasted_ms + leg_wall_ms), 2),
            "leaked_launches": leaked,
            **{k: v for k, v in counts.items()},
        }

    fault_free = run_leg(slow_ms=0.0, hedge=False)
    # self-calibrating fault: the slow replica's jitter base is 10x the
    # measured fault-free median, i.e. "one replica at 10x latency"
    slow_ms = round(slow_mult * max(0.5, fault_free["p50_ms"]), 3)
    unhedged = run_leg(slow_ms=slow_ms, hedge=False)
    hedged = run_leg(slow_ms=slow_ms, hedge=True)
    ff_p99 = max(1e-9, fault_free["p99_ms"])
    return {
        "slow_replica_ms": slow_ms,
        "fault_free": fault_free,
        "unhedged": unhedged,
        "hedged": hedged,
        "hedge_rate": hedged["hedge_rate"],
        "wasted_work_pct": hedged["wasted_work_pct"],
        "p99_vs_fault_free": {
            "unhedged_x": round(unhedged["p99_ms"] / ff_p99, 2),
            "hedged_x": round(hedged["p99_ms"] / ff_p99, 2),
        },
    }


def _concurrent_qps_bench() -> dict:
    """Sustained QPS under 100+ simultaneous clients (round-12 concurrent
    serving tier).  Two modes over identical same-fingerprint workloads
    (one query shape, distinct literals — the regime cross-query batching
    exists for):

      batched:   clients call broker.submit(sql).result(); in-flight
                 same-shape queries coalesce in the MicroBatcher (real
                 wall-clock window, PINOT_TPU_BATCH_WAIT_MS) and execute
                 as ONE vmapped plan launch per segment
      unbatched: thread-per-request broker.query(sql) — the synchronous
                 scatter path every client used before this tier

    A mixed-shape leg runs the batched path over three distinct shapes to
    exercise per-fingerprint grouping under a storm.  Reports sustained
    QPS + client-observed p50/p95/p99 per mode and the speedup ratio;
    `batched_qps` / `batch_speedup` feed the bench-history gate."""
    import threading

    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.coordinator import Coordinator
    from pinot_tpu.cluster.server import ServerInstance
    from pinot_tpu.query import executor as sse_executor
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.config import SegmentsConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.utils.metrics import METRICS

    schema = Schema(
        "t",
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
            FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
        ],
    )
    coord = Coordinator(replication=2)
    for i in range(2):
        coord.register_server(ServerInstance(f"server{i}"))
    coord.add_table(schema, TableConfig(name="t", segments=SegmentsConfig(time_column="ts")))
    rng = np.random.default_rng(23)
    rows = int(os.environ.get("BENCH_QPS_ROWS", 20_000))
    for i in range(4):
        coord.add_segment(
            "t",
            build_segment(
                schema,
                {
                    "city": rng.choice(["sf", "nyc", "la"], rows).astype(object),
                    "v": rng.integers(0, 100, rows),
                    "ts": 1_700_000_000_000 + rng.integers(0, 86_400_000, rows).astype(np.int64),
                },
                f"seg{i}",
            ),
        )
    broker = Broker(coord)

    shapes = [
        lambda i: (
            "SELECT city, COUNT(*), SUM(v) FROM t "
            f"WHERE v < {50 + i % 40} GROUP BY city ORDER BY city"
        ),
        lambda i: f"SELECT COUNT(*), MAX(v) FROM t WHERE v > {i % 40}",
        lambda i: f"SELECT city, SUM(v) FROM t WHERE v >= {i % 30} GROUP BY city ORDER BY city LIMIT 2",
    ]

    n_clients = int(os.environ.get("BENCH_QPS_CLIENTS", 120))
    reqs = int(os.environ.get("BENCH_QPS_REQS", 2))

    def run_mode(issue, sql_for) -> dict:
        """All clients start behind one barrier; sustained QPS is completed
        requests over the span from release to last join."""
        lats = []
        lock = threading.Lock()
        barrier = threading.Barrier(n_clients + 1)

        def client(cid):
            barrier.wait()
            for r in range(reqs):
                sql = sql_for(cid * reqs + r)
                t0 = time.perf_counter()
                issue(sql)
                dt = (time.perf_counter() - t0) * 1000.0
                with lock:
                    lats.append(dt)

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        arr = np.asarray(lats)
        return {
            "qps": round(len(lats) / wall, 1),
            "wall_s": round(wall, 4),
            "requests": len(lats),
            "p50_ms": round(float(np.percentile(arr, 50)), 3),
            "p95_ms": round(float(np.percentile(arr, 95)), 3),
            "p99_ms": round(float(np.percentile(arr, 99)), 3),
        }

    # warm every shape through both paths so neither mode pays compiles:
    # one sync query (base plan) + one full-width batch (vmapped plan)
    for sh in shapes:
        broker.query(sh(0))
        futs = [broker.submit(sh(j)) for j in range(sse_executor.batch_width())]
        broker.drain_batches()
        for f in futs:
            f.result()

    sse_executor.BATCH_AUDIT.reset()
    b0 = METRICS.counter("broker.batches").value
    batched = run_mode(lambda s: broker.submit(s).result(), shapes[0])
    batched["batches"] = METRICS.counter("broker.batches").value - b0
    batched["batch_compiles"] = sse_executor.BATCH_AUDIT.snapshot()["compiles"]
    unbatched = run_mode(broker.query, shapes[0])
    mixed = run_mode(
        lambda s: broker.submit(s).result(), lambda i: shapes[i % len(shapes)](i)
    )
    speedup = round(batched["qps"] / unbatched["qps"], 3) if unbatched["qps"] else None
    return {
        "clients": n_clients,
        "requests_per_client": reqs,
        "rows_per_segment": rows,
        "batched": batched,
        "unbatched": unbatched,
        "mixed_shapes_batched": mixed,
        "batch_speedup": speedup,
    }


def _mesh_scaling_bench() -> dict:
    """2-D (replica x shard) mesh scale-out section (multi-host tentpole).

    Three measurements over one dataset:

      topologies: warm scan rows/s per mesh shape — 1-D "seg" 8-dev
                  baseline vs 2x4 / 4x2 / 1x8 two-axis meshes, asserting
                  BIT-IDENTICAL rows per topology (the hierarchical
                  shard-then-replica combine must not change results)
      shard axis: rows/s at full shard width vs a single-device mesh —
                  `mesh_shard_speedup` is the capacity-scaling ratio
      replica axis: concurrent QPS through ReplicatedEngine at R=2 (two
                  4-device rows, whole batches round-robin across rows)
                  vs R=1 — `mesh_replica_qps_scale` is the QPS ratio

    HONESTY NOTE: in-image the 8 "devices" are XLA host-platform threads on
    however many cores the container grants (often ONE), so both ratios
    measure collective/dispatch overhead, not real parallel speedup — expect
    ~1.0 and read them as regression canaries (a broken hierarchical combine
    or a row that stops serving moves them), not as scaling claims.  Real
    per-axis scaling needs real hardware (ICI shard rows, DCN replica rows).
    """
    import threading

    from pinot_tpu.parallel.engine import DistributedEngine, ReplicatedEngine
    from pinot_tpu.parallel.mesh import default_mesh, make_mesh2d
    from pinot_tpu.parallel.stacked import StackedTable
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    rng = np.random.default_rng(31)
    rows = int(os.environ.get("BENCH_MESH_ROWS", 1 << 20))
    schema = Schema(
        "t",
        [
            FieldSpec("k", DataType.INT),
            FieldSpec("m", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    data = {
        "k": rng.integers(0, 1024, rows).astype(np.int32),
        "m": rng.integers(1, 1000, rows).astype(np.int64),
    }
    stacked = StackedTable.build(schema, data, num_shards=8)
    ctx = parse_query("SELECT k, COUNT(*), SUM(m) FROM t WHERE m > 100 GROUP BY k LIMIT 1100")

    def scan_leg(mesh) -> tuple:
        eng = DistributedEngine(mesh)
        eng.register_table("t", stacked)
        res = eng.execute(ctx)  # compile + correctness
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            eng.execute(ctx)
            ts.append(time.perf_counter() - t0)
        return round(rows / float(np.min(ts)), 1), [tuple(r) for r in res.rows]

    base_rps, base_rows = scan_leg(default_mesh())
    topologies = {"seg8": {"rows_per_sec": base_rps, "bit_identical": True}}
    for r, s in [(1, 8), (2, 4), (4, 2)]:
        rps, out = scan_leg(make_mesh2d(r, s))
        same = out == base_rows
        topologies[f"{r}x{s}"] = {"rows_per_sec": rps, "bit_identical": same}
        assert same, f"mesh {r}x{s} drifted from the 1-D baseline"

    one_dev_rps, _ = scan_leg(default_mesh(num_devices=1))
    shard_speedup = round(topologies["1x8"]["rows_per_sec"] / one_dev_rps, 3)

    def qps_leg(num_replicas: int) -> dict:
        eng = ReplicatedEngine(num_replicas=num_replicas)
        eng.register_table("t", stacked)
        n_clients = int(os.environ.get("BENCH_MESH_CLIENTS", 8))
        reqs = int(os.environ.get("BENCH_MESH_REQS", 4))
        # warm every replica row's plan/device caches out of the timed span
        for _ in range(num_replicas):
            eng.execute(ctx)
        lats = []
        lock = threading.Lock()
        barrier = threading.Barrier(n_clients + 1)

        def client():
            barrier.wait()
            for _ in range(reqs):
                t0 = time.perf_counter()
                eng.execute(ctx)
                dt = (time.perf_counter() - t0) * 1000.0
                with lock:
                    lats.append(dt)

        threads = [threading.Thread(target=client, daemon=True) for _ in range(n_clients)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        arr = np.asarray(lats)
        return {
            "replicas": num_replicas,
            "qps": round(len(lats) / wall, 1),
            "p50_ms": round(float(np.percentile(arr, 50)), 3),
            "p99_ms": round(float(np.percentile(arr, 99)), 3),
        }

    qps_r1 = qps_leg(1)
    qps_r2 = qps_leg(2)
    replica_scale = round(qps_r2["qps"] / qps_r1["qps"], 3) if qps_r1["qps"] else None
    return {
        "rows": rows,
        "topologies": topologies,
        "single_device_rows_per_sec": one_dev_rps,
        "mesh_shard_speedup": shard_speedup,
        "qps_r1": qps_r1,
        "qps_r2": qps_r2,
        "mesh_replica_qps_scale": replica_scale,
    }


def _working_set_sweep() -> dict:
    """Tiered-storage capacity sweep (round-14 tentpole).

    HBM is now a cost-aware cache over host RAM (segment/residency.py):
    macro-batch slices are staged through an async double-buffered copy
    stream and evicted by coldness when the budget fills.  This section
    sizes the sequential-scan working set W empirically (resident bytes
    after an unbounded-budget scan), then reruns the same group-by scan
    with the cache budget at 2W / W / W/4 — i.e. the working set at
    0.5x / 1x / 4x of HBM — and reports the rows/s degradation curve,
    the prefetch-hit rate of the staging stream, and staging-stall time.
    Every leg must be bit-exact against an untiered (hbm_cache_bytes=0,
    full-pinning) reference: eviction churn may cost throughput, never
    correctness.  bench_record lifts the 1x/4x rows/s and the 4x
    prefetch-hit rate into the gate metrics.
    """
    import jax

    from pinot_tpu.parallel.engine import DistributedEngine
    from pinot_tpu.parallel.stacked import StackedTable
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.utils.metrics import METRICS

    rng = np.random.default_rng(7)
    # capacity behaviour is about ratios, not scale — cap the table so the
    # sweep stays cheap inside the CPU smoke run (BENCH_ROWS=1<<20)
    n = min(N_ROWS, 1 << 22)
    schema = Schema(
        "ws",
        [
            FieldSpec("g", DataType.INT),
            FieldSpec("m", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    data = {
        "g": rng.integers(0, 512, n).astype(np.int32),
        "m": rng.integers(0, 1 << 20, n).astype(np.int64),
    }
    sql = "SELECT g, COUNT(*), SUM(m) FROM ws GROUP BY g ORDER BY g LIMIT 600"
    ndev = len(jax.devices())
    # ~16 macro-batches (~12 B/doc: packed g codes + raw m): the 4x leg
    # keeps only ~4 slices resident, so the copy stream runs continuously
    # while earlier batches scan — the double-buffering regime under test
    launch_bytes = max(4096, (12 * max(n // ndev, 1)) // 16)

    def build(cache_bytes):
        eng = DistributedEngine(launch_bytes=launch_bytes, hbm_cache_bytes=cache_bytes)
        eng.register_table("ws", StackedTable.build(schema, dict(data), eng.num_devices))
        return eng

    ref_eng = build(0)  # tiering disabled: the pre-r14 full-pinning path
    ref_rows = ref_eng.query(sql).rows

    probe = build(1 << 40)  # effectively unbounded budget: measures W
    assert probe.query(sql).rows == ref_rows, "tiered probe diverged from untiered reference"
    wset = int(probe.residency.resident_bytes)
    probe.residency.shutdown()

    iters = max(2, min(K_ITERS, 4))
    legs = {}
    for label, budget in (
        ("0.5x", 2 * wset),
        ("1x", wset + (64 << 10)),  # dict-page headroom: fully resident
        ("4x", max(4 * launch_bytes, wset // 4)),
    ):
        eng = build(budget)
        # cold pass pays compiles + the first staging wave; the timed loop
        # measures the steady state each leg is meant to expose
        assert eng.query(sql).rows == ref_rows, f"tiered {label} leg diverged"
        h0 = METRICS.counter("engine.prefetchHits").value
        s0 = METRICS.counter("engine.stagingStalls").value
        st0 = METRICS.snapshot()["histograms"].get("residency.stagingStallMs", {})
        t0 = time.perf_counter()
        for _ in range(iters):
            assert eng.query(sql).rows == ref_rows, f"tiered {label} leg diverged"
        wall = time.perf_counter() - t0
        hits = METRICS.counter("engine.prefetchHits").value - h0
        stalls = METRICS.counter("engine.stagingStalls").value - s0
        st1 = METRICS.snapshot()["histograms"].get("residency.stagingStallMs", {})
        stall_ms = st1.get("count", 0) * st1.get("meanMs", 0.0) - st0.get(
            "count", 0
        ) * st0.get("meanMs", 0.0)
        snap = eng.residency.snapshot()
        legs[label] = {
            "budget_bytes": int(budget),
            "rows_per_sec": round(n * iters / wall, 1),
            "prefetch_hits": int(hits),
            "staging_stalls": int(stalls),
            "prefetch_hit_rate": round(hits / (hits + stalls), 3) if hits + stalls else 1.0,
            "staging_stall_ms": round(max(stall_ms, 0.0), 3),
            "evictions": snap["evictions"],
            "bit_exact": True,
        }
        eng.residency.shutdown()
    return {
        "rows": n,
        "working_set_bytes": wset,
        "launch_bytes": int(launch_bytes),
        "iters_per_leg": iters,
        "legs": legs,
    }


def _failover_bench() -> dict:
    """Coordinator HA failover drill (round-18 tentpole).

    One meta_dir, a leader and a hot standby sharing a SIMULATED clock
    (lease TTL 2s), brokers behind a CoordinatorHandle whose sleep hook
    advances that clock — the whole failover runs in virtual time, so the
    blackout figure measures the protocol (lease expiry + standby
    replay-to-tip + handle adoption), not host scheduling noise:

      1. FaultPlan.pause_leader freezes the leader (no lease renews, the
         control plane refuses with NotLeaderError, the data plane keeps
         serving the last versioned view)
      2. one control-plane write fires through the handle; every park
         backoff advances the sim clock AND issues one data-plane query
         through the broker (the concurrent load), until the standby's
         election tick sees the expired lease and promotes
      3. the resumed old leader's next journaled write must FENCE
         (FencedEpochError) — split-brain cannot reach the journal

    Reports control-plane blackout ms (sim delta from pause to the write
    landing on the new leader), data-plane success rate during the
    blackout, and the standby's replay-to-tip ms; `failover_blackout_ms`
    joins GATE_METRICS_LOWER in the bench-history gate."""
    import tempfile

    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.coordinator import Coordinator
    from pinot_tpu.cluster.election import CoordinatorHandle, FencedEpochError
    from pinot_tpu.cluster.faults import FaultPlan
    from pinot_tpu.cluster.server import ServerInstance
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.config import SegmentsConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.utils.metrics import METRICS

    tmp = tempfile.mkdtemp(prefix="pinot-failover-")
    sim = [0.0]

    def clock() -> float:
        return sim[0]

    ttl_s = 2.0
    leader = Coordinator(
        replication=2,
        meta_dir=os.path.join(tmp, "meta"),
        deep_store=os.path.join(tmp, "deep"),
        node_id="coord-a",
        lease_ttl_s=ttl_s,
        clock=clock,
    )
    plan = FaultPlan(seed=7).attach_coordinator(leader)

    probes = {"ok": 0, "bad": 0}
    in_blackout = [False]
    sql = "SELECT city, COUNT(*), SUM(v) FROM t GROUP BY city ORDER BY city"
    expected = []  # filled after warm-up

    def sim_sleep(s: float) -> None:
        sim[0] += s
        if in_blackout[0]:
            # the concurrent query load: one data-plane probe per park
            # backoff, served off the last routing view while leaderless
            try:
                r = broker.query(sql)
                probes["ok" if list(r.rows) == expected else "bad"] += 1
            except Exception:  # noqa: BLE001 — a refused probe is the datum
                probes["bad"] += 1

    handle = CoordinatorHandle([leader], sleep=sim_sleep, clock=clock)
    schema = Schema(
        "t",
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
            FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
        ],
    )
    for i in range(2):
        handle.register_server(
            ServerInstance(f"server{i}", data_dir=os.path.join(tmp, f"server{i}"))
        )
    handle.add_table(schema, TableConfig(name="t", segments=SegmentsConfig(time_column="ts")))
    rng = np.random.default_rng(19)
    rows = int(os.environ.get("BENCH_FAILOVER_ROWS", 2_000))
    for i in range(4):
        handle.add_segment(
            "t",
            build_segment(
                schema,
                {
                    "city": rng.choice(["sf", "nyc", "la"], rows).astype(object),
                    "v": rng.integers(0, 100, rows),
                    "ts": 1_700_000_000_000
                    + rng.integers(0, 86_400_000, rows).astype(np.int64),
                },
                f"seg{i}",
                output_dir=os.path.join(tmp, "build", f"seg{i}"),
            ),
        )

    # hot standby boots AFTER the load so bootstrap + incremental tail both run
    standby = Coordinator(
        replication=2,
        meta_dir=os.path.join(tmp, "meta"),
        deep_store=os.path.join(tmp, "deep"),
        node_id="coord-b",
        standby=True,
        lease_ttl_s=ttl_s,
        clock=clock,
    )
    plan.attach_coordinator(standby)
    handle.add_candidate(standby)

    broker = Broker(handle)
    warm = broker.query(sql)
    expected.extend(list(warm.rows))
    old_epoch = leader.election.epoch

    # ---- the drill ----------------------------------------------------
    f0 = METRICS.counter("coordinator.fencedAppends").value
    plan.pause_leader("coord-a")
    t0 = sim[0]
    in_blackout[0] = True
    handle.heartbeat("server0")  # parks, ticks the election, lands on coord-b
    in_blackout[0] = False
    blackout_ms = (sim[0] - t0) * 1000.0

    # ---- split-brain fence proof --------------------------------------
    plan.resume_leader("coord-a")
    fenced = False
    try:
        leader.drop_table("t")  # old epoch writing directly: must fence
    except FencedEpochError:
        fenced = True
    post = broker.query(sql)  # routed via the adopted new leader's view
    n_probes = probes["ok"] + probes["bad"]
    return {
        "lease_ttl_s": ttl_s,
        "blackout_ms": round(blackout_ms, 3),
        "replay_to_tip_ms": round(standby.last_promote_ms, 3),
        "data_plane": {
            "queries_during_blackout": n_probes,
            "ok": probes["ok"],
            "success_rate": round(probes["ok"] / n_probes, 3) if n_probes else None,
        },
        "old_epoch": old_epoch,
        "new_epoch": standby.election.epoch,
        "new_leader": standby.node_id,
        "old_leader_fenced": fenced,
        "fenced_appends": METRICS.counter("coordinator.fencedAppends").value - f0,
        "post_failover_query_ok": list(post.rows) == expected,
    }


def _autopilot_overload_bench() -> dict:
    """Closed-loop autopilot vs a grid of static knob settings (ISSUE 18):
    mixed-tenant load (scans + group-bys on `hot`, funnels on `events`)
    offered at 3x estimated capacity by paced client threads, with one of
    two replicas carrying a seeded latency jitter (the r15 gray-fault
    model).  Every leg runs the same admission ceiling and the same fault;
    only the knob settings differ — static legs pin KnobRegistry overrides
    up front, the autopilot leg starts at env defaults and lets the
    controller move one knob per tick.  Reports admitted p99 per leg,
    `autopilot_admitted_p99_ms` (lower-is-better in the `cli perf --check`
    gate), `autopilot_vs_best_static`, and the knob-change count against
    the controller's own oscillation bound."""
    import threading

    from pinot_tpu.cluster.admission import (
        AdmissionController,
        QueryKilledError,
        ReservationError,
        TooManyRequestsError,
        estimate_query_cost,
    )
    from pinot_tpu.cluster import autopilot as ap_mod
    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.coordinator import Coordinator
    from pinot_tpu.cluster.faults import FaultPlan
    from pinot_tpu.cluster.server import ServerInstance
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.config import SegmentsConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query
    from pinot_tpu.utils import perf

    rows = int(os.environ.get("BENCH_AUTOPILOT_ROWS", 5_000))
    n_clients = int(os.environ.get("BENCH_AUTOPILOT_CLIENTS", 12))
    # two-phase legs: an unmeasured warm-up (the closed loop converges, the
    # static legs burn the identical schedule) then the measured window
    reqs_warm = int(os.environ.get("BENCH_AUTOPILOT_WARM_REQS", 48))
    # at 3x overload most offered requests shed, so the admitted-p99 order
    # statistic needs a wide measured window to settle (legs are seconds each)
    reqs_meas = int(os.environ.get("BENCH_AUTOPILOT_REQS", 160))
    reqs = reqs_warm + reqs_meas
    overload_x = 3.0

    rng = np.random.default_rng(11)
    coord = Coordinator(replication=2)
    for i in range(2):
        coord.register_server(ServerInstance(f"server{i}"))
    hot = Schema(
        "hot",
        [
            FieldSpec("city", DataType.STRING),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
            FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
        ],
    )
    coord.add_table(hot, TableConfig(name="hot", segments=SegmentsConfig(time_column="ts")))
    events = Schema(
        "events",
        [
            FieldSpec("uid", DataType.LONG),
            FieldSpec("url", DataType.STRING),
            FieldSpec("ts", DataType.TIMESTAMP, role=FieldRole.DATE_TIME),
        ],
    )
    coord.add_table(
        events, TableConfig(name="events", segments=SegmentsConfig(time_column="ts"))
    )
    for i in range(4):
        coord.add_segment(
            "hot",
            build_segment(
                hot,
                {
                    "city": rng.choice(["sf", "nyc", "la"], rows).astype(object),
                    "v": rng.integers(0, 100, rows),
                    "ts": 1_700_000_000_000 + rng.integers(0, 86_400_000, rows).astype(np.int64),
                },
                f"hot{i}",
            ),
        )
        coord.add_segment(
            "events",
            build_segment(
                events,
                {
                    "uid": rng.integers(0, 300, rows).astype(np.int64),
                    "url": rng.choice(["/home", "/product", "/cart"], rows).astype(object),
                    "ts": 1_700_000_000_000 + rng.integers(0, 86_400_000, rows).astype(np.int64),
                },
                f"ev{i}",
            ),
        )
    broker = Broker(coord)
    broker.health.brownout_factor = float("inf")  # isolate knobs from routing-away

    shapes = [
        lambda i: (
            "SELECT city, COUNT(*), SUM(v) FROM hot "
            f"WHERE v < {50 + i % 40} GROUP BY city ORDER BY city"
        ),
        lambda i: f"SELECT COUNT(*), MAX(v) FROM hot WHERE v > {i % 40}",
        lambda i: (
            "SELECT FUNNELCOUNT(STEPS(url = '/home', url = '/cart'), "
            f"CORRELATEBY(uid)) FROM events WHERE uid >= {i % 20}"
        ),
    ]

    def sql_at(i: int) -> str:
        return shapes[i % len(shapes)](i)

    for i in range(12):  # warm every shape: parse, plan, compile, hedge windows
        broker.query(sql_at(i))

    # ---- capacity + gray fault calibration ----------------------------
    cal = []
    for i in range(30):
        t0 = time.perf_counter()
        broker.query(sql_at(i))
        cal.append((time.perf_counter() - t0) * 1000)
    med_ms = float(np.median(cal))
    capacity_qps = 1000.0 / med_ms
    slow_ms = round(4.0 * max(0.5, med_ms), 3)
    FaultPlan(seed=17).jitter("server0", base_ms=slow_ms, sigma=0.3).attach(coord)

    unit_cost = estimate_query_cost(
        parse_query(shapes[0](0)), coord.tables["hot"].segment_meta.values()
    ).units
    rate_units = capacity_qps * unit_cost
    # the static env ceilings every leg (and the registry clamps) run under:
    # hedging on with a fat budget, admission refill at estimated capacity
    env_ceilings = {
        "PINOT_TPU_HEDGE_BUDGET_PCT": "60",
        "PINOT_TPU_ADMISSION_RATE": f"{rate_units:.4f}",
    }
    saved_env = {k: os.environ.get(k) for k in env_ceilings}
    os.environ.update(env_ceilings)
    broker.hedge.enabled_default = True
    # achievable target under the fault model: one un-hedged scatter leg
    # rides the slow replica, so the admitted tail floors near 2x its
    # jitter base — an SLO below that saturates the ladder instead of
    # letting the loop settle on the cheapest config that meets it
    slo_ms = round(2.0 * slow_ms, 3)
    interval_s = n_clients / (overload_x * capacity_qps)  # per-client pacing

    def run_leg(overrides) -> dict:
        ap_mod.reset_knobs()
        if overrides:
            ap_mod.knobs().set_many(overrides, who="static-config")
        perf.PERF_LEDGER.reset()
        adm = AdmissionController(
            rate_units_per_s=rate_units,
            burst_units=2 * unit_cost,
            max_queue=0,
            knob="admission_rate",
        )
        broker.governor.admission = adm
        pilot = None
        if overrides is None:  # the closed-loop leg
            # 0.1 s tick: fast enough to converge well inside the warm-up
            # phase, slow enough that the controller's own ledger snapshots
            # don't tax the saturated host during the measured window
            pilot = ap_mod.Autopilot(
                governor=broker.governor, slo_ms=slo_ms, tick_s=0.1
            )
            pilot.start()
        lats, lock = [], threading.Lock()
        counts = {"admitted": 0, "shed": 0, "killed": 0}
        barrier = threading.Barrier(n_clients + 1)

        def client(cid):
            barrier.wait()
            for r in range(reqs):
                time.sleep(interval_s)
                measured = r >= reqs_warm
                t0 = time.perf_counter()
                try:
                    broker.query(sql_at(cid * reqs + r))
                except TooManyRequestsError:
                    if measured:
                        with lock:
                            counts["shed"] += 1
                except (QueryKilledError, ReservationError):
                    if measured:
                        with lock:
                            counts["killed"] += 1
                else:
                    if measured:
                        with lock:
                            counts["admitted"] += 1
                            lats.append((time.perf_counter() - t0) * 1000)

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        barrier.wait()
        for t in threads:
            t.join()
        broker.hedge_drain()
        leg = {
            **counts,
            "admitted_p99_ms": (
                round(float(np.percentile(lats, 99)), 3) if lats else None
            ),
            "admitted_p50_ms": (
                round(float(np.percentile(lats, 50)), 3) if lats else None
            ),
        }
        if pilot is not None:
            pilot.stop()
            snap = pilot.snapshot()
            moves = [
                d for d in snap["decisions"] if d["action"] in ("degrade", "recover")
            ]
            win, cap = snap["changeBound"]["windowTicks"], snap["changeBound"]["maxChanges"]
            worst = 0
            ticks = [d["tick"] for d in moves]
            for t in ticks:
                worst = max(worst, len([m for m in ticks if t - win < m <= t]))
            assert worst <= cap, f"oscillation bound violated: {worst} moves/{win} ticks"
            leg["knob_changes"] = snap["knobChanges"]
            leg["ladder_walks"] = snap["ladderWalks"]
            leg["max_changes_per_window"] = worst
            leg["change_bound"] = cap
            leg["final_knobs"] = {
                n: k["value"]
                for n, k in snap["knobs"].items()
                if k["overridden"]
            }
        return leg

    # admitted-p99 under a shed-heavy window is a tail order statistic riding
    # on the seeded jitter's random draw — gate the median repeat, not one
    # draw. Repeats are interleaved round-robin across configs (not config by
    # config) so slow host drift over the section lands on every config
    # equally instead of taxing whichever leg happens to run last.
    n_rep = int(os.environ.get("BENCH_AUTOPILOT_REPEATS", 3))

    def median_leg(runs) -> dict:
        runs = sorted(runs, key=lambda leg: leg["admitted_p99_ms"] or float("inf"))
        med = runs[len(runs) // 2]
        med["admitted_p99_ms_runs"] = [r["admitted_p99_ms"] for r in runs]
        return med

    try:
        static_grid = {
            "default": {},  # env ceilings as-is: hedge 60%, full refill rate
            "no_hedge": {"hedge_budget_pct": 0.0},
            "half_rate": {"admission_rate": 0.5 * rate_units},
            # the degradation ladder's floor: if the closed loop saturates,
            # this is its static twin — the grid always contains whatever
            # config the controller converges to
            "floor": {
                "hedge_budget_pct": 0.0,
                "batch_wait_ms": 8.0,
                "pipeline_depth": 1,
                "staging_depth": 1,
                "admission_rate": 0.25 * rate_units,
                "degrade_level": 3,
            },
        }
        order = list(static_grid.items()) + [("autopilot", None)]
        rep_runs = {name: [] for name, _ in order}
        for _ in range(n_rep):
            for name, ov in order:
                rep_runs[name].append(run_leg(ov))
        statics = {name: median_leg(rep_runs[name]) for name in static_grid}
        pilot_leg = median_leg(rep_runs["autopilot"])
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        ap_mod.reset_knobs()
        broker.governor.admission = AdmissionController()  # back to permissive

    best_name, best = min(
        statics.items(), key=lambda kv: kv[1]["admitted_p99_ms"] or float("inf")
    )
    vs_best = (
        round(pilot_leg["admitted_p99_ms"] / best["admitted_p99_ms"], 3)
        if pilot_leg["admitted_p99_ms"] and best["admitted_p99_ms"]
        else None
    )
    return {
        "capacity_qps_est": round(capacity_qps, 1),
        "offered_x": overload_x,
        "slow_replica_ms": slow_ms,
        "slo_ms": slo_ms,
        "clients": n_clients,
        "warmup_requests_per_client": reqs_warm,
        "measured_requests_per_client": reqs_meas,
        "repeats": n_rep,
        "static": statics,
        "best_static": best_name,
        "autopilot": pilot_leg,
        "autopilot_vs_best_static": vs_best,
    }


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pinot_tpu import ops
    from pinot_tpu.parallel.engine import DistributedEngine
    from pinot_tpu.parallel.stacked import StackedTable
    from pinot_tpu.spi.config import IndexingConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    rng = np.random.default_rng(42)
    n = N_ROWS
    schema = Schema(
        "lineorder",
        [
            FieldSpec("lo_orderdate", DataType.INT),
            FieldSpec("lo_quantity", DataType.INT),
            FieldSpec("lo_discount", DataType.INT),
            FieldSpec("lo_revenue", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    data = {
        "lo_orderdate": (19920101 + rng.integers(0, 2406, n)).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
        # cardinality 11 -> 4-bit lanes: the scan-bound section's packed
        # column (8 codes per uint32 word)
        "lo_discount": rng.integers(0, 11, n).astype(np.int32),
        "lo_revenue": rng.integers(100, 1_000_000, n).astype(np.int64),
    }

    cfg = TableConfig(
        "lineorder",
        indexing=IndexingConfig(range_index_columns=["lo_quantity"]),
    )
    ndev = len(jax.devices())
    stacked = StackedTable.build(schema, data, num_shards=ndev, table_config=cfg)
    engine = DistributedEngine()
    engine.register_table("lineorder", stacked)

    sql = (
        "SELECT lo_orderdate, SUM(lo_revenue) FROM lineorder "
        "WHERE lo_quantity < 25 GROUP BY lo_orderdate LIMIT 2500"
    )
    ctx = parse_query(sql)

    r = engine.execute(ctx)  # full-path warm-up: compile + correctness
    assert r.rows, "bench query returned nothing"
    index_uses = list(r.stats.filter_index_uses)
    assert index_uses, "bench filter must ride the range index"

    # ---- end-to-end timing + latency distribution ---------------------
    # execute() feeds the dist.queryLatency histogram; resetting first makes
    # the p50/p95/p99 below cover exactly these runs
    from pinot_tpu.utils.metrics import METRICS

    METRICS.reset()
    e2e_ts = []
    for _ in range(7):
        t0 = time.perf_counter()
        engine.execute(ctx)
        e2e_ts.append(time.perf_counter() - t0)
    e2e = float(np.min(e2e_ts))
    lat = METRICS.snapshot()["histograms"]["dist.queryLatency"]

    # ---- distinct-literal sweep ---------------------------------------
    # Round-6 tentpole proof: N same-shape queries differing only in the
    # filter literal must share ONE compiled kernel — the plan cache keys
    # on the shape fingerprint (literals canonicalized to parameter slots)
    # and the literal rides in as a device argument.  Before
    # parameterization each literal was a fresh trace+compile.
    from pinot_tpu.analysis.compile_audit import DIST_AUDIT

    DIST_AUDIT.reset()
    sweep_n = int(os.environ.get("BENCH_SWEEP", 20))
    sweep_ts = []
    for i in range(sweep_n):
        q = parse_query(
            "SELECT lo_orderdate, SUM(lo_revenue) FROM lineorder "
            f"WHERE lo_quantity < {5 + (i % 40)} GROUP BY lo_orderdate LIMIT 2500"
        )
        t0 = time.perf_counter()
        engine.execute(q)
        sweep_ts.append(time.perf_counter() - t0)
    sweep_compiles = sum(DIST_AUDIT.counts().values())
    # snapshot here so the audit covers exactly the sweep since reset():
    # cold = first trace per shape, warm_recompiles = re-traces of a seen
    # shape (a literal leaking into the plan key shows up here first)
    plan_cache = DIST_AUDIT.summary()
    sweep = {
        "queries": sweep_n,
        "compiles": sweep_compiles,
        "cache_hit_rate": round((sweep_n - sweep_compiles) / sweep_n, 3),
        "warm_p50_ms": round(float(np.median(sweep_ts)) * 1000, 3),
        "warm_p50_rows_per_sec": round(n / float(np.median(sweep_ts)), 1),
    }

    # ---- per-stage trace summary --------------------------------------
    # one traced run (separate plan-cache entry: options ride the
    # fingerprint); per-stage ms aggregated by span base name
    from pinot_tpu.query.analyze import _span_ms_index

    traced = engine.execute(parse_query("SET trace = true; " + sql))
    stage_ms = {
        k: round(v, 3)
        for k, v in sorted(_span_ms_index(traced.stats.trace).items())
        if ":" not in k  # per-batch dispatch:N spans already sum under 'dispatch'
    }

    # ---- marginal kernel timing ---------------------------------------
    # Macro-batch launches (round 5): the engine splits the doc axis so one
    # launch's while-loop capture copy never exceeds the HBM budget — the
    # fix that fits 1.07B rows on a single chip.  All batches share shapes,
    # so the K-loop compiles once and runs per batch; timings sum batches.
    plan = engine._plan(ctx, stacked)
    batches = engine.device_batches(plan, stacked)
    # per-iteration param wobble so the loop body depends on the index — no
    # loop-invariant hoisting.  The indexed filter ships bitmap words: XOR
    # the first word with (i % 2), flipping one doc's membership.
    bits_key = next(iter(plan.row_sharded_params), None)
    hi_key = next((k for k in plan.params if k.endswith(".hi")), None)

    def make_loop(k_iters: int):
        def run(cols, params):
            def body(i, acc):
                p = dict(params)
                if bits_key is not None:
                    w = params[bits_key]
                    p[bits_key] = w.at[..., 0].set(w[..., 0] ^ (i % 2).astype(jnp.uint32))
                elif hi_key is not None:
                    p[hi_key] = params[hi_key] - (i % 2).astype(jnp.int32)
                presence, partials = plan.fn(cols, p)
                leaves = jax.tree_util.tree_leaves((presence, partials))
                return acc + sum(jnp.sum(l).astype(jnp.float64) for l in leaves)

            return lax.fori_loop(0, k_iters, body, jnp.float64(0))

        fn = jax.jit(run)
        for cols, params in batches:  # compile + first transfer
            jax.device_get(fn(cols, params))
        return fn

    def time_once(fn) -> float:
        t0 = time.perf_counter()
        for cols, params in batches:
            jax.device_get(fn(cols, params))
        return time.perf_counter() - t0

    fn_1 = make_loop(1)
    fn_k = make_loop(K_ITERS)

    # Round-5 hardening (VERDICT r4 #1): a single (t_1, t_K) pair is not
    # robust to host contention — one slow t_K understated r4 by 21x.
    # Take the median slope over >=3 interleaved pairs (each timing the min
    # of 3 runs), cross-check against the amortized lower bound
    # n*K/min(t_K) — which cannot be corrupted by subtraction noise — and
    # re-measure when the two disagree by >25%.  Report the max of the two
    # (the amortized figure still *includes* fixed dispatch overhead, so it
    # is a strict lower bound on marginal throughput), plus run variance.
    def measure_pair():
        t1 = min(time_once(fn_1) for _ in range(3))
        tk = min(time_once(fn_k) for _ in range(3))
        return t1, tk

    pairs = [measure_pair() for _ in range(3)]

    def summarize(ps):
        # a contended t_1 can exceed t_K, making the slope non-positive —
        # such pairs are invalid samples, not data; drop them rather than
        # clamp (a clamp would publish an absurdly HIGH record).
        slopes = [(tk - t1) / (K_ITERS - 1) for t1, tk in ps]
        valid = [s for s in slopes if s > 0]
        min_tk = min(tk for _, tk in ps)
        amortized = n * K_ITERS / min_tk  # lower bound, subtraction-free
        if not valid:
            return None, 0.0, amortized, [], len(slopes)
        per_query = float(np.median(valid))
        return per_query, n / per_query, amortized, valid, len(slopes) - len(valid)

    per_query, marg, amortized, slopes, n_invalid = summarize(pairs)
    remeasured = 0
    while (marg < 0.75 * amortized or not slopes) and remeasured < 2:
        # slope estimate inconsistent with its own lower bound (or no valid
        # pair at all): contention hit a timing run.  Gather more pairs.
        pairs.extend(measure_pair() for _ in range(2))
        per_query, marg, amortized, slopes, n_invalid = summarize(pairs)
        remeasured += 1

    # marg can only be trusted above the floor; with no valid slopes the
    # subtraction-free amortized floor IS the measurement.
    rows_per_sec = max(marg, amortized)
    spread = (
        (max(slopes) - min(slopes)) / float(np.median(slopes)) if slopes else -1.0
    )

    # Physical scan bandwidth: bytes the kernel actually streams per row —
    # bit-packed dict columns at code_bits/8 (the uint32 lane words are
    # what ships; perf.analytic_bytes_per_row reads the stored lane width),
    # null bitmaps at 1 byte/row, plus one uint32 per 32 rows for each
    # row-sharded index-bitmap param.
    from pinot_tpu.utils import perf

    bytes_per_row = perf.analytic_bytes_per_row(
        (stacked.column(name) for name in plan.needed_columns),
        bitmap_params=len(plan.row_sharded_params),
    )
    # Logical consumption bandwidth: decoded widths of every column the
    # QUERY references — including the index-answered filter column the
    # kernel never touches.  effective_bytes_per_sec is rows/s times THIS
    # figure: how fast the engine chews logical data, the row-store
    # equivalent a user compares engines by.  The physical figure above
    # (smaller, post-packing) is what the roofline divides by.
    _DECODED_WIDTH = {"INT": 4, "LONG": 8, "FLOAT": 4, "DOUBLE": 8}
    logical_bytes_per_row = sum(
        _DECODED_WIDTH[f.data_type.value] for f in schema.fields if f.name != "lo_discount"
    ) + len(plan.row_sharded_params) * 4 / 32

    # ---- roofline reconciliation (observatory r6) ---------------------
    # Two byte models for the same kernel: the analytic packed-storage
    # estimate above vs XLA's own cost_analysis() on the lowered plan
    # (force="xla" — on CPU the serving path skips the extra lowering, but
    # the bench pays it once to reconcile the models).  The roofline %
    # divides the PACKED physical figure into the device peak;
    # cost_analysis stays reported as the reconciliation cross-check.

    batch_rows = getattr(plan, "batch_docs", 0) or n
    xla_cost = perf.capture_cost(
        plan.fn,
        batches[0],
        perf.analytic_cost(
            batch_rows,
            bytes_per_row,
            kind=plan.kind,
            num_groups=plan.num_groups,
            num_entries=len(plan.aggs),
        ),
        force="xla",
    )
    cost_bpr = xla_cost.bytes_accessed / batch_rows if xla_cost.source == "xla" else None
    peak_bps = perf.peak_hbm_bytes_per_sec()
    device_kind = jax.devices()[0].device_kind
    roofline = {
        "device_kind": device_kind,
        "peak_hbm_bytes_per_sec": peak_bps,
        "source": xla_cost.source,  # "xla" when cost_analysis answered, else "analytic"
        "analytic_bytes_per_row": round(bytes_per_row, 3),
        "cost_analysis_bytes_per_row": round(cost_bpr, 3) if cost_bpr is not None else None,
        # >1 means XLA sees more traffic than the packed-storage model
        # (widening copies, bitmap word reads); the gap is the reconciliation
        "bytes_model_ratio": round(cost_bpr / bytes_per_row, 3) if cost_bpr and bytes_per_row else None,
        "cost_bytes_per_sec": round(rows_per_sec * cost_bpr, 1) if cost_bpr is not None else None,
        # per-section achieved-vs-peak %: marginal kernel, e2e, warm sweep —
        # all from PACKED physical bytes (bit-packed forward index widths)
        "kernel_roofline_pct": round(100.0 * rows_per_sec * bytes_per_row / peak_bps, 3),
        "e2e_roofline_pct": round(100.0 * (n / e2e) * bytes_per_row / peak_bps, 3),
        "warm_p50_roofline_pct": round(
            100.0 * sweep["warm_p50_rows_per_sec"] * bytes_per_row / peak_bps, 3
        ),
    }

    # ---- scan-bound / agg-bound sections (packed forward indexes) -----
    # scan_bound: low-selectivity predicate over the UNINDEXED 4-bit
    # lo_discount column — the kernel streams packed lane words and
    # unpacks in-register, so throughput is filter-scan-limited.
    # agg_bound: no filter, group-by-heavy multi-agg — throughput is
    # accumulate-limited.  Both report achieved rows/s and roofline %
    # from packed physical bytes; both are gated (perf.GATE_METRICS).
    def _section(sql_s: str, warm_iters: int = 5) -> dict:
        ctx_s = parse_query(sql_s)
        res_s = engine.execute(ctx_s)  # compile + correctness
        assert res_s.rows, f"section query returned nothing: {sql_s}"
        ts = []
        for _ in range(warm_iters):
            t0 = time.perf_counter()
            engine.execute(ctx_s)
            ts.append(time.perf_counter() - t0)
        sec = float(np.min(ts))
        plan_s = engine._plan(ctx_s, stacked)
        pbpr = perf.analytic_bytes_per_row(
            (stacked.column(nm) for nm in plan_s.needed_columns),
            bitmap_params=len(plan_s.row_sharded_params),
        )
        rps = n / sec
        return {
            "sql": sql_s,
            "rows_per_sec": round(rps, 1),
            "packed_bytes_per_row": round(pbpr, 3),
            "bytes_per_sec": round(rps * pbpr, 1),
            "roofline_pct": round(100.0 * rps * pbpr / peak_bps, 3),
        }

    scan_bound_sql = "SELECT COUNT(*) FROM lineorder WHERE lo_discount = 7"
    agg_bound_sql = (
        "SELECT lo_orderdate, COUNT(*), SUM(lo_revenue), AVG(lo_quantity) "
        "FROM lineorder GROUP BY lo_orderdate LIMIT 2500"
    )
    scan_bound = _section(scan_bound_sql)
    agg_bound = _section(agg_bound_sql)

    # ---- packed-parity: packed vs unpacked execution is bit-exact -----
    # The same table with packing metadata stripped rides the raw unpacked
    # path end to end; every query must return IDENTICAL rows — cold and
    # warm, batched (small launch_bytes forces macro-batching) and not.
    import dataclasses as _dc

    plain_cols = {
        nm: _dc.replace(c, code_bits=None, packed=None)
        for nm, c in stacked.columns.items()
    }
    plain = StackedTable(
        stacked.schema, plain_cols, stacked.valid, stacked.num_docs,
        indexes=stacked.indexes,
    )
    parity = {"bit_exact": True, "cases": 0}
    parity_sqls = [sql, scan_bound_sql, agg_bound_sql]
    for lb in (None, 8 << 20):
        eng_p = DistributedEngine(launch_bytes=lb) if lb else DistributedEngine()
        eng_p.register_table("lineorder", stacked)
        eng_u = DistributedEngine(launch_bytes=lb) if lb else DistributedEngine()
        eng_u.register_table("lineorder", plain)
        for sql_s in parity_sqls:
            q = parse_query(sql_s)
            cold_p = [tuple(r) for r in eng_p.execute(q).rows]
            cold_u = [tuple(r) for r in eng_u.execute(q).rows]
            warm_p = [tuple(r) for r in eng_p.execute(q).rows]
            warm_u = [tuple(r) for r in eng_u.execute(q).rows]
            parity["cases"] += 1
            if not (cold_p == cold_u == warm_p == warm_u):
                parity["bit_exact"] = False
                parity.setdefault("mismatches", []).append(
                    {"sql": sql_s, "batched": bool(lb)}
                )
    assert parity["bit_exact"], f"packed/unpacked parity FAILED: {parity}"

    report = {
        "metric": "ssb_groupby_rows_scanned_per_sec",
        "value": round(rows_per_sec, 1),
        "unit": "rows/sec",
        "vs_baseline": round(rows_per_sec / JAVA_SERVER_ROWS_PER_SEC, 3),
        "value_marginal": round(marg, 1),
        "value_amortized_floor": round(amortized, 1),
        "run_variance": round(spread, 4),
        "timing_pairs": [[round(a, 4), round(b, 4)] for a, b in pairs],
        "invalid_pairs": n_invalid,
        "remeasure_rounds": remeasured,
        "value_e2e": round(n / e2e, 1),
        "e2e_seconds": round(e2e, 4),
        "latency_ms": {
            "count": lat["count"],
            "p50": round(lat["p50Ms"], 3),
            "p95": round(lat["p95Ms"], 3),
            "p99": round(lat["p99Ms"], 3),
            "mean": round(lat["meanMs"], 3),
            "max": round(lat["maxMs"], 3),
        },
        "trace_stage_ms": stage_ms,
        "distinct_literal_sweep": sweep,
        "plan_cache": {
            "hits": plan_cache["hits"],
            "cold_compiles": plan_cache["cold_compiles"],
            "warm_recompiles": plan_cache["warm_recompiles"],
            "hit_rate": round(plan_cache["hit_rate"], 3),
        },
        "rows": n,
        "filter_index_uses": index_uses,
        "cpu_proxy_rows_per_sec": round(_cpu_proxy(), 1),
        "baseline_denominator": JAVA_SERVER_ROWS_PER_SEC,
        "backend": ops.scan_backend(),
        # logical (decoded-width) model: how fast the engine consumes the
        # query's data; the packed physical figure drives the roofline
        "effective_bytes_per_sec": round(rows_per_sec * logical_bytes_per_row, 1),
        "logical_bytes_per_row": round(logical_bytes_per_row, 3),
        "physical_bytes_per_sec": round(rows_per_sec * bytes_per_row, 1),
        "scan_bound": scan_bound,
        "agg_bound": agg_bound,
        "packed_parity": parity,
        "roofline": roofline,
        "overload": _overload_bench(),
        "tail_latency": _tail_latency_bench(),
        "concurrent_qps": _concurrent_qps_bench(),
        "mesh_scaling": _mesh_scaling_bench(),
        "working_set_sweep": _working_set_sweep(),
        "failover": _failover_bench(),
        "autopilot_overload": _autopilot_overload_bench(),
    }
    print(json.dumps(report))

    # ---- bench history (regression gate input) ------------------------
    # One flat line per run; `cli perf --check` compares the newest line
    # against the pinned BENCH_BASELINE.json.  PINOT_TPU_BENCH_HISTORY=0
    # disables; any other value overrides the path.
    history = os.environ.get(
        "PINOT_TPU_BENCH_HISTORY",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_history.jsonl"),
    )
    if history != "0":
        rec = perf.bench_record(report)
        rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        perf.append_bench_history(history, rec)


if __name__ == "__main__":
    main()
