#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that pinot_tpu still starts on the chip.

Drives the served query path once at real size on ONE TPU chip, through the
entry points a client uses:

  device   jax.devices(); anything but a TPU exits non-zero at once.  The
           chip's own program must be in force: scan_backend()="pallas",
           accum_policy()="chunked32".
  kernels  ten kernel-level exactness checks against numpy (signed int32 /
           int64 limb sums, bitmap unpack, sparse tables, macro-batched
           range-index group-by, sketches, MV explode).
  load     a lineorder-shaped table (make_data below), made from --seed
           (--rows, default 2^25: see DEFAULT_ROWS for the cut from 2^27),
           built as 1.5M-row segments -> Coordinator.add_segment -> one
           ServerInstance on the chip, columns resident in HBM (packed).
  served   QueryServer over the Broker; five queries POSTed over HTTP, each
           cold then warm, every answer compared with numpy on the same
           arrays (integers exactly).
  stacked  the same data as one StackedTable through DistributedEngine.

`--chips 4` runs ONLY the cross-chip path and what it is compared with: the
DistributedEngine over a 1x4 mesh, then four ServerInstances (one per chip)
behind one Broker.

One JSON object per phase line; any phase that raises, any `match: false`,
any plan on an unexpected backend -> non-zero exit and no final line.  The
last stdout line of a passing run is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

One process, JAX imported once, no child process.  `--rehearse` is the
sandbox rehearsal (CPU, interpret-mode scan): every line it prints is marked
as a rehearsal and it never prints the final `ok` line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

HEADLINE_ROWS = 1 << 27  # the repo's headline size
# The default is cut to 2^25, the floor, for two measured reasons (PR 22):
# time — the sparse query (d), with all 1.32M groups tracked so its answer is
# exact, runs ~2.1 us/row served and ~0.6 us/row stacked on one v5e, and the
# 2^27 run had used 894.9 s of the smoke's 1200 s with two stacked queries to
# go; memory — XLA holds a materialized lane unpack of a packed column as a
# [words, lanes] view tile-padded to 512 B per word, so the stacked (d), whose
# row sort cannot fuse the unpack of the 16-bit key, needs 16.25G of the
# chip's 15.75G at 2^26 rows (AOT compile for a described v5e) and 8G at 2^25.
# --rows runs larger sizes where the caller has the time and the memory.
DEFAULT_ROWS = 1 << 25
ROWS_CUT_REASON = (
    "time: query (d) tracks all 1.32M groups at ~2.1 us/row served, ~0.6 us/row stacked, and "
    "2^27 rows overran the 1200 s limit; memory: the stacked (d) holds the 16-bit key's lane "
    "unpack tile-padded to 512 B/word, 16.25G of 15.75G HBM at 2^26 rows"
)
MIN_ROWS = 1 << 25
SEGMENT_ROWS = 1_500_000  # upstream pinot-perf segment size (SURVEY.md section 6)
TABLE = "lineorder"
OD_CARD, QTY_CARD, DISC_CARD = 2406, 50, 11
NUM_GROUPS_D = OD_CARD * QTY_CARD * DISC_CARD  # 1,323,300 possible groups

# every group must be tracked for the sparse answer to be EXACT (the default
# numGroupsLimit trims per segment/device, an accuracy valve by design)
_OPTS = f"SET numGroupsLimit = {NUM_GROUPS_D}; "
QUERIES: Dict[str, str] = {
    # bitmap words + 16-bit packed key + int64_sum
    "a": (
        f"SELECT lo_orderdate, SUM(lo_revenue) FROM {TABLE} "
        "WHERE lo_quantity < 25 GROUP BY lo_orderdate LIMIT 2500"
    ),
    # 4-bit lanes
    "b": f"SELECT COUNT(*) FROM {TABLE} WHERE lo_discount = 7",
    # three aggregates share one scan
    "c": (
        f"SELECT lo_orderdate, COUNT(*), SUM(lo_revenue), AVG(lo_quantity) "
        f"FROM {TABLE} GROUP BY lo_orderdate LIMIT 2500"
    ),
    # past the dense table: sort + scatter, merge_sparse_tables
    "d": (
        _OPTS + f"SELECT lo_orderdate, lo_quantity, lo_discount, SUM(lo_revenue) "
        f"FROM {TABLE} GROUP BY lo_orderdate, lo_quantity, lo_discount "
        "ORDER BY SUM(lo_revenue) DESC LIMIT 100"
    ),
    # filtered selection, fully ordered so the answer is one list
    "e": (
        f"SELECT lo_orderdate, lo_quantity, lo_revenue FROM {TABLE} "
        "WHERE lo_discount = 3 AND lo_quantity > 45 "
        "ORDER BY lo_revenue DESC, lo_orderdate DESC, lo_quantity DESC LIMIT 10"
    ),
}
# the kernel each query's dense scan must get on the chip (scalar
# aggregations, the sparse sort path and selections have no dense scan)
EXPECTED_BACKEND = {"a": "pallas", "b": "xla", "c": "pallas", "d": "xla", "e": "xla"}


class SmokeFailure(Exception):
    """A phase ran and its outcome is wrong."""


class Reporter:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse

    def emit(self, phase: str, **fields: Any) -> None:
        line: Dict[str, Any] = {"phase": phase}
        if self.rehearse:
            line["rehearsal"] = True
        line.update(fields)
        print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# data and the plain reference
# ---------------------------------------------------------------------------
def make_data(rows: int, seed: int) -> Dict[str, np.ndarray]:
    """Four lineorder columns: orderdate card 2406 -> 16-bit lanes, quantity
    card 50 with a range index, discount card 11 -> 4-bit lanes, revenue
    int64."""
    rng = np.random.default_rng(seed)
    return {
        "lo_orderdate": (19920101 + rng.integers(0, OD_CARD, rows)).astype(np.int32),
        "lo_quantity": rng.integers(1, QTY_CARD + 1, rows).astype(np.int32),
        "lo_discount": rng.integers(0, DISC_CARD, rows).astype(np.int32),
        "lo_revenue": rng.integers(100, 1_000_000, rows).astype(np.int64),
    }


def _exact_group_sums(keys: np.ndarray, values: np.ndarray, size: int) -> np.ndarray:
    """int64 group sums via two float64 bincounts over 2^20 halves: each
    half-sum stays far below 2^53, so the result is exact."""
    lo = np.bincount(keys, weights=(values & 0xFFFFF).astype(np.float64), minlength=size)
    hi = np.bincount(keys, weights=(values >> 20).astype(np.float64), minlength=size)
    return lo.astype(np.int64) + (hi.astype(np.int64) << 20)


def reference_answers(data: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """Plain numpy answers to QUERIES over the same generated arrays."""
    od = data["lo_orderdate"]
    qty = data["lo_quantity"]
    disc = data["lo_discount"]
    rev = data["lo_revenue"]
    odc = (od - 19920101).astype(np.int64)
    ref: Dict[str, Any] = {}

    m = qty < 25
    cnt = np.bincount(odc[m], minlength=OD_CARD)
    sums = _exact_group_sums(odc[m], rev[m], OD_CARD)
    ref["a"] = {int(19920101 + g): int(sums[g]) for g in np.flatnonzero(cnt)}

    ref["b"] = int((disc == 7).sum())

    cnt = np.bincount(odc, minlength=OD_CARD)
    sums = _exact_group_sums(odc, rev, OD_CARD)
    qsum = _exact_group_sums(odc, qty.astype(np.int64), OD_CARD)
    ref["c"] = {
        int(19920101 + g): (int(cnt[g]), int(sums[g]), int(qsum[g]) / int(cnt[g]))
        for g in np.flatnonzero(cnt)
    }

    key = (odc * QTY_CARD + (qty - 1)) * DISC_CARD + disc
    dsums = _exact_group_sums(key, rev, NUM_GROUPS_D)
    ref["d"] = {"sums": dsums, "top": np.sort(dsums)[::-1][:100]}

    sel = np.flatnonzero((disc == 3) & (qty > 45))
    order = np.lexsort((-qty[sel], -od[sel].astype(np.int64), -rev[sel]))[:10]
    ref["e"] = [
        (int(od[i]), int(qty[i]), int(rev[i])) for i in sel[order]
    ]
    return ref


def _as_int(x: Any) -> int:
    if isinstance(x, float) and not x.is_integer():
        raise SmokeFailure(f"expected an integer value, got {x!r}")
    return int(x)


def check_answer(qid: str, rows: List[List[Any]], ref: Dict[str, Any]) -> Tuple[bool, str]:
    """(match, detail) of one query's rows against the reference."""
    if qid == "a":
        got = {_as_int(r[0]): _as_int(r[1]) for r in rows}
        return got == ref["a"], f"{len(got)} groups"
    if qid == "b":
        return _as_int(rows[0][0]) == ref["b"], f"count {rows[0][0]}"
    if qid == "c":
        exp = ref["c"]
        if {_as_int(r[0]) for r in rows} != set(exp) or len(rows) != len(exp):
            return False, "group set differs"
        for r in rows:
            c, s, avg = exp[_as_int(r[0])]
            if _as_int(r[1]) != c or _as_int(r[2]) != s:
                return False, f"group {r[0]}: count/sum differ"
            if abs(float(r[3]) - avg) > 1e-9 * abs(avg):
                return False, f"group {r[0]}: avg differs"
        return True, f"{len(rows)} groups"
    if qid == "d":
        sums = ref["d"]["sums"]
        got = []
        for r in rows:
            k = ((_as_int(r[0]) - 19920101) * QTY_CARD + (_as_int(r[1]) - 1)) * DISC_CARD + _as_int(r[2])
            if int(sums[k]) != _as_int(r[3]):
                return False, f"group {r[:3]}: sum differs"
            got.append(_as_int(r[3]))
        # ties at the cut may pick different groups; the sums may not differ
        ok = got == [int(v) for v in ref["d"]["top"][: len(got)]] and len(got) == min(
            100, int((sums > 0).sum())
        )
        return ok, f"top {len(got)}"
    if qid == "e":
        got = [tuple(_as_int(v) for v in r) for r in rows]
        return got == ref["e"], f"{len(got)} rows"
    raise KeyError(qid)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def _traced_backends() -> Dict[str, int]:
    from pinot_tpu.utils.metrics import METRICS

    counters = METRICS.snapshot()["counters"]
    return {
        k.rsplit(".", 1)[1]: int(v) for k, v in counters.items() if k.startswith("scan.traced.")
    }


def run_queries(
    rep: Reporter,
    phase: str,
    qids: List[str],
    run_sql: Callable[[str], Tuple[List[List[Any]], Optional[float]]],
    ref: Dict[str, Any],
    pallas_name: str,
) -> None:
    """Each query cold then warm; compare both answers; report the kernel the
    cold run's trace picked.  run_sql -> (rows, compile seconds or None)."""
    for qid in qids:
        before = _traced_backends()
        t0 = time.perf_counter()
        rows_cold, compile_s = run_sql(QUERIES[qid])
        cold_s = time.perf_counter() - t0
        after = _traced_backends()
        t0 = time.perf_counter()
        rows_warm, _ = run_sql(QUERIES[qid])
        warm_s = time.perf_counter() - t0
        traced = {k: after.get(k, 0) - before.get(k, 0) for k in after}
        backend = pallas_name if traced.get(pallas_name, 0) > 0 else "xla"
        expected = pallas_name if EXPECTED_BACKEND[qid] == "pallas" else "xla"
        ok_c, detail = check_answer(qid, rows_cold, ref)
        ok_w, _ = check_answer(qid, rows_warm, ref)
        match = bool(ok_c and ok_w)
        rep.emit(
            phase,
            query=qid,
            backend=backend,
            expected_backend=expected,
            compile_s=None if compile_s is None else round(compile_s, 3),
            cold_s=round(cold_s, 3),
            warm_s=round(warm_s, 3),
            match=match,
            detail=detail,
        )
        if not match:
            raise SmokeFailure(f"{phase} query {qid}: answer differs from the numpy reference")
        if backend != expected:
            raise SmokeFailure(f"{phase} query {qid}: plan ran on {backend}, expected {expected}")


def phase_kernels(rep: Reporter) -> None:
    """The ten exactness assertions that used to run in a child process of
    the test suite, now on the device this process holds."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu import ops
    from pinot_tpu.parallel import mesh as mesh_mod
    from pinot_tpu.parallel.engine import DistributedEngine
    from pinot_tpu.parallel.stacked import StackedTable
    from pinot_tpu.query import planner
    from pinot_tpu.query.engine import QueryEngine
    from pinot_tpu.query.functions import get_agg_function
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.spi.config import IndexingConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
    from pinot_tpu.sql.parser import parse_query

    t_start = time.perf_counter()
    rng = np.random.default_rng(7)
    n, G = 200_000, 64
    done: List[str] = []

    def need(cond: bool, what: str) -> None:
        if not cond:
            raise SmokeFailure(f"kernels: {what}")
        done.append(what)

    codes_np = rng.integers(0, G, n).astype(np.int32)
    vals_np = rng.integers(-1_000_000, 1_000_000, n).astype(np.int32)
    mask_np = rng.random(n) < 0.7
    codes, vals, mask = jnp.asarray(codes_np), jnp.asarray(vals_np), jnp.asarray(mask_np)
    be = ops.scan_backend()

    def np_group(v64: np.ndarray) -> np.ndarray:
        out = np.zeros(G, dtype=np.int64)
        np.add.at(out, codes_np, np.where(mask_np, v64, 0))
        return out

    exp = np_group(vals_np.astype(np.int64))
    got = jax.device_get(jax.jit(lambda v, m, c: ops.group_sum(v, m, c, G))(vals, mask, codes))
    need(np.array_equal(np.asarray(got).astype(np.int64), exp), "grouped int32 SUM exact")
    [t32] = jax.device_get(
        jax.jit(lambda v, m, c: ops.fused_group_tables([("int_sum", v, m, (4, True))], c, G, backend=be))(
            vals, mask, codes
        )
    )
    need(np.array_equal(np.asarray(t32).astype(np.int64), exp), f"fused int32 SUM exact ({be})")
    got_s = float(jax.device_get(jax.jit(ops.masked_sum)(vals, mask)))
    need(got_s == float(exp.sum()), "masked int32 SUM exact")

    words_np = rng.integers(0, 2**32, 2048, dtype=np.uint64).astype(np.uint32)
    got_b = jax.device_get(
        jax.jit(lambda w: ops.unpack_bitmap_words(w, 2048 * 32))(jnp.asarray(words_np))
    )
    exp_b = np.unpackbits(words_np.view(np.uint8), bitorder="little").astype(bool)
    need(np.array_equal(np.asarray(got_b), exp_b), "bitmap word unpack exact")

    vals64_np = rng.integers(-(1 << 35), 1 << 35, n, dtype=np.int64)
    vals64 = jnp.asarray(vals64_np)
    exp64 = np_group(vals64_np)
    got64 = jax.device_get(jax.jit(lambda v, m, c: ops.group_sum(v, m, c, G))(vals64, mask, codes))
    need(np.array_equal(np.asarray(got64).astype(np.int64), exp64), "grouped int64 SUM exact")
    [t64] = jax.device_get(
        jax.jit(lambda v, m, c: ops.fused_group_tables([("int64_sum", v, m, 8)], c, G, backend=be))(
            vals64, mask, codes
        )
    )
    need(np.array_equal(np.asarray(t64).astype(np.int64), exp64), f"fused int64 SUM exact ({be})")
    got64_s = float(jax.device_get(jax.jit(ops.masked_sum)(vals64, mask)))
    need(got64_s == float(np.where(mask_np, vals64_np, 0).sum()), "masked int64 SUM exact")

    neg1 = jnp.full((n,), -1, jnp.int64)
    gneg = jax.device_get(
        jax.jit(lambda v, c: ops.group_sum(v, jnp.ones((n,), bool), c, G))(neg1, codes)
    )
    need(
        np.array_equal(np.asarray(gneg).astype(np.int64), -np.bincount(codes_np, minlength=G)),
        "all -1 int64 SUM exact",
    )

    key_np = rng.integers(0, 5000, n).astype(np.int64)
    sum_fn = get_agg_function("sum")
    uniq, partials = jax.device_get(
        jax.jit(
            lambda v, m, k: planner.sparse_grouped_tables([sum_fn], [(v, m)], m, k, 6000)
        )(vals.astype(jnp.float64), mask, jnp.asarray(key_np))
    )
    uniq = np.asarray(uniq)
    present = uniq != planner.SPARSE_EMPTY_KEY
    hsum = np.bincount(key_np[mask_np], weights=vals_np[mask_np].astype(np.float64), minlength=5000)
    hcnt = np.bincount(key_np[mask_np], minlength=5000)
    need(
        np.array_equal(uniq[present], np.flatnonzero(hcnt))
        and np.array_equal(np.asarray(partials[0]["sum"])[present], hsum[hcnt > 0]),
        "sparse group tables exact",
    )

    n2 = 1 << 16
    schema = Schema(
        "t",
        [
            FieldSpec("g", DataType.INT),
            FieldSpec("q", DataType.INT),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    data = {
        "g": rng.integers(0, 50, n2).astype(np.int32),
        "q": rng.integers(0, 100, n2).astype(np.int32),
        "v": rng.integers(-(10**9), 10**9, n2).astype(np.int64),
    }
    cfg = TableConfig("t", indexing=IndexingConfig(range_index_columns=["q"]))
    # launch_bytes this small forces several macro-batched launches
    eng = DistributedEngine(mesh=mesh_mod.default_mesh(num_devices=1), launch_bytes=n2 * 3)
    st = StackedTable.build(schema, dict(data), 1, table_config=cfg)
    eng.register_table("t", st)
    ctx = parse_query("SELECT g, SUM(v), COUNT(*) FROM t WHERE q < 37 GROUP BY g ORDER BY g LIMIT 64")
    need(len(eng._plan(ctx, st).batch_offsets) >= 2, "macro-batched launches planned")
    r = eng.execute(ctx)
    fm = data["q"] < 37
    esum = _exact_group_sums(data["g"][fm].astype(np.int64), data["v"][fm] + 10**9, 50)
    ecnt = np.bincount(data["g"][fm], minlength=50)
    need(
        ("q", "range") in r.stats.filter_index_uses
        and {int(a): (int(b), int(c)) for a, b, c in r.rows}
        == {g: (int(esum[g] - 10**9 * ecnt[g]), int(ecnt[g])) for g in np.flatnonzero(ecnt)},
        "macro-batched range-index group-by exact",
    )

    rdc = eng.query("SELECT DISTINCTCOUNT(g) FROM t")
    true_v = len(np.unique(data["v"]))
    rhll = eng.query("SELECT DISTINCTCOUNTHLL(v) FROM t")
    need(
        int(rdc.rows[0][0]) == len(np.unique(data["g"]))
        and abs(int(rhll.rows[0][0]) - true_v) / true_v < 0.1,
        "sketches on the device",
    )

    mv_schema = Schema(
        "m",
        [
            FieldSpec("tags", DataType.STRING, single_value=False),
            FieldSpec("x", DataType.INT, role=FieldRole.METRIC),
        ],
    )
    pool = np.asarray(["a", "b", "c", "d"])
    mv_rows = np.empty(5000, dtype=object)
    for i in range(5000):
        mv_rows[i] = list(rng.choice(pool, int(rng.integers(0, 4))))
    xs = rng.integers(0, 100, 5000).astype(np.int32)
    qe = QueryEngine()
    qe.register_table(mv_schema)
    qe.add_segment("m", build_segment(mv_schema, {"tags": mv_rows, "x": xs}, "s0"))
    rmv = qe.query("SELECT tags, COUNT(*), SUM(x) FROM m GROUP BY tags ORDER BY tags LIMIT 10")
    emv: Dict[str, Tuple[int, int]] = {}
    for row_tags, x in zip(mv_rows, xs):
        for t in row_tags:
            c0, s0 = emv.get(t, (0, 0))
            emv[t] = (c0 + 1, s0 + int(x))
    need({a: (int(b), int(c)) for a, b, c in rmv.rows} == emv, "MV explode group-by exact")

    rep.emit("kernels", checks=done, match=True, seconds=round(time.perf_counter() - t_start, 1))


def _schema_and_config():
    from pinot_tpu.spi.config import IndexingConfig, TableConfig
    from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema

    schema = Schema(
        TABLE,
        [
            FieldSpec("lo_orderdate", DataType.INT),
            FieldSpec("lo_quantity", DataType.INT),
            FieldSpec("lo_discount", DataType.INT),
            FieldSpec("lo_revenue", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    cfg = TableConfig(TABLE, indexing=IndexingConfig(range_index_columns=["lo_quantity"]))
    return schema, cfg


def build_cluster(rep: Reporter, data, segment_rows: int, devices) -> Tuple[Any, List[Any]]:
    """Segments -> Coordinator -> one ServerInstance per device, columns
    staged into that device's HBM (packed forward indexes)."""
    import jax

    from pinot_tpu.cluster.coordinator import Coordinator
    from pinot_tpu.cluster.server import ServerInstance
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.utils import native

    t0 = time.perf_counter()
    schema, cfg = _schema_and_config()
    coord = Coordinator(replication=1)
    servers = [ServerInstance(f"server{i}", device=d) for i, d in enumerate(devices)]
    for s in servers:
        coord.register_server(s)
    coord.add_table(schema, cfg)
    rows = len(data["lo_orderdate"])
    n_seg = 0
    for start in range(0, rows, segment_rows):
        chunk = {k: v[start : start + segment_rows] for k, v in data.items()}
        coord.add_segment(TABLE, build_segment(schema, chunk, f"seg{n_seg}", table_config=cfg))
        n_seg += 1
    staged = 0
    placed = set()
    for s in servers:
        for seg in s.segments.get(TABLE, {}).values():
            tree = seg.to_device(device=s.device, packed_codes=True, residency=s.residency)
            for leaf in jax.tree_util.tree_leaves(tree):
                staged += int(leaf.nbytes)
                placed |= set(leaf.devices())
    stats = devices[0].memory_stats() or {}
    rep.emit(
        "load",
        rows=rows,
        segments=n_seg,
        segment_rows=segment_rows,
        servers=len(servers),
        bytes_staged=staged,
        device0_bytes_in_use=stats.get("bytes_in_use"),
        devices_holding_segments=len(placed),
        native_library=native.available(),
        seconds=round(time.perf_counter() - t0, 1),
    )
    if len(placed) != len(devices):
        raise SmokeFailure(
            f"segments live on {len(placed)} devices, expected {len(devices)}: {sorted(map(str, placed))}"
        )
    return coord, servers


def phase_served(rep: Reporter, coord, servers, qids: List[str], ref, pallas_name: str, phase="served") -> None:
    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.rest import PinotClient, QueryServer

    broker = Broker(coord)
    srv = QueryServer(broker).start()
    try:
        client = PinotClient(f"http://127.0.0.1:{srv.port}")

        def compile_ms_total() -> float:
            total = 0.0
            for s in servers:
                t = s.metrics.snapshot()["timers"].get("server.compileMs")
                if t:
                    total += t["count"] * t["meanMs"]
            return total

        def run_sql(sql: str):
            before = compile_ms_total()
            payload = client.execute(sql)
            if payload.get("exceptions") or payload.get("partialResult"):
                raise SmokeFailure(f"partial answer: {payload.get('exceptions')}")
            return payload["resultTable"]["rows"], (compile_ms_total() - before) / 1000.0

        run_queries(rep, phase, qids, run_sql, ref, pallas_name)
    finally:
        srv.stop()


def phase_stacked(rep: Reporter, data, mesh, qids: List[str], ref, pallas_name: str, phase="stacked") -> None:
    from pinot_tpu.parallel.engine import DistributedEngine
    from pinot_tpu.parallel.stacked import StackedTable

    schema, cfg = _schema_and_config()
    t0 = time.perf_counter()
    stacked = StackedTable.build(schema, data, num_shards=mesh.devices.size, table_config=cfg)
    engine = DistributedEngine(mesh=mesh)
    engine.register_table(TABLE, stacked)
    rep.emit(
        phase,
        step="build",
        mesh=dict(zip(mesh.axis_names, (int(d) for d in mesh.devices.shape))),
        rows=int(stacked.num_docs),
        seconds=round(time.perf_counter() - t0, 1),
    )

    def run_sql(sql: str):
        r = engine.query(sql)
        return [list(row) for row in r.rows], r.stats.compile_ms / 1000.0

    run_queries(rep, phase, qids, run_sql, ref, pallas_name)


# ---------------------------------------------------------------------------
def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS)
    ap.add_argument("--segment-rows", type=int, default=SEGMENT_ROWS)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--rehearse",
        action="store_true",
        help="sandbox rehearsal on the CPU (interpret-mode scan); never prints the ok line",
    )
    args = ap.parse_args(argv)
    try:
        return _run(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1


def _run(args: argparse.Namespace) -> int:
    rep = Reporter(args.rehearse)

    restore_env: Dict[str, Optional[str]] = {}
    if args.rehearse:
        # the rehearsal picks its own platform; the real run takes what is there
        for k, v in (("JAX_PLATFORMS", "cpu"), ("PINOT_TPU_SCAN_BACKEND", "interpret")):
            restore_env[k] = os.environ.get(k)
            os.environ[k] = v
        if "jax" not in sys.modules and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
            ).strip()
    elif args.rows < MIN_ROWS:
        print(f"--rows {args.rows} is below the floor of {MIN_ROWS}", file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if not args.rehearse and platform != "tpu":
        print(f"chip_smoke needs a TPU; JAX found platform {platform!r}", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"--chips {args.chips} but JAX found {len(devs)} device(s)", file=sys.stderr)
        return 2
    devs = devs[: args.chips]

    from pinot_tpu import ops
    from pinot_tpu.ops import segmented
    from pinot_tpu.parallel import mesh as mesh_mod

    ops.scan_backend.cache_clear()
    try:
        pallas_name = "interpret" if args.rehearse else "pallas"
        rep.emit(
            "device",
            platform=platform,
            kind=devs[0].device_kind,
            count=len(devs),
            scan_backend=ops.scan_backend(),
            accum_policy=segmented.accum_policy(),
            compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_compilation_cache_dir,
        )
        if ops.scan_backend() != pallas_name:
            raise SmokeFailure(f"scan_backend() is {ops.scan_backend()!r}, expected {pallas_name!r}")
        if not args.rehearse and segmented.accum_policy() != "chunked32":
            raise SmokeFailure(f"accum_policy() is {segmented.accum_policy()!r}, expected 'chunked32'")
        rep.emit(
            "rows",
            rows=args.rows,
            headline_rows=HEADLINE_ROWS,
            cut=args.rows < HEADLINE_ROWS,
            **({"reason": ROWS_CUT_REASON} if args.rows == DEFAULT_ROWS else {}),
        )

        t0 = time.perf_counter()
        data = make_data(args.rows, args.seed)
        ref = reference_answers(data)
        rep.emit("reference", rows=args.rows, seed=args.seed, seconds=round(time.perf_counter() - t0, 1))

        if args.chips == 1:
            phase_kernels(rep)
            coord, servers = build_cluster(rep, data, args.segment_rows, devs)
            phase_served(rep, coord, servers, list("abcde"), ref, pallas_name)
            for s in servers:  # hand the HBM back before the stacked copy lands
                for name in list(s.segment_names(TABLE)):
                    s.drop_segment(TABLE, name)
            del coord, servers
            phase_stacked(
                rep, data, mesh_mod.default_mesh(num_devices=1), list("abcd"), ref, pallas_name
            )
        else:
            mesh = mesh_mod.make_mesh2d(1, 4, num_devices=4)
            phase_stacked(rep, data, mesh, list("acd"), ref, pallas_name, phase="mesh_1x4")
            coord, servers = build_cluster(rep, data, args.segment_rows, devs)
            phase_served(rep, coord, servers, ["a"], ref, pallas_name, phase="four_servers")
    finally:
        for k, v in restore_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        ops.scan_backend.cache_clear()

    if args.rehearse:
        rep.emit("done", phases_passed=True)
        return 0
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": platform,
                    "kind": devs[0].device_kind,
                    "count": len(jax.devices()),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
