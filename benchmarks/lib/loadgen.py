"""The load generator: one process, a few threads, requests over HTTP to the
front door.  A traffic mix is a data file (`traffic/<mix>.json`); this is the
one general generator that reads it.

closed loop: `clients` threads, each sending its next request when the last
one is answered, rotating through `templates` from an offset set by the seed.
open loop: arrivals on a schedule fixed by the mix (`schedule_seed`): every
--seed offers the same Poisson sample of due times and the same template at
each of them, and draws only the literals (and the table).  The templates are
not drawn independently either: each gets its exact share of the offered
requests (largest remainder), in one drawn order.  A fresh sample per seed
moved the tails by tens of per cent on the chip (a burst of group-bys in one
run, none in the next): that is the seed changing the work.  So the tail a
run reports is that of one arrival trace, not of the Poisson process.  A request is
timed from when it was DUE, and how late the generator sent it is reported.
"""
from __future__ import annotations

import http.client
import json
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from lib import templates as tpl

REQUEST_TIMEOUT_S = 120.0
# The kernel dropped the connection before the front door answered (its listen queue holds 5: a stall of
# the host overflows it).  A client library connects again, so the generator does, once; the time counts.
RECONNECT_ON = (ConnectionResetError, ConnectionRefusedError, BrokenPipeError)


@dataclass
class Request:
    index: int
    client: int
    template: str
    params: Dict[str, int]
    due: float  # seconds from the window's start (open loop: the schedule; closed: when sent)
    sent: float = 0.0
    done: float = 0.0
    status: int = 0
    error: Optional[str] = None
    columns: List[str] = field(default_factory=list)
    rows: List[List[Any]] = field(default_factory=list)
    meta: Dict[str, Any] = field(default_factory=dict)
    spans: Optional[Dict[str, Any]] = None
    reconnects: int = 0

    @property
    def latency_s(self) -> float:
        return self.done - self.due


def post(url: str, sql: str) -> Dict[str, Any]:
    """One query over a connection of its own (the front door speaks
    HTTP/1.0 and closes after each answer)."""
    conn = http.client.HTTPConnection(url, timeout=REQUEST_TIMEOUT_S)
    try:
        conn.request("POST", "/query/sql", json.dumps({"sql": sql}), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        return {"status": resp.status, "body": json.loads(body) if body else {}}
    finally:
        conn.close()


def send(url: str, req: Request, template: Dict[str, Any], traced: bool, t0: float) -> None:
    """Send one request and record its answer and its times (seconds from t0)."""
    sql = tpl.render(template, req.params, traced)
    req.sent = time.perf_counter() - t0
    try:
        try:
            out = post(url, sql)
        except RECONNECT_ON:
            req.reconnects = 1
            out = post(url, sql)
        req.status = out["status"]
        body = out["body"]
        if req.status == 200:
            table = body.get("resultTable") or {}
            req.columns = list((table.get("dataSchema") or {}).get("columnNames") or [])
            req.rows = table.get("rows") or []
            req.spans = body.get("trace")
            req.meta = {k: body.get(k) for k in (
                "partialResult", "exceptions", "numSegmentsQueried", "numServersQueried",
                "numServersResponded", "numDocsScanned", "timeUsedMs")}
        else:
            req.error = str(body.get("errorCode") or body.get("error") or req.status)
    except (OSError, http.client.HTTPException, ValueError) as e:
        req.error = f"{type(e).__name__}: {e}"
    req.done = time.perf_counter() - t0


def plan_closed(mix: Dict[str, Any], seed: int) -> Dict[str, Any]:
    names = list(mix["templates"])
    clients = int(mix["clients"])
    base = int(np.random.default_rng([int(seed), 0xC105ED]).integers(0, len(names)))
    # the clients keep a fixed spacing in the rotation; the seed turns the wheel
    offsets = [(base + (i * len(names)) // clients) % len(names) for i in range(clients)]
    return {"names": names, "clients": clients, "offsets": offsets}


def plan_open(mix: Dict[str, Any], seed: int, seconds: float) -> List[Dict[str, Any]]:
    """[{due, template}] for the window, from the mix alone (`seed` only
    names the run: the schedule is the same for every seed).  Each template
    gets its exact share of the requests, then the order is drawn."""
    fixed = np.random.default_rng(int(mix["schedule_seed"]))
    rate = float(mix["rate_qps"])
    gaps = fixed.exponential(1.0 / rate, size=int(rate * seconds * 1.5) + 64)
    n = int(np.searchsorted(np.cumsum(gaps), seconds))
    gaps = gaps[:n]
    names = list(mix["weights"])
    w = np.asarray([float(mix["weights"][k]) for k in names])
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[: n - int(counts.sum())]:
        counts[i] += 1
    assigned = np.repeat(np.arange(len(names)), counts)
    due = np.cumsum(gaps)
    assigned = fixed.permutation(assigned)
    return [{"due": float(d), "template": names[int(a)]} for d, a in zip(due, assigned)]


def run_closed(url, mix, query_set, seed, seconds, traced=False) -> Dict[str, Any]:
    plan = plan_closed(mix, seed)
    out: List[List[Request]] = [[] for _ in range(plan["clients"])]
    t0 = time.perf_counter()

    def client(ci: int) -> None:
        rng = np.random.default_rng([int(seed), 1 + ci])
        k = plan["offsets"][ci]
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                return
            name = plan["names"][k % len(plan["names"])]
            k += 1
            template = query_set["templates"][name]
            req = Request(len(out[ci]) * plan["clients"] + ci, ci, name, tpl.draw_params(template, rng), now)
            send(url, req, template, traced, t0)
            out[ci].append(req)

    threads = [threading.Thread(target=client, args=(i,), name=f"client{i}") for i in range(plan["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    reqs = sorted((r for c in out for r in c), key=lambda r: r.due)
    return {"requests": reqs, "t0": t0, "window_s": float(seconds), "offered": len(reqs),
            "late_ms": {"p50": 0.0, "max": 0.0}, "plan": {"offsets": plan["offsets"]},
            "reconnects": sum(r.reconnects for r in reqs)}


def run_open(url, mix, query_set, seed, seconds, traced=False,
             rate_qps: Optional[float] = None) -> Dict[str, Any]:
    mix = dict(mix, rate_qps=rate_qps) if rate_qps is not None else mix
    schedule = plan_open(mix, seed, seconds)
    rng = np.random.default_rng([int(seed), 1])
    reqs = [
        Request(i, -1, s["template"], tpl.draw_params(query_set["templates"][s["template"]], rng), s["due"])
        for i, s in enumerate(schedule)
    ]
    work: "queue.Queue[Optional[Request]]" = queue.Queue()
    t0 = time.perf_counter()

    def worker() -> None:
        while True:
            req = work.get()
            if req is None:
                return
            send(url, req, query_set["templates"][req.template], traced, t0)

    workers = [threading.Thread(target=worker, name=f"worker{i}") for i in range(int(mix["workers"]))]
    for t in workers:
        t.start()
    for req in reqs:  # the dispatcher: this thread sleeps until each request is due
        wait = req.due - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        work.put(req)
    for _ in workers:
        work.put(None)
    drain_until = time.perf_counter() + float(mix.get("drain_s", 20.0))
    for t in workers:
        t.join(timeout=max(0.0, drain_until - time.perf_counter()) + REQUEST_TIMEOUT_S)
    late = np.asarray([max(0.0, r.sent - r.due) for r in reqs if r.sent]) * 1000.0
    return {"requests": reqs, "t0": t0, "window_s": float(seconds), "offered": len(reqs),
            "late_ms": {"p50": float(np.median(late)) if late.size else 0.0,
                        "max": float(late.max()) if late.size else 0.0},
            "plan": {"rate_qps": float(mix["rate_qps"])}, "reconnects": sum(r.reconnects for r in reqs)}


def run(url, mix, query_set, seed, seconds, **kw) -> Dict[str, Any]:
    if mix["loop"] == "closed":
        kw.pop("rate_qps", None)
        return run_closed(url, mix, query_set, seed, seconds, **kw)
    if mix["loop"] == "open":
        return run_open(url, mix, query_set, seed, seconds, **kw)
    raise ValueError(f"traffic mix {mix.get('name')}: unknown loop {mix['loop']!r}")
