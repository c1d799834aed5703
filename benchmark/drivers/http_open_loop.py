"""Traffic kind ``http_open_loop``: requests on a seeded arrival
schedule over keep-alive HTTP to ``POST /queries.json`` of a
``QueryServer`` deployed in this process through the normal path
(``deploy()`` -> ``build_deployment`` -> ``warm_up``).

The load comes from child processes that never import jax
(``harness/loadgen.py``): the parent holds the chip, and the server's
threads share no interpreter lock with the generator.
"""

from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.harness import collect, data, oracle, schedule

LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "harness", "loadgen.py")


def start_server(ctx):
    """Seeded models persisted as an engine instance, then the server
    as ``pio deploy`` starts it. Returns (server, ServingState)."""
    from predictionio_tpu.utils import metrics
    from predictionio_tpu.workflow.create_server import (
        QueryServer,
        ServerConfig,
    )

    data.memory_storage()
    state = data.build_serving_instance(ctx.cell.config, ctx.seed, ctx.spans)
    c0 = metrics.JIT_COMPILE_SECONDS.value()
    t = time.perf_counter()
    server = QueryServer(ServerConfig(ip="127.0.0.1", port=0))
    server.start(undeploy_stale=False)
    ctx.spans["deploy_s"] = time.perf_counter() - t
    ctx.spans["compile_s"] = metrics.JIT_COMPILE_SECONDS.value() - c0
    return server, state


def _post(conn: http.client.HTTPConnection, query: Dict[str, Any]):
    conn.request("POST", "/queries.json", body=json.dumps(query),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    return resp.status, (json.loads(body) if body else None)


def oracle_round(ctx, state: data.ServingState, addr: Tuple[str, int],
                 tag: int, n: int, why: List[str]) -> None:
    """``n`` queries against the numpy float32 oracle built from the
    seeded tables: user queries (seen masked; two-stage: brute-force
    re-rank of the stage-1 top-N), and item-similarity queries where
    the mix sends them."""
    rng = np.random.default_rng([ctx.seed, 3, tag])
    st = state.structure
    mix = ctx.cell.traffic
    n_item = n // 8 if float(mix.get("item_query_share", 0.0)) > 0 else 0
    users = rng.choice(st.n_users, size=n - n_item, replace=False)
    conn = http.client.HTTPConnection(*addr, timeout=60)
    took: List[float] = []
    try:
        for u in users.tolist():
            seen = st.user_items(u)
            certain = None
            if state.candidates:
                want, certain = oracle.scores_two_stage(
                    state.user_factors[u], state.item_factors,
                    state.stage2_users[u], state.stage2_items, seen,
                    state.candidates, oracle.SCORE_RTOL)
            else:
                want = oracle.scores_single(
                    state.user_factors[u], state.item_factors, seen)
            t = time.perf_counter()
            status, body = _post(conn, {"user": f"u{u}", "num": 10})
            took.append(time.perf_counter() - t)
            bad = f"status {status}" if status != 200 else \
                oracle.check_answer(body.get("itemScores", []), want, 10,
                                    oracle.SCORE_RTOL, certain)
            if bad:
                why.append(f"user u{u}: {bad}")
        for _ in range(n_item):
            q = np.unique(rng.choice(st.n_items, size=int(
                rng.integers(1, 4)), replace=False))
            want = oracle.scores_similar(q, state.item_factors)
            status, body = _post(conn, {"items": [f"i{i}" for i in q],
                                        "num": 10})
            bad = f"status {status}" if status != 200 else \
                oracle.check_answer(body.get("itemScores", []), want, 10,
                                    oracle.SIMILAR_RTOL)
            if bad:
                why.append(f"items {q.tolist()}: {bad}")
    finally:
        conn.close()
    # one query at a time on an idle server: the floor of the latency
    ctx.spans[f"lone_query_p50_ms_{tag}"] = float(np.median(took)) * 1e3


def offer(ctx, addr: Tuple[str, int], state: data.ServingState,
          seconds: float, seed: int, rate_qps: Optional[float] = None,
          tag: str = "w", on_window=None) -> Dict[str, Any]:
    """Offer the mix's schedule and wait for the generators. Returns
    per-request arrays for the requests due inside the window, plus the
    window's epoch bounds. ``on_window(t0, t1)`` runs in the parent
    between spawning the generators and waiting for them."""
    mix = ctx.cell.traffic
    st = state.structure
    sched = schedule.build_schedule(mix, st.n_users, st.n_items, seed,
                                    seconds, rate_qps=rate_qps)
    gens = int(mix.get("generators", 2))
    timeout_s = float(mix.get("timeout_ms", 1000)) / 1e3
    paths = []
    for k in range(gens):
        p = os.path.join(ctx.workdir, f"sched-{tag}-{k}.npz")
        schedule.save_share(p, sched, k, gens)
        paths.append((p, os.path.join(ctx.workdir, f"out-{tag}-{k}.npz")))
    pool = int(mix.get("connection_pool", 0))
    # generators import numpy, open their pool (2 ms a connection),
    # then run the ramp: the window starts after all of that
    epoch = time.time() + float(mix.get("ramp_s", 0.0)) + 2.0 \
        + 0.003 * pool
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, LOADGEN, addr[0], str(addr[1]), sp, op,
         repr(epoch), repr(timeout_s), str(pool)], env=env)
        for sp, op in paths]
    try:
        if on_window is not None:
            on_window(epoch, epoch + seconds)
        deadline = epoch + seconds + timeout_s + 60.0
        for p in procs:
            rc = p.wait(timeout=max(1.0, deadline - time.time()))
            if rc != 0:
                raise RuntimeError(f"load generator exited {rc}")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
    parts = [np.load(op) for _, op in paths]
    cat = {k: np.concatenate([z[k] for z in parts])
           for k in ("index", "due", "sent", "done", "status", "ok")}
    order = np.argsort(cat["index"])
    cat = {k: v[order] for k, v in cat.items()}
    inside = cat["due"] >= 0.0
    out = {k: v[inside] for k, v in cat.items()}
    out["epoch"] = epoch
    out["seconds"] = seconds
    out["timeout_s"] = timeout_s
    return out


def summarise(res: Dict[str, Any]) -> Dict[str, float]:
    """Latency from the DUE time, over the answers that were well
    formed; every other request is a failure. Percentiles are taken
    over all requests due in the window with a failure counted at the
    time limit (past any latency limit, and finite, so the line stays
    JSON): a drop never flatters a tail."""
    lat = np.where(res["ok"], np.minimum(res["done"] - res["due"],
                                         res["timeout_s"]),
                   res["timeout_s"]) * 1e3
    late = (res["sent"] - res["due"]) * 1e3
    n = len(lat)
    good = int(res["ok"].sum())
    return {
        "attempted": n, "failed": n - good,
        "served_qps": good / res["seconds"],
        "query_p50_ms": float(np.percentile(lat, 50)) if n else np.nan,
        "query_p95_ms": float(np.percentile(lat, 95)) if n else np.nan,
        "query_p99_ms": float(np.percentile(lat, 99)) if n else np.nan,
        "gen_late_p99_ms": float(np.nanpercentile(late, 99)) if n else 0.0,
        "gen_late_max_ms": float(np.nanmax(late)) if n else 0.0,
    }


def run(ctx) -> Dict[str, Any]:
    server, state = start_server(ctx)
    why: List[str] = []
    try:
        addr = server.address
        t = time.perf_counter()
        n_check = int(ctx.cell.traffic.get("oracle_queries", 64))
        oracle_round(ctx, state, addr, 0, n_check, why)
        ctx.spans["oracle_before_s"] = time.perf_counter() - t
        snaps: Dict[str, Any] = {}
        trace = collect.TraceSlice(ctx.workdir + "/trace") \
            if ctx.trace else None

        def on_window(t0: float, t1: float) -> None:
            ctx.spans["setup_s"] = t0 - ctx.t_process_start
            if trace is not None:
                slice_s = min(3.0, (t1 - t0) / 2)
                trace.run_at(t0 + (t1 - t0 - slice_s) / 2, slice_s)
            time.sleep(max(0.0, t0 - time.time()))
            snaps["before"] = collect.snapshot()
            time.sleep(max(0.0, t1 - time.time()))
            snaps["after"] = collect.snapshot()

        res = offer(ctx, addr, state, ctx.seconds, ctx.seed,
                    on_window=on_window)
        if trace is not None:
            trace.join()
        oracle_round(ctx, state, addr, 1, n_check, why)
    finally:
        server.stop()
    s = summarise(res)
    # a drop is a failure of the system, not noise: below the knee the
    # schedule's count is fixed, so ``served_qps`` moves only with
    # drops, and a bound of some percent would let hundreds pass
    allowed = float(ctx.cell.traffic["max_failed_share"])
    if s["failed"] > allowed * s["attempted"]:
        why.append(f"{s['failed']} of {s['attempted']} requests failed "
                   f"(more than the mix's share of {allowed})")
    t0, t1 = res["epoch"], res["epoch"] + res["seconds"]
    flight = collect.flight_between(t0, t1)
    compiles = int(snaps["after"]["counters"]["jit_compiles"]
                   - snaps["before"]["counters"]["jit_compiles"])
    compiles += sum(1 for r in flight if r.get("aot") != "hit")
    bad_status = {int(c): int((res["status"] == c).sum())
                  for c in np.unique(res["status"]) if c != 200}
    starts = res["epoch"] + res["due"]
    ends = res["epoch"] + np.where(np.isnan(res["done"]), res["due"],
                                   res["done"])

    def gap_label(a: float, b: float) -> str:
        busy = bool(((starts < b) & (ends > a)).any())
        return "requests outstanding (host path)" if busy \
            else "no request outstanding"

    readers = {
        "before": snaps["before"], "after": snaps["after"],
        "flight": flight, "loadgen": s,
        "trace": trace.reduce(gap_label) if trace is not None else None,
        "trace_window": None if trace is None
        else (trace.started, trace.stopped),
        "work": state.work("http_open_loop",
                           int(ctx.cell.config["shape"]["rank"])),
    }
    return {
        "correct": not why, "why": why, "attempted": s["attempted"],
        "failed": s["failed"], "compiles_in_window": compiles,
        "end_to_end": {"served_qps": s["served_qps"],
                       "query_p50_ms": s["query_p50_ms"],
                       "query_p99_ms": s["query_p99_ms"],
                       "setup_s": ctx.spans["setup_s"]},
        "readers": readers,
        "notes": {"loadgen": s, "bad_status": bad_status,
                  "dispatches": len(flight)},
    }
