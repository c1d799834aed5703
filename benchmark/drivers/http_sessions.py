"""Traffic kind ``http_sessions``: session queries ``{"user", "items",
"num"}`` on a seeded open-loop schedule over keep-alive HTTP to ``POST
/queries.json`` of a ``QueryServer`` deployed in this process through
the normal path (``deploy()`` -> ``build_deployment`` -> ``warm_up``,
which compiles the lane's ladder and prefills the resident sessions).

The load comes from ``harness/loadgen.py`` as it is, in child processes
that never import jax. ``correct`` is decided outside the window by
``harness/sess_check.py``: what the TIMED lane computed (its audits,
``SessionTopK.audits``) for the check sessions' probe queries before
and after the window and for the latest of their queries inside it
(those of the lane's last ``check.audits`` dispatches that held one),
against the float32 reference. The reference is fed what THIS driver
knows was sent, not what the lane says it did: a check session's
events are the builder's history, then the probes' events, then the
window's log cut into the schedule's queries for that user (every
query's events contiguous, none lost, doubled or made up:
``sess_schedule.parse_log``), and the output table is drawn here again
from ``--seed``. After the window the server is stopped and the lane
closed: the reference's activations need the pool's room.

``python3 -m benchmark.drivers.http_sessions --knee`` is the cell's
rate sweep, by ``find_knee.py``'s rule and the mix's ``knee`` block
(``find_knee.py`` itself deploys through ``http_open_loop`` and its
schedule, which has no session query).
"""

from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.drivers import http_open_loop as base  # noqa: E402
from benchmark.harness import collect, data, sess_schedule  # noqa: E402
from benchmark.models import sessionrec  # noqa: E402


def say(ctx, what: str) -> None:
    """Progress on stderr as it happens: a run that is cut shows how
    far it got."""
    print(f"benchmark: +{time.time() - ctx.t_process_start:.1f}s {what}",
          file=sys.stderr, flush=True)


def start_server(ctx):
    """Seeded model persisted as an engine instance, then the server
    as ``pio deploy`` starts it. Returns (server, lane, histories)."""
    from predictionio_tpu.utils import metrics
    from predictionio_tpu.workflow.create_server import (
        QueryServer,
        ServerConfig,
    )

    config = ctx.cell.config
    if config["env"].get("PIO_SERVE_PRECISION") \
            != config["store"]["precision"]:
        raise ValueError("the configuration's store precision and its "
                         "PIO_SERVE_PRECISION differ")
    data.memory_storage()
    t = time.perf_counter()
    models, params, hist = sessionrec.build(config, ctx.seed)
    data.persist_instance(config["engine_factory"], params, models)
    ctx.spans["build_persist_s"] = time.perf_counter() - t
    say(ctx, "model persisted; deploying")
    c0 = metrics.JIT_COMPILE_SECONDS.value()
    t = time.perf_counter()
    server = QueryServer(ServerConfig(ip="127.0.0.1", port=0))
    server.start(undeploy_stale=False)
    ctx.spans["deploy_s"] = time.perf_counter() - t
    ctx.spans["compile_s"] = metrics.JIT_COMPILE_SECONDS.value() - c0
    lane = server._deployment.models[0].device_server()
    resident = lane.session_report().get("residentSeconds")
    if resident:
        ctx.spans["sess_prefill_s"] = resident
    say(ctx, f"deployed: {json.dumps(lane.session_report())} ladder "
        f"{json.dumps(lane.ladder_report().get('coverage', {}))}")
    return server, lane, hist


def lane_counters() -> Dict[str, Optional[float]]:
    """The session lane's counters, None where the program has none."""
    from predictionio_tpu.utils import metrics as m

    def val(name, **labels):
        c = getattr(m, name, None)
        return None if c is None else float(c.value(**labels))

    return {
        "tokens": val("SESS_TOKENS", program="extend"),
        "positions": val("SESS_POSITIONS"),
        "selected": val("SESS_SELECTED", kind="selected"),
        "eligible": val("SESS_SELECTED", kind="eligible"),
        "local_picks": val("SESS_LOCAL_PICKS"),
        "experts_touched": val("SESS_EXPERTS_TOUCHED"),
        "evictions": val("SESS_EVICTIONS"),
        "cache_tokens": val("SESS_CACHE_TOKENS"),
        "cache_capacity": val("SESS_CACHE_CAPACITY")}


def well_formed(status: int, body, num: int) -> Optional[str]:
    if status != 200:
        return f"status {status}"
    scores = (body or {}).get("itemScores")
    if not isinstance(scores, list) or not 0 < len(scores) <= num:
        return f"{len(scores or [])} itemScores for num {num}"
    return None


def probe_round(ctx, addr, lane, users, tag: str, records, why) -> None:
    """One session query to each check session, one at a time on an
    idle server; what the lane computed for each, and the events it was
    sent, go to ``records``."""
    rng = np.random.default_rng([ctx.seed, 5, len(tag)])
    n_items = int(ctx.cell.config["shape"]["n_items"])
    conn = http.client.HTTPConnection(*addr, timeout=120)
    took = []
    try:
        for u in users:
            items = rng.integers(0, n_items, int(rng.integers(1, 4)))
            t = time.perf_counter()
            status, body = base._post(conn, {
                "user": f"u{u}", "items": [f"i{i}" for i in items],
                "num": 10})
            took.append(time.perf_counter() - t)
            bad = well_formed(status, body, 10)
            if bad:
                why.append(f"probe {tag} u{u}: {bad}")
                continue
            records[u]["sent"] += [int(i) for i in items]
            records[u]["answers"].append(dict(lane.audits(u)[-1], tag=tag))
    finally:
        conn.close()
    ctx.spans[f"lone_query_p50_ms_{tag}"] = float(np.median(took)) * 1e3


def order_check(addr, u: int, n_items: int, record, why) -> None:
    """Per-user order: a query's answer changes when, and only when,
    its own events are withheld. Before (no events), with two events,
    then again with none: the third answer is the second's exactly (the
    same prefix), the second is not the first's."""
    conn = http.client.HTTPConnection(*addr, timeout=120)
    try:
        a0 = base._post(conn, {"user": f"u{u}", "num": 10})
        a1 = base._post(conn, {"user": f"u{u}", "num": 10, "items": [
            f"i{n_items - 1}", f"i{n_items - 2}"]})
        a2 = base._post(conn, {"user": f"u{u}", "num": 10})
    finally:
        conn.close()
    if a1[0] == 200:
        record["sent"] += [n_items - 1, n_items - 2]
    for tag, a in (("before", a0), ("with", a1), ("after", a2)):
        bad = well_formed(a[0], a[1], 10)
        if bad:
            why.append(f"order check {tag} u{u}: {bad}")
            return
    if a2[1] != a1[1]:
        why.append(f"order check u{u}: the same prefix gave two answers")
    if a1[1] == a0[1]:
        why.append(f"order check u{u}: two new events changed nothing")


def offer(ctx, addr, seconds: float, seed: int,
          rate_qps: Optional[float] = None, tag: str = "w",
          on_window=None) -> Dict[str, Any]:
    """``http_open_loop.offer`` with the session schedule."""
    mix = ctx.cell.traffic
    shape = ctx.cell.config["shape"]
    sched = sess_schedule.build_schedule(
        mix, int(shape["n_users"]), int(shape["n_items"]), seed, seconds,
        rate_qps=rate_qps)
    gens = int(mix.get("generators", 2))
    timeout_s = float(mix.get("timeout_ms", 1000)) / 1e3
    paths = []
    for k in range(gens):
        p = os.path.join(ctx.workdir, f"sched-{tag}-{k}.npz")
        sess_schedule.save_share(p, sched, k, gens)
        paths.append((p, os.path.join(ctx.workdir, f"out-{tag}-{k}.npz")))
    pool = int(mix.get("connection_pool", 0))
    epoch = time.time() + float(mix.get("ramp_s", 0.0)) + 2.0 \
        + 0.003 * pool
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, base.LOADGEN, addr[0], str(addr[1]), sp, op,
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
    # the ramp's requests reached the sessions too
    out.update(epoch=epoch, seconds=seconds, timeout_s=timeout_s,
               schedule=sched, answered={
                   int(i) for i in cat["index"][cat["status"] == 200]})
    return out


def window_log(res, u: int, log, why) -> None:
    """``log``: what session ``u`` appended while the generators ran.
    It must cut into the schedule's queries for ``u``, each query's
    events contiguous and in its order, every answered query there once
    (one that was not answered may be missing)."""
    queries = sess_schedule.queries_of(res["schedule"], u)
    order = sess_schedule.parse_log(log, queries)
    if order is None:
        why.append(f"session u{u}: the {len(log)} events it appended in "
                   "the window are not its scheduled queries' events, "
                   "each query's together and in order")
        return
    lost = sorted(set(queries) & res["answered"] - set(order))
    if lost:
        why.append(f"session u{u}: {len(lost)} answered queries' events "
                   f"are not in its session (first: request {lost[0]})")


def check_users(ctx, hist) -> List[int]:
    """The shortest and the longest session, and seeded others."""
    n = int(ctx.cell.config["check"]["sessions"])
    by_len = sorted(hist, key=lambda u: len(hist[u]))
    picked = [by_len[0], by_len[-1]]
    rest = [u for u in by_len[1:-1]]
    rng = np.random.default_rng([ctx.seed, 6])
    picked += rng.choice(rest, size=max(0, min(n - 2, len(rest))),
                         replace=False).tolist()
    return [int(u) for u in picked[:n]]


def run(ctx) -> Dict[str, Any]:
    from benchmark.harness import seq_trace, sess_check, trace_reduce

    server, lane, hist = start_server(ctx)
    why: List[str] = []
    users = check_users(ctx, hist)
    records = {u: {"user": u, "answers": [], "sent": []} for u in users}
    config = ctx.cell.config
    n_items = int(config["shape"]["n_items"])
    lane.watch(users)
    try:
        addr = server.address
        t = time.perf_counter()
        probe_round(ctx, addr, lane, users, "before", records, why)
        order_check(addr, users[-1], n_items, records[users[-1]], why)
        ctx.spans["probes_before_s"] = time.perf_counter() - t
        say(ctx, f"probed in {ctx.spans['probes_before_s']:.1f}s; "
            f"lone query {ctx.spans['lone_query_p50_ms_before']:.1f} ms")
        # one at a time so far: the sessions hold exactly the builder's
        # histories and then what was sent, in that order
        for u in users:
            records[u]["events"] = np.concatenate(
                [hist[u], np.asarray(records[u]["sent"], np.int32)])
            if lane.session_events(u).tolist() \
                    != records[u]["events"].tolist():
                why.append(f"session u{u} before the window: not the "
                           "stored history and then the probes' events")
        snaps: Dict[str, Any] = {}
        trace = collect.TraceSlice(ctx.workdir + "/trace") \
            if ctx.trace else None

        def on_window(t0: float, t1: float) -> None:
            ctx.spans["setup_s"] = t0 - ctx.t_process_start
            slice_s = min(3.0, (t1 - t0) / 2)
            s0 = t0 + (t1 - t0 - slice_s) / 2
            if trace is not None:
                trace.run_at(s0, slice_s)
            time.sleep(max(0.0, t0 - time.time()))
            snaps["before"] = collect.snapshot()
            snaps["lane_before"] = lane_counters()
            if trace is not None:
                # the counters at the slice's own ends: its device time
                # is divided by the work of the same dispatches
                time.sleep(max(0.0, s0 - time.time()))
                snaps["slice_before"] = lane_counters()
                time.sleep(max(0.0, s0 + slice_s - time.time()))
                snaps["slice_after"] = lane_counters()
            time.sleep(max(0.0, t1 - time.time()))
            snaps["after"] = collect.snapshot()
            snaps["lane_after"] = lane_counters()

        res = offer(ctx, addr, ctx.seconds, ctx.seed, on_window=on_window)
        if trace is not None:
            trace.join()
        say(ctx, "window done: " + json.dumps(base.summarise(res)))
        for u in users:
            before = len(records[u]["events"])
            log = lane.session_events(u)[before:]
            window_log(res, u, log, why)
            records[u]["events"] = np.concatenate(
                [records[u]["events"], log])
            seen = {a["length"] for a in records[u]["answers"]}
            records[u]["answers"] += [
                dict(a, tag="window") for a in lane.audits(u)
                if a["length"] not in seen]
            records[u]["sent"] = []
        probe_round(ctx, addr, lane, users, "after", records, why)
        for u in users:
            records[u]["events"] = np.concatenate(
                [records[u]["events"],
                 np.asarray(records[u]["sent"], np.int32)])
            if lane.session_events(u).tolist() \
                    != records[u]["events"].tolist():
                why.append(f"session u{u} at the end: not what it was "
                           "sent, in the order it was sent")
        report = lane.session_report()
        theta = lane.theta
    finally:
        server.stop()
    t = time.perf_counter()
    lane.close()
    # the output table drawn again here: the reference scores against
    # the seed's table, and the lane's own must be that table
    drawn = sessionrec.output_table(config, ctx.seed)
    if not bool((drawn[:n_items] == theta["out_emb"][:n_items]).all()):
        why.append("the lane's output table is not the seed's")
    theta = dict(theta, out_emb=drawn)
    say(ctx, "server stopped; comparing with the reference")
    check = sess_check.compare(theta, sessionrec.block_of(config),
                               list(records.values()), config["check"], why)
    rows = check["answers"]
    check["compared"] = {
        "answers": len(rows),
        "in_window": sum(r["tag"] == "window" for r in rows),
        "in_window_slot_over_0": sum(r["tag"] == "window" and r["slot"] > 0
                                     for r in rows),
        "by_query_bucket": {str(b): sum(r["bucket"] == b for r in rows)
                            for b in sorted({r["bucket"] for r in rows})}}
    ctx.spans["reference_s"] = time.perf_counter() - t
    say(ctx, f"compared in {ctx.spans['reference_s']:.1f}s: "
        + json.dumps(check["worst"]) + " " + json.dumps(check["compared"]))
    s = base.summarise(res)
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

    scopes = None
    if trace is not None:
        path = trace_reduce.find_xplane(trace.directory)
        scopes = seq_trace.reduce_file(path) if path else None
    la, lb = snaps["lane_after"], snaps["lane_before"]
    work = {"kind": "http_sessions",
            "block": sessionrec.block_of(ctx.cell.config),
            "dispatches": sum(1 for r in flight if r.get("lane") == "sess"),
            "cache_tokens": la["cache_tokens"],
            "cache_capacity": la["cache_capacity"]}
    counted = ("tokens", "positions", "selected", "eligible", "local_picks",
               "experts_touched", "evictions")
    for k in counted:
        work[k] = None if la[k] is None else la[k] - lb[k]
    if work["tokens"] is None:
        work["dispatches"] = 0
    # the same over the traced slice alone (the rooflines divide the
    # slice's device time by it)
    work_slice = None
    if "slice_after" in snaps and work["dispatches"]:
        sa, sb = snaps["slice_after"], snaps["slice_before"]
        work_slice = dict(
            work, **{k: sa[k] - sb[k] for k in counted},
            dispatches=sum(1 for r in collect.flight_between(
                trace.started, trace.stopped) if r.get("lane") == "sess"))
    readers = {
        "before": snaps["before"], "after": snaps["after"],
        "flight": flight, "loadgen": s,
        "trace": trace.reduce(gap_label) if trace is not None else None,
        "trace_window": None if trace is None
        else (trace.started, trace.stopped),
        "trace_scopes": scopes, "work": work, "work_slice": work_slice,
    }
    stage_table = None
    if scopes:
        from benchmark.harness import sess_metrics

        m = sess_metrics.module({"trace_scopes": scopes, "work": work})
        if m:
            stage_table = {"dispatch_ms": 1e3 * m["seconds"] / m["count"],
                           "count": m["count"],
                           "scope_ms": {k: 1e3 * v / m["count"] for k, v in
                                        sorted(m["scopes"].items(),
                                               key=lambda kv: -kv[1])[:16]}}
    return {
        "correct": not why, "why": why, "attempted": s["attempted"],
        "failed": s["failed"], "compiles_in_window": compiles,
        "end_to_end": {"served_qps": s["served_qps"],
                       "query_p50_ms": s["query_p50_ms"],
                       "query_p99_ms": s["query_p99_ms"],
                       "setup_s": ctx.spans["setup_s"]},
        "readers": readers,
        "notes": {"loadgen": s, "bad_status": bad_status,
                  "dispatches": len(flight), "check": check,
                  "sessions": report,
                  "work": {k: v for k, v in work.items() if k != "block"},
                  "stage_table": stage_table},
    }


def knee(argv=None) -> int:
    import argparse
    import shutil
    import tempfile

    ap = argparse.ArgumentParser()
    ap.add_argument("--knee", action="store_true")
    ap.add_argument("--workload", default="seqrec-glm5.sess-extend")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0

    from benchmark import find_knee
    from benchmark import run as runner
    from benchmark.harness import cell as cells

    cell = cells.load_cell(args.workload, rehearse=args.rehearse)
    kn = cell.traffic["knee"]
    args.seconds = float(kn["step_seconds"])
    device = runner.prepare_process(cell, args.rehearse)
    workdir = tempfile.mkdtemp(prefix="pio-knee-")
    rows = []
    try:
        ctx = runner.Context(cell, args, workdir)
        server, lane, hist = start_server(ctx)
        try:
            rate, fails = float(kn["start_qps"]), 0
            for step in range(int(kn["max_steps"])):
                res = offer(ctx, server.address, args.seconds,
                            args.seed + step, rate_qps=rate, tag=f"k{step}")
                row = find_knee.sustained(res, base.summarise(res), rate,
                                          float(kn["limit_ms"]))
                row["sessions"] = lane.session_report()
                rows.append(row)
                print(json.dumps(row), flush=True)
                fails = 0 if row["sustained"] else fails + 1
                if fails >= 2:
                    break
                rate = round(rate * float(kn["factor"]))
        finally:
            server.stop()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    good = [r["offered_qps"] for r in rows if r["sustained"]]
    found = max(good) if good else None
    summary = {"workload": args.workload, "device": device,
               "knee_qps": found,
               "rate_qps": None if found is None
               else int(round(0.8 * found / 10.0)) * 10,
               "spans": ctx.spans, "steps": rows}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"knee-{args.workload}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: summary[k] for k in
                      ("workload", "knee_qps", "rate_qps", "spans")}))
    return 0


if __name__ == "__main__":
    sys.exit(knee())
