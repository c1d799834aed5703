"""Traffic kind ``http_sess_hybrid``: session queries ``{"user", "items",
"num"}`` (``http_sessions``' form, schedule and load generators) to a
DENSE model whose every layer holds TWO memories of a session
(``models/hybrec.py``: the ``falcon_h1`` block, a slot kind and a block
kind over the same layers in one pool), every session 2k-14k events
long and resident, deployed in this process through the normal path
(``deploy()`` -> ``build_deployment`` -> ``warm_up``, which compiles the
lane's ladder and prefills the resident sessions by the chunked SSD
form).

``correct`` is decided outside the window by ``harness/hyb_check.py``:
what the TIMED lane computed (its audits; with the lane idle the check
sessions' SLOTS before and behind a probe: every layer's state and
tail; at the end the key and value rows it holds for them) for the
check sessions' probe queries before and after the window and for the
latest of their queries inside it, against the float32 reference fed
what THIS driver knows was sent (the builder's history, the probes'
events, the window's log cut into the schedule's queries for that
user). The check sessions: the shortest, the longest, the median one,
and one of ``check.probe_session`` events that only the probes touch.
After the window the server is stopped and the lane closed: the
reference's activations need the pool's room.

Every compared reading is printed beside its limit on stderr
(``benchmark: check {...}``: the worst of each, the first over its
limit, the requests that failed) before the result line, whose keys
``benchmark/run.py`` fixes.

``python3 -m benchmark.drivers.http_sess_hybrid --knee`` is the cell's
rate sweep, by ``find_knee.py``'s rule and the mix's ``knee`` block.
The probes, the choice of check sessions and the reading of an answer
with its slot are ``drivers/http_sess_long.py``'s.
"""

from __future__ import annotations

import faulthandler

faulthandler.enable()   # a fault leaves every thread's stack on stderr

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.drivers import http_open_loop as base  # noqa: E402
from benchmark.drivers import http_sess_long as hl  # noqa: E402
from benchmark.drivers import http_sessions as hs  # noqa: E402
from benchmark.drivers.http_sess_mixed import memory_now  # noqa: E402
from benchmark.harness import collect, data  # noqa: E402
from benchmark.models import hybrec  # noqa: E402

EXTEND_MODULE = "jit_hyb_extend"
WORKLOAD = "seqrec-falconh1.sess-hybrid"
COUNTED = ("tokens", "positions", "evictions", "rows_read_attn",
           "state_bytes_read", "state_bytes_written")


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
    models, params, hist = hybrec.build(config, ctx.seed)
    data.persist_instance(config["engine_factory"], params, models)
    ctx.spans["build_persist_s"] = time.perf_counter() - t
    hs.say(ctx, "model persisted; deploying")
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
    hs.say(ctx, f"deployed: {json.dumps(lane.session_report())} ladder "
           f"{json.dumps(lane.ladder_report().get('coverage', {}))} "
           f"{memory_now()}")
    return server, lane, hist


def lane_counters() -> Dict[str, Optional[float]]:
    """The lane's counters, None where the program has none."""
    from predictionio_tpu.utils import metrics as m

    def val(name, **labels):
        c = getattr(m, name, None)
        return None if c is None else float(c.value(**labels))

    return {
        "tokens": val("SESS_TOKENS", program="extend"),
        "positions": val("SESS_POSITIONS"),
        "evictions": val("SESS_EVICTIONS"),
        "rows_read_attn": val("SESS_ROWS_READ", kind="attn"),
        "state_bytes_read": val("SESS_STATE_BYTES", dir="read"),
        "state_bytes_written": val("SESS_STATE_BYTES", dir="written"),
        "state_slots": val("SESS_STATE_SLOTS"),
        "state_capacity": val("SESS_STATE_CAPACITY"),
        "slot_bytes": val("SESS_STATE_SLOT_BYTES"),
        "kind_tokens_attn": val("SESS_KIND_TOKENS", kind="attn"),
        "cache_tokens": val("SESS_CACHE_TOKENS"),
        "cache_capacity": val("SESS_CACHE_CAPACITY")}


def module(scopes):
    """The extend program's device time by scope and by kernel, from
    ``seq_trace.by_module_and_scope``."""
    found = [m for k, m in (scopes or {}).items()
             if k.startswith(EXTEND_MODULE)]
    if not found:
        return None
    out = {"seconds": sum(m["seconds"] for m in found),
           "count": sum(m["count"] for m in found), "scopes": {},
           "kernels": {}}
    for m in found:
        for kind in ("scopes", "kernels"):
            for k, v in m[kind].items():
                out[kind][k] = out[kind].get(k, 0.0) + v
    return out if out["count"] else None


def delta(after, before, **more) -> Optional[Dict[str, Any]]:
    """The counters' growth between two readings, the gauges as they
    stood at the later one; None where the program has no such
    counter."""
    if any(after[k] is None for k in COUNTED):
        return None
    out = {k: after[k] - before[k] for k in COUNTED}
    out.update({k: after[k] for k in after if k not in COUNTED})
    out.update(more)
    return out


def run(ctx) -> Dict[str, Any]:
    from benchmark.harness import hyb_check, seq_trace, trace_reduce

    server, lane, hist = start_server(ctx)
    why: List[str] = []
    users = hl.check_users(ctx, hist)
    records = {u: {"user": u, "answers": [], "sent": []} for u in users}
    config = ctx.cell.config
    n_items = int(config["shape"]["n_items"])
    block = hybrec.block_of(config)
    lane.watch(users)
    try:
        addr = server.address
        t = time.perf_counter()
        hl.probe_round(ctx, addr, lane, users, "before", 1, records, why)
        hs.order_check(addr, users[1], n_items, records[users[1]], why)
        ctx.spans["probes_before_s"] = time.perf_counter() - t
        hs.say(ctx, f"probed in {ctx.spans['probes_before_s']:.1f}s; "
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
            snaps["memory_at_end"] = memory_now()

        res = hs.offer(ctx, addr, ctx.seconds, ctx.seed, on_window=on_window)
        if trace is not None:
            trace.join()
        hs.say(ctx, "window done: " + json.dumps(base.summarise(res))
               + " " + snaps["memory_at_end"])
        for u in users:
            before = len(records[u]["events"])
            log = lane.session_events(u)[before:]
            hs.window_log(res, u, log, why)
            records[u]["events"] = np.concatenate(
                [records[u]["events"], log])
            # the FIRST answer at each length: the query that appended
            # the events (one without events answers at the same length
            # and audits no event of its own); the generators are done
            # and the lane idle, so the slot read now is the state as
            # of the session's LATEST answer
            seen = {a["length"] for a in records[u]["answers"]}
            slot = lane.session_state(u)
            for a in lane.audits(u):
                if a["length"] not in seen:
                    seen.add(a["length"])
                    records[u]["answers"].append(dict(
                        a, tag="window", held_state=slot if slot
                        and slot["length"] == a["length"] else None))
            records[u]["sent"] = []
        hl.probe_round(ctx, addr, lane, users, "after",
                    int(config["check"].get("probes_after", 3)), records,
                    why)
        for u in users:
            records[u]["events"] = np.concatenate(
                [records[u]["events"],
                 np.asarray(records[u]["sent"], np.int32)])
            if lane.session_events(u).tolist() \
                    != records[u]["events"].tolist():
                why.append(f"session u{u} at the end: not what it was "
                           "sent, in the order it was sent")
            # the rows the lane holds for the session, once: a kind that
            # keeps every position never rewrites one, so every audited
            # answer attended over a prefix of these
            records[u]["cache"] = lane.session_rows(u)
        report = lane.session_report()
        theta = lane.theta
    finally:
        server.stop()
    t = time.perf_counter()
    lane.close()
    # the output table drawn again here: the reference scores against
    # the seed's table, and the lane's own must be that table
    drawn = hybrec.output_table(config, ctx.seed)
    if not bool((drawn[:n_items] == theta["out_emb"][:n_items]).all()):
        why.append("the lane's output table is not the seed's")
    theta = dict(theta, out_emb=drawn)
    hs.say(ctx, "server stopped; comparing with the reference")
    check = hyb_check.compare(theta, block, list(records.values()),
                              config["check"], why)
    rows = check["answers"]
    for what in ("states", "steps", "attentions"):
        if not check[f"{what}_compared"]:
            # (a reading nobody took stands at 0 beside its limit)
            why.append(f"no answer had its {what} compared")
    check["compared"] = {
        "answers": len(rows),
        "in_window": sum(r["tag"] == "window" for r in rows),
        "in_window_slot_over_0": sum(r["tag"] == "window" and r["slot"] > 0
                                     for r in rows),
        "states": check["states_compared"],
        "steps": check["steps_compared"],
        "attentions": check["attentions_compared"],
        "lengths": {f"u{u}": [len(hist[u]), len(records[u]["events"])]
                    for u in users}}
    ctx.spans["reference_s"] = time.perf_counter() - t
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
    # every compared reading beside its limit, the first that failed
    # and the requests that failed: one line, whatever the verdict
    hs.say(ctx, "check " + json.dumps({
        "correct": not why and not compiles,
        "readings": {k: [check["worst"][k], v]
                     for k, v in check["limits"].items()},
        "first_over": check["first_over"], "first_why": why[:1],
        "requests_failed": int(s["failed"]),
        "requests_attempted": int(s["attempted"]),
        "bad_status": bad_status, "compiles_in_window": compiles,
        "compared": check["compared"],
        "reference_s": round(ctx.spans["reference_s"], 1)}))
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

    def lane_records(rs):
        mine = [r for r in rs if r.get("lane") == "sess"]
        return {"dispatches": len(mine),
                "queries": sum(int(r.get("batch") or 0) for r in mine)}

    def work_of(after, before, records_):
        w = delta(after, before, block=block, **lane_records(records_))
        if w is not None and w["slot_bytes"]:
            # a live query reads its slot once: the slots' traffic
            # counts the queries that brought events
            w["live_queries"] = w["state_bytes_read"] / w["slot_bytes"]
        return w

    hyb = work_of(snaps["lane_after"], snaps["lane_before"], flight)
    hyb_slice = None
    if "slice_after" in snaps and hyb is not None:
        hyb_slice = work_of(
            snaps["slice_after"], snaps["slice_before"],
            collect.flight_between(trace.started, trace.stopped))
    # the keys the session lane's shared readers take
    # (sess_metrics.work: sess_tokens_per_dispatch, sess_cache_fill_share)
    work = None if hyb is None else dict(hyb, kind="http_sessions")
    mod = module(scopes)
    readers = {
        "before": snaps["before"], "after": snaps["after"],
        "flight": flight, "loadgen": s,
        "trace": trace.reduce(gap_label) if trace is not None else None,
        "trace_window": None if trace is None
        else (trace.started, trace.stopped),
        "trace_scopes": scopes, "work": work, "work_slice": None,
        "hyb": hyb, "hyb_slice": hyb_slice, "hyb_module": mod,
    }
    stage_table = None
    if mod:
        stage_table = {
            "dispatch_ms": 1e3 * mod["seconds"] / mod["count"],
            "count": mod["count"],
            "scope_ms": {k: 1e3 * v / mod["count"] for k, v in
                         sorted(mod["scopes"].items(),
                                key=lambda kv: -kv[1])[:20]},
            "kernel_ms": {k: 1e3 * v / mod["count"]
                          for k, v in mod["kernels"].items()}}
    return {
        "correct": not why, "why": why, "attempted": s["attempted"],
        "failed": s["failed"], "compiles_in_window": compiles,
        "end_to_end": {"served_qps": s["served_qps"],
                       "query_p50_ms": s["query_p50_ms"],
                       "query_p99_ms": s["query_p99_ms"],
                       "setup_s": ctx.spans["setup_s"]},
        "readers": readers,
        "notes": {"loadgen": s, "bad_status": bad_status,
                  "dispatches": len(flight), "check": {
                      k: v for k, v in check.items() if k != "answers"},
                  "check_rows": [[r["user"], r["tag"], r["length"]] + [
                      float(f"{r.get(k, -1):.3g}") for k in check["limits"]]
                      for r in rows],
                  "sessions": report, "memory_at_end": json.loads(
                      snaps["memory_at_end"]),
                  "work": None if hyb is None else {
                      k: v for k, v in hyb.items() if k != "block"},
                  "stage_table": stage_table},
    }


def knee(argv=None) -> int:
    import argparse
    import shutil
    import tempfile

    ap = argparse.ArgumentParser()
    ap.add_argument("--knee", action="store_true")
    ap.add_argument("--workload", default=WORKLOAD)
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
                res = hs.offer(ctx, server.address, args.seconds,
                               args.seed + step, rate_qps=rate,
                               tag=f"k{step}")
                row = find_knee.sustained(res, base.summarise(res), rate,
                                          float(kn["limit_ms"]))
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
               else int(0.8 * found // 10) * 10,
               "spans": ctx.spans, "memory": json.loads(memory_now()),
               "sessions": lane.session_report(), "steps": rows}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"knee-{args.workload}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: summary[k] for k in
                      ("workload", "knee_qps", "rate_qps", "spans",
                       "memory")}))
    return 0


if __name__ == "__main__":
    sys.exit(knee())
