"""Traffic kind ``http_slates``: slate queries ``{"user", "items",
"num"}`` (``num`` is the slate's length) on ``http_sessions``'s seeded
open-loop schedule over keep-alive HTTP to ``POST /queries.json`` of a
``QueryServer`` deployed in this process through the normal path
(``deploy()`` -> ``build_deployment`` -> ``warm_up``, which compiles the
lane's ladder and prefills the resident sessions under the block-causal
mask, tails left uncommitted).

The load comes from ``harness/loadgen.py`` as it is, through
``http_sessions.offer``, the slates' lengths in exact shares
(:func:`build_schedule`). ``correct`` is decided outside the window by
``harness/slate_check.py``: what the TIMED lane computed (its audits)
for the check sessions' probe queries before and after the window and
for the latest of their rounds inside it (four of the traffic's
sessions, and the short one the traffic never asks: a wrong mask
shows in the logits there), teacher-forced against the
float32 reference, which is fed what THIS driver knows was sent (the
builder's history, the probes' events, the window's log cut into the
schedule's queries for that user). Besides: every check session holds
exactly what it was sent, in order, its committed length always whole
blocks; the lane's counters add up to the slates the schedule asked
for (every answered query's ``num`` positions unmasked, once; under
the static rule a query's passes are its positions and its rounds);
no compile in the window. After the window the server is stopped and
the lane closed: the reference's activations need the pool's room.

``python3 -m benchmark.drivers.http_slates --knee`` is the cell's rate
sweep, by ``find_knee.py``'s rule and the mix's ``knee`` block.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.drivers import http_open_loop as base  # noqa: E402
from benchmark.drivers import http_sessions as hs  # noqa: E402
from benchmark.harness import collect, data  # noqa: E402
from benchmark.models import slaterec  # noqa: E402

ROUND_MODULE = "jit_slate_round"


def start_server(ctx):
    """Seeded model persisted as an engine instance, then the server
    as ``pio deploy`` starts it. Returns (server, lane, histories)."""
    from predictionio_tpu.utils import metrics
    from predictionio_tpu.workflow.create_server import (
        QueryServer,
        ServerConfig,
    )

    config, mix = ctx.cell.config, ctx.cell.traffic
    if config["env"].get("PIO_SERVE_PRECISION") \
            != config["store"]["precision"]:
        raise ValueError("the configuration's store precision and its "
                         "PIO_SERVE_PRECISION differ")
    gen = config["generation"]
    for key in ("block_length", "denoising_steps", "remasking"):
        if mix[key] != gen[key]:
            raise ValueError(f"the mix states {key} {mix[key]!r}, the "
                             f"configuration {gen[key]!r}")
    data.memory_storage()
    t = time.perf_counter()
    models, params, hist = slaterec.build(config, ctx.seed)
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
           f"{json.dumps(lane.ladder_report().get('coverage', {}))}")
    return server, lane, hist


def build_schedule(mix, n_users: int, n_items: int, seed: int,
                   seconds: float, rate_qps: Optional[float] = None):
    """``sess_schedule.build_schedule``'s requests with the slates'
    lengths in EXACT shares, shuffled by the seed (the ramp's requests
    and the window's each): as the arrivals are conditioned on their
    count, so that every seed offers the same work. A slate of 32 is
    four times the work of one of 8, and the median of the window's
    latencies sits where the queries of 8 end and those of 16 begin:
    with the lengths drawn independently, a seed's share of short
    slates (0.5 +- 0.018 over 765 requests) moved ``query_p50_ms`` by
    14% over three seeds (PERF.md section 6, PR 33)."""
    sched = _plain_schedule(mix, n_users, n_items, seed, seconds,
                            rate_qps=rate_qps)
    rng = np.random.default_rng([int(seed), 8])
    values = np.asarray([int(v) for v in mix["num"]["values"]])
    shares = np.asarray(mix["num"]["shares"], np.float64)
    num = np.asarray(sched["num"]).copy()
    n_ramp = int(sched["n_ramp"])
    for lo, hi in ((0, n_ramp), (n_ramp, len(num))):
        # largest remainders: the counts add up to the requests
        exact = shares / shares.sum() * (hi - lo)
        counts = np.floor(exact).astype(int)
        short = (hi - lo) - int(counts.sum())
        counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
        num[lo:hi] = rng.permutation(np.repeat(values, counts))
    bodies = []
    for body, k in zip(sched["bodies"], num.tolist()):
        q = json.loads(body)
        q["num"] = int(k)
        bodies.append(json.dumps(q, separators=(",", ":")).encode())
    return dict(sched, num=num, bodies=bodies)


_plain_schedule = hs.sess_schedule.build_schedule


def offer(ctx, addr, seconds: float, seed: int, **kw) -> Dict[str, Any]:
    """``http_sessions.offer`` (generators, epoch, collection) over
    :func:`build_schedule`."""
    from unittest import mock

    with mock.patch.object(hs.sess_schedule, "build_schedule",
                           build_schedule):
        return hs.offer(ctx, addr, seconds, seed, **kw)


def slate_counters() -> Dict[str, Optional[float]]:
    """The slate lane's counters, None where the program has none (the
    parent commit has no such lane)."""
    from predictionio_tpu.utils import metrics as m

    def val(name, **labels):
        c = getattr(m, name, None)
        return None if c is None else float(c.value(**labels))

    return {
        "rounds": val("SLATE_ROUNDS"),
        "passes_query": val("SLATE_PASSES", kind="query"),
        "passes_device": val("SLATE_PASSES", kind="device"),
        "unmasked": val("SLATE_TOKENS_UNMASKED"),
        "carried": val("SLATE_CARRIED"),
        "experts_touched": val("SLATE_EXPERTS_TOUCHED"),
        "cache_rows_read": val("SLATE_CACHE_ROWS_READ"),
        "event_tokens": val("SESS_TOKENS", program="extend")}


def well_formed(status: int, body, num: int) -> Optional[str]:
    """A slate: exactly ``num`` distinct items, confidences in (0, 1]."""
    if status != 200:
        return f"status {status}"
    scores = (body or {}).get("itemScores")
    if not isinstance(scores, list) or len(scores) != num:
        return f"{len(scores or [])} itemScores for a slate of {num}"
    if len({s["item"] for s in scores}) != num:
        return "an item twice in one slate"
    if not all(0.0 < float(s["score"]) <= 1.0 + 1e-6 for s in scores):
        return "a confidence outside (0, 1]"
    return None


def to_rows(ctx, ids) -> np.ndarray:
    return slaterec.skip_mask(np.asarray(ids, np.int64), int(
        ctx.cell.config["generation"]["mask_token_id"]))


def to_ids(ctx, rows) -> np.ndarray:
    rows = np.asarray(rows, np.int64)
    return rows - (rows > int(
        ctx.cell.config["generation"]["mask_token_id"]))


def take_audits(lane, records) -> None:
    """The lane's kept audits of every check session, each once (two
    queries may find a session at one committed length: an audit is
    told by the tokens it started from too)."""
    for u, rec in records.items():
        for a in lane.audits(u):
            held = a["ids"] if a["kind"] == "events" \
                else np.concatenate([a["tail"], a["taken"]])
            key = (a["kind"], a["len0"], a.get("pos0"),
                   np.asarray(held, np.int64).tobytes())
            if key not in rec["keys"]:
                rec["keys"].add(key)
                rec["audits"].append(a)


def probe_round(ctx, addr, lane, users, tag: str, records, why) -> None:
    """One slate query to each check session, one at a time on an idle
    server; the events it was sent go to ``records``, then what the
    lane computed."""
    rng = np.random.default_rng([ctx.seed, 5, len(tag)])
    n_ids = int(ctx.cell.config["shape"]["n_items"])
    conn = http.client.HTTPConnection(*addr, timeout=300)
    took = []
    try:
        for u in users:
            items = rng.integers(0, n_ids, int(rng.integers(1, 6)))
            t = time.perf_counter()
            status, body = base._post(conn, {
                "user": f"u{u}", "items": [f"i{i}" for i in items],
                "num": 8})
            took.append(time.perf_counter() - t)
            bad = well_formed(status, body, 8)
            if bad:
                why.append(f"probe {tag} u{u}: {bad}")
                continue
            records[u]["sent"] += to_rows(ctx, items).tolist()
            seen = set(records[u]["events"].tolist()) \
                | set(records[u]["sent"])
            got = {int(s["item"][1:]) for s in body["itemScores"]}
            if set(to_rows(ctx, sorted(got)).tolist()) & seen:
                why.append(f"probe {tag} u{u}: a seen item in the slate")
    finally:
        conn.close()
    take_audits(lane, records)
    for u in users:
        for a in records[u]["audits"]:
            a.setdefault("tag", tag)
    ctx.spans[f"lone_query_p50_ms_{tag}"] = float(np.median(took)) * 1e3


def settle(ctx, lane, records, why, when: str) -> None:
    """Every check session holds the driver's events and then what was
    just sent, its committed length whole blocks."""
    B = int(ctx.cell.config["generation"]["block_length"])
    for u, rec in records.items():
        rec["events"] = np.concatenate(
            [rec["events"], np.asarray(rec["sent"], np.int32)])
        rec["sent"] = []
        if lane.session_events(u).tolist() != rec["events"].tolist():
            why.append(f"session u{u} {when}: not what it was sent, in "
                       "the order it was sent")
        if lane.cached_length(u) != len(rec["events"]) // B * B:
            why.append(f"session u{u} {when}: {lane.cached_length(u)} "
                       f"events committed of {len(rec['events'])} (the "
                       "whole blocks, and never the tail)")


def counters_add_up(ctx, res, before, after, why) -> Dict[str, Any]:
    """No query's rounds lost or doubled: over the generators' whole
    run the lane unmasked exactly the answered queries' ``num``
    positions; under the static rule at ``steps >= block`` a query's
    passes are its positions and its rounds; the rounds lie between no
    tail and the longest tail."""
    gen = ctx.cell.config["generation"]
    B = int(gen["block_length"])
    d = {k: after[k] - before[k] for k in after if after[k] is not None}
    num = np.asarray(res["schedule"]["num"])
    nums = num[sorted(res["answered"])]
    asked = int(nums.sum())
    out = dict(d, asked=asked)
    n_all = len(res["schedule"]["due"])
    if len(res["answered"]) != n_all:
        return out      # a failed request may still have been decoded
    if int(d["unmasked"]) != asked:
        why.append(f"the lane unmasked {int(d['unmasked'])} positions for "
                   f"slates of {asked} in all")
    lo = int(np.sum(-(-nums // B)))
    hi = int(np.sum(-(-(nums + B - 1) // B)))
    if not lo <= int(d["rounds"]) <= hi:
        why.append(f"{int(d['rounds'])} rounds for slates that need "
                   f"{lo}-{hi}")
    if gen["remasking"] == "low_confidence_static" \
            and int(gen["denoising_steps"]) >= B \
            and int(d["passes_query"]) != int(d["unmasked"] + d["rounds"]):
        why.append(f"{int(d['passes_query'])} passes where the static rule "
                   f"needs {int(d['unmasked'] + d['rounds'])}")
    return out


def module(scopes):
    """The round program's device time by scope, from
    ``seq_trace.by_module_and_scope``."""
    found = [m for k, m in (scopes or {}).items()
             if k.startswith(ROUND_MODULE)]
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


def run(ctx) -> Dict[str, Any]:
    from benchmark.harness import seq_trace, slate_check, trace_reduce

    server, lane, hist = start_server(ctx)
    why: List[str] = []
    short = slaterec.short_user(ctx.cell.config)
    users = hs.check_users(ctx, {u: h for u, h in hist.items()
                                 if u != short}) + [short]
    records = {u: {"user": u, "audits": [], "sent": [], "keys": set(),
                   "events": np.asarray(hist[u], np.int32)} for u in users}
    config = ctx.cell.config
    lane.watch(users)
    try:
        addr = server.address
        t = time.perf_counter()
        probe_round(ctx, addr, lane, users, "before", records, why)
        ctx.spans["probes_before_s"] = time.perf_counter() - t
        hs.say(ctx, f"probed in {ctx.spans['probes_before_s']:.1f}s; "
               f"lone query {ctx.spans['lone_query_p50_ms_before']:.1f} ms")
        settle(ctx, lane, records, why, "before the window")
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
            snaps["lane_before"] = hs.lane_counters()
            snaps["slate_before"] = slate_counters()
            if trace is not None:
                time.sleep(max(0.0, s0 - time.time()))
                snaps["slice_before"] = slate_counters()
                time.sleep(max(0.0, s0 + slice_s - time.time()))
                snaps["slice_after"] = slate_counters()
            time.sleep(max(0.0, t1 - time.time()))
            snaps["after"] = collect.snapshot()
            snaps["lane_after"] = hs.lane_counters()
            snaps["slate_after"] = slate_counters()

        run_before = slate_counters()
        res = offer(ctx, addr, ctx.seconds, ctx.seed, on_window=on_window)
        run_after = slate_counters()
        if trace is not None:
            trace.join()
        hs.say(ctx, "window done: " + json.dumps(base.summarise(res)))
        added = counters_add_up(ctx, res, run_before, run_after, why)
        for u in users:
            before = len(records[u]["events"])
            log = lane.session_events(u)[before:]
            hs.window_log(res, u, to_ids(ctx, log), why)
            records[u]["sent"] = log.tolist()
        take_audits(lane, records)
        for u in users:
            for a in records[u]["audits"]:
                a.setdefault("tag", "window")
        settle(ctx, lane, records, why, "after the window")
        probe_round(ctx, addr, lane, users, "after", records, why)
        settle(ctx, lane, records, why, "at the end")
        report = lane.session_report()
        theta = lane.theta
    finally:
        server.stop()
    t = time.perf_counter()
    lane.close()
    # the output table drawn again here: the reference scores against
    # the seed's table, and the lane's own must be that table
    drawn = slaterec.output_table(config, ctx.seed)
    rows = int(config["vocab_size"])
    if not bool((drawn[:rows] == theta["out_emb"][:rows]).all()):
        why.append("the lane's output table is not the seed's")
    theta = dict(theta, out_emb=drawn)
    hs.say(ctx, "server stopped; comparing with the reference")
    check = slate_check.compare(
        theta, slaterec.block_of(config), list(records.values()),
        config["check"], why, compute_dtype=str(config["compute_dtype"]))
    found = check["answers"]
    check["compared"] = {
        "audits": len(found),
        "rounds": sum(r["kind"] == "round" for r in found),
        "event_commits": sum(r["kind"] == "events" for r in found),
        "in_window": sum(r["tag"] == "window" for r in found),
        "in_window_slot_over_0": sum(r["tag"] == "window" and r["slot"] > 0
                                     for r in found)}
    if not check["compared"]["in_window"]:
        why.append("no round of a check session inside the window was "
                   "compared")
    del check["answers"]
    ctx.spans["reference_s"] = time.perf_counter() - t
    hs.say(ctx, f"compared in {ctx.spans['reference_s']:.1f}s: "
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
    la = snaps["lane_after"]
    rounds_in = sum(1 for r in flight if r.get("lane") == "sess")
    # the keys sess_cache_fill_share reads (sess_metrics.work)
    work = {"kind": "http_sessions", "dispatches": rounds_in,
            "cache_tokens": la["cache_tokens"],
            "cache_capacity": la["cache_capacity"]}
    sa, sb = snaps["slate_after"], snaps["slate_before"]
    slate = None
    if sa["rounds"] is not None:
        slate = {k: sa[k] - sb[k] for k in sa}
        slate.update(dispatches=rounds_in, block=slaterec.block_of(config),
                     queries=int(np.sum(res["ok"])))
    slate_slice = None
    if slate and "slice_after" in snaps:
        xa, xb = snaps["slice_after"], snaps["slice_before"]
        slate_slice = dict(
            slate, **{k: xa[k] - xb[k] for k in xa},
            dispatches=sum(1 for r in collect.flight_between(
                trace.started, trace.stopped) if r.get("lane") == "sess"))
    readers = {
        "before": snaps["before"], "after": snaps["after"],
        "flight": flight, "loadgen": s,
        "trace": trace.reduce(gap_label) if trace is not None else None,
        "trace_window": None if trace is None
        else (trace.started, trace.stopped),
        "trace_scopes": scopes, "work": work, "work_slice": None,
        "slate": slate, "slate_slice": slate_slice,
        "slate_module": module(scopes),
    }
    stage_table = None
    m = readers["slate_module"]
    if m and slate_slice and slate_slice["passes_device"]:
        stage_table = {
            "round_ms": 1e3 * m["seconds"] / m["count"], "count": m["count"],
            "passes_a_round": slate_slice["passes_device"] / m["count"],
            "scope_ms": {k: 1e3 * v / m["count"] for k, v in
                         sorted(m["scopes"].items(),
                                key=lambda kv: -kv[1])[:16]},
            "kernel_ms": {k: 1e3 * v / m["count"]
                          for k, v in m["kernels"].items()}}
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
                  "sessions": report, "counters": added,
                  "slate": {k: v for k, v in (slate or {}).items()
                            if k != "block"},
                  "stage_table": stage_table},
    }


def knee(argv=None) -> int:
    import argparse
    import shutil
    import tempfile

    ap = argparse.ArgumentParser()
    ap.add_argument("--knee", action="store_true")
    ap.add_argument("--workload", default="seqrec-sdar.slate-gen")
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
        server, lane, _ = start_server(ctx)
        try:
            rate, fails = float(kn["start_qps"]), 0
            for step in range(int(kn["max_steps"])):
                c0 = slate_counters()
                res = offer(ctx, server.address, args.seconds,
                            args.seed + step, rate_qps=rate,
                            tag=f"k{step}")
                c1 = slate_counters()
                row = find_knee.sustained(res, base.summarise(res), rate,
                                          float(kn["limit_ms"]))
                row["rows_a_round"] = (c1["rounds"] - c0["rounds"]) / max(
                    1.0, sum(1 for r in collect.flight_between(
                        res["epoch"] - 5.0, time.time())
                        if r.get("lane") == "sess"))
                rows.append(row)
                print(json.dumps(row), flush=True)
                fails = 0 if row["sustained"] else fails + 1
                if fails >= 2:
                    break
                rate = max(rate + 1, round(rate * float(kn["factor"])))
        finally:
            server.stop()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    good = [r["offered_qps"] for r in rows if r["sustained"]]
    found = max(good) if good else None
    summary = {"workload": args.workload, "device": device,
               "knee_qps": found,
               "rate_qps": None if found is None
               else int(round(0.8 * found)),
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
