"""Find a serving cell's knee, once, on the chip:

    python3 benchmark/find_knee.py --workload <name>

Deploys the cell as ``run.py`` does, then offers its traffic mix at a
geometric ladder of rates. Every parameter of the sweep (latency limit,
first rate, factor, seconds a step, most steps) is the mix file's
``knee`` block, so the sweep behind a cell's ``rate_qps`` is repeated
by naming the cell. A rate is sustained when at least 99% of the
requests sent are answered well formed inside ``limit_ms`` of their
due time, the rate achieved is at least 0.98 of the rate offered, and
the backlog does not grow (the last third's median latency is under
twice the first third's plus 1 ms). The knee is the highest sustained
rate; the sweep stops after two rates in a row fail. The mix file then
gets ``rate_qps`` = 0.8 x knee rounded to 50, by hand: no search ever
runs inside a cell. The table goes to stdout (one JSON line per step,
then a summary) and to ``chiprun_out/knee-<name>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def sustained(res, s, rate: float, limit_ms: float) -> dict:
    lat = np.where(res["ok"], res["done"] - res["due"],
                   res["timeout_s"]) * 1e3
    inside = float((lat <= limit_ms).mean())
    third = len(lat) // 3
    first, last = np.median(lat[:third]), np.median(lat[-third:])
    row = {"offered_qps": rate, "achieved_qps": s["served_qps"],
           "p50_ms": s["query_p50_ms"], "p95_ms": s["query_p95_ms"],
           "p99_ms": s["query_p99_ms"],
           "share_inside_limit": inside,
           "gen_late_p99_ms": s["gen_late_p99_ms"],
           "gen_late_max_ms": s["gen_late_max_ms"],
           "first_third_p50_ms": float(first),
           "last_third_p50_ms": float(last), "failed": s["failed"]}
    row["sustained"] = bool(
        inside >= 0.99 and s["served_qps"] >= 0.98 * rate
        and last <= 2.0 * first + 1.0)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0

    from benchmark import run as runner
    from benchmark.harness import cell as cells

    cell = cells.load_cell(args.workload, rehearse=args.rehearse)
    knee = cell.traffic["knee"]
    args.seconds = float(knee["step_seconds"])
    device = runner.prepare_process(cell, args.rehearse)
    from benchmark.drivers import http_open_loop as drv

    workdir = tempfile.mkdtemp(prefix="pio-knee-")
    rows = []
    try:
        ctx = runner.Context(cell, args, workdir)
        server, state = drv.start_server(ctx)
        try:
            why: list = []
            drv.oracle_round(ctx, state, server.address, 0, 16, why)
            rate, fails = float(knee["start_qps"]), 0
            for step in range(int(knee["max_steps"])):
                res = drv.offer(ctx, server.address, state, args.seconds,
                                args.seed + step, rate_qps=rate,
                                tag=f"k{step}")
                row = sustained(res, drv.summarise(res), rate,
                                float(knee["limit_ms"]))
                rows.append(row)
                print(json.dumps(row), flush=True)
                fails = 0 if row["sustained"] else fails + 1
                if fails >= 2:
                    break
                rate = round(rate * float(knee["factor"]))
        finally:
            server.stop()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    good = [r["offered_qps"] for r in rows if r["sustained"]]
    found = max(good) if good else None
    summary = {"workload": args.workload, "device": device,
               "knee_qps": found,
               "rate_qps": None if found is None
               else int(round(0.8 * found / 50.0)) * 50,
               "oracle_failures": why, "spans": ctx.spans, "steps": rows}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"knee-{args.workload}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: summary[k] for k in
                      ("workload", "knee_qps", "rate_qps",
                       "oracle_failures")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
