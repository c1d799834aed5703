"""Traffic kind ``batch_job``: a JSONL file of user queries through
``BatchPredictor`` (the ``pio batchpredict`` engine), passes repeated
into fresh output directories while the window is open. The rate is the
queries of whole passes over first start to last end.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import collect, data, oracle


def _write_queries(path: str, users: np.ndarray, num: int) -> None:
    with open(path, "w") as f:
        for u in users.tolist():
            f.write(json.dumps({"user": f"u{u}", "num": num},
                               separators=(",", ":")) + "\n")


def _check_results(state: data.ServingState, out_dir: str, n_expected: int,
                   sample: np.ndarray, num: int, why: List[str]) -> int:
    """Every record well formed; ``sample`` positions against the
    oracle. Returns the count of malformed records."""
    from predictionio_tpu.batch.predict import read_results

    records = read_results(out_dir)
    if len(records) != n_expected:
        why.append(f"{out_dir}: {len(records)} records for "
                   f"{n_expected} queries")
    bad = sum(1 for r in records
              if not oracle.well_formed(r.get("prediction"), num))
    st = state.structure
    for pos in sample.tolist():
        rec = records[pos]
        u = int(rec["query"]["user"][1:])
        want = oracle.scores_single(
            state.user_factors[u], state.item_factors, st.user_items(u))
        msg = oracle.check_answer(
            rec["prediction"].get("itemScores", []), want, num,
            oracle.SCORE_RTOL)
        if msg:
            why.append(f"batch record {pos} (u{u}): {msg}")
    return bad


def run(ctx) -> Dict[str, Any]:
    from predictionio_tpu.batch.predict import (
        BatchPredictConfig,
        BatchPredictor,
    )
    from predictionio_tpu.utils import metrics

    mix = ctx.cell.traffic
    data.memory_storage()
    state = data.build_serving_instance(ctx.cell.config, ctx.seed, ctx.spans)
    st = state.structure
    num = int(mix["num"])
    per_pass = min(int(mix["users_per_pass"]), st.n_users)
    rng = np.random.default_rng([ctx.seed, 5])
    users = rng.permutation(st.n_users)
    warm_users, pass_users = users[:int(mix["warm_users"])], users[:per_pass]
    warm_in = os.path.join(ctx.workdir, "warm.jsonl")
    pass_in = os.path.join(ctx.workdir, "users.jsonl")
    _write_queries(warm_in, warm_users, num)
    _write_queries(pass_in, pass_users, num)
    cfg = BatchPredictConfig(
        output_dir=os.path.join(ctx.workdir, "warm"), input_path=warm_in,
        chunk_size=int(mix["chunk_size"]))
    predictor = BatchPredictor(cfg)
    c0 = metrics.JIT_COMPILE_SECONDS.value()
    t = time.perf_counter()
    predictor.load()
    ctx.spans["deploy_s"] = time.perf_counter() - t
    ctx.spans["compile_s"] = metrics.JIT_COMPILE_SECONDS.value() - c0
    why: List[str] = []
    t = time.perf_counter()
    predictor.run()                      # first executions, file paths
    n_check = int(mix.get("oracle_queries", 64))
    crng = np.random.default_rng([ctx.seed, 6])
    _check_results(state, cfg.output_dir, len(warm_users),
                   crng.choice(len(warm_users), size=min(
                       n_check, len(warm_users)), replace=False), num, why)
    ctx.spans["warm_pass_and_oracle_s"] = time.perf_counter() - t

    trace = collect.TraceSlice(ctx.workdir + "/trace") if ctx.trace else None
    passes: List[List[float]] = []
    before = collect.snapshot()
    t0 = time.time()
    ctx.spans["setup_s"] = t0 - ctx.t_process_start
    if trace is not None:
        slice_s = min(3.0, ctx.seconds / 2)
        trace.run_at(t0 + (ctx.seconds - slice_s) / 2, slice_s)
    failed_passes = 0
    while time.time() - t0 < ctx.seconds:
        predictor.config = dataclasses.replace(
            cfg, input_path=pass_in,
            output_dir=os.path.join(ctx.workdir, f"pass-{len(passes)}"))
        a = time.time()
        try:
            predictor.run()
        except Exception as e:
            failed_passes += 1
            why.append(f"pass raised {e!r}")
        passes.append([a, time.time()])
    t1 = time.time()
    after = collect.snapshot()
    if trace is not None:
        trace.join()
    scored = int(after["counters"]["batchpredict_scored"]
                 - before["counters"]["batchpredict_scored"])
    flight = collect.flight_between(t0, t1)
    compiles = int(after["counters"]["jit_compiles"]
                   - before["counters"]["jit_compiles"])
    compiles += sum(1 for r in flight if r.get("aot") != "hit")
    malformed = _check_results(
        state, predictor.config.output_dir, per_pass,
        crng.choice(per_pass, size=min(n_check, per_pass), replace=False),
        num, why) if failed_passes < len(passes) else per_pass
    attempted = per_pass * len(passes)
    failed = attempted - scored + malformed
    if failed:
        why.append(f"{failed} of {attempted} queries were not scored "
                   "well formed")

    def gap_label(a: float, b: float) -> str:
        mid = (a + b) / 2
        inside = any(s <= mid <= e for s, e in passes)
        return "inside a pass (host: parse, render, shard write)" \
            if inside else "between passes (read queries, manifest)"

    readers = {
        "before": before, "after": after, "flight": flight,
        "trace": trace.reduce(gap_label) if trace is not None else None,
        "trace_window": None if trace is None
        else (trace.started, trace.stopped),
        "work": state.work("batch_job",
                           int(ctx.cell.config["shape"]["rank"])),
    }
    return {
        "correct": not why, "why": why, "attempted": attempted,
        "failed": failed, "compiles_in_window": compiles,
        "end_to_end": {"served_qps": (scored - malformed) / (t1 - t0),
                       "setup_s": ctx.spans["setup_s"]},
        "readers": readers,
        "notes": {"passes": len(passes), "scored": scored,
                  "pass_s": [round(e - s, 3) for s, e in passes[:16]],
                  "dispatches": len(flight)},
    }
