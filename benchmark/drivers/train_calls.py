"""Traffic kind ``train_calls``: back-to-back whole ``train()`` calls of
the configuration's algorithm on data prepared in set-up.

The measured quantity is the work of one whole call (table upload,
iterations, factors back to the host) over the MEDIAN call time of the
window: a new call starts while the window is open and the last one
runs to its end, so the rate does not jump with the count of calls that
happen to fit, and one stalled call (the chip machine showed a call of
6.8 s among 3.78 s ones, once in 13 runs) does not move it.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import collect, data, oracle


def _prepare(ctx):
    from predictionio_tpu.core.context import workflow_context
    from predictionio_tpu.ops.als import ALSParams
    from predictionio_tpu.templates.recommendation.engine import (
        ALSAlgorithm,
        IndexedTrainingData,
        PreparatorParams,
        RatingsPreparator,
    )

    cfg = ctx.cell.config
    shape, tr = cfg["shape"], cfg["train"]
    t = time.perf_counter()
    st = data.draw_structure(shape)
    rows = st.rows()
    vals = data.rating_values(st.n_events, ctx.seed)
    user_map, item_map = data.entity_maps(st.n_users, st.n_items)
    ctx.spans["draw_data_s"] = time.perf_counter() - t
    cctx = workflow_context(mode="train")
    td = IndexedTrainingData(user_map, item_map, rows, st.cols, vals)
    t = time.perf_counter()
    pd = RatingsPreparator(PreparatorParams(
        bucketed=bool(tr["bucketed"]))).prepare(cctx, td)
    ctx.spans["prepare_s"] = time.perf_counter() - t
    algo = ALSAlgorithm(ALSParams(
        rank=int(shape["rank"]), num_iterations=int(tr["numIterations"]),
        lambda_=float(tr["lambda"]), alpha=float(tr["alpha"]),
        implicit_prefs=bool(tr["implicitPrefs"]), seed=ctx.seed,
        precision=str(tr["precision"])))
    return cctx, algo, pd, (rows, st.cols, vals)


def _check(ctx, model, events, why: List[str]) -> None:
    """Factors finite; the last half-step re-solved in float64 on a
    seeded sample of items; at rehearsal size the whole trajectory
    against the numpy trainer."""
    X, Y = model.user_factors, model.item_factors
    if not (np.isfinite(X).all() and np.isfinite(Y).all()):
        why.append("non-finite factors")
        return
    rows, cols, vals = events
    tr = ctx.cell.config["train"]
    rng = np.random.default_rng([ctx.seed, 4])
    n_items = Y.shape[0]
    sample = np.sort(rng.choice(n_items, size=min(256, n_items),
                                replace=False))
    want = oracle.half_step_items(sample, rows, cols, vals, X,
                                  float(tr["lambda"]), float(tr["alpha"]))
    scale = np.abs(want).max()
    err = np.abs(Y[sample] - want).max() / scale
    ctx.spans["half_step_rel_err"] = float(err)
    if err > oracle.HALF_STEP_RTOL:
        why.append(f"last half-step differs from float64 by {err:.3g} "
                   f"relative (limit {oracle.HALF_STEP_RTOL})")
    if ctx.rehearse:
        from predictionio_tpu.ops.als import init_factors

        X0, Y0 = init_factors(X.shape[0], Y.shape[0], X.shape[1], ctx.seed)
        Xn, Yn = oracle.train_als_numpy(
            rows, cols, vals, X.shape[0], Y.shape[0], np.asarray(X0),
            np.asarray(Y0), int(tr["numIterations"]),
            float(tr["lambda"]), float(tr["alpha"]))
        err = max(np.abs(X - Xn).max() / np.abs(Xn).max(),
                  np.abs(Y - Yn).max() / np.abs(Yn).max())
        ctx.spans["trajectory_rel_err"] = float(err)
        if err > 1e-2:
            why.append(f"trajectory differs from the numpy trainer by "
                       f"{err:.3g} relative")


def run(ctx) -> Dict[str, Any]:
    from predictionio_tpu.utils import metrics

    cctx, algo, pd, events = _prepare(ctx)
    iters = int(ctx.cell.config["train"]["numIterations"])
    pairs = int(pd.user_side.nnz)
    c0 = metrics.JIT_COMPILE_SECONDS.value()
    t = time.perf_counter()
    model = algo.train(cctx, pd)      # compiles (or loads) every program
    ctx.spans["warm_call_s"] = time.perf_counter() - t
    ctx.spans["compile_s"] = metrics.JIT_COMPILE_SECONDS.value() - c0
    why: List[str] = []

    calls: List[List[float]] = []
    trace = collect.TraceSlice(ctx.workdir + "/trace") if ctx.trace else None
    before = collect.snapshot()
    t0 = time.time()
    ctx.spans["setup_s"] = t0 - ctx.t_process_start
    failed = 0
    while time.time() - t0 < ctx.seconds:
        if trace is not None and len(calls) == 1:
            trace.start()
        a = time.time()
        try:
            model = algo.train(cctx, pd)
        except Exception as e:  # a failed call is a failed operation
            failed += 1
            why.append(f"train() raised {e!r}")
        calls.append([a, time.time()])
        if trace is not None and len(calls) == 2:
            trace.stop()
    after = collect.snapshot()
    compiles = int(after["counters"]["jit_compiles"]
                   - before["counters"]["jit_compiles"])
    _check(ctx, model, events, why)

    def gap_label(a: float, b: float) -> str:
        mid = (a + b) / 2
        for s, e in calls:
            if s <= mid <= e:
                if mid - s < (e - s) * 0.5:
                    return "inside a train call, first half (tables up)"
                return "inside a train call, second half (factors down)"
        return "between train calls"

    done = len(calls) - failed
    readers = {
        "before": before, "after": after, "flight": [],
        "trace": trace.reduce(gap_label) if trace is not None else None,
        "work": {"kind": "train_calls", "calls": done, "iterations": iters,
                 "pairs": pairs, "n_users": pd.user_side.n_rows,
                 "n_items": pd.item_side.n_rows,
                 "rank": int(ctx.cell.config["shape"]["rank"]),
                 "traced_calls": 1, "call_seconds": calls},
    }
    return {
        "correct": not why, "why": why, "attempted": len(calls),
        "failed": failed, "compiles_in_window": compiles,
        "end_to_end": {
            "train_pairs_per_s": pairs * iters / float(np.median(
                [e - s for s, e in calls])) if done else 0.0,
            "setup_s": ctx.spans["setup_s"]},
        "readers": readers,
        "notes": {"calls": len(calls), "pairs": pairs,
                  "call_s": [round(e - s, 4) for s, e in calls[:16]]},
    }
