"""Traffic kind ``seq_train_calls``: back-to-back whole
``SeqRecAlgorithm.train()`` calls of the sequentialrec template on
histories prepared (indexed, ordered, packed) in set-up, the first call
(compile or cache load) in set-up too.

One call = the configuration's optimizer steps with the input pipeline
running (each step's rows drawn on the host and handed over while the
device runs the step before), then the encode of EVERY user, then the
model's tables to the host. ``train_pairs_per_s`` is the next-item
targets a call's steps score (real positions; pads and the last
position of a history have none) over the MEDIAN call time of the
window, as ``train_calls`` reckons it: a call begun inside the window
runs to its end.

After the window, outside every timing, ``harness/seq_check.py``: the
system against the float32 oracle (``harness/oracle_seq.py``) at the
configuration's own widths: a seeded step batch's tensors; the TIMED
step program run once on it from a known state, its loss and, leaf by
leaf, its change of the parameters against the oracle's gradients and a
plain Adam; then the trained model served through ``DeviceTopK`` and a
sample of users' scores against ``oracle user vector . output table``.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import collect, data, seq_check
from benchmark.models import sequentialrec as seq_model


def prepare(ctx):
    from predictionio_tpu.core.context import workflow_context
    from predictionio_tpu.templates.sequentialrec.engine import (
        SeqPreparatorParams,
        SeqRecAlgorithm,
        SequencePreparator,
    )

    cfg = ctx.cell.config
    # first, so that a program without this backbone fails at once
    params = seq_model.seqrec_params(cfg, ctx.seed)
    t = time.perf_counter()
    st = data.draw_structure(cfg["shape"])
    td, events = seq_model.training_data(st, ctx.seed)
    ctx.spans["draw_data_s"] = time.perf_counter() - t
    cctx = workflow_context(mode="train")
    t = time.perf_counter()
    pd = SequencePreparator(SeqPreparatorParams(
        max_seq_len=params.max_seq_len, packed=True)).prepare(cctx, td)
    ctx.spans["prepare_s"] = time.perf_counter() - t
    return cctx, SeqRecAlgorithm(params), pd, st, events


def compare(ctx, algo, pd, model, events, operands=None) -> Dict[str, Any]:
    """Every reading of ``harness/seq_check.py`` for the trained
    ``model``; ``operands``: the oracle at that precision in the
    system's place (the control)."""
    from predictionio_tpu.templates.sequentialrec.engine import Query

    def predict(user: str):
        res = algo.predict(model, Query(user=user, num=10))
        return [(s.item, s.score) for s in res.item_scores]

    readings = seq_check.check_step(algo.params, pd.buckets,
                                    len(pd.item_map), model.theta,
                                    ctx.seed, operands)
    readings.update(seq_check.check_served(
        algo.params, pd, model, events, ctx.seed,
        int(ctx.cell.traffic["check_users"]), predict, operands))
    return readings


def _scope_notes(scopes, top: int = 14):
    """For PERF.md: each program's device self time by scope, largest
    first, and the kernels' part of it."""
    if not scopes:
        return None
    out = {}
    for name, m in scopes.items():
        rows = sorted(m["scopes"].items(), key=lambda kv: -kv[1])[:top]
        out[name] = {
            "seconds": round(m["seconds"], 4), "count": m["count"],
            "scopes": [[k or "(no scope)", round(v, 4)] for k, v in rows],
            "kernels": {k: round(v, 4) for k, v in m["kernels"].items()}}
    return out


def run(ctx) -> Dict[str, Any]:
    from predictionio_tpu.utils import metrics

    cctx, algo, pd, st, events = prepare(ctx)
    rows = pd.buckets
    c0 = metrics.JIT_COMPILE_SECONDS.value()
    t = time.perf_counter()
    model = algo.train(cctx, pd)      # compiles (or loads) both programs
    ctx.spans["warm_call_s"] = time.perf_counter() - t
    ctx.spans["compile_s"] = metrics.JIT_COMPILE_SECONDS.value() - c0
    why: List[str] = []

    calls: List[List[float]] = []
    losses: List[np.ndarray] = []
    trace = collect.TraceSlice(ctx.workdir + "/trace") if ctx.trace else None
    before = collect.snapshot()
    targets0 = metrics.SEQ_TRAIN_TARGETS.value()
    dropped0 = metrics.SEQ_DROPPED_TOKENS.value()
    t0 = time.time()
    ctx.spans["setup_s"] = t0 - ctx.t_process_start
    failed = 0
    while time.time() - t0 < ctx.seconds:
        if trace is not None and len(calls) == 1:
            trace.start()
        a = time.time()
        try:
            model = algo.train(cctx, pd)
            losses.append(np.asarray(algo.last_losses))
        except Exception as e:  # a failed call is a failed operation
            failed += 1
            why.append(f"train() raised {e!r}")
        calls.append([a, time.time()])
        if trace is not None and len(calls) == 2:
            trace.stop()
    after = collect.snapshot()
    done = len(calls) - failed
    compiles = int(after["counters"]["jit_compiles"]
                   - before["counters"]["jit_compiles"])
    targets = (metrics.SEQ_TRAIN_TARGETS.value() - targets0) / max(done, 1)
    dropped = metrics.SEQ_DROPPED_TOKENS.value() - dropped0
    if dropped:
        why.append(f"{dropped:.0f} (token, expert) pairs dropped")
    for i, ls in enumerate(losses):
        q = max(1, len(ls) // 4)
        if not np.isfinite(ls).all():
            why.append(f"call {i}: non-finite loss")
        elif not ls[-q:].mean() < ls[:q].mean():
            why.append(f"call {i}: loss did not fall over its steps "
                       f"({ls[:q].mean():.4g} -> {ls[-q:].mean():.4g})")
    expert = {s: metrics.SEQ_EXPERT_LOAD.value(stat=s)
              for s in ("max", "mean")}
    scopes = None
    if trace is not None:
        from benchmark.harness import seq_trace, trace_reduce

        path = trace_reduce.find_xplane(trace.directory)
        scopes = seq_trace.reduce_file(path) if path else None
    oracle = None
    if done:
        t = time.perf_counter()
        oracle = compare(ctx, algo, pd, model, events)
        seq_check.verdict(oracle, why)
        ctx.spans.update({k: v for k, v in oracle.items()
                          if isinstance(v, float)})
        ctx.spans["check_s"] = time.perf_counter() - t

    def gap_label(a: float, b: float) -> str:
        mid = (a + b) / 2
        for s, e in calls:
            if s <= mid <= e:
                return "inside a train call"
        return "between train calls"

    params = algo.params
    # a segment's length is its last token's position plus one
    seg_lengths = rows.pos.reshape(-1)[rows.last] + 1
    readers = {
        "before": before, "after": after, "flight": [],
        "trace": trace.reduce(gap_label) if trace is not None else None,
        "trace_scopes": scopes,
        "work": {
            "kind": "seq_train_calls", "calls": done, "traced_calls": 1,
            "steps": params.num_steps,
            "step_tokens": params.batch_size * rows.seq_len,
            "targets_per_call": targets, "call_seconds": calls,
            "encode_tokens": int(rows.seg.size),
            "segment_lengths": seg_lengths, "rows": len(rows),
            "row_len": rows.seq_len, "pad_share": rows.pad_share,
            "n_negatives": params.n_negatives, "expert_load": expert,
            "block": {"hidden": params.rank, "n_heads": params.n_heads,
                      "head_dim": params.head_dim,
                      "n_layers": params.n_layers,
                      "n_experts": params.n_experts,
                      "expert_width": params.expert_width,
                      "per_token": params.experts_per_token}},
    }
    return {
        "correct": not why, "why": why, "attempted": len(calls),
        "failed": failed, "compiles_in_window": compiles,
        "end_to_end": {
            "train_pairs_per_s": targets / float(np.median(
                [e - s for s, e in calls])) if done else 0.0,
            "setup_s": ctx.spans["setup_s"]},
        "readers": readers,
        "notes": {"device_scopes_s": _scope_notes(scopes), "oracle": oracle,
                  "calls": len(calls), "targets_per_call": targets,
                  "rows": len(rows), "pad_share": rows.pad_share,
                  "loss_first_last": [[float(ls[0]), float(ls[-1])]
                                      for ls in losses[:4]],
                  "expert_load": expert,
                  "call_s": [round(e - s, 4) for s, e in calls[:16]]},
    }
