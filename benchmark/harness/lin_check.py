"""What decides ``correct`` in the long-session cell, outside the
measured window: what the TIMED lane computed (``SessionTopK.audits``:
every item's score of a query and, for its last event, the residual
stream after every layer, the key and value rows written, each layer's
router picks, their gates, the router's input and the shared expert's
gate; ``SessionTopK.session_state`` with the lane idle: every Gated
DeltaNet layer's state and convolution tail as of the session's length)
against the reference's full forward pass over the session's whole
history as of that query (``oracle_qwen3next.forward``: float32 under
``default_matmul_precision("highest")``, the recurrence one position at
a time, the published widths, the lane's own bf16 weights read as
float32, the output table drawn again from the seed, the events as the
DRIVER knows them sent).

The router's cut is taken both ways round a tie: the reference routes
the query's last event to the experts the PROGRAM picked and says by
its own logits how far that cut is from one it could have taken itself.

What the chip taught (PERF.md section 6, PR 43): a recurrent layer
REMEMBERS what a bf16 lane and a float32 reference did differently at
EARLIER positions. A router's 10 of 512 flip on a near-tie at some
earlier position in some layer (the reference is given the lane's picks
at the audited position alone), the flipped position's stream moves by
tenths, and every DeltaNet layer behind it carries that in its state
for as long as its heads remember: the sound lane's scores, streams and
deeper states read ten times what an attention-only lane's do (which
sees one flipped row among thousands), and as much as a state kept in
bfloat16 does. So the readings are of two kinds, each with a limit
between what the sound lane reads on the chip and what a control reads
(PERF.md section 6 has both readings of each):

THROUGH the history (everything upstream in them; wide limits, which
the gross faults pass by tens):

- ``score_err`` = max over items of |lane - reference| over the
  standard deviation of the reference's scores.
- ``layer_err`` = the worst layer's ||lane - reference|| / ||reference||
  of the residual stream at the query's last event.
- ``state_err`` = every DeltaNet layer's state as a STATE: the relative
  Frobenius error of each value head's 128 x 128 block, worst head of
  the 6 x 32 (where the slot was read: after every probe and at the
  window's end); ``tail_err`` = the convolution tails, relative L2,
  worst layer.
- ``router_margin`` = the reference's k-th logit less the lowest picked
  one, or the highest one left out less the k-th, over the spread of
  the 512.

LOCAL (nothing upstream in them: the reference is given the lane's OWN
input to a layer and the lane's OWN memory, and computes what that one
layer makes of them; tight limits, which hold every layer's mechanism
at the published widths to its stated precision):

- ``state0_err`` = the FIRST DeltaNet layer's state alone, worst head:
  its input is the table's rows, so no earlier flip reaches it, and it
  has been carried through the whole history by the chunked prefill
  and every query since. The worst head is one that forgets fast: its
  state is the last few events', so what a query's own rows did wrong
  (a tail not carried into them) shows here. ``tail0_err``: its tail.
- ``state0_slow_err`` = the same layer's heads with the LONGEST
  memories (the quarter with the smallest ``exp(A_log)
  softplus(dt_bias)``), the worst of them: thousands of events summed,
  so the projections' bf16 roundings average out of it, while a state
  KEPT in bfloat16 rounds the sum itself at every event.
- ``step_state_err`` = EVERY DeltaNet layer's update: where the slot
  was read with the lane idle before a query and behind it, the
  reference advances the lane's state before by the query's rows (the
  lane's own input rows to that layer, audited) one position at a
  time; each head's difference over the norm of the CHANGE the steps
  made, worst head of the 6 x 32. A rounded state misses by the
  rounding of the whole state over a small change; a decay or a beta
  left out in any layer misses the change itself. ``step_tail_err``:
  the tail behind the query the same way; ``gdn_out_err``: the mixer's
  output for the query's last event (projections, convolution, norms,
  the state's read, the output's gate and projection).
- ``attn_out_err`` = each attention mixer's OUTPUT for the query's last
  event against the reference's dense softmax over the key and value
  rows the LANE holds for the session (read back through the session's
  own block list once the lane is idle: a kind that keeps every
  position never rewrites a row): the paged kernel at heads of 256,
  its block table, the QK norms, the partial rotation, the gate and
  the output projection. Every audited answer has it, those batched
  into slots behind the first included.
- ``cache_err`` = the key and value rows the lane wrote for the event
  in both attention layers against the reference's rows for the lane's
  OWN input to the layer, worst layer, relative L2: the precision the
  rows are computed and held in, and the rotation (a head rotated
  whole is caught here).
- ``attn_gate_err`` = the factor the lane multiplied its attention's
  output by against the sigmoid of the gate the reference projects
  from the lane's OWN input, worst layer, relative L2 (a lane without
  the gate multiplies by 1).
- ``moe_err`` = what each expert layer ADDED to the stream behind its
  mixer against the reference's held experts with the lane's picks
  plus its gated shared expert on that stream, worst layer.
- ``shared_gate_err`` = the shared expert's gate the lane used against
  a float64 sigmoid of ``w_sg . h2`` from the lane's OWN router input.
- ``gate_err`` = the gates the lane used against a float64 softmax over
  ALL 512 logits computed here from the lane's OWN router input,
  renormalised over its picks: the router product alone.
- ``head_err`` = every item's score against the reference's final norm
  and output table on the lane's OWN last stream, the largest
  difference over the standard deviation of the reference's scores.

``python3 -m benchmark.harness.lin_check [--control <name>]`` puts the
reference itself, degraded, in the lane's place on one seeded history
and sends what it computed for the last ``CONTROL_POSITIONS`` positions
through :func:`compare` exactly as a lane's answers go: ``CONTROLS``
names the reading that has to catch each; ``sound`` is the reference
undegraded and reads zeros.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Mapping, Optional, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORKLOAD = "seqrec-qwen3next.sess-long"
# each between what the sound lane reads on the chip and what the control
# named for it reads there on a history of 9,000 events, at least twice
# the former (PERF.md section 6, PR 43, has every reading). The first
# twelve: the most of sixteen runs (my chip runs, PR 43, calls 2-4a:
# 0.538, 0.133, 0.269, 0.132, 0.260 through the history; 0.0053, 0.0044,
# 0.0024, 0.0090, 0.00070, 3.8e-6, 4.5e-6 local) against the least of
# no_decay, beta_one and no_shared_gate through the history (3.98,
# 1.02, 1.73, 0.83, 2.36), tail_dropped 0.888, state_bf16 0.0148-0.0152,
# rope_all 0.818, no_attn_gate 1.01, no_shared_gate 5.81. The last six
# (the one-layer readings): the most of the second session's six runs
# (calls 5 and 6: 0.0020, 0.0048, 0.0044, 0.0024, 0.0048, 0.0097; the 64
# answers of a run and the six runs lie within 30% of each other) against
# attn_block_lost 0.0163 (no_attn_gate 1.02), deep_no_decay 0.203,
# state_bf16 0.0523 (deep_no_decay 0.999), no control for the tail,
# no_routed 0.688, final_norm_plain 4.69
LIMITS = {"score_err": 1.5, "layer_err": 0.4, "state_err": 0.6,
          "tail_err": 0.4, "router_margin": 0.6, "state0_err": 0.02,
          "state0_slow_err": 0.009, "tail0_err": 0.02, "cache_err": 0.02,
          "attn_gate_err": 0.03, "shared_gate_err": 1e-3, "gate_err": 1e-4,
          "attn_out_err": 0.008, "gdn_out_err": 0.02,
          "step_state_err": 0.015, "step_tail_err": 0.02, "moe_err": 0.02,
          "head_err": 0.04}
# a control, and the reading that has to catch it
CONTROLS = {"state_bf16": "step_state_err", "no_decay": "state0_err",
            "beta_one": "state0_err", "tail_dropped": "state0_err",
            "no_attn_gate": "attn_gate_err",
            "no_shared_gate": "shared_gate_err", "rope_all": "cache_err",
            "attn_block_lost": "attn_out_err",
            "deep_no_decay": "step_state_err", "no_routed": "moe_err",
            "final_norm_plain": "head_err"}
CONTROL_POSITIONS = 4    # the last positions of a control's history


def gate_err(theta, answer: Mapping[str, Any]) -> float:
    """The router product alone: the gates the lane used against a
    softmax over all the router's logits from ITS OWN router input,
    renormalised over ITS picks, in float64 here."""
    worst = 0.0
    for i, (h2, picks, gates) in enumerate(zip(
            answer["h2"], answer["picks"], answer["gates"])):
        w = np.asarray(theta[f"l{i}_router"], dtype=np.float64)
        z = np.asarray(h2, np.float64) @ w
        p = np.exp(z - z.max())[np.asarray(picks)]
        want = p / p.sum()
        worst = max(worst, float(np.max(
            np.abs(np.asarray(gates, np.float64) - want) / want)))
    return worst


def _rel(got, want) -> float:
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def local_errs(theta, block: Mapping[str, Any], answer: Mapping[str, Any],
               items: Sequence[int], cache=None) -> Dict[str, float]:
    """What the lane computed for a query (its new events ``items``,
    the last of them audited) from ITS OWN inputs and ITS OWN memory,
    layer by layer; worst layer each, relative. Every layer:
    ``shared_gate_err`` and ``moe_err`` (what the expert layer added to
    the stream behind the mixer, with the lane's picks). An attention
    layer: ``cache_err`` and ``attn_gate_err`` (the rows written and
    the factor on the output, from the layer's input) and, given the
    session's ``cache`` (``SessionTopK.session_rows``, on the device:
    ``{"k", "v"}: [attention layers, S, kv_width]``), ``attn_out_err``:
    the mixer's output over the rows the LANE holds. A DeltaNet layer,
    where the slot was read idle before and behind the query
    (``slot_before`` / ``held_state``): ``gdn_out_err`` (the mixer's
    output), ``step_state_err`` (the state behind the query against the
    reference's own steps from the lane's state before it, a head over
    the CHANGE the steps made, worst head) and ``step_tail_err``.
    ``head_err``: the scores against the final norm and the table on
    the lane's last stream, over their standard deviation."""
    from benchmark.harness import oracle_qwen3next as oracle

    n, length = len(items), int(answer["length"])
    layers = np.asarray(answer["layers"], np.float32)
    rows = np.asarray(answer["rows"], np.float32)[:, :n]
    mid = np.asarray(answer["mid"], np.float32)
    x_rows = np.concatenate([np.asarray(jnp_take(
        theta["item_emb"], items), np.float32)[None], rows[:-1]])
    pos = [length - 1]
    before, behind = answer.get("slot_before"), answer.get("held_state")
    stepped = before is not None and behind is not None \
        and int(before["length"]) == length - n
    out = {"cache_err": 0.0, "attn_gate_err": 0.0, "shared_gate_err": 0.0,
           "moe_err": 0.0}
    if cache is not None:
        out["attn_out_err"] = 0.0
    if stepped:
        out.update(gdn_out_err=0.0, step_state_err=0.0, step_tail_err=0.0)
    j = [0, 0]      # the layer's place among the DeltaNet, attention ones
    for i in range(int(block["n_layers"])):
        z = float(np.asarray(answer["h2"][i], np.float64)
                  @ np.asarray(theta[f"l{i}_sg"], np.float64)[:, 0])
        want = 1.0 / (1.0 + np.exp(-z))
        out["shared_gate_err"] = max(
            out["shared_gate_err"],
            abs(float(answer["sg"][i]) - want) / want)
        out["moe_err"] = max(out["moe_err"], _rel(
            layers[i] - mid[i], oracle.moe_local(
                theta, block, i, mid[i][None], answer["picks"][i][None])[0]))
        full = oracle.is_full(block, i)
        x_last, a = x_rows[i][-1], j[full]
        j[full] += 1
        if full:
            made, og = oracle.cache_rows(theta, block, i, x_last[None], pos)
            out["cache_err"] = max(out["cache_err"], _rel(np.concatenate(
                [answer["k"][a], answer["v"][a]]), np.asarray(made)[0]))
            out["attn_gate_err"] = max(
                out["attn_gate_err"], _rel(answer["og"][a], np.asarray(og)[0]))
            if cache is not None:
                y = oracle.attn_local(theta, block, i, x_last[None], pos,
                                      cache["k"][a], cache["v"][a])
                out["attn_out_err"] = max(
                    out["attn_out_err"],
                    _rel(mid[i] - x_last, np.asarray(y)[0]))
        elif stepped:
            y, S, tail = oracle.gdn_local(
                theta, block, i, x_rows[i], before["state"][a],
                before["tail"][a], length - n)
            out["gdn_out_err"] = max(out["gdn_out_err"],
                                     _rel(mid[i] - x_last, y[-1]))
            S0 = np.asarray(before["state"][a], np.float64)
            got = np.asarray(behind["state"][a], np.float64)
            heads = np.linalg.norm((got - S).reshape(len(S), -1), axis=-1) \
                / (np.linalg.norm((S - S0).reshape(len(S), -1), axis=-1)
                   + 1e-30)
            out["step_state_err"] = max(out["step_state_err"],
                                        float(heads.max()))
            out["step_tail_err"] = max(out["step_tail_err"],
                                       _rel(behind["tail"][a], tail))
    want = np.asarray(oracle.head_local(theta, block, layers[-1][None])[0],
                      np.float64)
    out["head_err"] = float(np.max(np.abs(np.asarray(
        answer["scores"], np.float64) - want)) / (want.std() + 1e-30))
    return out


def jnp_take(table, ids):
    """Rows ``ids`` of a (device) table, fetched."""
    import jax.numpy as jnp

    return jnp.take(table, jnp.asarray(np.asarray(ids, np.int32)), axis=0)


def cache_on_device(cache, s_block: int):
    """A session's fetched cache rows (``session_rows``) on the device,
    padded with zero rows to whole ``s_block``s (one compiled program
    for sessions of several lengths); None stays None."""
    import jax.numpy as jnp

    if cache is None:
        return None
    out = {}
    for name in ("k", "v"):
        a = jnp.asarray(cache[name])
        out[name] = jnp.pad(a, ((0, 0), (0, -a.shape[1] % s_block), (0, 0)))
    return out


def slow_heads(theta) -> np.ndarray:
    """The quarter of the first DeltaNet layer's value heads that
    forget slowest: the smallest ``exp(A_log) softplus(dt_bias)`` (the
    decay's rate at a zero input)."""
    rate = np.exp(np.asarray(theta["l0_a_log"], np.float64)) * np.log1p(
        np.exp(np.asarray(theta["l0_dt_bias"], np.float64)))
    return np.argsort(rate)[:max(1, len(rate) // 4)]


def state_errs(got: Mapping[str, Any], want: Mapping[str, Any],
               slow) -> Dict[str, float]:
    """``state_err``: each value head's block of every layer's state,
    relative Frobenius, the worst; ``tail_err``: the tails, relative
    L2, worst layer; ``state0_err`` / ``tail0_err``: the first DeltaNet
    layer's alone, and ``state0_slow_err`` its heads ``slow``."""
    gs, ws = (np.asarray(a["state"], np.float64) for a in (got, want))
    heads = np.linalg.norm((gs - ws).reshape(gs.shape[:2] + (-1,)), axis=-1) \
        / (np.linalg.norm(ws.reshape(ws.shape[:2] + (-1,)), axis=-1) + 1e-30)
    gt, wt = (np.asarray(a["tail"], np.float64).reshape(len(gs), -1)
              for a in (got, want))
    tails = np.linalg.norm(gt - wt, axis=-1) \
        / (np.linalg.norm(wt, axis=-1) + 1e-30)
    return {"state_err": float(heads.max()), "tail_err": float(tails.max()),
            "state0_err": float(heads[0].max()),
            "state0_slow_err": float(heads[0][slow].max()),
            "tail0_err": float(tails[0])}


def readings_of(answer: Mapping[str, Any], want_scores, want_layers,
                cuts: Mapping[str, float], want_state, theta, block,
                items: Sequence[int], cache=None) -> Dict[str, float]:
    want = np.asarray(want_scores, dtype=np.float64)
    got = np.asarray(answer["scores"], dtype=np.float64)
    wl = np.asarray(want_layers, dtype=np.float64)
    gl = np.asarray(answer["layers"], dtype=np.float64)
    out = {
        "score_err": float(np.max(np.abs(got - want)) / (want.std() + 1e-30)),
        "layer_err": float(np.max(np.linalg.norm(gl - wl, axis=-1)
                                  / (np.linalg.norm(wl, axis=-1) + 1e-30))),
        "router_margin": float(max(cuts["router_low"], cuts["router_out"])),
        "gate_err": gate_err(theta, answer),
        **local_errs(theta, block, answer, items, cache)}
    if answer.get("held_state") is not None:
        out.update(state_errs(answer["held_state"], want_state,
                              slow_heads(theta)))
    return out


def over(readings: Mapping[str, float]) -> List[str]:
    return [f"{k} {readings[k]:.4g} > {v}" for k, v in LIMITS.items()
            if k in readings and not readings[k] <= v]


def compare(theta, block: Mapping[str, Any], records: Sequence[Mapping],
            check: Mapping[str, Any], why: List[str]) -> Dict[str, Any]:
    """``records``: a session each, ``{"user", "events" (the whole
    history at the end), "answers": [{"tag", "length", "scores",
    "layers", "mid", "rows", "new", "k", "v", "og", "picks", "gates",
    "h2", "sg", "held_state" / "slot_before": None | {"state", "tail",
    "length"}}], "cache": None | {"k", "v", "length"}}`` (what
    ``SessionTopK.audits`` keeps of a dispatch; where the lane was idle
    behind / before it, ``session_state``; ``session_rows`` at the
    end). ONE reference pass a session gives every
    answer's position (the model is causal) and the states after the
    positions that have one. Appends to ``why``; returns the worst
    readings, every answer's, and ``first_over``: the first reading
    found over its limit."""
    from benchmark.harness import oracle_qwen3next as oracle

    worst = {k: 0.0 for k in LIMITS}
    rows = []
    exercised = set()
    first_over: Optional[str] = None
    for rec in records:
        by_pos = {a["length"] - 1: a for a in rec["answers"]
                  if a["length"] > 0}
        at = sorted(by_pos)
        if not at:
            continue
        out = oracle.forward(
            theta, np.asarray(rec["events"]), block, at=at,
            given={p: by_pos[p]["picks"] for p in at},
            states_at=[p for p in at
                       if by_pos[p].get("held_state") is not None],
            q_block=int(check["q_block"]), s_block=int(check["s_block"]),
            pad=int(check["s_block"]))
        cache = cache_on_device(rec.get("cache"), int(check["s_block"]))
        for j, p in enumerate(at):
            a = by_pos[p]
            n = int(np.asarray(a["new"]).ravel()[0])
            r = readings_of(a, out["scores"][j], out["layers"][:, j],
                            out["cuts"][p], out["states"].get(p), theta,
                            block, rec["events"][p + 1 - n:p + 1], cache)
            exercised |= {int(e) for e in np.asarray(a["picks"]).ravel()}
            rows.append(dict(
                r, user=int(rec["user"]), tag=a["tag"],
                length=int(a["length"]),
                **{k: a[k] for k in ("slot", "queries", "bucket")
                   if k in a}))
            for k, v in r.items():
                worst[k] = max(worst[k], v) if np.isfinite(v) else v
            found = [f"session u{rec['user']} {a['tag']} at {a['length']} "
                     f"events: {x}" for x in over(r)]
            if found and first_over is None:
                first_over = found[0]
            why += found
    return {"worst": worst, "answers": rows, "limits": dict(LIMITS),
            "first_over": first_over,
            "states_compared": sum("state_err" in r for r in rows),
            "steps_compared": sum("step_state_err" in r for r in rows),
            "attentions_compared": sum("attn_out_err" in r for r in rows),
            "experts_exercised": len(exercised)}


def control(name: str, seed: int, rehearse: bool, length: int,
            theta=None) -> Dict[str, Any]:
    """The reference, degraded as ``name`` says, in the lane's place on
    one seeded history of ``length`` events: its last
    ``CONTROL_POSITIONS`` positions' scores, layer states, rows, picks,
    gates and DeltaNet states go through :func:`compare` as a lane's
    answers do."""
    from benchmark.harness import cell as cells
    from benchmark.harness import oracle_qwen3next as oracle
    from benchmark.models import linrec, sessionrec
    from predictionio_tpu.ops import qwen3next

    config = cells.load_cell(WORKLOAD, rehearse=rehearse).config
    block = linrec.block_of(config)
    if theta is None:
        theta = qwen3next.draw_serving_theta(
            int(config["vocab_size"]), linrec.seqrec_params(config, seed))
    shape = dict(config["shape"], n_users=1, history_min=length,
                 history_max=length + 1)
    events = sessionrec.histories(shape, seed)[0]
    at = list(range(max(1, len(events) - CONTROL_POSITIONS), len(events)))
    check = config["check"]
    bad = oracle.forward(
        theta, events, block, at=at, states_at=[at[0] - 1] + at, rows=True,
        q_block=int(check["q_block"]), s_block=int(check["s_block"]),
        control=None if name == "sound" else name,
        # the dropped tail: at the border before the audited rows (a
        # query's rows that do not see the session's tail)
        tail_chunk=at[0])

    def slot(p):
        return dict(bad["states"][p], length=p + 1)

    # every audited position a query of one event of its own
    record = {"user": 0, "events": events, "answers": [
        {"tag": name, "length": p + 1, "scores": bad["scores"][j],
         "new": [1], "rows": bad["layers"][:, j][:, None],
         "held_state": slot(p), "slot_before": slot(p - 1),
         **{k: bad[k][:, j] for k in ("layers", "mid", "k", "v", "og",
                                      "picks", "gates", "h2", "sg")}}
        for j, p in enumerate(at)],
        "cache": {"k": bad["k_all"], "v": bad["v_all"],
                  "length": len(events)}}
    why: List[str] = []
    out = compare(theta, block, [record], check, why)
    by = CONTROLS.get(name)
    return {"control": name, "seed": seed, "length": int(len(events)),
            "readings": out["worst"], "limits": dict(LIMITS), "by": by,
            "caught": bool(why) if by is None
            else not out["worst"][by] <= LIMITS[by]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=tuple(CONTROLS) + ("sound",),
                    action="append")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--length", type=int, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark.harness import cell as cells
    from benchmark.models import linrec
    from predictionio_tpu.ops import qwen3next

    config = cells.load_cell(WORKLOAD, rehearse=args.rehearse).config
    theta = qwen3next.draw_serving_theta(
        int(config["vocab_size"]), linrec.seqrec_params(config, args.seed))
    length = args.length or (300 if args.rehearse else 9000)
    ok = True
    for name in args.control or ("sound",) + tuple(CONTROLS):
        out = control(name, args.seed, args.rehearse, length, theta)
        ok = ok and out["caught"] == (name != "sound")
        print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
