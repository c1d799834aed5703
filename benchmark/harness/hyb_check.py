"""What decides ``correct`` in the hybrid-session cell, outside the
measured window: what the TIMED lane computed (``SessionTopK.audits``:
every item's score of a query and, for its last event, the residual
stream after every layer, the two branches' outputs before their one
add, the key and value rows written; ``SessionTopK.session_state`` with
the lane idle: every layer's Mamba-2 state and convolution tail as of
the session's length; ``SessionTopK.session_rows`` at the end: the key
and value rows it holds) against the reference's full forward pass over
the session's whole history as of that query
(``oracle_falconh1.forward``: float32 under
``default_matmul_precision("highest")``, the recurrence one position at
a time, the published widths, the lane's own bf16 weights read as
float32, the output table drawn again from the seed, the events as the
DRIVER knows them sent).

The model is dense: no router flips on a near-tie, so a bf16 lane stays
within rounding of the float32 reference THROUGH the whole history (an
attention-only lane's hundredths, not the tenths a routed recurrent
lane reads: PERF.md section 6, PR 43), and the readings through the
history already hold every multiplier and every branch. The readings
are still of two kinds, each with a limit between what the sound lane
reads on the chip and what a control reads (PERF.md section 6, PR 48,
has both readings of each):

THROUGH the history (everything upstream in them):

- ``score_err`` = max over items of |lane - reference| over the
  standard deviation of the reference's scores.
- ``layer_err`` = the worst layer's ||lane - reference|| / ||reference||
  of the residual stream at the query's last event.
- ``att_err`` / ``ssm_err`` = the attention branch's and the Mamba-2
  branch's output before the add, worst layer, relative L2: each
  branch's multiplier, the convolution's bias, ``D x`` and the gated
  norm's order are factors of these.
- ``cache_err`` = the key and value rows the lane wrote for the event,
  worst layer, relative L2 (``key_multiplier`` is a factor of a key
  row).
- ``state_err`` = every layer's state as a STATE: the relative
  Frobenius error of each head's 128 x 256 block, worst head of the 6 x
  32 (where the slot was read: after every probe and at the window's
  end); ``tail_err`` = the convolution tails, relative L2, worst layer:
  a slot that is not as of its session's length misses both.

LOCAL (nothing upstream in them: the reference is given the lane's OWN
input to a layer and the lane's OWN memory, and computes what that one
layer makes of them):

- ``step_state_err`` = EVERY layer's update: where the slot was read
  with the lane idle before a query and behind it, the reference
  advances the lane's state before by the query's rows (the lane's own
  input rows to that layer, audited) one position at a time; each
  head's difference over the norm of the CHANGE the steps made, worst
  head of the 6 x 32. A state KEPT in bfloat16 misses by the rounding
  of the whole state over a small change. ``step_tail_err``: the tail
  behind the query the same way; ``ssm_out_err``: the Mamba-2 branch's
  output for the query's last event from the lane's state and tail
  before it (a tail that was not shifted shows here).
- ``attn_out_err`` = each layer's attention branch's OUTPUT for the
  query's last event against the reference's dense softmax over the key
  and value rows the LANE holds for the session (read back through the
  session's own block list once the lane is idle: a kind that keeps
  every position never rewrites a row): the paged kernel at 5 query
  heads a key/value head, its block table, the rotation, the output
  projection and its multiplier.
- ``head_err`` = every item's score against the reference's final norm,
  output table and ``lm_head_multiplier`` on the lane's OWN last
  stream, over the standard deviation of the reference's scores.

``python3 -m benchmark.harness.hyb_check [--control <name>]`` puts the
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

from benchmark.harness.lin_check import cache_on_device, jnp_take  # noqa: E402

WORKLOAD = "seqrec-falconh1.sess-hybrid"
# each between the most the sound lane read on the chip in seven runs on
# seven seeds, five untraced and two traced (my chip runs, PR 48, call
# 1: 0.0195, 0.0034, 0.0033, 0.0044, 0.0043, 0.00097, 0.0039 through the
# history; 0.0026, 0.0024, 0.0026, 0.0021, 0.0090 local; the seven lie
# within 10% of each other but ``state_err``, 0.00074-0.00097) and the
# least its control read there on a history of 6,000 events, at least
# twice the former: state_bf16 0.0486 / 0.0109 / 0.0265 / 0.0108 on
# scores, streams, the Mamba-2 branch and the rows (so a state kept in
# bfloat16 fails FIVE limits: those, ``state_err`` 0.481 and
# ``step_state_err`` 0.992), no_attn_out_mult 438, norm_before_gate
# 0.981, no_key_mult 1.07, slot_ahead 0.268 / 1.44 / 0.86, stale_tail
# 0.285, no_attn_out_mult 25.7, no_head_mult (PERF.md section 6)
LIMITS = {"score_err": 0.04, "layer_err": 0.008, "att_err": 0.01,
          "ssm_err": 0.012, "cache_err": 0.01, "state_err": 0.005,
          "tail_err": 0.01, "step_state_err": 0.01, "step_tail_err": 0.01,
          "ssm_out_err": 0.01, "attn_out_err": 0.008, "head_err": 0.03}
# a control, and the reading that has to catch it
CONTROLS = {"state_bf16": "step_state_err", "no_d_skip": "ssm_err",
            "no_conv_bias": "ssm_err", "no_key_mult": "cache_err",
            "no_ssm_out_mult": "ssm_err", "no_attn_out_mult": "att_err",
            "norm_before_gate": "ssm_err", "stale_tail": "ssm_out_err",
            "slot_ahead": "tail_err", "no_head_mult": "head_err"}
CONTROL_POSITIONS = 4    # the last positions of a control's history


def _rel(got, want) -> float:
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def _worst(got, want) -> float:
    """The worst layer's relative L2 of ``[layers, ...]`` arrays."""
    return max(_rel(g, w) for g, w in zip(got, want))


def _heads(got, want, over=None) -> np.ndarray:
    """Each head's ||got - want|| over ||over or want||: ``[layers,
    heads]`` from ``[layers, heads, P, N]`` states."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    base = want if over is None else np.asarray(over, np.float64)
    flat = lambda a: a.reshape(a.shape[:2] + (-1,))  # noqa: E731
    return np.linalg.norm(flat(got - want), axis=-1) \
        / (np.linalg.norm(flat(base), axis=-1) + 1e-30)


def local_errs(theta, block: Mapping[str, Any], answer: Mapping[str, Any],
               items: Sequence[int], cache=None) -> Dict[str, float]:
    """What the lane computed for a query (its new events ``items``,
    the last of them audited) from ITS OWN inputs and ITS OWN memory,
    layer by layer; worst layer each. Given the session's ``cache``
    (``SessionTopK.session_rows``, on the device: ``{"k", "v"}:
    [layers, S, kv_width]``): ``attn_out_err``. Where the slot was read
    idle before and behind the query (``slot_before`` /
    ``held_state``): ``ssm_out_err``, ``step_state_err`` and
    ``step_tail_err``. Always ``head_err``."""
    from benchmark.harness import oracle_falconh1 as oracle

    n, length = len(items), int(answer["length"])
    layers = np.asarray(answer["layers"], np.float32)
    rows = np.asarray(answer["rows"], np.float32)[:, :n]
    x_rows = np.concatenate([(np.asarray(jnp_take(
        theta["item_emb"], items), np.float32)
        * np.float32(block["emb_mult"]))[None], rows[:-1]])
    pos = [length - 1]
    before, behind = answer.get("slot_before"), answer.get("held_state")
    stepped = before is not None and behind is not None \
        and int(before["length"]) == length - n
    out: Dict[str, float] = {}
    if cache is not None:
        out["attn_out_err"] = 0.0
    if stepped:
        out.update(ssm_out_err=0.0, step_state_err=0.0, step_tail_err=0.0)
    for i in range(int(block["n_layers"])):
        x_last = x_rows[i][-1]
        if cache is not None:
            y = oracle.attn_local(theta, block, i, x_last[None], pos,
                                  cache["k"][i], cache["v"][i])
            out["attn_out_err"] = max(
                out["attn_out_err"], _rel(answer["att"][i], np.asarray(y)[0]))
        if stepped:
            y, S, tail = oracle.ssm_local(
                theta, block, i, x_rows[i], before["state"][i],
                before["tail"][i], length - n)
            out["ssm_out_err"] = max(out["ssm_out_err"],
                                     _rel(answer["ssm"][i], y[-1]))
            S0 = np.asarray(before["state"][i], np.float64)
            heads = _heads(np.asarray(behind["state"][i])[None], S[None],
                           over=(S - S0)[None])
            out["step_state_err"] = max(out["step_state_err"],
                                        float(heads.max()))
            out["step_tail_err"] = max(out["step_tail_err"],
                                       _rel(behind["tail"][i], tail))
    want = np.asarray(oracle.head_local(theta, block, layers[-1][None])[0],
                      np.float64)
    out["head_err"] = float(np.max(np.abs(np.asarray(
        answer["scores"], np.float64) - want)) / (want.std() + 1e-30))
    return out


def readings_of(answer: Mapping[str, Any], want: Mapping[str, Any], j: int,
                want_state, theta, block, items: Sequence[int], cache=None
                ) -> Dict[str, float]:
    """One answer's readings: ``want``: the reference's forward, ``j``
    the answer's place among its positions."""
    ws = np.asarray(want["scores"][j], np.float64)
    out = {
        "score_err": float(np.max(np.abs(np.asarray(
            answer["scores"], np.float64) - ws)) / (ws.std() + 1e-30)),
        "layer_err": _worst(answer["layers"], want["layers"][:, j]),
        "att_err": _worst(answer["att"], want["att"][:, j]),
        "ssm_err": _worst(answer["ssm"], want["ssm"][:, j]),
        "cache_err": _worst(
            np.concatenate([answer["k"], answer["v"]], axis=-1),
            np.concatenate([want["k"][:, j], want["v"][:, j]], axis=-1)),
        **local_errs(theta, block, answer, items, cache)}
    held = answer.get("held_state")
    if held is not None:
        out["state_err"] = float(_heads(held["state"],
                                        want_state["state"]).max())
        out["tail_err"] = _worst(held["tail"], want_state["tail"])
    return out


def over(readings: Mapping[str, float]) -> List[str]:
    return [f"{k} {readings[k]:.4g} > {v}" for k, v in LIMITS.items()
            if k in readings and not readings[k] <= v]


def compare(theta, block: Mapping[str, Any], records: Sequence[Mapping],
            check: Mapping[str, Any], why: List[str]) -> Dict[str, Any]:
    """``records``: a session each, ``{"user", "events" (the whole
    history at the end), "answers": [{"tag", "length", "scores",
    "layers", "att", "ssm", "mid", "rows", "new", "k", "v",
    "held_state" / "slot_before": None | {"state", "tail", "length"}}],
    "cache": None | {"k", "v", "length"}}`` (what
    ``SessionTopK.audits`` keeps of a dispatch; where the lane was idle
    behind / before it, ``session_state``; ``session_rows`` at the
    end). ONE reference pass a session gives every answer's position
    (the model is causal) and the states after the positions that have
    one. Appends to ``why``; returns the worst readings, every
    answer's, and ``first_over``: the first reading found over its
    limit."""
    from benchmark.harness import oracle_falconh1 as oracle

    worst = {k: 0.0 for k in LIMITS}
    rows = []
    first_over: Optional[str] = None
    for rec in records:
        by_pos = {a["length"] - 1: a for a in rec["answers"]
                  if a["length"] > 0}
        at = sorted(by_pos)
        if not at:
            continue
        out = oracle.forward(
            theta, np.asarray(rec["events"]), block, at=at,
            states_at=[p for p in at
                       if by_pos[p].get("held_state") is not None],
            q_block=int(check["q_block"]), s_block=int(check["s_block"]),
            pad=int(check["s_block"]))
        cache = cache_on_device(rec.get("cache"), int(check["s_block"]))
        for j, p in enumerate(at):
            a = by_pos[p]
            n = int(np.asarray(a["new"]).ravel()[0])
            held = a.get("held_state")
            if held is not None and int(held["length"]) != p + 1:
                why.append(f"session u{rec['user']} {a['tag']}: a slot of "
                           f"length {held['length']} behind an answer at "
                           f"{p + 1} events")
            r = readings_of(a, out, j, out["states"].get(p), theta, block,
                            rec["events"][p + 1 - n:p + 1], cache)
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
            "attentions_compared": sum("attn_out_err" in r for r in rows)}


def control(name: str, seed: int, rehearse: bool, length: int,
            theta=None) -> Dict[str, Any]:
    """The reference, degraded as ``name`` says, in the lane's place on
    one seeded history of ``length`` events: its last
    ``CONTROL_POSITIONS`` positions' scores, streams, branches, rows
    and states go through :func:`compare` as a lane's answers do."""
    from benchmark.harness import cell as cells
    from benchmark.harness import oracle_falconh1 as oracle
    from benchmark.models import hybrec, sessionrec
    from predictionio_tpu.ops import falconh1

    config = cells.load_cell(WORKLOAD, rehearse=rehearse).config
    block = hybrec.block_of(config)
    if theta is None:
        theta = falconh1.draw_serving_theta(
            int(config["vocab_size"]), hybrec.seqrec_params(config, seed))
    shape = dict(config["shape"], n_users=1, history_min=length,
                 history_max=length + 1)
    events = sessionrec.histories(shape, seed)[0]
    # (one event behind the audited ones: a slot that is AHEAD holds it)
    at = list(range(max(1, len(events) - 1 - CONTROL_POSITIONS),
                    len(events) - 1))
    check = config["check"]
    bad = oracle.forward(
        theta, events, block, at=at, states_at=[at[0] - 1] + at, rows=True,
        q_block=int(check["q_block"]), s_block=int(check["s_block"]),
        control=None if name == "sound" else name,
        # the stale tail: from the first audited row on (a query's rows
        # that read a tail the event before them did not shift)
        stale_at=at[0])

    def slot(p):
        return dict(bad["states"][p], length=p + 1)

    # every audited position a query of one event of its own
    record = {"user": 0, "events": events[:at[-1] + 1], "answers": [
        {"tag": name, "length": p + 1, "scores": bad["scores"][j],
         "new": [1], "rows": bad["layers"][:, j][:, None],
         "held_state": slot(p), "slot_before": slot(p - 1),
         **{k: bad[k][:, j] for k in ("layers", "att", "ssm", "mid", "k",
                                      "v")}}
        for j, p in enumerate(at)],
        "cache": {"k": bad["k_all"][:, :at[-1] + 1],
                  "v": bad["v_all"][:, :at[-1] + 1], "length": at[-1] + 1}}
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
    from benchmark.models import hybrec
    from predictionio_tpu.ops import falconh1

    config = cells.load_cell(WORKLOAD, rehearse=args.rehearse).config
    theta = falconh1.draw_serving_theta(
        int(config["vocab_size"]), hybrec.seqrec_params(config, args.seed))
    length = args.length or (300 if args.rehearse else 6000)
    ok = True
    for name in args.control or ("sound",) + tuple(CONTROLS):
        out = control(name, args.seed, args.rehearse, length, theta)
        ok = ok and out["caught"] == (name != "sound")
        print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
