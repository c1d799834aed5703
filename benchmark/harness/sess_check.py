"""What decides ``correct`` in the session cell, outside the measured
window: what the TIMED lane computed (``SessionTopK.audits``: every
item's score of a query and, for its last event, the residual stream
after every layer, the positions each layer attended over, each expert
layer's router picks, their gates and the router's input) against the
reference's full forward pass over the session's whole history as of
that query (``oracle_glm5.forward``: float32 under
``default_matmul_precision("highest")``, the published widths, the
lane's own bf16 weights read as float32, the output table drawn again
from the seed, the events as the DRIVER knows them sent).

Both cuts are taken both ways round a tie, as the two-stage oracle
takes its candidate cut: the reference attends the query's last event
over the positions the PROGRAM selected and routes it to the experts
the program picked, and says by its own index / router scores how far
those cuts are from cuts it could have taken itself.

Six readings an answer, each with a limit between what the sound lane
reads on the chip and what a control reads (PERF.md section 6 has both
readings of each):

- ``score_err`` = max over items of |lane - reference| over the
  standard deviation of the reference's scores. The lane multiplies
  bf16 operands (8 bits of mantissa: about 0.4% a product) through 6
  layers and holds bf16 caches; earlier positions take their own cuts
  in that precision, so a near-tie there lands as a small difference
  here.
- ``layer_err`` = the worst layer's ||lane - reference|| / ||reference||
  of the residual stream at the query's last event: one routed expert
  left out, or added twice, moves a token's stream by a tenth or more
  where the final scores (a mean over 2,048 attended keys later) move
  by less than bf16 does.
- ``cache_err`` = the two cache rows the lane wrote for that event in
  every layer against the reference's rows for the lane's OWN input to
  the layer (``cache_err``), worst layer, relative L2: the precision
  the rows are computed and held in, nothing upstream in it. With both
  cuts given, a float8 cache moves the final scores no more than bf16
  does (its rounding is zero-mean and attention averages 2,048 rows),
  so this reading is what holds the cache to bf16.
- ``index_regret`` = what the reference's own best ``index_topk`` index
  scores sum to, less what the program's selection sums to, a kept key,
  over the eligible scores' standard deviation, worst layer: 0 for any
  top set whichever way round its ties go, and a MEAN over the cut: the
  worst single key (``index_low`` / ``index_out``, kept in the notes)
  is decided by the handful of cached rows that differ because an
  earlier position's router pick flipped in bf16, reads 1.0-1.7 in
  sound runs and cannot tell a wrong selection from a sound one.
- ``router_margin`` = the reference's k-th ``score + bias`` less the
  lowest picked one, or the highest one left out less the k-th, over
  the spread of the 256.
- ``gate_err`` = the gates the lane used against float64 gates computed
  here from the lane's OWN router input (``gate_err``): the router
  product alone, nothing upstream in it. The configuration states a
  float32 router; bf16 operands read a thousand times the float32
  product's error and nothing else in the comparison can see them (the
  lane's bf16 activations move the router's input more than rounding it
  does).

``python3 -m benchmark.harness.sess_check [--control <name>]`` puts the
reference itself, degraded, in the lane's place on one seeded history,
and sends what it computed for the last ``CONTROL_POSITIONS`` positions
through :func:`compare` exactly as a lane's answers go (its own cuts
GIVEN to the sound reference): ``float8_cache`` (the cached latents
and index keys rounded through float8_e4m3fn), ``bf16_router``, and
the planted faults ``dropped_expert`` (the held expert those positions
pick most adds nothing), ``index_skips_last_block``,
``index_swaps_tenth`` (a tenth of the kept keys give way to keys left
out, whatever their scores), ``stale_row`` and
``router_ignores_bias``. ``CONTROLS`` names the reading that has to
catch each; ``sound`` is the reference undegraded and reads zeros.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Mapping, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LIMITS = {"score_err": 0.15, "layer_err": 0.06, "cache_err": 0.006,
          "index_regret": 0.07, "router_margin": 0.1, "gate_err": 3e-5}
# a control, and the reading that has to catch it
CONTROLS = {"float8_cache": "cache_err", "bf16_router": "gate_err",
            "dropped_expert": "layer_err",
            "index_skips_last_block": "index_regret",
            "index_swaps_tenth": "index_regret", "stale_row": "score_err",
            "router_ignores_bias": "router_margin"}
CONTROL_POSITIONS = 8    # the last positions of a control's history


def gate_err(theta, block: Mapping[str, Any], answer: Mapping[str, Any]
             ) -> float:
    """The router product alone: the gates the lane used against
    ``scale * s / sum s`` over ITS picks from ITS OWN router input, in
    float64 here (``s = sigmoid(h2 W_r)``). Nothing upstream enters,
    so what is left is the precision of the router product itself."""
    worst = 0.0
    for j, (h2, picks, gates) in enumerate(zip(
            answer["h2"], answer["picks"], answer["gates"])):
        w = np.asarray(theta[f"l{int(block['n_dense']) + j}_router"],
                       dtype=np.float64)
        s = 1.0 / (1.0 + np.exp(-(np.asarray(h2, np.float64) @ w)))
        s = s[np.asarray(picks)]
        want = float(block["route_scale"]) * s / s.sum()
        worst = max(worst, float(np.max(
            np.abs(np.asarray(gates, np.float64) - want) / want)))
    return worst


def cache_err(theta, block: Mapping[str, Any], answer: Mapping[str, Any],
              item: int) -> float:
    """The cache rows alone: what the lane WROTE for the query's last
    event (item ``item``) in every layer against the reference's rows
    for the lane's OWN input to that layer (the table's row, then its
    own residual stream), worst layer, relative L2. Nothing upstream
    enters: what is left is the precision the rows are computed and
    held in."""
    from benchmark.harness import oracle_glm5

    wide = int(block["kv_rank"]) + int(block["d_rope"])
    layers = np.asarray(answer["layers"], np.float32)
    x_in = np.concatenate([np.asarray(
        theta["item_emb"][int(item)], np.float32)[None], layers[:-1]])
    pos = [int(answer["length"]) - 1]
    worst = 0.0
    for i in range(int(block["n_layers"])):
        want = np.asarray(oracle_glm5.cache_rows(
            theta, block, i, x_in[i][None], pos), np.float64)[0]
        got = np.concatenate([
            np.asarray(answer["lat"][i], np.float64)[:wide],
            np.asarray(answer["ik"][i], np.float64)])
        worst = max(worst, float(np.linalg.norm(got - want)
                                 / (np.linalg.norm(want) + 1e-30)))
    return worst


def readings_of(answer: Mapping[str, Any], want_scores, want_layers,
                cuts: Mapping[str, float], theta, block, item: int
                ) -> Dict[str, float]:
    want = np.asarray(want_scores, dtype=np.float64)
    got = np.asarray(answer["scores"], dtype=np.float64)
    wl = np.asarray(want_layers, dtype=np.float64)
    gl = np.asarray(answer["layers"], dtype=np.float64)
    return {
        "score_err": float(np.max(np.abs(got - want)) / (want.std() + 1e-30)),
        "layer_err": float(np.max(np.linalg.norm(gl - wl, axis=-1)
                                  / (np.linalg.norm(wl, axis=-1) + 1e-30))),
        "cache_err": cache_err(theta, block, answer, item),
        "index_regret": float(cuts["index_regret"]),
        "router_margin": float(max(cuts["router_low"], cuts["router_out"])),
        "gate_err": gate_err(theta, block, answer)}


def over(readings: Mapping[str, float]) -> List[str]:
    return [f"{k} {readings[k]:.4g} > {v}" for k, v in LIMITS.items()
            if not readings[k] <= v]


def compare(theta, block: Mapping[str, Any], records: Sequence[Mapping],
            check: Mapping[str, Any], why: List[str]) -> Dict[str, Any]:
    """``records``: a session each, ``{"user", "events" (the whole
    history at the end), "answers": [{"tag", "length", "scores",
    "layers", "selected", "lat", "ik", "picks", "gates", "h2"}]}`` (what
    ``SessionTopK.audits`` keeps of a dispatch). ONE reference pass a
    session gives every answer's position (the model is causal: the
    state at a position does not depend on what came later). Appends
    to ``why``; returns the worst readings and every answer's."""
    from benchmark.harness import oracle_glm5

    worst = {k: 0.0 for k in LIMITS}
    rows, skipped = [], 0
    held = range(int(block["first"]), int(block["first"]) + int(block["held"]))
    exercised = set()
    qb = int(check["q_block"])
    for rec in records:
        by_pos, per_block = {}, {}
        for a in rec["answers"]:
            if a["length"] > 0:
                by_pos[a["length"] - 1] = a
        for p in sorted(by_pos):
            mine = per_block.setdefault(p // qb, [])
            if len(mine) < oracle_glm5.AUDITED:
                mine.append(p)
            else:
                skipped += 1
        at = [p for ps in per_block.values() for p in ps]
        if not at:
            continue
        given = {p: {"selected": by_pos[p]["selected"],
                     "picks": by_pos[p]["picks"]} for p in at}
        out = oracle_glm5.forward(
            theta, np.asarray(rec["events"]), block, at=at, given=given,
            q_block=qb, head_group=int(check["head_group"]),
            key_block=int(check.get("key_block", 1)))
        layers = np.asarray(out["layers"])
        for j, p in enumerate(at):
            a = by_pos[p]
            r = readings_of(a, np.asarray(out["scores"][j]), layers[:, j],
                            out["cuts"][p], theta, block,
                            int(rec["events"][p]))
            exercised |= {int(e) for e in np.asarray(a["picks"]).ravel()
                          if int(e) in held}
            rows.append(dict(
                r, user=int(rec["user"]), tag=a["tag"],
                length=int(a["length"]),
                index_low=out["cuts"][p]["index_low"],
                index_out=out["cuts"][p]["index_out"],
                **{k: a[k] for k in ("slot", "queries", "bucket")
                   if k in a}))
            for k, v in r.items():
                worst[k] = max(worst[k], v) if np.isfinite(v) else v
            why += [f"session u{rec['user']} {a['tag']} at {a['length']} "
                    f"events: {x}" for x in over(r)]
    return {"worst": worst, "answers": rows, "limits": dict(LIMITS),
            "skipped": skipped, "held_experts_exercised": len(exercised)}


def control(name: str, seed: int, rehearse: bool, length: int,
            theta=None) -> Dict[str, Any]:
    """The reference, degraded as ``name`` says, in the lane's place on
    one seeded history of ``length`` events: its last
    ``CONTROL_POSITIONS`` positions' scores, layer states, cuts and
    gates go through :func:`compare` as a lane's answers do."""
    import jax.numpy as jnp

    from benchmark.harness import cell as cells
    from benchmark.harness import oracle_glm5
    from benchmark.models import sessionrec
    from predictionio_tpu.ops import mla

    cell = cells.load_cell("seqrec-glm5.sess-extend", rehearse=rehearse)
    config = cell.config
    block = sessionrec.block_of(config)
    if theta is None:
        theta = mla.draw_serving_theta(
            int(config["vocab_size"]), sessionrec.seqrec_params(config, seed))
    shape = dict(config["shape"], n_users=1, history_min=length,
                 history_max=length)
    events = sessionrec.histories(shape, seed)[0]
    at = list(range(max(0, len(events) - CONTROL_POSITIONS), len(events)))
    kw = dict(at=at, q_block=int(config["check"]["q_block"]),
              head_group=int(config["check"]["head_group"]),
              key_block=int(config["check"].get("key_block", 1)))
    from predictionio_tpu.ops.sessions import SESS_BLOCK

    how: Dict[str, Any] = {
        "sound": {},
        "float8_cache": {"cache_dtype": jnp.float8_e4m3fn},
        "bf16_router": {"router_dtype": jnp.bfloat16},
    }.get(name, {"fault": name, "fault_block": SESS_BLOCK})
    if name == "dropped_expert":
        # the held expert the compared positions pick most: one no
        # compared token routes to cannot show at them
        sound = oracle_glm5.forward(theta, events, block, **kw)
        picks = np.concatenate([sound["audit"][p]["picks"].ravel()
                                for p in at]) - int(block["first"])
        picks = picks[(picks >= 0) & (picks < int(block["held"]))]
        how["fault_expert"] = int(np.bincount(picks).argmax()) \
            if len(picks) else 0
    bad = oracle_glm5.forward(theta, events, block, **kw, **how)
    layers = np.asarray(bad["layers"])
    record = {"user": 0, "events": events, "answers": [dict(
        bad["audit"][p], tag=name, length=p + 1,
        scores=np.asarray(bad["scores"][j]), layers=layers[:, j])
        for j, p in enumerate(at)]}
    why: List[str] = []
    out = compare(theta, block, [record], config["check"], why)
    by = CONTROLS.get(name)
    return {"control": name, "seed": seed, "length": int(length),
            "readings": out["worst"], "limits": dict(LIMITS), "by": by,
            "caught": bool(why) if by is None
            else not out["worst"][by] <= LIMITS[by], **{
                k: v for k, v in how.items() if k == "fault_expert"}}


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
    from benchmark.models import sessionrec
    from predictionio_tpu.ops import mla

    config = cells.load_cell("seqrec-glm5.sess-extend",
                             rehearse=args.rehearse).config
    theta = mla.draw_serving_theta(
        int(config["vocab_size"]),
        sessionrec.seqrec_params(config, args.seed))
    ok = True
    for name in args.control or ("sound",) + tuple(CONTROLS):
        out = control(name, args.seed, args.rehearse,
                      args.length or (40 if args.rehearse else 6144), theta)
        ok = ok and out["caught"] == (name != "sound")
        print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
