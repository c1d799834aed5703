"""What decides ``correct`` in the slate cell, outside the measured
window: what the TIMED lane computed (its audits,
``SessionTopK.audits``) for the check sessions before, inside and
after the window, TEACHER-FORCED against the float32 reference
(``oracle_sdar``: ``default_matmul_precision("highest")``, the
published widths, the lane's own bf16 weights read as float32, no
cache of the lane's, the events as the DRIVER knows them sent).

Sampled tokens are not compared across implementations (with seeded
weights the largest of 151,935 near-uniform logits flips on rounding).
Instead, for every audited ROUND (one block of one query's slate) the
reference runs its own forward over the session's events as the driver
knows them, then over that query's earlier blocks (the lane's tokens),
then, pass by pass, over the block's input ids AS THE LANE HAD THEM,
routed by ITS OWN router, except that a token whose lane picks are a
flipped near-tie (every one within the ``router_margin`` limit of the
reference's own cut: with seeded weights the 8th and 9th of 128
near-equal probabilities swap on rounding, and one swapped expert
moves a token's stream by more than any precision does) follows the
lane's picks; a token the lane routed further off keeps the
reference's picks and fails on ``router_margin`` and ``logit_err``
both. It must give the lane's logits at every row; from the LANE'S OWN
logits the unmasking rule must give the lane's picks; and the commit
pass (and every audited commit of new events) must have had the
reference's layer inputs, written the reference's key and value rows
and routed as the reference routes.

Readings, each with a limit between what the sound lane reads and what
a control reads (PERF.md section 6 has both readings of each):

- ``logit_err`` = max over a pass's rows and items of |lane -
  reference| over the standard deviation of the reference's logits at
  that row. bf16 operands through 6 layers and bf16 cache rows read a
  few percent of a standard deviation; a wrong mask, a missing
  renormalisation or a cache in a coarser precision several times
  that.
- ``cache_err`` = the key and value rows a commit wrote (as the cache
  holds them), against the reference's rows for the lane's OWN input
  to the layer (``oracle.rows_of``), relative L2, worst layer: the
  precision the rows are computed and held in, nothing upstream in
  it. It reads the same at every history length, so it is what stands
  between bf16 and a coarser cache at the cell's 4k-32k sessions,
  where thousands of keys average a coarse row's error out of the
  logits.
- ``state_err`` = that input itself, every layer's, against the
  reference's own forward of the same tokens, relative L2, worst
  layer: what ``cache_err`` holds the rows against is the program's
  word, and this holds it to the reference's (a commit pass has no
  logits to show a wrong mask or a wrong stream).
- ``gate_err`` = the gates the lane used against the reference's for
  the same experts, max absolute: the float32 router product and the
  renormalisation.
- ``router_margin`` = how far the reference's probability of a pick of
  the lane's lies under the reference's own k-th, over the k-th, worst
  pick: a swapped near-tie reads under the limit, a wrong router over
  it.
- ``unmask_gap`` = where the rule applied here to the lane's own
  logits picks other rows or tokens than the lane did: the gap in
  confidence (or logit) between the two choices, relative; 0 when they
  agree. A tie reads ~1e-7; a skipped pass or a wrong rule reads 1.

Structure is exact, no tolerance: a round's block starts at a block
boundary after the session's committed length, which is a multiple of
the block length (the provisional tail never committed); its fixed rows
are the driver's tail events; pass ``i + 1`` starts from pass ``i``'s
result; the passes are as many as the rule needs; the result holds no
mask, no seen item, no item twice.

A wrong mask inside a block of 4 moves a row's attention over
thousands of keys far less than over a few: the logits show it five
times as clearly on a SHORT history. So the cell keeps one check session
of ``check.short_session`` events beside the traffic's 24 (no query of
the window goes to it; the probes before and after do), served by the
timed lane's own programs, and the controls run at that length.
``--history <n>`` runs them at another (PERF.md section 6 has their
readings at the shortest resident session's length, 4,962 events: the
wrong mask reads ``logit_err`` 0.62 there against 3.1 on the short
session, and the lane's own bf16 reads up to 0.15; a coarse cache is
caught by ``cache_err`` alone at either length).

``python3 -m benchmark.harness.slate_check [--control <name> ...]
[--history <n>] [--rehearse]`` puts the reference itself, degraded, in
the lane's place on one seeded history and sends what it computed through
:func:`compare` exactly as a lane's audits go: ``low_operands`` (bf16
where float32 is stated in rehearsal; float8_e4m3fn at the published
widths), ``float8_cache`` (the cached rows rounded through
float8_e4m3fn), ``causal_mask`` (causal where block-causal is due),
``tail_committed`` (a tail committed early: the cache one block ahead),
``no_renorm`` (the gates not divided by their sum), ``pass_skipped``
(one denoising pass left out). Exit 0 when each is caught and the
sound reference passes.
"""

from __future__ import annotations

import functools
import sys
from typing import Any, Dict, List, Mapping, Optional, Sequence

import numpy as np

LIMITS = {
    # between the sound lane's largest reading and the smallest of the
    # controls each number is there to catch, at the published widths
    # under this comparison (PERF.md section 6, PR 33, call 5). Lane,
    # 4 runs with the short session: logit_err 0.080-0.148, cache_err
    # 0.0102-0.0125, state_err 0.017-0.030, gate_err 0.006-0.015,
    # router_margin 0.021-0.097. Controls: causal mask logit_err 3.1 at
    # 23 events and 0.62 at 4,962, state_err 0.57 and 0.092; float8
    # cache cache_err 0.027-0.028 at both lengths (and nothing else
    # over its limit: its logit_err is 0.08-0.09); float8 operands
    # logit_err 1.0, router_margin 0.30; no renormalisation gate_err
    # 0.29-0.40
    "bfloat16": {"logit_err": 0.3, "cache_err": 0.019, "state_err": 0.06,
                 "gate_err": 0.05, "router_margin": 0.2,
                 "unmask_gap": 1e-4},
    "float32": {"logit_err": 2e-3, "cache_err": 2e-4, "state_err": 2e-4,
                "gate_err": 2e-4, "router_margin": 0.02,
                "unmask_gap": 1e-4},
}
CONTROLS = ("low_operands", "float8_cache", "causal_mask", "tail_committed",
            "no_renorm", "pass_skipped")


def _bucket(n: int, lo: int = 64) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class Past:
    """The reference's own keys and values of one session on the
    device, padded to a power of two, with the positions that count
    (-1: no key)."""

    def __init__(self, out: Mapping[str, Any], room: int):
        import jax.numpy as jnp

        n = out["k"].shape[1]
        self.size = _bucket(n + room)
        pad = ((0, 0), (0, self.size - n), (0, 0), (0, 0))
        self.k = jnp.asarray(np.pad(out["k"], pad))
        self.v = jnp.asarray(np.pad(out["v"], pad))
        # (user, cached length, the tokens behind it so far) -> the
        # forwards of a query's blocks that hold those tokens, from its
        # earlier audited rounds
        self.blocks: Dict[Any, list] = {}

    def upto(self, length: int, extra=()) -> Dict[str, Any]:
        """The session's first ``length`` positions, then ``extra``:
        forwards' outputs written behind them."""
        k, v = self.k, self.v
        pos = np.full(self.size, -1, np.int32)
        pos[:length] = np.arange(length)
        at = length
        for out in extra:
            t = out["k"].shape[1]
            k = k.at[:, at:at + t].set(out["k"])
            v = v.at[:, at:at + t].set(out["v"])
            pos[at:at + t] = out["pos"]
            at += t
        return {"k": k, "v": v, "pos": pos}


def cut_rows(out: Mapping[str, Any], n: int) -> Dict[str, Any]:
    """A forward's first ``n`` rows, as ``Past.upto`` takes them."""
    return {"k": out["k"][:, :n], "v": out["v"][:, :n], "pos": out["pos"][:n]}


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def routing_readings(picks, gates, want: Mapping[str, Any], rows: int
                     ) -> Dict[str, float]:
    """The lane's picks and gates ``[layers, R, k]`` against the
    reference's forward ``want`` of the same tokens (routed to those
    picks where they are near-ties of its own): its gates for them,
    and how far each pick lies under the reference's own k-th
    probability."""
    gate_err = margin = 0.0
    k = picks.shape[-1]
    for i in range(picks.shape[0]):
        for r in range(rows):
            probs = np.asarray(want["probs"][i, r], np.float64)
            kth = np.sort(probs)[-k]
            mine = probs[picks[i, r]]
            margin = max(margin, float(np.max((kth - mine) / kth)))
            gate_err = max(gate_err, float(np.max(np.abs(
                np.asarray(gates[i, r], np.float64)
                - np.asarray(want["gates"][i, r], np.float64)))))
    return {"gate_err": gate_err, "router_margin": max(margin, 0.0)}


def commit_readings(audit: Mapping[str, Any], want: Mapping[str, Any],
                    rows: int, pos, theta, block, oracle, kw
                    ) -> Dict[str, float]:
    """A commit pass: each layer's input against the reference's own,
    the rows it wrote against the reference's rows for THAT input, and
    its routing."""
    layers = audit["k"].shape[0]
    cache = state = 0.0
    for i in range(layers):
        state = max(state, _rel(audit["x"][i][:rows], want["x"][i][:rows]))
        k, v = oracle.rows_of(oracle.layer_params(theta, i),
                              audit["x"][i][:rows], pos[:rows], block,
                              kw.get("operands"))
        cache = max(cache, _rel(audit["k"][i, :rows], k),
                    _rel(audit["v"][i, :rows], v))
    return dict(routing_readings(audit["picks"], audit["gates"], want,
                                 rows), cache_err=cache, state_err=state)


def rule_gap(logits, masked, picked, tokens, barred, quota: int,
             block: Mapping[str, Any], oracle) -> float:
    """The rule applied here to the lane's own logits against what the
    lane picked: 0 when rows and tokens agree, else the relative gap
    between the two choices (a tie reads ~0)."""
    tok, conf = oracle.confidences(logits, barred[None, :])
    accept = oracle.unmask(conf, tok, masked, quota, block["remasking"],
                           block["threshold"])
    gap = 0.0
    z = np.where(barred[None, :], -np.inf, np.asarray(logits, np.float64))
    for r in np.flatnonzero(np.asarray(picked) | accept):
        if not (picked[r] and accept[r]):
            # another row: how close were the candidates' confidences
            other = conf[np.asarray(picked) ^ accept]
            gap = max(gap, float(np.ptp(other) / max(other.max(), 1e-30))
                      if len(other) > 1 else 1.0)
        elif int(tokens[r]) != int(tok[r]):
            top = z[r, tok[r]]
            gap = max(gap, float(abs(top - z[r, int(tokens[r])])
                                 / max(abs(top), 1e-30)))
    return gap


def check_round(a: Mapping[str, Any], events: np.ndarray, past: Past,
                theta, block: Mapping[str, Any], oracle, kw,
                why: List[str]) -> Optional[Dict[str, float]]:
    """One audited round: structure exactly, numbers as readings."""
    B, mask_id = int(block["block_len"]), int(block["mask_id"])
    tag = f"u{a['uid']} round {a['round']} at {a['pos0']}"
    len0, pos0, rows = int(a["len0"]), int(a["pos0"]), int(a["rows"])
    tail = np.asarray(a["tail"], np.int32)
    bad = []
    if len0 % B or (pos0 - len0) % B or pos0 < len0:
        bad.append("its block does not start at a block boundary behind "
                   f"a committed length that is whole blocks ({len0})")
    if events[len0:len0 + len(tail)].tolist() != tail.tolist() \
            or len(tail) >= B:
        bad.append("its tail is not the driver's events behind the "
                   "committed length")
    seq = np.concatenate([tail, np.asarray(a["taken"], np.int32)])
    if len(seq) < pos0 - len0:
        bad.append("its earlier blocks are not its tail and slate so far")
    fixed = np.asarray(a["fixed"], np.int32)
    if a["round"] == 0 and (pos0 != len0
                            or fixed.tolist() != tail.tolist()):
        bad.append("its first block does not hold the tail")
    if bad:
        why += [f"{tag}: {b}" for b in bad]
        return None
    # the reference's forwards of the query's earlier blocks: the
    # commit forwards of its earlier rounds where they were audited
    # (kept in ``past.blocks`` under the tokens they hold: two queries
    # that found the session at one committed length are told apart),
    # else a block at a time from its tokens
    done = tuple(seq[:pos0 - len0].tolist())
    extra = list(past.blocks.get((a["uid"], len0, done), ()))
    for p in range(len0 + B * len(extra) if extra else len0, pos0, B):
        ids = np.full(B, mask_id, np.int32)
        pos = np.full(B, -1, np.int32)
        n = min(B, pos0 - p)
        ids[:n], pos[:n] = seq[p - len0:p - len0 + n], p + np.arange(n)
        out = oracle.forward(theta, ids, pos, block,
                             past.upto(len0, extra), **kw)
        extra.append(cut_rows(out, n))
    here = past.upto(len0, extra)
    pos = np.full(B, -1, np.int32)
    pos[:rows] = pos0 + np.arange(rows)
    rows_total = int(theta["out_emb"].shape[0])
    barred = np.zeros(rows_total, bool)
    barred[events[:len0 + len(tail)]] = True
    barred[np.asarray(a["seen0"], np.int64)] = True
    barred[np.asarray(a["taken"], np.int64)] = True
    barred[int(block["n_items"]):] = True
    barred[mask_id] = True
    masked0 = int(np.sum(np.asarray(a["passes"][0]["masked"])[:rows])) \
        if a["passes"] else 0
    quota = -(-masked0 // int(block["steps"]))
    read = {"logit_err": 0.0, "unmask_gap": 0.0}
    ids = np.full(B, mask_id, np.int32)
    ids[:len(fixed)] = fixed
    masked = np.arange(B) >= len(fixed)
    masked[rows:] = False
    n_items = a["passes"][0]["logits"].shape[-1] if a["passes"] else 0
    for i, ps in enumerate(a["passes"]):
        lane_ids = np.asarray(ps["ids"], np.int32)
        lane_masked = np.asarray(ps["masked"], bool)
        if lane_ids[:rows].tolist() != ids[:rows].tolist() \
                or lane_masked[:rows].tolist() != masked[:rows].tolist():
            why.append(f"{tag}: pass {i} does not start from pass "
                       f"{i - 1}'s result")
            return None
        feed = np.where(np.arange(B) < rows, lane_ids, mask_id)
        out = oracle.forward(theta, feed.astype(np.int32), pos, block, here,
                             given=np.asarray(ps["picks"]), **kw)
        want = oracle.logits_of(theta, out["hidden"][:rows],
                                kw.get("operands"))[:, :n_items]
        got = np.asarray(ps["logits"], np.float64)[:rows]
        read["logit_err"] = max(read["logit_err"], float(np.max(
            np.max(np.abs(got - want), axis=1) / np.std(want, axis=1))))
        bar = barred.copy()
        bar[lane_ids[:rows][~lane_masked[:rows]]] = True
        picked = np.asarray(ps["picked"], bool)[:rows]
        nxt = np.asarray(a["passes"][i + 1]["ids"] if i + 1 < len(
            a["passes"]) else a["tokens"], np.int32)
        read["unmask_gap"] = max(read["unmask_gap"], rule_gap(
            got, lane_masked[:rows], picked, nxt[:rows], bar[:n_items],
            quota, block, oracle))
        ids = np.where(np.pad(picked, (0, B - rows)), nxt, ids)
        masked = masked & ~np.pad(picked, (0, B - rows))
    tokens = np.asarray(a["tokens"], np.int32)
    made = tokens[len(fixed):rows]
    if masked.any() or tokens[:rows].tolist() != ids[:rows].tolist():
        why.append(f"{tag}: the passes leave a mask, or the result is not "
                   "the last pass's")
        return None
    if (made == mask_id).any() or barred[made].any() \
            or len(set(made.tolist())) != len(made):
        why.append(f"{tag}: the block holds a mask, a seen item, an item "
                   "of the slate so far, or an item twice")
    need = oracle.passes_needed(masked0, block)
    if need is not None and len(a["passes"]) != need:
        why.append(f"{tag}: {len(a['passes'])} passes where the rule "
                   f"needs {need}")
    conf = np.asarray(a["conf"], np.float64)[len(fixed):rows]
    if not ((conf > 0) & (conf <= 1.0 + 1e-6)).all():
        why.append(f"{tag}: a confidence outside (0, 1]")
    feed = np.where(np.arange(B) < rows, tokens, mask_id).astype(np.int32)
    out = oracle.forward(theta, feed, pos, block, here,
                         given=np.asarray(a["picks"]), **kw)
    read.update(commit_readings(a, out, rows, pos, theta, block, oracle, kw))
    past.blocks[(a["uid"], len0, done + tuple(tokens[:rows].tolist()))] = \
        extra + [cut_rows(out, rows)]
    return read


def check_events(a: Mapping[str, Any], events: np.ndarray, past: Past,
                 theta, block: Mapping[str, Any], oracle, kw,
                 why: List[str]) -> Optional[Dict[str, float]]:
    """One audited commit of new events."""
    B = int(block["block_len"])
    len0, n = int(a["len0"]), int(a["tokens"])
    tag = f"u{a['uid']} commit of {n} at {len0}"
    ids = np.asarray(a["ids"], np.int32)
    if len0 % B or n % B or events[len0:len0 + n].tolist() != ids.tolist():
        why.append(f"{tag}: not whole blocks of the driver's events at a "
                   "block boundary")
        return None
    T = a["k"].shape[1]
    feed = np.full(T, int(block["mask_id"]), np.int32)
    pos = np.full(T, -1, np.int32)
    feed[:n], pos[:n] = ids, len0 + np.arange(n)
    out = oracle.forward(theta, feed, pos, block, past.upto(len0),
                         given=np.asarray(a["picks"]), **kw)
    return commit_readings(a, out, n, pos, theta, block, oracle, kw)


def compare(theta, block: Mapping[str, Any], records: Sequence[Mapping],
            check: Mapping[str, Any], why: List[str], oracle=None,
            compute_dtype: str = "bfloat16") -> Dict[str, Any]:
    """``records``: a check session each: ``user``, ``events`` (every
    event the driver knows it holds at the end, table rows), ``seen0``
    (rows barred from the start) and ``audits`` (the lane's, oldest
    first). Appends to ``why`` what is wrong; returns the worst
    readings and a row an audit."""
    if oracle is None:
        from benchmark.harness import oracle_sdar as oracle
    limits = LIMITS[compute_dtype]
    kw: Dict[str, Any] = {"q_rows": int(check.get("q_rows", 0)),
                          "margin": float(limits["router_margin"])}
    B = int(block["block_len"])
    worst: Dict[str, float] = {}
    rows_out = []
    for rec in records:
        events = np.asarray(rec["events"], np.int32)
        n = len(events) - len(events) % B
        out = oracle.forward(theta, events[:n], np.arange(n), block,
                             q_block=int(check["q_block"]), **kw)
        past = Past(out, room=64)
        for a in rec["audits"]:
            a = dict(a, seen0=rec.get("seen0", ()))
            fn = check_round if a["kind"] == "round" else check_events
            read = fn(a, events, past, theta, block, oracle, kw, why)
            if read is None:
                continue
            rows_out.append(dict(read, user=rec["user"], kind=a["kind"],
                                 len0=int(a["len0"]),
                                 tag=a.get("tag", ""),
                                 slot=int(a.get("slot", 0))))
            for k, v in read.items():
                worst[k] = max(worst.get(k, 0.0), float(v))
                if v > limits[k]:
                    why.append(
                        f"u{rec['user']} {a['kind']} at {a['len0']}: {k} "
                        f"{v:.4g} over its limit {limits[k]}")
    return {"worst": worst, "limits": limits, "answers": rows_out}


# -- the controls ------------------------------------------------------------------

def lane_like(theta, block, events: np.ndarray, nums: Sequence[int],
              oracle, fault: Optional[str] = None,
              operands=None, q_block: int = 0,
              q_rows: int = 0) -> List[Dict[str, Any]]:
    """What a lane's audits would hold for slates of ``nums`` items
    asked of a session of ``events`` one after another, computed by the
    REFERENCE (degraded by ``fault``): the controls' stand-in for the
    lane, through :func:`compare` like the lane's own."""
    import ml_dtypes

    B, mask_id = int(block["block_len"]), int(block["mask_id"])
    kw = {"operands": operands}
    if fault == "causal_mask":
        kw["mask"] = "causal"
    if fault == "no_renorm":
        kw["renorm"] = False
    n = len(events) - len(events) % B
    out = oracle.forward(theta, events[:n], np.arange(n), block,
                         q_block=q_block, q_rows=q_rows, **kw)

    def held(x):
        """A row as the (faulty) cache holds it."""
        if fault != "float8_cache":
            return x
        return np.asarray(x).astype(ml_dtypes.float8_e4m3fn).astype(
            np.float32)

    out = dict(out, k=held(out["k"]), v=held(out["v"]))
    past0 = oracle.extend_past({"k": out["k"][:, :0], "v": out["v"][:, :0],
                                "pos": out["pos"][:0]}, out)
    audits = []
    rows_total = int(theta["out_emb"].shape[0])
    for q, num in enumerate(nums):
        tail = events[n:]
        start = n
        if fault == "tail_committed" and len(tail):
            # the tail's rows written to the cache: the block starts a
            # block late and the tail is no longer the block's own
            tail_out = oracle.forward(theta, tail, n + np.arange(len(tail)),
                                      block, past0, **kw)
            past_q = oracle.extend_past(past0, tail_out)
            start, tail = n + len(tail), tail[:0]
        else:
            past_q = past0
        barred = np.zeros(rows_total, bool)
        barred[events] = True
        barred[int(block["n_items"]):] = True
        barred[mask_id] = True
        slate: List[int] = []
        pos0, rnd = start, 0
        while len(slate) < num:
            fixed = tail if rnd == 0 else tail[:0]
            rows = min(B, len(fixed) + num - len(slate))
            bar = barred.copy()
            bar[slate] = True
            blk = oracle.denoise_block(theta, past_q, fixed, rows, pos0, bar,
                                       block, **kw)
            passes = blk["passes"]
            if fault == "pass_skipped" and len(passes) > 1:
                passes = passes[:1] + passes[2:]
            commit = blk["commit"]
            pad = B - rows

            def padded(x, fill=0):
                return np.pad(np.asarray(x), [(0, pad)] + [(0, 0)] * (
                    np.asarray(x).ndim - 1), constant_values=fill)

            audits.append({
                "kind": "round", "uid": 0, "len0": start, "pos0": pos0,
                "rows": rows, "fixed": np.asarray(fixed, np.int32),
                "tail": np.asarray(tail, np.int32), "num": num,
                "taken": list(slate), "round": rnd, "slot": 0,
                "tokens": padded(blk["ids"], mask_id),
                "conf": padded(blk["conf"]), "when": padded(blk["when"], -1),
                "passes": [{"ids": padded(p["ids"], mask_id),
                            "masked": padded(p["masked"], False),
                            "picked": padded(p["picked"], False),
                            "picks": np.stack([padded(x)
                                               for x in p["picks"]]),
                            "logits": padded(p["logits"])}
                           for p in passes],
                "x": np.stack([padded(x) for x in commit["x"]]),
                "k": np.stack([padded(held(x).reshape(rows, -1))
                               for x in commit["k"]]),
                "v": np.stack([padded(held(x).reshape(rows, -1))
                               for x in commit["v"]]),
                "picks": np.stack([padded(x) for x in commit["picks"]]),
                "gates": np.stack([padded(x) for x in commit["gates"]])})
            slate += blk["ids"][len(fixed):].tolist()
            past_q = oracle.extend_past(past_q, dict(
                commit, k=held(commit["k"]), v=held(commit["v"])))
            pos0 += rows
            rnd += 1
    return audits


@functools.lru_cache(maxsize=1)
def _drawn(seed: int, rehearse: bool):
    """(configuration, block sizes, the seed's weights): drawn once for
    all the controls of a call."""
    from benchmark.harness.cell import load_cell
    from benchmark.models import slaterec
    from predictionio_tpu.ops import sdar

    config = load_cell("seqrec-sdar.slate-gen", rehearse=rehearse).config
    params = slaterec.seqrec_params(config, seed)
    return config, slaterec.block_of(config), sdar.draw_serving_theta(
        int(config["vocab_size"]), params)


def control(name: Optional[str], seed: int, rehearse: bool,
            history: Optional[int] = None) -> Dict[str, Any]:
    """One control (None: the sound reference) on one seeded history
    of ``history`` events (None: the cell's short check session's);
    returns ``compare``'s output and what it said."""
    import jax.numpy as jnp

    from benchmark.harness import oracle_sdar as oracle
    from benchmark.models import slaterec

    config, block, theta = _drawn(seed, rehearse)
    rng = np.random.default_rng([seed, 9])
    check = config["check"]
    ids = rng.integers(0, int(config["shape"]["n_items"]),
                       int(history or check["short_session"]))
    events = slaterec.skip_mask(ids, block["mask_id"])
    cd = str(config["compute_dtype"])
    low = {"float32": jnp.bfloat16, "bfloat16": jnp.float8_e4m3fn}[cd]
    audits = lane_like(theta, block, events, [6, 3], oracle, fault=name,
                       operands=low if name == "low_operands" else None,
                       q_block=int(check["q_block"]),
                       q_rows=int(check["q_rows"]))
    why: List[str] = []
    out = compare(theta, block, [{"user": 0, "events": events,
                                  "audits": audits}],
                  check, why, oracle, compute_dtype=cd)
    return dict(out, why=why)


def main(argv=None) -> int:
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--control", action="append",
                    choices=CONTROLS + ("sound",),
                    help="may be given several times; all of them, the "
                    "sound reference first, when left out")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--history", type=int,
                    help="events of the seeded history (the cell's short "
                    "check session's when left out)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    ok = True
    for name in args.control or ("sound",) + CONTROLS:
        sound = name == "sound"
        got = control(None if sound else name, args.seed, args.rehearse,
                      args.history)
        caught = bool(got["why"])
        print(json.dumps({"control": name, "caught": caught,
                          "history": args.history, "worst": got["worst"],
                          "why": got["why"][:3]}), flush=True)
        ok &= caught != sound
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
