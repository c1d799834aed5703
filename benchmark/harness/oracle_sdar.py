"""Plain float32 reference of the SDAR block (``model_type: sdar_moe``)
and of generation by diffusion over blocks, as the sequence lane runs
them. ``jax.numpy`` only, every product at
``jax.default_matmul_precision("highest")``; no cache, no kernel, no
batching, no dispatch plan: attention materialises its masked scores
and every expert runs on every token, the result masked to the 8
chosen. ``benchmark/harness/oracle_sdar.py`` is a copy of this file:
the benchmark's cell compares the system with it on the chip.

The model, from the published ``config.json`` of SDAR-30B-A3B-Chat
(``model_type: sdar_moe``) and the Qwen3-MoE family's public code it
is built on: a pre-norm residual block; ``q = h Wq`` as 32 heads of
128, ``k = h Wk`` and ``v = h Wv`` as 4 heads of 128, no bias; ``q``
and ``k`` each RMS-normed over their 128 values with one learned weight
a projection; rotate-half rotary positions, theta 1e6; query head ``i``
reads key/value head ``i // 8``; scale ``1 / sqrt(128)``; key ``j`` is
visible to query ``i`` iff ``p_j // B <= p_i // B`` (a token sees its
whole block and every earlier block); router logits ``h2 Wr`` in
float32, softmax over the 128 experts, the 8 largest, their weights
divided by their sum; each expert ``W_down(silu(W_gate x) * W_up x)``;
a final RMSNorm; an untied output table. The logits at a masked
position predict THAT position (no shift).

Generation of a slate (:func:`generate`): blocks of ``B`` positions are
decoded in order; a block starts as mask tokens (the first holds the
session's unfinished block, its ``n mod B`` newest events, unmasked);
a PASS is a forward of the block against everything before it and
itself, the argmax and its softmax probability (the confidence) at
every masked position, and the rule's unmasking
(``low_confidence_static``: the ``ceil(masked at start / steps)`` most
confident; ``low_confidence_dynamic``: every position at the threshold
or above, at least the most confident), until no mask is left.

Departures from the published model, each because the sequence lane
recommends items and does not model text:

- item ids stand for tokens; the mask token's row is no item;
- GREEDY: the argmax where the family's script samples at temperature
  1 (a served slate is a function of the session);
- an item already in the slate, and what the user has seen, is masked
  out of the logits before the argmax and the softmax: a slate is
  distinct unseen items. Two positions that pick the same item in one
  pass: the more confident takes it, the other stays masked;
- block length, steps, rule and threshold are inference settings the
  ``config.json`` does not carry: set from the family's generation
  script as remembered.

``operands`` rounds every matmul's operands through a lower dtype
(products still accumulate in float32): how the benchmark finds what a
computation in a precision below the stated one would read. ``past``
lets a forward CONTINUE one made before by this same file (the keys
and values it returned for the earlier positions): block-causal
attention never looks ahead of a block, so a sequence cut at a block
boundary and continued gives the whole sequence's numbers.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30


def _r(x, operands):
    x = x.astype(jnp.float32)
    return x if operands is None else x.astype(operands).astype(jnp.float32)


def _mm(a, b, operands=None):
    return jnp.matmul(_r(a, operands), _r(b, operands), precision=HIGHEST)


def rms_norm(x, g, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta: float):
    """``x: [T, heads, d]`` at ``pos: [T]``: ``x * cos + rotate_half(x) *
    sin``, the frequencies repeated over the two halves."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def visible(pos_q, pos_k, block: int, mask: str = "block"):
    """``[Tq, Tk]``: which keys a query sees. ``block``: its own block
    and every earlier one; ``causal`` (a control: the wrong mask):
    itself and every earlier position."""
    if mask == "causal":
        return pos_k[None, :] <= pos_q[:, None]
    return pos_k[None, :] // block <= pos_q[:, None] // block


def experts(h, w_router, w_gate, w_up, w_down, k: int, renorm: bool = True,
            operands=None, given=None, margin: float = 0.0):
    """Every expert on every token (a loop over experts), masked to the
    top ``k`` of the float32 softmax over the router's logits; the
    chosen weights divided by their sum (``renorm``). ``given [T, k]``
    (a check gives the program's picks: with seeded weights the 8th and
    9th of 128 near-equal probabilities swap on rounding, and one
    swapped expert moves a token's stream by more than any precision
    does): a token whose given experts ALL lie within ``margin`` of
    this router's own cut (probability at least ``(1 - margin)`` x its
    ``k``-th largest: the same picks at 0, or a flipped near-tie) is
    routed to them, weighted by this router's own probabilities of them; every
    other token keeps this router's own picks. Returns ``(y, picks [T,
    k], gates [T, k], probs [T, E])``."""
    logits = jnp.matmul(h.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, picks = jax.lax.top_k(probs, k)
    if given is not None:
        theirs = jnp.take_along_axis(probs, given, axis=-1)
        near = jnp.all(theirs >= (1.0 - margin) * gates[:, -1:], axis=-1,
                       keepdims=True)
        picks = jnp.where(near, given, picks)
        gates = jnp.where(near, theirs, gates)
    if renorm:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    E = w_router.shape[1]
    full = jnp.sum(jax.nn.one_hot(picks, E, dtype=jnp.float32)
                   * gates[..., None], axis=1)                  # [T, E]

    def one(y, args):
        wg, wu, wd, w = args
        a = jax.nn.silu(_mm(h, wg, operands)) * _mm(h, wu, operands)
        return y + w[:, None] * _mm(a, wd, operands), None

    y, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32),
                        (w_gate, w_up, w_down, full.T))
    return y, picks, gates, probs


def layer(p: Mapping[str, Any], x, pos, past_k, past_v, past_pos, given,
          cfg, operands=None, mask: str = "block", renorm: bool = True,
          q_rows: int = 0, margin: float = 0.0):
    """One layer on token rows ``x: [T, D]`` at ``pos: [T]`` that
    attend over ``past_k`` / ``past_v`` ``[S, KV, d]`` at ``past_pos
    [S]`` (negative: no key) and over each other. ``p``: the layer's
    parameters by their unprefixed names; ``given``, ``margin``:
    :func:`experts`'s (None: this router's own picks). ``q_rows``: materialise the
    scores of that many queries at a time (a divisor of ``T``; 0: all).
    Returns ``(x, k [T, KV, d], v, picks, gates, probs)``."""
    T = x.shape[0]
    H, KV, d = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"]
    eps = cfg["norm_eps"]
    h = rms_norm(x, p["ln1_g"], eps)
    q = _mm(h, p["wq"], operands).reshape(T, H, d)
    k = _mm(h, p["wk"], operands).reshape(T, KV, d)
    v = _mm(h, p["wv"], operands).reshape(T, KV, d)
    q = rope(rms_norm(q, p["qn_g"], eps), pos, cfg["rope_theta"])
    k = rope(rms_norm(k, p["kn_g"], eps), pos, cfg["rope_theta"])
    ks = jnp.concatenate([past_k.astype(jnp.float32), k], axis=0)
    vs = jnp.concatenate([past_v.astype(jnp.float32), v], axis=0)
    ps = jnp.concatenate([past_pos, pos])
    ok = visible(pos, ps, cfg["block_len"], mask) & (ps >= 0)[None, :]
    # query head i reads key/value head i // (H / KV)
    qg = q.reshape(T, KV, H // KV, d)

    def attend(args):
        q_b, ok_b = args
        s = jnp.einsum("tkgd,skd->kgts", _r(q_b, operands), _r(ks, operands),
                       precision=HIGHEST) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(ok_b[None, None], s, NEG), axis=-1)
        return jnp.einsum("kgts,skd->tkgd", _r(a, operands),
                          _r(vs, operands), precision=HIGHEST)

    if q_rows and T > q_rows:
        # the same numbers, ``q_rows`` queries' scores at a time
        o = jax.lax.map(attend, (
            qg.reshape((T // q_rows, q_rows) + qg.shape[1:]),
            ok.reshape(T // q_rows, q_rows, -1)))
    else:
        o = attend((qg, ok))
    o = o.reshape(T, H * d)
    x = x + _mm(o, p["wo"], operands)
    h2 = rms_norm(x, p["ln2_g"], eps)
    y, picks, gates, probs = experts(
        h2, p["router"], p["we_gate"], p["we_up"], p["we_down"],
        cfg["per_token"], renorm, operands, given, margin)
    return x + y, k, v, picks, gates, probs


def rows_of(p: Mapping[str, Any], x, pos, cfg, operands=None):
    """The key and value rows ``[T, KV x d]`` a layer computes from ITS
    OWN input ``x: [T, D]`` at ``pos``: what the rows a program wrote
    are held against with nothing upstream in the difference."""
    T = x.shape[0]
    KV, d = cfg["n_kv"], cfg["head_dim"]
    with jax.default_matmul_precision("highest"):
        h = rms_norm(jnp.asarray(x, jnp.float32),
                     p["ln1_g"].astype(jnp.float32), cfg["norm_eps"])
        k = _mm(h, p["wk"], operands).reshape(T, KV, d)
        v = _mm(h, p["wv"], operands)
        k = rope(rms_norm(k, p["kn_g"].astype(jnp.float32),
                          cfg["norm_eps"]), jnp.asarray(pos),
                 cfg["rope_theta"])
    return np.asarray(k.reshape(T, -1)), np.asarray(v)


@functools.lru_cache(maxsize=32)
def _layer_jit(cfg_items: Tuple, operands, mask: str, renorm: bool,
               q_rows: int, margin: float):
    cfg = dict(cfg_items)
    return jax.jit(functools.partial(layer, cfg=cfg, operands=operands,
                                     mask=mask, renorm=renorm,
                                     q_rows=q_rows, margin=margin))


def layer_params(theta: Mapping[str, Any], i: int) -> Dict[str, Any]:
    pre = f"l{i}_"
    return {k[len(pre):]: v for k, v in theta.items() if k.startswith(pre)}


def _cfg_key(cfg: Mapping[str, Any]) -> Tuple:
    keys = ("n_heads", "n_kv", "head_dim", "norm_eps", "rope_theta",
            "block_len", "per_token")
    return tuple((k, cfg[k]) for k in keys)


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def forward(theta: Mapping[str, Any], ids, pos, cfg: Mapping[str, Any],
            past: Optional[Mapping[str, Any]] = None, operands=None,
            mask: str = "block", renorm: bool = True,
            q_block: int = 0, q_rows: int = 0, given=None,
            margin: float = 0.0) -> Dict[str, Any]:
    """The whole model over ``ids`` at ``pos`` (1-D; a masked position
    holds the mask token's id), continuing ``past`` (``{"k", "v":
    [layers, S, KV, d], "pos": [S]}`` of an earlier call; None: from
    the start); ``given [layers, T, k]``, ``margin``: the experts a
    program routed to and how far from this router's cut they may lie
    (:func:`experts`). Returns ``{"hidden": [T, D]`` (final norm applied),
    ``"k"``, ``"v"``: ``[layers, T, KV, d]``, ``"picks"`` / ``"gates"``:
    ``[layers, T, k]``, ``"probs"``: ``[layers, T, E]``, ``"x"``: ``[layers, T, D]`` (each
    layer's input), ``"pos"``}``.
    ``q_block``: run the rows that many at a time, each batch
    continuing the one before (whole blocks: the same numbers; a long
    history does not fit otherwise); ``q_rows``: :func:`layer`'s. One
    jitted program a layer shape
    (the past padded to a power of two): eager ``jax.numpy`` compiles
    every distinct shape of every op."""
    ids = np.asarray(ids, dtype=np.int32)
    pos = np.asarray(pos, dtype=np.int32)
    n_layers = int(cfg["n_layers"])
    KV, d = int(cfg["n_kv"]), int(cfg["head_dim"])
    if past is None:
        past = {"k": np.zeros((n_layers, 0, KV, d), np.float32),
                "v": np.zeros((n_layers, 0, KV, d), np.float32),
                "pos": np.zeros((0,), np.int32)}
    if q_block and len(ids) > q_block:
        if q_block % int(cfg["block_len"]):
            raise ValueError("q_block: whole blocks")
        outs = []
        for a in range(0, len(ids), q_block):
            out = forward(theta, ids[a:a + q_block], pos[a:a + q_block],
                          cfg, past, operands, mask, renorm, q_rows=q_rows)
            outs.append(out)
            past = extend_past(past, out)
        return {k: np.concatenate([o[k] for o in outs],
                                  axis=0 if k in ("hidden", "pos") else 1)
                for k in outs[0]}
    # (a last batch's rows: the largest divisor the limit allows)
    rows = 0 if not q_rows or len(ids) <= q_rows else max(
        r for r in range(1, q_rows + 1) if len(ids) % r == 0)
    fn = _layer_jit(_cfg_key(cfg), operands, mask, bool(renorm), rows,
                    float(margin))
    # the past padded to a power of two (one program a bucket); a
    # caller that keeps it on the device passes it padded already,
    # with position -1 where there is no key
    pk, pv, pp = past["k"], past["v"], np.asarray(past["pos"], np.int32)
    pad = (_bucket(pk.shape[1]) if pk.shape[1] else 0) - pk.shape[1]
    if pad:
        pk = np.pad(np.asarray(pk, np.float32),
                    ((0, 0), (0, pad), (0, 0), (0, 0)))
        pv = np.pad(np.asarray(pv, np.float32),
                    ((0, 0), (0, pad), (0, 0), (0, 0)))
        pp = np.pad(pp, (0, pad), constant_values=-1)
    x = jnp.take(theta["item_emb"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    out: Dict[str, List[Any]] = {k: [] for k in ("k", "v", "picks", "gates",
                                                 "probs", "x")}
    with jax.default_matmul_precision("highest"):
        for i in range(n_layers):
            out["x"].append(np.asarray(x))
            x, k, v, picks, gates, probs = fn(
                layer_params(theta, i), x, jnp.asarray(pos), pk[i], pv[i],
                pp, None if given is None
                else jnp.asarray(given[i], jnp.int32))
            for name, a in (("k", k), ("v", v), ("picks", picks),
                            ("gates", gates), ("probs", probs)):
                out[name].append(np.asarray(a))
        hidden = rms_norm(x, theta["ln_f_g"].astype(jnp.float32),
                          float(cfg["norm_eps"]))
    got = {k: np.stack(v) for k, v in out.items()}
    got.update(hidden=np.asarray(hidden), pos=pos)
    return got


def extend_past(past: Mapping[str, Any], out: Mapping[str, Any],
                rows: Optional[slice] = None) -> Dict[str, Any]:
    """``past`` with the rows (all, or ``rows``) of a forward's
    output behind it."""
    rows = rows or slice(None)
    return {"k": np.concatenate([past["k"], out["k"][:, rows]], axis=1),
            "v": np.concatenate([past["v"], out["v"][:, rows]], axis=1),
            "pos": np.concatenate([past["pos"], out["pos"][rows]])}


def logits_of(theta: Mapping[str, Any], hidden, operands=None):
    """``hidden [T, D]`` against the output table: ``[T, rows]``."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_mm(jnp.asarray(hidden),
                              jnp.asarray(theta["out_emb"]).T, operands))


def unmask(conf, tok, masked, quota: int, rule: str,
           threshold: float) -> np.ndarray:
    """Which masked positions of ONE block a pass unmasks: candidates
    most confident first (ties: the earlier position);
    ``low_confidence_static`` takes the first ``quota``,
    ``low_confidence_dynamic`` every one at ``threshold`` or above and
    always the first; a candidate whose token an earlier candidate of
    this pass took stays masked. ``[R]`` bool."""
    conf, tok = np.asarray(conf, np.float64), np.asarray(tok)
    masked = np.asarray(masked, bool)
    order = sorted(np.flatnonzero(masked), key=lambda j: (-conf[j], j))
    accept = np.zeros(len(conf), bool)
    took: List[int] = []
    for r, j in enumerate(order):
        want = len(took) < quota if rule == "low_confidence_static" \
            else (conf[j] >= threshold or r == 0)
        if want and int(tok[j]) not in took:
            accept[j] = True
            took.append(int(tok[j]))
    return accept


def passes_needed(masked: int, cfg: Mapping[str, Any]) -> Optional[int]:
    """Denoising passes a block with ``masked`` masks takes under the
    static rule (None: the dynamic rule's count depends on the
    confidences)."""
    if cfg["remasking"] != "low_confidence_static" or not masked:
        return None
    return -(-masked // -(-masked // int(cfg["steps"])))


def confidences(logits, barred) -> Tuple[np.ndarray, np.ndarray]:
    """Per row of ``logits [R, rows]`` under ``barred [rows]`` (and
    ``[R, rows]``): the argmax and its softmax probability over what
    is not barred (float64)."""
    z = np.where(barred, -np.inf, np.asarray(logits, np.float64))
    tok = np.argmax(z, axis=-1)
    top = np.max(z, axis=-1)
    return tok.astype(np.int32), 1.0 / np.sum(np.exp(z - top[..., None]),
                                              axis=-1)


def denoise_block(theta, past, fixed: Sequence[int], n_rows: int, pos0: int,
                  barred: np.ndarray, cfg: Mapping[str, Any], **kw
                  ) -> Dict[str, Any]:
    """One block at positions ``pos0 ..``: its first ``len(fixed)``
    rows hold ``fixed`` (a session's tail), the other ``n_rows -
    len(fixed)`` start masked. ``barred [rows]``: what may not be
    generated (seen, in the slate, the mask token, rows past the
    catalog). Returns ``{"ids", "conf", "when" (the pass that unmasked
    each row, -1: fixed), "passes": [{"ids", "masked", "logits",
    "picked", "picks"}], "commit": the finished block's forward}``."""
    R, mask_id = int(n_rows), int(cfg["mask_id"])
    ids = np.full(R, mask_id, np.int32)
    ids[:len(fixed)] = np.asarray(fixed, np.int32)
    masked = np.arange(R) >= len(fixed)
    conf = np.where(masked, 0.0, 1.0)
    when = np.full(R, -1, np.int32)
    pos = pos0 + np.arange(R, dtype=np.int32)
    quota = -(-int(masked.sum()) // int(cfg["steps"]))
    passes = []
    while masked.any():
        out = forward(theta, ids, pos, cfg, past, **kw)
        logits = logits_of(theta, out["hidden"], kw.get("operands"))
        bar = np.array(barred, bool)
        bar[ids[~masked]] = True
        tok, c = confidences(logits, bar[None, :])
        accept = unmask(c, tok, masked, quota, cfg["remasking"],
                        float(cfg["threshold"]))
        passes.append({"ids": ids.copy(), "masked": masked.copy(),
                       "logits": logits, "picked": accept,
                       "picks": out["picks"]})
        ids = np.where(accept, tok, ids).astype(np.int32)
        conf = np.where(accept, c, conf)
        when = np.where(accept, len(passes) - 1, when)
        masked = masked & ~accept
    return {"ids": ids, "conf": conf, "when": when, "passes": passes,
            "commit": forward(theta, ids, pos, cfg, past, **kw)}


def generate(theta, events: Sequence[int], num: int, seen,
             cfg: Mapping[str, Any], **kw) -> Dict[str, Any]:
    """A slate of ``num`` items for a session of ``events``: the
    events up to the last block boundary are run once (no cache: this
    is the whole history's forward), then blocks are decoded in order.
    Returns ``{"slate", "conf": [num], "blocks": denoise_block's
    outputs}``."""
    B = int(cfg["block_len"])
    events = np.asarray(events, dtype=np.int32)
    n = len(events) - len(events) % B
    past = None
    if n:
        out = forward(theta, events[:n], np.arange(n), cfg, **kw)
        past = extend_past({"k": out["k"][:, :0], "v": out["v"][:, :0],
                            "pos": out["pos"][:0]}, out)
    rows = int(theta["out_emb"].shape[0])
    barred = np.zeros(rows, bool)
    barred[np.asarray(seen, dtype=np.int64)] = True
    barred[events] = True
    barred[int(cfg["n_items"]):] = True
    barred[int(cfg["mask_id"])] = True
    tail = events[n:].tolist()
    slate: List[int] = []
    conf: List[float] = []
    blocks = []
    pos0 = n
    while len(slate) < num:
        fixed = tail if pos0 == n else []
        n_rows = min(B, len(fixed) + num - len(slate))
        bar = barred.copy()
        bar[slate] = True
        blk = denoise_block(theta, past, fixed, n_rows, pos0, bar, cfg, **kw)
        blocks.append(blk)
        slate += blk["ids"][len(fixed):].tolist()
        conf += blk["conf"][len(fixed):].tolist()
        base = past or {"k": blk["commit"]["k"][:, :0],
                        "v": blk["commit"]["v"][:, :0],
                        "pos": blk["commit"]["pos"][:0]}
        past = extend_past(base, blk["commit"])
        pos0 += n_rows
    return {"slate": np.asarray(slate, np.int32),
            "conf": np.asarray(conf, np.float64), "blocks": blocks}
