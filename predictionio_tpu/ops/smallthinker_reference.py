"""Plain float32 reference of SmallThinker-21BA3B's block
(``model_name: smallthinker_21b_instruct``) as the sequence lane serves
it: the full forward pass over ONE user's whole history. ``jax.numpy``
only, every product at ``jax.default_matmul_precision("highest")``; no
cache, no kernel, no batching, no dispatch plan: attention
materialises its masked scores (a block of queries at a time, so that
13k events at the published widths fit), the masks are built from
positions, and EVERY expert runs on every token, the picked ones
weighted (a scan over the experts: one expert's ``[T, 768]`` at a
time). A layer is one jitted call with fixed shapes (the history
padded to a power of two), because op-by-op execution compiles every
distinct shape of every operation.
``benchmark/harness/oracle_smallthinker.py`` is a copy of this file: the
benchmark's cell compares the served lane with it on the chip.

The layer, from the published ``config.json`` and the catalog's
description of the family (layer ``i``; ``g = sliding_window_layout[i]``,
equal to ``rope_layout[i]``)::

    h  = rmsnorm(x; w_in, eps)                      # input_layernorm
    r  = W_router h            [experts], float32   # BEFORE attention
    q  = W_q h [H x d]   k = W_k h [KV x d]   v = W_v h [KV x d]
    g == 1: q, k = rope(q, pos), rope(k, pos)       # half-split, theta
            key j visible to query p  iff  0 <= p - j < window
    g == 0: no rotation; key j visible iff j <= p
    a  = softmax(q k^T / sqrt(d)) v, head h reads key/value head h // G
    x  = x + W_o a
    h2 = rmsnorm(x; w_post, eps)                    # post_attention_layernorm
    e_1..e_k = the k largest of r;  w = softmax(r[e_1..e_k])
    x  = x + sum_j w_j W_down[e_j] (relu(W_gate[e_j] h2) * W_up[e_j] h2)

then the final RMSNorm and the untied output table.

Departures from the published model, each ASSUMED (the catalog's
``config`` names only the sizes):

- item ids stand for tokens; the tables hold the catalog's rows;
- the router reads ``h`` (the catalog's ``described_as``: "router
  placed before attention"; the key that switches it is not in the
  catalog's ``config``);
- the window's convention ``p - j < window`` (the query's own position
  and ``window - 1`` before it), as the family's masking code has it,
  as remembered;
- the half-split (rotate-half) rotation;
- no secondary experts (``described_as`` names them for the family; the
  row's ``config`` has no key for them);
- ``norm_topk_prob`` leaves a softmax over the picked logits as it is
  (it sums to 1).

Controls and planted faults (what the benchmark's comparison must
catch; DATA of the jitted layer, :func:`knobs_of`, so one compiled
program serves the sound pass and every control): ``window_off_by_one``
(``p - j <= window``), ``rope_on_global_layer``,
``router_after_attention`` (the router reads ``h2``), ``silu_experts``,
``stale_released_block`` (a window layer also reads the
``stale_block`` positions before its window, which hold ANOTHER
position's rows, as a block given back and handed out again would),
``float8_cache`` (keys and values rounded to float8_e4m3's bits) and
``bf16_router`` (the router product's operands rounded to bfloat16).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
CONTROLS = ("window_off_by_one", "rope_on_global_layer",
            "router_after_attention", "silu_experts",
            "stale_released_block", "float8_cache", "bf16_router")
STALE_SHIFT = 7919      # whose rows a stale block holds: this far away


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_f32(a), _f32(b), precision=HIGHEST)


def _bf16(x):
    """``x`` rounded to bfloat16 by an operation the compiler may not
    elide (a pair of casts it may, with excess precision allowed)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _f8(x):
    """``x`` rounded to float8_e4m3's 4 exponent and 3 mantissa bits,
    likewise (a pair of casts read exactly the sound pass on the chip:
    my chip run, PR 39)."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def rms_norm(x, g, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta: float):
    """Half-split rotation of ``x: [T, heads, d]`` at ``pos: [T]``:
    ``(x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)``, the angle of
    pair ``i`` ``pos * theta^(-i / (d / 2))``."""
    half = x.shape[-1] // 2
    # the frequencies on the host, in float64: the chip's float32 power
    # is a few ulp off, which 14k positions turn into a thousandth of
    # the rotated row (my chip run, PR 39: 0.0019 between this function
    # run eagerly and the same lines constant-folded inside a jit)
    inv = jnp.asarray(1.0 / theta ** (np.arange(half) / half), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def knobs_of(control: Optional[str] = None, stale_block: int = 0
             ) -> np.ndarray:
    """The controls as data: ``[window_extra, rope_global,
    router_from_h2, silu, stale rows, float8 cache, bf16 router]``."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}: one of {CONTROLS}")
    k = np.zeros(7, np.int32)
    if control is not None:
        at = CONTROLS.index(control)
        k[at] = int(stale_block) if control == "stale_released_block" else 1
    return k


def softmax_over(logits, picks):
    """The weights of ``picks [.., k]``: a softmax over THEIR logits."""
    return jax.nn.softmax(jnp.take_along_axis(logits, picks, axis=-1),
                          axis=-1)


def layer(p: Mapping[str, Any], x, g, knobs, at, given, given_ok, *,
          cfg: Mapping[str, Any], q_block: int):
    """One layer over the whole (padded) history ``x: [T, D]``; ``g``:
    1 for a rotary window layer, 0 for a global one; ``at [A]``: the
    audited positions; ``given [A, k]`` under ``given_ok [A]``: router
    picks to take at them in place of the layer's own (the program's,
    so that a tie taken the other way round is no difference).
    Returns the new ``x`` and, at the audited positions, the router's
    input ``h``, its logits, the picks used, their weights and the key
    and value rows."""
    T, D = x.shape
    H, KV, d = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"]
    G, W, k_top = H // KV, cfg["window"], cfg["per_token"]
    eps = cfg["norm_eps"]
    pos = jnp.arange(T, dtype=jnp.int32)
    (extra, rope_global, from_h2, silu, stale, float8,
     bf16_router) = (knobs[i] for i in range(7))
    h = rms_norm(x, _f32(p["ln1_g"]), eps)
    q = _mm(h, p["wq"]).reshape(T, H, d)
    k = _mm(h, p["wk"]).reshape(T, KV, d)
    v = _mm(h, p["wv"]).reshape(T, KV, d)
    rotate = (g > 0) | (rope_global > 0)
    q = jnp.where(rotate, rope(q, pos, cfg["rope_theta"]), q)
    k = jnp.where(rotate, rope(k, pos, cfg["rope_theta"]), k)
    k = jnp.where(float8 > 0, _f8(k), k)
    v = jnp.where(float8 > 0, _f8(v), v)
    # the stale control: a position before the window reads as the rows
    # of a position STALE_SHIFT away
    k_far = jnp.roll(k, STALE_SHIFT, axis=0)
    v_far = jnp.roll(v, STALE_SHIFT, axis=0)

    def block(args):
        q_b, pos_b = args                                   # [qb, H, d], [qb]
        back = pos_b[:, None] - pos[None, :]                # [qb, T]
        inside = (back >= 0) & ((g == 0) | (back < W + extra))
        old = (g > 0) & (back >= W + extra) & (back < W + extra + stale)
        s = jnp.einsum("qkgd,skd->kgqs", q_b.reshape(-1, KV, G, d), k,
                       precision=HIGHEST)
        s_far = jnp.einsum("qkgd,skd->kgqs", q_b.reshape(-1, KV, G, d),
                           k_far, precision=HIGHEST)
        s = jnp.where(inside, s, jnp.where(old, s_far, NEG)) \
            / jnp.sqrt(jnp.float32(d))
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", jnp.where(inside, a, 0.0), v,
                       precision=HIGHEST) \
            + jnp.einsum("kgqs,skd->qkgd", jnp.where(old, a, 0.0), v_far,
                         precision=HIGHEST)
        return o.reshape(-1, H * d)

    o = jax.lax.map(block, (q.reshape(T // q_block, q_block, H, d),
                            pos.reshape(T // q_block, q_block)))
    x = x + _mm(o.reshape(T, H * d), p["wo"])
    h2 = rms_norm(x, _f32(p["ln2_g"]), eps)
    r_in = jnp.where(from_h2 > 0, h2, h)
    w_r = _f32(p["router"])
    logits = jnp.where(
        bf16_router > 0,
        jnp.matmul(_bf16(r_in), _bf16(w_r), precision=HIGHEST),
        jnp.matmul(r_in, w_r, precision=HIGHEST))
    _, picks = jax.lax.top_k(logits, k_top)
    picks = picks.astype(jnp.int32).at[at].set(
        jnp.where(given_ok[:, None], given, picks[at]))
    weights = softmax_over(logits, picks)
    E = logits.shape[-1]
    w_full = jnp.sum(jax.nn.one_hot(picks, E, dtype=jnp.float32)
                     * weights[..., None], axis=1)          # [T, E]

    def expert(y, e):
        wg, wu, wd, w_e = e
        gate = _mm(h2, wg)
        gate = jnp.where(silu > 0, jax.nn.silu(gate), jax.nn.relu(gate))
        return y + w_e[:, None] * _mm(gate * _mm(h2, wu), wd), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["we_gate"], p["we_up"], p["we_down"], w_full.T))
    x = x + y
    return x, {"h": h[at], "logits": logits[at], "picks": picks[at],
               "gates": weights[at], "k": k.reshape(T, -1)[at],
               "v": v.reshape(T, -1)[at], "x": x[at]}


@functools.lru_cache(maxsize=8)
def _layer_jit(cfg_items, q_block: int):
    return jax.jit(functools.partial(layer, cfg=dict(cfg_items),
                                     q_block=q_block))


def layer_params(theta: Mapping[str, Any], i: int) -> Dict[str, Any]:
    pre = f"l{i}_"
    return {k[len(pre):]: v for k, v in theta.items() if k.startswith(pre)}


def _cfg_key(cfg: Mapping[str, Any]):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if k in ("n_heads", "n_kv", "head_dim", "window",
                                 "per_token", "norm_eps", "rope_theta")))


def _bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def router_cuts(logits, picks, k: int) -> Dict[str, float]:
    """How far ``picks`` are from a cut the reference could have taken
    itself, by ITS logits: its k-th largest less the lowest picked one
    (``router_low``), and the highest one left out less the k-th
    (``router_out``), over the spread of the logits. Both 0 for any top
    set, whichever way round its ties go."""
    logits = np.asarray(logits, np.float64)
    picks = np.asarray(picks)
    kth = np.sort(logits)[-k]
    left = np.delete(logits, picks)
    spread = logits.std() + 1e-30
    return {"router_low": float(max(0.0, kth - logits[picks].min())
                                / spread),
            "router_out": float(max(0.0, left.max() - kth) / spread)}


def _rows(g, wk, wv, x_in, pos, rotated, *, cfg):
    h = rms_norm(_f32(x_in), _f32(g), cfg["norm_eps"])
    k = _mm(h, wk).reshape(-1, cfg["n_kv"], cfg["head_dim"])
    k = jnp.where(rotated, rope(k, pos, cfg["rope_theta"]), k)
    return jnp.concatenate([k.reshape(len(pos), -1), _mm(h, wv)], axis=-1)


@functools.lru_cache(maxsize=8)
def _rows_jit(cfg_items):
    return jax.jit(functools.partial(_rows, cfg=dict(cfg_items)))


def cache_rows(theta: Mapping[str, Any], cfg: Mapping[str, Any], i: int,
               x_in, pos: Sequence[int]):
    """The key and value rows layer ``i`` writes for inputs ``x_in [n,
    D]`` at positions ``pos`` (``[n, 2 x kv_width]``): what a check
    holds the lane's written rows against, from the lane's OWN input."""
    with jax.default_matmul_precision("highest"):
        return _rows_jit(_cfg_key(cfg))(
            theta[f"l{i}_ln1_g"], theta[f"l{i}_wk"], theta[f"l{i}_wv"],
            jnp.asarray(x_in, jnp.float32), jnp.asarray(pos, jnp.int32),
            bool(cfg["pattern"][i]))


def forward(theta: Mapping[str, Any], ids, cfg: Mapping[str, Any], *,
            at: Optional[Sequence[int]] = None,
            given: Optional[Mapping[int, Any]] = None, q_block: int = 512,
            control: Optional[str] = None, stale_block: int = 0,
            pad: int = 0) -> Dict[str, Any]:
    """The whole history ``ids [n]`` through every layer. ``cfg``:
    ``n_layers``, ``n_heads``, ``n_kv``, ``head_dim``, ``window``,
    ``pattern`` (a layer: 1 rotary window, 0 global), ``per_token``,
    ``norm_eps``, ``rope_theta``, ``n_items``. ``at``: the positions to
    report (None: every one); ``given``: ``{position: picks [layers,
    k]}`` to take there; ``pad``: pad the history to this many
    positions at least (one compiled program for histories of several
    lengths). Returns, at those positions in ``at``'s order:
    ``scores [A, items]``, ``layers [L, A, D]`` (the residual stream
    after every layer), ``h`` (the router's input), ``logits [L, A,
    experts]``, ``picks``, ``gates``, ``k`` / ``v`` ``[L, A,
    kv_width]``, ``first [L, A]`` (the first position a layer reads for
    it) and ``cuts``: per position the worst layer's
    :func:`router_cuts`."""
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    at = list(range(n)) if at is None else [int(p) for p in at]
    given = given or {}
    L, k_top = int(cfg["n_layers"]), int(cfg["per_token"])
    T = _bucket(max(n, int(pad)), q_block)
    knobs = knobs_of(control, stale_block)
    extra = int(control == "window_off_by_one")
    a_pos = jnp.asarray(at, jnp.int32)
    run = _layer_jit(_cfg_key(cfg), min(q_block, T))
    with jax.default_matmul_precision("highest"):
        x = jnp.zeros((T, theta["item_emb"].shape[1]), jnp.float32).at[
            :n].set(_f32(jnp.take(theta["item_emb"], jnp.asarray(ids),
                                  axis=0)))
        kept: Dict[str, list] = {}
        for i in range(L):
            g_ok = np.asarray([p in given for p in at])
            g_picks = np.stack([np.asarray(given[p])[i] if p in given
                                else np.zeros(k_top, np.int32) for p in at])
            x, out = run(layer_params(theta, i), x,
                         jnp.int32(cfg["pattern"][i]), jnp.asarray(knobs),
                         a_pos,
                         jnp.asarray(g_picks, jnp.int32), jnp.asarray(g_ok))
            for key, val in out.items():
                kept.setdefault(key, []).append(val)
        hq = rms_norm(x[a_pos], _f32(theta["ln_f_g"]), cfg["norm_eps"])
        scores = _mm(hq, _f32(theta["out_emb"][:int(cfg["n_items"])]).T)
    got = {k: np.asarray(jnp.stack(v)) for k, v in kept.items()}
    cuts = {}
    for j, p in enumerate(at):
        worst = {"router_low": 0.0, "router_out": 0.0}
        for i in range(L):
            c = router_cuts(got["logits"][i, j], got["picks"][i, j], k_top)
            worst = {k: max(worst[k], c[k]) for k in worst}
        cuts[p] = worst
    # (a control that reads further back says so: the first position
    # a layer READ)
    extra += int(knobs[4])
    first = np.asarray([[max(0, p - int(cfg["window"]) - extra + 1)
                         if cfg["pattern"][i] else 0 for p in at]
                        for i in range(L)], np.int32)
    return {"scores": np.asarray(scores), "layers": got["x"],
            "h": got["h"], "logits": got["logits"], "picks": got["picks"],
            "gates": got["gates"], "k": got["k"], "v": got["v"],
            "first": first, "cuts": cuts}
