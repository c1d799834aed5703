"""Plain float32 reference of Qwen3-Next-80B-A3B's block
(``model_type: qwen3_next``) as the sequence lane serves it: the full
forward pass over ONE user's whole history. ``jax.numpy`` only, every
product at ``jax.default_matmul_precision("highest")``; no cache, no
kernel, no batching, no dispatch plan and NO CHUNKED FORM: a Gated
DeltaNet layer advances its state by the recurrence ONE POSITION AT A
TIME (a ``lax`` loop with bounds given as data, so that the state can
be handed out at any position without a second pass), attention
materialises its masked scores (a block of queries at a time), and a
held expert runs on the tokens that PICKED it and on no other (gathered
by a sort of the picks; 128 experts dense over 65k tokens are 10^17
operations). A layer is a few jitted calls with fixed shapes (the
history padded to whole blocks), because op-by-op execution compiles
every distinct shape of every operation; a DeltaNet layer takes the
history ``s_block`` rows at a time so that its projections fit beside
the weights. ``benchmark/harness/oracle_qwen3next.py`` is a copy of
this file: the benchmark's cell compares the served lane with it on the
chip.

The layers, from the published ``config.json`` and the catalog's
description of the family (layer ``i`` of 48: attention where ``(i + 1)
% full_attention_interval == 0``, Gated DeltaNet otherwise; ``rms0(x;
w) = x / sqrt(mean(x^2) + eps) * (1 + w)``)::

    h  = rms0(x; w_in)
    x  = x + mixer_i(h)
    h2 = rms0(x; w_post)
    p  = softmax(W_router h2) [512], float32; e_1..e_10 its 10 largest
    w_j = p[e_j] / sum_j p[e_j]
    x  = x + sum_j w_j expert_{e_j}(h2) + sigmoid(w_sg . h2) expert_shared(h2)
         expert(h) = W_down (silu(W_gate h) * W_up h)

Gated DeltaNet mixer (16 key heads, 32 value heads of 128; value head
``j`` reads key head ``j // 2``)::

    [q | k | v | z] = W_qkvz h ;  [b | a] = W_ba h
    [q | k | v] = silu(conv4(q | k | v))     causal, depthwise, no bias
    beta = sigmoid(b) ;  g = -exp(A_log) softplus(a + dt_bias)
    q = q / ||q|| / sqrt(128) ;  k = k / ||k||
    S = exp(g_t) S ;  d_t = beta_t (v_t - S^T k_t)
    S = S + k_t d_t^T ;  o_t = S^T q_t
    y = W_out (rmsnorm(o_t; w_n) * silu(z_t))

Gated attention mixer (16 heads of 256 on 2 key/value heads)::

    [qq | gate] = W_q h (a head: 256 + 256) ;  k = W_k h ;  v = W_v h
    qq = rms0(qq; w_qn), k = rms0(k; w_kn) ; the first 64 of 256 rotated
    a  = softmax(qq k^T / 16) v  causal ;  y = W_o (a * sigmoid(gate))

then the final ``rms0`` and the untied output table.

Departures from the published model, each ASSUMED (the catalog's
``config`` names only the sizes; the configuration's file lists them
with their reasons): the layout of ``W_qkvz`` / ``W_ba`` (``q | k | v |
z`` and ``b | a``, whole); zero-centred norm weights and the plain one
of the gated norm; the L2 norms (``x / sqrt(sum x^2 + 1e-6)``) and the
``1 / sqrt(128)`` on ``q``; the state in float32; no multi-token
prediction module; item ids as tokens; the experts this chip holds
(``first .. first + held`` of the router's outputs: a pick held
elsewhere adds nothing, here as in the program).

Controls and planted faults (what the benchmark's comparison must
catch; DATA of the jitted calls, :func:`knobs_of`, so one compiled
program serves the sound pass and every control): ``state_bf16`` (the
state rounded to bfloat16 after every position), ``no_decay`` (``g =
0``), ``beta_one``, ``tail_dropped`` (the convolution reads zeros
across every ``tail_chunk``-th position: a tail dropped between
chunks), ``no_attn_gate``, ``no_shared_gate``, ``rope_all`` (all 256
values of a head rotated), ``attn_block_lost`` (rows past the first
``LOST_BLOCK`` positions, or the history's first quarter if that is
shorter, do not see them: a block missing from a table),
``deep_no_decay`` (``no_decay`` in every DeltaNet layer but the first),
``no_routed`` (the routed experts' sum left out) and
``final_norm_plain`` (the final norm's weight taken plain, not ``1 +
w``).

What ONE layer does with given inputs and a given memory, for a check
that holds a lane to the reference LOCALLY (nothing upstream in the
comparison): :func:`cache_rows`, :func:`attn_local` (an attention
mixer's output over given key and value rows), :func:`gdn_local` (a
DeltaNet mixer's output, state and tail from a given state and tail
on), :func:`moe_local` (the expert layer with given picks) and
:func:`head_local` (the final norm and the output table).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
L2_EPS = 1e-6
CONTROLS = ("state_bf16", "no_decay", "beta_one", "tail_dropped",
            "no_attn_gate", "no_shared_gate", "rope_all", "attn_block_lost",
            "deep_no_decay", "no_routed", "final_norm_plain")
LOST_BLOCK = 256        # positions ``attn_block_lost`` hides
CFG_KEYS = ("n_heads", "n_kv", "head_dim", "rot_dim", "k_heads", "v_heads",
            "k_dim", "v_dim", "conv", "per_token", "first", "norm_eps",
            "rope_theta")


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_f32(a), _f32(b), precision=HIGHEST)


def _bf16(x):
    """``x`` rounded to bfloat16 by an operation the compiler may not
    elide (a pair of casts it may, with excess precision allowed)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rms0(x, w, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def rms_plain(x, w, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def rope(x, pos, theta: float, rot: int):
    """Half-split rotation of the first ``rot`` values of ``x: [T,
    heads, d]`` at ``pos: [T]``: ``(x1, x2) -> (x1 cos - x2 sin, x2 cos
    + x1 sin)`` over the pairs ``(i, i + rot / 2)``, the angle of pair
    ``i`` ``pos * theta^(-i / (rot / 2))``; the rest passes."""
    half = rot // 2
    # the frequencies on the host, in float64 (the chip's float32 power
    # is a few ulp off, which long positions turn into a thousandth of
    # the rotated row: my chip run, PR 39)
    inv = jnp.asarray(1.0 / theta ** (np.arange(half) / half), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def knobs_of(control: Optional[str] = None, tail_chunk: int = 0
             ) -> np.ndarray:
    """The controls as data, in ``CONTROLS``' order (``tail_dropped``:
    the chunk a dropped tail is dropped at)."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}: one of {CONTROLS}")
    k = np.zeros(len(CONTROLS), np.int32)
    if control is not None:
        k[CONTROLS.index(control)] = int(tail_chunk) \
            if control == "tail_dropped" else 1
    return k


# -- a Gated DeltaNet layer, ``s_block`` rows at a time ----------------------------

def gdn_block(p: Mapping[str, Any], x, S, tail, o_buf, pos0, lo, hi, knobs,
              *, cfg: Mapping[str, Any]):
    """Rows ``pos0 .. pos0 + R`` of the history (``x: [R, D]``, the
    residual stream) through the mixer, the recurrence advanced over
    the rows ``lo <= t < hi`` of the block only, from state ``S [VH, dk,
    dv]`` on; ``tail [K - 1, C]``: the convolution's inputs of the ``K -
    1`` positions before the block; ``o_buf [R, VH, dv]``: the rule's
    outputs of the rows advanced by earlier calls. Returns the state
    after row ``hi - 1``, the tail after it, ``o_buf`` with the rows
    advanced here, and the mixer's output ``[R, D]`` (right for the
    rows advanced so far)."""
    R = x.shape[0]
    KH, VH, dk, dv = cfg["k_heads"], cfg["v_heads"], cfg["k_dim"], \
        cfg["v_dim"]
    K, C = cfg["conv"], 2 * KH * dk + VH * dv
    state_bf16, no_decay, beta_one, drop = (knobs[i] for i in range(4))
    h = rms0(x, p["ln1_g"], cfg["norm_eps"])
    qkvz = _mm(h, p["w_qkvz"])
    ba = _mm(h, p["w_ba"])
    mixed, z = qkvz[:, :C], qkvz[:, C:].reshape(R, VH, dv)
    beta = jnp.where(beta_one > 0, 1.0, jax.nn.sigmoid(ba[:, :VH]))
    g = -jnp.exp(_f32(p["a_log"])) * jax.nn.softplus(
        ba[:, VH:] + _f32(p["dt_bias"]))
    g = jnp.where(no_decay > 0, 0.0, g)
    xp = jnp.concatenate([_f32(tail), mixed], axis=0)       # [R + K - 1, C]
    at = pos0 + jnp.arange(R)
    w = _f32(p["conv"])
    y = jnp.zeros((R, C), jnp.float32)
    for j in range(K):
        src = at - (K - 1) + j          # the position tap j reads
        # the control: a tap that reaches across a chunk's border reads 0
        cut = (drop > 0) & (src // jnp.maximum(drop, 1)
                            < at // jnp.maximum(drop, 1))
        y = y + w[j] * jnp.where(cut[:, None], 0.0, xp[j:j + R])
    y = jax.nn.silu(y)
    q = l2(y[:, :KH * dk].reshape(R, KH, dk)) * (dk ** -0.5)
    k = l2(y[:, KH * dk:2 * KH * dk].reshape(R, KH, dk))
    v = y[:, 2 * KH * dk:].reshape(R, VH, dv)
    rep = VH // KH
    # (what needs no state is made before the loop, a head a value head)
    q, k = jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1)
    decay = jnp.exp(g)

    def step(t, carry):
        S, o_buf = carry
        S = S * decay[t][:, None, None]
        d = beta[t][:, None] * (v[t] - jnp.sum(S * k[t][:, :, None], axis=1))
        S = S + k[t][:, :, None] * d[:, None, :]
        S = jnp.where(state_bf16 > 0, _bf16(S), S)
        o = jnp.sum(S * q[t][:, :, None], axis=1)            # [VH, dv]
        return S, jax.lax.dynamic_update_slice_in_dim(o_buf, o[None], t, 0)

    S, o_buf = jax.lax.fori_loop(lo, hi, step, (S, o_buf))
    out = rms_plain(o_buf, p["gn_g"], cfg["norm_eps"]) * jax.nn.silu(z)
    return S, jax.lax.dynamic_slice_in_dim(xp, hi, K - 1, axis=0), o_buf, \
        _mm(out.reshape(R, VH * dv), p["w_out"])


def attn_layer(p: Mapping[str, Any], x, knobs, *, cfg: Mapping[str, Any],
               q_block: int):
    """The gated attention mixer over the whole (padded) history ``x:
    [T, D]``, dense and causal, a block of queries at a time. Returns
    its output ``[T, D]`` and every position's key and value rows ``[T,
    KV x d]``."""
    T, D = x.shape
    H, KV, d, rot = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"], \
        cfg["rot_dim"]
    G = H // KV
    no_gate, rope_all, lost = knobs[4], knobs[6], knobs[7]
    pos = jnp.arange(T, dtype=jnp.int32)
    theta = cfg["rope_theta"]

    def turn(a, at):
        return jnp.where(rope_all > 0, rope(a, at, theta, d),
                         rope(a, at, theta, rot))

    h = rms0(x, p["ln1_g"], cfg["norm_eps"])
    k = turn(rms0(_mm(h, p["wk"]).reshape(T, KV, d), p["kn_g"],
                  cfg["norm_eps"]), pos)
    v = _mm(h, p["wv"]).reshape(T, KV, d)

    def block(args):
        h_b, pos_b = args
        qg = _mm(h_b, p["wq"]).reshape(-1, H, 2 * d)
        q = turn(rms0(qg[..., :d], p["qn_g"], cfg["norm_eps"]), pos_b)
        gate = jnp.where(no_gate > 0, 1.0, jax.nn.sigmoid(qg[..., d:]))
        s = jnp.einsum("qkgd,skd->kgqs", q.reshape(-1, KV, G, d), k,
                       precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        ok = (pos[None, :] <= pos_b[:, None]) & ~(
            (pos[None, :] < lost) & (pos_b[:, None] >= lost))
        a = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", a, v, precision=HIGHEST)
        return _mm((o.reshape(-1, H, d) * gate).reshape(-1, H * d), p["wo"])

    y = jax.lax.map(block, (h.reshape(T // q_block, q_block, D),
                            pos.reshape(T // q_block, q_block)))
    return y.reshape(T, D), k.reshape(T, KV * d), v.reshape(T, KV * d)


def route(p: Mapping[str, Any], x, at, given, given_ok, *,
          cfg: Mapping[str, Any]):
    """The router over ``x: [T, D]`` (the stream after the mixer): its
    input ``h2``, the logits, the picks (``given [A, k]`` under
    ``given_ok [A]`` taken at the positions ``at`` in place of its own)
    and their renormalised weights."""
    h2 = rms0(x, p["ln2_g"], cfg["norm_eps"])
    logits = jnp.matmul(h2, _f32(p["router"]), precision=HIGHEST)
    _, picks = jax.lax.top_k(logits, cfg["per_token"])
    picks = picks.astype(jnp.int32).at[at].set(
        jnp.where(given_ok[:, None], given, picks[at]))
    probs = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), picks,
                                axis=-1)
    return h2, logits, picks, probs / jnp.sum(probs, axis=-1, keepdims=True)


def experts(p: Mapping[str, Any], x, h2, picks, weights, knobs, *,
            cfg: Mapping[str, Any], cap: int):
    """``x`` plus the expert layer on ``h2``: every HELD expert on the
    (at most ``cap``) tokens that picked it, weighted, and the shared
    expert on every token, scaled by the sigmoid of its gate."""
    T, k = picks.shape
    held = p["we_gate"].shape[0]
    local = picks.reshape(-1) - cfg["first"]
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)
    tok, wt = order // k, weights.reshape(-1)[order]
    counts = jnp.bincount(key, length=held + 1)[:held]
    starts = jnp.cumsum(counts) - counts

    def one(y, e):
        idx = jnp.minimum(starts[e] + jnp.arange(cap), T * k - 1)
        ok = jnp.arange(cap) < counts[e]
        rows = tok[idx]
        hin = h2[rows]
        out = _mm(jax.nn.silu(_mm(hin, p["we_gate"][e]))
                  * _mm(hin, p["we_up"][e]), p["we_down"][e])
        return y.at[rows].add(out * jnp.where(ok, wt[idx], 0.0)[:, None]), \
            None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    y = jnp.where(knobs[9] > 0, 0.0, y)
    sg = jax.nn.sigmoid(jnp.matmul(h2, _f32(p["sg"]), precision=HIGHEST))
    sg = jnp.where(knobs[5] > 0, 1.0, sg)
    shared = _mm(jax.nn.silu(_mm(h2, p["ws_gate"])) * _mm(h2, p["ws_up"]),
                 p["ws_down"])
    return x + y + sg * shared, sg[:, 0]


@functools.lru_cache(maxsize=16)
def _jit(name: str, cfg_items, **static):
    fn = {"gdn_block": gdn_block, "attn_layer": attn_layer, "route": route,
          "experts": experts}[name]
    donate = {"gdn_block": (4,)}.get(name, ())
    return jax.jit(functools.partial(fn, cfg=dict(cfg_items), **static),
                   donate_argnums=donate)


@functools.partial(jax.jit, static_argnums=(2,))
def _rows_of(x, start, n: int):
    return jax.lax.dynamic_slice_in_dim(x, start, n, axis=0)


def layer_params(theta: Mapping[str, Any], i: int) -> Dict[str, Any]:
    pre = f"l{i}_"
    return {k[len(pre):]: v for k, v in theta.items() if k.startswith(pre)}


def _cfg_key(cfg: Mapping[str, Any]):
    return tuple(sorted((k, cfg[k]) for k in CFG_KEYS))


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def is_full(cfg: Mapping[str, Any], i: int) -> bool:
    return (i + 1) % int(cfg["interval"]) == 0


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


def _rows(g, wq, wk, wv, kn, x_in, pos, knobs, *, cfg):
    H, d = cfg["n_heads"], cfg["head_dim"]
    h = rms0(_f32(x_in), g, cfg["norm_eps"])
    k = rms0(_mm(h, wk).reshape(-1, cfg["n_kv"], d), kn, cfg["norm_eps"])
    k = jnp.where(knobs[6] > 0, rope(k, pos, cfg["rope_theta"], d),
                  rope(k, pos, cfg["rope_theta"], cfg["rot_dim"]))
    og = jax.nn.sigmoid(_mm(h, wq).reshape(-1, H, 2 * d)[..., d:])
    og = jnp.where(knobs[4] > 0, 1.0, og)
    return jnp.concatenate([k.reshape(len(pos), -1), _mm(h, wv)],
                           axis=-1), og.reshape(len(pos), H * d)


@functools.lru_cache(maxsize=8)
def _rows_jit(cfg_items):
    return jax.jit(functools.partial(_rows, cfg=dict(cfg_items)))


def cache_rows(theta: Mapping[str, Any], cfg: Mapping[str, Any], i: int,
               x_in, pos: Sequence[int], control: Optional[str] = None):
    """What attention layer ``i`` computes from inputs ``x_in [n, D]`` at
    positions ``pos`` alone: the key and value rows it writes (``[n, 2
    x kv_width]``) and the factor its attention's output is multiplied
    by, the sigmoid of its gate (``[n, H x d]``): what a check holds
    the lane's written rows and its factor against, from the lane's
    OWN input."""
    with jax.default_matmul_precision("highest"):
        return _rows_jit(_cfg_key(cfg))(
            theta[f"l{i}_ln1_g"], theta[f"l{i}_wq"], theta[f"l{i}_wk"],
            theta[f"l{i}_wv"], theta[f"l{i}_kn_g"],
            jnp.asarray(x_in, jnp.float32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(knobs_of(control)))


def _attend(g, wq, wo, qn, x_in, pos, K, V, *, cfg):
    H, KV, d = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"]
    S = K.shape[0]
    h = rms0(_f32(x_in), g, cfg["norm_eps"])
    qg = _mm(h, wq).reshape(-1, H, 2 * d)
    q = rope(rms0(qg[..., :d], qn, cfg["norm_eps"]), pos,
             cfg["rope_theta"], cfg["rot_dim"])
    s = jnp.einsum("qkgd,skd->kgqs", q.reshape(-1, KV, H // KV, d),
                   _f32(K).reshape(S, KV, d), precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(d))
    ok = jnp.arange(S)[None, :] <= pos[:, None]
    a = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", a, _f32(V).reshape(S, KV, d),
                   precision=HIGHEST)
    return _mm((o.reshape(-1, H, d) * jax.nn.sigmoid(qg[..., d:])).reshape(
        -1, H * d), wo)


@functools.lru_cache(maxsize=8)
def _attend_jit(cfg_items):
    return jax.jit(functools.partial(_attend, cfg=dict(cfg_items)))


def attn_local(theta: Mapping[str, Any], cfg: Mapping[str, Any], i: int,
               x_in, pos: Sequence[int], K, V):
    """Attention layer ``i``'s mixer output ``[n, D]`` for inputs ``x_in
    [n, D]`` at positions ``pos`` over GIVEN key and value rows ``K`` /
    ``V`` ``[S, kv_width]`` (row ``s``: position ``s``'s, a row's own
    included; a row sees the positions up to its own): the projections,
    QK norms, rotation, the softmax over the rows, the output's gate
    and the output projection, dense."""
    with jax.default_matmul_precision("highest"):
        return _attend_jit(_cfg_key(cfg))(
            theta[f"l{i}_ln1_g"], theta[f"l{i}_wq"], theta[f"l{i}_wo"],
            theta[f"l{i}_qn_g"], jnp.asarray(x_in, jnp.float32),
            jnp.asarray(pos, jnp.int32), K, V)


def gdn_local(theta: Mapping[str, Any], cfg: Mapping[str, Any], i: int,
              x_in, state, tail, pos0: int = 0):
    """DeltaNet layer ``i``'s mixer over the rows ``x_in [n, D]`` (at
    positions ``pos0 ..``) from a GIVEN ``state [VH, dk, dv]`` and
    ``tail [K - 1, C]`` on, the recurrence one row at a time: its
    output ``[n, D]``, the state and the tail after the rows."""
    n = len(x_in)
    R = _up(n, 8)
    x = jnp.zeros((R, x_in.shape[-1]), jnp.float32).at[:n].set(
        jnp.asarray(x_in, jnp.float32))
    VH, dv = cfg["v_heads"], cfg["v_dim"]
    with jax.default_matmul_precision("highest"):
        S, tail, _, y = _jit("gdn_block", _cfg_key(cfg))(
            layer_params(theta, i), x, jnp.asarray(state, jnp.float32),
            jnp.asarray(tail, jnp.float32),
            jnp.zeros((R, VH, dv), jnp.float32), int(pos0), 0, n,
            jnp.asarray(knobs_of()))
    return np.asarray(y[:n]), np.asarray(S), np.asarray(tail)


def moe_local(theta: Mapping[str, Any], cfg: Mapping[str, Any], i: int,
              x_mid, picks):
    """Layer ``i``'s expert layer on the rows ``x_mid [n, D]`` (the
    stream behind the mixer) with GIVEN picks ``[n, k]``: what it adds
    to the stream, ``[n, D]`` (the held experts picked, weighted by the
    router's own renormalised softmax, and the gated shared expert)."""
    n = len(x_mid)
    R = _up(n, 8)
    x = jnp.zeros((R, x_mid.shape[-1]), jnp.float32).at[:n].set(
        jnp.asarray(x_mid, jnp.float32))
    given = jnp.zeros((R, int(cfg["per_token"])), jnp.int32).at[:n].set(
        jnp.asarray(picks, jnp.int32))
    p, key = layer_params(theta, i), _cfg_key(cfg)
    with jax.default_matmul_precision("highest"):
        h2, _, took, weights = _jit("route", key)(
            p, x, jnp.arange(R), given, jnp.arange(R) < n)
        out, _ = _jit("experts", key, cap=R)(
            p, x, h2, took, weights, jnp.asarray(knobs_of()))
    return np.asarray(out - x)[:n]


def head_local(theta: Mapping[str, Any], cfg: Mapping[str, Any], x):
    """Every item's score ``[n, items]`` from the last layer's stream
    ``x [n, D]``: the final norm and the output table."""
    with jax.default_matmul_precision("highest"):
        hq = rms0(jnp.asarray(x, jnp.float32), theta["ln_f_g"],
                  cfg["norm_eps"])
        return np.asarray(_mm(
            hq, _f32(theta["out_emb"][:int(cfg["n_items"])]).T))


def _gdn_layer(p, x, n: int, cfg, knobs, snaps: Sequence[int], s_block: int):
    """A DeltaNet layer over the history's first ``n`` rows of ``x [T,
    D]``, ``s_block`` rows a call; ``snaps``: the positions after which
    the state and the tail are handed out. Returns the mixer's output
    ``[T, D]`` and ``{position: (state, tail)}``."""
    T = x.shape[0]
    VH, dk, dv = cfg["v_heads"], cfg["k_dim"], cfg["v_dim"]
    C = 2 * cfg["k_heads"] * dk + VH * dv
    run = _jit("gdn_block", _cfg_key(cfg))
    S = jnp.zeros((VH, dk, dv), jnp.float32)
    tail = jnp.zeros((cfg["conv"] - 1, C), jnp.float32)
    ys, kept = [], {}
    for p0 in range(0, T, s_block):
        rows = min(s_block, n - p0)
        if rows <= 0:
            ys.append(jnp.zeros((s_block, x.shape[1]), jnp.float32))
            continue
        x_b = _rows_of(x, p0, s_block)
        cuts = sorted({s - p0 + 1 for s in snaps if p0 <= s < p0 + rows}
                      | {rows})
        o_buf = jnp.zeros((s_block, VH, dv), jnp.float32)
        lo = 0
        for hi in cuts:
            S, tail_hi, o_buf, y = run(p, x_b, S, tail, o_buf, p0, lo, hi,
                                       jnp.asarray(knobs))
            if p0 + hi - 1 in snaps:
                kept[p0 + hi - 1] = (np.asarray(S), np.asarray(tail_hi))
            lo = hi
        tail = tail_hi
        ys.append(y)
    return jnp.concatenate(ys, axis=0), kept


def forward(theta: Mapping[str, Any], ids, cfg: Mapping[str, Any], *,
            at: Optional[Sequence[int]] = None,
            given: Optional[Mapping[int, Any]] = None,
            states_at: Sequence[int] = (), q_block: int = 256,
            s_block: int = 8192, control: Optional[str] = None,
            tail_chunk: int = 0, pad: int = 0, rows: bool = False
            ) -> Dict[str, Any]:
    """The whole history ``ids [n]`` through every layer. ``cfg``:
    ``n_layers``, ``interval`` (full_attention_interval), ``n_heads``,
    ``n_kv``, ``head_dim``, ``rot_dim``, ``k_heads``, ``v_heads``,
    ``k_dim``, ``v_dim``, ``conv``, ``per_token``, ``first`` (the first
    expert held), ``norm_eps``, ``rope_theta``, ``n_items``. ``at``: the
    positions to report (None: every one); ``given``: ``{position: picks
    [layers, k]}`` to take there; ``states_at``: the positions AFTER
    which every DeltaNet layer's state and tail are handed out; ``pad``:
    pad the history to this many positions at least (one compiled
    program for histories of several lengths). Returns, at the
    positions in ``at``'s order: ``scores [A, items]``, ``layers [L, A,
    D]`` (the residual stream after every layer), ``h2`` (the router's
    input), ``logits [L, A, experts]``, ``picks``, ``gates``, ``sg [L,
    A]`` (the shared expert's gate), ``k`` / ``v`` ``[attention layers,
    A, kv_width]``, ``og`` ``[attention layers, A, H x d]`` (the factor
    on the attention's output), ``mid [L, A, D]`` (the stream behind
    every layer's mixer), with ``rows`` also ``k_all`` / ``v_all``
    ``[attention layers, n, kv_width]`` (every position's rows),
    ``cuts`` (per position the worst layer's
    :func:`router_cuts`) and ``states``: ``{position: {"state":
    [DeltaNet layers, VH, dk, dv], "tail": [DeltaNet layers, K - 1,
    C]}}``."""
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    at = list(range(n)) if at is None else [int(p) for p in at]
    given = given or {}
    snaps = sorted({int(s) for s in states_at})
    L, k_top = int(cfg["n_layers"]), int(cfg["per_token"])
    q_block = min(q_block, _up(n, 8))
    s_block = _up(min(s_block, _up(n, q_block)), q_block)
    T = _up(max(n, int(pad)), s_block)
    knobs = knobs_of(control, tail_chunk)
    knobs[7] *= min(LOST_BLOCK, max(1, n // 4))
    a_pos = jnp.asarray(at, jnp.int32)
    key = _cfg_key(cfg)
    g_ok = jnp.asarray([p in given for p in at])
    kept: Dict[str, list] = {k: [] for k in (
        "x", "h2", "logits", "picks", "gates", "sg", "k", "v", "og", "mid",
        "k_all", "v_all")}
    states = {s: {"state": [], "tail": []} for s in snaps}
    with jax.default_matmul_precision("highest"):
        x = jnp.zeros((T, theta["item_emb"].shape[1]), jnp.float32).at[
            :n].set(_f32(jnp.take(theta["item_emb"], jnp.asarray(ids),
                                  axis=0)))
        for i in range(L):
            p = layer_params(theta, i)
            if is_full(cfg, i):
                y, k_rows, v_rows = _jit("attn_layer", key, q_block=q_block)(
                    p, x, jnp.asarray(knobs))
                kept["k"].append(k_rows[a_pos])
                kept["v"].append(v_rows[a_pos])
                if rows:
                    kept["k_all"].append(k_rows[:n])
                    kept["v_all"].append(v_rows[:n])
                kept["og"].append(cache_rows(theta, cfg, i, x[a_pos], at,
                                             control)[1])
            else:
                # (``deep_no_decay``: ``no_decay`` behind the first)
                deep = knobs.copy()
                deep[1] |= int(knobs[8] and i > 0)
                y, got = _gdn_layer(p, x, n, cfg, deep, snaps, s_block)
                for s in snaps:
                    states[s]["state"].append(got[s][0])
                    states[s]["tail"].append(got[s][1])
            x = x + y
            kept["mid"].append(x[a_pos])
            g_picks = jnp.asarray(np.stack(
                [np.asarray(given[q])[i] if q in given
                 else np.zeros(k_top, np.int32) for q in at]), jnp.int32)
            h2, logits, picks, weights = _jit("route", key)(
                p, x, a_pos, g_picks, g_ok)
            held = p["we_gate"].shape[0]
            local = np.asarray(picks).reshape(-1) - int(cfg["first"])
            most = int(np.bincount(local[(local >= 0) & (local < held)],
                                   minlength=1).max())
            cap = max(8, 1 << int(np.ceil(np.log2(max(most, 1)))))
            x, sg = _jit("experts", key, cap=cap)(
                p, x, h2, picks, weights, jnp.asarray(knobs))
            for name, val in (("x", x), ("h2", h2), ("logits", logits),
                              ("picks", picks), ("gates", weights),
                              ("sg", sg)):
                kept[name].append(val[a_pos])
        hq = (rms_plain if knobs[10] else rms0)(
            x[a_pos], theta["ln_f_g"], cfg["norm_eps"])
        scores = _mm(hq, _f32(theta["out_emb"][:int(cfg["n_items"])]).T)
    out = {k: np.asarray(jnp.stack(v)) for k, v in kept.items() if v}
    cuts = {}
    for j, q in enumerate(at):
        worst = {"router_low": 0.0, "router_out": 0.0}
        for i in range(L):
            c = router_cuts(out["logits"][i, j], out["picks"][i, j], k_top)
            worst = {k: max(worst[k], c[k]) for k in worst}
        cuts[q] = worst
    return {"scores": np.asarray(scores), "layers": out["x"],
            "h2": out["h2"], "logits": out["logits"], "picks": out["picks"],
            "gates": out["gates"], "sg": out["sg"], "k": out.get("k"),
            "v": out.get("v"), "og": out.get("og"), "mid": out["mid"],
            "k_all": out.get("k_all"), "v_all": out.get("v_all"),
            "cuts": cuts,
            "states": {s: {k: np.stack(v) for k, v in d.items()}
                       for s, d in states.items()}}
