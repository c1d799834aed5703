"""Plain float32 reference of Falcon-H1-34B-Instruct's block
(``model_type: falcon_h1``) as the sequence lane serves it: the full
forward pass over ONE user's whole history. ``jax.numpy`` only, every
product at ``jax.default_matmul_precision("highest")``; no cache, no
kernel, no batching and NO CHUNKED FORM: the Mamba-2 heads advance
their state by the recurrence ONE POSITION AT A TIME (a ``lax`` loop
with bounds given as data, so that the state can be handed out at any
position without a second pass), attention materialises its masked
scores (a block of queries at a time). A layer is a few jitted calls
with fixed shapes (the history padded to whole blocks), because
op-by-op execution compiles every distinct shape of every operation;
the Mamba-2 branch takes the history ``s_block`` rows at a time so that
its projection fits beside the weights, the SwiGLU ``q_block`` rows at
a time. ``benchmark/harness/oracle_falconh1.py`` is a copy of this
file: the benchmark's cell compares the served lane with it on the
chip.

The layer, from the published ``config.json`` (72 identical layers,
hidden 5120; ``rms(x; w) = x / sqrt(mean(x^2) + 1e-5) * w``)::

    h   = rms(x; w_in)
    att = W_o softmax(q k^T / sqrt(128)) v * attention_out_multiplier
          q = rope(W_q a), k = rope(W_k a * key_multiplier), v = W_v a,
          a = h * attention_in_multiplier; 20 heads on 4 key/value
          heads of 128; rotate-half over the whole head, theta 1e11
    [z | xBC | dt] = W_in (h * ssm_in_multiplier) * m
          W_in: 5120 -> 4096 + (4096 + 512 + 512) + 32; m scales the z,
          x, B, C and dt slices by ssm_multipliers[0..4]
    xBC = silu(conv4(xBC) + b)                causal, depthwise, with bias
    dt  = softplus(dt + dt_bias) ;  A = -exp(A_log)        a head of 32
    S   = exp(dt A) S + dt x (x) B ;  y = S C + D x         a head, a step
          (head j reads group j // 16 of the 2 groups of B and C)
    ssm = W_out (rms_group(y * silu(z)) * w_n) * ssm_out_multiplier
          (mamba_norm_before_gate false; groups of 4096 / 2)
    x   = x + att + ssm                      ONE residual add for both
    h2  = rms(x; w_post)
    x   = x + W_down(silu(W_gate h2 * mlp_multipliers[0]) * W_up h2)
              * mlp_multipliers[1]

in: ``item_emb[tok] * embedding_multiplier``; out: the final ``rms``
and ``W_head h * lm_head_multiplier``, untied tables.

Departures from the published model and ASSUMED choices (the catalog's
``config`` names sizes and multipliers only; the configuration's file
lists them with their reasons): the layout of ``W_in``'s outputs (``z |
x | B | C | dt``, whole) and which slice each of the five
``ssm_multipliers`` scales (in that order); the gated norm's grouping
(an RMS norm over each of ``mamba_n_groups`` groups of ``d_ssm /
n_groups`` values, one weight a value, AFTER the gate); ``D`` a head,
drawn 1; ``A_log = log(A)``, ``A`` uniform on [1, 16); ``dt_bias`` the
inverse softplus of a step log-uniform on [0.001, 0.1]; the
convolution's bias normal with deviation 0.29 (the deviation of
PyTorch's default for a kernel of 4); no clamp on ``dt``; the state in
float32; item ids as tokens; the vocabulary this chip holds (a slice of
both tables: scores are over the held rows).

Controls and planted faults (what the benchmark's comparison must
catch; DATA of the jitted calls, :func:`knobs_of`, so one compiled
program serves the sound pass and every control): ``state_bf16`` (the
state rounded to bfloat16 after every position), ``no_d_skip`` (``D x``
left out), ``no_conv_bias``, ``no_key_mult`` (``key_multiplier`` left
out), ``no_ssm_out_mult``, ``no_attn_out_mult``, ``norm_before_gate``
(the gated norm before the gate), ``stale_tail`` (from position
``stale_at`` on, a tap of the convolution that reaches before it reads
the input ONE position older: the last three inputs not shifted by the
event before), ``slot_ahead`` (the states handed out are those one
position LATER than asked: a slot advanced without its length) and
``no_head_mult`` (``lm_head_multiplier`` left out of the scores).

What ONE layer does with given inputs and a given memory, for a check
that holds a lane to the reference LOCALLY (nothing upstream in the
comparison): :func:`cache_rows` (the key and value rows a layer
writes), :func:`attn_local` (the attention branch's output over given
key and value rows), :func:`ssm_local` (the Mamba-2 branch's output,
state and tail from a given state and tail on) and :func:`head_local`.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
CONTROLS = ("state_bf16", "no_d_skip", "no_conv_bias", "no_key_mult",
            "no_ssm_out_mult", "no_attn_out_mult", "norm_before_gate",
            "stale_tail", "slot_ahead", "no_head_mult")
CFG_KEYS = ("n_heads", "n_kv", "head_dim", "ssm_heads", "ssm_head_dim",
            "d_state", "n_groups", "conv", "norm_eps", "rope_theta",
            "attn_in", "attn_out", "key_mult", "ssm_in", "ssm_mults",
            "ssm_out", "mlp_mults")


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_f32(a), _f32(b), precision=HIGHEST)


def _bf16(x):
    """``x`` rounded to bfloat16 by an operation the compiler may not
    elide (a pair of casts it may, with excess precision allowed)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rms(x, w, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def rope(x, pos, theta: float):
    """Rotate-half rotation of the whole head: ``x [T, heads, d]`` at
    ``pos [T]``: ``(x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)`` over
    the pairs ``(i, i + d / 2)``, the angle of pair ``i`` ``pos *
    theta^(-i / (d / 2))`` (the frequencies in float64 on the host)."""
    half = x.shape[-1] // 2
    inv = jnp.asarray(1.0 / theta ** (np.arange(half) / half), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def knobs_of(control: Optional[str] = None, stale_at: int = 0) -> np.ndarray:
    """The controls as data, in ``CONTROLS``' order (``stale_tail``:
    the position the stale tail is read from)."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}: one of {CONTROLS}")
    k = np.zeros(len(CONTROLS), np.int32)
    if control is not None:
        k[CONTROLS.index(control)] = max(int(stale_at), 1) \
            if control == "stale_tail" else 1
    return k


def sizes(cfg: Mapping[str, Any]):
    """``(d_ssm, conv channels, group width of B | C)``."""
    d_ssm = cfg["ssm_heads"] * cfg["ssm_head_dim"]
    gs = cfg["n_groups"] * cfg["d_state"]
    return d_ssm, d_ssm + 2 * gs, gs


def mup_vector(cfg: Mapping[str, Any]):
    d_ssm, _, gs = sizes(cfg)
    return jnp.asarray(np.repeat(
        np.asarray(cfg["ssm_mults"], np.float32),
        [d_ssm, d_ssm, gs, gs, cfg["ssm_heads"]]))


# -- the Mamba-2 branch, ``s_block`` rows at a time ---------------------------------

def ssm_block(p: Mapping[str, Any], x, S, tail, y_buf, pos0, lo, hi, knobs,
              *, cfg: Mapping[str, Any]):
    """Rows ``pos0 .. pos0 + R`` of the history (``x: [R, D]``, the
    residual stream) through the Mamba-2 branch, the recurrence
    advanced over the rows ``lo <= t < hi`` of the block only, from
    state ``S [heads, P, N]`` on; ``tail [K, C]``: the convolution's
    inputs of the ``K`` positions before the block (one more than the
    kernel reads, for the ``stale_tail`` control); ``y_buf [R, heads,
    P]``: the scan's outputs of the rows advanced by earlier calls.
    Returns the state after row ``hi - 1``, the tail after it, ``y_buf``
    with the rows advanced here, and the branch's output ``[R, D]``
    (right for the rows advanced so far)."""
    R = x.shape[0]
    MH, P, N, G = cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["d_state"], \
        cfg["n_groups"]
    K = cfg["conv"]
    d_ssm, C, gs = sizes(cfg)
    state_bf16, no_d, no_bias = knobs[0], knobs[1], knobs[2]
    no_out, gate_last, stale = knobs[4], knobs[6], knobs[7]
    h = rms(x, p["ln1_g"], cfg["norm_eps"])
    zxbcdt = _mm(h * cfg["ssm_in"], p["w_in"]) * mup_vector(cfg)
    z, mixed = zxbcdt[:, :d_ssm], zxbcdt[:, d_ssm:d_ssm + C]
    dt = jax.nn.softplus(zxbcdt[:, d_ssm + C:] + _f32(p["dt_bias"]))
    A = -jnp.exp(_f32(p["a_log"]))
    xp = jnp.concatenate([_f32(tail), mixed], axis=0)       # [K + R, C]
    at = pos0 + jnp.arange(R)
    w = _f32(p["conv"])
    y = jnp.zeros((R, C), jnp.float32)
    for j in range(K):
        src = at - (K - 1) + j          # the position tap j reads
        # the control: a tap that reaches before ``stale`` from a row at
        # or behind it reads the input one position older
        old = (stale > 0) & (src < stale) & (at >= stale)
        y = y + w[j] * jnp.where(old[:, None], xp[j:j + R],
                                 xp[j + 1:j + 1 + R])
    y = y + jnp.where(no_bias > 0, 0.0, _f32(p["conv_b"]))
    y = jax.nn.silu(y)
    xs = y[:, :d_ssm].reshape(R, MH, P)
    rep = MH // G
    Bm = jnp.repeat(y[:, d_ssm:d_ssm + gs].reshape(R, G, N), rep, axis=1)
    Cm = jnp.repeat(y[:, d_ssm + gs:].reshape(R, G, N), rep, axis=1)
    decay = jnp.exp(dt * A)

    def step(t, carry):
        S, y_buf = carry
        S = S * decay[t][:, None, None] \
            + (dt[t][:, None] * xs[t])[:, :, None] * Bm[t][:, None, :]
        S = jnp.where(state_bf16 > 0, _bf16(S), S)
        o = jnp.sum(S * Cm[t][:, None, :], axis=-1)          # [heads, P]
        return S, jax.lax.dynamic_update_slice_in_dim(y_buf, o[None], t, 0)

    S, y_buf = jax.lax.fori_loop(lo, hi, step, (S, y_buf))
    o = y_buf + jnp.where(no_d > 0, 0.0, _f32(p["d_skip"]))[None, :, None] \
        * xs
    o = o.reshape(R, d_ssm)

    def norm(a):
        g = a.reshape(R, G, -1)
        return (g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                             + cfg["norm_eps"])).reshape(R, -1) \
            * _f32(p["gn_g"])

    gate = jax.nn.silu(z)
    o = jnp.where(gate_last > 0, norm(o) * gate, norm(o * gate))
    out = _mm(o, p["w_out"]) * jnp.where(no_out > 0, 1.0, cfg["ssm_out"])
    return S, jax.lax.dynamic_slice_in_dim(xp, hi, K, axis=0), y_buf, out


def attn_layer(p: Mapping[str, Any], x, knobs, *, cfg: Mapping[str, Any],
               q_block: int):
    """The attention branch over the whole (padded) history ``x: [T,
    D]``, dense and causal, a block of queries at a time. Returns its
    output ``[T, D]`` and every position's key and value rows ``[T, KV
    x d]``."""
    T, D = x.shape
    H, KV, d = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"]
    G = H // KV
    pos = jnp.arange(T, dtype=jnp.int32)
    theta = cfg["rope_theta"]
    kmul = jnp.where(knobs[3] > 0, 1.0, cfg["key_mult"])
    omul = jnp.where(knobs[5] > 0, 1.0, cfg["attn_out"])
    a = rms(x, p["ln1_g"], cfg["norm_eps"]) * cfg["attn_in"]
    k = rope(_mm(a, p["wk"]).reshape(T, KV, d) * kmul, pos, theta)
    v = _mm(a, p["wv"]).reshape(T, KV, d)
    wq, wo = _f32(p["wq"]), _f32(p["wo"])

    def block(args):
        a_b, pos_b = args
        q = rope(_mm(a_b, wq).reshape(-1, H, d), pos_b, theta)
        s = jnp.einsum("qkgd,skd->kgqs", q.reshape(-1, KV, G, d), k,
                       precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        ok = pos[None, :] <= pos_b[:, None]
        w = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", w, v, precision=HIGHEST)
        return _mm(o.reshape(-1, H * d), wo) * omul

    y = jax.lax.map(block, (a.reshape(T // q_block, q_block, D),
                            pos.reshape(T // q_block, q_block)))
    return y.reshape(T, D), k.reshape(T, KV * d), v.reshape(T, KV * d)


def mlp_layer(p: Mapping[str, Any], x, *, cfg: Mapping[str, Any],
              q_block: int):
    """``x`` plus the dense SwiGLU on ``rms(x)``, ``q_block`` rows at a
    time."""
    T, D = x.shape
    g_mult, d_mult = cfg["mlp_mults"]
    w_gate, w_up, w_down = (_f32(p[k]) for k in ("w_gate", "w_up", "w_down"))

    def block(x_b):
        h2 = rms(x_b, p["ln2_g"], cfg["norm_eps"])
        g = jax.nn.silu(_mm(h2, w_gate) * g_mult)
        return x_b + _mm(g * _mm(h2, w_up), w_down) * d_mult

    return jax.lax.map(block, x.reshape(T // q_block, q_block, D)).reshape(
        T, D)


@functools.lru_cache(maxsize=16)
def _jit(name: str, cfg_items, **static):
    fn = {"ssm_block": ssm_block, "attn_layer": attn_layer,
          "mlp_layer": mlp_layer}[name]
    donate = {"ssm_block": (4,)}.get(name, ())
    return jax.jit(functools.partial(fn, cfg=dict(cfg_items), **static),
                   donate_argnums=donate)


@functools.partial(jax.jit, static_argnums=(2,))
def _rows_of(x, start, n: int):
    return jax.lax.dynamic_slice_in_dim(x, start, n, axis=0)


def layer_params(theta: Mapping[str, Any], i: int) -> Dict[str, Any]:
    pre = f"l{i}_"
    return {k[len(pre):]: v for k, v in theta.items() if k.startswith(pre)}


def _cfg_key(cfg: Mapping[str, Any]):
    return tuple(sorted((k, tuple(cfg[k]) if isinstance(
        cfg[k], (list, tuple)) else cfg[k]) for k in CFG_KEYS))


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


# -- one layer from given inputs and a given memory ---------------------------------

def _rows(g, wk, wv, x_in, pos, knobs, *, cfg):
    a = rms(_f32(x_in), g, cfg["norm_eps"]) * cfg["attn_in"]
    kmul = jnp.where(knobs[3] > 0, 1.0, cfg["key_mult"])
    k = rope(_mm(a, wk).reshape(-1, cfg["n_kv"], cfg["head_dim"]) * kmul,
             pos, cfg["rope_theta"])
    return jnp.concatenate([k.reshape(len(pos), -1), _mm(a, wv)], axis=-1)


@functools.lru_cache(maxsize=8)
def _rows_jit(cfg_items):
    return jax.jit(functools.partial(_rows, cfg=dict(cfg_items)))


def cache_rows(theta: Mapping[str, Any], cfg: Mapping[str, Any], i: int,
               x_in, pos: Sequence[int], control: Optional[str] = None):
    """What layer ``i``'s attention branch writes for inputs ``x_in [n,
    D]`` (the residual stream) at positions ``pos``: the key and value
    rows ``[n, 2 x kv_width]``: what a check holds the lane's written
    rows against, from the lane's OWN input."""
    with jax.default_matmul_precision("highest"):
        return _rows_jit(_cfg_key(cfg))(
            theta[f"l{i}_ln1_g"], theta[f"l{i}_wk"], theta[f"l{i}_wv"],
            jnp.asarray(x_in, jnp.float32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(knobs_of(control)))


def _attend(g, wq, wo, x_in, pos, K, V, *, cfg):
    H, KV, d = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"]
    S = K.shape[0]
    a = rms(_f32(x_in), g, cfg["norm_eps"]) * cfg["attn_in"]
    q = rope(_mm(a, wq).reshape(-1, H, d), pos, cfg["rope_theta"])
    s = jnp.einsum("qkgd,skd->kgqs", q.reshape(-1, KV, H // KV, d),
                   _f32(K).reshape(S, KV, d), precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(d))
    ok = jnp.arange(S)[None, :] <= pos[:, None]
    w = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", w, _f32(V).reshape(S, KV, d),
                   precision=HIGHEST)
    return _mm(o.reshape(-1, H * d), wo) * cfg["attn_out"]


@functools.lru_cache(maxsize=8)
def _attend_jit(cfg_items):
    return jax.jit(functools.partial(_attend, cfg=dict(cfg_items)))


def attn_local(theta: Mapping[str, Any], cfg: Mapping[str, Any], i: int,
               x_in, pos: Sequence[int], K, V):
    """Layer ``i``'s attention branch's output ``[n, D]`` for inputs
    ``x_in [n, D]`` at positions ``pos`` over GIVEN key and value rows
    ``K`` / ``V`` ``[S, kv_width]`` (row ``s``: position ``s``'s, a
    row's own included; a row sees the positions up to its own): the
    query's projection and rotation, the softmax over the rows, the
    output projection and its multiplier, dense."""
    with jax.default_matmul_precision("highest"):
        return _attend_jit(_cfg_key(cfg))(
            theta[f"l{i}_ln1_g"], theta[f"l{i}_wq"], theta[f"l{i}_wo"],
            jnp.asarray(x_in, jnp.float32), jnp.asarray(pos, jnp.int32),
            K, V)


def _tail_in(tail, cfg):
    """A slot's tail ``[K - 1, C]`` as :func:`ssm_block` takes it (one
    older row, unread without the ``stale_tail`` control)."""
    tail = jnp.asarray(tail, jnp.float32)
    return jnp.concatenate([jnp.zeros_like(tail[:1]), tail], axis=0)


def ssm_local(theta: Mapping[str, Any], cfg: Mapping[str, Any], i: int,
              x_in, state, tail, pos0: int = 0):
    """Layer ``i``'s Mamba-2 branch over the rows ``x_in [n, D]`` (the
    residual stream at positions ``pos0 ..``) from a GIVEN ``state
    [heads, P, N]`` and ``tail [K - 1, C]`` on, the recurrence one row
    at a time: its output ``[n, D]``, the state and the tail after the
    rows."""
    n = len(x_in)
    R = _up(n, 8)
    x = jnp.zeros((R, x_in.shape[-1]), jnp.float32).at[:n].set(
        jnp.asarray(x_in, jnp.float32))
    MH, P = cfg["ssm_heads"], cfg["ssm_head_dim"]
    with jax.default_matmul_precision("highest"):
        S, tail, _, y = _jit("ssm_block", _cfg_key(cfg))(
            layer_params(theta, i), x, jnp.asarray(state, jnp.float32),
            _tail_in(tail, cfg), jnp.zeros((R, MH, P), jnp.float32),
            int(pos0), 0, n, jnp.asarray(knobs_of()))
    return np.asarray(y[:n]), np.asarray(S), np.asarray(tail[1:])


def head_local(theta: Mapping[str, Any], cfg: Mapping[str, Any], x):
    """Every item's score ``[n, items]`` from the last layer's stream
    ``x [n, D]``: the final norm, the output table and
    ``lm_head_multiplier``."""
    with jax.default_matmul_precision("highest"):
        hq = rms(jnp.asarray(x, jnp.float32), theta["ln_f_g"],
                 cfg["norm_eps"])
        return np.asarray(_mm(
            hq, _f32(theta["out_emb"][:int(cfg["n_items"])]).T)
            * cfg["head_mult"])


# -- the whole history ---------------------------------------------------------------

def _ssm_layer(p, x, n: int, cfg, knobs, snaps: Sequence[int], s_block: int):
    """The Mamba-2 branch over the history's first ``n`` rows of ``x
    [T, D]``, ``s_block`` rows a call; ``snaps``: the positions after
    which the state and the tail are handed out. Returns the branch's
    output ``[T, D]`` and ``{position: (state, tail)}``."""
    T = x.shape[0]
    MH, P, N = cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["d_state"]
    C = sizes(cfg)[1]
    run = _jit("ssm_block", _cfg_key(cfg))
    S = jnp.zeros((MH, P, N), jnp.float32)
    tail = jnp.zeros((cfg["conv"], C), jnp.float32)
    ys, kept = [], {}
    for p0 in range(0, T, s_block):
        rows = min(s_block, n - p0)
        if rows <= 0:
            ys.append(jnp.zeros((s_block, x.shape[1]), jnp.float32))
            continue
        x_b = _rows_of(x, p0, s_block)
        cuts = sorted({s - p0 + 1 for s in snaps if p0 <= s < p0 + rows}
                      | {rows})
        y_buf = jnp.zeros((s_block, MH, P), jnp.float32)
        lo = 0
        for hi in cuts:
            S, tail_hi, y_buf, y = run(p, x_b, S, tail, y_buf, p0, lo, hi,
                                       jnp.asarray(knobs))
            if p0 + hi - 1 in snaps:
                kept[p0 + hi - 1] = (np.asarray(S), np.asarray(tail_hi[1:]))
            lo = hi
        tail = tail_hi
        ys.append(y)
    return jnp.concatenate(ys, axis=0), kept


def forward(theta: Mapping[str, Any], ids, cfg: Mapping[str, Any], *,
            at: Optional[Sequence[int]] = None,
            states_at: Sequence[int] = (), q_block: int = 256,
            s_block: int = 4096, control: Optional[str] = None,
            stale_at: int = 0, pad: int = 0, rows: bool = False
            ) -> Dict[str, Any]:
    """The whole history ``ids [n]`` through every layer. ``cfg``:
    ``n_layers``, ``n_heads``, ``n_kv``, ``head_dim``, ``ssm_heads``,
    ``ssm_head_dim``, ``d_state``, ``n_groups``, ``conv``, ``norm_eps``,
    ``rope_theta``, ``n_items`` and the multipliers ``attn_in``,
    ``attn_out``, ``key_mult``, ``emb_mult``, ``head_mult``, ``ssm_in``,
    ``ssm_mults`` (five), ``ssm_out``, ``mlp_mults`` (two). ``at``: the
    positions to report (None: every one); ``states_at``: the positions
    AFTER which every layer's state and tail are handed out; ``pad``:
    pad the history to this many positions at least (one compiled
    program for histories of several lengths). Returns, at the
    positions in ``at``'s order: ``scores [A, items]``, ``layers [L, A,
    D]`` (the residual stream after every layer), ``att`` / ``ssm`` ``[L,
    A, D]`` (the two branches' outputs), ``mid [L, A, D]`` (the stream
    behind their add), ``k`` / ``v`` ``[L, A, kv_width]``, with ``rows``
    also ``k_all`` / ``v_all`` ``[L, n, kv_width]`` (every position's
    rows), and ``states``: ``{position: {"state": [L, heads, P, N],
    "tail": [L, K - 1, C]}}``."""
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    at = list(range(n)) if at is None else [int(p) for p in at]
    knobs = knobs_of(control, stale_at)
    ahead = int(knobs[8])
    asked = sorted({int(s) for s in states_at})
    if ahead and asked and asked[-1] + 1 >= n:
        raise ValueError("slot_ahead needs an event behind the last state")
    snaps = [s + ahead for s in asked]
    L = int(cfg["n_layers"])
    q_block = min(q_block, _up(n, 8))
    s_block = _up(min(s_block, _up(n, q_block)), q_block)
    T = _up(max(n, int(pad)), s_block)
    a_pos = jnp.asarray(at, jnp.int32)
    key = _cfg_key(cfg)
    kept: Dict[str, list] = {k: [] for k in (
        "x", "att", "ssm", "mid", "k", "v", "k_all", "v_all")}
    states = {s: {"state": [], "tail": []} for s in asked}
    with jax.default_matmul_precision("highest"):
        x = jnp.zeros((T, theta["item_emb"].shape[1]), jnp.float32).at[
            :n].set(_f32(jnp.take(theta["item_emb"], jnp.asarray(ids),
                                  axis=0)) * cfg["emb_mult"])
        for i in range(L):
            p = layer_params(theta, i)
            att, k_rows, v_rows = _jit("attn_layer", key, q_block=q_block)(
                p, x, jnp.asarray(knobs))
            ssm, got = _ssm_layer(p, x, n, cfg, knobs, snaps, s_block)
            for s in asked:
                states[s]["state"].append(got[s + ahead][0])
                states[s]["tail"].append(got[s + ahead][1])
            x = x + att + ssm
            for name, val in (("att", att), ("ssm", ssm), ("mid", x),
                              ("k", k_rows), ("v", v_rows)):
                kept[name].append(val[a_pos])
            if rows:
                kept["k_all"].append(k_rows[:n])
                kept["v_all"].append(v_rows[:n])
            x = _jit("mlp_layer", key, q_block=q_block)(p, x)
            kept["x"].append(x[a_pos])
        hq = rms(x[a_pos], theta["ln_f_g"], cfg["norm_eps"])
        scores = _mm(hq, _f32(theta["out_emb"][:int(cfg["n_items"])]).T) \
            * (1.0 if knobs[9] else cfg["head_mult"])
    out = {k: np.asarray(jnp.stack(v)) for k, v in kept.items() if v}
    return {"scores": np.asarray(scores), "layers": out["x"],
            "att": out["att"], "ssm": out["ssm"], "mid": out["mid"],
            "k": out["k"], "v": out["v"], "k_all": out.get("k_all"),
            "v_all": out.get("v_all"),
            "states": {s: {k: np.stack(v) for k, v in d.items()}
                       for s, d in states.items()}}
